"""Answering PCR queries with the TDR index (paper §V, Alg. 2) — batched.

The paper's Alg. 2 interleaves pruning with a DFS.  Here the same logic is a
**planner/executor split**, both halves batched over the whole query set and
running end-to-end on packed uint32 words through ``repro.core.engine``:

Planner — ``compile_queries`` flattens DNF terms into a fully vectorized
``QueryPlan``: packed required/forbidden label-slot planes, packed raw
forbidden-label rows, and padded required-label ids.  No per-edge or
per-vertex host arrays — everything edge-indexed is derived on device by
the executor via label gathers (no ``elab == l`` Python scans, no
``[Q, E]`` host-side dense masks).  Per-pattern rows are cached on the
index keyed by the hash-consed canonical pattern (``pattern_rows``), so
repeated query shapes skip DNF expansion and plane scatters; callers that
manage their own plans and job-axis padding (``repro.launch.serve``) use
``answer_plan`` directly.

Phase 1 — *filter cascade* (pure index math, no traversal):
  * ``u == v``            -> TRUE iff the term requires no labels
  * ``bits(v) ⊄ N_out(u)``-> FALSE   (paper: VertexReach)
  * ``bits(u) ⊄ N_in(v)`` -> FALSE   (paper: VertexReach, reverse)
  * interval ancestor + unconstrained term -> TRUE (paper: early stopping)
  * per-way group pruning via ``kernels.ops.filter_ways`` (the fused
    Pallas cascade on TPU / ref oracle elsewhere); no surviving way -> FALSE
  * everything else -> UNKNOWN, goes to phase 2.

Phase 2 — *corridor-compacted bidirectional expansion* for survivors only.
The paper's two-dimensional filters confine any u→v path to the Bloom
corridor ``V_out(u) ∩ V_in(v)``; the executor turns that pruning into a
*compute* restriction, not just an output mask:

  * **Compaction** — per job chunk (32 queries wide by default), the
    corridor rows are unioned into an active vertex set, renumbered
    into an induced subgraph (edge lists / padded-incidence gather
    matrices for the segment backend, packed per-label-class
    sub-adjacency bit-matrices for the ``pallas`` backend).  ``|V'|``
    and ``|E'|`` are padded to ``{2^k, 3·2^(k-1)}`` buckets so jit
    shapes stay stable and recompiles stay bounded; when the corridor
    is near-total the chunk runs on cached full-graph operands instead
    (corridor mask built on device, no host membership round-trip).
  * **Bidirectional meet-in-the-middle** — a forward frontier of
    seen-subset states expands from ``u`` while a backward frontier of
    states co-reachable to ``v`` expands from ``v``, both as ``[V', Q]``
    packed state-subset bitfields (bit s of word (x, q) == "query q can
    stand at x having seen required-subset s" / "can reach v collecting
    s").  A query finishes as soon as some vertex holds forward state s₁
    and backward state s₂ with ``s₁ | s₂ == full_mask`` — roughly half
    the rounds of one-directional expansion.  Finished queries' columns
    are frozen by a per-query done mask, and the fixpoint's ``changed``
    flag falls out of the round's own new-bit computation (``upd & ~f``)
    instead of a second full-frontier compare.
  * One round is a packed gather + per-edge constant-mask-shift subset
    transition + OR-reduction over padded in/out-incidence (segment
    backend), or one ``kernels.bitset_matmul`` per label class per
    direction (``pallas`` backend).

The expansion is the same boolean-semiring product the index build uses
and the corridor is sound (every vertex of a u→v path lies in it), so
answers stay exact: property tests assert bit-equality with the DFS
oracle and with the retained PR-1 full-graph executor (``exact_mode=
"legacy"``).  Chunks are dispatched without host syncs and collected
once at the end; ``QueryStats`` fetches round counters lazily.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import warnings
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import bitset
from . import engine as engine_mod
from . import graph as graph_mod
from . import pattern as pat
from . import rpq as rpq_mod
from . import dfs_baseline as dfs_mod
from .semiring import COUNT_CAP, DIST16
from .tdr_build import TDRIndex, _null_words
from ..utils import spans

FALSE, TRUE, UNKNOWN = 0, 1, 2

_FULL = jnp.uint32(0xFFFFFFFF)
# largest per-edge RPQ transition table ([E', J, q_u] uint32) gathered once
# per query; larger tables are re-gathered per NFA state in each push
_RPQ_TABLE_BYTES = 1 << 28

EXACT_MODES = ("auto", "compact", "full", "legacy")

#: query kinds the planner emits (one per query): boolean reachability,
#: shortest pattern-constrained hop distance, an actual witness path,
#: bounded label-distinct route counting, and regular path queries.
#: ``answer_plan`` serves "bool"; the semiring executors at the bottom of
#: this module serve dist/witness/count; "rpq" queries carry a
#: ``repro.core.rpq`` AST instead of a pattern and run ``rpq_batch``.
QUERY_KINDS = ("bool", "dist", "witness", "count", "rpq")


# ------------------------------------------------------------------ plans
@dataclasses.dataclass
class QueryPlan:
    """Planner output: one flattened DNF-term job per row, packed planes.

    ``req_w``/``forb_w`` are label-*slot* planes (the index's Bloom space,
    used by the filter cascade); ``forb_raw_w`` is packed over raw label
    ids — the executor's edge-forbid test must be exact, and slot hashing
    may collide when ``n_labels > lab_slots``.
    """
    qid: np.ndarray         # int32 [J] query id (-1 = padding row)
    u: np.ndarray           # int32 [J]
    v: np.ndarray           # int32 [J]
    req_w: np.ndarray       # uint32 [J, Wl]   required label-slot plane
    forb_w: np.ndarray      # uint32 [J, Wl]   forbidden label-slot plane
    forb_raw_w: np.ndarray  # uint32 [J, WL]   raw forbidden labels (packed)
    req_labels: np.ndarray  # int32 [J, max_m] raw required ids, -1 padded
    full_mask: np.ndarray   # int32 [J]        target subset state
    n_queries: int
    max_m: int
    # per-*query* kind (one of QUERY_KINDS); () means all-"bool".  Kinds
    # ride on the plan so mixed batches partition once, at the driver.
    kinds: tuple = ()

    @property
    def n_jobs(self) -> int:
        return int(self.qid.shape[0])

    def pad_to(self, jp: int) -> "QueryPlan":
        """Pad the job axis (padding rows: qid=-1 self-queries, empty
        pattern -> TRUE in the cascade but never landing in answers)."""
        j = self.n_jobs
        if jp <= j:
            return self
        p = jp - j

        def zrows(a):
            return np.concatenate(
                [a, np.zeros((p,) + a.shape[1:], dtype=a.dtype)])

        return QueryPlan(
            qid=np.concatenate([self.qid, np.full(p, -1, np.int32)]),
            u=zrows(self.u), v=zrows(self.v),
            req_w=zrows(self.req_w), forb_w=zrows(self.forb_w),
            forb_raw_w=zrows(self.forb_raw_w),
            req_labels=np.concatenate(
                [self.req_labels, np.full((p, self.max_m), -1, np.int32)]),
            full_mask=zrows(self.full_mask),
            n_queries=self.n_queries, max_m=self.max_m, kinds=self.kinds)


#: unread phase-2 round counters a ``QueryStats`` holds before it reads
#: them all at once (a long-lived server never reads ``exact_rounds``)
_ROUND_PARTS_CAP = 256


@dataclasses.dataclass
class QueryStats:
    n_queries: int = 0
    n_jobs: int = 0
    filter_false: int = 0
    filter_true: int = 0
    exact_jobs: int = 0
    plan_lookups: int = 0      # pattern-plan cache probes (compile_queries)
    plan_misses: int = 0       # ... that had to run DNF + plane scatters
    # query ids that reached phase 2 in the last answer_plan call (the
    # serving warmup uses these as expansion-compiling probe queries)
    exact_qids: list = dataclasses.field(default_factory=list, repr=False)
    corridor_active: int = 0   # Σ |V'| over dispatched phase-2 chunks
    corridor_total: int = 0    # Σ |V|  over dispatched phase-2 chunks
    saturated_chunks: int = 0  # chunks whose probe the summaries answered
    # wall time of the ``query.plan``, ``query.phase1`` and ``query.phase2``
    # spans (``repro.utils.spans``): plan compile, the filter cascade, and
    # exact expansion from dispatch to collected answers
    plan_s: float = 0.0
    phase1_s: float = 0.0
    phase2_s: float = 0.0
    exact_chunks: int = 0      # phase-2 chunks run (each adds its rounds)
    edge_chunks: int = 0       # ... of them expanded by lane_matmul_edges
    # rounds of the chunks already read, plus the device round counters
    # not yet read: those are fetched on .exact_rounds access (or once
    # _ROUND_PARTS_CAP pile up), so dispatching never waits on them
    _rounds: int = dataclasses.field(default=0, repr=False)
    _round_parts: list = dataclasses.field(default_factory=list, repr=False)

    def add_chunk(self, rounds) -> None:
        """Count one phase-2 chunk and its rounds (a device scalar whose
        chunk the caller has already collected, so reading it waits on no
        computation)."""
        self.exact_chunks += 1
        self._round_parts.append(rounds)
        if len(self._round_parts) >= _ROUND_PARTS_CAP:
            self._fold_rounds()

    def _fold_rounds(self) -> None:
        parts, self._round_parts = self._round_parts, []
        self._rounds += int(sum(jax.device_get(parts)))

    @property
    def exact_rounds(self) -> int:
        self._fold_rounds()
        return self._rounds

    @property
    def corridor_occupancy(self) -> float:
        """Mean |V'|/|V| over phase-2 chunks (1.0 when nothing compacted)."""
        if not self.corridor_total:
            return 1.0
        return self.corridor_active / self.corridor_total


class PatternRows(NamedTuple):
    """Per-pattern compiled plan rows (one row per DNF term) — everything
    in a ``QueryPlan`` that does not depend on the endpoints, so one cache
    entry serves every (u, v) pair asking the same composite pattern."""
    req_w: np.ndarray       # uint32 [T, Wl]
    forb_w: np.ndarray      # uint32 [T, Wl]
    forb_raw_w: np.ndarray  # uint32 [T, WL]
    req_labels: np.ndarray  # int32 [T, max_m]
    full_mask: np.ndarray   # int32 [T]

    @property
    def n_terms(self) -> int:
        return int(self.full_mask.shape[0])


PLAN_CACHE_CAP = 4096   # canonical patterns retained per index

# guards the per-index plan-cache dicts: the serving layer resolves
# patterns from many client threads concurrently with the scheduler
# thread, and the LRU pop/reinsert refresh is not atomic under the GIL
_plan_cache_lock = threading.Lock()


def _compile_pattern_rows(index: TDRIndex, p: pat.Pattern,
                          max_m: int) -> PatternRows:
    """Compile one pattern's DNF terms into packed plan rows."""
    cfg = index.cfg
    wl = bitset.n_words(cfg.lab_bits)
    wraw = bitset.n_words(max(index.graph.n_labels, 1))
    terms = pat.to_dnf(p)
    t_n = len(terms)
    req_w = np.zeros((t_n, wl), dtype=np.uint32)
    forb_w = np.zeros((t_n, wl), dtype=np.uint32)
    forb_raw_w = np.zeros((t_n, wraw), dtype=np.uint32)
    req_labels = np.full((t_n, max_m), -1, dtype=np.int32)
    full_mask = np.zeros(t_n, dtype=np.int32)
    req_j, req_l, forb_j, forb_l = [], [], [], []
    for j, term in enumerate(terms):
        if len(term.require) > max_m:
            raise ValueError(
                f"term with {len(term.require)} required labels exceeds "
                f"max_m={max_m}; decompose the pattern")
        rl = sorted(term.require)
        req_j += [j] * len(rl); req_l += rl
        forb_j += [j] * len(term.forbid); forb_l += sorted(term.forbid)
        req_labels[j, :len(rl)] = rl
        full_mask[j] = (1 << len(rl)) - 1
    if req_j:
        rj = np.asarray(req_j); rl = np.asarray(req_l, np.int64)
        bitset.set_bits_np(req_w, (rj,), index.lab_slot[rl])
    if forb_j:
        fj = np.asarray(forb_j); fl = np.asarray(forb_l, np.int64)
        bitset.set_bits_np(forb_w, (fj,), index.lab_slot[fl])
        bitset.set_bits_np(forb_raw_w, (fj,), fl)
    return PatternRows(req_w, forb_w, forb_raw_w, req_labels, full_mask)


def pattern_rows(index: TDRIndex, p: pat.Pattern, max_m: int = 4,
                 stats: "QueryStats | None" = None,
                 kind: str = "bool") -> PatternRows:
    """Cached plan rows for one pattern (hash-consed canonical key).

    The cache lives on the index (rows bake in ``lab_slot`` and the label
    word widths) and is a bounded LRU, so steady query traffic with
    repeated composite patterns skips DNF expansion and plane construction
    entirely — the serving layer leans on this for its plan cache.
    ``stats`` counts the lookup (and the miss, if any) exactly.  ``kind``
    partitions the LRU per query kind: the row *content* is
    kind-independent, but a shared entry must never let one kind's
    eviction/refresh pattern alias another's (the serving layer keys its
    result cache the same way)."""
    key = (pat.canonical_key(p), max_m, kind)
    if stats is not None:
        stats.plan_lookups += 1
    with _plan_cache_lock:
        cache = getattr(index, "_plan_cache", None)
        if cache is None:
            cache = {}
            index._plan_cache = cache
        rows = cache.get(key)
        if rows is not None:
            cache[key] = cache.pop(key)     # refresh LRU position
            return rows
    if stats is not None:
        stats.plan_misses += 1
    # DNF expansion + plane scatters run outside the lock (a slow first
    # compile of one pattern must not stall every other submitter)
    rows = _compile_pattern_rows(index, pat.canonicalize(p), max_m)
    with _plan_cache_lock:
        while len(cache) >= PLAN_CACHE_CAP:
            cache.pop(next(iter(cache)))
        cache[key] = rows
    return rows


def compile_queries(index: TDRIndex,
                    queries: Sequence[tuple[int, int, pat.Pattern]],
                    max_m: int = 4,
                    stats: "QueryStats | None" = None) -> QueryPlan:
    """Compile (u, v, pattern[, kind]) tuples into a ``QueryPlan``.

    Per-pattern rows come from the hash-consed plan cache
    (``pattern_rows``); only the endpoint columns and query-id row map are
    assembled fresh, so batches dominated by repeated patterns plan in
    O(n_queries) numpy concatenation.  The optional fourth element is one
    of ``QUERY_KINDS`` (default "bool"); it does not change the plan rows,
    only which executor the driver routes the query to."""
    with spans.span("query.plan", stats, "plan_s"):
        cfg = index.cfg
        wl = bitset.n_words(cfg.lab_bits)
        wraw = bitset.n_words(max(index.graph.n_labels, 1))
        kinds = []
        norm = []
        for q in queries:
            kind = q[3] if len(q) > 3 else "bool"
            if kind not in QUERY_KINDS:
                raise ValueError(
                    f"unknown query kind {kind!r}; expected one of "
                    f"{QUERY_KINDS}")
            if kind == "rpq":
                raise ValueError(
                    "kind='rpq' queries carry a repro.core.rpq AST, not a "
                    "pattern; route them through rpq_batch / answer_mixed")
            kinds.append(kind)
            norm.append((q[0], q[1], q[2]))
        queries = norm
        rows_per_q = [pattern_rows(index, p, max_m, stats=stats)
                      for (_, _, p) in queries]
        counts = np.asarray([r.n_terms for r in rows_per_q], dtype=np.int64)

        def cat(name, empty_cols):
            parts = [getattr(r, name) for r in rows_per_q if r.n_terms]
            if not parts:
                dt = np.int32 if name in ("req_labels", "full_mask") else \
                    np.uint32
                shape = (0,) if name == "full_mask" else (0, empty_cols)
                return np.zeros(shape, dtype=dt)
            return np.concatenate(parts)

        uv = np.asarray([(u, v) for (u, v, _) in queries],
                        dtype=np.int32).reshape(len(queries), 2)
        qid = np.repeat(np.arange(len(queries), dtype=np.int32), counts)
        return QueryPlan(
            qid=qid,
            u=np.repeat(uv[:, 0], counts),
            v=np.repeat(uv[:, 1], counts),
            req_w=cat("req_w", wl), forb_w=cat("forb_w", wl),
            forb_raw_w=cat("forb_raw_w", wraw),
            req_labels=cat("req_labels", max_m),
            full_mask=cat("full_mask", 0),
            n_queries=len(queries), max_m=max_m,
            kinds=tuple(kinds) if any(k != "bool" for k in kinds) else ())


# ----------------------------------------------------------- phase 1 (jit)
@functools.partial(jax.jit, static_argnames=("k", "mode"))
def _filter_cascade(u, v, req_w, forb_w, null_w,
                    vtx_packed, h_vtx, h_lab, v_vtx, v_lab,
                    n_out, n_in, sat_out, sat_in, push, pop,
                    *, k: int, mode: str):
    """Vectorised filter cascade -> verdict [J] in {FALSE, TRUE, UNKNOWN}.

    All label planes arrive packed; the per-way group predicate runs through
    ``kernels.ops.filter_ways`` (fused Pallas kernel / ref oracle).

    ``sat_out``/``sat_in`` are the level-1 row summaries of the compressed
    ``N_out``/``N_in`` planes (bool [V]): an ALL_ONE row contains every
    Bloom pattern, so its membership test is answered by the summary bit —
    bit-identical by construction, and on saturated traffic the word-level
    containment scan contributes nothing."""
    from repro.kernels import ops  # deferred: kernels import repro.core

    vbits = vtx_packed[v]            # [J, Wv]
    ubits = vtx_packed[u]

    req_empty = jnp.all(req_w == 0, axis=-1)
    forb_empty = jnp.all(forb_w == 0, axis=-1)

    # u == v: empty path
    same = u == v
    true_same = same & req_empty

    # global membership filters (sound negatives); summary-first: a
    # saturated row answers TRUE without the word-level containment
    topo_out = sat_out[u] | bitset.words_contain(n_out[u], vbits)
    topo_in = sat_in[v] | bitset.words_contain(n_in[v], ubits)
    topo_maybe = topo_out & topo_in

    # interval: DFS-forest ancestor => topologically reachable (sound positive)
    anc = (push[u] < push[v]) & (pop[v] < pop[u])
    true_anc = anc & req_empty & forb_empty & ~same

    # ---- per-way group pruning (fused kernel) ----
    way_ok = ops.filter_ways(h_vtx[u], h_lab[u], v_vtx[u], v_lab[u],
                             vbits, req_w, forb_w, null_w, mode=mode)
    any_way = jnp.any(way_ok, axis=-1)

    maybe = topo_maybe & (any_way | same)
    verdict = jnp.where(true_same | true_anc, TRUE,
                        jnp.where(maybe, UNKNOWN, FALSE))
    # u==v with required labels: no path; it's FALSE only if no self-loop
    # cycle can satisfy -- conservative: keep UNKNOWN path for same-vertex
    # queries with labels (cycles through u can satisfy the pattern).
    verdict = jnp.where(same & ~req_empty,
                        jnp.where(any_way, UNKNOWN, FALSE), verdict)
    return verdict


# ----------------------------------------------------------- phase 2 (jit)
def _state_has_masks(n_states: int, max_m: int) -> np.ndarray:
    """HAS[i] = packed mask of subset-states whose bit i is set."""
    has = np.zeros(max(max_m, 1), dtype=np.uint32)
    for i in range(max_m):
        for s in range(n_states):
            if (s >> i) & 1:
                has[i] |= np.uint32(1) << np.uint32(s)
    return has


def _sup_table(n_states: int) -> np.ndarray:
    """SUP[t] = packed mask of subset-states s with ``s ⊇ t``."""
    sup = np.zeros(n_states, dtype=np.uint32)
    for t in range(n_states):
        for s in range(n_states):
            if s & t == t:
                sup[t] |= np.uint32(1) << np.uint32(s)
    return sup


def _corridor_mask(u, v, n_out_u, n_in_v, vtx_packed):
    """Packed Bloom corridor ``V_out(u) ∩ V_in(v)`` as a [V, Q] word mask
    (all-ones where vertex x may lie on a u→v path)."""
    q_n = u.shape[0]
    cor = (bitset.words_contain(n_out_u[:, None, :], vtx_packed[None, :, :]) &
           bitset.words_contain(n_in_v[:, None, :], vtx_packed[None, :, :]))
    cor = cor.at[jnp.arange(q_n), v].set(True)
    cor = cor.at[jnp.arange(q_n), u].set(True)
    return bitset.full_words_where(cor.T)                # [V, Q]


class PlanDevice(NamedTuple):
    """Device-resident mirror of the plan's job-axis arrays — transferred
    once per batch; chunks ship only their job-id rows and gather in-jit.
    (A NamedTuple so jit treats it as a pytree of arrays.)"""
    u: Any
    v: Any
    req_labels: Any
    forb_raw_w: Any
    full_mask: Any


@jax.jit
def _corridor_member(jobs, plan_u, plan_v, n_out, n_in, vtx_packed):
    """Corridor membership bool [J, V] (endpoints always members)."""
    u, v = plan_u[jobs], plan_v[jobs]
    mem = (bitset.words_contain(n_out[u][:, None, :], vtx_packed[None, :, :])
           & bitset.words_contain(n_in[v][:, None, :],
                                  vtx_packed[None, :, :]))
    iota = jnp.arange(u.shape[0])
    return mem.at[iota, v].set(True).at[iota, u].set(True)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _corridor_chunk_counts(jobs, plan_u, plan_v, n_out, n_in, vtx_packed,
                           *, chunk: int):
    """Exact per-chunk corridor-*union* size int32 [J/chunk] (the
    compaction probe: one tiny transfer instead of shipping [J, V]
    membership to the host; per-job sums would badly over-estimate the
    union when corridors overlap)."""
    mem = _corridor_member(jobs, plan_u, plan_v, n_out, n_in, vtx_packed)
    union = mem.reshape(-1, chunk, mem.shape[1]).any(axis=1)
    return union.sum(axis=1, dtype=jnp.int32)


def _transition(val, has, sh):
    """Apply subset transition ``s -> s | m`` to packed state bitfields.

    ``has`` masks the state bits whose subset already contains the edge's
    required label (they stay); the rest shift up by ``sh = 2^i`` (setting
    bit i of the subset index).  ``has = ~0, sh = 0`` is the identity."""
    return (val & has) | ((val & ~has) << sh)


def _edge_state_masks(lab, req_labels, forb_raw_w, n_states: int, max_m: int,
                      neutral=None):
    """Per-(edge|class, query) transition operands ``(allow, has, sh)``.

    ``lab`` is the per-edge (or per-label-class) raw label id; ``neutral``
    marks class rows that merge all labels special for nobody (always
    allowed, identity transition).  The forbid test reads the *raw* packed
    forbidden rows — slot hashing may collide and the exact phase must not
    over-forbid."""
    q_n = req_labels.shape[0]
    labx = jnp.maximum(lab, 0)
    okbit = (forb_raw_w[:, labx >> 5] >>
             (labx & 31).astype(jnp.uint32)[None, :]) & 1       # [Q, E|C]
    allow_b = okbit == 0
    if neutral is not None:
        allow_b = neutral[None, :] | allow_b
    allow = bitset.full_words_where(allow_b).T                  # [E|C, Q]
    has_c = _state_has_masks(n_states, max_m)
    has = jnp.full((lab.shape[0], q_n), _FULL, jnp.uint32)
    sh = jnp.zeros((lab.shape[0], q_n), jnp.uint32)
    for i in range(max_m):  # static unroll; require-sets hold distinct labels
        match = req_labels[:, i][None, :] == lab[:, None]
        if neutral is not None:
            match = match & ~neutral[:, None]
        has = jnp.where(match, jnp.uint32(has_c[i]), has)
        sh = jnp.where(match, jnp.uint32(1 << i), sh)
    return allow, has, sh


def _sup_need(full_mask, n_states: int):
    """sup_need[s1, q] = packed mask of backward states completing s1 to
    ``full_mask[q]`` (s2 with ``s1 | s2 ⊇ full``)."""
    sup = jnp.asarray(_sup_table(n_states))
    rows = [sup[full_mask & ((n_states - 1) & ~s1)]
            for s1 in range(n_states)]
    return jnp.stack(rows)                                      # [S, Q]


def _meet(f, b, sup_need):
    """done[q] = ∃ vertex x, states s1 ∈ f[x,q], s2 ∈ b[x,q] with
    ``s1 | s2 == full_mask[q]`` (the bidirectional termination test)."""
    n_states = sup_need.shape[0]
    shifts = jnp.arange(n_states, dtype=jnp.uint32)
    fb = (f[None, :, :] >> shifts[:, None, None]) & jnp.uint32(1)  # [S,V,Q]
    hit = (b[None, :, :] & sup_need[:, None, :]) != 0
    return jnp.any((fb != 0) & hit, axis=(0, 1))


def _bidi_loop(f0, b0, push_f, push_b, cor_w, sup_need, max_rounds: int):
    """Alternating bidirectional fixpoint.  One iteration = one forward +
    one backward expansion; a query's columns freeze once it meets, and
    ``changed`` is derived from the rounds' own new bits (``upd & ~f``) —
    no second full-frontier compare."""

    def cond(st):
        _, _, done, (cf, cb), it = st
        return (cf | cb) & ~jnp.all(done) & (it < max_rounds)

    def body(st):
        f, b, done, (cf, cb), it = st
        mask = cor_w & bitset.full_words_where(~done)[None, :]
        # a direction whose last push added nothing is at its fixpoint
        # (monotone, and the live mask only shrinks) — skip its push
        new_f = jax.lax.cond(cf, lambda a: push_f(a) & mask & ~a,
                             jnp.zeros_like, f)
        f = f | new_f
        new_b = jax.lax.cond(cb, lambda a: push_b(a) & mask & ~a,
                             jnp.zeros_like, b)
        b = b | new_b
        done = done | _meet(f, b, sup_need)
        return (f, b, done,
                (jnp.any(new_f != 0), jnp.any(new_b != 0)), it + 1)

    st0 = (f0, b0, _meet(f0, b0, sup_need),
           (jnp.bool_(True), jnp.bool_(True)), jnp.int32(0))
    _, _, done, _, rounds = jax.lax.while_loop(cond, body, st0)
    return done, rounds


def _bidi_segment_core(su, sv, req_labels, forb_raw_w, full_mask, cor_w,
                       sub_lab, sub_src, sub_dst, ids_in, ids_out,
                       n_states: int, max_m: int, max_rounds: int,
                       chunk_words: int):
    """Segment-backend bidirectional fixpoint over a (sub)graph's edge
    lists.  ``ids_in`` / ``ids_out`` are padded incidence gather matrices
    (edge ids grouped by dst / src, ``E'`` = sentinel pointing at an
    appended zero row) — when they are ``None`` the OR-reduction falls
    back to packed segment reductions (hub-skewed graphs where padding
    would blow the cap)."""
    q_n = su.shape[0]
    v_p = cor_w.shape[0]
    allow, has, sh = _edge_state_masks(sub_lab, req_labels, forb_raw_w,
                                       n_states, max_m)
    sup_need = _sup_need(full_mask, n_states)
    iota = jnp.arange(q_n)
    f0 = jnp.zeros((v_p, q_n), jnp.uint32).at[su, iota].set(jnp.uint32(1))
    b0 = jnp.zeros((v_p, q_n), jnp.uint32).at[sv, iota].set(jnp.uint32(1))

    def reduce_cols(val, ids):
        # per-incidence-column gathers accumulate without the [V', D, Q]
        # transient a single 3D gather would materialize (3× faster on CPU)
        out = val[ids[:, 0]]
        for j in range(1, ids.shape[1]):  # static unroll over D columns
            out = out | val[ids[:, j]]
        return out

    def push(frontier, gather_idx, ids, scatter_idx):
        val = _transition(frontier[gather_idx] & allow, has, sh)  # [E', Q]
        if ids is None:
            return bitset.segment_or_words(val, scatter_idx,
                                           num_segments=v_p,
                                           chunk_words=chunk_words)
        val = jnp.concatenate(
            [val, jnp.zeros((1, q_n), jnp.uint32)], axis=0)
        for level in ids:   # 1 level, or virtual-row split on heavy tails
            val = reduce_cols(val, level)
        return val                                               # [V', Q]

    return _bidi_loop(
        f0, b0,
        lambda f: push(f, sub_src, ids_in, sub_dst),
        lambda b: push(b, sub_dst, ids_out, sub_src),
        cor_w, sup_need, max_rounds)


def _job_rows(jobs, dev: PlanDevice, m_eff: int):
    """Gather a chunk's plan rows on device (jobs is the only transfer)."""
    return (dev.req_labels[jobs][:, :m_eff], dev.forb_raw_w[jobs],
            dev.full_mask[jobs])


@functools.partial(jax.jit, static_argnames=("n_states", "max_m",
                                             "max_rounds", "chunk_words"))
def _expand_bidi(jobs, dev, su, sv, cor, sub_lab, sub_src, sub_dst,
                 ids_in, ids_out, *, n_states: int, max_m: int,
                 max_rounds: int, chunk_words: int):
    """Compacted-subgraph entry: ``cor`` is the per-query corridor
    membership bool [V', Q] extracted on the host during compaction;
    ``su``/``sv`` are the renumbered endpoints."""
    req_labels, forb_raw_w, full_mask = _job_rows(jobs, dev, max_m)
    return _bidi_segment_core(
        su, sv, req_labels, forb_raw_w, full_mask,
        bitset.full_words_where(cor), sub_lab, sub_src, sub_dst,
        ids_in, ids_out, n_states, max_m, max_rounds, chunk_words)


@functools.partial(jax.jit, static_argnames=("n_states", "max_m",
                                             "max_rounds", "chunk_words"))
def _expand_bidi_full(jobs, dev, n_out, n_in, vtx_packed, sub_lab,
                      sub_src, sub_dst, ids_in, ids_out, *, n_states: int,
                      max_m: int, max_rounds: int, chunk_words: int):
    """Full-graph entry for near-total corridors: endpoints and corridor
    mask both derived on device — no host membership round-trip."""
    req_labels, forb_raw_w, full_mask = _job_rows(jobs, dev, max_m)
    u, v = dev.u[jobs], dev.v[jobs]
    cor_w = _corridor_mask(u, v, n_out[u], n_in[v], vtx_packed)
    return _bidi_segment_core(
        u, v, req_labels, forb_raw_w, full_mask, cor_w, sub_lab,
        sub_src, sub_dst, ids_in, ids_out, n_states, max_m, max_rounds,
        chunk_words)


def _bidi_matmul_core(su, sv, edges_rev, edges_fwd, class_label,
                      req_labels, forb_raw_w, full_mask, cor_w,
                      n_states: int, max_m: int, max_rounds: int, mode: str):
    """Pallas-backend bidirectional fixpoint: one ``lane_matmul_edges``
    call per label class per direction per round, on the classes' edge
    lists ``(rows, cols, count)`` of ``engine.class_edge_lists_np``
    (forward frontier uses the reverse lists, backward the forward
    ones), so a round's work grows with the edges, not with V²."""
    q_n = su.shape[0]
    v_p = cor_w.shape[0]
    neutral = class_label < 0
    allow, has, sh = _edge_state_masks(class_label, req_labels, forb_raw_w,
                                       n_states, max_m, neutral=neutral)
    sup_need = _sup_need(full_mask, n_states)
    iota = jnp.arange(q_n)
    f0 = jnp.zeros((v_p, q_n), jnp.uint32).at[su, iota].set(jnp.uint32(1))
    b0 = jnp.zeros((v_p, q_n), jnp.uint32).at[sv, iota].set(jnp.uint32(1))

    def push(frontier, edge_set):
        # scan (not unroll) over label classes: one kernel call *site* per
        # direction keeps the while-loop body's XLA program small — an
        # unrolled 2·(C+1) pallas calls per round made compiles explode
        def body(upd, operand):
            edges_c, allow_c, has_c, sh_c = operand
            y = engine_mod._edge_rows(edges_c, frontier, mode)
            return upd | _transition(y & allow_c[None, :],
                                     has_c[None, :], sh_c[None, :]), None
        upd, _ = jax.lax.scan(body, jnp.zeros_like(frontier),
                              (edge_set, allow, has, sh))
        return upd

    return _bidi_loop(
        f0, b0,
        lambda f: push(f, edges_rev),
        lambda b: push(b, edges_fwd),
        cor_w, sup_need, max_rounds)


@functools.partial(jax.jit, static_argnames=("n_states", "max_m",
                                             "max_rounds", "mode"))
def _expand_bidi_matmul(jobs, dev, su, sv, edges_rev, edges_fwd,
                        class_label, cor, *, n_states: int, max_m: int,
                        max_rounds: int, mode: str):
    """Compacted-subgraph entry (``cor`` = membership bool [V', Q])."""
    req_labels, forb_raw_w, full_mask = _job_rows(jobs, dev, max_m)
    return _bidi_matmul_core(
        su, sv, edges_rev, edges_fwd, class_label, req_labels, forb_raw_w,
        full_mask, bitset.full_words_where(cor), n_states, max_m,
        max_rounds, mode)


@functools.partial(jax.jit, static_argnames=("n_states", "max_m",
                                             "max_rounds", "mode"))
def _expand_bidi_matmul_full(jobs, dev, edges_rev, edges_fwd, class_label,
                             n_out, n_in, vtx_packed, *, n_states: int,
                             max_m: int, max_rounds: int, mode: str):
    """Full-graph entry: corridor mask built on device from the Blooms."""
    req_labels, forb_raw_w, full_mask = _job_rows(jobs, dev, max_m)
    u, v = dev.u[jobs], dev.v[jobs]
    cor_w = _corridor_mask(u, v, n_out[u], n_in[v], vtx_packed)
    return _bidi_matmul_core(
        su=u, sv=v, edges_rev=edges_rev, edges_fwd=edges_fwd,
        class_label=class_label, req_labels=req_labels,
        forb_raw_w=forb_raw_w, full_mask=full_mask, cor_w=cor_w,
        n_states=n_states, max_m=max_m, max_rounds=max_rounds, mode=mode)


# ------------------------------------------------- legacy (PR-1) executors
def _expand_loop(f0, upd_of, v, full_mask, max_rounds):
    """One-directional fixpoint driver (retained full-V path): iterate
    until every query's target state bit is set, nothing changes, or
    ``max_rounds`` is hit.  Finished queries' columns freeze and the
    ``changed`` flag is derived from the round's own new bits."""
    q_n = v.shape[0]

    def done_of(f):
        return (f[v, jnp.arange(q_n)] >>
                full_mask.astype(jnp.uint32)) & 1 == 1

    def cond(state):
        _, done, changed, it = state
        return changed & ~jnp.all(done) & (it < max_rounds)

    def body(state):
        f, done, _, it = state
        live = bitset.full_words_where(~done)[None, :]
        new = upd_of(f) & ~f & live
        f = f | new
        return f, done | done_of(f), jnp.any(new != 0), it + 1

    st0 = (f0, done_of(f0), jnp.bool_(True), jnp.int32(0))
    f, done, _, rounds = jax.lax.while_loop(cond, body, st0)
    return done, rounds


@functools.partial(jax.jit, static_argnames=("v_n", "n_states", "max_m",
                                             "max_rounds", "chunk_words"))
def _expand_segment(u, v, req_labels, forb_raw_w, full_mask,
                    n_out_u, n_in_v, vtx_packed, elab, edge_src, edge_dst,
                    *, v_n: int, n_states: int, max_m: int, max_rounds: int,
                    chunk_words: int):
    """Legacy segment executor: full-graph frontier [V, Q]; one round =
    gather, per-edge transition, packed segment-OR scatter."""
    q_n = u.shape[0]
    cor_mask = _corridor_mask(u, v, n_out_u, n_in_v, vtx_packed)
    allow, has, sh = _edge_state_masks(elab, req_labels, forb_raw_w,
                                       n_states, max_m)

    f0 = jnp.zeros((v_n, q_n), jnp.uint32)
    f0 = f0.at[u, jnp.arange(q_n)].set(jnp.uint32(1))   # state ∅ at source

    def upd_of(f):
        val = _transition(f[edge_src] & allow, has, sh)         # [E, Q]
        upd = bitset.segment_or_words(val, edge_dst, num_segments=v_n,
                                      chunk_words=chunk_words)
        return upd & cor_mask

    return _expand_loop(f0, upd_of, v, full_mask, max_rounds)


@functools.partial(jax.jit, static_argnames=("n_states", "max_m",
                                             "max_rounds", "mode"))
def _expand_matmul(u, v, class_adj, class_label, req_labels, forb_raw_w,
                   full_mask, n_out_u, n_in_v, vtx_packed, *,
                   n_states: int, max_m: int, max_rounds: int, mode: str):
    """Legacy pallas executor: one ``bitset_matmul`` per label class per
    round on the packed full-graph reverse adjacency."""
    q_n = u.shape[0]
    cor_mask = _corridor_mask(u, v, n_out_u, n_in_v, vtx_packed)
    neutral = class_label < 0
    allow, has, sh = _edge_state_masks(class_label, req_labels, forb_raw_w,
                                       n_states, max_m, neutral=neutral)

    v_n = vtx_packed.shape[0]
    f0 = jnp.zeros((v_n, q_n), jnp.uint32)
    f0 = f0.at[u, jnp.arange(q_n)].set(jnp.uint32(1))

    def upd_of(f):
        upd = jnp.zeros_like(f)
        for c in range(class_adj.shape[0]):  # static unroll, C small
            y = engine_mod._matmul_rows(class_adj[c], f, mode)[:v_n]
            upd = upd | _transition(y & allow[c][None, :],
                                    has[c][None, :], sh[c][None, :])
        return upd & cor_mask

    return _expand_loop(f0, upd_of, v, full_mask, max_rounds)


# ---------------------------------------------------------------- executor
@dataclasses.dataclass
class ChunkResult:
    """Un-synced result of one dispatched chunk (device handles)."""
    jobs: np.ndarray        # padded job ids [Q]
    real_n: int
    reached: Any            # device (or host) bool [Q]
    rounds: Any             # device int32 scalar (or int)
    n_active: int = 0       # |V'| this chunk ran on
    v_total: int = 0        # |V| of the full graph
    edge_kernel: bool = False  # expanded by the edge-list class kernel


class ExactExecutor:
    """Persistent phase-2 executor bound to one (index, engine) pair.

    Holds the device-resident operands (edge lists, label rows, Blooms,
    cached full-graph incidence) plus host mirrors for per-chunk corridor
    compaction, and keeps the jitted expansion entry points warm across
    ``answer_batch`` calls.  Chunk shapes (|V'|, |E'|, incidence width)
    are padded to power-of-two buckets so recompiles stay bounded.
    ``dispatch_chunk`` never blocks: it returns device handles that the
    driver collects once all chunks are in flight."""

    # cap on the padded-incidence gather transient (bytes); beyond it the
    # round falls back to packed segment reductions (extreme hub skew)
    GATHER_BYTES_CAP = 1 << 28

    def __init__(self, index: TDRIndex, eng: "engine_mod.Engine"):
        self.index = index
        self.engine = eng
        g = index.graph
        self.elab = jnp.asarray(g.labels)
        self.src_np = g.src
        self.dst_np = np.asarray(g.indices)
        self.lab_np = np.asarray(g.labels)
        self._full_inc: tuple | None = None   # cached full-graph incidence

    def special_labels(self, plan: QueryPlan,
                       jobs: np.ndarray) -> tuple[int, ...]:
        """Labels some pending job requires or forbids (the matmul backend
        gets one adjacency class per special label + one neutral)."""
        req = plan.req_labels[jobs]
        spec = set(int(l) for l in req[req >= 0])
        forb = np.bitwise_or.reduce(plan.forb_raw_w[jobs], axis=0)
        bits = np.unpackbits(forb.astype("<u4").view(np.uint8),
                             bitorder="little")
        spec.update(np.flatnonzero(bits).tolist())
        return tuple(sorted(spec))

    def eff_states(self, plan: QueryPlan, jobs: np.ndarray,
                   pin_m: int | None = None) -> tuple[int, int]:
        """(m_eff, n_states) for the pending set: the widest require-set
        actually present, not the plan-level ``max_m`` cap.  ``pin_m``
        (serving) raises it to a fixed floor so steady traffic keeps one
        static state width per chunk shape instead of recompiling per
        batch composition."""
        m_eff = int((plan.req_labels[jobs] >= 0).sum(axis=1).max(initial=0))
        if pin_m is not None:
            m_eff = min(max(m_eff, pin_m), plan.max_m)
        return m_eff, 1 << m_eff

    # ------------------------------------------------------------ planning
    def _sliced_corridor(self, dev: PlanDevice, jobs: np.ndarray, fn,
                         out: np.ndarray) -> np.ndarray:
        """Run a per-job corridor jit over bounded-shape job slices."""
        idx = self.index
        p_n = len(jobs)
        step = 256
        for c0 in range(0, p_n, step):
            sl = jobs[c0:c0 + step]
            jp = graph_mod.pad_pow2(len(sl), lo=16)
            pj = np.concatenate(
                [sl, np.full(jp - len(sl), sl[0], sl.dtype)])
            res = np.asarray(fn(
                jnp.asarray(pj.astype(np.int32)), dev.u, dev.v,
                idx.n_out, idx.n_in, idx.vtx_packed))
            out[c0:c0 + step] = res[:len(sl)]
        return out

    def chunk_union_counts(self, dev: PlanDevice, jobs: np.ndarray,
                           chunk: int) -> np.ndarray:
        """Exact corridor-union size per ``chunk``-sized job group (the
        cheap compaction probe).  Tail groups are padded with their own
        first job so the union is not polluted across chunks."""
        idx = self.index
        starts = range(0, len(jobs), chunk)
        out = np.empty(len(starts), dtype=np.int32)
        step = max(chunk, (256 // chunk) * chunk)
        padded = []
        for c0 in starts:
            grp = jobs[c0:c0 + chunk]
            if len(grp) < chunk:
                grp = np.concatenate(
                    [grp, np.full(chunk - len(grp), grp[0], grp.dtype)])
            padded.append(grp)
        pj = np.concatenate(padded)
        for i0 in range(0, len(pj), step):
            sl = pj[i0:i0 + step]
            if len(sl) < step:   # pad with whole dummy chunks of sl[0]
                sl = np.concatenate(
                    [sl, np.full(step - len(sl), sl[0], sl.dtype)])
            res = np.asarray(_corridor_chunk_counts(
                jnp.asarray(sl.astype(np.int32)), dev.u, dev.v,
                idx.n_out, idx.n_in, idx.vtx_packed, chunk=chunk))
            n = min(len(res), len(out) - i0 // chunk)
            out[i0 // chunk:i0 // chunk + n] = res[:n]
        return out

    def corridor_members(self, dev: PlanDevice,
                         jobs: np.ndarray) -> np.ndarray:
        """Corridor membership bool [P, V] (fetched only for the jobs of
        chunks that will actually compact)."""
        return self._sliced_corridor(
            dev, jobs, _corridor_member,
            np.empty((len(jobs), self.index.graph.n_vertices), dtype=bool))

    # ------------------------------------------------------------ dispatch
    def dispatch_chunk(self, plan: QueryPlan, dev: PlanDevice | None,
                       jobs: np.ndarray,
                       member: np.ndarray | None, special: tuple[int, ...],
                       mode: str, pin_m: int | None = None) -> ChunkResult:
        """Dispatch one padded chunk of pending jobs -> ``ChunkResult``
        holding un-synced device handles."""
        if mode == "legacy":
            reached, rounds = self._run_legacy(plan, jobs, special)
            return ChunkResult(jobs, len(jobs), reached, rounds,
                               self.index.graph.n_vertices,
                               self.index.graph.n_vertices)
        return self._run_bidi(plan, dev, jobs, member, special, mode, pin_m)

    def _run_bidi(self, plan: QueryPlan, dev: PlanDevice,
                  jobs: np.ndarray,
                  member: np.ndarray | None, special: tuple[int, ...],
                  mode: str, pin_m: int | None = None) -> ChunkResult:
        """``member is None`` -> full-graph bidi (corridor built on
        device); else corridor compaction over the member rows."""
        idx, eng = self.index, self.engine
        g = idx.graph
        q_n = len(jobs)
        v_n = g.n_vertices
        m_eff, n_states = self.eff_states(plan, jobs, pin_m)
        if n_states > 32:
            raise ValueError(
                f"max_m={m_eff} needs {n_states} subset states; the packed "
                "executor holds at most 32 (max_m <= 5)")

        compacted = member is not None
        if compacted:
            active = member.any(axis=0)
            n_sub = int(active.sum())
            v_p = graph_mod.pad_bucket(n_sub, lo=32)
            if v_p >= v_n and mode == "auto":
                compacted = False   # probe over-estimated; run full
        if compacted:
            sub_ids, renum, s, d, l = graph_mod.induced_edges(
                g, active, src=self.src_np)
            if s.shape[0] == 0:
                # corridor holds no edges: only the empty path exists, and
                # phase 1 already answered those — nothing is reachable
                return ChunkResult(jobs, q_n, np.zeros(q_n, bool), 0,
                                   n_sub, v_n)
            cor = np.zeros((v_p, q_n), dtype=bool)
            cor[:n_sub] = member[:, sub_ids].T
            su = renum[plan.u[jobs]]
            sv = renum[plan.v[jobs]]
        else:
            # endpoints resolve on device (dev.u[jobs]) in the full path
            n_sub = v_p = v_n
            s, d, l = self.src_np, self.dst_np, self.lab_np

        max_rounds = v_p * n_states + 1
        jobs_j = jnp.asarray(jobs.astype(np.int32))

        use_matmul = eng.backend == "pallas"
        if use_matmul:
            kw = bitset.n_words(v_p)
            n_mats = 2 * (len(special) + 1)
            if n_mats * v_p * kw * 4 > eng.config.max_dense_bytes:
                warnings.warn(
                    f"engine: {n_mats} label-class adjacency matrices "
                    "exceed max_dense_bytes; expanding this chunk via the "
                    "segment path", stacklevel=3)
                use_matmul = False

        if use_matmul:
            class_label = jnp.asarray(np.asarray(special + (-1,), np.int32))
            if compacted:
                edges_rev, edges_fwd = (
                    tuple(jnp.asarray(a) for a in
                          engine_mod.class_edge_lists_np(
                              s, d, l, special, reverse=rev))
                    for rev in (True, False))
                reached, rounds = _expand_bidi_matmul(
                    jobs_j, dev, jnp.asarray(su), jnp.asarray(sv),
                    edges_rev, edges_fwd, class_label, jnp.asarray(cor),
                    n_states=n_states, max_m=m_eff, max_rounds=max_rounds,
                    mode=eng.matmul_mode)
            else:
                edges_rev = eng.label_class_edges(special, reverse=True)
                edges_fwd = eng.label_class_edges(special, reverse=False)
                reached, rounds = _expand_bidi_matmul_full(
                    jobs_j, dev, edges_rev, edges_fwd, class_label,
                    idx.n_out, idx.n_in, idx.vtx_packed, n_states=n_states,
                    max_m=m_eff, max_rounds=max_rounds,
                    mode=eng.matmul_mode)
            return ChunkResult(jobs, q_n, reached, rounds, n_sub, v_n,
                               edge_kernel=True)

        if compacted:
            e_real = s.shape[0]
            e_p = graph_mod.pad_bucket(e_real, lo=32)
            if e_p > e_real:   # bucket |E'|; padding rows duplicate edge 0
                rep = e_p - e_real
                s = np.concatenate([s, np.repeat(s[:1], rep)])
                d = np.concatenate([d, np.repeat(d[:1], rep)])
                l = np.concatenate([l, np.repeat(l[:1], rep)])
            ids_in = graph_mod.incidence_plan(d[:e_real], v_p, e_p)
            ids_out = graph_mod.incidence_plan(s[:e_real], v_p, e_p)
            lab_j, s_j, d_j = jnp.asarray(l), jnp.asarray(s), jnp.asarray(d)
            # extreme skew beyond what the virtual-row split absorbs:
            # over the cap, skip the device transfer and fall back to
            # packed segment reductions on the same edge arrays
            if (sum(a.size for a in ids_in + ids_out) * q_n * 4
                    > self.GATHER_BYTES_CAP):
                in_j = out_j = None
            else:
                in_j = tuple(jnp.asarray(a) for a in ids_in)
                out_j = tuple(jnp.asarray(a) for a in ids_out)
        else:
            lab_j, s_j, d_j, in_j, out_j = self._full_incidence()
            if (sum(a.size for a in in_j + out_j) * q_n * 4
                    > self.GATHER_BYTES_CAP):
                in_j = out_j = None
        kw = dict(n_states=n_states, max_m=m_eff, max_rounds=max_rounds,
                  chunk_words=eng.config.chunk_words)
        if compacted:
            reached, rounds = _expand_bidi(
                jobs_j, dev, jnp.asarray(su), jnp.asarray(sv),
                jnp.asarray(cor), lab_j, s_j, d_j, in_j, out_j, **kw)
        else:
            reached, rounds = _expand_bidi_full(
                jobs_j, dev, idx.n_out, idx.n_in, idx.vtx_packed,
                lab_j, s_j, d_j, in_j, out_j, **kw)
        return ChunkResult(jobs, q_n, reached, rounds, n_sub, v_n)

    def _full_incidence(self):
        """Cached full-graph operand tuple for near-total corridors."""
        if self._full_inc is None:
            g = self.index.graph
            e_n = g.n_edges
            ids_in = graph_mod.incidence_plan(self.dst_np, g.n_vertices,
                                              e_n)
            ids_out = graph_mod.incidence_plan(self.src_np, g.n_vertices,
                                               e_n)
            self._full_inc = (
                self.elab, self.engine.edge_src, self.engine.edge_dst,
                tuple(jnp.asarray(a) for a in ids_in),
                tuple(jnp.asarray(a) for a in ids_out))
        return self._full_inc

    def _run_legacy(self, plan: QueryPlan, jobs: np.ndarray,
                    special: tuple[int, ...]):
        """PR-1 one-directional full-graph expansion (kept as comparison
        oracle and ``exact_mode="legacy"``)."""
        idx, eng = self.index, self.engine
        g = idx.graph
        n_states = 1 << plan.max_m
        if n_states > 32:
            raise ValueError(
                f"max_m={plan.max_m} needs {n_states} subset states; the "
                "packed executor holds at most 32 (max_m <= 5)")
        max_rounds = g.n_vertices * n_states + 1
        uu = jnp.asarray(plan.u[jobs])
        vv = jnp.asarray(plan.v[jobs])
        req_labels = jnp.asarray(plan.req_labels[jobs])
        forb_raw_w = jnp.asarray(plan.forb_raw_w[jobs])
        full_mask = jnp.asarray(plan.full_mask[jobs])
        n_out_u, n_in_v = idx.n_out[uu], idx.n_in[vv]
        use_matmul = eng.backend == "pallas"
        if use_matmul and not eng.can_pack_dense(len(special) + 1):
            # the class-matrix set would blow the dense cap the engine
            # promised to respect — run this batch's rounds as packed
            # segment reductions instead (same bits, no dense operand)
            warnings.warn(
                f"engine: {len(special) + 1} label-class adjacency "
                "matrices exceed max_dense_bytes; expanding this batch "
                "via the segment path", stacklevel=3)
            use_matmul = False
        if use_matmul:
            class_adj = eng.label_class_adjacency(special)
            class_label = jnp.asarray(np.asarray(special + (-1,), np.int32))
            reached, rounds = _expand_matmul(
                uu, vv, class_adj, class_label, req_labels, forb_raw_w,
                full_mask, n_out_u, n_in_v, idx.vtx_packed,
                n_states=n_states, max_m=plan.max_m, max_rounds=max_rounds,
                mode=eng.matmul_mode)
        else:
            reached, rounds = _expand_segment(
                uu, vv, req_labels, forb_raw_w, full_mask, n_out_u, n_in_v,
                idx.vtx_packed, self.elab, eng.edge_src, eng.edge_dst,
                v_n=g.n_vertices, n_states=n_states, max_m=plan.max_m,
                max_rounds=max_rounds,
                chunk_words=eng.config.chunk_words)
        return reached, rounds


def _executor(index: TDRIndex, eng: "engine_mod.Engine") -> ExactExecutor:
    ex = getattr(eng, "_executor", None)
    if ex is None or ex.index is not index:
        ex = ExactExecutor(index, eng)
        eng._executor = ex
    return ex


# ----------------------------------------------------------------- driver
@functools.lru_cache(maxsize=8)
def _null_words_dev(cfg) -> jax.Array:
    """Device copy of the packed NULL plane (keyed by the frozen config)."""
    return jnp.asarray(_null_words(cfg))


def answer_batch(index: TDRIndex,
                 queries: Sequence[tuple[int, int, pat.Pattern]],
                 *, max_m: int = 4, exact_chunk: int = 32,
                 stats: QueryStats | None = None,
                 filters_only: bool = False,
                 backend: str | None = None,
                 exact_mode: str = "auto",
                 engine_config: "engine_mod.EngineConfig | None" = None,
                 mesh=None) -> np.ndarray:
    """Answer a batch of PCR queries.  Returns bool [n_queries].

    Compilation goes through the hash-consed pattern-plan cache
    (``pattern_rows``); answering is ``answer_plan`` — callers that manage
    their own plans and padding (the serving scheduler) use that entry
    point directly.
    """
    plan = compile_queries(index, queries, max_m=max_m, stats=stats)
    return answer_plan(index, plan, exact_chunk=exact_chunk, stats=stats,
                       filters_only=filters_only, backend=backend,
                       exact_mode=exact_mode, engine_config=engine_config,
                       mesh=mesh)


def answer_plan(index: TDRIndex, plan: QueryPlan,
                *, exact_chunk: int = 32,
                stats: QueryStats | None = None,
                filters_only: bool = False,
                backend: str | None = None,
                exact_mode: str = "auto",
                engine_config: "engine_mod.EngineConfig | None" = None,
                mesh=None,
                special_labels: Sequence[int] | None = None,
                pin_m: int | None = None,
                pad_lo: int = 16) -> np.ndarray:
    """Answer a compiled ``QueryPlan``.  Returns bool [plan.n_queries].

    ``backend``/``engine_config`` select the packed-word engine backend for
    phase 2 (and the kernel mode for phase 1); default follows the
    ``repro.core.engine`` contract.  ``exact_mode`` picks the phase-2
    executor: "auto" (bidirectional, corridor-compacted whenever the
    padded corridor bucket is smaller than V), "compact" (force
    compaction), "full" (bidirectional on the full graph), or "legacy"
    (the retained PR-1 one-directional executor).

    The job axis is padded onto the ``{2^k, 3·2^(k-1)}`` bucket grid
    (``graph.pad_bucket``, via ``QueryPlan.pad_to``; ``pad_lo`` is the
    grid floor — the serving scheduler passes its own so its warmed grid
    and live batches agree), so jit shapes under varying batch sizes stay
    on a logarithmic grid of variants.  The
    serving scheduler pre-compiles that grid and pins the two
    content-dependent statics — ``pin_m`` fixes the subset-state width,
    ``special_labels`` fixes the label-class set (it is unioned with the
    labels the batch actually needs, so a pin can widen but never break
    correctness) — which makes steady-state traffic recompile-free.

    ``mesh`` (a ``jax.sharding.Mesh``) distributes the batch: the phase-1
    cascade runs with the job axis sharded over every device
    (``repro.core.distributed.filter_cascade_sharded``; the index planes
    are broadcast, the plan rows are the only sharded traffic) and
    compacted phase-2 expansion chunks are round-robined across the
    mesh's devices — chunk dispatch never blocks, so devices expand
    concurrently, while full-graph chunks stay with the shared V-sized
    operands on the lead device.  Answers are bit-identical to the
    single-device path.
    """
    if plan.max_m > 5:
        raise ValueError(
            f"max_m={plan.max_m}: the packed executor holds subset states "
            "in one uint32 bitfield, so at most 5 required labels per term "
            "(32 states); decompose the pattern")
    if exact_mode not in EXACT_MODES:
        raise ValueError(f"unknown exact_mode {exact_mode!r}; expected one "
                         f"of {EXACT_MODES}")
    if any(k != "bool" for k in plan.kinds):
        raise ValueError(
            "answer_plan serves kind='bool' plans only; route mixed-kind "
            "batches through answer_mixed (or dist_batch / witness / "
            "count_routes directly)")
    stats = stats if stats is not None else QueryStats()
    with spans.span("query.phase1", stats, "phase1_s"):
        eng = index.engine(backend, engine_config)
        stats.n_queries += plan.n_queries
        stats.n_jobs += plan.n_jobs
        answers = np.zeros(plan.n_queries, dtype=bool)
        if plan.n_jobs == 0:
            return answers

        # pad the job axis onto the bucket grid so jit shapes stay stable
        # (and, under a mesh, further to a multiple of the device count)
        plan_p = plan.pad_to(graph_mod.pad_bucket(plan.n_jobs, lo=pad_lo))
        if mesh is not None:
            n_dev = mesh.devices.size
            plan_p = plan_p.pad_to(-(-plan_p.n_jobs // n_dev) * n_dev)
        pd_u, pd_v = jnp.asarray(plan_p.u), jnp.asarray(plan_p.v)
        if mesh is not None:
            # deferred: distributed imports this module back
            from . import distributed as dist_mod
            verdict = dist_mod.filter_cascade_sharded(index, plan_p, mesh,
                                                      eng.kernel_mode)
        else:
            sat_out_d, sat_in_d = index.summary_flags_dev()
            verdict = np.asarray(_filter_cascade(
                pd_u, pd_v,
                jnp.asarray(plan_p.req_w), jnp.asarray(plan_p.forb_w),
                _null_words_dev(index.cfg),
                index.vtx_packed, index.h_vtx, index.h_lab, index.v_vtx,
                index.v_lab, index.n_out, index.n_in, sat_out_d, sat_in_d,
                index.push, index.pop, k=index.cfg.k, mode=eng.kernel_mode))

        real = plan_p.qid >= 0
        stats.filter_false += int(((verdict == FALSE) & real).sum())
        stats.filter_true += int(((verdict == TRUE) & real).sum())
        np.logical_or.at(answers, plan_p.qid[(verdict == TRUE) & real],
                         True)

    pending = np.flatnonzero((verdict == UNKNOWN) & real)
    # jobs whose query is already TRUE need no exact work
    pending = pending[~answers[plan_p.qid[pending]]]
    if filters_only:
        # treat UNKNOWN as reachable (upper bound) -- used to measure the
        # cascade's pruning power in benchmarks
        np.logical_or.at(answers, plan_p.qid[pending], True)
        return answers
    stats.exact_jobs += len(pending)
    stats.exact_qids = np.unique(plan_p.qid[pending]).tolist()
    if len(pending) == 0:
        return answers

    with spans.span("query.phase2", stats, "phase2_s"):
        ex = _executor(index, eng)
        v_n = index.graph.n_vertices
        special = ex.special_labels(plan_p, pending)
        if special_labels is not None:
            # a serving pin fixes the label-class set (stable operand
            # shapes, resident adjacency cache); union keeps it sound if
            # traffic ever needs a label outside the pin
            special = tuple(sorted(set(int(l) for l in special_labels)
                                   | set(special)))
        dev = None
        if exact_mode != "legacy":
            dev = PlanDevice(pd_u, pd_v, jnp.asarray(plan_p.req_labels),
                             jnp.asarray(plan_p.forb_raw_w),
                             jnp.asarray(plan_p.full_mask))

        # chunk layout + compaction probe: per-job corridor sizes cost one
        # tiny device round-trip; full [P, V] membership is fetched only for
        # the jobs of chunks that will actually compact
        starts = list(range(0, len(pending), exact_chunk))
        if exact_mode == "legacy" or exact_mode == "full":
            compact_flags = [False] * len(starts)
        elif exact_mode == "compact":
            compact_flags = [True] * len(starts)
        else:
            # summary-first probe skip: a chunk whose every job has
            # ALL_ONE N_out[u] and N_in[v] rows (level-1 summaries of the
            # compressed planes) has corridor == full V *exactly*, so the
            # probe would always pick the full-graph path — settle those
            # chunks from the host flags and probe only the rest (whole
            # chunks, in order, so ``chunk_union_counts``'s sequential
            # grouping stays aligned)
            flags = index.summary_flags()
            jsat = (flags["sat_out"][plan_p.u[pending]]
                    & flags["sat_in"][plan_p.v[pending]])
            sat_chunks = [bool(jsat[c0:c0 + exact_chunk].all())
                          for c0 in starts]
            stats.saturated_chunks += sum(sat_chunks)
            compact_flags = [False] * len(starts)
            probe_starts = [c0 for c0, s in zip(starts, sat_chunks)
                            if not s]
            if probe_starts:
                probe_jobs = np.concatenate(
                    [pending[c0:c0 + exact_chunk] for c0 in probe_starts])
                unions = ex.chunk_union_counts(dev, probe_jobs,
                                               exact_chunk)
                for c0, u in zip(probe_starts, unions):
                    compact_flags[c0 // exact_chunk] = (
                        graph_mod.pad_bucket(int(u), lo=32) < v_n)
        member = None
        mem_off = {}
        if any(compact_flags):
            cjobs = np.concatenate(
                [pending[c0:c0 + exact_chunk]
                 for c0, flag in zip(starts, compact_flags) if flag])
            member = ex.corridor_members(dev, cjobs)
            off = 0
            for c0, flag in zip(starts, compact_flags):
                if flag:
                    n = len(pending[c0:c0 + exact_chunk])
                    mem_off[c0] = (off, off + n)
                    off += n

        # dispatch every chunk, then collect once — no per-chunk host sync.
        # Under a mesh, *compacted* chunks round-robin over its devices:
        # their operands (induced subgraph, membership rows) are per-chunk
        # host data that must transfer anyway, so spreading them is pure
        # concurrency (dispatch is async).  Full-graph chunks stay on the
        # lead device, where the V-sized shared operands (index planes,
        # cached incidence / class adjacency) already live —
        # round-robining those would re-ship the whole index every chunk.
        devices = list(mesh.devices.flat) if mesh is not None else [None]
        with spans.span("query.phase2.dispatch"):
            results = []
            rr = 0
            for c0, flag in zip(starts, compact_flags):
                jobs = pending[c0:c0 + exact_chunk]
                real_n = len(jobs)
                rows = member[slice(*mem_off[c0])] if flag else None
                if real_n < exact_chunk:   # pad to a stable jit shape
                    jobs = np.concatenate(
                        [jobs, np.full(exact_chunk - real_n, jobs[0],
                                       np.int64)])
                    if rows is not None:
                        rows = np.concatenate(
                            [rows, np.repeat(rows[:1],
                                             exact_chunk - real_n, axis=0)])
                dev_i = devices[0] if mesh is None or not flag \
                    else devices[rr % len(devices)]
                rr += flag
                if dev_i is None:
                    res = ex.dispatch_chunk(plan_p, dev, jobs, rows,
                                            special, exact_mode, pin_m)
                else:
                    with jax.default_device(dev_i):
                        res = ex.dispatch_chunk(plan_p, dev, jobs, rows,
                                                special, exact_mode, pin_m)
                res.real_n = real_n
                results.append(res)
        with spans.span("query.phase2.collect"):
            for res in results:
                reached = np.asarray(res.reached)[:res.real_n]
                hit = res.jobs[:res.real_n][reached]
                np.logical_or.at(answers, plan_p.qid[hit], True)
                stats.add_chunk(res.rounds)
                stats.edge_chunks += res.edge_kernel
                stats.corridor_active += res.n_active
                stats.corridor_total += res.v_total
    return answers


def answer(index: TDRIndex, u: int, v: int, p: pat.Pattern, **kw) -> bool:
    """Single-query convenience wrapper over ``answer_batch``."""
    return bool(answer_batch(index, [(u, v, p)], **kw)[0])


# ------------------------------------------- semiring query kinds (PR 8)
# The executors below answer the non-boolean QUERY_KINDS over the same
# corridor-compacted subgraphs phase 2 uses, but with a (min, +) distance
# DP ("dist"/"witness", uint16 lanes saturating at DIST_INF) or a
# saturating route-count DP ("count", uint32 lanes clamped at ``cap``)
# instead of the packed boolean closure.  Product-graph states are the
# same (vertex, seen-required-subset) pairs; the carrier is a dense
# [V', J, S] lane plane rather than one packed uint32 bitfield.
#
# Soundness of reusing the corridor: every vertex on a u→v walk is both
# reachable from u and co-reachable to v, so it lies in the true
# corridor, of which the Bloom corridor N_out(u) ∩ N_in(v) is a
# superset — compaction never cuts a path or a counted walk.

#: distance-plane INF (the uint16 carrier's saturation point)
DIST_INF = int(np.iinfo(np.uint16).max)

# int32 INF sentinel for the bidirectional meet arithmetic: large enough
# to dominate any real distance (<= DIST_INF - 1), small enough that
# sentinel + sentinel cannot wrap int32
_DBIG = 1 << 24


def _edge_dist_ops(lab, req_labels, forb_raw_w, max_m: int,
                   evalid=None, neutral=None):
    """Per-(job, edge|class) DP operands: ``allow`` bool [J, E] (edge
    usable for the job) and ``sh`` int32 [J, E] (the subset bit the edge's
    label sets, 0 if not required).  ``evalid`` masks bucket-padding edge
    rows — duplicated edges are harmless for the idempotent boolean
    closure but would double-count in the sum DP and must never relax a
    distance either.  ``neutral`` marks merged label-class rows (always
    allowed, no subset bit), as in ``_edge_state_masks``."""
    labx = jnp.maximum(lab, 0)
    okbit = (forb_raw_w[:, labx >> 5] >>
             (labx & 31).astype(jnp.uint32)[None, :]) & 1        # [J, E|C]
    allow = okbit == 0
    if neutral is not None:
        allow = allow | neutral[None, :]
    if evalid is not None:
        allow = allow & evalid[None, :]
    sh = jnp.zeros((req_labels.shape[0], lab.shape[0]), jnp.int32)
    for i in range(max_m):  # static unroll; require-sets hold distinct ids
        match = req_labels[:, i][:, None] == lab[None, :]
        if neutral is not None:
            match = match & ~neutral[None, :]
        sh = jnp.where(match, jnp.int32(1 << i), sh)
    return allow, sh


def _flip_states(rows, sh, n_states: int):
    """``rows[..., s ^ sh]`` along the trailing subset-state axis, where
    ``sh`` (broadcast against ``rows``) is 0 or one subset bit.  A static
    select per bit, not ``take_along_axis``: its int32 index plane has a
    minor dim of 1, which the TPU's (8, 128) tiling pads 32x — 16 GiB at
    E=2^20 edges × 32 jobs."""
    s_idx = np.arange(n_states)
    out = rows
    bit = 1
    while bit < n_states:
        out = jnp.where(sh == bit, rows[..., s_idx ^ bit], out)
        bit <<= 1
    return out


def _dist_meet(df, db, full_mask, best, n_states: int):
    """best[j] = min over vertices x and state pairs (s1, s2) with
    ``s1 | s2 == full_mask[j]`` of ``df[x,j,s1] + db[x,j,s2]`` — the
    distance analogue of the boolean ``_meet``: min over the corridor
    instead of an existence test."""
    dfi = jnp.where(df == DIST_INF, _DBIG, df.astype(jnp.int32))
    dbi = jnp.where(db == DIST_INF, _DBIG, db.astype(jnp.int32))
    s_idx = jnp.arange(n_states, dtype=jnp.int32)
    for s1 in range(n_states):  # static unroll, S <= 32
        valid = (jnp.int32(s1) | s_idx)[None, :] == full_mask[:, None]
        tot = dfi[:, :, s1][:, :, None] + dbi                   # [V', J, S]
        tot = jnp.where(valid[None, :, :], tot, _DBIG)
        best = jnp.minimum(best, tot.min(axis=(0, 2)))
    return best


def _dist_bidi_loop(df0, db0, push_f, push_b, full_mask, it_cap,
                    n_states: int, max_rounds: int):
    """Alternating bidirectional (min, +) fixpoint.  A job is done once
    its best meet value is <= 2·it: after ``it`` rounds each plane holds
    every product-distance <= it exactly, so any path of length
    L <= 2·it has already met — the best is provably final.  ``it_cap``
    is *traced* (k-hop-bounded queries stop at ceil(k/2) rounds without
    a recompile per k)."""
    j_n = df0.shape[1]
    best0 = _dist_meet(df0, db0, full_mask,
                       jnp.full(j_n, _DBIG, jnp.int32), n_states)

    def cond(st):
        _, _, best, cf, cb, it = st
        done = best <= 2 * it
        return ((cf | cb) & ~jnp.all(done)
                & (it < max_rounds) & (it < it_cap))

    def body(st):
        df, db, best, cf, cb, it = st
        # a direction whose last push relaxed nothing is at its fixpoint
        updf = jax.lax.cond(cf, push_f,
                            lambda a: jnp.full_like(a, DIST_INF), df)
        ndf = jnp.minimum(df, updf)
        updb = jax.lax.cond(cb, push_b,
                            lambda a: jnp.full_like(a, DIST_INF), db)
        ndb = jnp.minimum(db, updb)
        best = _dist_meet(ndf, ndb, full_mask, best, n_states)
        return (ndf, ndb, best, jnp.any(ndf != df), jnp.any(ndb != db),
                it + 1)

    st0 = (df0, db0, best0, jnp.bool_(True), jnp.bool_(True),
           jnp.int32(0))
    _, _, best, _, _, rounds = jax.lax.while_loop(cond, body, st0)
    return best, rounds


@functools.partial(jax.jit, static_argnames=("v_p", "n_states", "max_m",
                                             "max_rounds"))
def _dist_bidi(su, sv, req_labels, forb_raw_w, full_mask, sub_src,
               sub_dst, sub_lab, evalid, it_cap, *, v_p: int,
               n_states: int, max_m: int, max_rounds: int):
    """Segment-family bidirectional distance core over a (sub)graph's
    edge lists: one round = lane gather, per-edge subset transition
    (take the min of "already had the label" and "just gained it"),
    saturating +1, segment-min scatter."""
    j_n = su.shape[0]
    allow, sh = _edge_dist_ops(sub_lab, req_labels, forb_raw_w, max_m,
                               evalid=evalid)
    allowT = allow.T[:, :, None]                                # [E, J, 1]
    shT = sh.T[:, :, None]
    s_idx = jnp.arange(n_states, dtype=jnp.int32)
    iota = jnp.arange(j_n)
    inf = jnp.uint16(DIST_INF)

    def push(dist, gat, scat):
        rows = dist[gat]                                        # [E, J, S]
        alt = _flip_states(rows, shT, n_states)
        ok = ((s_idx[None, None, :] & shT) == shT) & allowT
        val = jnp.where(ok, jnp.minimum(rows, alt), inf)
        val = val + (val < inf).astype(jnp.uint16)   # saturating +1
        return jax.ops.segment_min(val, scat, num_segments=v_p)

    df0 = jnp.full((v_p, j_n, n_states), DIST_INF,
                   jnp.uint16).at[su, iota, 0].set(0)
    db0 = jnp.full((v_p, j_n, n_states), DIST_INF,
                   jnp.uint16).at[sv, iota, 0].set(0)
    return _dist_bidi_loop(
        df0, db0,
        lambda d: push(d, sub_src, sub_dst),
        lambda d: push(d, sub_dst, sub_src),
        full_mask, it_cap, n_states, max_rounds)


@functools.partial(jax.jit, static_argnames=("n_states", "max_m",
                                             "max_rounds", "mode"))
def _dist_bidi_matmul(su, sv, req_labels, forb_raw_w, full_mask, adj_rev,
                      adj_fwd, class_label, it_cap, *, n_states: int,
                      max_m: int, max_rounds: int, mode: str):
    """Pallas-backend distance core: one ``kernels.lane_matmul`` (min
    combine) per label class per direction per round, the distance plane
    flattened to [V', J·S] lanes.  ``_matmul_rows`` applies the DIST16
    extend (saturating +1) after each matmul; min is monotone, so
    extend-after-reduce equals extend-before-reduce and the per-class
    results combine by plain lane-min."""
    j_n = su.shape[0]
    v_p = adj_rev.shape[1]
    neutral = class_label < 0
    allow, sh = _edge_dist_ops(class_label, req_labels, forb_raw_w, max_m,
                               neutral=neutral)
    s_idx = jnp.arange(n_states, dtype=jnp.int32)
    iota = jnp.arange(j_n)
    inf = jnp.uint16(DIST_INF)

    def push(dist, adj_set):
        flat = dist.reshape(v_p, j_n * n_states)

        def body(upd, operand):
            adj_c, allow_c, sh_c = operand          # [V', Kw], [J], [J]
            y = engine_mod._matmul_rows(
                adj_c, flat, mode, sr=DIST16)[:v_p].reshape(
                    v_p, j_n, n_states)
            shc = sh_c[None, :, None]
            alt = _flip_states(y, shc, n_states)
            ok = (((s_idx[None, None, :] & shc) == shc)
                  & allow_c[None, :, None])
            return jnp.minimum(upd, jnp.where(ok, jnp.minimum(y, alt),
                                              inf)), None

        upd, _ = jax.lax.scan(
            body, jnp.full((v_p, j_n, n_states), DIST_INF, jnp.uint16),
            (adj_set, allow.T, sh.T))
        return upd

    df0 = jnp.full((v_p, j_n, n_states), DIST_INF,
                   jnp.uint16).at[su, iota, 0].set(0)
    db0 = jnp.full((v_p, j_n, n_states), DIST_INF,
                   jnp.uint16).at[sv, iota, 0].set(0)
    return _dist_bidi_loop(
        df0, db0,
        lambda d: push(d, adj_rev),
        lambda d: push(d, adj_fwd),
        full_mask, it_cap, n_states, max_rounds)


@functools.partial(jax.jit, static_argnames=("v_p", "n_states", "max_m",
                                             "max_rounds"))
def _dist_forward_parents(su, req_labels, forb_raw_w, sub_src, sub_dst,
                          sub_lab, evalid, *, v_p: int, n_states: int,
                          max_m: int, max_rounds: int):
    """Single-term forward distance DP with parent-edge planes.

    Unit weights make the DP BFS-layered — a cell's first finite write is
    its final distance — so recording a parent only on ``winner`` cells
    (``upd < dist``) is exact.  Parent recovery is two-pass: the round's
    arriving values are compared against the winning value and the
    minimal matching edge id is scattered (no value<<shift|id packing,
    which would overflow int32 on large |V'|·S).  Per-edge parent
    scatters are inherently edge-indexed, so witness extraction uses this
    segment core on both backends."""
    allow, sh = _edge_dist_ops(sub_lab, req_labels[None, :],
                               forb_raw_w[None, :], max_m, evalid=evalid)
    allow = allow[0][:, None]                                   # [E, 1]
    sh = sh[0][:, None]
    s_idx = jnp.arange(n_states, dtype=jnp.int32)
    inf = jnp.uint16(DIST_INF)
    eids = jnp.arange(sub_lab.shape[0], dtype=jnp.int32)[:, None]
    d0 = jnp.full((v_p, n_states), DIST_INF, jnp.uint16).at[su, 0].set(0)
    p0 = jnp.full((v_p, n_states), -1, jnp.int32)

    def cond(st):
        _, _, ch, it = st
        return ch & (it < max_rounds)

    def body(st):
        d, par, _, it = st
        rows = d[sub_src]                                       # [E, S]
        alt = _flip_states(rows, sh, n_states)
        ok = ((s_idx[None, :] & sh) == sh) & allow
        val = jnp.where(ok, jnp.minimum(rows, alt), inf)
        val = val + (val < inf).astype(jnp.uint16)
        upd = jax.ops.segment_min(val, sub_dst, num_segments=v_p)
        winner = upd < d                  # first discovery == final dist
        match = (val == upd[sub_dst]) & (val < inf)
        cand = jnp.where(match, eids, jnp.int32(1 << 30))
        parc = jax.ops.segment_min(cand, sub_dst, num_segments=v_p)
        par = jnp.where(winner, parc, par)
        return jnp.minimum(d, upd), par, jnp.any(winner), it + 1

    d, par, _, rounds = jax.lax.while_loop(
        cond, body, (d0, p0, jnp.bool_(True), jnp.int32(0)))
    return d, par, rounds


@functools.partial(jax.jit, static_argnames=("v_p", "n_states", "max_m",
                                             "cap"))
def _count_forward(su, sv, req_labels, forb_raw_w, full_mask, sub_src,
                   sub_dst, sub_lab, evalid, hops, *, v_p: int,
                   n_states: int, max_m: int, cap: int):
    """Bounded route-count DP: w[x, j, s] = number of length-r walks
    from u reaching x having seen subset s, every partial sum clamped at
    ``cap``.  A target state s collects from s (label already seen) and
    — when the edge's label is required, ``sh > 0`` — from s^sh, summing
    both; ``hops`` is traced (``fori_loop``), so the bound changes
    without a recompile.  Saturating add of non-negative values is
    associative, so per-edge clamp + segment-sum + clamp equals clamping
    the true total (the dfs_baseline oracle's semantics exactly)."""
    j_n = su.shape[0]
    allow, sh = _edge_dist_ops(sub_lab, req_labels, forb_raw_w, max_m,
                               evalid=evalid)
    allowT = allow.T[:, :, None]
    shT = sh.T[:, :, None]
    s_idx = jnp.arange(n_states, dtype=jnp.int32)
    iota = jnp.arange(j_n)
    capv = jnp.uint32(cap)
    w0 = jnp.zeros((v_p, j_n, n_states),
                   jnp.uint32).at[su, iota, 0].set(1)
    total0 = jnp.where((su == sv) & (full_mask == 0), jnp.uint32(1),
                       jnp.uint32(0))   # the empty walk

    def body(_, st):
        w, total = st
        rows = w[sub_src]                                       # [E, J, S]
        alt = _flip_states(rows, shT, n_states)
        contrib = rows + jnp.where(shT > 0, alt, 0)
        ok = ((s_idx[None, None, :] & shT) == shT) & allowT
        val = jnp.where(ok, jnp.minimum(contrib, capv), jnp.uint32(0))
        wn = jnp.minimum(
            jax.ops.segment_sum(val, sub_dst, num_segments=v_p), capv)
        total = jnp.minimum(total + wn[sv, iota, full_mask], capv)
        return wn, total

    _, total = jax.lax.fori_loop(0, hops, body, (w0, total0))
    return total


class _KindChunk(NamedTuple):
    """Host-side operands of one compacted (or full-graph) DP chunk."""
    v_p: int                    # padded vertex bucket
    su: np.ndarray              # renumbered sources int32 [J]
    sv: np.ndarray              # renumbered targets int32 [J]
    src: np.ndarray             # edge sources int32 [E'] (bucket-padded)
    dst: np.ndarray             # edge targets int32 [E']
    lab: np.ndarray             # edge labels int32 [E']
    evalid: np.ndarray          # bool [E'], False on padding rows
    sub_ids: np.ndarray | None  # local -> original vertex ids (None=full)
    n_sub: int                  # |V'| before padding


def _kind_chunk(index: TDRIndex, ex: ExactExecutor, plan: QueryPlan,
                dev: PlanDevice, jobs: np.ndarray,
                exact_mode: str) -> _KindChunk:
    """Corridor-compact one job chunk for the lane DPs (same probe and
    bucket discipline as ``ExactExecutor._run_bidi``, but edge padding
    rows are *masked* via ``evalid`` instead of relying on idempotence)."""
    g = index.graph
    v_n = g.n_vertices
    compact = exact_mode in ("auto", "compact")
    if compact:
        member = ex.corridor_members(dev, jobs)
        active = member.any(axis=0)
        n_sub = int(active.sum())
        if (exact_mode == "auto"
                and graph_mod.pad_bucket(max(n_sub, 1), lo=32) >= v_n):
            compact = False
    if compact:
        sub_ids, renum, s, d, l = graph_mod.induced_edges(
            g, active, src=ex.src_np)
        su = renum[plan.u[jobs]].astype(np.int32)
        sv = renum[plan.v[jobs]].astype(np.int32)
        v_p = graph_mod.pad_bucket(max(n_sub, 1), lo=32)
    else:
        sub_ids = None
        n_sub = v_p = v_n
        s, d, l = ex.src_np, ex.dst_np, ex.lab_np
        su = plan.u[jobs].astype(np.int32)
        sv = plan.v[jobs].astype(np.int32)
    e_real = int(s.shape[0])
    e_p = graph_mod.pad_bucket(max(e_real, 1), lo=32)
    evalid = np.zeros(e_p, dtype=bool)
    evalid[:e_real] = True
    if e_p > e_real:
        rep = e_p - e_real
        if e_real:
            s = np.concatenate([s, np.repeat(s[:1], rep)])
            d = np.concatenate([d, np.repeat(d[:1], rep)])
            l = np.concatenate([l, np.repeat(l[:1], rep)])
        else:   # corridor holds no edges: DP sees an empty, masked bucket
            s = np.zeros(e_p, np.int32)
            d = np.zeros(e_p, np.int32)
            l = np.zeros(e_p, np.int32)
    return _KindChunk(v_p, su, sv, np.ascontiguousarray(s),
                      np.ascontiguousarray(d), np.ascontiguousarray(l),
                      evalid, sub_ids, n_sub)


def dist_batch(index: TDRIndex,
               queries: Sequence[tuple[int, int, pat.Pattern]],
               *, k: int | None = None, max_m: int = 4,
               exact_chunk: int = 32, backend: str | None = None,
               exact_mode: str = "auto",
               engine_config: "engine_mod.EngineConfig | None" = None,
               special_labels: Sequence[int] | None = None,
               pin_m: int | None = None,
               stats: QueryStats | None = None) -> np.ndarray:
    """Shortest pattern-constrained hop distances.  Returns int64
    [n_queries]; -1 = unreachable (or farther than ``k`` when a k-hop
    bound is given — the bound also caps the DP at ceil(k/2) rounds,
    traced, so varying k never recompiles).

    Multi-term patterns take the min over terms.  ``exact_mode`` follows
    ``answer_plan`` minus "legacy"; on the pallas backend chunks run the
    per-label-class ``lane_matmul`` core when the class matrices fit the
    engine's dense budget, else the segment core (bit-equal results)."""
    if exact_mode not in ("auto", "compact", "full"):
        raise ValueError(f"unknown exact_mode {exact_mode!r} for dist; "
                         "expected auto | compact | full")
    stats = stats if stats is not None else QueryStats()
    plan = compile_queries(index, queries, max_m=max_m, stats=stats)
    with spans.span("query.phase2", stats, "phase2_s"):
        eng = index.engine(backend, engine_config)
        stats.n_queries += plan.n_queries
        stats.n_jobs += plan.n_jobs
        out = np.full(plan.n_queries, -1, np.int64)
        if plan.n_jobs == 0:
            return out
        ex = _executor(index, eng)
        jobs_all = np.arange(plan.n_jobs)
        m_eff, n_states = ex.eff_states(plan, jobs_all, pin_m)
        if n_states > 32:
            raise ValueError(
                f"max_m={m_eff} needs {n_states} subset states; the lane "
                "executor holds at most 32 (max_m <= 5)")
        dev = PlanDevice(jnp.asarray(plan.u), jnp.asarray(plan.v),
                         jnp.asarray(plan.req_labels),
                         jnp.asarray(plan.forb_raw_w),
                         jnp.asarray(plan.full_mask))
        best_j = np.full(plan.n_jobs, _DBIG, np.int64)
        for c0 in range(0, plan.n_jobs, exact_chunk):
            jobs = jobs_all[c0:c0 + exact_chunk]
            real_n = len(jobs)
            if real_n < exact_chunk:   # pad to a stable jit shape
                jobs = np.concatenate(
                    [jobs, np.full(exact_chunk - real_n, jobs[0])])
            ch = _kind_chunk(index, ex, plan, dev, jobs, exact_mode)
            max_rounds = ch.v_p * n_states + 1
            it_cap = jnp.int32(max_rounds if k is None
                               else max(-(-int(k) // 2), 0))
            req = jnp.asarray(plan.req_labels[jobs][:, :m_eff])
            frw = jnp.asarray(plan.forb_raw_w[jobs])
            fm = jnp.asarray(plan.full_mask[jobs])
            su, sv = jnp.asarray(ch.su), jnp.asarray(ch.sv)
            best = rounds = None
            if eng.backend == "pallas":
                special = ex.special_labels(plan, jobs)
                if special_labels is not None:
                    special = tuple(sorted(
                        set(int(l) for l in special_labels) | set(special)))
                kw_b = bitset.n_words(ch.v_p)
                n_mats = 2 * (len(special) + 1)
                if n_mats * ch.v_p * kw_b * 4 <= eng.config.max_dense_bytes:
                    class_label = jnp.asarray(
                        np.asarray(special + (-1,), np.int32))
                    if ch.sub_ids is None:
                        adj_rev = eng.label_class_adjacency(special,
                                                            reverse=True)
                        adj_fwd = eng.label_class_adjacency(special,
                                                           reverse=False)
                    else:
                        # padding rows duplicate edge 0: the same bit set
                        # twice — idempotent in a packed bit-matrix
                        adj_rev = jnp.asarray(
                            engine_mod.pack_label_class_edges_np(
                                ch.src, ch.dst, ch.lab, ch.v_p, special,
                                reverse=True))
                        adj_fwd = jnp.asarray(
                            engine_mod.pack_label_class_edges_np(
                                ch.src, ch.dst, ch.lab, ch.v_p, special,
                                reverse=False))
                    best_d, rounds = _dist_bidi_matmul(
                        su, sv, req, frw, fm, adj_rev, adj_fwd, class_label,
                        it_cap, n_states=n_states, max_m=m_eff,
                        max_rounds=max_rounds, mode=eng.matmul_mode)
                    best = np.asarray(best_d)
            if best is None:
                best_d, rounds = _dist_bidi(
                    su, sv, req, frw, fm, jnp.asarray(ch.src),
                    jnp.asarray(ch.dst), jnp.asarray(ch.lab),
                    jnp.asarray(ch.evalid), it_cap, v_p=ch.v_p,
                    n_states=n_states, max_m=m_eff, max_rounds=max_rounds)
                best = np.asarray(best_d)
            best_j[jobs[:real_n]] = best[:real_n]
            stats.add_chunk(rounds)
            stats.corridor_active += ch.n_sub
            stats.corridor_total += index.graph.n_vertices
        bq = np.full(plan.n_queries, _DBIG, np.int64)
        np.minimum.at(bq, plan.qid, best_j)
        reach = bq < _DBIG
        out[reach] = bq[reach]
        if k is not None:
            out[out > int(k)] = -1
        stats.exact_jobs += plan.n_jobs
    return out


def dist(index: TDRIndex, u: int, v: int, p: pat.Pattern, **kw) -> int:
    """Single-query shortest pattern-constrained distance (hops), -1 if
    unreachable — convenience wrapper over ``dist_batch``."""
    return int(dist_batch(index, [(u, v, p)], **kw)[0])


def witness(index: TDRIndex, u: int, v: int, p: pat.Pattern,
            *, max_m: int = 4, backend: str | None = None,
            exact_mode: str = "auto",
            engine_config: "engine_mod.EngineConfig | None" = None,
            pin_m: int | None = None,
            stats: QueryStats | None = None
            ) -> list[tuple[int, int, int]] | None:
    """An actual shortest witness path for a PCR query.

    Returns a list of ``(x, y, label)`` edges chaining u→v whose label
    set satisfies ``p`` and whose length equals the exact shortest
    pattern-constrained distance; ``[]`` when the empty path answers
    (u == v and some term requires nothing); ``None`` when unreachable.
    Every returned path is replayed against the raw graph through
    ``dfs_baseline.verify_witness`` before it leaves this function."""
    if exact_mode not in ("auto", "compact", "full"):
        raise ValueError(f"unknown exact_mode {exact_mode!r} for witness; "
                         "expected auto | compact | full")
    plan = compile_queries(index, [(u, v, p)], max_m=max_m, stats=stats)
    if plan.n_jobs == 0:
        return None
    eng = index.engine(backend, engine_config)
    ex = _executor(index, eng)
    jobs = np.arange(plan.n_jobs)
    m_eff, n_states = ex.eff_states(plan, jobs, pin_m)
    if n_states > 32:
        raise ValueError(
            f"max_m={m_eff} needs {n_states} subset states; the lane "
            "executor holds at most 32 (max_m <= 5)")
    dev = PlanDevice(jnp.asarray(plan.u), jnp.asarray(plan.v),
                     jnp.asarray(plan.req_labels),
                     jnp.asarray(plan.forb_raw_w),
                     jnp.asarray(plan.full_mask))
    ch = _kind_chunk(index, ex, plan, dev, jobs, exact_mode)
    max_rounds = ch.v_p * n_states + 1
    src_j, dst_j = jnp.asarray(ch.src), jnp.asarray(ch.dst)
    lab_j, ev_j = jnp.asarray(ch.lab), jnp.asarray(ch.evalid)
    best_t = -1
    best_len = None
    planes: list = []
    for t in range(plan.n_jobs):   # term shapes identical -> one compile
        dplane, par, _ = _dist_forward_parents(
            jnp.int32(int(ch.su[t])),
            jnp.asarray(plan.req_labels[t, :m_eff]),
            jnp.asarray(plan.forb_raw_w[t]), src_j, dst_j, lab_j, ev_j,
            v_p=ch.v_p, n_states=n_states, max_m=m_eff,
            max_rounds=max_rounds)
        planes.append((dplane, par))
        d_t = int(np.asarray(
            dplane[int(ch.sv[t]), int(plan.full_mask[t])]))
        if d_t < DIST_INF and (best_len is None or d_t < best_len):
            best_t, best_len = t, d_t
    if best_len is None:
        return None
    if best_len == 0:
        return []
    dn = np.asarray(planes[best_t][0]).astype(np.int64)
    pn = np.asarray(planes[best_t][1])
    req = plan.req_labels[best_t]
    x = int(ch.sv[best_t])
    state = int(plan.full_mask[best_t])
    path: list[tuple[int, int, int]] = []
    while dn[x, state] > 0:
        e = int(pn[x, state])
        px, lx = int(ch.src[e]), int(ch.lab[e])
        shx = 0
        for i in range(m_eff):
            if int(req[i]) == lx:
                shx = 1 << i
        want = dn[x, state] - 1
        nxt = None
        # the pre-edge state dropped the edge's subset bit, or already
        # had the label; either predecessor one hop closer is valid
        for so in ([state, state ^ shx] if shx else [state]):
            if dn[px, so] == want:
                nxt = so
                break
        if nxt is None:
            raise RuntimeError("witness backtrack: broken parent chain "
                               f"at vertex {x}, state {state}")
        path.append((px, x, lx))
        x, state = px, nxt
    path.reverse()
    if ch.sub_ids is not None:   # map compacted ids back to the graph
        path = [(int(ch.sub_ids[a]), int(ch.sub_ids[b]), l)
                for (a, b, l) in path]
    if len(path) != best_len or not dfs_mod.verify_witness(
            index.graph, u, v, p, path):
        raise RuntimeError("witness verification failed: extracted path "
                           "does not replay on the graph")
    return path


def count_routes(index: TDRIndex, u: int, v: int, p: pat.Pattern,
                 *, hops: int, cap: int = COUNT_CAP, max_m: int = 4,
                 backend: str | None = None, exact_mode: str = "auto",
                 engine_config: "engine_mod.EngineConfig | None" = None,
                 pin_m: int | None = None,
                 stats: QueryStats | None = None) -> int:
    """Number of pattern-satisfying u→v walks of length <= ``hops``,
    saturating at ``cap`` (``semiring.COUNT_CAP`` by default).

    Walks, not simple paths — a cycle counts per traversal, exactly the
    product-graph DP the ``dfs_baseline.count_routes`` oracle runs.
    Single-DNF-term patterns only: terms of a composite pattern overlap,
    so a per-term sum would double-count (the same restriction as the
    oracle).  ``hops`` is traced — varying it never recompiles."""
    if exact_mode not in ("auto", "compact", "full"):
        raise ValueError(f"unknown exact_mode {exact_mode!r} for count; "
                         "expected auto | compact | full")
    terms = pat.to_dnf(p)
    if len(terms) != 1:
        raise ValueError(
            f"count_routes needs a single-DNF-term pattern, got "
            f"{len(terms)} terms")
    plan = compile_queries(index, [(u, v, p)], max_m=max_m, stats=stats)
    eng = index.engine(backend, engine_config)
    ex = _executor(index, eng)
    jobs = np.arange(plan.n_jobs)
    m_eff, n_states = ex.eff_states(plan, jobs, pin_m)
    if n_states > 32:
        raise ValueError(
            f"max_m={m_eff} needs {n_states} subset states; the lane "
            "executor holds at most 32 (max_m <= 5)")
    dev = PlanDevice(jnp.asarray(plan.u), jnp.asarray(plan.v),
                     jnp.asarray(plan.req_labels),
                     jnp.asarray(plan.forb_raw_w),
                     jnp.asarray(plan.full_mask))
    ch = _kind_chunk(index, ex, plan, dev, jobs, exact_mode)
    # a round's segment_sum adds at most ``cap`` per edge into its target:
    # the widest in-degree bounds the uint32 accumulator before the clamp
    max_in = int(np.bincount(ch.dst[ch.evalid]).max(initial=0))
    if max_in * cap >= 1 << 32:
        raise ValueError(
            f"cap={cap} with in-degree {max_in} could wrap the uint32 "
            "count accumulator; lower the cap")
    total = _count_forward(
        jnp.asarray(ch.su), jnp.asarray(ch.sv),
        jnp.asarray(plan.req_labels[:, :m_eff]),
        jnp.asarray(plan.forb_raw_w), jnp.asarray(plan.full_mask),
        jnp.asarray(ch.src), jnp.asarray(ch.dst), jnp.asarray(ch.lab),
        jnp.asarray(ch.evalid), jnp.int32(int(hops)), v_p=ch.v_p,
        n_states=n_states, max_m=m_eff, cap=int(cap))
    return int(np.asarray(total)[0])


# ------------------------------------------------ RPQ executor (PR 10)
# Regular path queries constrain the label *order* along a path, which
# the subset-state planes above cannot express.  The fragment that DNF
# lowering can absorb exactly (unions of single-atom stars — the RPQ
# spelling of LCR) rides ``answer_plan`` untouched; everything else runs
# the same corridor-compacted bidirectional expansion generalized from
# subset-states to Glushkov NFA states: the ``[V', J]`` packed plane's
# uint32 holds "NFA states reachable at vertex x" (forward) / "states
# from which (v, accept) is reachable" (backward), per-edge transitions
# come from the dense per-job ``[L, 32]`` NFA tables, and a query meets
# as soon as some vertex holds ``f & b != 0``.  The TDR filter cascade
# still prunes via the regex's label over-approximation — but only a
# FALSE verdict is sound (set logic is order-blind), so the cascade runs
# ``filters_only`` and survivors go to the product executor.


class RpqRows(NamedTuple):
    """Per-regex compiled operands (endpoint-independent, cached like
    ``PatternRows`` under the same LRU with kind="rpq" keys)."""
    tab: np.ndarray             # uint32 [L, 32]  forward NFA table
    rtab: np.ndarray            # uint32 [L, 32]  reverse NFA table
    accept: int                 # uint32 accept-state bitmask
    nullable: bool              # ε ∈ L(r): u == v answers True
    nfa_states: int             # Glushkov state count (<= 32)
    lowered: Any                # exact pattern.Pattern lowering, or None
    approx: Any                 # over-approximation pattern (prune only)
    feasible: bool              # False: some required label can't exist
    alpha: tuple                # in-graph alphabet (pallas label classes)

    @property
    def n_terms(self) -> int:
        return 1                # one product-executor job per query


def _compile_rpq_rows(index: TDRIndex, r, max_m: int) -> RpqRows:
    n_labels = index.graph.n_labels
    nfa = rpq_mod.compile_nfa(r, n_labels)
    lowered = rpq_mod.lower_to_pattern(r, n_labels)
    approx, feasible = rpq_mod.approx_pattern(r, n_labels,
                                              max_require=max_m)
    alpha = tuple(sorted(a for a in rpq_mod.alphabet(r) if a < n_labels))
    return RpqRows(tab=nfa.tab, rtab=nfa.rtab, accept=int(nfa.accept),
                   nullable=bool(nfa.nullable), nfa_states=nfa.n_states,
                   lowered=lowered, approx=approx, feasible=feasible,
                   alpha=alpha)


def rpq_rows(index: TDRIndex, r, max_m: int = 4,
             stats: "QueryStats | None" = None) -> RpqRows:
    """Cached compiled operands for one RPQ (hash-consed canonical key,
    same bounded LRU and lock discipline as ``pattern_rows``)."""
    key = (rpq_mod.canonical_key(r), max_m, "rpq")
    if stats is not None:
        stats.plan_lookups += 1
    with _plan_cache_lock:
        cache = getattr(index, "_plan_cache", None)
        if cache is None:
            cache = {}
            index._plan_cache = cache
        rows = cache.get(key)
        if rows is not None:
            cache[key] = cache.pop(key)     # refresh LRU position
            return rows
    if stats is not None:
        stats.plan_misses += 1
    # NFA construction + lowering run outside the lock (pattern_rows'
    # compile-outside-lock idiom)
    rows = _compile_rpq_rows(index, rpq_mod.canonicalize(r), max_m)
    with _plan_cache_lock:
        while len(cache) >= PLAN_CACHE_CAP:
            cache.pop(next(iter(cache)))
        cache[key] = rows
    return rows


def _nfa_apply(masks, tab_q, q_u: int = 32, rolled: bool = False):
    """Union of ``tab_q(q)`` over the set bits q of ``masks`` — one NFA
    step applied to a packed state-subset plane (``tab_q(q)`` is the
    transition row of state q, broadcastable against ``masks``).  A
    ``q_u``-step loop (the chunk's NFAs use only states < q_u, so higher
    bits are provably never set): a static unroll, or with ``rolled`` a
    ``fori_loop`` so that one state's rows are live at a time.  Linearity
    over union (δ(S₁∪S₂, a) = δ(S₁,a) ∪ δ(S₂,a)) is what lets the push
    below OR-gather neighbours *before* applying the transition table."""
    if rolled:
        def body(q, out):
            hit = ((masks >> q.astype(jnp.uint32)) & jnp.uint32(1)) != 0
            return out | jnp.where(hit, tab_q(q), jnp.uint32(0))

        return jax.lax.fori_loop(0, q_u, body, jnp.zeros_like(masks))
    out = jnp.zeros_like(masks)
    for q in range(q_u):
        hit = ((masks >> q) & jnp.uint32(1)) != 0
        out = out | jnp.where(hit, tab_q(q), jnp.uint32(0))
    return out


def _rpq_sup_need(q_n: int):
    """``_meet``'s sup_need specialized to the NFA meet: forward state q
    completes with exactly backward state q, so done ⟺ f & b != 0."""
    bits = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return jnp.broadcast_to(bits[:, None], (32, q_n))


@functools.partial(jax.jit, static_argnames=("v_p", "max_rounds",
                                             "chunk_words", "q_u"))
def _rpq_bidi(su, sv, tabs, rtabs, accept, sub_src, sub_dst, sub_lab,
              evalid, ids_in, ids_out, *, v_p: int, max_rounds: int,
              chunk_words: int, q_u: int = 32):
    """Segment-backend product-graph fixpoint over a (sub)graph's edge
    lists.  One round = lane gather, per-edge NFA transition from the
    job's dense table, OR-reduction over the padded in/out incidence
    (``ids_in``/``ids_out``, sentinel = the appended zero row; padding
    edges are simply never referenced).  When the incidence is ``None``
    (degree skew beyond the gather cap) the reduction falls back to
    packed segment ORs with explicit ``evalid`` masking — a padding
    edge would inject fake word letters; unlike the idempotent subset-
    state closure, a fabricated edge changes the language.

    ``q_u`` (static) caps the NFA-apply unroll: every NFA in the chunk
    has <= q_u states, so bits >= q_u are never set in any plane and
    the sliced per-edge tables stay exact."""
    q_n = su.shape[0]
    iota = jnp.arange(q_n)
    f0 = jnp.zeros((v_p, q_n), jnp.uint32).at[su, iota].set(jnp.uint32(1))
    b0 = jnp.zeros((v_p, q_n), jnp.uint32).at[sv, iota].set(accept)
    # per-edge tables [E', J, q_u] are gathered once per query when they
    # fit the cap; above it (4 GiB at E'=2^20 edges and 32 jobs) each
    # push re-gathers one state's rows at a time
    rolled = sub_lab.shape[0] * q_n * q_u * 4 > _RPQ_TABLE_BYTES
    if rolled:
        def tab_e(q):
            return tabs[:, sub_lab, q].T                      # [E', J]

        def rtab_e(q):
            return rtabs[:, sub_lab, q].T
    else:
        tab_all = jnp.transpose(tabs[:, sub_lab, :q_u], (1, 0, 2))
        rtab_all = jnp.transpose(rtabs[:, sub_lab, :q_u], (1, 0, 2))

        def tab_e(q):
            return tab_all[..., q]                            # [E', J]

        def rtab_e(q):
            return rtab_all[..., q]

    ev = evalid[:, None]
    cor_w = jnp.full((v_p, q_n), _FULL)

    def reduce_cols(val, ids):
        # per-column gathers accumulate without the [V', D, J] transient
        # a single 3D gather would materialize (same idiom as the
        # boolean core: 3× faster on CPU than scatter-reduce)
        out = val[ids[:, 0]]
        for j in range(1, ids.shape[1]):  # static unroll over D columns
            out = out | val[ids[:, j]]
        return out

    def push(frontier, gat, te, scat, ids):
        val = _nfa_apply(frontier[gat], te, q_u, rolled)     # [E', J]
        if ids is None:
            val = jnp.where(ev, val, jnp.uint32(0))
            return bitset.segment_or_words(val, scat, num_segments=v_p,
                                           chunk_words=chunk_words)
        val = jnp.concatenate(
            [val, jnp.zeros((1, q_n), jnp.uint32)], axis=0)
        for level in ids:   # 1 level, or virtual-row split on heavy tails
            val = reduce_cols(val, level)
        return val                                           # [V', J]

    return _bidi_loop(
        f0, b0,
        lambda f: push(f, sub_src, tab_e, sub_dst, ids_in),
        lambda b: push(b, sub_dst, rtab_e, sub_src, ids_out),
        cor_w, _rpq_sup_need(q_n), max_rounds)


@functools.partial(jax.jit, static_argnames=("max_rounds", "mode", "q_u"))
def _rpq_bidi_matmul(su, sv, tabs, rtabs, accept, adj_rev, adj_fwd,
                     class_label, *, max_rounds: int, mode: str,
                     q_u: int = 32):
    """Pallas-backend product-graph fixpoint: one ``bitset_matmul`` per
    label class per direction per round.  Every in-graph alphabet label
    of the chunk gets its own class; the merged neutral class carries a
    zero transition table — sound because a label outside every job's
    alphabet has an all-zero NFA table row anyway (no word of the
    language uses it)."""
    q_n = su.shape[0]
    v_p = adj_rev.shape[1]
    iota = jnp.arange(q_n)
    f0 = jnp.zeros((v_p, q_n), jnp.uint32).at[su, iota].set(jnp.uint32(1))
    b0 = jnp.zeros((v_p, q_n), jnp.uint32).at[sv, iota].set(accept)
    labx = jnp.maximum(class_label, 0)
    live = (class_label >= 0)[:, None, None]
    tab_cls = jnp.where(live,
                        jnp.transpose(tabs[:, labx, :q_u], (1, 0, 2)),
                        jnp.uint32(0))                      # [C+1, J, q_u]
    rtab_cls = jnp.where(live,
                         jnp.transpose(rtabs[:, labx, :q_u], (1, 0, 2)),
                         jnp.uint32(0))
    cor_w = jnp.full((v_p, q_n), _FULL)

    def push(frontier, adj_set, tab_set):
        # scan over label classes (one kernel call site per direction,
        # as in _bidi_matmul_core)
        def body(upd, operand):
            adj_c, tab_c = operand                  # [V', Kw], [J, 32]
            y = engine_mod._matmul_rows(adj_c, frontier, mode)[:v_p]
            return upd | _nfa_apply(y, lambda q: tab_c[None, :, q],
                                    q_u), None
        upd, _ = jax.lax.scan(body, jnp.zeros_like(frontier),
                              (adj_set, tab_set))
        return upd

    return _bidi_loop(
        f0, b0,
        lambda f: push(f, adj_rev, tab_cls),
        lambda b: push(b, adj_fwd, rtab_cls),
        cor_w, _rpq_sup_need(q_n), max_rounds)


def rpq_batch(index: TDRIndex, queries: Sequence[tuple], *,
              max_m: int = 4, exact_chunk: int = 32,
              backend: str | None = None, exact_mode: str = "auto",
              engine_config: "engine_mod.EngineConfig | None" = None,
              special_labels: Sequence[int] | None = None,
              pin_m: int | None = None, pad_lo: int = 16,
              q_unroll: int | None = None,
              stats: QueryStats | None = None) -> np.ndarray:
    """Answer ``(u, v, rpq)`` regular path queries.  Returns bool [n].

    ``q_unroll`` pins the static NFA state-unroll width (a power of two
    in 4..32).  ``None`` derives the tightest width from each chunk's
    regexes — small automata run up to 8x fewer per-edge table ops; a
    serving layer pins 32 so the compiled shape never depends on which
    regexes a batch happens to hold.

    Three routes, all oracle-equal to ``dfs_baseline.answer_rpq``:

    * **lowered** — regexes in the index-expressible fragment
      (``rpq.lower_to_pattern``) become plain PCR queries and take
      ``answer_plan`` *bit-for-bit* with the equivalent composite
      pattern (an LCR asked as ``(a|b|…)*`` shares plans, caches, and
      answers with the LCR asked directly);
    * **infeasible** — a required label no graph edge can carry: only
      the empty path remains, so the answer is ``u == v and ε ∈ L(r)``;
    * **product** — everything else: the filter cascade on the regex's
      over-approximation pattern prunes (FALSE verdicts only — TRUE is
      order-blind and proves nothing), survivors run the corridor-
      compacted automaton-product expansion on either backend.
    """
    if exact_mode not in ("auto", "compact", "full"):
        raise ValueError(f"unknown exact_mode {exact_mode!r} for rpq; "
                         "expected auto | compact | full")
    if q_unroll is not None and q_unroll not in (4, 8, 16, 32):
        raise ValueError(f"q_unroll must be a power of two in 4..32, "
                         f"got {q_unroll!r}")
    eng = index.engine(backend, engine_config)
    stats = stats if stats is not None else QueryStats()
    out = np.zeros(len(queries), dtype=bool)
    if not queries:
        return out
    with spans.span("query.plan", stats, "plan_s"):
        rows = [rpq_rows(index, r, max_m, stats=stats)
                for (_, _, r) in queries]

    low_ix = [i for i, rw in enumerate(rows) if rw.lowered is not None]
    if low_ix:
        lowq = [(queries[i][0], queries[i][1], rows[i].lowered)
                for i in low_ix]
        plan = compile_queries(index, lowq, max_m=max_m, stats=stats)
        ans = answer_plan(index, plan, exact_chunk=exact_chunk,
                          stats=stats, backend=backend,
                          exact_mode=exact_mode,
                          engine_config=engine_config,
                          special_labels=special_labels, pin_m=pin_m,
                          pad_lo=pad_lo)
        out[low_ix] = ans

    hard_ix = [i for i, rw in enumerate(rows) if rw.lowered is None]
    # ε answers need no path; infeasible regexes allow nothing else
    for i in list(hard_ix):
        u, v, _ = queries[i][:3]
        if u == v and rows[i].nullable:
            out[i] = True
            hard_ix.remove(i)
        elif not rows[i].feasible:
            hard_ix.remove(i)       # out[i] stays False
    if not hard_ix:
        return out

    # phase 1: the cascade on the over-approximation — a FALSE verdict
    # refutes the RPQ (every matching word satisfies the approximation);
    # filters_only returns the sound upper bound TRUE ∪ UNKNOWN
    approxq = [(queries[i][0], queries[i][1], rows[i].approx)
               for i in hard_ix]
    aplan = compile_queries(index, approxq, max_m=max_m, stats=stats)
    ub = answer_plan(index, aplan, exact_chunk=exact_chunk, stats=stats,
                     filters_only=True, backend=backend,
                     exact_mode=exact_mode, engine_config=engine_config,
                     special_labels=special_labels, pin_m=pin_m,
                     pad_lo=pad_lo)
    pos_of = {i: k for k, i in enumerate(hard_ix)}  # aplan job per query
    hard_ix = [i for i, alive in zip(hard_ix, ub) if alive]
    if not hard_ix:
        return out

    # phase 2: automaton-product expansion.  The approx plan is single-
    # term per query (its job k is approxq position k), so it doubles as
    # the endpoint plan and the Bloom-corridor compaction source.
    with spans.span("query.phase2", stats, "phase2_s"):
        ex = _executor(index, eng)
        jobs_all = np.asarray([pos_of[i] for i in hard_ix], dtype=np.int64)
        dev = PlanDevice(jnp.asarray(aplan.u), jnp.asarray(aplan.v),
                         jnp.asarray(aplan.req_labels),
                         jnp.asarray(aplan.forb_raw_w),
                         jnp.asarray(aplan.full_mask))
        done_all = np.zeros(len(jobs_all), dtype=bool)
        for c0 in range(0, len(jobs_all), exact_chunk):
            jobs = jobs_all[c0:c0 + exact_chunk]
            real_n = len(jobs)
            if real_n < exact_chunk:    # pad to a stable jit shape
                jobs = np.concatenate(
                    [jobs, np.full(exact_chunk - real_n, jobs[0])])
            ch = _kind_chunk(index, ex, aplan, dev, jobs, exact_mode)
            qrows = [rows[hard_ix[c0 + (j if j < real_n else 0)]]
                     for j in range(len(jobs))]
            if q_unroll is None:
                q_u = 4
                while q_u < max(rw.nfa_states for rw in qrows):
                    q_u *= 2
            else:
                q_u = q_unroll
            max_rounds = ch.v_p * q_u + 1    # product-graph diameter bound
            tabs = jnp.asarray(np.stack([rw.tab for rw in qrows]))
            rtabs = jnp.asarray(np.stack([rw.rtab for rw in qrows]))
            accept = jnp.asarray(
                np.asarray([rw.accept for rw in qrows], np.uint32))
            su, sv = jnp.asarray(ch.su), jnp.asarray(ch.sv)
            done = rounds = None
            if eng.backend == "pallas" and ch.evalid.any():
                # per-alphabet-label classes; the merged neutral class has a
                # zero NFA table.  Skipped when the corridor held no real
                # edges — the packed fake 0→0 edge would fabricate a letter.
                special = set()
                for rw in qrows:
                    special.update(rw.alpha)
                if special_labels is not None:
                    special.update(int(l) for l in special_labels
                                   if 0 <= int(l) < index.graph.n_labels)
                special = tuple(sorted(special))
                kw_b = bitset.n_words(ch.v_p)
                n_mats = 2 * (len(special) + 1)
                if n_mats * ch.v_p * kw_b * 4 <= eng.config.max_dense_bytes:
                    class_label = jnp.asarray(
                        np.asarray(special + (-1,), np.int32))
                    if ch.sub_ids is None:
                        adj_rev = eng.label_class_adjacency(special,
                                                            reverse=True)
                        adj_fwd = eng.label_class_adjacency(special,
                                                            reverse=False)
                    else:
                        adj_rev = jnp.asarray(
                            engine_mod.pack_label_class_edges_np(
                                ch.src, ch.dst, ch.lab, ch.v_p, special,
                                reverse=True))
                        adj_fwd = jnp.asarray(
                            engine_mod.pack_label_class_edges_np(
                                ch.src, ch.dst, ch.lab, ch.v_p, special,
                                reverse=False))
                    done_d, rounds = _rpq_bidi_matmul(
                        su, sv, tabs, rtabs, accept, adj_rev, adj_fwd,
                        class_label, max_rounds=max_rounds,
                        mode=eng.matmul_mode, q_u=q_u)
                    done = np.asarray(done_d)
            if done is None:
                # padded-incidence gathers replace the scatter segment-OR
                # (built from the real edges only, so padding rows need no
                # mask on this path); degree skew past the cap falls back
                e_real = int(ch.evalid.sum())
                e_p = int(ch.src.shape[0])
                ids_in = ids_out = None
                if e_real:
                    plan_in = graph_mod.incidence_plan(
                        ch.dst[:e_real], ch.v_p, e_p)
                    plan_out = graph_mod.incidence_plan(
                        ch.src[:e_real], ch.v_p, e_p)
                    gb = sum(a.size for a in plan_in + plan_out) * \
                        len(jobs) * 4
                    if gb <= ExactExecutor.GATHER_BYTES_CAP:
                        ids_in = tuple(jnp.asarray(a) for a in plan_in)
                        ids_out = tuple(jnp.asarray(a) for a in plan_out)
                done_d, rounds = _rpq_bidi(
                    su, sv, tabs, rtabs, accept, jnp.asarray(ch.src),
                    jnp.asarray(ch.dst), jnp.asarray(ch.lab),
                    jnp.asarray(ch.evalid), ids_in, ids_out, v_p=ch.v_p,
                    max_rounds=max_rounds,
                    chunk_words=eng.config.chunk_words, q_u=q_u)
                done = np.asarray(done_d)
            done_all[c0:c0 + real_n] = done[:real_n]
            stats.add_chunk(rounds)
            stats.corridor_active += ch.n_sub
            stats.corridor_total += index.graph.n_vertices
        for i, d in zip(hard_ix, done_all):
            out[i] = bool(d)
        stats.exact_jobs += len(jobs_all)
    return out


def answer_rpq(index: TDRIndex, u: int, v: int, r, **kw) -> bool:
    """Single-query convenience wrapper over ``rpq_batch``."""
    return bool(rpq_batch(index, [(u, v, r)], **kw)[0])


def answer_mixed(index: TDRIndex, queries: Sequence[tuple], *,
                 hops: int = 8, k: int | None = None,
                 cap: int = COUNT_CAP, max_m: int = 4,
                 backend: str | None = None, exact_mode: str = "auto",
                 engine_config: "engine_mod.EngineConfig | None" = None,
                 stats: QueryStats | None = None) -> list:
    """Answer a mixed-kind batch of ``(u, v, pattern[, kind])`` queries.

    Results align with the input order: bool for "bool", int distance
    (-1 unreachable) for "dist", an edge list / [] / None for "witness",
    an int for "count" (bounded by ``hops``, clamped at ``cap``), and
    bool for "rpq" (whose third element is a ``repro.core.rpq`` AST
    rather than a pattern).  Same-kind queries batch together;
    "witness"/"count" run per query."""
    kinds = [(q[3] if len(q) > 3 else "bool") for q in queries]
    for kd in kinds:
        if kd not in QUERY_KINDS:
            raise ValueError(f"unknown query kind {kd!r}; expected one "
                             f"of {QUERY_KINDS}")
    common = dict(max_m=max_m, backend=backend, exact_mode=exact_mode,
                  engine_config=engine_config, stats=stats)
    results: list = [None] * len(queries)
    bool_ix = [i for i, kd in enumerate(kinds) if kd == "bool"]
    if bool_ix:
        ans = answer_batch(index, [queries[i][:3] for i in bool_ix],
                           **common)
        for i, a in zip(bool_ix, ans):
            results[i] = bool(a)
    rpq_ix = [i for i, kd in enumerate(kinds) if kd == "rpq"]
    if rpq_ix:
        # the third element is a repro.core.rpq AST, not a pattern —
        # compile_queries would reject it, so partition before batching
        ans = rpq_batch(index, [queries[i][:3] for i in rpq_ix], **common)
        for i, a in zip(rpq_ix, ans):
            results[i] = bool(a)
    dist_ix = [i for i, kd in enumerate(kinds) if kd == "dist"]
    if dist_ix:
        ds = dist_batch(index, [queries[i][:3] for i in dist_ix], k=k,
                        **common)
        for i, dv in zip(dist_ix, ds):
            results[i] = int(dv)
    for i, kd in enumerate(kinds):
        if kd == "witness":
            results[i] = witness(index, *queries[i][:3], **common)
        elif kd == "count":
            results[i] = count_routes(index, *queries[i][:3], hops=hops,
                                      cap=cap, **common)
    return results
