"""Packed-word semiring closure engine — one core shared by build & query.

Everything the TDR pipeline computes — index construction (§IV Alg. 1),
vertical k-level propagation, and the query-side product-graph expansion
(§V Alg. 2) — is one primitive applied in different shapes:

    out[a] = (+)_{(a,b) ∈ E} extend(x[b])     (semiring propagate)

The fixpoint/propagate cores are parameterized by a ``repro.core.semiring``
instance (static under jit, so each algebra compiles its own
specialization).  The default — and the only carrier the index planes
use — is ``semiring.BOOLEAN``: packed uint32 words, (+) = OR, extend =
identity, whose generic code path emits *exactly* the traced ops of the
pre-refactor OR engine (bit-identity asserted in tests/test_semiring.py).
``DIST16``/``DIST8`` (min-plus over saturating unsigned lanes) and
``COUNT`` (saturating add, non-idempotent — ``closure`` refuses it) drive
the witness/distance/route-count query kinds in ``tdr_query``.

This module provides the primitive **end-to-end on packed uint32 words**
for the boolean carrier (32 graph bits per lane element; no ``[V, nbits]``
boolean plane at rest) behind a pluggable backend:

* ``segment`` — reference backend; chunked ``segment_max`` over word-chunk
  transients (``bitset.segment_or_words``).  Works on any jax backend and
  any graph size; the default off-TPU.
* ``pallas``  — routes every fixpoint round / frontier expansion through
  ``repro.kernels.bitset_matmul`` on a packed adjacency bit-matrix
  (``[V, ceil(V/32)]`` uint32, bit j of row i == edge i→j).  Real kernel on
  TPU, interpret mode elsewhere.  Dense ``V×V/8`` bytes, so the engine
  auto-falls back to ``segment`` above ``EngineConfig.max_dense_bytes``.
  The boolean phase-2 class expansion instead walks per-class edge lists
  (``class_edge_lists_np``, ``_edge_rows``: ``lane_matmul_edges``).

Backend selection contract (see ARCHITECTURE.md):

1. An explicitly requested backend ("segment" | "pallas") always wins.
2. The ``REPRO_ENGINE_BACKEND`` environment variable replaces the default
   resolution when the request is "auto"/unset.
3. "auto" resolves to ``pallas`` on TPU, ``segment`` elsewhere.
4. A ``pallas`` request that cannot be honoured (adjacency over the dense
   cap) falls back to ``segment`` with a warning — never an error.

Both backends are bit-exact (property-tested against each other and the
bool-plane oracle in ``tests/test_engine.py``).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from . import bitset
from .compressed import BlockCompressed, compress_blocks, patch_blocks
from .graph import Graph, csr_row_edges, pad_bucket
from .semiring import BOOLEAN, Semiring

ENV_BACKEND = "REPRO_ENGINE_BACKEND"
BACKENDS = ("segment", "pallas")


def resolve_backend(requested: str = "auto") -> str:
    """Resolve a backend name per the selection contract above.

    The ``REPRO_ENGINE_BACKEND`` environment variable replaces the
    *default* ("auto"/empty) resolution only — an explicitly requested
    backend wins, so backend sweeps and bit-equality comparisons cannot be
    silently collapsed onto one backend by ambient environment."""
    req = requested or "auto"
    if req == "auto":
        req = os.environ.get(ENV_BACKEND, "").strip() or "auto"
    if req == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "segment"
    if req not in BACKENDS:
        raise ValueError(
            f"unknown engine backend {req!r}; expected one of "
            f"{('auto',) + BACKENDS}")
    return req


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    backend: str = "auto"        # "auto" | "segment" | "pallas"
    bit_chunk: int = 64          # transient chunk width (bits) for segment ORs
    interpret: bool | None = None  # pallas interpret; None -> off-TPU only
    max_dense_bytes: int = 1 << 28  # pallas dense-adjacency cap (auto-fallback)
    sparse: bool = True          # block-sparse closure fixpoints (both backends)
    block_rows: int = 8          # row-block height of the block-sparse operand
    block_words: int = 1         # word-block width  (8x1 = 8x32-bit blocks)
    sparse_dense_frac: float = 0.5  # segment: frontier fraction -> dense round

    @property
    def chunk_words(self) -> int:
        return max(1, self.bit_chunk // bitset.WORD)


# ------------------------------------------------------- adjacency packing
def pack_adjacency_np(graph: Graph, *, reverse: bool = False) -> np.ndarray:
    """Packed adjacency bit-matrix uint32 ``[V, ceil(V/32)]``.

    Forward: bit v of row u == edge u→v (the closure/propagate operand).
    Reverse: bit u of row v == edge u→v.
    """
    v_n = graph.n_vertices
    kw = bitset.n_words(v_n)
    a = np.zeros((v_n, kw), dtype=np.uint32)
    src, dst = graph.src, graph.indices
    rows, cols = (dst, src) if reverse else (src, dst)
    bitset.set_bits_np(a, (rows,), cols)
    return a


def pack_label_class_edges_np(src: np.ndarray, dst: np.ndarray,
                              labels: np.ndarray, n_vertices: int,
                              special_labels, *,
                              reverse: bool = True) -> np.ndarray:
    """Per-label-class packed adjacency ``[C+1, V, ceil(V/32)]`` from raw
    edge arrays (used for per-chunk corridor-compacted subgraphs as well
    as the whole graph).

    One bit-matrix per *special* label (labels that some pending query
    requires or forbids) plus a final **neutral** class OR-ing every edge
    whose label is special for nobody — those edges behave identically for
    all queries (always allowed, subset-bit 0), so one matmul covers them.
    """
    kw = bitset.n_words(n_vertices)
    special = list(special_labels)
    out = np.zeros((len(special) + 1, n_vertices, kw), dtype=np.uint32)
    rows, cols = (dst, src) if reverse else (src, dst)
    cls = np.full(labels.shape[0], len(special), dtype=np.int64)
    for i, l in enumerate(special):
        cls[labels == l] = i
    bitset.set_bits_np(out, (cls, rows), cols)
    return out


def class_edge_lists_np(src: np.ndarray, dst: np.ndarray,
                        labels: np.ndarray, special_labels, *,
                        reverse: bool = True
                        ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-label-class edge lists ``(rows, cols, count)`` from raw edge
    arrays: the operand of ``kernels.ops.frontier_step_edges``.

    The same classes as ``pack_label_class_edges_np`` (one per special
    label, then the neutral class), and the same ``reverse`` meaning:
    class ``c``'s first ``count[c]`` entries are the set bits ``(row,
    col)`` of its packed matrix, so one round ORs ``x[col]`` into
    ``out[row]``.  ``rows`` and ``cols`` are int32 ``[C+1, E_pad]``,
    sorted by row within a class; ``E_pad`` is the largest class's count
    on the ``graph.pad_bucket`` grid, so an update that moves edge counts
    keeps the shape within a bucket.  Padding entries are 0 and never
    read."""
    special = list(special_labels)
    rows, cols = (dst, src) if reverse else (src, dst)
    cls = np.full(labels.shape[0], len(special), dtype=np.int64)
    for i, l in enumerate(special):
        cls[labels == l] = i
    count = np.bincount(cls, minlength=len(special) + 1)
    e_pad = pad_bucket(max(int(count.max()), 1), lo=32)
    order = np.lexsort((rows, cls))
    slot = np.arange(order.shape[0]) - np.repeat(np.cumsum(count) - count,
                                                 count)
    out_r = np.zeros((len(special) + 1, e_pad), dtype=np.int32)
    out_c = np.zeros_like(out_r)
    out_r[cls[order], slot] = rows[order]
    out_c[cls[order], slot] = cols[order]
    return out_r, out_c, count.astype(np.int32)


def pack_label_class_adjacency_np(graph: Graph, special_labels,
                                  *, reverse: bool = True) -> np.ndarray:
    """Whole-graph wrapper over ``pack_label_class_edges_np``."""
    return pack_label_class_edges_np(graph.src, graph.indices, graph.labels,
                                     graph.n_vertices, special_labels,
                                     reverse=reverse)


# --------------------------------------------------------- jitted closures
@functools.partial(jax.jit, static_argnames=("num_segments", "chunk_words",
                                             "max_iters", "sr"))
def _closure_segment(base: jax.Array, gather_idx: jax.Array,
                     scatter_idx: jax.Array, *, num_segments: int,
                     chunk_words: int, max_iters: int,
                     sr: Semiring = BOOLEAN):
    """lfp(R = base (+) A⊗R) via packed segment reductions.

    ``sr`` is static: the boolean instantiation traces the exact
    pre-refactor ops (``segment_or_words`` + the ``upd & ~r`` changed-flag
    idiom live inside ``sr.segment_combine``/``sr.accumulate``)."""

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed, it < max_iters)

    def body(state):
        r, _, it = state
        upd = sr.segment_combine(sr.extend(r[gather_idx]), scatter_idx,
                                 num_segments=num_segments,
                                 chunk_words=chunk_words)
        # boolean: the changed flag falls out of the round's own OR
        r, changed = sr.accumulate(r, upd)
        return r, changed, it + 1

    r, _, rounds = jax.lax.while_loop(cond, body,
                                      (base, jnp.bool_(True), jnp.int32(0)))
    return r, rounds


def _edge_rows(edges: tuple[jax.Array, jax.Array, jax.Array],
               x: jax.Array, mode: str) -> jax.Array:
    """Boolean sibling of ``_matmul_rows`` over one class's edge list
    ``(rows, cols, count)`` (``class_edge_lists_np``): ``out[r] |= x[c]``,
    bit-identical to ``_matmul_rows`` on the class's packed matrix."""
    from repro.kernels import ops  # deferred: kernels import repro.core
    rows, cols, count = edges
    return ops.frontier_step_edges(rows, cols, count, x,
                                   n_rows=x.shape[0], mode=mode)


def _matmul_rows(adj: jax.Array, x: jax.Array, mode: str,
                 tiles: tuple[int, int, int] | None = None,
                 sr: Semiring = BOOLEAN) -> jax.Array:
    """``(+)_j adj[i,j] (x) x[j]`` with x's row count padded to adj's bit
    width (the packed adjacency is word-aligned: K = ceil(V/32)*32 >= V;
    pad rows carry no adjacency bits, so the pad value never selects)."""
    from repro.kernels import ops  # deferred: kernels import repro.core
    k = adj.shape[1] * bitset.WORD
    if x.shape[0] < k:
        x = jnp.concatenate(
            [x, jnp.zeros((k - x.shape[0],) + x.shape[1:], x.dtype)], axis=0)
    if sr.packed:
        return ops.frontier_step(adj, x, mode=mode, tiles=tiles)
    return sr.extend(ops.frontier_step_lanes(adj, x, op=sr.op, cap=sr.cap,
                                             mode=mode, tiles=tiles))


@functools.partial(jax.jit, static_argnames=("max_iters", "mode", "sr"))
def _closure_matmul(base: jax.Array, adj: jax.Array, *, max_iters: int,
                    mode: str, sr: Semiring = BOOLEAN):
    """Same fixpoint with rounds routed through the Pallas kernels
    (``bitset_matmul`` for the packed boolean carrier, ``lane_matmul``
    for lane carriers)."""

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed, it < max_iters)

    def body(state):
        r, _, it = state
        upd = _matmul_rows(adj, r, mode, sr=sr)
        # boolean: the changed flag falls out of the round's own OR
        r, changed = sr.accumulate(r, upd)
        return r, changed, it + 1

    r, _, rounds = jax.lax.while_loop(cond, body,
                                      (base, jnp.bool_(True), jnp.int32(0)))
    return r, rounds


@functools.partial(jax.jit, static_argnames=("mode", "max_iters"))
def _closure_blocksparse(base: jax.Array, comp: BlockCompressed, *,
                         mode: str, max_iters: int):
    """Delta-form fixpoint over the block-compressed adjacency.

    Each round expands only the *newly set* rows (``new``): since the lfp
    is unique and OR distributes, ``R ∨ A⊗new`` reaches the same fixpoint
    as ``R ∨ A⊗R`` — and a shrinking frontier means the per-round k-block
    any-bit summary goes dark block by block, which is exactly what the
    kernel's ZERO/dead-block skip turns into saved work."""
    from repro.kernels import ops  # deferred: kernels import repro.core

    def expand(x):  # x row-padding to the block grid happens in the kernel
        return ops.frontier_step_sparse(comp, x, mode=mode)

    def cond(state):
        _, _, changed, it = state
        return jnp.logical_and(changed, it < max_iters)

    def body(state):
        r, new, _, it = state
        nxt = expand(new) & ~r
        return r | nxt, nxt, jnp.any(nxt != 0), it + 1

    r, _, _, rounds = jax.lax.while_loop(
        cond, body, (base, base, jnp.bool_(True), jnp.int32(0)))
    return r, rounds


@functools.partial(jax.jit, static_argnames=("num_segments", "chunk_words",
                                             "max_iters", "max_active"))
def _closure_segment_until_sparse(base: jax.Array, gather_idx: jax.Array,
                                  scatter_idx: jax.Array, *,
                                  num_segments: int, chunk_words: int,
                                  max_iters: int, max_active: int):
    """Dense segment rounds in ONE jitted while_loop, exiting early once
    the frontier (rows with fresh bits) shrinks to ``max_active`` rows.

    The host frontier loop pays a device→host sync every round to learn
    the active set; while the frontier covers most of the graph those
    syncs cost more than the edge work they could save, so this stage
    burns through the high-occupancy rounds sync-free and hands the
    small-frontier tail (``(r, new, rounds)``) to the compacted gathers.
    """

    def cond(state):
        _, _, n_act, it = state
        return jnp.logical_and(n_act > max_active, it < max_iters)

    def body(state):
        r, _, _, it = state
        upd = bitset.segment_or_words(r[gather_idx], scatter_idx,
                                      num_segments=num_segments,
                                      chunk_words=chunk_words)
        new = upd & ~r
        n_act = jnp.sum(jnp.any(new != 0, axis=-1).astype(jnp.int32))
        return r | new, new, n_act, it + 1

    r, new, _, rounds = jax.lax.while_loop(
        cond, body,
        (base, base, jnp.int32(num_segments + 1), jnp.int32(0)))
    return r, new, rounds


@functools.partial(jax.jit, static_argnames=("num_segments", "chunk_words"))
def _sparse_segment_round(x: jax.Array, gather_idx: jax.Array,
                          scatter_idx: jax.Array, *, num_segments: int,
                          chunk_words: int) -> jax.Array:
    """One frontier-compacted semiring round: gather/scatter over the
    *active* edge subset only.  Padding slots gather a zero row (index
    ``V`` of the extended table) and scatter to the dropped out-of-range
    segment, so bucket-padded edge counts keep jit signatures stable."""
    x_ext = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
    return bitset.segment_or_words(x_ext[gather_idx], scatter_idx,
                                   num_segments=num_segments,
                                   chunk_words=chunk_words)


# ------------------------------------------------- mesh-aware entry points
# These run *inside* ``shard_map`` blocks (repro.core.distributed): the
# vertex dimension is 1-D partitioned over the flattened mesh axes, each
# device owns a contiguous block of rows, and the only cross-device traffic
# is the all_gather of the packed uint32 closure words — no ``[V, nbits]``
# boolean plane ever crosses devices.


def all_gather_words(x_local: jax.Array, axis_names) -> jax.Array:
    """Gather shard-local packed rows into the full table ``[V, W]``.

    Gathers the innermost mesh axis first so the flattened ordering matches
    the axis-major shard numbering of a ``P(axis_names)`` leading-dim spec.
    The payload stays packed uint32 end-to-end.
    """
    full = x_local
    for ax in reversed(tuple(axis_names)):
        full = jax.lax.all_gather(full, axis_name=ax, tiled=True)
    return full


def propagate_sharded(x_local: jax.Array, gather_idx: jax.Array,
                      scatter_idx: jax.Array, valid_words: jax.Array,
                      axis_names, *, num_segments: int,
                      chunk_words: int) -> jax.Array:
    """One sharded semiring round ``out[a] = OR_{(a,b)} x[b]`` (packed).

    ``gather_idx`` holds the *global* remote endpoint of each shard-owned
    edge (indexing the all_gathered table), ``scatter_idx`` the shard-local
    owned endpoint, and ``valid_words`` an all-ones/all-zeros uint32 mask
    zeroing the padding slots of the static edge layout.
    """
    full = all_gather_words(x_local, axis_names)
    vals = full[gather_idx] & valid_words
    return bitset.segment_or_words(vals, scatter_idx,
                                   num_segments=num_segments,
                                   chunk_words=chunk_words)


def closure_sharded(base: jax.Array, step, axis_names, *, max_iters: int):
    """lfp(R = base ∨ step(R)) over shard-local rows; returns (R, rounds).

    Same ``upd & ~r`` changed-flag idiom as ``_closure_segment``, but the
    flag is all-reduced over the mesh every round so every device stops at
    the same globally-converged round — callers never guess a round count.
    """

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed, it < max_iters)

    def body(state):
        r, _, it = state
        new = step(r) & ~r
        changed = jax.lax.psum(jnp.any(new != 0).astype(jnp.int32),
                               tuple(axis_names)) > 0
        return r | new, changed, it + 1

    r, _, rounds = jax.lax.while_loop(cond, body,
                                      (base, jnp.bool_(True), jnp.int32(0)))
    return r, rounds


def closure_sharded_delta(base: jax.Array, gather_idx: jax.Array,
                          scatter_idx: jax.Array, valid_words: jax.Array,
                          axis_names, *, per: int, v_pad: int,
                          chunk_words: int, row_budget: int,
                          max_iters: int):
    """Delta-row exchange fixpoint: ship *changed rows*, not the table.

    The row-granular analogue of the two-level compressed planes: each
    device keeps a pending bitmap (level-1 summary — which of its rows
    carry bits the mesh has not seen; an unchanged row is an ALL_ZERO
    delta and never crosses the wire) and per round ships at most
    ``row_budget`` pending rows as a sentinel-padded ``(global id,
    packed payload)`` pair (the level-2 pool).  Receivers scatter the
    shipped rows into a zeroed table and run the ordinary local packed
    OR-reduction, so per-round exchange traffic is
    ``budget × (W + 1)`` words instead of ``per × W``.

    Rows left over when the budget binds stay pending and ship on later
    rounds; a row whose content changes after shipping re-enters the
    bitmap.  Every changed row therefore ships eventually, and because
    the OR fixpoint is monotone with a unique least solution, the result
    is **bit-identical** to ``closure_sharded`` over the dense exchange —
    an overflowing budget costs extra rounds, never bits.  Convergence is
    the all-reduced "any row still pending" flag.

    Returns ``(r_local, rounds)`` like ``closure_sharded``.
    """
    axes = tuple(axis_names)
    budget = min(row_budget, per)
    w = base.shape[1]
    lane = jnp.arange(per, dtype=jnp.int32)
    flat = jnp.int32(0)
    for ax in axes:  # outer-major, matching the P(axes) shard numbering
        flat = flat * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
    row0 = flat * per

    def cond(state):
        _, _, changed, it = state
        return jnp.logical_and(changed, it < max_iters)

    def body(state):
        r, pend, _, it = state
        # first `budget` pending local rows (sentinel `per` pads the tail)
        ship = jax.lax.sort(jnp.where(pend, lane, jnp.int32(per)))[:budget]
        live = ship < per
        r_ext = jnp.concatenate([r, jnp.zeros((1, w), r.dtype)])
        payload = r_ext[ship]                       # sentinel -> zero row
        gid = jnp.where(live, ship + row0, jnp.int32(v_pad))
        gids = all_gather_words(gid, axes)          # [S*B]
        pays = all_gather_words(payload, axes)      # [S*B, W]
        # real global ids are distinct within a round (each row ships only
        # from its owner); sentinel slots all write the zero row
        tbl = jnp.zeros((v_pad + 1, w), r.dtype).at[gids].set(pays)[:v_pad]
        upd = bitset.segment_or_words(
            tbl[gather_idx] & valid_words, scatter_idx,
            num_segments=per, chunk_words=chunk_words)
        new = upd & ~r
        shipped = jnp.zeros(per + 1, bool).at[ship].set(True)[:per]
        pend = (pend & ~shipped) | jnp.any(new != 0, axis=1)
        changed = jax.lax.psum(jnp.any(pend).astype(jnp.int32), axes) > 0
        return r | new, pend, changed, it + 1

    pend0 = jnp.any(base != 0, axis=1)
    r, _, _, rounds = jax.lax.while_loop(
        cond, body, (base, pend0, jnp.bool_(True), jnp.int32(0)))
    return r, rounds


# ------------------------------------------------------------------ engine
class Engine:
    """OR-semiring propagation over one graph, packed words in/out.

    Holds the device-resident edge lists and (for the ``pallas`` backend)
    the packed adjacency bit-matrices, so repeated build/query calls reuse
    the same operands and jit caches.
    """

    def __init__(self, graph: Graph, config: EngineConfig = EngineConfig()):
        backend = resolve_backend(config.backend)
        kw = bitset.n_words(graph.n_vertices)
        dense_bytes = graph.n_vertices * kw * 4
        if backend == "pallas" and dense_bytes > config.max_dense_bytes:
            warnings.warn(
                f"engine: dense adjacency needs {dense_bytes/1e6:.0f} MB "
                f"(> max_dense_bytes={config.max_dense_bytes/1e6:.0f} MB); "
                "falling back to the segment backend", stacklevel=2)
            backend = "segment"
        self.graph = graph
        self.config = config
        self.backend = backend
        self.interpret = (jax.default_backend() != "tpu"
                          if config.interpret is None else config.interpret)
        self.edge_src = jnp.asarray(graph.src)
        self.edge_dst = jnp.asarray(graph.indices)
        self._adj: dict[bool, jax.Array] = {}
        self._bcomp: dict[bool, BlockCompressed] = {}
        self._label_adj: dict[tuple, jax.Array] = {}
        self._label_edges: dict[tuple, tuple] = {}
        self._rev_graph: Graph | None = None

    # ------------------------------------------------------------ operands
    @property
    def matmul_mode(self) -> str:
        """kernels.ops mode implementing this engine's matmul calls."""
        return "interpret" if self.interpret else "pallas"

    @property
    def kernel_mode(self) -> str:
        """kernels.ops mode for auxiliary fused kernels (way_filter &c.)."""
        return self.matmul_mode if self.backend == "pallas" else "ref"

    # distinct special-label sets whose class matrices stay resident; the
    # per-set footprint is (C+1) dense adjacencies, so the cache is a small
    # LRU rather than unbounded under varied query traffic
    LABEL_ADJ_CACHE = 4

    def can_pack_dense(self, n_matrices: int = 1) -> bool:
        """Would ``n_matrices`` dense adjacency bit-matrices fit the cap?"""
        kw = bitset.n_words(self.graph.n_vertices)
        return (n_matrices * self.graph.n_vertices * kw * 4
                <= self.config.max_dense_bytes)

    def adjacency(self, *, reverse: bool = False) -> jax.Array:
        """Cached packed adjacency bit-matrix ``[V, ceil(V/32)]``."""
        if reverse not in self._adj:
            self._adj[reverse] = jnp.asarray(
                pack_adjacency_np(self.graph, reverse=reverse))
        return self._adj[reverse]

    def block_adjacency(self, *, reverse: bool = False) -> BlockCompressed:
        """Cached block-compressed adjacency (the sparse-closure operand).

        ZERO blocks cost 2 bits, so for the sparse graphs the paper
        targets this is E-proportional storage where the dense bit-matrix
        is V²-proportional — it is what lifts the closure operand past
        ``max_dense_bytes``-scale vertex counts."""
        if reverse not in self._bcomp:
            self._bcomp[reverse] = compress_blocks(
                pack_adjacency_np(self.graph, reverse=reverse),
                br=self.config.block_rows, bw=self.config.block_words,
                nbits=self.graph.n_vertices)
        return self._bcomp[reverse]

    def label_class_adjacency(self, special_labels, *,
                              reverse: bool = True) -> jax.Array:
        """Per-label-class adjacency ``[C+1, V, Kw]`` (LRU-cached).

        ``reverse=True`` (bit j of row i == edge j→i) drives forward
        frontier expansion; ``reverse=False`` drives the backward frontier
        of the bidirectional executor."""
        labels = tuple(sorted(set(int(l) for l in special_labels)))
        return self._lru(self._label_adj, (labels, reverse),
                         lambda: jnp.asarray(pack_label_class_adjacency_np(
                             self.graph, labels, reverse=reverse)))

    def label_class_edges(self, special_labels, *, reverse: bool = True
                          ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Per-label-class edge lists ``(rows, cols, count)`` on device
        (``class_edge_lists_np`` over the whole graph; LRU-cached like
        ``label_class_adjacency``, whose ``reverse`` meaning it keeps)."""
        labels = tuple(sorted(set(int(l) for l in special_labels)))
        g = self.graph
        return self._lru(self._label_edges, (labels, reverse),
                         lambda: tuple(jnp.asarray(a) for a in
                                       class_edge_lists_np(
                                           g.src, g.indices, g.labels,
                                           labels, reverse=reverse)))

    def _lru(self, cache: dict, key, build):
        """``cache[key]``, built on a miss; at most ``LABEL_ADJ_CACHE``
        entries, the least recently used dropped first."""
        if key in cache:
            cache[key] = cache.pop(key)  # refresh LRU
        else:
            while len(cache) >= self.LABEL_ADJ_CACHE:
                cache.pop(next(iter(cache)))
            cache[key] = build()
        return cache[key]

    # ---------------------------------------------------------- primitives
    def segment_or(self, values: jax.Array, segment_ids: jax.Array,
                   num_segments: int) -> jax.Array:
        """OR-reduce packed rows by arbitrary segment ids (projections)."""
        return bitset.segment_or_words(values, segment_ids,
                                       num_segments=num_segments,
                                       chunk_words=self.config.chunk_words)

    def propagate(self, x: jax.Array, *, reverse: bool = False,
                  sr: Semiring = BOOLEAN) -> jax.Array:
        """One semiring round: ``out[a] = (+)_{(a,b)} extend(x[b])``.

        ``sr=BOOLEAN`` (default) is the packed OR round of PR 1-7,
        bit-identical to the pre-refactor engine; min-plus/count carriers
        run one lane per column of ``x``."""
        if self.backend == "pallas":
            return _matmul_rows(self.adjacency(reverse=reverse), x,
                                self.matmul_mode, sr=sr)
        gather = self.edge_dst if not reverse else self.edge_src
        scatter = self.edge_src if not reverse else self.edge_dst
        if sr.packed:
            return self.segment_or(x[gather], scatter, self.graph.n_vertices)
        return sr.segment_combine(sr.extend(x[gather]), scatter,
                                  num_segments=self.graph.n_vertices)

    def closure(self, base: jax.Array, *, reverse: bool = False,
                max_iters: int | None = None,
                sparse: bool | None = None,
                sr: Semiring = BOOLEAN) -> tuple[jax.Array, int]:
        """Least fixpoint ``R = base (+) propagate(R)``; returns (R, rounds).

        ``base`` is packed uint32 ``[V, W]``.  The lfp is unique, so any
        seed between the true base and the fixpoint converges to the same
        bits — incremental maintenance (``tdr_build.update_index``) leans
        on this by re-entering the closure from the *previous* converged
        state plus a delta, which typically terminates in 1-2 rounds
        instead of a diameter's worth.

        ``sparse`` routes the fixpoint through the block-sparse path: the
        block-compressed adjacency and delta-frontier rounds on
        ``pallas``, frontier-compacted edge gathers on ``segment``.  Both
        are bit-identical to the dense fixpoint — sparsity only changes
        which work is skipped.  The default (``None`` +
        ``EngineConfig.sparse``) engages it only where skipping pays:
        always on ``segment``, and on ``pallas`` only under the real TPU
        lowering — in interpret mode the per-grid-step Python dispatch
        dwarfs any skipped block, so the dense kernel is faster there
        (pass ``sparse=True`` to force the block-sparse path anyway,
        e.g. for equivalence tests).

        ``sr`` selects the semiring.  Fixpoints need an idempotent (+)
        (the convergence predicate compares successive planes), so the
        COUNT carrier is refused — route counting is a *bounded* DP in
        ``tdr_query.count_routes``.  Non-packed carriers always run the
        dense cores (the frontier/block-sparse machinery is specific to
        the packed boolean layout)."""
        max_iters = max_iters or self.graph.n_vertices
        if not sr.idempotent:
            raise ValueError(
                f"closure needs an idempotent semiring, got {sr.name}; "
                "use a bounded DP (tdr_query.count_routes) instead")
        if sparse is None:
            sparse = self.config.sparse and (
                self.backend == "segment" or not self.interpret)
        if not sr.packed:
            if self.backend == "pallas":
                return _closure_matmul(base, self.adjacency(reverse=reverse),
                                       max_iters=max_iters,
                                       mode=self.matmul_mode, sr=sr)
            gather = self.edge_dst if not reverse else self.edge_src
            scatter = self.edge_src if not reverse else self.edge_dst
            return _closure_segment(base, gather, scatter,
                                    num_segments=self.graph.n_vertices,
                                    chunk_words=self.config.chunk_words,
                                    max_iters=max_iters, sr=sr)
        if self.backend == "pallas":
            if sparse:
                return _closure_blocksparse(
                    base, self.block_adjacency(reverse=reverse),
                    mode=self.matmul_mode, max_iters=max_iters)
            return _closure_matmul(base, self.adjacency(reverse=reverse),
                                   max_iters=max_iters,
                                   mode=self.matmul_mode)
        if sparse:
            return self._closure_segment_frontier(base, reverse=reverse,
                                                  max_iters=max_iters)
        gather = self.edge_dst if not reverse else self.edge_src
        scatter = self.edge_src if not reverse else self.edge_dst
        return _closure_segment(base, gather, scatter,
                                num_segments=self.graph.n_vertices,
                                chunk_words=self.config.chunk_words,
                                max_iters=max_iters)

    def _gather_csr(self, reverse: bool) -> Graph:
        """CSR grouped by each round's *gather* endpoint: forward
        propagation gathers ``x[dst]``, so its edge subsets come from the
        edge-reversed CSR (and vice versa)."""
        if reverse:
            return self.graph
        if self._rev_graph is None:
            self._rev_graph = self.graph.reverse()
        return self._rev_graph

    def _closure_segment_frontier(self, base: jax.Array, *, reverse: bool,
                                  max_iters: int) -> tuple[jax.Array, int]:
        """Host-driven delta fixpoint for the segment backend: each round
        gathers only edges incident to the still-active frontier rows
        (bucket-padded so the jit-shape count stays logarithmic), falling
        back to a full dense round while the frontier covers more than
        ``sparse_dense_frac`` of the vertices."""
        v = self.graph.n_vertices
        g = self._gather_csr(reverse)
        thresh = int(self.config.sparse_dense_frac * v)
        gather = self.edge_dst if not reverse else self.edge_src
        scatter = self.edge_src if not reverse else self.edge_dst
        # stage 1: high-occupancy rounds run dense inside one jitted loop
        # (no per-round host sync); it exits when the frontier thins out
        r, new, rounds_d = _closure_segment_until_sparse(
            jnp.asarray(base), gather, scatter, num_segments=v,
            chunk_words=self.config.chunk_words, max_iters=max_iters,
            max_active=thresh)
        rounds = int(rounds_d)
        # stage 2: small-frontier tail — compacted edge gathers, one
        # device→host sync per round to learn the active set
        while rounds < max_iters:
            act = np.flatnonzero(np.asarray(jnp.any(new != 0, axis=-1)))
            if act.size == 0:
                break
            rounds += 1
            if act.size > thresh:
                # the frontier can re-widen (a hub lighting up its whole
                # out-neighbourhood); fall back to a dense round
                upd = self.propagate(new, reverse=reverse)
            else:
                counts = (g.indptr[act + 1] - g.indptr[act]).astype(np.int64)
                gat = np.repeat(act.astype(np.int64), counts)
                scat = g.indices[csr_row_edges(g.indptr, act)].astype(
                    np.int64)
                b = pad_bucket(max(gat.size, 1), lo=32)
                gat_p = np.full(b, v, dtype=np.int64)
                gat_p[:gat.size] = gat
                scat_p = np.full(b, v, dtype=np.int64)  # dropped segment
                scat_p[:scat.size] = scat
                upd = _sparse_segment_round(
                    new, jnp.asarray(gat_p), jnp.asarray(scat_p),
                    num_segments=v, chunk_words=self.config.chunk_words)
            nxt = upd & ~r
            r = r | nxt
            new = nxt
        return r, rounds

    # ------------------------------------------------------------- updates
    def apply_delta(self, graph: Graph, added: np.ndarray,
                    removed: np.ndarray) -> "Engine":
        """New engine over the post-update ``graph`` (same vertex set),
        reusing this engine's resolved backend/config.

        Any cached dense adjacency bit-matrix is *patched*, not repacked:
        only the rows whose edge set changed (sources for the forward
        matrix, destinations for the reverse one) are re-derived from the
        new CSR and scattered in on device — O(|touched rows|) transfer
        instead of O(V·V/8).  Label-class adjacency and edge-list caches
        are dropped (they rebuild lazily on the next query batch)."""
        if graph.n_vertices != self.graph.n_vertices:
            raise ValueError("apply_delta requires a fixed vertex set")
        new = object.__new__(Engine)
        new.graph = graph
        new.config = self.config
        new.backend = self.backend
        new.interpret = self.interpret
        new.edge_src = jnp.asarray(graph.src)
        new.edge_dst = jnp.asarray(graph.indices)
        new._adj = {}
        new._bcomp = {}
        new._label_adj = {}
        new._label_edges = {}
        new._rev_graph = None
        rev_csr = None

        def touched_rows(reverse: bool) -> np.ndarray:
            col = 1 if reverse else 0
            return np.unique(np.concatenate(
                [added[:, col], removed[:, col]])).astype(np.int64)

        def patched_row_bits(reverse: bool, rows: np.ndarray,
                             kw: int) -> np.ndarray:
            nonlocal rev_csr
            if reverse and rev_csr is None:
                rev_csr = graph.reverse()
            g = rev_csr if reverse else graph
            counts = (g.indptr[rows + 1] - g.indptr[rows]).astype(np.int64)
            pos = np.repeat(np.arange(rows.shape[0]), counts)
            eidx = csr_row_edges(g.indptr, rows)
            rowbits = np.zeros((rows.shape[0], kw), dtype=np.uint32)
            bitset.set_bits_np(rowbits, (pos,), g.indices[eidx])
            return rowbits

        for reverse, adj in self._adj.items():
            rows = touched_rows(reverse)
            if rows.size == 0:
                new._adj[reverse] = adj
                continue
            rowbits = patched_row_bits(reverse, rows, adj.shape[1])
            new._adj[reverse] = adj.at[jnp.asarray(rows)].set(
                jnp.asarray(rowbits))
        for reverse, comp in self._bcomp.items():
            rows = touched_rows(reverse)
            if rows.size == 0:
                new._bcomp[reverse] = comp
                continue
            rowbits = patched_row_bits(reverse, rows, comp.shape[1])
            new._bcomp[reverse] = patch_blocks(comp, rows, rowbits)
        return new


def jit_cache_entries() -> int:
    """Total compiled-variant count across the packed-word hot path.

    Sums the jit caches of every jitted entry point in the engine, the
    query planner/executor, the bitset primitives, and the kernel surface.
    The serving benchmark snapshots this after warmup and asserts a zero
    delta over the measurement window — steady-state traffic on the
    bucket grid must never recompile.
    """
    import sys

    from repro.core import bitset as bitset_mod, tdr_query
    from repro.kernels import (bitset_matmul, block_sparse, ops,
                               pattern_filter, popcount)
    total = 0
    for mod in (sys.modules[__name__], bitset_mod, tdr_query, ops,
                bitset_matmul, block_sparse, pattern_filter, popcount):
        for obj in vars(mod).values():
            size = getattr(obj, "_cache_size", None)
            if callable(size):
                total += int(size())
    return total


def make_engine(graph: Graph, backend: str | None = None,
                config: EngineConfig | None = None) -> Engine:
    """Engine factory: ``backend`` shorthand overrides ``config.backend``."""
    cfg = config or EngineConfig()
    if backend is not None:
        cfg = dataclasses.replace(cfg, backend=backend)
    return Engine(graph, cfg)
