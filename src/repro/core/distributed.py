"""Distributed TDR on the packed-word engine: sharded build + query.

Scaling posture (the multi-pod story for the paper's engine):

* The vertex set is 1-D partitioned over every device of the mesh (the
  flattened axes, contiguous blocks of ``ceil(V/n)`` rows per device).
  Each device owns the index rows of its vertex shard plus the out-edges
  of its shard (for forward propagation and the per-way projections) and
  the in-edges of its shard (for the reverse closure).  The adjacency
  never moves.
* One fixpoint round = ``all_gather`` of the **packed uint32 closure
  words** (``V × W`` words — 32× fewer gather bytes than the retired
  bool-plane exchange) followed by a purely local packed OR-reduction for
  owned vertices (``bitset.segment_or_words``).  On a 512-chip mesh with
  V=10M and 256-bit Blooms that is 320 MB per round over ICI — a few ms —
  against an embarrassingly parallel local update.
* Convergence is a ``changed`` flag derived from the round's own new bits
  (``upd & ~r``) and all-reduced over the mesh every round
  (``engine.closure_sharded``) — every device stops at the same globally
  converged round; callers never guess a round count.
* ``build_index(graph, cfg, mesh=...)`` shards **all** of Alg. 1 this
  way — forward/reverse closures, vertical k-level propagation, and the
  per-way projections — and is bit-identical to the single-device
  ``tdr_build.build_index`` (the OR fixpoint has a unique least solution
  and every reduction is exact bitwise OR).
* ``answer_batch(index, queries, mesh=...)`` broadcasts the compiled
  ``QueryPlan``, runs the phase-1 filter cascade with the job axis
  sharded over the mesh, and round-robins *compacted* phase-2 expansion
  chunks across the mesh's devices (their operands are per-chunk host
  data that transfers anyway; dispatch is async, so devices expand
  concurrently — full-graph chunks stay with the V-sized shared
  operands on the lead device).

The same code runs on 1 CPU device in tests, on the 8-fake-device mesh in
``tests/multidevice_check.py``, and on the 512-way fake-device mesh in the
dry-run (``repro/launch/dryrun.py --arch tdr-graph``).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import bitset
from . import engine as engine_mod
from . import tdr_build as build_mod
from . import tdr_query as query_mod
from .graph import Graph


def _pad_to(x: np.ndarray, n: int, axis: int = 0, fill=0) -> np.ndarray:
    pad = n - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return np.pad(x, widths, constant_values=fill)


@dataclasses.dataclass(frozen=True)
class ShardEdges:
    """Dense per-shard edge layout (static shapes for any mesh).

    ``local`` is the shard-owned endpoint as a shard-local row id,
    ``remote`` the other endpoint as a *global* id (it indexes the
    all_gathered closure table), ``eidx`` the global edge id (aligning
    per-edge payloads such as label planes and way ids to the shard
    layout), and ``valid`` masks the padding slots.
    """
    local: np.ndarray    # int32 [S, e_max]
    remote: np.ndarray   # int32 [S, e_max]
    eidx: np.ndarray     # int32 [S, e_max]
    valid: np.ndarray    # bool  [S, e_max]


def partition_graph(graph: Graph, n_shards: int, *,
                    by: str = "src") -> tuple[int, ShardEdges]:
    """Pad V to a multiple of shards; group edges by the owning endpoint.

    ``by="src"`` assigns each edge to the shard owning its source (forward
    propagation / projections); ``by="dst"`` to the shard owning its
    destination (reverse propagation).  Returns ``(v_pad, ShardEdges)``.
    """
    if by not in ("src", "dst"):
        raise ValueError(f"partition_graph: by={by!r}")
    v_pad = -(-graph.n_vertices // n_shards) * n_shards
    per = v_pad // n_shards
    src, dst = graph.src, graph.indices
    own, other = (src, dst) if by == "src" else (dst, src)
    shard_of = own // per
    e_max = int(max(1, np.bincount(shard_of, minlength=n_shards).max()))
    local = np.zeros((n_shards, e_max), dtype=np.int32)
    remote = np.zeros((n_shards, e_max), dtype=np.int32)
    eidx = np.zeros((n_shards, e_max), dtype=np.int32)
    valid = np.zeros((n_shards, e_max), dtype=bool)
    for s in range(n_shards):
        ids = np.flatnonzero(shard_of == s)
        k = ids.shape[0]
        local[s, :k] = own[ids] - s * per
        remote[s, :k] = other[ids]
        eidx[s, :k] = ids
        valid[s, :k] = True
    return v_pad, ShardEdges(local, remote, eidx, valid)


def _put(mesh: Mesh, spec: P, *arrays):
    sh = NamedSharding(mesh, spec)
    out = tuple(jax.device_put(a, sh) for a in arrays)
    return out if len(out) > 1 else out[0]


# ------------------------------------------------------------ closure only
def distributed_closure(graph: Graph, seed_words: np.ndarray, mesh: Mesh,
                        *, max_iters: int | None = None,
                        chunk_words: int = 2,
                        row_budget: int | None = None) -> jax.Array:
    """Reachability-closure fixpoint, vertex-sharded over ``mesh``.

    ``seed_words`` is the packed uint32 ``[V, W]`` per-vertex hash
    pattern; the result is the packed closure with semantics **identical
    to the single-device ``tdr_build`` fixpoint**:

        R[u] = OR_{u →+ v} seed[v]

    i.e. the vertex's own seed bits are *not* included unless ``u`` lies
    on a cycle (``tdr_build`` ORs ``vtx_w`` into ``n_out`` separately).
    Convergence comes from the all-reduced changed flag — no caller-
    guessed round count — and the per-round exchange payload is the
    packed word table, never a bool plane.

    ``row_budget`` switches the exchange to the delta-row scheme
    (``engine.closure_sharded_delta``): per round each device ships at
    most that many *changed* rows as sentinel-padded ``(id, payload)``
    pairs — the row-granular analogue of the two-level compressed
    planes — instead of all-gathering its full word block.  The result
    is bit-identical for any budget ≥ 1 (the OR fixpoint has a unique
    least solution; an overflowing budget only adds rounds).
    """
    seed_words = np.asarray(seed_words)
    if seed_words.dtype != np.uint32:
        raise TypeError(
            "distributed_closure takes packed uint32 seed words "
            f"(got {seed_words.dtype}); pack bool planes with "
            "bitset.pack_bits_np first")
    n_shards = mesh.devices.size
    axes = tuple(mesh.axis_names)
    v_pad, ed = partition_graph(graph, n_shards, by="src")
    per = v_pad // n_shards
    w = seed_words.shape[1]
    rows = _pad_to(seed_words, v_pad).reshape(n_shards, per, w)
    iters = max_iters or v_pad
    spec = P(axes)

    # check_vma=False: the varying-manual-axes checker is off for the
    # converged while_loop (the psum'd changed flag is replicated by
    # construction — every device sees the same reduction).  jit: an
    # eager shard_map dispatches op by op on every device.
    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, check_vma=False,
                       in_specs=(spec, spec, spec, spec), out_specs=spec)
    def run(rows_s, local_s, remote_s, valid_s):
        rows_l = rows_s[0]
        loc, rem = local_s[0], remote_s[0]
        okw = bitset.full_words_where(valid_s[0])[:, None]

        def step(r):
            return engine_mod.propagate_sharded(
                r, rem, loc, okw, axes, num_segments=per,
                chunk_words=chunk_words)

        base = step(rows_l)  # successor seeds: self excluded, as in build
        if row_budget is not None:
            # a binding budget trades rounds for traffic: scale the
            # dense-round bound by the worst-case per-device backlog
            backlog = -(-per // max(1, min(row_budget, per)))
            r, _ = engine_mod.closure_sharded_delta(
                base, rem, loc, okw, axes, per=per, v_pad=v_pad,
                chunk_words=chunk_words, row_budget=row_budget,
                max_iters=iters * backlog)
        else:
            r, _ = engine_mod.closure_sharded(base, step, axes,
                                              max_iters=iters)
        return r[None]

    out = run(_put(mesh, spec, rows),
              *_put(mesh, spec, ed.local, ed.remote, ed.valid))
    return jnp.asarray(np.asarray(out).reshape(v_pad, w)
                       [:graph.n_vertices])


# ------------------------------------------------------------ index build
def build_index(graph: Graph, cfg: "build_mod.TDRConfig | None" = None, *,
                mesh: Mesh, chunk_words: int | None = None
                ) -> "build_mod.TDRIndex":
    """Vertex-sharded construction of the full TDR index (Alg. 1).

    Host precompute (DFS intervals, hash rows, label slots, way routing)
    is identical to the single-device path; every device-side fixpoint and
    projection is sharded over ``mesh`` with the packed-word exchange
    described in the module docstring.  The result is bit-identical to
    ``tdr_build.build_index(graph, cfg)`` on all index planes.
    """
    cfg = cfg or build_mod.TDRConfig()
    v_n = graph.n_vertices
    push, pop, disc = build_mod.dfs_intervals(graph)
    vtx_words_np = build_mod._vertex_bit_words(cfg, disc)      # [V, Wv]
    lab_slot = build_mod._label_slots(cfg, graph.n_labels)
    g_count, way = build_mod.way_assignment(cfg, graph, disc)
    lab_words = build_mod._edge_label_words(cfg, lab_slot, graph.labels)
    null_w = build_mod._null_words(cfg)                        # [Wl]

    n_shards = mesh.devices.size
    axes = tuple(mesh.axis_names)
    v_pad, fwd = partition_graph(graph, n_shards, by="src")
    _, rev = partition_graph(graph, n_shards, by="dst")
    per = v_pad // n_shards
    gmax = cfg.g_max
    cw = chunk_words or max(1, cfg.bit_chunk // bitset.WORD)
    iters = cfg.max_fixpoint_iters or v_n
    wv, wl = vtx_words_np.shape[1], lab_words.shape[1]

    # per-edge payloads aligned to the forward shard layout (zeroed pads;
    # an edgeless graph has nothing to gather — every slot is padding)
    if graph.n_edges:
        labw_f = np.where(fwd.valid[:, :, None], lab_words[fwd.eidx],
                          np.uint32(0)).astype(np.uint32)
        way_f = np.where(fwd.valid, way[fwd.eidx], 0).astype(np.int32)
    else:
        labw_f = np.zeros(fwd.eidx.shape + (wl,), dtype=np.uint32)
        way_f = np.zeros(fwd.eidx.shape, dtype=np.int32)

    rows = _pad_to(vtx_words_np, v_pad).reshape(n_shards, per, wv)
    leaf = _pad_to(graph.out_degree() == 0, v_pad).reshape(n_shards, per)
    g_sh = _pad_to(g_count, v_pad).reshape(n_shards, per)
    spec = P(axes)
    null_j = jnp.asarray(null_w)

    # check_vma=False and jit: see distributed_closure
    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, check_vma=False,
                       in_specs=(spec,) * 11, out_specs=(spec,) * 7)
    def run(rows_s, leaf_s, g_s, floc_s, frem_s, fok_s, flab_s, fway_s,
            rloc_s, rrem_s, rok_s):
        vtx_l = rows_s[0]                       # [per, Wv]
        leaf_l, g_l = leaf_s[0], g_s[0]
        f_loc, f_rem = floc_s[0], frem_s[0]
        labw, way_l = flab_s[0], fway_s[0]
        r_loc, r_rem = rloc_s[0], rrem_s[0]
        fokw = bitset.full_words_where(fok_s[0])[:, None]
        rokw = bitset.full_words_where(rok_s[0])[:, None]

        def prop_f(x):
            return engine_mod.propagate_sharded(
                x, f_rem, f_loc, fokw, axes, num_segments=per,
                chunk_words=cw)

        def prop_r(x):
            return engine_mod.propagate_sharded(
                x, r_rem, r_loc, rokw, axes, num_segments=per,
                chunk_words=cw)

        # ---- forward vertex closure  R[u] = OR (bit(v) | R[v]) ----------
        base_v = prop_f(vtx_l)
        r_vtx, rounds = engine_mod.closure_sharded(base_v, prop_f, axes,
                                                   max_iters=iters)
        # ---- forward label closure --------------------------------------
        base_l = bitset.segment_or_words(labw, f_loc, num_segments=per,
                                         chunk_words=cw)
        r_lab, _ = engine_mod.closure_sharded(base_l, prop_f, axes,
                                              max_iters=iters)
        # ---- reverse closure for N_in -----------------------------------
        base_r = prop_r(vtx_l)
        n_in, _ = engine_mod.closure_sharded(base_r, prop_r, axes,
                                             max_iters=iters)

        # ---- vertical levels (exact k-round propagation) ----------------
        cur_lab = jnp.where(leaf_l[:, None], null_j[None, :], base_l)
        cur_vtx = base_v
        d_lab, d_vtx = [cur_lab], [cur_vtx]
        for _ in range(1, cfg.k):
            nxt_lab = jnp.where(leaf_l[:, None], null_j[None, :],
                                prop_f(cur_lab))
            nxt_vtx = jnp.where(leaf_l[:, None], jnp.uint32(0),
                                prop_f(cur_vtx))
            d_lab.append(nxt_lab)
            d_vtx.append(nxt_vtx)
            cur_lab, cur_vtx = nxt_lab, nxt_vtx

        # ---- per-way projections (packed-word gathers + segment ORs) ----
        full_vtx = engine_mod.all_gather_words(vtx_l, axes)
        full_rvtx = engine_mod.all_gather_words(r_vtx, axes)
        full_rlab = engine_mod.all_gather_words(r_lab, axes)
        seg = f_loc * gmax + way_l
        n_seg = per * gmax

        def proj(vals):
            return bitset.segment_or_words(vals & fokw, seg,
                                           num_segments=n_seg,
                                           chunk_words=cw)

        h_vtx = proj(full_vtx[f_rem] | full_rvtx[f_rem])
        h_lab = proj(labw | full_rlab[f_rem])
        v_lab_lv = [proj(labw)]
        v_vtx_lv = [proj(full_vtx[f_rem])]
        for l in range(1, cfg.k):
            v_lab_lv.append(proj(engine_mod.all_gather_words(
                d_lab[l - 1], axes)[f_rem]))
            v_vtx_lv.append(proj(engine_mod.all_gather_words(
                d_vtx[l - 1], axes)[f_rem]))

        h_vtx = h_vtx.reshape(per, gmax, wv)
        h_lab = h_lab.reshape(per, gmax, wl)
        v_lab_p = jnp.stack(v_lab_lv, axis=1).reshape(per, gmax, cfg.k, wl)
        v_vtx_p = jnp.stack(v_vtx_lv, axis=1).reshape(per, gmax, cfg.k, wv)

        # the vertex hashes itself into each *used* way (Alg. 1 line 10)
        way_used = jnp.arange(gmax)[None, :] < g_l[:, None]
        h_vtx = h_vtx | jnp.where(way_used[:, :, None], vtx_l[:, None, :],
                                  jnp.uint32(0))
        n_out = bitset.or_reduce(h_vtx, axis=1) if gmax > 0 else r_vtx
        return (h_vtx[None], h_lab[None], v_vtx_p[None], v_lab_p[None],
                (n_out | vtx_l)[None], (n_in | vtx_l)[None],
                rounds.reshape(1))

    outs = run(*_put(mesh, spec, rows, leaf, g_sh, fwd.local, fwd.remote,
                     fwd.valid, labw_f, way_f, rev.local, rev.remote,
                     rev.valid))
    h_vtx, h_lab, v_vtx, v_lab, n_out, n_in, rounds = (
        np.asarray(o) for o in outs)
    idx = build_mod.TDRIndex(
        cfg=cfg, graph=graph,
        h_vtx=jnp.asarray(h_vtx.reshape(v_pad, gmax, wv)[:v_n]),
        h_lab=jnp.asarray(h_lab.reshape(v_pad, gmax, wl)[:v_n]),
        v_vtx=jnp.asarray(v_vtx.reshape(v_pad, gmax, cfg.k, wv)[:v_n]),
        v_lab=jnp.asarray(v_lab.reshape(v_pad, gmax, cfg.k, wl)[:v_n]),
        n_out=jnp.asarray(n_out.reshape(v_pad, wv)[:v_n]),
        n_in=jnp.asarray(n_in.reshape(v_pad, wv)[:v_n]),
        push=jnp.asarray(push), pop=jnp.asarray(pop),
        g_count=jnp.asarray(g_count),
        vtx_words=vtx_words_np, lab_slot=lab_slot,
        fixpoint_rounds=int(rounds.max()),
        # pin the hash layout so tdr_build.update_index on a
        # distributed-built index can fall back to a layout-pinned
        # rebuild (the sharded build keeps no raw closure planes)
        disc=disc,
    )
    return idx


# -------------------------------------------------------- query answering
def filter_cascade_sharded(index: "build_mod.TDRIndex",
                           plan: "query_mod.QueryPlan", mesh: Mesh,
                           mode: str) -> np.ndarray:
    """Phase-1 filter cascade with the job axis sharded over ``mesh``.

    The (small) plan rows are the only job-axis traffic; the index planes
    are broadcast once.  Each device runs the vectorized cascade for its
    job shard; the verdicts concatenate back — no collectives needed.
    ``plan.n_jobs`` must be a multiple of the mesh size (pad with
    ``QueryPlan.pad_to``).
    """
    axes = tuple(mesh.axis_names)
    n_dev = mesh.devices.size
    if plan.n_jobs % n_dev:
        raise ValueError(
            f"job axis {plan.n_jobs} not divisible by mesh size {n_dev}")
    spec_j = P(axes)
    k = index.cfg.k

    # check_vma=False: the varying-manual-axes checker has no rule for
    # the pallas_call the cascade's fused way filter lowers to
    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh, check_vma=False,
        in_specs=(spec_j,) * 4 + (P(),) * 12, out_specs=spec_j)
    def run(u, v, req_w, forb_w, null_w, vtx_packed, h_vtx, h_lab, v_vtx,
            v_lab, n_out, n_in, sat_out, sat_in, push, pop):
        return query_mod._filter_cascade(
            u, v, req_w, forb_w, null_w, vtx_packed, h_vtx, h_lab, v_vtx,
            v_lab, n_out, n_in, sat_out, sat_in, push, pop, k=k, mode=mode)

    job_args = _put(mesh, spec_j, plan.u.astype(np.int32),
                    plan.v.astype(np.int32), plan.req_w, plan.forb_w)
    # the index planes replicate once per mesh, not once per batch
    key = (tuple(mesh.axis_names),
           tuple(int(d.id) for d in mesh.devices.flat))
    bcast = index._replicated.get(key)
    if bcast is None:
        sat_out_d, sat_in_d = index.summary_flags_dev()
        bcast = _put(mesh, P(), query_mod._null_words_dev(index.cfg),
                     index.vtx_packed, index.h_vtx, index.h_lab,
                     index.v_vtx, index.v_lab, index.n_out, index.n_in,
                     sat_out_d, sat_in_d, index.push, index.pop)
        index._replicated[key] = bcast
    return np.asarray(run(*job_args, *bcast))


def answer_batch(index: "build_mod.TDRIndex", queries, *, mesh: Mesh,
                 **kw) -> np.ndarray:
    """Distributed PCR answering: ``tdr_query.answer_batch`` with the
    phase-1 cascade job-sharded over ``mesh`` and compacted phase-2
    chunks round-robined across its devices."""
    return query_mod.answer_batch(index, queries, mesh=mesh, **kw)


# ------------------------------------------------- shape-only lowerings
def lower_distributed_closure(mesh: Mesh, v_global: int, e_max: int,
                              nbits: int, rounds: int, chunk: int = 64):
    """Shape-only lowering of the distributed fixpoint (for the dry-run).

    Returns the lowered computation for ``.compile()`` — proving the
    sharding/collective schedule is coherent on the production mesh
    without allocating the graph.  The per-round exchange is the packed
    uint32 word table (``all_gather`` of ``[per, W]`` uint32 blocks).
    Unlike the runtime paths, the round count here is *static* (a
    ``fori_loop``) so the dry-run's loop-aware HLO cost accounting sees a
    fixed trip count; ``distributed_closure``/``build_index`` converge via
    the all-reduced changed flag instead.
    """
    n_shards = mesh.devices.size
    axes = tuple(mesh.axis_names)
    per = -(-v_global // n_shards)
    words = bitset.n_words(nbits)
    cw = max(1, chunk // bitset.WORD)
    spec = P(axes)
    sharding = NamedSharding(mesh, spec)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(spec, spec, spec, spec), out_specs=spec)
    def run(rows_s, local_s, remote_s, valid_s):
        rows_l = rows_s[0]
        loc, rem = local_s[0], remote_s[0]
        okw = bitset.full_words_where(valid_s[0])[:, None]

        def step(r):
            return engine_mod.propagate_sharded(
                r, rem, loc, okw, axes, num_segments=per, chunk_words=cw)

        def body(_, r):
            return r | step(r)

        return jax.lax.fori_loop(0, rounds, body, step(rows_l))[None]

    args = (
        jax.ShapeDtypeStruct((n_shards, per, words), jnp.uint32,
                             sharding=sharding),
        jax.ShapeDtypeStruct((n_shards, e_max), jnp.int32, sharding=sharding),
        jax.ShapeDtypeStruct((n_shards, e_max), jnp.int32, sharding=sharding),
        jax.ShapeDtypeStruct((n_shards, e_max), jnp.bool_, sharding=sharding),
    )
    return jax.jit(run).lower(*args)


def lower_distributed_closure_2d(mesh: Mesh, v_global: int, e_max: int,
                                 nbits: int, rounds: int, *,
                                 word_shards: int = 8, chunk: int = 64):
    """§Perf iteration T1/T2: 2-D (vertex × word) partitioning.

    The 1-D layout gathers the full packed table (V × W words) on every
    device every round.  But the OR-recurrence is elementwise in the word
    dimension, so a device that owns only ``W/word_shards`` words needs
    only those words of every referenced row: re-viewing the flattened
    mesh as ``(vertex_shards × word_shards)`` divides per-round gather
    traffic by ``word_shards`` at identical per-device compute.  State is
    packed uint32 at rest *and* in flight — the word axis shards on whole
    words, so no pack/unpack transposes the exchange.  Edge lists are
    replicated across the word axis (static, once).
    """
    n_dev = mesh.devices.size
    assert n_dev % word_shards == 0
    v_shards = n_dev // word_shards
    mesh2 = Mesh(mesh.devices.reshape(v_shards, word_shards),
                 ("vtx", "word"))
    per_v = -(-v_global // v_shards)
    w_words = bitset.n_words(nbits)
    assert w_words % word_shards == 0, (w_words, word_shards)
    per_w = w_words // word_shards
    cw = min(max(1, chunk // bitset.WORD), per_w)
    sh_r = NamedSharding(mesh2, P("vtx", None, "word"))
    sh_e = NamedSharding(mesh2, P("vtx", None))

    @functools.partial(
        jax.shard_map, mesh=mesh2,
        in_specs=(P("vtx", None, "word"), P("vtx", None), P("vtx", None),
                  P("vtx", None)),
        out_specs=P("vtx", None, "word"))
    def run(rows_s, local_s, remote_s, valid_s):
        rows_l = rows_s[0]                  # [per_v, per_w] packed uint32
        loc, rem = local_s[0], remote_s[0]
        okw = bitset.full_words_where(valid_s[0])[:, None]

        def round_(r_local):
            # gather over the vertex axis ONLY; each device pulls just its
            # own word slice of every row, already packed (no transient
            # bool plane anywhere in the exchange)
            full = jax.lax.all_gather(r_local, axis_name="vtx",
                                      tiled=True)      # [v_pad, per_w]
            vals = full[rem] & okw
            upd = bitset.segment_or_words(vals, loc, num_segments=per_v,
                                          chunk_words=cw)
            return r_local | upd

        def body(_, r):
            return round_(r)

        return jax.lax.fori_loop(0, rounds, body, round_(rows_l))[None]

    args = (
        jax.ShapeDtypeStruct((v_shards, per_v, w_words), jnp.uint32,
                             sharding=sh_r),
        jax.ShapeDtypeStruct((v_shards, e_max), jnp.int32, sharding=sh_e),
        jax.ShapeDtypeStruct((v_shards, e_max), jnp.int32, sharding=sh_e),
        jax.ShapeDtypeStruct((v_shards, e_max), jnp.bool_, sharding=sh_e),
    )
    with mesh2:
        return jax.jit(run).lower(*args)
