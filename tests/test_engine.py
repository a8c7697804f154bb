"""Packed-word engine: backend bit-equality, bool-plane oracle equivalence,
planner/executor vs the DFS oracle, and kernel load-bearing-ness."""
import numpy as np
import pytest

import hypothesis as hp
import hypothesis.strategies as st

import jax.numpy as jnp

from repro.core import (bitset, dfs_baseline, engine, graph as G,
                        pattern as pat, tdr_build, tdr_query)

CFG = tdr_build.TDRConfig(vtx_bits=64, g_max=4, k=3)
BACKENDS = ("segment", "pallas")


# ----------------------------------------------------- primitive equality
@hp.given(seed=st.integers(0, 10_000))
@hp.settings(max_examples=10, deadline=None)
def test_segment_or_words_matches_bool_plane(seed):
    rng = np.random.default_rng(seed)
    e, nbits, s = 64, 70, 17
    vals = rng.random((e, nbits)) < 0.15
    seg = rng.integers(0, s, size=e)
    want = np.asarray(bitset.pack_bits(bitset.segment_or(
        jnp.asarray(vals), jnp.asarray(seg), num_segments=s)))
    got = np.asarray(bitset.segment_or_words(
        jnp.asarray(bitset.pack_bits_np(vals)), jnp.asarray(seg),
        num_segments=s, chunk_words=1))
    np.testing.assert_array_equal(got, want)


@hp.given(seed=st.integers(0, 10_000), kind=st.sampled_from(["er", "pa"]))
@hp.settings(max_examples=8, deadline=None)
def test_engine_closure_matches_dfs_oracle(seed, kind):
    """Both backends' packed closure == the per-vertex DFS reachable set."""
    g = G.random_graph(kind, 40, 2.0, 4, seed=seed)
    _, _, disc = tdr_build.dfs_intervals(g)
    rows = tdr_build._vertex_bit_rows(CFG, disc)
    rows_packed = jnp.asarray(bitset.pack_bits_np(rows))
    results = {}
    for backend in BACKENDS:
        eng = engine.make_engine(g, backend=backend)
        base = eng.propagate(rows_packed)
        r, _ = eng.closure(base)
        results[backend] = np.asarray(r)
    np.testing.assert_array_equal(results["segment"], results["pallas"])
    for u in range(0, g.n_vertices, 7):
        reach = dfs_baseline.reachable_set(g, u)
        want = np.zeros(CFG.vtx_bits, dtype=bool)
        for v in np.flatnonzero(reach):
            want |= rows[v]
        got = np.unpackbits(results["segment"][u].view(np.uint8),
                            bitorder="little")[:CFG.vtx_bits].astype(bool)
        np.testing.assert_array_equal(got, want)


@hp.given(seed=st.integers(0, 10_000), kind=st.sampled_from(["er", "pa"]))
@hp.settings(max_examples=6, deadline=None)
def test_build_index_backend_bit_equality(seed, kind):
    g = G.random_graph(kind, 50, 2.2, 5, seed=seed)
    idx = {b: tdr_build.build_index(g, CFG, backend=b) for b in BACKENDS}
    for f in ("h_vtx", "h_lab", "v_vtx", "v_lab", "n_out", "n_in"):
        np.testing.assert_array_equal(
            np.asarray(getattr(idx["segment"], f)),
            np.asarray(getattr(idx["pallas"], f)), err_msg=f)
    assert idx["segment"].fixpoint_rounds == idx["pallas"].fixpoint_rounds


# --------------------------------------------------- planner + executor
def _random_queries(rng, g, n):
    qs = []
    for _ in range(n):
        u, v = int(rng.integers(g.n_vertices)), int(rng.integers(
            g.n_vertices))
        if rng.integers(5) == 0:
            v = u   # self-queries: only cycles through u can satisfy
        kind = rng.integers(5)
        labs = rng.choice(g.n_labels, size=min(2, g.n_labels),
                          replace=False).tolist()
        if kind == 0:
            p = pat.all_of(labs)
        elif kind == 1:
            p = pat.any_of(labs)
        elif kind == 2:
            p = pat.none_of(labs)
        elif kind == 3:
            p = pat.parse(f"l{labs[0]} & !l{labs[-1]}")
        else:
            p = pat.lcr(labs, g.n_labels)
        qs.append((u, v, p))
    return qs


@hp.given(seed=st.integers(0, 10_000), kind=st.sampled_from(["er", "pa"]))
@hp.settings(max_examples=8, deadline=None)
def test_answer_batch_matches_oracle_both_backends(seed, kind):
    rng = np.random.default_rng(seed)
    g = G.random_graph(kind, 40, 2.0, 4, seed=seed)
    idx = tdr_build.build_index(g, CFG)
    queries = _random_queries(rng, g, 20)
    want = [dfs_baseline.answer_pcr(g, u, v, p) for u, v, p in queries]
    for backend in BACKENDS:
        got = tdr_query.answer_batch(idx, queries, backend=backend)
        assert got.tolist() == want, backend


@hp.given(seed=st.integers(0, 10_000), kind=st.sampled_from(["er", "pa"]))
@hp.settings(max_examples=6, deadline=None)
def test_exact_modes_bit_equal(seed, kind):
    """The corridor-compacted and bidirectional-full executors must be
    bit-equal to the DFS oracle *and* to the retained PR-1 full-graph
    executor (exact_mode="legacy"), including forbidden-label patterns
    and u==v cycle queries."""
    rng = np.random.default_rng(seed)
    g = G.random_graph(kind, 45, 2.3, 4, seed=seed)
    idx = tdr_build.build_index(g, CFG)
    queries = _random_queries(rng, g, 20)
    want = [dfs_baseline.answer_pcr(g, u, v, p) for u, v, p in queries]
    legacy = tdr_query.answer_batch(idx, queries, backend="segment",
                                    exact_mode="legacy").tolist()
    assert legacy == want
    for mode in ("auto", "compact", "full"):
        got = tdr_query.answer_batch(idx, queries, backend="segment",
                                     exact_mode=mode).tolist()
        assert got == want == legacy, mode


def test_exact_modes_bit_equal_pallas():
    """Same bit-equality through the pallas (interpret) matmul executors:
    compacted per-chunk sub-adjacency and device-built full corridor."""
    for seed in (2, 9):
        rng = np.random.default_rng(seed)
        g = G.random_graph("pa", 40, 2.5, 4, seed=seed)
        idx = tdr_build.build_index(g, CFG)
        queries = _random_queries(rng, g, 15)
        want = [dfs_baseline.answer_pcr(g, u, v, p) for u, v, p in queries]
        for mode in ("compact", "full", "legacy"):
            got = tdr_query.answer_batch(idx, queries, backend="pallas",
                                         exact_mode=mode).tolist()
            assert got == want, (seed, mode)


@pytest.mark.parametrize("mode", ["full", "compact"])
@pytest.mark.parametrize("seed", [3, 11])
def test_pallas_edge_kernel_answers_match_oracle(mode, seed):
    """Phase 2 on the pallas backend (interpret mode) expands every chunk
    with the edge-list class kernel, full-graph and corridor-compacted,
    and answers as ``dfs_baseline`` does."""
    rng = np.random.default_rng(seed)
    g = G.random_graph("er", 96, 1.3, 4, seed=seed)
    idx = tdr_build.build_index(g, CFG)
    queries = _random_queries(rng, g, 16)
    want = [dfs_baseline.answer_pcr(g, u, v, p) for u, v, p in queries]
    stats = tdr_query.QueryStats()
    got = tdr_query.answer_batch(idx, queries, backend="pallas",
                                 exact_mode=mode, exact_chunk=8,
                                 stats=stats).tolist()
    assert got == want
    assert stats.exact_chunks > 0
    assert stats.edge_chunks == stats.exact_chunks
    if mode == "compact":
        assert stats.corridor_occupancy < 1.0


@pytest.mark.parametrize("reverse", [True, False])
@pytest.mark.parametrize("special", [(), (1,), (0, 2, 3)])
def test_class_edge_lists_match_packed_classes(special, reverse):
    """Each class's edge list sets exactly the bits of its packed
    matrix (same classes, same ``reverse`` meaning), sorted by row, with
    ``E_pad`` on the ``pad_bucket`` grid."""
    g = G.random_graph("pa", 70, 3.0, 4, seed=4)
    packed = engine.pack_label_class_adjacency_np(g, special,
                                                  reverse=reverse)
    rows, cols, count = engine.class_edge_lists_np(
        g.src, g.indices, g.labels, special, reverse=reverse)
    assert rows.shape == cols.shape == (len(special) + 1, rows.shape[1])
    assert rows.shape[1] == G.pad_bucket(int(count.max()), lo=32)
    assert count.sum() == g.n_edges
    for c in range(len(special) + 1):
        n = count[c]
        assert np.all(np.diff(rows[c, :n]) >= 0)
        a = np.zeros_like(packed[c])
        bitset.set_bits_np(a, (rows[c, :n],), cols[c, :n])
        np.testing.assert_array_equal(a, packed[c])


def test_self_cycle_queries_exact():
    """u==v with required labels is satisfiable only by a cycle through
    u collecting them — exact on every executor path."""
    g = G.Graph.from_edges(
        5, 2, [(0, 1, 0), (1, 2, 1), (2, 0, 0), (3, 4, 1)])
    idx = tdr_build.build_index(g, tdr_build.TDRConfig(vtx_bits=32))
    for mode in ("auto", "compact", "full", "legacy"):
        assert tdr_query.answer(idx, 0, 0, pat.all_of([0, 1]),
                                exact_mode=mode) is True
        assert tdr_query.answer(idx, 3, 3, pat.all_of([1]),
                                exact_mode=mode) is False


def test_corridor_compaction_prunes_and_lazy_stats():
    """On a sparse graph the corridor must actually shrink the expansion
    (occupancy < 1), and QueryStats fetches round counters lazily."""
    g = G.erdos_renyi(120, 1.2, 4, seed=5)
    idx = tdr_build.build_index(g, CFG)
    rng = np.random.default_rng(5)
    queries = _random_queries(rng, g, 40)
    stats = tdr_query.QueryStats()
    got = tdr_query.answer_batch(idx, queries, backend="segment",
                                 stats=stats)
    want = [dfs_baseline.answer_pcr(g, u, v, p) for u, v, p in queries]
    assert got.tolist() == want
    assert stats.exact_jobs > 0
    assert stats.corridor_total > 0
    assert stats.corridor_occupancy < 1.0, \
        "sparse corridors should compact below full V"
    # lazy round counters: stored as device scalars, summed on access
    assert isinstance(stats.exact_rounds, int)
    assert stats.exact_rounds > 0
    assert stats.phase1_s > 0 and stats.phase2_s > 0


def test_round_counters_count_chunks_and_stay_bounded():
    """Every phase-2 chunk is counted with its rounds (at least one); the
    unread device round counters are folded on read, and a stats object
    that is never read holds fewer than ``_ROUND_PARTS_CAP`` of them."""
    g = G.erdos_renyi(120, 1.2, 4, seed=5)
    idx = tdr_build.build_index(g, CFG)
    queries = _random_queries(np.random.default_rng(6), g, 40)
    stats = tdr_query.QueryStats()
    tdr_query.answer_batch(idx, queries, backend="segment", exact_chunk=8,
                           exact_mode="full", stats=stats)
    assert stats.exact_jobs > 8
    assert stats.exact_chunks == -(-stats.exact_jobs // 8)
    assert stats.exact_rounds >= stats.exact_chunks
    assert stats._round_parts == []
    assert stats.plan_s > 0

    stats = tdr_query.QueryStats()
    n = 3 * tdr_query._ROUND_PARTS_CAP + 5
    for i in range(n):
        stats.add_chunk(jnp.int32(i % 7))
        assert len(stats._round_parts) < tdr_query._ROUND_PARTS_CAP
    want = sum(i % 7 for i in range(n))
    assert stats.exact_chunks == n
    assert stats.exact_rounds == want
    assert stats._round_parts == []
    assert stats.exact_rounds == want


def test_incidence_plan_matches_bruteforce():
    """One- and two-level padded incidence reduce to the same segment OR
    (two-level triggers on the pa graph's hub tail)."""
    rng = np.random.default_rng(0)
    levels_seen = set()
    for kind in ("er", "pa"):
        g = G.random_graph(kind, 400, 4.0, 4, seed=0)
        keys = np.asarray(g.indices)
        plan = G.incidence_plan(keys, g.n_vertices, g.n_edges)
        levels_seen.add(len(plan))
        val = rng.integers(0, 2 ** 32, (g.n_edges + 1, 2),
                           dtype=np.uint32)
        val[-1] = 0
        cur = val
        for level in plan:
            nxt = np.zeros((level.shape[0], 2), np.uint32)
            for i in range(level.shape[0]):
                for j in level[i]:
                    if j < cur.shape[0]:
                        nxt[i] |= cur[j]
            cur = np.concatenate([nxt, np.zeros((1, 2), np.uint32)])
        want = np.zeros((g.n_vertices, 2), np.uint32)
        for e in range(g.n_edges):
            want[keys[e]] |= val[e]
        np.testing.assert_array_equal(cur[:g.n_vertices], want, err_msg=kind)
    assert levels_seen == {1, 2}, \
        "expected er to stay one-level and pa's hubs to trigger two-level"


def test_special_labels_multiword():
    """The vectorized forbidden-label extraction must read every word of
    the packed raw plane (labels >= 32 live past the first uint32)."""
    g = G.erdos_renyi(30, 2.0, 70, seed=0)
    idx = tdr_build.build_index(g, tdr_build.TDRConfig(vtx_bits=64))
    qs = [(0, 5, pat.none_of([0, 33, 69])), (1, 7, pat.all_of([2, 40])),
          (2, 9, pat.parse("l5 & !l64"))]
    plan = tdr_query.compile_queries(idx, qs)
    ex = tdr_query.ExactExecutor(idx, idx.engine("segment"))
    jobs = np.arange(plan.n_jobs)
    assert ex.special_labels(plan, jobs) == (0, 2, 5, 33, 40, 64, 69)
    # single-job slices see only their own labels
    assert ex.special_labels(plan, np.array([0])) == (0, 33, 69)


def test_query_plan_is_packed_and_padded():
    g = G.fig2_example()
    idx = tdr_build.build_index(g, tdr_build.TDRConfig(vtx_bits=32))
    plan = tdr_query.compile_queries(
        idx, [(0, 5, pat.all_of([1, 3])), (0, 4, pat.none_of([0, 1]))])
    assert plan.req_w.dtype == np.uint32
    assert plan.forb_raw_w.dtype == np.uint32
    assert plan.full_mask.tolist() == [3, 0]
    padded = plan.pad_to(16)
    assert padded.n_jobs == 16 and padded.qid[-1] == -1
    assert padded.n_queries == plan.n_queries


def test_index_arrays_are_packed_words():
    """No [V, nbits] bool plane at rest: every index array is uint32."""
    g = G.erdos_renyi(60, 2.0, 4, seed=0)
    idx = tdr_build.build_index(g, CFG)
    for f in ("h_vtx", "h_lab", "v_vtx", "v_lab", "n_out", "n_in"):
        arr = getattr(idx, f)
        assert arr.dtype == jnp.uint32, f
    assert idx.vtx_words.dtype == np.uint32
    assert idx.h_vtx.shape[-1] == bitset.n_words(CFG.vtx_bits)


# ----------------------------------------------- kernels are load-bearing
def test_pallas_backend_invokes_bitset_matmul():
    from repro.kernels import ops
    g = G.erdos_renyi(50, 2.5, 4, seed=7)

    before = ops.KERNEL_INVOCATIONS["bitset_matmul"]
    idx = tdr_build.build_index(g, CFG, backend="pallas")
    after_build = ops.KERNEL_INVOCATIONS["bitset_matmul"]
    assert after_build > before, "build fixpoint skipped the Pallas kernel"

    # a query mix that cannot all be resolved by phase 1 filters; phase 2
    # expands its label classes with the edge-list kernel
    rng = np.random.default_rng(0)
    queries = _random_queries(rng, g, 30)
    stats = tdr_query.QueryStats()
    edges_before = ops.KERNEL_INVOCATIONS["lane_matmul_edges"]
    tdr_query.answer_batch(idx, queries, backend="pallas", stats=stats)
    after_query = ops.KERNEL_INVOCATIONS["lane_matmul_edges"]
    assert stats.exact_jobs > 0, "no job reached phase 2; pick other seeds"
    assert after_query > edges_before, \
        "exact expansion skipped the Pallas kernel"
    assert stats.edge_chunks == stats.exact_chunks > 0


def test_segment_backend_uses_no_pallas_kernel():
    from repro.kernels import ops
    g = G.erdos_renyi(40, 2.0, 4, seed=1)
    before = dict(ops.KERNEL_INVOCATIONS)
    idx = tdr_build.build_index(g, CFG, backend="segment")
    stats = tdr_query.QueryStats()
    tdr_query.answer_batch(
        idx, _random_queries(np.random.default_rng(1), g, 10),
        backend="segment", stats=stats)
    assert dict(ops.KERNEL_INVOCATIONS) == before
    assert stats.exact_chunks > 0 and stats.edge_chunks == 0


# ------------------------------------------------------ backend selection
def test_backend_env_override(monkeypatch):
    # env replaces the default resolution only ...
    monkeypatch.setenv(engine.ENV_BACKEND, "pallas")
    assert engine.resolve_backend("auto") == "pallas"
    assert engine.resolve_backend("") == "pallas"
    # ... but never an explicitly requested backend (sweeps stay truthful)
    assert engine.resolve_backend("segment") == "segment"
    monkeypatch.setenv(engine.ENV_BACKEND, "segment")
    assert engine.resolve_backend("pallas") == "pallas"
    assert engine.resolve_backend("auto") == "segment"
    monkeypatch.delenv(engine.ENV_BACKEND)
    assert engine.resolve_backend("auto") in BACKENDS
    with pytest.raises(ValueError):
        engine.resolve_backend("mxu")


def test_pallas_auto_fallback_on_dense_cap():
    g = G.erdos_renyi(64, 2.0, 4, seed=0)
    with pytest.warns(UserWarning, match="falling back"):
        eng = engine.make_engine(
            g, config=engine.EngineConfig(backend="pallas",
                                          max_dense_bytes=64))
    assert eng.backend == "segment"


def test_label_adjacency_cache_is_bounded():
    g = G.erdos_renyi(40, 2.0, 8, seed=0)
    eng = engine.make_engine(g, backend="pallas")
    for l in range(8):
        eng.label_class_adjacency((l,))
        eng.label_class_edges((l,))
    assert len(eng._label_adj) <= engine.Engine.LABEL_ADJ_CACHE
    assert len(eng._label_edges) <= engine.Engine.LABEL_ADJ_CACHE
    # a hit returns the cached operand and refreshes it as most recent
    assert eng.label_class_edges((7,)) is eng.label_class_edges((7,))
    assert next(reversed(eng._label_edges)) == ((7,), True)


def test_executor_falls_back_when_class_set_blows_cap():
    """Per-batch label-class matrices over the dense cap must not OOM the
    pallas backend: the batch expands via segment rounds, bit-identically."""
    g = G.erdos_renyi(40, 2.5, 6, seed=3)
    idx = tdr_build.build_index(g, CFG)
    rng = np.random.default_rng(3)
    queries = _random_queries(rng, g, 15)
    want = tdr_query.answer_batch(idx, queries, backend="segment").tolist()
    kw = (g.n_vertices + 31) // 32
    cap = 2 * g.n_vertices * kw * 4   # fits the base matrix, not C+1 classes
    cfg = engine.EngineConfig(backend="pallas", max_dense_bytes=cap)
    with pytest.warns(UserWarning, match="segment path"):
        got = tdr_query.answer_batch(idx, queries, engine_config=cfg)
    assert got.tolist() == want


def test_index_caches_engines_and_adjacency():
    g = G.erdos_renyi(30, 2.0, 4, seed=0)
    idx = tdr_build.build_index(g, CFG, backend="pallas")
    assert idx.engine("pallas") is idx.engine("pallas")
    a1 = idx.adj_packed()
    a2 = idx.engine().adjacency()
    # adjacency row u must contain exactly u's successors
    adj = np.asarray(a1)
    bits = np.unpackbits(adj.view(np.uint8), axis=1, bitorder="little")
    for u in range(g.n_vertices):
        np.testing.assert_array_equal(
            np.flatnonzero(bits[u][:g.n_vertices]),
            np.unique(g.successors(u)))


def test_vtx_packed_cached_plainly():
    g = G.erdos_renyi(20, 1.5, 3, seed=0)
    idx = tdr_build.build_index(g, CFG)
    p1 = idx.vtx_packed
    assert idx.vtx_packed is p1                 # cached attribute, no hack
    np.testing.assert_array_equal(
        np.asarray(p1), bitset.pack_bits_np(idx.vtx_bit_rows))
