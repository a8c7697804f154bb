"""TDR index + query engine: paper examples, oracle equivalence,
filter soundness, distributed build (hypothesis property tests)."""
import numpy as np
import pytest

import hypothesis as hp
import hypothesis.strategies as st

from repro.core import (dfs_baseline, graph as G, lcr, pattern as pat,
                        tdr_build, tdr_query)

CFG = tdr_build.TDRConfig(vtx_bits=64, g_max=4, k=3)


@pytest.fixture(scope="module")
def fig2():
    g = G.fig2_example()
    return g, tdr_build.build_index(g, tdr_build.TDRConfig(vtx_bits=32,
                                                           g_max=2, k=2))


def test_paper_example1(fig2):
    g, idx = fig2
    # v0 -(b AND d)-> v5 : true via path a,d,b
    assert tdr_query.answer(idx, 0, 5, pat.all_of([1, 3])) is True
    # v0 -NOT{a,b}-> v4 : false (all paths to v4 carry b)
    assert tdr_query.answer(idx, 0, 4, pat.none_of([0, 1])) is False


def test_paper_example3(fig2):
    g, idx = fig2
    assert tdr_query.answer(idx, 7, 4, pat.none_of([0])) is False
    assert tdr_query.answer(idx, 0, 6, pat.all_of([1, 4])) is True


def test_self_query(fig2):
    g, idx = fig2
    assert tdr_query.answer(idx, 3, 3, pat.none_of([0])) is True
    assert tdr_query.answer(idx, 3, 3, pat.all_of([0])) is False


def _random_queries(rng, g, n):
    qs = []
    for _ in range(n):
        u, v = int(rng.integers(g.n_vertices)), int(rng.integers(
            g.n_vertices))
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
@hp.settings(max_examples=15, deadline=None)
def test_tdr_matches_oracle(seed, kind):
    rng = np.random.default_rng(seed)
    g = G.random_graph(kind, 40, 2.0, 4, seed=seed)
    idx = tdr_build.build_index(g, CFG)
    queries = _random_queries(rng, g, 20)
    got = tdr_query.answer_batch(idx, queries)
    want = [dfs_baseline.answer_pcr(g, u, v, p) for u, v, p in queries]
    assert got.tolist() == want


@hp.given(seed=st.integers(0, 10_000))
@hp.settings(max_examples=10, deadline=None)
def test_filters_are_sound(seed):
    """Phase-1 filters alone (UNKNOWN -> true) must over-approximate: never
    reject a truly-reachable query."""
    rng = np.random.default_rng(seed)
    g = G.erdos_renyi(40, 2.5, 4, seed=seed)
    idx = tdr_build.build_index(g, CFG)
    queries = _random_queries(rng, g, 20)
    upper = tdr_query.answer_batch(idx, queries, filters_only=True)
    want = [dfs_baseline.answer_pcr(g, u, v, p) for u, v, p in queries]
    for ub, w in zip(upper.tolist(), want):
        if w:
            assert ub, "filter cascade produced a false negative"


def test_stats_pruning_happens():
    g = G.erdos_renyi(60, 1.2, 4, seed=3)   # sparse -> most pairs failing
    idx = tdr_build.build_index(g, CFG)
    rng = np.random.default_rng(0)
    queries = _random_queries(rng, g, 60)
    stats = tdr_query.QueryStats()
    tdr_query.answer_batch(idx, queries, stats=stats)
    assert stats.filter_false > 0          # the index prunes something
    assert stats.exact_jobs < stats.n_jobs


def test_lcr_translation_matches_oracle():
    g = G.erdos_renyi(40, 2.0, 4, seed=9)
    idx = tdr_build.build_index(g, CFG)
    rng = np.random.default_rng(1)
    queries = []
    for _ in range(20):
        u, v = int(rng.integers(40)), int(rng.integers(40))
        allowed = rng.choice(4, size=2, replace=False).tolist()
        queries.append((u, v, allowed))
    got = lcr.answer_lcr_batch(idx, queries)
    want = [dfs_baseline.answer_lcr(g, u, v, set(a)) for u, v, a in queries]
    assert got.tolist() == want


def test_p2h_lite_matches_oracle():
    g = G.erdos_renyi(25, 1.5, 3, seed=4)
    full = lcr.P2HLite.build(g)
    rng = np.random.default_rng(2)
    for _ in range(30):
        u, v = int(rng.integers(25)), int(rng.integers(25))
        allowed = rng.choice(3, size=2, replace=False).tolist()
        assert full.query(u, v, allowed) == dfs_baseline.answer_lcr(
            g, u, v, set(allowed))


def test_index_size_accounting():
    g = G.erdos_renyi(100, 3.0, 4, seed=0)
    idx = tdr_build.build_index(g, CFG)
    logical = idx.size_bytes(logical=True)
    dense = idx.size_bytes(logical=False)
    assert 0 < logical <= dense


def test_distributed_closure_matches_oracle():
    """Converged packed-word closure == the tdr_build fixpoint semantics:
    R[u] = OR over v with u →+ v of bits(v) — the vertex's own seed bits
    are NOT included unless u lies on a cycle (no rounds= guess, no
    rows[u] OR papering over the old self-seed mismatch)."""
    import jax
    from jax.sharding import Mesh
    from repro.core import distributed
    g = G.erdos_renyi(50, 2.0, 4, seed=1)
    cfg = tdr_build.TDRConfig(vtx_bits=64)
    _, _, disc = tdr_build.dfs_intervals(g)
    words = tdr_build._vertex_bit_words(cfg, disc)
    rows = tdr_build._vertex_bit_rows(cfg, disc)
    mesh = Mesh(np.array(jax.devices()).reshape(1,), ("data",))
    rvec = np.asarray(distributed.distributed_closure(g, words, mesh))
    for u in range(0, 50, 7):
        reach = dfs_baseline.reachable_set(g, u)
        want = np.zeros(cfg.vtx_bits, dtype=bool)
        for v in np.flatnonzero(reach):
            want |= rows[v]
        got = np.unpackbits(rvec[u].view(np.uint8),
                            bitorder="little")[:64].astype(bool)
        assert (want == got).all()


def test_distributed_closure_rejects_bool_planes():
    """The bool-plane exchange is retired: packed uint32 words only."""
    import jax
    import pytest as pt
    from jax.sharding import Mesh
    from repro.core import distributed
    g = G.erdos_renyi(10, 1.5, 2, seed=0)
    mesh = Mesh(np.array(jax.devices()).reshape(1,), ("data",))
    with pt.raises(TypeError, match="packed uint32"):
        distributed.distributed_closure(
            g, np.zeros((10, 32), dtype=bool), mesh)


def test_hash_schedule_never_wraps():
    """All n_hashes Bloom position arrays must be pairwise distinct — the
    pre-fix key schedule wrapped at 4 hashes (ks[(i-1) % 3]), making hash
    4 duplicate hash 1 bit-for-bit with zero added selectivity."""
    disc = np.arange(200, dtype=np.int64)
    for scheme in ("dfs-block", "mult"):
        cfg = tdr_build.TDRConfig(vtx_bits=256, n_hashes=8,
                                  hash_scheme=scheme)
        pos = tdr_build._vertex_hash_positions(cfg, disc)
        assert len(pos) == 8
        for i in range(len(pos)):
            for j in range(i + 1, len(pos)):
                assert not np.array_equal(pos[i], pos[j]), (scheme, i, j)
    # the first four hashes (the pre-fix reach) are frozen: same keys
    ks = tdr_build._hash_keys(3)
    assert [int(k) for k in ks] == [0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F,
                                    0x165667B19E3779F9]
