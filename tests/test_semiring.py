"""Semiring engine: boolean bit-identity + dist/witness/count oracles.

Three contracts, one per instantiation of the generalized fixpoint core:

* ``BOOLEAN`` — the generic paths must be *bit-identical* to the
  pre-refactor packed-uint32 engine.  Asserted two ways: the semiring
  methods trace to literally the same jaxpr as the hand-coded OR idioms,
  and ``closure(sr=BOOLEAN)`` planes equal the default closure on both
  backends (which in turn equal the DFS oracle).

* ``DIST16`` — ``tdr_query.dist_batch`` / ``witness`` equal the
  product-graph BFS oracle (``dfs_baseline.shortest_pcr``) on random
  graphs x patterns x backends, including ``u == v``, unreachable pairs,
  and k-hop bounds; every witness path replays through
  ``verify_witness`` and has exactly the oracle's length (200+ cases).

* ``COUNT`` — ``tdr_query.count_routes`` equals the layered walk-count
  DP with saturating add, including cap-saturation cases; ``closure``
  refuses the non-idempotent carrier.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _qgen import mixed_queries
from repro.core import dfs_baseline, engine, graph as G, pattern as pat
from repro.core import tdr_build, tdr_query
from repro.core.semiring import (BOOLEAN, COUNT, COUNT_CAP, DIST8, DIST16,
                                 Semiring, by_name)
from repro.kernels import ops

BACKENDS = ("segment", "pallas")


# ---------------------------------------------------------------------------
# boolean bit-identity
# ---------------------------------------------------------------------------

def test_boolean_methods_trace_to_packed_or_idioms():
    """The BOOLEAN branch of every semiring method emits the *same jaxpr*
    as the pre-refactor hand-coded packed-OR code — the generic engine
    cannot drift from the bit-plane layout without failing here."""
    r = jnp.zeros((8, 4), jnp.uint32)
    u = jnp.ones((8, 4), jnp.uint32)

    def hand_accumulate(r, u):
        new = u & ~r
        return r | new, jnp.any(new != 0)

    assert str(jax.make_jaxpr(BOOLEAN.accumulate)(r, u)) == \
        str(jax.make_jaxpr(hand_accumulate)(r, u))
    assert str(jax.make_jaxpr(BOOLEAN.combine)(r, u)) == \
        str(jax.make_jaxpr(lambda a, b: a | b)(r, u))
    assert str(jax.make_jaxpr(BOOLEAN.extend)(r)) == \
        str(jax.make_jaxpr(lambda a: a)(r))


@pytest.mark.parametrize("backend", BACKENDS)
def test_boolean_closure_bit_identical(backend):
    """closure(sr=BOOLEAN) == closure() == DFS reachability, per backend."""
    g = G.random_graph("pa", 50, 2.0, 4, seed=5)
    eng = engine.make_engine(g, backend=backend)
    v_n = g.n_vertices
    kw = eng.adjacency().shape[1]
    base = jnp.asarray(np.eye(v_n, kw * 32, dtype=np.uint8).reshape(
        v_n, kw, 32) << np.arange(32, dtype=np.uint32)).sum(
            axis=2, dtype=jnp.uint32)
    dflt, _ = eng.closure(base)
    gen, _ = eng.closure(base, sr=BOOLEAN)
    np.testing.assert_array_equal(np.asarray(dflt), np.asarray(gen))
    got = np.asarray(dflt)
    for u in range(0, v_n, 11):
        reach = dfs_baseline.reachable_set(g, u)
        reach[u] = True  # closure seeds the diagonal
        bits = np.unpackbits(got[u].view(np.uint8),
                             bitorder="little")[:v_n].astype(bool)
        np.testing.assert_array_equal(bits, reach)


def test_semiring_registry_and_scalars():
    assert by_name("boolean") is BOOLEAN
    assert by_name("count") is COUNT
    with pytest.raises(ValueError):
        by_name("tropical-float")
    assert DIST16.inf == 65535 and DIST8.inf == 255
    assert DIST16.zero == DIST16.inf and DIST16.one == 0
    assert COUNT.zero == 0 and COUNT.one == 1 and COUNT.cap == COUNT_CAP
    with pytest.raises(ValueError):
        BOOLEAN.inf
    with pytest.raises(ValueError):
        COUNT.accumulate(jnp.zeros(2, jnp.uint32), jnp.ones(2, jnp.uint32))


# ---------------------------------------------------------------------------
# lane kernels: pallas(interpret) == ref, per semiring op
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sr", [DIST16, DIST8, COUNT],
                         ids=lambda s: s.name)
def test_lane_matmul_matches_ref(sr):
    """Interpret-mode lane kernel == jnp reference, saturation included."""
    rng = np.random.default_rng(int(sr.cap) + len(sr.name))
    m, k, w = 24, 37, 6
    a = np.asarray(bitset_pack(rng.random((m, k)) < 0.3))
    hi = sr.zero if sr.op == "min" else max(sr.cap, 1)
    x = rng.integers(0, hi + 1, size=(k, w)).astype(np.dtype(sr.dtype_name))
    # the kernel takes a word-aligned K; pad rows carry no adjacency bits
    xp = np.pad(x, ((0, a.shape[1] * 32 - k), (0, 0)))
    got = ops.frontier_step_lanes(jnp.asarray(a), jnp.asarray(xp),
                                  op=sr.op, cap=sr.cap, mode="interpret")
    ref = ops.frontier_step_lanes(jnp.asarray(a), jnp.asarray(xp),
                                  op=sr.op, cap=sr.cap, mode="ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # and both equal a dense numpy evaluation of the semiring product
    ab = np.unpackbits(a.view(np.uint8), axis=1,
                       bitorder="little")[:, :k].astype(bool)
    want = np.zeros((m, w), dtype=x.dtype)
    for i in range(m):
        sel = x[ab[i]]
        if sr.op == "min":
            want[i] = sel.min(axis=0) if sel.size else sr.zero
        else:
            want[i] = np.minimum(
                sel.sum(axis=0, dtype=np.uint64),
                np.uint64(sr.cap)).astype(x.dtype) if sel.size else 0
    np.testing.assert_array_equal(np.asarray(ref), want)


def bitset_pack(rows: np.ndarray) -> np.ndarray:
    from repro.core import bitset
    return bitset.pack_bits_np(np.asarray(rows, dtype=bool))


@pytest.mark.parametrize("sr", [DIST16, COUNT], ids=lambda s: s.name)
def test_closure_matmul_rows_extend(sr):
    """_matmul_rows applies extend after the lane reduce: for DIST the
    result is 1 + min over selected rows (saturating); for COUNT it is
    the capped sum unchanged."""
    a = bitset_pack(np.array([[1, 1, 0], [0, 0, 0]], dtype=bool))
    x = jnp.asarray(np.array([[3], [5], [9]], dtype=sr.dtype_name))
    out = np.asarray(engine._matmul_rows(jnp.asarray(a), x, "ref", sr=sr))
    if sr.op == "min":
        assert out.tolist() == [[4], [sr.zero]]  # min(3,5)+1; empty -> INF
    else:
        assert out.tolist() == [[8], [0]]


def test_closure_refuses_count():
    g = G.erdos_renyi(10, 1.0, 2, seed=0)
    eng = engine.make_engine(g, backend="segment")
    with pytest.raises(ValueError, match="idempotent"):
        eng.closure(jnp.zeros((10, 1), jnp.uint32), sr=COUNT)


# ---------------------------------------------------------------------------
# dist: oracle equality across graphs x patterns x backends
# ---------------------------------------------------------------------------

def _graphs():
    return [G.random_graph(kind, 48, deg, 4, seed=s)
            for (kind, deg, s) in
            (("er", 1.6, 1), ("er", 2.4, 2), ("pa", 2.0, 3), ("pa", 3.0, 4))]


@pytest.mark.parametrize("backend", BACKENDS)
def test_dist_matches_bfs_oracle(backend):
    g = _graphs()[0 if backend == "segment" else 2]
    idx = tdr_build.build_index(g)
    rng = np.random.default_rng(21)
    qs = mixed_queries(rng, g, 40)
    got = tdr_query.dist_batch(idx, qs, backend=backend)
    want = [dfs_baseline.shortest_pcr(g, u, v, p) for (u, v, p) in qs]
    assert got.tolist() == want
    # k-hop bound: answers prune to -1 beyond k, never change below it
    for k in (0, 1, 3):
        gk = tdr_query.dist_batch(idx, qs, k=k, backend=backend)
        wk = [d if 0 <= d <= k else -1 for d in want]
        assert gk.tolist() == wk


def test_dist_exact_modes_agree():
    g = _graphs()[1]
    idx = tdr_build.build_index(g)
    qs = mixed_queries(np.random.default_rng(8), g, 24)
    want = tdr_query.dist_batch(idx, qs, exact_mode="full").tolist()
    for mode in ("auto", "compact"):
        assert tdr_query.dist_batch(idx, qs, exact_mode=mode).tolist() == want
    assert want == [dfs_baseline.shortest_pcr(g, u, v, p) for u, v, p in qs]


def test_dist_edge_cases():
    g = _graphs()[0]
    idx = tdr_build.build_index(g)
    true_p = pat.none_of([])
    assert tdr_query.dist(idx, 3, 3, true_p) == 0          # empty walk
    assert tdr_query.dist(idx, 3, 3, pat.all_of([0])) != 0  # must move
    # an unreachable pair: fabricate one via a label every edge forbids
    assert tdr_query.dist(idx, 0, 1,
                          pat.none_of(list(range(g.n_labels)))) == -1


# ---------------------------------------------------------------------------
# witness: 200+ randomized cases, path-valid + oracle-shortest
# ---------------------------------------------------------------------------

def test_witness_matches_oracle_200_cases():
    """Every witness replays edge-by-edge through the graph and has
    exactly the oracle's shortest length; unreachable pairs return None.
    4 graphs x 60 queries = 240 randomized cases (same padded V so the
    forward-parent DP compiles once per state count)."""
    rng = np.random.default_rng(99)
    reachable = 0
    for gi, g in enumerate(_graphs()):
        idx = tdr_build.build_index(g)
        backend = "pallas" if gi == 3 else "segment"
        for (u, v, p) in mixed_queries(rng, g, 60):
            want = dfs_baseline.shortest_pcr(g, u, v, p)
            path = tdr_query.witness(idx, u, v, p, backend=backend,
                                     exact_mode="full")
            if want < 0:
                assert path is None, (gi, u, v, p)
            else:
                reachable += 1
                # witness() itself re-verifies and raises on mismatch;
                # assert the contract independently here anyway.
                assert len(path) == want, (gi, u, v, p)
                assert dfs_baseline.verify_witness(g, u, v, p, path)
    assert reachable >= 40  # the pools genuinely exercise the DP


def test_witness_trivial_and_compact():
    g = _graphs()[2]
    idx = tdr_build.build_index(g)
    assert tdr_query.witness(idx, 7, 7, pat.none_of([])) == []
    qs = mixed_queries(np.random.default_rng(12), g, 12)
    for (u, v, p) in qs:   # corridor compaction never changes witnesses
        full = tdr_query.witness(idx, u, v, p, exact_mode="full")
        auto = tdr_query.witness(idx, u, v, p, exact_mode="auto")
        if full is None:
            assert auto is None
        else:
            assert len(auto) == len(full)
            assert dfs_baseline.verify_witness(g, u, v, p, auto)


# ---------------------------------------------------------------------------
# count: bounded walk DP with saturating add
# ---------------------------------------------------------------------------

def _single_term_queries(rng, g, n):
    out = []
    while len(out) < n:
        for (u, v, p) in mixed_queries(rng, g, n):
            if len(pat.to_dnf(p)) == 1:
                out.append((u, v, p))
    return out[:n]


@pytest.mark.parametrize("backend", BACKENDS)
def test_count_routes_matches_oracle(backend):
    g = _graphs()[1 if backend == "segment" else 3]
    idx = tdr_build.build_index(g)
    rng = np.random.default_rng(31)
    for (u, v, p) in _single_term_queries(rng, g, 20):
        for hops in (0, 2, 5):
            want = dfs_baseline.count_routes(g, u, v, p, hops=hops,
                                             cap=COUNT_CAP)
            got = tdr_query.count_routes(idx, u, v, p, hops=hops,
                                         backend=backend)
            assert got == want, (u, v, p, hops)


def test_count_saturates_at_cap():
    """A tiny cap forces clamping; per-round saturating add must equal
    the oracle's clamped total on every query (associativity of the
    saturating monoid — the property the per-round clamp relies on)."""
    g = _graphs()[3]
    idx = tdr_build.build_index(g)
    rng = np.random.default_rng(44)
    sat = 0
    for (u, v, p) in _single_term_queries(rng, g, 15):
        want = dfs_baseline.count_routes(g, u, v, p, hops=8, cap=7)
        got = tdr_query.count_routes(idx, u, v, p, hops=8, cap=7)
        assert got == want, (u, v, p)
        sat += want == 7
    assert sat >= 1  # the cap actually bites somewhere


@pytest.mark.parametrize("hub", [False, True], ids=["spread", "hub"])
def test_count_cap_bounded_by_in_degree(hub):
    """The uint32 accumulator wraps only if one vertex's in-edges times
    the cap reach 2^32: a large graph with small in-degrees counts at a
    cap that edges times cap would overflow; a hub of 64 in-edges at
    cap 2^26 is refused."""
    n = 96
    if hub:
        edges = [(i, 0, 0) for i in range(1, 65)] + [(0, 1, 0)]
    else:
        edges = [(i, (i + d) % n, d % 2) for i in range(n)
                 for d in (1, 2, 3)]
    g = G.Graph.from_edges(n, 2, edges)
    idx = tdr_build.build_index(g)
    cap = 1 << 26
    assert g.n_edges * cap >= 1 << 32     # the old edge-count bound
    if hub:
        with pytest.raises(ValueError, match="in-degree"):
            tdr_query.count_routes(idx, 1, 0, pat.label(0), hops=3,
                                   cap=cap, exact_mode="full")
        return
    p = pat.label(1)
    for u, v in ((0, 5), (3, 3), (10, 40)):
        assert tdr_query.count_routes(idx, u, v, p, hops=6, cap=cap) == \
            dfs_baseline.count_routes(g, u, v, p, hops=6, cap=cap)


def test_count_rejects_multi_term():
    g = _graphs()[0]
    idx = tdr_build.build_index(g)
    with pytest.raises(ValueError, match="single"):
        tdr_query.count_routes(idx, 0, 1, pat.any_of([0, 1]), hops=3)


# ---------------------------------------------------------------------------
# mixed-kind batches through one plan
# ---------------------------------------------------------------------------

def test_answer_mixed_aligns_kinds():
    g = _graphs()[2]
    idx = tdr_build.build_index(g)
    rng = np.random.default_rng(55)
    base = mixed_queries(rng, g, 24)
    kinds = ["bool", "dist", "witness", "count"]
    queries, want = [], []
    for i, (u, v, p) in enumerate(base):
        k = kinds[i % 4]
        if k == "count" and len(pat.to_dnf(p)) != 1:
            k = "dist"
        queries.append((u, v, p, k))
        if k == "bool":
            want.append(dfs_baseline.answer_pcr(g, u, v, p))
        elif k == "dist":
            want.append(dfs_baseline.shortest_pcr(g, u, v, p))
        elif k == "witness":
            want.append(dfs_baseline.shortest_pcr(g, u, v, p))
        else:
            want.append(dfs_baseline.count_routes(g, u, v, p, hops=6,
                                                  cap=COUNT_CAP))
    got = tdr_query.answer_mixed(idx, queries, hops=6)
    assert len(got) == len(queries)
    for (q, w, a) in zip(queries, want, got):
        if q[3] == "witness":
            if w < 0:
                assert a is None
            else:
                assert len(a) == w
                assert dfs_baseline.verify_witness(g, q[0], q[1], q[2], a)
        else:
            assert a == w, (q, w, a)


def test_compile_queries_validates_kind():
    g = _graphs()[0]
    idx = tdr_build.build_index(g)
    with pytest.raises(ValueError, match="kind"):
        tdr_query.compile_queries(idx, [(0, 1, pat.all_of([0]), "fuzzy")])
    plan = tdr_query.compile_queries(
        idx, [(0, 1, pat.all_of([0]), "dist"), (1, 2, pat.all_of([1]))])
    assert plan.kinds and plan.kinds[-1] == "bool"
    with pytest.raises(ValueError, match="answer_mixed"):
        tdr_query.answer_plan(idx, plan)
