"""Serving scheduler: property tests against direct ``answer_batch``.

The contract under test: **any** arrival order, batch-boundary split,
result-cache state, duplicate mix (including ``u == v`` self-queries and
repeated identical requests) must produce answers bit-identical to one
direct ``answer_batch`` call over the same queries.  The scheduler's
batching is driven deterministically here — ``_serve_batch`` on explicit
splits — plus one threaded end-to-end pass through ``submit`` to cover
the queue/condvar path.  Plan canonicalization gets its own equivalence
property (hash-consing must never change semantics).
"""
import threading

import numpy as np
import pytest

import hypothesis as hp
import hypothesis.strategies as st

from repro.core import dfs_baseline, graph as G, pattern as pat
from repro.core import rpq, tdr_build, tdr_query
from repro.launch import serve

CFG = tdr_build.TDRConfig(vtx_bits=64, g_max=4, k=3)

# built lazily at module scope (not a fixture) so the @given property
# tests can use it too
_CACHE: dict = {}


def _served_graph():
    if "gi" not in _CACHE:
        g = G.random_graph("er", 40, 2.0, 4, seed=7)
        _CACHE["gi"] = (g, tdr_build.build_index(g, CFG))
    return _CACHE["gi"]


@pytest.fixture(scope="module")
def served_graph():
    return _served_graph()


def _query_pool(g, seed: int, n: int = 24):
    """Mixed pool: all families, u==v self-queries, repeated patterns."""
    rng = np.random.default_rng(seed)
    pool = []
    for i in range(n):
        u = int(rng.integers(g.n_vertices))
        v = u if i % 6 == 5 else int(rng.integers(g.n_vertices))
        labs = rng.choice(g.n_labels, size=2, replace=False).tolist()
        kind = i % 5
        if kind == 0:
            p = pat.all_of(labs)
        elif kind == 1:
            p = pat.any_of(labs)
        elif kind == 2:
            p = pat.none_of(labs)
        elif kind == 3:
            p = pat.parse(f"l{labs[0]} & !l{labs[1]}")
        else:
            p = pat.lcr(labs, g.n_labels)
        pool.append((u, v, p))
    return pool


def _drive(server, requests):
    """Feed requests through the scheduler core on explicit batch
    boundaries (deterministic, no timing): returns per-request answers."""
    futs = []
    for batch in requests:
        reqs = []
        for (u, v, p) in batch:
            rows = tdr_query.pattern_rows(server.index, p,
                                          server.config.max_m)
            req = serve._Request(u, v, p, (u, v, pat.canonical_key(p)),
                                 rows.n_terms)
            reqs.append(req)
            futs.append(req.future)
        server._serve_batch(reqs)
    return [f.result(timeout=30) for f in futs]


@hp.given(seed=st.integers(0, 10_000),
          splits=st.lists(st.integers(1, 8), min_size=1, max_size=6),
          dup=st.booleans(), cache=st.booleans())
@hp.settings(max_examples=12, deadline=None)
def test_any_split_matches_direct(seed, splits, dup, cache):
    """Arrival order + batch-boundary splits + cache state never change
    answers vs a single direct answer_batch call."""
    g, idx = _served_graph()
    rng = np.random.default_rng(seed)
    pool = _query_pool(g, seed)
    order = rng.permutation(len(pool)).tolist()
    if dup:   # duplicates, some landing in the same batch, some across
        order = order + order[::2]
    queries = [pool[i] for i in order]

    server = serve.QueryServer(idx, result_cache=64 if cache else 0)
    # split the stream on the drawn boundaries (cycled until exhausted)
    batches, i, si = [], 0, 0
    while i < len(queries):
        n = splits[si % len(splits)]
        batches.append(queries[i:i + n])
        i += n
        si += 1
    got = _drive(server, batches)
    want = tdr_query.answer_batch(idx, queries).tolist()
    assert got == want
    # a replay over a warm result cache must also agree
    if cache:
        again = _drive(server, [queries])
        assert again == want


def test_dedup_and_cache_counted(served_graph):
    g, idx = served_graph
    q = _query_pool(g, 3)[0]
    server = serve.QueryServer(idx, result_cache=16)
    got = _drive(server, [[q, q, q]])
    assert got == [got[0]] * 3
    assert server.stats.dedup_hits == 2
    before = server.stats.cache_hits
    got2 = _drive(server, [[q]])
    assert got2 == [got[0]]
    assert server.stats.cache_hits == before + 1


def test_threaded_submit_matches_direct(served_graph):
    """End-to-end through submit(): concurrent clients, real scheduler
    thread, mixed duplicates — equal to the direct call."""
    g, idx = served_graph
    pool = _query_pool(g, 11, n=30)
    want = tdr_query.answer_batch(idx, pool).tolist()
    with serve.QueryServer(idx, max_wait_ms=1.0, result_cache=32) as srv:
        srv.warmup(pool[:8])
        results = {}
        lock = threading.Lock()

        def client(ids):
            for i in ids:
                u, v, p = pool[i]
                got = srv.submit(u, v, p).result(timeout=60)
                with lock:
                    results.setdefault(i, []).append(got)

        shards = [list(range(j, len(pool), 4)) + [0, 1] for j in range(4)]
        threads = [threading.Thread(target=client, args=(s,))
                   for s in shards]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    for i, vals in results.items():
        assert all(v == want[i] for v in vals), (i, vals, want[i])


def test_queue_wait_and_request_ids(served_graph):
    """Enqueued requests take FIFO ids; each served request's wait from
    submit to the start of its batch is summed in ``queue_wait_s``."""
    g, idx = served_graph
    pool = _query_pool(g, 13, n=12)
    want = tdr_query.answer_batch(idx, pool).tolist()
    server = serve.QueryServer(idx, result_cache=0)
    futs = [server.submit(u, v, p) for u, v, p in pool]   # queued first
    assert [r.rid for r in server._queue] == list(range(len(pool)))
    server.start()
    try:
        assert [f.result(timeout=60) for f in futs] == want
    finally:
        server.stop()
    assert server.stats.served == len(pool)
    assert server.stats.queue_wait_s > 0


def test_admission_control(served_graph):
    g, idx = served_graph
    q = _query_pool(g, 5)[0]
    server = serve.QueryServer(idx, max_queue=2, result_cache=0)
    # scheduler not started: the queue fills and non-blocking submits shed
    server.submit(*q, block=False)
    server.submit(*q, block=False)
    with pytest.raises(serve.QueueFull):
        server.submit(*q, block=False)
    assert server.stats.rejected == 1
    with pytest.raises(serve.QueueFull):
        server.submit(*q, block=True, timeout=0.01)
    # draining on start answers the backlog
    server.start()
    server.stop(drain=True)


def test_pinned_plan_matches_unpinned(served_graph):
    """pin_m / special_labels pins change shapes, never answers."""
    g, idx = served_graph
    pool = _query_pool(g, 17)
    plan = tdr_query.compile_queries(idx, pool)
    want = tdr_query.answer_plan(idx, plan).tolist()
    for pin_m in (1, 2, 4):
        got = tdr_query.answer_plan(
            idx, plan, pin_m=pin_m,
            special_labels=tuple(range(g.n_labels)),
            exact_mode="full").tolist()
        assert got == want
    oracle = [dfs_baseline.answer_pcr(g, u, v, p) for u, v, p in pool]
    assert want == oracle


def test_canonicalize_equivalence():
    """Hash-consing: canonical form is interned, key-stable, and
    semantically identical to the original pattern."""
    rng = np.random.default_rng(0)

    def rand_pat(depth=3):
        k = int(rng.integers(4)) if depth else 0
        if k == 0:
            return pat.label(int(rng.integers(4)))
        if k == 1:
            return pat.not_(rand_pat(depth - 1))
        kids = tuple(rand_pat(depth - 1)
                     for _ in range(int(rng.integers(1, 4))))
        return pat.And(kids) if k == 2 else pat.Or(kids)

    import itertools
    for _ in range(60):
        p = rand_pat()
        c = pat.canonicalize(p)
        assert pat.canonicalize(c) is pat.canonicalize(p)
        assert pat.canonical_key(c) == pat.canonical_key(p)
        labs = sorted(pat.labels_of(p))
        for bits in itertools.product((False, True), repeat=len(labs)):
            present = frozenset(l for l, b in zip(labs, bits) if b)
            assert pat.evaluate(p, present) == pat.evaluate(c, present)


def test_mixed_kind_load_no_recompile(served_graph):
    """Satellite contract: after a warmup pool covering every query kind,
    sustained mixed-kind traffic (bool/dist/witness/count/rpq, duplicate
    and fresh keys alike) adds ZERO jit cache entries — every kind's
    bucket grid is pinned up front — and every answer equals its oracle.
    Also pins the per-kind result-cache key: a dist hit must not serve a
    bool request for the same (u, v, pattern)."""
    from repro.core import engine as engine_mod

    g, idx = served_graph
    pool = _query_pool(g, 23, n=20)
    single = [q for q in pool if len(pat.to_dnf(q[2])) == 1]
    # rpq pool: lowered ((a|b)* rides answer_plan) and product-route
    # (order-constrained) regexes, plus u==v ε and unmatchable shapes —
    # few distinct keys so one scheduler batch stays inside the warmed
    # job buckets
    rpq_pool = [
        (0, 7, rpq.parse("(l0 | l1)*")),
        (3, 3, rpq.parse("l2*")),
        (1, 9, rpq.parse("l0 . (l1 | l2)*")),
        (5, 5, rpq.parse("l3 . l0")),
        (2, 11, rpq.parse("(l0 | l1 | l2 | l3)+")),
        (4, 8, rpq.parse("l1 . l2 . l3")),
        (6, 6, rpq.parse("l0?")),
        (0, 13, rpq.Sym(g.n_labels)),          # unmatchable atom
    ]
    with serve.QueryServer(idx, max_wait_ms=1.0, result_cache=64) as srv:
        srv.warmup(pool)
        n0 = engine_mod.jit_cache_entries()
        rng = np.random.default_rng(23)
        futs = []
        for i in range(60):
            u, v, p = pool[int(rng.integers(len(pool)))]
            kd = ("bool", "dist", "witness")[i % 3]
            futs.append(((u, v, p, kd), srv.submit(u, v, p, kind=kd)))
        for (u, v, p) in single[:6]:
            futs.append(((u, v, p, "count"),
                         srv.submit(u, v, p, kind="count", hops=4)))
        for i in range(20):
            u, v, r = rpq_pool[int(rng.integers(len(rpq_pool)))]
            futs.append(((u, v, r, "rpq"),
                         srv.submit(u, v, r, kind="rpq")))
        for (u, v, p, kd), f in futs:
            got = f.result(timeout=60)
            if kd == "bool":
                assert got == dfs_baseline.answer_pcr(g, u, v, p)
            elif kd == "dist":
                assert got == dfs_baseline.shortest_pcr(g, u, v, p)
            elif kd == "witness":
                want = dfs_baseline.shortest_pcr(g, u, v, p)
                if want < 0:
                    assert got is None
                else:
                    assert len(got) == want
                    assert dfs_baseline.verify_witness(g, u, v, p, got)
            elif kd == "rpq":
                assert got == dfs_baseline.answer_rpq(g, u, v, p), \
                    (u, v, rpq.unparse(p))
            else:
                assert got == dfs_baseline.count_routes(
                    g, u, v, p, hops=4, cap=32767)
        assert engine_mod.jit_cache_entries() == n0, \
            "mixed-kind load recompiled after warmup"
        # an rpq submit takes a regex AST, not a pattern — rejected on
        # the caller thread like every other submit-time contract
        with pytest.raises(ValueError, match="rpq"):
            srv.submit(0, 1, pat.label(0), kind="rpq")
        # result-cache keys carry the kind: same (u,v,p) under two kinds
        # is two distinct entries with kind-correct answers
        u, v, p = pool[0]
        b = srv.submit(u, v, p, kind="bool").result(timeout=60)
        d = srv.submit(u, v, p, kind="dist").result(timeout=60)
        assert isinstance(b, (bool, np.bool_)) and isinstance(d, int)
        assert b == (d >= 0)
        # count on a multi-term pattern is rejected on the caller thread
        multi = next(q for q in pool if len(pat.to_dnf(q[2])) > 1)
        with pytest.raises(ValueError, match="single"):
            srv.submit(*multi, kind="count", hops=2)
        with pytest.raises(ValueError, match="kind"):
            srv.submit(u, v, p, kind="fuzzy")


def test_plan_cache_hits(served_graph):
    g, idx = served_graph
    p = pat.all_of([0, 1])
    stats = tdr_query.QueryStats()
    tdr_query.compile_queries(idx, [(0, 1, p), (2, 3, p), (1, 1, p)],
                              stats=stats)
    assert stats.plan_lookups == 3
    assert stats.plan_misses <= 1   # one DNF expansion serves all three
