"""RPQ front-end: oracle-first property suite.

Layered the way the executors were built (the oracle lands first and is
itself cross-checked before anything downstream leans on it):

1. ``dfs_baseline.answer_rpq`` (product-graph BFS) vs brute-force path
   enumeration at tiny sizes — the oracle is tested, not assumed.
2. Front-end algebra: parse/unparse round-trip fuzz (precedence and
   parenthesization edge cases), canonicalize idempotence + language
   preservation, the Glushkov NFA vs the independent span matcher.
3. The DNF-lowering rewriter: every regex it claims index-expressible is
   *language-equal* to its lowering on all words up to length 4; the
   inexpressible shapes must return None (route to the product
   executor) — no silent wrong-fragment lowering.
4. The executors: ``rpq_batch`` equals the oracle across graphs ×
   backends × exact modes × u==v × unreachable × empty-language regexes
   (>= 200 generated cases), LCR-as-RPQ matches the existing LCR path
   bit-for-bit, and ``answer_mixed`` routes kind="rpq" correctly.
"""
import itertools

import numpy as np
import pytest

import hypothesis as hp
import hypothesis.strategies as st

import _qgen
from repro.core import dfs_baseline, graph as G, pattern as pat, rpq
from repro.core import tdr_build, tdr_query

CFG = tdr_build.TDRConfig(vtx_bits=64, g_max=4, k=3)

# built lazily at module scope so @given property tests can share them
_CACHE: dict = {}


def _graphs():
    if "gs" not in _CACHE:
        _CACHE["gs"] = [
            G.random_graph("er", 40, 2.0, 4, seed=7),
            G.random_graph("pa", 30, 2.5, 3, seed=11),
        ]
    return _CACHE["gs"]


def _index(gi: int, backend: str):
    key = ("idx", gi, backend)
    if key not in _CACHE:
        _CACHE[key] = tdr_build.build_index(_graphs()[gi], CFG,
                                            backend=backend)
    return _CACHE[key]


def _rand_rpq(rng, n_labels, depth=3):
    return _qgen.random_rpq(rng, n_labels, depth=depth)


# ------------------------------------------------- 1. the oracle itself
def _enumerate_words(g, u, v, max_len):
    """Every label word along some u→v path of length <= max_len (walks,
    so cycles re-enter; bounded length keeps it finite)."""
    words = set()
    stack = [(u, ())]
    while stack:
        x, w = stack.pop()
        if x == v:
            words.add(w)
        if len(w) == max_len:
            continue
        for i in range(int(g.indptr[x]), int(g.indptr[x + 1])):
            stack.append((int(g.indices[i]), w + (int(g.labels[i]),)))
    return words


@hp.given(seed=st.integers(0, 10_000))
@hp.settings(max_examples=20, deadline=None)
def test_oracle_vs_brute_force_enumeration(seed):
    """answer_rpq on tiny graphs == "some enumerated path word matches",
    for regexes whose shortest accepted word is short enough that the
    length-6 enumeration horizon is conclusive when it says True."""
    rng = np.random.default_rng(seed)
    g = G.random_graph("er", int(rng.integers(4, 13)), 1.5, 3,
                       seed=int(rng.integers(1000)))
    r = _rand_rpq(rng, g.n_labels, depth=2)
    u, v = int(rng.integers(g.n_vertices)), int(rng.integers(g.n_vertices))
    words = _enumerate_words(g, u, v, max_len=6)
    brute = any(rpq.matches(r, w) for w in words)
    got = dfs_baseline.answer_rpq(g, u, v, r)
    if brute:
        assert got, f"oracle missed a length<=6 witness for " \
            f"({u},{v},{rpq.unparse(r)})"
    elif not got:
        pass        # agree on False
    else:
        # oracle says True via a path longer than the horizon: verify by
        # re-running the enumeration one notch deeper before accepting
        deeper = _enumerate_words(g, u, v, max_len=10)
        assert any(rpq.matches(r, w) for w in deeper), \
            f"oracle claims True with no witness <= 10 for " \
            f"({u},{v},{rpq.unparse(r)})"


def test_oracle_fixed_cases():
    """Hand-checkable product-BFS cases: order sensitivity, ε, cycles."""
    g = G.Graph.from_edges(4, 2, [(0, 1, 0), (1, 2, 1), (2, 0, 0)])
    assert dfs_baseline.answer_rpq(g, 0, 2, rpq.parse("l0 . l1"))
    assert not dfs_baseline.answer_rpq(g, 0, 2, rpq.parse("l1 . l0"))
    assert dfs_baseline.answer_rpq(g, 0, 0, rpq.parse("l0*"))      # ε
    assert not dfs_baseline.answer_rpq(g, 0, 0, rpq.parse("l1+"))
    assert dfs_baseline.answer_rpq(g, 0, 0, rpq.parse("(l0.l1.l0)+"))
    assert not dfs_baseline.answer_rpq(g, 0, 2, rpq.parse("l0 . l0"))


# --------------------------------------- 2. front-end algebra + the NFA
@hp.given(seed=st.integers(0, 100_000))
@hp.settings(max_examples=100, deadline=None)
def test_parse_unparse_roundtrip(seed):
    rng = np.random.default_rng(seed)
    r = _rand_rpq(rng, 4, depth=4)
    text = rpq.unparse(r)
    back = rpq.parse(text)
    assert back == r, f"{text!r} reparsed as {rpq.unparse(back)!r}"
    assert rpq.canonical_key(back) == rpq.canonical_key(r)


def test_parse_precedence_and_parens():
    # concatenation binds tighter than |, postfix tighter than both
    assert rpq.parse("l0 | l1 . l2") == rpq.Alt(
        (rpq.Sym(0), rpq.Cat((rpq.Sym(1), rpq.Sym(2)))))
    assert rpq.parse("(l0 | l1) . l2") == rpq.Cat(
        (rpq.Alt((rpq.Sym(0), rpq.Sym(1))), rpq.Sym(2)))
    assert rpq.parse("l0 . l1*") == rpq.Cat(
        (rpq.Sym(0), rpq.Star(rpq.Sym(1))))
    assert rpq.parse("(l0 . l1)*") == rpq.Star(
        rpq.Cat((rpq.Sym(0), rpq.Sym(1))))
    assert rpq.parse("l0*+?") == rpq.Opt(rpq.Plus(rpq.Star(rpq.Sym(0))))
    assert rpq.parse("l0 l1") == rpq.parse("l0 . l1")   # juxtaposition
    assert rpq.parse("0 1") == rpq.parse("l0 . l1")     # bare digits
    for bad in ("", "l0 |", "(l0", "l0)", "*l0", "l0 & l1", "lx"):
        with pytest.raises(ValueError):
            rpq.parse(bad)


@hp.given(seed=st.integers(0, 100_000))
@hp.settings(max_examples=60, deadline=None)
def test_canonicalize_idempotent_language_preserving(seed):
    rng = np.random.default_rng(seed)
    r = _rand_rpq(rng, 3, depth=3)
    c = rpq.canonicalize(r)
    assert rpq.canonicalize(c) is rpq.canonicalize(r)   # interned + stable
    assert rpq.canonical_key(c) == rpq.canonical_key(r)
    for n in range(4):
        for w in itertools.product(range(3), repeat=n):
            assert rpq.matches(c, w) == rpq.matches(r, w), \
                f"canonicalize changed L({rpq.unparse(r)}) at {w}"


@hp.given(seed=st.integers(0, 100_000))
@hp.settings(max_examples=60, deadline=None)
def test_nfa_equals_span_matcher(seed):
    """compile_nfa (what every executor runs) vs the independent span
    matcher, all words up to length 4."""
    rng = np.random.default_rng(seed)
    r = _rand_rpq(rng, 3, depth=3)
    nfa = rpq.compile_nfa(r, 3)
    assert nfa.nullable == rpq.matches(r, ())
    assert bool(nfa.accept & 1) == nfa.nullable
    for n in range(5):
        for w in itertools.product(range(3), repeat=n):
            s = np.uint32(nfa.start)
            for a in w:
                ns = np.uint32(0)
                for q in range(nfa.n_states):
                    if (int(s) >> q) & 1:
                        ns |= nfa.tab[a][q]
                s = ns
            assert bool(int(s) & nfa.accept) == rpq.matches(r, w)


def test_nfa_state_cap():
    wide = rpq.Cat(tuple(rpq.Sym(0) for _ in range(40)))
    with pytest.raises(ValueError, match="at most"):
        rpq.compile_nfa(wide, 2)


# ------------------------------------------------------- 3. the rewriter
@hp.given(seed=st.integers(0, 100_000))
@hp.settings(max_examples=80, deadline=None)
def test_rewriter_language_equality(seed):
    """Whenever the rewriter claims a regex is index-expressible, the
    lowering must be language-EQUAL on every word up to length 4 (both
    directions — a word matches the regex iff its label set satisfies
    the pattern).  Not just agreement on sampled graphs."""
    rng = np.random.default_rng(seed)
    n_l = 3
    r = _rand_rpq(rng, n_l, depth=3)
    p = rpq.lower_to_pattern(r, n_l)
    if p is None:
        return
    for n in range(5):
        for w in itertools.product(range(n_l), repeat=n):
            want = rpq.matches(r, w)
            got = pat.evaluate(p, frozenset(w))
            assert got == want, \
                f"lowering {pat.unparse(p)!r} of {rpq.unparse(r)!r} " \
                f"differs at word {w}"


def test_rewriter_fragment_boundaries():
    """The expressible fragment is exactly unions of single-atom stars;
    order/count-constrained shapes must route to the product executor."""
    n_l = 4
    expressible = ["l0*", "(l0|l1)*", "(l0|l1)* | l2*", "(l0*)*",
                   "(l0* | l1)*", "l0* | l0*"]
    for s in expressible:
        assert rpq.lower_to_pattern(rpq.parse(s), n_l) is not None, s
    inexpressible = ["l0", "l0 . l1", "(l0.l1)*", "l0+", "l0?",
                     "l0* . l1*", "l0 | l1*", "(l0|l1.l2)*"]
    for s in inexpressible:
        assert rpq.lower_to_pattern(rpq.parse(s), n_l) is None, s
    # ... and the executor really does give them product-graph answers
    # (the property suite below covers this across random cases; here we
    # pin one order-sensitive pair an LCR-style lowering would conflate)
    g = G.Graph.from_edges(3, 2, [(0, 1, 0), (1, 2, 1)])
    idx = tdr_build.build_index(g, CFG)
    assert tdr_query.answer_rpq(idx, 0, 2, rpq.parse("l0 . l1"))
    assert not tdr_query.answer_rpq(idx, 0, 2, rpq.parse("l1 . l0"))


def test_lcr_as_rpq_bit_for_bit():
    """(a|b|…)* asked as an RPQ returns the same array as the existing
    LCR pattern path — same planner, same caches, same engine."""
    for gi, g in enumerate(_graphs()):
        idx = _index(gi, "segment")
        rng = np.random.default_rng(100 + gi)
        rpq_qs, pat_qs = [], []
        for _ in range(20):
            u = int(rng.integers(g.n_vertices))
            v = int(rng.integers(g.n_vertices))
            labs = sorted(set(rng.integers(0, g.n_labels, size=2).tolist()))
            rpq_qs.append((u, v, rpq.lcr(labs, g.n_labels)))
            pat_qs.append((u, v, pat.lcr(labs, g.n_labels)))
        got = tdr_query.rpq_batch(idx, rpq_qs)
        want = tdr_query.answer_batch(idx, pat_qs)
        assert got.tolist() == want.tolist()
        oracle = [dfs_baseline.answer_pcr(g, u, v, p)
                  for u, v, p in pat_qs]
        assert got.tolist() == oracle


# ------------------------------------------------------ 4. the executors
def _case_pool(gi, seed, n):
    g = _graphs()[gi]
    rng = np.random.default_rng(seed)
    qs = _qgen.rpq_queries(rng, g, n)
    # make sure the advertised edge cases are represented every run
    qs.append((0, 0, rpq.parse("l0*")))                  # ε at u == v
    qs.append((0, 0, rpq.parse("l0 . l1")))              # u == v, no ε
    qs.append((0, g.n_vertices - 1,
               rpq.Sym(g.n_labels)))                     # unmatchable atom
    qs.append((1, 2, rpq.Star(rpq.Sym(g.n_labels))))     # ε-only language
    return qs


def test_executor_vs_oracle_200_cases():
    """The acceptance sweep: >= 200 generated (graph, query) cases per
    backend, both graphs, mixed expressible/product routes, compared to
    the product-BFS oracle."""
    total = 0
    for backend in ("segment", "pallas"):
        for gi, g in enumerate(_graphs()):
            idx = _index(gi, backend)
            qs = _case_pool(gi, seed=1000 + gi, n=110)
            want = [dfs_baseline.answer_rpq(g, u, v, r) for u, v, r in qs]
            got = tdr_query.rpq_batch(idx, qs, backend=backend)
            assert got.tolist() == want, \
                [(u, v, rpq.unparse(r))
                 for (u, v, r), a, b in zip(qs, got.tolist(), want)
                 if a != b][:5]
            total += len(qs)
    assert total >= 200 * 2     # >= 200 per backend


def test_exact_modes_agree():
    gi = 0
    g = _graphs()[gi]
    idx = _index(gi, "segment")
    qs = _case_pool(gi, seed=5, n=24)
    want = [dfs_baseline.answer_rpq(g, u, v, r) for u, v, r in qs]
    for mode in ("auto", "compact", "full"):
        got = tdr_query.rpq_batch(idx, qs, exact_mode=mode)
        assert got.tolist() == want, mode
    with pytest.raises(ValueError, match="exact_mode"):
        tdr_query.rpq_batch(idx, qs, exact_mode="legacy")


def test_rolled_table_path_vs_oracle(monkeypatch):
    """Above ``_RPQ_TABLE_BYTES`` the segment executor re-gathers one NFA
    state's per-edge rows per push instead of hoisting the whole table:
    forced here with a zero cap, it must give the oracle's answers."""
    gi = 0
    g = _graphs()[gi]
    idx = _index(gi, "segment")
    qs = _case_pool(gi, seed=9, n=24)
    want = [dfs_baseline.answer_rpq(g, u, v, r) for u, v, r in qs]
    monkeypatch.setattr(tdr_query, "_RPQ_TABLE_BYTES", 0)
    tdr_query._rpq_bidi.clear_cache()      # the cap is read at trace time
    try:
        got = tdr_query.rpq_batch(idx, qs, backend="segment",
                                  exact_mode="full")
    finally:
        tdr_query._rpq_bidi.clear_cache()
    assert got.tolist() == want


def test_answer_mixed_routes_rpq():
    gi = 0
    g = _graphs()[gi]
    idx = _index(gi, "segment")
    rng = np.random.default_rng(9)
    mixed = []
    for i, (u, v, p) in enumerate(_qgen.mixed_queries(rng, g, 12)):
        mixed.append((u, v, p, ("bool", "dist")[i % 2]))
    for (u, v, r) in _qgen.rpq_queries(rng, g, 12):
        mixed.append((u, v, r, "rpq"))
    res = tdr_query.answer_mixed(idx, mixed)
    for (q, got) in zip(mixed, res):
        u, v, x, kd = q
        if kd == "bool":
            assert got == dfs_baseline.answer_pcr(g, u, v, x)
        elif kd == "dist":
            assert got == dfs_baseline.shortest_pcr(g, u, v, x)
        else:
            assert got == dfs_baseline.answer_rpq(g, u, v, x)


def test_compile_queries_rejects_rpq_kind():
    idx = _index(0, "segment")
    with pytest.raises(ValueError, match="rpq"):
        tdr_query.compile_queries(idx, [(0, 1, pat.label(0), "rpq")])


def test_rpq_rows_cached():
    idx = _index(0, "segment")
    stats = tdr_query.QueryStats()
    r1 = rpq.parse("l0 . (l1 | l2)*")
    r2 = rpq.parse("l0 (l2 | l1)*")     # same canonical form
    tdr_query.rpq_rows(idx, r1, stats=stats)
    tdr_query.rpq_rows(idx, r2, stats=stats)
    assert stats.plan_lookups == 2
    assert stats.plan_misses <= 1
    rows = tdr_query.rpq_rows(idx, r1)
    assert rows.lowered is None and rows.feasible
    assert rows.n_terms == 1


# --------------------------------------------------- API edges & errors
def test_constructor_helpers_and_nullable():
    assert rpq.cat(rpq.sym(0)) == rpq.Sym(0)     # single-kid cat collapses
    r2 = rpq.cat(rpq.sym(0), rpq.sym(1))
    assert isinstance(r2, rpq.Cat) and not rpq.nullable(r2)
    assert rpq.nullable(rpq.star(rpq.sym(0)))
    assert rpq.nullable(rpq.opt(rpq.sym(1)))
    assert not rpq.nullable(rpq.plus(rpq.sym(1)))
    assert rpq.nullable(rpq.plus(rpq.star(rpq.sym(0))))   # Plus defers
    assert not rpq.nullable(rpq.sym(0))
    assert rpq.nullable(rpq.cat(rpq.star(rpq.sym(0)), rpq.opt(rpq.sym(1))))
    assert rpq.nullable(rpq.alt(rpq.sym(0), rpq.star(rpq.sym(1))))


def test_canonicalize_error_branches():
    with pytest.raises(ValueError, match="negative"):
        rpq.canonicalize(rpq.Sym(-1))
    with pytest.raises(ValueError, match="empty concat"):
        rpq.canonicalize(rpq.Cat(()))
    with pytest.raises(ValueError, match="empty alt"):
        rpq.canonicalize(rpq.Alt(()))
    with pytest.raises(TypeError):
        rpq.canonicalize("l0")
    assert rpq.canonicalize(rpq.Cat((rpq.Sym(3),))) == rpq.Sym(3)


def test_parse_truncated_input():
    with pytest.raises(ValueError, match="unexpected end"):
        rpq.parse("(l0 | l1")
    with pytest.raises(ValueError, match="bad character"):
        rpq.parse("l0 & l1")     # & is pattern syntax, not RPQ syntax


def test_approx_pattern_max_require_truncates_soundly():
    r = rpq.parse("l0 . l1 . l2 . l3")
    full, feas = rpq.approx_pattern(r, 6)
    trunc, feas2 = rpq.approx_pattern(r, 6, max_require=2)
    assert feas and feas2
    # dropping requirements only weakens the filter: anything the full
    # over-approximation accepts, the truncated one must accept too
    for bits in range(1 << 6):
        w = frozenset(i for i in range(6) if bits & (1 << i))
        if pat.evaluate(full, w):
            assert pat.evaluate(trunc, w)
