"""The one traffic generator: a mix file's parameters -> seeded queries.

A mix (``bench/traffic/<name>.json``) asks for boolean queries of the
paper's §VI-A families, a share ``true_share`` of them with the answer
true and the rest false.  Queries are drawn uniformly — endpoints a
uniform pair of distinct vertices, the two labels a uniform pair of
distinct labels — and kept when their answer is the one wanted, so each
part is uniform traffic conditioned on its answer.  With
``false_reachable`` a false draw is kept only where u reaches v when the
labels are ignored, so that only the labels refute it: the filter
cascade's reachability tests cannot, and most such draws reach the exact
search.  No (u, v, pattern) appears twice.

Deciding the answer of every draw by search would cost as much as the
program under test, so most draws are settled by a pivot: h, the vertex
of highest total degree.  For each label set a family allows, one
bit-parallel breadth-first search from h finds every x that h reaches and
every x that reaches h.  Then u→h→v is a path, and when each required
label has an edge whose ends lie on a cycle through h, a walk through h
collects them: such a draw is true.  Every other draw goes to the exact
reference (``bench.reference``); those are the draws near the edge of the
graph's giant component, where the search is small.

A family whose answer is rarer than ``min_share`` among uniform draws
(LCR-true on a sparse ER graph: about one draw in 10^5; AND-false on a
strongly connected PA graph: none) cannot be filled that way.  For the
true part it is drawn from seeded walks instead (u uniform, v where the
walk ends, the labels read off the walk); for the false part it is left
out and the other families share its count.  Which happened is reported.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import reference
from bench.graphs import Csr

FAMILIES = reference.FAMILIES


@dataclasses.dataclass
class Queries:
    u: np.ndarray       # int64 [N]
    v: np.ndarray       # int64 [N]
    fam: np.ndarray     # int64 [N] index into FAMILIES
    a: np.ndarray       # int64 [N] first label (a < b)
    b: np.ndarray       # int64 [N]
    truth: np.ndarray   # bool  [N] each query's answer
    info: dict          # per answer and family: count, how drawn, share

    def __len__(self) -> int:
        return int(self.u.shape[0])

    def take(self, sl) -> "Queries":
        return Queries(self.u[sl], self.v[sl], self.fam[sl], self.a[sl],
                       self.b[sl], self.truth[sl], self.info)


def pair_index(a: np.ndarray, b: np.ndarray, n_labels: int) -> np.ndarray:
    """Index of the label pair a < b among the L(L-1)/2 pairs."""
    return a * n_labels - a * (a + 1) // 2 + (b - a - 1)


class Pivot:
    """Reachability to and from the pivot under every label set that a
    family allows: class 0 allows every label (AND, OR), class 1+p forbids
    pair p (NOT), class 1+P+p allows only pair p (LCR)."""

    def __init__(self, g: Csr, rg: Csr):
        n_l = g.n_labels
        self.n_pairs = n_l * (n_l - 1) // 2
        a, b = np.triu_indices(n_l, k=1)
        allow = np.ones((n_l, 1 + 2 * self.n_pairs), dtype=bool)
        for p, (la, lb) in enumerate(zip(a, b)):
            allow[[la, lb], 1 + p] = False
            allow[:, 1 + self.n_pairs + p] = False
            allow[[la, lb], 1 + self.n_pairs + p] = True
        words = -(-allow.shape[1] // 64)
        bits = np.zeros((n_l, words * 64), dtype=bool)
        bits[:, :allow.shape[1]] = allow
        allow_w = np.packbits(bits, axis=1, bitorder="little").view("<u8")
        deg = np.diff(g.indptr) + np.diff(rg.indptr)
        self.h = int(np.argmax(deg))
        self.fwd = _reach(g, self.h, allow_w)    # h reaches x
        self.bwd = _reach(rg, self.h, allow_w)   # x reaches h
        on = self.bit(self.fwd, g.src, 0) & self.bit(self.bwd, g.indices, 0)
        self.cycle_label = np.bincount(g.labels[on], minlength=n_l) > 0

    @staticmethod
    def bit(words: np.ndarray, x: np.ndarray, c) -> np.ndarray:
        c = np.asarray(c, np.int64)
        return ((words[x, c // 64] >> (c % 64).astype(np.uint64))
                & np.uint64(1)).astype(bool)

    def certify(self, u, v, fam, a, b, n_labels: int) -> np.ndarray:
        """True where the draw is proven true through the pivot."""
        AND, OR, NOT, LCR = range(len(FAMILIES))
        p = pair_index(a, b, n_labels)
        cls = np.select([fam == NOT, fam == LCR],
                        [1 + p, 1 + self.n_pairs + p], 0)
        via = self.bit(self.bwd, u, cls) & self.bit(self.fwd, v, cls)
        la, lb = self.cycle_label[a], self.cycle_label[b]
        labels_ok = np.select([fam == AND, fam == OR], [la & lb, la | lb],
                              True)
        return via & labels_ok


def _reach(g: Csr, h: int, allow_w: np.ndarray) -> np.ndarray:
    """Bit-parallel BFS from ``h`` over ``g``'s edges: bit c of row x is
    set iff h reaches x using only labels that class c allows."""
    reach = np.zeros((g.n_vertices, allow_w.shape[1]), dtype=np.uint64)
    reach[h] = ~np.uint64(0)
    front = np.array([h], dtype=np.int64)
    while front.size:
        start = g.indptr[front]
        cnt = g.indptr[front + 1] - start
        tot = int(cnt.sum())
        if tot == 0:
            break
        first = np.cumsum(cnt) - cnt
        e = np.repeat(start - first, cnt) + np.arange(tot)
        val = reach[np.repeat(front, cnt)] & allow_w[g.labels[e]]
        dst = g.indices[e]
        order = np.argsort(dst)
        dst = dst[order]
        starts = np.flatnonzero(np.r_[True, dst[1:] != dst[:-1]])
        red = np.bitwise_or.reduceat(val[order], starts, axis=0)
        to = dst[starts]
        new = reach[to] | red
        changed = (new != reach[to]).any(axis=1)
        reach[to] = new
        front = to[changed].astype(np.int64)
    return reach


def _uniform(rng, n: int, fid: int, g: Csr):
    u = rng.integers(0, g.n_vertices, n)
    v = (u + rng.integers(1, g.n_vertices, n)) % g.n_vertices
    a = rng.integers(0, g.n_labels, n)
    b = (a + rng.integers(1, g.n_labels, n)) % g.n_labels
    return u, v, np.full(n, fid), np.minimum(a, b), np.maximum(a, b)


def _walks(rng, n: int, fid: int, g: Csr, max_len: int):
    """Walks of up to ``max_len`` edges from uniform starts, each step a
    uniform out-edge; a walk stops before an edge that would bring a third
    distinct label.  Returns draws whose walk ended away from its start;
    their pattern over the walk's labels is true by construction."""
    u = rng.integers(0, g.n_vertices, n)
    steps = rng.integers(1, max_len + 1, n)
    x = u.copy()
    la = np.full(n, -1)
    lb = np.full(n, -1)
    alive = np.ones(n, dtype=bool)
    moved = np.zeros(n, dtype=bool)
    for k in range(max_len):
        deg = g.indptr[x + 1] - g.indptr[x]
        alive &= (steps > k) & (deg > 0)
        e = g.indptr[x] + (rng.random(n) * np.maximum(deg, 1)).astype(
            np.int64)
        e = np.minimum(e, g.n_edges - 1)
        lab = g.labels[e].astype(np.int64)
        fits = (la < 0) | (lab == la) | (lb < 0) | (lab == lb)
        alive &= fits
        lb = np.where(alive & (la >= 0) & (lab != la) & (lb < 0), lab, lb)
        la = np.where(alive & (la < 0), lab, la)
        x = np.where(alive, g.indices[e], x)
        moved |= alive
    other = (la + rng.integers(1, g.n_labels, n)) % g.n_labels
    lb = np.where(lb < 0, other, lb)
    keep = moved & (x != u)
    a, b = np.minimum(la, lb)[keep], np.maximum(la, lb)[keep]
    return u[keep], x[keep], np.full(int(keep.sum()), fid), a, b


def _answers(g, rg, pivot, u, v, fam, a, b) -> np.ndarray:
    ans = pivot.certify(u, v, fam, a, b, g.n_labels)
    rest = np.flatnonzero(~ans)
    if rest.size:
        ans[rest] = reference.answer(g, rg, u[rest], v[rest], fam[rest],
                                     a[rest], b[rest])
    return ans


def _key(g: Csr, u, v, fam, a, b) -> np.ndarray:
    pair = pair_index(a, b, g.n_labels)
    n_pairs = g.n_labels * (g.n_labels - 1) // 2
    return ((fam * n_pairs + pair) * g.n_vertices + u) * g.n_vertices + v


def _wanted(g, rg, pivot, draw, truth: bool, reachable: bool) -> np.ndarray:
    """Where a draw has the answer ``truth`` (and, for a false draw with
    ``reachable``, u reaches v through the pivot under every label)."""
    ok = _answers(g, rg, pivot, *draw) == truth
    if reachable and not truth:
        u, v = draw[0], draw[1]
        ok &= pivot.bit(pivot.bwd, u, 0) & pivot.bit(pivot.fwd, v, 0)
    return ok


def _part(g, rg, pivot, mix, truth: bool, n: int, rng, seen):
    """``n`` draws with answer ``truth`` whose keys are not in ``seen``."""
    reachable = bool(mix.get("false_reachable", False))
    probe = int(mix["probe_draws"])
    plan = {}
    for name in mix["families"]:
        draw = _uniform(rng, probe, FAMILIES.index(name), g)
        share = float(_wanted(g, rg, pivot, draw, truth, reachable).mean())
        if share >= mix["min_share"]:
            plan[name] = ("uniform", share)
        elif truth:
            plan[name] = ("walk", share)
        else:
            plan[name] = ("left out", share)
    kept = [f for f in plan if plan[f][0] != "left out"]
    if not kept:
        raise ValueError(f"no family of the mix has answer {truth} here")
    counts = dict.fromkeys(kept, n // len(kept))
    for f in kept[:n % len(kept)]:
        counts[f] += 1
    got = []
    for name in kept:
        fid = FAMILIES.index(name)
        how, share = plan[name]
        need = counts[name]
        while need > 0:
            if how == "walk":
                want = 2 * need + 16
                draw = _walks(rng, want, fid, g, int(mix["walk_max_edges"]))
            else:
                want = int(need / share * 1.2) + 16
                draw = _uniform(rng, want, fid, g)
                ok = _wanted(g, rg, pivot, draw, truth, reachable)
                draw = tuple(x[ok] for x in draw)
            key = _key(g, *draw)
            key, first = np.unique(key, return_index=True)
            fresh = ~np.isin(key, seen)
            idx = np.sort(first[fresh])[:need]
            seen = np.union1d(seen, _key(g, *(x[idx] for x in draw)))
            got.append(tuple(x[idx] for x in draw))
            need -= idx.size
    info = {name: {"count": counts.get(name, 0), "drawn": plan[name][0],
                   "share_of_uniform_draws": plan[name][1]}
            for name in plan}
    return got, seen, info


def generate(g: Csr, rg: Csr, mix: dict, n: int,
             rng: np.random.Generator, pivot: Pivot | None = None
             ) -> Queries:
    """``n`` distinct queries of ``mix``, in a seeded random order."""
    pivot = pivot or Pivot(g, rg)
    n_true = int(round(n * float(mix["true_share"])))
    parts, truths, info = [], [], {}
    seen = np.zeros(0, np.int64)
    for truth, count in ((True, n_true), (False, n - n_true)):
        if count == 0:
            continue
        got, seen, info[str(truth).lower()] = _part(g, rg, pivot, mix, truth,
                                                    count, rng, seen)
        parts += got
        truths += [np.full(p[0].shape[0], truth) for p in got]
    cols = [np.concatenate([p[i] for p in parts]).astype(np.int64)
            for i in range(5)]
    truth = np.concatenate(truths)
    order = rng.permutation(cols[0].shape[0])
    u, v, fam, a, b = (c[order] for c in cols)
    return Queries(u, v, fam, a, b, truth[order], info)
