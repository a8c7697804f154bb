"""Plain exact reference for boolean pattern-constrained reachability.

A query ``(u, v, family, a, b)`` asks for a u→v path whose *set* of edge
labels satisfies the paper's §VI-A pattern family over labels ``a``, ``b``:

* ``AND`` — the path carries both ``a`` and ``b``;
* ``OR``  — the path carries ``a`` or ``b``;
* ``NOT`` — the path carries neither ``a`` nor ``b``;
* ``LCR`` — every edge of the path is labelled ``a`` or ``b``.

The empty path (``u == v``) carries no label.  Each family is a disjunction
of terms ``(require R, forbid F)``; a term holds iff some path avoids every
label of F and carries every label of R.  It is decided by breadth-first
search over the product of the graph with the subsets of R, from both ends
at once: forward states ``(x, s)`` (u reaches x collecting s ⊆ R) and
backward states ``(x, t)`` (x reaches v collecting t), expanding per term
the side with the smaller frontier.  The term holds as soon as some x holds
s and t with ``s ∪ t = R``; it fails once either side has no new state.

Everything is numpy over the benchmark's own CSR arrays, batched over many
terms; nothing of the program is imported.
"""
from __future__ import annotations

import numpy as np

from bench.graphs import Csr

FAMILIES = ("AND", "OR", "NOT", "LCR")
_S = 4                   # subset states of a term with at most 2 required labels
_VISITED_CELLS = 1 << 26  # bool cells per side in one batch of terms


def terms(fam: np.ndarray, a: np.ndarray, b: np.ndarray, n_labels: int):
    """Expand queries into terms: ``(qid, table, full)``.

    ``table[j, l]`` is -1 if term j forbids label l, 1 or 2 if l is its
    first or second required label, else 0; ``full[j]`` is the subset of
    required bits a satisfying path collects."""
    fam = np.asarray(fam, np.int64)
    if fam.size and (fam.min() < 0 or fam.max() >= len(FAMILIES)):
        raise ValueError(f"family ids outside 0..{len(FAMILIES) - 1}")
    AND, OR, NOT, LCR = range(len(FAMILIES))
    n_terms = np.where(fam == OR, 2, 1)
    qid = np.repeat(np.arange(fam.shape[0], dtype=np.int64), n_terms)
    second = np.zeros(qid.shape[0], dtype=bool)   # OR's term over b
    second[np.cumsum(n_terms)[fam == OR] - 1] = True
    f = fam[qid]
    la = np.asarray(a, np.int64)[qid]
    lb = np.asarray(b, np.int64)[qid]
    rows = np.arange(qid.shape[0])
    table = np.zeros((qid.shape[0], n_labels), np.int8)
    table[f == LCR] = -1
    for fid, va, vb in ((AND, 1, 2), (NOT, -1, -1), (LCR, 0, 0)):
        sel = f == fid
        table[rows[sel], la[sel]] = va
        table[rows[sel], lb[sel]] = vb
    sel = (f == OR) & ~second
    table[rows[sel], la[sel]] = 1
    sel = (f == OR) & second
    table[rows[sel], lb[sel]] = 1
    full = np.select([f == AND, f == OR], [3, 1], 0).astype(np.int8)
    return qid, table, full


def answer(g: Csr, rg: Csr, u, v, fam, a, b) -> np.ndarray:
    """Exact answers (bool [N]) for the queries; ``rg = g.reverse()``."""
    u = np.asarray(u, np.int64)
    v = np.asarray(v, np.int64)
    qid, table, full = terms(fam, a, b, g.n_labels)
    held = holds(g, rg, u[qid], v[qid], table, full)
    out = np.zeros(u.shape[0], dtype=bool)
    np.logical_or.at(out, qid, held)
    return out


def holds(g: Csr, rg: Csr, tu, tv, table, full) -> np.ndarray:
    """Whether each term ``(tu, tv, table, full)`` has a satisfying path."""
    n = tu.shape[0]
    out = np.zeros(n, dtype=bool)
    step = max(1, _VISITED_CELLS // (g.n_vertices * _S))
    for j0 in range(0, n, step):
        sl = slice(j0, j0 + step)
        out[sl] = _holds_batch(g, rg, tu[sl], tv[sl], table[sl], full[sl])
    return out


def _expand(csr: Csr, keys: np.ndarray, table: np.ndarray) -> np.ndarray:
    """Successor states of ``keys`` over ``csr``'s edges (unsorted, with
    repeats).  A key is ``(term * V + vertex) * 4 + subset``."""
    v_n = csr.n_vertices
    term = keys // (v_n * _S)
    x = (keys // _S) % v_n
    s = keys % _S
    start = csr.indptr[x]
    cnt = csr.indptr[x + 1] - start
    tot = int(cnt.sum())
    if tot == 0:
        return np.zeros(0, np.int64)
    first = np.cumsum(cnt) - cnt
    e = np.repeat(start - first, cnt) + np.arange(tot)
    tt = np.repeat(term, cnt)
    effect = table[tt, csr.labels[e]]
    ok = effect >= 0
    return ((tt[ok] * v_n + csr.indices[e][ok]) * _S
            + (np.repeat(s, cnt)[ok] | effect[ok]))


def _meets(new: np.ndarray, other: np.ndarray, full: np.ndarray,
           v_n: int) -> np.ndarray:
    """Terms (local ids) for which a new state meets a visited state of
    the other side with the union of their subsets equal to ``full``."""
    term = new // (v_n * _S)
    s = new % _S
    base = new - s
    hit = np.zeros(new.shape[0], dtype=bool)
    for t in range(_S):
        hit |= ((s | t) == full[term]) & other[base + t]
    return np.unique(term[hit])


def _holds_batch(g, rg, tu, tv, table, full) -> np.ndarray:
    n = tu.shape[0]
    v_n = g.n_vertices
    full = full.astype(np.int64)
    ids = np.arange(n, dtype=np.int64)
    seen = {True: np.zeros(n * v_n * _S, bool),
            False: np.zeros(n * v_n * _S, bool)}
    front = {True: (ids * v_n + tu) * _S, False: (ids * v_n + tv) * _S}
    seen[True][front[True]] = True
    seen[False][front[False]] = True
    held = (tu == tv) & (full == 0)
    done = held.copy()
    while not done.all():
        counts = {side: np.bincount(front[side] // (v_n * _S), minlength=n)
                  for side in (True, False)}
        done |= (counts[True] == 0) | (counts[False] == 0)
        forward = counts[True] <= counts[False]
        for side, csr in ((True, g), (False, rg)):
            term = front[side] // (v_n * _S)
            go = ~done[term] & (forward[term] == side)
            stay = ~done[term] & ~go
            new = np.unique(_expand(csr, front[side][go], table))
            new = new[~seen[side][new]]
            seen[side][new] = True
            met = _meets(new, seen[not side], full, v_n)
            held[met] = True
            front[side] = np.concatenate([front[side][stay], new])
        done |= held
    return held
