"""The paper's §VI-A synthetic graph families, made from a seed.

Copies of ``repro.core.graph.erdos_renyi`` and ``preferential_attachment``
(same draws, so the same seed gives the same edge set as the program's own
generators), with the edge-set dedup and CSR assembly done in numpy.  The
benchmark keeps its own copy so that the graph the reference searches is
never one the program made.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Csr:
    """Edge-labelled digraph as CSR sorted by (src, dst, label)."""
    n_vertices: int
    n_labels: int
    indptr: np.ndarray    # int64 [V+1]
    indices: np.ndarray   # int32 [E] destination
    labels: np.ndarray    # int32 [E]

    @property
    def n_edges(self) -> int:
        return int(self.indices.shape[0])

    @property
    def src(self) -> np.ndarray:
        return np.repeat(np.arange(self.n_vertices, dtype=np.int32),
                         np.diff(self.indptr))

    def reverse(self) -> "Csr":
        """Same edges grouped by destination: ``indices`` are sources."""
        order = np.lexsort((self.src, self.indices))
        return _assemble(self.n_vertices, self.n_labels,
                         self.indices[order], self.src[order],
                         self.labels[order])


def _assemble(n_vertices: int, n_labels: int, src, dst, lab) -> Csr:
    counts = np.bincount(src, minlength=n_vertices)
    indptr = np.zeros(n_vertices + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    return Csr(n_vertices, n_labels, indptr, np.asarray(dst, np.int32),
               np.asarray(lab, np.int32))


def from_edges(n_vertices: int, n_labels: int, src, dst, lab) -> Csr:
    """Edge *set* (duplicates collapse) as CSR in (src, dst, label) order."""
    v, l = np.int64(n_vertices), np.int64(n_labels)
    keys = np.unique((np.asarray(src, np.int64) * v
                      + np.asarray(dst, np.int64)) * l
                     + np.asarray(lab, np.int64))
    uv = keys // l
    return _assemble(n_vertices, n_labels, (uv // v).astype(np.int32),
                     uv % v, keys % l)


def erdos_renyi(n_vertices: int, avg_degree: float, n_labels: int,
                rng: np.random.Generator) -> Csr:
    """ER digraph: uniform endpoints, no self-loops, labels uniform."""
    n_edges = int(n_vertices * avg_degree)
    src = rng.integers(0, n_vertices, size=n_edges)
    dst = rng.integers(0, n_vertices, size=n_edges)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    lab = rng.integers(0, n_labels, size=src.shape[0])
    return from_edges(n_vertices, n_labels, src, dst, lab)


def preferential_attachment(n_vertices: int, avg_degree: float,
                            n_labels: int, rng: np.random.Generator) -> Csr:
    """PA digraph: each new vertex links to ``avg_degree/2`` earlier targets
    drawn by in-degree + 1 and receives as many edges from uniform earlier
    sources.  A per-vertex loop, O(V^2): about a second at V=8192."""
    m = max(1, int(round(avg_degree / 2)))
    src, dst, lab = [], [], []
    weight = np.ones(n_vertices, dtype=np.float64)
    for v in range(1, n_vertices):
        w = weight[:v] / weight[:v].sum()
        targets = rng.choice(v, size=min(m, v), replace=False, p=w)
        for t in targets:
            src.append(v)
            dst.append(int(t))
            lab.append(int(rng.integers(0, n_labels)))
            weight[t] += 1.0
        for s in rng.integers(0, v, size=m):
            src.append(int(s))
            dst.append(v)
            lab.append(int(rng.integers(0, n_labels)))
            weight[v] += 1.0
    return from_edges(n_vertices, n_labels, src, dst, lab)


FAMILIES = {"er": erdos_renyi, "pa": preferential_attachment}


def make(spec: dict, rng: np.random.Generator) -> Csr:
    """The graph a configuration file's ``graph`` entry describes."""
    return FAMILIES[spec["family"]](spec["n_vertices"], spec["avg_degree"],
                                    spec["n_labels"], rng)
