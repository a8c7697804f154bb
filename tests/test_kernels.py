"""Pallas kernels (interpret mode) vs pure-jnp oracles: shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitset
from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("m,k,w", [
    (8, 32, 1), (16, 64, 2), (50, 96, 3), (130, 256, 5), (1, 32, 1),
    (257, 160, 7), (64, 1024, 4),
    # the fully-occupied default tile + non-tile-multiple wide shapes
    # (the vectorized column-broadcast inner loop's padding paths)
    (128, 128, 128), (100, 224, 40), (70, 64, 33),
])
@pytest.mark.parametrize("density", [0.02, 0.3])
def test_bitset_matmul_sweep(m, k, w, density):
    a_bool = RNG.random((m, k)) < density
    x = RNG.integers(0, 2 ** 32, size=(k, w), dtype=np.uint32)
    a_packed = jnp.asarray(bitset.pack_bits_np(a_bool))
    xj = jnp.asarray(x)
    want = np.asarray(ref.bitset_matmul_ref(a_packed, xj))
    got = np.asarray(ops.frontier_step(a_packed, xj, mode="interpret"))
    np.testing.assert_array_equal(got, want)


def test_frontier_step_tiles_passthrough():
    """Explicit (ti, tk, tw) overrides reach the kernel and stay exact at
    shapes that are not multiples of the requested tiles."""
    a_bool = RNG.random((90, 160)) < 0.1
    x = RNG.integers(0, 2 ** 32, size=(160, 5), dtype=np.uint32)
    a_packed = jnp.asarray(bitset.pack_bits_np(a_bool))
    xj = jnp.asarray(x)
    want = np.asarray(ref.bitset_matmul_ref(a_packed, xj))
    for tiles in [(32, 64, 2), (64, 160, 5), (128, 32, 8)]:
        got = np.asarray(ops.frontier_step(a_packed, xj, mode="interpret",
                                           tiles=tiles))
        np.testing.assert_array_equal(got, want, err_msg=str(tiles))


def test_bitset_matmul_mxu_path():
    a_bool = RNG.random((40, 96)) < 0.2
    x = RNG.integers(0, 2 ** 32, size=(96, 3), dtype=np.uint32)
    a_packed = jnp.asarray(bitset.pack_bits_np(a_bool))
    want = np.asarray(ref.bitset_matmul_ref(a_packed, jnp.asarray(x)))
    got = np.asarray(ops.frontier_step(a_packed, jnp.asarray(x),
                                       mode="mxu"))
    np.testing.assert_array_equal(got, want)


def test_bitset_matmul_tiling_variants():
    from repro.kernels.bitset_matmul import bitset_matmul
    a_bool = RNG.random((100, 128)) < 0.1
    x = RNG.integers(0, 2 ** 32, size=(128, 6), dtype=np.uint32)
    a_packed = jnp.asarray(bitset.pack_bits_np(a_bool))
    want = np.asarray(ref.bitset_matmul_ref(a_packed, jnp.asarray(x)))
    for ti, tk, tw in [(32, 32, 2), (128, 64, 3), (8, 128, 6)]:
        got = np.asarray(bitset_matmul(a_packed, jnp.asarray(x), ti=ti,
                                       tk=tk, tw=tw, interpret=True))
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("j,g,k,wv,wl", [
    (5, 2, 1, 1, 1), (37, 4, 3, 3, 2), (128, 4, 2, 8, 2), (1, 1, 4, 2, 2),
])
def test_way_filter_sweep(j, g, k, wv, wl):
    hv = RNG.integers(0, 2 ** 32, (j, g, wv), dtype=np.uint32)
    hl = RNG.integers(0, 2 ** 32, (j, g, wl), dtype=np.uint32)
    vv = RNG.integers(0, 2 ** 32, (j, g, k, wv), dtype=np.uint32)
    vl = RNG.integers(0, 2 ** 32, (j, g, k, wl), dtype=np.uint32)
    vb = (RNG.integers(0, 2 ** 32, (j, wv), dtype=np.uint32)
          & RNG.integers(0, 2 ** 32, (j, wv), dtype=np.uint32)
          & RNG.integers(0, 2 ** 32, (j, wv), dtype=np.uint32))
    rq = (RNG.integers(0, 2 ** 32, (j, wl), dtype=np.uint32)
          & RNG.integers(0, 2 ** 32, (j, wl), dtype=np.uint32))
    fb = RNG.integers(0, 2 ** 32, (j, wl), dtype=np.uint32)
    npl = np.zeros(wl, np.uint32)
    npl[-1] = 1 << 31
    args = [jnp.asarray(v) for v in (hv, hl, vv, vl, vb, rq, fb, npl)]
    want = np.asarray(ops.filter_ways(*args, mode="ref"))
    got = np.asarray(ops.filter_ways(*args, mode="interpret"))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,w", [(1, 1), (77, 9), (600, 3)])
def test_popcount_sweep(n, w):
    x = RNG.integers(0, 2 ** 32, (n, w), dtype=np.uint32)
    want = np.asarray(ops.popcount(jnp.asarray(x), mode="ref"))
    got = np.asarray(ops.popcount(jnp.asarray(x), mode="interpret"))
    np.testing.assert_array_equal(got, want)
    # cross-check against numpy
    expect = np.array([bin(int(v)).count("1") for row in x for v in row]
                      ).reshape(n, w).sum(-1)
    np.testing.assert_array_equal(want, expect)


def test_frontier_step_is_one_bfs_round():
    """Kernel semantics == one BFS frontier expansion on a real graph."""
    from repro.core import graph as G
    g = G.erdos_renyi(64, 3.0, 2, seed=0)
    adj = np.zeros((64, 64), dtype=bool)
    adj[g.src, g.indices] = True
    a_packed = jnp.asarray(bitset.pack_bits_np(adj))
    # frontier = identity bits: after one step, row u = successors of u
    eye = np.eye(64, dtype=bool)
    x = jnp.asarray(bitset.pack_bits_np(eye))
    out = np.asarray(ops.frontier_step(a_packed, x, mode="interpret"))
    out_bool = np.unpackbits(
        out.view(np.uint8), axis=1, bitorder="little")[:, :64].astype(bool)
    np.testing.assert_array_equal(out_bool, adj)


# --------------------------------------------- edge-list class expansion
def _edge_case(v, q, edges, e_pad, seed):
    """A class's edge list sorted by destination row, padded to ``e_pad``
    with in-range garbage (a pad entry that were read would set bits)."""
    rng = np.random.default_rng(seed)
    dst = np.array([d for d, _ in edges], np.int32)
    src = np.array([s for _, s in edges], np.int32)
    order = np.argsort(dst, kind="stable")
    dst_p = rng.integers(0, v, e_pad).astype(np.int32)
    src_p = rng.integers(0, v, e_pad).astype(np.int32)
    dst_p[:len(edges)] = dst[order]
    src_p[:len(edges)] = src[order]
    x = rng.integers(1, 2 ** 32, size=(v, q), dtype=np.uint32)
    return dst_p, src_p, len(edges), x


def _random_edges(v, n, seed):
    rng = np.random.default_rng(seed)
    return [(int(d), int(s)) for d, s in rng.integers(0, v, (n, 2))]


@pytest.mark.parametrize("v,q,edges,e_pad,chunk", [
    (37, 7, [], 32, None),                               # empty class
    (24, 7, [(3, 5), (3, 5), (4, 4), (0, 0), (3, 5), (9, 4), (9, 4)],
     32, None),                                          # dups, self-loops
    (37, 1, _random_edges(37, 50, 1), 64, None),         # V % 8 != 0
    (131, 32, _random_edges(131, 200, 2), 256, None),    # V % 128 != 0
    (64, 32, _random_edges(64, 48, 3), 48, None),        # count == bucket
    (64, 7, _random_edges(64, 64, 4), 64, None),         # count == bucket
    (50, 32, _random_edges(50, 90, 5), 96, 16),          # grid of chunks
    (50, 7, _random_edges(50, 37, 6), 96, 16),           # ... with empty ones
])
def test_lane_matmul_edges_matches_dense(v, q, edges, e_pad, chunk):
    """The edge kernel (interpret), its "ref" twin and the dense
    ``lane_matmul`` oracle on the packed matrix of the same edges agree
    bit for bit; padding entries past ``count`` never reach a row."""
    from repro.kernels.bitset_matmul import lane_matmul_edges
    dst, src, count, x = _edge_case(v, q, edges, e_pad, seed=v + q)
    kw = bitset.n_words(v)
    a = np.zeros((v, kw), np.uint32)
    if edges:
        bitset.set_bits_np(a, (dst[:count],), src[:count])
    x_k = np.zeros((kw * 32, q), np.uint32)
    x_k[:v] = x
    want = np.asarray(ref.lane_matmul_ref(jnp.asarray(a), jnp.asarray(x_k),
                                          op="or"))
    args = (jnp.asarray(dst), jnp.asarray(src), jnp.int32(count),
            jnp.asarray(x))
    got_ref = np.asarray(ops.frontier_step_edges(*args, n_rows=v,
                                                 mode="ref"))
    if chunk is None:
        got = np.asarray(ops.frontier_step_edges(*args, n_rows=v,
                                                 mode="interpret"))
    else:
        got = np.asarray(lane_matmul_edges(*args, n_rows=v, chunk=chunk,
                                           interpret=True))
    np.testing.assert_array_equal(got_ref, want)
    np.testing.assert_array_equal(got, want)
    if not edges:
        assert not got.any()
