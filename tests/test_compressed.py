"""Two-level compressed planes: codec round-trips on degenerate shapes,
patch-vs-fresh canonical equality, the block operand and its sparse
closure path, and the compressed cache carried across 100+ random
``update_index`` interleavings on both backends.

The contract under test is bit-identity everywhere: ``decompress`` must
reproduce the dense plane exactly, ``patch_rows``/``patch_blocks`` must
land in the same canonical form a fresh ``compress`` of the patched
dense plane would, the block-sparse closure must equal the dense
fixpoint word-for-word, and an index's cached compressed planes must
stay equal to fresh compressions of its dense planes after any update.
"""
import numpy as np
import pytest

import hypothesis as hp
import hypothesis.strategies as st

import jax.numpy as jnp

from repro.core import compressed as C, engine, graph as G, tdr_build
from repro.kernels import ops
from test_updates import N_L, N_V, _random_step

CFG = tdr_build.TDRConfig(vtx_bits=64, g_max=4, k=3)


def _mix_rows(rng, n, w, nbits, p_zero=0.3, p_one=0.3):
    """Random packed rows with a heavy mix of all-zero / all-one rows —
    the distribution the two-level layout is built for."""
    masks = C._valid_masks(w, nbits)
    rows = (rng.integers(0, 2 ** 32, size=(n, w), dtype=np.uint32)
            & masks[None, :])
    u = rng.random(n)
    rows[u < p_zero] = 0
    rows[u > 1 - p_one] = masks[None, :]
    return rows


# ------------------------------------------------------- row-level codec
@pytest.mark.parametrize("shape,nbits", [
    ((0, 3), None),      # empty graph: a plane with zero rows
    ((1, 1), 1),         # V=1, a single valid bit
    ((5, 2), 37),        # valid bits not a multiple of the word size
    ((7, 2), 63),        # partial tail word
    ((4, 3, 2), 64),     # leading plane dims (V, g_max, W)
])
def test_roundtrip_degenerate_shapes(shape, nbits):
    rng = np.random.default_rng(sum(shape))
    w = shape[-1]
    n = int(np.prod(shape[:-1]))
    plane = _mix_rows(rng, n, w, nbits or w * 32).reshape(shape)
    c = C.compress(plane, nbits=nbits)
    np.testing.assert_array_equal(c.decompress(), plane)
    assert c.shape == shape


def test_roundtrip_uniform_planes():
    masks = C._valid_masks(2, 50)
    zeros = np.zeros((6, 2), np.uint32)
    ones = np.broadcast_to(masks, (6, 2)).copy()
    for plane, state in ((zeros, C.ALL_ZERO), (ones, C.ALL_ONE)):
        c = C.compress(plane, nbits=50)
        np.testing.assert_array_equal(c.decompress(), plane)
        assert (c.row_states == state).all()
        assert c.pool.size == 0          # uniform rows never hit the pool
        assert c.nbytes < c.dense_nbytes


@hp.given(seed=st.integers(0, 10_000))
@hp.settings(max_examples=25, deadline=None)
def test_patch_rows_matches_fresh_compress(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 40))
    w = int(rng.integers(1, 5))
    nbits = int(rng.integers(1, w * 32 + 1))
    rows = _mix_rows(rng, n, w, nbits)
    c = C.compress(rows, nbits=nbits)
    np.testing.assert_array_equal(c.decompress(), rows)

    sel = rng.choice(n, size=int(rng.integers(0, n + 1)), replace=False)
    new = _mix_rows(rng, sel.size, w, nbits)
    rows2 = rows.copy()
    rows2[sel] = new
    c2 = c.patch_rows(sel, new)
    np.testing.assert_array_equal(c2.decompress(), rows2)
    # canonical form, not just bit-identity: a patched layout must be
    # indistinguishable from a fresh compression (same_as compares the
    # state arrays and pool directly)
    assert c2.same_as(C.compress(rows2, nbits=nbits))


# ----------------------------------------------------- block-level codec
@pytest.mark.parametrize("m,kw,nbits,br,bw", [
    (1, 1, 1, 8, 1),     # single row, single valid bit
    (5, 2, 37, 8, 1),    # row tail: m not a multiple of br
    (16, 4, 128, 4, 2),  # multi-word blocks, exact grid
    (9, 3, 70, 8, 1),    # both tails partial
])
def test_blocks_roundtrip(m, kw, nbits, br, bw):
    rng = np.random.default_rng(m * 31 + kw)
    a = _mix_rows(rng, m, kw, nbits)
    c = C.compress_blocks(a, br=br, bw=bw, nbits=nbits)
    np.testing.assert_array_equal(C.decompress_blocks(c), a)
    zeros = np.zeros_like(a)
    cz = C.compress_blocks(zeros, br=br, bw=bw, nbits=nbits)
    np.testing.assert_array_equal(C.decompress_blocks(cz), zeros)
    assert cz.n_mixed == 0


@hp.given(seed=st.integers(0, 10_000))
@hp.settings(max_examples=20, deadline=None)
def test_patch_blocks_matches_fresh(seed):
    rng = np.random.default_rng(seed)
    m = int(rng.integers(1, 30))
    kw = int(rng.integers(1, 4))
    nbits = int(rng.integers(1, kw * 32 + 1))
    a = _mix_rows(rng, m, kw, nbits)
    c = C.compress_blocks(a, nbits=nbits)
    sel = np.sort(rng.choice(m, size=int(rng.integers(1, m + 1)),
                             replace=False))
    new = _mix_rows(rng, sel.size, kw, nbits)
    a2 = a.copy()
    a2[sel] = new
    c2 = C.patch_blocks(c, sel, new)
    np.testing.assert_array_equal(C.decompress_blocks(c2), a2)
    fresh = C.compress_blocks(a2, nbits=nbits)
    assert int(c2.n_mixed) == int(fresh.n_mixed)
    np.testing.assert_array_equal(np.asarray(c2.states),
                                  np.asarray(fresh.states))


# --------------------------------------------------- sparse closure paths
def _closure_base(g, eng):
    _, _, disc = tdr_build.dfs_intervals(g)
    return eng.propagate(jnp.asarray(tdr_build._vertex_bit_words(CFG,
                                                                 disc)))


def test_blocksparse_closure_bit_identical_pallas():
    """Explicit sparse=True on the pallas backend runs the block-sparse
    kernel (the counter bumps at trace time, so it is asserted once over
    the session-unique shapes) and matches the dense fixpoint exactly;
    the default policy under interpret routes dense and leaves it cold."""
    n0 = ops.KERNEL_INVOCATIONS["block_sparse_matmul"]
    for kind in ("er", "pa"):
        g = G.random_graph(kind, 96, 3.0, 8, seed=3)
        eng = engine.make_engine(g, backend="pallas")
        base = _closure_base(g, eng)
        r_dense, _ = eng.closure(base, sparse=False)
        r_sparse, _ = eng.closure(base, sparse=True)
        np.testing.assert_array_equal(np.asarray(r_sparse),
                                      np.asarray(r_dense), err_msg=kind)
        n1 = ops.KERNEL_INVOCATIONS["block_sparse_matmul"]
        assert n1 > n0, "sparse closure never traced the sparse kernel"
        r_def, _ = eng.closure(base)
        np.testing.assert_array_equal(np.asarray(r_def),
                                      np.asarray(r_dense), err_msg=kind)
        if eng.interpret:
            # default policy routes interpret-mode closures dense: no
            # new sparse-kernel trace may appear
            assert ops.KERNEL_INVOCATIONS["block_sparse_matmul"] == n1


def _lane_brute(a_bits, x, op, cap):
    """``(+)_j a[i, j] (x) x[j]`` by brute force (numpy)."""
    sel = a_bits[:, :, None]
    if op == "or":
        return np.bitwise_or.reduce(np.where(sel, x[None], 0), axis=1)
    if op == "min":
        ident = np.iinfo(x.dtype).max
        return np.where(sel, x[None], ident).min(axis=1).astype(x.dtype)
    tot = np.where(sel, x[None].astype(np.uint64), 0).sum(axis=1)
    return np.minimum(tot, cap).astype(x.dtype)


@pytest.mark.parametrize("op,dtype", [("or", np.uint32), ("min", np.uint16),
                                      ("min", np.uint32), ("sum", np.uint32)])
@pytest.mark.parametrize("chunk", [4, 8192], ids=["chunked", "one-call"])
def test_block_sparse_kernel_matches_brute_force(op, dtype, chunk,
                                                 monkeypatch):
    """The interpret-mode block-sparse kernel over its entry list equals
    the jnp oracle and a brute force, on ZERO / ONE / MIXED blocks with
    row and column tails and a dead X k-block, both in one call and with
    the entry list cut into chunks of 4 (strips straddle the cuts)."""
    from repro.kernels import block_sparse

    rng = np.random.default_rng(11)
    m, kw, nbits, br = 45, 3, 90, 8
    masks = C._valid_masks(kw, nbits)
    a = rng.integers(0, 2 ** 32, size=(m, kw), dtype=np.uint32)
    kind = rng.integers(0, 3, size=(-(-m // br), kw))   # 0 zero, 1 one
    for bi in range(kind.shape[0]):
        rows = slice(bi * br, (bi + 1) * br)
        for bj in range(kw):
            a[rows, bj] = {0: 0, 1: masks[bj]}.get(kind[bi, bj],
                                                  a[rows, bj])
    a &= masks[None, :]
    c = C.compress_blocks(a, br=br, bw=1, nbits=nbits)
    assert {0, 1, 2} <= set(np.asarray(c.states).ravel().tolist())
    cap = 1000
    hi = 16 if op == "sum" else cap         # sums both under and at cap
    x = rng.integers(0, hi, size=(nbits, 5)).astype(dtype)
    ident = np.iinfo(dtype).max if op == "min" else 0
    x[32:64] = ident                                    # dead k-block 1
    a_bits = np.unpackbits(a.view(np.uint8), axis=1,
                           bitorder="little").astype(bool)[:, :nbits]
    want = _lane_brute(a_bits, x, op, cap)
    monkeypatch.setattr(block_sparse, "CHUNK", chunk)
    got = block_sparse.block_sparse_lane_matmul(
        c, jnp.asarray(x), op=op, cap=cap, interpret=True)
    ref = block_sparse.block_sparse_lane_matmul_ref(c, jnp.asarray(x),
                                                    op=op, cap=cap)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(ref), want)


@pytest.mark.parametrize("kind", ["er", "pa"])
def test_segment_sparse_closure_bit_identical(kind):
    """The two-stage frontier-compacted segment closure (dense jitted
    rounds, then compacted sparse tail) == the plain dense fixpoint."""
    for seed in range(4):
        g = G.random_graph(kind, 120, 2.5, 6, seed=seed)
        eng = engine.make_engine(g, backend="segment")
        base = _closure_base(g, eng)
        r_dense, _ = eng.closure(base, sparse=False)
        r_sparse, _ = eng.closure(base, sparse=True)
        np.testing.assert_array_equal(
            np.asarray(r_sparse), np.asarray(r_dense),
            err_msg=f"{kind} seed={seed}")


def test_saturated_closure_rows_all_one():
    """With more vertices than Bloom bits, dense-graph closure rows
    saturate; the level-1 summary must flag exactly those rows."""
    g = G.random_graph("er", 80, 8.0, 4, seed=0)
    idx = tdr_build.build_index(g, CFG, backend="segment")
    flags = idx.summary_flags()
    n_out = np.asarray(idx.n_out)
    masks = C._valid_masks(n_out.shape[-1], CFG.vtx_bits)
    want = (n_out == masks[None, :]).all(axis=1)
    np.testing.assert_array_equal(flags["sat_out"], want)
    assert want.any(), "no saturated row — graph too sparse for the test"


# ---------------------------------------- cache carry across update chains
N_TRIALS = {"segment": 70, "pallas": 40}


@pytest.mark.parametrize("backend", ["segment", "pallas"])
def test_compressed_cache_tracks_update_interleavings(backend):
    """Seed the compressed-plane cache, then chain random update steps:
    after every ``update_index`` the carried cache must decompress
    bit-identically to — and be in the same canonical form as — a fresh
    compression of every dense plane."""
    for trial in range(N_TRIALS[backend]):
        rng = np.random.default_rng(7000 + trial)
        g = G.random_graph(["er", "pa"][trial % 2], N_V, 2.0, N_L,
                           seed=trial)
        cur = tdr_build.build_index(g, CFG, backend=backend)
        cur.compressed_planes()       # seed the cache so updates carry it
        curg = g
        for _ in range(int(rng.integers(1, 4))):
            add, rem = _random_step(rng, curg)
            delta = curg.apply_updates(add, rem)
            cur = tdr_build.update_index(cur, delta, backend=backend,
                                         rebuild_threshold=2.0)
            curg = delta.graph
            comp = cur.compressed_planes()
            for name, (arr, nbits) in cur.plane_specs().items():
                dense = np.asarray(arr)
                np.testing.assert_array_equal(
                    comp[name].decompress(), dense,
                    err_msg=f"{backend} trial={trial} plane={name}")
                assert comp[name].same_as(C.compress(dense, nbits=nbits)), \
                    f"{backend} trial={trial} plane={name}: non-canonical"
