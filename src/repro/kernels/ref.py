"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bitset

WORD = 32


def bitset_matmul_ref(a_packed: jax.Array, x: jax.Array) -> jax.Array:
    """OR_j (A[i,j] & X[j,:]) — dense oracle via unpack + int matmul."""
    m, kw = a_packed.shape
    k, w = x.shape
    a_bool = bitset.unpack_bits(a_packed, k)                # [M, K]
    x_bits = bitset.unpack_bits(x, w * WORD)                # [K, W*32]
    prod = jnp.dot(a_bool.astype(jnp.int32), x_bits.astype(jnp.int32)) > 0
    return bitset.pack_bits(prod)                           # [M, W]


def lane_matmul_ref(a_packed: jax.Array, x: jax.Array, *, op: str,
                    cap: int = 0) -> jax.Array:
    """``(+)_j (A[i,j] (x) X[j,:])`` over semiring carrier lanes.

    Dense oracle for ``bitset_matmul.lane_matmul``: unpack the adjacency
    bits and reduce along K with the lane combine (OR / min-with-INF /
    saturating sum).  Materializes an [M, K, W] transient — fine at the
    test/smoke scales the oracle runs at, not a production path.
    """
    m, kw = a_packed.shape
    k, w = x.shape
    a_bool = bitset.unpack_bits(a_packed, k)                # [M, K]
    sel = a_bool[:, :, None]                                # [M, K, 1]
    if op == "or":
        vals = jnp.where(sel, x[None], jnp.zeros((), x.dtype))
        return jax.lax.reduce(vals, jnp.zeros((), x.dtype),
                              jnp.bitwise_or, (1,))
    if op == "min":
        inf = jnp.array(jnp.iinfo(x.dtype).max, x.dtype)
        vals = jnp.where(sel, x[None], inf)
        return jnp.min(vals, axis=1)
    assert op == "sum", op
    # inputs are <= cap (the DP clamps every round), so a uint32 accumulator
    # cannot wrap before the clamp: K * cap <= 2^16 * (2^15-1) < 2^32
    vals = jnp.where(sel, x[None].astype(jnp.uint32), jnp.uint32(0))
    return jnp.minimum(jnp.sum(vals, axis=1),
                       jnp.uint32(cap)).astype(x.dtype)


def lane_matmul_edges_ref(dst: jax.Array, src: jax.Array,
                          count: jax.Array, x: jax.Array, *,
                          n_rows: int) -> jax.Array:
    """``out[dst[e], :] |= x[src[e], :]`` over the first ``count`` edges —
    the oracle of ``bitset_matmul.lane_matmul_edges``: a packed
    segment-OR of the gathered rows, padding entries sent to a dropped
    segment."""
    live = jnp.arange(dst.shape[0]) < count
    seg = jnp.where(live, dst, n_rows)
    return bitset.segment_or_words(x[jnp.where(live, src, 0)], seg,
                                   num_segments=n_rows)


def way_filter_ref(h_vtx, h_lab, v_vtx, v_lab, vbits, req, forb, null_plane):
    """Reference way-viability predicate (mirrors tdr_query phase 1)."""
    has_tgt = bitset.words_contain(h_vtx, vbits[:, None, :])
    has_req = bitset.words_contain(h_lab, req[:, None, :])
    real = v_lab & ~forb[:, None, None, :] & ~null_plane[None, None, None, :]
    blocked = jnp.all(real == 0, axis=-1)
    reached = bitset.words_contain(v_vtx, vbits[:, None, None, :])
    reached_upto = jnp.cumsum(reached.astype(jnp.int32), axis=-1) > 0
    not_before = jnp.concatenate(
        [jnp.ones_like(reached_upto[..., :1]), ~reached_upto[..., :-1]],
        axis=-1)
    refuted = jnp.any(blocked & not_before, axis=-1)
    return has_tgt & has_req & ~refuted


def popcount_rows_ref(words: jax.Array) -> jax.Array:
    return bitset.popcount(words)
