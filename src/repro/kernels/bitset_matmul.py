"""Semiring bit-matmul Pallas kernel over a packed adjacency bit-matrix.

This is the compute hot-spot of the TPU-adapted TDR engine: one fixpoint
round of the closure build and one round of product-graph frontier expansion
are both

    out[i, w] = (+)_j ( A[i, j]  (x)  X[j, w] )

with ``A`` a packed adjacency bit-matrix (bit j of row i = edge i→j) and
``X`` one carrier lane per element: packed reachability bitsets (32 graph
columns per uint32, (+) = OR) for the boolean carrier, or one semiring lane
(uint8/16/32) for the distance and count carriers ((+) = min or saturating
sum).  The kernel runs on the VPU: each adjacency bit is widened to an
all-ones/all-zeros lane mask that gates one row of ``X`` into the
accumulator — the arithmetic shape of a matmul without an MXU contraction
(OR is not a (+) the MXU supports).  ``repro.kernels.ops`` also exposes an
MXU variant that unpacks to bf16 and thresholds a real matmul — see
ARCHITECTURE.md ("Kernel lowerings") for the roofline comparison.

The index-build closure fixpoint, the ``min``/``sum`` lane carriers and
the RPQ and legacy query executors dispatch here when
``repro.core.engine`` selects the ``pallas`` backend (interpret mode
off-TPU); see ARCHITECTURE.md for the layering.  The boolean phase-2
class expansion of the PCR executor does not: its operand is each label
class's *edge list*, and ``lane_matmul_edges`` (end of this module)
expands it in work that grows with the class's edges, where the dense
kernel gates all V² adjacency bits whatever the density.  The dense cap
(``EngineConfig.max_dense_bytes``) still decides which chunks take the
Pallas path at all.

Tiling: grid (M/TI, W/TW, Kw/TKW); the word axis is innermost
("arbitrary") so the output tile stays resident in VMEM while adjacency and
carrier tiles stream through.  Every block keeps TPU-legal trailing dims:
the adjacency block is (TI, TKW) words with TKW = 128 lanes (the word axis
is zero-padded to a multiple of it), the carrier block (TKW·32, TW) and
the output block (TI, TW) with TW = 128 lanes (or the whole lane axis).
VMEM per step at the defaults: 64 KiB of adjacency + 2 MiB of carrier
rows (lane-padded) + the output tile, double-buffered — well under the
16 MiB scoped VMEM of a v5e core.

Inside a step, a ``fori_loop`` walks the block's real (unpadded) adjacency
words: a dynamic lane rotation brings word ``wk`` of every row to lane 0,
and its 32 bits gate the 32 carrier rows ``wk·32 .. wk·32+31`` (one
aligned dynamic sublane slice).  All arithmetic is int32 (Mosaic has no
unsigned min/reduction): lanes enter as the int32 bit pattern of their
uint32 value, with the sign bit flipped for ``min`` so signed order
equals unsigned order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WORD = 32
LANES = 128
_SIGN = 0x80000000


def to_keys(x: jax.Array, op: str) -> jax.Array:
    """Carrier lanes -> the int32 keys the kernels compute on."""
    u = x.astype(jnp.uint32)
    if op == "min":
        u = u ^ jnp.uint32(_SIGN)
    return jax.lax.bitcast_convert_type(u, jnp.int32)


def from_keys(k: jax.Array, op: str, dtype) -> jax.Array:
    """Inverse of ``to_keys`` (back to the carrier dtype)."""
    u = jax.lax.bitcast_convert_type(k, jnp.uint32)
    if op == "min":
        u = u ^ jnp.uint32(_SIGN)
    return u.astype(dtype)


def key_ident(op: str, dtype) -> int:
    """The (+)-identity of ``op`` over ``dtype`` lanes, as an int32 key:
    0 for or/sum, dtype-max (INF) for min."""
    if op != "min":
        return 0
    key = int(jnp.iinfo(dtype).max) ^ _SIGN
    return key - (1 << 32) if key & _SIGN else key


def combine(op: str, cap: int, acc, upd):
    """Fold a partial (+)-reduction into ``acc`` (int32 keys)."""
    if op == "or":
        return acc | upd
    if op == "min":
        return jnp.minimum(acc, upd)
    return jnp.minimum(acc + upd, jnp.int32(cap))


def contract(a: jax.Array, x_ref, acc: jax.Array, n_words, *, op: str,
             cap: int, ident: int) -> jax.Array:
    """``acc (+)= (+)_j a_bit[:, j] (x) x_ref[j, :]`` over the first
    ``n_words`` words of ``a`` ([R, words] int32 adjacency words; the
    rest is zero padding, never visited; ``x_ref`` holds the ``words·32``
    carrier rows).  Shared by the dense and block-sparse kernels."""
    words = a.shape[1]
    ident_v = jnp.int32(ident)

    def word(wk, acc):
        # lane wk of every row -> lane 0 (a static lane slice after a
        # dynamic rotate: Mosaic has no dynamic lane indexing)
        col = a if words == 1 else pltpu.roll(a, (words - wk) % words, 1)
        col = col[:, :1]                                   # [R, 1]
        xw = x_ref[pl.ds(pl.multiple_of(wk * WORD, WORD), WORD), :]
        for b in range(WORD):
            sel = jnp.int32(0) - ((col >> b) & 1)          # 0 / all-ones
            row = xw[b:b + 1, :]                           # [1, TW]
            if op == "or":
                acc = acc | (sel & row)
            elif op == "min":
                acc = jnp.minimum(acc, (row & sel) | (ident_v & ~sel))
            else:
                acc = jnp.minimum(acc + (sel & row), jnp.int32(cap))
        return acc

    return jax.lax.fori_loop(0, n_words, word, acc)


def _kernel(a_ref, x_ref, o_ref, *, kw: int, op: str, cap: int,
            ident: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.full(o_ref.shape, ident, jnp.int32)

    tkw = a_ref.shape[1]
    acc = contract(a_ref[...], x_ref,
                   jnp.full(o_ref.shape, ident, jnp.int32),
                   jnp.minimum(tkw, kw - kk * tkw),
                   op=op, cap=cap, ident=ident)
    o_ref[...] = combine(op, cap, o_ref[...], acc)


@functools.partial(jax.jit,
                   static_argnames=("op", "cap", "ti", "tk", "tw",
                                    "interpret"))
def lane_matmul(a_packed: jax.Array, x: jax.Array, *, op: str,
                cap: int = 0, ti: int = 128, tk: int = LANES * WORD,
                tw: int = LANES, interpret: bool = False) -> jax.Array:
    """``(+)_j (A[i,j] (x) X[j,:])`` — packed-bit adjacency, lane carrier.

    Args:
      a_packed: uint32 [M, K//32] adjacency bit-rows (bit j of row i).
      x:        [K, W] semiring carrier lanes (uint8/uint16/uint32).
      op:       lane combine — "or", "min" (identity dtype-max) or
                "sum" (saturating at ``cap``; lanes must be <= ``cap``).
      ti, tk, tw: row, adjacency-column (bits) and lane tile.  The
                defaults give TPU-legal blocks for every shape; other
                values are for interpret-mode tiling tests.
    Returns:
      [M, W] in ``x.dtype``.  Padding rows of ``a_packed`` have no bits
      set, so pad lanes never leak into real outputs regardless of op.
    """
    assert op in ("or", "min", "sum"), op
    m, kw = a_packed.shape
    k, w = x.shape
    assert kw * WORD == k, (a_packed.shape, x.shape)
    ti = min(ti, m) or 1
    tkw = max(1, tk // WORD)
    tw = min(tw, w) or 1

    m_pad = -(-m // ti) * ti
    kw_pad = -(-kw // tkw) * tkw
    w_pad = -(-w // tw) * tw
    a_p = jnp.pad(jax.lax.bitcast_convert_type(a_packed, jnp.int32),
                  ((0, m_pad - m), (0, kw_pad - kw)))
    x_p = jnp.pad(to_keys(x, op), ((0, (kw_pad - kw) * WORD),
                                   (0, w_pad - w)))
    ident = key_ident(op, x.dtype)

    out = pl.pallas_call(
        functools.partial(_kernel, kw=kw, op=op, cap=cap, ident=ident),
        grid=(m_pad // ti, w_pad // tw, kw_pad // tkw),
        in_specs=[
            pl.BlockSpec((ti, tkw), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((tkw * WORD, tw), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((ti, tw), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, w_pad), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_p, x_p)
    return from_keys(out[:m, :w], op, x.dtype)


def bitset_matmul(a_packed: jax.Array, x: jax.Array, *, ti: int = 128,
                  tk: int = LANES * WORD, tw: int = LANES,
                  interpret: bool = False) -> jax.Array:
    """``OR_j (A[i,j] & X[j,:])`` over packed uint32 operands.

    Args:
      a_packed: uint32 [M, K//32] adjacency bit-rows.
      x:        uint32 [K, W] packed bitsets.
    Returns:
      uint32 [M, W].
    """
    return lane_matmul(a_packed, x, op="or", ti=ti, tk=tk, tw=tw,
                       interpret=interpret)


# ------------------------------------------------- edge-list class expansion
#: edges per grid step of ``lane_matmul_edges``: two int32 SMEM blocks of
#: 32 KiB each (double-buffered: 128 KiB of the 1 MiB SMEM)
EDGE_CHUNK = 8192
#: edges per rolled loop iteration (the scalar loads of one step overlap
#: the vector work of the others)
EDGE_UNROLL = 8


def _edges_kernel(cnt_ref, dst_ref, src_ref, x_ref, o_ref, *, chunk: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.int32)

    # the real edges of this grid step; padding entries are never read
    n = jnp.clip(cnt_ref[0] - i * chunk, 0, chunk)

    def edge(e):
        d = dst_ref[e]
        row = x_ref[pl.ds(src_ref[e], 1), :]
        o_ref[pl.ds(d, 1), :] = o_ref[pl.ds(d, 1), :] | row

    def block(b, carry):
        for u in range(EDGE_UNROLL):
            edge(b * EDGE_UNROLL + u)
        return carry

    def tail(e, carry):
        edge(e)
        return carry

    jax.lax.fori_loop(0, n // EDGE_UNROLL, block, 0)
    jax.lax.fori_loop(n - n % EDGE_UNROLL, n, tail, 0)


@functools.partial(jax.jit, static_argnames=("n_rows", "chunk",
                                             "interpret"))
def lane_matmul_edges(dst: jax.Array, src: jax.Array, count: jax.Array,
                      x: jax.Array, *, n_rows: int, chunk: int = EDGE_CHUNK,
                      interpret: bool = False) -> jax.Array:
    """``out[dst[e], :] |= x[src[e], :]`` over the first ``count`` edges.

    The boolean class expansion of phase 2 from the class's edge list:
    bit-identical to ``lane_matmul(a, x, op="or")`` on the packed matrix
    ``a`` holding the same edges (bit ``src`` of row ``dst``), duplicates
    and self-loops included, in work that grows with ``count`` and not
    with ``n_rows · x.shape[0]``.

    Args:
      dst, src: int32 [E_pad] edge endpoints, ``dst`` < ``n_rows`` and
                ``src`` < ``x.shape[0]`` for the first ``count`` entries;
                the rest is padding, never read.  Sorting by ``dst``
                keeps one output row hot across its run of edges.
      count:    int32 scalar, the number of real edges.
      x:        uint32 [K, W] carrier (packed state words).
      n_rows:   rows of the output.
      chunk:    edges per grid step; the default fits SMEM, smaller
                values are for interpret-mode tests of the grid.
    Returns:
      uint32 [n_rows, W].

    Grid: one step per ``chunk`` edges (``E_pad`` is padded up to a
    multiple of it), the edge blocks in SMEM, the real count
    scalar-prefetched.  The carrier and the output are whole-array VMEM
    blocks resident across the grid, one buffer each: VMEM holds
    ``2 · R · 128⌈W/128⌉ · 4`` bytes (R = rows rounded up to 8), 8 MiB at
    V=8192 and W=32 (4 MiB each, lane-padded).  A step walks its real
    edges with a one-row dynamic sublane load of ``x``, an OR and a
    one-row store into the output row.
    """
    e_pad = dst.shape[0]
    chunk = max(1, min(chunk, e_pad))
    e_full = -(-e_pad // chunk) * chunk
    if e_full > e_pad:
        dst = jnp.pad(dst, (0, e_full - e_pad))
        src = jnp.pad(src, (0, e_full - e_pad))
    k, w = x.shape
    r_pad = -(-max(n_rows, k) // 8) * 8
    x_i = jnp.pad(jax.lax.bitcast_convert_type(x.astype(jnp.uint32),
                                               jnp.int32),
                  ((0, r_pad - k), (0, 0)))
    vmem = 2 * r_pad * (-(-w // LANES) * LANES) * 4
    resident = dict(pipeline_mode=pl.Buffered(1))
    out = pl.pallas_call(
        functools.partial(_edges_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                 # the real edge count
            grid=(e_full // chunk,),
            in_specs=[
                pl.BlockSpec((chunk,), lambda i, c: (i,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((chunk,), lambda i, c: (i,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((r_pad, w), lambda i, c: (0, 0), **resident),
            ],
            out_specs=pl.BlockSpec((r_pad, w), lambda i, c: (0, 0),
                                   **resident)),
        out_shape=jax.ShapeDtypeStruct((r_pad, w), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, vmem + (8 << 20))),
        interpret=interpret,
    )(jnp.reshape(count, (1,)).astype(jnp.int32), dst.astype(jnp.int32),
      src.astype(jnp.int32), x_i)
    return jax.lax.bitcast_convert_type(out[:n_rows], jnp.uint32)
