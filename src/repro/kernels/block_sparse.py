"""Block-sparse semiring bit-matmul Pallas kernel.

Same contraction as ``bitset_matmul.lane_matmul`` —

    out[i, w] = (+)_j ( A[i, j]  (x)  X[j, w] )

— but ``A`` arrives in the two-level block form of
``repro.core.compressed.BlockCompressed``: a 2-bit state per
``(row-block × word-block)`` tile (ALL_ZERO / ALL_ONE / MIXED) plus a
compacted pool holding only the MIXED detail blocks.  The kernel walks the
operand's *entry list* — one entry per non-ZERO block, sorted by row-block
— over the grid ``(out-lane tile, entry)``, so ZERO blocks cost no grid
step at all, and per entry

* **skips** the step when the corresponding X k-block carries nothing but
  (+)-identities this round (``x_any`` — the per-round frontier summary
  the delta fixpoint recomputes, which is what makes late closure rounds
  nearly free),
* **short-circuits** ALL_ONE blocks to a precomputed per-k-block
  column-(+) of X (``col_or`` — a full block contributes the (+) of its
  columns, no contraction needed),
* **gathers** MIXED blocks from the pool via scalar-prefetched slot ids
  (``pltpu.PrefetchScalarGridSpec``: the slot indirection is resolved in
  SMEM before the block's DMA is issued) and contracts them with the
  same bit-gated VPU accumulation as the dense kernel
  (``bitset_matmul.contract``).

The output strip of an entry stays resident in VMEM while its row's
entries stream through (they are consecutive); the entry flagged
``first`` initializes it.  The entry list is prefetched in chunks of
``CHUNK`` entries, one ``pallas_call`` each, so SMEM holds a bounded
slice whatever the graph: a later chunk takes the running output as an
aliased input and picks up a row strip that straddles the cut from it.
Entries are *inputs*, not statics, so one compiled closure serves every
round of a fixpoint while the frontier summary changes underneath it.
``block_sparse_matmul_ref`` is the pure-jnp oracle (and the segment-family
lowering): identical semantics via a gathered batched unpack-matmul over
pool blocks plus a segment-OR, bit-for-bit equal to the dense
``ref.bitset_matmul_ref``.

Tile notes: the out tile is ``(br, TW)`` and X blocks ``(bw·32, TW)``.
With one-word blocks (``bw = 1``, the engine default: 8 rows × 32
columns) the pool is passed lane-dense as ``[br, P]`` — block
``(br, 128)`` holds 128 pool blocks and a lane rotation brings the wanted
one to lane 0 — since a ``[P, br, 1]`` array would pad every block to a
full (8, 128) tile.  Wider blocks must be multiples of 128 words (the
word walk rotates lanes) and are fetched as ``(1, br, bw)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bitset
from repro.core.compressed import ALL_ONE, MIXED, BlockCompressed
from .bitset_matmul import (LANES, combine, contract, from_keys, key_ident,
                            to_keys)

WORD = 32
# entries prefetched per pallas_call: 3 int32 arrays = 96 KiB of SMEM
CHUNK = 8192


def _kernel(row_ref, col_ref, meta_ref, xany_ref, pool_ref, x_ref,
            colr_ref, *rest, kw: int, bw: int, op: str, cap: int,
            ident: int, carry: bool):
    del row_ref  # consumed by the output BlockSpec's index map
    o_ref = rest[-1]
    n = pl.program_id(1)
    k = col_ref[n]
    meta = meta_ref[n]
    st = meta & 3
    first = (meta >> 2) & 1

    @pl.when(first == 1)
    def _init():
        o_ref[...] = jnp.full(o_ref.shape, ident, jnp.int32)

    if carry:  # a strip cut by the previous chunk resumes from its output
        prev_ref = rest[0]

        @pl.when((n == 0) & (first == 0))
        def _resume():
            o_ref[...] = prev_ref[...]

    live = xany_ref[k] != 0

    @pl.when(live & (st == ALL_ONE))
    def _one():
        o_ref[...] = combine(op, cap, o_ref[...], colr_ref[0])

    @pl.when(live & (st == MIXED))
    def _mixed():
        if bw == 1:
            lane = (meta >> 3) & (LANES - 1)
            a = pltpu.roll(pool_ref[...], (LANES - lane) & (LANES - 1),
                           1)[:, :1]
        else:
            a = pool_ref[0]
        acc = contract(a, x_ref, jnp.full(o_ref.shape, ident, jnp.int32),
                       jnp.minimum(bw, kw - k * bw),
                       op=op, cap=cap, ident=ident)
        o_ref[...] = combine(op, cap, o_ref[...], acc)


@functools.partial(jax.jit,
                   static_argnames=("mb", "br", "bw", "kw", "tw", "op",
                                    "cap", "chunk", "interpret"))
def _block_sparse_call(ent_row, ent_col, ent_meta, xany, pool, x, colr, *,
                       mb: int, br: int, bw: int, kw: int, tw: int,
                       op: str, cap: int, chunk: int, interpret: bool):
    w = x.shape[1]
    bk = bw * WORD
    tw = min(tw, w) or 1
    w_pad = -(-w // tw) * tw
    ident = key_ident(op, x.dtype)
    x_p = jnp.pad(to_keys(x, op), ((0, 0), (0, w_pad - w)),
                  constant_values=ident)
    colr_p = jnp.pad(to_keys(colr, op), ((0, 0), (0, w_pad - w)),
                     constant_values=ident)[:, None, :]    # [KB, 1, W]
    pool_i = jax.lax.bitcast_convert_type(pool, jnp.int32)
    if bw == 1:
        # lane-dense [br, P]: pool block s is lane s % 128 of block s // 128
        pool_i = pool_i[:, :, 0].T
        pool_i = jnp.pad(pool_i, ((0, 0), (0, -pool_i.shape[1] % LANES)))
        pool_spec = pl.BlockSpec(
            (br, LANES), lambda j, n, r, c, m, xa: (0, m[n] >> 10))
    else:
        pool_spec = pl.BlockSpec(
            (1, br, bw), lambda j, n, r, c, m, xa: (m[n] >> 3, 0, 0))

    # pad the entry list to whole chunks with inert entries on the last
    # strip (ZERO, not first)
    n_ent = ent_row.shape[0]
    chunk = min(chunk, n_ent)
    pad = -n_ent % chunk
    ent_row = jnp.pad(ent_row, (0, pad), mode="edge")
    ent_col = jnp.pad(ent_col, (0, pad))
    ent_meta = jnp.pad(ent_meta, (0, pad))

    out_spec = pl.BlockSpec((br, tw), lambda j, n, r, c, m, xa: (r[n], j))
    in_specs = [
        pool_spec,
        pl.BlockSpec((bk, tw), lambda j, n, r, c, m, xa: (c[n], j)),
        pl.BlockSpec((1, 1, tw), lambda j, n, r, c, m, xa: (c[n], 0, j)),
    ]
    out = None
    for c0 in range(0, n_ent + pad, chunk):
        carry = out is not None
        sl = slice(c0, c0 + chunk)
        out = pl.pallas_call(
            functools.partial(_kernel, kw=kw, bw=bw, op=op, cap=cap,
                              ident=ident, carry=carry),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,         # row, col, meta, x_any
                grid=(w_pad // tw, chunk),
                in_specs=in_specs + [out_spec] * carry,
                out_specs=out_spec),
            out_shape=jax.ShapeDtypeStruct((mb * br, w_pad), jnp.int32),
            # the running output is aliased in: strips this chunk never
            # visits keep what earlier chunks wrote
            input_output_aliases={7: 0} if carry else {},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(ent_row[sl], ent_col[sl], ent_meta[sl], xany, pool_i, x_p,
          colr_p, *([out] if carry else []))
    return from_keys(out[:, :w], op, x.dtype)


def _lane_ident(op: str, dt):
    if op == "min":
        return jnp.array(jnp.iinfo(dt).max, dt)
    return jnp.zeros((), dt)


def _pad_k_lanes(x: jax.Array, k_pad: int, op: str) -> jax.Array:
    """K-pad with the (+)-identity so pad rows cannot perturb any op.

    (The bit-selection already masks pad rows out for ZERO/MIXED blocks,
    but an ALL_ONE block spanning the pad region reduces over them.)"""
    if x.shape[0] < k_pad:
        pad = jnp.full((k_pad - x.shape[0],) + x.shape[1:],
                       _lane_ident(op, x.dtype), x.dtype)
        x = jnp.concatenate([x, pad], axis=0)
    return x


def _k_block_lane_summaries(x: jax.Array, kb: int, bk: int, op: str,
                            cap: int):
    """Per-k-block column-(+) and liveness flags of the lane operand."""
    xr = _pad_k_lanes(x, kb * bk, op).reshape(kb, bk, x.shape[1])
    ident = _lane_ident(op, x.dtype)
    if op == "or":
        colr = jax.lax.reduce(xr, jnp.zeros((), x.dtype),
                              jax.lax.bitwise_or, (1,))
    elif op == "min":
        colr = jnp.min(xr, axis=1)
    else:
        colr = jnp.minimum(jnp.sum(xr.astype(jnp.uint32), axis=1),
                           jnp.uint32(cap)).astype(x.dtype)
    xany = jnp.any(xr != ident, axis=(1, 2)).astype(jnp.int32)
    return colr, xany


def block_sparse_lane_matmul(comp: BlockCompressed, x: jax.Array, *,
                             op: str, cap: int = 0, tw: int = LANES,
                             interpret: bool = False) -> jax.Array:
    """``(+)_j (A[i,j] (x) X[j,:])`` with A block-compressed, X in
    semiring carrier lanes.  Identical to ``lane_matmul`` on the
    decompressed adjacency."""
    m, kw = comp.shape
    mb, kb = comp.grid
    bk = comp.bw * WORD
    colr, xany = _k_block_lane_summaries(x, kb, bk, op, cap)
    out = _block_sparse_call(
        comp.ent_row, comp.ent_col, comp.ent_meta, xany, comp.pool,
        _pad_k_lanes(x, kb * bk, op), colr, mb=mb, br=comp.br, bw=comp.bw,
        kw=kw, tw=tw, op=op, cap=cap, chunk=CHUNK, interpret=interpret)
    return out[:m]


def block_sparse_matmul(comp: BlockCompressed, x: jax.Array, *,
                        tw: int = LANES,
                        interpret: bool = False) -> jax.Array:
    """``OR_j (A[i,j] & X[j,:])`` with A in block-compressed form.

    Args:
      comp: block states/slots/pool of the packed A ``[M, K//32]``.
      x:    uint32 ``[V, W]`` packed bitsets, ``V <= K`` (zero-padded).
    Returns:
      uint32 ``[M, W]`` — bit-identical to the dense kernel.
    """
    return block_sparse_lane_matmul(comp, x, op="or", tw=tw,
                                    interpret=interpret)


# ------------------------------------------------------------- jnp oracle
def block_sparse_lane_matmul_ref(comp: BlockCompressed, x: jax.Array, *,
                                 op: str, cap: int = 0) -> jax.Array:
    """Pure-jnp oracle for ``block_sparse_lane_matmul``."""
    m, _ = comp.shape
    mb, kb = comp.grid
    br, bw = comp.br, comp.bw
    bk = bw * WORD
    w = x.shape[1]
    ident = _lane_ident(op, x.dtype)
    xr = _pad_k_lanes(x, kb * bk, op).reshape(kb, bk, w)
    colr, xany = _k_block_lane_summaries(x, kb, bk, op, cap)

    one = (comp.states == ALL_ONE) & (xany != 0)[None, :]
    one_vals = jnp.where(one[:, :, None], colr[None, :, :], ident)
    if op == "or":
        one_c = jax.lax.reduce(one_vals, jnp.zeros((), x.dtype),
                               jax.lax.bitwise_or, (1,))
    elif op == "min":
        one_c = jnp.min(one_vals, axis=1)
    else:
        one_c = jnp.minimum(jnp.sum(one_vals.astype(jnp.uint32), axis=1),
                            jnp.uint32(cap)).astype(x.dtype)

    def blk(a_blk, x_blk):                                # [br,bw],[bk,W]
        a_bool = bitset.unpack_bits(a_blk, bk)[:, :, None]
        if op == "or":
            vals = jnp.where(a_bool, x_blk[None], jnp.zeros((), x.dtype))
            return jax.lax.reduce(vals, jnp.zeros((), x.dtype),
                                  jax.lax.bitwise_or, (1,))
        if op == "min":
            return jnp.min(jnp.where(a_bool, x_blk[None], ident), axis=1)
        vals = jnp.where(a_bool, x_blk[None].astype(jnp.uint32),
                         jnp.uint32(0))
        return jnp.minimum(jnp.sum(vals, axis=1),
                           jnp.uint32(cap)).astype(x.dtype)

    contrib = jax.vmap(blk)(comp.pool, xr[comp.mix_bj])   # [P, br, W]
    flat = contrib.reshape(contrib.shape[0], br * w)
    if op == "or":
        mix = bitset.segment_or_words(flat, comp.mix_bi, num_segments=mb)
    elif op == "min":
        mix = jax.ops.segment_min(flat, comp.mix_bi, num_segments=mb)
    else:
        mix = jnp.minimum(
            jax.ops.segment_sum(flat.astype(jnp.uint32), comp.mix_bi,
                                num_segments=mb),
            jnp.uint32(cap)).astype(x.dtype)
    mix = mix.reshape(mb, br, w)
    if op == "or":
        out = mix | one_c[:, None, :]
    elif op == "min":
        out = jnp.minimum(mix, one_c[:, None, :])
    else:
        out = jnp.minimum(mix.astype(jnp.uint32)
                          + one_c[:, None, :].astype(jnp.uint32),
                          jnp.uint32(cap)).astype(x.dtype)
    return out.reshape(mb * br, w)[:m]


def block_sparse_matmul_ref(comp: BlockCompressed,
                            x: jax.Array) -> jax.Array:
    """Pure-jnp lowering of the same block-sparse contraction (the
    segment-family path): ONE blocks resolve through the k-block
    column-OR, MIXED blocks are gathered from the pool and contracted by
    a vmapped unpack-matmul, then segment-OR'd into their row-blocks."""
    m, _ = comp.shape
    mb, kb = comp.grid
    br, bw = comp.br, comp.bw
    bk = bw * WORD
    w = x.shape[1]
    xr = _pad_k_lanes(x, kb * bk, "or").reshape(kb, bk, w)
    colr, xany = _k_block_lane_summaries(x, kb, bk, "or", 0)

    one = (comp.states == ALL_ONE) & (xany != 0)[None, :]
    one_or = jax.lax.reduce(
        jnp.where(one[:, :, None], colr[None, :, :], jnp.uint32(0)),
        jnp.uint32(0), jax.lax.bitwise_or, (1,))         # [MB, W]

    def blk(a_blk, x_blk):                               # [br,bw],[bk,W]
        a_bool = bitset.unpack_bits(a_blk, bk)
        x_bits = bitset.unpack_bits(x_blk, w * WORD)
        prod = jnp.dot(a_bool.astype(jnp.int32),
                       x_bits.astype(jnp.int32)) > 0
        return bitset.pack_bits(prod)                    # [br, W]

    contrib = jax.vmap(blk)(comp.pool, xr[comp.mix_bj])  # [P, br, W]
    mix_or = bitset.segment_or_words(
        contrib.reshape(contrib.shape[0], br * w), comp.mix_bi,
        num_segments=mb).reshape(mb, br, w)
    out = (mix_or | one_or[:, None, :]).reshape(mb * br, w)
    return out[:m]
