"""Two-level compressed bit-plane layout (§IV block decomposition).

The dense index planes are packed uint32 ``[..., W]`` arrays whose words
are overwhelmingly uniform: exactly-ℓ-hop level sets and empty ways leave
long all-zero runs, and converged closures over a graph with a giant
component leave all-one runs (measured on the ER/PA smoke graphs: ~60% of
words all-zero, ~20% all-one).  This module stores such planes in a
hierarchical two-level form:

* **Level 1 — row summary.**  One 2-bit state per row-block:
  ``ALL_ZERO`` / ``ALL_ONE`` / ``MIXED``.  Uniform rows (an empty way, a
  saturated closure row) cost 2 bits total; the query filter cascade and
  the phase-2 corridor probe read this level directly (a saturated
  ``n_out``/``n_in`` row answers containment without touching words).
* **Level 2 — word detail.**  For MIXED rows only, one 2-bit state per
  word-block, again ZERO/ONE/MIXED.
* **Pool.**  The MIXED detail words, compacted row-major.  Everything
  else (mixed-row ids, pool offsets) is derivable by prefix sums and is
  cached but not counted in ``nbytes``.

Row-blocks are a single row and word-blocks a single word by default: a
geometry sweep on the smoke indexes showed multi-row blocks dilute the
uniform runs (4.0x -> 1.3x as rows-per-block grows from 1 to 8), while
the two-level row/word split beats a flat per-word summary (4.5x vs 4.0x
on ER, 5.0x vs 4.2x on PA).

``BlockCompressed`` is the *device-facing* sibling used by the engine's
block-sparse fixpoint: a ``(row-block × word-block)`` state grid over the
packed adjacency plus a compacted pool of MIXED detail blocks, shaped for
``repro.kernels.block_sparse`` (ZERO blocks are skipped, ONE blocks
short-circuit to a column-OR, MIXED blocks are gathered from the pool).

All states are monotone under OR-semiring growth: ZERO -> MIXED -> ONE
(promotion only); demotion happens only through ``patch_rows`` when an
update rewrites a row outright.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from . import bitset
from .graph import pad_bucket

WORD = 32
ALL_ZERO, ALL_ONE, MIXED = 0, 1, 2
_FULL = np.uint32(0xFFFFFFFF)


def _valid_masks(w: int, nbits: int | None) -> np.ndarray:
    """Per-word valid-bit mask uint32 [w] (tail word may be partial)."""
    nbits = w * WORD if nbits is None else int(nbits)
    bits = np.minimum(np.maximum(nbits - WORD * np.arange(w), 0), WORD)
    return ((np.uint64(1) << bits.astype(np.uint64)) - 1).astype(np.uint32)


def _row_word_states(rows: np.ndarray, masks: np.ndarray):
    """(row_states uint8 [R], word_states uint8 [R, W]) of a dense plane."""
    zero = rows == 0
    ones = (rows == masks[None, :]) & (masks[None, :] != 0)
    wstates = np.where(zero, ALL_ZERO,
                       np.where(ones, ALL_ONE, MIXED)).astype(np.uint8)
    rstates = np.full(rows.shape[0], MIXED, dtype=np.uint8)
    rstates[zero.all(axis=1)] = ALL_ZERO
    rstates[ones.all(axis=1)] = ALL_ONE
    return rstates, wstates


@dataclasses.dataclass(frozen=True)
class CompressedPlanes:
    """Two-level compressed form of one packed plane (host-resident).

    ``decompress()`` is bit-identical to the dense plane it was built
    from; ``nbytes`` counts the canonical storage only (2-bit packed
    states + pool words) — the unpacked state views and prefix offsets
    are derivable caches.
    """
    shape: tuple                 # original plane shape (..., W)
    nbits: int                   # valid bits per row (tail words partial)
    row_states: np.ndarray       # uint8 [R]         (level 1)
    mix_rows: np.ndarray         # int64 [MR]        rows with state MIXED
    word_states: np.ndarray      # uint8 [MR, W]     (level 2, mixed rows)
    pool: np.ndarray             # uint32 [NW]       mixed words, row-major
    pool_off: np.ndarray         # int64 [MR + 1]    prefix into ``pool``

    # ------------------------------------------------------------- sizes
    @property
    def n_rows(self) -> int:
        return int(self.row_states.shape[0])

    @property
    def n_words(self) -> int:
        return int(self.shape[-1])

    @property
    def dense_nbytes(self) -> int:
        return self.n_rows * self.n_words * 4

    @property
    def nbytes(self) -> int:
        states = -(-self.n_rows // 4) - (-self.word_states.size // 4)
        return states + self.pool.size * 4

    @property
    def ratio(self) -> float:
        return self.dense_nbytes / max(self.nbytes, 1)

    # ------------------------------------------------------------ codecs
    def decompress(self) -> np.ndarray:
        masks = _valid_masks(self.n_words, self.nbits)
        out = np.zeros((self.n_rows, self.n_words), dtype=np.uint32)
        out[self.row_states == ALL_ONE] = masks[None, :]
        mixed = self.word_states == MIXED
        rows = np.where(self.word_states == ALL_ONE,
                        masks[None, :], np.uint32(0))
        rows[mixed] = self.pool
        out[self.mix_rows] = rows
        return out.reshape(self.shape)

    def same_as(self, other: "CompressedPlanes") -> bool:
        return (self.shape == other.shape and self.nbits == other.nbits
                and np.array_equal(self.row_states, other.row_states)
                and np.array_equal(self.word_states, other.word_states)
                and np.array_equal(self.pool, other.pool))

    # ----------------------------------------------------------- updates
    def patch_rows(self, rows: np.ndarray,
                   new_rows: np.ndarray) -> "CompressedPlanes":
        """Re-summarize ``rows`` from their new dense words; every other
        row's states and pool segment are carried over untouched, so an
        update's cost is O(|patch| + pool) with no full decompress."""
        rows = np.asarray(rows, dtype=np.int64).reshape(-1)
        if rows.size == 0:
            return self
        new_rows = np.asarray(new_rows, dtype=np.uint32)
        new_rows = new_rows.reshape(rows.size, self.n_words)
        masks = _valid_masks(self.n_words, self.nbits)
        r_new, w_new = _row_word_states(new_rows, masks)

        row_states = self.row_states.copy()
        row_states[rows] = r_new

        patched = np.zeros(self.n_rows, dtype=bool)
        patched[rows] = True
        keep = ~patched[self.mix_rows]
        pool_row = np.repeat(self.mix_rows,
                             np.diff(self.pool_off))        # [NW]
        pool_keep = keep[np.searchsorted(self.mix_rows, pool_row)]

        add = r_new == MIXED
        mix_ids = np.concatenate([self.mix_rows[keep], rows[add]])
        order = np.argsort(mix_ids, kind="stable")
        wstack = np.concatenate([self.word_states[keep], w_new[add]])
        pool_ids = np.concatenate(
            [pool_row[pool_keep],
             np.repeat(rows[add], (w_new[add] == MIXED).sum(axis=1))])
        pool_vals = np.concatenate(
            [self.pool[pool_keep], new_rows[add][w_new[add] == MIXED]])
        pool_order = np.argsort(pool_ids, kind="stable")
        wstates = wstack[order]
        counts = (wstates == MIXED).sum(axis=1, dtype=np.int64)
        return CompressedPlanes(
            shape=self.shape, nbits=self.nbits, row_states=row_states,
            mix_rows=mix_ids[order], word_states=wstates,
            pool=pool_vals[pool_order],
            pool_off=np.concatenate([[0], np.cumsum(counts)]))


def compress(plane, *, nbits: int | None = None) -> CompressedPlanes:
    """Compress a packed uint32 plane ``[..., W]`` (any leading dims)."""
    dense = np.asarray(plane, dtype=np.uint32)
    shape = dense.shape
    w = shape[-1] if dense.ndim else 1
    rows = dense.reshape(-1, w)
    nbits = w * WORD if nbits is None else int(nbits)
    masks = _valid_masks(w, nbits)
    rstates, wstates = _row_word_states(rows, masks)
    mix_rows = np.flatnonzero(rstates == MIXED).astype(np.int64)
    wstates = wstates[mix_rows]
    mixed = wstates == MIXED
    counts = mixed.sum(axis=1, dtype=np.int64)
    return CompressedPlanes(
        shape=shape, nbits=nbits, row_states=rstates, mix_rows=mix_rows,
        word_states=wstates, pool=rows[mix_rows][mixed],
        pool_off=np.concatenate([[0], np.cumsum(counts)]))


# ---------------------------------------------------- device block operand
@dataclasses.dataclass(frozen=True)
class BlockCompressed:
    """Block-state form of a packed bit-matrix for the block-sparse
    fixpoint kernel: states over ``(br rows × bw words)`` blocks plus a
    compacted pool of the MIXED blocks (bucket-padded so one closure's
    jit signature is stable).  Fields are jax arrays, ready to feed
    ``repro.kernels.block_sparse`` / its jnp oracle.

    The kernel walks the *entry list* ``ent_*``: one entry per non-ZERO
    block (plus one ZERO entry for each all-ZERO row strip, so every
    output strip is initialized), sorted by (row-block, word-block) and
    bucket-padded with inert ZERO entries on the last strip.  It is
    E-proportional, unlike the ``[MB, KB]`` state grid, so it is what the
    kernel prefetches into SMEM."""
    shape: tuple                 # dense packed shape (M, Kw)
    nbits: int                   # valid columns (K bits)
    br: int
    bw: int
    states: object               # uint8 [MB, KB]
    slots: object                # int32 [MB, KB] pool slot (0 if uniform)
    pool: object                 # uint32 [P, br, bw] compacted MIXED blocks
    mix_bi: object               # int32 [P] row-block of pool slot
    mix_bj: object               # int32 [P] word-block of pool slot
    n_mixed: int                 # live pool slots (<= P, rest padding)
    ent_row: object              # int32 [N] row-block of entry
    ent_col: object              # int32 [N] word-block of entry
    ent_meta: object             # int32 [N] slot << 3 | first << 2 | state

    @property
    def grid(self) -> tuple:
        return self.states.shape

    @property
    def nbytes(self) -> int:
        mb, kb = self.states.shape
        return (-(-mb * kb // 4) + int(self.n_mixed) * self.br * self.bw * 4
                + 12 * int(np.shape(self.ent_row)[0]))

    @property
    def dense_nbytes(self) -> int:
        return int(self.shape[0] * self.shape[1] * 4)


def _entries(states: np.ndarray, slots: np.ndarray) -> dict:
    """The kernel's entry list (see ``BlockCompressed``) from the state
    grid: ``first`` marks the entry that initializes its row strip."""
    import jax.numpy as jnp

    nz = states != ALL_ZERO
    nz[~nz.any(axis=1), 0] = True        # a ZERO entry for empty strips
    bi, bj = np.nonzero(nz)              # row-major: sorted by (bi, bj)
    st = states[bi, bj].astype(np.int32)
    slot = np.where(st == MIXED, slots[bi, bj], 0).astype(np.int32)
    first = np.ones(bi.size, dtype=np.int32)
    first[1:] = bi[1:] != bi[:-1]
    n = bi.size
    p = pad_bucket(n, lo=8)
    row = np.full(p, bi[-1], dtype=np.int32)
    row[:n] = bi
    col = np.zeros(p, dtype=np.int32)
    col[:n] = bj
    meta = np.zeros(p, dtype=np.int32)   # padding: ZERO, not first
    meta[:n] = (slot << 3) | (first << 2) | st
    return dict(ent_row=jnp.asarray(row), ent_col=jnp.asarray(col),
                ent_meta=jnp.asarray(meta))


def compress_blocks(a_packed: np.ndarray, *, br: int = 8, bw: int = 1,
                    nbits: int | None = None) -> BlockCompressed:
    """Build the block-state operand from a dense packed bit-matrix.

    Blocks straddling the row or valid-column tail never classify
    ``ALL_ONE`` (the padding is zero and the tail mask partial), so the
    ONE short-circuit stays exact without per-block tail handling.
    """
    import jax.numpy as jnp

    a = np.asarray(a_packed, dtype=np.uint32)
    m, kw = a.shape
    nbits = kw * WORD if nbits is None else int(nbits)
    mb, kb = -(-m // br), -(-kw // bw)
    pad = np.zeros((mb * br, kb * bw), dtype=np.uint32)
    pad[:m, :kw] = a
    blocks = (pad.reshape(mb, br, kb, bw).transpose(0, 2, 1, 3)
              .reshape(mb, kb, br, bw))
    full = np.zeros((mb * br, kb * bw), dtype=np.uint32)
    full[:m, :kw] = _valid_masks(kw, nbits)[None, :]
    full = (full.reshape(mb, br, kb, bw).transpose(0, 2, 1, 3)
            .reshape(mb, kb, br, bw))
    zero = (blocks == 0).all(axis=(2, 3))
    ones = ((blocks == full).all(axis=(2, 3))
            & (full != 0).all(axis=(2, 3)))
    states = np.where(zero, ALL_ZERO,
                      np.where(ones, ALL_ONE, MIXED)).astype(np.uint8)
    bi, bj = np.nonzero(states == MIXED)
    n_mixed = bi.size
    p = max(pad_bucket(max(n_mixed, 1), lo=8), 1)
    pool = np.zeros((p, br, bw), dtype=np.uint32)
    pool[:n_mixed] = blocks[bi, bj]
    slots = np.zeros((mb, kb), dtype=np.int32)
    slots[bi, bj] = np.arange(n_mixed, dtype=np.int32)
    pad_i = np.full(p - n_mixed, mb, dtype=np.int32)   # OOB segment sentinel
    return BlockCompressed(
        shape=(m, kw), nbits=nbits, br=br, bw=bw,
        states=jnp.asarray(states), slots=jnp.asarray(slots),
        pool=jnp.asarray(pool),
        mix_bi=jnp.asarray(np.concatenate([bi.astype(np.int32), pad_i])),
        mix_bj=jnp.asarray(np.concatenate([bj.astype(np.int32),
                                           np.zeros(p - n_mixed,
                                                    np.int32)])),
        n_mixed=n_mixed, **_entries(states, slots))


def _bc_flatten(c: BlockCompressed):
    # n_mixed travels as a () int32 leaf, NOT static aux: its value changes
    # under updates, and only shapes/dtypes may key the jit cache — a
    # same-bucket pool must hit the already-compiled fixpoint.
    return ((c.states, c.slots, c.pool, c.mix_bi, c.mix_bj,
             np.int32(c.n_mixed), c.ent_row, c.ent_col, c.ent_meta),
            (c.shape, c.nbits, c.br, c.bw))


def _bc_unflatten(aux, children) -> BlockCompressed:
    shape, nbits, br, bw = aux
    (states, slots, pool, mix_bi, mix_bj, n_mixed, ent_row, ent_col,
     ent_meta) = children
    return BlockCompressed(shape=shape, nbits=nbits, br=br, bw=bw,
                           states=states, slots=slots, pool=pool,
                           mix_bi=mix_bi, mix_bj=mix_bj, n_mixed=n_mixed,
                           ent_row=ent_row, ent_col=ent_col,
                           ent_meta=ent_meta)


# Pytree registration lets jitted fixpoints close over the block operand
# directly; the geometry fields are static aux data, so a re-bucketed pool
# (different P) is a fresh jit signature while same-shape updates hit the
# compiled closure.
jax.tree_util.register_pytree_node(BlockCompressed, _bc_flatten,
                                   _bc_unflatten)


def patch_blocks(comp: BlockCompressed, rows: np.ndarray,
                 row_words: np.ndarray) -> BlockCompressed:
    """Re-summarize only the row-block strips touched by ``rows`` (new
    dense words ``row_words`` uint32 [len(rows), Kw]); untouched strips
    keep their states, and the pool is re-compacted host-side in O(P)."""
    import jax.numpy as jnp

    rows = np.asarray(rows, dtype=np.int64).reshape(-1)
    if rows.size == 0:
        return comp
    m, kw = comp.shape
    br, bw = comp.br, comp.bw
    mb, kb = comp.grid
    states = np.asarray(comp.states).copy()
    slots_old = np.asarray(comp.slots)
    pool_old = np.asarray(comp.pool)

    bi_aff = np.unique(rows // br)
    # materialize the affected strips from the old block form
    strip = np.zeros((bi_aff.size, br, kb * bw), dtype=np.uint32)
    full_row = np.zeros(kb * bw, dtype=np.uint32)
    full_row[:kw] = _valid_masks(kw, comp.nbits)
    for s, bi in enumerate(bi_aff):
        for bj in np.flatnonzero(states[bi] != ALL_ZERO):
            blk = (full_row[None, bj * bw:(bj + 1) * bw].repeat(br, axis=0)
                   if states[bi, bj] == ALL_ONE
                   else pool_old[slots_old[bi, bj]])
            strip[s, :, bj * bw:(bj + 1) * bw] = blk
    # zero rows beyond M in the last strip stay zero; scatter the patch
    strip_rows = strip.reshape(bi_aff.size * br, kb * bw)
    local = np.searchsorted(bi_aff, rows // br) * br + rows % br
    strip_rows[local, :kw] = np.asarray(row_words, dtype=np.uint32)
    strip_rows[:, kw:] = 0

    blocks = (strip_rows.reshape(bi_aff.size, br, kb, bw)
              .transpose(0, 2, 1, 3))
    fullb = np.zeros((bi_aff.size * br, kb * bw), dtype=np.uint32)
    valid = (bi_aff[:, None] * br + np.arange(br)[None, :]).reshape(-1) < m
    fullb[valid] = full_row
    fullb = fullb.reshape(bi_aff.size, br, kb, bw).transpose(0, 2, 1, 3)
    zero = (blocks == 0).all(axis=(2, 3))
    ones = ((blocks == fullb).all(axis=(2, 3))
            & (fullb != 0).all(axis=(2, 3)))
    states[bi_aff] = np.where(zero, ALL_ZERO,
                              np.where(ones, ALL_ONE, MIXED)).astype(np.uint8)

    # re-compact the pool: untouched strips keep their blocks verbatim
    bi, bj = np.nonzero(states == MIXED)
    n_mixed = bi.size
    touched = np.isin(bi, bi_aff)
    vals = np.empty((n_mixed, br, bw), dtype=np.uint32)
    vals[~touched] = pool_old[slots_old[bi[~touched], bj[~touched]]]
    vals[touched] = blocks[np.searchsorted(bi_aff, bi[touched]),
                           bj[touched]]
    p = max(pad_bucket(max(n_mixed, 1), lo=8), 1)
    pool = np.zeros((p, br, bw), dtype=np.uint32)
    pool[:n_mixed] = vals
    slots = np.zeros((mb, kb), dtype=np.int32)
    slots[bi, bj] = np.arange(n_mixed, dtype=np.int32)
    pad_i = np.full(p - n_mixed, mb, dtype=np.int32)
    return BlockCompressed(
        shape=comp.shape, nbits=comp.nbits, br=br, bw=bw,
        states=jnp.asarray(states), slots=jnp.asarray(slots),
        pool=jnp.asarray(pool),
        mix_bi=jnp.asarray(np.concatenate([bi.astype(np.int32), pad_i])),
        mix_bj=jnp.asarray(np.concatenate([bj.astype(np.int32),
                                           np.zeros(p - n_mixed,
                                                    np.int32)])),
        n_mixed=n_mixed, **_entries(states, slots))


def decompress_blocks(comp: BlockCompressed) -> np.ndarray:
    """Dense packed bit-matrix back from the block form (bit-identical)."""
    m, kw = comp.shape
    mb, kb = comp.grid
    states = np.asarray(comp.states)
    slots = np.asarray(comp.slots)
    pool = np.asarray(comp.pool)
    full = np.zeros((mb * comp.br, kb * comp.bw), dtype=np.uint32)
    full[:m, :kw] = _valid_masks(kw, comp.nbits)[None, :]
    full = (full.reshape(mb, comp.br, kb, comp.bw).transpose(0, 2, 1, 3)
            .reshape(mb, kb, comp.br, comp.bw))
    blocks = np.where((states == ALL_ONE)[:, :, None, None], full, 0)
    bi, bj = np.nonzero(states == MIXED)
    blocks = blocks.astype(np.uint32)
    blocks[bi, bj] = pool[slots[bi, bj]]
    dense = (blocks.reshape(mb, kb, comp.br, comp.bw)
             .transpose(0, 2, 1, 3).reshape(mb * comp.br, kb * comp.bw))
    return dense[:m, :kw]
