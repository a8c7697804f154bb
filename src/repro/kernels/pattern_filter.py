"""Fused phase-1 filter-cascade Pallas kernel.

Evaluates the per-(job, way) group-pruning predicate of ``tdr_query`` in a
single VPU pass over packed words — the query-side hot loop when millions of
PCR queries are screened per second:

    way_ok[j,g] =   (vbits[j] ⊆ H_vtx[j,g])            # target containment
                  ∧ (req[j]   ⊆ H_lab[j,g])            # required labels
                  ∧ ¬ ∃ℓ<k: blocked(j,g,ℓ) ∧ ¬reached_before(j,g,ℓ)

    blocked(j,g,ℓ)  = (V_lab[j,g,ℓ] ∧ ¬forb[j] ∧ ¬NULL) = ∅
    reached(j,g,ℓ)  =  vbits[j] ⊆ V_vtx[j,g,ℓ]

Inputs arrive pre-gathered per job (the ``u``-row gather is a plain XLA op
outside the kernel), so the kernel is a pure streaming elementwise pass:
bytes dominate, arithmetic intensity ≈ 1 op/byte — firmly memory-bound,
which is why fusing the whole cascade into one pass (instead of 5 separate
XLA reductions) is the win.

Layout: the job axis is the lane axis.  The wrapper flattens every operand
to ``[rows, J]`` (rows = (way, level, word) in row-major order), so each
word of each way is one lane-dense row and every reduction over words,
ways or levels is a static unroll of row-wise ANDs/ORs — no lane
reductions, no cumulative scans, nothing Mosaic cannot lower.  The result
is an int32 ``[G, J]`` 0/1 plane (Mosaic stores no bool blocks), turned
back into ``bool [J, G]`` outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(hv_ref, hl_ref, vv_ref, vl_ref, vb_ref, rq_ref, fb_ref, o_ref,
            *, g: int, k: int, wv: int, wl: int):
    def row(ref, r):
        return ref[r:r + 1, :]                                  # [1, TJ]

    def contains(ref, base, sub_ref, n):
        ok = None
        for w in range(n):
            s = row(sub_ref, w)
            c = (row(ref, base + w) & s) == s
            ok = c if ok is None else ok & c
        return ok

    def blocked(base):
        ok = None
        for w in range(wl):
            c = (row(vl_ref, base + w) & ~row(fb_ref, w)) == 0
            ok = c if ok is None else ok & c
        return ok

    for gi in range(g):
        ok = (contains(hv_ref, gi * wv, vb_ref, wv)
              & contains(hl_ref, gi * wl, rq_ref, wl))
        # static prefix-OR over the k levels: level l refutes the way only
        # if the target was not already reached at some level l' < l
        seen = None
        for l in range(k):
            lvl = gi * k + l
            blk = blocked(lvl * wl)
            ok = ok & ~(blk if seen is None else blk & ~seen)
            reached = contains(vv_ref, lvl * wv, vb_ref, wv)
            seen = reached if seen is None else seen | reached
        o_ref[gi:gi + 1, :] = jnp.where(ok, jnp.int32(1), jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("tj", "interpret"))
def way_filter(h_vtx: jax.Array, h_lab: jax.Array, v_vtx: jax.Array,
               v_lab: jax.Array, vbits: jax.Array, req: jax.Array,
               forb: jax.Array, null_plane: jax.Array, *, tj: int = 256,
               interpret: bool = False) -> jax.Array:
    """Fused way-viability predicate -> bool [J, G].

    All inputs packed uint32, already gathered per job:
      h_vtx [J,G,Wv] h_lab [J,G,Wl] v_vtx [J,G,k,Wv] v_lab [J,G,k,Wl]
      vbits [J,Wv] req/forb [J,Wl] null_plane [Wl]
    ``tj`` is the job (lane) tile: a multiple of 128, or at least J.
    """
    j, g, wv = h_vtx.shape
    k = v_vtx.shape[2]
    wl = h_lab.shape[-1]
    tj = j if j <= tj else tj
    j_pad = -(-j // tj) * tj

    def lanes(x):   # [J, ...] -> int32 [rows, J_pad], the job axis on lanes
        x = jax.lax.bitcast_convert_type(x.reshape(j, -1), jnp.int32)
        return jnp.pad(x.T, ((0, 0), (0, j_pad - j)))

    # NULL is masked exactly like a forbidden label, so fold it in here
    args = [lanes(h_vtx), lanes(h_lab), lanes(v_vtx), lanes(v_lab),
            lanes(vbits), lanes(req), lanes(forb | null_plane[None, :])]
    out = pl.pallas_call(
        functools.partial(_kernel, g=g, k=k, wv=wv, wl=wl),
        grid=(j_pad // tj,),
        in_specs=[pl.BlockSpec((a.shape[0], tj), lambda i: (0, i))
                  for a in args],
        out_specs=pl.BlockSpec((g, tj), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((g, j_pad), jnp.int32),
        interpret=interpret,
    )(*args)
    return out[:, :j].T != 0
