"""Population-count reduction Pallas kernel.

Used for way-saturation diagnostics (how full each Bloom way is — drives the
``succ_per_way`` rebalancing heuristic) and for index-size accounting.  A
pure streaming reduce: SWAR popcount per word, sum over the trailing word
axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, o_ref):
    x = x_ref[...]
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    x = (x * jnp.uint32(0x01010101)) >> 24
    o_ref[...] = x.astype(jnp.int32).sum(axis=-1)


@functools.partial(jax.jit, static_argnames=("tr", "interpret"))
def popcount_rows(words: jax.Array, *, tr: int = 1024,
                  interpret: bool = False) -> jax.Array:
    """Popcount over the trailing axis of uint32 [N, W] -> int32 [N]."""
    n, w = words.shape
    tr = max(1, min(tr, n))
    n_pad = -(-n // tr) * tr
    x = jnp.pad(words, ((0, n_pad - n), (0, 0)))
    out = pl.pallas_call(
        _kernel,
        grid=(n_pad // tr,),
        in_specs=[pl.BlockSpec((tr, w), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tr,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.int32),
        interpret=interpret,
    )(x)
    return out[:n]
