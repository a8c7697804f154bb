"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

The TPU compiler compiles for a chip that is described, not attached, so
these run anywhere the TPU library is installed and catch what the
interpret-mode tests cannot: block shapes the chip refuses, unsupported
Mosaic ops, SMEM/VMEM overflows.  Widths are those of the one-chip smoke
run (``chip_smoke.py`` leg A: V=8192, 256-bit Blooms, G=4, k=3).

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.core import compressed, distributed, engine, graph as G
from repro.kernels import block_sparse, pattern_filter
from repro.kernels.bitset_matmul import (bitset_matmul, lane_matmul,
                                         lane_matmul_edges)

V = 8192
KW = V // 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("w", [2, 8, 32])
def test_bitset_matmul_compiles(one_chip, w):
    _compile(bitset_matmul,
             jax.ShapeDtypeStruct((V, KW), jnp.uint32, sharding=one_chip),
             jax.ShapeDtypeStruct((V, w), jnp.uint32, sharding=one_chip))


@pytest.mark.parametrize("op,dtype", [("or", jnp.uint32),
                                      ("min", jnp.uint16),
                                      ("sum", jnp.uint32)])
def test_lane_matmul_compiles(one_chip, op, dtype):
    _compile(lambda a, x: lane_matmul(a, x, op=op, cap=(1 << 15) - 1),
             jax.ShapeDtypeStruct((V, KW), jnp.uint32, sharding=one_chip),
             jax.ShapeDtypeStruct((V, 64), dtype, sharding=one_chip))


@pytest.mark.parametrize("e_pad", [4096, 6144, 8192, 12288])
def test_lane_matmul_edges_compiles(one_chip, e_pad):
    """Phase 2's class expansion as served at V=8192: 8 special labels
    plus the neutral class under ``scan``, 32 jobs a chunk; 12288 edges
    take two grid steps."""
    classes, q = 9, 32

    def expand(rows, cols, count, x):
        def body(acc, op):
            return acc | lane_matmul_edges(*op, x, n_rows=V), None
        return jax.lax.scan(body, jnp.zeros_like(x), (rows, cols, count))[0]

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    compiled = _compile(expand, i32(classes, e_pad), i32(classes, e_pad),
                        i32(classes),
                        jax.ShapeDtypeStruct((V, q), jnp.uint32,
                                             sharding=one_chip))
    assert "%lane_matmul_edges" in compiled.as_text()


@pytest.mark.parametrize("op", ["or", "min"])
def test_block_sparse_compiles(one_chip, op):
    g = G.preferential_attachment(V, 4.0, 8, seed=0)
    cfg = engine.EngineConfig()
    comp = compressed.compress_blocks(engine.pack_adjacency_np(g),
                                      br=cfg.block_rows,
                                      bw=cfg.block_words, nbits=V)

    def sds(a):
        return jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                    sharding=one_chip)

    def run(ent_row, ent_col, ent_meta, pool, x):
        c = dataclasses.replace(comp, ent_row=ent_row, ent_col=ent_col,
                                ent_meta=ent_meta, pool=pool)
        if op == "or":
            return block_sparse.block_sparse_matmul(c, x)
        return block_sparse.block_sparse_lane_matmul(c, x, op=op)

    # the entry list spans several SMEM chunks at this size
    assert comp.ent_row.shape[0] > block_sparse.CHUNK
    dtype = jnp.uint32 if op == "or" else jnp.uint16
    _compile(run, sds(comp.ent_row), sds(comp.ent_col), sds(comp.ent_meta),
             sds(comp.pool),
             jax.ShapeDtypeStruct((V, 8), dtype, sharding=one_chip))


def test_way_filter_compiles(one_chip):
    j, g, k, wv, wl = 256, 4, 3, 8, 2

    def u32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)

    _compile(pattern_filter.way_filter, u32(j, g, wv), u32(j, g, wl),
             u32(j, g, k, wv), u32(j, g, k, wl), u32(j, wv), u32(j, wl),
             u32(j, wl), u32(wl))


def test_distributed_closure_compiles_on_four_chips(topo):
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    low = distributed.lower_distributed_closure(mesh, V, e_max=V, nbits=256,
                                                rounds=4)
    assert "all-gather" in low.compile().as_text()
