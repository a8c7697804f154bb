"""Public jit'd wrappers for the TDR kernels.

On TPU these lower to the Pallas kernels; on CPU (this container) they run
the kernels in ``interpret=True`` mode, or — for the big batched call sites
where interpret-mode Python execution would dominate — the pure-jnp oracle,
which is numerically identical.  Selection is explicit so tests can force
either path; ``repro.core.engine`` maps its backend choice onto these modes
(the backend-selection contract is documented in ARCHITECTURE.md).

``frontier_step_mxu`` is the beyond-paper MXU lowering of the same semiring
step (unpack → bf16 matmul → threshold → repack): ARCHITECTURE.md ("Kernel
lowerings") compares its roofline against the VPU kernel.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from repro.core import bitset
from . import block_sparse, ref
from .bitset_matmul import bitset_matmul, lane_matmul, lane_matmul_edges
from .pattern_filter import way_filter
from .popcount import popcount_rows

WORD = 32

# Trace-time invocation counter per kernel: incremented whenever a Pallas
# lowering (real or interpret) is routed to, i.e. whenever the kernel ends
# up in the compiled computation.  Tests assert on deltas to prove the
# kernels are load-bearing for a given engine backend.
KERNEL_INVOCATIONS: collections.Counter = collections.Counter()


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def frontier_step(a_packed: jax.Array, x: jax.Array, *,
                  mode: str = "auto",
                  tiles: tuple[int, int, int] | None = None) -> jax.Array:
    """One boolean-semiring expansion round: OR_j (A[i,j] & X[j,:]).

    mode: "auto" | "pallas" | "interpret" | "ref" | "mxu"
    tiles: optional (ti, tk, tw) override for the Pallas lowering, for
      callers and benchmarks that need to pin tile shapes.  The defaults
      already clamp to the operand (``ti = min(ti, m)`` etc.), so small
      operands collapse their grid without an override.
    """
    if mode == "auto":
        mode = "pallas" if _on_tpu() else "ref"
    tile_kw = dict(zip(("ti", "tk", "tw"), tiles)) if tiles else {}
    if mode == "pallas":
        KERNEL_INVOCATIONS["bitset_matmul"] += 1
        return bitset_matmul(a_packed, x, **tile_kw)
    if mode == "interpret":
        KERNEL_INVOCATIONS["bitset_matmul"] += 1
        return bitset_matmul(a_packed, x, interpret=True, **tile_kw)
    if mode == "mxu":
        return frontier_step_mxu(a_packed, x)
    if mode == "ref":
        return ref.bitset_matmul_ref(a_packed, x)
    raise ValueError(mode)


def frontier_step_lanes(a_packed: jax.Array, x: jax.Array, *, op: str,
                        cap: int = 0, mode: str = "auto",
                        tiles: tuple[int, int, int] | None = None
                        ) -> jax.Array:
    """One semiring expansion round over carrier *lanes* (uint8/16/32
    per element, not packed bits): ``(+)_j (A[i,j] (x) X[j,:])``.

    ``op`` selects the lane combine ("or" | "min" | "sum"); the min
    identity is dtype-max (= INF), sum saturates at ``cap``.  Same
    mode contract as ``frontier_step``.
    """
    if mode == "auto":
        mode = "pallas" if _on_tpu() else "ref"
    tile_kw = dict(zip(("ti", "tk", "tw"), tiles)) if tiles else {}
    if mode in ("pallas", "interpret"):
        KERNEL_INVOCATIONS["lane_matmul"] += 1
        return lane_matmul(a_packed, x, op=op, cap=cap,
                           interpret=(mode == "interpret"), **tile_kw)
    if mode == "ref":
        return ref.lane_matmul_ref(a_packed, x, op=op, cap=cap)
    raise ValueError(mode)


def frontier_step_edges(dst: jax.Array, src: jax.Array, count: jax.Array,
                        x: jax.Array, *, n_rows: int,
                        mode: str = "auto") -> jax.Array:
    """One boolean expansion round from an edge list:
    ``out[dst[e]] |= x[src[e]]`` over the first ``count`` edges (see
    ``bitset_matmul.lane_matmul_edges``).  Same mode contract as
    ``frontier_step`` less "mxu"; "ref" is a packed segment-OR."""
    if mode == "auto":
        mode = "pallas" if _on_tpu() else "ref"
    if mode in ("pallas", "interpret"):
        KERNEL_INVOCATIONS["lane_matmul_edges"] += 1
        return lane_matmul_edges(dst, src, count, x, n_rows=n_rows,
                                 interpret=(mode == "interpret"))
    if mode == "ref":
        return ref.lane_matmul_edges_ref(dst, src, count, x, n_rows=n_rows)
    raise ValueError(mode)


def frontier_step_sparse(comp, x: jax.Array, *,
                         mode: str = "auto") -> jax.Array:
    """Block-sparse expansion round over a ``BlockCompressed`` adjacency:
    ZERO blocks skipped, ONE blocks short-circuited to a column-OR, MIXED
    blocks gathered from the pool (see ``kernels.block_sparse``).

    mode: "auto" | "pallas" | "interpret" | "ref" — same contract as
    ``frontier_step``; "ref" is the pure-jnp segment-family lowering.
    """
    if mode == "auto":
        mode = "pallas" if _on_tpu() else "ref"
    if mode in ("pallas", "interpret"):
        KERNEL_INVOCATIONS["block_sparse_matmul"] += 1
        return block_sparse.block_sparse_matmul(
            comp, x, interpret=(mode == "interpret"))
    if mode == "ref":
        return block_sparse.block_sparse_matmul_ref(comp, x)
    raise ValueError(mode)


@jax.jit
def frontier_step_mxu(a_packed: jax.Array, x: jax.Array) -> jax.Array:
    """MXU lowering: unpack to bf16, real matmul, threshold, repack.

    32× the bytes of the packed VPU path but contraction runs at MXU rate;
    wins when K (graph block) is reused across many frontier columns.
    """
    m, kw = a_packed.shape
    k, w = x.shape
    a_bool = bitset.unpack_bits(a_packed, k).astype(jnp.bfloat16)
    x_bits = bitset.unpack_bits(x, w * WORD).astype(jnp.bfloat16)
    y = jax.lax.dot_general(a_bool, x_bits, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return bitset.pack_bits(y > 0)


def filter_ways(h_vtx, h_lab, v_vtx, v_lab, vbits, req, forb, null_plane,
                *, mode: str = "auto") -> jax.Array:
    """Fused per-(job, way) viability predicate -> bool [J, G]."""
    if mode == "auto":
        mode = "pallas" if _on_tpu() else "ref"
    if mode == "pallas":
        KERNEL_INVOCATIONS["way_filter"] += 1
        return way_filter(h_vtx, h_lab, v_vtx, v_lab, vbits, req, forb,
                          null_plane)
    if mode == "interpret":
        KERNEL_INVOCATIONS["way_filter"] += 1
        return way_filter(h_vtx, h_lab, v_vtx, v_lab, vbits, req, forb,
                          null_plane, interpret=True)
    if mode == "ref":
        return ref.way_filter_ref(h_vtx, h_lab, v_vtx, v_lab, vbits, req,
                                  forb, null_plane)
    raise ValueError(mode)


def popcount(words: jax.Array, *, mode: str = "auto") -> jax.Array:
    if mode == "auto":
        mode = "pallas" if _on_tpu() else "ref"
    if mode == "pallas":
        return popcount_rows(words)
    if mode == "interpret":
        return popcount_rows(words, interpret=True)
    if mode == "ref":
        return ref.popcount_rows_ref(words)
    raise ValueError(mode)
