"""Semiring carriers for the packed-plane fixpoint engine.

The TDR engine iterates ``r <- r (+) step(r)`` until a fixpoint.  PR 1-7
hard-coded the boolean OR semiring over packed uint32 words; this module
names the algebra so the same closure/propagate cores (and the pallas
kernels under ``repro.kernels``) run three instantiations:

``BOOLEAN``
    the original packed carrier — 32 graph bits per uint32 lane,
    ``combine`` = bitwise OR, ``extend`` = identity.  The generic code
    paths emit *literally the same traced ops* as the pre-refactor
    engine, so every plane (build, update, distributed exchange,
    snapshot round-trip) stays bit-identical on both backends.

``DIST16`` / ``DIST8``
    hop-distance (min, +) over saturating unsigned lanes.  One lane per
    query/state column, ``INF`` = dtype max, ``extend`` = saturating +1
    (``d + (d < INF)`` — branch-free, never wraps).  Idempotent, so the
    closure fixpoint converges; drives ``tdr_query.dist`` / ``witness``.

``COUNT``
    bounded route counting with saturating add, capped at ``cap`` so a
    dense graph cannot overflow the uint32 lane (and so that per-round
    clamping is exact: saturating add is associative for non-negative
    values).  NOT idempotent — ``closure()`` refuses it; route counting
    runs a hop-bounded DP in ``tdr_query.count_routes`` instead.

Instances are frozen and hashable, so they ride through ``jax.jit`` as
static arguments: each semiring gets its own compiled specialization and
the boolean one keeps its pre-refactor HLO.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from . import bitset

#: saturation cap for COUNT: 2^15 - 1.  A per-round segment_sum adds at
#: most ``cap`` per in-edge of a vertex, so below 2^17 in-edges per vertex
#: (checked by ``tdr_query.count_routes``) uint32 lane sums cannot wrap
#: before the clamp.
COUNT_CAP = (1 << 15) - 1


@dataclasses.dataclass(frozen=True)
class Semiring:
    """A (+)/(x) algebra over one carrier lane.

    ``op`` names the lane-level combine the kernels implement
    ("or" | "min" | "sum"); ``packed`` marks the 32-bits-per-word boolean
    carrier (the only one the bit-plane layout applies to).  ``zero`` is
    the (+)-identity (absorbing for paths that do not exist), ``one``
    the path-weight of the empty path.  ``idempotent`` is the convergence
    predicate's precondition: ``closure`` fixpoints are only defined when
    ``combine(a, a) == a``.
    """

    name: str
    op: str                   # lane combine: "or" | "min" | "sum"
    dtype_name: str           # carrier lane dtype
    packed: bool              # 32 graph bits per uint32 lane?
    idempotent: bool          # combine(a, a) == a (closure well-defined)
    cap: int = 0              # saturation cap ("sum" only)

    # -- carrier ----------------------------------------------------------
    @property
    def dtype(self):
        return jnp.dtype(self.dtype_name)

    @property
    def zero(self) -> int:
        """(+)-identity scalar: 0 for or/sum, dtype-max (INF) for min."""
        if self.op == "min":
            return int(jnp.iinfo(self.dtype).max)
        return 0

    @property
    def one(self) -> int:
        """(x)-identity scalar: the weight of the empty path."""
        return 0 if self.op == "min" else 1

    @property
    def inf(self) -> int:
        """Alias for the min-semiring unreachable sentinel."""
        if self.op != "min":
            raise ValueError(f"{self.name}: inf only defined for min")
        return self.zero

    def init(self, shape) -> jax.Array:
        """A carrier plane of (+)-identities."""
        return jnp.full(shape, self.zero, self.dtype)

    # -- algebra (trace-time; jnp in, jnp out) ----------------------------
    def combine(self, a, b):
        """(+): OR / elementwise min / saturating add."""
        if self.op == "or":
            return a | b
        if self.op == "min":
            return jnp.minimum(a, b)
        return jnp.minimum(a + b, jnp.asarray(self.cap, self.dtype))

    def extend(self, vals):
        """(x) with a unit edge weight: identity for or/sum, saturating
        +1 for min (INF stays INF, INF-1 saturates to INF)."""
        if self.op == "min":
            return vals + (vals < jnp.asarray(self.zero, self.dtype)
                           ).astype(self.dtype)
        return vals

    def segment_combine(self, vals, segment_ids, *, num_segments: int,
                        chunk_words: int = 0):
        """(+)-reduce ``vals`` rows into ``num_segments`` rows.

        The boolean carrier keeps the chunked packed-word OR (identical
        traced ops to the pre-refactor engine); min/sum use the native
        scatter reductions with the matching identity fill.
        """
        if self.op == "or":
            return bitset.segment_or_words(
                vals, segment_ids, num_segments=num_segments,
                chunk_words=chunk_words)
        if self.op == "min":
            return jax.ops.segment_min(
                vals, segment_ids, num_segments=num_segments)
        out = jax.ops.segment_sum(
            vals.astype(jnp.uint32), segment_ids, num_segments=num_segments)
        return jnp.minimum(out, jnp.uint32(self.cap)).astype(self.dtype)

    def accumulate(self, r, upd) -> Tuple[jax.Array, jax.Array]:
        """One fixpoint round: fold ``upd`` into ``r``.

        Returns ``(new_r, changed)``.  The boolean branch keeps the
        ``upd & ~r`` new-bits idiom verbatim (bit-identity contract);
        min compares planes (monotone decreasing, so inequality is
        exactly "some lane improved")."""
        if not self.idempotent:
            raise ValueError(
                f"{self.name}: accumulate/closure need an idempotent (+)")
        if self.op == "or":
            new = upd & ~r
            return r | new, jnp.any(new != 0)
        new_r = jnp.minimum(r, upd)
        return new_r, jnp.any(new_r != r)


BOOLEAN = Semiring(name="boolean", op="or", dtype_name="uint32",
                   packed=True, idempotent=True)
DIST16 = Semiring(name="dist16", op="min", dtype_name="uint16",
                  packed=False, idempotent=True)
DIST8 = Semiring(name="dist8", op="min", dtype_name="uint8",
                 packed=False, idempotent=True)
COUNT = Semiring(name="count", op="sum", dtype_name="uint32",
                 packed=False, idempotent=False, cap=COUNT_CAP)

_BY_NAME = {s.name: s for s in (BOOLEAN, DIST16, DIST8, COUNT)}


def by_name(name: str) -> Semiring:
    try:
        return _BY_NAME[name]
    except KeyError:
        raise ValueError(
            f"unknown semiring {name!r}; have {sorted(_BY_NAME)}") from None
