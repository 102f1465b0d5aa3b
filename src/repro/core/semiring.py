"""Semirings for associative-array algebra (paper §II).

An associative array A: K1 x K2 -> V carries a commutative monoid (V, add, zero)
used to combine colliding entries on block update, plus a multiplicative op for
array-array contraction (A @ B).  The paper grounds SQL (union-intersection),
NoSQL and NewSQL table semantics in this algebra; we expose the standard set.

Only `add`/`zero` participate in the streaming-update hot path; `mul`/`one`
are used by the query-side contractions (e.g. nearest-neighbor = A @ v).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class Semiring:
    """A (add, zero, mul, one) semiring over array values.

    ``segment_add`` must implement the same reduction as ``add`` over runs:
    (vals, segment_ids, num_segments) -> per-segment reduction.  The row
    and column reductions use it, where ids are not contiguous runs.  It
    lowers to a scatter, which on TPU v5e costs 6-9 ns per element: the
    merge's canonicalization, whose runs are contiguous after its sort,
    uses a segmented scan over ``add`` instead (``assoc._segmented_scan``).
    """

    name: str
    add: Callable[[Array, Array], Array]
    zero: float
    mul: Callable[[Array, Array], Array]
    one: float
    segment_add: Callable[..., Array]

    def zeros(self, shape, dtype) -> Array:
        return jnp.full(shape, jnp.asarray(self.zero, dtype=dtype))


def _seg(fn):
    def run(vals, segment_ids, num_segments, sorted=False):
        return fn(vals, segment_ids, num_segments=num_segments,
                  indices_are_sorted=sorted)
    return run


PLUS_TIMES = Semiring(
    name="plus.times",
    add=jnp.add, zero=0.0,
    mul=jnp.multiply, one=1.0,
    segment_add=_seg(jax.ops.segment_sum),
)

# max.plus — tropical; value combine keeps the max (e.g. "latest timestamp").
MAX_PLUS = Semiring(
    name="max.plus",
    add=jnp.maximum, zero=-jnp.inf,
    mul=jnp.add, one=0.0,
    segment_add=_seg(jax.ops.segment_max),
)

# min.plus — shortest-path style combine.
MIN_PLUS = Semiring(
    name="min.plus",
    add=jnp.minimum, zero=jnp.inf,
    mul=jnp.add, one=0.0,
    segment_add=_seg(jax.ops.segment_min),
)

# max.min — bottleneck / fuzzy-logic semiring (paper's union-intersection
# analogue over numeric stand-ins).
MAX_MIN = Semiring(
    name="max.min",
    add=jnp.maximum, zero=-jnp.inf,
    mul=jnp.minimum, one=jnp.inf,
    segment_add=_seg(jax.ops.segment_max),
)


_BY_NAME = {s.name: s for s in (PLUS_TIMES, MAX_PLUS, MIN_PLUS, MAX_MIN)}


def get(name: str) -> Semiring:
    try:
        return _BY_NAME[name]
    except KeyError:
        raise ValueError(
            f"unknown semiring {name!r}; available: {sorted(_BY_NAME)}")


def reduce_kind(sr: Semiring) -> str:
    """How ``sr.add`` reduces over an axis: "sum" | "max" | "min".

    The single source of truth for every add-reduction dispatch outside
    ``segment_add`` (axis reductions, scatter combines, mesh collectives
    — repro/query/engine.py, core/distributed.py).  Raises on an unknown
    semiring instead of silently picking a wrong reduction.
    """
    if sr.name == "plus.times":
        return "sum"
    if sr.name in ("max.plus", "max.min"):
        return "max"
    if sr.name == "min.plus":
        return "min"
    raise ValueError(f"no add-reduction known for semiring {sr.name!r}")


def integer_zero(sr: Semiring, dtype) -> Array:
    """Semiring zero clamped into an integer dtype's range."""
    z = sr.zero
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        if z == -jnp.inf:
            return jnp.asarray(info.min, dtype)
        if z == jnp.inf:
            return jnp.asarray(info.max, dtype)
    return jnp.asarray(z, dtype)
