"""The comparison that decides ``correct``.

Each number compared is ``(name, value, limit)`` and passes when
``value <= limit``.  Every answer here is exact (unit values, float32
totals below 2^24, integer counters), so every limit is 0.  The readings
the limits were set from are in PERF.md.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from bench.reference import Reference, coalesce_layers

Number = Tuple[str, float, float]


def window(compiles: int) -> List[Number]:
    """Programs compiled inside the measured window."""
    return [("compiles", compiles, 0)]


def dtype_gap(layers: dict, dtype: str) -> int:
    """Buffers of the sampled instances' layers that are not held in the
    stated precision: int32 keys and ``dtype`` values."""
    want = (np.dtype(np.int32), np.dtype(np.int32), np.dtype(dtype))
    return sum(np.asarray(buf).dtype != w
               for per in layers.values() for layer in per
               for buf, w in zip(layer[:3], want))


def fleet(count: int, expected: int, overflow: int, layers: dict,
          ref: Reference, dtype: str) -> List[Number]:
    """The fleet the last cycle left: its exact update count, its overflow,
    the precision of its buffers, and every layer of each sampled instance
    (``layers[i]`` as read from the device) against the reference's
    coalesce of that instance."""
    key_gap, value_gap = 0, 0.0
    for i in ref.ids:
        g_r, g_c, g_v = coalesce_layers(layers[i])
        r, c, v = ref.coalesced(i)
        got = (g_r.astype(np.int64) << 32) | g_c.astype(np.int64)
        want = (r.astype(np.int64) << 32) | c.astype(np.int64)
        key_gap += len(np.setxor1d(got, want, assume_unique=True))
        both, gi, wi = np.intersect1d(got, want, assume_unique=True,
                                      return_indices=True)
        if len(both):
            value_gap = max(value_gap, float(np.max(np.abs(g_v[gi]
                                                           - v[wi]))))
    return [("count_gap", abs(int(count) - int(expected)), 0),
            ("overflow", int(overflow), 0),
            ("dtype_gap", int(dtype_gap(layers, dtype)), 0),
            ("key_gap", key_gap, 0),
            ("value_gap", value_gap, 0)]


def passed(numbers: List[Number]) -> bool:
    return all(v <= lim for _, v, lim in numbers)


def as_dict(numbers: List[Number]) -> dict:
    return {name: {"value": v, "limit": lim} for name, v, lim in numbers}
