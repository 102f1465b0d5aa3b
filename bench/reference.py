"""The plain reference: a numpy coalesce of each sampled instance's stream.

Copied from ``chip_smoke.Reference``, which checked the fleet on
the chip.  It imports nothing of the program and takes nothing the program
made: its input is the benchmark's own stream.  Totals are float64 sums,
so with unit values every total is an exact integer.

``dtype`` picks the precision the totals are accumulated in: float64 for
the reference, ``bfloat16`` for the control (one step below the float32
that the configurations state), where each key's total is a running
bfloat16 sum, as a store that kept bfloat16 values would hold it.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import ml_dtypes
import numpy as np

BF16 = np.dtype(ml_dtypes.bfloat16)


def _keys(rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
    return (rows.astype(np.int64) << 32) | cols.astype(np.int64)


def _group_sum(keys: np.ndarray, vals: np.ndarray, dtype):
    """(unique sorted keys, per-key sum in ``dtype``)."""
    if not len(keys):
        return keys, np.zeros(0)
    if dtype == BF16:
        order = np.argsort(keys, kind="stable")
        k = keys[order]
        starts = np.flatnonzero(np.r_[True, k[1:] != k[:-1]])
        tot = np.add.reduceat(vals[order].astype(BF16), starts)
        return k[starts], tot.astype(np.float64)
    uniq, inv = np.unique(keys, return_inverse=True)
    return uniq, np.bincount(inv, weights=vals.astype(np.float64))


class Reference:
    """Every (row, col, val) a few instances were sent, coalesced by key
    with the plus.times add."""

    def __init__(self, ids: Sequence[int], dtype=np.float64):
        self.ids = [int(i) for i in ids]
        self.dtype = np.dtype(dtype)
        self.parts: Dict[int, List[tuple]] = {i: [] for i in self.ids}
        self._cache: Dict[int, tuple] = {}

    def add(self, rows, cols, vals) -> None:
        """``rows``/``cols``/``vals`` hold the sampled instances' updates,
        ``[len(ids), ...]`` in the order of ``ids``."""
        for n, i in enumerate(self.ids):
            self.parts[i].append(tuple(np.asarray(x[n]).ravel()
                                       for x in (rows, cols, vals)))
        self._cache.clear()

    def drop_last(self) -> None:
        """Forget the last ``add`` (the control that loses acknowledged
        updates)."""
        for i in self.ids:
            self.parts[i].pop()
        self._cache.clear()

    def coalesced(self, i: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(rows, cols, totals) of instance ``i``, sorted by (row, col)."""
        if i not in self._cache:
            r, c, v = (np.concatenate(p) for p in zip(*self.parts[i]))
            uniq, tot = _group_sum(_keys(r, c), v, self.dtype)
            self._cache[i] = ((uniq >> 32).astype(np.int32),
                              (uniq & 0xFFFFFFFF).astype(np.int32), tot)
        return self._cache[i]


def coalesce_layers(layers) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(rows, cols, totals) of one instance's hierarchy as read from the
    device: ``layers`` is a list of host ``(hi, lo, val, nnz)`` with the
    live slots first; a key's copies in several layers, or in the raw
    layer-0 buffer, add up, as the plus.times merge would add them."""
    hi = np.concatenate([l[0][:int(l[3])] for l in layers])
    lo = np.concatenate([l[1][:int(l[3])] for l in layers])
    val = np.concatenate([l[2][:int(l[3])] for l in layers])
    uniq, tot = _group_sum(_keys(hi, lo), val, np.dtype(np.float64))
    return ((uniq >> 32).astype(np.int32),
            (uniq & 0xFFFFFFFF).astype(np.int32), tot)
