"""Streaming network analytics over the live hierarchy — paper follow-up
"Streaming 1.9 Billion Hypersparse Network Updates per Second with D4M"
(arXiv:1907.04217) computes traffic-matrix statistics (degrees, heavy
hitters) WHILE the fleet ingests; this module composes those statistics
from per-layer reductions so the merged array is never materialized:

    stat(merge(layers)) == sr-combine_i stat(layer_i)

which holds for every reduction here because ``sr.add`` across a key's
per-layer copies is exactly the merge's combine (sum under plus.times;
max/min are idempotent), and every contraction used (``reduce_rows``,
``reduce_cols``, ``spmv``, ``spmv_t``) is linear in that sense.  The lazy
layer-0 append buffer needs no special data path — but it IS a raw buffer,
so layer 0 always reduces with ``sorted=False``: that drops the
``indices_are_sorted`` hint (its keys are unsorted and duplicated) AND
gates live slots by ``nnz`` (``assoc._live_slots``) instead of trusting
slots past ``nnz`` to hold sentinel keys / zero values, matching the
engine's ``_raw_point``/``extract_rows`` discipline.

All functions are jit-safe and vmap-safe over the instance axis; fleet-wide
callers map them over instances in ``instance_batch`` chunks.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import assoc
from repro.core import semiring as sr_mod
from repro.core.semiring import Semiring

Array = jax.Array


# Dense per-row temporaries must not grow with the instance count: a
# fleet-wide reduction that vmaps ``top_k_rows`` over 1,024 instances at
# num_rows = 2^22 would hold 1,024 x 16 MiB per dense vector, more than a
# 16 GiB v5e chip.  Callers ``lax.map`` over instances in batches of
# ``instance_batch(num_rows)``, which keeps batch x _DENSE_VECTORS x 4 B x
# num_rows under a quarter of that chip's HBM.
_DENSE_BUDGET_BYTES = 4 << 30
_DENSE_VECTORS = 8      # [num_rows] 4-byte vectors live at once per instance


def instance_batch(num_rows: int) -> int:
    """Instances per ``lax.map`` step for a dense [num_rows] reduction."""
    return max(1, _DENSE_BUDGET_BYTES // (_DENSE_VECTORS * 4 * num_rows))


def _layer_combine(sr: Semiring, parts) -> Array:
    out = parts[0]
    for p in parts[1:]:
        out = sr.add(out, p)
    return out


def out_degrees(h, num_rows: int, sr: Semiring = sr_mod.PLUS_TIMES) -> Array:
    """Per-row totals (weighted out-degrees under plus.times) without
    merging: layer-wise ``assoc.reduce_rows`` + semiring combine.  Layer 0
    is reduced as a RAW buffer (sorted=False) so the lazy append
    discipline — duplicates and all — needs no canonicalization."""
    parts = [assoc.reduce_rows(h.layers[0], num_rows, sr, sorted=False)]
    parts += [assoc.reduce_rows(l, num_rows, sr) for l in h.layers[1:]]
    return _layer_combine(sr, parts)


def in_degrees(h, num_cols: int, sr: Semiring = sr_mod.PLUS_TIMES) -> Array:
    """Per-column totals (weighted in-degrees under plus.times); ``lo`` is
    the minor key so no layer earns the sorted-indices hint, but layer 0
    still reduces as a RAW buffer (sorted=False) for the ``nnz`` live-slot
    gate."""
    parts = [assoc.reduce_cols(h.layers[0], num_cols, sr, sorted=False)]
    parts += [assoc.reduce_cols(l, num_cols, sr) for l in h.layers[1:]]
    return _layer_combine(sr, parts)


def degree_vectors(h, num_rows: int, num_cols: int,
                   sr: Semiring = sr_mod.PLUS_TIMES) -> Tuple[Array, Array]:
    """(out_degrees, in_degrees) — the traffic-matrix row/col statistics of
    arXiv:1907.04217, one dispatch, no merge."""
    return out_degrees(h, num_rows, sr), in_degrees(h, num_cols, sr)


def row_occupancy(h, num_rows: int) -> Array:
    """Number of live stored entries per row across every layer (layer 0
    counted as a raw buffer, so duplicate keys count per slot).  Zero means
    the row was never touched — the mask ``top_k_rows`` needs, because a
    row's semiring TOTAL cannot distinguish "never updated" from "updates
    summing to the add identity" (and under min-reduce semirings the
    identity is +inf, which ``lax.top_k`` would rank first)."""
    total = jnp.zeros((num_rows,), jnp.int32)
    for i, l in enumerate(h.layers):
        valid = assoc._live_slots(l, sorted=i > 0)
        ids = jnp.where(valid, l.hi, num_rows)
        total = total + jax.ops.segment_sum(
            valid.astype(jnp.int32), ids,
            num_segments=num_rows + 1)[:num_rows]
    return total


def top_k_rows(h, num_rows: int, k: int,
               sr: Semiring = sr_mod.PLUS_TIMES) -> Tuple[Array, Array]:
    """Heavy hitters: the k EXTREMAL live rows by semiring row total (top
    talkers of the network traffic matrix).  Returns (totals, row ids),
    both [k].

    Untouched rows hold the semiring's add identity and are masked out via
    ``row_occupancy`` — without the mask they poisoned the ranking twice:
    under min-reduce semirings (min.plus) the identity is +inf, which
    ``lax.top_k`` ranks as the LARGEST total, so "heavy hitters" returned
    nothing but empty rows; and under plus.times a dead row's 0.0 outranked
    every live row with a negative total.

    Ordering follows the semiring's notion of extremal: descending totals
    for sum/max reductions, ASCENDING for min reductions (min.plus heavy
    hitters are the smallest accumulated totals — e.g. shortest observed
    paths).  When fewer than ``k`` rows are live, the tail is padded with
    the dtype's worst-ranked value (``-inf``/``+inf`` for floats, the
    iinfo extremes for integer hierarchies — masking with a float inf
    would silently promote exact integer totals to float32) and arbitrary
    row ids.
    """
    deg = out_degrees(h, num_rows, sr)
    live = row_occupancy(h, num_rows) > 0
    if jnp.issubdtype(deg.dtype, jnp.integer):
        info = jnp.iinfo(deg.dtype)
        worst_max, worst_min = info.min, info.max
    else:
        worst_max, worst_min = -jnp.inf, jnp.inf
    if sr_mod.reduce_kind(sr) == "min":
        score = jnp.where(live, deg, jnp.asarray(worst_min, deg.dtype))
        neg, ids = jax.lax.top_k(-score, k)
        return -neg, ids
    return jax.lax.top_k(
        jnp.where(live, deg, jnp.asarray(worst_max, deg.dtype)), k)


def spmv(h, x: Array, num_rows: int,
         sr: Semiring = sr_mod.PLUS_TIMES) -> Array:
    """y = A (.) x against the live hierarchy: per-layer ``assoc.spmv``
    combined with the semiring (exact — ``mul`` distributes over the layer
    combine: sum of products under plus.times, and max/min are monotone in
    the matrix argument for the tropical semirings)."""
    parts = [assoc.spmv(h.layers[0], x, num_rows, sr, sorted=False)]
    parts += [assoc.spmv(l, x, num_rows, sr) for l in h.layers[1:]]
    return _layer_combine(sr, parts)


def spmv_t(h, x: Array, num_cols: int,
           sr: Semiring = sr_mod.PLUS_TIMES) -> Array:
    """y = A' (.) x against the live hierarchy (transpose contraction);
    layer 0 contracts as a RAW buffer (sorted=False)."""
    parts = [assoc.spmv_t(h.layers[0], x, num_cols, sr, sorted=False)]
    parts += [assoc.spmv_t(l, x, num_cols, sr) for l in h.layers[1:]]
    return _layer_combine(sr, parts)


def ata_correlation(h, x: Array, num_rows: int, num_cols: int,
                    sr: Semiring = sr_mod.PLUS_TIMES) -> Array:
    """One A'A correlation step applied to a vector: y = A'(A x).

    A'A is the column-key correlation matrix of D4M's analytic toolbox
    (shared-neighbor counts when A is an adjacency matrix); applying it
    through the two-step contraction never forms A'A OR the merged A —
    both contractions stream over the layers.
    """
    u = spmv(h, x, num_rows, sr)
    return spmv_t(h, u, num_cols, sr)
