"""Static-capacity associative-array segments (sorted COO) — paper §II.

A D4M associative array maps (row, col) string/int keys to semiring values.
Under jit every shape must be static, so an array is stored as a *segment*:

    hi : int32[C]   row keys   (lexicographic major)
    lo : int32[C]   col keys   (lexicographic minor)
    val: V[C]       semiring values
    nnz: int32      live-entry count

Entries [0, nnz) are sorted by (hi, lo) and unique; slots [nnz, C) hold the
SENTINEL key and the semiring zero.  This invariant ("canonical form") lets
merges concatenate raw buffers without masking.

All ops are pure, jit-safe and vmap-safe (instances dimension), matching the
paper's share-nothing multi-instance design.

CONTRACTS
---------
The invariants every producer and consumer of a segment trades on.  They
are enforced mechanically three ways: statically by
``repro.analysis.lint`` (rules R001-R005), at trace time by
``repro.analysis.contracts`` under ``REPRO_CHECK=1``, and post-lowering
by ``repro.analysis.tracekit`` (rules J001-J006 over the staged
jaxpr/HLO); EXPERIMENTS.md cross-references this section.

1. **Canonical form** (``sorted=True`` paths, every layer >= 1, and layer 0
   outside lazy-append mode): entries [0, nnz) are sorted-unique by
   (hi, lo) and contain no SENTINEL key.  Consumers may binary-search,
   run-merge without re-sorting, and pass ``indices_are_sorted`` hints.
2. **Sentinel tail**: slots [nnz, C) hold exactly (SENTINEL, SENTINEL,
   semiring zero).  This is what lets ``merge``/``merge_many`` concatenate
   whole buffers without masking — a single dirty tail slot silently
   corrupts every downstream merge and reduction.
3. **Raw-buffer contract** (``sorted=False`` paths — the lazy layer-0
   append buffer, checkpoint-restored or externally built segments): ONLY
   slots [0, nnz) are meaningful.  Entries there may be unsorted and
   duplicated; the tail is not trusted.  Reductions over raw buffers must
   gate live slots via ``_live_slots(seg, sorted=False)`` (the
   ``arange(C) < nnz`` gate) — lint rule R005 flags reductions over
   ``.val`` that do neither.
4. **nnz bound**: 0 <= nnz <= C always; overflow is reported through the
   separate ``overflow`` counters, never by letting nnz exceed capacity.
5. **Counter words** (``hier.HierAssoc``): the raw-update total is a
   (hi, lo) = (int32, uint32) carry pair — lo wraps mod 2**32, hi counts
   wraps and is never negative; total live slots never exceed the 64-bit
   update total.
6. **32-bit discipline** (tracekit J001/J005): keys, counters and values
   stay <= 32 bits inside every compiled kernel.  Compares over (hi, lo)
   pairs are LEXICOGRAPHIC pair-compares — never a pack into an int64
   (J005 flags the widening), and no traced computation may touch
   f64/c128 (J001 flags x64 leaks).  This is what keeps the bytes each
   merge moves on the paper's roofline (arXiv:1902.00846 §IV).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import semiring as sr_mod
from repro.core.semiring import Semiring

Array = jax.Array

# Largest int32 — real keys must be strictly smaller.
SENTINEL = jnp.iinfo(jnp.int32).max

# Sort strategy for canonicalization.  The paper's merge hot path is
# dominated by the sort.  ``lexsort`` returns a permutation which we then
# apply with three separate gathers; ``lax.sort`` with num_keys=2 CO-SORTS
# the value payload inside the one variadic sort — no gather passes.
# Measured on the d4m ingest probes (EXPERIMENTS.md §Perf, hillclimb 3).
CO_SORT = True


def _sorted_by_key(hi: "Array", lo: "Array", val: "Array"):
    if CO_SORT:
        return jax.lax.sort((hi, lo, val), num_keys=2)
    order = jnp.lexsort((lo, hi))
    return hi[order], lo[order], val[order]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AssocSegment:
    """One canonical-form associative array segment."""

    hi: Array
    lo: Array
    val: Array
    nnz: Array

    @property
    def capacity(self) -> int:
        return self.hi.shape[-1]

    @property
    def dtype(self):
        return self.val.dtype


def empty(capacity: int, dtype=jnp.float32,
          sr: Semiring = sr_mod.PLUS_TIMES) -> AssocSegment:
    zero = sr_mod.integer_zero(sr, dtype)
    return AssocSegment(
        hi=jnp.full((capacity,), SENTINEL, jnp.int32),
        lo=jnp.full((capacity,), SENTINEL, jnp.int32),
        val=jnp.full((capacity,), zero, dtype),
        nnz=jnp.zeros((), jnp.int32),
    )


def _canonicalize(hi: Array, lo: Array, val: Array, out_capacity: int,
                  sr: Semiring) -> Tuple[AssocSegment, Array]:
    """Sort by (hi, lo), combine duplicate keys with sr.add, compact, pad.

    Inputs may contain SENTINEL entries (ignored).  Returns the canonical
    segment of the requested capacity plus an ``overflow`` count of unique
    entries dropped because they exceeded out_capacity (largest keys drop
    first, preserving the sorted prefix).

    No scatter: after the co-sort every run of equal keys is contiguous, so
    a segmented scan leaves each run's total in its last slot, and a second
    sort moves those slots to the front in key order while every other slot,
    blanked to the SENTINEL key and the semiring zero, sorts to the tail.
    The phases carry a ``jax.named_scope`` (``canon.sort``,
    ``canon.value_sum`` for the scan, ``canon.key_scatter`` for the
    compaction sort) that reaches the compiled ops' ``op_name``, so a device
    trace can be split by phase (``stages.op_scopes``).  Scopes are metadata
    only.
    """
    n = hi.shape[-1]
    with jax.named_scope("canon.sort"):
        hi_s, lo_s, val_s = _sorted_by_key(hi, lo, val)

    prev_same = (hi_s[1:] == hi_s[:-1]) & (lo_s[1:] == lo_s[:-1])
    first = jnp.concatenate([jnp.ones((1,), bool), ~prev_same])
    last = jnp.concatenate([~prev_same, jnp.ones((1,), bool)])
    valid = hi_s != SENTINEL
    n_unique = jnp.sum(first & valid).astype(jnp.int32)

    zero = sr_mod.integer_zero(sr, val.dtype)
    with jax.named_scope("canon.value_sum"):
        totals = _segmented_scan(first, val_s, sr.add, zero)

    with jax.named_scope("canon.key_scatter"):
        keep = last & valid
        out_hi, out_lo, out_val = _sorted_by_key(
            jnp.where(keep, hi_s, SENTINEL), jnp.where(keep, lo_s, SENTINEL),
            jnp.where(keep, totals, zero))

    if out_capacity >= n:
        pad = out_capacity - n
        out_hi = jnp.concatenate([out_hi, jnp.full((pad,), SENTINEL, jnp.int32)])
        out_lo = jnp.concatenate([out_lo, jnp.full((pad,), SENTINEL, jnp.int32)])
        out_val = jnp.concatenate([out_val, jnp.full((pad,), zero, val.dtype)])
        overflow = jnp.zeros((), jnp.int32)
    else:
        out_hi = out_hi[:out_capacity]
        out_lo = out_lo[:out_capacity]
        out_val = out_val[:out_capacity]
        overflow = jnp.maximum(n_unique - out_capacity, 0).astype(jnp.int32)

    nnz = jnp.minimum(n_unique, out_capacity).astype(jnp.int32)
    return AssocSegment(out_hi, out_lo, out_val, nnz), overflow


def _segmented_scan(first: Array, val: Array, add, zero) -> Array:
    """Inclusive scan of ``val`` under ``add`` that restarts at every slot
    where ``first`` is set: each run's total lands in its last slot.

    Blocked, so the compiled program stays small at millions of slots: the
    slots are laid out in rows of 128 (one vector register's lanes; rows of
    1,024 timed within 1% on a v5e), each row is scanned by 7
    shift-and-combine steps, the rows' own totals are scanned the same way
    (recursively), and each row then takes in the total carried from the
    rows before it in one elementwise pass.  Only slots of one run are ever
    combined, so a plus.times total is exact wherever the run's total is.
    """
    n, row = val.shape[0], 128
    if n <= row:
        return _shift_scan(first, val, add, zero)[1]
    rows = -(-n // row)
    pad = rows * row - n
    f = jnp.concatenate([first, jnp.ones((pad,), bool)]).reshape(rows, row)
    v = jnp.concatenate([val, jnp.full((pad,), zero, val.dtype)]
                        ).reshape(rows, row)
    f, v = _shift_scan(f, v, add, zero)
    # row r continues the run open at the end of row r - 1 until its own
    # first run start; row 0 starts with one (first[0] is always set)
    ends = _segmented_scan(f[:, -1], v[:, -1], add, zero)
    carry = jnp.concatenate([jnp.full((1,), zero, val.dtype), ends[:-1]])
    v = jnp.where(f, v, add(carry[:, None], v))
    return v.reshape(-1)[:n]


def _shift_scan(f: Array, v: Array, add, zero) -> Tuple[Array, Array]:
    """Segmented inclusive scan along the last axis (Hillis-Steele): after
    the step of shift k, ``v`` holds the combine of the 2k slots up to and
    including each slot, cut at the latest run start among them, and ``f``
    says whether a run starts among them.  Slots before the row's start
    read as ``zero`` (``add``'s identity) in no run start."""
    width = v.shape[-1]
    k = 1
    while k < width:
        lead = v.shape[:-1] + (k,)
        f_in = jnp.concatenate([jnp.zeros(lead, bool), f[..., :-k]], axis=-1)
        v_in = jnp.concatenate([jnp.full(lead, zero, v.dtype), v[..., :-k]],
                               axis=-1)
        v = jnp.where(f, v, add(v_in, v))
        f = f | f_in
        k *= 2
    return f, v


def mask_coo(rows: Array, cols: Array, vals: Array,
             mask: Array | None, sr: Semiring
             ) -> Tuple[Array, Array, Array]:
    """int32-cast a COO block and blank masked-out entries to the SENTINEL
    key / semiring zero (the canonical 'ignore me' encoding)."""
    rows = rows.astype(jnp.int32)
    cols = cols.astype(jnp.int32)
    if mask is not None:
        zero = sr_mod.integer_zero(sr, vals.dtype)
        rows = jnp.where(mask, rows, SENTINEL)
        cols = jnp.where(mask, cols, SENTINEL)
        vals = jnp.where(mask, vals, zero)
    return rows, cols, vals


def from_coo(rows: Array, cols: Array, vals: Array, capacity: int,
             sr: Semiring = sr_mod.PLUS_TIMES,
             mask: Array | None = None) -> Tuple[AssocSegment, Array]:
    """Build a canonical segment from an (unsorted, possibly duplicated) block."""
    rows, cols, vals = mask_coo(rows, cols, vals, mask, sr)
    return _canonicalize(rows, cols, vals, capacity, sr)


def merge(a: AssocSegment, b: AssocSegment, out_capacity: int,
          sr: Semiring = sr_mod.PLUS_TIMES) -> Tuple[AssocSegment, Array]:
    """a (+) b under the semiring, into a segment of out_capacity."""
    hi = jnp.concatenate([a.hi, b.hi])
    lo = jnp.concatenate([a.lo, b.lo])
    val = jnp.concatenate([a.val, b.val.astype(a.val.dtype)])
    return _canonicalize(hi, lo, val, out_capacity, sr)


def merge_kernel(a: AssocSegment, b: AssocSegment, out_capacity: int,
                 sr: Semiring = sr_mod.PLUS_TIMES
                 ) -> Tuple[AssocSegment, Array]:
    """Kernel-backed merge: Pallas sorting-network path (VMEM-resident on
    TPU, interpret mode on CPU).  Falls back to the XLA-sort path above the
    kernel capacity ceiling."""
    from repro.kernels.hier_merge import ops as hm_ops

    total = a.capacity + b.capacity
    if total > hm_ops.MAX_KERNEL_CAPACITY:
        return merge(a, b, out_capacity, sr)
    hi, lo, val, nnz, ovf = hm_ops.merge(
        a.hi, a.lo, a.val, b.hi, b.lo, b.val.astype(a.val.dtype),
        out_capacity=out_capacity, sr_name=sr.name)
    return AssocSegment(hi, lo, val, nnz), ovf


def merge_many(segments, hi: Array, lo: Array, val: Array, *,
               out_capacity: int, sr: Semiring = sr_mod.PLUS_TIMES,
               use_kernel: bool = False,
               debug: bool = False) -> Tuple[AssocSegment, Array]:
    """Semiring-merge k canonical segments plus one RAW (unsorted, possibly
    duplicated, sentinel-masked) COO buffer in a SINGLE canonicalization.

    This is the fused spill cascade's data plane: instead of one sort per
    hierarchy level, every spilling layer's buffer and the incoming block
    are combined in one pass.  With ``use_kernel`` the Pallas multi-way
    merge is used below its capacity ceiling (the sorted runs are bitonic-
    merged, not re-sorted); otherwise one XLA co-sort does everything.

    ``debug`` (or tracing inside ``contracts.activate()``) emits checkify
    checks that every input run really is canonical — the precondition this
    whole fusion trades on — and that the merged output is too.  Only legal
    inside a ``checkify.checkify``-transformed program.
    """
    segments = tuple(segments)
    if debug or _deep_checks_active():
        from repro.analysis import contracts
        for i, s in enumerate(segments):
            contracts.check_canonical(s, sr, name=f"merge_many input run {i}")
        out, ovf = _merge_many_impl(segments, hi, lo, val,
                                    out_capacity=out_capacity, sr=sr,
                                    use_kernel=use_kernel)
        contracts.check_canonical(out, sr, name="merge_many output")
        return out, ovf
    return _merge_many_impl(segments, hi, lo, val, out_capacity=out_capacity,
                            sr=sr, use_kernel=use_kernel)


def _deep_checks_active() -> bool:
    from repro.analysis import contracts
    return contracts.deep_checks_active()


def _merge_many_impl(segments, hi: Array, lo: Array, val: Array, *,
                     out_capacity: int, sr: Semiring,
                     use_kernel: bool) -> Tuple[AssocSegment, Array]:
    if use_kernel:
        from repro.kernels.hier_merge import ops as hm_ops

        run_caps = tuple(s.capacity for s in segments)
        if hm_ops.multi_padded_capacity(hi.shape[-1], run_caps) \
                <= hm_ops.MAX_KERNEL_CAPACITY:
            run_arrays = []
            for s in segments:
                run_arrays += [s.hi, s.lo, s.val.astype(val.dtype)]
            o_hi, o_lo, o_val, nnz, ovf = hm_ops.merge_multi(
                hi, lo, val, *run_arrays,
                out_capacity=out_capacity, sr_name=sr.name)
            return AssocSegment(o_hi, o_lo, o_val, nnz), ovf
    cat_hi = jnp.concatenate([hi] + [s.hi for s in segments])
    cat_lo = jnp.concatenate([lo] + [s.lo for s in segments])
    cat_val = jnp.concatenate([val] + [s.val.astype(val.dtype)
                                       for s in segments])
    return _canonicalize(cat_hi, cat_lo, cat_val, out_capacity, sr)


def gate_segment(seg: AssocSegment, keep,
                 sr: Semiring = sr_mod.PLUS_TIMES) -> AssocSegment:
    """All-or-nothing participation gate for a canonical run.

    With ``keep`` False the segment is blanked to the all-SENTINEL empty run
    — which is itself canonical, so the kernel path may still treat it as a
    sorted run; with ``keep`` True it is returned unchanged.  ``keep`` may be
    a traced scalar: this is the branch-free alternative to selecting runs
    with ``lax.switch``, which under ``vmap`` lowers to select-over-all-
    branches and makes every instance execute every spill depth's merge
    (EXPERIMENTS.md §Multi-instance scaling).  The fused cascade gates each
    layer's buffer into ONE fixed-shape ``merge_many`` instead.
    """
    zero = sr_mod.integer_zero(sr, seg.dtype)
    return AssocSegment(
        hi=jnp.where(keep, seg.hi, SENTINEL),
        lo=jnp.where(keep, seg.lo, SENTINEL),
        val=jnp.where(keep, seg.val, zero),
        nnz=jnp.where(keep, seg.nnz, 0).astype(jnp.int32))


def clear(seg: AssocSegment, sr: Semiring = sr_mod.PLUS_TIMES) -> AssocSegment:
    return empty(seg.capacity, seg.dtype, sr)


# ---------------------------------------------------------------- queries ---

def lookup(seg: AssocSegment, row, col,
           sr: Semiring = sr_mod.PLUS_TIMES, sorted: bool = True) -> Array:
    """Point query A(row, col); semiring zero when absent.

    ``sorted=False`` admits a RAW buffer (lazy layer-0 append buffer, or any
    segment of unknown provenance): matches are additionally gated by the
    ``nnz`` live-slot mask, so stale keys beyond the live prefix can never
    alias a real (row, col) — the raw-buffer contract, see CONTRACTS.
    """
    match = (seg.hi == row) & (seg.lo == col) & _live_slots(seg, sorted)
    zero = sr_mod.integer_zero(sr, seg.dtype)
    return jnp.where(jnp.any(match),
                     jnp.sum(jnp.where(match, seg.val, zero), dtype=seg.dtype)
                     if sr.name == "plus.times"
                     else seg.val[jnp.argmax(match)],
                     zero)


def extract_row(seg: AssocSegment, row) -> Tuple[Array, Array, Array]:
    """All (col, val) pairs of one row plus a validity mask (Fig 1's
    nearest-neighbor query)."""
    m = seg.hi == row
    return seg.lo, seg.val, m


def _live_slots(seg: AssocSegment, sorted: bool) -> Array:
    """Validity mask for a reduction input.

    Canonical segments (``sorted=True``) are fully described by the
    sentinel invariant: slots [nnz, C) hold SENTINEL / semiring zero.  A
    RAW buffer (``sorted=False`` — the lazy layer-0 append buffer, or any
    externally constructed / checkpoint-restored segment) only promises
    that slots [0, nnz) are meaningful, so raw reductions must ALSO gate on
    ``arange(C) < nnz`` — the same live-slot gate ``engine._raw_point`` and
    ``engine.extract_rows`` apply.  The in-repo ingest paths keep the tail
    sentinel-clean — no longer just "verified once in PR 5" but enforced at
    trace time by ``repro.analysis.contracts.check_canonical`` under
    ``REPRO_CHECK=1`` and at lint time by rule R005 — but the raw-buffer
    CONTRACT is still nnz, not the tail, and trusting the tail made the
    analytics reductions wrong for any state that doesn't uphold the
    stronger invariant.
    """
    valid = seg.hi != SENTINEL
    if not sorted:
        valid &= jnp.arange(seg.capacity) < seg.nnz
    return valid


def reduce_rows(seg: AssocSegment, num_rows: int,
                sr: Semiring = sr_mod.PLUS_TIMES,
                sorted: bool = True) -> Array:
    """Dense per-row reduction (e.g. out-degrees under plus.times).

    ``sorted=False`` lifts the canonical-form assumption so the same
    reduction runs over a RAW buffer (the lazy layer-0 append buffer, with
    unsorted and duplicated keys), gating live slots by ``nnz`` instead of
    trusting the sentinel tail — the streaming query engine (repro/query)
    composes per-layer reductions without merging layers.
    """
    ids = jnp.where(_live_slots(seg, sorted), seg.hi, num_rows)
    # hi is sorted in canonical form and clipping maps to the max id only.
    out = sr.segment_add(seg.val, ids, num_rows + 1, sorted=sorted)
    return out[:num_rows]


def reduce_cols(seg: AssocSegment, num_cols: int,
                sr: Semiring = sr_mod.PLUS_TIMES,
                sorted: bool = True) -> Array:
    """Dense per-column reduction (in-degrees under plus.times).

    ``sorted`` here means "canonical segment", matching ``reduce_rows`` —
    ``lo`` is the minor sort key so the segment ids never earn the
    ``indices_are_sorted`` hint either way, but ``sorted=False`` adds the
    raw-buffer live-slot gate by ``nnz``.
    """
    ids = jnp.where(_live_slots(seg, sorted), seg.lo, num_cols)
    out = sr.segment_add(seg.val, ids, num_cols + 1)
    return out[:num_cols]


def spmv(seg: AssocSegment, x: Array, num_rows: int,
         sr: Semiring = sr_mod.PLUS_TIMES, sorted: bool = True) -> Array:
    """y = A (.) x under the semiring: y[r] = add_c mul(A[r,c], x[c]).

    This is the paper's Fig 1 graph operation (neighbors of a vertex) when x
    is an indicator vector.  ``sorted=False`` admits a RAW buffer (lazy
    layer-0 append buffer), live slots gated by ``nnz`` — see
    ``reduce_rows``.
    """
    zero = sr_mod.integer_zero(sr, seg.dtype)
    valid = _live_slots(seg, sorted)
    gathered = x[jnp.clip(seg.lo, 0, x.shape[0] - 1)]
    prod = jnp.where(valid, sr.mul(seg.val, gathered.astype(seg.dtype)), zero)
    ids = jnp.where(valid, seg.hi, num_rows)
    return sr.segment_add(prod, ids, num_rows + 1, sorted=sorted)[:num_rows]


def spmv_t(seg: AssocSegment, x: Array, num_cols: int,
           sr: Semiring = sr_mod.PLUS_TIMES, sorted: bool = True) -> Array:
    """y = A' (.) x under the semiring: y[c] = add_r mul(A[r,c], x[r]).

    The transpose contraction — with ``spmv`` it composes the A'(Ax)
    correlation step (A'A applied to a vector) WITHOUT materializing A'A
    or even the merged A: the streaming query engine sums the per-layer
    contractions.  ``lo`` is the minor sort key, so the segment ids never
    earn the ``indices_are_sorted`` hint; ``sorted=False`` marks a RAW
    buffer input and gates live slots by ``nnz`` like ``spmv`` — the
    raw-buffer treatment it was missing until PR 5.
    """
    zero = sr_mod.integer_zero(sr, seg.dtype)
    valid = _live_slots(seg, sorted)
    gathered = x[jnp.clip(seg.hi, 0, x.shape[0] - 1)]
    prod = jnp.where(valid, sr.mul(seg.val, gathered.astype(seg.dtype)), zero)
    ids = jnp.where(valid, seg.lo, num_cols)
    return sr.segment_add(prod, ids, num_cols + 1)[:num_cols]


def to_dense(seg: AssocSegment, num_rows: int, num_cols: int,
             sr: Semiring = sr_mod.PLUS_TIMES, sorted: bool = True) -> Array:
    """Materialize the segment densely.  ``sorted=False`` marks a RAW buffer
    and gates live slots by ``nnz`` instead of trusting the sentinel tail
    (the PR 5 dirty-tail class — see CONTRACTS)."""
    zero = sr_mod.integer_zero(sr, seg.dtype)
    dense = jnp.full((num_rows, num_cols), zero, seg.dtype)
    valid = _live_slots(seg, sorted)
    r = jnp.where(valid, seg.hi, 0)
    c = jnp.where(valid, seg.lo, 0)
    v = jnp.where(valid, seg.val, zero)
    # Keys are unique in canonical form -> combine with sr.add against zero
    # base is a plain set; use add to stay correct for non-canonical input.
    if sr.name == "plus.times":
        return dense.at[r, c].add(v)
    return dense.at[r, c].max(v) if sr.name in ("max.plus", "max.min") \
        else dense.at[r, c].min(v)


def total(seg: AssocSegment, sr: Semiring = sr_mod.PLUS_TIMES,
          sorted: bool = True) -> Array:
    """Reduce every live value with ``sr.add``.  ``sorted=False`` marks a
    RAW buffer and gates live slots by ``nnz`` instead of trusting the
    sentinel tail (see CONTRACTS)."""
    zero = sr_mod.integer_zero(sr, seg.dtype)
    vals = jnp.where(_live_slots(seg, sorted), seg.val, zero)
    if sr.name == "plus.times":
        return jnp.sum(vals)
    return jnp.max(vals) if sr.name in ("max.plus", "max.min") else jnp.min(vals)
