"""Hierarchical associative arrays (paper Fig 2).

Layers A_0 .. A_L with cut thresholds c_0 < c_1 < ... < c_L.  Block updates
are semiring-merged into A_0 (the smallest array, sized for the fastest
memory — VMEM on TPU).  After each update the spill cascade runs bottom-up:
if nnz(A_i) > c_i then A_i is merged into A_{i+1} and cleared.  Queries merge
every layer.  Cuts trade update cost against query cost; they are config
knobs swept by benchmarks/bench_cut_sweep.py.

Capacity discipline (static shapes under jit):
    C_0 = c_0 + block_size
    C_i = c_i + C_{i-1}            (a spill can deposit at most C_{i-1})
so no merge can arithmetically overflow except at the last layer, where an
``overflow`` counter records dropped entries (the driver treats a non-zero
counter as a snapshot-to-store event).

The structure is a pytree: `vmap` gives per-device instance batches and
`shard_map` places instance groups on devices (core/distributed.py), matching
the paper's 34,000 share-nothing instances.

The single-sort fused cascade (``fused=True``) is the production default for
``update``, ``flush`` and ``query_all``: the spill chain / drain / query is
planned with scalar nnz arithmetic and executed as ONE canonicalization
(``assoc.merge_many``).  The per-layer pairwise path stays available behind
``fused=False`` as the reference oracle (tests/test_fused_cascade.py).

Instance batching: a vmapped ``lax.switch`` lowers to select-over-all-
branches, so the per-depth branches of the fused cascade would all execute
for every instance on every step.  ``batch_mode="branchfree"`` executes the
planned depth with ZERO control flow instead — one fixed-shape masked
``merge_many`` (``_fused_execute_planned``) whose participating layers are
gated by ``assoc.gate_segment`` — and ``core.stream.ingest_instances``
groups whole instance batches by planned depth on top (``batch_mode=
"grouped"``): the all-append cohort pays no sort at all and each deeper
cohort drains one member at a time, so a lone deep instance never drags
the fleet into its merge (tests/test_batched_ingest.py).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro import stages
from repro.analysis import contracts
from repro.core import assoc
from repro.core import semiring as sr_mod
from repro.core.assoc import AssocSegment
from repro.core.semiring import Semiring

Array = jax.Array


def layer_capacities(cuts: Tuple[int, ...], block_size: int) -> Tuple[int, ...]:
    caps = []
    prev = block_size
    for c in cuts:
        caps.append(c + prev)
        prev = caps[-1]
    return tuple(caps)


def merge_width(caps: Tuple[int, ...], block: int, depth: int) -> int:
    """Slots the fused cascade's one merge sorts for a depth-``depth`` plan,
    over layers of capacities ``caps`` (``layer_capacities``) fed
    ``block``-entry blocks: the incoming block plus every layer buffer in
    [0, depth] (with ``lazy_l0`` layer 0 enters as a raw run of the same
    width), block + C_0 + ... + C_depth."""
    return block + sum(caps[:depth + 1])


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HierAssoc:
    """Hierarchical associative array state (functional)."""

    layers: Tuple[AssocSegment, ...]
    spills: Array        # int32[L]  cumulative spill events per layer
    overflow: Array      # int32     unique entries dropped at the last layer
    # 64-bit raw-update counter as a (hi, lo) word pair: the paper's fleets
    # ingest 1.9e9 updates/s, so a single int32 counter wraps in about one
    # second.  int64 is unavailable without jax_enable_x64, so exactness
    # comes from uint32 wraparound carry detection (``_bump_counter``) —
    # ``exact_update_count`` reassembles the true total on the host.
    n_updates: Array     # uint32   low word of the update counter
    n_updates_hi: Array  # int32    high word (counts 2**32 carries)
    cuts: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True))

    @property
    def num_layers(self) -> int:
        return len(self.layers)

    @property
    def capacities(self) -> Tuple[int, ...]:
        return tuple(l.capacity for l in self.layers)

    def nnz_per_layer(self) -> Array:
        return jnp.stack([l.nnz for l in self.layers])


def create(cuts: Tuple[int, ...], block_size: int, dtype=jnp.float32,
           sr: Semiring = sr_mod.PLUS_TIMES) -> HierAssoc:
    if list(cuts) != sorted(cuts) or len(set(cuts)) != len(cuts):
        raise ValueError(f"cuts must be strictly increasing, got {cuts}")
    caps = layer_capacities(cuts, block_size)
    return HierAssoc(
        layers=tuple(assoc.empty(c, dtype, sr) for c in caps),
        spills=jnp.zeros((len(cuts),), jnp.int32),
        overflow=jnp.zeros((), jnp.int32),
        n_updates=jnp.zeros((), jnp.uint32),
        n_updates_hi=jnp.zeros((), jnp.int32),
        cuts=tuple(cuts),
    )


def _bump_counter(lo: Array, hi: Array, n: Array) -> Tuple[Array, Array]:
    """Add ``n`` raw updates to the (hi, lo) counter words.

    uint32 addition wraps; a wrap happened iff the new low word is smaller
    than the old one, which carries exactly one 2**32 into the high word.
    Exact for ANY addend below 2**32 — block counts on the update path,
    but also a whole instance's low word when elastic rebalance folds two
    counters.  64-bit-exact counting without int64 (jax_enable_x64 is off
    by default and flipping it globally changes dtype semantics repo-wide).
    """
    new_lo = lo + n.astype(jnp.uint32)
    new_hi = hi + (new_lo < lo).astype(jnp.int32)
    return new_lo, new_hi


def exact_update_count(h: HierAssoc) -> int:
    """Host-side exact 64-bit total of the update counter words; sums over
    any leading instance axes, so it works on vmapped fleet states too."""
    import numpy as np
    lo = np.asarray(jax.device_get(h.n_updates), np.int64)
    hi = np.asarray(jax.device_get(h.n_updates_hi), np.int64)
    return int(lo.sum() + (hi.sum() << np.int64(32)))


def metrics_snapshot(h: HierAssoc) -> dict:
    """Fleet observability sample: the whole ``[I, …]`` (or single) state
    reduced to a handful of scalars/vectors in ONE dispatch.

    Everything is computed on device — per-layer nnz totals and mean
    occupancy, cumulative spills, overflow, a depth histogram (instances
    per deepest-non-empty layer; bin 0 = empty), and the exact update
    counter as (hi, lo) words (uint32 prefix-sum wrap detection, same
    carry discipline as ``_bump_counter`` — no int64, J005-clean).  The
    host transfer happens in the caller (``obs.metrics.fleet_sample``)
    at the sampling boundary, never via a callback inside traced code
    (J004).  Knob-free by construction: the signature pins geometry only,
    so every semiring/fused/lazy variant of a fleet shares one compiled
    snapshot program.
    """
    sig = stages.signature_for_state(h)
    return metrics_snapshot_wrapped(sig)(h)


def metrics_snapshot_wrapped(sig: stages.Signature) -> stages.Wrapped:
    """Keyed snapshot program for one hierarchy geometry — registered in
    ``stages.fleet_jobs`` so tracekit audits/budgets it like any
    production entry."""
    def run(h):
        return _metrics_snapshot_body(h)

    return stages.wrap(run, "hier.metrics_snapshot", sig)


def _metrics_snapshot_body(h: HierAssoc) -> dict:
    num_layers = h.num_layers
    nnz = [l.nnz for l in h.layers]          # each [I, ...] or scalar
    nnz_total = jnp.stack([jnp.sum(n).astype(jnp.int32) for n in nnz])
    occupancy = jnp.stack([jnp.mean(n.astype(jnp.float32)) / c
                           for n, c in zip(nnz, h.capacities)])
    # per-instance depth: 1 + deepest layer holding data (0 = empty)
    depth = jnp.zeros(jnp.shape(nnz[0]), jnp.int32)
    for i, n in enumerate(nnz):
        depth = jnp.where(n > 0, jnp.int32(i + 1), depth)
    depth_hist = jnp.zeros((num_layers + 1,), jnp.int32) \
        .at[jnp.reshape(depth, (-1,))].add(1)
    spills = jnp.sum(jnp.reshape(h.spills, (-1, len(h.cuts))), axis=0)
    # exact fleet update total without int64: uint32 prefix sum of the low
    # words wraps at most once per step, and each wrap is one 2**32 carry
    lo = jnp.reshape(h.n_updates, (-1,))
    csum = jnp.cumsum(lo)
    carries = jnp.sum((csum[1:] < csum[:-1]).astype(jnp.int32))
    return dict(
        nnz=nnz_total,
        occupancy=occupancy,
        depth_hist=depth_hist,
        spills=spills,
        overflow=jnp.sum(h.overflow).astype(jnp.int32),
        updates_lo=csum[-1],
        updates_hi=jnp.sum(h.n_updates_hi).astype(jnp.int32) + carries,
    )


def _merge(a, b, cap, sr, use_kernel):
    if use_kernel:
        return assoc.merge_kernel(a, b, cap, sr)
    return assoc.merge(a, b, cap, sr)


def _spill(src: AssocSegment, dst: AssocSegment, sr: Semiring,
           use_kernel: bool = False, src_canonical: bool = True
           ) -> Tuple[AssocSegment, AssocSegment, Array]:
    if src_canonical:
        merged, ovf = _merge(dst, src, dst.capacity, sr, use_kernel)
    else:
        # src is a lazy append buffer (unsorted, duplicated): the pairwise
        # bitonic kernel requires canonical inputs, so route through the
        # multi-way merge, which sorts the raw side first.
        merged, ovf = assoc.merge_many((dst,), src.hi, src.lo, src.val,
                                       out_capacity=dst.capacity, sr=sr,
                                       use_kernel=use_kernel)
    return assoc.clear(src, sr), merged, ovf


def _cascade(h: HierAssoc, sr: Semiring, use_kernel: bool = False,
             lazy_l0: bool = False) -> HierAssoc:
    layers = list(h.layers)
    spills = h.spills
    overflow = h.overflow
    for i in range(len(layers) - 1):
        src, dst = layers[i], layers[i + 1]
        src_canonical = not (lazy_l0 and i == 0)

        def do_spill(src=src, dst=dst, src_canonical=src_canonical):
            new_src, new_dst, ovf = _spill(src, dst, sr, use_kernel,
                                           src_canonical)
            return new_src, new_dst, jnp.int32(1), ovf

        def no_spill(src=src, dst=dst):
            return src, dst, jnp.int32(0), jnp.int32(0)

        new_src, new_dst, spilled, ovf = jax.lax.cond(
            src.nnz > h.cuts[i], do_spill, no_spill)
        layers[i], layers[i + 1] = new_src, new_dst
        spills = spills.at[i].add(spilled)
        overflow = overflow + ovf
    # Last layer has no spill target; flag pressure past its cut.
    last = layers[-1]
    spills = spills.at[-1].add(
        (last.nnz > h.cuts[-1]).astype(jnp.int32))
    return dataclasses.replace(
        h, layers=tuple(layers), spills=spills, overflow=overflow)


def _lazy_append(l0: AssocSegment, hi: Array, lo: Array, val: Array,
                 n_live: Array | None = None) -> Tuple[AssocSegment, Array]:
    """Append a block into the layer-0 buffer (LSM memtable discipline).

    ``n_live`` is the number of potentially-live slots in the block's prefix
    (``sum(mask)`` for a compacted masked block, ``nnz`` for a canonical
    one); the buffer's nnz advances by that count, not by the physical block
    width, so sparse blocks stop inflating occupancy.  The block's sentinel
    tail still gets written, but the next append starts at the new nnz and
    overwrites it — every slot past nnz stays sentinel.

    The clamp keeps the write in-bounds, but when nnz > capacity - block it
    lands the block on top of live buffer slots [start, nnz).  Those entries
    are destroyed, not merged — the returned ``clobbered`` count (an upper
    bound on unique keys lost, consistent with slot-counting nnz) must be
    added to overflow.  Cascade planning keeps this at zero in normal
    operation.
    """
    b = hi.shape[-1]
    if n_live is None:
        n_live = jnp.int32(b)
    start = jnp.minimum(l0.nnz, l0.capacity - b)
    clobbered = jnp.maximum(l0.nnz - start, 0).astype(jnp.int32)
    layer0 = AssocSegment(
        hi=jax.lax.dynamic_update_slice(l0.hi, hi, (start,)),
        lo=jax.lax.dynamic_update_slice(l0.lo, lo, (start,)),
        val=jax.lax.dynamic_update_slice(
            l0.val, val.astype(l0.val.dtype), (start,)),
        nnz=start + jnp.int32(n_live))
    return layer0, clobbered


def _compact_masked(rows: Array, cols: Array, vals: Array, mask: Array
                    ) -> Tuple[Array, Array, Array]:
    """Stable-partition a sentinel-blanked masked block: live entries to the
    front, masked-out sentinels to the tail.  One O(B) scatter — no sort —
    so the lazy-append fast path stays sort-free.  The destination indices
    form a permutation (live slots [0, sum(mask)), dead slots from the back)
    so every slot is written exactly once."""
    mask = mask.astype(bool)        # callers may pass 0/1 ints; ~ needs bool
    b = rows.shape[-1]
    live_pos = jnp.cumsum(mask) - 1
    dead_pos = b - jnp.cumsum(~mask)
    dest = jnp.where(mask, live_pos, dead_pos).astype(jnp.int32)
    scatter = lambda x: jnp.zeros_like(x).at[dest].set(x)
    return scatter(rows), scatter(cols), scatter(vals)


def _plan_spill_depth(h: HierAssoc, block_slots) -> Array:
    """Pure scalar arithmetic on per-layer nnz counters: the fused cascade's
    destination layer for an incoming block of ``block_slots`` entries
    (a Python int for a dense block, or a traced scalar — ``sum(mask)`` —
    for a masked one, so sparse blocks are planned at their true slot cost
    instead of the block capacity).

    Layer 0 spills iff its slots plus the block exceed c_0; layer i spills
    iff every layer above it spills AND the accumulated slot count exceeds
    c_i.  ``nnz`` is a slot count (an upper bound on unique keys), so the
    plan never under-provisions: the chosen destination d satisfies
    occupancy_d <= c_d <= C_d for d < L-1, making overflow possible only at
    the last layer.  No array data is touched — this is the "plan before
    moving" half of the single-sort cascade.
    """
    occupancy = jnp.int32(block_slots)
    depth = jnp.int32(0)
    chain = jnp.bool_(True)
    for i in range(h.num_layers - 1):
        occupancy = occupancy + h.layers[i].nnz
        spill_i = chain & (occupancy > h.cuts[i])
        depth = jnp.where(spill_i, jnp.int32(i + 1), depth)
        chain = spill_i
    return depth


def _fused_execute_planned(h: HierAssoc, rows: Array, cols: Array,
                           vals: Array, n_live: Array, depth: Array, *,
                           up_to: int, sr: Semiring, use_kernel: bool,
                           lazy_l0: bool, may_not_fit: bool = False
                           ) -> HierAssoc:
    """Divergence-free fused-cascade executor for a planned block.

    Serves every spill depth in [0, ``up_to``] with ONE fixed-shape
    ``assoc.merge_many``: layer i's buffer participates iff ``i <= depth``
    (``assoc.gate_segment`` blanks non-participants to all-sentinel runs,
    which are still canonical), the canonical result is scattered back to
    the planned destination layer with ``jnp.where`` selects, and shallower
    layers are cleared.  No ``lax.switch``/``lax.cond`` anywhere on the data
    path, so under ``vmap`` each instance pays exactly one merge — the
    batched switch lowers to select-over-all-branches and charged every
    instance every depth's merge (EXPERIMENTS.md §Multi-instance scaling).

    ``up_to`` bounds the merge width statically: the batched ingest layouts
    (core/stream.py) call this with ``up_to = max(planned depths)``
    (bucketed) or with each cohort's own depth (grouped, one member at a
    time) so a shallow cohort never touches deep-layer buffers;
    ``up_to = L - 1`` is the general single-call form.  ``depth <= up_to``
    is the caller's contract.  With ``lazy_l0`` and a depth-0 plan the lazy append is still
    taken (selected per instance), and when ``up_to == 0`` with a
    statically-fitting block the merge is skipped entirely — the all-append
    cohort pays zero sorts.

    ``rows``/``cols``/``vals`` must already be sentinel-masked, compacted
    and dtype-cast (``_prepare_block``); ``may_not_fit`` marks the one shape
    (masked block wider than the creation block size) whose append can
    physically clobber, needing the dynamic fit check.
    """
    B = rows.shape[-1]
    caps = h.capacities
    L = h.num_layers
    vdtype = h.layers[0].dtype
    zero = sr_mod.integer_zero(sr, vdtype)
    lazy_append = lazy_l0 and B <= h.cuts[0]

    if lazy_append:
        l0_app, clobbered = _lazy_append(h.layers[0], rows, cols, vals,
                                         n_live=n_live)
        fits = (h.layers[0].nnz + B <= caps[0]) if may_not_fit \
            else jnp.bool_(True)
        take_append = (depth == 0) & fits
        if up_to == 0 and not may_not_fit:
            # whole cohort appends: zero sorts, the LSM fast path.
            new_layers = (l0_app,) + h.layers[1:]
            spills = h.spills.at[-1].add(
                (new_layers[-1].nnz > h.cuts[-1]).astype(jnp.int32))
            lo, hi = _bump_counter(h.n_updates, h.n_updates_hi, n_live)
            return dataclasses.replace(
                h, layers=new_layers, spills=spills,
                overflow=h.overflow + clobbered,
                n_updates=lo, n_updates_hi=hi)

    # The ONE masked merge: raw block (+ lazy layer-0 buffer) plus every
    # gated layer buffer in [first, up_to].
    if lazy_l0:
        l0 = h.layers[0]
        raw = (jnp.concatenate([rows, l0.hi]),
               jnp.concatenate([cols, l0.lo]),
               jnp.concatenate([vals, l0.val]))
        first = 1
    else:
        raw = (rows, cols, vals)
        first = 0
    runs = tuple(
        h.layers[i] if i == 0          # depth >= 0 always: no gate needed
        else assoc.gate_segment(h.layers[i], depth >= i, sr)
        for i in range(first, up_to + 1))
    width = merge_width(caps, B, up_to)
    seg, _ = assoc.merge_many(runs, *raw, out_capacity=width, sr=sr,
                              use_kernel=use_kernel)
    n_unique = seg.nnz
    cap_d = jnp.asarray(caps[:up_to + 1], jnp.int32)[depth]
    ovf = jnp.maximum(n_unique - cap_d, 0).astype(jnp.int32)

    new_layers = []
    for i in range(L):
        li = h.layers[i]
        if i > up_to:
            new_layers.append(li)
            continue
        is_dest = depth == jnp.int32(i)
        consumed = depth > jnp.int32(i)
        new_layers.append(AssocSegment(
            hi=jnp.where(is_dest, seg.hi[:caps[i]],
                         jnp.where(consumed, assoc.SENTINEL, li.hi)),
            lo=jnp.where(is_dest, seg.lo[:caps[i]],
                         jnp.where(consumed, assoc.SENTINEL, li.lo)),
            val=jnp.where(is_dest, seg.val[:caps[i]],
                          jnp.where(consumed, zero, li.val)),
            nnz=jnp.where(is_dest, jnp.minimum(n_unique, jnp.int32(caps[i])),
                          jnp.where(consumed, 0, li.nnz))))
    if lazy_append:
        new_layers[0] = AssocSegment(
            hi=jnp.where(take_append, l0_app.hi, new_layers[0].hi),
            lo=jnp.where(take_append, l0_app.lo, new_layers[0].lo),
            val=jnp.where(take_append, l0_app.val, new_layers[0].val),
            nnz=jnp.where(take_append, l0_app.nnz, new_layers[0].nnz))
        ovf = jnp.where(take_append, clobbered, ovf)
    spills = h.spills \
        + (jnp.arange(L, dtype=jnp.int32) < depth).astype(jnp.int32)
    spills = spills.at[-1].add(
        (new_layers[-1].nnz > h.cuts[-1]).astype(jnp.int32))
    lo, hi = _bump_counter(h.n_updates, h.n_updates_hi, n_live)
    return dataclasses.replace(
        h, layers=tuple(new_layers), spills=spills,
        overflow=h.overflow + ovf, n_updates=lo, n_updates_hi=hi)


def _prepare_block(h: HierAssoc, rows: Array, cols: Array, vals: Array,
                   mask: Array | None, sr: Semiring
                   ) -> Tuple[Array, Array, Array, Array]:
    """Shared fused-path prologue: int32/dtype-cast, sentinel-blank masked
    entries, compact a masked block front-first and return its live-slot
    count (``sum(mask)`` — the mask-aware occupancy the planner charges)."""
    vdtype = h.layers[0].dtype
    rows, cols, vals = assoc.mask_coo(rows, cols, vals.astype(vdtype), mask,
                                      sr)
    if mask is None:
        n_live = jnp.int32(rows.shape[-1])
    else:
        n_live = jnp.sum(mask).astype(jnp.int32)
        rows, cols, vals = _compact_masked(rows, cols, vals, mask)
    return rows, cols, vals, n_live


def _update_fused(h: HierAssoc, rows: Array, cols: Array, vals: Array,
                  mask: Array | None, sr: Semiring, use_kernel: bool,
                  lazy_l0: bool, batch_mode: str = "switch") -> HierAssoc:
    """Single-sort fused spill cascade (tentpole path).

    The layered path pays up to L+1 canonicalization sorts per block (block
    dedup, layer-0 merge, one per cascading spill) and re-sorts already-
    sorted layer buffers at every level.  Here the spill chain is *planned*
    first (scalar arithmetic on nnz counters and cuts), then a single
    ``lax.switch`` branch concatenates the raw COO block with every spilling
    layer's buffer and runs ONE canonicalization into the deepest
    destination layer.  With ``lazy_l0`` the no-spill branch degenerates to
    a pure append — zero sorts for the common case, the LSM memtable
    discipline fused with the paper's hierarchy.

    ``batch_mode`` picks the execution strategy for the planned depth:
    ``"switch"`` (default) materializes one ``lax.switch`` branch per depth
    — optimal single-instance, but a *vmapped* switch lowers to select-over-
    all-branches, charging every instance every depth's merge.
    ``"branchfree"`` routes through ``_fused_execute_planned``: one
    fixed-shape masked merge serves all depths, so the vmapped layout pays
    one merge per instance.  Instance-batched callers should prefer
    ``core.stream.ingest_instances(batch_mode="grouped")``, which
    additionally skips the merge for append cohorts and sizes each deeper
    cohort member's merge to its own planned depth.

    Masked blocks are planned at their live-slot count ``sum(mask)`` (not
    the block capacity B) and compacted front-first with one O(B) scatter,
    so a sparse block costs only its live entries in occupancy — the old
    capacity-based plan over-spilled on every masked block.
    """
    B = rows.shape[-1]
    vdtype = h.layers[0].dtype
    rows, cols, vals, n_live = _prepare_block(h, rows, cols, vals, mask, sr)
    depth = _plan_spill_depth(h, n_live)
    caps = h.capacities
    L = h.num_layers

    # The mask-aware plan admits nnz + n_live <= c_0, but the append
    # physically writes B slots: only a MASKED block wider than the
    # creation block_size (B > C_0 - c_0) can reach past capacity and
    # clobber live entries — for every other shape the plan bound implies
    # nnz + B <= C_0, so the fit check is statically true and must not be
    # traced (a vmapped lax.cond executes both branches, which would bolt
    # a full-width merge onto every no-spill append).
    append_always_fits = mask is None or B <= caps[0] - h.cuts[0]

    if batch_mode == "branchfree":
        return _fused_execute_planned(
            h, rows, cols, vals, n_live, depth, up_to=L - 1, sr=sr,
            use_kernel=use_kernel, lazy_l0=lazy_l0,
            may_not_fit=not append_always_fits)

    # A block physically wider than c_0 cannot use the append fast path
    # (its fixed-size slice would not fit layer 0) even when the mask-aware
    # plan lands on depth 0 — branch 0 then runs the canonicalizing merge
    # into layer 0 instead.
    lazy_append = lazy_l0 and B <= h.cuts[0]

    def merge_to_depth(d: int):
        if lazy_l0:
            # Layer 0 is an append buffer (unsorted); fold it into the
            # raw side so the kernel path sees true sorted runs only —
            # also for d == 0, where the buffer re-canonicalizes in place.
            l0 = h.layers[0]
            raw = (jnp.concatenate([rows, l0.hi]),
                   jnp.concatenate([cols, l0.lo]),
                   jnp.concatenate([vals, l0.val]))
            runs = h.layers[1:d + 1]
        else:
            raw = (rows, cols, vals)
            runs = h.layers[:d + 1]
        seg, ovf = assoc.merge_many(runs, *raw, out_capacity=caps[d],
                                    sr=sr, use_kernel=use_kernel)
        new_layers = tuple(assoc.empty(caps[i], vdtype, sr)
                           for i in range(d)) + (seg,) + h.layers[d + 1:]
        spills = h.spills.at[:d].add(1) if d else h.spills
        return new_layers, spills, ovf

    def make_branch(d: int):
        def run(_):
            if d == 0 and lazy_append:
                def append(_):
                    layer0, clobbered = _lazy_append(
                        h.layers[0], rows, cols, vals, n_live=n_live)
                    return (layer0,) + h.layers[1:], h.spills, clobbered

                if append_always_fits:
                    return append(None)
                fits = h.layers[0].nnz + B <= caps[0]
                return jax.lax.cond(fits, append,
                                    lambda _: merge_to_depth(0), None)
            return merge_to_depth(d)
        return run

    new_layers, spills, ovf = jax.lax.switch(
        depth, [make_branch(d) for d in range(L)], None)
    # Pressure flag for the spill-less last layer (same as the layered path).
    spills = spills.at[-1].add(
        (new_layers[-1].nnz > h.cuts[-1]).astype(jnp.int32))
    lo, hi = _bump_counter(h.n_updates, h.n_updates_hi, n_live)
    return dataclasses.replace(
        h,
        layers=new_layers,
        spills=spills,
        overflow=h.overflow + ovf,
        n_updates=lo,
        n_updates_hi=hi,
    )


def update(h: HierAssoc, rows: Array, cols: Array, vals: Array,
           mask: Array | None = None,
           sr: Semiring = sr_mod.PLUS_TIMES,
           use_kernel: bool = False,
           lazy_l0: bool = False,
           fused: bool = True,
           batch_mode: str = "switch") -> HierAssoc:
    """Block-update: semiring-add a COO block into the hierarchy (Fig 2).

    ``lazy_l0=True`` (beyond-paper optimization, EXPERIMENTS.md §Perf):
    layer 0 becomes an APPEND buffer — the incoming block is deduped and
    sorted (O(B log B)) but NOT re-merged with layer 0's contents
    (O((c0+B) log (c0+B)) saved per block); layer 0 is only canonicalized
    when the spill cascade or a query consumes it.  This is the LSM
    memtable discipline applied inside the paper's hierarchy.  ``nnz`` of
    layer 0 then counts occupied SLOTS (an upper bound on unique keys),
    which is exactly what the cut threshold compares against.  Restricted
    to plus.times: duplicate keys in the buffer must sum-combine.

    ``fused=True`` (the production default) routes through the single-sort
    fused spill cascade (``_update_fused``): one canonicalization per block
    instead of up to L+1.  ``fused=False`` keeps the per-layer reference
    cascade — the query-equivalent oracle the equivalence suite checks
    against.

    ``batch_mode`` (fused only): ``"switch"`` executes the planned depth as
    one ``lax.switch`` branch (best single-instance); ``"branchfree"``
    executes it as one masked fixed-shape merge with no control flow — the
    divergence-free form a ``vmap`` over instances needs, because a batched
    switch executes every branch.  Instance-batched ingest should use
    ``core.stream.ingest_instances(batch_mode="grouped")``, which adds
    batch-level depth-cohort grouping on top.
    """
    sig = stages.signature_for_state(
        h, sr=sr, use_kernel=use_kernel, lazy_l0=lazy_l0, fused=fused,
        batch_mode=batch_mode,
        allowed_batch_modes=("switch", "branchfree"))
    if contracts.enabled() and not stages.is_tracing(h, rows, cols, vals,
                                                     mask):
        err, out = update_wrapped(contracts.debug_signature(sig))(
            h, rows, cols, vals, mask)
        contracts.throw(err)
        return out
    return update_wrapped(sig)(h, rows, cols, vals, mask)


def update_wrapped(sig: stages.Signature) -> stages.Wrapped:
    """Keyed block-update program for one config signature (the staged
    front door ``update`` routes through; ``stages.precompile_fleet``
    warms it directly).

    A signature carrying ``contracts.DEBUG_EXTRA`` returns the checkified
    sanitizer build — same program plus contract checks on the input and
    output state and on every internal merge; it returns ``(err, out)``
    and keys a SEPARATE cache entry, so the production key's program never
    contains a check.
    """
    sr = sr_mod.get(sig.sr)
    use_kernel, lazy_l0 = sig.use_kernel, sig.lazy_l0

    def run(h, rows, cols, vals, mask):
        if sig.fused:
            return _update_fused(h, rows, cols, vals, mask, sr, use_kernel,
                                 lazy_l0, batch_mode=sig.batch_mode)
        merged, ovf0 = assoc.from_coo(rows, cols, vals, rows.shape[-1], sr,
                                      mask=mask)
        if lazy_l0:
            # merged is canonical (live prefix, sentinel tail): advance the
            # buffer by its unique count, not the physical block width.
            layer0, ovf1 = _lazy_append(h.layers[0], merged.hi, merged.lo,
                                        merged.val, n_live=merged.nnz)
        else:
            layer0, ovf1 = _merge(h.layers[0], merged,
                                  h.layers[0].capacity, sr, use_kernel)
        n_new = rows.shape[-1] if mask is None else jnp.sum(mask)
        lo, hi = _bump_counter(h.n_updates, h.n_updates_hi, jnp.int32(n_new))
        h2 = dataclasses.replace(
            h,
            layers=(layer0,) + h.layers[1:],
            overflow=h.overflow + ovf0 + ovf1,
            n_updates=lo,
            n_updates_hi=hi,
        )
        return _cascade(h2, sr, use_kernel, lazy_l0)

    if contracts.sig_debug(sig):
        def checked(h, rows, cols, vals, mask):
            contracts.check_hier(h, sr, l0_sorted=not lazy_l0,
                                 name="hier.update input")
            with contracts.activate():
                out = run(h, rows, cols, vals, mask)
            contracts.check_hier(out, sr, l0_sorted=not lazy_l0,
                                 name="hier.update output")
            return out
        return stages.wrap(contracts.checkified(checked), "hier.update", sig)
    return stages.wrap(run, "hier.update", sig)


def query_all(h: HierAssoc, sr: Semiring = sr_mod.PLUS_TIMES,
              use_kernel: bool = False,
              lazy_l0: bool = False,
              fused: bool = True) -> AssocSegment:
    """Sum all layers into one canonical segment (paper: query path).

    ``fused=True`` (default) runs ONE ``assoc.merge_many`` canonicalization
    over every layer — layer 0's buffer rides the raw side, which is correct
    whether it is a lazy append buffer or canonical (sorted data is a valid
    unsorted input) — instead of L-1 pairwise merges at full
    ``sum(capacities)`` width each.  ``fused=False`` keeps the pairwise
    reference path; it needs ``lazy_l0=True`` when the hierarchy is operated
    with lazy layer-0 appends so the buffer is merged as raw data.
    """
    sig = stages.signature_for_state(h, sr=sr, use_kernel=use_kernel,
                                     lazy_l0=lazy_l0, fused=fused)
    return query_all_wrapped(sig)(h)


def query_all_wrapped(sig: stages.Signature) -> stages.Wrapped:
    """Keyed merge-all-layers program for one config signature."""
    sr = sr_mod.get(sig.sr)
    use_kernel, lazy_l0, fused = sig.use_kernel, sig.lazy_l0, sig.fused

    def run(h):
        return _query_all_body(h, sr, use_kernel, lazy_l0, fused)

    return stages.wrap(run, "hier.query_all", sig)


def _query_all_body(h: HierAssoc, sr: Semiring, use_kernel: bool,
                    lazy_l0: bool, fused: bool) -> AssocSegment:
    cap = sum(h.capacities)
    l0 = h.layers[0]
    if fused:
        # No single-layer shortcut: layer 0 may be a lazy append buffer and
        # the caller is not required to say so on the fused path — always
        # canonicalize, so the result is correct for either discipline.
        return assoc.merge_many(h.layers[1:], l0.hi, l0.lo, l0.val,
                                out_capacity=cap, sr=sr,
                                use_kernel=use_kernel)[0]
    if h.num_layers == 1:
        if lazy_l0:
            # The append buffer is unsorted and duplicated; canonicalize it
            # even with no other layer to merge against.
            acc, _ = assoc.merge_many((), l0.hi, l0.lo, l0.val,
                                      out_capacity=cap, sr=sr,
                                      use_kernel=use_kernel)
            return acc
        return l0
    acc = h.layers[-1]
    for layer in reversed(h.layers[1:-1]):
        acc, _ = _merge(acc, layer, cap, sr, use_kernel)
    if lazy_l0:
        acc, _ = assoc.merge_many((acc,), l0.hi, l0.lo, l0.val,
                                  out_capacity=cap, sr=sr,
                                  use_kernel=use_kernel)
    else:
        acc, _ = _merge(acc, l0, cap, sr, use_kernel)
    return acc


def lookup(h: HierAssoc, row, col, sr: Semiring = sr_mod.PLUS_TIMES,
           use_kernel: bool = False) -> Array:
    """Point query without materializing the merged array.

    ``row``/``col`` may be scalars or [Q] vectors: the batched query
    engine (repro/query/engine.py) answers the whole vector in one jit
    dispatch — per-layer lexicographic binary search over the canonical
    runs plus a raw scan/canonicalization of the layer-0 buffer, so it is
    correct whether layer 0 is canonical or a lazy append buffer.  The old
    per-layer O(L*C)-per-query scan survives as ``lookup_layered``, the
    oracle tests/test_query_engine.py compares against.
    """
    from repro.query import engine
    return engine.lookup(h, row, col, sr=sr, use_kernel=use_kernel)


def lookup_layered(h: HierAssoc, row, col,
                   sr: Semiring = sr_mod.PLUS_TIMES) -> Array:
    """Reference point query: full per-layer scans, scalar row/col.

    Kept as the engine's oracle (and for lazy layer-0 buffers it is
    trivially correct: ``assoc.lookup`` under plus.times sums every
    matching slot, duplicates included).  Layer 0 is queried under the
    raw-buffer contract (``sorted=False`` — live slots gated by ``nnz``),
    which is valid whether it is a lazy append buffer or canonical; deeper
    layers are always canonical.
    """
    vals = [assoc.lookup(l, row, col, sr, sorted=i > 0)
            for i, l in enumerate(h.layers)]
    out = vals[0]
    for v in vals[1:]:
        out = sr.add(out, v)
    return out


def total_nnz_upper_bound(h: HierAssoc) -> Array:
    """Sum of per-layer nnz (keys may repeat across layers)."""
    return jnp.sum(h.nnz_per_layer())


def _flush_fused(h: HierAssoc, sr: Semiring, use_kernel: bool) -> HierAssoc:
    """Fused drain: ONE ``assoc.merge_many`` canonicalization folds every
    layer into the last one (layer 0's buffer rides the raw side, so a lazy
    append buffer needs no special-casing), instead of L-1 pairwise merges
    at increasing widths.  Spill accounting matches the layered drain: one
    event per non-empty source layer, plus the last-layer pressure flag."""
    caps = h.capacities
    l0 = h.layers[0]
    seg, ovf = assoc.merge_many(h.layers[1:], l0.hi, l0.lo, l0.val,
                                out_capacity=caps[-1], sr=sr,
                                use_kernel=use_kernel)
    spills = h.spills
    # Match the layered drain's accounting: layer i records a spill event
    # when any data exists in layers [0, i] — the pairwise drain cascades
    # upstream contents THROUGH every intermediate layer, so emptiness of
    # layer i alone does not suppress its event.
    cum_nnz = jnp.int32(0)
    for i in range(h.num_layers - 1):
        cum_nnz = cum_nnz + h.layers[i].nnz
        spills = spills.at[i].add((cum_nnz > 0).astype(jnp.int32))
    spills = spills.at[-1].add((seg.nnz > h.cuts[-1]).astype(jnp.int32))
    new_layers = tuple(assoc.empty(caps[i], l0.dtype, sr)
                       for i in range(h.num_layers - 1)) + (seg,)
    return dataclasses.replace(h, layers=new_layers, spills=spills,
                               overflow=h.overflow + ovf)


def flush(h: HierAssoc, sr: Semiring = sr_mod.PLUS_TIMES,
          use_kernel: bool = False, lazy_l0: bool = False,
          fused: bool = True) -> HierAssoc:
    """Force-spill every layer downward (checkpoint/drain path).

    ``fused=True`` (default) drains with a single canonicalization
    (``_flush_fused``); ``fused=False`` keeps the pairwise per-layer
    reference drain.  Both record the same spill telemetry as the update
    paths: a spill event per non-empty source layer and the ``spills[-1]``
    pressure bump when the drained last layer exceeds its cut.
    """
    sig = stages.signature_for_state(h, sr=sr, use_kernel=use_kernel,
                                     lazy_l0=lazy_l0, fused=fused)
    if contracts.enabled() and not stages.is_tracing(h):
        err, out = flush_wrapped(contracts.debug_signature(sig))(h)
        contracts.throw(err)
        return out
    return flush_wrapped(sig)(h)


def flush_wrapped(sig: stages.Signature) -> stages.Wrapped:
    """Keyed force-spill program for one config signature.  A signature
    carrying ``contracts.DEBUG_EXTRA`` returns the checkified sanitizer
    build (see ``update_wrapped``)."""
    sr = sr_mod.get(sig.sr)
    use_kernel, lazy_l0, fused = sig.use_kernel, sig.lazy_l0, sig.fused

    def run(h):
        return _flush_body(h, sr, use_kernel, lazy_l0, fused)

    if contracts.sig_debug(sig):
        def checked(h):
            contracts.check_hier(h, sr, l0_sorted=not lazy_l0,
                                 name="hier.flush input")
            with contracts.activate():
                out = run(h)
            # Every layer of a drained hierarchy is canonical, including
            # layer 0 (emptied), regardless of the append discipline.
            contracts.check_hier(out, sr, l0_sorted=True,
                                 name="hier.flush output")
            return out
        return stages.wrap(contracts.checkified(checked), "hier.flush", sig)
    return stages.wrap(run, "hier.flush", sig)


def _flush_body(h: HierAssoc, sr: Semiring, use_kernel: bool,
                lazy_l0: bool, fused: bool) -> HierAssoc:
    if fused:
        return _flush_fused(h, sr, use_kernel)
    layers = list(h.layers)
    spills = h.spills
    overflow = h.overflow
    for i in range(len(layers) - 1):
        moved = (layers[i].nnz > 0).astype(jnp.int32)
        new_src, new_dst, ovf = _spill(layers[i], layers[i + 1], sr,
                                       use_kernel,
                                       src_canonical=not (lazy_l0 and i == 0))
        layers[i], layers[i + 1] = new_src, new_dst
        spills = spills.at[i].add(moved)
        overflow = overflow + ovf
    # Last-layer pressure flag, same as _cascade and _update_fused record it
    # on the update path — without it spill telemetry drifts between the
    # update and drain paths.
    spills = spills.at[-1].add(
        (layers[-1].nnz > h.cuts[-1]).astype(jnp.int32))
    return dataclasses.replace(h, layers=tuple(layers), spills=spills,
                               overflow=overflow)
