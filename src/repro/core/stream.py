"""Streaming ingestion engine — the paper's measured workload loop (§III).

The benchmark workload is "1,000 sets of 100,000 entries" ingested per
instance.  StreamEngine runs that as a single ``lax.scan`` over update blocks
so the whole ingest compiles to one XLA program (no per-block dispatch
overhead — the TPU analogue of the paper's in-process update loop).

``chunk=T_inner`` pre-combines T_inner consecutive stream blocks into one
larger block per hierarchy update, so their dedup/merge happens in a single
sort — the same amortization as the paper's blocking of 100,000-entry sets,
one level up.  ``fused=True`` (the default) routes each block through the
single-sort fused spill cascade (core/hier.py); ``fused=False`` selects the
layered reference path (the equivalence oracle).

Instances: `ingest` is written for one hierarchy and one [T, B] block stream;
the production multi-instance layout is ``ingest_instances``.  Its default
``batch_mode="grouped"`` swaps the loop order to ``scan`` over time of a
BATCHED step: every instance's spill depth is planned first (scalar
arithmetic), then the step executes PER DEPTH COHORT — the depth-0 cohort
(the overwhelmingly common case) runs as a pure batched append scatter with
zero sorts, and each deeper cohort d drains through a dynamic-trip-count
loop that pays exactly one masked merge sized to layers [0, d] PER COHORT
MEMBER (``hier._fused_execute_planned`` on one instance at a time, reached
through a depth-ordered ``argsort`` index vector), skipped entirely when the
cohort is empty.  A step's cost is therefore sum_i W(depth_i) — one deep
instance costs ITS merge, not a fleet-wide one.  ``batch_mode="bucketed"``
is the PR-3 layout: one batch-level ``lax.switch`` on the *maximum* planned
depth, so a single deep instance drags every instance in the batch into a
merge sized to the deepest layer — optimal for synchronized fleets, and the
A/B baseline the desynchronized-fleet benchmark compares against
(EXPERIMENTS.md §Desynchronization matrix).  ``batch_mode="branchfree"``
keeps vmap-of-scan with the per-instance masked merge; ``batch_mode=
"switch"`` is the legacy vmapped ``lax.switch`` layout, which lowers to
select-over-all-branches and made the fused win vanish under vmap
(EXPERIMENTS.md §Multi-instance scaling).  ``core.distributed`` places
instance groups on devices; all modes stay collective-free on the update
path (the cohort loop's trip counts are per-device scalars).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro import stages
from repro.analysis import contracts
from repro.core import assoc, hier
from repro.core import semiring as sr_mod
from repro.core.hier import HierAssoc
from repro.core.semiring import Semiring

Array = jax.Array

# canonical knob domain lives in repro/stages.py (the shared signature
# canonicalizer); re-exported here for existing importers
BATCH_MODES = stages.BATCH_MODES


def _chunk_stream(rows: Array, cols: Array, vals: Array, chunk: int,
                  fused: bool, layer0_headroom: int):
    """Reshape a [..., T, B] stream to [..., T/chunk, chunk*B]."""
    T, B = rows.shape[-2], rows.shape[-1]
    if T % chunk:
        raise ValueError(f"stream length {T} not divisible by chunk "
                         f"{chunk}")
    if not fused and chunk * B > layer0_headroom:
        raise ValueError(
            f"chunk*B = {chunk * B} exceeds layer-0 headroom "
            f"{layer0_headroom}; use fused=True or a "
            f"hierarchy created with block_size >= {chunk * B}")
    shape = rows.shape[:-2] + (T // chunk, chunk * B)
    return rows.reshape(shape), cols.reshape(shape), vals.reshape(shape)


def _normalize_chunked_telemetry(telem: dict, chunk: int,
                                 time_axis: int = 0) -> dict:
    """Make telemetry comparable across ``chunk`` settings.

    The scan emits one snapshot per hierarchy UPDATE ([T/chunk] entries), so
    spill-rate curves from a chunk=4 run had 4x fewer points per input block
    than a chunk=1 run and could not be overlaid.  Normalize the standard
    keys to per-INPUT-block units (each update's snapshot repeated ``chunk``
    times — cumulative counters become step functions of the input-block
    axis, directly comparable) and keep the raw per-update view under
    ``telem["per_update"]``.  ``time_axis`` is 0 for single-instance
    telemetry and 1 for the instance-major [I, T, ...] batched layout.
    """
    if chunk <= 1:
        return telem
    out = {k: jnp.repeat(v, chunk, axis=time_axis) for k, v in telem.items()}
    out["per_update"] = telem
    return out


def ingest(h: HierAssoc, rows: Array, cols: Array, vals: Array,
           sr: Semiring = sr_mod.PLUS_TIMES,
           use_kernel: bool = False,
           lazy_l0: bool = False,
           fused: bool = True,
           chunk: int = 1,
           batch_mode: str = "switch",
           ) -> Tuple[HierAssoc, dict]:
    """Scan a [T, B] stream of update blocks into the hierarchy.

    ``chunk > 1`` reshapes the stream to [T/chunk, chunk*B]: chunk blocks
    enter the hierarchy as one update, pre-combined by the update's single
    canonicalization sort.  The layered path sizes layer 0 for the creation
    block size, so chunking beyond it requires ``fused=True`` (the fused
    planner provisions any incoming block against the whole cut stack).

    ``batch_mode`` selects the fused execution strategy per update
    (``"switch"`` default for this single-instance entry point,
    ``"branchfree"`` for callers that vmap this function directly —
    ``ingest_instances`` picks for you and additionally offers the batched
    ``"grouped"``/``"bucketed"`` layouts).

    Returns the final state plus per-step telemetry (layer-0 nnz and
    cumulative spill counts) used by the update-rate benchmarks to verify
    the paper's claim that most updates never touch slow memory.  Telemetry
    is reported in per-INPUT-block units regardless of ``chunk`` (the raw
    per-update view rides along under ``telem["per_update"]``), so spill
    curves from different chunk settings overlay correctly.
    """
    sig = stages.signature_for_state(
        h, sr=sr, use_kernel=use_kernel, lazy_l0=lazy_l0, fused=fused,
        chunk=chunk, batch_mode=batch_mode,
        allowed_batch_modes=("switch", "branchfree"))
    return _ingest_wrapped(sig)(h, rows, cols, vals)


def _ingest_wrapped(sig: stages.Signature) -> stages.Wrapped:
    """Keyed single-instance scan-ingest program for one config signature."""
    sr = sr_mod.get(sig.sr)

    def run(h, rows, cols, vals):
        if sig.chunk > 1:
            rows, cols, vals = _chunk_stream(
                rows, cols, vals, sig.chunk, sig.fused,
                h.layers[0].capacity - h.cuts[0])

        def step(state: HierAssoc, block):
            r, c, v = block
            new_state = hier.update(state, r, c, v, sr=sr,
                                    use_kernel=sig.use_kernel,
                                    lazy_l0=sig.lazy_l0, fused=sig.fused,
                                    batch_mode=sig.batch_mode)
            telemetry = dict(
                nnz0=new_state.layers[0].nnz,
                spills=new_state.spills,
                overflow=new_state.overflow,
            )
            return new_state, telemetry

        final, telem = jax.lax.scan(step, h, (rows, cols, vals))
        return final, _normalize_chunked_telemetry(telem, sig.chunk)

    return stages.wrap(run, "stream.ingest", sig)


def ingest_jit(cuts: Tuple[int, ...], block_size: int, dtype=jnp.float32,
               sr: Semiring = sr_mod.PLUS_TIMES, *,
               use_kernel: bool = False,
               lazy_l0: bool = False,
               fused: bool = True,
               chunk: int = 1,
               batch_mode: str = "switch"):
    """Build a staged (state, stream) -> (state, telemetry) ingest fn.

    ``cuts``/``block_size``/``dtype`` pin the hierarchy geometry the
    returned function is specialized to; knob validation routes through the
    shared ``stages.signature_of`` canonicalizer (one error message at
    every entry point) and mismatched states or streams fail fast at
    lower/trace time via ``stages.check_state`` instead of silently
    ingesting with the wrong configuration.
    """
    sig = stages.signature_of(
        cuts=cuts, block_size=block_size, dtype=dtype, sr=sr,
        use_kernel=use_kernel, lazy_l0=lazy_l0, fused=fused, chunk=chunk,
        batch_mode=batch_mode,
        allowed_batch_modes=("switch", "branchfree"))
    sr_obj = sr_mod.get(sig.sr)

    def run(h, rows, cols, vals):
        stages.check_state(sig, h, block=rows.shape[-1])
        return ingest(h, rows, cols, vals, sr=sr_obj,
                      use_kernel=sig.use_kernel, lazy_l0=sig.lazy_l0,
                      fused=sig.fused, chunk=sig.chunk,
                      batch_mode=sig.batch_mode)

    return stages.wrap(run, "stream.ingest_jit", sig)


def _select_depth0_leaves(states: HierAssoc, s0: HierAssoc, take0: Array
                          ) -> HierAssoc:
    """Keep the depth-0 executor's result for cohort members, the original
    state for everyone else — touching ONLY the leaves a depth-0 step can
    change (layer 0 and the scalar ledgers).  Deep layer buffers come from
    the original state untouched, so the all-append fast path never moves
    I x C_deep bytes through a select."""
    def sel(a: Array, b: Array) -> Array:
        m = take0.reshape(take0.shape + (1,) * (a.ndim - 1))
        return jnp.where(m, a, b)

    layer0 = jax.tree.map(sel, s0.layers[0], states.layers[0])
    return dataclasses.replace(
        states,
        layers=(layer0,) + states.layers[1:],
        spills=sel(s0.spills, states.spills),
        overflow=sel(s0.overflow, states.overflow),
        n_updates=sel(s0.n_updates, states.n_updates),
        n_updates_hi=sel(s0.n_updates_hi, states.n_updates_hi))


def _grouped_execute(states: HierAssoc, rows: Array, cols: Array, vals: Array,
                     n_live: Array, depths: Array, *, sr: Semiring,
                     use_kernel: bool, lazy_l0: bool, may_not_fit: bool
                     ) -> HierAssoc:
    """Depth-cohort grouped executor: per-step cost = sum_i W(depth_i).

    The depth-0 cohort executes as the batched append scatter (zero sorts
    with ``lazy_l0``), selected per instance.  Instances planning deeper
    spills drain through one dynamic-trip-count ``fori_loop`` PER STATIC
    DEPTH, reached through a depth-ordered ``argsort`` index vector: cohort
    d occupies a contiguous run of the sorted order, and each iteration
    slices ONE member's layers [0, d], runs the masked fused merge sized to
    exactly those layers, and scatters the result back.  A ``lax.cond``
    skips a depth entirely when its cohort is empty that step, so a batch
    with no deep instance never touches deep-layer buffers — and a batch
    WITH one pays that one instance's merge, not a fleet-wide one (the
    ``batch_mode="bucketed"`` failure mode this replaces as the default).

    Layers deeper than a cohort's d enter the sliced state as loop-invariant
    empty dummies carrying only the member's true nnz scalar (the executor
    reads deep layers solely for the last-layer pressure flag), so a depth-1
    iteration moves O(W_1) bytes even when C_{L-1} is huge.

    Named scopes mark the layers for a device trace (``stages.op_scopes``):
    ``cohort.d0`` is the append cohort and its select, ``cohort.d{d}`` all
    of depth d's member loop, and inside it ``cohort.take`` / ``cohort.put``
    the per-member slices and write-backs.
    """
    L = len(states.cuts)
    caps = tuple(l.hi.shape[-1] for l in states.layers)
    vdtype = states.layers[0].val.dtype

    # depth-0 cohort: vmapped up_to=0 executor (pure append under lazy_l0);
    # non-members' results are computed against layer 0 only and discarded.
    # The whole pass is cond-skipped when no instance appends this step, so
    # the per-step cost really is sum_i W(depth_i).
    take0 = depths == 0

    def depth0_pass(s):
        s0 = jax.vmap(
            lambda h, r, c, v, nl: hier._fused_execute_planned(
                h, r, c, v, nl, jnp.int32(0), up_to=0, sr=sr,
                use_kernel=use_kernel, lazy_l0=lazy_l0,
                may_not_fit=may_not_fit))(s, rows, cols, vals, n_live)
        return _select_depth0_leaves(s, s0, take0)

    with jax.named_scope("cohort.d0"):
        # reprolint: allow(R002) batch-level cond on a per-batch scalar; this function IS the batched layout and never runs under vmap
        cur = jax.lax.cond(jnp.any(take0), depth0_pass, lambda s: s, states)

    order = jnp.argsort(depths).astype(jnp.int32)
    ds = depths[order]

    def cohort_pass(cur: HierAssoc, d: int) -> HierAssoc:
        start = jnp.searchsorted(ds, d, side="left").astype(jnp.int32)
        n_d = jnp.searchsorted(ds, d, side="right").astype(jnp.int32) - start
        dummies = tuple(assoc.empty(caps[i], vdtype, sr)
                        for i in range(d + 1, L))

        def body(j, carry: HierAssoc) -> HierAssoc:
            idx = order[start + j]
            pick = lambda x: jax.lax.dynamic_index_in_dim(
                x, idx, 0, keepdims=False)
            with jax.named_scope("cohort.take"):
                shallow = jax.tree.map(pick, tuple(carry.layers[:d + 1]))
                deep = tuple(
                    dataclasses.replace(dm, nnz=pick(carry.layers[i].nnz))
                    for i, dm in zip(range(d + 1, L), dummies))
                one = HierAssoc(layers=shallow + deep,
                                spills=pick(carry.spills),
                                overflow=pick(carry.overflow),
                                n_updates=pick(carry.n_updates),
                                n_updates_hi=pick(carry.n_updates_hi),
                                cuts=carry.cuts)
                block = tuple(pick(x) for x in (rows, cols, vals, n_live))
            out = hier._fused_execute_planned(
                one, *block, jnp.int32(d), up_to=d, sr=sr,
                use_kernel=use_kernel, lazy_l0=lazy_l0)
            put = lambda full, v: jax.lax.dynamic_update_index_in_dim(
                full, v, idx, 0)
            with jax.named_scope("cohort.put"):
                new_shallow = jax.tree.map(put, tuple(carry.layers[:d + 1]),
                                           tuple(out.layers[:d + 1]))
                return dataclasses.replace(
                    carry, layers=new_shallow + carry.layers[d + 1:],
                    spills=put(carry.spills, out.spills),
                    overflow=put(carry.overflow, out.overflow),
                    n_updates=put(carry.n_updates, out.n_updates),
                    n_updates_hi=put(carry.n_updates_hi, out.n_updates_hi))

        # the cohort's bounds stay outside its scope: XLA merges the
        # depths' identical searchsorted loops, and with them their names
        with jax.named_scope(f"cohort.d{d}"):
            # reprolint: allow(R002) batch-level cohort skip on a per-batch scalar count; never reached under vmap (see docstring)
            return jax.lax.cond(
                n_d > 0,
                lambda s: jax.lax.fori_loop(0, n_d, body, s),
                lambda s: s,
                cur)

    for d in range(1, L):
        cur = cohort_pass(cur, d)
    return cur


def update_instances(states: HierAssoc, rows: Array, cols: Array, vals: Array,
                     sr: Semiring = sr_mod.PLUS_TIMES,
                     use_kernel: bool = False,
                     lazy_l0: bool = False,
                     batch_mode: str = "grouped",
                     mask: Array | None = None) -> HierAssoc:
    """One fused update of a whole instance batch ([I, B]).

    Plan-then-execute across the batch: every instance's spill depth comes
    first (vmapped scalar arithmetic over nnz counters — no array data
    touched), then ``batch_mode`` picks how the planned depths execute.
    Both predicates are plain per-batch scalars (this function must NOT be
    called under vmap — it IS the batched layout), so unlike a vmapped
    switch they really branch:

      * ``"grouped"`` (production default) — per-depth-cohort execution:
        the depth-0 cohort runs the pure batched append scatter (zero sorts
        with ``lazy_l0``; a layer-0-only merge without), and each deeper
        cohort d drains through a dynamic-trip loop paying ONE masked merge
        sized to layers [0, d] per member (``_grouped_execute``).  Step
        cost is sum_i W(depth_i): one deep instance does not drag the rest
        of the fleet into its merge.
      * ``"bucketed"`` — ONE batch-level ``lax.switch`` on the maximum
        planned depth: max depth 0 executes the batched append, max depth d
        executes one divergence-free masked merge per instance
        (``hier._fused_execute_planned``) sized to layers [0, d] for ALL
        instances; shallower instances gate deeper layers out and depth-0
        instances keep their append via ``jnp.where``.  Cost is
        I x W(max depth) — optimal when the fleet spills in lockstep, the
        A/B baseline for desynchronized fleets.

    ``mask`` ([I, B] bool) blanks per-entry updates exactly like
    ``hier.update``'s mask: masked blocks are planned and counted at their
    live-entry count ``sum(mask)`` per instance.

    Equivalent per instance to ``hier.update(fused=True)`` — contents,
    spills, overflow and update counters (tests/test_batched_ingest.py).
    Zero collectives: under ``shard_map`` every predicate is per-device.
    """
    sig = stages.signature_for_state(
        states, sr=sr, use_kernel=use_kernel, lazy_l0=lazy_l0,
        batch_mode=batch_mode, allowed_batch_modes=("grouped", "bucketed"),
        extra=(("masked", mask is not None),))
    if contracts.enabled() and not stages.is_tracing(states, rows, cols,
                                                     vals, mask):
        dsig = contracts.debug_signature(sig)
        err, out = stages.dispatch(
            "stream.update_instances", dsig,
            lambda: _update_instances_impl(dsig),
            states, rows, cols, vals, mask)
        contracts.throw(err)
        return out
    return stages.dispatch(
        "stream.update_instances", sig,
        lambda: _update_instances_impl(sig), states, rows, cols, vals, mask)


def _update_instances_impl(sig: stages.Signature):
    sr = sr_mod.get(sig.sr)
    use_kernel, lazy_l0 = sig.use_kernel, sig.lazy_l0
    batch_mode = sig.batch_mode

    def run(states, rows, cols, vals, mask):
        return _update_instances_body(states, rows, cols, vals, sr,
                                      use_kernel, lazy_l0, batch_mode, mask)

    if not contracts.sig_debug(sig):
        return run

    def checked(states, rows, cols, vals, mask):
        contracts.check_hier(states, sr, l0_sorted=not lazy_l0,
                             name="stream.update_instances input")
        # Re-derive the spill plan the executor trusts to slice layers and
        # bound-check it against the static hierarchy depth.
        prep = jax.vmap(
            lambda h, r, c, v, m: hier._prepare_block(h, r, c, v, m, sr),
            in_axes=(0, 0, 0, 0, None if mask is None else 0))
        _, _, _, n_live = prep(states, rows, cols, vals, mask)
        depths = jax.vmap(hier._plan_spill_depth, in_axes=(0, 0))(
            states, n_live)
        contracts.check_plan(depths, states.cuts,
                             name="stream.update_instances")
        with contracts.activate():
            out = run(states, rows, cols, vals, mask)
        contracts.check_hier(out, sr, l0_sorted=not lazy_l0,
                             name="stream.update_instances output")
        return out

    return contracts.checkified(checked)


def _update_instances_body(states, rows, cols, vals, sr, use_kernel,
                           lazy_l0, batch_mode, mask):
    B = rows.shape[-1]
    L = len(states.cuts)
    caps0 = states.layers[0].hi.shape[-1]
    # mirrors hier._update_fused: only a MASKED block wider than the
    # creation block size can physically clobber on the append fast path
    may_not_fit = mask is not None and B > caps0 - states.cuts[0]
    prep = jax.vmap(
        lambda h, r, c, v, m: hier._prepare_block(h, r, c, v, m, sr),
        in_axes=(0, 0, 0, 0, None if mask is None else 0))
    rows, cols, vals, n_live = prep(states, rows, cols, vals, mask)
    depths = jax.vmap(hier._plan_spill_depth, in_axes=(0, 0))(states, n_live)

    if batch_mode == "grouped":
        return _grouped_execute(states, rows, cols, vals, n_live, depths,
                                sr=sr, use_kernel=use_kernel, lazy_l0=lazy_l0,
                                may_not_fit=may_not_fit)

    dmax = jnp.max(depths)

    def make_branch(d: int):
        def run(operands):
            s, dep = operands
            return jax.vmap(
                lambda h, r, c, v, nl, dd: hier._fused_execute_planned(
                    h, r, c, v, nl, dd, up_to=d, sr=sr,
                    use_kernel=use_kernel, lazy_l0=lazy_l0,
                    may_not_fit=may_not_fit))(
                s, rows, cols, vals, n_live, dep)
        return run

    return jax.lax.switch(dmax, [make_branch(d) for d in range(L)],
                          (states, depths))


def ingest_instances(states: HierAssoc, rows: Array, cols: Array, vals: Array,
                     sr: Semiring = sr_mod.PLUS_TIMES,
                     use_kernel: bool = False,
                     lazy_l0: bool = False,
                     fused: bool = True,
                     chunk: int = 1,
                     batch_mode: str = "grouped"):
    """Instance-batched ingest: states is an instance-batched HierAssoc
    pytree and the stream arrays are [I, T, B].

    ``batch_mode`` (fused path only; the layered oracle always vmaps):

      * ``"grouped"`` (production default) — ``lax.scan`` over time of the
        depth-cohort batched step (``update_instances``): the update-path
        cost of a step is the SUM of each instance's own planned depth —
        the depth-0 cohort appends with no sort at all, and each deeper
        cohort drains one member at a time through a dynamic-trip loop, so
        one deep instance never drags the rest of the fleet into its
        merge (the desynchronized-fleet regime; EXPERIMENTS.md
        §Desynchronization matrix).
      * ``"bucketed"`` — the PR-3 layout: one batch-level ``lax.switch``
        per step on the DEEPEST planned spill, charging every instance a
        merge sized to that depth.  Matches grouped when the fleet spills
        in lockstep; the desynchronization A/B baseline.
      * ``"branchfree"`` — vmap-of-scan with the per-instance masked merge
        (one fixed-shape merge per instance per step, no batch grouping).
      * ``"switch"`` — the legacy vmapped ``lax.switch`` layout; kept as
        the A/B baseline because a batched switch executes every branch.

    All modes return identical states and per-instance telemetry
    ([I, T, ...], per-input-block units under ``chunk``).
    """
    sig = stages.signature_for_state(
        states, sr=sr, use_kernel=use_kernel, lazy_l0=lazy_l0, fused=fused,
        chunk=chunk, batch_mode=batch_mode)
    return ingest_instances_jit(sig)(states, rows, cols, vals)


def ingest_instances_jit(sig: stages.Signature = None, *,
                         with_telemetry: bool = True, donate: bool = False,
                         **knobs) -> stages.Wrapped:
    """Staged (states, [I,T,B] stream) -> (states[, telemetry]) program.

    The ONE builder behind every instance-batched ingest dispatch —
    ``ingest_instances`` itself, ``launch/ingest.py``, the benchmarks, and
    ``query.service.make_ingest_fn`` (which passes ``with_telemetry=False,
    donate=True`` so XLA DCEs the telemetry and updates the fleet state in
    place) — so they all share one cache entry per config signature and
    ``stages.precompile_fleet`` can warm exactly the programs the CLIs will
    dispatch.  Build it from an existing ``Signature`` or from knob kwargs
    (``cuts``/``sr``/``lazy_l0``/...).
    """
    if sig is None:
        sig = stages.signature_of(**knobs)
    sr = sr_mod.get(sig.sr)

    def run(states, rows, cols, vals):
        out = _ingest_instances_body(states, rows, cols, vals, sr, sig)
        return out if with_telemetry else out[0]

    return stages.wrap(run, "stream.ingest_instances", sig,
                       static=(("telemetry", with_telemetry),),
                       donate_argnums=(0,) if donate else None)


def _ingest_instances_body(states, rows, cols, vals, sr: Semiring,
                           sig: stages.Signature):
    use_kernel, lazy_l0 = sig.use_kernel, sig.lazy_l0
    fused, chunk, batch_mode = sig.fused, sig.chunk, sig.batch_mode
    if not fused or batch_mode in ("switch", "branchfree"):
        return jax.vmap(
            lambda h, r, c, v: ingest(
                h, r, c, v, sr=sr, use_kernel=use_kernel, lazy_l0=lazy_l0,
                fused=fused, chunk=chunk,
                batch_mode=batch_mode if batch_mode in ("switch",
                                                        "branchfree")
                else "switch"))(states, rows, cols, vals)

    if chunk > 1:
        rows, cols, vals = _chunk_stream(
            rows, cols, vals, chunk, fused,
            int(states.layers[0].hi.shape[-1]) - states.cuts[0])
    # time-major for the scan: [I, T, B] -> [T, I, B]
    rows_t = jnp.moveaxis(rows, -2, 0)
    cols_t = jnp.moveaxis(cols, -2, 0)
    vals_t = jnp.moveaxis(vals, -2, 0)

    def step(s: HierAssoc, block):
        r, c, v = block
        new_s = update_instances(s, r, c, v, sr=sr, use_kernel=use_kernel,
                                 lazy_l0=lazy_l0, batch_mode=batch_mode)
        telemetry = dict(
            nnz0=new_s.layers[0].nnz,
            spills=new_s.spills,
            overflow=new_s.overflow,
        )
        return new_s, telemetry

    final, telem = jax.lax.scan(step, states, (rows_t, cols_t, vals_t))
    # back to instance-major [I, T, ...] so every batch_mode agrees
    telem = {k: jnp.moveaxis(v, 0, 1) for k, v in telem.items()}
    return final, _normalize_chunked_telemetry(telem, chunk, time_axis=1)
