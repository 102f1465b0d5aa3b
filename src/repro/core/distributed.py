"""Distributed placement of D4M instances — paper §III scaled out.

The paper runs 34,000 independent database instances across 1,100 nodes with
no coordination on the update path; aggregate throughput scales linearly
(Fig 3).  Here the same topology is expressed as:

    shard_map over mesh axes  ×  vmap over per-device instances

Update path: zero collectives (share-nothing, paper-faithful).
Query  path: global analytics are mesh reductions (psum) over per-instance
partial results — e.g. a global degree histogram over every instance's graph.

Elasticity: instances are assigned to devices by consistent hashing of the
instance id so that growing/shrinking the mesh remaps a minimal fraction of
instances (launch/train.py uses this for elastic restart).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro import stages
from repro.core import hier, stream
from repro.core import semiring as sr_mod
from repro.core.hier import HierAssoc
from repro.core.semiring import Semiring

Array = jax.Array


def instance_assignment(n_instances: int, n_devices: int) -> jnp.ndarray:
    """Rendezvous (highest-random-weight) assignment instance -> device.

    device(i) = argmax_d hash(i, d): stable across runs, and when the
    fleet grows from N to N+k devices only the instances whose new
    device wins move (~k/(N+k) in expectation) — true consistent-hashing
    behavior for elastic rescale, unlike a mod-N hash which reshuffles
    almost everything.
    """
    ids = jnp.arange(n_instances, dtype=jnp.uint32)[:, None]
    devs = jnp.arange(n_devices, dtype=jnp.uint32)[None, :]
    h = ids * jnp.uint32(2654435761) ^ devs * jnp.uint32(40503)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(2246822519)
    h = h ^ (h >> 13)
    return jnp.argmax(h, axis=1).astype(jnp.int32)


def create_instances(n_instances: int, cuts: Tuple[int, ...], block_size: int,
                     dtype=jnp.float32, sr: Semiring = sr_mod.PLUS_TIMES,
                     sharding=None) -> HierAssoc:
    """Instance-batched hierarchy pytree (leading axis = instance).

    One compiled program broadcasts a single empty instance over the fleet
    and writes every leaf where ``sharding`` places it (default: the
    default device).  Split on the instance axis, a fleet larger than one
    device's memory never lands whole on the first device."""
    sig = stages.signature_of(cuts=cuts, block_size=block_size, dtype=dtype,
                              sr=sr)
    broadcast = stages.wrap(
        lambda one: jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_instances,) + x.shape), one),
        "distributed.create_instances", sig, static=(n_instances,),
        out_shardings=sharding)
    return broadcast(hier.create(cuts, block_size, dtype, sr))


def sharded_ingest_fn(mesh: Mesh, data_axes: Tuple[str, ...],
                      sr: Semiring = sr_mod.PLUS_TIMES,
                      lazy_l0: bool = False,
                      use_kernel: bool = False,
                      fused: bool = True,
                      chunk: int = 1,
                      batch_mode: str = "grouped"):
    """Build the distributed ingest step.

    States and streams are sharded over ``data_axes`` on their instance
    (leading) axis; each device runs its own instance group — no collectives
    on the update path, exactly the paper's share-nothing design.  ``fused``
    (default) runs the single-sort fused spill cascade per instance
    (hier.py) — ``fused=False`` is the layered reference oracle; ``chunk``
    pre-combines that many stream blocks per hierarchy update.

    ``batch_mode`` picks the instance-batched execution strategy
    (``stream.ingest_instances``): the ``"grouped"`` default plans every
    local instance's spill depth and executes per depth cohort (batched
    append for the depth-0 cohort, a dynamic-trip merge loop per deeper
    cohort), so one deep instance costs its own merge instead of dragging
    the device's whole instance group into it — every predicate and trip
    count is per-device, so the desynchronization fix costs no collectives
    either.  ``"bucketed"`` is the PR-3 branch-on-deepest layout (the
    synchronized-fleet A/B baseline).
    """
    sig = stages.signature_of(sr=sr, use_kernel=use_kernel, lazy_l0=lazy_l0,
                              fused=fused, chunk=chunk,
                              batch_mode=batch_mode, mesh=mesh,
                              data_axes=data_axes)
    spec = P(data_axes)

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec, spec),
             out_specs=(spec, spec), check_vma=False)
    def dist_ingest(states, rows, cols, vals):
        return stream.ingest_instances(states, rows, cols, vals, sr=sr,
                                       use_kernel=use_kernel, lazy_l0=lazy_l0,
                                       fused=fused, chunk=chunk,
                                       batch_mode=batch_mode)

    return stages.wrap(dist_ingest, "distributed.sharded_ingest_fn", sig,
                       donate_argnums=(0,))


def _mesh_semiring_combine(sr: Semiring, x: Array, axis_name: str) -> Array:
    """Mesh reduction matching the semiring's add: psum for plus.times,
    pmax/pmin for the idempotent tropical semirings (dispatch via
    ``semiring.reduce_kind``, which raises on unknown semirings)."""
    op = {"sum": jax.lax.psum, "max": jax.lax.pmax, "min": jax.lax.pmin}
    return op[sr_mod.reduce_kind(sr)](x, axis_name)


def sharded_query_fn(mesh: Mesh, data_axes: Tuple[str, ...],
                     sr: Semiring = sr_mod.PLUS_TIMES,
                     use_kernel: bool = False,
                     l0_mode: str = "auto",
                     per_instance: bool = False):
    """Fleet-wide point queries: shard_map fanout + semiring-combine gather.

    The query vector is replicated to every device; each device answers it
    against its LOCAL instance group with one batched engine dispatch
    (vmapped ``engine.point_lookup`` — no flush, no merge), then the
    per-instance hits are semiring-combined, first across the local vmap
    axis and then across the mesh (psum/pmax/pmin to match ``sr.add``).
    The result is the value the whole fleet's merged array would hold at
    each key — the read-path dual of ``sharded_ingest_fn``, and the only
    collectives in the system stay on the query path, exactly the paper's
    share-nothing split.

    ``per_instance=True`` skips both combines and returns the [I, Q]
    per-instance values instead (instance-major, matching the state's
    leading axis) for callers that post-process per database.
    """
    from repro.query import engine

    sig = stages.signature_of(sr=sr, use_kernel=use_kernel, l0_mode=l0_mode,
                              mesh=mesh, data_axes=data_axes,
                              extra=(("per_instance", per_instance),))
    spec = P(data_axes)
    out_spec = spec if per_instance else P()

    @partial(shard_map, mesh=mesh, in_specs=(spec, P(), P()),
             out_specs=out_spec, check_vma=False)
    def dist_query(states, q_rows, q_cols):
        local = jax.vmap(
            lambda h: engine.point_lookup(h, q_rows, q_cols, sr=sr,
                                          use_kernel=use_kernel,
                                          l0_mode=l0_mode))(states)
        if per_instance:
            return local
        local = engine.reduce_axis(sr, local, axis=0)
        for ax in data_axes:
            local = _mesh_semiring_combine(sr, local, ax)
        return local

    return stages.wrap(dist_query, "distributed.sharded_query_fn", sig)


def global_degree_histogram_fn(mesh: Mesh, data_axes: Tuple[str, ...],
                               num_rows: int, num_bins: int,
                               sr: Semiring = sr_mod.PLUS_TIMES):
    """Query path: global out-degree histogram across every instance.

    Per-instance row reductions -> local histogram -> psum over the mesh.
    This is the "sum all layers / reduce globally" analytics pattern of §II.
    Local instances run ``analytics.instance_batch(num_rows)`` at a time so
    the dense [num_rows] degree vectors stay bounded at any fleet size.
    """
    from repro.core import assoc
    from repro.query import analytics

    spec = P(data_axes)

    @partial(shard_map, mesh=mesh, in_specs=(spec,), out_specs=P(),
             check_vma=False)
    def histogram(states):
        def one_instance(h):
            merged = hier.query_all(h, sr)
            deg = assoc.reduce_rows(merged, num_rows, sr)
            counts = jnp.zeros((num_bins,), jnp.int32)
            nz = deg > 0
            # bin = floor(log2(deg)) from frexp's exact exponent: the v5e's
            # log2 puts 2^15 in bin 14
            _, exp = jnp.frexp(jnp.maximum(deg, 1))
            bins = jnp.clip(exp - 1, 0, num_bins - 1)
            return counts.at[bins].add(nz.astype(jnp.int32))

        local = jax.lax.map(
            one_instance, states,
            batch_size=analytics.instance_batch(num_rows)).sum(axis=0)
        for ax in data_axes:
            local = jax.lax.psum(local, ax)
        return local

    sig = stages.signature_of(sr=sr, mesh=mesh, data_axes=data_axes,
                              extra=(("num_rows", int(num_rows)),
                                     ("num_bins", int(num_bins))))
    return stages.wrap(histogram, "distributed.global_degree_histogram",
                       sig)


def aggregate_update_counts_fn(mesh: Mesh, data_axes: Tuple[str, ...]):
    """Total updates ingested across the fleet (throughput accounting).

    The paper's fleets count 1.9e9 updates *per second*, so int32 psum
    arithmetic broke the counter in about one second (wraps at ~2.1e9).
    int64 is unavailable without ``jax_enable_x64``, so exactness comes
    from word splitting instead: per device, the uint32 low words are
    summed with wraparound-carry detection (a wrapping cumsum decreases
    exactly at the carries) and the resulting 32-bit total is split into
    16-bit halves whose int32 psums cannot overflow below ~2^15 devices;
    the 2^32-carry words ride psum directly.  The returned callable
    reassembles the exact 64-bit total on the host (as a numpy int64), so
    ``int(fn(states))`` keeps working — now past 2^31 and 2^32.
    """
    spec = P(data_axes)

    @partial(shard_map, mesh=mesh, in_specs=(spec,), out_specs=P(),
             check_vma=False)
    def count_parts(states):
        lo = states.n_updates.reshape(-1)           # uint32[I] low words
        hi = states.n_updates_hi.reshape(-1)        # int32[I]  2^32 carries
        csum = jnp.cumsum(lo)                       # uint32, wraps
        carries = jnp.sum((csum[1:] < csum[:-1]).astype(jnp.int32))
        lo_total = csum[-1]                         # uint32 device total
        hi_total = jnp.sum(hi) + carries
        parts = jnp.stack([
            hi_total,
            (lo_total >> jnp.uint32(16)).astype(jnp.int32),
            (lo_total & jnp.uint32(0xFFFF)).astype(jnp.int32)])
        for ax in data_axes:
            parts = jax.lax.psum(parts, ax)
        return parts

    jitted = stages.wrap(count_parts, "distributed.aggregate_update_counts",
                         stages.signature_of(mesh=mesh,
                                             data_axes=data_axes))

    def count(states):
        import numpy as np
        p = np.asarray(jax.device_get(jitted(states)), np.int64)
        return np.int64((p[0] << np.int64(32)) + (p[1] << np.int64(16))
                        + p[2])

    return count
