"""The paper's driver: N hierarchical D4M instances x R-MAT edge streams.

    PYTHONPATH=src python -m repro.launch.ingest --instances 8 \
        --blocks 64 --block-size 4096 --cuts 2048,16384,131072

Reproduces §III of the paper at container scale: every instance ingests its
own power-law stream ("thousands of processors each creating many different
graphs"), there is NO cross-instance traffic on the update path, and the
reported metric is sustained updates/second.  Telemetry verifies the
hierarchy claim: the fraction of updates that never leave layer 0.

Fault tolerance: the whole fleet state (every instance's hierarchy) is a
pytree — checkpointed atomically every ``--ckpt-every`` scan rounds and
restorable onto a different instance count (runtime/elastic.py).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import stages
from repro.checkpoint import latest_step, restore, save
from repro.core import distributed, stream
from repro.data.powerlaw import instance_streams


def run(args) -> dict:
    cuts = tuple(int(c) for c in args.cuts.split(","))
    key = jax.random.PRNGKey(args.seed)

    states = distributed.create_instances(
        args.instances, cuts, args.block_size)

    fused = not getattr(args, "layered", False)
    # "auto" couples the append buffer to the fused default; "on"/"off"
    # decouple the two knobs for A/B runs
    lazy_arg = getattr(args, "lazy_l0", "auto")
    lazy_l0 = fused if lazy_arg == "auto" else lazy_arg == "on"
    chunk = getattr(args, "chunk", 1)
    use_kernel = getattr(args, "use_kernel", False)
    batch_mode = getattr(args, "batch_mode", "grouped")
    sig = stages.signature_of(cuts=cuts, block_size=args.block_size,
                              fused=fused, lazy_l0=lazy_l0, chunk=chunk,
                              use_kernel=use_kernel, batch_mode=batch_mode)
    obs_on = getattr(args, "obs", False)
    if obs_on:
        from repro import obs
        obs.enable(getattr(args, "obs_dir", None) or None)
    blocks_per_round = max(args.blocks // args.rounds, 1)
    if getattr(args, "precompile", False):
        report = stages.precompile_fleet(
            sig, instances=args.instances, blocks=blocks_per_round)
        if args.verbose:
            for entry, how in report.items():
                print(f"[precompile] {entry}: {how}")
    ingest = stream.ingest_instances_jit(sig)

    start_round = 0
    if args.ckpt_dir and args.resume:
        last = latest_step(args.ckpt_dir)
        if last is not None:
            states = restore(args.ckpt_dir, last, states)
            start_round = last
            print(f"[resume] round {last}")
    # spill counters in the state are cumulative since CREATION; remember
    # the restored baseline so the fast-layer fraction below only accounts
    # for this run's updates.
    spills_l0_baseline = int(jnp.sum(states.spills[:, 0]))

    total_updates = 0
    wall = 0.0
    spill_counts = None
    if obs_on:
        from repro.obs import metrics as obs_metrics
        from repro.obs import trace as obs_trace
        # baseline fleet sample BEFORE the stream: the monitor's rate is
        # the exact device-counter delta over the summed round walls, the
        # same number this CLI prints (counter/wall agreement < 1% is the
        # tentpole acceptance test)
        obs_trace.emit("fleet", **obs_metrics.fleet_sample(states))
    for rnd in range(start_round, args.rounds):
        rkey = jax.random.fold_in(key, rnd)
        rows, cols, vals = instance_streams(
            rkey, args.instances, blocks_per_round, args.block_size,
            scale=args.scale)
        t0 = time.time()
        states, telem = ingest(states, rows, cols, vals)
        jax.block_until_ready(states.n_updates)
        dt = time.time() - t0
        wall += dt
        n = args.instances * blocks_per_round * args.block_size
        total_updates += n
        spill_counts = telem["spills"][:, -1]     # final cumulative spills
        if obs_on:
            # sampling boundary: one ingest_round span + ONE snapshot
            # dispatch, both outside the timed region
            obs_trace.emit("ingest_round", round=rnd, updates=n,
                           wall_s=dt, rate=n / dt)
            obs_trace.emit("fleet", **obs_metrics.fleet_sample(states))
        if args.verbose:
            print(f"round {rnd}: {n/dt:,.0f} updates/s "
                  f"(total {total_updates:,})")
        if args.ckpt_dir and (rnd + 1) % args.ckpt_every == 0:
            save(args.ckpt_dir, rnd + 1, states)

    # hierarchy telemetry: how much traffic stayed in fast memory?  A spill
    # can occur at most once per hierarchy UPDATE, and chunking folds
    # ``chunk`` stream blocks into one update — normalize by updates, not
    # raw blocks, or the fast-layer fraction inflates by 1 - 1/chunk.
    n_updates_total = ((args.rounds - start_round) * blocks_per_round
                       // max(chunk, 1))
    spills_l0 = (int(jnp.sum(spill_counts[:, 0])) - spills_l0_baseline) \
        if spill_counts is not None else 0
    frac_fast = 1.0 - spills_l0 / max(args.instances * n_updates_total, 1)
    rate = total_updates / wall if wall else 0.0
    from repro.core.hier import exact_update_count
    out = dict(updates_per_s=rate, total_updates=total_updates,
               wall_s=wall, frac_blocks_layer0=frac_fast,
               # exact 64-bit (hi, lo) reassembly — int32 summing broke
               # past ~2.1e9 fleet updates (about one paper-second)
               n_updates_counter=exact_update_count(states),
               overflow=int(jnp.sum(states.overflow)))
    if obs_on:
        obs_metrics.export_stages_gauges()
        obs_trace.emit("metrics", **obs_metrics.REGISTRY.snapshot())
        obs_trace.emit("run_summary", kind="ingest", **out)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--instances", type=int, default=8)
    ap.add_argument("--blocks", type=int, default=64)
    ap.add_argument("--block-size", type=int, default=4096)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--cuts", default="2048,16384,131072")
    ap.add_argument("--scale", type=int, default=18)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=4)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--verbose", action="store_true")
    ap.add_argument("--layered", action="store_true",
                    help="reference per-layer cascade instead of the fused "
                    "default (A/B oracle)")
    ap.add_argument("--lazy-l0", dest="lazy_l0",
                    choices=("auto", "on", "off"), default="auto",
                    help="layer-0 append buffer; auto = follow the fused "
                    "default")
    ap.add_argument("--chunk", type=int, default=1,
                    help="stream blocks pre-combined per hierarchy update "
                    "(fused only; must divide blocks/rounds)")
    ap.add_argument("--use-kernel", dest="use_kernel", action="store_true",
                    help="Pallas merge kernels (interpret mode off-TPU)")
    ap.add_argument("--batch-mode", dest="batch_mode",
                    choices=("grouped", "bucketed", "branchfree", "switch"),
                    default="grouped",
                    help="instance-batched execution strategy: grouped = "
                    "plan all depths, execute per depth cohort so one deep "
                    "instance pays only its own merge (production default); "
                    "bucketed = branch once per step on the deepest "
                    "(synchronized-fleet A/B baseline); branchfree = one "
                    "masked merge per instance; switch = legacy vmapped "
                    "lax.switch (executes every branch — the divergence "
                    "A/B baseline)")
    ap.add_argument("--precompile", action="store_true",
                    help="compile the whole dispatch set up front "
                    "(stages.precompile_fleet) before streaming")
    ap.add_argument("--obs", action="store_true",
                    help="emit obs.jsonl observability events "
                    "(dispatch spans, per-round fleet samples); aggregate "
                    "with python -m repro.launch.monitor")
    ap.add_argument("--obs-dir", dest="obs_dir", default="",
                    help="observability output directory (default 'obs' "
                    "or REPRO_OBS_DIR)")
    args = ap.parse_args()
    stages.set_cache_dir(stages.default_cache_dir())
    out = run(args)
    print(f"sustained {out['updates_per_s']:,.0f} updates/s over "
          f"{out['total_updates']:,} updates "
          f"({out['wall_s']:.1f}s); counter={out['n_updates_counter']:,} "
          f"overflow={out['overflow']}")


if __name__ == "__main__":
    main()
