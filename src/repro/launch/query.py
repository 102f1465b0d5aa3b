"""Query-serving driver: answer queries against the fleet WHILE it ingests.

    PYTHONPATH=src python -m repro.launch.query --instances 8 \
        --blocks 64 --block-size 2048 --cuts 4096,32768,262144 \
        --queries 256 --rounds 8

The read-side companion of ``launch/ingest.py`` (the LM driver stays in
``launch/serve.py``): every instance ingests its own R-MAT stream through
the production fused/bucketed path, and between ingest rounds the batched
query engine (repro/query) answers Q-vector point lookups plus a top-k
heavy-hitter analytic against the LIVE hierarchies — no flush, no merge.
Reports sustained updates/s NEXT TO queries/s and per-batch query latency,
plus the ingest-only baseline rate so read-path interference is visible
(the bench criterion is < 10%, EXPERIMENTS.md §Query-serving).

Defaults for the query knobs come from ``configs/d4m_stream.py``
(``query_batch``/``query_l0_mode``/``queries_per_round``).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro import stages
from repro.configs import get_config
from repro.core import distributed
from repro.data.powerlaw import instance_streams
from repro.query import service


def run(args) -> dict:
    cuts = tuple(int(c) for c in args.cuts.split(","))
    if getattr(args, "obs", False):
        from repro import obs
        obs.enable(getattr(args, "obs_dir", None) or None)
    if getattr(args, "precompile", False):
        # run_service slices the stream into T//rounds blocks per round —
        # precompile against exactly that shape so the service loop's first
        # dispatch is already staged.
        n_keys = 1 << args.scale
        sig = stages.signature_of(
            cuts=cuts, block_size=args.block_size,
            fused=not args.layered, lazy_l0=not args.no_lazy_l0,
            chunk=args.chunk, use_kernel=args.use_kernel,
            batch_mode=args.batch_mode, l0_mode=args.l0_mode)
        stages.precompile_fleet(
            sig, instances=args.instances,
            blocks=args.blocks // args.rounds, queries=args.queries,
            analytics_num_rows=0 if args.no_analytics else n_keys,
            analytics_k=args.top_k)
    key = jax.random.PRNGKey(args.seed)
    rows, cols, vals = instance_streams(
        key, args.instances, args.blocks, args.block_size, scale=args.scale)
    qkey = jax.random.fold_in(key, 7)
    n_keys = 1 << args.scale
    q_rows = jax.random.randint(qkey, (args.queries,), 0, n_keys, jnp.int32)
    q_cols = jax.random.randint(jax.random.fold_in(qkey, 1),
                                (args.queries,), 0, n_keys, jnp.int32)

    kwargs = dict(
        rounds=args.rounds,
        lazy_l0=not args.no_lazy_l0,
        use_kernel=args.use_kernel,
        fused=not args.layered,
        chunk=args.chunk,
        batch_mode=args.batch_mode,
        l0_mode=args.l0_mode,
        queries_per_round=args.queries_per_round,
        analytics_num_rows=0 if args.no_analytics else n_keys,
        analytics_k=args.top_k,
        slo_p99_ms=getattr(args, "slo_p99_ms", None),
    )
    states = distributed.create_instances(
        args.instances, cuts, args.block_size)
    _, base = service.run_service(states, rows, cols, vals, q_rows, q_cols,
                                  with_queries=False, **kwargs)
    states = distributed.create_instances(
        args.instances, cuts, args.block_size)
    states, stats = service.run_service(states, rows, cols, vals,
                                        q_rows, q_cols,
                                        with_queries=True, **kwargs)
    stats["ingest_only_updates_per_s"] = base["updates_per_s"]
    stats["ingest_interference"] = (
        1.0 - stats["updates_per_s"] / base["updates_per_s"]
        if base["updates_per_s"] else 0.0)
    if getattr(args, "obs", False):
        from repro.obs import metrics as obs_metrics
        from repro.obs import trace as obs_trace
        obs_trace.emit("fleet", **obs_metrics.fleet_sample(states))
        obs_metrics.export_stages_gauges()
        obs_trace.emit("metrics", **obs_metrics.REGISTRY.snapshot())
    return stats


def main():
    cfg = get_config("d4m-stream")
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--instances", type=int, default=8)
    ap.add_argument("--blocks", type=int, default=64)
    ap.add_argument("--block-size", type=int, default=2048)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--cuts", default="4096,32768,262144")
    ap.add_argument("--scale", type=int, default=18)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--queries", type=int, default=cfg.query_batch,
                    help="Q-vector width per engine dispatch")
    ap.add_argument("--queries-per-round", dest="queries_per_round",
                    type=int, default=cfg.queries_per_round)
    ap.add_argument("--l0-mode", dest="l0_mode",
                    choices=("auto", "scan", "canon"),
                    default=cfg.query_l0_mode,
                    help="layer-0 query strategy: masked raw scan vs one "
                    "in-dispatch canonicalization of the buffer")
    ap.add_argument("--top-k", dest="top_k", type=int, default=8,
                    help="heavy-hitter rows per analytics batch")
    ap.add_argument("--no-analytics", action="store_true",
                    help="point lookups only (skip the top-k reduction)")
    ap.add_argument("--layered", action="store_true",
                    help="reference per-layer cascade on the write side")
    ap.add_argument("--no-lazy-l0", action="store_true",
                    help="canonical layer 0 instead of the append buffer")
    ap.add_argument("--chunk", type=int, default=1)
    ap.add_argument("--use-kernel", dest="use_kernel", action="store_true")
    ap.add_argument("--batch-mode", dest="batch_mode",
                    choices=("grouped", "bucketed", "branchfree", "switch"),
                    default=cfg.batch_mode)
    ap.add_argument("--precompile", action="store_true",
                    help="compile the whole dispatch set up front "
                    "(stages.precompile_fleet) before serving")
    ap.add_argument("--obs", action="store_true",
                    help="emit obs.jsonl observability events; aggregate "
                    "with python -m repro.launch.monitor")
    ap.add_argument("--obs-dir", dest="obs_dir", default="",
                    help="observability output directory (default 'obs' "
                    "or REPRO_OBS_DIR)")
    ap.add_argument("--slo-p99-ms", dest="slo_p99_ms", type=float,
                    default=None,
                    help="query-batch latency SLO target: breaches are "
                    "counted (and emitted as obs events) per batch, and "
                    "slo_attainment lands in the stats")
    args = ap.parse_args()
    stages.set_cache_dir(stages.default_cache_dir())
    out = run(args)
    print(f"ingest  {out['updates_per_s']:,.0f} upd/s "
          f"(ingest-only {out['ingest_only_updates_per_s']:,.0f}, "
          f"interference {out['ingest_interference']:+.1%})")
    print(f"queries {out['queries_per_s']:,.0f} q/s over "
          f"{out['n_queries']:,} lookups; "
          f"latency p50 {out['latency_p50_s']*1e3:.2f} / "
          f"p95 {out['latency_p95_s']*1e3:.2f} / "
          f"p99 {out['latency_p99_s']*1e3:.2f} ms "
          f"(max {out['latency_max_s']*1e3:.2f} ms)")
    if out.get("slo_p99_ms") is not None:
        print(f"SLO     p99 target {out['slo_p99_ms']:g} ms: "
              f"attainment {out['slo_attainment']:.2%} "
              f"({out['slo_breaches']} breaches)")


if __name__ == "__main__":
    main()
