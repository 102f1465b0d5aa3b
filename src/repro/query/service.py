"""Read-while-ingest service loop: serve queries AGAINST the live fleet.

The paper's point in sustaining 1.9B updates/s is to *analyze* streaming
network data (arXiv:1907.04217) — which means the read path must run while
the write path streams, without draining the hierarchy.  This module
interleaves jitted ingest rounds (``stream.ingest_instances`` — the
production depth-cohort grouped layout) with jitted query batches (``engine`` point
lookups vmapped over the local instances, ``analytics`` reductions mapped
over them in bounded batches)
and reports both sides of the ledger: sustained updates/s, queries/s and
per-batch query latency.  Because the engine never mutates or merges
state, the only coupling between the two paths is the device itself — the
benchmark criterion is that interleaving costs the ingest rate < 10%
(BENCH_query.json, EXPERIMENTS.md §Query-serving).

``launch/query.py`` is the CLI driver; ``benchmarks/bench_query.py``
uses the same loop for the interleaved arm.
"""
from __future__ import annotations

import time
from typing import Tuple

import jax
import jax.numpy as jnp

from repro import stages
from repro.core import semiring as sr_mod
from repro.core import stream
from repro.core.semiring import Semiring
from repro.obs import slo as obs_slo
from repro.obs import trace as obs_trace
from repro.query import analytics, engine

Array = jax.Array


def make_ingest_fn(sr: Semiring = sr_mod.PLUS_TIMES, *,
                   use_kernel: bool = False, lazy_l0: bool = False,
                   fused: bool = True, chunk: int = 1,
                   batch_mode: str = "grouped"):
    """Staged (states, [I,T,B] stream) -> states round step (telemetry
    dropped so XLA can DCE it on the hot path).  The state is donated —
    matching ``distributed.sharded_ingest_fn`` — so each round updates the
    hierarchy buffers in place instead of copying the whole fleet state;
    callers must use the returned states, never the argument.  Routes
    through ``stream.ingest_instances_jit`` so the service shares the
    keyed compile cache with every other ingest entry point."""
    sig = stages.signature_of(sr=sr, use_kernel=use_kernel, lazy_l0=lazy_l0,
                              fused=fused, chunk=chunk,
                              batch_mode=batch_mode)
    return stream.ingest_instances_jit(sig, with_telemetry=False,
                                       donate=True)


def make_point_query_fn(sr: Semiring = sr_mod.PLUS_TIMES, *,
                        use_kernel: bool = False, l0_mode: str = "auto"):
    """Staged (states, q_rows [Q], q_cols [Q]) -> values [I, Q]: one
    engine dispatch answers the whole query vector for every local
    instance (the vmapped analogue of ``stream.update_instances``)."""
    sig = stages.signature_of(sr=sr, use_kernel=use_kernel, l0_mode=l0_mode)

    def run(s, q_rows, q_cols):
        return jax.vmap(
            lambda h: engine.point_lookup(h, q_rows, q_cols, sr=sr,
                                          use_kernel=use_kernel,
                                          l0_mode=l0_mode))(s)
    return stages.wrap(run, "service.point_query", sig)


def make_analytics_fn(num_rows: int, k: int,
                      sr: Semiring = sr_mod.PLUS_TIMES):
    """Staged states -> (top-k totals [I, k], top-k row ids [I, k]).

    Instances run ``analytics.instance_batch(num_rows)`` at a time, so the
    dense [num_rows] temporaries stay bounded at any fleet size."""
    sig = stages.signature_of(sr=sr, extra=(("num_rows", int(num_rows)),
                                            ("k", int(k))))

    def run(s):
        return jax.lax.map(
            lambda h: analytics.top_k_rows(h, num_rows, k, sr=sr), s,
            batch_size=analytics.instance_batch(num_rows))
    return stages.wrap(run, "service.analytics", sig)


def run_service(states, rows: Array, cols: Array, vals: Array,
                q_rows: Array, q_cols: Array, *,
                rounds: int,
                sr: Semiring = sr_mod.PLUS_TIMES,
                use_kernel: bool = False, lazy_l0: bool = False,
                fused: bool = True, chunk: int = 1,
                batch_mode: str = "grouped",
                l0_mode: str = "auto",
                queries_per_round: int = 1,
                analytics_num_rows: int = 0, analytics_k: int = 8,
                with_queries: bool = True,
                slo_p99_ms: float | None = None) -> Tuple[object, dict]:
    """Interleave ``rounds`` ingest rounds with query batches.

    ``rows``/``cols``/``vals`` are the full [I, T, B] stream (T must divide
    by ``rounds``); ``q_rows``/``q_cols`` are [Q] query vectors reissued
    every batch (fresh keys per batch would re-trace nothing — shapes are
    static).  ``with_queries=False`` runs the identical ingest schedule
    with no read path — the ingest-only baseline the <10% interference
    criterion compares against.  Returns (final states, stats dict).

    Query-batch latency routes through the shared mergeable
    ``obs.metrics`` histogram (one percentile implementation for the
    service, benchmarks, and the monitor): ``latency_p50_s`` is now an
    interpolated p50 and ``latency_p95_s``/``latency_p99_s`` ride
    alongside; ``latency_max_s`` stays exact.  ``slo_p99_ms`` arms the
    per-batch SLO check — ``slo_attainment``/``slo_breaches`` land in the
    stats and each breach emits an ``slo_breach`` obs event when tracing
    is enabled.  Ingest rounds run under a non-raising
    ``obs.slo.StallDetector`` (``stalled_rounds``).
    """
    I, T, B = rows.shape
    if rounds < 2:
        # round 0 is the untimed warmup/compile round: with rounds=1 the
        # WHOLE stream ingests inside it and the loop below never runs, so
        # the reported rates were silently 0.0 — refuse instead.
        raise ValueError(
            f"rounds must be >= 2 (round 0 is the untimed warmup round; "
            f"rounds={rounds} would ingest the whole stream in it and "
            f"report zero rates)")
    if T % rounds:
        raise ValueError(f"stream length {T} not divisible by rounds "
                         f"{rounds}")
    per = T // rounds
    ingest = make_ingest_fn(sr, use_kernel=use_kernel, lazy_l0=lazy_l0,
                            fused=fused, chunk=chunk, batch_mode=batch_mode)
    query = make_point_query_fn(sr, use_kernel=use_kernel, l0_mode=l0_mode)
    analytic = (make_analytics_fn(analytics_num_rows, analytics_k, sr)
                if analytics_num_rows else None)

    # warmup/compile outside the timed region (the service steady state is
    # what the paper's rates describe, not the first-dispatch compile)
    states = jax.block_until_ready(
        ingest(states, rows[:, :per], cols[:, :per], vals[:, :per]))
    if with_queries:
        jax.block_until_ready(query(states, q_rows, q_cols))
        if analytic is not None:
            jax.block_until_ready(analytic(states))

    ingest_wall = 0.0
    query_wall = 0.0          # point-lookup batches only
    analytics_wall = 0.0      # top-k batches, kept separate so queries/s
    n_queries = 0             # is the point-lookup rate, not a blend
    tracker = obs_slo.SLOTracker(target_p99_ms=slo_p99_ms, name="query")
    stall = obs_slo.StallDetector(name="service.ingest")
    for rnd in range(1, rounds):
        sl = slice(rnd * per, (rnd + 1) * per)
        t0 = time.perf_counter()
        states = ingest(states, rows[:, sl], cols[:, sl], vals[:, sl])
        jax.block_until_ready(states)
        dt = time.perf_counter() - t0
        ingest_wall += dt
        stall.observe(dt)
        if with_queries:
            for _ in range(queries_per_round):
                t0 = time.perf_counter()
                jax.block_until_ready(query(states, q_rows, q_cols))
                dt = time.perf_counter() - t0
                query_wall += dt
                tracker.observe(dt)
                n_queries += I * q_rows.shape[0]
            if analytic is not None:
                t0 = time.perf_counter()
                jax.block_until_ready(analytic(states))
                analytics_wall += time.perf_counter() - t0
    timed_rounds = rounds - 1
    n_updates = I * timed_rounds * per * B
    hist = tracker.hist
    stats = dict(
        updates_per_s=n_updates / ingest_wall if ingest_wall else 0.0,
        queries_per_s=n_queries / query_wall if query_wall else 0.0,
        ingest_wall_s=ingest_wall,
        query_wall_s=query_wall,
        analytics_wall_s=analytics_wall,
        n_updates=n_updates,
        n_queries=n_queries,
        # one-release aliases of the histogram percentiles (pre-obs names)
        latency_p50_s=hist.percentile(50) if tracker.n else 0.0,
        latency_p95_s=hist.percentile(95) if tracker.n else 0.0,
        latency_p99_s=hist.percentile(99) if tracker.n else 0.0,
        latency_max_s=hist.vmax if tracker.n else 0.0,
        slo_p99_ms=slo_p99_ms,
        slo_attainment=tracker.attainment(),
        slo_breaches=tracker.breaches,
        stalled_rounds=stall.stalls,
        rounds=timed_rounds,
    )
    obs_trace.emit("service_summary", n_updates=n_updates,
                   ingest_wall_s=ingest_wall, n_queries=n_queries,
                   query_wall_s=query_wall,
                   stalled_rounds=stall.stalls,
                   slo=tracker.summary() if tracker.n else None)
    return states, stats
