"""obskit (repro/obs + launch/monitor): metrics, tracing, SLOs (ISSUE 9).

Covers the acceptance grid:

  * mergeable log-bucket histograms: percentile accuracy within the bucket
    relative-error bound, merge == union (order-independent), JSONL
    round-trip with schema pinning;
  * ``hier.metrics_snapshot``: one dispatch returns fleet truth — per-layer
    nnz/occupancy, spills, depth histogram, and the EXACT (hi, lo) update
    counter including uint32 carry wraps — matching the host-side oracles;
  * observability-off invariance: with tracing off, instrumented entries
    add ZERO lowerings/compiles (``stages.stats()``) and the production
    jaxpr is bit-identical whether the dispatch hook is installed or not
    (the PR 7 debug-twin discipline applied to obs);
  * dispatch spans: obs.jsonl records are schema-complete with monotonic
    per-process sequence numbers, memory/disk/compile provenance, the
    lower/load split of compile seconds, and start/end on the epoch clock
    of a profiler trace;
  * per-entry ``stages.stats()`` + the ``stats(reset=True)``
    concurrent-emission guarantee (no count lost between read and reset);
  * SLO layer: tracker attainment/breaches, stall detector;
  * ``run_service`` percentile fix: p50 <= p95 <= p99 <= max from the
    shared histogram, old field names still present;
  * launch/monitor aggregation: multi-process rates, strict schema gate,
    and the end-to-end 1% agreement between OBS_SUMMARY.json fleet
    updates/s and ``hier.exact_update_count`` / wall.
"""
import argparse
import dataclasses
import json
import math
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import stages
from repro.core import distributed, hier, stream
from repro.launch import ingest as launch_ingest
from repro.launch import monitor
from repro.obs import metrics, slo, trace
from repro.query import service

CUTS = (48, 192)
BLOCK = 16


@pytest.fixture
def obs_dir(tmp_path):
    """Arm tracing into a throwaway dir for one test; always disarm (the
    hook and the fd are process-global state)."""
    d = tmp_path / "obs"
    trace.enable(str(d))
    try:
        yield str(d)
    finally:
        trace.disable()


def _fleet(i=3, cuts=CUTS, block=BLOCK):
    states = distributed.create_instances(i, cuts, block)
    key = jax.random.PRNGKey(7)
    shape = (i, 4, block)
    rows = jax.random.randint(key, shape, 0, 4096, jnp.int32)
    cols = jax.random.randint(jax.random.fold_in(key, 1), shape, 0, 4096,
                              jnp.int32)
    vals = jnp.ones(shape, jnp.float32)
    sig = stages.signature_of(cuts=cuts, block_size=block, lazy_l0=True,
                              batch_mode="grouped")
    run = stream.ingest_instances_jit(sig, with_telemetry=False)
    return run(states, rows, cols, vals)


# ------------------------------------------------------------- histogram ----


def test_histogram_percentiles_within_bucket_error():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-7.0, sigma=1.5, size=4000)
    h = metrics.Histogram()
    for s in samples:
        h.observe(float(s))
    # one log bucket spans a factor of 10**(1/BPD); the interpolated value
    # can be off by at most that ratio either way
    tol = 10 ** (1 / metrics.BUCKETS_PER_DECADE)
    for q in (10, 50, 90, 95, 99):
        exact = float(np.percentile(samples, q))
        got = h.percentile(q)
        assert exact / tol <= got <= exact * tol, (q, exact, got)
    assert h.count == len(samples)
    assert h.vmin == samples.min() and h.vmax == samples.max()
    np.testing.assert_allclose(h.mean(), samples.mean(), rtol=1e-9)


def test_histogram_merge_is_union_and_order_independent():
    rng = np.random.default_rng(1)
    a_s, b_s = rng.exponential(1e-3, 500), rng.exponential(5e-2, 700)
    a, b, union = metrics.Histogram(), metrics.Histogram(), \
        metrics.Histogram()
    for s in a_s:
        a.observe(float(s))
        union.observe(float(s))
    for s in b_s:
        b.observe(float(s))
        union.observe(float(s))
    ab = metrics.Histogram().merge(a).merge(b)
    ba = metrics.Histogram().merge(b).merge(a)
    for m in (ab, ba):
        assert m.buckets == union.buckets
        assert m.count == union.count
        for q in (50, 95, 99):
            assert m.percentile(q) == union.percentile(q)


def test_histogram_roundtrip_and_schema_pin():
    h = metrics.Histogram()
    for v in (1e-6, 3e-4, 2e-2, 5.0):
        h.observe(v)
    h2 = metrics.Histogram.from_dict(json.loads(json.dumps(h.to_dict())))
    assert h2.buckets == h.buckets and h2.count == h.count
    assert h2.percentile(50) == h.percentile(50)
    bad = h.to_dict()
    bad["schema"] = dict(bad["schema"], bpd=999)
    with pytest.raises(ValueError, match="schema"):
        metrics.Histogram.from_dict(bad)


def test_histogram_extremes_clamp_to_observed():
    h = metrics.Histogram()
    h.observe(0.0)          # underflow bucket
    h.observe(1e9)          # overflow bucket
    assert h.percentile(1) == 0.0
    assert h.percentile(99) == 1e9


def test_registry_counters_gauges_histograms():
    reg = metrics.Registry()
    reg.inc("updates", 5)
    reg.inc("updates", 3)
    reg.gauge("occupancy", 0.5)
    reg.histogram("lat").observe(1e-3)
    snap = reg.snapshot()
    assert snap["counters"]["updates"] == 8
    assert snap["gauges"]["occupancy"] == 0.5
    assert snap["histograms"]["lat"]["count"] == 1


# ------------------------------------------------------- metrics_snapshot ---


def test_metrics_snapshot_matches_host_oracles():
    states = _fleet()
    snap = jax.device_get(hier.metrics_snapshot(states))
    nnz = np.asarray(jax.device_get(states.nnz_per_layer()))   # [L, I]
    np.testing.assert_array_equal(np.asarray(snap["nnz"]), nnz.sum(axis=1))
    caps = states.capacities
    np.testing.assert_allclose(
        np.asarray(snap["occupancy"]),
        [nnz[li].mean() / caps[li] for li in range(len(caps))], rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(snap["spills"]),
        np.asarray(jax.device_get(states.spills)).sum(axis=0))
    depth = (nnz > 0).astype(int) * (np.arange(len(caps))[:, None] + 1)
    depth = depth.max(axis=0)                                  # [I]
    want_hist = np.bincount(depth, minlength=len(caps) + 1)
    np.testing.assert_array_equal(np.asarray(snap["depth_hist"]), want_hist)
    total = int(snap["updates_lo"]) + (int(snap["updates_hi"]) << 32)
    assert total == hier.exact_update_count(states)


def test_metrics_snapshot_exact_across_uint32_wrap():
    states = _fleet()
    lo = np.array([2**32 - 5, 2**32 - 3, 7], np.uint32)
    hi = np.array([1, 2, 0], np.int32)
    states = dataclasses.replace(states, n_updates=jnp.asarray(lo),
                                 n_updates_hi=jnp.asarray(hi))
    s = metrics.fleet_sample(states)
    want = int(lo.astype(np.int64).sum()) + ((1 + 2) << 32)
    assert s["updates"] == want == hier.exact_update_count(states)


def test_fleet_sample_single_instance():
    h = hier.create(CUTS, BLOCK)
    s = metrics.fleet_sample(h)
    assert s["nnz"] == [0, 0] and s["updates"] == 0
    assert s["depth_hist"] == [1, 0, 0]


# -------------------------------------------------- off-path invariance -----


def test_obs_off_adds_zero_lowerings_and_identical_jaxpr(tmp_path):
    """The tentpole invariance: a warmed entry re-dispatched with tracing
    ON performs zero staging work, and the jaxpr traced under the installed
    hook is bit-identical to the production one (the hook is host-side
    only, so it cannot appear in traced code — J004 stays clean by
    construction)."""
    states = _fleet()
    w = hier.metrics_snapshot_wrapped(
        stages.signature_for_state(states))
    jax.block_until_ready(jax.tree_util.tree_leaves(w(states)))  # warm
    jaxpr_off = str(w.lower(states).jaxpr)
    before = stages.stats()
    trace.enable(str(tmp_path / "obs"))
    try:
        jax.block_until_ready(jax.tree_util.tree_leaves(w(states)))
        after = stages.stats()
        assert after["lowerings"] == before["lowerings"]
        assert after["compiles"] == before["compiles"]
        assert after["memory_hits"] == before["memory_hits"] + 1
        # re-trace the SAME entry while the hook is installed: the traced
        # program must not change (fresh jit so the lowered cache is not
        # consulted)
        jaxpr_on = str(jax.make_jaxpr(w.fn)(states))
    finally:
        trace.disable()
    jaxpr_fresh_off = str(jax.make_jaxpr(w.fn)(states))
    assert jaxpr_on == jaxpr_fresh_off
    assert str(w.lower(states).jaxpr) == jaxpr_off


def test_scoped_ingest_adds_no_lowerings_with_tracing_off_or_on(tmp_path):
    """The ingest program's named scopes are metadata: re-dispatching the
    warmed scoped program, with tracing off and then on, and reading its
    op-to-scope table stage nothing new, and its jaxpr is the same with the
    dispatch hook installed or not."""
    states = distributed.create_instances(3, CUTS, BLOCK)
    shape = (3, 4, BLOCK)
    rows = jnp.arange(np.prod(shape), dtype=jnp.int32).reshape(shape) % 97
    vals = jnp.ones(shape, jnp.float32)
    sig = stages.signature_of(cuts=CUTS, block_size=BLOCK, lazy_l0=True,
                              batch_mode="grouped")
    w = stream.ingest_instances_jit(sig, with_telemetry=False)
    jax.block_until_ready(w(states, rows, rows, vals))         # warm
    before = stages.stats()
    jax.block_until_ready(w(states, rows, rows, vals))         # off
    tables = stages.compiled_for(w, states, rows, rows, vals).op_scopes()
    assert any("cohort.d0" in op for t in tables.values()
               for op in t.values())
    jaxpr_off = str(jax.make_jaxpr(w.fn)(states, rows, rows, vals))
    trace.enable(str(tmp_path / "obs"))
    try:
        jax.block_until_ready(w(states, rows, rows, vals))     # on
        jaxpr_on = str(jax.make_jaxpr(w.fn)(states, rows, rows, vals))
    finally:
        trace.disable()
    after = stages.stats()
    assert after["lowerings"] == before["lowerings"]
    assert after["compiles"] == before["compiles"]
    assert after["memory_hits"] >= before["memory_hits"] + 2
    assert jaxpr_on == jaxpr_off


# ------------------------------------------------------------ trace spans ---


def test_dispatch_spans_schema_and_monotonic_seq(obs_dir):
    states = _fleet()
    for _ in range(3):
        jax.block_until_ready(
            jax.tree_util.tree_leaves(hier.metrics_snapshot(states)))
    trace.emit("custom", foo=1)
    path = trace.out_path()
    records = [json.loads(line) for line in open(path)]
    assert records, "no events written"
    seqs = []
    for rec in records:
        for field in trace.SCHEMA_FIELDS:
            assert field in rec, rec
        seqs.append(rec["seq"])
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    spans = [r for r in records if r["ev"] == "dispatch"]
    assert {s["entry"] for s in spans} >= {"hier.metrics_snapshot"}
    for s in spans:
        assert s["prov"] in ("memory", "disk", "compile")
        assert s["wall_s"] >= 0 and "sig" in s
        # on the trace's epoch clock, bracketing the dispatch's wall time
        assert isinstance(s["t0_ns"], int) and isinstance(s["t1_ns"], int)
        assert s["t1_ns"] - s["t0_ns"] >= s["wall_s"] * 1e9 - 1e3
        assert abs(s["t0_ns"] * 1e-9 - s["t"]) < 60
        assert s["compile_s"] == pytest.approx(s["lower_s"] + s["load_s"],
                                               abs=2e-6)
        if s["prov"] == "memory":
            assert s["lower_s"] == s["load_s"] == 0
    assert any(r["ev"] == "custom" for r in records)


def test_emit_disabled_is_noop(tmp_path):
    assert not trace.enabled()
    assert trace.emit("nope") is False


# ----------------------------------------------- per-entry stages stats -----


def test_stats_per_entry_dispatches_and_wall():
    stages.reset_stats()
    states = _fleet()     # dispatches stream.ingest_instances once
    jax.block_until_ready(
        jax.tree_util.tree_leaves(hier.metrics_snapshot(states)))
    s = stages.stats()
    pe = s["per_entry"]
    assert pe["stream.ingest_instances"]["dispatches"] == 1
    assert pe["hier.metrics_snapshot"]["dispatches"] == 1
    assert all(v["wall_s"] > 0 for v in pe.values())
    assert s["dispatches"] == sum(v["dispatches"] for v in pe.values())
    reg = metrics.Registry()
    metrics.export_stages_gauges(reg)
    snap = reg.snapshot()["gauges"]
    assert snap["stages.entry.hier.metrics_snapshot.dispatches"] == 1
    assert snap["stages.dispatches"] == s["dispatches"]


def test_stats_reset_is_concurrent_emission_safe():
    """N dispatching threads race a collector calling stats(reset=True):
    snapshot+zero happen under one lock, so the per-entry dispatch counts
    across all snapshots sum to exactly the number of dispatches."""
    sig = stages.signature_of(extra=(("test", "obs-concurrent"),))
    w = stages.wrap(lambda x: x + 1, "test.obs_concurrent", sig)
    x = jnp.zeros((8,), jnp.float32)
    jax.block_until_ready(w(x))            # compile outside the race
    stages.reset_stats()
    n_threads, iters = 4, 25
    collected = []
    stop = threading.Event()

    def collect():
        while not stop.is_set():
            collected.append(stages.stats(reset=True))
        collected.append(stages.stats(reset=True))

    def work():
        for _ in range(iters):
            jax.block_until_ready(w(x))

    collector = threading.Thread(target=collect)
    workers = [threading.Thread(target=work) for _ in range(n_threads)]
    collector.start()
    for t in workers:
        t.start()
    for t in workers:
        t.join()
    stop.set()
    collector.join()
    total = sum(s["per_entry"].get("test.obs_concurrent", {})
                .get("dispatches", 0) for s in collected)
    assert total == n_threads * iters


# ------------------------------------------------------------------- SLO ----


def test_slo_tracker_attainment_and_breaches(obs_dir):
    t = slo.SLOTracker(target_p99_ms=1.0, name="t")
    assert t.observe(0.5e-3) is False
    assert t.observe(2e-3) is True
    assert t.observe(0.2e-3) is False
    assert t.breaches == 1 and t.attainment() == pytest.approx(2 / 3)
    summ = t.summary()
    assert summ["count"] == 3 and summ["target_p99_ms"] == 1.0
    recs = [json.loads(line) for line in open(trace.out_path())]
    breaches = [r for r in recs if r["ev"] == "slo_breach"]
    assert len(breaches) == 1 and breaches[0]["slo"] == "t"
    # no target -> perfect attainment, nothing breaches
    free = slo.SLOTracker()
    free.observe(10.0)
    assert free.attainment() == 1.0 and free.breaches == 0


def test_stall_detector_flags_slow_step():
    d = slo.StallDetector(threshold=3.0, warmup_steps=1, name="x")
    assert not any(d.observe(0.1) for _ in range(4))
    assert d.observe(1.0) is True
    assert d.stalls == 1
    # clamped EMA: the stall did not poison the baseline
    assert d.ema_s < 0.2


# ------------------------------------------------- service percentiles ------


def _service_stats(slo_p99_ms=None):
    I, T, B = 2, 8, 8
    rng = np.random.default_rng(3)
    rows = jnp.asarray(rng.integers(0, 512, (I, T, B)), jnp.int32)
    cols = jnp.asarray(rng.integers(0, 512, (I, T, B)), jnp.int32)
    vals = jnp.ones((I, T, B), jnp.float32)
    q = jnp.asarray(rng.integers(0, 512, (8,)), jnp.int32)
    states = distributed.create_instances(I, (16, 64), block_size=B)
    _, stats = service.run_service(states, rows, cols, vals, q, q,
                                   rounds=4, lazy_l0=True,
                                   slo_p99_ms=slo_p99_ms)
    return stats


def test_run_service_reports_interpolated_percentiles():
    stats = _service_stats()
    p50, p95, p99 = (stats["latency_p50_s"], stats["latency_p95_s"],
                     stats["latency_p99_s"])
    assert 0 < p50 <= p95 <= p99
    assert p99 <= stats["latency_max_s"] * (
        10 ** (1 / metrics.BUCKETS_PER_DECADE))
    # pre-obs aliases survive for one release
    for alias in ("latency_p50_s", "latency_max_s"):
        assert alias in stats
    assert stats["slo_attainment"] == 1.0 and stats["slo_breaches"] == 0
    assert "stalled_rounds" in stats


def test_run_service_slo_breach_accounting():
    stats = _service_stats(slo_p99_ms=1e-6)   # impossible target
    # one SLO observation per query batch: every batch breaches
    assert stats["slo_breaches"] == stats["rounds"]
    assert stats["slo_attainment"] == 0.0
    assert stats["slo_p99_ms"] == 1e-6


# ----------------------------------------------------------- monitor --------


def _jl(run, pid, seq, ev, **fields):
    return json.dumps(dict(ev=ev, run=run, seq=seq, t=1000.0 + seq,
                           pid=pid, **fields))


def test_monitor_aggregates_multi_process_rates(tmp_path):
    t = slo.SLOTracker(target_p99_ms=5.0)
    t.observe(1e-3)
    t.observe(10e-3)
    lines = [
        _jl("r1", 1, 1, "fleet", updates=0, nnz=[5, 0], occupancy=[.1, 0],
            spills=[0, 0], depth_hist=[0, 1], overflow=0),
        _jl("r1", 1, 2, "ingest_round", updates=1000, wall_s=2.0),
        _jl("r1", 1, 3, "fleet", updates=1000, nnz=[10, 2],
            occupancy=[.2, .1], spills=[1, 0], depth_hist=[0, 1],
            overflow=0),
        _jl("r2", 2, 1, "ingest_round", updates=300, wall_s=1.0),
        _jl("r2", 2, 2, "service_summary", n_updates=0, ingest_wall_s=0.0,
            n_queries=100, query_wall_s=0.5, slo=t.summary()),
    ]
    (tmp_path / "obs.jsonl").write_text("\n".join(lines) + "\n")
    summary = monitor.main(["--once", "--strict", "--obs-dir",
                            str(tmp_path)])
    assert summary["sources"] == 2
    # counter-delta rate for source 1 (500/s), round-sum for source 2
    assert summary["fleet"]["updates_per_s"] == pytest.approx(800.0)
    assert summary["fleet"]["updates_total"] == 1300
    assert summary["fleet"]["queries_per_s"] == pytest.approx(200.0)
    assert summary["slo"]["attainment"] == pytest.approx(0.5)
    assert summary["slo"]["breaches"] == 1
    assert summary["per_layer"]["nnz"] == [10, 2]
    assert (tmp_path / "OBS_SUMMARY.json").exists()


def test_monitor_strict_fails_on_malformed(tmp_path):
    (tmp_path / "obs.jsonl").write_text(
        _jl("r1", 1, 1, "ingest_round", updates=10, wall_s=1.0)
        + "\nthis is not json\n"
        + json.dumps(dict(ev="x"))       # missing schema fields
        + "\n")
    summary = monitor.main(["--once", "--obs-dir", str(tmp_path)])
    assert summary["malformed_records"] == 2
    with pytest.raises(SystemExit):
        monitor.main(["--once", "--strict", "--obs-dir", str(tmp_path)])


def test_monitor_rate_agrees_with_exact_counter(tmp_path):
    """The tentpole acceptance: OBS_SUMMARY.json fleet updates/s ==
    hier.exact_update_count / wall to within 1%."""
    d = str(tmp_path / "obs")
    args = argparse.Namespace(
        instances=2, blocks=8, block_size=32, rounds=4, cuts="64,256",
        scale=10, seed=0, ckpt_dir="", ckpt_every=4, resume=False,
        verbose=False, layered=False, lazy_l0="auto", chunk=1,
        use_kernel=False, batch_mode="grouped",
        precompile=False, obs=True, obs_dir=d)
    try:
        out = launch_ingest.run(args)
    finally:
        trace.disable()
    summary = monitor.main(["--once", "--strict", "--obs-dir", d])
    counter_rate = out["n_updates_counter"] / out["wall_s"]
    assert summary["fleet"]["updates_per_s"] == pytest.approx(
        counter_rate, rel=0.01)
    assert summary["fleet"]["updates_total"] == out["n_updates_counter"]
    assert not math.isnan(summary["fleet"]["updates_per_s"])
    spans = summary["dispatch"]
    assert "stream.ingest_instances" in spans
    assert "hier.metrics_snapshot" in spans
