"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload paper-ingest --seed 7 --seconds 40 \
        --trace 0

The cell, its configuration, traffic mix and metrics come from
``BENCHMARK.json`` at the root of the checkout.  Set-up (``setup_s``, from
process start to the window's start) builds the cell's programs and the
cycle's inputs from ``--seed`` and runs each program once.  The window is
whole cycles until ``--seconds`` have passed (``harness``); with
``--trace 1`` it is one cycle under the profiler, and the line carries the
per-layer metrics instead of the end-to-end ones.  Then the fleet the last
cycle left is compared with the numpy reference (``check``): each number compared is printed beside its
limit, last on standard error and last in the result line.

Exits non-zero, printing no result, without a TPU, with fewer chips than
the cell asks for, or on a device kind missing from ``bench/peaks.json``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

from bench import check, harness, spec  # noqa: E402
from bench import trace as trace_mod  # noqa: E402
from repro import stages  # noqa: E402

CACHE_DIR = os.path.join(_ROOT, ".jax-cache")
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_JAX_COMPILES = [0]


def _on_duration(event: str, _secs: float, **_kw) -> None:
    if event in _COMPILE_EVENTS:
        _JAX_COMPILES[0] += 1


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def chips(count: int):
    """The first ``count`` TPU devices, or exit non-zero."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench/run.py: needs a TPU, JAX found "
                 f"{devices[0].platform!r}")
    if len(devices) < count:
        sys.exit(f"bench/run.py: the cell needs {count} chips, found "
                 f"{len(devices)}")
    try:
        spec.peaks(devices[0].device_kind)
    except KeyError as e:
        sys.exit(f"bench/run.py: {e}")
    return devices[:count]


def end_to_end(cyc, cycles: int, wall: float, setup_s: float) -> dict:
    return {"setup_s": setup_s,
            "updates_per_s": cycles * cyc.updates / wall}


def run(cell: dict, cfg: dict, traffic: dict, e2e: list, per_layer: list,
        seed: int, seconds: float, traced: bool, devices,
        keep_trace: str = None) -> dict:
    """One run of ``cell`` on ``devices``; returns the result line."""
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    cyc = harness.setup(cfg, traffic, seed, devices[0])
    stages_compiles = stages.stats()["compiles"]
    jax_compiles = _JAX_COMPILES[0]
    setup_s = time.perf_counter() - T_START
    phases = dict(start=setup_s - sum(cyc.phases.values()), **cyc.phases)
    log(f"setup_s {setup_s:.3f} ("
        + ", ".join(f"{k} {v:.3f}" for k, v in phases.items())
        + "); stages.stats "
        + json.dumps({k: v for k, v in stages.stats().items()
                      if k != "per_entry"}))

    trace_dir = None
    if traced:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="bench-trace-")
        stages.set_trace_hook(lambda **_: None,
                              annotation=jax.profiler.TraceAnnotation)
        jax.profiler.start_trace(trace_dir)
        try:
            states, cycles, wall = harness.window(
                cyc, seconds, span=jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
            stages.set_trace_hook(None)
    else:
        states, cycles, wall = harness.window(cyc, seconds)

    d_stages = stages.stats()["compiles"] - stages_compiles
    d_jax = _JAX_COMPILES[0] - jax_compiles
    log(f"window: {cycles} cycles in {wall:.3f} s; compiles in window: "
        f"stages {d_stages}, jax lowerings+compiles {d_jax}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    ids = cyc.ids
    fleet_host = harness.read_fleet(states, ids)
    harness.delete(states)
    del states
    spills = fleet_host["spills"]
    log(f"exact update count {fleet_host['count']:,} (expected "
        f"{cyc.updates:,}); overflow {fleet_host['overflow']}")
    log("spills per instance into layers 1.."
        f"{spills.shape[-1] - 1} (min/mean): "
        + ", ".join(f"{spills[:, d].min()}/{spills[:, d].mean():.2f}"
                    for d in range(spills.shape[-1] - 1))
        + f"; last-layer pressure events {int(spills[:, -1].sum())}")
    log(f"reference instances {ids}")
    ref = harness.reference(cyc, ids)
    numbers = harness.compare(cyc, fleet_host, ref, d_stages + d_jax)
    correct = check.passed(numbers)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": correct,
           "attempted": cycles * len(cyc.stream),
           "failed": 0}
    if traced:
        reading = trace_mod.read(trace_dir,
                                 counts=dict(updates=cycles * cyc.updates))
        if keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reading.busy_s
        device["window_s"] = reading.window_s
        metrics = {}
        for m in per_layer:
            v = spec.reader(m["name"])(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        out["breakdown"] = reading.breakdown()
    else:
        values = end_to_end(cyc, cycles, wall, setup_s)
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in e2e if m["name"] in values}
    out["device"] = device
    for name, v, lim in numbers:
        log(f"check {name} {v} limit {lim}")
    out["checks"] = check.as_dict(numbers)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="keep the profiler trace in this directory")
    args = ap.parse_args(argv)
    bench = spec.load()
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    devices = chips(cell["chips"])
    if cell["chips"] != 1:
        sys.exit("bench/run.py: only one-chip cells are implemented")
    stages.set_cache_dir(CACHE_DIR)
    out = run(cell, cfg, traffic,
              spec.metrics_for(bench, "end_to_end", cell["name"]),
              spec.metrics_for(bench, "per_layer", cell["name"]),
              args.seed, args.seconds, bool(args.trace), devices,
              keep_trace=args.keep_trace)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
