"""Attribute a traced cycle's device time to the program's named scopes.

The ingest program names its layers with ``jax.named_scope``:
``cohort.d0`` (the append cohort), ``cohort.d<d>`` (depth d's member
loop), ``cohort.take`` / ``cohort.put`` (a member's slices and
write-backs) and ``canon.sort`` / ``canon.value_sum`` /
``canon.key_scatter`` (the canonicalization's co-sort, value sum and key
scatters).  A device trace's op events carry no scope, only the
instruction's name, so ``stages.op_scopes`` gives the join: per HLO module
name, the ``op_name`` path of every instruction of the executable's
optimized HLO.  ``reduce`` joins ``trace.reduce``'s per-call op times to
the call's table by instruction name.

``run.py`` does not call this module yet.  Run it on the chip as

    python3 bench/scopes.py --workload paper-ingest --seed 7
    python3 bench/scopes.py --probe bench/testdata

The first runs the cell's set-up and one traced cycle, as
``run.py --trace 1`` does, and prints one JSON line: the cell's accepted
per-layer metrics, the scope metrics below, the coverage of each call
and the front door's set-up counters (a warm run loads every program from
the persistent cache: ``disk_hits`` > 0, ``compiles`` 0).  The second
records ``probe-scoped.xplane.pb.gz`` and ``probe-scoped.json`` (tables
and counts) at a tiny size for the tests.
"""
import argparse
import collections
import dataclasses
import gzip
import json
import os
import re
import shutil
import sys
import tempfile
from typing import Dict, Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import trace  # noqa: E402

# Scopes are read only where the tables know at least this share of a
# call's device time: a table that misses ops hands their time to no scope.
MIN_COVERAGE = 0.99
# The program's scope names are dotted, ``<layer>.<part>``; JAX's own path
# components (``jit(run)``, ``while``, ``branch_1_fun``) are not.
_SCOPE = re.compile(r"[a-z][a-z0-9_]*\.[a-z0-9_]+")
PROBE = "probe-scoped"

# call kind -> HLO module name -> instruction name -> op_name
Tables = Dict[str, Dict[str, Dict[str, str]]]


def scope_path(op_name: str) -> str:
    """The program's scopes in an ``op_name``, outermost first:
    ``jit(run)/while/body/cohort.d1/cond/.../canon.sort/sort`` ->
    ``cohort.d1/canon.sort``; ``""`` when unscoped."""
    return "/".join(c for c in op_name.split("/") if _SCOPE.fullmatch(c))


@dataclasses.dataclass
class ScopeReading:
    """Op self seconds per call kind: all of them (``device_s``, from
    ``trace.reduce``), those whose instruction the tables know
    (``known_s``), and those by scope path (``scope_s``; ``""``
    for a known op under no scope)."""

    device_s: Dict[str, float]
    known_s: Dict[str, float]
    scope_s: Dict[str, Dict[str, float]]

    def coverage(self, call: str) -> Optional[float]:
        """Share of ``call``'s device time whose op the tables know."""
        total = self.device_s.get(call)
        return self.known_s.get(call, 0.0) / total if total else None

    def under(self, call: str, *scopes: str) -> Optional[float]:
        """Self seconds of ``call``'s ops under any of ``scopes``; None
        unless every scope names some op and coverage reaches
        ``MIN_COVERAGE``."""
        paths = {p: set(p.split("/")) for p in self.scope_s.get(call, {})}
        if not all(any(sc in parts for parts in paths.values())
                   for sc in scopes):
            return None
        cov = self.coverage(call)
        if cov is None or cov < MIN_COVERAGE:
            return None
        return sum(s for p, s in self.scope_s[call].items()
                   if paths[p].intersection(scopes))


def _by_instruction(modules: Dict[str, Dict[str, str]]) -> Dict[str, str]:
    """One call's tables without their module level: ``trace.reduce``
    keeps an op's instruction, not its module.  An instruction that two of
    the call's modules scope differently is left out, so its time counts
    as unknown and lowers the coverage."""
    out: Dict[str, str] = {}
    clash = set()
    for table in modules.values():
        for instr, op_name in table.items():
            if out.setdefault(instr, op_name) != op_name:
                clash.add(instr)
    return {k: v for k, v in out.items() if k not in clash}


def reduce(reading: trace.Reading, tables: Tables) -> ScopeReading:
    """``trace.reduce``'s op self times per call, joined by instruction
    name (the first word of an op's label) to the call's tables."""
    known_s = collections.Counter()
    scope_s = collections.defaultdict(collections.Counter)
    for call, ops in reading.op_s.items():
        table = _by_instruction(tables.get(call, {}))
        for label, seconds in ops.items():
            op_name = table.get(label.split(" ", 1)[0])
            if op_name is not None:
                known_s[call] += seconds
                scope_s[call][scope_path(op_name)] += seconds
    return ScopeReading(dict(reading.device_s), dict(known_s),
                        {k: dict(v) for k, v in scope_s.items()})


def _per(seconds: Optional[float], n) -> Optional[float]:
    return None if seconds is None or not n else seconds * 1e9 / n


def metrics(r: ScopeReading, counts: dict) -> Dict[str, Optional[float]]:
    """The per-layer readings the scopes and counters give; None where a
    scope or a count is missing, or coverage of ``ingest`` is short."""
    ingest = lambda *scopes: r.under("ingest", *scopes)  # noqa: E731
    updates = counts.get("updates")
    return {
        "append_ns_per_update": _per(ingest("cohort.d0"), updates),
        "merge_d1_ns_per_slot": _per(ingest("cohort.d1"),
                                     counts.get("merged_slots_d1")),
        "merge_d2_ns_per_slot": _per(ingest("cohort.d2"),
                                     counts.get("merged_slots_d2")),
        "canon_value_sum_ns_per_update": _per(ingest("canon.value_sum"),
                                              updates),
        "canon_key_scatter_ns_per_update": _per(
            ingest("canon.key_scatter"), updates),
        "cohort_move_ns_per_update": _per(
            ingest("cohort.take", "cohort.put"), updates),
        "setup_lower_s": counts.get("setup_lower_s"),
        "setup_load_s": counts.get("setup_load_s"),
    }


def merges_per_depth(spills):
    """The merges that landed at each depth 1..L-1, from the program's
    ``HierAssoc.spills`` counter ([..., L], any leading instance axes): a
    depth-d merge adds 1 to ``spills[:d]``, so ``spills[d-1] - spills[d]``
    merges landed at depth d < L-1 and ``spills[L-2]`` at the deepest
    (``spills[L-1]`` also counts last-layer pressure, so it is not used).
    Returns an int64 array [..., L-1]; column d-1 is depth d."""
    s = np.asarray(spills, np.int64)
    deeper = np.concatenate([s[..., 1:-1], np.zeros_like(s[..., :1])],
                            axis=-1)
    return s[..., :-1] - deeper


def merged_slots(cfg: dict, spills, cycles: int) -> Dict[str, int]:
    """Slots sorted by the merges of ``cycles`` cycles at each depth
    (``merged_slots_d<d>``), from the spill counters of the fleet one cycle
    left (``merges_per_depth``) and ``hier.merge_width``."""
    from repro.core import hier
    block = cfg["block_size"]
    caps = hier.layer_capacities(tuple(cfg["cuts"]), block)
    merges = merges_per_depth(spills).sum(axis=0)
    return {f"merged_slots_d{d}": cycles * int(n) * hier.merge_width(
                caps, block, d)
            for d, n in enumerate(merges, start=1)}


def setup_seconds(stats: dict) -> Dict[str, float]:
    """``lower_s`` and ``load_s`` summed over the front door's entries
    (``stages.stats()``), as ``setup_lower_s`` and ``setup_load_s``."""
    entries = stats["per_entry"].values()
    return {f"setup_{k}": sum(e[k] for e in entries)
            for k in ("lower_s", "load_s")}


def load_probe(testdata: str, out: str):
    """(xplane path, tables, counts) of the recorded scoped probe, its
    trace unpacked into the directory ``out``."""
    with open(os.path.join(testdata, f"{PROBE}.json")) as f:
        meta = json.load(f)
    path = os.path.join(out, f"{PROBE}.xplane.pb")
    with gzip.open(os.path.join(testdata, f"{PROBE}.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path, meta["tables"], meta["counts"]


# ------------------------------------------------------------- on the chip --

# The probe: the fleet of ``probe.xplane.pb.gz`` (4 instances, block 1,024,
# cuts 2,048 / 16,384 / 131,072), ingest only, 24 blocks per instance in 6
# rounds so that merges reach depth 2.
PROBE_SIZE = dict(instances_per_chip=4, block_size=1024,
                  cuts=[2048, 16384, 131072])
PROBE_TRAFFIC = dict(name=PROBE, blocks_per_cycle=24, rounds_per_cycle=6)


def traced_cycle(cfg: dict, traffic: dict, seed: int, device, trace_dir):
    """Set-up, then one cycle under the profiler with the harness's
    ``bench.<call>`` spans and ``stages``' entry annotations; returns
    (tables, counts, ``stages.stats()`` after set-up)."""
    import jax

    from bench import harness
    from repro import stages

    cyc = harness.setup(cfg, traffic, seed, device)
    stats = stages.stats()
    counts = setup_seconds(stats)
    stages.set_trace_hook(lambda **_: None,
                          annotation=jax.profiler.TraceAnnotation)
    jax.profiler.start_trace(trace_dir)
    try:
        states, cycles, _ = harness.window(
            cyc, 0.0, span=jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
        stages.set_trace_hook(None)
    spills = np.asarray(states.spills)
    harness.delete(states)
    counts.update(updates=cycles * cyc.updates,
                  **merged_slots(cfg, spills, cycles))
    tables = {"ingest": stages.op_scopes(cyc.fleet.ingest.entry)}
    return tables, counts, stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--probe", metavar="DIR",
                    help="record the scoped probe into DIR")
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)

    from bench import run, spec
    from repro import stages
    bench = spec.load()
    if args.probe:
        cfg = dict(spec.config(bench, "d4m-paper"), **PROBE_SIZE)
        traffic, per_layer = PROBE_TRAFFIC, []
    else:
        cell = spec.cell(bench, args.workload)
        cfg = spec.config(bench, cell["config"])
        traffic = spec.traffic(cell["traffic"])
        per_layer = spec.metrics_for(bench, "per_layer", cell["name"])
    devices = run.chips(1)
    stages.set_cache_dir(run.CACHE_DIR)
    trace_dir = args.keep_trace or tempfile.mkdtemp(prefix="scopes-")
    tables, counts, stats = traced_cycle(
        cfg, traffic, args.seed, devices[0], trace_dir)
    from jax.profiler import ProfileData
    path = trace.xplane_path(trace_dir)
    old = trace.reduce(ProfileData.from_file(path), counts)
    r = reduce(old, tables)
    split = collections.Counter()
    for p, s in r.scope_s.get("ingest", {}).items():
        top = p.split("/")[0]
        split[top if top.startswith("cohort.d") or not p else "other"] += s
    split["unknown"] = r.device_s["ingest"] - r.known_s.get("ingest", 0.0)
    out = dict(
        device=devices[0].device_kind,
        setup_stats={k: v for k, v in stats.items() if k != "per_entry"},
        counts=counts,
        accepted={m["name"]: spec.reader(m["name"])(old) for m in per_layer},
        scopes=metrics(r, counts),
        coverage={c: r.coverage(c) for c in r.device_s},
        ingest_device_s=old.device_s.get("ingest"),
        ingest_split_s=dict(split),
        canon_sort_ns_per_update=_per(r.under("ingest", "canon.sort"),
                                      counts["updates"]),
        scope_s=r.scope_s.get("ingest", {}))
    if args.probe:
        os.makedirs(args.probe, exist_ok=True)
        with open(path, "rb") as src, gzip.open(os.path.join(
                args.probe, f"{PROBE}.xplane.pb.gz"), "wb") as dst:
            shutil.copyfileobj(src, dst)
        with open(os.path.join(args.probe, f"{PROBE}.json"), "w") as f:
            json.dump(dict(tables=tables, counts=counts), f)
    if args.keep_trace is None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
