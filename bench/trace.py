"""Reduce a profiler trace to device busy time and per-call device time.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` and nothing else:

* device ops: the events of each TPU plane's ``XLA Ops`` line;
* host spans: the benchmark's ``bench.<call>`` annotations (and the
  ``stages`` entry annotations) on the host plane;
* busy time: the union of a device's op intervals inside the window (the
  first span's start to the last span's end), averaged over devices;
* attribution: an op belongs to the last ``bench.`` span that started
  before it.  That is sound because the loop is closed: each call is
  waited for before the next is sent, so a call's device work falls
  between its span's start and the next span's start.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
# An op event is named by its HLO instruction: "%sort.107 = (s32[...]) sort(".
_HLO = re.compile(r"%(?P<short>[^ ]+) = (?P<type>.*?) (?P<opcode>[a-z][a-z0-9_-]*)\(")
_KIND = re.compile(r"\bkind=(k\w+)")
_SUFFIX = re.compile(r"[.:]\d+$")

Interval = Tuple[int, int]


def xplane_path(trace_dir: str) -> str:
    """The one ``.xplane.pb`` under ``trace_dir`` (a file path passes)."""
    if os.path.isfile(trace_dir):
        return trace_dir
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} .xplane.pb under {trace_dir}")
    return found[0]


def parse_op(name: str) -> Tuple[str, str]:
    """(label, opcode) of an op event: the instruction's name and result
    type (``sort.107 (s32[17000000], ...)``, cut to 80 characters) and its
    HLO opcode (``sort``; a fusion's carries its kind, ``fusion:kCustom``).
    A name that is not an HLO instruction is its own label, and its opcode
    is the name without its number."""
    m = _HLO.match(name)
    if not m:
        return name[:80], _SUFFIX.sub("", name)
    opcode = m["opcode"]
    if opcode == "fusion":
        kind = _KIND.search(name, m.end())
        if kind:
            opcode = f"fusion:{kind[1]}"
    return (f"{m['short']} {m['type']}"[:80], opcode)


def is_sort(opcode: str) -> bool:
    return opcode == "sort"


def is_scatter(opcode: str) -> bool:
    """A scatter: XLA's TPU backend emits each as a ``kCustom`` fusion (the
    merge's ``segment_sum`` and key scatters after each sort)."""
    return opcode in ("scatter", "fusion:kCustom")


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class Reading:
    """What the per-layer readers (``bench/metrics``) read.  Op times are
    self times: a ``while`` or ``conditional`` event holds its body's ops,
    so each event's nested events are taken out of its own time."""

    window_s: float
    busy_s: float
    counts: Dict[str, int]                   # updates in the window
    calls: Dict[str, int]                    # spans per call kind
    device_s: Dict[str, float]               # op self seconds per call kind
    op_s: Dict[str, Dict[str, float]]        # call kind -> op label -> s
    opcode: Dict[str, str]                   # op label -> HLO opcode
    gaps: List[Tuple[str, float]]            # idle gaps, by host activity

    def ops_of(self, call: str, pred) -> float:
        """Self seconds of ``call``'s ops whose opcode ``pred`` accepts."""
        return sum(s for k, s in self.op_s.get(call, {}).items()
                   if pred(self.opcode[k]))

    def breakdown(self, n: int = 10) -> dict:
        ops = collections.Counter({f"{call}/{k}": s
                                   for call, per in self.op_s.items()
                                   for k, s in per.items()})
        return {"device_ops": [[k, s] for k, s in ops.most_common(n)],
                "idle_gaps": [[k, s] for k, s in
                              sorted(self.gaps, key=lambda g: -g[1])[:n]]}


def _events(line):
    for e in line.events:
        yield e.name, int(e.start_ns), int(e.end_ns)


def self_times(events: List[Tuple[str, int, int]]) -> List[int]:
    """Each event's duration less that of the events nested in it
    (``events`` sorted by start; nested events lie inside their parent)."""
    out = [e - s for _, s, e in events]
    stack: List[int] = []
    for i, (_, s, e) in enumerate(events):
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]] -= e - s
        stack.append(i)
    return out


def planes(pd) -> Tuple[list, list]:
    """(device planes with an ops line, host planes)."""
    dev, host = [], []
    for p in pd.planes:
        if p.name.startswith("/device:"):
            if any(l.name == OPS_LINE for l in p.lines):
                dev.append(p)
        elif p.name.startswith("/host:"):
            host.append(p)
    return dev, host


def reduce(pd, counts: Dict[str, int]) -> Reading:
    dev, host = planes(pd)
    if not dev:
        raise ValueError("the trace has no device plane with an "
                         f"{OPS_LINE!r} line")
    spans = sorted((s, e, name[len(SPAN_PREFIX):])
                   for p in host for l in p.lines
                   for name, s, e in _events(l)
                   if name.startswith(SPAN_PREFIX))
    if not spans:
        raise ValueError(f"the trace has no {SPAN_PREFIX!r} host spans")
    lo, hi = spans[0][0], max(e for _, e, _ in spans)
    starts = [s for s, _, _ in spans]

    def call_at(t: int) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return spans[i][2] if i >= 0 else "before"

    def activity_at(t: int) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and spans[i][1] >= t:
            return spans[i][2]
        return "between calls"

    busy_total = 0
    device_s: Dict[str, float] = collections.Counter()
    op_s: Dict[str, Dict[str, float]] = collections.defaultdict(
        collections.Counter)
    opcode: Dict[str, str] = {}
    gaps: List[Tuple[str, float]] = []
    for p in dev:
        line = next(l for l in p.lines if l.name == OPS_LINE)
        events = sorted((ev for ev in _events(line)
                         if ev[2] > lo and ev[1] < hi),
                        key=lambda ev: (ev[1], -ev[2]))
        for (name, s, _), own in zip(events, self_times(events)):
            call = call_at(s)
            label, opcode[label] = parse_op(name)
            device_s[call] += own * 1e-9
            op_s[call][label] += own * 1e-9
        busy = clip(union([(s, e) for _, s, e in events]), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps.append((activity_at((g0 + g1) // 2), (g1 - g0) * 1e-9))
    calls = collections.Counter(name for _, _, name in spans)
    return Reading(window_s=(hi - lo) * 1e-9,
                   busy_s=busy_total * 1e-9 / len(dev),
                   counts=dict(counts), calls=dict(calls),
                   device_s=dict(device_s),
                   op_s={k: dict(v) for k, v in op_s.items()},
                   opcode=opcode, gaps=gaps)


def read(trace_dir: str, counts: Dict[str, int]) -> Reading:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(xplane_path(trace_dir)), counts)
