"""The trace reduction: busy time, attribution of device ops to the calls
whose spans hold them, idle gaps, and the per-layer readers.

``testdata/probe.xplane.pb.gz`` was recorded on one TPU v5e by a traced
run of the harness at a small size: 4 d4m instances (block 1,024, cuts
2,048/16,384/131,072, R-MAT scale 22), one cycle of 4 rounds of 2 blocks,
each round then 2 lookup batches of 256 keys and a top-8 batch."""
import glob
import gzip
import os
from types import SimpleNamespace as NS

import pytest

from bench import spec, trace

BENCH = spec.load()
TESTDATA = os.path.join(spec.BENCH, "testdata")
# every reader on file, also those no cell reports yet (see PERF.md)
READERS = sorted(os.path.basename(p)[:-len(".py")] for p in glob.glob(
    os.path.join(spec.BENCH, "metrics", "*.py")))


PROBE_COUNTS = dict(updates=4 * 8 * 1024, lookup=8, topk=4)


def _line(name, events):
    return NS(name=name, events=[NS(name=n, start_ns=s, end_ns=e)
                                 for n, s, e in events])


def _profile():
    host = NS(name="/host:CPU", lines=[_line("python", [
        ("bench.create", 0, 100), ("bench.ingest", 100, 1000),
        ("bench.lookup", 1000, 1300), ("bench.topk", 1300, 2000)])])
    dev = NS(name="/device:TPU:0", lines=[
        _line("XLA Modules", [("jit_run", 110, 990)]),
        _line(trace.OPS_LINE, [
            ("%broadcast.1 = s32[8]{0} broadcast(s32[] %c)", 10, 60),
            ("%while.2 = (s32[], s32[8]) while((s32[], s32[8]) %t)", 110,
             900),
            ("%sort.3 = (s32[8]{0}, f32[8]{0}) sort(s32[8] %a, f32[8] %b)",
             120, 620),
            ("%fusion.7 = f32[8]{0} fusion(f32[8] %s), kind=kCustom", 620,
             900),
            ("%fusion.2 = f32[8]{0} fusion(f32[8] %s), kind=kLoop", 1010,
             1210),
            ("%all-reduce.1 = f32[8]{0} all-reduce(f32[8] %x)", 1210, 1250),
            ("%reduce.4 = f32[8]{0} reduce(f32[8] %y, f32[] %z)", 1400,
             1900)])])
    return NS(planes=[host, dev])


def test_reduce_synthetic_profile():
    r = trace.reduce(_profile(), counts=dict(updates=1000, lookup=1, topk=1))
    assert r.window_s == pytest.approx(2000e-9)
    assert r.busy_s == pytest.approx((50 + 790 + 240 + 500) * 1e-9)
    # the while's own time is what its body's ops leave uncovered
    assert r.device_s["ingest"] == pytest.approx(790e-9)
    assert r.op_s["ingest"]["while.2 (s32[], s32[8])"] == pytest.approx(
        10e-9)
    assert r.ops_of("ingest", trace.is_sort) == pytest.approx(500e-9)
    assert r.ops_of("ingest", trace.is_scatter) == pytest.approx(280e-9)
    assert r.ops_of("lookup", trace.is_scatter) == 0
    assert r.calls == {"create": 1, "ingest": 1, "lookup": 1, "topk": 1}
    assert sum(s for _, s in r.gaps) == pytest.approx(
        r.window_s - r.busy_s)
    b = r.breakdown()
    assert b["device_ops"][0] == ["ingest/sort.3 (s32[8]{0}, f32[8]{0})",
                                  pytest.approx(500e-9)]
    assert len(b["idle_gaps"]) <= 10
    values = {name: spec.reader(name)(r) for name in READERS}
    assert values["merge_sort_ns_per_update"] == pytest.approx(0.5)
    assert values["merge_scatter_ns_per_update"] == pytest.approx(0.28)
    assert values["ingest_ns_per_update"] == pytest.approx(0.79)
    assert 0 < values["device_idle_share"] < 100


def test_reader_finds_nothing_without_its_calls():
    r = trace.reduce(_profile(), counts=dict(updates=1000))
    r.calls.pop("lookup")
    r.device_s.pop("ingest")
    for name in READERS:
        if name != "device_idle_share":
            assert spec.reader(name)(r) is None, name


def test_parse_op():
    label, opcode = trace.parse_op(
        "%sort.107 = (s32[17]{0:T(1024)}, f32[17]{0:T(1024)}) sort(s32[17]"
        "{0:T(1024)} %concatenate.74), dimensions={0}")
    assert label == "sort.107 (s32[17]{0:T(1024)}, f32[17]{0:T(1024)})"
    assert trace.is_sort(opcode)
    assert trace.parse_op("jit_run(123)") == ("jit_run(123)", "jit_run(123)")
    assert trace.parse_op(
        "%fusion.80 = f32[17]{0:T(1024)S(1)} fusion(s32[17]{0:T(1024)} %a, "
        "f32[17]{0} %sort.112), kind=kCustom, calls=%fused_computation.91"
    ) == ("fusion.80 f32[17]{0:T(1024)S(1)}", "fusion:kCustom")
    assert trace.is_scatter("fusion:kCustom")
    assert not trace.is_scatter("fusion:kLoop")
    assert trace.union([(5, 9), (0, 3), (2, 4)]) == [(0, 4), (5, 9)]


@pytest.fixture(scope="module")
def probe(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "probe.xplane.pb"
    with gzip.open(os.path.join(TESTDATA, "probe.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    return trace.read(str(path), PROBE_COUNTS)


def test_reduce_chip_trace(probe):
    assert probe.calls == {"create": 1, "ingest": 4, "lookup": 8, "topk": 4}
    assert 0 < probe.busy_s < probe.window_s
    # self times add up to the busy time: nested ops are not counted twice
    assert sum(probe.device_s.values()) == pytest.approx(probe.busy_s,
                                                         rel=1e-6)
    assert probe.ops_of("ingest", trace.is_sort) > 0
    assert probe.ops_of("topk", trace.is_sort) > 0
    assert {"while", "sort", "fusion:kLoop", "fusion:kCustom"} <= set(
        probe.opcode.values())
    b = probe.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["device_ops"][0][0].startswith("topk/")


@pytest.mark.parametrize("name", READERS)
def test_readers_on_chip_trace(probe, name):
    v = spec.reader(name)(probe)
    assert v is not None and v > 0
    if name == "device_idle_share":
        assert v < 100
