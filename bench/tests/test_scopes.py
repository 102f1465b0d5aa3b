"""Attribution of device time to the program's named scopes
(``bench/scopes.py``): ``trace.reduce`` gives each op's self time to the
call whose span holds it, and the call's tables give the op's instruction
its scope path; coverage guards every reading.  The spill counter's merges
per depth are checked against a host replay of the planner."""
import os
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

from bench import scopes, spec, trace
from repro import stages
from repro.core import distributed, hier, stream

TESTDATA = os.path.join(spec.BENCH, "testdata")


def _line(name, events):
    return NS(name=name, events=[NS(name=n, start_ns=s, end_ns=e)
                                 for n, s, e in events])


def _profile():
    host = NS(name="/host:CPU", lines=[_line("python", [
        ("bench.create", 0, 100), ("bench.ingest", 100, 1000),
        ("bench.lookup", 1000, 1300)])])
    dev = NS(name="/device:TPU:0", lines=[
        # two programs lowered under one module name, told apart by call
        _line("XLA Modules", [("jit_run(111)", 105, 990),
                              ("jit_run(222)", 1005, 1290)]),
        _line(trace.OPS_LINE, [
            ("%broadcast.1 = s32[8]{0} broadcast(s32[] %c)", 10, 60),
            ("%while.2 = (s32[], s32[8]) while((s32[], s32[8]) %t)", 110,
             900),
            ("%sort.3 = (s32[8]{0}, f32[8]{0}) sort(s32[8] %a, f32[8] %b)",
             120, 620),
            ("%fusion.7 = f32[8]{0} fusion(f32[8] %s), kind=kCustom", 620,
             900),
            ("%fusion.9 = f32[8]{0} fusion(f32[8] %s), kind=kLoop", 900,
             950),
            ("%sort.3 = (s32[8]{0}) sort(s32[8] %a)", 1010, 1210),
            ("%copy.1 = s32[8]{0} copy(s32[8] %a)", 1210, 1250)])])
    return NS(planes=[host, dev])


MERGE = "jit(run)/while/body/cohort.d1/cond/branch_1_fun"
TABLES = {
    "ingest": {"jit_run": {
        "while.2": "jit(run)/while",
        "sort.3": f"{MERGE}/canon.sort/jit(sort)/sort",
        "fusion.7": f"{MERGE}/canon.key_scatter/scatter"}},
    "lookup": {"jit_run": {"sort.3": "jit(run)/canon.sort/sort",
                           "copy.1": ""}},
}


def test_scope_path_keeps_the_programs_dotted_names():
    assert scopes.scope_path(f"{MERGE}/canon.sort/jit(sort)/sort") == \
        "cohort.d1/canon.sort"
    assert scopes.scope_path("jit(run)/while/body/add") == ""


def _reduce(tables):
    return scopes.reduce(trace.reduce(_profile(), {}), tables)


def test_attribution_by_call_and_module():
    r = _reduce(TABLES)
    # the same device time as the accepted reduction counts
    assert r.device_s == pytest.approx(
        trace.reduce(_profile(), {}).device_s)
    assert r.device_s["ingest"] == pytest.approx(840e-9)
    # fusion.9 is not in the table: known but 790 of 840 ns
    assert r.coverage("ingest") == pytest.approx(790 / 840)
    assert r.scope_s["ingest"] == pytest.approx({
        "": 10e-9, "cohort.d1/canon.sort": 500e-9,
        "cohort.d1/canon.key_scatter": 280e-9})
    # sort.3 of the lookup call reads the lookup program's table
    assert r.scope_s["lookup"] == pytest.approx({"canon.sort": 200e-9,
                                                 "": 40e-9})
    assert r.coverage("lookup") == pytest.approx(1.0)
    assert r.coverage("create") == 0.0        # no table for that call
    # below MIN_COVERAGE nothing is read
    assert r.under("ingest", "cohort.d1") is None
    assert r.under("lookup", "canon.sort") == pytest.approx(200e-9)


def test_an_instruction_two_modules_scope_apart_is_unknown():
    """A call's tables are joined by instruction name alone: where two of
    its modules scope one name differently, the op's time is unknown."""
    tables = {"lookup": dict(TABLES["lookup"], jit_other={
        "sort.3": "jit(other)/canon.value_sum/sort", "copy.1": ""})}
    r = _reduce(tables)
    assert r.scope_s["lookup"] == pytest.approx({"": 40e-9})
    assert r.coverage("lookup") == pytest.approx(40 / 240)
    assert r.under("lookup", "canon.sort") is None


def test_under_sums_nested_scopes_and_needs_each_scope():
    tables = {"ingest": {"jit_run": dict(TABLES["ingest"]["jit_run"],
                                         **{"fusion.9": ""})}}
    r = _reduce(tables)
    assert r.coverage("ingest") == pytest.approx(1.0)
    assert r.under("ingest", "cohort.d1") == pytest.approx(780e-9)
    assert r.under("ingest", "cohort.d1", "canon.sort") == \
        pytest.approx(780e-9)
    assert r.under("ingest", "canon.sort", "canon.key_scatter") == \
        pytest.approx(780e-9)
    assert r.under("ingest", "canon.sort", "cohort.take") is None
    assert r.under("ingest", "cohort.d2") is None
    # the cohorts and the unscoped ops add up to the call's device time
    assert r.under("ingest", "cohort.d1") + r.scope_s["ingest"][""] == \
        pytest.approx(r.device_s["ingest"], rel=1e-9)
    m = scopes.metrics(r, dict(updates=1000, merged_slots_d1=390))
    assert m["merge_d1_ns_per_slot"] == pytest.approx(2.0)
    assert m["canon_key_scatter_ns_per_update"] == pytest.approx(0.28)
    assert m["append_ns_per_update"] is None       # no cohort.d0 op
    assert m["merge_d2_ns_per_slot"] is None
    assert m["setup_lower_s"] is None


def test_merged_slots_at_the_paper_cell():
    """18 depth-1 and 3 depth-2 merges per instance and cycle at 16
    instances: 662.4 M and 816 M slots."""
    cfg = dict(cuts=[200000, 1600000, 12800000], block_size=100000)
    spills = np.tile([21, 3, 0], (16, 1))
    assert scopes.merged_slots(cfg, spills, cycles=1) == dict(
        merged_slots_d1=662_400_000, merged_slots_d2=816_000_000)
    assert scopes.merged_slots(cfg, spills, cycles=2)[
        "merged_slots_d2"] == 1_632_000_000


def test_setup_seconds_sums_entries():
    stats = {"per_entry": {
        "a": dict(dispatches=2, wall_s=1.0, lower_s=0.5, load_s=2.0),
        "b": dict(dispatches=1, wall_s=1.0, lower_s=0.25, load_s=0.0)}}
    assert scopes.setup_seconds(stats) == dict(setup_lower_s=0.75,
                                               setup_load_s=2.0)


# The scoped probe: a traced cycle of the fleet of ``probe.xplane.pb.gz``
# (4 instances, block 1,024, cuts 2,048 / 16,384 / 131,072, R-MAT scale
# 22), ingest only, 24 blocks per instance in 6 rounds so that merges
# reach depth 2, recorded on one TPU v5e by ``bench/scopes.py --probe``
# from an ingest executable the persistent cache served; beside it the
# ingest program's op-to-scope tables and the run's counts.
READINGS = tuple(scopes.metrics(scopes.ScopeReading({}, {}, {}), {}))


@pytest.fixture(scope="module")
def probe(tmp_path_factory):
    from jax.profiler import ProfileData
    path, tables, counts = scopes.load_probe(
        TESTDATA, str(tmp_path_factory.mktemp("probe")))
    old = trace.reduce(ProfileData.from_file(path), counts)
    return scopes.reduce(old, tables), old, counts


def test_scoped_probe_covers_the_ingest_call_and_adds_up(probe):
    r, old, _ = probe
    assert r.device_s == pytest.approx(old.device_s)
    assert r.coverage("ingest") >= scopes.MIN_COVERAGE
    split = sum(s for p, s in r.scope_s["ingest"].items()
                if not p or p.split("/")[0].startswith("cohort.d"))
    unknown = r.device_s["ingest"] - r.known_s["ingest"]
    assert split + unknown == pytest.approx(r.device_s["ingest"], rel=1e-6)
    # at the probe's size the canonicalization's sorts are (nearly) every
    # sort of the call; at the paper cell's, XLA also sorts the depth-2
    # key scatters' indices under ``canon.key_scatter``
    assert r.under("ingest", "canon.sort") == pytest.approx(
        old.ops_of("ingest", trace.is_sort), rel=0.05)


@pytest.mark.parametrize("name", READINGS)
def test_scope_readings_on_the_chip_probe(probe, name):
    r, _, counts = probe
    v = scopes.metrics(r, counts)[name]
    assert v is not None and v > 0


# A three-layer fleet at a tiny size, ingesting a stream that merges at
# both depths.
CUTS = (32, 128, 1024)
BLOCK = 16
I, T = 3, 24


def _replay_merges(rows, cols):
    """Host replay of the fused planner on one instance's stream: layer 0
    counts slots (the lazy append keeps duplicates), deeper layers their
    unique keys; returns merges per depth 1..L-1."""
    L = len(CUTS)
    caps = hier.layer_capacities(CUTS, BLOCK)
    keys = [[] for _ in range(L)]
    nnz = [0] * L
    merges = [0] * L
    for r, c in zip(rows, cols):
        block = list(zip(r.tolist(), c.tolist()))
        occupancy, depth, chain = BLOCK, 0, True
        for i in range(L - 1):
            occupancy += nnz[i]
            chain = chain and occupancy > CUTS[i]
            depth = i + 1 if chain else depth
        if depth == 0:
            keys[0] += block
            nnz[0] += BLOCK
            continue
        merged = set(block).union(*map(set, keys[:depth + 1]))
        for i in range(depth):
            keys[i], nnz[i] = [], 0
        keys[depth] = list(merged)
        nnz[depth] = min(len(merged), caps[depth])
        merges[depth] += 1
    return merges[1:]


def test_merges_per_depth_match_a_host_replay():
    """On a three-layer fleet whose stream merges at depth 1 and 2, the
    spill counter's merges per depth equal a host replay's."""
    sig = stages.signature_of(cuts=CUTS, block_size=BLOCK, lazy_l0=True,
                              batch_mode="grouped")
    run = stream.ingest_instances_jit(sig, with_telemetry=False)
    rng = np.random.default_rng(3)
    rows, cols = (rng.integers(0, 4096, (I, T, BLOCK)).astype(np.int32)
                  for _ in range(2))
    out = run(distributed.create_instances(I, CUTS, BLOCK), rows, cols,
              np.ones((I, T, BLOCK), np.float32))
    got = scopes.merges_per_depth(jax.device_get(out.spills))
    want = np.array([_replay_merges(rows[i], cols[i]) for i in range(I)])
    np.testing.assert_array_equal(got, want)
    assert want[:, 0].min() > 0 and want[:, 1].min() > 0
