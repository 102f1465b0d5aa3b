"""One tiny cycle of each traffic mix through the program's entries, on the
CPU: the reference comparison passes, and each fault planted under the
timed path makes ``correct`` come out false."""
import glob
import os

import jax
import numpy as np
import pytest

from bench import check, control, gen, harness, run, spec

BENCH = spec.load()
MIXES = sorted(os.path.basename(p)[:-len(".json")] for p in glob.glob(
    os.path.join(spec.BENCH, "traffic", "*.json")))


def tiny(config="d4m-paper"):
    """The configuration at a size the CPU runs in seconds; every layer
    still spills in a cycle of 64 blocks."""
    cfg = dict(spec.config(BENCH, config))
    cfg.update(instances_per_chip=4, block_size=64, cuts=[128, 1024, 8192],
               rmat_scale=10)
    return cfg


def tiny_traffic(mix):
    return dict(spec.traffic(mix))


def one_cycle(cfg, traffic, seed, fleet_patch=None):
    cyc = harness.setup(cfg, traffic, seed, jax.devices()[0])
    if fleet_patch:
        fleet_patch(cyc.fleet)
    states, cycles, _ = harness.window(cyc, 0.0)
    ids = gen.sample_ids(seed, cyc.fleet.n)
    host = harness.read_fleet(states, ids)
    return cyc, host, harness.compare(cyc, host, harness.reference(cyc, ids),
                                      0)


@pytest.mark.parametrize("mix", MIXES)
def test_tiny_cycle_passes_the_reference(mix):
    cyc, host, numbers = one_cycle(tiny(), tiny_traffic(mix), 2**33 + 5)
    assert check.passed(numbers), numbers
    names = {n for n, _, _ in numbers}
    assert {"compiles", "count_gap", "overflow", "dtype_gap", "key_gap",
            "value_gap"} <= names
    assert host["count"] == cyc.updates
    assert (host["spills"][:, :-1] > 0).all()     # every layer spilled


def _unchanged(fleet):
    fleet.ingest = lambda states, rows, cols, vals: states


def _half_batch(fleet):
    ingest = fleet.ingest
    fleet.ingest = lambda s, r, c, v: ingest(
        s, r[..., : r.shape[-1] // 2], c[..., : c.shape[-1] // 2],
        v[..., : v.shape[-1] // 2] * 2)


def _value_altered(fleet):
    ingest = fleet.ingest
    fleet.ingest = lambda s, r, c, v: ingest(s, r, c, v.at[0, 0, 0].add(1.0))


def _bf16_store(fleet):
    create = fleet.create
    fleet.create = lambda: create(dtype="bfloat16")


FAULTS = [("ingest", _unchanged), ("ingest", _half_batch),
          ("ingest", _value_altered), ("ingest", _bf16_store)]


@pytest.mark.parametrize("mix,fault", FAULTS,
                         ids=[f"{m}-{f.__name__[1:]}" for m, f in FAULTS])
def test_planted_fault_fails_the_check(mix, fault):
    # the seed's sampled instances include instance 0, which two faults hit
    seed = next(s for s in range(100) if 0 in gen.sample_ids(s, 4))
    _, _, numbers = one_cycle(tiny(), tiny_traffic(mix), seed, fault)
    assert not check.passed(numbers), numbers


def test_run_reports_not_correct_with_the_timed_path_broken(monkeypatch):
    """The whole of a run but the look for a chip, with the ingest under
    the window returning its state unchanged."""
    monkeypatch.setattr(harness.Fleet, "__init__", _patched_init)
    cell = spec.cell(BENCH, "paper-ingest")
    out = run.run(cell, tiny(), tiny_traffic("ingest"),
                  spec.metrics_for(BENCH, "end_to_end", cell["name"]),
                  spec.metrics_for(BENCH, "per_layer", cell["name"]),
                  seed=3, seconds=0.0, traced=False,
                  devices=jax.devices()[:1])
    assert out["correct"] is False
    assert out["checks"]["count_gap"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert {"setup_s", "updates_per_s"} <= set(out["metrics"])


_INIT = harness.Fleet.__init__


def _patched_init(self, *a, **kw):
    _INIT(self, *a, **kw)
    _unchanged(self)


@pytest.mark.parametrize("mix", MIXES)
def test_controls_fail_the_check(mix):
    """The bfloat16 control fails on the precision of its store, and, at a
    scale where keys pass 256 updates, on its values; the lost-round
    control fails on the count and the keys."""
    cfg, traffic = tiny(), tiny_traffic(mix)
    failed = {}
    for size in ({}, dict(block_size=2048, cuts=[4096, 32768, 262144],
                          rmat_scale=8)):
        cfg.update(size)
        ids = gen.sample_ids(7, 4)
        stream = control.host_stream(7, cfg, traffic, jax.devices()[0], ids)
        for name in ("bf16", "lost-round", "none"):
            numbers = control.readings(cfg, traffic, stream, ids, 4, name)
            failed[name, bool(size)] = {n for n, v, lim in numbers
                                        if v > lim}
    assert failed["none", False] == failed["none", True] == set()
    assert failed["bf16", False] == {"dtype_gap"}
    assert {"dtype_gap", "value_gap"} <= failed["bf16", True]
    assert {"count_gap", "key_gap"} <= failed["lost-round", False]


def test_reference_bf16_stalls_where_float32_does_not():
    ref = harness.Reference([0])
    low = harness.Reference([0], control.BF16)
    rows = np.zeros((1, 1, 300), np.int32)
    for r in (ref, low):
        r.add(rows, rows, np.ones((1, 1, 300), np.float32))
    assert ref.coalesced(0)[2][0] == 300
    assert low.coalesced(0)[2][0] == 256
