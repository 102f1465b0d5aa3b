"""palkit (repro/analysis/palkit.py) — Pallas kernel audit + VMEM budgets.

Covers the ISSUE 10 acceptance grid:

  * per-rule seeded-violation fixtures for K001-K006, each a small
    pallas_call traced through ``record_fn`` that fires EXACTLY its own
    rule while the clean twin stays quiet;
  * suppression: reasoned ``# palkit: allow(...) kernel=<glob>`` comments
    and the committed-baseline diff (shared ``repro.analysis.baseline``);
  * VMEM budgets: static-arithmetic measurement pinned against the
    COMMITTED ``VMEM_BUDGETS.json`` (machine-independent, so tier-1 can
    enforce it — corrupting a BlockSpec or inflating scratch breaks it
    here, not just in CI), compare verdicts, and the CLI exit codes;
  * the tier-1 gate: ``test_kernels_are_audit_clean`` pins the whole
    registry against the EMPTY committed baseline, with the two K005
    divergence surfaces visible as reasoned allows.
"""
import json

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import baseline, palkit
from repro.kernels import registry

F = jnp.float32


def _records(name, fn, *avals):
    recs = palkit.record_fn(name, fn, *avals)
    assert recs, f"{name}: no pallas_call reached"
    return recs


def _fired(name, fn, *avals, cfg=None):
    return {v.rule for v in palkit.run_rules(_records(name, fn, *avals),
                                             cfg)}


def _copy(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _block_call(kernel, in_shape, out_shape, in_block, out_block, grid,
                in_map, out_map, scratch=()):
    def f(x):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=0,
                grid=grid,
                in_specs=[pl.BlockSpec(in_block, in_map)],
                out_specs=pl.BlockSpec(out_block, out_map),
                scratch_shapes=list(scratch),
            ),
            out_shape=jax.ShapeDtypeStruct(out_shape, F),
            interpret=False)(x)
    return f, jax.ShapeDtypeStruct(in_shape, F)


# ------------------------------------------------- seeded rule fixtures -----


def test_k001_lane_misalignment():
    bad, a = _block_call(_copy, (8, 136), (8, 136), (8, 136), (8, 136),
                         (1,), lambda i: (0, 0), lambda i: (0, 0))
    ok, b = _block_call(_copy, (8, 128), (8, 128), (8, 128), (8, 128),
                        (1,), lambda i: (0, 0), lambda i: (0, 0))
    assert _fired("fx.k001_bad", bad, a) == {"K001"}
    assert _fired("fx.k001_ok", ok, b) == set()


def test_k001_sublane_misalignment():
    # 6 rows of f32: neither divides nor is a multiple of the sublane 8
    bad, a = _block_call(_copy, (6, 128), (6, 128), (6, 128), (6, 128),
                         (1,), lambda i: (0, 0), lambda i: (0, 0))
    # 4 rows divide the sublane count — a legal narrow tile
    ok, b = _block_call(_copy, (4, 128), (4, 128), (4, 128), (4, 128),
                        (1,), lambda i: (0, 0), lambda i: (0, 0))
    assert _fired("fx.k001_sub_bad", bad, a) == {"K001"}
    assert _fired("fx.k001_sub_ok", ok, b) == set()


def test_k002_vmem_ceiling():
    def kern(x_ref, o_ref, buf):
        o_ref[...] = x_ref[...]

    big = pltpu.VMEM((4096, 1280), jnp.float32)       # 20 MiB scratch
    small = pltpu.VMEM((8, 128), jnp.float32)
    bad, a = _block_call(kern, (8, 128), (8, 128), (8, 128), (8, 128),
                         (1,), lambda i: (0, 0), lambda i: (0, 0),
                         scratch=(big,))
    ok, b = _block_call(kern, (8, 128), (8, 128), (8, 128), (8, 128),
                        (1,), lambda i: (0, 0), lambda i: (0, 0),
                        scratch=(small,))
    assert _fired("fx.k002_bad", bad, a) == {"K002"}
    assert _fired("fx.k002_ok", ok, b) == set()
    # the ceiling is a knob: tighten it under the small twin and it fires
    tight = palkit.AuditConfig(vmem_limit_bytes=1024)
    assert _fired("fx.k002_ok", ok, b, cfg=tight) == {"K002"}


def test_k003_index_map_oob_over_grid():
    def mk(grid):
        return _block_call(_copy, (16, 128), (16, 128), (8, 128), (8, 128),
                           (grid,), lambda i: (i, 0), lambda i: (i, 0))

    bad, a = mk(3)          # step 2 selects block row 2 of a 2-block array
    ok, b = mk(2)
    assert _fired("fx.k003_bad", bad, a) == {"K003"}
    assert _fired("fx.k003_ok", ok, b) == set()
    vs = palkit.run_rules(_records("fx.k003_bad", bad, a))
    assert all(v.detail.startswith("oob:") for v in vs)


def test_k004_output_revisit_without_guarded_init():
    def acc(x_ref, o_ref):
        o_ref[...] += x_ref[...]

    def guarded(x_ref, o_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)
        o_ref[...] += x_ref[...]

    def mk(kernel):
        # the out map ignores the 2-step grid axis -> the first of the two
        # output blocks is revisited (out must be larger than its block, or
        # Pallas marks the window trivial and un-pipelined)
        return _block_call(kernel, (16, 128), (16, 128), (8, 128), (8, 128),
                           (2,), lambda i: (i, 0), lambda i: (0, 0))

    bad, a = mk(acc)
    ok, b = mk(guarded)
    assert _fired("fx.k004_bad", bad, a) == {"K004"}
    assert _fired("fx.k004_ok", ok, b) == set()
    vs = palkit.run_rules(_records("fx.k004_bad", bad, a))
    assert [v.detail for v in vs] == ["revisit:out0"]


def test_k004_dead_grid_axis():
    f, a = _block_call(_copy, (8, 128), (8, 128), (8, 128), (8, 128),
                       (4,), lambda i: (0, 0), lambda i: (0, 0))
    vs = palkit.run_rules(_records("fx.k004_dead", f, a))
    assert {v.rule for v in vs} == {"K004"}
    assert any(v.detail == "dead-axis:0" for v in vs)


def test_k005_dynamic_addressing():
    def dyn(s_ref, x_ref, o_ref):
        start = s_ref[0]
        o_ref[...] = x_ref[pl.ds(start * 8, 8), :]

    def static(s_ref, x_ref, o_ref):
        o_ref[...] = x_ref[0:8, :]

    def mk(kernel):
        def f(s, x):
            return pl.pallas_call(
                kernel,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(1,),
                    in_specs=[pl.BlockSpec((16, 128), lambda i, s: (0, 0))],
                    out_specs=pl.BlockSpec((8, 128), lambda i, s: (0, 0)),
                ),
                out_shape=jax.ShapeDtypeStruct((8, 128), F),
                interpret=False)(s, x)
        return f

    s = jax.ShapeDtypeStruct((1,), jnp.int32)
    x = jax.ShapeDtypeStruct((16, 128), F)
    bad_vs = palkit.run_rules(_records("fx.k005_bad", mk(dyn), s, x))
    assert {v.rule for v in bad_vs} == {"K005"}
    assert [v.detail for v in bad_vs] == ["dynamic-ds"]
    assert _fired("fx.k005_ok", mk(static), s, x) == set()


def test_k005_prefetch_reading_index_map_on_registry_job():
    # embedding_bag's table-row block choice reads the prefetched indices:
    # the canonical index-map divergence surface, excused in-tree
    job = next(j for j in registry.jobs() if j.family == "embedding_bag")
    vs = palkit.run_rules(palkit.record_job(job))
    assert any(v.rule == "K005" and v.detail == "index-map" for v in vs)


def _dma_call(kernel, sem):
    def f(x):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=0,
                grid=(1,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
                scratch_shapes=[pltpu.VMEM((2, 8, 128), jnp.float32), sem],
            ),
            out_shape=jax.ShapeDtypeStruct((8, 128), F),
            interpret=False)(x)
    return f, jax.ShapeDtypeStruct((16, 128), F)


def test_k006_unwaited_async_copy():
    def bad_kernel(x_ref, o_ref, buf, sem):
        pltpu.make_async_copy(x_ref.at[pl.ds(0, 8)], buf.at[0],
                              sem.at[0]).start()
        o_ref[...] = jnp.zeros_like(o_ref)

    def ok_kernel(x_ref, o_ref, buf, sem):
        cp = pltpu.make_async_copy(x_ref.at[pl.ds(0, 8)], buf.at[0],
                                   sem.at[0])
        cp.start()
        cp.wait()
        o_ref[...] = buf[0]

    bad, a = _dma_call(bad_kernel, pltpu.SemaphoreType.DMA((2,)))
    ok, b = _dma_call(ok_kernel, pltpu.SemaphoreType.DMA((2,)))
    bad_vs = palkit.run_rules(_records("fx.k006_bad", bad, a))
    assert {v.rule for v in bad_vs} == {"K006"}
    assert [v.detail for v in bad_vs] == ["unwaited"]
    assert _fired("fx.k006_ok", ok, b) == set()


def test_k006_semaphore_slot_mismatch():
    def kernel(x_ref, o_ref, buf, sem):
        cp = pltpu.make_async_copy(x_ref.at[pl.ds(0, 8)], buf.at[0],
                                   sem.at[0])
        cp.start()
        cp.wait()
        o_ref[...] = buf[0]

    # one semaphore slot sequencing a depth-2 double buffer
    bad, a = _dma_call(kernel, pltpu.SemaphoreType.DMA((1,)))
    vs = palkit.run_rules(_records("fx.k006_slot", bad, a))
    assert {v.rule for v in vs} == {"K006"}
    assert all(v.detail.startswith("slot-mismatch") for v in vs)


def test_grid_sample_large_grids_hit_the_corners():
    pts = set(palkit._grid_sample((100000,), limit=4096))
    assert pts == {(0,), (1,), (50000,), (99998,), (99999,)}
    # small grids are exhaustive
    assert len(list(palkit._grid_sample((4, 8), limit=4096))) == 32


# ------------------------------------------------ suppression + baseline ----


def test_allow_comment_scanning_and_matching(tmp_path):
    good = tmp_path / "good"
    good.mkdir()
    (good / "owner.py").write_text(
        "# palkit: allow(K001) kernel=fx.* odd tile is deliberate here\n")
    allows = palkit.scan_allows([str(good)])
    v = palkit.Violation("K001", "fx.k001_bad", "in0:8x136", "m")
    assert palkit.suppressed(v, allows)
    # wrong rule or non-matching kernel glob never suppresses
    assert not palkit.suppressed(
        palkit.Violation("K002", v.kernel, "ceiling", "m"), allows)
    assert not palkit.suppressed(
        palkit.Violation("K001", "hier_merge.merge_pallas/n512", "d", "m"),
        allows)

    # a reasonless allow is ignored — same discipline as reprolint/tracekit
    bare = tmp_path / "bare"
    bare.mkdir()
    (bare / "owner.py").write_text("# palkit: allow(K001) kernel=fx.*\n")
    assert not palkit.suppressed(v, palkit.scan_allows([str(bare)]))


def test_baseline_keys_are_per_kernel_and_counted(tmp_path):
    v = palkit.Violation("K003", "fam.kernel/n1", "oob:in0", "msg")
    assert v.key == "K003 fam.kernel/n1 oob:in0"
    path = tmp_path / "base.txt"
    path.write_text("# comment\n" + v.key + "\n")
    base = baseline.load_baseline(str(path))
    assert baseline.new_violations([v], base) == []
    # one baseline key admits exactly one occurrence
    assert baseline.new_violations([v, v], base) == [v]


def test_committed_baseline_is_empty():
    assert sum(baseline.load_baseline(
        palkit.DEFAULT_BASELINE).values()) == 0


# --------------------------------------------------- tier-1 audit gate ------


def test_kernels_are_audit_clean():
    """Tier-1 gate: the whole kernel registry is K-clean against the
    EMPTY committed baseline; the only hits are the two K005 divergence
    surfaces, excused by reasoned in-tree allows."""
    result = palkit.audit_kernels()
    assert [v.render() for v in result["fresh"]] == []
    assert {r.name for r in result["records"]} \
        >= {j.name for j in registry.jobs()}
    assert {(v.rule, v.detail) for v in result["suppressed"]} \
        == {("K005", "index-map"), ("K005", "dynamic-ds")}
    for key, row in result["measured"].items():
        assert row["vmem_bytes"] > 0, key


def test_committed_vmem_budgets_match_measurement():
    """VMEM rows are pure static shape arithmetic — identical on every
    machine — so tier-1 pins the COMMITTED budgets, not a regenerated
    copy: corrupting a BlockSpec or inflating scratch fails here."""
    committed = palkit.load_budgets(palkit.DEFAULT_BUDGETS)
    assert committed, "VMEM_BUDGETS.json missing — run --update and commit"
    measured = palkit.measure(palkit.trace_kernels())
    diff = palkit.compare_budgets(
        measured, committed,
        committed["_meta"].get("tolerance", palkit.DEFAULT_TOLERANCE))
    assert diff["breaches"] == []
    assert diff["missing"] == []
    assert diff["stale"] == []


def test_k000_trace_failure_is_reported_not_raised():
    def broken(x, *, interpret):
        raise ValueError("boom")

    import numpy as np
    bad = registry.KernelJob(
        name="fx.broken/x", family="fx", fn=broken,
        make_inputs=lambda seed: (np.zeros((8, 128), np.float32),),
        oracle=None)
    result = palkit.audit_kernels(jobs=[bad], src=(),
                                  baseline_path="/nonexistent/base.txt")
    assert [(v.rule, v.kernel) for v in result["fresh"]] \
        == [("K000", "fx.broken/x")]
    # without a failures list the tracer error propagates (tests want it)
    with pytest.raises(ValueError):
        palkit.trace_kernels([bad])


def test_audit_only_jobs_are_traced_not_executed():
    job = next(j for j in registry.jobs() if j.audit_only)
    recs = palkit.record_job(job)          # traces fine on abstract inputs
    assert recs
    blocks, scratch = recs[0].vmem_bytes()
    assert blocks + scratch > 0


# ----------------------------------------------------------- budgets --------


def test_compare_budgets_verdicts():
    budgets = {"kernels": {"a": dict(vmem_bytes=1000),
                           "c": dict(vmem_bytes=10)}}
    row = dict(family="f", grid="-", block_bytes=0, scratch_bytes=0)
    measured = {"a": dict(row, vmem_bytes=1200),
                "b": dict(row, vmem_bytes=5)}
    diff = palkit.compare_budgets(measured, budgets, tolerance=0.10)
    assert len(diff["breaches"]) == 1 and "a" in diff["breaches"][0]
    assert diff["missing"] == ["b"]
    assert diff["stale"] == ["c"]
    # within tolerance -> ok; well under -> ratchet candidate, not failure
    close = {"a": dict(row, vmem_bytes=1050)}
    assert palkit.compare_budgets(close, budgets, 0.10)["breaches"] == []
    low = {"a": dict(row, vmem_bytes=500)}
    d2 = palkit.compare_budgets(low, budgets, 0.10)
    assert d2["breaches"] == [] and d2["improved"] == ["a"]


# ----------------------------------------------------------------- CLI ------


@pytest.fixture(scope="module")
def budgets_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("budgets") / "VMEM_BUDGETS.json"
    assert palkit.main(["--update", "--budgets", str(path), "-q"]) == 0
    return str(path)


def test_cli_check_clean_tree_exits_0(budgets_file):
    data = json.loads(open(budgets_file).read())
    assert set(data["kernels"]) == {j.name for j in registry.jobs()}
    assert palkit.main(["--check", "--budgets", budgets_file, "-q"]) == 0


def test_cli_budget_breach_exits_1(budgets_file, tmp_path):
    data = json.loads(open(budgets_file).read())
    key = sorted(data["kernels"])[0]
    data["kernels"][key]["vmem_bytes"] = 1        # guaranteed breach
    breach = tmp_path / "breach.json"
    breach.write_text(json.dumps(data))
    assert palkit.main(["--check", "--budgets", str(breach), "-q"]) == 1


def test_cli_unbudgeted_kernel_exits_1(tmp_path):
    assert palkit.main(["--check", "-q",
                        "--budgets", str(tmp_path / "none.json")]) == 1


@pytest.mark.parametrize("rule", sorted(palkit.RULES))
def test_cli_exits_1_on_each_seeded_rule(rule, budgets_file, monkeypatch):
    v = palkit.Violation(rule, "fx.seeded", "detail", "seeded")

    def fake_audit(jobs=None, **kw):
        return dict(records=[], violations=[v], suppressed=[],
                    fresh=[v], measured={})

    monkeypatch.setattr(palkit, "audit_kernels", fake_audit)
    assert palkit.main(["--check", "-q", "--budgets", budgets_file]) == 1
