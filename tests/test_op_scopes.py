"""Named scopes and counters at the ingest program's layer boundaries.

The grouped ``lazy_l0`` ingest names its layers with ``jax.named_scope``
(``cohort.*`` in ``stream._grouped_execute``, ``canon.*`` in
``assoc._canonicalize``).  A device trace joins its ops to those names
through ``stages.op_scopes``, read from each executable's own optimized
HLO.  These tests guard the names a trace reader uses, the merge width,
the tables' provenance (the executable, also one the persistent cache
served, never a re-lowering) and the front door's ``lower_s`` /
``load_s``.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import stages
from repro.core import distributed, hier, stream

CUTS = (32, 128, 1024)
BLOCK = 16
I, T = 3, 24
# every scope a trace reader of the ingest path reads
SCOPES = ("cohort.d0", "cohort.d1", "cohort.d2", "cohort.take", "cohort.put",
          "canon.sort", "canon.value_sum", "canon.key_scatter")


def _stream(seed=3):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, 4096, (I, T, BLOCK)).astype(np.int32)
    cols = rng.integers(0, 4096, (I, T, BLOCK)).astype(np.int32)
    return rows, cols


@pytest.fixture(scope="module")
def ingest():
    """The grouped lazy_l0 ingest at a three-layer fleet, on a stream whose
    merges reach depth 2; returns (program, its executable, final state,
    host stream)."""
    sig = stages.signature_of(cuts=CUTS, block_size=BLOCK, lazy_l0=True,
                              batch_mode="grouped")
    run = stream.ingest_instances_jit(sig, with_telemetry=False)
    rows, cols = _stream()
    args = (distributed.create_instances(I, CUTS, BLOCK), jnp.asarray(rows),
            jnp.asarray(cols), jnp.ones((I, T, BLOCK), jnp.float32))
    out = jax.block_until_ready(run(*args))
    return run, stages.compiled_for(run, *args), out, (rows, cols)


def _parts(table):
    return [op.split("/") for op in table.values()]


def test_scoped_ingest_names_every_layer_scope(ingest):
    _, comp, out, _ = ingest
    assert int(np.asarray(out.spills)[:, 1].min()) > 0   # depth 2 reached
    tables = comp.op_scopes()
    assert len(tables) == 1
    (table,) = tables.values()
    parts = _parts(table)
    for scope in SCOPES:
        assert any(scope in p for p in parts), scope
    # the nesting a reader relies on: the canonicalization and a member's
    # data movement run inside a merging depth's loop
    for p in parts:
        assert sum(c.startswith("cohort.d") for c in p) <= 1, p
        if any(c.startswith(("canon.", "cohort.take", "cohort.put"))
               for c in p):
            assert "cohort.d1" in p or "cohort.d2" in p, p


@pytest.mark.parametrize("depth", [0, 1, 2])
def test_merge_width_is_the_sorted_operand(ingest, depth):
    """``hier.merge_width`` states the length of the sort the program runs
    under ``cohort.d<depth>``; the append cohort sorts nothing."""
    _, comp, _, _ = ingest
    widths = set()
    for line in comp.as_text().splitlines():
        op = re.search(r'op_name="([^"]*)"', line)
        if " sort(" in line and op and f"cohort.d{depth}" in op[1]:
            widths.add(int(re.search(r"\[(\d+)\]", line)[1]))
    width = hier.merge_width(hier.layer_capacities(CUTS, BLOCK), BLOCK, depth)
    assert widths == ({width} if depth else set())


def test_parse_op_scopes():
    text = "\n".join([
        "HloModule jit_run, is_scheduled=true",
        "",
        "%fused_computation (p: s32[4]) -> s32[4] {",
        '  %add.1 = s32[4]{0} add(%p, %p), metadata={op_name="jit(run)/add"'
        ' source_file="x.py"}',
        "}",
        "ENTRY %main.9 (a: s32[4]) -> s32[4] {",
        "  %a = s32[4]{0} parameter(0)",
        '  %sort.3 = (s32[4]{0}) sort(%a), dimensions={0}, metadata={'
        'op_type="sort" op_name="jit(run)/while/body/cohort.d1/canon.sort/'
        'sort"}',
        '  ROOT %fusion.2 = s32[4]{0} fusion(%sort.3), kind=kLoop, '
        'calls=%fused_computation, metadata={op_name="jit(run)/add"}',
        "}",
        "HloModule jit_lambda, is_scheduled=true",
        '  %copy.1 = f32[2]{0} copy(%b), metadata={op_name="jit(<lambda>)/'
        'copy"}',
    ])
    assert stages.parse_op_scopes(text) == {
        "jit_run": {"add.1": "jit(run)/add", "a": "",
                    "sort.3": "jit(run)/while/body/cohort.d1/canon.sort/sort",
                    "fusion.2": "jit(run)/add"},
        "jit_lambda": {"copy.1": "jit(<lambda>)/copy"}}


def test_op_scopes_drop_what_two_modules_of_one_name_disagree_on():
    """Two programs both lowered as ``jit_run``: an instruction they name
    alike but scope differently is left out of the merged table, and each
    entry's own table keeps it."""
    def program(scope):
        def run(x):
            with jax.named_scope(scope):
                return jnp.sort(x) * 2
        return run

    x = jnp.arange(8, dtype=jnp.float32)[::-1]
    for scope in ("cohort.dx", "cohort.dy"):
        sig = stages.signature_of(extra=(("test", f"clash-{scope}"),))
        stages.wrap(program(scope), f"test.clash.{scope}", sig)(x)
    one = stages.op_scopes("test.clash.cohort.dx")["jit_run"]
    two = stages.op_scopes("test.clash.cohort.dy")["jit_run"]
    both = stages.op_scopes()["jit_run"]
    differ = {k for k in one.keys() & two.keys() if one[k] != two[k]}
    assert differ and any("cohort.dx" in one[k] for k in differ)
    assert not differ & both.keys()


@pytest.fixture
def cache_dir(tmp_path):
    stages.set_cache_dir(str(tmp_path))
    try:
        yield str(tmp_path)
    finally:
        stages.set_cache_dir(None)


def test_op_scopes_of_a_disk_loaded_executable(cache_dir):
    """The table of an executable the persistent cache served equals the
    compiled one's, and comes from the executable: one that cannot print
    its HLO raises instead of answering from a re-lowering."""
    sig = stages.signature_of(cuts=(8, 32), block_size=4, lazy_l0=True,
                              batch_mode="grouped",
                              extra=(("test", "scopes-disk"),))
    rows = jnp.asarray(np.arange(2 * 6 * 4).reshape(2, 6, 4) % 13,
                       jnp.int32)
    vals = jnp.ones((2, 6, 4), jnp.float32)

    def dispatch():
        run = stream.ingest_instances_jit(sig, with_telemetry=False)
        args = (distributed.create_instances(2, (8, 32), 4), rows, rows,
                vals)
        jax.block_until_ready(run(*args))
        return stages.compiled_for(run, *args)

    fresh = dispatch().op_scopes()
    stages.clear_memory_cache()
    stages.reset_stats()
    comp = dispatch()
    assert comp.from_disk and stages.stats()["compiles"] == 0
    loaded = comp.op_scopes()
    assert loaded == fresh
    assert any("cohort.d1" in op for t in loaded.values()
               for op in t.values())

    comp._scopes, comp._executable = None, object()
    lowerings = stages.stats()["lowerings"]
    with pytest.raises(AttributeError):
        comp.op_scopes()
    assert stages.stats()["lowerings"] == lowerings


def test_lower_and_load_seconds_on_a_miss_not_on_a_hit():
    sig = stages.signature_of(extra=(("test", "lower-load"),))
    w = stages.wrap(lambda x: jnp.cumsum(x) + 1, "test.lower_load", sig)
    x = jnp.arange(16, dtype=jnp.float32)
    jax.block_until_ready(w(x))
    first = dict(stages.stats()["per_entry"]["test.lower_load"])
    assert first["lower_s"] > 0 and first["load_s"] > 0
    jax.block_until_ready(w(x))
    again = stages.stats()["per_entry"]["test.lower_load"]
    assert again["dispatches"] == first["dispatches"] + 1
    assert again["lower_s"] == first["lower_s"]
    assert again["load_s"] == first["load_s"]
