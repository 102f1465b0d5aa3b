"""tracekit (repro/analysis/tracekit.py) — jaxpr/HLO audit + cost budgets.

Covers the ISSUE 8 acceptance grid:

  * per-rule seeded-violation fixtures for J001-J006, each firing EXACTLY
    its own rule while the clean twin stays quiet;
  * suppression: reasoned ``# tracekit: allow(...)`` comments and the
    committed-baseline diff (reuse of the shared
    ``repro.analysis.baseline`` machinery);
  * cost budgets: compare semantics (ok / breach / missing / stale /
    improved) plus the CLI exit codes — ``--check`` exits 0 on a clean
    tree with fresh budgets, 1 on a seeded violation of every rule, 1 on
    a budget breach, 1 on an unbudgeted entry;
  * the tier-1 gate: ``test_fleet_is_audit_clean`` pins the production
    dispatch set against the EMPTY baseline.
"""
import contextlib
import json
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro import stages
from repro.analysis import baseline, tracekit


def _wrap(fn, name, **kw):
    sig = stages.signature_of(extra=(("test_tracekit", name),))
    return stages.wrap(fn, f"test.tracekit.{name}", sig, **kw)


def _rules_fired(wrapped, *args, acfg=None, x64=False):
    """Audit one record in isolation (no global-cache J006 scan) and
    return the set of rule ids that fired."""
    ctx = jax.enable_x64(True) if x64 else contextlib.nullcontext()
    with ctx, warnings.catch_warnings():
        warnings.simplefilter("ignore")   # "donated buffers not usable"
        rec = tracekit.record(wrapped, *args)
        rec.lowered  # force the trace inside the x64 context
        vs = tracekit.run_rules([rec], acfg, lowered_keys=())
    return {v.rule for v in vs}


F32_8 = jax.ShapeDtypeStruct((8,), jnp.float32)
I32_8 = jax.ShapeDtypeStruct((8,), jnp.int32)


# ------------------------------------------------- seeded rule fixtures -----


def test_j001_f64_promotion_fires_exactly_once():
    bad = _wrap(lambda x: x.astype(jnp.float64) * 2.0, "j001_bad")
    ok = _wrap(lambda x: x * 2.0, "j001_ok")
    assert _rules_fired(bad, F32_8, x64=True) == {"J001"}
    assert _rules_fired(ok, F32_8) == set()


def test_j002_oversized_baked_constant():
    big = jnp.zeros((300, 1024), jnp.float32)        # 1.2 MB > 1 MiB
    small = jnp.arange(8, dtype=jnp.float32)
    bad = _wrap(lambda x: x + big[0, :8], "j002_bad")
    ok = _wrap(lambda x: x + small, "j002_ok")
    assert _rules_fired(bad, F32_8) == {"J002"}
    assert _rules_fired(ok, F32_8) == set()
    # threshold is a knob: raise it above the constant and the rule quiets
    lax = tracekit.AuditConfig(const_bytes=2 << 20)
    assert _rules_fired(bad, F32_8, acfg=lax) == set()


def test_j003_unhonored_donation():
    # output shape can't alias the donated input buffer -> donation is a
    # silent copy; the same-shape twin aliases and stays clean
    bad = _wrap(lambda x: x[:4] * 2.0, "j003_bad", donate_argnums=(0,))
    ok = _wrap(lambda x: x + 1.0, "j003_ok", donate_argnums=(0,))
    undeclared = _wrap(lambda x: x[:4] * 2.0, "j003_undeclared")
    assert _rules_fired(bad, F32_8) == {"J003"}
    assert _rules_fired(ok, F32_8) == set()
    assert _rules_fired(undeclared, F32_8) == set()


def test_j004_host_callback_in_traced_body():
    def bad_fn(x):
        jax.debug.print("nnz={n}", n=x.sum())
        return x + 1.0

    bad = _wrap(bad_fn, "j004_bad")
    ok = _wrap(lambda x: x + 1.0, "j004_ok")
    assert _rules_fired(bad, F32_8) == {"J004"}
    assert _rules_fired(ok, F32_8) == set()


def test_j005_int64_widening_vs_pair_compare():
    def packed(hi, lo):     # the anti-pattern CONTRACTS bans
        return (hi.astype(jnp.int64) << 32) | lo.astype(jnp.int64)

    def lexicographic(hi, lo):   # the pair-compare discipline
        return (hi < lo) | ((hi == lo) & (lo < hi))

    bad = _wrap(packed, "j005_bad")
    ok = _wrap(lexicographic, "j005_ok")
    assert _rules_fired(bad, I32_8, I32_8, x64=True) == {"J005"}
    # the clean twin stays int32 even with x64 enabled process-wide
    assert _rules_fired(ok, I32_8, I32_8, x64=True) == set()


def test_j006_retrace_surface_leak():
    w = _wrap(lambda x: x + 1.0, "j006")
    keys = [w._key((jax.ShapeDtypeStruct((n,), jnp.float32),))
            for n in (4, 8, 16, 32)]
    rec = tracekit.record(w, jax.ShapeDtypeStruct((4,), jnp.float32))
    tight = tracekit.AuditConfig(retrace_limit=3)
    vs = tracekit.run_rules([rec], tight, lowered_keys=keys)
    assert {v.rule for v in vs} == {"J006"}
    assert "4 distinct aval signatures" in vs[0].message
    # within the default limit (4) the same history is fine
    assert tracekit.run_rules([rec], lowered_keys=keys) == []
    # other entries' lowerings never count against this one
    other = [k[:1] + ("other-sig",) + k[2:] for k in keys]
    assert tracekit.run_rules([rec], tight, lowered_keys=other) == []


# ------------------------------------------------ suppression + baseline ----


def test_allow_comment_scanning_and_matching(tmp_path):
    good = tmp_path / "good"
    good.mkdir()
    (good / "owner.py").write_text(
        "# tracekit: allow(J004) entry=test.tracekit.* "
        "telemetry channel, removed in prod builds\n")
    allows = tracekit.scan_allows([str(good)])
    v = tracekit.Violation("J004", "test.tracekit.j004_bad",
                           "debug_callback", "m")
    assert tracekit.suppressed(v, allows)
    # wrong rule or non-matching glob never suppresses
    assert not tracekit.suppressed(
        tracekit.Violation("J001", v.entry, "f64", "m"), allows)
    assert not tracekit.suppressed(
        tracekit.Violation("J004", "service.ingest", "d", "m"), allows)

    # a reasonless allow is ignored — same discipline as reprolint
    bare = tmp_path / "bare"
    bare.mkdir()
    (bare / "owner.py").write_text(
        "# tracekit: allow(J004) entry=test.tracekit.*\n")
    assert not tracekit.suppressed(v, tracekit.scan_allows([str(bare)]))


def test_baseline_keys_are_line_free_and_counted(tmp_path):
    v = tracekit.Violation("J001", "svc.entry", "float64", "msg")
    assert v.key == "J001 svc.entry float64"
    path = tmp_path / "base.txt"
    path.write_text("# comment\n" + v.key + "\n")
    base = baseline.load_baseline(str(path))
    assert baseline.new_violations([v], base) == []
    # one baseline key admits exactly one occurrence
    assert baseline.new_violations([v, v], base) == [v]


def test_committed_baseline_is_empty():
    assert sum(baseline.load_baseline(
        tracekit.DEFAULT_BASELINE).values()) == 0


# ----------------------------------------------------------- budgets --------


def test_compare_budgets_verdicts():
    budgets = {"entries": {
        "e1 aaa": dict(flops=100.0, bytes_accessed=1000.0, peak_bytes=None),
        "e3 ccc": dict(flops=10.0, bytes_accessed=10.0, peak_bytes=10.0),
    }}
    measured = {
        "e1 aaa": dict(flops=120.0, bytes_accessed=1000.0, peak_bytes=5.0),
        "e2 bbb": dict(flops=1.0, bytes_accessed=1.0, peak_bytes=1.0),
    }
    diff = tracekit.compare_budgets(measured, budgets, tolerance=0.10)
    assert len(diff["breaches"]) == 1 and "e1 aaa" in diff["breaches"][0]
    assert diff["missing"] == ["e2 bbb"]
    assert diff["stale"] == ["e3 ccc"]
    # within tolerance on every field -> no breach
    close = {"e1 aaa": dict(flops=109.0, bytes_accessed=1050.0,
                            peak_bytes=None)}
    assert tracekit.compare_budgets(close, budgets, 0.10)["breaches"] == []
    # well under budget -> flagged as a ratchet candidate, not a failure
    low = {"e1 aaa": dict(flops=50.0, bytes_accessed=500.0,
                          peak_bytes=None)}
    d2 = tracekit.compare_budgets(low, budgets, 0.10)
    assert d2["breaches"] == [] and d2["improved"] == ["e1 aaa"]


# ------------------------------------------------- fleet audit + CLI --------


FLEET_ENTRIES = {"stream.ingest_instances", "service.ingest",
                 "service.point_query", "service.analytics", "hier.update",
                 "hier.flush", "hier.query_all",
                 "query.engine.point_lookup",
                 # the observability sample is a production dispatch too:
                 # audited + budgeted like every other fleet entry (ISSUE 9)
                 "hier.metrics_snapshot"}


def test_fleet_is_audit_clean():
    """Tier-1 gate: the production dispatch set is J-clean against the
    EMPTY committed baseline.  Budget values are machine-dependent and are
    enforced by the CI tracekit job, not here."""
    sig = stages.signature_of(cuts=(96, 384), block_size=32, lazy_l0=True,
                              batch_mode="grouped", l0_mode="auto")
    result = stages.audit(sig, instances=2, blocks=2, queries=8,
                          analytics_num_rows=256, analytics_k=4)
    assert [v.render() for v in result["fresh"]] == []
    assert {r.entry for r in result["records"]} >= FLEET_ENTRIES
    # every audited entry yields a budgetable cost row
    for key, row in result["measured"].items():
        assert row["flops"] is not None, key
        assert row["bytes_accessed"] is not None, key


@pytest.fixture(scope="module")
def budgets_file(tmp_path_factory):
    """Fresh budgets for THIS machine — the CLI tests exercise check
    semantics without coupling to the committed COST_BUDGETS.json."""
    path = tmp_path_factory.mktemp("budgets") / "COST_BUDGETS.json"
    assert tracekit.main(["--update", "--budgets", str(path), "-q"]) == 0
    return str(path)


def test_cli_check_clean_tree_exits_0(budgets_file):
    data = json.loads(open(budgets_file).read())
    assert {e["entry"] for e in data["entries"].values()} >= FLEET_ENTRIES
    assert tracekit.main(["--check", "--budgets", budgets_file, "-q"]) == 0


def test_cli_budget_breach_exits_1(budgets_file, tmp_path):
    data = json.loads(open(budgets_file).read())
    key = sorted(data["entries"])[0]
    data["entries"][key]["flops"] = 1.0      # guaranteed breach
    breach = tmp_path / "breach.json"
    breach.write_text(json.dumps(data))
    assert tracekit.main(["--check", "--budgets", str(breach), "-q"]) == 1


def test_cli_unbudgeted_entry_exits_1(tmp_path):
    assert tracekit.main(["--check", "-q",
                          "--budgets", str(tmp_path / "none.json")]) == 1


@pytest.mark.parametrize("rule", sorted(tracekit.RULES))
def test_cli_exits_1_on_each_seeded_rule(rule, budgets_file, monkeypatch):
    v = tracekit.Violation(rule, "test.seeded", "detail", "seeded")

    def fake_audit(cfg=None, **kw):
        return dict(records=[], violations=[v], suppressed=[],
                    fresh=[v], measured={})

    monkeypatch.setattr(tracekit, "audit_fleet", fake_audit)
    assert tracekit.main(["--check", "-q", "--budgets", budgets_file]) == 1
