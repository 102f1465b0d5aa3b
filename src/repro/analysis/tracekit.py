"""tracekit — jaxpr/HLO-level audit + committed cost budgets for the fleet.

``repro.analysis.lint`` (PR 7) enforces contracts at the SOURCE level; it
is structurally blind to what the *compiled* hot path actually does.  The
paper's 1.9B upd/s (arXiv:1902.00846) — and the 40x follow-up's 75B
inserts/s (arXiv:2001.06935) — live or die on bytes moved per merge, so a
silent dtype upcast, a giant baked-in constant, or an unhonored donation
is a perf bug even when every source line is clean.  Since PR 6 every
production dispatch routes through ``repro.stages``, which now keeps the
closed jaxpr on each ``Lowered`` — ONE choke point where the entire fleet
dispatch set can be audited post-lowering.

Run as::

    python -m repro.analysis.tracekit --check     # CI / tier-1 gate
    python -m repro.analysis.tracekit --update    # regenerate budgets

Rules (each guards a compiled-artifact invariant source lint cannot see):

J001  float64/complex128 anywhere in a traced computation.  x64 is off in
      production; an f64 aval means someone enabled it (import-order
      accident) — a silent 2x bandwidth hit on every buffer it touches.
J002  Closure-captured constant above a size threshold baked into the
      executable: compile bloat, AOT-cache key instability, and a copy of
      the constant in every specialization.  State belongs in arguments.
J003  Declared donation not honored: the entry was built with
      ``donate_argnums`` but the compiled module carries no
      ``input_output_alias`` — every service round copies the whole fleet
      state it believed it was updating in place.
J004  Host callback (``pure_callback``/``io_callback``/``debug_callback``,
      incl. ``jax.debug.print``) reachable from a production entry: a
      device->host sync on the hot path.
J005  Integer widening: a 64-bit integer intermediate produced from
      <=32-bit integer inputs.  The (hi, lo) pair-compare discipline
      (core/assoc.py CONTRACTS) exists precisely so key compares never
      pay int64 bandwidth; packing pairs into int64 defeats it.
J006  Retrace-surface leak: one (entry, signature) lowered under more
      than N distinct abstract-shape signatures in this process — shape
      polymorphism leaking through the signature, each leak a separate
      compile + cache entry.

Suppression: jaxprs have no source lines, so allows are PER ENTRY — put

    # tracekit: allow(J004) entry=service.ingest <reason>

on any line in the audited source tree (``--src``, default ``src/``).
The entry field is an ``fnmatch`` glob; the reason is mandatory.
Accepted debt can also live in the committed baseline
(``tracekit_baseline.txt``, same machinery as reprolint via
``repro.analysis.baseline`` — it starts and stays empty).

Cost budgets: ``--update`` records per-(entry, signature)
``cost_analysis()`` FLOPs / bytes-accessed / peak temp memory into the
committed ``COST_BUDGETS.json``; ``--check`` fails when any entry exceeds
its budget by more than ``--tolerance`` (default 10%) or dispatches an
entry with no budget at all.  Budgets are perf contracts enforced like
tests: a change that quietly doubles the bytes a merge moves now fails CI
with a table instead of landing.
"""
from __future__ import annotations

import argparse
import dataclasses
import fnmatch
import hashlib
import json
import os
import re
import sys
import time
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from repro.analysis import baseline as _baseline

RULES = {
    "J001": "float64/complex128 aval in a traced computation (x64 leak)",
    "J002": "oversized closure constant baked into the executable",
    "J003": "declared donation not honored by the compiled module",
    "J004": "host callback reachable from a production entry",
    "J005": "int64 intermediate widened from <=32-bit integer inputs",
    "J006": "entry lowered under too many distinct aval signatures",
}

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "tracekit_baseline.txt")
DEFAULT_BUDGETS = os.path.join(_ROOT, "COST_BUDGETS.json")
DEFAULT_SRC = os.path.join(_ROOT, "src")
DEFAULT_TOLERANCE = 0.10

_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "debug_print", "callback"}

_ALLOW_RE = re.compile(
    r"#\s*tracekit:\s*allow\(([A-Za-z0-9, ]+)\)\s+entry=(\S+)\s*(.*)$")


@dataclasses.dataclass(frozen=True)
class Violation:
    rule: str
    entry: str
    detail: str          # stable scope token — the baseline identity
    message: str

    @property
    def key(self) -> str:
        return f"{self.rule} {self.entry} {self.detail}"

    def render(self) -> str:
        return f"{self.entry}: {self.rule} {self.message}"


@dataclasses.dataclass
class AuditConfig:
    """Rule thresholds.  ``const_bytes``: J002 fires above this many bytes
    in one baked constant.  ``retrace_limit``: J006 fires when one
    (entry, signature) has been lowered under MORE than this many distinct
    aval signatures."""
    const_bytes: int = 1 << 20
    retrace_limit: int = 4


# ------------------------------------------------------------- records ------


class AuditRecord:
    """One audited cache entry: the staged artifacts (jaxpr / compiled HLO
    / cost model) behind a single (entry, signature, avals) key."""

    def __init__(self, entry: str, wrapped, args: tuple):
        self.entry = entry
        self.wrapped = wrapped
        self.args = args
        self.sig = wrapped.sig
        self.key = wrapped._key(args)
        self._lowered = None
        self._compiled = None

    @property
    def lowered(self):
        if self._lowered is None:
            self._lowered = self.wrapped.lower(*self.args)
        return self._lowered

    @property
    def compiled(self):
        if self._compiled is None:
            self._compiled = self.lowered.compile()
        return self._compiled

    @property
    def jaxpr(self):
        return self.lowered.jaxpr

    @property
    def donate_argnums(self) -> Tuple[int, ...]:
        return tuple(dict(self.wrapped.jit_kwargs).get("donate_argnums",
                                                       ()))

    def hlo(self) -> str:
        # Compiled.as_text degrades to the re-lowered IR for deserialized
        # executables that cannot answer (stages satellite, ISSUE 8)
        return self.compiled.as_text()

    def cost(self) -> dict:
        try:
            return self.compiled.cost_analysis()
        except Exception:
            return {}

    def peak_bytes(self) -> Optional[int]:
        try:
            mem = self.compiled.memory_analysis()
        except Exception:
            return None
        return None if mem is None \
            else int(getattr(mem, "temp_size_in_bytes", 0))


def record(wrapped, *args, entry: Optional[str] = None) -> AuditRecord:
    """Build an audit record for one staged entry (fixture tests drive the
    rules through this without touching the global cache scan)."""
    return AuditRecord(entry or wrapped.entry, wrapped, tuple(args))


# ---------------------------------------------------------- jaxpr walking ---


def _iter_jaxprs(jaxpr) -> Iterable:
    """The jaxpr and every sub-jaxpr reachable through eqn params
    (pjit/scan/while bodies, cond branches, custom_* rules...)."""
    closed = getattr(jaxpr, "jaxpr", None)
    inner = closed if closed is not None else jaxpr
    yield jaxpr
    for eqn in getattr(inner, "eqns", ()):
        for val in eqn.params.values():
            for sub in _subjaxprs_of(val):
                yield from _iter_jaxprs(sub)


def _subjaxprs_of(val) -> Iterable:
    if hasattr(val, "eqns") or hasattr(val, "jaxpr"):
        yield val
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _subjaxprs_of(v)


def _inner(jaxpr):
    return getattr(jaxpr, "jaxpr", jaxpr)


def _eqns(jaxpr) -> Iterable:
    for j in _iter_jaxprs(jaxpr):
        yield from getattr(_inner(j), "eqns", ())


def _consts(jaxpr) -> Iterable:
    for j in _iter_jaxprs(jaxpr):
        yield from getattr(j, "consts", ())


def _aval_of(var):
    return getattr(var, "aval", None)


def _all_avals(jaxpr) -> Iterable[Tuple[object, str]]:
    """Every aval in the computation with a short location label."""
    for j in _iter_jaxprs(jaxpr):
        inner = _inner(j)
        for var in getattr(inner, "invars", ()):
            a = _aval_of(var)
            if a is not None:
                yield a, "invar"
        for eqn in getattr(inner, "eqns", ()):
            for var in eqn.outvars:
                a = _aval_of(var)
                if a is not None:
                    yield a, eqn.primitive.name


def _dtype_of(aval):
    return getattr(aval, "dtype", None)


# ----------------------------------------------------------------- rules ----


def _j001(rec: AuditRecord, cfg: AuditConfig) -> Iterable[Violation]:
    if rec.jaxpr is None:
        return
    hits: Dict[str, str] = {}
    for aval, where in _all_avals(rec.jaxpr):
        dt = _dtype_of(aval)
        if dt is not None and dt.kind in ("f", "c") and dt.itemsize >= 8:
            hits.setdefault(dt.name, where)
    for name, where in sorted(hits.items()):
        yield Violation(
            "J001", rec.entry, name,
            f"{name} aval (first at '{where}') in the traced computation "
            "— x64 is off in production; this is a silent 2x bandwidth "
            "hit or a truncation waiting at the boundary")


def _j002(rec: AuditRecord, cfg: AuditConfig) -> Iterable[Violation]:
    if rec.jaxpr is None:
        return
    seen: Set[str] = set()
    for c in _consts(rec.jaxpr):
        nbytes = getattr(c, "nbytes", None)
        if nbytes is None:
            continue
        if nbytes > cfg.const_bytes:
            shape = "x".join(map(str, getattr(c, "shape", ())))
            dt = getattr(getattr(c, "dtype", None), "name", "?")
            detail = f"const[{shape}:{dt}]"
            if detail in seen:
                continue
            seen.add(detail)
            yield Violation(
                "J002", rec.entry, detail,
                f"closure constant {shape}:{dt} ({nbytes} bytes > "
                f"{cfg.const_bytes}) baked into the executable — compile "
                "bloat + AOT-cache key instability; pass it as an "
                "argument instead")


def _j003(rec: AuditRecord, cfg: AuditConfig) -> Iterable[Violation]:
    donated = rec.donate_argnums
    if not donated:
        return
    try:
        hlo = rec.hlo()
    except Exception:
        return
    if "input_output_alias" not in hlo:
        yield Violation(
            "J003", rec.entry, "donation",
            f"donate_argnums={donated} declared but the compiled module "
            "has NO input_output_alias — the donated buffers are copied, "
            "not reused; every service round copies the whole state")


def _j004(rec: AuditRecord, cfg: AuditConfig) -> Iterable[Violation]:
    if rec.jaxpr is None:
        return
    hit: Set[str] = set()
    for eqn in _eqns(rec.jaxpr):
        name = eqn.primitive.name
        if name in _CALLBACK_PRIMS and name not in hit:
            hit.add(name)
            yield Violation(
                "J004", rec.entry, name,
                f"host callback '{name}' reachable from a production "
                "entry — a device->host sync (and a debug leftover, if "
                "this is jax.debug.print) on the hot path")


def _j005(rec: AuditRecord, cfg: AuditConfig) -> Iterable[Violation]:
    if rec.jaxpr is None:
        return
    seen: Set[str] = set()
    for eqn in _eqns(rec.jaxpr):
        in_ints = [(_dtype_of(_aval_of(v))) for v in eqn.invars]
        in_ints = [d for d in in_ints if d is not None and d.kind in "iu"]
        if not in_ints or any(d.itemsize >= 8 for d in in_ints):
            continue
        for var in eqn.outvars:
            dt = _dtype_of(_aval_of(var))
            if dt is not None and dt.kind in "iu" and dt.itemsize >= 8:
                prim = eqn.primitive.name
                if prim in seen:
                    continue
                seen.add(prim)
                yield Violation(
                    "J005", rec.entry, f"widen:{prim}",
                    f"'{prim}' widens <=32-bit integer inputs to "
                    f"{dt.name} — (hi, lo) pair-compares must stay int32 "
                    "(core/assoc.py CONTRACTS), packing into int64 "
                    "doubles key bandwidth across the kernel boundary")


def _j006(records: Sequence[AuditRecord], cfg: AuditConfig,
          lowered_keys: Sequence) -> Iterable[Violation]:
    """Unlike J001-J005 this is a process-level rule: it counts every
    lowering the stages cache has seen for the audited (entry, signature)
    pairs, not just the audited records themselves."""
    # job labels (r.entry) can differ from the cache key's own entry name
    # (service.ingest wraps the stream entry) — match on key identity,
    # report under the audited label.
    audited = {(r.key[0], r.key[1]): r.entry for r in records}
    per: Dict[Tuple, Set] = {}
    for key in lowered_keys:
        ident = (key[0], key[1])
        if ident in audited:
            per.setdefault(ident, set()).add((key[4], key[5]))
    for ident, avals in sorted(per.items(), key=lambda kv: audited[kv[0]]):
        if len(avals) > cfg.retrace_limit:
            yield Violation(
                "J006", audited[ident], "retrace",
                f"lowered under {len(avals)} distinct aval signatures "
                f"(limit {cfg.retrace_limit}) in one process — shape "
                "polymorphism is leaking through the signature; each "
                "leak is a separate compile + cache entry")


_RECORD_RULES = (_j001, _j002, _j003, _j004, _j005)


def run_rules(records: Sequence[AuditRecord],
              cfg: Optional[AuditConfig] = None,
              lowered_keys: Optional[Sequence] = None) -> List[Violation]:
    """All J-rule violations over ``records`` (unsuppressed view — allows
    and baseline are applied by the caller/CLI)."""
    cfg = cfg or AuditConfig()
    out: List[Violation] = []
    for rec in records:
        for rule in _RECORD_RULES:
            out.extend(rule(rec, cfg))
    if lowered_keys is None:
        from repro import stages
        lowered_keys = stages.lowered_keys()
    out.extend(_j006(records, cfg, lowered_keys))
    return sorted(out, key=lambda v: (v.entry, v.rule, v.detail))


# ----------------------------------------------------------- suppression ----


def scan_allows(paths: Sequence[str]) -> List[Tuple[Set[str], str, str]]:
    """Collect ``# tracekit: allow(J00x) entry=<glob> <reason>`` comments
    from the source tree.  Jaxprs have no source lines, so allows are
    per-entry: the glob names the entry (or entries) being excused, and a
    missing reason does not suppress — same discipline as reprolint."""
    from repro.analysis.lint import iter_py_files
    out: List[Tuple[Set[str], str, str]] = []
    for path in iter_py_files(paths):
        with open(path, "r", encoding="utf-8") as fh:
            for line in fh:
                m = _ALLOW_RE.search(line)
                if m:
                    rules = {r.strip() for r in m.group(1).split(",")
                             if r.strip()}
                    out.append((rules, m.group(2), m.group(3).strip()))
    return out


def suppressed(v: Violation,
               allows: Sequence[Tuple[Set[str], str, str]]) -> bool:
    return any(v.rule in rules and reason
               and fnmatch.fnmatchcase(v.entry, glob)
               for rules, glob, reason in allows)


# ---------------------------------------------------------------- budgets ---

_BUDGET_FIELDS = ("flops", "bytes_accessed", "peak_bytes")


def _sig_digest(rec: AuditRecord) -> str:
    # Deliberately excludes the jax version (unlike the span digest): a
    # toolchain bump should show up as a budget DIFF, not a key change
    # that silently orphans every committed budget.
    text = "|".join([repr(rec.sig), str(rec.key[2]), str(rec.key[3]),
                     str(rec.key[4]), repr(rec.key[5])])
    return hashlib.sha256(text.encode()).hexdigest()[:12]


def measure(records: Sequence[AuditRecord]) -> Dict[str, dict]:
    """Per-(entry, signature) cost rows keyed ``"<entry> <digest>"``."""
    out: Dict[str, dict] = {}
    for rec in records:
        cost = rec.cost()
        out[f"{rec.entry} {_sig_digest(rec)}"] = dict(
            entry=rec.entry,
            signature=_sig_summary(rec.sig),
            flops=cost.get("flops"),
            bytes_accessed=cost.get("bytes accessed"),
            peak_bytes=rec.peak_bytes(),
        )
    return out


def _sig_summary(sig) -> str:
    parts = []
    for f in dataclasses.fields(sig):
        v = getattr(sig, f.name)
        if v not in (None, (), False) and not (f.name == "dtype"
                                               and v == "float32") \
                and not (f.name == "sr" and v == "plus.times") \
                and not (f.name == "chunk" and v == 1):
            parts.append(f"{f.name}={v}")
    return " ".join(parts) or "<default>"


def load_budgets(path: str) -> dict:
    if not os.path.exists(path):
        return {}
    with open(path, "r", encoding="utf-8") as fh:
        return json.load(fh)


def write_budgets(path: str, measured: Dict[str, dict],
                  tolerance: float) -> None:
    import jax
    payload = {
        "_meta": dict(
            tolerance=tolerance,
            generated=time.strftime("%Y-%m-%dT%H:%M:%S"),
            jax=jax.__version__, backend=jax.default_backend(),
            command="python -m repro.analysis.tracekit --update",
            note="committed per-(entry, signature) cost budgets — "
                 "--check fails when an entry exceeds its budget by "
                 "more than the tolerance",
        ),
        "entries": {k: measured[k] for k in sorted(measured)},
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
        fh.write("\n")


def compare_budgets(measured: Dict[str, dict], budgets: dict,
                    tolerance: float = DEFAULT_TOLERANCE) -> dict:
    """Budget-vs-actual diff: ``breaches`` (actual > budget * (1+tol)),
    ``missing`` (dispatched but unbudgeted — a new entry must be
    committed via --update), ``stale`` (budgeted but not dispatched),
    ``improved`` (actual < budget / (1+tol) — candidates to ratchet
    down), and the full ``rows`` table."""
    entries = budgets.get("entries", {})
    breaches, missing, improved, rows = [], [], [], []
    for key, act in sorted(measured.items()):
        bud = entries.get(key)
        if bud is None:
            missing.append(key)
            rows.append((key, None, act, "MISSING"))
            continue
        verdict = "ok"
        for field in _BUDGET_FIELDS:
            b, a = bud.get(field), act.get(field)
            if b in (None, 0) or a is None:
                continue
            if a > b * (1.0 + tolerance):
                verdict = "BREACH"
                breaches.append(
                    f"{key}: {field} {a:.4g} > budget {b:.4g} "
                    f"(+{(a / b - 1) * 100:.1f}%, tolerance "
                    f"{tolerance * 100:.0f}%)")
            elif a < b / (1.0 + tolerance) and verdict == "ok":
                verdict = "improved"
        if verdict == "improved":
            improved.append(key)
        rows.append((key, bud, act, verdict))
    stale = sorted(set(entries) - set(measured))
    return dict(breaches=breaches, missing=missing, stale=stale,
                improved=improved, rows=rows)


def render_budget_table(rows) -> str:
    out = [f"{'entry (sig digest)':<52s} {'field':<14s} "
           f"{'budget':>12s} {'actual':>12s}  verdict"]
    for key, bud, act, verdict in rows:
        first = True
        for field in _BUDGET_FIELDS:
            b = "-" if bud is None or bud.get(field) is None \
                else f"{bud[field]:.4g}"
            a = "-" if act.get(field) is None else f"{act[field]:.4g}"
            label = key if first else ""
            tag = verdict if first else ""
            out.append(f"{label:<52s} {field:<14s} {b:>12s} {a:>12s}  "
                       f"{tag}")
            first = False
    return "\n".join(out)


# ------------------------------------------------------------ fleet audit ---


def audit_fleet(cfg=None, *, audit_cfg: Optional[AuditConfig] = None,
                src: Sequence[str] = (DEFAULT_SRC,),
                baseline_path: str = DEFAULT_BASELINE,
                **fleet_kw) -> dict:
    """Precompile a config's whole dispatch set (``stages.fleet_jobs`` —
    the SAME jobs ``precompile_fleet`` warms) and audit every artifact.

    Returns ``violations`` (every hit), ``fresh`` (neither allowed in-tree
    nor baselined — the failing set), ``measured`` (the cost rows budgets
    are checked against) and the ``records`` themselves.  ``cfg`` defaults
    to the d4m-stream smoke config; pass ``analytics_num_rows`` etc.
    through ``fleet_kw`` to widen the set, exactly as for
    ``precompile_fleet``."""
    from repro import stages
    if cfg is None:
        from repro.configs import d4m_stream
        cfg = d4m_stream.smoke_config()
    if not isinstance(cfg, stages.Signature) \
            and "analytics_num_rows" not in fleet_kw:
        scale = int(getattr(cfg, "rmat_scale", 0) or 0)
        if scale:
            fleet_kw["analytics_num_rows"] = 1 << scale
    jobs = stages.fleet_jobs(cfg, **fleet_kw)
    records = [record(w, *args, entry=e) for e, w, args in jobs]
    violations = run_rules(records, audit_cfg)
    allows = scan_allows(list(src)) if src else []
    unsuppressed = [v for v in violations if not suppressed(v, allows)]
    base = _baseline.load_baseline(baseline_path)
    fresh = _baseline.new_violations(unsuppressed, base)
    return dict(records=records, violations=violations,
                suppressed=[v for v in violations
                            if suppressed(v, allows)],
                fresh=fresh, measured=measure(records))


_BASELINE_HEADER = (
    "# tracekit baseline — accepted pre-existing debt, one\n"
    "# 'RULE entry detail' key per violation.  Regenerate with\n"
    "#   python -m repro.analysis.tracekit --write-baseline\n"
    "# New violations (keys not in this file) fail the audit; prefer\n"
    "# reasoned '# tracekit: allow(J00x) entry=<glob> <reason>' comments\n"
    "# in-tree so the debt stays visible next to its owner.\n")


def _resolve_config(name: str):
    from repro.configs import d4m_stream
    return (d4m_stream.config() if name == "production"
            else d4m_stream.smoke_config())


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.tracekit",
        description="jaxpr/HLO audit + cost budgets over the fleet "
                    "dispatch set (J001-J006)")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--check", action="store_true", default=True,
                      help="audit + budget check (default); exit 1 on new "
                      "violations or budget breaches")
    mode.add_argument("--update", action="store_true",
                      help="regenerate COST_BUDGETS.json with a printed "
                      "diff against the committed budgets")
    mode.add_argument("--write-baseline", action="store_true",
                      help="accept current J-violations as the baseline")
    ap.add_argument("--config", default="smoke",
                    choices=("smoke", "production"),
                    help="fleet config to audit (default: smoke — the "
                    "entry set is identical, only shapes differ)")
    ap.add_argument("--budgets", default=DEFAULT_BUDGETS,
                    help="budget file (default: committed "
                    "COST_BUDGETS.json)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--src", nargs="*", default=[DEFAULT_SRC],
                    help="source tree scanned for allow comments")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="budget tolerance (default: the budget file's, "
                    f"else {DEFAULT_TOLERANCE})")
    ap.add_argument("--const-bytes", type=int, default=None,
                    help="J002 threshold in bytes")
    ap.add_argument("--retrace-limit", type=int, default=None,
                    help="J006 distinct-aval-signature limit")
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)

    acfg = AuditConfig()
    if args.const_bytes is not None:
        acfg.const_bytes = args.const_bytes
    if args.retrace_limit is not None:
        acfg.retrace_limit = args.retrace_limit

    result = audit_fleet(_resolve_config(args.config), audit_cfg=acfg,
                         src=args.src, baseline_path=args.baseline)
    fresh, measured = result["fresh"], result["measured"]

    if args.write_baseline:
        unsuppressed = [v for v in result["violations"]
                        if v not in result["suppressed"]]
        _baseline.write_baseline(args.baseline, unsuppressed,
                                 _BASELINE_HEADER)
        print(f"baseline written: {len(unsuppressed)} entries -> "
              f"{args.baseline}")
        return 0

    budgets = load_budgets(args.budgets)
    tol = args.tolerance if args.tolerance is not None \
        else budgets.get("_meta", {}).get("tolerance", DEFAULT_TOLERANCE)

    if args.update:
        diff = compare_budgets(measured, budgets, tol)
        write_budgets(args.budgets, measured, tol)
        print(f"budgets written: {len(measured)} entries -> "
              f"{args.budgets}")
        if not args.quiet:
            print(render_budget_table(diff["rows"]))
            for line in diff["breaches"]:
                print(f"  was-breach: {line}")
            for key in diff["stale"]:
                print(f"  dropped stale entry: {key}")
        return 0

    # --check
    if not args.quiet:
        for v in fresh:
            print(v.render())
    counts = _baseline.per_rule_counts(result["violations"], RULES)
    fresh_counts = _baseline.per_rule_counts(fresh, RULES)
    print("tracekit per-rule counts (total / new):")
    for rule in sorted(counts):
        print(f"  {rule}: {counts[rule]} / {fresh_counts.get(rule, 0)}"
              f"  — {RULES.get(rule, 'internal')}")
    n_sup = len(result["suppressed"])
    print(f"{len(result['violations'])} violation(s), {n_sup} allowed, "
          f"{len(fresh)} new")

    diff = compare_budgets(measured, budgets, tol)
    print(f"cost budgets ({args.budgets}, tolerance {tol * 100:.0f}%):")
    print(render_budget_table(diff["rows"]))
    for line in diff["breaches"]:
        print(f"BUDGET BREACH: {line}")
    for key in diff["missing"]:
        print(f"NO BUDGET: {key} — run --update and commit the diff")
    for key in diff["stale"]:
        print(f"stale budget (not dispatched): {key}")
    ok = not fresh and not diff["breaches"] and not diff["missing"]
    print("tracekit:", "clean" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
