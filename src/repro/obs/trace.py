"""Dispatch-level tracing: structured JSONL spans behind ``REPRO_OBS``.

Every concrete call through the jit front door (``stages.Wrapped`` →
``Compiled``) is a *dispatch*: entry name, config-signature digest, wall
time, its start and end on the epoch clock (``t0_ns``/``t1_ns``), compile
seconds when the call triggered staging work (split into ``lower_s`` and
``load_s``), and cache provenance (memory / disk / compile).  When tracing is enabled
(``REPRO_OBS=1`` or ``obs.enable()``), ``stages`` calls the hook
installed here and each span becomes one JSON line in
``<obs_dir>/obs.jsonl``.

Design constraints, mirrored from PR 7's debug-twin discipline:

- **Host-side only.**  The hook fires around the already-compiled
  executable call — it never participates in tracing, so production
  jaxprs are bit-identical with observability on or off and the fleet
  stays tracekit J004-clean (no host callbacks in traced code).  The
  off-path cost is a single module-global read per dispatch: zero extra
  lowerings, well under 1% dispatch wall (measured in
  EXPERIMENTS.md §Observability).
- **Mergeable across N processes.**  Records are appended with a single
  ``os.write`` on an ``O_APPEND`` fd — atomic on POSIX for these line
  sizes — so any number of launch processes can share one ``obs.jsonl``.
  Every record carries a per-process ``run`` id, a monotonic ``seq``, a
  wall-clock ``t`` and ``pid``; ``launch/monitor.py`` groups by (run,
  pid) and verifies ``seq`` gaps/ordering per process.
- **Optional profiler nesting.**  ``enable(annotate=True)`` (or
  ``REPRO_OBS_ANNOTATE=1``) wraps each executable call in a
  ``jax.profiler.TraceAnnotation(entry)`` so dispatch spans line up with
  device traces in TensorBoard/perfetto.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
import uuid
from typing import Optional

ENV = "REPRO_OBS"
ENV_DIR = "REPRO_OBS_DIR"
ENV_ANNOTATE = "REPRO_OBS_ANNOTATE"
DEFAULT_DIR = "obs"
FILENAME = "obs.jsonl"
# every record must carry these — launch/monitor's schema check
SCHEMA_FIELDS = ("ev", "run", "seq", "t", "pid")

_LOCK = threading.Lock()
_STATE = dict(enabled=False, fd=None, path=None, run=None, seq=0)


def env_enabled(env: Optional[str] = None) -> bool:
    """Truthiness convention shared with ``REPRO_CHECK``: unset, empty and
    ``"0"`` mean off."""
    v = os.environ.get(ENV) if env is None else env
    return v not in (None, "", "0")


def enabled() -> bool:
    return _STATE["enabled"]


def run_id() -> Optional[str]:
    return _STATE["run"]


def out_path() -> Optional[str]:
    return _STATE["path"]


def enable(obs_dir: Optional[str] = None, *,
           annotate: Optional[bool] = None) -> str:
    """Open ``<obs_dir>/obs.jsonl`` and install the stages dispatch hook.
    Idempotent; returns the JSONL path."""
    from repro import stages
    with _LOCK:
        if _STATE["enabled"]:
            return _STATE["path"]
        d = obs_dir or os.environ.get(ENV_DIR) or DEFAULT_DIR
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, FILENAME)
        fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        _STATE.update(enabled=True, fd=fd, path=path,
                      run=uuid.uuid4().hex[:12], seq=0)
    if annotate is None:
        annotate = env_enabled(os.environ.get(ENV_ANNOTATE))
    ann = None
    if annotate:
        try:
            from jax.profiler import TraceAnnotation as ann
        except Exception:           # profiler surface varies by jax build
            ann = None
    stages.set_trace_hook(_on_dispatch, annotation=ann)
    emit("obs_start", argv=list(sys.argv))
    return path


def disable() -> None:
    """Uninstall the hook and close the stream (flushes nothing — every
    record was already written atomically)."""
    from repro import stages
    stages.set_trace_hook(None)
    with _LOCK:
        fd = _STATE["fd"]
        _STATE.update(enabled=False, fd=None, path=None, run=None, seq=0)
    if fd is not None:
        os.close(fd)


def emit(ev: str, **fields) -> bool:
    """Append one event record; no-op (returns False) when disabled.
    Never raises into the caller — observability must not break the
    dispatch path."""
    with _LOCK:
        if not _STATE["enabled"]:
            return False
        _STATE["seq"] += 1
        rec = dict(ev=ev, run=_STATE["run"], seq=_STATE["seq"],
                   t=time.time(), pid=os.getpid())
        rec.update(fields)
        try:
            line = json.dumps(rec, separators=(",", ":")) + "\n"
            os.write(_STATE["fd"], line.encode())
        except (OSError, TypeError, ValueError):
            return False
    return True


def _on_dispatch(*, entry: str, digest: str, wall_s: float,
                 compile_s: float, lower_s: float, load_s: float,
                 provenance: str, t0_ns: int, t1_ns: int) -> None:
    """The hook ``stages.Wrapped.__call__`` fires per concrete dispatch.
    ``t0_ns``/``t1_ns`` are ``time.time_ns()``, the clock of a profiler
    trace's ``profile_start_time``, so a record can be placed on it."""
    emit("dispatch", entry=entry, sig=digest, wall_s=round(wall_s, 9),
         compile_s=round(compile_s, 6), lower_s=round(lower_s, 6),
         load_s=round(load_s, 6), prov=provenance, t0_ns=t0_ns,
         t1_ns=t1_ns)
