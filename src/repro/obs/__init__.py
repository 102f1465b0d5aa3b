"""obskit — fleet-wide metrics, dispatch tracing, and SLO instrumentation.

Three host-side modules plus one device-side entry:

- ``obs.metrics``: counters/gauges + mergeable fixed log-bucket
  histograms (the ONE percentile implementation shared by
  ``query.service``, ``benchmarks/common`` and the SLO layer), and
  ``fleet_sample(states)`` → the ``hier.metrics_snapshot`` jitted entry
  (one dispatch per sample, audited/budgeted by tracekit).
- ``obs.trace``: per-dispatch JSONL spans hooked into the ``stages``
  front door behind ``REPRO_OBS=1`` / ``obs.enable()`` — host-side only,
  so production jaxprs are bit-identical with observability off.
- ``obs.slo``: latency SLOs with breach events, and a non-raising stall
  detector for serving loops.

Aggregation/dashboard lives in ``repro.launch.monitor`` (reads what
``obs.trace`` writes).
"""
from repro.obs import metrics, slo, trace                      # noqa: F401
from repro.obs.metrics import REGISTRY, Histogram, Registry    # noqa: F401
from repro.obs.slo import SLOTracker, StallDetector            # noqa: F401
from repro.obs.trace import disable, emit, enable, enabled     # noqa: F401

# REPRO_OBS=1 in the environment arms tracing at first import, the same
# convention as REPRO_CHECK — reliable for CLIs
# and CI without call-order footguns.
if trace.env_enabled():
    trace.enable()
