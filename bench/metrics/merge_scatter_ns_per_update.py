"""Device time of the scatters under the ingest dispatches (the merge's
``segment_sum`` of values and key scatters after each sort,
``assoc._canonicalize``) per update ingested."""
from bench.trace import is_scatter


def read(r):
    if not r.device_s.get("ingest") or not r.counts.get("updates"):
        return None
    return r.ops_of("ingest", is_scatter) * 1e9 / r.counts["updates"]
