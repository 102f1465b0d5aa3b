"""Device time of the sort ops under the ingest dispatches (the merge's
canonicalization, ``assoc.merge_many`` -> XLA sort) per update ingested."""
from bench.trace import is_sort


def read(r):
    if not r.device_s.get("ingest") or not r.counts.get("updates"):
        return None
    return r.ops_of("ingest", is_sort) * 1e9 / r.counts["updates"]
