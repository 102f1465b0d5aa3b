"""Device time of the ingest dispatches per update ingested."""


def read(r):
    s = r.device_s.get("ingest")
    if not s or not r.counts.get("updates"):
        return None
    return s * 1e9 / r.counts["updates"]
