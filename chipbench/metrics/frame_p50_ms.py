"""Median request latency, due time to logits on the host (host clock)."""

from chipbench import reduce


def read(run):
    lat = run.latencies_ms
    return reduce.percentile(lat, 50) if lat.size else None
