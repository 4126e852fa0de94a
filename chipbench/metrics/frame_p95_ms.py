"""95th percentile of request latency, due time to logits on the host,
over every request the window answered (host clock)."""

from chipbench import reduce


def read(run):
    lat = run.latencies_ms
    return reduce.percentile(lat, 95) if lat.size else None
