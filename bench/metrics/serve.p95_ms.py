"""The 95th percentile of the serving latency, each request timed from its
due time to its answer (failed ones count as infinite), in milliseconds.
A per-layer reading and not an end-to-end metric: at 0.8 x the knee its
runs spread too widely for any bound of at most 25%."""
from bench import harness


def read(run):
    lat = run.host.get("latency_s")
    return 1e3 * harness.percentile(lat, 95) if lat else None
