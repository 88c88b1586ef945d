"""The median serving latency, each request timed from its due time to
its answer (failed ones count as infinite), in milliseconds.  A per-layer
reading and not an end-to-end metric: at 0.8 x the knee and a 10 s
window its runs spread too widely for a bound of at most 25%."""
from bench import harness


def read(run):
    lat = run.host.get("latency_s")
    return 1e3 * harness.percentile(lat, 50) if lat else None
