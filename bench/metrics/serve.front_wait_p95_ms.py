"""Time a request spends outside the engine (``serve/front.py`` admission
queue and runner, ``serve/scheduler.py``): per request, its latency from
its due time minus the engine's own ``SampleResult.latency_s``; the 95th
percentile, in milliseconds."""
from bench import harness


def read(run):
    w = run.host.get("wait_s")
    return 1e3 * harness.percentile(w, 95) if w else None
