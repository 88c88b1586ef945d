"""Time a request spends in the engine (``serve/engine.py``), from its
submit to the drain that completes it (``SampleResult.latency_s``); the
95th percentile, in milliseconds."""
from bench import harness


def read(run):
    e = run.host.get("engine_s")
    return 1e3 * harness.percentile(e, 95) if e else None
