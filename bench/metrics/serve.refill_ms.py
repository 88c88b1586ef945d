"""Time the engine takes to refill free lanes from its pending samples
(span ``serve.refill`` around ``SamplingEngine._fill`` in
``serve/engine.py``, when it fills any: the host arrays, their transfers
and the refill's dispatch); the median over the traced window, in
milliseconds.  Read from the program's span records
(``repro.serve.spans``); none where the program records no spans."""
from bench import harness


def read(run):
    try:
        from repro.serve import spans
    except ImportError:
        return None
    d = [s.end_ns - s.start_ns for s in spans.snapshot()
         if s.name == "serve.refill"]
    return 1e-6 * harness.percentile(d, 50) if d else None
