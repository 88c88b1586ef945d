"""Host time to dispatch one compiled engine block (span
``serve.dispatch`` around the block's call in ``SamplingEngine.step``,
``serve/engine.py``); the median over the traced window, in milliseconds.
Read from the program's span records (``repro.serve.spans``); none where
the program records no spans."""
from bench import harness


def read(run):
    try:
        from repro.serve import spans
    except ImportError:
        return None
    d = [s.end_ns - s.start_ns for s in spans.snapshot()
         if s.name == "serve.dispatch"]
    return 1e-6 * harness.percentile(d, 50) if d else None
