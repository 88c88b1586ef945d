"""Time the runner takes to hand finished results to their requests
(span ``serve.handoff`` in ``serve/front.py``: ``take_results``, the
conversion to ``SampleResult`` and each future's completion, with the
callers' callbacks); the median over the traced window, in milliseconds.
Read from the program's span records (``repro.serve.spans``); none where
the program records no spans."""
from bench import harness


def read(run):
    try:
        from repro.serve import spans
    except ImportError:
        return None
    d = [s.end_ns - s.start_ns for s in spans.snapshot()
         if s.name == "serve.handoff"]
    return 1e-6 * harness.percentile(d, 50) if d else None
