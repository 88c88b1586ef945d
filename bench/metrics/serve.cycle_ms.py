"""Length of one engine cycle on the runner thread (span ``serve.cycle``
around ``_EngineRunner._drive_block`` in ``serve/front.py``: the engine's
drain, refill and block dispatch, the hand-off of results and the
deadline checks); the median over the traced window, in milliseconds.
Read from the program's span records (``repro.serve.spans``); none where
the program records no spans."""
from bench import harness


def read(run):
    try:
        from repro.serve import spans
    except ImportError:
        return None
    d = [s.end_ns - s.start_ns for s in spans.snapshot()
         if s.name == "serve.cycle"]
    return 1e-6 * harness.percentile(d, 50) if d else None
