"""Time a request waits in its runner's admission queue, from
``ServeFront.submit`` to the start of its admission (span ``serve.queue``
in ``serve/front.py``); the 95th percentile over the traced window, in
milliseconds.  Read from the program's span records
(``repro.serve.spans``); none where the program records no spans."""
from bench import harness


def read(run):
    try:
        from repro.serve import spans
    except ImportError:
        return None
    d = [s.end_ns - s.start_ns for s in spans.snapshot()
         if s.name == "serve.queue"]
    return 1e-6 * harness.percentile(d, 95) if d else None
