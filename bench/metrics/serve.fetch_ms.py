"""Time the engine's drain spends on a block in which lanes finished
(span ``serve.fetch`` in ``serve/engine.py``: the compaction's dispatch,
the row fetches and the assembly of results); the median over the traced
window, in milliseconds.  Read from the program's span records
(``repro.serve.spans``); none where the program records no spans."""
from bench import harness


def read(run):
    try:
        from repro.serve import spans
    except ImportError:
        return None
    d = [s.end_ns - s.start_ns for s in spans.snapshot()
         if s.name == "serve.fetch"]
    return 1e-6 * harness.percentile(d, 50) if d else None
