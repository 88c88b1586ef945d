"""Share of the runner thread's recorded time it spent waiting for a
request with none in flight (span ``serve.idle`` in ``serve/front.py``):
summed ``serve.idle`` over the extent of the thread's top-level spans
(``serve.idle``, ``serve.admit``, ``serve.cycle``) in the traced window,
in percent, over every runner thread.  Read from the program's span
records (``repro.serve.spans``); none where the program records no
spans."""
TOP = ("serve.idle", "serve.admit", "serve.cycle")


def read(run):
    try:
        from repro.serve import spans
    except ImportError:
        return None
    extent, idle = {}, 0
    for s in spans.snapshot():
        if s.name in TOP and s.parent is None:
            a, b = extent.get(s.thread, (s.start_ns, s.end_ns))
            extent[s.thread] = (min(a, s.start_ns), max(b, s.end_ns))
            if s.name == "serve.idle":
                idle += s.end_ns - s.start_ns
    total = sum(b - a for a, b in extent.values())
    return 100.0 * idle / total if total > 0 else None
