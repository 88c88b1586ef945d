"""Share of the engine's lanes that hold a sample when a block is
dispatched (counts ``lanes_busy`` and ``lanes`` of span
``serve.dispatch`` in ``serve/engine.py``; a dispatch that raised has no
``lanes_busy``); the mean over the blocks of the traced window, in
percent.  Read from the program's span records
(``repro.serve.spans``); none where the program records no spans."""


def read(run):
    try:
        from repro.serve import spans
    except ImportError:
        return None
    occ = [s.attrs["lanes_busy"] / s.attrs["lanes"]
           for s in spans.snapshot()
           if s.name == "serve.dispatch" and "lanes_busy" in s.attrs]
    return 100.0 * sum(occ) / len(occ) if occ else None
