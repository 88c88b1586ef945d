"""Calls the runner thread makes to the device per engine cycle: the
``device_calls`` counts (``SamplingEngine.counters["device_calls"]``:
compiled programs, slices and host-device transfers) of the spans
``serve.cycle`` and top-level ``serve.admit`` in ``serve/front.py``,
summed over the traced window (a span left by an exception has none),
over the number of ``serve.cycle`` spans.
Read from the program's span records (``repro.serve.spans``); none where
the program records no spans."""


def read(run):
    try:
        from repro.serve import spans
    except ImportError:
        return None
    calls = cycles = 0
    for s in spans.snapshot():
        if s.name == "serve.cycle":
            cycles += 1
            calls += s.attrs.get("device_calls", 0)
        elif s.name == "serve.admit" and s.parent is None:
            calls += s.attrs.get("device_calls", 0)
    return calls / cycles if cycles else None
