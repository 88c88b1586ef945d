"""Spans and counters of the serving path (``repro.serve.spans``).

A small bitseq front and engine on the CPU: with the profiler off a
served request leaves no records; under ``jax.profiler.trace`` every
engine cycle has its ``serve.cycle`` span with its children nested under
it and their counts set, the same names land on the trace's host plane,
children lie inside their parents and the runner's top-level spans do not
overlap.  No timing threshold: the spans' coverage of the runner's time
is a chip measurement.
"""
import glob
import threading
import time

import jax
import numpy as np
import pytest

from repro import recipes
from repro.envs.registry import make_env
from repro.serve import SampleRequest, SamplingEngine, Scheduler, ServeFront
from repro.serve import spans

BITSEQ = dict(env="bitseq", overrides={"n": 16, "k": 4})
CHILDREN = {"serve.sync", "serve.fetch", "serve.refill", "serve.dispatch",
            "serve.handoff"}
TOP = {"serve.idle", "serve.admit", "serve.cycle"}


def _options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


class _CountedRunner:
    """Counts the runner's cycles from outside its spans, and waits until
    the last one has closed."""

    def __init__(self, runner):
        self.started = self.done = 0
        inner = runner._drive_block

        def drive():
            self.started += 1
            try:
                inner()
            finally:
                self.done += 1

        runner._drive_block = drive
        self.runner = runner

    def settle(self, timeout=30.0):
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            if self.started == self.done and not self.runner.inflight:
                return
            time.sleep(0.01)
        raise AssertionError("the runner did not settle")


@pytest.fixture(scope="module")
def front():
    f = ServeFront(Scheduler(num_lanes=4), checkpoint_poll_s=None)
    f.request(SampleRequest(num_samples=3, seed=1, **BITSEQ))   # compiles
    yield f
    f.shutdown(drain=True, timeout=60)


@pytest.fixture(scope="module")
def traced(front, tmp_path_factory):
    """Serve a few requests through the front under a profiler session;
    returns the records, the trace file and the runner's cycle count."""
    runner = next(iter(front._runners.values()))
    counted = _CountedRunner(runner)
    log_dir = tmp_path_factory.mktemp("trace")
    spans.clear()
    with jax.profiler.trace(str(log_dir), profiler_options=_options()):
        futs = [front.submit(SampleRequest(num_samples=n, seed=100 + n,
                                           **BITSEQ))
                for n in (5, 2, 7)]
        for f in futs:
            f.result(timeout=120)
        counted.settle()
        time.sleep(0.3)             # the runner idles through whole waits
        stop_ns = time.perf_counter_ns()
    recs = spans.snapshot()
    spans.clear()
    xplane = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    return {"recs": recs, "xplane": xplane, "cycles": counted.started,
            "runner": runner.ident, "stop_ns": stop_ns}


@pytest.fixture(scope="module")
def engine():
    env = make_env("bitseq", n=16, k=4)
    env_params = env.init(jax.random.PRNGKey(0))
    policy = recipes.get("bitseq_tb").make_policy(env)
    policy_params = policy.init(jax.random.PRNGKey(0))
    eng = SamplingEngine(env, env_params, policy, policy_params,
                         num_lanes=3)
    eng.submit(num_samples=4, seed=3)
    eng.run()                                                   # compiles
    return eng


def test_profiler_off_leaves_no_records(front):
    spans.clear()
    front.request(SampleRequest(num_samples=4, seed=2, **BITSEQ))
    assert spans.snapshot() == []
    with spans.span("serve.cycle") as sp:
        assert sp is None


def test_device_calls_do_not_depend_on_the_recorder(engine, tmp_path):
    """The engine counts the same device calls for the same work with the
    recorder on and off."""
    def served_calls(seed):
        n0 = engine.counters["device_calls"]
        engine.submit(num_samples=5, seed=seed)
        engine.run()
        return engine.counters["device_calls"] - n0

    off = served_calls(21)
    with jax.profiler.trace(str(tmp_path), profiler_options=_options()):
        on = served_calls(21)
    spans.clear()
    assert on == off > 0


def test_every_cycle_has_its_span_with_children_and_counts(traced):
    recs = traced["recs"]
    cycles = [s for s in recs if s.name == "serve.cycle"]
    assert len(cycles) == traced["cycles"] > 0
    for s in cycles:
        assert s.parent is None and s.thread == traced["runner"]
        assert s.attrs["device_calls"] >= 1
    kids = [s for s in recs if s.name in CHILDREN]
    assert {s.name for s in kids} == CHILDREN
    assert all(s.parent == "serve.cycle" for s in kids)
    admits = [s for s in recs if s.name == "serve.admit"]
    assert sorted(s.attrs["samples"] for s in admits) == [2, 5, 7]
    assert all(s.attrs["rid"] >= 0 and s.attrs["device_calls"] >= 1
               for s in admits)
    queued = [s for s in recs if s.name == "serve.queue"]
    assert sorted(s.attrs["rid"] for s in queued) == sorted(
        s.attrs["rid"] for s in admits)
    by = {n: [s for s in recs if s.name == n] for n in CHILDREN}
    assert sum(s.attrs["rows"] for s in by["serve.fetch"]) == 14
    assert sum(s.attrs["filled"] for s in by["serve.refill"]) == 14
    assert sum(s.attrs["results"] for s in by["serve.handoff"]) == 3
    for s in by["serve.dispatch"]:
        assert 1 <= s.attrs["lanes_busy"] <= s.attrs["lanes"] == 4


def test_children_lie_inside_parents_and_top_level_spans_do_not_overlap(
        traced):
    recs = traced["recs"]
    for s in recs:
        assert s.start_ns <= s.end_ns
        if s.parent is None:
            continue
        assert any(p.name == s.parent and p.thread == s.thread
                   and p.start_ns <= s.start_ns and s.end_ns <= p.end_ns
                   for p in recs), s
    top = sorted((s for s in recs if s.name in TOP and s.parent is None),
                 key=lambda s: s.start_ns)
    assert {s.name for s in top} >= {"serve.admit", "serve.cycle"}
    assert all(s.thread == traced["runner"] for s in top)
    for a, b in zip(top, top[1:]):
        assert a.end_ns <= b.start_ns, (a, b)


def test_span_names_land_on_the_trace_host_plane(traced):
    assert len(traced["xplane"]) == 1
    prof = jax.profiler.ProfileData.from_file(traced["xplane"][0])
    names = {ev.name for plane in prof.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("serve.")}
    # a span still open when the session stops is recorded, but the
    # profiler drops its event
    closed = {s.name for s in traced["recs"] if s.end_ns < traced["stop_ns"]}
    assert closed == TOP | CHILDREN | {"serve.queue"}
    assert closed <= names
    # the benchmark's gap labels are its own ``bench.*`` spans
    assert not any(s.name.startswith("bench.") for s in traced["recs"])


def test_lanes_busy_matches_the_lanes_the_test_counts(engine, tmp_path):
    """Driving the engine by hand, the test tracks the occupied lanes from
    what each step drained and the samples still pending; each block's
    ``lanes_busy`` must equal that count."""
    L = engine.num_lanes
    pending = 0
    for n, seed in ((2, 31), (5, 32)):
        engine.submit(num_samples=n, seed=seed)
        pending += n
    busy, want = 0, []
    spans.clear()
    with jax.profiler.trace(str(tmp_path), profiler_options=_options()):
        while pending or busy:
            blocks = engine.counters["blocks"]
            busy -= engine.step()
            filled = min(L - busy, pending)
            busy, pending = busy + filled, pending - filled
            if engine.counters["blocks"] > blocks:
                want.append(busy)
    got = [s.attrs["lanes_busy"] for s in spans.snapshot()
           if s.name == "serve.dispatch"]
    spans.clear()
    assert got == want and max(want) == L


def test_recorder_is_thread_safe_and_bounded():
    rec = spans.Recorder(maxlen=64)
    before = spans._recording
    spans._recording = lambda: True
    try:
        def work(i):
            for _ in range(50):
                with rec.span("serve.x", i=i):
                    with rec.span("serve.y"):
                        pass
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
    finally:
        spans._recording = before
    got = rec.snapshot()
    assert len(got) == 64
    assert all(s.parent == ("serve.x" if s.name == "serve.y" else None)
               for s in got)
    assert np.all([s.start_ns <= s.end_ns for s in got])
