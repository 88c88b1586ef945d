"""The readers of the serving path's span metrics
(``bench/metrics/serve.*``), on planted span records with known answers,
and with no records at all."""
import sys

import pytest

import repro.serve
from bench import harness
from repro.serve import spans
from repro.serve.spans import Span

RUNNER, SUBMIT = 1, 2
MS = 1_000_000


def _span(name, start_ms, end_ms, parent=None, thread=RUNNER, **attrs):
    return Span(name, int(start_ms * MS), int(end_ms * MS), thread, parent,
                attrs)


def _planted():
    """Two 10 ms runner cycles, an admission, 20 ms of idle and a span of
    another thread; the runner's extent is 0..50 ms."""
    return [
        _span("serve.idle", 0, 10),
        _span("serve.queue", 9, 12, rid=0),
        _span("serve.admit", 12, 14, rid=0, samples=5, device_calls=3),
        _span("serve.sync", 14, 15, "serve.cycle"),
        _span("serve.refill", 15, 18, "serve.cycle", filled=5),
        _span("serve.dispatch", 18, 19, "serve.cycle", lanes_busy=5,
              lanes=64),
        _span("serve.cycle", 14, 24, device_calls=8),
        _span("serve.sync", 24, 25, "serve.cycle"),
        _span("serve.fetch", 25, 31, "serve.cycle", rows=5),
        _span("serve.dispatch", 31, 33, "serve.cycle", lanes_busy=16,
              lanes=64),
        _span("serve.handoff", 33, 34, "serve.cycle", results=1),
        _span("serve.cycle", 24, 34, device_calls=15),
        _span("serve.queue", 30, 37, rid=1),
        _span("serve.idle", 40, 50),
        _span("serve.idle", 0, 100, thread=SUBMIT),  # not a runner's extent
    ]


WANT = {
    "serve.cycle_ms": 10.0,
    "serve.sync_ms": 1.0,
    "serve.fetch_ms": 6.0,
    "serve.refill_ms": 3.0,
    "serve.dispatch_ms": 1.0,          # nearest-rank median of 1 and 2
    "serve.handoff_ms": 1.0,
    "serve.queue_wait_p95_ms": 7.0,
    "serve.lane_occupancy": 100.0 * (5 / 64 + 16 / 64) / 2,
    "serve.device_calls_per_cycle": (3 + 8 + 15) / 2,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_planted_records(name, monkeypatch):
    monkeypatch.setattr(spans, "snapshot", _planted)
    got = harness.metric_reader(name).read(None)
    assert got == pytest.approx(WANT[name], rel=1e-12)


def test_runner_idle_share_over_each_runner_threads_extent(monkeypatch):
    """20 ms idle in the runner's 50 ms, plus a second runner idle 5 of
    its 10 ms; the submitting thread's span has no top-level name."""
    recs = _planted()[:-1] + [
        _span("serve.idle", 0, 5, thread=3),
        _span("serve.cycle", 5, 10, thread=3, device_calls=2),
        _span("serve.queue", 0, 100, thread=SUBMIT)]
    monkeypatch.setattr(spans, "snapshot", lambda: recs)
    got = harness.metric_reader("serve.runner_idle_share").read(None)
    assert got == pytest.approx(100.0 * (20 + 5) / (50 + 10), rel=1e-12)


def test_admit_nested_in_a_cycle_is_counted_once(monkeypatch):
    """A request replayed inside a cycle (quarantine) is admitted under
    ``serve.cycle``, whose count already holds its calls."""
    recs = [_span("serve.admit", 1, 2, "serve.cycle", device_calls=3),
            _span("serve.cycle", 0, 5, device_calls=9)]
    monkeypatch.setattr(spans, "snapshot", lambda: recs)
    got = harness.metric_reader("serve.device_calls_per_cycle").read(None)
    assert got == 9


@pytest.mark.parametrize("name", sorted(WANT) + ["serve.runner_idle_share"])
def test_reader_reads_nothing_without_records(name, monkeypatch):
    monkeypatch.setattr(spans, "snapshot", lambda: [])
    assert harness.metric_reader(name).read(None) is None


@pytest.mark.parametrize("name", sorted(WANT) + ["serve.runner_idle_share"])
def test_reader_reads_nothing_from_a_program_without_spans(name,
                                                          monkeypatch):
    """An older program has no ``repro.serve.spans``: the reader returns
    ``None`` and does not raise."""
    monkeypatch.delattr(repro.serve, "spans")
    monkeypatch.setitem(sys.modules, "repro.serve.spans", None)
    assert harness.metric_reader(name).read(None) is None
