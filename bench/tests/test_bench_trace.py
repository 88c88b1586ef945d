"""The trace reduction, on a trace of ``bitseq120.train_tb_b16`` recorded
on a TPU v5e (a 10 ms traced window of ``bench/run.py``, the profiler's
``xplane.pb`` as written) and kept beside this file."""
from pathlib import Path

import jax
import pytest

from bench import harness, trace

TRACE = Path(__file__).parent / "data" / "bitseq120.train_tb_b16.xplane.pb"
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def profile():
    return jax.profiler.ProfileData.from_file(str(TRACE))


@pytest.fixture(scope="module")
def red(profile):
    return trace.reduce_profile(profile)


def plain_busy(profile):
    """Window and busy time by a direct pass: the ``bench.window`` span,
    and the union of ``XLA Ops`` intervals starting inside it."""
    w0 = w1 = None
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "bench.window":
                        w0, w1 = ev.start_ns, ev.end_ns
    ivs = []
    for plane in profile.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ivs += [(ev.start_ns, min(ev.end_ns, w1))
                            for ev in line.events if w0 <= ev.start_ns < w1]
    busy, end = 0.0, None
    for s, e in sorted(ivs):
        if end is None or s > end:
            busy += e - s
            end = e
        elif e > end:
            busy += e - end
            end = e
    return (w1 - w0) * 1e-9, busy * 1e-9


def test_busy_and_window_match_a_plain_pass(profile, red):
    window, busy = plain_busy(profile)
    assert red.devices == 1
    assert red.window_s == pytest.approx(window, rel=1e-9)
    assert red.busy_s == pytest.approx(busy, rel=1e-9)
    assert 0 < red.busy_s < red.window_s
    assert 0 < red.idle_share < 1


def test_idle_gaps_and_busy_fill_the_window(red):
    gaps = sum(s for _, s in red.gaps.values())
    assert gaps + red.busy_s == pytest.approx(red.window_s, rel=1e-6)
    assert set(red.gaps) <= {"bench.window", "bench.step", "bench.sync",
                             "no bench span"}


def test_kernels_are_found_once_per_call(red):
    steps, _ = red.module_time(__import__("re").compile("step_with_eval"))
    assert steps > 0
    attn, _ = red.op_time(harness.metric_reader(
        "roofline.decode_attention").PATTERN)
    tl, _ = red.op_time(harness.metric_reader(
        "roofline.traj_logprob").PATTERN)
    # 15 rollout steps x 3 layers, and one forward and one backward pass,
    # per training step; the window may cut the first and last step
    assert abs(attn - 45 * steps) <= 45
    assert abs(tl - 2 * steps) <= 2


def test_roofline_shares_are_shares(red):
    view = type("View", (), {"reduction": red, "peaks": PEAKS,
                             "flops": staticmethod(harness.flops_module)})
    for name in ("roofline.decode_attention", "roofline.traj_logprob"):
        v = harness.metric_reader(name).read(view)
        assert 0 < v <= 100, (name, v)


def test_breakdown(red):
    b = red.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    for name, seconds in b["device_ops"] + b["idle_gaps"]:
        assert isinstance(name, str) and seconds > 0
    assert [s for _, s in b["device_ops"]] == sorted(
        (s for _, s in b["device_ops"]), reverse=True)
