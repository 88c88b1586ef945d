"""Operations and bytes from shapes, against hand counts at small shapes,
and the peaks table."""
import re

import pytest

from bench import harness, trace

BITSEQ = {"env": {"n": 4, "k": 2},
          "policy": {"dim": 2, "ff_dim": 4, "num_layers": 1, "num_heads": 1}}
HYPER = {"env": {"dim": 2, "side": 3}, "policy": {"hidden": [4]}}


def test_kernel_ops_from_operand_shapes():
    f = harness.flops_module
    # valid counts, queries (B, D), keys and values (B, S, D), head map
    assert f("decode_attention").ops([(2,), (2, 8), (2, 5, 8), (2, 5, 8),
                                      (8, 8)]) == 2 * (2 * 5 * 8 * 2)
    # logits (B, T, A), actions, mask, valid
    assert f("traj_logprob").ops([(2, 4, 64), (2, 4, 1), (2, 4, 64),
                                  (2, 4, 1)]) == 4 * 2 * 4 * 64
    # 6 states: 15 pairs of 6 operations per trajectory
    assert f("subtb_loss").ops([(2,), (2, 6, 1), (2, 1, 6)]) == 2 * 15 * 6


def test_decode_step_ops():
    f = harness.flops_module("decode_step")
    # D=2, F=4, A=8: K and V 16, query 8, scores and values over 3 slots
    # 24, projection 8, feed-forward 32, readout 32, draw 32
    assert f.row_flops(1, 2, 4, 8, 3) == 16 + 8 + 24 + 8 + 32 + 32 + 32
    nl, B, C, D, F, A = 1, 4, 3, 2, 4, 8
    operands = [(B,), (B,), (B, 1), (B, D), (nl, B, C, D), (nl, B, C, D),
                (B, A), (B, A), (D, D)] + [(nl, D)] * 12 + [(nl, D, F)] + \
        [(nl, F)] * 9
    assert f.ops(operands) == B * f.row_flops(nl, D, F, A, C)
    assert f.model_flops_per_sample(BITSEQ) == \
        f.row_flops(1, 2, 4, 8, 1) + f.row_flops(1, 2, 4, 8, 2)


def test_whole_steps():
    tb = harness.flops_module("decode_transformer_tb")
    # L=2, A=8: rollout 224, objective forward 3 states x 156
    assert tb.rollout_flops(BITSEQ) == 224
    assert tb.objective_forward_flops(BITSEQ) == 468
    assert tb.flops_per_traj(BITSEQ) == 224 + 3 * 468
    mlp = harness.flops_module("mlp_subtb")
    assert mlp.forward_flops(HYPER) == 2 * (6 * 4 + 4 * 4)
    assert mlp.flops_per_traj(HYPER) == (5 + 3 * 6) * 80


def test_peaks_known_and_unknown():
    assert harness.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v9 imaginary")


def test_roofline_share_counts_hbm_bytes_only():
    hbm = ("%k = f32[8,64]{1,0:T(8,128)} custom-call(f32[8,64]{1,0:T(8,128)}"
           " %a, f32[8,64]{1,0:T(8,128)S(1)} %b), custom_call_target="
           "\"tpu_custom_call\"")
    red = trace.Reduction(window_s=1.0, busy_s=0.5, devices=1,
                          ops={hbm: [4, 2e-3], "%fusion.1 = f32[8]": [9, 1]},
                          modules={}, gaps={})
    peaks = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    res, ops = trace.custom_call_types(hbm)
    assert [s for _, s, _ in ops] == [(8, 64), (8, 64)]
    # the result and the first operand are in HBM, the second in VMEM
    assert trace.hbm_bytes(res) + trace.hbm_bytes(ops) == 2 * 8 * 64 * 4
    got = trace.roofline_share(red, re.compile("tpu_custom_call"),
                               lambda shapes: 1e6, peaks)
    least = max(1e6 / 1e12, 2 * 8 * 64 * 4 / 1e9)
    assert got == pytest.approx(100 * 4 * least / 2e-3)
    assert trace.roofline_share(red, re.compile("nothing"),
                                lambda shapes: 1, peaks) is None
