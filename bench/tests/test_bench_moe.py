"""Operations and bytes of the ``moonlight16b_ep8`` cell from shapes, by
hand count at small shapes, and the held experts' per-layer metrics on a
trace of their forward and backward recorded on a TPU v5e (three
gradient steps of ``models.moe.held_experts`` on 512 tokens, 8 of 64
experts, 2048 -> 1408 -> 2048, under the benchmark's window span) and
kept beside this file."""
import re
from pathlib import Path

import jax
import pytest

from bench import harness, trace

TRACE = Path(__file__).parent / "data" / "moonlight16b_ep8.moe_experts.xplane.pb"
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# D=2, one head (nope 1, rope 2, v 1), latent rank 1, one dense layer of
# width 1, vocabulary 3; a 2-token prompt and 1 appended token
TINY = {"hidden_size": 2, "num_attention_heads": 1, "qk_nope_head_dim": 1,
        "qk_rope_head_dim": 2, "v_head_dim": 1, "kv_lora_rank": 1,
        "num_hidden_layers": 1, "first_k_dense_replace": 1,
        "intermediate_size": 1, "vocab_size": 3,
        "recipe_env": {"prompt_len": 2, "length": 1}}


def test_step_flops_by_hand():
    f = harness.flops_module("mla_moe_tb")
    # projections q 12, latent 12, output 4; up-projection 4; scores and
    # values 8 per position of context; dense FFN 12; head 12
    assert f.expanded_flops(TINY, 1) == 40
    # query into the latent 2, scores 6 and values 2 per cached position,
    # the weighted latent out 2
    assert f.latent_flops(TINY, 2) == 48
    assert f.prefill_flops(TINY) == 40 + 12
    assert f.decode_flops(TINY) == 48 + 12 + 12
    assert f.objective_forward_flops(TINY) == (40 + 48 + 56) + 3 * 12 \
        + 2 * 12
    assert f.flops_per_traj(TINY) == 52 + 72 + 3 * 204


def test_moe_layer_flops_count_the_held_share():
    f = harness.flops_module("mla_moe_tb")
    cfg = {"hidden_size": 4, "first_k_dense_replace": 1,
           "moe_intermediate_size": 3, "n_routed_experts": 8,
           "experts_held": 2, "num_experts_per_tok": 4,
           "n_shared_experts": 2}
    # router 64; one held application per token (4 x 2/8) of 72; shared
    # MLP of width 6, 144
    assert f.ffn_flops(cfg, 1) == 64 + 72 + 144


def test_grouped_matmul_cost_from_operand_shapes():
    f = harness.flops_module("moe_experts")
    cfg = {"experts_held": 8, "n_routed_experts": 64}
    text = ("%ragged-dot-none.4 = f32[64,16]{1,0:T(8,128)} custom-call("
            "s32[1]{0:T(128)} %a, s32[9]{0:T(128)S(1)} %b, "
            "f32[64,32]{1,0:T(8,128)} %x, f32[8,32,16]{2,1,0:T(8,128)} %w), "
            "custom_call_target=\"tpu_custom_call\"")
    ops, byts = f.cost(*trace.custom_call_types(text), cfg)
    # 64 buffered pairs, an eighth of them on held experts
    assert ops == 2 * 8 * 32 * 16
    assert byts == 8 * 32 * 16 * 4 + (64 * 16 + 64 * 32) * 4 / 8
    assert harness.metric_reader("roofline.moe_experts").PATTERN.search(text)
    meta = text.replace("ragged-dot-none.4", "ragged-dot-metadata")
    assert not harness.metric_reader(
        "roofline.moe_experts").PATTERN.search(meta)


@pytest.fixture(scope="module")
def red():
    return trace.reduce_profile(
        jax.profiler.ProfileData.from_file(str(TRACE)))


def view(red):
    return type("View", (), {
        "reduction": red, "peaks": PEAKS, "host": {"steps": 3},
        "config": {"experts_held": 8, "n_routed_experts": 64},
        "flops": staticmethod(harness.flops_module)})


def test_grouped_matmuls_are_found_in_every_step(red):
    pattern = harness.metric_reader("roofline.moe_experts").PATTERN
    n, t = red.op_time(pattern)
    # three steps, each with its forward products and their gradients
    # (the window may cut a call of the first or last step)
    assert n >= 3 * 6 and t > 0
    meta, _ = red.op_time(re.compile(r"^%ragged-dot-metadata"))
    assert meta > 0


def test_expert_metrics_read_the_trace(red):
    share = harness.metric_reader("roofline.moe_experts").read(view(red))
    assert 0 < share <= 100, share
    ms = harness.metric_reader("moe.experts_ms").read(view(red))
    _, t = red.op_time(harness.metric_reader("roofline.moe_experts").PATTERN)
    assert ms == pytest.approx(1e3 * t / 3)


def test_expert_metrics_are_silent_without_the_kernel():
    other = trace.reduce_profile(jax.profiler.ProfileData.from_file(str(
        Path(__file__).parent / "data" / "bitseq120.train_tb_b16.xplane.pb")))
    assert harness.metric_reader("roofline.moe_experts").read(
        view(other)) is None
    assert harness.metric_reader("moe.experts_ms").read(view(other)) is None
