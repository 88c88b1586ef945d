"""Compile the main-path Pallas kernels for a described TPU v5e, off the chip.

Interpret mode accepts block shapes, scalar layouts and lane slices that
Mosaic refuses, so every kernel the training and serving paths lower on the
TPU is compiled here with ``interpret=False`` for one chip of a described
``v5e:2x2`` topology, at the shapes ``chip_smoke.py`` drives: the
``bitseq_tb`` recipe (n=120, k=8: 15 words, 3840 forward actions, a 3-layer
width-64 8-head decode transformer) at the paper's 16 envs and at 256, and
the ``hypergrid_subtb`` paper grid (20^4: 77-step trajectories);
``decode_attention`` also at ``amp_tb``'s 61-slot cache, where the VMEM cap
on a K/V block gives smaller row blocks.  Nothing
runs; a compile that passes says the compiler accepts the kernel, not that
its results are right (the interpret-mode oracle tests cover that).
The ``lm_tb`` cell's shapes are compiled too: ``traj_logprob`` over a
20480-id vocabulary slice (32 continuations of 64 tokens) and the held
experts' grouped matmul (``models.moe.held_experts``: 8 of Moonlight's 64
experts, 2048 -> 1408 -> 2048) on the per-row teacher-forced pass's 32 x
320 tokens and on the shared prompt's, the rows' and a decode step's token
counts, forward and backward.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test collection must be the
same on every pytest-xdist worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention import (decode_attention_pallas,
                                            decode_step_pallas)
from repro.kernels.subtb_loss import subtb_loss_pallas
from repro.kernels.traj_logprob import traj_logprob_pallas

# bitseq_tb recipe defaults (recipes/seqs.py) and the hypergrid 20^4 grid
LAYERS, DIM, HEADS, FF = 3, 64, 8, 256
WORDS, ACTIONS = 15, 15 * 256          # L = n / k positions, L * 2^k actions
CAPACITY = WORDS + 1                   # cache slots: BOS + one per word
AMP_CAPACITY = 60 + 1                  # amp_tb: max_len 60 + BOS, block_k 64
SUBTB_STATES = 4 * 19 + 2              # T + 1 for dim=4, side=20
BATCHES = (16, 256)                    # paper num_envs, and a chip-filling one
# lm_tb (recipes/lm.py): 32 continuations of 64 tokens after a 256-token
# prompt, over a 20480-id slice; Moonlight-16B-A3B expert widths
LM_ENVS, LM_STEPS, LM_VOCAB, LM_PROMPT = 32, 64, 20480, 256
D_MODEL, EXPERT_FF, EXPERTS, HELD, TOP_K = 2048, 1408, 64, 8, 6


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: an abstract argument on one described chip."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A described-topology compile is written to the persistent cache but
    cannot be read back without a chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_calls(fn, *args) -> int:
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def _assert_kernel(fn, *args):
    assert _kernel_calls(fn, *args) >= 1


def _decoder_weights(spec):
    L, D, F = LAYERS, DIM, FF
    shapes = {"ln1_scale": (L, D), "ln1_bias": (L, D), "q_w": (L, D, D),
              "q_b": (L, D), "kv_w": (L, D, 2 * D), "kv_b": (L, 2 * D),
              "proj_w": (L, D, D), "proj_b": (L, D), "ln2_scale": (L, D),
              "ln2_bias": (L, D), "ff1_w": (L, D, F), "ff1_b": (L, F),
              "ff2_w": (L, F, D), "ff2_b": (L, D), "ln_f_scale": (D,),
              "ln_f_bias": (D,), "q0": (D,)}
    return {k: spec(s) for k, s in shapes.items()}


@pytest.mark.parametrize("batch", BATCHES)
def test_decode_step_compiles(spec, batch):
    B, L, C, D, A = batch, LAYERS, CAPACITY, DIM, ACTIONS
    step = lambda w, x, kc, vc, n, s, g, m, wo, bo, t: decode_step_pallas(
        w, x, kc, vc, n, s, g, m, wo, bo, t, num_heads=HEADS,
        interpret=False)
    _assert_kernel(step, _decoder_weights(spec), spec((B, D)),
                   spec((L, B, C, D)), spec((L, B, C, D)),
                   spec((B,), jnp.int32), spec((B,), jnp.int32),
                   spec((B, A)), spec((B, A), jnp.bool_), spec((D, A)),
                   spec((A,)), spec((B,)))


@pytest.mark.parametrize("batch,capacity", [
    *(pytest.param(b, CAPACITY, id=str(b)) for b in BATCHES),
    pytest.param(256, AMP_CAPACITY, id="256-amp"),
])
def test_decode_attention_compiles(spec, batch, capacity):
    hd = DIM // HEADS
    _assert_kernel(
        lambda q, k, v, n: decode_attention_pallas(q, k, v, n,
                                                   interpret=False),
        spec((batch, HEADS, hd)), spec((batch, capacity, HEADS, hd)),
        spec((batch, capacity, HEADS, hd)), spec((batch,), jnp.int32))


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("actions", [ACTIONS, WORDS], ids=["fwd", "bwd"])
def test_traj_logprob_compiles(spec, batch, actions):
    B, T = batch, WORDS
    _assert_kernel(
        lambda lg, a, m, v: traj_logprob_pallas(lg, a, m, v,
                                                interpret=False),
        spec((B, T, actions)), spec((B, T), jnp.int32),
        spec((B, T, actions), jnp.bool_), spec((B, T), jnp.bool_))


@pytest.mark.parametrize("actions", [LM_VOCAB, 1], ids=["fwd", "bwd"])
def test_traj_logprob_compiles_at_lm_vocab(spec, actions):
    B, T = LM_ENVS, LM_STEPS
    _assert_kernel(
        lambda lg, a, m, v: traj_logprob_pallas(lg, a, m, v,
                                                interpret=False),
        spec((B, T, actions)), spec((B, T), jnp.int32),
        spec((B, T, actions), jnp.bool_), spec((B, T), jnp.bool_))


def _held_experts_grads(spec, tokens):
    """The held experts' forward and backward over ``tokens`` tokens,
    compiled for one described chip: its grouped-matmul calls and text."""
    from repro.models import moe

    def loss(p, x, idx, w):
        return jnp.sum(moe.held_experts(p, x, idx, w, 0))

    p = {"gate": {"w": spec((D_MODEL, HELD, EXPERT_FF))},
         "up": {"w": spec((D_MODEL, HELD, EXPERT_FF))},
         "down": {"w": spec((EXPERT_FF, HELD, D_MODEL))}}
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        p, spec((tokens, D_MODEL)), spec((tokens, TOP_K), jnp.int32),
        spec((tokens, TOP_K))).compile().as_text()
    calls = [l for l in text.splitlines()
             if "custom-call(" in l and "%ragged-dot-" in l
             and "metadata" not in l.split("=")[0]]
    return calls, text


def test_held_experts_grouped_matmul_compiles(spec):
    """Forward and backward of the held experts on the teacher-forced
    pass's tokens: the grouped matmuls forward and their gradient products
    backward lower to the TPU's ragged-dot kernels."""
    calls, _ = _held_experts_grads(spec, LM_ENVS * (LM_PROMPT + LM_STEPS))
    assert len(calls) >= 6, calls


@pytest.mark.parametrize("tokens", [LM_PROMPT - 1, LM_ENVS * (LM_STEPS + 1),
                                    LM_ENVS],
                         ids=["prompt", "rows", "decode"])
def test_held_experts_take_the_kernel_at_every_row_count(spec, tokens):
    """The shared prompt's pass (255 tokens x 6 choices, not a multiple of
    8), the rows after it and a decode step: every grouped matmul takes
    the ragged-dot kernel, and none the dense expansion (a convolution over
    every held expert) that XLA gives other row counts."""
    calls, text = _held_experts_grads(spec, tokens)
    assert len(calls) >= 6, calls
    assert " convolution(" not in text


@pytest.mark.parametrize("batch", BATCHES)
def test_subtb_loss_compiles(spec, batch):
    _assert_kernel(
        lambda phi, n: subtb_loss_pallas(phi, n, lam=0.9, interpret=False),
        spec((batch, SUBTB_STATES)), spec((batch,), jnp.int32))


def test_bitseq_tb_train_step_compiles(spec, monkeypatch):
    """The whole jitted ``bitseq_tb`` step at the paper's 16 envs: the
    platform gates see a TPU (steered here, since this process's backend is
    the CPU), so the rollout's cached queries lower through
    ``decode_attention`` (3 layers) and the TB objective through
    ``traj_logprob`` (forward and backward log-probs)."""
    from repro import recipes
    from repro.algo import TrainLoop
    from repro.recipes.base import RunOptions

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    recipe = recipes.get("bitseq_tb")
    env = recipe.make_env()
    policy = recipe.make_policy(env)
    cfg = recipe.make_config(env, RunOptions(num_envs=BATCHES[0]))
    loop = TrainLoop(env, env.init(jax.random.PRNGKey(0)), policy, cfg)
    state = jax.tree_util.tree_map(
        lambda s: spec(s.shape, s.dtype),
        jax.eval_shape(loop.init, jax.random.PRNGKey(1)))
    assert _kernel_calls(loop.step_fn, state) == LAYERS + 2
