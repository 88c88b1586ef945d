"""GFlowNet fine-tuning of a language-model policy: TB over
on-policy continuations of a fixed prompt, sampled through the model's
latent cache (Hu et al. 2023, arXiv:2310.04363).

``lm_tb``: Moonlight-16B-A3B as one chip's share of an EP8 deployment
(``configs.moonlight_16b_a3b.ep8_share``: layer 0 and 4 MoE layers, 8 of
64 experts, 20480 of the 163840 ids), a 256-token prompt and 64 appended
tokens, scored by a seeded bigram reward.
"""
from __future__ import annotations

from ..configs.registry import POLICY_ARCHS
from ..core.policies import make_lm_policy
from ..core.trainer import GFNConfig
from ..envs.lm_tokens import LMTokenEnvironment
from .base import Recipe, register


def _lm_env(vocab: int = 20480, length: int = 64, prompt_len: int = 256,
            rank: int = 16, beta: float = 1.0, seed: int = 0):
    return LMTokenEnvironment(vocab=vocab, length=length,
                              prompt_len=prompt_len, rank=rank, beta=beta,
                              seed=seed)


def _lm_policy(env):
    cfg = POLICY_ARCHS["moonlight-16b-a3b"].ep8_share()
    if cfg.vocab_size != env.vocab:
        raise ValueError(f"the policy's vocabulary slice is {cfg.vocab_size} "
                         f"ids, the env's {env.vocab}")
    return make_lm_policy(cfg, env.prompt, env.length, env.pad)


register(Recipe(
    name="lm_tb",
    description="TB fine-tuning of a Moonlight-16B-A3B policy (one chip's "
                "EP8 share) on 64-token continuations of a seeded prompt",
    make_env=_lm_env,
    make_policy=_lm_policy,
    make_config=lambda env, opts: GFNConfig(
        objective="tb", num_envs=opts.num_envs, lr=1e-5, log_z_lr=0.1),
    iterations=10000,
    eval_every=0,
    num_envs=32,
))
