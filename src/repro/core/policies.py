"""Policy factories for GFlowNet environments.

A policy is ``(init, apply)`` where ``apply(params, obs)`` returns a dict:
  logits    (B, A)    forward action logits
  logits_b  (B, Ab)   backward action logits (omitted -> uniform P_B)
  log_flow  (B,)      state-flow head (DB / SubTB / FLDB)

``params['log_z']`` is the TB normalizing-constant estimate; trainers give it
its own learning rate (paper Tables 3-7).
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from ..models import mla_moe
from ..nn.core import (dense_apply, dense_init, embedding_apply,
                       embedding_init, mlp_apply, mlp_init, normal_init)
from ..nn.transformer import (cache_fill, cache_init, decode_encoder_init,
                              decoder_stacked_weights, encoder_apply,
                              encoder_apply_bank, encoder_apply_cached,
                              encoder_init, encoder_query_cached,
                              positional_embedding_init)
from .types import sample_masked_per_env


class Policy(NamedTuple):
    """``(init, apply)`` plus optional incremental-decode entry points.

    Policies built with ``arch="decode"`` additionally provide the KV-cache
    protocol consumed by :func:`repro.core.rollout.forward_rollout`:

      cache_init(params, batch_size)                   -> cache
      apply_cached(params, cache, token, pos, length,
                   step=None)                          -> (out, cache)
      cache_fill(params, cache, tokens)                -> cache  (bulk load)
      query_cached(params, cache, length)              -> out    (no append)
      sample_cached(params, cache, token, pos, length,
                    env_keys, fwd_mask, step=None,
                    eps=0.0, logit_temp=None)  -> (actions, log_pf,
                                                   out, cache)
      apply_traj(params, obs)                          -> out over (T+1)*B

    ``apply_traj`` takes a batch's stored ``(T+1, B, S)`` token observations
    and returns the heads dict ``apply`` gives on the flattened states,
    projecting each trajectory's tokens to K/V once instead of once per
    state.  It holds for envs that write each token slot at most once per
    trajectory, the same property the cached rollout relies on.

    ``sample_cached`` is the FUSED per-step entry: append + query + masked
    categorical sampling issued as one op from the rollout scan body / serve
    lane step.  On CPU it composes the exact same jnp ops as the unfused
    ``apply_cached`` + ``sample_masked_per_env`` chain (bitwise-identical
    trajectories); on the TPU with statically-zero ``eps`` it lowers the
    whole step through the fused Pallas kernel (``kernels.ops.decode_step``).
    The choice is by platform and input only.

    Continuous-action policies (``nn.flows``, for envs with
    ``continuous_actions = True``) leave the categorical surface unused and
    instead provide density entry points — samplers draw real-valued actions
    and objectives teacher-force transition *densities* through them:

      sample(params, obs, mask, env_keys, eps=0.0) -> (action, log_pf)
      log_prob(params, obs, action)                -> (B,) fwd log-density
      sample_b(params, obs, mask, env_keys)        -> (bwd_action, log_pb)
      log_prob_b(params, obs_next, bwd_action)     -> (B,) bwd log-density
      log_state_flow(params, obs)                  -> (B,) flow head (DB)
    """
    init: Callable
    apply: Callable
    cache_init: Optional[Callable] = None
    apply_cached: Optional[Callable] = None
    cache_fill: Optional[Callable] = None
    query_cached: Optional[Callable] = None
    sample_cached: Optional[Callable] = None
    apply_traj: Optional[Callable] = None
    sample: Optional[Callable] = None
    log_prob: Optional[Callable] = None
    sample_b: Optional[Callable] = None
    log_prob_b: Optional[Callable] = None
    log_state_flow: Optional[Callable] = None


def make_mlp_policy(obs_dim: int, action_dim: int,
                    backward_action_dim: Optional[int] = None,
                    hidden: Sequence[int] = (256, 256),
                    learn_backward: bool = False,
                    flow_head: bool = True,
                    init_log_z: float = 0.0) -> Policy:
    """MLP policy (paper hypergrid / TFBind8 / QM9 setup: 2x256)."""

    def init(key):
        heads = action_dim + (backward_action_dim if learn_backward else 0) \
            + (1 if flow_head else 0)
        p = {"torso": mlp_init(key, obs_dim, list(hidden), heads),
             "log_z": jnp.zeros((), jnp.float32) + init_log_z}
        return p

    def apply(params, obs):
        out = mlp_apply(params["torso"], obs.astype(jnp.float32))
        res = {"logits": out[..., :action_dim]}
        off = action_dim
        if learn_backward:
            res["logits_b"] = out[..., off:off + backward_action_dim]
            off += backward_action_dim
        if flow_head:
            res["log_flow"] = out[..., off]
        return res

    return Policy(init, apply)


def make_transformer_policy(vocab_size: int, max_len: int, action_dim: int,
                            backward_action_dim: Optional[int] = None,
                            num_layers: int = 3, dim: int = 64,
                            num_heads: int = 8,
                            learn_backward: bool = False,
                            flow_head: bool = True,
                            init_log_z: float = 0.0,
                            arch: str = "pooled") -> Policy:
    """Transformer policy over integer token observations (paper bitseq/AMP:
    3 layers, 8 heads, dim 64).

    ``arch="pooled"`` (default, the seed architecture): bidirectional encoder
    over the padded sequence, mean-pooled readout.  ``arch="decode"``: the
    incremental-decode latent-query architecture (see
    ``nn.transformer.decode_encoder_init``) — per-layer K/V from frozen
    token+position embeddings, a learned latent query reads the state out.
    It is a pure function of the observation's (token, position) set, so
    stored observations stay valid for teacher forcing and DP evals, and it
    exposes the KV-cache entry points that let
    :func:`repro.core.rollout.forward_rollout` skip re-encoding the full
    sequence at every step.  The pad/empty token is assumed to be
    ``vocab_size - 1`` (true for every sequence env in this repo).
    """
    if arch not in ("pooled", "decode"):
        raise ValueError(f"unknown transformer arch {arch!r}")
    heads = action_dim + (backward_action_dim if learn_backward else 0) \
        + (1 if flow_head else 0)
    pad_id = vocab_size - 1

    def heads_out(out):
        res = {"logits": out[..., :action_dim]}
        off = action_dim
        if learn_backward:
            res["logits_b"] = out[..., off:off + backward_action_dim]
            off += backward_action_dim
        if flow_head:
            res["log_flow"] = out[..., off]
        return res

    if arch == "pooled":
        def init(key):
            ks = jax.random.split(key, 4)
            return {
                "embed": embedding_init(ks[0], vocab_size, dim),
                "pos": positional_embedding_init(ks[1], max_len, dim),
                "encoder": encoder_init(ks[2], num_layers=num_layers,
                                        dim=dim, num_heads=num_heads),
                "readout": dense_init(ks[3], dim, heads),
                "log_z": jnp.zeros((), jnp.float32) + init_log_z,
            }

        def apply(params, tokens):
            tokens = tokens.astype(jnp.int32)
            x = embedding_apply(params["embed"], tokens)
            x = x + params["pos"]["pos"][None, :tokens.shape[1]]
            h = encoder_apply(params["encoder"], x, num_heads=num_heads)
            pooled = jnp.mean(h, axis=1)
            return heads_out(dense_apply(params["readout"], pooled))

        return Policy(init, apply)

    # -- arch == "decode" ---------------------------------------------------

    def init(key):
        ks = jax.random.split(key, 5)
        return {
            "embed": embedding_init(ks[0], vocab_size, dim),
            "pos": positional_embedding_init(ks[1], max_len, dim),
            "bos": normal_init(ks[2], (dim,), std=0.02),
            "decoder": decode_encoder_init(ks[3], num_layers=num_layers,
                                           dim=dim, num_heads=num_heads),
            "readout": dense_init(ks[4], dim, heads),
            "log_z": jnp.zeros((), jnp.float32) + init_log_z,
        }

    def _embed(params, tokens, pos):
        return (embedding_apply(params["embed"], tokens)
                + embedding_apply({"table": params["pos"]["pos"]},
                                  jnp.clip(pos, 0, max_len - 1)))

    def _bank_heads(params, tokens, present):
        """Heads of queries over the bank of ``tokens`` (B, S), one per
        leading row of ``present`` (..., B, S), which masks the slots."""
        B, S = tokens.shape
        xs = _embed(params, tokens, jnp.arange(S)[None, :])
        bos = jnp.broadcast_to(params["bos"][None, None, :], (B, 1, dim))
        xs = jnp.concatenate([bos, xs], axis=1)
        mask = jnp.concatenate(
            [jnp.ones(present.shape[:-1] + (1,), bool), present], axis=-1)
        h = encoder_apply_bank(params["decoder"], xs, mask,
                               num_heads=num_heads)
        return heads_out(dense_apply(params["readout"], h))

    def apply(params, tokens):
        tokens = tokens.astype(jnp.int32)
        return _bank_heads(params, tokens, tokens != pad_id)

    def apply_traj(params, obs):
        obs = obs.astype(jnp.int32)
        # a slot is written at most once per trajectory, so its one non-pad
        # token is its minimum over the states (pad_id is the largest id);
        # slots no state holds stay pad, masked out of every query
        return _bank_heads(params, jnp.min(obs, axis=0), obs != pad_id)

    def cache_init_fn(params, batch_size):
        x0 = jnp.broadcast_to(params["bos"][None, :], (batch_size, dim))
        return cache_init(params["decoder"], x0, max_len + 1,
                          num_heads=num_heads)

    def apply_cached(params, cache, token, pos, length, step=None):
        x_new = _embed(params, token.astype(jnp.int32), pos)
        # token added at scan step t-1 lives in slot t — a batch-uniform
        # scalar for lockstep rollouts, or a (B,) per-row vector for the
        # serving engine's lanes (see nn.transformer.cache_append).
        # step=None falls back to the max per-env length, correct when all
        # envs fill in lockstep.
        slot = jnp.max(length) if step is None else step
        slot = jnp.clip(slot, 1, max_len)
        y, cache = encoder_apply_cached(params["decoder"], x_new, cache,
                                        length, num_heads=num_heads,
                                        slot=slot)
        return heads_out(dense_apply(params["readout"], y)), cache

    def cache_fill_fn(params, cache, tokens):
        tokens = tokens.astype(jnp.int32)
        S = tokens.shape[1]
        xs = _embed(params, tokens, jnp.arange(S)[None, :])
        return cache_fill(params["decoder"], cache, xs, num_heads=num_heads)

    def query_cached(params, cache, length):
        y = encoder_query_cached(params["decoder"], cache, length,
                                 num_heads=num_heads)
        return heads_out(dense_apply(params["readout"], y))

    def sample_cached(params, cache, token, pos, length, env_keys, fwd_mask,
                      step=None, eps=0.0, logit_temp=None):
        """Fused decode step: append + query + masked sampling as one op.

        ``env_keys``: (B, 2) per-env sampling keys (the rollout's
        ``derive_env_keys`` grid row / the engine's per-lane fold);
        ``fwd_mask``: (B, A) legal forward actions (callers pass their
        already-safed mask); ``logit_temp``: optional (B,) logit scale.
        Returns ``(actions, log_pf, out, cache)`` with ``out`` the full
        heads dict (same as ``apply_cached``'s).
        """
        eps_zero = isinstance(eps, (int, float)) and eps == 0.0
        if eps_zero and jax.default_backend() == "tpu":
            from ..kernels.ops import decode_step
            x_new = _embed(params, token.astype(jnp.int32), pos)
            slot = jnp.max(length) if step is None else step
            slot = jnp.clip(slot, 1, max_len)
            # Gumbel-max over the masked log-softmax IS the categorical
            # draw: jax.random.categorical(key_c, logp) computes
            # argmax(logp + gumbel(key_c)), and key_c is the second of
            # sample_masked's split(key, 3) — so the kernel consumes the
            # same noise the jnp path would.
            key_c = jax.vmap(lambda k: jax.random.split(k, 3)[1])(env_keys)
            gumbel = jax.vmap(
                lambda k: jax.random.gumbel(k, (action_dim,)))(key_c)
            w = decoder_stacked_weights(params["decoder"])
            w_out = params["readout"]["w"][:, :action_dim]
            b_out = params["readout"]["b"][:action_dim]
            actions, log_pf, y, cache = decode_step(
                w, x_new, cache, length, slot, gumbel, fwd_mask,
                w_out, b_out, logit_temp, num_heads=num_heads)
            out = heads_out(dense_apply(params["readout"], y))
            return actions, log_pf, out, cache
        out, cache = apply_cached(params, cache, token, pos, length,
                                  step=step)
        logits = out["logits"] if logit_temp is None \
            else out["logits"] * logit_temp[:, None]
        actions, log_pf = sample_masked_per_env(None, logits, fwd_mask,
                                                eps=eps, env_keys=env_keys)
        return actions, log_pf, out, cache

    return Policy(init, apply, cache_init=cache_init_fn,
                  apply_cached=apply_cached, cache_fill=cache_fill_fn,
                  query_cached=query_cached, sample_cached=sample_cached,
                  apply_traj=apply_traj)


def make_phylo_policy(env, num_layers: int = 6, dim: int = 32,
                      num_heads: int = 8, embed_dim: int = 128,
                      init_log_z: float = 0.0) -> Policy:
    """Slot-permutation-equivariant transformer policy for the phylogenetic
    environment (paper Table 6 architecture): transformer over node slots
    with NO positional embedding; merge-pair logits are symmetric bilinear
    scores of slot embeddings; backward logits are per-slot scalars.
    """
    K = env.num_slots
    F = env.obs_feat_dim
    pairs = env.pairs  # (P, 2)

    def init(key):
        ks = jax.random.split(key, 5)
        return {
            "inp": dense_init(ks[0], F, dim),
            "encoder": encoder_init(ks[1], num_layers=num_layers, dim=dim,
                                    num_heads=num_heads, ff_dim=embed_dim),
            "pair_proj": dense_init(ks[2], dim, dim),
            "bwd_head": dense_init(ks[3], dim, 1),
            "flow_head": dense_init(ks[4], dim, 1),
            "log_z": jnp.zeros((), jnp.float32) + init_log_z,
        }

    def apply(params, obs):
        # obs: (B, K, F)
        x = dense_apply(params["inp"], obs.astype(jnp.float32))
        h = encoder_apply(params["encoder"], x, num_heads=num_heads)
        e = dense_apply(params["pair_proj"], h)        # (B, K, dim)
        scores = jnp.einsum('bid,bjd->bij', e, e) / jnp.sqrt(
            jnp.float32(e.shape[-1]))
        logits = scores[:, pairs[:, 0], pairs[:, 1]]   # (B, P)
        logits_b = dense_apply(params["bwd_head"], h)[..., 0]  # (B, K)
        log_flow = jnp.mean(dense_apply(params["flow_head"], h)[..., 0],
                            axis=-1)
        return {"logits": logits, "logits_b": logits_b,
                "log_flow": log_flow}

    return Policy(init, apply)


def make_lm_policy(model_cfg, prompt, max_len: int, pad_id: int) -> Policy:
    """A language model (``models.mla_moe``) as the policy of
    :class:`repro.envs.lm_tokens.LMTokenEnvironment`: the next-token
    distribution after ``prompt`` and the continuation so far.

    Observations are continuations (N, ``max_len``) padded with ``pad_id``.
    Every row continues the one prompt, so each pass runs the prompt but
    its last token once, at batch 1 (``mla_moe.shared_prefix``), and the
    rows attend over its latents: ``apply`` runs ``[prompt[-1]] +
    continuation`` per state, ``apply_traj`` per trajectory (the pass is
    causal, so position t is state t's own), both at positions ``P - 1``
    on; ``cache_init`` broadcasts the latents into the rollout's cache.
    Decode step t feeds the state's last token (the prompt's last at t = 0)
    at position ``P - 1 + t`` through the latent cache.  Gradients reach
    the prompt's pass summed over the rows.  ``log_z`` is one scalar:
    there is one prompt, so it is exact.
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    P = prompt.shape[0]

    def init(key):
        p = mla_moe.init_params(key, model_cfg)
        p["log_z"] = jnp.zeros((), jnp.float32)
        return p

    def _hidden(params, obs):
        """Rows ``[prompt[-1]] + continuation`` (N, max_len + 1) after the
        prompt's shared prefix: hidden states at positions P - 1 on."""
        obs = obs.astype(jnp.int32)
        cont = jnp.where(obs == pad_id, 0, obs)
        rows = jnp.concatenate(
            [jnp.broadcast_to(prompt[-1], (obs.shape[0], 1)), cont], axis=1)
        pre = mla_moe.shared_prefix(params, prompt[:-1], model_cfg)
        return mla_moe.hidden(params, rows, model_cfg, prefix=pre)

    def apply(params, obs):
        h = _hidden(params, obs)
        last = jnp.sum(obs != pad_id, axis=-1)
        h = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
        return {"logits": mla_moe.logits(params, h)}

    def apply_traj(params, obs):
        # each position is written once per trajectory, so a trajectory's
        # tokens are its states' elementwise minimum (pad_id is the largest)
        Tp1, B, _ = obs.shape
        h = jnp.swapaxes(_hidden(params, jnp.min(obs, axis=0)), 0, 1)
        return {"logits": mla_moe.logits(params, h).reshape(Tp1 * B, -1)}

    def cache_fill_fn(params, cache, tokens):
        return mla_moe.prefill(params, cache, tokens.astype(jnp.int32),
                               model_cfg)

    def cache_init_fn(params, batch_size):
        cache = mla_moe.cache_init(model_cfg, batch_size, P + max_len)
        return mla_moe.load(cache, mla_moe.shared_prefix(
            params, prompt[:-1], model_cfg))

    def apply_cached(params, cache, token, pos, length, step=None):
        t = jnp.max(length) if step is None else step
        tok = jnp.where(length == 0, prompt[-1], token.astype(jnp.int32))
        logits, cache = mla_moe.decode(params, cache, tok, P - 1 + t,
                                       model_cfg)
        return {"logits": logits}, cache

    def sample_cached(params, cache, token, pos, length, env_keys, fwd_mask,
                      step=None, eps=0.0, logit_temp=None):
        out, cache = apply_cached(params, cache, token, pos, length,
                                  step=step)
        logits = out["logits"] if logit_temp is None \
            else out["logits"] * logit_temp[:, None]
        actions, log_pf = sample_masked_per_env(None, logits, fwd_mask,
                                                eps=eps, env_keys=env_keys)
        return actions, log_pf, out, cache

    return Policy(init, apply, cache_init=cache_init_fn,
                  apply_cached=apply_cached, cache_fill=cache_fill_fn,
                  sample_cached=sample_cached, apply_traj=apply_traj)
