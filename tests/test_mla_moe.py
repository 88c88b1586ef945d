"""The ``mla_moe`` family (Moonlight-16B-A3B) as a GFlowNet policy, at a
smoke size on the CPU, against the plain reference
(``models/ref_mla_moe.py``) on seeded random weights.

Tolerances: both sides compute in float32 on the CPU (the reference at
``highest`` matmul precision, which the CPU always gives), and differ only
in the order of their sums (blocked attention over the latent cache,
grouped expert matmuls, one causal pass against per-sequence passes).  A
few float32 roundings of values of order 1-10 stay under 1e-5 relative;
bfloat16 anywhere would show up at ~1e-2.  Gradients go through one more
pass, so they get 1e-4 of the largest entry.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algo import TrainLoop
from repro.configs import moonlight_16b_a3b
from repro.core import objectives
from repro.core.policies import make_lm_policy
from repro.core.rollout import forward_rollout
from repro.core.trainer import GFNConfig, make_loss_fn
from repro.envs.lm_tokens import LMTokenEnvironment
from repro.models import mla, mla_moe, moe
from repro.models import ref_mla_moe as ref

CFG = moonlight_16b_a3b.smoke_config()
P, T, B = 6, 4, 3


def seeded_params(cfg, seed=0):
    """Random weights with non-trivial norm scales and router bias."""
    leaves, tdef = jax.tree_util.tree_flatten(
        mla_moe.init_params(jax.random.PRNGKey(seed), cfg))
    k = jax.random.PRNGKey(seed + 1)
    params = jax.tree_util.tree_unflatten(tdef, [
        x + 0.3 * jax.random.normal(jax.random.fold_in(k, i), x.shape)
        if x.ndim == 1 else x for i, x in enumerate(leaves)])
    params["log_z"] = jnp.float32(0.7)
    return params


@pytest.fixture(scope="module")
def env():
    return LMTokenEnvironment(vocab=CFG.vocab_size, length=T, prompt_len=P,
                              rank=4, seed=3)


@pytest.fixture(scope="module")
def policy(env):
    return make_lm_policy(CFG, env.prompt, T, env.pad)


def ref_logits(params, prompt, conts):
    fwd = jax.jit(ref.forward, static_argnums=2)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([fwd(params, jnp.concatenate(
            [jnp.asarray(prompt), c]), CFG)[P - 1:P + T - 1] for c in conts])


def test_prefill_then_latent_decode_matches_reference(env, policy):
    params = seeded_params(CFG)
    conts = jax.random.randint(jax.random.PRNGKey(5), (B, T), 0, CFG.vocab_size)
    before = dict(mla.counters)
    cache = jax.jit(policy.cache_init, static_argnums=1)(params, B)
    step = jax.jit(lambda p, c, tok, t: policy.apply_cached(
        p, c, tok, None, jnp.full((B,), t), step=t))
    got = []
    for t in range(T):
        out, cache = step(params, cache, conts[:, max(t - 1, 0)], t)
        got.append(out["logits"])
    want = ref_logits(params, env.prompt, conts)
    np.testing.assert_allclose(jnp.stack(got, 1), want, rtol=1e-5,
                               atol=1e-5 * float(jnp.max(jnp.abs(want))))
    # the prompt's prefill expands; the decode step, traced once, reads
    # the latent cache in every layer
    assert mla.counters["expanded"] > before["expanded"]
    assert mla.counters["latent_decode"] - before["latent_decode"] == \
        CFG.num_hidden_layers


def test_apply_traj_matches_per_state_apply(env, policy):
    params = seeded_params(CFG, 1)
    conts = jax.random.randint(jax.random.PRNGKey(6), (B, T), 0, CFG.vocab_size)
    obs = jnp.stack([jnp.where(jnp.arange(T) < t, conts, env.pad)
                     for t in range(T + 1)])                 # (T+1, B, T)
    traj = jax.jit(policy.apply_traj)(params, obs)["logits"]
    each = jax.jit(policy.apply)(params, obs.reshape((T + 1) * B, T))[
        "logits"]
    np.testing.assert_allclose(traj, each, rtol=1e-5, atol=1e-5)


def test_tb_loss_and_gradients_match_reference(env, policy):
    """The program's TB loss over an on-policy batch sampled through the
    latent cache (shared-bank teacher-forced pass) against the reference's
    on the same continuations."""
    params = seeded_params(CFG, 2)
    env_params = env.init(jax.random.PRNGKey(0))
    batch = jax.jit(lambda p: forward_rollout(
        jax.random.PRNGKey(7), env, env_params, policy, p, B))(params)
    before = objectives.counters["shared_bank"]
    loss_fn = make_loss_fn(env, policy, GFNConfig(objective="tb"))
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
    assert objectives.counters["shared_bank"] == before + 1
    conts = batch.obs[-1]
    (want, log_pf), want_g = jax.jit(ref.loss_and_grads, static_argnums=4)(
        params, jnp.asarray(env.prompt), conts, batch.log_reward, CFG)
    np.testing.assert_allclose(batch.log_pf_beh, log_pf, rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(want_g)):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-4 * scale,
                                   err_msg=jax.tree_util.keystr(path))
    # the score-correction bias steers the choice and takes no gradient
    assert not np.any(grads["layers"]["layer_1"]["moe"]["router"]["bias"])


def per_row_apply_traj(env, params, obs):
    """``apply_traj`` as one causal pass over prompt + continuation per
    trajectory (``mla_moe.hidden`` on the broadcast prompt), at
    ``highest`` precision: the pass the shared prefix replaces."""
    Tp1, n, _ = obs.shape
    cont = jnp.min(obs, axis=0)
    tokens = jnp.concatenate([jnp.broadcast_to(jnp.asarray(env.prompt),
                                               (n, P)),
                              jnp.where(cont == env.pad, 0, cont)], axis=1)
    with jax.default_matmul_precision("highest"):
        h = mla_moe.hidden(params, tokens, CFG)[:, P - 1:P + T]
        return {"logits": mla_moe.logits(params, jnp.swapaxes(h, 0, 1))
                .reshape(Tp1 * n, -1)}


def test_shared_prefix_pass_matches_per_row_pass(env, policy):
    """The teacher-forced pass after the prompt's batch-1 prefix gives the
    per-row pass's logits, and the TB loss through it the per-row loss and
    every parameter's gradient (the prompt's share summed over the rows)."""
    params = seeded_params(CFG, 3)
    env_params = env.init(jax.random.PRNGKey(1))
    batch = jax.jit(lambda p: forward_rollout(
        jax.random.PRNGKey(8), env, env_params, policy, p, B))(params)
    got = jax.jit(policy.apply_traj)(params, batch.obs)["logits"]
    want = jax.jit(lambda p, o: per_row_apply_traj(env, p, o))(
        params, batch.obs)["logits"]
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * float(jnp.max(jnp.abs(want))))
    per_row = policy._replace(
        apply_traj=lambda p, o: per_row_apply_traj(env, p, o))
    cfg = GFNConfig(objective="tb")
    loss, grads = jax.jit(jax.value_and_grad(make_loss_fn(env, policy, cfg)))(
        params, batch)
    want, want_g = jax.jit(jax.value_and_grad(
        make_loss_fn(env, per_row, cfg)))(params, batch)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(want_g)):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-4 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_cache_init_is_the_per_row_prefill(env, policy):
    """The rollout's cache, filled from one batch-1 pass of the prompt,
    holds in every row what the per-row prefill writes there (decoding
    from it is checked against the reference above)."""
    params = seeded_params(CFG, 4)
    got = jax.jit(policy.cache_init, static_argnums=1)(params, B)
    prompt = jnp.broadcast_to(jnp.asarray(env.prompt)[:-1], (B, P - 1))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: mla_moe.prefill(
            p, mla_moe.cache_init(CFG, B, P + T), prompt, CFG))(params)
    for k in ("c", "k_pe"):
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)
        assert not np.any(got[k][:, :, P - 1:])
        np.testing.assert_array_equal(
            got[k], np.broadcast_to(got[k][:, :1], got[k].shape))


@pytest.mark.parametrize("entry", ["apply_traj", "apply", "cache_init",
                                   "tb_loss_grad"])
def test_prompt_runs_once_per_traced_pass(env, policy, monkeypatch, entry):
    """Each traced pass runs the prompt once at batch 1 (``shared_prefix``
    counts it); no expanded attention call sees the prompt per row."""
    params = seeded_params(CFG, 5)
    conts = jax.random.randint(jax.random.PRNGKey(9), (B, T), 0,
                               CFG.vocab_size)
    obs = jnp.stack([jnp.where(jnp.arange(T) < t, conts, env.pad)
                     for t in range(T + 1)])                 # (T+1, B, T)
    calls = []
    real = mla.mla_expanded

    def recorded(p, x, positions, cfg, prefix=None):
        calls.append((x.shape[:2], prefix is not None))
        return real(p, x, positions, cfg, prefix)

    monkeypatch.setattr(mla, "mla_expanded", recorded)
    if entry == "tb_loss_grad":
        batch = jax.jit(lambda p: forward_rollout(
            jax.random.PRNGKey(7), env, env.init(jax.random.PRNGKey(0)),
            policy, p, B))(params)
        calls.clear()
    run = {"apply_traj": lambda p: policy.apply_traj(p, obs),
           "apply": lambda p: policy.apply(p, obs.reshape(-1, T)),
           "cache_init": lambda p: policy.cache_init(p, B),
           "tb_loss_grad": lambda p: jax.grad(make_loss_fn(
               env, policy, GFNConfig(objective="tb")))(p, batch)}[entry]
    before = mla.counters["shared_prefix"]
    jax.jit(run).lower(params)
    assert mla.counters["shared_prefix"] == before + 1
    rows = {"apply_traj": B, "apply": (T + 1) * B, "tb_loss_grad": B}
    want = {((1, P - 1), False)}
    if entry in rows:
        want.add(((rows[entry], T + 1), True))
    assert set(calls) == want


def test_reward_is_the_seeded_bigram_score(env):
    params = env.init(jax.random.PRNGKey(0))
    toks = jnp.array([[1, 2, 3, 4], [0, 0, 5, 63]], jnp.int32)
    term = env.terminal_state_from_tokens(toks)
    u, v = np.asarray(params["u"]), np.asarray(params["v"])
    for row, got in zip(np.asarray(toks), env.log_reward(term, params)):
        seq = [int(env.prompt[-1])] + list(row)
        want = sum(u[a] @ v[b] for a, b in zip(seq[:-1], seq[1:])) / 2.0
        assert float(got) == pytest.approx(want, rel=1e-5)


def test_router_matches_handwritten_noaux_tc():
    rng = np.random.RandomState(0)
    x = rng.standard_normal((7, 5)).astype(np.float32)
    w = rng.standard_normal((5, 12)).astype(np.float32)
    bias = rng.standard_normal(12).astype(np.float32)
    k, scaling = 3, 2.446
    idx, wts = moe.sigmoid_topk_route(jnp.asarray(x), jnp.asarray(w),
                                      jnp.asarray(bias), k, scaling)
    for t in range(7):
        s = 1.0 / (1.0 + np.exp(-(x[t] @ w)))
        chosen = sorted(range(12), key=lambda e: -(s[e] + bias[e]))[:k]
        assert sorted(np.asarray(idx[t])) == sorted(chosen)
        want = {e: s[e] / sum(s[c] for c in chosen) * scaling for e in chosen}
        for e, g in zip(np.asarray(idx[t]), np.asarray(wts[t])):
            assert g == pytest.approx(want[int(e)], rel=1e-5)


def test_expert_shares_add_up_to_the_whole_layer():
    """Eight chips each hold 4 of the 32 routed experts of one layer; their
    parts, with the shared experts counted once, add up to the reference's
    layer with every expert held."""
    whole = CFG.__class__(**{**CFG.__dict__, "experts_held":
                             CFG.n_routed_experts})
    p = seeded_params(whole, 4)["layers"]["layer_1"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(8), (9, CFG.hidden_size))
    idx, w = moe.sigmoid_topk_route(x, p["router"]["w"], p["router"]["bias"],
                                    CFG.num_experts_per_tok,
                                    CFG.routed_scaling_factor)
    G = CFG.experts_held
    before = dict(moe.counters)
    share = jax.jit(moe.held_experts, static_argnums=4)
    parts = []
    for chip in range(CFG.n_routed_experts // G):
        held = jax.tree_util.tree_map(lambda a: a[:, chip * G:(chip + 1) * G],
                                      p["experts"])
        parts.append(share(held, x, idx, w, chip * G))
    got = sum(parts) + mla_moe.silu_mlp(p["shared"], x)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.moe, static_argnums=2)(p, x, whole)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert moe.counters["held_dropless"] - before["held_dropless"] == 8
    assert moe.counters["experts_held"] - before["experts_held"] == 32


def test_rows_past_the_groups_stay_out_of_both_directions(monkeypatch):
    """The TPU's grouped-matmul kernel leaves the rows past the held
    experts' groups unwritten, in its result and in its operand's gradient.
    Filled with NaN here, they reach neither the layer's output nor any
    gradient: both match the kernel that writes zeros there."""
    real = jax.lax.ragged_dot

    def dead(rows, sizes):
        return (jnp.arange(rows) >= jnp.sum(sizes))[:, None]

    @jax.custom_vjp
    def unwritten(lhs, rhs, sizes):
        return jnp.where(dead(lhs.shape[0], sizes), jnp.nan,
                         real(lhs, rhs, sizes))

    def fwd(lhs, rhs, sizes):
        return unwritten(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, ct):
        lhs, rhs, sizes = res
        d_lhs, d_rhs = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)[1](ct)
        d_lhs = jnp.where(dead(lhs.shape[0], sizes), jnp.nan, d_lhs)
        return d_lhs, d_rhs, np.zeros(sizes.shape, jax.dtypes.float0)

    unwritten.defvjp(fwd, bwd)
    p = seeded_params(CFG, 5)["layers"]["layer_1"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(9), (11, CFG.hidden_size))

    def loss(experts, x, w_router):
        idx, w = moe.sigmoid_topk_route(x, w_router, p["router"]["bias"],
                                        CFG.num_experts_per_tok,
                                        CFG.routed_scaling_factor)
        return jnp.sum(jnp.sin(moe.held_experts(experts, x, idx, w, 0)))

    args = (p["experts"], x, p["router"]["w"])
    want = jax.value_and_grad(loss, argnums=(0, 1, 2))(*args)
    monkeypatch.setattr(jax.lax, "ragged_dot", unwritten)
    got = jax.value_and_grad(loss, argnums=(0, 1, 2))(*args)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.all(np.isfinite(g))
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("tokens", [9, 255, 256])
def test_grouped_matmul_rows_are_a_multiple_of_8(monkeypatch, tokens):
    """XLA's TPU compiler takes its grouped-matmul kernel only for a row
    count that is a multiple of 8 (the shared prompt's 255 tokens x 4
    choices here are not; other counts get a dense expansion whose results
    are wrong on the chip): the held experts pad their pairs to one, and
    the padding changes no output."""
    p = seeded_params(CFG, 7)["layers"]["layer_1"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(10), (tokens, CFG.hidden_size))
    idx, w = moe.sigmoid_topk_route(x, p["router"]["w"], p["router"]["bias"],
                                    CFG.num_experts_per_tok,
                                    CFG.routed_scaling_factor)
    real, rows = jax.lax.ragged_dot, []

    def recorded(lhs, rhs, sizes, *a, **kw):
        rows.append(lhs.shape[0])
        return real(lhs, rhs, sizes, *a, **kw)

    monkeypatch.setattr(jax.lax, "ragged_dot", recorded)
    got = moe.held_experts(p["experts"], x, idx, w, 0)
    assert rows and all(r % 8 == 0 and r - 8 < tokens * idx.shape[1]
                        for r in rows)
    want = jnp.zeros_like(x)
    for e in range(CFG.experts_held):
        ex = jax.tree_util.tree_map(lambda a: a[:, e], p["experts"])
        gate = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        want = want + gate[:, None] * mla_moe.silu_mlp(ex, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_lm_tb_trains_through_trainloop(env, policy):
    """The env, reward and cached on-policy rollout through ``TrainLoop``'s
    python-mode step, for two steps."""
    loop = TrainLoop(env, env.init(jax.random.PRNGKey(0)), policy,
                     GFNConfig(objective="tb", num_envs=B, lr=1e-3))
    state = loop.init(jax.random.PRNGKey(1))
    p0 = state.train.params
    step = jax.jit(loop._step_with_eval)
    for _ in range(2):
        state, (metrics, batch) = step(state)
        assert np.isfinite(float(metrics["loss"]))
    assert batch.actions.shape == (T, B)
    assert float(jnp.abs(state.train.params["log_z"] - p0["log_z"])) > 0


def test_ep8_share_is_the_published_model_cut():
    full, share = moonlight_16b_a3b.config(), moonlight_16b_a3b.ep8_share()
    changed = {k for k in full.__dict__ if full.__dict__[k] !=
               share.__dict__[k]}
    assert changed == {"num_hidden_layers", "experts_held", "vocab_size"}
    n = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        mla_moe.param_shapes(share), is_leaf=lambda x: isinstance(x, tuple)))
    assert n == pytest.approx(568.5e6, rel=1e-3)
