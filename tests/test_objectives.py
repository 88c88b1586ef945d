"""Objective-function tests: exact identities on enumerable MDPs and
degeneracy relations between losses."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core.objectives import (db_loss, evaluate_trajectory, fldb_loss,
                                   mdb_loss, subtb_loss, tb_loss)
from repro.core.policies import make_mlp_policy
from repro.core.rollout import forward_rollout

KEY = jax.random.PRNGKey(0)


def make_hypergrid(dim=2, side=4):
    env = repro.HypergridEnvironment(dim=dim, side=side)
    return env, env.init(KEY)


def rollout_and_eval(env, params, policy, pp, B=32, stop=None):
    batch = forward_rollout(KEY, env, params, policy.apply, pp, B)
    ev = evaluate_trajectory(policy.apply, pp, batch, stop_action=stop)
    return batch, ev


class TestIdentities:
    """With a *perfect* flow/policy pair, every loss must be ~0.  We build
    the perfect solution on a tiny hypergrid by dynamic programming over the
    DAG with uniform P_B, then check the losses evaluate to zero."""

    def _perfect_tb_quantities(self, env, params, B=16):
        """Construct exact log F / P_F by backward induction (uniform P_B)."""
        side, dim = env.side, env.dim
        import itertools
        states = list(itertools.product(range(side), repeat=dim))
        idx = {s: i for i, s in enumerate(states)}
        pos = jnp.asarray(states, jnp.int32)
        log_r = np.asarray(env.reward_module.log_reward(
            pos, params.reward_params))
        # backward induction in reverse topological order (sum of coords)
        # F(s->sf) = R(s); F(s->s') = F(s') * P_B(s|s')
        flow = np.zeros(len(states))
        order = sorted(states, key=lambda s: -sum(s))
        for s in order:
            f = np.exp(log_r[idx[s]])            # stop edge flow
            for i in range(dim):
                child = list(s)
                child[i] += 1
                c = tuple(child)
                if c in idx:
                    n_parents = sum(1 for j in range(dim) if c[j] > 0)
                    f += flow[idx[c]] / n_parents
            flow[idx[s]] = f
        log_flow = np.log(flow)

        def policy_logits(s):
            """exact P_F(.|s) from edge flows."""
            logits = np.full(dim + 1, -np.inf)
            logits[dim] = log_r[idx[s]]
            for i in range(dim):
                child = list(s)
                child[i] += 1
                c = tuple(child)
                if c in idx:
                    n_parents = sum(1 for j in range(dim) if c[j] > 0)
                    logits[i] = np.log(flow[idx[c]] / n_parents)
            return logits

        return idx, log_flow, policy_logits, log_r

    def test_losses_zero_at_optimum(self):
        env, params = make_hypergrid(dim=2, side=3)
        idx, log_flow, policy_logits, log_r = \
            self._perfect_tb_quantities(env, params)

        logit_table = np.stack([policy_logits(s) for s in
                                sorted(idx, key=lambda s: idx[s])])
        flow_table = log_flow
        side = env.side

        def apply(params_, obs):
            # obs is one-hot (B, dim*side) -> decode position
            pos = jnp.argmax(obs.reshape(-1, env.dim, side), axis=-1)
            flat = pos[:, 0] * side + pos[:, 1]
            logits = jnp.asarray(logit_table)[flat]
            # uniform backward logits (masked later)
            return {"logits": logits,
                    "logits_b": jnp.zeros((obs.shape[0],
                                           env.backward_action_dim)),
                    "log_flow": jnp.asarray(flow_table)[flat]}

        batch = forward_rollout(KEY, env, params, apply, None, 64)
        ev = evaluate_trajectory(apply, None, batch, stop_action=env.dim)
        log_z_true = jax.nn.logsumexp(jnp.asarray(log_r))
        assert float(tb_loss(ev, batch, log_z_true)) < 1e-6
        assert float(db_loss(ev, batch)) < 1e-6
        assert float(subtb_loss(ev, batch, 0.9)) < 1e-6

    def test_tb_equals_subtb_full_trajectory_term(self):
        """SubTB with only the (0, n) pair == TB residual; check via
        lambda -> large limit on fixed-length env (bitseq)."""
        env = repro.BitSeqEnvironment(n=8, k=4)
        params = env.init(KEY)
        from repro.core.policies import make_transformer_policy
        pol = make_transformer_policy(env.vocab_size, env.L, env.action_dim,
                                      env.backward_action_dim, num_layers=1,
                                      dim=16)
        pp = pol.init(KEY)
        batch = forward_rollout(KEY, env, params, pol.apply, pp, 8)
        ev = evaluate_trajectory(pol.apply, pp, batch)
        # fixed-length env, uniform P_B has a single parent choice ordering:
        # compare TB loss against manual sum
        s_pf = jnp.sum(ev.log_pf, 0)
        s_pb = jnp.sum(ev.log_pb, 0)
        manual = jnp.mean((pp["log_z"] + s_pf - batch.log_reward - s_pb) ** 2)
        np.testing.assert_allclose(float(tb_loss(ev, batch, pp["log_z"])),
                                   float(manual), rtol=1e-6)

    def test_uniform_pb_value(self):
        """Uniform P_B on bitseq: after t forward steps the next backward
        log-prob is -log(t+1) (t+1 filled positions)."""
        env = repro.BitSeqEnvironment(n=8, k=4)
        params = env.init(KEY)
        from repro.core.policies import make_transformer_policy
        pol = make_transformer_policy(env.vocab_size, env.L, env.action_dim,
                                      env.backward_action_dim, num_layers=1,
                                      dim=16)
        pp = pol.init(KEY)
        batch = forward_rollout(KEY, env, params, pol.apply, pp, 4)

        def apply_uniform(params_, obs):
            B = obs.shape[0]
            return {"logits": jnp.zeros((B, env.action_dim)),
                    "log_flow": jnp.zeros((B,))}

        ev = evaluate_trajectory(apply_uniform, None, batch)
        # at transition t the child state has t+1 filled positions
        for t in range(env.L):
            expect = -np.log(t + 1)
            np.testing.assert_allclose(np.asarray(ev.log_pb[t]),
                                       expect, rtol=1e-5)


class TestSubTBImpls:
    """``subtb_loss`` backends (dense pairwise tensor, O(T) prefix-sum
    recurrence, Pallas kernel) must agree to fp tolerance on arbitrary
    rollouts, including variable-length ones with invalid tails."""

    @pytest.mark.parametrize("lam", [0.5, 0.9, 0.99])
    def test_backends_agree_hypergrid(self, lam):
        env, params = make_hypergrid(2, 5)
        pol = make_mlp_policy(env.obs_dim, env.action_dim,
                              env.backward_action_dim, hidden=(16,))
        pp = pol.init(KEY)
        batch = forward_rollout(KEY, env, params, pol.apply, pp, 16)
        ev = evaluate_trajectory(pol.apply, pp, batch, stop_action=env.dim)
        dense = float(subtb_loss(ev, batch, lam, impl="dense"))
        prefix = float(subtb_loss(ev, batch, lam, impl="prefix"))
        pallas = float(subtb_loss(ev, batch, lam, impl="pallas"))
        auto = float(subtb_loss(ev, batch, lam))
        np.testing.assert_allclose(prefix, dense, rtol=1e-5)
        np.testing.assert_allclose(pallas, dense, rtol=1e-4)
        np.testing.assert_allclose(auto, dense, rtol=1e-4)

    def test_backends_agree_variable_length(self):
        """Variable-length trajectories (DAG stop action) exercise the
        on-trajectory masking of all three backends."""
        env = repro.DAGEnvironment(d=3)
        params = env.init(KEY)
        pol = make_mlp_policy(9, env.action_dim, env.backward_action_dim,
                              hidden=(16,), learn_backward=True)
        pp = pol.init(KEY)
        batch = forward_rollout(KEY, env, params, pol.apply, pp, 16)
        ev = evaluate_trajectory(pol.apply, pp, batch)
        dense = float(subtb_loss(ev, batch, 0.9, impl="dense"))
        prefix = float(subtb_loss(ev, batch, 0.9, impl="prefix"))
        pallas = float(subtb_loss(ev, batch, 0.9, impl="pallas"))
        np.testing.assert_allclose(prefix, dense, rtol=1e-5)
        np.testing.assert_allclose(pallas, dense, rtol=1e-4)

    def test_prefix_gradients_match_dense(self):
        env, params = make_hypergrid(2, 4)
        pol = make_mlp_policy(env.obs_dim, env.action_dim,
                              env.backward_action_dim, hidden=(16,))
        pp = pol.init(KEY)
        batch = forward_rollout(KEY, env, params, pol.apply, pp, 8)

        def loss(impl):
            return lambda p: subtb_loss(
                evaluate_trajectory(pol.apply, p, batch, env.dim), batch,
                0.9, impl=impl)

        g_dense = jax.grad(loss("dense"))(pp)
        # "pallas" must be jax.grad-safe too: its forward is the kernel,
        # its custom backward differentiates the prefix recurrence
        for impl in ("prefix", "pallas"):
            g_other = jax.grad(loss(impl))(pp)
            for a, b in zip(jax.tree_util.tree_leaves(g_dense),
                            jax.tree_util.tree_leaves(g_other)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5, rtol=1e-4,
                                           err_msg=impl)


class TestMDB:
    def test_mdb_zero_for_exact_posterior_policy(self):
        """On a 2-node DAG env the flow equations are solvable by hand:
        uniform P_B and reward-proportional stop probabilities satisfy MDB
        when P_F matches flow ratios; we verify a fitted policy reaches
        ~0 loss (already covered by integration) and that the loss is
        invariant to adding constants to log R (normalization freedom)."""
        env = repro.DAGEnvironment(d=2)
        params = env.init(KEY)
        pol = make_mlp_policy(4, env.action_dim, env.backward_action_dim,
                              hidden=(32,), learn_backward=True)
        pp = pol.init(KEY)
        batch = forward_rollout(KEY, env, params, pol.apply, pp, 16)
        ev = evaluate_trajectory(pol.apply, pp, batch,
                                 stop_action=env.stop_action)
        l1 = float(mdb_loss(ev, batch))
        import dataclasses
        batch2 = dataclasses.replace(
            batch, log_r_state=batch.log_r_state + 7.0)
        l2 = float(mdb_loss(ev, batch2))
        np.testing.assert_allclose(l1, l2, rtol=1e-4)


class TestFLDB:
    def test_fldb_equals_db_without_shaping(self):
        """With E == 0 everywhere and terminal flow pinned, FLDB residual ==
        DB residual when log R == 0 (paper: FLDB reduces to DB)."""
        env = repro.IsingEnvironment(n=2, sigma=0.0)   # J = 0 -> log R = 0
        params = env.init(KEY)
        pol = make_mlp_policy(4, env.action_dim, env.backward_action_dim,
                              hidden=(16,), learn_backward=True)
        pp = pol.init(KEY)
        batch = forward_rollout(KEY, env, params, pol.apply, pp, 8)
        ev = evaluate_trajectory(pol.apply, pp, batch)
        np.testing.assert_allclose(float(fldb_loss(ev, batch)),
                                   float(db_loss(ev, batch)), rtol=1e-5)


class TestGradients:
    def test_all_objectives_have_finite_grads(self):
        env, params = make_hypergrid(2, 4)
        pol = make_mlp_policy(env.obs_dim, env.action_dim,
                              env.backward_action_dim, hidden=(16,),
                              learn_backward=True)
        pp = pol.init(KEY)
        batch = forward_rollout(KEY, env, params, pol.apply, pp, 8)

        for name, fn in [
            ("tb", lambda p: tb_loss(evaluate_trajectory(pol.apply, p, batch,
                                                         env.dim), batch,
                                     p["log_z"])),
            ("db", lambda p: db_loss(evaluate_trajectory(pol.apply, p, batch,
                                                         env.dim), batch)),
            ("subtb", lambda p: subtb_loss(
                evaluate_trajectory(pol.apply, p, batch, env.dim), batch)),
        ]:
            g = jax.grad(fn)(pp)
            leaves = jax.tree_util.tree_leaves(g)
            assert all(np.all(np.isfinite(np.asarray(x))) for x in leaves), \
                f"{name} grads not finite"
            total = sum(float(jnp.sum(jnp.abs(x))) for x in leaves)
            assert total > 0, f"{name} grads all zero"


# ---------------------------------------------------------------------------
# Shared K/V bank: one bank per trajectory vs `apply` on every stored state
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _seq_case(name, kind, B=8):
    """(env, decode policy, params, batch, stop action) at test size."""
    from repro.core.policies import make_transformer_policy
    from repro.core.rollout import backward_rollout
    from repro.envs.sequences import AMPEnvironment, TFBind8Environment
    if name == "bitseq":
        env = repro.BitSeqEnvironment(n=16, k=4)
        max_len, stop = env.L, None
    elif name == "tfbind8":
        env = TFBind8Environment()
        max_len, stop = env.length, None
    else:
        env = AMPEnvironment(max_len=6)
        max_len, stop = env.max_len, env.stop_action
    pol = make_transformer_policy(env.vocab_size, max_len, env.action_dim,
                                  env.backward_action_dim, num_layers=2,
                                  dim=16, num_heads=4, learn_backward=True,
                                  arch="decode")
    ep, pp = env.init(KEY), pol.init(KEY)
    if kind == "early":
        # favour the stop action so the batch's envs end at different steps
        pp = dict(pp, readout=dict(pp["readout"], b=pp["readout"]["b"]
                                   .at[env.stop_action].add(3.0)))
    batch, final = forward_rollout(KEY, env, ep, pol, pp, B,
                                   return_final_state=True)
    if kind == "backward":
        batch = backward_rollout(jax.random.PRNGKey(1), env, ep, pol, pp,
                                 final, collect=True).batch
    return env, pol, pp, batch, stop


@pytest.mark.parametrize("name,objective,kind", [
    (name, obj, "forward") for name in ("bitseq", "tfbind8", "amp")
    for obj in ("tb", "db", "subtb")] + [
    ("amp", "tb", "early"), ("bitseq", "db", "backward"),
    ("amp", "subtb", "backward")])
def test_shared_bank_matches_per_state_apply(name, objective, kind):
    """The shared bank gives the per-state ``apply``'s TrajEval, loss and
    gradients to fp32 tolerance: forward rollouts (bitseq writes anywhere,
    TFBind8 appends, AMP has a stop action), a batch whose envs end at
    different steps, and backward-built (replayed) batches."""
    from repro.core.objectives import OBJECTIVES
    from repro.core.trainer import GFNConfig
    env, pol, pp, batch, stop = _seq_case(name, kind)
    if kind == "early":
        lengths = np.asarray(batch.valid.sum(0))
        assert len(set(lengths.tolist())) > 1, lengths
    cfg = GFNConfig(objective=objective, stop_action=stop)

    def loss_and_grads(policy_apply, shared):
        def loss(p):
            ev = evaluate_trajectory(policy_apply, p, batch, stop,
                                     shared_bank=shared)
            return OBJECTIVES[objective](ev, batch, p, cfg), ev
        return jax.jit(jax.value_and_grad(loss, has_aux=True))(pp)

    (loss_s, ev_s), g_s = loss_and_grads(pol, True)
    (loss_p, ev_p), g_p = loss_and_grads(pol.apply, False)
    for field, a, b in zip(ev_s._fields, ev_s, ev_p):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5, err_msg=field)
    np.testing.assert_allclose(float(loss_s), float(loss_p), rtol=1e-5)
    flat_p = dict(jax.tree_util.tree_leaves_with_path(g_p))
    for path, a in jax.tree_util.tree_leaves_with_path(g_s):
        b = np.asarray(flat_p[path])
        np.testing.assert_allclose(
            np.asarray(a), b, rtol=1e-4, atol=1e-5 * np.max(np.abs(b)),
            err_msg=jax.tree_util.keystr(path))


def _bypass_case(name):
    from repro.core.policies import make_transformer_policy
    from repro.envs import ObservationTransform
    from repro.envs.sequences import QM9Environment
    if name == "hypergrid_mlp":
        env, _ = make_hypergrid(2, 4)
        return env, make_mlp_policy(env.obs_dim, env.action_dim,
                                    env.backward_action_dim, hidden=(16,),
                                    learn_backward=True)

    class Rewritten(ObservationTransform):
        def transform_obs(self, obs):
            return obs + 0

    env = (Rewritten(repro.BitSeqEnvironment(n=16, k=4))
           if name == "bitseq_obs_transform" else QM9Environment())
    width = env.L if name == "bitseq_obs_transform" else env.length
    return env, make_transformer_policy(
        env.vocab_size, width, env.action_dim, env.backward_action_dim,
        num_layers=2, dim=16, num_heads=4, learn_backward=True,
        arch="decode")


@pytest.mark.parametrize("name", ["hypergrid_mlp", "bitseq_obs_transform",
                                  "qm9_decode"])
def test_per_state_bank_bypass(name):
    """An MLP policy, an observation-rewriting transform and QM9's prepend
    edits keep the per-state pass, with the seed's loss."""
    from repro.core import objectives
    from repro.core.trainer import GFNConfig, make_loss_fn
    env, pol = _bypass_case(name)
    ep, pp = env.init(KEY), pol.init(KEY)
    batch = forward_rollout(KEY, env, ep, pol, pp, 8)
    cfg = GFNConfig(objective="db")
    assert not objectives.shared_bank_engaged(env, pol)
    before = dict(objectives.counters)
    loss = make_loss_fn(env, pol, cfg)(pp, batch)
    assert objectives.counters["per_state_bank"] == \
        before["per_state_bank"] + 1
    assert objectives.counters["shared_bank"] == before["shared_bank"]
    seed = db_loss(evaluate_trajectory(pol.apply, pp, batch), batch)
    np.testing.assert_array_equal(np.asarray(loss), np.asarray(seed))


@pytest.mark.parametrize("recipe,env_kw,path", [
    ("bitseq_tb", {"n": 16, "k": 4}, "shared_bank"),
    ("hypergrid_subtb", {"dim": 2, "side": 4}, "per_state_bank")])
def test_train_step_bank_path(recipe, env_kw, path):
    """A recipe's compiled training step records the bank its loss took."""
    from repro import recipes
    from repro.algo import TrainLoop
    from repro.core import objectives
    from repro.recipes.base import RunOptions
    r = recipes.get(recipe)
    env = r.make_env(**env_kw)
    ep = env.init(KEY)
    cfg = r.make_config(env, RunOptions(num_envs=4))
    loop = TrainLoop(env, ep, r.make_policy(env), cfg)
    state = loop.init(KEY)
    before = dict(objectives.counters)
    jax.eval_shape(loop.step_fn, state)
    other = ({"shared_bank", "per_state_bank"} - {path}).pop()
    assert objectives.counters[path] == before[path] + 1
    assert objectives.counters[other] == before[other]
