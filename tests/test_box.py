"""Continuous-state GFlowNet suite (Box env + flow policy heads):

- density correctness: squashed-mixture and full policy log-densities
  integrate to ~1 by quadrature; Dirac transitions contribute 0
- geometry: forward/backward round-trips respect the delta-min / boundary
  constraints; backward collection reaches s0
- plan parity: seed-determinism and bitwise single vs data_parallel
  trajectories on the conftest-forced 8-virtual-device mesh
- quadrature evaluator: normalized target, metric wiring sanity
- vocabulary independence: the TB/DB estimators consume only TrajEval's
  (T, B) grids — they accept log-*densities* (which may exceed 0) untouched
  (referenced by the OBJECTIVE_PARTS comment in core/objectives.py)
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.rollout import RolloutBatch, backward_rollout, forward_rollout
from repro.core.trainer import GFNConfig
from repro.envs.box import BoxEnvironment, BoxState
from repro.nn.flows import (make_box_flow_policy, squashed_mixture_log_prob,
                            squashed_mixture_sample)
from repro.rewards.box import BoxRewardModule, mixture_log_density

KEY = jax.random.PRNGKey(0)
SHARDS = 8
TOL = 1e-5


def _env(**kw):
    return BoxEnvironment(BoxRewardModule(), **kw)


def _setup(num_envs=0, hidden=(32,)):
    env = _env()
    params = env.init(KEY)
    policy = make_box_flow_policy(env, hidden=hidden, num_components=3)
    pp = policy.init(jax.random.PRNGKey(1))
    return env, params, policy, pp


def _obs_for(env, params, pos, steps, terminal=False):
    pos = jnp.asarray(pos, jnp.float32).reshape(1, 2)
    state = BoxState(pos=pos,
                     terminal=jnp.full((1,), terminal),
                     steps=jnp.full((1,), steps, jnp.int32))
    return env.observe(state, params)


# ---------------------------------------------------------------------------
# Density correctness
# ---------------------------------------------------------------------------

class TestDensities:
    @pytest.mark.parametrize("lo,hi", [(0.1, 0.25), (0.1, 0.105),
                                       (0.0, 1.0)])
    def test_squashed_mixture_integrates_to_one(self, lo, hi):
        """exp(log_prob) of the squashed mixture integrates to ~1 on
        [lo, hi] by trapezoid quadrature — the change of variables is
        exact."""
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
        logits = jax.random.normal(k1, (4,))
        means = 2.0 * jax.random.normal(k2, (4,))
        log_scales = jax.random.normal(k3, (4,)) * 0.5
        n = 20001
        xs = jnp.linspace(lo, hi, n)
        dens = jnp.exp(squashed_mixture_log_prob(
            jnp.broadcast_to(logits, (n, 4)),
            jnp.broadcast_to(means, (n, 4)),
            jnp.broadcast_to(log_scales, (n, 4)),
            xs, jnp.full((n,), lo), jnp.full((n,), hi)))
        mass = jnp.trapezoid(dens, xs)
        assert abs(float(mass) - 1.0) < 2e-3

    def test_forward_policy_total_probability_is_one(self):
        """At a content state: p(exit) + integral of the increment density
        over the 2-D support = 1 (1-D quadrature per coordinate — the
        density factorizes given the observation)."""
        env, params, policy, pp = _setup()
        obs = _obs_for(env, params, (0.3, 0.4), steps=2)
        lo, hi = env.forward_support(obs[:, :2])
        lo, hi = np.asarray(lo)[0], np.asarray(hi)[0]
        n = 2001
        total_inc = 1.0
        for d in range(2):
            xs = np.linspace(lo[d], hi[d], n)
            # factorized: probe coordinate d along its interval with the
            # other coordinate pinned mid-support
            other = 0.5 * (lo[1 - d] + hi[1 - d])
            u = np.full((n, 2), other, np.float32)
            u[:, d] = xs
            act = jnp.concatenate([jnp.asarray(u),
                                   jnp.zeros((n, 1))], axis=1)
            lp = policy.log_prob(pp, jnp.broadcast_to(obs, (n, 4)), act)
            # divide out the pinned coordinate's density to leave the
            # 1-D marginal of coordinate d (plus the no-exit factor once)
            dens = np.exp(np.asarray(lp))
            marg = np.trapezoid(dens, xs)
            total_inc *= marg
        # each marg includes (1 - p_exit) * dens_other(pinned); normalize
        # via a direct joint evaluation at the pinned midpoint instead:
        mid = 0.5 * (lo + hi)
        act_mid = jnp.asarray([[mid[0], mid[1], 0.0]], jnp.float32)
        joint_mid = float(np.exp(np.asarray(
            policy.log_prob(pp, obs, act_mid))[0]))
        exit_act = jnp.asarray([[0.0, 0.0, 1.0]], jnp.float32)
        p_exit = float(np.exp(np.asarray(
            policy.log_prob(pp, obs, exit_act))[0]))
        # total_inc = prod_d integral[ p_noexit * f_d(x) * f_other(mid) ]
        #           = p_noexit^2 * f_x(mid) * f_y(mid) * 1 * 1 ... solve:
        # joint_mid = p_noexit * f_x(mid) * f_y(mid)
        inc_mass = total_inc / joint_mid
        assert abs(p_exit + inc_mass - 1.0) < 5e-3

    def test_backward_density_integrates_to_one(self):
        env, params, policy, pp = _setup()
        obs = _obs_for(env, params, (0.5, 0.55), steps=3)
        pos = obs[:, :2]
        lo, hi = env.backward_support(pos, jnp.full((1,), 3, jnp.int32))
        lo, hi = np.asarray(lo)[0], np.asarray(hi)[0]
        assert np.all(hi - lo > 1e-3)
        n = 1501
        xs = [np.linspace(lo[d], hi[d], n) for d in range(2)]
        gx, gy = np.meshgrid(xs[0], xs[1], indexing="ij")
        u = jnp.asarray(np.stack([gx.ravel(), gy.ravel()], 1), jnp.float32)
        act = jnp.concatenate([u, jnp.zeros((n * n, 1))], axis=1)
        lp = policy.log_prob_b(pp, jnp.broadcast_to(obs, (n * n, 4)), act)
        dens = np.asarray(lp, np.float64).reshape(n, n)
        mass = np.trapezoid(np.trapezoid(np.exp(dens), xs[1], axis=1),
                            xs[0])
        assert abs(mass - 1.0) < 5e-3

    def test_dirac_backward_transitions_are_log_zero(self):
        env, params, policy, pp = _setup()
        # un-exit at a terminal copy
        obs_t = _obs_for(env, params, (0.4, 0.6), steps=4, terminal=True)
        act = jnp.asarray([[0.0, 0.0, 1.0]], jnp.float32)
        assert float(policy.log_prob_b(pp, obs_t, act)[0]) == 0.0
        # one-increment state steps straight back to s0
        obs_1 = _obs_for(env, params, (0.15, 0.2), steps=1)
        act = jnp.asarray([[0.15, 0.2, 0.0]], jnp.float32)
        assert float(policy.log_prob_b(pp, obs_1, act)[0]) == 0.0

    def test_sample_log_pf_matches_log_prob(self):
        """The density returned by sample() is exactly log_prob of the
        realized action (same convention as the categorical sampler)."""
        env, params, policy, pp = _setup()
        B = 64
        _, state = env.reset(B, params)
        state = BoxState(pos=jnp.full((B, 2), 0.35),
                         terminal=jnp.zeros((B,), bool),
                         steps=jnp.full((B,), 2, jnp.int32))
        obs = env.observe(state, params)
        mask = env.forward_mask(state, params)
        keys = jax.random.split(jax.random.PRNGKey(5), B)
        for eps in (0.0, 0.3):
            act, lp = policy.sample(pp, obs, mask, keys, eps=eps)
            np.testing.assert_allclose(
                np.asarray(lp), np.asarray(policy.log_prob(pp, obs, act)),
                rtol=1e-6, atol=1e-6)

    def test_exit_illegal_at_s0_and_forced_at_boundary(self):
        env, params, policy, pp = _setup()
        B = 32
        keys = jax.random.split(jax.random.PRNGKey(3), B)
        # s0: steps=0 -> exit arm off, all draws must increment
        obs0, state0 = env.reset(B, params)
        act, _ = policy.sample(pp, obs0, env.forward_mask(state0, params),
                               keys)
        assert not np.any(np.asarray(act[:, 2]) > 0.5)
        # within delta_min of the boundary: exit forced
        near = BoxState(pos=jnp.full((B, 2), 0.95),
                        terminal=jnp.zeros((B,), bool),
                        steps=jnp.full((B,), 4, jnp.int32))
        obs_n = env.observe(near, params)
        act, lp = policy.sample(pp, obs_n, env.forward_mask(near, params),
                                keys)
        assert np.all(np.asarray(act[:, 2]) > 0.5)
        np.testing.assert_allclose(np.asarray(lp), 0.0, atol=1e-6)


# ---------------------------------------------------------------------------
# Geometry / round-trips
# ---------------------------------------------------------------------------

class TestGeometry:
    def test_forward_rollout_respects_constraints(self):
        env, params, policy, pp = _setup(hidden=(32, 32))
        B = 128
        batch = forward_rollout(jax.random.PRNGKey(11), env, params, policy,
                                pp, B, exploration_eps=0.2)
        acts = np.asarray(batch.actions)           # (T, B, 3)
        valid = np.asarray(batch.valid)
        obs = np.asarray(batch.obs)                # (T+1, B, 4)
        pos = obs[:, :, :2]
        assert np.all(pos >= -TOL) and np.all(pos <= 1.0 + TOL)
        inc = np.logical_and(valid, acts[:, :, 2] < 0.5)
        u = acts[:, :, :2]
        assert np.all(u[inc] >= env.delta_min - 1e-4)
        assert np.all(u[inc] <= env.delta_max + 1e-4)
        # increments never overshoot: u <= 1 - pos on valid increment rows
        room = (1.0 - pos[:-1])[inc]
        assert np.all(u[inc] <= room + 1e-4)
        # every env exits within max_steps
        assert np.all(obs[-1, :, 3] > 0.5)
        # positions freeze after exit
        done = obs[:, :, 3] > 0.5
        frozen = done[:-1]
        np.testing.assert_allclose(pos[1:][frozen], pos[:-1][frozen],
                                   atol=1e-7)

    def test_forward_backward_round_trip(self):
        """Stepping backward with the stored structural-reverse actions
        retraces the forward trajectory exactly back to s0."""
        env, params, policy, pp = _setup()
        B = 32
        batch, final = forward_rollout(jax.random.PRNGKey(2), env, params,
                                       policy, pp, B,
                                       return_final_state=True)
        out = backward_rollout(jax.random.PRNGKey(3), env, params, policy,
                               pp, final, collect=True)
        obs0 = np.asarray(out.batch.obs[0])
        np.testing.assert_allclose(obs0[:, :2], 0.0, atol=1e-6)
        assert not np.any(obs0[:, 3] > 0.5)
        # log_pb finite; log_pf of the reconstructed forward path finite
        assert np.all(np.isfinite(np.asarray(out.log_pb)))
        assert np.all(np.isfinite(np.asarray(out.log_pf)))

    def test_backward_support_is_reachability_consistent(self):
        """Along forward-sampled trajectories, the stored increment always
        lies inside backward_support at the successor state — the interval
        the backward density is normalized over."""
        env, params, policy, pp = _setup()
        batch = forward_rollout(jax.random.PRNGKey(4), env, params, policy,
                                pp, 96, exploration_eps=0.2)
        obs = np.asarray(batch.obs)
        acts = np.asarray(batch.actions)
        valid = np.asarray(batch.valid)
        inc = np.logical_and(valid, acts[:, :, 2] < 0.5)
        T = acts.shape[0]
        for t in range(T):
            rows = np.where(inc[t])[0]
            if rows.size == 0:
                continue
            nxt = obs[t + 1][rows]
            pos = jnp.asarray(nxt[:, :2])
            steps = jnp.asarray(
                np.round(nxt[:, 2] * env.max_steps), jnp.int32)
            lo, hi = env.backward_support(pos, steps)
            u = acts[t][rows][:, :2]
            assert np.all(u >= np.asarray(lo) - 1e-4), t
            assert np.all(u <= np.asarray(hi) + 1e-4), t

    def test_max_steps_bound(self):
        env = _env()
        # delta_min=0.1: at most 10 increments (worst case hugs the lower
        # bound), plus the exit action
        assert env.max_increments == 10
        assert env.max_steps == 11

    def test_invalid_deltas_rejected(self):
        with pytest.raises(ValueError, match="delta_min"):
            _env(delta_min=0.3, delta_max=0.2)


# ---------------------------------------------------------------------------
# Plan parity / determinism (mirrors tests/test_plan.py)
# ---------------------------------------------------------------------------

class TestPlanParity:
    pytestmark = pytest.mark.skipif(
        jax.device_count() < SHARDS,
        reason=f"needs {SHARDS} (virtual) devices; conftest forces them "
               "unless XLA_FLAGS was preset")

    def test_sharded_forward_rollout_bitwise_identical(self):
        from jax import shard_map

        from repro.distributed.sharding import rollout_batch_specs
        from repro.launch.mesh import make_mesh

        env, params, policy, pp = _setup()
        k = jax.random.PRNGKey(42)
        B, b = 16, 16 // SHARDS
        full = forward_rollout(k, env, params, policy, pp, B,
                               exploration_eps=0.1)
        mesh = make_mesh((SHARDS,), ("batch",))

        def local():
            off = jax.lax.axis_index("batch") * b
            return forward_rollout(k, env, params, policy, pp, b,
                                   exploration_eps=0.1, env_offset=off)

        shb = jax.jit(shard_map(local, mesh=mesh, in_specs=(),
                                out_specs=rollout_batch_specs("batch"),
                                check_vma=False))()
        np.testing.assert_array_equal(np.asarray(full.actions),
                                      np.asarray(shb.actions))
        np.testing.assert_array_equal(np.asarray(full.done),
                                      np.asarray(shb.done))
        np.testing.assert_allclose(np.asarray(full.log_reward),
                                   np.asarray(shb.log_reward), rtol=1e-6)

    def test_training_parity_single_vs_data_parallel(self):
        from repro.algo import TrainLoop
        from repro.recipes import get
        from repro.recipes.base import RunOptions

        recipe = get("box_tb")
        env = recipe.make_env()
        params = env.init(KEY)
        policy = recipe.make_policy(env)
        cfg = recipe.make_config(env, RunOptions(iterations=12, num_envs=16))
        single = TrainLoop(env, params, policy, cfg, plan="single")
        dp = TrainLoop(env, params, policy, cfg, plan="data_parallel")
        assert dp.plan.num_shards == SHARDS

        def losses(loop):
            _, (m, _) = loop.run(jax.random.PRNGKey(7), 12, mode="scan")
            return np.asarray(m["loss"]), np.asarray(m["mean_log_reward"])

        l1, r1 = losses(single)
        l8, r8 = losses(dp)
        assert np.all(np.isfinite(l8))
        np.testing.assert_allclose(l1, l8, rtol=2e-3, atol=1e-4)
        # identical sampled trajectories => tight reward agreement
        np.testing.assert_allclose(r1, r8, rtol=1e-5, atol=1e-6)

    def test_seed_determinism(self):
        env, params, policy, pp = _setup()
        a = forward_rollout(jax.random.PRNGKey(5), env, params, policy, pp,
                            32, exploration_eps=0.1)
        b = forward_rollout(jax.random.PRNGKey(5), env, params, policy, pp,
                            32, exploration_eps=0.1)
        c = forward_rollout(jax.random.PRNGKey(6), env, params, policy, pp,
                            32, exploration_eps=0.1)
        np.testing.assert_array_equal(np.asarray(a.actions),
                                      np.asarray(b.actions))
        assert not np.array_equal(np.asarray(a.actions),
                                  np.asarray(c.actions))


# ---------------------------------------------------------------------------
# Quadrature evaluator
# ---------------------------------------------------------------------------

class TestQuadratureEval:
    def test_target_matches_normalized_reward(self):
        from repro.evals import QuadratureDistributionEval
        env, params, policy, pp = _setup()
        G = 16
        ev = QuadratureDistributionEval(env, params, policy, grid_size=G,
                                        num_samples=128)
        tgt = np.asarray(ev.target)
        assert tgt.shape == (G * G,)
        np.testing.assert_allclose(tgt.sum(), 1.0, rtol=1e-5)
        centers = (np.arange(G) + 0.5) / G
        xx, yy = np.meshgrid(centers, centers, indexing="ij")
        pos = jnp.asarray(np.stack([xx.ravel(), yy.ravel()], 1), jnp.float32)
        log_r = np.log(np.asarray(params["r0"]) + np.exp(np.asarray(
            mixture_log_density(pos, params))))
        want = np.exp(log_r - log_r.max())
        want /= want.sum()
        np.testing.assert_allclose(tgt, want, rtol=1e-4, atol=1e-7)

    def test_known_mixture_sanity(self):
        """Binning exact draws from the target multinomial reproduces the
        target within sampling noise -> the TV wiring itself is sound."""
        from repro.evals import QuadratureDistributionEval
        env, params, policy, pp = _setup()
        G = 16
        ev = QuadratureDistributionEval(env, params, policy, grid_size=G,
                                        num_samples=128)
        tgt = np.asarray(ev.target, np.float64)
        rng = np.random.default_rng(0)
        counts = rng.multinomial(200_000, tgt / tgt.sum())
        emp = counts / counts.sum()
        assert 0.5 * np.abs(emp - tgt).sum() < 0.02

    def test_flat_index_layout(self):
        from repro.evals import QuadratureDistributionEval
        env, params, policy, pp = _setup()
        ev = QuadratureDistributionEval(env, params, policy, grid_size=4,
                                        num_samples=8)
        pos = jnp.asarray([[0.0, 0.0], [0.99, 0.99], [0.3, 0.8]])
        np.testing.assert_array_equal(np.asarray(ev.flat_index(pos)),
                                      [0, 15, 1 * 4 + 3])

    def test_eval_call_returns_finite_metrics(self):
        from repro.evals import QuadratureDistributionEval
        env, params, policy, pp = _setup()
        ev = QuadratureDistributionEval(env, params, policy, grid_size=8,
                                        num_samples=256)
        out = ev(jax.random.PRNGKey(0), pp)
        assert set(out) == {"quad_tv", "quad_jsd"}
        for v in out.values():
            v = float(v)
            assert np.isfinite(v) and 0.0 <= v <= 1.0


# ---------------------------------------------------------------------------
# Objectives are action-vocabulary independent
# ---------------------------------------------------------------------------

class TestVocabularyIndependence:
    """tb/db consume only TrajEval grids + scalar batch fields: feeding
    log-*densities* (values > 0, impossible for categorical log-probs)
    produces exactly the hand-computed losses."""

    def _fake_batch(self, T, B, log_reward, valid, done):
        z2 = jnp.zeros((T, B))
        return RolloutBatch(
            obs=jnp.zeros((T + 1, B, 4)),
            fwd_mask=jnp.ones((T + 1, B, 2), bool),
            bwd_mask=jnp.ones((T + 1, B, 2), bool),
            actions=jnp.zeros((T, B, 3)),
            bwd_actions=jnp.zeros((T, B, 3)),
            valid=jnp.asarray(valid),
            done=jnp.asarray(done),
            log_reward=jnp.asarray(log_reward),
            log_r_state=jnp.zeros((T + 1, B)),
            energy=jnp.zeros((T + 1, B)),
            log_pf_beh=z2)

    def test_tb_parts_with_densities(self):
        from repro.core.objectives import TrajEval, combine_parts, tb_parts
        T, B = 3, 2
        log_pf = jnp.asarray([[2.5, -1.0], [3.0, 0.5], [0.0, 1.5]])
        log_pb = jnp.asarray([[0.0, 4.0], [1.0, 0.0], [0.0, -2.0]])
        valid = jnp.asarray([[True, True], [True, True], [False, True]])
        done = jnp.asarray([[False] * 2] * 3 + [[True] * 2])
        lr = jnp.asarray([1.2, -0.3])
        ev = TrajEval(log_pf=jnp.where(valid, log_pf, 0.0),
                      log_pb=jnp.where(valid, log_pb, 0.0),
                      log_flow=jnp.zeros((T + 1, B)),
                      log_pf_stop=jnp.zeros((T + 1, B)))
        batch = self._fake_batch(T, B, lr, valid, done)
        log_z = jnp.asarray(0.7)
        num, den = tb_parts(ev, batch, log_z)
        pf = np.where(np.asarray(valid), np.asarray(log_pf), 0.0).sum(0)
        pb = np.where(np.asarray(valid), np.asarray(log_pb), 0.0).sum(0)
        delta = 0.7 + pf - np.asarray(lr) - pb
        np.testing.assert_allclose(float(num), (delta ** 2).sum(),
                                   rtol=1e-6)
        assert float(den) == B
        np.testing.assert_allclose(float(combine_parts(num, den)),
                                   (delta ** 2).mean(), rtol=1e-6)

    def test_db_parts_with_densities(self):
        from repro.core.objectives import TrajEval, db_parts
        T, B = 2, 1
        log_pf = jnp.asarray([[1.5], [2.0]])
        log_pb = jnp.asarray([[0.0], [3.5]])
        log_flow = jnp.asarray([[0.4], [1.1], [0.0]])
        valid = jnp.ones((T, B), bool)
        done = jnp.asarray([[False], [False], [True]])
        lr = jnp.asarray([2.2])
        ev = TrajEval(log_pf=log_pf, log_pb=log_pb, log_flow=log_flow,
                      log_pf_stop=jnp.zeros((T + 1, B)))
        batch = self._fake_batch(T, B, lr, valid, done)
        num, den = db_parts(ev, batch)
        # terminal flow pinned to log R
        flows = np.asarray([[0.4], [1.1], [2.2]])
        delta = (flows[:-1] + np.asarray(log_pf)
                 - flows[1:] - np.asarray(log_pb))
        np.testing.assert_allclose(float(num), (delta ** 2).sum(),
                                   rtol=1e-6)
        assert float(den) == T * B

    def test_evaluate_trajectory_dispatches_on_density_heads(self):
        """A Policy with log_prob set routes through the continuous path:
        TrajEval's grids are exactly the policy densities of the stored
        actions (teacher forcing)."""
        from repro.core.objectives import evaluate_trajectory
        env, params, policy, pp = _setup()
        batch = forward_rollout(jax.random.PRNGKey(9), env, params, policy,
                                pp, 16)
        ev = evaluate_trajectory(policy, pp, batch)
        T, B = batch.actions.shape[:2]
        assert ev.log_pf.shape == (T, B)
        want = jax.vmap(
            lambda o, a: policy.log_prob(pp, o, a))(batch.obs[:-1],
                                                    batch.actions)
        np.testing.assert_allclose(
            np.asarray(jnp.where(batch.valid, want, 0.0)),
            np.asarray(ev.log_pf), rtol=1e-5, atol=1e-5)
        # on-policy: teacher-forced log_pf == behavior log_pf (eps=0)
        np.testing.assert_allclose(np.asarray(ev.log_pf),
                                   np.asarray(batch.log_pf_beh),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Registry / CLI satellites
# ---------------------------------------------------------------------------

class TestRegistryAndCLI:
    def test_box_registered_as_continuous(self):
        from repro.envs.registry import get_env
        e = get_env("box")
        assert e.action_space == "continuous"
        assert e.serving == "none"
        assert "reward_cache" not in e.transforms

    def test_list_envs_shows_actions_column(self, capsys):
        from repro.run import main
        assert main(["--list-envs"]) == 0
        out = capsys.readouterr().out
        box_row = [ln for ln in out.splitlines()
                   if ln.startswith("box")][0]
        assert "actions=continuous" in box_row
        assert "actions=discrete" in out

    def test_reward_cache_on_box_rejected_cleanly(self, capsys):
        from repro.run import main
        rc = main(["--env", "box", "--transform", "reward_cache",
                   "--iterations", "1"])
        assert rc == 2
        err = capsys.readouterr().err
        assert "does not support transform 'reward_cache'" in err

    def test_box_short_training_smoke(self):
        """--env box trains end-to-end: finite losses, metrics rows with
        the quadrature metric names."""
        from repro.run import run_recipe
        out = run_recipe("box_tb", iterations=8, num_envs=16, eval_every=4,
                         eval_batch=64, log=lambda *_: None)
        losses = [r["loss"] for r in out["history"]]
        assert np.all(np.isfinite(losses))
        assert {"quad_tv", "quad_jsd"} <= set(out["metrics"][0])
