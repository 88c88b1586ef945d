"""Mesh-plan suite: single vs data_parallel parity on a forced
8-virtual-device CPU mesh (see conftest.py), per-shard FIFO buffer
properties, plan registry semantics, seed-plan shapes, and TrainLoop
checkpoint resume."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro
from _hyp import given, settings, st
from repro.algo import (DataParallelPlan, ExecutionPlan, ReplaySampler,
                        ShardInfo, TrainLoop, VmapSeedsPlan, auto_plan,
                        make_plan)
from repro.buffer.fifo import FIFOBuffer
from repro.core.policies import make_mlp_policy
from repro.core.rollout import forward_rollout
from repro.core.trainer import GFNConfig
from repro.recipes.base import RunOptions

KEY = jax.random.PRNGKey(0)
SHARDS = 8

pytestmark = pytest.mark.skipif(
    jax.device_count() < SHARDS,
    reason=f"needs {SHARDS} (virtual) devices; conftest forces them unless "
           "XLA_FLAGS was preset")


def _losses(loop, key, n):
    _, (m, _) = loop.run(key, n, mode="scan")
    return np.asarray(m["loss"]), np.asarray(m["mean_log_reward"])


def _parity(env, env_params, policy, cfg, n=25, rtol=2e-3):
    """data_parallel over 8 shards must reproduce single-device per-step
    losses within float tolerance (identical trajectories; the loss/grad
    reassociate across the shard reduction, so updates drift by ~1 ulp per
    step)."""
    single = TrainLoop(env, env_params, policy, cfg, plan="single")
    dp = TrainLoop(env, env_params, policy, cfg, plan="data_parallel")
    assert dp.plan.num_shards == SHARDS
    l1, r1 = _losses(single, jax.random.PRNGKey(7), n)
    l8, r8 = _losses(dp, jax.random.PRNGKey(7), n)
    assert np.all(np.isfinite(l8))
    np.testing.assert_allclose(l1, l8, rtol=rtol, atol=1e-4)
    # mean log-reward is a pure function of the sampled trajectories: it
    # must match tightly, proving the shards sampled the same batch
    np.testing.assert_allclose(r1, r8, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Rollout-level parity
# ---------------------------------------------------------------------------

class TestRolloutParity:
    def test_sharded_forward_rollout_samples_identical_actions(self):
        from jax import shard_map

        from repro.distributed.sharding import rollout_batch_specs
        from repro.launch.mesh import make_mesh

        env = repro.HypergridEnvironment(dim=2, side=6)
        params = env.init(KEY)
        pol = make_mlp_policy(env.obs_dim, env.action_dim,
                              env.backward_action_dim, hidden=(32,))
        pp = pol.init(KEY)
        k = jax.random.PRNGKey(42)
        B, b = 16, 16 // SHARDS
        full = forward_rollout(k, env, params, pol.apply, pp, B,
                               exploration_eps=0.1)
        mesh = make_mesh((SHARDS,), ("batch",))

        def local():
            off = jax.lax.axis_index("batch") * b
            return forward_rollout(k, env, params, pol.apply, pp, b,
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

    def test_env_offset_slices_the_same_stream(self):
        """forward_rollout(b, env_offset=o) equals rows [o, o+b) of the
        full-batch rollout — the slicing invariance everything rests on."""
        env = repro.HypergridEnvironment(dim=2, side=5)
        params = env.init(KEY)
        pol = make_mlp_policy(env.obs_dim, env.action_dim,
                              env.backward_action_dim, hidden=(16,))
        pp = pol.init(KEY)
        k = jax.random.PRNGKey(3)
        full = forward_rollout(k, env, params, pol.apply, pp, 12)
        part = forward_rollout(k, env, params, pol.apply, pp, 4,
                               env_offset=5)
        np.testing.assert_array_equal(np.asarray(full.actions[:, 5:9]),
                                      np.asarray(part.actions))


# ---------------------------------------------------------------------------
# Recipe-level training parity (the ISSUE acceptance set)
# ---------------------------------------------------------------------------

class TestTrainingParity:
    @pytest.mark.parametrize("objective", ["tb", "db", "subtb"])
    def test_hypergrid_recipes(self, objective):
        from repro.recipes import get
        recipe = get(f"hypergrid_{objective}")
        env = recipe.make_env(dim=2, side=6)
        params = env.init(KEY)
        policy = recipe.make_policy(env)
        cfg = recipe.make_config(env, RunOptions(iterations=25, num_envs=16))
        _parity(env, params, policy, cfg)

    def test_bitseq_tb_recipe(self):
        from repro.recipes import get
        recipe = get("bitseq_tb")
        env = recipe.make_env(n=16, k=4)          # L=4: small enough for CPU
        params = env.init(KEY)
        policy = recipe.make_policy(env)          # 3-layer decode transformer
        cfg = recipe.make_config(env, RunOptions(iterations=12, num_envs=16))
        _parity(env, params, policy, cfg, n=12, rtol=5e-3)

    def test_dag_mdb_recipe(self):
        from repro.recipes import get
        recipe = get("dag_mdb")
        env = recipe.make_env(d=3, num_samples=20)
        params = env.init(KEY)
        policy = recipe.make_policy(env)
        cfg = recipe.make_config(env, RunOptions(iterations=20, num_envs=16))
        _parity(env, params, policy, cfg, n=20)

    def test_eval_suite_rows_match_single_device(self):
        """EvalSuite runs replicated outside the shard_map: metric rows of a
        data_parallel run must match the single-device rows."""
        from repro.recipes import get
        recipe = get("hypergrid_tb")
        env = recipe.make_env(dim=2, side=4)
        params = env.init(KEY)
        policy = recipe.make_policy(env)
        opts = RunOptions(iterations=12, num_envs=16, eval_every=5,
                          eval_batch=200)
        cfg = recipe.make_config(env, opts)

        def run(plan):
            from repro.evals import EvalSuite
            suite = EvalSuite(
                recipe.make_evals(env, params, policy, opts), every=5)
            loop = TrainLoop(env, params, policy, cfg, evals=suite,
                             plan=plan)
            state, _ = loop.run(jax.random.PRNGKey(1), 12, mode="scan")
            return suite.rows(state.metrics)

        rows1, rows8 = run("single"), run("data_parallel")
        assert [r["step"] for r in rows1] == [r["step"] for r in rows8] \
            == [0, 5, 10]
        for a, b in zip(rows1, rows8):
            for name in a:
                np.testing.assert_allclose(a[name], b[name], rtol=2e-3,
                                           atol=1e-4, err_msg=name)

    def test_replay_sampler_trains_per_shard(self):
        """No single-device parity for replay (buffers are per shard by
        design), but the sharded run must train, keep one buffer per shard,
        and never gather across devices."""
        env = repro.HypergridEnvironment(dim=2, side=6)
        params = env.init(KEY)
        pol = make_mlp_policy(env.obs_dim, env.action_dim,
                              env.backward_action_dim, hidden=(64, 64))
        cfg = GFNConfig(objective="tb", num_envs=16, lr=1e-3, log_z_lr=1e-1,
                        stop_action=env.dim, exploration_eps=0.1)
        loop = TrainLoop(env, params, pol, cfg,
                         sampler=ReplaySampler(capacity=512,
                                               replay_batch=16),
                         plan="data_parallel")
        st, (m, _) = loop.run(jax.random.PRNGKey(3), 150, mode="scan")
        L = np.asarray(m["loss"])
        assert np.all(np.isfinite(L))
        assert L[-20:].mean() < 0.5 * L[:20].mean()
        sizes = np.asarray(st.sampler.size)
        assert sizes.shape == (SHARDS,)
        assert (sizes > 0).all() and (sizes <= 512 // SHARDS).all()


# ---------------------------------------------------------------------------
# Per-shard FIFO buffers
# ---------------------------------------------------------------------------

class TestPerShardFIFO:
    @given(capacity=st.integers(16, 64), batch=st.integers(1, 4))
    @settings(deadline=None, max_examples=8)
    def test_shards_stay_disjoint_under_shard_map(self, capacity, batch):
        """Each shard's buffer only ever holds items that shard inserted,
        and sampling returns only local items."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import make_mesh

        capacity -= capacity % SHARDS            # keep it divisible
        buf = FIFOBuffer.per_shard(capacity, SHARDS, min_batch=batch)
        mesh = make_mesh((SHARDS,), ("batch",))
        state0 = jax.tree_util.tree_map(
            lambda x: jnp.stack([x] * SHARDS),
            buf.init({"x": jnp.zeros((), jnp.int32)}))

        def local(block):
            s = jax.tree_util.tree_map(lambda x: x[0], block)
            shard = jax.lax.axis_index("batch")
            for step in range(3):
                items = 1000 * shard + 10 * step + jnp.arange(batch)
                s = buf.add_batch(s, {"x": items})
            out = buf.sample(s, jax.random.fold_in(KEY, shard), 32)["x"]
            return jax.tree_util.tree_map(lambda x: x[None], s), out[None]

        run = jax.jit(shard_map(local, mesh=mesh, in_specs=(P("batch"),),
                                out_specs=(P("batch"), P("batch")),
                                check_vma=False))
        state, sampled = run(state0)
        data = np.asarray(state.data["x"])       # (SHARDS, capacity/SHARDS)
        sampled = np.asarray(sampled)            # (SHARDS, 32)
        for shard in range(SHARDS):
            filled = data[shard][:int(np.asarray(state.size)[shard])]
            assert np.all(filled // 1000 == shard), (shard, filled)
            assert np.all(sampled[shard] // 1000 == shard)
        assert np.all(np.asarray(state.size) == min(3 * batch,
                                                    capacity // SHARDS))

    def test_per_shard_capacity_validation(self):
        with pytest.raises(ValueError, match="not divisible"):
            FIFOBuffer.per_shard(100, 8)
        with pytest.raises(ValueError, match="absorb"):
            FIFOBuffer.per_shard(16, 8, min_batch=4)
        assert FIFOBuffer.per_shard(64, 8, min_batch=4).capacity == 8
        assert FIFOBuffer.per_shard(64, 1).capacity == 64

    def test_replay_sampler_rejects_indivisible_capacity(self):
        env = repro.HypergridEnvironment(dim=2, side=4)
        params = env.init(KEY)
        pol = make_mlp_policy(env.obs_dim, env.action_dim,
                              env.backward_action_dim, hidden=(8,))
        cfg = GFNConfig(objective="tb", num_envs=16, stop_action=env.dim)
        with pytest.raises(ValueError, match="divisible"):
            TrainLoop(env, params, pol, cfg,
                      sampler=ReplaySampler(capacity=100),
                      plan="data_parallel")


# ---------------------------------------------------------------------------
# Plan registry + seed plans
# ---------------------------------------------------------------------------

class TestPlans:
    def test_make_plan_names_and_describe(self):
        assert type(make_plan("single")) is ExecutionPlan
        assert type(make_plan(None)) is ExecutionPlan
        p = make_plan("data_parallel", devices=4)
        assert isinstance(p, DataParallelPlan)
        assert p.describe() == {"plan": "data_parallel", "device_count": 4,
                                "mesh_shape": [4]}
        s = make_plan("vmap_seeds", num_seeds=3)
        assert s.seeds == 3 and s.device_count == 1
        sd = make_plan("seeds_x_data", num_seeds=3, devices=2)
        assert sd.seeds == 3 and sd.device_count == 2
        inst = DataParallelPlan(num_devices=2)
        assert make_plan(inst) is inst
        with pytest.raises(KeyError):
            make_plan("pmap")
        with pytest.raises(ValueError):
            make_plan("vmap_seeds")

    def test_auto_plan_divisibility_fallback(self):
        assert auto_plan(16).name == "data_parallel"
        assert auto_plan(6).name == "single"      # 6 % 8 != 0
        assert auto_plan(16, devices=1).name == "single"
        # make_plan('auto', num_envs=...) shares the same fallback, so
        # TrainLoop(plan='auto') never errors on an awkward batch
        assert make_plan("auto", num_envs=6).name == "single"
        assert make_plan("auto", num_envs=16).name == "data_parallel"

    def test_trainloop_auto_plan_falls_back_on_awkward_batch(self):
        env = repro.HypergridEnvironment(dim=2, side=4)
        params = env.init(KEY)
        pol = make_mlp_policy(env.obs_dim, env.action_dim,
                              env.backward_action_dim, hidden=(8,))
        cfg = GFNConfig(objective="tb", num_envs=12, stop_action=env.dim)
        loop = TrainLoop(env, params, pol, cfg, plan="auto")
        assert loop.plan.name == "single"
        cfg16 = cfg._replace(num_envs=16)
        assert TrainLoop(env, params, pol, cfg16,
                         plan="auto").plan.name == "data_parallel"

    def test_non_shard_aware_sampler_rejected_on_mesh(self):
        from repro.algo import Sampler

        class Legacy(Sampler):
            name = "legacy"

            def build(self, env, env_params, policy_apply, cfg):
                return (lambda: ()), (lambda s, k, p, t: (s, None))

        env = repro.HypergridEnvironment(dim=2, side=4)
        params = env.init(KEY)
        pol = make_mlp_policy(env.obs_dim, env.action_dim,
                              env.backward_action_dim, hidden=(8,))
        cfg = GFNConfig(objective="tb", num_envs=16, stop_action=env.dim)
        with pytest.raises(TypeError, match="shard"):
            TrainLoop(env, params, pol, cfg, sampler=Legacy(),
                      plan="data_parallel")
        # ...but it still composes with the single-device plan
        TrainLoop(env, params, pol, cfg, sampler=Legacy(), plan="single")

    def test_shard_info_split_batch_errors(self):
        si = ShardInfo(axis="batch", num_shards=8)
        assert si.split_batch(16) == 2
        with pytest.raises(ValueError, match="divisible"):
            si.split_batch(12)
        assert ShardInfo().split_batch(12) == 12
        assert ShardInfo().env_offset(4) == 0

    def test_indivisible_batch_raises_at_loop_construction(self):
        env = repro.HypergridEnvironment(dim=2, side=4)
        params = env.init(KEY)
        pol = make_mlp_policy(env.obs_dim, env.action_dim,
                              env.backward_action_dim, hidden=(8,))
        cfg = GFNConfig(objective="tb", num_envs=12, stop_action=env.dim)
        with pytest.raises(ValueError, match="divisible"):
            TrainLoop(env, params, pol, cfg, plan="data_parallel")

    def test_vmap_seeds_plan_scan_shapes(self):
        env = repro.HypergridEnvironment(dim=2, side=4)
        params = env.init(KEY)
        pol = make_mlp_policy(env.obs_dim, env.action_dim,
                              env.backward_action_dim, hidden=(16,))
        cfg = GFNConfig(objective="tb", num_envs=8, stop_action=env.dim)
        loop = TrainLoop(env, params, pol, cfg,
                         plan=VmapSeedsPlan(3))
        st, (m, _) = loop.run(jax.random.PRNGKey(5), 10, mode="scan")
        assert np.asarray(m["loss"]).shape == (10, 3)
        # seeds are independent runs
        assert not np.allclose(np.asarray(m["loss"])[:, 0],
                               np.asarray(m["loss"])[:, 1])

    def test_seeds_x_data_plan_runs_and_matches_vmap_seeds(self):
        """The composed plan distributes each seed's batch over the mesh;
        per-env keyed sampling makes it reproduce the pure vmap_seeds plan
        (same seeds, same trajectories) within float tolerance."""
        env = repro.HypergridEnvironment(dim=2, side=4)
        params = env.init(KEY)
        pol = make_mlp_policy(env.obs_dim, env.action_dim,
                              env.backward_action_dim, hidden=(16,))
        cfg = GFNConfig(objective="tb", num_envs=16, stop_action=env.dim)
        a = TrainLoop(env, params, pol, cfg, plan=VmapSeedsPlan(2))
        b = TrainLoop(env, params, pol, cfg,
                      plan=make_plan("seeds_x_data", num_seeds=2))
        _, (ma, _) = a.run(jax.random.PRNGKey(5), 8, mode="scan")
        _, (mb, _) = b.run(jax.random.PRNGKey(5), 8, mode="scan")
        np.testing.assert_allclose(np.asarray(ma["loss"]),
                                   np.asarray(mb["loss"]), rtol=2e-3,
                                   atol=1e-4)

    def test_legacy_vmap_seeds_mode_requires_single_plan(self):
        env = repro.HypergridEnvironment(dim=2, side=4)
        params = env.init(KEY)
        pol = make_mlp_policy(env.obs_dim, env.action_dim,
                              env.backward_action_dim, hidden=(8,))
        cfg = GFNConfig(objective="tb", num_envs=16, stop_action=env.dim)
        loop = TrainLoop(env, params, pol, cfg, plan="data_parallel")
        with pytest.raises(ValueError, match="seeds_x_data"):
            loop.run(KEY, 5, mode="vmap_seeds", num_seeds=2)


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

class TestCheckpointedTrainLoop:
    def _loop(self, plan="single"):
        env = repro.HypergridEnvironment(dim=2, side=5)
        params = env.init(KEY)
        pol = make_mlp_policy(env.obs_dim, env.action_dim,
                              env.backward_action_dim, hidden=(16,))
        cfg = GFNConfig(objective="tb", num_envs=16, stop_action=env.dim)
        return TrainLoop(env, params, pol, cfg, plan=plan)

    @pytest.mark.parametrize("plan", ["single", "data_parallel"])
    def test_resume_reproduces_straight_run(self, plan, tmp_path):
        from repro.checkpoint.manager import CheckpointManager
        loop = self._loop(plan)
        straight, _ = loop.run(jax.random.PRNGKey(9), 10, mode="python")

        mgr = CheckpointManager(tmp_path / "ckpt")
        loop.run(jax.random.PRNGKey(9), 5, mode="python", checkpoint=mgr,
                 checkpoint_every=5)
        assert mgr.latest_step() == 5
        resumed, _ = loop.run(jax.random.PRNGKey(9), 10, mode="python",
                              checkpoint=mgr, checkpoint_every=5,
                              restore=True)
        assert int(np.asarray(resumed.train.step)) == 10
        for a, b in zip(jax.tree_util.tree_leaves(straight.train.params),
                        jax.tree_util.tree_leaves(resumed.train.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)

    def test_restore_under_different_plan_fails_loudly(self, tmp_path):
        """A checkpoint saved under data_parallel carries per-shard sampler
        axes; restoring it into a single-plan loop must raise instead of
        silently loading stale-shaped arrays."""
        from repro.checkpoint.manager import CheckpointManager
        env = repro.HypergridEnvironment(dim=2, side=5)
        params = env.init(KEY)
        pol = make_mlp_policy(env.obs_dim, env.action_dim,
                              env.backward_action_dim, hidden=(16,))
        cfg = GFNConfig(objective="tb", num_envs=16, stop_action=env.dim)
        mgr = CheckpointManager(tmp_path / "ckpt")
        dp = TrainLoop(env, params, pol, cfg,
                       sampler=ReplaySampler(capacity=64, replay_batch=16),
                       plan="data_parallel")
        dp.run(jax.random.PRNGKey(9), 4, mode="python", checkpoint=mgr,
               checkpoint_every=4)
        single = TrainLoop(env, params, pol, cfg,
                           sampler=ReplaySampler(capacity=64,
                                                 replay_batch=16),
                           plan="single")
        with pytest.raises(ValueError, match="same plan"):
            single.run(jax.random.PRNGKey(9), 8, mode="python",
                       checkpoint=mgr, restore=True)

    def test_checkpoint_rejected_in_scan_mode(self, tmp_path):
        from repro.checkpoint.manager import CheckpointManager
        loop = self._loop()
        with pytest.raises(ValueError, match="python"):
            loop.run(KEY, 5, mode="scan",
                     checkpoint=CheckpointManager(tmp_path / "c"))

    def test_run_recipe_checkpoint_cli_path(self, tmp_path):
        from repro.run import run_recipe
        ck = str(tmp_path / "ck")
        run_recipe("hypergrid_tb", iterations=6, num_envs=8, eval_every=3,
                   env={"dim": 2, "side": 4}, checkpoint_dir=ck,
                   checkpoint_every=4, log=lambda *_: None)
        out = run_recipe("hypergrid_tb", iterations=9, num_envs=8,
                         eval_every=3, env={"dim": 2, "side": 4},
                         checkpoint_dir=ck, checkpoint_every=4,
                         restore=True, log=lambda *_: None)
        assert int(np.asarray(out["state"].train.step)) == 9
        assert [r["step"] for r in out["metrics"]] == [0, 3, 6]


# ---------------------------------------------------------------------------
# CLI plan path
# ---------------------------------------------------------------------------

class TestRunRecipePlans:
    def test_run_recipe_data_parallel_matches_single(self):
        from repro.run import run_recipe
        kw = dict(iterations=8, num_envs=16, eval_every=4,
                  env={"dim": 2, "side": 4}, log=lambda *_: None)
        out1 = run_recipe("hypergrid_tb", plan="single", **kw)
        out8 = run_recipe("hypergrid_tb", plan="data_parallel", **kw)
        l1 = [r["loss"] for r in out1["history"]]
        l8 = [r["loss"] for r in out8["history"]]
        np.testing.assert_allclose(l1, l8, rtol=2e-3, atol=1e-4)
        for a, b in zip(out1["metrics"], out8["metrics"]):
            np.testing.assert_allclose(a["exact_tv"], b["exact_tv"],
                                       rtol=2e-3, atol=1e-4)

    def test_run_recipe_vmap_seeds_plan(self):
        from repro.run import run_recipe
        out = run_recipe("hypergrid_tb", iterations=5, num_envs=8,
                         eval_every=5, env={"dim": 2, "side": 4},
                         plan="vmap_seeds", num_seeds=2,
                         log=lambda *_: None)
        assert np.isfinite(out["history"][-1]["loss"])

    def test_cli_plan_flag(self):
        from repro.run import main
        assert main(["--recipe", "hypergrid_tb", "--iterations", "5",
                     "--eval-every", "5", "--num-envs", "16",
                     "--set", "dim=2", "--set", "side=4",
                     "--plan", "data_parallel", "--devices", "4"]) == 0

    def test_run_override_recipe_rejects_plan(self):
        from repro.run import run_recipe
        with pytest.raises(ValueError, match="custom training driver"):
            run_recipe("ising_ebgfn", plan="data_parallel",
                       log=lambda *_: None)
