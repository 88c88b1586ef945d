"""Infrastructure tests: buffer, checkpointing, optimizer, gradient
compression, rollout properties (hypothesis)."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis or deterministic fallback

import repro
from repro.buffer.fifo import FIFOBuffer
from repro.checkpoint.manager import CheckpointManager
from repro.core.policies import make_mlp_policy
from repro.core.rollout import backward_rollout, forward_rollout
from repro.distributed.compress import (compressed_psum, dequantize_int8,
                                        ef_int8_transform, quantize_int8)
from repro.optim import adamw as optim

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# FIFO buffer
# ---------------------------------------------------------------------------

class TestBuffer:
    def test_fifo_wraparound(self):
        buf = FIFOBuffer(capacity=8)
        st_ = buf.init({"x": jnp.zeros((), jnp.int32)})
        st_ = buf.add_batch(st_, {"x": jnp.arange(5)})
        assert int(st_.size) == 5
        st_ = buf.add_batch(st_, {"x": jnp.arange(5, 11)})
        assert int(st_.size) == 8
        # oldest entries (0, 1, 2) overwritten by (8, 9, 10)
        vals = set(np.asarray(st_.data["x"]).tolist())
        assert vals == {3, 4, 5, 6, 7, 8, 9, 10}

    def test_sample_only_valid(self):
        buf = FIFOBuffer(capacity=16)
        st_ = buf.init({"x": jnp.zeros((), jnp.int32)})
        st_ = buf.add_batch(st_, {"x": jnp.arange(4) + 100})
        s = buf.sample(st_, KEY, 64)
        assert np.all(np.asarray(s["x"]) >= 100)

    @settings(max_examples=20, deadline=None)
    @given(cap=st.integers(2, 32), n1=st.integers(1, 30),
           n2=st.integers(1, 30))
    def test_fifo_size_invariant(self, cap, n1, n2):
        buf = FIFOBuffer(capacity=cap)
        s = buf.init({"x": jnp.zeros((), jnp.int32)})
        s = buf.add_batch(s, {"x": jnp.arange(min(n1, cap))})
        s = buf.add_batch(s, {"x": jnp.arange(min(n2, cap))})
        assert int(s.size) == min(min(n1, cap) + min(n2, cap), cap)


# ---------------------------------------------------------------------------
# Checkpoint manager
# ---------------------------------------------------------------------------

class TestCheckpoint:
    def _tree(self, key):
        return {"a": jax.random.normal(key, (4, 8)),
                "b": {"c": jax.random.normal(key, (3,)).astype(jnp.bfloat16),
                      "d": jnp.int32(7)}}

    def test_roundtrip_including_bf16(self):
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, process_index=0)
            tree = self._tree(KEY)
            mgr.save(10, tree)
            restored = mgr.restore(10, tree)
            for a, b in zip(jax.tree_util.tree_leaves(tree),
                            jax.tree_util.tree_leaves(restored)):
                assert a.dtype == b.dtype
                np.testing.assert_array_equal(np.asarray(a, np.float32),
                                              np.asarray(b, np.float32))

    def test_latest_and_retention(self):
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, keep=2, process_index=0)
            tree = self._tree(KEY)
            for s in (1, 2, 3, 4):
                mgr.save(s, tree)
            assert mgr.latest_step() == 4
            assert mgr.all_steps() == [3, 4]   # retention

    def test_incomplete_checkpoint_ignored(self):
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, process_index=0)
            mgr.save(5, self._tree(KEY))
            # a torn save: directory without MANIFEST
            os.makedirs(os.path.join(d, "step_9"))
            assert mgr.latest_step() == 5

    def test_async_save(self):
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, process_index=0)
            mgr.save(3, self._tree(KEY), blocking=False)
            mgr.wait()
            assert mgr.latest_step() == 3


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------

class TestOptim:
    def test_adam_quadratic_convergence(self):
        tx = optim.adam(0.1)
        params = {"w": jnp.asarray(5.0)}
        state = tx.init(params)
        for _ in range(200):
            g = jax.grad(lambda p: (p["w"] - 2.0) ** 2)(params)
            upd, state = tx.update(g, state, params)
            params = optim.apply_updates(params, upd)
        np.testing.assert_allclose(float(params["w"]), 2.0, atol=1e-2)

    def test_clip_by_global_norm(self):
        tx = optim.clip_by_global_norm(1.0)
        g = {"a": jnp.full((4,), 10.0)}
        out, _ = tx.update(g, (), None)
        gn = float(jnp.linalg.norm(out["a"]))
        np.testing.assert_allclose(gn, 1.0, rtol=1e-4)

    def test_label_lr_groups(self):
        tx = optim.scale_by_label(
            lambda n: "z" if "log_z" in n else "d", {"z": 10.0, "d": 1.0})
        g = {"log_z": jnp.asarray(1.0), "w": jnp.asarray(1.0)}
        out, _ = tx.update(g, (), None)
        assert float(out["log_z"]) == 10.0 and float(out["w"]) == 1.0

    def test_cosine_schedule_endpoints(self):
        sched = optim.cosine_schedule(1.0, 100, warmup=10)
        np.testing.assert_allclose(float(sched(jnp.asarray(0))), 0.0)
        np.testing.assert_allclose(float(sched(jnp.asarray(10))), 1.0,
                                   rtol=1e-5)
        assert float(sched(jnp.asarray(100))) < 1e-3


# ---------------------------------------------------------------------------
# Gradient compression
# ---------------------------------------------------------------------------

class TestCompression:
    def test_quantize_roundtrip_error_bound(self):
        x = jax.random.normal(KEY, (1000,))
        q, s = quantize_int8(x)
        err = float(jnp.max(jnp.abs(dequantize_int8(q, s) - x)))
        assert err <= float(s) * 0.5 + 1e-9

    def test_error_feedback_accumulates_unbiased(self):
        """Sum of EF-compressed grads tracks sum of true grads."""
        tx = ef_int8_transform()
        g = {"w": 1e-3 * jnp.ones((64,))}   # tiny grads: heavy quantization
        state = tx.init(g)
        total = jnp.zeros((64,))
        for _ in range(100):
            out, state = tx.update(g, state)
            total = total + out["w"]
        # accumulated compressed sum ~= 100 * g despite per-step rounding
        np.testing.assert_allclose(np.asarray(total), 0.1, rtol=0.05)

    def test_compressed_psum_on_mesh(self):
        """shard_map int8 psum matches exact psum within quantization tol."""
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        mesh = jax.make_mesh((1,), ("pod",))
        x = jax.random.normal(KEY, (8, 16))

        def f(x):
            return compressed_psum({"g": x}, "pod")["g"]

        out = shard_map(f, mesh=mesh, in_specs=P(), out_specs=P())(x)
        scale = float(jnp.max(jnp.abs(x))) / 127.0
        np.testing.assert_allclose(np.asarray(out), np.asarray(x),
                                   atol=scale)


# ---------------------------------------------------------------------------
# Rollout properties (hypothesis)
# ---------------------------------------------------------------------------

class TestRolloutProperties:
    @settings(max_examples=8, deadline=None)
    @given(dim=st.integers(2, 3), side=st.integers(3, 6),
           seed=st.integers(0, 100))
    def test_rollout_terminates_and_rewards_emitted_once(self, dim, side,
                                                         seed):
        env = repro.HypergridEnvironment(dim=dim, side=side)
        params = env.init(KEY)
        pol = make_mlp_policy(env.obs_dim, env.action_dim,
                              env.backward_action_dim, hidden=(16,))
        b = forward_rollout(jax.random.PRNGKey(seed), env, params,
                            pol.apply, pol.init(KEY), 8)
        assert bool(jnp.all(b.done[-1]))
        # each env's log-reward equals the reward of its final position
        pos = jnp.argmax(b.obs[-1].reshape(8, dim, side), -1)
        lr = env.reward_module.log_reward(pos, params.reward_params)
        np.testing.assert_allclose(np.asarray(b.log_reward),
                                   np.asarray(lr), atol=1e-5)

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 1000))
    def test_backward_rollout_logprobs_finite_and_negative(self, seed):
        env = repro.BitSeqEnvironment(n=16, k=4)
        params = env.init(KEY)
        from repro.core.policies import make_transformer_policy
        pol = make_transformer_policy(env.vocab_size, env.L,
                                      env.action_dim,
                                      env.backward_action_dim,
                                      num_layers=1, dim=16)
        pp = pol.init(KEY)
        words = jax.random.randint(jax.random.PRNGKey(seed), (4, env.L),
                                   0, env.m)
        term = env.terminal_state_from_words(words)
        out = backward_rollout(jax.random.PRNGKey(seed + 1), env, params,
                               pol.apply, pp, term)
        assert np.all(np.isfinite(np.asarray(out.log_pf)))
        assert np.all(np.asarray(out.log_pf) <= 0.0)
        # uniform P_B over L! deconstruction orders and m^L words:
        # log_pb = -log(L!) exactly for this env
        import math
        np.testing.assert_allclose(np.asarray(out.log_pb),
                                   -math.log(math.factorial(env.L)),
                                   rtol=1e-5)
