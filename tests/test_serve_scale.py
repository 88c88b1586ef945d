"""Mesh-native serving tests: sharding, lean drain, dedup, autosizing.

PR 9's tentpole makes the :class:`repro.serve.SamplingEngine` lane pool
mesh-native (``plan="data_parallel"`` shards lanes over the device mesh via
``shard_map``) and cuts per-block host overhead (device-side done count,
compact-and-fetch drain, pipelined dispatch).  These tests pin:

- **sharded parity**: a data-parallel lane pool is bitwise
  ``forward_rollout`` on both serving tiers (KV-cached bitseq, full-obs
  hypergrid), including mixed-temperature pools and lane-count rounding —
  sharding must be a pure execution detail (graded on the conftest-forced
  virtual-device CPU mesh);
- **lean drain**: zero-completion blocks cost one scalar sync (no
  observation, no transfer), non-zero ones a compiled compaction; the
  one-block drain lag never mis-handles a request cancelled between
  dispatch and drain;
- **cross-request dedup**: requests differing in ANY parity-contract field
  (seed, num_samples, logit_temp, reward_beta — and checkpoint step, which
  keys the engine itself) never share a cache entry, while exact duplicates
  are served bitwise-equal from one computation;
- **lane-pool autosizing**: resize/prewarm preserve parity, refuse occupied
  pools, and the front's EWMA arrival estimate grows/shrinks the pool
  across power-of-two buckets.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro import recipes
from repro.algo.plan import make_plan
from repro.core.rollout import forward_rollout
from repro.envs.registry import get_env, make_env
from repro.serve import (SampleRequest, SamplingEngine, Scheduler,
                         ServeFront)
from repro.serve.errors import EngineFailure

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >= 4 devices (tests/conftest.py forces 8 virtual CPU "
           "devices; CI's serve jobs force 4)")

BITSEQ = {"n": 8, "k": 2}


@pytest.fixture(scope="module")
def bitseq8_setup():
    env = make_env("bitseq", **BITSEQ)
    env_params = env.init(jax.random.PRNGKey(0))
    policy = recipes.get("bitseq_tb").make_policy(env)
    policy_params = policy.init(jax.random.PRNGKey(0))
    return env, env_params, policy, policy_params


@pytest.fixture(scope="module")
def single_engine(bitseq8_setup):
    env, ep, pol, pp = bitseq8_setup
    return SamplingEngine(env, ep, pol, pp, num_lanes=3)


@pytest.fixture(scope="module")
def dp_engine(bitseq8_setup):
    env, ep, pol, pp = bitseq8_setup
    # 6 requested lanes must round up to 8 (a multiple of the 4 shards)
    return SamplingEngine(env, ep, pol, pp, num_lanes=6,
                          plan=make_plan("data_parallel", devices=4))


@pytest.fixture(scope="module")
def dedup_engine(bitseq8_setup):
    env, ep, pol, pp = bitseq8_setup
    return SamplingEngine(env, ep, pol, pp, num_lanes=4,
                          dedup_cache_size=16)


# -- sharded parity ----------------------------------------------------------

@needs_mesh
def test_sharded_lane_rounding(dp_engine):
    """num_lanes is rounded up to a shard multiple (6 -> 8 on 4 devices)."""
    assert dp_engine.num_lanes == 8
    assert dp_engine.plan.describe() == {
        "plan": "data_parallel", "device_count": 4, "mesh_shape": [4]}


@needs_mesh
def test_sharded_engine_matches_forward_rollout(bitseq8_setup, dp_engine):
    """7 samples through an 8-lane/4-shard pool: several refill waves with
    ragged shard occupancy, still bitwise the solo forward_rollout batch."""
    env, ep, pol, pp = bitseq8_setup
    key = jax.random.PRNGKey(7)
    ref = forward_rollout(key, env, ep, pol, pp, 7)
    rid = dp_engine.submit(num_samples=7, key=key)
    res = dp_engine.run()[rid]
    assert np.array_equal(res.samples, np.asarray(ref.obs[-1]))
    assert np.array_equal(res.log_rewards, np.asarray(ref.log_reward))


@needs_mesh
def test_sharded_mixed_temperature_pool(bitseq8_setup, dp_engine,
                                        single_engine):
    """Mixed-temperature co-tenants on a sharded pool reproduce their
    single-device runs: β scales rewards exactly, a tempered-policy request
    matches the same request on the unsharded engine bitwise."""
    env, ep, pol, pp = bitseq8_setup
    key = jax.random.PRNGKey(3)
    rid_plain = dp_engine.submit(num_samples=2, key=key)
    rid_beta = dp_engine.submit(num_samples=2, key=key, reward_beta=2.0)
    rid_temp = dp_engine.submit(num_samples=2, key=key, logit_temp=0.5)
    out = dp_engine.run()
    plain, beta, temp = out[rid_plain], out[rid_beta], out[rid_temp]

    ref = forward_rollout(key, env, ep, pol, pp, 2)
    assert np.array_equal(plain.samples, np.asarray(ref.obs[-1]))
    assert np.array_equal(plain.log_rewards, np.asarray(ref.log_reward))
    assert np.array_equal(beta.samples, plain.samples)
    assert np.array_equal(beta.log_rewards, 2.0 * plain.log_rewards)

    rid_solo = single_engine.submit(num_samples=2, key=key, logit_temp=0.5)
    solo = single_engine.run()[rid_solo]
    assert np.array_equal(temp.samples, solo.samples)
    assert np.array_equal(temp.log_rewards, solo.log_rewards)


@needs_mesh
def test_sharded_full_obs_hypergrid():
    """The non-cached serving tier (full re-observation per step) shards
    identically: hypergrid on 4 shards is bitwise forward_rollout."""
    env = make_env("hypergrid", dim=2, side=5)
    ep = env.init(jax.random.PRNGKey(0))
    pol = recipes.get(get_env("hypergrid").recipe).make_policy(env)
    pp = pol.init(jax.random.PRNGKey(0))
    eng = SamplingEngine(env, ep, pol, pp, num_lanes=4,
                         plan=make_plan("data_parallel", devices=4))
    key = jax.random.PRNGKey(19)
    ref = forward_rollout(key, env, ep, pol, pp, 6)
    rid = eng.submit(num_samples=6, key=key)
    res = eng.run()[rid]
    assert np.array_equal(res.samples, np.asarray(ref.obs[-1]))
    assert np.array_equal(res.log_rewards, np.asarray(ref.log_reward))


@needs_mesh
def test_scheduler_data_parallel_round_trip(bitseq8_setup):
    """Scheduler(plan=..., devices=...) builds sharded engines that stay
    bitwise through the full SampleRequest -> SampleResult path."""
    env, ep, pol, pp = bitseq8_setup
    sched = Scheduler(num_lanes=6, plan="data_parallel", devices=4)
    rid = sched.submit(SampleRequest(env="bitseq", num_samples=5, seed=9,
                                     overrides=BITSEQ))
    res = sched.run(only=(rid,))[rid]
    ref = forward_rollout(jax.random.PRNGKey(9), env, ep, pol, pp, 5)
    assert np.array_equal(np.asarray(res.samples), np.asarray(ref.obs[-1]))
    assert np.array_equal(np.asarray(res.log_rewards),
                          np.asarray(ref.log_reward))
    eng = next(iter(sched._engines.values()))
    assert eng.num_lanes == 8 and eng.plan.describe()["device_count"] == 4


def test_scheduler_env_var_plan_defaults(monkeypatch):
    """REPRO_SERVE_PLAN / REPRO_SERVE_DEVICES supply scheduler defaults (so
    CI forces the sharded path without touching call sites); explicit
    arguments win over them."""
    monkeypatch.setenv("REPRO_SERVE_PLAN", "data_parallel")
    monkeypatch.setenv("REPRO_SERVE_DEVICES", "4")
    s = Scheduler()
    assert s.plan_spec == "data_parallel" and s.devices == 4
    s2 = Scheduler(plan="single", devices=1)
    assert s2.plan_spec == "single" and s2.devices == 1
    monkeypatch.delenv("REPRO_SERVE_PLAN")
    monkeypatch.delenv("REPRO_SERVE_DEVICES")
    assert Scheduler().plan_spec is None


# -- host-sync-lean drain ----------------------------------------------------

def test_zero_completion_drain_is_one_scalar(single_engine):
    """A block in which nothing finished costs exactly one scalar readback
    (the count rides the block's dispatch): no observation, no compaction,
    no row transfer."""
    eng = single_engine
    before = dict(eng.counters)
    nd = jnp.zeros((eng.num_lanes,), bool)
    eng._undrained = (nd, eng._jcount(nd))
    assert eng._drain_pending() == 0
    assert eng.counters["drain_skips"] == before["drain_skips"] + 1
    assert eng.counters["drain_packs"] == before["drain_packs"]


def test_lean_drain_counters_over_a_run(single_engine):
    """A real request hits both drain paths: most blocks complete nothing
    (skipped), terminal blocks go through the compiled compaction."""
    eng = single_engine
    before = dict(eng.counters)
    rid = eng.submit(num_samples=5, seed=77)
    res = eng.run()[rid]
    assert res.samples.shape[0] == 5
    assert eng.counters["drain_skips"] > before["drain_skips"]
    assert eng.counters["drain_packs"] > before["drain_packs"]


def test_cancel_between_dispatch_and_drain(single_engine):
    """The pipelined drain observes completions one block late; a request
    cancelled in that window (lane already refilled to idle) must drain as
    a no-op, not a LanePoisoned false positive."""
    eng = single_engine
    rid = eng.submit(num_samples=1, seed=123)
    for _ in range(10 * eng.T):
        eng.step()
        if eng._undrained is not None and int(jax.device_get(
                eng._undrained[1])):
            break
    else:
        pytest.fail("request never completed a block")
    eng.cancel(rid)                     # frees the lane, resets it to idle
    eng.step()                          # drains the stale newly_done
    assert rid not in eng.take_results()
    assert not eng._occupied.any()
    eng.run()                           # pool is healthy and drains clean


# -- cross-request dedup -----------------------------------------------------

_FIELDS = ("seed", "num_samples", "logit_temp", "reward_beta")


@pytest.mark.parametrize("field", _FIELDS)
@given(delta=st.integers(1, 7))
@settings(max_examples=5, deadline=None)
def test_dedup_contract_field_difference_never_shares(dedup_engine, field,
                                                      delta):
    """Two requests differing in any parity-contract field map to distinct
    cache entries: the perturbed request is always a dedup miss (never a
    hit, never an in-flight join), for every perturbation magnitude."""
    eng = dedup_engine
    base = {"seed": 100 + 10 * _FIELDS.index(field), "num_samples": 2,
            "logit_temp": 1.0, "reward_beta": 1.0}
    pert = dict(base)
    if field == "seed":
        pert["seed"] += delta
    elif field == "num_samples":
        pert["num_samples"] += delta
    elif field == "logit_temp":
        pert["logit_temp"] += delta * 0.125
    else:
        pert["reward_beta"] += delta * 0.25
    eng.submit(**base)
    eng.run()
    c1 = dict(eng.counters)
    rid = eng.submit(**pert)
    out = eng.run()
    assert eng.counters["dedup_hits"] == c1["dedup_hits"]
    assert eng.counters["dedup_joins"] == c1["dedup_joins"]
    assert eng.counters["dedup_misses"] == c1["dedup_misses"] + 1
    assert out[rid].dedup is False


def test_dedup_exact_duplicate_computes_once(dedup_engine):
    """Exact duplicates share one computation: an in-flight duplicate joins
    as a waiter (no extra lane work), a post-completion duplicate is an LRU
    hit (no lane work at all), and both are bitwise the primary's result.
    The engine's block counter proves the lanes ran once."""
    eng = dedup_engine
    kw = {"num_samples": 3, "seed": 7000}
    c0 = dict(eng.counters)
    r1 = eng.submit(**kw)
    r2 = eng.submit(**kw)               # in flight: joins r1
    assert eng.counters["dedup_joins"] == c0["dedup_joins"] + 1
    out = eng.run()
    blocks_after = eng.counters["blocks"]
    assert np.array_equal(out[r1].samples, out[r2].samples)
    assert np.array_equal(out[r1].log_rewards, out[r2].log_rewards)
    assert out[r1].dedup is False and out[r2].dedup is True

    r3 = eng.submit(**kw)               # completed: LRU hit, zero lane work
    assert eng.counters["dedup_hits"] == c0["dedup_hits"] + 1
    out3 = eng.run()
    assert eng.counters["blocks"] == blocks_after  # no block dispatched
    assert out3[r3].dedup is True
    assert np.array_equal(out3[r3].samples, out[r1].samples)
    assert np.array_equal(out3[r3].log_rewards, out[r1].log_rewards)
    assert out3[r3].latency_s == 0.0


def test_dedup_cancel_primary_promotes_waiter(bitseq8_setup, dedup_engine):
    """Cancelling a primary with waiters hands the in-flight computation
    over: the waiter completes bitwise-correct, nothing is recomputed."""
    env, ep, pol, pp = bitseq8_setup
    eng = dedup_engine
    kw = {"num_samples": 2, "seed": 7100}
    r1 = eng.submit(**kw)
    r2 = eng.submit(**kw)
    eng.step()                          # lanes are in flight
    eng.cancel(r1)
    out = eng.run()
    assert r1 not in out and r2 in out
    ref = forward_rollout(jax.random.PRNGKey(7100), env, ep, pol, pp, 2)
    assert np.array_equal(out[r2].samples, np.asarray(ref.obs[-1]))
    assert np.array_equal(out[r2].log_rewards, np.asarray(ref.log_reward))


def test_dedup_engine_key_separates_checkpoint_steps(tmp_path):
    """Checkpoint step is a parity-contract field too — it keys the engine
    itself, so requests pinned to different steps can never share a dedup
    entry (distinct engines, each with its own cache)."""
    from repro.checkpoint.manager import CheckpointManager
    env = make_env("bitseq", **BITSEQ)
    pol = recipes.get("bitseq_tb").make_policy(env)
    pp = pol.init(jax.random.PRNGKey(0))
    mgr = CheckpointManager(str(tmp_path), keep=4)
    mgr.save(1, {".train": {".params": pp}})
    mgr.save(2, {".train": {".params": pp}})
    sched = Scheduler(num_lanes=2)
    kw = dict(env="bitseq", num_samples=2, seed=5, overrides=BITSEQ,
              checkpoint=str(tmp_path))
    a = sched.submit(SampleRequest(step=1, **kw))
    b = sched.submit(SampleRequest(step=2, **kw))
    out = sched.run()
    assert sched.num_engines == 2
    for e in sched._engines.values():
        assert e.counters["dedup_hits"] == 0
        assert e.counters["dedup_joins"] == 0
    # same params at both steps, so the *results* agree bitwise — only the
    # cache entries are separate
    assert np.array_equal(np.asarray(out[a].samples),
                          np.asarray(out[b].samples))


# -- lane-pool resizing ------------------------------------------------------

def test_resize_preserves_parity_and_refuses_occupied(bitseq8_setup):
    env, ep, pol, pp = bitseq8_setup
    eng = SamplingEngine(env, ep, pol, pp, num_lanes=2)
    key = jax.random.PRNGKey(31)
    rid = eng.submit(num_samples=3, key=key)
    ref = eng.run()[rid]

    assert eng.resize(5) is True and eng.num_lanes == 5
    assert eng.resize(5) is False       # same size: no-op
    rid2 = eng.submit(num_samples=3, key=key)
    res = eng.run()[rid2]
    assert np.array_equal(res.samples, ref.samples)
    assert np.array_equal(res.log_rewards, ref.log_rewards)
    assert eng.counters["resizes"] == 1

    rid3 = eng.submit(num_samples=1, seed=32)
    eng.step()                          # pool is now occupied
    with pytest.raises(EngineFailure):
        eng.resize(7)
    out = eng.run()                     # still healthy after the refusal
    assert rid3 in out

    # prewarm compiles other buckets but restores the current size, and
    # the pool still serves bitwise afterwards
    eng.prewarm([2, 8])
    assert eng.num_lanes == 5
    rid4 = eng.submit(num_samples=3, key=key)
    res4 = eng.run()[rid4]
    assert np.array_equal(res4.samples, ref.samples)


@needs_mesh
def test_resize_rounds_to_shard_multiple(bitseq8_setup):
    env, ep, pol, pp = bitseq8_setup
    eng = SamplingEngine(env, ep, pol, pp, num_lanes=4,
                         plan=make_plan("data_parallel", devices=4))
    key = jax.random.PRNGKey(41)
    rid = eng.submit(num_samples=2, key=key)
    ref = eng.run()[rid]
    assert eng.resize(5) is True
    assert eng.num_lanes == 8           # 5 -> 8 on 4 shards
    rid2 = eng.submit(num_samples=2, key=key)
    res = eng.run()[rid2]
    assert np.array_equal(res.samples, ref.samples)


# -- front autosizing --------------------------------------------------------

def test_autosize_buckets_are_bounded_powers_of_two():
    front = ServeFront(Scheduler(num_lanes=2), checkpoint_poll_s=None,
                       autosize=True, min_lanes=2, max_lanes=16)
    try:
        assert front.autosize_buckets() == [2, 4, 8, 16]
    finally:
        front.shutdown(drain=False, timeout=10.0)


def test_front_autosize_grows_then_shrinks():
    """A burst of large requests drives the EWMA demand estimate up (the
    pool grows to a bigger power-of-two bucket once idle); when traffic
    goes quiet the idle-clamped arrival rate decays and the pool shrinks
    back to min_lanes.  All resizes happen between requests."""
    sched = Scheduler(num_lanes=2, dedup_cache_size=0)
    front = ServeFront(sched, checkpoint_poll_s=None, autosize=True,
                       min_lanes=2, max_lanes=8)
    try:
        base = dict(env="bitseq", overrides=BITSEQ)
        futs = [front.submit(SampleRequest(num_samples=8, seed=500 + i,
                                           **base))
                for i in range(6)]
        for f in futs:
            assert f.result(timeout=300) is not None
        runner = next(iter(front._runners.values()))
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and runner.engine.num_lanes <= 2:
            time.sleep(0.05)
        assert runner.engine.num_lanes > 2, "pool never grew after burst"
        rstats = front.stats()["engines"][0]
        assert "arrival_rate_hz" in rstats and "queued_samples" in rstats

        # quiet traffic: a few spaced tiny requests, then nothing — the
        # idle clamp drags demand to ~1 and the pool returns to min_lanes
        for i in range(3):
            time.sleep(0.3)
            front.request(SampleRequest(num_samples=1, seed=600 + i,
                                        **base))
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and runner.engine.num_lanes > 2:
            time.sleep(0.05)
        assert runner.engine.num_lanes == 2, "pool never shrank when idle"
        assert runner.counters["autosize_resizes"] >= 2
        # autosizing never broke parity: a fresh request is still bitwise
        res = front.request(SampleRequest(num_samples=2, seed=700, **base))
        env = make_env("bitseq", **BITSEQ)
        ep = env.init(jax.random.PRNGKey(0))
        pol = recipes.get("bitseq_tb").make_policy(env)
        pp = pol.init(jax.random.PRNGKey(0))
        ref = forward_rollout(jax.random.PRNGKey(700), env, ep, pol, pp, 2)
        assert np.array_equal(np.asarray(res.samples),
                              np.asarray(ref.obs[-1]))
    finally:
        front.shutdown(drain=True, timeout=60.0)
