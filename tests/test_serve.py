"""Serving-engine tests: continuous batching must be *invisible* in the
samples.

The engine's parity contract (src/repro/serve/engine.py) says a request's
samples equal ``forward_rollout(request_key, ...)`` bit-for-bit regardless
of lane count, pool co-tenants, or refill order.  These tests pin that
contract on both serving tiers (KV-cached bitseq, full-obs hypergrid),
check refilled lanes leak nothing, check mixed-temperature pools reproduce
their single-request runs, and pin the satellite key-derivation identity
(`derive_env_keys` == the per-step fold_in chain it replaced).
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import recipes
from repro.core.rollout import forward_rollout
from repro.core.types import derive_env_keys
from repro.envs.registry import make_env
from repro.envs.transforms import apply_transforms
from repro.serve import SampleRequest, SamplingEngine, Scheduler
from repro.serve.api import make_handler


@pytest.fixture(scope="module")
def bitseq_setup():
    env = make_env("bitseq", n=16, k=4)
    env_params = env.init(jax.random.PRNGKey(0))
    policy = recipes.get("bitseq_tb").make_policy(env)
    policy_params = policy.init(jax.random.PRNGKey(0))
    return env, env_params, policy, policy_params


@pytest.fixture(scope="module")
def bitseq_engine(bitseq_setup):
    env, env_params, policy, policy_params = bitseq_setup
    # 3 lanes so any request with >3 samples must continuously rebatch
    return SamplingEngine(env, env_params, policy, policy_params,
                          num_lanes=3)


def test_derive_env_keys_matches_fold_in_chain():
    """The hoisted (T, B) key grid is bitwise the per-step fold_in chain
    the rollout scan used to run (vmap does not change fold_in's math)."""
    T, B, off = 5, 4, 7
    keys = jax.random.split(jax.random.PRNGKey(3), T)
    env_ids = off + jnp.arange(B)
    grid = derive_env_keys(keys, env_ids)
    assert grid.shape == (T, B, 2)
    for t in range(T):
        for i in range(B):
            ref = jax.random.fold_in(keys[t], off + i)
            assert np.array_equal(np.asarray(grid[t, i]), np.asarray(ref))


def test_engine_matches_forward_rollout_under_rebatching(bitseq_setup,
                                                         bitseq_engine):
    """7 samples through 3 lanes: several refill waves, still bitwise the
    single forward_rollout(key, ..., 7) batch."""
    env, env_params, policy, policy_params = bitseq_setup
    key = jax.random.PRNGKey(7)
    ref = forward_rollout(key, env, env_params, policy, policy_params, 7)
    rid = bitseq_engine.submit(num_samples=7, key=key)
    res = bitseq_engine.run()[rid]
    assert np.array_equal(res.samples, np.asarray(ref.obs[-1]))
    assert np.array_equal(res.log_rewards, np.asarray(ref.log_reward))
    assert bitseq_engine.counters["blocks"] > 0


def test_refilled_lanes_leak_no_state(bitseq_engine):
    """Three identical-key requests across a 2-deep pool: the 2nd and 3rd
    run in lanes vacated by earlier occupants, so any state/cache leakage
    shows up as a bitwise mismatch between the three results."""
    key = jax.random.PRNGKey(11)
    rids = [bitseq_engine.submit(num_samples=2, key=key) for _ in range(3)]
    out = bitseq_engine.run()
    first = out[rids[0]]
    for rid in rids[1:]:
        assert np.array_equal(out[rid].samples, first.samples)
        assert np.array_equal(out[rid].log_rewards, first.log_rewards)
        assert np.array_equal(out[rid].steps, first.steps)


def test_mixed_temperature_pool_reproduces_solo_runs(bitseq_setup,
                                                     bitseq_engine):
    """Requests at three different temperatures share the pool; each must
    reproduce the run it would get alone (temperature is lane-resident,
    never cross-lane)."""
    env, env_params, policy, policy_params = bitseq_setup
    key = jax.random.PRNGKey(3)
    rid_plain = bitseq_engine.submit(num_samples=2, key=key)
    rid_beta = bitseq_engine.submit(num_samples=2, key=key, reward_beta=2.0)
    rid_temp = bitseq_engine.submit(num_samples=2, key=key, logit_temp=0.5)
    out = bitseq_engine.run()
    plain, beta, temp = out[rid_plain], out[rid_beta], out[rid_temp]

    # beta=1 lanes are bitwise the bare rollout (x1.0 multiplies exactly)
    ref = forward_rollout(key, env, env_params, policy, policy_params, 2)
    assert np.array_equal(plain.samples, np.asarray(ref.obs[-1]))
    assert np.array_equal(plain.log_rewards, np.asarray(ref.log_reward))

    # reward_beta tempers the *reward*, not the policy: same trajectories,
    # log-rewards scaled by beta (x2.0 is exact in fp); and it matches
    # forward_rollout on the RewardExponent-wrapped env
    assert np.array_equal(beta.samples, plain.samples)
    assert np.array_equal(beta.log_rewards, 2.0 * plain.log_rewards)
    wrapped = apply_transforms(env, ("reward_exponent:beta=2.0",))
    wref = forward_rollout(key, wrapped,
                           wrapped.init(jax.random.PRNGKey(0)),
                           policy, policy_params, 2)
    assert np.array_equal(beta.log_rewards, np.asarray(wref.log_reward))

    # logit_temp changes the sampled trajectories; a solo run at the same
    # temperature (fresh lanes, nothing else in the pool) must match
    rid_solo = bitseq_engine.submit(num_samples=2, key=key, logit_temp=0.5)
    solo = bitseq_engine.run()[rid_solo]
    assert np.array_equal(temp.samples, solo.samples)
    assert np.array_equal(temp.log_rewards, solo.log_rewards)


def test_full_obs_env_engine_parity():
    """The non-sequence tier (no KV cache, full re-observation per step)
    honors the same parity contract."""
    env = make_env("hypergrid", dim=2, side=6)
    env_params = env.init(jax.random.PRNGKey(0))
    policy = recipes.get("hypergrid_tb").make_policy(env)
    policy_params = policy.init(jax.random.PRNGKey(0))
    engine = SamplingEngine(env, env_params, policy, policy_params,
                            num_lanes=4)
    assert not engine.cached
    key = jax.random.PRNGKey(5)
    ref = forward_rollout(key, env, env_params, policy, policy_params, 6)
    rid = engine.submit(num_samples=6, key=key)
    res = engine.run()[rid]
    assert np.array_equal(res.samples, np.asarray(ref.obs[-1]))
    assert np.array_equal(res.log_rewards, np.asarray(ref.log_reward))


@pytest.fixture(scope="module")
def scheduler():
    return Scheduler(num_lanes=3)


def test_scheduler_coalesces_same_env_requests(scheduler):
    """Two requests differing only in temperature/seed share one engine
    (one compiled program); distinct env configs get their own."""
    base = dict(env="bitseq", overrides={"n": 16, "k": 4})
    r0 = scheduler.submit(SampleRequest(num_samples=2, seed=1, **base))
    r1 = scheduler.submit(SampleRequest(num_samples=2, seed=2,
                                        reward_beta=2.0, **base))
    assert scheduler.num_engines == 1
    out = scheduler.run()
    assert set(out) == {r0, r1}
    for rid in (r0, r1):
        assert len(out[rid].samples) == 2
        assert len(out[rid].log_rewards) == 2
    # engine-local parity carries through the scheduler surface
    env = make_env("bitseq", n=16, k=4)
    env_params = env.init(jax.random.PRNGKey(0))
    policy = recipes.get("bitseq_tb").make_policy(env)
    policy_params = policy.init(jax.random.PRNGKey(0))
    ref = forward_rollout(jax.random.PRNGKey(1), env, env_params,
                          policy, policy_params, 2)
    assert np.array_equal(np.asarray(out[r0].samples),
                          np.asarray(ref.obs[-1]))


def test_scheduler_rejects_unservable_env(scheduler):
    with pytest.raises(ValueError, match="not servable"):
        scheduler.submit(SampleRequest(env="ising"))


def test_http_endpoint_round_trip(scheduler):
    """POST /sample + GET /envs over the stdlib endpoint (reusing the
    module scheduler so the bitseq engine is already compiled)."""
    import json
    from http.client import HTTPConnection
    from http.server import HTTPServer

    server = HTTPServer(("127.0.0.1", 0), make_handler(scheduler))
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        conn = HTTPConnection("127.0.0.1", server.server_address[1],
                              timeout=120)
        body = json.dumps({"env": "bitseq", "num_samples": 2, "seed": 9,
                           "overrides": {"n": 16, "k": 4}})
        conn.request("POST", "/sample", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        doc = json.loads(resp.read())
        assert len(doc["samples"]) == 2
        assert len(doc["log_rewards"]) == 2

        conn.request("GET", "/envs")
        resp = conn.getresponse()
        assert resp.status == 200
        envs = {row["env"]: row["serving"]
                for row in json.loads(resp.read())["envs"]}
        assert envs["bitseq"] == "kv-cache"
        assert envs["ising"] == "none"

        conn.request("POST", "/sample", json.dumps({"num_samples": 1}),
                     {"Content-Type": "application/json"})
        assert conn.getresponse().status == 400
    finally:
        server.shutdown()
        server.server_close()
