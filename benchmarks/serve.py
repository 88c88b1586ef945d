"""Serving benchmark: continuous batching vs a naive request-wave server.

Workload: a wave of heterogeneous sampling requests (2-8 samples each, one
PRNG seed per request) against the full-size bitseq120 env.  Two servers:

- **naive**: the pad-to-max, restart-batch-per-request-wave baseline — one
  compiled ``forward_rollout`` at the wave's max request size, re-launched
  per request in arrival order (each request waits for every batch before
  it, and small requests pay the padded batch).
- **engine**: :class:`repro.serve.SamplingEngine` — all requests' samples
  packed into one lane pool, drained/refilled per step (continuous
  batching), so the whole wave advances as a few large device batches.

Both servers produce bitwise-identical samples per request (the engine
parity contract), so this measures scheduling alone.  Rows report
requests/s (``it_per_s``) plus p50/p99 per-request latency; CI's
serve-smoke job asserts the engine clears the >= 1.5x acceptance bar.

A second pair of rows measures the *front* (ISSUE 8): the same request
mix pushed by 8 concurrent clients through the threaded
:class:`repro.serve.ServeFront` vs pushed serially through the legacy
blocking single-threaded path — client-observed req/s and p99 under
contention, sharing one engine/scheduler so only the front differs.

ISSUE 9 adds three more row families:

- **drain** (``serve/drain_noop_*``): the per-block host-sync cost when
  zero lanes finished — the lean path (one device-side counter fetch,
  what ``step()`` now pays) vs the PR-8 shape (full-pool observation plus
  four more full-pool pulls).  CI asserts lean is strictly faster.
- **dedup** (``serve/bitseq120_dedup50_*``): a 50%-duplicate request mix
  (every other request repeats one heavy request) through engines with
  dedup on vs off — effective req/s and the hit/join counters.  CI
  asserts the >= 2x acceptance bar.
- **mesh** (``serve/bitseq120_engine_{single,dpN}_l*``): the same wave
  through the same-size lane pool under ``plan="single"`` vs
  ``data_parallel`` over ``SERVE_MESH_SHARDS`` forced virtual CPU
  devices — the fixed-global-lanes sharding-efficiency form PR 4's mesh
  rows use (re-exec'd in a subprocess when the parent backend already
  fixed its device count).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np

from .common import require_cpu_for_child, row

SERVE_MESH_SHARDS = 4


def _pct(lat_s, q) -> float:
    return float(np.percentile(np.asarray(lat_s) * 1e3, q))


def run(quick: bool = True):
    from repro import recipes
    from repro.core.rollout import forward_rollout
    from repro.envs.registry import make_env
    from repro.serve import SamplingEngine

    env = make_env("bitseq")  # paper-scale n=120, k=8 (T = 15 steps)
    env_params = env.init(jax.random.PRNGKey(0))
    policy = recipes.get("bitseq_tb").make_policy(env)
    policy_params = policy.init(jax.random.PRNGKey(0))

    n_req = 8 if quick else 32
    lanes = 32 if quick else 64
    # skewed request-size mix (mostly small, a few large): the realistic
    # serving load that pad-to-max punishes — the naive server computes
    # max(sizes) trajectories per request no matter how small the request,
    # the engine only fills the lanes the wave actually needs
    sizes = [1, 2, 8, 3, 1, 4, 2, 8]
    reqs = [(1000 + i, sizes[i % len(sizes)]) for i in range(n_req)]
    pad = max(ns for _, ns in reqs)
    total = sum(ns for _, ns in reqs)

    # -- naive: one padded compiled rollout, restarted per request ----------
    @jax.jit
    def naive_rollout(key):
        b = forward_rollout(key, env, env_params, policy, policy_params, pad)
        return b.obs[-1], b.log_reward

    # both servers are timed as the median of 3 identical windows (the
    # time_iterations convention): the first post-compile window pays
    # allocator/layout run-in on shared CPU boxes, and one hot window is
    # not a stable estimate there either
    jax.block_until_ready(naive_rollout(jax.random.PRNGKey(0)))  # compile
    naive_times = []
    for _ in range(3):
        t0 = time.perf_counter()
        lat_naive = []
        for seed, ns in reqs:
            out = naive_rollout(jax.random.PRNGKey(seed))
            jax.block_until_ready(out)  # request completes with its batch
            lat_naive.append(time.perf_counter() - t0)
        naive_times.append(time.perf_counter() - t0)
    naive_s = float(np.median(naive_times))

    # -- engine: every request packed into one continuously-batched pool ----
    engine = SamplingEngine(env, env_params, policy, policy_params,
                            num_lanes=lanes)
    # warm with a pool-filling wave: compiles step/refill/drain AND pays
    # the first-full-pool run-in, so the timed waves are steady-state
    rid = engine.submit(num_samples=lanes, seed=0)
    engine.run()
    engine_times = []
    for _ in range(3):
        t0 = time.perf_counter()
        rids = [engine.submit(num_samples=ns, seed=seed)
                for seed, ns in reqs]
        results = engine.run()
        engine_times.append(time.perf_counter() - t0)
        lat_engine = [results[r].latency_s for r in rids]
    engine_s = float(np.median(engine_times))

    naive_rps = n_req / naive_s
    engine_rps = n_req / engine_s
    rows = [
        row("serve/bitseq120_naive", naive_rps,
            p50_ms=round(_pct(lat_naive, 50), 1),
            p99_ms=round(_pct(lat_naive, 99), 1),
            requests=n_req, samples=total, pad=pad),
        row("serve/bitseq120_engine", engine_rps,
            p50_ms=round(_pct(lat_engine, 50), 1),
            p99_ms=round(_pct(lat_engine, 99), 1),
            requests=n_req, samples=total, lanes=lanes,
            speedup_vs_naive=round(engine_rps / naive_rps, 2),
            **engine.plan.describe()),
    ]
    rows.extend(_front_rows(quick))
    rows.extend(_drain_rows(quick, env, env_params, policy, policy_params))
    rows.extend(_dedup_rows(quick, env, env_params, policy, policy_params))
    rows.extend(run_mesh_serve(quick))
    return rows


def _front_rows(quick: bool):
    """Threaded front (8 concurrent clients) vs the legacy single-threaded
    blocking path, client-observed.  One bitseq120 engine/scheduler config
    on both sides, so the delta is pure front scheduling + contention."""
    from repro.serve import SampleRequest, Scheduler, ServeFront

    n_clients = 8
    n_per = 2 if quick else 6
    sizes = [1, 2, 8, 3, 1, 4, 2, 8]
    base = dict(env="bitseq", overrides={})

    def reqs_for(tid):
        return [SampleRequest(num_samples=sizes[(tid + j) % len(sizes)],
                              seed=2000 + tid * n_per + j, **base)
                for j in range(n_per)]

    # -- serial baseline: requests processed one at a time ------------------
    sched_s = Scheduler(num_lanes=32)
    rid = sched_s.submit(SampleRequest(num_samples=2, seed=0, **base))
    sched_s.run(only=(rid,))            # compile
    all_reqs = [r for t in range(n_clients) for r in reqs_for(t)]
    t0 = time.perf_counter()
    lat_serial = []
    for req in all_reqs:
        ts = time.perf_counter()
        rid = sched_s.submit(req)
        sched_s.run(only=(rid,))
        lat_serial.append(time.perf_counter() - ts)
    serial_s = time.perf_counter() - t0

    # -- threaded front: 8 concurrent clients -------------------------------
    sched_c = Scheduler(num_lanes=32)
    front = ServeFront(sched_c, max_queue=64, checkpoint_poll_s=None)
    front.request(SampleRequest(num_samples=2, seed=0, **base))  # compile
    lat_conc, lock = [], threading.Lock()

    def client(tid):
        for req in reqs_for(tid):
            ts = time.perf_counter()
            front.request(req, client=f"bench-{tid}")
            dt = time.perf_counter() - ts
            with lock:
                lat_conc.append(dt)

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    conc_s = time.perf_counter() - t0
    front.shutdown(drain=True, timeout=60.0)

    n_req = len(all_reqs)
    serial_rps = n_req / serial_s
    conc_rps = n_req / conc_s
    # the real plan/mesh fields of the engines the front actually drove
    # (REPRO_SERVE_PLAN/_DEVICES may have forced the sharded path)
    planned = next(iter(sched_c._engines.values())).plan.describe()
    return [
        row("serve/bitseq120_front_serial", serial_rps,
            p50_ms=round(_pct(lat_serial, 50), 1),
            p99_ms=round(_pct(lat_serial, 99), 1),
            requests=n_req, clients=1, **planned),
        row("serve/bitseq120_front_concurrent8", conc_rps,
            p50_ms=round(_pct(lat_conc, 50), 1),
            p99_ms=round(_pct(lat_conc, 99), 1),
            requests=n_req, clients=n_clients,
            speedup_vs_serial=round(conc_rps / serial_rps, 2), **planned),
    ]


def _drain_rows(quick: bool, env, env_params, policy, policy_params):
    """Per-block host-sync cost when zero lanes finished — the common case
    at ``steps_per_sync="auto"``.  Lean = what ``step()`` pays now: the
    done count is computed inside the block's own dispatch, so the drain
    reads back one scalar and skips everything else.  Full = the
    observe-the-pool-to-find-out shape (full-pool observation + four more
    full-pool pulls).  Both iterate the identical no-completion state, so
    the delta is pure host sync; CI asserts lean is strictly faster."""
    import jax.numpy as jnp

    from repro.serve import SamplingEngine

    lanes = 32
    engine = SamplingEngine(env, env_params, policy, policy_params,
                            num_lanes=lanes)
    engine.submit(num_samples=2, seed=0)
    engine.run()                         # compile step/refill/count/pack
    nd = jnp.zeros((lanes,), bool)
    cnt = engine._jcount(nd)             # rides the block dispatch in step()
    n = 300 if quick else 1500

    engine._undrained = (nd, cnt)
    engine._drain_pending()              # warm the lean path
    t0 = time.perf_counter()
    for _ in range(n):
        engine._undrained = (nd, cnt)
        engine._drain_pending()
    lean_s = time.perf_counter() - t0

    np.asarray(engine._jobserve(engine.lane))   # warm the full pull
    t0 = time.perf_counter()
    for _ in range(n):
        np.asarray(engine._jobserve(engine.lane))
        np.asarray(engine.lane.log_r)
        np.asarray(engine.lane.request_id)
        np.asarray(engine.lane.env_id)
        np.asarray(engine.lane.t)
    full_s = time.perf_counter() - t0

    lean_rps, full_rps = n / lean_s, n / full_s
    return [
        row("serve/drain_noop_full_pull", full_rps, lanes=lanes,
            host_syncs=5),
        row("serve/drain_noop_lean", lean_rps, lanes=lanes, host_syncs=1,
            speedup_vs_full_pull=round(lean_rps / full_rps, 2)),
    ]


def _dedup_rows(quick: bool, env, env_params, policy, policy_params):
    """Effective req/s on a 50%-duplicate mix: every other request repeats
    one heavy (16-sample) request, interleaved with unique small requests —
    the duplicate-heavy load cross-request dedup exists for.  With dedup
    on, the hot request computes once (1 miss + joins/LRU hits) and only
    the unique tail touches lanes; with dedup off every duplicate recomputes
    its 8 samples.  CI asserts the >= 2x acceptance bar."""
    from repro.serve import SamplingEngine

    lanes = 32
    n_req = 16 if quick else 48
    hot_seed, hot_ns = 900, 16
    small = [1, 2, 3, 2]
    mix = []
    for i in range(n_req // 2):
        mix.append((hot_seed, hot_ns))
        mix.append((1000 + i, small[i % len(small)]))

    def wave(cache_size):
        engine = SamplingEngine(env, env_params, policy, policy_params,
                                num_lanes=lanes,
                                dedup_cache_size=cache_size)
        engine.submit(num_samples=lanes, seed=0)
        engine.run()                     # compile + first-full-pool run-in
        t0 = time.perf_counter()
        rids = [engine.submit(num_samples=ns, seed=s) for s, ns in mix]
        res = engine.run()
        dt = time.perf_counter() - t0
        assert all(r in res for r in rids)
        return dt, engine

    off_s, _ = wave(0)
    on_s, eng = wave(64)
    off_rps, on_rps = n_req / off_s, n_req / on_s
    served_dedup = (eng.counters["dedup_hits"] + eng.counters["dedup_joins"])
    return [
        row("serve/bitseq120_dedup50_off", off_rps, requests=n_req,
            duplicates=n_req // 2, lanes=lanes),
        row("serve/bitseq120_dedup50_on", on_rps, requests=n_req,
            duplicates=n_req // 2, lanes=lanes,
            dedup_hits=eng.counters["dedup_hits"],
            dedup_joins=eng.counters["dedup_joins"],
            hit_rate=round(served_dedup / n_req, 2),
            speedup_vs_off=round(on_rps / off_rps, 2)),
    ]


def _mesh_serve_rows(quick: bool, shards: int):
    """Fixed-global-lanes sharding efficiency (PR 4's mesh-row form): the
    same request wave through the same-size lane pool, single-device vs
    ``data_parallel`` over ``shards`` devices.  Lane work is row-local, so
    perfect sharding would hold req/s constant (efficiency 1.0); the row
    measures what shard_map dispatch + per-shard refill actually cost."""
    from repro import recipes
    from repro.envs.registry import make_env
    from repro.serve import SamplingEngine

    env = make_env("bitseq")
    env_params = env.init(jax.random.PRNGKey(0))
    policy = recipes.get("bitseq_tb").make_policy(env)
    policy_params = policy.init(jax.random.PRNGKey(0))

    lanes = 32
    n_req = 8 if quick else 24
    sizes = [1, 2, 8, 3, 1, 4, 2, 8]
    reqs = [(3000 + i, sizes[i % len(sizes)]) for i in range(n_req)]

    def rate(plan):
        engine = SamplingEngine(env, env_params, policy, policy_params,
                                num_lanes=lanes, plan=plan)
        engine.submit(num_samples=lanes, seed=0)
        engine.run()                     # compile + first-full-pool run-in
        vals = []
        for _ in range(3):
            t0 = time.perf_counter()
            for seed, ns in reqs:
                engine.submit(num_samples=ns, seed=seed)
            engine.run()
            vals.append(n_req / (time.perf_counter() - t0))
        return float(np.median(vals)), engine

    single_rps, _ = rate("single")
    dp_rps, eng = rate("data_parallel")
    return [
        row(f"serve/bitseq120_engine_single_l{lanes}", single_rps,
            requests=n_req, lanes=lanes),
        row(f"serve/bitseq120_engine_dp{shards}_l{lanes}", dp_rps,
            requests=n_req, lanes=lanes,
            sharding_efficiency=f"{dp_rps / single_rps:.2f}",
            **eng.plan.describe()),
    ]


def run_mesh_serve(quick: bool = True, shards: int = SERVE_MESH_SHARDS):
    """Multi-device serve rows: in-process when enough devices are visible,
    else (CPU only) re-exec'd with ``--xla_force_host_platform_device_count``
    — the same rule as ``benchmarks.rollout.run_mesh``."""
    if jax.device_count() >= shards:
        return _mesh_serve_rows(quick, shards)
    require_cpu_for_child(shards)
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={shards}"])
    env.pop("REPRO_SERVE_PLAN", None)    # the rows pin their plans
    env.pop("REPRO_SERVE_DEVICES", None)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    cmd = [sys.executable, "-m", "benchmarks.serve", "--mesh-json",
           "--shards", str(shards)] + ([] if quick else ["--full"])
    out = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                         text=True, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(
            f"serve mesh benchmark subprocess failed:\n{out.stdout[-2000:]}"
            f"\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def _mesh_json_main(argv):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh-json", action="store_true")
    ap.add_argument("--shards", type=int, default=SERVE_MESH_SHARDS)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args(argv)
    rows = _mesh_serve_rows(quick=not args.full, shards=args.shards)
    print(json.dumps(rows))


if __name__ == "__main__":
    _mesh_json_main(sys.argv[1:])
