"""Continuously-batched GFlowNet sampling engine.

One engine owns a pool of ``num_lanes`` *lanes* — slots of a single
compiled program — each carrying its own env state, KV cache rows, RNG
stream, request id, and temperatures.  Every call to the jitted step
advances all lanes one transition; when a lane's trajectory terminates, its
sample is drained host-side and the lane is immediately refilled from the
pending queue **without recompilation** (all shapes are static in
``num_lanes``), so variable-length rollouts never wait for a batch's max
length and heterogeneous requests pack into one device batch.  This is the
compile-once/run-many serving shape the paper's throughput claims imply:
compilation is paid once per (env, policy, lane count), then amortized over
every request the engine ever serves.

Multi-device lane pools
-----------------------
Pass ``plan="data_parallel"`` (or a :class:`repro.algo.plan.ExecutionPlan`)
and the pool shards over the plan's mesh via ``shard_map``: the lane axis
is the batch axis, refills keep per-shard static shapes, and the per-lane
β/temperature vectors shard alongside the pool.  Because every per-lane
operation is row-independent (see the parity contract below), sharding is
a pure execution detail — samples stay bitwise identical to the
single-device engine for any shard count.  ``num_lanes`` is rounded up to
a multiple of the shard count.  The host-side bookkeeping (pending queue,
drain, dedup) is untouched: ``_jstep``/``_jrefill`` are the only compiled
entry points and they swap between ``jit`` and ``jit(shard_map(...))``.
When several sharded engines share one process (a multi-env front), their
dispatches serialize on a process-wide lock — concurrent collective
programs deadlock XLA:CPU's per-device worker threads (see
:data:`_MESH_DISPATCH`).

Host-sync-lean drain
--------------------
The per-block host cost is one scalar readback — the count of lanes that
finished, computed *inside* the block's own dispatch (psum'd across
shards on a mesh) — fetched while the *next* block is already dispatched
(``step()`` drains block ``k-1`` after launching block ``k``; terminal
lanes hold their state verbatim through the extra block, so the drain is
exact).  When the count is zero (the common case at
``steps_per_sync="auto"``) nothing else is touched; otherwise a compiled
compaction (:math:`O(L)` argsort, done lanes first) packs the terminal
rows so the host fetches exactly ``count`` rows of
(obs, log_r, request_id, env_id, t) instead of five full-pool arrays.

Spans and device calls
----------------------
While a profiler session is active the engine records, through
:mod:`repro.serve.spans`, ``serve.sync`` (the count's readback),
``serve.fetch`` (compaction and the ``rows`` fetched), ``serve.refill``
(``filled`` lanes) and ``serve.dispatch`` (the block, with ``lanes_busy``
of ``lanes``).  ``counters["device_calls"]`` counts every call of the
engine that reaches the device, a program or a transfer, recorder on or
off.

Cross-request dedup
-------------------
With ``dedup_cache_size > 0`` (the :class:`repro.serve.Scheduler` default),
requests identical under the parity contract — same engine (env,
transforms, checkpoint step) and same (request key, num_samples,
logit_temp, reward_beta) — compute once: duplicates of an in-flight
request join it as waiters and fan out its :class:`EngineResult` on
completion; duplicates of a recently-completed request are served from a
bounded LRU without touching a lane.  Hit/join/miss counters surface
through the front's ``/stats``.  The raw engine default is **off** so
direct engine users (tests, benchmarks) measure real lane work.

Determinism / parity contract
-----------------------------
A request is sampled from ``jax.random.split(request_key, T)`` step keys,
with sample ``i`` drawing through ``fold_in(step_keys[t], i)`` at its step
``t`` — exactly the stream :func:`repro.core.rollout.forward_rollout`
consumes (after PR 6's hoisted :func:`repro.core.types.derive_env_keys`).
Since every per-lane operation is row-independent (per-row cache scatter,
per-row length-masked attention, per-row env dynamics), a lane replays its
trajectory bitwise regardless of which other requests share the pool,
which lane it landed on, or how the pool is sharded: engine samples for a
request equal ``forward_rollout(request_key, env, ..., num_samples)``
bit-for-bit (``tests/test_serve.py``, ``tests/test_serve_scale.py``).

Per-lane temperature
--------------------
Two knobs, both request-scoped and lane-resident:

- ``logit_temp`` scales the forward logits before sampling (a tempered
  *policy*; 1.0 multiplies through exactly, preserving parity).
- ``reward_beta`` is threaded through a :class:`RewardExponent`-style
  params layer the engine owns: the env the engine serves is wrapped so
  the β leaf is a ``(num_lanes,)`` vector and ``log_reward`` broadcasts
  per lane — requests at different reward temperatures coexist in one
  batch (Shen et al.'s tempered-sampling knob, served).

Sequence envs with the incremental-observation protocol keep PR 3's
KV-cache fast path: each lane appends its newest token's K/V at its *own*
trajectory step (a per-row scatter — see
:func:`repro.nn.transformer.cache_append`); everything else falls back to
full re-observation per step.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..core.rollout import _cache_engaged, _policy_entry
from ..core.types import pytree_dataclass, sample_masked_per_env
from ..envs.base import Environment, _select_state
from ..envs.transforms import RewardExponent, TransformedParams
from . import spans
from .errors import EngineFailure, LanePoisoned

# One process can host several sharded engines (the front runs one per
# env/checkpoint contract) whose lane pools share the same device mesh.
# Their compiled programs carry collectives (the drain psum, compaction
# gathers), and XLA:CPU's per-device worker threads deadlock if two
# collective programs are in flight at once: each parks a subset of the
# device threads at its own rendezvous, waiting forever for threads the
# other program holds.  Every sharded dispatch therefore serializes here
# and syncs before releasing; single-device engines never touch the lock.
_MESH_DISPATCH = threading.Lock()


@pytree_dataclass
class LaneState:
    """Device-resident state of the lane pool (leading dim = num_lanes).

    step_keys    (L, T, 2)  per-lane step-key table split(request_key, T)
    env_id       (L,)       sample index within the lane's request (fold_in)
    request_id   (L,)       engine-local request id; -1 = idle lane
    t            (L,)       per-lane trajectory step counter
    logit_temp   (L,)       forward-logit scale
    reward_beta  (L,)       reward exponent β (served via the params layer)
    log_r        (L,)       accumulated terminal log-reward
    """
    env_state: Any
    cache: Any
    prev_action: jax.Array
    step_keys: jax.Array
    env_id: jax.Array
    request_id: jax.Array
    t: jax.Array
    logit_temp: jax.Array
    reward_beta: jax.Array
    log_r: jax.Array


class _PendingSample(NamedTuple):
    request_id: int
    env_id: int
    step_keys: np.ndarray       # (T, 2) uint32
    logit_temp: float
    reward_beta: float


class EngineResult(NamedTuple):
    """One completed request: ``samples[i]`` is the terminal observation of
    sample ``i`` (same layout as ``RolloutBatch.obs[-1]`` rows).  ``dedup``
    marks results served from another request's computation (in-flight
    fan-out or LRU hit) — bitwise equal to computing them, by the parity
    contract."""
    request_id: int
    samples: np.ndarray         # (num_samples, ...) terminal observations
    log_rewards: np.ndarray     # (num_samples,)
    steps: np.ndarray           # (num_samples,) trajectory lengths
    latency_s: float
    dedup: bool = False


class SamplingEngine:
    """Compiled sampling service over one (env, policy params) pair.

    ``env``/``env_params`` may already carry a transform stack; the engine
    wraps one more :class:`RewardExponent` layer on top to own the per-lane
    β vector (β=1 multiplies log-rewards through exactly, so an untempered
    engine is bitwise the bare env).  ``use_cache`` as in
    :func:`repro.core.rollout.forward_rollout`.  ``plan`` shards the lane
    pool (see module docs); ``dedup_cache_size`` bounds the LRU of recent
    results duplicates are served from (0 disables dedup entirely).
    """

    def __init__(self, env: Environment, env_params, policy, policy_params,
                 *, num_lanes: int = 16,
                 use_cache: Union[bool, str] = "auto",
                 max_steps: Optional[int] = None,
                 steps_per_sync: Union[int, str] = "auto",
                 plan=None, dedup_cache_size: int = 0,
                 fault_plan=None, max_step_retries: int = 2,
                 retry_backoff_s: float = 0.02):
        from ..algo.plan import make_plan
        policy, apply_fn = _policy_entry(policy)
        self.cached = _cache_engaged(env, policy, use_cache)
        self.env = RewardExponent(env, beta=1.0)
        self.inner_params = env_params
        self.plan = make_plan(plan if plan is not None else "single")
        if self.plan.name not in ("single", "data_parallel"):
            raise ValueError(
                f"SamplingEngine supports plan 'single' or 'data_parallel', "
                f"got {self.plan.name!r} (the lane pool has no seed axis)")
        self._shards = int(getattr(self.plan, "num_shards", 1))
        self.num_lanes = L = self._round_lanes(num_lanes)
        self.T = T = int(max_steps if max_steps is not None
                         else env.max_steps)
        # how many lane transitions one compiled block advances before the
        # host looks at the pool again: larger blocks amortize dispatch +
        # host-sync cost across micro-steps (a scan inside the jit, like
        # forward_rollout's), at the price of drain/refill granularity —
        # a finished lane idles up to steps_per_sync-1 transitions before
        # the host notices.  Parity is invariant: terminal lanes no-op.
        if steps_per_sync == "auto":
            steps_per_sync = max(1, min(4, T // 2))
        self.steps_per_sync = M = max(1, int(steps_per_sync))
        self._policy, self._apply_fn = policy, apply_fn
        self._policy_params = policy_params
        self._pending: deque = deque()
        self._requests: Dict[int, dict] = {}
        self._results: Dict[int, EngineResult] = {}
        self._next_id = 0
        self._occupied = np.zeros(L, bool)
        self._undrained = None      # newly_done of the in-flight block
        self._faults = fault_plan
        self.max_step_retries = int(max_step_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self.dedup_cache_size = max(0, int(dedup_cache_size))
        self._dedup_lru: "OrderedDict[tuple, EngineResult]" = OrderedDict()
        self._dedup_inflight: Dict[tuple, int] = {}     # ckey -> primary
        self._dedup_key_of: Dict[int, tuple] = {}       # primary -> ckey
        self._dedup_waiters: Dict[int, List[int]] = {}  # primary -> rids
        #: robustness + perf counters surfaced through the front's /stats;
        #: ``device_calls`` counts the engine's calls that reach the device
        #: (:meth:`_dispatch`, :meth:`_fetch`, :meth:`_put`)
        self.counters: Dict[str, int] = {
            "requests": 0, "completed": 0, "cancelled": 0,
            "blocks": 0, "step_retries": 0, "step_failures": 0,
            "drain_skips": 0, "drain_packs": 0, "resizes": 0,
            "dedup_hits": 0, "dedup_joins": 0, "dedup_misses": 0,
            "device_calls": 0}

        env_w = self.env

        def params_with_beta(beta_vec):
            return TransformedParams(inner=env_params,
                                     extra={"beta": beta_vec})

        self._params_with_beta = params_with_beta

        def step(lane: LaneState):
            ep = params_with_beta(lane.reward_beta)
            state = lane.env_state
            active = lane.request_id >= 0
            fmask = env_w.forward_mask(state, ep)
            was_done = env_w.is_terminal(state, ep)
            live = jnp.logical_and(active, jnp.logical_not(was_done))
            # per-lane step key: the same fold_in(step_keys[t], env_id)
            # chain forward_rollout derives for its whole batch up front
            t_idx = jnp.clip(lane.t, 0, T - 1)
            key_t = jnp.take_along_axis(
                lane.step_keys, t_idx[:, None, None], axis=1)[:, 0]
            env_keys = jax.vmap(jax.random.fold_in)(key_t, lane.env_id)
            safe_mask = jnp.where(live[:, None], fmask,
                                  jnp.ones_like(fmask))
            if self.cached and policy.sample_cached is not None:
                # fused per-lane step: append + query + tempered sampling
                # as one op (per-row slot = lane.t, per-row logit_temp)
                token, pos, length = env_w.observe_last(state, ep,
                                                        lane.prev_action)
                actions, _, _, cache = policy.sample_cached(
                    policy_params, lane.cache, token, pos, length,
                    env_keys, safe_mask, step=lane.t,
                    logit_temp=lane.logit_temp)
            else:
                if self.cached:
                    token, pos, length = env_w.observe_last(
                        state, ep, lane.prev_action)
                    out, cache = policy.apply_cached(
                        policy_params, lane.cache, token, pos, length,
                        step=lane.t)
                else:
                    out = apply_fn(policy_params, env_w.observe(state, ep))
                    cache = lane.cache
                logits = out["logits"] * lane.logit_temp[:, None]
                actions, _ = sample_masked_per_env(None, logits, safe_mask,
                                                   env_keys=env_keys)
            _, nstate, log_r, done, _ = env_w.step(state, actions, ep)
            # idle lanes hold their state verbatim (env.step only no-ops
            # terminal states; an idle lane may hold an initial one)
            nstate = _select_state(jnp.logical_not(live), state, nstate)
            newly_done = jnp.logical_and(live, done)
            new_lane = LaneState(
                env_state=nstate, cache=cache,
                prev_action=jnp.where(live, actions, lane.prev_action),
                step_keys=lane.step_keys, env_id=lane.env_id,
                request_id=lane.request_id,
                t=jnp.where(live, lane.t + 1, lane.t),
                logit_temp=lane.logit_temp, reward_beta=lane.reward_beta,
                log_r=lane.log_r + jnp.where(live, log_r, 0.0))
            return new_lane, newly_done

        def refill(lane: LaneState, mask, step_keys, env_id, request_id,
                   logit_temp, reward_beta):
            """Reset the lanes under ``mask`` to fresh request state; all
            shapes are static, so refills never recompile.  Fresh lanes take
            a brand-new reset state and cache row — nothing of the previous
            occupant survives.  Lane count comes from the *argument* shapes
            (the per-shard count under shard_map, the pool size otherwise),
            so the same closure serves every pool size and shard count."""
            Lb = mask.shape[0]
            ep = params_with_beta(lane.reward_beta)
            _, state0 = env_w.reset(Lb, ep)
            sel = lambda a, b: jnp.where(
                mask.reshape(mask.shape + (1,) * (a.ndim - 1)), a, b)
            env_state = jax.tree_util.tree_map(sel, state0, lane.env_state)
            if self.cached:
                # cache leaves are stacked (num_layers, B, ...) — the lane
                # axis is axis 1, not the leading axis env-state leaves use
                cache0 = policy.cache_init(policy_params, Lb)
                sel_row = lambda a, b: jnp.where(
                    mask.reshape((1, Lb) + (1,) * (a.ndim - 2)), a, b)
                cache = jax.tree_util.tree_map(sel_row, cache0, lane.cache)
            else:
                cache = lane.cache
            w = lambda a, b: jnp.where(mask, a, b)
            return LaneState(
                env_state=env_state, cache=cache,
                prev_action=w(jnp.zeros((Lb,), jnp.int32),
                              lane.prev_action),
                step_keys=jnp.where(mask[:, None, None], step_keys,
                                    lane.step_keys),
                env_id=w(env_id, lane.env_id),
                request_id=w(request_id, lane.request_id),
                t=w(jnp.zeros((Lb,), jnp.int32), lane.t),
                logit_temp=w(logit_temp, lane.logit_temp),
                reward_beta=w(reward_beta, lane.reward_beta),
                log_r=w(jnp.zeros((Lb,), jnp.float32), lane.log_r))

        def block(lane: LaneState):
            lane, nds = jax.lax.scan(lambda l, _: step(l), lane, None,
                                     length=M)
            # a lane finishes at most once per occupancy (live goes False
            # at its terminal micro-step), so OR-ing over the block is the
            # exact set of lanes that completed since the last sync.  The
            # done *count* is computed here, inside the block's dispatch,
            # so the host's per-block drain cost is one scalar readback —
            # no extra device round-trip just to learn "nothing finished"
            nd = jnp.any(nds, axis=0)
            return lane, nd, jnp.sum(nd.astype(jnp.int32))

        def pack(lane: LaneState, newly_done):
            # compiled drain compaction: done lanes first (stable, so lane
            # order is preserved within each group); the host then fetches
            # only the leading `count` rows of each output
            order = jnp.argsort(jnp.logical_not(newly_done)).astype(
                jnp.int32)
            obs = env_w.observe(lane.env_state,
                                params_with_beta(lane.reward_beta))
            take = lambda a: jnp.take(a, order, axis=0)
            return (order, take(obs), take(lane.log_r),
                    take(lane.request_id), take(lane.env_id), take(lane.t))

        if self._shards > 1:
            from ..distributed.sharding import lane_state_specs
            mesh, axis = self.plan.mesh, self.plan.axis
            specs = lane_state_specs(axis)
            lane_sp = P(axis)

            def block_psum(lane: LaneState):
                lane, nd, cnt = block(lane)
                # per-shard partial counts -> one replicated global scalar
                return lane, nd, jax.lax.psum(cnt, axis)

            # check_vma=False: every op is row-local; there is nothing
            # replicated to verify and the check defeats prefix specs
            self._jstep = jax.jit(shard_map(
                block_psum, mesh=mesh, in_specs=(specs,),
                out_specs=(specs, lane_sp, P()), check_vma=False))
            self._jrefill = jax.jit(shard_map(
                refill, mesh=mesh, in_specs=(specs,) + (lane_sp,) * 6,
                out_specs=specs, check_vma=False))
        else:
            self._jstep = jax.jit(block)
            self._jrefill = jax.jit(refill)
        # drain helpers are plain jits: on a sharded pool GSPMD partitions
        # the count and gathers the (rare) compaction
        self._jcount = jax.jit(
            lambda nd: jnp.sum(nd.astype(jnp.int32)))
        self._jpack = jax.jit(pack)
        self._jobserve = jax.jit(
            lambda lane: env_w.observe(
                lane.env_state, params_with_beta(lane.reward_beta)))
        self._jreassign = jax.jit(
            lambda lane, old, new: dataclasses.replace(
                lane, request_id=jnp.where(lane.request_id == old, new,
                                           lane.request_id)))

        self.lane = self._init_lane(L)

    # -- lane pool construction / sizing -------------------------------------
    def _round_lanes(self, n: int) -> int:
        """Round a lane count up to a multiple of the shard count (each
        shard owns a static-shape slice of the pool)."""
        n = max(1, int(n))
        d = self._shards
        return ((n + d - 1) // d) * d

    def _init_lane(self, L: int) -> LaneState:
        _, state0 = self.env.reset(L, self._params_with_beta(jnp.ones(L)))
        cache0 = (self._policy.cache_init(self._policy_params, L)
                  if self.cached else ())
        return LaneState(
            env_state=state0, cache=cache0,
            prev_action=jnp.zeros((L,), jnp.int32),
            step_keys=jnp.zeros((L, self.T, 2), jnp.uint32),
            env_id=jnp.zeros((L,), jnp.int32),
            request_id=jnp.full((L,), -1, jnp.int32),
            t=jnp.zeros((L,), jnp.int32),
            logit_temp=jnp.ones((L,), jnp.float32),
            reward_beta=jnp.ones((L,), jnp.float32),
            log_r=jnp.zeros((L,), jnp.float32))

    def _dispatch(self, fn, *args):
        """Execute a compiled entry point against the lane pool.

        Single-device pools call straight through — dispatch stays async,
        which the lean drain's block-overlap depends on.  Sharded pools
        take the process-wide :data:`_MESH_DISPATCH` lock and block until
        the program completes before releasing it, so at most one
        collective program is ever in flight (see the lock's comment for
        the deadlock this prevents).  The forced sync costs nothing in
        that regime: the virtual devices time-slice the same host, so
        there is no cross-program compute overlap to preserve.
        Counts one device call.
        """
        self.counters["device_calls"] += 1
        if self._shards == 1:
            return fn(*args)
        with _MESH_DISPATCH:
            out = fn(*args)
            jax.block_until_ready(out)
        return out

    def _fetch(self, x, rows: Optional[int] = None) -> np.ndarray:
        """Copy a device array, or its first ``rows`` rows, to the host:
        one device call for the transfer and one for the slice."""
        if rows is not None:
            self.counters["device_calls"] += 1
            x = x[:rows]
        self.counters["device_calls"] += 1
        return np.asarray(x)

    def _put(self, x: np.ndarray) -> jax.Array:
        """Copy a host array to the device: one device call."""
        self.counters["device_calls"] += 1
        return jnp.asarray(x)

    def resize(self, num_lanes: int) -> bool:
        """Rebuild the lane pool at a new size between requests.  Returns
        whether the size actually changed (the requested count is rounded
        to a shard multiple).  The pending queue, dedup cache, and results
        survive — the parity contract is lane-count-invariant — but the
        pool must be idle: raises :class:`EngineFailure` if any lane is
        occupied.  The compiled closures are shape-polymorphic, so each
        distinct size compiles once and is cached by jit thereafter
        (:meth:`prewarm` pays those compiles up front)."""
        L = self._round_lanes(num_lanes)
        if L == self.num_lanes:
            return False
        self._drain_pending()
        if self._occupied.any():
            raise EngineFailure(
                "cannot resize a lane pool with occupied lanes")
        self.num_lanes = L
        self.lane = self._init_lane(L)
        self._occupied = np.zeros(L, bool)
        self.counters["resizes"] += 1
        return True

    def prewarm(self, sizes) -> None:
        """Compile step/refill/drain at each lane-pool size (rounded to
        shard multiples), then restore the current size.  Call at startup
        so autosizing between the given buckets never pays XLA mid-serve."""
        orig = self.num_lanes
        for L in sorted({self._round_lanes(s) for s in sizes}):
            self.resize(L)
            lane, nd, _ = self._dispatch(self._jstep, self.lane)
            packed = self._dispatch(self._jpack, lane, nd)
            self._dispatch(self._jcount, nd)
            self._dispatch(self._jrefill, lane, jnp.zeros((L,), bool),
                           jnp.zeros((L, self.T, 2), jnp.uint32),
                           jnp.zeros((L,), jnp.int32),
                           jnp.full((L,), -1, jnp.int32),
                           jnp.ones((L,), jnp.float32),
                           jnp.ones((L,), jnp.float32))
            jax.block_until_ready(packed)
        self.resize(orig)

    # -- request intake ------------------------------------------------------
    def submit(self, *, num_samples: int = 1, seed: int = 0,
               key: Optional[jax.Array] = None, logit_temp: float = 1.0,
               reward_beta: float = 1.0) -> int:
        """Queue a request for ``num_samples`` trajectories; returns its
        engine-local request id.  ``key`` (or ``PRNGKey(seed)``) is the
        request key of the parity contract: sample ``i`` reproduces
        ``forward_rollout(key, ...)`` trajectory ``i`` when
        ``logit_temp == reward_beta == 1``.

        With dedup enabled, a request identical to one in flight joins it
        as a waiter (one computation, fanned out on completion) and a
        request identical to a recently-completed one is answered from the
        LRU without touching a lane — either way the returned id resolves
        through :meth:`take_results` exactly like a computed one."""
        if num_samples < 1:
            raise ValueError(f"num_samples must be >= 1, got {num_samples}")
        rid = self._next_id
        self._next_id += 1
        if key is None:
            key = self._dispatch(jax.random.PRNGKey, seed)
        step_keys = self._fetch(
            self._dispatch(jax.random.split, key, self.T)).astype(np.uint32)
        self.counters["requests"] += 1
        if self.dedup_cache_size:
            # everything request-scoped in the parity contract; the engine
            # itself pins (env, transforms, checkpoint step)
            ckey = (step_keys.tobytes(), int(num_samples),
                    float(logit_temp), float(reward_beta))
            hit = self._dedup_lru.get(ckey)
            if hit is not None:
                self._dedup_lru.move_to_end(ckey)
                self.counters["dedup_hits"] += 1
                self.counters["completed"] += 1
                self._results[rid] = hit._replace(
                    request_id=rid, latency_s=0.0, dedup=True)
                return rid
            prim = self._dedup_inflight.get(ckey)
            if prim is not None and prim in self._requests:
                self.counters["dedup_joins"] += 1
                self._dedup_waiters.setdefault(prim, []).append(rid)
                return rid
            self.counters["dedup_misses"] += 1
            self._dedup_inflight[ckey] = rid
            self._dedup_key_of[rid] = ckey
        for i in range(num_samples):
            self._pending.append(_PendingSample(rid, i, step_keys,
                                                float(logit_temp),
                                                float(reward_beta)))
        self._requests[rid] = {"num_samples": int(num_samples),
                               "collected": {},
                               "t0": time.perf_counter()}
        return rid

    # -- lane pool management ------------------------------------------------
    def _fill(self) -> None:
        if not self._pending:
            return
        free = np.nonzero(~self._occupied)[0]
        if free.size == 0:
            return
        with spans.span("serve.refill") as sp:
            L, T = self.num_lanes, self.T
            mask = np.zeros(L, bool)
            step_keys = np.zeros((L, T, 2), np.uint32)
            env_id = np.zeros(L, np.int32)
            request_id = np.zeros(L, np.int32)
            logit_temp = np.ones(L, np.float32)
            reward_beta = np.ones(L, np.float32)
            for b in free:
                if not self._pending:
                    break
                s = self._pending.popleft()
                mask[b] = True
                step_keys[b] = s.step_keys
                env_id[b] = s.env_id
                request_id[b] = s.request_id
                logit_temp[b] = s.logit_temp
                reward_beta[b] = s.reward_beta
                self._occupied[b] = True
            self.lane = self._dispatch(
                self._jrefill, self.lane, *map(self._put, (
                    mask, step_keys, env_id, request_id, logit_temp,
                    reward_beta)))
            if sp:
                sp.set(filled=int(mask.sum()))

    def _drain_pending(self) -> int:
        """Drain the completions of the last dispatched block against the
        current lane pool.  Terminal lanes hold their state verbatim
        through subsequent blocks, so draining one block late is exact —
        and lets the host overlap this sync with device compute.  Costs a
        single scalar fetch when nothing finished; otherwise a compiled
        compaction and exactly ``count`` rows of host transfer."""
        und = self._undrained
        if und is None:
            return 0
        self._undrained = None
        nd, cnt = und
        # the count was computed inside the block's own dispatch; reading
        # it back is the drain's entire cost when nothing finished
        with spans.span("serve.sync"):
            count = int(self._fetch(cnt))
        if count == 0:
            self.counters["drain_skips"] += 1
            return 0
        self.counters["drain_packs"] += 1
        with spans.span("serve.fetch", rows=count):
            return self._collect(count, nd)

    def _collect(self, k: int, nd) -> int:
        """Fetch the ``k`` finished rows of the pool (compacted first by
        ``_jpack``) and fold them into their requests."""
        packed = self._dispatch(self._jpack, self.lane, nd)
        order, obs, log_r, rid, eid, steps = (self._fetch(x, k)
                                              for x in packed)
        rows = []
        for i in range(k):
            b, r = int(order[i]), int(rid[i])
            if r < 0 or r not in self._requests:
                # cancelled (and possibly reset to idle) between the block
                # dispatch and this drain — nothing to collect
                self._occupied[b] = False
                continue
            rows.append((i, b, r))
        # drain-time validation: a finished lane must carry a finite
        # log-reward and a trajectory length the env can actually produce.
        # Anything else means device state was corrupted (a lane_state
        # fault, or a real bug) — surface it as a typed LanePoisoned so the
        # front quarantines this engine and replays its requests, instead
        # of silently returning garbage samples.
        bad = [(i, b, r) for i, b, r in rows
               if not np.isfinite(log_r[i]) or not 1 <= steps[i] <= self.T]
        if bad:
            raise LanePoisoned(
                f"drained lane(s) {[b for _, b, _ in bad]} carry malformed "
                f"state (log_r={[float(log_r[i]) for i, _, _ in bad]}, "
                f"steps={[int(steps[i]) for i, _, _ in bad]})",
                extra={"lanes": [b for _, b, _ in bad],
                       "request_ids": [r for _, _, r in bad]})
        now = time.perf_counter()
        for i, b, r in rows:
            req = self._requests[r]
            req["collected"][int(eid[i])] = (obs[i], float(log_r[i]),
                                             int(steps[i]))
            self._occupied[b] = False
            if len(req["collected"]) == req["num_samples"]:
                got = [req["collected"][j]
                       for j in range(req["num_samples"])]
                res = EngineResult(
                    request_id=r,
                    samples=np.stack([g[0] for g in got]),
                    log_rewards=np.asarray([g[1] for g in got],
                                           np.float32),
                    steps=np.asarray([g[2] for g in got], np.int32),
                    latency_s=now - req["t0"])
                self._requests.pop(r)
                self._results[r] = res
                self.counters["completed"] += 1
                self._dedup_complete(r, res)
        return k

    def _dedup_complete(self, rid: int, res: EngineResult) -> None:
        """Fan a primary's result out to its waiters and publish it to the
        LRU so future duplicates skip the lanes entirely."""
        ckey = self._dedup_key_of.pop(rid, None)
        if ckey is None:
            return
        if self._dedup_inflight.get(ckey) == rid:
            del self._dedup_inflight[ckey]
        for w in self._dedup_waiters.pop(rid, []):
            self._results[w] = res._replace(request_id=w, dedup=True)
            self.counters["completed"] += 1
        self._dedup_lru[ckey] = res
        self._dedup_lru.move_to_end(ckey)
        while len(self._dedup_lru) > self.dedup_cache_size:
            self._dedup_lru.popitem(last=False)

    def _poison_occupied_lanes(self) -> None:
        """lane_state fault: overwrite every occupied lane's accumulated
        log-reward with NaN — malformed device state that drain-time
        validation must catch as :class:`LanePoisoned`."""
        occ = jnp.asarray(self._occupied)
        self.lane = dataclasses.replace(
            self.lane, log_r=jnp.where(occ, jnp.nan, self.lane.log_r))

    # -- drive ---------------------------------------------------------------
    def step(self) -> int:
        """Drain the previous block's completions, refill free lanes, and
        dispatch the next compiled block (``steps_per_sync`` transitions)
        without waiting for it; returns how many lanes the drain freed.

        The one-block drain lag means a request's completion is observed
        on the step call *after* its terminal block — the host-side price
        of never blocking on the in-flight block.  When the pool is empty
        after draining (and nothing is pending) no block is dispatched, so
        idle steps cost one scalar sync at most.

        Transient step failures (injected or real) are retried with
        exponential backoff up to ``max_step_retries`` times — the jitted
        step is a pure function of the lane state, so a retry replays the
        block bitwise.  Exhausted retries raise a typed
        :class:`EngineFailure`; malformed drained lanes raise
        :class:`LanePoisoned` (no retry — device state is already bad).
        Either way the caller should treat this engine as quarantined.
        """
        finished = self._drain_pending()
        self._fill()
        if not self._occupied.any():
            return finished
        attempt = 0
        while True:
            try:
                if self._faults is not None:
                    for f in self._faults.fires("latency"):
                        time.sleep(f.latency_s)
                    if self._faults.fires("lane_state"):
                        self._poison_occupied_lanes()
                    self._faults.maybe_raise("engine_step")
                with spans.span("serve.dispatch",
                                lanes=self.num_lanes) as sp:
                    lane, newly_done, cnt = self._dispatch(self._jstep,
                                                           self.lane)
                    if sp:
                        sp.set(lanes_busy=int(self._occupied.sum()))
                break
            except Exception as e:
                attempt += 1
                self.counters["step_retries"] += 1
                if attempt > self.max_step_retries:
                    self.counters["step_failures"] += 1
                    raise EngineFailure(
                        f"engine step failed after {attempt} attempts "
                        f"({type(e).__name__}: {e})") from e
                time.sleep(self.retry_backoff_s * (2 ** (attempt - 1)))
        self.lane = lane
        self._undrained = (newly_done, cnt)
        self.counters["blocks"] += 1
        return finished

    # -- robustness surface (used by repro.serve.front) -----------------------
    @property
    def has_work(self) -> bool:
        return bool(self._pending) or bool(self._occupied.any())

    @property
    def has_results(self) -> bool:
        """Completed results awaiting :meth:`take_results` — may be
        non-empty with no work at all (dedup LRU hits)."""
        return bool(self._results)

    @property
    def occupancy(self) -> float:
        """Fraction of lanes currently running a sample."""
        return float(self._occupied.mean()) if self.num_lanes else 0.0

    def take_results(self) -> Dict[int, EngineResult]:
        """Return (and clear) the completed results so far — the
        incremental-drive counterpart of :meth:`run`'s final handoff."""
        out, self._results = self._results, {}
        return out

    def progress(self, rid: int) -> Dict[str, int]:
        """Partial-progress snapshot of an in-flight request."""
        req = self._requests.get(rid)
        if req is None:
            done = rid in self._results
            return {"collected": (self._results[rid].samples.shape[0]
                                  if done else 0),
                    "num_samples": (self._results[rid].samples.shape[0]
                                    if done else 0),
                    "complete": done}
        lanes = int(((np.asarray(self.lane.request_id) == rid)
                     & self._occupied).sum())
        return {"collected": len(req["collected"]),
                "num_samples": req["num_samples"],
                "lanes_in_flight": lanes, "complete": False}

    def cancel(self, rid: int) -> Dict[str, int]:
        """Abort an in-flight request: drop its queued samples, reset (and
        free) its lanes, forget its partial results.  Returns the partial
        progress it had made — the 504 response's metadata.  Cancelling an
        unknown/completed request is a no-op returning zeros.

        Dedup'd requests never waste the shared computation: cancelling a
        waiter just detaches it, and cancelling a primary with waiters
        *promotes* the first waiter to primary — the in-flight lanes are
        reassigned on device and keep running for the survivors."""
        # waiter: the computation belongs to the primary and keeps running
        for prim, ws in list(self._dedup_waiters.items()):
            if rid in ws:
                ws.remove(rid)
                if not ws:
                    del self._dedup_waiters[prim]
                self.counters["cancelled"] += 1
                req = self._requests.get(prim)
                return {"collected": 0,
                        "num_samples": (req["num_samples"] if req else 0),
                        "lanes_freed": 0, "pending_removed": 0}
        # primary with waiters: hand the computation over
        ws = self._dedup_waiters.pop(rid, None)
        if ws:
            new = ws.pop(0)
            if ws:
                self._dedup_waiters[new] = ws
            ckey = self._dedup_key_of.pop(rid, None)
            if ckey is not None:
                self._dedup_key_of[new] = ckey
                self._dedup_inflight[ckey] = new
            req = self._requests.pop(rid)
            self._requests[new] = req
            if any(s.request_id == rid for s in self._pending):
                self._pending = deque(
                    s._replace(request_id=new) if s.request_id == rid
                    else s for s in self._pending)
            if ((self._fetch(self.lane.request_id) == rid)
                    & self._occupied).any():
                self.lane = self._dispatch(self._jreassign, self.lane,
                                           rid, new)
            self.counters["cancelled"] += 1
            return {"collected": len(req["collected"]),
                    "num_samples": req["num_samples"],
                    "lanes_freed": 0, "pending_removed": 0}
        before = len(self._pending)
        self._pending = deque(s for s in self._pending
                              if s.request_id != rid)
        removed = before - len(self._pending)
        mask = (self._fetch(self.lane.request_id) == rid) & self._occupied
        lanes_freed = int(mask.sum())
        if lanes_freed:
            L, T = self.num_lanes, self.T
            # _jrefill with request_id=-1 resets the lanes to pristine idle
            # state (fresh env state + cache rows), so the pool stays
            # healthy — nothing of the cancelled occupant survives
            self.lane = self._dispatch(
                self._jrefill, self.lane, *map(self._put, (
                    mask, np.zeros((L, T, 2), np.uint32),
                    np.zeros(L, np.int32), np.full(L, -1, np.int32),
                    np.ones(L, np.float32), np.ones(L, np.float32))))
            self._occupied[mask] = False
        req = self._requests.pop(rid, None)
        if req is not None:
            self.counters["cancelled"] += 1
            ckey = self._dedup_key_of.pop(rid, None)
            if ckey is not None and self._dedup_inflight.get(ckey) == rid:
                del self._dedup_inflight[ckey]
        return {"collected": len(req["collected"]) if req else 0,
                "num_samples": req["num_samples"] if req else 0,
                "lanes_freed": lanes_freed, "pending_removed": removed}

    def run(self) -> Dict[int, EngineResult]:
        """Drive until every submitted request has completed; returns (and
        clears) the finished :class:`EngineResult`\\ s keyed by request id."""
        budget = (len(self._pending) + int(self._occupied.sum())) \
            * (self.T + self.steps_per_sync) + self.T \
            + 2 * self.steps_per_sync
        while self._pending or self._occupied.any():
            self.step()
            budget -= self.steps_per_sync
            if budget < 0:
                raise EngineFailure(
                    "engine failed to drain its lane pool within the "
                    "worst-case step budget — an env whose trajectories "
                    "exceed max_steps?")
        return self.take_results()
