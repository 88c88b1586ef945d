"""Device-mesh execution plans: where (and how many times) a train step runs.

The paper's headline claim is *scale*, but a single ``vmap_seeds`` axis tops
out at one chip.  An :class:`ExecutionPlan` makes the device layout a
first-class, composable property of a :class:`repro.algo.TrainLoop`:

    single                 one device, the seed trainer's behavior (default)
    vmap_seeds(S)          S independent training runs vmapped on one device
    data_parallel(D)       rollouts + objectives shard_map'ped over a
                           ``(D,)`` device mesh along the batch axis
    seeds_x_data(S, D)     their composition: every device carries all S
                           seeds' shard of the batch (vmap inside shard_map)

The plan owns the three things that differ across layouts:

- **mesh construction** (backed by :func:`repro.launch.mesh.make_mesh`) and
  the in/out PartitionSpecs of one training step (backed by
  :func:`repro.distributed.sharding.rollout_batch_specs`);
- **RNG splitting**: the training key stays replicated and every rollout
  draw is keyed per *global* env id (``sample_masked_per_env``), so a
  ``data_parallel`` run samples bit-identical trajectories to a ``single``
  run of the same global batch — sharding is a pure execution detail;
- **state layout**: sampler state (e.g. replay buffers) lives *per shard* —
  a leading device axis sharded over the mesh, no cross-device gathers on
  the hot path — while params/optimizer state stay replicated and gradients
  and the loss reduce via ``lax.psum`` of (sum, weight) objective parts
  inside the step, so updates are bitwise-deterministic for a fixed mesh.

EvalSuite hooks run *outside* the shard_map on the replicated params, so
metric rows stay identical to single-device runs.

On CPU the whole path is exercised with virtual devices::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python -m repro.run --recipe hypergrid_tb --plan data_parallel
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.types import replace
from ..launch.mesh import make_mesh


class ShardInfo:
    """How one training step sees the mesh from inside the compiled step.

    Samplers consume this to size their per-shard work: ``split_batch``
    turns a global batch into the per-shard slice, ``env_offset`` is the
    global index of the shard's first environment (a traced
    ``lax.axis_index`` under ``data_parallel``, the constant 0 otherwise) —
    exactly what :func:`repro.core.rollout.forward_rollout` needs to keep
    per-env random streams identical to a single-device run.
    """

    def __init__(self, axis: Optional[str] = None, num_shards: int = 1):
        self.axis = axis
        self.num_shards = num_shards

    def split_batch(self, global_batch: int) -> int:
        if self.num_shards == 1:
            return global_batch
        if global_batch % self.num_shards:
            raise ValueError(
                f"global batch {global_batch} is not divisible by the "
                f"{self.num_shards}-shard mesh axis {self.axis!r}; pick a "
                "batch size that is a multiple of the device count")
        return global_batch // self.num_shards

    def env_offset(self, local_batch: int) -> Union[int, jax.Array]:
        if self.axis is None:
            return 0
        return jax.lax.axis_index(self.axis) * local_batch

    def fold_shard(self, key: jax.Array) -> jax.Array:
        """Decorrelate a per-step key across shards (replay selection etc.;
        anything that must NOT be identical on every shard)."""
        if self.axis is None:
            return key
        return jax.random.fold_in(key, jax.lax.axis_index(self.axis))

    def psum(self, tree):
        if self.axis is None:
            return tree
        return jax.lax.psum(tree, self.axis)

    def pmean(self, tree):
        if self.axis is None:
            return tree
        return jax.lax.pmean(tree, self.axis)


class ExecutionPlan:
    """Single-device plan — the identity layout (and the base class).

    A plan exposes:

    ``shard_info()``            how samplers should slice the batch
    ``wrap_step(core)``         turn ``core(train, sampler)`` into
                                ``step(LoopState) -> (LoopState, aux)``
    ``prepare_state(state)``    add/shard the per-device state axes
    ``describe()``              plan/device metadata for perf rows & logs
    ``seeds``                   seed-axis size (None = no seed axis)
    """

    name = "single"
    seeds: Optional[int] = None

    def shard_info(self) -> ShardInfo:
        return ShardInfo()

    @property
    def device_count(self) -> int:
        return 1

    @property
    def num_shards(self) -> int:
        return 1

    @property
    def mesh_shape(self) -> Optional[Tuple[int, ...]]:
        return None

    def prepare_state(self, state):
        return state

    def wrap_step(self, core):
        def step_fn(state):
            (train, sampler), out = core(state.train, state.sampler)
            return replace(state, train=train, sampler=sampler), out
        return step_fn

    def describe(self) -> dict:
        """Provenance fields for perf rows — splat into
        :func:`benchmarks.common.row` (keys match its named params)."""
        return {"plan": self.name, "device_count": self.device_count,
                "mesh_shape": (list(self.mesh_shape)
                               if self.mesh_shape else None)}

    def __repr__(self):
        args = ", ".join(f"{k}={v!r}"
                         for k, v in dict(self.describe(),
                                          num_seeds=self.seeds).items()
                         if k != "plan" and v not in (None, 1))
        return f"{type(self).__name__}({args})"


class VmapSeedsPlan(ExecutionPlan):
    """S independent training runs, one device: the step is vmapped over a
    leading seed axis on every carried leaf (the paper's "trainer
    vectorization" future-work item, now one plan among equals)."""

    name = "vmap_seeds"

    def __init__(self, num_seeds: int):
        if not num_seeds or num_seeds < 1:
            raise ValueError(f"vmap_seeds needs num_seeds >= 1, "
                             f"got {num_seeds!r}")
        self.seeds = int(num_seeds)

    def wrap_step(self, core):
        vcore = jax.vmap(core)

        def step_fn(state):
            (train, sampler), out = vcore(state.train, state.sampler)
            return replace(state, train=train, sampler=sampler), out
        return step_fn


class DataParallelPlan(ExecutionPlan):
    """Shard the batch axis over a ``(D,)`` device mesh with ``shard_map``.

    Inside the step every shard rolls out its slice of the global batch
    (per-shard env stepping, per-shard replay buffers), computes the
    objective's local ``(sum, weight)`` parts and their gradient, and the
    plan ``psum``s those — no cross-device gather of trajectories ever
    happens.  Params/optimizer state are replicated; with psum'd gradients
    every device applies the identical update, so training is
    bitwise-deterministic for a fixed mesh and matches the single-device
    run up to float reassociation of the batch reduction.
    """

    name = "data_parallel"

    def __init__(self, num_devices: Optional[int] = None, mesh=None,
                 axis: str = "batch"):
        self.axis = axis
        self._mesh = mesh
        self._num_devices = num_devices
        if mesh is not None and axis not in mesh.axis_names:
            raise ValueError(f"mesh {mesh} has no axis {axis!r}")

    @property
    def mesh(self):
        if self._mesh is None:
            n = self._num_devices or jax.device_count()
            self._mesh = make_mesh((n,), (self.axis,))
        return self._mesh

    @property
    def num_shards(self) -> int:
        return self.mesh.shape[self.axis]

    @property
    def device_count(self) -> int:
        return self.mesh.devices.size

    @property
    def mesh_shape(self) -> Tuple[int, ...]:
        return tuple(self.mesh.devices.shape)

    def shard_info(self) -> ShardInfo:
        return ShardInfo(axis=self.axis, num_shards=self.num_shards)

    def _seed_axes(self) -> int:
        return 0

    def _vmap_core(self, core):
        return core

    def prepare_state(self, state):
        """Stack one identical copy of the sampler state per shard (leading
        device axis, sharded over the mesh) and commit the replicated parts
        so the first step doesn't pay a surprise resharding."""
        D = self.num_shards
        sampler = jax.tree_util.tree_map(
            lambda x: jnp.stack([x] * D), state.sampler)
        sampler = jax.device_put(
            sampler, NamedSharding(self.mesh, P(self.axis)))
        train = jax.device_put(state.train, NamedSharding(self.mesh, P()))
        return replace(state, train=train, sampler=sampler)

    def wrap_step(self, core):
        from ..distributed.sharding import rollout_batch_specs
        mesh, axis = self.mesh, self.axis
        vcore = self._vmap_core(core)
        batch_specs = rollout_batch_specs(axis, lead=self._seed_axes())
        samp_spec = P(axis)

        def local_fn(train, samp_block):
            # drop the per-shard block dim (D,...)->(1,...)->(...) in, undo out
            samp = jax.tree_util.tree_map(lambda x: x[0], samp_block)
            (train, samp), (metrics, batch) = vcore(train, samp)
            samp = jax.tree_util.tree_map(lambda x: x[None], samp)
            return (train, samp), (metrics, batch)

        sharded = shard_map(
            local_fn, mesh=mesh,
            in_specs=(P(), samp_spec),
            out_specs=((P(), samp_spec), (P(), batch_specs)),
            check_vma=False)

        def step_fn(state):
            (train, sampler), out = sharded(state.train, state.sampler)
            return replace(state, train=train, sampler=sampler), out
        return step_fn


class SeedsByDataPlan(DataParallelPlan):
    """``seeds x data``: every device holds its batch shard of all S seeds.

    Composition is vmap *inside* shard_map — the per-shard step is vmapped
    over the seed axis, so seed parallelism costs no extra devices and the
    per-seed psum'd reductions stay independent (``lax.psum`` over the mesh
    axis maps through ``vmap``).
    """

    name = "seeds_x_data"

    def __init__(self, num_seeds: int, num_devices: Optional[int] = None,
                 mesh=None, axis: str = "batch"):
        super().__init__(num_devices=num_devices, mesh=mesh, axis=axis)
        if not num_seeds or num_seeds < 1:
            raise ValueError(f"seeds_x_data needs num_seeds >= 1, "
                             f"got {num_seeds!r}")
        self.seeds = int(num_seeds)

    def _seed_axes(self) -> int:
        return 1

    def _vmap_core(self, core):
        return jax.vmap(core)


PLANS = {
    cls.name: cls for cls in (ExecutionPlan, VmapSeedsPlan,
                              DataParallelPlan, SeedsByDataPlan)
}


def make_plan(spec=None, *, devices: Optional[int] = None,
              num_seeds: Optional[int] = None,
              num_envs: Optional[int] = None) -> ExecutionPlan:
    """Coerce a plan spec (instance or name) into an :class:`ExecutionPlan`.

    Names: ``single`` | ``vmap_seeds`` | ``data_parallel`` |
    ``seeds_x_data`` | ``auto`` (data_parallel over all visible devices
    when there is more than one — with a fallback to single when
    ``num_envs`` is given and doesn't shard evenly, see
    :func:`auto_plan`).
    """
    if spec is None:
        spec = "single"
    if isinstance(spec, ExecutionPlan):
        return spec
    if spec == "auto":
        if num_seeds is not None:
            raise ValueError(
                "plan 'auto' never adds a seed axis; pick 'vmap_seeds' or "
                "'seeds_x_data' explicitly when passing num_seeds")
        if num_envs is not None:
            return auto_plan(num_envs, devices)
        n = devices or jax.device_count()
        if n > 1:
            return DataParallelPlan(num_devices=n)
        return ExecutionPlan()
    if spec == "single":
        return ExecutionPlan()
    if spec == "vmap_seeds":
        return VmapSeedsPlan(num_seeds)
    if spec == "data_parallel":
        return DataParallelPlan(num_devices=devices)
    if spec == "seeds_x_data":
        return SeedsByDataPlan(num_seeds, num_devices=devices)
    raise KeyError(f"unknown plan {spec!r}; "
                   f"available: {sorted(PLANS)} + 'auto'")


def auto_plan(num_envs: int, devices: Optional[int] = None) -> ExecutionPlan:
    """``auto`` with a divisibility guard: data_parallel over the visible
    devices when the global batch shards evenly, else single.  The guard
    only inspects ``num_envs`` — sampler-level constraints (a replay
    capacity or ``replay_batch`` that doesn't divide by the shard count)
    still raise at ``TrainLoop`` construction with a pointed message."""
    n = devices or jax.device_count()
    if n > 1 and num_envs % n == 0:
        return DataParallelPlan(num_devices=n)
    return ExecutionPlan()
