"""ZeRO stage-1: reduce-scattered gradients, sharded optimizer state.

Every rank in plain data parallelism holds the full optimizer state and
redundantly applies the full update. ZeRO-1 (Rajbhandari et al., 2020,
"ZeRO: Memory Optimizations Toward Training Trillion Parameter Models")
keeps the wire bytes of a bandwidth-optimal allreduce — which is a
reduce-scatter plus an all-gather — but inserts the optimizer between the
halves: reduce-scatter the gradients, update only this rank's 1/N shard of
the optimizer state, all-gather the updated shard. Optimizer compute and
optimizer-state memory shrink by ~1/N per device; parameters stay
replicated (stage 1 only).

The partition is defined by ``ops.fusion.BucketSchedule``: gradients are
packed into reverse-traversal buckets, each padded to a multiple of the
world size, and rank ``r`` owns flat chunk ``r`` of every bucket (the same
chunk the schedule's reduce-scatter deposits on it). Optimizer state is
stored per bucket as a ``[world, shard]`` array sharded over the scatter
axes, so the N-way partition is visible to jax as a real sharding — each
device materializes 1/N of the bytes.

Works with any elementwise ``optax`` transformation (sgd, momentum, adam,
adamw — anything whose update for element ``i`` depends only on
gradient/param/state element ``i``). Transformations that take global
norms across the whole pytree (clip_by_global_norm) would compute
shard-local norms here; compose those INSIDE the model's loss or before
``DistributedOptimizer`` instead.
"""

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops import collective, fusion
from horovod_tpu.ops.reduction import Average, Sum
from horovod_tpu.telemetry import scopes


@dataclasses.dataclass(frozen=True)
class ZeroPlan:
    """Static description of the optimizer-state partition: the bucket
    schedule (which flat ranges exist and who owns which chunk) plus the
    reduction op. Hashable — it rides as pytree aux data on
    :class:`ZeroState` so the partition travels with the state through
    jit/shard_map without retracing surprises."""

    schedule: fusion.BucketSchedule
    op: str = Average


class ZeroState:
    """Sharded optimizer state: ``inner`` is the wrapped optax state over
    the bucket-row pytree ``{"b0": [world, shard0], ...}``; ``plan`` is the
    static partition. Registered as a pytree node with ``plan`` as aux so
    tree_map/jit see only the arrays."""

    def __init__(self, inner: Any, plan: ZeroPlan):
        self.inner = inner
        self.plan = plan

    def tree_flatten(self):
        return ((self.inner,), self.plan)

    @classmethod
    def tree_unflatten(cls, plan, children):
        return cls(children[0], plan)

    def __repr__(self):
        return f"ZeroState(buckets={len(self.plan.schedule.buckets)})"


jax.tree_util.register_pytree_node(
    ZeroState, ZeroState.tree_flatten, ZeroState.tree_unflatten)


def _register_flax_serialization():
    """Make ZeroState round-trip through ``checkpoint.py`` (flax msgpack
    only serializes types it knows): the state dict carries the inner
    leaves; the static plan is NOT serialized — it is rebuilt from the
    live target's plan on restore, which is exactly the checkpoint
    module's structure-from-target contract."""
    try:
        from flax import serialization
    except ImportError:  # pragma: no cover - flax is a hard dep in practice
        return

    def to_state(z):
        return {"inner": serialization.to_state_dict(z.inner)}

    def from_state(target, state):
        return ZeroState(
            serialization.from_state_dict(target.inner, state["inner"]),
            target.plan)

    serialization.register_serialization_state(ZeroState, to_state,
                                               from_state)


_register_flax_serialization()


def _bucket_key(i):
    return f"b{i}"


def make_plan(params, op=Average, axes=None, threshold_bytes=None,
              hierarchical=False, mesh=None):
    """Build the ZeRO partition for ``params`` over the current mesh."""
    from horovod_tpu.parallel import mesh as mesh_lib

    if op not in (Sum, Average):
        raise ValueError(f"ZeRO-1 supports Sum or Average, got {op!r}")
    mesh = mesh if mesh is not None else mesh_lib.get_mesh()
    axes = collective._resolve_axes(axes)
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    world = 1
    for a in axes:
        world *= shape[a]
    leaves = jax.tree_util.tree_leaves(params)
    if not leaves:
        raise ValueError("ZeRO-1 needs a non-empty parameter pytree")
    schedule = fusion.bucket_schedule(leaves, world,
                                      threshold_bytes=threshold_bytes,
                                      axes=axes, hierarchical=hierarchical)
    return ZeroPlan(schedule=schedule, op=op)


def _bucket_rows(schedule, idx, leaves):
    """Pack bucket ``idx`` of ``leaves`` into its padded flat form and
    reshape to ``[world, shard]`` rows (row ``r`` = rank ``r``'s chunk)."""
    flat = fusion._pack(schedule.buckets[idx], leaves)
    pad = schedule.padded_sizes[idx] - flat.shape[0]
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(schedule.world, schedule.shard_sizes[idx])


# the GSPMD plan layer (parallel/gspmd.py) packs gradients/params into
# the same rows; public alias so it does not reach into a private name
bucket_rows = _bucket_rows


def init(tx, params, plan):
    """Initialize the wrapped optimizer over the bucket-row view of
    ``params``. Runs at top level (outside shard_map): the rows come out
    replicated and become genuinely sharded when placed with
    :func:`state_specs` shardings (``training.make_train_step`` does
    this)."""
    schedule = plan.schedule
    leaves = jax.tree_util.tree_leaves(params)
    rows = {_bucket_key(i): _bucket_rows(schedule, i, leaves)
            for i in range(len(schedule.buckets))}
    return ZeroState(tx.init(rows), plan)


def state_specs(zstate):
    """PartitionSpecs for a :class:`ZeroState`: bucket-row leaves
    (``[world, shard]``) are sharded over the scatter axes on dim 0;
    everything else (step counts, schedules) replicated. Returns a
    ZeroState-shaped spec tree, usable directly in shard_map in/out_specs
    and for ``jax.device_put`` placement."""
    schedule = zstate.plan.schedule
    row_spec = P(tuple(schedule.axes))

    def one(leaf):
        shape = jnp.shape(leaf)
        if len(shape) >= 1 and shape[0] == schedule.world:
            return row_spec
        return P()

    return ZeroState(jax.tree_util.tree_map(one, zstate.inner), zstate.plan)


def _local_param_rows(schedule, leaves):
    """This rank's ``[1, shard]`` slice of every bucket's packed params
    (replicated params sliced at ``mesh_rank`` — no communication)."""
    rank = collective.mesh_rank(schedule.axes)
    rows = {}
    for i in range(len(schedule.buckets)):
        flat = fusion._pack(schedule.buckets[i], leaves)
        pad = schedule.padded_sizes[i] - flat.shape[0]
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        shard = schedule.shard_sizes[i]
        rows[_bucket_key(i)] = lax.dynamic_slice(
            flat, (rank * shard,), (shard,))[None]
    return rows


def apply_shards(tx, grad_rows, zstate, params, wire=None,
                 ag_residuals=None):
    """The sharded-update tail: run ``tx.update`` on this rank's gradient
    shards (``{"bi": [1, shard]}``), then all-gather the updated-parameter
    DELTAS back into a full update pytree. Must run inside a named-axis
    context (shard_map). Returns ``(updates, new_zstate)`` with ``updates``
    shaped like ``params`` — feed ``optax.apply_updates``.

    ``wire`` (an ``ops.compression`` compressor) narrows the delta
    all-gather to the wire format; ``ag_residuals`` (a list of per-bucket
    shard-sized arrays) additionally turns on delta error feedback — the
    quantization error of THIS rank's delta shard is carried into the
    next step's shard before encoding, so the cumulative applied delta
    tracks the exact one (DoubleSqueeze-style; ``training.
    make_train_step`` threads the residuals through the train state).
    With ``ag_residuals`` the return grows to ``(updates, new_zstate,
    new_ag_residuals)``."""
    schedule = zstate.plan.schedule
    leaves, treedef = jax.tree_util.tree_flatten(params)
    with scopes.device(scopes.OPTIMIZER):
        param_rows = _local_param_rows(schedule, leaves)
        update_rows, new_inner = tx.update(grad_rows, zstate.inner,
                                           param_rows)

    new_residuals = list(ag_residuals) if ag_residuals is not None else None
    new_leaves = [None] * len(leaves)
    for i in range(len(schedule.buckets)):
        row = update_rows[_bucket_key(i)][0]
        if wire is None:
            flat = fusion.all_gather_bucket(schedule, i, row)
        else:
            res = ag_residuals[i] if ag_residuals is not None else None
            flat, new_res = fusion.all_gather_bucket_compressed(
                schedule, i, row, wire, residual=res)
            if new_residuals is not None:
                new_residuals[i] = new_res
        for j, arr in fusion.unpack_bucket(schedule, i, flat,
                                           leaves).items():
            new_leaves[j] = arr
    # a leaf can only be missing if the schedule was built for a different
    # pytree — fail loudly rather than emit zero updates
    missing = [j for j, leaf in enumerate(new_leaves) if leaf is None]
    if missing:
        raise ValueError(
            f"ZeRO plan does not cover gradient leaves {missing}; was the "
            "optimizer initialized with a different parameter tree?")
    updates = jax.tree_util.tree_unflatten(treedef, new_leaves)
    new_zstate = ZeroState(new_inner, zstate.plan)
    if new_residuals is not None:
        return updates, new_zstate, new_residuals
    return updates, new_zstate


def sharded_update(tx, grads, zstate, params, wire=None):
    """Full ZeRO-1 exchange for one already-accumulated gradient pytree:
    per-bucket reduce-scatter → sharded ``tx.update`` → all-gather of the
    updates. The ``DistributedOptimizer(sharded_update=True).update``
    implementation; the overlapped microbatch pipeline in
    ``training.make_train_step`` instead accumulates reduce-scattered
    shards itself and calls :func:`apply_shards` directly.

    ``wire`` compresses both halves of the exchange (gradient
    reduce-scatter + delta all-gather) STATELESSLY — this entry point has
    no step-to-step carry, so no error feedback; the pipeline path in
    ``make_train_step`` is the one that threads residuals."""
    schedule = zstate.plan.schedule
    leaves = jax.tree_util.tree_leaves(grads)
    grad_rows = {}
    for i in range(len(schedule.buckets)):
        if wire is None:
            shard = fusion.reduce_scatter_bucket(schedule, i, leaves,
                                                 op=zstate.plan.op)
        else:
            shard, _ = fusion.reduce_scatter_bucket_compressed(
                schedule, i, leaves, wire, op=zstate.plan.op)
        grad_rows[_bucket_key(i)] = shard[None]
    return apply_shards(tx, grad_rows, zstate, params, wire=wire)


def local_state_bytes(zstate):
    """Per-device optimizer-state bytes under this partition (the ZeRO-1
    memory claim, computable without devices): sharded ``[world, shard]``
    leaves count ``1/world`` of their bytes, replicated leaves count in
    full."""
    schedule = zstate.plan.schedule

    def one(total, leaf):
        arr = jnp.asarray(leaf)
        nbytes = arr.size * arr.dtype.itemsize
        if arr.ndim >= 1 and arr.shape[0] == schedule.world:
            return total + nbytes // schedule.world
        return total + nbytes

    return jax.tree_util.tree_reduce(one, zstate.inner, 0)
