"""JAX user-facing API: DistributedOptimizer, broadcast, Join.

Rebuilds the L5 user contract of the reference for JAX/optax:

* ``DistributedOptimizer`` — wraps an ``optax.GradientTransformation`` so
  gradients are fusion-bucketed and allreduced across the mesh before the
  inner update (reference: ``horovod/torch/__init__.py:57-212``
  ``_DistributedOptimizer``; ``horovod/tensorflow/__init__.py:266-311``).
* ``distributed_grad`` / ``distributed_value_and_grad`` — the
  ``DistributedGradientTape`` analogue
  (``horovod/tensorflow/__init__.py:475-531``).
* ``broadcast_variables`` / ``broadcast_parameters`` /
  ``broadcast_optimizer_state`` — rank-0 state sync at startup
  (``horovod/torch/__init__.py:440-560``,
  ``hvd.broadcast_global_variables``).
* ``join`` — uneven-data fault tolerance
  (``EnqueueJoin``, ``operations.cc:909``; zero-fill semantics
  ``controller.cc:209-220``).

All of these are meant to be used inside a ``jax.shard_map``-style SPMD step
(each shard computes local gradients on its local batch — the Horovod
programming model) OR at top level eagerly across processes.
"""

import jax
import jax.numpy as jnp

from horovod_tpu.ops import collective
from horovod_tpu.ops.collective import Adasum, Average, Sum
from horovod_tpu.ops.fusion import fused_allreduce
from horovod_tpu.telemetry import scopes


def DistributedGradientTransform(op=Average, axes=None, compression=None,
                                 threshold_bytes=None, hierarchical=None):
    """An ``optax.GradientTransformation`` that allreduces gradients across
    the mesh (fused, optionally compressed/hierarchical/Adasum). Chain it
    before any optimizer: ``optax.chain(DistributedGradientTransform(), tx)``.
    """
    import optax

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        del params
        reduced = fused_allreduce(
            updates, op=op, axes=axes, compression=compression,
            threshold_bytes=threshold_bytes, hierarchical=hierarchical)
        return reduced, state

    return optax.GradientTransformation(init_fn, update_fn)


def _scoped_optimizer(inner):
    """``inner`` with its update traced under ``hvd_optimizer``. ``init`` is
    the inner's own, so the state tree (and every checkpoint of it) is
    what it was."""
    import optax

    def update_fn(updates, state, params=None):
        with scopes.device(scopes.OPTIMIZER):
            return inner.update(updates, state, params)

    return optax.GradientTransformation(inner.init, update_fn)


class HorovodOptimizer:
    """The object ``DistributedOptimizer`` returns: duck-typed as an
    ``optax.GradientTransformation`` (``init``/``update``) and carrying the
    reduction configuration as attributes so the training pipeline
    (``training.make_train_step(accum_steps=..., overlap_grads=True)``) can
    introspect it — which collective op, which axes, whether the optimizer
    state is ZeRO-sharded, and the unwrapped inner transform for updates
    on gradients the pipeline has already reduced."""

    def __init__(self, inner, op, axes, compression, threshold_bytes,
                 hierarchical, sharded_update, backward_passes_per_step):
        self.inner = inner
        self.op = op
        self.axes = axes
        self.threshold_bytes = threshold_bytes
        self.hierarchical = hierarchical
        self.sharded_update = sharded_update
        self.backward_passes_per_step = backward_passes_per_step

        from horovod_tpu.ops import compression as compression_lib

        # ``None`` defers to config.wire_dtype AT USE TIME (the config
        # does not exist before hvd.init(), and the autotuner's wire
        # axis may install its winner after this optimizer is built —
        # same late binding as _hierarchical_resolved); an explicit
        # "none"/Compression.none pins uncompressed regardless of config.
        self._wire_forced_off = False
        if isinstance(compression, str):
            name = compression
            compression = compression_lib.by_name(compression)
            if compression is None and name is not None:
                self._wire_forced_off = True
        elif isinstance(compression, compression_lib.NoneCompressor):
            self._wire_forced_off = True
            compression = None
        if compression is not None:
            self._check_wire(compression)
        self._compression = compression

        if sharded_update:
            if op not in (Sum, Average):
                raise ValueError(
                    f"sharded_update supports Sum or Average, got {op!r}")
            if backward_passes_per_step > 1:
                raise ValueError(
                    "sharded_update accumulates via make_train_step("
                    "accum_steps=...) — backward_passes_per_step>1 would "
                    "stack a second accumulator on top")
        self._transform = None
        self._transform_wire = self._WIRE_UNSET
        self._config_wire_warned = False

    def _check_wire(self, compression):
        if (getattr(compression, "chunked", False)
                and self.op not in (Sum, Average)):
            raise ValueError(
                f"chunked wire format {compression.name!r} only composes "
                f"with Sum/Average reductions (got {self.op!r}): e.g. "
                "int8 wire + Adasum is unsupported — per-chunk scales "
                "cannot ride Adasum's dot-product composition. Use "
                "bf16/fp16 (cast) compression or drop the quantizer.")

    @property
    def compression(self):
        """The resolved wire format: the explicit argument if one was
        given, else ``config.wire_dtype`` read at access time (so an
        optimizer built before ``hvd.init()`` / before the autotuner
        installed its wire-axis winner still picks the config value up),
        else ``None``. A config-derived DEFAULT that is incompatible
        with this optimizer's op (e.g. int8 installed globally while
        this one runs Adasum) is ignored with a warning — only an
        EXPLICIT argument hard-errors on an unsupported combo."""
        if self._compression is not None or self._wire_forced_off:
            return self._compression
        from horovod_tpu import basics
        from horovod_tpu.ops import compression as compression_lib
        cfg = basics._state.config
        if cfg is None or not cfg.wire_dtype:
            return None
        wire = compression_lib.by_name(cfg.wire_dtype)
        if isinstance(wire, compression_lib.NoneCompressor):
            return None
        if wire is not None:
            try:
                self._check_wire(wire)
            except ValueError as e:
                if not self._config_wire_warned:
                    self._config_wire_warned = True
                    import warnings
                    warnings.warn(
                        f"ignoring config.wire_dtype={cfg.wire_dtype!r} "
                        f"for this optimizer (op={self.op!r}): {e}")
                return None
        return wire

    _WIRE_UNSET = object()

    def _ensure_transform(self):
        """Build the chained (non-sharded) transform against the wire
        format resolved NOW, rebuilding if the resolution has changed
        since (init() before the autotuner installs config.wire_dtype
        must not freeze the stale value while ``tx.compression`` reports
        the new one). Rebuilding is safe: the chain's state structure
        does not depend on the wire format — only the traced update
        math changes, which is the point."""
        wire = self.compression
        if self._transform is None or wire is not self._transform_wire:
            import optax

            chained = optax.chain(
                DistributedGradientTransform(
                    op=self.op, axes=self.axes, compression=wire,
                    threshold_bytes=self.threshold_bytes,
                    hierarchical=self.hierarchical),
                _scoped_optimizer(self.inner),
            )
            if self.backward_passes_per_step > 1:
                chained = optax.MultiSteps(
                    chained,
                    every_k_schedule=self.backward_passes_per_step)
            self._transform = chained
            self._transform_wire = wire
        return self._transform

    def init(self, params):
        if self.sharded_update:
            from horovod_tpu.parallel import zero
            plan = zero.make_plan(
                params, op=self.op, axes=self.axes,
                threshold_bytes=self.threshold_bytes,
                hierarchical=bool(self._hierarchical_resolved()))
            return zero.init(self.inner, params, plan)
        return self._ensure_transform().init(params)

    def update(self, updates, state, params=None):
        if self.sharded_update:
            from horovod_tpu.parallel import zero
            if params is None:
                raise ValueError("sharded_update needs params: "
                                 "tx.update(grads, state, params)")
            return zero.sharded_update(self.inner, updates, state, params,
                                       wire=self.compression)
        return self._ensure_transform().update(updates, state, params)

    def update_preaveraged(self, grads, state, params=None):
        """Inner update on gradients that are ALREADY reduced across the
        mesh (the overlap pipeline reduce-scatters during backward and
        all-gathers before calling this) — skips the chained allreduce,
        preserves the chain's state structure."""
        if self.sharded_update or self.backward_passes_per_step > 1:
            raise ValueError("update_preaveraged is the plain-optimizer "
                             "tail of the overlap pipeline")
        with scopes.device(scopes.OPTIMIZER):
            inner_updates, inner_state = self.inner.update(grads, state[1],
                                                           params)
        return inner_updates, (state[0], inner_state)

    def update_spmd(self, grads, state, params, plan, wire=None,
                    ag_residuals=None):
        """The GSPMD-path update (``training.make_train_step(spmd=True)``
        routes here): gradients arrive as the logical GLOBAL-batch mean —
        XLA's inserted collectives already own the reduction — so no
        allreduce is chained. ZeRO-1 state goes through the plan's
        sharding-constraint exchange (``parallel/gspmd.apply_shards_spmd``,
        no explicit collective calls); plain state through the inner
        transform with the chain structure preserved, so optimizer state
        and checkpoints stay interchangeable with the explicit path.
        Same public ``DistributedOptimizer`` surface — this method is the
        routing, not a new user contract.

        ``wire``/``ag_residuals`` thread a CAST wire format (and its
        delta error-feedback carry) into the ZeRO-1 constraint exchange
        — see ``apply_shards_spmd``; chunked quantizers never reach
        here (the train step compiles them as a shard_map island)."""
        if self.sharded_update:
            from horovod_tpu.parallel import gspmd
            if params is None:
                raise ValueError("sharded_update needs params: "
                                 "tx.update_spmd(grads, state, params, plan)")
            return gspmd.apply_shards_spmd(self.inner, grads, state,
                                           params, plan, wire=wire,
                                           ag_residuals=ag_residuals)
        if self.backward_passes_per_step > 1:
            raise ValueError(
                "backward_passes_per_step>1 has no GSPMD path — its "
                "accumulator lives in the explicit pipeline; use "
                "make_train_step(accum_steps=...) there")
        if wire is not None or ag_residuals is not None:
            raise ValueError(
                "wire=/ag_residuals= narrow the ZeRO-1 "
                "(sharded_update=True) constraint exchange; the plain "
                "path's cast narrowing lives in the train step itself")
        return self.update_preaveraged(grads, state, params)

    def _hierarchical_resolved(self):
        if self.hierarchical is not None:
            return self.hierarchical
        from horovod_tpu import basics
        cfg = basics._state.config
        return cfg.hierarchical_allreduce if cfg is not None else False


def DistributedOptimizer(tx, op=Average, axes=None, compression=None,
                         threshold_bytes=None, hierarchical=None,
                         backward_passes_per_step=1, sharded_update=False):
    """Wrap optimizer ``tx`` so every update first averages gradients across
    all shards (the core Horovod contract,
    ``horovod/torch/__init__.py:57``). With
    ``backward_passes_per_step > 1`` gradients are accumulated locally and
    the allreduce fires every k-th step
    (``horovod/torch/__init__.py`` backward_passes_per_step).

    ``sharded_update=True`` switches the exchange to ZeRO stage-1
    (``parallel/zero.py``): gradients are reduce-scattered per fusion
    bucket, ``tx`` updates only this rank's 1/N shard of its state, and the
    updated parameter deltas are all-gathered — same wire bytes as the
    bandwidth-optimal allreduce, ~1/N the optimizer compute and state
    memory per device. ``tx`` must be elementwise (see the zero module
    docstring); ``init``/``update`` must then run where the mesh axes are
    bound (inside ``shard_map`` — ``training.make_train_step`` handles
    placement and specs automatically).

    ``compression`` picks the collective wire format: a compressor from
    ``hvd.Compression`` (``bf16``, ``fp8_e4m3``, ``int8``, ...) or its
    name as a string. ``None`` (default) defers to ``config.wire_dtype``
    (``HOROVOD_WIRE_DTYPE`` / the autotuner's wire axis), which itself
    defaults to uncompressed; pass ``Compression.none`` / ``"none"`` to
    force uncompressed regardless of config. Compression composes with
    ``sharded_update`` and the overlapped pipeline (``training.
    make_train_step(overlap_grads=True)`` threads the per-bucket
    error-feedback residual); genuinely unsupported combos — a chunked
    quantizer with Adasum/Min/Max — raise loudly (docs/PERFORMANCE.md,
    "Wire compression"). The config deferral binds LATE — at first use,
    not at construction — so building the optimizer before ``hvd.init()``
    or before the autotuner installs its winner still honors the
    config."""
    return HorovodOptimizer(
        tx, op=op, axes=axes, compression=compression,
        threshold_bytes=threshold_bytes, hierarchical=hierarchical,
        sharded_update=sharded_update,
        backward_passes_per_step=backward_passes_per_step)


def distributed_value_and_grad(fun, op=Average, axes=None, compression=None,
                               **grad_kwargs):
    """``jax.value_and_grad`` whose gradients are allreduced across shards
    (the ``DistributedGradientTape`` analogue,
    ``horovod/tensorflow/__init__.py:475-531``)."""
    vg = jax.value_and_grad(fun, **grad_kwargs)

    def wrapped(*args, **kwargs):
        value, grads = vg(*args, **kwargs)
        grads = fused_allreduce(grads, op=op, axes=axes,
                                compression=compression)
        return value, grads

    return wrapped


def distributed_grad(fun, op=Average, axes=None, compression=None,
                     **grad_kwargs):
    """``jax.grad`` with cross-shard gradient averaging."""
    g = jax.grad(fun, **grad_kwargs)

    def wrapped(*args, **kwargs):
        return fused_allreduce(g(*args, **kwargs), op=op, axes=axes,
                               compression=compression)

    return wrapped


def broadcast_variables(tree, root_rank=0, axes=None):
    """Replace every leaf with shard ``root_rank``'s value — the startup
    parameter sync (``horovod/torch/__init__.py:440``
    ``broadcast_parameters``, ``BroadcastGlobalVariablesHook``
    ``horovod/tensorflow/__init__.py:194-227``)."""
    return jax.tree_util.tree_map(
        lambda x: collective.broadcast(x, root_rank=root_rank, axes=axes),
        tree)


# Horovod names both of these in different frameworks; keep the aliases.
broadcast_parameters = broadcast_variables


def broadcast_optimizer_state(opt_state, root_rank=0, axes=None):
    """Broadcast optimizer state from ``root_rank``
    (``horovod/torch/__init__.py:472-560``). With optax the state is a
    pytree, so unlike the reference no state_dict walking is needed —
    one fused broadcast covers it. Non-float leaves (step counters) are
    broadcast as-is."""
    return broadcast_variables(opt_state, root_rank=root_rank, axes=axes)


def allreduce_metrics(metrics, axes=None, op=Average):
    """Reduce scalar metrics across shards at epoch end (reference:
    ``MetricAverageCallback``, ``horovod/_keras/callbacks.py:46-85``).

    ``op=Average`` (default) matches the reference: every metric becomes
    an fp32 mean — including int-valued ones (a sample COUNT averaged
    across shards is a float). Pass ``op=Sum`` for totals: integer
    leaves then keep their dtype (int counts stay exact ints).

    ``metrics`` may be any pytree (nested dicts of a framework's logs
    included); non-numeric leaves (strings, ``None``) pass through
    unchanged — the reference iterates ``logs`` items and only ever sees
    numeric metric values, so reducing a string has no reference
    semantics to honor and dropping it would lose the user's data.
    An empty dict/pytree comes back as-is."""
    def _numeric(x):
        if isinstance(x, (bool, int, float)) or (
                hasattr(x, "dtype") and hasattr(x, "shape")):
            try:
                return jnp.issubdtype(jnp.result_type(x), jnp.number) or \
                    jnp.issubdtype(jnp.result_type(x), jnp.bool_)
            # hvd-lint: disable=HVD-EXCEPT -- dtype probe: an unresolvable leaf passes through as-is on every rank
            except Exception:
                return False
        return False

    def one(x):
        if not _numeric(x):
            return x
        x = jnp.asarray(x)
        if op == Average or jnp.issubdtype(x.dtype, jnp.floating):
            x = jnp.asarray(x, jnp.float32)
        return collective.allreduce(x, op=op, axes=axes)
    return jax.tree_util.tree_map(one, metrics)


def join(grads_tree, is_active, op=Average, axes=None, **fusion_kwargs):
    """Join-aware gradient allreduce for uneven data: shards whose data is
    exhausted pass ``is_active=False`` and contribute zeros; the mean is
    taken over *active* shards only.

    This is the compiled-data-plane realization of the reference's Join op
    (``message.h:49`` JOIN request type; coordinator counts joined ranks and
    zero-fills them, ``controller.cc:797-820``, ``tensor_queue.h:39-41``).
    Host-level join (process drops out of the loop entirely) is handled by
    the controller — see ``horovod_tpu.runtime``.
    """
    active = jnp.asarray(is_active, jnp.float32)
    n_active = collective.allreduce(active, op=Sum, axes=axes)
    n_active = jnp.maximum(n_active, 1.0)

    def _one(g):
        masked = g * active.astype(g.dtype)
        summed = collective.allreduce(masked, op=Sum, axes=axes)
        if op == Average:
            summed = summed / n_active.astype(summed.dtype)
        return summed

    return jax.tree_util.tree_map(_one, grads_tree), n_active
