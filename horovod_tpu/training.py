"""SPMD training-step builders: the Horovod programming model, compiled.

The reference's user contract is "compute local gradients, the framework
averages them" (``horovod/torch/__init__.py:57`` et al.). Here that contract
is compiled into one XLA program: ``make_train_step`` wraps a flax model +
``DistributedOptimizer`` into a ``shard_map``-ped step over the global mesh
— per-shard batches in, replicated params/optimizer state, gradient
allreduce (fused/hierarchical/compressed) inside.

These builders power ``bench.py``, ``__graft_entry__.py``, ``examples/``
and the end-to-end tests; they are also the reference pattern for users
writing their own steps.
"""

import dataclasses
import functools
import time
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from horovod_tpu import basics, hvd_jax
from horovod_tpu import telemetry as telemetry_lib
from horovod_tpu.diag import recorder as _flightrec
from horovod_tpu.ops import collective, fusion
from horovod_tpu.parallel import gspmd as gspmd_lib
from horovod_tpu.parallel import mesh as mesh_lib
from horovod_tpu.parallel import zero as zero_lib
from horovod_tpu.telemetry import ledger as _ledger_lib
from horovod_tpu.telemetry import scopes
from horovod_tpu.telemetry import startup as _startup


@dataclasses.dataclass
class TrainState:
    """Replicated training state (params + optimizer + BN stats + step)."""
    params: Any
    opt_state: Any
    batch_stats: Any
    step: Any

    def tree_flatten(self):
        return ((self.params, self.opt_state, self.batch_stats, self.step),
                None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten)


def softmax_cross_entropy(logits, labels):
    """Mean cross-entropy with integer labels."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def create_train_state(model, tx, rng, sample_input, **apply_kwargs):
    """Initialize replicated state for ``model`` (flax) and optimizer ``tx``
    (typically ``hvd.DistributedOptimizer(optax...)``)."""
    variables = model.init(rng, sample_input, train=False, **apply_kwargs)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    return TrainState(params=params, opt_state=tx.init(params),
                      batch_stats=batch_stats, step=jnp.zeros((), jnp.int32))


def replicated_specs(state):
    return jax.tree_util.tree_map(lambda _: P(), state)


def state_specs(state):
    """PartitionSpecs for a :class:`TrainState`: everything replicated,
    except ZeRO-sharded optimizer state (``parallel/zero.ZeroState``) whose
    bucket rows are sharded over their scatter axes — the ~1/N
    optimizer-state memory is real, not just an algorithmic claim.
    Delegates to ``parallel/gspmd.state_partition_specs`` — ONE spec
    authority, shared by the explicit shard_map path, the GSPMD jit
    path, placement and checkpointing."""
    return gspmd_lib.state_partition_specs(state)


def _put(x, sharding):
    """``device_put`` to ``sharding``, multi-process safe: host or
    process-local values headed for a sharding that spans processes are
    sliced locally (``cluster.procmesh.place``) instead of letting
    device_put broadcast the whole value through the collective fabric
    to assert cross-process equality — that broadcast runs per call,
    per leaf, and on the gloo CPU transport it can mis-pair with the
    step's own async collectives. Already-global arrays keep the plain
    device_put (no-op when already placed)."""
    if sharding.is_fully_addressable:
        return jax.device_put(x, sharding)
    from horovod_tpu.cluster import procmesh

    return procmesh.place(x, sharding)


def _placer(mesh, spec):
    """device_put to a stable NamedSharding (no-op when already placed).

    ``spec`` is a single PartitionSpec for every leaf, or a pytree of
    specs matching the data (the ZeRO state path). Keeping input shardings
    identical across calls matters: the first call sees uncommitted host
    arrays while later calls see outputs committed to the mesh — without
    pinning, jit recompiles and (on jax 0.9 CPU meshes) trips an XLA
    buffer-count mismatch."""
    if isinstance(spec, P):
        sharding = jax.sharding.NamedSharding(mesh, spec)

        def place(tree):
            return jax.tree_util.tree_map(
                lambda x: _put(x, sharding), tree)

        return place

    def place(tree):
        return jax.tree_util.tree_map(
            lambda x, s: _put(
                x, jax.sharding.NamedSharding(mesh, s)), tree, spec)

    return place


def _classification_grads(model, loss_fn, params, stats, inputs, labels,
                          dropout_rng):
    """Forward, loss and backward of one (micro)batch at fixed
    ``params``: ``((loss, new batch_stats), grads)`` — ``{}`` for a
    model that keeps no statistics."""
    def compute_loss(params):
        variables = {"params": params}
        if stats:
            variables["batch_stats"] = stats
            logits, mutated = model.apply(
                variables, inputs, train=True, mutable=["batch_stats"],
                rngs={"dropout": dropout_rng})
            new_stats = mutated["batch_stats"]
        else:
            logits = model.apply(variables, inputs, train=True,
                                 rngs={"dropout": dropout_rng})
            new_stats = {}
        with scopes.device(scopes.LOSS):
            return loss_fn(logits, labels), new_stats

    return jax.value_and_grad(compute_loss, has_aux=True)(params)


@jax.custom_vjp
def _next_token_ll(logits, targets):
    """Log-likelihood ``[B, T]`` of ``targets`` under next-token
    ``logits`` (whose last position is dropped where the targets are one
    shorter), in fp32. The reduction is the caller's: a masked sum over
    the global count, a mean, a mean plus auxiliary terms.

    The backward pass is its own, ``g * (onehot(target) - exp(logits -
    lse))``: it needs the logits as they came in and one log-sum-exp a
    row, where autodiff of ``log_softmax`` keeps an f32 ``[B, T, V]``
    from the forward pass to the backward. ``targets`` are vocabulary
    indices in ``[0, V)``; one outside counts as a logit of zero."""
    return _next_token_ll_fwd(logits, targets)[0]


def _rows_of(logits, rows):
    """``rows [B, T]`` at the logits' own length: a zero in the last
    position where the targets are one shorter, so that every pass over
    ``[B, S, V]`` runs on the array the head wrote and not on a cut (or,
    backward, a padded) copy of it."""
    if rows.shape[1] == logits.shape[1] - 1:
        return jnp.pad(rows, ((0, 0), (0, 1)))
    return rows


def _hits(logits, targets):
    """``[B, S, V]`` booleans: where a position's target sits."""
    return (jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                     logits.ndim - 1)
            == _rows_of(logits, targets)[..., None])


def _next_token_ll_fwd(logits, targets):
    with scopes.device(scopes.LOSS):
        x = logits.astype(jnp.float32)
        top = jnp.max(x, axis=-1)
        log_sum = jnp.log(jnp.sum(jnp.exp(x - top[..., None]), axis=-1))
        # the target's logit as a masked sum, exact like a gather: it
        # fuses into the pass that sums the exponentials, and reads the
        # model's float32 cast of its bfloat16 logits through the cast; a
        # gather takes no fused operand, so XLA writes the whole float32
        # copy for it (3.3 GB a step at 16,384 x 50,304)
        at = jnp.sum(jnp.where(_hits(logits, targets), x, 0.0), axis=-1)
        ll = ((at - top) - log_sum)[:, :targets.shape[1]]
        return ll, (logits, top + log_sum, targets)


def _next_token_ll_bwd(residuals, g):
    logits, lse, targets = residuals
    with scopes.device(scopes.LOSS):
        prob = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
        d = _rows_of(logits, g)[..., None] * (_hits(logits, targets) - prob)
        return d.astype(logits.dtype), None


_next_token_ll.defvjp(_next_token_ll_fwd, _next_token_ll_bwd)


def _next_state(state, updates, opt_state, batch_stats, loss,
                mean_over=None):
    """How a step ends, ``(new state, loss)``: ``updates`` applied to
    the params and the step counted. A per-shard (``shard_map``) step
    names the axes to average over in ``mean_over``: its BatchNorm
    statistics (per-shard normalization like the reference, one
    consistent copy for checkpointing) and its loss are the shards'
    means."""
    with scopes.device(scopes.OPTIMIZER):
        params = optax.apply_updates(state.params, updates)
    if mean_over is not None:
        if batch_stats:
            batch_stats = jax.tree_util.tree_map(
                lambda x: collective.allreduce(
                    x, op=collective.Average, axes=mean_over), batch_stats)
        with scopes.device(scopes.LOSS):
            loss = collective.allreduce(loss, op=collective.Average,
                                        axes=mean_over)
    return TrainState(params=params, opt_state=opt_state,
                      batch_stats=batch_stats, step=state.step + 1), loss


def _grad_schedule(tx, reduce_axes, state, world):
    """The ONE bucket schedule of a step's gradient exchange — the traced
    step (world from the named axes) and the error-feedback carry (world
    from the step's mesh) must shape against the same plan. Under ZeRO-1
    the optimizer-state partition IS the schedule."""
    if tx.sharded_update:
        return state.opt_state.plan.schedule
    return fusion.bucket_schedule(
        jax.tree_util.tree_leaves(state.params), world=world,
        threshold_bytes=tx.threshold_bytes, axes=reduce_axes,
        hierarchical=tx._hierarchical_resolved())


def _reduce_scatter_grads(tx, reduce_axes, state, grads, wire, residuals):
    """From inside the per-shard step, reduce-scatter every bucket of one
    gradient tree, at the ``wire`` format when there is one. Returns
    ``(schedule, shards)``: this shard's 1/N of each bucket. ``residuals``
    (error feedback: one per bucket, or None) are replaced in place by
    the new quantization errors."""
    schedule = _grad_schedule(tx, reduce_axes, state,
                              collective.mesh_size(reduce_axes))
    op = state.opt_state.plan.op if tx.sharded_update else tx.op
    leaves = jax.tree_util.tree_leaves(grads)
    shards = []
    for i in range(len(schedule.buckets)):
        if wire is None:
            s = fusion.reduce_scatter_bucket(schedule, i, leaves, op=op)
        else:
            s, new_r = fusion.reduce_scatter_bucket_compressed(
                schedule, i, leaves, wire, op=op,
                residual=None if residuals is None else residuals[i])
            if residuals is not None:
                residuals[i] = new_r
        shards.append(s)
    return schedule, shards


def _update_from_shards(tx, schedule, shards, state, wire, residuals):
    """The exchange's tail, ``(updates, opt_state)`` from the reduced
    gradient shards: the ZeRO-1 sharded update (no gradient all-gather
    at all), or one all-gather per bucket and the inner optimizer.
    ``residuals`` as in :func:`_reduce_scatter_grads`, for the
    all-gather direction."""
    if tx.sharded_update:
        grad_rows = {f"b{i}": s[None] for i, s in enumerate(shards)}
        out = zero_lib.apply_shards(
            tx.inner, grad_rows, state.opt_state, state.params, wire=wire,
            ag_residuals=residuals)
        if residuals is not None:
            residuals[:] = out[2]
        return out[:2]
    leaves, treedef = jax.tree_util.tree_flatten(state.params)
    new_leaves = [None] * len(leaves)
    for i, s in enumerate(shards):
        if wire is None:
            flat = fusion.all_gather_bucket(schedule, i, s)
        else:
            flat, new_r = fusion.all_gather_bucket_compressed(
                schedule, i, s, wire,
                residual=None if residuals is None else residuals[i])
            if residuals is not None:
                residuals[i] = new_r
        for j, arr in fusion.unpack_bucket(schedule, i, flat,
                                           leaves).items():
            new_leaves[j] = arr
    grads = jax.tree_util.tree_unflatten(treedef, new_leaves)
    return tx.update_preaveraged(grads, state.opt_state, state.params)


def _sum_squares(arrays):
    return sum(jnp.sum(jnp.square(a.astype(jnp.float32))) for a in arrays)


def _shards_norm(shards, reduce_axes):
    """Shards partition the globally-averaged gradient: the psum of
    their sum-squares IS its exact norm² (the pad zeros contribute
    nothing)."""
    return jnp.sqrt(collective.allreduce(
        _sum_squares(shards), op=collective.Sum, axes=reduce_axes))


def _wire_drift_checker(tx, wire):
    """Per-step guard of a build that compiled its wire format in: the
    format is resolved ONCE, when the step is built (bucket collectives
    and residual shapes of the overlap pipeline; the chunked shard_map
    island, the cast-narrowed constraints, or neither, on the GSPMD
    path), but config.wire_dtype binds late — an autotuner that
    installs its winner AFTER the step was built would otherwise leave
    tx.compression claiming a format the running program never applies
    (or vice versa). Warn once, in either drift direction, instead of
    silently diverging."""
    warned = [False]

    def check():
        now = tx.compression
        if warned[0] or now is wire:
            return
        warned[0] = True
        built = (f"built with {wire.name!r}" if wire is not None
                 else "built uncompressed")
        warnings.warn(
            f"tx.compression now resolves to "
            f"{getattr(now, 'name', None)!r} but this train step was "
            f"{built} — the wire format is baked into the compiled "
            "program at make_train_step time. Rebuild the step (after "
            "the autotuner / config.wire_dtype install) for the new "
            "format to take effect.", stacklevel=3)

    return check


class _WireCarry:
    """The error-feedback residuals of a wire-compressed exchange, and
    their life. One fp32 ``[world, n]`` buffer per bucket and direction
    (``"rs"``: the reduce-scatter's padded bucket; ``"ag"``: the
    all-gather's shard), row r = rank r's carry, sharded over the
    scatter axes. Rebuildable by construction — zeros, built lazily from
    the live state — so never checkpointed; donated into every dispatch,
    so dropped when one raises. ``directions`` names those that carry:
    both for the overlap pipeline and the chunked island, ``("ag",)``
    for the cast + ZeRO-1 annotation path, none with error feedback off
    (an empty tree: zero buffers, no effect on a program it is passed
    to)."""

    def __init__(self, tx, mesh, reduce_axes, directions):
        self._tx = tx
        self._mesh = mesh
        self._axes = tuple(reduce_axes)
        self._directions = tuple(directions)
        self._held = None
        self.spec = P(self._axes)

    def get(self, state):
        if self._held is None:
            self._held = self._wire_state_for(state)
        return self._held

    def keep(self, new):
        self._held = new

    def reset(self):
        """Drop the carried residuals; the next step rebuilds zeros.
        Call after restoring ``state`` to an earlier commit (elastic
        rollback / checkpoint restore) so the compensation restarts
        clean instead of carrying a later step's error."""
        self._held = None

    def _wire_state_for(self, state):
        if not self._directions:
            return {"rs": [], "ag": []}
        # world from the mesh THIS step was built on (the global mesh
        # can be a different one — e.g. a sub-mesh step built while a
        # bigger mesh is set — and a mismatched world here would shape
        # the residual buffers against the wrong schedule)
        schedule = _grad_schedule(
            self._tx, self._axes, state,
            int(np.prod([self._mesh.shape[a] for a in self._axes])))
        sizes = {"rs": schedule.padded_sizes, "ag": schedule.shard_sizes}
        sharding = jax.sharding.NamedSharding(self._mesh, self.spec)

        def zeros(i, n):
            # non-float buckets are never quantized (the bucket ops pass
            # their residual through untouched) — a zero-width buffer
            # keeps the per-bucket index alignment without the HBM or
            # donation traffic of a dead fp32 carry
            if not jnp.issubdtype(schedule.buckets[i].dtype, jnp.floating):
                n = 0
            return _put(jnp.zeros((schedule.world, n), jnp.float32),
                        sharding)

        return {d: ([zeros(i, n) for i, n in enumerate(sizes[d])]
                    if d in self._directions else [])
                for d in ("rs", "ag")}

    @staticmethod
    def shard_rows(wire_state):
        """Inside the per-shard step: ``(rs, ag)`` residual lists for
        the bucket ops (None where a direction carries nothing), each
        this shard's ``[1, n]`` row of the global buffer, squeezed."""
        return tuple([r[0] for r in wire_state[d]] or None
                     for d in ("rs", "ag"))

    @staticmethod
    def from_rows(rs, ag):
        """The carry a per-shard step returns: its rows, unsqueezed."""
        return {"rs": [r[None] for r in rs or ()],
                "ag": [r[None] for r in ag or ()]}


class _HostStep:
    """The host side of a train step, the same for every builder: what
    surrounds the dispatch of ``program``. A call opens ``hvd_step``,
    pulls the batch from the loader if none was given, checks the wire
    format for drift, brackets the step for the flight recorder, places
    state, carry and batch under ``hvd_place``, calls the program — and
    nothing else — under ``hvd_launch``, keeps the new carry (or drops
    it when the dispatch raises), settles the goodput ledger and feeds
    the instruments. All of it is host-side floats and None checks: the
    compiled program is byte-identical with a loader, a recorder, the
    ledger or instruments present or absent (tests/test_data_plane,
    test_diag, test_goodput).

    What differs between the builders is data. ``program`` answers
    ``program(*placed)`` and ``program.lower(*placed)`` (a ``jax.jit``
    object, or :class:`_SpmdProgram`) and returns ``(state, [carry,]
    loss[, grad norm])``; ``place_batch`` holds one placer per batch
    argument; ``carry`` is the :class:`_WireCarry` riding as the second
    argument and output, or None; ``prepare(placed)`` returns what to
    launch for these arguments (the GSPMD builds fetch — at a first
    shape, compile — their AOT executable there, BETWEEN placement and
    launch, so that ``hvd_launch`` holds the dispatch alone)."""

    # read through to the program (set at its first build) on the builds
    # whose step does not hold them itself
    _PROGRAM_ATTRS = ("jitted", "compiled_collectives",
                      "compiled_axis_collectives")

    def __init__(self, program, mesh, place_batch, carry=None,
                 check_wire=None, prepare=None, compiled_path=False,
                 instruments=None, loader=None):
        self._program = program
        self._mesh = mesh
        self._place_batch = tuple(place_batch)
        self._carry = carry
        self._check_wire = check_wire
        self._prepare = prepare
        self._compiled_path = compiled_path
        self._instruments = instruments
        self._loader = loader
        self._n = 0
        self._first_trace = True
        self._settles_ledger = True  # elastic_train_loop must not re-settle
        if instruments is not None:
            self.instruments = instruments

    def __getattr__(self, name):
        if name in self._PROGRAM_ATTRS:
            return getattr(self._program, name)
        raise AttributeError(name)

    def place_state(self, state):
        # once a GSPMD program is built, its cached shardings tree is
        # reused instead of re-deriving specs on every step
        shardings = getattr(self._program, "state_shardings", None)
        if shardings is None:
            return _placer(self._mesh, state_specs(state))(state)
        return jax.tree_util.tree_map(_put, state, shardings)

    def _place(self, state, batch):
        if len(batch) != len(self._place_batch):
            raise TypeError(
                f"this step takes {len(self._place_batch)} batch "
                f"argument(s) after the state, got {len(batch)}")
        carry = () if self._carry is None else (self._carry.get(state),)
        return (self.place_state(state),) + carry + tuple(
            place(x) for place, x in zip(self._place_batch, batch))

    def _loader_batch(self):
        if self._loader is None:
            raise TypeError(
                "step(state) with no batch needs a loader — build the "
                "step with make_train_step(..., loader=...) or pass "
                "the batch explicitly")
        batch = next(self._loader)
        if not (isinstance(batch, (tuple, list))
                and len(batch) == len(self._place_batch)):
            raise TypeError(
                "the loader's source must yield (inputs, labels) "
                f"batches for this step; got {type(batch).__name__} "
                f"of {len(batch) if hasattr(batch, '__len__') else '?'}")
        return tuple(batch)

    def step(self, state, *batch):
        # the set-up record keeps every step until the first one that ran
        # from warm caches alone; after that a step pays this one check
        # (and a call: the body below is the same with and without it)
        record = _startup.RECORD
        entry = (None if record.closed
                 else record.open_span(scopes.STEP, step_num=self._n))
        if entry is None:
            return self._step(state, batch, None)
        try:
            out = self._step(state, batch, entry["attrs"])
        except BaseException:
            record.close_span(entry)
            raise
        record.step_returned(entry)
        return out

    __call__ = step

    def _step(self, state, batch, marks):
        n = self._n
        with scopes.step(n):
            if not batch:
                batch = self._loader_batch()
            self._n = n + 1
            if self._check_wire is not None:
                self._check_wire()
            _flightrec.step_begin(n)  # a None check with no recorder
            tl = (basics._state.timeline
                  if self._instruments is not None else None)
            flow = None
            if tl is not None and self._first_trace:
                # the first call traces: open an enclosing slice + flow
                # on the marker tid so the bucket markers emitted during
                # tracing link back to this dispatch (ops/fusion reads
                # _step_flow_id; flows need a B/E slice on their tid to
                # bind in Perfetto's legacy-JSON importer)
                tl.start_activity("marker", "step_trace_dispatch")
                flow = tl.flow_start("step_dispatch")
                tl._step_flow_id = flow
            t0 = time.perf_counter()
            try:
                with scopes.host(scopes.PLACE):
                    placed = self._place(state, batch)
                if marks is not None:
                    marks["place_end"] = time.time()
                launch = (self._program if self._prepare is None
                          else self._prepare(placed))
                with scopes.host(scopes.LAUNCH):
                    outs = launch(*placed)
                if marks is not None:
                    marks["launch_end"] = time.time()
                if self._carry is not None:
                    self._carry.keep(outs[1])
            except BaseException:
                # the residuals were donated into the failed dispatch
                # and may already be invalidated — drop them so the
                # retry path (elastic rollback) rebuilds zeros instead
                # of dying on deleted arrays forever
                if self._carry is not None:
                    self._carry.reset()
                raise
            finally:
                if flow is not None:
                    self._first_trace = False
                    tl._step_flow_id = None
                    tl.flow_end("step_dispatch", flow)
                    tl.end_activity("marker")
            _flightrec.step_end(n)
            # the goodput ledger settles at every step boundary: the
            # interval since the last settle, minus the stalls other
            # subsystems charged (data_wait, ckpt_stall, compile, ...),
            # is booked as compute. Resolved at CALL time (hvd.init opens
            # a fresh run ledger).
            ledger = _ledger_lib.get_ledger()
            if self._compiled_path:
                ledger.note_compiled_path()
            ledger.settle_step()
            loss, *extra = outs[1:] if self._carry is None else outs[2:]
            if self._instruments is not None:
                self._instruments.record_step(
                    batch=int(batch[0].shape[0]),
                    dispatch_s=time.perf_counter() - t0, loss=loss,
                    grad_norm=extra[0] if extra else None, timeline=tl,
                    step_no=n)
        return outs[0], loss

    def lower(self, state, *batch):
        """AOT lower with the SAME placement the executed path uses, so
        the compile cache is shared and cost_analysis describes the
        module that actually runs."""
        with _startup.span(scopes.LOWER):
            return self._program.lower(*self._place(state, batch))


def _xray(step, state, *batch, k=3, profile_dir=None):
    """Opt-in compiled-step X-ray of a GSPMD step: run K steps of the
    ALREADY compiled executable under a device trace and attribute
    where the device time went (telemetry/xprof.py). Capture wraps
    around the dispatch — the compiled program is byte-identical with
    X-ray off. State threads through the captured steps (donation as
    usual): returns ``(new_state, summary)``."""
    from horovod_tpu.telemetry import xprof as _xprof
    return _xprof.xray_run(
        step, state, batch or step._loader_batch(), k=k,
        profile_dir=profile_dir,
        compiled_collectives=lambda: step.compiled_collectives)


def make_train_step(model, tx, mesh=None, loss_fn=softmax_cross_entropy,
                    batch_axes=None, donate=True, dropout_seed=0,
                    accum_steps=1, overlap_grads=False, telemetry=None,
                    error_feedback=True, loader=None, spmd=False):
    """Build a jitted SPMD classification train step.

    ``spmd=True`` selects the **GSPMD hot path** (docs/PERFORMANCE.md,
    "The GSPMD path"): the whole step is jitted with
    ``in_shardings``/``out_shardings`` derived from one
    :class:`~horovod_tpu.parallel.gspmd.GspmdPlan` — batches sharded
    over the data axes, params replicated, ZeRO-1 rows ``P(data)`` —
    and contains **no explicit collective calls**; XLA inserts the
    gradient reduction (and, for ``sharded_update``, the
    reduce-scatter/all-gather pair) from the sharding annotations, and
    the latency-hiding scheduler overlaps them with compute. Same
    ``step(state, inputs, labels)`` contract and interchangeable
    optimizer state/checkpoints. Semantics differences, documented:
    BatchNorm normalizes with GLOBAL-batch statistics (sync-BN; the
    explicit path is per-shard), and dropout draws one global stream.
    ``accum_steps``/``overlap_grads`` are the explicit pipeline's knobs
    and are rejected here; a wire-compressed optimizer compiles the
    compression IN-PLACE — chunked quantizers (fp8/int8) as a
    ``shard_map`` island inside the jitted program (which restores the
    explicit path's per-shard BN/dropout semantics for that build),
    cast wires (bf16/float16) as dtype-narrowed sharding constraints
    that keep the annotation-only program — docs/PERFORMANCE.md.

    Returns ``step(state, inputs, labels) -> (state, loss)`` where
    ``inputs``/``labels`` are global arrays whose leading (batch) dim is
    sharded over the data axes and ``state`` is replicated (ZeRO-sharded
    optimizer state excepted).

    ``loader`` (a ``horovod_tpu.data.PrefetchLoader``) wires the data
    plane in: the step's own mesh placement (``device_put`` to the data
    axes) is installed into the loader, so batches are staged onto
    device BY THE PREFETCH THREAD while the previous step runs, and
    ``step(state)`` with no batch arguments pulls ``(inputs, labels)``
    from the loader (recording ``hvd_data_wait_seconds`` for any stall).
    The loader only changes who feeds the program, never the program:
    the compiled step is byte-identical with and without one
    (tests/test_data_plane.py). Gradients are allreduced by ``tx`` (wrap
    with ``hvd.DistributedOptimizer``); BN stats are averaged across shards
    (per-shard normalization like the reference, one consistent stats copy
    for checkpointing); loss is averaged.

    ``accum_steps=K`` splits each shard's batch into K equal microbatches
    and accumulates gradients across them (one optimizer step per call —
    the compiled analogue of ``backward_passes_per_step``, with the batch
    presented whole). With ``overlap_grads=True`` the exchange is the
    bucketed reduce-scatter PIPELINE: each microbatch's gradient buckets
    (reverse-traversal order — ready-first) are reduce-scattered as soon as
    that microbatch's backward produces them, so microbatch k+1's compute
    overlaps bucket k's reduction inside one XLA program (the async-
    collective scheduler flags — ``config.xla_overlap_flags`` — make the
    overlap real on TPU). The accumulators hold 1/N-sized reduced shards
    instead of full gradients. The shards then feed either one all-gather
    per bucket + the inner optimizer (plain data parallelism) or the
    ZeRO-1 sharded update (``DistributedOptimizer(sharded_update=True)``)
    with no extra gradient all-gather at all. Numerics match the
    ``accum_steps=1`` baseline up to reduction-order tolerance when the
    model is microbatch-invariant (no BatchNorm across microbatches).
    ``overlap_grads`` requires ``tx`` to be a ``DistributedOptimizer``.

    ``telemetry`` (default: auto — on when a metrics endpoint is
    configured, see ``horovod_tpu.telemetry.enabled``) instruments the
    returned step: step latency / examples-per-sec / dispatch-time
    metrics plus deferred loss and grad-norm gauges, timeline counter
    events, and a flow linking the tracing dispatch to its bucket
    markers. When on, the compiled program additionally computes the
    gradient L2 norm (exact norm of the globally-averaged gradient in
    the overlapped paths; root-mean of per-shard local norms otherwise —
    docs/OBSERVABILITY.md); when off the program is byte-identical to
    the uninstrumented build.

    **Wire compression** (``DistributedOptimizer(compression=...)``) in
    the ``overlap_grads`` pipeline narrows every bucket collective to the
    wire format. The format is resolved when THIS function is called and
    baked into the compiled program — build the step after the autotuner
    installs ``config.wire_dtype`` (a later config change warns at the
    next step call instead of silently applying). The reduce-scatter
    ships quantized gradient rows, and
    the all-gather (of gradient shards, or of ZeRO-1's parameter deltas)
    ships quantized shards — 1/4 the wire bytes at fp8/int8, 1/2 at
    bf16. With ``error_feedback=True`` (default) one fp32 residual per
    bucket AND direction is threaded through the step: each step's
    quantization error is added back into the next step's bucket before
    encoding, which is what keeps the compressed trajectory within the
    documented epsilon of the exact one (docs/PERFORMANCE.md, "Wire
    compression"). The residual buffers live OUTSIDE the checkpointable
    ``TrainState`` — they are rebuildable state, initialized to zero and
    excluded from checkpoint manifests; a restore merely restarts the
    compensation (``step.reset_error_feedback()`` drops the carry
    explicitly after rolling ``state`` back to an earlier commit, and a
    step that raises drops it automatically — the donated buffers may
    already be invalid). With ``tx.compression is None`` the residual plumbing
    vanishes and the compiled program is byte-identical to the
    uncompressed build.
    """
    tele_on = (telemetry_lib.enabled() if telemetry is None
               else bool(telemetry))
    mesh = mesh if mesh is not None else mesh_lib.get_mesh()
    if spmd:
        return _make_spmd_train_step(
            model, tx, mesh, loss_fn, batch_axes, donate, dropout_seed,
            accum_steps, overlap_grads, tele_on, error_feedback, loader)

    data_axes = batch_axes or mesh_lib.data_axis_names(mesh)
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    pipelined = overlap_grads or accum_steps > 1
    is_hvd_tx = isinstance(tx, hvd_jax.HorovodOptimizer)
    if pipelined:
        if not is_hvd_tx:
            raise ValueError(
                "accum_steps>1 / overlap_grads=True need the optimizer "
                "built by hvd.DistributedOptimizer(...) — the pipeline "
                "takes over its gradient reduction")
        if tx.backward_passes_per_step > 1:
            raise ValueError(
                "accum_steps and backward_passes_per_step are two "
                "accumulators for the same thing; use accum_steps")
    reduce_axes = (tuple(tx.axes) if is_hvd_tx and tx.axes is not None
                   else data_axes)
    # wire compression rides the bucket collectives of the OVERLAP
    # pipeline here; the non-overlapped paths compress inside tx's own
    # fused allreduce / sharded_update. Error feedback needs a
    # step-to-step carry, so it exists only when a wire format is on.
    # The wire format is resolved HERE, once: it is baked into the
    # compiled program (bucket collectives, residual shapes), so build
    # the step AFTER the autotuner installs its wire-axis winner. A
    # config change after build cannot take effect — the drift check
    # warns instead of silently diverging from tx.compression.
    wire = tx.compression if (is_hvd_tx and overlap_grads) else None
    use_ef = wire is not None and error_feedback
    carry = _WireCarry(tx, mesh, reduce_axes,
                       ("rs", "ag") if use_ef else ())

    def local_step(state, wire_state, inputs, labels):
        rs_res, ag_res = _WireCarry.shard_rows(wire_state)
        # per-step AND per-shard dropout stream (reference semantics:
        # each rank draws independent masks); each microbatch folds its
        # index in on top
        base_rng = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(dropout_seed), state.step),
            collective.mesh_rank(data_axes))

        if inputs.shape[0] % accum_steps:
            raise ValueError(
                f"per-shard batch {inputs.shape[0]} does not divide into "
                f"accum_steps={accum_steps} microbatches")
        micro = inputs.shape[0] // accum_steps

        stats = state.batch_stats
        acc_shards, acc_grads, loss_sum = None, None, 0.0
        if pipelined:
            for k in range(accum_steps):
                xk = inputs[k * micro:(k + 1) * micro]
                yk = labels[k * micro:(k + 1) * micro]
                (loss_k, stats), grads_k = _classification_grads(
                    model, loss_fn, state.params, stats, xk, yk,
                    jax.random.fold_in(base_rng, k))
                loss_sum = loss_sum + loss_k
                if overlap_grads:
                    # reduce-scatter every bucket of THIS microbatch now:
                    # the next microbatch's backward has no data
                    # dependence on these collectives, so the latency-
                    # hiding scheduler overlaps them (reduce-scatter is
                    # linear — summing per-microbatch shards equals
                    # scattering the sum)
                    schedule, shards_k = _reduce_scatter_grads(
                        tx, reduce_axes, state, grads_k, wire, rs_res)
                    acc_shards = (shards_k if acc_shards is None else
                                  [a + s for a, s in zip(acc_shards,
                                                         shards_k)])
                else:
                    acc_grads = (grads_k if acc_grads is None else
                                 jax.tree_util.tree_map(
                                     jnp.add, acc_grads, grads_k))
        else:
            (loss_sum, stats), grads = _classification_grads(
                model, loss_fn, state.params, stats, inputs, labels,
                base_rng)

        inv_k = 1.0 / accum_steps
        gnorm = None
        if overlap_grads:
            shards = [s * jnp.asarray(inv_k, s.dtype) for s in acc_shards]
            if tele_on:
                gnorm = _shards_norm(shards, reduce_axes)
            updates, opt_state = _update_from_shards(
                tx, schedule, shards, state, wire, ag_res)
        else:
            if pipelined:
                grads = jax.tree_util.tree_map(
                    lambda g: g * jnp.asarray(inv_k, g.dtype), acc_grads)
            if tele_on:
                # grads here are LOCAL (reduction happens inside tx):
                # the root-mean across ranks of local norm² — an upper
                # bound of the averaged-grad norm (Jensen), and the
                # divergence signal observability wants
                gnorm = jnp.sqrt(collective.allreduce(
                    _sum_squares(jax.tree_util.tree_leaves(grads)),
                    op=collective.Average, axes=reduce_axes))
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
        with scopes.device(scopes.LOSS):
            loss = loss_sum * inv_k
        new_state, loss = _next_state(state, updates, opt_state, stats,
                                      loss, mean_over=data_axes)
        out = (new_state, _WireCarry.from_rows(rs_res, ag_res), loss)
        return out + (gnorm,) if tele_on else out

    def hvd_train_step(state, wire_state, inputs, labels):
        specs = state_specs(state)
        wspecs = jax.tree_util.tree_map(lambda _: carry.spec, wire_state)
        sharded = jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(specs, wspecs, P(data_axes), P(data_axes)),
            out_specs=(specs, wspecs) + (P(),) * (2 if tele_on else 1),
            check_vma=False)
        return sharded(state, wire_state, inputs, labels)

    # the carry is an EMPTY pytree unless error feedback is on, so the
    # extra jit argument contributes zero buffers and the compiled
    # program stays byte-identical to the uncompressed build.
    # the function's name is the compiled module's (jit_hvd_train_step)
    # and, unlike a scope, part of the persistent compile cache's key
    jitted = jax.jit(hvd_train_step,
                     donate_argnums=(0, 1) if donate else ())
    place_data = _placer(mesh, P(data_axes))
    if loader is not None:
        # stage prefetched batches straight to this step's mesh placement
        # on the PRODUCER thread — by dispatch time place_data is a no-op
        loader.attach_placement(place_data, spec=P(data_axes))

    step = _HostStep(
        jitted, mesh, (place_data, place_data), carry=carry,
        check_wire=(_wire_drift_checker(tx, wire)
                    if is_hvd_tx and overlap_grads else None),
        instruments=(telemetry_lib.StepInstruments(accum_steps=accum_steps)
                     if tele_on else None),
        loader=loader)
    step.jitted = jitted  # AOT access (lower/compile/cost_analysis)
    step.reset_error_feedback = carry.reset
    step.loader = loader
    step.place_data = place_data
    return step


def _spmd_gate(tx, what):
    """Shared validation for the GSPMD builders: the optimizer
    contract. Returns the resolved wire format (``None`` or a
    compressor — the caller compiles it in-place: the shard_map island
    for chunked quantizers, dtype-narrowed constraints for casts)."""
    if not isinstance(tx, hvd_jax.HorovodOptimizer):
        raise ValueError(
            f"{what}(spmd=True) needs the optimizer built by "
            "hvd.DistributedOptimizer(...) — the GSPMD step routes its "
            "gradient reduction through the plan")
    if tx.op != hvd_jax.Average:
        raise ValueError(
            f"the GSPMD step computes the global-batch mean loss — that "
            f"is op=Average semantics; got {tx.op!r}. Adasum/Min/Max "
            "reductions live on the explicit path (spmd=False)")
    if tx.backward_passes_per_step > 1:
        raise ValueError(
            "backward_passes_per_step>1 has no GSPMD path — its "
            "accumulator lives in the explicit pipeline")
    return tx.compression


class _SpmdProgram:
    """The shared machinery of both GSPMD step flavors (classification
    and LM): the lazily built jit wrapper — ``in_shardings``/
    ``out_shardings`` need the first state's tree structure, so the jit
    is constructed on first use and cached, one structure per step —
    plus the once-per-build compiled-collective accounting and the AOT
    lower. One copy, so a fix to either flavor cannot miss the other.

    ``batch_specs`` are the PartitionSpecs of the batch arguments;
    ``n_scalar_outs`` counts the replicated scalar outputs after the
    state (loss, optional grad norm). ``carry_spec``, where there is an
    error-feedback carry, is the ONE spec of its whole tree (a pytree
    prefix — which is how the wire-residual dict rides as one
    argument): the carry is then the second argument and the second
    output, and donated with the state (the residuals are dead after
    each step — donating them keeps the EF carry HBM-neutral, same as
    the explicit path's ``donate_argnums=(0, 1)``)."""

    def __init__(self, plan, global_step, batch_specs, n_scalar_outs,
                 donate, carry_spec=None):
        self.plan = plan
        self._fn = global_step
        self._carry_specs = () if carry_spec is None else (carry_spec,)
        self._batch_specs = tuple(batch_specs)
        self._n_out = int(n_scalar_outs)
        self._donate = donate
        self.jitted = None
        self.state_shardings = None
        self._cache = gspmd_lib.CompiledProgramCache(mesh=plan.mesh)
        self.compiled_collectives = None
        self.compiled_axis_collectives = None

    def jitted_for(self, placed_state):
        if self.jitted is None:
            self.state_shardings = gspmd_lib.state_shardings(
                self.plan, placed_state)
            carry = tuple(self.plan.sharding(s) for s in self._carry_specs)
            self.jitted = jax.jit(
                self._fn,
                in_shardings=(self.state_shardings,) + carry + tuple(
                    self.plan.sharding(s) for s in self._batch_specs),
                out_shardings=(self.state_shardings,) + carry
                + (self.plan.sharding(P()),) * self._n_out,
                donate_argnums=(tuple(range(1 + len(carry)))
                                if self._donate else ()))
        return self.jitted

    def executable(self, placed):
        """ONE compile per argument-shape signature: AOT lower+compile
        on first sight of a shape set (a shorter final batch from a
        ``drop_last=False`` loader, an eval batch), then the cached
        executable — the jit wrapper would retrace those transparently,
        and this cache keeps that behavior instead of crashing on a
        shape mismatch. The cache/accounting machinery is the shared
        ``gspmd.CompiledProgramCache`` (the serving engine wraps the
        same one): executables are called directly, and each new
        program's collectives are accounted as it compiles — the same
        once-per-compile semantics as the trace-time counters. Donation
        and in/out shardings were fixed at jit construction and carry
        into every executable."""
        ex = self._cache.executable(self.jitted_for(placed[0]), placed)
        self.compiled_collectives = self._cache.last_collectives
        self.compiled_axis_collectives = self._cache.last_axis_collectives
        return ex

    def lower(self, *placed):
        """AOT lower with the executed path's placement — for
        ``cost_analysis``-style callers; ``.compile()`` on the result
        is a fresh compile (the executing path's artifact is
        :meth:`executable`)."""
        return self.jitted_for(placed[0]).lower(*placed)


def _through_wire_dtype(grads, wire):
    """The cast wires' plain-DP hint on the annotation-only programs.
    Plain DP has no sharded consumer to hang a narrow constraint on:
    round-trip the logical gradient through the wire dtype — the applied
    update carries the wire precision, and the convert adjacent to XLA's
    inserted all-reduce is the cue for sinking the reduction to the
    narrow width where the backend can."""
    return jax.tree_util.tree_map(
        lambda g: (g.astype(wire.wire_dtype).astype(g.dtype)
                   if jnp.issubdtype(g.dtype, jnp.floating) else g), grads)


def _spmd_host_step(prog, mesh, place_batch, tx, wire, **kwargs):
    """The :class:`_HostStep` of a GSPMD build: the drift check always
    on, the AOT executable (one compile per argument-shape signature)
    fetched between placement and launch, the ledger told that the
    compiled path ran, and the attributes the GSPMD steps carry."""
    step = _HostStep(prog, mesh, place_batch,
                     check_wire=_wire_drift_checker(tx, wire),
                     prepare=prog.executable, compiled_path=True,
                     **kwargs)
    step.plan = prog.plan
    step.spmd = True
    step.xray = functools.partial(_xray, step)
    return step


def _make_spmd_train_step(model, tx, mesh, loss_fn, batch_axes, donate,
                          dropout_seed, accum_steps, overlap_grads, tele_on,
                          error_feedback, loader):
    """The GSPMD hot path behind ``make_train_step(spmd=True)`` — see
    that docstring and ``parallel/gspmd.py`` for the contract.

    Wire compression compiles IN-PLACE (no fallback):

    * **Chunked quantizers** (fp8/int8) need per-device partial
      gradients and per-chunk scales, which no annotation can express —
      so the per-shard forward/backward + quantized bucket exchange +
      optimizer tail run as ONE ``shard_map`` island
      (``gspmd.shard_map_island``) inside the jitted program. XLA's
      latency-hiding scheduler still owns the schedule; the wire moves
      narrow bytes (all-to-all of int8/fp8 rows + fp32 scales).
      Semantics inside the island are the EXPLICIT path's: per-shard
      BatchNorm statistics (averaged after) and per-shard dropout
      streams — not the annotation path's sync-BN/global stream.
    * **Cast wires** (bf16/float16) keep the annotation-only global
      program (sync-BN, one dropout stream): ZeRO-1's constraint
      exchange narrows both halves by dtype-narrowed constraints
      (``gspmd.apply_shards_spmd(wire=...)``, with delta-EF on the
      all-gather half); the plain-DP path round-trips the logical
      gradient through the wire dtype as a convert-sinking hint.
    * ``wire is None`` compiles the byte-identical uncompressed program
      (the wire-residual argument is an empty pytree — zero buffers).
    """
    wire = _spmd_gate(tx, "make_train_step")
    if accum_steps != 1 or overlap_grads:
        raise ValueError(
            "accum_steps/overlap_grads are the explicit pipeline's "
            "microbatch knobs; the GSPMD step compiles the whole batch "
            "and XLA's latency-hiding scheduler owns the compute/comms "
            "overlap")

    plan = gspmd_lib.derive_plan(mesh)
    data_axes = tuple(batch_axes) if batch_axes else plan.data_axes
    batch_spec = P(data_axes)

    sharded_tx = tx.sharded_update
    reduce_axes = (tuple(tx.axes) if tx.axes is not None else data_axes)
    chunked = wire is not None and getattr(wire, "chunked", False)
    # EF carries exist where a step-to-step residual is well-defined:
    # both halves of the chunked island exchange, and the delta
    # all-gather of the cast+ZeRO-1 annotation path. The cast plain-DP
    # hint is stateless (a residual would have to be added to the
    # still-unreduced logical gradient — see apply_shards_spmd).
    use_ef = (wire is not None and error_feedback
              and (chunked or sharded_tx))
    carry = _WireCarry(
        tx, mesh, reduce_axes,
        () if not use_ef else ("rs", "ag") if chunked else ("ag",))

    if chunked:
        def local_step(state, wire_state, inputs, labels):
            # the shard_map island: per-shard forward/backward feeding
            # the chunked quantize->alltoall->dequantize bucket exchange
            # — the same data plane as the explicit overlap pipeline,
            # but compiled INSIDE the GSPMD jit step so the surrounding
            # program (and its scheduler) stays XLA's.
            rs_res, ag_res = _WireCarry.shard_rows(wire_state)
            # per-step AND per-shard dropout stream — explicit-path
            # semantics (each rank draws independent masks)
            rng = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(dropout_seed),
                                   state.step),
                collective.mesh_rank(data_axes))
            (loss, stats), grads = _classification_grads(
                model, loss_fn, state.params, state.batch_stats, inputs,
                labels, rng)
            schedule, shards = _reduce_scatter_grads(
                tx, reduce_axes, state, grads, wire, rs_res)
            gnorm = _shards_norm(shards, reduce_axes) if tele_on else None
            updates, opt_state = _update_from_shards(
                tx, schedule, shards, state, wire, ag_res)
            new_state, loss = _next_state(state, updates, opt_state,
                                          stats, loss, mean_over=data_axes)
            out = (new_state, _WireCarry.from_rows(rs_res, ag_res), loss)
            return out + (gnorm,) if tele_on else out

        def global_step(state, wire_state, inputs, labels):
            specs = state_specs(state)
            wspecs = jax.tree_util.tree_map(lambda _: carry.spec,
                                            wire_state)
            island = gspmd_lib.shard_map_island(
                local_step, plan,
                in_specs=(specs, wspecs, batch_spec, batch_spec),
                out_specs=(specs, wspecs) + (P(),) * (2 if tele_on else 1))
            return island(state, wire_state, inputs, labels)
    else:
        def global_step(state, wire_state, inputs, labels):
            # ONE global dropout stream per step: there is no per-shard
            # rank to fold in — masks are drawn over the global batch
            # (the explicit path draws per-shard streams;
            # docs/PERFORMANCE.md)
            rng = jax.random.fold_in(jax.random.PRNGKey(dropout_seed),
                                     state.step)
            (loss, stats), grads = _classification_grads(
                model, loss_fn, state.params, state.batch_stats, inputs,
                labels, rng)
            if wire is not None and not sharded_tx:
                grads = _through_wire_dtype(grads, wire)
            gnorm = None
            if tele_on:
                # grads are the logical global-mean gradient — this is
                # its exact L2 norm (same definition as the overlapped
                # path)
                gnorm = jnp.sqrt(_sum_squares(
                    jax.tree_util.tree_leaves(grads)))
            new_wire = {"rs": [], "ag": []}
            if use_ef:  # the cast + ZeRO-1 delta carry
                updates, opt_state, new_wire["ag"] = tx.update_spmd(
                    grads, state.opt_state, state.params, plan,
                    wire=wire, ag_residuals=list(wire_state["ag"]))
            else:
                updates, opt_state = tx.update_spmd(
                    grads, state.opt_state, state.params, plan,
                    wire=wire if sharded_tx else None)
            new_state, loss = _next_state(state, updates, opt_state,
                                          stats, loss)
            out = (new_state, new_wire, loss)
            return out + (gnorm,) if tele_on else out

    # the wire-residual carry (error feedback on) rides as ONE extra
    # jit argument — a dict of per-bucket [world, n] fp32 arrays,
    # sharded over the scatter axes — and comes back as the matching
    # extra output. With EF off (including compression off) the
    # argument is OMITTED entirely, keeping the program — down to its
    # result metadata — byte-identical to a build with no wire
    # plumbing at all.
    if use_ef:
        program_fn = global_step
    else:
        def program_fn(state, inputs, labels):
            out = global_step(state, {"rs": [], "ag": []}, inputs,
                              labels)
            return (out[0],) + out[2:]  # drop the empty wire slot

        # keep the jitted module's name (jit_global_step) — the
        # compression-off program must be byte-identical, debug
        # metadata included
        program_fn.__name__ = "global_step"
        program_fn.__qualname__ = global_step.__qualname__
    prog = _SpmdProgram(plan, program_fn, (batch_spec, batch_spec),
                        n_scalar_outs=2 if tele_on else 1, donate=donate,
                        carry_spec=carry.spec if use_ef else None)

    place_data = _placer(mesh, batch_spec)
    if loader is not None:
        # prefetched batches are staged by the PRODUCER thread directly
        # onto the plan's batch NamedSharding — they arrive matching the
        # compiled step's in_shardings, so dispatch-time placement is a
        # no-op
        loader.attach_placement(place_data,
                                spec=plan.sharding(batch_spec))

    step = _spmd_host_step(
        prog, mesh, (place_data, place_data), tx, wire,
        carry=carry if use_ef else None,
        instruments=telemetry_lib.StepInstruments() if tele_on else None,
        loader=loader)
    step.reset_error_feedback = carry.reset
    step.loader = loader
    step.place_data = place_data
    return step


def _make_spmd_lm_train_step(model, tx, mesh, batch_axis, donate):
    """The GSPMD LM step behind ``make_lm_train_step(spmd=True)``:
    next-token mean loss over the batch-sharded tokens.

    Wire compression compiles IN-PLACE, mirroring
    ``_make_spmd_train_step``: chunked quantizers (fp8/int8) run the
    per-shard forward/backward + quantized bucket exchange as a
    ``shard_map`` island inside the jitted program; cast wires keep the
    annotation-only global program (dtype-narrowed constraints under
    ZeRO-1, a round-trip convert hint under plain DP). LM compression
    is STATELESS — no error-feedback carry — matching the explicit LM
    step's ``fused_allreduce`` route, so ``step(state, tokens)`` keeps
    its two-argument signature and the two builds stay head-to-head
    comparable in ``bench.py``."""
    wire = _spmd_gate(tx, "make_lm_train_step")
    plan = gspmd_lib.derive_plan(mesh)
    token_spec = P(batch_axis)
    sharded_tx = tx.sharded_update
    reduce_axes = (tuple(tx.axes) if tx.axes is not None
                   else (batch_axis,))
    chunked = wire is not None and getattr(wire, "chunked", False)

    def _local_loss(params, tokens):
        ll = _next_token_ll(model.apply({"params": params}, tokens),
                            tokens[:, 1:])
        with scopes.device(scopes.LOSS):
            return -jnp.mean(ll)

    if chunked:
        def local_step(state, tokens):
            # the shard_map island (see _make_spmd_train_step): the
            # per-shard mean over an equal token shard, averaged across
            # shards, IS the exact global mean
            loss, grads = jax.value_and_grad(_local_loss)(state.params,
                                                          tokens)
            schedule, shards = _reduce_scatter_grads(
                tx, reduce_axes, state, grads, wire, None)
            updates, opt_state = _update_from_shards(
                tx, schedule, shards, state, wire, None)
            return _next_state(state, updates, opt_state,
                               state.batch_stats, loss,
                               mean_over=(batch_axis,))

        def global_step(state, tokens):
            specs = state_specs(state)
            island = gspmd_lib.shard_map_island(
                local_step, plan,
                in_specs=(specs, token_spec),
                out_specs=(specs, P()))
            return island(state, tokens)
    else:
        def global_step(state, tokens):
            # the global mean IS the exact loss — no allreduce of
            # per-shard partial means to get right
            loss, grads = jax.value_and_grad(_local_loss)(state.params,
                                                          tokens)
            if wire is not None and not sharded_tx:
                grads = _through_wire_dtype(grads, wire)
            updates, opt_state = tx.update_spmd(
                grads, state.opt_state, state.params, plan,
                wire=wire if sharded_tx else None)
            return _next_state(state, updates, opt_state,
                               state.batch_stats, loss)

    prog = _SpmdProgram(plan, global_step, (token_spec,), n_scalar_outs=1,
                        donate=donate)
    return _spmd_host_step(prog, mesh, (_placer(mesh, token_spec),), tx,
                           wire)


def elastic_train_loop(elastic_state, train_step, batch_fn, num_steps,
                       commit_every=1, checkpoint_every=None,
                       on_step=None):
    """Drive ``train_step`` under the elastic retry loop
    (``hvd.elastic.run``): commit/restore/sync semantics come from
    ``elastic_state`` (a ``hvd.elastic.JaxState`` whose ``train_state``
    attribute holds the :class:`TrainState`), membership interrupts are
    honored at commit boundaries, and a worker failure rolls back to the
    last commit before retrying.

    ``batch_fn`` supplies data two ways: a callable ``batch_fn(step) ->
    (inputs, labels)`` (step-indexed so a restored worker re-reads the
    right batch), or a ``horovod_tpu.data.PrefetchLoader`` — then the
    loop pulls prefetched batches, attaches the loader to
    ``elastic_state`` (when it is a ``JaxState``) so the loader's
    cursor commits, restores and re-syncs WITH the model state, and a
    rollback after a worker failure replays the exact batches of the
    rolled-back steps. ``on_step(step, loss)`` is an optional observer.
    Returns the final ``TrainState``.

    ``checkpoint_every=K`` sets the DISK cadence independently of the
    in-memory ``commit_every``: every K-th commit is persisted through
    the async sharded checkpoint subsystem (``horovod_tpu/ckpt``,
    docs/CHECKPOINT.md), where the training stall is only the
    device→host snapshot — the serialize/fsync/manifest commit overlaps
    the following steps (``hvd_ckpt_blocking_seconds`` vs
    ``hvd_ckpt_save_seconds``). Requires a ``JaxState`` built with a
    ``directory``; the final commit always flushes to disk.

    When telemetry is enabled and ``train_step`` is not already an
    instrumented ``make_train_step`` build, the loop records step
    latency / examples-per-sec itself, so a hand-written step function
    still shows up on the metrics plane.
    """
    from horovod_tpu import elastic as _elastic

    if checkpoint_every is not None:
        if not getattr(elastic_state, "_directory", None):
            raise ValueError(
                "checkpoint_every needs an elastic state with a "
                "checkpoint directory (JaxState(directory=...))")
        elastic_state.checkpoint_every = max(1, int(checkpoint_every))

    loader = (batch_fn if hasattr(batch_fn, "cursor")
              and hasattr(batch_fn, "__next__") else None)
    if loader is not None and hasattr(elastic_state, "attach_loader"):
        # cursor rides the commit/restore/sync/manifest machinery
        elastic_state.attach_loader(loader)

    own_instruments = None
    if telemetry_lib.enabled() and not hasattr(train_step, "instruments"):
        own_instruments = telemetry_lib.StepInstruments()

    # a hand-written train_step doesn't settle the goodput ledger itself
    # — the loop does it, so its steps still get time attribution
    _goodput = (None if getattr(train_step, "_settles_ledger", False)
                else _ledger_lib.get_ledger)

    def _batch_of(inputs):
        # hand-written steps may take pytree batches; any leaf's leading
        # dim is the per-call example count (0 when unknowable)
        leaves = jax.tree_util.tree_leaves(inputs)
        try:
            return int(leaves[0].shape[0])
        except (IndexError, AttributeError, TypeError):
            return 0

    def _step_of(ts):
        return int(jax.device_get(ts.step))

    @_elastic.run
    def _loop(state):
        while _step_of(state.train_state) < num_steps:
            if loader is not None:
                inputs, labels = next(loader)
            else:
                inputs, labels = batch_fn(_step_of(state.train_state))
            t0 = time.perf_counter()
            new_ts, loss = train_step(state.train_state, inputs, labels)
            if _goodput is not None:
                _goodput().settle_step()
            if own_instruments is not None:
                own_instruments.record_step(
                    batch=_batch_of(inputs),
                    dispatch_s=time.perf_counter() - t0, loss=loss,
                    timeline=basics._state.timeline)
            state.train_state = new_ts
            done = _step_of(new_ts)
            if on_step is not None:
                on_step(done, float(jax.device_get(loss)))
            if done % commit_every == 0 or done >= num_steps:
                if done >= num_steps and hasattr(state, "checkpoint_every"):
                    # the final commit must reach disk regardless of the
                    # thinned cadence — but the cadence itself must
                    # survive (an elastic retry re-enters this loop with
                    # the same state object)
                    cadence = state.checkpoint_every
                    state.checkpoint_every = 1
                    try:
                        state.commit()
                    finally:
                        state.checkpoint_every = cadence
                else:
                    state.commit()
        state.flush()  # drain any async save before leaving the loop
        return state.train_state

    return _loop(elastic_state)


def make_lm_train_step(model, tx, mesh=None, batch_axis="data",
                       seq_axis=None, donate=True, spmd=False):
    """Build a jitted SPMD language-model train step (next-token loss).

    ``spmd=True`` selects the GSPMD hot path (no explicit collectives;
    see ``make_train_step``). It shards the batch axis only — ring
    attention over ``seq_axis`` is an explicit shard_map schedule and
    stays on the default path.

    ``tokens`` is ``[B, S]``; B is sharded over ``batch_axis`` and, when
    ``seq_axis`` is set, S over ``seq_axis`` with ring attention inside the
    model (``cfg.sequence_axis`` must name the same axis). The next-token
    loss is **exact** under sequence sharding: each shard's final position
    is scored against the first token of the next shard (fetched with one
    ``ppermute`` over ``seq_axis``), only the global final position is
    masked, and the mean is normalized by the global target count — so the
    seq-parallel loss and gradient match the single-device full-sequence
    computation.
    """
    mesh = mesh if mesh is not None else mesh_lib.get_mesh()
    if spmd:
        if seq_axis is not None:
            raise ValueError(
                "make_lm_train_step(spmd=True) shards the batch axis "
                "only; ring attention (seq_axis) is the explicit path's "
                "shard_map schedule — drop seq_axis or spmd")
        return _make_spmd_lm_train_step(model, tx, mesh, batch_axis, donate)
    grad_axes = (batch_axis,) if seq_axis is None else (batch_axis, seq_axis)
    n_shards = int(np.prod([mesh.shape[a] for a in grad_axes]))
    n_seq = mesh.shape[seq_axis] if seq_axis else 1

    def local_step(state, tokens):
        if seq_axis is not None and n_seq > 1:
            # shard i's final target is shard i+1's first token; the wrap
            # pair (0 -> n-1) is masked below as the global final position
            nxt = jax.lax.ppermute(
                tokens[:, :1], seq_axis,
                perm=[((i + 1) % n_seq, i) for i in range(n_seq)])
            targets = jnp.concatenate([tokens[:, 1:], nxt], axis=1)
            is_last = jax.lax.axis_index(seq_axis) == n_seq - 1
            mask = jnp.ones(targets.shape, jnp.float32)
            mask = mask.at[:, -1].set(jnp.where(is_last, 0.0, 1.0))
        else:
            targets = tokens[:, 1:]
            mask = jnp.ones(targets.shape, jnp.float32)

        def compute_loss(params):
            ll = _next_token_ll(model.apply({"params": params}, tokens),
                                targets)
            with scopes.device(scopes.LOSS):
                local_sum = -jnp.sum(ll * mask)
                global_count = collective.allreduce(
                    jnp.asarray(jnp.sum(mask), jnp.float32),
                    op=collective.Sum, axes=grad_axes)
                # scaled so that the Average-allreduce of per-shard losses
                # (and of per-shard gradients, inside ``tx``) equals the
                # exact global-mean loss/gradient
                return local_sum * n_shards / global_count

        loss, grads = jax.value_and_grad(compute_loss)(state.params)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        return _next_state(state, updates, opt_state, state.batch_stats,
                           loss, mean_over=grad_axes)

    token_spec = P(batch_axis, seq_axis) if seq_axis else P(batch_axis)

    def hvd_lm_train_step(state, tokens):
        specs = state_specs(state)
        sharded = jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(specs, token_spec),
            out_specs=(specs, P()),
            check_vma=False)
        return sharded(state, tokens)

    # named for what it is: jit_hvd_lm_train_step (see make_train_step)
    jitted = jax.jit(hvd_lm_train_step,
                     donate_argnums=(0,) if donate else ())
    step = _HostStep(jitted, mesh, (_placer(mesh, token_spec),))
    step.jitted = jitted  # AOT access (lower/compile/cost_analysis)
    return step
