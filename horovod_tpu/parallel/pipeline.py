"""Pipeline parallelism over a ``stage`` mesh axis: GPipe and 1F1B.

Beyond-parity (SURVEY §2.7 marks PP absent from the 2019 reference) —
the TPU-native formulation: the layer stack's parameters are STACKED on
a leading dim and sharded over the ``stage`` axis (each stage holds its
contiguous slice of layers), activations flow stage-to-stage with
``ppermute`` inside a compiled ``scan`` over schedule ticks, and every
stage executes the same per-tick program (SPMD lockstep) with
``lax.cond`` skipping the ticks a stage idles — bubbles cost a branch,
not a full layer-stack application.

Two schedules:

* ``pipelined_forward`` — GPipe. One differentiable XLA program:
  reverse-mode AD routes cotangents through the transposed
  ``ppermute``s, so backward pipelining falls out of autodiff. Simple
  and composable (it is just a function of the params), but the scan
  saves residuals for every tick: activation memory grows O(n_micro).
* ``pipeline_train_1f1b`` — 1F1B. Forward AND backward are explicitly
  scheduled in ONE forward-only scan; each stage keeps ring buffers of
  at most ``n_stages`` in-flight microbatch activations and computes
  its backward with a per-microbatch ``jax.vjp`` (recompute-from-saved-
  input, i.e. remat at stage granularity). Activation memory is
  O(n_stages) regardless of ``n_micro`` — the schedule to use when you
  scale microbatches to shrink the bubble fraction.

Both compose with data parallelism (``batch_axis``: each data slice
runs its own pipeline; parameter cotangents are psum'd over the data
axis) and with tensor parallelism (``param_specs``: per-leaf
PartitionSpecs for the non-stacked dims, with ``block_fn`` free to use
collectives over the model axis — the Megatron column/row pattern).
"""

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def stack_params(param_trees):
    """Stack per-layer param trees along a new leading dim — the layout
    the pipeline schedules shard over the stage axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *param_trees)


def _param_in_specs(stacked_params, axis_name, param_specs):
    """Per-leaf in_specs: stage-sharded leading dim + the caller's TP
    spec for the remaining dims (replicated when param_specs is None)."""
    if param_specs is None:
        return P(axis_name)
    def join(spec):
        return P(axis_name, *tuple(spec))
    return jax.tree_util.tree_map(
        join, param_specs, is_leaf=lambda v: isinstance(v, P))


def _check_shapes(stacked_params, h, mesh, axis_name, n_micro, batch_axis):
    n_stages = mesh.shape[axis_name]
    B = h.shape[0]
    dp = mesh.shape[batch_axis] if batch_axis else 1
    if B % (n_micro * dp):
        raise ValueError(
            f"batch {B} not divisible by n_micro={n_micro} x dp={dp}")
    L = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if L % n_stages:
        raise ValueError(f"{L} layers not divisible by {n_stages} stages")
    return n_stages


def _apply_local(block_fn, params, x):
    # this stage's slice of the layer stack, in order
    return jax.lax.scan(lambda c, p: (block_fn(p, c), None), x, params)[0]


def _vma_of(x):
    """Varying-manifest axes of a traced value (vma type system)."""
    return tuple(getattr(jax.typeof(x), "vma", ()))


def _pcast_to(x, axes):
    """Promote ``x`` to varying over ``axes`` (no-op where already)."""
    missing = tuple(a for a in axes if a not in _vma_of(x))
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def pipelined_forward(block_fn: Callable[[Any, Any], Any], stacked_params,
                      h, *, mesh, axis_name="stage", n_micro=None,
                      batch_axis=None, param_specs=None, remat=False):
    """Run ``h`` through the stacked layers as a GPipe pipeline.

    ``block_fn(layer_params, x) -> x`` applies ONE layer. ``stacked_params``
    has every leaf stacked ``[L, ...]``; L must divide by the stage-axis
    size (each stage scans its local layers in order). ``h`` is the input
    activation ``[B, ...]`` with the per-shard batch divisible by
    ``n_micro`` (default: one microbatch per stage).

    ``batch_axis`` composes PP with DP: ``h``'s leading dim shards over
    that mesh axis and each data slice runs its own pipeline; the stacked
    params are replicated across ``batch_axis``, so their reverse-mode
    cotangents are psum'd over it by the ``shard_map`` transpose — the
    gradient allreduce falls out for free.

    ``param_specs`` composes PP with TP: a tree of ``PartitionSpec``s for
    the per-layer (unstacked) dims — e.g. ``P(None, 'model')`` for a
    column-parallel kernel — and ``block_fn`` may use collectives over
    the model axis (its AD transpose handles the backward collectives).

    CONTRACT (round 4, breaking): the pipeline runs under
    ``check_vma=True``, so a ``block_fn`` using collectives must be
    vma-correct — promote replicated operands with
    ``jax.lax.pcast(x, axis, to='varying')`` before mixing them into a
    ``psum``. Plain (collective-free) blocks need no change. See
    docs/PARALLELISM.md for the canonical TP block.

    Bubble ticks take a ``lax.cond`` fast path (identity) instead of a
    full layer-stack application, so the (n_stages-1) bubble slots cost
    a branch each rather than compute.

    ``remat=True`` wraps each layer application in ``jax.checkpoint``:
    the scan saves only per-layer boundaries and recomputes block
    internals in backward — the knob between GPipe's O(n_micro)
    full-residual memory and 1F1B's O(n_stages) schedule.
    """
    n_stages = mesh.shape[axis_name]
    if n_micro is None:
        n_micro = n_stages
    _check_shapes(stacked_params, h, mesh, axis_name, n_micro, batch_axis)
    if remat:
        block_fn = jax.checkpoint(block_fn)

    def inner(params, h):
        n = jax.lax.axis_size(axis_name)
        s = jax.lax.axis_index(axis_name)
        micro = h.reshape(n_micro, h.shape[0] // n_micro, *h.shape[1:])
        micro = _pcast_to(micro, (axis_name,) +
                          ((batch_axis,) if batch_axis else ()))

        def tick(carry, t):
            state, outs = carry
            x_in = jax.lax.dynamic_index_in_dim(
                micro, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            cur = jnp.where(s == 0, x_in, state)
            # bubble skip: stage s computes micro t-s; out-of-range ticks
            # pass the activation through untouched (no compute, and no
            # NaN-able math on garbage — norm-blocks stay safe)
            valid = (t - s >= 0) & (t - s < n_micro)
            y = jax.lax.cond(
                valid, lambda p, c: _apply_local(block_fn, p, c),
                lambda p, c: c, params, cur)
            idx = t - (n - 1)
            upd = jax.lax.dynamic_update_index_in_dim(
                outs, y, jnp.clip(idx, 0, n_micro - 1), 0)
            take = (s == n - 1) & (idx >= 0) & (idx < n_micro)
            outs = jnp.where(take, upd, outs)
            # hand my output to the next stage (stage 0 receives zeros)
            state = jax.lax.ppermute(
                y, axis_name, [(i, i + 1) for i in range(n - 1)])
            return (state, outs), None

        state0 = micro[0]
        outs0 = jnp.zeros_like(micro)
        (_, outs), _ = jax.lax.scan(
            tick, (state0, outs0), jnp.arange(n_micro + n_stages - 1))
        # replicate the finished microbatches from the last stage
        outs = jax.lax.psum(
            jnp.where(s == n - 1, outs, jnp.zeros_like(outs)), axis_name)
        return outs.reshape(h.shape)

    io_spec = P(batch_axis) if batch_axis else P()
    # check_vma=True: same varying-manifest contract as the 1F1B path,
    # so one (vma-correct) block_fn serves both schedules and the AD
    # transpose of a TP block's pcast/psum lands the right collectives
    return jax.shard_map(inner, mesh=mesh,
                         in_specs=(_param_in_specs(stacked_params,
                                                   axis_name, param_specs),
                                   io_spec),
                         out_specs=io_spec,
                         check_vma=True)(stacked_params, h)


def _schedule_1f1b(n_stages, n_micro):
    """Static 1F1B schedule table, computed in Python at trace time.

    Greedy lockstep simulation (one F or B slot per stage per tick):
    a stage prefers backward once its in-flight count reaches
    ``min(n_micro, n_stages - s)`` — the classic warmup / steady-1F1B /
    cooldown shape. Returns ``(fwd, bwd)`` int arrays ``[T, n_stages]``
    holding the microbatch index each stage processes (-1 = idle), with
    peak in-flight microbatches per stage <= n_stages by construction.
    """
    fdone = [0] * n_stages
    bdone = [0] * n_stages
    f_tick = [[-1] * n_micro for _ in range(n_stages)]
    b_tick = [[-1] * n_micro for _ in range(n_stages)]
    fwd, bwd = [], []
    t = 0
    while bdone[0] < n_micro:
        frow = [-1] * n_stages
        brow = [-1] * n_stages
        for s in range(n_stages):
            m_f, m_b = fdone[s], bdone[s]
            f_ready = m_f < n_micro and (
                s == 0 or (0 <= f_tick[s - 1][m_f] < t))
            if s == n_stages - 1:
                b_ready = m_b < n_micro and 0 <= f_tick[s][m_b] < t
            else:
                b_ready = m_b < n_micro and 0 <= b_tick[s + 1][m_b] < t
            inflight = m_f - m_b
            max_inflight = min(n_micro, n_stages - s)
            # in-flight may NEVER exceed max_inflight: the ring buffers
            # (and the saved-input slots the backward recomputes from)
            # are sized by it — a stage at capacity idles until its next
            # backward is ready rather than clobbering a live slot
            if b_ready and (inflight >= max_inflight or m_f == n_micro):
                brow[s] = m_b
            elif f_ready and inflight < max_inflight:
                frow[s] = m_f
            elif b_ready:
                brow[s] = m_b
        for s in range(n_stages):
            if frow[s] >= 0:
                f_tick[s][frow[s]] = t
                fdone[s] += 1
            if brow[s] >= 0:
                b_tick[s][brow[s]] = t
                bdone[s] += 1
        fwd.append(frow)
        bwd.append(brow)
        t += 1
        if t > 4 * (n_micro + n_stages) + 8:
            raise RuntimeError("1F1B schedule did not converge")
    return np.asarray(fwd, np.int32), np.asarray(bwd, np.int32)


def pipeline_train_1f1b(block_fn: Callable[[Any, Any], Any], stacked_params,
                        h, per_micro_loss: Callable[[Any, Any], Any], *,
                        mesh, axis_name="stage", n_micro=None,
                        batch_axis=None, param_specs=None,
                        with_input_grad=False):
    """One 1F1B training step: ``(loss, stacked_grads)``.

    Unlike ``pipelined_forward`` (differentiate it yourself), this IS
    the forward+backward: the schedule interleaves one forward and one
    backward slot per stage per tick, backward recomputes the stage's
    forward from its saved INPUT via ``jax.vjp`` (stage-granular remat),
    and every buffer is a ring of ``n_stages`` microbatch activations —
    activation memory is O(n_stages), not O(n_micro).

    ``per_micro_loss(y, m) -> scalar`` scores the last stage's output
    for microbatch ``m``; the returned ``loss`` (and the grads) are the
    SUM over microbatches (and over ``batch_axis`` slices) — normalize
    inside ``per_micro_loss`` for a mean. ``stacked_grads`` matches
    ``stacked_params``'s layout and sharding. ``with_input_grad=True``
    appends d(loss)/d(h).

    ``batch_axis`` / ``param_specs`` compose with DP / TP exactly as in
    ``pipelined_forward`` (here the cross-data psum of the grads is
    explicit rather than an AD transpose).
    """
    n_stages = mesh.shape[axis_name]
    if n_micro is None:
        n_micro = n_stages
    _check_shapes(stacked_params, h, mesh, axis_name, n_micro, batch_axis)
    fwd_sched, bwd_sched = _schedule_1f1b(n_stages, n_micro)
    fwd_sched, bwd_sched = jnp.asarray(fwd_sched), jnp.asarray(bwd_sched)

    def inner(params, h):
        S = jax.lax.axis_size(axis_name)
        s = jax.lax.axis_index(axis_name)
        micro = h.reshape(n_micro, h.shape[0] // n_micro, *h.shape[1:])
        # canonical vma for the tick-loop state: varying over the stage
        # (every stage computes different values) and the data slice
        base = (axis_name,) + ((batch_axis,) if batch_axis else ())
        micro = _pcast_to(micro, base)
        ring = lambda: _pcast_to(  # noqa: E731
            jnp.zeros((n_stages,) + micro.shape[1:], micro.dtype), base)
        # grad accumulator: cotangents carry their PRIMAL's manifest
        # (the vma-typed pullback psums over axes the param does not
        # vary on — incl. the data axis — by itself), so the
        # accumulator keeps exactly the params' vma
        zero_grads = jax.tree_util.tree_map(jnp.zeros_like, params)
        zero_loss = _pcast_to(jnp.zeros((), jnp.float32), base)

        def tick(carry, t):
            inbox_f, saved_x, inbox_b, grads, dh, loss_acc = carry
            frow = fwd_sched[t]
            brow = bwd_sched[t]
            f_m = frow[s]
            b_m = brow[s]

            # ---- forward slot
            f_mc = jnp.maximum(f_m, 0)
            x_f = jnp.where(s == 0, micro[jnp.clip(f_m, 0, n_micro - 1)],
                            inbox_f[f_mc % n_stages])
            # last stage's forward output is never consumed (it is not
            # a ppermute source, and its backward recomputes from
            # saved_x inside vjp) — skip that dead layer-slice apply
            y_send = jax.lax.cond(
                (f_m >= 0) & (s < S - 1),
                lambda p, x: _apply_local(block_fn, p, x),
                lambda p, x: x, params, x_f)
            saved_x = jnp.where(f_m >= 0,
                                saved_x.at[f_mc % n_stages].set(x_f),
                                saved_x)

            # ---- backward slot (remat: re-run this stage's forward
            # from the saved input inside vjp)
            b_mc = jnp.maximum(b_m, 0)
            x_b = saved_x[b_mc % n_stages]
            dy_b = inbox_b[b_mc % n_stages]

            def canon(dp, dx, loss_m):
                # cond branches must agree on vma: promote every output
                # to the accumulator manifests (no-op when already there)
                dp = jax.tree_util.tree_map(
                    lambda v, t: _pcast_to(v, _vma_of(t)), dp, zero_grads)
                return dp, _pcast_to(dx, base), _pcast_to(loss_m, base)

            def b_run(p, x, dy, m):
                def last_branch(_):
                    loss_m, pull = jax.vjp(
                        lambda p_, x_: per_micro_loss(
                            _apply_local(block_fn, p_, x_), m).astype(
                                jnp.float32), p, x)
                    # seed inherits the primal's varying manifest
                    dp, dx = pull(loss_m * 0 + 1)
                    return canon(dp, dx, loss_m)
                def mid_branch(_):
                    y, pull = jax.vjp(
                        lambda p_, x_: _apply_local(block_fn, p_, x_),
                        p, x)
                    dp, dx = pull(_pcast_to(dy, _vma_of(y)))
                    return canon(dp, dx, zero_loss)
                return jax.lax.cond(s == S - 1, last_branch, mid_branch,
                                    None)

            dp, dx_send, loss_m = jax.lax.cond(
                b_m >= 0, b_run,
                lambda p, x, dy, m: canon(zero_grads, jnp.zeros_like(x),
                                          zero_loss),
                params, x_b, dy_b, b_mc)
            grads = jax.tree_util.tree_map(jnp.add, grads, dp)
            loss_acc = loss_acc + loss_m
            if with_input_grad:  # static: dh carry only when requested
                dh = jnp.where((s == 0) & (b_m >= 0),
                               dh.at[b_mc].set(dx_send), dh)

            # ---- exchange: activations right, cotangents left; the
            # receiver knows the arriving micro from the sender's
            # schedule row
            y_right = jax.lax.ppermute(
                y_send, axis_name, [(i, i + 1) for i in range(S - 1)])
            dx_left = jax.lax.ppermute(
                dx_send, axis_name, [(i, i - 1) for i in range(1, S)])
            arr_f = frow[(s - 1) % S]
            inbox_f = jnp.where(
                (s > 0) & (arr_f >= 0),
                inbox_f.at[jnp.maximum(arr_f, 0) % n_stages].set(y_right),
                inbox_f)
            arr_b = brow[(s + 1) % S]
            inbox_b = jnp.where(
                (s < S - 1) & (arr_b >= 0),
                inbox_b.at[jnp.maximum(arr_b, 0) % n_stages].set(dx_left),
                inbox_b)
            return (inbox_f, saved_x, inbox_b, grads, dh, loss_acc), None

        dh0 = jnp.zeros_like(micro) if with_input_grad else \
            _pcast_to(jnp.zeros((), micro.dtype), base)
        carry0 = (ring(), ring(), ring(), zero_grads, dh0, zero_loss)
        (_, _, _, grads, dh, loss_acc), _ = jax.lax.scan(
            tick, carry0, jnp.arange(fwd_sched.shape[0]))

        # loss lives on the last stage, dh on stage 0: replicate both.
        # grads need NO cross-data psum: the vma-typed pullback already
        # reduced them onto the params' manifest.
        loss = jax.lax.psum(loss_acc, axis_name)
        if batch_axis:
            loss = jax.lax.psum(loss, batch_axis)
        if not with_input_grad:
            return loss, grads
        dh = jax.lax.psum(
            jnp.where(s == 0, dh, jnp.zeros_like(dh)), axis_name)
        return loss, grads, dh.reshape(h.shape)

    p_specs = _param_in_specs(stacked_params, axis_name, param_specs)
    io_spec = P(batch_axis) if batch_axis else P()
    out_specs = (P(), p_specs) + ((io_spec,) if with_input_grad else ())
    # check_vma=True: the varying-manifest type system is what makes the
    # per-microbatch jax.vjp transpose collectives correctly when
    # block_fn is tensor-parallel (pcast-to-varying transposes to psum,
    # psum to pcast) — with it, TP input-cotangents come back complete
    # instead of per-model-shard partials.
    return jax.shard_map(
        inner, mesh=mesh, in_specs=(p_specs, io_spec),
        out_specs=out_specs,
        check_vma=True)(stacked_params, h)
