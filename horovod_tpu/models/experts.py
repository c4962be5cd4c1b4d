"""A no-drop expert layer that holds a share of the experts.

The sparse feed-forward of the DeepSeek-V3 family, as one chip of an
expert-parallel deployment sees it. The layer is told how many routed
experts exist (``n_routed_experts``), how many it holds (``experts_held``)
and which (``expert_offset`` .. ``expert_offset + experts_held``). It
scores and chooses over ALL experts and computes the part of the result
its own experts give:

    s   = sigmoid(y Wr)                      float32, [T, n_routed_experts]
    idx = top_k(s + b)                       b: selection bias, enters the
                                             choice only, no gradient
    w   = s[idx] / (sum(s[idx]) + 1e-20) * routed_scaling_factor
    out = sum over the k with idx_k held of  w_k * E_idx_k(y)

The weights are normalised over all k chosen, held or not; a token-slot
whose expert is not held adds nothing, and what the absent experts would
have added is left out, not stood in for: there is no exchange on one
chip. The shared experts (``shared_expert``: one module of width
``n_shared_experts * moe_d_ff``, or ``shared_d_ff``) are the caller's to
add; every share computes them alike.

An expert's body is ``expert_body``: ``"swiglu"``, ``(silu(y Wg) * (y
Wu)) Wd``, three matrices (the DeepSeek-V3 family's), or ``"relu2"``,
``relu(y Wu)^2 Wd``, two (the ``nemotron_h`` family's). Routed and shared
experts have the same body; the routing and the dispatch do not know it.

Routing is sort-and-gather: the ``T * k`` token-slots are sorted by
expert (held experts first, every slot of an absent expert after them),
the tokens gathered into that order, and the experts applied as grouped
products over the ragged groups.

The buffers in expert order (the gathered tokens, the experts' hidden
rows and output, and their gradients) hold ``rows`` rows, a bound on the
slots the held experts can receive that follows from shapes alone:
``HELD_ROOM`` times the ``T * k * experts_held / n_routed_experts`` slots
that balance would send them, rounded up to a megablox row tile, and
never more than ``T * k`` (``held_rows``). A step whose held slots pass
the bound takes the way out (``jax.lax.cond`` on ``total > rows``, under
the device scope ``hvd_moe_overflow``): the same body over one window of
``rows`` rows of the expert order after another, as many as the held
slots fill, their parts of the result summed. No slot is
dropped whatever the imbalance, shapes stay static with no capacity, and
no buffer in expert order has a row a slot. A share that holds every
expert has ``rows == T * k`` and one program with no conditional in it.
The two gathers back into token order (the weighted way back, and the
gradient's way back to the tokens) write ``T * k`` rows by nature and
read from the ``rows``-row buffers; a slot whose row lies outside the
buffer reads nothing (its index is clamped and its row masked).

The share is recomputed in the backward pass and keeps nothing but its
inputs: ``_routed`` is a ``jax.custom_vjp`` whose forward chooses the
size once and whose backward chooses it again, on the same predicate,
between each size's own recomputation and VJP. (Differentiating through
the ``cond`` would make the taken branch hand back the other's residuals,
zero-filled.)

The grouped product on the TPU is the Pallas megablox kernel
(``jax.experimental.pallas.ops.tpu.megablox``), which walks only the
tiles of the held groups; elsewhere ``jax.lax.ragged_dot``. (On the TPU
``ragged_dot`` compiles to the compiler's own Mosaic kernels, whose
``op_name`` is ``ragged-dot-none``: the scopes below, and the direction of
the pass, would be lost on the device trace. PERF.md, PR 27.)
"""

import dataclasses
import functools
import math
import warnings
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.telemetry import scopes

GMM_ROWS = (512, 256, 128)  # of a megablox tile: the first that divides the rows
# The expert-order buffers hold this many times the slots that balance
# would send the held experts. Measured on v5e (PR 32; both sparse cells,
# fourteen seeds each, every expert layer, at initialisation and every
# eighth of the first 48 steps on the cell's fixed batch, random weights):
# 0.38-1.68 times the expectation with 16 of 128 experts held (the first
# expert layer climbs to 1.25-1.68 within eight steps and stays), 0.05-
# 1.65 with 8 of 128 (1.65 at initialisation, falling as the batch is
# learnt); 10 of 784 readings past 1.5, none past 1.7; a router trained
# with its balancing bias sits near 1. At 2 a step of kanana-2-30b-a3b-
# train-s4096 runs 9.6% faster than with buffers of every slot; at 4,
# 0.7%: 49,152 rows of 2048 (201 MB) no longer fit the chip's 128 MiB of
# fast memory, which the gathers' sources of 24,576 rows (101 MB) do. Past
# the bound a step costs what buffers of every slot cost (two windows), so
# the bound is set where the common step is fastest, not where no step
# ever passes it.
HELD_ROOM = 2


class GroupedFallbackWarning(UserWarning):
    """On the TPU a grouped product ran as ``jax.lax.ragged_dot`` because
    megablox's tiles do not divide its sizes. As
    ``ops.flash_attention.FlashFallbackWarning``: shown once per shape; a
    run that must not fall back turns this category into an error."""


@dataclasses.dataclass(frozen=True)
class ExpertShareConfig:
    n_routed_experts: int = 128
    experts_held: int = 128
    expert_offset: int = 0
    num_experts_per_tok: int = 6
    moe_d_ff: int = 768
    n_shared_experts: int = 2
    routed_scaling_factor: float = 2.448
    # standard deviation the selection bias is drawn with at
    # initialisation (0: zeros, as a model trained from scratch starts)
    selection_bias_std: float = 0.0
    expert_body: str = "swiglu"  # | "relu2"
    # width of the shared experts' one module (None: n_shared_experts *
    # moe_d_ff)
    shared_d_ff: Optional[int] = None


def _relu2(x):
    return jnp.square(nn.relu(x))


class SwiGLU(nn.Module):
    """``(silu(y Wg) * (y Wu)) Wd``: the dense and the shared
    feed-forward."""
    d_ff: int
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, y):
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, dtype=self.dtype, use_bias=False, name=name)
        hidden = nn.silu(dense(self.d_ff, "gate_proj")(y)) * dense(
            self.d_ff, "up_proj")(y)
        return dense(y.shape[-1], "down_proj")(hidden)


class Relu2(nn.Module):
    """``relu(y Wu)^2 Wd``: the two-matrix feed-forward."""
    d_ff: int
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, y):
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, dtype=self.dtype, use_bias=False, name=name)
        return dense(y.shape[-1], "down_proj")(
            _relu2(dense(self.d_ff, "up_proj")(y)))


BODIES = {"swiglu": SwiGLU, "relu2": Relu2}


def shared_expert(cfg, dtype, name):
    """The shared experts of a layer sized by ``cfg``, as one module of
    the routed experts' body."""
    return BODIES[cfg.expert_body](
        cfg.shared_d_ff or cfg.n_shared_experts * cfg.moe_d_ff,
        dtype=dtype, name=name)


def _live(x, index, total):
    """``x[index]`` where ``0 <= index < total``, zero elsewhere. The
    grouped products never visit the rows from ``total`` on, so what they
    hold is undefined; and ``x`` may be a buffer of fewer rows than there
    are slots, one window of the expert order, so that an index lies
    before it or past its last row, where there is nothing to read: such
    an index reads an end row, and the mask drops it. (The select fuses
    into the gather: no pass of its own.)"""
    return jnp.where(((index >= 0) & (index < total))[:, None],
                     x[jnp.clip(index, 0, x.shape[0] - 1)], 0)


@jax.custom_vjp
def _dispatch(y, order, inverse, total):
    """Token-slot ``order[r]`` into row ``r``, for as many rows as
    ``order`` has. Slots are numbered choice-major, slot ``j * T + i`` is
    choice ``j`` of token ``i``, so ``[k * T, d]`` splits into ``[k, T,
    d]`` along its major axis and no tile is re-laid. The backward is written as the gather it is (each
    token sums its k slots), not as the scatter-add a gather's transpose
    would be."""
    del inverse, total
    return y[order % y.shape[0]]


def _dispatch_fwd(y, order, inverse, total):
    return y[order % y.shape[0]], (inverse, total, y.shape[0])


def _dispatch_bwd(res, g):
    inverse, total, t = res
    per_slot = _live(g, inverse, total).reshape(-1, t, g.shape[1])
    return (jnp.sum(per_slot.astype(jnp.float32), 0).astype(g.dtype),
            None, None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _weighted(rows, w):
    """``sum_j w[j, i] * rows[j, i]`` in float32. rows [k, T, d]."""
    return jnp.sum(rows.astype(jnp.float32) * w[..., None], 0)


@jax.custom_vjp
def _combine(out, w, order, inverse, total):
    """The way back: each token's k rows of ``out`` (in expert order;
    a slot of an absent expert, or of another window, reads a row
    outside ``0 .. total``: zero), weighted by ``w`` [k, T] and summed. Backward: a row's gradient is
    its token's, times its weight, gathered from ``[T, d]``."""
    t = w.shape[1]
    rows = _live(out, inverse, total).reshape(-1, t, out.shape[1])
    return _weighted(rows, w).astype(out.dtype)


def _combine_fwd(out, w, order, inverse, total):
    return (_combine(out, w, order, inverse, total),
            (out, w, order, inverse, total))


def _combine_bwd(res, g):
    out, w, order, inverse, total = res
    t = w.shape[1]
    # in expert order: row r is slot order[r], of token order[r] % T
    g_rows = g[order % t].astype(jnp.float32)
    d_out = (g_rows * w.reshape(-1)[order][:, None]).astype(out.dtype)
    d_w = _live(jnp.sum(out.astype(jnp.float32) * g_rows, -1, keepdims=True),
                inverse, total).reshape(w.shape)
    return d_out, d_w, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def route(scores, bias, k, scale):
    """``(idx [T, k], w [T, k])``: the experts each token chose by
    ``scores + bias`` and their weights from ``scores`` alone, normalised
    over the k and scaled. float32."""
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) * scale


def _tile(size, widths=(1024, 768, 512, 384, 256, 128)):
    """The widest of ``widths`` that divides ``size``, or None."""
    return next((t for t in widths if size % t == 0), None)


def _width(size):
    """The tile of a product's width: ``_tile``, or, where none divides it
    (an expert 1856 wide: 14.5 x 128 lanes), the whole width up to 2048 (a
    block as wide as the array is a legal block whatever the lanes)."""
    return _tile(size) or (size if size <= 2048 else None)


@jax.custom_vjp
def _gmm(xs, w, group_sizes):
    """The megablox grouped product ``[m, k] x [g, k, n] -> [m, n]`` with a
    tiling chosen for each of its three products (the library's own VJP
    hands one tiling to all three, and a tile has to divide both widths:
    at 2048 and 768 that leaves 256). Rows past the groups' end are never
    visited, forward or backward: what they hold is undefined, and a
    row's result depends on that row alone."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    (m, k), n = xs.shape, w.shape[2]
    return gmm(xs, w, group_sizes, xs.dtype,
               (_tile(m, GMM_ROWS), _width(k), _width(n)))


def _gmm_fwd(xs, w, group_sizes):
    return _gmm(xs, w, group_sizes), (xs, w, group_sizes)


def _gmm_bwd(res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    xs, w, group_sizes = res
    (m, k), n = xs.shape, w.shape[2]
    rows = _tile(m, GMM_ROWS)
    d_xs = gmm(g, w, group_sizes, xs.dtype, (rows, _width(n), _width(k)),
               transpose_rhs=True)
    d_w = tgmm(
        xs.swapaxes(0, 1), g, group_sizes, w.dtype,
        (rows, _width(k), _width(n)), num_actual_groups=w.shape[0])
    return d_xs, d_w, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(xs, w, group_sizes):
    """``xs[rows of group g] @ w[g]`` over consecutive row groups of
    ``group_sizes``; the rows past their sum come out UNDEFINED (the
    caller reads them through ``_live``). On the TPU the megablox kernel,
    which walks only the tiles of the groups; off the TPU, and with a
    ``GroupedFallbackWarning`` where the kernel's tiles do not divide the
    three sizes (a handful of tokens, a wide width off the 128 lanes),
    ``jax.lax.ragged_dot``."""
    if jax.devices()[0].platform == "tpu":
        if all((_tile(xs.shape[0], GMM_ROWS), _width(xs.shape[1]),
                _width(w.shape[2]))):
            return _gmm(xs, w, group_sizes)
        warnings.warn(
            f"models.experts.grouped_matmul: jax.lax.ragged_dot ran in "
            f"place of the megablox kernel for {tuple(xs.shape)} x "
            f"{tuple(w.shape)}: rows must divide by one of {GMM_ROWS} and "
            f"both widths by 128 (or be at most 2048)",
            GroupedFallbackWarning, stacklevel=2)
    return jax.lax.ragged_dot(xs, w, group_sizes)


def held_rows(slots, cfg):
    """The rows of the buffers in expert order, for ``slots`` token-slots
    a step: ``HELD_ROOM`` times what balance sends the held experts, up to
    a megablox row tile, and at most every slot."""
    expected = slots * cfg.experts_held / cfg.n_routed_experts
    tile = GMM_ROWS[0]
    return min(slots, math.ceil(HELD_ROOM * expected / tile) * tile)


def _prepared(c, dtype, y, params):
    """What the share starts from at either size: ``((w [k, T], the
    experts' matrices as the products take them), (idx [T, k], (order,
    inverse, group_sizes, total)))``, the second pair without a
    gradient."""
    held, k = c.experts_held, c.num_experts_per_tok
    with scopes.device(scopes.MOE_ROUTE):
        # the router in float32: a score's eighth bit decides a choice
        scores = jax.nn.sigmoid(jnp.dot(
            y.astype(jnp.float32), params["router"],
            precision=jax.lax.Precision.HIGHEST))
        idx, w = route(scores, params["e_score_correction_bias"], k,
                       c.routed_scaling_factor)
        # choice-major slots: slot j * T + i is choice j of token i
        local = idx.T.reshape(-1) - c.expert_offset
        is_held = (local >= 0) & (local < held)
        local = jnp.where(is_held, local, held)  # absent: sorted last
        order = jnp.argsort(local, stable=True)
        inverse = jnp.argsort(order)
        group_sizes = jnp.sum(local[:, None] == jnp.arange(held), axis=0,
                              dtype=jnp.int32)
        total = jnp.sum(group_sizes)
    with scopes.device(scopes.MOE_EXPERTS):
        cast = lambda name: params[name].astype(dtype)  # noqa: E731
        # gate and up as one product: the rows are read once, and one
        # gradient comes back to them
        first = (jnp.concatenate([cast("gate_proj"), cast("up_proj")], 2)
                 if c.expert_body == "swiglu" else cast("up_proj"))
        weights = first, cast("down_proj")
    return (w.T, weights), (idx, (order, inverse, group_sizes, total))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _held(c, rows, routing, window, y, w, weights):
    """The part of the result, ``[T, d]``, that the slots in rows
    ``window * rows`` .. ``(window + 1) * rows`` of the expert order give,
    through buffers of ``rows`` rows: with ``window`` 0 the held experts'
    whole part wherever ``total <= rows``. A function of its own under
    ``jax.jit``, so that the layers of a model, the two branches of
    ``_sized`` and both passes trace and lower ONE body a shape (the
    compiled program is the same: the compiler inlines it)."""
    order, inverse, group_sizes, total = routing
    first, w_down = weights
    start = window * rows
    # the window's own routing: its slots, where each slot's row lies in
    # it (before it or past it: dead), and its share of every group
    order = jax.lax.dynamic_slice_in_dim(
        jnp.pad(order, (0, -order.shape[0] % rows)), start, rows)
    ends = jnp.cumsum(group_sizes)
    inside = lambda edge: jnp.clip(edge - start, 0, rows)  # noqa: E731
    group_sizes = inside(ends) - inside(ends - group_sizes)
    inverse, total = inverse - start, inside(total)
    with scopes.device(scopes.MOE_ROUTE):
        xs = _dispatch(y.astype(w_down.dtype), order, inverse, total)
    with scopes.device(scopes.MOE_EXPERTS):
        hidden = grouped_matmul(xs, first, group_sizes)
        if c.expert_body == "swiglu":
            gate, up = jnp.split(hidden, 2, axis=1)
            hidden = nn.silu(gate) * up
        else:
            hidden = _relu2(hidden)
        out = grouped_matmul(hidden, w_down, group_sizes)
    with scopes.device(scopes.MOE_ROUTE):
        return _combine(out, w, order, inverse, total)


def _sized(c, routing, body, like, *operands):
    """``body(rows, 0, *operands)``, the whole expert order's live part in
    one window of ``rows = held_rows(T * k, c)`` rows, where the held
    slots fit it; else the way out that is always right, the sum of
    ``body(rows, window, *operands)`` over as many windows as the held
    slots fill (shaped and typed ``like`` the result: in its own
    precision); where one window holds every slot, that program and no
    conditional."""
    order, *_, total = routing
    slots = order.shape[0]
    rows = held_rows(slots, c)
    bounded = functools.partial(body, rows, jnp.int32(0))
    if rows == slots:
        return bounded(*operands)

    def way_out(*operands):
        with scopes.device(scopes.MOE_OVERFLOW):
            add = lambda window, acc: jax.tree_util.tree_map(  # noqa: E731
                jnp.add, acc, body(rows, window, *operands))
            return jax.lax.fori_loop(
                jnp.int32(0), -(-total // rows), add, jax.tree_util.tree_map(
                    lambda a: jnp.zeros(a.shape, a.dtype), like))

    # the bounded size as the false branch, the conditional's first: what
    # the compiler finds alike in both branches it may move out of them,
    # under the first one's names (it did when the way out was one
    # straight-line body: the sums over a token's k slots), and work that
    # every step does is no overflow
    return jax.lax.cond(total > rows, way_out, bounded, *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _routed(c, dtype, y, params):
    """``(the held experts' part of the result [T, d], idx [T, k])`` of
    ``y [T, d]`` under the share's parameters. Recomputed in the backward
    pass from ``y`` and the parameters, which are all it keeps."""
    (w, weights), (idx, routing) = _prepared(c, dtype, y, params)

    def body(rows, window, y, w, weights):
        return _held(c, rows, routing, window, y, w, weights)

    like = jax.ShapeDtypeStruct(y.shape, dtype)
    return _sized(c, routing, body, like, y, w, weights), idx


def _routed_fwd(c, dtype, y, params):
    return _routed(c, dtype, y, params), (y, params)


def _routed_bwd(c, dtype, res, cotangents):
    g, _ = cotangents  # the choices carry none
    # as jax.checkpoint: what is computed again below is not merged with
    # the forward pass's, whose buffers would then live until here
    y, params, g = jax.lax.optimization_barrier((*res, g))
    (w, weights), prepared_vjp, (_, routing) = jax.vjp(
        functools.partial(_prepared, c, dtype), y, params, has_aux=True)

    def body_vjp(rows, window, y, w, weights, g):
        return jax.vjp(functools.partial(_held, c, rows, routing, window),
                       y, w, weights)[1](g)

    d_y, d_w, d_weights = _sized(c, routing, body_vjp, (y, w, weights),
                                 y, w, weights, g)
    # the optimizer's converts stay outside the conditional: moved into
    # both branches, they make every weight gradient leave it twice, in
    # bfloat16 and in float32
    d_weights = jax.lax.optimization_barrier(d_weights)
    d_y_scores, d_params = prepared_vjp((d_w, d_weights))
    with scopes.device(scopes.MOE_ROUTE):
        return d_y + d_y_scores, d_params


_routed.defvjp(_routed_fwd, _routed_bwd)


class ExpertShare(nn.Module):
    """``y [T, d] -> [T, d]``: the routed part of the layer's result that
    the held experts give. ``cfg`` is an ``ExpertShareConfig``. Its
    buffers in expert order hold ``held_rows(T * k, cfg)`` rows; a step
    that sends the held experts more slots takes the way out, window by
    window through smaller buffers, and drops nothing (the module
    docstring). Recomputed in the backward pass, so a caller wraps it in
    no ``remat``."""
    cfg: ExpertShareConfig
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, y):
        c = self.cfg
        d = y.shape[1]
        n, held, f = c.n_routed_experts, c.experts_held, c.moe_d_ff
        if not 0 <= c.expert_offset <= n - held:
            raise ValueError(f"experts {c.expert_offset}.."
                             f"{c.expert_offset + held} of {n}")
        if c.expert_body not in BODIES:
            raise ValueError(f"unknown expert body {c.expert_body!r}")
        expert = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                              batch_axis=(0,))
        shapes = {"up_proj": (held, d, f), "down_proj": (held, f, d)}
        if c.expert_body == "swiglu":
            shapes = {"gate_proj": (held, d, f), **shapes}
        params = {name: self.param(name, expert, shape)
                  for name, shape in shapes.items()}
        params["router"] = self.param(
            "router", nn.initializers.lecun_normal(), (d, n))
        params["e_score_correction_bias"] = self.param(
            "e_score_correction_bias",
            nn.initializers.normal(c.selection_bias_std)
            if c.selection_bias_std else nn.initializers.zeros, (n,))
        out, idx = _routed(c, self.dtype, y, params)
        # for a caller that asks (mutable=["intermediates"]); else no-op
        self.sow("intermediates", "chosen", idx)
        return out
