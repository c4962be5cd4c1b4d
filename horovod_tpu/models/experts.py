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
products over the ragged groups. The buffers hold all ``T * k`` slots,
the most that can ever be held, so no slot is dropped whatever the
imbalance, and shapes stay static with no capacity.

The grouped product on the TPU is the Pallas megablox kernel
(``jax.experimental.pallas.ops.tpu.megablox``), which walks only the
tiles of the held groups; elsewhere ``jax.lax.ragged_dot``. (On the TPU
``ragged_dot`` compiles to the compiler's own Mosaic kernels, whose
``op_name`` is ``ragged-dot-none``: the scopes below, and the direction of
the pass, would be lost on the device trace. PERF.md, PR 27.)
"""

import dataclasses
import warnings
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.telemetry import scopes

GMM_ROWS = (512, 256, 128)  # of a megablox tile: the first that divides T * k


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
    """``x[index]`` with the rows read from past ``total`` set to zero:
    the grouped products never visit those rows, so what they hold is
    undefined. (The select fuses into the gather: no pass of its own.)"""
    return jnp.where((index < total)[:, None], x[index], 0)


@jax.custom_vjp
def _dispatch(y, order, inverse, total):
    """Token-slot ``order[r]`` into row ``r``. Slots are numbered
    choice-major, slot ``j * T + i`` is choice ``j`` of token ``i``, so
    ``[k * T, d]`` splits into ``[k, T, d]`` along its major axis and no
    tile is re-laid. The backward is written as the gather it is (each
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
    a slot of an absent expert reads a row past ``total``: zero),
    weighted by ``w`` [k, T] and summed. Backward: a row's gradient is
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


class ExpertShare(nn.Module):
    """``y [T, d] -> [T, d]``: the routed part of the layer's result that
    the held experts give. ``cfg`` is an ``ExpertShareConfig``."""
    cfg: ExpertShareConfig
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, y):
        c = self.cfg
        t, d = y.shape
        n, held, k, f = (c.n_routed_experts, c.experts_held,
                         c.num_experts_per_tok, c.moe_d_ff)
        if not 0 <= c.expert_offset <= n - held:
            raise ValueError(f"experts {c.expert_offset}.."
                             f"{c.expert_offset + held} of {n}")
        if c.expert_body not in BODIES:
            raise ValueError(f"unknown expert body {c.expert_body!r}")
        gated = c.expert_body == "swiglu"
        expert = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                              batch_axis=(0,))
        if gated:
            w_gate = self.param("gate_proj", expert, (held, d, f))
        w_up = self.param("up_proj", expert, (held, d, f))
        w_down = self.param("down_proj", expert, (held, f, d))
        router = self.param("router", nn.initializers.lecun_normal(), (d, n))
        bias = self.param("e_score_correction_bias",
                          nn.initializers.normal(c.selection_bias_std)
                          if c.selection_bias_std else nn.initializers.zeros,
                          (n,))

        with scopes.device(scopes.MOE_ROUTE):
            # the router in float32: a score's eighth bit decides a choice
            scores = jax.nn.sigmoid(jnp.dot(
                y.astype(jnp.float32), router,
                precision=jax.lax.Precision.HIGHEST))
            idx, w = route(scores, bias, k, c.routed_scaling_factor)
            # for a caller that asks (mutable=["intermediates"]); else no-op
            self.sow("intermediates", "chosen", idx)
            # choice-major slots: slot j * T + i is choice j of token i
            local = idx.T.reshape(-1) - c.expert_offset
            is_held = (local >= 0) & (local < held)
            local = jnp.where(is_held, local, held)  # absent: sorted last
            order = jnp.argsort(local, stable=True)
            inverse = jnp.argsort(order)
            group_sizes = jnp.sum(local[:, None] == jnp.arange(held), axis=0,
                                  dtype=jnp.int32)
            total = jnp.sum(group_sizes)
            xs = _dispatch(y.astype(self.dtype), order, inverse, total)

        with scopes.device(scopes.MOE_EXPERTS):
            cast = lambda a: a.astype(self.dtype)  # noqa: E731
            if gated:
                # gate and up as one product: xs is read once, and one
                # gradient comes back to it
                gate_up = grouped_matmul(
                    xs, jnp.concatenate([cast(w_gate), cast(w_up)], 2),
                    group_sizes)
                gate, up = jnp.split(gate_up, 2, axis=1)
                hidden = nn.silu(gate) * up
            else:
                hidden = _relu2(grouped_matmul(xs, cast(w_up), group_sizes))
            out = grouped_matmul(hidden, cast(w_down), group_sizes)

        with scopes.device(scopes.MOE_ROUTE):
            return _combine(out, w.T, order, inverse, total)
