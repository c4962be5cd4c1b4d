"""A Mamba-2 state-space mixer, the training form, with a chunked scan.

A mixer kind of ``models/transformer.py`` beside ``mha``, ``mla`` and
``kda``: the mixer of the ``nemotron_h`` family's ``M`` layers. For a
normed input ``y`` [S, d_model], ``H`` heads of ``P`` channels, ``G``
groups that share one ``B`` and one ``C`` of ``N`` states each (head ``h``
reads group ``h // (H / G)``), and a causal depthwise convolution of
``K`` taps:

    [z | xBC | dt] = y W_in              d_model -> H*P + (H*P + 2*G*N) + H
    xBC = silu(conv1d(xBC))              depthwise, K - 1 zeros left, bias
    [u | B | C] = xBC                    u [S, H, P];  B, C [S, G, N]
    D_t,h = softplus(dt_t,h + dt_bias_h) the step, float32
    a_t,h = exp(D_t,h * A_h),  A_h = -exp(A_log_h)
    S_t,h = a_t,h * S_t-1,h + D_t,h * u_t,h (x) B_t,g(h)     S in R^{P x N}
    o_t,h = S_t,h C_t,g(h) + D_h * u_t,h
    o = GroupRMSNorm(o * silu(z))        gate first, then RMS over a group
    out = o W_out                        H*P -> d_model

``chunked_scan`` computes the recurrence in chunks of ``chunk_size``
positions (the state-space-duality form): inside a chunk the lower
triangle of decay products times ``C B^T`` multiplies ``D * u`` as
matrix products; each chunk's end state is one more product; the state is
carried over the chunks; and what a chunk inherits reaches its positions
through ``C S`` times the decay since the chunk's start. The step, the
cumulative log-decay (differences BEFORE the ``exp``: a product of decays
underflows, a ratio of them overflows) and the carried state are float32;
the operands of the products are the module's dtype with float32
accumulation.

Two paths compute it, chosen by the shape alone. Heads that share whole
128-lane slabs, a group's heads and the states whole slabs and a chunk of
whole sublane tiles (the published 64 heads of 64 in 8 groups of 128
states, chunks of 128) take the Pallas kernels of ``ops/ssm_scan.py``: a
group's chunk lives in VMEM from its inputs to its outputs, the states of
the group's heads are carried in a VMEM scratch, and a backward kernel of
its own walks the chunks in reverse from the states the forward kept.
Every other size (the small cells of the tests) takes ``_plain_scan``
below, plain ``jax.numpy`` differentiated by jax and recomputed in the
backward pass (``jax.checkpoint``: its per-chunk decay matrices and states
are never kept), which is also what the kernels are tested against: all
chunks' products at once as batched matrix products and a ``lax.scan``
that carries the state over them.

Two device scopes: ``hvd_ssm_scan`` from ``(u, B, C, D)`` to ``o`` (either
path, forward and backward), and ``hvd_ssm`` for the rest of the mixer.

Training only: decode against a convolution window and a state cache is
ROADMAP's (queue R), and ``cache=`` is refused, not approximated.
"""

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.ops import ssm_scan
from horovod_tpu.telemetry import scopes


@dataclasses.dataclass(frozen=True)
class StateSpaceConfig:
    num_heads: int = 64
    head_dim: int = 64
    n_groups: int = 8
    state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    # the step's initial range and floor (dt_bias is its inverse softplus)
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4


def chunked_scan(u, b, c, dt, a, d_skip, chunk):
    """``o`` [B, S, H, P] of the recurrence in the module's docstring.

    u [B, S, H, P]; b, c [B, S, G, N]; dt [B, S, H] float32, positive;
    a [H] float32, negative; d_skip [H]. ``chunk`` must divide S. The
    products run in ``u.dtype`` with float32 accumulation.

    The shape alone chooses the path: sizes that fill the lanes take the
    kernels (``ops/ssm_scan.py``), which keep their inputs and the state
    each chunk inherits for a backward kernel of their own; every other
    size goes through ``_plain_scan``, recomputed in the backward pass.
    Either way a caller wraps this in no ``jax.checkpoint``."""
    if ssm_scan.supported(chunk, u.shape[3], u.shape[2] // b.shape[2],
                          b.shape[3], u.dtype):
        return ssm_scan.ssm_scan(u, b, c, dt, a, d_skip, chunk)
    # a chunk's decay matrices and states are eight times the size of
    # what goes in
    return jax.checkpoint(_plain_scan, static_argnums=(6,))(
        u, b, c, dt, a, d_skip, chunk)


def _plain_scan(u, b, c, dt, a, d_skip, chunk):
    """``chunked_scan`` in plain ``jax.numpy`` differentiated by jax."""
    f32, dtype = jnp.float32, u.dtype
    bsz, s, h, p = u.shape
    g, n = b.shape[2:]
    r, nc = h // g, s // chunk
    dot = lambda spec, x, y: jnp.einsum(  # noqa: E731
        spec, x, y, preferred_element_type=f32)
    # chunks, heads as (group, head of the group): c chunk, l/m position,
    # g group, r head, p channel, n state
    u_c = u.reshape(bsz, nc, chunk, g, r, p)
    b_c = b.reshape(bsz, nc, chunk, g, n)
    c_c = c.reshape(bsz, nc, chunk, g, n)
    dt_c = dt.reshape(bsz, nc, chunk, g, r)
    # log of the decay up to and including each position of its chunk
    cum = jnp.cumsum(dt_c * a.reshape(g, r), axis=2)
    cum = cum.transpose(0, 1, 3, 4, 2)                       # [B, c, g, r, l]
    du = (u_c.astype(f32) * dt_c[..., None]).astype(dtype)   # the step's input

    # inside a chunk: (L o C B^T) (D u), L the decay from m to l, l >= m
    visible = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        visible, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    cb = dot("bclgn,bcmgn->bcglm", c_c, b_c)
    scores = (cb[:, :, :, None] * decay).astype(dtype)   # [B, c, g, r, l, m]
    inside = dot("bcgrlm,bcmgrp->bclgrp", scores, du)

    # each chunk's own end state, and the states carried between chunks
    to_end = jnp.exp(cum[..., -1:] - cum).transpose(0, 1, 4, 2, 3)
    ends = dot("bcmgrp,bcmgn->bcgrpn",
               (du.astype(f32) * to_end[..., None]).astype(dtype), b_c)
    whole = jnp.exp(cum[..., -1])                            # [B, c, g, r]

    def carry(state, chunk_of):
        end, through = chunk_of
        return through[..., None, None] * state + end, state

    _, before = jax.lax.scan(
        carry, jnp.zeros((bsz, g, r, p, n), f32),
        (ends.swapaxes(0, 1), whole.swapaxes(0, 1)))
    before = before.swapaxes(0, 1)                       # [B, c, g, r, p, n]
    inherited = dot("bclgn,bcgrpn->bclgrp", c_c, before.astype(dtype))
    inherited = inherited * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]

    skip = d_skip.astype(f32).reshape(g, r)[..., None] * u_c.astype(f32)
    return (inside + inherited + skip).astype(dtype).reshape(bsz, s, h, p)


def _dt_bias_init(cfg):
    """The step log-uniform in [time_step_min, time_step_max], floored,
    through the inverse of softplus."""
    def init(key, shape, dtype=jnp.float32):
        lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, lo, hi)), cfg.time_step_floor)
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                      16.0)).astype(dtype)


class CausalConv1d(nn.Module):
    """Depthwise over the channels, ``taps - 1`` zeros on the left, with
    bias unless ``use_bias`` is off: ``out_t = bias + sum_k kernel[k] *
    x_{t - (taps - 1) + k}``, as shifted multiplies that fuse into one
    pass."""
    taps: int
    dtype: object = jnp.bfloat16
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        channels, s = x.shape[-1], x.shape[1]
        kernel = self.param(
            "kernel", nn.initializers.variance_scaling(
                1.0 / 3.0, "fan_in", "uniform", in_axis=0, out_axis=1),
            (self.taps, channels))
        out = 0
        if self.use_bias:
            out = self.param("bias", lambda key, shape: jax.random.uniform(
                key, shape, jnp.float32, -1.0, 1.0) / math.sqrt(self.taps),
                (channels,)).astype(self.dtype)
        x = x.astype(self.dtype)
        padded = jnp.pad(x, ((0, 0), (self.taps - 1, 0), (0, 0)))
        for k in range(self.taps):
            out = out + kernel[k].astype(self.dtype) * padded[:, k:k + s]
        return out


class GatedGroupNorm(nn.Module):
    """``rmsnorm_per_group(o * silu(z)) * scale``: the gate first, then
    the root mean square over each group's channels, float32 inside."""
    groups: int
    eps: float
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, o, z):
        scale = self.param("scale", nn.initializers.ones, (o.shape[-1],))
        x = o.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
        grouped = x.reshape(*x.shape[:-1], self.groups, -1)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(jnp.square(grouped), -1, keepdims=True) + self.eps)
        return (grouped.reshape(x.shape) * scale).astype(self.dtype)


class StateSpaceMixer(nn.Module):
    """``cfg`` is the model's ``TransformerConfig`` with ``cfg.ssm`` set."""
    cfg: object

    @nn.compact
    def __call__(self, x, positions=None, contiguous_positions=False,
                 cache=None):
        del positions, contiguous_positions  # the recurrence is the order
        cfg, m = self.cfg, self.cfg.ssm
        if cache is not None:
            raise NotImplementedError(
                "a state-space layer decodes against a convolution window "
                "and a state cache (ROADMAP queue R); this module trains "
                "only")
        if cfg.sequence_axis is not None:
            raise NotImplementedError(
                "the state-space mixer has no sequence-sharded schedule: "
                "build it with sequence_axis=None")
        if not cfg.causal:
            raise ValueError("a state-space mixer is causal by construction")
        bsz, s, _ = x.shape
        if s % m.chunk_size:
            raise ValueError(f"the chunked scan takes sequences that "
                             f"chunk_size {m.chunk_size} divides; got {s}")
        h, p, g, n = m.num_heads, m.head_dim, m.n_groups, m.state_size
        if h % g:
            raise ValueError(f"{h} heads do not split into {g} groups")
        inner, bc = h * p, 2 * g * n
        dense = lambda features, name: nn.Dense(  # noqa: E731
            features, dtype=cfg.dtype, use_bias=False, name=name)
        dt_bias = self.param("dt_bias", _dt_bias_init(m), (h,))
        a_log = self.param("A_log", _a_log_init, (h,))
        d_skip = self.param("D", nn.initializers.ones, (h,))
        with scopes.device(scopes.SSM):
            z, xbc, dt = jnp.split(dense(2 * inner + bc + h, "in_proj")(x),
                                   [inner, 2 * inner + bc], axis=-1)
            xbc = nn.silu(CausalConv1d(m.conv_kernel, dtype=cfg.dtype,
                                       name="conv1d")(xbc))
            u, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
            step = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        with scopes.device(scopes.SSM_SCAN):
            o = chunked_scan(
                u.reshape(bsz, s, h, p), b.reshape(bsz, s, g, n),
                c.reshape(bsz, s, g, n), step,
                -jnp.exp(a_log.astype(jnp.float32)), d_skip, m.chunk_size)
        with scopes.device(scopes.SSM):
            o = GatedGroupNorm(g, cfg.norm_eps, dtype=cfg.dtype,
                               name="norm")(o.reshape(bsz, s, inner), z)
            return dense(cfg.d_model, "out_proj")(o)
