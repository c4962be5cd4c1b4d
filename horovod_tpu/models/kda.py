"""Kimi Delta Attention (KDA), the training form, with a chunked scan.

The fourth mixer kind of ``models/transformer.py`` beside ``mha``, ``mla``
and ``ssm``: the linear mixer of the ``kimi_linear`` family, a gated delta
rule whose decay is a vector, one rate a channel of the key. For a normed
input ``y`` [S, d_model], ``H`` heads of ``D`` channels (q, k and v
alike), a causal depthwise convolution of ``K`` taps and low-rank pairs of
rank ``R``, a head h at a position t:

    q~ = silu(conv(y Wq));  k~ = silu(conv(y Wk));  v = silu(conv(y Wv))
                                     depthwise, K - 1 zeros left, no bias
    q_t = q~_t / |q~_t| * D^-1/2;   k_t = k~_t / |k~_t|      L2 norm a head
    g_t = -exp(A_log_h) * softplus((y_t Wf_a) Wf_b + dt_bias)   in R^D, <= 0
    beta_t = sigmoid(y_t Wb)                                    in R
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_t-1 + beta_t k_t v_t^T
                                     S in R^{D x D} (key x value), S_0 = 0
    o_t = S_t^T q_t
    out = [rmsnorm_head(o_t) * w * sigmoid((y_t Wg_a) Wg_b + b_g)] Wo

The decay reaches the old state BEFORE the delta correction reads it: the
state is read back at ``k_t`` (``S~^T k_t``, ``S~ = Diag(exp(g_t))
S_t-1``), and ``beta_t`` times what is missing from ``v_t`` is written.

``chunked_delta_scan`` computes the recurrence in chunks of ``chunk``
positions. With ``G_t`` the running sum of ``g`` inside a chunk and
``S_0`` what the chunk inherits:

    A_ti  = beta_t sum_c k_t,c k_i,c exp(G_t,c - G_i,c)   i < t, else 0
    (I + A) U = Diag(beta) (V - (K * exp(G)) S_0)         unit lower triangle
    o_t   = (q_t * exp(G_t))^T S_0
            + sum_{i<=t} [sum_c q_t,c k_i,c exp(G_t,c - G_i,c)] u_i
    S_C   = Diag(exp(G_C)) S_0 + sum_i (k_i * exp(G_C - G_i)) u_i^T

The decay between two positions is a vector, so it stays inside the sum
over channels, and it is always formed from a DIFFERENCE of ``G`` that is
not positive (``exp(G_t) * exp(-G_i)`` overflows): directly on the
``SUB x SUB`` (8 x 8) blocks of the diagonal, and for a pair in two different
blocks through the later block's first position b, ``exp(G_t - G_b) *
exp(G_b - G_i)``, both factors at most 1, which makes those blocks plain
matrix products. ``(I + A)^-1`` is built from the inverses of its
diagonal blocks (``A`` is strictly lower, so nilpotent: ``(I - A)(I +
A^2)(I + A^4)..``) merged two by two, ``[[P, 0], [X, Q]]^-1 = [[P^-1, 0],
[-Q^-1 X P^-1, Q^-1]]``. ``g``, ``G``, ``beta``, ``A``, the inverse and
the carried state are float32; the operands of the products with q, k, v
and u are the inputs' dtype with float32 accumulation.

Two paths compute it, chosen by the shape alone. Channels that fill the
128 lanes and a chunk of whole sublane tiles (the published 32 heads of
128 in chunks of 64) take the Pallas kernels of ``ops/delta_scan.py``: a
chunk lives in VMEM from its inputs to its outputs, the state is carried
in a VMEM scratch, and a backward kernel of its own walks the chunks in
reverse from the states the forward kept. Every other size (the small
cells of the tests) takes ``_plain_scan`` below, plain ``jax.numpy``
differentiated by jax, which is also what the kernels are tested against:
there the products of the inverse, 8 to 32 wide, run on the vector unit
as multiplies and sums; applied to ``beta V`` and to ``beta K exp(G)`` the
inverse leaves ``U = U_v - W S_0``, so everything but what meets the state
(two stacked products a chunk) is computed for all chunks at once, and a
``lax.scan`` carries ``S`` over them, a group of heads at a time with the
group recomputed in the backward pass (``jax.checkpoint``).

Two device scopes: ``hvd_kda_scan`` from ``(q, k, v, g, beta)`` to ``o``
(either path, forward and backward), and ``hvd_kda`` for the rest of the
mixer.

Training only: decode against three convolution windows and a state cache
is ROADMAP's (queue R), and ``cache=`` is refused, not approximated.
"""

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.models.ssm import (CausalConv1d, StateSpaceConfig,
                                    _a_log_init, _dt_bias_init)
from horovod_tpu.ops import delta_scan
from horovod_tpu.telemetry import scopes

# The two constants below are ``_plain_scan``'s (the kernels of
# ``ops/delta_scan.py`` have their own ``SUB``, the sublanes of a register,
# and work on one head at a time).
# Positions of a diagonal block, whose decays are formed pair by pair in
# float32 on the vector unit (SUB x D multiplies, an exp and two sums a
# position and head, and several times that in the backward pass: most of
# the plain scan's time on a v5e); the blocks below the diagonal cost one
# exp and one multiply a position, block and channel, and a matrix product.
# The plain form at 32 heads of 128 and 8,192 tokens, forward and backward
# a layer (my chip runs, PR 33, when it ran the cell): 45.1 ms at 16, 38.2
# at 8, 38.5 at 4.
SUB = 8
# Heads whose chunks the plain form works on at once. What a pass holds
# between the scan's forward and backward halves is a few float32 arrays a
# token and head (3.6 GiB for 32 heads at those sizes, 1 GiB for 8), and the
# smaller it is the more of it stays in the chip's fast memory: 41.9 ms a
# layer at 8 heads, 38.2 at 4, 38.6 at 2 (with 16 x 16 blocks: 53.8 at 32,
# 51.8 at 8, 44.3 at 4).
HEADS_AT_ONCE = 4


@dataclasses.dataclass(frozen=True)
class DeltaAttentionConfig:
    num_heads: int = 32
    head_dim: int = 128
    conv_kernel: int = 4
    chunk_size: int = 64
    gate_rank: int = 128  # of the decay's and the output gate's low-rank pairs


def _small_product(x, y):
    """``x @ y`` for matrices a few lanes wide, [..., n, m] x [..., m, p],
    in float32 as multiplies and a sum: batched products this small leave
    most of the matrix unit idle and cost it one load of its weights each
    (six at float32's precision); the vector unit takes them in passing."""
    return jnp.sum(x[..., :, :, None] * y[..., None, :, :], -2)


def _inverse_of_unit_lower(a):
    """``(I + a)^-1`` for ``a`` [..., n, n] strictly lower triangular,
    float32."""
    n = a.shape[-1]
    if n <= SUB:
        eye = jnp.eye(n, dtype=a.dtype)
        inverse, power, terms = eye - a, a, 2  # the sum of (-a)^i, i < terms
        while terms < n:
            power = _small_product(power, power)
            inverse, terms = _small_product(inverse, eye + power), 2 * terms
        return inverse
    h = n // 2
    first = _inverse_of_unit_lower(a[..., :h, :h])
    second = _inverse_of_unit_lower(a[..., h:, h:])
    below = -_small_product(_small_product(second, a[..., h:, :h]), first)
    return jnp.concatenate([
        jnp.concatenate([first, jnp.zeros_like(a[..., :h, h:])], -1),
        jnp.concatenate([below, second], -1)], -2)


def chunked_delta_scan(q, k, v, g, beta, chunk):
    """``o`` [B, S, H, D] of the recurrence in the module's docstring.

    q, k, v [B, S, H, D], q and k already normed (and q scaled); g
    [B, S, H, D] float32, not positive; beta [B, S, H] float32. ``chunk``
    must divide S. The products with q, k, v and u run in ``v.dtype`` with
    float32 accumulation. The shape chooses the path: channels that fill
    the 128 lanes and a chunk of whole sublane tiles go through the Pallas
    kernels (``ops/delta_scan.py``), which keep their inputs and the state
    each chunk inherits for a backward pass of their own; every other size
    through ``_plain_scan``. Either way a caller wraps this in no
    ``remat``."""
    if delta_scan.supported(chunk, k.shape[-1], v.shape[-1], v.dtype):
        return delta_scan.delta_scan(q, k, v, g, beta, chunk)
    return _plain_scan(q, k, v, g, beta, chunk)


def _plain_scan(q, k, v, g, beta, chunk):
    """``chunked_delta_scan`` in plain ``jax.numpy`` differentiated by jax:
    what every size off the lanes takes, and what the kernels are tested
    against. ``HEADS_AT_ONCE`` heads at a time, each group under
    ``jax.checkpoint``: the backward pass computes a group's forward again
    and keeps nothing of it but what went in."""
    bsz, s, h = k.shape[:3]
    at_once = math.gcd(h, HEADS_AT_ONCE)
    # [B, S, H, ..] -> [group, B, head of the group, S, ..]: one transpose
    # each way, outside the loop over the groups
    groups = lambda x: jnp.moveaxis(x.reshape(  # noqa: E731
        bsz, s, h // at_once, at_once, *x.shape[3:]), (2, 3), (0, 2))
    o = jax.lax.map(
        jax.checkpoint(lambda of_group: _scan_heads(*of_group, chunk)),
        tuple(groups(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, (0, 2), (2, 3)).reshape(*v.shape)


def _scan_heads(q, k, v, g, beta, chunk):
    """``chunked_delta_scan`` over the heads it is given, all at once and
    heads first: q, k, v, g [B, H, S, D], beta [B, H, S]; o [B, H, S, D]."""
    f32, dtype = jnp.float32, v.dtype
    bsz, h, s, d = k.shape
    n, sub = s // chunk, math.gcd(chunk, SUB)
    j = chunk // sub
    dot = lambda spec, x, y: jnp.einsum(  # noqa: E731
        spec, x, y, preferred_element_type=f32)
    # heads first, then chunks: b batch, h head, n chunk, t/i position,
    # j block of the chunk, d key channel, e value channel
    chunks = lambda x: x.reshape(  # noqa: E731
        bsz, h, n, chunk, *x.shape[3:])
    qf, kf, vf = (chunks(x).astype(f32) for x in (q, k, v))
    beta = chunks(beta.astype(f32))
    cum = jnp.cumsum(chunks(g.astype(f32)), axis=3)        # G [b, h, n, t, d]
    blocks = lambda x: x.reshape(bsz, h, n, j, sub, d)  # noqa: E731
    cum_b, q_b, k_b = blocks(cum), blocks(qf), blocks(kf)

    # the decayed k.k and q.k triangles. On the diagonal blocks, pair by
    # pair: k_i decayed from i to t, for i <= t
    visible = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    k_at = k_b[..., None, :, :] * jnp.exp(jnp.where(
        visible, cum_b[..., :, None, :] - cum_b[..., None, :, :], -jnp.inf))
    kk_diagonal = jnp.sum(k_b[..., :, None, :] * k_at, -1)  # [.., j, t, i]
    qk_diagonal = jnp.sum(q_b[..., :, None, :] * k_at, -1)
    # below them, through each block's first position: rows decayed since
    # it, columns decayed up to it, and nothing from that position on
    first = cum_b[..., 0, :]                               # [b, h, n, j, d]
    since = jnp.exp(cum_b - first[..., None, :])
    before = (jnp.arange(chunk) < sub * jnp.arange(j)[:, None])[..., None]
    up_to = jnp.exp(jnp.where(
        before, first[..., :, None, :] - cum[..., None, :, :], -jnp.inf))
    k_cols = (kf[..., None, :, :] * up_to).astype(dtype)   # [.., j, i, d]
    # k's rows and q's against the same columns, in one product
    rows = (jnp.concatenate([k_b, q_b], -2)
            * jnp.concatenate([since, since], -2)).astype(dtype)
    below = dot("bhnjtd,bhnjid->bhnjti", rows, k_cols)
    placed = lambda x: (  # noqa: E731  the diagonal blocks in their places
        x[..., :, :, None, :] * jnp.eye(j, dtype=f32)[:, None, :, None]
    ).reshape(bsz, h, n, chunk, chunk)
    whole_rows = lambda x: x.reshape(  # noqa: E731
        bsz, h, n, chunk, chunk)
    kk = whole_rows(below[..., :sub, :]) + placed(kk_diagonal)
    qk = (whole_rows(below[..., sub:, :])
          + placed(qk_diagonal)).astype(dtype)              # i <= t

    # (I + A)^-1 applied to beta V and to beta K exp(G): U = U_v - W S_0
    strictly = jnp.tril(jnp.ones((chunk, chunk), f32), -1)
    inverse = _inverse_of_unit_lower(
        beta[..., None] * kk * strictly).astype(dtype)
    decayed = jnp.exp(cum)
    u_v = dot("bhnti,bhnie->bhnte", inverse,
              (beta[..., None] * vf).astype(dtype))
    w = dot("bhnti,bhnid->bhntd", inverse,
            (beta[..., None] * kf * decayed).astype(dtype)).astype(dtype)
    whole = cum[..., -1, :]                                # [b, h, n, d]
    # what meets the inherited state, W and q exp(G), as one operand; what
    # meets U, the q.k triangle and (k exp(G_C - G))^T, as another: two
    # products a chunk where there were four
    on_state = jnp.concatenate([w, (qf * decayed).astype(dtype)], -2)
    on_u = jnp.concatenate([qk, jnp.swapaxes(
        (kf * jnp.exp(whole[..., None, :] - cum)).astype(dtype), -1, -2)],
        -2)

    def carry(state, chunk_of):
        u_v, on_state, on_u, through = chunk_of
        read = dot("bhtd,bhde->bhte", on_state, state.astype(dtype))
        u = (u_v - read[..., :chunk, :]).astype(dtype)
        written = dot("bhti,bhie->bhte", on_u, u)
        state = through[..., None] * state + written[..., chunk:, :]
        return state, (read[..., chunk:, :]
                       + written[..., :chunk, :]).astype(dtype)

    chunks_first = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731
    _, o = jax.lax.scan(
        carry, jnp.zeros((bsz, h, d, v.shape[-1]), f32),
        tuple(chunks_first(x) for x in (u_v, on_state, on_u,
                                        jnp.exp(whole))))
    # [n, b, h, t, e] -> [b, h, s, e]
    return jnp.moveaxis(o, 0, 2).reshape(bsz, h, s, v.shape[-1])


def _normed(x, scale):
    """``silu(x)``, L2-normed over the last axis (eps 1e-6 inside the
    root), times ``scale``: float32 inside, ``x.dtype`` out."""
    y = nn.silu(x.astype(jnp.float32))
    y = y * jax.lax.rsqrt(jnp.sum(jnp.square(y), -1, keepdims=True) + 1e-6)
    return (y * scale).astype(x.dtype)


def _log_decay(rate, a_log, dt_bias):
    """g [B, S, H, D] float32 from ``rate`` [B, S, H * D]."""
    step = jax.nn.softplus(rate.astype(jnp.float32) + dt_bias)
    return -jnp.exp(a_log.astype(jnp.float32))[:, None] * step.reshape(
        *rate.shape[:2], a_log.shape[0], -1)


class GatedHeadNorm(nn.Module):
    """``rmsnorm(o) * scale * sigmoid(gate)`` over each head's channels,
    float32 inside."""
    eps: float
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, o, gate):
        scale = self.param("scale", nn.initializers.ones, (o.shape[-1],))
        x = o.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + self.eps)
        return (x * scale * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(
            self.dtype)


class DeltaAttention(nn.Module):
    """``cfg`` is the model's ``TransformerConfig`` with ``cfg.kda`` set."""
    cfg: object

    @nn.compact
    def __call__(self, x, positions=None, contiguous_positions=False,
                 cache=None):
        del positions, contiguous_positions  # the recurrence is the order
        cfg, m = self.cfg, self.cfg.kda
        if cache is not None:
            raise NotImplementedError(
                "a delta-rule layer decodes against three convolution "
                "windows and a state cache (ROADMAP queue R); this module "
                "trains only")
        if cfg.sequence_axis is not None:
            raise NotImplementedError(
                "delta attention has no sequence-sharded schedule: build "
                "it with sequence_axis=None")
        if not cfg.causal:
            raise ValueError("delta attention is causal by construction")
        bsz, s, _ = x.shape
        if s % m.chunk_size:
            raise ValueError(f"the chunked scan takes sequences that "
                             f"chunk_size {m.chunk_size} divides; got {s}")
        h, d = m.num_heads, m.head_dim
        dense = lambda features, name, bias=False: nn.Dense(  # noqa: E731
            features, dtype=cfg.dtype, use_bias=bias, name=name)
        heads = lambda a: a.reshape(bsz, s, h, d)  # noqa: E731
        a_log = self.param("A_log", _a_log_init, (h,))
        # softplus(dt_bias) starts log-uniform in the state-space mixer's
        # published range, 0.001 to 0.1 floored at 1e-4
        dt_bias = self.param("dt_bias", _dt_bias_init(StateSpaceConfig()),
                             (h * d,))
        # the element-by-element chains (silu and the L2 norm, the
        # softplus, the gated norm) keep what goes into them, not their
        # float32 insides: jax.checkpoint around each
        with scopes.device(scopes.KDA):
            q, k, v = (heads(CausalConv1d(
                m.conv_kernel, use_bias=False, dtype=cfg.dtype,
                name=f"{name}_conv1d")(dense(h * d, f"{name}_proj")(x)))
                for name in "qkv")
            q = jax.checkpoint(_normed)(q, d ** -0.5)
            k = jax.checkpoint(_normed)(k, 1.0)
            v = nn.silu(v)
            rate = dense(h * d, "f_b_proj")(dense(m.gate_rank, "f_a_proj")(x))
            g = jax.checkpoint(_log_decay)(rate, a_log, dt_bias)
            beta = jax.nn.sigmoid(dense(h, "b_proj")(x).astype(jnp.float32))
        with scopes.device(scopes.KDA_SCAN):
            # makes a chunk's triangles and inverse again in the backward
            # pass, on either path; the kernels keep the state every chunk
            # inherits, the plain form not even that
            o = chunked_delta_scan(q, k, v, g, beta, m.chunk_size)
        with scopes.device(scopes.KDA):
            gate = dense(h * d, "g_b_proj", bias=True)(
                dense(m.gate_rank, "g_a_proj")(x))
            o = nn.remat(GatedHeadNorm)(cfg.norm_eps, dtype=cfg.dtype,
                                        name="o_norm")(o, heads(gate))
            return dense(cfg.d_model, "o_proj")(o.reshape(bsz, s, h * d))
