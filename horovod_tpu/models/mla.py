"""Multi-head latent attention (MLA), the training form.

The mixer of the DeepSeek-V3 family (``model_type deepseek_v3``): keys and
values are expanded from one low-rank latent a token, and position enters
through a separate rotary part that all heads share on the key side
(where ``rotary`` is off, the ``kimi_linear`` family's ``mla_use_nope``,
that part keeps its channels and is not turned: position then comes from
the model's other mixers):

    q = x Wq                      -> per head [q_nope | q_pe]
    [c | k_pe] = x Wkva ;  c = rmsnorm(c)
    [k_nope | v] per head = c Wkvb
    q_pe, k_pe = rope(., theta, interleaved pairs); k_pe is ONE head
                                  (``rotary`` off: both as they are)
    o = softmax(causal([q_nope|q_pe] [k_nope|k_pe]^T / sqrt(d_qk))) v
    out = concat(o) Wo

so a head is ``d_qk = qk_nope_head_dim + qk_rope_head_dim`` wide for q and
k and ``v_head_dim`` wide for v and o: the two head sizes
``ops/flash_attention.py`` takes. No query latent (``q_lora_rank`` null).
Everything here but the kernel runs under the ``hvd_mla`` device scope.

Training only: decode against a latent cache with absorbed weights is
ROADMAP D5 and is refused, not approximated.
"""

import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from horovod_tpu.telemetry import scopes


@dataclasses.dataclass(frozen=True)
class LatentAttentionConfig:
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e6
    rotary: bool = True  # off: q_pe and k_pe are concatenated unrotated


def rotary_interleaved(x, positions, theta):
    """Rotary embedding over interleaved pairs: elements ``(2i, 2i+1)`` of
    the last axis turn together by ``position * theta^(-2i/d)``. Returns
    the pairs de-interleaved (all first elements, then all second), the
    layout the published implementation leaves them in; q and k get the
    same permutation, so their products do not see it. x: [B, S, H, d],
    positions: [B, S]. Float32 inside: at theta 1e6 the angles of the slow
    pairs need more than bfloat16's eight bits."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


class LatentAttention(nn.Module):
    """``cfg`` is the model's ``TransformerConfig`` with ``cfg.mla`` set."""
    cfg: object

    @nn.compact
    def __call__(self, x, positions, contiguous_positions=False,
                 cache=None):
        cfg, m = self.cfg, self.cfg.mla
        if cache is not None:
            raise NotImplementedError(
                "latent attention decodes against a latent cache with "
                "absorbed weights (ROADMAP D5); this module trains only")
        if cfg.sequence_axis is not None:
            raise NotImplementedError(
                "latent attention has no ring schedule: build it with "
                "sequence_axis=None")
        h = cfg.num_heads
        dense = lambda features, name, axis=-1: nn.DenseGeneral(  # noqa: E731
            features, axis=axis, dtype=cfg.dtype, use_bias=False, name=name)
        with scopes.device(scopes.MLA):
            q = dense((h, m.qk_nope_head_dim + m.qk_rope_head_dim),
                      "q_proj")(x)
            latent = dense(m.kv_lora_rank + m.qk_rope_head_dim,
                           "kv_a_proj_with_mqa")(x)
            c, k_pe = jnp.split(latent, [m.kv_lora_rank], axis=-1)
            c = nn.RMSNorm(dtype=cfg.dtype, name="kv_a_layernorm")(c)
            kv = dense((h, m.qk_nope_head_dim + m.v_head_dim),
                       "kv_b_proj")(c)
            k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
            q_nope, q_pe = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
            k_pe = k_pe[:, :, None, :]
            if m.rotary:
                q_pe = rotary_interleaved(q_pe, positions, m.rope_theta)
                k_pe = rotary_interleaved(k_pe, positions, m.rope_theta)
            q = jnp.concatenate([q_nope, q_pe], axis=-1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_pe, q_pe.shape)], axis=-1)
        from horovod_tpu.models.transformer import (dense_attention,
                                                    wants_flash)
        from horovod_tpu.ops import flash_attention as fa
        if wants_flash(cfg, x.shape[1]) and contiguous_positions:
            out = fa.attention(q, k, v, causal=cfg.causal)
        else:
            if cfg.flash_attention:
                fa.warn_fallback(
                    "models.mla.LatentAttention", q.shape, k.shape[1],
                    "explicit positions were passed and the kernel masks "
                    "by contiguous offset only")
            out = dense_attention(q, k, v, causal=cfg.causal,
                                  q_positions=positions,
                                  kv_positions=positions)
        with scopes.device(scopes.MLA):
            return dense(cfg.d_model, "o_proj", axis=(-2, -1))(out)
