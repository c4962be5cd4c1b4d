"""Decoder-only Transformer with first-class sequence parallelism.

Not present in the 2019 reference (SURVEY.md §5.7: long-context machinery is
absent there) — built here because long-context is a first-class requirement
of the TPU framework. Design:

* Pre-RMSNorm, rotary position embeddings, GELU MLP — the standard modern
  decoder block, all shapes static and MXU-friendly (bf16 compute).
* A layer is a pair (mixer, feed-forward) read from
  ``TransformerConfig.layer_pattern``: multi-head attention (grouped-query
  where ``num_kv_heads`` says so, without rotary where ``rotary`` is
  off), latent attention (``models/mla.py``, with or without its rotary
  part), a state-space mixer (``models/ssm.py``) or delta attention
  (``models/kda.py``); GELU, SwiGLU, the capacity-dispatch MoE
  (``models/moe.py``) or the no-drop expert share with its shared experts
  (``models/experts.py``). Either half may be ``None``: a layer of one
  sublayer, behind one norm and one residual. The default pattern is the
  block above.
* More than one kind of multi-head attention in one model: every
  ``AttentionConfig`` of ``TransformerConfig.attention`` is a mixer the
  pattern calls by its ``kind``, with its own query and key/value heads,
  head size, sliding window (in the flash kernel's block schedule and in
  ``dense_attention`` alike), rotary embedding (theta, the width of a
  head it turns, YaRN's blended frequencies and its factor on cos and
  sin) and per-head sigmoid output gate, a norm over each head of q and k
  (``qk_norm``), and a lightning indexer (``indexer``,
  ``models/sparse_attention.py``): each query then attends to the
  ``top_k`` keys of the indexer's scores alone (``ops/sparse_attention.py``:
  the selection, flash attention under it and the indexer's KL loss as
  Pallas kernels), and the layer sows the KL, which
  ``training.make_lm_train_step`` adds to its loss. ``"mha"`` is the kind
  the top-level fields size, and its parameter tree is what it always was.
* ``sequence_axis``: when set (inside shard_map over that mesh axis), the
  sequence dimension is sharded across the axis and attention runs as
  **ring attention** (``horovod_tpu.parallel.ring``): K/V blocks rotate
  around the ring via ``lax.ppermute`` while each shard's Q stays put,
  with online-softmax accumulation — memory per chip stays O(S/n), enabling
  contexts n× longer than a single chip could hold.
* Causal masking composes with the ring: block pairs that are entirely
  in the future are still computed (static shapes) but masked.
* **Incremental decode** (``kv_cache=`` — the serving plane,
  docs/SERVING.md): feed only the new tokens with their absolute
  positions plus per-layer cached K/V; attention runs dense over
  cache ++ new (absolute-position masking makes pad slots exact no-ops)
  and the new tokens' K/V come back for the caller's paged pool
  (``horovod_tpu/serve/kvcache.py``). One parameter tree serves both
  modes — a training checkpoint decodes unchanged.
"""

import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.telemetry import scopes


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 4
    num_heads: int = 8
    d_model: int = 512
    d_ff: int = 2048
    dtype: Any = jnp.bfloat16
    causal: bool = True
    # mesh axis the sequence dim is sharded over (ring attention), or None
    sequence_axis: Optional[str] = None
    # fused Pallas flash-attention kernel for the local (non-ring) path
    # (ops/flash_attention.py). Requires the default contiguous positions;
    # gives way to plain XLA attention, with a FlashFallbackWarning,
    # when shapes don't tile or explicit positions are passed.
    # None (default) = auto: ON when running on TPU with local seq >=
    # 1024 (with bf16 operands and its default blocks the kernel's lead
    # over dense attention grows with sequence length and is gone by 512;
    # not measured in this round). OFF elsewhere (interpret mode would
    # crawl). Set True/False to force.
    flash_attention: Optional[bool] = None
    # Sparse-FFN blocks: every `moe_every`-th block (1-based; 0 = dense
    # everywhere) replaces its MLP with a top-k MoE of `num_experts`
    # experts (models/moe.py). `expert_mesh` activates the
    # expert-parallel sharding constraints over its `expert_axis` axis.
    moe_every: int = 0
    num_experts: int = 8
    # routing fanout: 1 = Switch, 2 = GShard top-2 (models/moe.py);
    # raise moe_capacity_factor with it (top-k needs ~k slots/token)
    moe_top_k: int = 1
    moe_capacity_factor: float = 2.0
    expert_mesh: Any = None
    expert_axis: str = "expert"
    # GShard grouped dispatch: tokens split into `moe_num_groups` groups
    # of (B*S)/G, dispatch memory O(T^2/G); `moe_group_axis` shards the
    # group dim (usually the data axis) so EP composes with DP
    moe_num_groups: int = 1
    moe_group_axis: Optional[str] = None
    # A layer is a pair (mixer, feed-forward), one pair a layer:
    #   mixer         "mha" (Attention below, sized by the "mha only"
    #                 fields) | the ``kind`` of an entry of ``attention``
    #                 (Attention too, sized by that entry: its heads,
    #                 window, rotary, gate, q/k norm and indexer) | "mla"
    #                 (models/mla.py, sized by ``mla``) | "ssm"
    #                 (models/ssm.py, sized by ``ssm``) | "kda"
    #                 (models/kda.py, sized by ``kda``) | None
    #   feed-forward  "gelu" (two matrices, width d_ff) | "moe" (the
    #                 capacity dispatch of models/moe.py) | "swiglu"
    #                 (gated, width d_ff) | "experts" (the no-drop share
    #                 of models/experts.py plus its shared expert where
    #                 one is sized, sized by ``experts``) | None
    # A half that is None has no norm, no parameters and no residual: the
    # layer is the other half alone. The pattern None: ("mha", "moe" on
    # every moe_every-th layer, else "gelu"), the block this file has
    # always built, parameter for parameter.
    layer_pattern: Optional[tuple] = None
    mla: Any = None      # models.mla.LatentAttentionConfig
    experts: Any = None  # models.experts.ExpertShareConfig
    ssm: Any = None      # models.ssm.StateSpaceConfig
    kda: Any = None      # models.kda.DeltaAttentionConfig
    attention: tuple = ()  # of AttentionConfig, one a kind of "mha"
    # "mha" only. Key/value heads (None: one a query head); query head i
    # attends to key/value head i // (num_heads // num_kv_heads). The
    # width of a head (None: d_model // num_heads). Rotary position
    # embedding on q and k, or none at all.
    num_kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    rotary: bool = True
    norm_eps: float = 1e-6  # of every RMSNorm (flax's own default)

    def attention_kind(self, mixer):
        """The ``AttentionConfig`` the pattern's ``mixer`` names: "mha" is
        the top-level fields', any other the entry of ``attention`` of
        that ``kind``; ``None`` when it names no multi-head attention."""
        if mixer == "mha":
            return AttentionConfig(
                kind="mha", num_heads=self.num_heads,
                num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim or self.d_model // self.num_heads,
                rotary=self.rotary)
        return next((a for a in self.attention if a.kind == mixer), None)

    def layers(self):
        """The (mixer, feed-forward) pair of every layer."""
        if self.layer_pattern is not None:
            if len(self.layer_pattern) != self.num_layers:
                raise ValueError(
                    f"layer_pattern names {len(self.layer_pattern)} layers "
                    f"and num_layers is {self.num_layers}")
            return tuple(tuple(pair) for pair in self.layer_pattern)
        return tuple(
            ("mha", "moe" if self.moe_every > 0
             and (i + 1) % self.moe_every == 0 else "gelu")
            for i in range(self.num_layers))


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN (``rope_type yarn``, its fields named as ``config.json`` names
    them): the rotary frequencies of a model trained to
    ``original_max_position_embeddings`` positions blended, a frequency at
    a time, between themselves (the pairs that turn more than
    ``beta_fast`` times over the original length) and themselves over
    ``factor`` (fewer than ``beta_slow`` times), and a factor on cos and
    sin that stands in for a temperature on the scores."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None  # None: 0.1 ln(factor) + 1

    def inv_freq(self, theta, width):
        """The ``width // 2`` blended inverse frequencies (numpy: they are
        constants of the program)."""
        n = np.arange(width // 2, dtype=np.float64)
        plain = theta ** (-2.0 * n / width)

        def turns(t):  # the index of the pair that turns t times
            original = self.original_max_position_embeddings
            return (width * math.log(original / (2 * math.pi * t))
                    / (2 * math.log(theta)))

        low = max(math.floor(turns(self.beta_fast)), 0)
        high = min(math.ceil(turns(self.beta_slow)), width - 1)
        ramp = np.clip((n - low) / max(high - low, 1e-3), 0.0, 1.0)
        return ((1.0 - ramp) * plain
                + ramp * plain / self.factor).astype(np.float32)

    def cos_sin_factor(self):
        if self.attention_factor is not None:
            return self.attention_factor
        return 0.1 * math.log(self.factor) + 1.0


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    """One kind of multi-head attention layer: what ``layer_pattern``
    calls it, and everything two kinds of one model may differ in."""
    kind: str
    num_heads: int
    head_dim: int
    # key/value heads (None: one a query head); query head i attends to
    # key/value head i // (num_heads // num_kv_heads)
    num_kv_heads: Optional[int] = None
    # a query sees itself and the window - 1 positions before it
    window: Optional[int] = None
    # rotary embedding (rotate-half) on the first ``rotary_dim`` elements
    # of each head of q and k (None: the whole head), the rest passed
    # through; ``yarn`` blends its frequencies
    rotary: bool = True
    rope_theta: float = 10000.0
    rotary_dim: Optional[int] = None
    yarn: Optional[YarnScaling] = None
    # head h's output times sigmoid(x Wg)[h], Wg [d_model, num_heads]
    gate: bool = False
    # RMSNorm over each head of q and of k (a learned head_dim-wide weight
    # each, eps ``norm_eps``), before rotary
    qk_norm: bool = False
    # a lightning indexer (models/sparse_attention.IndexerConfig): each
    # query attends to the top_k keys of its scores, and the layer sows the
    # indexer's KL loss. Training only, sequence_axis None
    indexer: Any = None
    # the layer recomputed in the backward pass (flax's remat), keeping
    # its input and, with an indexer, the selection (ops/sparse_attention
    # .SAVED): the selection runs once, the rest of the layer twice
    recompute: bool = False

    def rotate(self, x, positions):
        if not self.rotary:
            return x
        width, yarn = self.rotary_dim or self.head_dim, self.yarn
        return _rotary(x, positions, self.rope_theta, width,
                       yarn and yarn.inv_freq(self.rope_theta, width),
                       yarn and yarn.cos_sin_factor())


def _rotary(x, positions, theta=10000.0, width=None, inv_freq=None,
            factor=None):
    """Apply rotary position embedding (rotate-half) to the first
    ``width`` elements of every head (None: all of them) and pass the
    rest through. ``inv_freq`` [width // 2]: scaled inverse frequencies in
    place of ``theta^(-2n / width)``; ``factor`` multiplies cos and sin.
    x: [B, S, H, D], positions: [B, S]."""
    d = x.shape[-1] if width is None else width
    half = d // 2
    if inv_freq is None:
        freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32)
                                 / half))
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if factor is not None:
        cos, sin = cos * factor, sin * factor
    cos = cos[:, :, None, :].astype(x.dtype)
    sin = sin[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:d]
    turned = [x1 * cos - x2 * sin, x1 * sin + x2 * cos]
    if d < x.shape[-1]:
        turned.append(x[..., d:])
    return jnp.concatenate(turned, axis=-1)


def wants_flash(cfg, seq_len):
    """``cfg.flash_attention``, or its auto rule: TPU only, and only past
    the measured sequence crossover (see the field's comment)."""
    if cfg.flash_attention is not None:
        return cfg.flash_attention
    return jax.devices()[0].platform == "tpu" and seq_len >= 1024


def dense_attention(q, k, v, *, causal, q_positions, kv_positions,
                    window=None):
    """Single-device attention: softmax(QK^T/sqrt(d)) V with causal mask by
    absolute position (so it composes with sequence-sharded inputs), and
    with a ``window`` only the position itself and the ``window - 1``
    before it."""
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    scores = scores.astype(jnp.float32) / (float(d) ** 0.5)
    if window is not None and not causal:
        raise ValueError("a window lies behind a causal diagonal")
    if causal:
        behind = (q_positions[:, None, :, None]
                  - kv_positions[:, None, None, :])
        mask = behind >= 0 if window is None else (
            (behind >= 0) & (behind < window))
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attend(cfg, a, q, k, v, positions, contiguous_positions, cache=None):
    """``(softmax(q k^T / sqrt(d)) v under the mask of ``a``, an
    ``AttentionConfig``, the new tokens' (k, v) for the cache or None)``
    of q [B, S, H, D] and k, v [B, S, H_kv, D] after rotary, ``H_kv`` any
    number of heads that divides ``H`` (query head ``h`` sees key/value
    head ``h // (H / H_kv)``): the kernel, the ring or the dense path, as
    ``cfg`` and the positions choose. The flash kernel reads shared heads
    in place; the other paths take one key/value head a query head and
    get the heads repeated (``fa.per_query_head``). A plain function, not
    a method of ``Attention``: a module's method would put its name into
    every ``op_name`` under it."""
    from horovod_tpu.ops import flash_attention as fa
    if cache is not None:
        # incremental decode: attend over cached context ++ the new
        # tokens, and hand the new tokens' (post-rotary) K/V back to
        # the caller to write into its pool (serve/kvcache.py). Pad
        # context slots carry a sentinel position larger than any
        # real one, so the absolute-position causal mask hides them;
        # masked scores are exactly -inf -> exactly-zero probs, so
        # padding never perturbs the visible tokens' output. Always
        # the dense path: decode q_len (1, or one prefill chunk)
        # sits below the flash kernel's MXU block floor
        # (ops/flash_attention.kernel_supported routes it out too).
        ck, cv, ctx_positions = cache
        k, v = fa.per_query_head(q, k, v)
        k_all = jnp.concatenate([ck.astype(k.dtype), k], axis=1)
        v_all = jnp.concatenate([cv.astype(v.dtype), v], axis=1)
        kv_pos = jnp.concatenate([ctx_positions, positions], axis=1)
        return dense_attention(q, k_all, v_all, causal=cfg.causal,
                               q_positions=positions,
                               kv_positions=kv_pos), (k, v)
    use_flash = wants_flash(cfg, q.shape[1])
    if cfg.flash_attention and not contiguous_positions:
        # the kernel masks by offset-contiguous positions; arbitrary
        # user-supplied position arrays must use the dense path
        fa.warn_fallback(
            "models.transformer.Attention", q.shape, k.shape[1],
            "explicit positions were passed and the kernel masks "
            "by contiguous offset only")
    if cfg.sequence_axis is not None:
        from horovod_tpu.parallel import ring
        k, v = fa.per_query_head(q, k, v)
        if use_flash and contiguous_positions:
            # Pallas kernel per rotated K/V block, lse-merged
            out = ring.ring_attention(
                q, k, v, axis_name=cfg.sequence_axis,
                causal=cfg.causal, use_flash=True)
        else:
            out = ring.ring_attention(
                q, k, v, axis_name=cfg.sequence_axis,
                causal=cfg.causal, q_positions=positions,
                kv_positions=positions)
    elif use_flash and contiguous_positions:
        out = fa.attention(q, k, v, causal=cfg.causal, window=a.window)
    else:
        out = dense_attention(q, *fa.per_query_head(q, k, v),
                              causal=cfg.causal, q_positions=positions,
                              kv_positions=positions, window=a.window)
    return out, None


class Attention(nn.Module):
    """Multi-head attention of one kind (``cfg.attention_kind``): "mha",
    sized by ``cfg``'s top-level fields, or an entry of
    ``cfg.attention``."""
    cfg: TransformerConfig
    kind: str = "mha"

    @nn.compact
    def __call__(self, x, positions, contiguous_positions=False,
                 cache=None):
        cfg, a = self.cfg, self.cfg.attention_kind(self.kind)
        h, h_kv, d = a.num_heads, a.num_kv_heads or a.num_heads, a.head_dim
        if h % h_kv:
            raise ValueError(f"{h} query heads do not split over {h_kv} "
                             f"key/value heads")
        if a.indexer is not None:
            if cache is not None:
                raise NotImplementedError(
                    "models.transformer.Attention: decode with an indexer "
                    "cache is not built (the paged cache keeps no indexer "
                    "keys, serve/kvcache.py); a layer with an indexer "
                    "trains only")
            if cfg.sequence_axis is not None:
                raise NotImplementedError(
                    "models.transformer.Attention: ring attention "
                    "(parallel/ring.py) rotates key/value blocks and has no "
                    "per-query selection of keys; build a layer with an "
                    "indexer with sequence_axis=None")
        if a.window is not None:
            if cache is not None:
                raise NotImplementedError(
                    "models.transformer.Attention: the paged cache keeps "
                    "every position of every layer and frees none behind "
                    "a window (serve/kvcache.py); a windowed layer trains "
                    "only")
            if cfg.sequence_axis is not None:
                raise NotImplementedError(
                    "models.transformer.Attention: ring attention "
                    "(parallel/ring.py) rotates every key/value block to "
                    "every shard and has no window in its schedule; build "
                    "a windowed layer with sequence_axis=None")
        dense = lambda name, heads=h: nn.DenseGeneral(  # noqa: E731
            (heads, d), axis=-1, dtype=cfg.dtype, use_bias=False, name=name)
        with scopes.device(scopes.ATTN):
            q, k, v = dense("query")(x), dense("key", h_kv)(x), dense(
                "value", h_kv)(x)
            if a.qk_norm:
                norm = lambda name: nn.RMSNorm(  # noqa: E731
                    epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name)
                q, k = norm("query_norm")(q), norm("key_norm")(k)
            q, k = a.rotate(q, positions), a.rotate(k, positions)
            if h_kv != h and cache is not None:
                raise NotImplementedError(
                    "the paged cache holds one key/value head a query "
                    "head (serve/kvcache.py); grouped-query attention "
                    "trains only")
        if a.indexer is not None:
            from horovod_tpu.models.sparse_attention import (
                LOSSES, Indexer, attend_sparse)
            with scopes.device(scopes.DSA_INDEX):
                q_index, k_index, w = Indexer(
                    a.indexer, a.rope_theta, cfg.dtype, name="indexer")(
                        x, positions)
            # the kernels' scopes are their own (ops/sparse_attention.py)
            out, kl, selection = attend_sparse(cfg, a, q, k, v, q_index,
                                               k_index, w)
            new_kv = None
            # for a caller that asks (mutable=[...]); else no-ops
            self.sow(LOSSES, "indexer_kl", jnp.mean(kl))
            self.sow("intermediates", "selection", selection)
        else:
            with scopes.device(scopes.ATTN_FULL if a.window is None
                               else scopes.ATTN_WINDOW):
                out, new_kv = attend(cfg, a, q, k, v, positions,
                                     contiguous_positions, cache)
        with scopes.device(scopes.ATTN):
            if a.gate:
                gate = nn.Dense(h, dtype=cfg.dtype, use_bias=False,
                                name="gate")(x)
                out = out * jax.nn.sigmoid(gate)[..., None]
            out = nn.DenseGeneral(cfg.d_model, axis=(-2, -1),
                                  dtype=cfg.dtype, use_bias=False,
                                  name="out")(out)
        return out if cache is None else (out, new_kv)

class Block(nn.Module):
    cfg: TransformerConfig
    mixer: Optional[str] = "mha"
    feed_forward: Optional[str] = "gelu"

    @nn.compact
    def __call__(self, x, positions, contiguous_positions=False,
                 cache=None):
        cfg = self.cfg
        norm = lambda: nn.RMSNorm(  # noqa: E731
            epsilon=cfg.norm_eps, dtype=cfg.dtype)
        new_kv = None
        if self.mixer is not None:
            y = norm()(x)
            a = cfg.attention_kind(self.mixer)
            if a is not None:
                module = Attention
                if a.recompute:
                    from horovod_tpu.ops.sparse_attention import SAVED
                    module = nn.remat(
                        Attention, static_argnums=(3, 4),
                        policy=jax.checkpoint_policies.save_only_these_names(
                            *SAVED))
                attention = module(cfg, self.mixer, name="attn")
            elif self.mixer == "mla":
                from horovod_tpu.models.mla import LatentAttention
                attention = LatentAttention(cfg, name="attn")
            elif self.mixer == "ssm":
                from horovod_tpu.models.ssm import StateSpaceMixer
                attention = StateSpaceMixer(cfg, name="mixer")
            elif self.mixer == "kda":
                from horovod_tpu.models.kda import DeltaAttention
                attention = DeltaAttention(cfg, name="mixer")
            else:
                raise ValueError(f"unknown mixer {self.mixer!r}")
            out = attention(y, positions, contiguous_positions, cache)
            if cache is not None:
                out, new_kv = out
            x = x + out
        elif cache is not None:
            raise NotImplementedError(
                "a layer without a mixer has no cache entry: the serving "
                "path stacks one key/value pair a layer")
        if self.feed_forward is None:
            return x if cache is None else (x, new_kv)
        y = norm()(x)
        b, s, d = y.shape
        if self.feed_forward == "moe":
            from horovod_tpu.models.moe import MoE
            y = MoE(num_experts=cfg.num_experts, d_model=d,
                    d_ff=cfg.d_ff, dtype=cfg.dtype, mesh=cfg.expert_mesh,
                    expert_axis=cfg.expert_axis,
                    num_groups=cfg.moe_num_groups,
                    group_axis=cfg.moe_group_axis, top_k=cfg.moe_top_k,
                    capacity_factor=cfg.moe_capacity_factor,
                    name="moe")(y.reshape(b * s, d)).reshape(b, s, d)
        elif self.feed_forward == "gelu":
            y = nn.Dense(cfg.d_ff, dtype=cfg.dtype, use_bias=False)(y)
            y = nn.gelu(y)
            y = nn.Dense(cfg.d_model, dtype=cfg.dtype, use_bias=False)(y)
        elif self.feed_forward == "swiglu":
            from horovod_tpu.models.experts import SwiGLU
            y = SwiGLU(cfg.d_ff, dtype=cfg.dtype, name="mlp")(y)
        elif self.feed_forward == "experts":
            from horovod_tpu.models.experts import ExpertShare, shared_expert
            e = cfg.experts
            # the share recomputes itself in the backward pass and keeps
            # none of its buffers of token-slots
            routed = ExpertShare(e, dtype=cfg.dtype, name="experts")(
                y.reshape(b * s, d)).reshape(b, s, d)
            y = routed if not (e.n_shared_experts or e.shared_d_ff) else (
                routed + shared_expert(e, dtype=cfg.dtype,
                                       name="shared_experts")(y))
        else:
            raise ValueError(f"unknown feed-forward {self.feed_forward!r}")
        if cache is not None:
            return x + y, new_kv
        return x + y


class Transformer(nn.Module):
    """tokens [B, S_local] -> logits [B, S_local, vocab].

    With ``cfg.sequence_axis`` set, S_local = S_global / axis_size and
    ``positions`` must carry each shard's absolute positions (the training
    utilities compute them from the shard index).
    """
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, positions=None, train: bool = True,
                 kv_cache=None):
        del train  # no dropout in this family: decode needs no RNG
        cfg = self.cfg
        if kv_cache is not None:
            # incremental decode (docs/SERVING.md): ``kv_cache`` is
            # ``(ctx_k, ctx_v, ctx_positions)`` with per-layer context
            # K/V stacked ``[L, B, S_ctx, H, D]`` and ``ctx_positions``
            # ``[B, S_ctx]`` int32 absolute positions (pad slots carry a
            # sentinel past every real position). ``positions`` must be
            # the fed tokens' absolute positions. Returns
            # ``(logits, (new_k, new_v))`` — the fed tokens' K/V,
            # ``[L, B, S_q, H, D]``, for the caller's cache writes. The
            # same parameter tree drives both modes, so a training
            # checkpoint serves unchanged.
            if cfg.sequence_axis is not None:
                raise ValueError(
                    "incremental decode composes with a paged cache, not "
                    "ring attention — build the serving model with "
                    "sequence_axis=None")
            if not cfg.causal:
                raise ValueError("incremental decode requires causal "
                                 "attention (cfg.causal=True)")
            if positions is None:
                raise ValueError(
                    "incremental decode needs explicit absolute "
                    "positions for the fed tokens")
            ctx_k, ctx_v, ctx_positions = kv_cache
            x = nn.Embed(cfg.vocab_size, cfg.d_model,
                         dtype=cfg.dtype, name="embed")(tokens)
            new_ks, new_vs = [], []
            for i, (mixer, feed_forward) in enumerate(cfg.layers()):
                x, (nk, nv) = Block(cfg, mixer, feed_forward,
                                    name=f"block_{i}")(
                    x, positions, False,
                    (ctx_k[i], ctx_v[i], ctx_positions))
                new_ks.append(nk)
                new_vs.append(nv)
            x = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype)(x)
            logits = nn.Dense(cfg.vocab_size, dtype=cfg.dtype,
                              use_bias=False, name="lm_head")(x)
            return (logits.astype(jnp.float32),
                    (jnp.stack(new_ks), jnp.stack(new_vs)))
        contiguous = positions is None  # auto positions are 0..S-1
        if positions is None:
            from horovod_tpu.parallel.ring import default_positions
            positions = default_positions(cfg.sequence_axis,
                                          tokens.shape[0], tokens.shape[1])
        x = nn.Embed(cfg.vocab_size, cfg.d_model,
                     dtype=cfg.dtype, name="embed")(tokens)
        for i, (mixer, feed_forward) in enumerate(cfg.layers()):
            x = Block(cfg, mixer, feed_forward,
                      name=f"block_{i}")(x, positions, contiguous)
        x = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype)(x)
        logits = nn.Dense(cfg.vocab_size, dtype=cfg.dtype, use_bias=False,
                          name="lm_head")(x)
        return logits.astype(jnp.float32)
