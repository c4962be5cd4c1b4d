"""The lightning indexer of DeepSeek Sparse Attention (the DeepSeek-V3.2-Exp
report), for a multi-head attention layer of ``models/transformer.py``
whose ``AttentionConfig`` has an ``indexer``.

From the layer's normed input ``y``, detached (``ŷ = stop_gradient(y)``:
the indexer learns from its own loss alone):

    qI = ŷ WqI                     [T, heads, head_dim]
    kI = LayerNorm(ŷ WkI)          [T, head_dim], one key head, weight + bias
    qI, kI = rotary on the first ``rotary_dim`` of each head (rotate-half)
    w  = ŷ Ww / sqrt(heads)        [T, heads], float32

and ``ops/sparse_attention.py`` scores every causal pair, ``I[t, s] =
head_dim^-1/2 sum_j w[t, j] relu(qI_j[t] . kI[s])``, keeps each query's
``top_k`` keys, runs the main attention over them and gives the indexer's
KL loss a query. The layer sows the KL's mean (``LOSSES``,
``"indexer_kl"``), which ``training.make_lm_train_step`` adds to the loss
it differentiates, and the packed selection (``"intermediates"``,
``"selection"``), for a caller that asks.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class IndexerConfig:
    num_heads: int = 16
    head_dim: int = 64
    top_k: int = 2048
    # rotary (rotate-half, at the layer's theta) on the first this many
    # elements of each head of qI and kI, the rest passed through
    rotary_dim: int = 32


NORM_EPS = 1e-6  # of the LayerNorm on kI
# The collection the indexer's KL is sown into: a collection of its own,
# which the LM step builders sum into their loss at weight 1 (``"losses"``
# holds models/moe.py's auxiliary terms, which ``moe.aux_loss`` weighs)
LOSSES = "indexer_losses"


class Indexer(nn.Module):
    """``y [B, S, d] -> (qI [B, S, heads, head_dim], kI [B, S, head_dim],
    w [B, S, heads] float32)``, from ``y`` detached."""
    cfg: IndexerConfig
    rope_theta: float
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, y, positions):
        from horovod_tpu.models.transformer import _rotary
        c = self.cfg
        y = jax.lax.stop_gradient(y)
        q = nn.DenseGeneral((c.num_heads, c.head_dim), dtype=self.dtype,
                            use_bias=False, name="wq")(y)
        k = nn.Dense(c.head_dim, dtype=self.dtype, use_bias=False,
                     name="wk")(y)
        k = nn.LayerNorm(epsilon=NORM_EPS, dtype=self.dtype,
                         name="k_norm")(k)
        q = _rotary(q, positions, self.rope_theta, c.rotary_dim)
        k = _rotary(k[:, :, None, :], positions, self.rope_theta,
                    c.rotary_dim)[:, :, 0, :]
        w = nn.Dense(c.num_heads, dtype=self.dtype, use_bias=False,
                     name="weights_proj")(y)
        return q, k, w.astype(jnp.float32) * c.num_heads ** -0.5


def attend_sparse(cfg, a, q, k, v, q_index, k_index, w):
    """``(out [B, S, H, D], kl [B, S], packed selection)`` of a layer
    whose ``AttentionConfig`` ``a`` has an indexer: the sparse kernels
    where ``cfg`` asks for the flash kernel (``wants_flash``), the plain
    path elsewhere. Causal by index: position ``s`` of the sequence is a
    key of query ``t`` when ``s <= t``. A plain function, as
    ``transformer.attend``, so that no module's name enters the kernels'
    ``op_name``."""
    from horovod_tpu.models.transformer import wants_flash
    from horovod_tpu.ops import sparse_attention as sa
    return sa.sparse_attention(
        q, k, v, q_index, k_index, w, top_k=a.indexer.top_k,
        index_scale=a.indexer.head_dim ** -0.5,
        kernel=wants_flash(cfg, q.shape[1]),
        caller="models.transformer.Attention")
