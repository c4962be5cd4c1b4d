"""Plain reference of the decoder block the repo trains, from scratch.

Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no ``shard_map``,
no ``DistributedOptimizer``, no flax. It reads the parameter tree the
program's ``models/transformer.py`` creates and writes the equations out:

    x   = E[tokens]
    per layer:  y = rmsnorm(x) * g1
                q, k = rope(y Wq), rope(y Wk)   (rotary over the whole
                v    = y Wv                      head, theta 10,000, the
                                                 two halves rotated)
                x = x + softmax(causal(q k^T / sqrt(d_head))) v  Wo
                y = rmsnorm(x) * g2
                x = x + gelu_tanh(y W1) W2
    logits = (rmsnorm(x) * gf) Wh                (untied head)
    loss   = mean over every position but the last of
             -log softmax(logits)[next token]

The block departs from GPT-NeoX, whose widths the configurations carry
(RMSNorm for LayerNorm, no biases, sequential residual, rotary over the
whole head, tanh GELU): the configuration files list that under
``departures``; the reference follows the program, not the paper.
"""

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6  # flax.linen.RMSNorm's default, which the program uses


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + RMS_EPS) * scale


def _rope(x, theta):
    s, half = x.shape[1], x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def loss(params, tokens, *, num_layers, theta=10000.0):
    """Mean next-token cross-entropy of ``tokens`` [B, S]."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    x = f32(params["embed"]["embedding"])[tokens]
    s = tokens.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(num_layers):
        p = params[f"block_{i}"]
        y = _rmsnorm(x, f32(p["RMSNorm_0"]["scale"]))
        a = p["attn"]
        q = _rope(jnp.einsum("bsd,dhe->bshe", y, f32(a["query"]["kernel"])),
                  theta)
        k = _rope(jnp.einsum("bsd,dhe->bshe", y, f32(a["key"]["kernel"])),
                  theta)
        v = jnp.einsum("bsd,dhe->bshe", y, f32(a["value"]["kernel"]))
        scores = jnp.einsum("bqhe,bkhe->bhqk", q, k) / q.shape[-1] ** 0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        ctx = jnp.einsum("bhqk,bkhe->bqhe", probs, v)
        x = x + jnp.einsum("bqhe,hed->bqd", ctx, f32(a["out"]["kernel"]))
        y = _rmsnorm(x, f32(p["RMSNorm_1"]["scale"]))
        y = jax.nn.gelu(y @ f32(p["Dense_0"]["kernel"]), approximate=True)
        x = x + y @ f32(p["Dense_1"]["kernel"])
    x = _rmsnorm(x, f32(params["RMSNorm_0"]["scale"]))
    logits = x @ f32(params["lm_head"]["kernel"])
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
    return -jnp.mean(picked)


def loss_and_grad_norm(params, tokens, *, num_layers):
    """``(loss, global L2 norm of its gradient)``, float32, highest
    matmul precision. One sequence at a time, gradients summed: the
    float32 scores of a sequence are what limits the sample, and two
    sequences side by side would not fit beside a filled chip."""
    with jax.default_matmul_precision("highest"):
        def one(carry, seq):
            value, grads = jax.value_and_grad(loss)(
                params, seq[None], num_layers=num_layers)
            total, acc = carry
            return (total + value,
                    jax.tree_util.tree_map(jnp.add, acc, grads)), None

        zero = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, jnp.float32), params)
        (total, grads), _ = jax.lax.scan(one, (0.0, zero), tokens)
    n = tokens.shape[0]
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g / n))
                        for g in jax.tree_util.tree_leaves(grads)))
    return total / n, norm
