"""Reference for the ResNet cells: the same flax module, in float32,
outside any step builder.

A departure from the rule that a reference shares no code with the
program, said plainly: the forward pass is ``horovod_tpu.models``' own
module (built with ``dtype=float32``). What is independent is everything
around it: plain ``jax.value_and_grad`` under
``jax.default_matmul_precision("highest")``, the cross-entropy written
out here, no ``make_train_step``, no ``shard_map``, no
``DistributedOptimizer``, no donation. So it catches a wrong loss scale,
a wrong gradient average, a lost BatchNorm update or low-precision
accumulation in the step builder, and it cannot catch an error inside
the module. An independent ``jax.numpy`` ResNet is an open question in
``PERF.md``.
"""

import jax
import jax.numpy as jnp


def loss_and_grad_norm(model_f32, params, batch_stats, images, labels):
    """``(loss, global L2 norm of its gradient)`` of one batch, training
    mode (BatchNorm normalises with the batch's own statistics)."""
    def loss_fn(p):
        logits, _ = model_f32.apply(
            {"params": p, "batch_stats": batch_stats},
            images.astype(jnp.float32), train=True, mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))

    with jax.default_matmul_precision("highest"):
        value, grads = jax.value_and_grad(loss_fn)(params)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree_util.tree_leaves(grads)))
    return value, norm
