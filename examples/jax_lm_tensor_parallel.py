"""Tensor-parallel LM training over a (data x model) mesh.

The GSPMD path (``horovod_tpu/parallel/tensor.py``): attention heads and
the MLP hidden dim are sharded over the ``model`` axis by parameter
shardings alone; XLA inserts the Megatron-style all-reduces and the
cross-``data`` gradient reduction. Compare ``jax_lm_seq_parallel.py``
(ring attention over a ``seq`` axis) for the long-context strategy.

Run on the virtual CPU mesh:
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/jax_lm_tensor_parallel.py
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax

from horovod_tpu.models.transformer import Transformer, TransformerConfig
from horovod_tpu.parallel import tensor as tp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--model-parallel", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--seq-len", type=int, default=64)
    args = ap.parse_args()

    n = len(jax.devices())
    mp = args.model_parallel
    assert n % mp == 0, f"{n} devices not divisible by model={mp}"
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()).reshape(n // mp, mp), ("data", "model"))

    cfg = TransformerConfig(vocab_size=256, num_layers=2, num_heads=mp,
                            d_model=args.d_model, d_ff=4 * args.d_model,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    tx = optax.adam(1e-3)

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(8, args.seq_len)), jnp.int32)

    state = tp.shard_lm_state(model, tx, jax.random.PRNGKey(0), tokens[:1],
                              mesh)
    kern = state.params["block_0"]["Dense_0"]["kernel"]
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}, "
          f"d_ff kernel sharding: {kern.sharding.spec}, "
          f"per-device shard: {kern.addressable_shards[0].data.shape}")

    step = tp.make_tp_lm_train_step(model, tx, mesh)
    for i in range(args.steps):
        state, loss = step(state, tokens)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:3d} loss {float(loss):.4f}")
    print("done")


if __name__ == "__main__":
    main()
