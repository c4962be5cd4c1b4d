"""Switch-Transformer MoE LM training over a (data x expert) mesh.

Every 2nd block's MLP is a top-1 mixture-of-experts
(``TransformerConfig(moe_every=2)``); expert weights shard over the
``expert`` axis (num-experts / expert-parallel experts per device) and
GSPMD inserts the token all-to-alls (``docs/PARALLELISM.md`` — Expert
parallelism).

The full Switch training recipe is on: the router sows the
load-balancing auxiliary loss + router z-loss into the ``"losses"``
collection and ``make_tp_lm_train_step`` adds them to the LM loss
(weights 0.01 / 1e-3), and token dispatch is grouped
(``moe_num_groups``) so dispatch memory scales O(T^2/G) instead of
O(T^2).

Run on the virtual CPU mesh:
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/jax_lm_moe.py
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax

from horovod_tpu.models.transformer import Transformer, TransformerConfig
from horovod_tpu.parallel import make_tp_lm_train_step, shard_lm_state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--expert-parallel", type=int, default=4)
    ap.add_argument("--num-experts", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--seq-len", type=int, default=64)
    args = ap.parse_args()

    n = len(jax.devices())
    ep = args.expert_parallel
    assert n % ep == 0, f"{n} devices not divisible by expert={ep}"
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()).reshape(n // ep, ep), ("data", "expert"))

    cfg = TransformerConfig(vocab_size=256, num_layers=4, num_heads=4,
                            d_model=args.d_model, d_ff=4 * args.d_model,
                            dtype=jnp.float32, moe_every=2,
                            num_experts=args.num_experts, expert_mesh=mesh,
                            moe_num_groups=8, moe_group_axis="data")
    model = Transformer(cfg)
    tx = optax.adam(1e-3)

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(8, args.seq_len)), jnp.int32)

    state = shard_lm_state(model, tx, jax.random.PRNGKey(0), tokens[:1],
                           mesh, model_axis=None, expert_axis="expert")
    w_in = state.params["block_1"]["moe"]["w_in"]
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}, "
          f"experts: {args.num_experts}, w_in sharding: "
          f"{w_in.sharding.spec}, per-device shard: "
          f"{w_in.addressable_shards[0].data.shape}")

    step = make_tp_lm_train_step(model, tx, mesh, model_axis=None,
                                 expert_axis="expert")
    for i in range(args.steps):
        state, loss = step(state, tokens)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:3d} loss {float(loss):.4f}")
    print("done")


if __name__ == "__main__":
    main()
