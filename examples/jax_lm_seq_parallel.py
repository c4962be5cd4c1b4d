"""Long-context LM training with ring-attention sequence parallelism.

The TPU answer to the reference's data-parallel-only scaling story
(SURVEY §5.7 beyond-parity): a (data, seq) mesh where the sequence
dimension is sharded across chips and attention runs as a ring —
each shard holds S/n tokens, K/V blocks rotate around the ring via
``ppermute`` with online-softmax accumulation (fp32), so the sequence
length a job can train on scales linearly with the ``seq`` axis while
the next-token loss stays EXACT (boundary targets stitched across
shards, ``training.make_lm_train_step``).

Runs on any device count — on a laptop/CI use the virtual CPU mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/jax_lm_seq_parallel.py --data 2 --seq 4
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import training
from horovod_tpu.models.transformer import Transformer, TransformerConfig
from horovod_tpu.utils.benchmarks import compute_dtype


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", type=int, default=2, help="data-axis size")
    ap.add_argument("--seq", type=int, default=4, help="seq-axis size")
    ap.add_argument("--seq-len", type=int, default=256,
                    help="global sequence length (sharded seq-ways)")
    ap.add_argument("--batch", type=int, default=4,
                    help="global batch (sharded data-ways)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--flash", action="store_true",
                    help="Pallas flash kernels per ring block (fused "
                         "forward AND backward). Per-shard seq len must "
                         "divide by the kernel block (128, or the shard "
                         "length itself when shorter, min multiple of 8) "
                         "and head dim by 8 — otherwise the ring "
                         "silently falls back to the jnp path")
    args = ap.parse_args()

    hvd.init()
    devs = np.asarray(jax.devices())
    assert devs.size >= args.data * args.seq, (
        f"need {args.data * args.seq} devices, have {devs.size}")
    mesh = jax.sharding.Mesh(
        devs[:args.data * args.seq].reshape(args.data, args.seq),
        ("data", "seq"))

    dtype = compute_dtype()
    cfg = TransformerConfig(vocab_size=256, num_layers=args.layers,
                            num_heads=4, d_model=args.d_model,
                            d_ff=4 * args.d_model, dtype=dtype,
                            sequence_axis="seq",
                            flash_attention=args.flash)
    model = Transformer(cfg)
    # params are seq-layout independent: init with the dense clone
    init_model = Transformer(
        TransformerConfig(**{**cfg.__dict__, "sequence_axis": None}))

    tx = hvd.DistributedOptimizer(optax.adam(3e-3), axes=("data", "seq"))

    # toy copy-task data: predictable next tokens so loss visibly drops
    rng = np.random.default_rng(0)
    pattern = rng.integers(0, 256, size=(args.seq_len // 8,))
    tokens = jnp.asarray(np.tile(pattern, (args.batch, 8)), jnp.int32)

    state = training.create_train_state(init_model, tx,
                                        jax.random.PRNGKey(0), tokens[:1])
    step = training.make_lm_train_step(model, tx, mesh=mesh,
                                       batch_axis="data", seq_axis="seq")
    first = last = None
    for i in range(args.steps):
        state, loss = step(state, tokens)
        loss = float(loss)
        first = first if first is not None else loss
        last = loss
        if hvd.rank() == 0 and (i + 1) % 10 == 0:
            print(f"step {i + 1}: loss {loss:.4f}")
    assert last < first, (first, last)
    if hvd.rank() == 0:
        print(f"done: loss {first:.4f} -> {last:.4f} on a "
              f"{args.data}x{args.seq} (data x seq) mesh, "
              f"global seq len {args.seq_len}")


if __name__ == "__main__":
    main()
