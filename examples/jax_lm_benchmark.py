"""Synthetic LM training benchmark: tokens/sec through the framework
hot path (DistributedOptimizer -> exact sharded LM loss -> optimizer),
the language-model sibling of ``jax_synthetic_benchmark.py`` (reference
pattern: ``examples/pytorch_synthetic_benchmark.py`` timed batches).

Single chip (flash attention on TPU):

    python examples/jax_lm_benchmark.py --seq-len 2048

Sequence-parallel over a mesh (ring attention, flash per block):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/jax_lm_benchmark.py --data 2 --seq 4 --steps 3 \
        --layers 2 --d-model 64 --seq-len 1024
"""

import argparse
import json

import jax
import numpy as np

import horovod_tpu as hvd
from horovod_tpu.utils.benchmarks import (device_fields, make_lm_bench,
                                          slope_window, sync)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", type=int, default=1, help="data-axis size")
    ap.add_argument("--seq", type=int, default=1, help="seq-axis size")
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--seq-len", type=int, default=2048,
                    help="global sequence length")
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--no-flash", action="store_true")
    args = ap.parse_args()

    hvd.init()
    devs = np.asarray(jax.devices())
    n_used = args.data * args.seq
    assert devs.size >= n_used, f"need {n_used} devices, have {devs.size}"
    mesh = jax.sharding.Mesh(devs[:n_used].reshape(args.data, args.seq),
                             ("data", "seq"))

    seq_axis = "seq" if args.seq > 1 else None
    # the ONE copy of the workload (shared with bench.py's LM lines)
    step, state, tokens = make_lm_bench(
        mesh=mesh, seq_axis=seq_axis, batch=args.batch,
        seq_len=args.seq_len, layers=args.layers, d_model=args.d_model,
        heads=args.heads, vocab=args.vocab, flash=not args.no_flash)

    # one unconditional warm step (compile + prime the final-loss value;
    # safe at --warmup 0), then the requested extra warmup
    state, loss = step(state, tokens)
    sync(loss)
    for _ in range(args.warmup):
        state, loss = step(state, tokens)
        sync(loss)

    # readback-slope timing (utils/benchmarks.slope_window: the one copy
    # of the protocol)
    def once(carry):
        st, _ = carry
        st, loss = step(st, tokens)
        return (st, loss), loss

    dt, (state, loss) = slope_window(once, (state, loss), args.steps)

    tok_s = args.batch * args.seq_len * args.steps / dt
    print(json.dumps({
        "metric": "transformer_lm_tokens_per_sec",
        "value": round(tok_s, 1),
        "unit": "tokens/sec",
        "seq_len": args.seq_len,
        "mesh": {"data": args.data, "seq": args.seq},
        "flash_attention": not args.no_flash,
        "final_loss": round(float(loss), 4),
        # off the TPU the kernel runs in Pallas interpret mode; the
        # platform below says which this line is
        **device_fields(),
    }))


if __name__ == "__main__":
    main()
