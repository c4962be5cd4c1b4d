"""HBM roofline for the headline ResNet step — and, with ``--lm``, for
the MXU-saturating d2048 transformer LM step.

Is the measured MFU the hardware bound or a software gap? This script
answers with numbers, not claims:

* per-device ``flops`` and ``bytes accessed`` of the ACTUAL compiled
  train step, from XLA's own cost analysis;
* the chip's empirical bf16 matmul peak (``bench.calibrate_peak_tflops``
  — a measured ceiling, not a datasheet number);
* the chip's empirical HBM bandwidth: a streaming elementwise chain with
  ``optimization_barrier`` between iterations (defeats loop fusion, so
  every iteration really moves read+write bytes), timed by the readback
  slope protocol;
* the roofline bound ``t >= max(flops/peak, bytes/bw)`` vs the measured
  step time, and the achieved/bound ratio.

``--lm`` judges the LM MFU against its ACTUAL bound: the same compiled
``cost_analysis()`` flops+bytes for the d2048 flash-attention
transformer step (the ``lm_d2048`` workload bench.py's LM MFU line runs)
against the same empirical ceilings, emitting
``lm_roofline_achieved_over_bound`` — so the LM MFU can be read as "x%
of what this step could physically do", not against the matmul peak
alone.

Prints ONE JSON line per invocation, naming the platform,
``device_kind`` and device count it was taken on. Runs in one process,
which holds the chip; ``main()`` refuses any platform but a TPU. How
the FLOPs and bytes are counted: docs/PERFORMANCE.md, "How the
benchmarks measure".
"""

import argparse
import statistics

import jax
import jax.numpy as jnp
import numpy as np
import optax


def measure_hbm_bandwidth(nbytes=1 << 29, chain=8, repeats=3):
    """Empirical streaming bandwidth: x <- x + 1 on an nbytes buffer,
    ``chain`` barrier-separated iterations per call (each moves
    2*nbytes: one read + one write), slope-timed."""
    from horovod_tpu.utils.benchmarks import slope_window, sync

    n = nbytes // 2  # bf16
    x = jnp.zeros((n,), jnp.bfloat16)

    @jax.jit
    def stream(x):
        for _ in range(chain):
            x = jax.lax.optimization_barrier(x + jnp.bfloat16(1.0))
        return x

    x = stream(x)
    sync(x)
    samples = []
    for _ in range(repeats):
        dt, x = slope_window(lambda v: (stream(v),) * 2, x, iters=4,
                             base_iters=1)
        samples.append(4 * chain * 2 * nbytes / dt / 1e9)
    return statistics.median(samples)


def _roofline_result(metric, flops, bytes_accessed, peak_tf, bw_gbs,
                     step_s):
    """The shared roofline arithmetic + JSON shape for both workloads:
    one copy, so the ResNet and LM lines cannot compute their bound or
    MFU fields differently."""
    # publish what WAS measurable even when a ceiling calibration fails
    # (peak/bandwidth of 0 would otherwise divide-by-zero)
    t_compute = flops / (peak_tf * 1e12) if peak_tf > 0 else 0.0
    t_memory = bytes_accessed / (bw_gbs * 1e9) if bw_gbs > 0 else 0.0
    t_bound = max(t_compute, t_memory)
    result = {
        "metric": metric,
        "value": round(t_bound / step_s, 3) if t_bound else None,
        "unit": "ratio",
        "flops_per_step": flops,
        "bytes_accessed_per_step": bytes_accessed,
        "arithmetic_intensity_flops_per_byte": round(
            flops / bytes_accessed, 2) if bytes_accessed else None,
        "empirical_peak_tflops_bf16": round(peak_tf, 1),
        "empirical_hbm_gbs": round(bw_gbs, 1),
        "t_compute_ms": round(1e3 * t_compute, 2),
        "t_memory_ms": round(1e3 * t_memory, 2),
        "t_bound_ms": round(1e3 * t_bound, 2),
        "t_measured_ms": round(1e3 * step_s, 2),
        "bound_by": "memory" if t_memory > t_compute else "compute",
    }
    if peak_tf > 0:
        result["mfu_vs_empirical_peak_pct"] = round(
            100 * flops / step_s / (peak_tf * 1e12), 1)
    if t_bound > 0:
        result["mfu_bound_pct"] = round(100 * t_compute / t_bound, 1)
    return result


def resnet_roofline(args):
    import bench
    import horovod_tpu as hvd
    from horovod_tpu import training
    from horovod_tpu.utils.benchmarks import (make_model, repeat_throughput,
                                              synthetic_batch)

    hvd.init()
    model = make_model(args.model)
    tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))
    images, labels = synthetic_batch(args.batch_size * hvd.num_devices(),
                                     args.image_size)
    state = training.create_train_state(model, tx, jax.random.PRNGKey(0),
                                        images[:1])
    step = training.make_train_step(model, tx, donate=True)
    from horovod_tpu.utils.benchmarks import cost_analysis_dict
    cost = cost_analysis_dict(
        step.lower(state, images, labels).compile())
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))

    peak_tf, _ = bench.calibrate_peak_tflops()
    bw_gbs = measure_hbm_bandwidth()

    runs = repeat_throughput(step, state, images, labels, warmup=3,
                             iters=args.num_iters, repeats=args.repeats)
    step_s = statistics.median(r[1] for r in runs) / args.num_iters
    bench.emit(_roofline_result(
        f"{args.model}_roofline_achieved_over_bound", flops,
        bytes_accessed, peak_tf, bw_gbs, step_s))


def lm_roofline(args):
    """``--lm``: the d2048 flash-attention transformer step (the exact
    ``lm_d2048`` workload carrying bench.py's LM MFU) against the same
    empirical ceilings — its MFU judged against the step's ACTUAL
    roofline bound, not the pure-matmul peak."""
    import bench
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.utils.benchmarks import (make_lm_bench,
                                              repeat_step_windows)

    hvd.init()
    devs = np.asarray(jax.devices())
    mesh = jax.sharding.Mesh(devs[:1].reshape(1, 1), ("data", "seq"))
    step, state, tokens = make_lm_bench(
        mesh=mesh, seq_axis=None, batch=args.lm_batch,
        seq_len=args.lm_seq_len, layers=args.lm_layers,
        d_model=args.lm_d_model, heads=args.lm_heads,
        vocab=args.lm_vocab, flash=True)
    from horovod_tpu.utils.benchmarks import cost_analysis_dict
    cost = cost_analysis_dict(step.lower(state, tokens).compile())
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))

    peak_tf, _ = bench.calibrate_peak_tflops()
    bw_gbs = measure_hbm_bandwidth()

    dts, state = repeat_step_windows(
        lambda st: step(st, tokens), state, 2, args.num_iters,
        max(1, args.repeats))
    step_s = statistics.median(float(d) for d in dts) / args.num_iters
    result = _roofline_result(
        "lm_roofline_achieved_over_bound", flops, bytes_accessed,
        peak_tf, bw_gbs, step_s)
    n_bound = sum(1 for d in dts if getattr(d, "upper_bound", False))
    if n_bound:  # inverted-window fallbacks: bounds, not measurements
        result["upper_bound_windows"] = n_bound
    result.update({
        "lm_d_model": args.lm_d_model, "lm_layers": args.lm_layers,
        "lm_heads": args.lm_heads, "lm_seq_len": args.lm_seq_len,
        "lm_batch": args.lm_batch,
        "tokens_per_sec": round(args.lm_batch * args.lm_seq_len / step_s,
                                1),
    })
    bench.emit(result)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet101")
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--num-iters", type=int, default=10)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--lm", action="store_true",
                    help="roofline the d2048 transformer LM step instead "
                         "of the ResNet step (the bench.py LM MFU "
                         "workload; emits lm_roofline_achieved_over_bound)")
    ap.add_argument("--lm-d-model", type=int, default=2048)
    ap.add_argument("--lm-layers", type=int, default=8)
    ap.add_argument("--lm-heads", type=int, default=16)
    ap.add_argument("--lm-seq-len", type=int, default=2048)
    ap.add_argument("--lm-batch", type=int, default=8)
    ap.add_argument("--lm-vocab", type=int, default=32000)
    args = ap.parse_args()

    import bench
    import horovod_tpu as hvd

    hvd.init()  # before the first backend touch (its libtpu flags)
    bench.require_chip("bench_roofline.py")
    if args.lm:
        lm_roofline(args)
        return
    resnet_roofline(args)


if __name__ == "__main__":
    main()
