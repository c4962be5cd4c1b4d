"""Synthetic throughput benchmark (reference:
``examples/pytorch_synthetic_benchmark.py``): timed batches after warmup,
img/sec through the DistributedOptimizer hot path.

    python examples/jax_synthetic_benchmark.py --model resnet50 \
        --batch-size 64 --num-iters 10
"""

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import models, training
from horovod_tpu.utils.benchmarks import (compute_dtype, device_fields,
                                          slope_window, sync)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50",
                   choices=["resnet18", "resnet34", "resnet50", "resnet101",
                            "resnet152", "vgg16"])
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-warmup-batches", type=int, default=3)
    p.add_argument("--num-batches-per-iter", type=int, default=10)
    p.add_argument("--num-iters", type=int, default=5)
    p.add_argument("--fp16-allreduce", action="store_true",
                   help="bf16 wire compression for gradient allreduce")
    args = p.parse_args()

    hvd.init()
    ndev = hvd.num_devices()
    device = device_fields()
    dtype = compute_dtype()

    model_cls = {
        "resnet18": models.ResNet18, "resnet34": models.ResNet34,
        "resnet50": models.ResNet50, "resnet101": models.ResNet101,
        "resnet152": models.ResNet152, "vgg16": models.VGG16,
    }[args.model]
    model = model_cls(num_classes=1000, dtype=dtype)

    compression = hvd.Compression.bf16 if args.fp16_allreduce else None
    tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9),
                                  compression=compression)

    gb = args.batch_size * ndev
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.standard_normal(
        (gb, args.image_size, args.image_size, 3)), dtype)
    labels = jnp.asarray(rng.integers(0, 1000, size=(gb,)), jnp.int32)

    state = training.create_train_state(model, tx, jax.random.PRNGKey(0),
                                        images[:1])
    step = training.make_train_step(model, tx)

    print(f"Model: {args.model}, batch {args.batch_size}/chip x {ndev} "
          f"chips ({device['platform']}, {device['device_kind']})")
    for _ in range(args.num_warmup_batches):
        state, loss = step(state, images, labels)
        sync(loss)

    # readback-slope timing per iter (utils/benchmarks.slope_window)
    img_secs = []
    for i in range(args.num_iters):
        dt, state = slope_window(
            lambda st: step(st, images, labels), state,
            args.num_batches_per_iter, base_iters=1)
        rate = gb * args.num_batches_per_iter / dt
        img_secs.append(rate)
        print(f"Iter #{i}: {rate:.1f} img/sec total")
    print(f"Img/sec per chip: {np.mean(img_secs) / ndev:.1f} "
          f"+- {1.96 * np.std(img_secs) / ndev:.1f}")
    print(f"Total img/sec on {ndev} chip(s): {np.mean(img_secs):.1f} "
          f"+- {1.96 * np.std(img_secs):.1f}")
    state, loss = step(state, images, labels)
    print(json.dumps({
        "metric": f"{args.model}_synthetic_images_per_sec_per_chip",
        "value": round(float(np.mean(img_secs)) / ndev, 1),
        "unit": "images/sec/chip",
        "per_chip_batch": args.batch_size,
        "dtype": jnp.dtype(dtype).name,
        "final_loss": round(float(loss), 4),
        **device,
    }))


if __name__ == "__main__":
    main()
