"""The ResNet family: ``models/resnet.py`` trained through
``hvd.DistributedOptimizer(optax.sgd)``, ``training.create_train_state``
and the explicit ``training.make_train_step``, state donated: the
reference Horovod's own synthetic benchmark, on this repo's path.
"""

import types

import numpy as np

ITEM = "images"
REFERENCE_BATCH = 32  # what the float32 reference can hold: see build()

# bfloat16 convolutions and BatchNorm arithmetic through a hundred layers
# against float32 at the highest precision, on 32 images. Measured on the
# chip over 15 runs, each another seed (PERF.md, Findings): loss within
# 1.5e-4 relative, gradient norm within 1.1e-3. The tolerances leave five
# times that. A gradient left unaveraged over the chips or a cross-entropy
# summed where it should be averaged is off by a factor of the world size
# or the batch; BatchNorm statistics or a gradient accumulated in bfloat16
# lose the third digit of the norm.
RTOL = {"loss": 8e-4, "grad_norm": 5e-3}


def conv_macs_per_image(config, traffic):
    """Multiply-accumulates of every convolution and of the classifier
    for one image, v1.5 bottlenecks (stride on the 3x3), SAME padding."""
    size, width = traffic["image_size"], config["num_filters"]
    size = -(-size // 2)
    macs = 7 * 7 * 3 * width * size * size       # stem, stride 2
    size = -(-size // 2)                          # max-pool, stride 2
    channels = width
    for stage, blocks in enumerate(config["stage_sizes"]):
        mid = width * 2 ** stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            out = -(-size // stride)
            macs += channels * mid * size * size          # 1x1
            macs += 9 * mid * mid * out * out             # 3x3, strided
            macs += mid * 4 * mid * out * out             # 1x1
            if channels != 4 * mid or stride != 1:
                macs += channels * 4 * mid * out * out    # projection
            channels, size = 4 * mid, out
    return macs + channels * config["num_classes"]


def required_flops_per_item(config, traffic):
    """FLOPs the forward and backward passes of ONE image require: two a
    multiply-accumulate, forward once and backward twice (the gradient
    of the input and of the weights). BatchNorm, ReLU and pooling are not
    counted, and neither is the stem's unneeded input gradient taken off
    (0.5%): this is the convention of every published utilisation."""
    return 3 * 2 * conv_macs_per_image(config, traffic)


def kernel_work(config, traffic):
    return None  # no Pallas kernel on this path


def build(config, traffic, mesh, seed):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from benchmark.harness import check
    from benchmark.reference import resnet as reference
    from horovod_tpu import training
    from horovod_tpu.models.resnet import BottleneckBlock, ResNet

    assumed = config["assumed"]
    chips = mesh.devices.size
    if chips != 1:
        # the check below feeds every shard the same 32 images, so across
        # chips it could not tell an averaged gradient from an unexchanged
        # one (PERF.md section 7)
        raise ValueError("the resnet family's reference check covers one "
                         "chip; a data-parallel ResNet cell needs a check "
                         "that proves the exchange first")
    if traffic["per_chip_batch"] % REFERENCE_BATCH:
        raise ValueError(f"per_chip_batch must be a multiple of "
                         f"{REFERENCE_BATCH}")
    if assumed["compute_dtype"] != "bfloat16":
        raise ValueError("the resnet family runs bfloat16 compute")
    batch, size = traffic["per_chip_batch"] * chips, traffic["image_size"]
    classes = config["num_classes"]
    sizes = dict(stage_sizes=config["stage_sizes"],
                 block_cls=BottleneckBlock, num_classes=classes,
                 num_filters=config["num_filters"])
    model = ResNet(dtype=jnp.bfloat16, **sizes)
    model_f32 = ResNet(dtype=jnp.float32, **sizes)
    tx = hvd.DistributedOptimizer(optax.sgd(
        assumed["learning_rate"], momentum=assumed["momentum"]))
    step = training.make_train_step(model, tx, mesh=mesh, donate=True)
    replicated = NamedSharding(mesh, P())
    by_batch = NamedSharding(mesh, P("data"))
    seed = np.uint32(seed)

    init = jax.jit(lambda s: training.create_train_state(
        model, tx, jax.random.fold_in(jax.random.PRNGKey(s), 0),
        jnp.zeros((1, size, size, 3), jnp.bfloat16)),
        out_shardings=replicated)

    def draw_batch(s, n, stream):
        key = jax.random.fold_in(jax.random.PRNGKey(s), stream)
        images = jax.random.normal(key, (n, size, size, 3), jnp.bfloat16)
        labels = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0,
                                    classes, jnp.int32)
        return images, labels

    draw = jax.jit(lambda s: draw_batch(s, batch, 1),
                   out_shardings=by_batch)

    def draw_sample(s):
        images, labels = draw_batch(s, REFERENCE_BATCH, 2)
        times = batch // REFERENCE_BATCH
        return (images, labels), (jnp.tile(images, (times, 1, 1, 1)),
                                  jnp.tile(labels, times))

    sample = jax.jit(draw_sample, out_shardings=(replicated, by_batch))
    ref = jax.jit(lambda params, stats, images, labels:
                  reference.loss_and_grad_norm(model_f32, params, stats,
                                               images, labels))

    def reference_check(check_seed=seed):
        """(``check_seed``: another seed's parameters for the reference
        show that the check can fail.) One step of the step under test on
        32 seeded images repeated to
        its batch, against the float32 module on the 32. BatchNorm ties a
        loss to its batch, but a batch of whole copies has the mean and
        the (biased) variance of one copy in every channel, so loss and
        gradient of the repeated batch are those of the 32: the check
        runs the very program that is measured, and the reference stays
        at a size float32 can hold."""
        (images, labels), repeated = sample(seed)
        state = init(seed)
        state, loss = step(state, *repeated)
        got = {"loss": loss,
               "grad_norm": check.first_moment_norm(
                   state.opt_state, optax.TraceState, "trace")}
        del state
        fresh = init(np.uint32(check_seed))
        want = dict(zip(("loss", "grad_norm"), ref(
            fresh.params, fresh.batch_stats, images, labels)))
        return check.compare(got, want, RTOL)

    return types.SimpleNamespace(
        item=ITEM, items_per_step=batch, step=step,
        init_state=lambda: init(seed), batch=lambda: draw(seed),
        reference_check=reference_check, wants_pallas_kernel=False)
