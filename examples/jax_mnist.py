"""MNIST-scale training with horovod_tpu (reference:
``examples/tensorflow2_mnist.py``): wrap the optimizer, broadcast initial
state, shard the batch. Uses synthetic data so it runs hermetically.

Single chip:   python examples/jax_mnist.py
CPU 8-mesh:    JAX_PLATFORMS=cpu \
               XLA_FLAGS=--xla_force_host_platform_device_count=8 \
               python examples/jax_mnist.py
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import training
from horovod_tpu.models import MNISTConvNet


def main():
    hvd.init()
    ndev = hvd.num_devices()
    rng = np.random.default_rng(0)

    # synthetic "MNIST": a bright column at 2*label over noise
    n = 128 * ndev
    labels = rng.integers(0, 10, size=(n,))
    images = (rng.standard_normal((n, 28, 28, 1)) * 0.1).astype(np.float32)
    images[np.arange(n), :, labels * 2, 0] += 1.0

    model = MNISTConvNet()
    tx = hvd.DistributedOptimizer(optax.adam(3e-3))
    state = training.create_train_state(model, tx, jax.random.PRNGKey(0),
                                        jnp.zeros((1, 28, 28, 1)))
    step = training.make_train_step(model, tx)

    batch = 16 * ndev
    first_epoch_loss = None
    for epoch in range(6):
        perm = rng.permutation(n)
        epoch_loss = []
        for i in range(0, n, batch):
            idx = perm[i:i + batch]
            if len(idx) < batch:
                break
            state, loss = step(state, jnp.asarray(images[idx]),
                               jnp.asarray(labels[idx]))
            epoch_loss.append(float(loss))
        print(f"epoch {epoch}: loss {np.mean(epoch_loss):.4f}")
        if first_epoch_loss is None:
            first_epoch_loss = np.mean(epoch_loss)
    assert np.mean(epoch_loss) < first_epoch_loss * 0.6, "did not learn"
    print("done")


if __name__ == "__main__":
    main()
