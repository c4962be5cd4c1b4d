"""DistributedOptimizer / distributed_grad / broadcast / join tests.

Reference pattern: the optimizer tests in test/test_torch.py (grad averaging
across ranks, broadcast_parameters, broadcast_optimizer_state) and the Join
zero-fill semantics (controller.cc:209-220)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd_api
from horovod_tpu.ops import collective


def test_distributed_grad_averages(hvd, n_devices):
    def loss(w, x):
        return jnp.sum(w * x)

    def f():
        r = collective.mesh_rank().astype(jnp.float32)
        w = jnp.ones((4,))
        x = (r + 1) * jnp.ones((4,))  # per-shard data
        g = hvd_api.distributed_grad(loss)(w, x)
        return g

    out = jax.shard_map(f, mesh=hvd.mesh(), in_specs=(), out_specs=P(),
                        check_vma=False)()
    expected = np.mean(np.arange(1, n_devices + 1))
    np.testing.assert_allclose(out, expected * np.ones((4,)), rtol=1e-6)


def test_distributed_optimizer_step_equals_mean_grad_sgd(hvd, n_devices):
    lr = 0.1
    tx = hvd_api.DistributedOptimizer(optax.sgd(lr))

    def f():
        r = collective.mesh_rank().astype(jnp.float32)
        w = jnp.ones((3,))
        local_grad = (r + 1) * jnp.ones((3,))
        state = tx.init(w)
        updates, _ = tx.update(local_grad, state, w)
        return optax.apply_updates(w, updates)

    out = jax.shard_map(f, mesh=hvd.mesh(), in_specs=(), out_specs=P(),
                        check_vma=False)()
    mean_g = np.mean(np.arange(1, n_devices + 1))
    np.testing.assert_allclose(out, 1.0 - lr * mean_g, rtol=1e-6)


def test_distributed_optimizer_training_converges(hvd, n_devices):
    """End-to-end: per-shard data, replicated params, SPMD training step.
    This is the Horovod programming model (local grads + allreduce) compiled
    into one XLA program — the 'minimum end-to-end slice' of SURVEY.md §7."""
    rng = np.random.default_rng(0)
    w_true = rng.standard_normal(8).astype(np.float32)
    X = rng.standard_normal((n_devices * 16, 8)).astype(np.float32)
    y = X @ w_true

    tx = hvd_api.DistributedOptimizer(optax.adam(0.1))

    def local_loss(w, xb, yb):
        pred = xb @ w
        return jnp.mean((pred - yb) ** 2)

    def step(w, opt_state, xb, yb):
        g = jax.grad(local_loss)(w, xb, yb)  # local gradient
        updates, opt_state = tx.update(g, opt_state, w)  # allreduce inside
        return optax.apply_updates(w, updates), opt_state

    w0 = jnp.zeros((8,))
    opt_state0 = tx.init(w0)

    sharded_step = jax.jit(jax.shard_map(
        step, mesh=hvd.mesh(),
        in_specs=(P(), jax.tree_util.tree_map(lambda _: P(), opt_state0),
                  P("data"), P("data")),
        out_specs=(P(), jax.tree_util.tree_map(lambda _: P(), opt_state0)),
        check_vma=False))

    w, opt_state = w0, opt_state0
    for _ in range(200):
        w, opt_state = sharded_step(w, opt_state, X, y)
        # one step in flight at a time: 200 dispatches queued ahead, each
        # an eight-participant rendezvous on the CPU backend's thread
        # pool, abort the process (SIGABRT, no Python error) when other
        # xdist workers hold the cores
        w.block_until_ready()
    np.testing.assert_allclose(np.asarray(w), w_true, atol=1e-2)


def test_broadcast_variables(hvd, n_devices):
    def f():
        r = collective.mesh_rank().astype(jnp.float32)
        params = {"w": (r + 1) * jnp.ones((4,)), "b": r * jnp.ones((2,))}
        return hvd_api.broadcast_variables(params, root_rank=0)

    out = jax.shard_map(f, mesh=hvd.mesh(), in_specs=(),
                        out_specs={"w": P(), "b": P()}, check_vma=False)()
    np.testing.assert_allclose(out["w"], np.ones((4,)))
    np.testing.assert_allclose(out["b"], np.zeros((2,)))


def test_broadcast_optimizer_state(hvd, n_devices):
    tx = optax.adam(1e-3)

    def f():
        r = collective.mesh_rank().astype(jnp.float32)
        w = (r + 1) * jnp.ones((3,))
        state = tx.init(w)
        state = jax.tree_util.tree_map(
            lambda x: x + r if jnp.issubdtype(x.dtype, jnp.floating) else x,
            state)
        return hvd_api.broadcast_optimizer_state(state, root_rank=0)

    state0 = tx.init(jnp.ones((3,)))
    specs = jax.tree_util.tree_map(lambda _: P(), state0)
    out = jax.shard_map(f, mesh=hvd.mesh(), in_specs=(), out_specs=specs,
                        check_vma=False)()
    # root is rank 0 whose floats were +0 -> identical to fresh init
    ref_leaves = jax.tree_util.tree_leaves(state0)
    out_leaves = jax.tree_util.tree_leaves(out)
    for a, b in zip(out_leaves, ref_leaves):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))


def test_join_uneven_data(hvd, n_devices):
    """Shards beyond rank 2 have exhausted data: mean over active only
    (zero-fill semantics of the reference Join op)."""
    n_active = 3

    def f():
        r = collective.mesh_rank()
        active = r < n_active
        g = {"w": (r + 1).astype(jnp.float32) * jnp.ones((4,))}
        reduced, count = hvd_api.join(g, active)
        return reduced, count

    out, count = jax.shard_map(f, mesh=hvd.mesh(), in_specs=(),
                               out_specs=({"w": P()}, P()),
                               check_vma=False)()
    assert float(count) == n_active
    expected = np.mean(np.arange(1, n_active + 1))
    np.testing.assert_allclose(out["w"], expected * np.ones((4,)), rtol=1e-6)


def test_allreduce_metrics(hvd, n_devices):
    def f():
        r = collective.mesh_rank().astype(jnp.float32)
        return hvd_api.allreduce_metrics({"loss": r, "acc": 2 * r})

    out = jax.shard_map(f, mesh=hvd.mesh(), in_specs=(),
                        out_specs={"loss": P(), "acc": P()},
                        check_vma=False)()
    mean_r = np.mean(np.arange(n_devices))
    np.testing.assert_allclose(out["loss"], mean_r)
    np.testing.assert_allclose(out["acc"], 2 * mean_r)


def test_allreduce_metrics_sum_keeps_int_dtype(hvd, n_devices):
    """op=Sum totals int-valued metrics exactly in their own dtype
    (sample counts stay ints); Average still yields the fp32 mean."""
    from horovod_tpu.ops.reduction import Sum

    def f():
        n = collective.mesh_rank().astype(jnp.int32) + 1
        return hvd_api.allreduce_metrics({"count": n}, op=Sum)

    out = jax.shard_map(f, mesh=hvd.mesh(), in_specs=(),
                        out_specs={"count": P()}, check_vma=False)()
    assert out["count"].dtype == jnp.int32
    assert int(out["count"]) == n_devices * (n_devices + 1) // 2


def test_backward_passes_per_step(hvd, n_devices):
    tx = hvd_api.DistributedOptimizer(optax.sgd(1.0),
                                      backward_passes_per_step=2)

    def f():
        r = collective.mesh_rank().astype(jnp.float32)
        w = jnp.zeros((2,))
        state = tx.init(w)
        g = (r + 1) * jnp.ones((2,))
        u1, state = tx.update(g, state, w)
        w = optax.apply_updates(w, u1)
        u2, state = tx.update(g, state, w)
        w = optax.apply_updates(w, u2)
        return w

    out = jax.shard_map(f, mesh=hvd.mesh(), in_specs=(), out_specs=P(),
                        check_vma=False)()
    # after 2 micro-steps: one real step with the mean over accumulated grads
    mean_g = np.mean(np.arange(1, n_devices + 1))
    np.testing.assert_allclose(np.asarray(out), -mean_g * np.ones((2,)),
                               rtol=1e-6)
