"""Checkpoint/resume conventions (reference
``examples/keras_imagenet_resnet50.py:85-103``): rank-0-only writes,
broadcast resume step, broadcast params/opt_state on restore. The kill
test crashes a 2-proc run mid-training and verifies the resumed run
reproduces the uninterrupted run's losses exactly."""

import os

import numpy as np
import pytest

from horovod_tpu.run import api


def _make_train(ckpt_dir, crash_at):
    def train():
        import jax
        import numpy as np
        import optax

        import horovod_tpu as hvd
        from horovod_tpu import checkpoint
        hvd.init()
        rank, size = hvd.rank(), hvd.size()

        rng = np.random.RandomState(0)
        X = rng.randn(64, 4).astype(np.float32)
        W = np.array([[2.0], [-3.0], [0.5], [1.0]], dtype=np.float32)
        Y = X @ W
        xs, ys = X[rank::size], Y[rank::size]

        params = {"w": np.zeros((4, 1), dtype=np.float32)}
        opt = hvd.DistributedOptimizer(optax.adam(0.1))
        state = opt.init(params)

        step, params, state, meta = checkpoint.restore_or_init(
            ckpt_dir, params, state)
        if step > 0 and hvd.rank() == 0:
            assert meta == {"note": "test"}  # saved meta comes back

        @jax.jit
        def loss_and_grad(p):
            def f(p):
                import jax.numpy as jnp
                return jnp.mean((xs @ p["w"] - ys) ** 2)
            return jax.value_and_grad(f)(p)

        losses = []
        for i in range(step, 10):
            loss, grads = loss_and_grad(params)
            updates, state = opt.update(grads, state, params)
            params = optax.apply_updates(params, updates)
            losses.append(float(loss))
            checkpoint.save_checkpoint(ckpt_dir, i + 1, params, state,
                                       meta={"note": "test"}, keep=3)
            if crash_at is not None and i + 1 == crash_at:
                os_mod = __import__("os")
                os_mod._exit(17)  # simulate a hard crash mid-job
        return step, losses
    return train


def test_kill_and_resume_2proc(tmp_path):
    env = {"JAX_PLATFORMS": "cpu"}
    golden_dir = str(tmp_path / "golden")
    crash_dir = str(tmp_path / "crash")

    # uninterrupted golden run
    golden = api.run(_make_train(golden_dir, None), np=2, extra_env=env)
    g_start, g_losses = golden[0]
    assert g_start == 0 and len(g_losses) == 10

    # run that dies hard at step 6 (both ranks _exit after saving ckpt-6)
    with pytest.raises(RuntimeError):
        api.run(_make_train(crash_dir, 6), np=2, extra_env=env)
    from horovod_tpu import checkpoint
    assert checkpoint.list_steps(crash_dir)[-1] == 6

    # resume: must pick up at step 6 and reproduce the golden tail
    # (losses are shard-local → compare rank against rank)
    resumed = api.run(_make_train(crash_dir, None), np=2, extra_env=env)
    for (r_start, r_losses), (_, rank_golden) in zip(resumed, golden):
        assert r_start == 6
        np.testing.assert_allclose(r_losses, rank_golden[6:], rtol=1e-6)


def test_rank0_only_writes(tmp_path):
    ckpt_dir = str(tmp_path / "ck")

    def probe():
        import numpy as np

        import horovod_tpu as hvd
        from horovod_tpu import checkpoint
        hvd.init()
        # distinct params per rank: after restore_or_init all ranks must
        # hold rank 0's values (broadcast-from-root discipline)
        params = {"w": np.full((3,), float(hvd.rank() + 1),
                               dtype=np.float32)}
        path = checkpoint.save_checkpoint(ckpt_dir, 5, params)
        step, params, _, _meta = checkpoint.restore_or_init(ckpt_dir,
                                                            params)
        return (path is not None, step, float(params["w"][0]))

    results = api.run(probe, np=2, extra_env={"JAX_PLATFORMS": "cpu"})
    wrote = [w for w, _, _ in results]
    assert wrote == [True, False]  # only rank 0 wrote
    for _, step, val in results:
        assert step == 5
        assert val == 1.0  # rank 0's params everywhere


def test_keep_prunes_old_checkpoints(tmp_path, monkeypatch):
    # single-process: rank()==0 without init via basics? simplest: run
    # through the API contract directly in-process
    import horovod_tpu as hvd
    from horovod_tpu import checkpoint
    hvd.init()
    try:
        d = str(tmp_path)
        for s in range(1, 6):
            checkpoint.save_checkpoint(d, s, {"w": np.ones(2)}, keep=2)
        assert checkpoint.list_steps(d) == [4, 5]
        params, _opt, meta = checkpoint.restore_checkpoint(
            d, 5, {"w": np.zeros(2)})
        np.testing.assert_allclose(params["w"], 1.0)
        # meta round-trips (flax target-structure pitfall)
        checkpoint.save_checkpoint(d, 7, {"w": np.ones(2)},
                                   meta={"epoch": 3, "note": "x"})
        _p, _o, meta = checkpoint.restore_checkpoint(d, 7,
                                                     {"w": np.zeros(2)})
        assert meta == {"epoch": 3, "note": "x"}
    finally:
        hvd.shutdown()


def test_atomic_write_no_partial(tmp_path):
    import horovod_tpu as hvd
    from horovod_tpu import checkpoint
    hvd.init()
    try:
        d = str(tmp_path)
        checkpoint.save_checkpoint(d, 1, {"w": np.ones(4)})
        # a stale tmp file (crashed mid-write) must not count as a step
        open(os.path.join(d, "ckpt-2.msgpack.tmp"), "wb").write(b"junk")
        assert checkpoint.list_steps(d) == [1]
        assert checkpoint.resume_step(d) == 1
    finally:
        hvd.shutdown()
