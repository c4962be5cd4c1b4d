"""Pipeline parallelism: the GPipe and 1F1B schedules must equal running
the layer stack sequentially on one device — forward AND gradients
(GPipe via reverse-mode through the transposed ppermutes; 1F1B via its
explicit per-microbatch vjp schedule)."""

import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from horovod_tpu.parallel.pipeline import (_schedule_1f1b,
                                           pipeline_train_1f1b,
                                           pipelined_forward, stack_params)


class Layer(nn.Module):
    d: int

    @nn.compact
    def __call__(self, x):
        h = nn.Dense(2 * self.d, use_bias=False)(x)
        return x + nn.Dense(self.d, use_bias=False)(nn.gelu(h))


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("stage",))


def _setup(rng, n_layers=4, d=8, batch=8):
    layer = Layer(d)
    x = jnp.asarray(rng.standard_normal((batch, d)), jnp.float32)
    trees = [layer.init(jax.random.PRNGKey(i), x)["params"]
             for i in range(n_layers)]
    block_fn = lambda p, v: layer.apply({"params": p}, v)  # noqa: E731
    return block_fn, stack_params(trees), x


def _oracle(block_fn, stacked, x):
    return jax.lax.scan(lambda c, p: (block_fn(p, c), None), x, stacked)[0]


@pytest.mark.parametrize("n_stages,n_layers,n_micro", [
    (4, 4, 4),   # one layer per stage
    (2, 4, 8),   # two layers per stage, more microbatches than stages
    (4, 8, 2),   # fewer microbatches than stages
])
def test_pipeline_matches_sequential(rng, n_stages, n_layers, n_micro):
    block_fn, stacked, x = _setup(rng, n_layers=n_layers)
    out = pipelined_forward(block_fn, stacked, x, mesh=_mesh(n_stages),
                            n_micro=n_micro)
    want = _oracle(block_fn, stacked, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("n_stages,n_layers,n_micro", [
    (4, 4, 4),   # one layer per stage
    (2, 4, 8),   # two layers per stage, more microbatches than stages
    (4, 8, 2),   # fewer microbatches than stages (drain-tick clipping)
])
def test_pipeline_gradients_match(rng, n_stages, n_layers, n_micro):
    block_fn, stacked, x = _setup(rng, n_layers=n_layers)
    mesh = _mesh(n_stages)

    def pp_loss(params):
        return jnp.mean(pipelined_forward(block_fn, params, x, mesh=mesh,
                                          n_micro=n_micro) ** 2)

    def oracle_loss(params):
        return jnp.mean(_oracle(block_fn, params, x) ** 2)

    lp, gp = jax.value_and_grad(pp_loss)(stacked)
    lo, go = jax.value_and_grad(oracle_loss)(stacked)
    np.testing.assert_allclose(float(lp), float(lo), rtol=1e-6)
    for path, leaf in jax.tree_util.tree_leaves_with_path(gp):
        want = dict(jax.tree_util.tree_leaves_with_path(go))[path]
        np.testing.assert_allclose(np.asarray(leaf), np.asarray(want),
                                   rtol=2e-4, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))


def test_pipeline_composes_with_data_parallel(rng):
    """PP x DP on a (data x stage) mesh: each data slice pipelines its
    batch shard; stacked-param gradients come back psum'd over the data
    axis by the shard_map transpose — identical to the global oracle."""
    block_fn, stacked, x = _setup(rng, n_layers=4, batch=8)
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "stage"))

    def pp_loss(params):
        out = pipelined_forward(block_fn, params, x, mesh=mesh,
                                batch_axis="data", n_micro=2)
        return jnp.mean(out ** 2)

    def oracle_loss(params):
        return jnp.mean(_oracle(block_fn, params, x) ** 2)

    lp, gp = jax.value_and_grad(pp_loss)(stacked)
    lo, go = jax.value_and_grad(oracle_loss)(stacked)
    np.testing.assert_allclose(float(lp), float(lo), rtol=1e-6)
    for path, leaf in jax.tree_util.tree_leaves_with_path(gp):
        want = dict(jax.tree_util.tree_leaves_with_path(go))[path]
        np.testing.assert_allclose(np.asarray(leaf), np.asarray(want),
                                   rtol=2e-4, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))


class _NormLayer(nn.Module):
    """vjp of x/||x|| is NaN at x=0: the regression class for bubble
    seeding (a zeros-seeded schedule returns finite loss, NaN grads)."""

    @nn.compact
    def __call__(self, x):
        y = nn.Dense(x.shape[-1], use_bias=False)(x)
        return y / jnp.linalg.norm(y, axis=-1, keepdims=True)


def test_pipeline_grads_finite_for_norm_blocks(rng):
    layer = _NormLayer()
    x = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
    trees = [layer.init(jax.random.PRNGKey(i), x)["params"]
             for i in range(4)]
    stacked = stack_params(trees)
    block_fn = lambda p, v: layer.apply({"params": p}, v)  # noqa: E731
    mesh = _mesh(4)

    def pp_loss(params):
        return jnp.mean(
            pipelined_forward(block_fn, params, x, mesh=mesh) ** 2)

    def oracle_loss(params):
        return jnp.mean(_oracle(block_fn, params, x) ** 2)

    lp, gp = jax.value_and_grad(pp_loss)(stacked)
    lo, go = jax.value_and_grad(oracle_loss)(stacked)
    np.testing.assert_allclose(float(lp), float(lo), rtol=1e-6)
    for path, leaf in jax.tree_util.tree_leaves_with_path(gp):
        assert np.isfinite(np.asarray(leaf)).all(), (
            f"NaN grads through bubble ticks: {jax.tree_util.keystr(path)}")
        want = dict(jax.tree_util.tree_leaves_with_path(go))[path]
        np.testing.assert_allclose(np.asarray(leaf), np.asarray(want),
                                   rtol=2e-4, atol=1e-6)


def test_pipeline_rejects_indivisible_shapes(rng):
    block_fn, stacked, x = _setup(rng, n_layers=4, batch=8)
    with pytest.raises(ValueError, match="not divisible"):
        pipelined_forward(block_fn, stacked, x, mesh=_mesh(4), n_micro=3)
    with pytest.raises(ValueError, match="layers not divisible"):
        pipelined_forward(block_fn, stacked, x, mesh=_mesh(3), n_micro=4)


# ---- 1F1B -----------------------------------------------------------------

def _grads_match(got, want, **kw):
    wm = dict(jax.tree_util.tree_leaves_with_path(want))
    for path, leaf in jax.tree_util.tree_leaves_with_path(got):
        np.testing.assert_allclose(np.asarray(leaf), np.asarray(wm[path]),
                                   err_msg=jax.tree_util.keystr(path), **kw)


@pytest.mark.parametrize("n_stages,n_micro", [(2, 2), (2, 8), (4, 4),
                                              (4, 8), (4, 16)])
def test_1f1b_schedule_properties(n_stages, n_micro):
    """Every stage forwards and backwards each microbatch exactly once,
    in-flight stays within min(n_micro, n_stages - s), and the total
    tick count is the classic 2*(n_micro + n_stages - 1)."""
    fwd, bwd = _schedule_1f1b(n_stages, n_micro)
    assert fwd.shape[0] == 2 * (n_micro + n_stages - 1)
    for s in range(n_stages):
        assert sorted(m for m in fwd[:, s] if m >= 0) == list(range(n_micro))
        assert sorted(m for m in bwd[:, s] if m >= 0) == list(range(n_micro))
        inflight = 0
        peak = 0
        for t in range(fwd.shape[0]):
            inflight += int(fwd[t, s] >= 0) - int(bwd[t, s] >= 0)
            peak = max(peak, inflight)
        assert peak <= min(n_micro, n_stages - s), (s, peak)


@pytest.mark.parametrize("n_stages,n_layers,n_micro", [
    (4, 4, 4),   # one layer per stage
    (2, 4, 8),   # two layers per stage, ring-buffer reuse (M > S)
    (4, 8, 2),   # fewer microbatches than stages
    (4, 4, 16),  # deep microbatching
])
def test_1f1b_matches_sequential(rng, n_stages, n_layers, n_micro):
    block_fn, stacked, x = _setup(rng, n_layers=n_layers, batch=16)
    mesh = _mesh(n_stages)
    loss, grads = pipeline_train_1f1b(
        block_fn, stacked, x, lambda y, m: jnp.sum(y ** 2), mesh=mesh,
        n_micro=n_micro)
    lo, go = jax.value_and_grad(
        lambda p: jnp.sum(_oracle(block_fn, p, x) ** 2))(stacked)
    np.testing.assert_allclose(float(loss), float(lo), rtol=1e-5)
    _grads_match(grads, go, rtol=2e-4, atol=1e-5)


def test_1f1b_input_grad_matches(rng):
    block_fn, stacked, x = _setup(rng, n_layers=4, batch=16)
    mesh = _mesh(4)
    loss, grads, dh = pipeline_train_1f1b(
        block_fn, stacked, x, lambda y, m: jnp.sum(y ** 2), mesh=mesh,
        n_micro=4, with_input_grad=True)
    _, pull = jax.vjp(lambda v: jnp.sum(_oracle(block_fn, stacked, v) ** 2),
                      x)
    (want,) = pull(jnp.ones(()))
    np.testing.assert_allclose(np.asarray(dh), np.asarray(want),
                               rtol=2e-4, atol=1e-5)


def test_1f1b_composes_with_data_parallel(rng):
    block_fn, stacked, x = _setup(rng, n_layers=4, batch=16)
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "stage"))
    loss, grads = pipeline_train_1f1b(
        block_fn, stacked, x, lambda y, m: jnp.sum(y ** 2), mesh=mesh,
        n_micro=2, batch_axis="data")
    lo, go = jax.value_and_grad(
        lambda p: jnp.sum(_oracle(block_fn, p, x) ** 2))(stacked)
    np.testing.assert_allclose(float(loss), float(lo), rtol=1e-5)
    _grads_match(grads, go, rtol=2e-4, atol=1e-5)


def _tp_block(p, x):
    """Megatron column/row pair, vma-correct: pcast-to-varying feeds the
    column matmul, psum closes the row product (their transposes — psum
    and pcast — are what 1F1B's inner vjp relies on)."""
    xv = jax.lax.pcast(x, "model", to="varying")
    return x + jax.lax.psum(jax.nn.gelu(xv @ p["w1"]) @ p["w2"], "model")


def _tp_dense(p, x):
    return x + jax.nn.gelu(x @ p["w1"]) @ p["w2"]


def _tp_setup(rng, d=8, ff=16, n_layers=4):
    trees = [{"w1": jnp.asarray(rng.standard_normal((d, ff)) / d ** 0.5,
                                jnp.float32),
              "w2": jnp.asarray(rng.standard_normal((ff, d)) / ff ** 0.5,
                                jnp.float32)} for _ in range(n_layers)]
    x = jnp.asarray(rng.standard_normal((16, d)), jnp.float32)
    specs = {"w1": P(None, "model"), "w2": P("model", None)}
    return stack_params(trees), x, specs


def test_1f1b_composes_with_tensor_and_data_parallel(rng):
    """PP x TP x DP on a (data, stage, model) mesh: loss and grads equal
    the single-device dense oracle."""
    stacked, x, specs = _tp_setup(rng)
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                ("data", "stage", "model"))
    loss, grads = pipeline_train_1f1b(
        _tp_block, stacked, x, lambda y, m: jnp.sum(y ** 2), mesh=mesh,
        n_micro=4, batch_axis="data", param_specs=specs)
    lo, go = jax.value_and_grad(
        lambda p: jnp.sum(_oracle(_tp_dense, p, x) ** 2))(stacked)
    np.testing.assert_allclose(float(loss), float(lo), rtol=1e-5)
    _grads_match(grads, go, rtol=2e-3, atol=1e-4)


def test_gpipe_composes_with_tensor_parallel(rng):
    """param_specs on the GPipe path: the shard_map AD transpose places
    the TP backward collectives."""
    stacked, x, specs = _tp_setup(rng)
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                ("data", "stage", "model"))

    def pp_loss(p):
        out = pipelined_forward(_tp_block, p, x, mesh=mesh, n_micro=2,
                                batch_axis="data", param_specs=specs)
        return jnp.sum(out ** 2)

    lp, gp = jax.value_and_grad(pp_loss)(stacked)
    lo, go = jax.value_and_grad(
        lambda p: jnp.sum(_oracle(_tp_dense, p, x) ** 2))(stacked)
    np.testing.assert_allclose(float(lp), float(lo), rtol=1e-5)
    _grads_match(gp, go, rtol=2e-3, atol=1e-4)


def test_gpipe_remat_matches_and_cuts_memory(rng):
    """remat=True: same loss/grads as plain GPipe, with the scan's
    saved residuals cut to per-layer boundaries."""
    block_fn, stacked, x = _setup(rng, n_layers=4, batch=16)
    mesh = _mesh(4)

    def loss(p, remat):
        out = pipelined_forward(block_fn, p, x, mesh=mesh, n_micro=4,
                                remat=remat)
        return jnp.sum(out ** 2)

    lp, gp = jax.value_and_grad(lambda p: loss(p, True))(stacked)
    lo, go = jax.value_and_grad(lambda p: loss(p, False))(stacked)
    np.testing.assert_allclose(float(lp), float(lo), rtol=1e-6)
    _grads_match(gp, go, rtol=2e-4, atol=1e-6)

    # memory: at wide layers + many microbatches, remat residuals are
    # a fraction of the full-activation residuals
    d, L, M = 128, 4, 16

    class Wide(nn.Module):
        @nn.compact
        def __call__(self, v):
            hdn = nn.Dense(4 * d, use_bias=False)(v)
            return v + nn.Dense(d, use_bias=False)(nn.gelu(hdn))

    layer = Wide()
    x0 = jnp.ones((8, d), jnp.float32)
    trees = [layer.init(jax.random.PRNGKey(i), x0)["params"]
             for i in range(L)]
    st = stack_params(trees)
    blk = lambda p, v: layer.apply({"params": p}, v)  # noqa: E731
    xw = jnp.ones((32 * M, d), jnp.float32)

    def mem(remat):
        f = jax.jit(jax.value_and_grad(lambda p: jnp.sum(pipelined_forward(
            blk, p, xw, mesh=mesh, n_micro=M, remat=remat) ** 2)))
        m = f.lower(st).compile().memory_analysis()
        return None if m is None else m.temp_size_in_bytes

    m_plain, m_remat = mem(False), mem(True)
    if m_plain is None:
        pytest.skip("backend reports no memory analysis")
    assert m_remat < m_plain, (m_remat, m_plain)


def test_1f1b_memory_bounded_vs_gpipe(rng):
    """THE point of 1F1B: activation memory O(n_stages), not O(n_micro).
    At n_micro=32 the compiled 1F1B step's temporaries must be far below
    GPipe-AD's (which saves residuals for every schedule tick)."""
    d, L, S, M = 128, 4, 4, 32

    class Wide(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = nn.Dense(4 * d, use_bias=False)(x)
            return x + nn.Dense(d, use_bias=False)(nn.gelu(h))

    layer = Wide()
    x0 = jnp.ones((8, d), jnp.float32)
    trees = [layer.init(jax.random.PRNGKey(i), x0)["params"]
             for i in range(L)]
    stacked = stack_params(trees)
    block_fn = lambda p, v: layer.apply({"params": p}, v)  # noqa: E731
    mesh = _mesh(S)
    x = jnp.ones((64 * M, d), jnp.float32)

    gp = jax.jit(jax.value_and_grad(lambda p: jnp.sum(pipelined_forward(
        block_fn, p, x, mesh=mesh, n_micro=M) ** 2)))
    f1 = jax.jit(lambda p: pipeline_train_1f1b(
        block_fn, p, x, lambda y, m: jnp.sum(y ** 2), mesh=mesh,
        n_micro=M))
    mg = gp.lower(stacked).compile().memory_analysis()
    m1 = f1.lower(stacked).compile().memory_analysis()
    if mg is None or m1 is None:
        pytest.skip("backend reports no memory analysis")
    # measured: ~259 MiB (GPipe) vs ~6 MiB (1F1B); 4x margin
    assert m1.temp_size_in_bytes * 4 < mg.temp_size_in_bytes, (
        m1.temp_size_in_bytes, mg.temp_size_in_bytes)


@pytest.mark.skipif("HVD_PERF_TESTS" not in __import__("os").environ,
                    reason="wall-clock perf assertion: opt-in via "
                           "HVD_PERF_TESTS=1 (flaky on loaded machines)")
def test_1f1b_throughput_beats_gpipe(rng):
    """At n_micro=8 on the virtual mesh, the explicitly scheduled step
    outruns differentiating the GPipe scan (measured ~2.8x; assert a
    conservative margin to stay robust to CI noise)."""
    d, L, S, M = 128, 4, 4, 8

    class Wide(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = nn.Dense(4 * d, use_bias=False)(x)
            return x + nn.Dense(d, use_bias=False)(nn.gelu(h))

    layer = Wide()
    x0 = jnp.ones((8, d), jnp.float32)
    trees = [layer.init(jax.random.PRNGKey(i), x0)["params"]
             for i in range(L)]
    stacked = stack_params(trees)
    block_fn = lambda p, v: layer.apply({"params": p}, v)  # noqa: E731
    mesh = _mesh(S)
    x = jnp.ones((64 * M, d), jnp.float32)

    gp = jax.jit(jax.value_and_grad(lambda p: jnp.sum(pipelined_forward(
        block_fn, p, x, mesh=mesh, n_micro=M) ** 2)))
    f1 = jax.jit(lambda p: pipeline_train_1f1b(
        block_fn, p, x, lambda y, m: jnp.sum(y ** 2), mesh=mesh,
        n_micro=M))

    def timeit(fn):
        jax.tree_util.tree_map(lambda a: a.block_until_ready(), fn(stacked))
        t0 = time.perf_counter()
        for _ in range(5):
            r = fn(stacked)
        jax.tree_util.tree_map(lambda a: a.block_until_ready(), r)
        return time.perf_counter() - t0

    t_gp, t_f1 = timeit(gp), timeit(f1)
    assert t_f1 < t_gp * 1.2, (t_f1, t_gp)
