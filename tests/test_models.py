"""Model zoo + training-step builder tests.

Pattern per SURVEY.md §4: end-to-end through the public API on the virtual
8-device mesh; numerical references computed locally (the Adasum-test
pattern, test_adasum_tensorflow.py:33-63, applied to ring attention)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd_api
from horovod_tpu import training
from horovod_tpu.models import (MLP, MNISTConvNet, ResNet18, ResNet50,
                                Transformer, TransformerConfig, VGG16)
from horovod_tpu.models.transformer import dense_attention
from horovod_tpu.parallel import ring


def _param_count(params):
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))


def test_resnet18_forward_shape():
    model = ResNet18(num_classes=10, dtype=jnp.float32)
    x = jnp.ones((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    y = model.apply(variables, x, train=False)
    assert y.shape == (2, 10)
    assert y.dtype == jnp.float32


def test_resnet50_param_count():
    """ResNet-50 has ~25.5M params — the standard architecture checksum."""
    model = ResNet50(num_classes=1000, dtype=jnp.float32)
    x = jnp.ones((1, 64, 64, 3))
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x, train=False))
    n = _param_count(variables["params"])
    assert 25.4e6 < n < 25.7e6, n


def test_vgg16_forward_shape():
    model = VGG16(num_classes=10, dtype=jnp.float32)
    x = jnp.ones((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    y = model.apply(variables, x, train=False)
    assert y.shape == (2, 10)


def test_mnist_convnet_forward():
    model = MNISTConvNet(dtype=jnp.float32)
    x = jnp.ones((4, 28, 28, 1))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    y = model.apply(variables, x, train=False)
    assert y.shape == (4, 10)


def test_transformer_forward_shape():
    cfg = TransformerConfig(vocab_size=100, num_layers=2, num_heads=4,
                            d_model=64, d_ff=128, dtype=jnp.float32)
    model = Transformer(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens, train=False)
    y = model.apply(variables, tokens)
    assert y.shape == (2, 16, 100)


def test_train_step_mlp_converges(hvd):
    """End-to-end: replicated params, sharded batch, fused grad allreduce."""
    rng = np.random.default_rng(0)
    X = rng.standard_normal((64, 8)).astype(np.float32)
    w_true = rng.standard_normal((8,)).astype(np.float32)
    y = (X @ w_true > 0).astype(np.int32)

    model = MLP(features=(16, 2))
    tx = hvd_api.DistributedOptimizer(optax.adam(0.05))
    state = training.create_train_state(model, tx, jax.random.PRNGKey(0),
                                        jnp.zeros((1, 8)))
    step = training.make_train_step(model, tx, donate=False)
    losses = []
    for _ in range(60):
        state, loss = step(state, jnp.asarray(X), jnp.asarray(y))
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5, losses[::10]


def test_train_step_batchnorm_model(hvd):
    """BN models thread batch_stats through the SPMD step."""
    model = ResNet18(num_classes=4, num_filters=8, dtype=jnp.float32)
    tx = hvd_api.DistributedOptimizer(optax.sgd(0.01))
    x = jnp.ones((8, 16, 16, 3))
    labels = jnp.zeros((8,), jnp.int32)
    state = training.create_train_state(model, tx, jax.random.PRNGKey(0),
                                        x[:1])
    assert state.batch_stats
    step = training.make_train_step(model, tx, donate=False)
    state2, loss = step(state, x, labels)
    assert int(state2.step) == 1
    assert np.isfinite(float(loss))
    # stats actually updated
    before = jax.tree_util.tree_leaves(state.batch_stats)
    after = jax.tree_util.tree_leaves(state2.batch_stats)
    assert any(not np.allclose(a, b) for a, b in zip(before, after))


def _ring_vs_dense(attn_fn, n_devices, heads=8):
    """Reference check: sharded attention == dense attention on full seq."""
    b, s, h, d = 2, 8 * n_devices, heads, 16
    rng = np.random.default_rng(1)
    q = rng.standard_normal((b, s, h, d)).astype(np.float32)
    k = rng.standard_normal((b, s, h, d)).astype(np.float32)
    v = rng.standard_normal((b, s, h, d)).astype(np.float32)
    positions = np.broadcast_to(np.arange(s)[None, :], (b, s)).copy()

    mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("seq",))

    def f(q, k, v, pos):
        return attn_fn(q, k, v, axis_name="seq", causal=True,
                       q_positions=pos, kv_positions=pos)

    out = jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq"),
                  P(None, "seq")),
        out_specs=P(None, "seq"), check_vma=False))(q, k, v, positions)

    ref = dense_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=True, q_positions=jnp.asarray(positions),
                          kv_positions=jnp.asarray(positions))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_matches_dense(hvd, n_devices):
    _ring_vs_dense(ring.ring_attention, n_devices)


def test_ulysses_attention_matches_dense(hvd, n_devices):
    _ring_vs_dense(ring.ulysses_attention, n_devices)


def test_ulysses_rejects_bad_heads(hvd, n_devices):
    if n_devices < 2:
        pytest.skip("needs multiple devices")
    with pytest.raises(Exception):
        _ring_vs_dense(ring.ulysses_attention, n_devices,
                       heads=n_devices + 1)


def test_lm_loss_exact_under_seq_parallel(hvd, n_devices):
    """Seq-parallel next-token loss/grads equal the single-device values.

    Uses a positionwise LM (logits depend only on the local token) so the
    only cross-shard coupling is the loss stitching itself: shard i's final
    target must be shard i+1's first token, and normalization must be by
    the global target count."""
    import flax.linen as nn

    ndata = 2
    nseq = n_devices // ndata
    if nseq < 2:
        pytest.skip("needs >=4 devices")

    class PositionwiseLM(nn.Module):
        vocab: int

        @nn.compact
        def __call__(self, tokens, train=False):
            emb = self.param("emb", nn.initializers.normal(1.0),
                             (self.vocab, self.vocab))
            return emb[tokens]

    model = PositionwiseLM(vocab=16)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 16, size=(ndata * 2, nseq * 4)),
        jnp.int32)

    def run(mesh, axes, batch_axis, seq_axis):
        tx = hvd_api.DistributedOptimizer(optax.sgd(0.1), axes=axes)
        state = training.create_train_state(
            model, tx, jax.random.PRNGKey(7), tokens[:1])
        step = training.make_lm_train_step(
            model, tx, mesh=mesh, batch_axis=batch_axis, seq_axis=seq_axis,
            donate=False)
        state, loss = step(state, tokens)
        return float(loss), state.params

    mesh1 = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    loss_ref, params_ref = run(mesh1, ("data",), "data", None)

    devs = np.asarray(jax.devices()).reshape(ndata, nseq)
    mesh2 = jax.sharding.Mesh(devs, ("data", "seq"))
    loss_par, params_par = run(mesh2, ("data", "seq"), "data", "seq")

    np.testing.assert_allclose(loss_par, loss_ref, rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=1e-5, atol=1e-6),
        params_par, params_ref)


def test_lm_train_step_sequence_parallel(hvd, n_devices):
    """Transformer with ring attention over a (data, seq) mesh trains."""
    ndata = 2
    nseq = n_devices // ndata
    if nseq < 2:
        pytest.skip("needs >=4 devices")
    devs = np.asarray(jax.devices()).reshape(ndata, nseq)
    mesh = jax.sharding.Mesh(devs, ("data", "seq"))

    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                            d_model=32, d_ff=64, dtype=jnp.float32,
                            sequence_axis="seq")
    model = Transformer(cfg)
    # init outside shard_map: use a dense-attention clone (same params)
    init_model = Transformer(
        TransformerConfig(**{**cfg.__dict__, "sequence_axis": None}))
    tx = hvd_api.DistributedOptimizer(optax.adam(0.01),
                                      axes=("data", "seq"))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, size=(ndata * 2, nseq * 8)),
        jnp.int32)
    state = training.create_train_state(init_model, tx, jax.random.PRNGKey(0),
                                        tokens[:1])
    step = training.make_lm_train_step(model, tx, mesh=mesh,
                                       batch_axis="data", seq_axis="seq",
                                       donate=False)
    losses = []
    for _ in range(20):
        state, loss = step(state, tokens)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses
