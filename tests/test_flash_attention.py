"""Pallas flash-attention kernel vs the plain-XLA oracle (CPU runs the
kernel in interpret mode; on TPU the same code compiles via Mosaic)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import (Transformer, TransformerConfig,
                                            dense_attention)
from horovod_tpu.ops import flash_attention as fa


def _qkv(rng, b=2, s=256, h=4, d=64):
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((b, s, h, d)), jnp.float32)
    return mk(), mk(), mk()


def _oracle(q, k, v, causal=True):
    b, s, h, d = q.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    return dense_attention(q, k, v, causal=causal, q_positions=pos,
                           kv_positions=pos)


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_oracle(causal):
    q, k, v = _qkv(np.random.default_rng(0))
    out = fa.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_oracle(q, k, v, causal)),
                               atol=2e-5)


def test_block_fit_non_pow2_sequences():
    """Sequences that are multiples of 128 but not of the 512 default
    block (1280, 1152) must still run the kernel: the block fits DOWN to
    the largest divisor instead of rejecting the shape."""
    assert fa._fit_block(1280, 512) == 256
    assert fa._fit_block(1152, 512) == 128
    assert fa._fit_block(2048, 512) == 512
    assert fa._fit_block(48, 512) == 48
    assert fa._fit_block(12, 512) == 0  # not a multiple of 8
    assert fa.kernel_supported(1280, 1280, 64)


def test_mxu_block_floor_routes_degenerate_tilings_to_fallback():
    """ADVICE round 5: a long sequence whose only fitting block is tiny
    (1048 = 8 * 131 -> block 8) would run an MXU-starved 8-wide kernel;
    kernel_supported must reject it so `attention` takes the dense XLA
    fallback. Short sequences that fit in ONE block stay on the kernel."""
    assert fa._fit_block(1048, 512) == 8       # fits, but degenerate
    assert not fa.kernel_supported(1048, 1048, 64)
    assert not fa.kernel_supported(512, 1048, 64)   # either side gates
    # whole-sequence blocks below 128 are still fine (96 = one block)
    assert fa.kernel_supported(96, 96, 32)
    assert fa.kernel_supported(1280, 1280, 64)      # floor met (256)
    q, k, v = _qkv(np.random.default_rng(3), s=160)  # 160 = 32*5
    out = fa.flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_oracle(q, k, v)), atol=2e-5)


def test_decode_shapes_route_to_dense_path():
    """ISSUE 11 satellite: q_len == 1 (incremental decode — one new
    token against a long cached K/V, the serve/engine.py hot loop) can
    never tile onto an MXU-floor block; kernel_supported must route it
    to the dense path EXPLICITLY — for every cache length, including
    ones whose kv side alone would tile — and the `attention` dispatch
    wrapper must produce oracle values there, not a Mosaic rejection."""
    for skv in (1, 7, 96, 512, 2048, 4096):
        assert not fa.kernel_supported(1, skv, 64), skv
    assert not fa.kernel_supported(512, 1, 64)  # kv side gates too
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((2, 1, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 512, 4, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 512, 4, 64)), jnp.float32)
    # the decoding query sits at the END of the cached context
    out = fa.attention(q, k, v, causal=True, q_offset=511)
    q_pos = jnp.full((2, 1), 511)
    kv_pos = jnp.broadcast_to(jnp.arange(512), (2, 512))
    oracle = dense_attention(q, k, v, causal=True, q_positions=q_pos,
                             kv_positions=kv_pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               atol=2e-5)


def test_bf16_forward_and_grads_match_f32_oracle():
    """bf16 inputs run the MXU-native path (matmul operands stay bf16,
    accumulation/softmax fp32) — values must track the f32 oracle within
    bf16 tolerance. Pins the perf-critical no-upcast behavior: fp32
    operands would run the MXU at a fraction of peak."""
    rng = np.random.default_rng(7)
    q32, k32, v32 = _qkv(rng, s=128)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q32, k32, v32))
    out = fa.flash_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(_oracle(q32, k32, v32)),
        atol=5e-2)

    def f(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v).astype(jnp.float32) ** 2)

    def f32(q, k, v):
        return jnp.sum(_oracle(q, k, v) ** 2)

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g32 = jax.grad(f32, argnums=(0, 1, 2))(q32, k32, v32)
    for a, b in zip(g, g32):
        assert a.dtype == jnp.bfloat16
        scale = np.maximum(np.abs(np.asarray(b)), 1.0)
        np.testing.assert_allclose(
            np.asarray(a, np.float32) / scale, np.asarray(b) / scale,
            atol=8e-2)


def test_gradients_match_oracle():
    q, k, v = _qkv(np.random.default_rng(1), s=128)

    def f_flash(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_oracle(q, k, v) ** 2)

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4)


def test_offsets_mask_correctly():
    """Ring-style shifted K/V block: only keys with absolute position <=
    query position may attend."""
    rng = np.random.default_rng(2)
    q, k, v = _qkv(rng, s=128)
    # queries are the SECOND shard (positions 128..255), keys the first
    out = fa.flash_attention(q, k, v, causal=True, q_offset=128,
                             kv_offset=0)
    # every key position (0..127) <= every query position -> full attend,
    # equals non-causal
    ref = fa.flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    # reversed roles: no key is visible -> output must be exactly zero
    # (not a spurious mean of V)
    out2 = fa.flash_attention(q, k, v, causal=True, q_offset=0,
                              kv_offset=128)
    np.testing.assert_array_equal(np.asarray(out2), 0.0)


def test_traced_offsets_under_jit():
    """Offsets ride scalar prefetch, so traced values work — what a
    sequence-parallel shard passes for a rotated K/V block."""
    rng = np.random.default_rng(5)
    q, k, v = _qkv(rng, s=128)

    @jax.jit
    def f(q, k, v, qo):
        return fa.flash_attention(q, k, v, causal=True, q_offset=qo,
                                  kv_offset=0)

    out = f(q, k, v, jnp.int32(128))
    ref = fa.flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_attention_fallback_on_odd_shapes():
    rng = np.random.default_rng(3)
    # S=100: block would be 100, not sublane-aligned -> must fall back
    assert not fa.kernel_supported(100, 100, 32)
    q = jnp.asarray(rng.standard_normal((1, 100, 2, 32)), jnp.float32)
    k, v = q + 1, q - 1
    # the caller asked for flash: it is told, by name and shape
    with pytest.warns(fa.FlashFallbackWarning,
                      match=r"attention: .*q\(1, 100, 2, 32\)"):
        out = fa.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_oracle(q, k, v)), atol=2e-5)
    # d % 8 != 0 -> jnp path
    assert not fa.kernel_supported(128, 128, 30)
    q2 = jnp.asarray(rng.standard_normal((1, 90, 2, 30)), jnp.float32)
    with pytest.warns(fa.FlashFallbackWarning):
        out2 = fa.attention(q2, q2, q2, causal=True)
    np.testing.assert_allclose(np.asarray(out2),
                               np.asarray(_oracle(q2, q2, q2)), atol=2e-5)
    # aligned sub-128 sequences DO take the kernel
    assert fa.kernel_supported(96, 96, 32)


def test_transformer_flash_matches_dense():
    cfg_dense = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                                  d_model=32, d_ff=64, dtype=jnp.float32)
    cfg_flash = TransformerConfig(**{**cfg_dense.__dict__,
                                     "flash_attention": True})
    tokens = jnp.asarray(
        np.random.default_rng(4).integers(0, 64, size=(2, 128)), jnp.int32)
    m_dense, m_flash = Transformer(cfg_dense), Transformer(cfg_flash)
    params = m_dense.init(jax.random.PRNGKey(0), tokens, train=False)
    out_d = m_dense.apply(params, tokens, train=False)
    out_f = m_flash.apply(params, tokens, train=False)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               atol=5e-5)


def _shard_ring(fn, mesh, n):
    from jax.sharding import PartitionSpec as P
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P(None, "seq"), P(None, "seq"),
                                 P(None, "seq")),
        out_specs=P(None, "seq"), check_vma=False))


def test_ring_flash_matches_jnp_ring(n_devices):
    """Flash-ring (pallas per block + lse merge) equals the jnp ring and
    the full-sequence oracle, values and gradients."""
    if n_devices < 4:
        pytest.skip("needs 4+ devices")
    from horovod_tpu.parallel import ring
    n = 4
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n]), ("seq",))
    rng = np.random.default_rng(7)
    b, s, h, d = 2, 4 * 128, 2, 32
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)

    flash = _shard_ring(
        lambda q, k, v: ring.ring_attention(q, k, v, "seq", causal=True,
                                            use_flash=True), mesh, n)
    plain = _shard_ring(
        lambda q, k, v: ring.ring_attention(q, k, v, "seq", causal=True),
        mesh, n)
    out_f, out_p = flash(q, k, v), plain(q, k, v)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_p),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(out_f),
                               np.asarray(_oracle(q, k, v)), atol=2e-5)

    # all three gradients: dq accumulates locally, dk/dv rotate home
    # with their blocks — the fused ring backward must match the dense
    # jnp-ring VJP exactly
    g_f = jax.grad(lambda q, k, v: jnp.sum(flash(q, k, v) ** 2),
                   argnums=(0, 1, 2))(q, k, v)
    g_p = jax.grad(lambda q, k, v: jnp.sum(plain(q, k, v) ** 2),
                   argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_p):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4)


def test_ring_flash_backward_memory_bounded(n_devices):
    """The fused ring backward must not materialize S_local x S_local
    score blocks: compiled temp memory stays well under the dense
    jnp-ring VJP's (which pays O(S_local^2) per scan step)."""
    if n_devices < 4:
        pytest.skip("needs 4+ devices")
    from horovod_tpu.parallel import ring
    n = 4
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n]), ("seq",))
    b, s, h, d = 1, 4 * 512, 2, 64  # S_local = 512

    def shard(fn):
        return _shard_ring(fn, mesh, n)

    flash = shard(lambda q, k, v: ring.ring_attention(
        q, k, v, "seq", causal=True, use_flash=True))
    plain = shard(lambda q, k, v: ring.ring_attention(
        q, k, v, "seq", causal=True))
    q = jnp.zeros((b, s, h, d), jnp.float32)

    def temp_bytes(f):
        g = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(f(q, k, v) ** 2), argnums=(0, 1, 2)))
        ma = g.lower(q, q, q).compile().memory_analysis()
        return getattr(ma, "temp_size_in_bytes", None)

    t_flash, t_plain = temp_bytes(flash), temp_bytes(plain)
    if t_flash is None or t_plain is None:
        pytest.skip("backend exposes no memory analysis")
    # observed ~9x on the CPU backend; require at least 2x headroom so
    # the assert is about the asymptotic class, not compiler noise
    assert t_flash * 2 < t_plain, (t_flash, t_plain)


def test_transformer_ring_flash_trains(hvd, n_devices):
    if n_devices < 4:
        pytest.skip("needs 4+ devices")
    import optax

    from horovod_tpu import hvd_jax, training
    ndata, nseq = 2, 2
    devs = np.asarray(jax.devices()[:4]).reshape(ndata, nseq)
    mesh = jax.sharding.Mesh(devs, ("data", "seq"))
    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                            d_model=32, d_ff=64, dtype=jnp.float32,
                            sequence_axis="seq", flash_attention=True)
    init_cfg = TransformerConfig(**{**cfg.__dict__, "sequence_axis": None,
                                    "flash_attention": False})
    tx = hvd_jax.DistributedOptimizer(optax.adam(0.01),
                                      axes=("data", "seq"))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, size=(4, nseq * 128)),
        jnp.int32)
    st = training.create_train_state(Transformer(init_cfg), tx,
                                     jax.random.PRNGKey(0), tokens[:1])
    step = training.make_lm_train_step(Transformer(cfg), tx, mesh=mesh,
                                       batch_axis="data", seq_axis="seq",
                                       donate=False)
    losses = []
    for _ in range(5):
        st, loss = step(st, tokens)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def _to_bh(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _ref_loss(q, k, v, qo, ko, causal=True):
    """sum(out**2) of the plain-XLA oracle on [B,S,H,D] inputs."""
    off = jnp.asarray([qo, ko], jnp.int32)
    r = fa._reference_attention(_to_bh(q), _to_bh(k), _to_bh(v), off,
                                causal, 1.0 / (q.shape[-1] ** 0.5))
    return jnp.sum(r ** 2)


@pytest.mark.parametrize("qo,ko", [(0, 0), (512, 0), (256, 256)])
def test_gradients_multi_block_and_offsets(qo, ko):
    """s=512 with block 128 -> 4x4 backward grid: exercises scratch
    init/finalize, cross-block accumulation (dK/dV over q-blocks, the
    resident dQ over kv-blocks), and the causal block-skip; the offset
    variants exercise the shifted-mask gradient paths."""
    rng = np.random.default_rng(9)
    q, k, v = _qkv(rng, s=512, h=2, d=32)

    def f_flash(q, k, v):
        return jnp.sum(fa.flash_attention(
            q, k, v, q_offset=qo, kv_offset=ko, block_q=128,
            block_k=128) ** 2)

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: _ref_loss(q, k, v, qo, ko),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3)


@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("sq,skv,bq,bk,qo,ko", [
    (256, 512, 128, 128, 256, 0),    # 2 x 4 blocks, queries the later half
    (512, 256, 128, 64, 0, 128),     # 4 x 4, early queries see no key
    (384, 256, 128, 128, 64, 192),   # 3 x 2, the diagonal off the blocks
    (256, 256, 64, 128, 0, 0),       # rectangular blocks, 4 x 2
], ids=["sq256-skv512", "sq512-skv256", "sq384-skv256", "rect-blocks"])
def test_one_pass_backward_matches_reference(sq, skv, bq, bk, qo, ko,
                                             causal, traced):
    """The one backward kernel against ``_reference_attention``'s
    gradients at more than one block on each axis, sq != skv, causal
    and not, with static and traced non-zero offsets."""
    rng = np.random.default_rng(sq + skv + qo)
    b, h, d = 2, 2, 32
    q = jnp.asarray(rng.standard_normal((b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, skv, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, skv, h, d)), jnp.float32)

    def f_flash(q, k, v, qo, ko):
        return jnp.sum(fa.flash_attention(
            q, k, v, causal=causal, q_offset=qo, kv_offset=ko, block_q=bq,
            block_k=bk) ** 2)

    grad = jax.grad(f_flash, argnums=(0, 1, 2))
    if traced:
        gf = jax.jit(grad)(q, k, v, jnp.int32(qo), jnp.int32(ko))
    else:
        gf = grad(q, k, v, qo, ko)
    gr = jax.grad(lambda q, k, v: _ref_loss(q, k, v, qo, ko, causal),
                  argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(gf, gr):
        assert a.dtype == r.dtype and a.shape == r.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_backward_of_a_wholly_masked_kv_block_is_exactly_zero(dtype):
    """A K/V block wholly after the queries: the kernel skips every
    (kv, q) pair, and dq, dk, dv must still be exact zeros — the
    accumulators are set before and written after the skipped steps —
    in the dtype of their primals (bf16 goes through the fp32 dQ
    scratch, fp32 accumulates in the output block itself)."""
    rng = np.random.default_rng(13)
    q, k, v = (x.astype(dtype) for x in _qkv(rng, s=256, h=2, d=32))

    def f(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, q_offset=0,
                                 kv_offset=256, block_q=128, block_k=128)
        return jnp.sum(out.astype(jnp.float32) * 3.0)

    for grad, x in zip(jax.grad(f, argnums=(0, 1, 2))(q, k, v), (q, k, v)):
        assert grad.dtype == x.dtype
        np.testing.assert_array_equal(np.asarray(grad, np.float32), 0.0)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-3),
                                        (jnp.bfloat16, 8e-2)],
                         ids=["f32", "bf16"])
def test_bwd_block_partials_are_f32_and_sum_to_the_full_gradient(dtype,
                                                                 atol):
    """What ring attention composes: per K/V block, the lse-returning
    forward, lse merging, then ``flash_attention_bwd_block`` against the
    global (lse, delta). The partials are fp32 whatever the inputs are,
    dq sums over blocks and dk/dv concatenate to the gradient of
    attention over the whole sequence; the last block lies wholly after
    the first half of the queries."""
    rng = np.random.default_rng(17)
    s, nblk = 512, 2
    q32, k32, v32 = _qkv(rng, b=1, s=s, h=2, d=32)
    q, k, v = (x.astype(dtype) for x in (q32, k32, v32))
    g = jnp.asarray(rng.standard_normal(q.shape), dtype)
    kw = dict(causal=True, block_q=128, block_k=128)
    sb = s // nblk
    blocks = [(k[:, n * sb:(n + 1) * sb], v[:, n * sb:(n + 1) * sb], n * sb)
              for n in range(nblk)]
    outs, lses = zip(*[fa.flash_attention_with_lse(q, kb, vb, kv_offset=o,
                                                   **kw)
                       for kb, vb, o in blocks])
    lse = jax.nn.logsumexp(jnp.stack(lses), axis=0)  # [B,S,H]
    out = sum(o.astype(jnp.float32) * jnp.exp(l - lse)[..., None]
              for o, l in zip(outs, lses))
    delta = jnp.sum(g.astype(jnp.float32) * out, axis=-1)
    parts = [fa.flash_attention_bwd_block(q, kb, vb, g, lse, delta,
                                          kv_offset=o, **kw)
             for kb, vb, o in blocks]
    for part in parts:
        assert all(x.dtype == jnp.float32 for x in part)
    dq = sum(p[0] for p in parts)
    dk = jnp.concatenate([p[1] for p in parts], axis=1)
    dv = jnp.concatenate([p[2] for p in parts], axis=1)

    def f_ref(q, k, v):
        r = fa._reference_attention(
            _to_bh(q), _to_bh(k), _to_bh(v), jnp.zeros(2, jnp.int32), True,
            1.0 / (q.shape[-1] ** 0.5))
        return jnp.sum(r * _to_bh(g.astype(jnp.float32)))

    ref = jax.grad(f_ref, argnums=(0, 1, 2))(q32, k32, v32)
    for a, r in zip((dq, dk, dv), ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), atol=atol)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-3),
                                        (jnp.bfloat16, 6e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_two_head_sizes_match_reference(causal, dtype, atol):
    """Latent attention's heads: q and k 192 wide (128 + 64 rotary), v,
    o, dO and dV 128 wide. Forward and all three gradients against
    ``_reference_attention`` at 2 x 2 blocks; the default scale is the
    scores' (1/sqrt(192))."""
    rng = np.random.default_rng(192128)
    b, s, h, d_qk, d_v = 1, 256, 2, 192, 128
    q = jnp.asarray(rng.standard_normal((b, s, h, d_qk)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, h, d_qk)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, h, d_v)), dtype)

    def f_flash(q, k, v):
        out = fa.flash_attention(q, k, v, causal=causal, block_q=128,
                                 block_k=128)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    (_, out), gf = jax.value_and_grad(f_flash, argnums=(0, 1, 2),
                                      has_aux=True)(q, k, v)
    assert out.shape == (b, s, h, d_v) and out.dtype == dtype
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    off = jnp.zeros(2, jnp.int32)

    def f_ref(q, k, v):
        r = fa._reference_attention(_to_bh(q), _to_bh(k), _to_bh(v), off,
                                    causal, 1.0 / d_qk ** 0.5)
        return jnp.sum(r ** 2), r

    (_, ref), gr = jax.value_and_grad(f_ref, argnums=(0, 1, 2),
                                      has_aux=True)(f32(q), f32(k), f32(v))
    np.testing.assert_allclose(
        np.asarray(f32(_to_bh(out))), np.asarray(ref), atol=atol)
    for a, r, x in zip(gf, gr, (q, k, v)):
        assert a.shape == x.shape and a.dtype == dtype
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(np.asarray(f32(a)), np.asarray(r),
                                   atol=atol * max(1.0, scale))


def test_two_head_sizes_fallback_and_gate():
    """The fallback rules cover the new shape: the gate takes ``d_v``,
    ``attention`` runs the kernel at 192 / 128 where the blocks fit and
    the plain path (same numbers, with a warning) where they do not,
    and q and k of different widths are refused."""
    assert fa.kernel_supported(4096, 4096, 192, d_v=128)
    assert not fa.kernel_supported(4096, 4096, 192, d_v=100)
    rng = np.random.default_rng(5)
    mk = lambda s, d: jnp.asarray(  # noqa: E731
        rng.standard_normal((1, s, 2, d)), jnp.float32)
    q, k, v = mk(1048, 192), mk(1048, 192), mk(1048, 128)  # block 8
    with pytest.warns(fa.FlashFallbackWarning):
        plain = fa.attention(q, k, v)
    assert plain.shape == (1, 1048, 2, 128)
    q, k, v = q[:, :128], k[:, :128], v[:, :128]
    np.testing.assert_allclose(np.asarray(fa.attention(q, k, v)),
                               np.asarray(plain[:, :128]), atol=2e-5)
    with pytest.raises(ValueError, match="share a head size"):
        fa.flash_attention(q, k[..., :128], v)


def _brute_force_schedule(sq, skv, bq, bk, qo, ko, causal):
    """Block kinds counted from the element-wise mask itself."""
    visible = np.ones((sq, skv), bool)
    if causal:
        visible = (qo + np.arange(sq))[:, None] >= (ko + np.arange(skv))[None]
    blocks = visible.reshape(sq // bq, bq, skv // bk, bk)
    some, every = blocks.any(axis=(1, 3)), blocks.all(axis=(1, 3))
    return {"interior": int(every.sum()),
            "diagonal": int((some & ~every).sum()),
            "skipped": int((~some).sum())}


@pytest.mark.parametrize("sq,skv,bq,bk,qo,ko,causal,want", [
    (2048, 2048, 512, 512, 0, 0, True, (6, 4, 6)),      # the lm-* cells
    (4096, 4096, 512, 512, 0, 0, True, (28, 8, 28)),    # kanana-...
    (2048, 2048, 512, 512, 0, 0, False, (16, 0, 0)),
    (512, 512, 128, 128, 0, 0, True, (6, 4, 6)),
    (512, 512, 128, 128, 256, 0, True, (13, 2, 1)),
    (512, 512, 128, 128, 0, 256, True, (1, 2, 13)),
    (512, 512, 128, 128, 0, 512, True, (0, 0, 16)),
    (512, 512, 128, 128, 512, 0, True, (16, 0, 0)),     # a ring's earlier shard
    (384, 256, 128, 128, 64, 192, True, None),           # diagonal off the blocks
    (256, 512, 64, 128, 100, 37, True, None),            # rectangular, odd offsets
    (1280, 1280, 512, 512, 0, 0, True, None),            # blocks fit down to 256
], ids=lambda x: None if isinstance(x, tuple) else str(x))
def test_block_schedule_counts_match_the_mask(sq, skv, bq, bk, qo, ko, causal,
                                              want):
    """``block_schedule`` — the classification the kernels branch on —
    against a brute-force count over the element-wise mask, and the two
    index-map clamps against the same classification: for every q block
    the k/v map never names a block past the last one it runs, for every
    kv block the q map never one before the first."""
    got = fa.block_schedule(sq, skv, bq, bk, qo, ko, causal)
    fbq, fbk = fa._fit_block(sq, bq), fa._fit_block(skv, bk)
    assert got == _brute_force_schedule(sq, skv, fbq, fbk, qo, ko, causal)
    assert sum(got.values()) == (sq // fbq) * (skv // fbk)
    if want is not None:
        assert (got["interior"], got["diagonal"], got["skipped"]) == want
    if not causal:
        return
    nq, nkv = sq // fbq, skv // fbk
    off = np.asarray([qo, ko], np.int32)
    skipped = np.array([[bool(fa._block_kind(qo + i * fbq, ko + j * fbk, fbq,
                                             fbk, True)[0])
                         for j in range(nkv)] for i in range(nq)])
    for i in range(nq):
        last = int(fa._last_visible_kv(i, off, fbq, fbk, nkv))
        run = np.flatnonzero(~skipped[i])
        assert last == (run[-1] if run.size else 0)
    for j in range(nkv):
        first = int(fa._first_visible_q(j, off, fbq, fbk, nq))
        run = np.flatnonzero(~skipped[:, j])
        assert first == (run[0] if run.size else nq - 1)


@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("d_qk,d_v", [(64, 64), (192, 128)],
                         ids=["d64", "d192-128"])
@pytest.mark.parametrize("qo,ko", [(0, 0), (256, 0), (0, 256), (0, 512)],
                         ids=["diag", "q-later", "kv-later", "kv-after"])
def test_forward_and_lse_on_all_three_block_kinds(qo, ko, d_qk, d_v, causal,
                                                  dtype, atol, traced):
    """sq = skv = 512 in 128 x 128 blocks: interior, diagonal and skipped
    pairs in one grid ((0, 0): 6 / 4 / 6), queries wholly after the keys
    (256, 0), query rows with no visible key beside rows with some
    (0, 256) and every pair skipped (0, 512). Output against
    ``_reference_attention`` and ``lse`` against a plain ``logsumexp``;
    a row that sees nothing gives zeros and ``NEG_INF``."""
    rng = np.random.default_rng(qo + 2 * ko + d_qk)
    b, s, h = 1, 512, 2
    mk = lambda d: jnp.asarray(  # noqa: E731
        rng.standard_normal((b, s, h, d)), dtype)
    q, k, v = mk(d_qk), mk(d_qk), mk(d_v)

    def f(q, k, v, qo, ko):
        return fa.flash_attention_with_lse(
            q, k, v, causal=causal, q_offset=qo, kv_offset=ko, block_q=128,
            block_k=128)

    if traced:
        out, lse = jax.jit(f)(q, k, v, jnp.int32(qo), jnp.int32(ko))
    else:
        out, lse = f(q, k, v, qo, ko)
    assert out.shape == (b, s, h, d_v) and out.dtype == dtype
    assert lse.shape == (b, s, h) and lse.dtype == jnp.float32

    sm_scale = 1.0 / d_qk ** 0.5
    f32 = lambda x: _to_bh(x).astype(jnp.float32)  # noqa: E731
    ref = fa._reference_attention(f32(q), f32(k), f32(v),
                                  jnp.asarray([qo, ko], jnp.int32), causal,
                                  sm_scale)
    scores = jnp.einsum("bqd,bkd->bqk", f32(q), f32(k)) * sm_scale
    visible = jnp.ones((s, s), bool)
    if causal:
        visible = (qo + jnp.arange(s))[:, None] >= (ko + jnp.arange(s))[None]
    ref_lse = jax.nn.logsumexp(jnp.where(visible, scores, -jnp.inf), axis=-1)
    seen = np.asarray(visible.any(axis=-1))
    got = np.asarray(f32(out))
    got_lse = np.asarray(lse.transpose(0, 2, 1).reshape(b * h, s))
    np.testing.assert_allclose(got, np.asarray(ref), atol=atol)
    np.testing.assert_allclose(got_lse[:, seen], np.asarray(ref_lse)[:, seen],
                               atol=atol)
    np.testing.assert_array_equal(got[:, ~seen], 0.0)
    np.testing.assert_array_equal(got_lse[:, ~seen], np.float32(fa.NEG_INF))


# ---------------------------------------------------------------- window

def _hand_mask(sq, skv, qo, ko, window):
    """[sq, skv]: query i sees key j when j <= i and i - j < window,
    from positions written out."""
    behind = (qo + np.arange(sq))[:, None] - (ko + np.arange(skv))[None, :]
    return (behind >= 0) & (behind < window)


def _hand_attention(q, k, v, mask):
    """Attention under an element-wise mask, [B, S, H, D] in float32; a
    row that sees nothing gives zeros."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
    probs = jax.nn.softmax(jnp.where(mask, scores, fa.NEG_INF), -1)
    probs = jnp.where(mask.any(-1)[:, None], probs, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# a window smaller than a block, equal to one, not a multiple of one,
# spanning several, with blocks that differ, and at least the sequence
WINDOWS = [(256, 24, 64, 64), (256, 64, 64, 64), (256, 100, 64, 64),
           (256, 160, 32, 32), (256, 48, 64, 32), (256, 48, 32, 64),
           (256, 256, 64, 64), (256, 1000, 64, 64)]


@pytest.mark.parametrize("s,window,bq,bk", WINDOWS, ids=lambda x: str(x))
def test_windowed_kernel_matches_the_hand_built_mask(s, window, bq, bk):
    """Output, ``dq``, ``dk`` and ``dv`` of the windowed kernels
    (interpret mode) against attention under a mask built by hand from
    positions, and against ``_reference_attention`` given the same
    window."""
    rng = np.random.default_rng(window + bq)
    q, k, v = _qkv(rng, b=1, s=s, h=2, d=16)
    weight = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    mask = jnp.asarray(_hand_mask(s, s, 0, 0, window))

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, window=window, block_q=bq,
                                  block_k=bk)

    got = kernel(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_hand_attention(q, k, v, mask)),
        atol=2e-5)
    ref = fa._reference_attention(
        _to_bh(q), _to_bh(k), _to_bh(v), jnp.zeros(2, jnp.int32), True,
        0.25, window)
    np.testing.assert_allclose(np.asarray(_to_bh(got)), np.asarray(ref),
                               atol=2e-5)
    grads = jax.grad(lambda *a: jnp.sum(kernel(*a) * weight),
                     argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(
        _hand_attention(*a, mask) * weight), argnums=(0, 1, 2))(q, k, v)
    for name, g, w in zip(("dq", "dk", "dv"), grads, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   err_msg=name)


@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("qo,ko", [(128, 0), (64, 32), (0, 64), (512, 0)],
                         ids=["q-later", "both", "kv-later", "behind"])
def test_windowed_kernel_with_offsets(qo, ko, traced):
    """``q_offset`` / ``kv_offset`` with a window, as Python ints (the
    inner grid dimension read off the classification) and traced (its
    bound at any alignment): queries later than the keys, both shifted
    off the blocks, rows that see nothing, and every key behind the
    window (zeros out, zero gradients)."""
    s, window = 128, 40
    rng = np.random.default_rng(qo + ko)
    q, k, v = _qkv(rng, b=1, s=s, h=2, d=16)
    weight = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    mask = jnp.asarray(_hand_mask(s, s, qo, ko, window))

    def loss(q, k, v, qo, ko):
        out = fa.flash_attention(q, k, v, window=window, q_offset=qo,
                                 kv_offset=ko, block_q=32, block_k=32)
        return jnp.sum(out * weight), out

    f = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
    if traced:
        (_, out), grads = jax.jit(f)(q, k, v, jnp.int32(qo), jnp.int32(ko))
    else:
        (_, out), grads = f(q, k, v, qo, ko)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_hand_attention(q, k, v, mask)),
        atol=2e-5)
    want = jax.grad(lambda *a: jnp.sum(
        _hand_attention(*a, mask) * weight), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(grads, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5)
    if (qo, ko) == (512, 0):
        assert not np.asarray(out).any() and not mask.any()


def test_a_window_of_the_whole_sequence_is_the_causal_kernel():
    """A window at least the sequence masks nothing more than the
    diagonal: the same output and gradients as ``window=None`` at the
    same blocks, and ``block_schedule`` equal to today's."""
    q, k, v = _qkv(np.random.default_rng(5), b=1, s=256, h=2, d=16)
    f = lambda window: jax.value_and_grad(  # noqa: E731
        lambda *a: jnp.sum(fa.flash_attention(
            *a, window=window, block_q=64, block_k=64) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    (plain, plain_grads), (wide, wide_grads) = f(None), f(256)
    np.testing.assert_allclose(float(wide), float(plain), rtol=1e-6)
    for g, w in zip(wide_grads, plain_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)
    for window in (256, 300, 10 ** 6):
        assert fa.block_schedule(256, 256, 64, 64, window=window) == (
            fa.block_schedule(256, 256, 64, 64))


def test_window_none_is_the_kernel_it_was(monkeypatch):
    """``window=None`` traces the program the causal kernel always
    traced: the same jaxpr as with every windowed branch made
    unreachable (the helpers raising), the full-length grid, and the
    output and gradients bit for bit those of the default call."""
    q, k, v = _qkv(np.random.default_rng(6), b=1, s=256, h=2, d=16)

    def program(**kw):
        return jax.make_jaxpr(jax.value_and_grad(
            lambda *a: jnp.sum(fa.flash_attention(
                *a, block_q=64, block_k=64, **kw)), argnums=(0, 1, 2)))(
                    q, k, v)

    with_argument = str(program(window=None))
    assert with_argument == str(program())

    def unreachable(*a, **kw):
        raise AssertionError("a windowed branch ran without a window")

    for name in ("_first_visible_kv", "_last_visible_q", "_window_steps"):
        monkeypatch.setattr(fa, name, unreachable)
    assert str(program(window=None)) == with_argument
    assert re.findall(r"grid=\(([\d, ]+)\)", with_argument) == [
        "2, 4, 4", "2, 4, 4"]
    f = lambda **kw: jax.value_and_grad(  # noqa: E731
        lambda *a: jnp.sum(fa.flash_attention(*a, **kw) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(f()),
                    jax.tree_util.tree_leaves(f(window=None))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _brute_force_window_schedule(sq, skv, bq, bk, qo, ko, window):
    blocks = _hand_mask(sq, skv, qo, ko, window).reshape(
        sq // bq, bq, skv // bk, bk)
    some, every = blocks.any(axis=(1, 3)), blocks.all(axis=(1, 3))
    return {"interior": int(every.sum()),
            "diagonal": int((some & ~every).sum()),
            "skipped": int((~some).sum())}, some


@pytest.mark.parametrize("sq,skv,bq,bk,qo,ko,window,want,steps", [
    # the sliding layers of laguna-xs.2-train-s8192 at the blocks measured
    (8192, 8192, 512, 512, 0, 0, 512, (0, 31, 225), (2, 2)),
    (8192, 8192, 1024, 1024, 0, 0, 512, (0, 15, 49), (2, 2)),
    (8192, 8192, 512, 256, 0, 0, 512, (0, 62, 450), (4, 2)),
    (8192, 8192, 256, 512, 0, 0, 512, (0, 62, 450), (2, 4)),
    (8192, 8192, 256, 256, 0, 0, 512, (31, 62, 931), (3, 3)),
    (8192, 8192, 128, 128, 0, 0, 512, (186, 124, 3786), (5, 5)),
    (512, 512, 128, 128, 0, 0, 128, None, None),
    (512, 512, 128, 128, 0, 0, 129, None, None),
    (512, 512, 128, 128, 0, 0, 200, None, None),
    (512, 512, 128, 128, 256, 0, 100, None, None),
    (384, 256, 128, 128, 64, 192, 150, None, None),
    (256, 512, 64, 128, 100, 37, 90, None, None),
], ids=lambda x: None if isinstance(x, tuple) else str(x))
def test_block_schedule_with_a_window(sq, skv, bq, bk, qo, ko, window, want,
                                      steps):
    """``block_schedule`` with a window against a brute-force count over
    the hand-built mask; the pairs a block runs are adjacent, the four
    index-map helpers name their ends, and ``_window_steps`` is the most
    any block runs (with traced offsets: a bound on it)."""
    got = fa.block_schedule(sq, skv, bq, bk, qo, ko, window=window)
    brute, some = _brute_force_window_schedule(sq, skv, bq, bk, qo, ko,
                                               window)
    assert got == brute
    if want is not None:
        assert (got["interior"], got["diagonal"], got["skipped"]) == want
    nq, nkv = sq // bq, skv // bk
    off = np.asarray([qo, ko], np.int32)
    for i in range(nq):
        run = np.flatnonzero(some[i])
        if run.size:
            assert (run == np.arange(run[0], run[-1] + 1)).all()
            assert int(fa._first_visible_kv(i, off, bq, bk, nkv,
                                            window)) == run[0]
            assert int(fa._last_visible_kv(i, off, bq, bk, nkv)) == run[-1]
    for j in range(nkv):
        run = np.flatnonzero(some[:, j])
        if run.size:
            assert int(fa._first_visible_q(j, off, bq, bk, nq)) == run[0]
            assert int(fa._last_visible_q(j, off, bq, bk, nq,
                                          window)) == run[-1]
    exact = fa._window_steps(sq, skv, bq, bk, window, qo, ko)
    assert exact == (max(some.sum(1).max(), 1), max(some.sum(0).max(), 1))
    if steps is not None:
        assert exact == steps
    bound = fa._window_steps(sq, skv, bq, bk, window, jnp.int32(qo),
                             jnp.int32(ko))
    assert exact[0] <= bound[0] <= nkv and exact[1] <= bound[1] <= nq


def test_windowed_grid_visits_only_what_the_window_reaches():
    """Pairs behind the window are skipped, not masked: the windowed
    kernels' inner grid dimension is ``_window_steps`` long, whatever the
    sequence, where the causal kernels' is the whole row of blocks."""
    q, k, v = _qkv(np.random.default_rng(7), b=1, s=512, h=1, d=16)
    grids = lambda **kw: re.findall(r"grid=\(([\d, ]+)\)", str(  # noqa: E731
        jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(fa.flash_attention(
            *a, block_q=64, block_k=64, **kw)), argnums=(0, 1, 2)))(
                q, k, v)))
    assert grids() == ["1, 8, 8", "1, 8, 8"]
    assert grids(window=64) == ["1, 8, 2", "1, 8, 2"]
    assert grids(window=100) == ["1, 8, 3", "1, 8, 3"]


def test_window_fallback_and_refusals():
    """``attention`` hands the window to the plain-XLA path when the
    shapes do not tile; a window without a causal diagonal, or of no
    positions, is refused; ``dense_attention`` takes the same window."""
    q, k, v = _qkv(np.random.default_rng(8), b=1, s=1048, h=1, d=16)
    mask = jnp.asarray(_hand_mask(1048, 1048, 0, 0, 100))
    with pytest.warns(fa.FlashFallbackWarning):
        got = fa.attention(q, k, v, window=100)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_hand_attention(q, k, v, mask)),
        atol=2e-5)
    pos = jnp.arange(1048)[None]
    np.testing.assert_allclose(
        np.asarray(dense_attention(q, k, v, causal=True, q_positions=pos,
                                   kv_positions=pos, window=100)),
        np.asarray(got), atol=2e-5)
    with pytest.raises(ValueError, match="behind a causal diagonal"):
        fa.flash_attention(q[:, :256], k[:, :256], v[:, :256], causal=False,
                           window=64)
    with pytest.raises(ValueError, match="positive number"):
        fa.flash_attention(q[:, :256], k[:, :256], v[:, :256], window=0)
    with pytest.raises(ValueError, match="causal"):
        dense_attention(q, k, v, causal=False, q_positions=pos,
                        kv_positions=pos, window=100)


# ------------------------------------------------- shared key/value heads

def _grouped_qkv(seed, b, s, h_kv, group, d=16, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    mk = lambda h: jnp.asarray(  # noqa: E731
        rng.standard_normal((b, s, h, d)), dtype)
    return mk(h_kv * group), mk(h_kv), mk(h_kv)


@pytest.mark.parametrize("window", [None, 300], ids=["causal", "window"])
@pytest.mark.parametrize("group,h_kv", [(2, 2), (6, 2), (8, 2), (16, 1)],
                         ids=lambda x: str(x))
def test_shared_heads_are_the_broadcast_read_in_place(group, h_kv, window):
    """The grouped kernels (k and v at ``H / g`` heads, read in place)
    against the one-head-each kernels given the heads repeated, at the
    default blocks of each (``WINDOW_BLOCKS`` with a window: four blocks
    of 512 at s2048), bfloat16 operands, two sequences: the output and
    the log-sum-exp rows bit for bit (each query head runs the same
    tiles), dQ bit for bit, and dK, dV within the bfloat16 rounding of
    the ``g``-term sum: the grouped backward sums the group's heads in
    float32 and rounds once, where the broadcast's transpose sums the
    heads' rounded gradients."""
    b, s = 2, 2048
    q, k, v = _grouped_qkv(group + h_kv, b, s, h_kv, group)
    k_rep, v_rep = fa.per_query_head(q, k, v)
    assert k_rep.shape == q.shape
    offsets = jnp.zeros(2, jnp.int32)
    windows = (None, None)
    if window is not None:
        windows = tuple((window, fa._window_steps(s, s, *blocks, window, 0,
                                                  0)[n])
                        for n, blocks in enumerate(fa.WINDOW_BLOCKS))
    blocks = (fa.FORWARD_BLOCKS if window is None else fa.WINDOW_BLOCKS[0])
    forward = lambda k, v: fa._flash_fwd_impl(  # noqa: E731
        fa._to_bh(q), fa._to_bh(k), fa._to_bh(v), offsets, True,
        16 ** -0.5, *blocks, True, with_lse=True, window=windows[0])
    for got, want in zip(forward(k, v), forward(k_rep, v_rep)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    weight = jnp.asarray(np.random.default_rng(1).standard_normal(q.shape),
                         jnp.float32)

    def grads(k, v):
        return jax.grad(lambda *a: jnp.sum(fa.flash_attention(
            *a, window=window).astype(jnp.float32) * weight),
            argnums=(0, 1, 2))(q, k, v)

    dq, dk, dv = grads(k, v)
    dq_rep, dk_heads, dv_heads = grads(k_rep, v_rep)
    np.testing.assert_array_equal(np.asarray(dq), np.asarray(dq_rep))
    for got, heads in ((dk, dk_heads), (dv, dv_heads)):
        assert got.shape == k.shape and got.dtype == jnp.bfloat16
        terms = np.asarray(heads, np.float32).reshape(
            b, s, h_kv, group, 16)
        want = terms.sum(3)
        # half a bfloat16 ulp (2^-9 of the magnitude) for each rounded
        # term and for the one rounding of the sum, and again as room
        bound = 2.0 ** -8 * (np.abs(terms).sum(3) + np.abs(want)) + 1e-6
        assert (np.abs(np.asarray(got, np.float32) - want) <= bound).all()


def test_shared_heads_refused_where_they_do_not_divide():
    q, k, v = _grouped_qkv(0, 1, 256, 3, 1)
    with pytest.raises(ValueError, match="divides q's"):
        fa.flash_attention(q[:, :, :2], k, v)
    with pytest.raises(ValueError, match="share one number"):
        fa.flash_attention(q, k, v[:, :, :1])


def test_one_head_each_traces_no_grouped_code(monkeypatch):
    """At ``g == 1`` (every MHA and latent-attention caller, and the
    benchmark probe's broadcast call) the kernels trace the program they
    traced before shared heads: the same jaxpr, forward and backward,
    causal and windowed, and the ring's two primitives, with every
    grouped helper made to raise; nothing is counted in the set-up
    record."""
    from horovod_tpu.telemetry import scopes, startup
    q, k, v = _qkv(np.random.default_rng(9), b=1, s=256, h=2, d=16)
    lse = jnp.zeros((1, 256, 2))

    def programs():
        train = [str(jax.make_jaxpr(jax.value_and_grad(
            lambda *a: jnp.sum(fa.flash_attention(
                *a, block_q=64, block_k=64, **kw)), argnums=(0, 1, 2)))(
                    q, k, v)) for kw in ({}, {"window": 100})]
        return train + [
            str(jax.make_jaxpr(lambda *a: fa.flash_attention_with_lse(
                *a, block_q=64, block_k=64))(q, k, v)),
            str(jax.make_jaxpr(lambda *a: fa.flash_attention_bwd_block(
                *a, block_q=64, block_k=64))(q, k, v, q, lse, lse))]

    before = programs()

    def unreachable(*a, **kw):
        raise AssertionError("a grouped branch ran with one head each")

    for name in ("_kv_head", "_block_rows", "_grouped_bwd"):
        monkeypatch.setattr(fa, name, unreachable)
    record = startup.Record()
    monkeypatch.setattr(startup, "RECORD", record)
    monkeypatch.setattr(record, "grouped_attention", unreachable)
    with startup.span(scopes.LOWER) as attrs:
        assert programs() == before
    assert attrs == {}
    assert re.findall(r"grid=\(([\d, ]+)\)", before[0]) == [
        "2, 4, 4", "2, 4, 4"]


def test_grouped_calls_are_counted_on_the_open_build_span(monkeypatch):
    """Each call traced with shared heads counts, with its group size,
    on the program span open while it is traced (``hvd_lower`` in a
    step's build), and nowhere once the record closed."""
    from horovod_tpu.telemetry import scopes, startup
    record = startup.Record()
    monkeypatch.setattr(startup, "RECORD", record)
    q6, k, v = _grouped_qkv(0, 1, 256, 1, 6, dtype=jnp.float32)
    q8 = jnp.concatenate([q6, q6[:, :, :2]], axis=2)
    run = lambda q, **kw: jax.make_jaxpr(  # noqa: E731
        lambda q: fa.flash_attention(q, k, v, **kw))(q)
    with startup.span(scopes.LOWER) as attrs:
        run(q6)
        run(q8, window=64)
        run(q8)
        run(q6[:, :, :1])  # one head each: not counted
    assert attrs == {"grouped_attention_calls": 3, "attention_groups": [6, 8]}
    assert [span["attrs"] for span in record.spans
            if span["name"] == scopes.LOWER] == [attrs]
    record.closed = True
    run(q6)
    assert attrs["grouped_attention_calls"] == 3
