"""The hybrid delta-attention / latent-attention / sparse-expert decoder
(a Kimi Delta Attention mixer with a chunked scan, latent attention
without rotary, a dense SwiGLU layer, SwiGLU experts that hold a share,
one shared expert) against its plain reference, at a small size on the CPU
with seeded random weights.

``tests/reference_kda_moe_lm.py`` is the in-repo reference;
``benchmark/reference/kda_moe_lm.py`` is the benchmark's copy (the
yardstick may not move with the program), and one test holds the two to
the same text.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import reference_kda_moe_lm as reference
from horovod_tpu import training
from horovod_tpu.models import experts as experts_lib
from horovod_tpu.models import kda as kda_lib
from horovod_tpu.models.mla import LatentAttention, LatentAttentionConfig
from horovod_tpu.models.transformer import (Block, Transformer,
                                            TransformerConfig)

VOCAB, SEQ, D = 64, 32, 32
# the cell's pattern: a dense delta layer, delta layers of experts, one
# layer of latent attention among them
PATTERN = (("kda", "swiglu"), ("kda", "experts"), ("mla", "experts"),
           ("kda", "experts"))
KDA = kda_lib.DeltaAttentionConfig(
    num_heads=4, head_dim=8, conv_kernel=4, chunk_size=8, gate_rank=8)
MLA = LatentAttentionConfig(kv_lora_rank=16, qk_nope_head_dim=8,
                            qk_rope_head_dim=4, v_head_dim=8, rotary=False)
EXPERTS = experts_lib.ExpertShareConfig(
    n_routed_experts=16, experts_held=16, expert_offset=0,
    num_experts_per_tok=4, moe_d_ff=24, n_shared_experts=1,
    routed_scaling_factor=2.446, selection_bias_std=0.05)


def _config(dtype=jnp.float32, experts=EXPERTS, pattern=PATTERN, **kw):
    kw = {"flash_attention": False, "mla": MLA, **kw}
    return TransformerConfig(
        vocab_size=VOCAB, num_layers=len(pattern), num_heads=4, d_model=D,
        d_ff=48, dtype=dtype, norm_eps=1e-5, kda=KDA, experts=experts,
        layer_pattern=pattern, **kw)


def _arch(experts=EXPERTS):
    return {"kda_head_dim": KDA.head_dim,
            "qk_nope_head_dim": MLA.qk_nope_head_dim,
            "kv_lora_rank": MLA.kv_lora_rank,
            "num_experts_per_tok": experts.num_experts_per_tok,
            "routed_scaling_factor": experts.routed_scaling_factor,
            "expert_offset": experts.expert_offset}


def _tokens(seed=0, batch=2):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, VOCAB, size=(batch, SEQ)), jnp.int32)


def _init(cfg, seed=0):
    return Transformer(cfg).init(jax.random.PRNGKey(seed),
                                 _tokens())["params"]


def _shapes(tree):
    return jax.tree_util.tree_map(lambda a: tuple(a.shape), tree)


def test_parameter_tree_carries_the_published_module_names():
    params = _init(_config())
    assert set(params) == {"embed", "RMSNorm_0", "lm_head"} | {
        f"block_{i}" for i in range(4)}
    assert set(params["block_0"]) == {"RMSNorm_0", "RMSNorm_1", "mixer",
                                      "mlp"}
    assert set(params["block_1"]) == {"RMSNorm_0", "RMSNorm_1", "mixer",
                                      "experts", "shared_experts"}
    assert set(params["block_2"]) == {"RMSNorm_0", "RMSNorm_1", "attn",
                                      "experts", "shared_experts"}
    inner = 4 * 8
    projection = {"kernel": (D, inner)}
    convolution = {"kernel": (4, inner)}  # no bias
    assert _shapes(params["block_0"]["mixer"]) == {
        "q_proj": projection, "k_proj": projection, "v_proj": projection,
        "q_conv1d": convolution, "k_conv1d": convolution,
        "v_conv1d": convolution, "A_log": (4,), "dt_bias": (inner,),
        "f_a_proj": {"kernel": (D, 8)}, "f_b_proj": {"kernel": (8, inner)},
        "b_proj": {"kernel": (D, 4)}, "g_a_proj": {"kernel": (D, 8)},
        "g_b_proj": {"kernel": (8, inner), "bias": (inner,)},
        "o_norm": {"scale": (8,)}, "o_proj": {"kernel": (inner, D)}}


def test_delta_parameters_start_as_the_family_publishes_them():
    mixer = _init(_config())["block_0"]["mixer"]
    step = np.asarray(jax.nn.softplus(mixer["dt_bias"]))
    assert (step >= 0.001 - 1e-6).all() and (step <= 0.1 + 1e-6).all()
    a = np.exp(np.asarray(mixer["A_log"]))
    assert (a >= 1.0).all() and (a <= 16.0).all() and a.std() > 0
    np.testing.assert_array_equal(np.asarray(mixer["o_norm"]["scale"]), 1.0)
    np.testing.assert_array_equal(np.asarray(mixer["g_b_proj"]["bias"]), 0.0)


def test_the_cells_sizes_count_the_parameters_the_file_states():
    """The published widths through ``jax.eval_shape``: one delta mixer is
    39,518,368 parameters, the latent-attention mixer 29,114,880."""
    cfg = TransformerConfig(
        vocab_size=128, num_layers=2, num_heads=32, d_model=2304, d_ff=128,
        norm_eps=1e-5, flash_attention=False,
        kda=kda_lib.DeltaAttentionConfig(),
        mla=LatentAttentionConfig(rotary=False),
        layer_pattern=(("kda", "swiglu"), ("mla", "swiglu")))
    shapes = jax.eval_shape(
        Transformer(cfg).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 64), jnp.int32))["params"]
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    assert count(shapes["block_0"]["mixer"]) == 39_518_368
    assert count(shapes["block_1"]["attn"]) == 29_114_880


def _scan_inputs(rng, s, heads=4, d=8, batch=2, decay=(0.0, 1.5)):
    f = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), jnp.float32)
    unit = lambda x: x / jnp.linalg.norm(  # noqa: E731
        x, axis=-1, keepdims=True)
    q, k, v = unit(f(batch, s, heads, d)), unit(f(batch, s, heads, d)), f(
        batch, s, heads, d)
    # a decay a channel from none at all to e^-1.5 a position: the fastest
    # channels forget within a few positions, the slowest carry the state
    # across every chunk
    g = -jnp.asarray(rng.uniform(*decay, (batch, s, heads, d)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 1.0, (batch, s, heads)), jnp.float32)
    return q, k, v, g, beta


def _recurrence(*args):
    with jax.default_matmul_precision("highest"):
        return reference._recurrence(*args)


def test_the_recurrence_against_a_written_out_loop(rng):
    """The reference's scan against numpy loops over heads and positions:
    the decay a channel reaches the old state first, then the state is
    read back at k and corrected."""
    q, k, v, g, beta = (np.asarray(x, np.float64)
                        for x in _scan_inputs(rng, 6, heads=2, d=3, batch=1))
    want = np.zeros((6, 2, 3))
    for h in range(2):
        state = np.zeros((3, 3))
        for t in range(6):
            state = np.exp(g[0, t, h])[:, None] * state
            state = state + beta[0, t, h] * np.outer(
                k[0, t, h], v[0, t, h] - state.T @ k[0, t, h])
            want[t, h] = state.T @ q[0, t, h]
    got = _recurrence(*(jnp.asarray(x, jnp.float32)
                        for x in (q, k, v, g, beta)))
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=1e-5)


@pytest.mark.parametrize("chunk,chunks", [(8, 1), (8, 5), (16, 2), (24, 2),
                                          (32, 2), (64, 2)],
                         ids=lambda x: str(x))
def test_chunked_scan_equals_the_recurrence(rng, chunk, chunks):
    """The chunked form against one position at a time, at chunks of one
    diagonal block and of several (eight at the published 64), with
    channels whose state lives far longer than a chunk: dropping what a
    chunk inherits moves the result by far more than the tolerance."""
    args = _scan_inputs(rng, chunks * chunk)
    got = kda_lib.chunked_delta_scan(*args, chunk)
    want = _recurrence(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-4)
    if chunks > 1:
        # the same scan over each chunk alone, its state not carried
        alone = jnp.concatenate([
            kda_lib.chunked_delta_scan(
                *(x[:, i * chunk:(i + 1) * chunk] for x in args), chunk)
            for i in range(chunks)], 1)
        assert float(jnp.abs(alone - want)[:, chunk:].max()) > 0.05


@pytest.mark.parametrize("chunk,s", [(8, 24), (32, 128)],
                         ids=["one_block", "two_blocks"])
def test_chunked_scan_gradient_equals_the_recurrences(rng, chunk, s):
    args = _scan_inputs(rng, s)
    weight = jnp.asarray(rng.standard_normal(args[2].shape), jnp.float32)
    got = jax.grad(lambda *x: jnp.sum(
        kda_lib.chunked_delta_scan(*x, chunk) * weight),
        argnums=range(5))(*args)
    want = jax.grad(lambda *x: jnp.sum(_recurrence(*x) * weight),
                    argnums=range(5))(*args)
    for name, g, w in zip(("q", "k", "v", "g", "beta"), got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w),
            atol=1e-5 + 2e-4 * float(jnp.abs(w).max()), err_msg=name)


@pytest.mark.parametrize("decay,what", [((0.0, 1e-3), "near_none"),
                                        ((30.0, 30.0), "all_of_it")],
                         ids=lambda x: x if isinstance(x, str) else "")
def test_chunked_scan_at_the_ends_of_the_decay(rng, decay, what):
    """g near 0: the state is never forgotten, and every pair of a chunk
    matters. g at -30 a position: the running sum reaches -1920 inside a
    chunk of 64, ``exp(G_t) * exp(-G_i)`` would be 0 * inf, the state is
    decayed to nothing and a position reads what it wrote itself. No
    ``inf``, no ``nan``, value and gradient, and still the recurrence."""
    args = _scan_inputs(rng, 128, decay=decay)
    got, grads = jax.value_and_grad(
        lambda *x: jnp.sum(jnp.square(kda_lib.chunked_delta_scan(*x, 64))),
        argnums=range(5))(*args)
    want, want_grads = jax.value_and_grad(
        lambda *x: jnp.sum(jnp.square(_recurrence(*x))),
        argnums=range(5))(*args)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-4)
    for g, w in zip(grads, want_grads):
        assert bool(jnp.isfinite(g).all())
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w),
            atol=1e-5 + 1e-3 * float(jnp.abs(w).max()))
    if what == "all_of_it":
        q, k, v, _, beta = args
        o = kda_lib.chunked_delta_scan(*args, 64)
        own = (beta[..., None] * v
               * jnp.sum(q * k, -1, keepdims=True))  # S_t = beta k v^T
        np.testing.assert_allclose(np.asarray(o), np.asarray(own),
                                   atol=1e-6)


def test_heads_go_through_the_scan_a_group_at_a_time(rng):
    """Twice ``HEADS_AT_ONCE`` heads are two groups: each head's result
    and gradient are what the head gives alone."""
    heads = 2 * kda_lib.HEADS_AT_ONCE
    args = _scan_inputs(rng, 16, heads=heads, d=4, batch=1)
    loss = lambda *x: jnp.sum(jnp.square(  # noqa: E731
        kda_lib.chunked_delta_scan(*x, 8)))
    got, grads = jax.value_and_grad(loss, argnums=range(5))(*args)
    alone = [jax.value_and_grad(loss, argnums=range(5))(
        *(x[:, :, h:h + 1] for x in args)) for h in range(heads)]
    np.testing.assert_allclose(float(got), sum(float(v) for v, _ in alone),
                               rtol=1e-5)
    for i, g in enumerate(grads):
        np.testing.assert_allclose(
            np.asarray(g), np.concatenate([np.asarray(a[i])
                                           for _, a in alone], 2),
            atol=1e-6)


def test_a_scalar_decay_a_head_is_another_function(rng):
    """What makes this the delta rule with a decay A CHANNEL: the mean of
    g over a head's channels in its place moves the result."""
    q, k, v, g, beta = _scan_inputs(rng, 32)
    got = kda_lib.chunked_delta_scan(q, k, v, g, beta, 16)
    scalar = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    other = kda_lib.chunked_delta_scan(q, k, v, scalar, beta, 16)
    assert float(jnp.abs(other - got).max()) > 0.05
    np.testing.assert_allclose(
        np.asarray(other), np.asarray(_recurrence(q, k, v, scalar, beta)),
        atol=2e-5)


@pytest.mark.parametrize("n", [1, 5, 16, 24, 64])
def test_the_unit_triangular_inverse(rng, n):
    """Entries up to a half: what beta k.k of unit keys can reach at
    most (there the inverse's own entries pass a hundred at 64
    positions)."""
    a = jnp.tril(jnp.asarray(rng.uniform(-0.5, 0.5, (3, n, n)),
                             jnp.float32), -1)
    got = kda_lib._inverse_of_unit_lower(a)
    np.testing.assert_allclose(
        np.asarray(got @ (jnp.eye(n) + a)),
        np.broadcast_to(np.eye(n), (3, n, n)),
        atol=1e-5 * max(1.0, float(jnp.abs(got).max())))


def test_bfloat16_operands_keep_float32_statistics(rng):
    """In bfloat16 the scan stays within bfloat16's rounding of the
    recurrence: the running sum of g is float32 whatever the operands."""
    args = _scan_inputs(rng, 128, decay=(0.0, 0.3))
    want = _recurrence(*args)
    q, k, v, g, beta = args
    got = kda_lib.chunked_delta_scan(
        *(x.astype(jnp.bfloat16) for x in (q, k, v)), g, beta, 64)
    assert got.dtype == jnp.bfloat16
    err = jnp.linalg.norm(got.astype(jnp.float32) - want) / jnp.linalg.norm(
        want)
    assert float(err) < 2e-2, float(err)


def test_latent_attention_without_rotary(rng):
    """``rotary=False``: q_pe and k_pe are concatenated as they are, the
    same parameter tree, and the result does not change when every
    position moves; with rotary on (the default) it does."""
    cfg = _config()
    y = jnp.asarray(rng.standard_normal((2, SEQ, D)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ))
    module = LatentAttention(cfg)
    params = module.init(jax.random.PRNGKey(0), y, positions)["params"]
    got = module.apply({"params": params}, y, positions)
    moved = module.apply({"params": params}, y, positions + 1000)
    np.testing.assert_allclose(np.asarray(moved), np.asarray(got), atol=1e-6)
    turning = dataclasses.replace(cfg, mla=dataclasses.replace(
        MLA, rotary=True))
    assert _shapes(LatentAttention(turning).init(
        jax.random.PRNGKey(0), y, positions)["params"]) == _shapes(params)
    turned = LatentAttention(turning).apply({"params": params}, y, positions)
    assert float(jnp.abs(turned - got).max()) > 1e-2
    with jax.default_matmul_precision("highest"):
        want = reference._attention(params, y, _arch())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_the_default_latent_attention_is_the_accepted_cells(monkeypatch):
    """``LatentAttentionConfig``'s default turns q_pe and k_pe as before:
    the parameter tree and the traced program of the accepted
    latent-attention cell's mixer are what they were without the field
    (the program with the branch taken out of the source)."""
    from horovod_tpu.models import mla as mla_lib

    assert LatentAttentionConfig().rotary is True
    assert [f.name for f in dataclasses.fields(LatentAttentionConfig)] == [
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "rope_theta", "rotary"]
    cfg = _config(mla=LatentAttentionConfig(
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8), pattern=(("mla", "swiglu"), ("mla", "experts")))
    tokens = _tokens()
    params = _init(cfg)
    assert _shapes(params["block_0"]["attn"]) == {
        "q_proj": {"kernel": (D, 4, 12)},
        "kv_a_proj_with_mqa": {"kernel": (D, 20)},
        "kv_a_layernorm": {"scale": (16,)},
        "kv_b_proj": {"kernel": (16, 4, 16)},
        "o_proj": {"kernel": (4, 8, D)}}
    program = lambda: str(jax.make_jaxpr(  # noqa: E731
        lambda p: Transformer(cfg).apply({"params": p}, tokens))(params))
    with_switch = program()
    calls = []
    turn = mla_lib.rotary_interleaved
    monkeypatch.setattr(mla_lib, "rotary_interleaved",
                        lambda *a: calls.append(1) or turn(*a))
    assert program() == with_switch and len(calls) == 4  # q, k: two layers


def _choices(model, params, tokens, k):
    _, kept = model.apply({"params": params}, tokens,
                          mutable=["intermediates"])
    zeros = jnp.zeros((tokens.size, k), jnp.int32)
    return jnp.stack([
        kept["intermediates"][f"block_{i}"]["experts"]["chosen"][0]
        if feed_forward == "experts" else zeros
        for i, (_, feed_forward) in enumerate(PATTERN)])


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 2e-4),
                                        (jnp.bfloat16, 8e-2)],
                         ids=["f32", "bf16"])
def test_logits_match_reference(dtype, rtol):
    """In float32 every logit agrees. In bfloat16 a near-tie between the
    last expert chosen and the next can fall the other way, so the
    reference's experts are evaluated under the program's choices, and the
    median token's error is held: a delta layer is a product of q, k and v
    where softmax attention over near-equal scores hardly feels q and k,
    so each one triples the rounding it is given (0.3% in, 0.8% out at
    this size) and three of them in a row read 4%."""
    params = _init(_config())
    tokens = _tokens(1)
    model = Transformer(_config(dtype))
    got = model.apply({"params": params}, tokens)
    assert got.dtype == jnp.float32 and got.shape == (2, SEQ, VOCAB)
    choices = (None if dtype == jnp.float32
               else _choices(model, params, tokens, 4))
    with jax.default_matmul_precision("highest"):
        want, _ = reference.forward(params, tokens, _arch(), choices)
    err = (np.linalg.norm(got - want, axis=-1)
           / np.linalg.norm(want, axis=-1))
    worst = err.max() if dtype == jnp.float32 else np.median(err)
    assert worst < rtol, err


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_train_step_matches_reference(hvd, dtype):
    """One step through ``make_lm_train_step`` and
    ``hvd.DistributedOptimizer`` with ``kda``, ``mla`` without rotary, the
    dense layer and the share in one pattern: the loss, the routing and
    every gradient leaf (SGD at rate 1: old - new parameters) against the
    reference holding the same share, here experts 6..9 of 16. In float32
    leaf by leaf and choice by choice. In bfloat16 the whole gradient's
    distance over its norm is held (what the cell's ``grad_error`` reads),
    with the reference's experts under the program's own choices."""
    share = dataclasses.replace(EXPERTS, experts_held=4, expert_offset=6)
    cfg = _config(dtype, experts=share)
    model = Transformer(cfg)
    tx = hvd.DistributedOptimizer(optax.sgd(1.0), axes=("data",))
    tokens = _tokens(2, batch=8)
    state = training.create_train_state(model, tx, jax.random.PRNGKey(3),
                                        tokens)
    before = jax.tree_util.tree_map(np.asarray, state.params)
    step = training.make_lm_train_step(model, tx, mesh=hvd.mesh(),
                                       batch_axis="data", donate=False)
    after, loss = step(state, tokens)
    got = jax.tree_util.tree_map(lambda a, b: a - np.asarray(b), before,
                                 after.params)
    chosen = _choices(model, before, tokens, 4)
    with jax.default_matmul_precision("highest"):
        (want_loss, own), want = jax.value_and_grad(
            reference.loss, has_aux=True)(
                before, tokens, _arch(share),
                chosen if dtype == jnp.bfloat16 else None)
    bias = got["block_1"]["experts"]["e_score_correction_bias"]
    assert not np.any(bias)  # the selection bias receives no gradient
    assert np.any(got["block_1"]["experts"]["router"])
    norm = lambda tree: np.sqrt(sum(  # noqa: E731
        float(np.sum(np.square(x)))
        for x in jax.tree_util.tree_leaves(tree)))
    if dtype == jnp.bfloat16:
        np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-3)
        # reads 0.54 here, where the two other sparse models read 0.07:
        # three delta layers in a row, each tripling the rounding it is
        # given (test_logits_match_reference), at heads 8 wide; one delta
        # layer alone reads 0.045, the cell at heads of 128 0.04 to 0.05
        apart = norm(jax.tree_util.tree_map(np.subtract, got, want))
        assert apart / norm(want) < 0.7
        return
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1),
                                  np.sort(np.asarray(own), -1))
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    np.testing.assert_allclose(norm(got), norm(want), rtol=1e-4)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        w = np.asarray(flat_want[path])
        np.testing.assert_allclose(
            g, w, atol=2e-5 + 2e-3 * float(np.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))


def _layer_parts(share, params, y):
    """(routed part, shared part) of an expert layer for y [T, d]."""
    routed = experts_lib.ExpertShare(share, dtype=jnp.float32).apply(
        {"params": params["experts"]}, y)
    shared = experts_lib.shared_expert(
        share, dtype=jnp.float32, name=None).apply(
        {"params": params["shared_experts"]}, y)
    return routed, shared


def test_four_shares_add_up_to_the_uncut_layer(rng):
    """The routed parts of all 4 shares of 4 experts each (16 experts, 4 a
    token), with the shared expert counted once, add up to what the uncut
    reference gives for the whole layer."""
    params = _init(_config(pattern=(("kda", "experts"),)))["block_0"]
    y = jnp.asarray(rng.standard_normal((40, D)), jnp.float32)
    total = 0.0
    for i in range(4):
        share = dataclasses.replace(EXPERTS, experts_held=4,
                                    expert_offset=4 * i)
        held = {**params["experts"], **{
            name: params["experts"][name][4 * i:4 * i + 4]
            for name in ("gate_proj", "up_proj", "down_proj")}}
        routed, shared = _layer_parts(
            share, {"experts": held,
                    "shared_experts": params["shared_experts"]}, y)
        total = total + routed
        # each share alone agrees with the reference given that share
        with jax.default_matmul_precision("highest"):
            want, _ = reference._routed(held, y, _arch(share))
        assert float(jnp.abs(want).max()) > 0.01
        np.testing.assert_allclose(np.asarray(routed), np.asarray(want),
                                   atol=2e-5)
    with jax.default_matmul_precision("highest"):
        whole, _ = reference._routed(params["experts"], y, _arch())
        whole = whole + reference._shared(params["shared_experts"], y)
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(whole), atol=5e-5)


@pytest.mark.parametrize("case", ["kda", "mla"])
def test_decode_is_refused_not_approximated(case):
    cfg = _config()
    y, positions = jnp.zeros((1, 8, D)), jnp.arange(8)[None]
    cache = (jnp.zeros((1, 4, 4, 8)), jnp.zeros((1, 4, 4, 8)),
             jnp.arange(4)[None])
    module, said = {"kda": (kda_lib.DeltaAttention, "state cache"),
                    "mla": (LatentAttention, "latent cache")}[case]
    with pytest.raises(NotImplementedError, match=said):
        module(cfg).init(jax.random.PRNGKey(0), y, positions, False, cache)


def test_what_the_mixer_cannot_run_raises():
    cfg = _config()
    with pytest.raises(ValueError, match="chunk_size 8 divides; got 12"):
        kda_lib.DeltaAttention(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 12, D)))
    with pytest.raises(NotImplementedError, match="sequence-sharded"):
        kda_lib.DeltaAttention(dataclasses.replace(
            cfg, sequence_axis="seq")).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, D)))
    with pytest.raises(ValueError, match="unknown mixer 'delta'"):
        Block(cfg, "delta", "swiglu").init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, D)), jnp.arange(8)[None])


def test_benchmark_reference_is_a_copy():
    """The benchmark's reference and this directory's are the same text
    (the yardstick keeps its own copy) and give the same numbers."""
    from benchmark.reference import kda_moe_lm as copy

    text = lambda m: open(m.__file__, "rb").read()  # noqa: E731
    assert text(reference) == text(copy)
    share = dataclasses.replace(EXPERTS, experts_held=4, expert_offset=8)
    params = _init(_config(experts=share))
    tokens = _tokens(5)
    a = reference.loss_and_grad(params, tokens, _arch(share))
    b = copy.loss_and_grad(params, tokens, _arch(share))
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_blockwise_gradient_is_the_whole_functions():
    """``loss_and_grad`` (a program a kind of half, one sequence at a
    time) gives the loss and the gradient of ``loss`` differentiated
    whole."""
    share = dataclasses.replace(EXPERTS, experts_held=4, expert_offset=2)
    params = _init(_config(experts=share))
    tokens = _tokens(6, batch=3)
    value, grads, idx = reference.loss_and_grad(params, tokens,
                                                _arch(share))
    with jax.default_matmul_precision("highest"):
        (want, want_idx), want_grads = jax.value_and_grad(
            reference.loss, has_aux=True)(params, tokens, _arch(share))
    np.testing.assert_allclose(float(value), float(want), rtol=1e-6)
    flat = dict(jax.tree_util.tree_leaves_with_path(grads))
    for path, w in jax.tree_util.tree_leaves_with_path(want_grads):
        np.testing.assert_allclose(
            np.asarray(flat[path]), np.asarray(w),
            atol=1e-6 + 1e-4 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))
    assert idx.shape == (3, len(PATTERN), SEQ, 4)
    np.testing.assert_array_equal(
        np.asarray(idx).transpose(1, 0, 2, 3).reshape(
            len(PATTERN), 3 * SEQ, 4), np.asarray(want_idx))


# What the small cell below is held to: the cell's limits
# (``families/kda_moe_lm.LIMITS``) with room for heads 64 wide in a model
# 128 wide, where a delta layer's rounding weighs more than at the
# published widths (sound here: grad_error 0.071, routing_apart 0.035,
# loss 2.3e-4, grad_norm 7.4e-4).
SMALL_LIMITS = {"loss": 1.5e-3, "grad_norm": 1e-2, "grad_error": 0.12,
                "routing_apart": 0.05}


@pytest.fixture(scope="module")
def small_cell():
    """The benchmark family of ``kimi-linear-48b-a3b-train-s4096`` built
    at a small size on this machine's mesh, one step of it taken, and the
    sound reference's readings: what ``reference_check`` does, in its
    parts, under ``SMALL_LIMITS``."""
    import json

    import horovod_tpu as hvd
    from benchmark.families import kda_moe_lm as family

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        config = json.load(f)
    config.update(hidden_size=128, intermediate_size=192,
                  num_attention_heads=4, num_key_value_heads=4,
                  kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16, moe_intermediate_size=32, num_experts=4,
                  vocab_size=256, num_experts_per_token=3)
    config["linear_attn_config"].update(num_heads=2, head_dim=64)
    config["deployment"]["router_width"] = 16
    config["assumed"].update(flash_attention=False, kda_gate_rank=16)
    # four chunks of the published 64 positions: the running sum of the
    # log-decay grows as far inside a chunk as it does in the cell
    traffic = {"per_chip_batch": 2, "seq_len": 256}
    hvd.shutdown()
    hvd.init()
    limits, family.LIMITS = family.LIMITS, SMALL_LIMITS
    try:
        built = family.build(config, traffic, hvd.mesh(), 11)
        got = built.step_numbers()
        _, sound = built.compare(got, built.reference_numbers(got[2]))
        hvd.shutdown()
        yield config, built, got, sound
    finally:
        family.LIMITS = limits


def _faults():
    from benchmark.reference import kda_moe_lm_faults
    return kda_moe_lm_faults


def test_the_cells_limits_are_tighter_than_the_small_cells():
    from benchmark.families import kda_moe_lm as family

    assert set(family.LIMITS) == set(SMALL_LIMITS)
    assert all(family.LIMITS[name] <= SMALL_LIMITS[name]
               for name in SMALL_LIMITS)


def test_the_small_cell_agrees_with_its_reference(small_cell):
    _, _, got, sound = small_cell
    told = [name for name, r in sound.items()
            if name != "routing" and not r["agrees"]]
    assert not told, {name: sound[name] for name in told}
    assert len(sound["routing"]["apart_per_layer"]) == 4
    assert got[2].shape == (2, 5, 256, 3)  # two sequences, every layer
    # the finer readings are read, not judged
    for name in ("scan_grad_error", "attention_grad_error"):
        assert sound[name]["tolerance"] is None and sound[name]["agrees"]
        assert 0 < sound[name]["relative_error"] < 0.1


@pytest.mark.parametrize("fault", _faults().FAULTS)
def test_a_fault_in_the_reference_is_told_by_the_limits_that_tell_it(
        small_cell, fault):
    """Each fault of ``benchmark/reference/kda_moe_lm_faults.py`` planted
    into the benchmark's reference, against the step the family took: the
    first limit that told it on the chip (``TOLD_BY``) tells it here; those
    that need the cell's size (``NEEDS_THE_CELLS_SIZE``) show here in a
    reading that rises over its sound value; and what no limit told on the
    chip (the reference at the step's own precision) stays sound."""
    config, built, got, sound = small_cell
    faults = _faults()
    with faults.planted(fault, config):
        agrees, report = built.compare(got, built.reference_numbers(got[2]))
    read = faults.readings(report)
    over = lambda name: (  # noqa: E731
        read[name] / sound[name]["relative_error"])
    if fault in faults.NEEDS_THE_CELLS_SIZE:
        assert over(faults.NEEDS_THE_CELLS_SIZE[fault]) > 1.1, read
    else:
        assert set(faults.TOLD_BY[fault][:1]) <= set(read["told_by"]), read
    assert agrees == (not read["told_by"])
    if fault == "bfloat16_operands":
        assert agrees and over("scan_grad_error") < 1.25, read
    # the fault came out again: the next call is sound
    assert faults.reference._route.__module__ == faults.reference.__name__
    assert faults.reference.MANTISSA_BITS is None
