"""The window / full attention hybrid decoder with sparse experts (two
kinds of multi-head attention in one model: their own query heads over
shared key/value heads, a sliding window, rotary a kind with YaRN on part
of a head, a sigmoid output gate a head; a dense SwiGLU layer, SwiGLU
experts that hold a share, one shared expert) against its plain
reference, at a small size on the CPU with seeded random weights.

``tests/reference_swa_moe_lm.py`` is the in-repo reference;
``benchmark/reference/swa_moe_lm.py`` is the benchmark's copy (the
yardstick may not move with the program), and one test holds the two to
the same text.
"""

import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import reference_swa_moe_lm as reference
from horovod_tpu import training
from horovod_tpu.models import experts as experts_lib
from horovod_tpu.models import transformer as transformer_lib
from horovod_tpu.models.transformer import (Attention, AttentionConfig,
                                            Block, Transformer,
                                            TransformerConfig, YarnScaling)

VOCAB, SEQ, D = 64, 32, 32
# the cell's pattern in small: a dense full layer, sliding layers of
# experts, a full layer of experts among them
PATTERN = (("full", "swiglu"), ("sliding", "experts"),
           ("sliding", "experts"), ("full", "experts"))
# YaRN made for 8 positions and run at 32: the blend is exercised
YARN = YarnScaling(factor=16.0, original_max_position_embeddings=8,
                   beta_fast=4.0, beta_slow=1.0,
                   attention_factor=1.2772588722239782)
FULL = AttentionConfig(kind="full", num_heads=4, head_dim=8, num_kv_heads=2,
                       rope_theta=500000.0, rotary_dim=4, yarn=YARN,
                       gate=True)
SLIDING = AttentionConfig(kind="sliding", num_heads=6, head_dim=8,
                          num_kv_heads=2, window=5, rope_theta=10000.0,
                          rotary_dim=8, gate=True)
EXPERTS = experts_lib.ExpertShareConfig(
    n_routed_experts=16, experts_held=16, expert_offset=0,
    num_experts_per_tok=4, moe_d_ff=24, n_shared_experts=1, shared_d_ff=24,
    routed_scaling_factor=2.5, selection_bias_std=0.05)


def _config(dtype=jnp.float32, experts=EXPERTS, pattern=PATTERN,
            attention=(FULL, SLIDING), **kw):
    kw = {"flash_attention": False, **kw}
    return TransformerConfig(
        vocab_size=VOCAB, num_layers=len(pattern), num_heads=4, d_model=D,
        d_ff=48, dtype=dtype, norm_eps=1e-6, attention=attention,
        experts=experts, layer_pattern=pattern, **kw)


def _described(a):
    """What the reference is told of a kind."""
    yarn = None if a.yarn is None else {
        **dataclasses.asdict(a.yarn),
        "attention_factor": a.yarn.cos_sin_factor()}
    return {"sliding_window": a.window, "rope_theta": a.rope_theta,
            "rotary_dim": a.rotary_dim or a.head_dim, "yarn": yarn}


def _arch(experts=EXPERTS, pattern=PATTERN, attention=(FULL, SLIDING)):
    return {"layer_kinds": [mixer for mixer, _ in pattern],
            "attention": {a.kind: _described(a) for a in attention},
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


def test_parameter_tree_sizes_each_kind_by_its_own_config():
    params = _init(_config())
    assert set(params) == {"embed", "RMSNorm_0", "lm_head"} | {
        f"block_{i}" for i in range(4)}
    assert set(params["block_0"]) == {"RMSNorm_0", "RMSNorm_1", "attn",
                                      "mlp"}
    assert set(params["block_1"]) == {"RMSNorm_0", "RMSNorm_1", "attn",
                                      "experts", "shared_experts"}
    for block, heads in (("block_0", 4), ("block_1", 6), ("block_3", 4)):
        assert _shapes(params[block]["attn"]) == {
            "query": {"kernel": (D, heads, 8)},
            "key": {"kernel": (D, 2, 8)}, "value": {"kernel": (D, 2, 8)},
            "gate": {"kernel": (D, heads)},
            "out": {"kernel": (heads, 8, D)}}


def test_the_cells_sizes_count_the_parameters_the_file_states():
    """The published widths through ``jax.eval_shape``: a full layer's
    attention is 29,458,432 parameters, a sliding layer's 37,879,808."""
    yarn = YarnScaling(factor=64.0, beta_fast=64.0,
                       original_max_position_embeddings=4096)
    kinds = (AttentionConfig(kind="full", num_heads=48, head_dim=128,
                             num_kv_heads=8, rope_theta=5e5, rotary_dim=64,
                             yarn=yarn, gate=True),
             AttentionConfig(kind="sliding", num_heads=64, head_dim=128,
                             num_kv_heads=8, window=512, gate=True))
    cfg = TransformerConfig(
        vocab_size=128, num_layers=2, num_heads=64, d_model=2048, d_ff=128,
        flash_attention=False, attention=kinds,
        layer_pattern=(("full", "swiglu"), ("sliding", "swiglu")))
    shapes = jax.eval_shape(
        Transformer(cfg).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 64), jnp.int32))["params"]
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    assert count(shapes["block_0"]["attn"]) == 29_458_432
    assert count(shapes["block_1"]["attn"]) == 37_879_808
    assert yarn.cos_sin_factor() == pytest.approx(1.4158883083359672)


def test_one_kind_of_mha_builds_the_tree_and_the_program_it_built():
    """A config that names no kind builds today's parameter tree, name
    for name and shape for shape, and a kind of the same sizes under
    another name builds the same tree and gives the same logits: "mha"
    IS the kind the top-level fields size."""
    base = dict(vocab_size=VOCAB, num_layers=2, num_heads=4, d_model=D,
                d_ff=48, dtype=jnp.float32, flash_attention=False,
                num_kv_heads=2, head_dim=8)
    plain = TransformerConfig(**base)
    params = _init(plain)
    assert _shapes(params["block_0"]) == {
        "RMSNorm_0": {"scale": (D,)}, "RMSNorm_1": {"scale": (D,)},
        "Dense_0": {"kernel": (D, 48)}, "Dense_1": {"kernel": (48, D)},
        "attn": {"query": {"kernel": (D, 4, 8)},
                 "key": {"kernel": (D, 2, 8)},
                 "value": {"kernel": (D, 2, 8)},
                 "out": {"kernel": (4, 8, D)}}}
    named = TransformerConfig(
        **base, layer_pattern=(("same", "gelu"),) * 2,
        attention=(AttentionConfig(kind="same", num_heads=4, head_dim=8,
                                   num_kv_heads=2),))
    assert _shapes(_init(named)) == _shapes(params)
    tokens = _tokens(3)
    np.testing.assert_array_equal(
        np.asarray(Transformer(named).apply({"params": params}, tokens)),
        np.asarray(Transformer(plain).apply({"params": params}, tokens)))
    assert plain.attention_kind("mha") == AttentionConfig(
        kind="mha", num_heads=4, head_dim=8, num_kv_heads=2)
    assert plain.attention_kind("mla") is None


def _old_rotary(x, positions):
    """``_rotary`` as it stood before it took arguments."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (10000.0 ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_rotary_without_arguments_is_bit_for_bit_what_it_was(rng, dtype):
    x = jnp.asarray(rng.standard_normal((2, SEQ, 3, 16)), dtype)
    positions = jnp.broadcast_to(jnp.arange(SEQ) + 1000, (2, SEQ))
    want = np.asarray(_old_rotary(x, positions).astype(jnp.float32))
    for got in (transformer_lib._rotary(x, positions),
                transformer_lib._rotary(x, positions, 10000.0, 16),
                AttentionConfig(kind="a", num_heads=3,
                                head_dim=16).rotate(x, positions)):
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)), want)


def test_partial_rotary_turns_the_first_elements_and_passes_the_rest(rng):
    x = jnp.asarray(rng.standard_normal((1, SEQ, 2, 16)), jnp.float32)
    positions = jnp.arange(SEQ)[None]
    got = transformer_lib._rotary(x, positions, 500000.0, 8)
    np.testing.assert_array_equal(np.asarray(got[..., 8:]),
                                  np.asarray(x[..., 8:]))
    np.testing.assert_allclose(
        np.asarray(got[..., :8]),
        np.asarray(transformer_lib._rotary(x[..., :8], positions, 500000.0)),
        atol=1e-6)
    kind = _described(AttentionConfig(
        kind="a", num_heads=2, head_dim=16, rope_theta=500000.0,
        rotary_dim=8))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(reference._rope(x, kind)),
                               atol=1e-6)


def test_yarn_frequencies_are_the_published_formulas():
    """The cell's full layers: r = 64, theta 500000, factor 64 over 4096
    positions, beta 64 and 1, written out here as the issue writes them,
    against the program's and the reference's."""
    yarn = YarnScaling(factor=64.0, original_max_position_embeddings=4096,
                       beta_fast=64.0, beta_slow=1.0,
                       attention_factor=1.4158883083359672)
    n = np.arange(32)
    p = 500000.0 ** (2 * n / 64)
    dim = lambda t: (64 * math.log(4096 / (2 * math.pi * t))  # noqa: E731
                     / (2 * math.log(500000.0)))
    low, high = max(math.floor(dim(64)), 0), min(math.ceil(dim(1)), 63)
    assert (low, high) == (5, 16)
    ramp = np.clip((n - low) / (high - low), 0, 1)
    want = (1 - ramp) / p + ramp / (64 * p)
    got = yarn.inv_freq(500000.0, 64)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # fast pairs keep their frequency, slow ones are divided by 64, and
    # those between are blended
    assert got[0] == 1.0 and got[5] == pytest.approx(1 / p[5])
    assert got[16] == pytest.approx(1 / (64 * p[16]))
    assert 1 / (64 * p[10]) < got[10] < 1 / p[10]
    kind = _described(AttentionConfig(
        kind="a", num_heads=2, head_dim=128, rope_theta=500000.0,
        rotary_dim=64, yarn=yarn))
    np.testing.assert_allclose(np.asarray(reference._inv_freq(kind)), want,
                               rtol=1e-5)
    assert reference._cos_sin_factor(kind) == 1.4158883083359672
    assert YarnScaling(64.0, 4096).cos_sin_factor() == pytest.approx(
        0.1 * math.log(64) + 1)


@pytest.mark.parametrize("kind", [FULL, SLIDING], ids=lambda a: a.kind)
def test_attention_layer_of_each_kind_matches_the_reference(rng, kind):
    """One ``Attention`` of each kind against the reference's equations:
    grouped query heads, the kind's rotary (YaRN past its original 8
    positions), its mask and its gate; and what each switch is worth:
    without the window, the gate, the rotary or the blend the output
    moves by far more than the tolerance."""
    cfg = _config()
    y = jnp.asarray(rng.standard_normal((2, SEQ, D)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ))
    module = Attention(cfg, kind.kind)
    params = module.init(jax.random.PRNGKey(0), y, positions)["params"]
    got = module.apply({"params": params}, y, positions)
    with jax.default_matmul_precision("highest"):
        want = reference._attention(params, y, _described(kind))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    switches = [dict(gate=False), dict(rotary=False)]
    switches.append(dict(yarn=None) if kind.window is None
                    else dict(window=None))
    for switch in switches:
        other = dataclasses.replace(kind, **switch)
        moved = Attention(dataclasses.replace(
            cfg, attention=(other,)), kind.kind).apply(
                {"params": params}, y, positions)
        assert float(jnp.abs(moved - got).max()) > 1e-2, switch


def test_reference_attention_in_blocks_of_rows_is_the_whole(rng,
                                                            monkeypatch):
    """The reference's attention ``ROWS`` query positions at a time
    against all positions at once."""
    y = jnp.asarray(rng.standard_normal((2, SEQ, D)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ))
    params = Attention(_config(), "sliding").init(
        jax.random.PRNGKey(1), y, positions)["params"]
    with jax.default_matmul_precision("highest"):
        whole = reference._attention(params, y, _described(SLIDING))
        monkeypatch.setattr(reference, "ROWS", 8)
        blocks = reference._attention(params, y, _described(SLIDING))
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole),
                               atol=1e-6)


def test_flash_and_dense_paths_of_a_windowed_layer_agree(rng):
    """The kernel (interpret mode) and ``dense_attention`` are the same
    function of a sliding layer: at a sequence of several windows the two
    paths give the same output."""
    kind = dataclasses.replace(SLIDING, window=40)
    y = jnp.asarray(rng.standard_normal((1, 128, D)), jnp.float32)
    positions = jnp.arange(128)[None]
    cfg = _config(attention=(kind,))
    params = Attention(cfg, "sliding").init(
        jax.random.PRNGKey(2), y, positions)["params"]
    dense = Attention(cfg, "sliding").apply({"params": params}, y, positions)
    flash = Attention(dataclasses.replace(cfg, flash_attention=True),
                      "sliding").apply({"params": params}, y, positions,
                                       True)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               atol=2e-5)


def _choices(model, params, tokens, k):
    _, kept = model.apply({"params": params}, tokens,
                          mutable=["intermediates"])
    zeros = jnp.zeros((tokens.size, k), jnp.int32)
    return jnp.stack([
        kept["intermediates"][f"block_{i}"]["experts"]["chosen"][0]
        if feed_forward == "experts" else zeros
        for i, (_, feed_forward) in enumerate(PATTERN)])


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 2e-4),
                                        (jnp.bfloat16, 4e-2)],
                         ids=["f32", "bf16"])
def test_logits_match_reference(dtype, rtol):
    """In float32 every logit agrees. In bfloat16 a near-tie between the
    last expert chosen and the next can fall the other way, so the
    reference's experts are evaluated under the program's choices, and the
    median token's error is held."""
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
    ``hvd.DistributedOptimizer`` with two kinds of attention of different
    head counts, partial and whole rotary, YaRN past its original length,
    the window, the gate, the dense layer and the share in one pattern:
    the loss, the routing and every gradient leaf (SGD at rate 1: old -
    new parameters) against the reference holding the same share, here
    experts 6..9 of 16. In float32 leaf by leaf and choice by choice. In
    bfloat16 the whole gradient's distance over its norm is held (what the
    cell's ``grad_error`` reads), with the reference's experts under the
    program's own choices."""
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
    assert np.any(got["block_1"]["attn"]["gate"]["kernel"])
    norm = lambda tree: np.sqrt(sum(  # noqa: E731
        float(np.sum(np.square(x)))
        for x in jax.tree_util.tree_leaves(tree)))
    if dtype == jnp.bfloat16:
        np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-3)
        apart = norm(jax.tree_util.tree_map(np.subtract, got, want))
        assert apart / norm(want) < 0.12
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


@pytest.mark.parametrize("experts,top_k,held", [(32, 4, 8), (16, 4, 4)],
                         ids=["four_shares_of_8_of_32",
                              "four_shares_of_4_of_16"])
def test_the_shares_add_up_to_the_uncut_layer(rng, experts, top_k, held):
    """The routed parts of all four shares (32 experts, 4 a token, 8
    held: Laguna's routing in small; and 16 experts, 4 held), with the
    shared expert counted once, add up to what the uncut reference gives
    for the whole layer."""
    whole_share = dataclasses.replace(
        EXPERTS, n_routed_experts=experts, experts_held=experts,
        num_experts_per_tok=top_k)
    params = _init(_config(experts=whole_share,
                           pattern=(("sliding", "experts"),)))["block_0"]
    y = jnp.asarray(rng.standard_normal((40, D)), jnp.float32)
    total = 0.0
    for i in range(experts // held):
        share = dataclasses.replace(whole_share, experts_held=held,
                                    expert_offset=held * i)
        kept = {**params["experts"], **{
            name: params["experts"][name][held * i:held * (i + 1)]
            for name in ("gate_proj", "up_proj", "down_proj")}}
        routed, shared = _layer_parts(
            share, {"experts": kept,
                    "shared_experts": params["shared_experts"]}, y)
        total = total + routed
        # each share alone agrees with the reference given that share
        with jax.default_matmul_precision("highest"):
            want, _ = reference._routed(kept, y, _arch(share))
        assert float(jnp.abs(want).max()) > 0.01
        np.testing.assert_allclose(np.asarray(routed), np.asarray(want),
                                   atol=2e-5)
    with jax.default_matmul_precision("highest"):
        whole, _ = reference._routed(params["experts"], y,
                                     _arch(whole_share))
        whole = whole + reference._shared(params["shared_experts"], y)
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(whole), atol=5e-5)


@pytest.mark.parametrize("case", ["cache", "ring"])
def test_what_a_windowed_layer_cannot_run_is_refused(case):
    """A window meets the paged cache, or ring attention: refused, naming
    the module, not approximated."""
    y, positions = jnp.zeros((1, 8, D)), jnp.arange(8)[None]
    cache = (jnp.zeros((1, 4, 6, 8)), jnp.zeros((1, 4, 6, 8)),
             jnp.arange(4)[None])
    if case == "cache":
        cfg, args, said = _config(), (False, cache), "frees none behind"
    else:
        cfg, args, said = _config(sequence_axis="seq"), (), "no window"
    with pytest.raises(NotImplementedError,
                       match="models.transformer.Attention.*" + said):
        Attention(cfg, "sliding").init(jax.random.PRNGKey(0), y, positions,
                                       *args)


def test_unknown_kinds_and_grouped_decode_are_refused():
    cfg = _config()
    y, positions = jnp.zeros((1, 8, D)), jnp.arange(8)[None]
    with pytest.raises(ValueError, match="unknown mixer 'global'"):
        Block(cfg, "global", "swiglu").init(jax.random.PRNGKey(0), y,
                                            positions)
    cache = (jnp.zeros((1, 4, 4, 8)), jnp.zeros((1, 4, 4, 8)),
             jnp.arange(4)[None])
    with pytest.raises(NotImplementedError, match="grouped-query"):
        Attention(cfg, "full").init(jax.random.PRNGKey(0), y, positions,
                                    False, cache)


def test_benchmark_reference_is_a_copy():
    """The benchmark's reference and this directory's are the same text
    (the yardstick keeps its own copy) and give the same numbers."""
    from benchmark.reference import swa_moe_lm as copy

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
# (``families/swa_moe_lm.LIMITS``) with room for heads 16 wide in a model
# 128 wide over 256 positions (sound here: grad_error 0.044,
# routing_apart 0.020, loss 2.1e-4, grad_norm 1.5e-3, attention_apart
# 0.0034).
SMALL_LIMITS = {"loss": 1.5e-3, "grad_norm": 1e-2, "grad_error": 0.10,
                "attention_apart": 0.01,
                "routing_apart": 0.042}


@pytest.fixture(scope="module")
def small_cell():
    """The benchmark family of ``laguna-xs.2-train-s8192`` built at a
    small size on this machine's mesh (one sequence a chip, as the cell),
    one step of it taken, and the sound reference's readings: what
    ``reference_check`` does, in its parts, under ``SMALL_LIMITS``."""
    import json

    import horovod_tpu as hvd
    from benchmark.families import swa_moe_lm as family

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs",
                           "laguna-xs.2.json")) as f:
        config = json.load(f)
    config.update(
        hidden_size=128, intermediate_size=192, head_dim=16,
        num_key_value_heads=2, num_hidden_layers=4,
        num_attention_heads_per_layer=[4, 8, 8, 4],
        layer_types=config["layer_types"][:3] + ["full_attention"],
        mlp_layer_types=config["mlp_layer_types"][:4],
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        num_experts=4, vocab_size=256, num_experts_per_tok=3,
        sliding_window=48)
    # YaRN made for 64 positions and run at 256, four times past it
    config["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 64
    config["deployment"]["router_width"] = 16
    config["assumed"]["flash_attention"] = False
    traffic = {"per_chip_batch": 1, "seq_len": 256}
    hvd.shutdown()
    hvd.init()
    limits, family.LIMITS = family.LIMITS, SMALL_LIMITS
    try:
        built = family.build(config, traffic, hvd.mesh(), 3_000_000_011)
        got = built.step_numbers()
        _, sound = built.compare(got, built.reference_numbers(got[2]))
        hvd.shutdown()
        yield config, built, got, sound
    finally:
        family.LIMITS = limits


def _faults():
    from benchmark.reference import swa_moe_lm_faults
    return swa_moe_lm_faults


def test_the_cells_limits_are_tighter_than_the_small_cells():
    from benchmark.families import swa_moe_lm as family

    assert set(family.LIMITS) == set(SMALL_LIMITS)
    assert all(family.LIMITS[name] <= SMALL_LIMITS[name]
               for name in SMALL_LIMITS)


def test_the_small_cell_agrees_with_its_reference(small_cell, n_devices):
    _, _, got, sound = small_cell
    told = [name for name, r in sound.items()
            if name != "routing" and not r["agrees"]]
    assert not told, {name: sound[name] for name in told}
    assert len(sound["routing"]["apart_per_layer"]) == 3
    # the sequences checked: two where the batch has room, every layer
    assert got[2].shape == (min(2, n_devices), 4, 256, 3)
    # the finer reading is read, not judged
    name = "attention_grad_error"
    assert sound[name]["tolerance"] is None and sound[name]["agrees"]
    assert 0 < sound[name]["relative_error"] < 0.1


@pytest.mark.parametrize("fault", _faults().FAULTS)
def test_a_fault_in_the_reference_is_told_by_the_limits_that_tell_it(
        small_cell, fault):
    """Each fault of ``benchmark/reference/swa_moe_lm_faults.py`` planted
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
        assert agrees and over("attention_grad_error") < 1.25, read
    # the fault came out again: the next call is sound
    assert faults.reference._route.__module__ == faults.reference.__name__
    assert faults.reference.MANTISSA_BITS is None
