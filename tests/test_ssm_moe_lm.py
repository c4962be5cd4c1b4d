"""The hybrid state-space / sparse-expert decoder (a Mamba-2 mixer with a
chunked scan, layers of one sublayer, grouped-query attention without
rotary, relu^2 experts that hold a share, one shared expert) against its
plain reference, at a small size on the CPU with seeded random weights.

``tests/reference_ssm_moe_lm.py`` is the in-repo reference;
``benchmark/reference/ssm_moe_lm.py`` is the benchmark's copy (the
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

import reference_ssm_moe_lm as reference
from horovod_tpu import training
from horovod_tpu.models import experts as experts_lib
from horovod_tpu.models import ssm as ssm_lib
from horovod_tpu.models.transformer import (Attention, Block, Transformer,
                                            TransformerConfig)

VOCAB, SEQ, D = 64, 32, 32
PATTERN = "MEM*E"
LAYER = {"M": ("ssm", None), "E": (None, "experts"), "*": ("mha", None)}
# a step up to 1 and A up to 16: a state decays to nothing inside a chunk
# for the fast heads and lives across several for the slow ones
SSM = ssm_lib.StateSpaceConfig(
    num_heads=8, head_dim=4, n_groups=2, state_size=8, conv_kernel=4,
    chunk_size=8, time_step_min=0.01, time_step_max=1.0)
EXPERTS = experts_lib.ExpertShareConfig(
    n_routed_experts=16, experts_held=16, expert_offset=0,
    num_experts_per_tok=3, moe_d_ff=24, n_shared_experts=1, shared_d_ff=40,
    routed_scaling_factor=2.5, selection_bias_std=0.05, expert_body="relu2")


def _config(dtype=jnp.float32, experts=EXPERTS, pattern=PATTERN, **kw):
    kw = {"flash_attention": False, **kw}
    return TransformerConfig(
        vocab_size=VOCAB, num_layers=len(pattern), num_heads=4,
        num_kv_heads=2, head_dim=8, rotary=False, d_model=D, d_ff=0,
        dtype=dtype, norm_eps=1e-5, ssm=SSM, experts=experts,
        layer_pattern=tuple(LAYER[kind] for kind in pattern), **kw)


def _arch(experts=EXPERTS):
    return {"mamba_head_dim": SSM.head_dim, "n_groups": SSM.n_groups,
            "ssm_state_size": SSM.state_size,
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
        f"block_{i}" for i in range(5)}
    # one norm a layer, and the one sublayer
    assert set(params["block_0"]) == {"RMSNorm_0", "mixer"}
    assert set(params["block_1"]) == {"RMSNorm_0", "experts",
                                      "shared_experts"}
    assert set(params["block_3"]) == {"RMSNorm_0", "attn"}
    inner, bc = 8 * 4, 2 * 2 * 8
    assert _shapes(params["block_0"]["mixer"]) == {
        "in_proj": {"kernel": (D, 2 * inner + bc + 8)},
        "conv1d": {"kernel": (4, inner + bc), "bias": (inner + bc,)},
        "dt_bias": (8,), "A_log": (8,), "D": (8,),
        "norm": {"scale": (inner,)}, "out_proj": {"kernel": (inner, D)}}
    assert _shapes(params["block_3"]["attn"]) == {
        "query": {"kernel": (D, 4, 8)}, "key": {"kernel": (D, 2, 8)},
        "value": {"kernel": (D, 2, 8)}, "out": {"kernel": (4, 8, D)}}
    # two matrices an expert, routed and shared: no gate
    assert _shapes(params["block_1"]["experts"]) == {
        "up_proj": (16, D, 24), "down_proj": (16, 24, D),
        "router": (D, 16), "e_score_correction_bias": (16,)}
    assert _shapes(params["block_1"]["shared_experts"]) == {
        "up_proj": {"kernel": (D, 40)}, "down_proj": {"kernel": (40, D)}}


def test_state_space_parameters_start_as_the_family_publishes_them():
    mixer = _init(_config())["block_0"]["mixer"]
    step = np.asarray(jax.nn.softplus(mixer["dt_bias"]))
    assert (step >= 0.01 - 1e-6).all() and (step <= 1.0 + 1e-6).all()
    a = np.exp(np.asarray(mixer["A_log"]))
    assert (a >= 1.0).all() and (a <= 16.0).all() and a.std() > 0
    np.testing.assert_array_equal(np.asarray(mixer["D"]), 1.0)
    np.testing.assert_array_equal(np.asarray(mixer["norm"]["scale"]), 1.0)
    floored = ssm_lib._dt_bias_init(dataclasses.replace(
        SSM, time_step_min=1e-6, time_step_max=1e-5, time_step_floor=1e-4))(
        jax.random.PRNGKey(0), (64,))
    np.testing.assert_allclose(np.asarray(jax.nn.softplus(floored)), 1e-4,
                               rtol=1e-4)


MHA = {"query": {"kernel": (16, 2, 8)}, "key": {"kernel": (16, 2, 8)},
       "value": {"kernel": (16, 2, 8)}, "out": {"kernel": (2, 8, 16)}}
NORMS = {"RMSNorm_0": {"scale": (16,)}, "RMSNorm_1": {"scale": (16,)}}
GATED = {name: {"kernel": shape} for name, shape in (
    ("gate_proj", (16, 32)), ("up_proj", (16, 32)), ("down_proj", (32, 16)))}


@pytest.mark.parametrize("kw,block", [
    # lm-d768 / lm-d2048: the default pattern
    (dict(), {**NORMS, "attn": MHA, "Dense_0": {"kernel": (16, 32)},
              "Dense_1": {"kernel": (32, 16)}}),
    # the capacity-dispatch layer of moe_every
    (dict(moe_every=1, num_experts=4),
     {**NORMS, "attn": MHA, "moe": {"gate": (16, 4), "w_in": (4, 16, 32),
                                    "w_out": (4, 32, 16)}}),
    # kanana-2-30b-a3b: (mla, swiglu) then (mla, experts)
    (dict(layer_pattern=(("mla", "swiglu"),), mla=True),
     {**NORMS, "mlp": GATED, "attn": {
         "q_proj": {"kernel": (16, 2, 12)},
         "kv_a_proj_with_mqa": {"kernel": (16, 12)},
         "kv_a_layernorm": {"scale": (8,)},
         "kv_b_proj": {"kernel": (8, 2, 16)},
         "o_proj": {"kernel": (2, 8, 16)}}}),
    (dict(layer_pattern=(("mha", "experts"),), experts=True),
     {**NORMS, "attn": MHA, "shared_experts": GATED, "experts": {
         "gate_proj": (4, 16, 16), "up_proj": (4, 16, 16),
         "down_proj": (4, 16, 16), "router": (16, 4),
         "e_score_correction_bias": (4,)}}),
], ids=["default", "moe_every", "mla_swiglu", "swiglu_experts"])
def test_every_pattern_the_accepted_cells_build_keeps_its_tree(kw, block):
    """The fields this model added (``num_kv_heads``, ``head_dim``,
    ``rotary``, ``norm_eps``, ``ssm``, a half that is None, the experts'
    body) leave every older pattern's parameter tree as it was, name for
    name and shape for shape: two norms a layer, the same modules."""
    from horovod_tpu.models.mla import LatentAttentionConfig

    if kw.get("mla"):
        kw["mla"] = LatentAttentionConfig(
            kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8)
    if kw.get("experts"):
        kw["experts"] = experts_lib.ExpertShareConfig(
            n_routed_experts=4, experts_held=4, num_experts_per_tok=2,
            moe_d_ff=16, n_shared_experts=2)
    cfg = TransformerConfig(vocab_size=VOCAB, num_layers=1, num_heads=2,
                            d_model=16, d_ff=32, dtype=jnp.float32,
                            flash_attention=False, **kw)
    assert (cfg.num_kv_heads, cfg.head_dim, cfg.rotary, cfg.norm_eps,
            cfg.ssm) == (None, None, True, 1e-6, None)
    params = _init(cfg)
    assert set(params) == {"embed", "block_0", "RMSNorm_0", "lm_head"}
    assert _shapes(params["block_0"]) == block


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 2e-4),
                                        (jnp.bfloat16, 5e-2)],
                         ids=["f32", "bf16"])
def test_logits_match_reference(dtype, rtol):
    """In float32 every logit agrees. In bfloat16 a near-tie between the
    last expert chosen and the next can fall the other way, and the
    swapped expert's output is not small: the median token's error is
    held, not the worst's (as for the latent-attention model)."""
    params = _init(_config())
    tokens = _tokens(1)
    got = Transformer(_config(dtype)).apply({"params": params}, tokens)
    assert got.dtype == jnp.float32 and got.shape == (2, SEQ, VOCAB)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.forward(params, tokens, _arch())
    err = (np.linalg.norm(got - want, axis=-1)
           / np.linalg.norm(want, axis=-1))
    worst = err.max() if dtype == jnp.float32 else np.median(err)
    assert worst < rtol, err


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_train_step_matches_reference(hvd, dtype):
    """One step through ``make_lm_train_step`` and
    ``hvd.DistributedOptimizer``: the loss, and every gradient leaf (SGD
    at rate 1: old - new parameters), against the reference holding the
    same share, here experts 6..9 of 16. In float32 leaf by leaf. In
    bfloat16 the activations carry 8 bits and a few tokens choose another
    expert than the reference, so the whole gradient's distance over its
    norm is held (what the cell's ``grad_error`` reads), with the
    reference's experts under the program's own choices."""
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
    choices = None
    if dtype == jnp.bfloat16:
        _, kept = model.apply({"params": before}, tokens,
                              mutable=["intermediates"])
        zeros = jnp.zeros((8 * SEQ, 3), jnp.int32)
        choices = jnp.stack([
            kept["intermediates"][f"block_{i}"]["experts"]["chosen"][0]
            if kind == "E" else zeros for i, kind in enumerate(PATTERN)])
    with jax.default_matmul_precision("highest"):
        (want_loss, _), want = jax.value_and_grad(
            reference.loss, has_aux=True)(before, tokens, _arch(share),
                                          choices)
    bias = got["block_1"]["experts"]["e_score_correction_bias"]
    assert not np.any(bias)  # the selection bias receives no gradient
    assert np.any(got["block_1"]["experts"]["router"])
    if dtype == jnp.bfloat16:
        np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-3)
        norm = lambda tree: np.sqrt(sum(  # noqa: E731
            float(np.sum(np.square(x)))
            for x in jax.tree_util.tree_leaves(tree)))
        apart = norm(jax.tree_util.tree_map(np.subtract, got, want))
        assert apart / norm(want) < 0.1  # reads 0.068 here
        return
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        w = np.asarray(flat_want[path])
        np.testing.assert_allclose(
            g, w, atol=2e-5 + 2e-3 * float(np.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))


def _scan_inputs(rng, s, heads=8, groups=2, p=4, n=8, batch=2):
    f = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), jnp.float32)
    u, b, c = f(batch, s, heads, p), f(batch, s, groups, n), f(
        batch, s, groups, n)
    # steps of 0.05 to 1.5 and A from 0.5 to 16: the fastest heads forget
    # within a few positions, the slowest carry a state across every chunk
    dt = jnp.asarray(rng.uniform(0.05, 1.5, (batch, s, heads)), jnp.float32)
    a = -jnp.asarray(np.geomspace(0.02, 4.0, heads), jnp.float32)
    d = f(heads)
    return u, b, c, dt, a, d


def _recurrence(u, b, c, dt, a, d):
    share = u.shape[2] // b.shape[2]
    with jax.default_matmul_precision("highest"):
        return reference._recurrence(
            u, jnp.repeat(b, share, 2), jnp.repeat(c, share, 2), dt,
            dt * a, d)


@pytest.mark.parametrize("chunks", [1, 2, 5], ids=["one", "two", "several"])
def test_chunked_scan_equals_the_recurrence(rng, chunks):
    """The chunked form against one step at a time, with heads whose
    state lives far longer than a chunk: dropping what a chunk inherits
    moves the result by far more than the tolerance."""
    chunk = 8
    args = _scan_inputs(rng, chunks * chunk)
    got = ssm_lib.chunked_scan(*args, chunk)
    want = _recurrence(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
    if chunks > 1:
        # the same scan over each chunk alone, its state not carried
        alone = jnp.concatenate([
            ssm_lib.chunked_scan(*(x[:, i * chunk:(i + 1) * chunk]
                                   for x in args[:4]), *args[4:], chunk)
            for i in range(chunks)], 1)
        assert float(jnp.abs(alone - want)[:, chunk:].max()) > 0.1


def test_chunked_scan_gradient_equals_the_recurrences(rng):
    args = _scan_inputs(rng, 24)
    weight = jnp.asarray(rng.standard_normal((2, 24, 8, 4)), jnp.float32)
    got = jax.grad(lambda *x: jnp.sum(
        ssm_lib.chunked_scan(*x, 8) * weight), argnums=range(6))(*args)
    want = jax.grad(lambda *x: jnp.sum(_recurrence(*x) * weight),
                    argnums=range(6))(*args)
    for name, g, w in zip(("u", "B", "C", "dt", "A", "D"), got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w),
            atol=1e-4 + 2e-4 * float(jnp.abs(w).max()), err_msg=name)


def test_a_head_reads_its_group_against_a_written_out_loop(rng):
    """16 heads over 2 groups, 8 heads a group: head h reads B and C of
    group h // 8, in a loop over heads, positions and states."""
    s, heads, groups, p, n = 6, 16, 2, 2, 3
    u, b, c, dt, a, d = (np.asarray(x, np.float64) for x in _scan_inputs(
        rng, s, heads, groups, p, n, batch=1))
    want = np.zeros((s, heads, p))
    for h in range(heads):
        group, state = h // 8, np.zeros((p, n))
        for t in range(s):
            state = (np.exp(dt[0, t, h] * a[h]) * state + dt[0, t, h]
                     * np.outer(u[0, t, h], b[0, t, group]))
            want[t, h] = state @ c[0, t, group] + d[h] * u[0, t, h]
    got = ssm_lib.chunked_scan(*(jnp.asarray(x, jnp.float32)
                                 for x in (u, b, c, dt, a, d)), 3)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=1e-4)
    swapped = ssm_lib.chunked_scan(*(jnp.asarray(x, jnp.float32) for x in (
        u, b[:, :, ::-1], c[:, :, ::-1], dt, a, d)), 3)
    assert float(jnp.abs(swapped[0] - want).max()) > 0.1


def test_the_gate_comes_before_the_group_norm(rng):
    o, z = (jnp.asarray(rng.standard_normal((2, 5, 16)), jnp.float32)
            for _ in range(2))
    scale = jnp.asarray(rng.uniform(0.5, 2.0, 16), jnp.float32)
    got = ssm_lib.GatedGroupNorm(4, 1e-5, dtype=jnp.float32).apply(
        {"params": {"scale": scale}}, o, z)
    gated = np.asarray(o, np.float64) * np.asarray(jax.nn.silu(z))
    groups = gated.reshape(2, 5, 4, 4)
    want = (groups / np.sqrt((groups ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(2, 5, 16) * np.asarray(scale)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    # the other order is another function: norm, then gate
    normed = np.asarray(o, np.float64).reshape(2, 5, 4, 4)
    after = (normed / np.sqrt((normed ** 2).mean(-1, keepdims=True) + 1e-5)
             ).reshape(2, 5, 16) * np.asarray(scale) * np.asarray(
                 jax.nn.silu(z))
    assert np.abs(after - want).max() > 0.1
    np.testing.assert_allclose(
        np.asarray(reference._gated_norm(o, z, scale, 4)), want, atol=1e-5)


def test_grouped_query_attention_against_repeated_heads(rng):
    """4 query heads over 2 key/value heads against plain multi-head
    attention whose key and value kernels hold each shared head twice;
    no rotary: the result does not change when every position moves."""
    cfg = _config()
    y = jnp.asarray(rng.standard_normal((2, SEQ, D)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ))
    params = Attention(cfg).init(jax.random.PRNGKey(0), y, positions)[
        "params"]
    got = Attention(cfg).apply({"params": params}, y, positions)
    full = dataclasses.replace(cfg, num_kv_heads=None)
    repeated = {**params, **{name: {"kernel": jnp.repeat(
        params[name]["kernel"], 2, axis=1)} for name in ("key", "value")}}
    want = Attention(full).apply({"params": repeated}, y, positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    moved = Attention(cfg).apply({"params": params}, y, positions + 1000)
    np.testing.assert_allclose(np.asarray(moved), np.asarray(got), atol=1e-6)
    turned = Attention(dataclasses.replace(cfg, rotary=True)).apply(
        {"params": params}, y, positions)
    assert float(jnp.abs(turned - got).max()) > 1e-2
    # a written-out head: query head 3 reads key/value head 1
    q = np.asarray(y[0] @ params["query"]["kernel"][:, 3])
    k = np.asarray(y[0] @ params["key"]["kernel"][:, 1])
    v = np.asarray(y[0] @ params["value"]["kernel"][:, 1])
    scores = np.where(np.tril(np.ones((SEQ, SEQ), bool)),
                      q @ k.T / np.sqrt(8.0), -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    head = (probs / probs.sum(-1, keepdims=True)) @ v
    others = Attention(cfg).apply({"params": {**params, "out": {
        "kernel": params["out"]["kernel"].at[3].set(0.0)}}}, y, positions)
    np.testing.assert_allclose(
        np.asarray(got[0] - others[0]),
        head @ np.asarray(params["out"]["kernel"][3]), atol=1e-5)


def test_flash_path_broadcasts_the_shared_heads_into_the_kernel():
    """``flash_attention=True`` sends the 2 key/value heads, repeated
    over their query heads, through the kernel (interpret mode here) and
    agrees with the plain path, forward and gradient."""
    import warnings

    from horovod_tpu.ops.flash_attention import FlashFallbackWarning

    cfg = _config()
    params = _init(cfg)
    tokens = _tokens(4)
    loss = lambda c: lambda p: jnp.sum(jnp.square(  # noqa: E731
        Transformer(c).apply({"params": p}, tokens)))
    plain, plain_grad = jax.value_and_grad(loss(cfg))(params)
    with warnings.catch_warnings():
        warnings.simplefilter("error", FlashFallbackWarning)
        flash, flash_grad = jax.value_and_grad(loss(dataclasses.replace(
            cfg, flash_attention=True)))(params)
    np.testing.assert_allclose(float(flash), float(plain), rtol=1e-5)
    for name in ("query", "key", "value", "out"):
        a, b = (g["block_3"]["attn"][name]["kernel"]
                for g in (flash_grad, plain_grad))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4 * float(jnp.abs(b).max()))


def _layer_parts(share, params, y):
    """(routed part, shared part) of an expert layer for y [T, d]."""
    routed = experts_lib.ExpertShare(share, dtype=jnp.float32).apply(
        {"params": params["experts"]}, y)
    shared = experts_lib.shared_expert(
        share, dtype=jnp.float32, name=None).apply(
        {"params": params["shared_experts"]}, y)
    return routed, shared


def test_sixteen_shares_add_up_to_the_uncut_layer(rng):
    """The routed parts of all 16 shares of 8 experts each (128 experts,
    6 a token), with the shared expert counted once, add up to what the
    uncut reference gives for the whole layer."""
    whole_cfg = dataclasses.replace(
        EXPERTS, n_routed_experts=128, experts_held=128,
        num_experts_per_tok=6)
    params = _init(_config(experts=whole_cfg, pattern="E"))["block_0"]
    y = jnp.asarray(rng.standard_normal((40, D)), jnp.float32)
    arch = _arch(whole_cfg)
    total = 0.0
    for i in range(16):
        share = dataclasses.replace(whole_cfg, experts_held=8,
                                    expert_offset=8 * i)
        held = {**params["experts"], **{
            name: params["experts"][name][8 * i:8 * i + 8]
            for name in ("up_proj", "down_proj")}}
        routed, shared = _layer_parts(
            share, {"experts": held,
                    "shared_experts": params["shared_experts"]}, y)
        total = total + routed
        # each share alone agrees with the reference given that share
        with jax.default_matmul_precision("highest"):
            want, _ = reference._routed(held, y, _arch(share))
        np.testing.assert_allclose(np.asarray(routed), np.asarray(want),
                                   atol=2e-5)
    with jax.default_matmul_precision("highest"):
        whole, _ = reference._routed(params["experts"], y, arch)
        whole = whole + reference._shared(params["shared_experts"], y)
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(whole), atol=5e-5)
    # a shared expert is relu(y Wu)^2 Wd, written out
    up, down = (np.asarray(params["shared_experts"][name]["kernel"])
                for name in ("up_proj", "down_proj"))
    np.testing.assert_allclose(
        np.asarray(shared),
        np.maximum(np.asarray(y) @ up, 0.0) ** 2 @ down, atol=2e-5)


def test_no_slot_is_dropped_at_any_imbalance_under_relu2(rng):
    """Every token sent to the same held experts (a selection bias that
    outweighs every score): the two held experts each see all T tokens,
    and the result is still the reference's, row for row."""
    share = dataclasses.replace(EXPERTS, experts_held=2, expert_offset=4)
    params = _init(_config(experts=share, pattern="E"))["block_0"][
        "experts"]
    params = {**params, "e_score_correction_bias":
              jnp.zeros(16).at[jnp.asarray([4, 5, 9])].set(10.0)}
    y = jnp.asarray(rng.standard_normal((48, D)), jnp.float32)
    got, state = experts_lib.ExpertShare(share, dtype=jnp.float32).apply(
        {"params": params}, y, mutable=["intermediates"])
    idx = np.asarray(state["intermediates"]["chosen"][0])
    assert all(set(row) == {4, 5, 9} for row in idx)
    with jax.default_matmul_precision("highest"):
        want, _ = reference._routed(params, y, _arch(share))
    assert np.all(np.abs(np.asarray(want)).sum(-1) > 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_an_unknown_expert_body_is_refused():
    odd = dataclasses.replace(EXPERTS, expert_body="geglu")
    with pytest.raises(ValueError, match="unknown expert body 'geglu'"):
        experts_lib.ExpertShare(odd, dtype=jnp.float32).init(
            jax.random.PRNGKey(0), jnp.zeros((8, D)))


@pytest.mark.parametrize("case", ["ssm", "grouped_query", "no_mixer"])
def test_decode_is_refused_not_approximated(case):
    cfg = _config()
    y, positions = jnp.zeros((1, 8, D)), jnp.arange(8)[None]
    cache = (jnp.zeros((1, 4, 4, 8)), jnp.zeros((1, 4, 4, 8)),
             jnp.arange(4)[None])
    if case == "ssm":
        with pytest.raises(NotImplementedError, match="state cache"):
            ssm_lib.StateSpaceMixer(cfg).init(
                jax.random.PRNGKey(0), y, positions, False, cache)
    elif case == "grouped_query":
        with pytest.raises(NotImplementedError, match="grouped-query"):
            Attention(cfg).init(jax.random.PRNGKey(0), y, positions, False,
                                cache)
    else:
        with pytest.raises(NotImplementedError, match="without a mixer"):
            Block(cfg, None, "experts").init(
                jax.random.PRNGKey(0), y, positions, False, cache)


def test_multi_head_decode_still_works_at_equal_head_counts(rng):
    """The serving path (``kv_cache=``) of the default block: prefill
    through the cache equals the whole forward pass, as before."""
    cfg = TransformerConfig(vocab_size=VOCAB, num_layers=2, num_heads=2,
                            d_model=16, d_ff=32, dtype=jnp.float32,
                            flash_attention=False)
    tokens = _tokens(7, batch=1)[:, :8]
    params = Transformer(cfg).init(jax.random.PRNGKey(0), tokens)["params"]
    whole = Transformer(cfg).apply({"params": params}, tokens)
    empty = jnp.zeros((2, 1, 0, 2, 8))
    positions = jnp.arange(8)[None]
    logits, (k, v) = Transformer(cfg).apply(
        {"params": params}, tokens, positions=positions,
        kv_cache=(empty, empty, jnp.zeros((1, 0), jnp.int32)))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(whole),
                               atol=1e-5)
    assert k.shape == v.shape == (2, 1, 8, 2, 8)


def test_a_sequence_the_chunk_does_not_divide_raises():
    cfg = _config()
    with pytest.raises(ValueError, match="chunk_size 8 divides; got 12"):
        ssm_lib.StateSpaceMixer(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 12, D)))


def test_benchmark_reference_is_a_copy():
    """The benchmark's reference and this directory's are the same text
    (the yardstick keeps its own copy) and give the same numbers."""
    from benchmark.reference import ssm_moe_lm as copy

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
    """``loss_and_grad`` (a program a layer kind, one sequence at a time)
    gives the loss and the gradient of ``loss`` differentiated whole."""
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
    assert idx.shape == (3, len(PATTERN), SEQ, 3)
    np.testing.assert_array_equal(
        np.asarray(idx).transpose(1, 0, 2, 3).reshape(
            len(PATTERN), 3 * SEQ, 3), np.asarray(want_idx))


@pytest.fixture(scope="module")
def small_cell():
    """The benchmark family of ``nemotron-3-nano-30b-a3b-train-s4096``
    built at a small size on this machine's mesh, one step of it taken,
    and the sound reference's readings: what ``reference_check`` does, in
    its parts."""
    import json

    import horovod_tpu as hvd
    from benchmark.families import ssm_moe_lm as family

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        config = json.load(f)
    config.update(hidden_size=64, mamba_num_heads=8, mamba_head_dim=8,
                  n_groups=2, ssm_state_size=16, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16,
                  moe_intermediate_size=32,
                  moe_shared_expert_intermediate_size=64,
                  n_routed_experts=4, vocab_size=256,
                  num_experts_per_tok=3)
    config["deployment"]["router_width"] = 16
    config["assumed"]["flash_attention"] = False
    # four chunks of the published 128 positions: the cumulative log-decay
    # grows as far inside a chunk as it does in the cell
    traffic = {"per_chip_batch": 2, "seq_len": 512}
    hvd.shutdown()
    hvd.init()
    built = family.build(config, traffic, hvd.mesh(), 11)
    got = built.step_numbers()
    _, sound = built.compare(got, built.reference_numbers(got[2]))
    hvd.shutdown()
    return config, built, got, sound


def _faults():
    from benchmark.reference import ssm_moe_lm_faults
    return ssm_moe_lm_faults


def test_the_small_cell_agrees_with_its_reference(small_cell):
    _, _, got, sound = small_cell
    told = [name for name, r in sound.items()
            if name != "routing" and not r["agrees"]]
    assert not told, {name: sound[name] for name in told}
    assert len(sound["routing"]["apart_per_layer"]) == 4
    assert got[2].shape == (2, 9, 512, 3)  # two sequences, every layer
    # the finer readings are read, not judged
    for name in ("scan_grad_error", "attention_grad_error"):
        assert sound[name]["tolerance"] is None and sound[name]["agrees"]
        assert 0 < sound[name]["relative_error"] < 0.1


@pytest.mark.parametrize("fault", _faults().FAULTS)
def test_a_fault_in_the_reference_is_told_by_the_limits_that_tell_it(
        small_cell, fault):
    """Each fault of ``benchmark/reference/ssm_moe_lm_faults.py`` planted
    into the benchmark's reference, against the step the family took: the
    first limit that told it on the chip (``TOLD_BY``) tells it here; those
    that need the cell's size (``NEEDS_THE_CELLS_SIZE``) show here in a
    reading that rises well over its sound value; and what no limit told
    on the chip (the reference's own precision, bfloat16 operands, among
    them) leaves the scan's finer reading where it was."""
    config, built, got, sound = small_cell
    faults = _faults()
    with faults.planted(fault, config):
        agrees, report = built.compare(got, built.reference_numbers(got[2]))
    read = faults.readings(report)
    over = lambda name: (  # noqa: E731
        read[name] / sound[name]["relative_error"])
    if fault in faults.NEEDS_THE_CELLS_SIZE:
        assert over(faults.NEEDS_THE_CELLS_SIZE[fault]) > 1.5, read
    else:
        assert set(faults.TOLD_BY[fault][:1]) <= set(read["told_by"]), read
    assert agrees == (not read["told_by"])
    if fault in ("bfloat16_operands", "step_bfloat16",
                 "carried_state_bfloat16"):
        assert agrees and over("scan_grad_error") < 1.5, read
    # the fault came out again: the next call is sound
    assert faults.reference._route.__module__ == faults.reference.__name__
    assert faults.reference.MANTISSA_BITS is None
