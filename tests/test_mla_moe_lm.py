"""The DeepSeek-V3-type decoder (latent attention, a leading dense layer,
no-drop expert layers that hold a share, shared experts) against its plain
reference, at a small size on the CPU with seeded random weights.

``tests/reference_mla_moe_lm.py`` is the in-repo reference;
``benchmark/reference/mla_moe_lm.py`` is the benchmark's copy (the
yardstick may not move with the program), and one test holds the two to
the same numbers.
"""

import contextlib
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import reference_mla_moe_lm as reference
import reference_ssm_moe_lm as reference_ssm
from horovod_tpu import training
from horovod_tpu.models import experts as experts_lib
from horovod_tpu.models import mla as mla_lib
from horovod_tpu.models.transformer import Transformer, TransformerConfig

VOCAB, SEQ, LAYERS = 64, 16, 3
MLA = mla_lib.LatentAttentionConfig(kv_lora_rank=16, qk_nope_head_dim=8,
                                    qk_rope_head_dim=8, v_head_dim=8)
EXPERTS = experts_lib.ExpertShareConfig(
    n_routed_experts=16, experts_held=16, expert_offset=0,
    num_experts_per_tok=3, moe_d_ff=16, n_shared_experts=2,
    routed_scaling_factor=2.448, selection_bias_std=0.05)


def _config(dtype=jnp.float32, experts=EXPERTS, **kw):
    return TransformerConfig(
        vocab_size=VOCAB, num_layers=LAYERS, num_heads=2, d_model=32,
        d_ff=48, dtype=dtype, flash_attention=False, mla=MLA,
        experts=experts,
        layer_pattern=(("mla", "swiglu"),) + (("mla", "experts"),) * 2, **kw)


def _arch(experts=EXPERTS):
    return {"qk_nope_head_dim": MLA.qk_nope_head_dim,
            "kv_lora_rank": MLA.kv_lora_rank, "rope_theta": MLA.rope_theta,
            "num_experts_per_tok": experts.num_experts_per_tok,
            "routed_scaling_factor": experts.routed_scaling_factor,
            "expert_offset": experts.expert_offset}


def _tokens(seed=0, batch=2):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, VOCAB, size=(batch, SEQ)), jnp.int32)


def _init(cfg, seed=0):
    return Transformer(cfg).init(jax.random.PRNGKey(seed),
                                 _tokens())["params"]


def test_parameter_tree_is_the_published_modules():
    params = _init(_config())
    assert set(params) == {"embed", "block_0", "block_1", "block_2",
                           "RMSNorm_0", "lm_head"}
    assert set(params["block_0"]) == {"RMSNorm_0", "RMSNorm_1", "attn",
                                      "mlp"}
    assert set(params["block_1"]) == {"RMSNorm_0", "RMSNorm_1", "attn",
                                      "experts", "shared_experts"}
    assert set(params["block_1"]["attn"]) == {
        "q_proj", "kv_a_proj_with_mqa", "kv_a_layernorm", "kv_b_proj",
        "o_proj"}
    e = params["block_1"]["experts"]
    assert e["gate_proj"].shape == (16, 32, 16)
    assert e["down_proj"].shape == (16, 16, 32)
    assert e["router"].shape == (32, 16) and e["router"].dtype == jnp.float32
    assert float(jnp.std(e["e_score_correction_bias"])) > 0  # drawn


def test_default_pattern_builds_the_old_block():
    """``layer_pattern=None`` is the block the file has always built:
    the same parameter tree, names and shapes, MoE layers included."""
    cfg = TransformerConfig(vocab_size=VOCAB, num_layers=2, num_heads=2,
                            d_model=16, d_ff=32, dtype=jnp.float32,
                            moe_every=2, num_experts=4)
    assert cfg.layers() == (("mha", "gelu"), ("mha", "moe"))
    params = _init(cfg)
    assert set(params["block_0"]) == {"RMSNorm_0", "RMSNorm_1", "attn",
                                      "Dense_0", "Dense_1"}
    assert set(params["block_0"]["attn"]) == {"query", "key", "value",
                                              "out"}
    assert set(params["block_1"]) == {"RMSNorm_0", "RMSNorm_1", "attn",
                                      "moe"}
    with pytest.raises(ValueError, match="layer_pattern names 1 layers"):
        dataclasses.replace(cfg, layer_pattern=(("mha", "gelu"),)).layers()
    # what PR 31 added stays out of it: the key/value heads are the query
    # heads, rotary is on, the norms keep flax's eps, and the kernels are
    # the shapes they were
    assert (cfg.num_kv_heads, cfg.head_dim, cfg.rotary, cfg.norm_eps,
            cfg.ssm) == (None, None, True, 1e-6, None)
    assert {name: leaf["kernel"].shape
            for name, leaf in params["block_0"]["attn"].items()} == {
        "query": (16, 2, 8), "key": (16, 2, 8), "value": (16, 2, 8),
        "out": (2, 8, 16)}
    # a half that is None takes its norm with it and leaves the other
    # half's names alone
    one = _init(dataclasses.replace(
        cfg, moe_every=0, layer_pattern=(("mha", None), (None, "gelu"))))
    assert set(one["block_0"]) == {"RMSNorm_0", "attn"}
    assert set(one["block_1"]) == {"RMSNorm_0", "Dense_0", "Dense_1"}


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 2e-4),
                                        (jnp.bfloat16, 5e-2)],
                         ids=["f32", "bf16"])
def test_logits_match_reference(dtype, rtol):
    """In float32 every logit agrees. In bfloat16 a near-tie between the
    last expert chosen and the next can fall the other way, and the
    swapped expert's output is not small (a few of these 32 tokens do
    it): the median token's error is held, not the worst's."""
    params = _init(_config())
    tokens = _tokens(1)
    got = Transformer(_config(dtype)).apply(
        {"params": params}, tokens)
    assert got.dtype == jnp.float32 and got.shape == (2, SEQ, VOCAB)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.forward(params, tokens, _arch())
    err = (np.linalg.norm(got - want, axis=-1)
           / np.linalg.norm(want, axis=-1))
    worst = err.max() if dtype == jnp.float32 else np.median(err)
    assert worst < rtol, err


def test_under_the_programs_choices_every_token_agrees():
    """The reference with its experts evaluated under the choices the
    bfloat16 program made: no token passes through another expert, so the
    WORST token's logits agree, not only the median's; the reference's own
    choice still comes back, and differs for the tokens that made the
    median necessary above. Under its own choices nothing changes."""
    params = _init(_config())
    tokens = _tokens(1)
    got, kept = Transformer(_config(jnp.bfloat16)).apply(
        {"params": params}, tokens, mutable=["intermediates"])
    chosen = jnp.stack(
        [jnp.zeros((2 * SEQ, 3), jnp.int32)]
        + [kept["intermediates"][f"block_{i}"]["experts"]["chosen"][0]
           for i in (1, 2)])
    with jax.default_matmul_precision("highest"):
        free, own = reference.forward(params, tokens, _arch())
        want, own_under = reference.forward(params, tokens, _arch(), chosen)
        same, _ = reference.forward(params, tokens, _arch(), own)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(free))
    assert (np.sort(own[1:], -1) != np.sort(chosen[1:], -1)).any()
    np.testing.assert_array_equal(np.asarray(own_under[1]),
                                  np.asarray(own[1]))
    err = (np.linalg.norm(got - want, axis=-1)
           / np.linalg.norm(want, axis=-1))
    assert err.max() < 5e-2, err


@pytest.mark.parametrize("offset", [0, 6], ids=["first", "middle"])
def test_train_step_matches_reference(hvd, offset):
    """One step through ``make_lm_train_step`` and
    ``hvd.DistributedOptimizer``: the loss, and every gradient leaf (SGD
    at rate 1: old - new parameters), against the reference holding the
    same share, here 4 of 16 experts."""
    share = dataclasses.replace(EXPERTS, experts_held=4,
                                expert_offset=offset)
    cfg = _config(experts=share)
    model = Transformer(cfg)
    tx = hvd.DistributedOptimizer(optax.sgd(1.0), axes=("data",))
    tokens = _tokens(2, batch=8)
    state = training.create_train_state(model, tx, jax.random.PRNGKey(3),
                                        tokens)
    before = jax.tree_util.tree_map(np.asarray, state.params)
    step = training.make_lm_train_step(model, tx, mesh=hvd.mesh(),
                                       batch_axis="data", donate=False)
    after, loss = step(state, tokens)
    with jax.default_matmul_precision("highest"):
        (want_loss, _), want = jax.value_and_grad(
            reference.loss, has_aux=True)(before, tokens, _arch(share))
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    got = jax.tree_util.tree_map(lambda a, b: a - np.asarray(b), before,
                                 after.params)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        w = np.asarray(flat_want[path])
        np.testing.assert_allclose(
            g, w, atol=2e-5 + 2e-3 * float(np.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))
    bias = got["block_1"]["experts"]["e_score_correction_bias"]
    assert not np.any(bias)  # the selection bias receives no gradient
    assert np.any(got["block_1"]["experts"]["router"])


def _layer_parts(share, params, y):
    """(routed part, shared part) of block_1's feed-forward for y [T, d]."""
    routed = experts_lib.ExpertShare(share, dtype=jnp.float32).apply(
        {"params": params["experts"]}, y)
    shared = experts_lib.SwiGLU(
        share.n_shared_experts * share.moe_d_ff, dtype=jnp.float32).apply(
        {"params": params["shared_experts"]}, y)
    return routed, shared


def test_eight_shares_add_up_to_the_uncut_layer(rng):
    """The routed parts of all 8 shares of 2 experts each, with the shared
    experts counted once, add up to what the uncut reference gives for the
    whole layer."""
    params = _init(_config())["block_1"]
    y = jnp.asarray(rng.standard_normal((40, 32)), jnp.float32)
    total = 0.0
    for i in range(8):
        share = dataclasses.replace(EXPERTS, experts_held=2,
                                    expert_offset=2 * i)
        held = {**params["experts"], **{
            name: params["experts"][name][2 * i:2 * i + 2]
            for name in ("gate_proj", "up_proj", "down_proj")}}
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
        whole, _ = reference._routed(params["experts"], y, _arch())
        whole = whole + reference._shared(params["shared_experts"], y)
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(whole), atol=5e-5)


def test_no_slot_is_dropped_at_any_imbalance(rng):
    """Every token sent to the same held experts (a selection bias that
    outweighs every score): the two held experts each see all T tokens,
    T * k / n_routed_experts * 8 what balance would give them, and the
    result is still the reference's, row for row."""
    share = dataclasses.replace(EXPERTS, experts_held=2, expert_offset=4)
    params = _init(_config(experts=share))["block_1"]["experts"]
    params = {**params, "e_score_correction_bias":
              jnp.zeros(16).at[jnp.asarray([4, 5, 9])].set(10.0)}
    y = jnp.asarray(rng.standard_normal((48, 32)), jnp.float32)
    got, state = experts_lib.ExpertShare(share, dtype=jnp.float32).apply(
        {"params": params}, y, mutable=["intermediates"])
    idx = np.asarray(state["intermediates"]["chosen"][0])
    assert all(set(row) == {4, 5, 9} for row in idx)
    with jax.default_matmul_precision("highest"):
        want, _ = reference._routed(params, y, _arch(share))
    assert np.all(np.abs(np.asarray(want)).sum(-1) > 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_selection_bias_changes_the_choice_and_not_the_weights(rng):
    scores = jax.nn.sigmoid(jnp.asarray(rng.standard_normal((64, 16)),
                                        jnp.float32))
    bias = jnp.asarray(rng.standard_normal(16) * 0.1, jnp.float32)
    idx0, w0 = experts_lib.route(scores, jnp.zeros(16), 3, 2.448)
    idx, w = experts_lib.route(scores, bias, 3, 2.448)
    assert np.any(np.sort(idx, -1) != np.sort(idx0, -1))
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(w), chosen / chosen.sum(-1, keepdims=True) * 2.448,
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.448, rtol=1e-6)
    # where the bias left the choice alone (its order aside), the weights
    # are the same
    by_expert = lambda i, x: np.take_along_axis(  # noqa: E731
        np.asarray(x), np.argsort(np.asarray(i), -1), -1)
    same = np.all(np.sort(idx, -1) == np.sort(idx0, -1), -1)
    assert same.any() and not same.all()
    np.testing.assert_allclose(by_expert(idx, w)[same],
                               by_expert(idx0, w0)[same], rtol=1e-6)


def test_interleaved_rotary_against_a_written_out_loop(rng):
    x = rng.standard_normal((1, 5, 2, 8))
    theta, d = 1e6, 8
    want = np.zeros_like(x)
    for s in range(5):
        for h in range(2):
            for i in range(d // 2):
                angle = s * theta ** (-2 * i / d)
                a, b = x[0, s, h, 2 * i], x[0, s, h, 2 * i + 1]
                want[0, s, h, i] = a * np.cos(angle) - b * np.sin(angle)
                want[0, s, h, d // 2 + i] = (b * np.cos(angle)
                                             + a * np.sin(angle))
    positions = jnp.arange(5)[None]
    got = mla_lib.rotary_interleaved(jnp.asarray(x, jnp.float32),
                                     positions, theta)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(reference._rope_interleaved(
            jnp.asarray(x, jnp.float32), theta)), want, atol=1e-5)


def test_one_rotary_key_is_shared_by_all_heads(rng):
    """The attention module against a loop over heads in which every head
    scores its ``q_pe`` against the SAME rotated ``k_pe``."""
    cfg = _config()
    params = _init(cfg)["block_1"]["attn"]
    y = jnp.asarray(rng.standard_normal((1, SEQ, 32)), jnp.float32)
    positions = jnp.arange(SEQ)[None]
    got = mla_lib.LatentAttention(cfg).apply({"params": params}, y,
                                             positions)
    nope, rank = MLA.qk_nope_head_dim, MLA.kv_lora_rank
    yn = np.asarray(y[0], np.float64)
    latent = yn @ np.asarray(params["kv_a_proj_with_mqa"]["kernel"])
    c = latent[:, :rank]
    c = (c / np.sqrt((c * c).mean(-1, keepdims=True) + 1e-6)
         * np.asarray(params["kv_a_layernorm"]["scale"]))
    rot = lambda a: np.asarray(mla_lib.rotary_interleaved(  # noqa: E731
        jnp.asarray(a[None, :, None, :], jnp.float32), positions,
        1e6))[0, :, 0]
    k_pe = rot(latent[:, rank:])  # one key for every head
    out = np.zeros((SEQ, 32))
    mask = np.tril(np.ones((SEQ, SEQ), bool))
    for h in range(2):
        q = yn @ np.asarray(params["q_proj"]["kernel"])[:, h]
        kv = c @ np.asarray(params["kv_b_proj"]["kernel"])[:, h]
        scores = (q[:, :nope] @ kv[:, :nope].T
                  + rot(q[:, nope:]) @ k_pe.T) / np.sqrt(16.0)
        scores = np.where(mask, scores, -np.inf)
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        out += (probs @ kv[:, nope:]) @ np.asarray(
            params["o_proj"]["kernel"])[h]
    np.testing.assert_allclose(np.asarray(got[0]), out, atol=2e-5)


def test_flash_path_runs_the_kernel_at_two_head_sizes():
    """``flash_attention=True`` sends q, k at 16 and v at 8 through the
    kernel (interpret mode here) and agrees with the plain path."""
    cfg = _config()
    params = _init(cfg)
    tokens = _tokens(4)
    plain = Transformer(cfg).apply({"params": params}, tokens)
    import warnings

    from horovod_tpu.ops.flash_attention import FlashFallbackWarning
    with warnings.catch_warnings():
        warnings.simplefilter("error", FlashFallbackWarning)
        flash = Transformer(dataclasses.replace(
            cfg, flash_attention=True)).apply({"params": params}, tokens)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(plain),
                               atol=2e-4)


def test_decode_is_refused_not_approximated():
    cfg = _config()
    with pytest.raises(NotImplementedError, match="latent cache"):
        mla_lib.LatentAttention(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4, 32)),
            jnp.arange(4)[None], False, (None, None, None))


def test_benchmark_reference_is_a_copy():
    """The benchmark's reference and this directory's give the same
    numbers (they are the same text: the yardstick keeps its own copy)."""
    from benchmark.reference import mla_moe_lm as copy

    share = dataclasses.replace(EXPERTS, experts_held=4, expert_offset=8)
    params = _init(_config(experts=share))
    tokens = _tokens(5)
    a = reference.loss_and_grad(params, tokens, _arch(share))
    b = copy.loss_and_grad(params, tokens, _arch(share))
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    strip = lambda m: open(m.__file__).read()  # noqa: E731
    assert strip(reference) == strip(copy)


def test_blockwise_gradient_is_the_whole_functions():
    """``loss_and_grad`` (blocks, one sequence at a time) gives the loss
    and the gradient of ``loss`` differentiated whole."""
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
    assert idx.shape == (3, LAYERS, SEQ, 3)
    np.testing.assert_array_equal(
        np.asarray(idx).transpose(1, 0, 2, 3).reshape(LAYERS, 3 * SEQ, 3),
        np.asarray(want_idx))


def test_a_grouped_product_that_falls_back_on_the_tpu_says_so(
        rng, monkeypatch):
    """Off the TPU ``ragged_dot`` is the path and says nothing; on it,
    sizes megablox's tiles do not divide run ``ragged_dot`` with a
    ``GroupedFallbackWarning`` naming them, which the benchmark family and
    ``chip_smoke.py`` turn into an error."""
    import warnings

    xs = jnp.asarray(rng.standard_normal((96, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, 32, 16)), jnp.float32)
    sizes = jnp.asarray([10, 0, 50, 20], jnp.int32)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        quiet = experts_lib.grouped_matmul(xs, w, sizes)
    monkeypatch.setattr(jax, "devices", lambda *a: [
        type("Tpu", (), {"platform": "tpu"})()])
    with pytest.warns(experts_lib.GroupedFallbackWarning,
                      match=r"\(96, 32\) x \(4, 32, 16\)"):
        loud = experts_lib.grouped_matmul(xs, w, sizes)
    np.testing.assert_array_equal(np.asarray(loud), np.asarray(quiet))
    ends = np.cumsum(np.asarray(sizes))
    for g, (lo, hi) in enumerate(zip(ends - np.asarray(sizes), ends)):
        np.testing.assert_allclose(np.asarray(quiet[lo:hi]),
                                   np.asarray(xs[lo:hi] @ w[g]), rtol=1e-5)


@pytest.mark.parametrize("rows", [24, 12], ids=["every_slot", "bounded"])
def test_rows_past_the_groups_may_hold_anything(rng, rows):
    """The grouped products never visit the rows past the groups' end, on
    the TPU what those rows hold is undefined: NaN there, in the experts'
    output and in the gradient that comes back to the dispatch, reaches
    neither the result nor a gradient. So with buffers of every slot, and
    so with buffers of fewer rows than there are slots, where the two
    gathers back into token order meet indices past the buffer: a dead
    slot reads the buffer's last row (NaN here) and is masked, and no
    live slot reads it."""
    t, k, d, total = 8, 3, 4, 10
    local = jnp.asarray(rng.permutation(np.repeat([0, 1, 2], t * k // 3)))
    whole_order = jnp.argsort(local, stable=True)
    inverse = jnp.argsort(whole_order)
    order = whole_order[:rows]
    w = jnp.asarray(rng.random((k, t)), jnp.float32)
    out = jnp.asarray(rng.standard_normal((t * k, d)), jnp.float32)
    dirty = out[:rows].at[total:].set(jnp.nan)
    clean = out.at[total:].set(0.0)
    want = np.einsum("jtd,jt->td",
                     np.asarray(clean)[np.asarray(inverse)].reshape(k, t, d),
                     np.asarray(w))
    got, vjp = jax.vjp(lambda o, w: experts_lib._combine(
        o, w, order, inverse, total), dirty, w)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    g = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    d_out, d_w = vjp(g)
    assert d_out.shape == (rows, d) and np.isfinite(np.asarray(d_out)).all()
    want_vjp = jax.vjp(lambda o, w: jnp.einsum(
        "jtd,jt->td", o[inverse].reshape(k, t, d), w), clean, w)[1](g)
    np.testing.assert_allclose(np.asarray(d_w), np.asarray(want_vjp[1]),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(d_out),
                               np.asarray(want_vjp[0])[:rows], rtol=1e-5)
    # the dispatch's backward: a token sums its live slots' rows only
    y = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    xs, vjp = jax.vjp(lambda y: experts_lib._dispatch(
        y, order, inverse, total), y)
    np.testing.assert_array_equal(np.asarray(xs),
                                  np.asarray(y)[np.asarray(order) % t])
    g = jnp.asarray(rng.standard_normal((t * k, d)), jnp.float32)
    (d_y,) = vjp(g[:rows].at[total:].set(jnp.nan))
    live = np.where((np.asarray(inverse) < total)[:, None],
                    np.asarray(g)[np.asarray(inverse)], 0.0)
    np.testing.assert_allclose(np.asarray(d_y),
                               live.reshape(k, t, d).sum(0), rtol=1e-5)


# Shapes at which the expert-order buffers are smaller than the slots: 640
# tokens x 3 choices = 1920 slots; with 1 of 16 experts held 120 are
# expected, with 2 held 240, and ``held_rows`` is 512 both times (a
# megablox row tile above HELD_ROOM times as many).
BOUNDED = dataclasses.replace(EXPERTS, experts_held=1, expert_offset=4)
BOUNDED_T = 640
# the experts a selection bias sends every token to (None: no bias),
# how many of them the share holds from expert 4 on, and the held slots
# that gives: about 120 of 512 rows; 640, past 512 rows (two windows);
# 1280 (three windows, and the 1920 slots end in the middle of the fourth)
CROWDS = {"under": (None, 1, None), "over": ((4, 9, 10), 1, 640),
          "over_ragged": ((4, 5, 9), 2, 1280)}


def _bounded_share(body, rng, crowd):
    """``(share, parameters, y, rows)`` of a share whose buffers hold
    fewer rows than the 1920 slots, under ``CROWDS[crowd]``."""
    chosen, held, _ = CROWDS[crowd]
    share = dataclasses.replace(BOUNDED, expert_body=body, experts_held=held)
    y = jnp.asarray(rng.standard_normal((BOUNDED_T, 32)), jnp.float32)
    params = dict(experts_lib.ExpertShare(share, dtype=jnp.float32).init(
        jax.random.PRNGKey(1), y)["params"])
    if chosen:
        params["e_score_correction_bias"] = jnp.zeros(16).at[
            jnp.asarray(chosen)].set(10.0)
    rows = experts_lib.held_rows(BOUNDED_T * 3, share)
    assert rows == 512 and (BOUNDED_T * 3) % rows == 384
    return share, params, y, rows


def _share_and_gradients(share, params, y, g):
    """The share's output for ``y`` and the gradients of ``sum(out * g)``
    by ``y`` and every parameter, with the held slots of the step."""
    def run(y, params):
        out, state = experts_lib.ExpertShare(share, dtype=jnp.float32).apply(
            {"params": params}, y, mutable=["intermediates"])
        idx = state["intermediates"]["chosen"][0]
        held = jnp.sum((idx >= share.expert_offset)
                       & (idx < share.expert_offset + share.experts_held))
        return jnp.sum(out * g), (out, held)

    (_, (out, held)), grads = jax.jit(jax.value_and_grad(
        run, argnums=(0, 1), has_aux=True))(y, params)
    return out, grads, int(held)


@pytest.fixture
def overflow_branch_runs(monkeypatch):
    """A mark for every time the share's way out ran on the device: a
    callback traced into the ``hvd_moe_overflow`` scope."""
    runs = []
    device = experts_lib.scopes.device

    @contextlib.contextmanager
    def counting(name):
        with device(name):
            if name == experts_lib.scopes.MOE_OVERFLOW:
                jax.debug.callback(lambda: runs.append(1))
            yield

    monkeypatch.setattr(experts_lib.scopes, "device", counting)
    return runs


@pytest.mark.parametrize("crowd", sorted(CROWDS))
@pytest.mark.parametrize("body", ["swiglu", "relu2"])
def test_bounded_buffers_give_what_full_size_buffers_give(
        rng, monkeypatch, overflow_branch_runs, body, crowd):
    """Under the bound the share runs through buffers of ``held_rows``
    rows once; past it the way out runs, forward and backward once each,
    a window of the expert order at a time. Output and every gradient are
    what a share whose buffers hold every slot gives, and the output the
    reference's."""
    share, params, y, rows = _bounded_share(body, rng, crowd)
    g = jnp.asarray(rng.standard_normal(y.shape), jnp.float32)
    out, grads, held = _share_and_gradients(share, params, y, g)
    jax.effects_barrier()
    crowded = CROWDS[crowd][2]
    assert held == crowded if crowded else 0 < held <= rows
    assert len(overflow_branch_runs) == (2 if held > rows else 0)
    with monkeypatch.context() as m:
        m.setattr(experts_lib, "HELD_ROOM", 16)  # every slot, held or not
        assert experts_lib.held_rows(BOUNDED_T * 3, share) == BOUNDED_T * 3
        want, want_grads, _ = _share_and_gradients(share, params, y, g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    flat, flat_want = (jax.tree_util.tree_leaves_with_path(t)
                       for t in (grads, want_grads))
    assert len(flat) == (6 if body == "swiglu" else 5)
    for (path, got_leaf), (_, want_leaf) in zip(flat, flat_want):
        np.testing.assert_allclose(
            np.asarray(got_leaf), np.asarray(want_leaf), rtol=1e-5,
            atol=1e-6 * float(jnp.abs(want_leaf).max()) + 1e-12,
            err_msg=jax.tree_util.keystr(path))
    assert not np.any(grads[1]["e_score_correction_bias"])
    assert np.any(grads[1]["router"]) and np.any(grads[0])
    plain_reference = reference if body == "swiglu" else reference_ssm
    with jax.default_matmul_precision("highest"):
        plain, _ = plain_reference._routed(params, y, _arch(share))
    np.testing.assert_allclose(np.asarray(out), np.asarray(plain), atol=2e-5)


def _share_gradient(share, t):
    """``(value and gradient of the share's summed output by y and the
    parameters, y, the parameters' shapes)`` for ``t`` tokens."""
    y = jnp.zeros((t, 32), jnp.float32)
    module = experts_lib.ExpertShare(share, dtype=jnp.float32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0), y)["params"]
    return jax.value_and_grad(
        lambda y, p: jnp.sum(module.apply({"params": p}, y)),
        argnums=(0, 1)), y, params


def _share_gradient_jaxpr(share, t):
    fn, y, params = _share_gradient(share, t)
    return jax.make_jaxpr(fn)(y, params)


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations call."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def test_a_share_that_holds_every_expert_has_no_conditional():
    """Where the buffers' bound is every slot there is one program, not a
    choice of two: no ``cond`` in the jaxpr, no conditional in what it
    lowers to; a share of an eighth has one each way."""
    def lowered(share):
        fn, y, params = _share_gradient(share, BOUNDED_T)
        text = jax.jit(fn).lower(y, params).as_text()
        return text.count("stablehlo.case") + text.count("stablehlo.if")

    conds = lambda share: sum(  # noqa: E731
        eqn.primitive.name == "cond" for eqn in _equations(
            _share_gradient_jaxpr(share, BOUNDED_T).jaxpr))
    assert conds(EXPERTS) == 0 and lowered(EXPERTS) == 0
    # the conditional each way, and the loop over windows in its way out
    assert conds(BOUNDED) == 2 and lowered(BOUNDED) >= 2


@pytest.mark.parametrize("body", ["swiglu", "relu2"])
def test_neither_direction_holds_an_array_of_every_slot(body):
    """In both branches, forward and backward, nothing has a row a slot
    but the index vectors and what the gathers back into token order give
    (``_live``: a gather, and the select that puts a scalar zero in its
    dead rows): no buffer in expert order, and no zeros handed back for
    another branch's residuals, which would be a result of the branch.
    The buffers in expert order are ``held_rows`` long in both, and the
    way out is a loop over windows."""
    share = dataclasses.replace(BOUNDED, expert_body=body)
    slots, rows = BOUNDED_T * 3, 512
    assert experts_lib.held_rows(slots, share) == rows
    jaxpr = _share_gradient_jaxpr(share, BOUNDED_T).jaxpr
    conds = [eqn for eqn in _equations(jaxpr)
             if eqn.primitive.name == "cond"]
    assert len(conds) == 2

    def wide(branch):
        """(primitive, shapes it reads) of every equation of ``branch``
        that makes a float array with a row a slot."""
        return [(eqn.primitive.name,
                 [v.aval.shape for v in eqn.invars if hasattr(v, "aval")])
                for eqn in _equations(branch.jaxpr) for v in eqn.outvars
                if v.aval.ndim > 1 and v.aval.shape[0] == slots
                and jnp.issubdtype(v.aval.dtype, jnp.inexact)]

    for cond in conds:
        bounded, way_out = cond.params["branches"]
        for branch in (bounded, way_out):
            assert all(v.aval.shape[0] != slots
                       for v in branch.jaxpr.outvars)
            made = wide(branch)
            assert {name for name, _ in made} <= {
                "gather", "select_n", "jit", "pjit", "broadcast_in_dim"}
            # a gather back reads a buffer of ``rows`` rows; what is
            # broadcast is the scalar zero of a mask
            gathers = [reads for name, reads in made if name == "gather"]
            assert gathers and all(reads[0][0] == rows for reads in gathers)
            assert all(reads == [()] for name, reads in made
                       if name == "broadcast_in_dim")
            # the grouped products run on ``rows`` rows
            products = [v.aval.shape for eqn in _equations(branch.jaxpr)
                        if eqn.primitive.name.startswith("ragged_dot")
                        for v in eqn.outvars if v.aval.ndim == 2]
            assert products and all(shape[0] == rows for shape in products)
        names = lambda branch: {  # noqa: E731
            eqn.primitive.name for eqn in _equations(branch.jaxpr)}
        assert "while" in names(way_out) and "while" not in names(bounded)


@pytest.fixture(scope="module")
def small_cell():
    """The benchmark family of ``kanana-2-30b-a3b-train-s4096`` built at a
    small size on this machine's mesh, one step of it taken, and the sound
    reference's readings: what ``reference_check`` does, in its parts."""
    import json

    import horovod_tpu as hvd
    from benchmark.families import mla_moe_lm as family

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs",
                           "kanana-2-30b-a3b.json")) as f:
        config = json.load(f)
    config.update(hidden_size=64, num_attention_heads=2, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
                  intermediate_size=128, moe_intermediate_size=32,
                  n_routed_experts=4, vocab_size=256, num_hidden_layers=3,
                  num_experts_per_tok=3)
    config["deployment"]["router_width"] = 16
    config["assumed"]["flash_attention"] = False
    traffic = {"per_chip_batch": 2, "seq_len": 128}
    hvd.shutdown()
    hvd.init()
    built = family.build(config, traffic, hvd.mesh(), 3000000001)
    got = built.step_numbers()
    _, sound = built.compare(got, built.reference_numbers(got[2]))
    hvd.shutdown()
    return config, built, got, sound


def _faults():
    from benchmark.reference import mla_moe_lm_faults
    return mla_moe_lm_faults


def test_the_small_cell_agrees_with_its_reference(small_cell):
    _, _, got, sound = small_cell
    told = [name for name, r in sound.items()
            if name != "routing" and not r["agrees"]]
    assert not told, {name: sound[name] for name in told}
    assert len(sound["routing"]["apart_per_layer"]) == 2
    assert got[2].shape == (2, 3, 128, 3)  # two sequences, every layer


@pytest.mark.parametrize("fault", _faults().FAULTS)
def test_a_fault_in_the_reference_is_told_by_the_limits_that_tell_it(
        small_cell, fault):
    """Each fault of ``benchmark/reference/mla_moe_lm_faults.py`` planted
    into the benchmark's reference, against the step the family took: the
    first limit that told it on the chip (``TOLD_BY``) tells it here (the
    others need the cell's depth: two sparse layers compound less than
    four), and the reference's own precision, bfloat16 operands, is told
    by none."""
    config, built, got, _ = small_cell
    faults = _faults()
    with faults.planted(fault, config):
        agrees, report = built.compare(got, built.reference_numbers(got[2]))
    read = faults.readings(report)
    assert set(faults.TOLD_BY[fault][:1]) <= set(read["told_by"]), read
    assert agrees == (not read["told_by"])
    if fault == "bfloat16_operands":
        assert agrees, read
    # the fault came out again: the next call is sound
    assert faults.reference._route.__module__ == faults.reference.__name__
    assert faults.reference.MANTISSA_BITS is None
