"""The learned-sparse-attention decoder with sparse experts (grouped-query
attention with a norm on each head of q and k and a lightning indexer a
layer that chooses each query's keys and learns from its own KL loss; a
softmax router over SwiGLU experts that hold a share, no shared expert)
against its plain reference, at a small size on the CPU with seeded random
weights; and what the new options leave of the models that do not use
them.

``tests/reference_dsa_moe_lm.py`` is the in-repo reference;
``benchmark/reference/dsa_moe_lm.py`` is the benchmark's copy (the
yardstick may not move with the program), and one test holds the two to
the same text.
"""

import copy
import dataclasses
import hashlib
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import reference_dsa_moe_lm as reference
from horovod_tpu import training
from horovod_tpu.models import experts as experts_lib
from horovod_tpu.models.sparse_attention import LOSSES, IndexerConfig
from horovod_tpu.models.transformer import (Attention, AttentionConfig,
                                            Transformer, TransformerConfig)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, SEQ, D = 64, 96, 32
INDEXER = IndexerConfig(num_heads=2, head_dim=8, top_k=24, rotary_dim=4)
SPARSE = AttentionConfig(kind="sparse", num_heads=4, head_dim=8,
                         num_kv_heads=2, rope_theta=1e7, qk_norm=True,
                         indexer=INDEXER)
EXPERTS = experts_lib.ExpertShareConfig(
    n_routed_experts=16, experts_held=16, expert_offset=0,
    num_experts_per_tok=4, moe_d_ff=24, n_shared_experts=0,
    routed_scaling_factor=1.0, router="softmax")


def _config(dtype=jnp.float32, experts=EXPERTS, layers=2, attention=SPARSE,
            **kw):
    kw = {"flash_attention": False, **kw}
    return TransformerConfig(
        vocab_size=VOCAB, num_layers=layers, num_heads=4, d_model=D,
        d_ff=48, dtype=dtype, norm_eps=1e-6, attention=(attention,),
        experts=experts, layer_pattern=((attention.kind, "experts"),) * layers,
        **kw)


def _arch(experts=EXPERTS, indexer=INDEXER):
    return {"rope_theta": 1e7, "index_top_k": indexer.top_k,
            "index_rotary_dim": indexer.rotary_dim,
            "num_experts_per_tok": experts.num_experts_per_tok,
            "expert_offset": experts.expert_offset}


def _tokens(seed=0, batch=2):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, VOCAB, size=(batch, SEQ)), jnp.int32)


def _init(cfg, seed=0):
    return Transformer(cfg).init(jax.random.PRNGKey(seed),
                                 _tokens())["params"]


def _observed(model, params, tokens):
    """``(L_I [L], choices [L, B*S, k], selections [L, B, rows, S])`` of
    the model's forward pass."""
    _, kept = model.apply({"params": params}, tokens,
                          mutable=["intermediates", LOSSES])
    blocks = sorted(kept[LOSSES], key=lambda n: int(n.split("_")[1]))
    return (jnp.stack([kept[LOSSES][n]["attn"]["indexer_kl"][0]
                       for n in blocks]),
            jnp.stack([kept["intermediates"][n]["experts"]["chosen"][0]
                       for n in blocks]),
            jnp.stack([kept["intermediates"][n]["attn"]["selection"][0]
                       for n in blocks]))


def test_an_indexed_layer_has_the_indexer_and_the_norms_and_no_more():
    params = _init(_config())
    assert set(params["block_0"]) == {"RMSNorm_0", "RMSNorm_1", "attn",
                                      "experts"}
    shapes = jax.tree_util.tree_map(lambda a: tuple(a.shape),
                                    params["block_0"]["attn"])
    assert shapes == {
        "query": {"kernel": (D, 4, 8)}, "key": {"kernel": (D, 2, 8)},
        "value": {"kernel": (D, 2, 8)}, "out": {"kernel": (4, 8, D)},
        "query_norm": {"scale": (8,)}, "key_norm": {"scale": (8,)},
        "indexer": {"wq": {"kernel": (D, 2, 8)}, "wk": {"kernel": (D, 8)},
                    "k_norm": {"scale": (8,), "bias": (8,)},
                    "weights_proj": {"kernel": (D, 2)}}}
    # the softmax router has no selection bias
    assert set(params["block_0"]["experts"]) == {
        "gate_proj", "up_proj", "down_proj", "router"}


def test_the_cells_sizes_count_the_parameters_the_file_states():
    """The configuration at its published widths through
    ``jax.eval_shape``: 562,290,560 parameters, as the file states and as
    the family counts them."""
    from benchmark.families import dsa_moe_lm as family

    config = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "keye-vl-2.0-30b-a3b.json")))
    cfg, _ = family.model_config(config, flash_attention=False)
    shapes = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))["params"]
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    assert count == config["parameters"] == family.parameters(config)
    attn = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        shapes["block_0"]["attn"]))
    assert attn == 18_874_368 + 256 + 2_261_120


def test_train_step_matches_reference(hvd):
    """One step through ``make_lm_train_step`` and
    ``hvd.DistributedOptimizer`` (SGD at rate 1: old - new parameters is
    the gradient) in float32 against the reference holding the same share,
    experts 6..9 of 16, under the program's selections and choices: the
    loss is L_LM + the layers' L_I, and every gradient leaf agrees."""
    share = dataclasses.replace(EXPERTS, experts_held=4, expert_offset=6)
    cfg = _config(experts=share)
    model = Transformer(cfg)
    tx = hvd.DistributedOptimizer(optax.sgd(1.0), axes=("data",))
    tokens = _tokens(2, batch=hvd.mesh().devices.size)
    state = training.create_train_state(model, tx, jax.random.PRNGKey(3),
                                        tokens)
    before = jax.tree_util.tree_map(np.asarray, state.params)
    kls, chosen, selections = _observed(model, before, tokens)
    step = training.make_lm_train_step(model, tx, mesh=hvd.mesh(),
                                       batch_axis="data", donate=False)
    after, loss = step(state, tokens)
    got = jax.tree_util.tree_map(lambda a, b: a - np.asarray(b), before,
                                 after.params)
    with jax.default_matmul_precision("highest"):
        (want_loss, (lm, want_kls, own)), want = jax.value_and_grad(
            reference.loss, has_aux=True)(before, tokens, _arch(share),
                                          chosen, selections)
    assert float(jnp.sum(want_kls)) > 0.01
    np.testing.assert_allclose(np.asarray(kls), np.asarray(want_kls),
                               rtol=1e-4)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    # float32 on both sides: a choice differs only where two scores tie
    # to their last bits
    apart = np.mean(np.sort(np.asarray(chosen), -1)
                    != np.sort(np.asarray(own), -1))
    assert apart < 1e-3
    # the step chooses again in its own program (a shard_map over one
    # sequence a device), where a key whose score ties another's to the
    # last bits may go the other way and move that query's gradient: the
    # whole gradient's distance is held here, and the model's gradient
    # leaf by leaf below
    norm = lambda tree: np.sqrt(sum(  # noqa: E731
        float(np.sum(np.square(x))) for x in jax.tree_util.tree_leaves(tree)))
    apart = norm(jax.tree_util.tree_map(np.subtract, got, want))
    assert apart / norm(want) < 5e-3


@pytest.mark.parametrize("spmd", [False, True], ids=["explicit", "spmd"])
def test_a_moe_every_models_step_adds_no_auxiliary_term(hvd, spmd):
    """``models/moe.py``'s layer sows its auxiliary terms into
    ``"losses"``; the LM step builders add the indexer's collection alone,
    so a ``moe_every`` model's step loss and gradient are the next-token
    loss's and nothing more."""
    cfg = TransformerConfig(vocab_size=VOCAB, num_layers=2, num_heads=4,
                            d_model=16, d_ff=32, dtype=jnp.float32,
                            moe_every=2, num_experts=4)
    model = Transformer(cfg)
    tx = hvd.DistributedOptimizer(optax.sgd(1.0), axes=("data",))
    tokens = _tokens(5, batch=hvd.mesh().devices.size)[:, :16]
    state = training.create_train_state(model, tx, jax.random.PRNGKey(1),
                                        tokens)
    before = jax.tree_util.tree_map(np.asarray, state.params)
    _, sown = model.apply({"params": before}, tokens, mutable=["losses"])
    assert jax.tree_util.tree_leaves(sown["losses"])

    def plain(p, tokens):
        ll = jax.nn.log_softmax(model.apply({"params": p}, tokens)[:, :-1])
        return -jnp.mean(jnp.take_along_axis(ll, tokens[:, 1:, None], -1))

    # the capacity dispatch sizes its buffers from the tokens it is given:
    # the explicit path applies the model to a device's sequence, GSPMD to
    # the whole batch
    shards = [tokens] if spmd else jnp.split(tokens, tokens.shape[0])
    want_loss, want = jax.value_and_grad(lambda p: sum(
        plain(p, t) for t in shards) / len(shards))(before)

    step = training.make_lm_train_step(model, tx, mesh=hvd.mesh(),
                                       batch_axis="data", donate=False,
                                       spmd=spmd)
    after, loss = step(state, tokens)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    got = jax.tree_util.tree_map(lambda a, b: a - np.asarray(b), before,
                                 after.params)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w),
            atol=1e-6 + 1e-4 * float(np.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))


def test_the_models_gradient_is_the_references_leaf_by_leaf():
    """The gradient of L_LM + the layers' L_I through the model's own
    forward pass (the program that gives the selections and choices) is
    the reference's under them, in float32, leaf by leaf."""
    share = dataclasses.replace(EXPERTS, experts_held=4, expert_offset=6)
    model = Transformer(_config(experts=share))
    params = _init(model.cfg, seed=3)
    tokens = _tokens(2, batch=4)
    _, chosen, selections = _observed(model, params, tokens)

    def loss(p):
        logits, sown = model.apply({"params": p}, tokens, mutable=[LOSSES])
        ll = jax.nn.log_softmax(logits[:, :-1], -1)
        lm = -jnp.mean(jnp.take_along_axis(ll, tokens[:, 1:, None], -1))
        return lm + sum(jax.tree_util.tree_leaves(sown[LOSSES]))

    got = jax.grad(loss)(params)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: reference.loss(
            p, tokens, _arch(share), chosen, selections)[0])(params)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        w = np.asarray(flat_want[path])
        np.testing.assert_allclose(
            np.asarray(g), w, atol=2e-6 + 1e-4 * float(np.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))


def _indexer_leaves(tree):
    return [(jax.tree_util.keystr(p), a) for p, a in
            jax.tree_util.tree_leaves_with_path(tree)]


def test_each_loss_reaches_its_own_parameters_alone():
    """L_LM reaches no indexer parameter (a top-k passes no gradient to
    the scores); the layers' L_I reach nothing but the indexer (its input
    is detached and the probabilities it learns from are held); together
    both are non-zero where they should be."""
    cfg = _config()
    model = Transformer(cfg)
    params = _init(cfg, seed=4)
    tokens = _tokens(4)

    def parts(p):
        logits, sown = model.apply({"params": p}, tokens,
                                   mutable=[LOSSES])
        ll = jax.nn.log_softmax(logits[:, :-1], -1)
        lm = -jnp.mean(jnp.take_along_axis(ll, tokens[:, 1:, None], -1))
        return lm, sum(jax.tree_util.tree_leaves(sown[LOSSES]))

    d_lm = jax.grad(lambda p: parts(p)[0])(params)
    d_index = jax.grad(lambda p: parts(p)[1])(params)
    for name, g in _indexer_leaves(d_lm):
        assert ("['indexer']" in name) == (not np.any(np.asarray(g))), name
    for name, g in _indexer_leaves(d_index):
        assert ("['indexer']" in name) == bool(np.any(np.asarray(g))), name


def test_blockwise_gradient_is_the_whole_functions(monkeypatch):
    """``loss_and_grad`` (a program a half, one sequence at a time, the
    experts 32 tokens at a time) gives the two losses and the gradient of
    ``loss`` differentiated whole."""
    share = dataclasses.replace(EXPERTS, experts_held=4, expert_offset=2)
    monkeypatch.setattr(reference, "TOKENS", 32)
    params = _init(_config(experts=share))
    tokens = _tokens(6, batch=3)
    lm, kls, grads, idx, counts = reference.loss_and_grad(
        params, tokens, _arch(share))
    with jax.default_matmul_precision("highest"):
        (want, (want_lm, want_kls, want_idx)), want_grads = (
            jax.value_and_grad(reference.loss, has_aux=True)(
                params, tokens, _arch(share)))
    np.testing.assert_allclose(float(lm), float(want_lm), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(kls), np.asarray(want_kls),
                               rtol=1e-5)
    flat = dict(jax.tree_util.tree_leaves_with_path(grads))
    for path, w in jax.tree_util.tree_leaves_with_path(want_grads):
        np.testing.assert_allclose(
            np.asarray(flat[path]), np.asarray(w),
            atol=1e-6 + 1e-4 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))
    assert idx.shape == (3, 2, SEQ, 4)
    # a reference's own selection is its own: nothing apart, same sizes
    assert reference.selection_numbers(counts) == {
        "selection_apart": 0.0, "selection_size_apart": 0.0}


def test_the_losses_under_the_own_selection_are_the_whole_functions(
        monkeypatch):
    """``own_losses`` (a program a half, one sequence at a time) gives the
    two losses of ``loss`` with no selection brought, under the experts'
    choices brought; and under a program's selection the losses move."""
    share = dataclasses.replace(EXPERTS, experts_held=4, expert_offset=2)
    monkeypatch.setattr(reference, "TOKENS", 32)
    params = _init(_config(experts=share))
    tokens = _tokens(7, batch=2)
    with jax.default_matmul_precision("highest"):
        _, (want_lm, want_kls, idx) = reference.loss(params, tokens,
                                                     _arch(share))
    # idx [L, B*S, k] -> the step's layout [B, L, S, k]
    choices = jnp.swapaxes(idx.reshape(idx.shape[0], 2, SEQ, -1), 0, 1)
    lm, kls = reference.own_losses(params, tokens, _arch(share), choices)
    np.testing.assert_allclose(float(lm), float(want_lm), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(kls), np.asarray(want_kls),
                               rtol=1e-5)
    # the first 24 causal keys of every query, another selection than
    # the indexer's: the step-selection losses differ from the own ones
    first = jnp.arange(SEQ)[None, :] < 24
    causal = jnp.arange(SEQ)[None, :] <= jnp.arange(SEQ)[:, None]
    from horovod_tpu.ops import sparse_attention as sa
    words = sa.pack(jnp.broadcast_to(first & causal, (2, SEQ, SEQ)))
    with jax.default_matmul_precision("highest"):
        _, (other_lm, other_kls, _) = reference.loss(
            params, tokens, _arch(share), idx, jnp.stack([words] * 2))
    assert abs(float(other_lm) - float(lm)) > 1e-4 * float(lm)
    assert not np.allclose(np.asarray(other_kls), np.asarray(kls))


@pytest.mark.parametrize("experts,top_k,held", [(32, 4, 8), (16, 4, 4)],
                         ids=["four_shares_of_8_of_32",
                              "four_shares_of_4_of_16"])
def test_the_shares_add_up_to_the_uncut_layer(rng, experts, top_k, held):
    """With the softmax router, the routed parts of all four shares add up
    to what the uncut reference gives for the whole layer (there is no
    shared expert to count once)."""
    whole_share = dataclasses.replace(
        EXPERTS, n_routed_experts=experts, experts_held=experts,
        num_experts_per_tok=top_k)
    params = _init(_config(experts=whole_share, layers=1))["block_0"]
    y = jnp.asarray(rng.standard_normal((40, D)), jnp.float32)
    total = 0.0
    for i in range(experts // held):
        share = dataclasses.replace(whole_share, experts_held=held,
                                    expert_offset=held * i)
        kept = {**params["experts"], **{
            name: params["experts"][name][held * i:held * (i + 1)]
            for name in ("gate_proj", "up_proj", "down_proj")}}
        routed = experts_lib.ExpertShare(share, dtype=jnp.float32).apply(
            {"params": kept}, y)
        total = total + routed
        with jax.default_matmul_precision("highest"):
            want, _ = reference._routed(kept, y, _arch(share))
        assert float(jnp.abs(want).max()) > 0.01
        np.testing.assert_allclose(np.asarray(routed), np.asarray(want),
                                   atol=2e-5)
    with jax.default_matmul_precision("highest"):
        whole, _ = reference._routed(params["experts"], y,
                                     _arch(whole_share))
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=5e-5)


# The parameter tree each accepted LM configuration built before the new
# options (sorted leaf paths, shapes and dtypes, sha256), at its published
# widths: read from the parent commit's program.
TREES = {"kanana-2-30b-a3b": ("mla_moe_lm", "b4-s4096", "1a5c756fbc1366d9"),
         "nemotron-3-nano-30b-a3b": ("ssm_moe_lm", "b2-s4096",
                                     "7a447448e7a7d4e9"),
         "kimi-linear-48b-a3b": ("kda_moe_lm", "b2-s4096",
                                 "bb6e884af5f5ec56"),
         "laguna-xs.2": ("swa_moe_lm", "b1-s8192", "97f2912a0de48097"),
         "lm-d2048": ("decoder_lm", "b8-s2048", "6af58bc9f2219122")}


@pytest.mark.parametrize("name", sorted(TREES))
def test_the_accepted_configurations_build_the_trees_they_built(
        hvd, monkeypatch, name):
    """With the new options at their defaults (no indexer, no q/k norm,
    the sigmoid router, a shared expert wherever one is sized) every
    accepted LM cell's family builds its parameter tree name for name and
    shape for shape."""
    family_name, traffic_name, digest = TREES[name]
    family = importlib.import_module(f"benchmark.families.{family_name}")
    load = lambda *p: json.load(open(os.path.join(  # noqa: E731
        REPO, "benchmark", *p)))
    seen = {}

    def stop(model, *_, **__):
        seen["cfg"] = model.cfg
        raise StopIteration

    monkeypatch.setattr(training, "make_lm_train_step", stop)
    with pytest.raises(StopIteration):
        family.build(load("configs", name + ".json"),
                     load("traffic", traffic_name + ".json"), hvd.mesh(), 1)
    shapes = jax.eval_shape(Transformer(seen["cfg"]).init,
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))["params"]
    items = sorted((jax.tree_util.keystr(p), tuple(a.shape), str(a.dtype))
                   for p, a in jax.tree_util.tree_leaves_with_path(shapes))
    assert hashlib.sha256(repr(items).encode()).hexdigest()[:16] == digest


@pytest.mark.parametrize("room,rows", [(None, 2048), (2, 2048), (3, 3072),
                                      (16, 8192)])
def test_the_held_room_sizes_the_expert_order_buffers(room, rows):
    """``ExpertShareConfig.held_room`` times the 1,024 slots that balance
    sends 16 of 128 experts out of 8,192, up to a tile of 512 and at most
    every slot; unset, ``HELD_ROOM``'s 2."""
    share = dataclasses.replace(EXPERTS, n_routed_experts=128,
                                experts_held=16, held_room=room)
    assert experts_lib.held_rows(8192, share) == rows


def test_the_sigmoid_router_is_the_formula_it_was(rng):
    """The default router's choices and weights are, bit for bit, the
    formula it had before a softmax could be asked for: sigmoid of the
    float32 product, top-k of the scores plus the bias, normalised and
    scaled."""
    cfg = dataclasses.replace(EXPERTS, router="sigmoid",
                              selection_bias_std=0.05,
                              routed_scaling_factor=2.5)
    y = jnp.asarray(rng.standard_normal((64, D)), jnp.float32)
    params = experts_lib.ExpertShare(cfg, dtype=jnp.float32).init(
        jax.random.PRNGKey(1), y)["params"]
    (w, _), (idx, _) = experts_lib._prepared(cfg, jnp.float32, y, params)
    scores = jax.nn.sigmoid(jnp.dot(y, params["router"],
                                    precision=jax.lax.Precision.HIGHEST))
    want_idx, want_w = experts_lib.route(
        scores, params["e_score_correction_bias"], 4, 2.5)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(want_w).T)


@pytest.mark.parametrize("case", ["cache", "ring"])
def test_what_an_indexed_layer_cannot_run_is_refused(case):
    """Decode through the paged cache (no indexer cache is built) and ring
    attention (no per-query selection in its schedule) are refused with
    the reason."""
    cfg = _config(sequence_axis="seq" if case == "ring" else None)
    x = jnp.zeros((1, 16, D), jnp.float32)
    pos = jnp.arange(16)[None]
    layer = Attention(cfg, "sparse")
    if case == "cache":
        cache = (jnp.zeros((1, 4, 2, 8)), jnp.zeros((1, 4, 2, 8)),
                 jnp.zeros((1, 4), jnp.int32))
        with pytest.raises(NotImplementedError, match="indexer cache"):
            layer.init(jax.random.PRNGKey(0), x, pos, False, cache)
    else:
        with pytest.raises(NotImplementedError, match="ring attention"):
            layer.init(jax.random.PRNGKey(0), x, pos)


def test_benchmark_reference_is_a_copy():
    """The benchmark's reference and this directory's are the same text
    and give the same numbers."""
    from benchmark.reference import dsa_moe_lm as copy_of

    text = lambda m: open(m.__file__, "rb").read()  # noqa: E731
    assert text(reference) == text(copy_of)
    share = dataclasses.replace(EXPERTS, experts_held=4, expert_offset=8)
    params = _init(_config(experts=share))
    tokens = _tokens(5)
    a = reference.loss_and_grad(params, tokens, _arch(share))
    b = copy_of.loss_and_grad(params, tokens, _arch(share))
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# What the small cell below is held to: the cell's limits
# (``families/dsa_moe_lm.LIMITS``) with room for heads 16 wide in a model
# 64 wide over 128 positions, where one key is 1/24 of a selection (sound
# here: loss 9e-5, grad_error 0.017, routing_apart 0.008, index_loss 4e-4,
# selection_apart 7.2e-4, bfloat16 operands 1.4e-3).
SMALL_LIMITS = {"loss": 1.5e-3, "index_loss": 2e-2, "grad_norm": 2.5e-2,
                "grad_error": 0.10, "routing_apart": 0.05,
                "selection_apart": 2e-3, "selection_size_apart": 0.0,
                "attention_apart": 0.03}


def small_config():
    """The cell's configuration shrunk to run here (every width smaller,
    the indexer's top-k 24 of 128 positions, the kernels off)."""
    config = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "keye-vl-2.0-30b-a3b.json")))
    config = copy.deepcopy(config)
    config.update(hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16,
                  moe_intermediate_size=32, num_experts=8,
                  num_local_experts=8, num_experts_per_tok=4, vocab_size=64,
                  num_hidden_layers=2)
    config["sa_config"] = dict(config["sa_config"], indexer_num_heads=2,
                               indexer_head_dim=8, topk=24)
    config["deployment"] = dict(config["deployment"], router_width=16)
    config["assumed"] = dict(config["assumed"], flash_attention=False,
                             index_rotary_dim=4)
    return config, {"per_chip_batch": 1, "seq_len": 128}


@pytest.fixture(scope="module")
def small_cell():
    """The benchmark family of ``keye-vl-2.0-30b-a3b-train-s32768`` built
    at a small size on this machine's mesh, one step of it taken, and the
    sound reference's readings, under ``SMALL_LIMITS``."""
    import horovod_tpu as hvd
    from benchmark.families import dsa_moe_lm as family

    config, traffic = small_config()
    hvd.shutdown()
    hvd.init()
    limits, family.LIMITS = family.LIMITS, SMALL_LIMITS
    try:
        built = family.build(config, traffic, hvd.mesh(), 3_000_000_019)
        got = built.step_numbers()
        _, sound = built.compare(got, built.reference_numbers(got))
        hvd.shutdown()
        yield config, built, got, sound
    finally:
        family.LIMITS = limits


def _faults():
    from benchmark.reference import dsa_moe_lm_faults
    return dsa_moe_lm_faults


def test_the_cells_limits_are_tighter_than_the_small_cells():
    from benchmark.families import dsa_moe_lm as family

    assert set(family.LIMITS) == set(SMALL_LIMITS)
    assert all(family.LIMITS[name] <= SMALL_LIMITS[name]
               for name in SMALL_LIMITS)


def test_the_small_cell_agrees_with_its_reference(small_cell, n_devices):
    _, _, got, sound = small_cell
    told = [name for name, r in sound.items()
            if name != "routing" and not r["agrees"]]
    assert not told, {name: sound[name] for name in told}
    assert got[2].shape == (min(2, n_devices), 2, 128, 4)
    assert got[4].shape == (2, min(2, n_devices), 8, 128)
    for name in ("attention_grad_error", "indexer_grad_error"):
        assert sound[name]["tolerance"] is None and sound[name]["agrees"]
        assert 0 < sound[name]["relative_error"] < 0.1
    # under the reference's own selection: read, not judged, and of the
    # size of the same losses under the step's
    for name in ("loss_own_selection", "index_loss_own_selection"):
        assert sound[name]["tolerance"] is None and sound[name]["agrees"]
        assert sound[name]["relative_error"] < SMALL_LIMITS[
            name.replace("_own_selection", "")]


@pytest.mark.parametrize("fault", _faults().FAULTS)
def test_a_fault_in_the_reference_is_told_by_a_limit(small_cell, fault):
    """Each fault of ``benchmark/reference/dsa_moe_lm_faults.py`` planted
    into the benchmark's reference, against the step the family took: the
    first limit that tells it (``TOLD_BY``) tells it here, and the
    reference at the step's own precision stays sound."""
    config, built, got, sound = small_cell
    faults = _faults()
    with faults.planted(fault):
        agrees, report = built.compare(got, built.reference_numbers(got))
    read = faults.readings(report)
    assert agrees == (not read["told_by"])
    assert set(faults.TOLD_BY[fault][:1]) <= set(read["told_by"]), read
    if fault == "bfloat16_operands":
        assert agrees, read
    assert faults.reference.MANTISSA_BITS is None
    assert faults.reference._sparse()
