"""The learned-sparse-attention decoder family with sparse experts
(``model_type KeyeVL2``, the language model of Keye-VL-2.0): Qwen3-MoE
blocks, grouped-query attention with a norm on each head of q and k, to
which DeepSeek Sparse Attention adds a lightning indexer a layer that
chooses ``sa_config.topk`` keys for every query and learns from its own
KL loss; a softmax router choosing top-k SwiGLU experts, no shared
expert. ``models/transformer.py`` with an ``AttentionConfig`` that has an
``indexer``, trained through ``hvd.DistributedOptimizer(optax.adamw)``
and ``training.make_lm_train_step``, the way a user builds it.

A configuration file of this family carries the keys of the model's own
``config.json`` and, under ``deployment``, over how many chips each layer
is divided and which experts this chip holds: ``num_experts``,
``num_local_experts`` and ``vocab_size`` are what is HELD here,
``published`` has the model's counts, and the router keeps the published
width. What ``config.json`` leaves open is under ``assumed``.
"""

import dataclasses
import types
import warnings

import numpy as np

from benchmark.families.mla_moe_lm import ITEM, _expected_slots

# What decides ``correct``, and why these limits. The step computes in
# bfloat16 with float32 parameters, router, indexer scores, loss and
# softmax statistics; the reference is float32 at the highest matmul
# precision. As in the other sparse families the reference evaluates its
# experts under the STEP'S choices, and its main attention and indexer
# loss over the STEP'S selection of keys (its scores, weights and their
# gradients stay its own); the two choices' and the two selections'
# distances are readings of their own. PERF.md section 6 holds the
# readings each limit was set from, sound and under the faults of
# ``benchmark/reference/dsa_moe_lm_faults.py``.
#
# ``loss``: the next-token loss L_LM, relative. ``index_loss``: the sum
# over layers of the indexer's KL, relative. ``grad_norm`` and
# ``grad_error``: of the gradient of L_LM + sum L_I (the indexer's
# parameters' part is that of L_I alone), as in the other families.
# ``routing_apart``: the share of the token-slots the reference would
# choose that the step did not, in the layer where it is largest.
# ``selection_apart``: the share of the keys the step selected that the
# reference scores below its own ``topk``-th score by more than the
# bfloat16 rounding of the indexer's operands allows (the bound
# ``reference._allowance``, never reached by rounding alone), in the layer
# where it is largest. ``selection_size_apart``: the keys by which the two
# selections' sizes differ, over the step's: 0 unless one side takes
# another number of keys. ``attention_apart``: the largest relative
# distance, token by token, between a sparse layer's attention as the
# timed model calls it and the reference's under the step's selection,
# on seeded q, k, v and indexer operands at the timed sequence.
# ``loss_own_selection`` and ``index_loss_own_selection``: ``loss`` and
# ``index_loss`` with the reference's main attention and indexer loss over
# its OWN selection (``reference.own_losses``), read and NOT judged: a key
# whose score lies within the step's rounding of the ``topk``-th may go
# either way, and a limit waits for the readings of a dozen seeds.
#
# ``loss`` stands between its two readings on the chip: sound 6.7e-6 to
# 2.6e-5 over eight seeds, float8 operands 2.0e-4 (bfloat16 operands
# 2.5e-5): 3.1 times the largest sound reading, float8 2.5 times the limit.
# ``grad_norm`` likewise: sound 3e-5 to 1.38e-2 over the same eight seeds,
# float8 3.5e-2: 1.8 times above the one, 1.4 times below the other.
LIMITS = {"loss": 8e-5, "index_loss": 5e-3, "grad_norm": 2.5e-2,
          "grad_error": 0.10, "routing_apart": 0.03,
          "selection_apart": 1e-3, "selection_size_apart": 0.0,
          "attention_apart": 0.01}
# Finer readings of the gradient's distance, read on every run's
# ``reference_check`` line and NOT judged.
PARTS = {"attention_grad_error": lambda path: "['attn']" in path
         and "['indexer']" not in path}
INDEXER = lambda path: "['indexer']" in path  # noqa: E731


def _sizes(config):
    sa = config["sa_config"]
    layers = config["num_hidden_layers"]
    if config["mlp_only_layers"] or config["decoder_sparse_step"] != 1:
        raise ValueError("the dsa_moe_lm family runs every layer with "
                         "experts (mlp_only_layers [], decoder_sparse_step "
                         "1)")
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the indexer has one key head")
    return dict(
        layers=layers, d=config["hidden_size"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head=config["head_dim"],
        d_expert=config["moe_intermediate_size"],
        held=config["num_experts"],
        experts=config["deployment"]["router_width"],
        top_k=config["num_experts_per_tok"], vocab=config["vocab_size"],
        index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"], index_top_k=sa["topk"])


def _attention_parameters(z):
    """q, k, v and the output projection of one layer."""
    return z["d"] * z["head"] * (2 * z["heads"] + 2 * z["kv_heads"])


def _index_parameters(z):
    """The indexer's three projections (WqI, WkI, Ww)."""
    return z["d"] * (z["index_heads"] * z["index_dim"] + z["index_dim"]
                     + z["index_heads"])


def parameters(config):
    """What the configuration's ``parameters`` states: every leaf of the
    tree held here."""
    z = _sizes(config)
    layer = (_attention_parameters(z) + 2 * z["head"]
             + _index_parameters(z) + 2 * z["index_dim"]
             + z["d"] * z["experts"] + 2 * z["d"]
             + z["held"] * 3 * z["d"] * z["d_expert"])
    return z["layers"] * layer + 2 * z["d"] * z["vocab"] + z["d"]


def _pairs(s, top_k):
    """``(causal pairs, selected pairs)`` of ``s`` positions: the
    half-square with its diagonal, and ``sum_t min(t + 1, top_k)``."""
    causal = s * (s + 1) // 2
    if top_k >= s:
        return causal, causal
    return causal, top_k * (top_k + 1) // 2 + (s - top_k) * top_k


def _sparse_flops(z, s):
    """The attention over the selected pairs, forward and backward: ``2 *
    2 * head`` a pair and query head forward (q.k and p v), twice that
    backward."""
    return 3 * _pairs(s, z["index_top_k"])[1] * 2 * 2 * z["head"] * z["heads"]


def _index_flops(z, s):
    """The indexer's scores of every causal pair (``2 * index_dim`` a pair
    and indexer head), forward only (the selection passes no gradient);
    the KL's backward over the selected pairs (dqI and dkI, twice that);
    the projections of the detached input, forward and the weights'
    gradient (4 a parameter and token: no gradient to the input)."""
    causal, selected = _pairs(s, z["index_top_k"])
    pair = 2 * z["index_dim"] * z["index_heads"]
    return causal * pair + selected * 2 * pair + 4 * _index_parameters(z) * s


def required_flops_per_item(config, traffic):
    """FLOPs the forward and backward passes of ONE token require, from
    shapes alone: 6 a parameter of every matrix a token is multiplied by
    (2 forward, 4 backward): the attention projections, the routers, the
    head; the routed experts at the EXPECTED number of held experts a
    token (``_expected_slots``: 1.0 of the 8 chosen at 16 of 128); the
    sparse attention over the selected pairs (``_sparse_flops``) and the
    indexer (``_index_flops``). No recomputation, no embedding lookup, no
    rotary, no norms, no elementwise work, no selection."""
    z = _sizes(config)
    s = traffic["seq_len"]
    matmul = 6 * (z["layers"] * (_attention_parameters(z)
                                 + z["d"] * z["experts"]
                                 + _expected_slots(z, 1) * 3 * z["d"]
                                 * z["d_expert"])
                  + z["d"] * z["vocab"])
    return matmul + z["layers"] * (_sparse_flops(z, s)
                                   + _index_flops(z, s)) / s


def kernel_work(config, traffic):
    """What one step asks of its kernels on ONE chip, forward and backward
    summed, nothing recomputed, from shapes alone and the same whatever
    implements it.

    ``sparse_flops``/``sparse_bytes``: the attention over the selected
    pairs (``_sparse_flops``); bytes of q, k, v, o in bfloat16, the
    selection as an int32 index a selected key and query and the
    head-averaged probabilities as a float32 one, forward; the same again
    backward with dO, dQ, dK, dV.

    ``index_flops``/``index_bytes``: the indexer (``_index_flops``): the
    scores, the KL's backward and the projections; bytes of the layer's
    input (bfloat16) and the projections' weights, qI, kI and w read, the
    selection written and the probabilities read, forward; backward the
    input, qI, kI, w, the probabilities and the selection again and dqI,
    dkI, dw and the weights' gradients (float32) written.

    ``grouped_flops``/``grouped_bytes``: the routed experts' grouped
    products, at the expected number of held token-slots a layer
    (``_expected_slots``), as the other sparse families count them."""
    z = _sizes(config)
    b, s = traffic["per_chip_batch"], traffic["seq_len"]
    _, selected = _pairs(s, z["index_top_k"])
    q = s * z["heads"] * z["head"] * 2
    kv = s * z["kv_heads"] * z["head"] * 2
    chosen = selected * 4 * 2  # the index and the probability of a pair
    sparse_bytes = (2 * q + 2 * kv + chosen) + (3 * q + 4 * kv + chosen)
    index_ops = s * (z["index_heads"] * z["index_dim"] * 2 + z["index_dim"]
                     * 2 + z["index_heads"] * 4)
    weights = _index_parameters(z)
    index_bytes = ((s * z["d"] * 2 + weights * 4 + index_ops + chosen)
                   + (s * z["d"] * 2 + 2 * index_ops + chosen + weights * 4))
    slots = _expected_slots(z, b * s)
    d, f = z["d"], z["d_expert"]
    one_product = slots * (d + f) + z["held"] * d * f
    return {"sparse_flops": z["layers"] * b * _sparse_flops(z, s),
            "sparse_bytes": z["layers"] * b * sparse_bytes,
            "index_flops": z["layers"] * b * _index_flops(z, s),
            "index_bytes": z["layers"] * b * index_bytes,
            "grouped_flops": z["layers"] * slots * 6 * 3 * d * f,
            "grouped_bytes": z["layers"] * 3 * 3 * 2 * one_product}


def model_config(config, flash_attention=None):
    """The ``models.transformer.TransformerConfig`` the configuration
    sizes, and the description the reference is given (``arch``)."""
    import jax.numpy as jnp

    from horovod_tpu.models.experts import ExpertShareConfig
    from horovod_tpu.models.sparse_attention import IndexerConfig
    from horovod_tpu.models.transformer import (AttentionConfig,
                                                TransformerConfig)

    z = _sizes(config)
    assumed, deployment = config["assumed"], config["deployment"]
    indexer = IndexerConfig(
        num_heads=z["index_heads"], head_dim=z["index_dim"],
        top_k=z["index_top_k"], rotary_dim=assumed["index_rotary_dim"])
    sparse = AttentionConfig(
        kind="sparse", num_heads=z["heads"], head_dim=z["head"],
        num_kv_heads=z["kv_heads"], rope_theta=float(config["rope_theta"]),
        qk_norm=True, indexer=indexer,
        recompute=deployment["recompute_attention"])
    cfg = TransformerConfig(
        vocab_size=z["vocab"], num_layers=z["layers"], num_heads=z["heads"],
        d_model=z["d"], d_ff=config["intermediate_size"],
        dtype=jnp.bfloat16, norm_eps=config["rms_norm_eps"],
        sequence_axis=None,
        flash_attention=(assumed["flash_attention"] if flash_attention is None
                         else flash_attention),
        layer_pattern=(("sparse", "experts"),) * z["layers"],
        attention=(sparse,),
        experts=ExpertShareConfig(
            n_routed_experts=z["experts"], experts_held=z["held"],
            expert_offset=deployment["expert_offset"],
            num_experts_per_tok=z["top_k"], moe_d_ff=z["d_expert"],
            n_shared_experts=0, routed_scaling_factor=1.0,
            router="softmax", held_room=deployment["held_room"]))
    arch = {"rope_theta": float(config["rope_theta"]),
            "index_top_k": z["index_top_k"],
            "index_rotary_dim": assumed["index_rotary_dim"],
            "num_experts_per_tok": z["top_k"],
            "expert_offset": deployment["expert_offset"]}
    return cfg, arch


def build(config, traffic, mesh, seed):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from benchmark.harness import share_check
    from benchmark.reference import dsa_moe_lm as reference
    from horovod_tpu import training
    from horovod_tpu.models.experts import GroupedFallbackWarning
    from horovod_tpu.models.sparse_attention import LOSSES
    from horovod_tpu.models.transformer import Transformer

    # as jobs/train.py does for the flash kernel: a grouped product that
    # fell back to plain XLA is not what this cell measures
    warnings.simplefilter("error", GroupedFallbackWarning)
    z = _sizes(config)
    assumed, deployment = config["assumed"], config["deployment"]
    chips = mesh.devices.size
    batch, seq = traffic["per_chip_batch"] * chips, traffic["seq_len"]
    if seq > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {seq} is past the configuration's "
                         f"{config['max_position_embeddings']} positions")
    # what the program's attention and expert layer fix: a file that asks
    # for anything else would be run as this under its own name
    fixed = {"model_type": "KeyeVL2", "attention_bias": False,
             "tie_word_embeddings": False, "norm_topk_prob": True,
             "hidden_act": "silu", "use_sliding_window": False,
             "num_local_experts": config["num_experts"]}
    asked = {key: config[key] for key in fixed}
    if asked != fixed or assumed["compute_dtype"] != "bfloat16":
        raise ValueError(f"the dsa_moe_lm family runs {fixed} in bfloat16; "
                         f"the configuration asks for {asked}, "
                         f"{assumed['compute_dtype']}")
    cfg, arch = model_config(config)
    # parameters do not depend on the attention path: initialise without
    # the kernels, on as few positions as the grouped products' tiles take
    init_model = Transformer(dataclasses.replace(cfg, flash_attention=False))
    tx = hvd.DistributedOptimizer(optax.adamw(assumed["learning_rate"]),
                                  axes=("data",))
    model = Transformer(cfg)
    step = training.make_lm_train_step(model, tx, mesh=mesh,
                                       batch_axis="data")
    replicated = NamedSharding(mesh, P())
    by_batch = NamedSharding(mesh, P("data"))
    seed = np.uint32(seed)
    checked = min(2, batch)

    # the seed is an argument and every array is made inside one jitted
    # call: nothing is initialised eagerly, nothing is captured
    init = jax.jit(lambda s: training.create_train_state(
        init_model, tx, jax.random.fold_in(jax.random.PRNGKey(s), 0),
        jnp.zeros((1, 128), jnp.int32)), out_shardings=replicated)
    draw = jax.jit(lambda s: jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(s), 1), (batch, seq), 0,
        z["vocab"], jnp.int32), out_shardings=by_batch)

    def draw_sample(s):
        sequences = jax.random.randint(
            jax.random.fold_in(jax.random.PRNGKey(s), 2), (checked, seq), 0,
            z["vocab"], jnp.int32)
        return sequences, jnp.repeat(sequences, batch // checked, axis=0)

    sample = jax.jit(draw_sample, out_shardings=(replicated, by_batch))

    @jax.jit
    def observed(params, tokens):
        """What a step does not show, from the timed step's model on the
        same batch and parameters: the experts each layer chose [B, L,
        S, k], each layer's indexer loss [L] and packed selection [L, B,
        S / 32, S]."""
        _, kept = model.apply({"params": params}, tokens,
                              mutable=["intermediates", LOSSES])
        b = tokens.shape[0]
        blocks = [f"block_{i}" for i in range(z["layers"])]
        mid = kept["intermediates"]
        return (jnp.stack([mid[n]["experts"]["chosen"][0].reshape(
                    b, seq, z["top_k"]) for n in blocks], 1),
                jnp.stack([kept[LOSSES][n]["attn"]["indexer_kl"][0]
                           for n in blocks]),
                jnp.stack([mid[n]["attn"]["selection"][0] for n in blocks]))

    def draw_probe(s):
        """Seeded q [1, S, H, D], k, v [1, S, G, D], qI [1, S, HI, DI], kI
        [1, S, DI] in bfloat16 and w [1, S, HI] in float32 at the timed
        sequence."""
        shapes = [(z["heads"], z["head"]), (z["kv_heads"], z["head"]),
                  (z["kv_heads"], z["head"]),
                  (z["index_heads"], z["index_dim"]), (z["index_dim"],)]
        keys = jax.random.split(jax.random.fold_in(
            jax.random.PRNGKey(s), 3), 6)
        made = [jax.random.normal(key, (1, seq, *shape), jnp.float32).astype(
            jnp.bfloat16) for key, shape in zip(keys, shapes)]
        return (*made, jax.random.normal(keys[5], (1, seq, z["index_heads"]),
                                         jnp.float32) / 4.0)

    @jax.jit
    def probed(s):
        """``(attention, packed selection)`` of a sparse layer as the
        timed model calls it (``models/sparse_attention.attend_sparse``)
        on ``draw_probe``'s operands."""
        from horovod_tpu.models.sparse_attention import attend_sparse
        out, _, words = attend_sparse(cfg, cfg.attention_kind("sparse"),
                                      *draw_probe(s))
        return out[0], words[0]

    def probe_numbers():
        return probed(seed)

    def reference_probe(words):
        """The reference's attention on the same operands under the step's
        selection, float32 at its precision, jitted anew at every call (a
        fault put into the reference module shows)."""
        q, k, v = (t[0].astype(jnp.float32) for t in draw_probe(seed)[:3])
        with jax.default_matmul_precision(reference.PRECISION):
            # a new function each call: a fault put into the reference
            # module is traced anew
            return jax.jit(lambda *a: reference.attend_probe(*a))(
                q, k, v, words)

    def attention_apart(got, want):
        """The largest ``|program - reference| / |reference|`` of a token,
        over the timed sequence's tokens."""
        apart = jnp.linalg.norm(
            (got.astype(jnp.float32) - want).reshape(seq, -1), axis=-1)
        return float(jnp.max(apart / jnp.linalg.norm(want.reshape(seq, -1),
                                                     axis=-1)))

    def step_numbers():
        """One step of the timed step on the seeded sequences repeated to
        its batch: ``(L_LM, Adam's first moment, the routing's choices on
        the sequences, the indexer losses [L], the selections [L,
        sequence, S / 32, S], (the probe's attention, its selection))``.
        L_LM is the step's loss less the indexer losses of the same
        model's forward pass on the same batch from the same parameters,
        which also gives the choices and the selections."""
        _, repeated = sample(seed)
        state = init(seed)
        choices, kls, words = observed(state.params, repeated)
        every = batch // checked
        choices, words = choices[::every], words[:, ::every]
        state, loss = step(state, repeated)
        return (loss - jnp.sum(kls), share_check.first_moment(
            state.opt_state), choices, kls, words, probe_numbers())

    def reference_numbers(got, check_seed=seed):
        """``(the reference's L_LM, its gradient, its own routing's
        choices, its L_I [L], its selection counts [L, 3], its attention
        on the probe, (L_LM, L_I [L]) over its own selections)`` with its
        experts evaluated under the step's choices and, but for the last,
        its attention and indexer loss over the step's selections
        (``benchmark/reference/dsa_moe_lm.py``'s head). The
        reference jits its own blocks, anew at every call: a fault put
        into the reference module shows."""
        sequences, _ = sample(seed)
        params = init(np.uint32(check_seed)).params
        lm, kls, grads, own, counts = reference.loss_and_grad(
            params, sequences, arch, got[2], got[4])
        return (lm, grads, own, kls, counts, reference_probe(got[5][1]),
                reference.own_losses(params, sequences, arch, got[2]))

    def compare(got, want):
        """``(agrees, report)`` of ``step_numbers()`` against
        ``reference_numbers(...)``."""
        routing = share_check.routing_numbers(
            got[2], want[2], expert_layers=list(range(z["layers"])),
            offset=deployment["expert_offset"], held=z["held"],
            experts=z["experts"], repeats=batch // checked,
            expected=_expected_slots(z, batch * seq))
        agrees, report = share_check.compare(got, want, LIMITS, routing,
                                             PARTS)
        g_index, w_index = float(jnp.sum(got[3])), float(jnp.sum(want[3]))
        readings = {
            "index_loss": (g_index, w_index,
                           abs(g_index - w_index) / abs(w_index)
                           if w_index else float("inf")),
            **{name: (value, 0.0, value) for name, value in
               reference.selection_numbers(want[4]).items()},
            "attention_apart": (attention_apart(got[5][0], want[5]),) * 3}
        readings["attention_apart"] = (readings["attention_apart"][0], 0.0,
                                       readings["attention_apart"][0])
        for name, (g, w, err) in readings.items():
            report[name] = {"step": g, "reference": w, "relative_error": err,
                            "tolerance": LIMITS[name],
                            "agrees": bool(err <= LIMITS[name])}
        # both losses under the reference's own selection, read and not
        # judged
        own_lm, own_kls = (float(own) for own in (want[6][0],
                                                  jnp.sum(want[6][1])))
        for name, g, w in (("loss_own_selection", float(got[0]), own_lm),
                           ("index_loss_own_selection", g_index, own_kls)):
            report[name] = {"step": g, "reference": w,
                            "relative_error": abs(g - w) / abs(w) if w
                            else float("inf"),
                            "tolerance": None, "agrees": True}
        # the indexer's leaves apart, read and not judged (a reference
        # without the indexer's loss has no gradient there to divide by)
        norm, apart = share_check.part_numbers(got[1], want[1], INDEXER)
        report["indexer_grad_error"] = {
            "step": apart, "reference": norm,
            "relative_error": apart / norm if norm else float("inf"),
            "tolerance": None, "agrees": True}
        report["index_loss"]["per_layer"] = {
            "step": np.asarray(got[3]).tolist(),
            "reference": np.asarray(want[3]).tolist()}
        return agrees and all(report[name]["agrees"] for name in readings), \
            report

    def reference_check(check_seed=seed):
        """One step of the step under test against the plain reference
        (``check_seed``: another seed's parameters for the reference show
        that the check can fail)."""
        got = step_numbers()
        return compare(got, reference_numbers(got, check_seed))

    return types.SimpleNamespace(
        item=ITEM, items_per_step=batch * seq, step=step,
        init_state=lambda: init(seed), batch=lambda: (draw(seed),),
        reference_check=reference_check,
        # the parts of the check, for the study of its limits
        # (benchmark/reference/dsa_moe_lm_faults.py)
        step_numbers=step_numbers, reference_numbers=reference_numbers,
        compare=compare, probe_numbers=probe_numbers,
        reference_probe=reference_probe, attention_apart=attention_apart,
        wants_pallas_kernel=bool(assumed["flash_attention"]))
