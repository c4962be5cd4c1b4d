"""The latent-attention, sparse-expert decoder family (``model_type
deepseek_v3``): ``models/transformer.py`` with the layer pattern
(mla, swiglu), (mla, experts)..., trained through
``hvd.DistributedOptimizer(optax.adamw)`` and
``training.make_lm_train_step``, the way a user builds it.

A configuration file of this family carries the keys of the model's own
``config.json`` and, under ``deployment``, over how many chips each layer
is divided and which experts this chip holds: ``n_routed_experts`` and
``vocab_size`` are what is HELD here, ``published`` has the model's
counts, and the router keeps the published width.
"""

import dataclasses
import types
import warnings

import numpy as np

ITEM = "tokens"
ADAM_B1 = 0.9  # optax.adamw's default, which the step is built with

# What decides ``correct``, and why these limits. The step computes in
# bfloat16 with float32 parameters, router, softmax statistics and loss;
# the reference is float32 at the highest matmul precision. Beside the
# rounding of activations and gradients to 8 bits of mantissa, the routing
# separates them: the scores are float32 on both sides, but the step's
# come from bfloat16 activations, so a near-tie between the sixth and
# seventh score falls the other way for 1.2-1.8% of a layer's token-slots,
# the token passes through another expert, and that expert's output is not
# small. That is no fault of either side and says nothing of precision, so
# the reference evaluates its experts under the STEP'S choices (its scores,
# weights and router gradient stay its own), and the two choices' distance
# is a reading of its own. Every number below: my chip runs, PR 27,
# thirteen seeds sound and ten faults planted into the reference
# (``benchmark/reference/mla_moe_lm_faults.py``; PERF.md section 6).
#
# ``grad_error``: the distance between the gradient the step applied and
# the reference's, over the reference's norm. Sound 0.0637-0.0726 (0.091-
# 0.097 before the choices were shared); the reference at float8's three
# bits of mantissa, the precision below the one the configuration states,
# 0.3075; a combine without ``routed_scaling_factor`` 0.300, the slots
# past twice the mean load dropped 0.212, no shared expert 1.40. The limit
# stands 1.65 times above the largest sound reading and 1.8 to 2.5 times
# below those. The reference with bfloat16 operands, the configuration's
# OWN precision, reads 0.0672 beside the sound 0.0673: a side as precise
# as the step is as right as the step, and no limit can or should tell it;
# a bfloat16 softmax (0.0678) or router (0.0673) and one slot in 600
# dropped (0.0698) lie inside the seeds' readings likewise.
# ``routing_apart``: the share of the token-slots the reference would
# choose, in the layer where it is largest, that the step did not choose
# for that token. Sound 0.0169-0.0184 (0.012 in the first sparse layer,
# more in each later one); float8 0.192, unscaled 0.170, past capacity
# 0.078, no shared expert 0.676; a bfloat16 router 0.0178: its rounding
# is less than what the bfloat16 activations already did to the scores.
# ``loss``: sound 8.9e-6 to 1.8e-4 (two seeds of thirteen past 1.5e-4, the
# accepted cells' limit, which does not hold here; root mean square
# 7.9e-5). A bfloat16 log-softmax reads 5.0e-4 (its log-sum-exp, near 10.2
# for every token of a random model, is rounded to a grid of 1/16), no
# shared expert 6.1e-4. The limit stands 2.6 times above the largest sound
# reading and only a tenth below the bfloat16 loss: nearer, a sound seed
# would fail. float8 (2.7e-4) and the unscaled combine (2.8e-4) pass it
# and fail others.
# ``grad_norm`` keeps the accepted cells' 1e-2: sound at most 1.1e-3 in
# twenty-seven readings, unscaled 0.022, no shared expert 0.40.
LIMITS = {"loss": 4.5e-4, "grad_norm": 1e-2, "grad_error": 0.12,
          "routing_apart": 0.05}


def _sizes(config):
    held, total = config["n_routed_experts"], config["deployment"][
        "router_width"]
    return dict(
        layers=config["num_hidden_layers"],
        dense_layers=config["first_k_dense_replace"],
        d=config["hidden_size"], heads=config["num_attention_heads"],
        d_nope=config["qk_nope_head_dim"], d_rope=config["qk_rope_head_dim"],
        d_qk=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        d_v=config["v_head_dim"], rank=config["kv_lora_rank"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        shared=config["n_shared_experts"], held=held, experts=total,
        top_k=config["num_experts_per_tok"], vocab=config["vocab_size"])


def _attention_parameters(z):
    """q_proj, kv_a_proj_with_mqa, kv_b_proj, o_proj (the norms multiply
    nothing a token is multiplied by)."""
    return (z["d"] * z["heads"] * z["d_qk"]
            + z["d"] * (z["rank"] + z["d_rope"])
            + z["rank"] * z["heads"] * (z["d_nope"] + z["d_v"])
            + z["heads"] * z["d_v"] * z["d"])


def _expected_slots(z, tokens):
    """Token-slots of ``tokens`` tokens that fall on the experts held, in
    expectation under the router's own scores (a random router treats
    every expert alike): ``top_k * held / experts`` a token."""
    return tokens * z["top_k"] * z["held"] / z["experts"]


def required_flops_per_item(config, traffic):
    """FLOPs the forward and backward passes of ONE token require, from
    shapes alone: 6 a parameter of every matrix a token is multiplied by
    (2 forward, 4 backward), plus causal attention at half its square
    (QK^T at d_qk and PV at d_v: 2*S*(d_qk + d_v) a head forward for the
    whole square, twice that backward, halved). The routed experts count
    at the EXPECTED number of held experts a token (``_expected_slots``:
    0.75 of the 6 chosen at 16 of 128). No recomputation (the step
    recomputes each block: not required work), no embedding lookup, no
    elementwise work."""
    z = _sizes(config)
    sparse_layers = z["layers"] - z["dense_layers"]
    swiglu = lambda width: 3 * z["d"] * width  # noqa: E731
    matmul = 6 * (
        z["layers"] * _attention_parameters(z)
        + z["dense_layers"] * swiglu(z["d_ff"])
        + sparse_layers * (swiglu(z["shared"] * z["d_expert"])
                           + z["d"] * z["experts"])
        + sparse_layers * _expected_slots(z, 1) * swiglu(z["d_expert"])
        + z["d"] * z["vocab"])
    attention = (z["layers"] * 3 * 0.5 * z["heads"]
                 * 2 * traffic["seq_len"] * (z["d_qk"] + z["d_v"]))
    return matmul + attention


def kernel_work(config, traffic):
    """What one step asks of its kernels on ONE chip, forward and backward
    summed, nothing recomputed.

    ``flops``/``bytes``: the flash kernel. Per layer, batch row and head,
    forward half of (2*S^2*d_qk + 2*S^2*d_v) FLOPs and twice that
    backward; q, k (and dq, dk) at d_qk, v, o (and dO, dV) at d_v, in
    bfloat16, and the float32 row statistics (once forward, lse and delta
    backward).

    ``grouped_flops``/``grouped_bytes``: the routed experts' grouped
    products, at the expected number of held token-slots a layer
    (``_expected_slots``): 6 * 3 * d * d_expert FLOPs a slot; bytes of the
    three products of a SwiGLU taken one by one, each reading its two
    operands and writing its result once, forward, input gradient and
    weight gradient (bfloat16): 3 * 2 * (m*k + m*n + held*k*n)."""
    z = _sizes(config)
    b, s = traffic["per_chip_batch"], traffic["seq_len"]
    calls = z["layers"] * b * z["heads"]
    fwd = 0.5 * 2 * s * s * (z["d_qk"] + z["d_v"])
    wide, narrow, stats = s * z["d_qk"] * 2, s * z["d_v"] * 2, s * 4
    sparse_layers = z["layers"] - z["dense_layers"]
    slots = _expected_slots(z, b * s)
    d, f = z["d"], z["d_expert"]
    one_product = slots * (d + f) + z["held"] * d * f
    return {"flops": calls * 3 * fwd,
            "bytes": calls * ((2 * wide + 2 * narrow + stats)
                              + (4 * wide + 4 * narrow + 2 * stats)),
            "grouped_flops": sparse_layers * slots * 6 * 3 * d * f,
            "grouped_bytes": sparse_layers * 3 * 3 * 2 * one_product}


def build(config, traffic, mesh, seed):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from benchmark.reference import mla_moe_lm as reference
    from horovod_tpu import training
    from horovod_tpu.models.experts import (ExpertShareConfig,
                                            GroupedFallbackWarning)
    from horovod_tpu.models.mla import LatentAttentionConfig
    from horovod_tpu.models.transformer import (Transformer,
                                                TransformerConfig)

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
    # what the program's latent attention and expert layer fix: a file
    # that asks for anything else would be run as this under its own name
    fixed = {"model_type": "deepseek_v3", "q_lora_rank": None,
             "rope_scaling": None, "rope_interleave": True,
             "hidden_act": "silu", "scoring_func": "sigmoid",
             "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
             "norm_topk_prob": True, "moe_layer_freq": 1,
             "attention_bias": False, "tie_word_embeddings": False,
             "rms_norm_eps": 1e-6}
    asked = {key: config[key] for key in fixed}
    if asked != fixed or assumed["compute_dtype"] != "bfloat16":
        raise ValueError(f"the mla_moe_lm family runs {fixed} in bfloat16; "
                         f"the configuration asks for {asked}, "
                         f"{assumed['compute_dtype']}")
    cfg = TransformerConfig(
        vocab_size=z["vocab"], num_layers=z["layers"], num_heads=z["heads"],
        d_model=z["d"], d_ff=z["d_ff"], dtype=jnp.bfloat16,
        sequence_axis=None, flash_attention=assumed["flash_attention"],
        layer_pattern=(("mla", "swiglu"),) * z["dense_layers"]
        + (("mla", "experts"),) * (z["layers"] - z["dense_layers"]),
        mla=LatentAttentionConfig(
            kv_lora_rank=z["rank"], qk_nope_head_dim=z["d_nope"],
            qk_rope_head_dim=z["d_rope"], v_head_dim=z["d_v"],
            rope_theta=float(config["rope_theta"])),
        experts=ExpertShareConfig(
            n_routed_experts=z["experts"], experts_held=z["held"],
            expert_offset=deployment["expert_offset"], num_experts_per_tok=z[
                "top_k"], moe_d_ff=z["d_expert"],
            n_shared_experts=z["shared"],
            routed_scaling_factor=config["routed_scaling_factor"],
            selection_bias_std=assumed["selection_bias_std"]))
    arch = {"qk_nope_head_dim": z["d_nope"], "kv_lora_rank": z["rank"],
            "rope_theta": float(config["rope_theta"]),
            "num_experts_per_tok": z["top_k"],
            "routed_scaling_factor": config["routed_scaling_factor"],
            "expert_offset": deployment["expert_offset"]}
    # parameters do not depend on the attention path: initialise without
    # the kernel, on as few positions as the grouped products' tiles take
    init_model = Transformer(dataclasses.replace(cfg, flash_attention=False))
    tx = hvd.DistributedOptimizer(optax.adamw(assumed["learning_rate"]),
                                  axes=("data",))
    model = Transformer(cfg)
    step = training.make_lm_train_step(model, tx, mesh=mesh,
                                       batch_axis="data")
    replicated = NamedSharding(mesh, P())
    by_batch = NamedSharding(mesh, P("data"))
    seed = np.uint32(seed)

    # the seed is an argument and every array is made inside one jitted
    # call: nothing is initialised eagerly, nothing is captured
    init = jax.jit(lambda s: training.create_train_state(
        init_model, tx, jax.random.fold_in(jax.random.PRNGKey(s), 0),
        jnp.zeros((1, 128), jnp.int32)), out_shardings=replicated)
    draw = jax.jit(lambda s: jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(s), 1), (batch, seq), 0,
        z["vocab"], jnp.int32), out_shardings=by_batch)

    def draw_sample(s):
        two = jax.random.randint(
            jax.random.fold_in(jax.random.PRNGKey(s), 2), (2, seq), 0,
            z["vocab"], jnp.int32)
        # blocks, not tiles, as in decoder_lm: each half of a mesh's
        # chips would see one of the two sequences
        return two, jnp.repeat(two, batch // 2, axis=0)

    sample = jax.jit(draw_sample, out_shardings=(replicated, by_batch))

    @jax.jit
    def chosen(params, tokens):
        """The experts the program's routing chooses for ``tokens``,
        [B, L, S, k] (a dense layer's rows are zeros): the forward pass of
        the timed step's model, which sows each share's choice."""
        _, kept = model.apply({"params": params}, tokens,
                              mutable=["intermediates"])
        b = tokens.shape[0]
        return jnp.stack([
            kept["intermediates"][f"block_{i}"]["experts"]["chosen"][0]
            .reshape(b, seq, z["top_k"]) if feed_forward == "experts"
            else jnp.zeros((b, seq, z["top_k"]), jnp.int32)
            for i, (_, feed_forward) in enumerate(cfg.layers())], 1)

    def step_numbers():
        """One step of the timed step on two seeded sequences repeated to
        its batch: ``(loss, Adam's first moment, the routing's choices on
        the two sequences)``. After ONE step from a zero state the moment
        is ``(1 - b1)`` times the gradient the step applied, the only
        place a step built by ``training.py`` shows it. A step shows no
        choice, so the choices are those of the same model's forward pass
        on the same batch from the same parameters."""
        _, repeated = sample(seed)
        state = init(seed)
        choices = chosen(state.params, repeated)[::batch // 2]
        state, loss = step(state, repeated)
        moments = [n for n in jax.tree_util.tree_leaves(
            state.opt_state,
            is_leaf=lambda x: isinstance(x, optax.ScaleByAdamState))
            if isinstance(n, optax.ScaleByAdamState)]
        if len(moments) != 1:
            raise RuntimeError(f"expected one ScaleByAdamState in the "
                               f"optimizer state, found {len(moments)}")
        # the first moment's own buffers: no copy beside the state, and
        # the rest of the state goes with this frame
        return loss, moments[0].mu, choices

    def reference_numbers(choices, check_seed=seed):
        """``(the plain reference's loss on the two sequences, its
        gradient, its own routing's choices [sequence, layer, position,
        k])`` with its experts evaluated under ``choices``, the step's
        (the head of ``benchmark/reference/mla_moe_lm.py`` says what stays
        the reference's own). The reference jits its own blocks, anew at
        every call: a fault put into the reference module shows."""
        two, _ = sample(seed)
        # the reference needs room: only parameters are alive beside it
        params = init(np.uint32(check_seed)).params
        return reference.loss_and_grad(params, two, arch, choices)

    sparse = slice(z["dense_layers"], None)

    def routing_numbers(choices, own):
        """The routing's own numbers, of the checked step's batch (the two
        sequences, repeated): token-slots that fell on the experts held,
        a layer, beside their expectation; the largest load of any
        expert, and of any expert held, over the mean load; and the share
        of the token-slots the reference chooses, a layer, whose expert
        the step did not choose for that token."""
        idx, own = (np.asarray(a)[:, sparse] for a in (choices, own))
        lo = deployment["expert_offset"]
        held = ((idx >= lo) & (idx < lo + z["held"])).sum(axis=(0, 2, 3))
        loads = np.stack([np.bincount(layer.ravel(), minlength=z["experts"])
                          for layer in idx.transpose(1, 0, 2, 3)])
        apart = 1.0 - (own[..., :, None] == idx[..., None, :]).any(-1).mean(
            axis=(0, 2, 3))
        return {"held_slots_per_layer": (held * (batch // 2)).tolist(),
                "expected_held_slots": _expected_slots(z, batch * seq),
                "largest_load_over_mean": (loads.max(1)
                                           / loads.mean(1)).tolist(),
                "largest_held_load_over_mean": (
                    loads[:, lo:lo + z["held"]].max(1)
                    / loads.mean(1)).tolist(),
                "apart_per_layer": apart.tolist()}

    @jax.jit
    def gradient_numbers(moment, want):
        """``(|got|, |want|, |got - want|)``, global L2 norms over every
        leaf, ``got`` the gradient in the first moment."""
        got = jax.tree_util.tree_map(
            lambda m: m.astype(jnp.float32) / (1.0 - ADAM_B1), moment)
        norm = lambda tree: jnp.sqrt(sum(  # noqa: E731
            jnp.sum(jnp.square(x))
            for x in jax.tree_util.tree_leaves(tree)))
        return norm(got), norm(want), norm(jax.tree_util.tree_map(
            jnp.subtract, got, want))

    def compare(got, want):
        """``(agrees, report)`` of the step's ``(loss, first moment,
        choices)`` against the reference's ``(loss, gradient, own
        choices)``: the family's own comparison (``harness/check.compare``
        takes numbers; ``grad_error`` is a distance between two trees and
        ``routing_apart`` one between two choices)."""
        got_norm, want_norm, apart = (float(x) for x in gradient_numbers(
            got[1], want[1]))
        routing = routing_numbers(got[2], want[2])
        relative = lambda g, w: (g, w, abs(g - w) / abs(w))  # noqa: E731
        readings = {
            "loss": relative(float(got[0]), float(want[0])),
            "grad_norm": relative(got_norm, want_norm),
            "grad_error": (apart, want_norm, apart / want_norm),
            "routing_apart": (max(routing["apart_per_layer"]), 0.0,
                              max(routing["apart_per_layer"]))}
        report = {name: {"step": g, "reference": w, "relative_error": err,
                         "tolerance": LIMITS[name],
                         "agrees": bool(err <= LIMITS[name])}
                  for name, (g, w, err) in readings.items()}
        return all(r["agrees"] for r in report.values()), {
            **report, "routing": routing}

    def reference_check(check_seed=seed):
        """One step of the step under test against the plain reference
        (``check_seed``: another seed's parameters for the reference show
        that the check can fail)."""
        got = step_numbers()
        return compare(got, reference_numbers(got[2], check_seed))

    return types.SimpleNamespace(
        item=ITEM, items_per_step=batch * seq, step=step,
        init_state=lambda: init(seed), batch=lambda: (draw(seed),),
        reference_check=reference_check,
        # the parts of the check, for the study of its limits
        # (benchmark/reference/mla_moe_lm_faults.py)
        step_numbers=step_numbers, reference_numbers=reference_numbers,
        compare=compare,
        wants_pallas_kernel=bool(assumed["flash_attention"]))
