"""The hybrid delta-attention / latent-attention / sparse-expert decoder
family (``model_type kimi_linear``): ``models/transformer.py`` with a
layer's mixer read from ``linear_attn_config`` (``kda_layers``: Kimi Delta
Attention; ``full_attn_layers``: latent attention without rotary, layers
counted from 1) and its feed-forward from ``first_k_dense_replace`` (a
dense SwiGLU, then top-k SwiGLU experts with one shared expert), trained
through ``hvd.DistributedOptimizer(optax.adamw)`` and
``training.make_lm_train_step``, the way a user builds it.

A configuration file of this family carries the keys of the model's own
``config.json`` and, under ``deployment``, over how many chips each layer
is divided and which experts this chip holds: ``num_experts`` and
``vocab_size`` are what is HELD here, ``published`` has the model's
counts, and the router keeps the published width. The sizes of the delta
mixer that ``config.json`` does not state are under ``assumed``.
"""

import dataclasses
import types
import warnings

import numpy as np

from benchmark.families.mla_moe_lm import (ITEM, _attention_parameters,
                                           _expected_slots)

# What decides ``correct``, and why these limits. The step computes in
# bfloat16 with float32 parameters, router, loss, softmax statistics and
# delta-rule statistics (the log-decay, its running sum, beta, the
# triangular inverse, the carried state); the reference is float32 at the
# highest matmul precision and runs the delta rule one position at a time.
# As in the two other sparse families the reference evaluates its experts
# under the STEP'S choices (its scores, weights and router gradient stay
# its own), and the two choices' distance is a reading of its own. Every
# number below: my chip runs, PR 33, twenty seeds sound and sixteen faults
# planted into the reference
# (``benchmark/reference/kda_moe_lm_faults.py``; PERF.md section 6).
#
# ``routing_apart``: the share of the token-slots the reference would
# choose, in the layer where it is largest, that the step did not choose
# for that token. The finest reading here, and larger than in the other
# two sparse cells (0.013-0.018): the eighth and ninth of 256 scores lie
# closer than the sixth and seventh of 128, and a delta layer passes on
# three times the rounding it is given. Sound 0.02776-0.03053 over twenty
# seeds (0.017 in the first expert layer, more in each later one). The
# running sum of the log-decay in bfloat16 0.0414, rotary applied in the
# latent-attention layer 0.0445, a combine without
# ``routed_scaling_factor`` 0.081, float8 operands 0.258, the decay applied
# after the correction 0.274, beta 1 0.726, the state dropped at every
# chunk boundary 0.748, ONE DECAY A HEAD in place of a decay a channel
# 0.878, no shared expert 0.902, silu for sigmoid in the output gate 0.922,
# no L2 norm 0.985 (the unnormed state overflows: every other reading is
# NaN, which passes no limit). The limit stands 1.21 times above the
# largest sound reading and 1.12 times below the bfloat16 running sum. The
# reference with bfloat16 operands, the configuration's OWN precision
# (0.0305-0.0308), the carried state alone in bfloat16 (0.0366) and a
# bfloat16 softmax or router (0.0292-0.0294) pass: a side as precise as
# the step is as right as the step.
# ``grad_error``: the distance between the gradient the step applied and
# the reference's, over the reference's norm. Sound 0.0573-0.0699; the
# reference at float8's three bits of mantissa, the precision below the
# one the configuration states, 0.450; unscaled 0.147, the decay after the
# correction 0.490, and 1.15 to 1.80 for beta 1, the dropped state, silu
# in the gate, one decay a head, no shared expert. The limit stands 1.43
# times above the largest sound reading and 1.47 to 4.5 times below the
# first three. The bfloat16 running sum (0.0735) and rotary (0.0895) pass
# it and fail ``routing_apart``.
# ``loss``: sound 9.1e-8 to 8.5e-5 over twenty seeds; it keeps the accepted
# sparse cells' 4.5e-4, 5.3 times above the largest sound reading. A
# bfloat16 log-softmax reads 1.17e-3 on one seed and 3e-5 on another (the
# log-sum-exp's place on bfloat16's grid of 1/16), the dropped state
# 1.59e-3, beta 1 1.67e-3, float8 5.6e-4; the decay after the correction
# (9.1e-5 and 6.8e-4: told on one seed of two), the unscaled combine
# (2.5e-5) and no shared expert (1.2e-4) pass it and fail others.
# ``grad_norm`` keeps the accepted cells' 1e-2: sound at most 3.6e-4; no
# shared expert 0.54, silu in the gate 0.219, beta 1 0.084, one decay a
# head 0.026, the dropped state 0.022.
LIMITS = {"loss": 4.5e-4, "grad_norm": 1e-2, "grad_error": 0.10,
          "routing_apart": 0.037}
# Finer readings of the gradient's distance, over the leaves a name takes:
# read on every run's ``reference_check`` line and NOT judged.
# ``scan_grad_error``, the vectors that only the delta rule's statistics
# read, the decay's rate and bias of every delta layer;
# ``attention_grad_error``, the latent-attention layer's kernels.
PARTS = {
    "scan_grad_error": lambda path: path.endswith(
        ("['mixer']['dt_bias']", "['mixer']['A_log']")),
    "attention_grad_error": lambda path: "['attn']" in path,
}


def _sizes(config):
    layers = config["num_hidden_layers"]
    linear = config["linear_attn_config"]
    kda, full = linear["kda_layers"], linear["full_attn_layers"]
    if sorted(kda + full) != list(range(1, layers + 1)):
        raise ValueError(f"kda_layers {kda} and full_attn_layers {full} do "
                         f"not name each of the {layers} layers once")
    dense = config["first_k_dense_replace"]
    assumed = config["assumed"]
    return dict(
        pattern=tuple(("kda" if i in kda else "mla",
                       "swiglu" if i <= dense else "experts")
                      for i in range(1, layers + 1)),
        kda_layers=len(kda), mla_layers=len(full), dense_layers=dense,
        expert_layers=layers - dense, d=config["hidden_size"],
        kda_heads=linear["num_heads"], kda_width=linear["head_dim"],
        taps=linear["short_conv_kernel_size"],
        chunk=assumed["kda_chunk_size"], gate_rank=assumed["kda_gate_rank"],
        heads=config["num_attention_heads"],
        d_nope=config["qk_nope_head_dim"], d_rope=config["qk_rope_head_dim"],
        d_qk=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        d_v=config["v_head_dim"], rank=config["kv_lora_rank"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        shared=config["num_shared_experts"], held=config["num_experts"],
        experts=config["deployment"]["router_width"],
        top_k=config["num_experts_per_token"], vocab=config["vocab_size"])


def _kda_parameters(z):
    """The matrices a token is multiplied by: q, k, v and o projections,
    the decay's and the gate's low-rank pairs, beta (the convolutions,
    the norms, the biases and ``A_log`` multiply element by element)."""
    inner = z["kda_heads"] * z["kda_width"]
    return (4 * z["d"] * inner + 2 * z["gate_rank"] * (z["d"] + inner)
            + z["d"] * z["kda_heads"])


def delta_flops_per_token(z):
    """FLOPs the delta rule of ONE layer requires a token, forward and
    backward (three times the forward), at chunks of ``chunk`` positions
    C, heads D wide, nothing recomputed. Forward, a token and head: the
    decayed ``k.k`` triangle and the decayed ``q.k`` triangle, each at
    half its square (2 * C * D / 2 each: a position sees the positions
    before it); the unit-triangular system by substitution for its two
    right-hand sides, ``beta V`` and ``beta K exp(G)`` (2 * C / 2 * D
    each); ``A_qk U`` at half its square (C * D); and three products with
    the D x D state, ``W S_0``, ``(q exp(G)) S_0`` and ``K^T U`` (2 * D *
    D each): 5 * C * D + 6 * D^2."""
    chunk, d = z["chunk"], z["kda_width"]
    return 3 * z["kda_heads"] * (5 * chunk * d + 6 * d * d)


def delta_bytes_per_token(z):
    """Bytes the delta rule of ONE layer must move a token: forward q, k,
    v (bfloat16) and the log-decay g and beta (float32, the precision the
    configuration states for them) read and ``o`` (bfloat16) written;
    backward the same five and ``dO`` read and the five gradients
    written."""
    inner = z["kda_heads"] * z["kda_width"]
    inputs = 3 * inner * 2 + (inner + z["kda_heads"]) * 4
    return 3 * inputs + 2 * inner * 2


def required_flops_per_item(config, traffic):
    """FLOPs the forward and backward passes of ONE token require, from
    shapes alone: 6 a parameter of every matrix a token is multiplied by
    (2 forward, 4 backward): the delta layers' projections and low-rank
    pairs, latent attention's four, the dense SwiGLU, the routers, the
    shared experts, the head; the routed experts at the EXPECTED number of
    held experts a token (``_expected_slots``: 0.25 of the 8 chosen at 8
    of 256); causal attention at half its square in the latent-attention
    layers (QK^T at d_qk and PV at d_v); and the delta rule
    (``delta_flops_per_token``). No recomputation (the step recomputes the
    delta scan and the expert share: not required work), no embedding
    lookup, no convolution, no elementwise work."""
    z = _sizes(config)
    swiglu = lambda width: 3 * z["d"] * width  # noqa: E731
    matmul = 6 * (
        z["kda_layers"] * _kda_parameters(z)
        + z["mla_layers"] * _attention_parameters(z)
        + z["dense_layers"] * swiglu(z["d_ff"])
        + z["expert_layers"] * (swiglu(z["shared"] * z["d_expert"])
                                + z["d"] * z["experts"]
                                + _expected_slots(z, 1)
                                * swiglu(z["d_expert"]))
        + z["d"] * z["vocab"])
    attention = (z["mla_layers"] * 3 * 0.5 * z["heads"]
                 * 2 * traffic["seq_len"] * (z["d_qk"] + z["d_v"]))
    return matmul + attention + z["kda_layers"] * delta_flops_per_token(z)


def kernel_work(config, traffic):
    """What one step asks of its kernels on ONE chip, forward and backward
    summed, nothing recomputed.

    ``flops``/``bytes``: the flash kernel of the latent-attention layers,
    as ``mla_moe_lm`` counts it. Per layer, batch row and head, forward
    half of (2*S^2*d_qk + 2*S^2*d_v) FLOPs and twice that backward; q, k
    (and dq, dk) at d_qk, v, o (and dO, dV) at d_v, in bfloat16, and the
    float32 row statistics (once forward, lse and delta backward).

    ``grouped_flops``/``grouped_bytes``: the routed experts' grouped
    products, at the expected number of held token-slots a layer
    (``_expected_slots``): 6 * 3 * d * d_expert FLOPs a slot; bytes of the
    three products of a SwiGLU taken one by one, each reading its two
    operands and writing its result once, forward, input gradient and
    weight gradient (bfloat16): 3 * 2 * (m*k + m*n + held*k*n).

    ``delta_flops``/``delta_bytes``: the delta layers' scans
    (``delta_flops_per_token``, ``delta_bytes_per_token``): the same
    required work whatever implements it."""
    z = _sizes(config)
    b, s = traffic["per_chip_batch"], traffic["seq_len"]
    calls = z["mla_layers"] * b * z["heads"]
    fwd = 0.5 * 2 * s * s * (z["d_qk"] + z["d_v"])
    wide, narrow, stats = s * z["d_qk"] * 2, s * z["d_v"] * 2, s * 4
    slots = _expected_slots(z, b * s)
    d, f = z["d"], z["d_expert"]
    one_product = slots * (d + f) + z["held"] * d * f
    tokens = b * s * z["kda_layers"]
    return {"flops": calls * 3 * fwd,
            "bytes": calls * ((2 * wide + 2 * narrow + stats)
                              + (4 * wide + 4 * narrow + 2 * stats)),
            "grouped_flops": z["expert_layers"] * slots * 6 * 3 * d * f,
            "grouped_bytes": z["expert_layers"] * 3 * 3 * 2 * one_product,
            "delta_flops": tokens * delta_flops_per_token(z),
            "delta_bytes": tokens * delta_bytes_per_token(z)}


def build(config, traffic, mesh, seed):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from benchmark.harness import share_check
    from benchmark.reference import kda_moe_lm as reference
    from horovod_tpu import training
    from horovod_tpu.models.experts import (ExpertShareConfig,
                                            GroupedFallbackWarning)
    from horovod_tpu.models.kda import DeltaAttentionConfig
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
    if seq > config["model_max_length"]:
        raise ValueError(f"seq_len {seq} is past the configuration's "
                         f"{config['model_max_length']} positions")
    # what the program's delta attention, latent attention and expert
    # layer fix: a file that asks for anything else would be run as this
    # under its own name
    fixed = {"model_type": "kimi_linear", "mla_use_nope": True,
             "q_lora_rank": None, "rope_scaling": None,
             "hidden_act": "silu", "moe_router_activation_func": "sigmoid",
             "moe_renormalize": True, "num_expert_group": 1,
             "topk_group": 1, "moe_layer_freq": 1, "num_shared_experts": 1,
             "num_nextn_predict_layers": 0, "tie_word_embeddings": False,
             "num_key_value_heads": config["num_attention_heads"]}
    asked = {key: config[key] for key in fixed}
    if asked != fixed or assumed["compute_dtype"] != "bfloat16":
        raise ValueError(f"the kda_moe_lm family runs {fixed} in bfloat16; "
                         f"the configuration asks for {asked}, "
                         f"{assumed['compute_dtype']}")
    cfg = TransformerConfig(
        vocab_size=z["vocab"], num_layers=len(z["pattern"]),
        num_heads=z["heads"], d_model=z["d"], d_ff=z["d_ff"],
        dtype=jnp.bfloat16, norm_eps=config["rms_norm_eps"],
        sequence_axis=None, flash_attention=assumed["flash_attention"],
        layer_pattern=z["pattern"],
        kda=DeltaAttentionConfig(
            num_heads=z["kda_heads"], head_dim=z["kda_width"],
            conv_kernel=z["taps"], chunk_size=z["chunk"],
            gate_rank=z["gate_rank"]),
        mla=LatentAttentionConfig(
            kv_lora_rank=z["rank"], qk_nope_head_dim=z["d_nope"],
            qk_rope_head_dim=z["d_rope"], v_head_dim=z["d_v"],
            rope_theta=float(config["rope_theta"]), rotary=False),
        experts=ExpertShareConfig(
            n_routed_experts=z["experts"], experts_held=z["held"],
            expert_offset=deployment["expert_offset"],
            num_experts_per_tok=z["top_k"], moe_d_ff=z["d_expert"],
            n_shared_experts=z["shared"],
            routed_scaling_factor=config["routed_scaling_factor"],
            selection_bias_std=assumed["selection_bias_std"]))
    arch = {"kda_head_dim": z["kda_width"],
            "qk_nope_head_dim": z["d_nope"], "kv_lora_rank": z["rank"],
            "num_experts_per_tok": z["top_k"],
            "routed_scaling_factor": config["routed_scaling_factor"],
            "expert_offset": deployment["expert_offset"]}
    # parameters do not depend on the attention path: initialise without
    # the kernel, on as few positions as the chunk and the grouped
    # products' tiles take
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
        jnp.zeros((1, max(128, z["chunk"])), jnp.int32)),
        out_shardings=replicated)
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
    routes = [i for i, (_, feed_forward) in enumerate(z["pattern"])
              if feed_forward == "experts"]

    @jax.jit
    def chosen(params, tokens):
        """The experts the program's routing chooses for ``tokens``,
        [B, L, S, k] (the dense layer's rows are zeros): the forward pass
        of the timed step's model, which sows each share's choice."""
        _, kept = model.apply({"params": params}, tokens,
                              mutable=["intermediates"])
        b = tokens.shape[0]
        return jnp.stack([
            kept["intermediates"][f"block_{i}"]["experts"]["chosen"][0]
            .reshape(b, seq, z["top_k"]) if i in routes
            else jnp.zeros((b, seq, z["top_k"]), jnp.int32)
            for i in range(len(z["pattern"]))], 1)

    def step_numbers():
        """One step of the timed step on two seeded sequences repeated to
        its batch: ``(loss, Adam's first moment, the routing's choices on
        the two sequences)``. A step shows no choice, so the choices are
        those of the same model's forward pass on the same batch from the
        same parameters."""
        _, repeated = sample(seed)
        state = init(seed)
        choices = chosen(state.params, repeated)[::batch // 2]
        state, loss = step(state, repeated)
        # the first moment's own buffers: the rest of the state goes with
        # this frame
        return loss, share_check.first_moment(state.opt_state), choices

    def reference_numbers(choices, check_seed=seed):
        """``(the plain reference's loss on the two sequences, its
        gradient, its own routing's choices [sequence, layer, position,
        k])`` with its experts evaluated under ``choices``, the step's
        (the head of ``benchmark/reference/kda_moe_lm.py`` says what stays
        the reference's own). The reference jits its own blocks, anew at
        every call: a fault put into the reference module shows."""
        two, _ = sample(seed)
        # the reference needs room: only parameters are alive beside it
        params = init(np.uint32(check_seed)).params
        return reference.loss_and_grad(params, two, arch, choices)

    def compare(got, want):
        """``(agrees, report)`` of the step's ``(loss, first moment,
        choices)`` against the reference's ``(loss, gradient, own
        choices)``."""
        routing = share_check.routing_numbers(
            got[2], want[2], expert_layers=routes,
            offset=deployment["expert_offset"], held=z["held"],
            experts=z["experts"], repeats=batch // 2,
            expected=_expected_slots(z, batch * seq))
        return share_check.compare(got, want, LIMITS, routing, PARTS)

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
        # (benchmark/reference/kda_moe_lm_faults.py)
        step_numbers=step_numbers, reference_numbers=reference_numbers,
        compare=compare,
        wants_pallas_kernel=bool(assumed["flash_attention"]))
