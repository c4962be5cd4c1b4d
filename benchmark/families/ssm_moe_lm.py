"""The hybrid state-space / sparse-expert decoder family (``model_type
nemotron_h``): ``models/transformer.py`` with layers of ONE sublayer, read
from ``hybrid_override_pattern`` (``M`` a Mamba-2 mixer, ``E`` relu^2
experts with a shared expert, ``*`` grouped-query attention without
position embedding), trained through
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

from benchmark.families.mla_moe_lm import ITEM, _expected_slots

# What decides ``correct``, and why these limits. The step computes in
# bfloat16 with float32 parameters, router, loss, softmax statistics and
# scan statistics (the step, the cumulative log-decay, the carried state);
# the reference is float32 at the highest matmul precision and runs the
# state-space layer as the recurrence it is. As in ``mla_moe_lm`` the
# reference evaluates its experts under the STEP'S choices (its scores,
# weights and router gradient stay its own), and the two choices' distance
# is a reading of its own. Every number below: my chip runs, PR 31,
# twenty seeds sound and sixteen faults planted into the reference
# (``benchmark/reference/ssm_moe_lm_faults.py``; PERF.md section 6).
#
# ``routing_apart``: the share of the token-slots the reference would
# choose, in the layer where it is largest, that the step did not choose
# for that token. It is the finest reading here: the scores are float32 on
# both sides, so what separates the choices is what the layers before the
# router did to the activations, and a near-tie feels the smallest of it.
# Sound 0.01290-0.01459 over twenty seeds (0.007 in the first expert
# layer, more in each later one). The scan's statistics in bfloat16 (the
# step, the cumulative log-decay summed and rounded inside a chunk, the
# carried state) 0.02244, the cumulative log-decay alone 0.02144, rotary
# applied in the one attention layer 0.02553; float8 operands 0.1341, the
# state dropped at every chunk boundary 0.1057, a combine without
# ``routed_scaling_factor`` 0.1646, the gate after the norm 0.383, relu
# for relu^2 0.499, no shared expert 0.844, no ``D * u`` 0.855. The limit
# stands 1.23 times above the largest sound reading and 1.25 times below
# the bfloat16 scan statistics. The step alone in bfloat16 (0.01324), the
# carried state alone (0.0144), the reference with bfloat16 operands, the
# configuration's OWN precision (0.01367), and a bfloat16 softmax or
# router lie inside the seeds' readings: a side as precise as the step is
# as right as the step.
# ``grad_error``: the distance between the gradient the step applied and
# the reference's, over the reference's norm. Sound 0.0361-0.0460; the
# reference at float8's three bits of mantissa, the precision below the
# one the configuration states, 0.2138; the state dropped at chunk
# boundaries 0.1812, unscaled 0.2775, and 0.65 to 1.33 for the gate after
# the norm, relu, no skip, no shared expert. The limit stands 1.52 times
# above the largest sound reading and 2.6 to 3 times below the first
# three. The bfloat16 scan statistics (0.0556) and rotary (0.0637) pass it
# and fail ``routing_apart``.
# ``loss``: sound 1.7e-6 to 9.1e-5 over twenty seeds (two past 7e-5), so
# the dense cells' 1.5e-4 would leave under twice of room; it keeps
# ``kanana-2-30b-a3b``'s 4.5e-4, five times above the largest sound
# reading. A bfloat16 log-softmax reads 1.66e-3, no skip 2.2e-3, relu
# 1.4e-3, no shared expert 1.6e-3; the gate after the norm (4.0e-4), float8
# (1.6e-5) and the unscaled combine (1.2e-4) pass it and fail others.
# ``grad_norm`` keeps the accepted cells' 1e-2: sound at most 4.5e-4;
# the gate after the norm 0.043, relu 0.116, no skip 0.38, no shared
# expert 0.055.
LIMITS = {"loss": 4.5e-4, "grad_norm": 1e-2, "grad_error": 0.07,
          "routing_apart": 0.018}
# Finer readings of the gradient's distance, over the leaves a name takes:
# read on every run's ``reference_check`` line and NOT judged (ten sound
# seeds are few to set a limit by; PERF.md section 7).
# ``scan_grad_error``, the two vectors that only the scan's statistics
# read, the step's bias and the decay's rate of every state-space layer:
# sound 0.0299-0.0510, the bfloat16 scan statistics 0.1329, the cumulative
# log-decay alone 0.1145, the state dropped at chunk boundaries 0.613.
# ``attention_grad_error``, the attention layer's four kernels: sound
# 0.0293-0.0375, rotary applied 0.1946.
PARTS = {
    "scan_grad_error": lambda path: path.endswith(
        ("['mixer']['dt_bias']", "['mixer']['A_log']")),
    "attention_grad_error": lambda path: "['attn']" in path,
}


def _sizes(config):
    pattern = config["hybrid_override_pattern"]
    if (len(pattern) != config["num_hidden_layers"]
            or set(pattern) - set("ME*")):
        raise ValueError(f"hybrid_override_pattern {pattern!r} names "
                         f"{len(pattern)} layers of M, E and *; "
                         f"num_hidden_layers is "
                         f"{config['num_hidden_layers']}")
    heads, width = config["mamba_num_heads"], config["mamba_head_dim"]
    return dict(
        pattern=pattern, d=config["hidden_size"],
        ssm_layers=pattern.count("M"), expert_layers=pattern.count("E"),
        attention_layers=pattern.count("*"),
        ssm_heads=heads, ssm_width=width, inner=heads * width,
        groups=config["n_groups"], states=config["ssm_state_size"],
        taps=config["conv_kernel"], chunk=config["chunk_size"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], d_head=config["head_dim"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["moe_shared_expert_intermediate_size"],
        held=config["n_routed_experts"],
        experts=config["deployment"]["router_width"],
        top_k=config["num_experts_per_tok"], vocab=config["vocab_size"])


def _ssm_parameters(z):
    """in_proj and out_proj (the convolution, the norms and the three
    per-head vectors multiply element by element)."""
    return (z["d"] * (2 * z["inner"] + 2 * z["groups"] * z["states"]
                      + z["ssm_heads"]) + z["inner"] * z["d"])


def _attention_parameters(z):
    return z["d"] * z["d_head"] * (2 * z["heads"] + 2 * z["kv_heads"])


def scan_flops_per_token(z):
    """FLOPs the scan of ONE state-space layer requires a token, forward
    and backward (three times the forward), at chunks of ``chunk``
    positions, nothing recomputed. Forward, a token: ``C B^T`` a group at
    half its square (2 * chunk * N / 2: a position sees the positions
    before it), ``(L o C B^T)(D u)`` a head at half its square
    (2 * chunk * P / 2), the chunk's end state (2 * P * N a head) and the
    inherited state's part ``C S`` (2 * N * P a head)."""
    chunk, p, n = z["chunk"], z["ssm_width"], z["states"]
    return 3 * (z["groups"] * chunk * n
                + z["ssm_heads"] * (chunk * p + 4 * p * n))


def scan_bytes_per_token(z):
    """Bytes the scan of ONE state-space layer must move a token, in
    bfloat16: forward ``u, B, C`` and the step read and ``o`` written;
    backward the same four and ``dO`` read and the four gradients
    written."""
    inputs = (z["inner"] + 2 * z["groups"] * z["states"] + z["ssm_heads"])
    return 2 * ((inputs + z["inner"]) + (inputs + z["inner"]) + inputs)


def required_flops_per_item(config, traffic):
    """FLOPs the forward and backward passes of ONE token require, from
    shapes alone: 6 a parameter of every matrix a token is multiplied by
    (2 forward, 4 backward): the state-space layers' two projections,
    attention's four, the routers, the shared experts, the head; the
    routed experts at the EXPECTED number of held experts a token
    (``_expected_slots``: 0.375 of the 6 chosen at 8 of 128); causal
    attention at half its square (2 * S * 2 * d_head a head forward for
    the whole square, twice that backward, halved); and the scan
    (``scan_flops_per_token``). No recomputation (the step recomputes the
    scan and the expert share: not required work), no embedding lookup,
    no convolution, no elementwise work."""
    z = _sizes(config)
    relu2 = lambda width: 2 * z["d"] * width  # noqa: E731
    matmul = 6 * (
        z["ssm_layers"] * _ssm_parameters(z)
        + z["attention_layers"] * _attention_parameters(z)
        + z["expert_layers"] * (relu2(z["d_shared"])
                                + z["d"] * z["experts"]
                                + _expected_slots(z, 1)
                                * relu2(z["d_expert"]))
        + z["d"] * z["vocab"])
    attention = (z["attention_layers"] * 3 * 0.5 * z["heads"]
                 * 2 * traffic["seq_len"] * 2 * z["d_head"])
    return matmul + attention + z["ssm_layers"] * scan_flops_per_token(z)


def kernel_work(config, traffic):
    """What one step asks of its kernels on ONE chip, forward and backward
    summed, nothing recomputed.

    ``flops``/``bytes``: the flash kernel, one attention layer. Per batch
    row and query head, forward half of 4 * S^2 * d_head FLOPs and twice
    that backward; q, o (and dO, dq) a query head and k, v (and dk, dv) a
    KEY/VALUE head (what is required: the step broadcasts them before the
    kernel), in bfloat16, and the float32 row statistics (once forward,
    lse and delta backward).

    ``grouped_flops``/``grouped_bytes``: the routed experts' grouped
    products, at the expected number of held token-slots a layer
    (``_expected_slots``): 6 * 2 * d * d_expert FLOPs a slot; bytes of
    the two products of a relu^2 expert taken one by one, each reading
    its two operands and writing its result once, forward, input gradient
    and weight gradient (bfloat16): 2 * 3 * 2 * (m*k + m*n + held*k*n).

    ``scan_flops``/``scan_bytes``: the state-space layers' scans
    (``scan_flops_per_token``, ``scan_bytes_per_token``)."""
    z = _sizes(config)
    b, s = traffic["per_chip_batch"], traffic["seq_len"]
    rows = z["attention_layers"] * b
    fwd = 0.5 * 4 * s * s * z["d_head"]
    tensor, stats = s * z["d_head"] * 2, s * 4
    slots = _expected_slots(z, b * s)
    d, f = z["d"], z["d_expert"]
    one_product = slots * (d + f) + z["held"] * d * f
    tokens = b * s * z["ssm_layers"]
    return {"flops": rows * z["heads"] * 3 * fwd,
            "bytes": rows * (z["heads"] * (6 * tensor + 3 * stats)
                             + z["kv_heads"] * 6 * tensor),
            "grouped_flops": z["expert_layers"] * slots * 6 * 2 * d * f,
            "grouped_bytes": z["expert_layers"] * 2 * 3 * 2 * one_product,
            "scan_flops": tokens * scan_flops_per_token(z),
            "scan_bytes": tokens * scan_bytes_per_token(z)}


LAYER = {"M": ("ssm", None), "E": (None, "experts"), "*": ("mha", None)}


def build(config, traffic, mesh, seed):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from benchmark.harness import share_check
    from benchmark.reference import ssm_moe_lm as reference
    from horovod_tpu import training
    from horovod_tpu.models.experts import (ExpertShareConfig,
                                            GroupedFallbackWarning)
    from horovod_tpu.models.ssm import StateSpaceConfig
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
    # what the program's state-space mixer, attention and expert layer
    # fix: a file that asks for anything else would be run as this under
    # its own name
    fixed = {"model_type": "nemotron_h", "mamba_hidden_act": "silu",
             "mlp_hidden_act": "relu2", "mamba_proj_bias": False,
             "use_conv_bias": True, "attention_bias": False,
             "mlp_bias": False, "use_bias": False, "sliding_window": None,
             "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
             "n_shared_experts": 1, "tie_word_embeddings": False,
             "residual_in_fp32": False,
             "norm_eps": config["layer_norm_epsilon"]}
    asked = {key: config[key] for key in fixed}
    if asked != fixed or assumed["compute_dtype"] != "bfloat16":
        raise ValueError(f"the ssm_moe_lm family runs {fixed} in bfloat16; "
                         f"the configuration asks for {asked}, "
                         f"{assumed['compute_dtype']}")
    cfg = TransformerConfig(
        vocab_size=z["vocab"], num_layers=len(z["pattern"]),
        num_heads=z["heads"], num_kv_heads=z["kv_heads"],
        head_dim=z["d_head"], rotary=False, d_model=z["d"],
        d_ff=config["intermediate_size"], dtype=jnp.bfloat16,
        norm_eps=config["layer_norm_epsilon"], sequence_axis=None,
        flash_attention=assumed["flash_attention"],
        layer_pattern=tuple(LAYER[kind] for kind in z["pattern"]),
        ssm=StateSpaceConfig(
            num_heads=z["ssm_heads"], head_dim=z["ssm_width"],
            n_groups=z["groups"], state_size=z["states"],
            conv_kernel=z["taps"], chunk_size=z["chunk"],
            time_step_min=config["time_step_min"],
            time_step_max=config["time_step_max"],
            time_step_floor=config["time_step_floor"]),
        experts=ExpertShareConfig(
            n_routed_experts=z["experts"], experts_held=z["held"],
            expert_offset=deployment["expert_offset"],
            num_experts_per_tok=z["top_k"], moe_d_ff=z["d_expert"],
            n_shared_experts=config["n_shared_experts"],
            shared_d_ff=z["d_shared"], expert_body="relu2",
            routed_scaling_factor=config["routed_scaling_factor"],
            selection_bias_std=assumed["selection_bias_std"]))
    arch = {"mamba_head_dim": z["ssm_width"], "n_groups": z["groups"],
            "ssm_state_size": z["states"],
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
    routes = [i for i, kind in enumerate(z["pattern"]) if kind == "E"]

    @jax.jit
    def chosen(params, tokens):
        """The experts the program's routing chooses for ``tokens``,
        [B, L, S, k] (the rows of a layer that routes nothing are zeros):
        the forward pass of the timed step's model, which sows each
        share's choice."""
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
        (the head of ``benchmark/reference/ssm_moe_lm.py`` says what stays
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
        # (benchmark/reference/ssm_moe_lm_faults.py)
        step_numbers=step_numbers, reference_numbers=reference_numbers,
        compare=compare,
        wants_pallas_kernel=bool(assumed["flash_attention"]))
