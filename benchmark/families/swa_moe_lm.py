"""The window / full attention hybrid decoder family with sparse experts
(``model_type laguna``): ``models/transformer.py`` with a layer's mixer
read from ``layer_types`` (``full_attention`` or ``sliding_attention``:
two kinds of multi-head attention, each with its own query heads from
``num_attention_heads_per_layer``, its own rotary from ``rope_parameters``,
the sliding kind with ``sliding_window``, both with a sigmoid output gate a
head) and its feed-forward from ``mlp_layer_types`` (a dense SwiGLU, or
top-k SwiGLU experts with one shared expert), trained through
``hvd.DistributedOptimizer(optax.adamw)`` and
``training.make_lm_train_step``, the way a user builds it.

A configuration file of this family carries the keys of the model's own
``config.json`` and, under ``deployment``, over how many chips each layer
is divided and which experts this chip holds: ``num_experts`` and
``vocab_size`` are what is HELD here, ``published`` has the model's
counts, and the router keeps the published width. What ``config.json``
leaves open is under ``assumed``.
"""

import dataclasses
import functools
import types
import warnings

import numpy as np

from benchmark.families.mla_moe_lm import ITEM, _expected_slots

# What decides ``correct``, and why these limits. The step computes in
# bfloat16 with float32 parameters, router, loss and softmax statistics;
# the reference is float32 at the highest matmul precision, its mask
# built from positions and its rotary from the published formulas. As in
# the three other sparse families the reference evaluates its experts
# under the STEP'S choices (its scores, weights and router gradient stay
# its own), and the two choices' distance is a reading of its own. Every
# number below: my chip runs, PR 37, twenty-one seeds sound (nineteen for
# ``attention_apart``) and sixteen faults planted into the reference on
# two seeds (``benchmark/reference/swa_moe_lm_faults.py``; PERF.md
# section 6).
#
# ``routing_apart``: the share of the token-slots the reference would
# choose, in the layer where it is largest (the last of seven: 0.016 in
# the first expert layer, more in each later one), that the step did not
# choose for that token. Sound 0.03293-0.03517. Float8 operands 0.313,
# the window left out of the sliding layers 0.192, the sliding layers
# with the full layers' 48 heads 0.158, a combine without
# ``moe_routed_scaling_factor`` 0.139, and 0.82 to 0.97 for the gate left
# out or a silu, the rotary over the whole head of a full layer, YaRN's
# blend or its factor on cos and sin left out, the two thetas swapped, no
# shared expert. The limit stands 1.19 times above the largest sound
# reading and 3.3 times below the nearest fault. The reference with
# bfloat16 operands, the configuration's OWN precision (0.0350), a
# bfloat16 router (0.0340) and a bfloat16 softmax (0.0390) pass: a side as
# precise as the step is as right as the step.
# ``grad_error``: the distance between the gradient the step applied and
# the reference's, over the reference's norm. Sound 0.0695-0.0765; the
# reference at float8's three bits of mantissa, the precision below the
# one the configuration states, 0.498-0.501; no window 0.311, 48 heads in
# a sliding layer's place 0.248, unscaled 0.241, and 1.26 to 2.04 for the
# gate's, the rotary's and the shared expert's faults. The limit stands
# 1.31 times above the largest sound reading and 2.4 times below the
# nearest fault.
# ``attention_apart``: the distance between the attention of a layer of
# each kind as the timed model calls it (``models.transformer.attend``:
# the flash kernel with the kind's window, after the key/value head's
# broadcast) and the reference's (``reference.attend``) on seeded q, k
# and v of one key/value head's query heads at the timed sequence, over
# the reference's norm, the larger of the two kinds. It is here for what
# a whole step cannot show: **a window of 511 or 513**. With random
# weights every key of a window weighs about 1/512 of its query's softmax,
# and one key more or fewer moves ``grad_error`` by 0.001 and
# ``routing_apart`` by 0.001-0.0014, less than the seeds do. On the probe
# sound reads 0.00215-0.00218 (full 0.00206-0.00213), a window one short
# or one long 0.0381-0.0393, float8 operands 0.0526, no window 1.22-1.25;
# bfloat16 operands 0.0027 and a bfloat16 softmax 0.0046 pass. The limit
# stands 4.6 times above the largest sound reading and 3.8 times below
# the window's faults.
# ``loss``: sound 1.8e-6 to 9.5e-5 over twenty-one seeds; it keeps the
# accepted sparse cells' 4.5e-4, 4.7 times above the largest sound
# reading. A bfloat16 log-softmax reads 1.28e-3 and 7.3e-4; the gate's
# faults 1.4e-3 to 1.8e-3; the rotary's and the shared expert's on one
# seed of two (6e-5 to 2.4e-3): they fail others on both.
# ``grad_norm`` keeps the accepted cells' 1e-2: sound at most 8.8e-4; no
# shared expert 0.74-0.78, YaRN's factor left out 0.27, the rotary over
# the whole head 0.154, the gate's faults 0.07-0.09.
LIMITS = {"loss": 4.5e-4, "grad_norm": 1e-2, "grad_error": 0.10,
          "routing_apart": 0.042, "attention_apart": 0.01}
# A finer reading of the gradient's distance, over the attention layers'
# leaves: read on every run's ``reference_check`` line and NOT judged.
PARTS = {"attention_grad_error": lambda path: "['attn']" in path}
KINDS = {"full_attention": "full", "sliding_attention": "sliding"}


def _sizes(config):
    layers = config["num_hidden_layers"]
    per_layer = (config["layer_types"], config["mlp_layer_types"],
                 config["num_attention_heads_per_layer"])
    if any(len(listed) != layers for listed in per_layer):
        raise ValueError(f"layer_types, mlp_layer_types and "
                         f"num_attention_heads_per_layer name "
                         f"{[len(x) for x in per_layer]} layers of "
                         f"{layers}")
    heads = {}
    for kind, h in zip(config["layer_types"], per_layer[2]):
        if heads.setdefault(KINDS[kind], h) != h:
            raise ValueError(f"{kind} layers with {heads[KINDS[kind]]} and "
                             f"{h} query heads: a kind has one size")
    kinds = [KINDS[kind] for kind in config["layer_types"]]
    return dict(
        pattern=tuple((kind, {"dense": "swiglu", "sparse": "experts"}[ff])
                      for kind, ff in zip(kinds, config["mlp_layer_types"])),
        kinds=kinds, heads=heads,
        full_layers=kinds.count("full"),
        sliding_layers=kinds.count("sliding"),
        dense_layers=config["mlp_layer_types"].count("dense"),
        expert_layers=config["mlp_layer_types"].count("sparse"),
        d=config["hidden_size"], kv_heads=config["num_key_value_heads"],
        head=config["head_dim"], window=config["sliding_window"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["shared_expert_intermediate_size"],
        held=config["num_experts"],
        experts=config["deployment"]["router_width"],
        top_k=config["num_experts_per_tok"], vocab=config["vocab_size"])


def _attention_parameters(z, kind):
    """q, k, v, the gate a head and the output projection of one layer."""
    h = z["heads"][kind]
    return (z["d"] * z["head"] * (2 * h + 2 * z["kv_heads"]) + z["d"] * h)


def _pairs(z, kind, s):
    """The (query, key) pairs one head's mask leaves of ``s`` positions:
    the causal half-square with its diagonal, or the band of ``window``
    positions under it."""
    w = z["window"]
    if kind == "full" or w >= s:
        return s * (s + 1) // 2
    return w * s - w * (w - 1) // 2


def parameters(config):
    """What the configuration's ``parameters`` states: every leaf of the
    tree held here."""
    z = _sizes(config)
    swiglu = lambda width: 3 * z["d"] * width  # noqa: E731
    norms = 2 * z["d"] * len(z["pattern"]) + z["d"]
    return (sum(_attention_parameters(z, kind) for kind in z["kinds"])
            + z["dense_layers"] * swiglu(z["d_ff"])
            + z["expert_layers"] * (swiglu(z["d_shared"])
                                    + z["d"] * z["experts"] + z["experts"]
                                    + z["held"] * swiglu(z["d_expert"]))
            + 2 * z["d"] * z["vocab"] + norms)


def required_flops_per_item(config, traffic):
    """FLOPs the forward and backward passes of ONE token require, from
    shapes alone: 6 a parameter of every matrix a token is multiplied by
    (2 forward, 4 backward): the attention layers' projections and gates,
    the dense SwiGLU, the routers, the shared experts, the head; the
    routed experts at the EXPECTED number of held experts a token
    (``_expected_slots``: 0.25 of the 8 chosen at 8 of 256); and the
    scores and their products with v (2 x 128 each a pair forward, twice
    that backward) at the pairs the mask leaves (``_pairs``: half the
    square in a full layer, a band of 512 in a sliding one). No
    recomputation, no embedding lookup, no rotary, no elementwise work."""
    z = _sizes(config)
    s = traffic["seq_len"]
    swiglu = lambda width: 3 * z["d"] * width  # noqa: E731
    matmul = 6 * (
        sum(_attention_parameters(z, kind) for kind in z["kinds"])
        + z["dense_layers"] * swiglu(z["d_ff"])
        + z["expert_layers"] * (swiglu(z["d_shared"])
                                + z["d"] * z["experts"]
                                + _expected_slots(z, 1)
                                * swiglu(z["d_expert"]))
        + z["d"] * z["vocab"])
    scores = sum(3 * z["heads"][kind] * _pairs(z, kind, s) * 2
                 * 2 * z["head"] for kind in z["kinds"]) / s
    return matmul + scores


def kernel_work(config, traffic):
    """What one step asks of its kernels on ONE chip, forward and backward
    summed, nothing recomputed, from shapes alone and the same whatever
    implements it.

    ``full_flops``/``full_bytes`` and ``window_flops``/``window_bytes``:
    the attention of the full and of the sliding layers. Per layer, batch
    row and query head, forward ``2 * pairs * (128 + 128)`` FLOPs
    (``_pairs``: S (S + 1) / 2 full, W S - W (W - 1) / 2 windowed) and
    twice that backward; q, k, v, o (and dO, dq, dk, dv) in bfloat16 and
    the float32 row statistics (once forward, lse and delta backward). k
    and v are counted once a QUERY head, as the kernel is given them
    today (the key/value heads broadcast before it): a kernel that reads
    the shared head itself will read less than this count, and a
    ``benchmark`` PR then restates it.

    ``window_shape``: ``(S, W)`` of the sliding layers' calls, for the
    reader of the block schedule.

    ``grouped_flops``/``grouped_bytes``: the routed experts' grouped
    products, at the expected number of held token-slots a layer
    (``_expected_slots``), as the three other sparse families count
    them."""
    z = _sizes(config)
    b, s = traffic["per_chip_batch"], traffic["seq_len"]
    tensor, stats = s * z["head"] * 2, s * 4
    bytes_a_call = (4 * tensor + stats) + (8 * tensor + 2 * stats)
    work = {}
    for name, kind, layers in (("full", "full", z["full_layers"]),
                               ("window", "sliding", z["sliding_layers"])):
        calls = layers * b * z["heads"].get(kind, 0)
        work[f"{name}_flops"] = (calls * 3 * 2 * _pairs(z, kind, s)
                                 * 2 * z["head"])
        work[f"{name}_bytes"] = calls * bytes_a_call
    slots = _expected_slots(z, b * s)
    d, f = z["d"], z["d_expert"]
    one_product = slots * (d + f) + z["held"] * d * f
    return {**work, "window_shape": [s, z["window"]],
            "grouped_flops": z["expert_layers"] * slots * 6 * 3 * d * f,
            "grouped_bytes": z["expert_layers"] * 3 * 3 * 2 * one_product}


def attention_kinds(config):
    """``{kind: keyword arguments of models.transformer.AttentionConfig}``
    (``yarn``: those of ``YarnScaling``, or None) and ``{kind: the
    reference's description of it}``, both from ``rope_parameters``,
    ``sliding_window`` and the heads a kind's layers have."""
    z = _sizes(config)
    program, reference = {}, {}
    for listed, kind in KINDS.items():
        if kind not in z["heads"]:
            continue
        rope = config["rope_parameters"][listed]
        rotary_dim = int(rope["partial_rotary_factor"] * z["head"])
        yarn = None
        if rope["rope_type"] == "yarn":
            yarn = {"factor": rope["factor"],
                    "original_max_position_embeddings":
                        rope["original_max_position_embeddings"],
                    "beta_fast": rope["beta_fast"],
                    "beta_slow": rope["beta_slow"],
                    "attention_factor": rope["attention_factor"]}
        elif rope["rope_type"] != "default":
            raise ValueError(f"rope_type {rope['rope_type']!r}: the family "
                             f"knows default and yarn")
        window = z["window"] if kind == "sliding" else None
        program[kind] = dict(
            kind=kind, num_heads=z["heads"][kind], head_dim=z["head"],
            num_kv_heads=z["kv_heads"], window=window,
            rope_theta=float(rope["rope_theta"]), rotary_dim=rotary_dim,
            yarn=yarn, gate=True)
        reference[kind] = {"sliding_window": window,
                           "rope_theta": rope["rope_theta"],
                           "rotary_dim": rotary_dim, "yarn": yarn}
    return program, reference


def build(config, traffic, mesh, seed):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from benchmark.harness import share_check
    from benchmark.reference import swa_moe_lm as reference
    from horovod_tpu import training
    from horovod_tpu.models.experts import (ExpertShareConfig,
                                            GroupedFallbackWarning)
    from horovod_tpu.models.transformer import (AttentionConfig,
                                                Transformer,
                                                TransformerConfig,
                                                YarnScaling, attend)

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
    fixed = {"model_type": "laguna", "attention_bias": False,
             "gating": True, "tie_word_embeddings": False,
             "moe_apply_router_weight_on_input": False}
    asked = {key: config[key] for key in fixed}
    if asked != fixed or assumed["compute_dtype"] != "bfloat16":
        raise ValueError(f"the swa_moe_lm family runs {fixed} in bfloat16; "
                         f"the configuration asks for {asked}, "
                         f"{assumed['compute_dtype']}")
    kinds, described = attention_kinds(config)
    for sized in kinds.values():
        if sized["yarn"] is not None:
            sized["yarn"] = YarnScaling(**sized["yarn"])
    cfg = TransformerConfig(
        vocab_size=z["vocab"], num_layers=len(z["pattern"]),
        num_heads=max(z["heads"].values()), d_model=z["d"], d_ff=z["d_ff"],
        dtype=jnp.bfloat16, norm_eps=config["rms_norm_eps"],
        sequence_axis=None, flash_attention=assumed["flash_attention"],
        layer_pattern=z["pattern"],
        attention=tuple(AttentionConfig(**sized)
                        for sized in kinds.values()),
        experts=ExpertShareConfig(
            n_routed_experts=z["experts"], experts_held=z["held"],
            expert_offset=deployment["expert_offset"],
            num_experts_per_tok=z["top_k"], moe_d_ff=z["d_expert"],
            n_shared_experts=1, shared_d_ff=z["d_shared"],
            routed_scaling_factor=config["moe_routed_scaling_factor"],
            selection_bias_std=assumed["selection_bias_std"]))
    arch = {"layer_kinds": z["kinds"], "attention": described,
            "num_experts_per_tok": z["top_k"],
            "routed_scaling_factor": config["moe_routed_scaling_factor"],
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
    # the check's sequences: two where the batch has room for both, as in
    # the other sparse families; the one the step takes at a batch of one
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
        # blocks, not tiles, as in decoder_lm: each half of a mesh's
        # chips would see one of the two sequences
        return sequences, jnp.repeat(sequences, batch // checked, axis=0)

    sample = jax.jit(draw_sample, out_shardings=(replicated, by_batch))
    routes = [i for i, (_, feed_forward) in enumerate(z["pattern"])
              if feed_forward == "experts"]

    def draw_probe(s, kind):
        """Seeded q, k and v for one key/value head and its query heads of
        ``kind`` at the timed sequence, [1, S, H / shared, e] and twice
        [1, S, 1, e], rounded to bfloat16 on both sides' behalf."""
        a = cfg.attention_kind(kind)
        shapes = [(1, seq, a.num_heads // a.num_kv_heads, a.head_dim)] + [
            (1, seq, 1, a.head_dim)] * 2
        keys = jax.random.split(jax.random.fold_in(
            jax.random.PRNGKey(s), 3 + z["kinds"].index(kind)), 3)
        return tuple(jax.random.normal(key, shape, jnp.float32).astype(
            jnp.bfloat16) for key, shape in zip(keys, shapes))

    @functools.partial(jax.jit, static_argnums=1)
    def probed(s, kind):
        """The attention of a layer of ``kind`` as the timed model calls
        it (``models.transformer.attend``: the kernel under the cell's
        configuration, after the key/value head's broadcast) on
        ``draw_probe``'s q, k and v."""
        a = cfg.attention_kind(kind)
        q, k, v = draw_probe(s, kind)
        k, v = (jnp.repeat(t, q.shape[2], axis=2) for t in (k, v))
        positions = jnp.arange(seq)[None]
        return attend(cfg, a, q, k, v, positions, True)[0]

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

    def probe_numbers():
        """``{kind: the program's attention on the seeded probe}``."""
        return {kind: probed(seed, kind) for kind in described}

    def reference_probe():
        """``{kind: the reference's attention on the same q, k and v}``,
        float32 at its precision, jitted anew at every call (a fault put
        into the reference module shows)."""
        with jax.default_matmul_precision(reference.PRECISION):
            return {kind: jax.jit(functools.partial(
                reference.attend, kind=described[kind]))(*(
                    t.astype(jnp.float32) for t in draw_probe(seed, kind)))
                    for kind in described}

    def attention_apart(got, want):
        """``{kind: |program - reference| / |reference|}`` of the two
        sides' attention on the probe."""
        return {kind: float(
            jnp.linalg.norm(got[kind].astype(jnp.float32) - want[kind])
            / jnp.linalg.norm(want[kind])) for kind in described}

    def step_numbers():
        """One step of the timed step on the seeded sequences repeated to
        its batch: ``(loss, Adam's first moment, the routing's choices on
        the sequences, each kind's attention on the seeded probe)``. A
        step shows no choice, so the choices are those of the same
        model's forward pass on the same batch from the same
        parameters."""
        _, repeated = sample(seed)
        state = init(seed)
        choices = chosen(state.params, repeated)[::batch // checked]
        state, loss = step(state, repeated)
        # the first moment's own buffers: the rest of the state goes with
        # this frame
        return (loss, share_check.first_moment(state.opt_state), choices,
                probe_numbers())

    def reference_numbers(choices, check_seed=seed):
        """``(the plain reference's loss on the sequences, its gradient,
        its own routing's choices [sequence, layer, position, k], its
        attention on the seeded probe)`` with
        its experts evaluated under ``choices``, the step's (the head of
        ``benchmark/reference/swa_moe_lm.py`` says what stays the
        reference's own). The reference jits its own blocks, anew at
        every call: a fault put into the reference module shows."""
        sequences, _ = sample(seed)
        # the reference needs room: only parameters are alive beside it
        params = init(np.uint32(check_seed)).params
        return (*reference.loss_and_grad(params, sequences, arch, choices),
                reference_probe())

    def compare(got, want):
        """``(agrees, report)`` of the step's ``(loss, first moment,
        choices, probed attention)`` against the reference's ``(loss,
        gradient, own choices, attention on the same q, k, v)``."""
        routing = share_check.routing_numbers(
            got[2], want[2], expert_layers=routes,
            offset=deployment["expert_offset"], held=z["held"],
            experts=z["experts"], repeats=batch // checked,
            expected=_expected_slots(z, batch * seq))
        agrees, report = share_check.compare(got, want, LIMITS, routing,
                                             PARTS)
        apart = attention_apart(got[3], want[3])
        worst = max(apart.values())
        report["attention_apart"] = {
            "step": worst, "reference": 0.0, "relative_error": worst,
            "tolerance": LIMITS["attention_apart"], "by_kind": apart,
            "agrees": bool(worst <= LIMITS["attention_apart"])}
        return agrees and report["attention_apart"]["agrees"], report

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
        # (benchmark/reference/swa_moe_lm_faults.py)
        step_numbers=step_numbers, reference_numbers=reference_numbers,
        compare=compare, probe_numbers=probe_numbers,
        reference_probe=reference_probe, attention_apart=attention_apart,
        wants_pallas_kernel=bool(assumed["flash_attention"]))
