"""The decoder LM family: ``models/transformer.py`` trained through
``hvd.DistributedOptimizer(optax.adamw)`` and
``training.make_lm_train_step``, the way a user builds it.

A configuration file of this family carries the published keys of a
GPT-NeoX ``config.json`` (``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``intermediate_size``, ``vocab_size``) and what
the source does not give under ``assumed``.
"""

import dataclasses
import types

import numpy as np

ITEM = "tokens"
ADAM_B1 = 0.9  # optax.adamw's default, which the step is built with

# The step computes in bfloat16 with float32 parameters, accumulation and
# loss; the reference is float32 at the highest matmul precision. What
# separates them is the rounding of activations and gradients to 8 bits
# of mantissa (2^-9 = 0.2% an element), which averages out over the 10^8
# terms of a norm. Measured on the chip over 44 runs of three cells, each
# another seed (PERF.md, Findings): the loss differs by at most 2.7e-5
# relative, the gradient norm by at most 1.9e-3. The tolerances leave five
# times that. A gradient sum left undivided by the world size is off by a
# factor of the world size, a stale or unexchanged gradient by tens of
# percent (each half of the chips sees another sequence: the reference fed
# another seed's parameters missed by 2.7e-2), and a softmax, loss or
# gradient accumulated in bfloat16 loses these numbers' third digit.
RTOL = {"loss": 1.5e-4, "grad_norm": 1e-2}


def _sizes(config):
    d = config["hidden_size"]
    return dict(layers=config["num_hidden_layers"], d=d,
                heads=config["num_attention_heads"],
                d_head=d // config["num_attention_heads"],
                d_ff=config["intermediate_size"],
                vocab=config["vocab_size"])


def required_flops_per_item(config, traffic):
    """FLOPs the forward and backward passes of ONE token require, from
    shapes alone: 6 a parameter of every matrix a token is multiplied by
    (2 forward, 4 backward), plus causal attention at half its square
    (QK^T and PV: 4*S*d forward for the whole square, twice that
    backward, halved). No recomputation, no embedding lookup, no
    elementwise work."""
    z = _sizes(config)
    per_layer = 4 * z["d"] * z["d"] + 2 * z["d"] * z["d_ff"]
    matmul = 6 * (z["layers"] * per_layer + z["d"] * z["vocab"])
    attention = z["layers"] * 3 * 0.5 * 4 * traffic["seq_len"] * z["d"]
    return matmul + attention


def kernel_work(config, traffic):
    """What one step asks of the flash kernel on ONE chip, forward and
    backward summed: FLOPs (causal half; backward = dV, dP, dQ, dK = twice
    the forward; the recomputation of QK^T that the kernel chooses is not
    required work) and the bytes that must cross HBM (q, k, v, o and the
    row statistics once forward; q, k, v, o, do in and dq, dk, dv out
    backward; bfloat16, statistics float32)."""
    z = _sizes(config)
    b, s = traffic["per_chip_batch"], traffic["seq_len"]
    calls = z["layers"] * b * z["heads"]
    fwd = 0.5 * 4 * s * s * z["d_head"]
    tensor = s * z["d_head"] * 2
    stats = s * 4
    return {"flops": calls * 3 * fwd,
            "bytes": calls * ((4 * tensor + stats)
                              + (8 * tensor + 2 * stats))}


def build(config, traffic, mesh, seed):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from benchmark.harness import check
    from benchmark.reference import decoder_lm as reference
    from horovod_tpu import training
    from horovod_tpu.models.transformer import (Transformer,
                                                TransformerConfig)

    z = _sizes(config)
    assumed = config["assumed"]
    chips = mesh.devices.size
    batch, seq = traffic["per_chip_batch"] * chips, traffic["seq_len"]
    if seq > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {seq} is past the configuration's "
                         f"{config['max_position_embeddings']} positions")
    # what the repo's one decoder block fixes: a file that asks for
    # anything else would be run as this anyway, under its own name
    fixed = {"rotary_emb_base": 10000, "hidden_act": "gelu",
             "tie_word_embeddings": False}
    asked = {key: config[key] for key in fixed}
    if asked != fixed or assumed["compute_dtype"] != "bfloat16":
        raise ValueError(f"the decoder_lm family runs {fixed} in bfloat16; "
                         f"the configuration asks for {asked}, "
                         f"{assumed['compute_dtype']}")
    cfg = TransformerConfig(
        vocab_size=z["vocab"], num_layers=z["layers"], num_heads=z["heads"],
        d_model=z["d"], d_ff=z["d_ff"], dtype=jnp.bfloat16,
        sequence_axis=None, flash_attention=assumed["flash_attention"])
    # parameters do not depend on the attention path: initialise without
    # the kernel, on a few positions
    init_model = Transformer(dataclasses.replace(cfg, flash_attention=False))
    tx = hvd.DistributedOptimizer(optax.adamw(assumed["learning_rate"]),
                                  axes=("data",))
    step = training.make_lm_train_step(Transformer(cfg), tx, mesh=mesh,
                                       batch_axis="data")
    replicated = NamedSharding(mesh, P())
    by_batch = NamedSharding(mesh, P("data"))
    seed = np.uint32(seed)

    # the seed is an argument and every array is made inside one jitted
    # call: nothing is initialised eagerly, nothing is captured
    init = jax.jit(lambda s: training.create_train_state(
        init_model, tx, jax.random.fold_in(jax.random.PRNGKey(s), 0),
        jnp.zeros((1, 16), jnp.int32)), out_shardings=replicated)
    draw = jax.jit(lambda s: jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(s), 1), (batch, seq), 0,
        z["vocab"], jnp.int32), out_shardings=by_batch)

    def draw_sample(s):
        two = jax.random.randint(
            jax.random.fold_in(jax.random.PRNGKey(s), 2), (2, seq), 0,
            z["vocab"], jnp.int32)
        # blocks, not tiles: the first half of the chips sees only the
        # first sequence and the second half only the second, so the
        # step's gradient equals the reference's only if the exchange
        # averaged over the chips
        return two, jnp.repeat(two, batch // 2, axis=0)

    sample = jax.jit(draw_sample, out_shardings=(replicated, by_batch))
    ref = jax.jit(lambda params, two: reference.loss_and_grad_norm(
        params, two, num_layers=z["layers"]))

    def reference_check(check_seed=seed):
        """One step of the step under test on two seeded sequences
        repeated to its batch, against the plain reference on the two.
        (``check_seed``: another seed's parameters for the reference show
        that the check can fail.)"""
        two, repeated = sample(seed)
        state = init(seed)
        state, loss = step(state, repeated)
        got = {"loss": loss,
               "grad_norm": check.first_moment_norm(
                   state.opt_state, optax.ScaleByAdamState, "mu")
               / (1.0 - ADAM_B1)}
        del state
        # the reference needs room: only parameters are alive beside it
        params = init(np.uint32(check_seed)).params
        want = dict(zip(("loss", "grad_norm"), ref(params, two)))
        return check.compare(got, want, RTOL)

    return types.SimpleNamespace(
        item=ITEM, items_per_step=batch * seq, step=step,
        init_state=lambda: init(seed), batch=lambda: (draw(seed),),
        reference_check=reference_check,
        wants_pallas_kernel=bool(assumed["flash_attention"]))
