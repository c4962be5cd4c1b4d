"""Faults for the plain reference of ``kda_moe_lm``, and the study that
reads what the family's comparison says of each.

``correct`` compares one step of the timed step with the reference
(``families/kda_moe_lm.py``: loss, gradient norm, gradient distance, the
two routings' distance). Its limits mean something only beside what a
faulty side reads, so the faults live here, in the repo: each is planted
into the REFERENCE module (the step under test is the timed one and
stays), the comparison is symmetric in what it measures, and
``tests/test_kda_moe_lm.py`` plants every one of them at a small size on
the CPU. On the chip:

    python3 -m benchmark.reference.kda_moe_lm_faults <seed>... [<fault>...]

prints, a JSON line each: every seed's sound readings, then on the first
seed the readings under each fault (or under those named), ``agrees`` and
the limits that told it. PERF.md (section 6, PR 33) holds what the chip
read.
"""

import contextlib
import json
import sys

import jax
import jax.numpy as jnp

from benchmark.reference import kda_moe_lm as reference


def _bf16(x):
    """``x`` rounded to bfloat16's eight bits. (A pair of converts would
    not do: the TPU compiler may keep the excess precision and drop it.)"""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _rounded(f):
    """``f`` with its first argument through bfloat16 and its result too."""
    return lambda x, *rest: f(x.astype(jnp.bfloat16), *rest).astype(
        jnp.float32)


def _faults(config):
    """``{name: {attribute of the reference module: its faulty value}}``.
    ``bfloat16_operands`` is no fault: it is the precision the
    configuration states, and shows what a side as precise as the step
    reads. ``float8_operands`` is the precision below it."""
    chunk_size = config["assumed"]["kda_chunk_size"]  # two faults know it
    theta = float(config["rope_theta"])
    sound = {name: getattr(reference, name) for name in (
        "_log_decay", "_delta_step", "_carried", "_route", "_shared",
        "_cross_entropy", "_softmax", "_scores")}

    def scalar_decay(a_log, rate, dt_bias):
        """One decay a head, the mean of the channels' log-decays: a
        gated delta rule, not this one."""
        g = sound["_log_decay"](a_log, rate, dt_bias)
        return jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)

    def cumulative_decay_bfloat16(a_log, rate, dt_bias):
        """What a chunked scan reads whose running sum of g is summed in
        bfloat16 inside each chunk: every position's decay becomes the
        difference of two rounded partial sums."""
        g = sound["_log_decay"](a_log, rate, dt_bias)
        bsz, s, h, d = g.shape
        chunk = min(chunk_size, s)
        cum = _bf16(jnp.cumsum(g.reshape(bsz, s // chunk, chunk, h, d), 2))
        return jnp.concatenate([cum[:, :, :1], cum[:, :, 1:]
                                - cum[:, :, :-1]], 2).reshape(g.shape)

    def decay_after_correction(state, k, v, g, beta):
        u = beta[..., None] * (v - reference._mm("bhde,bhd->bhe", state, k))
        return jnp.exp(g)[..., None] * (
            state + reference._mm("bhd,bhe->bhde", k, u))

    def dropped_at_chunk_boundary(state, t):
        return jnp.where(t % chunk_size == 0, 0.0, 1.0) * state

    def rotary(q_pe, k_pe):
        """Interleaved pairs turned by ``position * theta^(-2i/d)``, as
        the latent attention of ``models/mla.py`` does with rotary on."""
        def turn(x):
            s, half = x.shape[1], x.shape[-1] // 2
            freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
            angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
            cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
            a, b = x[..., 0::2], x[..., 1::2]
            return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                                   -1)
        return turn(q_pe), turn(k_pe)

    def unscaled(p, y, arch, choice=None):
        idx, w, own = sound["_route"](p, y, arch, choice)
        return idx, w / arch["routed_scaling_factor"], own

    return {
        "bfloat16_operands": {"MANTISSA_BITS": 7},
        "float8_operands": {"MANTISSA_BITS": 3},
        # the delta rule
        "scalar_decay_a_head": {"_log_decay": scalar_decay},
        "beta_one": {"_write_strength": jnp.ones_like},
        "no_l2_norm": {"_l2norm": lambda x: x},
        "decay_after_correction": {"_delta_step": decay_after_correction},
        "state_dropped_at_chunk_boundary": {
            "_carried": dropped_at_chunk_boundary},
        "cumulative_decay_bfloat16": {
            "_log_decay": cumulative_decay_bfloat16},
        "carried_state_bfloat16": {"_carried": lambda state, t: _bf16(state)},
        "silu_output_gate": {"_out_gate": jax.nn.silu},
        # latent attention, the experts, the loss
        "rotary_applied": {"_positioned": rotary},
        "no_shared_expert": {"_shared": lambda p, y: 0.0 * sound["_shared"](
            p, y)},
        "combine_unscaled": {"_route": unscaled},
        "loss_bfloat16": {"_cross_entropy": _rounded(
            sound["_cross_entropy"])},
        "softmax_bfloat16": {"_softmax": _rounded(sound["_softmax"])},
        "router_bfloat16": {"_scores": lambda y, router: jax.nn.sigmoid(
            (y.astype(jnp.bfloat16) @ router.astype(jnp.bfloat16)).astype(
                jnp.float32))},
    }


# Which of the family's limits told each fault on the chip, the one that
# told it by most first: those that told it on BOTH seeds the faults were
# planted on, 3300000004 and, on the final tree, 3300000115 (my chip runs,
# PR 33; PERF.md section 6 has the readings). A bfloat16 log-softmax is the
# exception: its loss reads 1.17e-3 on the first seed and 3e-5 on the
# second (every row's log-sum-exp, near 9.93, rounds to the same point of a
# grid of 1/16, so the error is where the seed's mean falls between two
# points). Nothing told those with an empty row: bfloat16 operands are
# the step's own precision, and the carried state alone in bfloat16, or a
# softmax or a router in bfloat16, move every reading by less than the
# limits' room over the seeds (the carried state reads 0.0366 and 0.0360 of
# 0.037).
# Without the L2 norm the reference's state overflows: every reading but
# the routing's is NaN, which passes no limit.
TOLD_BY = {
    "bfloat16_operands": (),
    "float8_operands": ("routing_apart", "grad_error", "loss"),
    "scalar_decay_a_head": ("routing_apart", "grad_error", "grad_norm",
                            "loss"),
    "beta_one": ("routing_apart", "grad_error", "grad_norm", "loss"),
    "no_l2_norm": ("routing_apart", "loss", "grad_norm", "grad_error"),
    "decay_after_correction": ("routing_apart", "grad_error"),
    "state_dropped_at_chunk_boundary": ("routing_apart", "grad_error",
                                        "loss", "grad_norm"),
    "cumulative_decay_bfloat16": ("routing_apart",),
    "carried_state_bfloat16": (),
    "silu_output_gate": ("routing_apart", "grad_norm", "grad_error"),
    "rotary_applied": ("routing_apart",),
    "no_shared_expert": ("grad_norm", "routing_apart", "grad_error"),
    "combine_unscaled": ("routing_apart", "grad_error"),
    "loss_bfloat16": ("loss",),
    "softmax_bfloat16": (),
    "router_bfloat16": (),
}
# What a small size cannot tell by the limit that told it on the chip, and
# the reading that shows it there, over its sound value
# (``tests/test_kda_moe_lm.py``): by a quarter for the running sum in
# bfloat16 (four chunks of heads 64 wide), seven times for rotary, eight
# for a bfloat16 log-softmax, whose rounded logits show in the gradient's
# norm where a loss over 256 logits hides them.
NEEDS_THE_CELLS_SIZE = {
    "cumulative_decay_bfloat16": "scan_grad_error",
    "rotary_applied": "attention_grad_error",
    "loss_bfloat16": "grad_norm",
}
FAULTS = tuple(TOLD_BY)


@contextlib.contextmanager
def planted(name, config):
    """The reference module with fault ``name`` in it (``loss_and_grad``
    jits its blocks anew at every call, so the next call runs it)."""
    patch = _faults(config)[name]
    sound = {attribute: getattr(reference, attribute) for attribute in patch}
    for attribute, value in patch.items():
        setattr(reference, attribute, value)
    try:
        yield
    finally:
        for attribute, value in sound.items():
            setattr(reference, attribute, value)


def readings(report):
    """What the comparison read, and which limits it passed."""
    return {**{name: report[name]["relative_error"] for name in report
               if name != "routing"},
            "told_by": [name for name in report if name != "routing"
                        and not report[name]["agrees"]],
            "apart_per_layer": report["routing"]["apart_per_layer"]}


def main(argv):
    import warnings

    import horovod_tpu as hvd
    from benchmark.families import kda_moe_lm as family
    from benchmark.harness import gate
    from horovod_tpu.ops.flash_attention import FlashFallbackWarning

    warnings.simplefilter("error", FlashFallbackWarning)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    hvd.init()
    gate.require_chips(1)
    with open("benchmark/configs/kimi-linear-48b-a3b.json") as f:
        config = json.load(f)
    with open("benchmark/traffic/b2-s4096.json") as f:
        traffic = json.load(f)
    say = lambda **fields: print(json.dumps(fields), flush=True)  # noqa: E731
    only = [a for a in argv if not a.isdigit()]  # faults named: those only
    argv = [a for a in argv if a.isdigit()]
    for seed in (int(a) for a in argv):
        built = family.build(config, traffic, hvd.mesh(), seed)
        got = built.step_numbers()
        agrees, report = built.compare(got, built.reference_numbers(got[2]))
        say(seed=seed, agrees=agrees, **readings(report),
            held_slots_per_layer=report["routing"]["held_slots_per_layer"])
        del got, report, built
    built = family.build(config, traffic, hvd.mesh(), int(argv[0]))
    got = built.step_numbers()
    for name in only or FAULTS:
        with planted(name, config):
            agrees, report = built.compare(
                got, built.reference_numbers(got[2]))
        say(fault=name, agrees=agrees, **readings(report))
        del report


if __name__ == "__main__":
    main(sys.argv[1:])
