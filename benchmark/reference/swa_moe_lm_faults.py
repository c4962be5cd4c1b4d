"""Faults for the plain reference of ``swa_moe_lm``, and the study that
reads what the family's comparison says of each.

``correct`` compares one step of the timed step with the reference
(``families/swa_moe_lm.py``: loss, gradient norm, gradient distance, the
two routings' distance, and the two sides' attention on a seeded probe). Its limits mean something only beside what a
faulty side reads, so the faults live here, in the repo: each is planted
into the REFERENCE module (the step under test is the timed one and
stays), the comparison is symmetric in what it measures, and
``tests/test_swa_moe_lm.py`` plants every one of them at a small size on
the CPU. On the chip:

    python3 -m benchmark.reference.swa_moe_lm_faults <seed>... [<fault>...]

prints, a JSON line each: every seed's sound readings, then on the first
seed the readings under each fault (or under those named), ``agrees`` and
the limits that told it. PERF.md (section 6, PR 37) holds what the chip
read.
"""

import contextlib
import json
import sys

import jax
import jax.numpy as jnp

from benchmark.reference import swa_moe_lm as reference


def _rounded(f):
    """``f`` with its first argument through bfloat16 and its result too."""
    return lambda x, *rest: f(x.astype(jnp.bfloat16), *rest).astype(
        jnp.float32)


def _faults(config):
    """``{name: {attribute of the reference module: its faulty value}}``.
    ``bfloat16_operands`` is no fault: it is the precision the
    configuration states, and shows what a side as precise as the step
    reads. ``float8_operands`` is the precision below it."""
    window = config["sliding_window"]
    sound = {name: getattr(reference, name) for name in (
        "_window", "_theta", "_rotated_width", "_inv_freq",
        "_cos_sin_factor", "_heads", "_route", "_shared", "_cross_entropy",
        "_softmax", "_scores")}
    sliding = lambda kind: kind["sliding_window"] is not None  # noqa: E731
    thetas = sorted(float(rope["rope_theta"]) for name, rope in config[
        "rope_parameters"].items() if isinstance(rope, dict))
    fewest = min(config["num_attention_heads_per_layer"])

    def windowed(width):
        return lambda kind: width if sliding(kind) else None

    def swapped(kind):
        theta = sound["_theta"](kind)
        return thetas[0] if theta == thetas[-1] else thetas[-1]

    def plain_frequencies(kind):
        return sound["_inv_freq"]({**kind, "yarn": None})

    def whole_head(kind):
        return config["head_dim"]

    def unscaled(p, y, arch, choice=None):
        idx, w, own = sound["_route"](p, y, arch, choice)
        return idx, w / arch["routed_scaling_factor"], own

    return {
        "bfloat16_operands": {"MANTISSA_BITS": 7},
        "float8_operands": {"MANTISSA_BITS": 3},
        # the mask
        "no_window": {"_window": lambda kind: None},
        "window_one_short": {"_window": windowed(window - 1)},
        "window_one_long": {"_window": windowed(window + 1)},
        # the rotary
        "rotary_over_the_whole_head": {"_rotated_width": whole_head},
        "yarn_blend_left_out": {"_inv_freq": plain_frequencies},
        "attention_factor_left_out": {"_cos_sin_factor": lambda kind: 1.0},
        "thetas_swapped": {"_theta": swapped},
        # the heads and the gate
        "no_gate": {"_head_gate": lambda y, kernel: jnp.ones(
            (*y.shape[:-1], kernel.shape[-1]), jnp.float32)},
        "silu_gate": {"_head_gate": lambda y, kernel: jax.nn.silu(
            reference._mm("bsd,dh->bsh", y, kernel))},
        "sliding_layers_with_the_full_layers_heads": {
            "_heads": lambda kind, h: fewest if sliding(kind) else h},
        # the experts, the loss
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
# planted on, 3700000101 and 3700000113 (``attention_apart``: 3700000201
# and 3700000202; my chip runs, PR 37; PERF.md section 6 has the
# readings). ``loss`` told five more on one seed of the two and is not
# listed for them. Nothing told those with an empty row: bfloat16 operands
# are the step's own precision, and a softmax or a router in bfloat16
# moves every reading by less than the limits' room over the seeds (a
# bfloat16 softmax reads ``routing_apart`` 0.0390 of 0.042 and
# ``attention_apart`` 0.0046 of 0.01).
# A window one position short or long is told by ``attention_apart``
# ALONE: with random weights a key at the window's edge weighs 1/512 of
# its query's softmax, and a whole step's readings move by less than the
# seeds do (``grad_error`` 0.0733 -> 0.0744, ``routing_apart`` 0.0339 ->
# 0.0349 and 0.0353, sound seeds 0.0329 to 0.0352).
TOLD_BY = {
    "bfloat16_operands": (),
    "float8_operands": ("routing_apart", "attention_apart", "grad_error"),
    "no_window": ("attention_apart", "routing_apart", "grad_error"),
    "window_one_short": ("attention_apart",),
    "window_one_long": ("attention_apart",),
    "rotary_over_the_whole_head": ("routing_apart", "grad_norm",
                                   "grad_error", "loss"),
    "yarn_blend_left_out": ("routing_apart", "grad_error"),
    "attention_factor_left_out": ("grad_norm", "routing_apart",
                                  "grad_error"),
    "thetas_swapped": ("routing_apart", "grad_error"),
    "no_gate": ("routing_apart", "grad_error", "grad_norm", "loss"),
    "silu_gate": ("routing_apart", "grad_error", "grad_norm", "loss"),
    "sliding_layers_with_the_full_layers_heads": ("routing_apart",
                                                  "grad_error"),
    "no_shared_expert": ("grad_norm", "routing_apart", "grad_error"),
    "combine_unscaled": ("routing_apart", "grad_error"),
    "loss_bfloat16": ("loss",),
    "softmax_bfloat16": (),
    "router_bfloat16": (),
}
# What a small size cannot tell by the limit that told it on the chip, and
# the reading that shows it there, over its sound value
# (``tests/test_swa_moe_lm.py``): a bfloat16 log-softmax, whose rounded
# logits show in the gradient's norm where a loss over 256 logits hides
# them.
NEEDS_THE_CELLS_SIZE = {"loss_bfloat16": "grad_norm"}
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
    from benchmark.families import swa_moe_lm as family
    from benchmark.harness import gate
    from horovod_tpu.ops.flash_attention import FlashFallbackWarning

    warnings.simplefilter("error", FlashFallbackWarning)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    hvd.init()
    gate.require_chips(1)
    with open("benchmark/configs/laguna-xs.2.json") as f:
        config = json.load(f)
    with open("benchmark/traffic/b1-s8192.json") as f:
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
