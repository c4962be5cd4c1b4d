"""Faults for the plain reference of ``dsa_moe_lm``, and the study that
reads what the family's comparison says of each.

``correct`` compares one step of the timed step with the reference
(``families/dsa_moe_lm.py``: the two losses, the gradient's norm and
distance, the two routings' distance, the two selections' distance and
sizes, and the two sides' attention on a seeded probe). Its limits mean
something only beside what a faulty side reads, so the faults live here,
in the repo: each is planted into the REFERENCE module (the step under
test is the timed one and stays), and ``tests/test_dsa_moe_lm.py`` plants
every one of them at a small size on the CPU. On the chip:

    python3 -m benchmark.reference.dsa_moe_lm_faults <seed>... [<fault>...]

prints, a JSON line each: every seed's sound readings, then on the first
seed the readings under each fault (or under those named), ``agrees`` and
the limits that told it. PERF.md (section 6) holds what the chip
read.
"""

import contextlib
import json
import sys

import jax
import jax.numpy as jnp

from benchmark.reference import dsa_moe_lm as reference


def _faults():
    """``{name: {attribute of the reference module: its faulty value}}``.
    ``bfloat16_operands`` is no fault: it is the precision the
    configuration states, and shows what a side as precise as the step
    reads. ``float8_operands`` is the precision below it."""
    sound_top_k = reference._top_k
    return {
        "bfloat16_operands": {"MANTISSA_BITS": 7},
        "float8_operands": {"MANTISSA_BITS": 3},
        # the selection
        "dense_causal_attention": {"_sparse": lambda: False},
        "top_2047": {"_top_k": lambda arch: sound_top_k(arch) - 1},
        "no_relu_in_scores": {"_relu": lambda a: a},
        "index_weights_left_out": {"_index_weights": jnp.ones_like},
        "indexer_input_not_detached": {"_detach": lambda y: y},
        # reduce_precision, not a round trip through bfloat16: the compiler
        # may drop a float32 -> bfloat16 -> float32 pair as excess precision
        "scores_ranked_in_bfloat16": {
            "_ranked": lambda s: jax.lax.reduce_precision(
                s, exponent_bits=8, mantissa_bits=7)},
        # the losses, the attention, the experts
        "index_loss_dropped": {"_index_loss_weight": lambda: 0.0},
        "no_qk_norm": {"_qk_norm": lambda x, scale: x},
        "theta_1e6": {"_theta": lambda arch: 1e6},
        "sigmoid_router": {"_router": jax.nn.sigmoid},
    }


# Which of the family's limits tell each fault, the one that told it by
# most first (small size, tests/test_dsa_moe_lm.py; PERF.md section 6 has
# what the chip read). Nothing tells those with an empty row: bfloat16
# operands are the step's own precision, and scores ranked in bfloat16 move
# the reference's k-th score by less than the step's own bfloat16 scores
# lie from the reference's (a side as precise as the step is as right as
# the step).
TOLD_BY = {
    "bfloat16_operands": (),
    "float8_operands": ("grad_error",),
    "dense_causal_attention": ("index_loss",),
    "top_2047": ("selection_size_apart",),
    "no_relu_in_scores": ("index_loss",),
    "index_weights_left_out": ("index_loss",),
    "indexer_input_not_detached": ("grad_error",),
    "scores_ranked_in_bfloat16": (),
    "index_loss_dropped": ("index_loss",),
    "no_qk_norm": ("grad_error",),
    "theta_1e6": ("grad_error",),
    "sigmoid_router": ("grad_error",),
}
FAULTS = tuple(_faults())


@contextlib.contextmanager
def planted(name):
    """The reference module with fault ``name`` in it (``loss_and_grad``
    and the probe jit their blocks anew at every call, so the next call
    runs it)."""
    patch = _faults()[name]
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
    from benchmark.families import dsa_moe_lm as family
    from benchmark.harness import gate
    from horovod_tpu.ops.flash_attention import FlashFallbackWarning

    warnings.simplefilter("error", FlashFallbackWarning)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    hvd.init()
    gate.require_chips(1)
    with open("benchmark/configs/keye-vl-2.0-30b-a3b.json") as f:
        config = json.load(f)
    with open("benchmark/traffic/b1-s32768.json") as f:
        traffic = json.load(f)
    say = lambda **fields: print(json.dumps(fields), flush=True)  # noqa: E731
    only = [a for a in argv if not a.isdigit()]  # faults named: those only
    argv = [a for a in argv if a.isdigit()]
    for seed in (int(a) for a in argv):
        built = family.build(config, traffic, hvd.mesh(), seed)
        got = built.step_numbers()
        agrees, report = built.compare(got, built.reference_numbers(got))
        say(seed=seed, agrees=agrees, **readings(report),
            held_slots_per_layer=report["routing"]["held_slots_per_layer"])
        del got, report, built
    built = family.build(config, traffic, hvd.mesh(), int(argv[0]))
    got = built.step_numbers()
    for name in only or FAULTS:
        with planted(name):
            agrees, report = built.compare(got, built.reference_numbers(got))
        say(fault=name, agrees=agrees, **readings(report))
        del report


if __name__ == "__main__":
    main(sys.argv[1:])
