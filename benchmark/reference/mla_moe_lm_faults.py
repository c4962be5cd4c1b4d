"""Faults for the plain reference of ``mla_moe_lm``, and the study that
reads what the family's comparison says of each.

``correct`` compares one step of the timed step with the reference
(``families/mla_moe_lm.py``: loss, gradient norm, gradient distance, the
two routings' distance). Its limits mean something only beside what a
faulty side reads, so the faults live here, in the repo: each is planted
into the REFERENCE module (the step under test is the timed one and
stays), the comparison is symmetric in what it measures, and
``tests/test_mla_moe_lm.py`` plants every one of them at a small size on
the CPU. On the chip:

    python3 -m benchmark.reference.mla_moe_lm_faults <seed>... [<fault>...]

prints, a JSON line each: every seed's sound readings, then on the first
seed the readings under each fault (or under those named), ``agrees`` and
the limits that told it. PERF.md (section 6, PR 27) holds what the chip read.
"""

import contextlib
import json
import sys

import jax
import jax.numpy as jnp

from benchmark.reference import mla_moe_lm as reference


def _rounded(f):
    """``f`` with its first argument through bfloat16 and its result too."""
    return lambda x, *rest: f(x.astype(jnp.bfloat16), *rest).astype(
        jnp.float32)


def _faults(config):
    """``{name: {attribute of the reference module: its faulty value}}``.
    ``bfloat16_operands`` is no fault: it is the precision the
    configuration states, and shows what a side as precise as the step
    reads. ``float8_operands`` is the precision below it."""
    sound = {name: getattr(reference, name) for name in (
        "_softmax", "_scores", "_cross_entropy", "_route", "_shared")}
    shared_width = config["n_shared_experts"] * config["moe_intermediate_size"]

    def slot_dropped(p, y, arch, choice=None):
        """One token in a hundred loses its last slot."""
        idx, w, own = sound["_route"](p, y, arch, choice)
        return idx, w.at[::100, -1].set(0.0), own

    def past_capacity_dropped(p, y, arch, choice=None):
        """What a capacity of twice the mean load drops: an expert's
        slots past it, in token order (``models/moe.py``'s dispatch at
        ``capacity_factor`` 2)."""
        idx, w, own = sound["_route"](p, y, arch, choice)
        experts = p["router"].shape[1]
        slot = jax.nn.one_hot(idx.reshape(-1), experts, dtype=jnp.int32)
        place = jnp.sum((jnp.cumsum(slot, 0) - 1) * slot, -1)
        return idx, jnp.where(place.reshape(idx.shape)
                              < 2 * idx.size // experts, w, 0.0), own

    def unscaled(p, y, arch, choice=None):
        idx, w, own = sound["_route"](p, y, arch, choice)
        return idx, w / arch["routed_scaling_factor"], own

    def no_shared(p, y):
        out = sound["_shared"](p, y)  # the dense layer's SwiGLU stays
        return out * (p["gate_proj"]["kernel"].shape[1] != shared_width)

    return {
        "bfloat16_operands": {"MANTISSA_BITS": 7},
        "float8_operands": {"MANTISSA_BITS": 3},
        "softmax_bfloat16": {"_softmax": _rounded(sound["_softmax"])},
        "router_bfloat16": {"_scores": lambda y, router: jax.nn.sigmoid(
            (y.astype(jnp.bfloat16) @ router.astype(jnp.bfloat16)).astype(
                jnp.float32))},
        "loss_bfloat16": {"_cross_entropy": _rounded(
            sound["_cross_entropy"])},
        "slot_dropped": {"_route": slot_dropped},
        "past_capacity_dropped": {"_route": past_capacity_dropped},
        "no_shared_expert": {"_shared": no_shared},
        "combine_unscaled": {"_route": unscaled},
    }


# Which of the family's limits told each fault on the chip, the one that
# told it by most first (my chip runs,
# PR 27; PERF.md section 6 has the readings). Nothing told the four with an
# empty row: bfloat16 operands are the step's own precision, and a softmax
# or a router in bfloat16, or one token-slot in 600 dropped, move the
# gradient distance by under a twentieth of what the step's own bfloat16
# puts there.
TOLD_BY = {
    "bfloat16_operands": (),
    "float8_operands": ("routing_apart", "grad_error"),
    "softmax_bfloat16": (),
    "router_bfloat16": (),
    "loss_bfloat16": ("loss",),
    "slot_dropped": (),
    "past_capacity_dropped": ("grad_error", "routing_apart"),
    "no_shared_expert": ("grad_norm", "grad_error", "routing_apart"),
    "combine_unscaled": ("routing_apart", "grad_error", "grad_norm"),
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
    from benchmark.families import mla_moe_lm as family
    from benchmark.harness import gate
    from horovod_tpu.ops.flash_attention import FlashFallbackWarning

    warnings.simplefilter("error", FlashFallbackWarning)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    hvd.init()
    gate.require_chips(1)
    with open("benchmark/configs/kanana-2-30b-a3b.json") as f:
        config = json.load(f)
    with open("benchmark/traffic/b4-s4096.json") as f:
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
