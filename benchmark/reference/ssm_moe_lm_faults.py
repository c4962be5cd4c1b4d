"""Faults for the plain reference of ``ssm_moe_lm``, and the study that
reads what the family's comparison says of each.

``correct`` compares one step of the timed step with the reference
(``families/ssm_moe_lm.py``: loss, gradient norm, gradient distance, the
two routings' distance). Its limits mean something only beside what a
faulty side reads, so the faults live here, in the repo: each is planted
into the REFERENCE module (the step under test is the timed one and
stays), the comparison is symmetric in what it measures, and
``tests/test_ssm_moe_lm.py`` plants every one of them at a small size on
the CPU. On the chip:

    python3 -m benchmark.reference.ssm_moe_lm_faults <seed>... [<fault>...]

prints, a JSON line each: every seed's sound readings, then on the first
seed the readings under each fault (or under those named), ``agrees`` and
the limits that told it. PERF.md (section 6, PR 31) holds what the chip
read.
"""

import contextlib
import json
import sys

import jax
import jax.numpy as jnp

from benchmark.reference import ssm_moe_lm as reference

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
    chunk_size = config["chunk_size"]  # two of the faults know it
    sound = {name: getattr(reference, name) for name in (
        "_step_size", "_log_decay", "_carried", "_skip", "_gated_norm",
        "_act", "_shared", "_route", "_positioned", "_cross_entropy",
        "_softmax", "_scores")}

    def step_bfloat16(dt, bias):
        return _bf16(sound["_step_size"](_bf16(dt), bias))

    def carried_bfloat16(state, t):
        return _bf16(state)

    def log_decay_bfloat16(step, a):
        """What a chunked scan reads whose cumulative log-decay is summed
        in bfloat16 inside each chunk: every position's decay becomes the
        difference of two rounded partial sums."""
        log_a = sound["_log_decay"](step, a)
        bsz, s, h = log_a.shape
        chunk = min(chunk_size, s)
        cum = jnp.cumsum(log_a.reshape(bsz, s // chunk, chunk, h), 2)
        cum = _bf16(cum)
        first = cum[:, :, :1]
        return jnp.concatenate([first, cum[:, :, 1:] - cum[:, :, :-1]],
                               2).reshape(bsz, s, h)

    def dropped_at_chunk_boundary(state, t):
        return jnp.where(t % chunk_size == 0, 0.0, 1.0) * state

    def gate_after_norm(o, z, scale, groups):
        grouped = o.reshape(*o.shape[:-1], groups, -1)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, -1, keepdims=True)
            + reference.RMS_EPS)
        return grouped.reshape(o.shape) * scale * jax.nn.silu(z)

    def rotary(q, k):
        def turn(x):
            s, half = x.shape[1], x.shape[-1] // 2
            freqs = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
            angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
            cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
            a, b = x[..., :half], x[..., half:]
            return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
        return turn(q), turn(k)

    def unscaled(p, y, arch, choice=None):
        idx, w, own = sound["_route"](p, y, arch, choice)
        return idx, w / arch["routed_scaling_factor"], own

    return {
        "bfloat16_operands": {"MANTISSA_BITS": 7},
        "float8_operands": {"MANTISSA_BITS": 3},
        # the scan's statistics, one by one and together
        "step_bfloat16": {"_step_size": step_bfloat16},
        "cumulative_decay_bfloat16": {"_log_decay": log_decay_bfloat16},
        "carried_state_bfloat16": {"_carried": carried_bfloat16},
        "scan_statistics_bfloat16": {"_step_size": step_bfloat16,
                                     "_log_decay": log_decay_bfloat16,
                                     "_carried": carried_bfloat16},
        "state_dropped_at_chunk_boundary": {
            "_carried": dropped_at_chunk_boundary},
        "no_skip": {"_skip": lambda d, u: 0.0 * sound["_skip"](d, u)},
        "gate_after_norm": {"_gated_norm": gate_after_norm},
        "relu_not_squared": {"_act": jax.nn.relu},
        "no_shared_expert": {"_shared": lambda p, y: 0.0 * sound["_shared"](
            p, y)},
        "combine_unscaled": {"_route": unscaled},
        "rotary_applied": {"_positioned": rotary},
        "loss_bfloat16": {"_cross_entropy": _rounded(
            sound["_cross_entropy"])},
        "softmax_bfloat16": {"_softmax": _rounded(sound["_softmax"])},
        "router_bfloat16": {"_scores": lambda y, router: jax.nn.sigmoid(
            (y.astype(jnp.bfloat16) @ router.astype(jnp.bfloat16)).astype(
                jnp.float32))},
    }


# Which of the family's limits told each fault on the chip, the one that
# told it by most first (my chip runs, PR 31; PERF.md section 6 has the
# readings). Nothing told those with an empty row: bfloat16 operands are
# the step's own precision, and the step or the carried state alone in
# bfloat16, or a softmax or a router in bfloat16, move every reading by
# less than the seeds do.
TOLD_BY = {
    "bfloat16_operands": (),
    "float8_operands": ("routing_apart", "grad_error"),
    "step_bfloat16": (),
    "cumulative_decay_bfloat16": ("routing_apart",),
    "carried_state_bfloat16": (),
    "scan_statistics_bfloat16": ("routing_apart",),
    "state_dropped_at_chunk_boundary": ("routing_apart", "grad_error"),
    "no_skip": ("routing_apart", "grad_norm", "grad_error", "loss"),
    "gate_after_norm": ("routing_apart", "grad_error", "grad_norm"),
    "relu_not_squared": ("routing_apart", "grad_error", "grad_norm",
                         "loss"),
    "no_shared_expert": ("routing_apart", "grad_error", "grad_norm",
                         "loss"),
    "combine_unscaled": ("routing_apart", "grad_error"),
    "rotary_applied": ("routing_apart",),
    "loss_bfloat16": ("loss",),
    "softmax_bfloat16": (),
    "router_bfloat16": (),
}
# What a small size cannot tell by the limit that told it on the chip, and
# the reading that shows it there, well over its sound value
# (``tests/test_ssm_moe_lm.py``). One token-slot is a thousandth of a
# layer's at a small size, coarser than what the first two do to the
# choices (on the chip ``routing_apart`` alone tells them, by a quarter); a
# sequence of a few chunks has a few boundaries to drop a state at; and the
# log-sum-exp over a small vocabulary lies on a finer bfloat16 grid.
NEEDS_THE_CELLS_SIZE = {
    "cumulative_decay_bfloat16": "scan_grad_error",
    "scan_statistics_bfloat16": "scan_grad_error",
    "state_dropped_at_chunk_boundary": "scan_grad_error",
    "loss_bfloat16": "loss",
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
    from benchmark.families import ssm_moe_lm as family
    from benchmark.harness import gate
    from horovod_tpu.ops.flash_attention import FlashFallbackWarning

    warnings.simplefilter("error", FlashFallbackWarning)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    hvd.init()
    gate.require_chips(1)
    with open("benchmark/configs/nemotron-3-nano-30b-a3b.json") as f:
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
