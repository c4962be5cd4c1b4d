"""What the compiled step holds on one chip: ``memory_analysis()``'s
arguments + outputs + temporaries - aliased. A count of the compiler's,
so it repeats exactly. Not the allocator's ``peak_bytes_in_use``, which
on this backend does not see a program's temporaries (PERF.md section 7);
both are printed on the ``window`` line of every run."""

LAYER, UNIT, MOVES = "step builders", "GiB", "step_ms"


def read(run):
    return run["memory"]["compiled_footprint_bytes"] / 2 ** 30
