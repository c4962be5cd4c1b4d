"""What the phase metrics need and ``jobs/train.py`` does not hand over
yet, made a second time.

The traced run's reduction keeps neither the HLO instruction table nor the
trace (``layer_inputs`` holds ``xplane.reduce``'s summary; the trace
directory is removed), and ``xplane.parse`` drops every host span but the
benchmark's own three. PR 24 added the phase metrics and may edit no file
the benchmark had. So the first phase reader of a run builds the cell's
step again from the command line, as ``benchmark/run.py`` did: the compiled
text comes from the compile cache this run filled (same key, same
executable, same instruction names as the events of ``run["summary"]``),
and a window of steps under ``jax.profiler`` gives the ``hvd_*`` host
spans. It adds the step's Python tracing and a dozen steps to a
``--trace 1`` run and nothing to any other.

This file goes when a ``benchmark`` PR lets ``jobs/train.py`` call
``phases.summarize`` itself and put the result under
``layer_inputs["phases"]`` (PERF.md section 7 names the lines).
"""

import argparse
import importlib
import json
import math
import shutil
import sys
import tempfile
import traceback

from benchmark.harness import hlo, phases, xplane
from benchmark.run import HERE, ROOT, load_json


def cell_of_command_line(argv):
    """``(config, traffic, seed)`` of ``--workload`` and ``--seed``, found
    the way ``benchmark/run.py`` finds them."""
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args, _ = ap.parse_known_args(argv)
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (load_json(ROOT, entry["file"]),
            load_json(HERE, "traffic", cell["traffic"] + ".json"),
            args.seed)


def host_spans(profile):
    """The program's ``hvd_*`` spans and the benchmark's own three, from
    the host plane of a ``ProfileData``, sorted by start."""
    out = []
    for plane in profile.planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("hvd_") or e.name in xplane.HOST_SPANS:
                    out.append(xplane.Event(
                        e.name, e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9))
    return sorted(out, key=lambda e: e.start)


def observe(argv):
    """``(instruction table, host spans, longest idle gaps)`` of the
    cell's step, built and run again: see the module's docstring."""
    import jax
    from jax.profiler import ProfileData

    import horovod_tpu as hvd
    from benchmark.jobs import train

    config, traffic, seed = cell_of_command_line(argv)
    family = importlib.import_module(
        f"benchmark.families.{config['family']}")
    built = family.build(config, traffic, hvd.mesh(), seed)
    state, batch = built.init_state(), built.batch()
    table = hlo.instruction_table(
        built.step.lower(state, *batch).compile().as_text())
    state, _ = train.warm_up(built, state, batch, 1)

    trace_dir = tempfile.mkdtemp(prefix="benchmark-phases-")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            _, _, _, raised = train.run_windows(
                built, state, batch, traffic["steps_per_window"], math.inf,
                max_windows=1)
        finally:
            jax.profiler.stop_trace()
        if raised is not None:
            raise raised
        profile = ProfileData.from_file(xplane.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    host = host_spans(profile)
    return table, host, phases.idle_gaps(
        xplane.parse(profile)["chips"], host)


def phases_of(run):
    """``phases.summarize`` of ``run`` (a traced run's ``layer_inputs``)
    with the ``phases`` line printed, or ``None`` with the reason on
    stderr: a phase metric is left out, a run never fails for it."""
    try:
        importlib.import_module("horovod_tpu.telemetry.scopes")
    except ImportError:
        print("benchmark: this program names no phases (it has no "
              "horovod_tpu/telemetry/scopes.py); the phase metrics are "
              "left out", file=sys.stderr)
        return None
    try:
        table, host, gaps = observe(sys.argv[1:])
    except Exception:  # the boundary: the run's own result stands
        traceback.print_exc()
        print("benchmark: the step could not be built and traced again; "
              "the phase metrics are left out", file=sys.stderr)
        return None
    out = phases.summarize(
        run["summary"], table,
        [e for e in host if e.name in phases.HOST_SPANS],
        run["traced_steps"])
    out["idle_gaps"] = gaps
    print(json.dumps({"phase": "phases", **out}), flush=True)
    return out
