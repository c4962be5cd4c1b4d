"""What a training cell's run does with the step its family built.

Set-up: ``hvd.init()``, the chip gate, state and batch made on the device
from the seed, the step compiled ahead of time (from the persistent cache
on every run but a checkout's first), the family's reference check, the
warm-up. Then windows of K steps back to back (``run_windows``) until
``--seconds`` have passed. With ``--trace 1`` a few untraced windows give
the throughput, then ``jax.profiler`` records a few more and the trace is
reduced here.
"""

import importlib
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
import warnings

from benchmark.harness import gate, hlo, xplane
from benchmark.harness.compile_watch import CompileWatch

UNTRACED_WINDOWS = 3  # of a --trace 1 run, for mfu_pct


def say(**fields):
    """An earlier line: information, never read by the driver."""
    print(json.dumps(fields), flush=True)


def warm_up(built, state, batch, steps):
    """``steps`` steps and a wait: set-up, not measurement."""
    import jax

    losses = []
    for _ in range(steps):
        state, loss = built.step(state, *batch)
        losses.append(loss)
    return state, [float(x) for x in jax.device_get(losses)]


def run_windows(built, state, batch, k, seconds, max_windows=None):
    """Windows of ``k`` steps, back to back, for ``seconds`` (at least
    one window, at most ``max_windows``).

    A training loop never waits for a step it has dispatched, so the
    timed loop does not either: one window is always queued behind the
    one being timed, and a window's seconds run from the moment the
    window before it was done (``jax.block_until_ready`` on its last
    loss returned) to the moment it was. The chip goes from the last
    step of one window into the first of the next; the window that fills
    the queue and the one that drains it are not timed.

    Returns ``(state, [(seconds, [loss, ...])], steps dispatched,
    raised)``. The host spans are the ones a traced run names its idle
    gaps for."""
    import jax
    from jax.profiler import TraceAnnotation

    dispatched = 0

    def dispatch(state):
        nonlocal dispatched
        losses = []
        with TraceAnnotation("dispatch"):
            for _ in range(k):
                state, loss = built.step(state, *batch)
                losses.append(loss)
                dispatched += 1
        return state, losses

    def wait(losses):
        with TraceAnnotation("block"):
            jax.block_until_ready(losses[-1])
        return time.perf_counter()

    windows, raised = [], None
    try:
        state, lead_in = dispatch(state)
        state, queued = dispatch(state)
        done_at = wait(lead_in)
        until = done_at + seconds
        while True:
            timed = queued
            state, queued = dispatch(state)
            previous, done_at = done_at, wait(timed)
            with TraceAnnotation("between_windows"):
                windows.append((done_at - previous, [
                    float(x) for x in jax.device_get(timed)]))
            if done_at >= until or len(windows) == max_windows:
                break
        wait(queued)
    except Exception as e:  # a step that raises is a failed step
        raised = e
    return state, windows, dispatched, raised


def record_trace(built, state, batch, k, windows):
    """``windows`` timed windows (and the two around them) under
    ``jax.profiler``. The trace goes to a temporary directory outside the
    checkout and is removed once read. Returns ``(state, steps in the
    trace, xplane.load's events)``."""
    import jax

    trace_dir = tempfile.mkdtemp(prefix="benchmark-trace-")
    try:
        # the Python tracer would log every call of the dispatch loop:
        # a slower host, and a trace ten times the size
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            state, _, steps, raised = run_windows(
                built, state, batch, k, math.inf, max_windows=windows)
        finally:
            jax.profiler.stop_trace()
        if raised is not None:
            raise raised
        return state, steps, xplane.load(xplane.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def memory_fields(compiled, devices):
    """The compiled step's footprint a chip (arguments + outputs +
    temporaries - aliased) beside what the allocator says it saw."""
    m = compiled.memory_analysis()
    footprint = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
    stats = [d.memory_stats() or {} for d in devices]
    return {"compiled_footprint_bytes": footprint,
            "compiled_argument_bytes": m.argument_size_in_bytes,
            "compiled_temp_bytes": m.temp_size_in_bytes,
            "allocator_peak_bytes": max(
                (s.get("peak_bytes_in_use", 0) for s in stats), default=0),
            "allocator_limit_bytes": stats[0].get("bytes_limit")}


def run(cell, config, traffic, args, process_start):
    """One run of one training cell. Returns the dict ``benchmark.run``
    turns into the last line."""
    import jax

    import horovod_tpu as hvd
    from horovod_tpu.ops.flash_attention import FlashFallbackWarning

    # asked for the kernel and got plain attention: an error, not a result
    warnings.simplefilter("error", FlashFallbackWarning)
    # every program of a run goes into the persistent cache, however fast
    # it compiled: a warm run then compiles nothing (the default keeps
    # only programs that took a second, so small ones came and went)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    hvd.init()  # first backend touch, under the flags it sets
    devices, peaks = gate.require_chips(cell["chips"])
    mesh = hvd.mesh()
    if dict(mesh.shape) != traffic["mesh"]:
        sys.exit(f"benchmark: the cell's traffic wants the mesh "
                 f"{traffic['mesh']} and hvd.init() built "
                 f"{dict(mesh.shape)} over the {len(devices)} chips here")
    init_s = time.perf_counter() - process_start
    say(phase="init", init_s=init_s, mesh=dict(mesh.shape),
        compile_cache_dir=jax.config.jax_compilation_cache_dir,
        compile_cache_max_size=jax.config.jax_compilation_cache_max_size,
        libtpu_init_args=os.environ.get("LIBTPU_INIT_ARGS", ""))

    family = importlib.import_module(
        f"benchmark.families.{config['family']}")
    built = family.build(config, traffic, mesh, args.seed)
    k = traffic["steps_per_window"]

    with CompileWatch() as setup_watch:
        state, batch = built.init_state(), built.batch()
        with CompileWatch() as step_watch:
            t0 = time.perf_counter()
            compiled = built.step.lower(state, *batch).compile()
            compile_s = time.perf_counter() - t0
        text = compiled.as_text()
        kernels = text.count(hlo.PALLAS_TARGET)
        if built.wants_pallas_kernel and not kernels:
            sys.exit("benchmark: the compiled step holds no "
                     f"{hlo.PALLAS_TARGET}: the flash kernel did not "
                     "compile as a kernel")
        say(phase="compile", compile_s=compile_s, pallas_calls=kernels,
            **step_watch.fields())

        del state  # the reference check wants the chip's memory
        t0 = time.perf_counter()
        agrees, report = built.reference_check()
        say(phase="reference_check", seconds=time.perf_counter() - t0,
            agrees=agrees, **report)

        t0 = time.perf_counter()
        state = built.init_state()
        state, warm = warm_up(built, state, batch, traffic["warmup_steps"])
        say(phase="warmup", seconds=time.perf_counter() - t0,
            losses=warm, **setup_watch.fields())

    collectives = hlo.collective_axis_bytes(
        text, mesh.devices.shape, mesh.axis_names)
    say(phase="collectives", by_axis=collectives)

    traced = bool(args.trace)
    setup_s = time.perf_counter() - process_start
    with CompileWatch() as window_watch:
        state, windows, _, raised = run_windows(
            built, state, batch, k, args.seconds,
            max_windows=UNTRACED_WINDOWS if traced else None)
    if raised is not None:
        say(phase="window", raised=repr(raised))

    losses = [x for _, ls in windows for x in ls]
    attempted = len(losses) + (k if raised is not None else 0)
    failed = (sum(not math.isfinite(x) for x in losses)
              + (k if raised is not None else 0))
    per_chip = built.items_per_step / cell["chips"]
    rates = [k * per_chip / s for s, _ in windows]
    rate = statistics.median(rates) if rates else 0.0
    step_ms = (statistics.median(s for s, _ in windows) / k * 1e3
               if windows else 0.0)
    memory = memory_fields(compiled, devices)
    say(phase="window", windows=len(windows), steps_per_window=k,
        median=rate, mean=statistics.fmean(rates) if rates else 0.0,
        slowest=min(rates, default=0.0), fastest=max(rates, default=0.0),
        unit=f"{built.item}/s/chip",
        window_seconds=[s for s, _ in windows],
        first_loss=losses[:1], last_loss=losses[-1:],
        **window_watch.fields(), **memory)

    result = {
        "correct": bool(agrees and not failed and windows
                        and window_watch.quiet),
        "attempted": attempted, "failed": failed,
        "values": {f"{built.item}_per_s_per_chip": rate,
                   "step_ms": step_ms, "setup_s": setup_s},
        "device": {
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            # the allocator's peak misses a program's temporaries on this
            # backend (PERF.md section 7): the compiled step's footprint
            # is the floor of what the fullest chip held
            "memory_peak_bytes": max(memory["compiled_footprint_bytes"],
                                     memory["allocator_peak_bytes"])},
    }
    if not traced or raised is not None:
        return result

    state, steps, trace = record_trace(built, state, batch, k,
                                       traffic["trace_windows"])
    table = hlo.instruction_table(text)
    summary = xplane.reduce(
        trace, lambda name: table.get(name, {}).get(
            "category", "unattributed"))
    if not summary["chips"]:
        sys.exit("benchmark: the trace holds no device operation")
    say(phase="trace", steps=steps, chips=sorted(summary["chips"]),
        window_s=summary["window_s"], busy_s=summary["busy_s"],
        idle_share=summary["idle_share"],
        category_share=summary["category_share"],
        named_share=summary["named_share"],
        unattributed=summary["unattributed"][:20])
    result["correct"] = bool(result["correct"]
                             and summary["named_share"] >= 0.95)
    result["device"].update(busy_s=summary["busy_s"],
                            window_s=summary["window_s"])

    def label(name):
        info = table.get(name)
        if info is None:
            return name + "|unattributed"
        return "|".join((name, info["category"],
                         info["op_name"][-80:]))

    top = sorted(summary["by_name"].items(), key=lambda kv: -kv[1])[:10]
    result["breakdown"] = {
        "device_ops": [[label(name), s] for name, s in top],
        "idle_gaps": [[name, s] for name, s in summary["gaps"][:5]]}
    result["layer_inputs"] = {
        "summary": summary, "traced_steps": steps, "memory": memory,
        "collectives": collectives, "init_s": init_s,
        "compile_s": compile_s, "items_per_s_per_chip": rate,
        "required_flops_per_item": family.required_flops_per_item(
            config, traffic),
        "kernel_work": family.kernel_work(config, traffic),
        "peaks": peaks, "chips": cell["chips"]}
    return result
