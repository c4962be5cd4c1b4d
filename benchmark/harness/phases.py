"""From HLO ``op_name``s and host spans to the step's own phases.

The program names its work from inside (``horovod_tpu/telemetry/scopes.py``):
``jax.named_scope`` puts ``hvd_exchange/bucket<i>``, ``hvd_optimizer`` and
``hvd_loss`` into the ``op_name`` of every instruction traced under them,
beside what jax and flax already write there (``jvp(...)``,
``transpose(jvp(...))``, module names), and ``hvd_step`` / ``hvd_place`` /
``hvd_launch`` are host spans in the profiler's own trace. ``phase_of`` sorts
an ``op_name`` into a phase; ``summarize`` is arithmetic on what
``xplane.reduce`` and ``hlo.instruction_table`` already made, so the tests
drive it with hand-made inputs. Nothing here touches jax.
"""

import re
import statistics
import sys

from benchmark.harness import xplane

PHASES = ("exchange", "optimizer", "loss_head", "backward", "forward",
          "compiler", "unscoped")
SCOPES = {"hvd_exchange": "exchange", "hvd_optimizer": "optimizer",
          "hvd_loss": "loss_head"}
HOST_SPANS = ("hvd_step", "hvd_place", "hvd_launch")
MIN_SCOPED_SHARE = 0.95

_SCOPE_RE = re.compile(r"(?<![\w.])(" + "|".join(SCOPES) + r")(?![\w.])")
_LM_HEAD_RE = re.compile(r"(?<![\w.])lm_head(?![\w.])")


def phase_of(op_name):
    """The phase an instruction's ``op_name`` puts it in.

    The innermost (rightmost) ``hvd_*`` scope wins, inside ``jvp(...)`` and
    ``transpose(...)`` wrappers too; a path component ``lm_head`` is
    ``loss_head`` whatever the direction; else ``transpose(`` is
    ``backward`` and ``jvp(`` ``forward``. An *empty* ``op_name`` is
    ``compiler``: an instruction the compiler inserted (``copy-done``,
    ``dynamic-update-slice``) that no source operation owns. What is left
    has a source and no owner: ``unscoped``."""
    if not op_name:
        return "compiler"
    scopes = _SCOPE_RE.findall(op_name)
    if scopes:
        return SCOPES[scopes[-1]]
    if _LM_HEAD_RE.search(op_name):
        return "loss_head"
    if "transpose(" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    return "unscoped"


def innermost_span_over(gap, host):
    """The name of the host span that covers most of ``gap``; of several
    that cover as much (``dispatch`` holds ``hvd_step`` holds
    ``hvd_place``), the shortest, which is the innermost."""
    best, best_cover, best_len = "no_span", 0.0, 0.0
    for ev in host:
        cover = min(ev.end, gap[1]) - max(ev.start, gap[0])
        length = ev.end - ev.start
        if cover > best_cover or (cover == best_cover > 0.0
                                  and length < best_len):
            best, best_cover, best_len = ev.name, cover, length
    return best


def idle_gaps(chips, host, top=5):
    """``[[span, seconds]]``: the longest gaps between device operations
    on the chip that idled most (``chips``: ``xplane.parse``'s events by
    chip), each named for the innermost host span over it."""
    worst, worst_idle = [], -1.0
    for events in chips.values():
        busy = xplane.merge((e.start, e.end) for e in events)
        gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
        idle = sum(hi - lo for lo, hi in gaps)
        if idle > worst_idle:
            worst, worst_idle = gaps, idle
    worst.sort(key=lambda g: g[0] - g[1])
    return [[innermost_span_over(g, host), g[1] - g[0]]
            for g in worst[:top]]


def summarize(summary, table, spans, steps):
    """The phases of one traced run.

    ``summary`` is ``xplane.reduce``'s (per chip ``by_name`` self seconds
    and ``window_s``), ``table`` ``hlo.instruction_table``'s (the
    ``op_name`` of every instruction; a fusion is booked under its own
    instruction's), ``spans`` the host events named ``hvd_*``, ``steps``
    the steps the device window held.

    Per chip the self seconds by phase; by phase the worst chip's share of
    its window and its milliseconds a step; ``scoped_share``: of the self
    time of operations that have a source (all but ``compiler``), the share
    that has an owner (all but ``unscoped``); the ``unscoped`` ``op_name``s
    by seconds, a chip's mean, first 20; from the host spans the median
    ``hvd_step`` / ``hvd_place`` / ``hvd_launch`` in milliseconds and how
    many ``hvd_step`` spans there were."""
    chips, unscoped = {}, {}
    n = len(summary["chips"])
    for chip, reduced in summary["chips"].items():
        by_phase = dict.fromkeys(PHASES, 0.0)
        for name, self_s in reduced["by_name"].items():
            op_name = table.get(name, {}).get("op_name")
            # an event the table lacks has no known source: not owned
            phase = "unscoped" if op_name is None else phase_of(op_name)
            by_phase[phase] += self_s
            if phase == "unscoped":
                key = op_name or name
                unscoped[key] = unscoped.get(key, 0.0) + self_s / n
        chips[chip] = {"window_s": reduced["window_s"], "self_s": by_phase}
    per = list(chips.values())
    sourced = sum(sum(c["self_s"].values()) - c["self_s"]["compiler"]
                  for c in per)
    owned = sourced - sum(c["self_s"]["unscoped"] for c in per)
    durations = {name: [1e3 * (e.end - e.start) for e in spans
                        if e.name == name] for name in HOST_SPANS}
    return {
        "chips": chips,
        "share": {p: max(c["self_s"][p] / c["window_s"] for c in per)
                  for p in PHASES},
        "ms_per_step": {p: max(c["self_s"][p] for c in per) / steps * 1e3
                        for p in PHASES},
        "scoped_share": owned / sourced if sourced else 0.0,
        "unscoped": sorted(unscoped.items(), key=lambda kv: -kv[1])[:20],
        "host": {"steps": len(durations["hvd_step"]),
                 **{f"{name}_ms": (statistics.median(values)
                                   if values else None)
                    for name, values in durations.items()}},
    }


def of_run(run):
    """``run["phases"]``: ``summarize``'s dict, or ``None`` where the run
    could not tell (the program has no scopes, as before PR 24). The job
    puts it there; where it has not, it is made now, once, and kept."""
    if "phases" not in run:
        from benchmark.harness import rebuild

        run["phases"] = rebuild.phases_of(run)
    return run["phases"]


def device_pct(run, phase):
    """A device phase's share of the traced window, worst chip, in
    percent, or ``None`` (reason on stderr) when under 95% of the sourced
    device time has an owner: a scope that is missing, or an executable
    that the compile cache returned from before the scopes, must never
    read as a small number."""
    phases = of_run(run)
    if phases is None:
        return None
    if phases["scoped_share"] < MIN_SCOPED_SHARE:
        print(f"benchmark: {phase}_pct left out: scoped_share "
              f"{phases['scoped_share']:.4f} is under {MIN_SCOPED_SHARE}; "
              f"unscoped: {phases['unscoped'][:5]}", file=sys.stderr)
        return None
    return 100.0 * phases["share"][phase]
