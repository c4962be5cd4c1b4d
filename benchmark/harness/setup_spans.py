"""The set-up of a run as the program recorded it from inside.

``horovod_tpu/telemetry/startup.py`` keeps one list of spans on
``time.time()`` from the first line of ``import horovod_tpu`` to the first
``hvd_step`` that ran from warm caches alone: the program's own
(``hvd_import``, ``hvd_init`` and its four children, ``hvd_lower``, every
``hvd_step`` until it closes) and jax's top-level trace, lowering and
backend spans with the program's name. ``jobs/train.py`` hands nothing of
it over, and this PR may edit no file the benchmark had, so the first
set-up reader of a run imports the program's record in its own process
(the run's process), reduces it here, prints one earlier line
``{"phase": "setup_spans", ...}`` and keeps the result under
``run["setup_spans"]`` for the other readers.

``reduce`` is arithmetic on a list of spans, so the tests drive it with
hand-made records. A reader says nothing (``None``, the reason once on
stderr) where the program keeps no record, where the record never closed
(no two steps ran), or where a span it needs is not there: an absent span
must never read as zero seconds.
"""

import json
import sys

from benchmark.harness.xplane import merge

COLUMNS = {"jax_trace": "trace_s", "jax_lower": "lower_s",
           "jax_xla": "xla_s"}
LISTED_FROM_S = 0.05  # a program under this is summed into ``others``
GAPS = 5


def label(span):
    program = span["attrs"].get("program")
    return span["name"] if program is None else f"{span['name']}:{program}"


def step_program(spans):
    """The step's program by construction, not by a list of names: the
    last lowering under the first ``hvd_lower`` that holds one
    (placement may lower small programs first), else the last one past
    the placement of the first ``hvd_step`` that holds one."""
    found = {}
    for span in spans:
        if span["name"] != "jax_lower" or span["parent"] is None:
            continue
        parent = spans[span["parent"]]
        if parent["name"] == "hvd_lower" or (
                parent["name"] == "hvd_step" and span["start"]
                >= parent["attrs"].get("place_end", span["start"])):
            found[span["parent"]] = span["attrs"]["program"]
    for kind in ("hvd_lower", "hvd_step"):
        for index in sorted(found):
            if spans[index]["name"] == kind:
                return found[index]
    return None


def programs(spans):
    """By program, in order of first appearance: ``builds`` (top-level
    lowerings; a trace that jax's own cache answered leaves a span of no
    length and no build), ``traces`` (top-level trace spans), the seconds
    of each kind, and what the persistent cache said."""
    table = {}
    for span in spans:
        column = COLUMNS.get(span["name"])
        if column is None or span["end"] is None:
            continue
        line = table.setdefault(span["attrs"]["program"], {
            "program": span["attrs"]["program"], "builds": 0, "traces": 0,
            "trace_s": 0.0, "lower_s": 0.0, "xla_s": 0.0, "cache": []})
        line[column] += span["end"] - span["start"]
        line["builds"] += span["name"] == "jax_lower"
        line["traces"] += span["name"] == "jax_trace"
        said = span["attrs"].get("cache")
        if said and said not in line["cache"]:
            line["cache"].append(said)
    for line in table.values():
        line["cache"] = "+".join(line["cache"]) or None
    return table


def first_build(spans, program):
    """The build of ``program`` that ``built.step.lower(...).compile()``
    made: under the first ``hvd_lower`` its placement (up to the first
    trace of the program), trace and lowering, and the first backend span
    of the program after them. ``None`` without an ``hvd_lower``."""
    index = next((i for i, s in enumerate(spans)
                  if s["name"] == "hvd_lower" and s["end"] is not None),
                 None)
    if index is None:
        return None
    lower = spans[index]
    out = {"hvd_lower_s": lower["end"] - lower["start"], "place_s": None,
           "trace_s": 0.0, "lower_s": 0.0, "xla_s": None, "cache": None}
    for span in spans:
        if span["attrs"].get("program") != program:
            continue
        if span["parent"] == index and span["name"] in ("jax_trace",
                                                        "jax_lower"):
            if span["name"] == "jax_trace" and out["place_s"] is None:
                out["place_s"] = span["start"] - lower["start"]
            out[COLUMNS[span["name"]]] += span["end"] - span["start"]
        elif (span["name"] == "jax_xla" and out["xla_s"] is None
              and span["start"] >= lower["end"]):
            out["xla_s"] = span["end"] - span["start"]
            out["cache"] = span["attrs"].get("cache")
    return out


def reduce(spans, closed_at, late_builds=None):
    """The ``setup_spans`` line of one record. ``spans``: the record's
    list (``name``, ``start``, ``end``, ``parent`` as an index, ``attrs``);
    ``closed_at``: when it closed, ``None`` if it never did. Raises
    ``ValueError`` with the reason where the line cannot be made."""
    if closed_at is None:
        raise ValueError("the set-up record never closed: no step ran "
                         "from warm caches alone")
    first = next((s for s in spans if s["name"] == "hvd_import"), None)
    if first is None:
        raise ValueError("the set-up record holds no hvd_import: its "
                         "window has no start")
    start = first["start"]
    window = closed_at - start
    if not window > 0:
        raise ValueError(f"the set-up record's window is {window} s long")
    closed = [s for s in spans if s["end"] is not None]
    covered = merge((max(s["start"], start), min(s["end"], closed_at))
                    for s in closed
                    if min(s["end"], closed_at) > max(s["start"], start))
    named = sum(hi - lo for lo, hi in covered)
    edges = [start] + [t for pair in covered for t in pair] + [closed_at]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)[:GAPS]

    def neighbour(at, ends):
        """The span that ends (or starts) exactly at ``at``; of several
        the outermost, which is the longest."""
        near = [s for s in closed if (s["end"] if ends else s["start"])
                == at]
        return label(max(near, key=lambda s: s["end"] - s["start"])) \
            if near else ("window_start" if ends else "record_closed")

    table = programs(spans)
    step_name = step_program(spans)
    step = table.get(step_name)
    def seconds(p):
        return p["trace_s"] + p["lower_s"] + p["xla_s"]

    def listed_alone(p):
        return p is step or seconds(p) >= LISTED_FROM_S

    listed = sorted(filter(listed_alone, table.values()),
                    key=lambda p: -seconds(p))
    rest = [p for p in table.values() if not listed_alone(p)]
    other = [p for p in table.values() if p is not step]
    return {
        "window_s": window,
        "named_share": named / window,
        "unnamed_s": window - named,
        "spans": [{"name": s["name"], "at_s": s["start"] - start,
                   "seconds": (None if s["end"] is None
                               else s["end"] - s["start"]),
                   "parent": (None if s["parent"] is None
                              else spans[s["parent"]]["name"]),
                   **{k: (v - start if k.endswith("_end") else v)
                      for k, v in s["attrs"].items()}}
                  for s in spans if s["name"] not in COLUMNS],
        "step_program": step_name,
        "step": step,
        "first_build": (None if step is None
                        else first_build(spans, step_name)),
        "programs": listed,
        "others": {"programs": len(rest),
                   "builds": sum(p["builds"] for p in rest),
                   **{c: sum(p[c] for p in rest)
                      for c in COLUMNS.values()}},
        "other_programs_s": {c: sum(p[c] for p in other)
                             for c in COLUMNS.values()},
        "late_builds": {p: {"builds": n, "seconds": s}
                        for p, (n, s) in (late_builds or {}).items()},
        "gaps": [{"seconds": seconds, "at_s": lo - start,
                  "after": neighbour(lo, ends=True),
                  "before": neighbour(hi, ends=False)}
                 for seconds, lo, hi in gaps],
    }


def _read():
    try:
        from horovod_tpu.telemetry import startup
    except ImportError:
        print("benchmark: this program keeps no set-up record (it has no "
              "horovod_tpu/telemetry/startup.py); the set-up metrics are "
              "left out", file=sys.stderr)
        return None
    record = startup.RECORD
    try:
        out = reduce(list(record.spans),
                     record.closed_at if record.closed else None,
                     dict(record.late_builds))
    except ValueError as e:
        print(f"benchmark: {e}; the set-up metrics are left out",
              file=sys.stderr)
        return None
    print(json.dumps({"phase": "setup_spans", **out}), flush=True)
    return out


def of_run(run):
    """``reduce``'s dict of this process's record, read and printed once
    a run, or ``None`` (the reason once on stderr)."""
    if "setup_spans" not in run:
        run["setup_spans"] = _read()
    return run["setup_spans"]


def span_seconds(run, name, less=None):
    """The seconds of the first span called ``name`` (less those of the
    first called ``less``), or ``None`` where the record, or either span,
    is not there."""
    found = of_run(run)
    if found is None:
        return None
    seconds = []
    for wanted in (name, less):
        if wanted is None:
            continue
        span = next((s for s in found["spans"] if s["name"] == wanted
                     and s["seconds"] is not None), None)
        if span is None:
            print(f"benchmark: the set-up record holds no {wanted}; the "
                  "metric that reads it is left out", file=sys.stderr)
            return None
        seconds.append(span["seconds"])
    return seconds[0] - sum(seconds[1:])


def of_step(run, field):
    """``field`` of the step's program's line (every build before the
    record closed, summed), or ``None`` where the record is not there or
    shows no lowering under ``hvd_lower`` or a step's launch."""
    found = of_run(run)
    if found is None:
        return None
    if found["step"] is None:
        print("benchmark: the set-up record shows no lowering under "
              "hvd_lower or a step: the step's program is not known; "
              f"step_{field} is left out", file=sys.stderr)
        return None
    return found["step"][field]
