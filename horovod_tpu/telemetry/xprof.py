"""Compiled-step X-ray: device-trace time attribution for the GSPMD
hot path.

The compiled step is a time black box to the host-side planes: the
goodput ledger books the whole dispatch as one ``compute`` lump
(collectives compiled into the program are *inside* the step — its
``exposed_collective`` phase is structurally zero under ``spmd=True``),
and ``parallel/gspmd.collective_bytes_from_hlo`` prices the compiled
collectives in **bytes** but says nothing about *time*. This module
answers "where did my compiled step go" from the framework's own
captures:

1. **Capture** — :func:`capture_steps` wraps K executions of the
   already-compiled AOT executable in a ``jax.profiler`` device trace
   (the same capture ``/profile?seconds=N`` takes). The step's compiled
   program is untouched — X-ray orchestration lives entirely outside
   the jit, so programs are byte-identical with it off.
2. **Parse** — :func:`analyze_capture` loads the TraceViewer JSON the
   profiler wrote (``plugins/profile/<run>/*.trace.json(.gz)``),
   identifies **device lanes** (on TPU: every lane of a ``/device:*``
   pid; on the CPU backend: lanes whose events carry an ``hlo_op``
   arg), and buckets device time by op category — each collective kind
   (the same :data:`~horovod_tpu.parallel.gspmd.COLLECTIVE_OPS`
   authority the HLO byte parser matches, async ``-start``/``-done``
   pairs included), ``matmul_conv``, ``fusion``, other HLO ops,
   host↔device ``copy`` traffic, executor ``runtime`` overhead, and
   ``idle`` (no device lane doing anything). Time attribution is
   innermost-wins self time, so a wrapper event never double-counts
   its children.
3. **Attribute** — exposed vs **overlapped** collective time from
   timeline overlap: each collective's in-flight window (sync event
   span, or ``-start``→``-done`` for the async pairs the
   latency-hiding scheduler emits) is intersected with the union of
   compute intervals across all device lanes; the uncovered remainder
   is *exposed* — time the device spent exchanging with nothing to
   hide behind. Joined against the compiled module's per-op byte
   accounting, each collective also gets an **effective exchange
   bandwidth** (aggregate bytes moved / aggregate in-flight seconds).

The honesty gate mirrors the goodput ledger's: ``bucketed_fraction``
is the share of device time (self time + idle) the classifier could
*name* — device-lane events matching no known category count as
``unattributed`` and push it down, so a new runtime/backend event
family degrades LOUDLY instead of silently vanishing
(``bench.py --spmd`` errors below :data:`BUCKETED_GATE`).

Surfaces: ``step.xray(k)`` on the GSPMD train steps (returns the
threaded state + this summary), ``hvd-doctor xray <dir>``
(``diag/xray.py``), the ``step_attribution`` block in
``bench.py --spmd``, ``/profile?seconds=N&wait=1`` on the metrics
server, and the ``hvd_xray_*`` gauge family
(docs/OBSERVABILITY.md, "Where did my compiled step go").
"""

import glob
import gzip
import json
import logging
import os

from horovod_tpu.parallel.gspmd import (COLLECTIVE_OPS, collective_kind,
                                        collective_label)

logger = logging.getLogger("horovod_tpu")

# every category a device-lane second can land in (idle is derived —
# window minus busy — but reported in the same table)
COLLECTIVE_CATEGORIES = tuple(collective_label(op)
                              for op in COLLECTIVE_OPS)
CATEGORIES = COLLECTIVE_CATEGORIES + (
    "matmul_conv", "fusion", "other_op", "copy", "runtime",
    "unattributed", "idle")

# categories whose intervals count as "compute the scheduler can hide a
# collective behind" for the exposed-vs-overlapped split
COMPUTE_CATEGORIES = ("matmul_conv", "fusion", "other_op")

# bench.py --spmd fails its step_attribution block below this
BUCKETED_GATE = 0.95

# executor / runtime event families KNOWN to ride device lanes without
# being HLO ops (XLA:CPU thunk executor, pjrt transpose plans, stream
# bookkeeping). Anything on a device lane matching neither an HLO
# category nor one of these is UNATTRIBUTED — the loud bucket.
RUNTIME_PREFIXES = (
    "ThunkExecutor", "ThreadpoolListener", "Transpose", "TransposePlan",
    "TfrtCpu", "PjRt", "Stream", "ExecuteThunks", "XlaModule",
    "RunId", "Barrier", "EventPool", "BFCAllocator",
)

_MATMUL_ROOTS = ("dot", "conv", "convolution", "gemm", "matmul",
                 "einsum", "cudnn", "cublas")
_COPY_ROOTS = ("copy", "copy-start", "copy-done", "infeed", "outfeed",
               "send", "send-done", "recv", "recv-done", "transfer",
               "dynamic-update-slice-start", "host",
               "d2d", "h2d", "d2h")

# a lane whose hlo-op events are at least this share of its events is a
# device executor lane; the host python thread also annotates a FEW
# dispatch events with hlo_op args (~1% of its events empirically) and
# must not drag its 99% host bookkeeping into device attribution, while
# the sparsest real executor lane observed is ~45% hlo
DEVICE_LANE_HLO_FRACTION = 0.1

SUMMARY_PREFIX = "xray.rank"
VERDICTS = ("comms-bound", "compute-bound", "overlap-broken",
            "copy-bound", "idle-bound", "empty-capture")

# verdict thresholds, as fractions of total attributed device time
# (self time + idle) — documented in docs/OBSERVABILITY.md's runbook
EXPOSED_COMMS_BOUND = 0.25   # exposed collective time alone
OVERLAP_BROKEN_COLL = 0.10   # collective window share where ...
OVERLAP_BROKEN_EXPOSED = 0.5 # ... this share of it being exposed is broken
COPY_BOUND = 0.15
IDLE_BOUND = 0.35


def _event_root(name):
    """``all-reduce-start.1`` → matching root; ``loop_fusion.2`` →
    ``loop_fusion``. HLO numbering is ``.N``; keep dashes/underscores
    (they are part of op names)."""
    return name.split(".", 1)[0].split(" ", 1)[0]


def classify_device_event(name, has_hlo_arg=False):
    """Category of one device-lane event by name (the trace twin of the
    HLO byte parser's op matching — collective kinds come from the ONE
    shared classifier in ``parallel/gspmd.py``)."""
    kind, _edge = collective_kind(name)
    if kind is not None:
        return collective_label(kind)
    root = _event_root(name)
    lower = root.lower()
    if any(lower.startswith(r) for r in _MATMUL_ROOTS):
        return "matmul_conv"
    if "fusion" in lower:
        return "fusion"
    if any(lower == r or lower.startswith(r + "-") or
           lower.startswith(r + "_") for r in _COPY_ROOTS):
        return "copy"
    if has_hlo_arg:
        # a real HLO op we have no special bucket for (reduce, tanh,
        # scatter, ...): compute, named honestly
        return "other_op"
    if any(root.startswith(p) for p in RUNTIME_PREFIXES):
        return "runtime"
    return "unattributed"


# -- trace loading -----------------------------------------------------------

def load_trace_file(path):
    """One TraceViewer JSON (gz or plain) → its ``traceEvents`` list.
    Torn/truncated captures raise ``ValueError`` with the path."""
    try:
        if path.endswith(".gz"):
            with gzip.open(path, "rt") as f:
                doc = json.load(f)
        else:
            with open(path) as f:
                doc = json.load(f)
    except (OSError, EOFError, json.JSONDecodeError) as e:
        raise ValueError(f"unreadable trace {path}: {e}") from e
    events = doc.get("traceEvents") if isinstance(doc, dict) else None
    if not isinstance(events, list):
        raise ValueError(f"{path} has no traceEvents list")
    return events


def find_capture(profile_dir):
    """The NEWEST profiler run under ``profile_dir`` and its trace
    files: ``jax.profiler`` writes ``plugins/profile/<timestamp>/
    <host>.trace.json.gz`` per capture. Returns ``(run_dir, [paths])``
    or ``(None, [])`` when nothing was captured. ``profile_dir`` may
    also BE a run dir (or hold loose ``*.trace.json`` files)."""
    runs = sorted(glob.glob(os.path.join(
        glob.escape(profile_dir), "plugins", "profile", "*")))
    candidates = ([r for r in runs if os.path.isdir(r)] or [profile_dir])
    for run in reversed(candidates):
        paths = sorted(
            glob.glob(os.path.join(glob.escape(run), "*.trace.json.gz"))
            + glob.glob(os.path.join(glob.escape(run), "*.trace.json")))
        if paths:
            return run, paths
    return None, []


# -- attribution -------------------------------------------------------------

def _merge_intervals(intervals):
    """Sorted union of ``[(start, end)]`` — total covered length is
    ``sum(e - s)`` of the result."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap_seconds(window, merged):
    """Length of ``window ∩ merged`` (merged = sorted disjoint)."""
    lo, hi = window
    covered = 0.0
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        covered += min(e, hi) - max(s, lo)
    return covered


def _self_times(lane_events):
    """Innermost-wins self time per event of ONE lane: each event's
    duration minus the spans of events nested inside it (a
    ``ThunkExecutor::Execute`` wrapper must not double-count the HLO
    ops it ran). Events are Chrome complete events; partial overlaps
    are clipped to the enclosing span. Returns ``[(event, self_s)]``."""
    evs = sorted(lane_events, key=lambda e: (e["ts"], -e["dur"]))
    out = []
    stack = []  # indices into out, open ancestry
    for ev in evs:
        start, end = ev["ts"], ev["ts"] + ev["dur"]
        while stack and out[stack[-1]][0]["ts"] + \
                out[stack[-1]][0]["dur"] <= start:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            p_end = parent[0]["ts"] + parent[0]["dur"]
            parent[1] -= max(0.0, min(end, p_end) - start)
        out.append([ev, float(ev["dur"])])
        stack.append(len(out) - 1)
    return [(ev, max(0.0, s)) for ev, s in out]


def _device_lanes(events):
    """Group raw trace events into device lanes. A pid whose
    ``process_name`` starts with ``/device:`` is a device (TPU/GPU
    backends — every lane of it counts); otherwise a ``(pid, tid)``
    lane is a device lane when any of its events carries an ``hlo_op``
    arg (the XLA:CPU executor threads). Returns ``{(pid, tid):
    [event]}`` with events normalized to ``{ts, dur, name, hlo}`` in
    SECONDS."""
    proc_names = {}
    thread_names = {}
    lanes = {}
    lane_hlo = {}
    for e in events:
        if not e or not isinstance(e, dict):
            continue  # profilers emit empty tail elements; torn dumps
        if e.get("ph") == "M":
            if e.get("name") == "process_name":
                proc_names[e.get("pid")] = (e.get("args") or {}).get(
                    "name", "")
            elif e.get("name") == "thread_name":
                thread_names[(e.get("pid"), e.get("tid"))] = \
                    (e.get("args") or {}).get("name", "")
            continue
        if e.get("ph") not in (None, "X") or "ts" not in e:
            continue
        try:
            ts = float(e["ts"]) * 1e-6
            dur = float(e.get("dur", 0.0)) * 1e-6
        except (TypeError, ValueError):
            continue
        key = (e.get("pid"), e.get("tid"))
        has_hlo = "hlo_op" in (e.get("args") or {})
        if has_hlo:
            lane_hlo[key] = lane_hlo.get(key, 0) + 1
        lanes.setdefault(key, []).append(
            {"ts": ts, "dur": max(0.0, dur),
             "name": str(e.get("name", "")), "hlo": has_hlo})
    device = {}
    for key, lane in lanes.items():
        if str(proc_names.get(key[0], "")).startswith("/device:"):
            device[key] = lane
            continue
        # the host python thread annotates a few dispatch events with
        # hlo_op args too — only a lane MOSTLY made of hlo events is an
        # executor lane
        if str(thread_names.get(key, "")) == "python":
            continue
        if lane_hlo.get(key, 0) >= DEVICE_LANE_HLO_FRACTION * len(lane):
            device[key] = lane
    return device


def _collective_windows(lane):
    """In-flight windows ``[(kind, start, end)]`` of one lane: a sync
    collective's window is its event span; an async ``-start`` pairs
    with the NEXT ``-done`` of the same kind on the lane (the
    latency-hiding scheduler's pattern), the window reaching from the
    start event's begin to the done event's end. An unpaired start
    (torn capture) degrades to its own event span."""
    out = []
    open_starts = {}  # kind -> event
    for ev in sorted(lane, key=lambda e: e["ts"]):
        kind, edge = collective_kind(ev["name"])
        if kind is None:
            continue
        if edge == "start":
            prev = open_starts.get(kind)
            if prev is not None:  # two opens, no done: close the first
                out.append((kind, prev["ts"], prev["ts"] + prev["dur"]))
            open_starts[kind] = ev
        elif edge == "done":
            start = open_starts.pop(kind, None)
            begin = start["ts"] if start is not None else ev["ts"]
            out.append((kind, begin, ev["ts"] + ev["dur"]))
        else:
            out.append((kind, ev["ts"], ev["ts"] + ev["dur"]))
    for kind, ev in open_starts.items():
        out.append((kind, ev["ts"], ev["ts"] + ev["dur"]))
    return out


def attribute(events, steps=None):
    """The X-ray summary of one capture's raw trace events (every host
    file concatenated): per-category device self-time, idle, the
    exposed-vs-overlapped split per collective kind, and the
    ``bucketed_fraction`` honesty gate. Pure function — the synthetic-
    fixture tests drive it without a profiler run."""
    lanes = _device_lanes(events)
    categories = {c: 0.0 for c in CATEGORIES}
    compute_intervals = []
    busy_intervals = []
    windows = []
    span_lo, span_hi = None, None
    for lane in lanes.values():
        for ev, self_s in _self_times(lane):
            cat = classify_device_event(ev["name"], ev["hlo"])
            categories[cat] += self_s
            end = ev["ts"] + ev["dur"]
            busy_intervals.append((ev["ts"], end))
            if cat in COMPUTE_CATEGORIES:
                compute_intervals.append((ev["ts"], end))
            span_lo = ev["ts"] if span_lo is None else min(span_lo,
                                                           ev["ts"])
            span_hi = end if span_hi is None else max(span_hi, end)
        windows.extend(_collective_windows(lane))
    window_seconds = (span_hi - span_lo) if span_lo is not None else 0.0
    busy = _merge_intervals(busy_intervals)
    busy_seconds = sum(e - s for s, e in busy)
    idle = max(0.0, window_seconds - busy_seconds)
    categories["idle"] = idle
    compute = _merge_intervals(compute_intervals)

    collectives = {}
    for kind, s, e in windows:
        slot = collectives.setdefault(collective_label(kind), {
            "seconds": 0.0, "exposed_seconds": 0.0,
            "overlapped_seconds": 0.0, "events": 0})
        dur = max(0.0, e - s)
        hidden = _overlap_seconds((s, e), compute)
        slot["seconds"] += dur
        slot["overlapped_seconds"] += hidden
        slot["exposed_seconds"] += max(0.0, dur - hidden)
        slot["events"] += 1

    total = sum(categories.values())
    bucketed = ((total - categories["unattributed"]) / total
                if total > 0 else 0.0)
    summary = {
        "xray": 1,
        "device_lanes": len(lanes),
        "window_seconds": round(window_seconds, 9),
        "busy_seconds": round(busy_seconds, 9),
        "device_seconds": {c: round(s, 9)
                           for c, s in categories.items()},
        "bucketed_fraction": round(bucketed, 6),
        "unattributed_seconds": round(categories["unattributed"], 9),
        "collectives": {k: {f: (round(v, 9) if f != "events" else v)
                            for f, v in slot.items()}
                        for k, slot in sorted(collectives.items())},
    }
    if steps is not None:
        summary["steps"] = int(steps)
    summary["verdict"] = verdict(summary)
    return summary


def verdict(summary):
    """Name the step's dominant sink from an attribution summary — the
    fix-it table in docs/OBSERVABILITY.md keys off these:

    * ``comms-bound``    — exposed collective time ≥ 25% of device time:
      the exchange itself is the wall, overlap cannot save it.
    * ``overlap-broken`` — collectives take ≥ 10% of device time and
      over half of it is exposed: the bytes are modest but the
      scheduler is not hiding them (ordering/donation/flag problem).
    * ``copy-bound``     — host↔device copies ≥ 15% (staging problem).
    * ``idle-bound``     — no device lane busy ≥ 35% of the window (the
      host is not feeding the devices; see the goodput ledger for
      which host phase ate it).
    * ``compute-bound``  — none of the above: the device spent its time
      in matmul/fusion compute, which is the healthy verdict.
    * ``empty-capture``  — no device events parsed at all."""
    cats = summary["device_seconds"]
    total = sum(cats.values())
    if total <= 0 or summary["device_lanes"] == 0:
        return "empty-capture"
    coll_total = sum(c["seconds"]
                     for c in summary["collectives"].values())
    exposed = sum(c["exposed_seconds"]
                  for c in summary["collectives"].values())
    if exposed / total >= EXPOSED_COMMS_BOUND:
        return "comms-bound"
    if coll_total / total >= OVERLAP_BROKEN_COLL \
            and coll_total > 0 \
            and exposed / coll_total >= OVERLAP_BROKEN_EXPOSED:
        return "overlap-broken"
    if cats.get("copy", 0.0) / total >= COPY_BOUND:
        return "copy-bound"
    if cats.get("idle", 0.0) / total >= IDLE_BOUND:
        return "idle-bound"
    return "compute-bound"


def dominant_sink(summary):
    """The largest device-time category of a summary —
    ``(category, seconds)``, with exposed collective time preferred
    over raw category time when it leads (the actionable number)."""
    cats = {c: s for c, s in summary["device_seconds"].items() if s > 0}
    if not cats:
        return None, 0.0
    cat = max(cats, key=cats.get)
    return cat, cats[cat]


def join_collective_bytes(summary, compiled_collectives, steps=None):
    """Join per-collective device time against the compiled module's
    byte accounting (``step.compiled_collectives`` /
    ``gspmd.collective_bytes_from_hlo``): each kind gains
    ``bytes_per_step`` (per device) and ``effective_gbps`` — aggregate
    bytes moved across all device lanes over the captured steps,
    divided by aggregate in-flight seconds. The byte keys accept both
    raw op names and ``spmd_``-prefixed telemetry labels."""
    if not compiled_collectives:
        return summary
    steps = steps if steps is not None else summary.get("steps") or 1
    lanes = max(1, summary.get("device_lanes", 1))
    by_label = {}
    for op, tot in compiled_collectives.items():
        name = op[5:] if op.startswith("spmd_") else op
        kind, _ = collective_kind(name)
        if kind is None:  # telemetry labels are underscore-form
            kind, _ = collective_kind(name.replace("_", "-"))
        if kind is None:
            continue
        slot = by_label.setdefault(collective_label(kind), 0)
        by_label[collective_label(kind)] = slot + int(
            tot.get("bytes", 0) if isinstance(tot, dict) else tot)
    for label, slot in summary["collectives"].items():
        nbytes = by_label.get(label)
        if nbytes is None:
            continue
        slot["bytes_per_step"] = nbytes
        if slot["seconds"] > 0:
            slot["effective_gbps"] = round(
                nbytes * steps * lanes / slot["seconds"] / 1e9, 3)
    return summary


# -- capture orchestration ---------------------------------------------------

def analyze_capture(profile_dir, steps=None):
    """Parse the newest profiler run under ``profile_dir`` into an
    attribution summary (all host trace files concatenated). Raises
    ``ValueError`` when no capture exists or every file is torn."""
    run, paths = find_capture(profile_dir)
    if not paths:
        raise ValueError(f"no trace capture under {profile_dir} "
                         "(expected plugins/profile/<run>/"
                         "*.trace.json[.gz])")
    events, errors = [], []
    for p in paths:
        try:
            events.extend(load_trace_file(p))
        except ValueError as e:
            errors.append(str(e))
    if not events and errors:
        raise ValueError("; ".join(errors))
    summary = attribute(events, steps=steps)
    summary["capture_dir"] = run
    if errors:
        summary["torn_files"] = errors
    return summary


def capture_steps(run_once, steps, profile_dir):
    """Run ``run_once(i)`` K times inside one ``jax.profiler`` trace
    into ``profile_dir``, forcing each iteration to completion
    (``utils.benchmarks.sync`` — a host readback) so the device lanes
    hold exactly the K steps. Returns the last result."""
    import jax

    from horovod_tpu.utils.benchmarks import sync

    out = None
    jax.profiler.start_trace(profile_dir)
    try:
        for i in range(steps):
            out = run_once(i)
            sync(out)
    finally:
        jax.profiler.stop_trace()
    return out


def write_summary(summary, directory, rank=0):
    """Atomically drop ``xray.rank<r>.json`` into ``directory`` — the
    artifact ``hvd-doctor xray <dir>`` aggregates (the X-ray twin of
    the goodput ledger's ``goodput.rank<r>.json``)."""
    payload = dict(summary)
    payload["rank"] = int(rank)
    path = os.path.join(directory, f"{SUMMARY_PREFIX}{int(rank)}.json")
    try:
        os.makedirs(directory, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
    except OSError:
        logger.warning("xray: summary dump to %s failed", path,
                       exc_info=True)
        return None
    return path


def xray_run(step_fn, state, step_args, k=3, profile_dir=None,
             compiled_collectives=None, rank=None):
    """The ``step.xray(k)`` engine: one warm call OUTSIDE the trace
    (so a first-shape AOT compile never pollutes the capture), then K
    traced steps, parse, join bytes, record the ``hvd_xray_*`` gauges
    and write the summary next to the capture. ``state`` threads
    through every call (the steps donate their inputs as usual) and
    comes back with the summary: ``(state, summary)``."""
    import tempfile

    if k < 1:
        raise ValueError(f"xray needs at least one step, got k={k}")
    if profile_dir is None:
        profile_dir = tempfile.mkdtemp(prefix="hvd_xray_")
    holder = [state]

    def run_once(_i):
        new_state, loss = step_fn(holder[0], *step_args)
        holder[0] = new_state
        return loss

    new_state, _ = step_fn(holder[0], *step_args)  # warm outside trace
    holder[0] = new_state
    capture_steps(run_once, k, profile_dir)
    summary = analyze_capture(profile_dir, steps=k)
    coll = (compiled_collectives() if callable(compiled_collectives)
            else compiled_collectives)
    join_collective_bytes(summary, coll, steps=k)
    try:
        from horovod_tpu.telemetry import instruments as _tele
        _tele.record_xray(summary)
    # hvd-lint: disable=HVD-EXCEPT -- gauge mirror is best-effort; the summary is the product
    except Exception:
        logger.debug("xray: gauge mirror unavailable", exc_info=True)
    if rank is None:
        try:
            from horovod_tpu import basics
            rank = basics.rank()
        # hvd-lint: disable=HVD-EXCEPT -- uninitialized runtime defaults to rank 0
        except Exception:
            rank = 0
    write_summary(summary, profile_dir, rank=rank)
    return holder[0], summary
