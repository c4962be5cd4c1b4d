"""From a ``jax.profiler`` trace (``.xplane.pb``) to seconds.

``load`` reads the file with ``jax.profiler.ProfileData`` and keeps, for
every TPU chip, the events of its ``XLA Ops`` and ``Async XLA Ops`` lines,
and from the host the benchmark's own spans. ``reduce`` is arithmetic on those plain
tuples (the interval arithmetic of ``horovod_tpu/telemetry/xprof.py``,
copied): the union of busy intervals, innermost-wins self time by
category and by name, collective in-flight time that no computation on
that chip covers, and the longest idle gaps named for the host span
that covers them. ``reduce`` never touches jax, so the tests drive it
with hand-made events.
"""

import glob
import os
import re
from collections import namedtuple

Event = namedtuple("Event", "name start end")  # seconds on one clock

DEVICE_PLANE_RE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_SPANS = ("dispatch", "block", "between_windows")
COMPUTE = ("pallas_kernel", "matmul_conv", "fusion", "other_op", "copy")


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _events(line, keep=None):
    """A line's events under the name of their HLO instruction: the chip
    names an event by the instruction's whole text, ``%name = type
    op(...)``."""
    out = []
    for e in line.events:
        name = e.name.split(" = ", 1)[0].lstrip("%")
        if keep is None or name in keep:
            out.append(Event(name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9))
    return out


def parse(profile):
    """``{"chips": {ordinal: [Event]}, "async": {ordinal: [Event]},
    "host": [Event]}`` of a ``ProfileData``: per chip the operations its
    core ran (``XLA Ops``) and the windows its asynchronous operations
    were in flight (``Async XLA Ops``: start of ``X-start`` to end of
    ``X-done``, under the start's name), and the benchmark's host spans.
    One clock."""
    chips, in_flight, host = {}, {}, []
    for plane in profile.planes:
        m = DEVICE_PLANE_RE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    chips.setdefault(int(m.group(1)), []).extend(
                        _events(line))
                elif line.name == ASYNC_LINE:
                    in_flight.setdefault(int(m.group(1)), []).extend(
                        _events(line))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(_events(line, HOST_SPANS))
    return {"chips": chips, "async": in_flight,
            "host": sorted(host, key=lambda e: e.start)}


def load(path):
    from jax.profiler import ProfileData

    return parse(ProfileData.from_file(path))


# ---- interval arithmetic --------------------------------------------------

def merge(intervals):
    """Sorted disjoint union of ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(window, merged):
    """Length of ``window`` that the disjoint sorted ``merged`` covers."""
    lo, hi = window
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def self_times(events):
    """``[(event, self seconds)]`` of one line, innermost wins: an
    event's time less what the events nested inside it cover (a
    ``while`` must not count its body twice)."""
    out, stack = [], []
    for ev in sorted(events, key=lambda e: (e.start, -(e.end - e.start))):
        while stack and out[stack[-1]][0].end <= ev.start:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[1] -= max(0.0, min(ev.end, parent[0].end) - ev.start)
        out.append([ev, ev.end - ev.start])
        stack.append(len(out) - 1)
    return [(ev, max(0.0, s)) for ev, s in out]


def collective_windows(events, in_flight, is_collective):
    """In-flight windows of one chip's collectives. A synchronous one is
    its own span on the operations line. An asynchronous one is on the
    chip's in-flight line from the start of its ``-start`` to the end of
    its ``-done``; those two instructions are on the operations line as
    well, inside the window, so the union counts nothing twice."""
    return merge((e.start, e.end) for e in list(events) + list(in_flight)
                 if is_collective(e.name))


def _host_span_over(gap, host):
    """The benchmark's host span that covers most of ``gap``."""
    best, best_cover = "no_span", 0.0
    for ev in host:
        cover = min(ev.end, gap[1]) - max(ev.start, gap[0])
        if cover > best_cover:
            best, best_cover = ev.name, cover
    return best


def reduce_chip(events, in_flight, host, classify):
    """One chip's traced window: ``events`` its operations, ``in_flight``
    its asynchronous windows. ``classify(name) -> category`` (of
    ``harness.hlo.instruction_table``, or ``unattributed``)."""
    if not events:
        return None
    lo = min(e.start for e in events)
    hi = max(e.end for e in events)
    busy = merge((e.start, e.end) for e in events)
    busy_s = sum(e - s for s, e in busy)
    by_category, by_name = {}, {}
    for ev, self_s in self_times(events):
        cat = classify(ev.name)
        by_category[cat] = by_category.get(cat, 0.0) + self_s
        by_name[ev.name] = by_name.get(ev.name, 0.0) + self_s
    compute = merge((e.start, e.end) for e in events
                    if classify(e.name) in COMPUTE)
    in_flight = collective_windows(
        events, in_flight, lambda n: classify(n) == "collective")
    in_flight_s = sum(e - s for s, e in in_flight)
    exposed_s = in_flight_s - sum(covered(w, compute) for w in in_flight)
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": hi - lo, "busy_s": busy_s,
        "idle_s": (hi - lo) - busy_s,
        "by_category": by_category, "by_name": by_name,
        "collective_in_flight_s": in_flight_s,
        "collective_exposed_s": max(0.0, exposed_s),
        "gaps": [(_host_span_over(g, host), g[1] - g[0])
                 for g in gaps[:10]],
    }


def reduce(trace, classify):
    """The whole trace: one ``reduce_chip`` per chip under ``"chips"``,
    plus what the metrics read. Shares are of the traced window; where
    chips differ the worst chip is reported and ``busy_s``/``window_s``
    are averaged over the chips, as the contract's ``device`` wants."""
    chips = {k: reduce_chip(v, trace.get("async", {}).get(k, []),
                            trace["host"], classify)
             for k, v in sorted(trace["chips"].items())}
    chips = {k: v for k, v in chips.items() if v}
    if not chips:
        return {"chips": {}}
    n = len(chips)
    per = list(chips.values())
    total_self = sum(sum(c["by_category"].values()) for c in per)
    named = total_self - sum(c["by_category"].get("unattributed", 0.0)
                             for c in per)
    by_name = {}
    for c in per:
        for name, s in c["by_name"].items():
            by_name[name] = by_name.get(name, 0.0) + s / n

    cats = sorted({k for c in per for k in c["by_category"]})
    return {
        "chips": chips,
        "busy_s": sum(c["busy_s"] for c in per) / n,
        "window_s": sum(c["window_s"] for c in per) / n,
        "idle_share": max(c["idle_s"] / c["window_s"] for c in per),
        "category_share": {cat: max(
            c["by_category"].get(cat, 0.0) / c["window_s"] for c in per)
            for cat in cats},
        "category_s": {cat: sum(
            c["by_category"].get(cat, 0.0) for c in per) / n
            for cat in cats},
        "exposed_collective_share": max(
            c["collective_exposed_s"] / c["window_s"] for c in per),
        "named_share": named / total_self if total_self else 0.0,
        "unattributed": sorted(
            {name for c in per for name in c["by_name"]
             if classify(name) == "unattributed"}),
        "by_name": by_name,
        "gaps": max(per, key=lambda c: c["idle_s"])["gaps"],
    }
