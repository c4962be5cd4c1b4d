"""The set-up record: what a process did between its first line of
``import horovod_tpu`` and its first step from warm caches alone.

One plain list of spans on one clock, ``time.time()``: the clock jax's own
time-span events carry, and the one the profiler stamps a
``TraceAnnotation`` with, so set-up spans, host spans and device events of
an ``.xplane.pb`` line up. Two kinds of span land here:

* the program's own (``scopes.IMPORT``, ``scopes.INIT`` and its four
  children, ``scopes.LOWER``, every ``scopes.STEP`` until the record
  closes), opened through :func:`span` or by ``training._HostStep.step``;
  each but ``hvd_import`` is a ``scopes.host`` ``TraceAnnotation`` too;
* jax's (``scopes.JAX_TRACE`` / ``JAX_LOWER`` / ``JAX_XLA``), handed over
  by the one listener the program has (``instruments
  .install_compile_listeners``): the TOP-LEVEL spans of
  ``/jax/core/compile/{jaxpr_trace,jaxpr_to_mlir_module,backend_compile}
  _duration`` with jax's ``fun_name``. jax records a scalar at each span's
  start and a time span at its end, so a depth counter a thread tells a
  top-level span without keeping its children: a kernel body traced while
  the step is lowered is lowering, a step's thousands of inner
  ``jax.numpy`` traces cost an increment and a decrement each, and the
  record's length is bounded by the number of programs built.

The record closes at the return of the first ``hvd_step`` since whose
predecessor (of any step of the process) nothing was built: the first
step that ran from warm caches alone. After that nothing is appended;
what jax builds later is counted by program name in ``late_builds``.

Always on: no knob, no registry family, no exporter. ``TimeLedger
.write_dump`` puts :meth:`Record.summary` under ``startup`` and
``telemetry/report.py`` prints it; the benchmark reads ``RECORD.spans``.
Callers go through the module (``startup.RECORD``), so that a test can
put a fresh :class:`Record` in its place.
"""

import contextlib
import threading
import time

from horovod_tpu.telemetry import scopes

# jax's event -> the name its top-level spans are kept under
KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": scopes.JAX_TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": scopes.JAX_LOWER,
    "/jax/core/compile/backend_compile_duration": scopes.JAX_XLA,
}
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                "/jax/compilation_cache/cache_misses": "miss"}
# summary()'s seconds column of each kind
_COLUMN = {scopes.JAX_TRACE: "trace_s", scopes.JAX_LOWER: "lower_s",
           scopes.JAX_XLA: "xla_s"}


class _Thread(threading.local):
    """jax's side of one thread: how deep in build spans it is, what the
    persistent cache said inside the backend span that is open, and the
    program spans open on it (innermost last)."""

    def __init__(self):
        self.depth = 0
        self.cache = None
        self.open = []


_thread = _Thread()


def program_of(fun_name):
    """``my_step`` of jax's ``fun_name``: the trace event carries the
    function's name, the lowering and backend events ``jit(my_step)``."""
    name = str(fun_name)
    if name.endswith(")") and "(" in name:
        return name[name.index("(") + 1:-1]
    return name


class Record:

    def __init__(self):
        self.spans = []   # {"name", "start", "end", "parent", "attrs"}
        self.closed = False
        self.closed_at = None
        self.builds = 0   # top-level jax spans while open, all threads
        self.late_builds = {}  # program -> [count, seconds], once closed
        self._builds_at_step_return = None
        self._lock = threading.Lock()

    def add(self, name, start, end, parent=None, **attrs):
        """Append one span (``parent``: its index in ``spans``, or None)
        and return it with its index. Nothing once the record closed."""
        entry = {"name": name, "start": start, "end": end,
                 "parent": parent, "attrs": attrs}
        with self._lock:
            if self.closed:
                return None, None
            self.spans.append(entry)
            return entry, len(self.spans) - 1

    # -- the program's own spans --------------------------------------------
    def open_span(self, name, **attrs):
        """Open a program span on this thread: jax's spans and program
        spans that end up inside it name it as their parent."""
        stack = _thread.open
        entry, index = self.add(name, time.time(), None,
                                stack[-1] if stack else None, **attrs)
        if entry is not None:
            stack.append(index)
        return entry

    def close_span(self, entry):
        entry["end"] = time.time()
        _thread.open.pop()

    def step_returned(self, entry):
        """The end of one ``hvd_step``: close the record if nothing was
        built since the step before it returned."""
        self.close_span(entry)
        if self._builds_at_step_return == self.builds:
            with self._lock:
                self.closed = True
                self.closed_at = entry["end"]
        self._builds_at_step_return = self.builds

    def grouped_attention(self, group):
        """One attention call traced with ``group`` query heads a
        key/value head (``ops/flash_attention.flash_attention``, which
        reads the shared heads in place): counted on the innermost
        program span open on this thread, ``hvd_lower`` while the step is
        built, as ``grouped_attention_calls`` and the sorted
        ``attention_groups``. A fact of the build, not of a step: the
        kernels take shared heads whenever k has fewer heads than q."""
        stack = _thread.open
        with self._lock:
            if self.closed or not stack:
                return
            attrs = self.spans[stack[-1]]["attrs"]
            attrs["grouped_attention_calls"] = attrs.get(
                "grouped_attention_calls", 0) + 1
            attrs["attention_groups"] = sorted(
                {*attrs.get("attention_groups", ()), group})

    # -- jax's spans ----------------------------------------------------------
    def built(self, name, start, end, program, cache):
        """One top-level build span of jax's, from the listener: kept
        while the record is open, counted by program once it closed."""
        stack = _thread.open
        attrs = {"program": program}
        if name == scopes.JAX_XLA:
            attrs["cache"] = cache
        entry, _ = self.add(name, start, end,
                            stack[-1] if stack else None, **attrs)
        with self._lock:
            if entry is not None:
                self.builds += 1
                return
            late = self.late_builds.setdefault(program, [0, 0.0])
            late[0] += name == scopes.JAX_LOWER
            late[1] += end - start

    # -- reading --------------------------------------------------------------
    def step_program(self):
        """The step's program, by construction: the ``fun_name`` of the
        last lowering span under the first ``hvd_lower`` (placement may
        build small programs of its own first; ``program.lower`` is the
        last thing it does) or, where nothing was lowered ahead of time,
        of the last one past the placement of the first step that
        lowered anything."""
        spans = list(self.spans)
        found = {}  # parent's index -> its last lowering's program
        for span in spans:
            if span["name"] != scopes.JAX_LOWER or span["parent"] is None:
                continue
            parent = spans[span["parent"]]
            if parent["name"] == scopes.LOWER or (
                    parent["name"] == scopes.STEP
                    and span["start"] >= parent["attrs"].get(
                        "place_end", span["start"])):
                found[span["parent"]] = span["attrs"]["program"]
        for kind in (scopes.LOWER, scopes.STEP):
            for index in sorted(found):
                if spans[index]["name"] == kind:
                    return found[index]
        return None

    def programs(self):
        """One line a program built before the record closed, in order of
        first appearance: ``builds`` (its top-level lowerings: a jaxpr
        that jax's own cache answered leaves a trace span of no length
        and no build), the seconds of its top-level trace, lowering and
        backend spans, and what the persistent cache said (``hit``,
        ``miss``, both as ``miss+hit``, or None)."""
        table = {}
        for span in list(self.spans):
            column = _COLUMN.get(span["name"])
            if column is None:
                continue
            line = table.setdefault(span["attrs"]["program"], {
                "program": span["attrs"]["program"], "builds": 0,
                "trace_s": 0.0, "lower_s": 0.0, "xla_s": 0.0,
                "cache": None})
            line[column] += span["end"] - span["start"]
            line["builds"] += span["name"] == scopes.JAX_LOWER
            said = span["attrs"].get("cache")
            if said and said not in (line["cache"] or "").split("+"):
                line["cache"] = (f"{line['cache']}+{said}" if line["cache"]
                                 else said)
        return list(table.values())

    def summary(self):
        """What ``goodput.rank<r>.json`` carries under ``startup``: the
        program's own spans (a parent by name), the table of programs,
        the step's program and what was built after the record closed."""
        spans = list(self.spans)
        own = [{"name": s["name"], "start": s["start"], "end": s["end"],
                "parent": (None if s["parent"] is None
                           else spans[s["parent"]]["name"]), **s["attrs"]}
               for s in spans if s["name"] not in _COLUMN]
        return {"closed": self.closed, "closed_at": self.closed_at,
                "spans": own, "step_program": self.step_program(),
                "programs": self.programs(),
                "late_builds": {p: {"builds": n, "seconds": s}
                                for p, (n, s) in self.late_builds.items()}}


RECORD = Record()


@contextlib.contextmanager
def span(name, **attrs):
    """A program span around the block: a ``scopes.host`` annotation in a
    profile, and an entry of the record while it is open. Yields the
    entry's attributes (a dict to add to; a throwaway one once closed)."""
    with scopes.host(name):
        entry = None if RECORD.closed else RECORD.open_span(name, **attrs)
        if entry is None:
            yield {}
            return
        try:
            yield entry["attrs"]
        finally:
            RECORD.close_span(entry)


# -- the listener's three bodies (instruments.install_compile_listeners) -----

def build_started(event):
    if event in KINDS:
        _thread.depth += 1
        _thread.cache = None


def cache_said(event):
    said = CACHE_EVENTS.get(event)
    if said is not None:
        _thread.cache = said
    return said


def build_ended(event, start, end, fun_name):
    """The seconds of a top-level build span (kept, or counted late), or
    None for a span inside another and for any other event."""
    name = KINDS.get(event)
    if name is None:
        return None
    # a listener installed inside a span sees its end alone: never under 0
    _thread.depth = depth = max(_thread.depth - 1, 0)
    if depth:
        return None
    RECORD.built(name, start, end, program_of(fun_name), _thread.cache)
    return end - start
