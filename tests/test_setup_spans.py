"""The set-up record (``horovod_tpu/telemetry/startup.py``): the program's
own spans from ``import horovod_tpu`` to the first step from warm caches,
jax's top-level build spans through the one listener, and
``hvd_compile_seconds_total`` as the seconds that were spent.

Every test but the first group runs against a fresh ``Record`` put in
``startup.RECORD``'s place; the persistent cache of the tests that need
one lives in their ``tmp_path``.
"""

import contextlib
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.experimental.compilation_cache import compilation_cache

import horovod_tpu as hvd_api
from horovod_tpu import training
from horovod_tpu.telemetry import get_registry, instruments, ledger, report
from horovod_tpu.telemetry import scopes, startup

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORLD = 4
INIT_PARTS = [scopes.INIT_CONFIG, scopes.INIT_DISTRIBUTED,
              scopes.INIT_BACKEND, scopes.INIT_SERVICES]

FRESH_PROCESS = """
import json, time
before = time.time()
import horovod_tpu as hvd
from horovod_tpu.telemetry import startup
hvd.init()
once = len(startup.RECORD.spans)
hvd.init()
print(json.dumps({"before": before, "after": time.time(), "once": once,
                  "spans": startup.RECORD.spans}))
"""


@pytest.fixture(scope="module")
def fresh_process(tmp_path_factory):
    """The record of a process that imports the package and calls
    ``init()`` twice: the only way to see ``hvd_import`` as a process's
    first import of jax leaves it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(
                   tmp_path_factory.mktemp("cache")))
    out = subprocess.run([sys.executable, "-c", FRESH_PROCESS], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_import_and_init_spans_in_order(fresh_process):
    names = [s["name"] for s in fresh_process["spans"]
             if s["name"].startswith("hvd_")]
    assert names == [scopes.IMPORT, scopes.INIT] + INIT_PARTS
    starts = [s["start"] for s in fresh_process["spans"]
              if s["name"].startswith("hvd_")]
    assert starts == sorted(starts)


def test_init_parts_lie_inside_init_and_fill_it(fresh_process):
    spans = fresh_process["spans"]
    at = {s["name"]: i for i, s in enumerate(spans)}
    whole = spans[at[scopes.INIT]]
    assert whole["parent"] is None
    inside = 0.0
    for name in INIT_PARTS:
        part = spans[at[name]]
        assert part["parent"] == at[scopes.INIT]
        assert whole["start"] <= part["start"] <= part["end"] <= whole["end"]
        inside += part["end"] - part["start"]
    # the four parts are init(): what is left is a log line
    assert (whole["end"] - whole["start"]) - inside < 0.05
    assert spans[at[scopes.INIT_BACKEND]]["attrs"]["devices"] >= 1


def test_spans_are_on_time_time(fresh_process):
    """One clock: every span lies between two ``time.time()`` readings
    taken around the import and the two ``init()`` calls."""
    for s in fresh_process["spans"]:
        assert (fresh_process["before"] <= s["start"] <= s["end"]
                <= fresh_process["after"]), s


def test_hvd_import_opens_the_record_before_jax(fresh_process):
    first = fresh_process["spans"][0]
    assert first["name"] == scopes.IMPORT
    assert first["attrs"] == {"jax_was_imported": False}
    # jax, flax and optax are imported inside it
    assert first["end"] - first["start"] > 0.2


def test_second_init_adds_nothing(fresh_process):
    assert fresh_process["once"] == len(fresh_process["spans"])
    assert sum(s["name"] == scopes.INIT
               for s in fresh_process["spans"]) == 1


# -- against a fresh record ---------------------------------------------------

@pytest.fixture()
def record(hvd, monkeypatch):
    """A fresh record in the process record's place (``hvd``: the
    listener is installed by ``init()``)."""
    fresh = startup.Record()
    monkeypatch.setattr(startup, "RECORD", fresh)
    return fresh


@pytest.fixture()
def cache_dir(tmp_path):
    """A persistent cache of this test's own, empty."""
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    compilation_cache.reset_cache()
    yield tmp_path
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def _kept(record, name=None):
    return [s for s in record.spans
            if s["name"] in startup.KINDS.values()
            and name in (None, s["name"])]


@pytest.mark.parametrize("fun_name, program", [
    ("my_step", "my_step"), ("jit(my_step)", "my_step"),
    ("pmap(<lambda>)", "<lambda>")])
def test_program_of_jaxs_names(fun_name, program):
    assert startup.program_of(fun_name) == program


def test_spans_inside_another_keep_no_entry(record):
    """The listener's bodies on hand-made events: a trace inside a
    lowering belongs to the lowering; seconds come back for top-level
    spans alone."""
    trace, lower, _ = startup.KINDS
    startup.build_started(lower)
    startup.build_started(trace)
    assert startup.build_ended(trace, 1.0, 2.0, "kernel_body") is None
    assert startup.build_ended(lower, 0.5, 3.0, "jit(f)") == 2.5
    assert startup.build_ended("/jax/other", 0.0, 9.0, "f") is None
    assert [(s["name"], s["attrs"]["program"]) for s in record.spans] == [
        (scopes.JAX_LOWER, "f")]
    # an end without its start (a listener installed inside a span)
    assert startup.build_ended(trace, 3.0, 3.5, "g") == 0.5


def test_hundreds_of_jnp_calls_leave_one_trace_span(record):
    def four_hundred(x):
        for i in range(400):
            x = jnp.add(x, i)  # each a jitted jax.numpy call: a trace span
        return x

    jax.jit(four_hundred).lower(np.ones((4,), np.float32))
    traces = _kept(record, scopes.JAX_TRACE)
    assert [s["attrs"]["program"] for s in traces] == ["four_hundred"]
    # one trace and one lowering: the record is bounded by the programs
    # built, not by what they hold
    assert len(record.spans) == 2


def _slow_to_compile():
    """A program XLA's CPU backend takes a second or two over and jax
    traces and lowers in a few hundredths: a fresh function a call, so that
    jax's in-memory caches never answer for the persistent one."""
    def a_hundred_sorts(x):
        for i in range(100):
            x = lax.sort(x + i, dimension=i % 2)
        return x
    return jax.jit(a_hundred_sorts)


def test_cold_then_warm_reads_miss_then_hit(record, cache_dir):
    x = jnp.ones((32, 32))
    _slow_to_compile().lower(x).compile()
    _slow_to_compile().lower(x).compile()
    backend = [s for s in _kept(record, scopes.JAX_XLA)
               if s["attrs"]["program"] == "a_hundred_sorts"]
    assert [s["attrs"]["cache"] for s in backend] == ["miss", "hit"]
    line, = [p for p in record.programs() if p["program"] == "a_hundred_sorts"]
    assert line["builds"] == 2 and line["cache"] == "miss+hit"
    assert line["xla_s"] == pytest.approx(
        sum(s["end"] - s["start"] for s in backend))


def test_a_cache_hit_books_the_seconds_spent_not_the_seconds_saved(
        record, cache_dir):
    """``hvd_compile_seconds_total`` and the ledger's ``compile`` phase
    rise by no more than the call took: before this record they rose by
    the compile the hit did NOT make (``compile_time_saved_sec``)."""
    x = jnp.ones((32, 32))
    counter = get_registry().get(instruments.COMPILE_SECONDS)
    t0 = time.time()
    _slow_to_compile().lower(x).compile()
    cold = time.time() - t0
    run = ledger.get_ledger()
    booked, charged = counter.value, run.snapshot()["phases"]["compile"]
    t0 = time.time()
    _slow_to_compile().lower(x).compile()
    warm = time.time() - t0
    assert warm < cold / 2  # the second call did read the cache
    rose = counter.value - booked
    assert 0 < rose <= warm
    assert run.snapshot()["phases"]["compile"] - charged <= warm
    # and each second once: what was booked is the record's spans
    warm_spans = _kept(record)[-3:]
    assert [s["name"] for s in warm_spans] == [
        scopes.JAX_TRACE, scopes.JAX_LOWER, scopes.JAX_XLA]
    assert rose == pytest.approx(
        sum(s["end"] - s["start"] for s in warm_spans))


def _classifier():
    """``(step, state, (inputs, labels))`` of a small MLP through
    ``make_train_step`` on the data mesh."""
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(10)(nn.relu(nn.Dense(32)(x)))

    model = MLP()
    tx = hvd_api.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                      axes=("data",))
    rng = np.random.default_rng(0)
    inputs = jnp.asarray(rng.normal(size=(WORLD * 2, 12)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, size=(WORLD * 2,)), jnp.int32)
    state = training.create_train_state(model, tx, jax.random.PRNGKey(0),
                                        inputs[:1])
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:WORLD]), ("data",))
    step = training.make_train_step(model, tx, mesh=mesh, donate=False)
    return step, state, (inputs, labels)


@pytest.fixture()
def lowered(hvd, monkeypatch):
    """A step lowered and compiled ahead of time against a record opened
    after the step's inputs were made: ``(record, step, state, batch)``."""
    step, state, batch = _classifier()
    fresh = startup.Record()
    monkeypatch.setattr(startup, "RECORD", fresh)
    step.lower(state, *batch).compile()
    return fresh, step, state, batch


def _steps(lowered, n):
    record, step, state, batch = lowered
    for _ in range(n):
        state, _ = step(state, *batch)
    return state


def test_lower_then_two_steps(lowered):
    record = lowered[0]
    _steps(lowered, 2)
    own = [s for s in record.spans if s["name"].startswith("hvd_")]
    assert [s["name"] for s in own] == [scopes.LOWER, scopes.STEP,
                                        scopes.STEP]
    assert [s["attrs"]["step_num"] for s in own[1:]] == [0, 1]
    for s in own[1:]:
        assert (s["start"] <= s["attrs"]["place_end"]
                <= s["attrs"]["launch_end"] <= s["end"])
    # the step's program is named by the lowering under hvd_lower
    lowered_there = [s for s in _kept(record, scopes.JAX_LOWER)
                     if s["parent"] == record.spans.index(own[0])]
    assert record.step_program() == lowered_there[-1]["attrs"]["program"]
    assert record.step_program() == "hvd_train_step"
    # nothing was built between the two steps' returns: the second is the
    # first step from warm caches alone
    assert record.closed and record.closed_at == own[-1]["end"]


def test_the_first_call_reuses_what_hvd_lower_made(lowered):
    """As jax 0.9.0 really does it: the first call of the ``jax.jit``
    object after ``lower().compile()`` leaves one more top-level trace
    span (the jaxpr cache answers inside it) and lowers and compiles
    nothing: ONE build, two trace spans."""
    record = lowered[0]
    _steps(lowered, 2)
    program = record.step_program()
    line, = [p for p in record.programs() if p["program"] == program]
    assert line["builds"] == 1
    kinds = [s["name"] for s in _kept(record)
             if s["attrs"]["program"] == program]
    assert kinds == [scopes.JAX_TRACE, scopes.JAX_LOWER, scopes.JAX_XLA,
                     scopes.JAX_TRACE]
    # the second trace span is under the first step and took no time
    again = [s for s in _kept(record, scopes.JAX_TRACE)
             if s["attrs"]["program"] == program][-1]
    assert record.spans[again["parent"]]["attrs"]["step_num"] == 0
    assert again["end"] - again["start"] < line["trace_s"] / 10


def test_one_step_cannot_close_the_record(lowered):
    record = lowered[0]
    _steps(lowered, 1)
    assert not record.closed and record.closed_at is None


def test_a_build_between_two_steps_keeps_the_record_open(lowered):
    record, step, state, batch = lowered
    state, _ = step(state, *batch)
    jax.jit(lambda x: x * 3.0)(np.ones((5,), np.float32))
    state, _ = step(state, *batch)
    assert not record.closed
    state, _ = step(state, *batch)
    assert record.closed
    last = [s for s in record.spans if s["name"] == scopes.STEP][-1]
    assert record.closed_at == last["end"]
    assert last["attrs"]["step_num"] == 2


def test_later_steps_and_builds_leave_the_record_alone(lowered):
    record, step, state, batch = lowered
    state = _steps(lowered, 2)
    assert record.closed
    length = len(record.spans)
    program = record.step_program()
    # a new shape: the step's program is traced, lowered and compiled again
    inputs, labels = batch
    state, _ = step(state, jnp.concatenate([inputs, inputs]),
                    jnp.concatenate([labels, labels]))
    state, _ = step(state, *batch)
    with startup.span(scopes.LOWER):
        pass
    assert len(record.spans) == length
    assert record.late_builds[program][0] == 1
    assert record.late_builds[program][1] > 0
    assert record.summary()["late_builds"][program]["builds"] == 1


def test_a_step_that_raises_leaves_no_span_open(lowered):
    record, step, state, batch = lowered
    with pytest.raises(TypeError):
        step(state, batch[0])  # one batch argument short
    failed = [s for s in record.spans if s["name"] == scopes.STEP][-1]
    assert failed["end"] is not None and not record.closed
    jax.jit(lambda x: x - 7.0)(np.ones((6,), np.float32))
    assert _kept(record)[-1]["parent"] is None


class _NoRecord:
    """``startup`` with nothing behind it."""

    class RECORD:
        closed = True

    @staticmethod
    def span(name, **attrs):
        return contextlib.nullcontext({})


def _compiled_text():
    """``(step, state, batch, text)``: the step's optimised HLO without
    the tables of files, functions and stack frames between the module's
    header line and its first computation (they hold the test's own
    lines, which differ from one call site to the next)."""
    step, state, batch = _classifier()
    out, tables = [], False
    for line in step.lower(state, *batch).compile().as_text().split("\n"):
        if line == "FileNames":
            tables = True
        elif tables and line.startswith(("%", "ENTRY")):
            tables = False
        if not tables:
            out.append(line)
    assert len(out) > 10, "the module's computations were cut away"
    return step, state, batch, "\n".join(out)


def test_the_record_changes_no_program(hvd, monkeypatch):
    """Host-side floats only: the optimised HLO of the step is the same
    text, every instruction's metadata with it, with the record patched
    out (compiled with the persistent cache off, so that neither text
    comes out of it)."""
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with_record = _compiled_text()[-1]
        monkeypatch.setattr(training, "_startup", _NoRecord)
        step, state, batch, without = _compiled_text()
        state, loss = step(state, *batch)  # and the closed path runs
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert np.isfinite(float(loss))
    assert "ENTRY" in with_record and with_record == without


def test_write_dump_carries_the_record(lowered, tmp_path):
    _steps(lowered, 1)
    run = ledger.TimeLedger(enabled=True)
    run.start()
    with open(run.write_dump(str(tmp_path), rank=0)) as f:
        dump = json.load(f)
    kept = dump["startup"]
    assert kept["closed"] is False and kept["closed_at"] is None
    assert [s["name"] for s in kept["spans"]] == [
        scopes.LOWER, scopes.STEP]
    assert kept["step_program"] == "hvd_train_step"
    line, = [p for p in kept["programs"]
             if p["program"] == kept["step_program"]]
    assert set(line) == {"program", "builds", "trace_s", "lower_s",
                         "xla_s", "cache"}
    assert line["builds"] == 1 and line["trace_s"] > 0


def test_report_prints_the_start_up_under_the_phases(lowered, tmp_path):
    _steps(lowered, 2)
    run = ledger.TimeLedger(enabled=True)
    run.start()
    run.write_dump(str(tmp_path), rank=0)
    dumps, _ = report.load_dumps(str(tmp_path))
    text = report.format_report(report.aggregate(dumps))
    assert "rank 0 start-up:" in text
    assert "to the first step from warm caches" in text
    assert "step program: hvd_train_step" in text
    assert scopes.LOWER in text and "builds" in text
