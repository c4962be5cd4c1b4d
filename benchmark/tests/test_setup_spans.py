"""``harness/setup_spans.py`` on a hand-made record with known answers,
on records that cannot be read (never closed, no ``hvd_import``, no record
at all: every reader ``None``, one line on stderr), and the seven readers
against ``BENCHMARK.json``."""

import importlib
import json
import os
import sys

import pytest

from benchmark.harness import setup_spans

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
READERS = ("import_s", "backend_init_s", "init_rest_s", "step_trace_s",
           "step_lower_s", "step_xla_s", "step_builds")
T0 = 1_791_000_000.0  # a wall-clock reading: the record's clock


def span(name, start, end, parent=None, **attrs):
    return {"name": name, "start": T0 + start, "end": T0 + end,
            "parent": parent, "attrs": attrs}


def jax_span(kind, start, end, program, parent=None, **attrs):
    return span(kind, start, end, parent, program=program, **attrs)


def record():
    """A set-up of 40 s: import 0-3, init 5-13 (backend 6-12), the
    state's program 14-18, ``hvd_lower`` 20-27 (placement lowers a small
    program first, then the step: trace 21-24 with nothing kept of what
    is inside it, lowering 24-27), the step's cache read 27-29, the
    first step 30-31 with a second, empty trace of the step, a reference
    program 31-36 (overlapping spans: its lowering 32-35 lies inside a
    program span of the benchmark's own that the record does not have),
    two warm-up steps 38-39 and 39-40."""
    return [
        span("hvd_import", 0, 3, jax_was_imported=True),
        span("hvd_init", 5, 13),
        span("hvd_init_config", 5, 5.5, 1),
        span("hvd_init_distributed", 5.5, 6, 1),
        span("hvd_init_backend", 6, 12, 1, devices=4),
        span("hvd_init_services", 12, 13, 1),
        jax_span("jax_trace", 14, 15, "init"),
        jax_span("jax_lower", 15, 16, "init"),
        jax_span("jax_xla", 16, 18, "init", cache="hit"),
        span("hvd_lower", 20, 27),
        jax_span("jax_lower", 20.25, 20.5, "_multi_slice", 9),
        jax_span("jax_trace", 21, 24, "hvd_lm_train_step", 9),
        jax_span("jax_lower", 24, 27, "hvd_lm_train_step", 9),
        jax_span("jax_xla", 27, 29, "hvd_lm_train_step", cache="hit"),
        span("hvd_step", 30, 31, step_num=0, place_end=T0 + 30.5,
             launch_end=T0 + 31),
        jax_span("jax_trace", 30.5, 30.5, "hvd_lm_train_step", 14),
        jax_span("jax_trace", 31, 33, "reference"),
        jax_span("jax_lower", 32, 35, "reference"),
        jax_span("jax_xla", 35, 36, "reference", cache="miss"),
        span("hvd_step", 38, 39, step_num=1, place_end=T0 + 38.5,
             launch_end=T0 + 39),
        span("hvd_step", 39, 40, step_num=2, place_end=T0 + 39.5,
             launch_end=T0 + 40),
    ]


@pytest.fixture()
def line():
    return setup_spans.reduce(record(), T0 + 40,
                              {"hvd_lm_train_step": [2, 9.0]})


def test_merge_is_the_union_of_overlapping_spans():
    assert setup_spans.merge([(5, 7), (0, 2), (1, 3), (6, 6.5)]) == [
        (0, 3), (5, 7)]


def test_named_share_counts_every_second_once(line):
    # named: 0-3, 5-13, 14-18, 20-29, 30-36, 38-40 = 32 of 40
    assert line["window_s"] == pytest.approx(40.0)
    assert line["named_share"] == pytest.approx(32 / 40)
    assert line["unnamed_s"] == pytest.approx(8.0)


def test_gaps_are_named_by_their_neighbours(line):
    assert [g["seconds"] for g in line["gaps"]] == sorted(
        (g["seconds"] for g in line["gaps"]), reverse=True)
    gaps = sorted(((g["seconds"], g["at_s"], g["after"], g["before"])
                   for g in line["gaps"]),
                  key=lambda g: (-round(g[0], 3), g[1]))
    assert gaps == [
        (pytest.approx(2.0), pytest.approx(3.0), "hvd_import", "hvd_init"),
        (pytest.approx(2.0), pytest.approx(18.0), "jax_xla:init",
         "hvd_lower"),
        (pytest.approx(2.0), pytest.approx(36.0), "jax_xla:reference",
         "hvd_step"),
        (pytest.approx(1.0), pytest.approx(13.0), "hvd_init",
         "jax_trace:init"),
        (pytest.approx(1.0), pytest.approx(29.0),
         "jax_xla:hvd_lm_train_step", "hvd_step"),
    ]


def test_the_steps_program_is_the_last_lowering_under_hvd_lower(line):
    assert line["step_program"] == "hvd_lm_train_step"
    assert line["step"] == {
        "program": "hvd_lm_train_step", "builds": 1, "traces": 2,
        "trace_s": pytest.approx(3.0), "lower_s": pytest.approx(3.0),
        "xla_s": pytest.approx(2.0), "cache": "hit"}
    assert line["first_build"] == {
        "hvd_lower_s": pytest.approx(7.0), "place_s": pytest.approx(1.0),
        "trace_s": pytest.approx(3.0), "lower_s": pytest.approx(3.0),
        "xla_s": pytest.approx(2.0), "cache": "hit"}
    assert line["other_programs_s"] == {
        "trace_s": pytest.approx(3.0), "lower_s": pytest.approx(4.25),
        "xla_s": pytest.approx(3.0)}
    assert line["late_builds"] == {
        "hvd_lm_train_step": {"builds": 2, "seconds": 9.0}}


def test_without_hvd_lower_the_first_launch_names_the_step():
    spans = [s for s in record() if s["name"] != "hvd_lower"
             and s["parent"] != 9]
    step = next(i for i, s in enumerate(spans) if s["name"] == "hvd_step")
    spans.insert(step + 1, jax_span("jax_lower", 30.75, 31,
                                    "hvd_train_step", step))
    for s in spans[step + 2:]:
        if s["parent"] is not None:
            s["parent"] = step
    assert setup_spans.step_program(spans) == "hvd_train_step"
    # a lowering before the step's placement ended is placement's
    spans[step + 1]["start"] = T0 + 30.25
    assert setup_spans.step_program(spans) is None


def test_program_spans_are_listed_relative_to_the_windows_start(line):
    own = line["spans"]
    assert [s["name"] for s in own][:6] == [
        "hvd_import", "hvd_init", "hvd_init_config",
        "hvd_init_distributed", "hvd_init_backend", "hvd_init_services"]
    backend = own[4]
    assert backend["at_s"] == pytest.approx(6.0)
    assert backend["seconds"] == pytest.approx(6.0)
    assert backend["parent"] == "hvd_init" and backend["devices"] == 4
    first_step = next(s for s in own if s["name"] == "hvd_step")
    assert first_step["place_end"] == pytest.approx(30.5)
    assert not any(s["name"].startswith("jax_") for s in own)
    json.dumps(line)  # the line is printed as JSON


def _run_with(line):
    return {"setup_spans": line}


def test_the_seven_readers_on_the_record(line):
    run = _run_with(line)
    got = {name: importlib.import_module(
        f"benchmark.layer_metrics.{name}").read(run) for name in READERS}
    assert got == {
        "import_s": pytest.approx(3.0), "backend_init_s": pytest.approx(6.0),
        "init_rest_s": pytest.approx(2.0),
        "step_trace_s": pytest.approx(3.0),
        "step_lower_s": pytest.approx(3.0), "step_xla_s": pytest.approx(2.0),
        "step_builds": 1}
    # the inside reading stays under the outside one it splits
    assert got["import_s"] + got["backend_init_s"] + got["init_rest_s"] \
        <= 13.0


@pytest.mark.parametrize("spans, closed_at, reason", [
    (record(), None, "never closed"),
    (record()[1:], T0 + 40, "no hvd_import"),
    (record(), T0 - 1, "window"),
])
def test_a_record_that_cannot_be_read_raises_the_reason(spans, closed_at,
                                                        reason):
    with pytest.raises(ValueError, match=reason):
        setup_spans.reduce(spans, closed_at)


def _all_none(capsys, run):
    for name in READERS:
        reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
        assert reader.read(run) is None, name
    return capsys.readouterr()


def test_a_record_that_never_closed_reads_as_nothing(capsys, monkeypatch):
    from horovod_tpu.telemetry import startup

    open_record = startup.Record()
    open_record.spans.extend(record())
    monkeypatch.setattr(startup, "RECORD", open_record)
    said = _all_none(capsys, {})
    assert said.err.count("\n") == 1 and "never closed" in said.err
    assert "setup_spans" not in said.out


def test_no_record_reads_as_nothing_with_one_line_on_stderr(
        capsys, monkeypatch):
    """The parent commit under this PR's benchmark files: the program has
    no ``telemetry/startup.py``."""
    import horovod_tpu.telemetry

    monkeypatch.delattr(horovod_tpu.telemetry, "startup")
    monkeypatch.setitem(sys.modules, "horovod_tpu.telemetry.startup", None)
    said = _all_none(capsys, {})
    assert said.err.count("\n") == 1
    assert "keeps no set-up record" in said.err
    assert said.out == ""


def test_an_absent_span_never_reads_as_zero_seconds(line, capsys):
    line["spans"] = [s for s in line["spans"]
                     if s["name"] != "hvd_init_backend"]
    line["step"] = None
    run = _run_with(line)
    for name in ("backend_init_s", "init_rest_s", "step_trace_s",
                 "step_builds"):
        reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
        assert reader.read(run) is None, name
    assert importlib.import_module(
        "benchmark.layer_metrics.import_s").read(run) == pytest.approx(3.0)
    assert "hvd_init_backend" in capsys.readouterr().err


def test_the_closed_record_of_this_process_is_printed_once(capsys,
                                                           monkeypatch):
    from horovod_tpu.telemetry import startup

    closed = startup.Record()
    closed.spans.extend(record())
    closed.closed, closed.closed_at = True, T0 + 40
    monkeypatch.setattr(startup, "RECORD", closed)
    run = {}
    for name in READERS:
        importlib.import_module(
            f"benchmark.layer_metrics.{name}").read(run)
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    printed = json.loads(out[0])
    assert printed["phase"] == "setup_spans"
    assert printed["named_share"] == pytest.approx(0.8)


@pytest.mark.parametrize("name", READERS)
def test_readers_match_their_entries(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
        entry["layer"], entry["unit"], entry["moves"])
    assert entry["moves"] == "setup_s" and "workloads" not in entry
    assert entry["better"] == "lower"
    assert entry["source"] == ("program_counter" if name == "step_builds"
                               else "program_span")
