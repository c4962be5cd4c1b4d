"""``harness/phases.py`` on the ``op_name`` shapes the chip writes, on a
hand-made two-chip trace with known answers, and on PR 22's two recorded
fixtures (which carry no scope: the readers must say nothing there)."""

import gzip
import os

import pytest
from jax.profiler import ProfileData

from benchmark.harness import hlo, phases, xplane
from benchmark.harness.xplane import Event
from benchmark.layer_metrics import (exchange_pct, host_dispatch_ms,
                                     loss_head_pct, optimizer_pct)

STEP = "jit(hvd_lm_train_step)/shard_map/"


@pytest.mark.parametrize("op_name,phase", [
    (STEP + "hvd_exchange/bucket3/psum", "exchange"),
    (STEP + "hvd_exchange/bucket0/hvd_exchange/bucket0/all_to_all",
     "exchange"),
    (STEP + "hvd_optimizer/mul", "optimizer"),
    (STEP + "transpose(jvp(Transformer))/lm_head/dot_general", "loss_head"),
    (STEP + "jvp(Transformer)/lm_head/dot_general", "loss_head"),
    (STEP + "transpose(jvp(hvd_loss))/mul", "loss_head"),
    (STEP + "jvp(hvd_loss)/log_softmax/reduce_max", "loss_head"),
    (STEP + "hvd_loss/psum", "loss_head"),
    ("jit(hvd_train_step)/shard_map/jvp(hvd_loss)/reduce_sum", "loss_head"),
    (STEP + "transpose(jvp(Transformer))/block_3/attn/pallas_call",
     "backward"),
    (STEP + "jvp(Transformer)/block_3/Dense_0/dot_general", "forward"),
    # the innermost scope wins over the direction and over an outer scope
    (STEP + "transpose(jvp(Transformer))/hvd_exchange/bucket1/add",
     "exchange"),
    (STEP + "hvd_exchange/bucket1/hvd_optimizer/add", "optimizer"),
    # an empty op_name: the compiler's own insertion
    ("", "compiler"),
    # a source operation nobody owns
    ("jit(outer)/shard_map/psum", "unscoped"),
    (STEP + "add", "unscoped"),
    # part of another word is no scope
    (STEP + "my_hvd_loss_scale/mul", "unscoped"),
    (STEP + "jvp(Transformer)/not_lm_head/dot_general", "forward"),
])
def test_phase_of(op_name, phase):
    assert phases.phase_of(op_name) == phase


# two chips, a window of 10 s and three steps each. Chip 1's exchange is
# the longer one; chip 0's optimizer is.
TABLE = {
    "fusion.1": {"op_name": STEP + "jvp(Transformer)/block_0/mul"},
    "fusion.2": {"op_name": STEP + "transpose(jvp(Transformer))/block_0/mul"},
    "all-reduce.1": {"op_name": STEP + "hvd_exchange/bucket0/psum"},
    "fusion.3": {"op_name": STEP + "hvd_optimizer/add"},
    "fusion.4": {"op_name": STEP + "jvp(hvd_loss)/reduce_sum"},
    "copy-done.1": {"op_name": ""},
    "fusion.5": {"op_name": STEP + "add"},
}


def _summary(by_name_of_chip):
    return {"chips": {chip: {"window_s": 10.0, "by_name": by_name}
                      for chip, by_name in by_name_of_chip.items()}}


SUMMARY = _summary({
    0: {"fusion.1": 3.0, "fusion.2": 3.0, "all-reduce.1": 1.0,
        "fusion.3": 1.5, "fusion.4": 1.0, "copy-done.1": 0.4,
        "fusion.5": 0.1},
    1: {"fusion.1": 3.0, "fusion.2": 3.0, "all-reduce.1": 2.0,
        "fusion.3": 0.5, "fusion.4": 1.0, "copy-done.1": 0.4,
        "fusion.5": 0.1},
})
SPANS = [Event("hvd_step", 0.0, 0.090), Event("hvd_place", 0.001, 0.071),
         Event("hvd_launch", 0.071, 0.089),
         Event("hvd_step", 0.1, 0.180), Event("hvd_place", 0.101, 0.161),
         Event("hvd_launch", 0.161, 0.179),
         Event("hvd_step", 0.2, 0.300), Event("hvd_place", 0.201, 0.281),
         Event("hvd_launch", 0.281, 0.299)]


def test_summarize_reports_the_worst_chip():
    got = phases.summarize(SUMMARY, TABLE, SPANS, steps=3)
    assert got["chips"][1]["self_s"]["exchange"] == pytest.approx(2.0)
    assert got["share"]["exchange"] == pytest.approx(0.2)      # chip 1
    assert got["share"]["optimizer"] == pytest.approx(0.15)    # chip 0
    assert got["share"]["loss_head"] == pytest.approx(0.1)
    assert got["share"]["forward"] == pytest.approx(0.3)
    assert got["share"]["backward"] == pytest.approx(0.3)
    assert got["share"]["compiler"] == pytest.approx(0.04)
    assert got["ms_per_step"]["exchange"] == pytest.approx(2000 / 3)
    # sourced 2 x 9.6 s, unowned 2 x 0.1 s
    assert got["scoped_share"] == pytest.approx(1 - 0.2 / 19.2)
    assert got["unscoped"] == [(STEP + "add", pytest.approx(0.1))]
    assert got["host"] == {
        "steps": 3, "hvd_step_ms": pytest.approx(90.0),
        "hvd_place_ms": pytest.approx(70.0),
        "hvd_launch_ms": pytest.approx(18.0)}


def test_an_event_the_table_lacks_is_not_owned():
    got = phases.summarize(_summary({0: {"fusion.1": 9.0, "fusion.99": 1.0}}),
                           TABLE, [], steps=1)
    assert got["share"]["unscoped"] == pytest.approx(0.1)
    assert got["unscoped"] == [("fusion.99", pytest.approx(1.0))]
    assert got["host"]["steps"] == 0 and got["host"]["hvd_step_ms"] is None


READERS = {"exchange": exchange_pct, "optimizer": optimizer_pct,
           "loss_head": loss_head_pct}


@pytest.mark.parametrize("phase", sorted(READERS))
def test_device_readers(phase, capsys):
    scoped = phases.summarize(SUMMARY, TABLE, SPANS, steps=3)
    assert READERS[phase].read({"phases": scoped}) == pytest.approx(
        100.0 * scoped["share"][phase])
    # 94% owned: a missing scope must not read as a small number
    holed = phases.summarize(
        _summary({0: {"fusion.1": 9.4, "fusion.5": 0.6}}), TABLE, SPANS, 3)
    assert holed["scoped_share"] == pytest.approx(0.94)
    assert READERS[phase].read({"phases": holed}) is None
    assert "scoped_share 0.9400" in capsys.readouterr().err
    # the run could not tell at all (a program without scopes)
    assert READERS[phase].read({"phases": None}) is None


def test_host_reader():
    scoped = phases.summarize(SUMMARY, TABLE, SPANS, steps=3)
    assert host_dispatch_ms.read({"phases": scoped}) == pytest.approx(90.0)
    spanless = phases.summarize(SUMMARY, TABLE, [], steps=3)
    assert host_dispatch_ms.read({"phases": spanless}) is None
    assert host_dispatch_ms.read({"phases": None}) is None


HOST = [Event("dispatch", 0.0, 4.0), Event("hvd_step", 0.1, 1.0),
        Event("hvd_place", 0.2, 0.8), Event("hvd_launch", 0.8, 0.95),
        Event("hvd_step", 1.1, 2.0), Event("block", 4.0, 9.0)]


@pytest.mark.parametrize("gap,span", [
    ((0.3, 0.7), "hvd_place"),     # dispatch, hvd_step and hvd_place cover it
    ((0.82, 0.9), "hvd_launch"),
    ((0.3, 0.9), "hvd_step"),      # hvd_place covers less of it
    ((2.5, 3.5), "dispatch"),      # by dispatch alone
    ((5.0, 6.0), "block"),
    ((9.5, 9.9), "no_span"),
])
def test_a_gap_is_named_for_the_innermost_span(gap, span):
    assert phases.innermost_span_over(gap, HOST) == span


def test_without_program_spans_a_gap_is_named_as_before():
    ours = [e for e in HOST if not e.name.startswith("hvd_")]
    for gap in ((0.3, 0.7), (2.5, 3.5), (3.5, 4.5), (9.5, 9.9)):
        assert (phases.innermost_span_over(gap, ours)
                == xplane._host_span_over(gap, ours))


def test_idle_gaps_of_the_chip_that_idled_most():
    chips = {0: [Event("a", 0.0, 1.0), Event("b", 1.0, 2.0)],
             1: [Event("a", 0.0, 0.3), Event("b", 0.7, 0.82),
                 Event("c", 0.9, 2.0)]}
    assert phases.idle_gaps(chips, HOST) == [
        ["hvd_place", pytest.approx(0.4)],
        ["hvd_launch", pytest.approx(0.08)]]
    assert phases.idle_gaps({}, HOST) == []


# ---- PR 22's recorded fixtures: no scope in them ---------------------------
FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")


def _recorded(stem):
    def read(name):
        with gzip.open(os.path.join(FIXTURES, name)) as f:
            return f.read()

    table = hlo.instruction_table(read(stem + ".hlo.txt.gz").decode())
    trace = xplane.parse(ProfileData.from_serialized_xspace(
        read(stem + ".xplane.pb.gz")))
    return table, xplane.reduce(trace, lambda name: table.get(name, {}).get(
        "category", "unattributed"))


# shares of device self time by what the op_names already carried before
# the program named anything (ISSUE 24's table, from these two files)
@pytest.mark.parametrize("stem,unscoped,compiler,ms_unscoped", [
    ("lm-d768-1chip", 0.0348, 0.0293, 6.67),
    ("lm-d768-4chip", 0.1219, 0.0323, 25.65),
])
def test_recorded_fixtures_have_unowned_time_and_say_nothing(
        stem, unscoped, compiler, ms_unscoped):
    table, summary = _recorded(stem)
    got = phases.summarize(summary, table, [], steps=3)
    assert got["share"]["exchange"] == got["share"]["optimizer"] == 0.0
    assert got["share"]["unscoped"] == pytest.approx(unscoped, abs=5e-5)
    assert got["share"]["compiler"] == pytest.approx(compiler, abs=5e-5)
    assert got["ms_per_step"]["unscoped"] == pytest.approx(ms_unscoped,
                                                           abs=5e-3)
    assert got["scoped_share"] < phases.MIN_SCOPED_SHARE + 0.02
    assert all("hvd_" not in name for name, _ in got["unscoped"])
    run = {"phases": got}
    if got["scoped_share"] < phases.MIN_SCOPED_SHARE:
        assert exchange_pct.read(run) is None
    assert host_dispatch_ms.read(run) is None
