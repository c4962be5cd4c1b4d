"""Compiled-step X-ray: trace attribution, the doctor, and the trend
tool (telemetry/xprof.py, diag/xray.py, telemetry/trend.py).

Tier-1 drives the parser on checked-in synthetic trace fixtures
(tests/fixtures/xray/) so classification is exercised without a
profiler run; the one real CPU-backend capture round-trip is marked
slow (a cold ``jax.profiler`` start costs ~16 s).
"""

import gzip
import json
import os
import shutil

import pytest

from horovod_tpu.diag import xray as xray_doctor
from horovod_tpu.parallel.gspmd import (COLLECTIVE_OPS, collective_kind,
                                        collective_label)
from horovod_tpu.telemetry import trend, xprof

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "xray")


def _fixture_events(name):
    return xprof.load_trace_file(os.path.join(FIXTURES, name))


def _capture_dir(tmp_path, *fixtures):
    """Lay fixtures out in the profiler's on-disk shape
    (``<dir>/plugins/profile/<run>/*.trace.json``)."""
    run = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    run.mkdir(parents=True)
    for f in fixtures:
        shutil.copy(os.path.join(FIXTURES, f), run / f)
    return str(tmp_path)


# -- the shared classifier ---------------------------------------------------

def test_collective_kind_matches_every_priced_op():
    """The ONE classifier covers every op the byte parser prices,
    sync and async edges both."""
    for op in COLLECTIVE_OPS:
        assert collective_kind(f"{op}.1") == (op, None)
        assert collective_kind(op) == (op, None)
        assert collective_kind(f"{op}-start.2") == (op, "start")
        assert collective_kind(f"{op}-done.2") == (op, "done")
        # variadic fused instances keep plain numbering
        assert collective_kind(f"{op}.17") == (op, None)


def test_collective_kind_rejects_non_collectives():
    for name in ("all-reducer.1", "dot.3", "reduce.1", "reduce-window.2",
                 "collective-permute-start-done-ish", "copy.1", ""):
        kind, edge = collective_kind(name)
        assert kind is None or name.startswith(kind)
    assert collective_kind("all-reducer.1") == (None, None)
    assert collective_kind("reduce.4") == (None, None)


def test_classify_device_event_buckets():
    assert xprof.classify_device_event("all-reduce.3", True) == "all_reduce"
    assert xprof.classify_device_event("reduce-scatter-start.1",
                                       True) == "reduce_scatter"
    assert xprof.classify_device_event("dot.7", True) == "matmul_conv"
    assert xprof.classify_device_event("convolution.2",
                                       True) == "matmul_conv"
    assert xprof.classify_device_event("loop_fusion.9", True) == "fusion"
    assert xprof.classify_device_event("multiply_add_fusion",
                                       True) == "fusion"
    assert xprof.classify_device_event("copy.1", True) == "copy"
    assert xprof.classify_device_event("copy-start.2", True) == "copy"
    assert xprof.classify_device_event("D2D Dispatch", False) == "copy"
    assert xprof.classify_device_event("tanh.4", True) == "other_op"
    assert xprof.classify_device_event("ThunkExecutor::Execute",
                                       False) == "runtime"
    assert xprof.classify_device_event("ThreadpoolListener::StartRegion",
                                       False) == "runtime"
    # the honesty bucket: an unknown non-hlo event is NOT silently
    # binned — it degrades the gated fraction
    assert xprof.classify_device_event("SomeNewRuntimeThing",
                                       False) == "unattributed"


# -- fixture-driven attribution ----------------------------------------------

def test_overlapped_collective_hides_behind_compute():
    """Async all-reduce (-start @10µs … -done ends @120µs) fully inside
    the compute union (dot 0–100, fusion 100–140): zero exposed."""
    s = xprof.attribute(_fixture_events("overlapped.trace.json"))
    ar = s["collectives"]["all_reduce"]
    assert ar["events"] == 1
    assert ar["seconds"] == pytest.approx(110e-6, rel=1e-6)
    assert ar["overlapped_seconds"] == pytest.approx(110e-6, rel=1e-6)
    assert ar["exposed_seconds"] == 0.0
    assert s["verdict"] == "compute-bound"
    assert s["bucketed_fraction"] == pytest.approx(1.0)


def test_exposed_collective_with_no_compute_behind_it():
    """Sync all-gather + reduce-scatter after compute ended: fully
    exposed, and the verdict calls the step comms-bound."""
    s = xprof.attribute(_fixture_events("exposed.trace.json"))
    ag = s["collectives"]["all_gather"]
    rs = s["collectives"]["reduce_scatter"]
    assert ag["exposed_seconds"] == pytest.approx(30e-6, rel=1e-6)
    assert ag["overlapped_seconds"] == 0.0
    assert rs["exposed_seconds"] == pytest.approx(10e-6, rel=1e-6)
    assert s["verdict"] == "comms-bound"
    # the wrapper ThunkExecutor span self-times to ~0 under its hlo
    # children (innermost wins) — runtime must not double-count
    assert s["device_seconds"]["runtime"] == pytest.approx(0.0, abs=1e-9)
    assert s["device_seconds"]["matmul_conv"] == pytest.approx(
        40e-6, rel=1e-6)
    assert s["device_seconds"]["copy"] == pytest.approx(10e-6, rel=1e-6)
    assert s["device_seconds"]["idle"] == pytest.approx(10e-6, rel=1e-6)


def test_host_python_lane_is_not_a_device_lane():
    """The python thread annotates a few dispatch events with hlo_op
    args; its 200µs host span must not land in device attribution."""
    s = xprof.attribute(_fixture_events("overlapped.trace.json"))
    # only the two /device: lanes count
    assert s["device_lanes"] == 2
    total = sum(s["device_seconds"].values())
    assert total < 150e-6  # the 200µs PjitFunction span stayed out


def test_async_pair_torn_capture_degrades_to_start_span():
    """A -start with no -done (capture stopped mid-flight) charges its
    own event span instead of an unbounded window."""
    events = [
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 100,
         "name": "dot.1", "args": {"hlo_op": "dot.1"}},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 100, "dur": 5,
         "name": "all-reduce-start.1",
         "args": {"hlo_op": "all-reduce-start.1"}},
    ]
    s = xprof.attribute(events)
    assert s["collectives"]["all_reduce"]["seconds"] == pytest.approx(
        5e-6, rel=1e-6)


def test_unattributed_device_time_fails_the_gate():
    """A device lane dominated by an unknown event family pushes
    bucketed_fraction under the bench gate — loud, not silent."""
    events = [
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 10,
         "name": "dot.1", "args": {"hlo_op": "dot.1"}},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 10, "dur": 90,
         "name": "BrandNewBackendThing"},
    ]
    s = xprof.attribute(events)
    assert s["device_seconds"]["unattributed"] == pytest.approx(
        90e-6, rel=1e-6)
    assert s["bucketed_fraction"] < xprof.BUCKETED_GATE


def test_empty_and_torn_captures(tmp_path):
    s = xprof.attribute(_fixture_events("empty.trace.json"))
    assert s["verdict"] == "empty-capture"
    assert s["device_lanes"] == 0
    with pytest.raises(ValueError):
        _fixture_events("torn.trace.json")
    # a capture dir with ONLY a torn file raises; torn + good parses
    # the good file and reports the torn one
    d = _capture_dir(tmp_path, "torn.trace.json")
    with pytest.raises(ValueError):
        xprof.analyze_capture(d)
    d2 = _capture_dir(tmp_path / "b", "torn.trace.json",
                      "exposed.trace.json")
    s2 = xprof.analyze_capture(d2)
    assert s2["verdict"] == "comms-bound"
    assert len(s2["torn_files"]) == 1


def test_analyze_capture_picks_newest_run_and_reads_gz(tmp_path):
    old = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    new = tmp_path / "plugins" / "profile" / "2026_01_02_00_00_00"
    old.mkdir(parents=True)
    new.mkdir(parents=True)
    shutil.copy(os.path.join(FIXTURES, "overlapped.trace.json"),
                old / "host.trace.json")
    with open(os.path.join(FIXTURES, "exposed.trace.json"), "rb") as f:
        with gzip.open(new / "host.trace.json.gz", "wb") as g:
            g.write(f.read())
    s = xprof.analyze_capture(str(tmp_path))
    assert s["capture_dir"] == str(new)
    assert s["verdict"] == "comms-bound"  # the exposed fixture


def test_self_time_innermost_wins():
    """Nested events: parent is charged only its uncovered remainder."""
    events = [
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 100,
         "name": "fusion.1", "args": {"hlo_op": "fusion.1"}},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 20, "dur": 30,
         "name": "dot.2", "args": {"hlo_op": "dot.2"}},
    ]
    s = xprof.attribute(events)
    assert s["device_seconds"]["fusion"] == pytest.approx(70e-6, rel=1e-6)
    assert s["device_seconds"]["matmul_conv"] == pytest.approx(
        30e-6, rel=1e-6)


def test_verdict_rules():
    def summary(cats, colls):
        base = {c: 0.0 for c in xprof.CATEGORIES}
        base.update(cats)
        return {"device_lanes": 1, "device_seconds": base,
                "collectives": colls}

    assert xprof.verdict(summary({"matmul_conv": 1.0}, {})) == \
        "compute-bound"
    assert xprof.verdict(summary(
        {"matmul_conv": 1.0, "all_reduce": 0.5},
        {"all_reduce": {"seconds": 0.5, "exposed_seconds": 0.5,
                        "overlapped_seconds": 0.0}})) == "comms-bound"
    # modest collective share, but over half exposed: overlap-broken
    assert xprof.verdict(summary(
        {"matmul_conv": 1.0, "all_reduce": 0.15},
        {"all_reduce": {"seconds": 0.15, "exposed_seconds": 0.12,
                        "overlapped_seconds": 0.03}})) == "overlap-broken"
    assert xprof.verdict(summary(
        {"matmul_conv": 1.0, "copy": 0.3}, {})) == "copy-bound"
    assert xprof.verdict(summary(
        {"matmul_conv": 1.0, "idle": 0.8}, {})) == "idle-bound"
    assert xprof.verdict(summary({}, {})) == "empty-capture"


def test_bandwidth_join_accepts_both_label_forms():
    events = [
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 40,
         "name": "all-reduce.1", "args": {"hlo_op": "all-reduce.1"}},
        {"ph": "X", "pid": 1, "tid": 2, "ts": 0, "dur": 20,
         "name": "all-gather.1", "args": {"hlo_op": "all-gather.1"}},
    ]
    s = xprof.attribute(events, steps=2)
    xprof.join_collective_bytes(
        s, {"spmd_all_reduce": {"calls": 1, "bytes": 1_000_000},
            "all-gather": {"calls": 1, "bytes": 500_000}}, steps=2)
    ar = s["collectives"]["all_reduce"]
    ag = s["collectives"]["all_gather"]
    assert ar["bytes_per_step"] == 1_000_000
    # 1MB x 2 steps x 2 lanes / 40µs = 100 GB/s
    assert ar["effective_gbps"] == pytest.approx(100.0)
    assert ag["bytes_per_step"] == 500_000
    assert ag["effective_gbps"] == pytest.approx(100.0)


def test_xray_gauges_land_in_catalogue_registry():
    from horovod_tpu.telemetry import instruments as tele
    from horovod_tpu.telemetry.registry import MetricsRegistry
    r = MetricsRegistry()
    s = xprof.attribute(_fixture_events("exposed.trace.json"))
    xprof.join_collective_bytes(s, {"all-gather": {"bytes": 1000}},
                                steps=1)
    tele.record_xray(s, registry=r)
    text = r.render_prometheus()
    assert tele.XRAY_DEVICE_SECONDS in text
    assert tele.XRAY_BUCKETED_FRACTION in text
    assert tele.XRAY_EXPOSED_SECONDS in text
    assert tele.XRAY_COLLECTIVE_GBPS in text
    assert 'category="idle"' in text


# -- the doctor --------------------------------------------------------------

def test_doctor_xray_on_raw_capture(tmp_path, capsys):
    d = _capture_dir(tmp_path, "exposed.trace.json")
    rc = xray_doctor.main([d, "--json"])
    assert rc == 0
    out = capsys.readouterr()
    summary = json.loads(out.out)
    assert summary["verdict"] == "comms-bound"
    assert "VERDICT: comms-bound" in out.err
    assert "dominant sink" in out.err


def test_doctor_xray_prefers_written_summaries(tmp_path, capsys):
    s = xprof.attribute(_fixture_events("overlapped.trace.json"))
    path = xprof.write_summary(s, str(tmp_path), rank=3)
    assert path.endswith("xray.rank3.json")
    rc = xray_doctor.main([str(tmp_path), "--json"])
    assert rc == 0
    reread = json.loads(capsys.readouterr().out)
    assert reread["verdict"] == "compute-bound"
    assert reread["rank"] == 3


def test_doctor_xray_empty_dir_exits_2(tmp_path, capsys):
    assert xray_doctor.main([str(tmp_path)]) == 2


def test_doctor_cli_dispatch_table():
    from horovod_tpu.diag.doctor import SUBCOMMANDS
    assert set(SUBCOMMANDS) == {"hang", "perf", "serve", "xray"}


def test_doctor_cli_routes_xray(tmp_path, capsys):
    from horovod_tpu.diag.doctor import doctor_cli
    d = _capture_dir(tmp_path, "exposed.trace.json")
    assert doctor_cli(["xray", d, "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["verdict"] == "comms-bound"


# -- ledger compiled-path annotation -----------------------------------------

def test_ledger_compiled_path_flag_reaches_report(tmp_path, capsys):
    from horovod_tpu.telemetry import report as report_mod
    from horovod_tpu.telemetry.ledger import TimeLedger
    led = TimeLedger(enabled=True)
    led.start()
    led.note_compiled_path()
    led.settle_step()
    assert led.snapshot()["compiled_path"] is True
    path = led.write_dump(str(tmp_path), rank=0)
    assert json.load(open(path))["compiled_path"] is True
    report = report_mod.run(str(tmp_path))
    assert report["fleet"]["compiled_path"] is True
    text = report_mod.format_report(report)
    assert "hvd-doctor xray" in text  # the silent-zero annotation
    # an eager-path run gets no annotation
    led2 = TimeLedger(enabled=True)
    led2.start()
    led2.settle_step()
    led2.write_dump(str(tmp_path / "eager"), rank=0)
    report2 = report_mod.run(str(tmp_path / "eager"))
    assert report2["fleet"]["compiled_path"] is False
    assert "hvd-doctor xray" not in report_mod.format_report(report2)


# -- the trend tool ----------------------------------------------------------

def test_trend_direction_inference():
    assert trend.direction("step_ms_gspmd") == -1
    assert trend.direction("lm_gspmd_over_explicit_step_time") == -1
    assert trend.direction("ttft_ms") == -1
    assert trend.direction("tokens_per_sec") == 1
    assert trend.direction("mfu_vs_empirical_peak_pct") == 1
    assert trend.direction("goodput.goodput_ratio") == 1


def test_trend_flags_regressions_by_direction(tmp_path):
    r1 = tmp_path / "BENCH_r01.json"
    r2 = tmp_path / "BENCH_r02.json"
    r1.write_text(json.dumps({"parsed": {
        "step_ms_gspmd": 100.0, "tokens_per_sec": 1000.0,
        "goodput": {"goodput_ratio": 0.95}}}))
    r2.write_text(json.dumps({"parsed": {
        "step_ms_gspmd": 110.0,        # +10% step time: regression
        "tokens_per_sec": 940.0,       # -6% throughput: regression
        "goodput": {"goodput_ratio": 0.96}}}))  # better: fine
    report = trend.compare(trend.load_rounds(
        trend.find_rounds([str(tmp_path)]))[0])
    assert set(report["regressions"]) == {"step_ms_gspmd",
                                          "tokens_per_sec"}
    m = report["metrics"]["step_ms_gspmd"]
    assert m["change_pct"] == pytest.approx(10.0)
    assert "REGRESSION" in trend.format_trend(report)
    # improvements in the lower-is-better direction are not flagged
    r3 = tmp_path / "BENCH_r03.json"
    r3.write_text(json.dumps({"parsed": {"step_ms_gspmd": 90.0}}))
    report2 = trend.compare(trend.load_rounds(
        trend.find_rounds([str(tmp_path)]))[0])
    assert "step_ms_gspmd" not in report2["regressions"]


def test_trend_on_checked_in_rounds():
    """The checked-in rounds (BENCH_r06-r09 and SCALING_r01, all CPU
    rounds) parse and produce a multi-metric trend — the tool must keep
    reading what the repo actually checks in."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rounds, skipped = trend.load_rounds(trend.find_rounds([repo]))
    assert len(rounds) >= 5
    assert not skipped
    report = trend.compare(rounds)
    assert "step_ms_gspmd" in report["metrics"]
    assert "goodput.goodput_ratio" in report["metrics"]


def test_trend_cli_too_few_rounds(tmp_path):
    assert trend.main([str(tmp_path)]) == 2


def _scaling_doc(eff_2x2, unattr=0.01):
    return {"bench": "scaling", "model": "resnet18",
            "baseline_world": "1x1",
            "worlds": [
                {"world": "1x1", "efficiency": 1.0,
                 "img_per_sec_per_chip": 10.0, "step_ms_median": 100.0,
                 "goodput": {"ratio": 0.9, "unattributed_frac": unattr}},
                {"world": "2x2", "efficiency": eff_2x2,
                 "img_per_sec_per_chip": 10.0 * eff_2x2,
                 "step_ms_median": 100.0 / max(eff_2x2, 1e-9),
                 "goodput": {"ratio": 0.85,
                             "unattributed_frac": unattr}},
            ],
            "efficiency_curve": {"1x1": 1.0, "2x2": eff_2x2}}


def test_trend_reads_scaling_rounds_per_world(tmp_path):
    """SCALING_*.json sweeps join the trend as per-world series:
    a bent efficiency curve is a regression (higher-is-better), a
    cheaper step is not."""
    (tmp_path / "SCALING_r01.json").write_text(
        json.dumps(_scaling_doc(0.90)))
    (tmp_path / "SCALING_r02.json").write_text(
        json.dumps(_scaling_doc(0.70)))  # curve bent >5%: regression
    paths = trend.find_rounds([str(tmp_path)])
    assert [os.path.basename(p) for p in paths] == \
        ["SCALING_r01.json", "SCALING_r02.json"]
    report = trend.compare(trend.load_rounds(paths)[0])
    assert "scaling.2x2.efficiency" in report["regressions"]
    # step_ms got worse with the efficiency; lower-is-better catches it
    assert "scaling.2x2.step_ms_median" in report["regressions"]
    assert "scaling.1x1.efficiency" not in report["regressions"]
    assert trend.direction("scaling.2x2.efficiency") == 1
    assert trend.direction("scaling.2x2.goodput.unattributed_frac") == -1


def test_trend_mixes_bench_and_scaling_rounds(tmp_path):
    """BENCH and SCALING families coexist: disjoint key spaces, one
    report."""
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"parsed": {"step_ms_gspmd": 100.0}}))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"parsed": {"step_ms_gspmd": 101.0}}))
    (tmp_path / "SCALING_r01.json").write_text(
        json.dumps(_scaling_doc(0.9)))
    (tmp_path / "SCALING_r02.json").write_text(
        json.dumps(_scaling_doc(0.91)))
    report = trend.compare(trend.load_rounds(
        trend.find_rounds([str(tmp_path)]))[0])
    assert "step_ms_gspmd" in report["metrics"]
    assert "scaling.2x2.efficiency" in report["metrics"]
    assert report["regressions"] == []


def test_trend_on_checked_in_scaling_round():
    """The checked-in SCALING_r01.json parses into per-world metrics —
    the sweep the repo ships must keep feeding the trend tool."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "SCALING_r01.json")
    with open(path) as f:
        metrics = trend.extract_metrics(json.load(f))
    worlds = {k.split(".")[1] for k in metrics if k.startswith("scaling.")}
    assert len(worlds) >= 2
    for w in worlds:
        assert f"scaling.{w}.efficiency" in metrics
        assert metrics[f"scaling.{w}.goodput.unattributed_frac"] <= 0.02


# -- the real capture (slow) -------------------------------------------------

@pytest.mark.slow
def test_step_xray_end_to_end_and_byte_identical_programs(hvd, tmp_path):
    """The acceptance round-trip on the 8-device CPU mesh: ``step.xray``
    on real ResNet and LM GSPMD steps names a dominant sink, buckets
    >=95% of device time, joins bandwidth from HLO bytes — and the
    compiled programs are byte-identical with X-ray off (capture wraps
    the AOT executable; nothing reaches the traced function)."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd_api
    from horovod_tpu import training
    from horovod_tpu.utils.benchmarks import (make_lm_bench, make_model,
                                              synthetic_batch)

    n = len(jax.devices())

    # ResNet half
    model = make_model("resnet18")
    tx = hvd_api.DistributedOptimizer(optax.sgd(0.05))
    step = training.make_train_step(model, tx, donate=False, spmd=True)
    X, y = synthetic_batch(n, 32)
    state = training.create_train_state(model, tx,
                                        jax.random.PRNGKey(0), X[:1])
    baseline_hlo = step.lower(state, X, y).compile().as_text()
    state, _ = step(state, X, y)
    state, summary = step.xray(state, X, y, k=2,
                               profile_dir=str(tmp_path / "resnet"))
    assert summary["verdict"] in xprof.VERDICTS
    # on the CPU backend "device lanes" are executor threadpool lanes —
    # at least one per virtual device, sometimes more
    assert summary["device_lanes"] >= n
    assert summary["bucketed_fraction"] >= xprof.BUCKETED_GATE
    sink, sink_s = xprof.dominant_sink(summary)
    assert sink is not None and sink_s > 0
    ar = summary["collectives"]["all_reduce"]
    assert ar["bytes_per_step"] > 0 and "effective_gbps" in ar
    # X-ray left the compiled program untouched
    assert step.lower(state, X, y).compile().as_text() == baseline_hlo

    # LM half
    lm_step, lm_state, tokens = make_lm_bench(
        mesh=hvd_api.mesh(), seq_axis=None, flash=None, spmd=True,
        batch=2 * n, seq_len=32, layers=1, d_model=32, heads=4,
        vocab=128)
    lm_baseline = lm_step.lower(lm_state, tokens).compile().as_text()
    lm_state, _ = lm_step(lm_state, tokens)
    lm_state, lm_summary = lm_step.xray(
        lm_state, tokens, k=2, profile_dir=str(tmp_path / "lm"))
    assert lm_summary["verdict"] in xprof.VERDICTS
    assert lm_summary["bucketed_fraction"] >= xprof.BUCKETED_GATE
    assert lm_summary["collectives"]  # the fused AR is visible
    assert lm_step.lower(lm_state, tokens).compile().as_text() == \
        lm_baseline
    # the doctor reads the written summary
    assert xray_doctor.main([str(tmp_path / "resnet")]) == 0
