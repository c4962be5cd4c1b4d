"""Unit tests for the shared timing scaffold (utils/benchmarks.py) —
the measurement discipline every bench path rides
(docs/PERFORMANCE.md, "How the benchmarks measure")."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.utils import benchmarks


def test_window_time_is_a_float_with_flag():
    t = benchmarks.WindowTime(1.5)
    assert t == 1.5 and t + 0.5 == 2.0
    assert t.upper_bound is False
    assert t.asymmetric is False
    b = benchmarks.WindowTime(2.0, upper_bound=True)
    assert b.upper_bound is True
    a = benchmarks.WindowTime(2.0, asymmetric=True)
    assert a.asymmetric is True
    assert isinstance(b * 2, float)


def test_sync_forces_scalar_readback():
    out = benchmarks.sync({"a": jnp.arange(4.0)})
    assert isinstance(out, float) and out == 0.0


def test_slope_window_measures_per_iteration_cost():
    """A step with a known sleep: the median pairwise slope across the
    interleaved windows must recover the per-iteration cost, cancelling
    fixed overhead. A warmup sync first: pending async work left by
    earlier tests in the process must drain OUTSIDE the timed windows
    (a single base/full pair lets it deflate the slope)."""
    benchmarks.sync(jnp.zeros(()))  # warmup: flush pending device work

    def step(state):
        time.sleep(0.01)
        return state + 1, jnp.asarray(float(state))

    dt, state = benchmarks.slope_window(step, 0, iters=5, base_iters=1)
    assert isinstance(dt, benchmarks.WindowTime)
    assert not dt.upper_bound
    assert 0.03 < dt < 0.3  # ~5 * 10 ms, generous bounds for CI noise
    # state threads through every call: 1 flush + 3 rounds of
    # (1 + 3 + 6)-iteration windows; a single jitter-inversion retry
    # adds one more full set (a THIRD is not legal)
    assert state in (31, 61)


def test_slope_window_inverted_marks_upper_bound():
    """When the 'work' is pure jitter (longer windows measured FASTER),
    the fallback reports the median full window and FLAGS it — bound
    samples must be distinguishable from measurements (ADVICE r4)."""
    calls = {"n": 0}

    def step(state):
        calls["n"] += 1
        # rounds=1, iters=2, base_iters=1 -> windows of 1/2/3 iters:
        # call 1 is the untimed flush; calls 2 and 8 are the two BASE
        # windows (attempt + retry). Making only those slow drives the
        # median pairwise slope negative both times.
        time.sleep(0.05 if calls["n"] in (2, 8) else 0.001)
        return state, jnp.asarray(0.0)

    with pytest.warns(UserWarning, match="inverted twice"):
        dt, _ = benchmarks.slope_window(step, 0, iters=2, base_iters=1,
                                        rounds=1)
    assert dt.upper_bound is True
    assert dt > 0


def test_slope_window_flags_asymmetric_fixed_cost():
    """A fixed cost that attaches to SOME window lengths only (here: the
    mid-length window) deflates one segment rate and inflates the other;
    the disagreement between the implied per-iteration rates must be
    flagged — the sample is not a clean slope."""
    calls = {"n": 0}

    def step(state):
        calls["n"] += 1
        # rounds=1, iters=2, base_iters=1 -> flush(1), base(2), mid(3-4),
        # full(5-7): call 3 opens the mid window — give it a fixed extra
        extra = 0.05 if calls["n"] == 3 else 0.0
        time.sleep(0.01 + extra)
        return state + 1, jnp.asarray(0.0)

    with pytest.warns(UserWarning, match="asymmetrically"):
        dt, _ = benchmarks.slope_window(step, 0, iters=2, base_iters=1,
                                        rounds=1)
    assert dt.asymmetric is True
    assert not dt.upper_bound


def test_slope_window_sane_after_autotune_in_process(hvd):
    """Regression: running the fusion
    autotuner and then the timing primitive IN THE SAME PROCESS
    under-measured a 10 ms/iter step 4x (dt=0.0127 s for 5 iters) with
    upper_bound=False — autotune warm-up residue drained inside the next
    slope_window's single base window. The untimed flush iteration now
    pins that residue outside both windows; this test is the two-suite
    repro (test_fusion -> test_benchmarks_util) distilled into one."""
    from horovod_tpu.ops import fusion

    tree = {"a": jnp.ones((256,)), "b": jnp.ones((64, 4))}
    fusion.autotune_fusion_threshold(tree, candidates=[1 << 10, 1 << 20],
                                     trials=2, apply=False)

    def step(state):
        time.sleep(0.01)
        return state + 1, jnp.asarray(float(state))

    dt, _ = benchmarks.slope_window(step, 0, iters=5, base_iters=1)
    assert not dt.upper_bound
    assert 0.03 < dt < 0.3  # ~5 * 10 ms; a 4x under-measure would be .012


def test_repeat_throughput_propagates_window_times():
    def step(state, images, labels):
        return state, jnp.asarray(0.0)

    imgs = np.zeros((4, 1))
    runs = benchmarks.repeat_throughput(step, 0, imgs, None, warmup=0,
                                        iters=3, repeats=2)
    assert len(runs) == 2
    for rate, dt in runs:
        assert isinstance(dt, benchmarks.WindowTime)
        assert rate > 0


def test_overlap_variants_extend_with_wire_formats():
    """The --overlap/--compression combined mode (ISSUE 7 satellite):
    bare --overlap keeps the three-variant matrix; adding --compression
    appends an overlap+ZeRO-1 variant per wire format (the full
    pipeline in one run); a bogus format dies before any compile."""
    import sys

    import pytest

    sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
    import bench

    base, fmts = bench.overlap_variants(None)
    assert list(base) == ["baseline_fused_ar", "overlap_rs",
                          "overlap_rs_zero1"]
    assert fmts == []
    combined, fmts = bench.overlap_variants(["none", "int8", "fp8"])
    assert fmts == ["int8", "fp8"]
    assert combined["overlap_rs_zero1_int8"] == dict(
        sharded=True, overlap=True, wire="int8")
    assert combined["overlap_rs_zero1_fp8"]["wire"] == "fp8"
    # bare --compression (empty list) means the full format sweep
    _, fmts = bench.overlap_variants([])
    assert fmts == ["bf16", "fp8", "int8"]
    with pytest.raises(Exception):
        bench.overlap_variants(["float3"])


def test_lm_roofline_emits_bound_json(hvd, capsys, monkeypatch):
    """bench_roofline --lm (ISSUE 10 satellite): the d2048 LM MFU must
    be judged against the step's ACTUAL roofline bound. Runs the real
    compiled-step + cost_analysis machinery on a tiny transformer with
    the ceiling calibrations stubbed (a CPU box cannot sweep 8192-cubed
    bf16 matmuls in a unit test) and checks the JSON contract:
    lm_roofline_achieved_over_bound with the bound fields populated."""
    import argparse
    import json
    import sys

    sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
    import bench
    import bench_roofline

    monkeypatch.setattr(bench, "calibrate_peak_tflops",
                        lambda repeats=3: (100.0, 4096))
    monkeypatch.setattr(bench_roofline, "measure_hbm_bandwidth",
                        lambda *a, **k: 500.0)
    args = argparse.Namespace(lm_batch=2, lm_seq_len=64, lm_layers=1,
                              lm_heads=2, lm_d_model=32, lm_vocab=64,
                              num_iters=2, repeats=1)
    bench_roofline.lm_roofline(args)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["metric"] == "lm_roofline_achieved_over_bound"
    assert out["unit"] == "ratio"
    assert out["t_bound_ms"] == pytest.approx(
        max(out["t_compute_ms"], out["t_memory_ms"]))
    assert out["bound_by"] in ("compute", "memory")
    assert out["lm_d_model"] == 32 and out["tokens_per_sec"] > 0
    if out["flops_per_step"] > 0:
        assert out["value"] is not None
        assert out["mfu_bound_pct"] <= 100.0


def test_spmd_bench_mode_is_exclusive():
    """bench.py --spmd is its own comparison mode: combining it with
    --overlap/--compression/--data-plane must die at argument parsing,
    before any compile."""
    import subprocess
    import sys

    repo = __file__.rsplit("/tests/", 1)[0]
    proc = subprocess.run(
        [sys.executable, "bench.py", "--spmd", "--overlap"],
        cwd=repo, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "--spmd is its own comparison mode" in proc.stderr


def test_churn_bench_mode_is_exclusive():
    """bench.py --churn is its own comparison mode (the goodput-under-
    churn SLO gate): combining it with --overlap etc. dies at parsing."""
    import subprocess
    import sys

    repo = __file__.rsplit("/tests/", 1)[0]
    proc = subprocess.run(
        [sys.executable, "bench.py", "--churn", "--overlap"],
        cwd=repo, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "--churn is its own comparison mode" in proc.stderr


def test_churn_slo_gate_smoke():
    """ISSUE 15 acceptance: ``bench.py --churn`` runs a scripted
    preemption schedule, attributes every lost second (non-zero
    preemption lane, sum≈wall), and PASSes its goodput budget."""
    import json
    import os
    import subprocess
    import sys

    repo = __file__.rsplit("/tests/", 1)[0]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench.py", "--churn", "--churn-steps", "24",
         "--churn-preemptions", "2", "--churn-budget", "0.05",
         "--churn-drain-ms", "10"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["metric"] == "goodput_under_churn"
    assert out["slo"] == "PASS"
    assert out["preemptions"] == 2
    assert len(out["preempted_at_steps"]) == 2
    goodput = out["goodput"]
    assert goodput["phases"]["preemption"] > 0  # churn is attributed...
    assert sum(goodput["phases"].values()) == pytest.approx(
        goodput["wall_seconds"], rel=0.02)  # ...and nothing is lost
    assert out["value"] >= out["budget"]


def test_goodput_block_invariant_validation():
    """The BENCH `goodput` block contract (ISSUE 9 satellite): the phase
    sum must explain ~100% of wall time — an unattributed gap >2% (or a
    double-charged sum above wall) is a loud error, never silence."""
    from horovod_tpu.telemetry import report as report_mod

    good = {"wall_seconds": 10.0,
            "phases": {"compute": 9.5, "data_wait": 0.45}}
    assert report_mod.validate_goodput_block(good) is good

    with pytest.raises(report_mod.GoodputInvariantError,
                       match="unattributed"):
        report_mod.validate_goodput_block(
            {"wall_seconds": 10.0, "phases": {"compute": 9.0}})
    with pytest.raises(report_mod.GoodputInvariantError,
                       match="MORE than"):
        report_mod.validate_goodput_block(
            {"wall_seconds": 10.0,
             "phases": {"compute": 9.0, "data_wait": 2.0}})
    with pytest.raises(report_mod.GoodputInvariantError,
                       match="no wall time"):
        report_mod.validate_goodput_block({"wall_seconds": 0.0,
                                           "phases": {}})
    # right at the tolerance boundary: 2% unattributed passes
    report_mod.validate_goodput_block(
        {"wall_seconds": 10.0, "phases": {"compute": 9.8}})


def test_goodput_block_from_live_ledger():
    """report.goodput_block() finalizes the ledger and the emitted block
    passes its own validator (what every bench mode attaches)."""
    from horovod_tpu.telemetry import report as report_mod
    from horovod_tpu.telemetry.ledger import TimeLedger
    from horovod_tpu.telemetry.registry import MetricsRegistry

    t = [0.0]
    led = TimeLedger(clock=lambda: t[0], registry=MetricsRegistry(),
                     enabled=True)
    led.start()
    led.charge("data_wait", 0.4)
    t[0] = 1.0
    led.settle_step()
    t[0] = 1.1
    block = report_mod.goodput_block(ledger=led)
    assert block["phases"]["data_wait"] == pytest.approx(0.4)
    assert block["phases"]["compute"] == pytest.approx(0.6)
    assert block["wall_seconds"] == pytest.approx(1.1)
    assert block["unattributed_seconds"] == pytest.approx(0.0)
    assert block["steps"] == 1


def test_bench_attach_goodput_records_violation_loudly(capsys,
                                                       monkeypatch):
    """bench._attach_goodput never silently drops the invariant: a
    violating block yields a goodput_error field + a stderr shout, a
    healthy ledger yields the block, and HOROVOD_GOODPUT=0 (a
    documented opt-out) is skipped quietly — no false alarms."""
    import sys

    sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
    import bench

    from horovod_tpu.telemetry import ledger as ledger_lib
    from horovod_tpu.telemetry import report as report_mod
    from horovod_tpu.telemetry.ledger import TimeLedger
    from horovod_tpu.telemetry.registry import MetricsRegistry

    old = ledger_lib._ledger
    try:
        # healthy: real clock, one settled interval
        led = TimeLedger(registry=MetricsRegistry(), enabled=True)
        led.start()
        time.sleep(0.01)
        led.settle_step()
        ledger_lib._ledger = led
        result = {}
        bench._attach_goodput(result)
        assert "goodput" in result and "goodput_error" not in result

        # violating (an unattributed gap a phase hook failed to charge)
        def broken_block():
            raise report_mod.GoodputInvariantError("8.0% unattributed")

        monkeypatch.setattr(report_mod, "goodput_block", broken_block)
        result = {}
        bench._attach_goodput(result)
        assert "goodput" not in result
        assert "unattributed" in result["goodput_error"]
        assert "GOODPUT INVARIANT VIOLATED" in capsys.readouterr().err
        monkeypatch.undo()

        # opt-out: disabled ledger -> no block, no error, no shout
        ledger_lib._ledger = TimeLedger(registry=MetricsRegistry(),
                                        enabled=False)
        result = {}
        bench._attach_goodput(result)
        assert "goodput" not in result and "goodput_error" not in result
        assert capsys.readouterr().err == ""
    finally:
        ledger_lib._ledger = old
