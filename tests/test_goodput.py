"""Goodput-ledger tests: exclusive-phase accounting on a fake clock, the
/healthz 503 contract through an elastic reset, the 2-rank injected-
stall attribution acceptance run (data_wait + ckpt_stall within 20%,
``hvd-doctor perf`` names the dominant sink), byte-identical compiled
programs with the ledger on/off, and the report/dump round trip."""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from horovod_tpu.telemetry import ledger as ledger_lib
from horovod_tpu.telemetry import report as report_mod
from horovod_tpu.telemetry.ledger import PHASES, TimeLedger
from horovod_tpu.telemetry.registry import MetricsRegistry


def fake_ledger(**kw):
    t = [0.0]
    led = TimeLedger(clock=lambda: t[0], registry=MetricsRegistry(),
                     enabled=True, **kw)
    return led, t


# ---------------------------------------------------------------------------
# Ledger unit tests (fake clock)
# ---------------------------------------------------------------------------


def test_step_settle_books_residual_as_compute():
    led, t = fake_ledger()
    led.start()
    t[0] = 1.0
    led.charge("data_wait", 0.3)
    t[0] = 2.0
    led.settle_step()
    snap = led.snapshot()
    assert snap["phases"]["data_wait"] == pytest.approx(0.3)
    assert snap["phases"]["compute"] == pytest.approx(1.7)
    assert snap["wall_seconds"] == pytest.approx(2.0)
    assert snap["unattributed_seconds"] == pytest.approx(0.0)
    assert snap["goodput_ratio"] == pytest.approx(1.7 / 2.0)
    assert snap["steps"] == 1


def test_charges_clipped_to_the_interval():
    """Overlapping measurements cannot manufacture time: pending charges
    larger than the interval scale down proportionally so the phase sum
    still explains the interval exactly once."""
    led, t = fake_ledger()
    led.start()
    led.charge("data_wait", 3.0)
    led.charge("ckpt_stall", 1.0)
    t[0] = 1.0
    led.settle_step()
    snap = led.snapshot()
    assert snap["phases"]["data_wait"] == pytest.approx(0.75)
    assert snap["phases"]["ckpt_stall"] == pytest.approx(0.25)
    assert snap["phases"]["compute"] == pytest.approx(0.0)
    assert sum(snap["phases"].values()) == pytest.approx(1.0)


def test_idle_settle_splits_stall_vs_overhead():
    led, t = fake_ledger()
    led.start()
    t[0] = 0.1  # below the idle threshold -> bookkeeping overhead
    led.settle_idle()
    t[0] = 3.0  # a real unexplained gap -> stall_idle
    led.settle_idle()
    snap = led.snapshot()
    assert snap["phases"]["overhead"] == pytest.approx(0.1)
    assert snap["phases"]["stall_idle"] == pytest.approx(2.9)
    assert snap["phases"]["compute"] == 0.0


def test_phase_bracket_books_elapsed_minus_inner_charges():
    """A recovery bracket charges its span, but sub-stalls measured
    inside it (a ckpt flush during elastic reset) keep their own phase —
    phases stay exclusive, nothing is double-booked."""
    led, t = fake_ledger()
    led.start()
    with led.phase("re-rendezvous", charge="rendezvous_recovery"):
        t[0] = 2.0
        led.charge("ckpt_stall", 0.5)
        t[0] = 3.0
    led.settle_idle()
    snap = led.snapshot()
    assert snap["phases"]["rendezvous_recovery"] == pytest.approx(2.5)
    assert snap["phases"]["ckpt_stall"] == pytest.approx(0.5)
    assert sum(snap["phases"].values()) == pytest.approx(3.0)


def test_preemption_lane_attributes_eviction_time():
    """ISSUE 15: the ledger has a first-class ``preemption`` lane — the
    eviction handler's announce + grace-commit bracket lands there, so
    churn seconds are attributed, never 'unattributed'."""
    assert "preemption" in PHASES
    led, t = fake_ledger()
    led.start()
    t[0] = 1.0
    with led.phase("preemption"):
        t[0] = 1.4  # announce + bounded force-commit
    led.finalize()
    snap = led.snapshot()
    assert snap["phases"]["preemption"] == pytest.approx(0.4)
    assert snap["unattributed_seconds"] == pytest.approx(0.0)
    assert sum(snap["phases"].values()) == \
        pytest.approx(snap["wall_seconds"])
    block = report_mod.goodput_block(ledger=led)
    assert block["phases"]["preemption"] == pytest.approx(0.4)


def test_settle_mid_bracket_accounts_open_span():
    """A scrape-time settle while a rank is parked in recovery books the
    elapsed bracket time instead of leaving it unattributed."""
    led, t = fake_ledger()
    led.start()
    ctx = led.phase("ckpt_restore", charge="rendezvous_recovery")
    ctx.__enter__()
    t[0] = 4.0
    led.settle_idle()
    snap = led.snapshot()
    assert snap["phases"]["rendezvous_recovery"] == pytest.approx(4.0)
    t[0] = 5.0
    ctx.__exit__(None, None, None)
    led.settle_idle()
    assert led.snapshot()["phases"]["rendezvous_recovery"] == \
        pytest.approx(5.0)


def test_settle_mid_nested_brackets_counts_each_second_once():
    """Regression (review finding): a settle firing while NESTED
    brackets are open (re-rendezvous wrapping ckpt_restore — the real
    elastic shape) must book the overlapped span once, and the
    post-settle close path must not re-book or under-book it. Parent
    open t=0, child t=1, settle t=3, child closes t=4, parent t=5 ->
    exactly 5.0s of rendezvous_recovery, nothing else."""
    led, t = fake_ledger()
    led.start()
    parent = led.phase("re-rendezvous", charge="rendezvous_recovery")
    parent.__enter__()
    t[0] = 1.0
    child = led.phase("ckpt_restore", charge="rendezvous_recovery")
    child.__enter__()
    t[0] = 3.0
    # the live view mid-nesting already counts the overlap once
    assert led.snapshot()["phases"]["rendezvous_recovery"] == \
        pytest.approx(3.0)
    led.settle_idle()
    t[0] = 4.0
    child.__exit__(None, None, None)
    t[0] = 5.0
    parent.__exit__(None, None, None)
    snap = led.finalize()
    assert snap["phases"]["rendezvous_recovery"] == pytest.approx(5.0)
    assert snap["phases"]["stall_idle"] == 0.0
    assert sum(snap["phases"].values()) == pytest.approx(5.0)


def test_active_health_label_tracks_bracket_stack():
    led, _t = fake_ledger()
    assert led.active_health_label() is None
    with led.phase("re-rendezvous", charge="rendezvous_recovery"):
        assert led.active_health_label() == "re-rendezvous"
        with led.phase("ckpt_restore", charge="rendezvous_recovery"):
            assert led.active_health_label() == "ckpt_restore"
        assert led.active_health_label() == "re-rendezvous"
    assert led.active_health_label() is None


def test_disabled_ledger_is_inert(monkeypatch):
    led = TimeLedger(registry=MetricsRegistry(), enabled=False)
    led.start()
    led.charge("data_wait", 1.0)
    led.settle_step()
    assert not led.started
    snap = led.snapshot()
    assert snap["wall_seconds"] == 0.0
    assert all(v == 0.0 for v in snap["phases"].values())
    monkeypatch.setenv("HOROVOD_GOODPUT", "0")
    assert not ledger_lib.enabled()
    monkeypatch.setenv("HOROVOD_GOODPUT", "1")
    assert ledger_lib.enabled()


def test_health_brackets_survive_goodput_opt_out():
    """Regression (review finding): HOROVOD_GOODPUT=0 opts out of the
    TIME ACCOUNTING only — the /healthz 503-during-transition contract
    rides the same brackets and must keep working, with nothing
    charged."""
    led = TimeLedger(registry=MetricsRegistry(), enabled=False)
    with led.phase("re-rendezvous", charge="rendezvous_recovery"):
        assert led.active_health_label() == "re-rendezvous"
    assert led.active_health_label() is None
    snap = led.snapshot()
    assert all(v == 0.0 for v in snap["phases"].values())
    assert not led.started


def test_load_dumps_sums_elastic_lives(tmp_path):
    """Regression (review finding): a relaunched elastic worker writes
    one dump per LIFE (per-epoch dump dirs); the report must sum the
    disjoint windows, not keep the newest — dropping the pre-kill life
    hides exactly the recovery cost the report exists to expose."""
    (tmp_path / "epoch-1").mkdir()
    (tmp_path / "epoch-2").mkdir()
    _synth_dump(tmp_path / "epoch-1", 0, {"data_wait": 2.0}, steps=3)
    _synth_dump(tmp_path / "epoch-2", 0,
                {"rendezvous_recovery": 1.0}, steps=4)
    dumps, skipped = report_mod.load_dumps(str(tmp_path))
    assert not skipped and list(dumps) == [0]
    d = dumps[0]
    assert d["lives"] == 2
    assert d["phases"]["data_wait"] == pytest.approx(2.0)
    assert d["phases"]["rendezvous_recovery"] == pytest.approx(1.0)
    assert d["phases"]["compute"] == pytest.approx(2.0)  # 1.0 per life
    assert d["wall_seconds"] == pytest.approx(3.0 + 2.0)
    assert d["steps"] == 7
    report = report_mod.aggregate(dumps)
    assert report["fleet"]["wall_seconds"] == pytest.approx(5.0)
    assert report["fleet"]["dominant_sink"] == "data_wait"


def test_ledger_mirrors_into_registry():
    reg = MetricsRegistry()
    t = [0.0]
    led = TimeLedger(clock=lambda: t[0], registry=reg, enabled=True)
    led.start()
    led.charge("data_wait", 0.25)
    t[0] = 1.0
    led.settle_step()
    from horovod_tpu.telemetry import instruments as ti
    fam = reg.get(ti.TIME_SECONDS)
    sample = fam.sample()
    assert sample[("data_wait",)] == pytest.approx(0.25)
    assert sample[("compute",)] == pytest.approx(0.75)
    ratio = reg.get(ti.GOODPUT_RATIO)
    assert ratio.value == pytest.approx(0.75)


def test_dominant_sink():
    led, t = fake_ledger()
    led.start()
    led.charge("data_wait", 0.6)
    led.charge("ckpt_stall", 0.2)
    t[0] = 2.0
    led.settle_step()
    phase, secs = led.dominant_sink()
    assert phase == "data_wait" and secs == pytest.approx(0.6)


# ---------------------------------------------------------------------------
# Dump -> report -> hvd-doctor perf round trip (fake ledgers)
# ---------------------------------------------------------------------------


def _synth_dump(tmp_path, rank, phases, steps=4):
    led, t = fake_ledger()
    led.start()
    for p, s in phases.items():
        led.charge(p, s)
    t[0] = sum(phases.values()) + 1.0  # +1.0 of compute residual
    led.settle_step()
    led._steps_settled = steps
    path = led.write_dump(str(tmp_path), rank)
    assert path and path.endswith(f"goodput.rank{rank}.json")
    return path


def test_report_aggregates_and_names_dominant_sink(tmp_path, capsys):
    _synth_dump(tmp_path, 0, {"data_wait": 3.0, "ckpt_stall": 0.5})
    _synth_dump(tmp_path, 1, {"data_wait": 2.0, "compile": 1.0})
    dumps, skipped = report_mod.load_dumps(str(tmp_path))
    assert sorted(dumps) == [0, 1] and not skipped
    report = report_mod.aggregate(dumps)
    fleet = report["fleet"]
    assert fleet["dominant_sink"] == "data_wait"
    assert fleet["phases"]["data_wait"] == pytest.approx(5.0)
    assert fleet["phases"]["compute"] == pytest.approx(2.0)
    text = report_mod.format_report(report)
    assert "DOMINANT TIME SINK (fleet): data_wait" in text
    assert "rank 0" in text and "rank 1" in text
    # the hvd-doctor perf mode prints the same report
    from horovod_tpu.diag.doctor import doctor_cli
    assert doctor_cli(["perf", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "DOMINANT TIME SINK (fleet): data_wait" in out


def test_report_crosscheck_against_merged_trace(tmp_path):
    _synth_dump(tmp_path, 0, {"data_wait": 1.0})  # wall = 2.0 s
    # rank 0's trace spans 2.0 s (matches) in trace microseconds
    trace = [{"name": "a", "ph": "i", "ts": 0, "pid": 0},
             {"name": "b", "ph": "i", "ts": 2_000_000, "pid": 0}]
    tpath = tmp_path / "merged.json"
    tpath.write_text(json.dumps(trace))
    dumps, _ = report_mod.load_dumps(str(tmp_path))
    report = report_mod.aggregate(dumps)
    check = report_mod.crosscheck_trace(report, str(tpath))
    assert check["ranks"][0]["ok"] and not check["mismatched"]
    # a wildly shorter trace span is flagged
    tpath.write_text(json.dumps(trace[:1] + [
        {"name": "b", "ph": "i", "ts": 100_000, "pid": 0}]))
    check = report_mod.crosscheck_trace(report, str(tpath))
    assert check["mismatched"] == [0]
    assert "TRACE CROSS-CHECK" in report_mod.format_report(report)


def test_hvdrun_goodput_report_flag(tmp_path):
    from horovod_tpu.run import run as run_mod
    _synth_dump(tmp_path, 0, {"data_wait": 1.0})
    assert run_mod.main(["--goodput-report", str(tmp_path)]) == 0
    # no dumps -> the report says so and exits 2
    empty = tmp_path / "empty"
    empty.mkdir()
    assert run_mod.main(["--goodput-report", str(empty)]) == 2


# ---------------------------------------------------------------------------
# /healthz 503 during an elastic transition
# ---------------------------------------------------------------------------


def _get_health(port):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as resp:
            return resp.status, json.loads(resp.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


def test_healthz_503_through_elastic_reset(monkeypatch, tmp_path):
    """The satellite contract: during a re-rendezvous (elastic reset)
    the rank's /healthz flips to 503 with the phase in the body, then
    back to 200 once the rank is serving again. Driven through a REAL
    elastic retry (@hvd.elastic.run) with the real services health_fn."""
    import horovod_tpu as hvd_mod
    from horovod_tpu import basics, elastic
    from horovod_tpu.elastic.exceptions import WorkerFailureError

    monkeypatch.setenv("HOROVOD_METRICS_PORT", "0")
    hvd_mod.shutdown()
    hvd_mod.init()
    try:
        port = basics._state.metrics_server.port
        status, body = _get_health(port)
        assert status == 200 and body["status"] == "ok"
        assert "phase" not in body

        probes = []

        def probe_during_reset():
            probes.append(_get_health(port))

        state = elastic.ObjectState(value=1)
        state.register_reset_callbacks([probe_during_reset])
        calls = [0]

        @elastic.run(retryable=(WorkerFailureError,))
        def train(st):
            calls[0] += 1
            if calls[0] == 1:
                raise WorkerFailureError("injected peer failure")
            return st.value

        assert train(state) == 1
        # the probe ran INSIDE state.on_reset -> saw the 503 + phase
        assert probes, "reset callback never ran"
        status, body = probes[0]
        assert status == 503
        assert body["status"] == "recovering"
        assert body["phase"] == "re-rendezvous"
        # recovered: healthy again
        status, body = _get_health(port)
        assert status == 200 and body["status"] == "ok"
        # and the recovery time landed in the ledger
        snap = hvd_mod.telemetry.get_ledger().snapshot()
        assert snap["phases"]["rendezvous_recovery"] > 0
    finally:
        hvd_mod.shutdown()


# ---------------------------------------------------------------------------
# THE acceptance run: 2 ranks, injected data stall + forced blocking
# checkpoint -> the end-of-run report attributes both within 20% and
# hvd-doctor perf names the dominant sink.
# ---------------------------------------------------------------------------

DATA_DELAY_S = 0.10
# enough steps that the data stall outweighs the compile phase even when
# the other xdist workers have the cores (0.1 s alone, 0.4 s under load)
N_STEPS = 10
CKPT_SLEEP_S = 0.12
N_SAVES = 2


def _attribution_run(monkeypatch, tmp_path, rank, size, dump_dir):
    """One rank's run. Returns the stalls the training thread saw on the
    test's own clock: ``{"data_wait", "ckpt_stall"}`` in seconds. They
    are NOT the nominal injections: the prefetch thread produces batches
    while the first step traces and compiles, so part of the data delay
    is hidden (one batch on an idle machine, three under load), and a
    blocking save takes its sleeps plus the scheduler's wake-ups."""
    import jax
    import optax

    import horovod_tpu as hvd_mod
    from horovod_tpu import training
    from horovod_tpu.ckpt import AsyncCheckpointer
    from horovod_tpu.ckpt import sharded as sharded_lib
    from horovod_tpu.data import ArraySource, PrefetchLoader
    from horovod_tpu.models.simple import MLP

    monkeypatch.setenv("HOROVOD_RANK", str(rank))
    monkeypatch.setenv("HOROVOD_SIZE", str(size))
    monkeypatch.setenv("HOROVOD_FLIGHTREC_DIR", dump_dir)
    hvd_mod.shutdown()
    hvd_mod.init()
    try:
        batch = 8
        n = size * batch * N_STEPS
        rng = np.random.default_rng(0)
        xs = rng.standard_normal((n, 4)).astype(np.float32)
        ys = rng.integers(0, 3, n).astype(np.int32)

        model = MLP(features=(8, 3))
        tx = hvd_mod.DistributedOptimizer(optax.sgd(0.01))
        state = training.create_train_state(
            model, tx, jax.random.PRNGKey(0), xs[:1])

        seen = {"data_wait": 0.0, "ckpt_stall": 0.0}

        class TimedLoader(PrefetchLoader):
            def __next__(self):
                t0 = time.perf_counter()
                try:
                    return super().__next__()
                finally:
                    seen["data_wait"] += time.perf_counter() - t0

        loader = TimedLoader(
            ArraySource([xs, ys], delay_s=DATA_DELAY_S), batch,
            rank=rank, world=size, seed=0, shuffle=False, epochs=None)
        step = training.make_train_step(model, tx, loader=loader,
                                        donate=False)
        for _ in range(N_STEPS):
            state, _loss = step(state)
        loader.close()

        # the forced blocking checkpoint: every shard write sleeps —
        # the training thread sits in save(block=True)'s flush
        real_write = sharded_lib.write_shard

        def slow_write(directory, s, payload):
            time.sleep(CKPT_SLEEP_S)
            return real_write(directory, s, payload)

        monkeypatch.setattr(sharded_lib, "write_shard", slow_write)
        ck = AsyncCheckpointer(str(tmp_path / f"ckpt-r{rank}"), rank=0,
                               world=1)
        tree = {"w": np.arange(64, dtype=np.float32)}
        for s in range(1, N_SAVES + 1):
            t0 = time.perf_counter()
            ck.save(s, tree, block=True)
            seen["ckpt_stall"] += time.perf_counter() - t0
        ck.close()
        monkeypatch.setattr(sharded_lib, "write_shard", real_write)
    finally:
        hvd_mod.shutdown()  # writes goodput.rank<rank>.json to dump_dir
    return seen


def test_two_rank_injected_stall_attribution(monkeypatch, tmp_path,
                                             capsys):
    import optax

    import horovod_tpu as hvd_mod
    from horovod_tpu.diag.doctor import doctor_cli

    # warm the compile caches with the identical step shape so the
    # measured runs' compile phase stays small relative to the injected
    # stalls (the persistent XLA cache in conftest makes this stick)
    warm_dir = tmp_path / "warm"
    warm_dir.mkdir()
    _attribution_run(monkeypatch, tmp_path, 0, 2, str(warm_dir))

    # The timing bounds (the ledger within ±20% of the stall each rank
    # saw on the test's own clock, <2% unattributed) can still flake
    # under CPU contention; retry the measured run up to 3× with fresh
    # dirs — the structural asserts (both dumps present, self-describing
    # build_info, doctor exits 0) hold unconditionally on every attempt,
    # only the timing bounds may send us around again (same pattern as
    # test_ckpt.py's async-save stall bound).
    timing_failures = []
    for attempt in range(3):
        base = tmp_path / f"try{attempt}"
        base.mkdir()
        dump_dir = base / "dumps"
        dump_dir.mkdir()
        seen = {rank: _attribution_run(monkeypatch, base, rank, 2,
                                       str(dump_dir))
                for rank in (0, 1)}
        for rank in (0, 1):
            # the injections are real stalls, whatever the prefetch hid
            assert seen[rank]["data_wait"] > 0.4 * N_STEPS * DATA_DELAY_S
            assert seen[rank]["ckpt_stall"] >= N_SAVES * CKPT_SLEEP_S

        dumps, skipped = report_mod.load_dumps(str(dump_dir))
        assert sorted(dumps) == [0, 1], \
            f"missing dumps (skipped={skipped})"
        report = report_mod.aggregate(dumps)
        assert doctor_cli(["perf", str(dump_dir)]) == 0
        out = capsys.readouterr().out
        # dumps are self-describing (satellite: hvd_build_info)
        bi = report["ranks"][0]["build_info"]
        assert bi and set(bi) == {"version", "jax", "backend", "world"}
        assert bi["world"] == "2"

        try:
            for rank in (0, 1):
                phases = report["ranks"][rank]["phases"]
                for phase in ("data_wait", "ckpt_stall"):
                    assert phases[phase] == pytest.approx(
                        seen[rank][phase], rel=0.20), \
                        f"rank {rank} {phase} {phases[phase]:.3f}s vs " \
                        f"{seen[rank][phase]:.3f}s on the test's clock"
                # every second explained: the dump was written after a
                # final settle, so the unattributed tail is ~nothing
                assert report["ranks"][rank]["unattributed_seconds"] < \
                    0.02 * report["ranks"][rank]["wall_seconds"] + 1e-6
            # the dominant sink is the injected data stall, fleet-wide
            # and on both ranks — and hvd-doctor perf says so
            assert report["fleet"]["dominant_sink"] == "data_wait"
            for rank in (0, 1):
                assert report["ranks"][rank]["dominant_sink"] == \
                    "data_wait"
            assert "DOMINANT TIME SINK (fleet): data_wait" in out
            return  # timing bounds held
        except AssertionError as e:
            timing_failures.append(f"attempt {attempt}: {e}")

    pytest.fail("timing attribution out of bounds on 3 attempts:\n"
                + "\n".join(timing_failures))


# ---------------------------------------------------------------------------
# Byte-identical compiled programs with the ledger on/off
# ---------------------------------------------------------------------------


def test_compiled_step_byte_identical_ledger_on_off(hvd, monkeypatch):
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd_api
    from horovod_tpu import training
    from horovod_tpu.models.simple import MLP

    def lower_text():
        model = MLP(features=(8, 2))
        tx = hvd_api.DistributedOptimizer(optax.sgd(0.1))
        state = training.create_train_state(
            model, tx, jax.random.PRNGKey(0), jnp.zeros((1, 4)))
        step = training.make_train_step(model, tx, donate=False,
                                        telemetry=False)
        return step.lower(state, jnp.zeros((8, 4), jnp.float32),
                          jnp.zeros((8,), jnp.int32)).as_text()

    monkeypatch.setenv("HOROVOD_GOODPUT", "0")
    ledger_lib.reset_run()
    off = lower_text()
    monkeypatch.setenv("HOROVOD_GOODPUT", "1")
    led = ledger_lib.reset_run()
    on = lower_text()
    assert on == off
    assert led.enabled  # the on-build really ran with the ledger live


# ---------------------------------------------------------------------------
# Overhead: the per-step ledger work stays under the 2% budget (slow).
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_ledger_overhead_under_2pct(hvd):
    """The per-step ledger cost — one charge + one settle_step — timed
    in isolation against a real ~10ms compiled step, same protocol as
    the telemetry-instrumentation bound."""
    import jax
    import optax

    import horovod_tpu as hvd_api
    from horovod_tpu import training
    from horovod_tpu.models.simple import MLP

    model = MLP(features=(1024, 1024, 10))
    tx = hvd_api.DistributedOptimizer(optax.sgd(0.01))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((256, 512)).astype(np.float32)
    y = rng.integers(0, 10, 256).astype(np.int32)
    state = training.create_train_state(model, tx, jax.random.PRNGKey(0),
                                        x[:1])
    step = training.make_train_step(model, tx, donate=False,
                                    telemetry=False)

    def run(n):
        s = state
        t0 = time.perf_counter()
        for _ in range(n):
            s, loss = step(s, x, y)
        jax.block_until_ready(loss)
        return time.perf_counter() - t0

    run(3)
    iters = 30
    step_s = min(run(iters) for _ in range(3)) / iters

    led = TimeLedger(registry=MetricsRegistry(), enabled=True)
    led.start()
    reps = 2000
    t0 = time.perf_counter()
    for _ in range(reps):
        led.charge("data_wait", 1e-6)
        led.settle_step()
    ledger_s = (time.perf_counter() - t0) / reps
    overhead = ledger_s / step_s
    assert overhead < 0.02, \
        f"ledger overhead {overhead:.2%} >= 2% " \
        f"(settle {ledger_s * 1e6:.1f} us vs step {step_s * 1e3:.2f} ms)"


# ---------------------------------------------------------------------------
# Fleet aggregation: heartbeats -> cluster_view goodput
# ---------------------------------------------------------------------------


def test_cluster_view_aggregates_fleet_goodput():
    from horovod_tpu.elastic.discovery import FixedHosts
    from horovod_tpu.elastic.driver import ElasticDriver
    from horovod_tpu.telemetry import get_registry, instruments as ti

    driver = ElasticDriver(FixedHosts({"hostA": 2}), min_np=2)
    beats = {0: {"step": 5, "time": 1.0,
                 "metrics": {"goodput": {"compute": 8.0,
                                         "data_wait": 1.0}}},
             1: {"step": 5, "time": 1.0,
                 "metrics": {"goodput": {"compute": 6.0,
                                         "ckpt_stall": 1.0}}}}
    driver.worker_progress = lambda: beats
    view = driver.cluster_view()
    gp = view["goodput"]
    assert gp["phases"]["compute"] == pytest.approx(14.0)
    assert gp["phases"]["data_wait"] == pytest.approx(1.0)
    assert gp["ratio"] == pytest.approx(14.0 / 16.0)
    assert get_registry().get(ti.GOODPUT_RATIO).value == \
        pytest.approx(14.0 / 16.0)
    driver.stop()


def test_kv_snapshot_carries_goodput_phases():
    from horovod_tpu.telemetry import instruments as ti

    reg = MetricsRegistry()
    t = [0.0]
    led = TimeLedger(clock=lambda: t[0], registry=reg, enabled=True)
    led.start()
    led.charge("data_wait", 0.5)
    t[0] = 2.0
    led.settle_step()
    snap = ti.kv_snapshot(reg)
    assert snap["goodput"]["data_wait"] == pytest.approx(0.5)
    assert snap["goodput"]["compute"] == pytest.approx(1.5)
    assert len(json.dumps(snap)) < 500  # still heartbeat-compact
