"""Native core (cxx/) end-to-end tests: real localhost processes.

Reference strategy (SURVEY.md §4): collectives are tested multi-process on
localhost, never mocked. Here the harness spawns N python workers itself
(no mpirun on TPU VMs — that's the point of the TCP control plane)."""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(__file__), os.pardir)
WORKER = os.path.join(os.path.dirname(__file__), "native_worker.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_workers(scenario, size, env_extra=None, timeout=90):
    port = _free_port()
    # drop any HOROVOD_* inherited from the pytest process (an earlier
    # test may have initialized an adapter or leaked launcher vars) so a
    # scenario's topology/tuning env is exactly env_extra
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("HOROVOD_")}
    env["JAX_PLATFORMS"] = "cpu"  # workers never need a device
    env.update(env_extra or {})
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, scenario, str(r), str(size), str(port)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        for r in range(size)
    ]
    results = [p.communicate(timeout=timeout) for p in procs]
    for r, (p, (out, err)) in enumerate(zip(procs, results)):
        assert p.returncode == 0, (
            f"rank {r} failed (rc={p.returncode}):\n{out}\n{err}")
    return results


@pytest.mark.parametrize("size", [2, 4])
def test_collectives(size):
    _run_workers("collectives", size)


@pytest.mark.parametrize("size", [2, 4])
def test_adasum_matches_numpy_reference(size):
    _run_workers("adasum", size)


@pytest.mark.parametrize("size,local_size", [(4, 2), (8, 2), (8, 4)])
def test_hierarchical_adasum_matches_schedule_model(size, local_size):
    """op=adasum under an agreed 2-level topology takes the
    RS -> per-chunk Adasum -> AG -> /local_size composite
    (adasum_cuda_operations.cc role); the worker checks the values
    against the exact NumPy schedule model. (8,2) runs a 2-level
    cross tree; (8,4) runs 4 concurrent chunk trees."""
    _run_workers("hierarchical_adasum", size,
                 env_extra={"HOROVOD_LOCAL_SIZE": str(local_size)})


def test_errors_negotiated(tmp_path):
    _run_workers("errors", 2)


@pytest.mark.parametrize("size", [2, 4])
def test_cache_bitvector_cuts_control_bytes(size):
    """Steady state rides the hit-bitvector path: control-plane bytes per
    cycle drop >5x vs full negotiation on a 100-tensor workload."""
    _run_workers("cache_bytes", size, timeout=180)


def test_cache_invalidation_renegotiates():
    _run_workers("cache_invalidation", 2)


def test_autotune_converges_and_syncs(tmp_path):
    """hvdrun --autotune end-to-end: the coordinator's BO loop converges
    within its sample budget and every rank adopts identical tuned
    parameters (reference parameter_manager + SynchronizeParameters)."""
    log = tmp_path / "autotune.csv"
    results = _run_workers("autotune", 4, env_extra={
        "HOROVOD_AUTOTUNE": "1",
        "HOROVOD_AUTOTUNE_LOG": str(log),
        "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "1",
        "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "2",
        "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES": "8",
    }, timeout=180)
    import json as _json
    tuned = []
    for out, _ in results:
        line = [l for l in out.splitlines() if l.startswith("TUNED ")][0]
        tuned.append(tuple(_json.loads(line[len("TUNED "):])))
    assert len(set(tuned)) == 1, f"ranks disagree on tuned params: {tuned}"
    # --autotune-log-file wrote header + per-sample rows + converged row
    lines = log.read_text().strip().splitlines()
    assert lines[0].startswith("sample,fusion_threshold,cycle_time_ms")
    assert any(l.startswith("converged,") for l in lines)
    assert len([l for l in lines if not l.startswith(("sample", "converged"))
                ]) >= 8


def _cross_traffic(results):
    import json as _json
    local = cross = 0
    for out, _ in results:
        line = [l for l in out.splitlines() if l.startswith("DATABYTES ")][0]
        lb, cb = _json.loads(line[len("DATABYTES "):])
        local += lb
        cross += cb
    return local, cross


def test_hierarchical_cuts_cross_host_traffic():
    """Faked 2-host x 4-rank topology: the same workload run flat vs
    hierarchical must produce identical values (asserted in the worker)
    while the hierarchical schedule's cross-host bytes drop to about
    1/local_size of the flat ring's total traffic (reference
    nccl_operations.cc:150 schedule + MPIHierarchicalAllgather role)."""
    topo = {"HOROVOD_LOCAL_SIZE": "4"}
    flat = _run_workers("hierarchy", 8, env_extra=topo, timeout=180)
    hier = _run_workers("hierarchy", 8, env_extra={
        **topo,
        "HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
        "HOROVOD_HIERARCHICAL_ALLGATHER": "1",
    }, timeout=180)
    flat_local, flat_cross = _cross_traffic(flat)
    hier_local, hier_cross = _cross_traffic(hier)
    assert hier_cross < flat_cross, (
        f"hierarchical cross-host traffic not reduced: "
        f"hier={hier_cross} flat={flat_cross}")
    local_size = 4
    flat_total = flat_local + flat_cross
    assert hier_cross <= flat_total / local_size * 1.25, (
        f"cross-host bytes {hier_cross} not ~1/{local_size} of the flat "
        f"ring's total {flat_total}")


def test_autotune_categorical_dims_explored_and_synced(tmp_path):
    """With a faked 2x2 topology the BO loop searches the categorical
    hierarchical/cache dims alongside (fusion, cycle): the log must show
    both values of each categorical tried, and all ranks must agree on
    the winning combination (reference parameter_manager.h:186-220)."""
    log = tmp_path / "autotune.csv"
    results = _run_workers("autotune", 4, env_extra={
        "HOROVOD_AUTOTUNE": "1",
        "HOROVOD_AUTOTUNE_LOG": str(log),
        "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "1",
        "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "2",
        "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES": "8",
        "HOROVOD_LOCAL_SIZE": "2",
    }, timeout=180)
    import json as _json
    tuned = []
    for out, _ in results:
        line = [l for l in out.splitlines() if l.startswith("TUNED ")][0]
        tuned.append(tuple(_json.loads(line[len("TUNED "):])))
    assert len(set(tuned)) == 1, f"ranks disagree on tuned params: {tuned}"
    rows = [l.split(",") for l in log.read_text().strip().splitlines()
            if not l.startswith(("sample", "converged"))]
    hier_vals = {r[3] for r in rows}
    cache_vals = {r[4] for r in rows}
    assert hier_vals == {"0", "1"}, f"hierarchical dim not explored: {rows}"
    assert cache_vals == {"0", "1"}, f"cache dim not explored: {rows}"


def test_hierarchical_gate_agreed_not_split_on_env_drift():
    """Every rank requests hierarchical collectives but rank 0's topology
    env drifted (claims flat): the coordinator must turn the gates off
    for the whole job — a per-rank decision would deadlock mismatched
    ring schedules. The workload completing with exact values IS the
    assertion (a split decision hangs into the timeout)."""
    _run_workers("hierarchy_mismatch", 8, env_extra={
        "HOROVOD_LOCAL_SIZE": "4",
        "HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
        "HOROVOD_HIERARCHICAL_ALLGATHER": "1",
    }, timeout=120)


@pytest.mark.parametrize("size", [2, 4])
def test_zero_copy_enqueue(size):
    """Borrowed buffers move zero host-side memcpy bytes for broadcast
    and single-tensor allreduce (asserted in the worker via the core's
    copy counter)."""
    _run_workers("zerocopy", size)


def test_join_uneven_ranks():
    results = _run_workers("join", 4)
    last = {l for out, _ in results for l in out.splitlines()
            if l.startswith("JOINLAST ")}
    assert len(last) == 1, f"ranks disagree on the last-joined rank: {last}"


@pytest.mark.parametrize("size", [3, 4])
def test_join_with_cached_tensors(size):
    """Hit-path tensors survive a rank joining; new tensors negotiated
    while a rank is joined keep every cache replica in lockstep."""
    _run_workers("join_cached", size, timeout=120)


def test_join_rejects_allgather():
    _run_workers("join_allgather", 3)


def test_timeline_written(tmp_path):
    tl = str(tmp_path / "timeline.json")
    _run_workers("timeline", 2, env_extra={"HOROVOD_TIMELINE": tl})
    assert os.path.exists(tl)


def test_single_process_local():
    """size=1: everything is a local no-op (Horovod semantics)."""
    sys.path.insert(0, REPO)
    from horovod_tpu import _core as core
    core.init(rank=0, size=1)
    try:
        x = np.arange(5, dtype=np.float32)
        np.testing.assert_array_equal(core.allreduce(x, "sp.a"), x)
        np.testing.assert_array_equal(core.allgather(x, "sp.b"), x)
        np.testing.assert_array_equal(core.broadcast(x, "sp.c"), x)
        core.barrier()
    finally:
        core.shutdown()


def test_cxx_unit_tests():
    """The in-process C++ component tests (message/negotiator/cache/...)."""
    rv = subprocess.run(["make", "-C", os.path.join(REPO, "cxx"), "test"],
                        capture_output=True, text=True)
    assert rv.returncode == 0, rv.stdout + rv.stderr
    assert "ALL CXX UNIT TESTS PASSED" in rv.stdout


def test_library_is_stale_when_a_source_is_newer(tmp_path, monkeypatch):
    """horovod_tpu/lib/ and cxx/build/ are git-ignored, so a library left
    in a working tree by an older commit must not be what runs: the load
    path rebuilds when anything under cxx/ is newer than the library (and
    when there is no library). A tree without cxx/ — an installed
    package — ships its library built and is never stale."""
    from horovod_tpu import _core

    cxx = tmp_path / "cxx"
    (cxx / "src").mkdir(parents=True)
    (cxx / "include" / "hvd").mkdir(parents=True)
    lib = tmp_path / "libhvdcore.so"
    monkeypatch.setattr(_core, "_CXX_DIR", str(cxx))
    monkeypatch.setattr(_core, "_LIB_PATH", str(lib))
    for name in ("Makefile", "src/a.cc", "include/hvd/a.h"):
        (cxx / name).write_text("")
        os.utime(cxx / name, (1000, 1000))
    assert _core._stale()  # no library yet
    lib.write_text("")
    os.utime(lib, (2000, 2000))
    assert not _core._stale()
    for name in ("Makefile", "src/a.cc", "include/hvd/a.h"):
        os.utime(cxx / name, (3000, 3000))
        assert _core._stale(), name
        os.utime(cxx / name, (1000, 1000))
    monkeypatch.setattr(_core, "_CXX_DIR", str(tmp_path / "absent"))
    assert not _core._stale()
