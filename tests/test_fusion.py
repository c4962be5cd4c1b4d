"""Fusion-buffer tests (reference semantics: controller.cc:639-769
FuseResponses + fused allreduce value checks in test_tensorflow.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd_api
from horovod_tpu.ops import collective, fusion


def test_plan_buckets_groups_by_dtype():
    leaves = [np.ones((4,), np.float32), np.ones((2,), np.int32),
              np.ones((8,), np.float32)]
    buckets = fusion.plan_buckets(leaves, threshold_bytes=1 << 20)
    dtypes = sorted(str(b.dtype) for b in buckets)
    assert dtypes == ["float32", "int32"]
    f32 = next(b for b in buckets if str(b.dtype) == "float32")
    assert f32.leaf_indices == (0, 2)
    assert f32.sizes == (4, 8)


def test_plan_buckets_respects_threshold():
    leaves = [np.ones((100,), np.float32) for _ in range(10)]  # 400 B each
    buckets = fusion.plan_buckets(leaves, threshold_bytes=1000)
    assert len(buckets) == 5  # 2 leaves per 1000-B bucket
    # a single oversized leaf still gets a bucket
    big = [np.ones((1000,), np.float32)]
    assert len(fusion.plan_buckets(big, threshold_bytes=100)) == 1


def test_plan_buckets_reverse_traversal_order():
    """reverse=True packs back-to-front: backprop readiness order (the
    bucket the last layer's grads land in comes first)."""
    leaves = [np.ones((4,), np.float32), np.ones((8,), np.float32),
              np.ones((2,), np.float32)]
    buckets = fusion.plan_buckets(leaves, threshold_bytes=16, reverse=True)
    assert [b.leaf_indices for b in buckets] == [(2,), (1,), (0,)]
    # forward order for contrast
    fwd = fusion.plan_buckets(leaves, threshold_bytes=16)
    assert fwd[0].leaf_indices[0] == 0


def test_bucket_schedule_pads_to_world():
    leaves = [np.ones((5,), np.float32), np.ones((6,), np.float32)]
    sched = fusion.bucket_schedule(leaves, world=8, threshold_bytes=1 << 20,
                                   axes=("data",))
    assert len(sched.buckets) == 1
    assert sched.padded_sizes == (16,)  # 11 -> 16 (multiple of 8)
    assert sched.shard_sizes == (2,)
    assert sched.axes == ("data",)


def test_bucket_schedule_hierarchical_reorders_ici_first():
    leaves = [np.ones((8,), np.float32)]
    sched = fusion.bucket_schedule(leaves, world=8, threshold_bytes=1 << 20,
                                   axes=("dcn", "data"), hierarchical=True)
    assert sched.axes == ("data", "dcn")  # DCN stage moves 1/ici the bytes


def test_bucket_rs_ag_roundtrip_matches_fused_allreduce(hvd, n_devices):
    """reduce_scatter_bucket + all_gather_bucket + unpack == the fused
    allreduce of the same tree (the pipeline's exchange is the same
    reduction, split at the shard boundary)."""
    tree_template = [np.ones((5,), np.float32), np.ones((3, 2), np.float32)]

    def f():
        r = collective.mesh_rank().astype(jnp.float32)
        leaves = [(r + 1) * jnp.ones((5,)), (r + 2) * jnp.ones((3, 2))]
        sched = fusion.bucket_schedule(leaves, world=n_devices,
                                       threshold_bytes=1 << 20)
        out = [None, None]
        for i in range(len(sched.buckets)):
            shard = fusion.reduce_scatter_bucket(sched, i, leaves,
                                                 op=hvd_api.Average)
            flat = fusion.all_gather_bucket(sched, i, shard)
            for j, arr in fusion.unpack_bucket(sched, i, flat,
                                               leaves).items():
                out[j] = arr
        ref = fusion.fused_allreduce(list(leaves), op=hvd_api.Average)
        return out, ref

    specs = [P() for _ in tree_template]
    out, ref = jax.shard_map(f, mesh=hvd.mesh(), in_specs=(),
                             out_specs=(specs, specs), check_vma=False)()
    for o, e in zip(out, ref):
        np.testing.assert_allclose(np.asarray(o), np.asarray(e), rtol=1e-6)


def test_fused_allreduce_matches_unfused(hvd, n_devices):
    tree_shapes = {"w": (3, 4), "b": (4,), "scale": ()}

    def f():
        r = collective.mesh_rank().astype(jnp.float32)
        tree = {k: (r + 1) * jnp.ones(s) for k, s in tree_shapes.items()}
        fused = fusion.fused_allreduce(tree, op=hvd_api.Average)
        unfused = jax.tree_util.tree_map(
            lambda x: collective.allreduce(x, op=hvd_api.Average), tree)
        return fused, unfused

    specs = {k: P() for k in tree_shapes}
    fused, unfused = jax.shard_map(
        f, mesh=hvd.mesh(), in_specs=(),
        out_specs=(specs, specs), check_vma=False)()
    for k in tree_shapes:
        np.testing.assert_allclose(fused[k], unfused[k], rtol=1e-6)
        expected = np.mean(np.arange(1, n_devices + 1))
        np.testing.assert_allclose(fused[k], expected * np.ones(
            tree_shapes[k]), rtol=1e-6)


def test_fused_allreduce_mixed_dtypes(hvd, n_devices):
    def f():
        r = collective.mesh_rank()
        tree = {"f32": (r + 1).astype(jnp.float32) * jnp.ones((5,)),
                "bf16": (r + 1).astype(jnp.bfloat16) * jnp.ones(
                    (7,), jnp.bfloat16)}
        return fusion.fused_allreduce(tree, op=hvd_api.Sum)

    out = jax.shard_map(f, mesh=hvd.mesh(), in_specs=(),
                        out_specs={"f32": P(), "bf16": P()},
                        check_vma=False)()
    total = sum(range(1, n_devices + 1))
    np.testing.assert_allclose(out["f32"], total * np.ones((5,)))
    assert out["bf16"].dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out["bf16"], np.float32),
                               total * np.ones((7,)), rtol=1e-1)


def test_fused_allreduce_tiny_threshold_still_correct(hvd, n_devices):
    """Many buckets (threshold smaller than single leaves) == same values."""

    def f():
        r = collective.mesh_rank().astype(jnp.float32)
        tree = [r * jnp.ones((16,)) + i for i in range(6)]
        return fusion.fused_allreduce(tree, op=hvd_api.Average,
                                      threshold_bytes=8)

    out = jax.shard_map(f, mesh=hvd.mesh(), in_specs=(),
                        out_specs=[P()] * 6, check_vma=False)()
    mean_r = np.mean(np.arange(n_devices))
    for i in range(6):
        np.testing.assert_allclose(out[i], mean_r + i, rtol=1e-6)


def test_fused_allreduce_compressed(hvd, n_devices):
    def f():
        r = collective.mesh_rank().astype(jnp.float32)
        tree = {"a": (r + 1) * jnp.ones((4,)), "b": (r + 1) * jnp.ones((2,))}
        return fusion.fused_allreduce(tree, op=hvd_api.Average,
                                      compression=hvd_api.Compression.fp16)

    out = jax.shard_map(f, mesh=hvd.mesh(), in_specs=(),
                        out_specs={"a": P(), "b": P()}, check_vma=False)()
    expected = np.mean(np.arange(1, n_devices + 1))
    np.testing.assert_allclose(out["a"], expected, rtol=1e-2)
    assert out["a"].dtype == jnp.float32


def test_fused_allreduce_hierarchical_on_2d_mesh(hvd2d, n_devices):
    def f():
        r = collective.mesh_rank().astype(jnp.float32)
        tree = {"w": (r + 1) * jnp.ones((9,))}
        return fusion.fused_allreduce(tree, op=hvd_api.Average,
                                      hierarchical=True)

    out = jax.shard_map(f, mesh=hvd2d.mesh(), in_specs=(),
                        out_specs={"w": P()}, check_vma=False)()
    expected = np.mean(np.arange(1, n_devices + 1))
    np.testing.assert_allclose(out["w"], expected * np.ones((9,)), rtol=1e-6)


def test_hierarchical_rs_ag_pin_the_schedule_contract(hvd2d, n_devices):
    """parallel.hierarchical_reducescatter/allgather and the bucket
    schedule's reordered-axes composition (collective.reducescatter/
    allgather over ('data','dcn')) are two spellings of ONE chunk-
    ownership contract — rank mesh_rank(('data','dcn')) owns chunk r.
    Pinned here so they cannot drift apart: the ICI-first DCN-bytes
    economics in docs/PERFORMANCE.md assumes they agree."""
    from horovod_tpu.parallel import hierarchical as hier

    def f():
        r = collective.mesh_rank(("data", "dcn")).astype(jnp.float32)
        x = (r + 1.0) * (jnp.arange(n_devices * 2, dtype=jnp.float32) + 1.0)
        a = hier.hierarchical_reducescatter(x, ici_axes=("data",),
                                            dcn_axis="dcn", op="average")
        b = collective.reducescatter(x, op=hvd_api.Average,
                                     axes=("data", "dcn"))
        ga = hier.hierarchical_allgather(a, ici_axes=("data",),
                                         dcn_axis="dcn")
        gb = collective.allgather(b, axes=("data", "dcn"))
        return a, b, ga, gb

    shard_spec = P(("data", "dcn"))
    a, b, ga, gb = jax.shard_map(
        f, mesh=hvd2d.mesh(), in_specs=(),
        out_specs=(shard_spec, shard_spec, P(), P()), check_vma=False)()
    # position-dependent payload: the full reduction is mean(r+1)*(i+1),
    # so both the values AND the chunk ownership must agree
    expected = (np.mean(np.arange(1, n_devices + 1))
                * (np.arange(n_devices * 2) + 1.0))
    np.testing.assert_allclose(np.asarray(a), expected, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ga), expected, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gb), rtol=1e-6)


def test_fused_allreduce_hierarchical_adasum(hvd2d, n_devices, rng):
    """DistributedOptimizer(op=Adasum, hierarchical=True) semantics: the
    fused hierarchical branch must run the 2-level Adasum COMPOSITE
    (per-chunk Adasum across dcn), never a cross-slice psum."""
    from horovod_tpu.ops import adasum
    data_size = n_devices // 2
    vals = rng.standard_normal((n_devices, 10)).astype(np.float32)
    expected = adasum.hierarchical_adasum_np(
        vals.reshape(2, data_size, 10))

    def f():
        tree = {"g": jnp.asarray(vals)[
            collective.mesh_rank(("dcn", "data"))]}
        return fusion.fused_allreduce(tree, op=hvd_api.Adasum,
                                      axes=("dcn", "data"),
                                      hierarchical=True)

    out = jax.shard_map(f, mesh=hvd2d.mesh(), in_specs=(),
                        out_specs={"g": P()}, check_vma=False)()
    np.testing.assert_allclose(np.asarray(out["g"]), expected,
                               rtol=1e-4, atol=1e-5)


def test_fused_allreduce_hierarchical_min_falls_through(hvd2d, n_devices):
    """Min/Max have no RS->AR->AG form: with hierarchical=True they must
    fall through to the flat path and stay CORRECT (not raise, not
    silently sum)."""
    def f():
        r = collective.mesh_rank(("dcn", "data")).astype(jnp.float32)
        return fusion.fused_allreduce({"x": r + jnp.zeros((3,))},
                                      op=hvd_api.Min,
                                      axes=("dcn", "data"),
                                      hierarchical=True)

    out = jax.shard_map(f, mesh=hvd2d.mesh(), in_specs=(),
                        out_specs={"x": P()}, check_vma=False)()
    np.testing.assert_allclose(out["x"], np.zeros((3,)))


def test_fused_allreduce_empty_tree(hvd):
    assert fusion.fused_allreduce({}) == {}


def test_autotune_fusion_threshold(hvd):
    """Timed-trial bucket autotune: returns a candidate, times every
    candidate, and installs the winner as the process default — or
    abstains WITH a reason when the trials carry no rankable signal
    (unresolved upper bounds near the argmin on a loaded CI box)."""
    tree = {"a": jnp.ones((512,)), "b": jnp.ones((256,)),
            "c": jnp.ones((64, 8))}
    candidates = [1 << 10, 1 << 20]
    best, timings = fusion.autotune_fusion_threshold(
        tree, candidates=candidates, trials=2)
    assert set(timings) == set(candidates)
    assert all(t > 0 for t in timings.values())
    from horovod_tpu import basics
    if best is None:
        # abstention is only legal with a reason and an unresolved bound
        assert timings.abstain_reason
        assert any(getattr(t, "upper_bound", False)
                   for t in timings.values())
        return
    assert best in candidates
    assert timings.abstain_reason is None
    assert basics._state.config.fusion_threshold == best
    # the tuned default now drives fused_allreduce's bucket planning
    out = jax.shard_map(
        lambda t: fusion.fused_allreduce(t, op=hvd_api.Sum),
        mesh=hvd.mesh(), in_specs=(jax.tree_util.tree_map(
            lambda _: P(), tree),),
        out_specs=jax.tree_util.tree_map(lambda _: P(), tree),
        check_vma=False)(tree)
    np.testing.assert_allclose(out["a"], 8.0 * np.ones((512,)), rtol=1e-6)


def test_autotune_uses_shared_timing_primitive(hvd, monkeypatch):
    """The autotuner must time through utils.benchmarks.slope_window
    (the readback-slope protocol, the package's one timing primitive)
    and must thread a fresh salt into every trial call so that no two
    calls see identical inputs."""
    from horovod_tpu.utils import benchmarks

    calls = {"n": 0, "salts": []}
    real = benchmarks.slope_window

    def spying(step_once, state, iters, base_iters=2):
        calls["n"] += 1
        seen = []
        calls["salts"].append(seen)

        def spy_step(st):
            seen.append(float(st[1]))
            return step_once(st)

        return real(spy_step, state, iters, base_iters=base_iters)

    monkeypatch.setattr(benchmarks, "slope_window", spying)
    tree = {"a": jnp.ones((64,))}
    fusion.autotune_fusion_threshold(tree, candidates=[1 << 10, 1 << 20],
                                     trials=2, apply=False)
    # at least one slope window per candidate (inverted-window retries —
    # common for these noise-floor-sized trials — may add more)
    assert calls["n"] >= 2
    # every trial call within a window saw a distinct salt (fresh inputs,
    # no memoization)
    for seen in calls["salts"]:
        assert len(set(seen)) == len(seen)


def test_autotune_retries_inverted_windows(hvd, monkeypatch):
    """An inverted slope window is an upper BOUND, not a measurement:
    the autotuner must re-run the trial with 4x-escalated iters instead
    of ranking candidates on it, and surface both the retry count and
    the escalation count on the returned timings (bounds leak into the
    ranking when doubling creeps up too slowly)."""
    from horovod_tpu.utils import benchmarks

    seen = {"iters": []}

    def fake(step_once, state, iters, base_iters=2):
        seen["iters"].append(iters)
        # every first (trials-length) window inverts; the 4x escalation
        # clears the noise floor on its first retry
        return benchmarks.WindowTime(0.1 * iters,
                                     upper_bound=(iters == 2)), state

    monkeypatch.setattr(benchmarks, "slope_window", fake)
    tree = {"a": jnp.ones((64,))}
    best, timings = fusion.autotune_fusion_threshold(
        tree, candidates=[1 << 10, 1 << 20], trials=2, apply=False)
    assert timings.retried == 2  # both candidates hit the inversion
    # retries escalate iters x4 (bounded), one escalation per candidate
    assert seen["iters"] == [2, 8, 2, 8]
    assert timings.slope_window_escalations == 2
    # and the recorded values are normalized back to per-`trials` cost,
    # unflagged (the retry measured cleanly)
    for v in timings.values():
        assert not getattr(v, "upper_bound", False)
        assert v == pytest.approx(0.1 * 2)


def test_autotune_escalation_is_bounded_and_counted(hvd, monkeypatch):
    """A trial that NEVER resolves must stop escalating at the 16x
    bound (two 4x escalations) and keep its upper_bound flag — the
    abstention gate, not endless retrying, owns the hopeless case. A
    cleanly measured run reports zero escalations."""
    from horovod_tpu.utils import benchmarks

    seen = {"iters": []}

    def always_bounded(step_once, state, iters, base_iters=2):
        seen["iters"].append(iters)
        return benchmarks.WindowTime(0.1 * iters, upper_bound=True), state

    monkeypatch.setattr(benchmarks, "slope_window", always_bounded)
    tree = {"a": jnp.ones((64,))}
    best, timings = fusion.autotune_fusion_threshold(
        tree, candidates=[1 << 10], trials=2, apply=False)
    assert best is None  # unresolved bound at the argmin -> abstain
    assert seen["iters"] == [2, 8, 32]  # trials, x4, x16 — then stop
    assert timings.slope_window_escalations == 2

    seen["iters"].clear()

    def clean(step_once, state, iters, base_iters=2):
        seen["iters"].append(iters)
        return benchmarks.WindowTime(0.1 * iters), state

    monkeypatch.setattr(benchmarks, "slope_window", clean)
    best, timings = fusion.autotune_fusion_threshold(
        tree, candidates=[1 << 10], trials=2, apply=False)
    assert timings.slope_window_escalations == 0
    assert timings.retried == 0


def test_autotune_abstains_at_world_one():
    """With one participant over the reduction axes the fused
    collectives are no-ops: the tuner must return (None, timings) with
    a reason instead of installing a noise argmin.
    A single-device mesh is the realistic single-chip dev box."""
    from horovod_tpu.parallel import mesh as mesh_lib
    old = mesh_lib._current_mesh
    mesh_lib.set_mesh(mesh_lib.build_mesh(devices=[jax.devices()[0]]))
    try:
        tree = {"a": jnp.ones((64,))}
        best, timings = fusion.autotune_fusion_threshold(
            tree, candidates=[1 << 10, 1 << 20], trials=2)
    finally:
        mesh_lib.set_mesh(old)
    assert best is None
    assert "world size 1" in timings.abstain_reason
    assert timings == {}  # no trials were burned on a no-signal setup


def test_autotune_abstains_on_unresolved_bounds(hvd, monkeypatch):
    """A candidate whose timing is STILL an inverted-window upper bound
    after retries, and which sits within tolerance of the argmin, makes
    the ranking unsound (its true time could be anywhere at or below the
    bound): the tuner must abstain and leave the configured default
    untouched."""
    from horovod_tpu import basics
    from horovod_tpu.utils import benchmarks

    def always_bounded(step_once, state, iters, base_iters=2):
        return benchmarks.WindowTime(0.1 * iters, upper_bound=True), state

    monkeypatch.setattr(benchmarks, "slope_window", always_bounded)
    before = basics._state.config.fusion_threshold
    tree = {"a": jnp.ones((64,))}
    best, timings = fusion.autotune_fusion_threshold(
        tree, candidates=[1 << 10, 1 << 20], trials=2)
    assert best is None
    assert "upper bounds" in timings.abstain_reason
    assert all(t.upper_bound for t in timings.values())
    assert basics._state.config.fusion_threshold == before  # nothing installed


def test_no_block_until_ready_in_package():
    """The package ends every timing window one way — a host readback
    (utils.benchmarks.sync) — so NO code in it may call
    jax.block_until_ready for timing or completion: two ways to end a
    window are two protocols whose numbers cannot be compared."""
    import pathlib

    import horovod_tpu

    pkg = pathlib.Path(horovod_tpu.__file__).parent
    offenders = []
    for path in pkg.rglob("*.py"):
        text = path.read_text()
        if "block_until_ready(" in text:
            offenders.append(str(path.relative_to(pkg)))
    assert offenders == [], (
        f"block_until_ready call found in {offenders}; use "
        "utils.benchmarks.sync/slope_window instead")


def test_one_collective_per_bucket(hvd):
    """The fused path must emit exactly one all-reduce per dtype bucket
    (the whole point of fusion — reference fuses to one NCCL call per
    cycle, nccl_operations.cc:55-105)."""

    def f():
        tree = [jnp.ones((8,)) * i for i in range(10)]
        return fusion.fused_allreduce(tree, op=hvd_api.Sum)

    fn = jax.jit(jax.shard_map(f, mesh=hvd.mesh(), in_specs=(),
                               out_specs=[P()] * 10, check_vma=False))
    hlo = fn.lower().compile().as_text()
    # count all-reduce instruction DEFINITIONS (an op's result is
    # referenced by every consumer line, so a substring count scales with
    # the number of unpacked leaves, not collectives)
    import re
    defs = re.findall(r"= \S+ all-reduce(?:-start)?\(", hlo)
    assert len(defs) <= 2  # one bucket (plus possible fusion)
