"""GSPMD hot path (ISSUE 10): one logical mesh, NamedSharding-compiled
collectives. Pins the plan's spec derivation, the spmd train step's
parity with the explicit overlap+ZeRO pipeline (the dryrun 1b4 contract,
run here as the tier-1 smoke), the compiled-HLO byte accounting, the
compiled-in-place wire compression (the shard_map island for chunked
quantizers, dtype-narrowed constraints for casts — ISSUE 17), the
optimizer gate — and the tier-1 GUARD that
keeps the hot path ON the mesh: no new ``pmap(``/``shard_map(`` call
sites may appear in ``horovod_tpu/`` outside the pinned baseline
(``compat.py`` and ``parallel/gspmd.py`` excluded as the shim layers)."""

import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd_api
from horovod_tpu import compat, training
from horovod_tpu.models.simple import MLP
from horovod_tpu.parallel import gspmd
from horovod_tpu.parallel import mesh as mesh_lib

_PKG = os.path.join(os.path.dirname(__file__), os.pardir, "horovod_tpu")


# ---- tier-1 guard: the hot path stays on the mesh ---------------------

# Thin wrapper over the hvd-lint engine's HVD-MESH pass (ISSUE 12): the
# pinned call-site baseline now lives in the committed
# .hvd-lint-baseline.json (dated entries; compat.py and
# parallel/gspmd.py excluded inside the rule) and the engine's
# stale-entry ratchet replaces the hand-rolled shrink check — a removed
# pmap(/shard_map( site fails the run until the baseline is re-written
# (`hvd-lint --baseline write`), so old slack cannot quietly readmit a
# new explicit per-rank call site. Failure messages carry file:line.


def test_guard_no_new_pmap_or_shard_map_call_sites():
    from horovod_tpu.analysis import run_lint

    repo = os.path.abspath(os.path.join(_PKG, os.pardir))
    result = run_lint([_PKG], root=repo, rules={"HVD-MESH"},
                      baseline_path=os.path.join(
                          repo, ".hvd-lint-baseline.json"))
    assert not result.findings, (
        "new explicit pmap(/shard_map( call site(s) off the logical "
        "mesh — express the sharding as NamedSharding / "
        "with_sharding_constraint (parallel/gspmd.py) or justify the "
        "baseline addition in the PR (docs/ANALYSIS.md):\n"
        + "\n".join(f.format() for f in result.findings))
    assert not result.stale_baseline, (
        "HVD-MESH baseline overstates call sites — shrink it "
        "(`hvd-lint --baseline write`) so removed sites cannot "
        f"silently come back: {result.stale_baseline}")


# ---- plan derivation --------------------------------------------------

def test_derive_plan_specs(hvd):
    plan = gspmd.derive_plan()
    assert plan.data_axes == ("data",)
    assert plan.batch_spec == P(("data",))
    assert plan.world() == len(jax.devices())
    with pytest.raises(ValueError, match="model_axis"):
        gspmd.derive_plan(model_axis="nope")


def test_derive_plan_2d_mesh(hvd2d):
    plan = gspmd.derive_plan()
    assert set(plan.data_axes) == {"dcn", "data"}
    assert plan.world() == len(jax.devices())


def test_state_partition_specs_shards_zero_rows(hvd):
    from horovod_tpu.parallel import zero
    params = {"w": jnp.ones((40,)), "b": jnp.ones((8,))}
    tx = hvd_api.DistributedOptimizer(optax.adam(1e-2),
                                      sharded_update=True)
    state = training.create_train_state(MLP(features=(4,)), tx,
                                        jax.random.PRNGKey(0),
                                        jnp.ones((1, 8)))
    del params
    specs = training.state_specs(state)  # delegates to gspmd
    assert isinstance(specs.opt_state, zero.ZeroState)
    row_specs = [s for s in jax.tree_util.tree_leaves(
        specs.opt_state.inner, is_leaf=lambda x: isinstance(x, P))
        if s == P(("data",))]
    assert row_specs, "no ZeRO row leaf got the P('data') spec"
    for s in jax.tree_util.tree_leaves(
            specs.params, is_leaf=lambda x: isinstance(x, P)):
        assert s == P()


# ---- the spmd step: dryrun 1b4 parity as the tier-1 smoke -------------

def test_spmd_step_matches_explicit_overlap_zero1(hvd):
    """The 1b4 contract on the full 8-device mesh: same model/optimizer
    stepped by both hot paths on identical tiled batches -> same loss
    trajectory and params, genuinely sharded ZeRO rows, XLA-inserted
    collectives in the compiled module."""
    import __graft_entry__ as graft
    graft._dryrun_gspmd(jax.devices())


def test_spmd_wire_island_matches_exact_gspmd(hvd):
    """The 1b5 contract (ISSUE 17) as the tier-1 smoke, on a 2-device
    mesh: GSPMD+int8+EF and GSPMD+fp8+EF 8-step trajectories within
    WIRE_EPSILON of the exact fp32 GSPMD path, compression-off programs
    identical, compressed program different."""
    import __graft_entry__ as graft
    graft._dryrun_gspmd_wire(jax.devices()[:2])


def test_spmd_plain_dp_matches_explicit(hvd):
    """Non-sharded (plain DP) GSPMD: tx.update_spmd routes through the
    preserved optimizer chain, so state stays interchangeable."""
    n = len(jax.devices())
    rng = np.random.default_rng(5)
    sx = rng.standard_normal((2, 10))
    sy = rng.integers(0, 3, size=(2,))
    X = jnp.asarray(np.tile(sx, (n, 1)), jnp.float32)
    y = jnp.asarray(np.tile(sy, n), jnp.int32)
    model = MLP(features=(16, 3))

    def run(spmd):
        tx = hvd_api.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
        state = training.create_train_state(model, tx,
                                            jax.random.PRNGKey(1), X[:1])
        step = training.make_train_step(model, tx, donate=False,
                                        spmd=spmd)
        losses = []
        for _ in range(5):
            state, loss = step(state, X, y)
            losses.append(float(loss))
        return np.asarray(losses), state

    ex, ex_state = run(False)
    sp, sp_state = run(True)
    np.testing.assert_allclose(sp, ex, rtol=1e-5, atol=1e-7)
    for a, b in zip(jax.tree_util.tree_leaves(ex_state.opt_state),
                    jax.tree_util.tree_leaves(sp_state.opt_state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


def test_spmd_lm_step_matches_explicit(hvd):
    """GSPMD LM step: global-mean next-token loss over batch-sharded
    tokens tracks the explicit LM step's exact sharded loss."""
    from horovod_tpu.models.transformer import (Transformer,
                                                TransformerConfig)
    n = len(jax.devices())
    cfg = TransformerConfig(vocab_size=32, num_layers=1, num_heads=2,
                            d_model=16, d_ff=32, dtype=jnp.float32)
    model = Transformer(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 32, size=(2 * n, 16)), jnp.int32)

    def run(spmd):
        tx = hvd_api.DistributedOptimizer(optax.adam(1e-2))
        state = training.create_train_state(model, tx,
                                            jax.random.PRNGKey(2),
                                            tokens[:1])
        step = training.make_lm_train_step(model, tx, donate=False,
                                           spmd=spmd)
        losses = []
        for _ in range(4):
            state, loss = step(state, tokens)
            losses.append(float(loss))
        return np.asarray(losses)

    np.testing.assert_allclose(run(True), run(False), rtol=1e-4,
                               atol=1e-6)


def test_spmd_step_with_loader(hvd):
    """make_train_step(spmd=True, loader=...) stages batches to the
    plan's batch sharding and step(state) pulls them."""
    from horovod_tpu.data import ArraySource, PrefetchLoader
    n = len(jax.devices())
    B = 2 * n
    rng = np.random.default_rng(3)
    X = rng.standard_normal((4 * B, 6)).astype(np.float32)
    y = rng.integers(0, 3, size=(4 * B,)).astype(np.int32)
    model = MLP(features=(8, 3))
    tx = hvd_api.DistributedOptimizer(optax.sgd(0.05))
    loader = PrefetchLoader(ArraySource([X, y]), B, rank=0, world=1,
                            shuffle=False)
    try:
        step = training.make_train_step(model, tx, donate=False,
                                        spmd=True, loader=loader)
        state = training.create_train_state(model, tx,
                                            jax.random.PRNGKey(0),
                                            jnp.asarray(X[:1]))
        # the staging target is introspectable: the plan's batch
        # NamedSharding, so prefetched batches arrive matching the
        # compiled step's in_shardings
        assert isinstance(loader.placement_spec,
                          jax.sharding.NamedSharding)
        assert loader.placement_spec.spec == P(("data",))
        for _ in range(3):
            state, loss = step(state)
        assert np.isfinite(float(loss))
    finally:
        loader.close()


# ---- guards and wire routing ------------------------------------------

def test_spmd_rejects_explicit_pipeline_knobs(hvd):
    model = MLP(features=(4,))
    tx = hvd_api.DistributedOptimizer(optax.sgd(0.1))
    with pytest.raises(ValueError, match="explicit pipeline"):
        training.make_train_step(model, tx, spmd=True, accum_steps=2)
    tx_adasum = hvd_api.DistributedOptimizer(optax.sgd(0.1),
                                             op=hvd_api.Adasum)
    with pytest.raises(ValueError, match="Average"):
        training.make_train_step(model, tx_adasum, spmd=True)


def test_spmd_wire_compression_compiles_island_in_place(hvd):
    """A chunked wire (int8) under spmd=True compiles IN-PLACE as the
    shard_map island (ISSUE 17) — no fallback warning, the build stays
    the GSPMD step, it trains, and the island's quantized exchange shows
    up in the compiled byte accounting as all-to-all traffic."""
    n = len(jax.devices())
    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.normal(size=(2 * n, 6)), jnp.float32)
    y = jnp.asarray(np.arange(2 * n) % 3, jnp.int32)
    model = MLP(features=(8, 3))
    tx = hvd_api.DistributedOptimizer(optax.sgd(0.05),
                                      sharded_update=True,
                                      compression="int8")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        step = training.make_train_step(model, tx, donate=False,
                                        spmd=True)
    assert not any("falling back" in str(x.message) for x in w), (
        [str(x.message) for x in w])
    assert step.spmd  # still the GSPMD build
    state = training.create_train_state(model, tx, jax.random.PRNGKey(0),
                                        X[:1])
    losses = []
    for _ in range(3):
        state, loss = step(state, X, y)
        losses.append(float(loss))
    assert all(np.isfinite(v) for v in losses)
    if n > 1:
        # the chunked exchange is an alltoall of wire rows + scales —
        # the honest compiled bytes must include it
        assert step.compiled_collectives.get("all-to-all", {}).get(
            "calls", 0) >= 1, step.compiled_collectives


def test_spmd_cast_wire_keeps_annotation_program(hvd):
    """Cast wires (bf16) have an annotation-only form: no island, no
    fallback — the constraint path carries them and the step trains."""
    n = len(jax.devices())
    X = jnp.asarray(np.ones((2 * n, 6)), jnp.float32)
    y = jnp.asarray(np.zeros((2 * n,), np.int32))
    model = MLP(features=(8, 3))
    tx = hvd_api.DistributedOptimizer(optax.sgd(0.05),
                                      sharded_update=True,
                                      compression="bf16")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        step = training.make_train_step(model, tx, donate=False,
                                        spmd=True)
    assert not any("falling back" in str(x.message) for x in w)
    assert step.spmd
    state = training.create_train_state(model, tx, jax.random.PRNGKey(0),
                                        X[:1])
    state, loss = step(state, X, y)
    assert np.isfinite(float(loss))
    # no shard_map island on the cast path: the program stays pure
    # annotation — chunked formats are the only island tenants
    assert step.compiled_collectives.get("all-to-all") is None


def test_spmd_step_retraces_on_new_batch_shape(hvd):
    """A different batch shape (drop_last=False tail batch, an eval
    batch) must compile a second program and keep running — the jit
    wrapper would retrace transparently, and the AOT executable cache
    has to preserve that instead of crashing on a shape mismatch."""
    n = len(jax.devices())
    model = MLP(features=(8, 3))
    tx = hvd_api.DistributedOptimizer(optax.sgd(0.05))
    step = training.make_train_step(model, tx, donate=False, spmd=True)
    state = training.create_train_state(model, tx, jax.random.PRNGKey(0),
                                        jnp.ones((1, 6)))
    X1 = jnp.ones((2 * n, 6)); y1 = jnp.zeros((2 * n,), jnp.int32)
    X2 = jnp.ones((4 * n, 6)); y2 = jnp.zeros((4 * n,), jnp.int32)
    state, l1 = step(state, X1, y1)
    state, l2 = step(state, X2, y2)  # new shape: second program
    state, l3 = step(state, X1, y1)  # first program again, cached
    assert all(np.isfinite(float(v)) for v in (l1, l2, l3))


def test_spmd_step_warns_on_late_wire_install(hvd):
    """config.wire_dtype binds late on the explicit path; the GSPMD
    step bakes its (uncompressed) decision at build — installing a wire
    format AFTER building must WARN at the next step instead of
    silently running uncompressed while tx.compression claims int8."""
    from horovod_tpu import basics

    n = len(jax.devices())
    X = jnp.ones((2 * n, 6)); y = jnp.zeros((2 * n,), jnp.int32)
    model = MLP(features=(8, 3))
    tx = hvd_api.DistributedOptimizer(optax.sgd(0.05))
    step = training.make_train_step(model, tx, donate=False, spmd=True)
    state = training.create_train_state(model, tx, jax.random.PRNGKey(0),
                                        X[:1])
    state, _ = step(state, X, y)
    old = basics._state.config.wire_dtype
    basics._state.config.wire_dtype = "int8"
    try:
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            state, _ = step(state, X, y)
        drift = [str(x.message) for x in w
                 if "built uncompressed" in str(x.message)]
        assert drift
        # ISSUE 17 regression: compression now compiles in-place, so
        # the remedy is REBUILDING the step — the message must say so
        # and must not claim a fallback that no longer happens
        assert any("Rebuild the step" in m for m in drift), drift
        assert not any("fall" in m.lower() for m in drift), drift
    finally:
        basics._state.config.wire_dtype = old


def test_spmd_gate_rejects_plain_optax_optimizer(hvd):
    """The GSPMD step routes its gradient reduction through the plan the
    DistributedOptimizer carries: a bare optax transform has none, and
    the gate must say which optimizer it needs."""
    model = MLP(features=(4,))
    with pytest.raises(ValueError, match="hvd.DistributedOptimizer"):
        training.make_train_step(model, optax.sgd(0.1), spmd=True)


def test_bound_axis_names_tracks_the_trace_context(hvd):
    """compat.bound_axis_names is how collectives tell a named-axis
    trace from top level: empty outside, the mesh's axes inside
    shard_map."""
    assert compat.bound_axis_names() == ()
    seen = []

    def body(x):
        seen.append(compat.bound_axis_names())
        return x

    jax.shard_map(body, mesh=hvd.mesh(), in_specs=P("data"),
                  out_specs=P("data"))(jnp.zeros((8,)))
    assert seen == [("data",)]


# ---- compiled-HLO byte accounting -------------------------------------

def test_collective_bytes_from_hlo_parses_result_shapes():
    hlo = "\n".join([
        "%ar = f32[4,16]{1,0} all-reduce(f32[4,16]{1,0} %x), meta",
        "%ag = bf16[8,8]{1,0} all-gather(bf16[1,8]{1,0} %y), dims={0}",
        "%rs = f32[2]{0} reduce-scatter(f32[16]{0} %z), dims={0}",
        "%dot = f32[4,4]{1,0} dot(f32[4,8] %a, f32[8,4] %b)",
    ])
    got = gspmd.collective_bytes_from_hlo(hlo)
    assert got["all-reduce"] == {"calls": 1, "bytes": 4 * 16 * 4}
    assert got["all-gather"] == {"calls": 1, "bytes": 8 * 8 * 2}
    assert got["reduce-scatter"] == {"calls": 1, "bytes": 2 * 4}
    assert "dot" not in got


def test_collective_bytes_from_hlo_parses_async_start_done_pairs():
    """With the latency-hiding scheduler (the TPU configuration this
    path targets), collectives lower to -start/-done PAIRS: the -start
    must be counted once under the base op name — an async all-gather's
    tuple result counts only its OUTPUT element — and the -done must be
    skipped (counting both would double every collective)."""
    hlo = "\n".join([
        "%ars = f32[4,16]{1,0} all-reduce-start(f32[4,16]{1,0} %x)",
        "%ard = f32[4,16]{1,0} all-reduce-done(f32[4,16]{1,0} %ars)",
        "%ags = (bf16[1,8]{1,0}, bf16[8,8]{1,0}) "
        "all-gather-start(bf16[1,8]{1,0} %y), dimensions={0}",
        "%agd = bf16[8,8]{1,0} all-gather-done((bf16[1,8]{1,0}, "
        "bf16[8,8]{1,0}) %ags)",
    ])
    got = gspmd.collective_bytes_from_hlo(hlo)
    assert got["all-reduce"] == {"calls": 1, "bytes": 4 * 16 * 4}
    assert got["all-gather"] == {"calls": 1, "bytes": 8 * 8 * 2}
    assert set(got) == {"all-reduce", "all-gather"}

    # variadic async (AllReduceCombiner fuses k tensors into ONE
    # -start whose tuple is k aliased inputs + k outputs): the output
    # HALF must be counted, not just the last element
    variadic = ("%vars = (f32[64]{0}, f32[32]{0}, f32[64]{0}, "
                "f32[32]{0}) all-reduce-start(f32[64]{0} %a, "
                "f32[32]{0} %b)")
    got = gspmd.collective_bytes_from_hlo(variadic)
    assert got["all-reduce"] == {"calls": 1, "bytes": (64 + 32) * 4}

    # collective-permute-start carries trailing u32[] context handles
    # after the (operand, output) pair — they are not payload, and the
    # half-split must not land on them
    permute = ("%cps = (f32[16]{0}, f32[16]{0}, u32[], u32[]) "
               "collective-permute-start(f32[16]{0} %p), "
               "source_target_pairs={{0,1}}")
    got = gspmd.collective_bytes_from_hlo(permute)
    assert got["collective-permute"] == {"calls": 1, "bytes": 16 * 4}


def test_spmd_step_records_compiled_collectives(hvd):
    """The compiled path's byte accounting lands in the standard
    hvd_collective_* families under spmd_* op labels — once per
    compile, read off the module XLA actually produced."""
    from horovod_tpu import telemetry
    from horovod_tpu.telemetry import instruments as ti

    n = len(jax.devices())
    X = jnp.asarray(np.ones((2 * n, 6)), jnp.float32)
    y = jnp.asarray(np.zeros((2 * n,)), jnp.int32)
    model = MLP(features=(8, 3))
    tx = hvd_api.DistributedOptimizer(optax.adam(0.05),
                                      sharded_update=True)
    state = training.create_train_state(model, tx, jax.random.PRNGKey(0),
                                        X[:1])
    step = training.make_train_step(model, tx, donate=False, spmd=True)

    def spmd_bytes():
        fam = telemetry.get_registry().get(ti.COLLECTIVE_BYTES)
        s = fam.sample() if fam is not None else {}
        if not isinstance(s, dict):
            return 0.0
        return sum(v for k, v in s.items()
                   if any(str(p).startswith("spmd_") for p in k))

    before = spmd_bytes()
    state, _ = step(state, X, y)
    after = spmd_bytes()
    assert step.compiled_collectives, "no collectives parsed"
    assert after > before
    parsed = sum(t["bytes"] for t in step.compiled_collectives.values())
    assert after - before == pytest.approx(parsed)
    # once per compile, not per step
    state, _ = step(state, X, y)
    assert spmd_bytes() == after


def test_spmd_island_retrace_keeps_per_program_wire_accounting(hvd):
    """A second batch shape under the compressed island compiles a
    second program whose wire bytes are accounted ONCE for that
    program — re-running an already-compiled shape adds nothing
    (ISSUE 17: N-shape retrace keeps per-program wire accounting)."""
    from horovod_tpu import telemetry
    from horovod_tpu.telemetry import instruments as ti

    n = len(jax.devices())
    model = MLP(features=(8, 3))
    tx = hvd_api.DistributedOptimizer(optax.sgd(0.05),
                                      sharded_update=True,
                                      compression="int8")
    step = training.make_train_step(model, tx, donate=False, spmd=True)
    state = training.create_train_state(model, tx, jax.random.PRNGKey(0),
                                        jnp.ones((1, 6)))
    X1 = jnp.ones((2 * n, 6)); y1 = jnp.zeros((2 * n,), jnp.int32)
    X2 = jnp.ones((4 * n, 6)); y2 = jnp.zeros((4 * n,), jnp.int32)

    def spmd_bytes():
        fam = telemetry.get_registry().get(ti.COLLECTIVE_BYTES)
        s = fam.sample() if fam is not None else {}
        if not isinstance(s, dict):
            return 0.0
        return sum(v for k, v in s.items()
                   if any(str(p).startswith("spmd_") for p in k))

    b0 = spmd_bytes()
    state, _ = step(state, X1, y1)
    b1 = spmd_bytes()
    assert b1 > b0  # first program's island bytes recorded
    state, _ = step(state, X2, y2)
    b2 = spmd_bytes()
    assert b2 > b1  # second shape -> second program, its own bytes
    state, l3 = step(state, X1, y1)  # cached program: no new bytes
    assert spmd_bytes() == b2
    assert np.isfinite(float(l3))


def test_spmd_zero1_checkpoint_interchangeable_with_explicit(hvd):
    """ZeRO-1 optimizer state written by the explicit compressed
    pipeline restores bit-for-bit into the compiled island step and
    vice versa (ISSUE 17) — same tree structure, same leaf
    shapes/dtypes, and each path trains on from the other's state."""
    n = len(jax.devices())
    rng = np.random.default_rng(11)
    X = jnp.asarray(rng.normal(size=(2 * n, 6)), jnp.float32)
    y = jnp.asarray(np.arange(2 * n) % 3, jnp.int32)
    model = MLP(features=(8, 3))

    def build(spmd):
        tx = hvd_api.DistributedOptimizer(optax.adam(0.05),
                                          sharded_update=True,
                                          compression="int8")
        step = training.make_train_step(model, tx, donate=False,
                                        spmd=spmd)
        state = training.create_train_state(model, tx,
                                            jax.random.PRNGKey(0), X[:1])
        return step, state

    exp_step, exp_state = build(spmd=False)
    spmd_step, spmd_state = build(spmd=True)

    for _ in range(2):
        exp_state, _ = exp_step(exp_state, X, y)
        spmd_state, _ = spmd_step(spmd_state, X, y)

    # identical checkpoint payload: same treedef, same leaf shape/dtype
    e_leaves, e_def = jax.tree_util.tree_flatten(exp_state)
    s_leaves, s_def = jax.tree_util.tree_flatten(spmd_state)
    assert e_def == s_def
    for e, s in zip(e_leaves, s_leaves):
        assert e.shape == s.shape and e.dtype == s.dtype

    # "save" on one path, "restore" on the other, keep training
    host = [np.asarray(jax.device_get(v)) for v in e_leaves]
    restored = jax.tree_util.tree_unflatten(
        s_def, [jnp.asarray(v) for v in host])
    restored, loss_s = spmd_step(restored, X, y)
    assert np.isfinite(float(loss_s))

    host_b = [np.asarray(jax.device_get(v)) for v in s_leaves]
    restored_b = jax.tree_util.tree_unflatten(
        e_def, [jnp.asarray(v) for v in host_b])
    restored_b, loss_e = exp_step(restored_b, X, y)
    assert np.isfinite(float(loss_e))


def test_spmd_state_place_roundtrip(hvd):
    """place_state puts ZeRO rows on their NamedShardings; re-placing
    is a no-op (stable input shardings — no recompiles)."""
    model = MLP(features=(8, 3))
    tx = hvd_api.DistributedOptimizer(optax.adam(0.05),
                                      sharded_update=True)
    state = training.create_train_state(model, tx, jax.random.PRNGKey(0),
                                        jnp.ones((1, 6)))
    plan = gspmd.derive_plan()
    placed = gspmd.place_state(plan, state)
    row = placed.opt_state.inner[0].mu["b0"]
    assert {s.data.shape[0] for s in row.addressable_shards} == {1}
    again = gspmd.place_state(plan, placed)
    assert again.opt_state.inner[0].mu["b0"].sharding == row.sharding
