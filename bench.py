"""Headline benchmark: synthetic ResNet img/sec through the full framework
hot path (DistributedOptimizer -> fused allreduce -> optimizer update,
compiled over the global mesh).

The TPU analogue of the reference's synthetic benchmarks
(``/root/reference/examples/pytorch_synthetic_benchmark.py``: timed batches
after warmup, img/sec) and of ``tf_cnn_benchmarks`` as used for the
published numbers (``docs/benchmarks.rst:16-42``).

Baseline for ``vs_baseline``: the reference's documented sample output —
ResNet-101, batch 64/GPU, 16 Pascal GPUs: "total images/sec: 1656.82"
(``docs/benchmarks.rst:28-42``), i.e. **103.55 img/s per chip**. We run the
same workload (ResNet-101, synthetic data) per TPU chip.

Per-chip batch defaults to 256: the reference protocol is "the batch that
keeps the accelerator busy" (64 filled a 2017 P100); ``--batch-size 64``
reproduces the literal reference configuration.

MEASUREMENT PROTOCOL: all windows are ended by a forced host READBACK
and reported as the difference of a short and a long window
(``utils/benchmarks.repeat_throughput``), so fixed dispatch and readback
costs cancel. Method notes: docs/PERFORMANCE.md, "How the benchmarks
measure".

Prints ONE JSON line with metric/value/unit/vs_baseline plus achieved
TFLOP/s, the empirically calibrated peak (``--calibrate`` runs only the
calibration), MFU against that peak, and LM tokens/sec with the flash
kernel on/off. ``--repeats`` (default 5) reports the MEDIAN window with
min/max spread. Every line names the platform, ``device_kind`` and
device count it was taken on. The headline and ``--calibrate`` modes
measure a chip: they refuse any other platform, a ``device_kind`` that
is not in ``PEAK_TFLOPS_BF16`` is an error, and a phase that throws
ends the run with a non-zero exit. The whole benchmark runs in this
one process, which therefore holds the chip.
"""

import argparse
import json
import statistics
import sys

import jax
import optax

# reference docs/benchmarks.rst:28-42 — 1656.82 img/s over 16 Pascal GPUs
BASELINE_IMG_PER_SEC_PER_CHIP = 1656.82 / 16

# Published per-chip bf16 peaks in TFLOP/s, keyed by the exact
# ``device_kind`` jax reports (Google Cloud TPU documentation, system
# architecture pages of each generation). A kind that is not here is an
# error, never a guess.
PEAK_TFLOPS_BF16 = {"TPU v5 lite": 197.0, "TPU v5e": 197.0,
                    "TPU v5p": 459.0, "TPU v4": 275.0,
                    "TPU v4 lite": 138.0, "TPU v4i": 138.0,
                    "TPU v6 lite": 918.0, "TPU v6e": 918.0}


def emit(result):
    """Print one result line, naming the device it was taken on."""
    from horovod_tpu.utils.benchmarks import device_fields
    print(json.dumps({**result, **device_fields()}))


def require_chip(what):
    """The modes that publish device metrics fail where there is no
    chip; they never fall back to the CPU. Returns the published bf16
    peak of the chip found."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"{what} measures a TPU chip and found "
                 f"platform {dev.platform!r} ({dev.device_kind}); "
                 "refusing to report device metrics from it")
    if dev.device_kind not in PEAK_TFLOPS_BF16:
        sys.exit(f"{what}: device_kind {dev.device_kind!r} is "
                 "not in PEAK_TFLOPS_BF16; add its published peak with "
                 "its source before measuring on it")
    return PEAK_TFLOPS_BF16[dev.device_kind]


def calibrate_peak_tflops(repeats=3):
    """Empirical bf16 MXU peak: best sustained TFLOP/s of a pure-matmul
    chain, timed by the readback slope method (utils/benchmarks.sync).
    The denominator for an honest MFU is measured, not looked up:
    nothing this chip runs can exceed its own best matmul."""
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.utils.benchmarks import sync
    best = 0.0
    best_shape = None
    steps = 32
    rng = np.random.default_rng(0)
    for n in (4096, 8192):
        # near-unit spectral radius keeps the chain finite in bf16
        b = jnp.asarray(rng.standard_normal((n, n)) / (n ** 0.5),
                        jnp.bfloat16)
        x0 = jnp.ones((n, n), jnp.bfloat16)

        @jax.jit
        def chain(x, b=b):
            for _ in range(steps):
                x = jax.lax.dot(x, b,
                                preferred_element_type=jnp.bfloat16)
            return x

        x = chain(x0)
        sync(x)  # compile + true sync

        from horovod_tpu.utils.benchmarks import slope_window
        flops_per_chain = 2.0 * n * n * n * steps
        samples = []
        for _ in range(repeats):
            # step_once threads x (fresh inputs every call) and yields
            # it as the syncable too
            dt, x = slope_window(lambda v: (chain(v),) * 2, x,
                                 iters=4, base_iters=1)
            samples.append(4 * flops_per_chain / dt / 1e12)
        # median per shape (a best-of on noisy slopes biases high),
        # best shape wins
        tf_s = statistics.median(samples)
        if tf_s > best:
            best, best_shape = tf_s, n
    return best, best_shape


def lm_tokens_per_sec(flash, *, seq_len=2048, batch=8, layers=12,
                      d_model=768, heads=12, vocab=32000, steps=10,
                      warmup=3, seq_parallel=False):
    """Single-window LM training throughput (the shared
    ``make_lm_bench`` workload — exactly what jax_lm_benchmark.py
    runs). Returns ``(tokens_per_sec, achieved_tflops)`` where the
    TFLOP/s come from XLA's own per-device cost analysis of the step —
    the LM MFU numerator."""
    import numpy as np

    from horovod_tpu.utils.benchmarks import (cost_analysis_dict,
                                              make_lm_bench, slope_window,
                                              sync)

    devs = np.asarray(jax.devices())
    n_seq = devs.size if seq_parallel and devs.size > 1 else 1
    mesh = jax.sharding.Mesh(devs[:n_seq].reshape(1, n_seq),
                             ("data", "seq"))
    step, state, tokens = make_lm_bench(
        mesh=mesh, seq_axis="seq" if n_seq > 1 else None, batch=batch,
        seq_len=seq_len, layers=layers, d_model=d_model, heads=heads,
        vocab=vocab, flash=flash)
    cost = cost_analysis_dict(step.lower(state, tokens).compile())
    flops_per_step = float(cost["flops"])
    for _ in range(warmup):
        state, loss = step(state, tokens)
        sync(loss)
    dt, _ = slope_window(lambda st: step(st, tokens), state, steps)
    return (batch * seq_len * steps / dt,
            flops_per_step * steps / dt / 1e12)


def _opt_state_bytes_per_device(opt_state):
    """Measured per-device optimizer-state bytes: the bytes of every
    state leaf's shards resident on device 0 (replicated leaves count in
    full, ZeRO-sharded bucket rows count 1/N) — the ZeRO-1 memory claim
    read off the real arrays, not computed from the plan."""
    import jax as _jax
    dev0 = _jax.local_devices()[0]
    total = 0
    for leaf in _jax.tree_util.tree_leaves(opt_state):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            total += np_nbytes(leaf)
            continue
        total += sum(s.data.nbytes for s in shards if s.device == dev0)
    return total


def np_nbytes(x):
    import numpy as np
    a = np.asarray(x)
    return a.size * a.dtype.itemsize


def _record_step_time(args, step, state, images, labels, result, suffix):
    """Shared timing summary for the comparison modes: median
    slope-window step time into ``step_ms_<suffix>`` plus the
    conservative-bound count — one implementation so --overlap and
    --compression can never report inconsistently computed numbers."""
    from horovod_tpu.utils.benchmarks import repeat_throughput

    runs = repeat_throughput(step, state, images, labels,
                             max(args.num_warmup - 1, 0),
                             args.num_iters, args.repeats)
    dts = sorted(float(r[1]) for r in runs)
    dt = dts[len(dts) // 2]
    result[f"step_ms_{suffix}"] = round(1000 * dt / args.num_iters, 2)
    n_bound = sum(1 for r in runs
                  if getattr(r[1], "upper_bound", False))
    if n_bound:
        result[f"upper_bound_windows_{suffix}"] = n_bound


def overlap_variants(compression=None):
    """The ``--overlap`` comparison matrix: the three exchange variants,
    extended with ``overlap_rs_zero1_<fmt>`` (the FULL pipeline —
    overlapped exchange + ZeRO-1 + compressed wire) for each requested
    wire format. Formats are validated here so a typo dies before any
    compile. One function so the CLI contract and its test cannot
    drift."""
    from horovod_tpu.ops import compression as compression_lib

    variants = {
        "baseline_fused_ar": dict(sharded=False, overlap=False),
        "overlap_rs": dict(sharded=False, overlap=True),
        "overlap_rs_zero1": dict(sharded=True, overlap=True),
    }
    wire_formats = []
    if compression is not None:
        wire_formats = [f for f in (list(compression)
                                    or ["bf16", "fp8", "int8"])
                        if f != "none"]
        for f in wire_formats:
            compression_lib.by_name(f)  # fail fast on a typo
        for fmt in wire_formats:
            variants[f"overlap_rs_zero1_{fmt}"] = dict(
                sharded=True, overlap=True, wire=fmt)
    return variants, wire_formats


def overlap_comparison(args):
    """``--overlap``: step time for {baseline fused-allreduce, overlapped
    reduce-scatter pipeline, overlapped + ZeRO-1 sharded update} on the
    same comm-heavy workload (same model, same global batch, same
    accum_steps), plus measured per-device optimizer-state bytes.
    Combined with ``--compression`` the matrix extends with the FULL
    pipeline — overlapped + ZeRO-1 at each requested wire format
    (``overlap_rs_zero1_<fmt>``) — so prefetch-era rounds can benchmark
    the whole exchange (overlap + compressed wire) in one run instead of
    two mutually-exclusive modes. One JSON line, same contract as the
    headline bench."""
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import training
    from horovod_tpu.utils.benchmarks import make_model, synthetic_batch

    variants, wire_formats = overlap_variants(args.compression)

    hvd.init()
    ndev = hvd.num_devices()
    K = args.accum_steps
    global_batch = args.batch_size * ndev
    images, labels = synthetic_batch(global_batch, args.image_size)

    result = {"metric": f"{args.model}_overlap_pipeline_step_ms",
              "unit": "ms/step", "accum_steps": K, "devices": ndev,
              "per_chip_batch": args.batch_size, "repeats": args.repeats}
    if wire_formats:
        result["wire_formats"] = wire_formats
    for name, kind in variants.items():
        # adamw: momentum + second moment = the optimizer state ZeRO-1
        # shards; a fresh model+tx per variant so donation can't alias
        model = make_model(args.model)
        tx = hvd.DistributedOptimizer(optax.adamw(1e-3),
                                      sharded_update=kind["sharded"],
                                      compression=kind.get("wire"))
        step = training.make_train_step(model, tx, donate=True,
                                        accum_steps=K,
                                        overlap_grads=kind["overlap"])
        state = training.create_train_state(model, tx,
                                            jax.random.PRNGKey(0),
                                            images[:1])
        # run one real step to materialize the placed/donated state, then
        # read the optimizer-state footprint off the live arrays
        state, _ = step(state, images, labels)
        result[f"opt_state_bytes_per_device_{name}"] = (
            _opt_state_bytes_per_device(state.opt_state))
        _record_step_time(args, step, state, images, labels, result, name)
    base = result.get("opt_state_bytes_per_device_baseline_fused_ar", 0)
    z1 = result.get("opt_state_bytes_per_device_overlap_rs_zero1", 0)
    if base and z1:
        result["zero1_opt_state_shrink_factor"] = round(base / z1, 2)
    if result.get("step_ms_baseline_fused_ar", 0):
        for name in variants:
            if name != "baseline_fused_ar" and \
                    result.get(f"step_ms_{name}"):
                result[f"speedup_{name}_vs_baseline"] = round(
                    result["step_ms_baseline_fused_ar"] /
                    result[f"step_ms_{name}"], 3)
    result["telemetry"] = _telemetry_block()
    _attach_goodput(result)
    emit(result)


def compression_comparison(args):
    """``--compression``: the overlapped bucket pipeline at each requested
    wire format on the same workload — step time, bytes-on-wire, and the
    logical/wire compression ratio per format (docs/PERFORMANCE.md,
    "Wire compression"). Bytes come from the telemetry counters, which
    advance at TRACE time on the compiled path: the delta across the
    first (tracing) step call is the wire volume baked into one compiled
    step. One JSON line, same contract as the headline bench."""
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import telemetry, training
    from horovod_tpu.ops import compression as compression_lib
    from horovod_tpu.telemetry import instruments
    from horovod_tpu.utils.benchmarks import make_model, synthetic_batch

    formats = list(args.compression) or ["none", "bf16", "fp8", "int8"]
    for f in formats:
        compression_lib.by_name(f)  # fail fast on a typo
    if "none" not in formats:
        formats = ["none"] + formats  # ratio/speedup need the baseline

    hvd.init()
    ndev = hvd.num_devices()
    K = args.accum_steps
    global_batch = args.batch_size * ndev
    images, labels = synthetic_batch(global_batch, args.image_size)
    reg = telemetry.get_registry()

    def wire_totals():
        # bucket_* labels only: the pipeline's bucket counters aggregate
        # the primitive dispatches they wrap (alltoall/allgather/...),
        # which record under their own op labels too — summing every
        # label would double-count the same bytes
        out = []
        for name in (instruments.COLLECTIVE_BYTES,
                     instruments.COLLECTIVE_LOGICAL_BYTES):
            fam = reg.get(name)
            s = fam.sample() if fam is not None else {}
            if not isinstance(s, dict):
                out.append(float(s or 0.0))
                continue
            out.append(float(sum(
                v for k, v in s.items()
                if any(str(part).startswith("bucket_") for part in k))))
        return out

    result = {"metric": f"{args.model}_wire_compression_step_ms",
              "unit": "ms/step", "accum_steps": K, "devices": ndev,
              "per_chip_batch": args.batch_size, "repeats": args.repeats}
    for name in formats:
        model = make_model(args.model)
        tx = hvd.DistributedOptimizer(optax.sgd(1e-3, momentum=0.9),
                                      compression=name)
        step = training.make_train_step(model, tx, donate=True,
                                        accum_steps=K, overlap_grads=True)
        state = training.create_train_state(model, tx,
                                            jax.random.PRNGKey(0),
                                            images[:1])
        w0, l0 = wire_totals()
        state, _ = step(state, images, labels)  # traces + compiles
        w1, l1 = wire_totals()
        wire_b, logical_b = w1 - w0, l1 - l0
        result[f"wire_bytes_per_step_{name}"] = int(wire_b)
        result[f"logical_bytes_per_step_{name}"] = int(logical_b)
        if wire_b > 0:
            result[f"compression_ratio_{name}"] = round(
                logical_b / wire_b, 3)
        _record_step_time(args, step, state, images, labels, result, name)
    if result.get("step_ms_none"):
        for name in formats:
            if name != "none" and result.get(f"step_ms_{name}"):
                result[f"speedup_{name}_vs_none"] = round(
                    result["step_ms_none"] / result[f"step_ms_{name}"], 3)
    result["telemetry"] = _telemetry_block()
    _attach_goodput(result)
    emit(result)


def _record_lm_step_time(args, step, state, tokens, result, suffix):
    """LM-path timing summary for ``--spmd`` (the LM step takes
    ``(state, tokens)``): median slope-window step time into
    ``lm_step_ms_<suffix>`` plus the conservative-bound count — the
    same discipline as ``_record_step_time``, via the one shared
    warm-then-measure helper. Unlike the ResNet path — which burns one
    warmup on the state-materializing step call before its timing — the
    LM path arrives here cold, so the FULL ``num_warmup`` runs (the
    slope window's untimed flush would absorb a stray compile either
    way, but the two paths should enter their windows equally warm)."""
    from horovod_tpu.utils.benchmarks import repeat_step_windows

    dts, state = repeat_step_windows(
        lambda st: step(st, tokens), state,
        args.num_warmup, args.num_iters, args.repeats)
    ordered = sorted(float(d) for d in dts)
    result[f"lm_step_ms_{suffix}"] = round(
        1000 * ordered[len(ordered) // 2] / args.num_iters, 2)
    n_bound = sum(1 for d in dts if getattr(d, "upper_bound", False))
    if n_bound:
        result[f"lm_upper_bound_windows_{suffix}"] = n_bound
    return state


def spmd_comparison(args):
    """``--spmd``: the GSPMD-vs-explicit head-to-head (ROADMAP open item
    1; docs/PERFORMANCE.md, "The GSPMD path") on BOTH hot paths:

    * **ResNet**: explicit overlap+ZeRO-1 pipeline vs the
      NamedSharding-compiled GSPMD step (``make_train_step(spmd=True)``
      — no explicit collective calls, XLA inserts the exchange). With
      wire formats requested (``--compression``, or the ``--spmd-wire``
      default), each format adds a head-to-head PAIR: the explicit
      compressed pipeline (``explicit_wire_<fmt>``) and GSPMD with the
      compression compiled IN-PLACE (``gspmd_wire_<fmt>`` — the
      shard_map island for chunked fp8/int8, dtype-narrowed constraints
      for bf16 casts; ISSUE 17, no fallback).
    * **LM**: the shared ``make_lm_bench`` workload, batch-sharded over
      the full data mesh — GSPMD and the same per-format pairs vs the
      ``explicit`` LM step. The LM path has no overlap+ZeRO pipeline
      (``make_lm_train_step`` reduces via one fused allreduce), so its
      baseline is the explicit fused-AR step and its keys say
      ``lm_step_ms_explicit`` — deliberately NOT the ResNet half's
      ``explicit_overlap_zero1`` label.

    Emits per-variant step times, measured per-device optimizer-state
    bytes (the ZeRO-1 sharding must survive the path change), the
    compiled-HLO collective byte accounting for the GSPMD builds (the
    island's alltoall rides the same ``spmd_*`` counters — honest
    wire-width bytes off the module XLA produced), and the parity
    ratios the acceptance gates read: ``gspmd_over_explicit_step_time``
    <= 1.02 before GSPMD can become a default, and per format
    ``island_over_explicit_wire_<fmt>`` < 1 (the compiled island must
    beat the explicit compressed pipeline) plus
    ``island_over_gspmd_<fmt>`` (< 1 only where the wire is the
    bottleneck). One JSON line, same contract as
    the headline bench."""
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import training
    from horovod_tpu.utils.benchmarks import (make_lm_bench, make_model,
                                              synthetic_batch)

    hvd.init()
    ndev = hvd.num_devices()
    global_batch = args.batch_size * ndev
    images, labels = synthetic_batch(global_batch, args.image_size)

    if args.compression is None:
        formats = [args.spmd_wire]
    elif args.compression:
        formats = [f for f in args.compression if f != "none"]
    else:  # bare --compression: the documented island matrix
        formats = ["bf16", "fp8", "int8"]

    result = {"metric": f"{args.model}_gspmd_vs_explicit_step_ms",
              "unit": "ms/step", "devices": ndev,
              "per_chip_batch": args.batch_size, "repeats": args.repeats,
              "spmd_wire_formats": formats}

    variants = {
        "explicit_overlap_zero1": dict(spmd=False, wire=None),
        "gspmd": dict(spmd=True, wire=None),
    }
    for fmt in formats:
        variants[f"explicit_wire_{fmt}"] = dict(spmd=False, wire=fmt)
        variants[f"gspmd_wire_{fmt}"] = dict(spmd=True, wire=fmt)
    for name, kind in variants.items():
        model = make_model(args.model)
        tx = hvd.DistributedOptimizer(optax.adamw(1e-3),
                                      sharded_update=True,
                                      compression=kind["wire"])
        step = training.make_train_step(
            model, tx, donate=True, spmd=kind["spmd"],
            overlap_grads=not kind["spmd"])
        state = training.create_train_state(model, tx,
                                            jax.random.PRNGKey(0),
                                            images[:1])
        state, _ = step(state, images, labels)
        result[f"opt_state_bytes_per_device_{name}"] = (
            _opt_state_bytes_per_device(state.opt_state))
        if getattr(step, "compiled_collectives", None):
            result[f"compiled_collective_bytes_{name}"] = {
                op: t["bytes"]
                for op, t in step.compiled_collectives.items()}
        if name == "gspmd":
            # X-ray the uncompressed GSPMD step: where the compiled
            # step's device time goes, gated on the classifier naming
            # >=95% of it (state threads through the traced steps)
            state = _attach_step_attribution(result, step, state,
                                             images, labels)
        _record_step_time(args, step, state, images, labels, result, name)

    # -- LM path (the shared make_lm_bench workload, data-sharded) -----
    lm_cfg = dict(batch=2 * ndev, seq_len=args.spmd_lm_seq_len,
                  layers=2, d_model=args.spmd_lm_d_model, heads=8,
                  vocab=2048)
    result["lm_config"] = lm_cfg
    # the LM baseline is the explicit fused-allreduce step — there is
    # no overlap+ZeRO LM pipeline to compare against, and labeling it
    # as one would publish a parity ratio against a baseline that is
    # not the named thing
    lm_variants = {
        "explicit": dict(spmd=False, wire=None),
        "gspmd": dict(spmd=True, wire=None),
    }
    for fmt in formats:
        lm_variants[f"explicit_wire_{fmt}"] = dict(spmd=False, wire=fmt)
        lm_variants[f"gspmd_wire_{fmt}"] = dict(spmd=True, wire=fmt)
    for name, kind in lm_variants.items():
        step, state, tokens = make_lm_bench(
            mesh=hvd.mesh(), seq_axis=None, flash=None,
            spmd=kind["spmd"], compression=kind["wire"], **lm_cfg)
        state = _record_lm_step_time(args, step, state, tokens, result,
                                     name)
        if getattr(step, "compiled_collectives", None):
            result[f"lm_compiled_collective_bytes_{name}"] = {
                op: t["bytes"]
                for op, t in step.compiled_collectives.items()}

    for prefix, base_name, key in (
            ("step_ms", "explicit_overlap_zero1",
             "gspmd_over_explicit_step_time"),
            ("lm_step_ms", "explicit",
             "lm_gspmd_over_explicit_step_time")):
        base = result.get(f"{prefix}_{base_name}")
        got = result.get(f"{prefix}_gspmd")
        if base and got:
            result[key] = round(got / base, 3)
            result[key + "_parity_within_2pct"] = bool(
                got / base <= 1.02)
    # per-format island gates: vs the explicit compressed pipeline
    # (must win) and vs uncompressed GSPMD (wins where the wire is the
    # bottleneck)
    for fmt in formats:
        for prefix, tag in (("step_ms", ""), ("lm_step_ms", "lm_")):
            island = result.get(f"{prefix}_gspmd_wire_{fmt}")
            exp_c = result.get(f"{prefix}_explicit_wire_{fmt}")
            base = result.get(f"{prefix}_gspmd")
            if island and exp_c:
                result[f"{tag}island_over_explicit_wire_{fmt}"] = (
                    round(island / exp_c, 3))
            if island and base:
                result[f"{tag}island_over_gspmd_{fmt}"] = (
                    round(island / base, 3))
    result["telemetry"] = _telemetry_block()
    _attach_goodput(result)
    emit(result)


def data_plane_comparison(args):
    """``--data-plane``: the INPUT-BOUND configuration. The same compiled
    train step is driven two ways over the same deterministic batch
    stream: synchronously (batch assembly + the injected storage latency
    run on the TRAINING thread, the pre-data-plane behavior) and through
    the ``PrefetchLoader`` (assembly + host→device staging on the
    producer thread, overlapped with the running step). Reports both
    step times, the prefetch speedup, and the data-wait fraction the
    loader actually charged the training thread
    (``hvd_data_wait_seconds`` / wall) — when the pipeline keeps up the
    fraction is ~0 and prefetch-on step time collapses to compute
    (docs/DATA.md). ``--data-delay-ms`` is the per-batch synthetic
    storage latency that makes the run input-bound on purpose. One JSON
    line, same contract as the headline bench."""
    import time as _time

    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import telemetry, training
    from horovod_tpu.data import ArraySource, PrefetchLoader, segment
    from horovod_tpu.telemetry import instruments as ti
    from horovod_tpu.utils.benchmarks import (compute_dtype, make_model,
                                              sync)

    hvd.init()
    ndev = hvd.num_devices()
    global_batch = args.batch_size * ndev
    delay_s = args.data_delay_ms / 1e3
    iters, warmup = args.num_iters, args.num_warmup
    seed = 0

    # a host dataset 4 global batches deep, cycled across epochs — the
    # injected latency, not the resident size, is what models storage
    rng = np.random.default_rng(seed)
    n = global_batch * 4
    images_np = rng.standard_normal(
        (n, args.image_size, args.image_size, 3)).astype(compute_dtype())
    labels_np = rng.integers(0, 1000, size=(n,)).astype(np.int32)

    def batch_indices():
        """The loader's own deterministic plan, reproduced inline — the
        synchronous baseline consumes the IDENTICAL batch stream."""
        epoch = 0
        while True:
            seg = segment(n, seed=seed, epoch=epoch, world=1,
                          batch_size=global_batch, shuffle=True)
            for b in range(len(seg) // global_batch):
                yield seg[b * global_batch:(b + 1) * global_batch]
            epoch += 1

    def build():
        model = make_model(args.model)
        tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))
        step_kw = dict(donate=True)
        return model, tx, step_kw

    result = {"metric": f"{args.model}_data_plane_step_ms",
              "unit": "ms/step", "devices": ndev,
              "per_chip_batch": args.batch_size,
              "data_delay_ms": args.data_delay_ms,
              "prefetch_depth": args.prefetch_depth,
              "timed_iters": iters}

    # -- prefetch OFF: the loader's work serializes with the step -------
    model, tx, step_kw = build()
    step = training.make_train_step(model, tx, **step_kw)
    src = ArraySource([images_np, labels_np], delay_s=delay_s)
    plan = batch_indices()
    state = training.create_train_state(
        model, tx, jax.random.PRNGKey(0), jnp_first(images_np))
    for _ in range(warmup):
        x, y = src.batch(next(plan))
        state, loss = step(state, x, y)
        sync(loss)
    t0 = _time.perf_counter()
    for _ in range(iters):
        x, y = src.batch(next(plan))
        state, loss = step(state, x, y)
        sync(loss)
    off_s = _time.perf_counter() - t0
    result["step_ms_prefetch_off"] = round(1000 * off_s / iters, 2)

    # -- prefetch ON: producer thread assembles + stages ahead ----------
    model, tx, step_kw = build()
    loader = PrefetchLoader(
        ArraySource([images_np, labels_np], delay_s=delay_s),
        global_batch, depth=args.prefetch_depth, rank=0, world=1,
        seed=seed, shuffle=True, drop_last=True)
    step = training.make_train_step(model, tx, loader=loader, **step_kw)
    state = training.create_train_state(
        model, tx, jax.random.PRNGKey(0), jnp_first(images_np))
    reg = telemetry.get_registry()

    def wait_sum():
        fam = reg.get(ti.DATA_WAIT_SECONDS)
        return float(fam.sum) if fam is not None else 0.0

    for _ in range(warmup):
        state, loss = step(state)
        sync(loss)
    w0 = wait_sum()
    t0 = _time.perf_counter()
    for _ in range(iters):
        state, loss = step(state)
        sync(loss)
    on_s = _time.perf_counter() - t0
    waited = wait_sum() - w0
    loader.close()
    result["step_ms_prefetch_on"] = round(1000 * on_s / iters, 2)
    result["data_wait_fraction"] = round(waited / on_s, 4) if on_s else 0.0
    if on_s > 0:
        result["prefetch_speedup"] = round(off_s / on_s, 3)
    fam = reg.get(ti.DATA_BYTES_STAGED)
    if fam is not None:
        result["bytes_staged_total"] = int(fam.value)
    result["telemetry"] = _telemetry_block()
    _attach_goodput(result)
    emit(result)


def _churn_schedule(steps, preemptions, seed):
    """Map a seeded ChaosPlan's injection times onto step indices (same
    seed -> same schedule), so the churn bench is reproducible and
    comparable across runs the way the hvdrun chaos soak is."""
    from horovod_tpu.chaos import ChaosPlan
    plan = ChaosPlan.generate(seed=seed, interval=1.0, jitter=0.5,
                              kinds=("sigterm",), count=preemptions)
    if not plan.injections:
        return []
    t_max = plan.injections[-1].at or 1.0
    # never step 0 (nothing committed yet) and strictly increasing
    idxs, prev = [], 0
    for inj in plan.injections:
        idx = max(prev + 1, min(steps - 1,
                                int(inj.at / t_max * (steps - 1))))
        if idx >= steps:
            break
        idxs.append(idx)
        prev = idx
    return idxs


def churn_comparison(args):
    """``--churn``: goodput under a scripted preemption schedule — the
    SLO gate of the preemption-native story (docs/ELASTIC.md, "Running
    on spot capacity"). A small compiled train loop runs ``--churn-steps``
    steps; at seeded schedule points the loop simulates a graceful
    eviction exactly the way ``elastic/preempt.py`` spends it — a real
    ``AsyncCheckpointer`` force-commit plus the drain window — inside
    the ledger's ``preemption`` phase. The emitted ``goodput`` block
    must then (a) hold the sum≈wall invariant (every lost second
    attributed), (b) show a NON-ZERO ``preemption`` lane, and (c) keep
    ``goodput_ratio`` at or above ``--churn-budget``. Any violation is
    a loud nonzero exit — the gate, not a report. One JSON line, same
    contract as the headline bench."""
    import shutil
    import tempfile
    import time as _time

    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.ckpt import AsyncCheckpointer
    from horovod_tpu.telemetry import ledger as ledger_lib
    from horovod_tpu.telemetry import report as report_mod
    from horovod_tpu.telemetry.registry import MetricsRegistry
    from horovod_tpu.utils.benchmarks import sync

    hvd.init()
    steps = args.churn_steps
    schedule = _churn_schedule(steps, args.churn_preemptions,
                               args.churn_seed)

    # enough matmul per step that compute dominates the loop on a CPU
    # smoke run; the ratio gate is about attribution, not silicon speed
    n = 192
    rng = np.random.default_rng(args.churn_seed)
    b = jnp.asarray(rng.standard_normal((n, n)) / (n ** 0.5))

    @jax.jit
    def train_step(x):
        for _ in range(8):
            x = x @ b
        return x

    x = jnp.ones((n, n))
    tree = {"w": rng.standard_normal(1 << 16).astype(np.float32)}
    root = tempfile.mkdtemp(prefix="hvd_bench_churn_")
    ck = AsyncCheckpointer(root, keep=2, rank=0, world=1,
                           registry=MetricsRegistry())
    preempted_at = []
    try:
        sched = set(schedule)
        sync(train_step(x))  # compile outside the measured window
        # fresh attribution window: the SLO is about steady-state churn
        # cost, not one-time compilation (which has its own lane in the
        # headline modes)
        led = ledger_lib.reset_run()
        led.start()
        for i in range(steps):
            x = train_step(x)
            sync(x)
            led.settle_step()
            if i in sched:
                # one simulated graceful eviction: the grace commit (a
                # REAL async-checkpointer flush — its blocked time lands
                # in ckpt_stall, keeping phases exclusive) plus the
                # drain window (announce + exit + relaunch stand-in),
                # all inside the preemption lane like preempt.py spends
                # the real thing
                with led.phase("preemption"):
                    ck.save(i, tree)
                    ck.flush()
                    _time.sleep(args.churn_drain_ms / 1e3)
                preempted_at.append(i)
                _count_simulated_preemption()
        ck.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)

    result = {"metric": "goodput_under_churn", "unit": "ratio",
              "steps": steps, "churn_seed": args.churn_seed,
              "preemptions": len(preempted_at),
              "preempted_at_steps": preempted_at,
              "drain_ms": args.churn_drain_ms,
              "budget": args.churn_budget}
    failures = []
    try:
        block = report_mod.goodput_block()
        result["goodput"] = block
        preempt_s = float(block["phases"].get("preemption", 0.0))
        result["preemption_seconds"] = round(preempt_s, 4)
        result["value"] = block["goodput_ratio"]
        if preempted_at and preempt_s <= 0.0:
            failures.append(
                "preemption lane is EMPTY despite "
                f"{len(preempted_at)} scripted preemption(s) — the "
                "eviction window is not being attributed")
        if block["goodput_ratio"] < args.churn_budget:
            failures.append(
                f"goodput ratio {block['goodput_ratio']:.4f} under churn "
                f"fell below the {args.churn_budget:.4f} budget")
    except report_mod.GoodputInvariantError as e:
        result["goodput_error"] = str(e)
        failures.append(f"unattributed time under churn: {e}")
    if failures:
        result["slo"] = "FAIL"
        emit(result)
        for f in failures:
            print(f"bench --churn: SLO GATE FAILED: {f}", file=sys.stderr)
        sys.exit(2)
    result["slo"] = "PASS"
    emit(result)


def _count_simulated_preemption():
    from horovod_tpu.telemetry import instruments as _tele
    from horovod_tpu.telemetry.registry import get_registry
    get_registry().counter(
        _tele.PREEMPTIONS_TOTAL,
        "Preemption notices acted on, by source kind "
        "(docs/OBSERVABILITY.md)",
        label_names=("kind",)).labels("simulated").inc()


def jnp_first(images_np):
    """First example as the model-init sample input."""
    import jax.numpy as jnp
    return jnp.asarray(images_np[:1])


def _telemetry_block():
    """The registry snapshot for the BENCH json: collective bytes and
    bucket fill ride alongside throughput, so perf rounds can attribute
    a regression to wire volume / bucket structure without rerunning."""
    from horovod_tpu import telemetry
    snap = telemetry.get_registry().snapshot()
    keep = ("hvd_collective", "hvd_bucket", "hvd_step",
            "hvd_examples", "hvd_compile", "hvd_wire", "hvd_data")
    return {k: v for k, v in sorted(snap.items())
            if k.startswith(keep)}


def _attach_goodput(result):
    """The BENCH ``goodput`` block: the run ledger's phase breakdown
    with the *sum ≈ 100% of wall* invariant ENFORCED — an unattributed
    gap >2% of wall is a loud error (stderr + a ``goodput_error`` field),
    never silence, so perf regressions stay attributable
    (docs/OBSERVABILITY.md, "Where did my time go")."""
    from horovod_tpu.telemetry import ledger as ledger_lib
    from horovod_tpu.telemetry import report as report_mod
    if not ledger_lib.get_ledger().enabled:
        return  # HOROVOD_GOODPUT=0 is an opt-out, not a violation
    try:
        result["goodput"] = report_mod.goodput_block()
    except report_mod.GoodputInvariantError as e:
        print(f"bench: GOODPUT INVARIANT VIOLATED: {e}", file=sys.stderr)
        result["goodput_error"] = str(e)


def _attach_step_attribution(result, step, state, images, labels, k=3):
    """The BENCH ``step_attribution`` block (the training twin of
    bench_serve's ``tail_attribution``): X-ray K compiled steps
    (``step.xray`` → telemetry/xprof.py) and attach the device-time
    buckets, exposed-vs-overlapped collective split and verdict. The
    honesty gate is ENFORCED — a ``bucketed_fraction`` below 95% means
    the classifier can no longer name this backend's device time, and
    that is a loud error (stderr + ``step_attribution_error``), never
    silence; a capture that throws ends the run. Returns the threaded
    ``state`` (the traced steps donate their inputs as usual)."""
    from horovod_tpu.telemetry import xprof
    state, summary = step.xray(state, images, labels, k=k)
    result["step_attribution"] = summary
    if summary["bucketed_fraction"] < xprof.BUCKETED_GATE:
        msg = (f"step_attribution bucketed only "
               f"{summary['bucketed_fraction']:.1%} of device time "
               f"(gate {xprof.BUCKETED_GATE:.0%}) — unattributed "
               f"{summary['unattributed_seconds']:.4f}s; the trace "
               "classifier no longer understands this backend's "
               "events")
        print(f"bench: STEP ATTRIBUTION GATE FAILED: {msg}",
              file=sys.stderr)
        result["step_attribution_error"] = msg
    return state


def _checkpoint_block(nbytes=32 << 20):
    """Async-checkpoint microbench for the BENCH json (docs/
    CHECKPOINT.md): for a synthetic ``nbytes`` state, the synchronous
    ``save_sharded`` wall time (the old stall-until-durable cost), the
    stall the async path actually charges the training thread
    (snapshot + budget wait), the end-to-end commit latency, and the
    background serialize+fsync bandwidth. One rank, local disk — the
    floor a real run's shared filesystem can only raise."""
    import shutil
    import tempfile
    import time as _time

    import numpy as np

    from horovod_tpu.ckpt import AsyncCheckpointer, save_sharded
    from horovod_tpu.telemetry.registry import MetricsRegistry

    rng = np.random.default_rng(0)
    leaves = 8
    tree = {f"p{i}": rng.standard_normal(nbytes // 4 // leaves)
            .astype(np.float32) for i in range(leaves)}
    root = tempfile.mkdtemp(prefix="hvd_bench_ckpt_")
    try:
        t0 = _time.perf_counter()
        man = save_sharded(root, 1, tree, rank=0, world=1)
        sync_s = _time.perf_counter() - t0
        written = sum(s["bytes"] for s in man["shards"].values())

        ck = AsyncCheckpointer(root, keep=2, rank=0, world=1,
                               registry=MetricsRegistry())
        t0 = _time.perf_counter()
        blocking_s = ck.save(2, tree)
        ck.flush()
        total_s = _time.perf_counter() - t0
        ck.close()
        bg_s = max(total_s - blocking_s, 1e-9)
        return {
            "state_mb": round(nbytes / 2**20, 1),
            "sync_write_ms": round(sync_s * 1e3, 2),
            "snapshot_stall_ms": round(blocking_s * 1e3, 2),
            "commit_latency_ms": round(total_s * 1e3, 2),
            "background_write_mb_per_s": round(written / 2**20 / bg_s, 1),
            "blocking_pct_of_sync": round(100 * blocking_s / sync_s, 1),
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _flightrec_overhead_ns(n=200_000):
    """Micro-bench the flight recorder's hot-path cost (one collective
    entry: deque append + CRC chain) so a regression in the
    "bounded append, no I/O, no locks" contract shows in the BENCH json
    as flightrec_overhead_ns_per_event."""
    import time as _time

    from horovod_tpu.diag.recorder import FlightRecorder
    rec = FlightRecorder(capacity=4096, rank=0, size=1)
    shape, dtype = (1024, 1024), "float32"
    t0 = _time.perf_counter()
    for i in range(n):
        rec.collective_enter("allreduce", shape=shape, dtype=dtype,
                             nbytes=4 << 20, mode="trace")
    dt = _time.perf_counter() - t0
    return dt / n * 1e9


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="resnet101",
                        choices=["resnet18", "resnet50", "resnet101",
                                 "vgg16"])
    parser.add_argument("--batch-size", type=int, default=256,
                        help="per-chip batch size (64 = literal reference "
                             "config; 256 saturates a v5e MXU)")
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--num-warmup", type=int, default=3)
    parser.add_argument("--num-iters", type=int, default=20)
    parser.add_argument("--repeats", type=int, default=5,
                        help="timed windows; the median is reported "
                             "with its min/max spread")
    parser.add_argument("--no-calibrate", action="store_true",
                        help="skip the empirical-peak matmul sweep")
    parser.add_argument("--no-lm", action="store_true",
                        help="skip the LM tokens/sec (flash on/off) runs")
    parser.add_argument("--calibrate", action="store_true",
                        help="run ONLY the empirical-peak calibration and "
                             "print its JSON line")
    parser.add_argument("--overlap", action="store_true",
                        help="run ONLY the overlapped-exchange comparison: "
                             "baseline fused-AR vs bucketed RS pipeline vs "
                             "RS pipeline + ZeRO-1 (docs/PERFORMANCE.md); "
                             "add --compression to extend the matrix with "
                             "compressed-wire overlap+ZeRO-1 variants")
    parser.add_argument("--accum-steps", type=int, default=4,
                        help="gradient-accumulation microbatches for "
                             "--overlap (the pipeline overlaps bucket k's "
                             "reduce-scatter with microbatch k+1's "
                             "backward)")
    parser.add_argument("--compression", nargs="*", default=None,
                        metavar="{none,bf16,fp8,int8}",
                        help="run the wire-compression comparison: the "
                             "overlapped pipeline at each listed wire "
                             "format (bare --compression = all four), "
                             "emitting step time, bytes-on-wire, and the "
                             "compression ratio (docs/PERFORMANCE.md). "
                             "Combined with --overlap it extends that "
                             "matrix with overlap+ZeRO-1 variants at "
                             "each wire format — the full pipeline in "
                             "one run")
    parser.add_argument("--spmd", action="store_true",
                        help="run ONLY the GSPMD-vs-explicit comparison: "
                             "explicit overlap+ZeRO-1 vs the NamedSharding-"
                             "compiled GSPMD step vs GSPMD+wire compiled "
                             "IN-PLACE (the shard_map island for chunked "
                             "formats, dtype-narrowed constraints for "
                             "casts), head-to-head with the explicit "
                             "compressed pipeline, on the ResNet AND LM "
                             "paths (docs/PERFORMANCE.md, 'The GSPMD "
                             "path'). Combine with --compression to list "
                             "the wire formats (bare --compression = "
                             "bf16 fp8 int8)")
    parser.add_argument("--spmd-wire", default="int8",
                        metavar="{bf16,fp8,int8}",
                        help="wire format for the --spmd compressed "
                             "variants when --compression is not given "
                             "(default int8)")
    parser.add_argument("--spmd-lm-d-model", type=int, default=256,
                        help="--spmd LM-path model width (small default "
                             "so the comparison runs on CPU meshes; "
                             "raise on real chips)")
    parser.add_argument("--spmd-lm-seq-len", type=int, default=256,
                        help="--spmd LM-path sequence length")
    parser.add_argument("--data-plane", action="store_true",
                        help="run ONLY the input-bound data-plane "
                             "comparison: the same step fed "
                             "synchronously vs through the "
                             "PrefetchLoader, with data-wait fraction "
                             "(docs/DATA.md)")
    parser.add_argument("--data-delay-ms", type=float, default=30.0,
                        help="synthetic per-batch storage latency for "
                             "--data-plane (what makes the config "
                             "input-bound)")
    parser.add_argument("--prefetch-depth", type=int, default=3,
                        help="PrefetchLoader queue depth for --data-plane")
    parser.add_argument("--churn", action="store_true",
                        help="run ONLY the goodput-under-churn SLO gate: "
                             "a compiled loop with seeded simulated "
                             "graceful evictions (real checkpointer "
                             "force-commit + drain window in the "
                             "ledger's preemption lane); exits nonzero "
                             "when the goodput ratio falls below "
                             "--churn-budget, the preemption lane is "
                             "empty, or any lost second is unattributed "
                             "(docs/ELASTIC.md)")
    parser.add_argument("--churn-steps", type=int, default=80,
                        help="train steps for --churn")
    parser.add_argument("--churn-preemptions", type=int, default=3,
                        help="scripted preemptions for --churn")
    parser.add_argument("--churn-seed", type=int, default=0,
                        help="seed of the --churn preemption schedule")
    parser.add_argument("--churn-budget", type=float, default=0.25,
                        help="minimum acceptable goodput ratio under "
                             "churn (CPU-smoke-tuned default; raise on "
                             "real chips where compute dominates)")
    parser.add_argument("--churn-drain-ms", type=float, default=40.0,
                        help="simulated drain window per preemption "
                             "(announce + exit + relaunch stand-in)")
    parser.add_argument("--compare", nargs="*", default=None,
                        metavar="DIR_OR_FILE",
                        help="run NO benchmark: diff BENCH_*.json and "
                             "SCALING_*.json round files "
                             "(default: current directory) and flag "
                             "regressions worse than "
                             "--compare-threshold on step_ms, MFU, "
                             "goodput, serve tokens/s and per-world "
                             "scaling efficiency (telemetry/trend.py); "
                             "exits 1 when any metric regressed")
    parser.add_argument("--compare-threshold", type=float, default=5.0,
                        help="--compare regression threshold in "
                             "percent (default 5)")
    args = parser.parse_args()
    if args.repeats < 1:
        parser.error("--repeats must be >= 1")
    if args.accum_steps < 1:
        parser.error("--accum-steps must be >= 1")
    if args.data_plane and (args.overlap or args.compression is not None):
        parser.error("--data-plane is its own comparison mode; run it "
                     "separately from --overlap/--compression")
    if args.spmd and (args.overlap or args.data_plane):
        parser.error("--spmd is its own comparison mode; run it "
                     "separately from --overlap/--data-plane "
                     "(--compression composes: it lists the wire "
                     "formats for the compiled-island variants)")
    if args.churn and (args.overlap or args.compression is not None
                       or args.data_plane or args.spmd):
        parser.error("--churn is its own comparison mode; run it "
                     "separately from --overlap/--compression/"
                     "--data-plane/--spmd")
    if args.compare is not None:
        if (args.overlap or args.compression is not None
                or args.data_plane or args.spmd or args.churn):
            parser.error("--compare reads past rounds; it does not "
                         "combine with a benchmark mode")
        from horovod_tpu.telemetry import trend
        report = trend.run(args.compare,
                           threshold=args.compare_threshold / 100.0,
                           stream=sys.stderr)
        if report is None:
            sys.exit(2)
        print(json.dumps(report))
        sys.exit(1 if report["regressions"] else 0)
    if args.churn:
        if args.churn_steps < 2:
            parser.error("--churn-steps must be >= 2")
        if args.churn_preemptions < 1:
            parser.error("--churn-preemptions must be >= 1")
        churn_comparison(args)
        return

    if args.spmd:
        spmd_comparison(args)
        return

    if args.data_plane:
        data_plane_comparison(args)
        return

    if args.overlap:
        # with --compression too, the matrix gains the compressed
        # overlap+ZeRO-1 variants (the full pipeline in one run)
        overlap_comparison(args)
        return

    if args.compression is not None:
        compression_comparison(args)
        return

    if args.calibrate:
        require_chip("bench.py --calibrate")
        peak, shape = calibrate_peak_tflops()
        emit({"metric": "empirical_peak_tflops_bf16",
               "value": round(peak, 1), "unit": "TFLOP/s",
               "matmul_n": shape, "repeats": 3})
        return

    import horovod_tpu as hvd
    from horovod_tpu import training
    from horovod_tpu.utils.benchmarks import (cost_analysis_dict,
                                              make_model,
                                              repeat_throughput,
                                              synthetic_batch)

    hvd.init()
    peak = require_chip("bench.py's headline mode")
    ndev = hvd.num_devices()
    model = make_model(args.model)
    tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))
    global_batch = args.batch_size * ndev
    images, labels = synthetic_batch(global_batch, args.image_size)

    state = training.create_train_state(model, tx, jax.random.PRNGKey(0),
                                        images[:1])
    step = training.make_train_step(model, tx, donate=True)

    # XLA's own FLOP count for the whole train step -> honest MFU.
    # step is already jitted: lower() reuses its cache entry (no second
    # compile) and reports the post-partitioning PER-DEVICE module.
    # step.lower places args exactly like the timed path: same cache
    # key, so this is THE compile the loop reuses, not an extra one
    cost = cost_analysis_dict(step.lower(state, images, labels).compile())
    flops_per_device_step = float(cost["flops"])

    # fusion-threshold autotune on the real gradient pytree (reference
    # role: parameter_manager.h:186-220), timed by the shared
    # readback-slope primitive. Runs BEFORE the timed windows (donate=True
    # consumes `state` there) with apply=False so the headline workload
    # stays identical across rounds; the JSON records the winner.
    autotuned_mb = None
    autotune_abstained = None
    best_thr, at_timings = hvd.autotune_fusion_threshold(
        state.params, trials=5, apply=False)
    # measured-vs-guessed provenance: nonzero means some trials sat at
    # the noise floor and needed 4x iter escalation (a threshold that
    # stayed an upper bound after escalation abstains instead)
    autotune_escalations = at_timings.slope_window_escalations
    if best_thr is None:
        # abstention contract (docs/AUTOTUNE.md): no rankable signal
        # -> record null + the reason, never a noise argmin
        autotune_abstained = at_timings.abstain_reason
    else:
        autotuned_mb = best_thr >> 20

    runs = repeat_throughput(step, state, images, labels,
                             args.num_warmup, args.num_iters,
                             args.repeats)
    per_chip_runs = sorted(r[0] / ndev for r in runs)
    per_chip = statistics.median(per_chip_runs)
    dts = [r[1] for r in runs]
    dt = statistics.median(dts)
    n_bound = sum(1 for r in runs if getattr(r[1], "upper_bound", False))
    # cost_analysis is per-device already — no further /ndev
    achieved_tflops = flops_per_device_step * args.num_iters / dt / 1e12
    result = {
        "metric": f"{args.model}_synthetic_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / BASELINE_IMG_PER_SEC_PER_CHIP, 3),
        "repeats": args.repeats,
        "img_per_sec_per_chip_min": round(per_chip_runs[0], 2),
        "img_per_sec_per_chip_max": round(per_chip_runs[-1], 2),
        "step_ms_median": round(1000 * dt / args.num_iters, 2),
    }
    if n_bound:  # inverted-window fallbacks: bounds, not measurements
        result["upper_bound_windows"] = n_bound
    result["achieved_tflops_per_chip"] = round(achieved_tflops, 1)
    if achieved_tflops > peak:
        sys.exit(f"bench: achieved {achieved_tflops:.0f} TF/s exceeds the "
                 f"published {peak:.0f} TF/s of this device_kind — the "
                 "timing or the FLOP count is wrong; no result")
    result["mfu_vs_nominal_pct"] = round(100 * achieved_tflops / peak, 1)

    # empirical peak: a swept pure-matmul bf16 chain measured on this
    # chip, reported beside the published one. Calibration is gated ONLY
    # on --no-calibrate: the LM MFU below needs the peak too.
    emp_peak = 0.0
    if not args.no_calibrate:
        emp_peak, emp_shape = calibrate_peak_tflops()
        result["empirical_peak_tflops_bf16"] = round(emp_peak, 1)
        result["empirical_peak_matmul_n"] = emp_shape
        result["mfu_vs_empirical_peak_pct"] = round(
            100 * achieved_tflops / emp_peak, 1)

    # LM path: tokens/sec with the flash kernel on vs off (and
    # seq-parallel over the mesh when >1 device is present). Dense
    # attention at the flash batch does not fit a 16 GB chip (fp32
    # [B,12,2048,2048] scores) — itself the point of the kernel — so
    # the dense line runs at batch 2 and says so.
    if not args.no_lm:
        result["lm_seq_len"] = 2048

        def lm_line(key, mfu_key=None, **kw):
            toks, lm_tflops = lm_tokens_per_sec(**kw)
            result[key] = round(toks, 1)
            if mfu_key and emp_peak > 0:
                result[mfu_key] = round(100 * lm_tflops / emp_peak, 1)

        lm_line("lm_tokens_per_sec_flash_b8", flash=True, batch=8)
        lm_line("lm_tokens_per_sec_dense_b2", flash=False, batch=2)
        # MXU-saturating config: d_model 2048 puts the FLOPs in large
        # matmuls; this line carries the LM MFU
        lm_line("lm_d2048_tokens_per_sec_flash",
                mfu_key="lm_mfu_vs_empirical_peak_pct",
                flash=True, batch=8, layers=8, d_model=2048, heads=16,
                steps=5, warmup=2)
        if ndev > 1:
            lm_line("lm_tokens_per_sec_seq_parallel_flash_b8",
                    flash=True, batch=8, seq_parallel=True)

    result["autotuned_fusion_threshold_mb"] = autotuned_mb
    result["autotune_slope_window_escalations"] = autotune_escalations
    if autotune_abstained is not None:
        result["autotune_abstained"] = autotune_abstained
    result["flightrec_overhead_ns_per_event"] = round(
        _flightrec_overhead_ns(), 1)
    result["checkpoint"] = _checkpoint_block()
    result["telemetry"] = _telemetry_block()
    _attach_goodput(result)
    emit(result)
    if "goodput_error" in result:
        sys.exit(1)


if __name__ == "__main__":
    main()
