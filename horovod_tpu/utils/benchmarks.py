"""Shared scaffold for the repo's benchmark scripts (bench.py,
bench_scaling.py): model registry, synthetic batch synthesis, and the
warmup + timed-loop throughput measurement (reference pattern:
``examples/pytorch_synthetic_benchmark.py:95-115``). One copy, so dtype
and donation semantics cannot drift between scripts."""

import time

import jax
import jax.numpy as jnp
import numpy as np


def model_registry():
    from horovod_tpu import models
    return {"resnet18": models.ResNet18, "resnet50": models.ResNet50,
            "resnet101": models.ResNet101, "vgg16": models.VGG16}


def compute_dtype():
    """bf16 on TPU (MXU-native), f32 elsewhere (emulated bf16 on CPU is
    slow and proves nothing). The one place a benchmark's dtype follows
    the platform; every result line says which platform that was
    (:func:`device_fields`), and a path that must not follow it passes
    ``dtype=`` explicitly."""
    return (jnp.bfloat16 if jax.devices()[0].platform == "tpu"
            else jnp.float32)


def device_fields():
    """The device a result was taken on, as jax reports it — merged into
    every JSON line the benchmark scripts print, so a CPU run can never
    be read as a chip run."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def make_model(name, dtype=None, num_classes=1000):
    dtype = dtype if dtype is not None else compute_dtype()
    return model_registry()[name](num_classes=num_classes, dtype=dtype)


def synthetic_batch(global_batch, image_size, dtype=None, num_classes=1000,
                    seed=0):
    dtype = dtype if dtype is not None else compute_dtype()
    rng = np.random.default_rng(seed)
    images = jnp.asarray(rng.standard_normal(
        (global_batch, image_size, image_size, 3)), dtype)
    labels = jnp.asarray(rng.integers(0, num_classes,
                                      size=(global_batch,)), jnp.int32)
    return images, labels


def cost_analysis_dict(compiled):
    """``compiled.cost_analysis()`` normalized across jax versions (some
    return the per-device dict, some a 1-list of it) — the ONE copy;
    bench.py and bench_roofline.py both read flops/bytes through it so
    a version that returns the list form cannot zero one script's MFU
    while the other reports correctly."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost or {}


def sync(x):
    """End a timing window by reading ONE element back to the host: a
    host readback cannot complete before the value exists. The element
    is sliced on-device first so the readback moves 2-4 bytes —
    transferring a whole buffer would add a size-dependent,
    cold/warm-varying cost that poisons slope timing.
    """
    leaf = jax.tree_util.tree_leaves(x)[0]
    return float(jnp.ravel(leaf)[0])


class WindowTime(float):
    """A ``slope_window`` duration. ``upper_bound`` is True when the
    inverted-window fallback reported the FULL window time (fixed costs
    included) instead of a slope difference — a conservative bound, not
    a measurement. ``asymmetric`` is True when the per-iteration rates
    implied by the two window segments disagreed beyond tolerance — a
    fixed cost attached itself to SOME window lengths but not others, so
    the slope may be deflated/inflated rather than clean. Callers that
    publish medians can count either flag so suspect samples are
    distinguishable in the reported runs."""

    upper_bound = False
    asymmetric = False

    def __new__(cls, value, upper_bound=False, asymmetric=False):
        obj = super().__new__(cls, value)
        obj.upper_bound = upper_bound
        obj.asymmetric = asymmetric
        return obj


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    mid = n // 2
    return xs[mid] if n % 2 else 0.5 * (xs[mid - 1] + xs[mid])


def slope_window(step_once, state, iters, base_iters=2, rounds=3,
                 rate_tolerance=0.5):
    """THE timing primitive (one copy — every bench path uses it).

    Times ``iters`` iterations by the slope method, hardened with
    interleaved windows: each of ``rounds`` rounds runs a *base* window
    (``base_iters`` iterations), a *mid* window (``base_iters + h``,
    ``h = iters // 2``) and a *full* window (``base_iters + iters``),
    each terminated by a forced readback (``sync``). Every
    (shorter, longer) window pair within a round yields a pairwise
    per-iteration slope; the reported duration is the MEDIAN pairwise
    slope times ``iters``. The readback guarantees real completion and
    its cost — like every other fixed dispatch cost — cancels in each
    difference; the median across interleaved rounds keeps any one
    polluted window (GC pause, CI neighbor, async residue draining
    late) from owning the result the way a single base/full pair would.

    Asymmetric fixed-cost detection: with three window lengths the
    per-iteration rate is implied twice over disjoint segments —
    ``(t_mid - t_base) / h`` and ``(t_full - t_mid) / (iters - h)``. A
    fixed cost that cancels symmetrically leaves the two medians equal;
    one that attaches to some window lengths only (partial constant
    folding, length-dependent re-dispatch) deflates one segment and
    inflates the other. When the medians disagree by more than
    ``rate_tolerance`` x the overall rate (and by a material absolute
    amount — clock granularity on near-zero work does not count), the
    result is flagged
    ``asymmetric`` (and a warning names the two rates) — the sample is
    still the best available estimate, but it is not a clean slope.

    ``step_once(state) -> (state, syncable)`` advances ONE iteration and
    must thread state so no two calls see identical inputs.
    Returns ``(dt_for_iters, state)``; the duration is a ``WindowTime``
    whose ``upper_bound``/``asymmetric`` flags mark the fallback and
    suspect cases.

    Before the timed windows, ONE untimed flush iteration runs and is
    synced: any one-time cost left pending by earlier work in the
    process (deferred autotune/warm-up executables still draining, a
    first-touch compile) would land in the first short window and
    DEFLATE its slopes while passing as a clean measurement. The flush
    pins that residue outside every timed window.
    """
    import warnings

    def window(k, st):
        out = None
        t0 = time.perf_counter()
        for _ in range(k):
            st, out = step_once(st)
        sync(out)
        return time.perf_counter() - t0, st

    h = iters // 2
    lengths = ([base_iters, base_iters + h, base_iters + iters]
               if 0 < h < iters else [base_iters, base_iters + iters])

    def measure(st):
        slopes, seg_lo, seg_hi, fulls = [], [], [], []
        for _ in range(max(1, rounds)):
            times = []
            for k in lengths:
                t, st = window(k, st)
                times.append(t)
            fulls.append(times[-1])
            for i in range(len(lengths)):
                for j in range(i + 1, len(lengths)):
                    slopes.append((times[j] - times[i])
                                  / (lengths[j] - lengths[i]))
            if len(lengths) == 3:
                seg_lo.append((times[1] - times[0]) / h)
                seg_hi.append((times[2] - times[1]) / (iters - h))
        return slopes, seg_lo, seg_hi, fulls, st

    _, state = window(1, state)  # untimed flush: absorb one-time residue
    slopes, seg_lo, seg_hi, fulls, state = measure(state)
    per_iter = _median(slopes)
    if per_iter <= 0:
        # jitter inversion (fixed-cost noise exceeded the work): retry
        # one full interleaved set, then fall back to the median FULL
        # window time — an upper bound including fixed costs, so the
        # published rate can only be conservative. (Clamping the slope
        # would publish an absurd multi-billion-rate sample; raising
        # would turn tiny smoke runs on loaded CI machines into flaky
        # failures.)
        slopes, seg_lo, seg_hi, fulls, state = measure(state)
        per_iter = _median(slopes)
        if per_iter <= 0:
            bound = _median(fulls)
            warnings.warn(
                f"slope window inverted twice (median pairwise slope "
                f"{per_iter:.6f}s/iter over {iters} iters); reporting "
                f"the full-window upper bound — increase iters for a "
                f"real measurement", stacklevel=2)
            return WindowTime(bound, upper_bound=True), state
    asymmetric = False
    if seg_lo and seg_hi:
        lo, hi = _median(seg_lo), _median(seg_hi)
        # relative disagreement AND a material absolute amount (clock
        # granularity on near-zero work is not an asymmetric fixed cost)
        if (abs(hi - lo) > rate_tolerance * max(per_iter, 1e-12)
                and abs(hi - lo) * iters > 1e-4):
            asymmetric = True
            warnings.warn(
                f"slope window segments imply different per-iteration "
                f"rates ({lo:.6f}s vs {hi:.6f}s per iter, median "
                f"{per_iter:.6f}s): a fixed cost is attaching "
                f"asymmetrically to window lengths; treat this sample "
                f"as suspect", stacklevel=2)
    return WindowTime(per_iter * iters, asymmetric=asymmetric), state


def repeat_step_windows(step_once, state, warmup, iters, repeats,
                        base_iters=2):
    """THE warm-then-measure discipline, step-shape-agnostic: ``warmup``
    synced calls (covers compilation; later windows are warm by
    construction), then ``repeats`` slope windows over the continuously
    evolving state (donation-safe — consumed once, threaded through).
    ``step_once(state) -> (state, syncable)``. Returns
    ``(list[WindowTime], state)`` — the ``upper_bound``/``asymmetric``
    flags ride along, so every caller can tell measurements from
    inverted-window bounds. One copy: ``repeat_throughput`` (the
    (images, labels) classification shape), bench.py's LM comparison
    and bench_roofline's LM roofline all delegate here, so the timing
    discipline cannot drift between scripts."""
    for _ in range(warmup):
        state, out = step_once(state)
        sync(out)
    runs = []
    for _ in range(repeats):
        dt, state = slope_window(step_once, state, iters,
                                 base_iters=base_iters)
        runs.append(dt)
    return runs, state


def repeat_throughput(step, state, images, labels, warmup, iters,
                      repeats, base_iters=2):
    """``repeats`` slope-timed windows of a ``step(state, images,
    labels)`` classification step, returning a list of
    ``(img_per_sec, dt)`` where ``dt`` is a ``WindowTime`` — check its
    ``upper_bound`` flag to tell slope measurements from inverted-window
    conservative bounds. The (images, labels) view of
    :func:`repeat_step_windows`."""
    dts, _ = repeat_step_windows(
        lambda st: step(st, images, labels), state, warmup, iters,
        repeats, base_iters=base_iters)
    return [(images.shape[0] * iters / dt, dt) for dt in dts]


def timed_throughput(step, state, images, labels, warmup, iters):
    """img/s of ``step`` over one slope-timed window (readback-
    terminated base + full windows, difference reported — see
    ``slope_window``). The single-window view of ``repeat_throughput``
    so the timing discipline has exactly one copy."""
    return repeat_throughput(step, state, images, labels, warmup, iters,
                             repeats=1)[0]


def make_lm_bench(*, mesh, seq_axis, batch, seq_len, layers, d_model,
                 heads, vocab, flash, dtype=None, lr=3e-4, spmd=False,
                 compression=None):
    """Build the LM benchmark workload ONE way — ``bench.py`` and
    ``examples/jax_lm_benchmark.py`` share it so their numbers describe
    the same program: exact sharded LM loss through
    ``DistributedOptimizer`` on a (data, seq) mesh. Returns
    ``(step, state, tokens)``; ``flash=None`` means the auto default.
    ``spmd=True`` builds the GSPMD LM step (``make_lm_train_step(
    spmd=True)`` — batch sharding only) and ``compression`` the wire
    format, so ``bench.py --spmd`` runs the same workload through every
    exchange variant."""
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import training
    from horovod_tpu.models.transformer import (Transformer,
                                                TransformerConfig)

    if dtype is None:
        dtype = compute_dtype()
    cfg = TransformerConfig(vocab_size=vocab, num_layers=layers,
                            num_heads=heads, d_model=d_model,
                            d_ff=4 * d_model, dtype=dtype,
                            sequence_axis=seq_axis,
                            flash_attention=flash)
    # init single-device (no seq sharding, no kernel) so params exist
    # before the sharded step compiles — same trick both callers used
    init_cfg = TransformerConfig(**{**cfg.__dict__, "sequence_axis": None,
                                    "flash_attention": False})
    tx = hvd.DistributedOptimizer(
        optax.adamw(lr), axes=("data", "seq") if seq_axis else ("data",),
        compression=compression)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, vocab, size=(batch, seq_len)),
                         jnp.int32)
    state = training.create_train_state(Transformer(init_cfg), tx,
                                        jax.random.PRNGKey(0), tokens[:1])
    step = training.make_lm_train_step(Transformer(cfg), tx, mesh=mesh,
                                       batch_axis="data",
                                       seq_axis=seq_axis, spmd=spmd)
    return step, state, tokens
