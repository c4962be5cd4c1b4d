"""The quickest proof that the trainer still starts on the chip.

    python chip_smoke.py                # one TPU chip
    python chip_smoke.py --four-chips   # one four-chip host, nothing else

With no arguments it drives the training path once through the entry
points users call, at the full width of two models the repo supports:

* ``hvdrun``        ``hvdrun -np 1 -- python examples/jax_synthetic_benchmark.py``
                    as a child, before this process touches jax: the
                    launcher parent must not hold the chip its worker needs.
* ``flash_kernel``  the Pallas flash kernel's forward and gradients against
                    ``_reference_attention`` at the head shapes of the
                    main path and of latent attention (192 / 128), on the
                    chip (not the interpreter); then the forward alone
                    and forward + backward timed, beside the kernel's
                    ``block_schedule`` (interior / diagonal / skipped pairs).
* ``grouped_kernel`` the expert share's grouped products (megablox under
                    ``models/experts._gmm``'s VJP) against a loop over the
                    groups: result, input and weight gradient, at the
                    SwiGLU cell's sizes and at the relu^2 cell's (a width
                    off the 128 lanes).
* ``state_space_scan`` the state-space mixer's chunked scan
                    (``models/ssm.py``: the Pallas kernels of
                    ``ops/ssm_scan.py`` at these sizes, reported as
                    ``path``) against the recurrence one step at a time,
                    result and gradients, at its benchmark cell's sizes;
                    forward and backward timed beside the plain
                    ``jax.numpy`` form.
* ``delta_scan``    delta attention's chunked scan (``models/kda.py``:
                    the Pallas kernels of ``ops/delta_scan.py`` at these
                    sizes, reported as ``path``) against the delta rule one
                    position at a time, result and gradients, at its
                    benchmark cell's sizes; forward and backward timed
                    beside the plain ``jax.numpy`` form; then the whole
                    mixer forward and backward, timed, with both its
                    device scopes in the compiled text.
* ``lm``            the decoder LM, 8 layers d2048 16 heads, vocab 32000,
                    sequence 2048, batch 8, flash on (``make_lm_bench`` ->
                    ``make_lm_train_step``): loss finite and falling on a
                    repeated batch, ``tpu_custom_call`` in the compiled step.
* ``resnet101``     bf16 compute / f32 params, batch 256 at 224x224,
                    SGD-momentum, donated buffers (``create_train_state`` /
                    ``make_train_step``): loss finite at every step.
* ``serve``         a ``ServeEngine`` built as ``hvd-serve`` builds it, 12
                    layers d768: 512-token prompts, 32 greedy tokens each,
                    against the argmax of an uncached full forward.

``--four-chips`` runs only the data-parallel phase and what it is compared
with: the 12-layer d768 LM on the ``(data=4)`` mesh of ``hvd.init()``
against the same global batch and seed on the first chip alone.

Depth is cut and the weights are random, made from a seed; widths are not
cut. Every phase prints one JSON line. A phase that fails raises: nothing
here turns a failure into a result, a fallback from a Pallas kernel is an
error, and nothing runs unless jax's first device is a TPU. The last line
of standard output is the contract's
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Step times and compile seconds are information for the first benchmark PR,
not claims.
"""

import argparse
import gc
import json
import os
import signal
import subprocess
import sys
import time
import warnings

REPO = os.path.dirname(os.path.abspath(__file__))

# The platform a phase may run on. The script never sets JAX_PLATFORMS.
REQUIRED_PLATFORM = "tpu"

HVDRUN = dict(model="resnet50", batch=32, image=224, warmup=1,
              batches_per_iter=3, iters=1, timeout=600)
# kernel vs reference: the main path's head shapes [B, S, H, D] (a fifth
# number: v and o that wide, q and k at D; latent attention's 192 / 128 at
# the sequence of its benchmark cell; a sixth: a sliding window, the 512
# of laguna-xs.2-train-s8192's sliding layers at their sequence); batch 2,
# or 1 at s4096, or 8 heads at s8192, keeps the plain-XLA reference's
# S x S scores (and their gradients) small beside the kernel's operands
KERNEL_SHAPES = ((2, 2048, 16, 128), (2, 2048, 12, 64),
                 (1, 4096, 32, 192, 128), (1, 8192, 8, 128, 128, 512))
# bf16 operands, fp32 accumulation: a tensor agrees when its error is
# under 2% of the reference in L2 and no element is off by more than
# tests/test_flash_attention.py allows bf16 (5e-2 forward, 8e-2 grads)
KERNEL_REL_L2 = 2e-2
KERNEL_ATOL = dict(out=5e-2, dq=8e-2, dk=8e-2, dv=8e-2)
# the expert share's grouped products at its benchmark cells' sizes: the
# rows of its buffers in expert order (twice the slots the held experts
# expect), the expected slots live, in ragged groups. SwiGLU experts
# (16,384 * 6 slots, an eighth held, 16 groups): gate and up as one
# product [d, 2f], then down [f, d]. relu^2 experts (8,192 * 6 slots, a
# sixteenth held, 8 groups): up [d, f], down [f, d], at a width off the
# 128 lanes (1856 = 14.5 * 128)
GROUPED = (dict(rows=24576, live=12288, groups=16,
                products=((2048, 1536), (768, 2048))),
           dict(rows=6144, live=3072, groups=8,
                products=((2688, 1856), (1856, 2688))))
# the state-space mixer's chunked scan at its benchmark cell's sizes:
# [B, S] positions, H heads of P channels, G groups of N states, chunks of
# 128; against the recurrence one step at a time in float32
SCAN = dict(batch=2, seq_len=4096, heads=64, head_dim=64, groups=8,
            states=128, chunk=128)
SCAN_REL_L2 = 2e-2
# delta attention at its benchmark cell's sizes: [B, S] positions, H heads
# of D channels (q, k and v alike), chunks of 64, a model 2304 wide;
# against the delta rule one position at a time in float32
DELTA = dict(batch=2, seq_len=4096, heads=32, head_dim=128, chunk=64,
             d_model=2304)
LM = dict(layers=8, d_model=2048, heads=16, vocab=32000, seq_len=2048,
          batch=8, steps=4)
RESNET = dict(model="resnet101", batch=256, image=224, steps=3)
SERVE = dict(layers=12, d_model=768, heads=12, vocab=32000, prompts=4,
             prompt_len=512, new_tokens=32,
             # hvd-serve's defaults (serve/cli.py build_parser)
             max_slots=8, prefill_chunk=256, block_size=16,
             max_seq_len=2048)
# a greedy token may differ from the reference argmax only where the
# reference's own logits are closer than two bf16 steps at their scale
SERVE_LOGIT_TOL = 2 * 2.0 ** -5
DP = dict(layers=12, d_model=768, heads=12, vocab=32000, seq_len=2048,
          batch=8, steps=3)
# four chips against one, bf16: the same losses up to reduction order
DP_LOSS_RTOL = 1e-2


# ---- children (only while this process has not touched jax) ------------

def _run_child(cmd, timeout):
    """Run ``cmd`` in its own process group with the repo importable;
    the whole group is killed if it outlives ``timeout``. Returns its
    standard output; a non-zero exit raises with the child's tail."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(cmd, env=env, cwd=REPO, text=True,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.communicate()
        raise RuntimeError(f"{cmd[:4]} ... outlived its {timeout}s limit")
    if proc.returncode != 0:
        raise RuntimeError(
            f"{cmd[:4]} ... exited with {proc.returncode}\n"
            f"--- stdout ---\n{out[-2000:]}\n--- stderr ---\n{err[-4000:]}")
    return out


def _last_json(out):
    return json.loads(out.strip().splitlines()[-1])


def probe_device():
    """What jax finds, asked of a child so that this process does not
    yet hold the chip the hvdrun phase's worker needs."""
    code = ("import json, jax; d = jax.devices(); print(json.dumps("
            "{'platform': d[0].platform, 'kind': d[0].device_kind, "
            "'count': len(d)}))")
    return _last_json(_run_child([sys.executable, "-c", code], 300))


def phase_hvdrun():
    """The normal entry point: launcher parent + one worker. The parent
    imports horovod_tpu (and so jax) but must never start a backend, or
    its worker could not have the chip."""
    a = HVDRUN
    t0 = time.perf_counter()
    out = _run_child(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "1", "--",
         sys.executable, "examples/jax_synthetic_benchmark.py",
         "--model", a["model"], "--batch-size", str(a["batch"]),
         "--image-size", str(a["image"]),
         "--num-warmup-batches", str(a["warmup"]),
         "--num-batches-per-iter", str(a["batches_per_iter"]),
         "--num-iters", str(a["iters"])], a["timeout"])
    line = _last_json(out)
    if line["platform"] != REQUIRED_PLATFORM:
        raise RuntimeError(f"the hvdrun worker ran on {line['platform']!r}, "
                           f"not {REQUIRED_PLATFORM!r}: {line}")
    _check_finite("hvdrun worker loss", [line["final_loss"]])
    print(json.dumps({"phase": "hvdrun", "np": 1, "worker": line,
                      "wall_seconds": round(time.perf_counter() - t0, 1)}),
          flush=True)


# ---- in-process phases -------------------------------------------------

class CompileWatch:
    """Seconds jax spent in backend compiles and the persistent cache's
    hits and misses, over a ``with`` block."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, seconds, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def __enter__(self):
        import jax.monitoring as m
        m.register_event_listener(self._event)
        m.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as m
        m.unregister_event_listener(self._event)
        m.unregister_event_duration_listener(self._duration)

    def fields(self):
        return {"compile_seconds": round(self.seconds, 2),
                "persistent_cache": {"hits": self.hits,
                                     "misses": self.misses,
                                     "hit": self.hits > 0
                                     and self.misses == 0}}


def _device():
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def _emit(phase, **fields):
    import jax

    from horovod_tpu.utils.benchmarks import device_fields

    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({
        "phase": phase, **device_fields(), **fields,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_in_use": stats.get("bytes_in_use"),
        "libtpu_init_args": os.environ.get("LIBTPU_INIT_ARGS", ""),
    }), flush=True)


def _check_finite(what, values):
    import math
    if not all(math.isfinite(v) for v in values):
        raise RuntimeError(f"{what} is not finite: {values}")


def phase_init():
    """What the backend started under: the one installation, the flags
    ``hvd.init()`` handed libtpu (on every line as ``libtpu_init_args``),
    and where and how large the persistent compile cache may grow — a
    capped cache evicts, and a second run then compiles again."""
    import importlib.metadata as md

    import jax

    _emit("init",
          versions={p: md.version(p) for p in
                    ("jax", "jaxlib", "libtpu", "flax", "optax")},
          python=sys.version.split()[0],
          compile_cache_dir=jax.config.jax_compilation_cache_dir,
          compile_cache_max_size=jax.config.jax_compilation_cache_max_size,
          bytes_limit=(jax.devices()[0].memory_stats() or {}).get(
              "bytes_limit"))


def assert_kernel_compiled(text, where):
    """The flash kernel is in the compiled program as a Mosaic custom
    call; interpret mode would have lowered it to plain HLO."""
    if "tpu_custom_call" not in text:
        raise RuntimeError(f"{where}: no tpu_custom_call in the compiled "
                           "program — the flash kernel did not run as a "
                           "kernel")
    return text.count("tpu_custom_call")


def _timed_steps(step_once, n):
    """``n`` calls of ``step_once() -> loss``, each ended by reading the
    loss back. Returns (losses, seconds per call)."""
    losses, seconds = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        losses.append(float(step_once()))
        seconds.append(round(time.perf_counter() - t0, 4))
    return losses, seconds


def _ms_per_call(fn, args, calls=10):
    """Host milliseconds a call of ``fn(*args)``, ``calls`` of them queued
    back to back behind a warm one and ended by one wait."""
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t0) / calls * 1e3, 3)


def phase_flash_kernel():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops import flash_attention as fa

    results = []
    with CompileWatch() as watch:
        for shape in KERNEL_SHAPES:
            b, s, h, d = shape[:4]
            d_v = shape[4] if len(shape) > 4 else d
            window = shape[5] if len(shape) > 5 else None
            forward_blocks, backward_blocks = (
                (fa.FORWARD_BLOCKS, fa.BACKWARD_BLOCKS) if window is None
                else fa.WINDOW_BLOCKS)
            rng = np.random.default_rng(0)
            q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, width)),
                                   jnp.bfloat16) for width in (d, d, d_v))
            w = jnp.asarray(rng.standard_normal(v.shape), jnp.float32)

            # w is an argument, not a closure: a captured array becomes a
            # constant of the executable, and four 30 MB constants push
            # the train steps out of a size-capped compile cache
            def kernel_loss(q, k, v, w):
                out = fa.flash_attention(q, k, v, causal=True, window=window)
                return jnp.sum(out.astype(jnp.float32) * w), out

            def reference_loss(q, k, v, w):
                def to_bh(x):
                    return x.transpose(0, 2, 1, 3).reshape(b * h, s, -1)

                out = fa._reference_attention(
                    to_bh(q), to_bh(k), to_bh(v),
                    jnp.zeros((2,), jnp.int32), True, 1.0 / d ** 0.5,
                    window)
                out = out.reshape(b, h, s, d_v).transpose(0, 2, 1, 3)
                return jnp.sum(out.astype(jnp.float32) * w), out

            grad = lambda f: jax.jit(jax.value_and_grad(  # noqa: E731
                f, argnums=(0, 1, 2), has_aux=True))
            kernel = grad(kernel_loss)
            kernels = assert_kernel_compiled(
                kernel.lower(q, k, v, w).compile().as_text(),
                f"flash kernel fwd+bwd at {shape}")
            (_, out_k), grads_k = kernel(q, k, v, w)
            (_, out_r), grads_r = grad(reference_loss)(q, k, v, w)
            errors = {}
            for name, got, want in zip(("out", "dq", "dk", "dv"),
                                       (out_k,) + grads_k,
                                       (out_r,) + grads_r):
                got = np.asarray(got, np.float32)
                want = np.asarray(want, np.float32)
                rel = float(np.linalg.norm(got - want)
                            / np.linalg.norm(want))
                worst = float(np.max(np.abs(got - want)
                                     / np.maximum(np.abs(want), 1.0)))
                errors[name] = {"rel_l2": round(rel, 5),
                                "max_abs": round(worst, 5)}
                if not (rel <= KERNEL_REL_L2
                        and worst <= KERNEL_ATOL[name]):
                    raise RuntimeError(
                        f"flash kernel {name} at {shape} disagrees with "
                        f"_reference_attention: {errors[name]} (allowed "
                        f"rel_l2 {KERNEL_REL_L2}, max_abs "
                        f"{KERNEL_ATOL[name]})")
            # information, not claims: the forward alone and forward +
            # backward, each with the [B, S, H, D] <-> [BH, S, D] copies
            # around its kernels, and how many block pairs of a
            # batch*head lie under the diagonal, on it, and are skipped
            forward = jax.jit(lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, window=window))
            results.append({"shape": list(shape), "dtype": "bfloat16",
                            "tpu_custom_calls": kernels, "errors": errors,
                            "block_schedule": {
                                "forward": fa.block_schedule(
                                    s, s, *forward_blocks, window=window),
                                "backward": fa.block_schedule(
                                    s, s, *backward_blocks, window=window)},
                            "forward_ms": _ms_per_call(forward, (q, k, v)),
                            "forward_backward_ms": _ms_per_call(
                                kernel, (q, k, v, w))})
    _emit("flash_kernel", shapes=results,
          tolerance={"rel_l2": KERNEL_REL_L2, "max_abs": KERNEL_ATOL},
          **watch.fields())


def phase_grouped_kernel():
    """megablox under ``models/experts._gmm``'s own VJP (a tiling for each
    of its three products, ``transpose_rhs``, ``tgmm`` told the number of
    groups) against a loop over the groups in float32: the result, the
    input gradient and the weight gradient, over the live rows. The rows
    past the groups' end carry numbers like any other, in the operand and
    in the cotangent: the weight gradient must not see them."""
    import numpy as np

    rng = np.random.default_rng(0)
    results = []
    with CompileWatch() as watch:
        for a in GROUPED:
            results += _grouped_case(a, rng)
    _emit("grouped_kernel", products=results,
          tolerance={"rel_l2": KERNEL_REL_L2}, **watch.fields())


def _grouped_case(a, rng):
    """The readings of one entry of ``GROUPED``, a product each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models import experts

    sizes = rng.multinomial(a["live"],
                            rng.dirichlet(np.full(a["groups"], 0.5)))
    sizes[0] += sizes[3]
    sizes[3] = 0  # an expert no token chose
    group_of_row = jnp.asarray(np.repeat(np.arange(a["groups"]), sizes))
    sizes = jnp.asarray(sizes, jnp.int32)

    def kernel(xs, w, g):
        out, vjp = jax.vjp(lambda xs, w: experts._gmm(xs, w, sizes), xs, w)
        return (out,) + vjp(g)

    def loop(xs, w, g):
        live = lambda x: x[:a["live"]].astype(jnp.float32)  # noqa: E731

        def product(xs, w):
            def one(out, group):
                i, w_i = group
                return out + jnp.where((group_of_row == i)[:, None],
                                       xs @ w_i, 0.0), None
            return jax.lax.scan(
                one, jnp.zeros((a["live"], w.shape[2]), jnp.float32),
                (jnp.arange(a["groups"]), w))[0]

        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(product, live(xs), w.astype(jnp.float32))
            return (out,) + vjp(live(g))

    results = []
    for k, n in a["products"]:
        xs = jnp.asarray(rng.standard_normal((a["rows"], k)), jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal((a["groups"], k, n))
                        / k ** 0.5, jnp.bfloat16)
        g = jnp.asarray(rng.standard_normal((a["rows"], n)), jnp.bfloat16)
        compiled = jax.jit(kernel).lower(xs, w, g).compile()
        kernels = assert_kernel_compiled(
            compiled.as_text(), f"grouped product {k} x {n}")
        got, want = compiled(xs, w, g), jax.jit(loop)(xs, w, g)
        errors = {}
        for name, x, y in zip(("out", "d_xs", "d_w"), got, want):
            x = np.asarray(x, np.float32)[:len(y)]
            errors[name] = round(float(
                np.linalg.norm(x - np.asarray(y))
                / np.linalg.norm(y)), 5)
            if not errors[name] <= KERNEL_REL_L2:
                raise RuntimeError(
                    f"grouped product {name} at {a['rows']} x {k} x "
                    f"{n} disagrees with the loop over groups: rel_l2 "
                    f"{errors[name]} (allowed {KERNEL_REL_L2})")
        results.append({"shape": [a["rows"], k, n],
                        "live_rows": a["live"], "groups": a["groups"],
                        "dtype": "bfloat16",
                        "forward_backward_ms": _ms_per_call(compiled,
                                                            (xs, w, g)),
                        "tpu_custom_calls": kernels, "rel_l2": errors})
    return results


def _with_gradients(scan, count):
    """Jitted ``(o, gradients of the first count arguments)`` of
    ``sum(scan(*arguments but the last) * the last)``. The weight is an
    argument: closed over, its 134 MB would be a constant of the
    executable and evict the compile cache."""
    import jax
    import jax.numpy as jnp

    def value(*x):
        o = scan(*x[:-1])
        return jnp.sum(o.astype(jnp.float32) * x[-1]), o

    def run(*x):
        (_, o), grads = jax.value_and_grad(
            value, argnums=tuple(range(count)), has_aux=True)(*x)
        return (o,) + grads
    return jax.jit(run)


def _scan_errors(what, names, got, want):
    """``{name: rel_l2}`` of a chunked scan's tensors against the
    recurrence's; raises past ``SCAN_REL_L2``."""
    import numpy as np

    errors = {}
    for name, x, y in zip(names, got, want):
        x, y = (np.asarray(z, np.float32) for z in (x, y))
        errors[name] = round(float(np.linalg.norm(x - y)
                                   / np.linalg.norm(y)), 5)
        if not errors[name] <= SCAN_REL_L2:
            raise RuntimeError(
                f"{what}: {name} disagrees with the recurrence: rel_l2 "
                f"{errors[name]} (allowed {SCAN_REL_L2})")
    return errors


def _chosen_and_plain_ms(scan, plain, chunked, args, count):
    """Forward and backward milliseconds a layer of the path the shapes
    chose (``chunked``: its jitted value and gradients) beside the plain
    form's, in the same call."""
    import jax

    ms = {}
    for name, fn, with_gradients in (
            ("chosen", scan, chunked),
            ("plain", plain, _with_gradients(plain, count))):
        forward = _ms_per_call(jax.jit(fn), args[:count])
        both = _ms_per_call(with_gradients, args)
        ms[name] = {"forward_ms": forward,
                    "backward_ms": round(both - forward, 3),
                    "forward_backward_ms": both}
    return ms


def phase_state_space_scan():
    """``models/ssm.chunked_scan`` in bfloat16 at the sizes of its
    benchmark cell against the benchmark reference's recurrence, one step
    at a time in float32: the result and the gradients of ``u``, ``B``,
    ``C`` and the step. The steps and decays are drawn as the family
    initialises them, so the slow heads carry a state across every one of
    the 32 chunks. Which path the shapes chose (the Pallas kernels of
    ``ops/ssm_scan.py`` here), and its forward and backward milliseconds
    a layer beside the plain form's in the same call."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import ssm_moe_lm as reference
    from horovod_tpu.models import ssm
    from horovod_tpu.ops import ssm_scan

    a = SCAN
    rng = np.random.default_rng(0)
    shape = (a["batch"], a["seq_len"])
    normal = lambda *tail: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape + tail), jnp.bfloat16)
    u = normal(a["heads"], a["head_dim"])
    b, c = normal(a["groups"], a["states"]), normal(a["groups"], a["states"])
    weight = normal(a["heads"], a["head_dim"]).astype(jnp.float32)
    dt = jnp.asarray(np.exp(rng.uniform(
        np.log(0.001), np.log(0.1), shape + (a["heads"],))), jnp.float32)
    decay = -jnp.asarray(rng.uniform(1.0, 16.0, a["heads"]), jnp.float32)
    skip = jnp.ones((a["heads"],), jnp.float32)

    def recurrence(u, b, c, dt):
        share = a["heads"] // a["groups"]
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
        with jax.default_matmul_precision("highest"):
            return reference._recurrence(
                f32(u), jnp.repeat(f32(b), share, 2),
                jnp.repeat(f32(c), share, 2), dt, dt * decay, skip)

    scan = lambda *x: ssm.chunked_scan(  # noqa: E731
        *x, decay, skip, a["chunk"])
    # the form every size off the lanes takes, recomputed as it runs there
    plain = lambda *x: jax.checkpoint(  # noqa: E731
        ssm._plain_scan, static_argnums=(6,))(*x, decay, skip, a["chunk"])
    path = ("kernel" if ssm_scan.supported(
        a["chunk"], a["head_dim"], a["heads"] // a["groups"], a["states"],
        u.dtype) else "plain")
    with CompileWatch() as watch:
        args = (u, b, c, dt, weight)
        chunked = _with_gradients(scan, 4)
        if path == "kernel":
            assert_kernel_compiled(
                chunked.lower(*args).compile().as_text(),
                "state-space scan")
        errors = _scan_errors(
            "chunked scan", ("o", "d_u", "d_B", "d_C", "d_step"),
            chunked(*args), _with_gradients(recurrence, 4)(*args))
        ms = _chosen_and_plain_ms(scan, plain, chunked, args, 4)
        _emit("state_space_scan", sizes=a, dtype="bfloat16", path=path,
              rel_l2=errors, scan_ms=ms,
              forward_backward_ms=ms["chosen"]["forward_backward_ms"],
              tolerance={"rel_l2": SCAN_REL_L2}, **watch.fields())


def phase_delta_scan():
    """``models/kda.chunked_delta_scan`` in bfloat16 at the sizes of its
    benchmark cell against the benchmark reference's recurrence, one
    position at a time in float32: the result and the gradients of q, k,
    v, the log-decay and beta. Keys and queries are unit vectors and the
    log-decays are drawn as the family initialises them, so the slow
    channels carry a state across every one of the 64 chunks. Which path
    the shapes chose (the Pallas kernels of ``ops/delta_scan.py`` here),
    its forward and backward milliseconds a layer beside the plain
    form's in the same call, then the whole mixer, forward and backward,
    with its two scopes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import kda_moe_lm as reference
    from horovod_tpu.models import kda
    from horovod_tpu.models.transformer import TransformerConfig
    from horovod_tpu.ops import delta_scan
    from horovod_tpu.telemetry import scopes

    a = DELTA
    rng = np.random.default_rng(0)
    shape = (a["batch"], a["seq_len"], a["heads"])
    normal = lambda *tail: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape + tail), jnp.float32)
    unit = lambda x: (x / jnp.linalg.norm(  # noqa: E731
        x, axis=-1, keepdims=True)).astype(jnp.bfloat16)
    q, k = unit(normal(a["head_dim"])), unit(normal(a["head_dim"]))
    v = normal(a["head_dim"]).astype(jnp.bfloat16)
    weight = normal(a["head_dim"])
    g = -jnp.asarray(rng.uniform(1.0, 16.0, (a["heads"], 1)) * np.exp(
        rng.uniform(np.log(0.001), np.log(0.1), shape + (a["head_dim"],))),
        jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 1.0, shape), jnp.float32)

    def recurrence(q, k, v, g, beta):
        with jax.default_matmul_precision("highest"):
            return reference._recurrence(
                *(x.astype(jnp.float32) for x in (q, k, v)), g, beta)

    # the form every size off the lanes takes
    plain = lambda *x: kda._plain_scan(*x, a["chunk"])  # noqa: E731
    scan = lambda *x: kda.chunked_delta_scan(*x, a["chunk"])  # noqa: E731
    path = ("kernel" if delta_scan.supported(
        a["chunk"], a["head_dim"], a["head_dim"], v.dtype) else "plain")
    with CompileWatch() as watch:
        args = (q, k, v, g, beta, weight)
        chunked = _with_gradients(scan, 5)
        if path == "kernel":
            assert_kernel_compiled(
                chunked.lower(*args).compile().as_text(), "delta scan")
        errors = _scan_errors(
            "chunked delta scan",
            ("o", "d_q", "d_k", "d_v", "d_g", "d_beta"),
            chunked(*args), _with_gradients(recurrence, 5)(*args))
        ms = _chosen_and_plain_ms(scan, plain, chunked, args, 5)
        del args
        mixer = kda.DeltaAttention(TransformerConfig(
            d_model=a["d_model"], norm_eps=1e-5, kda=kda.DeltaAttentionConfig(
                num_heads=a["heads"], head_dim=a["head_dim"],
                chunk_size=a["chunk"])))
        x = jnp.asarray(rng.standard_normal(
            shape[:2] + (a["d_model"],)), jnp.bfloat16)
        params = jax.jit(mixer.init)(jax.random.PRNGKey(0), x)["params"]
        step = jax.jit(jax.grad(lambda p, x: jnp.sum(mixer.apply(
            {"params": p}, x).astype(jnp.float32)), argnums=(0, 1)))
        text = step.lower(params, x).compile().as_text()
        for scope in (scopes.KDA, scopes.KDA_SCAN):
            if f"/{scope}/" not in text:
                raise RuntimeError(f"delta attention: no instruction of the "
                                   f"compiled mixer is under {scope!r}")
        _emit("delta_scan", sizes=a, dtype="bfloat16", path=path,
              rel_l2=errors, scan_ms=ms,
              scan_forward_backward_ms=ms["chosen"]["forward_backward_ms"],
              mixer_forward_backward_ms=_ms_per_call(step, (params, x)),
              tolerance={"rel_l2": SCAN_REL_L2}, **watch.fields())


def _lm_run(mesh, a):
    """Build the LM benchmark workload on ``mesh``, compile its step
    ahead of time (timed, cache watched), then take ``a['steps']`` steps
    on one repeated batch through the step a user calls. Returns the
    phase fields plus the objects a caller wants to inspect."""
    import jax.numpy as jnp

    from horovod_tpu.utils.benchmarks import make_lm_bench

    step, state, tokens = make_lm_bench(
        mesh=mesh, seq_axis=None, batch=a["batch"], seq_len=a["seq_len"],
        layers=a["layers"], d_model=a["d_model"], heads=a["heads"],
        vocab=a["vocab"], flash=True, dtype=jnp.bfloat16)
    with CompileWatch() as watch:
        compiled = step.lower(state, tokens).compile()
    text = compiled.as_text()
    kernels = assert_kernel_compiled(text, "LM train step")
    box = [state]

    def once():
        box[0], loss = step(box[0], tokens)
        return loss

    losses, seconds = _timed_steps(once, a["steps"])
    _check_finite("LM loss", losses)
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"LM loss did not fall on a repeated batch: "
                           f"{losses}")
    fields = dict(
        config={k: a[k] for k in ("layers", "d_model", "heads", "vocab",
                                  "seq_len", "batch")},
        dtype="bfloat16", flash_attention=True, tpu_custom_calls=kernels,
        mesh=dict(zip(mesh.axis_names, mesh.devices.shape)),
        losses=[round(x, 4) for x in losses], step_seconds=seconds,
        **watch.fields())
    return fields, box[0], compiled, text, tokens


def phase_lm():
    import jax
    import numpy as np

    # bench.py's and the example's mesh: (data, seq) with seq unused
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                             ("data", "seq"))
    fields = _lm_run(mesh, LM)[0]
    _emit("lm", **fields)


def phase_resnet():
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import training
    from horovod_tpu.utils.benchmarks import make_model, synthetic_batch

    a = RESNET
    model = make_model(a["model"], dtype=jnp.bfloat16)
    tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))
    global_batch = a["batch"] * hvd.num_devices()
    images, labels = synthetic_batch(global_batch, a["image"],
                                     dtype=jnp.bfloat16)
    state = training.create_train_state(model, tx, jax.random.PRNGKey(0),
                                        images[:1])
    step = training.make_train_step(model, tx, donate=True)
    with CompileWatch() as watch:
        step.lower(state, images, labels).compile()
    box = [state]

    def once():
        box[0], loss = step(box[0], images, labels)
        return loss

    losses, seconds = _timed_steps(once, a["steps"])
    _check_finite("ResNet loss", losses)
    param_dtypes = sorted({str(x.dtype) for x in
                           jax.tree_util.tree_leaves(box[0].params)})
    _emit("resnet101", config=dict(a), compute_dtype="bfloat16",
          param_dtypes=param_dtypes, optimizer="sgd(0.01, momentum=0.9)",
          donate=True, losses=[round(x, 4) for x in losses],
          step_seconds=seconds, **watch.fields())


def phase_serve():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models.transformer import (Transformer,
                                                TransformerConfig)
    from horovod_tpu.parallel import mesh as mesh_lib
    from horovod_tpu.serve import engine as engine_lib
    from horovod_tpu.serve import kvcache

    a = SERVE
    # hvd-serve's construction (serve/cli.py main), weights from a seed
    # where it loads a checkpoint
    cfg = TransformerConfig(
        vocab_size=a["vocab"], num_layers=a["layers"],
        num_heads=a["heads"], d_model=a["d_model"],
        d_ff=4 * a["d_model"], dtype=jnp.bfloat16, causal=True)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    mbps = -(-a["max_seq_len"] // a["block_size"])
    kv = kvcache.KVCacheConfig(
        num_blocks=a["max_slots"] * mbps + 1, block_size=a["block_size"],
        num_layers=a["layers"], num_heads=a["heads"],
        head_dim=a["d_model"] // a["heads"], max_blocks_per_seq=mbps,
        dtype=jnp.bfloat16)
    eng = engine_lib.ServeEngine(
        model, params, kv, mesh=mesh_lib.build_mesh(jax.devices()),
        max_slots=a["max_slots"], prefill_chunk=a["prefill_chunk"])
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, a["vocab"],
                           size=(a["prompts"], a["prompt_len"]))
    t0 = time.perf_counter()
    with CompileWatch() as watch:
        eng.start()
        try:
            requests = [eng.generate(p, a["new_tokens"]) for p in prompts]
            generated = np.asarray([r.result(timeout=900)
                                    for r in requests])
        finally:
            eng.stop()
    wall = time.perf_counter() - t0
    if generated.shape != (a["prompts"], a["new_tokens"]):
        raise RuntimeError(f"serve returned {generated.shape} tokens")

    # one uncached full forward over prompt ++ the engine's own tokens:
    # position prompt_len-1+i must choose token i
    full = np.concatenate([prompts, generated[:, :-1]], axis=1)
    first = a["prompt_len"] - 1
    logits = np.asarray(jax.jit(
        lambda p, t: model.apply({"params": p}, t)[:, first:, :])(
            params, jnp.asarray(full, jnp.int32)))
    chosen = np.take_along_axis(logits, generated[..., None], -1)[..., 0]
    shortfall = logits.max(-1) - chosen
    exact = int((logits.argmax(-1) == generated).sum())
    if shortfall.max() > SERVE_LOGIT_TOL:
        raise RuntimeError(
            f"serve: a greedy token scores {shortfall.max():.4f} under "
            f"the uncached forward's best (allowed {SERVE_LOGIT_TOL})")
    _emit("serve", config=dict(a), dtype="bfloat16",
          tokens_generated=int(generated.size),
          argmax_exact=exact, argmax_within_tolerance=int(generated.size),
          worst_logit_shortfall=round(float(shortfall.max()), 5),
          logit_tolerance=SERVE_LOGIT_TOL, wall_seconds=round(wall, 2),
          **watch.fields())


def phase_data_parallel():
    """The path across chips: the same LM step on the four-chip mesh of
    ``hvd.init()`` and on the first chip alone."""
    import jax
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.parallel import gspmd

    mesh4 = hvd.mesh()
    n = mesh4.devices.size
    fields4, state4, compiled4, text4, tokens = _lm_run(mesh4, DP)

    def devices_of(x):
        return sorted(s.device.id for s in x.addressable_shards)

    # the batch as the compiled step takes it, and the state it returns
    token_sharding = compiled4.input_shardings[0][-1]
    batch_shard = token_sharding.shard_shape(tokens.shape)
    batch_devices = sorted(d.id for d in token_sharding.device_set)
    leaf_devices = {tuple(devices_of(x))
                    for x in jax.tree_util.tree_leaves(state4)}
    if (len(batch_devices) != n or batch_shard[0] * n != tokens.shape[0]
            or leaf_devices != {tuple(batch_devices)}):
        raise RuntimeError(
            f"not spread over {n} devices: batch shard {batch_shard} on "
            f"{batch_devices}, state leaves on {sorted(leaf_devices)}")
    by_axis = gspmd.collective_axis_bytes_from_hlo(text4, mesh4)
    if not by_axis.get("data", {}).get("bytes"):
        raise RuntimeError(f"no collective over the {n}-way data axis in "
                           f"the compiled step: {by_axis}")
    _emit("data_parallel_4", **fields4, batch_shard_shape=list(batch_shard),
          batch_devices=batch_devices,
          state_leaf_devices=sorted(map(list, leaf_devices)),
          zero_rows="not sharded by this step (plain DistributedOptimizer)",
          collectives_by_axis=by_axis)
    del state4, compiled4
    gc.collect()

    mesh1 = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    fields1 = _lm_run(mesh1, DP)[0]
    _emit("data_parallel_1", **fields1)
    worst = max(abs(x - y) / abs(y) for x, y in
                zip(fields4["losses"], fields1["losses"]))
    if worst > DP_LOSS_RTOL:
        raise RuntimeError(
            f"four chips and one disagree: {fields4['losses']} against "
            f"{fields1['losses']} (worst {worst:.4f}, allowed "
            f"{DP_LOSS_RTOL})")
    print(json.dumps({"phase": "data_parallel_agreement",
                      "losses_4": fields4["losses"],
                      "losses_1": fields1["losses"],
                      "worst_relative_difference": round(worst, 6),
                      "tolerance": DP_LOSS_RTOL}), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data-parallel LM phase on a "
                         "four-chip host and its one-chip comparison")
    args = ap.parse_args()

    found = probe_device()
    if found["platform"] != REQUIRED_PLATFORM:
        sys.exit(f"chip_smoke: needs a {REQUIRED_PLATFORM.upper()} and jax "
                 f"found platform {found['platform']!r} "
                 f"({found['count']} x {found['kind']}); no phase was run")
    if args.four_chips and found["count"] != 4:
        sys.exit(f"chip_smoke --four-chips: needs four chips, jax found "
                 f"{found['count']}; no phase was run")

    if not args.four_chips:
        phase_hvdrun()  # a child: must come before this process has jax

    import horovod_tpu as hvd
    from horovod_tpu.models.experts import GroupedFallbackWarning
    from horovod_tpu.ops.flash_attention import FlashFallbackWarning

    # asked for a kernel and got plain XLA: an error in every phase
    warnings.simplefilter("error", FlashFallbackWarning)
    warnings.simplefilter("error", GroupedFallbackWarning)
    hvd.init()  # first backend touch: libtpu starts under its flags
    device = _device()
    if device != found:
        sys.exit(f"chip_smoke: this process sees {device}, the probe saw "
                 f"{found}")
    phases = ([phase_init, phase_data_parallel] if args.four_chips else
              [phase_init, phase_flash_kernel, phase_grouped_kernel,
               phase_state_space_scan, phase_delta_scan, phase_lm,
               phase_resnet, phase_serve])
    for phase in phases:
        phase()
        gc.collect()  # the next phase needs the device memory back
    hvd.shutdown()
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
