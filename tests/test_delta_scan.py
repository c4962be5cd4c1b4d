"""The delta scan's Pallas kernels (``horovod_tpu/ops/delta_scan.py``) in
interpret mode on the CPU, at lane-wide sizes kept small: two heads of 128
channels, chunks of 16 and 64. Against the recurrence one position at a
time (``tests/reference_kda_moe_lm._recurrence``) and against the plain
``jax.numpy`` form the kernels stand in for (``models/kda._plain_scan``),
result and all five gradients. That the chip's compiler takes the kernels,
and the names its instructions carry, is ``tests/test_chip_compile.py``'s.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import kda as kda_lib
from horovod_tpu.ops import delta_scan
from test_kda_moe_lm import _recurrence, _scan_inputs

NAMES = ("q", "k", "v", "g", "beta")
# unit q and k, a log-decay a channel of up to 0.1 a position: the fastest
# channels forget within a few dozen positions, the slowest carry the state
# across every chunk
_inputs = functools.partial(_scan_inputs, heads=2, d=128, batch=1,
                            decay=(0.0, 0.1))


def _with_gradients(scan, weight):
    """Jitted ``(o, the five gradients)`` of ``sum(scan(...) * weight)``."""
    def value(*x):
        o = scan(*x)
        return jnp.sum(o.astype(jnp.float32) * weight), o

    def run(*x):
        (_, o), grads = jax.value_and_grad(
            value, argnums=range(5), has_aux=True)(*x)
        return (o,) + grads
    return jax.jit(run)


def _close(got, want, rtol, what):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want),
        atol=rtol * max(1e-2, float(jnp.abs(want).max())), err_msg=what)


@pytest.mark.parametrize("chunk,chunks,batch", [(16, 2, 2), (16, 4, 1),
                                                (64, 2, 1), (64, 3, 2)],
                         ids=lambda x: str(x))
def test_kernels_equal_the_recurrence_and_the_plain_form(rng, chunk, chunks,
                                                         batch):
    """``o`` and the gradients of q, k, v, g and beta, float32: the
    kernels, the plain form and the recurrence agree to float32's rounding,
    at one diagonal block pair a chunk (16) and at the published 64, over
    two to four chunks."""
    args = _inputs(rng, chunk * chunks, batch=batch)
    weight = jnp.asarray(rng.standard_normal(args[2].shape), jnp.float32)
    got = _with_gradients(
        lambda *x: delta_scan.delta_scan(*x, chunk), weight)(*args)
    want = _with_gradients(_recurrence, weight)(*args)
    plain = _with_gradients(
        lambda *x: kda_lib._plain_scan(*x, chunk), weight)(*args)
    for name, x, y, z in zip(("o",) + NAMES, got, want, plain):
        assert x.shape == y.shape and x.dtype == y.dtype, name
        _close(x, y, 2e-5, f"{name} against the recurrence")
        _close(x, z, 2e-5, f"{name} against the plain form")


@pytest.mark.parametrize("chunk", [16, 64])
def test_the_state_crosses_every_chunk_boundary(rng, chunk):
    """Channels whose state lives far longer than a chunk: the same scan
    over each chunk alone, its state not carried, differs by far more than
    the tolerance, in the result and in what the first chunk's k is owed by
    the later chunks' results."""
    args = _inputs(rng, 3 * chunk, decay=(0.0, 0.02))
    scan = lambda *x: delta_scan.delta_scan(*x, chunk)  # noqa: E731
    want = _recurrence(*args)
    _close(scan(*args), want, 2e-5, "o")
    alone = jnp.concatenate([
        scan(*(x[:, i * chunk:(i + 1) * chunk] for x in args))
        for i in range(3)], 1)
    assert float(jnp.abs(alone - want)[:, chunk:].max()) > 0.05
    # the backward kernel hands dS back across the boundary: the last
    # chunk's result alone reaches the first chunk's inputs
    later = lambda fn: jax.grad(lambda *x: jnp.sum(jnp.square(  # noqa: E731
        fn(*x)[:, 2 * chunk:])), argnums=(1, 3))(*args)
    for got, owed in zip(later(scan), later(_recurrence)):
        assert float(jnp.abs(owed[:, :chunk]).max()) > 0.05
        _close(got, owed, 5e-5, "what the first chunk is owed")


@pytest.mark.parametrize("decay,what", [((0.0, 1e-3), "near_none"),
                                        ((30.0, 30.0), "all_of_it")],
                         ids=lambda x: x if isinstance(x, str) else "")
def test_kernels_at_the_ends_of_the_decay(rng, decay, what):
    """g near 0: nothing is forgotten and every pair of a chunk matters.
    g at -30 a position: the running sum reaches -1920 inside a chunk of
    64, ``exp(G_t) * exp(-G_i)`` would be ``0 * inf``, the state is decayed
    to nothing and a position reads what it wrote itself. No ``inf``, no
    ``nan``, value and gradient, and still the recurrence."""
    args = _inputs(rng, 128, decay=decay)
    loss = lambda fn: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda *x: jnp.sum(jnp.square(fn(*x))), argnums=range(5)))(*args)
    got, grads = loss(lambda *x: delta_scan.delta_scan(*x, 64))
    want, want_grads = loss(_recurrence)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-4)
    for name, g, w in zip(NAMES, grads, want_grads):
        assert bool(jnp.isfinite(g).all()), name
        _close(g, w, 1e-3, name)
    if what == "all_of_it":
        q, k, v, _, beta = args
        own = (beta[..., None] * v
               * jnp.sum(q * k, -1, keepdims=True))  # S_t = beta k v^T
        np.testing.assert_allclose(
            np.asarray(delta_scan.delta_scan(*args, 64)), np.asarray(own),
            atol=1e-6)


def test_bfloat16_operands_keep_float32_statistics(rng):
    """bfloat16 q, k, v: ``o`` and the gradients come back in bfloat16 (g's
    and beta's in float32) within bfloat16's rounding of the float32
    recurrence, as the plain form's do: the running sum of g, the inverse
    and the carried state are float32 whatever the operands."""
    q, k, v, g, beta = _inputs(rng, 128, decay=(0.0, 0.3))
    weight = jnp.asarray(rng.standard_normal(v.shape), jnp.float32)
    half = tuple(x.astype(jnp.bfloat16) for x in (q, k, v)) + (g, beta)
    want = _with_gradients(_recurrence, weight)(q, k, v, g, beta)
    error = lambda x, y: float(  # noqa: E731
        jnp.linalg.norm(x.astype(jnp.float32) - y) / jnp.linalg.norm(y))
    for scan in (delta_scan.delta_scan, kda_lib._plain_scan):
        got = _with_gradients(lambda *x: scan(*x, 64), weight)(*half)
        for name, x, y in zip(("o",) + NAMES, got, want):
            assert x.dtype == (jnp.float32 if name in ("g", "beta")
                               else jnp.bfloat16), name
            assert error(x, y) < 2e-2, (scan.__name__, name, error(x, y))


def test_the_shape_chooses_the_path(rng, monkeypatch):
    """``chunked_delta_scan`` sends channels that fill the 128 lanes
    through the kernels and every other size (the small cells' ``d = 8``)
    through the plain form; nothing but the shape is read."""
    taken = []
    kernels, plain = delta_scan.delta_scan, kda_lib._plain_scan
    monkeypatch.setattr(delta_scan, "delta_scan", lambda *a, **kw: (
        taken.append("kernels"), kernels(*a, **kw))[1])
    monkeypatch.setattr(kda_lib, "_plain_scan", lambda *a: (
        taken.append("plain"), plain(*a))[1])
    for d, chunk, dtype, want in ((8, 16, jnp.float32, "plain"),
                                  (128, 16, jnp.float32, "kernels"),
                                  (128, 8, jnp.float32, "kernels"),
                                  # a bfloat16 tile is 16 rows
                                  (128, 8, jnp.bfloat16, "plain"),
                                  (128, 12, jnp.float32, "plain"),
                                  (192, 16, jnp.float32, "plain")):
        q, k, v, g, beta = _inputs(rng, 2 * chunk, heads=1, d=d)
        del taken[:]
        o = kda_lib.chunked_delta_scan(
            *(x.astype(dtype) for x in (q, k, v)), g, beta, chunk)
        assert taken == [want], (d, chunk, dtype)
        _close(o, _recurrence(q, k, v, g, beta),
               2e-5 if dtype == jnp.float32 else 3e-2, (d, chunk))
    assert delta_scan.supported(64, 128, 128, jnp.bfloat16)  # the cell's


def test_the_kernels_refuse_what_they_are_not_built_for(rng, monkeypatch):
    args = _inputs(rng, 32, d=8)
    with pytest.raises(ValueError, match="128 divides"):
        delta_scan.delta_scan(*args, 16)
    args = _inputs(rng, 48)
    with pytest.raises(ValueError, match="divides the sequence"):
        delta_scan.delta_scan(*args, 32)
    # in a process whose devices are TPUs the kernels are never
    # interpreted, as the flash kernel's are not
    monkeypatch.setattr(jax, "devices", lambda *a: [
        types.SimpleNamespace(platform="tpu")])
    with pytest.raises(ValueError, match="not interpreted"):
        delta_scan.delta_scan(*args, 16, interpret=True)
