"""The state-space scan's Pallas kernels (``horovod_tpu/ops/ssm_scan.py``)
in interpret mode on the CPU, at lane-wide sizes kept small: two groups of
two heads of 64 channels (one 128-lane slab a group), 128 states, chunks
of 16 and 32. Against the recurrence one step at a time
(``tests/reference_ssm_moe_lm._recurrence``) and against the plain
``jax.numpy`` form the kernels stand in for (``models/ssm._plain_scan``),
result and all six gradients. That the chip's compiler takes the kernels,
and the names its instructions carry, is ``tests/test_chip_compile.py``'s.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import ssm as ssm_lib
from horovod_tpu.ops import ssm_scan
from test_ssm_moe_lm import _recurrence, _scan_inputs

NAMES = ("u", "B", "C", "dt", "A", "D")
# steps of 0.05 to 1.5 and A from 0.02 to 4: the fastest heads forget within
# a few positions, the slowest carry a state across every chunk
_inputs = functools.partial(_scan_inputs, heads=4, groups=2, p=64, n=128,
                            batch=1)


def _with_gradients(scan, weight):
    """Jitted ``(o, the six gradients)`` of ``sum(scan(...) * weight)``."""
    def value(*x):
        o = scan(*x)
        return jnp.sum(o.astype(jnp.float32) * weight), o

    def run(*x):
        (_, o), grads = jax.value_and_grad(
            value, argnums=range(6), has_aux=True)(*x)
        return (o,) + grads
    return jax.jit(run)


def _close(got, want, rtol, what):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want),
        atol=rtol * max(1e-2, float(jnp.abs(want).max())), err_msg=what)


@pytest.mark.parametrize("chunk,chunks,batch", [(16, 2, 2), (16, 3, 1),
                                                (32, 2, 1), (32, 8, 2)],
                         ids=lambda x: str(x))
def test_kernels_equal_the_recurrence_and_the_plain_form(rng, chunk, chunks,
                                                         batch):
    """``o`` and the gradients of u, B, C, the step, A and D, float32: the
    kernels, the plain form and the recurrence agree to float32's rounding,
    over two to eight chunks: two, one and four chunks to a grid step, and
    two grid steps of four."""
    args = _inputs(rng, chunk * chunks, batch=batch)
    weight = jnp.asarray(rng.standard_normal(args[0].shape), jnp.float32)
    got = _with_gradients(
        lambda *x: ssm_scan.ssm_scan(*x, chunk), weight)(*args)
    want = _with_gradients(_recurrence, weight)(*args)
    plain = _with_gradients(
        lambda *x: ssm_lib._plain_scan(*x, chunk), weight)(*args)
    for name, x, y, z in zip(("o",) + NAMES, got, want, plain):
        assert x.shape == y.shape and x.dtype == y.dtype, name
        _close(x, y, 2e-5, f"{name} against the recurrence")
        _close(x, z, 2e-5, f"{name} against the plain form")


@pytest.mark.parametrize("p,heads,groups", [(128, 2, 2), (32, 8, 1),
                                            (64, 8, 2)],
                         ids=["a_slab_a_head", "four_heads_a_slab",
                              "two_slabs_a_group"])
def test_kernels_at_other_shares_of_a_slab(rng, p, heads, groups):
    """A head the whole slab, a quarter of it, and a group of two slabs:
    each head reads its own group, decays at its own rate and writes its
    own lanes."""
    args = _scan_inputs(rng, 32, heads=heads, groups=groups, p=p, n=128,
                        batch=1)
    weight = jnp.asarray(rng.standard_normal(args[0].shape), jnp.float32)
    got = _with_gradients(
        lambda *x: ssm_scan.ssm_scan(*x, 16), weight)(*args)
    want = _with_gradients(_recurrence, weight)(*args)
    for name, x, y in zip(("o",) + NAMES, got, want):
        _close(x, y, 2e-5, name)


@pytest.mark.parametrize("chunk", [16, 32])
def test_the_state_crosses_every_chunk_boundary(rng, chunk):
    """An impulse in chunk 0 read in the last chunk: ``u`` is zero but at
    the first position, so everything later positions see came through the
    carried state; and the last chunk's result alone reaches the first
    chunk's inputs through the ``dS`` the backward kernel hands back."""
    u, b, c, dt, a, d = _inputs(rng, 4 * chunk)
    dt = dt * 0.02                                   # slow heads
    u = u.at[:, 1:].set(0.0)
    args = (u, b, c, dt, a, jnp.zeros_like(d))
    scan = lambda *x: ssm_scan.ssm_scan(*x, chunk)  # noqa: E731
    want = _recurrence(*args)
    got = scan(*args)
    assert float(jnp.abs(want[:, 3 * chunk:]).max()) > 0.05
    _close(got, want, 2e-5, "o")
    later = lambda fn: jax.grad(lambda *x: jnp.sum(jnp.square(  # noqa: E731
        fn(*x)[:, 3 * chunk:])), argnums=(0, 1, 3))(*args)
    for got, owed in zip(later(scan), later(_recurrence)):
        assert float(jnp.abs(owed[:, :chunk]).max()) > 0.05
        _close(got, owed, 5e-5, "what the first chunk is owed")


@pytest.mark.parametrize("step,a,what", [(1e-4, 1.0, "the_floor"),
                                         (0.1, 16.0, "the_fastest"),
                                         (8.0, 16.0, "all_of_it")])
def test_kernels_at_the_ends_of_the_decay(rng, step, a, what):
    """The step at ``time_step_floor``: nothing is forgotten and every pair
    of a chunk matters. ``A`` at -16 with the step at 0.1: the running sum
    reaches -51 inside a chunk of 32. And a step of 8: -4096, ``exp(G_t) *
    exp(-G_i)`` would be ``0 * inf``, the state is decayed to nothing and a
    position reads what it wrote itself. No ``inf``, no ``nan``, value and
    gradient, and still the recurrence. (The kernels read the log-decay's
    gradient as ``<do, o - D u> - <x, dx>``, two sums that cancel where
    nothing outlives its own position: ``A``'s gradient, which adds them up
    over every position, is held to float32's rounding of what cancels.)"""
    chunk = 32
    u, b, c, dt, _, d = _inputs(rng, 64)
    args = (u, b, c, jnp.full_like(dt, step),
            jnp.full((4,), -a, jnp.float32), d)
    loss = lambda fn: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda *x: jnp.sum(jnp.square(fn(*x))), argnums=range(6)))(*args)
    got, grads = loss(lambda *x: ssm_scan.ssm_scan(*x, chunk))
    want, want_grads = loss(_recurrence)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-4)
    cancels = (float(jnp.finfo(jnp.float32).eps) * step * chunk
               * 2 * float(want))                # sum |do * o|, do = 2 o
    for name, g, w in zip(NAMES, grads, want_grads):
        assert bool(jnp.isfinite(g).all()), name
        if name == "A":
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w),
                atol=cancels + 1e-3 * float(jnp.abs(w).max()))
        else:
            _close(g, w, 1e-3, name)
    if what == "all_of_it":
        own = (step * jnp.sum(b * c, -1))[..., None]  # S_t = step u b^T
        own = jnp.repeat(own, 2, 2) * u + d[:, None] * u
        np.testing.assert_allclose(
            np.asarray(ssm_scan.ssm_scan(*args, chunk)), np.asarray(own),
            rtol=1e-4, atol=1e-4)


def test_bfloat16_operands_keep_float32_statistics(rng):
    """bfloat16 u, B, C: ``o`` and their gradients come back in bfloat16
    (the step's, A's and D's in float32) within bfloat16's rounding of the
    float32 recurrence, as the plain form's do. And the statistics are
    float32 whatever the operands: over 8 chunks of slow heads a state
    carried in bfloat16 (the recurrence with its state rounded at every
    chunk boundary) is several times further from the float32 recurrence
    than the kernels are."""
    chunk, s = 16, 128
    u, b, c, dt, a, d = _inputs(rng, s)
    dt, a = dt * 0.01, a * 0.1                       # nothing forgotten
    weight = jnp.asarray(rng.standard_normal(u.shape), jnp.float32)
    half = tuple(x.astype(jnp.bfloat16) for x in (u, b, c)) + (dt, a, d)
    want = _with_gradients(_recurrence, weight)(u, b, c, dt, a, d)
    error = lambda x, y: float(  # noqa: E731
        jnp.linalg.norm(x.astype(jnp.float32) - y) / jnp.linalg.norm(y))
    for scan in (ssm_scan.ssm_scan, ssm_lib._plain_scan):
        got = _with_gradients(lambda *x: scan(*x, chunk), weight)(*half)
        for name, x, y in zip(("o",) + NAMES, got, want):
            assert x.dtype == (jnp.bfloat16 if name in ("o", "u", "B", "C")
                               else jnp.float32), name
            assert error(x, y) < 2e-2, (scan.__name__, name, error(x, y))

    # the same operands, exactly (bfloat16 values held in float32), so
    # that only the statistics differ: the kernels against a carried
    # state rounded to bfloat16 at every chunk boundary
    exact = tuple(x.astype(jnp.float32) for x in half[:3]) + (dt, a, d)
    sound = _recurrence(*exact)

    def rounded_state(u, b, c, dt, a, d):
        share = u.shape[2] // b.shape[2]
        b, c = jnp.repeat(b, share, 2), jnp.repeat(c, share, 2)
        state, out = jnp.zeros(u.shape[:1] + u.shape[2:] + b.shape[-1:]), []
        for t in range(s):
            if t % chunk == 0:
                state = state.astype(jnp.bfloat16).astype(jnp.float32)
            state = (jnp.exp(dt[:, t] * a)[..., None, None] * state
                     + (dt[:, t, :, None] * u[:, t])[..., None]
                     * b[:, t, :, None, :])
            out.append(jnp.sum(state * c[:, t, :, None, :], -1)
                       + d[:, None] * u[:, t])
        return jnp.stack(out, 1)

    kernels = error(ssm_scan.ssm_scan(*exact, chunk), sound)
    carried_in_half = error(rounded_state(*exact), sound)
    assert kernels < 1e-5 and carried_in_half > 50 * kernels, (
        kernels, carried_in_half)


def test_the_shape_chooses_the_path(rng, monkeypatch):
    """``chunked_scan`` sends heads that fill whole lane slabs through the
    kernels and every other size (the small cells' 4 channels and 8
    states) through the plain form; nothing but the shape is read."""
    taken = []
    kernels, plain = ssm_scan.ssm_scan, ssm_lib._plain_scan
    monkeypatch.setattr(ssm_scan, "ssm_scan", lambda *a, **kw: (
        taken.append("kernels"), kernels(*a, **kw))[1])
    monkeypatch.setattr(ssm_lib, "_plain_scan", lambda *a: (
        taken.append("plain"), plain(*a))[1])
    for p, heads, groups, n, chunk, dtype, want in (
            (4, 8, 2, 8, 8, jnp.float32, "plain"),
            (64, 4, 2, 128, 16, jnp.float32, "kernels"),
            (64, 4, 2, 128, 8, jnp.float32, "kernels"),
            # a bfloat16 tile is 16 rows
            (64, 4, 2, 128, 8, jnp.bfloat16, "plain"),
            (64, 4, 2, 128, 12, jnp.float32, "plain"),
            # a group of one head of 64: half a slab
            (64, 2, 2, 128, 16, jnp.float32, "plain"),
            (64, 4, 2, 64, 16, jnp.float32, "plain"),
            (96, 4, 1, 128, 16, jnp.float32, "plain")):
        u, b, c, dt, a, d = _scan_inputs(rng, 2 * chunk, heads=heads,
                                         groups=groups, p=p, n=n, batch=1)
        del taken[:]
        o = ssm_lib.chunked_scan(
            *(x.astype(dtype) for x in (u, b, c)), dt, a, d, chunk)
        assert taken == [want], (p, heads, groups, n, chunk, dtype)
        _close(o, _recurrence(u, b, c, dt, a, d),
               2e-5 if dtype == jnp.float32 else 3e-2, (p, chunk))
    # the published sizes: 64 heads of 64 in 8 groups of 128 states,
    # chunks of 128, bfloat16
    cell = ssm_lib.StateSpaceConfig()
    assert ssm_scan.supported(
        cell.chunk_size, cell.head_dim, cell.num_heads // cell.n_groups,
        cell.state_size, jnp.bfloat16)
    assert (cell.chunk_size, cell.head_dim, cell.num_heads, cell.n_groups,
            cell.state_size) == (128, 64, 64, 8, 128)
    assert not ssm_scan.supported(256, 64, 8, 128, jnp.bfloat16)


def test_the_kernels_refuse_what_they_are_not_built_for(rng, monkeypatch):
    args = _scan_inputs(rng, 32)
    with pytest.raises(ValueError, match="128-lane slab"):
        ssm_scan.ssm_scan(*args, 16)
    args = _inputs(rng, 48)
    with pytest.raises(ValueError, match="divides the sequence"):
        ssm_scan.ssm_scan(*args, 32)
    # in a process whose devices are TPUs the kernels are never
    # interpreted, as the flash kernel's are not
    monkeypatch.setattr(jax, "devices", lambda *a: [
        types.SimpleNamespace(platform="tpu")])
    with pytest.raises(ValueError, match="not interpreted"):
        ssm_scan.ssm_scan(*args, 16, interpret=True)
