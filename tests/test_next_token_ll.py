"""``training._next_token_ll``: the LM loss's log-likelihood with a
backward pass of its own.

Held against ``jax.nn.log_softmax`` + ``take_along_axis`` under autodiff
(what it was until PR 30), in value and in the gradient with respect to
the logits; and against the one thing that made it worth writing: what
it keeps from the forward pass to the backward is the logits as they
came in and one number a row, never a wider ``[B, T, V]``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.training import _next_token_ll

B, S, V = 3, 8, 37


def _autodiff_ll(logits, targets):
    if targets.shape[1] == logits.shape[1] - 1:
        logits = logits[:, :-1]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def _case(dtype, form, seed=0):
    """Logits with one row whose maximum is +80 and one at -80, targets
    in either form the builders use, and a cotangent that differs by
    row (the same-length form's mask is part of it: zeros in the last
    position, as ``make_lm_train_step`` masks the global last one)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    logits = 3.0 * jax.random.normal(keys[0], (B, S, V), jnp.float32)
    logits = logits.at[0, 1].add(80.0 - jnp.max(logits[0, 1]))
    logits = logits.at[1, 2].add(-80.0 - jnp.max(logits[1, 2]))
    rows = S - 1 if form == "one_shorter" else S
    targets = jax.random.randint(keys[1], (B, rows), 0, V, jnp.int32)
    weights = jax.random.uniform(keys[2], (B, rows), jnp.float32, 0.5, 2.0)
    if form == "same_length_masked":
        weights = weights.at[:, -1].set(0.0)
    return logits.astype(dtype), targets, weights


def _value_and_grad(ll, logits, targets, weights):
    return jax.value_and_grad(
        lambda x: -jnp.sum(ll(x, targets) * weights))(logits)


@pytest.mark.parametrize("form", ["one_shorter", "same_length_masked"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_value_and_gradient_equal_autodiff_of_log_softmax(dtype, form):
    logits, targets, weights = _case(dtype, form)
    got, got_grad = _value_and_grad(_next_token_ll, logits, targets, weights)
    want, want_grad = _value_and_grad(_autodiff_ll, logits, targets, weights)
    assert got_grad.dtype == logits.dtype == want_grad.dtype
    assert got_grad.shape == logits.shape
    assert np.isfinite(float(got))
    assert np.all(np.isfinite(np.asarray(got_grad, np.float32)))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    # both are float32 arithmetic rounded once to the logits' dtype. The
    # exponent ``x - lse`` carries the rounding of an lse of 80, 80 *
    # 2^-24 = 5e-6, into the probability; and a last-place difference of
    # float32 can move a bfloat16 by one place, 2^-8
    rtol = 2e-5 if dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(np.asarray(got_grad, np.float32),
                               np.asarray(want_grad, np.float32),
                               rtol=rtol, atol=1e-7)
    if form == "one_shorter":
        # the dropped position has no say: its cotangent is zero
        assert not np.any(np.asarray(got_grad[:, -1], np.float32))


@pytest.mark.parametrize("form", ["one_shorter", "same_length_masked"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_forward_alone_and_under_jit(dtype, form):
    logits, targets, _ = _case(dtype, form, seed=1)
    want = _autodiff_ll(logits, targets)
    for ll in (_next_token_ll, jax.jit(_next_token_ll)):
        got = ll(logits, targets)
        assert got.dtype == jnp.float32 and got.shape == targets.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


def test_extreme_rows_neither_overflow_nor_vanish():
    """exp(80) squared overflows float32 and exp(-80) is no normal
    number: a pass that forgets the row's maximum shows here."""
    logits, targets, weights = _case(jnp.float32, "same_length_masked")
    ll = _next_token_ll(logits, targets)
    assert np.all(np.isfinite(np.asarray(ll)))
    _, grad = _value_and_grad(_next_token_ll, logits, targets, weights)
    for row in ((0, 1), (1, 2)):
        # -(onehot - softmax) * weight sums to zero over the vocabulary
        np.testing.assert_allclose(float(jnp.sum(grad[row])), 0.0, atol=1e-5)
        assert float(jnp.max(jnp.abs(grad[row]))) > 1e-3


def _residuals(ll, logits, targets):
    """What ``jax.vjp`` keeps for the backward pass: the outputs of the
    jaxpr of the function that returns the pullback."""
    return jax.make_jaxpr(
        lambda x: jax.vjp(lambda x: ll(x, targets), x)[1])(logits).out_avals


@pytest.mark.parametrize("form", ["one_shorter", "same_length_masked"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_no_residual_over_the_vocabulary_is_wider_than_the_logits(dtype,
                                                                  form):
    logits, targets, _ = _case(dtype, form)

    def over_vocabulary(avals):
        return [a for a in avals if a.ndim == 3 and a.shape[-1] == V]

    kept = over_vocabulary(_residuals(_next_token_ll, logits, targets))
    assert [(a.shape, a.dtype) for a in kept] == [(logits.shape,
                                                   logits.dtype)], kept
    if dtype == jnp.bfloat16:
        # the test can tell: autodiff of log_softmax keeps float32
        assert any(a.dtype == jnp.float32 for a in over_vocabulary(
            _residuals(_autodiff_ll, logits, targets)))
