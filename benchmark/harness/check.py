"""Helpers of the families' reference checks."""

import math

import jax
import jax.numpy as jnp


def first_moment_norm(opt_state, node_type, field):
    """Global L2 norm of the optimizer's first moment after ONE step from
    a zero state: the only place a step built by ``training.py`` shows
    the gradient it applied (after the exchange averaged it). ``field``
    of the one ``node_type`` node in ``opt_state`` (``mu`` of
    ``optax.ScaleByAdamState``, ``trace`` of ``optax.TraceState``)."""
    nodes = [n for n in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: isinstance(x, node_type))
        if isinstance(n, node_type)]
    if len(nodes) != 1:
        raise RuntimeError(f"expected one {node_type.__name__} in the "
                           f"optimizer state, found {len(nodes)}")
    moment = getattr(nodes[0], field)
    return jax.jit(lambda m: jnp.sqrt(sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)))
        for x in jax.tree_util.tree_leaves(m))))(moment)


def compare(got, want, rtol):
    """``{name: {...}}`` and whether every pair agrees. ``got``/``want``
    map a name to a number, ``rtol`` a name to its relative tolerance."""
    report, ok = {}, True
    for name, tol in rtol.items():
        g, w = float(got[name]), float(want[name])
        err = abs(g - w) / abs(w) if w else math.inf
        agrees = math.isfinite(g) and math.isfinite(w) and err <= tol
        ok = ok and agrees
        report[name] = {"step": g, "reference": w, "relative_error": err,
                        "tolerance": tol, "agrees": agrees}
    return ok, report
