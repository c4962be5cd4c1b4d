"""The parts of a sparse family's reference check that do not know the
model: the gradient a step applied, its distance from a reference's, the
two routings' distance, and the report with its limits.

``families/ssm_moe_lm.py`` is built on these. ``families/mla_moe_lm.py``
holds the same four as closures of its ``build`` (PR 27); a PR that adds a
configuration may edit no file the benchmark had, so they stay there until
a ``benchmark`` PR points that family here (PERF.md section 7).
"""

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1 = 0.9  # optax.adamw's default, which the families build with


def first_moment(opt_state):
    """Adam's first moment, the tree's own buffers (no copy beside the
    state). After ONE step from a zero state it is ``(1 - b1)`` times the
    gradient the step applied, the only place a step built by
    ``training.py`` shows it."""
    import optax

    moments = [n for n in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: isinstance(x, optax.ScaleByAdamState))
        if isinstance(n, optax.ScaleByAdamState)]
    if len(moments) != 1:
        raise RuntimeError(f"expected one ScaleByAdamState in the "
                           f"optimizer state, found {len(moments)}")
    return moments[0].mu


@jax.jit
def gradient_numbers(moment, want):
    """``(|got|, |want|, |got - want|)``, global L2 norms over every
    leaf, ``got`` the gradient in the first moment."""
    got = jax.tree_util.tree_map(
        lambda m: m.astype(jnp.float32) / (1.0 - ADAM_B1), moment)
    norm = lambda tree: jnp.sqrt(sum(  # noqa: E731
        jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(tree)))
    return norm(got), norm(want), norm(jax.tree_util.tree_map(
        jnp.subtract, got, want))


def routing_numbers(choices, own, *, expert_layers, offset, held, experts,
                    repeats, expected):
    """The routing's own numbers, of the checked step's batch (the seeded
    sequences, each ``repeats`` times). ``choices`` / ``own`` [sequence,
    layer, position, k]: the step's and the reference's; ``expert_layers``
    indexes the layers that route. Token-slots that fell on the experts
    held, a layer, beside their expectation; the largest load of any
    expert, and of any expert held, over the mean load; and the share of
    the token-slots the reference chooses, a layer, whose expert the step
    did not choose for that token."""
    idx, own = (np.asarray(a)[:, expert_layers] for a in (choices, own))
    on_share = ((idx >= offset) & (idx < offset + held)).sum(axis=(0, 2, 3))
    loads = np.stack([np.bincount(layer.ravel(), minlength=experts)
                      for layer in idx.transpose(1, 0, 2, 3)])
    apart = 1.0 - (own[..., :, None] == idx[..., None, :]).any(-1).mean(
        axis=(0, 2, 3))
    return {"held_slots_per_layer": (on_share * repeats).tolist(),
            "expected_held_slots": expected,
            "largest_load_over_mean": (loads.max(1)
                                       / loads.mean(1)).tolist(),
            "largest_held_load_over_mean": (
                loads[:, offset:offset + held].max(1)
                / loads.mean(1)).tolist(),
            "apart_per_layer": apart.tolist()}


def part_numbers(moment, want, chosen):
    """``(|want|, |got - want|)`` as ``gradient_numbers`` gives them, over
    the leaves whose path ``chosen`` takes (``jax.tree_util.keystr``)."""
    taken = [chosen(jax.tree_util.keystr(path)) for path, _ in
             jax.tree_util.tree_leaves_with_path(want)]
    pick = lambda tree: [leaf for leaf, take in zip(  # noqa: E731
        jax.tree_util.tree_leaves(tree), taken) if take]
    _, want_norm, apart = gradient_numbers(pick(moment), pick(want))
    return float(want_norm), float(apart)


def compare(got, want, limits, routing, parts=None):
    """``(agrees, report)`` of a step's ``(loss, first moment, choices)``
    against a reference's ``(loss, gradient, own choices)``: ``loss`` and
    ``grad_norm`` as relative errors, ``grad_error`` the distance between
    the two gradients over the reference's norm, ``routing_apart`` the
    largest layer's of ``routing["apart_per_layer"]``, and for every name
    of ``parts`` the same distance over the leaves its predicate takes; a
    name that ``limits`` lacks is read and not judged."""
    got_norm, want_norm, apart = (float(x) for x in gradient_numbers(
        got[1], want[1]))
    relative = lambda g, w: (g, w, abs(g - w) / abs(w))  # noqa: E731
    readings = {
        "loss": relative(float(got[0]), float(want[0])),
        "grad_norm": relative(got_norm, want_norm),
        "grad_error": (apart, want_norm, apart / want_norm),
        "routing_apart": (max(routing["apart_per_layer"]), 0.0,
                          max(routing["apart_per_layer"]))}
    for name, chosen in (parts or {}).items():
        part_norm, part_apart = part_numbers(got[1], want[1], chosen)
        readings[name] = (part_apart, part_norm, part_apart / part_norm)
    report = {name: {"step": g, "reference": w, "relative_error": err,
                     "tolerance": limits.get(name),
                     "agrees": bool(name not in limits
                                    or err <= limits[name])}
              for name, (g, w, err) in readings.items()}
    return all(r["agrees"] for r in report.values()), {
        **report, "routing": routing}
