"""Tensor parallelism: shard attention heads and the MLP hidden dim over a
``model`` mesh axis, letting XLA/GSPMD insert the collectives.

Not in the 2019 reference (SURVEY.md §2.7 marks TP "not required for
parity") — built because a complete TPU framework must scale models past
one chip's HBM, and because on TPU the idiomatic implementation is
compiler-first rather than hand-written collectives: parameters carry
``NamedSharding``s derived from name-based rules, the jitted train step
is ONE logical program over the global mesh, and GSPMD partitions the
einsums and places the all-reduces on the residual stream — the Megatron
column/row-parallel schedule, recovered by the compiler from the weight
layouts alone:

* q/k/v projections ``(d_model, heads, head_dim)`` → heads sharded
  (column-parallel); the attention itself is then embarrassingly
  head-parallel.
* attention out ``(heads, head_dim, d_model)`` → heads sharded
  (row-parallel; GSPMD emits the one all-reduce into the residual).
* MLP ``Dense_0 (d_model, d_ff)`` column-parallel, ``Dense_1
  (d_ff, d_model)`` row-parallel — one more all-reduce.
* ``lm_head (d_model, vocab)`` column-parallel: logits arrive
  vocab-sharded and stay so; the loss (``training._next_token_ll``)
  reduces over them, and GSPMD all-reduces a row's maximum, its sum of
  exponentials and its target's logit, ``[B, S]`` each.
* norms/embedding replicated.

Because the step is a single jitted program (no ``shard_map``), the data
axis needs no explicit gradient allreduce either: the global-batch mean
loss makes XLA emit the cross-data-axis reduction itself. Use a plain
optax optimizer here, not ``DistributedOptimizer`` (there is no named
axis inside to psum over — the compiler owns the collectives).
"""

from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from horovod_tpu.training import TrainState, _next_token_ll


def transformer_param_specs(params, model_axis="model", expert_axis=None):
    """Name-rule ``PartitionSpec`` tree for ``models.transformer`` params.

    ``model_axis=None`` disables the tensor-parallel rules (e.g. an
    expert-parallel-only mesh); ``expert_axis`` shards embedded MoE
    expert weights (``cfg.moe_every``) over that axis. Anything the
    rules don't recognize (norm scales, embeddings, biases, MoE gates)
    is replicated — the safe default for small tensors.
    """
    def spec_for(path, leaf):
        names = [getattr(k, "key", str(k)) for k in path]
        joined = "/".join(names)
        nd = getattr(leaf, "ndim", 0)
        if expert_axis and "moe/" in joined:
            from horovod_tpu.models.moe import expert_major_spec
            spec = expert_major_spec(joined, expert_axis)
            if spec is not None:
                return spec                        # one expert per shard
        if model_axis is None:
            return P()
        if any(f"{p}/kernel" in joined for p in ("query", "key", "value")):
            return P(None, model_axis, None)       # column: shard heads
        if "out/kernel" in joined and nd == 3:
            return P(model_axis, None, None)       # row: reduce to residual
        if "Dense_0/kernel" in joined:
            return P(None, model_axis)             # column: shard d_ff
        if "Dense_1/kernel" in joined:
            return P(model_axis, None)             # row: reduce to residual
        if "lm_head/kernel" in joined:
            return P(None, model_axis)             # vocab-sharded logits
        return P()
    return jax.tree_util.tree_map_with_path(spec_for, params)


def shard_lm_state(model, tx, rng, sample_tokens, mesh,
                   model_axis="model", expert_axis=None):
    """Initialize a TP/EP-sharded ``TrainState``: params placed by the
    rule shardings, optimizer state initialized UNDER jit so GSPMD
    propagates the matching layouts onto the moments."""
    variables = model.init(rng, sample_tokens)
    params = variables["params"]
    specs = transformer_param_specs(params, model_axis, expert_axis)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    params = jax.device_put(params, shardings)
    opt_state = jax.jit(tx.init)(params)
    return TrainState(params=params, opt_state=opt_state, batch_stats={},
                      step=jnp.zeros((), jnp.int32))


def make_tp_lm_train_step(model, tx, mesh, model_axis="model",
                          batch_axis="data", expert_axis=None,
                          donate=True, moe_aux_weight=0.01,
                          moe_z_weight=1e-3):
    """Jitted GSPMD language-model train step over a (data x model) mesh.

    ``step(state, tokens) -> (state, loss)``: ``tokens [B, S]`` sharded on
    ``batch_axis``, ``state`` from ``shard_lm_state``. Exact next-token
    loss; gradients/updates stay in the rule shardings (re-constrained
    after the update so a compiler heuristic can never drift the layout).

    MoE models (``cfg.moe_every``) sow Switch auxiliary terms into the
    ``"losses"`` collection; they are added here with the given weights
    (``moe_aux_weight`` load-balance, ``moe_z_weight`` router z-loss) —
    zero-cost no-op for dense models.
    """
    def step_fn(state, tokens):
        def compute_loss(params):
            logits, mutated = model.apply({"params": params}, tokens,
                                          mutable=["losses"])
            ll = _next_token_ll(logits, tokens[:, 1:])
            from horovod_tpu.models.moe import aux_loss
            return -jnp.mean(ll) + aux_loss(
                mutated, load_balance_weight=moe_aux_weight,
                router_z_weight=moe_z_weight)

        loss, grads = jax.value_and_grad(compute_loss)(state.params)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        specs = transformer_param_specs(params, model_axis, expert_axis)
        params = jax.lax.with_sharding_constraint(
            params, jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P)))
        new_state = TrainState(params=params, opt_state=opt_state,
                               batch_stats=state.batch_stats,
                               step=state.step + 1)
        return new_state, loss

    jitted = jax.jit(step_fn, donate_argnums=(0,) if donate else ())
    token_sharding = NamedSharding(mesh, P(batch_axis, None))

    def step(state, tokens):
        return jitted(state, jax.device_put(tokens, token_sharding))

    step.jitted = jitted
    return step
