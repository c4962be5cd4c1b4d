"""Plain reference of the hybrid state-space / sparse-expert decoder
(``model_type nemotron_h``: layers of ONE sublayer each, a Mamba-2 mixer,
grouped-query attention without position embedding, or relu^2 experts
with one shared expert), holding one chip's share of the experts.

Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no chunked form,
no sort, no ragged product, no ``shard_map``, no ``DistributedOptimizer``,
no flax. It reads the parameter tree the program's
``models/transformer.py`` creates for a pattern of (ssm, None),
(None, experts) and (mha, None) layers, tells a layer's kind by the
module it holds, and writes the equations out (RMSNorm eps 1e-5,
pre-norm, one residual a layer):

    x = E[tokens];  for every layer  x = x + f(rmsnorm(x) * g)
    f, "mixer" (H heads of P channels, G groups of N states, K taps):
        [z | xBC | dt] = y W_in
        xBC = silu(conv1d(xBC))      depthwise, K - 1 zeros on the left, bias
        [u | B | C] = xBC            head h reads group h // (H / G)
        D_t,h = softplus(dt_t,h + dt_bias_h)
        S_t,h = exp(D_t,h A_h) S_t-1,h + D_t,h u_t,h (x) B_t,g(h)
                                     A_h = -exp(A_log_h), S_-1 = 0
        o_t,h = S_t,h C_t,g(h) + D_h u_t,h
        o = rmsnorm_per_group(o * silu(z)) * gn     gate first, G groups
        f = o W_out
      the state-space layer AS THE RECURRENCE IT IS: a scan over time
      steps carrying S_t (nested: blocks of steps under
      ``jax.checkpoint``, so that the gradient at 4,096 positions fits;
      computed in blocks, not another algorithm).
    f, "attn" (H query heads over H_kv key/value heads, no position
      embedding of any kind):
        q = y Wq;  k = y Wk;  v = y Wv;  query head i reads head
        i // (H / H_kv);  o = softmax(causal(q k^T / sqrt(d))) v
        f = concat(o) Wo
    f, "experts":
        s   = sigmoid(y Wr)                       [T, n_routed_experts]
        idx = top_k(s + b)                        b enters the choice only
        w   = s[idx] / (sum(s[idx]) + 1e-20) * routed_scaling_factor
        f   = sum over the k whose expert is HELD of w_k * E_idx_k(y)
              + Shared(y)            E, Shared: relu(y Wu)^2 Wd
    logits = (rmsnorm(x) * gf) Wh                 (untied head)
    loss   = mean over every position but the last of
             -log softmax(logits)[next token]

The share: the tree holds ``experts_held`` experts, those numbered
``expert_offset ..``; the router is as wide as the model has experts and
the weights are normalised over all k chosen. A slot whose expert is not
held adds nothing, here as in the program. Every expert held is applied
to every token and masked: no token is gathered, sorted or dropped.

``forward`` and ``loss`` are the equations as one function, for the CPU
tests; ``loss_and_grad`` computes the same loss and gradient in blocks,
for the chip at the timed sizes.

``choices``: a program in bfloat16 sees scores that differ from these in
their third digit, and where a token's sixth and seventh score lie closer
than that it takes the other expert, whose output is not small. A caller
that compares such a program with this reference hands over the program's
choices: the experts are then evaluated under THOSE, while the scores, the
weights made from them and the router's gradient stay this reference's
own, and ``idx`` still returns what this reference would have chosen.

``MANTISSA_BITS`` and the small functions below it are the handles of the
study that shows which faults a comparison with this reference can tell
(``benchmark/reference/ssm_moe_lm_faults.py``); nothing else sets them.
"""

import jax
import jax.numpy as jnp

RMS_EPS = 1e-5
PRECISION = "highest"  # of every matrix product below
SCAN_BLOCK = 64  # time steps whose states the backward pass keeps at once
# None: the operands of a product are taken as they are, float32. A number:
# both are first rounded to that many bits of mantissa at float32's
# range (7: bfloat16's; 3: float8_e4m3's, as a product scaled to its range
# would see them).
MANTISSA_BITS = None


def _mm(spec, a, b):
    """Every matrix product of this file."""
    if MANTISSA_BITS is not None:
        a, b = (jax.lax.reduce_precision(x, 8, MANTISSA_BITS)
                for x in (a, b))
    return jnp.einsum(spec, a, b)


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + RMS_EPS) * scale


def _softmax(scores):
    return jax.nn.softmax(scores, -1)


# ---- the state-space layer ----

def _conv(xbc, kernel, bias):
    """Depthwise and causal: ``out_t = bias + sum_k kernel[k] *
    x_(t - (K - 1) + k)`` with zeros before the sequence's start.
    xbc [B, S, C], kernel [K, C]."""
    taps, s = kernel.shape[0], xbc.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    return bias + sum(kernel[k] * padded[:, k:k + s] for k in range(taps))


def _step_size(dt, dt_bias):
    return jax.nn.softplus(dt + dt_bias)


def _log_decay(step, a):
    """log a_t,h = D_t,h * A_h. step [B, S, H]."""
    return step * a


def _carried(state, t):
    """The state position ``t`` inherits from position ``t - 1``."""
    del t
    return state


def _skip(d, u):
    return d[:, None] * u


def _recurrence(u, b, c, step, log_a, d):
    """``o`` [B, S, H, P] by one step at a time. u [B, S, H, P]; b, c
    [B, S, H, N] (each head's group already chosen); step, log_a
    [B, S, H]; d [H]."""
    bsz, s, h, p = u.shape
    block = min(SCAN_BLOCK, s)
    if s % block:
        raise ValueError(f"{s} positions in blocks of {block}")

    def one(state, at):
        t, u_t, b_t, c_t, step_t, log_a_t = at
        state = (jnp.exp(log_a_t)[..., None, None] * _carried(state, t)
                 + _mm("bhp,bhn->bhpn", step_t[..., None] * u_t, b_t))
        return state, _mm("bhpn,bhn->bhp", state, c_t) + _skip(d, u_t)

    @jax.checkpoint
    def some(state, ats):
        return jax.lax.scan(one, state, ats)

    steps_first = lambda a: jnp.moveaxis(a, 1, 0).reshape(  # noqa: E731
        s // block, block, *a.shape[:1], *a.shape[2:])
    at = (jnp.arange(s).reshape(s // block, block),) + tuple(
        steps_first(a) for a in (u, b, c, step, log_a))
    _, o = jax.lax.scan(some, jnp.zeros((bsz, h, p, b.shape[-1])), at)
    return jnp.moveaxis(o.reshape(s, bsz, h, p), 0, 1)


def _gated_norm(o, z, scale, groups):
    """The gate first, then the root mean square over each group."""
    x = o * jax.nn.silu(z)
    grouped = x.reshape(*x.shape[:-1], groups, -1)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, -1, keepdims=True) + RMS_EPS)
    return grouped.reshape(x.shape) * scale


def _ssm(p, y, arch):
    heads, width = p["dt_bias"].shape[0], arch["mamba_head_dim"]
    groups, states = arch["n_groups"], arch["ssm_state_size"]
    inner, bsz, s = heads * width, y.shape[0], y.shape[1]
    proj = _mm("bsd,de->bse", y, _f32(p["in_proj"]["kernel"]))
    z, xbc, dt = (proj[..., :inner], proj[..., inner:-heads],
                  proj[..., -heads:])
    xbc = jax.nn.silu(_conv(xbc, _f32(p["conv1d"]["kernel"]),
                            _f32(p["conv1d"]["bias"])))
    u = xbc[..., :inner].reshape(bsz, s, heads, width)
    # head h reads group h // (heads / groups)
    of_head = lambda a: jnp.repeat(  # noqa: E731
        a.reshape(bsz, s, groups, states), heads // groups, axis=2)
    b = of_head(xbc[..., inner:inner + groups * states])
    c = of_head(xbc[..., inner + groups * states:])
    step = _step_size(dt, _f32(p["dt_bias"]))
    o = _recurrence(u, b, c, step,
                    _log_decay(step, -jnp.exp(_f32(p["A_log"]))),
                    _f32(p["D"]))
    o = _gated_norm(o.reshape(bsz, s, inner), z, _f32(p["norm"]["scale"]),
                    groups)
    return _mm("bse,ed->bsd", o, _f32(p["out_proj"]["kernel"]))


# ---- attention ----

def _positioned(q, k):
    """No position embedding: q and k as they are."""
    return q, k


def _attention(p, y, arch):
    del arch
    q = _mm("bsd,dhe->bshe", y, _f32(p["query"]["kernel"]))
    k = _mm("bsd,dhe->bshe", y, _f32(p["key"]["kernel"]))
    v = _mm("bsd,dhe->bshe", y, _f32(p["value"]["kernel"]))
    q, k = _positioned(q, k)
    share = q.shape[2] // k.shape[2]  # query heads a key/value head
    k, v = (jnp.repeat(a, share, axis=2) for a in (k, v))
    s = y.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(qkv):  # one head at a time: its [B, S, S] scores fit
        qh, kh, vh = qkv
        scores = _mm("bqe,bke->bqk", qh, kh) / qh.shape[-1] ** 0.5
        probs = _softmax(jnp.where(causal, scores, -jnp.inf))
        return _mm("bqk,bke->bqe", probs, vh)

    heads_first = lambda a: jnp.moveaxis(a, 2, 0)  # noqa: E731
    ctx = jax.lax.map(jax.checkpoint(head),
                      (heads_first(q), heads_first(k), heads_first(v)))
    return _mm("hbqe,hed->bqd", ctx, _f32(p["out"]["kernel"]))


# ---- experts ----

def _act(x):
    return jnp.square(jax.nn.relu(x))


def _expert(y, up, down):
    return _mm("...f,fd->...d", _act(_mm("...d,df->...f", y, up)), down)


def _scores(y, router):
    return jax.nn.sigmoid(_mm("td,de->te", y, router))


def _route(p, y, arch, choice=None):
    """``(idx [T, k], w [T, k], own [T, k])``: each token's experts, their
    weights from the scores alone, and the experts this reference chooses
    by score plus selection bias: ``idx`` is ``own``, or ``choice`` where
    the caller brings one."""
    s = _scores(y, _f32(p["router"]))
    bias = jax.lax.stop_gradient(_f32(p["e_score_correction_bias"]))
    _, own = jax.lax.top_k(s + bias, arch["num_experts_per_tok"])
    idx = own if choice is None else choice
    chosen = jnp.take_along_axis(s, idx, -1)
    return idx, (chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
                 * arch["routed_scaling_factor"]), own


def _routed(p, y, arch, choice=None):
    """``(the held experts' part of the layer's result [T, d], this
    reference's own choice [T, k])`` for ``y`` [T, d]."""
    idx, w, own = _route(p, y, arch, choice)

    def one(out, expert):  # a loop over the experts held, each masked
        e, up, down = expert
        weight = jnp.sum(
            jnp.where(idx == arch["expert_offset"] + e, w, 0.0), -1)
        return out + weight[:, None] * _expert(y, _f32(up), _f32(down)), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(y),
        (jnp.arange(p["up_proj"].shape[0]), p["up_proj"], p["down_proj"]))
    return out, own


def _shared(p, y):
    return _expert(y, _f32(p["up_proj"]["kernel"]),
                   _f32(p["down_proj"]["kernel"]))


# ---- layers ----

def _kind(p):
    return next(name for name in ("mixer", "attn", "experts") if name in p)


def _layer(p, x, arch, choice=None):
    """``(x + f(rmsnorm(x)), idx)``; ``idx`` [B*S, k] is this reference's
    own choice (zeros where the layer routes nothing), ``choice`` [B*S, k]
    the one the experts are evaluated under instead."""
    y = _rmsnorm(x, _f32(p["RMSNorm_0"]["scale"]))
    b, s, d = y.shape
    idx = jnp.zeros((b * s, arch["num_experts_per_tok"]), jnp.int32)
    kind = _kind(p)
    if kind == "mixer":
        return x + _ssm(p["mixer"], y, arch), idx
    if kind == "attn":
        return x + _attention(p["attn"], y, arch), idx
    routed, idx = _routed(p["experts"], y.reshape(b * s, d), arch, choice)
    return x + routed.reshape(b, s, d) + _shared(p["shared_experts"], y), idx


def _cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def _head(scale, kernel, x, tokens):
    logits = _mm("bsd,dv->bsv", _rmsnorm(x, _f32(scale)), _f32(kernel))
    return _cross_entropy(logits[:, :-1], tokens[:, 1:])


def _layers(params):
    return sum(name.startswith("block_") for name in params)


def forward(params, tokens, arch, choices=None):
    """``(logits [B, S, V], idx [L, B*S, k])`` of ``tokens`` [B, S].
    ``arch``: ``mamba_head_dim``, ``n_groups``, ``ssm_state_size``,
    ``num_experts_per_tok``, ``routed_scaling_factor``, ``expert_offset``;
    everything else is read off the tree. ``choices`` [L, B*S, k]: see the
    head of this file (only an expert layer's row is read)."""
    x = _f32(params["embed"]["embedding"])[tokens]
    own = []
    for i in range(_layers(params)):
        x, idx = _layer(params[f"block_{i}"], x, arch,
                        None if choices is None else choices[i])
        own.append(idx)
    x = _rmsnorm(x, _f32(params["RMSNorm_0"]["scale"]))
    return (_mm("bsd,dv->bsv", x, _f32(params["lm_head"]["kernel"])),
            jnp.stack(own))


def loss(params, tokens, arch, choices=None):
    """``(mean next-token cross-entropy, idx)`` of ``tokens`` [B, S]."""
    logits, idx = forward(params, tokens, arch, choices)
    return _cross_entropy(logits[:, :-1], tokens[:, 1:]), idx


def loss_and_grad(params, tokens, arch, choices=None):
    """``(loss, its gradient, idx [B, L, S, k])`` of ``tokens`` [B, S],
    float32 at ``PRECISION``: ``loss`` above and its gradient (``choices``
    [B, L, S, k] as the head of this file says), computed in blocks so
    that it fits beside the parameters and compiles in seconds. One
    sequence at a time, gradients summed; within a sequence every layer
    is a program of its own kind (one program for all state-space layers,
    one for all expert layers, one for attention), run forward keeping
    each layer's input, then backward through ``jax.vjp`` of the same
    function, which runs the layer forward again: what ``jax.checkpoint``
    around each layer would do inside one program, without compiling
    every layer's copy. Call it outside ``jax.jit``."""
    with jax.default_matmul_precision(PRECISION):
        programs = {}

        def program(kind):  # (forward -> (x, idx), backward -> (dp, dx))
            if kind not in programs:
                programs[kind] = (
                    jax.jit(lambda p, x, c: _layer(p, x, arch, c)),
                    jax.jit(lambda p, x, c, g: jax.vjp(
                        lambda p, x: _layer(p, x, arch, c)[0], p, x)[1](g)))
            return programs[kind]

        embed = jax.jit(lambda table, seq: _f32(table)[seq])
        embed_grad = jax.jit(lambda table, seq, g: jnp.zeros(
            table.shape, jnp.float32).at[seq].add(g))
        head = jax.jit(jax.value_and_grad(_head, argnums=(0, 1, 2)))
        add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
        blocks = [params[f"block_{i}"] for i in range(_layers(params))]

        def choice(b, i):  # of sequence b in layer i, or None
            if choices is None or _kind(blocks[i]) != "experts":
                return None
            return choices[b, i]

        total, grads, own = 0.0, None, []
        for b, seq in enumerate(tokens):
            seq = seq[None]
            x = embed(params["embed"]["embedding"], seq)
            inputs, idxs = [], []
            for i, p in enumerate(blocks):
                inputs.append(x)
                x, idx = program(_kind(p))[0](p, x, choice(b, i))
                idxs.append(idx)
            value, (g_scale, g_kernel, g) = head(
                params["RMSNorm_0"]["scale"], params["lm_head"]["kernel"],
                x, seq)
            one = {"RMSNorm_0": {"scale": g_scale},
                   "lm_head": {"kernel": g_kernel}}
            for i, p in reversed(list(enumerate(blocks))):
                one[f"block_{i}"], g = program(_kind(p))[1](
                    p, inputs.pop(), choice(b, i), g)
            one["embed"] = {"embedding": embed_grad(
                params["embed"]["embedding"], seq, g)}
            total = total + value
            grads = one if grads is None else add(grads, one)
            own.append(jnp.stack(idxs))
        n = tokens.shape[0]
        scale = jax.jit(lambda tree: jax.tree_util.tree_map(
            lambda g: g / n, tree))
        return total / n, scale(grads), jnp.stack(own)
