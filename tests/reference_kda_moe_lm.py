"""Plain reference of the hybrid delta-attention / latent-attention /
sparse-expert decoder (``model_type kimi_linear``: Kimi Delta Attention or
latent attention without rotary as a layer's mixer, a dense SwiGLU in the
leading layer and top-k SwiGLU experts with one shared expert after it),
holding one chip's share of the experts.

Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no chunked form,
no triangular solve, no sort, no ragged product, no ``shard_map``, no
``DistributedOptimizer``, no flax. It reads the parameter tree the
program's ``models/transformer.py`` creates for a pattern of (kda,
swiglu), (kda, experts) and (mla, experts) layers, tells a half's kind by
the module it holds, and writes the equations out (RMSNorm eps 1e-5,
pre-norm, two residuals a layer):

    x = E[tokens];  for every layer
        x = x + mixer(rmsnorm(x) * g1);  x = x + ff(rmsnorm(x) * g2)
    mixer, "mixer" (Kimi Delta Attention: H heads of D channels, K taps):
        q~ = silu(conv(y Wq)); k~ = silu(conv(y Wk)); v = silu(conv(y Wv))
                                 depthwise, K - 1 zeros on the left, no bias
        q_t = q~_t / |q~_t| * D^-1/2;  k_t = k~_t / |k~_t|    a head
        g_t = -exp(A_log_h) softplus((y_t Wf_a) Wf_b + dt_bias)   in R^D
        beta_t = sigmoid(y_t Wb)
        S~ = Diag(exp(g_t)) S_t-1                  the decay, a channel of k
        S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T   S in R^{D x D}, S_-1 = 0
        o_t = S_t^T q_t
        mixer = [rmsnorm_head(o_t) * w * sigmoid((y_t Wg_a) Wg_b + b_g)] Wo
      the delta rule AS THE RECURRENCE IT IS: a scan over positions
      carrying S_t (nested: blocks of positions under ``jax.checkpoint``,
      so that the gradient at 4,096 positions fits; computed in blocks,
      not another algorithm).
    mixer, "attn" (latent attention, no rotary: ``mla_use_nope``):
        q = y Wq -> per head [q_nope | q_pe]
        [c | k_pe] = y Wkva;  c = rmsnorm(c) * gc
        [k_nope | v] per head = c Wkvb;  k = [k_nope | k_pe], k_pe ONE head
        o = softmax(causal(q k^T / sqrt(d_qk))) v;  mixer = concat(o) Wo
    ff, "mlp":      (silu(y Wg) * (y Wu)) Wd
    ff, "experts":
        s   = sigmoid(y Wr)                       [T, n_routed_experts]
        idx = top_k(s + b)                        b enters the choice only
        w   = s[idx] / (sum(s[idx]) + 1e-20) * routed_scaling_factor
        ff  = sum over the k whose expert is HELD of w_k * E_idx_k(y)
              + Shared(y)            E, Shared: (silu(y Wg) * (y Wu)) Wd
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
their third digit, and where a token's eighth and ninth score lie closer
than that it takes the other expert, whose output is not small. A caller
that compares such a program with this reference hands over the program's
choices: the experts are then evaluated under THOSE, while the scores, the
weights made from them and the router's gradient stay this reference's
own, and ``idx`` still returns what this reference would have chosen.

``MANTISSA_BITS`` and the small functions below it are the handles of the
study that shows which faults a comparison with this reference can tell
(``benchmark/reference/kda_moe_lm_faults.py``); nothing else sets them.
"""

import jax
import jax.numpy as jnp

RMS_EPS = 1e-5
PRECISION = "highest"  # of every matrix product below
SCAN_BLOCK = 64  # positions whose states the backward pass keeps at once
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


def _dense(y, p):
    return _mm("bsd,de->bse", y, _f32(p["kernel"]))


# ---- delta attention ----

def _conv(x, kernel):
    """Depthwise and causal, no bias: ``out_t = sum_k kernel[k] *
    x_(t - (K - 1) + k)`` with zeros before the sequence's start.
    x [B, S, C], kernel [K, C]."""
    taps, s = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(kernel[k] * padded[:, k:k + s] for k in range(taps))


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _log_decay(a_log, rate, dt_bias):
    """g [B, S, H, D], one log-decay a channel: ``-exp(A_log_h) *
    softplus(rate + dt_bias)``. rate [B, S, H * D]."""
    heads = a_log.shape[0]
    step = jax.nn.softplus(rate + dt_bias)
    return -jnp.exp(a_log)[:, None] * step.reshape(
        *rate.shape[:2], heads, -1)


def _write_strength(x):
    return jax.nn.sigmoid(x)


def _carried(state, t):
    """The state position ``t`` inherits from position ``t - 1``."""
    del t
    return state


def _delta_step(state, k, v, g, beta):
    """``S_t`` from ``S_t-1``: the decay first, then the correction reads
    the decayed state. state [B, H, D, E]; k, g [B, H, D]; v [B, H, E];
    beta [B, H]."""
    state = jnp.exp(g)[..., None] * state
    u = beta[..., None] * (v - _mm("bhde,bhd->bhe", state, k))
    return state + _mm("bhd,bhe->bhde", k, u)


def _recurrence(q, k, v, g, beta):
    """``o`` [B, S, H, E] by one position at a time. q, k, g [B, S, H, D];
    v [B, S, H, E]; beta [B, S, H]."""
    bsz, s, h, d = k.shape
    block = min(SCAN_BLOCK, s)
    if s % block:
        raise ValueError(f"{s} positions in blocks of {block}")

    def one(state, at):
        t, q_t, k_t, v_t, g_t, beta_t = at
        state = _delta_step(_carried(state, t), k_t, v_t, g_t, beta_t)
        return state, _mm("bhde,bhd->bhe", state, q_t)

    @jax.checkpoint
    def some(state, ats):
        return jax.lax.scan(one, state, ats)

    steps_first = lambda a: jnp.moveaxis(a, 1, 0).reshape(  # noqa: E731
        s // block, block, *a.shape[:1], *a.shape[2:])
    at = (jnp.arange(s).reshape(s // block, block),) + tuple(
        steps_first(a) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(some, jnp.zeros((bsz, h, d, v.shape[-1])), at)
    return jnp.moveaxis(o.reshape(s, bsz, h, v.shape[-1]), 0, 1)


def _out_gate(x):
    return jax.nn.sigmoid(x)


def _kda(p, y, arch):
    d, bsz, s = arch["kda_head_dim"], y.shape[0], y.shape[1]
    heads = lambda a: a.reshape(bsz, s, -1, d)  # noqa: E731
    q, k, v = (heads(jax.nn.silu(_conv(
        _dense(y, p[f"{name}_proj"]), _f32(p[f"{name}_conv1d"]["kernel"]))))
        for name in "qkv")
    q, k = _l2norm(q) * d ** -0.5, _l2norm(k)
    g = _log_decay(_f32(p["A_log"]),
                   _dense(_dense(y, p["f_a_proj"]), p["f_b_proj"]),
                   _f32(p["dt_bias"]))
    beta = _write_strength(_dense(y, p["b_proj"]))
    o = _recurrence(q, k, v, g, beta)
    gate = (_dense(_dense(y, p["g_a_proj"]), p["g_b_proj"])
            + _f32(p["g_b_proj"]["bias"]))
    o = _rmsnorm(o, _f32(p["o_norm"]["scale"])) * _out_gate(heads(gate))
    return _dense(o.reshape(bsz, s, -1), p["o_proj"])


# ---- latent attention ----

def _positioned(q_pe, k_pe):
    """No rotary: the position parts of q and k as they are."""
    return q_pe, k_pe


def _attention(p, y, arch):
    nope, rank = arch["qk_nope_head_dim"], arch["kv_lora_rank"]
    q = _mm("bsd,dhe->bshe", y, _f32(p["q_proj"]["kernel"]))
    latent = _dense(y, p["kv_a_proj_with_mqa"])
    c = _rmsnorm(latent[..., :rank], _f32(p["kv_a_layernorm"]["scale"]))
    kv = _mm("bsr,rhe->bshe", c, _f32(p["kv_b_proj"]["kernel"]))
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe, k_pe = _positioned(q[..., nope:], latent[..., None, rank:])
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, q_pe.shape)], -1)
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
    return _mm("hbqe,hed->bqd", ctx, _f32(p["o_proj"]["kernel"]))


# ---- feed-forward ----

def _swiglu(y, gate, up, down):
    return _mm("...f,fd->...d", jax.nn.silu(_mm("...d,df->...f", y, gate))
               * _mm("...d,df->...f", y, up), down)


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
        e, gate, up, down = expert
        weight = jnp.sum(
            jnp.where(idx == arch["expert_offset"] + e, w, 0.0), -1)
        return out + weight[:, None] * _swiglu(
            y, _f32(gate), _f32(up), _f32(down)), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(y),
        (jnp.arange(p["gate_proj"].shape[0]), p["gate_proj"], p["up_proj"],
         p["down_proj"]))
    return out, own


def _shared(p, y):
    return _swiglu(y, *(_f32(p[name]["kernel"])
                        for name in ("gate_proj", "up_proj", "down_proj")))


# ---- layers ----

def _mixer(p, x, arch):
    """The first half of a layer: ``x + mixer(rmsnorm(x))``."""
    y = _rmsnorm(x, _f32(p["RMSNorm_0"]["scale"]))
    if "mixer" in p:
        return x + _kda(p["mixer"], y, arch)
    return x + _attention(p["attn"], y, arch)


def _feed_forward(p, x, arch, choice=None):
    """The second half: ``(x + ff(rmsnorm(x)), idx)``; ``idx`` [B*S, k] is
    this reference's own choice (zeros for the dense layer), ``choice``
    [B*S, k] the one the experts are evaluated under instead."""
    y = _rmsnorm(x, _f32(p["RMSNorm_1"]["scale"]))
    b, s, d = y.shape
    if "mlp" in p:
        idx = jnp.zeros((b * s, arch["num_experts_per_tok"]), jnp.int32)
        return x + _shared(p["mlp"], y), idx
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
    ``arch``: ``kda_head_dim``, ``qk_nope_head_dim``, ``kv_lora_rank``,
    ``num_experts_per_tok``, ``routed_scaling_factor``, ``expert_offset``;
    everything else is read off the tree. ``choices`` [L, B*S, k]: see the
    head of this file (the dense layer's row is not read)."""
    x = _f32(params["embed"]["embedding"])[tokens]
    own = []
    for i in range(_layers(params)):
        p = params[f"block_{i}"]
        x, idx = _feed_forward(p, _mixer(p, x, arch), arch,
                               None if choices is None else choices[i])
        own.append(idx)
    x = _rmsnorm(x, _f32(params["RMSNorm_0"]["scale"]))
    return (_mm("bsd,dv->bsv", x, _f32(params["lm_head"]["kernel"])),
            jnp.stack(own))


def loss(params, tokens, arch, choices=None):
    """``(mean next-token cross-entropy, idx)`` of ``tokens`` [B, S]."""
    logits, idx = forward(params, tokens, arch, choices)
    return _cross_entropy(logits[:, :-1], tokens[:, 1:]), idx


MIXER_PARTS = ("mixer", "attn", "RMSNorm_0")  # what a layer's first half reads


def loss_and_grad(params, tokens, arch, choices=None):
    """``(loss, its gradient, idx [B, L, S, k])`` of ``tokens`` [B, S],
    float32 at ``PRECISION``: ``loss`` above and its gradient (``choices``
    [B, L, S, k] as the head of this file says), computed in blocks so
    that it fits beside the parameters and compiles in seconds. One
    sequence at a time, gradients summed; within a sequence the two halves
    of a layer are programs of their own, one a kind of half (delta
    attention, latent attention, the dense feed-forward, the experts), run
    forward keeping each half's input, then backward through ``jax.vjp``
    of the same function, which runs the half forward again: what
    ``jax.checkpoint`` around each half would do inside one program,
    without compiling every layer's copy. Call it outside ``jax.jit``."""
    with jax.default_matmul_precision(PRECISION):
        def programs(half):  # (forward -> (x, idx), backward -> (dp, dx))
            return (jax.jit(lambda p, x, c: half(p, x, arch, c)),
                    jax.jit(lambda p, x, c, g: jax.vjp(
                        lambda p, x: half(p, x, arch, c)[0], p, x)[1](g)))

        # jit keys a program by the tree it is given: one a kind of half
        halves = (programs(lambda p, x, arch, c: (_mixer(p, x, arch), None)),
                  programs(_feed_forward))
        embed = jax.jit(lambda table, seq: _f32(table)[seq])
        embed_grad = jax.jit(lambda table, seq, g: jnp.zeros(
            table.shape, jnp.float32).at[seq].add(g))
        head = jax.jit(jax.value_and_grad(_head, argnums=(0, 1, 2)))
        add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
        layers = range(_layers(params))

        def part(i, second):  # the parameters a half reads
            return {name: leaf for name, leaf in params[f"block_{i}"].items()
                    if (name not in MIXER_PARTS) == bool(second)}

        def choice(b, i, second):  # of sequence b in layer i, or None
            return choices[b, i] if choices is not None and second else None

        total, grads, own = 0.0, None, []
        for b, seq in enumerate(tokens):
            seq = seq[None]
            x = embed(params["embed"]["embedding"], seq)
            inputs, idxs = [], []
            for i in layers:
                for second in (0, 1):
                    inputs.append(x)
                    x, idx = halves[second][0](part(i, second), x,
                                               choice(b, i, second))
                idxs.append(idx)
            value, (g_scale, g_kernel, g) = head(
                params["RMSNorm_0"]["scale"], params["lm_head"]["kernel"],
                x, seq)
            one = {"RMSNorm_0": {"scale": g_scale},
                   "lm_head": {"kernel": g_kernel}}
            for i in reversed(layers):
                one[f"block_{i}"] = {}
                for second in (1, 0):
                    g_part, g = halves[second][1](
                        part(i, second), inputs.pop(), choice(b, i, second),
                        g)
                    one[f"block_{i}"].update(g_part)
            one["embed"] = {"embedding": embed_grad(
                params["embed"]["embedding"], seq, g)}
            total = total + value
            grads = one if grads is None else add(grads, one)
            own.append(jnp.stack(idxs))
        n = tokens.shape[0]
        scale = jax.jit(lambda tree: jax.tree_util.tree_map(
            lambda g: g / n, tree))
        return total / n, scale(grads), jnp.stack(own)
