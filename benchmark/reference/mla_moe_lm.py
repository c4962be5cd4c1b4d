"""Plain reference of the DeepSeek-V3-type decoder (``model_type
deepseek_v3``: latent attention, one leading dense layer, then sparse
layers with shared experts), holding one chip's share of the experts.

Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no sort, no
ragged product, no ``shard_map``, no ``DistributedOptimizer``, no flax. It
reads the parameter tree the program's ``models/transformer.py`` creates
for the pattern (mla, swiglu), (mla, experts)... and writes the equations
out (RMSNorm eps 1e-6, pre-norm, sequential residual):

    x = E[tokens]
    attention (H heads), y = rmsnorm(x) * g1:
        q = y Wq                        -> per head [q_nope | q_pe]
        [c | k_pe] = y Wkva ;  c = rmsnorm(c) * gc
        [k_nope | v] per head = c Wkvb
        q_pe, k_pe = rope(., theta, interleaved pairs); k_pe is one head,
                     shared by all H
        o = softmax(causal([q_nope|q_pe] [k_nope|k_pe]^T / sqrt(d_qk))) v
        x = x + concat(o) Wo
    layer 0, y = rmsnorm(x) * g2:
        x = x + (silu(y Wg) * (y Wu)) Wd
    layers >= 1:
        s   = sigmoid(y Wr)                       [T, n_routed_experts]
        idx = top_k(s + b)                        b enters the choice only
        w   = s[idx] / (sum(s[idx]) + 1e-20) * routed_scaling_factor
        x   = x + sum over the k whose expert is HELD of w_k * E_idx_k(y)
                + Shared(y)                       E, Shared: SwiGLU
    logits = (rmsnorm(x) * gf) Wh                 (untied head)
    loss   = mean over every position but the last of
             -log softmax(logits)[next token]

The share: the tree holds ``experts_held`` experts, those numbered
``expert_offset ..``; the router is as wide as the model has experts and
the weights are normalised over all k chosen. A slot whose expert is not
held adds nothing, here as in the program. Every expert held is applied
to every token and masked: no token is gathered, sorted or dropped.

``forward`` and ``loss`` are the equations as one function, for the CPU
tests; ``loss_and_grad`` computes the same loss and gradient in
blocks, for the chip at the timed sizes.

``choices``: a program in bfloat16 sees scores that differ from these in
their third digit, and where a token's sixth and seventh score lie closer
than that it takes the other expert, whose output is not small. A caller
that compares such a program with this reference hands over the program's
choices: the experts are then evaluated under THOSE, while the scores, the
weights made from them and the router's gradient stay this reference's
own, and ``idx`` still returns what this reference would have chosen.

``MANTISSA_BITS`` is the handle of the study that shows which faults a
comparison with this reference can tell
(``benchmark/reference/mla_moe_lm_faults.py``); nothing else sets it.
"""

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6
PRECISION = "highest"  # of every matrix product below
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


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + RMS_EPS) * scale


def _rope_interleaved(x, theta):
    """x: [B, S, H, d]. The pair (x[2i], x[2i+1]) turns by
    ``position * theta^(-2i/d)``; the result keeps the published layout,
    first elements of all pairs, then second elements."""
    s, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _softmax(scores):
    return jax.nn.softmax(scores, -1)


def _swiglu(y, gate, up, down):
    return _mm("...f,fd->...d", jax.nn.silu(_mm("...d,df->...f", y, gate))
               * _mm("...d,df->...f", y, up), down)


def _attention(p, y, arch):
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    nope, rank = arch["qk_nope_head_dim"], arch["kv_lora_rank"]
    q = _mm("bsd,dhe->bshe", y, f32(p["q_proj"]["kernel"]))
    latent = _mm("bsd,de->bse", y, f32(p["kv_a_proj_with_mqa"]["kernel"]))
    c = _rmsnorm(latent[..., :rank], f32(p["kv_a_layernorm"]["scale"]))
    kv = _mm("bsr,rhe->bshe", c, f32(p["kv_b_proj"]["kernel"]))
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = _rope_interleaved(q[..., nope:], arch["rope_theta"])
    k_pe = _rope_interleaved(latent[..., None, rank:], arch["rope_theta"])
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
    return _mm("hbqe,hed->bqd", ctx, f32(p["o_proj"]["kernel"]))


def _scores(y, router):
    return jax.nn.sigmoid(_mm("td,de->te", y, router))


def _route(p, y, arch, choice=None):
    """``(idx [T, k], w [T, k], own [T, k])``: each token's experts, their
    weights from the scores alone, and the experts this reference chooses
    by score plus selection bias: ``idx`` is ``own``, or ``choice`` where
    the caller brings one."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    s = _scores(y, f32(p["router"]))
    bias = jax.lax.stop_gradient(f32(p["e_score_correction_bias"]))
    _, own = jax.lax.top_k(s + bias, arch["num_experts_per_tok"])
    idx = own if choice is None else choice
    chosen = jnp.take_along_axis(s, idx, -1)
    return idx, (chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
                 * arch["routed_scaling_factor"]), own


def _routed(p, y, arch, choice=None):
    """``(the held experts' part of the layer's result [T, d], this
    reference's own choice [T, k])`` for ``y`` [T, d]."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    idx, w, own = _route(p, y, arch, choice)

    def one(out, expert):  # a loop over the experts held, each masked
        e, gate, up, down = expert
        weight = jnp.sum(
            jnp.where(idx == arch["expert_offset"] + e, w, 0.0), -1)
        return out + weight[:, None] * _swiglu(
            y, f32(gate), f32(up), f32(down)), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(y),
        (jnp.arange(p["gate_proj"].shape[0]), p["gate_proj"], p["up_proj"],
         p["down_proj"]))
    return out, own


def _shared(p, y):
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    return _swiglu(y, *(f32(p[name]["kernel"])
                        for name in ("gate_proj", "up_proj", "down_proj")))


def _mixer(p, x, arch):
    """The first half of a block: ``x + attention(rmsnorm(x))``."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    return x + _attention(
        p["attn"], _rmsnorm(x, f32(p["RMSNorm_0"]["scale"])), arch)


def _feed_forward(p, x, arch, choice=None):
    """The second half: ``(x + feed_forward(rmsnorm(x)), idx)``; ``idx``
    [B*S, k] is this reference's own choice (zeros for the dense layer),
    ``choice`` [B*S, k] the one the experts are evaluated under instead."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    y = _rmsnorm(x, f32(p["RMSNorm_1"]["scale"]))
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
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    logits = _mm("bsd,dv->bsv", _rmsnorm(x, f32(scale)), f32(kernel))
    return _cross_entropy(logits[:, :-1], tokens[:, 1:])


def _layers(params):
    return sum(name.startswith("block_") for name in params)


def forward(params, tokens, arch, choices=None):
    """``(logits [B, S, V], idx [L, B*S, k])`` of ``tokens`` [B, S].
    ``arch``: ``qk_nope_head_dim``, ``kv_lora_rank``, ``rope_theta``,
    ``num_experts_per_tok``, ``routed_scaling_factor``, ``expert_offset``;
    everything else is read off the tree. ``choices`` [L, B*S, k]: see the
    head of this file (a dense layer's row is not read)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    x = f32(params["embed"]["embedding"])[tokens]
    own = []
    for i in range(_layers(params)):
        p = params[f"block_{i}"]
        x, idx = _feed_forward(p, _mixer(p, x, arch), arch,
                               None if choices is None else choices[i])
        own.append(idx)
    x = _rmsnorm(x, f32(params["RMSNorm_0"]["scale"]))
    return (_mm("bsd,dv->bsv", x, f32(params["lm_head"]["kernel"])),
            jnp.stack(own))


def loss(params, tokens, arch, choices=None):
    """``(mean next-token cross-entropy, idx)`` of ``tokens`` [B, S]."""
    logits, idx = forward(params, tokens, arch, choices)
    return _cross_entropy(logits[:, :-1], tokens[:, 1:]), idx


def loss_and_grad(params, tokens, arch, choices=None):
    """``(loss, its gradient, idx [B, L, S, k])`` of
    ``tokens`` [B, S], float32 at ``PRECISION``: ``loss`` above and its
    gradient (``choices`` [B, L, S, k] as the head of this file says),
    computed in blocks so that it fits beside the parameters
    and compiles in seconds. One sequence at a time, gradients summed;
    within a sequence the two halves of a block are programs of their own
    (``_mixer`` is one program for every layer, ``_feed_forward`` one for
    the dense layer and one for the sparse ones), run forward keeping
    each half's input, then backward through ``jax.vjp`` of the same
    functions, which runs the half forward again: what ``jax.checkpoint``
    around each half would do inside one program, without compiling every
    layer's copy. Call it outside ``jax.jit``."""
    with jax.default_matmul_precision(PRECISION):
        f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
        def programs(half):  # (forward -> (x, idx), backward -> (dp, dx))
            return (jax.jit(lambda p, x, c: half(p, x, arch, c)),
                    jax.jit(lambda p, x, c, g: jax.vjp(
                        lambda p, x: half(p, x, arch, c)[0], p, x)[1](g)))

        halves = {_mixer: programs(lambda p, x, arch, c: (
            _mixer(p, x, arch), None)), _feed_forward: programs(_feed_forward)}
        embed = jax.jit(lambda table, seq: f32(table)[seq])
        embed_grad = jax.jit(lambda table, seq, g: jnp.zeros(
            table.shape, jnp.float32).at[seq].add(g))
        head = jax.jit(jax.value_and_grad(_head, argnums=(0, 1, 2)))
        add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))

        def part(block, half):  # the parameters a half reads
            mixer = {"attn", "RMSNorm_0"}
            return {name: leaf for name, leaf in block.items()
                    if (name in mixer) == (half is _mixer)}

        def choice(b, i, half):  # of sequence b in layer i, or None
            if choices is None or half is _mixer:
                return None
            return choices[b, i]

        total, grads, own = 0.0, None, []
        for b, seq in enumerate(tokens):
            seq = seq[None]
            x = embed(params["embed"]["embedding"], seq)
            inputs, idxs = [], []
            for i in range(_layers(params)):
                for half in (_mixer, _feed_forward):
                    inputs.append(x)
                    x, idx = halves[half][0](
                        part(params[f"block_{i}"], half), x,
                        choice(b, i, half))
                idxs.append(idx)
            value, (g_scale, g_kernel, g) = head(
                params["RMSNorm_0"]["scale"], params["lm_head"]["kernel"],
                x, seq)
            one = {"RMSNorm_0": {"scale": g_scale},
                   "lm_head": {"kernel": g_kernel}}
            for i in reversed(range(_layers(params))):
                one[f"block_{i}"] = {}
                for half in (_feed_forward, _mixer):
                    g_part, g = halves[half][1](
                        part(params[f"block_{i}"], half), inputs.pop(),
                        choice(b, i, half), g)
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
