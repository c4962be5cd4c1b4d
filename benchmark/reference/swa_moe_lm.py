"""Plain reference of the window / full attention hybrid decoder with
sparse experts (``model_type laguna``: softmax attention of two kinds in
one model, one leading dense layer, then sparse layers with a shared
expert), holding one chip's share of the experts.

Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no sort, no
ragged product, no ``shard_map``, no ``DistributedOptimizer``, no flax. It
reads the parameter tree the program's ``models/transformer.py`` creates
for the pattern (full, swiglu), (sliding, experts) x 3, (full, experts)...
and writes the equations out (RMSNorm eps 1e-6, pre-norm, sequential
residual):

    x = E[tokens]
    attention of a layer's kind (H query heads: 48 full, 64 sliding; 8
    key/value heads; heads of 128), y = rmsnorm(x) * g1:
        q = y Wq ; k = y Wk ; v = y Wv          no bias, no norm on q or k
        q, k = rope(., the kind's theta) on the first r elements of each
               head (rotate-half), the rest passed through:
               full    r = 64, theta 500000, YaRN
               sliding r = 128, theta 10000, plain
        YaRN over r: p_n = theta^(2n / r), n = 0 .. r/2 - 1
               dim(t) = r ln(original / (2 pi t)) / (2 ln theta)
               low = max(floor(dim(beta_fast)), 0)
               high = min(ceil(dim(beta_slow)), r - 1)
               ramp_n = clip((n - low) / (high - low), 0, 1)
               inv_freq_n = (1 - ramp_n) / p_n + ramp_n / (factor p_n)
               cos and sin of position * inv_freq times attention_factor
        query head h reads key/value head h // (H / 8)
        position i sees position j when j <= i, and in a sliding layer
               also i - j < window (itself and the window - 1 before it)
        o_h = softmax(mask(q_h k^T / sqrt(128))) v
        g = sigmoid(y Wg)                        Wg [d, H]: a gate a head
        x = x + concat_h(g_h o_h) Wo
    layer 0, y = rmsnorm(x) * g2:
        x = x + (silu(y Wg) * (y Wu)) Wd
    layers >= 1:
        s   = sigmoid(y Wr)                       [T, num_experts]
        idx = top_k(s + b)                        b enters the choice only
        w   = s[idx] / (sum(s[idx]) + 1e-20) * moe_routed_scaling_factor
        x   = x + sum over the k whose expert is HELD of w_k * E_idx_k(y)
                + Shared(y)                       E, Shared: SwiGLU
    logits = (rmsnorm(x) * gf) Wh                 (untied head)
    loss   = mean over every position but the last of
             -log softmax(logits)[next token]

What ``config.json`` leaves open and this file assumes (the
configuration's ``assumed`` gives each one's ground): the gate is a
sigmoid a head from the layer's normed input; the router scores by a
sigmoid, normalises over the eight chosen and scales by 2.5, with a
selection bias in the choice only and no groups; the feed-forwards are
SiLU-gated; no norm on q or k.

The share: the tree holds ``experts_held`` experts, those numbered
``expert_offset ..``; the router is as wide as the model has experts and
the weights are normalised over all k chosen. A slot whose expert is not
held adds nothing, here as in the program. Every expert held is applied
to every token and masked: no token is gathered, sorted or dropped.

Attention is computed a key/value head (its H / 8 query heads) and
``ROWS`` query positions at a time, each block's scores against every
key built from positions and masked: 64 heads x 8192 x 8192 scores never
exist at once.

``forward`` and ``loss`` are the equations as one function, for the CPU
tests; ``loss_and_grad`` computes the same loss and gradient in
blocks, for the chip at the timed sizes.

``choices``: a program in bfloat16 sees scores that differ from these in
their third digit, and where a token's eighth and ninth score lie closer
than that it takes the other expert, whose output is not small. A caller
that compares such a program with this reference hands over the program's
choices: the experts are then evaluated under THOSE, while the scores, the
weights made from them and the router's gradient stay this reference's
own, and ``idx`` still returns what this reference would have chosen.

``MANTISSA_BITS`` is the handle of the study that shows which faults a
comparison with this reference can tell
(``benchmark/reference/swa_moe_lm_faults.py``); nothing else sets it.
"""

import math

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6
PRECISION = "highest"  # of every matrix product below
ROWS = 1024  # query positions of one block of scores
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


# What a layer's kind says of its attention: each a function of the kind's
# entry of ``arch["attention"]`` (``sliding_window``, ``rope_theta``,
# ``rotary_dim``, ``yarn``), so that the study of faults can put another
# in its place.
def _window(kind):
    return kind["sliding_window"]


def _theta(kind):
    return float(kind["rope_theta"])


def _rotated_width(kind):
    return kind["rotary_dim"]


def _inv_freq(kind):
    """The rotary's inverse frequencies over the rotated width: plain,
    or YaRN's blend of each with itself over ``factor``."""
    r, theta, yarn = _rotated_width(kind), _theta(kind), kind["yarn"]
    n = jnp.arange(r // 2, dtype=jnp.float32)
    p = theta ** (2.0 * n / r)
    if yarn is None:
        return 1.0 / p

    def dim(t):
        return (r * math.log(yarn["original_max_position_embeddings"]
                             / (2 * math.pi * t)) / (2 * math.log(theta)))

    low = max(math.floor(dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(dim(yarn["beta_slow"])), r - 1)
    ramp = jnp.clip((n - low) / (high - low), 0.0, 1.0)
    return (1.0 - ramp) / p + ramp / (yarn["factor"] * p)


def _cos_sin_factor(kind):
    return 1.0 if kind["yarn"] is None else kind["yarn"]["attention_factor"]


def _rope(x, kind):
    """x: [B, S, H, d]. Rotate-half on the first r elements of each head:
    element n turns with element n + r/2 by ``position * inv_freq_n``."""
    r, s = _rotated_width(kind), x.shape[1]
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * _inv_freq(kind)
    cos = (jnp.cos(angles) * _cos_sin_factor(kind))[:, None, :]
    sin = (jnp.sin(angles) * _cos_sin_factor(kind))[:, None, :]
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., r:]], -1)


def _seen(q_positions, k_positions, kind):
    """[Q, K]: which keys each query sees."""
    behind = q_positions[:, None] - k_positions[None, :]
    if _window(kind) is None:
        return behind >= 0
    return (behind >= 0) & (behind < _window(kind))


def _softmax(scores):
    return jax.nn.softmax(scores, -1)


def _head_gate(y, kernel):
    """[B, S, H]: what each head's output is multiplied by."""
    return jax.nn.sigmoid(_mm("bsd,dh->bsh", y, kernel))


def _heads(kind, h):
    """How many of a layer's ``h`` query heads there are."""
    del kind
    return h


def _swiglu(y, gate, up, down):
    return _mm("...f,fd->...d", jax.nn.silu(_mm("...d,df->...f", y, gate))
               * _mm("...d,df->...f", y, up), down)


def attend(q, k, v, kind):
    """``softmax(mask(q k^T / sqrt(e))) v`` of one kind of layer: q
    [B, S, H, e] against k, v [B, S, shared heads, e], query head h
    reading key/value head h // (H / shared), a key/value head and
    ``ROWS`` query positions at a time. (Public: the family's comparison
    also hands it seeded q, k and v beside the program's own attention,
    where one key at the window's edge shows; ``attention_apart``.)"""
    b, s, h, e = q.shape
    shared, rows = k.shape[2], min(ROWS, s)
    # [key/value head, block of rows, B, rows, its query heads, e]
    q = q.reshape(b, s // rows, rows, shared, h // shared, e).transpose(
        3, 1, 0, 2, 4, 5)
    positions = jnp.arange(s)

    def block(qkv):  # one key/value head's query heads, ``rows`` positions
        q_block, q_positions, k_head, v_head = qkv
        scores = _mm("bqge,bke->bgqk", q_block, k_head) / e ** 0.5
        probs = _softmax(jnp.where(_seen(q_positions, positions, kind),
                                   scores, -jnp.inf))
        return _mm("bgqk,bke->bqge", probs, v_head)

    def head(qkv):
        q_head, k_head, v_head = qkv
        return jax.lax.map(
            lambda qp: jax.checkpoint(block)((*qp, k_head, v_head)),
            (q_head, positions.reshape(s // rows, rows)))

    ctx = jax.lax.map(head, (q, jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    return ctx.transpose(2, 1, 3, 0, 4, 5).reshape(b, s, h, e)


def _attention(p, y, kind):
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    q = _rope(_mm("bsd,dhe->bshe", y, f32(p["query"]["kernel"])), kind)
    k = _rope(_mm("bsd,dhe->bshe", y, f32(p["key"]["kernel"])), kind)
    v = _mm("bsd,dhe->bshe", y, f32(p["value"]["kernel"]))
    h = q.shape[2]
    ctx = attend(q, k, v, kind)
    ctx = ctx * _head_gate(y, f32(p["gate"]["kernel"]))[..., None]
    ctx = jnp.where(jnp.arange(h)[:, None] < _heads(kind, h), ctx, 0.0)
    return _mm("bshe,hed->bsd", ctx, f32(p["out"]["kernel"]))


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


def _mixer(p, x, kind):
    """The first half of a block: ``x + attention(rmsnorm(x))``."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    return x + _attention(
        p["attn"], _rmsnorm(x, f32(p["RMSNorm_0"]["scale"])), kind)


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


def _kind(arch, i):
    """Layer ``i``'s entry of ``arch["attention"]``."""
    return arch["attention"][arch["layer_kinds"][i]]


def forward(params, tokens, arch, choices=None):
    """``(logits [B, S, V], idx [L, B*S, k])`` of ``tokens`` [B, S].
    ``arch``: ``layer_kinds`` (a name a layer), ``attention`` (a name's
    ``sliding_window``, ``rope_theta``, ``rotary_dim`` and ``yarn``: None,
    or ``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    ``beta_slow``, ``attention_factor``), ``num_experts_per_tok``,
    ``routed_scaling_factor``, ``expert_offset``; everything else is read
    off the tree. ``choices`` [L, B*S, k]: see the head of this file (a
    dense layer's row is not read)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    x = f32(params["embed"]["embedding"])[tokens]
    own = []
    for i in range(_layers(params)):
        p = params[f"block_{i}"]
        x, idx = _feed_forward(p, _mixer(p, x, _kind(arch, i)), arch,
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
    (``_mixer`` is one program a kind of attention, ``_feed_forward`` one
    for the dense layer and one for the sparse ones), run forward keeping
    each half's input, then backward through ``jax.vjp`` of the same
    functions, which runs the half forward again: what ``jax.checkpoint``
    around each half would do inside one program, without compiling every
    layer's copy. Call it outside ``jax.jit``."""
    with jax.default_matmul_precision(PRECISION):
        f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
        def programs(half):  # (forward -> (x, idx), backward -> (dp, dx))
            return (jax.jit(lambda p, x, c: half(p, x, c)),
                    jax.jit(lambda p, x, c, g: jax.vjp(
                        lambda p, x: half(p, x, c)[0], p, x)[1](g)))

        def mixer_of(name):
            return lambda p, x, c: (_mixer(p, x, arch["attention"][name]),
                                    None)

        halves = {name: programs(mixer_of(name))
                  for name in arch["attention"]}
        halves[_feed_forward] = programs(
            lambda p, x, c: _feed_forward(p, x, arch, c))
        embed = jax.jit(lambda table, seq: f32(table)[seq])
        embed_grad = jax.jit(lambda table, seq, g: jnp.zeros(
            table.shape, jnp.float32).at[seq].add(g))
        head = jax.jit(jax.value_and_grad(_head, argnums=(0, 1, 2)))
        add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))

        def part(block, mixer):  # the parameters a half reads
            names = {"attn", "RMSNorm_0"}
            return {name: leaf for name, leaf in block.items()
                    if (name in names) == mixer}

        def choice(b, i, half):  # of sequence b in layer i, or None
            if choices is None or half is not _feed_forward:
                return None
            return choices[b, i]

        total, grads, own = 0.0, None, []
        for b, seq in enumerate(tokens):
            seq = seq[None]
            x = embed(params["embed"]["embedding"], seq)
            inputs, idxs = [], []
            for i in range(_layers(params)):
                for half in (arch["layer_kinds"][i], _feed_forward):
                    inputs.append(x)
                    x, idx = halves[half][0](
                        part(params[f"block_{i}"], half is not _feed_forward),
                        x, choice(b, i, half))
                idxs.append(idx)
            value, (g_scale, g_kernel, g) = head(
                params["RMSNorm_0"]["scale"], params["lm_head"]["kernel"],
                x, seq)
            one = {"RMSNorm_0": {"scale": g_scale},
                   "lm_head": {"kernel": g_kernel}}
            for i in reversed(range(_layers(params))):
                one[f"block_{i}"] = {}
                for half in (_feed_forward, arch["layer_kinds"][i]):
                    g_part, g = halves[half][1](
                        part(params[f"block_{i}"], half is not _feed_forward),
                        inputs.pop(), choice(b, i, half), g)
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
