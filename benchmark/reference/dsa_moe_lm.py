"""Plain reference of the learned-sparse-attention decoder with sparse
experts (``Keye-VL-2.0-30B-A3B``'s language model, ``model_type
KeyeVL2``: Qwen3-MoE blocks with DeepSeek Sparse Attention), holding one
chip's share of the experts.

Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no ragged
product, no ``shard_map``, no ``DistributedOptimizer``, no flax. It reads
the parameter tree the program's ``models/transformer.py`` creates for the
pattern (sparse attention, experts) x L and writes the equations out
(RMSNorm eps 1e-6, pre-norm, sequential residual), ``t`` a query's
position and ``s`` a key's:

    x = E[tokens]
    every layer, y = rmsnorm(x) * g1:
        q = y Wq ; k = y Wk ; v = y Wv         32 and 4 and 4 heads of 128
        q, k = rmsnorm over each head (a 128-wide weight each), then
               rotary, rotate-half over the whole head, theta 1e7
        indexer, ŷ = stop_gradient(y):
            qI = ŷ WqI                          16 heads of 64
            kI = layernorm(ŷ WkI)               one head of 64, weight, bias
            qI, kI = rotary on the first 32 of each head, theta 1e7
            w  = ŷ Ww / sqrt(16)
            I[t, s] = sum_j w[t, j] relu(qI_j[t] . kI[s]) / sqrt(64), s <= t
        S_t = the min(t + 1, 2048) keys s <= t of largest I[t, s], equal
              scores to the earlier position (lax.top_k's order)
        o_h[t] = sum_{s in S_t} softmax_s(q_h[t] . k[s] / sqrt(128)) v[s],
                 query head h reading key/value head h // 8
        x = x + concat_h(o_h) Wo
        p[t, s] = stop_gradient(sum_h P_h[t, s]) / 32     s in S_t
        L_I = mean_t KL(p[t, .] || softmax_{S_t} I[t, .])
        y = rmsnorm(x) * g2:
        r   = softmax(y Wr)                    [T, 128], float32
        idx = top_k(r, 8)
        wgt = r[idx] / sum(r[idx])
        x   = x + sum over the k whose expert is HELD of wgt_k * E_idx_k(y)
              E: SwiGLU, 768 wide; no shared expert
    logits = (rmsnorm(x) * gf) Wh              (untied head)
    L_LM = mean over every position but the last of
           -log softmax(logits)[next token]
    loss = L_LM + sum over layers of L_I

What ``config.json`` leaves open and this file assumes (the
configuration's ``assumed`` gives each one's ground): the q/k norm; the
indexer's input taken from ``y``; the LayerNorm on ``kI``, the rotary on
half an indexer head, the ``16^-1/2`` on ``w``, the ``64^-1/2`` on ``I``,
the ReLU, the KL target and the detached input (the DeepSeek-V3.2-Exp
report and its inference code); every layer sparse; the tie rule.

The share: the tree holds ``experts_held`` experts, those numbered
``expert_offset ..``; the router is as wide as the model has experts and
the weights are normalised over all k chosen. A slot whose expert is not
held adds nothing, here as in the program. Every expert held is applied
to every token and masked.

Attention and the indexer are computed ``ROWS`` query positions at a
time, each block's scores against every key: 32 heads x 32768 x 32768
scores never exist at once; the experts ``TOKENS`` tokens at a time.

``choices``: the experts are evaluated under a program's choices where
the caller brings them (its scores, weights and router gradient stay this
reference's own; ``idx`` returns what this reference would choose), as in
the other sparse families. ``selections`` [L, B, S / 32, S] (the
program's packed selection, ``ops/sparse_attention.pack``'s layout): the
main attention and the indexer's loss are then evaluated over the
PROGRAM's ``S_t``, and beside them this reference's own selection is made
and compared with it (``selection_numbers``): a program in bfloat16 ranks
keys whose scores lie closer than its rounding in another order.
``own_losses`` evaluates both losses over this reference's OWN ``S_t``
instead, forward only.

``MANTISSA_BITS`` and the functions marked below are the handles of the
study of what a comparison with this reference can tell
(``benchmark/reference/dsa_moe_lm_faults.py``); nothing else sets them.
"""

import jax
import jax.numpy as jnp
import numpy as np

RMS_EPS = 1e-6
LN_EPS = 1e-6
PRECISION = "highest"  # of every matrix product below
ROWS = 256  # query positions of one block of scores
TOKENS = 2048  # tokens of one block of the experts' products
# None: the operands of a product are taken as they are, float32. A number:
# both are first rounded to that many bits of mantissa at float32's range
# (7: bfloat16's; 3: float8_e4m3's, as a product scaled to its range would
# see them).
MANTISSA_BITS = None
# the rounding of one bfloat16 operand, relative: half of 2^-7
BF16_ROUNDING = 2.0 ** -8


def _mm(spec, a, b):
    """Every matrix product of this file."""
    if MANTISSA_BITS is not None:
        a, b = (jax.lax.reduce_precision(x, 8, MANTISSA_BITS)
                for x in (a, b))
    return jnp.einsum(spec, a, b)


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + RMS_EPS) * scale


def _layernorm(x, scale, bias):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


# The handles of the study of faults: each one line of the equations.
def _theta(arch):
    return float(arch["rope_theta"])


def _qk_norm(x, scale):
    return _rmsnorm(x, scale)


def _detach(y):
    return jax.lax.stop_gradient(y)


def _relu(a):
    return jax.nn.relu(a)


def _index_weights(w):
    return w


def _top_k(arch):
    return arch["index_top_k"]


def _ranked(scores):
    """The scores the selection ranks."""
    return scores


def _sparse():
    """False: every causal key in place of ``S_t``."""
    return True


def _index_loss_weight():
    return 1.0


def _router(logits):
    return jax.nn.softmax(logits, -1)


def _rope(x, width, theta):
    """x: [..., S, H, d]. Rotate-half on the first ``width`` elements of
    each head: element n turns with element n + width/2 by ``position *
    theta^(-2n / width)``."""
    s = x.shape[-3]
    n = jnp.arange(width // 2, dtype=jnp.float32)
    angles = (jnp.arange(s, dtype=jnp.float32)[:, None]
              * theta ** (-2.0 * n / width))
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :width // 2], x[..., width // 2:width]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., width:]], -1)


def _swiglu(y, gate, up, down):
    return _mm("...f,fd->...d", jax.nn.silu(_mm("...d,df->...f", y, gate))
               * _mm("...d,df->...f", y, up), down)


def unpack(words, s):
    """A program's packed selection ``[S / 32, T]`` (bit ``i`` of row ``g
    * 8 + u`` is key ``g * 256 + i * 8 + u``) -> ``[T, S]`` booleans."""
    rows, t = words.shape
    w = words.reshape(rows // 8, 8, t)
    bits = (w[..., None] >> jnp.arange(32, dtype=jnp.int32)) & 1
    return bits.transpose(2, 0, 3, 1).reshape(t, rows * 32)[:, :s] != 0


def _indexer(p, y, arch):
    """``(qI [S, 16, 64], kI [S, 64], w [S, 16])`` of one sequence's ``y``
    [S, d]."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    y = _detach(y)
    width = arch["index_rotary_dim"]
    qi = _rope(_mm("sd,dhe->she", y, f32(p["wq"]["kernel"])), width,
               _theta(arch))
    ki = _layernorm(_mm("sd,de->se", y, f32(p["wk"]["kernel"])),
                    f32(p["k_norm"]["scale"]), f32(p["k_norm"]["bias"]))
    ki = _rope(ki[:, None, :], width, _theta(arch))[:, 0, :]
    w = _mm("sd,dh->sh", y, f32(p["weights_proj"]["kernel"]))
    return qi, ki, _index_weights(w) / qi.shape[1] ** 0.5


def index_scores(qi, ki, w):
    """``I [R, S]`` of ``R`` queries' ``qI [R, 16, 64]``, ``w [R, 16]``
    against every key's ``kI [S, 64]``, unmasked."""
    a = _mm("rhe,se->rhs", qi, ki)
    return jnp.einsum("rhs,rh->rs", _relu(a), w) / qi.shape[-1] ** 0.5


def _allowance(qi, ki, w):
    """``[R, S]``: how far a bfloat16 program's score of each pair may lie
    from this reference's when its operands (qI, kI and w) are each
    rounded to bfloat16: the bound ``BF16_ROUNDING (2 sum_j |w_j| sum_e
    |qI_je kI_e| + sum_j |w_j relu(a_j)|) / sqrt(64)``, never reached."""
    a = _mm("rhe,se->rhs", jnp.abs(qi), jnp.abs(ki))
    return BF16_ROUNDING * (
        2 * jnp.einsum("rhs,rh->rs", a, jnp.abs(w))
        + jnp.abs(index_scores(qi, ki, w))) / qi.shape[-1] ** 0.5


def _own(scores, t_pos, k):
    """``(selected [R, S], the k-th largest score [R])`` of this
    reference's own ranking of the causal keys."""
    s = scores.shape[1]
    masked = jnp.where(jnp.arange(s)[None, :] <= t_pos[:, None], scores,
                       -jnp.inf)
    vals, idx = jax.lax.top_k(_ranked(masked), min(k, s))
    ok = idx <= t_pos[:, None]
    rows = jnp.arange(scores.shape[0])[:, None]
    selected = jnp.zeros(scores.shape, bool).at[rows, idx].set(ok)
    return selected, vals[:, -1]


def attend(q, k, v, selected):
    """``(o [R, H, e], sum over heads of the probabilities [R, S])`` of
    ``R`` queries ``q [R, H, e]`` over keys ``k, v [S, G, e]`` under
    ``selected [R, S]``: query head h reads key/value head h // (H / G),
    a key/value head at a time. (Public: the family's comparison hands it
    the program's seeded probe.)"""
    r, h, e = q.shape
    groups = k.shape[1]
    qg = q.reshape(r, groups, h // groups, e).transpose(1, 0, 2, 3)

    def head(qkv):
        q_head, k_head, v_head = qkv                # [R, g, e], [S, e]
        scores = _mm("rge,se->grs", q_head, k_head) / e ** 0.5
        probs = jax.nn.softmax(jnp.where(selected, scores, -jnp.inf), -1)
        return _mm("grs,se->rge", probs, v_head), jnp.sum(probs, 0)

    ctx, probs = jax.lax.map(head, (qg, jnp.moveaxis(k, 1, 0),
                                    jnp.moveaxis(v, 1, 0)))
    return ctx.transpose(1, 0, 2, 3).reshape(r, h, e), jnp.sum(probs, 0)


def attend_probe(q, k, v, words):
    """The attention of one sequence (q [S, H, e], k, v [S, G, e]) under
    a program's packed selection, ``ROWS`` queries at a time: [S, H, e]."""
    s = q.shape[0]
    rows = min(ROWS, s)

    def block(args):
        q_block, w_block, t_pos = args
        causal = jnp.arange(s)[None, :] <= t_pos[:, None]
        return attend(q_block, k, v, unpack(w_block, s) if _sparse()
                      else causal)[0]

    out = jax.lax.map(jax.checkpoint(block), (
        q.reshape(s // rows, rows, *q.shape[1:]),
        words.reshape(words.shape[0], s // rows, rows).transpose(1, 0, 2),
        jnp.arange(s).reshape(s // rows, rows)))
    return out.reshape(q.shape)


def _sparse_layer(p, y, arch, words=None):
    """``(attention [S, d], L_I, selection numbers)`` of one sequence's
    ``y`` [S, d]: over the program's selection where ``words`` [S / 32, S]
    is given, else over this reference's own."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    theta = _theta(arch)
    q = _mm("sd,dhe->she", y, f32(p["query"]["kernel"]))
    k = _mm("sd,dhe->she", y, f32(p["key"]["kernel"]))
    v = _mm("sd,dhe->she", y, f32(p["value"]["kernel"]))
    q = _rope(_qk_norm(q, f32(p["query_norm"]["scale"])), q.shape[-1], theta)
    k = _rope(_qk_norm(k, f32(p["key_norm"]["scale"])), k.shape[-1], theta)
    qi, ki, w = _indexer(p["indexer"], y, arch)
    s, heads = q.shape[0], q.shape[1]
    rows = min(ROWS, s)
    k_top = _top_k(arch)

    def block(args):
        q_block, qi_block, w_block, t_pos, w_words = args
        scores = index_scores(qi_block, ki, w_block)
        own, kth = _own(scores, t_pos, k_top)
        own = jax.lax.stop_gradient(own)
        selected = own if words is None else unpack(w_words, s)
        causal = jnp.arange(s)[None, :] <= t_pos[:, None]
        ctx, probs = attend(q_block, k, v,
                            selected if _sparse() else causal)
        prob = jax.lax.stop_gradient(probs / heads)
        log_q = jax.nn.log_softmax(jnp.where(selected, scores, -jnp.inf), -1)
        live = selected & (prob > 0)
        p_safe = jnp.where(live, prob, 1.0)
        kl = jnp.sum(jnp.where(live, prob * (jnp.log(p_safe)
                                             - jnp.where(live, log_q, 0.0)),
                               0.0), -1)
        # the program's keys this reference ranks clearly below its own
        # k-th, and how many keys each side takes
        clear = jax.lax.stop_gradient(
            scores < kth[:, None] - _allowance(qi_block, ki, w_block))
        counts = jnp.stack([jnp.sum(selected & clear),
                            jnp.sum(jnp.abs(jnp.sum(selected, -1)
                                            - jnp.sum(own, -1))),
                            jnp.sum(selected)]).astype(jnp.float32)
        return ctx, kl, counts

    n = s // rows
    split = lambda a: a.reshape(n, rows, *a.shape[1:])  # noqa: E731
    w_words = (jnp.zeros((n, 1, rows), jnp.int32) if words is None else
               words.reshape(words.shape[0], n, rows).transpose(1, 0, 2))
    ctx, kl, counts = jax.lax.map(jax.checkpoint(block), (
        split(q), split(qi), split(w), split(jnp.arange(s)), w_words))
    out = _mm("she,hed->sd", ctx.reshape(s, heads, -1),
              f32(p["out"]["kernel"]))
    return out, _index_loss_weight() * jnp.mean(kl), jnp.sum(counts, 0)


def _mixer(p, x, arch, words=None):
    """The first half of a block: ``(x + attention(rmsnorm(x)), L_I,
    selection counts)`` for ``x`` [B, S, d]; ``words`` [B, S / 32, S]."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    y = _rmsnorm(x, f32(p["RMSNorm_0"]["scale"]))
    outs, kls, counts = [], [], []
    for b in range(x.shape[0]):
        out, kl, c = _sparse_layer(p["attn"], y[b], arch,
                                   None if words is None else words[b])
        outs.append(out)
        kls.append(kl)
        counts.append(c)
    return (x + jnp.stack(outs), jnp.mean(jnp.stack(kls)),
            jnp.sum(jnp.stack(counts), 0))


def _route(p, y, arch, choice=None):
    """``(idx [T, k], w [T, k], own [T, k])``: each token's experts, their
    weights from the scores, and the experts this reference chooses:
    ``idx`` is ``own``, or ``choice`` where the caller brings one."""
    r = _router(_mm("td,de->te", y, jnp.asarray(p["router"], jnp.float32)))
    _, own = jax.lax.top_k(r, arch["num_experts_per_tok"])
    idx = own if choice is None else choice
    chosen = jnp.take_along_axis(r, idx, -1)
    return idx, chosen / jnp.sum(chosen, -1, keepdims=True), own


def _routed(p, y, arch, choice=None):
    """``(the held experts' part [T, d], own choice [T, k])``, ``TOKENS``
    tokens at a time."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    idx, w, own = _route(p, y, arch, choice)
    experts = (jnp.arange(p["gate_proj"].shape[0]), p["gate_proj"],
               p["up_proj"], p["down_proj"])

    def block(args):
        y_block, idx_block, w_block = args

        def one(out, expert):  # a loop over the experts held, each masked
            e, gate, up, down = expert
            weight = jnp.sum(jnp.where(
                idx_block == arch["expert_offset"] + e, w_block, 0.0), -1)
            return out + weight[:, None] * _swiglu(
                y_block, f32(gate), f32(up), f32(down)), None

        return jax.lax.scan(one, jnp.zeros_like(y_block), experts)[0]

    t = y.shape[0]
    rows = TOKENS if t % TOKENS == 0 else t
    split = lambda a: a.reshape(t // rows, rows, *a.shape[1:])  # noqa: E731
    out = jax.lax.map(jax.checkpoint(block), (split(y), split(idx),
                                              split(w)))
    return out.reshape(y.shape), own


def _feed_forward(p, x, arch, choice=None):
    """The second half: ``(x + experts(rmsnorm(x)), idx [B*S, k])``."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    y = _rmsnorm(x, f32(p["RMSNorm_1"]["scale"]))
    b, s, d = y.shape
    routed, idx = _routed(p["experts"], y.reshape(b * s, d), arch, choice)
    return x + routed.reshape(b, s, d), idx


def _cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def _head(scale, kernel, x, tokens):
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    logits = _mm("bsd,dv->bsv", _rmsnorm(x, f32(scale)), f32(kernel))
    return _cross_entropy(logits[:, :-1], tokens[:, 1:])


def _layers(params):
    return sum(name.startswith("block_") for name in params)


def forward(params, tokens, arch, choices=None, selections=None):
    """``(logits [B, S, V], L_I [L], idx [L, B*S, k], selection counts
    [L, 3])`` of ``tokens`` [B, S]. ``arch``: ``rope_theta``,
    ``index_top_k``, ``index_rotary_dim``, ``num_experts_per_tok``,
    ``expert_offset``; everything else is read off the tree.
    ``choices`` [L, B*S, k] and ``selections`` [L, B, S / 32, S]: see the
    head of this file."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    x = f32(params["embed"]["embedding"])[tokens]
    kls, own, counts = [], [], []
    for i in range(_layers(params)):
        p = params[f"block_{i}"]
        x, kl, c = _mixer(p, x, arch,
                          None if selections is None else selections[i])
        x, idx = _feed_forward(p, x, arch,
                               None if choices is None else choices[i])
        kls.append(kl)
        own.append(idx)
        counts.append(c)
    x = _rmsnorm(x, f32(params["RMSNorm_0"]["scale"]))
    return (_mm("bsd,dv->bsv", x, f32(params["lm_head"]["kernel"])),
            jnp.stack(kls), jnp.stack(own), jnp.stack(counts))


def loss(params, tokens, arch, choices=None, selections=None):
    """``(L_LM + sum L_I, (L_LM, L_I [L], idx))`` of ``tokens`` [B, S]."""
    logits, kls, idx, _ = forward(params, tokens, arch, choices, selections)
    lm = _cross_entropy(logits[:, :-1], tokens[:, 1:])
    return lm + jnp.sum(kls), (lm, kls, idx)


def selection_numbers(counts):
    """``{"selection_apart": the share of the program's selected keys this
    reference scores clearly (by more than bfloat16 operands allow) below
    its own k-th, "selection_size_apart": the keys by which the two
    selections' sizes differ, over the program's}`` from ``forward``'s or
    ``loss_and_grad``'s counts [L, 3], the largest layer's."""
    counts = np.asarray(counts, np.float64)
    return {"selection_apart": float(np.max(counts[:, 0] / counts[:, 2])),
            "selection_size_apart": float(np.max(counts[:, 1]
                                                 / counts[:, 2]))}


def own_losses(params, tokens, arch, choices=None):
    """``(L_LM, L_I [L])`` of ``tokens`` [B, S] with the main attention and
    the indexer's loss over this reference's OWN selection, the experts
    under ``choices`` [B, L, S, k] where given: forward only, in
    ``loss_and_grad``'s blocks (one sequence and one half of a block a
    program, jitted anew at every call). Call it outside ``jax.jit``."""
    with jax.default_matmul_precision(PRECISION):
        f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
        mixer = jax.jit(lambda p, x: _mixer(p, x, arch)[:2])
        ff = jax.jit(lambda p, x, c: _feed_forward(p, x, arch, c)[0])
        embed = jax.jit(lambda table, seq: f32(table)[seq])
        head = jax.jit(_head)
        lm, kls = 0.0, 0.0
        for b, seq in enumerate(tokens):
            seq = seq[None]
            x = embed(params["embed"]["embedding"], seq)
            seq_kls = []
            for i in range(_layers(params)):
                p = params[f"block_{i}"]
                x, kl = mixer({n: p[n] for n in ("attn", "RMSNorm_0")}, x)
                seq_kls.append(kl)
                x = ff({n: p[n] for n in ("experts", "RMSNorm_1")}, x,
                       None if choices is None else choices[b, i])
            lm = lm + head(params["RMSNorm_0"]["scale"],
                           params["lm_head"]["kernel"], x, seq)
            kls = kls + jnp.stack(seq_kls)
        return lm / tokens.shape[0], kls / tokens.shape[0]


def loss_and_grad(params, tokens, arch, choices=None, selections=None):
    """``(L_LM, L_I [L], the gradient of L_LM + sum L_I, idx [B, L, S, k],
    selection counts [L, 3])`` of ``tokens`` [B, S], float32 at
    ``PRECISION``, computed in blocks so that it fits beside the
    parameters: one sequence at a time, gradients summed; each half of a
    block a program of its own, run forward keeping its input, then
    backward through ``jax.vjp`` of the same function (``jax.checkpoint``
    around each half, without compiling every layer's copy).
    ``choices`` [B, L, S, k] and ``selections`` [L, B, S / 32, S] as the
    head of this file says. Call it outside ``jax.jit``."""
    with jax.default_matmul_precision(PRECISION):
        f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731

        def mixer(p, x, words):
            x, kl, counts = _mixer(p, x, arch, words)
            return (x, kl), counts

        mixer_fwd = jax.jit(mixer)
        mixer_bwd = jax.jit(lambda p, x, words, g: jax.vjp(
            lambda p, x: mixer(p, x, words)[0], p, x)[1](
                (g, jnp.ones((), jnp.float32))))
        ff = lambda p, x, c: _feed_forward(p, x, arch, c)  # noqa: E731
        ff_fwd = jax.jit(ff)
        ff_bwd = jax.jit(lambda p, x, c, g: jax.vjp(
            lambda p, x: ff(p, x, c)[0], p, x)[1](g))
        embed = jax.jit(lambda table, seq: f32(table)[seq])
        embed_grad = jax.jit(lambda table, seq, g: jnp.zeros(
            table.shape, jnp.float32).at[seq].add(g))
        head = jax.jit(jax.value_and_grad(_head, argnums=(0, 1, 2)))
        add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))

        def part(block, first):  # the parameters a half reads
            return {name: leaf for name, leaf in block.items()
                    if (name in {"attn", "RMSNorm_0"}) == first}

        layers = _layers(params)
        lm, kls, grads, own = 0.0, 0.0, None, []
        counts = 0.0
        for b, seq in enumerate(tokens):
            seq = seq[None]
            x = embed(params["embed"]["embedding"], seq)
            inputs, idxs, seq_kls, seq_counts = [], [], [], []
            for i in range(layers):
                words = None if selections is None else selections[i][b:b + 1]
                p = params[f"block_{i}"]
                inputs.append(x)
                (x, kl), c = mixer_fwd(part(p, True), x, words)
                seq_kls.append(kl)
                seq_counts.append(c)
                inputs.append(x)
                x, idx = ff_fwd(part(p, False), x,
                                None if choices is None else choices[b, i])
                idxs.append(idx)
            value, (g_scale, g_kernel, g) = head(
                params["RMSNorm_0"]["scale"], params["lm_head"]["kernel"],
                x, seq)
            one = {"RMSNorm_0": {"scale": g_scale},
                   "lm_head": {"kernel": g_kernel}}
            for i in reversed(range(layers)):
                p = params[f"block_{i}"]
                words = None if selections is None else selections[i][b:b + 1]
                g_ff, g = ff_bwd(part(p, False), inputs.pop(),
                                 None if choices is None else choices[b, i],
                                 g)
                g_mix, g = mixer_bwd(part(p, True), inputs.pop(), words, g)
                one[f"block_{i}"] = {**g_mix, **g_ff}
            one["embed"] = {"embedding": embed_grad(
                params["embed"]["embedding"], seq, g)}
            lm = lm + value
            kls = kls + jnp.stack(seq_kls)
            counts = counts + jnp.stack(seq_counts)
            grads = one if grads is None else add(grads, one)
            own.append(jnp.stack(idxs))
        n = tokens.shape[0]
        scale = jax.jit(lambda tree: jax.tree_util.tree_map(
            lambda g: g / n, tree))
        return lm / n, kls / n, scale(grads), jnp.stack(own), counts
