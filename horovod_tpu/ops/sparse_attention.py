"""Attention over a learned selection of keys, as Pallas TPU kernels.

DeepSeek Sparse Attention (the lightning indexer and token-level top-k of
the DeepSeek-V3.2-Exp report): a small indexer scores every causal pair,

    I[t, s] = scale_i * sum_j w[t, j] * relu(qI_j[t] . kI[s])     s <= t

float32, and query ``t`` attends to ``S_t``, the ``min(t + 1, top_k)``
positions ``s <= t`` of largest ``I[t, s]`` (ties to the earlier
position), with every query head of the token over the same ``S_t``. The
indexer learns from its own loss, the KL divergence from the main
attention's head-averaged probabilities over ``S_t`` (held constant) to
``softmax`` over ``S_t`` of its scores:

    p[t, s]  = mean_h P_h[t, s]                  s in S_t, no gradient
    KL[t]    = sum_s p log p - sum_s p I[t, s] + logsumexp_{s in S_t} I[t, s]

``sparse_attention`` returns the attention, ``KL`` a query and the
selection (packed, below); the gradient of the attention reaches q, k and
v alone (a top-k passes none to the scores) and that of ``KL`` the
indexer alone.

On the TPU the work is five kernels over blocks of a query block and a
key block, never the ``S x S`` matrix:

* ``select`` (scope ``hvd_dsa_topk``): for a block of 128 queries, the
  scores of every causal key in VMEM (``[S, 128]`` int32, a float's bits
  in an order-keeping integer form), the ``top_k``-th largest key a query
  by a bitwise search (32 counting passes over the causal keys: the
  largest value with at least ``top_k`` keys at or above it), then the
  position up to which the keys equal to it are taken (a 15-pass search
  over positions), the log-sum-exp of the selected scores, and the
  selection packed into bits: ``words[b, g * 8 + u, t]`` bit ``i`` is key
  ``s = g * 256 + i * 8 + u`` (``[S / 32, S]`` int32, 128 MB at 32k).
  Packed along keys, a key block's mask is 32 shifts of ``[8, block_q]``
  rows stacked down sublanes (``_unpack``).
* ``_fwd_kernel``, ``_dq_kernel``, ``_dkv_kernel`` (scope
  ``hvd_attn_sparse/attn``): flash attention (``ops/flash_attention.py``'s
  transposed score tiles and online softmax) under the selection's mask,
  the causal block schedule's skipped blocks fetching nothing; q, k, v
  and o in the model's own ``[B, S, H * D]`` layout (a head a block of
  ``D`` columns, no copy around the kernels). Every head reads the same
  selection, so a grid step runs as many query heads as fit VMEM against
  one block pair (``_heads_per_step``: whole groups of a key/value
  head's query heads where they fit, all 32 at 32 heads of 128 over 4):
  the pair's words are unpacked once into an additive mask and each
  shared k and v tile is read once, and a group's heads follow one
  another in the step's body (``_each_head``). The backward is two
  kernels, dQ with the query block resident and dK, dV with the key block
  resident over the query blocks, the group's heads summed inside the
  step. A block pair under the diagonal runs whether or not a key of it
  was selected: with random weights every one holds selected keys.
* ``_kl_fwd_kernel``, ``_kl_bwd_kernel`` (scope ``hvd_dsa_index``): the
  head-averaged probabilities of a block pair, the indexer's scores of
  the pair again, and the KL's two sums a query; backward, ``dI = g
  (softmax_S(I) - p)`` and from it dqI, dw (a query block's, resident)
  and dkI (the whole sequence's, resident in VMEM, 16 MiB at 32k). The
  grid is (b, q-block, kv-block, head block), a step running as many
  query heads as the KL's VMEM count lets (``_kl_step_bytes``; all 32 at
  32 heads of 128 over 4, so q is fetched once a query block): each
  head's probabilities from the forward's log-sum-exp, against its
  key/value head's k columns, are summed in head order into one float32
  tile (``_probs_tile``), the head blocks innermost where the heads take
  several steps, and the pair's mask is unpacked once, at its last head
  block, for the sum and for the backward's ``softmax_S(I)``.

Elsewhere, and where the sequence does not tile the blocks (with a
``FlashFallbackWarning`` when the kernel was asked for), ``_plain`` gives
the same function in ``jax.numpy``: the scores, ``lax.top_k`` and a
gather of the selected keys, differentiated by autodiff.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.telemetry import scopes

BLOCK = 512         # query and key blocks of the attention and KL kernels
SELECT_BLOCK_Q = 128  # queries of one selection step
GROUP = 256         # keys of one row of words: 32 bits x 8 sublanes
NEG_INF = fa.NEG_INF
INT_MIN = -2 ** 31
_NT = fa._NT
_TN = fa._TN
_VMEM_BYTES = 100 << 20  # of v5e's 128 MiB; the largest kernel takes ~50
# the names ``checkpoint_name`` gives the kernels' selection and its
# scores' log-sum-exp: a layer recomputed in the backward pass
# (``models/transformer.AttentionConfig.recompute``) keeps these and runs
# the selection once
SELECTION = "dsa_selection"
SELECTION_LSE = "dsa_selection_lse"
SAVED = (SELECTION, SELECTION_LSE)


def words_rows(s):
    """Rows of the packed selection of ``s`` keys: 8 a group of 256."""
    return -(-s // GROUP) * 8


def pack(selected):
    """``selected [B, T, S]`` booleans (query, key) -> ``words [B,
    words_rows(S), T]`` int32, bit ``i`` of row ``g * 8 + u`` key ``g *
    256 + i * 8 + u``."""
    b, t, s = selected.shape
    rows = words_rows(s)
    sel = jnp.pad(selected, ((0, 0), (0, 0), (0, rows * 32 - s)))
    sel = sel.reshape(b, t, rows // 8, 32, 8).astype(jnp.int32)
    word = jnp.sum(sel << jnp.arange(32, dtype=jnp.int32)[:, None], axis=3)
    return word.transpose(0, 2, 3, 1).reshape(b, rows, t)


def unpack(words, s):
    """The inverse of ``pack``: ``[B, T, S]`` booleans."""
    b, rows, t = words.shape
    w = words.reshape(b, rows // 8, 8, t)
    bits = (w[..., None] >> jnp.arange(32, dtype=jnp.int32)) & 1
    # [B, group, u, T, bit] -> [B, T, group, bit, u]
    bits = bits.transpose(0, 3, 1, 4, 2).reshape(b, t, rows * 32)
    return bits[..., :s] != 0


def index_scores(q_index, k_index, w, scale):
    """``I [B, T, S]`` float32 of ``q_index [B, T, HI, DI]``, ``k_index
    [B, S, DI]`` and ``w [B, T, HI]``, every pair (the caller masks)."""
    a = jnp.einsum("bthd,bsd->bths", q_index, k_index,
                   preferred_element_type=jnp.float32)
    return scale * jnp.einsum("bths,bth->bts", jax.nn.relu(a),
                              w.astype(jnp.float32))


# ---------------------------------------------------------------------------
# the plain path

def _plain(q, k, v, q_index, k_index, w, top_k, scale_i):
    """``(out [B, S, H, D], kl [B, S], words)`` in ``jax.numpy``: the
    scores of every pair, ``lax.top_k`` (equal scores: the earlier
    position first), the selected keys gathered, autodiff for the
    gradients."""
    b, s, h, d = q.shape
    g = h // k.shape[2]
    scores = index_scores(q_index, k_index, w, scale_i)
    t_pos = jnp.arange(s)[:, None]
    causal = jnp.arange(s)[None, :] <= t_pos
    kk = min(top_k, s)
    with scopes.device(scopes.DSA_TOPK):
        _, idx = jax.lax.top_k(
            jnp.where(causal, jax.lax.stop_gradient(scores), -jnp.inf), kk)
        ok = idx <= t_pos                                    # [B, S, kk]
        rows = jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None]
        words = checkpoint_name(pack(jnp.zeros((b, s, s), bool).at[
            (*rows, idx)].set(ok)), SELECTION)
    with scopes.device(scopes.ATTN_SPARSE):
        # gathered in float32: the gradient's way back is a scatter-add,
        # which would sum a key's queries in the operands' precision
        take = jax.vmap(lambda x, i: x.astype(jnp.float32)[i])
        k_sel, v_sel = take(k, idx), take(v, idx)            # [B,S,kk,G,D]
        qg = q.reshape(b, s, k.shape[2], g, d)
        logits = jnp.einsum("btkgd,btskd->btkgs", qg, k_sel,
                            preferred_element_type=jnp.float32) / math.sqrt(d)
        logits = jnp.where(ok[:, :, None, None, :], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("btkgs,btskd->btkgd", probs, v_sel)
        out = out.reshape(b, s, h, d).astype(q.dtype)
    with scopes.device(scopes.DSA_INDEX):
        p = jax.lax.stop_gradient(jnp.mean(probs, axis=(2, 3)))  # [B,S,kk]
        chosen = jnp.take_along_axis(scores, idx, axis=-1)
        log_q = jax.nn.log_softmax(jnp.where(ok, chosen, -jnp.inf), axis=-1)
        live = ok & (p > 0)
        p_safe = jnp.where(live, p, 1.0)
        kl = jnp.sum(jnp.where(live, p * (jnp.log(p_safe)
                                          - jnp.where(live, log_q, 0.0)),
                               0.0), axis=-1)
    return out, kl, words


# ---------------------------------------------------------------------------
# kernels

def _unpack(words, rows):
    """``words [rows // 32, n]`` -> ``[rows, n]`` int32 of 0 and 1: the
    mask of a key block of ``rows`` keys (a multiple of ``GROUP``)."""
    pieces = []
    for g in range(rows // GROUP):
        part = words[g * 8:(g + 1) * 8, :]
        pieces += [jnp.right_shift(part, i) & 1 for i in range(32)]
    return jnp.concatenate(pieces, axis=0)


def _index_tile(ki, qi_ref, w_ref, heads, scale):
    """``I^T [c, block_q]`` float32 of keys ``ki [c, DI]`` against the
    block's queries (``qi_ref [1, HI, block_q, DI]``, ``w_ref [1, HI,
    block_q]``). One function for every kernel, so that the selection's
    and the KL's scores are the same numbers."""
    total = None
    for j in range(heads):
        a = jax.lax.dot_general(ki, qi_ref[0, j], _NT,
                                preferred_element_type=jnp.float32)
        term = jnp.maximum(a, 0.0) * w_ref[0, j:j + 1, :]
        total = term if total is None else total + term
    return total * scale


def _as_key(x):
    """float32 -> int32 in the same order (an involution of the bits)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ (jnp.right_shift(bits, 31) & 0x7FFFFFFF)


def _as_float(key):
    bits = key ^ (jnp.right_shift(key, 31) & 0x7FFFFFFF)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _select_kernel(qi_ref, ki_ref, w_ref, words_ref, lse_ref, keys_ref, *,
                   top_k, block_q, chunk, heads, scale, pos_bits):
    """One query block: its causal keys' scores into ``keys_ref``, the
    ``top_k``-th largest a query, the tie position, the selected scores'
    log-sum-exp and the packed selection."""
    i = pl.program_id(1)
    seq = keys_ref.shape[0]
    q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (1, block_q), 1)
    live = (i * block_q + block_q + chunk - 1) // chunk  # chunks to visit

    def rows(c, n):
        return pl.ds(pl.multiple_of(c * n, n), n)

    def k_pos(c, n):
        return c * n + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)

    def fill(c, carry):
        scores = _index_tile(ki_ref[0, rows(c, chunk), :], qi_ref, w_ref,
                             heads, scale)
        keys_ref[rows(c, chunk), :] = jnp.where(
            k_pos(c, chunk) <= q_pos, _as_key(scores), INT_MIN)
        return carry

    jax.lax.fori_loop(0, live, fill, 0)

    def count(pred):
        def body(c, acc):
            hit = pred(keys_ref[rows(c, chunk), :], k_pos(c, chunk))
            return acc + jnp.sum(jnp.where(hit, 1.0, 0.0), axis=0,
                                 keepdims=True)
        return jax.lax.fori_loop(0, live, body,
                                 jnp.zeros((1, block_q), jnp.float32))

    def bit_step(n, ans):  # the largest key with top_k keys at or above it
        cand = ans | jnp.left_shift(jnp.int32(1), 31 - n)
        hit = count(lambda key, _: key >= (cand ^ INT_MIN)) >= top_k
        return jnp.where(hit, cand, ans)

    thr = jax.lax.fori_loop(0, 32, bit_step, jnp.zeros(
        (1, block_q), jnp.int32)) ^ INT_MIN
    need = top_k - count(lambda key, _: key > thr)

    def pos_step(n, ans):  # the last position whose equal key is taken
        cand = ans | jnp.left_shift(jnp.int32(1), pos_bits - 1 - n)
        few = count(lambda key, pos: (key == thr) & (pos < cand)) < need
        return jnp.where(few, cand, ans)

    last = jax.lax.fori_loop(0, pos_bits, pos_step,
                             jnp.zeros((1, block_q), jnp.int32))

    def chosen(key, pos):
        return (key != INT_MIN) & ((key > thr) | ((key == thr)
                                                  & (pos <= last)))

    def top(c, m):
        key = keys_ref[rows(c, chunk), :]
        return jnp.maximum(m, jnp.max(jnp.where(
            chosen(key, k_pos(c, chunk)), _as_float(key), NEG_INF), axis=0,
            keepdims=True))

    m = jax.lax.fori_loop(0, live, top, jnp.full((1, block_q), NEG_INF,
                                                 jnp.float32))

    def total(c, acc):
        key = keys_ref[rows(c, chunk), :]
        return acc + jnp.sum(jnp.where(chosen(key, k_pos(c, chunk)),
                                       jnp.exp(_as_float(key) - m), 0.0),
                             axis=0, keepdims=True)

    lse_ref[0] = m + jnp.log(jax.lax.fori_loop(
        0, live, total, jnp.zeros((1, block_q), jnp.float32)))

    def pack_group(g, carry):
        inside = g * GROUP < live * chunk

        @pl.when(inside)
        def _word():
            sel = jnp.where(chosen(keys_ref[rows(g, GROUP), :],
                                   k_pos(g, GROUP)), 1, 0).astype(jnp.int32)
            word = sel[0:8, :]
            for bit in range(1, 32):
                word = word | jnp.left_shift(sel[bit * 8:(bit + 1) * 8, :],
                                             bit)
            words_ref[0, rows(g, 8), :] = word

        @pl.when(jnp.logical_not(inside))
        def _none():
            words_ref[0, rows(g, 8), :] = jnp.zeros((8, block_q), jnp.int32)

        return carry

    jax.lax.fori_loop(0, seq // GROUP, pack_group, 0)


def _select_call(qi, ki, w, top_k, scale, chunk, interpret):
    """qi [B, HI, S, DI], ki [B, S, DI], w [B, HI, S] float32 ->
    ``(words [B, S / 32, S], lse [B, 1, S])``."""
    b, heads, s, di = qi.shape
    bq = min(SELECT_BLOCK_Q, s)
    kern = functools.partial(
        _select_kernel, top_k=top_k, block_q=bq, chunk=chunk, heads=heads,
        scale=scale, pos_bits=max(int(s).bit_length(), 1))
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(b, s // bq),
            in_specs=[
                pl.BlockSpec((1, heads, bq, di), lambda n, i: (n, 0, i, 0)),
                pl.BlockSpec((1, s, di), lambda n, i: (n, 0, 0)),
                pl.BlockSpec((1, heads, bq), lambda n, i: (n, 0, i)),
            ],
            out_specs=(
                pl.BlockSpec((1, words_rows(s), bq), lambda n, i: (n, 0, i)),
                pl.BlockSpec((1, 1, bq), lambda n, i: (n, 0, i)),
            ),
            scratch_shapes=[pltpu.VMEM((s, bq), jnp.int32)]),
        out_shape=(jax.ShapeDtypeStruct((b, words_rows(s), s), jnp.int32),
                   jax.ShapeDtypeStruct((b, 1, s), jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(qi, ki, w)


def _last_kv(i, block_q, block_k):
    """The last key block query block ``i`` reaches."""
    return (i * block_q + block_q - 1) // block_k


def _first_q(j, block_q, block_k):
    """The first query block that reaches key block ``j``."""
    return (j * block_k) // block_q


def _bias(words, rows):
    """The selection's mask of a block pair as an additive float32 bias: 0
    where the key was selected, ``NEG_INF`` elsewhere. Added to a score
    tile it gives exactly what ``jnp.where(mask, s, NEG_INF)`` gives: no
    score comes near the magnitude at which ``s + NEG_INF`` would round
    off ``NEG_INF``."""
    return jnp.where(_unpack(words, rows) != 0, 0.0, NEG_INF)


def _columns(h, d):
    """Head ``h``'s ``d`` columns of a tile that holds several heads."""
    return pl.ds(pl.multiple_of(h * d, d), d)


def _step_bytes(heads, group, d, block, itemsize):
    """VMEM of one grid step of dQ, the largest of the three attention
    kernels: q, its cotangent and dQ double-buffered and dQ's float32
    accumulator a head, the k and v tiles double-buffered, the float32
    log-sum-exp and delta rows, the words and six float32 block-pair
    tiles."""
    kv_tiles = -(-heads // group)
    return (6 * block * block * 4 + 2 * block * block // 8
            + kv_tiles * 4 * block * d * itemsize
            + heads * (block * d * (6 * itemsize + 4) + 4 * 8 * block * 4))


def _kl_step_bytes(heads, group, d, block, itemsize, *, seq, index_heads,
                   index_dim):
    """VMEM of one grid step of the KL's backward, the larger of its two
    kernels: the q and k tiles double-buffered, the heads' log-sum-exp
    rows (8 sublanes each), the indexer's dkI of the whole sequence
    double-buffered, its query block's qI and dqI double-buffered and
    dqI's float32 accumulator (lanes padded to 128), and six float32
    block-pair tiles."""
    kv_tiles = -(-heads // group)
    lanes = -(-index_dim // 128) * 128
    return (2 * block * (heads + kv_tiles) * d * itemsize
            + heads * 2 * 8 * block * 4 + 2 * seq * lanes * 4
            + index_heads * block * lanes * (4 * itemsize + 4)
            + 6 * block * block * 4)


def _heads_per_step(h, group, d, block, itemsize, step_bytes=_step_bytes):
    """The query heads one grid step of a kernel runs, so that a block
    pair's mask is unpacked and its k (and v) tiles read once for all of
    them: the most that fit ``_VMEM_BYTES`` by ``step_bytes`` (the
    attention kernels' ``_step_bytes``, the KL's ``_kl_step_bytes``), in
    whole groups (a key/value head's ``group`` query heads; all ``h``
    where they fit) or else a divisor of one group."""
    kv = h // group
    sizes = [n for n in range(1, group + 1) if group % n == 0]
    sizes += [m * group for m in range(2, kv + 1) if kv % m == 0]
    return max(n for n in sizes if n == 1 or step_bytes(
        n, group, d, block, itemsize) <= _VMEM_BYTES)


def _head_blocks(h, group, d, block, itemsize, step_bytes=_step_bytes):
    """``(heads, kv_heads, steps)``: the query heads of a grid step, the
    key/value heads whose k and v tiles it reads, and the grid steps one
    group takes."""
    heads = _heads_per_step(h, group, d, block, itemsize, step_bytes)
    return heads, -(-heads // group), -(-group // heads)


def _each_head(heads, group, d, body, *refs):
    """``body(h, c, *tiles)`` for each query head ``h`` of a grid step,
    with ``c`` its key/value head among the step's and ``tiles`` that
    head's columns of each of ``refs`` (k and v, or k): a loop over the
    key/value heads, each tile read once, and inside it the heads of its
    group laid out one after the other, so that one head's products
    overlap the vector work of the head before."""
    per = min(heads, group)

    def kv_head(c, carry):
        tiles = [ref[0, :, _columns(c, d)] for ref in refs]
        for u in range(per):
            body(c * per + u, c, *tiles)
        return carry

    jax.lax.fori_loop(0, heads // per, kv_head, 0)


def _fwd_kernel(q_ref, k_ref, v_ref, words_ref, o_ref, lse_ref, bias_ref,
                m_ref, l_ref, acc_ref, *, heads, group, d, block_q, block_k,
                scale):
    """One (b, head block, q-block, kv-block) step of flash attention
    under the selection (``ops/flash_attention._kernel``'s transposed
    tiles): the block pair's mask unpacked once for the ``heads`` query
    heads in q's ``heads * d`` columns (``_each_head``)."""
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(j <= _last_kv(i, block_q, block_k))
    def _update():
        bias_ref[:] = _bias(words_ref[0], block_k)

        def head(h, c, k, v):
            s = jax.lax.dot_general(
                k, q_ref[0, :, _columns(h, d)], _NT,
                preferred_element_type=jnp.float32) * scale + bias_ref[:]
            m = m_ref[h]
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - jnp.where(m_new <= NEG_INF / 2, 0.0, m_new))
            alpha = jnp.exp(m - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=0, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                v, p.astype(v.dtype), _TN, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

        _each_head(heads, group, d, head, k_ref, v_ref)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        def head(h, carry):
            l = l_ref[h]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, :, _columns(h, d)] = (acc_ref[h] / l_safe).T.astype(
                o_ref.dtype)
            lse_ref[0, h] = jnp.where(l == 0.0, NEG_INF,
                                      m_ref[h] + jnp.log(l_safe))
            return carry

        jax.lax.fori_loop(0, heads, head, 0)


def _attn_fwd_call(q, k, v, words, d, block, interpret):
    """q [B, S, H * D], k, v [B, S, G * D] (the model's own layout: a head
    is a block of ``D`` columns), words -> ``(o [B, S, H * D], lse [B, H,
    1, S])``."""
    b, s, h = q.shape[0], q.shape[1], q.shape[2] // d
    group = h // (k.shape[2] // d)
    heads, kv_heads, steps = _head_blocks(h, group, d, block,
                                          q.dtype.itemsize)
    n = s // block
    kern = functools.partial(_fwd_kernel, heads=heads, group=group, d=d,
                             block_q=block, block_k=block,
                             scale=1.0 / math.sqrt(d))

    def kv_map(n_, hh, i, j):
        return n_, jnp.minimum(j, _last_kv(i, block, block)), hh // steps

    tile = pl.BlockSpec((1, block, heads * d),
                        lambda n_, hh, i, j: (n_, i, hh))
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(b, h // heads, n, n),
            in_specs=[
                tile,
                pl.BlockSpec((1, block, kv_heads * d), kv_map),
                pl.BlockSpec((1, block, kv_heads * d), kv_map),
                pl.BlockSpec((1, block // 32, block),
                             lambda n_, hh, i, j: (
                                 n_, jnp.minimum(j, _last_kv(i, block,
                                                             block)), i)),
            ],
            out_specs=(
                tile,
                pl.BlockSpec((1, heads, 1, block),
                             lambda n_, hh, i, j: (n_, hh, 0, i)),
            ),
            scratch_shapes=[pltpu.VMEM((block, block), jnp.float32),
                            pltpu.VMEM((heads, 1, block), jnp.float32),
                            pltpu.VMEM((heads, 1, block), jnp.float32),
                            pltpu.VMEM((heads, d, block), jnp.float32)]),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(q, k, v, words)


def _dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, words_ref,
               dq_ref, bias_ref, dq_acc, *, heads, group, d, block_q,
               block_k, scale):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(j <= _last_kv(i, block_q, block_k))
    def _update():
        bias_ref[:] = _bias(words_ref[0], block_k)

        def head(h, c, k, v):
            cols = _columns(h, d)
            s = jax.lax.dot_general(k, q_ref[0, :, cols], _NT,
                                    preferred_element_type=jnp.float32)
            p = jnp.exp(s * scale + bias_ref[:] - lse_ref[0, h])
            dp = jax.lax.dot_general(v, g_ref[0, :, cols], _NT,
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[0, h]) * scale).astype(k.dtype)
            dq_acc[h] += jax.lax.dot_general(
                ds, k, _TN, preferred_element_type=jnp.float32)

        _each_head(heads, group, d, head, k_ref, v_ref)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        def head(h, carry):
            dq_ref[0, :, _columns(h, d)] = dq_acc[h].astype(dq_ref.dtype)
            return carry

        jax.lax.fori_loop(0, heads, head, 0)


def _dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, words_ref,
                dk_ref, dv_ref, bias_ref, dk_acc, dv_acc, *, heads, group, d,
                block_q, block_k, scale):
    j, r, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((r == 0) & (i == 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(i >= _first_q(j, block_q, block_k))
    def _update():
        bias_ref[:] = _bias(words_ref[0], block_k)

        def head(h, c, k, v):
            cols = _columns(h, d)
            q, g = q_ref[0, :, cols], g_ref[0, :, cols]
            s = jax.lax.dot_general(k, q, _NT,
                                    preferred_element_type=jnp.float32)
            p = jnp.exp(s * scale + bias_ref[:] - lse_ref[0, h])
            dv_acc[c] += jnp.dot(p.astype(g.dtype), g,
                                 preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(v, g, _NT,
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[0, h]) * scale).astype(q.dtype)
            dk_acc[c] += jnp.dot(ds, q, preferred_element_type=jnp.float32)

        _each_head(heads, group, d, head, k_ref, v_ref)

    @pl.when((r == pl.num_programs(3) - 1) & (i == pl.num_programs(4) - 1))
    def _finalize():
        def head(c, carry):
            dk_ref[0, :, _columns(c, d)] = dk_acc[c].astype(dk_ref.dtype)
            dv_ref[0, :, _columns(c, d)] = dv_acc[c].astype(dv_ref.dtype)
            return carry

        jax.lax.fori_loop(0, dk_acc.shape[0], head, 0)


def _attn_bwd_call(q, k, v, g, lse, delta, words, d, block, interpret):
    """dQ, dK, dV of the attention under the selection, in q's, k's and v's
    layouts: two kernels, each running ``_heads_per_step`` query heads a
    grid step."""
    b, s, h = q.shape[0], q.shape[1], q.shape[2] // d
    group = h // (k.shape[2] // d)
    heads, kv_heads, steps = _head_blocks(h, group, d, block,
                                          q.dtype.itemsize)
    n = s // block
    scale = 1.0 / math.sqrt(d)
    last = lambda i: _last_kv(i, block, block)  # noqa: E731
    first = lambda j: _first_q(j, block, block)  # noqa: E731
    kern = dict(heads=heads, group=group, d=d, block_q=block, block_k=block,
                scale=scale)
    tile = (1, block, heads * d)
    row = (1, heads, 1, block)
    kv_tile = (1, block, kv_heads * d)
    q_tile = pl.BlockSpec(tile, lambda n_, hh, i, j: (n_, i, hh))
    q_row = pl.BlockSpec(row, lambda n_, hh, i, j: (n_, hh, 0, i))
    kv = pl.BlockSpec(kv_tile, lambda n_, hh, i, j: (
        n_, jnp.minimum(j, last(i)), hh // steps))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kern),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(b, h // heads, n, n),
            in_specs=[
                q_tile, kv, kv, q_tile, q_row, q_row,
                pl.BlockSpec((1, block // 32, block), lambda n_, hh, i, j: (
                    n_, jnp.minimum(j, last(i)), i)),
            ],
            out_specs=q_tile,
            scratch_shapes=[pltpu.VMEM((block, block), jnp.float32),
                            pltpu.VMEM((heads, block, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) + ("arbitrary",) * 3,
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(q, k, v, g, lse, delta, words)

    def q_side(n_, kh, j, r, i):
        """``(batch, head block, query block)`` of a dK, dV grid step."""
        return n_, kh * steps + r, jnp.maximum(i, first(j))

    q_tile = pl.BlockSpec(tile, lambda *a: (
        q_side(*a)[0], q_side(*a)[2], q_side(*a)[1]))
    q_row = pl.BlockSpec(row, lambda *a: (*q_side(*a)[:2], 0, q_side(*a)[2]))
    kv = pl.BlockSpec(kv_tile, lambda n_, kh, j, r, i: (n_, j, kh))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kern),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(b, k.shape[2] // d // kv_heads, n, steps, n),
            in_specs=[
                q_tile, kv, kv, q_tile, q_row, q_row,
                pl.BlockSpec((1, block // 32, block),
                             lambda n_, kh, j, r, i: (
                                 n_, j, jnp.maximum(i, first(j)))),
            ],
            out_specs=(kv, kv),
            scratch_shapes=[pltpu.VMEM((block, block), jnp.float32),
                            pltpu.VMEM((kv_heads, block, d), jnp.float32),
                            pltpu.VMEM((kv_heads, block, d), jnp.float32)]),
        out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) + ("arbitrary",) * 4,
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(q, k, v, g, lse, delta, words)
    return dq, dk, dv


def _probs_tile(q_ref, k_ref, lse_ref, p_acc, first, *, heads, group, d,
                scale):
    """Add the probabilities of a block pair (transposed) of the step's
    ``heads`` query heads, in head order, to ``p_acc`` (zeroed at the
    pair's ``first`` head block): each head's scores against its
    key/value head's k columns (``_each_head``). No mask: the caller
    masks the sum once, which gives what masking each head gives (``exp``
    is never ``-inf``, and a key outside the selection is 0 whatever the
    sum holds)."""
    @pl.when(first)
    def _zero():
        p_acc[:] = jnp.zeros_like(p_acc)

    def head(h, c, k):
        s = jax.lax.dot_general(k, q_ref[0, :, _columns(h, d)], _NT,
                                preferred_element_type=jnp.float32) * scale
        p_acc[:] += jnp.exp(s - lse_ref[0, h])

    _each_head(heads, group, d, head, k_ref)


def _kl_fwd_kernel(q_ref, k_ref, lse_ref, words_ref, qi_ref, ki_ref, w_ref,
                   ent_ref, cross_ref, p_acc, ent_acc, cross_acc, *, block_q,
                   block_k, all_heads, index_heads, index_scale, **layout):
    """One (b, q-block, kv-block, head block) step of the KL's sums: the
    step's query heads' probabilities added up (``_probs_tile``), and at
    the pair's last head block the mask unpacked once for the mean of
    ``all_heads`` and the indexer's scores of the pair."""
    i, j, hh = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    live = j <= _last_kv(i, block_q, block_k)
    last = hh == pl.num_programs(3) - 1

    @pl.when((j == 0) & (hh == 0))
    def _init():
        ent_acc[:] = jnp.zeros_like(ent_acc)
        cross_acc[:] = jnp.zeros_like(cross_acc)

    @pl.when(live)
    def _probs():
        _probs_tile(q_ref, k_ref, lse_ref, p_acc, hh == 0, **layout)

    @pl.when(live & last)
    def _terms():
        mask = _unpack(words_ref[0], block_k) != 0
        p = jnp.where(mask, p_acc[:], 0.0) * (1.0 / all_heads)
        score = _index_tile(ki_ref[0], qi_ref, w_ref, index_heads,
                            index_scale)
        pos = p > 0.0
        ent_acc[:] += jnp.sum(jnp.where(
            pos, p * jnp.log(jnp.where(pos, p, 1.0)), 0.0), axis=0,
            keepdims=True)
        cross_acc[:] += jnp.sum(p * score, axis=0, keepdims=True)

    @pl.when((j == pl.num_programs(2) - 1) & last)
    def _write():
        ent_ref[0] = ent_acc[:]
        cross_ref[0] = cross_acc[:]


def _kl_bwd_kernel(q_ref, k_ref, lse_ref, words_ref, qi_ref, ki_ref, w_ref,
                   lse_i_ref, gkl_ref, dqi_ref, dw_ref, dki_ref, p_acc, dqi_acc,
                   dw_acc, *, block_q, block_k, all_heads, index_heads,
                   index_scale, **layout):
    """The KL's backward a grid step: the probabilities as in the forward,
    then at the pair's last head block ``dI = g (softmax_S(I) - p)`` under
    the one mask, and from it dqI, dw and dkI."""
    i, j, hh = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    live = j <= _last_kv(i, block_q, block_k)
    last = hh == pl.num_programs(3) - 1

    @pl.when((i == 0) & (j == 0) & (hh == 0))
    def _init_dki():
        dki_ref[:] = jnp.zeros_like(dki_ref)

    @pl.when((j == 0) & (hh == 0))
    def _init():
        dqi_acc[:] = jnp.zeros_like(dqi_acc)
        dw_acc[:] = jnp.zeros_like(dw_acc)

    @pl.when(live)
    def _probs():
        _probs_tile(q_ref, k_ref, lse_ref, p_acc, hh == 0, **layout)

    @pl.when(live & last)
    def _grads():
        ki = ki_ref[0]
        mask = _unpack(words_ref[0], block_k) != 0
        p = jnp.where(mask, p_acc[:], 0.0) * (1.0 / all_heads)
        score = _index_tile(ki, qi_ref, w_ref, index_heads, index_scale)
        soft = jnp.where(mask, jnp.exp(score - lse_i_ref[0]), 0.0)
        d_score = (soft - p) * (gkl_ref[0] * index_scale)
        rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        for jj in range(index_heads):
            qi = qi_ref[0, jj]
            a = jax.lax.dot_general(ki, qi, _NT,
                                    preferred_element_type=jnp.float32)
            dw_acc[jj:jj + 1, :] += jnp.sum(d_score * jnp.maximum(a, 0.0),
                                            axis=0, keepdims=True)
            da = jnp.where(a > 0.0, d_score * w_ref[0, jj:jj + 1, :],
                           0.0).astype(ki.dtype)
            dqi_acc[jj] += jax.lax.dot_general(
                da, ki, _TN, preferred_element_type=jnp.float32)
            dki_ref[0, rows, :] += jnp.dot(da, qi,
                                           preferred_element_type=jnp.float32)

    @pl.when((j == pl.num_programs(2) - 1) & last)
    def _write():
        dqi_ref[0] = dqi_acc[:].astype(dqi_ref.dtype)
        dw_ref[0] = dw_acc[:]


def _kl_specs(q, k, qi, d, block, index_scale):
    """What the KL's two calls share: ``(grid, in_specs, params)``, the
    grid (b, q-block, kv-block, head block) with ``_heads_per_step`` query
    heads a step by the KL's own VMEM count, the head blocks innermost so
    that a pair's probabilities sum over them in one scratch tile; q is
    fetched once a query block where one block holds every head, k once
    a pair, and a skipped step repeats the last live step's blocks."""
    b, s, h = q.shape[0], q.shape[1], q.shape[2] // d
    hi, di = qi.shape[1], qi.shape[3]
    group = h // (k.shape[2] // d)
    heads, kv_heads, steps = _head_blocks(
        h, group, d, block, q.dtype.itemsize, functools.partial(
            _kl_step_bytes, seq=s, index_heads=hi, index_dim=di))
    n, blocks = s // block, h // heads
    last = lambda i: _last_kv(i, block, block)  # noqa: E731
    kv = lambda i, j: jnp.minimum(j, last(i))  # noqa: E731
    hb = lambda i, j, hh: jnp.where(  # noqa: E731
        j <= last(i), hh, blocks - 1)
    specs = [
        pl.BlockSpec((1, block, heads * d),
                     lambda n_, i, j, hh: (n_, i, hb(i, j, hh))),
        pl.BlockSpec((1, block, kv_heads * d), lambda n_, i, j, hh: (
            n_, kv(i, j), hb(i, j, hh) // steps)),
        pl.BlockSpec((1, heads, 1, block),
                     lambda n_, i, j, hh: (n_, hb(i, j, hh), 0, i)),
        pl.BlockSpec((1, block // 32, block),
                     lambda n_, i, j, hh: (n_, kv(i, j), i)),
        pl.BlockSpec((1, hi, block, di), lambda n_, i, j, hh: (n_, 0, i, 0)),
        pl.BlockSpec((1, block, di), lambda n_, i, j, hh: (n_, kv(i, j), 0)),
        pl.BlockSpec((1, hi, block), lambda n_, i, j, hh: (n_, 0, i)),
    ]
    params = dict(block_q=block, block_k=block, all_heads=h, index_heads=hi,
                  index_scale=index_scale, heads=heads, group=group, d=d,
                  scale=1.0 / math.sqrt(d))
    return (b, n, n, blocks), specs, params


def _kl_fwd_call(q, k, lse, words, qi, ki, w, d, index_scale, block,
                 interpret):
    """``(sum_s p log p, sum_s p I)`` a query, each [B, 1, S]."""
    b, s = q.shape[0], q.shape[1]
    grid, specs, params = _kl_specs(q, k, qi, d, block, index_scale)
    row = pl.BlockSpec((1, 1, block), lambda n_, i, j, hh: (n_, 0, i))
    return pl.pallas_call(
        functools.partial(_kl_fwd_kernel, **params),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=grid, in_specs=specs,
            out_specs=(row, row),
            scratch_shapes=[pltpu.VMEM((block, block), jnp.float32),
                            pltpu.VMEM((1, block), jnp.float32),
                            pltpu.VMEM((1, block), jnp.float32)]),
        out_shape=(jax.ShapeDtypeStruct((b, 1, s), jnp.float32),) * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) + ("arbitrary",) * 3,
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(q, k, lse, words, qi, ki, w)


def _kl_bwd_call(q, k, lse, words, qi, ki, w, lse_i, gkl, d, index_scale,
                 block, interpret):
    """``(dqI [B, HI, S, DI], dw [B, HI, S], dkI [B, S, DI] float32)``."""
    s = q.shape[1]
    hi, di = qi.shape[1], qi.shape[3]
    grid, specs, params = _kl_specs(q, k, qi, d, block, index_scale)
    row = pl.BlockSpec((1, 1, block), lambda n_, i, j, hh: (n_, 0, i))
    return pl.pallas_call(
        functools.partial(_kl_bwd_kernel, **params),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=grid, in_specs=specs + [row, row],
            out_specs=(
                pl.BlockSpec((1, hi, block, di),
                             lambda n_, i, j, hh: (n_, 0, i, 0)),
                pl.BlockSpec((1, hi, block), lambda n_, i, j, hh: (n_, 0, i)),
                pl.BlockSpec((1, s, di), lambda n_, i, j, hh: (n_, 0, 0))),
            scratch_shapes=[pltpu.VMEM((block, block), jnp.float32),
                            pltpu.VMEM((hi, block, di), jnp.float32),
                            pltpu.VMEM((hi, block), jnp.float32)]),
        out_shape=(jax.ShapeDtypeStruct(qi.shape, qi.dtype),
                   jax.ShapeDtypeStruct(w.shape, jnp.float32),
                   jax.ShapeDtypeStruct(ki.shape, jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) + ("arbitrary",) * 3,
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(q, k, lse, words, qi, ki, w, lse_i, gkl)


# ---------------------------------------------------------------------------
# the kernels' function and its gradient

def _heads_first(x):
    return jnp.swapaxes(x, 1, 2)


def _forward(q, k, v, qi, ki, w, words, lse_i, scale_i, d, block,
             interpret):
    """q [B, S, H * D], k, v [B, S, G * D] (the model's layout, a head a
    block of ``d`` columns), qi [B, HI, S, DI], ki [B, S, DI], w [B, HI,
    S] float32, the selection and its scores' log-sum-exp [B, 1, S]."""
    with scopes.device(scopes.ATTN_SPARSE):
        out, lse = _attn_fwd_call(q, k, v, words, d, block, interpret)
    with scopes.device(scopes.DSA_INDEX):
        ent, cross = _kl_fwd_call(q, k, lse, words, qi, ki, w, d, scale_i,
                                  block, interpret)
        kl = (ent - cross + lse_i)[:, 0]
    return out, kl, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _dsa(q, k, v, qi, ki, w, words, lse_i, scale_i, d, block, interpret):
    return _forward(q, k, v, qi, ki, w, words, lse_i, scale_i, d, block,
                    interpret)[:2]


def _dsa_fwd(q, k, v, qi, ki, w, words, lse_i, scale_i, d, block,
             interpret):
    out, kl, lse = _forward(q, k, v, qi, ki, w, words, lse_i, scale_i, d,
                            block, interpret)
    return (out, kl), (q, k, v, qi, ki, w, words, lse_i, out, lse)


def _dsa_bwd(scale_i, d, block, interpret, res, cotangents):
    q, k, v, qi, ki, w, words, lse_i, out, lse = res
    g, gkl = cotangents
    b, s, _ = q.shape
    with scopes.device(scopes.ATTN_SPARSE):
        delta = jnp.sum((g.astype(jnp.float32) * out.astype(jnp.float32))
                        .reshape(b, s, -1, d), axis=-1)
        delta = jnp.swapaxes(delta, 1, 2)[:, :, None, :]
        dq, dk, dv = _attn_bwd_call(q, k, v, g.astype(q.dtype), lse, delta,
                                    words, d, block, interpret)
    with scopes.device(scopes.DSA_INDEX):
        dqi, dw, dki = _kl_bwd_call(q, k, lse, words, qi, ki, w, lse_i,
                                    gkl[:, None, :], d, scale_i, block,
                                    interpret)
    # the selection and its log-sum-exp carry no gradient
    return (dq, dk, dv, dqi, dki.astype(ki.dtype), dw.astype(w.dtype), None,
            None)


_dsa.defvjp(_dsa_fwd, _dsa_bwd)


def sparse_attention(q, k, v, q_index, k_index, w, *, top_k, index_scale,
                     kernel=None, interpret=None, caller="ops.sparse_attention"):
    """``(out [B, S, H, D], kl [B, S], words [B, S / 32, S])``: the
    attention of q [B, S, H, D] over k, v [B, S, G, D] (query head ``h``
    reads key/value head ``h // (H / G)``) under each query's ``top_k``
    keys by the indexer's scores (q_index [B, S, HI, DI], k_index [B, S,
    DI], w [B, S, HI]: ``index_scale * sum_j w_j relu(qI_j . kI)``), the
    indexer's KL a query, and the packed selection (no gradient).

    ``kernel``: None picks the kernels on the TPU and the plain path
    elsewhere; True asks for the kernels (interpreted off the TPU), and a
    sequence they cannot tile then runs the plain path with a
    ``FlashFallbackWarning``; False, the plain path."""
    b, s, h, d = q.shape
    on_tpu = jax.devices()[0].platform == "tpu"
    if kernel is None:
        kernel = on_tpu
    block = BLOCK
    if kernel and s % block:
        fa.warn_fallback(caller, q.shape, s,
                         f"the sequence does not divide into blocks of "
                         f"{block} keys")
        kernel = False
    if not kernel:
        return _plain(q, k, v, q_index, k_index, w, top_k, index_scale)
    if interpret is None:
        interpret = not on_tpu
    qi, w = _heads_first(q_index), _heads_first(w.astype(jnp.float32))
    with scopes.device(scopes.DSA_TOPK):
        words, lse_i = _select_call(
            *jax.lax.stop_gradient((qi, k_index, w)), top_k,
            float(index_scale), block, interpret)
    # what a caller's jax.checkpoint may keep (SAVED) to run no selection
    # again in the backward pass
    words = checkpoint_name(words, SELECTION)
    lse_i = checkpoint_name(lse_i, SELECTION_LSE)
    # a head is a block of d columns: the kernels read q, k, v and write
    # o and the gradients in the model's layout, with no copy around them
    flat = lambda x: x.reshape(b, s, -1)  # noqa: E731
    out, kl = _dsa(flat(q), flat(k), flat(v), qi, k_index, w, words, lse_i,
                   float(index_scale), d, block, interpret)
    return out.reshape(q.shape), kl, words
