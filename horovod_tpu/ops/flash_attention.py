"""Fused flash attention as a Pallas TPU kernel.

The attention hot path of the transformer family (models/transformer.py)
as a VMEM-resident kernel: the grid is (batch*head, q-block, kv-block)
with the kv dimension innermost, so K/V stream through VMEM one
(block_k, d) tile at a time while fp32 scratch accumulators carry the
online-softmax (flash) recurrence across kv steps — the S x S score
matrix never exists and VMEM usage is bounded by the block sizes, not
the sequence length (reference role: the fused attention kernels every
CUDA framework hand-writes; see /opt/skills/guides/pallas_guide.md).

Both kernels build a block's scores TRANSPOSED (S^T = K Q^T, a
(block_k, block_q) tile), so everything that exists once per query — the
running maximum and sum, the rescale of the accumulator, ``lse``,
``delta`` — is one lane of a (1, block_q) row: reductions run down
sublanes, element-wise over registers, and a row broadcasts down
sublanes for free. The forward accumulates acc^T = V^T P^T and
transposes once when a q-block is done; ``lse`` leaves it as the rows
the backward reads ([BH, 1, Sq]).

The causal block schedule is read from the block's position (the
offsets ride in SMEM), never from a caller: a block pair is *skipped*
(its last query before its first key), *interior* (every score visible)
or *diagonal* (``_block_kind``; ``block_schedule`` counts them). A
skipped grid step runs nothing AND fetches nothing: the index maps of
the tiles that stream (k, v in the forward; q, dO, lse, delta in the
backward) clamp to the nearest block that does run, so the pipeline
sees an unchanged block index and issues no copy. Interior and diagonal
pairs run ONE body, masked: the mask's iota, compare and select hide
behind the matmuls, and a second unmasked body measured slower.

A sliding window (``window=W``: a query at position i sees key j when
``j <= i`` and ``i - j < W``, itself and the ``W - 1`` before it) is part
of the same schedule. A pair wholly behind the window (its first query
``W`` or more past its last key) is skipped like a pair above the
diagonal, and the grid does not even visit it: the inner grid dimension of
a windowed call is as long as the most blocks one outer block can see
(two at 512 x 512 and a window of 512, whatever the sequence), its index
maps start at the first block the window reaches (``_first_visible_kv``;
``_last_visible_q`` in the backward) and clamp at the diagonal as before.
A pair the window's lower edge crosses is masked by the same select as
the diagonal's. ``window=None`` is the causal kernel as it was.

Two head sizes: q and k are ``d_qk`` wide, v, the output, dO and dV
``d_v`` wide. Every caller but latent attention (models/mla.py: keys
carry a 64-wide rotary part the values lack, 192 against 128) passes
one size for both, and the kernels are the same program then.

Shared key/value heads (grouped-query attention): k and v may hold
``H / g`` heads. The forward's k and v index maps read row ``bh // g``
(``_kv_head``) and nothing else changes; the backward runs the ``g``
query heads of a key/value head one after another (grid (bh_kv, member,
kv-block, q-block), ``_grouped_bwd``) and sums their dK and dV in
sequence-sized fp32 scratch, written once a key/value head. Nothing is
broadcast to the query heads or summed back outside the kernels. At
``g == 1`` (every caller but grouped-query attention) neither path is
traced: the kernels are the program they were.

Sequence-parallel composition: ``q_offset``/``kv_offset`` give the
absolute position of the first query/key token. They ride a
scalar-prefetch argument (SMEM), so traced values — e.g. derived from
``lax.axis_index`` inside a shard_map — work; a shard holding a rotated
K/V block passes that block's global offset and the causal mask stays
exact. A query row with no visible keys outputs zeros (not a spurious
mean of V) and ``lse = NEG_INF``.

Gradients: custom VJP with **one fused backward kernel** on the grid
(batch*head, kv-block, q-block). For every visible block pair it
rebuilds the scores and P once from (q, k, lse) saved by the forward —
five matmuls and one ``exp`` — and feeds dV, dK (block-sized scratch,
q-blocks streamed) and dQ (an fp32 accumulator for the whole query
range of one batch*head, resident in VMEM) from them, so the backward,
like the forward, never materializes S x S and stays O(S * block) in
HBM (the flash-attention rematerialization policy).
Kernel matmuls run at the MXU's default precision with fp32
accumulation, matching XLA's own default on TPU. The ``attention``
helper gives way to the plain-XLA path when shapes don't tile and says
so with a :class:`FlashFallbackWarning`. Off the TPU the kernels run
under ``interpret=True``, which is how the CPU test suite exercises
them; in a process whose devices are TPUs they are never interpreted.
"""

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.telemetry import startup as _startup

# Block sizes, measured on v5e (bf16 operands, causal; each kernel alone,
# host clock over 20 calls, which reads 0.3-0.5 ms over the kernel's
# device time; PERF.md PR 26 and PR 28). Blocks clamp to the sequence, so
# short inputs still tile, and each kernel fits its default to the shapes
# it sees (``_fit_block``).
#
# Backward, 512 x 512: 2.85 ms at b8 h12 s2048 d64, 2.93 at h16 d128,
# 18.30 at b4 h32 s4096 192 / 128. A 256 on either side costs 25-36%
# more (grid steps and re-fetched tiles); a 1024 on one side +1-6%, on
# both +1.3%, +2.0%, -1.6%: not better at all three, so it stays.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
BACKWARD_BLOCKS = (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
# Forward, 1024 x 1024: 1.48 / 1.88 / 9.75 ms at the same three shapes,
# against 1.64 / 2.19 / 10.76 at 512 x 512, 1.58 / 2.04 / 10.51 at
# 512 x 1024 and 1.59 / 2.12 / 10.28 at 1024 x 512; a 256 on either side
# 2.00-3.69 / 12.75-13.61, a 2048 on either side 1.85-1.94 / 2.19-2.34 /
# 10.20-11.02. With two matmuls a step the forward's fixed cost a grid
# step (about 0.35 us, skipped steps too) weighs more than the larger
# masked share of a 1024-wide diagonal block. The 4 MiB score tile and
# its temporaries fit Mosaic's default scoped VMEM, fp32 operands too.
FORWARD_BLOCKS = (1024, 1024)
# With a window, (forward, backward), measured on v5e at b1 h64 s8192 d128
# and a window of 512 (bf16; host clock over 20 calls of each kernel with
# the [B, S, H, D] <-> [BH, S, D] copies around it, about 1.3 ms of the
# forward's reading and 2.6 of the backward's; PERF.md PR 37). Forward /
# backward ms at block_q x block_k: 512 x 512 4.65 / 5.60; 512 x 256
# 5.42 / 6.72; 1024 x 512 5.38 / 7.56; 512 x 1024 5.67 / 7.07; 1024 x 1024
# 6.07 / 8.48; 256 x 512 6.63 / 7.03; 256 x 256 6.76 / 7.92; 128 x 256
# 9.62 / 10.19; 256 x 128 9.10 / 13.21; 128 x 128 12.67 / 13.98. At 512 x
# 512 a q block runs two key blocks and half of their scores are live;
# wider blocks compute more outside the band than they save in grid
# steps, narrower ones starve the MXU and pay more steps (every pair is
# crossed by an edge at all of these sizes but the last four).
WINDOW_BLOCKS = ((512, 512), (512, 512))
NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))  # dot_general: a @ b.T
_TN = (((0,), (0,)), ((), ()))  # dot_general: a.T @ b
# What a kernel's block-sized tiles and temporaries may take of VMEM:
# Mosaic's own default scoped limit on v5e, which both kernels' default
# blocks fit. The backward adds its sequence-sized dQ on top.
_BLOCK_VMEM_BYTES = 16 << 20


class FlashFallbackWarning(UserWarning):
    """A caller asked for the flash kernel and got plain-XLA attention.
    Python's default filter shows each distinct message once, so a
    caller is told once per name and shape; a run that must not fall
    back turns this category into an error."""


def warn_fallback(caller, q_shape, kv_len, reason):
    warnings.warn(
        f"{caller}: flash attention was asked for and plain-XLA "
        f"attention ran instead for q{tuple(q_shape)} against "
        f"{kv_len} keys: {reason}", FlashFallbackWarning, stacklevel=3)


def _block_kind(q_first, kv_first, block_q, block_k, causal, window=None):
    """Where one (q-block, kv-block) pair lies against the causal
    diagonal, from the absolute positions of its first query and first
    key: ``(skipped, interior)``. *Skipped*: the block's last query comes
    before its first key, nothing is visible and nothing runs.
    *Interior*: the first query is at or after the last key, every score
    is visible and no mask is needed. Neither: the diagonal crosses the
    block. Without ``causal`` every pair is interior. With a ``window`` a
    pair is skipped too when its first query is ``window`` or more past
    its last key (wholly behind the window), and interior only when its
    last query is less than ``window`` past its first key; neither: an
    edge, the diagonal or the window's, crosses it. Plain comparisons,
    so Python ints, numpy arrays (``block_schedule``) and the kernels'
    traced int32 scalars all pass through it; the index-map helpers
    below are the same ``skipped`` inequalities solved for a block
    index."""
    if not causal:
        return False, True
    skipped = q_first + (block_q - 1) < kv_first
    interior = q_first >= kv_first + (block_k - 1)
    if window is not None:
        skipped = skipped | (q_first - (kv_first + (block_k - 1)) >= window)
        interior = interior & (q_first + (block_q - 1) - kv_first < window)
    return skipped, interior


def _last_visible_kv(i, off_ref, block_q, block_k, nkv):
    """Index of the last kv block that q block ``i`` does not skip
    (``kv_first <= q_last``), clamped into the grid. The forward's k and
    v index maps take ``min(j, this)``: a skipped step then names the
    block already in VMEM and the pipeline issues no copy."""
    reach = off_ref[0] + (i + 1) * block_q - 1 - off_ref[1]
    return jax.lax.div(jnp.clip(reach, 0, nkv * block_k - 1), block_k)


def _first_visible_q(j, off_ref, block_q, block_k, nq):
    """Index of the first q block that kv block ``j`` does not skip,
    clamped into the grid; the backward's q, dO, lse and delta index maps
    take ``max(i, this)`` (q is its inner grid dimension)."""
    reach = off_ref[1] + j * block_k - off_ref[0]
    return jax.lax.div(jnp.clip(reach, 0, nq * block_q - 1), block_q)


def _first_visible_kv(i, off_ref, block_q, block_k, nkv, window):
    """Index of the first kv block that q block ``i`` does not leave
    wholly behind its window (``kv_last > q_first - window``), clamped
    into the grid: where a windowed forward's inner grid dimension
    starts."""
    reach = off_ref[0] + i * block_q - (window - 1) - off_ref[1]
    return jax.lax.div(jnp.clip(reach, 0, nkv * block_k - 1), block_k)


def _last_visible_q(j, off_ref, block_q, block_k, nq, window):
    """Index of the last q block that still has kv block ``j`` inside its
    window (``q_first < kv_last + window``), clamped into the grid; the
    windowed backward's q, dO, lse and delta index maps take
    ``min(i, this)``."""
    reach = off_ref[1] + (j + 1) * block_k - 1 + (window - 1) - off_ref[0]
    return jax.lax.div(jnp.clip(reach, 0, nq * block_q - 1), block_q)


def _kv_head(bh, group):
    """The key/value row of query row ``bh`` where ``group`` query heads
    share each key/value head: with ``H = group * H_kv``, ``(b * H + h) //
    group`` is ``b * H_kv + h // group``, so ``_to_bh``'s [B * H, ...] and
    [B * H_kv, ...] line up as they are. A grouped call's index maps
    alone reach it."""
    return jax.lax.div(bh, group)


def _block_rows(j, block):
    """The rows of block ``j`` in a sequence-sized VMEM buffer: a grouped
    backward's dK and dV."""
    return pl.ds(pl.multiple_of(j * block, block), block)


def _kinds(sq, skv, bq, bk, q_offset, kv_offset, causal, window):
    """``(skipped, interior)`` of every block pair, [sq // bq, skv // bk]
    numpy booleans, from ``_block_kind``."""
    q_first = q_offset + np.arange(sq // bq)[:, None] * bq
    kv_first = kv_offset + np.arange(skv // bk)[None, :] * bk
    return tuple(
        np.broadcast_to(kind, (sq // bq, skv // bk))
        for kind in _block_kind(q_first, kv_first, bq, bk, causal, window))


def block_schedule(sq, skv, block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                   q_offset=0, kv_offset=0, causal=True, window=None):
    """How many block pairs of one batch*head lie wholly inside what the
    mask leaves (under the causal diagonal, and with a ``window`` inside
    it), are crossed by an edge (``diagonal``: the causal diagonal or the
    window's lower edge), and are skipped (nothing run, nothing fetched):
    counts from the classification the kernels themselves use, at the
    blocks they would fit. At 512 x 512
    ``{"interior": 6, "diagonal": 4, "skipped": 6}`` at s2048 and
    28 / 8 / 28 at s4096; with a window of 512 at s8192 0 / 31 / 225 (a
    q block runs the block on the diagonal and the one before it, and an
    edge crosses both)."""
    bq, bk = _fit_block(sq, block_q), _fit_block(skv, block_k)
    skipped, interior = _kinds(sq, skv, bq, bk, q_offset, kv_offset, causal,
                               window)
    return {"interior": int(interior.sum()),
            "diagonal": int((~skipped & ~interior).sum()),
            "skipped": int(skipped.sum())}


def _window_steps(sq, skv, block_q, block_k, window, q_offset, kv_offset):
    """``(kv blocks a q block visits, q blocks a kv block visits)``: the
    inner grid dimensions of a windowed forward and backward at these
    blocks. The blocks one outer block can see are adjacent, so with
    offsets known here (Python ints) the count is read off the
    classification; with traced offsets it is the most that a band
    ``block + window - 1`` positions wide can touch at any alignment."""
    nq, nkv = sq // block_q, skv // block_k
    if isinstance(q_offset, int) and isinstance(kv_offset, int):
        skipped, _ = _kinds(sq, skv, block_q, block_k, q_offset, kv_offset,
                            True, window)
        return (max(int((~skipped).sum(1).max()), 1),
                max(int((~skipped).sum(0).max()), 1))
    return (min(nkv, (block_q + window - 2) // block_k + 2),
            min(nq, (block_k + window - 2) // block_q + 2))


def _visible(q_pos, kv_pos, window):
    """The mask of a block that runs: causal, and inside the window."""
    if window is None:
        return q_pos >= kv_pos
    return (q_pos >= kv_pos) & (q_pos - kv_pos < window)


def _kernel(off_ref, q_ref, k_ref, v_ref, o_ref, *rest, block_q, block_k,
            causal, sm_scale, window=None, nkv=None):
    """One (bh, q-block, kv-block) grid step. The score tile is built
    transposed (S^T = K Q^T, ``(block_k, block_q)``, as in the backward),
    so one query's statistics are one lane of a ``(1, block_q)`` row: the
    running maximum and sum reduce down sublanes (element-wise over
    registers) and ``s - m``, the rescale of the accumulator and the
    final division broadcast a row down sublanes, which is free, where a
    ``(block_q, 1)`` column costs a cross-lane reduction and a lane
    broadcast each. Scratch (m, l, acc^T) carries the online-softmax
    state across the innermost kv dimension; ``rest`` is (lse, m, l, acc)
    when the log-sum-exp rows are an output, else (m, l, acc). With a
    ``window`` the innermost dimension counts from the first kv block the
    window reaches (of ``nkv``), not from block 0."""
    *lse_out, m_ref, l_ref, acc_ref = rest
    i = pl.program_id(1)
    step = pl.program_id(2)
    steps = pl.num_programs(2)
    j = step
    if window is not None:
        j = step + _first_visible_kv(i, off_ref, block_q, block_k, nkv,
                                     window)
    q_first = off_ref[0] + i * block_q
    kv_first = off_ref[1] + j * block_k

    @pl.when(step == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _update():
        # matmuls run on NATIVE-dtype operands (bf16 inputs hit the
        # MXU's bf16 multipliers — fp32 operands would run at a
        # fraction of peak) with fp32 accumulation; all softmax math
        # stays fp32. sm_scale is applied to the fp32 scores, not the
        # narrow inputs.
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            k, q, _NT, preferred_element_type=jnp.float32) * sm_scale
        m = m_ref[:]
        if causal:
            q_pos = q_first + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_q), 1)
            kv_pos = kv_first + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0)
            s = jnp.where(_visible(q_pos, kv_pos, window), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        # a query with nothing visible yet (m_new still NEG_INF)
        # subtracts 0 from its all-NEG_INF scores, so p = 0 and a
        # fully-masked query outputs zeros, not a spurious mean of V:
        # the guard is a row, not a second select over the tile
        p = jnp.exp(s - jnp.where(m_new <= NEG_INF / 2, 0.0, m_new))
        # m starts at the finite NEG_INF: exp(m - m_new) is 0 once a
        # query has seen a key, and 1 times l = acc = 0 before that
        scale = jnp.exp(m - m_new)
        l_ref[:] = l_ref[:] * scale + jnp.sum(p, axis=0, keepdims=True)
        # p cast to the value dtype for the MXU (the standard flash
        # choice); accumulation stays fp32 in scratch: acc^T += V^T P^T
        acc_ref[:] = acc_ref[:] * scale + jax.lax.dot_general(
            v, p.astype(v.dtype), _TN, preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    if causal:
        # skip kv blocks the causal mask kills entirely (scalar math
        # only — extracting from a vector is a Mosaic dynamic_slice).
        # Every block that runs is masked: a second, unmasked body for
        # interior blocks measured 0.5-1% SLOWER at all three benchmark
        # shapes (PERF.md, PR 28) — the mask hides behind the matmuls
        skipped, _ = _block_kind(q_first, kv_first, block_q, block_k, causal,
                                 window)
        if window is not None:
            skipped |= j >= nkv  # counted on past the last block
        pl.when(jnp.logical_not(skipped))(_update)
    else:
        _update()

    @pl.when(step == steps - 1)
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).T.astype(o_ref.dtype)
        for lse_ref in lse_out:
            # log-sum-exp per query; NEG_INF marks "nothing visible"
            # so cross-block combination gives this block zero weight
            lse_ref[0] = jnp.where(l == 0.0, NEG_INF,
                                   m_ref[:] + jnp.log(l_safe))


def _flash_fwd_impl(q, k, v, offsets, causal, sm_scale, block_q, block_k,
                    interpret, with_lse=False, window=None):
    """q: [BH, Sq, Dqk]; k: [BH_kv, Skv, Dqk]; v: [BH_kv, Skv, Dv];
    offsets: int32[2] -> [BH, Sq, Dv] (plus the fp32 log-sum-exp of every
    query as lane-dense rows, [BH, 1, Sq], when ``with_lse``: what the
    backward's ``rowspec`` reads). The two head sizes are one for every
    caller but latent attention, whose keys carry a rotary part the values
    lack. ``BH`` is ``g`` times ``BH_kv``: query row ``bh`` reads
    key/value row ``bh // g`` (``_kv_head``), and ``g`` is 1 for every
    caller but grouped-query attention. ``window``: ``None``, or ``(W,
    steps)``: the kv dimension of the grid then has ``steps`` blocks
    (``_window_steps``), counted from the first the window reaches."""
    window, steps = window or (None, None)
    bh, sq, d = q.shape
    skv, dv = k.shape[1], v.shape[2]
    group = bh // k.shape[0]
    nkv = skv // block_k
    kern = functools.partial(_kernel, block_q=block_q, block_k=block_k,
                             causal=causal, sm_scale=sm_scale)
    if window is not None:
        kern = functools.partial(kern, window=window, nkv=nkv)

    def kv_index(b, i, j, off_ref):
        if window is not None:
            j = j + _first_visible_kv(i, off_ref, block_q, block_k, nkv,
                                      window)
        if causal:
            j = jnp.minimum(
                j, _last_visible_kv(i, off_ref, block_q, block_k, nkv))
        return (b if group == 1 else _kv_head(b, group)), j, 0

    out_specs = pl.BlockSpec((1, block_q, dv), lambda b, i, j, *_: (b, i, 0))
    out_shape = jax.ShapeDtypeStruct((bh, sq, dv), q.dtype)
    if with_lse:
        out_specs = (out_specs,
                     pl.BlockSpec((1, 1, block_q),
                                  lambda b, i, j, *_: (b, 0, i)))
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, sq // block_q, nkv if window is None else steps),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, dv), kv_index),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((1, block_q), jnp.float32),   # m
            pltpu.VMEM((1, block_q), jnp.float32),   # l
            pltpu.VMEM((dv, block_q), jnp.float32),  # acc^T
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(offsets, q, k, v)


def _reference_attention(q, k, v, offsets, causal, sm_scale, window=None):
    """Plain-XLA fp32 attention on [BH, S, D] — the backward-pass
    recompute target and the correctness oracle in tests. Matches the
    kernel's fully-masked-row-outputs-zero convention."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        qp = offsets[0] + jnp.arange(q.shape[1])[:, None]
        kp = offsets[1] + jnp.arange(k.shape[1])[None, :]
        mask = _visible(qp, kp, window)
        s = jnp.where(mask, s, NEG_INF)
        any_visible = jnp.any(mask, axis=-1)[None, :, None]
    else:
        any_visible = True
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(any_visible, p, 0.0)
    return jnp.einsum("bqk,bkd->bqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _bwd_kernel(off_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *dq_scratch,
                block_q, block_k, causal, sm_scale, window=None, nq=None,
                group=None):
    """The whole backward: grid (bh, kv-block, q-block), q innermost.
    Each visible (kv, q) pair rebuilds its scores once, transposed
    (S^T = K Q^T, a (block_k, block_q) tile), so P^T and dS^T come out
    as the left operands dV += P^T dO and dK += dS^T Q want, and feeds
    dQ from the same dS^T by contracting over its rows. dK/dV live in
    block-sized scratch for one kv-block; dQ for the WHOLE query range
    of this bh stays in VMEM across the two inner grid dimensions: in
    ``dq_ref`` itself when that is fp32, else in an fp32 scratch cast
    into it on the last step. With a ``window`` the innermost dimension
    counts from the first q block (of ``nq``) that kv block ``j`` sees.

    With a ``group`` (query heads that share one key/value head) the grid
    is (bh_kv, member, kv-block, q-block): dQ is as above, for the query
    head ``member`` names; dK/dV sum over the members in sequence-sized
    fp32 scratch, kv block ``j``'s rows zeroed at the first member and
    written, once, into the sequence-sized output block at the last."""
    axis = 1 if group is None else 2
    j = pl.program_id(axis)
    step = pl.program_id(axis + 1)
    nkv = pl.num_programs(axis)
    steps = pl.num_programs(axis + 1)
    i = step
    if window is not None:
        i = step + _first_visible_q(j, off_ref, block_q, block_k, nq)
    q_off = off_ref[0]
    kv_off = off_ref[1]
    dq_acc = dq_scratch[0] if dq_scratch else dq_ref
    # kv block j's rows of dK/dV: all of the block-sized scratch
    kv_rows = slice(None) if group is None else _block_rows(j, block_k)

    @pl.when((j == 0) & (step == 0))
    def _init_dq():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    start = step == 0
    if group is not None:
        start &= pl.program_id(1) == 0

    @pl.when(start)
    def _init():
        dk_acc[kv_rows] = jnp.zeros((block_k, dk_acc.shape[-1]), jnp.float32)
        dv_acc[kv_rows] = jnp.zeros((block_k, dv_acc.shape[-1]), jnp.float32)

    def _update():
        # native-dtype matmul operands + fp32 accumulation (see _kernel)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        g = g_ref[0]
        # (1, block_q) rows: one statistic per column of the transposed
        # tile, broadcast down its sublanes
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(
            k, q, _NT, preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = (q_off + i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_q), 1))
            kv_pos = (kv_off + j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0))
            s = jnp.where(_visible(q_pos, kv_pos, window), s, NEG_INF)
        # p = exp(s - lse); rows with nothing visible have lse=NEG_INF
        p = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
        dv_acc[kv_rows] += jnp.dot(p.astype(g.dtype), g,
                                   preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, g, _NT,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk_acc[kv_rows] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        dq_acc[0, rows, :] += jax.lax.dot_general(
            ds, k, _TN, preferred_element_type=jnp.float32)

    if causal:
        skipped, _ = _block_kind(q_off + i * block_q, kv_off + j * block_k,
                                 block_q, block_k, causal, window)
        if window is not None:
            skipped |= i >= nq  # counted on past the last block
        pl.when(jnp.logical_not(skipped))(_update)
    else:
        _update()

    end = step == steps - 1
    if group is not None:
        end &= pl.program_id(1) == group - 1

    @pl.when(end)
    def _finalize():
        dk_ref[0, kv_rows] = dk_acc[kv_rows].astype(dk_ref.dtype)
        dv_ref[0, kv_rows] = dv_acc[kv_rows].astype(dv_ref.dtype)

    if dq_scratch:
        @pl.when((j == nkv - 1) & (step == steps - 1))
        def _finalize_dq():
            dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_impl(q, k, v, g, out, lse, offsets, causal, sm_scale,
                    block_q, block_k, interpret, window=None):
    """Fused flash backward from the forward's residuals; memory is
    O(S * block), never O(S^2)."""
    # delta_i = sum_d dO * O — the softmax-jacobian row correction
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)  # [BH, Sq]
    return _flash_bwd_core(q, k, v, g, lse[:, 0], delta, offsets, causal,
                           sm_scale, block_q, block_k, interpret,
                           window=window)


def _flash_bwd_core(q, k, v, g, lse, delta, offsets, causal, sm_scale,
                    block_q, block_k, interpret, out_dtype=None, window=None):
    """The one backward kernel launch, with (lse, delta) — fp32
    [BH, Sq] — supplied by the caller. Ring attention calls this per
    rotated K/V block with the globally-merged lse and the once-computed
    global delta — the per-block partials then sum to the exact
    global-softmax gradient (softmax over the union of blocks factorizes
    as p = exp(s - LSE)). ``out_dtype`` lets accumulating callers
    request fp32 partials. ``window``: ``None``, or ``(W, steps)``: the q
    dimension of the grid then has ``steps`` blocks, counted from the
    first that a kv block sees. k and v may hold ``BH / g`` rows
    (grouped-query attention, ``_flash_fwd_impl``): the grid then runs
    the ``g`` query heads of a key/value head one after another
    (``_grouped_bwd``) and dK, dV come out at k's and v's rows.

    The only quantity that grows with the sequence is the resident dQ:
    two output buffers of ``sq * d`` elements plus, unless dQ is fp32,
    the fp32 accumulator (1 MiB at s2048 d64 bf16, 2 MiB at s2048 d128,
    32 MiB for ring attention's fp32 partials at 32k a chip and d128 —
    of the 128 MiB a v5e core has; past ``sq * d`` = 8 Mi elements the
    compile fails with Mosaic's out-of-VMEM message); a grouped call
    adds its sequence-sized dK and dV (8 MiB of scratch and 8 of output
    buffers at s8192 d128 bf16)."""
    window, steps = window or (None, None)
    bh, sq, d = q.shape
    skv, dv = k.shape[1], v.shape[2]
    group = bh // k.shape[0]
    # grads mirror their primal dtypes (custom_vjp aval contract) unless
    # the caller wants uniform fp32 partials for accumulation
    dq_dtype = jnp.dtype(out_dtype or q.dtype)
    dk_dtype = out_dtype or k.dtype
    dv_dtype = out_dtype or v.dtype
    nq = sq // block_q

    def q_block(j, i, off_ref):
        # a skipped step names the first q block this kv block sees: the
        # tiles already in VMEM, so the pipeline copies nothing for it
        if window is not None:
            i = jnp.minimum(
                i + _first_visible_q(j, off_ref, block_q, block_k, nq),
                _last_visible_q(j, off_ref, block_q, block_k, nq, window))
        elif causal:
            i = jnp.maximum(
                i, _first_visible_q(j, off_ref, block_q, block_k, nq))
        return i

    # q, k, dq, dk at the score width d; v, dO, dv at the value width
    qspec = pl.BlockSpec((1, block_q, d),
                         lambda b, j, i, off: (b, q_block(j, i, off), 0))
    gspec = pl.BlockSpec((1, block_q, dv),
                         lambda b, j, i, off: (b, q_block(j, i, off), 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b, j, i, *_: (b, j, 0))
    vspec = pl.BlockSpec((1, block_k, dv), lambda b, j, i, *_: (b, j, 0))
    dqspec = pl.BlockSpec((1, sq, d), lambda b, j, i, *_: (b, 0, 0))
    # row statistics ride as [BH, 1, Sq]: lane-dense (1, block_q) rows
    rowspec = pl.BlockSpec((1, 1, block_q),
                           lambda b, j, i, off: (b, 0, q_block(j, i, off)))
    scratch = [pltpu.VMEM((block_k, d), jnp.float32),   # dk
               pltpu.VMEM((block_k, dv), jnp.float32)]  # dv
    resident = 2 * sq * d * dq_dtype.itemsize
    if dq_dtype != jnp.float32:
        scratch.append(pltpu.VMEM((1, sq, d), jnp.float32))
        resident += sq * d * 4
    kern = functools.partial(_bwd_kernel, block_q=block_q, block_k=block_k,
                             causal=causal, sm_scale=sm_scale)
    if window is not None:
        kern = functools.partial(kern, window=window, nq=nq)
    grid = (bh, skv // block_k, nq if window is None else steps)
    in_specs = [qspec, kspec, vspec, gspec, rowspec, rowspec]
    out_specs = (dqspec, kspec, vspec)
    if group > 1:
        grid, in_specs, out_specs, scratch, more = _grouped_bwd(
            group, grid, in_specs, out_specs, scratch, skv,
            jnp.dtype(dk_dtype).itemsize)
        resident += more
        kern = functools.partial(kern, group=group)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=(jax.ShapeDtypeStruct((bh, sq, d), dq_dtype),
                   jax.ShapeDtypeStruct((k.shape[0], skv, d), dk_dtype),
                   jax.ShapeDtypeStruct((k.shape[0], skv, dv), dv_dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) + ("arbitrary",) * (
                len(grid) - 1),
            vmem_limit_bytes=_BLOCK_VMEM_BYTES + resident),
        interpret=interpret,
    )(offsets, q, k, v, g, lse[:, None, :], delta[:, None, :])


def _grouped_bwd(group, grid, in_specs, out_specs, scratch, skv, itemsize):
    """The backward's launch for ``group`` query heads a key/value head,
    from the one-head-each launch (``grid`` (bh, nkv, steps), its specs
    and scratch): ``(grid, in_specs, out_specs, scratch, VMEM bytes it
    adds)``. The grid becomes (bh_kv, member, nkv, steps); the query
    side's maps (q, dO, lse, delta, dQ) name query row ``b * group +
    member``, the key/value side's (k, v) row ``b``; dK and dV become
    sequence-sized fp32 scratch and sequence-sized output blocks, which
    stay in VMEM across the group and are copied out once."""
    bh, nkv, steps = grid
    q_side = lambda spec: pl.BlockSpec(  # noqa: E731
        spec.block_shape,
        lambda b, r, j, i, off: spec.index_map(b * group + r, j, i, off))
    kv_side = lambda spec: pl.BlockSpec(  # noqa: E731
        spec.block_shape, lambda b, r, j, i, off: spec.index_map(b, j, i,
                                                                 off))
    qspec, kspec, vspec, gspec, rowspec, _ = in_specs
    widths = [spec.block_shape[-1] for spec in (kspec, vspec)]
    whole = [pl.BlockSpec((1, skv, w), lambda b, r, j, i, off: (b, 0, 0))
             for w in widths]
    return ((bh // group, group, nkv, steps),
            [q_side(qspec), kv_side(kspec), kv_side(vspec), q_side(gspec),
             q_side(rowspec), q_side(rowspec)],
            (q_side(out_specs[0]), *whole),
            [pltpu.VMEM((skv, w), jnp.float32) for w in widths] + scratch[2:],
            sum(skv * w * (4 + 2 * itemsize) for w in widths))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, offsets, causal, sm_scale, fwd_blocks, bwd_blocks,
           interpret, windows=(None, None)):
    """``windows``: the forward's and the backward's ``(W, steps)``
    (``_window_steps`` at each kernel's blocks), or ``None`` twice."""
    return _flash_fwd_impl(q, k, v, offsets, causal, sm_scale, *fwd_blocks,
                           interpret, window=windows[0])


def _flash_fwd(q, k, v, offsets, causal, sm_scale, fwd_blocks, bwd_blocks,
               interpret, windows):
    out, lse = _flash_fwd_impl(q, k, v, offsets, causal, sm_scale,
                               *fwd_blocks, interpret, with_lse=True,
                               window=windows[0])
    return out, (q, k, v, offsets, out, lse)


def _flash_bwd(causal, sm_scale, fwd_blocks, bwd_blocks, interpret, windows,
               res, g):
    q, k, v, offsets, out, lse = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, g, out, lse, offsets, causal,
                                 sm_scale, *bwd_blocks, interpret,
                                 window=windows[1])
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def _fit_block(n, preferred):
    """Largest block <= preferred that divides ``n`` and respects the
    fp32 sublane tile (8), halving down from the preferred size; 0 when
    nothing fits. Keeps big-block performance for the common pow2
    sequences without dropping support for e.g. seq 1280 (divides by
    256) or 1152 (divides by 128)."""
    b = min(preferred, n)
    while b >= 8:
        if n % b == 0 and b % 8 == 0:
            return b
        b //= 2
    return 0


MIN_BLOCK = 128  # MXU tile width: narrower blocks starve the systolic array


def _block_ok(n, preferred):
    """A fitted block is worth running only when it either spans the
    whole (short) sequence or meets the MXU floor: a long sequence whose
    only fitting block is tiny (e.g. 1048 -> 8) would issue 8-wide MXU
    ops all the way down — slower than the dense XLA path it replaces
    (ADVICE round 5)."""
    b = _fit_block(n, preferred)
    return b > 0 and (b == n or b >= MIN_BLOCK)


def kernel_supported(sq, skv, d, block_q=DEFAULT_BLOCK_Q,
                     block_k=DEFAULT_BLOCK_K, d_v=None):
    """True when these shapes tile onto the kernel (callers use this to
    fall back to the plain-XLA path). ``d`` is the head size of q and k,
    ``d_v`` that of v and the output where it differs."""
    # incremental-decode shapes (q_len == 1 — one new token per sequence
    # against a long cached K/V, the serve/engine.py hot loop) can never
    # tile onto an MXU-floor block: route them to the dense path
    # EXPLICITLY rather than relying on the block fit to bottom out —
    # the contract a decode caller depends on deserves its own gate
    # (and its own test), not an emergent property of _fit_block
    if sq == 1 or skv == 1:
        return False
    # blocks must respect the fp32 sublane tile (8) or Mosaic can
    # reject the lowering — the fallback contract depends on this gate —
    # and clear the MXU floor, or the dense fallback is faster
    return (d % 8 == 0 and (d_v or d) % 8 == 0 and _block_ok(sq, block_q)
            and _block_ok(skv, block_k))


def _prep(q, k, v, sm_scale, block_q, block_k, interpret, *passes):
    """Shared prologue: defaulting and tiling validation. q and k share
    one head size, v may have another (the output's); the default scale
    is the scores'. ``passes`` are the default ``(block_q, block_k)`` of
    the kernels the caller will launch; each gets the caller's sizes, or
    where it gave none its own default, fitted to the sequences."""
    on_tpu = jax.devices()[0].platform == "tpu"
    if interpret is None:
        interpret = not on_tpu
    elif interpret and on_tpu:
        raise ValueError(
            "flash attention kernels are not interpreted in a process "
            "whose devices are TPUs; drop interpret=True")
    b, sq, h, d = q.shape
    skv, dv = k.shape[1], v.shape[-1]
    if k.shape[-1] != d:
        raise ValueError(f"flash_attention: q and k must share a head "
                         f"size (q {d}, k {k.shape[-1]})")
    if k.shape[2] != v.shape[2] or h % k.shape[2]:
        raise ValueError(f"flash_attention: k and v share one number of "
                         f"heads that divides q's (q {h}, k {k.shape[2]}, "
                         f"v {v.shape[2]})")
    sm_scale = sm_scale if sm_scale is not None else 1.0 / (float(d) ** 0.5)
    blocks = [(_fit_block(sq, block_q or default_q),
               _fit_block(skv, block_k or default_k))
              for default_q, default_k in passes]
    if 0 in sum(blocks, ()) or d % 8 != 0 or dv % 8 != 0:
        raise ValueError(
            f"flash_attention needs a block (divisible by 8) that divides "
            f"S, and d % 8 == 0 (sq={sq}, skv={skv}, d={d}, d_v={dv}); use "
            f"ops.flash_attention.attention for automatic fallback")

    return (b, sq, h), sm_scale, interpret, *blocks


def _to_bh(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bh(x, b):
    bh, s, d = x.shape
    return x.reshape(b, bh // b, s, d).transpose(0, 2, 1, 3)


def flash_attention(q, k, v, *, causal=True, sm_scale=None, q_offset=0,
                    kv_offset=0, block_q=None, block_k=None, interpret=None,
                    window=None):
    """Fused attention on [B, S, H, D] tensors (the transformer layout).
    k and v may hold fewer heads, ``H_kv``, that divide q's: query head
    ``h`` then attends to key/value head ``h // (H / H_kv)``, read in
    place by the kernels (no broadcast to ``H``), and dK, dV come back
    at ``H_kv`` heads, summed over each head's queries inside the
    backward kernel. ``block_q``/``block_k`` left unset give each kernel
    its own default (``FORWARD_BLOCKS``, ``BACKWARD_BLOCKS``; with a
    ``window``, ``WINDOW_BLOCKS``); a given size holds for both.
    ``window``: a query sees itself and the ``window - 1`` positions
    before it (causal only).

    ``q_offset``/``kv_offset`` are the absolute positions of the first
    query/key token; ints or traced int32 scalars both work (they ride a
    scalar-prefetch argument), so a sequence-parallel shard can pass
    ``lax.axis_index(...) * s_local`` for a rotated K/V block."""
    if window is not None and (not causal or window < 1):
        raise ValueError(f"flash_attention: a window ({window}) is a "
                         f"positive number of positions behind a causal "
                         f"diagonal (causal={causal})")
    (b, sq, h), sm_scale, interpret, fwd_blocks, bwd_blocks = _prep(
        q, k, v, sm_scale, block_q, block_k, interpret,
        *((FORWARD_BLOCKS, BACKWARD_BLOCKS) if window is None
          else WINDOW_BLOCKS))
    if k.shape[2] != h:
        _startup.RECORD.grouped_attention(h // k.shape[2])
    offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(kv_offset, jnp.int32)])
    windows = (None, None)
    if window is not None:
        steps = lambda blocks: _window_steps(  # noqa: E731
            sq, k.shape[1], *blocks, window, q_offset, kv_offset)
        windows = ((window, steps(fwd_blocks)[0]),
                   (window, steps(bwd_blocks)[1]))
    out = _flash(_to_bh(q), _to_bh(k), _to_bh(v), offsets, causal, sm_scale,
                 fwd_blocks, bwd_blocks, interpret, windows)
    return _from_bh(out, b)


def flash_attention_with_lse(q, k, v, *, causal=True, sm_scale=None,
                             q_offset=0, kv_offset=0, block_q=None,
                             block_k=None, interpret=None):
    """Forward-only kernel call returning ``(out, lse)`` with
    ``lse[b, s, h]`` the log-sum-exp of each query row (NEG_INF when the
    row sees no keys). This is the blockwise-composition primitive: ring
    attention runs it per rotated K/V block and merges results by lse
    weighting (parallel/ring.py). Differentiation happens at the ring
    level, so this call is deliberately VJP-free."""
    (b, sq, h), sm_scale, interpret, (bq, bk) = _prep(
        q, k, v, sm_scale, block_q, block_k, interpret, FORWARD_BLOCKS)
    offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(kv_offset, jnp.int32)])
    out, lse = _flash_fwd_impl(_to_bh(q), _to_bh(k), _to_bh(v), offsets,
                               causal, sm_scale, bq, bk, interpret,
                               with_lse=True)
    out = _from_bh(out, b)
    lse = lse.reshape(b, h, sq).transpose(0, 2, 1)  # [BH,1,Sq] -> [B,S,H]
    return out, lse


def flash_attention_bwd_block(q, k, v, g, lse, delta, *, causal=True,
                              sm_scale=None, q_offset=0, kv_offset=0,
                              block_q=None, block_k=None, interpret=None):
    """Per-block fused backward for blockwise/ring composition: given
    this rank's queries ``q`` [B,Sq,H,D], one rotated K/V block
    [B,Skv,H,D], the upstream ``g`` = dO, the **globally merged**
    ``lse`` [B,Sq,H] (from ``flash_attention_with_lse`` + lse merging)
    and ``delta`` [B,Sq,H] = sum_d(dO * O) over the final output, runs
    the fused backward kernel and returns fp32 partials
    ``(dq, dk, dv)`` for exactly this block's contribution. Summing the
    partials over all blocks (rotating dk/dv with their K/V blocks
    around the ring) reproduces the exact global-softmax gradient,
    because p = exp(s - LSE) factorizes per block once LSE is global —
    the ring backward never materializes an S x S score matrix
    (parallel/ring.py ``_ring_attention_flash``)."""
    (b, sq, h), sm_scale, interpret, (bq, bk) = _prep(
        q, k, v, sm_scale, block_q, block_k, interpret, BACKWARD_BLOCKS)
    offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(kv_offset, jnp.int32)])

    def rows_bh(x):  # [B,Sq,H] -> [BH,Sq]
        return x.transpose(0, 2, 1).reshape(b * h, sq)

    dq, dk, dv = _flash_bwd_core(
        _to_bh(q), _to_bh(k), _to_bh(v), _to_bh(g), rows_bh(lse),
        rows_bh(delta), offsets, causal, sm_scale, bq, bk, interpret,
        out_dtype=jnp.float32)

    return _from_bh(dq, b), _from_bh(dk, b), _from_bh(dv, b)


def attention(q, k, v, *, causal=True, q_offset=0, kv_offset=0, window=None):
    """flash_attention, giving way to the plain-XLA path (with a
    :class:`FlashFallbackWarning`) when shapes don't tile onto the
    kernel blocks."""
    b, sq, _, d = q.shape
    skv = k.shape[1]
    if kernel_supported(sq, skv, d, d_v=v.shape[-1]):
        return flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                               kv_offset=kv_offset, window=window)
    warn_fallback("ops.flash_attention.attention", q.shape, skv,
                  "the shapes do not tile onto the kernel's blocks")
    offsets = jnp.asarray([q_offset, kv_offset], jnp.int32)
    k, v = per_query_head(q, k, v)
    out = _reference_attention(_to_bh(q), _to_bh(k), _to_bh(v), offsets,
                               causal, 1.0 / (float(d) ** 0.5), window)
    return _from_bh(out, b)


def per_query_head(q, k, v):
    """``(k, v)`` with one key/value head a query head of ``q``
    ([B, S, H, D]): shared heads repeated ``H / H_kv`` times (query head
    ``h`` sees head ``h // (H / H_kv)``), for the paths that take one head
    each — ``_reference_attention``, ring attention, the dense and decode
    paths of ``models/transformer.attend``. The repeat's transpose sums
    the query heads' gradients back. k and v as they are where they
    already have q's heads."""
    h = q.shape[2]
    return tuple(t if t.shape[2] == h else jnp.repeat(t, h // t.shape[2],
                                                      axis=2)
                 for t in (k, v))
