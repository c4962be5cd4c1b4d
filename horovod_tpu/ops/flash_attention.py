"""Fused flash attention as a Pallas TPU kernel.

The attention hot path of the transformer family (models/transformer.py)
as a VMEM-resident kernel: the grid is (batch*head, q-block, kv-block)
with the kv dimension innermost, so K/V stream through VMEM one
(block_k, d) tile at a time while fp32 scratch accumulators carry the
online-softmax (flash) recurrence across kv steps — the S x S score
matrix never exists and VMEM usage is bounded by the block sizes, not
the sequence length (reference role: the fused attention kernels every
CUDA framework hand-writes; see /opt/skills/guides/pallas_guide.md).

Two head sizes: q and k are ``d_qk`` wide, v, the output, dO and dV
``d_v`` wide. Every caller but latent attention (models/mla.py: keys
carry a 64-wide rotary part the values lack, 192 against 128) passes
one size for both, and the kernels are the same program then.

Sequence-parallel composition: ``q_offset``/``kv_offset`` give the
absolute position of the first query/key token. They ride a
scalar-prefetch argument (SMEM), so traced values — e.g. derived from
``lax.axis_index`` inside a shard_map — work; a shard holding a rotated
K/V block passes that block's global offset and the causal mask stays
exact. A query row with no visible keys outputs zeros (not a spurious
mean of V).

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Measured on v5e (bf16 operands, causal, b8 s2048; the backward kernel
# alone, host clock over 20 calls, PERF.md PR 26): 512x512 blocks run
# the backward in 2.97 ms at h12 d64 and 3.30 ms at h16 d128; a 256
# on either side costs 25-36% more (grid steps and re-fetched tiles), a
# 1024 is within +-5% (3.01-3.12 and 3.13-3.39 ms) and 2048 is slower
# again, so forward and backward share one size. Blocks clamp to the
# sequence, so short inputs still tile.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))  # dot_general: a @ b.T
_TN = (((0,), (0,)), ((), ()))  # dot_general: a.T @ b
# What a kernel's block-sized tiles and temporaries may take of VMEM:
# Mosaic's own default scoped limit on v5e, which the 512x512 blocks fit
# with room. The backward adds its sequence-sized dQ on top.
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


def _kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, block_q, block_k, causal, sm_scale, lse_ref=None):
    """One (bh, q-block, kv-block) grid step. Scratch (m, l, acc) carries
    the online-softmax state across the innermost kv dimension."""
    i = pl.program_id(1)
    j = pl.program_id(2)
    nkv = pl.num_programs(2)
    q_off = off_ref[0]
    kv_off = off_ref[1]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_pos = (q_off + i * block_q
             + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0))
    kv_start = kv_off + j * block_k

    def _update():
        # matmuls run on NATIVE-dtype operands (bf16 inputs hit the
        # MXU's bf16 multipliers — fp32 operands would run at a
        # fraction of peak) with fp32 accumulation; all softmax math
        # stays fp32. sm_scale is applied to the fp32 scores, not the
        # narrow inputs.
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jnp.dot(q, k.T,
                    preferred_element_type=jnp.float32) * sm_scale
        if causal:
            kv_pos = (kv_start +
                      jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1))
            s = jnp.where(q_pos >= kv_pos, s, NEG_INF)
        m = m_ref[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # rows with nothing visible yet keep p = 0, so a fully-masked
        # query outputs zeros instead of a spurious mean of V
        p = jnp.where(m_new <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        scale = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_new))
        l_ref[:] = l_ref[:] * scale + jnp.sum(p, axis=-1, keepdims=True)
        # p cast to the value dtype for the MXU (the standard flash
        # choice); accumulation stays fp32 in scratch
        acc_ref[:] = acc_ref[:] * scale + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    if causal:
        # skip kv blocks the causal mask kills entirely (scalar math
        # only — extracting from a vector is a Mosaic dynamic_slice)
        q_last = q_off + i * block_q + (block_q - 1)
        pl.when(q_last >= kv_start)(_update)
    else:
        _update()

    @pl.when(j == nkv - 1)
    def _finalize():
        l = l_ref[:]
        o_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)
        if lse_ref is not None:
            # log-sum-exp per query row; NEG_INF marks "nothing visible"
            # so cross-block combination gives this block zero weight
            lse_ref[0] = jnp.where(
                l == 0.0, NEG_INF,
                m_ref[:] + jnp.log(jnp.where(l == 0.0, 1.0, l)))


def _kernel_lse(off_ref, q_ref, k_ref, v_ref, o_ref, lse_out_ref, m_ref,
                l_ref, acc_ref, **kw):
    _kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            lse_ref=lse_out_ref, **kw)


def _flash_fwd_impl(q, k, v, offsets, causal, sm_scale, block_q, block_k,
                    interpret, with_lse=False):
    """q: [BH, Sq, Dqk]; k: [BH, Skv, Dqk]; v: [BH, Skv, Dv]; offsets:
    int32[2] -> [BH, Sq, Dv] (plus fp32 [BH, Sq, 1] log-sum-exp rows when
    ``with_lse`` — the trailing singleton satisfies Mosaic's
    last-two-dims tiling rule). The two head sizes are one for every
    caller but latent attention, whose keys carry a rotary part the
    values lack."""
    bh, sq, d = q.shape
    skv, dv = k.shape[1], v.shape[2]
    kw = dict(block_q=block_q, block_k=block_k, causal=causal,
              sm_scale=sm_scale)
    kern = functools.partial(_kernel_lse if with_lse else _kernel, **kw)
    out_specs = pl.BlockSpec((1, block_q, dv), lambda b, i, j, *_: (b, i, 0))
    out_shape = jax.ShapeDtypeStruct((bh, sq, dv), q.dtype)
    if with_lse:
        # lse rides as [BH, Sq, 1]: a (1, bq, 1) block satisfies the
        # Mosaic last-two-dims tiling rule where a 2-D (1, bq) cannot
        out_specs = (out_specs,
                     pl.BlockSpec((1, block_q, 1),
                                  lambda b, i, j, *_: (b, i, 0)))
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, sq // block_q, skv // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j, *_: (b, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, i, j, *_: (b, j, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m
            pltpu.VMEM((block_q, 1), jnp.float32),   # l
            pltpu.VMEM((block_q, dv), jnp.float32),  # acc
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(offsets, q, k, v)


def _reference_attention(q, k, v, offsets, causal, sm_scale):
    """Plain-XLA fp32 attention on [BH, S, D] — the backward-pass
    recompute target and the correctness oracle in tests. Matches the
    kernel's fully-masked-row-outputs-zero convention."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        qp = offsets[0] + jnp.arange(q.shape[1])[:, None]
        kp = offsets[1] + jnp.arange(k.shape[1])[None, :]
        mask = qp >= kp
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
                block_q, block_k, causal, sm_scale):
    """The whole backward: grid (bh, kv-block, q-block), q innermost.
    Each visible (kv, q) pair rebuilds its scores once, transposed
    (S^T = K Q^T, a (block_k, block_q) tile), so P^T and dS^T come out
    as the left operands dV += P^T dO and dK += dS^T Q want, and feeds
    dQ from the same dS^T by contracting over its rows. dK/dV live in
    block-sized scratch for one kv-block; dQ for the WHOLE query range
    of this bh stays in VMEM across the two inner grid dimensions: in
    ``dq_ref`` itself when that is fp32, else in an fp32 scratch cast
    into it on the last step."""
    j = pl.program_id(1)
    i = pl.program_id(2)
    nkv = pl.num_programs(1)
    nq = pl.num_programs(2)
    q_off = off_ref[0]
    kv_off = off_ref[1]
    dq_acc = dq_scratch[0] if dq_scratch else dq_ref

    @pl.when((j == 0) & (i == 0))
    def _init_dq():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

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
            s = jnp.where(q_pos >= kv_pos, s, NEG_INF)
        # p = exp(s - lse); rows with nothing visible have lse=NEG_INF
        p = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
        dv_acc[:] += jnp.dot(p.astype(g.dtype), g,
                               preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, g, _NT,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk_acc[:] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        dq_acc[0, rows, :] += jax.lax.dot_general(
            ds, k, _TN, preferred_element_type=jnp.float32)

    if causal:
        q_last = q_off + i * block_q + (block_q - 1)
        pl.when(q_last >= kv_off + j * block_k)(_update)
    else:
        _update()

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    if dq_scratch:
        @pl.when((j == nkv - 1) & (i == nq - 1))
        def _finalize_dq():
            dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_impl(q, k, v, g, out, lse, offsets, causal, sm_scale,
                    block_q, block_k, interpret):
    """Fused flash backward from the forward's residuals; memory is
    O(S * block), never O(S^2)."""
    # delta_i = sum_d dO * O — the softmax-jacobian row correction
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)  # [BH, Sq]
    return _flash_bwd_core(q, k, v, g, lse[..., 0], delta, offsets, causal,
                           sm_scale, block_q, block_k, interpret)


def _flash_bwd_core(q, k, v, g, lse, delta, offsets, causal, sm_scale,
                    block_q, block_k, interpret, out_dtype=None):
    """The one backward kernel launch, with (lse, delta) — fp32
    [BH, Sq] — supplied by the caller. Ring attention calls this per
    rotated K/V block with the globally-merged lse and the once-computed
    global delta — the per-block partials then sum to the exact
    global-softmax gradient (softmax over the union of blocks factorizes
    as p = exp(s - LSE)). ``out_dtype`` lets accumulating callers
    request fp32 partials.

    The only quantity that grows with the sequence is the resident dQ:
    two output buffers of ``sq * d`` elements plus, unless dQ is fp32,
    the fp32 accumulator (1 MiB at s2048 d64 bf16, 2 MiB at s2048 d128,
    32 MiB for ring attention's fp32 partials at 32k a chip and d128 —
    of the 128 MiB a v5e core has; past ``sq * d`` = 8 Mi elements the
    compile fails with Mosaic's out-of-VMEM message)."""
    bh, sq, d = q.shape
    skv, dv = k.shape[1], v.shape[2]
    # grads mirror their primal dtypes (custom_vjp aval contract) unless
    # the caller wants uniform fp32 partials for accumulation
    dq_dtype = jnp.dtype(out_dtype or q.dtype)
    dk_dtype = out_dtype or k.dtype
    dv_dtype = out_dtype or v.dtype
    # q, k, dq, dk at the score width d; v, dO, dv at the value width
    qspec = pl.BlockSpec((1, block_q, d), lambda b, j, i, *_: (b, i, 0))
    gspec = pl.BlockSpec((1, block_q, dv), lambda b, j, i, *_: (b, i, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b, j, i, *_: (b, j, 0))
    vspec = pl.BlockSpec((1, block_k, dv), lambda b, j, i, *_: (b, j, 0))
    dqspec = pl.BlockSpec((1, sq, d), lambda b, j, i, *_: (b, 0, 0))
    # row statistics ride as [BH, 1, Sq]: lane-dense (1, block_q) rows
    rowspec = pl.BlockSpec((1, 1, block_q), lambda b, j, i, *_: (b, 0, i))
    scratch = [pltpu.VMEM((block_k, d), jnp.float32),   # dk
               pltpu.VMEM((block_k, dv), jnp.float32)]  # dv
    resident = 2 * sq * d * dq_dtype.itemsize
    if dq_dtype != jnp.float32:
        scratch.append(pltpu.VMEM((1, sq, d), jnp.float32))
        resident += sq * d * 4
    return pl.pallas_call(
        functools.partial(_bwd_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, skv // block_k, sq // block_q),
            in_specs=[qspec, kspec, vspec, gspec, rowspec, rowspec],
            out_specs=(dqspec, kspec, vspec),
            scratch_shapes=scratch,
        ),
        out_shape=(jax.ShapeDtypeStruct((bh, sq, d), dq_dtype),
                   jax.ShapeDtypeStruct((bh, skv, d), dk_dtype),
                   jax.ShapeDtypeStruct((bh, skv, dv), dv_dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_BLOCK_VMEM_BYTES + resident),
        interpret=interpret,
    )(offsets, q, k, v, g, lse[:, None, :], delta[:, None, :])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, offsets, causal, sm_scale, block_q, block_k,
           interpret):
    return _flash_fwd_impl(q, k, v, offsets, causal, sm_scale, block_q,
                           block_k, interpret)


def _flash_fwd(q, k, v, offsets, causal, sm_scale, block_q, block_k,
               interpret):
    out, lse = _flash_fwd_impl(q, k, v, offsets, causal, sm_scale,
                               block_q, block_k, interpret, with_lse=True)
    return out, (q, k, v, offsets, out, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, res, g):
    q, k, v, offsets, out, lse = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, g, out, lse, offsets, causal,
                                 sm_scale, block_q, block_k, interpret)
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


def _prep(q, k, v, sm_scale, block_q, block_k, interpret):
    """Shared prologue: defaulting and tiling validation. q and k share
    one head size, v may have another (the output's); the default scale
    is the scores'."""
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
    sm_scale = sm_scale if sm_scale is not None else 1.0 / (float(d) ** 0.5)
    bq, bk = _fit_block(sq, block_q), _fit_block(skv, block_k)
    if bq == 0 or bk == 0 or d % 8 != 0 or dv % 8 != 0:
        raise ValueError(
            f"flash_attention needs a block (divisible by 8) that divides "
            f"S, and d % 8 == 0 (sq={sq}, skv={skv}, d={d}, d_v={dv}); use "
            f"ops.flash_attention.attention for automatic fallback")

    return (b, sq, h), sm_scale, bq, bk, interpret


def _to_bh(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bh(x, b):
    bh, s, d = x.shape
    return x.reshape(b, bh // b, s, d).transpose(0, 2, 1, 3)


def flash_attention(q, k, v, *, causal=True, sm_scale=None, q_offset=0,
                    kv_offset=0, block_q=DEFAULT_BLOCK_Q,
                    block_k=DEFAULT_BLOCK_K, interpret=None):
    """Fused attention on [B, S, H, D] tensors (the transformer layout).

    ``q_offset``/``kv_offset`` are the absolute positions of the first
    query/key token; ints or traced int32 scalars both work (they ride a
    scalar-prefetch argument), so a sequence-parallel shard can pass
    ``lax.axis_index(...) * s_local`` for a rotated K/V block."""
    (b, _, _), sm_scale, bq, bk, interpret = _prep(
        q, k, v, sm_scale, block_q, block_k, interpret)
    offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(kv_offset, jnp.int32)])
    out = _flash(_to_bh(q), _to_bh(k), _to_bh(v), offsets, causal, sm_scale,
                 bq, bk, interpret)
    return _from_bh(out, b)


def flash_attention_with_lse(q, k, v, *, causal=True, sm_scale=None,
                             q_offset=0, kv_offset=0,
                             block_q=DEFAULT_BLOCK_Q,
                             block_k=DEFAULT_BLOCK_K, interpret=None):
    """Forward-only kernel call returning ``(out, lse)`` with
    ``lse[b, s, h]`` the log-sum-exp of each query row (NEG_INF when the
    row sees no keys). This is the blockwise-composition primitive: ring
    attention runs it per rotated K/V block and merges results by lse
    weighting (parallel/ring.py). Differentiation happens at the ring
    level, so this call is deliberately VJP-free."""
    (b, sq, h), sm_scale, bq, bk, interpret = _prep(
        q, k, v, sm_scale, block_q, block_k, interpret)
    offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(kv_offset, jnp.int32)])
    out, lse = _flash_fwd_impl(_to_bh(q), _to_bh(k), _to_bh(v), offsets,
                               causal, sm_scale, bq, bk, interpret,
                               with_lse=True)
    out = _from_bh(out, b)
    lse = lse.reshape(b, h, sq).transpose(0, 2, 1)  # [BH,Sq,1] -> [B,S,H]
    return out, lse


def flash_attention_bwd_block(q, k, v, g, lse, delta, *, causal=True,
                              sm_scale=None, q_offset=0, kv_offset=0,
                              block_q=DEFAULT_BLOCK_Q,
                              block_k=DEFAULT_BLOCK_K, interpret=None):
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
    (b, sq, h), sm_scale, bq, bk, interpret = _prep(
        q, k, v, sm_scale, block_q, block_k, interpret)
    offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(kv_offset, jnp.int32)])

    def rows_bh(x):  # [B,Sq,H] -> [BH,Sq]
        return x.transpose(0, 2, 1).reshape(b * h, sq)

    dq, dk, dv = _flash_bwd_core(
        _to_bh(q), _to_bh(k), _to_bh(v), _to_bh(g), rows_bh(lse),
        rows_bh(delta), offsets, causal, sm_scale, bq, bk, interpret,
        out_dtype=jnp.float32)

    return _from_bh(dq, b), _from_bh(dk, b), _from_bh(dv, b)


def attention(q, k, v, *, causal=True, q_offset=0, kv_offset=0):
    """flash_attention, giving way to the plain-XLA path (with a
    :class:`FlashFallbackWarning`) when shapes don't tile onto the
    kernel blocks."""
    b, sq, _, d = q.shape
    skv = k.shape[1]
    if kernel_supported(sq, skv, d, d_v=v.shape[-1]):
        return flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                               kv_offset=kv_offset)
    warn_fallback("ops.flash_attention.attention", q.shape, skv,
                  "the shapes do not tile onto the kernel's blocks")
    offsets = jnp.asarray([q_offset, kv_offset], jnp.int32)
    out = _reference_attention(_to_bh(q), _to_bh(k), _to_bh(v), offsets,
                               causal, 1.0 / (float(d) ** 0.5))
    return _from_bh(out, b)
