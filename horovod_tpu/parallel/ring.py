"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Absent from the 2019 reference (SURVEY.md §5.7) but first-class here: the
mesh machinery that gives data parallelism also gives sequence sharding.
Two interchangeable strategies, both compiled by XLA over ICI:

* **Ring attention** (``ring_attention``): Q stays resident per shard; K/V
  blocks rotate around the mesh-axis ring via ``lax.ppermute`` while
  attention accumulates with the online-softmax (flash) recurrence in fp32.
  Per-chip memory stays O(S/n); the ppermute overlaps with the block
  matmuls in XLA's schedule. This is the TPU-idiomatic form of
  Ring Attention (Liu et al. 2023) — see PAPERS.md.
* **Ulysses** (``ulysses_attention``): one ``all_to_all`` re-shards from
  sequence-sharded/full-heads to head-sharded/full-sequence, runs dense
  attention locally, and reverses. Cheaper at moderate S, needs
  num_heads % axis_size == 0.

Causality is enforced by **absolute positions**, so both compose with any
ring order and with unequal offsets.
"""

import jax
import jax.numpy as jnp
from jax import lax

_NEG_BIG = jnp.float32(-1e30)


def default_positions(axis_name, batch, seq_local):
    """Absolute token positions for a sequence-sharded [B, S_local] block:
    this shard's offset on the ring plus the local arange. The single source
    of truth for the position formula used by causal masking."""
    offset = lax.axis_index(axis_name) * seq_local if axis_name else 0
    return (offset + jnp.arange(seq_local))[None, :] * jnp.ones(
        (batch, 1), jnp.int32)


def _block_update(q, k, v, q_pos, kv_pos, m, l, o, causal, scale):
    """One online-softmax accumulation step against a K/V block (fp32).

    q: [B,Sq,H,D]; k,v: [B,Sk,H,D]; m,l: [B,H,Sq]; o: [B,H,Sq,D].
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = q_pos[:, None, :, None] >= kv_pos[:, None, None, :]
        s = jnp.where(mask, s, _NEG_BIG)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # exp(-1e30 - m_new) could overflow to 1 when the whole row is masked
    # (m_new == -1e30); zero those probabilities explicitly instead.
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(s <= _NEG_BIG, 0.0, p)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return m_new, l_new, o_new


def ring_attention(q, k, v, axis_name, causal=True, q_positions=None,
                   kv_positions=None, use_flash=False):
    """Blockwise ring attention over the ``axis_name`` mesh axis.

    Shapes per shard: q/k/v ``[B, S_local, H, D]``; positions ``[B, S_local]``
    absolute token positions (used for causal masking across shards).
    Returns ``[B, S_local, H, D]`` in q.dtype.

    ``use_flash`` runs each shard's block attention through the Pallas
    flash kernel (ops/flash_attention.py) and merges blocks by
    log-sum-exp weighting; requires the DEFAULT contiguous positions
    (pass ``q_positions=None``); shapes that do not tile keep the jnp
    path with a ``FlashFallbackWarning``, and callers with custom
    positions keep it by contract.
    """
    if use_flash and q_positions is None and kv_positions is None:
        from horovod_tpu.ops import flash_attention as fa
        _, sq_, _, d_ = q.shape
        if fa.kernel_supported(sq_, sq_, d_):
            return _ring_attention_flash(q, k, v, axis_name, causal)
        # shapes don't tile onto the kernel: the jnp ring runs, under
        # the same contract as the local attention() helper
        fa.warn_fallback("parallel.ring.ring_attention", q.shape, sq_,
                         "the shapes do not tile onto the kernel's blocks")
    n = lax.axis_size(axis_name)
    b, sq, h, d = q.shape
    scale = 1.0 / (float(d) ** 0.5)
    if q_positions is None:
        q_positions = default_positions(axis_name, b, sq)
    if kv_positions is None:
        kv_positions = q_positions

    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(carry, _):
        k_blk, v_blk, kv_pos, m, l, o = carry
        m, l, o = _block_update(q, k_blk, v_blk, q_positions, kv_pos,
                                m, l, o, causal, scale)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        kv_pos = lax.ppermute(kv_pos, axis_name, perm)
        return (k_blk, v_blk, kv_pos, m, l, o), None

    m0 = jnp.full((b, h, sq), _NEG_BIG, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    (_, _, _, m, l, o), _ = lax.scan(
        step, (k, v, kv_positions, m0, l0, o0), None, length=n)
    l = jnp.where(l == 0.0, 1.0, l)
    out = (o / l[..., None]).astype(q.dtype)
    return jnp.einsum("bhqd->bqhd", out)


def _ring_attention_flash(q, k, v, axis_name, causal):
    """Ring attention whose per-block compute is the Pallas flash kernel
    in BOTH directions. Forward: blocks merge by the standard
    log-sum-exp composition ``out = sum_j exp(lse_j - LSE) * out_j``.
    Backward: a second ring pass runs the one fused backward kernel
    (dQ, dK and dV from a single rebuild of each score block) per
    rotated K/V block against the globally-merged lse (saved from the
    forward) and the once-computed ``delta = sum_d dO*O``; the dK/dV
    partial accumulators rotate WITH their K/V blocks, so after n steps
    each block's gradient arrives back at its home rank having collected
    every rank's contribution. p = exp(s - LSE) factorizes per block
    once LSE is global, so the summed partials equal the exact
    global-softmax gradient while peak memory stays O(S_local * block)
    — the dense jnp ring VJP it replaces materialized
    S_local x S_local score blocks per step."""
    from horovod_tpu.ops import flash_attention as fa

    n = lax.axis_size(axis_name)
    b, sq, h, d = q.shape
    perm = [(j, (j + 1) % n) for j in range(n)]

    def fwd_impl(q, k, v):
        # axis_index must be taken INSIDE the custom_vjp'd function: a
        # closed-over tracer has no constant handler under grad tracing
        me = lax.axis_index(axis_name)
        q_off = (me * sq).astype(jnp.int32)

        def step(carry, _):
            k_blk, v_blk, kv_off, o_run, lse_run = carry
            o_j, lse_j = fa.flash_attention_with_lse(
                q, k_blk, v_blk, causal=causal, q_offset=q_off,
                kv_offset=kv_off[0])
            # streaming log-sum-exp merge (elementwise, XLA-fused)
            m = jnp.maximum(lse_run, lse_j)
            m_safe = jnp.where(m <= _NEG_BIG / 2, 0.0, m)
            w_run = jnp.where(lse_run <= _NEG_BIG / 2, 0.0,
                              jnp.exp(lse_run - m_safe))
            w_j = jnp.where(lse_j <= _NEG_BIG / 2, 0.0,
                            jnp.exp(lse_j - m_safe))
            tot = w_run + w_j
            tot_safe = jnp.where(tot == 0.0, 1.0, tot)
            # fp32 carry across all n steps; cast once after the scan
            # (repeated bf16 re-rounding would compound over the ring)
            o_run = ((o_run * w_run[..., None]
                      + o_j.astype(jnp.float32) * w_j[..., None])
                     / tot_safe[..., None])
            lse_run = jnp.where(tot == 0.0, _NEG_BIG,
                                m_safe + jnp.log(tot_safe))
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)
            kv_off = lax.ppermute(kv_off, axis_name, perm)
            return (k_blk, v_blk, kv_off, o_run, lse_run), None

        kv_off0 = (me * sq).astype(jnp.int32)[None]
        o0 = jnp.zeros(q.shape, jnp.float32)
        lse0 = jnp.full((b, sq, h), _NEG_BIG, jnp.float32)
        (_, _, _, out, lse), _ = lax.scan(
            step, (k, v, kv_off0, o0, lse0), None, length=n)
        return out.astype(q.dtype), lse

    @jax.custom_vjp
    def run(q, k, v):
        out, _ = fwd_impl(q, k, v)
        return out

    def run_fwd(q, k, v):
        out, lse = fwd_impl(q, k, v)
        return out, (q, k, v, out, lse)

    def run_bwd(res, g):
        q, k, v, out, lse = res
        me = lax.axis_index(axis_name)
        q_off = (me * sq).astype(jnp.int32)
        # softmax-jacobian row correction against the FINAL output,
        # shared by every block's partial backward: [B,Sq,H,D] -> [B,Sq,H]
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)

        def step(carry, _):
            k_blk, v_blk, kv_off, dq_acc, dk_acc, dv_acc = carry
            dq_p, dk_p, dv_p = fa.flash_attention_bwd_block(
                q, k_blk, v_blk, g, lse, delta, causal=causal,
                q_offset=q_off, kv_offset=kv_off[0])
            dq_acc = dq_acc + dq_p
            dk_acc = dk_acc + dk_p
            dv_acc = dv_acc + dv_p
            # dk/dv accumulators travel WITH their K/V block: after the
            # full cycle they land home holding all ranks' contributions
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)
            kv_off = lax.ppermute(kv_off, axis_name, perm)
            dk_acc = lax.ppermute(dk_acc, axis_name, perm)
            dv_acc = lax.ppermute(dv_acc, axis_name, perm)
            return (k_blk, v_blk, kv_off, dq_acc, dk_acc, dv_acc), None

        kv_off0 = (me * sq).astype(jnp.int32)[None]
        zeros = jnp.zeros((b, sq, h, d), jnp.float32)
        (_, _, _, dq, dk, dv), _ = lax.scan(
            step, (k, v, kv_off0, zeros, zeros, zeros), None, length=n)
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    run.defvjp(run_fwd, run_bwd)
    return run(q, k, v)


def ulysses_attention(q, k, v, axis_name, causal=True, q_positions=None,
                      kv_positions=None):
    """Ulysses-style sequence parallelism: all-to-all from sequence-sharded
    to head-sharded, dense attention on the full sequence, and back.
    Requires ``num_heads % axis_size == 0``."""
    from horovod_tpu.models.transformer import dense_attention

    n = lax.axis_size(axis_name)
    b, sq, h, d = q.shape
    if h % n != 0:
        raise ValueError(f"num_heads {h} not divisible by axis size {n}")
    if q_positions is None:
        q_positions = default_positions(axis_name, b, sq)
    if kv_positions is None:
        kv_positions = q_positions

    def to_heads(x):  # [B,S/n,H,D] -> [B,S,H/n,D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qg, kg, vg = to_heads(q), to_heads(k), to_heads(v)
    pos_full = lax.all_gather(q_positions, axis_name, axis=1, tiled=True)
    kv_pos_full = lax.all_gather(kv_positions, axis_name, axis=1, tiled=True)
    out = dense_attention(qg, kg, vg, causal=causal, q_positions=pos_full,
                          kv_positions=kv_pos_full)
    # back: [B,S,H/n,D] -> [B,S/n,H,D]
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)
