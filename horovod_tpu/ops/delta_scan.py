"""The gated delta rule's chunked scan as Pallas TPU kernels.

The recurrence of ``models/kda.py`` (a delta rule whose decay is a vector,
one rate a channel of the key) from ``(q, k, v, g, beta)`` to ``o``, forward
and backward, behind one ``jax.custom_vjp``. Both kernels run on a grid over
(batch, heads, chunks) with the chunk axis sequential, a few heads side by
side and a few chunks of each in a loop to a step (``HEADS_A_STEP``,
``CHUNKS_A_STEP``): a chunk of
``C`` positions lives in VMEM from its inputs to its outputs, and the ``D x
E`` float32 state of a head (key x value, held transposed, ``[E, D]``, so
that a decay a channel is a row that broadcasts down sublanes) is a VMEM
scratch that a chunk reads and hands on. Nothing but the inputs, ``o`` and
the state each chunk inherits (``[B, H, S / C, E, D]`` float32, what the
backward kernel reads in place of a second forward pass) is written to HBM:
no triangle, no inverse, no decayed copy of k.

The arrays stay in the model's layout: ``[B, S, H, D]`` is ``[B, S, H * D]``
for nothing, and a block of it is ``C`` rows of one head's 128-lane slab, so
no transpose to heads-first surrounds the kernels. ``beta`` alone is
re-laid, ``[B, H, S / C, C]`` (1 MB at the cell's sizes): a head's rows stay
in VMEM while its chunks go by, and ``d beta`` is written the same way.

One chunk, with ``G`` the running sum of ``g`` inside it and ``S_0`` what it
inherits (``models/kda.py`` has the derivation):

    M_kk[t, i] = sum_c k[t, c] k[i, c] exp(G[t, c] - G[i, c])     i <  t
    M_qk[t, i] = sum_c q[t, c] k[i, c] exp(G[t, c] - G[i, c])     i <= t
    T   = (I + Diag(beta) M_kk)^-1
    U   = T Diag(beta) (V - (K * exp(G)) S_0)
    o   = (Q * exp(G)) S_0 + M_qk U
    S_C = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

Every ``exp`` is of a difference of ``G`` that is not positive. On the
``SUB x SUB`` (8 x 8) blocks of the diagonal the pairs are formed one offset
``t - i`` at a time: k rolled down ``s`` sublanes against k and q, ``[C, D]``
float32 multiplies and a lane sum a shift, whose ``[8, 128]`` rows are one
vector register each. Below the diagonal blocks a pair goes through the
later block's first position ``b``, ``exp(G_t - G_b) * exp(G_b - G_i)``,
both factors at most 1: a product a block row on the matrix unit. ``T`` is
built as ``models/kda._inverse_of_unit_lower`` builds it, from the inverses
of the diagonal blocks merged two by two: the diagonal blocks by
substitution on the vector unit, row ``r`` of all of them in one register,
with the pairs' sums of the shifts as multipliers; a merge as two masked ``C
x C`` float32 products, ``T <- T - T X T`` with ``X`` the part of ``A`` that
joins two neighbouring blocks.

The backward kernel walks the chunks in reverse carrying ``dS``. For each
chunk it makes the chunk-local forward again from the chunk's inputs and the
state it inherited, keeps the decayed copies in VMEM, and applies the
hand-derived transposes below (``_chunk_backward``): the triangles' part of
``dq``, ``dk`` and ``dG`` are the same decayed products with the cotangent
matrices in place of one operand, and ``dG`` of a decayed product is
``x * dx`` of its two ends. ``dg`` is the reverse running sum of ``dG``,
inside the kernel.

Precision, the configuration's (``scan_statistics_dtype``): ``g``, ``G``,
``beta``, ``A``, ``T`` and the carried state are float32; the operands of
the products with q, k, v, u and the cotangents are the inputs' dtype with
float32 accumulation; the products that build ``T`` run at the highest
precision.

Off the TPU the kernels run under ``interpret=True``, which is how the CPU
tests exercise them; in a process whose devices are TPUs they are never
interpreted. ``supported`` says which shapes the kernels are built for;
``models/kda.chunked_delta_scan`` sends every other shape through its plain
``jax.numpy`` form.
"""

import functools
import math
import types

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB = 8      # positions of a diagonal block: the sublanes of a register
LANES = 128
NEG = -1e30  # the log-decay of a pair that is not there: exp gives 0
# What a grid step takes, where the shape allows: four heads side by side,
# their code laid out one after the other so that the compiler fills the
# waits of one head's chain of small products with another's work, and four
# chunks of each in a loop, so that the grid's fixed cost a step (0.35 us)
# is paid a sixteenth as often. The loop's body is traced once and the two
# kernels once for all layers of a model (``_forward`` and ``_backward`` are
# jitted): the chunks of a step unrolled in Python ran 0.8 ms a layer faster
# and cost every program that holds the kernels seconds of tracing (the
# cell's warm set-up 117.6 s against 50.3). Forward + backward a layer at
# 2 x 4096 positions, 32 heads of 128, chunks of 64, bfloat16 (my chip runs,
# PR 34): heads x chunks a step 1 x 4 18.95 ms, 1 x 16 18.81, 2 x 2 16.87,
# 2 x 8 16.72, 4 x 4 15.96 (as here); 2 x 16 passes the kernels' 16 MiB of
# VMEM.
HEADS_A_STEP = 4
CHUNKS_A_STEP = 4
_NN = (((1,), (0,)), ((), ()))  # dot_general: a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def supported(chunk, d, d_v, dtype):
    """True where the kernels are built for these shapes: key and value
    channels whole 128-lane slabs, and a chunk of whole sublane tiles of
    ``dtype`` (8 rows of float32, 16 of bfloat16)."""
    rows = SUB * max(1, 4 // jnp.dtype(dtype).itemsize)
    return (d % LANES == 0 and d_v % LANES == 0 and chunk % rows == 0
            and chunk <= LANES)


def _dot(a, b, dims, dtype):
    """A product with q, k, v, u or a cotangent: operands in ``dtype``,
    float32 accumulation."""
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                               preferred_element_type=_F32)


def _dot32(a, b):
    """``a @ b`` of two float32 statistics (the inverse's steps)."""
    return jax.lax.dot_general(a, b, _NN, precision=_HIGHEST,
                               preferred_element_type=_F32)


def _down(x, s):
    """Rows moved ``s`` sublanes down: ``out[t] = x[t - s]`` (the first
    ``s`` rows wrap round; callers mask them)."""
    return x if s == 0 else pltpu.roll(x, s, 0)


def _up(x, s):
    """``out[t] = x[t + s]`` (the last ``s`` rows wrap round)."""
    return x if s == 0 else pltpu.roll(x, x.shape[0] - s, 0)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _running_sum(x, rows):
    """Inclusive running sum down the rows, in doubling steps."""
    s = 1
    while s < x.shape[0]:
        x = x + jnp.where(rows >= s, _down(x, s), 0.0)
        s *= 2
    return x


def _reverse_running_sum(x, rows):
    n, s = x.shape[0], 1
    while s < n:
        x = x + jnp.where(rows < n - s, _up(x, s), 0.0)
        s *= 2
    return x


def _column(row_vector, eye):
    """``[1, C]`` -> ``[C, 1]`` through the diagonal of a ``[C, C]``."""
    return jnp.sum(jnp.where(eye, row_vector, 0.0), 1, keepdims=True)


def _row(column, eye):
    """``[C, 1]`` -> ``[1, C]``."""
    return jnp.sum(jnp.where(eye, column, 0.0), 0, keepdims=True)


def _inverse_of_unit_lower(a, near, row, col, work):
    """``(I + a)^-1`` for ``a`` [C, C] float32 strictly lower triangular,
    as ``models/kda._inverse_of_unit_lower`` builds it: the ``SUB``-wide
    diagonal blocks first, then blocks merged two by two, ``[[P, 0], [X,
    Q]]^-1 = [[P^-1, 0], [-Q^-1 X P^-1, Q^-1]]``. Exact for any strictly
    lower ``a``.

    The diagonal blocks by substitution, all of them at once: row ``r`` of
    every block is one ``[C / SUB, C]`` register (a strided read of the
    scratch), ``T_r = e_r - sum_{j<r} a[r, j] T_j``, and the multipliers
    are what the shifts left in ``near`` (``near[s][t] = a[t, t - s]``
    along the lanes): 28 multiplies and subtractions, float32 on the
    vector unit. The merges on the matrix unit at the highest precision:
    with ``T`` the inverse of the diagonal blocks so far and ``X`` the part
    of ``a`` that joins neighbours, ``T - T X T``."""
    c = a.shape[0]
    blocks = c // SUB
    block = _iota((blocks, LANES), 0)
    lane = _iota((blocks, LANES), 1)
    rows_of = lambda r: pl.ds(r, blocks, stride=SUB)  # noqa: E731
    solved = []
    for r in range(SUB):
        t = (lane == SUB * block + r).astype(_F32)
        for j in range(r):
            t = t - near[r - j, rows_of(r), :] * solved[j]
        solved.append(t)
        work[rows_of(r), :] = t
    inverse = work[:, :c]
    width = SUB
    while width < c:
        joins = jnp.where((row // (2 * width) == col // (2 * width))
                          & (row // width != col // width), a, 0.0)
        inverse = inverse - _dot32(_dot32(inverse, joins), inverse)
        width *= 2
    return inverse


def _prepare(q, k, g, beta_row, dtype, work, kept=None):
    """The chunk-local forward up to ``T``. q, k, g ``[C, D]`` float32,
    beta_row ``[1, C]``. ``work``: scratch of the inverse's diagonal blocks
    (``near`` ``[SUB, C, LANES]``, ``inverse`` ``[C, LANES]`` float32).
    ``kept``: the backward kernel's scratch for the decayed copies
    (``decay``, ``k_at`` ``[SUB, C, D]`` float32 a shift; ``cols_decay``
    float32 and ``k_cols`` ``dtype`` ``[C / SUB, C, D]`` a block row), or
    ``None``."""
    c, d = k.shape
    blocks = c // SUB
    m = types.SimpleNamespace()
    m.rows = rows = _iota((c, d), 0)
    m.row = row = _iota((c, c), 0)
    m.col = col = _iota((c, c), 1)
    m.eye = row == col
    m.beta = _column(beta_row, m.eye)                        # [C, 1]
    cum = _running_sum(g, rows)                              # G
    # since the first position of its own block: the running sum of the
    # block's other rows
    since = jnp.where(rows % SUB == 0, 0.0, g)
    s = 1
    while s < SUB:
        since = since + jnp.where(rows % SUB >= s, _down(since, s), 0.0)
        s *= 2
    m.since = since = jnp.exp(since)
    m.decayed = jnp.exp(cum)                                 # exp(G)
    whole = cum[c - 1:c, :]                                  # G_C [1, D]
    m.to_end = jnp.exp(whole - cum)                          # exp(G_C - G)
    m.through = jnp.exp(whole)                               # exp(G_C)

    # the diagonal blocks, one offset t - i = s at a time
    kk = jnp.zeros((c, c), _F32)
    qk = jnp.zeros((c, c), _F32)
    for s in range(SUB):
        decay = jnp.exp(jnp.where(rows % SUB >= s, cum - _down(cum, s),
                                  NEG))
        k_at = _down(k, s) * decay             # k_i decayed from i to t
        if kept is not None:
            kept.decay[s], kept.k_at[s] = decay, k_at
        at = col == row - s
        if s:
            pair = jnp.sum(k * k_at, 1, keepdims=True)
            work.near[s] = jnp.broadcast_to(m.beta * pair, (c, LANES))
            kk = kk + jnp.where(at, pair, 0.0)
        qk = qk + jnp.where(at, jnp.sum(q * k_at, 1, keepdims=True), 0.0)
    # below them, a block row at a time through its first position: rows
    # decayed since it, columns decayed up to it and nothing from it on
    m.k_rows, m.q_rows = k * since, q * since
    if blocks > 1:
        below_kk, below_qk = [jnp.zeros((SUB, c), _F32)], [
            jnp.zeros((SUB, c), _F32)]
        for j in range(1, blocks):
            first = j * SUB
            cols_decay = jnp.exp(jnp.where(
                rows < first, cum[first:first + 1, :] - cum, NEG))
            k_cols = (k * cols_decay).astype(dtype)
            if kept is not None:
                kept.cols_decay[j], kept.k_cols[j] = cols_decay, k_cols
            both = _dot(jnp.concatenate(
                [m.k_rows[first:first + SUB], m.q_rows[first:first + SUB]],
                0), k_cols, _NT, dtype)                      # [2 SUB, C]
            below_kk.append(both[:SUB])
            below_qk.append(both[SUB:])
        kk = kk + jnp.concatenate(below_kk, 0)
        qk = qk + jnp.concatenate(below_qk, 0)
    m.kk, m.qk = kk, qk
    m.inverse = _inverse_of_unit_lower(m.beta * kk, work.near, row, col,
                                       work.inverse)
    return m


def _meet_state(m, q, k, v, state, dtype):
    """``(R, U)`` of a prepared chunk against the state it inherits
    (``[E, D]``, transposed): ``R = V - (K exp(G)) S_0`` and ``U = T
    Diag(beta) R``."""
    m.k_decayed, m.q_decayed = k * m.decayed, q * m.decayed
    m.k_to_end = k * m.to_end
    residual = v - _dot(m.k_decayed, state, _NT, dtype)
    u = _dot(m.inverse, m.beta * residual, _NN, dtype)
    return residual, u


def _streams(heads, chunks):
    """How many heads and how many chunks of each a grid step takes."""
    return math.gcd(heads, HEADS_A_STEP), math.gcd(chunks, CHUNKS_A_STEP)


def _scratch(names, refs, i):
    """The ``i``-th chunk in flight's slice of each scratch, by name."""
    return types.SimpleNamespace(
        **{name: ref.at[i] for name, ref in zip(names, refs)})


_WORK = ("near", "inverse")
_KEPT = ("decay", "k_at", "cols_decay", "k_cols")


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, states_ref,
                    state, *work, chunk, d, d_v):
    n = pl.program_id(2)
    dtype = v_ref.dtype
    heads, chunks = states_ref.shape[:2]

    @pl.when(n == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def one(r, _):
        rows = pl.ds(pl.multiple_of(r * chunk, chunk), chunk)
        for p in range(heads):
            key, value = pl.ds(p * d, d), pl.ds(p * d_v, d_v)
            q, k, v = (x[rows, lanes].astype(_F32) for x, lanes in (
                (q_ref, key), (k_ref, key), (v_ref, value)))
            inherited = state[p]
            states_ref[p, r] = inherited
            m = _prepare(q, k, g_ref[rows, key],
                         beta_ref[p, pl.ds(n * chunks + r, 1), :], dtype,
                         _scratch(_WORK, work, p))
            _, u = _meet_state(m, q, k, v, inherited, dtype)
            o_ref[rows, value] = (
                _dot(m.q_decayed, inherited, _NT, dtype)
                + _dot(m.qk, u, _NN, dtype)).astype(o_ref.dtype)
            state[p] = inherited * m.through + _dot(u, m.k_to_end, _TN,
                                                    dtype)

    jax.lax.fori_loop(0, chunks, one, None)


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref,
                     do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                     d_state, *scratch, chunk, d, d_v):
    steps = pl.num_programs(2)
    n = steps - 1 - pl.program_id(2)      # the chunks in reverse
    dtype = v_ref.dtype
    heads, chunks = states_ref.shape[:2]

    @pl.when(n == steps - 1)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    def one(i, _):
        r = chunks - 1 - i
        rows = pl.ds(pl.multiple_of(r * chunk, chunk), chunk)
        at = pl.ds(n * chunks + r, 1)
        for p in range(heads):
            key, value = pl.ds(p * d, d), pl.ds(p * d_v, d_v)
            q, k, v = (x[rows, lanes].astype(_F32) for x, lanes in (
                (q_ref, key), (k_ref, key), (v_ref, value)))
            d_q, d_k, d_v_, d_g, d_beta, d_state[p] = _chunk_backward(
                q, k, v, g_ref[rows, key], beta_ref[p, at, :],
                states_ref[p, r], do_ref[rows, value], d_state[p], dtype,
                _scratch(_WORK, scratch[:2], p),
                _scratch(_KEPT, scratch[2:], p))
            dq_ref[rows, key] = d_q.astype(dq_ref.dtype)
            dk_ref[rows, key] = d_k.astype(dk_ref.dtype)
            dv_ref[rows, value] = d_v_.astype(dv_ref.dtype)
            dg_ref[rows, key] = d_g
            dbeta_ref[p, at, :] = d_beta

    jax.lax.fori_loop(0, chunks, one, None)


def _chunk_backward(q, k, v, g, beta_row, state, d_o, d_next, dtype, work,
                    kept):
    """``(dq, dk, dv, dg, d beta [1, C], dS_0)`` of one chunk from ``dO``
    and the ``dS_C`` the next chunk handed back, the chunk's forward made
    again from its inputs and the state it inherited."""
    c, d = k.shape
    m = _prepare(q, k, g, beta_row, dtype, work, kept)
    residual, u = _meet_state(m, q, k, v, state, dtype)

    # o = (Q exp(G)) S_0 + M_qk U;  S_C = exp(G_C) S_0 + (K exp(G_C - G))^T U
    d_u = (_dot(m.qk, d_o, _TN, dtype)
           + _dot(m.k_to_end, d_next, _NT, dtype))           # [C, E]
    d_qk = jnp.where(m.col <= m.row, _dot(d_o, u, _NT, dtype), 0.0)
    d_q_decayed = _dot(d_o, state, _NN, dtype)               # [C, D]
    d_k_to_end = _dot(u, d_next, _NN, dtype)                 # [C, D]
    # U = T X, X = beta R, T = (I + A)^-1:  dX = T^T dU,  dA = -dX U^T
    d_x = _dot(m.inverse, d_u, _TN, dtype)                   # [C, E]
    d_a = jnp.where(m.col < m.row, -_dot(d_x, u, _NT, dtype), 0.0)
    d_beta = (jnp.sum(d_x * residual, 1, keepdims=True)
              + jnp.sum(d_a * m.kk, 1, keepdims=True))       # [C, 1]
    d_kk = m.beta * d_a
    d_residual = m.beta * d_x                                # dV
    d_k_decayed = -_dot(d_residual, state, _NN, dtype)       # [C, D]
    d_through = jnp.sum(state * d_next, 0, keepdims=True)    # [1, D]
    d_state = (d_next * m.through + _dot(d_o, m.q_decayed, _TN, dtype)
               - _dot(d_residual, m.k_decayed, _TN, dtype))

    # the triangles. With w[t, i, c] = exp(G[t, c] - G[i, c]):
    #   by_row_k[t] = sum_i dM_kk[t, i] k[i] w    by_row_q likewise, dM_qk
    #   by_col[i]   = sum_t (dM_kk[t, i] k[t] + dM_qk[t, i] q[t]) w
    # dq = by_row_q, dk = by_row_k + by_col, and the decay's share of dG is
    # k by_row_k + q by_row_q - k by_col
    by_row_k = jnp.zeros((c, d), _F32)
    by_row_q = jnp.zeros((c, d), _F32)
    by_col = jnp.zeros((c, d), _F32)
    for s in range(SUB):
        at = m.col == m.row - s
        decay, k_at = kept.decay[s], kept.k_at[s]
        pair_qk = jnp.sum(jnp.where(at, d_qk, 0.0), 1, keepdims=True)
        by_row_q = by_row_q + pair_qk * k_at
        met = pair_qk * q
        if s:
            pair_kk = jnp.sum(jnp.where(at, d_kk, 0.0), 1, keepdims=True)
            by_row_k = by_row_k + pair_kk * k_at
            met = met + pair_kk * k
        by_col = by_col + _up(met * decay, s)
    if c > SUB:
        below_k, below_q = [jnp.zeros((SUB, d), _F32)], [
            jnp.zeros((SUB, d), _F32)]
        for j in range(1, c // SUB):
            first = j * SUB
            both = jnp.concatenate([d_kk[first:first + SUB],
                                    d_qk[first:first + SUB]], 0)  # [2 SUB, C]
            rows = jnp.concatenate([m.k_rows[first:first + SUB],
                                    m.q_rows[first:first + SUB]], 0)
            by_row = _dot(both, kept.k_cols[j], _NN, dtype)  # [2 SUB, D]
            below_k.append(by_row[:SUB])
            below_q.append(by_row[SUB:])
            by_col = by_col + kept.cols_decay[j] * _dot(both, rows, _TN,
                                                        dtype)
        by_row_k = by_row_k + m.since * jnp.concatenate(below_k, 0)
        by_row_q = by_row_q + m.since * jnp.concatenate(below_q, 0)

    d_q = by_row_q + d_q_decayed * m.decayed
    d_k = (by_row_k + by_col + d_k_decayed * m.decayed
           + d_k_to_end * m.to_end)
    ended = m.k_to_end * d_k_to_end
    d_cum = (k * (by_row_k - by_col) + q * by_row_q
             + m.q_decayed * d_q_decayed + m.k_decayed * d_k_decayed - ended)
    d_whole = (jnp.sum(ended, 0, keepdims=True) + m.through * d_through)
    d_cum = d_cum + jnp.where(m.rows == c - 1, d_whole, 0.0)
    return (d_q, d_k, d_residual, _reverse_running_sum(d_cum, m.rows),
            _row(d_beta, m.eye), d_state)


def _specs(chunk, d, d_v, steps, streams, at):
    """Block specs on the grid (batch, heads / heads a step, chunks / chunks
    a step), ``at(n)`` the chunks a step works on: the heads' slabs of
    ``[B, S, H * D]``, their rows of beta ``[B, H, S / C, C]`` whole, the
    chunks' states ``[B, H, S / C, E, D]``."""
    heads, chunks = streams
    slab = lambda width: pl.BlockSpec(  # noqa: E731
        (None, chunks * chunk, heads * width),
        lambda b, h, n: (b, at(n), h))
    beta = pl.BlockSpec((None, heads, steps * chunks, chunk),
                        lambda b, h, n: (b, h, 0, 0))
    state = pl.BlockSpec((None, heads, chunks, d_v, d),
                         lambda b, h, n: (b, h, at(n), 0, 0))
    return slab(d), slab(d_v), beta, state


_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _sizes(q, v, heads, chunk):
    bsz, s, _ = q.shape
    d, d_v = q.shape[-1] // heads, v.shape[-1] // heads
    streams = _streams(heads, s // chunk)
    grid = (bsz, heads // streams[0], s // chunk // streams[1])
    in_flight = streams[0]
    work = [pltpu.VMEM((in_flight, SUB, chunk, LANES), _F32),
            pltpu.VMEM((in_flight, chunk, LANES), _F32)]
    return d, d_v, streams, grid, in_flight, work


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _forward(q, k, v, g, beta, heads, chunk, interpret):
    d, d_v, streams, grid, _, work = _sizes(q, v, heads, chunk)
    key, value, rows, state = _specs(chunk, d, d_v, grid[2], streams,
                                     lambda n: n)
    return pl.pallas_call(
        functools.partial(_forward_kernel, chunk=chunk, d=d, d_v=d_v),
        grid=grid,
        in_specs=[key, key, value, key, rows],
        out_specs=[value, state],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(
                       (grid[0], heads, q.shape[1] // chunk, d_v, d), _F32)],
        scratch_shapes=[pltpu.VMEM((streams[0], d_v, d), _F32), *work],
        compiler_params=_SEMANTICS, interpret=interpret,
        name="delta_scan_forward")(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def _backward(q, k, v, g, beta, states, d_o, heads, chunk, interpret):
    d, d_v, streams, grid, in_flight, work = _sizes(q, v, heads, chunk)
    key, value, rows, state = _specs(chunk, d, d_v, grid[2], streams,
                                     lambda n: grid[2] - 1 - n)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_backward_kernel, chunk=chunk, d=d, d_v=d_v),
        grid=grid,
        in_specs=[key, key, value, key, rows, state, value],
        out_specs=[key, key, value, key, rows],
        out_shape=[like(q), like(k), like(v), like(g), like(beta)],
        scratch_shapes=[
            pltpu.VMEM((streams[0], d_v, d), _F32), *work,
            pltpu.VMEM((in_flight, SUB, chunk, d), _F32),
            pltpu.VMEM((in_flight, SUB, chunk, d), _F32),
            pltpu.VMEM((in_flight, chunk // SUB, chunk, d), _F32),
            pltpu.VMEM((in_flight, chunk // SUB, chunk, d), v.dtype)],
        compiler_params=_SEMANTICS, interpret=interpret,
        name="delta_scan_backward")(q, k, v, g, beta, states, d_o)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _scan(q, k, v, g, beta, heads, chunk, interpret):
    return _forward(q, k, v, g, beta, heads, chunk, interpret)[0]


def _scan_fwd(q, k, v, g, beta, heads, chunk, interpret):
    o, states = _forward(q, k, v, g, beta, heads, chunk, interpret)
    return o, (q, k, v, g, beta, states)


def _scan_bwd(heads, chunk, interpret, kept, d_o):
    return tuple(_backward(*kept, d_o, heads, chunk, interpret))


_scan.defvjp(_scan_fwd, _scan_bwd)


def delta_scan(q, k, v, g, beta, chunk, *, interpret=None):
    """``o`` [B, S, H, E] of the gated delta rule; the arguments of
    ``models/kda.chunked_delta_scan``: q, k [B, S, H, D] and v [B, S, H, E]
    in one dtype, q and k normed; g [B, S, H, D] float32, not positive;
    beta [B, S, H] float32; ``chunk`` divides S. Differentiable in all
    five, and it keeps nothing for its backward pass but its inputs and the
    state each chunk inherits."""
    on_tpu = jax.devices()[0].platform == "tpu"
    if interpret is None:
        interpret = not on_tpu
    elif interpret and on_tpu:
        raise ValueError(
            "the delta scan's kernels are not interpreted in a process "
            "whose devices are TPUs; drop interpret=True")
    bsz, s, h, d = k.shape
    d_v = v.shape[-1]
    if s % chunk or not supported(chunk, d, d_v, v.dtype):
        raise ValueError(
            f"the delta scan's kernels take key and value channels that "
            f"{LANES} divides and a chunk of whole sublane tiles that "
            f"divides the sequence (s={s}, chunk={chunk}, d={d}, "
            f"d_v={d_v}, {v.dtype}); models/kda.chunked_delta_scan sends "
            f"other shapes through its plain form")
    slabs = lambda x: x.reshape(bsz, s, -1)  # noqa: E731
    o = _scan(slabs(q), slabs(k), slabs(v), slabs(g.astype(_F32)),
              jnp.moveaxis(beta.astype(_F32), 2, 1).reshape(
                  bsz, h, s // chunk, chunk),
              h, chunk, interpret)
    return o.reshape(bsz, s, h, d_v)
