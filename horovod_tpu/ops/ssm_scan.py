"""The state-space mixer's chunked scan as Pallas TPU kernels.

The recurrence of ``models/ssm.py`` (Mamba-2: a decay a head, ``G`` groups
that share one ``B`` and one ``C``) from ``(u, B, C, step, A, D)`` to ``o``,
forward and backward, behind one ``jax.custom_vjp``. Both kernels run on a
grid over (batch, groups, chunks) with the chunk axis sequential and a few
chunks to a step (``CHUNKS_A_STEP``): a chunk of ``C`` positions lives in
VMEM from its inputs to its outputs, and the ``P x N`` float32 states of a
group's heads (one scratch, held transposed, ``[N, R * P]``: a head's
channels along its own lanes, as in ``u``) are what a chunk reads and hands
on. Nothing but the inputs, ``o`` and the state each chunk inherits (``[B,
G, S / C, N, R * P]`` float32, what the backward kernel reads in place of a
second forward pass) is written to HBM: no decay triangle, no scores, no
end states.

The arrays stay in the model's layout: ``u`` and ``o`` are ``[B, S, H * P]``
and ``B`` and ``C`` ``[B, S, G * N]`` for nothing, and a block is ``C`` rows
of one group: its ``R = H / G`` heads' lanes of ``u`` (whole 128-lane slabs,
``128 / P`` heads to a slab) and its one slab of ``B`` and of ``C``, so no
transpose to heads-first surrounds the kernels, ``C B^T`` is made once a
chunk for the group's heads, and ``dB`` and ``dC``, which sum over them, are
finished inside a step. Where a head is narrower than a slab its scores
multiply the whole slab and its own lanes of the product are kept, and the
one product that contracts over a head's channels (``do x^T``) is given
``x`` with the other heads' lanes zeroed: the matrix unit is 128 wide
whatever is asked of it, and nothing is shuffled between lanes. The step
and its running log-decay alone are re-laid, by group and chunk with a row
a head (``[B, G, S / C, R, C]``, 2 MB each at the cell's sizes, positions
along the lanes), in ``jax.numpy`` around the kernels, where the running
sum itself is made (a product with a triangle of ones at float32's
precision) and differentiated; a chunk's ``[R, C]`` is transposed inside
the kernels, so that a head's running sum is at hand along the lanes (a
row of the ref) and down the sublanes (a column, spread over the head's
lanes by the lane-permute unit).

One chunk, one head, with ``G`` the running sum of ``step * A`` inside it,
``S_0`` what it inherits and ``x = step * u`` (``models/ssm.py`` has the
recurrence):

    L[l, m] = exp(G_l - G_m)                     m <= l, else 0
    o   = ((C B^T) * L) x + exp(G) * (C S_0^T) + D u
    S_C = exp(G_C) S_0 + (x * exp(G_C - G))^T B

Every ``exp`` is of a difference that is not positive, masked before it.

The backward kernel walks the chunks in reverse carrying ``dS``. For each
chunk it makes the chunk-local forward again from the chunk's inputs and the
state it inherited, and applies the hand-derived transposes in
``_chunk_backward``. The gradient of ``G`` needs no ``[C, C]`` reduction:
every term of ``o`` at position ``l`` carries ``exp(G_l)``, every term that
``x_m`` feeds carries ``exp(-G_m)``, and the whole of ``S_C`` carries
``exp(G_C)``, so ``dG_l = <do_l, o_l - D u_l> - <x_l, dx_l> + [l = C]
<dS_C, S_C>`` (two sums that share every term on the diagonal, so where
nothing outlives its own position they cancel to float32's rounding of
themselves, not to zero). The sums over a head's channels run down the
sublanes of a transposed slab and come out a row a head, as the step came
in. ``dD`` is summed down the rows as the chunks go by and over a head's
channels outside; ``dA`` and the step's share through the decay follow from
``dG`` in ``jax.numpy`` (the triangle's product, transposed).

Precision, the configuration's (``scan_statistics_dtype``): the step, ``G``,
the decay factors and the carried and kept state are float32; the operands
of every product are the inputs' dtype with float32 accumulation, rounded
where ``models/ssm._plain_scan`` rounds them (the scores after the triangle
is applied, ``x`` before its products, the inherited state before ``C
S_0^T``).

Off the TPU the kernels run under ``interpret=True``, which is how the CPU
tests exercise them; in a process whose devices are TPUs they are never
interpreted. ``supported`` says which shapes the kernels are built for;
``models/ssm.chunked_scan`` sends every other shape through its plain
``jax.numpy`` form.
"""

import functools
import math
import types

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB = 8      # the sublanes of a register
LANES = 128
NEG = -1e30  # the log-decay of a pair that is not there: exp gives 0
# Chunks of one group to a grid step, where the sequence allows, in a
# ``lax.fori_loop`` whose body is traced once (and the two kernels once for
# all layers of a model: ``_forward`` and ``_backward`` are jitted) and laid
# out chunk after chunk when it is lowered, so that the compiler fills one
# chunk's waits with the next one's work (everything but what meets the
# state). Forward + backward a layer at 2 x 4096 positions, 64 heads of 64
# in 8 groups of 128 states, chunks of 128, bfloat16, the kernels alone (my
# chip runs, PR 36, device time by instruction): 4 chunks in a rolled loop
# 0.605 + 1.064 ms, 2 and 8 the same within 2%; 4 laid out 0.423 + 0.898;
# half a second more of lowering a program that holds them.
CHUNKS_A_STEP = 4
_NN = (((1,), (0,)), ((), ()))  # dot_general: a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_F32 = jnp.float32


def supported(chunk, p, heads_a_group, n, dtype):
    """True where the kernels are built for these shapes: a head's ``p``
    channels a whole share of a 128-lane slab (or the slab), a group's
    heads whole slabs, ``n`` states whole slabs, and a chunk of whole
    sublane tiles of ``dtype`` (8 rows of float32, 16 of bfloat16), 128
    rows at most."""
    rows = SUB * max(1, 4 // jnp.dtype(dtype).itemsize)
    return (LANES % p == 0 and (heads_a_group * p) % LANES == 0
            and n % LANES == 0 and chunk % rows == 0 and chunk <= LANES)


def _dot(a, b, dims, dtype):
    """A product: operands in ``dtype``, float32 accumulation."""
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                               preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _of_head(x, i, p, lane):
    """A float32 slab with every lane but head ``i``'s zeroed."""
    if p == LANES:
        return x
    return jnp.where(lane // p == i, x, 0.0)


def _lanes_of(columns, p, lane):
    """``[C, 128]`` arrays, one a head of a slab and each the same along
    its lanes -> one ``[C, 128]``, each head's over its own ``p`` lanes. A
    lone head's goes through a select too: what is only a broadcast has no
    layout of its own, and Mosaic cannot cut a row from it and spread that
    down the sublanes ("broadcast in both sublanes and lanes")."""
    out = columns[0]
    for i, column in enumerate(columns[1:], 1):
        out = jnp.where(lane >= i * p, column, out)
    return out if len(columns) > 1 else jnp.where(lane >= 0, out, 0.0)


def _chunk(u_ref, b_ref, c_ref, dt_ref, cum_ref, r, rows, p, state, dtype):
    """What both kernels make of a chunk before they part: ``B``, ``C``
    and ``C B^T``, the mask, and ``slab(j)`` for the pieces of one slab of
    heads. ``state``: a ref of what the chunk inherits, held transposed,
    ``[N, R P]``."""
    c = rows.size
    q = LANES // p
    m = types.SimpleNamespace()
    m.b, m.c = b_ref[rows, :], c_ref[rows, :]
    cb = _dot(m.c, m.b, _NT, dtype)
    m.visible = _iota((c, c), 1) <= _iota((c, c), 0)
    m.lane = lane = _iota((c, LANES), 1)
    # the step and G: a row a head as they come, a column a head here
    dt, cum = dt_ref[r].T, cum_ref[r].T                      # [C, R]

    def slab(j, d_o=None):
        k = types.SimpleNamespace()
        k.lanes = pl.ds(j * LANES, LANES)
        k.u = u_ref[rows, k.lanes].astype(_F32)
        # the step and G, a head's over its own lanes; exp(G), exp(G_C - G)
        # and exp(G_C), the last a row that meets the state's lanes
        along = lambda x, h: jnp.broadcast_to(  # noqa: E731
            x[:, h:h + 1], lane.shape)
        heads = range(j * q, (j + 1) * q)
        k.dt = _lanes_of([along(dt, h) for h in heads], p, lane)
        since_a_head = [along(cum, h) for h in heads]
        since = _lanes_of(since_a_head, p, lane)
        last = since[c - 1:c, :]
        k.since, k.to_end = jnp.exp(since), jnp.exp(last - since)
        k.through = jnp.exp(last)
        x = k.u * k.dt                                       # step * u
        k.x = x.astype(dtype)
        k.scores, inside, k.d_cb = [], 0.0, 0.0
        for i in range(q):
            h = j * q + i
            decay = jnp.exp(jnp.where(
                m.visible, since_a_head[i][:, :c] - cum_ref[r, h:h + 1, :],
                NEG))
            k.scores.append((cb * decay).astype(dtype))
            # the head's product over the whole slab, its own lanes kept
            inside = _of_head(_dot(k.scores[i], k.x, _NN, dtype), i, p,
                              lane) + inside
            if d_o is not None:
                # d(cb) through this head's scores; 0 where decay is
                k.d_cb += _dot(d_o, _of_head(x, i, p, lane), _NT,
                               dtype) * decay
        k.o = inside + k.since * _dot(                       # o - D u
            m.c, state[:, k.lanes], _NN, dtype)
        k.x32 = k.x.astype(_F32)
        k.x_to_end = (k.x32 * k.to_end).astype(dtype)
        return k

    m.slab = slab
    return m


def _forward_kernel(u_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, o_ref,
                    states_ref, state, *, chunk, p):
    dtype = u_ref.dtype
    chunks = states_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def one(r, _):
        rows = pl.ds(pl.multiple_of(r * chunk, chunk), chunk)
        states_ref[r] = state[...]
        m = _chunk(u_ref, b_ref, c_ref, dt_ref, cum_ref, r, rows, p, state,
                   dtype)
        b_t = m.b.T
        for j in range(u_ref.shape[-1] // LANES):
            k = m.slab(j)
            o_ref[rows, k.lanes] = (k.o + d_ref[:, k.lanes] * k.u).astype(
                o_ref.dtype)
            state[:, k.lanes] = (k.through * state[:, k.lanes]
                                 + _dot(b_t, k.x_to_end, _NN, dtype))

    jax.lax.fori_loop(0, chunks, one, None, unroll=True)


def _backward_kernel(u_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, states_ref,
                     do_ref, du_ref, db_ref, dc_ref, ddt_ref, dcum_ref,
                     dd_ref, d_state, ended, x_to_end, d_inherited, *, chunk,
                     p):
    dtype = u_ref.dtype
    chunks = states_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)       # the last chunks: walked first
    def _():
        d_state[...] = jnp.zeros_like(d_state)
        ended[...] = jnp.zeros_like(ended)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    def one(i, _):
        r = chunks - 1 - i
        rows = pl.ds(pl.multiple_of(r * chunk, chunk), chunk)
        _chunk_backward(u_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref,
                        states_ref.at[r], do_ref, du_ref, db_ref, dc_ref,
                        ddt_ref, dcum_ref, dd_ref, d_state, ended, x_to_end,
                        d_inherited, r, rows, p, dtype)

    jax.lax.fori_loop(0, chunks, one, None, unroll=True)


def _chunk_backward(u_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, before,
                    do_ref, du_ref, db_ref, dc_ref, ddt_ref, dcum_ref, dd_ref,
                    d_state, ended, x_to_end, d_inherited, r, rows, p, dtype):
    """One chunk's gradients from ``do`` and the ``dS_C`` the next chunk
    handed back (``d_state``; ``ended`` is the ``<dS_C, S_C>`` a head that
    it left, on the head's lanes), the chunk's forward made again from its
    inputs and the state it inherited (``before``). With ``x = step * u``:

        o   = (cb * L) x + exp(G) (C S_0) + D u
        S_C = exp(G_C) S_0 + B^T (x exp(G_C - G))        (held transposed)

    ``x exp(G_C - G)`` and ``exp(G) do`` go to a scratch a slab at a time,
    and the products that sum over a group's heads are made once from it.
    """
    c = rows.size
    q = LANES // p
    m = _chunk(u_ref, b_ref, c_ref, dt_ref, cum_ref, r, rows, p, before,
               dtype)
    last_row = _iota((c, 1), 0) == c - 1
    at = _iota(ddt_ref.shape[1:], 0)                         # [R, C]
    d_cb = jnp.zeros((c, c), _F32)
    d_dt = jnp.zeros(at.shape, _F32)
    d_cum = jnp.zeros(at.shape, _F32)
    through = []
    for j in range(u_ref.shape[-1] // LANES):
        d_o = do_ref[rows, lanes := pl.ds(j * LANES, LANES)]
        k = m.slab(j, d_o)
        d_o32 = d_o.astype(_F32)
        d_cb = d_cb + k.d_cb
        # dx: through the scores' product and through the end state
        d_x = _dot(m.b, d_state[:, lanes], _NN, dtype) * k.to_end
        for i in range(q):
            d_x += _of_head(_dot(k.scores[i], d_o, _TN, dtype), i, p, m.lane)
        du_ref[rows, lanes] = (
            d_x * k.dt + d_ref[:, lanes] * d_o32).astype(du_ref.dtype)
        dd_ref[:, lanes] += jnp.sum(d_o32 * k.u, 0, keepdims=True)
        # the step's own share <u_l, dx_l>, and dG_l = <do_l, o_l - D u_l>
        # - <x_l, dx_l>, with <dS_C, S_C> at C: a head's channels summed
        # down the sublanes of the transposes, a row a head
        by_step = (d_x * k.u).T                              # [128, C]
        by_decay = (d_o32 * k.o - k.x32 * d_x
                    + jnp.where(last_row, ended[:, lanes], 0.0)).T
        for i in range(q):
            head = (lambda y: jnp.sum(  # noqa: E731
                y[i * p:(i + 1) * p], 0, keepdims=True))
            d_dt = jnp.where(at == j * q + i, head(by_step), d_dt)
            d_cum = jnp.where(at == j * q + i, head(by_decay), d_cum)
        through.append(k.through)
        x_to_end[:, lanes] = k.x_to_end
        d_inherited[:, lanes] = (k.since * d_o32).astype(dtype)
    ddt_ref[r] = d_dt
    dcum_ref[r] = d_cum
    # the state's three products, all the group's heads at once
    db_ref[rows, :] = (_dot(x_to_end[...], d_state[...], _NT, dtype)
                       + _dot(d_cb, m.c, _TN, dtype)).astype(db_ref.dtype)
    dc_ref[rows, :] = (_dot(d_inherited[...], before[...], _NT, dtype)
                       + _dot(d_cb, m.b, _NN, dtype)).astype(dc_ref.dtype)
    c_t = m.c.T
    for j in range(u_ref.shape[-1] // LANES):
        lanes = pl.ds(j * LANES, LANES)
        handed = (through[j] * d_state[:, lanes]
                  + _dot(c_t, d_inherited[:, lanes], _NN, dtype))
        d_state[:, lanes] = handed
        ended[:, lanes] = jnp.sum(handed * before[:, lanes], 0,
                                  keepdims=True)


def _chunks_a_step(chunks):
    return math.gcd(chunks, CHUNKS_A_STEP)


def _specs(u, b, chunk, groups, r, at):
    """Block specs on the grid (batch, groups, chunks / chunks a step),
    ``at(n)`` the chunks a step works on: the group's slabs of ``u`` and of
    ``B`` / ``C``, its rows ``[B, G, S / C, R, C]``, ``D`` a lane ``[G, 1,
    R P]`` and the chunks' states ``[B, G, S / C, N, R P]``."""
    s = u.shape[1]
    wide, n = u.shape[-1] // groups, b.shape[-1] // groups
    step = _chunks_a_step(s // chunk)
    slab = lambda width: pl.BlockSpec(  # noqa: E731
        (None, step * chunk, width), lambda i, g, k: (i, at(k), g))
    rows = pl.BlockSpec((None, None, step, r, chunk),
                        lambda i, g, k: (i, g, at(k), 0, 0))
    lane = pl.BlockSpec((None, 1, wide), lambda i, g, k: (g, 0, 0))
    state = pl.BlockSpec((None, None, step, n, wide),
                         lambda i, g, k: (i, g, at(k), 0, 0))
    return slab(wide), slab(n), rows, lane, state


_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _grid(u, chunk, groups):
    s = u.shape[1]
    return (u.shape[0], groups, s // chunk // _chunks_a_step(s // chunk))


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _forward(u, b, c, dt, cum, d_skip, chunk, p, interpret):
    groups, r = dt.shape[1], dt.shape[3]
    n = b.shape[-1] // groups
    wide, narrow, rows, lane, state = _specs(
        u, b, chunk, groups, r, lambda k: k)
    return pl.pallas_call(
        functools.partial(_forward_kernel, chunk=chunk, p=p),
        grid=_grid(u, chunk, groups),
        in_specs=[wide, narrow, narrow, rows, rows, lane],
        out_specs=[wide, state],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct(
                       (u.shape[0], groups, u.shape[1] // chunk, n, r * p),
                       _F32)],
        scratch_shapes=[pltpu.VMEM((n, r * p), _F32)],
        compiler_params=_SEMANTICS, interpret=interpret,
        name="ssm_scan_forward")(u, b, c, dt, cum, d_skip)


@functools.partial(jax.jit, static_argnums=(8, 9, 10))
def _backward(u, b, c, dt, cum, d_skip, states, d_o, chunk, p, interpret):
    groups, r = dt.shape[1], dt.shape[3]
    n = b.shape[-1] // groups
    grid = _grid(u, chunk, groups)
    wide, narrow, rows, lane, state = _specs(
        u, b, chunk, groups, r, lambda k: grid[2] - 1 - k)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    *grads, d_d = pl.pallas_call(
        functools.partial(_backward_kernel, chunk=chunk, p=p),
        grid=grid,
        in_specs=[wide, narrow, narrow, rows, rows, lane, state, wide],
        out_specs=[wide, narrow, narrow, rows, rows,
                   pl.BlockSpec((None, None, 1, r * p),
                                lambda i, g, k: (i, g, 0, 0))],
        out_shape=[like(u), like(b), like(c), like(dt), like(cum),
                   jax.ShapeDtypeStruct(
                       (u.shape[0], groups, 1, r * p), _F32)],
        scratch_shapes=[pltpu.VMEM((n, r * p), _F32),
                        pltpu.VMEM((1, r * p), _F32),
                        pltpu.VMEM((chunk, r * p), u.dtype),
                        pltpu.VMEM((chunk, r * p), u.dtype)],
        compiler_params=_SEMANTICS, interpret=interpret,
        name="ssm_scan_backward")(u, b, c, dt, cum, d_skip, states, d_o)
    return (*grads, jnp.sum(d_d, 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan(u, b, c, dt, cum, d_skip, chunk, p, interpret):
    return _forward(u, b, c, dt, cum, d_skip, chunk, p, interpret)[0]


def _scan_fwd(u, b, c, dt, cum, d_skip, chunk, p, interpret):
    o, states = _forward(u, b, c, dt, cum, d_skip, chunk, p, interpret)
    return o, (u, b, c, dt, cum, d_skip, states)


def _scan_bwd(chunk, p, interpret, kept, d_o):
    return _backward(*kept, d_o, chunk, p, interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssm_scan(u, b, c, dt, a, d_skip, chunk, *, interpret=None):
    """``o`` [B, S, H, P] of the state-space recurrence; the arguments of
    ``models/ssm.chunked_scan``: u [B, S, H, P] and b, c [B, S, G, N] in
    one dtype; dt [B, S, H] float32, positive; a [H] float32, negative;
    d_skip [H]; ``chunk`` divides S. Differentiable in all six, and it
    keeps nothing for its backward pass but its inputs, the running
    log-decay and the state each chunk inherits."""
    on_tpu = jax.devices()[0].platform == "tpu"
    if interpret is None:
        interpret = not on_tpu
    elif interpret and on_tpu:
        raise ValueError(
            "the state-space scan's kernels are not interpreted in a "
            "process whose devices are TPUs; drop interpret=True")
    bsz, s, h, p = u.shape
    g, n = b.shape[2:]
    if h % g or s % chunk or not supported(chunk, p, h // g, n, u.dtype):
        raise ValueError(
            f"the state-space scan's kernels take heads that share a "
            f"{LANES}-lane slab, groups and states of whole slabs and a "
            f"chunk of whole sublane tiles that divides the sequence "
            f"(s={s}, chunk={chunk}, h={h}, p={p}, g={g}, n={n}, "
            f"{u.dtype}); models/ssm.chunked_scan sends other shapes "
            f"through its plain form")
    r = h // g
    # the step by group, a row a head of each chunk: [B, G, S / C, R, C]
    dt = dt.astype(_F32).reshape(bsz, s // chunk, chunk, g, r).transpose(
        0, 3, 1, 4, 2)
    # the log of the decay up to and including each position of its chunk:
    # a running sum along the lanes, as a product with a triangle of ones
    # at float32's precision (jnp.cumsum's reduce_window along 128 lanes
    # and its transpose took 1.0 ms a layer on the chip, more than the
    # backward kernel)
    cum = jnp.matmul(dt * a.astype(_F32).reshape(g, 1, r, 1),
                     jnp.triu(jnp.ones((chunk, chunk), _F32)),
                     precision=jax.lax.Precision.HIGHEST)
    o = _scan(u.reshape(bsz, s, h * p), b.reshape(bsz, s, g * n),
              c.reshape(bsz, s, g * n), dt, cum,
              jnp.repeat(d_skip.astype(_F32), p).reshape(g, 1, r * p),
              chunk, p, interpret)
    return o.reshape(bsz, s, h, p)
