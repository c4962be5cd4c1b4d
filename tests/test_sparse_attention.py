"""The sparse-attention kernels (``ops/sparse_attention.py``) in interpret
mode against the plain reference's gather (``tests/reference_dsa_moe_lm``:
the indexer's scores, ``lax.top_k`` and attention over the selected keys,
float32): the output, dq, dk, dv, the indexer's KL a query (which reads
the head-summed probabilities) and its gradients; and the selection
against ``lax.top_k``, ties included."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_dsa_moe_lm as reference
from horovod_tpu.models.transformer import dense_attention
from horovod_tpu.ops import sparse_attention as sa

SCALE = 0.125  # 64^-1/2 at an indexer head of 64; any number here


@pytest.fixture
def blocks(monkeypatch):
    """Blocks of 256: two query and two key blocks at 512 positions."""
    monkeypatch.setattr(sa, "BLOCK", 256)


def _operands(seed, s=512, h=4, g=2, d=128, hi=4, di=64):
    rng = np.random.default_rng(seed)
    mk = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), jnp.float32).astype(jnp.bfloat16)
    return (mk(1, s, h, d), mk(1, s, g, d), mk(1, s, g, d), mk(1, s, hi, di),
            mk(1, s, di), jnp.asarray(rng.standard_normal((1, s, hi)),
                                      jnp.float32))


def _reference(q, k, v, qi, ki, w, top_k):
    """``(out, kl)`` of one sequence by the reference's functions, float32:
    its own selection of the same scores (no rounding in the way: the
    operands are bfloat16 numbers already)."""
    f32 = lambda x: x[0].astype(jnp.float32)  # noqa: E731
    q, k, v, qi, ki, w = map(f32, (q, k, v, qi, ki, w))
    s, heads = q.shape[0], q.shape[1]
    # the reference divides by sqrt(head_dim); the kernels take the scale
    scores = reference.index_scores(qi, ki, w) * qi.shape[-1] ** 0.5 * SCALE
    selected, _ = reference._own(scores, jnp.arange(s), top_k)
    selected = jax.lax.stop_gradient(selected)
    out, probs = reference.attend(q, k, v, selected)
    p = jax.lax.stop_gradient(probs / heads)
    log_q = jax.nn.log_softmax(jnp.where(selected, scores, -jnp.inf), -1)
    live = selected & (p > 0)
    kl = jnp.sum(jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0))
                                      - jnp.where(live, log_q, 0.0)), 0.0),
                 -1)
    return out, kl, selected


def _loss(fn, weights):
    def f(*x):
        out, kl = fn(*x)[:2]
        return (jnp.sum(out.astype(jnp.float32).reshape(weights.shape)
                        * weights) + 10.0 * jnp.mean(kl))
    return f


def _kernel(q, k, v, qi, ki, w, top_k):
    return sa.sparse_attention(q, k, v, qi, ki, w, top_k=top_k,
                               index_scale=SCALE, kernel=True)


@pytest.mark.parametrize("top_k,h,g", [
    (100, 8, 1), (100, 2, 2), (256, 4, 2), (300, 4, 2), (100, 16, 2),
    (100, 6, 2), (200, 32, 4)],
    ids=["k100_8_to_1", "k100_1_to_1", "k256_one_block", "k300_no_block",
         "k100_8_to_1_two_groups", "k100_3_to_1", "k200_cell_ratio"])
def test_kernels_match_the_reference_gather(blocks, top_k, h, g):
    """Queries before, at and past ``top_k`` (t < k takes the whole causal
    prefix), a k that is a block and one that divides no block, and
    grouped heads at 8 : 1 (one key/value head, and two), 3 : 1, 1 : 1 and
    the cell's 32 over 4: the output, dq, dk, dv, the KL a query and dqI,
    dkI, dw against the reference, and the packed selection is the
    reference's own. A grid step runs a whole group of query heads."""
    x = _operands(top_k + h, h=h, g=g)
    out, kl, words = _kernel(*x, top_k)
    want_out, want_kl, selected = _reference(*x, top_k)
    np.testing.assert_array_equal(np.asarray(sa.unpack(words, 512)[0]),
                                  np.asarray(selected))
    np.testing.assert_allclose(np.asarray(out[0], np.float32),
                               np.asarray(want_out), atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(np.asarray(kl[0]), np.asarray(want_kl),
                               atol=2e-3, rtol=2e-2)
    weights = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)).reshape(
        out.shape)
    got = jax.grad(_loss(lambda *a: _kernel(*a, top_k), weights),
                   argnums=range(6))(*x)
    want = jax.grad(_loss(lambda *a: (
        _reference(*a, top_k)[0][None], _reference(*a, top_k)[1][None]),
        weights), argnums=range(6))(*(a.astype(jnp.float32) for a in x))
    for name, a, b in zip(("dq", "dk", "dv", "dqI", "dkI", "dw"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) / np.linalg.norm(b) < 1e-2, name


def _kl_rule(seq=32768, index_heads=16, index_dim=64):
    """The KL kernels' VMEM count at an indexer's sizes (the cell's by
    default), for ``_heads_per_step``."""
    return functools.partial(sa._kl_step_bytes, seq=seq,
                             index_heads=index_heads, index_dim=index_dim)


@pytest.mark.parametrize("h,g,d,kl", [
    (32, 4, 128, False), (16, 16, 128, False), (6, 2, 128, False),
    (64, 8, 256, False), (32, 4, 128, True), (16, 16, 128, True),
    (6, 2, 128, True), (64, 8, 256, True)],
    ids=["cell", "mha", "group_3", "d256", "kl_cell", "kl_mha",
         "kl_group_3", "kl_d256"])
def test_the_heads_of_a_grid_step_follow_the_shapes(h, g, d, kl):
    """The query heads of a grid step, by the attention kernels' VMEM
    count and by the KL kernels' (``kl``: the cell's indexer, dkI of 32k
    positions resident): all of them where they fit the VMEM budget;
    else as many whole groups (a key/value head's query heads) as fit;
    else the largest divisor of one group that fits; one head a step at
    the least."""
    group = h // g
    rule = _kl_rule() if kl else sa._step_bytes
    step = lambda n: rule(n, group, d, 512, 2)  # noqa: E731
    heads = lambda: sa._heads_per_step(h, group, d, 512, 2, rule)  # noqa: E731
    fits = [n for n in range(1, h + 1) if step(n) <= sa._VMEM_BYTES and (
        group % n == 0 or (n % group == 0 and g % (n // group) == 0))]
    assert heads() == max(fits)
    if (h, d) == (32, 128):
        assert heads() == 32  # the cell: one step, four key/value heads
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sa, "_VMEM_BYTES", step(group))
        assert heads() == group
        if group % 2 == 0:
            mp.setattr(sa, "_VMEM_BYTES", step(2))
            assert heads() == 2
        mp.setattr(sa, "_VMEM_BYTES", 0)
        assert heads() == 1


def test_a_group_split_over_grid_steps_gives_the_same_numbers(blocks,
                                                              monkeypatch):
    """Two groups of 8 run two heads a grid step (a VMEM budget cut to fit
    no more) give the output and dq of both groups in one step bit for
    bit, and dk, dv to float32 rounding (the sum over heads and query
    blocks runs in another order)."""
    x = _operands(11, h=16, g=2)
    weights = jnp.cos(jnp.arange(512 * 16 * 128, dtype=jnp.float32)
                      ).reshape(1, 512, 16, 128)

    def run():
        out = _kernel(*x, 100)[0]
        grads = jax.grad(_loss(lambda *a: _kernel(*a, 100), weights),
                         argnums=range(3))(*x)
        return [np.asarray(a, np.float32) for a in (out, *grads)]

    assert sa._heads_per_step(16, 8, 128, sa.BLOCK, 2) == 16
    whole = run()
    monkeypatch.setattr(sa, "_VMEM_BYTES",
                        sa._step_bytes(2, 8, 128, sa.BLOCK, 2))
    assert sa._heads_per_step(16, 8, 128, sa.BLOCK, 2) == 2
    parts = run()
    for name, a, b in zip(("out", "dq", "dk", "dv"), parts, whole):
        if name in ("out", "dq"):
            np.testing.assert_array_equal(a, b, err_msg=name)
        else:
            assert np.linalg.norm(a - b) / np.linalg.norm(b) < 1e-2, name


def test_the_kl_split_over_grid_steps_gives_the_same_numbers(blocks,
                                                            monkeypatch):
    """The KL's kernels at two groups of 8 heads with a VMEM budget cut to
    fit one and then two heads a grid step (the head blocks innermost,
    their probabilities summed over the steps in one scratch tile) give
    the KL a query, dqI, dkI and dw of all 16 heads in one step bit for
    bit: the heads are summed in the same order, and the mask applied
    once to the sum."""
    x = _operands(13, h=16, g=2)
    rule = _kl_rule(512, 4, 64)

    def run():
        kl = _kernel(*x, 100)[1]
        grads = jax.grad(lambda *a: jnp.sum(
            _kernel(*a, 100)[1] * jnp.cos(jnp.arange(512.0))),
            argnums=(3, 4, 5))(*x)
        return [np.asarray(a, np.float32) for a in (kl, *grads)]

    assert sa._heads_per_step(16, 8, 128, sa.BLOCK, 2, rule) == 16
    whole = run()
    for heads in (1, 2):
        monkeypatch.setattr(sa, "_VMEM_BYTES",
                            rule(heads, 8, 128, sa.BLOCK, 2))
        assert sa._heads_per_step(16, 8, 128, sa.BLOCK, 2, rule) == heads
        for name, a, b in zip(("kl", "dqI", "dkI", "dw"), run(), whole):
            np.testing.assert_array_equal(a, b, err_msg=f"{name} {heads}")


def test_a_top_k_of_every_key_is_causal_attention(blocks):
    """With ``top_k`` at least the sequence every query takes its whole
    causal prefix: the kernels' output is ``dense_attention``'s."""
    q, k, v, qi, ki, w = _operands(7)
    out, _, words = _kernel(q, k, v, qi, ki, w, 1024)
    causal = np.tril(np.ones((512, 512), bool))
    np.testing.assert_array_equal(np.asarray(sa.unpack(words, 512)[0]),
                                  causal)
    pos = jnp.arange(512)[None]
    kk, vv = (jnp.repeat(t, 2, axis=2) for t in (k, v))
    want = dense_attention(q.astype(jnp.float32), kk.astype(jnp.float32),
                           vv.astype(jnp.float32), causal=True,
                           q_positions=pos, kv_positions=pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), atol=2e-2)


@pytest.mark.parametrize("top_k", [5, 64, 200])
def test_the_selection_is_top_k_with_ties_to_the_earlier_key(blocks, top_k):
    """Scores of small whole numbers (exact in bfloat16 and in float32
    sums, so every side computes the same numbers) tie by the hundred: the
    kernel selects what ``lax.top_k`` does, the earlier of equal keys
    first, and ``pack``/``unpack`` are each other's inverse."""
    rng = np.random.default_rng(top_k)
    ints = lambda *shape: jnp.asarray(  # noqa: E731
        rng.integers(-2, 3, shape), jnp.bfloat16)
    s = 512
    qi, ki = ints(1, s, 2, 64), ints(1, s, 64)
    ki = ki.at[:, 1::3].set(ki[:, ::3][:, :ki[:, 1::3].shape[1]])
    w = jnp.asarray(rng.integers(0, 3, (1, s, 2)), jnp.float32)
    q = k = v = jnp.zeros((1, s, 1, 128), jnp.bfloat16)
    words = sa.sparse_attention(q, k, v, qi, ki, w, top_k=top_k,
                                index_scale=1.0, kernel=True)[2]
    scores = sa.index_scores(qi, ki, w, 1.0)[0]
    assert len(np.unique(np.asarray(scores))) < s  # ties, many
    masked = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    _, idx = jax.lax.top_k(masked, top_k)
    want = np.zeros((s, s), bool)
    rows = np.arange(s)[:, None]
    want[rows, np.asarray(idx)] = np.asarray(idx) <= rows
    np.testing.assert_array_equal(np.asarray(sa.unpack(words, s)[0]), want)
    np.testing.assert_array_equal(np.asarray(sa.pack(jnp.asarray(want)[None])),
                                  np.asarray(words))


@pytest.mark.parametrize("s", [160, 768])
def test_a_sequence_off_the_blocks_takes_the_plain_path_and_says_so(s):
    """Asked for the kernels at a sequence that blocks of 512 do not
    divide (768 among them: a whole number of 256-key groups), the plain
    path runs, with a ``FlashFallbackWarning``; its result is the same
    function."""
    from horovod_tpu.ops.flash_attention import FlashFallbackWarning

    x = _operands(3, s=s, h=2, g=1)
    with pytest.warns(FlashFallbackWarning, match="blocks of 512"):
        out, kl, _ = _kernel(*x, 40)
    want_out, want_kl, _ = _reference(*x, 40)
    np.testing.assert_allclose(np.asarray(out[0], np.float32),
                               np.asarray(want_out), atol=2e-2)
    np.testing.assert_allclose(np.asarray(kl[0]), np.asarray(want_kl),
                               atol=2e-3, rtol=2e-2)
