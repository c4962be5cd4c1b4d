"""The main path's kernels compile for the chip, without the chip.

The TPU compiler is installed here and compiles for a device that is
described and not attached (``/opt/skills/guides/on-chip-measurement``
section 2). Interpret mode, which the rest of the CPU suite uses, cannot
see what Mosaic refuses — a slice off the tiling, too much VMEM — so the
flash kernel's forward, forward+backward and the lse-returning variant
with the blockwise backward that ring attention composes are compiled
here with ``interpret=False`` at the head shapes ``chip_smoke.py`` runs,
and at long sequences whose resident dQ (the backward's one
sequence-sized VMEM buffer) nears and passes Mosaic's default scoped
limit.
Nothing executes; a pass is not a chip run.
"""

import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.experimental.compilation_cache import (  # noqa: E402
    compilation_cache)
from jax.sharding import SingleDeviceSharding  # noqa: E402

from horovod_tpu.ops import delta_scan  # noqa: E402
from horovod_tpu.ops import flash_attention as fa  # noqa: E402
from horovod_tpu.ops import ssm_scan  # noqa: E402

# [B, S, H, D] of the d2048 16-head LM and the d768 12-head LM, and two
# long ones for the backward's one sequence-sized VMEM buffer, the
# resident dQ: at s8192 d128 it is 8-12 MiB beside the 512x512 tiles,
# at the edge of the 16 MiB a kernel gets unasked; at 32k a chip
# (ROADMAP R6's longest local sequence) 32-48 MiB, which compiles only
# because ``vmem_limit_bytes`` follows from the shapes
SHAPES = [(8, 2048, 16, 128), (8, 2048, 12, 64), (1, 8192, 16, 128),
          (1, 32768, 2, 128)]


@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip. The persistent compile cache is off
    around these compiles: it would store executables no CPU process
    can read back, and warn on every later run."""
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except (RuntimeError, NotImplementedError) as e:
        pytest.skip(f"no TPU compiler to describe a v5e topology: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _forward(q, k, v):
    return fa.flash_attention(q, k, v, interpret=False)


def _forward_backward(q, k, v):
    return jax.grad(
        lambda q, k, v: jnp.sum(_forward(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)


def _lse_and_blockwise_backward(q, k, v):
    # what parallel/ring.py runs per rotated K/V block: the lse-returning
    # forward, then the fused backward against a supplied (lse, delta)
    out, lse = fa.flash_attention_with_lse(q, k, v, interpret=False)
    delta = jnp.sum(out.astype(jnp.float32) ** 2, axis=-1)
    return fa.flash_attention_bwd_block(q, k, v, out, lse, delta,
                                        interpret=False)


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("fn,kernels", [
    (_forward, 1), (_forward_backward, 2), (_lse_and_blockwise_backward, 2)],
    ids=["forward", "forward_backward", "lse_blockwise_backward"])
def test_flash_kernel_compiles_for_v5e(v5e, shape, fn, kernels):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=v5e)
    text = jax.jit(fn).lower(x, x, x).compile().as_text()
    # one forward kernel and ONE backward kernel: a third call would be
    # the dQ pass come back (each pass rebuilds every block's scores)
    assert text.count("tpu_custom_call") == kernels, (
        f"{fn.__name__} at {shape}: expected {kernels} Mosaic kernel(s) "
        "in the compiled program")


@pytest.mark.parametrize("fn,kernels", [
    (_forward, 1), (_forward_backward, 2), (_lse_and_blockwise_backward, 2)],
    ids=["forward", "forward_backward", "lse_blockwise_backward"])
def test_flash_kernel_compiles_for_v5e_at_two_head_sizes(v5e, fn, kernels):
    """Latent attention's heads at the shape of the
    ``kanana-2-30b-a3b-train-s4096`` cell: q and k 192 wide (not a
    multiple of the 128 lanes), v and o 128 wide, 4 x 4096 x 32 heads."""
    qk = jax.ShapeDtypeStruct((4, 4096, 32, 192), jnp.bfloat16, sharding=v5e)
    v = jax.ShapeDtypeStruct((4, 4096, 32, 128), jnp.bfloat16, sharding=v5e)
    text = jax.jit(fn).lower(qk, qk, v).compile().as_text()
    assert text.count("tpu_custom_call") == kernels


def _index_maps(fn, *args):
    """For every ``pallas_call`` under ``fn``: operand -> the primitives
    of its block index map (empty for a map that only forwards grid
    indices)."""
    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    return [{bm.origin: {e.primitive.name
                         for e in bm.index_map_jaxpr.jaxpr.eqns}
             for bm in eqn.params["grid_mapping"].block_mappings}
            for eqn in calls(jax.make_jaxpr(fn)(*args).jaxpr)]


@pytest.mark.parametrize("shape", [(8, 2048, 12, 64), (4, 4096, 32, 192, 128)],
                         ids=["8x2048x12x64", "4x4096x32x192-128"])
def test_flash_kernel_skipped_steps_fetch_nothing(v5e, shape):
    """With TRACED offsets (what ring attention passes for a rotated
    block) forward, backward and the lse variant compile for the chip,
    and the index maps are the clamped ones: the forward's k and v maps
    take ``min(j, last visible kv block)`` and the backward's q, dO, lse
    and delta maps ``max(i, first visible q block)``, so a skipped grid
    step names the tile already in VMEM and the pipeline copies nothing;
    q, the outputs and the backward's k, v keep their plain maps."""
    qk = jax.ShapeDtypeStruct(shape[:3] + (shape[3],), jnp.bfloat16,
                              sharding=v5e)
    v = jax.ShapeDtypeStruct(shape[:3] + (shape[-1],), jnp.bfloat16,
                             sharding=v5e)
    off = jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e)

    def train(q, k, v, qo, ko):
        return jax.grad(lambda *x: jnp.sum(fa.flash_attention(
            *x, q_offset=qo, kv_offset=ko, interpret=False).astype(
                jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    def ring_block(q, k, v, qo, ko):
        kw = dict(q_offset=qo, kv_offset=ko, interpret=False)
        out, lse = fa.flash_attention_with_lse(q, k, v, **kw)
        delta = jnp.sum(out.astype(jnp.float32) ** 2, axis=-1)
        return fa.flash_attention_bwd_block(q, k, v, out, lse, delta, **kw)

    for fn in (train, ring_block):
        text = jax.jit(fn).lower(qk, qk, v, off, off).compile().as_text()
        assert text.count("tpu_custom_call") == 2
        forward, backward = _index_maps(fn, qk, qk, v, off, off)
        clamped = {name for name, prims in forward.items() if "min" in prims}
        assert clamped == {"args[2]", "args[3]"}, forward       # k, v
        assert not forward["args[1]"] and not forward["outputs[0]"]
        clamped = {name for name, prims in backward.items() if "max" in prims}
        assert clamped == {"args[1]", "args[4]", "args[5]", "args[6]"}, (
            backward)                                           # q, dO, lse, delta
        assert not backward["args[2]"] and not backward["args[3]"]


@pytest.mark.parametrize("shape,window,steps", [
    ((1, 8192, 64, 128), 512, True), ((1, 8192, 64, 128), 512, False),
    ((1, 8192, 48, 128), None, True)],
    ids=["sliding_64_heads", "sliding_traced_offsets", "full_48_heads"])
def test_laguna_attention_kernels_compile_for_v5e(v5e, shape, window, steps):
    """The flash kernels at the two shapes of ``laguna-xs.2-train-s8192``
    compiled for the chip: a sliding layer's 64 heads at 8192 positions
    with a window of 512 (its inner grid dimension read off the
    classification, and with traced offsets its bound), a full layer's 48
    heads without one. One forward and one backward kernel; the windowed
    forward's k and v maps and the backward's q, dO, lse and delta maps
    start at the first block the window reaches (an ``add``) and clamp at
    the last (a ``min``), so a step past it copies nothing."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=v5e)
    off = jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e)

    def train(q, k, v, *offsets):
        kw = dict(zip(("q_offset", "kv_offset"), offsets))
        return jax.grad(lambda *a: jnp.sum(fa.flash_attention(
            *a, window=window, interpret=False, **kw).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    args = (x, x, x) if steps else (x, x, x, off, off)
    text = jax.jit(train).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    forward, backward = _index_maps(train, *args)
    grids = re.findall(r"grid=\(([\d, ]+)\)", str(jax.make_jaxpr(train)(
        *args)))
    if window is None:
        assert grids == ["48, 8, 8", "48, 16, 16"]
        return
    # two blocks of 512 hold a window of 512 beside its diagonal; at an
    # alignment not known here, three
    inner = 2 if steps else 3
    bq, bk = fa.WINDOW_BLOCKS[0]
    assert grids[0] == f"64, {8192 // bq}, {inner}"
    bq, bk = fa.WINDOW_BLOCKS[1]
    assert grids[1] == f"64, {8192 // bk}, {inner}"
    for maps, moved in ((forward, {"args[2]", "args[3]"}),
                        (backward, {"args[1]", "args[4]", "args[5]",
                                    "args[6]"})):
        assert {name for name, prims in maps.items()
                if {"add", "min"} <= prims} == moved, maps


def test_laguna_cell_step_compiles_for_v5e_and_fits(v5e, monkeypatch):
    """The whole train step of ``laguna-xs.2-train-s8192`` as its
    benchmark family builds it (8 layers at the published widths, 1 x 8192
    tokens, AdamW), compiled for one described v5e chip: it fits the
    chip's 15.75 GiB as built (no attention mixer recomputed, nothing
    rematerialised by the compiler: 11.40 GiB, PERF.md section 4), both
    flash kernels and megablox are in it as kernels, nothing fell back,
    every kernel's ``op_name`` still ends ``attn/pallas_call`` (what the
    accepted readers know the flash kernel by), and the three attention
    scopes are on the attention layers' instructions alone, the windowed
    one on the six sliding layers and the full one on the two others.
    The 8 key/value heads reach the kernels as they are (PR 38): every
    flash kernel takes k and v at ``[8, 8192, 128]``, the backward gives
    dK and dV back at that size, nothing under ``hvd_attn`` broadcasts
    them to 48 or 64 query heads or sums them back (13.04 GiB with the
    broadcast), and the set-up record counts the eight grouped calls, at
    groups of 6 and 8, on the build's span."""
    import json
    import warnings

    import horovod_tpu as hvd
    from benchmark.families import swa_moe_lm as family
    from horovod_tpu.telemetry import scopes, startup
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    load = lambda *parts: json.load(open(os.path.join(  # noqa: E731
        repo, "benchmark", *parts)))
    config = load("configs", "laguna-xs.2.json")
    traffic = load("traffic", "b1-s8192.json")
    (device,) = v5e.device_set
    # the model's platform sniffing (auto flash, megablox) sees the chip
    monkeypatch.setattr(jax, "devices", lambda *a: [device])
    hvd.shutdown()
    hvd.init(devices=[device])
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error", fa.FlashFallbackWarning)
            built = family.build(config, traffic, hvd.mesh(), 7)
            replicated = NamedSharding(hvd.mesh(), P())
            state = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=replicated),
                jax.eval_shape(built.init_state))
            tokens = jax.ShapeDtypeStruct(
                (traffic["per_chip_batch"], traffic["seq_len"]), jnp.int32,
                sharding=NamedSharding(hvd.mesh(), P("data")))
            monkeypatch.setattr(startup, "RECORD", startup.Record())
            with startup.span(scopes.LOWER) as built_with:
                lowered = built.step.jitted.lower(state, tokens)
            compiled = lowered.compile()
    finally:
        hvd.shutdown()
    assert built_with == {"grouped_attention_calls": 8,
                          "attention_groups": [6, 8]}
    m = compiled.memory_analysis()
    footprint = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 11.0 < footprint / 2 ** 30 < 12.0  # 11.40 (PERF.md)
    parameters = sum(int(np.prod(a.shape)) for a in
                     jax.tree_util.tree_leaves(state.params))
    assert parameters == config["parameters"] == family.parameters(
        config) == 589_795_072
    text = compiled.as_text()
    assert not re.findall(r"\.remat[.\d]* = ", text)
    # a forward and a backward flash kernel in each of eight layers, and
    # megablox's three a product, two products a layer, forward,
    # recomputed, backward, at both sizes of the share's buffers, in seven
    assert text.count("tpu_custom_call") == 8 * 2 + 7 * 8 * 2
    calls = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)
    flash = [n for n in calls if "hvd_moe_experts" not in n]
    assert len(flash) == 16
    assert all(n.endswith("attn/pallas_call") for n in flash)
    block = lambda n: re.search(r"block_\d", n).group()  # noqa: E731
    under = lambda scope, names: [n for n in names if re.search(  # noqa: E731
        r"(?<![\w.])" + scope + r"(?![\w.])", n)]
    sliding = {"block_1", "block_2", "block_3", "block_5", "block_6",
               "block_7"}
    assert {block(n) for n in under("hvd_attn_window", flash)} == sliding
    assert {block(n) for n in under("hvd_attn_full", flash)} == {
        "block_0", "block_4"}
    assert len(under("hvd_attn_window", flash)) == 12
    assert sum("transpose(jvp(" in n for n in flash) == 8
    names = re.findall(r'op_name="([^"]*)"', text)
    scoped = [n for scope in ("hvd_attn", "hvd_attn_full", "hvd_attn_window")
              for n in under(scope, names)]
    assert scoped and all(re.search(r"block_\d/attn/hvd_attn", n)
                          for n in scoped)
    rest = under("hvd_attn", names)
    for part in ("query", "key", "value", "gate", "out"):
        assert any(f"hvd_attn/{part}/" in n for n in rest), part
    assert not any(n.endswith("pallas_call") for n in rest)
    # k and v at 8 heads into every kernel, dK and dV at 8 out of the
    # backward; no broadcast to the query heads and no sum back under
    # hvd_attn (the parent had 16 and 16 of them there)
    kernel_lines = [line for line in text.splitlines()
                    if 'custom_call_target="tpu_custom_call"' in line
                    and "hvd_moe_experts" not in line]
    for line in kernel_lines:
        operands = line.split("operand_layout_constraints=")[1].split(
            "frontend_attributes")[0]
        assert re.findall(r"bf16\[(\d+),8192,128\]", operands)[1:3] == [
            "8", "8"], operands
    backward = [line for line in kernel_lines if "transpose(jvp(" in line]
    assert len(backward) == 8 and all(re.search(
        r"= \(bf16\[(48|64),8192,128\]\{[^}]*\}, bf16\[8,8192,128\]"
        r"\{[^}]*\}, bf16\[8,8192,128\]", line) for line in backward)
    ops = re.findall(r"= (\S+) \S+\(.*?op_name=\"([^\"]*)\"", text)
    assert not [(shape, name) for shape, name in ops
                if under("hvd_attn", [name]) and re.match(
                    r"bf16\[8192,8,(6,|8,)?128\]", shape)
                and name.endswith(("/broadcast_in_dim", "/reduce_sum"))]


@pytest.mark.parametrize("k,n", [(2048, 1536), (768, 2048)],
                         ids=["gate_up", "down"])
def test_grouped_product_compiles_for_v5e(v5e, k, n):
    """The expert share's grouped products under ``experts._gmm``'s own
    VJP, at the sizes of the ``kanana-2-30b-a3b-train-s4096`` cell (16
    experts held; 98,304 token-slots, of which the buffers in expert order
    hold 24,576): three Mosaic kernels, the product, the input gradient
    (``transpose_rhs``) and the weight gradient (``tgmm``), each with the
    tiling ``experts._tile`` picks."""
    from horovod_tpu.models import experts

    cell = experts.ExpertShareConfig(n_routed_experts=128, experts_held=16)
    rows = experts.held_rows(98304, cell)
    assert rows == 24576

    def forward_backward(xs, w, sizes, g):
        out, vjp = jax.vjp(lambda xs, w: experts._gmm(xs, w, sizes), xs, w)
        return (out,) + vjp(g)

    shaped = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=v5e)
    text = jax.jit(forward_backward).lower(
        shaped(rows, k), shaped(16, k, n), shaped(16, dtype=jnp.int32),
        shaped(rows, n)).compile().as_text()
    assert text.count("tpu_custom_call") == 3


@pytest.mark.parametrize("k,n", [(2688, 1856), (1856, 2688)],
                         ids=["up", "down"])
def test_grouped_product_compiles_for_v5e_off_the_lanes(v5e, k, n):
    """The relu^2 experts' two grouped products at the sizes of the
    ``nemotron-3-nano-30b-a3b-train-s4096`` cell (8 experts held; 49,152
    token-slots, of which the buffers in expert order hold 6,144; an
    expert 1856 wide: 14.5 x 128 lanes, which no tile of
    ``experts._tile`` divides, so the tile is the whole width): three
    Mosaic kernels, and no fall back to ``ragged_dot``."""
    from horovod_tpu.models import experts

    cell = experts.ExpertShareConfig(n_routed_experts=128, experts_held=8)
    rows = experts.held_rows(49152, cell)
    assert rows == 6144

    assert experts._width(1856) == 1856 and experts._width(2688) == 384
    assert experts._width(4096) == 1024 and experts._width(4160) is None

    def forward_backward(xs, w, sizes, g):
        out, vjp = jax.vjp(lambda xs, w: experts._gmm(xs, w, sizes), xs, w)
        return (out,) + vjp(g)

    shaped = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=v5e)
    text = jax.jit(forward_backward).lower(
        shaped(rows, k), shaped(8, k, n), shaped(8, dtype=jnp.int32),
        shaped(rows, n)).compile().as_text()
    assert text.count("tpu_custom_call") == 3


def test_state_space_cell_step_compiles_for_v5e_and_fits(v5e, monkeypatch):
    """The whole train step of ``nemotron-3-nano-30b-a3b-train-s4096`` as
    its benchmark family builds it (9 layers at the published widths, 2 x
    4096 tokens, AdamW), compiled for one described v5e chip: it fits the
    chip's 15.75 GiB with only the expert share recomputed and nothing
    rematerialised by the compiler, the state-space scan's kernels, the
    flash kernel and megablox are in it as kernels, nothing fell back, no
    loop is left under the scan's scope, and the expert share's way out
    past its buffers' bound is a branch of its own whose scope is on
    nothing else. (With the plain scan: nothing recomputed compiled to
    16.32 GiB, the scan recomputed to 13.00, the whole mixer recomputed to
    11.79; with the kernels, which keep the states every chunk inherits,
    13.05: PERF.md section 4.)"""
    import json
    import warnings

    import horovod_tpu as hvd
    from benchmark.families import ssm_moe_lm as family
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    load = lambda *parts: json.load(open(os.path.join(  # noqa: E731
        repo, "benchmark", *parts)))
    config = load("configs", "nemotron-3-nano-30b-a3b.json")
    traffic = load("traffic", "b2-s4096.json")
    (device,) = v5e.device_set
    # the model's platform sniffing (auto flash, megablox) sees the chip
    monkeypatch.setattr(jax, "devices", lambda *a: [device])
    hvd.shutdown()
    hvd.init(devices=[device])
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error", fa.FlashFallbackWarning)
            built = family.build(config, traffic, hvd.mesh(), 7)
            replicated = NamedSharding(hvd.mesh(), P())
            state = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=replicated),
                jax.eval_shape(built.init_state))
            tokens = jax.ShapeDtypeStruct(
                (traffic["per_chip_batch"], traffic["seq_len"]), jnp.int32,
                sharding=NamedSharding(hvd.mesh(), P("data")))
            compiled = built.step.jitted.lower(state, tokens).compile()
    finally:
        hvd.shutdown()
    m = compiled.memory_analysis()
    footprint = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 12.0 < footprint / 2 ** 30 < 15.75
    parameters = sum(int(np.prod(a.shape)) for a in
                     jax.tree_util.tree_leaves(state.params))
    assert parameters == config["parameters"] == 666_963_456
    # one attention layer's forward and backward kernel, megablox's
    # three a product, two products a layer, forward, recomputed, backward,
    # at both sizes of the share's buffers in expert order, and the scan's
    # forward and backward kernel in each of the four state-space layers
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 + 4 * 8 * 2 + 4 * 2
    assert ".remat" not in text
    assert not [line for line in text.splitlines()
                if " while(" in line and "hvd_ssm_scan" in line]
    # one conditional a layer each way, and no instruction outside their
    # second branches, the way out, reads as overflow
    entry = text[text.index("\nENTRY "):]
    assert entry.count(" conditional(") == 2 * 4
    assert text.count("hvd_moe_overflow") > 0
    assert "hvd_moe_overflow" not in entry
    # no buffer of every slot is left in expert order, and the taken
    # branch hands back no zeros for the other's residuals
    assert footprint / 2 ** 30 < 14.1  # 14.091 until PR 32 (PERF.md)


@pytest.mark.parametrize("sizes", [
    (2, 4096, 64, 64, 8, 128, 128), (1, 64, 4, 64, 2, 128, 16),
    (1, 64, 8, 32, 1, 256, 32), (1, 32, 2, 128, 2, 128, 8, jnp.float32)],
    ids=["the_cells", "one_tile_chunks", "four_heads_a_slab",
         "a_slab_a_head"])
def test_ssm_scan_kernels_compile_for_v5e(v5e, sizes):
    """The state-space scan's forward and backward kernels
    (``ops/ssm_scan.py``) compiled for the chip at the sizes of
    ``nemotron-3-nano-30b-a3b-train-s4096`` (2 x 4096 positions, 64 heads
    of 64 in 8 groups of 128 states, chunks of 128, bfloat16), at chunks
    of one bfloat16 tile, at four heads to a slab with states two slabs
    wide, and at a head a slab in float32 with chunks of one tile: what
    interpret mode cannot see (the lane broadcasts, the transposes, the
    kept copies' VMEM). One kernel each way and nothing else of a scan's
    size: the arrays stay ``[B, S, H * P]``."""
    batch, s, heads, p, groups, n, chunk = sizes[:7]
    dtype = sizes[7] if len(sizes) > 7 else jnp.bfloat16
    like = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=v5e)
    args = (like((batch, s, heads, p), dtype),
            like((batch, s, groups, n), dtype),
            like((batch, s, groups, n), dtype), like((batch, s, heads)),
            like((heads,)), like((heads,)))
    assert ssm_scan.supported(chunk, p, heads // groups, n, dtype)
    scan = lambda *x: ssm_scan.ssm_scan(  # noqa: E731
        *x, chunk, interpret=False)
    text = jax.jit(scan).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    text = jax.jit(jax.grad(
        lambda *x: jnp.sum(scan(*x).astype(jnp.float32)),
        argnums=range(6))).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert " while(" not in text
    # the step and its running sum are re-laid by group, 2 MB each at the
    # cell's sizes; u, B, C, o and their gradients are never transposed
    big = batch * s * groups * n
    for transposed in re.findall(r"= \w+\[([\d,]+)\][^=]* transpose\(", text):
        assert np.prod([int(i) for i in transposed.split(",")]) < big


def test_state_space_layer_kernels_carry_the_scan_scope(v5e, monkeypatch):
    """``value_and_grad`` of one ``StateSpaceMixer`` layer at a
    kernel-sized shape, compiled for the chip: the two Mosaic calls are
    the state-space scan's, forward and backward, both carry
    ``hvd_ssm_scan`` in their ``op_name`` (what ``ssm_scan_pct`` and
    ``ssm_scan_roofline_pct`` read, inside ``jvp(...)`` and
    ``transpose(jvp(...))`` alike), and what is left of the scope around
    them is the running sum of the step's log-decay, its re-layouts and
    the sums that finish ``A``'s and ``D``'s gradients: no loop and no
    recomputed forward."""
    from horovod_tpu.models import ssm
    from horovod_tpu.models.transformer import TransformerConfig

    (device,) = v5e.device_set
    monkeypatch.setattr(jax, "devices", lambda *a: [device])
    mixer = ssm.StateSpaceMixer(TransformerConfig(
        d_model=256, norm_eps=1e-5, ssm=ssm.StateSpaceConfig(
            num_heads=4, head_dim=64, n_groups=2, state_size=128,
            chunk_size=64)))
    x = jax.ShapeDtypeStruct((2, 256, 256), jnp.bfloat16, sharding=v5e)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
        jax.eval_shape(lambda: mixer.init(
            jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype))["params"]))
    text = jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(mixer.apply(
        {"params": p}, x).astype(jnp.float32)), argnums=(0, 1))).lower(
            params, x).compile().as_text()
    calls = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)
    assert len(calls) == 2
    forward, backward = sorted(calls, key=lambda n: "transpose(" in n)
    assert forward.endswith(
        "hvd_ssm_scan/jit(_forward)/ssm_scan_forward/pallas_call")
    assert backward.endswith(
        "hvd_ssm_scan/jit(_backward)/ssm_scan_backward/pallas_call")
    assert "jvp(" in forward and "transpose(jvp(" in backward
    scan = {n for n in re.findall(r'op_name="([^"]*)"', text)
            if re.search(r"hvd_ssm_scan(?![\w.])", n)}
    assert not any("while" in n or "checkpoint" in n or "remat" in n
                   for n in scan)


def _delta_arguments(v5e, batch, s, heads, d, dtype=jnp.bfloat16):
    like = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=v5e)
    x = like((batch, s, heads, d), dtype)
    return (x, x, x, like((batch, s, heads, d), jnp.float32),
            like((batch, s, heads), jnp.float32))


@pytest.mark.parametrize("sizes", [(2, 4096, 32, 128, 64), (1, 256, 2, 256, 16),
                                   (1, 64, 1, 128, 8, jnp.float32)],
                         ids=["the_cells", "two_slabs_a_head", "one_block"])
def test_delta_scan_kernels_compile_for_v5e(v5e, sizes):
    """The delta scan's forward and backward kernels (``ops/delta_scan.py``)
    compiled for the chip at the sizes of
    ``kimi-linear-48b-a3b-train-s4096`` (2 x 4096 positions, 32 heads of
    128, chunks of 64, bfloat16), at heads two lane slabs wide with chunks
    of one bfloat16 tile, and at a chunk of one diagonal block in float32:
    what interpret mode cannot see (the sublane rolls, the aligned
    concatenations, the float32 products at the highest precision, the
    kept copies' VMEM). One kernel each way and nothing else of a scan's
    size: the arrays stay ``[B, S, H * D]``."""
    *shape, chunk = sizes[:5]
    args = _delta_arguments(v5e, *shape, *sizes[5:])
    scan = lambda *x: delta_scan.delta_scan(  # noqa: E731
        *x, chunk, interpret=False)
    text = jax.jit(scan).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    text = jax.jit(jax.grad(
        lambda *x: jnp.sum(scan(*x).astype(jnp.float32)),
        argnums=range(5))).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    # beta's rows go in and d beta's come out re-laid, 1 MB at the cell's
    # sizes; q, k, v, g, o and their gradients are never transposed
    big = shape[0] * shape[1] * shape[2] * shape[3]
    for transposed in re.findall(r"= \w+\[([\d,]+)\][^=]* transpose\(", text):
        assert np.prod([int(n) for n in transposed.split(",")]) < big


def test_delta_layer_kernels_carry_the_scan_scope(v5e, monkeypatch):
    """``value_and_grad`` of one ``DeltaAttention`` layer at a kernel-sized
    shape, compiled for the chip: the two Mosaic calls are the delta
    scan's, forward and backward, both carry ``hvd_kda_scan`` in their
    ``op_name`` (what ``kda_scan_pct`` and ``kda_scan_roofline_pct`` read,
    inside ``jvp(...)`` and ``transpose(jvp(...))`` alike), and neither is
    an ``attn/pallas_call`` (what ``mla_flash_pct`` and
    ``mla_flash_roofline_pct`` read in the same cell). What is left of the
    scope around them is beta's re-layout and reshapes."""
    from horovod_tpu.models import kda
    from horovod_tpu.models.transformer import TransformerConfig

    (device,) = v5e.device_set
    monkeypatch.setattr(jax, "devices", lambda *a: [device])
    mixer = kda.DeltaAttention(TransformerConfig(
        d_model=256, norm_eps=1e-5, kda=kda.DeltaAttentionConfig(
            num_heads=2, head_dim=128, chunk_size=64, gate_rank=32)))
    x = jax.ShapeDtypeStruct((2, 256, 256), jnp.bfloat16, sharding=v5e)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
        jax.eval_shape(lambda: mixer.init(
            jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype))["params"]))
    text = jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(mixer.apply(
        {"params": p}, x).astype(jnp.float32)), argnums=(0, 1))).lower(
            params, x).compile().as_text()
    calls = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)
    assert len(calls) == 2
    forward, backward = sorted(calls, key=lambda n: "transpose(" in n)
    assert forward.endswith(
        "hvd_kda_scan/jit(_forward)/delta_scan_forward/pallas_call")
    assert backward.endswith(
        "hvd_kda_scan/jit(_backward)/delta_scan_backward/pallas_call")
    assert "jvp(" in forward and "transpose(jvp(" in backward
    assert not any("attn/pallas_call" in n for n in calls)
    scan = {n for n in re.findall(r'op_name="([^"]*)"', text)
            if re.search(r"hvd_kda_scan(?![\w.])", n)}
    assert {n.rsplit("/", 1)[-1] for n in scan - set(calls)} <= {
        "reshape", "transpose", "convert_element_type", "jit(_forward)",
        "jit(_backward)"}


def test_delta_attention_cell_step_compiles_for_v5e_and_fits(v5e, monkeypatch):
    """The whole train step of ``kimi-linear-48b-a3b-train-s4096`` as its
    benchmark family builds it (5 layers at the published widths, 2 x 4096
    tokens, AdamW), compiled for one described v5e chip: it fits the
    chip's 15.75 GiB with the element-wise chains around the delta scan
    and the expert share recomputed, nothing rematerialised by the
    compiler, the delta scan's kernels, the flash kernel and megablox are
    in it as kernels, nothing fell back, and the two delta scopes are on
    the delta layers' instructions alone. (With the plain scan over all 32
    heads at once and the element-wise chains kept it compiled to 15.55
    GiB with 76 ``.remat`` instructions; with the plain scan four heads at
    a time to 13.22 with none; with the kernels, which keep the states
    every chunk inherits, to 13.74 with none: PERF.md section 4.)"""
    import json
    import re
    import warnings

    import horovod_tpu as hvd
    from benchmark.families import kda_moe_lm as family
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    load = lambda *parts: json.load(open(os.path.join(  # noqa: E731
        repo, "benchmark", *parts)))
    config = load("configs", "kimi-linear-48b-a3b.json")
    traffic = load("traffic", "b2-s4096.json")
    (device,) = v5e.device_set
    # the model's platform sniffing (auto flash, megablox) sees the chip
    monkeypatch.setattr(jax, "devices", lambda *a: [device])
    hvd.shutdown()
    hvd.init(devices=[device])
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error", fa.FlashFallbackWarning)
            built = family.build(config, traffic, hvd.mesh(), 7)
            replicated = NamedSharding(hvd.mesh(), P())
            state = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=replicated),
                jax.eval_shape(built.init_state))
            tokens = jax.ShapeDtypeStruct(
                (traffic["per_chip_batch"], traffic["seq_len"]), jnp.int32,
                sharding=NamedSharding(hvd.mesh(), P("data")))
            compiled = built.step.jitted.lower(state, tokens).compile()
    finally:
        hvd.shutdown()
    m = compiled.memory_analysis()
    footprint = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 12.0 < footprint / 2 ** 30 < 14.0  # 13.74 (PERF.md)
    parameters = sum(int(np.prod(a.shape)) for a in
                     jax.tree_util.tree_leaves(state.params))
    assert parameters == config["parameters"] == 602_450_816
    text = compiled.as_text()
    assert not re.findall(r"\.remat[.\d]* = ", text)
    # one latent-attention layer's forward and backward kernel, the delta
    # scan's two in each of four layers, and megablox's three a product,
    # two products a layer, forward, recomputed, backward, at both sizes
    # of the share's buffers
    assert text.count("tpu_custom_call") == 2 + 4 * 2 + 4 * 8 * 2
    # the scopes: on the delta layers (blocks 0, 1, 2 and 4), not on the
    # latent-attention layer (block 3) or anything outside a mixer
    names = re.findall(r'op_name="([^"]*)"', text)
    scan = [n for n in names if re.search(r"hvd_kda_scan(?![\w.])", n)]
    rest = [n for n in names if re.search(r"hvd_kda(?![\w.])", n)]
    assert scan and rest
    assert all("/mixer/" in n and "block_3" not in n for n in scan + rest)
    assert {re.search(r"block_\d", n).group() for n in scan} == {
        "block_0", "block_1", "block_2", "block_4"}
    assert any("block_3/attn/hvd_mla" in n for n in names)
    # the scan's kernels are under its scope, forward and backward, and
    # no loop over chunks is left in plain XLA; the projections under the
    # mixer's
    assert sum(n.endswith("delta_scan_forward/pallas_call")
               for n in set(scan)) == 4
    assert sum(n.endswith("delta_scan_backward/pallas_call")
               and "transpose(jvp(" in n for n in set(scan)) == 4
    assert not any("/while/" in n for n in scan)
    assert any("q_proj" in n for n in rest)
    assert not any("_proj" in n for n in scan)


def _vmem_bytes(shape, dtype):
    """Bytes of a VMEM buffer of ``shape``: its last two dimensions padded
    to whole (8 x 32-bit sublanes, 128 lanes) tiles."""
    itemsize = np.dtype(dtype).itemsize
    sub = 8 * 4 // itemsize
    *lead, rows, cols = shape
    return (int(np.prod(lead)) * -(-rows // sub) * sub * -(-cols // 128)
            * 128 * itemsize)


def test_sparse_attention_kernels_compile_for_v5e(v5e, monkeypatch):
    """The sparse attention's kernels (``ops/sparse_attention.py``: the
    selection, the attention forward, the KL's forward, dQ, dK and dV, the
    KL's backward) compiled for the chip at the cell's sizes, 1 x 32768
    positions, 32 query heads over 4 key/value heads of 128, an indexer of
    16 heads of 64 and 2048 keys a query: what interpret mode cannot see
    (the sequence-sized VMEM of the selection's keys and of dkI, the
    bit-packed masks, the lane-sliced head blocks). The attention kernels
    and the KL's run all 32 query heads (the 4 key/value heads' groups)
    a grid step, so their grids have one head block where 32 query heads
    stand, and each call's blocks (double-buffered) and scratch stay
    within the VMEM it asks for."""
    from horovod_tpu.ops import sparse_attention as sa

    calls = []
    pallas_call = sa.pl.pallas_call

    def recorded(kernel, *, grid_spec, out_shape, compiler_params, **kw):
        call = pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape,
                           compiler_params=compiler_params, **kw)

        def run(*args):
            many = lambda x: x if isinstance(x, tuple) else (x,)  # noqa: E731
            blocks = sum(2 * _vmem_bytes(spec.block_shape, a.dtype)
                         for spec, a in zip(
                             [*grid_spec.in_specs, *many(grid_spec.out_specs)],
                             [*args, *many(out_shape)]))
            scratch = sum(_vmem_bytes(m.shape, m.dtype)
                          for m in grid_spec.scratch_shapes)
            calls.append((kernel.func.__name__, grid_spec.grid,
                          blocks + scratch, compiler_params.vmem_limit_bytes))
            return call(*args)
        return run

    monkeypatch.setattr(sa.pl, "pallas_call", recorded)
    x = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=v5e)
    s = 32768
    args = (x(1, s, 32, 128), x(1, s, 4, 128), x(1, s, 4, 128),
            x(1, s, 16, 64), x(1, s, 64), x(1, s, 16, dtype=jnp.float32))

    def train(*a):
        def loss(*a):
            out, kl, _ = sa.sparse_attention(*a, top_k=2048,
                                             index_scale=0.125, kernel=True,
                                             interpret=False)
            return jnp.sum(out.astype(jnp.float32)) + jnp.mean(kl)
        return jax.value_and_grad(loss, argnums=range(6))(*a)

    text = jax.jit(train).lower(*args).compile().as_text()
    heads = sa._heads_per_step(32, 8, 128, sa.BLOCK, 2)
    assert heads == 32
    grids = {name: grid for name, grid, _, _ in calls}
    assert grids["_fwd_kernel"] == (1, 32 // heads, 64, 64) == (1, 1, 64, 64)
    assert grids["_dq_kernel"] == (1, 1, 64, 64)
    assert grids["_dkv_kernel"] == (1, 4 // (heads // 8), 64, 1, 64)
    assert sa._heads_per_step(32, 8, 128, sa.BLOCK, 2, functools.partial(
        sa._kl_step_bytes, seq=s, index_heads=16, index_dim=64)) == 32
    assert grids["_kl_fwd_kernel"] == (1, 64, 64, 1)
    assert grids["_kl_bwd_kernel"] == (1, 64, 64, 1)
    assert len(calls) == 6
    for name, _, need, limit in calls:
        assert limit is not None and need <= limit, (name, need, limit)
    names = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)
    scope = lambda n: re.search(  # noqa: E731
        r"hvd_dsa_topk|hvd_dsa_index|hvd_attn_sparse", n).group()
    assert sorted(map(scope, names)) == sorted(
        ["hvd_dsa_topk", "hvd_attn_sparse", "hvd_dsa_index",
         "hvd_attn_sparse", "hvd_attn_sparse", "hvd_dsa_index"])


def test_keye_cell_step_compiles_for_v5e_and_fits(v5e, monkeypatch):
    """The whole train step of ``keye-vl-2.0-30b-a3b-train-s32768`` as its
    benchmark family builds it (5 layers at the published widths, each
    attention layer recomputed in the backward pass keeping the selection,
    the expert share's buffers at a held room of 4, 1 x 32768 tokens,
    AdamW), compiled for one described v5e chip: it fits the chip's 15.75
    GiB (14.49 GiB, PERF.md section 4), the sparse
    kernels and megablox are in it as kernels, nothing fell back, the
    selection runs once a layer and the attention's forward kernel twice
    (the recomputation), and each new scope is on the indexed layers'
    kernels alone."""
    import json
    import warnings

    import horovod_tpu as hvd
    from benchmark.families import dsa_moe_lm as family
    from horovod_tpu.models.experts import GroupedFallbackWarning
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    load = lambda *parts: json.load(open(os.path.join(  # noqa: E731
        repo, "benchmark", *parts)))
    config = load("configs", "keye-vl-2.0-30b-a3b.json")
    traffic = load("traffic", "b1-s32768.json")
    (device,) = v5e.device_set
    monkeypatch.setattr(jax, "devices", lambda *a: [device])
    hvd.shutdown()
    hvd.init(devices=[device])
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error", fa.FlashFallbackWarning)
            warnings.simplefilter("error", GroupedFallbackWarning)
            built = family.build(config, traffic, hvd.mesh(), 7)
            replicated = NamedSharding(hvd.mesh(), P())
            state = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=replicated),
                jax.eval_shape(built.init_state))
            tokens = jax.ShapeDtypeStruct(
                (traffic["per_chip_batch"], traffic["seq_len"]), jnp.int32,
                sharding=NamedSharding(hvd.mesh(), P("data")))
            compiled = built.step.jitted.lower(state, tokens).compile()
    finally:
        hvd.shutdown()
    m = compiled.memory_analysis()
    footprint = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 13.5 < footprint / 2 ** 30 < 15.0  # 14.49 (PERF.md)
    parameters = sum(int(np.prod(a.shape)) for a in
                     jax.tree_util.tree_leaves(state.params))
    assert parameters == config["parameters"] == 562_290_560
    text = compiled.as_text()
    calls = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)
    experts = [n for n in calls if "hvd_moe_experts" in n]
    sparse = [n for n in calls if "hvd_moe_experts" not in n]
    # megablox: three a product, two products a layer, forward,
    # recomputed, backward, at both sizes of the share's buffers
    assert len(experts) == 5 * 16
    scope = lambda n: re.search(  # noqa: E731
        r"hvd_dsa_topk|hvd_dsa_index|hvd_attn_sparse", n).group()
    count = {name: sum(scope(n) == name for n in sparse)
             for name in ("hvd_dsa_topk", "hvd_attn_sparse", "hvd_dsa_index")}
    # a layer: the selection once; the attention forward, again in the
    # recomputation, dQ and dK/dV; the KL forward and backward
    assert count == {"hvd_dsa_topk": 5, "hvd_attn_sparse": 20,
                     "hvd_dsa_index": 10}
    assert {re.search(r"block_\d", n).group() for n in sparse} == {
        f"block_{i}" for i in range(5)}
    assert all(n.endswith("pallas_call") for n in sparse)
    assert sum("rematted_computation" in n for n in sparse) == 5


def test_chip_smoke_refuses_to_run_without_a_chip():
    """chip_smoke.py on a machine whose jax finds no TPU runs no phase,
    prints no result and exits non-zero naming the platform it found —
    the refusal is what keeps a later run from passing without the
    chip."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    rv = subprocess.run([sys.executable, "chip_smoke.py"], cwd=repo, env=env,
                        capture_output=True, text=True, timeout=120)
    assert rv.returncode != 0
    assert rv.stdout == ""
    assert "found platform 'cpu'" in rv.stderr
    assert "no phase was run" in rv.stderr


def test_lm_head_and_loss_keep_no_float32_logits(v5e):
    """The head and loss of the ``lm-d768-*`` cells, forward and
    backward, as the model writes them (a bfloat16 product read as
    float32): the only array over the vocabulary that the compiled
    program holds in memory is the bfloat16 one the head wrote. No
    float32 copy for the loss to gather from, none kept for the
    backward pass, and no cut or padded copy for the dropped last
    position (until PR 30: 3.3 GB written and read back every step)."""
    from horovod_tpu.training import _next_token_ll

    b, s, d, v = 8, 2048, 768, 50304

    def loss(w, h, tokens):
        logits = jnp.dot(h, w.astype(jnp.bfloat16)).astype(jnp.float32)
        return -jnp.mean(_next_token_ll(logits, tokens[:, 1:]))

    w = jax.ShapeDtypeStruct((d, v), jnp.float32, sharding=v5e)
    h = jax.ShapeDtypeStruct((b, s, d), jnp.bfloat16, sharding=v5e)
    tokens = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=v5e)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        w, h, tokens).compile().as_text()
    # the entry computation's instructions are the program's buffers;
    # what a fusion computes inside itself never reaches memory
    entry = text[text.index("\nENTRY"):]
    over_vocabulary = set(re.findall(rf"\w+\[{b},\d+,{v}\]", entry))
    assert over_vocabulary == {f"bf16[{b},{s},{v}]"}, over_vocabulary
