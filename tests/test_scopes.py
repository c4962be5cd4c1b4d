"""The names a step carries from inside (``horovod_tpu/telemetry/scopes.py``):
device scopes in the compiled text, host spans in the profiler's own trace.

Every compile here runs with the persistent compile cache off. jax strips
debug locations from the cache key (``jax_compilation_cache_include_
metadata_in_key`` is off), and a ``named_scope`` lives only there: with the
cache on, a scoped program hits the entry of the same program without
scopes and ``as_text()`` shows that one's ``op_name``s.
"""

import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.experimental.compilation_cache import compilation_cache

import horovod_tpu as hvd_api
from horovod_tpu import training
from horovod_tpu.models.transformer import Transformer, TransformerConfig
from horovod_tpu.telemetry import scopes

WORLD = 4


@pytest.fixture(autouse=True, scope="module")
def fresh_compiles():
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _mesh():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:WORLD]), ("data",))


def _lm(threshold_bytes=None, **step_kwargs):
    """``(step, state, (tokens,))`` of a two-layer LM on the data mesh."""
    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                            d_model=16, d_ff=32, dtype=jnp.float32,
                            sequence_axis=None)
    model = Transformer(cfg)
    tx = hvd_api.DistributedOptimizer(optax.adamw(1e-3), axes=("data",),
                                      threshold_bytes=threshold_bytes)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 64, size=(WORLD * 2, 8)), jnp.int32)
    state = training.create_train_state(model, tx, jax.random.PRNGKey(0),
                                        tokens[:1])
    step = training.make_lm_train_step(model, tx, mesh=_mesh(),
                                       batch_axis="data", donate=False,
                                       **step_kwargs)
    return step, state, (tokens,)


def _classifier(**step_kwargs):
    """``(step, state, (inputs, labels))`` of a small MLP through
    ``make_train_step`` with SGD momentum."""
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(10)(nn.relu(nn.Dense(32)(x)))

    model = MLP()
    tx = hvd_api.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                      axes=("data",))
    rng = np.random.default_rng(0)
    inputs = jnp.asarray(rng.normal(size=(WORLD * 2, 12)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, size=(WORLD * 2,)), jnp.int32)
    state = training.create_train_state(model, tx, jax.random.PRNGKey(0),
                                        inputs[:1])
    step = training.make_train_step(model, tx, mesh=_mesh(), donate=False,
                                    **step_kwargs)
    return step, state, (inputs, labels)


BUILDERS = {"lm": _lm, "classifier": _classifier}
# The annotation-only GSPMD programs hold no explicit collective under
# ``hvd_exchange``, so the device-scope tests stay on BUILDERS; what
# surrounds the dispatch on the host is one scaffold for all four.
HOST_BUILDERS = {**BUILDERS,
                 "lm-spmd": lambda: _lm(spmd=True),
                 "classifier-spmd": lambda: _classifier(spmd=True)}

_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = .*?\s([a-z][a-z\-]*)\(")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def _compiled_text(build):
    step, state, batch = build()
    return step.lower(state, *batch).compile().as_text()


def _op_names(text, opcode=None):
    """The ``op_name`` of every instruction of the module (of ``opcode``
    alone if given); fused computations included."""
    out = []
    for line in text.splitlines():
        m, name = _INSTR_RE.match(line), _OP_NAME_RE.search(line)
        if m and name and opcode in (None, m.group(1)):
            out.append(name.group(1))
    return out


@pytest.fixture(scope="module")
def texts():
    """Each builder's optimised HLO, compiled once for the module."""
    hvd_api.shutdown()
    hvd_api.init()
    try:
        yield {name: _compiled_text(build)
               for name, build in HOST_BUILDERS.items()}
    finally:
        hvd_api.shutdown()


@pytest.mark.parametrize("builder", sorted(BUILDERS))
def test_exchange_scope_on_collective_pack_and_unpack(texts, builder):
    text = texts[builder]
    reduces = [n for n in _op_names(text, "all-reduce")
               if scopes.EXCHANGE in n]
    assert reduces, "no all-reduce of the module is under hvd_exchange"
    assert all(f"{scopes.EXCHANGE}/bucket0" in n for n in reduces), reduces
    under = [n for n in _op_names(text) if scopes.EXCHANGE in n]
    # pack: the leaves flattened and concatenated; unpack: sliced and
    # reshaped back (the compiler fuses them, a fusion keeps one name)
    assert any(n.endswith(("/concatenate", "/ravel", "/reshape"))
               for n in under), under
    assert any(n.endswith(("/slice", "/reshape", "/dynamic_slice"))
               and "bucket0" in n for n in under), under
    assert any("/psum" in n for n in under), under


@pytest.mark.parametrize("builder", sorted(BUILDERS))
def test_optimizer_scope_on_the_update_arithmetic(texts, builder):
    names = [n for n in _op_names(texts[builder])
             if scopes.OPTIMIZER in n]
    assert names, "nothing of the module is under hvd_optimizer"
    # Adam's second moment / SGD's momentum trace, and the parameter write
    assert any(n.endswith(("/mul", "/add", "/sqrt", "/div", "/integer_pow"))
               for n in names), names
    # the exchange is not the optimizer's: the chain runs it before
    assert not any(scopes.EXCHANGE in n for n in names), names


@pytest.mark.parametrize("builder", sorted(BUILDERS))
def test_loss_scope_forward_and_backward(texts, builder):
    names = _op_names(texts[builder])
    forward = [n for n in names if f"jvp({scopes.LOSS})" in n
               and "transpose(" not in n]
    backward = [n for n in names
                if f"transpose(jvp({scopes.LOSS}))" in n]
    assert forward and backward, (forward, backward)
    if builder == "lm":
        # ``_next_token_ll``'s two passes: the row's maximum and its
        # log-sum-exp forward; backward the probabilities rebuilt from
        # the logits (a custom_vjp's backward carries the scope it was
        # written under), and no log_softmax for autodiff to keep from
        for op in ("reduce_max", "exp", "log"):
            assert any(n.endswith(f"/{op}") for n in forward), (op, forward)
        assert any(n.endswith("/exp") for n in backward), backward
        assert not any("log_softmax" in n for n in names), names
    else:
        assert any("log_softmax" in n or "reduce_max" in n or "exp" in n
                   for n in forward), forward


@pytest.mark.parametrize("builder", sorted(BUILDERS))
def test_scopes_change_no_program(monkeypatch, builder):
    """Without its ``metadata={...}`` the optimised HLO is the same,
    instruction for instruction, with every device scope replaced by a
    null context. (Until PR 29 ``strip`` cut the text at the tables,
    which follow the header line: it compared two header lines.)"""
    def strip(text):
        # every instruction's metadata, and the tables of files, functions
        # and stack frames it points into (between the module's header
        # line and its first computation)
        out, tables = [], False
        for line in text.split("\n"):
            if line == "FileNames":
                tables = True
            elif tables and line.startswith(("%", "ENTRY")):
                tables = False
            if not tables:
                out.append(line)
        assert len(out) > 10, "the module's computations were cut away"
        bare = re.sub(r",? ?metadata=\{[^}]*\}", "", "\n".join(out))
        # the compiler names some instructions after their op_name
        # (%jvp_jit_take_along_axis__.22): number the names by first use
        seen = {}
        return re.sub(r"%[\w.\-]+",
                      lambda m: seen.setdefault(m.group(0), f"%n{len(seen)}"),
                      bare)

    scoped = _compiled_text(BUILDERS[builder])
    monkeypatch.setattr(scopes, "device",
                        lambda name: contextlib.nullcontext())
    bare = _compiled_text(BUILDERS[builder])
    names = re.compile(r"hvd_(exchange|optimizer|loss)")
    assert names.search(scoped) and not names.search(strip(scoped))
    assert not names.search(bare)
    assert strip(scoped) == strip(bare)


def test_buckets_are_numbered_under_the_exchange(hvd):
    """A threshold that splits the gradients: each bucket's packing and
    unpacking carries its own index under ``hvd_exchange`` (the CPU
    compiler combines the buckets' all-reduces into one instruction, which
    keeps one name)."""
    text = _compiled_text(lambda: _lm(threshold_bytes=4096))
    found = {m for n in _op_names(text)
             for m in re.findall(r"hvd_exchange/(bucket\d+)/", n)}
    assert {"bucket0", "bucket1", "bucket2"} <= found, found


@pytest.mark.parametrize("inner", [optax.adamw(1e-3),
                                   optax.sgd(0.1, momentum=0.9)],
                         ids=["adamw", "sgd_momentum"])
def test_optimizer_state_tree_is_the_chains(hvd, inner):
    """The ``hvd_optimizer`` wrapper adds no level and no leaf: the state
    is ``(EmptyState(), inner.init(params))``, so a checkpoint written
    before the wrapper restores."""
    params = {"w": jnp.ones((3, 2)), "b": jnp.zeros((2,))}
    tx = hvd_api.DistributedOptimizer(inner)
    got = jax.tree_util.tree_structure(tx.init(params))
    want = jax.tree_util.tree_structure(
        (optax.EmptyState(), inner.init(params)))
    assert got == want


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("hvd_"):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.mark.parametrize("builder", sorted(HOST_BUILDERS))
def test_host_spans_in_the_profilers_trace(hvd, tmp_path, builder):
    """Two steps under ``jax.profiler``: two ``hvd_step`` spans numbered 0
    and 1, each holding one ``hvd_place`` and one ``hvd_launch``."""
    step, state, batch = HOST_BUILDERS[builder]()
    step.lower(state, *batch).compile()  # tracing is not what is timed
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(2):
            state, loss = step(state, *batch)
        jax.block_until_ready(loss)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    steps = sorted((e for e in events if e[0] == scopes.STEP),
                   key=lambda e: e[1])
    assert [e[3].get("step_num") for e in steps] == [0, 1]
    for _, start, end, _ in steps:
        inside = sorted(name for name, s, e, _ in events
                        if name != scopes.STEP and start <= s and e <= end)
        assert inside == [scopes.LAUNCH, scopes.PLACE]


# what each kind of step exposes beside ``__call__``; nothing of another
# kind's list may appear on it
_ALL_STEPS = {"jitted", "lower", "_settles_ledger"}
_CLASSIFICATION = {"loader", "place_data", "reset_error_feedback"}
_GSPMD = {"plan", "spmd", "compiled_collectives",
          "compiled_axis_collectives", "xray"}
STEP_ATTRIBUTES = {
    "lm": _ALL_STEPS,
    "classifier": _ALL_STEPS | _CLASSIFICATION,
    "lm-spmd": _ALL_STEPS | _GSPMD,
    "classifier-spmd": _ALL_STEPS | _CLASSIFICATION | _GSPMD,
}


@pytest.mark.parametrize("builder", sorted(HOST_BUILDERS))
def test_host_contract_of_a_step_call(hvd, monkeypatch, builder):
    """Each call gives the flight recorder one ``step_begin(n)`` /
    ``step_end(n)`` pair, ``n`` counting from 0, and settles the goodput
    ledger exactly once (the GSPMD builds first note the compiled path);
    the step carries the attributes of its kind and no other's."""
    from horovod_tpu.diag import recorder
    from horovod_tpu.telemetry import ledger

    events = []

    class Ledger:
        def note_compiled_path(self):
            events.append(("compiled_path",))

        def settle_step(self):
            events.append(("settle",))

    monkeypatch.setattr(recorder, "step_begin",
                        lambda n: events.append(("begin", n)))
    monkeypatch.setattr(recorder, "step_end",
                        lambda n: events.append(("end", n)))
    monkeypatch.setattr(ledger, "get_ledger", Ledger)

    step, state, batch = HOST_BUILDERS[builder]()
    spmd = builder.endswith("-spmd")
    optional = _CLASSIFICATION | _GSPMD | {"instruments"}
    assert {a for a in optional | _ALL_STEPS if hasattr(step, a)} \
        == STEP_ATTRIBUTES[builder]
    assert step._settles_ledger is True
    if spmd:
        assert step.spmd is True and step.jitted is None
        assert step.compiled_collectives is None
    for _ in range(2):
        state, loss = step(state, *batch)
    assert np.isfinite(float(loss))
    per_call = ([("compiled_path",)] if spmd else []) + [("settle",)]
    assert events == [("begin", 0), ("end", 0)] + per_call \
        + [("begin", 1), ("end", 1)] + per_call
    # the GSPMD steps read these through to the program, built by now
    assert step.jitted is not None
    if spmd:
        assert step.compiled_collectives is not None
        assert step.compiled_axis_collectives is not None
    lowered = step.lower(state, *batch)
    assert lowered.compile().as_text().startswith("HloModule jit_")


@pytest.mark.parametrize("builder,module", [
    ("lm", "jit_hvd_lm_train_step"), ("classifier", "jit_hvd_train_step"),
    ("lm-spmd", "jit_global_step"), ("classifier-spmd", "jit_global_step")])
def test_jitted_step_is_named_for_what_it_is(texts, builder, module):
    # The module name is part of the persistent compile cache's key; the
    # scopes are not (they are debug metadata, which the key leaves out).
    # A machine whose cache holds the program of a commit without scopes
    # would hand that executable, and its op_names, to the scoped program
    # if both were jit_outer: every phase metric would read nothing.
    assert texts[builder].startswith(f"HloModule {module},")


def _computations(text):
    """``{computation: [its instruction lines]}`` of a compiled module."""
    comps, current = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", line)
        if head:
            current = head.group(1)
            comps[current] = []
        elif current and _INSTR_RE.match(line):
            comps[current].append(line)
    return comps


def _op_names_reached(comps, name, seen=None):
    """The ``op_name``s of ``name``'s instructions and of every
    computation they call (fusions, nested control flow; not a reduction's
    scalar ``to_apply``, which runs as part of its caller and is shared
    between callers)."""
    seen = set() if seen is None else seen
    if name in seen or name not in comps:
        return []
    seen.add(name)
    out = []
    for line in comps[name]:
        out += _OP_NAME_RE.findall(line)
        for called in re.findall(
                r"(?:calls|body|condition)=%?([\w.\-]+)", line):
            out += _op_names_reached(comps, called, seen)
        for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
            for called in group.split(","):
                out += _op_names_reached(comps, called.strip().lstrip("%"),
                                         seen)
    return out


def test_overflow_scope_is_on_the_way_out_alone():
    """An expert share whose buffers hold fewer rows than there are slots
    compiles to one conditional each way. ``hvd_moe_route`` and
    ``hvd_moe_experts`` are on the instructions of both branches;
    ``hvd_moe_overflow`` is around them in the second branch, the way out
    past the buffers' bound, and on nothing else in the program: device
    time under it is time spent past the bound."""
    from horovod_tpu.models import experts as experts_lib

    share = experts_lib.ExpertShareConfig(
        n_routed_experts=16, experts_held=2, expert_offset=4,
        num_experts_per_tok=3, moe_d_ff=16)
    y = jnp.zeros((384, 32), jnp.float32)
    assert experts_lib.held_rows(384 * 3, share) == 512
    module = experts_lib.ExpertShare(share, dtype=jnp.float32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0), y)["params"]
    text = jax.jit(jax.value_and_grad(
        lambda y, p: jnp.sum(module.apply({"params": p}, y)),
        argnums=(0, 1))).lower(y, params).compile().as_text()
    comps = _computations(text)
    conditionals = [line for lines in comps.values() for line in lines
                    if _INSTR_RE.match(line).group(1) == "conditional"]
    assert len(conditionals) == 2
    has = lambda names, scope: [n for n in names if scope in n]  # noqa: E731
    inside = set()
    for line in conditionals:
        branches = [b.strip().lstrip("%") for b in re.search(
            r"branch_computations=\{([^}]*)\}", line).group(1).split(",")]
        bounded, full = (_op_names_reached(comps, b, inside)
                         for b in branches)
        for names in (bounded, full):
            assert has(names, scopes.MOE_ROUTE)
            assert has(names, scopes.MOE_EXPERTS)
        assert not has(bounded, scopes.MOE_OVERFLOW)
        scoped = has(full, scopes.MOE_ROUTE) + has(full, scopes.MOE_EXPERTS)
        assert scoped and all(scopes.MOE_OVERFLOW in n for n in scoped)
        assert all(n.index(scopes.MOE_OVERFLOW) < n.index(scope)
                   for n in scoped
                   for scope in (scopes.MOE_ROUTE, scopes.MOE_EXPERTS)
                   if scope in n)
    outside = [n for name, lines in comps.items() if name not in inside
               for line in lines for n in _OP_NAME_RE.findall(line)]
    assert has(outside, scopes.MOE_ROUTE)  # the router, top-k, the sorts
    assert not has(outside, scopes.MOE_OVERFLOW)


def test_delta_scopes_are_on_the_delta_mixer_and_split_it():
    """``hvd_kda_scan`` is on the delta rule from ``(q, k, v, g, beta)``
    to ``o`` (its products, its loop over the chunks; forward, recomputed
    and backward), ``hvd_kda`` on the rest of the mixer (projections,
    convolutions, norms, gates), every product of a delta layer's mixer
    is under exactly one of them, and nothing outside a delta mixer, the
    latent-attention layer beside it least of all, carries either."""
    from horovod_tpu.models.kda import DeltaAttentionConfig
    from horovod_tpu.models.mla import LatentAttentionConfig

    cfg = TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, d_model=16, d_ff=32,
        dtype=jnp.float32, norm_eps=1e-5, flash_attention=False,
        layer_pattern=(("kda", "swiglu"), ("mla", "swiglu")),
        kda=DeltaAttentionConfig(num_heads=2, head_dim=8, chunk_size=8,
                                 gate_rank=4),
        mla=LatentAttentionConfig(kv_lora_rank=8, qk_nope_head_dim=8,
                                  qk_rope_head_dim=4, v_head_dim=8,
                                  rotary=False))
    model = Transformer(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)[
        "params"]
    text = jax.jit(jax.grad(lambda p: jnp.sum(model.apply(
        {"params": p}, tokens)))).lower(params).compile().as_text()
    names = _OP_NAME_RE.findall(text)
    under = lambda scope: [n for n in names if re.search(  # noqa: E731
        scope + r"(?![\w.])", n)]
    scan, rest = under(scopes.KDA_SCAN), under(scopes.KDA)
    assert scan and rest and not set(scan) & set(rest)
    assert all("block_0/mixer/" in n for n in scan + rest)
    assert any("/while/" in n and n.endswith("dot_general") for n in scan)
    assert any("rematted_computation" in n for n in scan)
    assert any("transpose(jvp(" in n for n in scan)
    assert not any("_proj" in n or "conv1d" in n or "o_norm" in n
                   for n in scan)
    for part in ("q_proj", "k_conv1d", "f_b_proj", "b_proj", "g_b_proj",
                 "o_norm", "o_proj"):
        assert any(part in n for n in rest), part
    products = [n for n in names if "block_0/mixer/" in n
                and n.endswith("dot_general")]
    assert products and all(n in scan or n in rest for n in products)
    assert any("block_1/attn/hvd_mla" in n for n in names)


def test_attention_scopes_split_each_kind_of_layer():
    """``hvd_attn`` is on a multi-head attention layer outside its
    attention (the projections, rotary, the key/value heads' broadcast,
    the gate, the output projection), ``hvd_attn_full`` and
    ``hvd_attn_window`` on the attention itself of a layer without and
    with a window (the plain-XLA path here, the flash kernel on the
    chip), every product of an attention layer is under exactly one of
    the three, nothing outside one carries any, and the two kernel scopes
    end in the module's own name, so that a kernel under them is still
    the ``op_name`` that ends ``attn/pallas_call``."""
    from horovod_tpu.models.transformer import AttentionConfig

    assert scopes.ATTN_FULL.endswith("/attn")
    assert scopes.ATTN_WINDOW.endswith("/attn")
    kinds = (AttentionConfig(kind="full", num_heads=2, head_dim=8,
                             num_kv_heads=1, rotary_dim=4, gate=True),
             AttentionConfig(kind="sliding", num_heads=4, head_dim=8,
                             num_kv_heads=1, window=4, gate=True))
    cfg = TransformerConfig(
        vocab_size=64, num_layers=3, num_heads=2, d_model=16, d_ff=32,
        dtype=jnp.float32, flash_attention=False, attention=kinds,
        layer_pattern=(("full", "swiglu"), ("sliding", "swiglu"),
                       ("mha", "swiglu")))
    model = Transformer(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)[
        "params"]
    text = jax.jit(jax.grad(lambda p: jnp.sum(model.apply(
        {"params": p}, tokens)))).lower(params).compile().as_text()
    names = _OP_NAME_RE.findall(text)
    under = lambda scope: {n for n in names if re.search(  # noqa: E731
        r"(?<![\w.])" + scope + r"(?![\w.])", n)}
    rest, full, window = (under("hvd_attn"), under("hvd_attn_full"),
                          under("hvd_attn_window"))
    assert rest and full and window
    assert not rest & full and not rest & window and not full & window
    assert all(re.search(r"block_\d/attn/", n) for n in rest | full | window)
    # the layer without a window, named or plain "mha", is a full one
    assert {re.search(r"block_\d", n).group() for n in full} == {
        "block_0", "block_2"}
    assert {re.search(r"block_\d", n).group() for n in window} == {
        "block_1"}
    for part in ("query", "key", "value", "gate", "out"):
        assert any(f"hvd_attn/{part}/" in n for n in rest), part
    assert not any("query" in n or "gate" in n for n in full | window)
    products = [n for n in names if re.search(r"block_\d/attn/", n)
                and n.endswith("dot_general")]
    assert products and all(
        n in rest or n in full or n in window for n in products)
    assert any("transpose(jvp(" in n for n in window)
