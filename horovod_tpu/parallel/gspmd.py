"""GSPMD hot path: one logical mesh, NamedSharding-compiled collectives.

The explicit pipeline (``ops/fusion.py`` + ``training.make_train_step``
with ``overlap_grads=True``) hand-dispatches one reduce-scatter per
bucket and one all-gather per bucket, in an order the builder chose.
That mirrors reference Horovod's fusion buffer — which exists only
because the frameworks it wraps cannot schedule collectives themselves
(PAPER.md, layer map). XLA can: annotate the state with
:class:`~jax.sharding.NamedSharding` on ONE logical mesh, ``jax.jit``
the whole step, and the SPMD partitioner inserts, fuses and — with the
latency-hiding scheduler flags ``config.xla_overlap_flags`` already
installs — overlaps every collective the shardings imply. The pattern
scales "from 8-chip v4 to 6000-chip v5p without changing application
code" (SNIPPETS.md [2]/[3]).

This module is the plan layer for that path:

* :class:`GspmdPlan` — derives the logical mesh + axes from
  ``parallel/mesh.py``; batches shard over its data axes, params stay
  replicated, and ZeRO-1 optimizer rows shard over their SCHEDULE's
  scatter axes (``state_partition_specs`` → ``zero.state_specs``) on
  dim 0 of the same ``[world, shard]`` bucket layout the explicit path
  uses — so checkpoints are interchangeable between the two paths, bit
  for bit.
* :func:`apply_shards_spmd` — the ZeRO-1 exchange with **no explicit
  collective calls**: gradients are packed into the schedule's bucket
  rows and constrained to the row sharding (XLA inserts the
  reduce-scatter), the inner optimizer updates only the local rows, and
  the unpacked updates are constrained back to replicated (XLA inserts
  the all-gather).
* :func:`collective_bytes_from_hlo` / :func:`record_compiled_collectives`
  — byte accounting for the compiled path. There are no per-dispatch
  counters to advance (nothing in Python dispatches a collective), so
  the wire volume is read off the compiled HLO module itself and
  recorded under the standard ``hvd_collective_*`` families with
  ``spmd_*`` op labels.

``training.make_train_step(spmd=True)`` is the consumer;
``hvd.DistributedOptimizer`` stays the user-facing veneer
(``hvd_jax.HorovodOptimizer.update_spmd`` routes here).
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from horovod_tpu.parallel import mesh as mesh_lib
from horovod_tpu.telemetry import scopes


@dataclasses.dataclass(frozen=True)
class GspmdPlan:
    """Static description of the GSPMD hot path's logical mesh: which
    axes batches (and ZeRO rows) shard over, and which axis — if any —
    tensor-parallel layers may shard model weights over. Hashable, so a
    plan can key jit caches and ride as static data."""

    mesh: jax.sharding.Mesh
    data_axes: tuple
    model_axis: str = None

    @property
    def batch_spec(self):
        """Leading (batch) dim sharded over every data axis. ZeRO-1 row
        specs are NOT a plan property: a row's scatter axes belong to
        its ``ZeroState``'s schedule (``zero.state_specs`` /
        ``state_partition_specs`` below — an optimizer built with
        explicit ``axes=`` may scatter over a subset of the mesh), so
        :func:`apply_shards_spmd` derives them from the schedule it is
        handed rather than publishing a plan-level spec that could
        disagree with it."""
        return P(self.data_axes)

    def sharding(self, spec):
        return jax.sharding.NamedSharding(self.mesh, spec)

    def world(self):
        shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return int(np.prod([shape[a] for a in self.data_axes]))


def derive_plan(mesh=None, model_axis=None):
    """Build the :class:`GspmdPlan` for ``mesh`` (default: the mesh
    ``horovod_tpu.init()`` installed). Data axes come from
    ``mesh_lib.data_axis_names`` — ``data`` plus ``dcn`` when present —
    exactly the axes the explicit path reduces gradients over, so the
    two paths shard the same state the same way. ``model_axis`` names an
    extra mesh axis for tensor-parallel composition (validated to exist;
    the DP-only step leaves params replicated over it)."""
    mesh = mesh if mesh is not None else mesh_lib.get_mesh()
    data_axes = mesh_lib.data_axis_names(mesh)
    if not data_axes:
        raise ValueError(
            f"mesh {mesh.axis_names!r} has no data/dcn axis to shard "
            "batches over; build it with parallel.mesh.build_mesh")
    if model_axis is not None and model_axis not in mesh.axis_names:
        raise ValueError(
            f"model_axis {model_axis!r} is not an axis of the mesh "
            f"{mesh.axis_names!r}")
    return GspmdPlan(mesh=mesh, data_axes=tuple(data_axes),
                     model_axis=model_axis)


def state_partition_specs(state):
    """PartitionSpecs for a training-state pytree: everything replicated
    except ``ZeroState`` bucket rows, which shard over their schedule's
    scatter axes (``zero.state_specs``). The ONE spec authority for both
    hot paths — ``training.state_specs`` delegates here, so the explicit
    shard_map step, the GSPMD jit step, placement and checkpointing all
    agree on which leaf lives where."""
    from horovod_tpu.parallel import zero as zero_lib

    def one(node):
        if isinstance(node, zero_lib.ZeroState):
            return zero_lib.state_specs(node)
        return jax.tree_util.tree_map(lambda _: P(), node)

    return jax.tree_util.tree_map(
        one, state, is_leaf=lambda x: isinstance(x, zero_lib.ZeroState))


def _is_spec(x):
    return isinstance(x, P)


def state_shardings(plan, state):
    """``NamedSharding`` tree matching ``state``'s structure — feed
    straight to ``jax.jit(in_shardings=...)`` / ``out_shardings``."""
    return jax.tree_util.tree_map(plan.sharding,
                                  state_partition_specs(state),
                                  is_leaf=_is_spec)


def place_state(plan, state):
    """``device_put`` ``state`` onto its plan shardings (no-op when
    already placed) — the GSPMD analogue of the explicit path's
    ``place_state``, and what a checkpoint restore feeds its
    host-assembled tree through before stepping. Host or process-local
    leaves headed for a multi-process mesh are sliced locally
    (``cluster.procmesh.place``) rather than broadcast through the
    fabric by device_put's cross-process equality assert."""
    def _put(x, s):
        if s.is_fully_addressable:
            return jax.device_put(x, s)
        from horovod_tpu.cluster import procmesh

        return procmesh.place(x, s)

    return jax.tree_util.tree_map(_put, state,
                                  state_shardings(plan, state))


def constrain(x, plan, spec):
    """``with_sharding_constraint`` against the plan's mesh — the only
    way this path ever asks for communication: the constraint states
    where the value must live, XLA decides how it gets there."""
    return jax.lax.with_sharding_constraint(x, plan.sharding(spec))


def shard_map_island(fn, plan, in_specs, out_specs):
    """The SANCTIONED ``shard_map`` entry point of the GSPMD hot path:
    a per-shard region embedded INSIDE the jitted step, over the plan's
    mesh. The chunked quantized exchange (fp8/int8 wires) needs
    per-device partial gradients and per-chunk scales — values no
    sharding annotation can express — so the compressed
    reduce-scatter/all-gather cycle runs as this island while XLA's
    latency-hiding scheduler still owns the schedule of the surrounding
    program (``training._make_spmd_train_step`` is the consumer; the
    compiled module's collectives are accounted by the same HLO parser
    as the annotation-only path). Mesh-ratchet status: this helper lives
    in ``parallel/gspmd.py`` — one of hvd-lint HVD-MESH's excluded shim
    layers — precisely so the island call sites in ``training.py`` go
    through a named, reviewed entry point instead of growing new raw
    ``shard_map(`` sites (``analysis/rules/mesh.py``)."""
    return jax.shard_map(fn, mesh=plan.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def apply_shards_spmd(tx, grads, zstate, params, plan, wire=None,
                      ag_residuals=None):
    """ZeRO-1 under GSPMD: the sharding-annotation replacement for
    ``zero.sharded_update`` — identical ``[world, shard]`` bucket-row
    layout and identical inner-optimizer math, but **zero explicit
    collective calls**:

    1. pack the (logically global-mean) gradient into each bucket's
       padded rows and constrain them to ``schedule.axes`` on dim 0 —
       the partitioner turns the pending gradient reduction plus this
       sharded consumer into a reduce-scatter (or an all-reduce it then
       slices; either way the annotation, not this code, owns the
       choice and the latency-hiding scheduler owns the overlap);
    2. run ``tx.update`` on the row pytree — each device touches only
       its own rows, the ~1/N optimizer compute and state of ZeRO-1;
    3. constrain the updated rows replicated and unpack — the implied
       all-gather of the parameter deltas.

    Returns ``(updates, new_zstate)`` with ``updates`` shaped like
    ``params``. The inner state structure matches the explicit path's
    exactly, so checkpoints restore across paths unchanged.

    ``wire`` (a CAST compressor — bf16/float16) narrows both halves of
    the exchange by dtype-narrowed constraints: gradient rows are cast
    to the wire dtype BEFORE the row constraint (the pending reduction
    plus a sharded consumer at the narrow dtype lets the partitioner
    move the reduce-scatter's bytes at wire width), and the updated
    parameter-delta rows are cast before the replicated constraint (the
    implied all-gather genuinely moves wire-width bytes). Chunked
    quantizers (fp8/int8) are REJECTED here: per-chunk scales have no
    annotation-only form — that exchange is the :func:`shard_map_island`
    that ``training._make_spmd_train_step`` compiles instead.

    ``ag_residuals`` (per-bucket ``[world, shard]`` fp32 arrays, sharded
    over the schedule axes) turns on delta error feedback for the
    all-gather half only: the cast error of each delta row is carried
    into the next step's row before narrowing. The reduce-scatter half
    stays stateless by construction — a carried residual would have to
    be added to the still-unreduced logical gradient, forcing the
    reduction to complete BEFORE the narrowing cast and defeating the
    annotation. With ``ag_residuals`` the return grows to
    ``(updates, new_zstate, new_ag_residuals)``."""
    from horovod_tpu.ops import fusion
    from horovod_tpu.parallel import zero as zero_lib

    if wire is not None and getattr(wire, "chunked", False):
        raise ValueError(
            f"chunked wire format {wire.name!r} has no annotation-only "
            "form (per-chunk scales cannot ride a sharding constraint) "
            "— the quantized exchange is the shard_map island that "
            "training.make_train_step(spmd=True) compiles into the jit "
            "step; this constraint path narrows cast wires only")

    schedule = zstate.plan.schedule
    row_spec = P(tuple(schedule.axes))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    grad_leaves = jax.tree_util.tree_leaves(grads)
    if len(grad_leaves) != len(leaves):
        raise ValueError(
            f"gradient tree has {len(grad_leaves)} leaves, params have "
            f"{len(leaves)}; was the optimizer initialized with a "
            "different parameter tree?")
    grad_rows, param_rows = {}, {}
    for i in range(len(schedule.buckets)):
        rows = zero_lib.bucket_rows(schedule, i, grad_leaves)
        if wire is not None and jnp.issubdtype(rows.dtype, jnp.floating):
            # dtype-narrowed constraint: cast the (still logically
            # unreduced) rows to the wire dtype, then ask for the row
            # sharding — the partitioner owns where the reduce-scatter
            # lands, and the narrow producer lets it move wire-width
            # bytes; decode is the cast back for the fp32 update math
            grad_dtype = rows.dtype
            rows = constrain(rows.astype(wire.wire_dtype), plan,
                             row_spec).astype(grad_dtype)
            grad_rows[f"b{i}"] = rows
        else:
            grad_rows[f"b{i}"] = constrain(rows, plan, row_spec)
        param_rows[f"b{i}"] = constrain(
            zero_lib.bucket_rows(schedule, i, leaves), plan, row_spec)
    with scopes.device(scopes.OPTIMIZER):
        update_rows, new_inner = tx.update(grad_rows, zstate.inner,
                                           param_rows)

    new_residuals = list(ag_residuals) if ag_residuals is not None else None
    new_leaves = [None] * len(leaves)
    for i in range(len(schedule.buckets)):
        rows = constrain(update_rows[f"b{i}"], plan, row_spec)
        if wire is not None and jnp.issubdtype(rows.dtype, jnp.floating):
            # narrow the delta all-gather: each rank's [world, shard]
            # rows are cast to the wire dtype while still sharded, the
            # replicated constraint gathers the narrow bytes, and every
            # rank decodes the same values — params stay replicated-
            # consistent. Delta-EF compensates the cast error per row.
            out_dtype = rows.dtype
            x = rows
            if new_residuals is not None and new_residuals[i].size:
                x = x.astype(jnp.float32) + new_residuals[i].reshape(
                    x.shape)
                wire_rows = x.astype(wire.wire_dtype)
                new_residuals[i] = constrain(
                    x - wire_rows.astype(jnp.float32), plan, row_spec)
            else:
                wire_rows = x.astype(wire.wire_dtype)
            wire_rows = constrain(wire_rows, plan, row_spec)
            flat = constrain(wire_rows.reshape(-1), plan,
                             P()).astype(out_dtype)
        else:
            flat = constrain(rows.reshape(-1), plan, P())
        for j, arr in fusion.unpack_bucket(schedule, i, flat,
                                           leaves).items():
            new_leaves[j] = arr
    missing = [j for j, leaf in enumerate(new_leaves) if leaf is None]
    if missing:
        raise ValueError(
            f"ZeRO plan does not cover gradient leaves {missing}; was "
            "the optimizer initialized with a different parameter tree?")
    updates = jax.tree_util.tree_unflatten(treedef, new_leaves)
    new_zstate = zero_lib.ZeroState(new_inner, zstate.plan)
    if new_residuals is not None:
        return updates, new_zstate, new_residuals
    return updates, new_zstate


# -- compiled-HLO byte accounting -------------------------------------------

# The collective kinds this framework prices and attributes, in one
# place: the HLO byte parser below, the device-trace X-ray
# (telemetry/xprof.py) and the doctor's bandwidth join all derive their
# matching from this tuple + classifier — one authority, so a kind
# added here is priced AND time-attributed, and the two views can never
# drift on what counts as a collective.
COLLECTIVE_OPS = ("all-reduce", "reduce-scatter", "all-gather",
                  "all-to-all", "collective-permute")

# kinds as they appear in metric labels / summary JSON (dashes don't
# survive Prometheus label conventions)
def collective_label(op):
    return op.replace("-", "_")


_COLLECTIVE_KIND_RE = re.compile(
    r"^(" + "|".join(re.escape(op) for op in COLLECTIVE_OPS) + r")"
    r"(-start|-done)?(?:[.\-_]|\d|$)")


def collective_kind(name):
    """Classify one HLO instruction/op/trace-event name against
    :data:`COLLECTIVE_OPS`: returns ``(kind, async_edge)`` where
    ``kind`` is the base op (``"all-reduce"``) and ``async_edge`` is
    ``"start"``/``"done"`` for the latency-hiding scheduler's async
    pair halves (``all-reduce-start.1``), else ``None`` — or
    ``(None, None)`` when the name is not a collective. Longest-match
    first, so ``all-reduce-scatter-fusion``-style names cannot
    misclassify (``reduce-scatter`` is matched before a bare prefix
    could lie)."""
    m = _COLLECTIVE_KIND_RE.match(name)
    if not m:
        return None, None
    edge = m.group(2)
    return m.group(1), edge[1:] if edge else None


# `%name = f32[128,256]{1,0} all-reduce(...)` — result dtype/shape, then
# the collective op. Two wrinkles:
#
# * With the latency-hiding scheduler (the exact configuration this
#   path targets on TPU — config.xla_overlap_flags), collectives lower
#   to async `all-reduce-start`/`all-reduce-done` PAIRS instead of the
#   sync form. The `-start` carries the op (counted, attributed to the
#   base op name); the `-done` is the completion handle (skipped — the
#   regexes require `(` right after the optional `-start`, so `-done(`
#   never matches). CPU emits only sync forms, which is why a
#   CPU-only check cannot stand in for this.
# * Variadic/async collectives produce a TUPLE result. For variadic
#   sync ops every tuple element is an output (sum them); an async
#   `-start` tuple is (inputs..., outputs...) — symmetric halves, k
#   aliased inputs then k outputs (the combiner passes fuse many
#   gradient tensors into one variadic collective) — so sum only the
#   OUTPUT half; counting the input aliases too would double the
#   bytes.
_HLO_OP_ALTERNATION = "|".join(re.escape(op) for op in COLLECTIVE_OPS)
_HLO_RESULT_RE = re.compile(
    r"=\s*([a-z][a-z0-9]*)\[([0-9,]*)\][^=]*?"
    r"\b(" + _HLO_OP_ALTERNATION + r")(-start)?\(")
_HLO_TUPLE_RE = re.compile(
    r"=\s*\(.*?\)\s*"
    r"(" + _HLO_OP_ALTERNATION + r")(-start)?\(")
_HLO_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")

_HLO_ITEMSIZE = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}


def _shape_bytes(dtype, dims):
    itemsize = _HLO_ITEMSIZE.get(dtype)
    if itemsize is None:
        return 0
    n = 1
    for d in dims.split(","):
        d = d.strip()
        if d:
            n *= int(d)
    return n * itemsize


def _line_collective_bytes(line):
    """``(op, nbytes)`` when the HLO line is a counted collective
    instruction, else ``None`` — the one parser behind both the per-op
    and the per-axis accounting."""
    m = _HLO_RESULT_RE.search(line)
    if m:
        dtype, dims, op = m.group(1), m.group(2), m.group(3)
        return op, _shape_bytes(dtype, dims)
    t = _HLO_TUPLE_RE.search(line)
    if not t:
        return None
    op = t.group(1)
    head = line[:t.end(1)]
    shapes = _HLO_SHAPE_RE.findall(head)
    if t.group(2):
        # async -start: (inputs..., outputs...) — keep the
        # output half. collective-permute-start additionally
        # carries trailing rank-0 unsigned context handles
        # (u32[] tokens): strip those first, or the "half"
        # would land on them and count ~0 payload. An
        # unexpectedly odd tuple degrades to the final element
        # rather than over-counting.
        while (len(shapes) > 2 and shapes[-1][1] == ""
               and shapes[-1][0] in ("u32", "s32", "u64",
                                     "s64")):
            shapes = shapes[:-1]
        half = len(shapes) // 2
        shapes = (shapes[half:] if half and not len(shapes) % 2
                  else shapes[-1:])
    return op, sum(_shape_bytes(d, dims) for d, dims in shapes)


def collective_bytes_from_hlo(hlo_text):
    """Per-op collective byte/call totals of one compiled module, parsed
    from its optimized HLO text: ``{op: {"calls": n, "bytes": b}}``
    where ``bytes`` is the per-device result payload of every
    instruction of that op. This is the compiled path's replacement for
    the explicit pipeline's per-dispatch counters — the module IS the
    schedule, so the module is what gets accounted."""
    out = {}
    for line in hlo_text.splitlines():
        hit = _line_collective_bytes(line)
        if hit is None:
            continue
        op, nbytes = hit
        slot = out.setdefault(op, {"calls": 0, "bytes": 0})
        slot["calls"] += 1
        slot["bytes"] += nbytes
    return out


# Which mesh TIER does each collective ride? The partitioner stamps
# every collective with the participating device groups — explicit
# (`replica_groups={{0,1},{2,3}}`), iota/v2
# (`replica_groups=[2,4]<=[8]` with an optional `T(perm)` transpose),
# or, for collective-permute, `source_target_pairs={{0,4},{4,0}}`.
# Group members are LOGICAL partition ids, i.e. positions in the mesh's
# row-major device grid — so the axes a group varies over are exactly
# the mesh axes (ICI vs DCN tiers) its traffic rides.
_HLO_EXPLICIT_GROUPS_RE = re.compile(
    r"(?:replica_groups|source_target_pairs)=\{(\{[0-9, {}]*\})\}")
_HLO_IOTA_GROUPS_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\]"
    r"(?:T\(([0-9,]+)\))?")


def _parse_device_groups(line):
    """The collective's participating device-id groups, or ``None``
    when the line carries no group annotation (single-device module)."""
    m = _HLO_EXPLICIT_GROUPS_RE.search(line)
    if m:
        return [[int(x) for x in grp.split(",") if x.strip()]
                for grp in re.findall(r"\{([0-9, ]*)\}", m.group(1))]
    m = _HLO_IOTA_GROUPS_RE.search(line)
    if m:
        import numpy as _np
        n_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = _np.arange(int(_np.prod(dims)))
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            ids = ids.reshape(dims).transpose(perm).reshape(-1)
        return ids.reshape(n_groups, group_size).tolist()
    return None


def group_axes(groups, mesh):
    """The mesh axes a collective's device groups span, in mesh axis
    order — ``("data",)`` for an intra-host/ICI reduction, ``("dcn",)``
    for the cross-process tier, both for a global collective. For
    collective-permute pass the source→target pairs: the axes where
    source and target coordinates differ are the wire the hop rides."""
    shape = mesh.devices.shape
    varies = [False] * len(shape)
    import numpy as _np
    for grp in groups:
        coords = [_np.unravel_index(d, shape) for d in grp]
        for ax in range(len(shape)):
            if len({c[ax] for c in coords}) > 1:
                varies[ax] = True
    return tuple(a for a, v in zip(mesh.axis_names, varies) if v)


def collective_axis_bytes_from_hlo(hlo_text, mesh):
    """Per-mesh-tier collective byte totals of one compiled module:
    ``{axis_label: {"calls", "bytes", "ops": {op: bytes}}}`` where the
    label is ``"+"``-joined mesh axes (``"data"``, ``"dcn"``,
    ``"dcn+data"`` for a global collective) and ``"replica"`` collects
    instructions whose groups never leave one device (or carry no group
    annotation). This is what prices a DCN tier separately from ICI in
    the scaling sweep (bench_scaling.py / SCALING_*.json)."""
    out = {}
    for line in hlo_text.splitlines():
        hit = _line_collective_bytes(line)
        if hit is None:
            continue
        op, nbytes = hit
        groups = _parse_device_groups(line)
        axes = group_axes(groups, mesh) if groups else ()
        label = "+".join(axes) if axes else "replica"
        slot = out.setdefault(label, {"calls": 0, "bytes": 0, "ops": {}})
        slot["calls"] += 1
        slot["bytes"] += nbytes
        slot["ops"][op] = slot["ops"].get(op, 0) + nbytes
    return out


class CompiledProgramCache:
    """Shape-signature-keyed AOT executable cache: ONE
    ``lower().compile()`` per (jitted program, argument shape/dtype
    signature), each compiled module's collectives accounted exactly
    once via :func:`record_compiled_collectives` under ``<prefix>_*``
    labels, and the executable returned for DIRECT calls (on this jax
    an AOT compile does not populate the jit dispatch cache, so
    dispatching through the wrapper after compiling would build the
    identical module twice). The ONE copy of this machinery — the GSPMD
    training scaffold (``training._SpmdProgram``) and the serving
    engine (``serve/engine.py``) both wrap it, so a fix to the key or
    the accounting semantics cannot miss a site."""

    def __init__(self, prefix="spmd", mesh=None):
        self.prefix = prefix
        self.mesh = mesh  # set → per-axis (ICI/DCN tier) attribution too
        self._programs = {}  # sig -> (executable, collectives, by_axis)
        self.last_collectives = None
        self.last_axis_collectives = None

    @staticmethod
    def signature(args):
        import jax.numpy as jnp

        return tuple((tuple(jnp.shape(x)), str(jnp.result_type(x)))
                     for x in jax.tree_util.tree_leaves(args))

    def executable(self, jitted, args):
        key = self.signature(args)
        entry = self._programs.get(key)
        if entry is None:
            compiled = jitted.lower(*args).compile()
            by_axis = None
            try:
                collectives = record_compiled_collectives(
                    compiled, prefix=self.prefix)
                if self.mesh is not None:
                    by_axis = collective_axis_bytes_from_hlo(
                        compiled.as_text(), self.mesh)
            # hvd-lint: disable=HVD-EXCEPT -- HLO accounting must not kill a step
            except Exception:  # pragma: no cover — must not kill a step
                collectives = {}
            entry = (compiled, collectives, by_axis)
            self._programs[key] = entry
        self.last_collectives = entry[1]
        self.last_axis_collectives = entry[2]
        return entry[0]


def record_compiled_collectives(compiled, prefix="spmd"):
    """Account one compiled step's collectives into the standard
    telemetry families (``hvd_collective_{calls,bytes,logical_bytes}
    _total`` under ``<prefix>_<op>`` labels). Analogous to the explicit
    path's trace-time counters: recorded ONCE per compile, describing
    the collectives baked into the program — multiply by step count for
    cumulative volume (docs/OBSERVABILITY.md). Returns the parsed
    ``{op: {calls, bytes}}`` dict ({} when the HLO is unavailable)."""
    from horovod_tpu.telemetry import instruments as _tele

    try:
        text = compiled if isinstance(compiled, str) else compiled.as_text()
    # hvd-lint: disable=HVD-EXCEPT -- HLO text unavailable on this jax; accounting skipped
    except Exception:
        return {}
    ops = collective_bytes_from_hlo(text)
    for op, tot in ops.items():
        _tele.record_compiled_collective(
            f"{prefix}_{op}", calls=tot["calls"], nbytes=tot["bytes"])
    return ops
