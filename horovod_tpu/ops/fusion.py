"""Static tensor fusion: pack many small tensors into few big collectives.

The reference fuses at runtime: a background thread packs ready tensors into
a 64 MB fusion buffer each cycle and launches one collective per fused batch
(``FuseResponses``, ``horovod/common/controller.cc:639-769``;
``MemcpyInFusionBuffer``, ``horovod/common/ops/collective_operations.cc``).
That design fights XLA: a different fused set each step means a different
collective shape and a recompile.

The TPU-native design fuses **statically at trace time**: the gradient
pytree is flattened, leaves are grouped by dtype and packed in traversal
order into flat buckets of at most ``fusion_threshold`` bytes (default 64 MB,
matching ``operations.cc:403``), one collective is emitted per bucket, and
XLA sees the same shapes every step — compile once, zero renegotiation.
This is strictly stronger than the reference's steady-state response-cache
path (``response_cache.h:45-102``): the "cache hit" is baked into the
executable.
"""

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.ops import collective
from horovod_tpu.telemetry import scopes


@dataclasses.dataclass(frozen=True)
class _Bucket:
    """One fusion buffer: which flat leaves it packs and where."""
    dtype: object
    leaf_indices: tuple  # indices into the flattened leaf list
    sizes: tuple         # element count per packed leaf
    shapes: tuple        # original shape per packed leaf


def plan_buckets(leaves, threshold_bytes, reverse=False):
    """Greedy packing of leaves into dtype-homogeneous buckets of at most
    ``threshold_bytes`` (a single leaf larger than the threshold gets its own
    bucket, like a single tensor larger than the reference's fusion buffer,
    ``controller.cc:687-696``).

    ``reverse=True`` packs in REVERSE traversal order: backprop produces
    gradients for the last layers first, so reverse-ordered buckets fill in
    the order they become ready — the ordering the overlapped reduce-scatter
    pipeline (``bucket_schedule``) wants, and the same trick the reference's
    bucketed DDP implementations use (gradient hooks fire back-to-front)."""
    by_dtype = {}
    order = range(len(leaves) - 1, -1, -1) if reverse else range(len(leaves))
    for i in order:
        by_dtype.setdefault(jnp.asarray(leaves[i]).dtype, []).append(i)
    buckets = []
    for dtype, idxs in by_dtype.items():
        itemsize = np.dtype(dtype).itemsize
        cur, cur_bytes = [], 0
        for i in idxs:
            nbytes = int(np.prod(np.shape(leaves[i]))) * itemsize
            if cur and cur_bytes + nbytes > threshold_bytes:
                buckets.append(_make_bucket(dtype, cur, leaves))
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += nbytes
        if cur:
            buckets.append(_make_bucket(dtype, cur, leaves))
    return buckets


def _make_bucket(dtype, idxs, leaves):
    return _Bucket(
        dtype=dtype,
        leaf_indices=tuple(idxs),
        sizes=tuple(int(np.prod(np.shape(leaves[i])) or 1) for i in idxs),
        shapes=tuple(tuple(np.shape(leaves[i])) for i in idxs),
    )


def _pack(bucket, leaves):
    return jnp.concatenate(
        [jnp.ravel(leaves[i]) for i in bucket.leaf_indices])


def _unpack(bucket, flat):
    out = {}
    offset = 0
    for i, size, shape in zip(bucket.leaf_indices, bucket.sizes,
                              bucket.shapes):
        out[i] = flat[offset:offset + size].reshape(shape)
        offset += size
    return out


# ---------------------------------------------------------------------------
# Bucketed reduce-scatter pipeline.
#
# The overlapped gradient-exchange data plane: instead of one fused
# allreduce after the full backward, gradients are packed into
# reverse-traversal-ordered buckets and each bucket is reduce-scattered as
# soon as it is ready, so the next microbatch's backward overlaps the
# previous bucket's reduction (XLA's async-collective/latency-hiding
# scheduler does the actual overlapping — config.xla_overlap_flags). The
# reduced 1/world shards feed either an all-gather (plain data-parallel) or
# a ZeRO-1 sharded optimizer update (parallel/zero.py).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BucketSchedule:
    """Static plan for the pipelined bucket exchange.

    ``axes`` is the SCATTER ORDER: reduce-scatter walks it first-to-last,
    all-gather inverts it, and the shard owned by a rank is chunk
    ``collective.mesh_rank(axes)`` — so a consistent schedule is the single
    source of truth for which rank owns which flat range (the contract
    ``parallel/zero.py`` builds its optimizer-state partition on).
    ``padded_sizes`` rounds each bucket up to a multiple of ``world`` so
    XLA's equal-shard constraint holds for any parameter count."""

    buckets: tuple       # _Bucket, reverse-traversal (backward-ready) order
    padded_sizes: tuple  # per-bucket element count, multiple of world
    world: int
    axes: tuple

    @property
    def shard_sizes(self):
        return tuple(p // self.world for p in self.padded_sizes)


def bucket_schedule(leaves, world, threshold_bytes=None, axes=None,
                    hierarchical=False):
    """Plan the bucketed exchange for ``leaves`` (one bucket set, reused by
    every microbatch and every step — compile once).

    With ``hierarchical`` and a dcn axis present, the scatter order is
    reordered ICI-first so the DCN stage moves ``1/ici_size`` of the bytes
    (the two-level composition of ``parallel/hierarchical``)."""
    from horovod_tpu import basics
    from horovod_tpu.config import DEFAULT_FUSION_THRESHOLD
    from horovod_tpu.parallel.mesh import DCN_AXIS

    if threshold_bytes is None:
        cfg = basics._state.config
        threshold_bytes = (cfg.fusion_threshold if cfg is not None
                           else DEFAULT_FUSION_THRESHOLD)
    axes = collective._resolve_axes(axes)
    if hierarchical and DCN_AXIS in axes and len(axes) > 1:
        axes = tuple(a for a in axes if a != DCN_AXIS) + (DCN_AXIS,)
    buckets = tuple(plan_buckets(leaves, threshold_bytes, reverse=True))
    padded = tuple(sum(b.sizes) + (-sum(b.sizes)) % world for b in buckets)
    return BucketSchedule(buckets=buckets, padded_sizes=padded,
                          world=world, axes=axes)


def _timeline_mark(kind, idx, nbytes):
    """BUCKET_RS / BUCKET_AG instant markers: emitted at trace time (the
    pipeline is compiled, so per-step device timing lives in the XLA
    profiler; these markers document the emitted schedule next to it).
    When a step-dispatch flow is open (``training.make_train_step``
    stashes its id on the timeline), the marker joins it — linking the
    dispatch slice to the bucket collectives it scheduled."""
    from horovod_tpu import basics
    from horovod_tpu.diag import recorder as _flightrec
    _flightrec.record_event("bucket", kind=kind, idx=idx, nbytes=nbytes)
    tl = basics._state.timeline
    if tl is not None:
        tl.bucket_marker(kind, idx, nbytes,
                         flow_id=getattr(tl, "_step_flow_id", None))


def _bucket_fill(schedule, idx):
    used = sum(schedule.buckets[idx].sizes)
    padded = schedule.padded_sizes[idx]
    return used / padded if padded else 1.0


def _pack_padded(schedule, idx, leaves):
    """Bucket ``idx`` packed flat and zero-padded to its scheduled size."""
    flat = _pack(schedule.buckets[idx], leaves)
    pad = schedule.padded_sizes[idx] - flat.shape[0]
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat


def _in_bucket_scope(fn):
    """Trace ``fn(schedule, idx, ...)`` under ``hvd_exchange/bucket<idx>``:
    the overlap and ZeRO pipelines reach the bucket functions directly,
    so each names its own work on the device."""
    @functools.wraps(fn)
    def scoped(schedule, idx, *args, **kwargs):
        with scopes.bucket(idx):
            return fn(schedule, idx, *args, **kwargs)
    return scoped


@_in_bucket_scope
def reduce_scatter_bucket(schedule, idx, leaves, op=collective.Average):
    """Pack bucket ``idx`` from ``leaves``, pad to the schedule's padded
    size, and reduce-scatter it over the schedule's scatter order. Returns
    this rank's reduced shard (``shard_sizes[idx]`` elements)."""
    from horovod_tpu import telemetry

    t0 = time.perf_counter()
    flat = _pack_padded(schedule, idx, leaves)
    nbytes = flat.shape[0] * flat.dtype.itemsize
    _timeline_mark("RS", idx, nbytes)
    out = collective.reducescatter(flat, op=op, axes=schedule.axes)
    telemetry.record_bucket("rs", _bucket_fill(schedule, idx), nbytes,
                            dispatch_s=time.perf_counter() - t0,
                            dtype=flat.dtype)
    return out


@_in_bucket_scope
def reduce_scatter_bucket_compressed(schedule, idx, leaves, wire,
                                     op=collective.Average, residual=None):
    """Wire-compressed :func:`reduce_scatter_bucket`: the interconnect
    carries bucket ``idx`` at ``wire``'s width instead of the gradient
    dtype. Returns ``(shard, new_residual)``.

    * **Cast wire** (bf16/fp16): sums of cast values are meaningful, so
      the bucket is narrowed and reduce-scattered AT the wire dtype —
      same collective as the exact path, half the bytes.
    * **Chunked quantizer** (fp8/int8): per-chunk scales cannot be summed
      in flight, so the exchange is an all-to-all of the quantized
      ``[world, shard]`` rows (each rank receives every peer's
      contribution to ITS shard, still at wire width — the same
      bandwidth-optimal volume as a ring reduce-scatter) followed by a
      local decode-and-sum in fp32. Chunks never straddle the shard
      boundary, so each destination decodes its rows from the scales that
      rode with them.

    ``residual`` is the per-bucket error-feedback carry: it is added into
    the bucket BEFORE compression and the new quantization error
    (``values - decode(encode(values))``) comes back as ``new_residual``
    — the caller threads it into the next step (``training.
    make_train_step``). Pass ``residual=None`` for stateless compression
    (``new_residual`` is then None too). Non-float buckets are never
    narrowed: they take the exact path bit-for-bit and pass the residual
    through unchanged."""
    from horovod_tpu import telemetry

    if not jnp.issubdtype(schedule.buckets[idx].dtype, jnp.floating):
        # decide off the bucket's static dtype BEFORE packing — the
        # delegate re-packs, so packing here would trace the bucket twice
        return reduce_scatter_bucket(schedule, idx, leaves, op=op), residual
    t0 = time.perf_counter()
    flat = _pack_padded(schedule, idx, leaves)
    logical_nbytes = flat.shape[0] * flat.dtype.itemsize
    grad_dtype = flat.dtype
    world = schedule.world
    shard = schedule.shard_sizes[idx]
    if residual is not None:
        # the compensated sum and the residual math stay in fp32: for
        # bf16 gradients the quantization error sits at or below the
        # bf16 ulp, so adding the carry AT the gradient dtype would
        # round the compensation away and EF would silently degrade to
        # stateless quantization
        flat = flat.astype(jnp.float32) + residual.reshape(flat.shape)
    if getattr(wire, "chunked", False):
        q = wire.for_length(shard)
        rows = flat.reshape(world, shard)
        if residual is not None:
            wire_rows, scales, deq = q.roundtrip(rows)
            new_residual = (rows - deq).reshape(flat.shape)
        else:
            wire_rows, scales = q.compress_flat(rows)
            new_residual = None
        # per-ROW accounting: each of the world rows pads to a chunk
        # multiple and carries its own scales (chunks never straddle the
        # shard boundary), so the wire volume is world x the per-shard
        # cost, not one flat-bucket encode
        nbytes = q.wire_bytes(shard, grad_dtype) * world
        _timeline_mark("RS", idx, nbytes)
        # row r of the received array is rank r's quantized contribution
        # to THIS rank's shard (alltoall concatenates in linearized
        # mesh_rank order — the same ownership contract reducescatter
        # uses, pinned by tests/test_compression.py). The payload's
        # logical width is the full fp-width bucket; the scales are pure
        # wire overhead (logical 0), so the per-op wire/logical counters
        # stay consistent with the bucket-level aggregate.
        recv_rows = collective.alltoall(wire_rows, axes=schedule.axes,
                                        logical_nbytes=logical_nbytes)
        recv_scales = collective.alltoall(scales, axes=schedule.axes,
                                          logical_nbytes=0)
        vals = q.decompress_flat(recv_rows, recv_scales, jnp.float32,
                                 n=shard)
        out = jnp.sum(vals, axis=0)
        if op == collective.Average:
            out = out / world
        out = out.astype(grad_dtype)
    else:
        if residual is not None:
            wire_flat, _, deq = wire.roundtrip(flat)
            new_residual = flat - deq
        else:
            wire_flat, _ = wire.compress_flat(flat)
            new_residual = None
        nbytes = wire.wire_bytes(flat.shape[0], grad_dtype)
        _timeline_mark("RS", idx, nbytes)
        out = collective.reducescatter(
            wire_flat, op=op, axes=schedule.axes,
            logical_nbytes=logical_nbytes).astype(grad_dtype)
    telemetry.record_bucket("rs", _bucket_fill(schedule, idx), nbytes,
                            dispatch_s=time.perf_counter() - t0,
                            logical_nbytes=logical_nbytes,
                            dtype=grad_dtype)
    return out, new_residual


@_in_bucket_scope
def all_gather_bucket(schedule, idx, shard):
    """Inverse of :func:`reduce_scatter_bucket`: all-gather the per-rank
    shards of bucket ``idx`` back into the full (padded) flat bucket.
    ``collective.allgather`` walks the axes last-to-first, which inverts
    the scatter order, so chunk ownership round-trips exactly."""
    from horovod_tpu import telemetry

    t0 = time.perf_counter()
    nbytes = shard.shape[0] * schedule.world * shard.dtype.itemsize
    _timeline_mark("AG", idx, nbytes)
    out = collective.allgather(shard, axes=schedule.axes)
    telemetry.record_bucket("ag", _bucket_fill(schedule, idx), nbytes,
                            dispatch_s=time.perf_counter() - t0,
                            dtype=shard.dtype)
    return out


@_in_bucket_scope
def all_gather_bucket_compressed(schedule, idx, shard_vals, wire,
                                 residual=None):
    """Wire-compressed :func:`all_gather_bucket`: each rank narrows ITS
    shard of bucket ``idx`` (cast, or chunked-quantize with per-chunk
    scales riding along), all-gathers the wire payload, and decodes every
    peer's rows back to the full padded flat bucket. Returns
    ``(flat, new_residual)``.

    ``residual`` is the all-gather half's error-feedback carry (shard-
    sized — only this rank's own shard is ever encoded here): added in
    before compression, quantization error returned as ``new_residual``.
    In the ZeRO-1 pipeline the gathered payload is the parameter DELTA,
    so this is delta-EF (DoubleSqueeze-style two-way compensation): every
    rank applies the same decoded delta — params stay replicated-
    consistent — and the residual makes the CUMULATIVE applied delta
    track the exact one. Non-float shards take the exact path."""
    from horovod_tpu import telemetry

    t0 = time.perf_counter()
    if not jnp.issubdtype(shard_vals.dtype, jnp.floating):
        return all_gather_bucket(schedule, idx, shard_vals), residual
    world = schedule.world
    shard = schedule.shard_sizes[idx]
    logical_nbytes = shard * world * shard_vals.dtype.itemsize
    out_dtype = shard_vals.dtype
    x = shard_vals
    if residual is not None:
        # fp32 compensation math — see reduce_scatter_bucket_compressed
        x = x.astype(jnp.float32) + residual.reshape(x.shape)
    if getattr(wire, "chunked", False):
        q = wire.for_length(shard)
        if residual is not None:
            wire_shard, scales, deq = q.roundtrip(x)
            new_residual = x - deq
        else:
            wire_shard, scales = q.compress_flat(x)
            new_residual = None
        nbytes = q.wire_bytes(shard, out_dtype) * world
        _timeline_mark("AG", idx, nbytes)
        # allgather's own counter uses input-shard bytes; its logical
        # counterpart is this rank's shard at the logical dtype
        gathered = collective.allgather(
            wire_shard, axes=schedule.axes,
            logical_nbytes=shard * out_dtype.itemsize)
        g_scales = collective.allgather(scales, axes=schedule.axes,
                                        logical_nbytes=0)
        flat = q.decompress_flat(
            gathered.reshape(world, -1), g_scales.reshape(world, -1),
            out_dtype, n=shard).reshape(world * shard)
    else:
        if residual is not None:
            wire_shard, _, deq = wire.roundtrip(x)
            new_residual = x - deq
        else:
            wire_shard, _ = wire.compress_flat(x)
            new_residual = None
        nbytes = wire.wire_bytes(shard, out_dtype) * world
        _timeline_mark("AG", idx, nbytes)
        flat = collective.allgather(
            wire_shard, axes=schedule.axes,
            logical_nbytes=shard * out_dtype.itemsize
            ).astype(out_dtype)
    telemetry.record_bucket("ag", _bucket_fill(schedule, idx), nbytes,
                            dispatch_s=time.perf_counter() - t0,
                            logical_nbytes=logical_nbytes,
                            dtype=shard_vals.dtype)
    return flat, new_residual


@_in_bucket_scope
def unpack_bucket(schedule, idx, flat, leaves):
    """Scatter the flat bucket back into leaf positions: returns
    ``{leaf_index: array}`` with each array cast to its leaf's dtype
    (padding tail ignored)."""
    out = {}
    for i, arr in _unpack(schedule.buckets[idx], flat).items():
        out[i] = arr.astype(jnp.asarray(leaves[i]).dtype)
    return out


def fused_allreduce(tree, op=collective.Average, axes=None,
                    compression=None, threshold_bytes=None,
                    hierarchical=None):
    """Allreduce every leaf of ``tree`` using fused flat buckets.

    This is the gradient hot path — the TPU equivalent of the reference's
    fuse → collective → unfuse cycle (``PerformOperation``,
    ``operations.cc:227-304``), fully compiled.

    ``hierarchical`` forces the two-level ICI x DCN reduction (reference:
    ``NCCLHierarchicalAllreduce``, ``nccl_operations.cc:150-346``); default
    auto-enables it when the mesh has a dcn axis and config asks for it.

    ``compression`` may be a compressor object or a wire-dtype name
    (``"bf16"``/``"fp8_e4m3"``/``"int8"`` — ``compression.by_name``).
    Cast compressors narrow in place and reduce at the wire dtype;
    chunked quantizers (fp8/int8) are routed per float bucket through the
    bandwidth-optimal compressed reduce-scatter + all-gather pair
    (STATELESS here — no error feedback; the training pipeline carries
    the per-bucket residual). Chunked wire only composes with
    Sum/Average (Adasum/Min/Max have no exchange-then-reduce form — a
    loud error, not silent fallback); non-float buckets always take the
    exact path. Chunked wire is also SINGLE-LEVEL: ``hierarchical`` is
    ignored for it (with a warning when it would have applied) — the
    two-level ICI/DCN reduction has no compressed form, the DCN simply
    carries the narrowed volume.
    """
    from horovod_tpu import basics
    from horovod_tpu.config import DEFAULT_FUSION_THRESHOLD
    from horovod_tpu.ops import compression as compression_lib
    from horovod_tpu.parallel import hierarchical as hier_lib
    from horovod_tpu.parallel.mesh import DCN_AXIS

    if threshold_bytes is None:
        cfg = basics._state.config
        threshold_bytes = (cfg.fusion_threshold if cfg is not None
                           else DEFAULT_FUSION_THRESHOLD)
    if hierarchical is None:
        cfg = basics._state.config
        hierarchical = cfg.hierarchical_allreduce if cfg is not None else False
    if isinstance(compression, str):
        compression = compression_lib.by_name(compression)

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    axes = collective._resolve_axes(axes)
    buckets = plan_buckets(leaves, threshold_bytes)

    chunked = compression is not None and getattr(compression, "chunked",
                                                  False)
    if chunked:
        if op not in (collective.Sum, collective.Average):
            raise ValueError(
                f"chunked wire format {compression.name!r} only composes "
                f"with Sum/Average (got {op!r}): Adasum/Min/Max reductions "
                "have no exchange-then-reduce form")
        try:
            world = collective.mesh_size(axes)
        except Exception:
            raise ValueError(
                "chunked wire compression needs the compiled mesh path "
                "(hvd.init() / shard_map); no mesh is available") from None
        if world == 1:
            compression, chunked = None, False  # no wire to compress
        elif hierarchical and DCN_AXIS in axes and len(axes) > 1:
            # the chunked exchange is a single-level all-to-all: there is
            # no two-level compressed composition (decoded partial sums
            # cannot be re-quantized without a second error budget), so
            # the DCN hop carries full per-rank wire volume — at 1/4
            # width. Say so instead of silently eating the knob.
            import warnings
            warnings.warn(
                f"hierarchical allreduce is ignored for the chunked wire "
                f"format {compression.name!r}: the quantized exchange is "
                "single-level, so the dcn axis carries the (narrowed) "
                "per-rank volume without the ICI-first reduction. Use "
                "bf16 cast compression if the two-level path matters "
                "more than the 4x narrowing (docs/PERFORMANCE.md).",
                stacklevel=2)

    new_leaves = [None] * len(leaves)
    for n, bucket in enumerate(buckets):
        # pack, collective, unpack and an average's division, by bucket
        # (the chunked route's bucket functions nest their own name for
        # their one-bucket schedule inside it)
        with scopes.bucket(n):
            if chunked and jnp.issubdtype(bucket.dtype, jnp.floating):
                size = sum(bucket.sizes)
                sched1 = BucketSchedule(
                    buckets=(bucket,), padded_sizes=(size + (-size) % world,),
                    world=world, axes=axes)
                shard, _ = reduce_scatter_bucket_compressed(
                    sched1, 0, leaves, compression, op=op)
                flat, _ = all_gather_bucket_compressed(sched1, 0, shard,
                                                       compression)
                for i, arr in _unpack(bucket, flat).items():
                    new_leaves[i] = arr.astype(jnp.asarray(leaves[i]).dtype)
                continue
            flat = _pack(bucket, leaves)
            logical = flat.shape[0] * flat.dtype.itemsize
            if compression is not None:
                flat, ctx = compression.compress(flat)
            # the RS->AR->AG hierarchy only exists for sum/average; every
            # other op falls through to collective.allreduce, which computes
            # Min/Max flat and already runs Adasum's OWN 2-level composite
            # on a multi-axis mesh (ops/adasum.py) — one dispatch copy
            if (hierarchical and op in (collective.Sum, collective.Average)
                    and DCN_AXIS in axes and len(axes) > 1):
                from horovod_tpu import telemetry

                # hierarchical_allreduce composes raw lax collectives that
                # record nothing themselves — account the dispatch here so a
                # cast-compressed payload keeps its wire-vs-logical
                # attribution on this path too
                telemetry.record_collective(
                    "hier_allreduce", flat.shape[0] * flat.dtype.itemsize,
                    logical_nbytes=logical)
                ici_axes = tuple(a for a in axes if a != DCN_AXIS)
                flat = hier_lib.hierarchical_allreduce(
                    flat, ici_axes=ici_axes, dcn_axis=DCN_AXIS, op=op)
            else:
                flat = collective.allreduce(
                    flat, op=op, axes=axes,
                    logical_nbytes=(logical if compression is not None
                                    else None))
            if compression is not None:
                flat = compression.decompress(flat, ctx)
            for i, arr in _unpack(bucket, flat).items():
                new_leaves[i] = arr.astype(jnp.asarray(leaves[i]).dtype)
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


class AutotuneTimings(dict):
    """``{threshold_bytes: seconds}`` from :func:`autotune_fusion_threshold`
    plus ``retried`` — how many candidate trials hit an inverted slope
    window and entered the escalation loop (a nonzero count means the
    trial lengths were near the noise floor for this workload) —
    ``slope_window_escalations`` — how many 4x iter escalations those
    retries burned in total (0 with every trial cleanly measured; the
    BENCH json records it so a threshold that was MEASURED is
    distinguishable from one that was still a guessed upper bound after
    escalation) — and ``abstain_reason``: when the tuner returned
    ``(None, timings)`` instead of a winner, the human-readable reason
    why the trials carried no rankable signal (docs/AUTOTUNE.md, "When
    the tuner abstains")."""

    def __init__(self, *args, retried=0, slope_window_escalations=0,
                 abstain_reason=None, **kwargs):
        super().__init__(*args, **kwargs)
        self.retried = retried
        self.slope_window_escalations = slope_window_escalations
        self.abstain_reason = abstain_reason


def autotune_fusion_threshold(tree, op=collective.Average, axes=None,
                              candidates=None, trials=10, apply=True,
                              tolerance=0.10, wire_candidates=None):
    """Pick the fusion bucket threshold by timed trials at init.

    The compiled-path analogue of the reference autotuner's
    fusion-threshold search (``parameter_manager.h:186-220``): on TPU the
    fused set is static per executable, so instead of online Bayesian
    optimization over cycles, we compile one executable per candidate
    threshold, time the fused allreduce of the actual gradient pytree on
    the real mesh, and keep the fastest. With ``apply=True`` (default)
    the winner becomes the process-wide default ``fusion_threshold`` used
    by ``fused_allreduce`` / ``DistributedOptimizer``.

    Timing uses the shared readback-slope primitive
    (``utils.benchmarks.slope_window``); each trial call threads an
    incrementing ``salt`` operand and the evolving output back in as the
    next input, so no two calls see identical inputs.

    Returns ``(best_threshold_bytes, timings)`` where ``timings`` is an
    :class:`AutotuneTimings` — ``{threshold: seconds for ``trials`` iters}``
    whose ``retried`` attribute counts the trials that hit an inverted
    slope window and were re-run with doubled iters (ranking candidates on
    an inverted window's full-window upper bound would compare fixed
    dispatch costs, not bucket plans).

    **Abstention (no-signal contract, docs/AUTOTUNE.md):** the tuner
    returns ``(None, timings)`` — installing nothing, with
    ``timings.abstain_reason`` set — instead of publishing a fake winner
    when the trials cannot rank candidates:

    * the world size over ``axes`` is 1 (the collectives are no-ops;
      every "timing" is pure dispatch noise), or
    * after retries some candidate is still an unresolved upper BOUND
      (``WindowTime.upper_bound``) within ``tolerance`` of the argmin —
      its true time could be anywhere at or below the bound, so the
      argmin is not trustworthy.

    **Wire-dtype axis:** with ``wire_candidates`` (a list of wire-format
    names — ``["none", "bf16", "fp8_e4m3", "int8"]``) the search grid
    becomes the cross product ``(threshold, wire)`` — the wire format a
    bucket should ride at depends on the bucket size the threshold
    produces (small buckets are dispatch-bound and gain nothing from
    narrowing; big ones are bandwidth-bound), so the two knobs must be
    ranked jointly, not in sequence. Timings are then keyed by the
    ``(threshold_bytes, wire_name)`` pair, the SAME cross-rank
    flag-allreduce and abstention machinery applies to the flattened
    grid, and ``apply=True`` installs BOTH ``config.fusion_threshold``
    and ``config.wire_dtype``. Returns ``((threshold, wire), timings)``
    in this mode. Note the trials rank wall-clock only — the wire
    formats differ in NUMERICS too (docs/PERFORMANCE.md, "Wire
    compression"), which stays the user's call: pass only the formats
    whose accuracy budget fits the model.
    """
    from jax.sharding import PartitionSpec as P

    from horovod_tpu import basics
    from horovod_tpu.parallel import mesh as mesh_lib
    from horovod_tpu.utils.benchmarks import WindowTime, slope_window, sync

    from horovod_tpu.ops import compression as compression_lib

    if candidates is None:
        candidates = [1 << 20, 4 << 20, 16 << 20, 64 << 20]
    joint = wire_candidates is not None
    if joint:
        for w in wire_candidates:
            compression_lib.by_name(w)  # fail fast on a typo'd wire name
        keys = [(thr, w) for thr in candidates for w in wire_candidates]
    else:
        keys = list(candidates)
    try:
        mesh = mesh_lib.get_mesh()
    except RuntimeError:
        mesh = None
    axes_t = collective._resolve_axes(axes) if mesh is not None else axes

    # world size over the reduction axes: mesh participants on the
    # compiled path; on the eager fallback the participant set is the
    # native core's world when it is up (hvdrun multi-process without
    # jax.distributed — jax.process_count() is 1 per process there),
    # else the jax process count
    if mesh is not None:
        shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        world = int(np.prod([shape[a] for a in axes_t]))
    else:
        from horovod_tpu import _core as _core_probe
        world = (_core_probe.size() if _core_probe.is_initialized()
                 else jax.process_count())
    if world <= 1:
        return None, AutotuneTimings(abstain_reason=(
            f"world size 1 over axes {axes_t!r}: the fused collectives "
            "are local no-ops, so threshold timings carry no signal"))

    if joint and mesh is None:
        # the eager fallback times trials WITHOUT shard_map; chunked
        # quantizers need the compiled mesh path (fused_allreduce would
        # raise mid-trial and kill the whole search) — drop them from
        # the grid loudly and rank what can be measured
        dropped = sorted({
            w for w in wire_candidates
            if getattr(compression_lib.by_name(w), "chunked", False)})
        if dropped:
            import warnings
            warnings.warn(
                f"dropping chunked wire candidates {dropped} from the "
                "autotune grid: no compiled mesh is available (the eager "
                "fallback cannot run the quantized exchange). Initialize "
                "the mesh (hvd.init()) to rank fp8/int8.")
            keys = [k for k in keys if k[1] not in dropped]
        if not keys:
            return None, AutotuneTimings(abstain_reason=(
                "every wire candidate is a chunked quantizer and no "
                "compiled mesh is available to time them"))

    timings = AutotuneTimings()
    for key in keys:
        thr, wire_name = key if joint else (key, None)

        def f(t, salt, _thr=thr, _wire=wire_name):
            # salt-shift every leaf: distinct inputs per trial call, and
            # the reduced output (fed back as the next input) keeps
            # drifting, so no two calls are memoizable as pure replays.
            def shift(x):
                x = jnp.asarray(x)
                if jnp.issubdtype(x.dtype, jnp.floating):
                    return x + (salt * jnp.finfo(jnp.float32).eps).astype(
                        x.dtype)
                return x
            t = jax.tree_util.tree_map(shift, t)
            return fused_allreduce(t, op=op, axes=axes_t,
                                   threshold_bytes=_thr,
                                   compression=_wire)
        if mesh is not None:
            spec = jax.tree_util.tree_map(lambda _: P(), tree)
            f = jax.shard_map(f, mesh=mesh, in_specs=(spec, P()),
                              out_specs=spec, check_vma=False)
        jf = jax.jit(f)
        salt0 = jnp.zeros((), jnp.float32)
        sync(jf(tree, salt0))  # compile + true completion, outside timing

        def step_once(st):
            t, salt = st
            out = jf(t, salt)
            return (out, salt + 1.0), out

        st = (tree, salt0 + 1.0)
        dt, st = slope_window(step_once, st, trials)
        # Inverted slope window: the trial produced a full-window UPPER
        # BOUND (fixed dispatch costs included), not a measurement —
        # ranking candidates on it compares noise. Doubling creeps up
        # too slowly to clear the fixed-cost floor within its cap, and
        # bounds then leak into the ranking. Escalate HARD instead — x4
        # per retry, bounded at 16x — and count every escalation so the
        # BENCH json can tell a measured threshold from a guessed bound.
        iters = trials
        if dt.upper_bound:
            timings.retried += 1
            while dt.upper_bound and iters < trials * 16:
                iters *= 4
                timings.slope_window_escalations += 1
                dt, st = slope_window(step_once, st, iters)
        # normalize retried trials back to seconds-per-`trials`-iters so
        # candidates stay comparable under argmin
        timings[key] = WindowTime(float(dt) * trials / iters,
                                  upper_bound=dt.upper_bound,
                                  asymmetric=dt.asymmetric)

    # Multi-process: every rank must install the SAME winner, or ranks
    # would plan different bucket structures and emit mismatched
    # collectives. Sum the timings across ranks, then argmin — a
    # deterministic, globally identical choice. The upper-bound flags
    # ride along (max across ranks) so the abstain decision below is
    # identical everywhere too.
    from horovod_tpu import _core
    if _core.is_initialized() and _core.size() > 1:
        vals = np.asarray(
            [timings[c] for c in keys]
            + [float(getattr(timings[c], "upper_bound", False))
               for c in keys], np.float64)
        n = _AUTOTUNE_CALLS.setdefault("n", 0)
        _AUTOTUNE_CALLS["n"] = n + 1
        summed = _core.allreduce(vals, f"autotune.fusion.{n}", op="sum")
        timings = AutotuneTimings(
            {c: WindowTime(float(s), upper_bound=bool(b > 0))
             for c, s, b in zip(keys, summed, summed[len(keys):])},
            retried=timings.retried,
            slope_window_escalations=timings.slope_window_escalations)

    def _fmt_key(c):
        if joint:
            return f"{c[0] >> 20}MB/{c[1]}"
        return f"{c >> 20}MB"

    best = min(timings, key=timings.get)
    best_val = float(timings[best])
    # Abstain on unresolved bounds near the argmin: an upper BOUND only
    # says "the true time is <= this", so any bounded candidate within
    # `tolerance` of (or below) the best value could secretly be the
    # winner — publishing an argmin over it would install noise.
    unresolved = sorted(
        c for c in keys
        if getattr(timings[c], "upper_bound", False)
        and float(timings[c]) <= best_val * (1.0 + tolerance))
    if unresolved:
        timings.abstain_reason = (
            f"candidate(s) {[_fmt_key(c) for c in unresolved]} are still "
            f"inverted-window upper bounds within {tolerance:.0%} of the "
            "best measured time after retries; the argmin would rank "
            "noise — keeping the current default")
        return None, timings
    if apply and basics._state.config is not None:
        if joint:
            basics._state.config.fusion_threshold = best[0]
            basics._state.config.wire_dtype = (
                None if best[1] in (None, "none") else best[1])
        else:
            basics._state.config.fusion_threshold = best
    return best, timings


_AUTOTUNE_CALLS = {}
