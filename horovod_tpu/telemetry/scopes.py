"""The names a step carries from inside, on the profiler's own two
instruments and nothing else.

Device work: ``jax.named_scope`` writes its name into the ``op_name`` of
every HLO instruction traced under it, which is what the device trace and
``compiled.as_text()`` already carry (``jit(hvd_lm_train_step)/shard_map/
hvd_exchange/bucket3/psum``). Compile-time metadata only: the optimised
program is the same with or without it (tests/test_scopes.py).

Host work: ``jax.profiler.StepTraceAnnotation`` / ``TraceAnnotation`` land
on ``/host:CPU`` of the same ``.xplane.pb`` as the device events, on their
clock. With no trace running each costs a TraceMe activity check.

Both are always on: no knob, no registry metric, no exporter. The names
are the contract with ``benchmark/harness/phases.py``. A caller opens a
scope as ``scopes.device(scopes.LOSS)``, through the module, so that a test
can put a null context in its place.
"""

import jax

# device scopes
EXCHANGE = "hvd_exchange"    # pack, pad, compress, collective, unpack, divide
OPTIMIZER = "hvd_optimizer"  # the inner transform's update, apply_updates
LOSS = "hvd_loss"            # the loss after the model's last layer
# latent attention outside its kernel: the latent projections and norm,
# rotary, k from k_nope and the shared k_pe, the output projection
MLA = "hvd_mla"
# router product, scores, top-k, sort, gather into expert order, the
# weighted way back
MOE_ROUTE = "hvd_moe_route"
# the grouped products and the experts' element-wise body between them
MOE_EXPERTS = "hvd_moe_experts"
# around both of the above in the expert share's full-size branch, the one
# a step takes when its held slots pass the expert-order buffers' bound:
# device time here is how often, and for how long, the bound was passed
MOE_OVERFLOW = "hvd_moe_overflow"
# the state-space mixer outside its scan: in_proj, the convolution and its
# silu, softplus, the gate and group norm, out_proj
SSM = "hvd_ssm"
# from (u, B, C, step) to o: cumulative decay, the products inside a
# chunk, the chunk states, the scan over chunks, the inherited state's
# part, D * u; forward, recomputation and backward
SSM_SCAN = "hvd_ssm_scan"
# delta attention outside its scan: the three projections, their
# convolutions and silu, the L2 norms, the low-rank decay and its
# softplus, beta, the gated head norm, the output projection
KDA = "hvd_kda"
# from (q, k, v, g, beta) to o: the cumulative log-decay, the decayed
# k.k and q.k triangles of every chunk, the unit-triangular inverse, its
# products, the scan over chunks with the state; forward, recomputation
# and backward
KDA_SCAN = "hvd_kda_scan"
# multi-head attention (models/transformer.Attention) outside its kernel:
# the q, k, v projections, rotary, the key/value heads' broadcast and the
# layout copies, the output gate, the output projection
ATTN = "hvd_attn"
# the attention itself, forward and backward: the flash kernel's calls (or
# ring attention, or the plain-XLA path in its place) of a layer without a
# window and of a layer with one. Each name ends in the module's own
# ``attn``: the benchmark's accepted readers know the flash kernel as the
# ``op_name`` that ends ``attn/pallas_call``
ATTN_FULL = "hvd_attn_full/attn"
ATTN_WINDOW = "hvd_attn_window/attn"
# host spans
STEP = "hvd_step"      # one whole step(...) call; carries step_num
PLACE = "hvd_place"    # device_put of every leaf onto its sharding
LAUNCH = "hvd_launch"  # the call of the jitted / compiled step
# the set-up record's spans (telemetry/startup.py), host annotations too
IMPORT = "hvd_import"  # first to last line of horovod_tpu/__init__.py
INIT = "hvd_init"      # the whole of basics.init, and its four parts:
INIT_CONFIG = "hvd_init_config"  # env, logging, XLA flags, compile cache
INIT_DISTRIBUTED = "hvd_init_distributed"  # procmesh.ensure_distributed
INIT_BACKEND = "hvd_init_backend"    # the first backend touch: the mesh
INIT_SERVICES = "hvd_init_services"  # runtime.services.start
LOWER = "hvd_lower"    # _HostStep.lower: placement and program.lower
# jax's own top-level build spans, as the record keeps them
JAX_TRACE = "jax_trace"  # function -> jaxpr
JAX_LOWER = "jax_lower"  # jaxpr -> MLIR module
JAX_XLA = "jax_xla"      # the backend: a compile, or a cache read


def device(name):
    """Everything traced inside is ``.../<name>/...`` on the device."""
    return jax.named_scope(name)


def bucket(idx):
    """One bucket of the exchange's schedule: ``hvd_exchange/bucket<idx>``
    (both levels in one name: the bucket functions of ``ops/fusion.py`` are
    reached from several pipelines, none of which opens the outer one)."""
    return device(f"{EXCHANGE}/bucket{idx}")


def host(name):
    """A host span inside the step's ``hvd_step``."""
    return jax.profiler.TraceAnnotation(name)


def step(n):
    """The host span of step ``n``: what the profiler's step view groups
    by, and the number a step's spans share."""
    return jax.profiler.StepTraceAnnotation(STEP, step_num=n)
