"""ctypes binding to the native host core (libhvdcore.so).

Rebuilds the reference's ctypes surface (``horovod/common/basics.py:22``
loading the built extension and calling ``horovod_init``/...;
``horovod/torch/mpi_ops.py`` handle-based async ops) against the
TPU-framework core in ``cxx/``: name-negotiated queue, TCP controller,
ring collectives, Adasum, timeline, stall inspector.

The native core is the **host** data plane (numpy/torch CPU tensors, Join,
barrier, parameter sync). TPU-resident arrays use the compiled XLA path in
``horovod_tpu.ops.collective`` and never touch this module.
"""

import ctypes
import os
import subprocess

import numpy as np

# Request::Type (cxx/include/hvd/message.h)
ALLREDUCE, ALLGATHER, BROADCAST, JOIN, ADASUM, ALLTOALL = 0, 1, 2, 3, 4, 5
REDUCESCATTER, BARRIER = 6, 7
# ReduceOp (cxx/include/hvd/cpu_ops.h)
OP_SUM, OP_AVERAGE, OP_MIN, OP_MAX, OP_ADASUM = 0, 1, 2, 3, 4

_DTYPE_MAP = {
    np.dtype(np.uint8): 0, np.dtype(np.int8): 1,
    np.dtype(np.uint16): 2, np.dtype(np.int16): 3,
    np.dtype(np.int32): 4, np.dtype(np.int64): 5,
    np.dtype(np.float16): 6, np.dtype(np.float32): 7,
    np.dtype(np.float64): 8, np.dtype(np.bool_): 9,
}

_OP_MAP = {"sum": OP_SUM, "average": OP_AVERAGE, "min": OP_MIN,
           "max": OP_MAX, "adasum": OP_ADASUM}

_LIB_PATH = os.path.join(os.path.dirname(__file__), "lib", "libhvdcore.so")
_CXX_DIR = os.path.join(os.path.dirname(__file__), os.pardir, "cxx")

_lib = None


def _stale():
    """True when the library is missing or any source under cxx/ is
    newer than it. Both the library and cxx/build/ are git-ignored, so a
    library left in a working tree by an older commit must not be what
    runs. A tree with no cxx/ (an installed package) ships its library
    built and is never stale."""
    if not os.path.exists(_LIB_PATH):
        return True
    built = os.path.getmtime(_LIB_PATH)
    for sub in ("Makefile", "src", "include"):
        top = os.path.join(_CXX_DIR, sub)
        paths = [top] if os.path.isfile(top) else [
            os.path.join(d, f) for d, _, fs in os.walk(top) for f in fs]
        if any(os.path.getmtime(p) > built for p in paths):
            return True
    return False


def build(force=False):
    """Build libhvdcore.so from cxx/ (the reference's setup.py build step,
    here a plain make) when it is missing or older than its sources.
    File-locked: concurrently launched ranks must not run make into the
    same build dir at once."""
    if not (force or _stale()):
        return _LIB_PATH
    import fcntl
    lock_path = os.path.join(os.path.dirname(__file__), ".build.lock")
    with open(lock_path, "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            if force or _stale():  # not built while waiting
                subprocess.run(
                    ["make", "-C", os.path.abspath(_CXX_DIR), "-j"],
                    check=True, capture_output=True)
        finally:
            fcntl.flock(lock, fcntl.LOCK_UN)
    return _LIB_PATH


def _load():
    global _lib
    if _lib is not None:
        return _lib
    build()
    lib = ctypes.CDLL(_LIB_PATH)
    lib.hvdc_init.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_char_p,
                              ctypes.c_int, ctypes.c_char_p]
    lib.hvdc_enqueue.argtypes = [
        ctypes.c_int, ctypes.c_char_p, ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_double, ctypes.c_double]
    lib.hvdc_enqueue_borrow.argtypes = lib.hvdc_enqueue.argtypes
    lib.hvdc_copy_bytes.restype = ctypes.c_int64
    lib.hvdc_error_message.restype = ctypes.c_char_p
    lib.hvdc_last_error.restype = ctypes.c_char_p
    lib.hvdc_output_size.restype = ctypes.c_int64
    lib.hvdc_copy_output.argtypes = [ctypes.c_int, ctypes.c_void_p]
    lib.hvdc_autotune_state.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int)]
    lib.hvdc_control_bytes.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64)]
    lib.hvdc_data_bytes.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64)]
    _lib = lib
    return lib


def core_available():
    try:
        _load()
        return True
    # hvd-lint: disable=HVD-EXCEPT -- availability probe: any failure means the core is absent
    except Exception:
        return False


def init(rank=0, size=1, coord_host="127.0.0.1", coord_port=0,
         advertise_host="127.0.0.1"):
    """Start the native core (background negotiation loop + TCP planes).
    Reference: InitializeHorovodOnce (operations.cc:584)."""
    lib = _load()
    rv = lib.hvdc_init(rank, size, coord_host.encode(), coord_port,
                       advertise_host.encode())
    if rv != 0:
        raise RuntimeError("native core init failed: " +
                           lib.hvdc_last_error().decode())


def shutdown():
    if _lib is not None and _lib.hvdc_is_initialized():
        _sweep_orphans()  # drain completed fire-and-forget handles
        _lib.hvdc_shutdown()


def is_initialized():
    return _lib is not None and bool(_lib.hvdc_is_initialized())


def rank():
    return _lib.hvdc_rank() if _lib is not None else -1


def size():
    return _lib.hvdc_size() if _lib is not None else -1


# Buffers the core is borrowing, keyed by handle: the registry (not just
# the Handle object) pins each array until the op completes, so a caller
# that fires-and-forgets an inplace op can never leave the background
# loop holding a pointer into freed numpy memory.
_borrowed_refs = {}
# C handles whose Python Handle was garbage-collected before completion:
# their borrow must stay pinned until the background loop is done with
# the pointer, so they are swept (released + unpinned) from _enqueue once
# hvdc_poll reports completion. Keeps fire-and-forget callers leak-free.
_orphaned = set()


def _finalize_completed(h):
    """If handle ``h`` is done, unpin its borrow and release the C
    handle. Returns True when finalized (single home for the completion
    protocol: Handle.__del__ and the orphan sweep both go through it)."""
    if _lib is None or _lib.hvdc_poll(h) == 0:
        return False
    _borrowed_refs.pop(h, None)
    _lib.hvdc_release(h)
    return True


def _sweep_orphans():
    for h in list(_orphaned):
        if _finalize_completed(h):
            _orphaned.discard(h)


class Handle:
    """Async op handle (reference: horovod/torch/handle_manager.h).

    When ``borrowed`` is set the core operated zero-copy on that array's
    buffer: the handle keeps it alive until completion and ``wait``
    returns it directly (the result is already in place)."""

    def __init__(self, h, out_dtype, out_shape_hint=None, borrowed=None):
        self._h = h
        self._dtype = out_dtype
        self._shape_hint = out_shape_hint
        self._borrowed = borrowed  # ref holds caller buffer alive
        if borrowed is not None:
            _borrowed_refs[h] = borrowed
        self._released = False

    def poll(self):
        """True when the op has completed (reference hvd.poll)."""
        done = _lib.hvdc_poll(self._h) != 0
        if done:
            # core dropped the raw pointer: the registry pin can go even
            # if the caller never calls wait() (self._borrowed still
            # keeps the array alive for wait()'s in-place return)
            _borrowed_refs.pop(self._h, None)
        return done

    def __del__(self):
        if getattr(self, "_released", True):
            return
        try:
            if _lib is not None and not _finalize_completed(self._h):
                # still in flight: the background loop may hold our
                # buffer pointer — keep the pin, sweep after completion
                _orphaned.add(self._h)
        # hvd-lint: disable=HVD-EXCEPT -- interpreter shutdown: globals may already be gone
        except Exception:
            pass  # interpreter shutdown: globals may be gone

    def wait(self):
        """Block until done, return the result array (reference
        hvd.synchronize)."""
        if self._released:
            raise RuntimeError("handle already synchronized")
        rv = _lib.hvdc_wait(self._h)
        _borrowed_refs.pop(self._h, None)  # op done: core dropped the ptr
        if rv != 1:
            msg = _lib.hvdc_error_message(self._h).decode()
            _lib.hvdc_release(self._h)
            self._released = True
            raise RuntimeError(msg)
        nbytes = _lib.hvdc_output_size(self._h)
        if self._borrowed is not None and nbytes == 0:
            # in-place op on the borrowed buffer: nothing to copy out
            _lib.hvdc_release(self._h)
            self._released = True
            return self._borrowed
        out = np.empty(nbytes, dtype=np.uint8)
        _lib.hvdc_copy_output(self._h,
                              out.ctypes.data_as(ctypes.c_void_p))
        _lib.hvdc_release(self._h)
        self._released = True
        arr = out.view(self._dtype)
        if self._shape_hint is not None:
            arr = arr.reshape(self._shape_hint)
        return arr


def _enqueue(req_type, name, array, op=OP_SUM, root_rank=-1, prescale=1.0,
             postscale=1.0, out_shape=None, inplace=False):
    lib = _load()
    _sweep_orphans()
    arr = np.ascontiguousarray(array)
    if arr.dtype not in _DTYPE_MAP:
        raise ValueError(f"unsupported dtype {arr.dtype}")
    # zero-copy borrow: the core reads (and for allreduce/broadcast
    # writes) the caller's buffer directly. The in-place promise only
    # holds for a C-contiguous writable array — anything else would
    # silently reduce into a hidden ascontiguousarray copy while the
    # caller keeps reading their stale original, so refuse loudly.
    if inplace and (arr is not array or not arr.flags.writeable):
        raise ValueError(
            "inplace=True requires a C-contiguous writable ndarray "
            "(got a copy or read-only view); drop inplace or pass "
            "np.ascontiguousarray(x) yourself and read the result there")
    # Failure contract for inplace: if the collective fails, the buffer
    # contents are undefined — the single-tensor fast path may leave it
    # partially reduced, the fused path untouched (it scales and reduces
    # in the fusion buffer) — see hvdc_enqueue_borrow in
    # cxx/include/hvd/operations.h.
    borrow = inplace
    shape = (ctypes.c_int64 * arr.ndim)(*arr.shape)
    fn = lib.hvdc_enqueue_borrow if borrow else lib.hvdc_enqueue
    h = fn(req_type, name.encode(),
           arr.ctypes.data_as(ctypes.c_void_p), shape,
           arr.ndim, _DTYPE_MAP[arr.dtype], op, root_rank,
           prescale, postscale)
    if h < 0:
        raise RuntimeError(lib.hvdc_last_error().decode())
    return Handle(h, arr.dtype, out_shape, borrowed=arr if borrow else None)


def allreduce_async(array, name, op="average", prescale=1.0, postscale=1.0,
                    inplace=False):
    req = ADASUM if op == "adasum" else ALLREDUCE
    # the caller's array goes straight to _enqueue: its single
    # ascontiguousarray is what the inplace contract checks against
    return _enqueue(req, name, array, _OP_MAP[op],
                    out_shape=np.shape(array), prescale=prescale,
                    postscale=postscale, inplace=inplace)


def allreduce(array, name, op="average", **kw):
    return allreduce_async(array, name, op, **kw).wait()


def allgather_async(array, name):
    arr = np.ascontiguousarray(array)
    out_shape = (-1,) + arr.shape[1:] if arr.ndim > 0 else (-1,)
    return _enqueue(ALLGATHER, name, arr, out_shape=out_shape)


def allgather(array, name):
    return allgather_async(array, name).wait()


def broadcast_async(array, name, root_rank=0, inplace=False):
    return _enqueue(BROADCAST, name, array, root_rank=root_rank,
                    out_shape=np.shape(array), inplace=inplace)


def broadcast(array, name, root_rank=0, **kw):
    return broadcast_async(array, name, root_rank, **kw).wait()


def copy_bytes():
    """Cumulative host-side memcpy bytes the core has performed (enqueue
    copy-in, fusion staging, output copy-out). The zero-copy ``inplace``
    paths keep this flat for large tensors."""
    return int(_load().hvdc_copy_bytes())


def reducescatter_async(array, name, op="sum", prescale=1.0, postscale=1.0):
    """Reduce across ranks, scatter along dim 0: this rank receives rows
    [rank*base + min(rank, rem) ...) of the reduction (remainder rows go
    to the first ranks), matching the compiled path's dim-0 split."""
    arr = np.ascontiguousarray(array)
    d0 = arr.shape[0] if arr.ndim > 0 else 1
    n = _lib.hvdc_size() if _lib is not None and _lib.hvdc_size() > 0 else 1
    base, rem = divmod(d0, n)
    r = _lib.hvdc_rank() if _lib is not None else 0
    rows = base + (1 if r < rem else 0)
    out_shape = (rows,) + arr.shape[1:]
    return _enqueue(REDUCESCATTER, name, arr, _OP_MAP[op],
                    out_shape=out_shape, prescale=prescale,
                    postscale=postscale)


def reducescatter(array, name, op="sum", **kw):
    return reducescatter_async(array, name, op, **kw).wait()


def alltoall_async(array, name):
    arr = np.ascontiguousarray(array)
    return _enqueue(ALLTOALL, name, arr, out_shape=arr.shape)


def alltoall(array, name):
    return alltoall_async(array, name).wait()


def join():
    """Announce data exhaustion; returns the rank that joined LAST once
    every rank has joined (reference EnqueueJoin + hvd.join()'s
    last-joined-rank return, operations.cc:909)."""
    lib = _load()
    h = lib.hvdc_enqueue_join()
    if h < 0:
        raise RuntimeError("join: core not initialized")
    rv = lib.hvdc_wait(h)
    msg = lib.hvdc_error_message(h).decode()
    last = -1
    if rv == 1 and lib.hvdc_output_size(h) == 4:
        out = np.zeros(1, dtype=np.int32)
        lib.hvdc_copy_output(h, out.ctypes.data_as(ctypes.c_void_p))
        last = int(out[0])
    lib.hvdc_release(h)
    if rv != 1:
        raise RuntimeError(f"join failed: {msg}")
    return last


def barrier():
    lib = _load()
    if lib.hvdc_barrier() != 0:
        raise RuntimeError("barrier failed")
    # a barrier proves every previously enqueued op completed: sweep so
    # fire-and-forget callers that never enqueue again don't pin
    # orphaned buffers until process exit
    _sweep_orphans()


def control_bytes():
    """Cumulative control-plane bytes (sent, received) in negotiation
    rounds — the response-cache bitvector protocol shrinks these in
    steady state."""
    lib = _load()
    sent = ctypes.c_int64(0)
    recvd = ctypes.c_int64(0)
    if lib.hvdc_control_bytes(ctypes.byref(sent), ctypes.byref(recvd)) != 0:
        raise RuntimeError("native core is not initialized")
    return sent.value, recvd.value


def data_bytes():
    """Cumulative data-plane payload bytes (intra-host, cross-host) this
    rank has sent, split by the HOROVOD_LOCAL_*/CROSS_* topology —
    hierarchical collectives exist to shrink the cross-host share."""
    lib = _load()
    local = ctypes.c_int64(0)
    cross = ctypes.c_int64(0)
    if lib.hvdc_data_bytes(ctypes.byref(local), ctypes.byref(cross)) != 0:
        raise RuntimeError("native core is not initialized")
    return local.value, cross.value


def autotune_state():
    """Autotuner snapshot: dict with ``enabled``, current
    ``fusion_threshold`` / ``cycle_time_ms`` and the categorical
    ``hierarchical`` / ``cache`` gates, coordinator-side ``samples``
    (-1 on workers) and ``done`` (reference: parameter_manager state)."""
    lib = _load()
    fusion = ctypes.c_int64(0)
    cycle = ctypes.c_double(0.0)
    samples = ctypes.c_int(0)
    done = ctypes.c_int(0)
    hier = ctypes.c_int(0)
    cache = ctypes.c_int(0)
    rv = lib.hvdc_autotune_state(ctypes.byref(fusion), ctypes.byref(cycle),
                                 ctypes.byref(samples), ctypes.byref(done),
                                 ctypes.byref(hier), ctypes.byref(cache))
    if rv < 0:
        raise RuntimeError("native core is not initialized")
    return {"enabled": bool(rv), "fusion_threshold": fusion.value,
            "cycle_time_ms": cycle.value, "samples": samples.value,
            "done": bool(done.value), "hierarchical": bool(hier.value),
            "cache": bool(cache.value)}
