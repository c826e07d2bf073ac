"""ctypes bindings for libstf_runtime.so (runtime_cc/).

(ref: the reference loads its C++ core via swig pybind
tensorflow/python/pywrap_tensorflow; we bind the native runtime with
ctypes — no build-time Python binding dependency.)

Provides: crc32c, TFRecord reader/writer, arena allocator, flat graph
prune/topo-sort, and the C-API graph builder used by tests. All callers
must handle ``available() == False`` (no toolchain, STF_DISABLE_NATIVE);
a build that was attempted and failed raises instead.
"""

from __future__ import annotations

import ctypes
import fcntl
import os
import shutil
import subprocess
import threading
from typing import Iterator, List, Optional, Sequence

import numpy as np

from ..platform import sync as _sync

_lock = _sync.Lock("native/lib_load", rank=_sync.RANK_LIFECYCLE,
                   blocking_ok=True)
_lib = None
_tried = False

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_CC_DIR = os.path.join(_REPO_ROOT, "runtime_cc")
_LIB_NAMES = ("libstf_runtime.so",)


def _find_or_build() -> Optional[str]:
    """The library built from the sources on disk. Inside a checkout
    ``make`` decides: it rebuilds when any source, header or the
    Makefile is newer than the library and is a no-op otherwise, so a
    stale ``.so`` is never loaded. A failed build raises with the
    compiler's stderr. Without a toolchain or a ``runtime_cc/`` (an
    installed package) a prebuilt library beside this module is used,
    else None."""
    lib = os.path.join(_CC_DIR, _LIB_NAMES[0])
    if os.path.isdir(_CC_DIR) and shutil.which("make"):
        # one build at a time across processes (xdist workers, data
        # workers): flock the Makefile itself, no extra file
        with open(os.path.join(_CC_DIR, "Makefile")) as lock_f:
            fcntl.flock(lock_f, fcntl.LOCK_EX)
            proc = subprocess.run(["make", "-C", _CC_DIR, "-j4"],
                                  capture_output=True, text=True,
                                  timeout=240)
        if proc.returncode != 0:
            raise RuntimeError(
                f"building {lib} failed (make exit {proc.returncode}):\n"
                f"{proc.stderr}")
        return lib
    for c in (lib, os.path.join(os.path.dirname(__file__), _LIB_NAMES[0])):
        if os.path.exists(c):
            return c
    return None


def _bind(lib):
    c = ctypes
    u8p = c.POINTER(c.c_uint8)
    u64p = c.POINTER(c.c_uint64)
    lib.StfVersion.restype = c.c_char_p
    lib.StfCrc32c.argtypes = [u8p, c.c_size_t]
    lib.StfCrc32c.restype = c.c_uint32
    lib.StfMaskedCrc32c.argtypes = [u8p, c.c_size_t]
    lib.StfMaskedCrc32c.restype = c.c_uint32

    lib.StfNewStatus.restype = c.c_void_p
    lib.StfDeleteStatus.argtypes = [c.c_void_p]
    lib.StfGetCode.argtypes = [c.c_void_p]
    lib.StfGetCode.restype = c.c_int
    lib.StfMessage.argtypes = [c.c_void_p]
    lib.StfMessage.restype = c.c_char_p

    lib.StfRecordWriterOpen.argtypes = [c.c_char_p, c.c_int, c.c_void_p]
    lib.StfRecordWriterOpen.restype = c.c_void_p
    lib.StfRecordWriterWrite.argtypes = [c.c_void_p, u8p, c.c_size_t,
                                         c.c_void_p]
    lib.StfRecordWriterClose.argtypes = [c.c_void_p]

    lib.StfRecordReaderOpen.argtypes = [c.c_char_p, c.c_void_p]
    lib.StfRecordReaderOpen.restype = c.c_void_p
    lib.StfRecordReaderOpenBuffered.argtypes = [c.c_char_p, c.c_int64,
                                                c.c_void_p]
    lib.StfRecordReaderOpenBuffered.restype = c.c_void_p
    lib.StfRecordReaderNext.argtypes = [c.c_void_p, c.POINTER(u8p),
                                        c.POINTER(c.c_size_t), c.c_void_p]
    lib.StfRecordReaderNext.restype = c.c_int
    lib.StfRecordReaderNextBatch.argtypes = [
        c.c_void_p, c.c_int64, c.POINTER(u8p), c.POINTER(u64p), c.c_void_p]
    lib.StfRecordReaderNextBatch.restype = c.c_int64
    lib.StfRecordReaderClose.argtypes = [c.c_void_p]

    lib.StfArenaNew.argtypes = [c.c_size_t]
    lib.StfArenaNew.restype = c.c_void_p
    lib.StfArenaAlloc.argtypes = [c.c_void_p, c.c_size_t]
    lib.StfArenaAlloc.restype = c.c_void_p
    lib.StfArenaReset.argtypes = [c.c_void_p]
    lib.StfArenaBytesInUse.argtypes = [c.c_void_p]
    lib.StfArenaBytesInUse.restype = c.c_size_t
    lib.StfArenaBytesReserved.argtypes = [c.c_void_p]
    lib.StfArenaBytesReserved.restype = c.c_size_t
    lib.StfArenaDelete.argtypes = [c.c_void_p]

    i32p = c.POINTER(c.c_int32)
    lib.StfPruneToposort.argtypes = [c.c_int64, i32p, c.c_int64, i32p,
                                     c.c_int64, i32p]
    lib.StfPruneToposort.restype = c.c_int64

    lib.StfGraphNew.restype = c.c_void_p
    lib.StfGraphDelete.argtypes = [c.c_void_p]
    lib.StfGraphAddNode.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p,
                                    c.c_void_p]
    lib.StfGraphAddNode.restype = c.c_void_p
    lib.StfNodeAddInput.argtypes = [c.c_void_p, c.c_void_p, c.c_int]
    lib.StfNodeAddControlInput.argtypes = [c.c_void_p, c.c_void_p]
    lib.StfNodeSetDevice.argtypes = [c.c_void_p, c.c_char_p]
    lib.StfNodeSetAttrInt.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
    lib.StfNodeSetAttrFloat.argtypes = [c.c_void_p, c.c_char_p, c.c_double]
    lib.StfNodeSetAttrBool.argtypes = [c.c_void_p, c.c_char_p, c.c_int]
    lib.StfNodeSetAttrString.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p]
    lib.StfNodeAddOutput.argtypes = [c.c_void_p, c.c_char_p, c.c_int,
                                     c.POINTER(c.c_int64)]
    lib.StfGraphNumNodes.argtypes = [c.c_void_p]
    lib.StfGraphNumNodes.restype = c.c_int64
    lib.StfGraphToJson.argtypes = [c.c_void_p, c.POINTER(c.c_size_t),
                                   c.c_void_p]
    lib.StfGraphToJson.restype = c.c_void_p  # read via string_at with length
    lib.StfParseExamplesDense.argtypes = [
        c.POINTER(c.POINTER(c.c_uint8)), c.POINTER(c.c_size_t), c.c_int64,
        c.POINTER(c.c_char_p), c.POINTER(c.c_int32), c.POINTER(c.c_int64),
        c.c_int32, c.POINTER(c.c_void_p), c.POINTER(c.c_uint8), c.c_void_p]
    lib.StfParseExamplesDense.restype = c.c_int
    lib.StfParseExamplesRagged.argtypes = [
        c.POINTER(c.POINTER(c.c_uint8)), c.POINTER(c.c_size_t),
        c.c_int64, c.POINTER(c.c_char_p), c.POINTER(c.c_int32),
        c.POINTER(c.c_int64), c.c_int32, c.POINTER(c.c_void_p),
        c.POINTER(c.c_int64), c.c_void_p]
    lib.StfParseExamplesRagged.restype = c.c_int
    return lib


def _load():
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("STF_DISABLE_NATIVE"):
            return None
        path = _find_or_build()
        if path is None:
            return None
        _lib = _bind(ctypes.CDLL(path))
        return _lib


def available() -> bool:
    return _load() is not None


def version() -> str:
    lib = _load()
    return lib.StfVersion().decode() if lib else "unavailable"


class _Status:
    def __init__(self, lib):
        self._lib = lib
        self._h = lib.StfNewStatus()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._lib.StfDeleteStatus(self._h)
        return False

    @property
    def handle(self):
        return self._h

    def check(self):
        code = self._lib.StfGetCode(self._h)
        if code == 0:
            return
        from ..framework import errors

        msg = self._lib.StfMessage(self._h).decode()
        # StfCode uses the canonical TF error numbering, so user-data
        # errors (INVALID_ARGUMENT etc.) surface as the same exception
        # types the Python paths raise
        try:
            exc = errors.exception_type_from_error_code(code)
        except KeyError:
            raise errors.InternalError(None, None,
                                       f"[native:{code}] {msg}")
        raise exc(None, None, msg)


def crc32c(data: bytes) -> int:
    lib = _load()
    buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data)
    return lib.StfCrc32c(buf, len(data))


def masked_crc32c(data: bytes) -> int:
    lib = _load()
    buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data)
    return lib.StfMaskedCrc32c(buf, len(data))


def read_tfrecord_chunks(path: str, batch: int = 256,
                         buffer_size: Optional[int] = None
                         ) -> Iterator[List[bytes]]:
    """Iterate LISTS of records via the native reader — one yielded list
    per batched C call (the stf.data sharded-read stage moves these
    chunks through its ring buffers whole: one lock crossing per chunk).

    ``buffer_size`` sets the reader's zlib buffer via
    StfRecordReaderOpenBuffered when the built .so exports it. Records
    read before a mid-batch corruption are yielded first, then the
    error raises — matching the pure-Python reader's behavior.
    """
    lib = _load()
    with _Status(lib) as st:
        if buffer_size:
            h = lib.StfRecordReaderOpenBuffered(
                path.encode(), int(buffer_size), st.handle)
        else:
            h = lib.StfRecordReaderOpen(path.encode(), st.handle)
        st.check()
    try:
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        while True:
            buf = u8p()
            offs = u64p()
            # copy records + error out of the status BEFORE yielding, so
            # generator suspension cannot outlive the status/buffers
            err = None
            with _Status(lib) as st:
                n = lib.StfRecordReaderNextBatch(
                    h, batch, ctypes.byref(buf), ctypes.byref(offs),
                    st.handle)
                try:
                    st.check()
                except Exception as e:  # yield the good prefix, then raise
                    err = e
                records = []
                if n > 0:
                    raw = ctypes.string_at(buf, offs[n])
                    records = [raw[offs[i]:offs[i + 1]] for i in range(n)]
            if records:
                yield records
            if err is not None:
                raise err
            if n == 0:
                return
    finally:
        lib.StfRecordReaderClose(h)


def read_tfrecords(path: str, batch: int = 256) -> Iterator[bytes]:
    """Per-record view over ``read_tfrecord_chunks``."""
    for chunk in read_tfrecord_chunks(path, batch):
        yield from chunk


def parse_examples_dense(serialized, names, kinds, sizes):
    """Batch-parse serialized tf.Example protos into dense numpy arrays
    via the C++ fast parser (ref core/util/example_proto_fast_parsing.cc).

    serialized: sequence of bytes. names: feature names. kinds: 0=float32,
    1=int64 per feature. sizes: flat element count per feature.
    Returns (arrays, missing): arrays[f] is [n, sizes[f]] (float32/int64),
    missing is a bool [n, n_features] mask of absent features (caller
    applies FixedLenFeature defaults or raises).
    """
    lib = _load()
    if lib is None:
        raise RuntimeError("native runtime unavailable")
    n = len(serialized)
    nf = len(names)
    bufs = (ctypes.POINTER(ctypes.c_uint8) * n)()
    lens = (ctypes.c_size_t * n)()
    keepalive = []
    for i, s in enumerate(serialized):
        b = bytes(s)
        keepalive.append(b)
        bufs[i] = ctypes.cast(ctypes.c_char_p(b),
                              ctypes.POINTER(ctypes.c_uint8))
        lens[i] = len(b)
    cnames = (ctypes.c_char_p * nf)(*[x.encode() for x in names])
    ckinds = (ctypes.c_int32 * nf)(*kinds)
    csizes = (ctypes.c_int64 * nf)(*sizes)
    arrays = []
    outs = (ctypes.c_void_p * nf)()
    for f in range(nf):
        dt = np.float32 if kinds[f] == 0 else np.int64
        a = np.zeros((n, sizes[f]), dtype=dt)
        arrays.append(a)
        outs[f] = a.ctypes.data_as(ctypes.c_void_p)
    missing = np.zeros((n, nf), dtype=np.uint8)
    with _Status(lib) as st:
        rc = lib.StfParseExamplesDense(
            bufs, lens, n, cnames, ckinds, csizes, nf, outs,
            missing.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            st.handle)
        if rc:
            st.check()
    return arrays, missing.astype(bool)


def ragged_parse_available() -> bool:
    lib = _load()
    return lib is not None


def parse_examples_ragged(serialized, names, kinds, caps, pad_id=-1):
    """Batch-parse varlen tf.Example features into padded numpy arrays
    via the C++ fast parser (ISSUE 19: sparse id features feeding
    pooled embedding bags).

    serialized: sequence of bytes. names: feature names. kinds:
    0=float32, 1=int64 per feature. caps: per-feature padded row width.
    Returns (arrays, lengths): arrays[f] is [n, caps[f]] padded with
    ``pad_id`` (float features pad with 0.0); lengths is int64
    [n, n_features] holding each row's TRUE value count — entries may
    exceed caps[f] when the row was truncated (DATA.md contract: the
    caller clamps and accounts truncations; absent features are
    length 0).
    """
    lib = _load()
    if lib is None:
        raise RuntimeError("native ragged parser unavailable")
    n = len(serialized)
    nf = len(names)
    bufs = (ctypes.POINTER(ctypes.c_uint8) * n)()
    lens = (ctypes.c_size_t * n)()
    keepalive = []
    for i, s in enumerate(serialized):
        b = bytes(s)
        keepalive.append(b)
        bufs[i] = ctypes.cast(ctypes.c_char_p(b),
                              ctypes.POINTER(ctypes.c_uint8))
        lens[i] = len(b)
    cnames = (ctypes.c_char_p * nf)(*[x.encode() for x in names])
    ckinds = (ctypes.c_int32 * nf)(*kinds)
    ccaps = (ctypes.c_int64 * nf)(*caps)
    arrays = []
    outs = (ctypes.c_void_p * nf)()
    for f in range(nf):
        if kinds[f] == 0:
            a = np.zeros((n, caps[f]), dtype=np.float32)
        else:
            a = np.full((n, caps[f]), pad_id, dtype=np.int64)
        arrays.append(a)
        outs[f] = a.ctypes.data_as(ctypes.c_void_p)
    lengths = np.zeros((n, nf), dtype=np.int64)
    with _Status(lib) as st:
        rc = lib.StfParseExamplesRagged(
            bufs, lens, n, cnames, ckinds, ccaps, nf, outs,
            lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            st.handle)
        if rc:
            st.check()
    return arrays, lengths


def write_tfrecords(path: str, records: Sequence[bytes],
                    compression: int = 0) -> None:
    lib = _load()
    with _Status(lib) as st:
        h = lib.StfRecordWriterOpen(path.encode(), compression, st.handle)
        st.check()
    try:
        for rec in records:
            buf = (ctypes.c_uint8 * len(rec)).from_buffer_copy(rec)
            with _Status(lib) as st:
                lib.StfRecordWriterWrite(h, buf, len(rec), st.handle)
                st.check()
    finally:
        lib.StfRecordWriterClose(h)


class Arena:
    """Aligned host staging arena (ref BFC allocator role, see arena.cc)."""

    def __init__(self, block_bytes: int = 1 << 20):
        self._lib = _load()
        if self._lib is None:
            raise RuntimeError("native runtime unavailable")
        self._h = self._lib.StfArenaNew(block_bytes)

    def alloc_ndarray(self, shape, dtype=np.uint8) -> np.ndarray:
        """Arena-backed ndarray. The array keeps the arena alive; but
        ``reset()`` recycles the memory — arrays from before a reset must
        not be used after it."""
        dtype = np.dtype(dtype)
        nbytes = int(np.prod(shape)) * dtype.itemsize
        ptr = self._lib.StfArenaAlloc(self._h, max(nbytes, 1))
        if not ptr:
            raise MemoryError("arena allocation failed")
        buf = (ctypes.c_uint8 * nbytes).from_address(ptr)
        buf._arena = self  # keep-alive: ndarray.base -> ctypes buf -> arena
        return np.frombuffer(buf, dtype=dtype).reshape(shape)

    def reset(self):
        self._lib.StfArenaReset(self._h)

    @property
    def bytes_in_use(self) -> int:
        return self._lib.StfArenaBytesInUse(self._h)

    @property
    def bytes_reserved(self) -> int:
        return self._lib.StfArenaBytesReserved(self._h)

    def close(self):
        if self._h:
            self._lib.StfArenaDelete(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class ArenaPool:
    """Rotating pool of arenas for host→device staging buffers (the
    pinned-staging role of the reference's GPU host allocator,
    ref core/common_runtime/gpu/gpu_host_allocator.h).

    ``stage(x)`` copies a numpy batch (array / tuple / dict) into
    64-byte-aligned arena memory. A slot is recycled only after the
    device transfers recorded against it via ``mark_in_flight`` have
    completed (``jax.block_until_ready`` before reset) — the recycle
    barrier, not a timing assumption. NOT safe with backends whose
    device_put zero-copy ALIASES host buffers (CPU does, measured): the
    alias outlives any barrier. Callers must gate on the backend."""

    def __init__(self, slots: int = 4, block_bytes: int = 1 << 22):
        self._arenas = [Arena(block_bytes) for _ in range(slots)]
        self._inflight: List = [None] * slots
        self._i = 0
        self._last_slot = 0
        # acquire() runs in pipeline stage threads while mark_in_flight
        # runs in the transfer thread; rotation must be atomic
        self._rotate_lock = _sync.Lock("native/arena_rotate",
                                       rank=_sync.LEAF)

    def acquire(self):
        """Claim the next slot for direct batch assembly (the stf.data
        batch stage stacks straight into it — no later staging copy).
        Blocks until the slot's previously recorded device transfer
        completes, then resets the arena. Returns ``(slot_id, arena)``;
        pass slot_id back to ``mark_in_flight``. The CALLER must bound
        batches-in-flight below the slot count (prefetch ring capacity
        + 2 < slots) or a queued batch's memory would be recycled."""
        import jax

        with self._rotate_lock:
            slot = self._i
            self._i = (self._i + 1) % len(self._arenas)
        pending = self._inflight[slot]
        if pending is not None:
            # the DMA out of this slot's memory must finish before reuse
            jax.block_until_ready(pending)
            self._inflight[slot] = None
        a = self._arenas[slot]
        a.reset()
        return slot, a

    def _next(self) -> Arena:
        slot, a = self.acquire()
        self._last_slot = slot
        return a

    def stage(self, x):
        arena = self._next()

        def copy(a):
            if isinstance(a, tuple):
                return tuple(copy(e) for e in a)
            if isinstance(a, dict):
                return {k: copy(e) for k, e in a.items()}
            a = np.asarray(a)
            if a.dtype.hasobject or a.dtype.kind in "USV":
                return a  # strings stay host-side; nothing to stage
            out = arena.alloc_ndarray(a.shape, a.dtype)
            np.copyto(out, a)
            return out

        return copy(x)

    def mark_in_flight(self, device_arrays, slot=None) -> None:
        """Record the device arrays produced from a staged slot (the
        last ``stage()`` slot when ``slot`` is None, else an explicit
        ``acquire()`` slot id); their readiness gates recycling."""
        self._inflight[self._last_slot if slot is None else slot] = \
            device_arrays

    def close(self):
        for a in self._arenas:
            a.close()


def prune_toposort(n_nodes: int, edges: np.ndarray,
                   targets: Sequence[int]) -> Optional[List[int]]:
    """Topo order of dependency-ancestors of ``targets``.

    edges: int32 array (n_edges, 2) of (src, dst) = dst depends on src.
    Returns None on cycle (caller raises with graph context).
    """
    lib = _load()
    edges = np.ascontiguousarray(edges, dtype=np.int32)
    tg = np.ascontiguousarray(targets, dtype=np.int32)
    out = np.empty(n_nodes, dtype=np.int32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    n = lib.StfPruneToposort(
        n_nodes, edges.ctypes.data_as(i32p), len(edges),
        tg.ctypes.data_as(i32p), len(tg), out.ctypes.data_as(i32p))
    if n < 0:
        return None
    return out[:n].tolist()


_session_lib = None
_session_tried = False
# own lock: the session-lib build can take minutes and must not stall
# unrelated native calls serialized on _lock
_session_lock = _sync.Lock("native/session_lib_load",
                           rank=_sync.RANK_LIFECYCLE,
                           blocking_ok=True)


def load_session_lib():
    """libstf_session.so: the run-from-C entry points (StfSessionLoad/
    Run/Close, ref TF_SessionRun). Separate from libstf_runtime.so
    because it links libpython (the shim embeds CPython to drive the XLA
    executable). Returns the ctypes lib or None."""
    global _session_lib, _session_tried
    with _session_lock:
        if _session_lib is not None or _session_tried:
            return _session_lib
        _session_tried = True
        if os.environ.get("STF_DISABLE_NATIVE"):
            return None
        path = os.path.join(_CC_DIR, "libstf_session.so")
        if not os.path.exists(path):
            try:
                subprocess.run(["make", "-C", _CC_DIR, "session"],
                               check=True, capture_output=True, timeout=240)
            except Exception:
                return None
        if not os.path.exists(path):
            return None
        try:
            lib = ctypes.CDLL(path, mode=ctypes.RTLD_GLOBAL)
        except OSError:
            return None
        c = ctypes
        lib.StfSessionLoad.argtypes = [c.c_char_p, c.c_void_p]
        lib.StfSessionLoad.restype = c.c_void_p
        lib.StfSessionClose.argtypes = [c.c_void_p]
        lib.StfSessionRun.argtypes = [
            c.c_void_p, c.POINTER(c.c_char_p), c.c_void_p, c.c_int,
            c.POINTER(c.c_char_p), c.c_int, c.c_void_p, c.c_void_p]
        lib.StfTensorOutRelease.argtypes = [c.c_void_p]
        _session_lib = lib
        return lib


class CTensorSpec(ctypes.Structure):
    """Mirror of StfTensorSpec (runtime_cc/session_c.cc)."""
    _fields_ = [("dtype", ctypes.c_char_p), ("rank", ctypes.c_int),
                ("dims", ctypes.POINTER(ctypes.c_int64)),
                ("data", ctypes.c_void_p), ("nbytes", ctypes.c_size_t)]


class CTensorOut(ctypes.Structure):
    """Mirror of StfTensorOut (runtime_cc/session_c.cc)."""
    _fields_ = [("dtype", ctypes.c_char * 16), ("rank", ctypes.c_int),
                ("dims", ctypes.c_int64 * 8),
                ("data", ctypes.c_void_p), ("nbytes", ctypes.c_size_t)]


class CGraph:
    """Graph construction through the C API (ref TF_Graph); serializes to
    GraphDef-JSON consumable by stf.import_graph_def."""

    def __init__(self):
        self._lib = _load()
        if self._lib is None:
            raise RuntimeError("native runtime unavailable")
        self._h = self._lib.StfGraphNew()

    def add_node(self, op_type: str, name: str):
        with _Status(self._lib) as st:
            node = self._lib.StfGraphAddNode(self._h, op_type.encode(),
                                             name.encode(), st.handle)
            st.check()
        return node

    def add_input(self, node, src, out_index=0):
        self._lib.StfNodeAddInput(node, src, out_index)

    def add_control_input(self, node, src):
        self._lib.StfNodeAddControlInput(node, src)

    def set_attr(self, node, key, value):
        k = key.encode()
        if isinstance(value, bool):
            self._lib.StfNodeSetAttrBool(node, k, int(value))
        elif isinstance(value, int):
            self._lib.StfNodeSetAttrInt(node, k, value)
        elif isinstance(value, float):
            self._lib.StfNodeSetAttrFloat(node, k, value)
        elif isinstance(value, str):
            self._lib.StfNodeSetAttrString(node, k, value.encode())
        else:
            raise TypeError(f"unsupported C attr type {type(value)}")

    def add_output(self, node, dtype_name: str, shape=None):
        if shape is None:
            self._lib.StfNodeAddOutput(node, dtype_name.encode(), -1, None)
        else:
            dims = (ctypes.c_int64 * len(shape))(
                *[-1 if d is None else d for d in shape])
            self._lib.StfNodeAddOutput(node, dtype_name.encode(),
                                       len(shape), dims)

    @property
    def num_nodes(self) -> int:
        return self._lib.StfGraphNumNodes(self._h)

    def to_json(self) -> str:
        n = ctypes.c_size_t()
        with _Status(self._lib) as st:
            p = self._lib.StfGraphToJson(self._h, ctypes.byref(n), st.handle)
            st.check()
        return ctypes.string_at(p, n.value).decode()

    def close(self):
        if self._h:
            self._lib.StfGraphDelete(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
