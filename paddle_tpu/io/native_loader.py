"""Python binding for the native runtime (csrc/native_runtime.cpp).

Builds the shared library with g++ on first use (cached beside the source)
and exposes:
  * NativePrefetcher — background-thread batch prefetch through the C++
    bounded byte-queue; ctypes releases the GIL around pushes/pops so the
    producer's numpy work and the consumer's device feed overlap.
  * HostArena — size-bucketed staging allocator.
Falls back cleanly (ImportError) when no compiler is available; DataLoader
then uses its pure-Python thread prefetcher.
"""
from __future__ import annotations

import ctypes
import os
import pickle
import subprocess
import tempfile
import threading

import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "csrc", "native_runtime.cpp")
_SO = os.path.join(os.path.dirname(_SRC), "libpaddle_tpu_native.so")

_lib = None
_lib_lock = threading.Lock()


def _build():
    # Several processes may find no library at once (xdist workers, spawned
    # loader workers): each compiles to a name of its own and renames it
    # into place, so none ever loads a half-written file.
    cxx = os.environ.get("CXX", "g++")
    fd, tmp = tempfile.mkstemp(prefix=".libpaddle_tpu_native.", suffix=".so",
                               dir=os.path.dirname(_SO))
    os.close(fd)
    try:
        cmd = [cxx, "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp,
               "-pthread"]
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, _SO)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def get_lib():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if not os.path.exists(_SO) or (os.path.getmtime(_SO)
                                       < os.path.getmtime(_SRC)):
            _build()
        lib = ctypes.CDLL(_SO)
        lib.ptq_create.restype = ctypes.c_void_p
        lib.ptq_create.argtypes = [ctypes.c_size_t, ctypes.c_size_t]
        lib.ptq_push.restype = ctypes.c_int
        lib.ptq_push.argtypes = [ctypes.c_void_p,
                                 ctypes.POINTER(ctypes.c_uint8),
                                 ctypes.c_size_t]
        lib.ptq_peek_size.restype = ctypes.c_int64
        lib.ptq_peek_size.argtypes = [ctypes.c_void_p]
        lib.ptq_pop.restype = ctypes.c_int64
        lib.ptq_pop.argtypes = [ctypes.c_void_p,
                                ctypes.POINTER(ctypes.c_uint8),
                                ctypes.c_size_t]
        lib.ptq_pop_timed.restype = ctypes.c_int64
        lib.ptq_pop_timed.argtypes = [ctypes.c_void_p,
                                      ctypes.POINTER(ctypes.c_uint8),
                                      ctypes.c_size_t, ctypes.c_int64]
        lib.ptq_push_tagged.restype = ctypes.c_int
        lib.ptq_push_tagged.argtypes = [ctypes.c_void_p, ctypes.c_uint8,
                                        ctypes.POINTER(ctypes.c_uint8),
                                        ctypes.c_size_t]
        lib.ptq_size.restype = ctypes.c_int64
        lib.ptq_size.argtypes = [ctypes.c_void_p]
        lib.ptq_close.argtypes = [ctypes.c_void_p]
        lib.ptq_destroy.argtypes = [ctypes.c_void_p]
        lib.arena_create.restype = ctypes.c_void_p
        lib.arena_create.argtypes = [ctypes.c_size_t]
        lib.arena_alloc.restype = ctypes.c_void_p
        lib.arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
        lib.arena_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.arena_reserved_bytes.restype = ctypes.c_int64
        lib.arena_reserved_bytes.argtypes = [ctypes.c_void_p]
        lib.arena_destroy.argtypes = [ctypes.c_void_p]
        lib.ms_scan.restype = ctypes.c_longlong
        lib.ms_scan.argtypes = [ctypes.POINTER(ctypes.c_char),
                                ctypes.c_longlong, ctypes.c_int,
                                ctypes.POINTER(ctypes.c_longlong)]
        lib.ms_fill.restype = ctypes.c_int
        lib.ms_fill.argtypes = [ctypes.POINTER(ctypes.c_char),
                                ctypes.c_longlong, ctypes.c_int,
                                ctypes.POINTER(ctypes.c_uint8),
                                ctypes.POINTER(ctypes.c_longlong),
                                ctypes.POINTER(ctypes.c_void_p),
                                ctypes.c_longlong]
        _lib = lib
        return lib


def parse_multislot(data, slot_meta):
    """Parse a MultiSlot text buffer natively into padded per-slot arrays.

    data: bytes of slot-formatted lines. slot_meta: [(name, np_dtype,
    fixed_width_or_None), ...] as produced by fluid.dataset_feed's
    _slot_meta. Returns {name: [n_samples, width] ndarray}; raises
    ValueError on malformed input (same contract as the Python parser).
    """
    lib = get_lib()
    n_slots = len(slot_meta)
    if n_slots == 0:
        raise ValueError("no slots configured (set_use_var first)")
    # zero-copy when handed a bytearray: terminate IN PLACE (strtol/
    # strtof need it) instead of materializing a second dataset-sized
    # buffer
    if isinstance(data, bytearray):
        if not data.endswith(b"\0"):
            data.append(0)
    else:
        data = bytearray(data) + b"\0"
    length = len(data) - 1
    cbuf = (ctypes.c_char * len(data)).from_buffer(data)
    widths = (ctypes.c_longlong * n_slots)()
    n = lib.ms_scan(cbuf, length, n_slots, widths)
    if n < 0:
        raise ValueError("malformed MultiSlot data (token/slot mismatch)")
    out = {}
    ptrs = (ctypes.c_void_p * n_slots)()
    is_float = (ctypes.c_uint8 * n_slots)()
    final_w = (ctypes.c_longlong * n_slots)()
    for s, (name, dtype, fixed) in enumerate(slot_meta):
        w = int(widths[s])
        if fixed:
            w = max(w, int(fixed))  # parse buffer must hold every token
        is_float[s] = 1 if np.dtype(dtype) == np.float32 else 0
        arr = np.zeros((int(n), w),
                       np.float32 if is_float[s] else np.int64)
        out[name] = arr
        final_w[s] = w
        ptrs[s] = arr.ctypes.data_as(ctypes.c_void_p)
    if n and lib.ms_fill(cbuf, length, n_slots, is_float, final_w,
                         ptrs, n) != 0:
        raise ValueError("malformed MultiSlot data (value parse failed)")
    for s, (name, dtype, fixed) in enumerate(slot_meta):
        if fixed and out[name].shape[1] != int(fixed):
            out[name] = out[name][:, : int(fixed)]
    return out


def _serialize_batch(batch):
    """Split a batch into (metadata, concatenated raw bytes). Tensors/ndarrays
    travel as raw buffers; everything else via pickle in the metadata."""
    from ..core.tensor import Tensor
    arrays = []

    def strip(obj):
        if isinstance(obj, Tensor):
            a = obj.numpy()
            arrays.append(np.ascontiguousarray(a))
            return ("__arr__", len(arrays) - 1, a.dtype.str, a.shape, True)
        if isinstance(obj, np.ndarray):
            arrays.append(np.ascontiguousarray(obj))
            return ("__arr__", len(arrays) - 1, obj.dtype.str, obj.shape, False)
        if isinstance(obj, (list, tuple)):
            return type(obj)(strip(o) for o in obj)
        if isinstance(obj, dict):
            return {k: strip(v) for k, v in obj.items()}
        return obj

    meta = strip(batch)
    payload = b"".join(a.tobytes() for a in arrays)
    header = pickle.dumps((meta, [a.nbytes for a in arrays]))
    return (len(header).to_bytes(8, "little") + header + payload)


def _deserialize_batch(buf):
    from ..core.tensor import Tensor
    hlen = int.from_bytes(buf[:8], "little")
    meta, sizes = pickle.loads(bytes(buf[8:8 + hlen]))
    offset = 8 + hlen
    arrays = []
    for n in sizes:
        arrays.append(bytes(buf[offset:offset + n]))
        offset += n

    def rebuild(obj):
        if isinstance(obj, tuple) and len(obj) == 5 and obj[0] == "__arr__":
            _, idx, dtype, shape, is_tensor = obj
            a = np.frombuffer(arrays[idx], dtype=np.dtype(dtype)).reshape(shape)
            return Tensor(a) if is_tensor else a
        if isinstance(obj, tuple):
            return tuple(rebuild(o) for o in obj)
        if isinstance(obj, list):
            return [rebuild(o) for o in obj]
        if isinstance(obj, dict):
            return {k: rebuild(v) for k, v in obj.items()}
        return obj

    return rebuild(meta)


class NativePrefetcher:
    """Iterate `source_iter` on a background thread; batches flow through the
    C++ bounded queue as raw bytes."""

    def __init__(self, source_iter, depth=4, capacity_mb=512):
        self._lib = get_lib()
        self._q = self._lib.ptq_create(depth, capacity_mb << 20)
        self._exc = None
        self._thread = threading.Thread(target=self._producer,
                                        args=(source_iter,), daemon=True)
        self._thread.start()

    def _producer(self, source_iter):
        try:
            for batch in source_iter:
                data = _serialize_batch(batch)
                buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data)
                if self._lib.ptq_push(self._q, buf, len(data)) != 0:
                    return
        except Exception as e:  # surface on the consumer side
            self._exc = e
        finally:
            self._lib.ptq_close(self._q)

    def __iter__(self):
        try:
            while True:
                n = self._lib.ptq_peek_size(self._q)
                if n < 0:
                    break
                out = (ctypes.c_uint8 * n)()
                got = self._lib.ptq_pop(self._q, out, n)
                if got < 0:
                    break
                yield _deserialize_batch(memoryview(out))
            if self._exc is not None:
                raise self._exc
        finally:
            self._lib.ptq_destroy(self._q)
            self._q = None


class HostArena:
    """Size-bucketed host staging allocator (ref role: fluid memory pools)."""

    def __init__(self, limit_bytes=4 << 30):
        self._lib = get_lib()
        self._a = self._lib.arena_create(limit_bytes)

    def alloc(self, nbytes) -> int:
        p = self._lib.arena_alloc(self._a, nbytes)
        if not p:
            raise MemoryError(f"arena alloc of {nbytes} failed")
        return p

    def free(self, ptr: int):
        self._lib.arena_free(self._a, ptr)

    def buffer(self, nbytes):
        """numpy view over an arena block; call free(view.ctypes.data)."""
        ptr = self.alloc(nbytes)
        return np.ctypeslib.as_array(
            ctypes.cast(ptr, ctypes.POINTER(ctypes.c_uint8)), (nbytes,)), ptr

    @property
    def reserved_bytes(self):
        return self._lib.arena_reserved_bytes(self._a)

    def __del__(self):
        try:
            self._lib.arena_destroy(self._a)
        except Exception:
            pass
