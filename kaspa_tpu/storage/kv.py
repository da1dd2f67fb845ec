"""Persistent KV store: ctypes bindings over the native C++ engine.

The storage layer counterpart of the reference's kaspa-database
(database/src/: DB + DbWriter/BatchDbWriter + prefixed stores).  The C++
engine (native/kvstore/kvstore.cc) provides crash-consistent CRC-framed
atomic write batches over an append log with in-memory index; this module
adds the typed prefixed-store access layer (registry.rs/access.rs shape).

Builds the shared library on first use (g++, cached beside the source
under a name that carries the sources' digest — utils/nativebuild.py);
a pure-python fallback engine keeps tests running without a toolchain.
"""

from __future__ import annotations

import ctypes
import os
import struct
import threading

from kaspa_tpu.utils import nativebuild
from kaspa_tpu.utils.sync import ranked_lock

from kaspa_tpu.observability.core import REGISTRY
from kaspa_tpu.resilience.faults import FAULTS, FaultInjected

_JOURNAL_REPAIRS = REGISTRY.counter(
    "kv_journal_repairs", help="torn log tails truncated back to the last valid frame on replay"
)
_TORN_BYTES = REGISTRY.counter("kv_journal_torn_bytes", help="garbage bytes discarded by journal repair")

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native", "kvstore")
_BUILD_LOCK = ranked_lock("storage.build")


def _build_native():
    with _BUILD_LOCK:
        return nativebuild.build(
            os.path.join(_NATIVE_DIR, "kvstore.cc"), "kvstore", deps=(os.path.join(_NATIVE_DIR, "arena.h"),)
        )


# keys/values are raw binary (embedded NULs are the norm for hashes), so the
# callback must take void* — c_char_p would NUL-truncate before string_at
_ITER_CB = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_uint32, ctypes.c_void_p, ctypes.c_uint32, ctypes.c_void_p)


class _NativeEngine:
    def __init__(self, path: str):
        self.path = path
        lib = ctypes.CDLL(_build_native())
        lib.kv_open.restype = ctypes.c_void_p
        lib.kv_open.argtypes = [ctypes.c_char_p]
        lib.kv_close.argtypes = [ctypes.c_void_p]
        lib.kv_put.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32, ctypes.c_char_p, ctypes.c_uint32]
        lib.kv_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
        lib.kv_get.restype = ctypes.c_int64
        lib.kv_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32, ctypes.c_char_p, ctypes.c_uint32]
        lib.kv_batch_begin.argtypes = [ctypes.c_void_p]
        lib.kv_batch_commit.argtypes = [ctypes.c_void_p]
        lib.kv_len.restype = ctypes.c_uint64
        lib.kv_len.argtypes = [ctypes.c_void_p]
        lib.kv_iterate.argtypes = [ctypes.c_void_p, _ITER_CB, ctypes.c_void_p]
        lib.kv_iterate_prefix.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32, ctypes.c_int, _ITER_CB, ctypes.c_void_p,
        ]
        lib.kv_count_prefix.restype = ctypes.c_uint64
        lib.kv_count_prefix.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
        lib.kv_mem_stats.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64)]
        lib.kv_compact.argtypes = [ctypes.c_void_p]
        self._lib = lib
        self._h = lib.kv_open(path.encode())
        if not self._h:
            raise IOError(f"failed to open kv store at {path}")

    def put(self, key: bytes, value: bytes):
        rc = self._lib.kv_put(self._h, key, len(key), value, len(value))
        if rc != 0:
            raise IOError(f"kv_put failed: {rc}")

    def get(self, key: bytes):
        n = self._lib.kv_get(self._h, key, len(key), None, 0)
        if n < 0:
            return None
        buf = ctypes.create_string_buffer(n)
        rc = self._lib.kv_get(self._h, key, len(key), buf, n)
        if rc < 0:
            # values live on disk now: a failed pread must raise, never
            # hand zero-filled bytes to a consensus decoder
            raise IOError(f"kv_get read failed: {rc}")
        return buf.raw

    def has(self, key: bytes) -> bool:
        # length-probe only: no disk read
        return self._lib.kv_get(self._h, key, len(key), None, 0) >= 0

    def delete(self, key: bytes):
        self._lib.kv_delete(self._h, key, len(key))

    def batch_begin(self):
        rc = self._lib.kv_batch_begin(self._h)
        if rc != 0:
            raise IOError(f"kv_batch_begin failed: {rc}")

    def batch_commit(self):
        # fires BEFORE the native commit: the engine's own crash-safety
        # (CRC-framed atomic batch) must absorb the abandoned batch
        FAULTS.fire("storage.commit")
        rc = self._lib.kv_batch_commit(self._h)
        if rc != 0:
            raise IOError(f"kv_batch_commit failed: {rc}")

    def __len__(self):
        return self._lib.kv_len(self._h)

    def items(self):
        out = []

        def cb(k, klen, v, vlen, _ctx):
            out.append((ctypes.string_at(k, klen), ctypes.string_at(v, vlen)))

        self._lib.kv_iterate(self._h, _ITER_CB(cb), None)
        return out

    def items_prefix(self, prefix: bytes):
        """Ordered (key-without-prefix, value) pairs under ``prefix``."""
        n = len(prefix)
        out = []

        def cb(k, klen, v, vlen, _ctx):
            out.append((ctypes.string_at(k, klen)[n:], ctypes.string_at(v, vlen) if vlen else b""))

        self._lib.kv_iterate_prefix(self._h, prefix, n, 1, _ITER_CB(cb), None)
        return out

    def keys_prefix(self, prefix: bytes):
        """Ordered keys (without the prefix) under ``prefix`` — no disk reads."""
        n = len(prefix)
        out = []

        def cb(k, klen, _v, _vlen, _ctx):
            out.append(ctypes.string_at(k, klen)[n:])

        self._lib.kv_iterate_prefix(self._h, prefix, n, 0, _ITER_CB(cb), None)
        return out

    def count_prefix(self, prefix: bytes) -> int:
        return self._lib.kv_count_prefix(self._h, prefix, len(prefix))

    def mem_stats(self) -> dict:
        """Slab-arena stats of the resident index (the kaspa-alloc
        visibility story: allocator behavior is observable)."""
        out = (ctypes.c_uint64 * 4)()
        self._lib.kv_mem_stats(self._h, out)
        return {
            "arena_slabs": out[0],
            "arena_reserved_bytes": out[1],
            "arena_in_use_bytes": out[2],
            "arena_large_allocs": out[3],
        }

    def compact(self):
        rc = self._lib.kv_compact(self._h)
        if rc != 0:
            raise IOError(f"kv_compact failed: {rc}")

    def close(self):
        if self._h:
            self._lib.kv_close(self._h)
            self._h = None


class _PythonEngine:
    """Fallback with the same log format semantics (non-durable simplification:
    full-file rewrite on close/compact, in-memory otherwise)."""

    def __init__(self, path: str):
        self.path = path
        self.index: dict[bytes, bytes] = {}
        self._batch = False
        if os.path.exists(path):
            self._replay()
        self._log = open(path, "ab")
        self._pending = bytearray()

    def _replay(self):
        import zlib

        with open(self.path, "rb") as f:
            data = f.read()
        off = 0
        while off + 12 <= len(data):
            if data[off : off + 4] != b"KBAT":
                break
            (plen,) = struct.unpack_from("<I", data, off + 4)
            end = off + 8 + plen
            if end + 4 > len(data):
                break
            payload = data[off + 8 : end]
            (crc,) = struct.unpack_from("<I", data, end)
            if zlib.crc32(payload) != crc:
                break
            p = 0
            while p < plen:
                op = payload[p]
                klen, vlen = struct.unpack_from("<II", payload, p + 1)
                p += 9
                key = payload[p : p + klen]
                p += klen
                if op == 0:
                    self.index[key] = payload[p : p + vlen]
                else:
                    self.index.pop(key, None)
                p += vlen
            off = end + 4
        if off < len(data):
            # torn tail (crash mid-frame): truncate back to the last valid
            # frame so the append handle extends the *valid* prefix —
            # without this, later frames land after garbage and are
            # silently orphaned on the next replay
            _JOURNAL_REPAIRS.inc()
            _TORN_BYTES.inc(len(data) - off)
            with open(self.path, "r+b") as f:
                f.truncate(off)

    def put(self, key, value):
        self._pending += bytes([0]) + struct.pack("<II", len(key), len(value)) + key + value
        self.index[key] = value
        if not self._batch:
            self._flush()

    def delete(self, key):
        self._pending += bytes([1]) + struct.pack("<II", len(key), 0) + key
        self.index.pop(key, None)
        if not self._batch:
            self._flush()

    def _flush(self):
        import zlib

        if not self._pending:
            return
        payload = bytes(self._pending)
        frame = b"KBAT" + struct.pack("<I", len(payload)) + payload + struct.pack("<I", zlib.crc32(payload))
        act = FAULTS.fire("storage.flush")
        if act is not None and act.mode == "partial":
            # simulated crash mid-write: a deterministic prefix of the frame
            # hits the disk, the rest never does.  _pending is retained and
            # the torn tail is left behind — replay truncates it on reopen.
            cut = act.rng.randrange(1, len(frame))
            self._log.write(frame[:cut])
            self._log.flush()
            raise FaultInjected("storage.flush", act.hit, act.mode)
        start = self._log.tell()
        try:
            self._log.write(frame)
            self._log.flush()
        except Exception:
            # atomic append: a failed/short write must not leave a torn
            # frame for the *next* flush to bury — roll the file back to
            # the pre-write offset and keep _pending for a retry
            try:
                self._log.seek(start)
                self._log.truncate(start)
            except OSError:
                pass
            raise
        self._pending = bytearray()

    def get(self, key):
        return self.index.get(key)

    def has(self, key: bytes) -> bool:
        return key in self.index

    def batch_begin(self):
        self._batch = True

    def batch_commit(self):
        # same placement as the native engine: the abandoned batch must be
        # absorbed by the CRC frame discipline, not half-applied
        FAULTS.fire("storage.commit")
        self._batch = False
        self._flush()

    def __len__(self):
        return len(self.index)

    def items(self):
        return list(self.index.items())

    def items_prefix(self, prefix: bytes):
        n = len(prefix)
        return sorted((k[n:], v) for k, v in self.index.items() if k.startswith(prefix))

    def keys_prefix(self, prefix: bytes):
        n = len(prefix)
        return sorted(k[n:] for k in self.index if k.startswith(prefix))

    def count_prefix(self, prefix: bytes) -> int:
        return sum(1 for k in self.index if k.startswith(prefix))

    def mem_stats(self) -> dict:
        return {"arena_slabs": 0, "arena_reserved_bytes": 0, "arena_in_use_bytes": 0, "arena_large_allocs": 0}

    def compact(self):
        pass

    def close(self):
        self._flush()
        self._log.close()


def open_store(path: str, native: bool = True):
    if native:
        try:
            return _NativeEngine(path)
        except Exception:
            pass
    return _PythonEngine(path)


class KvStore:
    """Typed prefixed access (database/src/registry.rs + access.rs shape)."""

    def __init__(self, path: str, native: bool = True):
        self.path = path
        self.engine = open_store(path, native)

    def prefixed(self, prefix: bytes) -> "PrefixedStore":
        return PrefixedStore(self.engine, prefix)

    def batch(self):
        return _Batch(self.engine)

    def mem_stats(self) -> dict:
        return self.engine.mem_stats()

    def size_on_disk(self) -> int:
        try:
            return os.path.getsize(self.path)
        except OSError:
            return 0

    def close(self):
        self.engine.close()


class PrefixedStore:
    def __init__(self, engine, prefix: bytes):
        self.engine = engine
        self.prefix = prefix

    def put(self, key: bytes, value: bytes):
        self.engine.put(self.prefix + key, value)

    def get(self, key: bytes):
        return self.engine.get(self.prefix + key)

    def delete(self, key: bytes):
        self.engine.delete(self.prefix + key)

    def items(self):
        return self.engine.items_prefix(self.prefix)

    def keys(self):
        return self.engine.keys_prefix(self.prefix)

    def count(self) -> int:
        return self.engine.count_prefix(self.prefix)


class _Batch:
    """Atomic write batch with a real abort path.

    Mutations are buffered python-side and only touch the engine inside a
    begin/commit frame on successful exit — an exception inside the `with`
    leaves both the engine index and the log completely untouched
    (BatchDbWriter semantics, database/src/writer.rs)."""

    def __init__(self, engine):
        self.engine = engine
        self._ops: list[tuple] = []

    def put(self, key: bytes, value: bytes):
        self._ops.append(("put", key, value))

    def delete(self, key: bytes):
        self._ops.append(("del", key, None))

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None and self._ops:
            self.engine.batch_begin()
            try:
                for op, key, value in self._ops:
                    if op == "put":
                        self.engine.put(key, value)
                    else:
                        self.engine.delete(key)
            finally:
                self.engine.batch_commit()
        self._ops.clear()
        return False
