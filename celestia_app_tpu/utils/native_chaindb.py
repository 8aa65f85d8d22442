"""ctypes binding for the native storage engine (native/chaindb.cc).

The engine is a segmented append-only record store — (stream, height) ->
payload with CRC framing, torn-tail recovery, rollback/prune tombstones and
dead-segment GC. chain/storage.py layers the commit semantics (delta
chains, snapshot cadence, prune windows) on top; see that module for the
reference parity notes (tm-db/IAVL + celestia-core block store,
app/app.go:427-435).

``load()`` builds the .so via the native Makefile on first use (cheap,
dependency-tracked, safe when several processes do so at once — see
utils/native_build.py) and raises RuntimeError when no toolchain is
available — callers fall back to the pure-Python file backend.
"""

from __future__ import annotations

import ctypes
import os

from celestia_app_tpu.utils import native_build

NATIVE_DIR = native_build.NATIVE_DIR
LIB = os.path.join(NATIVE_DIR, "libchaindb.so")

_lib = None


def load() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    # ALWAYS run make (a no-op when fresh): its dependency tracking is what
    # keeps a stale .so from silently serving an outdated engine after
    # chaindb.cc changes. Only a missing .so makes a failed build fatal.
    try:
        native_build.make("libchaindb.so", native_dir=NATIVE_DIR,
                          timeout=120)
    except Exception as e:
        if not os.path.exists(LIB):
            raise RuntimeError(f"cannot build libchaindb.so: {e}")
    lib = ctypes.CDLL(LIB)
    lib.cdb_open.restype = ctypes.c_void_p
    lib.cdb_open.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p,
                             ctypes.c_int]
    lib.cdb_put.restype = ctypes.c_int
    lib.cdb_put.argtypes = [ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint64,
                            ctypes.c_char_p, ctypes.c_uint32]
    lib.cdb_tomb_at.restype = ctypes.c_int
    lib.cdb_tomb_at.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                ctypes.c_uint64]
    lib.cdb_tomb_above.restype = ctypes.c_int
    lib.cdb_tomb_above.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.cdb_sync.restype = ctypes.c_int
    lib.cdb_sync.argtypes = [ctypes.c_void_p]
    lib.cdb_get_len.restype = ctypes.c_int64
    lib.cdb_get_len.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                ctypes.c_uint64]
    lib.cdb_get.restype = ctypes.c_int
    lib.cdb_get.argtypes = [ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint64,
                            ctypes.c_char_p, ctypes.c_uint32]
    lib.cdb_latest.restype = ctypes.c_int64
    lib.cdb_latest.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.cdb_count.restype = ctypes.c_uint64
    lib.cdb_count.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.cdb_heights.restype = ctypes.c_int64
    lib.cdb_heights.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                ctypes.POINTER(ctypes.c_uint64),
                                ctypes.c_uint64]
    lib.cdb_segments.restype = ctypes.c_uint64
    lib.cdb_segments.argtypes = [ctypes.c_void_p]
    lib.cdb_close.restype = None
    lib.cdb_close.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


def available() -> bool:
    try:
        load()
        return True
    except (RuntimeError, OSError):
        # RuntimeError: no toolchain. OSError: a .so exists but cannot load
        # (wrong arch, truncated build) — fall back to the file engine
        # rather than wedging every ChainDB open.
        return False


class NativeLog:
    """One open chaindb directory. Thin, typed veneer over the C ABI."""

    def __init__(self, directory: str, *, read_only: bool = False):
        lib = load()
        err = ctypes.create_string_buffer(256)
        self._h = lib.cdb_open(directory.encode(), 1 if read_only else 0,
                               err, len(err))
        if not self._h:
            raise IOError(f"chaindb open failed: {err.value.decode()}")
        self._lib = lib

    def put(self, stream: int, height: int, payload: bytes) -> None:
        if self._lib.cdb_put(self._h, stream, height, payload,
                             len(payload)) != 0:
            raise IOError("chaindb put failed")

    def get(self, stream: int, height: int) -> bytes | None:
        n = self._lib.cdb_get_len(self._h, stream, height)
        if n < 0:
            return None
        buf = ctypes.create_string_buffer(max(int(n), 1))
        rc = self._lib.cdb_get(self._h, stream, height, buf, int(n))
        if rc < 0:
            raise IOError(f"chaindb get failed (rc={rc})")
        return buf.raw[:rc]

    def tomb_at(self, stream: int, height: int) -> None:
        if self._lib.cdb_tomb_at(self._h, stream, height) != 0:
            raise IOError("chaindb tomb_at failed")

    def tomb_above(self, height: int) -> None:
        if self._lib.cdb_tomb_above(self._h, height) != 0:
            raise IOError("chaindb tomb_above failed")

    def sync(self) -> None:
        if self._lib.cdb_sync(self._h) != 0:
            raise IOError("chaindb sync failed")

    def latest(self, stream: int) -> int | None:
        h = self._lib.cdb_latest(self._h, stream)
        return None if h < 0 else int(h)

    def heights(self, stream: int) -> list[int]:
        n = int(self._lib.cdb_count(self._h, stream))
        if n == 0:
            return []
        arr = (ctypes.c_uint64 * n)()
        got = self._lib.cdb_heights(self._h, stream, arr, n)
        return sorted(int(x) for x in arr[: abs(int(got))])

    def segments(self) -> int:
        return int(self._lib.cdb_segments(self._h))

    def close(self) -> None:
        if self._h:
            self._lib.cdb_close(self._h)
            self._h = None

    def __del__(self):  # best-effort: tests open/close many
        try:
            self.close()
        except Exception:  # lint: disable=except-swallow
            # logging (or any import) inside __del__ at interpreter
            # shutdown can itself raise; silence is the only safe option
            pass
