"""ctypes bindings for the native host runtime (native/slate_rt.cpp), with pure
Python fallbacks.

Reference analogue: the reference's C++ runtime layer — block-cyclic tile maps
(func.hh), the tile directory (MatrixStorage.hh) and the fixed-block memory pool
(src/core/Memory.cc).  The TPU compute path is XLA/Pallas; this is the *host*
side: integer-heavy owner-map/plan computation and workspace accounting.

``backend()`` reports which implementation is active.  The shared library is built
on demand with ``make`` in ``native/`` (no pip deps); every entry point falls back
to Python when the build is unavailable, and the test suite covers both paths.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import List, Optional, Tuple

import numpy as np

from .core.types import GridOrder

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libslate_rt.so")

_lib: Optional[ctypes.CDLL] = None
_tried = False


def _order_code(order) -> int:
    return 0 if GridOrder.from_string(order) == GridOrder.Col else 1


_FAIL_STAMP = os.path.join(_NATIVE_DIR, ".build_failed")


def _src_fingerprint() -> str:
    """Newest mtime over the native sources; keys the fail stamp so a stamp
    from an older (or transiently broken) tree doesn't suppress builds of a
    changed one."""
    try:
        ms = [os.path.getmtime(os.path.join(_NATIVE_DIR, f))
              for f in os.listdir(_NATIVE_DIR)
              if f.endswith((".cpp", ".cc", ".c", ".h", ".hpp")) or f == "Makefile"]
        return repr(max(ms)) if ms else "0"
    except OSError:
        return "0"


def _stamp_suppresses() -> bool:
    try:
        with open(_FAIL_STAMP) as f:
            return f.read().strip() == _src_fingerprint()
    except OSError:
        return False


def build() -> bool:
    """Compile native/libslate_rt.so with make.  Runs lazily on the first
    native call (never at import — an import must not spawn a compiler);
    callers can also invoke it explicitly after a clean.  A failed attempt is
    stamped with the source fingerprint so later sessions don't re-pay a
    doomed compile, but any source change invalidates the stamp; explicit
    build() always retries."""
    global _tried
    try:
        proc = subprocess.run(["make", "-C", _NATIVE_DIR], capture_output=True,
                              timeout=120)
        _tried = False            # allow _load to pick up the fresh build
        ok = proc.returncode == 0
    # slate-lint: disable=SLT501 -- `make` subprocess probe: only
    # subprocess errors can arise; a failed build is recorded in the stamp
    except Exception:
        ok = False
    try:
        if ok:
            if os.path.exists(_FAIL_STAMP):
                os.unlink(_FAIL_STAMP)
        else:
            with open(_FAIL_STAMP, "w") as f:
                f.write(_src_fingerprint())
    except OSError:
        pass
    return ok


def _should_autobuild() -> bool:
    import shutil
    if (os.environ.get("SLATE_TPU_NATIVE", "1") == "0"
            or os.path.exists(_LIB_PATH)
            or not os.path.isdir(_NATIVE_DIR)
            or not os.access(_NATIVE_DIR, os.W_OK)
            or shutil.which("make") is None
            or shutil.which(os.environ.get("CXX", "g++")) is None):
        return False
    if _stamp_suppresses():
        global _warned_stamp
        if not _warned_stamp:
            _warned_stamp = True
            import warnings
            warnings.warn(
                "slate_tpu native build previously failed for these sources "
                f"({_FAIL_STAMP} present); using pure-Python fallbacks. "
                "Call slate_tpu.native.build() to retry.")
        return False
    return True


_warned_stamp = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    if not os.path.exists(_LIB_PATH) and _should_autobuild():
        build()           # lazy first-use build (ADVICE: not at import time)
        _tried = True     # build() cleared it so a fresh .so is picked up here
    if not os.path.exists(_LIB_PATH):
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError:
        return None
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.srt_owner_map.argtypes = [ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
                                  ctypes.c_int32, ctypes.c_int32, i32p]
    lib.srt_local_tiles.argtypes = [ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
                                    ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                                    i64p]
    lib.srt_local_tiles.restype = ctypes.c_int64
    lib.srt_redist_plan.argtypes = [ctypes.c_int64, ctypes.c_int64,
                                    ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                                    ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                                    i32p, i32p]
    lib.srt_redist_plan.restype = ctypes.c_int64
    lib.srt_pool_new.argtypes = [ctypes.c_int64, ctypes.c_int64]
    lib.srt_pool_new.restype = ctypes.c_void_p
    lib.srt_pool_delete.argtypes = [ctypes.c_void_p]
    lib.srt_pool_alloc.argtypes = [ctypes.c_void_p]
    lib.srt_pool_alloc.restype = ctypes.c_int64
    lib.srt_pool_free.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.srt_pool_free.restype = ctypes.c_int32
    for fn in ("srt_pool_in_use", "srt_pool_capacity", "srt_pool_peak"):
        getattr(lib, fn).argtypes = [ctypes.c_void_p]
        getattr(lib, fn).restype = ctypes.c_int64
    _lib = lib
    return _lib


def backend() -> str:
    """'native' when libslate_rt.so is loaded, else 'python'."""
    return "native" if _load() is not None else "python"


# ---------------------------------------------------------------------------
# block-cyclic maps

def owner_map(mt: int, nt: int, p: int, q: int,
              order=GridOrder.Col) -> np.ndarray:
    """Full (mt, nt) int32 tile->rank map for a 2D block-cyclic grid
    (func.hh:178-186 applied over the whole tile space)."""
    code = _order_code(order)
    lib = _load()
    out = np.empty((mt, nt), dtype=np.int32)
    if lib is not None and mt * nt > 0:
        lib.srt_owner_map(mt, nt, p, q, code,
                          out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        return out
    i = np.arange(mt)[:, None] % p
    j = np.arange(nt)[None, :] % q
    return (i + j * p if code == 0 else i * q + j).astype(np.int32)


def local_tiles(mt: int, nt: int, p: int, q: int, rank: int,
                order=GridOrder.Col) -> np.ndarray:
    """(k, 2) array of the (i, j) tile indices owned by ``rank`` (the reference's
    per-rank tile-directory iteration, MatrixStorage.hh)."""
    code = _order_code(order)
    lib = _load()
    if lib is not None:
        count = lib.srt_local_tiles(mt, nt, p, q, code, rank, None)
        out = np.empty((count, 2), dtype=np.int64)
        if count:
            lib.srt_local_tiles(mt, nt, p, q, code, rank,
                                out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        return out
    om = owner_map(mt, nt, p, q, order)
    ii, jj = np.nonzero(om == rank)
    return np.stack([ii, jj], axis=1).astype(np.int64)


def redist_plan(mt: int, nt: int,
                src_grid: Tuple[int, int], dst_grid: Tuple[int, int],
                src_order=GridOrder.Col, dst_order=GridOrder.Col):
    """Per-tile (src_rank, dst_rank) maps between two block-cyclic layouts and the
    count of tiles that move (src/redistribute.cc's send/recv planning loop).

    Returns (src_map, dst_map, n_moved)."""
    c1, c2 = _order_code(src_order), _order_code(dst_order)
    lib = _load()
    if lib is not None:
        src = np.empty((mt, nt), dtype=np.int32)
        dst = np.empty((mt, nt), dtype=np.int32)
        moved = lib.srt_redist_plan(
            mt, nt, src_grid[0], src_grid[1], c1, dst_grid[0], dst_grid[1], c2,
            src.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            dst.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        return src, dst, int(moved)
    src = owner_map(mt, nt, src_grid[0], src_grid[1], src_order)
    dst = owner_map(mt, nt, dst_grid[0], dst_grid[1], dst_order)
    return src, dst, int(np.count_nonzero(src != dst))


# ---------------------------------------------------------------------------
# memory-pool accounting

class MemoryPool:
    """Fixed-block workspace accounting (src/core/Memory.cc free list).

    XLA owns the actual HBM; this tracks tile-granular workspace budget so
    drivers can reason about fit/spill (the reference's reserveDeviceWorkspace
    planning).  alloc() returns a block id or -1 when exhausted; free() returns
    False on double-free (the Debug.cc leak check).
    """

    def __init__(self, block_bytes: int, nblocks: int):
        self.block_bytes = int(block_bytes)
        self._lib = _load()
        if self._lib is not None:
            self._pool = self._lib.srt_pool_new(block_bytes, nblocks)
            self._free: Optional[List[int]] = None
        else:
            self._pool = None
            self._free = list(range(nblocks - 1, -1, -1))
            self._used = set()
            self._peak = 0
            self._cap = nblocks

    def alloc(self) -> int:
        if self._pool is not None:
            return int(self._lib.srt_pool_alloc(self._pool))
        if not self._free:
            return -1
        bid = self._free.pop()
        self._used.add(bid)
        self._peak = max(self._peak, len(self._used))
        return bid

    def free(self, block_id: int) -> bool:
        if self._pool is not None:
            return int(self._lib.srt_pool_free(self._pool, block_id)) == 0
        if block_id not in self._used:
            return False
        self._used.discard(block_id)
        self._free.append(block_id)
        return True

    @property
    def in_use(self) -> int:
        if self._pool is not None:
            return int(self._lib.srt_pool_in_use(self._pool))
        return len(self._used)

    @property
    def capacity(self) -> int:
        if self._pool is not None:
            return int(self._lib.srt_pool_capacity(self._pool))
        return self._cap

    @property
    def peak(self) -> int:
        if self._pool is not None:
            return int(self._lib.srt_pool_peak(self._pool))
        return self._peak

    def __del__(self):
        if getattr(self, "_pool", None) is not None and self._lib is not None:
            self._lib.srt_pool_delete(self._pool)
            self._pool = None


# NOTE: no import-time build — the native library compiles lazily on the first
# native call (_load), so `import slate_tpu` never spawns a compiler.  Opt out
# entirely with SLATE_TPU_NATIVE=0 (pure-Python fallbacks remain functional);
# a failed attempt is stamped keyed to the source fingerprint, so only the
# same broken tree is suppressed and a warning is emitted once.
