"""ctypes loader for the native C++ RS/CRC kernel (rs_cpu.cpp).

Builds the shared library on first use with g++ (no pip involved) and caches
it next to the source. Falls back cleanly if no compiler is present —
callers must check `available()`.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "rs_cpu.cpp")
_SO = os.path.join(_DIR, "_rs_cpu.so")

# No -march=native: the SIMD tiers carry their own target attributes
# and a __builtin_cpu_supports dispatch (rs_cpu.cpp), so the baseline
# code stays runnable on whatever CPU loads the library next.
CXX = "g++"
CXXFLAGS = ("-O3", "-shared", "-fPIC")

_lock = threading.Lock()
_lib = None
_tried = False
_build_error = ""


def _build() -> bool:
    """Compile to a temp file, then atomically replace the cached .so.
    Building in place would rewrite an inode that may already be mmapped
    by this process (stale-symbol retry path) — dlopen would then dedup to
    the corrupted old mapping; a fresh inode gives a fresh mapping."""
    global _build_error
    tmp = _SO + f".build.{os.getpid()}"  # unique per process: two
    # concurrent builders must not truncate each other's half-written file
    try:
        try:
            subprocess.run([CXX, *CXXFLAGS, "-o", tmp, _SRC],
                           check=True, capture_output=True, timeout=120)
            os.replace(tmp, _SO)
            return True
        except (OSError, subprocess.SubprocessError) as e:
            err = getattr(e, "stderr", b"") or b""
            _build_error = (f"{type(e).__name__}: {e} "
                            f"{err.decode(errors='replace')[-500:]}")
            return False
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def _bind(lib) -> None:
    """Declare ctypes signatures; raises AttributeError on a stale .so
    missing newer symbols."""
    lib.gf_apply.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
    ]
    lib.gf_apply.restype = None
    lib.gf_apply_strided.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64,
    ]
    lib.gf_apply_strided.restype = None
    lib.crc32c.argtypes = [ctypes.c_uint32, ctypes.c_void_p, ctypes.c_int64]
    lib.crc32c.restype = ctypes.c_uint32
    lib.pread_rows.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
    ]
    lib.pread_rows.restype = None
    lib.gf_force_impl.argtypes = [ctypes.c_int]
    lib.gf_force_impl.restype = ctypes.c_int
    lib.gf_impl_name.restype = ctypes.c_char_p


def _load():
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not os.path.exists(_SO) or os.path.getmtime(_SO) < os.path.getmtime(_SRC):
            if not _build():
                return None
        for attempt in range(2):
            try:
                lib = ctypes.CDLL(_SO)
                _bind(lib)
            except OSError:
                return None
            except AttributeError:
                # stale cached .so (e.g. copied with preserved mtimes)
                # predating a symbol — rebuild once, then give up so
                # callers fall back to pure Python
                if attempt or not _build():
                    return None
                continue
            _lib = lib
            return _lib
        return None


def available() -> bool:
    return _load() is not None


def rebuild() -> dict:
    """Build the library from rs_cpu.cpp on THIS machine (replacing a
    cached .so that may have been compiled elsewhere) and load it.
    Returns {"ok", "compiler", "flags", "impl", "error"} — what a
    preflight prints before it trusts the codec.  Call before anything
    else in the process has loaded the library."""
    global _tried
    with _lock:
        built = _lib is None and _build()
        _tried = False
    lib = _load()
    return {"ok": lib is not None, "built": built,
            "compiler": CXX, "flags": list(CXXFLAGS),
            "impl": lib.gf_impl_name().decode() if lib is not None
            else None,
            "error": None if lib is not None
            else (_build_error or "library did not load")}


def gf_apply(mat: np.ndarray, data: np.ndarray) -> np.ndarray:
    """out = mat (m,k) x data (k,n) over GF(256)."""
    lib = _load()
    assert lib is not None
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    m, k = mat.shape
    k2, n = data.shape
    assert k == k2
    out = np.zeros((m, n), dtype=np.uint8)
    lib.gf_apply(mat.ctypes.data, m, k, data.ctypes.data, out.ctypes.data, n)
    return out


def gf_apply_into(mat: np.ndarray, data: np.ndarray, out: np.ndarray,
                  col0: int = 0, length: int | None = None) -> None:
    """Accumulate mat (m,k) x data (k,n) into columns [col0, col0+length)
    of out (m,n), which must be zero there (or hold a partial sum). The
    call releases the GIL and touches nothing outside its column range, so
    disjoint ranges may run concurrently from a thread pool."""
    lib = _load()
    assert lib is not None
    assert mat.dtype == np.uint8 and mat.flags.c_contiguous
    assert data.dtype == np.uint8 and data.flags.c_contiguous
    assert out.dtype == np.uint8 and out.flags.c_contiguous
    m, k = mat.shape
    k2, n = data.shape
    assert k == k2 and out.shape == (m, n)
    if length is None:
        length = n - col0
    assert 0 <= col0 and col0 + length <= n
    lib.gf_apply_strided(mat.ctypes.data, m, k, data.ctypes.data,
                         out.ctypes.data, n, col0, length)


IMPL_AUTO, IMPL_SCALAR, IMPL_AVX2, IMPL_GFNI = 0, 1, 2, 3


def force_impl(which: int) -> int:
    """Pin the GF kernel tier (IMPL_*); returns the tier that will run.
    Benchmarks use this to measure each tier honestly."""
    lib = _load()
    assert lib is not None
    return int(lib.gf_force_impl(which))


def impl_name() -> str:
    """Name of the GF kernel tier currently selected."""
    lib = _load()
    assert lib is not None
    return lib.gf_impl_name().decode()


def crc32c(data: bytes | bytearray | memoryview | np.ndarray,
           crc: int = 0) -> int:
    """CRC32-C over any byte-shaped buffer WITHOUT copying it: bytes,
    bytearray, and memoryview all go through np.frombuffer (a view of
    the caller's memory), so checksumming a window of a cached record
    costs the table walk and nothing else. ``crc`` chains: feeding
    windows ``a`` then ``b`` with the running value equals one pass
    over ``a+b`` — the read plane verifies Range responses piecewise
    on exactly this property."""
    lib = _load()
    assert lib is not None
    if isinstance(data, np.ndarray):
        buf = np.ascontiguousarray(data, dtype=np.uint8)
    else:
        buf = np.frombuffer(data, dtype=np.uint8)
    if buf.size == 0:
        return crc & 0xFFFFFFFF
    return int(lib.crc32c(crc, buf.ctypes.data, buf.size))


def pread_rows(fds: list[int], offset: int, rows: np.ndarray,
               size: int) -> list[int]:
    """rows[r, :size] = `size` bytes from `offset` of file descriptor
    fds[r] (negative: the row is left as it is), every row in ONE
    foreign call: the interpreter lock is given away once for all the
    reads, not once a file. Returns the bytes read per row, short only
    at the end of a file; a failed read raises OSError."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library not available")
    if rows.dtype != np.uint8 or rows.ndim != 2 \
            or not rows.flags.writeable or rows.strides[1] != 1 \
            or rows.strides[0] < rows.shape[1]:
        raise ValueError("rows must be a writable 2-D uint8 array with "
                         "contiguous rows")
    n = len(fds)
    if n != rows.shape[0] or not 0 <= size <= rows.shape[1] \
            or offset < 0:
        raise ValueError(f"{n} files at {offset} for {size} bytes do "
                         f"not fit rows of shape {rows.shape}")
    c_fds = (ctypes.c_int32 * n)(*fds)
    got = (ctypes.c_int64 * n)()
    lib.pread_rows(c_fds, n, offset, rows.ctypes.data, rows.strides[0],
                   size, got)
    for r, g in enumerate(got):
        if g < 0:
            raise OSError(-g, os.strerror(-g), f"fd {fds[r]}")
    return list(got)
