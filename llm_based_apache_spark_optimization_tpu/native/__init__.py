"""Native runtime core: on-demand g++ build + ctypes bindings.

The reference's native layer lives out-of-tree in llama.cpp (SURVEY.md §2.3);
here it is in-tree C++ (native/src/) compiled once per machine into
`lib/liblsot_native.so` the first time a component needs it. ctypes (not
pybind11 — not available in this image) keeps the binding layer dependency-
free; every native feature has a pure-Python fallback so the framework
degrades gracefully where no C++ toolchain exists (LSOT_NO_NATIVE=1 forces
the fallbacks, used by tests to assert parity).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from pathlib import Path
from typing import List, Optional, Sequence, Tuple

_SRC_DIR = Path(__file__).parent / "src"
_LIB_DIR = Path(__file__).parent / "lib"
_LIB_PATH = _LIB_DIR / "liblsot_native.so"
_SOURCES = ("bpe.cpp", "gguf.cpp", "csvscan.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False
_built_here = False


def _build() -> bool:
    _LIB_DIR.mkdir(exist_ok=True)
    srcs = [str(_SRC_DIR / s) for s in _SOURCES]
    # Build to a temp name then rename: concurrent processes racing the build
    # see either no file or a complete one, never a half-written .so.
    tmp = _LIB_DIR / f"liblsot_native.{os.getpid()}.tmp.so"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
           f"-I{_SRC_DIR}", *srcs, "-o", str(tmp)]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _LIB_PATH)
        global _built_here
        _built_here = True
        return True
    except (subprocess.SubprocessError, FileNotFoundError, OSError):
        tmp.unlink(missing_ok=True)
        return False


def built_in_this_process() -> bool:
    """Whether `load_native` had to compile the library (a fresh checkout:
    `lib/` is git-ignored) rather than find one built earlier."""
    return _built_here


def load_native() -> Optional[ctypes.CDLL]:
    """The shared library, building it on first use; None when unavailable."""
    global _lib, _load_failed
    if os.environ.get("LSOT_NO_NATIVE") == "1":
        return None
    if _lib is not None:
        return _lib
    if _load_failed:
        return None
    with _lock:
        if _lib is not None:
            return _lib
        # Rebuild when any source is newer than the lib (dev loop).
        stale = not _LIB_PATH.exists() or any(
            (_SRC_DIR / s).stat().st_mtime > _LIB_PATH.stat().st_mtime
            for s in _SOURCES
        )
        if stale and not _build():
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(str(_LIB_PATH))
        except OSError:
            _load_failed = True
            return None
        _declare(lib)
        _lib = lib
        return _lib


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.lsot_bpe_new.restype = c.c_void_p
    lib.lsot_bpe_new.argtypes = [c.POINTER(c.c_int32), c.c_int32, c.c_int32]
    lib.lsot_bpe_free.argtypes = [c.c_void_p]
    lib.lsot_bpe_encode.restype = c.c_int32
    lib.lsot_bpe_encode.argtypes = [
        c.c_void_p, c.POINTER(c.c_uint8), c.c_int32,
        c.POINTER(c.c_int32), c.c_int32,
    ]
    lib.lsot_gguf_open.restype = c.c_void_p
    lib.lsot_gguf_open.argtypes = [c.c_char_p]
    lib.lsot_gguf_close.argtypes = [c.c_void_p]
    lib.lsot_gguf_n_tensors.restype = c.c_int32
    lib.lsot_gguf_n_tensors.argtypes = [c.c_void_p]
    lib.lsot_gguf_tensor_name.restype = c.c_char_p
    lib.lsot_gguf_tensor_name.argtypes = [c.c_void_p, c.c_int32]
    lib.lsot_gguf_tensor_ndim.restype = c.c_int32
    lib.lsot_gguf_tensor_ndim.argtypes = [c.c_void_p, c.c_int32]
    lib.lsot_gguf_tensor_dim.restype = c.c_uint64
    lib.lsot_gguf_tensor_dim.argtypes = [c.c_void_p, c.c_int32, c.c_int32]
    lib.lsot_gguf_tensor_dtype.restype = c.c_int32
    lib.lsot_gguf_tensor_dtype.argtypes = [c.c_void_p, c.c_int32]
    lib.lsot_gguf_tensor_nelems.restype = c.c_uint64
    lib.lsot_gguf_tensor_nelems.argtypes = [c.c_void_p, c.c_int32]
    lib.lsot_gguf_read_f32.restype = c.c_int32
    lib.lsot_gguf_read_f32.argtypes = [
        c.c_void_p, c.c_int32, c.POINTER(c.c_float), c.c_uint64,
    ]
    lib.lsot_gguf_meta_str.restype = c.c_char_p
    lib.lsot_gguf_meta_str.argtypes = [c.c_void_p, c.c_char_p]
    lib.lsot_gguf_meta_f64.restype = c.c_int32
    lib.lsot_gguf_meta_f64.argtypes = [
        c.c_void_p, c.c_char_p, c.POINTER(c.c_double),
    ]
    lib.lsot_gguf_last_error.restype = c.c_char_p
    lib.lsot_gguf_last_error.argtypes = []
    lib.lsot_csv_scan.restype = c.c_int32
    lib.lsot_csv_scan.argtypes = [
        c.c_char_p, c.POINTER(c.c_int32), c.c_int32, c.POINTER(c.c_int64),
    ]


class NativeBPE:
    """ctypes handle to the C++ BPE encoder; None-safe constructor wrapper is
    `NativeBPE.create` (returns None when the native lib is unavailable)."""

    def __init__(self, lib: ctypes.CDLL, merges: Sequence[Tuple[int, int]],
                 n_special: int):
        self._lib = lib
        flat = []
        for a, b in merges:
            flat += [int(a), int(b)]
        arr = (ctypes.c_int32 * len(flat))(*flat)
        self._h = lib.lsot_bpe_new(arr, len(merges), n_special)

    @classmethod
    def create(cls, merges: Sequence[Tuple[int, int]],
               n_special: int) -> Optional["NativeBPE"]:
        lib = load_native()
        return cls(lib, merges, n_special) if lib is not None else None

    def encode_bytes(self, data: bytes) -> List[int]:
        n = len(data)
        if n == 0:
            return []
        buf = (ctypes.c_uint8 * n).from_buffer_copy(data)
        out = (ctypes.c_int32 * n)()
        count = self._lib.lsot_bpe_encode(self._h, buf, n, out, n)
        if count < 0:  # cannot happen (merges only shrink); defensive
            raise RuntimeError("native BPE output overflow")
        return list(out[:count])

    def __del__(self):
        h, lib = getattr(self, "_h", None), getattr(self, "_lib", None)
        if h and lib is not None:
            lib.lsot_bpe_free(h)


#: Dtype code -> Spark-compatible dtype name (lsot_native.h LSOT_CSV_*).
CSV_DTYPE_NAMES = ("string", "int", "bigint", "double", "timestamp")


def csv_scan(path: str | os.PathLike, max_cols: int = 4096):
    """Native CSV schema-inference scan: (dtype names, data-row count), or
    None when the native lib is unavailable or the file is malformed —
    callers fall back to the Python inference pass."""
    lib = load_native()
    if lib is None:
        return None
    dtypes = (ctypes.c_int32 * max_cols)()
    n_rows = ctypes.c_int64()
    n = lib.lsot_csv_scan(str(path).encode(), dtypes, max_cols,
                          ctypes.byref(n_rows))
    if n < 0:
        return None
    return [CSV_DTYPE_NAMES[dtypes[i]] for i in range(n)], int(n_rows.value)


class GGUFReader:
    """Parsed GGUF file: tensor directory + metadata + f32 dequantization.

    Dequantizes F32/F16/Q8_0/Q4_0 and the K-quants (Q4_K/Q5_K/Q6_K) that
    current Ollama/llama.cpp model blobs actually ship."""

    F32, F16, Q4_0, Q8_0 = 0, 1, 2, 8
    Q4_K, Q5_K, Q6_K = 12, 13, 14

    def __init__(self, path: str | os.PathLike):
        lib = load_native()
        if lib is None:
            raise RuntimeError(
                "native library unavailable (g++ missing or LSOT_NO_NATIVE=1); "
                "GGUF reading requires the C++ core"
            )
        self._lib = lib
        self._h = lib.lsot_gguf_open(str(path).encode())
        if not self._h:
            raise ValueError(
                f"GGUF open failed: {lib.lsot_gguf_last_error().decode()}"
            )
        self._names = {}
        for i in range(lib.lsot_gguf_n_tensors(self._h)):
            self._names[lib.lsot_gguf_tensor_name(self._h, i).decode()] = i

    @property
    def tensor_names(self) -> List[str]:
        return list(self._names)

    def shape(self, name: str) -> Tuple[int, ...]:
        """Numpy-order shape (outermost first — reverse of GGUF dim order)."""
        i = self._names[name]
        nd = self._lib.lsot_gguf_tensor_ndim(self._h, i)
        dims = [self._lib.lsot_gguf_tensor_dim(self._h, i, d) for d in range(nd)]
        return tuple(int(d) for d in reversed(dims))

    def dtype(self, name: str) -> int:
        return self._lib.lsot_gguf_tensor_dtype(self._h, self._names[name])

    def meta_str(self, key: str) -> Optional[str]:
        v = self._lib.lsot_gguf_meta_str(self._h, key.encode())
        return v.decode() if v is not None else None

    def meta_num(self, key: str) -> Optional[float]:
        out = ctypes.c_double()
        ok = self._lib.lsot_gguf_meta_f64(self._h, key.encode(),
                                          ctypes.byref(out))
        return out.value if ok else None

    def tensor_f32(self, name: str):
        """Dequantized tensor as a float32 numpy array in numpy-order shape."""
        import numpy as np

        i = self._names[name]
        n = self._lib.lsot_gguf_tensor_nelems(self._h, i)
        out = np.empty(int(n), np.float32)
        rc = self._lib.lsot_gguf_read_f32(
            self._h, i, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n
        )
        if rc != 0:
            raise ValueError(
                f"GGUF read failed for {name}: "
                f"{self._lib.lsot_gguf_last_error().decode()}"
            )
        return out.reshape(self.shape(name))

    def close(self) -> None:
        if self._h:
            self._lib.lsot_gguf_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
