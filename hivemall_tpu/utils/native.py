"""ctypes bridge to the C++ native runtime pieces (native/hivemall_native.cpp).

Build-on-first-use: the shared object compiles with g++ (a few hundred ms)
the first time it's needed, then loads via ctypes. The artifact is keyed on
the CONTENT of its source and flags (``native/_native-<sha>.so``) and
written atomically, so a copied checkout never trusts a stale product (a
copy does not preserve mtimes) and several replicas or pool workers can
reach the build at once. A failed build leaves the pure Python/numpy paths
in charge with identical semantics (tests pin the bit-exact parity) and is
visible: :func:`status` says what loaded and why not.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading
from typing import Optional, Sequence

import numpy as np

__all__ = ["get_lib", "status", "build_artifact", "mmh3_batch_native",
           "mhash_batch_native", "bin_columns_native", "parse_libsvm_native",
           "canonicalize_fieldmajor_native"]

_LIB: Optional[ctypes.CDLL] = None
_TRIED = False
_ERROR: Optional[str] = None       # why the last build/load failed
_LOAD_LOCK = threading.Lock()      # ingest pool threads race the first load

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native",
    "hivemall_native.cpp")


def native_disabled() -> bool:
    """The ONE switch for every native path (the .so AND the mix server):
    HIVEMALL_TPU_NO_NATIVE=1 disables both."""
    return os.environ.get("HIVEMALL_TPU_NO_NATIVE") == "1"


def build_artifact(src: str, stem: str, ext: str, flags) -> Optional[str]:
    """Shared build-on-first-use: path of ``<dir(src)>/<stem>-<sha><ext>``
    where sha covers the source bytes and the compiler flags, compiling it
    with g++ if absent. The product lands under a temp name and is renamed
    into place, so concurrent builders each publish a complete file and
    readers never see a torn one. Returns None (never raises) when native
    is disabled or the build fails; the reason is kept for :func:`status`."""
    global _ERROR
    if native_disabled():
        _ERROR = "HIVEMALL_TPU_NO_NATIVE=1"
        return None
    cmd = ["g++", "-O3", "-std=c++17", *flags]
    base = os.path.join(os.path.dirname(src), stem)
    tmp = f"{base}.tmp.{os.getpid()}{ext}"
    try:
        with open(src, "rb") as f:
            sha = hashlib.sha256(
                f.read() + " ".join(cmd).encode()).hexdigest()
        out = f"{base}-{sha[:16]}{ext}"
        if os.path.exists(out):
            return out
        r = subprocess.run([*cmd, src, "-o", tmp], capture_output=True,
                           timeout=120)
        if r.returncode != 0:
            _ERROR = "g++ failed: " + r.stderr.decode(
                "utf8", "replace")[-400:]
            return None
        os.replace(tmp, out)
    except (OSError, subprocess.SubprocessError) as e:
        _ERROR = f"{type(e).__name__}: {e}"
        return None
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    for stale in glob.glob(f"{base}-*{ext}"):
        if stale != out:
            try:
                os.unlink(stale)     # products of an older source
            except OSError:
                pass
    return out


def _build() -> Optional[str]:
    # toolchains without libgomp: retry single-threaded
    return (build_artifact(_SRC, "_native", ".so",
                           ["-shared", "-fPIC", "-fopenmp"])
            or build_artifact(_SRC, "_native", ".so", ["-shared", "-fPIC"]))


def get_lib() -> Optional[ctypes.CDLL]:
    global _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    with _LOAD_LOCK:
        if not _TRIED:
            try:
                _load()
            finally:
                _TRIED = True
    return _LIB


def _load() -> None:
    global _LIB, _ERROR
    so = _build()
    if so is None:
        return
    try:
        lib = ctypes.CDLL(so)
    except OSError as e:
        _ERROR = f"dlopen: {e}"
        return
    lib.mmh3_32.restype = ctypes.c_uint32
    lib.mmh3_32.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_uint32]
    lib.mmh3_batch.restype = None
    lib.mhash_batch.restype = None
    lib.libsvm_parse.restype = ctypes.c_void_p
    lib.libsvm_parse.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.libsvm_rows.restype = ctypes.c_int64
    lib.libsvm_rows.argtypes = [ctypes.c_void_p]
    lib.libsvm_nnz.restype = ctypes.c_int64
    lib.libsvm_nnz.argtypes = [ctypes.c_void_p]
    lib.libsvm_fill.restype = None
    lib.libsvm_free.restype = None
    lib.libsvm_free.argtypes = [ctypes.c_void_p]
    lib.canon_measure.restype = ctypes.c_int
    lib.canon_fill.restype = None
    _LIB = lib
    _ERROR = None


def status() -> dict:
    """What the native layer is: ``{"loaded": bool, "path": ..., "error":
    ...}`` — the degradation to pure Python is allowed, not silent."""
    lib = get_lib()
    return {"loaded": lib is not None,
            "path": getattr(lib, "_name", None),
            "error": _ERROR}


def _pack(keys: Sequence[bytes | str]):
    enc = [k.encode("utf-8") if isinstance(k, str) else k for k in keys]
    offsets = np.zeros(len(enc) + 1, np.int64)
    for i, b in enumerate(enc):
        offsets[i + 1] = offsets[i] + len(b)
    return b"".join(enc), offsets


def mmh3_batch_native(keys: Sequence[bytes | str],
                      seed: int = 0) -> Optional[np.ndarray]:
    lib = get_lib()
    if lib is None or not len(keys):
        return None
    buf, offsets = _pack(keys)
    out = np.empty(len(keys), np.uint32)
    lib.mmh3_batch(buf, offsets.ctypes.data_as(ctypes.c_void_p),
                   ctypes.c_int64(len(keys)), ctypes.c_uint32(seed),
                   out.ctypes.data_as(ctypes.c_void_p))
    return out


def mhash_batch_native(keys: Sequence[bytes | str], num_features: int,
                       seed: int = 0) -> Optional[np.ndarray]:
    lib = get_lib()
    if lib is None or not len(keys):
        return None
    buf, offsets = _pack(keys)
    out = np.empty(len(keys), np.int64)
    lib.mhash_batch(buf, offsets.ctypes.data_as(ctypes.c_void_p),
                    ctypes.c_int64(len(keys)), ctypes.c_uint32(seed),
                    ctypes.c_int64(num_features),
                    out.ctypes.data_as(ctypes.c_void_p))
    return out


def parse_libsvm_native(path: str, *, zero_based: bool = False):
    """Parse a LIBSVM file with the C++ parser; None -> caller falls back."""
    if path.endswith(".gz"):
        return None
    lib = get_lib()
    if lib is None:
        return None
    h = lib.libsvm_parse(path.encode(), 1 if zero_based else 0)
    if not h:
        return None
    try:
        n = lib.libsvm_rows(h)
        nnz = lib.libsvm_nnz(h)
        idx = np.empty(nnz, np.int32)
        val = np.empty(nnz, np.float32)
        indptr = np.empty(n + 1, np.int64)
        labels = np.empty(n, np.float32)
        lib.libsvm_fill(ctypes.c_void_p(h),
                        idx.ctypes.data_as(ctypes.c_void_p),
                        indptr.ctypes.data_as(ctypes.c_void_p),
                        val.ctypes.data_as(ctypes.c_void_p),
                        labels.ctypes.data_as(ctypes.c_void_p))
    finally:
        lib.libsvm_free(ctypes.c_void_p(h))
    from ..io.sparse import SparseDataset
    return SparseDataset(idx, indptr, val, labels)


def canonicalize_fieldmajor_native(idx: np.ndarray, val: np.ndarray,
                                   fld: np.ndarray, F: int, max_m: int):
    """C++ field-major canonicalization (io.sparse semantic twin).

    Returns (idx2, val2, m) like io.sparse.canonicalize_fieldmajor,
    ``None`` if a row overflows max_m, or ``NotImplemented`` when the
    native lib is unavailable (caller falls back to numpy)."""
    lib = get_lib()
    if lib is None:
        return NotImplemented
    idx = np.ascontiguousarray(idx, np.int32)
    val = np.ascontiguousarray(val, np.float32)
    fld = np.ascontiguousarray(fld, np.int32)
    B, L = idx.shape
    m_needed = lib.canon_measure(
        val.ctypes.data_as(ctypes.c_void_p),
        fld.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(B), ctypes.c_int64(L),
        ctypes.c_int(F), ctypes.c_int(max_m))
    if m_needed < 0:
        return None
    m = 1
    while m < m_needed:
        m <<= 1
    out_idx = np.zeros((B, m * F), np.int32)
    out_val = np.zeros((B, m * F), np.float32)
    lib.canon_fill(
        idx.ctypes.data_as(ctypes.c_void_p),
        val.ctypes.data_as(ctypes.c_void_p),
        fld.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(B), ctypes.c_int64(L),
        ctypes.c_int(F), ctypes.c_int(m),
        out_idx.ctypes.data_as(ctypes.c_void_p),
        out_val.ctypes.data_as(ctypes.c_void_p))
    return out_idx, out_val, int(m)


def bin_columns_native(X: np.ndarray, edges: np.ndarray,
                       n_edges: np.ndarray):
    """C++ twin of quantize_bins' per-column searchsorted loop (round 4:
    it measured 1.6-1.9 s of the 1M x 28 RF build host side). Returns the
    uint8 code matrix or NotImplemented when the lib isn't available."""
    lib = get_lib()
    if lib is None:
        return NotImplemented
    X = np.ascontiguousarray(X, np.float32)
    edges = np.ascontiguousarray(edges, np.float32)
    n_edges = np.ascontiguousarray(n_edges, np.int32)
    n, d = X.shape
    codes = np.empty((n, d), np.uint8)
    lib.bin_columns(X.ctypes.data_as(ctypes.c_void_p),
                    ctypes.c_int64(n), ctypes.c_int64(d),
                    edges.ctypes.data_as(ctypes.c_void_p),
                    n_edges.ctypes.data_as(ctypes.c_void_p),
                    ctypes.c_int64(edges.shape[1]),
                    codes.ctypes.data_as(ctypes.c_void_p))
    return codes
