"""ctypes bindings for the native (C++) host kernels.

The reference's native layer is its entire C++ codebase; here the native
surface is the host-side stages that XLA cannot own: currently the
bulge-chasing band->tridiag kernel (``band_to_tridiag.cpp``). The library is
compiled on first use with g++ (no pybind11 in the image — plain C ABI via
ctypes); failures fall back to the numpy implementation transparently.

Each C++ call is bracketed by a ``stage.native.*`` host span (here, not at
the call sites, so a numpy fallback is never timed under a native name).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

from .. import obs
from ..types import ceil_div

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRCS = [os.path.join(_HERE, "band_to_tridiag.cpp"),
         os.path.join(_HERE, "secular.cpp"),
         os.path.join(_HERE, "deflate.cpp")]


def _cpu_tag() -> str:
    """Short tag identifying this host's ISA so a -march=native artifact is
    never loaded on a CPU it wasn't built for (package dirs can live on
    shared filesystems spanning heterogeneous nodes)."""
    import hashlib
    import platform

    ident = platform.machine()
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    ident += line
                    break
    except OSError:
        ident += platform.processor()
    return hashlib.sha1(ident.encode()).hexdigest()[:10]


_LIB = os.path.join(_HERE, f"libdlaf_native-{_cpu_tag()}.so")
_lock = threading.Lock()
_lib = None
_load_error: Exception | None = None

#: Test hook (health.inject.force_native_failure): when True, get_lib()
#: fails as if the compiler/loader had — exercising the cached-error
#: re-raise path and every native -> numpy degradation chain without
#: breaking a real toolchain.
_FORCE_BUILD_FAILURE = False


def _reset_for_tests(force_failure: bool = False) -> None:
    """Drop the cached library/error and (un)arm the forced-failure hook,
    so injection contexts neither see a pre-loaded library nor leak the
    injected failure into later callers."""
    global _lib, _load_error, _FORCE_BUILD_FAILURE
    with _lock:
        _lib = None
        _load_error = None
        _FORCE_BUILD_FAILURE = bool(force_failure)


def _build() -> str:
    # -march=native vectorizes the diagonal-major chase streams ~1.5x over
    # baseline -O3 (safe: the .so is built on first use per machine, never
    # committed); retried without the flag for toolchains that reject it
    base = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", *_SRCS,
            "-o", _LIB, "-lpthread"]
    try:
        subprocess.run(base[:1] + ["-march=native"] + base[1:],
                       check=True, capture_output=True)
    except subprocess.CalledProcessError:
        subprocess.run(base, check=True, capture_output=True)
    return _LIB


def get_lib():
    """Load (building if stale) the native library. A failed build/load is
    cached and re-raised immediately so callers with numpy fallbacks don't
    respawn the compiler on every call."""
    global _lib, _load_error
    with _lock:
        if _lib is not None:
            return _lib
        if _load_error is not None:
            raise _load_error
        try:
            if _FORCE_BUILD_FAILURE:
                raise RuntimeError(
                    "forced build failure (health.inject test hook)")
            if (not os.path.exists(_LIB)
                    or any(os.path.getmtime(_LIB) < os.path.getmtime(s)
                           for s in _SRCS)):
                _build()
            lib = ctypes.CDLL(_LIB)
            for name in ("dlaf_band_to_tridiag_d", "dlaf_band_to_tridiag_z",
                         "dlaf_secular_roots_d", "dlaf_secular_roots_d_nt"):
                fn = getattr(lib, name)
                fn.restype = ctypes.c_int
            lib.dlaf_deflate_scan_d.restype = ctypes.c_int64
        except Exception as e:
            _load_error = e
            # error level: an order-of-magnitude perf cliff must stay
            # visible even under DLAF_LOG=error deployments
            obs.get_logger("native").error(
                f"build/load failed ({e!r}); numpy fallbacks in effect")
            raise
        _lib = lib
        return lib


def secular_roots(ds: np.ndarray, zs: np.ndarray, rho: float,
                  nthreads: int | None = None):
    """Native counterpart of the host secular solver (safeguarded-Newton
    laed4 analog, ``secular.cpp``): returns ``(anchor, mu)`` with the same
    contract as ``tridiag_solver._secular_roots``.

    ``nthreads``: None or <= 0 = auto (hardware concurrency, bounded by
    roots per worker); >= 1 forces the worker count. Any count yields
    bitwise identical results — each root is independent."""
    ds = np.ascontiguousarray(ds, dtype=np.float64)
    zs = np.ascontiguousarray(zs, dtype=np.float64)
    k = ds.shape[0]
    anchor = np.zeros(k, dtype=np.int64)
    mu = np.zeros(k, dtype=np.float64)
    if k == 0:
        return anchor, mu
    lib = get_lib()
    with obs.span("stage.native.secular", fenced=False, k=k):
        rc = lib.dlaf_secular_roots_d_nt(
            ds.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            zs.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            ctypes.c_double(float(rho)), ctypes.c_long(k),
            anchor.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
            mu.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            ctypes.c_long(nthreads if nthreads is not None and nthreads > 0
                          else 0))
    if rc != 0:
        raise RuntimeError(f"native secular_roots failed rc={rc}")
    return anchor, mu


def deflate_scan(ds: np.ndarray, zs: np.ndarray, live: np.ndarray,
                 tol: float):
    """Native near-equal-pole deflation scan (``deflate.cpp``; reference
    ``merge.h:443-508``). Mutates ``zs``/``live`` in place (both must be
    contiguous arrays owned by the caller) and returns the applied Givens
    rotations as arrays ``(i, j, c, s)`` in application order."""
    n = ds.shape[0]
    if n == 0:
        return (np.zeros(0, np.int64), np.zeros(0, np.int64),
                np.zeros(0), np.zeros(0))
    assert zs.flags.c_contiguous and live.flags.c_contiguous
    lib = get_lib()
    gi = np.zeros(n, dtype=np.int64)
    gj = np.zeros(n, dtype=np.int64)
    gc = np.zeros(n, dtype=np.float64)
    gs = np.zeros(n, dtype=np.float64)
    ds = np.ascontiguousarray(ds, dtype=np.float64)
    with obs.span("stage.native.deflate", fenced=False, k=n):
        g = lib.dlaf_deflate_scan_d(
            ds.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            zs.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            live.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.c_int64(n), ctypes.c_double(tol),
            gi.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            gj.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            gc.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            gs.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    if g < 0:
        raise RuntimeError(f"native deflate_scan failed rc={g}")
    return gi[:g], gj[:g], gc[:g], gs[:g]


def _chase_threads() -> int:
    """Worker count for the pipelined sweep chase: the config knob
    ``chase_threads`` (0 = auto = CPU count; 1 = sequential). Results are
    bitwise identical at any count (disjoint pipelined windows)."""
    from ..config import get_configuration

    t = get_configuration().chase_threads
    if t <= 0:
        # affinity-aware (cgroup/taskset-limited) count: oversubscribed
        # spin-yield workers would thrash, not idle
        try:
            t = len(os.sched_getaffinity(0))
        except AttributeError:  # non-Linux
            t = os.cpu_count() or 1
    return t


def band_to_tridiag(band: np.ndarray, b: int, nthreads: int | None = None):
    """Native chase; same result contract as
    :func:`dlaf_tpu.eigensolver.band_to_tridiag.band_to_tridiag_numpy`.

    ``nthreads``: None or <= 0 means the config/auto policy (same as
    ``chase_threads = 0``); 1 sequential; > 1 pipelined workers."""
    from ..eigensolver.band_to_tridiag import TridiagResult

    n = band.shape[1]
    cplx = np.issubdtype(band.dtype, np.complexfloating)
    work_dtype = np.complex128 if cplx else np.float64
    band_w = np.ascontiguousarray(band, dtype=work_dtype)
    n_sweeps = max(n - 2, 0)
    n_steps = ceil_div(max(n - 1, 1), b) if n > 1 else 0
    v = np.zeros((n_sweeps, max(n_steps, 1), b), dtype=work_dtype)
    tau = np.zeros((n_sweeps, max(n_steps, 1)), dtype=work_dtype)
    d = np.zeros(n, dtype=np.float64)
    e_raw = np.zeros(max(n - 1, 0), dtype=work_dtype)
    if n_sweeps > 0 or n > 0:
        lib = get_lib()
        fn = lib.dlaf_band_to_tridiag_z if cplx else lib.dlaf_band_to_tridiag_d
        threads = nthreads if nthreads is not None and nthreads > 0 \
            else _chase_threads()
        with obs.span("stage.native.band_chase", fenced=False,
                      n=n, b=b, threads=threads):
            rc = fn(band_w.ctypes.data_as(ctypes.c_void_p),
                    ctypes.c_long(n), ctypes.c_long(b),
                    ctypes.c_long(max(n_steps, 1)),
                    v.ctypes.data_as(ctypes.c_void_p),
                    tau.ctypes.data_as(ctypes.c_void_p),
                    d.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                    e_raw.ctypes.data_as(ctypes.c_void_p),
                    ctypes.c_long(threads))
        if rc != 0:
            raise RuntimeError(f"native band_to_tridiag failed rc={rc}")
    phase = np.ones(n, dtype=work_dtype)
    if cplx:
        e = np.zeros(max(n - 1, 0), dtype=np.float64)
        for j in range(n - 1):
            mag = np.abs(e_raw[j])
            ph = e_raw[j] / mag if mag > 0 else 1.0
            phase[j + 1] = phase[j] * ph
            e[j] = mag
    else:
        e = np.real(e_raw)
    return TridiagResult(d=d, e=e, v=v[:, :n_steps if n_steps else 0],
                         tau=tau[:, :n_steps if n_steps else 0],
                         phase=phase, band=b)
