"""Build and load harness/ref_kernels.c: the reference's plain loops.

Built once per checkout into ``benchmark/.build/`` (a fixed path inside
the checkout, listed in .gitignore).  No compiler is an error: the
reference has one path, not a slow twin."""
from __future__ import annotations

import ctypes
import os
import shutil
import subprocess
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BUILD_DIR = os.path.join(os.path.dirname(HERE), ".build")
THREADS = 8          # the comparison runs once the window has closed

_LIB = None
_I64, _I32 = ctypes.c_int64, ctypes.c_int32


def _ptr(a):
    return a.ctypes.data_as(ctypes.c_void_p)


def lib():
    """The loaded library, built from the committed source if the build
    directory has none newer than the source."""
    global _LIB
    if _LIB is not None:
        return _LIB
    src = os.path.join(HERE, "ref_kernels.c")
    so = os.path.join(BUILD_DIR, "ref_kernels.so")
    if not (os.path.exists(so)
            and os.path.getmtime(so) >= os.path.getmtime(src)):
        cc = shutil.which(os.environ.get("CC", "cc")) or shutil.which("gcc")
        if cc is None:
            raise RuntimeError("benchmark: no C compiler for "
                               "harness/ref_kernels.c")
        os.makedirs(BUILD_DIR, exist_ok=True)
        tmp = f"{so}.{os.getpid()}"
        subprocess.run([cc, "-O2", "-shared", "-fPIC", "-o", tmp, src,
                        "-lm"], check=True)
        os.replace(tmp, so)
    _LIB = ctypes.CDLL(so)
    _LIB.ref_split_rows.restype = _I64
    return _LIB


def _chunks(n: int, parts: int):
    step = -(-n // parts)
    return [(lo, min(lo + step, n)) for lo in range(0, n, step)]


def _map(fn, n: int):
    """fn(lo, hi) over a few disjoint runs of rows, in threads where the
    run is long enough to pay for them (ctypes drops the GIL)."""
    runs = _chunks(n, THREADS) if n >= 200_000 else [(0, n)]
    if len(runs) == 1:
        return [fn(*runs[0])] if n else []
    with ThreadPoolExecutor(len(runs)) as pool:
        return list(pool.map(lambda r: fn(*r), runs))


def bin_rows(x: np.ndarray, uppers: np.ndarray,
             nbins: np.ndarray) -> np.ndarray:
    n, f = x.shape
    out = np.empty((n, f), np.uint8)
    L = lib()

    def run(lo, hi):
        L.ref_bin_rows(_ptr(x[lo:hi]), _I64(hi - lo), _I32(f),
                       _ptr(uppers), _ptr(nbins), _ptr(out[lo:hi]))
    _map(run, n)
    return out


def hist_rows(bins: np.ndarray, idx: np.ndarray, g: np.ndarray,
              h: np.ndarray) -> np.ndarray:
    """(F, 256, 3) sums of (g, h, 1) over the rows idx."""
    f = bins.shape[1]
    L = lib()

    def run(lo, hi):
        out = np.zeros((f, 256, 3), np.float64)
        L.ref_hist_rows(_ptr(bins), _I32(f), _ptr(idx[lo:hi]),
                        _I64(hi - lo), _ptr(g), _ptr(h), _ptr(out))
        return out
    parts = _map(run, len(idx))
    return sum(parts) if parts else np.zeros((f, 256, 3), np.float64)


def split_rows(bins: np.ndarray, idx: np.ndarray, feature: int,
               thr: int):
    n = len(idx)
    left = np.empty(n, np.int32)
    right = np.empty(n, np.int32)
    nl = lib().ref_split_rows(_ptr(bins), _I32(bins.shape[1]), _ptr(idx),
                              _I64(n), _I32(feature), _I32(thr),
                              _ptr(left), _ptr(right))
    return left[:nl].copy(), right[:n - nl].copy()


def route_rows(x: np.ndarray, feature, threshold, left, right) -> np.ndarray:
    """Leaf index of every row of x (raw values) in one tree."""
    n, f = x.shape
    feature = np.ascontiguousarray(feature, np.int32)
    threshold = np.ascontiguousarray(threshold, np.float64)
    left = np.ascontiguousarray(left, np.int32)
    right = np.ascontiguousarray(right, np.int32)
    out = np.empty(n, np.int32)
    L = lib()

    def run(lo, hi):
        L.ref_route_rows(_ptr(x[lo:hi]), _I64(hi - lo), _I32(f),
                         _ptr(feature), _ptr(threshold), _ptr(left),
                         _ptr(right), _I32(len(feature)), _ptr(out[lo:hi]))
    _map(run, n)
    return out


def lambdarank(score: np.ndarray, label: np.ndarray, disc: np.ndarray,
               bounds: np.ndarray, inv_max: np.ndarray, gains: np.ndarray,
               sigmoid: float, norm_floor: float):
    """(lambda, hessian) of every row: ``ref_lambdarank`` over runs of
    whole queries that hold about as many pairs each, in threads."""
    n, nq = len(score), len(bounds) - 1
    score = np.ascontiguousarray(score, np.float64)
    label = np.ascontiguousarray(label, np.int32)
    disc = np.ascontiguousarray(disc, np.float64)
    bounds = np.ascontiguousarray(bounds, np.int64)
    inv_max = np.ascontiguousarray(inv_max, np.float64)
    gains = np.ascontiguousarray(gains, np.float64)
    g = np.zeros(n)
    h = np.zeros(n)
    L = lib()
    pairs = np.cumsum(np.diff(bounds).astype(np.float64) ** 2)
    cuts = np.searchsorted(pairs, pairs[-1] * np.arange(1, THREADS)
                           / THREADS) if nq else []
    edges = np.unique(np.concatenate([[0], cuts, [nq]])).astype(np.int64)

    def run(q0, q1):
        L.ref_lambdarank(_ptr(score), _ptr(label), _ptr(disc),
                         _ptr(bounds), _ptr(inv_max), _I64(q0), _I64(q1),
                         _ptr(gains), ctypes.c_double(sigmoid),
                         ctypes.c_double(norm_floor), _ptr(g), _ptr(h))
    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(run, edges[:-1], edges[1:]))
    return g, h
