"""Native (C++) segment-build hot loops, compiled on first use.

The compute path is JAX/XLA on the TPU; the segment BUILD is host work
whose hot loops (cube grouping, grouped stats, fixed-bit packing) live in
seglib.cpp, compiled here with g++ -O3 into a cached shared object and
bound via ctypes (no pybind11 in the image). Every entry point has a
numpy fallback so the package works without a compiler — `lib()` returns
None then and callers keep their pure-python path.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

_SRC = os.path.join(os.path.dirname(__file__), "seglib.cpp")


def _build_dir() -> str:
    d = os.environ.get("PINOT_TPU_NATIVE_CACHE") or \
        os.path.join(os.path.expanduser("~"), ".cache", "pinot_tpu_native")
    os.makedirs(d, exist_ok=True)
    return d


def lib() -> Optional[ctypes.CDLL]:
    """The compiled library, building it if needed; None when no g++."""
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if os.environ.get("PINOT_TPU_NO_NATIVE") == "1":
            return None
        try:
            with open(_SRC, "rb") as fh:  # tpulint: disable=lock-blocking -- one-time native build memoized under the module lock: double-checked compile, only ever blocks on first use
                tag = hashlib.sha256(fh.read()).hexdigest()[:16]
            so = os.path.join(_build_dir(), f"seglib-{tag}.so")
            if not os.path.exists(so):
                tmp = so + f".tmp{os.getpid()}"
                subprocess.run(  # tpulint: disable=lock-blocking -- same one-time-build invariant: racing builders would compile the same .so twice and corrupt the rename dance
                    ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
                     "-o", tmp, _SRC],
                    check=True, capture_output=True)
                os.replace(tmp, so)      # atomic: racing builders agree
            cdll = ctypes.CDLL(so)
            _bind(cdll)
            _LIB = cdll
        except Exception:  # noqa: BLE001 — fallback is pure numpy
            _LIB = None
        return _LIB


def loaded() -> Optional[ctypes.CDLL]:
    """The library if `lib()` has already built and bound it, else None.
    For callers on a query path, which must never compile: the library
    is built where cubes are loaded (startree/cube.py)."""
    return _LIB


def _bind(cdll: ctypes.CDLL) -> None:
    i64, i32, u32, f64, vp = (ctypes.c_int64, ctypes.c_int32,
                              ctypes.c_uint32, ctypes.c_double,
                              ctypes.c_void_p)
    cdll.pack_bits_u32.argtypes = [vp, i64, ctypes.c_int, vp, i64]
    cdll.unpack_bits_u32.argtypes = [vp, i64, ctypes.c_int, i64, vp]
    cdll.group_index_i64.restype = i64
    cdll.group_index_i64.argtypes = [vp, i64, vp, vp]
    cdll.group_counts_i64.argtypes = [vp, i64, i64, vp]
    cdll.group_stats_f64.argtypes = [vp, vp, i64, i64, vp, vp, vp]
    cdll.group_stats_sorted_f64.argtypes = [vp, vp, i64, i64, vp, vp, vp,
                                            vp]
    cdll.packed_key_i64.argtypes = [vp, vp, ctypes.c_int, i64, vp]
    cdll.cube_select_gather.restype = i64
    cdll.cube_select_gather.argtypes = [i64, vp, vp, vp, vp, i64, vp, i64,
                                        i64, i64, vp, vp, vp, vp]


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


# ---------------------------------------------------------------------------
# numpy-signature wrappers (None return = caller takes the numpy path)
# ---------------------------------------------------------------------------


def pack_bits(ids: np.ndarray, num_bits: int) -> Optional[np.ndarray]:
    L = lib()
    if L is None:
        return None
    ids = np.ascontiguousarray(ids, dtype=np.int32)
    n = len(ids)
    n_words = (n * num_bits + 31) // 32
    out = np.empty(n_words, np.uint32)
    L.pack_bits_u32(_ptr(ids), n, num_bits, _ptr(out), n_words)
    return out


def unpack_bits(words: np.ndarray, num_bits: int,
                n: int) -> Optional[np.ndarray]:
    L = lib()
    if L is None:
        return None
    words = np.ascontiguousarray(words, dtype=np.uint32)
    out = np.empty(n, np.int32)
    L.unpack_bits_u32(_ptr(words), len(words), num_bits, n, _ptr(out))
    return out


def group_index(key: np.ndarray):
    """(sorted unique keys, per-row rank int32) or None (no native lib /
    alloc failure)."""
    L = lib()
    if L is None:
        return None
    key = np.ascontiguousarray(key, dtype=np.int64)
    n = len(key)
    uniq = np.empty(n, np.int64)
    rank = np.empty(n, np.int32)
    g = L.group_index_i64(_ptr(key), n, _ptr(uniq), _ptr(rank))
    if g < 0:
        return None
    return uniq[:g].copy(), rank


def group_counts(rank: np.ndarray, g: int) -> Optional[np.ndarray]:
    L = lib()
    if L is None:
        return None
    rank = np.ascontiguousarray(rank, dtype=np.int32)
    out = np.empty(g, np.int64)
    L.group_counts_i64(_ptr(rank), len(rank), g, _ptr(out))
    return out


def group_stats(rank: np.ndarray, vals: np.ndarray, g: int):
    """(sums, mins, maxs) float64 [g] or None."""
    L = lib()
    if L is None:
        return None
    rank = np.ascontiguousarray(rank, dtype=np.int32)
    vals = np.ascontiguousarray(vals, dtype=np.float64)
    sums = np.empty(g, np.float64)
    mins = np.empty(g, np.float64)
    maxs = np.empty(g, np.float64)
    L.group_stats_f64(_ptr(rank), _ptr(vals), len(rank), g,
                      _ptr(sums), _ptr(mins), _ptr(maxs))
    return sums, mins, maxs


def group_stats_sorted(order: np.ndarray, starts: np.ndarray, n: int,
                       vals: np.ndarray):
    """(sums, mins, maxs) per sorted-key run, gather fused in; None
    when no native lib."""
    L = lib()
    if L is None:
        return None
    order = np.ascontiguousarray(order, dtype=np.int64)
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    vals = np.ascontiguousarray(vals, dtype=np.float64)
    g = len(starts)
    sums = np.empty(g, np.float64)
    mins = np.empty(g, np.float64)
    maxs = np.empty(g, np.float64)
    L.group_stats_sorted_f64(_ptr(order), _ptr(starts), g, n, _ptr(vals),
                             _ptr(sums), _ptr(mins), _ptr(maxs))
    return sums, mins, maxs


def packed_key(dims, cards) -> Optional[np.ndarray]:
    """Mixed-radix key over int32 dim lanes in one native pass."""
    L = lib()
    if L is None or not dims:
        return None
    arrs = [np.ascontiguousarray(d, dtype=np.int32) for d in dims]
    n = len(arrs[0])
    ptrs = (ctypes.c_void_p * len(arrs))(
        *[a.ctypes.data_as(ctypes.c_void_p).value for a in arrs])
    cards64 = np.asarray(cards, dtype=np.int64)
    out = np.empty(n, np.int64)
    L.packed_key_i64(ptrs, _ptr(cards64), len(arrs), n, _ptr(out))
    return out


def cube_select_gather(tables, n_gcols: int, n_stats: int, cap: int,
                       block_limit: int):
    """One query's cube descent over all its segments in one foreign
    call. `tables` are seglib.cpp's five (seg_hdr, preds, ivs, gcols,
    stats), each a flat list of Python ints; the lanes behind the
    addresses in them are kept alive by the caller. -> (codes [G, m],
    counts [m], stat lanes [K, m], [matched, examined] a segment), or
    None where the stepwise numpy path has to answer: no library
    loaded, a level past `block_limit`, a lane id outside its table.
    `cap` is a guess at the rows the blocks hold; a short one costs a
    second call at the exact size."""
    L = loaded()
    if L is None:
        return None
    n_seg = len(tables[0]) // 4
    flat = np.array([v for t in tables for v in t], dtype=np.int64)
    addr, at = [], flat.ctypes.data
    for t in tables:
        addr.append(at)
        at += 8 * len(t)
    while True:
        # one buffer for what comes back as int64: codes, counts, and
        # each segment's matched and examined
        ints = np.empty((n_gcols + 1) * cap + 2 * n_seg, np.int64)
        lanes = np.empty((n_stats, cap), np.float64)
        out = ints.ctypes.data
        per_seg = ints[(n_gcols + 1) * cap:]
        m = L.cube_select_gather(
            n_seg, addr[0], addr[1], addr[2], addr[3], n_gcols, addr[4],
            n_stats, block_limit, cap, out, out + 8 * n_gcols * cap,
            _ptr(lanes), out + 8 * (n_gcols + 1) * cap)
        if m != -2:
            break
        cap = sum(per_seg.tolist()[1::2])
    if m < 0:
        return None
    codes = ints[:n_gcols * cap].reshape(n_gcols, cap)
    return (codes[:, :m], ints[n_gcols * cap:n_gcols * cap + m],
            lanes[:, :m], per_seg.tolist())
