"""ctypes bindings for the native host-runtime library (``native/ktpu.cc``).

Auto-builds the library with the repo's Makefile on first use, under a
name keyed by the hash of ``native/ktpu.cc`` and its Makefile: a ``.so``
built from any other source is never loaded. Every entry point has a
pure-numpy fallback so the package works without a toolchain — the
native path is a performance tier, not a dependency.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_tried = False

#: feasibility sentinel shared with the device solvers (ops/assign.NEG)
NEG = -1e30


def _build() -> str:
    """Path of the library built from the tree's ``ktpu.cc``: the name
    carries the hash of the source and its Makefile, so only their build
    can sit there. Built to a private name and renamed into place, so
    concurrent builders (test workers) never load a half-written file."""
    h = hashlib.sha256()
    for name in ("ktpu.cc", "Makefile"):
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(f.read())
    digest = h.hexdigest()[:16]
    path = os.path.join(_NATIVE_DIR, f"libktpu-{digest}.so")
    if not os.path.exists(path):
        tmp = f"{path}.{os.getpid()}.tmp"
        subprocess.run(
            ["make", "-s", f"TARGET={os.path.basename(tmp)}"],
            cwd=_NATIVE_DIR, check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, path)
    return path


def _load() -> Optional[ctypes.CDLL]:
    """Build (make) + dlopen the library once; None if unavailable."""
    global _lib, _lib_tried
    with _lock:
        if _lib is not None or _lib_tried:
            return _lib
        _lib_tried = True
        try:
            lib = ctypes.CDLL(_build())
            lib.hungarian_solve.argtypes = [
                ctypes.c_int32, ctypes.c_int32,
                np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            ]
            lib.aggregate_usage.argtypes = [
                ctypes.c_int32, ctypes.c_int32,
                np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                ctypes.c_int32,
                np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
            ]
            _lib = lib
        except Exception:
            _lib = None
        return _lib


def available() -> bool:
    return _load() is not None


# ---------------------------------------------------------------------------
# exact assignment
# ---------------------------------------------------------------------------


def hungarian(score: np.ndarray) -> np.ndarray:
    """Exact max-total-score assignment of rows (pods) to columns (node
    slots), one row per column. ``score`` (P, S) f32; entries <= NEG/10
    are infeasible. Returns (P,) int32 column per row, -1 = unassigned.

    The augmenting-path algorithm computes a perfect matching over rows,
    so every call pads P dummy "unassigned" columns whose score (-1e9)
    sits strictly between any real score and the infeasible sentinel:
    the optimum then maximizes cardinality first (every dummy taken costs
    more than any feasible edge), score total second — exactly the
    scheduling objective — and rows infeasible everywhere park on dummies
    instead of distorting the matching with sentinel-cost ties."""
    score = np.ascontiguousarray(score, np.float32)
    P, S = score.shape
    if P == 0 or S == 0:
        return np.full((P,), -1, np.int32)
    pad = np.full((P, P), -1e9, np.float32)
    padded = np.ascontiguousarray(np.concatenate([score, pad], axis=1))
    out = np.empty((P,), np.int32)
    lib = _load()
    if lib is not None:
        lib.hungarian_solve(P, padded.shape[1], padded, out)
    else:
        out = _hungarian_py(padded)
    out[out >= S] = -1  # dummy columns = unassigned
    return out


def _hungarian_py(score: np.ndarray) -> np.ndarray:
    """Numpy fallback: same shortest-augmenting-path algorithm."""
    BIG = 1e12
    P, S = score.shape
    # graftlint: disable=R5 -- host Hungarian oracle: f64 keeps the dual
    # potentials' tie-break ordering exact; nothing here rides the device
    cost = np.where(score <= -1e29, BIG, -score.astype(np.float64))
    u = np.zeros(P + 1)
    v = np.zeros(S + 1)
    match = np.zeros(S + 1, np.int64)
    way = np.zeros(S + 1, np.int64)
    for r in range(1, P + 1):
        minv = np.full(S + 1, np.inf)
        used = np.zeros(S + 1, bool)
        j0 = 0
        match[0] = r
        while True:
            used[j0] = True
            i0 = match[j0]
            cur = cost[i0 - 1, :] - u[i0] - v[1:]
            better = (~used[1:]) & (cur < minv[1:])
            minv[1:][better] = cur[better]
            way[1:][better] = j0
            free = ~used[1:]
            if not free.any():
                break
            j1 = 1 + int(np.argmin(np.where(free, minv[1:], np.inf)))
            delta = minv[j1]
            u[match[used]] += delta
            v[used] -= delta
            minv[1:][free] -= delta
            j0 = j1
            if match[j0] == 0:
                break
        while j0:
            j1 = way[j0]
            match[j0] = match[j1]
            j0 = j1
    out = np.full((P,), -1, np.int32)
    for j in range(1, S + 1):
        r = match[j]
        if r > 0 and cost[r - 1, j - 1] < BIG:
            out[r - 1] = j - 1
    return out


def exact_assign(
    score: np.ndarray, mask: np.ndarray, capacity: np.ndarray
) -> np.ndarray:
    """Assignment with per-node multi-capacity via slot expansion: node j
    contributes ``capacity[j]`` identical columns. ``score``/``mask``
    (P, N); ``capacity`` (N,) ints >= 0. Returns (P,) node index or -1.

    This is the exact counterpart of one batch_assign round for workloads
    where total score matters more than wall-clock (gang/offline packing);
    resource-vector feasibility beyond slot counts must be pre-encoded in
    ``mask``/``capacity`` by the caller."""
    P, N = score.shape
    cap = np.minimum(np.asarray(capacity, np.int64), P)
    cols = np.repeat(np.arange(N), cap)  # slot -> node
    if len(cols) == 0:
        return np.full((P,), -1, np.int32)
    s = np.where(mask, score, NEG)[:, cols]
    slot = hungarian(np.ascontiguousarray(s, np.float32))
    out = np.full((P,), -1, np.int32)
    ok = slot >= 0
    out[ok] = cols[slot[ok]]
    return out


# ---------------------------------------------------------------------------
# snapshot aggregation
# ---------------------------------------------------------------------------


def aggregate_usage(
    pod_req: np.ndarray,
    pod_nz: np.ndarray,
    pod_row: np.ndarray,
    out_req: np.ndarray,
    out_nz: np.ndarray,
) -> None:
    """In-place scatter-add of pod requests into node usage arrays (the
    NodeInfo.AddPod accumulation). Rows < 0 skip."""
    pod_req = np.ascontiguousarray(pod_req, np.float32)
    pod_nz = np.ascontiguousarray(pod_nz, np.float32)
    pod_row = np.ascontiguousarray(pod_row, np.int32)
    lib = _load()
    if lib is not None and len(pod_row):
        assert out_req.dtype == np.float32 and out_req.flags["C_CONTIGUOUS"]
        assert out_nz.dtype == np.float32 and out_nz.flags["C_CONTIGUOUS"]
        lib.aggregate_usage(
            len(pod_row), pod_req.shape[1], pod_req, pod_nz, pod_row,
            out_req.shape[0], out_req, out_nz,
        )
        return
    ok = pod_row >= 0
    np.add.at(out_req, pod_row[ok], pod_req[ok])
    np.add.at(out_nz, pod_row[ok], pod_nz[ok])
