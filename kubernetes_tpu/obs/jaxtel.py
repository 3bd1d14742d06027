"""Runtime JAX telemetry — compile-cache and transfer accounting the
static linter (kubernetes_tpu/lint) cannot see.

graftlint's R3 catches jit-in-a-loop *statically*; this module measures
the dynamic twin: whether the arguments a call site actually feeds its
jitted kernel keep the same abstract signature (shapes + dtypes +
static keys) call over call. A new signature at a known site is a
retrace (XLA recompiles); many retraces inside a short call window is a
retrace STORM — the exact failure mode bucketed batch shapes
(utils/interner.bucket_size) exist to prevent.

Everything here runs on the HOST side of the boundary, *before* the
jitted call: the digest reads only ``.shape``/``.dtype`` metadata (no
device sync), so instrumentation adds zero host syncs inside jitted
code — the lint gate stays green by construction.

Transfer accounting rides the same idea: :meth:`JaxTelemetry.readback`
wraps the ``np.asarray(...)`` host boundaries the driver already
declares, charging the bytes moved to a named site, and
:meth:`record_transfer` counts host->device uploads from array metadata.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Dict, Optional, Tuple

import numpy as np

from kubernetes_tpu.sanitize import make_lock


def _leaf_sig(x) -> object:
    """Abstract signature of one pytree leaf: (shape, dtype) for anything
    array-like, the value itself for hashable host scalars, else repr."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype))
    try:
        hash(x)
        return x
    except TypeError:
        return repr(x)


def abstract_digest(*trees, static=None) -> Tuple:
    """Hashable digest of the abstract (shape/dtype) signature of the
    given pytrees plus a static key — what jax's compile cache keys on
    for the dynamic arguments. Reads metadata only: no device sync."""
    import jax

    sigs = []
    for t in trees:
        if t is None:
            sigs.append(None)
            continue
        leaves = jax.tree_util.tree_leaves(t)
        sigs.append(tuple(_leaf_sig(x) for x in leaves))
    return (tuple(sigs), static)


def tree_nbytes(*trees) -> int:
    """Total byte size of every array-like leaf (metadata only)."""
    import jax

    total = 0
    for t in trees:
        if t is None:
            continue
        for x in jax.tree_util.tree_leaves(t):
            shape = getattr(x, "shape", None)
            dtype = getattr(x, "dtype", None)
            if shape is None or dtype is None:
                continue
            n = 1
            for d in shape:
                n *= int(d)
            total += n * np.dtype(str(dtype)).itemsize
    return total


#: Pallas kernel routes, "kernel:route" -> programs traced on that route
#: (route: pallas | interpret | jnp | jnp:<why>). The kernels pick their
#: route by a static shape rule while the caller is traced, so one entry
#: per compiled program says which implementation it holds — the
#: process-wide view of a choice no per-call digest can see.
_KERNEL_ROUTES: Dict[str, int] = {}
_KERNEL_ROUTES_LOCK = threading.Lock()


def record_kernel_route(kernel: str, route: str) -> None:
    """Count one trace of ``kernel`` on ``route`` (trace time only)."""
    key = f"{kernel}:{route}"
    with _KERNEL_ROUTES_LOCK:
        _KERNEL_ROUTES[key] = _KERNEL_ROUTES.get(key, 0) + 1


def kernel_routes() -> Dict[str, int]:
    with _KERNEL_ROUTES_LOCK:
        return dict(sorted(_KERNEL_ROUTES.items()))


class JaxTelemetry:
    """Per-site compile-cache observation + transfer accounting.

    ``record_call(site, *trees, static=...)`` classifies the call:

    - first digest ever seen at the site -> "compile" (cold miss);
    - digest already seen -> "hit";
    - NEW digest at a site that already compiled -> "retrace" (the
      counter the acceptance gate pins: exactly one increment when the
      batch shape changes).

    Retrace storms: >= ``storm_threshold`` retraces within the last
    ``storm_window`` calls at one site increments the storm counter once
    per crossing (the window then resets) — deterministic, count-based,
    no wall clock."""

    def __init__(self, metrics=None, storm_threshold: int = 8,
                 storm_window: int = 64,
                 signature_capacity: int = 4096,
                 lock_factory=None) -> None:
        self.metrics = metrics
        self.storm_threshold = max(1, int(storm_threshold))
        self.storm_window = max(1, int(storm_window))
        #: per-site cap on retained signatures — a sustained retrace
        #: storm mints a new digest every cycle, and an unbounded set
        #: would leak for as long as the pathology lasts (the recorder
        #: and trace rings are hard-bounded for the same reason). LRU:
        #: evicting a signature only means its NEXT appearance counts as
        #: a retrace again, which under a storm it effectively is.
        self.signature_capacity = max(1, int(signature_capacity))
        #: site -> insertion-ordered {digest: None} used as an LRU set
        self._seen: Dict[str, dict] = {}
        #: one lock for every counter dict: record_call/record_transfer
        #: run on the scheduler thread while snapshot() serves the
        #: /debug/flightrecorder handler thread — an unlocked dict
        #: iteration there can raise "dictionary changed size during
        #: iteration" mid-incident
        self._lock = make_lock(lock_factory, "obs.jaxtel")
        self.calls: Dict[str, int] = {}
        self.hits: Dict[str, int] = {}
        self.compiles: Dict[str, int] = {}
        self.retraces: Dict[str, int] = {}
        self.storms: Dict[str, int] = {}
        #: call indices (per site) of recent retraces, for the storm window
        self._retrace_at: Dict[str, deque] = {}
        #: (site, direction) -> [count, bytes]
        self.transfers: Dict[Tuple[str, str], list] = {}

    # -- compile cache ------------------------------------------------------

    def record_call(self, site: str, *trees, static=None,
                    warmup: bool = False) -> str:
        """Record one jitted-call observation; returns the class
        ("hit" | "compile" | "retrace"). ``warmup=True`` registers an
        AHEAD-OF-TIME compile (Scheduler.warmup's bucket sweep): a new
        signature there counts as a deliberate compile, never a retrace —
        retraces exist to flag recompiles sneaking onto the hot path."""
        digest = abstract_digest(*trees, static=static)
        with self._lock:
            seen = self._seen.setdefault(site, {})
            n = self.calls.get(site, 0) + 1
            self.calls[site] = n
            stormed = False
            if digest in seen:
                kind = "hit"
                self.hits[site] = self.hits.get(site, 0) + 1
                seen.pop(digest)  # re-inserted below as most-recent
            elif warmup or (not seen and not self.compiles.get(site)):
                kind = "compile"
                self.compiles[site] = self.compiles.get(site, 0) + 1
            else:
                kind = "retrace"
                self.retraces[site] = self.retraces.get(site, 0) + 1
                window = self._retrace_at.setdefault(site, deque())
                window.append(n)
                while window and n - window[0] >= self.storm_window:
                    window.popleft()
                if len(window) >= self.storm_threshold:
                    self.storms[site] = self.storms.get(site, 0) + 1
                    window.clear()
                    stormed = True
            seen[digest] = None
            while len(seen) > self.signature_capacity:
                seen.pop(next(iter(seen)))
        m = self.metrics
        if m is not None:
            m.jax_compile_cache.inc(site=site, result=kind)
            if kind == "retrace":
                m.jax_retraces.inc(site=site)
            if stormed:
                m.jax_retrace_storms.inc(site=site)
        return kind

    def retrace_total(self, site: Optional[str] = None) -> int:
        with self._lock:
            if site is not None:
                return self.retraces.get(site, 0)
            return sum(self.retraces.values())

    def storm_total(self, site: Optional[str] = None) -> int:
        """Storm detections across all sites (or one) — the incident
        recorder's per-cycle delta source, same locking as
        :meth:`retrace_total`."""
        with self._lock:
            if site is not None:
                return self.storms.get(site, 0)
            return sum(self.storms.values())

    # -- transfers ----------------------------------------------------------

    def record_transfer(self, site: str, direction: str, nbytes: int) -> None:
        """Charge ``nbytes`` moved across the device boundary to a site.
        ``direction``: "h2d" (upload) or "d2h" (readback)."""
        with self._lock:
            row = self.transfers.setdefault((site, direction), [0, 0])
            row[0] += 1
            row[1] += int(nbytes)
        if self.metrics is not None:
            self.metrics.host_transfer_bytes.inc(
                int(nbytes), site=site, direction=direction)
            self.metrics.host_transfers.inc(site=site, direction=direction)
            if direction == "d2h":
                # the readback wall's dedicated meter (one label, so a
                # dashboard sums sites without direction filtering);
                # duck-typed so partial metrics fakes stay valid
                rb = getattr(self.metrics, "readback_bytes", None)
                if rb is not None:
                    rb.inc(int(nbytes), site=site)

    def d2h_bytes_total(self) -> int:
        """Total d2h bytes across every site — the flight recorder diffs
        this per cycle into CycleRecord.readback_bytes."""
        with self._lock:
            return sum(row[1] for (site, d), row in self.transfers.items()
                       if d == "d2h")

    def readback(self, site: str, x):
        """The declared d2h host boundary: materialize ``x`` — a single
        array or a pytree of arrays (NamedTuple structure preserved) —
        on host in one ``jax.device_get`` and account the total bytes as
        ONE transfer at the site, instead of one sync + one accounting
        entry per leaf."""
        import jax

        host = jax.device_get(x)
        nbytes = sum(
            np.asarray(leaf).nbytes
            for leaf in jax.tree_util.tree_leaves(host))
        self.record_transfer(site, "d2h", nbytes)
        return host

    def record_upload(self, site: str, *trees) -> None:
        """Account an h2d upload from array metadata (no sync)."""
        self.record_transfer(site, "h2d", tree_nbytes(*trees))

    # -- introspection ------------------------------------------------------

    def signature_count(self, site: Optional[str] = None) -> int:
        """Retained signature-LRU size — per site, or summed across all
        sites. Locked: the soak sentinel samples from the maintenance
        thread while record_call inserts on the scheduler thread. Each
        per-site set is capped at ``signature_capacity``, so this total
        is bounded by sites x capacity; the sentinel watches it anyway
        because an unexpected NEW site minted per phase would still grow
        it without bound."""
        with self._lock:
            if site is not None:
                return len(self._seen.get(site, ()))
            return sum(len(s) for s in self._seen.values())

    def snapshot(self) -> dict:
        """JSON-shaped view for /debug endpoints and the flight
        recorder; locked — the handler thread reads while the scheduler
        thread inserts new sites."""
        with self._lock:
            return {
                "sites": {
                    site: {
                        "calls": self.calls.get(site, 0),
                        "hits": self.hits.get(site, 0),
                        "compiles": self.compiles.get(site, 0),
                        "retraces": self.retraces.get(site, 0),
                        "storms": self.storms.get(site, 0),
                    }
                    for site in sorted(self.calls)
                },
                "transfers": {
                    f"{site}:{direction}": {"count": row[0], "bytes": row[1]}
                    for (site, direction), row in sorted(
                        self.transfers.items())
                },
                "kernel_routes": kernel_routes(),
            }
