"""Scheduler cache — in-memory truth about nodes and (assumed) pods, with
generation-tracked incremental snapshot packing.

Reference: ``pkg/scheduler/internal/cache/cache.go``. Two ideas carry over
directly:

1. **Assumed-pod state machine** (``cache/interface.go:36-47``): the driver
   optimistically AssumePod()s a pod onto its chosen node the moment the
   algorithm picks it, so the next cycle sees the capacity as used while the
   binding RPC is still in flight. FinishBinding starts a TTL; if the bound
   pod add never arrives from the watch before the TTL, the assumption
   expires and capacity frees (``cache.go:674`` cleanupAssumedPods).
   ForgetPod undoes an assumption on bind failure (``scheduler.go:447``).

2. **Generation-ordered incremental snapshots** (``cache.go:211``
   UpdateNodeInfoSnapshot, ``cache.go:135`` moveNodeInfoToHead): every
   mutation bumps a per-node generation; snapshotting recomputes only rows
   whose generation passed the last snapshot. Here the columnar NodeTable is
   cached and only dirty node rows are repacked (a full repack happens only
   when universe widths or the node set shape change — rare by design,
   since widths are power-of-two bucketed).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Set, Tuple

import numpy as np

from kubernetes_tpu.api.types import Node, Pod
from kubernetes_tpu.obs.trace import annotate
from kubernetes_tpu.sanitize import assert_held, make_lock
from kubernetes_tpu.snapshot import (
    NodeTable,
    SnapshotPacker,
    owner_anchor_pairs,
)

#: cache.go — factory.NewConfigFactory wires a 30 s assumed-pod TTL.
DEFAULT_ASSUME_TTL_S = 30.0

# assumed-pod states
_ASSUMED = "assumed"  # Assume() called, bind in flight
_EXPIRING = "expiring"  # FinishBinding() called, TTL armed
_ADDED = "added"  # confirmed via watch AddPod


class CacheError(Exception):
    pass


class SchedulerCache:
    """Host-side cluster cache. Thread-free by design (the driver is a
    single loop around device dispatch); the watch pump calls the mutators
    between cycles."""

    def __init__(
        self,
        packer: Optional[SnapshotPacker] = None,
        ttl_s: float = DEFAULT_ASSUME_TTL_S,
        clock: Callable[[], float] = time.monotonic,
        max_dirty_frac: float = 0.25,
        lock_factory=None,
    ) -> None:
        self.packer = packer or SnapshotPacker()
        self.ttl_s = ttl_s
        self.clock = clock
        self._nodes: Dict[str, Node] = {}
        self._pods_by_node: Dict[str, Dict[str, Pod]] = {}
        self._pod_state: Dict[str, str] = {}  # key -> assumed state
        self._pod_node: Dict[str, str] = {}  # key -> node name
        self._pod_deadline: Dict[str, float] = {}  # key -> expiry (EXPIRING only)
        self._dirty: Set[str] = set()  # node names needing row repack
        self._shape_dirty = True  # node set / widths changed => full repack
        # ---- SelectorSpread owner counts (kept on pod insert/drop) -------
        #: owner-set id -> {node name: cached pods there matching the set};
        #: nonzero entries only. The solve reads the columns of its
        #: batch's owner sets (owner_columns), so a new owner set costs
        #: one column, never a repack of the cached pods
        self._owner_counts: Dict[int, Dict[str, int]] = {}
        #: owner sets below this id have their counts held; a set interned
        #: later is counted on first need (_sync_owner_sets)
        self._owner_synced = 0
        #: (namespace, key, value) -> {pod key: node name} of the cached
        #: pods carrying that label — where a new owner set's counts come
        #: from (its selectors' anchor pair, snapshot.owner_anchor_pairs)
        self._label_pods: Dict[Tuple[str, str, str], Dict[str, str]] = {}
        # cached snapshot state
        self._table: Optional[NodeTable] = None
        self._row_of: Dict[str, int] = {}
        self._widths_key: Optional[Tuple] = None
        # ---- device-resident snapshot state (device_snapshot) ------------
        #: dirty-row fraction above which patching the resident device
        #: table costs more than re-uploading it (the delta pack + scatter
        #: approach full-pack cost as the fraction grows)
        self.max_dirty_frac = max_dirty_frac
        self._dev = None  # resident ops.arrays.DeviceNodes
        self._dev_pad: int = 0  # its padded row count
        #: host refreshes the device hasn't applied yet: [(idx, sub)]
        #: deltas queued by _refresh_host (a host-only snapshot() caller
        #: consumes the dirty set; the device drains this queue later)
        self._pending_dev: List[Tuple[List[int], NodeTable]] = []
        #: a full host repack happened since the device last uploaded
        self._dev_stale: bool = True
        #: serializes snapshot refreshes: the cache is thread-free by
        #: design for MUTATIONS (driver loop), but server.py's
        #: extender-serving handler threads call the host snapshot()
        #: concurrently with the scheduler's device_snapshot() — without
        #: this lock a half-patched host table could be uploaded and
        #: then persist as the resident device snapshot
        self._snap_lock = make_lock(lock_factory, "cache.snap", "rlock")
        #: how the last device_snapshot() was produced: full | delta | clean
        self.last_snapshot_mode: str = ""
        #: host rows actually (re)packed + uploaded by the last call — the
        #: observability surface for "cost proportional to what changed"
        self.last_upload_rows: int = 0
        #: bytes the last call moved across the device boundary (full
        #: table or delta rows) — feeds the h2d transfer accounting
        self.last_upload_nbytes: int = 0
        #: faults.FaultInjector (or None): the chaos seam for the
        #: device-resident snapshot — "snapshot:device" rules
        #: (device_lost / device_oom) raise from device_snapshot(),
        #: exercising the scheduler's resident-rebuild recovery
        self.fault_injector = None
        #: obs.memledger.MemoryLedger (or None): device-memory
        #: accounting for the resident table + score summary — the
        #: scheduler attaches it post-construction (duck-typed, same
        #: contract as the injector above). Registrations ride the
        #: cache's OWN upload/drop edges so the ledger can never show
        #: a resident this cache already dropped
        self.memledger = None
        # ---- incremental-solve score cache (ops/fused_score) ---------
        #: device-resident NodeSummary aligned row-for-row with the
        #: resident DeviceNodes: the per-node slice of the score/
        #: feasibility plane the restricted solve picks candidates
        #: from. Maintained HERE, next to the snapshot, under the same
        #: full-vs-delta discipline — full uploads invalidate it
        #: (rebuilt lazily from the new resident table), delta cycles
        #: patch exactly the scattered rows with the same donated-
        #: scatter, clean cycles touch nothing — so it can never drift
        #: from the table it summarizes.
        self._summary = None
        #: bumps whenever the summary's row universe is rebuilt (full
        #: upload, drop, mesh change, enable) — the scheduler keys its
        #: warm-solve state (Sinkhorn potentials) on it so takeover /
        #: device-loss / epoch-growth invalidation is one comparison
        self.summary_generation = 0
        #: node COLUMNS patched by the last device_snapshot() call (the
        #: cycle's dirty frontier — candidate selection boosts them);
        #: empty on clean cycles, meaningless on full rebuilds (the
        #: whole plane was recomputed)
        self.last_patched_idx: List[int] = []
        self._score_cache_on = False
        self._summary_flags = {"honor_conditions": True,
                               "prefer_packed": False}
        #: the last score_summary() call had to REBUILD the plane from
        #: scratch (post-drop lazy build) — the scheduler reports zero
        #: reuse for that cycle instead of pretending the fresh plane
        #: was cached
        self.last_summary_rebuilt = False
        #: jax.sharding.Mesh (or None): the sharded execution backend's
        #: node-axis mesh (set_mesh). When set, the resident DeviceNodes
        #: lives SHARDED along N across the mesh: full uploads place via
        #: parallel.shard_nodes, and the delta scatter patches each
        #: shard locally (the re-packed rows + indices replicate; the
        #: donated scatter keeps the resident sharding, so no cross-
        #: device traffic beyond the small replicated delta)
        self.mesh = None

    # -- introspection -----------------------------------------------------

    def node(self, name: str) -> Optional[Node]:
        return self._nodes.get(name)

    def nodes(self) -> List[Node]:
        return list(self._nodes.values())

    def pods_on(self, node_name: str) -> List[Pod]:
        return list(self._pods_by_node.get(node_name, {}).values())

    def is_assumed(self, pod_key: str) -> bool:
        return self._pod_state.get(pod_key) in (_ASSUMED, _EXPIRING)

    def assumed_keys(self) -> List[str]:
        """Keys of every pod still in an assumed state (ASSUMED or
        EXPIRING) — what a takeover reconciliation diffs against the
        relisted hub truth, and what a deposed leader drains."""
        return [k for k, s in self._pod_state.items()
                if s in (_ASSUMED, _EXPIRING)]

    def pod_states(self) -> Dict[str, str]:
        """key -> "assumed" | "bound" for every cached pod — the
        state-conservation auditor's view (obs/audit.py): assumed covers
        ASSUMED and EXPIRING (bind in flight / TTL armed), bound is the
        watch-confirmed ADDED state."""
        return {
            k: ("assumed" if s in (_ASSUMED, _EXPIRING) else "bound")
            for k, s in self._pod_state.items()
        }

    def pod_count(self) -> int:
        return sum(len(m) for m in self._pods_by_node.values())

    def group_members(self, group: str) -> int:
        """Count of cached pods (assumed or bound) carrying
        ``pod_group == group`` — the gang gate's credit for members
        placed in EARLIER cycles. Without it a gang member whose bind
        failed transiently re-queues ALONE and can never satisfy
        minMember from inside its own batch: the group reads
        incomplete forever while its siblings run (a livelock, not a
        guard)."""
        return sum(1 for m in self._pods_by_node.values()
                   for p in m.values() if p.pod_group == group)

    def pod(self, key: str) -> Optional[Pod]:
        node = self._pod_node.get(key)
        if node is None:
            return None
        return self._pods_by_node.get(node, {}).get(key)

    def node_count(self) -> int:
        return len(self._nodes)

    # -- assumed-pod state machine ----------------------------------------

    def assume_pod(self, pod: Pod, node_name: str) -> None:
        """cache.go:275 AssumePod — place the pod in the cache now, before
        the binding is durable."""
        key = pod.key()
        if key in self._pod_state:
            raise CacheError(f"pod {key} already in cache ({self._pod_state[key]})")
        self.packer.intern_pod(pod)
        p = dataclasses.replace(pod, node_name=node_name)
        self._store(p, node_name)
        self._pod_state[key] = _ASSUMED
        self._pod_node[key] = node_name
        self._mark_dirty(node_name)

    def finish_binding(self, pod_key: str) -> None:
        """cache.go FinishBinding — arm the TTL; the watch-confirmed AddPod
        must arrive before it fires."""
        if self._pod_state.get(pod_key) == _ASSUMED:
            self._pod_state[pod_key] = _EXPIRING
            self._pod_deadline[pod_key] = self.clock() + self.ttl_s

    def forget_pod(self, pod_key: str) -> None:
        """cache.go ForgetPod — undo an assumption (bind failed)."""
        if self._pod_state.get(pod_key) not in (_ASSUMED, _EXPIRING):
            raise CacheError(f"pod {pod_key} is not assumed")
        self._drop_pod(pod_key)

    def pop_expired(self) -> List[Pod]:
        """cache.go:674 cleanupAssumedPods — expire overdue assumptions,
        returning the expired POD OBJECTS (node_name still carrying the
        node they were assumed onto) so the driver can log, count, emit
        an event, and requeue them instead of letting the pod vanish
        silently (scheduler._reap_expired_assumptions)."""
        now = self.clock()
        expired_keys = [
            k
            for k, d in self._pod_deadline.items()
            if d <= now and self._pod_state.get(k) == _EXPIRING
        ]
        out: List[Pod] = []
        for k in expired_keys:
            p = self.pod(k)
            self._drop_pod(k)
            if p is not None:
                out.append(p)
        return out

    def cleanup_expired(self) -> List[str]:
        """Key-returning wrapper over :meth:`pop_expired` (the original
        surface — existing callers and tests pin the key list)."""
        return [p.key() for p in self.pop_expired()]

    # -- watch-driven mutations -------------------------------------------

    def add_pod(self, pod: Pod) -> None:
        """Watch AddPod for an assigned pod: confirms an assumption or adds
        an unseen pod (cache.go AddPod)."""
        key = pod.key()
        state = self._pod_state.get(key)
        if state in (_ASSUMED, _EXPIRING):
            cached_node = self._pod_node.get(key)
            if cached_node != pod.node_name:
                # assumed onto the wrong node — trust the API (cache.go logs
                # and re-adds)
                self._drop_pod(key)
                self._insert_pod(pod)
            else:
                self._pod_state[key] = _ADDED
                self._pod_deadline.pop(key, None)
                # refresh the stored object to the API's version
                self._restore(pod, pod.node_name)
                self._mark_dirty(pod.node_name)
        elif state is None:
            self._insert_pod(pod)
        # state == ADDED: duplicate add — treat as update
        else:
            self.update_pod(pod)

    def update_pod(self, pod: Pod) -> None:
        key = pod.key()
        old_node = self._pod_node.get(key)
        if old_node is not None and old_node != pod.node_name:
            self._drop_pod(key)
            self._insert_pod(pod)
            return
        if old_node is None:
            self._insert_pod(pod)
            return
        self.packer.intern_pod(pod)
        self._restore(pod, old_node)
        self._mark_dirty(old_node)

    def remove_pod(self, pod_key: str) -> None:
        if pod_key in self._pod_node:
            self._drop_pod(pod_key)

    def add_node(self, node: Node) -> None:
        self.packer.intern_node(node)
        self._nodes[node.name] = node
        self._pods_by_node.setdefault(node.name, {})
        self._shape_dirty = True

    def update_node(self, node: Node) -> None:
        if node.name not in self._nodes:
            self.add_node(node)
            return
        self.packer.intern_node(node)
        self._nodes[node.name] = node
        self._mark_dirty(node.name)

    def remove_node(self, name: str) -> None:
        self._nodes.pop(name, None)
        # pods on the node stay until their own delete events arrive
        # (reference keeps the NodeInfo if pods remain; we simply drop the
        # row — those pods no longer contribute to any schedulable node)
        self._shape_dirty = True

    # -- internals ---------------------------------------------------------

    def _insert_pod(self, pod: Pod) -> None:
        if not pod.node_name:
            raise CacheError(f"pod {pod.key()} has no node assignment")
        self.packer.intern_pod(pod)
        self._store(pod, pod.node_name)
        self._pod_state[pod.key()] = _ADDED
        self._pod_node[pod.key()] = pod.node_name
        self._mark_dirty(pod.node_name)

    def _drop_pod(self, key: str) -> None:
        node = self._pod_node.pop(key)
        self._pod_state.pop(key, None)
        self._pod_deadline.pop(key, None)
        self._unstore(key, node)
        self._mark_dirty(node)

    # -- stored pods and their owner counts ---------------------------------

    # The stored pods, their label index and the owner counts change
    # together under the snapshot lock: the extender's handler threads
    # read them through owner_columns (and count a newly interned owner
    # set there) while the driver stores and drops pods.

    def _store(self, pod: Pod, node: str) -> None:
        """Put ``pod`` on ``node`` (no object stored under its key there)."""
        with self._snap_lock:
            self._owner_upkeep(pod, node, 1)
            self._pods_by_node.setdefault(node, {})[pod.key()] = pod

    def _unstore(self, key: str, node: str) -> None:
        with self._snap_lock:
            pods = self._pods_by_node.get(node)
            p = pods.get(key) if pods else None
            if p is not None:
                self._owner_upkeep(p, node, -1)
                del pods[key]

    def _restore(self, pod: Pod, node: str) -> None:
        """Replace the object stored under ``pod``'s key on ``node``;
        counts move only when the labels do."""
        with self._snap_lock:
            old = self._pods_by_node.get(node, {}).get(pod.key())
            if (old is not None and old.labels == pod.labels
                    and old.namespace == pod.namespace):
                self._pods_by_node[node][pod.key()] = pod
                return
            self._unstore(pod.key(), node)
            self._store(pod, node)

    def _owner_upkeep(self, pod: Pod, node: str, sign: int) -> None:
        """Count ``pod`` into (sign 1) or out of (-1) the per-node counts
        of every owner set it matches, and file it under its labels.
        Runs BEFORE the pod enters or leaves ``_pods_by_node``, so the
        catch-up of a newly interned owner set (which reads the stored
        pods) never sees it twice."""
        assert_held(self._snap_lock, "cache._owner_upkeep")
        u = self.packer.u
        if not pod.labels and not u.owner_open:
            return  # matches no owner set, files under no label
        with annotate("cache.owner_counts"):
            self._sync_owner_sets()
            ns, key = pod.namespace, pod.key()
            for k, v in pod.labels.items():
                lk = (ns, k, v)
                if sign > 0:
                    self._label_pods.setdefault(lk, {})[key] = node
                else:
                    d = self._label_pods.get(lk)
                    if d is not None:
                        d.pop(key, None)
                        if not d:
                            del self._label_pods[lk]
            synced = self._owner_synced
            for o in u.matching_owner_sets(pod):
                if o >= synced:
                    # interned by another thread since the catch-up
                    # above: the next catch-up counts it whole
                    continue
                c = self._owner_counts.setdefault(o, {})
                n = c.get(node, 0) + sign
                if n:
                    c[node] = n
                else:
                    c.pop(node, None)

    def _sync_owner_sets(self) -> None:
        """Count every owner set interned since the last call from the
        stored pods carrying its anchor label (all stored pods for a set
        with no positive requirement)."""
        assert_held(self._snap_lock, "cache._sync_owner_sets")
        u = self.packer.u
        n = len(u.owner_set_items)
        for o in range(self._owner_synced, n):
            ns, sels = u.owner_set_items[o]
            pairs = owner_anchor_pairs(sels)
            if pairs is None:
                cands = [(p, node) for node, pods in self._pods_by_node.items()
                         for p in pods.values()]
            else:
                cands = [(self._pods_by_node[node][key], node)
                         for k, v in pairs
                         for key, node in self._label_pods.get((ns, k, v),
                                                               {}).items()]
            counts: Dict[str, int] = {}
            for p, node in cands:
                if u.owner_set_matches(o, p.namespace, p.labels):
                    counts[node] = counts.get(node, 0) + 1
            if counts:
                self._owner_counts[o] = counts
        self._owner_synced = n

    def owner_counts(self) -> Dict[int, Dict[str, int]]:
        """owner-set id -> {node name: matching cached pods}, every owner
        set interned so far (nonzero entries only; a copy)."""
        with self._snap_lock:
            self._sync_owner_sets()
            return {o: dict(c) for o, c in self._owner_counts.items() if c}

    def owner_columns(self, cols, width: int, n_rows: int) -> np.ndarray:
        """(n_rows, width) f32 per-node counts of the owner sets ``cols``
        (column c = set ``cols[c]``), rows in the last snapshot's order:
        the SelectorSpread state a batch's solve reads. Costs O(n_rows x
        width) plus the nonzero counts of those sets — never the cached
        pods, nor the owner sets no column names."""
        out = np.zeros((n_rows, width), np.float32)
        if len(cols):
            with self._snap_lock:
                self._sync_owner_sets()
                row_of = self._row_of
                for c, o in enumerate(cols):
                    for node, k in self._owner_counts.get(int(o), {}).items():
                        r = row_of.get(node)
                        if r is not None:
                            out[r, c] = k
        return out

    def _mark_dirty(self, node_name: str) -> None:
        if node_name in self._nodes:
            self._dirty.add(node_name)

    def invalidate_snapshot(self) -> None:
        """Force a full repack on the next snapshot(). Needed when state
        OUTSIDE the node/pod tables changes row contents — e.g. a PVC
        rebinding changes which volume tokens scheduled pods resolve to
        without any node or pod mutation marking rows dirty."""
        self._shape_dirty = True

    # -- snapshot ----------------------------------------------------------

    def snapshot(self) -> NodeTable:
        """UpdateNodeInfoSnapshot (cache.go:211): return the columnar
        NodeTable, recomputing only dirty rows when shapes allow. Interning
        happens at mutation time (add/update/assume), so a clean-cache call
        is O(1) — the width comparison below catches any universe growth
        those mutations (or the driver interning pending pods) caused."""
        table, _mode, _idx, _sub = self._refresh_host()
        return table

    def _refresh_host(self):
        with self._snap_lock:
            return self._refresh_host_locked()

    def _refresh_host_locked(self):
        """Bring the cached host NodeTable up to date. Returns
        ``(table, mode, idx, sub)`` where mode is ``full`` | ``clean`` |
        ``delta``; on ``delta``, ``idx`` is the patched row indices and
        ``sub`` the delta NodeTable whose row j landed at ``idx[j]``.

        Device coherence: every host mutation is ALSO queued on
        ``_pending_dev`` (deltas) / flagged on ``_dev_stale`` (fulls), so
        a host-only ``snapshot()`` caller (server.py's extender-serving
        path) consuming the dirty set can never leave the resident
        device table silently stale — device_snapshot() drains the queue
        it missed."""
        assert_held(self._snap_lock, "cache._refresh_host_locked")
        # EXACT universe signature, not the bucketed widths: interner
        # growth WITHIN a power-of-two bucket still changes clean rows
        # (a pending pod interning a new selector pair must light
        # pair_mh on every node carrying that label) — the delta-vs-full
        # property test caught exactly that staleness against the old
        # widths-only key.
        wkey = self.packer.universe_node_sig()

        if (
            self._shape_dirty
            or self._table is None
            or wkey != self._widths_key
        ):
            self._dev_stale = True
            self._pending_dev.clear()
            return self._full_repack(), "full", None, None

        if not self._dirty:
            return self._table, "clean", None, None

        # incremental: repack only dirty rows. pack_nodes row computation is
        # node-local (cross-node info lives in the shared universe), so a
        # subset pack yields rows identical to a full pack.
        dirty = [n for n in self._dirty if n in self._nodes]
        sub_nodes = [self._nodes[n] for n in dirty]
        sub_pods = [p for n in dirty for p in self._pods_by_node.get(n, {}).values()]
        sub = self.packer.pack_nodes_delta(sub_nodes, sub_pods)
        if self.packer.universe_node_sig() != wkey:
            # packing grew a universe mid-flight — fall back to full
            self._dev_stale = True
            self._pending_dev.clear()
            return (
                self._full_repack(), "full", None, None,
            )
        t = self._table
        idx = []
        for j, name in enumerate(dirty):
            i = self._row_of[name]
            idx.append(i)
            for f in dataclasses.fields(NodeTable):
                if f.name in ("n", "zone_valid"):
                    continue
                getattr(t, f.name)[i] = getattr(sub, f.name)[j]
        # zone_valid is universe-shaped; refresh from the subset pack
        self._table = dataclasses.replace(t, zone_valid=sub.zone_valid)
        self._dirty.clear()
        if self._dev is not None and not self._dev_stale:
            self._pending_dev.append((idx, sub))
        return self._table, "delta", idx, sub

    def device_snapshot(self):
        """The device-resident snapshot: returns ``(table, dev, mode)``
        where ``dev`` is a DeviceNodes that lives on device ACROSS cycles.

        Steady-state cost is proportional to what changed: a clean cache
        returns the resident arrays untouched; a small dirty set re-packs
        only those rows on host and patches them in with one jitted
        scatter (buffer-donated, so no reallocation); a full rebuild
        happens only on node-set shape changes, universe width growth,
        explicit invalidation, or when the dirty fraction exceeds
        ``max_dirty_frac`` (patching would cost more than re-uploading).
        The delta-vs-full property test pins bit-identical arrays."""
        import numpy as np

        from kubernetes_tpu.ops.arrays import nodes_to_device, scatter_node_rows
        from kubernetes_tpu.utils.interner import bucket_size

        from kubernetes_tpu.obs.jaxtel import tree_nbytes

        # the SAME lock _refresh_host takes (RLock): branch selection,
        # pending-queue drain, and the upload itself must see one
        # consistent host table even while server handler threads call
        # the host-only snapshot() concurrently
        with self._snap_lock:
            return self._device_snapshot_locked(tree_nbytes)

    def _device_snapshot_locked(self, tree_nbytes):
        assert_held(self._snap_lock, "cache._device_snapshot_locked")
        import numpy as np

        from kubernetes_tpu.ops.arrays import nodes_to_device, scatter_node_rows
        from kubernetes_tpu.utils.interner import bucket_size

        if self.fault_injector is not None:
            # chaos seam: an armed device_lost/device_oom rule raises
            # here, standing in for a real XLA device error during the
            # scatter/upload — the driver's recovery drops the resident
            # table and rebuilds from the host mirror
            self.fault_injector.device_hook("snapshot:device")
        table, _mode, _idx, _sub = self._refresh_host()
        n_pad = bucket_size(max(table.n, 1))
        if self.mesh is not None:
            # the node bucket must divide across the mesh: both are
            # powers of two, so padding up to the device count suffices
            # (a 2-node cluster on an 8-device mesh rides 8 rows)
            n_pad = max(n_pad, int(self.mesh.devices.size))
        self.last_upload_rows = 0
        self.last_upload_nbytes = 0
        self.last_patched_idx = []
        pending_rows = sum(len(i) for i, _ in self._pending_dev)
        if (self._dev is None or self._dev_stale or n_pad != self._dev_pad
                or pending_rows > self.max_dirty_frac * max(table.n, 1)):
            # clear BEFORE the upload: a delta appended concurrently by a
            # host-only snapshot() (server.py runs in a handler thread)
            # then survives for the next drain — re-applying rows the
            # full table already carries is idempotent; dropping a delta
            # queued mid-upload would not be
            self._pending_dev.clear()
            if self.mesh is not None:
                # full rebuilds, interner-growth repacks, and post-
                # device-loss rebuilds all re-place onto the mesh here —
                # one seam (parallel.place_node_table, shared with the
                # non-resident scheduler paths), so no recovery path can
                # resurrect a single-device resident table under a
                # mesh-on scheduler
                from kubernetes_tpu.parallel.mesh import place_node_table

                self._dev = place_node_table(table, self.mesh,
                                             pad_to=n_pad)
            else:
                self._dev = nodes_to_device(table, pad_to=n_pad)
            self._dev_pad = n_pad
            self._dev_stale = False
            self.last_snapshot_mode = "full"
            self.last_upload_rows = table.n
            self.last_upload_nbytes = tree_nbytes(self._dev)
            self._mem_register("cache.node_table", self.last_upload_nbytes,
                               shape=f"N{n_pad}")
            if self._score_cache_on:
                # full rebuild: the whole score plane is recomputed —
                # drop the summary (rebuilt lazily from the new resident
                # table) and bump the generation so warm-solve state
                # keyed on it (Sinkhorn potentials) is invalidated too
                self._summary = None
                self.summary_generation += 1
                self._mem_deregister("cache.score_summary")
        elif not self._pending_dev:
            self.last_snapshot_mode = "clean"
        else:
            # delta: convert ONLY the queued dirty rows to device layout
            # and scatter them into the resident arrays (one jitted call
            # per queued host refresh — usually exactly one per cycle);
            # padded index slots point out of bounds and are dropped.
            # Pop-drain, never iterate-then-clear: a delta appended
            # concurrently must survive for the next drain instead of
            # being discarded unapplied.
            while self._pending_dev:
                idx, sub = self._pending_dev.pop(0)
                d_pad = bucket_size(max(len(idx), 1), 4)
                sub_dev = nodes_to_device(sub, pad_to=d_pad)
                pidx = np.full((d_pad,), n_pad, np.int32)
                pidx[: len(idx)] = idx
                if self.mesh is not None:
                    # replicate the delta rows so each shard applies its
                    # own slice locally (the donated scatter preserves
                    # the resident node-axis sharding; rows landing on
                    # other shards drop out of this shard's window)
                    from kubernetes_tpu.parallel.mesh import replicate

                    sub_dev = replicate(sub_dev, self.mesh)
                if self._score_cache_on and self._summary is not None:
                    # patch the score summary's SAME rows from the SAME
                    # delta pack — clean columns of the cached plane are
                    # reused untouched, only the dirty columns recompute
                    # (O(churn))
                    from kubernetes_tpu.ops.fused_score import (
                        node_summary,
                        patch_node_summary,
                    )

                    sub_sum = node_summary(sub_dev, **self._summary_flags)
                    self._summary = patch_node_summary(
                        self._summary, sub_sum, pidx)
                self._dev = scatter_node_rows(self._dev, sub_dev, pidx)
                self.last_upload_rows += len(idx)
                self.last_upload_nbytes += tree_nbytes(sub_dev)
                self.last_patched_idx.extend(idx)
            self.last_snapshot_mode = "delta"
        return table, self._dev, self.last_snapshot_mode

    def set_mesh(self, mesh) -> None:
        """Attach (or detach, with ``None``) the node-axis device mesh.
        Changing the mesh invalidates the resident table: its buffers
        live on the old device set, and the next device_snapshot()
        re-places in full onto the new one."""
        if mesh is not self.mesh:
            self.mesh = mesh
            self.drop_device_snapshot()

    def drop_device_snapshot(self) -> None:
        """Release the resident device table (tests / memory pressure);
        the next device_snapshot() re-uploads in full. The score-cache
        summary drops with it — every invalidation edge that lands here
        (takeover reconcile, device-loss recovery, mesh change) also
        drops the cached score plane and bumps its generation."""
        self._dev = None
        self._dev_pad = 0
        self._dev_stale = True
        self._pending_dev.clear()
        self._summary = None
        self.last_patched_idx = []
        self.summary_generation += 1
        # every ledger byte this cache owns dies with the drop — a
        # registration surviving here is exactly the leak the soak's
        # mem_residents sentinel exists to catch
        self._mem_deregister("cache.node_table", "cache.score_summary")

    def has_device_snapshot(self) -> bool:
        """Whether a resident device table currently exists (no upload,
        no lazy build) — the scheduler's state_sizes device keys and the
        drop-audit tests read this."""
        return self._dev is not None

    def _mem_register(self, name: str, nbytes: int, shape: str = "") -> None:
        """Duck-typed memory-ledger registration (no-op unattached)."""
        ml = self.memledger
        if ml is not None and getattr(ml, "enabled", False):
            ml.register(name, nbytes, shape=shape)

    def _mem_deregister(self, *names: str) -> None:
        ml = self.memledger
        if ml is not None and getattr(ml, "enabled", False):
            for n in names:
                ml.deregister(n)

    # -- incremental-solve score cache --------------------------------------

    def enable_score_cache(self, honor_conditions: bool = True,
                           prefer_packed: bool = False) -> None:
        """Turn the device-resident score/feasibility summary on (the
        scheduler does this when ``incremental.enabled``). The flags pin
        the summary's semantics to the scheduler's Policy/objective:
        whether node-condition predicates gate candidate eligibility,
        and whether the candidate ranking prefers packed (fullest-first)
        columns. Off by default — non-incremental users pay nothing."""
        self._score_cache_on = True
        self._summary_flags = {"honor_conditions": bool(honor_conditions),
                               "prefer_packed": bool(prefer_packed)}
        self._summary = None
        self.summary_generation += 1
        self._mem_deregister("cache.score_summary")

    def drop_score_summary(self) -> None:
        """Drop ONLY the cached score plane (the resident node table is
        still coherent): the next score_summary() rebuilds from the
        resident table, and the generation bump kills any warm-solve
        state keyed on the old plane. The scheduler's dirty-frac blowout
        route lands here — the snapshot's own blowout goes through the
        full-upload branch above."""
        with self._snap_lock:
            self._summary = None
            self.summary_generation += 1
            self._mem_deregister("cache.score_summary")

    def has_score_summary(self) -> bool:
        """Whether a cached score plane currently exists (no lazy
        build) — the scheduler's invalidation accounting asks before
        counting a drop that would be a no-op."""
        return self._summary is not None

    def score_summary(self):
        """The device-resident NodeSummary aligned row-for-row with the
        resident DeviceNodes (None when the cache is off or no resident
        table exists — e.g. host-mode snapshots during a device
        cooloff). Built lazily from the resident table on first demand
        after a full rebuild; thereafter patched in place by the delta
        path above. ``last_summary_rebuilt`` reports which of the two
        happened."""
        with self._snap_lock:
            self.last_summary_rebuilt = False
            if not self._score_cache_on or self._dev is None:
                return None
            if self._summary is None:
                from kubernetes_tpu.obs.jaxtel import tree_nbytes
                from kubernetes_tpu.ops.fused_score import node_summary

                self._summary = node_summary(self._dev,
                                             **self._summary_flags)
                self.last_summary_rebuilt = True
                self._mem_register("cache.score_summary",
                                   tree_nbytes(self._summary),
                                   shape=f"N{self._dev_pad}")
            return self._summary

    def _full_repack(self) -> NodeTable:
        nodes = list(self._nodes.values())
        pods = [
            p
            for name in self._nodes
            for p in self._pods_by_node.get(name, {}).values()
        ]
        self._table = self.packer.pack_nodes(nodes, pods)
        self._row_of = {nd.name: i for i, nd in enumerate(nodes)}
        # the pack itself may intern (first sight of a node's taints /
        # images) — the stored signature must be the POST-pack state
        self._widths_key = self.packer.universe_node_sig()
        self._dirty.clear()
        self._shape_dirty = False
        return self._table

    def node_order(self) -> List[str]:
        """Row order of the last snapshot (row index -> node name)."""
        out = [""] * len(self._row_of)
        for name, i in self._row_of.items():
            out[i] = name
        return out
