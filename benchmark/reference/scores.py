"""Kubernetes v1.16 default priorities, written plainly from the upstream
source (pkg/scheduler/algorithm/priorities, v1.16.0), for whole rows of
nodes. Independent of ``kubernetes_tpu``: it reads numpy arrays only.

Two entry points:

- :func:`bounds` gives, for one pod and every node, the lowest and the
  highest weighted score the node can have while the node's usage lies
  anywhere between two states (the start and the end of a cycle). A
  batched solve scores each round at the usage that round saw, which
  lies between the two; the bounds hold whatever that usage was.
- :func:`exact` is the same arithmetic at one state, in a chosen dtype,
  written with ``jax.numpy`` so that the lower-precision control runs on
  the chip.

Terms live in these cells: LeastRequested, BalancedResourceAllocation,
NodeAffinity (preferred), TaintToleration (PreferNoSchedule),
SelectorSpread. InterPodAffinity, NodePreferAvoidPods and ImageLocality
are the same on every node for pods and nodes without pod affinity
terms, avoid annotations or images; :func:`check_supported` refuses
inputs where they are not.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

MAX_PRIORITY = 10
ZONE_WEIGHTING = 2.0 / 3.0


@dataclass
class Nodes:
    """Node columns the priorities read."""

    cap_cpu: np.ndarray
    cap_mem: np.ndarray
    cap_pods: np.ndarray
    zone: np.ndarray  # (N,) int zone id, -1 = none
    prefer_taints: np.ndarray  # (N,) PreferNoSchedule taints (untolerated)


@dataclass
class State:
    """Usage of every node: requested cpu/mem (non-zero requests, as the
    priorities count them), pod count, and the pods of each owner (the
    Service or controller whose selector SelectorSpread counts) on each
    node, kept sparse as ``{owner: {node: pods}}``: a deployment with
    thousands of single-replica controllers would make a dense
    (owners, N) copy of each judged state hundreds of megabytes."""

    cpu: np.ndarray
    mem: np.ndarray
    pods: np.ndarray
    owners: dict = field(default_factory=dict)

    def copy(self, owner: int | None = None) -> "State":
        """A copy; given ``owner``, one that keeps that owner's counts
        alone, which is all that a score of its pod reads."""
        rows = (self.owners if owner is None
                else {owner: self.owners[owner]} if owner in self.owners
                else {})
        return State(self.cpu.copy(), self.mem.copy(), self.pods.copy(),
                     {o: dict(r) for o, r in rows.items()})

    def add(self, node: int, cpu: float, mem: float, owner: int,
            n: int = 1) -> None:
        """``n`` pods of one shape and owner (-1: none) onto ``node``
        (off it, for ``n < 0``)."""
        self.cpu[node] += n * cpu
        self.mem[node] += n * mem
        self.pods[node] += n
        if owner >= 0:
            row = self.owners.setdefault(owner, {})
            c = row.get(node, 0) + n
            if c:
                row[node] = c
            else:
                del row[node]
                if not row:
                    del self.owners[owner]

    def owner_counts(self, owner: int) -> np.ndarray:
        """(N,) pods of ``owner`` on each node."""
        out = np.zeros(len(self.cpu))
        row = self.owners.get(owner)
        if row:
            out[list(row)] = list(row.values())
        return out


@dataclass
class PodSpec:
    cpu: float
    mem: float
    owner: int  # -1 = no SelectorSpread owner
    zone_pref: int  # -1 = no preferred zone
    zone_weight: int


def check_supported(pods, nodes) -> None:
    """Refuse host objects whose other default priorities would differ
    between nodes (the reference does not implement them). A pod's
    controller (``owner_uid``) is fine while no node has avoid-pods
    annotations: NodePreferAvoidPods is then the same on every node. A
    pod spreads by one selector at most: v1.16 counts the pods matching
    all of them, which the reference's one owner per pod does not."""
    for n in nodes:
        if n.images or n.prefer_avoid_owner_uids:
            raise ValueError(f"reference: node {n.name} has images or "
                             "avoid-pods annotations")
    for p in pods:
        a = p.affinity
        if (a.pod_affinity_required or a.pod_affinity_preferred
                or a.pod_anti_affinity_required
                or a.pod_anti_affinity_preferred or p.images
                or p.topology_spread or p.tolerations
                or len(p.spread_selectors) > 1):
            raise ValueError(f"reference: pod {p.name} uses a feature the "
                             "reference does not implement")


def fits(spec: PodSpec, nodes: Nodes, st: State) -> np.ndarray:
    """PodFitsResources + the pod count (v1.16 GeneralPredicates)."""
    return ((st.cpu + spec.cpu <= nodes.cap_cpu)
            & (st.mem + spec.mem <= nodes.cap_mem)
            & (st.pods + 1 <= nodes.cap_pods))


def _least_requested(req, cap):
    """least_requested.go: ((capacity - requested) * 10) / capacity in
    int64, 0 when requested > capacity or capacity == 0."""
    req = np.floor(req).astype(np.int64)
    cap = np.floor(cap).astype(np.int64)
    ok = (cap > 0) & (req <= cap)
    return np.where(ok, ((cap - req) * MAX_PRIORITY) // np.maximum(cap, 1), 0)


def _frac(req, cap):
    return np.where(cap > 0, req / np.where(cap > 0, cap, 1.0), 1.0)


def _balanced_hi(cf_lo, cf_hi, mf_lo, mf_hi):
    """balanced_resource_allocation.go at the most favourable point."""
    gap = np.maximum(np.maximum(cf_lo - mf_hi, mf_lo - cf_hi), 0.0)
    s = ((1.0 - gap) * MAX_PRIORITY).astype(np.int64)
    return np.where((cf_lo >= 1) | (mf_lo >= 1), 0, s)


def _balanced_lo(cf_lo, cf_hi, mf_lo, mf_hi):
    gap = np.maximum(np.abs(cf_lo - mf_hi), np.abs(cf_hi - mf_lo))
    s = ((1.0 - gap) * MAX_PRIORITY).astype(np.int64)
    return np.where((cf_hi >= 1) | (mf_hi >= 1), 0, s)


def _normalize(raw, mx, reverse):
    """NormalizeReduce(MaxPriority, reverse) in int arithmetic."""
    raw = raw.astype(np.int64)
    if mx <= 0:
        return np.full(raw.shape, MAX_PRIORITY if reverse else 0, np.int64)
    s = (MAX_PRIORITY * raw) // mx
    return MAX_PRIORITY - s if reverse else s


def _spread(c, maxn, cz, maxz, have_zones):
    """selector_spreading.go CalculateSpreadPriorityReduce for arrays."""
    c = np.asarray(c, np.float64)
    f = np.where(maxn > 0, MAX_PRIORITY * ((maxn - c) / np.maximum(maxn, 1)),
                 float(MAX_PRIORITY))
    if have_zones:
        zs = np.where(maxz > 0,
                      MAX_PRIORITY * ((maxz - cz) / np.maximum(maxz, 1)),
                      float(MAX_PRIORITY))
        f = f * (1.0 - ZONE_WEIGHTING) + ZONE_WEIGHTING * zs
    return f.astype(np.int64)


def _zone_sums(counts, zone, n_zones, mask):
    out = np.zeros(n_zones)
    np.add.at(out, zone[mask], counts[mask])
    return out


def bounds(spec: PodSpec, nodes: Nodes, lo: State, hi: State,
           weights: dict, n_zones: int):
    """``(score_lo, score_hi, feasible_lo, feasible_hi)`` over all nodes
    for usage anywhere between ``lo`` and ``hi`` (``lo <= hi``
    elementwise). ``feasible_hi`` is feasibility at ``hi`` (so throughout
    the range), ``feasible_lo`` at ``lo`` (somewhere in it)."""
    f_hi = fits(spec, nodes, hi)
    f_lo = fits(spec, nodes, lo)
    s_lo = np.zeros(len(nodes.cap_cpu), np.int64)
    s_hi = np.zeros_like(s_lo)
    w = weights.get("LeastRequestedPriority", 0)
    if w:
        t_hi = (_least_requested(lo.cpu + spec.cpu, nodes.cap_cpu)
                + _least_requested(lo.mem + spec.mem, nodes.cap_mem)) // 2
        t_lo = (_least_requested(hi.cpu + spec.cpu, nodes.cap_cpu)
                + _least_requested(hi.mem + spec.mem, nodes.cap_mem)) // 2
        s_lo += w * t_lo
        s_hi += w * t_hi
    w = weights.get("BalancedResourceAllocation", 0)
    if w:
        cf_lo = _frac(lo.cpu + spec.cpu, nodes.cap_cpu)
        cf_hi = _frac(hi.cpu + spec.cpu, nodes.cap_cpu)
        mf_lo = _frac(lo.mem + spec.mem, nodes.cap_mem)
        mf_hi = _frac(hi.mem + spec.mem, nodes.cap_mem)
        s_lo += w * _balanced_lo(cf_lo, cf_hi, mf_lo, mf_hi)
        s_hi += w * _balanced_hi(cf_lo, cf_hi, mf_lo, mf_hi)
    w = weights.get("NodeAffinityPriority", 0)
    if w:
        raw = (np.where(nodes.zone == spec.zone_pref, spec.zone_weight, 0)
               if spec.zone_pref >= 0 else np.zeros(len(nodes.zone)))
        mx_lo = raw[f_hi].max(initial=0)
        mx_hi = raw[f_lo].max(initial=0)
        a = _normalize(raw, mx_lo, False)
        b = _normalize(raw, mx_hi, False)
        s_lo += w * np.minimum(a, b)
        s_hi += w * np.maximum(a, b)
    w = weights.get("TaintTolerationPriority", 0)
    if w:
        raw = nodes.prefer_taints
        a = _normalize(raw, raw[f_hi].max(initial=0), True)
        b = _normalize(raw, raw[f_lo].max(initial=0), True)
        s_lo += w * np.minimum(a, b)
        s_hi += w * np.maximum(a, b)
    w = weights.get("SelectorSpreadPriority", 0)
    if w:
        if spec.owner < 0:
            s_lo += w * MAX_PRIORITY
            s_hi += w * MAX_PRIORITY
        else:
            c_lo = lo.owner_counts(spec.owner)
            c_hi = hi.owner_counts(spec.owner)
            have = bool((nodes.zone >= 0).any())
            maxn_lo = c_lo[f_hi].max(initial=0)
            maxn_hi = c_hi[f_lo].max(initial=0)
            z_lo = _zone_sums(c_lo, nodes.zone, n_zones, f_hi & (nodes.zone >= 0))
            z_hi = _zone_sums(c_hi, nodes.zone, n_zones, f_lo & (nodes.zone >= 0))
            zi = np.maximum(nodes.zone, 0)
            s_hi += w * _spread(c_lo, maxn_hi, z_lo[zi], z_hi.max(initial=0),
                                have)
            s_lo += w * _spread(c_hi, np.maximum(maxn_lo, c_hi), z_hi[zi],
                                np.maximum(z_lo.max(initial=0), z_hi[zi]),
                                have)
    return s_lo, s_hi, f_lo, f_hi


def exact(spec: PodSpec, nodes: Nodes, st: State, weights: dict,
          n_zones: int, dtype):
    """The weighted score of every node at one state, with every
    arithmetic step of the priorities in ``dtype`` (``jax.numpy``, so the
    lower-precision control runs on the chip). Infeasible nodes get
    -inf. Feasibility and pod counts are exact (numpy)."""
    feas = fits(spec, nodes, st)
    n = len(nodes.cap_cpu)
    na = (np.where(nodes.zone == spec.zone_pref, spec.zone_weight, 0)
          if spec.zone_pref >= 0 else np.zeros(n))
    if spec.owner >= 0:
        c = st.owner_counts(spec.owner)
        z = _zone_sums(c, nodes.zone, n_zones, feas & (nodes.zone >= 0))
        zc = z[np.maximum(nodes.zone, 0)]
        spread = 1
    else:
        c = zc = np.zeros(n)
        z = np.zeros(1)
        spread = 0
    w = tuple(sorted((k, float(v)) for k, v in weights.items()))
    return _exact_core(st.cpu + spec.cpu, st.mem + spec.mem, nodes.cap_cpu,
                       nodes.cap_mem, feas, na, nodes.prefer_taints, c, zc,
                       z.max(initial=0), w=w, spread=spread,
                       zones=bool((nodes.zone >= 0).any()),
                       dtype=np.dtype(dtype).name)


def _exact_core_impl(cpu, mem, cap_cpu, cap_mem, feas, na, taints, c, zc,
                     mxz, *, w, spread, zones, dtype):
    import jax.numpy as jnp

    def a(x):
        return jnp.asarray(x).astype(dtype)

    weights = dict(w)
    ten, zero, one = a(MAX_PRIORITY), a(0), a(1)
    total = jnp.zeros(cpu.shape, dtype)
    wt = weights.get("LeastRequestedPriority", 0)
    if wt:
        def lr(req, cap):
            req, cap = a(req), a(cap)
            s = jnp.floor((cap - req) * ten / cap)
            return jnp.where((cap > 0) & (req <= cap), s, zero)

        t = jnp.floor((lr(cpu, cap_cpu) + lr(mem, cap_mem)) / a(2))
        total = total + a(wt) * t
    wt = weights.get("BalancedResourceAllocation", 0)
    if wt:
        cf = a(cpu) / a(cap_cpu)
        mf = a(mem) / a(cap_mem)
        t = jnp.floor((one - jnp.abs(cf - mf)) * ten)
        total = total + a(wt) * jnp.where((cf >= 1) | (mf >= 1), zero, t)
    wt = weights.get("NodeAffinityPriority", 0)
    if wt:
        raw = a(na)
        mx = jnp.max(jnp.where(feas, raw, zero))
        t = jnp.where(mx > 0, jnp.floor(ten * raw / jnp.maximum(mx, one)),
                      zero)
        total = total + a(wt) * t
    wt = weights.get("TaintTolerationPriority", 0)
    if wt:
        raw = a(taints)
        mx = jnp.max(jnp.where(feas, raw, zero))
        t = jnp.where(mx > 0,
                      ten - jnp.floor(ten * raw / jnp.maximum(mx, one)), ten)
        total = total + a(wt) * t
    wt = weights.get("SelectorSpreadPriority", 0)
    if wt:
        if not spread:
            total = total + a(wt) * ten
        else:
            cj = a(c)
            mxn = jnp.max(jnp.where(feas, cj, zero))
            f = jnp.where(mxn > 0,
                          ten * ((mxn - cj) / jnp.maximum(mxn, one)), ten)
            if zones:
                mz = a(mxz)
                zs = jnp.where(mz > 0,
                               ten * ((mz - a(zc)) / jnp.maximum(mz, one)),
                               ten)
                f = f * a(1.0 - ZONE_WEIGHTING) + a(ZONE_WEIGHTING) * zs
            total = total + a(wt) * jnp.floor(f)
    return jnp.where(feas, total.astype(jnp.float32), -jnp.inf)


_JIT = {}


def _exact_core(*args, **static):
    import jax

    fn = _JIT.get("core")
    if fn is None:
        fn = _JIT["core"] = jax.jit(
            _exact_core_impl,
            static_argnames=("w", "spread", "zones", "dtype"))
    return fn(*args, **static)
