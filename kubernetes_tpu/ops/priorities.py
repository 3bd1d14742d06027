"""Vectorized Score priorities — the reference's Map/Reduce priority library
(``pkg/scheduler/algorithm/priorities/``) recast as fused (pods x nodes) f32
kernels.

The reference maps each priority per node under a 16-goroutine fan-out
(``generic_scheduler.go:738``), reduces (normalizes) per pod, then takes the
weighted sum (``:799-829``). Here each priority emits the whole (P, N) matrix
at once; reduces are per-row ops; the weighted sum is one fused combine.

Go's integer arithmetic (scores are int64 0..10 with repeated integer
division) is emulated with ``floor(x + eps)`` in f32 — exact on realistic
resource values; see ``_idiv``.
"""

from __future__ import annotations

from typing import Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp

from kubernetes_tpu.api.types import MAX_PRIORITY
from kubernetes_tpu.ops.arrays import DeviceNodes, DevicePods, DeviceSelectors
from kubernetes_tpu.ops.predicates import preferred_program_score

_EPS = 1e-5


def _idiv(num: jnp.ndarray, den: jnp.ndarray) -> jnp.ndarray:
    """Go-style integer division num/den in f32: floor with a small epsilon
    to absorb f32 rounding below exact integer ratios."""
    return jnp.floor(num / jnp.maximum(den, 1e-30) + _EPS)


def _normalize_reduce(raw: jnp.ndarray, mask: jnp.ndarray, reverse: bool) -> jnp.ndarray:
    """priorities/reduce.go NormalizeReduce: per pod, scale scores so the max
    becomes MaxPriority; if max==0 -> all MaxPriority when reversed, else 0.

    ``mask`` is the pod's Filter feasibility row — the reference reduces over
    the *filtered* node list only (PrioritizeNodes receives filteredNodes,
    generic_scheduler.go:684), so the max is taken over feasible nodes."""
    masked = jnp.where(mask, raw, 0.0)
    mx = jnp.max(masked, axis=1, keepdims=True)  # (P, 1)
    scaled = _idiv(MAX_PRIORITY * raw, jnp.where(mx > 0, mx, 1.0))
    scaled = jnp.where(mx > 0, scaled, 0.0)
    if reverse:
        scaled = jnp.where(mx > 0, MAX_PRIORITY - scaled, float(MAX_PRIORITY))
    return scaled


def _requested_fractions(pods: DevicePods, nodes: DeviceNodes):
    """(cpu, mem) total nonzero-request fractions after placing each pod on
    each node — shared scaffold of the ResourceAllocationPriority family
    (resource_allocation.go:39)."""
    cpu_req = pods.nonzero_req[:, 0:1] + nodes.nonzero_req[None, :, 0]
    mem_req = pods.nonzero_req[:, 1:2] + nodes.nonzero_req[None, :, 1]
    cpu_cap = nodes.allocatable[None, :, 0]
    mem_cap = nodes.allocatable[None, :, 1]
    return cpu_req, mem_req, cpu_cap, mem_cap


def least_requested(pods, nodes, sel, topo, mask) -> jnp.ndarray:
    """least_requested.go: ((cap-req)*10/cap + (cap-req)*10/cap)/2, integer
    divisions preserved; req>cap or cap==0 scores 0."""
    cpu_req, mem_req, cpu_cap, mem_cap = _requested_fractions(pods, nodes)

    def score(req, cap):
        s = _idiv((cap - req) * MAX_PRIORITY, cap)
        return jnp.where((cap <= 0) | (req > cap), 0.0, s)

    return _idiv(score(cpu_req, cpu_cap) + score(mem_req, mem_cap), 2.0)


def most_requested(pods, nodes, sel, topo, mask) -> jnp.ndarray:
    """most_requested.go: (req*10/cap) averaged — the bin-packing dual."""
    cpu_req, mem_req, cpu_cap, mem_cap = _requested_fractions(pods, nodes)

    def score(req, cap):
        s = _idiv(req * MAX_PRIORITY, cap)
        return jnp.where((cap <= 0) | (req > cap), 0.0, s)

    return _idiv(score(cpu_req, cpu_cap) + score(mem_req, mem_cap), 2.0)


def balanced_allocation(pods, nodes, sel, topo, mask) -> jnp.ndarray:
    """balanced_resource_allocation.go (two-resource form): score =
    int((1 - |cpuFrac - memFrac|) * 10); any fraction >= 1 scores 0."""
    cpu_req, mem_req, cpu_cap, mem_cap = _requested_fractions(pods, nodes)
    cf = jnp.where(cpu_cap > 0, cpu_req / jnp.maximum(cpu_cap, 1e-30), 1.0)
    mf = jnp.where(mem_cap > 0, mem_req / jnp.maximum(mem_cap, 1e-30), 1.0)
    diff = jnp.abs(cf - mf)
    score = jnp.floor((1.0 - diff) * MAX_PRIORITY + _EPS)
    return jnp.where((cf >= 1.0) | (mf >= 1.0), 0.0, score)


def _node_affinity_raw(pods, nodes, sel) -> jnp.ndarray:
    """Usage-invariant raw weight sums (the map phase) — hoistable out of
    the round loop; only the mask-dependent NormalizeReduce is per-round."""
    prog = preferred_program_score(sel, nodes)  # (Gp, N)
    idx = jnp.clip(pods.prefprog_id, 0, prog.shape[0] - 1)
    return jnp.where((pods.prefprog_id >= 0)[:, None], prog[idx], 0.0)


def node_affinity(pods, nodes, sel, topo, mask) -> jnp.ndarray:
    """node_affinity.go: weight-sum of matched PreferredDuringScheduling
    terms, NormalizeReduce(10, false)."""
    return _normalize_reduce(_node_affinity_raw(pods, nodes, sel), mask,
                             reverse=False)


def _taint_toleration_raw(pods, nodes, sel) -> jnp.ndarray:
    """Usage-invariant intolerable-taint counts (taints never change
    within a batch) — the matmul half of the kernel, hoistable."""
    tol_idx = jnp.clip(pods.tolset_id, 0, sel.tol_soft_mh.shape[0] - 1)
    tol_rows = jnp.where((pods.tolset_id >= 0)[:, None], sel.tol_soft_mh[tol_idx], 0.0)
    soft_count = jnp.sum(nodes.taint_soft_mh, axis=1)  # (N,)
    tolerated = tol_rows @ nodes.taint_soft_mh.T  # (P, N)
    return soft_count[None, :] - tolerated


def taint_toleration(pods, nodes, sel, topo, mask) -> jnp.ndarray:
    """taint_toleration.go: count PreferNoSchedule taints not tolerated,
    NormalizeReduce(10, reverse=true)."""
    return _normalize_reduce(_taint_toleration_raw(pods, nodes, sel), mask,
                             reverse=True)


def image_locality(pods, nodes, sel, topo, mask) -> jnp.ndarray:
    """image_locality.go: sum of (size * nodes-with-image/total-nodes) over
    the pod's images present on the node, clamped to [23MB, 1000MB] and
    scaled to 0..10."""
    mb = 1024.0 * 1024.0
    lo, hi = 23.0 * mb, 1000.0 * mb
    total = jnp.maximum(jnp.sum(nodes.valid.astype(jnp.float32)), 1.0)
    num_nodes = jnp.sum(
        jnp.where(nodes.valid[:, None], nodes.image_mh, 0.0), axis=0
    )  # (Ui,) nodes having each image
    spread = num_nodes / total
    # truncation to int64 per image (scaledImageScore) then summed
    scaled = jnp.floor(sel.image_sizes * spread + _EPS)  # (Ui,)
    sum_scores = pods.image_mh @ (nodes.image_mh * scaled[None, :]).T  # (P, N)
    clamped = jnp.clip(sum_scores, lo, hi)
    return _idiv(MAX_PRIORITY * (clamped - lo), hi - lo)


def selector_spread(pods, nodes, sel, topo, mask) -> jnp.ndarray:
    """selector_spreading.go: map = count of same-namespace pods on the node
    matching all owner selectors; reduce = 10*(max-count)/max blended 1/3
    with the zone-level equivalent at 2/3 (zoneWeighting, :34) when zones
    exist.

    ``pods.owner_id`` indexes the batch's owner columns: ``nodes``
    must carry the same batch's counts (arrays.attach_owner_columns)."""
    width = nodes.owner_counts.shape[1]
    if width != pods.owner_match_mh.shape[1]:
        raise ValueError(
            f"selector_spread: the node table carries {width} owner "
            f"columns, the pod batch {pods.owner_match_mh.shape[1]}: "
            "attach the batch's owner columns (attach_owner_columns)")
    if width == 0:
        counts = jnp.zeros(mask.shape, jnp.float32)
    else:
        idx = jnp.clip(pods.owner_id, 0, width - 1)
        counts = jnp.where(
            (pods.owner_id >= 0)[:, None], nodes.owner_counts.T[idx], 0.0
        )  # (P, N)
    counts = jnp.where(mask, counts, 0.0)
    max_node = jnp.max(counts, axis=1, keepdims=True)  # (P, 1)

    # zone aggregation as a one-hot matmul: Zmat (N, Z)
    n_zones = nodes.zone_valid.shape[0]
    has_zone = nodes.zone_id >= 0
    zid = jnp.clip(nodes.zone_id, 0, n_zones - 1)
    zmat = (
        (zid[:, None] == jnp.arange(n_zones)[None, :])
        & has_zone[:, None]
    ).astype(jnp.float32)  # (N, Z)
    zcounts = counts @ zmat  # (P, Z) — per-pod per-zone matched-pod totals
    # zones present *for this pod* = zones containing a feasible node
    # (the reference builds countsByZone from the pod's scored node list)
    zone_present = (mask.astype(jnp.float32) @ zmat) > 0  # (P, Z)
    max_zone = jnp.max(jnp.where(zone_present, zcounts, -jnp.inf), axis=1, keepdims=True)
    have_zones = jnp.any(zone_present, axis=1, keepdims=True)  # (P, 1)

    node_score = jnp.where(
        max_node > 0,
        MAX_PRIORITY * (max_node - counts) / jnp.maximum(max_node, 1e-30),
        float(MAX_PRIORITY),
    )
    zcount_of_node = jnp.take_along_axis(
        zcounts, jnp.broadcast_to(zid[None, :], (zcounts.shape[0], zid.shape[0])), axis=1
    )  # (P, N)
    zone_score = jnp.where(
        max_zone > 0,
        MAX_PRIORITY * (max_zone - zcount_of_node) / jnp.maximum(max_zone, 1e-30),
        float(MAX_PRIORITY),
    )
    blend = jnp.where(
        have_zones & has_zone[None, :],
        node_score * (1.0 / 3.0) + zone_score * (2.0 / 3.0),
        node_score,
    )
    return jnp.floor(blend + _EPS)  # reference truncates the final float


def node_prefer_avoid(pods, nodes, sel, topo, mask) -> jnp.ndarray:
    """node_prefer_avoid_pods.go: 0 when the node's preferAvoidPods
    annotation lists the pod's controller owner, else 10 (weight 10000 in
    the default provider drowns other priorities)."""
    idx = jnp.clip(pods.owner_uid_id, 0, nodes.avoid_mh.shape[1] - 1)
    avoided = jnp.where(
        (pods.owner_uid_id >= 0)[:, None], nodes.avoid_mh.T[idx], 0.0
    )
    return jnp.where(avoided > 0, 0.0, float(MAX_PRIORITY))


def equal_priority(pods, nodes, sel, topo, mask) -> jnp.ndarray:
    """generic_scheduler.go:840 EqualPriority."""
    return jnp.ones((pods.req.shape[0], nodes.allocatable.shape[0]), jnp.float32)


def inter_pod_affinity(pods, nodes, sel, topo, mask) -> jnp.ndarray:
    """interpod_affinity.go CalculateInterPodAffinityPriority (symmetric
    weighted term counts, min/max-normalized). No-op (all zeros) when no
    topology tables were packed."""
    if topo is None:
        return jnp.zeros((pods.req.shape[0], nodes.allocatable.shape[0]), jnp.float32)
    from kubernetes_tpu.ops.topology import inter_pod_affinity_score

    return inter_pod_affinity_score(pods, nodes, topo, mask)


def even_pods_spread(pods, nodes, sel, topo, mask) -> jnp.ndarray:
    """even_pods_spread.go CalculateEvenPodsSpreadPriority (feature-gated in
    the reference; enabled here whenever soft constraints exist)."""
    if topo is None:
        return jnp.zeros((pods.req.shape[0], nodes.allocatable.shape[0]), jnp.float32)
    from kubernetes_tpu.ops.predicates import selector_program_match
    from kubernetes_tpu.ops.topology import even_pods_spread_score

    prog = selector_program_match(sel, nodes)
    return even_pods_spread_score(pods, nodes, topo, prog, mask)


#: RequestedToCapacityRatio default shape: least-utilized preferred
#: (requested_to_capacity_ratio.go:41 defaultFunctionShape).
DEFAULT_FUNCTION_SHAPE = ((0, 10), (100, 0))


def _broken_linear(p: jnp.ndarray, shape) -> jnp.ndarray:
    """buildBrokenLinearFunction (requested_to_capacity_ratio.go:110):
    piecewise-linear through integer (utilization, score) points with Go
    int64 division (truncation toward zero — jnp.fix). ``shape`` is a
    static tuple so each segment unrolls into the trace."""
    xs = [float(x) for x, _ in shape]
    ys = [float(y) for _, y in shape]
    out = jnp.full_like(p, ys[-1])
    for i in reversed(range(len(xs))):
        if i == 0:
            seg = jnp.full_like(p, ys[0])
        else:
            seg = ys[i - 1] + jnp.trunc(
                (ys[i] - ys[i - 1]) * (p - xs[i - 1]) / (xs[i] - xs[i - 1])
            )
        out = jnp.where(p <= xs[i], seg, out)
    return out


def make_requested_to_capacity_ratio(shape=DEFAULT_FUNCTION_SHAPE) -> "PriorityFn":
    """RequestedToCapacityRatioResourceAllocationPriority
    (requested_to_capacity_ratio.go:87): per-resource utilization percent
    through the shape function, cpu/mem averaged with integer division.

    Utilization uses the scaffold's requested = pod nonzero request + node
    nonzero usage (resource_allocation.go:49-58). The percent floor adds a
    1e-4 epsilon before flooring: Go computes (cap-req)*100/cap in exact
    int64 while we ride f32 — the epsilon absorbs representation error so
    exact-integer percentages (the common round-number case) floor the Go
    way; adversarial near-boundary byte counts may differ by 1 score step.
    """

    def one(req, cap):
        bad = (cap <= 0) | (req > cap)
        pct = 100.0 - jnp.floor(
            (cap - req) * 100.0 / jnp.maximum(cap, 1.0) + 1e-4
        )
        return _broken_linear(jnp.where(bad, 100.0, pct), shape)

    def kernel(pods, nodes, sel, topo, mask) -> jnp.ndarray:
        cpu_req, mem_req, cpu_cap, mem_cap = _requested_fractions(pods, nodes)
        cpu = one(cpu_req, jnp.broadcast_to(cpu_cap, cpu_req.shape))
        mem = one(mem_req, jnp.broadcast_to(mem_cap, mem_req.shape))
        return jnp.trunc((cpu + mem) / 2.0)

    return kernel


def make_node_label(key_id: int, presence: bool) -> "PriorityFn":
    """NodeLabelPriority (node_label.go:47): MaxPriority when the node's
    having label ``key_id`` agrees with ``presence``, else 0. ``key_id``
    indexes the label-key universe (intern the label before packing)."""

    def kernel(pods, nodes, sel, topo, mask) -> jnp.ndarray:
        has = nodes.key_mh[:, key_id] > 0  # (N,)
        hit = has if presence else ~has
        row = jnp.where(hit, float(MAX_PRIORITY), 0.0)
        return jnp.broadcast_to(row[None, :], (pods.req.shape[0], nodes.n))

    return kernel


def resource_limits(pods, nodes, sel, topo, mask) -> jnp.ndarray:
    """ResourceLimitsPriority (resource_limits.go:44): score 1 when the
    node's allocatable satisfies the pod's cpu OR memory limit (a declared,
    non-zero limit that fits), else 0."""
    cap = nodes.allocatable  # (N, R); cols 0/1 = cpu_milli/memory (RES_CPU/RES_MEM)
    cpu_ok = (pods.limits[:, 0:1] > 0) & (pods.limits[:, 0:1] <= cap[:, 0][None, :])
    mem_ok = (pods.limits[:, 1:2] > 0) & (pods.limits[:, 1:2] <= cap[:, 1][None, :])
    return (cpu_ok | mem_ok).astype(jnp.float32)


PriorityFn = Callable[..., jnp.ndarray]  # (pods, nodes, sel, topo, mask) -> (P, N)

#: Registry name -> kernel; names mirror factory registrations
#: (algorithmprovider/defaults/register_priorities.go).
PRIORITY_REGISTRY: Dict[str, PriorityFn] = {
    "LeastRequestedPriority": least_requested,
    "MostRequestedPriority": most_requested,
    "BalancedResourceAllocation": balanced_allocation,
    "NodeAffinityPriority": node_affinity,
    "TaintTolerationPriority": taint_toleration,
    "ImageLocalityPriority": image_locality,
    "SelectorSpreadPriority": selector_spread,
    "NodePreferAvoidPodsPriority": node_prefer_avoid,
    "EqualPriority": equal_priority,
    "InterPodAffinityPriority": inter_pod_affinity,
    "EvenPodsSpreadPriority": even_pods_spread,
    "RequestedToCapacityRatioPriority": make_requested_to_capacity_ratio(),
    "ResourceLimitsPriority": resource_limits,
}


def register_priority(name: str, fn: PriorityFn) -> None:
    """Add a custom-configured priority (the factory/plugins.go
    RegisterPriorityMapReduceFunction analog) — e.g. a NodeLabelPriority
    bound to a specific label, or a RequestedToCapacityRatio with a custom
    shape. Weights dicts may then reference ``name``."""
    PRIORITY_REGISTRY[name] = fn

#: Default provider weights (defaults.go:119 defaultPriorities).
#: EvenPodsSpreadPriority joins via the EvenPodsSpread feature gate
#: (defaults.go:91-100), not the default set.
DEFAULT_WEIGHTS: Dict[str, float] = {
    "SelectorSpreadPriority": 1,
    "InterPodAffinityPriority": 1,
    "LeastRequestedPriority": 1,
    "BalancedResourceAllocation": 1,
    "NodePreferAvoidPodsPriority": 10000,
    "NodeAffinityPriority": 1,
    "TaintTolerationPriority": 1,
    "ImageLocalityPriority": 1,
}


#: the exact full-matrix constant each kernel produces when its inputs are
#: absent from the snapshot (verified by tests/test_priorities.py gating
#: equality): reverse-normalized kernels and spread/avoid fill MaxPriority
#: everywhere (NormalizeReduce's max==0 branch is mask-independent),
#: forward-normalized and sum-based kernels fill 0.
EMPTY_CONSTANTS: Dict[str, float] = {
    "NodeAffinityPriority": 0.0,
    "TaintTolerationPriority": float(MAX_PRIORITY),
    "ImageLocalityPriority": 0.0,
    "SelectorSpreadPriority": float(MAX_PRIORITY),
    "NodePreferAvoidPodsPriority": float(MAX_PRIORITY),
    "ResourceLimitsPriority": 0.0,
    # the two topology scores normalize all-zero raw forward -> 0
    "InterPodAffinityPriority": 0.0,
    "EvenPodsSpreadPriority": 0.0,
}

#: the stock kernels the constants were derived from: register_priority()
#: may rebind a registry name, and the gate must never constant-fold a
#: custom kernel (its empty-input behavior is unknown)
_STOCK_KERNELS: Dict[str, PriorityFn] = {
    name: PRIORITY_REGISTRY[name] for name in EMPTY_CONSTANTS
}


def empty_priorities(node_table, pod_table) -> tuple:
    """Host-side feature gate (the device twin of the reference skipping
    plugins a profile doesn't enable): names whose kernels provably
    produce their :data:`EMPTY_CONSTANTS` for THIS snapshot because the
    inputs they read are entirely absent. Computed on the packed host
    tables (numpy, no device sync) and threaded into the solvers as a
    STATIC jit key — the round loop then adds a scalar instead of paying
    the kernel's matmul + masked reductions every round
    (benchres/solver_profile_cpu.json: these were 2/3 of scoring cost on
    constraint-light workloads)."""
    import numpy as np

    out = []
    if pod_table.prefprog_id.size == 0 or (pod_table.prefprog_id < 0).all():
        out.append("NodeAffinityPriority")  # no preferred node affinity
    if node_table.taint_soft_mh.size == 0 or node_table.taint_soft_mh.sum() == 0:
        out.append("TaintTolerationPriority")  # no PreferNoSchedule taints
    if pod_table.image_mh.size == 0 or pod_table.image_mh.sum() == 0:
        out.append("ImageLocalityPriority")  # no pod lists images
    if pod_table.owner_id.size == 0 or (pod_table.owner_id < 0).all():
        out.append("SelectorSpreadPriority")  # no spread-owner selectors
    if (node_table.avoid_mh.size == 0 or node_table.avoid_mh.sum() == 0
            or (pod_table.owner_uid_id < 0).all()):
        out.append("NodePreferAvoidPodsPriority")
    if pod_table.limits is None or np.asarray(pod_table.limits).max(initial=0) <= 0:  # graftlint: disable=R7 -- host pack table, no device sync
        out.append("ResourceLimitsPriority")
    # topology scores: gate only with full evidence — no (anti)affinity on
    # any batch pod AND zero node-side anti/sym term counts (symmetry
    # inputs from existing pods); spread presence is a packed pod column
    if (not pod_table.has_aff.any()
            and node_table.anti_counts.sum() == 0
            and node_table.sym_counts.sum() == 0):
        out.append("InterPodAffinityPriority")
    if ((pod_table.spread_hard_id < 0).all()
            and (pod_table.spread_soft_id < 0).all()):
        out.append("EvenPodsSpreadPriority")
    return tuple(out)


def solver_gates(node_table, pod_table):
    """The one evidence rule every solver caller needs, in one place:
    ``(skip_priorities, no_ports, no_pod_affinity, no_spread)`` for this
    snapshot+batch. The two topology MASK gates share the score gates'
    evidence by construction."""
    from kubernetes_tpu.ops.predicates import pods_have_no_ports

    skip = empty_priorities(node_table, pod_table)
    return (skip, pods_have_no_ports(pod_table),
            "InterPodAffinityPriority" in skip,
            "EvenPodsSpreadPriority" in skip)


#: a snapshot of the whole stock registry at import time: the fused
#: normalize path (and its integer-sum exactness argument) only applies
#: when every ACTIVE kernel is stock — register_priority() rebinding any
#: name disables fusion for configs that use it
_ALL_STOCK_KERNELS: Dict[str, PriorityFn] = dict(PRIORITY_REGISTRY)


def _fusable(weights: Dict[str, float], skip) -> bool:
    """True when the NA+TT fused accumulate is provably bit-identical:
    every active kernel is stock (all stock kernels floor their scores to
    integer-valued f32 — verified across priorities.py and topology.py)
    and every weight is an integer, so all partial sums are exact f32
    integers (< 2^24) and addition regrouping cannot round."""
    for name, w in weights.items():
        if not w or name in skip:
            continue
        if PRIORITY_REGISTRY.get(name) is not _ALL_STOCK_KERNELS.get(name):
            return False
        if float(w) != int(w):
            return False
    return True


def _fused_pair_normalize(raw_fwd, raw_rev, mask, w_fwd, w_rev):
    """One-output fused form of the two hoisted-raw normalizes
    (NodeAffinity forward + TaintToleration reverse): on a
    Pallas-capable backend this routes to the two-pass HBM-minimal
    kernel pair (ops/fused_score.py); the jnp expression below is the
    universal fallback — identical per-element arithmetic to two
    :func:`_normalize_reduce` calls with the weighted pair landing as
    ONE (P, N) term. Exactness of the regrouped accumulation is the
    :func:`_fusable` integer argument; measured CPU effect of the jnp
    form is neutral-to-negative (XLA:CPU's own fusion already wins —
    benchres/fused_score_cpu.json), which is why the solver only engages
    fusion under the Pallas policy (see batch_assign)."""
    from kubernetes_tpu.ops.fused_score import fused_pair_normalize_device

    out = fused_pair_normalize_device(raw_fwd, raw_rev, mask, w_fwd, w_rev)
    if out is not None:
        return out
    masked_f = jnp.where(mask, raw_fwd, 0.0)
    mxf = jnp.max(masked_f, axis=1, keepdims=True)
    sf = _idiv(MAX_PRIORITY * raw_fwd, jnp.where(mxf > 0, mxf, 1.0))
    sf = jnp.where(mxf > 0, sf, 0.0)
    masked_r = jnp.where(mask, raw_rev, 0.0)
    mxr = jnp.max(masked_r, axis=1, keepdims=True)
    sr = _idiv(MAX_PRIORITY * raw_rev, jnp.where(mxr > 0, mxr, 1.0))
    sr = jnp.where(mxr > 0, sr, 0.0)
    sr = jnp.where(mxr > 0, MAX_PRIORITY - sr, float(MAX_PRIORITY))
    return w_fwd * sf + w_rev * sr


#: stock kernels whose full (P, N) score reads NO usage field and NO mask
#: — computable once per batch and reused every round verbatim
STATIC_FULL = ("ImageLocalityPriority", "NodePreferAvoidPodsPriority",
               "ResourceLimitsPriority")
#: stock kernels whose RAW map phase is usage-invariant but whose
#: NormalizeReduce depends on the per-round feasibility mask:
#: name -> (raw_fn, reverse)
STATIC_RAW = {
    "NodeAffinityPriority": (_node_affinity_raw, False),
    "TaintTolerationPriority": (_taint_toleration_raw, True),
}


def hoist_priorities(pods, nodes, sel,
                     weights: Dict[str, float] | None = None,
                     skip=()) -> Dict[str, tuple]:
    """The usage-invariant slice of scoring, computed ONCE per batch (the
    device analog of the reference computing plugin-independent state
    once per pod — and the round-4 answer to the profile finding that the
    static kernels were ~2/3 of per-round scoring cost,
    benchres/solver_profile_cpu.json). Returns ``{name: ("full", matrix)
    | ("raw", raw_matrix, reverse)}`` for :func:`run_priorities` to
    consume; skipped (gated) and custom-registered kernels are NOT
    hoisted — the gate constant-folds the former and the latter's
    static-ness is unknown."""
    weights = DEFAULT_WEIGHTS if weights is None else weights
    parts: Dict[str, tuple] = {}
    for name, w in weights.items():
        if not w or name in skip:
            continue
        if PRIORITY_REGISTRY.get(name) is not _STOCK_KERNELS.get(name):
            continue
        if name in STATIC_FULL:
            parts[name] = ("full",
                           PRIORITY_REGISTRY[name](pods, nodes, sel, None,
                                                   None))
        elif name in STATIC_RAW:
            raw_fn, reverse = STATIC_RAW[name]
            parts[name] = ("raw", raw_fn(pods, nodes, sel), reverse)
    return parts


def run_priorities(
    pods: DevicePods,
    nodes: DeviceNodes,
    sel: DeviceSelectors,
    mask: jnp.ndarray,
    weights: Dict[str, float] | None = None,
    topo=None,
    skip=(),
    hoisted: Dict[str, tuple] | None = None,
    fused: bool = False,
) -> jnp.ndarray:
    """PrioritizeNodes (generic_scheduler.go:684): weighted sum of all
    enabled priorities -> (P, N) f32 total score. ``skip`` names kernels
    (from :func:`empty_priorities`) replaced by their exact
    :data:`EMPTY_CONSTANTS` scalar. ``hoisted`` takes
    :func:`hoist_priorities` output; accumulation stays in weights-dict
    order with identical per-kernel arithmetic, so hoisted and unhoisted
    totals are bit-identical (pinned by tests/test_priorities.py).

    ``fused=True`` additionally collapses the two hoisted-raw normalizes
    (NodeAffinity + TaintToleration) into one single-output kernel —
    applied only when :func:`_fusable` proves the regrouped accumulation
    exact (all-stock kernels, integer weights), so it is ALWAYS
    bit-identical; non-fusable configs silently take the standard path."""
    weights = DEFAULT_WEIGHTS if weights is None else weights
    hoisted = hoisted or {}
    _NA, _TT = "NodeAffinityPriority", "TaintTolerationPriority"
    fuse_pair = ()
    if (fused and _fusable(weights, skip)
            and all(n in hoisted and hoisted[n][0] == "raw"
                    and weights.get(n) and n not in skip
                    for n in (_NA, _TT))):
        # dict order decides which name triggers the combined accumulate
        fuse_pair = tuple(n for n in weights if n in (_NA, _TT))
    total = jnp.zeros((pods.req.shape[0], nodes.allocatable.shape[0]), jnp.float32)
    for name, w in weights.items():
        if not w:
            continue
        if name in fuse_pair:
            if name == fuse_pair[0]:
                total = total + _fused_pair_normalize(
                    hoisted[_NA][1], hoisted[_TT][1], mask,
                    float(weights[_NA]), float(weights[_TT]))
            continue  # second of the pair: already accumulated
        if (name in skip and name in EMPTY_CONSTANTS
                and PRIORITY_REGISTRY[name] is _STOCK_KERNELS[name]):
            total = total + w * EMPTY_CONSTANTS[name]
        elif name in hoisted:
            kind, val, *rest = hoisted[name]
            term = val if kind == "full" else _normalize_reduce(
                val, mask, rest[0])
            total = total + w * term
        else:
            total = total + w * PRIORITY_REGISTRY[name](pods, nodes, sel, topo, mask)
    return total
