"""Batched assignment — replacing the reference's one-pod-at-a-time driver
loop (``pkg/scheduler/scheduler.go:462`` scheduleOne → ``selectHost``
``generic_scheduler.go:292``) with whole-queue placement on device.

Two solvers:

- ``greedy_assign`` — the **parity path**: a ``lax.scan`` over pods in
  activeQ order (priority desc, arrival asc — the queue comparator,
  ``internal/queue/scheduling_queue.go``), recomputing predicates+priorities
  for the one pod against the *current* usage state each step. Bit-for-bit
  the reference's serial semantics (modulo selectHost's randomized
  round-robin tie-break: we take the lowest node index deterministically).

- ``batch_assign`` — the **fast path**: assign-and-mask rounds. Every round,
  all unplaced pods score all nodes at once (MXU), argmax their best node,
  and per-node acceptance admits the highest-priority prefix that fits
  capacity (segmented prefix sums); usage updates by scatter-add and the
  next round re-masks. Contended capacity thus resolves in O(rounds)
  full-matrix passes instead of O(pods) serial cycles.

Pods with host ports get conservative treatment in the fast path (one
port-bearing pod per node per round) so intra-batch port conflicts can
never be admitted; the round structure retries the rest.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops.arrays import DeviceNodes, DevicePods, DeviceSelectors
from kubernetes_tpu.ops.predicates import (
    run_predicates,
    static_predicate_reasons,
    static_volume_reasons,
)
from kubernetes_tpu.ops.priorities import run_priorities

NEG = -1e30

#: auto-routing thresholds (batch_assign auto_sinkhorn): route a batch
#: to the transport plan only when round 0 shows a REAL tie-contention
#: cohort — at least this many bidders whose multi-way-tied best
#: columns are oversubscribed...
AUTO_TIE_MIN_COHORT = 8
#: ...AND whose runner-up gaps differ by at least this many score steps
#: (heterogeneous opportunity cost is what per-pod argmax cannot see;
#: a homogeneous cohort reaches the OT outcome through rotation +
#: score-ordered admission — the r3 margin-ordered evidence).
AUTO_TIE_GAP_MARGIN = 2.0

#: kernels that can create the asymmetric-second-choice signature the
#: auto-router hunts. When the host-side gates prove ALL of them absent
#: from a batch (solver_gates skip list), every pod's score row is
#: resource-shaped and tie cohorts are gap-homogeneous by construction —
#: the router is compiled OUT for that batch (zero overhead on the
#: gated-light fast path; measured +50% otherwise at 1000x4096).
_PREFERENCE_KERNELS = (
    "NodeAffinityPriority", "SelectorSpreadPriority",
    "InterPodAffinityPriority", "EvenPodsSpreadPriority",
    "TaintTolerationPriority", "ImageLocalityPriority",
)


class UsageState(NamedTuple):
    """The mutable slice of node state — what AddPod touches in the
    reference's NodeInfo (node_info.go AddPod: requested, nonZeroRequest,
    usedPorts, pod list) plus spread counts."""

    requested: jnp.ndarray  # (N, R)
    nonzero_req: jnp.ndarray  # (N, 2)
    port_any: jnp.ndarray  # (N, Upp)
    port_wild: jnp.ndarray  # (N, Upp)
    port_spec: jnp.ndarray  # (N, Upip)
    owner_counts: jnp.ndarray  # (N, Ob) — the batch's owner columns
    matcher_counts: jnp.ndarray  # (N, M)
    anti_counts: jnp.ndarray  # (N, Ua)
    sym_counts: jnp.ndarray  # (N, Us)
    aff_pod_count: jnp.ndarray  # (N,)
    vol_any: jnp.ndarray  # (N, Uv)
    vol_rw: jnp.ndarray  # (N, Uv)
    pd_mh: jnp.ndarray  # (N, Uvd)
    csi_mh: jnp.ndarray  # (N, Uvc)


def usage_from_nodes(nodes: DeviceNodes) -> UsageState:
    return UsageState(
        requested=nodes.requested,
        nonzero_req=nodes.nonzero_req,
        port_any=nodes.port_any_mh,
        port_wild=nodes.port_wild_mh,
        port_spec=nodes.port_spec_mh,
        owner_counts=nodes.owner_counts,
        matcher_counts=nodes.matcher_counts,
        anti_counts=nodes.anti_counts,
        sym_counts=nodes.sym_counts,
        aff_pod_count=nodes.aff_pod_count,
        vol_any=nodes.vol_any_mh,
        vol_rw=nodes.vol_rw_mh,
        pd_mh=nodes.pd_mh,
        csi_mh=nodes.csi_mh,
    )


def nodes_with_usage(nodes: DeviceNodes, u: UsageState) -> DeviceNodes:
    return nodes._replace(
        requested=u.requested,
        nonzero_req=u.nonzero_req,
        port_any_mh=u.port_any,
        port_wild_mh=u.port_wild,
        port_spec_mh=u.port_spec,
        owner_counts=u.owner_counts,
        matcher_counts=u.matcher_counts,
        anti_counts=u.anti_counts,
        sym_counts=u.sym_counts,
        aff_pod_count=u.aff_pod_count,
        vol_any_mh=u.vol_any,
        vol_rw_mh=u.vol_rw,
        pd_mh=u.pd_mh,
        csi_mh=u.csi_mh,
    )


def _apply_batch(u: UsageState, pods: DevicePods, node_idx: jnp.ndarray,
                 accepted: jnp.ndarray) -> UsageState:
    """Scatter accepted pods into the usage state. ``node_idx`` (P,) row per
    pod; ``accepted`` (P,) bool gates contributions (rejected rows scatter
    zeros into row 0 harmlessly)."""
    tgt = jnp.where(accepted, node_idx, 0)
    w = accepted.astype(jnp.float32)[:, None]
    return UsageState(
        requested=u.requested.at[tgt].add(pods.req * w),
        nonzero_req=u.nonzero_req.at[tgt].add(pods.nonzero_req * w),
        port_any=u.port_any.at[tgt].max(
            jnp.maximum(pods.port_wild_pp, pods.port_spec_pp) * w
        ),
        port_wild=u.port_wild.at[tgt].max(pods.port_wild_pp * w),
        port_spec=u.port_spec.at[tgt].max(pods.port_spec_pip * w),
        owner_counts=u.owner_counts.at[tgt].add(pods.owner_match_mh * w),
        matcher_counts=u.matcher_counts.at[tgt].add(pods.matcher_mh * w),
        anti_counts=u.anti_counts.at[tgt].add(pods.anti_term_mh * w),
        sym_counts=u.sym_counts.at[tgt].add(pods.sym_term_mh * w),
        aff_pod_count=u.aff_pod_count.at[tgt].add(
            pods.has_aff.astype(jnp.float32) * w[:, 0]
        ),
        vol_any=u.vol_any.at[tgt].max(pods.vol_any_mh * w),
        vol_rw=u.vol_rw.at[tgt].max(pods.vol_rw_mh * w),
        pd_mh=u.pd_mh.at[tgt].max(pods.pd_mh * w),
        csi_mh=u.csi_mh.at[tgt].max(pods.csi_mh * w),
    )


def _pod_slice(pods: DevicePods, p: jnp.ndarray) -> DevicePods:
    """One-row DevicePods view at dynamic index p (static shapes)."""
    take = lambda a: jax.lax.dynamic_index_in_dim(a, p, axis=0, keepdims=True)
    return DevicePods(*[take(f) for f in pods])


def queue_order(pods: DevicePods) -> jnp.ndarray:
    """activeQ comparator: priority desc, then arrival (row order) asc —
    scheduling_queue.go's podsCompareBackoffCompleted/less func analog.
    Invalid (padding) rows sort last."""
    pri = jnp.where(pods.valid, pods.priority, jnp.iinfo(jnp.int32).min)
    return jnp.lexsort((pods.order, -pri))


@partial(jax.jit, static_argnames=("weights_key", "skip_key", "no_ports",
                                   "no_pod_affinity", "no_spread"))
def _greedy_impl(pods, nodes, sel, topo, vol, weights_key, extra_mask,
                 static_vol=None, enabled_mask=None, extra_score=None,
                 skip_key=(), no_ports=False, no_pod_affinity=False,
                 no_spread=False):
    weights = dict(weights_key) if weights_key is not None else None
    P = pods.req.shape[0]
    perm = queue_order(pods)
    u0 = usage_from_nodes(nodes)
    # static predicate bits hoisted out of the scan; each step slices its row
    static_bits, prog = static_predicate_reasons(pods, nodes, sel)
    if vol is not None and static_vol is None:
        static_vol = static_volume_reasons(pods, nodes, sel, vol, prog=prog)

    def step(u, p):
        pod = _pod_slice(pods, p)
        cur = nodes_with_usage(nodes, u)
        extra = jax.lax.dynamic_index_in_dim(extra_mask, p, axis=0, keepdims=True)
        sv = (
            jax.lax.dynamic_index_in_dim(static_vol, p, axis=0, keepdims=True)
            if static_vol is not None
            else None
        )
        sb = jax.lax.dynamic_index_in_dim(static_bits, p, axis=0, keepdims=True)
        mask = (
            run_predicates(pod, cur, sel, topo, vol, sv, enabled_mask,
                           hoisted=(sb, prog), no_ports=no_ports,
                           no_pod_affinity=no_pod_affinity,
                           no_spread=no_spread).mask
            & extra
        )  # (1, N)
        score = run_priorities(pod, cur, sel, mask, weights, topo,
                               skip=skip_key)
        if extra_score is not None:
            score = score + jax.lax.dynamic_index_in_dim(
                extra_score, p, axis=0, keepdims=True
            )
        masked = jnp.where(mask, score, NEG)
        best = jnp.argmax(masked[0])
        ok = mask[0, best] & pod.valid[0]
        u = _apply_batch(u, pod, best[None], ok[None])
        return u, jnp.where(ok, best.astype(jnp.int32), -1)

    u, picks = jax.lax.scan(step, u0, perm)
    assigned = jnp.full((P,), -1, jnp.int32).at[perm].set(picks)
    return assigned, u


def greedy_assign(
    pods: DevicePods,
    nodes: DeviceNodes,
    sel: DeviceSelectors,
    weights: Optional[Dict[str, float]] = None,
    topo=None,
    extra_mask: Optional[jnp.ndarray] = None,
    vol=None,
    static_vol: Optional[jnp.ndarray] = None,
    enabled_mask: Optional[int] = None,
    extra_score: Optional[jnp.ndarray] = None,
    skip_priorities=(),
    no_ports: bool = False,
    no_pod_affinity: bool = False,
    no_spread: bool = False,
    fault_hook=None,
    fault_site: str = "solve:greedy",
) -> Tuple[jnp.ndarray, UsageState]:
    """Serial-parity solver. Returns (assigned node row per pod or -1,
    final usage). ``extra_mask`` (P, N) ANDs into feasibility — the driver
    feeds the nominated-pods pass-A mask through it (podFitsOnNode's
    two-pass rule, generic_scheduler.go:610). ``skip_priorities``: names
    from :func:`~kubernetes_tpu.ops.priorities.empty_priorities`, whose
    kernels are replaced by their exact constants (static jit key).

    ``fault_hook(site, assigned, usage, rounds, n_nodes)`` is the
    solver-entry fault-injection seam (kubernetes_tpu/faults.py): called
    with the would-be result, it may raise a SolverFault or return a
    poisoned triple — exactly what an out-of-process solver timing out
    or lying over the wire would look like to the driver."""
    key = tuple(sorted(weights.items())) if weights is not None else None
    if extra_mask is None:
        extra_mask = jnp.ones(
            (pods.req.shape[0], nodes.allocatable.shape[0]), bool
        )
    assigned, u = _greedy_impl(pods, nodes, sel, topo, vol, key, extra_mask,
                               static_vol, enabled_mask, extra_score,
                               skip_key=tuple(skip_priorities),
                               no_ports=no_ports,
                               no_pod_affinity=no_pod_affinity,
                               no_spread=no_spread)
    if fault_hook is not None:
        assigned, u, _ = fault_hook(fault_site, assigned, u, 0,
                                    nodes.allocatable.shape[0])
    return assigned, u


def _segment_prefix(values: jnp.ndarray, seg_starts: jnp.ndarray) -> jnp.ndarray:
    """Exclusive prefix sums within contiguous segments. ``values`` (P, R)
    sorted by segment; ``seg_starts`` (P,) index of each row's segment
    start."""
    excl = jnp.cumsum(values, axis=0) - values
    return excl - excl[seg_starts]


def _inverse_permutation(perm: jnp.ndarray) -> jnp.ndarray:
    """inv[perm[i]] = i for a permutation ``perm``. ``argsort`` of a
    permutation IS its inverse, and XLA lowers it to one vectorized sort —
    the scatter spelling (``zeros.at[perm].set(iota)``) lowers on XLA:CPU
    to a P-trip while loop of one-element dynamic-update-slices (profiled:
    the two rank scatters were a measurable slice of every round)."""
    return jnp.argsort(perm).astype(jnp.int32)


#: lean-score support: the usage-dependent resource kernels the fused
#: round can inline (shared-fraction form), plus EqualPriority (a
#: constant). Everything else (or a non-integer weight, or a rebound
#: registry name) routes the batch to the general round path.
_LEAN_DYNAMIC = ("LeastRequestedPriority", "MostRequestedPriority",
                 "BalancedResourceAllocation")


def _lean_score_plan(weights_key, skip_key):
    """Host-side (trace-time) scoring plan for the fused round: returns
    ``(const_total, terms)`` — the exact scalar sum of all gated/constant
    kernels plus the ordered (name, weight) list of live resource kernels
    — or None when any active kernel falls outside the provably-exact
    lean set. Exactness mirrors priorities._fusable: every stock kernel
    floors to integer-valued f32 and every weight is an integer, so all
    partial sums are exact f32 integers and regrouping cannot round."""
    from kubernetes_tpu.ops.priorities import (
        _ALL_STOCK_KERNELS,
        _STOCK_KERNELS,
        DEFAULT_WEIGHTS,
        EMPTY_CONSTANTS,
        PRIORITY_REGISTRY,
    )

    weights = (dict(weights_key) if weights_key is not None
               else DEFAULT_WEIGHTS)
    const_total = 0.0
    terms = []
    for name, w in weights.items():
        if not w:
            continue
        if float(w) != int(w):
            return None
        if PRIORITY_REGISTRY.get(name) is not _ALL_STOCK_KERNELS.get(name):
            return None  # rebound kernel: empty/lean behavior unknown
        if (name in skip_key and name in EMPTY_CONSTANTS
                and PRIORITY_REGISTRY[name] is _STOCK_KERNELS[name]):
            const_total += w * EMPTY_CONSTANTS[name]
        elif name == "EqualPriority":
            const_total += w * 1.0
        elif name in _LEAN_DYNAMIC:
            terms.append((name, float(w)))
        else:
            return None
    return const_total, tuple(terms)


def _lean_masked_score(pods, nodes, u, active, static_ok, res_on, plan):
    """The fused round's single (P, N) pass: feasibility mask and weighted
    score in one expression, emitted as ``ms = where(mask, score, NEG)``
    so XLA materializes exactly ONE (P, N) f32 matrix per round instead
    of mask + per-kernel score temporaries. Arithmetic is verbatim
    priorities.least_requested / most_requested / balanced_allocation
    with the shared request fractions computed once (regrouped
    accumulation exact per :func:`_lean_score_plan`)."""
    from kubernetes_tpu.ops.predicates import resource_fit_mask
    from kubernetes_tpu.ops.priorities import MAX_PRIORITY, _EPS, _idiv

    const_total, terms = plan
    mask = static_ok & active[:, None]
    if res_on:
        mask = mask & resource_fit_mask(pods.req, nodes.allocatable,
                                        u.requested)
    score = jnp.full((pods.req.shape[0], nodes.allocatable.shape[0]),
                     jnp.float32(const_total))
    if terms:
        # shared ResourceAllocationPriority scaffold (computed once)
        cpu_req = pods.nonzero_req[:, 0:1] + u.nonzero_req[None, :, 0]
        mem_req = pods.nonzero_req[:, 1:2] + u.nonzero_req[None, :, 1]
        cpu_cap = nodes.allocatable[None, :, 0]
        mem_cap = nodes.allocatable[None, :, 1]

        def capped(req, cap, s):
            return jnp.where((cap <= 0) | (req > cap), 0.0, s)

        for name, w in terms:
            if name == "LeastRequestedPriority":
                t = _idiv(
                    capped(cpu_req, cpu_cap,
                           _idiv((cpu_cap - cpu_req) * MAX_PRIORITY, cpu_cap))
                    + capped(mem_req, mem_cap,
                             _idiv((mem_cap - mem_req) * MAX_PRIORITY,
                                   mem_cap)),
                    2.0)
            elif name == "MostRequestedPriority":
                t = _idiv(
                    capped(cpu_req, cpu_cap,
                           _idiv(cpu_req * MAX_PRIORITY, cpu_cap))
                    + capped(mem_req, mem_cap,
                             _idiv(mem_req * MAX_PRIORITY, mem_cap)),
                    2.0)
            else:  # BalancedResourceAllocation
                cf = jnp.where(cpu_cap > 0,
                               cpu_req / jnp.maximum(cpu_cap, 1e-30), 1.0)
                mf = jnp.where(mem_cap > 0,
                               mem_req / jnp.maximum(mem_cap, 1e-30), 1.0)
                t = jnp.floor((1.0 - jnp.abs(cf - mf)) * MAX_PRIORITY + _EPS)
                t = jnp.where((cf >= 1.0) | (mf >= 1.0), 0.0, t)
            score = score + w * t
    return jnp.where(mask, score, NEG)


def _blocked_pick(tied, arank):
    """Exact (rot+1)-th-set-bit selection without the (P, N) cumsum or the
    (P, N) argmax (profiled at 23 ms + 16 ms per round on XLA:CPU at the
    headline shape — scan-shaped lowerings that don't vectorize): count
    set bits per 64-column block (a fast reduce), locate the target block
    with a (P, N/64) scan, then rank within the one gathered block. The
    chosen column is bit-identical to the cumsum spelling; rotation
    semantics (``rot = arank % tcount``) are unchanged."""
    P, N = tied.shape
    BL = min(64, N)
    if N % BL:
        # non-bucketed node axis (every in-repo caller pads to a
        # power-of-two bucket, but pad_to is an open parameter): the
        # blocked reshape can't apply — take the full-width cumsum
        # spelling, same picks
        pos = jnp.cumsum(tied.astype(jnp.int32), axis=1)
        tcount = pos[:, -1]
        rot = jnp.where(tcount > 0, arank % jnp.maximum(tcount, 1), 0)
        pick = tied & (pos == (rot + 1)[:, None])
        choice = jnp.argmax(pick, axis=1).astype(jnp.int32)
        return jnp.where(tcount > 0, choice, 0), tcount
    t3 = tied.reshape(P, N // BL, BL)
    return _blocked_pick_core(
        t3, lambda bidx: jnp.take_along_axis(
            t3, bidx[:, None, None], axis=1)[:, 0, :],
        arank)


def _blocked_pick_core(t3, gather_block, arank):
    """Shared core of the blocked selection. ``t3`` is the (P, N/BL, BL)
    tied view; ``gather_block(bidx) -> (P, BL) bool`` re-derives one
    block's tied bits (the lean path recomputes them from the gathered
    masked-score slice so the full tied matrix is never materialized).
    Block counts ride int8 (BL <= 64 < 127) — XLA:CPU materializes the
    reduce's convert, and a quarter-width buffer is a quarter of that
    traffic."""
    P = t3.shape[0]
    BL = t3.shape[2]
    bc = jnp.sum(t3.astype(jnp.int8), axis=2,
                 dtype=jnp.int8).astype(jnp.int32)  # (P, N/BL)
    bexcl = jnp.cumsum(bc, axis=1) - bc  # exclusive block prefix
    tcount = bexcl[:, -1] + bc[:, -1]
    rot = jnp.where(tcount > 0, arank % jnp.maximum(tcount, 1), 0)
    hit = (bexcl <= rot[:, None]) & (bexcl + bc > rot[:, None])
    bidx = jnp.argmax(hit, axis=1).astype(jnp.int32)
    blk = gather_block(bidx)
    want = rot - jnp.take_along_axis(bexcl, bidx[:, None], axis=1)[:, 0]
    cpos = jnp.cumsum(blk.astype(jnp.int32), axis=1)  # (P, BL) — small
    inblk = blk & (cpos == (want + 1)[:, None])
    off = jnp.argmax(inblk, axis=1).astype(jnp.int32)
    choice = jnp.where(tcount > 0, bidx * BL + off, 0)
    return choice, tcount


def _admit_scored(choice, rank, req, free, per_node_cap, capacity_on,
                  sorted_gate=None):
    """Score-ordered per-node admission — ONE spelling shared by the
    general and lean round bodies (their bit-identity is the module's
    core claim, so the rule lives in one place): group chosen pods by
    node (queue rank ascending within a node), admit the prefix that
    fits remaining capacity (``capacity_on`` is the trace-time
    PodFitsResources gate), cap admissions per node per round.
    ``sorted_gate(order2, seg_starts) -> (P,) bool`` lets the general
    path AND in its port-conflict guard in the same sorted frame.
    Returns the (P,) accepted mask in original row order."""
    P = choice.shape[0]
    big = jnp.int32(free.shape[0] + 1)
    ckey = jnp.where(choice >= 0, choice, big)
    order2 = jnp.lexsort((rank, ckey))  # grouped by chosen node, rank asc
    c_s = choice[order2]
    ckey_s = ckey[order2]  # sorted — safe for searchsorted
    req_s = req[order2]
    seg_starts = jnp.searchsorted(ckey_s, ckey_s, side="left")
    prefix = _segment_prefix(req_s, seg_starts)  # usage by earlier pods
    free_s = free[jnp.clip(c_s, 0, free.shape[0] - 1)]
    if capacity_on:
        fits = jnp.all(prefix + req_s <= free_s + 1e-6, axis=1)
    else:
        # a Policy bypassing PodFitsResources must also bypass the
        # in-round capacity admission guard (it exists only to keep
        # same-round co-admissions consistent with that predicate)
        fits = jnp.ones((P,), bool)
    within = jnp.arange(P, dtype=jnp.int32) - seg_starts
    acc_s = (c_s >= 0) & fits & (within < per_node_cap)
    if sorted_gate is not None:
        acc_s = acc_s & sorted_gate(order2, seg_starts)
    return acc_s[_inverse_permutation(order2)]


def _lean_rounds(pods, nodes, sel, rank, lean_plan, max_rounds,
                 per_node_cap, enabled_mask):
    """The fused round loop for lean batches (see the routing comment in
    :func:`_batch_impl`). Same carry, same three-exit cond, same
    admission rule; one materialized (P, N) matrix per round."""
    from kubernetes_tpu.ops.predicates import BIT

    P = pods.req.shape[0]
    N = nodes.allocatable.shape[0]
    static_reasons, _prog = static_predicate_reasons(pods, nodes, sel)
    if enabled_mask is not None:
        static_reasons = static_reasons & jnp.int32(enabled_mask)
    static_ok = (static_reasons == 0) & nodes.valid[None, :] \
        & pods.valid[:, None]
    res_on = enabled_mask is None or bool(
        enabled_mask & (1 << BIT["PodFitsResources"]))
    window = N * per_node_cap
    guard = NEG * 0.5  # real scores are finite and tiny next to NEG

    def round_body(carry):
        assigned, u, _, rnd, use_plan, sk_stats = carry
        active = (assigned == -1) & pods.valid
        ms = _lean_masked_score(pods, nodes, u, active, static_ok, res_on,
                                lean_plan)
        rowmax = jnp.max(ms, axis=1, keepdims=True)
        feasible_any = rowmax[:, 0] > guard
        wkey = jnp.where(active & feasible_any, rank, jnp.int32(P + 1))
        arank = _inverse_permutation(jnp.argsort(wkey))
        if P > window:
            # the bidder window only binds when more pods than window
            # slots exist — a trace-time fact, so small batches compile
            # it out entirely (arank < window is vacuous there)
            gate = active & feasible_any & (arank < window)
            ms = jnp.where(gate[:, None], ms, NEG)
            rowmax = jnp.max(ms, axis=1, keepdims=True)
        # tied bits are derived views over ms — never materialized as a
        # (P, N) matrix; the block gather re-derives its one (P, BL)
        # slice from ms directly
        N_ = ms.shape[1]
        BL = min(64, N_)
        row_live = rowmax > guard  # (P, 1)
        if N_ % BL:
            # non-bucketed node axis: materialize tied once and use the
            # shared fallback (see _blocked_pick)
            choice, _tc = _blocked_pick((ms >= rowmax) & row_live, arank)
        else:
            ms3 = ms.reshape(P, N_ // BL, BL)
            t3 = (ms3 >= rowmax[:, :, None]) & row_live[:, :, None]

            def gather_block(bidx):
                blk_ms = jnp.take_along_axis(
                    ms3, bidx[:, None, None], axis=1)[:, 0, :]  # (P, BL)
                return (blk_ms >= rowmax) & row_live

            choice, _tc = _blocked_pick_core(t3, gather_block, arank)
        feasible = jnp.take_along_axis(
            ms, choice[:, None], axis=1)[:, 0] > guard
        choice = jnp.where(feasible, choice, -1)
        # shared score-ordered per-node admission — the port/topology
        # guards the lean gates prove vacuous are simply absent
        accepted = _admit_scored(choice, rank, pods.req,
                                 nodes.allocatable - u.requested,
                                 per_node_cap, res_on)
        new_assigned = jnp.where(accepted, choice, assigned)
        u = _apply_batch(u, pods, jnp.where(accepted, choice, 0), accepted)
        return (new_assigned, u, jnp.any(accepted), rnd + 1, use_plan,
                sk_stats)

    def cond(carry):
        assigned, _, progressed, rnd, _, _ = carry
        return (progressed & (rnd < max_rounds)
                & jnp.any((assigned == -1) & pods.valid))

    init = (jnp.full((P,), -1, jnp.int32), usage_from_nodes(nodes),
            jnp.asarray(True), jnp.asarray(0, jnp.int32),
            jnp.asarray(False), jnp.full((2,), -1.0, jnp.float32))
    with jax.named_scope("lean_rounds"):
        assigned, u, _, rounds, _, sk_stats = jax.lax.while_loop(
            cond, round_body, init)
    return assigned, u, rounds, sk_stats


@partial(jax.jit, static_argnames=("weights_key", "max_rounds", "per_node_cap",
                                   "use_sinkhorn", "skip_key", "no_ports",
                                   "no_pod_affinity", "no_spread",
                                   "fused_score", "auto_sinkhorn",
                                   "with_stats", "enabled_mask", "sk_tol",
                                   "potentials_out"))
def _batch_impl(pods, nodes, sel, topo, weights_key, max_rounds, per_node_cap,
                extra_mask, vol=None, static_vol=None, enabled_mask=None,
                extra_score=None, use_sinkhorn=False, skip_key=(),
                no_ports=False, no_pod_affinity=False, no_spread=False,
                fused_score=True, auto_sinkhorn=True, with_stats=False,
                sk_init=None, sk_tol=None, potentials_out=False):
    weights = dict(weights_key) if weights_key is not None else None
    # warm-started Sinkhorn (incremental solve, docs/perf.md): engage the
    # potential carry ONLY when a warm start or tolerance is requested —
    # the stock path keeps its per-round cold start bit for bit (each
    # round's plan solves from zeros exactly as before)
    sk_warm = (sk_init is not None) or (sk_tol is not None)
    # trace-time routing gate: no preference kernel live -> no possible
    # asymmetric tie cohort -> compile the router (and the plan branch)
    # out entirely
    auto_sinkhorn = (auto_sinkhorn and not use_sinkhorn
                     and not all(k in skip_key
                                 for k in _PREFERENCE_KERNELS))
    P = pods.req.shape[0]
    perm = queue_order(pods)
    rank = _inverse_permutation(perm)
    # ---- fused lean round path (trace-time routed) -----------------------
    # Constraint-light batches — no topology/volume/port coupling, no
    # extender/plugin mask or score, argmax tie-break, and a provably
    # exact lean scoring plan — run a round body that materializes ONE
    # (P, N) f32 matrix per round (the masked score) instead of the
    # general path's reasons + mask + per-kernel score temporaries, and
    # pick tied columns with the blocked selection instead of the (P, N)
    # cumsum + argmax. Placements are bit-identical to the general path
    # (identical mask/score arithmetic, rotation tie-break, and
    # score-ordered admission — pinned by the tests/test_fused_validate.py
    # parity suite): on the CPU headline shape this is the difference
    # between losing to and beating the sequential oracle (see
    # docs/perf.md readback budget).
    lean_plan = None
    if (topo is None and vol is None and static_vol is None
            and extra_mask is None and extra_score is None and no_ports
            and not use_sinkhorn and not auto_sinkhorn):
        lean_plan = _lean_score_plan(weights_key, skip_key)
    if lean_plan is not None:
        lr = _lean_rounds(pods, nodes, sel, rank, lean_plan, max_rounds,
                          per_node_cap, enabled_mask)
        if potentials_out:
            # the lean route never engages the transport plan (its gates
            # require use_sinkhorn and the auto-router off) — zero
            # potentials keep the return structure uniform
            return lr + ((jnp.zeros((P,), jnp.float32),
                          jnp.zeros((nodes.allocatable.shape[0],),
                                    jnp.float32)),)
        return lr
    # pods carrying host ports or attach-counted/conflict-checked volumes
    # are admitted at most one per node per round (conservative, exact):
    # their feasibility couples across same-round admissions to one node
    has_port = (
        jnp.sum(pods.port_wild_pp, axis=1) + jnp.sum(pods.port_spec_pp, axis=1)
    ) > 0
    if vol is not None:
        has_port = has_port | (
            jnp.sum(pods.vol_any_mh, axis=1)
            + jnp.sum(pods.pd_mh, axis=1)
            + jnp.sum(pods.csi_mh, axis=1)
            > 0
        )
    # usage-invariant predicate bits + selector program table, computed
    # ONCE against the base nodes: the round loop below re-evaluates only
    # the dynamic predicates (resources/ports/topology/volumes) against
    # the usage-updated node view
    hoisted = static_predicate_reasons(pods, nodes, sel)
    if vol is not None and static_vol is None:
        static_vol = static_volume_reasons(pods, nodes, sel, vol,
                                           prog=hoisted[1])
    # usage-invariant SCORING slice, once per batch: the static kernels'
    # full matrices + the static raw map phases (ops/priorities.py
    # hoist_priorities) — the round loop then pays only the per-round
    # mask-dependent normalizes and the genuinely dynamic kernels
    from kubernetes_tpu.ops.priorities import hoist_priorities

    hoisted_prio = hoist_priorities(pods, nodes, sel, weights, skip_key)
    if topo is not None and not (no_pod_affinity and no_spread):
        from kubernetes_tpu.ops.topology import sensitive_keys

        # (P, K) topology keys along which same-round co-admission into one
        # topology group could violate required anti-affinity / hard spread
        # (static over rounds; the per-round escape check is inside the
        # loop). Skipped when BOTH batch gates hold: a universe matcher
        # left by a long-gone affinity pod would otherwise mark clean pods
        # topology-sensitive and serialize their admissions per pair.
        sens = sensitive_keys(pods, topo, nodes.topo_pair_id.shape[1])
    else:
        sens = None

    def round_body(carry):
        assigned, u, _, rnd, use_plan, sk_stats, sk_u, sk_v = carry
        cur = nodes_with_usage(nodes, u)
        active = (assigned == -1) & pods.valid
        mask = (
            run_predicates(pods, cur, sel, topo, vol, static_vol,
                           enabled_mask, hoisted=hoisted,
                           no_ports=no_ports,
                           no_pod_affinity=no_pod_affinity,
                           no_spread=no_spread).mask
            & active[:, None]
        )
        if extra_mask is not None:
            mask = mask & extra_mask
        score = run_priorities(pods, cur, sel, mask, weights, topo,
                               skip=skip_key, hoisted=hoisted_prio,
                               fused=fused_score)
        if extra_score is not None:
            score = score + extra_score
        # ---- bidder window: the next K pods the serial loop would pop ----
        # Only the top K = N*per_node_cap active pods (by queue rank) that
        # have at least one feasible node may bid this round. Per-round
        # admissions are capped at K anyway, so this costs no throughput,
        # and it makes priority ordering a structural invariant: a pod can
        # be admitted only when fewer than K feasible higher-rank pods are
        # still waiting (the serial loop is the K=1 case). Pods with no
        # feasible node don't consume window slots — the serial loop pops
        # them, fails them, and moves on (they may become feasible later in
        # the batch as affinity targets land).
        feasible_any = jnp.any(mask, axis=1)
        wkey = jnp.where(active & feasible_any, rank, jnp.int32(P + 1))
        arank = _inverse_permutation(jnp.argsort(wkey))
        window = nodes.allocatable.shape[0] * per_node_cap
        # pre-window feasibility, kept for the auto-router: the window
        # admits only the next K bidders, so a tie-contention cohort
        # whose tail populations are still queued (exactly the
        # asymmetric-second-choice scenario) would be invisible to a
        # post-window detector in round 0
        mask_full = mask
        mask = mask & (active & feasible_any & (arank < window))[:, None]
        # deterministic tie-break spread — the batched analog of
        # selectHost's randomized round-robin among max-scoring nodes
        # (generic_scheduler.go:292). Without it, a uniform workload herds
        # every bidder onto the same lowest-index argmax node each round
        # and throughput collapses to per_node_cap pods/round. Each bidder
        # rotates among its EXACTLY-tied best nodes by its dense window
        # index, so the best-ranked bidder still takes the lowest node
        # index (deterministic) and equal-score cohorts fan out evenly.
        rowmax = jnp.max(jnp.where(mask, score, NEG), axis=1, keepdims=True)
        masked = jnp.where(mask, score - rowmax, NEG)

        def column_slots():
            # column capacity: how many ACTIVE pods could land on each node,
            # bounded per resource by the smallest active request — the pod
            # count column alone (~110/node) almost never binds, which would
            # degrade the plan to a per-row softmax with no pre-spreading
            from kubernetes_tpu.snapshot import RES_PODS

            free = jnp.maximum(nodes.allocatable - u.requested, 0.0)  # (N, R)
            min_req = jnp.min(
                jnp.where(
                    active[:, None] & (pods.req > 0), pods.req, jnp.inf
                ),
                axis=0,
            )  # (R,)
            per_res = jnp.where(
                jnp.isfinite(min_req),
                jnp.floor(free / jnp.maximum(min_req, 1e-30)),
                jnp.inf,
            )
            slots = jnp.min(per_res, axis=1)
            return jnp.where(jnp.isfinite(slots), slots, free[:, RES_PODS])

        def plan_tied(slots, pu, pv):
            # choose from the entropic-OT transport plan instead of the raw
            # per-pod argmax: the plan balances the whole batch against node
            # capacities, so contended pods pre-spread instead of colliding
            # (ops/sinkhorn.py; SURVEY.md §7.2 step 5). Convergence stats
            # (iterations-to-tolerance, final residual) ride the carry so
            # the driver can surface them per cycle without a host sync;
            # with_stats is a static key, so disabling telemetry compiles
            # the stats scan out entirely. Under sk_warm the potentials
            # ride the carry too: each round (and, via sk_init, each
            # CYCLE) warm-starts from the previous equilibrium, with the
            # sk_tol early-exit capping converged re-solves at one
            # verification iteration.
            from kubernetes_tpu.ops.sinkhorn import sinkhorn_plan

            res = sinkhorn_plan(masked, mask, slots,
                                with_stats=with_stats,
                                init=(pu, pv) if sk_warm else None,
                                tol=sk_tol, return_potentials=True)
            if with_stats:
                plan, stats, (pu2, pv2) = res
            else:
                plan, (pu2, pv2) = res
                stats = jnp.full((2,), -1.0, jnp.float32)
            # identical pods get identical plan rows (Sinkhorn scaling
            # preserves row identity), so the plan argmax needs the same
            # rotation tie-break as the raw-score branch or a uniform
            # cohort herds onto one node at per_node_cap pods/round
            pmasked = jnp.where(mask, plan, -1.0)
            prowmax = jnp.max(pmasked, axis=1, keepdims=True)
            return mask & (pmasked >= prowmax), stats, pu2, pv2

        argmax_tied = mask & (score >= rowmax)
        if use_sinkhorn:
            tied, sk_stats, sk_u, sk_v = plan_tied(column_slots(),
                                                   sk_u, sk_v)
        elif auto_sinkhorn:
            # ---- per-batch solver routing (VERDICT r4 item 5) ----
            # Decide ONCE, from round 0's structures: the plan wins only
            # on tie-contention with ASYMMETRIC second choices (pinned by
            # tests/test_sinkhorn.py::test_plan_beats_argmax_on_tied_
            # preferences); everything else takes the argmax path, so
            # the detection must separate (a) multi-way-tied bids, on
            # (b) oversubscribed columns, with (c) heterogeneous
            # runner-up gaps — each alone is argmax territory (uniform
            # cohorts rotate out; unique-best contention is what the
            # score-ordered admission already resolves).
            slots = column_slots()

            def detect():
                # evaluated over the PRE-window mask: the whole batch's
                # tie structure, not just the next K bidders (score is
                # computed before windowing, so this costs no extra
                # scoring — only the detection's own reductions, paid
                # once per batch inside the rnd==0 cond)
                rm = jnp.max(jnp.where(mask_full, score, NEG), axis=1,
                             keepdims=True)
                tied_f = mask_full & (score >= rm)
                tc0 = jnp.sum(tied_f, axis=1).astype(jnp.float32)
                share = tied_f.astype(jnp.float32) / jnp.maximum(
                    tc0, 1.0)[:, None]
                demand = jnp.sum(share, axis=0)  # (N,) intended tie mass
                over = demand > jnp.maximum(slots, 1e-9)
                cohort = (tc0 >= 2.0) & jnp.any(
                    tied_f & over[None, :], axis=1)
                alt = mask_full & ~tied_f
                r2 = jnp.max(jnp.where(alt, score, NEG), axis=1)
                gap = jnp.where(jnp.any(alt, axis=1),
                                rm[:, 0] - r2, 1e3)
                gmin = jnp.min(jnp.where(cohort, gap, jnp.inf))
                gmax = jnp.max(jnp.where(cohort, gap, -jnp.inf))
                return ((jnp.sum(cohort) >= AUTO_TIE_MIN_COHORT)
                        & (gmax - gmin >= AUTO_TIE_GAP_MARGIN))

            prev_decision = use_plan
            use_plan = jax.lax.cond(rnd == 0, detect,
                                    lambda: prev_decision)
            prev_stats = sk_stats
            prev_u, prev_v = sk_u, sk_v
            tied, sk_stats, sk_u, sk_v = jax.lax.cond(
                use_plan,
                lambda: plan_tied(slots, prev_u, prev_v),
                lambda: (argmax_tied, prev_stats, prev_u, prev_v))
        else:
            tied = argmax_tied
        # rotation pick via the blocked two-level selection (bit-identical
        # to the old full-width cumsum + argmax, which profiled at
        # 23 ms + 16 ms per round on XLA:CPU — see _blocked_pick)
        choice, _tcount = _blocked_pick(tied, arank)  # (P,)
        feasible = jnp.take_along_axis(mask, choice[:, None], axis=1)[:, 0]
        choice = jnp.where(feasible, choice, -1)

        # ---- per-node acceptance: highest-priority prefix that fits ----
        # (shared spelling: _admit_scored). The admission cap exists
        # because all pods in a round score against the SAME usage state:
        # unbounded admission herds the whole queue onto the current-best
        # node (usage-sensitive scores — LeastRequested, SelectorSpread —
        # only update between rounds). A small cap turns each round into
        # an auction step: nodes admit their best bidders, usage updates,
        # the rest re-bid. cap=1 approaches the serial loop's packing
        # quality; larger caps trade score fidelity for fewer rounds.
        from kubernetes_tpu.ops.predicates import BIT as _BIT

        res_on = enabled_mask is None or bool(
            enabled_mask & (1 << _BIT["PodFitsResources"]))

        def port_gate(order2, seg_starts):
            # one port-bearing pod per node per round (conservative, exact)
            hp_s = has_port[order2].astype(jnp.int32)
            hp_prefix = _segment_prefix(hp_s[:, None], seg_starts)[:, 0]
            return (hp_s == 0) | (hp_prefix == 0)

        accepted = _admit_scored(choice, rank, pods.req,
                                 nodes.allocatable - u.requested,
                                 per_node_cap, res_on,
                                 sorted_gate=port_gate)

        if sens is not None:
            from kubernetes_tpu.ops.topology import self_escape_active

            big = jnp.int32(2**30)

            def first_per_group(ok, gate, key):
                """Keep only the lowest-rank gated pod per group; ungated
                pods pass through."""
                gkey = jnp.where(gate, key, big)
                o = jnp.lexsort((rank, gkey))
                gk_s = gkey[o]
                starts = jnp.searchsorted(gk_s, gk_s, side="left")
                within = jnp.arange(P, dtype=jnp.int32) - starts
                keep_s = (gk_s == big) | (within == 0)
                keep = jnp.zeros((P,), bool).at[o].set(keep_s)
                return ok & (keep | ~gate)

            # one topo-sensitive pod per topology pair per round — the
            # batched guard for anti-affinity / hard-spread interactions
            # among same-round admissions (the serial loop never needs
            # this; in-batch it replaces per-pod cache updates)
            ok = accepted
            tpid = nodes.topo_pair_id
            for k in range(tpid.shape[1]):
                pair = tpid[jnp.clip(choice, 0, tpid.shape[0] - 1), k]
                gate = ok & (choice >= 0) & sens[:, k] & (pair >= 0)
                ok = first_per_group(ok, gate, pair)
            if not no_pod_affinity:
                # one self-match escapee per affinity program per round:
                # the second first-pod-of-a-group must wait and join the
                # first (affinity-only machinery)
                esc = self_escape_active(pods, cur, topo)
                gate_e = ok & (choice >= 0) & esc
                ok = first_per_group(ok, gate_e, pods.affprog_id)
            accepted = ok

        new_assigned = jnp.where(accepted, choice, assigned)
        u = _apply_batch(u, pods, jnp.where(accepted, choice, 0), accepted)
        progressed = jnp.any(accepted)
        return (new_assigned, u, progressed, rnd + 1, use_plan, sk_stats,
                sk_u, sk_v)

    def cond(carry):
        assigned, _, progressed, rnd = carry[:4]
        # three exits: a no-progress round (contention fixpoint), the
        # round budget, or — the hot-path case — NOTHING LEFT TO PLACE.
        # Without the third check every fully-placed batch pays one dead
        # full-matrix round just to discover it made no progress (the
        # uncontended headline's entire round 2); the (P,) reduction here
        # is noise next to the (P, N) passes it skips. Placements are
        # untouched: a round with zero active pods cannot change anything.
        return (progressed & (rnd < max_rounds)
                & jnp.any((assigned == -1) & pods.valid))

    # sk_stats: [-1, -1] = sinkhorn never engaged this solve; otherwise
    # the LAST round's [iterations-to-converge, final residual].
    # sk_u/sk_v: the potential carry — seeded from sk_init (a previous
    # cycle's equilibrium) under sk_warm, zeros otherwise.
    N_nodes = nodes.allocatable.shape[0]
    u0_init = (sk_init[0] if sk_warm and sk_init is not None
               else jnp.zeros((P,), jnp.float32))
    v0_init = (sk_init[1] if sk_warm and sk_init is not None
               else jnp.zeros((N_nodes,), jnp.float32))
    init = (jnp.full((P,), -1, jnp.int32), usage_from_nodes(nodes),
            jnp.asarray(True), jnp.asarray(0, jnp.int32),
            jnp.asarray(False), jnp.full((2,), -1.0, jnp.float32),
            u0_init.astype(jnp.float32), v0_init.astype(jnp.float32))
    with jax.named_scope("general_rounds"):
        assigned, u, _, rounds, _, sk_stats, sk_u, sk_v = jax.lax.while_loop(
            cond, round_body, init)
    if potentials_out:
        return assigned, u, rounds, sk_stats, (sk_u, sk_v)
    return assigned, u, rounds, sk_stats


def batch_assign(
    pods: DevicePods,
    nodes: DeviceNodes,
    sel: DeviceSelectors,
    weights: Optional[Dict[str, float]] = None,
    max_rounds: int = 256,
    per_node_cap: int = 1,
    topo=None,
    extra_mask: Optional[jnp.ndarray] = None,
    vol=None,
    static_vol: Optional[jnp.ndarray] = None,
    enabled_mask: Optional[int] = None,
    extra_score: Optional[jnp.ndarray] = None,
    use_sinkhorn: bool = False,
    skip_priorities=(),
    no_ports: bool = False,
    no_pod_affinity: bool = False,
    no_spread: bool = False,
    fused_score: bool = True,
    auto_sinkhorn: bool = True,
    fault_hook=None,
    fault_site: str = "solve:batch",
    stats_out: bool = False,
    sk_init=None,
    sk_tol: Optional[float] = None,
    potentials_out: bool = False,
) -> Tuple[jnp.ndarray, UsageState, jnp.ndarray]:
    """Fast batched solver. Returns (assigned row per pod or -1, final
    usage, rounds executed). ``per_node_cap`` bounds admissions per node per
    round (see _batch_impl); with P pending pods and N nodes expect about
    ceil(P / (N * cap)) rounds on uniform workloads. ``extra_mask`` as in
    :func:`greedy_assign`.

    ``stats_out`` appends a 4th element: a (2,) f32 device array
    [sinkhorn iterations-to-converge, final residual] from the last
    round that ran the transport plan, or [-1, -1] when the plan never
    engaged (argmax path). Stays a device value — the observability
    layer reads it back once per cycle at the host boundary.

    ``fused_score`` (feature flag, default on): collapse the two hoisted
    normalize-reduce scoring kernels into one single-output pass per
    round (ops/priorities.py _fused_pair_normalize). Only engages when
    the regrouped accumulation is provably exact (all-stock kernels,
    integer weights) — bit-identical placements either way, pinned by
    tests/test_priorities.py.

    ``extra_mask=None`` is a TRACE-TIME fact (not substituted with an
    all-true matrix): clean batches route to the fused lean round path
    (see _batch_impl) whose per-round device work — and therefore the
    d2h readback wait at the host boundary — is several times smaller.

    Warm-started Sinkhorn (incremental solve): ``sk_init`` seeds the
    transport-plan potentials from a previous solve's equilibrium (a
    ``(u0, v0)`` pair), ``sk_tol`` switches the scaling to the
    tolerance-gated early-exit loop, and ``potentials_out`` appends the
    final ``(u, v)`` pair to the return so the caller can carry it into
    the next cycle. All three leave the stock cold-start path untouched
    when unset."""
    if fused_score:
        # resolve the backend policy HERE so it becomes part of the jit
        # key: use_pallas() reads env + backend at call time, and a
        # policy flip must recompile, not hit a stale cache entry
        from kubernetes_tpu.ops.fused_score import use_pallas

        fused_score = use_pallas()
    args, kw = _batch_impl_call(
        pods, nodes, sel, weights, max_rounds, per_node_cap, topo,
        extra_mask, vol, static_vol, enabled_mask, extra_score,
        use_sinkhorn, skip_priorities, no_ports, no_pod_affinity,
        no_spread, fused_score, auto_sinkhorn, stats_out,
        sk_init, sk_tol, potentials_out)
    out = _batch_impl(*args, **kw)
    potentials = out[4] if potentials_out else None
    assigned, u, rounds, sk_stats = out[:4]
    if fault_hook is not None:
        # the fault-injection seam (see greedy_assign): the hook stands
        # where an out-of-process solver's response would be decoded
        assigned, u, rounds = fault_hook(fault_site, assigned, u, rounds,
                                         nodes.allocatable.shape[0])
    ret = (assigned, u, rounds)
    if stats_out:
        ret = ret + (sk_stats,)
    if potentials_out:
        ret = ret + (potentials,)
    return ret


def _batch_impl_call(pods, nodes, sel, weights, max_rounds, per_node_cap,
                     topo, extra_mask, vol, static_vol, enabled_mask,
                     extra_score, use_sinkhorn, skip_priorities, no_ports,
                     no_pod_affinity, no_spread, fused_score, auto_sinkhorn,
                     stats_out, sk_init=None, sk_tol=None,
                     potentials_out=False):
    """THE one spelling of the ``_batch_impl`` invocation — returns
    ``(args, kwargs)`` for both the live call (:func:`batch_assign`)
    and the AOT lowering (:func:`solve_cost_analysis`), so the cost
    capture can never silently lower a different program than the one
    live cycles run (a new kwarg added in one place and missed in the
    other would skew model_efficiency without failing anything)."""
    key = tuple(sorted(weights.items())) if weights is not None else None
    args = (pods, nodes, sel, topo, key, max_rounds, per_node_cap,
            extra_mask, vol, static_vol, enabled_mask, extra_score,
            use_sinkhorn)
    kw = dict(skip_key=tuple(skip_priorities), no_ports=no_ports,
              no_pod_affinity=no_pod_affinity, no_spread=no_spread,
              fused_score=fused_score, auto_sinkhorn=auto_sinkhorn,
              with_stats=stats_out, sk_init=sk_init, sk_tol=sk_tol,
              potentials_out=potentials_out)
    return args, kw


def solve_cost_analysis(
    pods: DevicePods,
    nodes: DeviceNodes,
    sel: DeviceSelectors,
    weights: Optional[Dict[str, float]] = None,
    *,
    max_rounds: int = 256,
    per_node_cap: int = 1,
    topo=None,
    vol=None,
    static_vol: Optional[jnp.ndarray] = None,
    enabled_mask: Optional[int] = None,
    extra_score: Optional[jnp.ndarray] = None,
    use_sinkhorn: bool = False,
    skip_priorities=(),
    no_ports: bool = False,
    no_pod_affinity: bool = False,
    no_spread: bool = False,
    stats_out: bool = False,
) -> Optional[dict]:
    """XLA cost analysis of the dense batch solve at this exact
    signature — the perf ledger's model-side capture (obs/ledger.py):
    warmup lowers the SAME jitted program :func:`batch_assign` runs
    (identical static keys) and reads the compiled executable's
    ``cost_analysis()`` flops / bytes-accessed. Best-effort by
    contract: returns ``{"flops": ..., "bytes_accessed": ...}`` or
    ``None`` when the backend (or this jax version) declines AOT
    analysis — warmup must never fail for its accountant. Host-side
    AOT only; never on the cycle path."""
    from kubernetes_tpu.ops.fused_score import use_pallas

    from kubernetes_tpu.obs.ledger import capture_cost_analysis

    args, kw = _batch_impl_call(
        pods, nodes, sel, weights, max_rounds, per_node_cap, topo,
        None, vol, static_vol, enabled_mask, extra_score,
        use_sinkhorn, skip_priorities, no_ports, no_pod_affinity,
        no_spread, use_pallas(), True, stats_out)
    return capture_cost_analysis(lambda: _batch_impl.lower(*args, **kw))


def solve_memory_analysis(
    pods: DevicePods,
    nodes: DeviceNodes,
    sel: DeviceSelectors,
    weights: Optional[Dict[str, float]] = None,
    *,
    max_rounds: int = 256,
    per_node_cap: int = 1,
    topo=None,
    vol=None,
    static_vol: Optional[jnp.ndarray] = None,
    enabled_mask: Optional[int] = None,
    extra_score: Optional[jnp.ndarray] = None,
    use_sinkhorn: bool = False,
    skip_priorities=(),
    no_ports: bool = False,
    no_pod_affinity: bool = False,
    no_spread: bool = False,
    stats_out: bool = False,
) -> Optional[dict]:
    """XLA memory analysis of the dense batch solve at this exact
    signature — the memory ledger's preflight capture
    (obs/memledger.py): warmup lowers the SAME jitted program
    :func:`batch_assign` runs (identical static keys, via
    :func:`_batch_impl_call` like :func:`solve_cost_analysis`) and
    reads the compiled executable's ``memory_analysis()``
    argument/output/temp bytes. Best-effort by contract: returns the
    byte dict or ``None`` when the backend declines — warmup must
    never fail for its accountant. Host-side AOT only; never on the
    cycle path (``memory_analysis`` exists only on the COMPILED
    stage, so each capture pays one AOT compile at warmup)."""
    from kubernetes_tpu.ops.fused_score import use_pallas

    from kubernetes_tpu.obs.memledger import capture_memory_analysis

    args, kw = _batch_impl_call(
        pods, nodes, sel, weights, max_rounds, per_node_cap, topo,
        None, vol, static_vol, enabled_mask, extra_score,
        use_sinkhorn, skip_priorities, no_ports, no_pod_affinity,
        no_spread, use_pallas(), True, stats_out)
    return capture_memory_analysis(
        lambda: _batch_impl.lower(*args, **kw))


# graftlint: disable-scope=R2,R7 -- the deliberate host boundary: trust-but-
# verify reads the solver's claimed result back ONCE per cycle to check it
# before any pod binds; cheap O(P*R + N*R) numpy by design (see docstring)
def validate_solution(
    assigned, usage: UsageState, pods: DevicePods, nodes: DeviceNodes,
    enabled_mask: Optional[int] = None,
) -> Tuple[bool, str]:
    """Trust-but-verify for a solver result before any pod is assumed —
    the check that keeps a lying/corrupted solver (or a stale-snapshot
    race) from binding an infeasible pod. Returns (ok, reason) with
    ``reason`` one of shape | dtype | range | invalid-node | finiteness
    | capacity.

    Deliberately cheap (O(P·R + N·R) host numpy): shape and index-range
    sanity, claimed-usage finiteness, and a full per-node capacity
    recomputation from the assignment itself (never trusting the
    solver's usage for feasibility). Capacity is only enforced when the
    PodFitsResources predicate is (the Policy-bypass rule the solvers
    themselves follow), and only blames nodes that were within
    allocatable BEFORE this batch — force-bound overcommit from the
    cache is not the solver's lie."""
    import numpy as np

    from kubernetes_tpu.ops.predicates import BIT

    P = pods.req.shape[0]
    try:
        a = np.asarray(assigned)
    except Exception:
        return False, "dtype"
    if a.ndim != 1 or a.shape[0] < P:
        return False, "shape"
    a = a[:P]
    if not np.issubdtype(a.dtype, np.integer):
        if not np.all(np.isfinite(a)):
            return False, "finiteness"
        if np.any(a != np.floor(a)):
            return False, "dtype"
        a = a.astype(np.int64)
    valid = np.asarray(pods.valid)
    nvalid = np.asarray(nodes.valid)
    N = nvalid.shape[0]
    if np.any(valid & ((a < -1) | (a >= N))):
        return False, "range"
    sel = valid & (a >= 0)
    if np.any(sel & ~nvalid[np.clip(a, 0, N - 1)]):
        return False, "invalid-node"
    if not bool(np.all(np.isfinite(np.asarray(usage.requested)))):
        return False, "finiteness"
    res_on = enabled_mask is None or bool(
        enabled_mask & (1 << BIT["PodFitsResources"])
    )
    if res_on and np.any(sel):
        req = np.asarray(pods.req)
        base = np.asarray(nodes.requested)
        alloc = np.asarray(nodes.allocatable)
        add = np.zeros_like(base)
        np.add.at(add, a[sel], req[sel])
        # relative tolerance: float32 scatter-add drift scales with the
        # magnitude (memory columns are bytes), so an absolute epsilon
        # would false-positive on honest results
        tol = 1e-5 * np.maximum(alloc, 1.0) + 1e-6
        pre_ok = base <= alloc + tol
        over = (base + add > alloc + tol) & nvalid[:, None] & (add > 0)
        if np.any(over & pre_ok):
            return False, "capacity"
    return True, ""


#: device_validate verdict-code vocabulary, in the same precedence order
#: the host checker reports (index 0 = ok). Host-side decode:
#: ``VALIDATE_REASONS[int(code)]``.
VALIDATE_REASONS = ("", "shape", "dtype", "range", "invalid-node",
                    "finiteness", "capacity")


@partial(jax.jit, static_argnames=("enabled_mask",))
def _device_validate_impl(assigned, usage_requested, pods, nodes,
                          enabled_mask=None):
    """Device half of :func:`device_validate`: every check
    :func:`validate_solution` runs, as one jitted reduction over the
    assignment — the verdict stays a pair of device scalars until the
    driver's single end-of-solve readback."""
    from kubernetes_tpu.ops.predicates import BIT

    with jax.named_scope("device_validate"):
        P = pods.req.shape[0]
        valid = pods.valid
        nvalid = nodes.valid
        N = nvalid.shape[0]
        a = assigned[:P]
        if not jnp.issubdtype(a.dtype, jnp.integer):
            # a lying solver returning floats: finiteness first, then the
            # integer-valuedness check, then proceed on the floored values —
            # the same precedence the host checker applies
            fin_a_bad = ~jnp.all(jnp.isfinite(a))
            dtype_bad = jnp.any(jnp.where(jnp.isfinite(a), a != jnp.floor(a),
                                          False))
            a = jnp.where(jnp.isfinite(a), a, -2.0).astype(jnp.int32)
        else:
            fin_a_bad = jnp.asarray(False)
            dtype_bad = jnp.asarray(False)
            a = a.astype(jnp.int32)
        range_bad = jnp.any(valid & ((a < -1) | (a >= N)))
        sel = valid & (a >= 0)
        ac = jnp.clip(a, 0, N - 1)
        invalid_node = jnp.any(sel & ~nvalid[ac])
        fin_bad = ~jnp.all(jnp.isfinite(usage_requested))
        res_on = enabled_mask is None or bool(
            enabled_mask & (1 << BIT["PodFitsResources"]))
        if res_on:
            req = pods.req
            base = nodes.requested
            alloc = nodes.allocatable
            w = sel.astype(req.dtype)[:, None]
            add = jnp.zeros_like(base).at[jnp.where(sel, ac, 0)].add(req * w)
            tol = 1e-5 * jnp.maximum(alloc, 1.0) + 1e-6
            pre_ok = base <= alloc + tol
            over = (base + add > alloc + tol) & nvalid[:, None] & (add > 0)
            cap_bad = jnp.any(over & pre_ok)
        else:
            cap_bad = jnp.asarray(False)
        code = jnp.where(
            fin_a_bad, 5, jnp.where(
                dtype_bad, 2, jnp.where(
                    range_bad, 3, jnp.where(
                        invalid_node, 4, jnp.where(
                            fin_bad, 5, jnp.where(cap_bad, 6, 0))))))
        return code.astype(jnp.int32), jnp.sum(sel, dtype=jnp.int32)


def device_validate(assigned, usage: UsageState, pods: DevicePods,
                    nodes: DeviceNodes,
                    enabled_mask: Optional[int] = None):
    """Fused on-device twin of :func:`validate_solution` — the readback
    killer: instead of materializing the assignment, the claimed usage,
    and four node/pod tables on host to re-check capacity (six device
    syncs per cycle), the whole verdict is computed on device and rides
    the driver's ONE end-of-solve readback as two int32 scalars
    ``(code, valid_count)``; decode with :data:`VALIDATE_REASONS`.

    Semantics are bit-matched to the host checker (pinned by the
    randomized parity suite in tests/test_fused_validate.py) with two
    host-visible shortcuts kept on host because they read metadata only:
    a result that is not array-like at all, or whose shape cannot cover
    the batch, never reaches the device. The one caveat: the capacity
    recomputation's f32 scatter-add may associate differently than the
    host's sequential ``np.add.at``, so verdicts within one float ulp of
    the relative tolerance boundary can differ — the host checker stays
    the trust floor (``robustness.host_validate``) and the parity oracle.

    Like the host checker this never trusts the solver's claimed usage
    for feasibility — capacity is recomputed from the assignment itself;
    the claimed usage is only checked for finiteness."""
    shape = getattr(assigned, "shape", None)
    dtype = getattr(assigned, "dtype", None)
    if shape is None or dtype is None or len(shape) != 1 \
            or shape[0] < pods.req.shape[0]:
        return None  # host verdict: (False, "shape") — caller falls back
    return _device_validate_impl(assigned, usage.requested, pods, nodes,
                                 enabled_mask=enabled_mask)
