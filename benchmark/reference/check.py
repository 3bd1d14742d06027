"""The output check: replay what the run did against the host objects and
judge every answer with the plain reference.

Numbers compared, each against its limit (``LIMITS``; PERF.md gives the
readings each was set from):

- ``double_binds``: bindings of a pod already bound (the API server's
  list, read from the binder). Limit 0.
- ``over_allocatable``: nodes whose cpu, memory or pod count exceeds
  allocatable at the end of any cycle, recomputed from the host objects
  with every delete applied in order. Limit 0.
- ``infeasible``: placements on an unknown node or one that a hard
  predicate forbids (unschedulable, untolerated NoSchedule taint, node
  selector, required affinity). Limit 0.
- ``false_unschedulable``: pods a cycle failed while a node fitted them
  at the end of that cycle. Limit 0.
- ``unanswered``: pods due in the window that no cycle bound (open
  loop: within the drain after the window; backlog: pods a cycle popped
  and neither bound nor failed), plus 1 when the window bound nothing.
  Limit 0.
- ``score_regret``: over a sample of the window's placements drawn from
  the seed, the largest amount by which some node that still fitted the
  pod at the end of its cycle is sure to outscore the chosen node, under
  the v1.16 default priorities at any usage between the cycle's start
  and end (:func:`scores.bounds`). The batched solve picks a top-scored
  node at the usage of its round, so a sound run reads 0 or less. A node
  that took ``k`` pods in a cycle admitted at most ``per_node_cap`` of
  them per round, so its last-admitted pod scored it with at least
  ``k - per_node_cap`` of the others already on it; the same amount is
  taken over the sampled nodes for that pod (:func:`node_regret`).
"""

from __future__ import annotations

import numpy as np

from reference import scores as S

#: number -> limit. ``score_regret``: see PERF.md "Limits".
LIMITS = {
    "double_binds": 0,
    "over_allocatable": 0,
    "infeasible": 0,
    "false_unschedulable": 0,
    "unanswered": 0,
    "score_regret": 1.5,
}


def node_arrays(cluster) -> S.Nodes:
    ns = cluster.nodes
    return S.Nodes(
        cap_cpu=np.array([n.allocatable.cpu_milli for n in ns], np.float64),
        cap_mem=np.array([n.allocatable.memory for n in ns], np.float64),
        cap_pods=np.array([n.allocatable.pods for n in ns], np.float64),
        zone=np.asarray(cluster.node_zone, np.int64),
        prefer_taints=cluster.node_tainted.astype(np.int64))


def _hard_ok(pod, node) -> bool:
    """Static hard predicates a pod of these cells could meet."""
    if node.unschedulable:
        return False
    for t in node.taints:
        if t.effect in ("NoSchedule", "NoExecute"):
            return False  # the cells' pods carry no tolerations
    for k, v in pod.node_selector.items():
        if node.labels.get(k) != v:
            return False
    return not pod.affinity.node_required


class Replay:
    """Node usage of the cluster as the run changed it, event by event."""

    def __init__(self, log, config: dict) -> None:
        cl = log.cluster
        self.log = log
        self.nodes = node_arrays(cl)
        N = len(cl.nodes)
        self.n_zones = int(max(cl.node_zone.max(initial=-1) + 1, 1))
        self.state = S.State(np.zeros(N), np.zeros(N), np.zeros(N))
        self.zone_weight = int(config["cluster"]["zone_affinity_weight"])
        self.node_index = {n.name: i for i, n in enumerate(cl.nodes)}
        self.weights = dict(config["priorities"])
        self.prefill = dict(log.prefill)
        S.check_supported(log.pool.pending + cl.existing, cl.nodes)
        for node, owner in zip(cl.existing_node.tolist(),
                               cl.existing_controller.tolist()):
            self.state.add(node, cl.existing_cpu, cl.existing_mem, owner)
        for inc, node in log.prefill:
            self._add(inc, node, 1)

    def spec(self, inc: int) -> S.PodSpec:
        cl = self.log.cluster
        s = self.log.pool.slot_of[inc]
        return S.PodSpec(cl.pod_cpu, cl.pod_mem, cl.slot_owner(s),
                         int(cl.slot_zone_pref[s % len(cl.slot_zone_pref)]),
                         self.zone_weight)

    def _add(self, inc: int, node: int, sign: int) -> None:
        sp = self.spec(inc)
        self.state.add(node, sp.cpu, sp.mem, sp.owner, sign)


def check(log, config: dict, traffic: dict, seed: int,
          limits: dict = LIMITS, control=None) -> dict:
    """Returns ``{number: (value, limit)}`` and the ``correct`` verdict
    under ``"correct"``. ``control(rp, start, incs, prev)`` -> ``(placed,
    end)`` puts another placer in the program's place on the sampled
    cycles (``prev``: the state at the previous cycle's start, or None):
    its placements of the same pods from the same start are the ones
    judged for ``score_regret`` and ``over_allocatable`` (the control,
    ``reference/control.py``)."""
    rp = Replay(log, config)
    pool = log.pool
    rng = np.random.default_rng(int(seed) % (2**63) + 7)
    win = [i for i, c in enumerate(log.cycles) if c.in_window
           and c.bind_hi > c.bind_lo]
    k = min(int(traffic["sample_cycles"]), len(win))
    sampled = set(rng.choice(win, size=k, replace=False).tolist()) if k else set()
    per_cycle = int(traffic["sample_pods_per_cycle"])
    cap = int(config["scheduler"]["per_node_cap"])
    b = log.bindings
    bound_at = {}  # inc -> node index, while bound
    ever = set()  # every incarnation bound once
    double = infeasible = false_unsched = 0
    over = set()
    regret = -np.inf
    n_regret = 0
    deletes = log.deletes
    di = 0
    start = None
    nodes = log.cluster.nodes
    for ci, cyc in enumerate(log.cycles):
        while di < len(deletes) and deletes[di][1] <= cyc.bind_lo:
            inc = deletes[di][0]
            node = bound_at.pop(inc, None)
            if node is None:
                node = rp.prefill.get(inc)
            if node is not None:
                rp._add(inc, node, -1)
            di += 1
        prev = start if control is not None else None
        start = (rp.state.copy() if ci in sampled or control is not None
                 else None)
        touched = []
        placed = []
        for k2 in range(cyc.bind_lo, cyc.bind_hi):
            key, name = b[k2]
            inc = pool.inc_of(key)
            node = rp.node_index.get(name)
            if inc in ever:
                double += 1
            ever.add(inc)
            if node is None or not _hard_ok(pool.pending_form(inc), nodes[node]):
                infeasible += 1
                continue
            bound_at[inc] = node
            rp._add(inc, node, 1)
            touched.append(node)
            placed.append((inc, node))
        st = rp.state
        over.update(_over(rp, st, touched))
        for key in cyc.failed:
            inc = pool.inc_of(key)
            if S.fits(rp.spec(inc), rp.nodes, st).any():
                false_unsched += 1
        if ci in sampled and placed:
            judged, end = placed, st
            if control is not None:
                incs = [i for i, _ in placed] + [pool.inc_of(k)
                                                 for k in cyc.failed]
                judged, end = control(rp, start, incs, prev)
                over.update(_over(rp, end, [n for _, n in judged]))
            pick = rng.choice(len(judged), size=min(per_cycle, len(judged)),
                              replace=False)
            for j in pick:
                r = placement_regret(rp, start, end, *judged[j])
                regret = max(regret, r)
                n_regret += 1
            by_node = {}
            for inc, node in judged:
                by_node.setdefault(node, []).append(inc)
            crowded = sorted(n for n, v in by_node.items() if len(v) > cap)
            if crowded:
                pick = rng.choice(crowded, size=min(per_cycle // 4 or 1,
                                                    len(crowded)),
                                  replace=False)
                for node in pick:
                    r = node_regret(rp, start, end, int(node),
                                    by_node[int(node)], cap)
                    regret = max(regret, r)
    unanswered = _unanswered(log)
    vals = {"double_binds": double, "over_allocatable": len(over),
            "infeasible": infeasible, "false_unschedulable": false_unsched,
            "unanswered": unanswered,
            "score_regret": float(regret) if n_regret else 0.0}
    out = {k: (v, limits[k]) for k, v in vals.items()}
    out["correct"] = all(v <= limits[k] for k, v in vals.items()) \
        and (n_regret > 0 or not win)
    out["regret_samples"] = n_regret
    return out


def _over(rp: Replay, st: S.State, touched) -> list:
    """The nodes of ``touched`` over cpu, memory or pod count."""
    t = np.asarray(touched, np.int64)
    n = rp.nodes
    return t[(st.cpu[t] > n.cap_cpu[t]) | (st.mem[t] > n.cap_mem[t])
             | (st.pods[t] > n.cap_pods[t])].tolist()


def placement_regret(rp: Replay, start: S.State, end: S.State, inc: int,
                     node: int) -> float:
    """How far a node that still fits the pod at the cycle's end is sure
    to outscore the chosen ``node`` (<= 0: consistent with an argmax at
    some round's usage)."""
    spec = rp.spec(inc)
    # the chosen node's usage when the pod was scored excluded the pod
    own_hi = end.copy(spec.owner)
    own_hi.add(node, spec.cpu, spec.mem, spec.owner, -1)
    lo, _, _, f_hi = S.bounds(spec, rp.nodes, start, end, rp.weights,
                              rp.n_zones)
    _, hi_own, _, _ = S.bounds(spec, rp.nodes, start, own_hi, rp.weights,
                               rp.n_zones)
    f_hi = f_hi.copy()
    f_hi[node] = False
    if not f_hi.any():
        return -np.inf
    return float(lo[f_hi].max() - hi_own[node])


def node_regret(rp: Replay, start: S.State, end: S.State, node: int,
                incs: list, cap: int) -> float:
    """A node took ``k = len(incs)`` pods, at most ``cap`` per round, so
    the last one admitted scored it with at least ``k - cap`` of the
    others on it. Returns the least, over the pods on the node, of how
    far some node still fitting at the cycle's end is sure to outscore it
    at such usage (> 0: no pod could have been admitted last)."""
    k = len(incs)
    if k <= cap:
        return -np.inf
    specs = [rp.spec(inc) for inc in incs]
    best = np.inf
    seen = set()
    for j, spec in enumerate(specs):
        key = (spec.cpu, spec.mem, spec.owner, spec.zone_pref)
        if key in seen:
            continue
        seen.add(key)
        # usage of ``node`` when this pod was scored, had it come last:
        # at least the k - cap smallest requests of the others (resource
        # by resource, as the shapes may differ), at most every other
        # pod; owner counts anywhere in between
        others = specs[:j] + specs[j + 1:]
        lo_st = start.copy(spec.owner)
        lo_st.cpu[node] += sum(sorted(o.cpu for o in others)[:k - cap])
        lo_st.mem[node] += sum(sorted(o.mem for o in others)[:k - cap])
        lo_st.pods[node] += k - cap
        hi_st = end.copy(spec.owner)
        hi_st.add(node, spec.cpu, spec.mem, spec.owner, -1)
        lo_oth, _, _, f_hi = S.bounds(spec, rp.nodes, start, end, rp.weights,
                                      rp.n_zones)
        _, hi_n, _, _ = S.bounds(spec, rp.nodes, lo_st, hi_st, rp.weights,
                                 rp.n_zones)
        f_hi = f_hi.copy()
        f_hi[node] = False
        if not f_hi.any():
            return -np.inf
        best = min(best, float(lo_oth[f_hi].max() - hi_n[node]))
    return best


def _unanswered(log) -> int:
    n = 0
    if log.due is not None:
        lo, hi = log.window_incs
        n += int(np.isnan(log.observed[lo:hi]).sum())
    else:
        n += sum(max(0, c.attempted - c.scheduled - len(c.failed))
                 for c in log.cycles)
    bound = sum(c.scheduled for c in log.cycles if c.in_window)
    return n + (1 if bound == 0 else 0)
