"""SelectorSpread at the shape of the v1.16 density test
(test/e2e/scalability/density.go): nodes saturated with pods of a few
ReplicationControllers, then latency pods that are each the only replica
of a controller of their own, so every arriving pod brings an owner set
no earlier pod had.

Pinned here: the program's SelectorSpread scores and placements against
the plain reference (``seqref``) over cycles of new owner sets,
confirmations and oldest-first deletes; the cache's incrementally kept
owner counts against a full recount after every step of random
sequences; a new owner set costing a delta snapshot, not a full one;
and the device owner state sized by the batch, not by the owner sets
ever interned."""

import dataclasses
import random

import numpy as np
import pytest

from kubernetes_tpu import seqref
from kubernetes_tpu.api.types import LabelSelector, Requirement
from kubernetes_tpu.cache import SchedulerCache
from kubernetes_tpu.ops.arrays import (
    attach_owner_columns,
    pods_to_device,
    selectors_to_device,
)
from kubernetes_tpu.ops.predicates import run_predicates
from kubernetes_tpu.ops.priorities import selector_spread
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.snapshot import owner_width
from kubernetes_tpu.testing import make_node, make_pod

N_NODES, ZONES, PER_NODE, N_RC = 48, 4, 30, 3
NODE = dict(cpu_milli=4000, memory=34359738368, pods=110)
SATURATION = dict(cpu_milli=40, memory=343597383)  # 1/100 of the node
LATENCY = dict(cpu_milli=800, memory=6871947673)  # 1/5 of the node
MAX_BOUND = N_NODES  # latency pods kept bound, deleted oldest-first


def rc_pod(name, rc, node=""):
    sel = LabelSelector(match_labels={"name": rc})
    return make_pod(name, node_name=node,
                    labels={"name": rc, "type": "densityPod"},
                    owner_uid=f"rc-{rc}", spread_selectors=(sel,),
                    **SATURATION)


def latency_pod(name):
    sel = LabelSelector(match_labels={"name": name})
    return make_pod(name, labels={"name": name}, owner_uid=f"rc-{name}",
                    spread_selectors=(sel,), **LATENCY)


def density_cluster(seed):
    rng = random.Random(seed)
    nodes = [make_node(f"node-{i}", zone=f"zone-{i % ZONES}", **NODE)
             for i in range(N_NODES)]
    where = list(range(N_NODES)) * PER_NODE
    rcs = [i % N_RC for i in range(len(where))]
    rng.shuffle(where)
    rng.shuffle(rcs)
    existing = [rc_pod(f"saturation-{i}", f"density-{rcs[i]}",
                       f"node-{where[i]}") for i in range(len(where))]
    return nodes, existing


def recount(cache):
    """Owner counts recounted from scratch: every owner set interned, every
    cached pod, the reference's matching rule."""
    u = cache.packer.u
    out = {}
    for o, (ns, sels) in enumerate(u.owner_set_items):
        c = {}
        for nd_name, pods in cache._pods_by_node.items():
            for p in pods.values():
                if p.namespace == ns and all(s.matches(p.labels)
                                             for s in sels):
                    c[nd_name] = c.get(nd_name, 0) + 1
        if c:
            out[o] = c
    return out


def _spread_scores(sched, pending):
    """The program's SelectorSpread kernel on the cycle's own inputs (the
    resident snapshot with the batch's owner columns) next to the
    reference's scores over the same feasible nodes."""
    cache, pk = sched.cache, sched.cache.packer
    for p in pending:
        pk.intern_pod(p)
    _nt, dn, _mode = cache.device_snapshot()
    pt = pk.pack_pods(pending)
    dn = attach_owner_columns(dn, cache.owner_columns(
        pt.owner_cols, pt.owner_match_mh.shape[1], dn.valid.shape[0]))
    dp = pods_to_device(pt)
    ds = selectors_to_device(pk.pack_selector_tables())
    mask = np.asarray(run_predicates(dp, dn, ds).mask)
    got = np.asarray(selector_spread(dp, dn, ds, None, mask))
    order = cache.node_order()
    nodes = [cache.node(n) for n in order]
    m = mask[: len(pending), : len(nodes)]
    want = seqref.selector_spread_scores(
        pending, nodes, {n: cache.pods_on(n) for n in order}, m)
    return got[: len(pending), : len(nodes)], np.asarray(want), m


@pytest.mark.parametrize("solver,seed", [("greedy", 3), ("batch", 5),
                                         ("batch", 11)])
def test_density_spread_and_placements_match_seqref(solver, seed):
    """Cycles of latency pods (each a new owner set, now and then a
    saturation pod of an existing controller among them), every binding
    confirmed, bound latency pods deleted oldest-first above one per
    node. Each cycle: the SelectorSpread scores equal the reference's on
    every feasible node; the serial solver places exactly as the
    reference's scheduleOne loop; every placement is feasible."""
    rng = random.Random(seed)
    nodes, existing = density_cluster(seed)
    sched = Scheduler(solver=solver, enable_preemption=False)
    for nd in nodes:
        sched.on_node_add(nd)
    for p in existing:
        sched.on_pod_add(p)
    bound = []  # (pending form, node) oldest first
    seq = 0
    live_scores = 0
    for cycle in range(8):
        pending = []
        for _ in range(rng.randrange(2, 12)):
            if rng.random() < 0.2:
                pending.append(rc_pod(f"late-{seq}",
                                      f"density-{rng.randrange(N_RC)}"))
            else:
                pending.append(latency_pod(f"lat-{seq}"))
            seq += 1
        got, want, m = _spread_scores(sched, pending)
        np.testing.assert_array_equal(np.where(m, got, 0),
                                      np.where(m, want, 0),
                                      err_msg=f"cycle {cycle}")
        live_scores += int((np.where(m, want, 10) < 10).sum())
        order = sched.cache.node_order()
        node_objs = [sched.cache.node(n) for n in order]
        scheduled = [p for n in order for p in sched.cache.pods_on(n)]
        serial = seqref.serial_schedule(pending, node_objs, scheduled)
        for p in pending:
            sched.on_pod_add(p)
        res = sched.schedule_cycle()
        assert res.owner_columns == len({p.spread_selectors[0].match_labels[
            "name"] for p in pending})
        assert res.scheduled == len(pending)
        if solver == "greedy":
            for p, (j, _s) in zip(pending, serial):
                assert res.assignments[p.key()] == order[j], (cycle, p.name)
        for p in pending:
            node = res.assignments[p.key()]
            here = [q for q in sched.cache.pods_on(node) if q.key() != p.key()]
            assert seqref.feasible(p, sched.cache.node(node), here)
            sched.on_pod_update(p, dataclasses.replace(p, node_name=node))
            bound.append((p, node))
        while len(bound) > MAX_BOUND:
            p, node = bound.pop(0)
            sched.on_pod_delete(dataclasses.replace(p, node_name=node))
        assert sched.cache.owner_counts() == recount(sched.cache)
    # the spread term really moved scores (a pod matching pods on a node)
    assert live_scores > 0


def _random_selectors(rng, vocab):
    k = rng.random()
    key, val = rng.choice(vocab)
    if k < 0.5:
        return (LabelSelector(match_labels={key: val}),)
    if k < 0.65:
        vals = tuple(sorted({val, rng.choice(vocab)[1]}))
        return (LabelSelector(match_expressions=(
            Requirement(key, "In", vals),)),)
    if k < 0.75:  # no positive requirement: checked against every pod
        return (LabelSelector(match_expressions=(
            Requirement(key, "NotIn", (val,)),)),)
    if k < 0.8:
        return (LabelSelector(),)  # matches every pod of the namespace
    k2, v2 = rng.choice(vocab)
    return (LabelSelector(match_labels={key: val}),
            LabelSelector(match_expressions=(Requirement(k2, "Exists", ()),)))


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_incremental_owner_counts_equal_full_recount(seed):
    """Random sequences of pod adds, assumes, confirmations (labels kept
    or changed), deletes, forgets and new owner sets — interned by
    pending pods between the pods' arrivals: after EVERY step the
    cache's counts equal a from-scratch recount, and the owner columns
    of a batch equal the packer's one-shot count."""
    rng = random.Random(seed)
    vocab = [(k, f"v{j}") for k in ("app", "tier", "name") for j in range(3)]
    cache = SchedulerCache()
    pk = cache.packer
    nodes = [make_node(f"n{i}", zone=f"z{i % 2}") for i in range(6)]
    for nd in nodes:
        cache.add_node(nd)
    placed = {}
    owners = []
    for step in range(120):
        op = rng.random()
        names = [nd.name for nd in nodes]
        if op < 0.25:
            labels = dict(rng.sample(vocab, rng.randrange(0, 3)))
            ns = rng.choice(["default", "default", "other"])
            pod = make_pod(f"p{step}", namespace=ns, labels=labels,
                           node_name=rng.choice(names))
            if rng.random() < 0.5:
                cache.add_pod(pod)
            else:
                cache.assume_pod(dataclasses.replace(pod, node_name=""),
                                 pod.node_name)
            placed[pod.key()] = pod
        elif op < 0.4 and placed:
            key = rng.choice(sorted(placed))
            cache.remove_pod(key)
            del placed[key]
        elif op < 0.5 and placed:
            # confirmation by the API: same node, labels kept or edited
            key = rng.choice(sorted(placed))
            old = placed[key]
            labels = (dict(old.labels) if rng.random() < 0.5
                      else dict(rng.sample(vocab, rng.randrange(0, 3))))
            new = dataclasses.replace(old, labels=labels)
            cache.add_pod(new)
            placed[key] = new
        elif op < 0.55 and placed:
            key = rng.choice(sorted(placed))
            if cache.is_assumed(key):
                cache.forget_pod(key)
                del placed[key]
        else:
            # a pending pod interns a new owner set
            ns = rng.choice(["default", "other"])
            sels = _random_selectors(rng, vocab)
            p = make_pod(f"pending{step}", namespace=ns,
                         spread_selectors=sels)
            pk.intern_pod(p)
            owners.append(p)
        assert cache.owner_counts() == recount(cache), f"step {step}"
    assert len(pk.u.owner_set_items) > 20
    # a batch's columns: the cache's counts against the one-shot count
    cache.snapshot()
    order = cache.node_order()
    node_objs = [cache.node(n) for n in order]
    pt = pk.pack_pods(owners[-12:])
    want = pk.pack_owner_columns(
        node_objs, [p for n in order for p in cache.pods_on(n)], pt)
    got = cache.owner_columns(pt.owner_cols, pt.owner_match_mh.shape[1],
                              len(order))
    np.testing.assert_array_equal(got, want)
    assert want.sum() > 0


def test_new_owner_set_costs_a_delta_snapshot():
    """A latency pod whose owner set is new must not repack every node:
    after its arrival the next cycle's snapshot is a delta (only the
    rows the previous cycle's bindings dirtied), never a full one."""
    nodes, existing = density_cluster(0)
    sched = Scheduler(enable_preemption=False)
    for nd in nodes:
        sched.on_node_add(nd)
    for p in existing:
        sched.on_pod_add(p)
    modes = []
    for c in range(4):
        pods = [latency_pod(f"lat-{c}-{i}") for i in range(3)]
        for p in pods:
            sched.on_pod_add(p)
        res = sched.schedule_cycle()
        assert res.scheduled == 3
        modes.append(res.snapshot_mode)
        for p in pods:
            sched.on_pod_update(p, dataclasses.replace(
                p, node_name=res.assignments[p.key()]))
    assert modes[0] == "full"
    assert modes[1:] == ["delta"] * 3


def test_owner_state_shape_is_set_by_the_batch():
    """The device owner state is sized by the batch's owner sets: after
    10,000 owner sets are interned and their pods forgotten, a batch of
    the same pods presents the same owner-column shapes as before, the
    resident node table still carries none, and no full repack follows."""
    nodes, existing = density_cluster(1)
    sched = Scheduler(enable_preemption=False)
    for nd in nodes:
        sched.on_node_add(nd)
    for p in existing:
        sched.on_pod_add(p)
    cache, pk = sched.cache, sched.cache.packer
    batch = [latency_pod(f"batch-{i}") for i in range(5)]

    def shapes():
        _nt, dn, mode = cache.device_snapshot()
        pt = pk.pack_pods(batch)
        dnb = sched._with_owner_columns(dn, pt)
        return (dn.owner_counts.shape, dnb.owner_counts.shape,
                pods_to_device(pt).owner_match_mh.shape, mode)

    before = shapes()
    n_pad = before[1][0]
    assert before[0] == (n_pad, 0)
    assert before[1] == (n_pad, owner_width(5)) == (n_pad, 8)
    for i in range(10_000):
        p = latency_pod(f"churn-{i}")
        pk.intern_pod(p)
        pk.forget_pod(p.key())
    assert len(pk.u.owner_set_items) > 10_000
    after = shapes()
    assert after[:3] == before[:3]
    assert after[3] == "clean"


def test_batches_without_owners_keep_the_lean_path():
    """A batch that spreads by no owner set (every ``sp5k-base`` batch)
    presents no owner columns, SelectorSpread folds to its constant and
    the lean round path is taken, owner-bearing pods in the cluster or
    not; a batch of latency pods takes the general rounds."""
    from kubernetes_tpu.ops.assign import _lean_score_plan
    from kubernetes_tpu.ops.priorities import solver_gates

    nodes, existing = density_cluster(2)
    cache = SchedulerCache()
    for nd in nodes:
        cache.add_node(nd)
    for p in existing:
        cache.add_pod(p)
    pk = cache.packer
    base = [make_pod(f"base-{i}", cpu_milli=100, memory=524288000)
            for i in range(8)]
    for p in base:
        pk.intern_pod(p)
    nt = cache.snapshot()
    pt = pk.pack_pods(base)
    assert pt.owner_match_mh.shape == (8, 0) and len(pt.owner_cols) == 0
    skip = solver_gates(nt, pt)[0]
    assert "SelectorSpreadPriority" in skip
    assert _lean_score_plan(None, tuple(skip)) is not None
    dense = pk.pack_pods([latency_pod(f"lat-{i}") for i in range(8)])
    skip = solver_gates(nt, dense)[0]
    assert "SelectorSpreadPriority" not in skip
    assert _lean_score_plan(None, tuple(skip)) is None


def test_extender_owner_columns_race_driver_upkeep():
    """An extender handler thread interns a new owner set and reads its
    columns (SchedulerCache.owner_columns, which counts the new set)
    while the driver stores and drops labelled pods. No read raises, and
    afterwards the kept counts equal a full recount: no update is lost
    to a catch-up racing the driver's upkeep."""
    import sys
    import threading

    cache = SchedulerCache()
    pk = cache.packer
    nodes = [make_node(f"n{i}") for i in range(8)]
    for nd in nodes:
        cache.add_node(nd)
    cache.snapshot()
    errors = []
    stop = threading.Event()

    def driver():
        try:
            live = []
            for i in range(1500):
                pod = make_pod(f"p{i}", labels={"app": f"a{i % 5}",
                                                "name": f"p{i}"},
                               node_name=f"n{i % 8}")
                cache.add_pod(pod)
                live.append(pod.key())
                if len(live) > 200:
                    cache.remove_pod(live.pop(0))
        except Exception as e:  # surfaced by the assert below
            errors.append(e)
        finally:
            stop.set()

    def extender(t):
        try:
            j = 0
            while not stop.is_set():
                # a new owner set a request's pod brings, anchored on a
                # label the driver's pods carry
                sel = LabelSelector(
                    match_labels={"app": f"a{j % 5}"},
                    match_expressions=(Requirement(
                        "name", "NotIn", (f"x{t}-{j}",)),))
                p = make_pod(f"req-{t}-{j}", spread_selectors=(sel,))
                pk.intern_pod(p)
                pt = pk.pack_pods([p])
                cache.owner_columns(pt.owner_cols,
                                    pt.owner_match_mh.shape[1], len(nodes))
                j += 1
        except Exception as e:
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)  # interleave the threads finely
    try:
        threads = [threading.Thread(target=extender, args=(0,)),
                   threading.Thread(target=driver)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    assert not errors, errors
    assert len(pk.u.owner_set_items) > 10
    assert cache.owner_counts() == recount(cache)


def test_owner_set_is_indexed_before_it_is_published():
    """A reader on another thread bounds owner sets by owner_set_items
    (the cache's catch-up) and finds matches through the index: a set
    must be in the index by the time it appears in owner_set_items, or
    a pod stored in between is never counted into it."""
    from kubernetes_tpu.snapshot import Universe, owner_anchor_pairs

    u = Universe()

    class Published(list):
        def append(self, item):
            ns, sels = item
            pairs = owner_anchor_pairs(sels)
            filed = (u.owner_open.get(ns, ()) if pairs is None else
                     [o for k, v in pairs
                      for o in u.owner_index.get((ns, k, v), ())])
            assert len(self) in filed
            super().append(item)

    u.owner_set_items = Published()
    for sels in ((LabelSelector(match_labels={"name": "a"}),),
                 (LabelSelector(match_expressions=(
                     Requirement("tier", "In", ("x", "y")),)),),
                 (LabelSelector(match_expressions=(
                     Requirement("tier", "NotIn", ("x",)),)),)):
        assert u.intern_owner_set("default", sels) == len(u.owner_set_items) - 1
    assert u.matching_owner_sets(make_pod("p", labels={"name": "a"})) == [0, 2]
