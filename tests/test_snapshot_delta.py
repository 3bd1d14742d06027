"""Incremental device-resident snapshot (PR 5 tentpole): property tests
that the delta path — dirty rows re-packed on host, patched into the
resident device arrays by the jitted scatter — is bit-identical to a
full rebuild after ANY event sequence, plus the fallback triggers
(shape change, width growth, dirty fraction, explicit invalidation)
and the pack-memo correctness on the pod axis."""

import dataclasses
import random

import numpy as np
import pytest

from kubernetes_tpu.cache import SchedulerCache
from kubernetes_tpu.ops.arrays import nodes_to_device
from kubernetes_tpu.testing import make_node, make_pod


def _full_device(cache):
    """Reference: a fresh full pack + upload of the cache's world."""
    pods = [p for nd in cache.nodes() for p in cache.pods_on(nd.name)]
    return nodes_to_device(cache.packer.pack_nodes(cache.nodes(), pods))


def _assert_dev_equal(dev, ref, ctx=""):
    for name in dev._fields:
        a, b = np.asarray(getattr(dev, name)), np.asarray(getattr(ref, name))
        assert a.shape == b.shape, f"{ctx}{name}: shape {a.shape} != {b.shape}"
        assert np.array_equal(a, b), f"{ctx}{name}: values diverged"


def _rand_node(rng, i):
    labels = {}
    if rng.random() < 0.5:
        labels["zone"] = f"z{rng.randrange(3)}"
    if rng.random() < 0.3:
        labels[f"k{rng.randrange(4)}"] = f"v{rng.randrange(3)}"
    return make_node(
        f"n{i}",
        cpu_milli=rng.choice([2000, 4000, 8000]),
        memory=rng.choice([8, 16, 32]) * 2**30,
        pods=110,
        labels=labels,
    )


def _rand_pod(rng, i):
    kw = dict(cpu_milli=rng.choice([100, 250, 500]),
              memory=rng.choice([128, 256, 512]) * 2**20)
    if rng.random() < 0.25:
        kw["labels"] = {"app": f"a{rng.randrange(3)}"}
    if rng.random() < 0.15:
        kw["node_selector"] = {"zone": f"z{rng.randrange(3)}"}
    return make_pod(f"p{i}", **kw)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_delta_patched_device_tables_match_full_rebuild(seed):
    """The acceptance property: after randomized event sequences (node
    add/update/delete, pod assume/add/update/delete — the informer +
    bind-effect feed), the resident device table equals a from-scratch
    full pack bit for bit, on EVERY snapshot call."""
    rng = random.Random(seed)
    cache = SchedulerCache()
    # allow plenty of delta headroom so both paths are exercised
    cache.max_dirty_frac = 0.5
    for i in range(12):
        cache.add_node(_rand_node(rng, i))
    placed = {}  # key -> node name
    pod_seq = 0
    modes = []
    for step in range(60):
        op = rng.random()
        names = [nd.name for nd in cache.nodes()]
        if op < 0.35 and names:
            pod = _rand_pod(rng, pod_seq)
            pod_seq += 1
            node = rng.choice(names)
            if rng.random() < 0.5:
                cache.assume_pod(pod, node)
            else:
                cache.add_pod(dataclasses.replace(pod, node_name=node))
            placed[pod.key()] = node
        elif op < 0.5 and placed:
            key = rng.choice(sorted(placed))
            cache.remove_pod(key)
            del placed[key]
        elif op < 0.65 and names:
            # node update: condition/label churn marks the row dirty
            name = rng.choice(names)
            nd = cache.node(name)
            cache.update_node(dataclasses.replace(
                nd, unschedulable=not nd.unschedulable))
        elif op < 0.72:
            cache.add_node(_rand_node(rng, 100 + step))
        elif op < 0.78 and len(names) > 4:
            victim = rng.choice(names)
            cache.remove_node(victim)
            for key, node in list(placed.items()):
                if node == victim:
                    cache.remove_pod(key)
                    del placed[key]
        elif op < 0.83:
            cache.invalidate_snapshot()
        elif op < 0.88:
            # host-only consumer (server.py extender path): eats the
            # dirty set; the device must drain the queued deltas later
            cache.snapshot()
        if rng.random() < 0.6:
            _t, dev, mode = cache.device_snapshot()
            modes.append(mode)
            _assert_dev_equal(dev, _full_device(cache),
                              ctx=f"seed {seed} step {step} [{mode}] ")
    # the sequence must actually exercise the delta path, not just fall
    # back to full every time (that would vacuously pass)
    assert "delta" in modes, f"no delta snapshot taken (modes: {set(modes)})"
    assert "full" in modes


def test_width_growth_forces_full_rebuild():
    """Universe width growth (a pod whose selector interns a new label
    bucket past the current power-of-two) must fall back to a full
    rebuild — and still match the reference."""
    cache = SchedulerCache()
    for i in range(8):
        cache.add_node(make_node(f"n{i}", labels={"zone": f"z{i % 2}"}))
    cache.device_snapshot()
    # intern a flood of distinct selector pairs -> widths() changes
    for j in range(40):
        cache.packer.intern_pod(
            make_pod(f"sel{j}", node_selector={f"key{j}": f"val{j}"}))
    _t, dev, mode = cache.device_snapshot()
    assert mode == "full"
    _assert_dev_equal(dev, _full_device(cache))


def test_dirty_fraction_above_threshold_reuploads_full():
    cache = SchedulerCache(max_dirty_frac=0.25)
    for i in range(8):
        cache.add_node(make_node(f"n{i}"))
    cache.device_snapshot()
    for i in range(4):  # 50% dirty > 25%
        nd = cache.node(f"n{i}")
        cache.update_node(dataclasses.replace(nd, unschedulable=True))
    _t, dev, mode = cache.device_snapshot()
    assert mode == "full"
    _assert_dev_equal(dev, _full_device(cache))
    # one small change now rides the delta path
    cache.update_node(dataclasses.replace(cache.node("n7"),
                                          unschedulable=True))
    _t, dev, mode = cache.device_snapshot()
    assert mode == "delta" and cache.last_upload_rows == 1
    _assert_dev_equal(dev, _full_device(cache))


def test_host_only_snapshot_cannot_strand_device_table():
    """server.py's extender-serving path calls the HOST snapshot(),
    consuming the dirty set; the resident device table must drain the
    missed deltas on its next refresh instead of reporting 'clean' over
    stale rows."""
    cache = SchedulerCache(max_dirty_frac=0.9)
    for i in range(8):
        cache.add_node(make_node(f"n{i}"))
    cache.device_snapshot()
    cache.assume_pod(make_pod("x0", cpu_milli=200), "n2")
    cache.snapshot()  # host-only caller eats the dirty set
    _t, dev, mode = cache.device_snapshot()
    assert mode == "delta" and cache.last_upload_rows == 1
    _assert_dev_equal(dev, _full_device(cache))
    # two host-only refreshes queue two deltas; one device drain applies
    # both and the arrays still match
    cache.assume_pod(make_pod("x1", cpu_milli=200), "n3")
    cache.snapshot()
    cache.assume_pod(make_pod("x2", cpu_milli=200), "n4")
    cache.snapshot()
    _t, dev, mode = cache.device_snapshot()
    assert mode == "delta" and cache.last_upload_rows == 2
    _assert_dev_equal(dev, _full_device(cache))


def test_clean_cache_reuses_resident_arrays():
    cache = SchedulerCache()
    for i in range(4):
        cache.add_node(make_node(f"n{i}"))
    _t, dev1, mode1 = cache.device_snapshot()
    _t, dev2, mode2 = cache.device_snapshot()
    assert mode1 == "full" and mode2 == "clean"
    assert dev2 is dev1  # the SAME resident object, no work done
    assert cache.last_upload_rows == 0


def test_volume_state_change_invalidates_through_pack_epoch():
    """set_volume_state bumps the pack epoch; the scheduler path calls
    invalidate_snapshot, but even a bare cache sees fresh pod tables —
    the PodTable memo must never serve rows packed under dead volume
    state."""
    pk = SchedulerCache().packer
    pod = make_pod("v0", cpu_milli=100)
    t1 = pk.pack_pods([pod])
    assert pk.pack_pods([pod]) is t1  # memo hit under unchanged sig
    pk.set_volume_state()  # epoch bump
    t2 = pk.pack_pods([pod])
    assert t2 is not t1  # stale table not served
    np.testing.assert_array_equal(t1.req, t2.req)


def test_pod_pack_memo_invalidates_on_universe_growth():
    """Same pods + a GROWN matcher universe (bucket unchanged) must
    repack: the old rows would miss the new matcher's column."""
    from kubernetes_tpu.api.types import (
        Affinity,
        LabelSelector,
        PodAffinityTerm,
    )

    pk = SchedulerCache().packer
    pods = [make_pod(f"m{i}", labels={"app": "web"}) for i in range(3)]
    t1 = pk.pack_pods(pods)
    assert pk.pack_pods(pods) is t1
    # a new pod with anti-affinity interns a matcher the existing pods
    # match — their matcher_mh rows change even though widths may not
    affp = make_pod("anti0", affinity=Affinity(pod_anti_affinity_required=(
        PodAffinityTerm(
            label_selector=LabelSelector(match_labels={"app": "web"}),
            topology_key="kubernetes.io/hostname"),
    )))
    pk.intern_pod(affp)
    t2 = pk.pack_pods(pods)
    assert t2 is not t1
    assert t2.matcher_mh[:, : t1.matcher_mh.shape[1]].sum() \
        >= t1.matcher_mh.sum()


def test_pending_pod_update_invalidates_pack_memo():
    """Review finding (r6): a pending pod updated IN PLACE (same uid)
    whose new selector values are all already interned moves neither
    the (key, uid) memo key nor the universe signature — the driver's
    on_pod_update must forget the pod so the next pack re-interns,
    or the scheduler keeps placing it by the pre-update spec."""
    from kubernetes_tpu.scheduler import Scheduler

    s = Scheduler(enable_preemption=False)
    s.on_node_add(make_node("m0", cpu_milli=4000, labels={"tier": "a"}))
    s.on_node_add(make_node("m1", cpu_milli=4000, labels={"tier": "b"}))
    old = make_pod("sel", cpu_milli=100, node_selector={"tier": "a"})
    s.queue.add(old)
    pk = s.cache.packer
    pk.intern_pod(old)
    # pre-intern BOTH label pairs so the update changes no interner
    pk.intern_pod(make_pod("other", cpu_milli=100,
                           node_selector={"tier": "b"}))
    pk.pack_pods([old])  # memoize under the OLD spec
    new = dataclasses.replace(old, node_selector={"tier": "b"})
    s.on_pod_update(old, new)
    r = s.schedule_cycle()
    assert r.assignments.get("default/sel") == "m1", r.assignments


def test_forget_pod_drops_memoized_tables():
    pk = SchedulerCache().packer
    pods = [make_pod(f"f{i}") for i in range(2)]
    t1 = pk.pack_pods(pods)
    pk.forget_pod(pods[0].key())
    assert pk.pack_pods(pods) is not t1  # epoch bump invalidated


_PER_POD_CACHES = ("_pod_refs", "_vol_cache", "_vol_pods", "_vec_cache")


def _vol_pod(name, uid):
    from kubernetes_tpu.api.types import PodVolume

    return dataclasses.replace(
        make_pod(name, cpu_milli=1), uid=uid,
        volumes=(PodVolume(pvc=f"claim-{name}"),))


#: each per-pod cache filled through its own insert site alone, so a
#: site that stores without indexing the uid is not masked by another
_FILL_ONE_CACHE = {
    "_pod_refs": lambda pk, p: pk.intern_pod(
        dataclasses.replace(p, volumes=())),
    "_vol_pods": lambda pk, p: pk.intern_pod(p),
    "_vol_cache": lambda pk, p: pk.resolve_volumes(p),
    "_vec_cache": lambda pk, p: pk._pod_vectors([p], pk.u.n_resources()),
}


@pytest.mark.parametrize("cache_name", _PER_POD_CACHES)
def test_forget_pod_drops_every_uid_of_the_key(cache_name):
    """A pod deleted and recreated under the same key leaves entries for
    both uids; forget_pod drops them all from each cache through the
    pod-key index, leaves other pods' entries alone, leaves no index
    entry for the key, and bumps the pack epoch once."""
    pk = SchedulerCache().packer
    first, again = _vol_pod("churn", "uid-a"), _vol_pod("churn", "uid-b")
    others = [_vol_pod(f"keep{i}", f"uid-k{i}") for i in range(3)]
    for p in (first, again, *others):
        _FILL_ONE_CACHE[cache_name](pk, p)
    cache = getattr(pk, cache_name)
    key = first.key()
    assert {k for k in cache if k[0] == key} == {
        (key, "uid-a"), (key, "uid-b")}
    kept = {k: v for k, v in cache.items() if k[0] != key}
    assert len(kept) == len(others)
    epoch = pk._pack_epoch

    pk.forget_pod(key)

    assert not [k for k in cache if k[0] == key]
    assert dict(cache) == kept
    assert key not in pk._uids_by_key
    assert all(o.key() in pk._uids_by_key for o in others)
    assert pk._pack_epoch == epoch + 1


class _UnwalkableDict(dict):
    """A dict that answers lookups and pops but refuses to be walked."""

    def _walked(self, *a, **kw):
        raise AssertionError("a per-pod cache was walked")

    __iter__ = keys = items = values = _walked


def test_forget_pod_never_walks_a_cache():
    """Structural guard, no timing: with 1,000 pods packed and every
    per-pod cache replaced by a dict that raises when walked, forget_pod
    still drops the pod's entries — it reaches them by key alone."""
    pk = SchedulerCache().packer
    pods = [dataclasses.replace(_vol_pod(f"w{i}", f"uid-w{i}"),
                                node_name="n0") for i in range(1000)]
    pk.pack_nodes([make_node("n0")], pods)  # interns: all four caches
    for name in _PER_POD_CACHES:
        assert len(getattr(pk, name)) == 1000, name
        setattr(pk, name, _UnwalkableDict(dict.items(getattr(pk, name))))
    victim = pods[500].key()
    pk.forget_pod(victim)
    pk.forget_pod("default/never-seen")
    for name in _PER_POD_CACHES:
        cache = getattr(pk, name)
        assert len(cache) == 999, name
        assert (victim, "uid-w500") not in cache, name
    assert victim not in pk._uids_by_key


def test_scheduler_uses_resident_snapshot_across_cycles():
    """Driver integration: cycle 1 uploads full; an idle-state cycle 2
    with new pods only reuses/patches (assume effects dirty exactly the
    landed rows); metrics + CycleResult record the mode."""
    from kubernetes_tpu.scheduler import Scheduler

    s = Scheduler(enable_preemption=False)
    for i in range(8):
        s.on_node_add(make_node(f"n{i}", cpu_milli=4000,
                                memory=32 * 2**30, pods=110))
    for i in range(10):
        s.queue.add(make_pod(f"p{i}", cpu_milli=100, memory=256 * 2**20))
    r1 = s.schedule_cycle()
    assert r1.scheduled == 10 and r1.snapshot_mode == "full"
    for i in range(10, 14):
        s.queue.add(make_pod(f"p{i}", cpu_milli=100, memory=256 * 2**20))
    r2 = s.schedule_cycle()
    assert r2.scheduled == 4
    # the 10 binds dirtied <= 8 rows of 8 -> full (frac), but after a
    # quiet cycle the assume effects of THIS cycle are <= 4 rows
    for i in range(14, 16):
        s.queue.add(make_pod(f"p{i}", cpu_milli=100, memory=256 * 2**20))
    r3 = s.schedule_cycle()
    assert r3.scheduled == 2
    assert r3.snapshot_mode in ("delta", "full", "clean")
    m = s.metrics.snapshot_packs
    total = sum(m.value(mode=md) for md in ("full", "delta", "clean"))
    assert total == 3
    # legacy path still works bit-identically
    s2 = Scheduler(enable_preemption=False, device_resident_snapshot=False)
    for i in range(8):
        s2.on_node_add(make_node(f"n{i}", cpu_milli=4000,
                                 memory=32 * 2**30, pods=110))
    for i in range(10):
        s2.queue.add(make_pod(f"p{i}", cpu_milli=100, memory=256 * 2**20))
    r = s2.schedule_cycle()
    assert r.scheduled == 10 and r.snapshot_mode == "host"
    assert r.assignments == r1.assignments
