"""Unit tests for the scheduler cache — assumed-pod state machine
(``cache/interface.go:36-47``) and incremental snapshot parity
(``cache.go:211`` UpdateNodeInfoSnapshot)."""

import numpy as np
import pytest

from kubernetes_tpu.api.types import LabelSelector
from kubernetes_tpu.cache import CacheError, SchedulerCache
from kubernetes_tpu.snapshot import RES_CPU, SnapshotPacker
from kubernetes_tpu.testing import make_node, make_pod


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _table_row(cache, node_name):
    t = cache.snapshot()
    i = cache.node_order().index(node_name)
    return t, i


def test_assume_finish_add_lifecycle():
    c = SchedulerCache(clock=FakeClock())
    c.add_node(make_node("n1"))
    p = make_pod("p", cpu_milli=500)
    c.assume_pod(p, "n1")
    assert c.is_assumed(p.key())
    t, i = _table_row(c, "n1")
    assert t.requested[i, RES_CPU] == 500

    c.finish_binding(p.key())
    # watch confirms
    bound = make_pod("p", cpu_milli=500, node_name="n1")
    c.add_pod(bound)
    assert not c.is_assumed(p.key())
    t, i = _table_row(c, "n1")
    assert t.requested[i, RES_CPU] == 500


def test_assume_expiry_frees_capacity():
    clk = FakeClock()
    c = SchedulerCache(clock=clk, ttl_s=30)
    c.add_node(make_node("n1"))
    p = make_pod("p", cpu_milli=500)
    c.assume_pod(p, "n1")
    c.finish_binding(p.key())
    clk.advance(31)
    expired = c.cleanup_expired()
    assert expired == [p.key()]
    t, i = _table_row(c, "n1")
    assert t.requested[i, RES_CPU] == 0


def test_assume_without_finish_never_expires():
    clk = FakeClock()
    c = SchedulerCache(clock=clk, ttl_s=30)
    c.add_node(make_node("n1"))
    c.assume_pod(make_pod("p", cpu_milli=500), "n1")
    clk.advance(1000)
    assert c.cleanup_expired() == []
    assert c.is_assumed("default/p")


def test_forget_pod():
    c = SchedulerCache(clock=FakeClock())
    c.add_node(make_node("n1"))
    p = make_pod("p", cpu_milli=500)
    c.assume_pod(p, "n1")
    c.forget_pod(p.key())
    t, i = _table_row(c, "n1")
    assert t.requested[i, RES_CPU] == 0
    with pytest.raises(CacheError):
        c.forget_pod(p.key())


def test_double_assume_raises():
    c = SchedulerCache(clock=FakeClock())
    c.add_node(make_node("n1"))
    p = make_pod("p")
    c.assume_pod(p, "n1")
    with pytest.raises(CacheError):
        c.assume_pod(p, "n1")


def test_add_pod_corrects_wrong_assumption():
    c = SchedulerCache(clock=FakeClock())
    c.add_node(make_node("n1"))
    c.add_node(make_node("n2"))
    p = make_pod("p", cpu_milli=300)
    c.assume_pod(p, "n1")
    # API says it actually landed on n2
    c.add_pod(make_pod("p", cpu_milli=300, node_name="n2"))
    t = c.snapshot()
    order = c.node_order()
    assert t.requested[order.index("n1"), RES_CPU] == 0
    assert t.requested[order.index("n2"), RES_CPU] == 300


def _assert_tables_equal(a, b):
    for f in (
        "allocatable requested nonzero_req pair_mh taint_hard_mh port_any_mh "
        "matcher_counts anti_counts sym_counts aff_pod_count"
    ).split():
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f), err_msg=f)


def test_incremental_snapshot_matches_full_repack():
    """After arbitrary mutations, the dirty-row incremental snapshot must be
    identical to a from-scratch pack of the same state."""
    c = SchedulerCache(clock=FakeClock())
    for i in range(6):
        c.add_node(make_node(f"n{i}", zone=f"z{i % 2}"))
    c.snapshot()  # establish the cached table

    # mutations: pods land, one leaves, one node updates
    for i in range(8):
        c.add_pod(make_pod(f"p{i}", cpu_milli=100 * (i + 1), node_name=f"n{i % 3}",
                           labels={"app": f"a{i % 2}"}))
    c.remove_pod("default/p3")
    c.update_node(make_node("n4", cpu_milli=64000, zone="z0"))
    inc = c.snapshot()

    # fresh cache, same end state
    c2 = SchedulerCache(packer=SnapshotPacker(), clock=FakeClock())
    for i in range(6):
        if i == 4:
            c2.add_node(make_node("n4", cpu_milli=64000, zone="z0"))
        else:
            c2.add_node(make_node(f"n{i}", zone=f"z{i % 2}"))
    for i in range(8):
        if i == 3:
            continue
        c2.add_pod(make_pod(f"p{i}", cpu_milli=100 * (i + 1), node_name=f"n{i % 3}",
                            labels={"app": f"a{i % 2}"}))
    full = c2.snapshot()

    # row orders agree (same insertion order)
    assert c.node_order() == c2.node_order()
    _assert_tables_equal(inc, full)
    # the SelectorSpread counts each cache keeps per owner set agree too
    svc = LabelSelector(match_labels={"app": "a0"})
    for cc in (c, c2):
        cc.packer.intern_pod(make_pod("pending", labels={"app": "a0"},
                                      spread_selectors=(svc,)))
    assert c.owner_counts() == c2.owner_counts() != {}


def test_incremental_snapshot_after_universe_growth_falls_back():
    c = SchedulerCache(clock=FakeClock())
    c.add_node(make_node("n1"))
    c.snapshot()
    # a pod with a brand-new label selector universe entry forces widths to
    # change -> full repack path (must not crash or corrupt)
    c.add_pod(make_pod("p", node_name="n1", node_selector={"brand-new-key": "v"}))
    t = c.snapshot()
    assert t.n == 1


def test_node_remove_drops_row():
    c = SchedulerCache(clock=FakeClock())
    c.add_node(make_node("n1"))
    c.add_node(make_node("n2"))
    c.snapshot()
    c.remove_node("n1")
    t = c.snapshot()
    assert t.n == 1
    assert c.node_order() == ["n2"]
