"""Differential tests: vectorized priority kernels vs the Go-faithful
oracle — analog of priorities' *_test.go table tests plus fuzzing."""

import random

import jax.numpy as jnp
import numpy as np

import pyref
from kubernetes_tpu.api.types import LabelSelector, Taint, Toleration
from kubernetes_tpu.ops.arrays import nodes_to_device, pods_to_device, selectors_to_device
from kubernetes_tpu.ops import priorities as prio
from kubernetes_tpu.ops.predicates import run_predicates
from kubernetes_tpu.snapshot import SnapshotPacker
from kubernetes_tpu.testing import make_node, make_pod, node_affinity_preferred, req
from test_predicates import random_cluster


def build(nodes, scheduled, pending):
    pk = SnapshotPacker()
    for p in list(scheduled) + list(pending):
        pk.intern_pod(p)
    nt = pk.pack_nodes(nodes, scheduled)
    pt = pk.pack_pods(pending)
    st = pk.pack_selector_tables()
    oc = pk.pack_owner_columns(nodes, scheduled, pt)
    dn = nodes_to_device(nt, owner_counts=oc)
    dp, ds = pods_to_device(pt), selectors_to_device(st)
    mask = run_predicates(dp, dn, ds).mask
    return dn, dp, ds, mask


def crop(a, pending, nodes):
    return np.asarray(a)[: len(pending), : len(nodes)]


def by_node(nodes, scheduled):
    d = {nd.name: [] for nd in nodes}
    for p in scheduled:
        if p.node_name in d:
            d[p.node_name].append(p)
    return d


def assert_matches(got, want, pending, nodes, mask, name):
    want = np.asarray(want, np.float64)
    ok = (np.abs(got - want) < 1e-6) | ~mask
    if not ok.all():
        i, j = np.argwhere(~ok)[0]
        raise AssertionError(
            f"{name}: pod {pending[i].name} node {nodes[j].name}: "
            f"device={got[i, j]} oracle={want[i, j]}\npod={pending[i]}\nnode={nodes[j]}"
        )


def test_resource_allocation_family_differential():
    for seed in range(6):
        rng = random.Random(100 + seed)
        nodes, scheduled, pending = random_cluster(rng, n_nodes=10, n_sched=25, n_pending=10)
        dn, dp, ds, mask = build(nodes, scheduled, pending)
        npods = by_node(nodes, scheduled)
        m = crop(mask, pending, nodes)
        for name, kernel, oracle in [
            ("least", prio.least_requested, pyref.least_requested_score),
            ("most", prio.most_requested, pyref.most_requested_score),
            ("balanced", prio.balanced_allocation, pyref.balanced_allocation_score),
        ]:
            got = crop(kernel(dp, dn, ds, None, mask), pending, nodes)
            want = [
                [oracle(p, nd, npods[nd.name]) for nd in nodes] for p in pending
            ]
            assert_matches(got, want, pending, nodes, m, name)


def test_taint_toleration_differential():
    for seed in range(6):
        rng = random.Random(200 + seed)
        nodes, scheduled, pending = random_cluster(rng, n_nodes=10, n_sched=5, n_pending=10)
        dn, dp, ds, mask = build(nodes, scheduled, pending)
        m = crop(mask, pending, nodes)
        got = crop(prio.taint_toleration(dp, dn, ds, None, mask), pending, nodes)
        want = pyref.taint_toleration_scores(pending, nodes, m)
        assert_matches(got, want, pending, nodes, m, "taint_toleration")


def test_node_affinity_preferred_differential():
    rng = random.Random(7)
    nodes = [
        make_node(f"n{i}", labels={"disk": rng.choice(["ssd", "hdd"]), "tier": rng.choice(["a", "b"])})
        for i in range(8)
    ]
    pending = []
    for i in range(8):
        aff = node_affinity_preferred(
            (rng.choice([1, 5, 50]), [req("disk", "In", "ssd")]),
            (rng.choice([1, 10]), [req("tier", "In", rng.choice(["a", "b"]))]),
        )
        pending.append(make_pod(f"p{i}", affinity=aff))
    pending.append(make_pod("noaff"))
    dn, dp, ds, mask = build(nodes, [], pending)
    m = crop(mask, pending, nodes)
    got = crop(prio.node_affinity(dp, dn, ds, None, mask), pending, nodes)
    want = pyref.node_affinity_scores(pending, nodes, m)
    assert_matches(got, want, pending, nodes, m, "node_affinity")


def test_selector_spread_differential():
    for seed in range(5):
        rng = random.Random(300 + seed)
        svc = LabelSelector(match_labels={"app": "web"})
        nodes = [
            make_node(f"n{i}", zone=rng.choice(["z0", "z1", None]))
            for i in range(9)
        ]
        scheduled = [
            make_pod(
                f"s{i}",
                node_name=f"n{rng.randrange(9)}",
                labels={"app": rng.choice(["web", "db"])},
            )
            for i in range(15)
        ]
        pending = [
            make_pod(f"p{i}", labels={"app": "web"}, spread_selectors=(svc,))
            for i in range(4)
        ] + [make_pod("plain")]
        dn, dp, ds, mask = build(nodes, scheduled, pending)
        m = crop(mask, pending, nodes)
        got = crop(prio.selector_spread(dp, dn, ds, None, mask), pending, nodes)
        want = pyref.selector_spread_scores(pending, nodes, by_node(nodes, scheduled), m)
        assert_matches(got, want, pending, nodes, m, "selector_spread")


def test_image_locality_differential():
    rng = random.Random(9)
    imgs = {f"img{k}": rng.choice([10, 50, 300, 900]) * 1024 * 1024 for k in range(6)}
    nodes = [
        make_node(f"n{i}", images={k: v for k, v in imgs.items() if rng.random() < 0.5})
        for i in range(8)
    ]
    pending = [
        make_pod(f"p{i}", images=tuple(rng.sample(sorted(imgs), k=rng.choice([1, 2, 3]))))
        for i in range(6)
    ]
    dn, dp, ds, mask = build(nodes, [], pending)
    m = crop(mask, pending, nodes)
    got = crop(prio.image_locality(dp, dn, ds, None, mask), pending, nodes)
    want = pyref.image_locality_scores(pending, nodes)
    assert_matches(got, want, pending, nodes, m, "image_locality")


def test_node_prefer_avoid_differential():
    nodes = [
        make_node("a", prefer_avoid_owner_uids=("rc-1",)),
        make_node("b"),
    ]
    pending = [
        make_pod("p1", owner_uid="rc-1"),
        make_pod("p2", owner_uid="rc-2"),
        make_pod("p3"),
    ]
    dn, dp, ds, mask = build(nodes, [], pending)
    m = crop(mask, pending, nodes)
    got = crop(prio.node_prefer_avoid(dp, dn, ds, None, mask), pending, nodes)
    want = pyref.prefer_avoid_scores(pending, nodes)
    assert_matches(got, want, pending, nodes, m, "prefer_avoid")


def test_weighted_sum_runs():
    rng = random.Random(11)
    nodes, scheduled, pending = random_cluster(rng, n_nodes=6, n_sched=8, n_pending=5)
    dn, dp, ds, mask = build(nodes, scheduled, pending)
    total = prio.run_priorities(dp, dn, ds, mask)
    assert total.shape == mask.shape
    assert np.isfinite(np.asarray(total)).all()


def test_requested_to_capacity_ratio_differential():
    shapes = [
        ((0, 10), (100, 0)),  # default: prefer least utilized
        ((0, 0), (100, 10)),  # bin-packing
        ((0, 0), (50, 10), (100, 5)),  # peak at 50%
    ]
    for seed in range(4):
        rng = random.Random(500 + seed)
        nodes, scheduled, pending = random_cluster(rng, n_nodes=10, n_sched=25, n_pending=10)
        dn, dp, ds, mask = build(nodes, scheduled, pending)
        npods = by_node(nodes, scheduled)
        m = crop(mask, pending, nodes)
        for shape in shapes:
            kernel = prio.make_requested_to_capacity_ratio(shape)
            got = crop(kernel(dp, dn, ds, None, mask), pending, nodes)
            want = [
                [
                    pyref.requested_to_capacity_score(p, nd, npods[nd.name], shape)
                    for nd in nodes
                ]
                for p in pending
            ]
            assert_matches(got, want, pending, nodes, m, f"RTCR{shape}")


def test_node_label_priority():
    nodes = [
        make_node("n0", labels={"disktype": "ssd"}),
        make_node("n1"),
    ]
    pending = [make_pod("p0")]
    pk = SnapshotPacker()
    key_id = pk.u.label_keys.intern("disktype")
    for p in pending:
        pk.intern_pod(p)
    dn = nodes_to_device(pk.pack_nodes(nodes, []))
    dp = pods_to_device(pk.pack_pods(pending))
    ds = selectors_to_device(pk.pack_selector_tables())
    mask = run_predicates(dp, dn, ds).mask
    got_p = crop(prio.make_node_label(key_id, True)(dp, dn, ds, None, mask), pending, nodes)
    got_a = crop(prio.make_node_label(key_id, False)(dp, dn, ds, None, mask), pending, nodes)
    for j, nd in enumerate(nodes):
        assert got_p[0, j] == pyref.node_label_score(nd, "disktype", True)
        assert got_a[0, j] == pyref.node_label_score(nd, "disktype", False)


def test_resource_limits_priority_differential():
    from kubernetes_tpu.api.types import Resources

    nodes = [
        make_node("n-big", cpu_milli=32000, memory=64 * 2**30),
        make_node("n-small", cpu_milli=500, memory=2**28),
    ]
    pending = [
        make_pod("p-none"),  # no limits -> 0 everywhere
        make_pod("p-cpu", limits=Resources(cpu_milli=1000)),
        make_pod("p-both", limits=Resources(cpu_milli=100, memory=2**30)),
        make_pod("p-huge", limits=Resources(cpu_milli=64000, memory=2**40)),
    ]
    pk = SnapshotPacker()
    for p in pending:
        pk.intern_pod(p)
    dn = nodes_to_device(pk.pack_nodes(nodes, []))
    dp = pods_to_device(pk.pack_pods(pending))
    ds = selectors_to_device(pk.pack_selector_tables())
    mask = run_predicates(dp, dn, ds).mask
    got = crop(prio.resource_limits(dp, dn, ds, None, mask), pending, nodes)
    for i, p in enumerate(pending):
        for j, nd in enumerate(nodes):
            assert got[i, j] == pyref.resource_limits_score(p, nd), (p.name, nd.name)


def test_register_custom_priority_in_weighted_sum():
    nodes = [make_node("n0", labels={"gpu": "true"}), make_node("n1")]
    pending = [make_pod("p0")]
    pk = SnapshotPacker()
    key_id = pk.u.label_keys.intern("gpu")
    for p in pending:
        pk.intern_pod(p)
    dn = nodes_to_device(pk.pack_nodes(nodes, []))
    dp = pods_to_device(pk.pack_pods(pending))
    ds = selectors_to_device(pk.pack_selector_tables())
    mask = run_predicates(dp, dn, ds).mask
    prio.register_priority("NodeLabelPriority/gpu", prio.make_node_label(key_id, True))
    try:
        total = crop(
            prio.run_priorities(dp, dn, ds, mask, {"NodeLabelPriority/gpu": 2.0}),
            pending, nodes,
        )
        assert total[0, 0] == 20.0 and total[0, 1] == 0.0
    finally:
        del prio.PRIORITY_REGISTRY["NodeLabelPriority/gpu"]


def test_empty_feature_gate_is_exact():
    """empty_priorities + EMPTY_CONSTANTS (the host-side feature gate the
    solvers thread through as a static jit key) must be EXACT: on a
    snapshot without the gated features, the gated weighted sum equals
    the full computation bit-for-bit over the whole matrix."""
    import numpy as np

    from kubernetes_tpu.ops.priorities import (
        EMPTY_CONSTANTS,
        empty_priorities,
        run_priorities,
    )
    from kubernetes_tpu.snapshot import SnapshotPacker
    from kubernetes_tpu.models.cluster import make_nodes, make_pods

    nodes = make_nodes(64, zones=4)
    existing = make_pods(32, "old", assigned_round_robin_over=64)
    pending = make_pods(48)
    pk = SnapshotPacker()
    for p in existing + pending:
        pk.intern_pod(p)
    nt = pk.pack_nodes(nodes, existing)
    pt = pk.pack_pods(pending)
    gate = empty_priorities(nt, pt)
    # the base workload has none of the gated features
    assert set(EMPTY_CONSTANTS) == set(gate)
    dn, dp, ds = (nodes_to_device(nt), pods_to_device(pt),
                  selectors_to_device(pk.pack_selector_tables()))
    mask = run_predicates(dp, dn, ds).mask
    full = np.asarray(run_priorities(dp, dn, ds, mask))
    gated = np.asarray(run_priorities(dp, dn, ds, mask, skip=gate))
    assert (full == gated).all()


def test_empty_feature_gate_respects_present_features():
    """Each feature's presence must disarm exactly its gate."""
    from kubernetes_tpu.api.types import Taint
    from kubernetes_tpu.ops.priorities import empty_priorities
    from kubernetes_tpu.snapshot import SnapshotPacker
    from kubernetes_tpu.testing import node_affinity_preferred

    def gate_for(nodes, pending):
        pk = SnapshotPacker()
        for p in pending:
            pk.intern_pod(p)
        return empty_priorities(pk.pack_nodes(nodes, []),
                                pk.pack_pods(pending))

    base_nodes = [make_node("n0")]
    # preferred node affinity present
    p = make_pod("a", affinity=node_affinity_preferred(
        (3, [req("disk", "In", "ssd")])))
    assert "NodeAffinityPriority" not in gate_for(base_nodes, [p])
    # soft taints present
    soft = [make_node("n0", taints=[Taint("flaky", "", "PreferNoSchedule")])]
    assert "TaintTolerationPriority" not in gate_for(soft, [make_pod("b")])
    # pod images present
    assert "ImageLocalityPriority" not in gate_for(
        base_nodes, [make_pod("c", images=("app:v1",))])
    # spread owners present
    svc = LabelSelector(match_labels={"app": "web"})
    assert "SelectorSpreadPriority" not in gate_for(
        base_nodes, [make_pod("d", labels={"app": "web"},
                              spread_selectors=(svc,))])
    # avoid annotation + owner uid present
    avoid = make_node("n0")
    avoid.prefer_avoid_owner_uids = ("rc-1",)
    assert "NodePreferAvoidPodsPriority" not in gate_for(
        [avoid], [make_pod("e", owner_uid="rc-1")])
    # limits present
    from kubernetes_tpu.api.types import Resources

    assert "ResourceLimitsPriority" not in gate_for(
        base_nodes, [make_pod("f", limits=Resources(cpu_milli=500))])


def test_gate_never_folds_custom_kernels():
    """Regression (r3 review): register_priority may rebind a gated stock
    name; the gate must then call the custom kernel, never its stock
    constant."""
    import numpy as np

    from kubernetes_tpu.ops import priorities as P
    from kubernetes_tpu.snapshot import SnapshotPacker
    from kubernetes_tpu.models.cluster import make_nodes, make_pods

    nodes, pending = make_nodes(8, zones=2), make_pods(6)
    pk = SnapshotPacker()
    for p in pending:
        pk.intern_pod(p)
    nt, pt = pk.pack_nodes(nodes, []), pk.pack_pods(pending)
    gate = P.empty_priorities(nt, pt)
    assert "ImageLocalityPriority" in gate
    dn, dp, ds = (nodes_to_device(nt), pods_to_device(pt),
                  selectors_to_device(pk.pack_selector_tables()))
    mask = run_predicates(dp, dn, ds).mask
    stock = P.PRIORITY_REGISTRY["ImageLocalityPriority"]
    try:
        P.register_priority(
            "ImageLocalityPriority",
            lambda pods, nodes, sel, topo, m: jnp.full(
                (pods.req.shape[0], nodes.allocatable.shape[0]), 7.0),
        )
        got = np.asarray(P.run_priorities(
            dp, dn, ds, mask, {"ImageLocalityPriority": 1.0}, skip=gate))
        assert (got == 7.0).all()  # custom kernel ran; constant 0 did not
    finally:
        P.register_priority("ImageLocalityPriority", stock)


def test_hoisted_priorities_bit_identical():
    """hoist_priorities + run_priorities(hoisted=) must reproduce the
    unhoisted total BIT-FOR-BIT (same accumulation order, same per-kernel
    arithmetic) across workloads exercising every hoisted kernel, both
    mask shapes, and the gate interplay."""
    import numpy as np

    from kubernetes_tpu.ops.priorities import (
        empty_priorities,
        hoist_priorities,
        run_priorities,
    )
    from bench import build_variant

    for variant in ("base", "node_affinity", "selector_spread"):
        w = build_variant(variant, 60, 30, 128)
        dp, dv = w.device_batch(w.pending[:128], 128)
        fr = run_predicates(dp, w.dn, w.ds, topo=w.dt, vol=dv)
        for mask in (fr.mask,
                     fr.mask & (np.arange(fr.mask.shape[1]) % 2 == 0)[None, :]):
            for skip in ((), empty_priorities(
                    w.pk.pack_nodes(w.nodes, w.existing),
                    w.pk.pack_pods(w.pending))):
                plain = run_priorities(dp, w.dn, w.ds, mask, topo=w.dt,
                                       skip=skip)
                hp = hoist_priorities(dp, w.dn, w.ds, skip=skip)
                hoisted = run_priorities(dp, w.dn, w.ds, mask, topo=w.dt,
                                         skip=skip, hoisted=hp)
                assert (np.asarray(plain) == np.asarray(hoisted)).all(), (
                    variant, skip)


def test_hoist_skips_custom_kernels():
    """A custom kernel registered over a stock name must never be
    hoisted (its static-ness is unknown) — mirror of the gate's
    _STOCK_KERNELS identity check."""
    from kubernetes_tpu.ops.priorities import (
        PRIORITY_REGISTRY,
        hoist_priorities,
        register_priority,
    )
    from bench import build_variant

    w = build_variant("base", 20, 10, 32)
    dp, _ = w.device_batch(w.pending[:32], 32)
    stock = PRIORITY_REGISTRY["ImageLocalityPriority"]
    try:
        register_priority("ImageLocalityPriority",
                          lambda p, n, s, t, m: stock(p, n, s, t, m))
        hp = hoist_priorities(dp, w.dn, w.ds)
        assert "ImageLocalityPriority" not in hp
        assert "TaintTolerationPriority" in hp  # others still hoist
    finally:
        register_priority("ImageLocalityPriority", stock)


def test_fused_pair_normalize_bit_identical(monkeypatch):
    """The fused NA+TT normalize must be bit-identical to the two
    separate _normalize_reduce calls on every path: the jnp fallback
    expression, AND the Pallas kernel pair (exercised in interpret mode
    on CPU via KTPU_PALLAS=1 — the same kernels the TPU path compiles);
    at the solver level, placements must be identical with the fusion
    engaged vs disabled."""
    import numpy as np

    from kubernetes_tpu.ops.assign import batch_assign
    from kubernetes_tpu.ops.priorities import (
        _fused_pair_normalize,
        _normalize_reduce,
        empty_priorities,
        hoist_priorities,
        run_priorities,
    )
    from bench import build_variant

    w = build_variant("node_affinity", 60, 30, 128)
    dp, dv = w.device_batch(w.pending[:128], 128)
    fr = run_predicates(dp, w.dn, w.ds, topo=w.dt, vol=dv)
    hp = hoist_priorities(dp, w.dn, w.ds)
    raw_na = hp["NodeAffinityPriority"][1]
    raw_tt = hp["TaintTolerationPriority"][1]
    want = (1.0 * np.asarray(_normalize_reduce(raw_na, fr.mask, False))
            + 1.0 * np.asarray(_normalize_reduce(raw_tt, fr.mask, True)))

    # jnp fallback expression
    monkeypatch.setenv("KTPU_PALLAS", "0")
    got_jnp = np.asarray(_fused_pair_normalize(raw_na, raw_tt, fr.mask,
                                               1.0, 1.0))
    assert (got_jnp == want).all()

    # Pallas kernel pair, interpret mode (the TPU kernels' semantics)
    monkeypatch.setenv("KTPU_PALLAS", "1")
    got_pl = np.asarray(_fused_pair_normalize(raw_na, raw_tt, fr.mask,
                                              1.0, 1.0))
    assert (got_pl == want).all()

    # run_priorities totals with the fusion engaged vs standard path
    for skip in ((), empty_priorities(
            w.pk.pack_nodes(w.nodes, w.existing),
            w.pk.pack_pods(w.pending))):
        fused = run_priorities(dp, w.dn, w.ds, fr.mask, topo=w.dt,
                               skip=skip, hoisted=hp, fused=True)
        monkeypatch.setenv("KTPU_PALLAS", "0")
        plain = run_priorities(dp, w.dn, w.ds, fr.mask, topo=w.dt,
                               skip=skip, hoisted=hp)
        assert (np.asarray(plain) == np.asarray(fused)).all(), skip
        monkeypatch.setenv("KTPU_PALLAS", "1")

    # solver level: fusion engaged (interpret pallas) vs disabled
    a_f, u_f, r_f = batch_assign(dp, w.dn, w.ds, topo=w.dt, vol=dv,
                                 per_node_cap=4, fused_score=True)
    monkeypatch.setenv("KTPU_PALLAS", "0")
    a_u, u_u, r_u = batch_assign(dp, w.dn, w.ds, topo=w.dt, vol=dv,
                                 per_node_cap=4, fused_score=False)
    assert (np.asarray(a_f) == np.asarray(a_u)).all()
    assert (np.asarray(u_f.requested) == np.asarray(u_u.requested)).all()
    assert int(r_f) == int(r_u)


def test_fused_pair_disengages_for_custom_kernels_and_float_weights():
    """Fusion must fall back to the standard path whenever the
    exactness proof doesn't hold: any custom-registered kernel among the
    active weights, or a non-integer weight."""
    import numpy as np

    from kubernetes_tpu.ops.priorities import (
        DEFAULT_WEIGHTS,
        PRIORITY_REGISTRY,
        _fusable,
        hoist_priorities,
        register_priority,
        run_priorities,
    )
    from bench import build_variant

    assert _fusable(DEFAULT_WEIGHTS, ())
    assert not _fusable({**DEFAULT_WEIGHTS, "LeastRequestedPriority": 1.5}, ())

    w = build_variant("node_affinity", 20, 10, 32)
    dp, _ = w.device_batch(w.pending[:32], 32)
    fr = run_predicates(dp, w.dn, w.ds, topo=w.dt)
    stock = PRIORITY_REGISTRY["LeastRequestedPriority"]
    try:
        register_priority("LeastRequestedPriority",
                          lambda p, n, s, t, m: stock(p, n, s, t, m) + 0.25)
        assert not _fusable(DEFAULT_WEIGHTS, ())
        hp = hoist_priorities(dp, w.dn, w.ds)
        plain = run_priorities(dp, w.dn, w.ds, fr.mask, topo=w.dt, hoisted=hp)
        fused = run_priorities(dp, w.dn, w.ds, fr.mask, topo=w.dt, hoisted=hp,
                               fused=True)
        # fused flag on, but fusion disengaged -> same graph, same result
        assert (np.asarray(plain) == np.asarray(fused)).all()
    finally:
        register_priority("LeastRequestedPriority", stock)
