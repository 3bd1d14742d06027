"""The plain reference: v1.16 arithmetic, bounds against the one-state
scorer, and the output check's regret on placements known to be right
or wrong."""

import numpy as np
import pytest

from reference import scores as S


def _nodes(n=6, zones=2):
    return S.Nodes(cap_cpu=np.full(n, 4000.0), cap_mem=np.full(n, 10000.0),
                   cap_pods=np.full(n, 110.0), zone=np.arange(n) % zones,
                   prefer_taints=(np.arange(n) % 3 == 0).astype(np.int64))


def test_v116_least_requested_and_balanced():
    # least_requested.go: ((4000-1000)*10)/4000 = 7, ((10000-5000)*10)/10000 = 5
    assert S._least_requested(np.array([1000.0]), np.array([4000.0]))[0] == 7
    assert S._least_requested(np.array([5000.0]), np.array([10000.0]))[0] == 5
    assert S._least_requested(np.array([5000.0]), np.array([4000.0]))[0] == 0
    # balanced: cpu 0.25, mem 0.5 -> int((1 - 0.25) * 10) = 7
    f = S._balanced_lo(np.array([0.25]), np.array([0.25]),
                       np.array([0.5]), np.array([0.5]))
    assert f[0] == 7


def test_v116_spread_and_normalize():
    # counts [0, 1, 2], no zones: 10 * (2 - c) / 2
    got = S._spread(np.array([0, 1, 2]), 2, np.zeros(3), 0, False)
    assert got.tolist() == [10, 5, 0]
    assert S._normalize(np.array([0, 10]), 10, False).tolist() == [0, 10]
    assert S._normalize(np.array([0, 1]), 1, True).tolist() == [10, 0]
    assert S._normalize(np.array([0, 0]), 0, True).tolist() == [10, 10]


@pytest.mark.parametrize("service,zone_pref", [(-1, -1), (1, 0)])
def test_bounds_at_one_state_equal_the_exact_scorer(service, zone_pref):
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    nodes = _nodes()
    n = len(nodes.cap_cpu)
    k = rng.integers(0, 30, n).astype(float)
    counts = rng.integers(0, 4, (3, n))
    st = S.State(k * 100, k * 200, k,
                 {o: {i: int(c) for i, c in enumerate(row) if c}
                  for o, row in enumerate(counts)})
    spec = S.PodSpec(100.0, 200.0, service, zone_pref, 10)
    w = {"LeastRequestedPriority": 1, "BalancedResourceAllocation": 1,
         "NodeAffinityPriority": 1, "TaintTolerationPriority": 1,
         "SelectorSpreadPriority": 1}
    lo, hi, _, _ = S.bounds(spec, nodes, st, st, w, 2)
    ex = np.asarray(S.exact(spec, nodes, st, w, 2, jnp.float32))
    assert (lo == hi).all()
    assert (lo == ex).all()


def test_bounds_contain_every_state_between():
    rng = np.random.default_rng(1)
    nodes = _nodes()
    n = len(nodes.cap_cpu)
    k0 = rng.integers(0, 10, n).astype(float)
    k1 = k0 + rng.integers(0, 5, n)
    lo_st = S.State(k0 * 100, k0 * 200, k0)
    hi_st = S.State(k1 * 100, k1 * 200, k1)
    w = {"LeastRequestedPriority": 1, "BalancedResourceAllocation": 1}
    spec = S.PodSpec(100.0, 200.0, -1, -1, 0)
    lo, hi, _, _ = S.bounds(spec, nodes, lo_st, hi_st, w, 2)
    for _ in range(50):
        k = k0 + (rng.random(n) * (k1 - k0 + 1)).astype(int).clip(0, k1 - k0)
        st = S.State(k * 100, k * 200, k)
        s, _, _, _ = S.bounds(spec, nodes, st, st, w, 2)
        assert (lo <= s).all() and (s <= hi).all()


def test_selector_spread_counts_existing_controllers_in_zones():
    """selector_spreading.go (v1.16) CalculateSpreadPriorityReduce worked
    by hand. The pod's controller has 3, 1, 2, 0, 0 pods on five nodes of
    zones a, a, b, b, b (another controller's pods are not counted): the
    most on a node is 3, zone a holds 4 and b 2, and the score is
    int(10 (3 - c) / 3 * 1/3 + 10 (4 - zone's) / 4 * 2/3):
    int(0 + 0), int(2.22 + 0), int(1.11 + 3.33), int(3.33 + 3.33) twice."""
    import jax.numpy as jnp

    nodes = S.Nodes(cap_cpu=np.full(5, 4000.0), cap_mem=np.full(5, 1e4),
                    cap_pods=np.full(5, 110.0),
                    zone=np.array([0, 0, 1, 1, 1]),
                    prefer_taints=np.zeros(5, np.int64))
    n = np.array([3.0, 1, 2, 0, 0])
    st = S.State(n * 40, n * 100, n + 1,
                 {0: {0: 3, 1: 1, 2: 2}, 1: {3: 5, 4: 1}})
    spec = S.PodSpec(800.0, 2000.0, 0, -1, 0)
    w = {"SelectorSpreadPriority": 1}
    lo, hi, _, _ = S.bounds(spec, nodes, st, st, w, 2)
    assert lo.tolist() == hi.tolist() == [0, 2, 4, 6, 6]
    ex = np.asarray(S.exact(spec, nodes, st, w, 2, jnp.float32))
    assert ex.tolist() == [0, 2, 4, 6, 6]
    # one more of its pods lands on node 3 during the cycle: every state
    # between reads inside the bounds; at the end zone b holds 3, so
    # int(2.22 + 0), int(1.11 + 1.67), int(2.22 + 1.67), int(3.33 + 1.67)
    end = st.copy()
    end.add(3, 800.0, 2000.0, 0)
    lo, hi, _, _ = S.bounds(spec, nodes, st, end, w, 2)
    for s in (st, end):
        one, _, _, _ = S.bounds(spec, nodes, s, s, w, 2)
        assert (lo <= one).all() and (one <= hi).all()
    assert S.bounds(spec, nodes, end, end, w, 2)[0].tolist() == [0, 2, 2, 3, 5]


def _regret_case(node1_usage):
    """Two nodes of 10,000 cpu and memory, node 0 empty; in one cycle it
    took two small pods (1,000) and one big (5,000), at most ``cap`` = 2
    per round. Returns the replay stand-in, the cycle's start and end
    states."""
    from types import SimpleNamespace

    nodes = S.Nodes(cap_cpu=np.full(2, 1e4), cap_mem=np.full(2, 1e4),
                    cap_pods=np.full(2, 110.0), zone=np.full(2, -1),
                    prefer_taints=np.zeros(2, np.int64))
    specs = {0: S.PodSpec(1000.0, 1000.0, -1, -1, 0),
             1: S.PodSpec(1000.0, 1000.0, -1, -1, 0),
             2: S.PodSpec(5000.0, 5000.0, -1, -1, 0)}
    rp = SimpleNamespace(spec=specs.__getitem__, nodes=nodes,
                         weights={"LeastRequestedPriority": 1}, n_zones=1)
    u = np.array([0.0, node1_usage])
    start = S.State(u.copy(), u.copy(), (u > 0).astype(float))
    end = start.copy()
    for s in specs.values():
        end.add(0, s.cpu, s.mem, -1)
    return rp, start, end


@pytest.mark.parametrize("node1_usage,sound", [(0.0, False), (6000.0, True)])
def test_node_regret_floor_with_pods_of_two_shapes(node1_usage, sound):
    """With node 1 empty, the batch cannot have put all three on node 0:
    whichever came last saw at least one other there (least requested
    4 for the big pod, 8 for a small one) against an empty node 1 (5 and
    9). The floor of the first benchmark, the node's end usage less
    ``cap`` times the pod's own request, put the big pod's at the empty
    node and read 0: a pass. With node 1 at 6,000 the big pod fits only
    node 0, and rounds of the small pods first, then the big one, place
    all three there: sound."""
    from reference.check import node_regret

    rp, start, end = _regret_case(node1_usage)
    r = node_regret(rp, start, end, 0, [0, 1, 2], cap=2)
    assert (r <= 0) == sound, r
    if not sound:
        assert r == 1
        # the same-request floor for the big pod, as it was
        big = rp.spec(2)
        old = start.copy()
        old.cpu[0] = max(end.cpu[0] - 2 * big.cpu, start.cpu[0])
        old.mem[0] = max(end.mem[0] - 2 * big.mem, start.mem[0])
        hi = end.copy()
        hi.add(0, big.cpu, big.mem, -1, -1)
        lo_oth = S.bounds(big, rp.nodes, start, end, rp.weights, 1)[0]
        hi_n = S.bounds(big, rp.nodes, old, hi, rp.weights, 1)[1]
        assert lo_oth[1] - hi_n[0] <= 0
