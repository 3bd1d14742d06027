#!/usr/bin/env python
"""Sinkhorn-vs-argmax placement QUALITY evidence (VERDICT r3 item 2:
"demonstrate a workload where the OT plan beats argmax rounds on
placement quality ... or demote it").

Round 3 established that on margin-ORDERED workloads (one population
strictly outscores the other on the contended nodes) the round solver's
score-ordered per-node admission already reaches the OT outcome. The
residual gap is TOP-SCORE TIES with asymmetric second choices — steep
pods (hot=10, cold=0) tie with flat pods (hot=10, cold=9) on scarce hot
nodes, flat population listed first so ordering tie-breaks oppose the
steep pods. Per-pod argmax has no opportunity-cost term; the transport
plan prices hot-column contention and routes flat mass to the plentiful
near-equal cold columns.

The construction and the comparison are IMPORTED from
tests/test_sinkhorn.py (the pinned single source — this script only
scales it up), so the published evidence can never drift from the
regression test. Run with JAX_PLATFORMS=cpu for the CPU path.
"""
from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

if os.environ.get("JAX_PLATFORMS", "") == "cpu":
    import jax

    jax.config.update("jax_platforms", "cpu")


def main():
    import numpy as np

    from test_sinkhorn import (
        run_tied_preferences_comparison,
        tied_preferences_workload,
    )

    sizes = dict(n_hot=8, n_cold=56, n_steep=32, n_flat=224)
    results = run_tied_preferences_comparison(**sizes)

    # DEFAULT config (r5 auto-router, no flag): must match the plan
    from kubernetes_tpu.ops.arrays import (
        nodes_to_device,
        pods_to_device,
        selectors_to_device,
    )
    from kubernetes_tpu.ops.assign import batch_assign
    from kubernetes_tpu.snapshot import SnapshotPacker

    nodes, pods, points = tied_preferences_workload(**sizes)
    pk = SnapshotPacker()
    for p in pods:
        pk.intern_pod(p)
    nt = pk.pack_nodes(nodes, [])
    pt = pk.pack_pods(pods)
    a, _, _ = batch_assign(pods_to_device(pt),
                           nodes_to_device(nt),
                           selectors_to_device(pk.pack_selector_tables()),
                           per_node_cap=2)
    assigned = np.asarray(a)[:len(pods)]
    default_points = points(assigned)

    # solution scores via the ONE source of truth
    # (kubernetes_tpu/scenarios/quality.py — the scenario-pack PR moved
    # mean_score/balanced there; this script used to have no comparable
    # figure and bench.py carried a private copy of the arithmetic)
    from kubernetes_tpu.scenarios.quality import node_resources_score

    sel = assigned >= 0
    final_req = np.asarray(nt.requested).copy()
    np.add.at(final_req, assigned[sel], np.asarray(pt.req)[:len(pods)][sel])
    out = {
        "workload": sizes,
        "argmax_points": results[False],
        "sinkhorn_points": results[True],
        "default_config_points": default_points,
        "default_config_scores": node_resources_score(
            np.asarray(nt.allocatable), final_req, assigned),
        "auto_router_engaged": default_points == results[True],
        "verdict": ("sinkhorn_wins" if results[True] > results[False]
                    else ("identical" if results[True] == results[False]
                          else "argmax_wins")),
    }
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
