"""Tiny cells on the CPU: a temporary checkout root holding a copy of
``benchmark/`` and a ``BENCHMARK.json`` whose configurations and traffic
mixes are cut to a size a test run holds. The program itself is imported
from the repository."""

from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY_CLUSTER = {"nodes": 48, "zones": 4, "existing_pods": 24}
TINY_PREFS = {"taint_every": 7, "services": 4, "zone_affinity_weight": 10}
TINY_SCHED = {"max_batch": 256, "pipeline_chunk": 128,
              "serving": {"min_wait_s": 0.005, "max_wait_s": 0.05,
                          "target_bucket": 64}}
TINY_TRAFFIC = {
    "backlog": {"pending": 512, "max_bound": 300, "prefill_bound": 300,
                "warm_pod_buckets": [128, 256], "trace_offset_s": 0.5,
                "trace_seconds": 1.0, "sample_cycles": 3,
                "sample_pods_per_cycle": 64},
    "open": {"rate_pods_s": 200, "max_bound": 300, "prefill_bound": 300,
             "warm_pod_buckets": [8, 16, 32, 64], "drain_s": 20,
             "warm_s": 0.5, "warm_bursts": [16, 32], "trace_seconds": 1.0,
             "sample_cycles": 4, "sample_pods_per_cycle": 64},
}


#: a deployment shaped as the v1.16 density test (test/e2e/scalability/
#: density.go): existing pods at 30 per node and 1/100 of a node, owned by
#: ReplicationControllers, and traffic pods at 1/5 of a node, each the
#: only replica of its own controller. 48 such nodes hold 3 traffic pods
#: each, 144 in all; the mixes keep 48 bound, one per node as the test's
#: 5,000 latency pods on 5,000 nodes, and the backlog 64 pending (its
#: warmup samples 64 of them).
DENSITY_CLUSTER = {
    "nodes": 48, "zones": 4, "existing_pods": 1440,
    "existing_pod": {"cpu_milli": 40, "memory_bytes": 343597383},
    "existing_controllers": {"count": 3, "name": "density1440",
                             "labels": {"type": "densityPod"}},
    "pod": {"cpu_milli": 800, "memory_bytes": 6871947673},
    "pod_controller": "own",
}
DENSITY_TRAFFIC = {
    "backlog": {"pending": 64, "max_bound": 48, "prefill_bound": 48,
                "warm_pod_buckets": [64, 128]},
    "open": {"rate_pods_s": 60, "max_bound": 48, "prefill_bound": 48,
             "warm_bursts": [8, 16]},
}


def add_density(root: str) -> None:
    """A density-shaped configuration and a mix of each kind, added to a
    checkout root as new files and entries alone: cells
    ``density.backlog`` and ``density.open``."""
    b = os.path.join(root, "benchmark")
    with open(os.path.join(b, "configs", "sp5k-base.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "density"
    cfg["cluster"].update(DENSITY_CLUSTER)
    with open(os.path.join(b, "configs", "density.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "density", "source": "test",
                            "file": "benchmark/configs/density.json",
                            "reduced": ["nodes", "existing_pods"],
                            "why": "test"})
    for kind, over in DENSITY_TRAFFIC.items():
        with open(os.path.join(b, "traffic", f"{kind}.json")) as f:
            t = json.load(f)
        t.update(over, name=f"density-{kind}")
        with open(os.path.join(b, "traffic", f"density-{kind}.json"),
                  "w") as f:
            json.dump(t, f)
        cell = f"density.{kind}"
        spec["workloads"].append({"name": cell, "config": "density",
                                  "traffic": f"density-{kind}", "chips": 1,
                                  "why": "test"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if f"sp5k-base.{kind}" in m.get("workloads", ()):
                m["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)


def make_root(dst: str) -> str:
    """A checkout root with tiny configurations and mixes."""
    shutil.copytree(BENCH, os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        path = os.path.join(dst, c["file"])
        with open(path) as f:
            cfg = json.load(f)
        cfg["cluster"].update(TINY_CLUSTER)
        cfg["scheduler"].update(TINY_SCHED)
        with open(path, "w") as f:
            json.dump(cfg, f)
    # every preference the generator and the reference know, live: the
    # shape a sourced preference cell would take (no such cell yet,
    # PERF.md open questions)
    with open(os.path.join(dst, "benchmark", "configs", "sp5k-base.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "prefs"
    cfg["cluster"].update(TINY_PREFS)
    with open(os.path.join(dst, "benchmark", "configs", "prefs.json"), "w") as f:
        json.dump(cfg, f)
    spec["configs"].append({"name": "prefs", "source": "test",
                            "file": "benchmark/configs/prefs.json",
                            "reduced": ["nodes"], "why": "test"})
    spec["workloads"].append({"name": "prefs.backlog", "config": "prefs",
                              "traffic": "backlog", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "sp5k-base.backlog" in m.get("workloads", ()):
            m["workloads"].append("prefs.backlog")
    for name, over in TINY_TRAFFIC.items():
        path = os.path.join(dst, "benchmark", "traffic", f"{name}.json")
        with open(path) as f:
            t = json.load(f)
        t.update(over)
        with open(path, "w") as f:
            json.dump(t, f)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return dst


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    root = make_root(str(tmp_path_factory.mktemp("bench_root")))
    add_density(root)
    return root
