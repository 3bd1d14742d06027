"""The harness end to end at a tiny size on the CPU: found by name, data
driven, refuses a machine without a TPU, and its output check fails a
run whose timed path is broken underneath and a run of the control."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from conftest import BENCH, REPO, add_density, make_root

import run


def tiny(root, cell, seed=3, seconds=1.5, trace=False):
    spec = run.load_spec(root)
    return run.run_cell(spec, cell, seed, seconds, trace, require_tpu=False,
                        root=root, t_start=time.perf_counter())


def test_no_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        "sp5k-base.backlog", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_files_alone_exit_nonzero_with_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        "sp5k-base.backlog", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("cell", ["sp5k-base.backlog",
                                  "prefs.backlog",
                                  "sp5k-base.open"])
def test_tiny_cell_is_correct_and_reports_its_metrics(tiny_root, cell):
    out = tiny(tiny_root, cell, seed=2**31 + 5)
    assert out["correct"], out["checks"]
    spec = run.load_spec(tiny_root)
    want = {m["name"] for m in run.metrics_of(spec, "end_to_end", cell)}
    assert set(out["metrics"]) == want
    assert list(out)[-1] == "checks"
    assert out["checks"]["regret_samples"]["value"] > 0


def _files(root):
    out = {}
    for dp, _, fs in os.walk(os.path.join(root, "benchmark")):
        for f in fs:
            p = os.path.join(dp, f)
            out[p] = open(p, "rb").read()
    return out


def test_new_config_traffic_and_metric_need_no_edit(tmp_path):
    """A throwaway configuration, traffic mix and per-layer metric, added
    as new files and entries in a copy of BENCHMARK.json, are found by
    name: no existing file changes."""
    root = make_root(str(tmp_path))
    before = _files(root)
    b = os.path.join(root, "benchmark")
    cfg = json.load(open(os.path.join(b, "configs", "sp5k-base.json")))
    cfg["name"] = "throwaway"
    cfg["cluster"]["nodes"] = 40
    json.dump(cfg, open(os.path.join(b, "configs", "throwaway.json"), "w"))
    tr = json.load(open(os.path.join(b, "traffic", "backlog.json")))
    tr["name"] = "throwaway-mix"
    tr["pending"] = 192
    json.dump(tr, open(os.path.join(b, "traffic", "throwaway-mix.json"), "w"))
    with open(os.path.join(b, "metrics", "throwaway_cycles.py"), "w") as f:
        f.write("def read(view):\n    return float(len(view.cycles))\n")
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({"name": "throwaway", "source": "test",
                            "file": "benchmark/configs/throwaway.json",
                            "reduced": ["nodes"], "why": "test"})
    spec["workloads"].append({"name": "throwaway.mix", "config": "throwaway",
                              "traffic": "throwaway-mix", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "throwaway_cycles", "unit": "cycles",
                              "better": "higher", "source": "program_counter",
                              "layer": "queue and cycle driver",
                              "moves": "pods_per_s",
                              "workloads": ["throwaway.mix"]})
    for m in spec["end_to_end"]:
        if m["name"] == "pods_per_s":
            m["workloads"].append("throwaway.mix")
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    out = tiny(root, "throwaway.mix", trace=True)
    assert out["correct"], out["checks"]
    assert out["metrics"]["throwaway_cycles"]["value"] > 0
    for p, data in before.items():
        assert open(p, "rb").read() == data, f"{p} was edited"


@pytest.mark.parametrize("cell", ["density.backlog", "density.open"])
def test_density_config_needs_no_edit(tmp_path, cell):
    """A deployment shaped as the v1.16 density test (controller-owned
    existing pods at 30 per node, traffic pods of another shape, each its
    own controller's only replica) is a configuration file, mixes and
    entries alone, and its runs are correct."""
    root = make_root(str(tmp_path))
    before = _files(root)
    add_density(root)
    out = tiny(root, cell, seed=2**31 + 11)
    assert out["correct"], out["checks"]
    assert out["checks"]["regret_samples"]["value"] > 0
    for p, data in before.items():
        assert open(p, "rb").read() == data, f"{p} was edited"


# -- the timed path broken underneath: correct must come out false --------


def _patch_solve(monkeypatch, fault):
    import jax.numpy as jnp

    import kubernetes_tpu.ops.assign as A

    real = A.batch_assign

    def broken(dp, dn, *a, **kw):
        out = real(dp, dn, *a, **kw)
        assigned = out[0]
        n_pods = jnp.sum(dp.valid)
        idx = jnp.arange(assigned.shape[0])
        n_nodes = jnp.sum(dn.valid)
        if fault == "unchanged":
            assigned = jnp.full_like(assigned, -1)
        elif fault == "half":
            assigned = jnp.where(idx < n_pods // 2, assigned, -1)
        elif fault == "altered":
            assigned = jnp.where(assigned >= 0, (assigned + 1) % n_nodes,
                                 assigned)
        return (assigned,) + tuple(out[1:])

    monkeypatch.setattr(A, "batch_assign", broken)


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("cell", ["sp5k-base.backlog", "sp5k-base.open"])
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, fault, cell):
    _patch_solve(monkeypatch, fault)
    out = tiny(tiny_root, cell, seed=17)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell,fault", [
    ("density.backlog", "unchanged"), ("density.backlog", "half"),
    ("density.open", "unchanged"), ("density.open", "half"),
    ("density.open", "altered")])
@pytest.mark.parametrize("seed", [17, 19])
def test_broken_timed_path_is_not_correct_on_density(tiny_root, monkeypatch,
                                                     cell, fault, seed):
    """The same faults on the density shape. Each of its nodes holds what
    every other holds, so a backlog cycle spread over them and moved one
    node on is a spread as good (every sampled pod reads a regret of -4):
    no fault, and not listed. A few open-loop pods moved onto a node that
    holds one more traffic pod read 2 (seed 19) or 4 (seed 18)."""
    _patch_solve(monkeypatch, fault)
    out = tiny(tiny_root, cell, seed=seed)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell,control", [
    ("sp5k-base.backlog", "one_round"),
    ("prefs.backlog", "one_round"),
    ("sp5k-base.open", "one_round"),
    ("density.open", "stale_snapshot")])
def test_control_is_not_correct_and_the_program_is(tiny_root, cell, control):
    """The control (reference/control.py) at a size a test run holds: the
    reference placing each cycle in one round, or (where a node fits no
    more of a cycle's pods than one round may put there) by a snapshot one
    cycle old, fails the check on three seeds; the same runs' program
    placements pass. Not on ``density.backlog``: each cycle there replaces
    every traffic pod of the last on nodes that all hold the same, so
    neither control's placements are worse than the program's (both read
    -1 or -4); the density deployment is an open loop."""
    import driver
    from reference import control as ctl
    from reference.check import check

    spec = run.load_spec(tiny_root)
    _, config, traffic = run.resolve(spec, cell, tiny_root)
    traffic = dict(traffic, sample_cycles=1000)  # judge every cycle
    runner = {"closed_backlog": driver.run_backlog,
              "open_loop": driver.run_open}[traffic["kind"]]
    for seed in (1, 2, 3):
        log, sched, _ = runner(config, traffic, seed, 2.0)
        del sched
        assert check(log, config, traffic, seed)["correct"]
        res = check(log, config, traffic, seed,
                    control=getattr(ctl, control)())
        assert not res["correct"], res
        assert res["score_regret"][0] >= 1
