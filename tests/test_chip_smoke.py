"""chip_smoke.py's phases at a tiny size on the CPU (the Pallas kernels
interpreted, the mesh on virtual devices), and its refusal to pass
anywhere but a TPU. The full size runs only on the chip."""

import json
import os
import subprocess
import sys

import pytest

from kubernetes_tpu.config import WarmupConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_main_refuses_the_cpu(capsys):
    assert chip_smoke.main([]) == 1
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "no TPU" in last["error"]


def test_headline_phase_tiny():
    out = chip_smoke.phase_headline(
        n_nodes=64, n_existing=16, n_pending=1000, zones=4,
        cfg_overrides={"max_batch": 512, "pipeline_chunk": 128,
                       "warmup": WarmupConfig(enabled=True, min_bucket=64)})
    assert out["bound"] == out["pending"] == 1000
    assert out["retraces_after_warmup"] == 0
    assert out["tiers"] == ["batch"]


def test_constraint_and_kernel_phases_tiny(monkeypatch):
    monkeypatch.setenv("KTPU_PALLAS", "1")  # interpret mode on the CPU
    out = chip_smoke.phase_constraints(n_nodes=64, n_pods=200, zones=4,
                                       compiled=False)
    assert out["bound"] == 200
    assert out["kernel_routes"]["sinkhorn:interpret"] >= 1
    assert out["kernel_routes"]["fused_pair:interpret"] >= 1
    k = chip_smoke.phase_kernels(P=256, N=384, compiled=False)
    assert k["fused_pair_mismatches"] == 0
    assert k["sinkhorn_max_abs_diff"] < 1e-4


def test_mesh_phase_tiny():
    out = chip_smoke.phase_mesh(chips=4, n_nodes=256, n_pods=128, zones=4)
    assert out["placements_identical"]
    assert out["shard_rows"] == [64] * 4


def test_a_failed_check_fails_the_run(capsys):
    def boom():
        chip_smoke.check(False, "seeded failure")

    assert chip_smoke.run_phases([("ok", dict), ("boom", boom)]) is False
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["ok"] for x in lines] == [True, False]
    assert "seeded failure" in lines[1]["error"]


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_placement(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins when set (files land there only);
    otherwise the fixed <checkout>/.jax_cache."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from kubernetes_tpu.utils.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.values['jax_compilation_cache_dir'])\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
        code += "jax.jit(lambda x: x * 3 + 1)(jnp.ones(4)).block_until_ready()\n"
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-800:]
    returned, configured = r.stdout.split()[-2:]
    want = str(tmp_path) if env_dir else os.path.join(REPO, ".jax_cache")
    assert returned == configured == want
    if env_dir:
        assert any(f.endswith("-cache") for f in os.listdir(tmp_path))
