#!/usr/bin/env python
"""Bring-up check: the served scheduling path on one TPU chip, at the
scheduler_perf 5k-node size, with nothing allowed to fall back.

Phases, in one process (a chip belongs to one process at a time):

- ``device``: ``jax.devices()`` must be TPUs. Anything else ends the run
  here with ``"ok": false`` and a non-zero exit — there is no CPU
  fallback.
- ``headline``: ``Scheduler.from_config`` on a default configuration
  with warmup on, exactly as ``cli.run`` builds it. 5,000 base nodes
  (4 CPU / 32 Gi / 110 pods), 1,000 existing pods bound round-robin and
  30,000 pending base pods go in through ``on_node_add``/``on_pod_add``;
  ``warmup()``, then ``schedule_cycle()`` until the queue drains. Every
  pod must bind exactly once, no node may exceed its allocatable
  (recomputed from the host objects), every cycle must run on the
  configured solver with no ladder fallback and no host-mode snapshot,
  and warmup must leave nothing for the hot path to retrace.
- ``constraints``: the same served path at 5,000 nodes with
  SelectorSpread owners, preferred zone affinity and PreferNoSchedule
  taints: the preference kernels are live, so the solve compiles the
  Sinkhorn auto-router's plan branch and the fused NodeAffinity +
  TaintToleration pair — both must take the compiled Pallas route.
- ``kernels``: ``sinkhorn_plan(pallas=True, interpret=False)`` and
  ``fused_pair_normalize_device`` called directly at 8192x5120 and
  compared with their jnp twins; the lowered programs must hold the
  Mosaic kernel (``tpu_custom_call``).

``--chips 4`` runs only the ``mesh`` phase: 50,000 nodes and one
8,192-pod batch under ``parallel: {mesh: 4}``, then the same feed with
the mesh off on ``jax.devices()[0]``. Placements must be bit-identical
and the resident node table must sit on 4 devices, a quarter of the
rows each.

Every line before the last is one JSON object per phase: informational
(compile seconds, cycles, bound counts, warm pods/s), not a benchmark.
The last line is ``{"ok": ..., "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)


class SmokeFailure(AssertionError):
    """A phase's check failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(obj: dict) -> None:
    print(json.dumps(obj, sort_keys=True), flush=True)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def phase_device(info: dict, chips: int) -> None:
    check(info["platform"] == "tpu",
          f"no TPU: JAX found {info['count']} {info['platform']} "
          "device(s); this check never falls back to the CPU")
    check(info["count"] >= chips,
          f"--chips {chips} needs {chips} devices, JAX found "
          f"{info['count']}")


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------


def build_scheduler(cfg_overrides: dict):
    """A Scheduler built the way cli.run builds it, from a default
    configuration with warmup on plus ``cfg_overrides``."""
    import dataclasses

    from kubernetes_tpu.config import KubeSchedulerConfiguration, WarmupConfig
    from kubernetes_tpu.scheduler import Scheduler

    cfg = KubeSchedulerConfiguration(warmup=WarmupConfig(enabled=True))
    cfg = dataclasses.replace(cfg, **cfg_overrides)
    return cfg, Scheduler.from_config(cfg)


def drain(sched, cfg, max_cycles: int = 256) -> dict:
    """``schedule_cycle()`` until a cycle attempts nothing; checks every
    cycle ran on the configured solver with no fallback and no host-mode
    snapshot."""
    cycles, attempted, t0 = 0, 0, time.perf_counter()
    tiers, modes, first_s = set(), set(), 0.0
    for _ in range(max_cycles):
        r = sched.schedule_cycle()
        if r.attempted == 0:
            break
        cycles += 1
        if cycles == 1:
            first_s = time.perf_counter() - t0
        attempted += r.attempted
        tiers.add(r.solver_tier)
        modes.add(r.snapshot_mode)
        check(r.solver_tier == cfg.solver,
              f"cycle {cycles} ran on tier {r.solver_tier!r}, not the "
              f"configured {cfg.solver!r}")
        check(r.solver_fallbacks == 0,
              f"cycle {cycles} took {r.solver_fallbacks} solver fallbacks")
        check(r.snapshot_mode != "host",
              f"cycle {cycles} packed a host-mode snapshot")
    return {"cycles": cycles, "attempted": attempted,
            "cycles_s": time.perf_counter() - t0, "first_cycle_s": first_s,
            "tiers": sorted(tiers), "snapshot_modes": sorted(modes)}


def check_bindings(sched, nodes, existing, pending) -> dict:
    """Every pending pod bound exactly once, to a known node, and no node
    over its allocatable — recomputed from the host objects."""
    bindings = sched.binder.bindings
    keys = [k for k, _ in bindings]
    check(len(keys) == len(set(keys)),
          f"{len(keys) - len(set(keys))} pods bound more than once")
    want = {p.key() for p in pending}
    check(set(keys) == want,
          f"bound {len(set(keys) & want)}/{len(want)} pending pods "
          f"(+{len(set(keys) - want)} unexpected)")
    by_node = {n.name: [0.0, 0.0, 0] for n in nodes}
    placed = [(p, p.node_name) for p in existing]
    pods = {p.key(): p for p in pending}
    placed += [(pods[k], node) for k, node in bindings]
    for pod, node in placed:
        check(node in by_node, f"{pod.key()} bound to unknown node {node}")
        req = pod.effective_requests()
        use = by_node[node]
        use[0] += req.cpu_milli
        use[1] += req.memory
        use[2] += 1
    over = [n.name for n in nodes
            if by_node[n.name][0] > n.allocatable.cpu_milli
            or by_node[n.name][1] > n.allocatable.memory
            or by_node[n.name][2] > n.allocatable.pods]
    check(not over, f"{len(over)} nodes over allocatable, e.g. {over[:3]}")
    return {"bound": len(keys), "pending": len(want),
            "nodes_used": sum(1 for u in by_node.values() if u[2])}


def feed(sched, nodes, existing, pending) -> None:
    for n in nodes:
        sched.on_node_add(n)
    for p in list(existing) + list(pending):
        sched.on_pod_add(p)


def phase_headline(n_nodes=5000, n_existing=1000, n_pending=30000,
                   zones=10, cfg_overrides=None) -> dict:
    from kubernetes_tpu.models.cluster import make_nodes, make_pods

    cfg, sched = build_scheduler(cfg_overrides or {})
    nodes = make_nodes(n_nodes, zones=zones)
    existing = make_pods(n_existing, name_prefix="existing",
                         assigned_round_robin_over=n_nodes)
    pending = make_pods(n_pending)
    feed(sched, nodes, existing, pending)
    t0 = time.perf_counter()
    warmed = sched.warmup(sample_pods=pending[:64])
    warmup_s = time.perf_counter() - t0
    check(warmed > 0, "warmup compiled nothing")
    resets = sched.metrics.recovery_device_resets.value()
    check(resets == 0, f"warmup aborted on {resets:.0f} device errors")
    retraces0 = sched.obs.jax.retrace_total()
    out = drain(sched, cfg)
    retraces = sched.obs.jax.retrace_total() - retraces0
    check(retraces == 0, f"{retraces} retraces after warmup")
    out.update(check_bindings(sched, nodes, existing, pending))
    out.update(warmup_s=warmup_s, warmed_shapes=warmed,
               retraces_after_warmup=retraces,
               warm_pods_per_s=out["attempted"] / max(out["cycles_s"], 1e-9))
    return out


def constraint_feed(n_nodes: int, n_pods: int, zones: int):
    """SelectorSpread owners + preferred zone affinity over nodes of which
    every 7th carries a PreferNoSchedule taint: SelectorSpread,
    NodeAffinity and TaintToleration all stay live."""
    from kubernetes_tpu.api.types import (
        EFFECT_PREFER_NO_SCHEDULE,
        Affinity,
        NodeSelectorTerm,
        PreferredSchedulingTerm,
        Requirement,
        Taint,
    )
    from kubernetes_tpu.models.cluster import make_nodes, make_spread_pods

    nodes = make_nodes(n_nodes, zones=zones)
    for i, n in enumerate(nodes):
        if i % 7 == 0:
            n.taints = (Taint("dedicated", "batch",
                              EFFECT_PREFER_NO_SCHEDULE),)
    pods = make_spread_pods(n_pods, n_services=64)
    for i, p in enumerate(pods):
        p.affinity = Affinity(node_preferred=(PreferredSchedulingTerm(
            weight=10, preference=NodeSelectorTerm((Requirement(
                "failure-domain.beta.kubernetes.io/zone", "In",
                (f"zone-{i % zones}",)),))),))
    return nodes, pods


def phase_constraints(n_nodes=5000, n_pods=4096, zones=10,
                      compiled=True) -> dict:
    from kubernetes_tpu.config import WarmupConfig
    from kubernetes_tpu.obs.jaxtel import kernel_routes

    cfg, sched = build_scheduler({"warmup": WarmupConfig()})
    nodes, pods = constraint_feed(n_nodes, n_pods, zones)
    feed(sched, nodes, [], pods)
    before = kernel_routes()
    out = drain(sched, cfg)
    routes = {k: v - before.get(k, 0) for k, v in kernel_routes().items()
              if v != before.get(k, 0)}
    route = "pallas" if compiled else "interpret"
    for kernel in ("sinkhorn", "fused_pair"):
        check(routes.get(f"{kernel}:{route}", 0) > 0,
              f"the solve traced no {kernel}:{route} kernel (routes: "
              f"{routes})")
    out.update(check_bindings(sched, nodes, [], pods))
    out["kernel_routes"] = routes
    return out


# ---------------------------------------------------------------------------
# the kernels, against their jnp twins
# ---------------------------------------------------------------------------


def phase_kernels(P=8192, N=5120, compiled=True, seed=0) -> dict:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubernetes_tpu.ops.fused_score import fused_pair_normalize_device
    from kubernetes_tpu.ops.priorities import _normalize_reduce
    from kubernetes_tpu.ops.sinkhorn import sinkhorn_plan

    rng = np.random.default_rng(seed)
    out = {"shape": [P, N]}

    # Sinkhorn: tolerances of the retired tests_tpu compiled test
    score = jnp.asarray(rng.uniform(0, 10, (P, N)).astype(np.float32))
    mask = jnp.asarray(rng.uniform(size=(P, N)) > 0.3)
    cap = jnp.asarray(rng.integers(1, 5, N).astype(np.float32))
    t0 = time.perf_counter()
    lowered = jax.jit(functools.partial(
        sinkhorn_plan, iters=15, pallas=True,
        interpret=not compiled)).lower(score, mask, cap)
    text = lowered.as_text()
    kernel = lowered.compile()
    out["sinkhorn_compile_s"] = time.perf_counter() - t0
    got = np.asarray(kernel(score, mask, cap))
    want = np.asarray(jax.jit(functools.partial(
        sinkhorn_plan, iters=15, pallas=False))(score, mask, cap))
    out["sinkhorn_max_abs_diff"] = float(np.max(np.abs(got - want)))
    out["sinkhorn_mosaic"] = "tpu_custom_call" in text
    check(np.allclose(got, want, rtol=1e-4, atol=1e-5),
          f"sinkhorn Pallas vs jnp: max |diff| "
          f"{out['sinkhorn_max_abs_diff']:.3g} over rtol 1e-4/atol 1e-5")

    # fused NodeAffinity + TaintToleration pair: bit-identical
    raw_f = jnp.asarray(rng.integers(0, 50, (P, N)).astype(np.float32))
    raw_r = jnp.asarray(rng.integers(0, 5, (P, N)).astype(np.float32))
    mask = jnp.asarray(rng.random((P, N)) < 0.7)
    t0 = time.perf_counter()
    lowered = jax.jit(lambda a, b, m: fused_pair_normalize_device(
        a, b, m, 1.0, 1.0)).lower(raw_f, raw_r, mask)
    text_pair = lowered.as_text()
    kernel = lowered.compile()
    out["fused_pair_compile_s"] = time.perf_counter() - t0
    got = np.asarray(kernel(raw_f, raw_r, mask))
    want = np.asarray(jax.jit(lambda a, b, m: _normalize_reduce(a, m, False)
                              + _normalize_reduce(b, m, True))(
        raw_f, raw_r, mask))
    out["fused_pair_mismatches"] = int(np.sum(got != want))
    out["fused_pair_mosaic"] = "tpu_custom_call" in text_pair
    check(out["fused_pair_mismatches"] == 0,
          f"fused pair Pallas vs jnp: {out['fused_pair_mismatches']} "
          "elements differ")
    if compiled:
        check(out["sinkhorn_mosaic"] and out["fused_pair_mosaic"],
              "a lowered kernel program holds no tpu_custom_call")
    return out


# ---------------------------------------------------------------------------
# four chips: the node-axis mesh against mesh off
# ---------------------------------------------------------------------------


def _mesh_run(mesh, n_nodes: int, n_pods: int, zones: int) -> tuple:
    from kubernetes_tpu.config import ParallelConfig, WarmupConfig
    from kubernetes_tpu.models.cluster import make_nodes, make_pods

    cfg, sched = build_scheduler({"warmup": WarmupConfig(),
                                  "parallel": ParallelConfig(mesh=mesh)})
    nodes = make_nodes(n_nodes, zones=zones)
    pending = make_pods(n_pods)
    feed(sched, nodes, [], pending)
    out = drain(sched, cfg)
    out.update(check_bindings(sched, nodes, [], pending))
    placements = dict(sched.binder.bindings)
    dn = sched.cache._dev
    shards = [(s.device, s.data.shape[0])
              for s in dn.allocatable.addressable_shards]
    return out, placements, shards, int(dn.allocatable.shape[0])


def phase_mesh(chips=4, n_nodes=50000, n_pods=8192, zones=10) -> dict:
    import jax

    sharded, placed_mesh, shards, rows = _mesh_run(chips, n_nodes, n_pods,
                                                    zones)
    devices = {d for d, _ in shards}
    check(len(devices) == chips,
          f"node table on {len(devices)} devices, not {chips}")
    check(all(n == rows // chips for _, n in shards),
          f"node table shards {[n for _, n in shards]} are not "
          f"{rows}/{chips} rows each")
    single, placed_one, shards1, _ = _mesh_run("off", n_nodes, n_pods, zones)
    check({d for d, _ in shards1} == {jax.devices()[0]},
          f"mesh-off node table not on {jax.devices()[0]}")
    diff = sum(1 for k in placed_one if placed_mesh.get(k) != placed_one[k])
    check(placed_mesh == placed_one,
          f"{diff} placements differ between mesh {chips} and mesh off")
    return {"sharded": sharded, "single": single, "rows": rows,
            "shard_rows": [n for _, n in shards],
            "shard_devices": sorted(str(d) for d in devices),
            "placements_identical": True}


def run_phases(phases) -> bool:
    """Run ``(name, fn)`` phases in order, printing one JSON line each;
    a failed phase is reported and the rest still run."""
    ok = True
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            res = fn()
            say({"phase": name, "ok": True,
                 "seconds": time.perf_counter() - t0, **res})
        except Exception as e:  # every phase reports; the verdict is ok
            traceback.print_exc()
            ok = False
            say({"phase": name, "ok": False,
                 "seconds": time.perf_counter() - t0,
                 "error": f"{type(e).__name__}: {e}"[:2000]})
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    args = ap.parse_args(argv)
    device = {"platform": "none", "kind": "", "count": 0}
    try:
        device = device_info()
        phase_device(device, args.chips)
        from kubernetes_tpu.utils.compile_cache import enable_compile_cache

        say({"phase": "device", "ok": True, **device,
             "compile_cache": enable_compile_cache()})
    except Exception as e:
        traceback.print_exc()
        say({"ok": False, "device": device,
             "error": f"{type(e).__name__}: {e}"[:2000]})
        return 1
    if args.chips == 4:
        phases = [("mesh", lambda: phase_mesh(chips=4))]
    else:
        phases = [("headline", phase_headline),
                  ("constraints", phase_constraints),
                  ("kernels", phase_kernels)]
    ok = run_phases(phases)
    device = device_info()
    say({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
