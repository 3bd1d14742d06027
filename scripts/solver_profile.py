#!/usr/bin/env python
"""Per-phase solver profile — where a scheduling round's time goes.

Produces the same record shape as ``benchres/solver_profile_cpu.json`` so
the CPU and TPU profiles are directly comparable (VERDICT.md round-4
item 3: re-run the phase profile on hardware before optimizing scoring).
Each phase and each priority kernel is jitted separately and timed as the
min of N runs with ``block_until_ready`` — compile excluded.

Usage:  python scripts/solver_profile.py [--out benchres/solver_profile_tpu.json]
        (pins to CPU only when JAX_PLATFORMS=cpu is exported; otherwise
        uses whatever backend jax initializes — on the chip, run it
        through the chip tool in one process)
"""
# graftlint: disable-file=R3 -- profiler by design: each phase/kernel gets
# its own jax.jit wrapper built once, warmed, then timed (compile excluded);
# the wrapper-per-call pattern the rule hunts is the measurement harness here
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timeit(fn, n=5):
    import jax

    fn()  # warmup/compile
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def profile_shape(name: str, n_nodes: int, n_pending: int, n_existing: int,
                  full: bool) -> dict:
    import jax.numpy as jnp

    from bench import build_variant
    from kubernetes_tpu.ops.assign import batch_assign
    from kubernetes_tpu.ops.predicates import run_predicates, static_predicate_reasons
    from kubernetes_tpu.ops.priorities import (
        DEFAULT_WEIGHTS,
        PRIORITY_REGISTRY,
        run_priorities,
    )
    import jax

    w = build_variant(name, n_nodes, n_existing, n_pending)
    dp, dv = w.device_batch(w.pending[:n_pending], n_pending)
    dn, ds, dt = w.dn, w.ds, w.dt

    rec: dict = {}
    rec["filter_full_s"] = round(timeit(
        jax.jit(lambda: run_predicates(dp, dn, ds, topo=dt, vol=dv))), 3)
    rec["filter_static_part_s"] = round(timeit(
        jax.jit(lambda: static_predicate_reasons(dp, dn, ds))), 3)

    fr = jax.jit(lambda: run_predicates(dp, dn, ds, topo=dt, vol=dv))()
    mask = fr.mask
    rec["score_s"] = round(timeit(
        jax.jit(lambda: run_priorities(dp, dn, ds, mask, topo=dt))), 3)

    t0 = time.perf_counter()
    a, u, r = batch_assign(dp, dn, ds, topo=dt, vol=dv, per_node_cap=2)
    jax.block_until_ready(a)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    a, u, r = batch_assign(dp, dn, ds, topo=dt, vol=dv, per_node_cap=2)
    jax.block_until_ready(a)
    rec[f"full_solve_s_{int(r)}_rounds"] = round(time.perf_counter() - t0, 3)
    rec["full_solve_compile_s"] = round(compile_s, 1)

    if full:
        prio_ms = {}
        for pname, weight in DEFAULT_WEIGHTS.items():
            if not weight:
                continue
            fn = PRIORITY_REGISTRY[pname]
            try:
                prio_ms[_short(pname)] = int(1000 * timeit(
                    jax.jit(lambda fn=fn: fn(dp, dn, ds, dt, mask))))
            except Exception as e:  # a kernel needing absent inputs
                prio_ms[_short(pname)] = f"error: {e}"[:80]
        rec["priorities_ms"] = prio_ms
    return rec


def _short(name: str) -> str:
    # LeastRequestedPriority -> least_requested (match the cpu profile keys)
    import re

    s = re.sub("Priority$", "", name)
    return re.sub(r"(?<!^)(?=[A-Z])", "_", s).lower()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="benchres/solver_profile_tpu.json")
    ap.add_argument("--nodes", type=int, default=5000)
    ap.add_argument("--pods", type=int, default=8192)
    ap.add_argument("--quick", action="store_true",
                    help="base shape only (smoke test)")
    args = ap.parse_args()

    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    platform = jax.devices()[0].platform
    out = {
        "what": (f"Per-phase solver profile on the {platform} backend "
                 "(min of 5, jitted per phase)"),
        "platform": platform,
        "shapes": {
            f"base/{args.nodes}x{args.pods}": profile_shape(
                "base", args.nodes, args.pods, min(1000, args.nodes),
                full=True),
        },
    }
    if not args.quick:
        out["shapes"]["even_spread/2000x4096"] = profile_shape(
            "even_spread", 2000, 4096, 500, full=False)
    if os.path.dirname(args.out):
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out["shapes"], indent=2))


if __name__ == "__main__":
    main()
