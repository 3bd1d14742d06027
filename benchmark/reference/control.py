"""The control: the plain reference put in the program's place with one
stated guarantee broken.

The v1.16 priorities are integer arithmetic and the configurations state
no floating precision, so the control breaks the stated guarantee "every
placement is a top-scored node at the usage of its solve round": it
places a whole cycle's pods in one round (:func:`one_round`), each on its
argmax at the cycle's start usage, scores never refreshed as the batch
lands: the shortcut that would tempt a later PR cutting the rounds.

Where a node fits no more of a cycle's pods than ``per_node_cap`` (a
deployment whose nodes are nearly full, or cycles of a few pods), one
round is a placement the guarantee allows, and :func:`stale_snapshot`
is the control: each pod placed by a snapshot one cycle old, the step
that would tempt a PR that skips the per-cycle snapshot.

On each sampled cycle of a real run at the cell's own size, a placer
puts the same pods from the same start state; the output check judges
those placements exactly as it judges the program's.

    python3 benchmark/reference/control.py --workload <cell> --seeds 1,2,3 --seconds 5

prints one JSON line per seed with the numbers of the program and of
each control (``one_round``, ``stale_snapshot``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

from reference import scores as S  # noqa: E402
from reference.check import check  # noqa: E402


def one_round():
    """``control(rp, start, incs, prev) -> (placed, end)``: the control. Each pod
    goes to its top-scored node at the cycle's start usage, scores never
    refreshed as the batch lands (a one-round solve), the next-best node
    when that one is full. Scores in float32, all nodes, lowest index on
    ties."""
    import jax.numpy as jnp

    def place(rp, start, incs, prev):
        st = start.copy()
        placed = []
        order_of = {}
        for inc in incs:
            spec = rp.spec(inc)
            key = (spec.cpu, spec.mem, spec.owner, spec.zone_pref)
            order = order_of.get(key)
            if order is None:
                s = np.asarray(S.exact(spec, rp.nodes, start, rp.weights,
                                       rp.n_zones, jnp.float32))
                order = np.argsort(-s, kind="stable")[:np.isfinite(s).sum()]
                order_of[key] = order
            for node in order:
                if (st.cpu[node] + spec.cpu <= rp.nodes.cap_cpu[node]
                        and st.mem[node] + spec.mem <= rp.nodes.cap_mem[node]
                        and st.pods[node] + 1 <= rp.nodes.cap_pods[node]):
                    break
            else:
                continue
            node = int(node)
            st.add(node, spec.cpu, spec.mem, spec.owner)
            placed.append((inc, node))
        return placed, st

    return place


def stale_snapshot():
    """``control(rp, start, incs, prev) -> (placed, end)``: each pod, one
    at a time as v1.16 schedules, on its top-scored node (float32, all
    nodes, lowest index on ties) by the usage at the previous cycle's
    start, updated only by its own placements. Breaks "a top-scored node
    at the usage of its round" where the previous cycle's bindings move
    the order, and "no node over allocatable" where they filled a node
    the old snapshot still shows room on."""
    import jax.numpy as jnp

    def place(rp, start, incs, prev):
        seen = (start if prev is None else prev).copy()
        end = start.copy()
        placed = []
        for inc in incs:
            spec = rp.spec(inc)
            s = np.asarray(S.exact(spec, rp.nodes, seen, rp.weights,
                                   rp.n_zones, jnp.float32))
            if not np.isfinite(s).any():
                continue
            node = int(np.argmax(s))
            seen.add(node, spec.cpu, spec.mem, spec.owner)
            end.add(node, spec.cpu, spec.mem, spec.owner)
            placed.append((inc, node))
        return placed, end

    return place


def main(argv=None) -> int:
    import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    spec = run.load_spec()
    cell, config, traffic = run.resolve(spec, args.workload)
    run.device_info(int(cell["chips"]))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(run.ROOT, ".jax_cache")
    from kubernetes_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import driver

    runner = {"closed_backlog": driver.run_backlog,
              "open_loop": driver.run_open}[traffic["kind"]]
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        log, sched, _ = runner(config, traffic, seed, args.seconds, None)
        del sched
        out = {"seed": seed, "workload": args.workload}
        for name, ctl in (("program", None), ("one_round", one_round()),
                          ("stale_snapshot", stale_snapshot())):
            res = check(log, config, traffic, seed, control=ctl)
            out[name] = {k: (v[0] if isinstance(v, tuple) else v)
                         for k, v in res.items()}
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
