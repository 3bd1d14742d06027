#!/usr/bin/env python
"""Sustained-churn benchmark — the serving mode's acceptance harness.

Holds a creates+deletes/sec rate against the scheduler for a fixed
wall-time and reports p50/p99 CREATE-TO-BIND latency (the production
serving metric, not batch throughput), shed/429 counts, solve-site
retrace counts (jaxtel), and watch fan-out lag. Three arms, all in one
record so rounds stay comparable::

    serving   the event-driven micro-batch loop (doorbell + window)
    fixed     the legacy fixed-interval cycle loop (--cycle-interval
              semantics: solve when work exists, sleep the interval on
              an empty pop) at the SAME churn rate
    overload  the serving loop offered >= 4x the base rate behind the
              APF-style flow controller: excess creates shed with
              429-equivalent rejections while admitted pods keep a
              bounded p99 and the scheduler queue stays bounded
    failover  kill-the-leader mid-churn: two replicas share a lease
              (fenced binds + takeover reconciliation attached); the
              leader is hard-killed at 40% of the run and the arm
              reports takeover time (kill -> standby's first bind) and
              post-recovery p99 create-to-bind, with a CAS'd shared
              truth proving zero double-binds across the handover

With ``--mesh N`` the record becomes the COMPOSED serving-on-mesh
family (``benchres/churn_mesh_r*.json``, default 5000 nodes — the
paper's scheduler_perf count) built on serving.ServingRuntime::

    serving     sustained churn through doorbell micro-batches solving
                under GSPMD on the node-sharded resident snapshot,
                thousands of WatchHub watchers fanning out every bind,
                creates admitted through the APF mutating flow whose
                saturation probe is Scheduler.backend_pressure
    failover    kill-the-leader with BOTH replicas on the mesh: the
                standby re-places the resident snapshot SHARDED,
                re-warms, relists its watchers, zero double binds
    shard_loss  one mesh device lost mid-churn (chaos.MeshChaos):
                cooloff -> host-mode cycles (warmed host-fallback
                shapes, zero retraces) -> heal back to sharded, the
                doorbell loop never stalling

Usage::

    python scripts/bench_churn.py                      # full (~3 min)
    python scripts/bench_churn.py --smoke              # ~6 s sanity run
    python scripts/bench_churn.py --rate 800 --duration 90

Writes ``benchres/churn_r01.json`` (``--out``); the churn gates in
scripts/bench_compare.py diff the two newest churn_r*.json.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

# the CPU only when the caller pins it (JAX_PLATFORMS=cpu): then the
# mesh arms get 8 virtual devices, set BEFORE jax initializes. Unpinned,
# JAX takes the machine's accelerator.
if (os.environ.get("JAX_PLATFORMS") == "cpu"
        and "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from kubernetes_tpu.chaos import MeshChaos  # noqa: E402
from kubernetes_tpu.config import (  # noqa: E402
    ParallelConfig,
    RecoveryConfig,
    ServingConfig,
    WarmupConfig,
)
from kubernetes_tpu.scheduler import Scheduler  # noqa: E402
from kubernetes_tpu.serving import (  # noqa: E402
    Doorbell,
    FlowController,
    FlowSchema,
    RequestRejected,
    ServingLoop,
    ServingRuntime,
    WatchHub,
)
from kubernetes_tpu.testing import make_node, make_pod  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: pod shape used by every arm (uniform so the solve signature is one
#: warmed bucket family)
POD_CPU = 50.0
POD_MEM = 128 * 2**20


def build_scheduler(n_nodes: int, warm_buckets, solver: str = "batch",
                    binder=None, incremental=None):
    """A fresh scheduler + AOT warmup over the serving bucket grid."""
    kw = {}
    if incremental is not None:
        kw["incremental"] = incremental
    s = Scheduler(
        enable_preemption=False,
        solver=solver,
        binder=binder,
        warmup=WarmupConfig(enabled=True, pod_buckets=tuple(warm_buckets)),
        **kw,
    )
    for i in range(n_nodes):
        s.on_node_add(make_node(f"node-{i}", cpu_milli=64000,
                                memory=256 * 2**30, pods=500))
    sample = [make_pod("warm-sample", cpu_milli=POD_CPU, memory=POD_MEM)]
    t0 = time.monotonic()
    compiled = s.warmup(sample_pods=sample)
    return s, compiled, time.monotonic() - t0


class ChurnProducer:
    """Drives creates+deletes against the scheduler. Creates are new
    pending pods (the create stamp is the queue-add time the e2e
    histogram measures from); deletes retire previously BOUND pods, so
    the node table churns too (the delta-snapshot path). All scheduler
    mutations go through ``lock`` — the serving loop's ingest seam.

    Arrival shape is BURSTY (``burst_hz`` trains, default 10 Hz): the
    production pattern an interval-paced loop handles worst — a burst
    landing during the post-empty-pop sleep waits out the rest of the
    interval — and uniform trickle would flatter it. Pacing is
    elapsed-based with catch-up, so a slow consumer cannot silently
    lower the offered rate; ``flood=True`` (the overload arm) ignores
    pacing and offers as fast as Python can submit."""

    def __init__(self, sched, lock, rate_ops_s: float, duration_s: float,
                 admit=None, hub: "WatchHub | None" = None,
                 name: str = "arm", burst_hz: float = 10.0,
                 flood: bool = False) -> None:
        self.sched = sched
        self.lock = lock
        self.rate = rate_ops_s
        self.duration = duration_s
        #: admission gate for creates (the overload arm's APF seam):
        #: callable raising RequestRejected to shed
        self.admit = admit
        self.hub = hub
        self.name = name
        self.burst_hz = burst_hz
        self.flood = flood
        self.created = 0
        self.deleted = 0
        self.shed = 0
        self.bound_backlog: list = []  # (key, node) awaiting delete
        self.max_queue_depth = 0
        self.results: list = []  # CycleResults (on_cycle feeds this)

    def on_cycle(self, res) -> None:
        self.results.append(res)

    def _drain_new_binds(self, seen_idx: int) -> int:
        while seen_idx < len(self.results):
            self.bound_backlog.extend(
                self.results[seen_idx].assignments.items())
            seen_idx += 1
        return seen_idx

    def _create_one(self) -> None:
        pod = make_pod(f"{self.name}-{self.created + self.shed}",
                       cpu_milli=POD_CPU, memory=POD_MEM)
        if self.admit is not None:
            try:
                self.admit(pod)
            except RequestRejected:
                self.shed += 1
                return
        with self.lock:
            self.sched.on_pod_add(pod)
        self.created += 1

    def _delete_some(self, n: int) -> None:
        for _ in range(n):
            if not self.bound_backlog:
                return
            key, node = self.bound_backlog.pop(0)
            ns, pname = key.split("/", 1)
            gone = make_pod(pname, namespace=ns, cpu_milli=POD_CPU,
                            memory=POD_MEM, node_name=node)
            with self.lock:
                self.sched.on_pod_delete(gone)
            if self.hub is not None:
                self.hub.publish(("DELETED", key))
            self.deleted += 1

    def run(self) -> None:
        start = time.monotonic()
        seen = 0
        if self.flood:
            # overload: no pacing — every iteration offers a create and
            # retires binds; the APF gate decides what sheds
            while time.monotonic() - start < self.duration:
                self._create_one()
                seen = self._drain_new_binds(seen)
                self._delete_some(len(self.bound_backlog) - 64)
                self.max_queue_depth = max(self.max_queue_depth,
                                           len(self.sched.queue))
            return
        burst_s = 1.0 / self.burst_hz
        issued = 0
        next_burst = start
        while True:
            now = time.monotonic()
            if now - start >= self.duration:
                break
            if now < next_burst:
                time.sleep(next_burst - now)
            next_burst += burst_s
            # elapsed-based catch-up: the offered rate holds even when a
            # burst was delayed by lock contention with a long solve
            target = self.rate * (min(time.monotonic(), start
                                      + self.duration) - start)
            ops = int(target) - issued
            issued += ops
            seen = self._drain_new_binds(seen)
            n_creates = ops // 2 + (ops % 2)
            for _ in range(n_creates):
                self._create_one()
            self._delete_some(ops // 2)
            self.max_queue_depth = max(self.max_queue_depth,
                                       len(self.sched.queue))


def summarize(producer: ChurnProducer, wall_s: float, sched) -> dict:
    lats = [v for r in producer.results for v in r.e2e_latency_s.values()]
    la = np.asarray(lats) if lats else np.asarray([0.0])
    flushes = {}
    for r in producer.results:
        if r.flush_trigger:
            flushes[r.flush_trigger] = flushes.get(r.flush_trigger, 0) + 1
    # per-cycle solve_s split by solve_scope (full vs restricted) — the
    # incremental mode's warm-start wins must be visible in the record,
    # not just in the aggregate latency
    by_scope: dict = {}
    for r in producer.results:
        if not r.solve_scope:
            continue
        d = by_scope.setdefault(r.solve_scope,
                                {"cycles": 0, "solve_s_sum": 0.0})
        d["cycles"] += 1
        d["solve_s_sum"] += r.solve_s
    scope_out = {
        k: {"cycles": v["cycles"],
            "mean_solve_s": round(v["solve_s_sum"] / v["cycles"], 6)}
        for k, v in sorted(by_scope.items())
    }
    sites = sched.obs.jax.snapshot()["sites"].get("solve", {})
    # the perf ledger's per-arm summary (obs/ledger.py): measured-vs-
    # modeled efficiency, per-phase attribution shares, SLO burn count —
    # the bench_compare `ledger` gate family reads exactly this shape,
    # so the next churn record carries the falsification evidence per
    # arm. getattr: older schedulers / fakes without a ledger skip it.
    ledger = getattr(sched.obs, "ledger", None)
    ledger_out = (ledger.arm_summary()
                  if ledger is not None and ledger.enabled else None)
    # the device-memory ledger's per-arm summary (obs/memledger.py):
    # modeled-vs-measured resident bytes, watermark peak, preflight
    # verdict counts, OOM forensic ring — the bench_compare `memory`
    # gate family reads exactly this shape (absence-tolerant, same
    # contract as the perf-ledger block above)
    memledger = getattr(sched.obs, "memledger", None)
    memory_out = (memledger.arm_summary()
                  if memledger is not None and memledger.enabled else None)
    # per-arm tail-attribution block (obs/journey.py): the retained
    # journey closest to the arm's p99 create-to-bind, with its phase
    # decomposition — the record-level answer to "WHERE did the p99 pod
    # spend its latency", plus the arm's incident count so the
    # bench_compare `journey` gate family can pin clean arms at zero.
    # Absence-tolerant like the ledger blocks above.
    journeys = getattr(sched.obs, "journeys", None)
    tail_out = None
    if journeys is not None and getattr(journeys, "enabled", False):
        snap = journeys.snapshot()
        slowest = [j for j in (snap.get("slowest") or [])
                   if j.get("e2e_s") is not None]
        if slowest:
            p99 = float(np.percentile(la, 99))
            pick = min(slowest, key=lambda j: abs(j["e2e_s"] - p99))
            incidents = getattr(sched.obs, "incidents", None)
            tail_out = {
                "p99_s": p99,
                "p99_pod": pick.get("pod", ""),
                "e2e_s": pick.get("e2e_s"),
                "phases_s": pick.get("phases_s", {}),
                "phase_share": pick.get("phase_share", {}),
                "share_sum": round(sum(
                    v for v in pick.get("phase_share", {}).values()), 4),
                "slowest_retained": len(slowest),
                "journeys_bound": snap.get("bound", 0),
                "journeys_dropped": snap.get("dropped", 0),
                "incidents": (int(incidents.total)
                              if incidents is not None
                              and getattr(incidents, "enabled", False)
                              else None),
            }
    return {
        **({"ledger": ledger_out} if ledger_out else {}),
        **({"memory": memory_out} if memory_out else {}),
        **({"tail": tail_out} if tail_out else {}),
        "solve_s_by_scope": scope_out,
        "wall_s": round(wall_s, 2),
        "created": producer.created,
        "deleted": producer.deleted,
        "bound": int(sum(r.scheduled for r in producer.results)),
        "cycles": len(producer.results),
        "ops_per_sec": round((producer.created + producer.deleted)
                             / max(wall_s, 1e-9), 1),
        "p50_s": round(float(np.percentile(la, 50)), 4),
        "p90_s": round(float(np.percentile(la, 90)), 4),
        "p99_s": round(float(np.percentile(la, 99)), 4),
        "max_s": round(float(la.max()), 4),
        "latency_samples": len(lats),
        "max_queue_depth": producer.max_queue_depth,
        "flushes": flushes,
        "jax": {k: sites.get(k, 0)
                for k in ("calls", "hits", "compiles", "retraces")},
        "retraces_total": sched.obs.jax.retrace_total(),
    }


def drain(sched, timeout_s: float = 15.0) -> bool:
    """Let the loop finish the residual queue after the producer stops."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if len(sched.queue) == 0:
            return True
        time.sleep(0.02)
    return len(sched.queue) == 0


def run_serving_arm(rate: float, duration: float, n_nodes: int,
                    warm_buckets, serving_cfg: ServingConfig,
                    overload: bool = False) -> dict:
    """One serving-loop arm; with ``overload`` the producer FLOODS
    creates (no pacing — many times the base rate, measured and
    reported) through the APF flow controller: creates shed with
    429-equivalents once the scheduler's pending depth crosses the
    bound, so the queue stays bounded and admitted pods keep a bounded
    p99."""
    sched, compiled, warm_s = build_scheduler(n_nodes, warm_buckets)
    bell = sched.attach_doorbell(Doorbell())
    hub = WatchHub(buffer=1024, metrics=sched.metrics)
    fast_w = hub.register()
    lazy_w = hub.register()   # polled once per second
    stuck_w = hub.register()  # never polls: must be evicted, not stall us
    admit = None
    ctrl = None
    shed_queue_bound = 2 * serving_cfg.target_bucket
    if overload:
        ctrl = FlowController(
            flows=[FlowSchema("mutating", concurrency=1024,
                              queue_length=0, queue_timeout_s=0.0)],
            retry_after_s=1.0)
        # the bounded-queue contract: shed creates while the scheduler's
        # pending depth exceeds the bound — 429 + Retry-After instead of
        # unbounded queue growth
        ctrl.set_saturation("mutating", lambda: len(sched.queue),
                            maximum=shed_queue_bound)

        def admit(pod):
            seat = ctrl.acquire("mutating")
            ctrl.release(seat)

    loop = ServingLoop(sched, bell, serving_cfg)
    prod = ChurnProducer(sched, loop.lock, rate, duration,
                         admit=admit, hub=hub, flood=overload,
                         name="ov" if overload else "sv")
    loop.on_cycle = lambda res: (
        prod.on_cycle(res),
        [hub.publish(("BOUND", k)) for k in res.assignments],
    )
    stop = threading.Event()
    loop_t = threading.Thread(target=loop.run, args=(stop,), daemon=True)
    lazy_stop = threading.Event()

    def lazy_poll():
        while not lazy_stop.is_set():
            try:
                lazy_w.poll()
            except Exception:
                return
            lazy_stop.wait(1.0)

    lazy_t = threading.Thread(target=lazy_poll, daemon=True)
    t0 = time.monotonic()
    loop_t.start()
    lazy_t.start()
    fast_stop = threading.Event()

    def fast_poll():
        while not fast_stop.is_set():
            try:
                fast_w.poll()
            except Exception:
                return
            fast_stop.wait(0.02)

    fast_t = threading.Thread(target=fast_poll, daemon=True)
    fast_t.start()
    prod.run()
    drained = drain(sched)
    wall = time.monotonic() - t0
    stop.set()
    lazy_stop.set()
    fast_stop.set()
    loop_t.join(timeout=10)
    lazy_t.join(timeout=5)
    fast_t.join(timeout=5)
    out = summarize(prod, wall, sched)
    out.update({
        "mode": "serving",
        "warmup": {"compiled": compiled, "seconds": round(warm_s, 1)},
        "drained": drained,
        "doorbell_rings": sched.doorbell.rings_total,
        "watch": hub.stats(),
        "watch_stuck_evicted": stuck_w.gone,
    })
    if overload:
        total_offered = prod.created + prod.shed
        out.update({
            "mode": "overload",
            "offered_ops_per_sec": round(
                (prod.created + prod.deleted + prod.shed)
                / max(wall, 1e-9), 1),
            "overload_factor_vs_base": round(
                (prod.created + prod.deleted + prod.shed)
                / max(wall, 1e-9) / max(rate, 1e-9), 1),
            "shed_429": prod.shed,
            "admitted": prod.created,
            "shed_rate": round(prod.shed / max(total_offered, 1), 4),
            "shed_queue_bound": shed_queue_bound,
            "flowcontrol": ctrl.stats(),
        })
    return out


# ---------------------------------------------------------------------------
# composed serving-on-mesh arm family (--mesh): the production posture —
# ServingRuntime (serving loop + APF backend-pressure shedding + watch
# hub) over the node-sharded backend at the scheduler_perf node count,
# with kill-the-leader and kill-one-shard chaos arms
# ---------------------------------------------------------------------------


def build_runtime(n_nodes: int, warm_buckets, serving_cfg: ServingConfig,
                  mesh: int = 0, binder=None, recovery=None):
    """A fresh COMPOSED replica: mesh-backed scheduler + ServingRuntime
    (doorbell, loop, APF flow with the backend-pressure probe, watch
    hub) + AOT warmup over the serving grid — sharded AND host-fallback
    shapes, so neither micro-batch churn nor a shard-loss cooloff ever
    retraces."""
    kw = {}
    if mesh:
        kw["parallel"] = ParallelConfig(mesh=mesh)
    if recovery is not None:
        kw["recovery"] = recovery
    s = Scheduler(
        enable_preemption=False,
        solver="batch",
        binder=binder,
        warmup=WarmupConfig(enabled=True, pod_buckets=tuple(warm_buckets)),
        **kw,
    )
    for i in range(n_nodes):
        s.on_node_add(make_node(f"node-{i}", cpu_milli=64000,
                                memory=256 * 2**30, pods=500))
    rt = ServingRuntime(s, serving_cfg)
    t0 = time.monotonic()
    compiled = rt.warm_if_pending(
        sample_pods=[make_pod("warm-sample", cpu_milli=POD_CPU,
                              memory=POD_MEM)])
    return rt, compiled, time.monotonic() - t0


def _watcher_fleet(hub, n_watchers: int, stuck: int = 5):
    """Register ``n_watchers`` live watchers (drained by a few poller
    threads round-robin — thousands of sockets timeshare a handful of
    handler threads in any real deployment) plus ``stuck`` watchers
    that never poll: the hub must evict them instead of stalling."""
    watchers = [hub.register() for _ in range(n_watchers)]
    stuck_ws = [hub.register() for _ in range(stuck)]
    stop = threading.Event()
    threads = []

    def poller(group):
        while not stop.is_set():
            for w in group:
                try:
                    w.poll()
                except Exception:
                    pass  # evicted mid-run: the relist case, keep going
            stop.wait(0.05)

    k = max(1, min(4, n_watchers))
    for i in range(k):
        t = threading.Thread(target=poller, args=(watchers[i::k],),
                             daemon=True)
        t.start()
        threads.append(t)

    def shutdown():
        stop.set()
        for t in threads:
            t.join(timeout=5)

    return stuck_ws, shutdown


def _mesh_summary(rt, prod, wall: float, compiled: int, warm_s: float,
                  mesh: int) -> dict:
    sched = rt.sched
    out = summarize(prod, wall, sched)
    bound = max(out["bound"], 1)
    out.update({
        "mesh": mesh,
        "creates_per_sec": round(prod.created / max(wall, 1e-9), 1),
        "warmup": {"compiled": compiled, "seconds": round(warm_s, 1)},
        "doorbell_rings": sched.doorbell.rings_total,
        # d2h bytes per BOUND pod across the whole arm — the PR-7
        # answer-sized boundary, now sharded (one int32 per padded pod
        # slot + per-cycle scalars; nothing (P, N)-shaped crosses)
        "readback_bytes_per_pod": round(
            sched.obs.jax.d2h_bytes_total() / bound, 2),
        "snapshot_modes": dict(prod.snapshot_modes),
    })
    return out


class MeshChurnProducer(ChurnProducer):
    """ChurnProducer that also histograms per-cycle snapshot modes and
    stamps cycle completion times (the doorbell-stall evidence)."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.snapshot_modes: dict = {}
        self.cycle_stamps: list = []

    def on_cycle(self, res) -> None:
        super().on_cycle(res)
        self.cycle_stamps.append(time.monotonic())
        if res.snapshot_mode:
            self.snapshot_modes[res.snapshot_mode] = \
                self.snapshot_modes.get(res.snapshot_mode, 0) + 1


def run_mesh_serving_arm(rate: float, duration: float, n_nodes: int,
                         warm_buckets, serving_cfg: ServingConfig,
                         mesh: int, n_watchers: int) -> dict:
    """Sustained churn through the composed runtime at the
    scheduler_perf node count: doorbell-driven micro-batches solving
    under GSPMD on the node-sharded resident snapshot, thousands of
    WatchHub watchers fanning out every bind, and creates admitted
    through the APF mutating flow whose saturation probe is the
    scheduler's REAL backend pressure."""
    rt, compiled, warm_s = build_runtime(n_nodes, warm_buckets,
                                         serving_cfg, mesh=mesh)
    sched = rt.sched

    def admit(pod):
        seat = rt.flow.acquire("mutating")
        rt.flow.release(seat)

    prod = MeshChurnProducer(sched, rt.loop.lock, rate, duration,
                             admit=admit, hub=rt.hub, name="msv")
    rt.loop.on_cycle = lambda res: (
        prod.on_cycle(res),
        [rt.hub.publish(("BOUND", k)) for k in res.assignments],
    )
    stuck_ws, shutdown_watchers = _watcher_fleet(rt.hub, n_watchers)
    stop = threading.Event()
    loop_t = threading.Thread(target=rt.loop.run, args=(stop,),
                              daemon=True)
    t0 = time.monotonic()
    loop_t.start()
    prod.run()
    drained = drain(sched, timeout_s=30.0)
    wall = time.monotonic() - t0
    stop.set()
    loop_t.join(timeout=10)
    shutdown_watchers()
    out = _mesh_summary(rt, prod, wall, compiled, warm_s, mesh)
    out.update({
        "mode": "mesh_serving",
        "drained": drained,
        "watchers": n_watchers,
        "watch": rt.hub.stats(),
        "watch_stuck_evicted": sum(1 for w in stuck_ws if w.gone),
        "shed_429": prod.shed,
        "shed_bound": rt.shed_bound(),
        "flowcontrol": rt.flow.stats(),
    })
    return out


def run_mesh_shard_loss_arm(rate: float, duration: float, n_nodes: int,
                            warm_buckets, serving_cfg: ServingConfig,
                            mesh: int, loss_frac: float = 0.4,
                            cooloff_s: float = 2.0) -> dict:
    """Kill-one-shard mid-churn: at ``loss_frac`` of the run a mesh
    device is lost (chaos.MeshChaos arms ShardLost at the snapshot
    seam). The scheduler must take the existing cooloff -> host-mode ->
    heal-sharded path WITHOUT stalling the doorbell loop: producers
    keep feeding, host-mode cycles keep binding (single-device, warmed
    by the host-fallback sweep — zero retraces), and after the cooloff
    the resident table re-places SHARDED. Reports the heal time and the
    longest cycle-to-cycle gap through the whole arc."""
    rt, compiled, warm_s = build_runtime(
        n_nodes, warm_buckets, serving_cfg, mesh=mesh,
        recovery=RecoveryConfig(device_reset_limit=1,
                                device_cooloff_s=cooloff_s))
    sched = rt.sched
    chaos = MeshChaos(sched)
    prod = MeshChurnProducer(sched, rt.loop.lock, rate, duration,
                             name="msl")

    def on_cycle(res):
        prod.on_cycle(res)
        chaos.observe(res, time.monotonic())

    rt.loop.on_cycle = on_cycle
    stop = threading.Event()
    loop_t = threading.Thread(target=rt.loop.run, args=(stop,),
                              daemon=True)
    t0 = time.monotonic()
    loss_at = t0 + duration * loss_frac
    def arm_loss():
        delay = loss_at - time.monotonic()
        if delay > 0 and stop.wait(delay):
            return  # the run ended before the loss point
        chaos.lose_shard(time.monotonic())

    arm_t = threading.Thread(target=arm_loss, daemon=True)
    loop_t.start()
    arm_t.start()
    prod.run()
    drained = drain(sched, timeout_s=max(30.0, 3 * cooloff_s))
    wall = time.monotonic() - t0
    stop.set()
    loop_t.join(timeout=10)
    arm_t.join(timeout=5)
    out = _mesh_summary(rt, prod, wall, compiled, warm_s, mesh)
    stamps = prod.cycle_stamps
    max_gap = max((b - a for a, b in zip(stamps, stamps[1:])),
                  default=0.0)
    out.update({
        "mode": "mesh_shard_loss",
        "drained": drained,
        "loss_at_s": round((chaos.lost_at or t0) - t0, 2),
        "cooloff_s": cooloff_s,
        # the longest stall between consecutive cycle completions —
        # spanning the loss, the host-mode window, and the sharded heal
        "doorbell_max_gap_s": round(max_gap, 3),
        **chaos.report(),
    })
    return out


def run_mesh_failover_arm(rate: float, duration: float, n_nodes: int,
                          warm_buckets, serving_cfg: ServingConfig,
                          mesh: int, kill_frac: float = 0.4) -> dict:
    """Kill-the-leader with BOTH replicas on the mesh: the standby's
    takeover must re-place the resident snapshot SHARDED (reconcile ->
    cache re-place seam), re-warm the sharded buckets (in-process jit
    cache makes it a cheap no-op here; a cold standby recompiles off
    the hot path), relist its watchers (the composed runtime's
    eviction broadcast), and keep double_bind_attempts at 0 through
    the handover — the elector tick, reconcile, and mesh re-placement
    all serialize on the ingest lock via ServingRuntime.gate."""
    from kubernetes_tpu.config import LeaderElectionConfig
    from kubernetes_tpu.leaderelection import InMemoryLock, LeaderElector

    lease_s = min(2.0, max(duration / 2.0, 0.5))
    le_cfg = LeaderElectionConfig(
        lease_duration_s=lease_s, renew_deadline_s=lease_s * 0.7,
        retry_period_s=lease_s * 0.15)
    truth = MiniTruth()
    lock = InMemoryLock()

    class Replica:
        def __init__(self, name):
            self.name = name
            self.rt, self.compiled, self.warm_s = build_runtime(
                n_nodes, warm_buckets, serving_cfg, mesh=mesh,
                binder=truth.binder())
            self.sched = self.rt.sched
            self.elector = LeaderElector(name, lock, le_cfg)
            self.rt.attach_elector(self.elector)
            # a couple of watchers per replica: the takeover must
            # 410-relist them, not silently splice histories
            self.watchers = [self.rt.hub.register() for _ in range(3)]
            self.stop = threading.Event()
            self.results: list = []
            self.dead = False
            self.other = None

        def on_cycle(self, res):
            self.results.append((time.monotonic(), res))
            for k in res.assignments:
                self.rt.hub.publish(("BOUND", k))
            peer = self.other
            if peer is not None and not peer.dead and res.assignments:
                for key, node in res.assignments.items():
                    ns, pname = key.split("/", 1)
                    old = make_pod(pname, namespace=ns, cpu_milli=POD_CPU,
                                   memory=POD_MEM)
                    new = make_pod(pname, namespace=ns, cpu_milli=POD_CPU,
                                   memory=POD_MEM, node_name=node)
                    peer.rt.loop.ingest(peer.sched.on_pod_update, old, new)

        def run(self):
            self.rt.loop.on_cycle = self.on_cycle
            self.rt.run(self.stop, elector=self.elector,
                        retry_period_s=le_cfg.retry_period_s)

        def kill(self):
            self.dead = True
            self.stop.set()

    a, b = Replica("a"), Replica("b")
    a.other, b.other = b, a
    assert a.elector.tick()  # 'a' is the established leader

    threads = [threading.Thread(target=r.run, daemon=True) for r in (a, b)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    kill_at = t0 + duration * kill_frac
    created = 0
    burst_s = 0.1
    next_burst = t0
    kill_t = None
    create_rate = rate / 2.0
    while True:
        now = time.monotonic()
        if now - t0 >= duration:
            break
        if kill_t is None and now >= kill_at:
            a.kill()
            kill_t = time.monotonic()
        if now < next_burst:
            time.sleep(next_burst - now)
        next_burst += burst_s
        target = int(create_rate * (min(time.monotonic(), t0 + duration)
                                    - t0))
        while created < target:
            pod_name = f"mfo-{created}"
            for r in (a, b):
                if not r.dead:
                    r.rt.loop.ingest(
                        r.sched.on_pod_add,
                        make_pod(pod_name, cpu_milli=POD_CPU,
                                 memory=POD_MEM))
            created += 1
    if kill_t is None:
        a.kill()
        kill_t = time.monotonic()
    drained = drain(b.sched, timeout_s=max(30.0, 3 * lease_s))
    wall = time.monotonic() - t0
    for r in (a, b):
        r.stop.set()
    for t in threads:
        t.join(timeout=10)

    takeover_s = None
    post_p99 = None
    post_window = [res for t, res in b.results if t > kill_t
                   and res.scheduled]
    if post_window:
        first_bind_t = min(t for t, res in b.results
                           if t > kill_t and res.scheduled)
        takeover_s = first_bind_t - kill_t
        settle = first_bind_t + max(1.0, 0.15 * duration)
        lats = [v for t, res in b.results if t >= settle
                for v in res.e2e_latency_s.values()]
        if not lats:
            lats = [v for t, res in b.results if t > kill_t
                    for v in res.e2e_latency_s.values()]
        post_p99 = round(float(np.percentile(np.asarray(lats), 99)), 4)

    # takeover onto the MESH, verified: the standby's resident table is
    # sharded across the full device set after the handover
    _, dev, _ = b.sched.cache.device_snapshot()
    standby_mesh = int(dev.allocatable.sharding.mesh.devices.size) \
        if dev is not None else 0
    return {
        "mode": "mesh_failover",
        "mesh": mesh,
        "wall_s": round(wall, 2),
        "created": created,
        "bound": len(truth.bound),
        "drained": drained,
        "lease_duration_s": lease_s,
        "kill_after_s": round(kill_t - t0, 2),
        "leader_cycles_before_kill": len(a.results),
        "standby_cycles_after_kill": len(post_window),
        "takeover_s": (round(takeover_s, 3)
                       if takeover_s is not None else None),
        "post_recovery_p99_s": post_p99,
        "double_bind_attempts": truth.double_bind_attempts,
        "takeovers": int(b.sched.metrics.recovery_takeovers.value()),
        "fenced_binds": int(
            a.sched.metrics.recovery_fenced_binds.value()
            + b.sched.metrics.recovery_fenced_binds.value()),
        "standby_resident_mesh": standby_mesh,
        "standby_retraces": b.sched.obs.jax.retrace_total(),
        # satellite evidence: the handover relisted the watchers (410 +
        # relist hint), never a silent history splice
        "watchers_evicted_on_takeover": b.rt.hub.stats()["evicted"],
        "jax": {"retraces": b.sched.obs.jax.retrace_total()},
    }


# ---------------------------------------------------------------------------
# network-chaos arm (--net-chaos): serving on the mesh under injected
# network faults — ambiguous bind timeouts (the hub may have committed),
# duplicated/reordered/dropped watch confirmations, and a mid-run relist
# storm — with the state-conservation auditor running at the configured
# low frequency inside ServingRuntime. Record family:
# benchres/churn_net_r*.json, gated by bench_compare's `netchaos` family.
# ---------------------------------------------------------------------------


class NetTruth:
    """CAS'd truth with an injected NETWORK between it and the
    scheduler: the bind RPC is :class:`chaos.AmbiguousBinder` (the ONE
    implementation of the rpc_error / commit-coin rpc_timeout dispatch
    and the double-bind-attempt meter) pointed at this thread-safe
    truth store instead of a sim hub; ``rpc:get`` rules make the
    read-your-write verification GET flaky the same way
    (chaos.raise_injected_rpc)."""

    def __init__(self, injector) -> None:
        import threading as _th

        self.injector = injector
        self.lock = _th.Lock()
        self.uids: dict = {}      # key -> uid (every created pod)
        self.bound: dict = {}     # key -> node
        self.deleted: set = set()

    def register(self, pod) -> None:
        """Admission-side registration (the producer's admit hook)."""
        with self.lock:
            self.uids[pod.key()] = getattr(pod, "uid", "")

    def delete(self, key: str) -> None:
        with self.lock:
            self.deleted.add(key)

    def binder(self):
        from kubernetes_tpu.chaos import AmbiguousBinder

        truth = self

        class _Binder(AmbiguousBinder):
            """AmbiguousBinder whose truth is the bench's dict store:
            only the commit differs — the fault dispatch, the
            commit-coin, and double_bind_attempts accounting are the
            tested chaos.py implementation."""

            def __init__(self):
                super().__init__(hub=None, injector=truth.injector)

            def _commit(self, pod, node_name):
                with truth.lock:
                    key = pod.key()
                    if key in truth.bound:
                        self.double_bind_attempts += 1
                        raise RuntimeError(
                            f"{key} already bound to {truth.bound[key]}")
                    truth.bound[key] = node_name
                    self.commits += 1

        return _Binder()

    def reader(self):
        """The scheduler's ``pod_reader`` — a GET against this truth,
        riding the same faulty network (``rpc:get``)."""
        from kubernetes_tpu.chaos import raise_injected_rpc

        truth = self

        def read(key):
            from types import SimpleNamespace

            raise_injected_rpc(truth.injector, "rpc:get")
            with truth.lock:
                if key in truth.deleted or key not in truth.uids:
                    return None
                return SimpleNamespace(uid=truth.uids[key],
                                       node_name=truth.bound.get(key, ""))

        return read

    def list_pods(self):
        """The relist source (reconcile's truth list): every live pod
        as a schedulable object, bound ones carrying their node."""
        with self.lock:
            out = []
            for key, uid in self.uids.items():
                if key in self.deleted:
                    continue
                ns, name = key.split("/", 1)
                p = make_pod(name, namespace=ns, cpu_milli=POD_CPU,
                             memory=POD_MEM,
                             node_name=self.bound.get(key, ""))
                p.uid = uid
                out.append(p)
            return out


class NetChurnProducer(MeshChurnProducer):
    """MeshChurnProducer that keeps the NetTruth registry in sync:
    creates register (the admit hook handles that), deletes mark the
    truth so the reader answers "gone" and the relist excludes them."""

    def __init__(self, *a, truth=None, **kw):
        super().__init__(*a, **kw)
        self.truth = truth

    def _delete_some(self, n: int) -> None:
        for _ in range(n):
            if not self.bound_backlog:
                return
            key, node = self.bound_backlog.pop(0)
            self.truth.delete(key)
            ns, pname = key.split("/", 1)
            gone = make_pod(pname, namespace=ns, cpu_milli=POD_CPU,
                            memory=POD_MEM, node_name=node)
            with self.lock:
                self.sched.on_pod_delete(gone)
            if self.hub is not None:
                self.hub.publish(("DELETED", key))
            self.deleted += 1


def run_net_chaos_arm(rate: float, duration: float, n_nodes: int,
                      warm_buckets, serving_cfg: ServingConfig,
                      mesh: int, bind_timeout_rate: float = 0.03,
                      bind_error_rate: float = 0.02,
                      get_timeout_rate: float = 0.05,
                      dup_rate: float = 0.08,
                      reorder_rate: float = 0.15,
                      drop_rate: float = 0.02,
                      storm_frac: float = 0.5,
                      audit_interval_s: float = 0.5) -> dict:
    """Sustained churn through the composed serving runtime (on the
    mesh) while the NETWORK misbehaves: a configured fraction of bind
    RPCs times out ambiguously (the truth may have committed — the
    read-your-write protocol must adopt, never re-bind), bind
    confirmations relay back duplicated/reordered/occasionally dropped,
    and one mid-run RELIST STORM re-delivers the whole truth at once
    (scheduler.reconcile — which also heals any dropped
    confirmations well inside the assume TTL). The ServingRuntime's
    state-conservation auditor sweeps at ``audit_interval_s``; the arm
    ends with a settled truth-mode double-audit. The acceptance bar:
    zero double-bind attempts, zero invariant violations, every created
    pod bound, zero retraces."""
    import random as _random

    from kubernetes_tpu.config import ObservabilityConfig, ParallelConfig
    from kubernetes_tpu.faults import FaultInjector
    from kubernetes_tpu.serving import ServingRuntime as _SR

    injector = FaultInjector(seed=7)
    injector.arm("rpc:bind", "rpc_timeout", rate=bind_timeout_rate)
    injector.arm("rpc:bind", "rpc_error", rate=bind_error_rate)
    injector.arm("rpc:get", "rpc_timeout", rate=get_timeout_rate)
    injector.arm("watch:event", "duplicate", rate=dup_rate)
    injector.arm("watch:event", "drop", rate=drop_rate)
    injector.arm("watch:batch", "reorder", rate=reorder_rate)
    truth = NetTruth(injector)
    binder = truth.binder()
    kw = {}
    if mesh:
        kw["parallel"] = ParallelConfig(mesh=mesh)
    sched = Scheduler(
        enable_preemption=False,
        solver="batch",
        binder=binder,
        pod_reader=truth.reader(),
        observability=ObservabilityConfig(
            audit_interval_s=audit_interval_s),
        warmup=WarmupConfig(enabled=True,
                            pod_buckets=tuple(warm_buckets)),
        **kw,
    )
    for i in range(n_nodes):
        sched.on_node_add(make_node(f"node-{i}", cpu_milli=64000,
                                    memory=256 * 2**30, pods=500))
    rt = _SR(sched, serving_cfg)
    t0w = time.monotonic()
    compiled = rt.warm_if_pending(
        sample_pods=[make_pod("warm-sample", cpu_milli=POD_CPU,
                              memory=POD_MEM)])
    warm_s = time.monotonic() - t0w
    prod = NetChurnProducer(sched, rt.loop.lock, rate, duration,
                            admit=truth.register, hub=rt.hub,
                            name="net", truth=truth)
    rng = _random.Random(7)
    dropped_confirms: list = []  # keys to heal at the relist storm

    def relay_binds(res):
        """Bind confirmations fan back as watch MODIFIEDs through the
        injected network: duplicated, reordered, occasionally dropped
        (the relist storm re-delivers the dropped ones)."""
        events = []
        for key, node in res.assignments.items():
            kind = injector.pick("watch:event")
            if kind == "drop":
                dropped_confirms.append(key)
                continue
            events.append((key, node))
            if kind == "duplicate":
                events.append((key, node))
        if len(events) > 1 and injector.pick("watch:batch") == "reorder":
            rng.shuffle(events)
        for key, node in events:
            ns, pname = key.split("/", 1)
            old = make_pod(pname, namespace=ns, cpu_milli=POD_CPU,
                           memory=POD_MEM)
            new = make_pod(pname, namespace=ns, cpu_milli=POD_CPU,
                           memory=POD_MEM, node_name=node)
            rt.loop.ingest(sched.on_pod_update, old, new)

    def on_cycle(res):
        # relay the confirmations BEFORE publishing the result to the
        # producer: on_cycle runs outside the ingest lock, so the
        # producer could otherwise learn of a bind, delete the pod, and
        # have the still-undelivered MODIFIED resurrect it — an
        # ordering a real informer stream (DELETE after MODIFIED in
        # resourceVersion order) can never produce
        relay_binds(res)
        for k in res.assignments:
            rt.hub.publish(("BOUND", k))
        prod.on_cycle(res)

    rt.loop.on_cycle = on_cycle
    stop = threading.Event()
    loop_t = threading.Thread(target=rt.loop.run, args=(stop,),
                              daemon=True)
    storms = {"count": 0}

    def relist_storm():
        """The forced-410 analog: the WHOLE truth re-delivered at once
        (reconcile = the Reflector's Replace pass), healing any dropped
        confirmations — well inside the assume TTL."""
        delay = duration * storm_frac
        if stop.wait(delay):
            return
        # list the truth AT reconcile time, under the ingest lock — a
        # snapshot taken at enqueue time goes stale against binds that
        # commit before the lock is acquired, and reconcile would
        # forget-and-requeue an already-committed bind (a double-bind
        # attempt a real relist, always freshly served, cannot cause)
        rt.loop.ingest(lambda: sched.reconcile(truth.list_pods()))
        storms["count"] += 1

    storm_t = threading.Thread(target=relist_storm, daemon=True)
    t0 = time.monotonic()
    loop_t.start()
    storm_t.start()
    prod.run()
    # settle: the fault window CLOSES (a real outage ends too). With
    # the injector disarmed, one relist resurfaces the pods the
    # ambiguity protocol sent to the unschedulable queue (its 60-second
    # leftover flush outlives the bench window) and adopts every
    # binding whose confirmation was dropped; the drain then converges
    # the rest on a now-clean network. The acceptance bar (all bound,
    # nothing leaked or parked, zero double binds, zero violations) is
    # judged on this settled state — convergence-after-faults is the
    # invariant, not convergence-despite-ongoing-faults-forever.
    injector.rules.clear()
    rt.loop.ingest(lambda: sched.reconcile(truth.list_pods()))
    drained = drain(sched, timeout_s=30.0)
    wall = time.monotonic() - t0
    stop.set()
    loop_t.join(timeout=10)
    storm_t.join(timeout=5)
    # settled truth-mode double-audit: the two-strike checks need their
    # confirming pass on a stable state
    final_violations = 0
    with rt.loop.lock:
        for _ in range(2):
            final_violations += len(rt.auditor.audit(
                sched, truth_pods=truth.list_pods()))
    out = _mesh_summary(rt, prod, wall, compiled, warm_s, mesh)
    ambiguous = binder.timeouts_committed + binder.timeouts_uncommitted
    out.update({
        "mode": "net_chaos",
        "drained": drained,
        "fault_rates": {
            "bind_timeout": bind_timeout_rate,
            "bind_error": bind_error_rate,
            "get_timeout": get_timeout_rate,
            "watch_duplicate": dup_rate,
            "watch_reorder": reorder_rate,
            "watch_drop": drop_rate,
        },
        "faults_fired": {f"{s}:{k}": n
                         for (s, k), n in injector.fired.items()},
        "ambiguous_bind_timeouts": ambiguous,
        "timeouts_committed": binder.timeouts_committed,
        "timeouts_uncommitted": binder.timeouts_uncommitted,
        "bind_rpc_errors": binder.rpc_errors,
        "ambiguous_frac_of_binds": round(
            ambiguous / max(len(truth.bound), 1), 4),
        "bind_ambiguous_resolutions": {
            r: int(sched.metrics.bind_ambiguous.value(resolution=r))
            for base in ("adopted", "requeued", "conflict", "gone",
                         "deferred")
            for r in (base, f"expired-{base}")
            if sched.metrics.bind_ambiguous.value(resolution=r)
        },
        "double_bind_attempts": binder.double_bind_attempts,
        "bound_truth": len(truth.bound),
        "created": prod.created,
        "relist_storms": storms["count"],
        "dropped_confirmations": len(dropped_confirms),
        "audits": rt.auditor.audits,
        "invariant_violations": (rt.auditor.violations_total
                                 if rt.auditor else -1),
        "violations_recent": rt.auditor.report()["recent"],
        "final_truth_audit_violations": final_violations,
        "leaked_assumptions": len(sched.cache.assumed_keys()),
        "parked_ambiguous": len(sched._ambiguous_binds),
    })
    return out


class MiniTruth:
    """The hub's Binding subresource, miniaturized for the bench: a
    CAS'd shared truth both replicas bind through. A second bind of the
    same key raises — so ``double_bind_attempts`` staying 0 across a
    leader kill IS the no-double-bind invariant, measured."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.bound: dict = {}
        self.double_bind_attempts = 0

    def binder(self):
        truth = self

        class _Binder:
            def bind(self, pod, node_name):
                with truth.lock:
                    if pod.key() in truth.bound:
                        truth.double_bind_attempts += 1
                        raise RuntimeError(
                            f"{pod.key()} already bound to "
                            f"{truth.bound[pod.key()]}")
                    truth.bound[pod.key()] = node_name

        return _Binder()


def run_failover_arm(rate: float, duration: float, n_nodes: int,
                     warm_buckets, serving_cfg: ServingConfig,
                     kill_frac: float = 0.4) -> dict:
    """Kill-the-leader mid-churn. Two serving replicas share an
    in-memory lease; both are fed every create (informer parity) and
    the leader's binds are relayed to the standby as watch MODIFIED
    events. At ``kill_frac`` of the run the leader is hard-killed (its
    loop stops; no graceful release — the worst case), the standby
    steals the lease after decay, reconciles, and finishes the queue.
    Reports takeover time (kill -> standby's first bind), post-recovery
    p99 create-to-bind, and the double-bind count from the CAS'd shared
    truth."""
    from kubernetes_tpu.config import LeaderElectionConfig
    from kubernetes_tpu.leaderelection import InMemoryLock, LeaderElector

    lease_s = min(2.0, max(duration / 2.0, 0.5))
    le_cfg = LeaderElectionConfig(
        lease_duration_s=lease_s, renew_deadline_s=lease_s * 0.7,
        retry_period_s=lease_s * 0.15)
    truth = MiniTruth()
    lock = InMemoryLock()

    class Replica:
        def __init__(self, name):
            self.name = name
            self.sched, self.compiled, self.warm_s = build_scheduler(
                n_nodes, warm_buckets, binder=truth.binder())
            self.bell = self.sched.attach_doorbell(Doorbell())
            self.elector = LeaderElector(name, lock, le_cfg)
            self.sched.attach_elector(self.elector)
            self.loop = ServingLoop(self.sched, self.bell, serving_cfg)
            self.stop = threading.Event()
            self.results: list = []  # (wall stamp, CycleResult)
            self.dead = False
            self.other = None

        def on_cycle(self, res):
            self.results.append((time.monotonic(), res))
            # relay binds to the standby — the watch MODIFIED fan-out
            # that keeps its queue from re-scheduling bound pods
            peer = self.other
            if peer is not None and not peer.dead and res.assignments:
                for key, node in res.assignments.items():
                    ns, pname = key.split("/", 1)
                    old = make_pod(pname, namespace=ns, cpu_milli=POD_CPU,
                                   memory=POD_MEM)
                    new = make_pod(pname, namespace=ns, cpu_milli=POD_CPU,
                                   memory=POD_MEM, node_name=node)
                    peer.loop.ingest(peer.sched.on_pod_update, old, new)

        def gate(self):
            # tick under the ingest lock: the acquire/depose callbacks
            # (reconcile, drain) mutate the queue/cache the producer
            # thread feeds through the same lock
            with self.loop.lock:
                leading = self.elector.tick()
            if leading:
                return True
            self.stop.wait(le_cfg.retry_period_s)
            return False

        def run(self):
            self.loop.on_cycle = self.on_cycle
            self.loop.run(self.stop, gate=self.gate)

        def kill(self):
            """Hard death: the loop stops, the lease decays on its own
            (no release — the crash case, not the SIGTERM case)."""
            self.dead = True
            self.stop.set()

    a, b = Replica("a"), Replica("b")
    a.other, b.other = b, a
    assert a.elector.tick()  # 'a' is the established leader

    threads = [threading.Thread(target=r.run, daemon=True) for r in (a, b)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    kill_at = t0 + duration * kill_frac
    created = 0
    burst_s = 0.1
    next_burst = t0
    kill_t = None
    create_rate = rate / 2.0  # ops = creates+deletes elsewhere; pure creates
    while True:
        now = time.monotonic()
        if now - t0 >= duration:
            break
        if kill_t is None and now >= kill_at:
            a.kill()
            kill_t = time.monotonic()
        if now < next_burst:
            time.sleep(next_burst - now)
        next_burst += burst_s
        target = int(create_rate * (min(time.monotonic(), t0 + duration)
                                    - t0))
        while created < target:
            pod_name = f"fo-{created}"
            for r in (a, b):
                if not r.dead:
                    r.loop.ingest(
                        r.sched.on_pod_add,
                        make_pod(pod_name, cpu_milli=POD_CPU,
                                 memory=POD_MEM))
            created += 1
    if kill_t is None:  # tiny smoke runs: kill after the paced window
        a.kill()
        kill_t = time.monotonic()
    drained = drain(b.sched, timeout_s=max(15.0, 3 * lease_s))
    wall = time.monotonic() - t0
    for r in (a, b):
        r.stop.set()
    for t in threads:
        t.join(timeout=10)

    takeover_s = None
    post_p99 = None
    post_window = [res for t, res in b.results if t > kill_t
                   and res.scheduled]
    if post_window:
        first_bind_t = min(t for t, res in b.results
                           if t > kill_t and res.scheduled)
        takeover_s = first_bind_t - kill_t
        settle = first_bind_t + max(1.0, 0.15 * duration)
        lats = [v for t, res in b.results if t >= settle
                for v in res.e2e_latency_s.values()]
        if not lats:  # smoke runs: everything bound inside the settle
            lats = [v for t, res in b.results if t > kill_t
                    for v in res.e2e_latency_s.values()]
        post_p99 = round(float(np.percentile(np.asarray(lats), 99)), 4)

    pre_lats = [v for t, res in a.results for v in res.e2e_latency_s.values()]
    return {
        "mode": "failover",
        "wall_s": round(wall, 2),
        "created": created,
        "bound": len(truth.bound),
        "drained": drained,
        "lease_duration_s": lease_s,
        "kill_after_s": round(kill_t - t0, 2),
        "leader_cycles_before_kill": len(a.results),
        "standby_cycles_after_kill": len(post_window),
        "takeover_s": (round(takeover_s, 3)
                       if takeover_s is not None else None),
        "post_recovery_p99_s": post_p99,
        "pre_kill_p99_s": (round(float(np.percentile(
            np.asarray(pre_lats), 99)), 4) if pre_lats else None),
        "double_bind_attempts": truth.double_bind_attempts,
        "takeovers": int(
            b.sched.metrics.recovery_takeovers.value()),
        "fenced_binds": int(
            a.sched.metrics.recovery_fenced_binds.value()
            + b.sched.metrics.recovery_fenced_binds.value()),
    }


def run_fixed_arm(rate: float, duration: float, n_nodes: int,
                  warm_buckets, cycle_interval: float = 0.25) -> dict:
    """The legacy baseline: cli.run's pre-serving loop verbatim — solve
    whenever the queue pops work, sleep --cycle-interval on an empty
    pop — at the same churn rate."""
    sched, compiled, warm_s = build_scheduler(n_nodes, warm_buckets)
    lock = threading.RLock()
    prod = ChurnProducer(sched, lock, rate, duration, name="fx")
    stop = threading.Event()

    def legacy_loop():
        while not stop.is_set():
            with lock:
                r = sched.schedule_cycle()
            prod.on_cycle(r)
            if r.attempted == 0:
                stop.wait(cycle_interval)

    t0 = time.monotonic()
    loop_t = threading.Thread(target=legacy_loop, daemon=True)
    loop_t.start()
    prod.run()
    drained = drain(sched)
    wall = time.monotonic() - t0
    stop.set()
    loop_t.join(timeout=10)
    out = summarize(prod, wall, sched)
    out.update({
        "mode": "fixed",
        "cycle_interval_s": cycle_interval,
        "warmup": {"compiled": compiled, "seconds": round(warm_s, 1)},
        "drained": drained,
    })
    return out


# ---------------------------------------------------------------------------
# incremental-solve sweep (--incr-sweep): the O(churn) acceptance
# evidence — steady-state cycle cost must stay FLAT as the cluster grows
# at fixed churn rate under the incremental mode, while the cold-solve
# arm grows with N; plus a seeded warm-vs-cold placement-quality
# comparison. Record family: benchres/churn_incr_r*.json, gated by
# scripts/bench_compare.py's `incremental` family.
# ---------------------------------------------------------------------------


def run_incr_cell(rate: float, duration: float, n_nodes: int,
                  warm_buckets, serving_cfg: ServingConfig,
                  incremental: bool, candidate_bucket: int = 256) -> dict:
    """One sweep cell: sustained churn through the serving loop at ONE
    cluster size, with the incremental mode on (warm) or off (cold).
    The steady-state cycle cost is the median per-cycle solve_s over
    the SECOND half of the run (the first half absorbs cache warm-in
    and scheduler ramp)."""
    from kubernetes_tpu.config import IncrementalConfig

    inc = IncrementalConfig(enabled=incremental,
                            candidate_bucket=candidate_bucket)
    sched, compiled, warm_s = build_scheduler(n_nodes, warm_buckets,
                                              incremental=inc)
    bell = sched.attach_doorbell(Doorbell())
    loop = ServingLoop(sched, bell, serving_cfg)
    prod = MeshChurnProducer(sched, loop.lock, rate, duration,
                             name="iw" if incremental else "ic")
    loop.on_cycle = prod.on_cycle
    stop = threading.Event()
    loop_t = threading.Thread(target=loop.run, args=(stop,), daemon=True)
    t0 = time.monotonic()
    loop_t.start()
    prod.run()
    drained = drain(sched)
    wall = time.monotonic() - t0
    stop.set()
    loop_t.join(timeout=10)
    out = summarize(prod, wall, sched)
    solved = [r for r in prod.results if r.solve_scope]
    tail = solved[len(solved) // 2:]
    restricted = [r for r in solved if r.solve_scope == "restricted"]
    bound = max(out["bound"], 1)
    out.update({
        "mode": "incr_warm" if incremental else "incr_cold",
        "nodes": n_nodes,
        "drained": drained,
        "warmup": {"compiled": compiled, "seconds": round(warm_s, 1)},
        "solve_cycles": len(solved),
        "restricted_frac": round(len(restricted) / max(len(solved), 1), 3),
        "reuse_frac_mean": round(
            float(np.mean([r.reuse_frac for r in restricted]))
            if restricted else 0.0, 4),
        # the flatness basis: steady-state MEDIAN per-cycle solve cost
        # over the second half of the run (median, not mean — shared
        # bench hosts throw multi-ms scheduling noise at individual
        # cycles and a handful of outliers must not fake growth)
        "steady_mean_solve_s": round(
            float(np.median([r.solve_s for r in tail]))
            if tail else 0.0, 6),
        "steady_mean_cycle_s": round(
            float(np.median([r.elapsed_s for r in tail]))
            if tail else 0.0, 6),
        "readback_bytes_per_pod": round(
            sched.obs.jax.d2h_bytes_total() / bound, 2),
        "snapshot_modes": dict(prod.snapshot_modes),
    })
    return out


def _lean_quality(sched, assignments) -> float:
    """Mean generic lean score (free-capacity fractions, the stock
    LeastRequested shape) of the chosen nodes at bind time — the
    warm-vs-cold quality basis. Host-side, from the cache's node
    objects (no device work)."""
    scores = []
    for _key, node_name in assignments:
        nd = sched.cache.node(node_name)
        if nd is None:
            continue
        used_cpu = sum(p.effective_requests().cpu_milli
                       for p in sched.cache.pods_on(node_name))
        used_mem = sum(p.effective_requests().memory
                       for p in sched.cache.pods_on(node_name))
        r = nd.allocatable
        cf = max(0.0, (r.cpu_milli - used_cpu)) / max(r.cpu_milli, 1e-9)
        mf = max(0.0, (r.memory - used_mem)) / max(r.memory, 1e-9)
        scores.append(0.5 * (cf + mf))
    return float(np.mean(scores)) if scores else 0.0


def run_incr_quality(n_nodes: int, warm_buckets, seeds=(1, 2, 3),
                     batch: int = 48, preload_frac: float = 0.3,
                     candidate_bucket: int = 256,
                     inc_kwargs=None) -> dict:
    """Seeded warm-vs-cold placement comparison: identical pre-loaded
    clusters and identical pod batches solved by an incremental and a
    cold scheduler. The restricted solve must place EVERY pod the cold
    solve places (under-placement falls back to cold by construction —
    this pins it), and the mean lean quality of its choices must stay
    within the documented delta. ``restricted_engaged`` reports whether
    the warm arm's steady cycles actually ran restricted — a quality
    pass where the warm arm silently solved cold would be vacuous."""
    import random

    from kubernetes_tpu.config import IncrementalConfig

    deltas = []
    placed_equal = True
    restricted_engaged = True
    for seed in seeds:
        pair = []
        for incremental in (True, False):
            inc = IncrementalConfig(enabled=incremental,
                                    candidate_bucket=candidate_bucket,
                                    **((inc_kwargs or {})
                                       if incremental else {}))
            sched, _c, _w = build_scheduler(n_nodes, warm_buckets,
                                            incremental=inc)
            # heterogeneous pre-load so candidate ranking has real work
            rng2 = random.Random(seed)
            for i in range(int(n_nodes * preload_frac)):
                node = f"node-{rng2.randrange(n_nodes)}"
                sched.cache.add_pod(make_pod(
                    f"pre-{seed}-{i}", node_name=node,
                    cpu_milli=rng2.choice([500, 2000, 8000]),
                    memory=rng2.choice([1, 4, 16]) * 2**30))
            for i in range(batch):
                sched.on_pod_add(make_pod(
                    f"q-{seed}-{i}",
                    cpu_milli=rng2.choice([100, 250, 500]),
                    memory=rng2.choice([128, 256, 512]) * 2**20))
            # first cycle is a full snapshot (cold); churn one pod so the
            # second cycle runs delta → restricted under the warm arm
            r1 = sched.schedule_cycle()
            sched.on_pod_add(make_pod(f"q2-{seed}",
                                      cpu_milli=100, memory=128 * 2**20))
            r2 = sched.schedule_cycle()
            assigns = list(r1.assignments.items()) \
                + list(r2.assignments.items())
            pair.append({
                "placed": r1.scheduled + r2.scheduled,
                "scopes": [r1.solve_scope, r2.solve_scope],
                "quality": _lean_quality(sched, assigns),
            })
        warm_cell, cold_cell = pair
        if warm_cell["placed"] != cold_cell["placed"]:
            placed_equal = False
        if warm_cell["scopes"][1] != "restricted":
            restricted_engaged = False
        base = max(cold_cell["quality"], 1e-9)
        deltas.append((cold_cell["quality"] - warm_cell["quality"]) / base)
    return {
        "seeds": list(seeds),
        "batch": batch,
        "placed_equal": placed_equal,
        "restricted_engaged": restricted_engaged,
        "score_delta_frac_max": round(max(deltas), 4),
        "score_delta_frac_mean": round(float(np.mean(deltas)), 4),
    }


def run_incr_sweep(args, warm_buckets, serving_cfg: ServingConfig) -> int:
    """The --incr-sweep record: warm (incremental) and cold cells at
    each cluster size, flatness ratios, the seeded quality comparison,
    and the acceptance criteria."""
    from kubernetes_tpu.config import IncrementalConfig

    sizes = [int(s) for s in str(args.incr_sizes).split(",") if s]
    smoke = bool(getattr(args, "smoke", False))
    # smoke cells are seconds-long on tiny clusters: the harness is
    # what's under test, not the flatness claim — shrink the candidate
    # bucket so the restricted route still engages
    cand = 32 if smoke else IncrementalConfig().candidate_bucket
    record = {
        "name": "churn_incr",
        "rate_ops_s": args.incr_rate,
        "duration_s": args.incr_duration,
        "sizes": sizes,
        "smoke": smoke,
        "warm_buckets": list(warm_buckets),
        "candidate_bucket": cand,
        "quality_bound": IncrementalConfig().quality_delta,
        "platform": {"python": sys.version.split()[0]},
        "cells": {},
        "errors": [],
    }
    try:
        import jax

        record["platform"]["jax_backend"] = jax.default_backend()
        record["platform"]["devices"] = len(jax.devices())
        record["platform"]["device_kind"] = jax.devices()[0].device_kind
    except Exception:
        pass
    for n in sizes:
        for incremental in (True, False):
            label = f"{'warm' if incremental else 'cold'}_{n}"
            print(f"  cell {label}...", file=sys.stderr)
            try:
                cell = run_incr_cell(args.incr_rate, args.incr_duration,
                                     n, warm_buckets, serving_cfg,
                                     incremental,
                                     candidate_bucket=cand)
                record["cells"][label] = cell
                print(f"    solve={cell['steady_mean_solve_s']*1e3:.2f}ms"
                      f"/cycle restricted={cell['restricted_frac']}"
                      f" retraces={cell['jax'].get('retraces')}",
                      file=sys.stderr)
            except Exception as e:
                import traceback

                traceback.print_exc()
                record["errors"].append(f"{label}: {e!r}")
    print("  quality (warm vs cold, seeded)...", file=sys.stderr)
    try:
        # the quality cluster must EXCEED the candidate bucket — and
        # the batch must fit the restricted gate (≤ maxBatchFrac·C) —
        # or the warm arm silently solves cold and the comparison is
        # vacuous (restricted_engaged pins it either way)
        record["quality"] = run_incr_quality(
            max(min(sizes), 2 * cand), warm_buckets,
            batch=min(48, max(8, (2 * cand) // 5)),
            candidate_bucket=cand)
    except Exception as e:
        import traceback

        traceback.print_exc()
        record["errors"].append(f"quality: {e!r}")

    def growth(kind: str):
        lo = record["cells"].get(f"{kind}_{sizes[0]}") or {}
        hi = record["cells"].get(f"{kind}_{sizes[-1]}") or {}
        a = lo.get("steady_mean_solve_s") or 0.0
        b = hi.get("steady_mean_solve_s") or 0.0
        return round(b / a, 3) if a > 0 else None

    record["flatness"] = {
        "basis": "steady_mean_solve_s (median of second-half cycles)",
        "size_ratio": round(sizes[-1] / max(sizes[0], 1), 1),
        "warm_growth": growth("warm"),
        "cold_growth": growth("cold"),
    }
    cells = record["cells"]
    q = record.get("quality") or {}
    warm_cells = [v for k, v in cells.items() if k.startswith("warm_")]
    record["criteria"] = {
        # the tentpole claim: incremental steady-state cycle cost flat
        # (≤ 1.3x) across a ≥4x cluster-size sweep at fixed churn rate.
        # Seconds-long smoke cells are pure scheduling noise — smoke
        # validates the harness (engagement/retraces/readback/quality),
        # the full run validates the flatness claim.
        "incr_flat_ok": bool(smoke or (
            record["flatness"]["warm_growth"] is not None
            and record["flatness"]["warm_growth"] <= 1.3)),
        # ...while the cold solve's cost visibly grows with N
        "cold_grows_ok": bool(smoke or (
            record["flatness"]["cold_growth"] is not None
            and record["flatness"]["warm_growth"] is not None
            and record["flatness"]["cold_growth"]
            > record["flatness"]["warm_growth"] + 0.2)),
        # restricted cycles actually carried the warm arms (no silent
        # cold fallback pretending to be incremental)
        "restricted_engaged_ok": bool(
            warm_cells
            and all(c.get("restricted_frac", 0) >= 0.8
                    for c in warm_cells)),
        # retraces_total covers EVERY recorded site (the restricted
        # path registers 'incremental' alongside 'solve' — a retrace
        # there must fail the gate too)
        "zero_retraces_ok": bool(
            cells
            and all(c.get("retraces_total",
                          c.get("jax", {}).get("retraces", 1)) == 0
                    for c in cells.values())),
        "readback_budget_ok": bool(
            cells
            and all(0 < c.get("readback_bytes_per_pod", 1e9) <= 16.0
                    for c in cells.values())),
        "quality_ok": bool(
            q.get("placed_equal")
            and q.get("restricted_engaged")
            and q.get("score_delta_frac_max") is not None
            and q["score_delta_frac_max"] <= record["quality_bound"]),
        "drained_ok": bool(
            cells and all(c.get("drained") for c in cells.values())),
    }
    _write_record(record, args.out)
    print(json.dumps({"flatness": record["flatness"],
                      "criteria": record["criteria"]}, indent=1))
    ok = all(record["criteria"].values()) and not record["errors"]
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# sparsity-first sweep (--sparse-sweep): the ISSUE-20 acceptance
# evidence — restricted-primary vs dense-primary cells at 2048 -> 50k
# nodes. Per size and arm: one COLD double-batch probe (sparse arm must
# route PARTITIONED — capacity-balanced restricted frames, cost
# sublinear in N vs the dense oracle's slope) followed by sustained
# churn (steady cycles must stay flat and ride restricted/partitioned
# >= 90% under the sparse arm). Record family:
# benchres/churn_sparse_r*.json, gated by scripts/bench_compare.py's
# `sparse` family.
# ---------------------------------------------------------------------------


def _sparse_inc(primary: bool, candidate_bucket: int):
    """The two sweep arms' IncrementalConfigs: sparsity-first PRIMARY
    (restricted warm route + partitioned cold route + candidate-bucket
    auto-tuning) vs the dense-primary baseline (incremental off — every
    cycle solves the full plane)."""
    from kubernetes_tpu.config import IncrementalConfig

    if not primary:
        return IncrementalConfig(enabled=False)
    return IncrementalConfig(enabled=True, primary=True, auto_tune=True,
                             candidate_bucket=candidate_bucket)


def _sparse_cold_probe(sched, batch: int, tag: str, n_nodes: int) -> dict:
    """Two genuinely COLD cycles through one warmed scheduler: before
    each probe a quiet node is deleted, forcing the full-snapshot
    rebuild that kills every warm caryover — exactly the cold-start
    shape the partitioned route exists for (the steady-state restricted
    route correctly declines a full rebuild; an oversized batch would
    instead be absorbed by the candidate auto-tuner widening C). The
    node bucket is a power of two and the sweep sizes are at/below
    bucket boundaries, so a delete never changes ``n_pad`` — no new
    solve shapes, no retraces. The pair evidences route stability
    (both probes must take the same scope under the sparse arm:
    partitioned).

    ``route_s`` is the cycle's ``solve:*`` span time from the flight
    record — the ROUTE's own cost (block deal + frame solves for
    partitioned, the (P, N) plane for dense). ``solve_s`` (the whole
    solve trace) is kept for reference but is dominated at 50k by the
    full-snapshot rebuild both arms pay identically, which would bury
    the route comparison the cold-slope gate makes."""
    probes = []
    route_s = []
    used = set()
    victim = n_nodes - 1
    for round_i in range(2):
        while f"node-{victim}" in used and victim > 0:
            victim -= 1
        sched.on_node_delete(f"node-{victim}")
        victim -= 1
        for i in range(batch):
            sched.on_pod_add(make_pod(f"{tag}-cold{round_i}-{i}",
                                      cpu_milli=POD_CPU, memory=POD_MEM))
        r = sched.schedule_cycle()
        probes.append(r)
        rec = sched.obs.recorder.records()[-1]
        route_s.append(sum(v for k, v in rec.spans.items()
                           if k.startswith("solve:")) or r.solve_s)
        used.update(r.assignments.values())
    return {
        "batch": batch,
        "scheduled": int(sum(r.scheduled for r in probes)),
        "scopes": [r.solve_scope for r in probes],
        "cold_blocks": [r.cold_blocks for r in probes],
        "solve_s": [round(r.solve_s, 6) for r in probes],
        "route_s": [round(t, 6) for t in route_s],
        # min of the two: the route's cost with upload noise excluded
        "best_solve_s": round(min(r.solve_s for r in probes), 6),
        "best_route_s": round(min(route_s), 6),
    }


def run_sparse_size(rate: float, duration: float, n_nodes: int,
                    warm_buckets, serving_cfg: ServingConfig,
                    primary: bool, candidate_bucket: int,
                    cold_batch: int = 64):
    """One (size, arm) pair: build + warm ONCE, probe the cold route,
    then run sustained churn through the serving loop on the same
    scheduler. Returns (cold_probe, churn_cell)."""
    inc = _sparse_inc(primary, candidate_bucket)
    sched, compiled, warm_s = build_scheduler(n_nodes, warm_buckets,
                                              incremental=inc)
    arm = "sparse" if primary else "dense"
    cold = _sparse_cold_probe(sched, cold_batch, f"{arm}{n_nodes}",
                              n_nodes)
    cold.update({"mode": f"{arm}_cold", "nodes": n_nodes})
    bell = sched.attach_doorbell(Doorbell())
    loop = ServingLoop(sched, bell, serving_cfg)
    prod = MeshChurnProducer(sched, loop.lock, rate, duration,
                             name="sp" if primary else "sd")
    loop.on_cycle = prod.on_cycle
    stop = threading.Event()
    loop_t = threading.Thread(target=loop.run, args=(stop,), daemon=True)
    t0 = time.monotonic()
    loop_t.start()
    prod.run()
    drained = drain(sched)
    wall = time.monotonic() - t0
    stop.set()
    loop_t.join(timeout=10)
    out = summarize(prod, wall, sched)
    solved = [r for r in prod.results if r.solve_scope]
    tail = solved[len(solved) // 2:]
    # engagement counts BOTH sparsity-first scopes: steady micro-batches
    # ride restricted, cold/ineligible-warm cycles ride partitioned —
    # only a fall-through to the dense oracle counts against the arm
    engaged = [r for r in solved
               if r.solve_scope in ("restricted", "partitioned")]
    bound = max(out["bound"], 1)
    out.update({
        "mode": f"{arm}_primary",
        "nodes": n_nodes,
        "drained": drained,
        "warmup": {"compiled": compiled, "seconds": round(warm_s, 1)},
        "solve_cycles": len(solved),
        "restricted_frac": round(len(engaged) / max(len(solved), 1), 3),
        "partitioned_cycles": int(sum(
            1 for r in solved if r.solve_scope == "partitioned")),
        "steady_mean_solve_s": round(
            float(np.median([r.solve_s for r in tail]))
            if tail else 0.0, 6),
        # the flatness basis: the ROUTE's own per-cycle cost (the
        # cycle's solve:* span from the flight record — restricted /
        # partitioned / batch), median over the second half of the
        # ring. r.solve_s is the whole cycle trace, which at 50k is
        # dominated by the O(N) delta-snapshot patch BOTH arms pay
        # identically (ledger snapshot share ~0.74) — on that basis
        # both arms "grow" ~2x with N and the route comparison the
        # sparse_flat gate makes is buried, exactly the contamination
        # the cold probe's best_route_s already excludes.
        "steady_route_s": _steady_route_s(sched),
        "readback_bytes_per_pod": round(
            sched.obs.jax.d2h_bytes_total() / bound, 2),
        "snapshot_modes": dict(prod.snapshot_modes),
    })
    cold["retraces_total"] = out["retraces_total"]
    return cold, out


def _steady_route_s(sched) -> float:
    """Median per-cycle ``solve:*`` span over the second half of the
    flight-record ring (capacity 256 >= the sweep's ~152 cycles, so the
    tail half is pure steady-state churn)."""
    route = [sum(v for k, v in rec.spans.items()
                 if k.startswith("solve:"))
             for rec in sched.obs.recorder.records()
             if any(k.startswith("solve:") for k in rec.spans)]
    tail = route[len(route) // 2:]
    return round(float(np.median(tail)) if tail else 0.0, 6)


def run_sparse_sweep(args, warm_buckets,
                     serving_cfg: ServingConfig) -> int:
    """The --sparse-sweep record: sparse (restricted-primary) and dense
    (dense-primary) cells at each cluster size, cold-route slope
    comparison, flatness ratios, the seeded quality comparison, and the
    acceptance criteria the bench_compare `sparse` family gates."""
    from kubernetes_tpu.config import IncrementalConfig

    sizes = [int(s) for s in str(args.sparse_sizes).split(",") if s]
    smoke = bool(getattr(args, "smoke", False))
    cand = 32 if smoke else IncrementalConfig().candidate_bucket
    record = {
        "name": "churn_sparse",
        "rate_ops_s": args.sparse_rate,
        "duration_s": args.sparse_duration,
        "sizes": sizes,
        "smoke": smoke,
        "warm_buckets": list(warm_buckets),
        "candidate_bucket": cand,
        "cold_batch": args.sparse_cold_batch,
        "quality_bound": IncrementalConfig().quality_delta,
        "platform": {"python": sys.version.split()[0]},
        "cells": {},
        "cold": {},
        "errors": [],
    }
    try:
        import jax

        record["platform"]["jax_backend"] = jax.default_backend()
        record["platform"]["devices"] = len(jax.devices())
        record["platform"]["device_kind"] = jax.devices()[0].device_kind
    except Exception:
        pass
    for n in sizes:
        for primary in (True, False):
            label = f"{'sparse' if primary else 'dense'}_{n}"
            print(f"  cell {label}...", file=sys.stderr)
            try:
                cold, cell = run_sparse_size(
                    args.sparse_rate, args.sparse_duration, n,
                    warm_buckets, serving_cfg, primary, cand,
                    cold_batch=args.sparse_cold_batch)
                record["cold"][label] = cold
                record["cells"][label] = cell
                print(f"    cold={cold['best_route_s']*1e3:.2f}ms route "
                      f"({'/'.join(map(str, cold['scopes']))}) steady="
                      f"{cell['steady_route_s']*1e3:.2f}ms route/cycle "
                      f"engaged={cell['restricted_frac']} "
                      f"retraces={cell['retraces_total']}",
                      file=sys.stderr)
            except Exception as e:
                import traceback

                traceback.print_exc()
                record["errors"].append(f"{label}: {e!r}")
    print("  quality (sparse vs dense, seeded)...", file=sys.stderr)
    try:
        record["quality"] = run_incr_quality(
            max(min(sizes), 2 * cand), warm_buckets,
            batch=min(48, max(8, (2 * cand) // 5)),
            candidate_bucket=cand,
            inc_kwargs={"primary": True, "auto_tune": True})
    except Exception as e:
        import traceback

        traceback.print_exc()
        record["errors"].append(f"quality: {e!r}")

    def growth(kind: str):
        # route-span basis (see _steady_route_s); steady_mean_solve_s
        # fallback keeps older records comparable
        lo = record["cells"].get(f"{kind}_{sizes[0]}") or {}
        hi = record["cells"].get(f"{kind}_{sizes[-1]}") or {}
        a = lo.get("steady_route_s") or lo.get("steady_mean_solve_s") or 0.0
        b = hi.get("steady_route_s") or hi.get("steady_mean_solve_s") or 0.0
        return round(b / a, 3) if a > 0 else None

    def cold_slope(kind: str):
        lo = record["cold"].get(f"{kind}_{sizes[0]}") or {}
        hi = record["cold"].get(f"{kind}_{sizes[-1]}") or {}
        a = lo.get("best_route_s", lo.get("best_solve_s"))
        b = hi.get("best_route_s", hi.get("best_solve_s"))
        if a is None or b is None:
            return None
        return (b - a) / max(sizes[-1] - sizes[0], 1)
    s_slope, d_slope = cold_slope("sparse"), cold_slope("dense")
    record["flatness"] = {
        "basis": ("steady_route_s (median solve:* span, second-half "
                  "cycles)"),
        "size_ratio": round(sizes[-1] / max(sizes[0], 1), 1),
        "sparse_growth": growth("sparse"),
        "dense_growth": growth("dense"),
    }
    record["cold_slope"] = {
        "basis": ("best_route_s cold probe (solve:* span), "
                  "(t_hi - t_lo) / (N_hi - N_lo)"),
        "sparse_s_per_node": s_slope,
        "dense_s_per_node": d_slope,
        "ratio": (round(s_slope / d_slope, 3)
                  if s_slope is not None and d_slope and d_slope > 0
                  else None),
    }
    cells = record["cells"]
    q = record.get("quality") or {}
    sparse_cells = [v for k, v in cells.items()
                    if k.startswith("sparse_")]
    sparse_cold = [v for k, v in record["cold"].items()
                   if k.startswith("sparse_")]
    record["criteria"] = {
        # the tentpole claim, arm 1: sparse steady-state cycle cost
        # flat (<= 1.3x) across the sweep at fixed churn rate. Smoke
        # cells are seconds-long scheduling noise — smoke validates the
        # harness, the full run validates the flatness claim.
        "sparse_flat_ok": bool(smoke or (
            record["flatness"]["sparse_growth"] is not None
            and record["flatness"]["sparse_growth"] <= 1.3)),
        # the tentpole claim, arm 2: the PARTITIONED cold route's cost
        # grows sublinearly vs the dense oracle (slope ratio <= 0.6)
        "sparse_cold_sublinear_ok": bool(smoke or (
            record["cold_slope"]["ratio"] is not None
            and record["cold_slope"]["ratio"] <= 0.6)),
        # the sparse arm actually RODE the sparsity-first routes: >= 90%
        # of churn cycles restricted/partitioned AND every cold probe
        # took the partitioned route (not a silent dense fall-through)
        "sparse_engaged_ok": bool(
            sparse_cells
            and all(c.get("restricted_frac", 0) >= 0.9
                    for c in sparse_cells)
            and sparse_cold
            and all(s == "partitioned"
                    for c in sparse_cold for s in c.get("scopes", []))),
        # zero retraces across every cell — the warmed C ladder, the
        # hint/quota variants, and the partition signatures all held
        "sparse_zero_retraces_ok": bool(
            cells and all(c.get("retraces_total", 1) == 0
                          for c in cells.values())),
        # d2h stays answer-sized on the sparse arm: assignment vector +
        # scalars (rounds/depth/code) only — <= 12 B per bound pod
        # (one int32 per pod plus per-cycle fixed scalars amortized
        # over the cycle's batch; tighter than the 16-byte mesh
        # budget). The smoke run's seconds-long window is dominated by
        # drain-tail cycles whose fixed scalars amortize over a
        # handful of pods; the absolute bar holds on the full record.
        "sparse_readback_ok": bool(smoke or (
            sparse_cells
            and all(0 < c.get("readback_bytes_per_pod", 1e9) <= 12.0
                    for c in sparse_cells))),
        "sparse_quality_ok": bool(
            q.get("placed_equal")
            and q.get("restricted_engaged")
            and q.get("score_delta_frac_max") is not None
            and q["score_delta_frac_max"] <= record["quality_bound"]),
        "sparse_drained_ok": bool(
            cells and all(c.get("drained") for c in cells.values())),
    }
    _write_record(record, args.out)
    print(json.dumps({"flatness": record["flatness"],
                      "cold_slope": record["cold_slope"],
                      "criteria": record["criteria"]}, indent=1))
    ok = all(record["criteria"].values()) and not record["errors"]
    return 0 if ok else 1


def _write_record(record: dict, out_path: str) -> None:
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w", encoding="utf-8") as fh:
        json.dump(record, fh, indent=1, sort_keys=True)
        fh.write("\n")
    print(f"wrote {out_path}", file=sys.stderr)


def finish_net_record(record: dict, args) -> int:
    """Criteria + write for the --net-chaos record (the network-fault
    acceptance, ISSUE 15): faults demonstrably injected (ambiguous
    timeouts on >= 1% of binds, watch duplicates AND reorders fired,
    exactly one mid-run relist storm), yet zero bind RPCs reached the
    truth for an already-bound pod, zero state-conservation violations
    (runtime sweeps AND the settled truth-mode double-audit), every
    created pod bound, nothing leaked or parked, zero retraces, and
    the p99 create-to-bind still bounded under the fault load."""
    nc = record["arms"].get("net_chaos") or {}
    record["criteria"] = {
        "net_no_double_binds": bool(
            nc.get("double_bind_attempts", 1) == 0),
        "net_zero_invariant_violations": bool(
            nc.get("invariant_violations", 1) == 0
            and nc.get("final_truth_audit_violations", 1) == 0
            and nc.get("audits", 0) > 0),
        "net_all_bound": bool(
            nc.get("drained")
            and nc.get("bound_truth", -1) == nc.get("created", -2)
            and nc.get("leaked_assumptions", 1) == 0
            and nc.get("parked_ambiguous", 1) == 0),
        "net_ambiguous_rate_ok": bool(
            nc.get("ambiguous_frac_of_binds", 0) >= 0.01),
        "net_watch_fuzz_ok": bool(
            nc.get("faults_fired", {}).get("watch:event:duplicate", 0) > 0
            and nc.get("faults_fired", {}).get("watch:batch:reorder", 0)
            > 0),
        "net_relist_storm_ok": bool(nc.get("relist_storms", 0) >= 1),
        "net_zero_retraces_ok": bool(
            nc.get("retraces_total",
                   nc.get("jax", {}).get("retraces", 1)) == 0),
        "net_p99_bounded_ok": bool(nc.get("p99_s", 1e9) < 2.0),
    }
    _write_record(record, args.out)
    print(json.dumps(record["criteria"], indent=1))
    ok = all(record["criteria"].values()) and not record["errors"]
    return 0 if ok else 1


def finish_mesh_record(record: dict, args) -> int:
    """Criteria + write for the --mesh arm family (the composed
    serving-on-mesh acceptance): sustained rate held at the 5000-node
    shape, p99 bounded, zero post-warmup retraces EVERYWHERE (the
    shard-loss arm's host-mode cycles included — that is what the
    host-fallback warmup buys), takeover ~ lease decay with the
    standby's resident table sharded across the full mesh and zero
    double binds, the lost shard healing back to sharded without
    stalling the doorbell loop, readback inside the answer-sized
    budget, and the watcher fleet served with only the stuck watchers
    evicted."""
    sv = record["arms"].get("serving") or {}
    fo = record["arms"].get("failover") or {}
    sl = record["arms"].get("shard_loss") or {}
    lease = fo.get("lease_duration_s", 2.0) or 2.0
    cooloff = sl.get("cooloff_s", 2.0) or 2.0
    record["criteria"] = {
        "mesh_sustained_rate_ok": bool(
            sv.get("ops_per_sec", 0) >= record["rate_ops_s"] * 0.9
            and sv.get("drained")),
        "mesh_p99_bounded_ok": bool(sv.get("p99_s", 1e9) < 2.0),
        "mesh_zero_retraces_ok": bool(
            sv.get("jax", {}).get("retraces", 1) == 0
            and fo.get("jax", {}).get("retraces", 1) == 0
            and sl.get("jax", {}).get("retraces", 1) == 0),
        "mesh_readback_ok": bool(
            0 < sv.get("readback_bytes_per_pod", 1e9) <= 16.0
            and 0 < sl.get("readback_bytes_per_pod", 1e9) <= 16.0),
        "mesh_watchers_ok": bool(
            sv.get("watch", {}).get("watchers", 0) >= args.watchers
            and sv.get("watch_stuck_evicted", 0) > 0),
        "mesh_takeover_ok": bool(
            fo.get("takeover_s") is not None
            and fo["takeover_s"] < 3 * lease + 2.0),
        "mesh_no_double_binds": bool(
            fo.get("double_bind_attempts", 1) == 0),
        "mesh_failover_drained_ok": bool(
            fo.get("drained") and fo.get("bound") == fo.get("created")),
        "mesh_takeover_sharded_ok": bool(
            fo.get("standby_resident_mesh", 0) == record["mesh"]),
        "mesh_shard_healed_ok": bool(
            sl.get("healed_sharded") and sl.get("drained")),
        "mesh_doorbell_no_stall_ok": bool(
            0 < sl.get("doorbell_max_gap_s", 1e9) < cooloff + 3.0),
    }
    _write_record(record, args.out)
    print(json.dumps(record["criteria"], indent=1))
    ok = all(record["criteria"].values()) and not record["errors"]
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rate", type=float, default=None,
                    help="target creates+deletes per second (default "
                         "500; 300 with --mesh — the 8-virtual-device "
                         "CPU mesh timeshares one socket)")
    ap.add_argument("--duration", type=float, default=65.0,
                    help="seconds of sustained churn per arm (default 65)")
    ap.add_argument("--overload-factor", type=float, default=4.0)
    ap.add_argument("--overload-duration", type=float, default=25.0)
    ap.add_argument("--failover-duration", type=float, default=30.0,
                    help="kill-the-leader arm length (leader dies at "
                         "40%% of it)")
    ap.add_argument("--mesh", type=int, default=0,
                    help="composed serving-on-mesh arm family: run the "
                         "mesh_serving / mesh_failover / mesh_shard_loss "
                         "arms on an N-device node-axis mesh (default "
                         "nodes become 5000, out becomes "
                         "churn_mesh_r01.json)")
    ap.add_argument("--watchers", type=int, default=2000,
                    help="WatchHub watchers registered in the "
                         "mesh_serving arm (default 2000)")
    ap.add_argument("--shard-loss-duration", type=float, default=30.0,
                    help="kill-one-shard arm length (the shard dies at "
                         "40%% of it)")
    ap.add_argument("--nodes", type=int, default=None,
                    help="cluster size (default 64; 5000 with --mesh — "
                         "the paper's scheduler_perf node count)")
    ap.add_argument("--max-wait", type=float, default=None,
                    help="micro-batch window ceiling (default 20ms; "
                         "50ms with --mesh)")
    ap.add_argument("--cycle-interval", type=float, default=0.25,
                    help="the fixed arm's idle sleep (the legacy default)")
    ap.add_argument("--net-chaos", action="store_true",
                    help="network-chaos arm: serving on the mesh under "
                         "ambiguous bind timeouts, fuzzed watch "
                         "confirmations, and a mid-run relist storm, "
                         "with the state-conservation auditor sweeping "
                         "(record family churn_net_r*.json)")
    ap.add_argument("--net-bind-timeout-rate", type=float, default=0.03,
                    help="fraction of bind RPCs that time out "
                         "ambiguously (the ISSUE bar is >= 0.01)")
    ap.add_argument("--sparse-sweep", action="store_true",
                    help="sparsity-first sweep: restricted-primary vs "
                         "dense-primary cells (cold partitioned probe + "
                         "sustained churn) at each cluster size (record "
                         "family churn_sparse_r*.json)")
    ap.add_argument("--sparse-sizes", default="2048,8192,50000",
                    help="comma-separated cluster sizes for "
                         "--sparse-sweep (first and last anchor the "
                         "flatness and cold-slope ratios)")
    ap.add_argument("--sparse-rate", type=float, default=200.0,
                    help="fixed churn rate (ops/s) per --sparse-sweep "
                         "cell")
    ap.add_argument("--sparse-duration", type=float, default=15.0,
                    help="seconds of sustained churn per --sparse-sweep "
                         "cell")
    ap.add_argument("--sparse-cold-batch", type=int, default=64,
                    help="cold-probe batch size per --sparse-sweep cell "
                         "(pads to a warmed pod bucket; the probe takes "
                         "the PARTITIONED route because it forces a "
                         "full-snapshot rebuild first, not because of "
                         "its size)")
    ap.add_argument("--incr-sweep", action="store_true",
                    help="incremental-solve cluster-size sweep: warm "
                         "(incremental) vs cold cells at each size, "
                         "flatness ratios + seeded quality comparison "
                         "(record family churn_incr_r*.json)")
    ap.add_argument("--incr-sizes", default="1024,4096",
                    help="comma-separated cluster sizes for --incr-sweep "
                         "(first and last anchor the flatness ratio)")
    ap.add_argument("--incr-rate", type=float, default=200.0,
                    help="fixed churn rate (ops/s) per --incr-sweep cell")
    ap.add_argument("--incr-duration", type=float, default=20.0,
                    help="seconds of sustained churn per --incr-sweep "
                         "cell")
    ap.add_argument("--smoke", action="store_true",
                    help="~6 s sanity run (2 s arms, tiny buckets)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    if args.net_chaos and args.mesh == 0:
        args.mesh = 2  # "serving on the mesh" — light 2-way default
    if args.nodes is None:
        args.nodes = (512 if args.net_chaos
                      else 5000 if args.mesh else 64)
    if args.rate is None:
        args.rate = (200.0 if args.net_chaos
                     else 300.0 if args.mesh else 500.0)
    if args.max_wait is None:
        args.max_wait = 0.05 if args.mesh else 0.02
    if args.out is None:
        args.out = os.path.join(
            REPO_ROOT, "benchres",
            "churn_net_r01.json" if args.net_chaos
            else "churn_sparse_r01.json" if args.sparse_sweep
            else "churn_incr_r01.json" if args.incr_sweep
            else "churn_mesh_r01.json" if args.mesh
            else "churn_r01.json")
    if args.smoke:
        args.duration = 2.0
        args.overload_duration = 2.0
        args.failover_duration = 4.0
        args.shard_loss_duration = 4.0
        args.rate = min(args.rate, 200.0)
        args.nodes = min(args.nodes, 64 if args.mesh else 8)
        args.watchers = min(args.watchers, 50)
        args.incr_duration = 3.0
        args.incr_sizes = "64,256"
        args.sparse_duration = 3.0
        args.sparse_sizes = "256,1024"
        args.sparse_cold_batch = 12
    if args.incr_sweep or args.sparse_sweep:
        # bucket 4 included: micro-batch tails pad down to it, and an
        # unwarmed solver bucket compiling mid-churn is exactly the p99
        # spike the warmup contract forbids
        warm_buckets = (4, 8, 16, 32, 64) if not args.smoke else (4, 8, 16)
        serving_cfg = ServingConfig(
            enabled=True, min_wait_s=0.002, max_wait_s=args.max_wait,
            target_bucket=max(warm_buckets), idle_wait_s=0.1)
        if args.sparse_sweep:
            print(f"sparsity-first sweep: {args.sparse_rate:.0f} ops/s "
                  f"x {args.sparse_duration:.0f}s per cell, sizes "
                  f"{args.sparse_sizes}", file=sys.stderr)
            return run_sparse_sweep(args, warm_buckets, serving_cfg)
        print(f"incremental sweep: {args.incr_rate:.0f} ops/s x "
              f"{args.incr_duration:.0f}s per cell, sizes "
              f"{args.incr_sizes}", file=sys.stderr)
        return run_incr_sweep(args, warm_buckets, serving_cfg)
    if args.mesh:
        # the composed arms present micro-batch buckets only; the cap
        # keeps the warmed sharded grid small (4 shapes x {sharded,
        # host-fallback}) at the 8192-row node bucket
        warm_buckets = (8, 16, 32, 64) if not args.smoke else (8, 16)
    else:
        warm_buckets = ((8, 16, 32, 64, 128, 256) if not args.smoke
                        else (8, 16, 32))

    serving_cfg = ServingConfig(
        enabled=True, min_wait_s=0.002, max_wait_s=args.max_wait,
        target_bucket=max(warm_buckets), idle_wait_s=0.1,
        # mesh mode bounds each watcher's send buffer tighter: the
        # stuck-watcher eviction must engage inside one bench run
        watch_buffer=1024 if args.mesh else 4096)

    record = {
        "name": ("churn_net" if args.net_chaos
                 else "churn_mesh" if args.mesh else "churn"),
        "rate_ops_s": args.rate,
        "duration_s": args.duration,
        "nodes": args.nodes,
        "mesh": args.mesh,
        "warm_buckets": list(warm_buckets),
        "serving_config": {"min_wait_s": serving_cfg.min_wait_s,
                           "max_wait_s": serving_cfg.max_wait_s,
                           "target_bucket": serving_cfg.target_bucket},
        "platform": {"python": sys.version.split()[0]},
        "arms": {},
        "errors": [],
    }
    try:
        import jax

        record["platform"]["jax_backend"] = jax.default_backend()
        record["platform"]["devices"] = len(jax.devices())
        record["platform"]["device_kind"] = jax.devices()[0].device_kind
    except Exception:
        pass

    if args.net_chaos:
        arm_plan = (
            ("net_chaos", lambda: run_net_chaos_arm(
                args.rate, args.duration, args.nodes, warm_buckets,
                serving_cfg, args.mesh,
                bind_timeout_rate=args.net_bind_timeout_rate)),
        )
    elif args.mesh:
        arm_plan = (
            ("serving", lambda: run_mesh_serving_arm(
                args.rate, args.duration, args.nodes, warm_buckets,
                serving_cfg, args.mesh, args.watchers)),
            ("failover", lambda: run_mesh_failover_arm(
                args.rate, args.failover_duration, args.nodes,
                warm_buckets, serving_cfg, args.mesh)),
            ("shard_loss", lambda: run_mesh_shard_loss_arm(
                args.rate, args.shard_loss_duration, args.nodes,
                warm_buckets, serving_cfg, args.mesh)),
        )
    else:
        arm_plan = (
            ("serving", lambda: run_serving_arm(
                args.rate, args.duration, args.nodes, warm_buckets,
                serving_cfg)),
            ("fixed", lambda: run_fixed_arm(
                args.rate, args.duration, args.nodes, warm_buckets,
                cycle_interval=args.cycle_interval)),
            ("overload", lambda: run_serving_arm(
                args.rate, args.overload_duration, args.nodes,
                warm_buckets, serving_cfg, overload=True)),
            ("failover", lambda: run_failover_arm(
                args.rate, args.failover_duration, args.nodes,
                warm_buckets, serving_cfg)),
        )
    print(f"churn bench: {args.rate:.0f} ops/s x {args.duration:.0f}s "
          f"per arm, {args.nodes} nodes"
          + (f", mesh={args.mesh}" if args.mesh else ""), file=sys.stderr)
    for name, fn in arm_plan:
        print(f"  arm {name}...", file=sys.stderr)
        try:
            record["arms"][name] = fn()
            a = record["arms"][name]
            if name == "failover":
                print(f"    takeover={a.get('takeover_s')}s "
                      f"post_p99={a.get('post_recovery_p99_s')}s "
                      f"double_binds={a.get('double_bind_attempts')}",
                      file=sys.stderr)
                continue
            if name == "net_chaos":
                print(f"    bound={a.get('bound_truth')}/"
                      f"{a.get('created')} "
                      f"ambiguous={a.get('ambiguous_bind_timeouts')} "
                      f"double_binds={a.get('double_bind_attempts')} "
                      f"violations={a.get('invariant_violations')} "
                      f"p99={a.get('p99_s')}s", file=sys.stderr)
                continue
            if name == "shard_loss":
                print(f"    heal={a.get('shard_heal_s')}s "
                      f"host_cycles={a.get('host_mode_cycles')} "
                      f"max_gap={a.get('doorbell_max_gap_s')}s "
                      f"retraces={a['jax'].get('retraces')}",
                      file=sys.stderr)
                continue
            print(f"    {a.get('ops_per_sec', 0)} ops/s  "
                  f"p50={a['p50_s']}s p99={a['p99_s']}s "
                  f"retraces={a['jax'].get('retraces')} "
                  f"shed={a.get('shed_429', 0)}", file=sys.stderr)
        except Exception as e:  # a failed arm is a recorded bench error
            import traceback

            traceback.print_exc()
            record["errors"].append(f"{name}: {e!r}")

    if args.net_chaos:
        return finish_net_record(record, args)
    if args.mesh:
        return finish_mesh_record(record, args)
    sv = record["arms"].get("serving") or {}
    fx = record["arms"].get("fixed") or {}
    ov = record["arms"].get("overload") or {}
    fo = record["arms"].get("failover") or {}
    lease = fo.get("lease_duration_s", 2.0) or 2.0
    record["criteria"] = {
        # failover: the standby bound within a small multiple of the
        # lease decay, every created pod landed, and the CAS'd truth
        # saw zero double-bind attempts across the handover
        "failover_takeover_ok": bool(
            fo.get("takeover_s") is not None
            and fo["takeover_s"] < 3 * lease + 2.0),
        "failover_no_double_binds": bool(
            fo.get("double_bind_attempts", 1) == 0),
        "failover_drained_ok": bool(
            fo.get("drained") and fo.get("bound") == fo.get("created")),
        "failover_post_p99_bounded_ok": bool(
            fo.get("post_recovery_p99_s") is not None
            and fo["post_recovery_p99_s"] < 2.0),
        "sustained_rate_ok": bool(
            sv.get("ops_per_sec", 0) >= args.rate * 0.95
            and sv.get("wall_s", 0) >= args.duration
            and sv.get("drained")),
        "zero_retraces_ok": sv.get("jax", {}).get("retraces", 1) == 0,
        "p99_vs_fixed_ok": bool(
            sv.get("p99_s", 1e9) < 2 * max(fx.get("p99_s", 0), 1e-9)),
        "overload_rate_ok": bool(
            ov.get("offered_ops_per_sec", 0)
            >= args.overload_factor * max(sv.get("ops_per_sec", args.rate),
                                          1e-9)),
        # shedding is demand-driven: the probe only answers 429 while
        # pending depth exceeds shed_queue_bound, so a host whose flood
        # never pushes the queue past the bound legitimately sheds
        # zero. The failure mode this guards is depth PAST the bound
        # without 429s — not a flood that stayed inside it.
        "overload_sheds_ok": bool(
            ov.get("shed_429", 0) > 0
            or ov.get("max_queue_depth", 1 << 30)
            <= ov.get("shed_queue_bound", 0)),
        "overload_p99_bounded_ok": bool(ov.get("p99_s", 1e9) < 2.0),
        "overload_queue_bounded_ok": bool(
            ov.get("max_queue_depth", 1 << 30)
            <= ov.get("shed_queue_bound", 0) + args.rate),
    }
    # diagnostic, NOT a criterion: criteria holds only booleans — the
    # exit code is all(criteria.values()) and a 0.0 ratio must not fail
    record["p99_ratio_vs_fixed"] = round(
        sv.get("p99_s", 0) / max(fx.get("p99_s", 1e-9), 1e-9), 3)
    _write_record(record, args.out)
    print(json.dumps(record["criteria"], indent=1))
    ok = all(record["criteria"].values()) and not record["errors"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
