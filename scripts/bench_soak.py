#!/usr/bin/env python
"""Day-in-the-life soak — ONE composed runtime through every regime.

Every other bench arm is a minute-scale, single-purpose cell built
fresh per arm; this driver builds ONE ``ServingRuntime`` (mesh-backed,
incremental solve on, perf ledger + SLO watchdog armed, the
state-conservation auditor sweeping, consolidation scenario pack
loaded) and runs it through a scripted day: mixed traffic (gangs +
singletons + priority tiers), steady-state consolidation re-packing
under churn, preemption cascades under tight capacity, leader
kill/re-acquire with takeover reconciliation, shard loss healing back
to sharded, and the full PR-15 network-fault load — each regime
separated by CLEAN phases where the cluster must return to quiescence
(SLO burn delta 0, no counter movement) while
:class:`kubernetes_tpu.soak.SoakSentinels` snapshots every
unbounded-unless-maintained structure and fails the run on monotonic
growth across the clean boundaries.

Phase plan (durations scale with ``--minutes``; ``--phases`` selects a
subset by name)::

    traffic      mixed gangs/singletons across 3 priority tiers, churn
    clean-1      recovery window (sentinel baseline point)
    repack       same churn with scenario.repack_interval_s armed
    clean-2
    cascade      tight capacity: tier-100 load forcing preemption
                 cascades over the resident tier-0/50 population
    clean-3
    leader-kill  two depose/re-acquire cycles mid-traffic (lease
                 stolen by an intruder record, then released)
    clean-4
    shard-loss   one mesh device lost mid-traffic; heal to sharded
    clean-5
    net-faults   chaos.arm_net_fault_load: ambiguous binds, fuzzed
                 watch confirmations; healed by a closing reconcile
    clean-6
    traffic-2    the p99-drift probe: same load as phase 1, end of life
    clean-final  settle, final reconcile + truth-mode double audit

Usage::

    python scripts/bench_soak.py                  # full (~17 min)
    python scripts/bench_soak.py --smoke          # ~40 s sanity run
    python scripts/bench_soak.py --minutes 30     # scale every phase
    python scripts/bench_soak.py --phases traffic,clean-1,repack

Writes ``benchres/soak_r01.json`` (``--out``); the ``soak`` gate
family in scripts/bench_compare.py enforces its criteria.
"""

from __future__ import annotations

import argparse
import dataclasses as _dc
import json
import os
import random
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
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

from bench_churn import NetTruth, _write_record  # noqa: E402
from kubernetes_tpu.chaos import (  # noqa: E402
    MeshChaos,
    arm_net_fault_load,
    disarm_net_fault_load,
)
from kubernetes_tpu.config import (  # noqa: E402
    IncrementalConfig,
    LeaderElectionConfig,
    LedgerConfig,
    ObservabilityConfig,
    ParallelConfig,
    RecoveryConfig,
    ScenarioConfig,
    ServingConfig,
    WarmupConfig,
)
from kubernetes_tpu.faults import FaultInjector  # noqa: E402
from kubernetes_tpu.leaderelection import (  # noqa: E402
    InMemoryLock,
    LeaderElectionRecord,
    LeaderElector,
)
from kubernetes_tpu.sanitize import LockSanitizerConfig  # noqa: E402
from kubernetes_tpu.scheduler import Scheduler  # noqa: E402
from kubernetes_tpu.serving import ServingRuntime  # noqa: E402
from kubernetes_tpu.soak import (  # noqa: E402
    SoakEngine,
    SoakPhase,
    SoakSentinels,
    standard_counters,
)
from kubernetes_tpu.testing import make_node, make_pod  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: soak pod shape: big enough that a node holds ~21 (so "tight
#: capacity" is reachable with hundreds, not tens of thousands, of
#: pods), uniform so the solve signature stays one warmed bucket family
POD_CPU = 3000.0
POD_MEM = 128 * 2**20
NODE_CPU = 64000.0
PODS_PER_NODE = int(NODE_CPU // POD_CPU)


class SoakTruth(NetTruth):
    """NetTruth that remembers each pod's CREATED spec (priority, gang
    fields, soak-sized resources) so the relist and the bind-confirm
    relay rebuild the exact object — bench_churn's uniform-pod
    shortcuts would corrupt priorities and capacity accounting here."""

    def __init__(self, injector) -> None:
        super().__init__(injector)
        self.spec: dict = {}  # key -> Pod as created

    def register(self, pod) -> None:
        with self.lock:
            self.uids[pod.key()] = getattr(pod, "uid", "")
            self.spec[pod.key()] = pod

    def delete(self, key: str) -> None:
        with self.lock:
            self.deleted.add(key)
            self.spec.pop(key, None)

    def get_spec(self, key: str):
        with self.lock:
            return self.spec.get(key)

    def list_pods(self):
        with self.lock:
            out = []
            for key, uid in self.uids.items():
                if key in self.deleted or key not in self.spec:
                    continue
                p = _dc.replace(self.spec[key],
                                node_name=self.bound.get(key, ""),
                                deletion_timestamp=0.0)
                p.uid = uid
                out.append(p)
            return out


class SoakTraffic:
    """The one producer for every phase: creates (singletons and
    gangs across priority tiers), bound-pod churn deletes trimming the
    resident population to a target, bind-confirm relays through the
    (possibly faulty) watch network, and victim-delete relays for the
    preemption cascades. All ingress rides ``loop.ingest`` (the
    cross-thread seam); ``on_cycle`` runs on the loop thread outside
    the ingest lock."""

    def __init__(self, rt, truth, injector, chaos=None) -> None:
        self.rt = rt
        self.sched = rt.sched
        self.truth = truth
        self.injector = injector
        self.chaos = chaos
        self.rng = random.Random(11)
        self.seq = 0
        self.created = 0
        self.deleted = 0
        self.preempt_relayed = 0
        self.bound_backlog: list = []   # keys in bind order (FIFO trim)
        self.dead: set = set()          # victim-deleted keys
        self.dropped_confirms: list = []
        self.victim_q: list = []        # pods victim_deleter parked
        self.repack_q: list = []        # pods repack_evictor parked
        self.repack_evicted = 0
        self.lats: list = []            # per-phase e2e latencies
        self._lock = threading.Lock()

    # -- ingress -----------------------------------------------------------

    def _new_pod(self, priority: int, group: str = "",
                 min_available: int = 0):
        self.seq += 1
        kw = {}
        if group:
            kw = {"pod_group": group, "pod_group_min_available": min_available}
        return make_pod(f"soak-{self.seq}", cpu_milli=POD_CPU,
                        memory=POD_MEM, priority=priority, **kw)

    def spawn(self, priority: int = 0, gang: int = 0) -> int:
        """Create one unit of load: a singleton, or a ``gang``-sized
        PodGroup admitted in one ingest burst so the micro-batch window
        usually sees the whole gang together."""
        pods = []
        if gang > 1:
            gname = f"gang-{self.seq}"
            pods = [self._new_pod(priority, gname, gang)
                    for _ in range(gang)]
        else:
            pods = [self._new_pod(priority)]
        for p in pods:
            self.truth.register(p)
            self.rt.loop.ingest(self.sched.on_pod_add, p)
            self.rt.hub.publish(("ADDED", p.key()))
        self.created += len(pods)
        return len(pods)

    def trim(self, target: int) -> int:
        """Churn deletes: drop the OLDEST bound pods until the live
        resident population is back at ``target``."""
        n = 0
        while True:
            with self._lock:
                live = [k for k in self.bound_backlog if k not in self.dead]
                if len(live) <= target or not self.bound_backlog:
                    break
                key = self.bound_backlog.pop(0)
                if key in self.dead:
                    self.dead.discard(key)
                    continue
            spec = self.truth.get_spec(key)
            node = self.truth.bound.get(key, "")
            self.truth.delete(key)
            if spec is not None:
                gone = _dc.replace(spec, node_name=node)
                self.rt.loop.ingest(self.sched.on_pod_delete, gone)
            self.rt.hub.publish(("DELETED", key))
            self.deleted += 1
            n += 1
        return n

    def resident(self) -> int:
        with self._lock:
            return len([k for k in self.bound_backlog
                        if k not in self.dead])

    # -- cycle-side relays --------------------------------------------------

    def victim_deleter(self, pod) -> None:
        """Scheduler's hub-deleter seam, called MID-CYCLE under the
        ingest lock: commit the deletion at the truth, park the watch
        DELETE for the on_cycle relay (the victim holds its capacity
        as terminating until it lands — the stock hub semantics)."""
        self.truth.delete(pod.key())
        with self._lock:
            self.victim_q.append(pod)
            self.dead.add(pod.key())

    def repack_evictor(self, pod) -> None:
        """Scheduler's repack drain seam, called under the loop lock:
        a consolidation re-pack is an EVICTION at the truth (the stock
        truth binder forbids re-binding a live key — a real apiserver
        would too), so commit the delete now and park the pod; the
        on_cycle relay delivers the watch DELETE and re-creates the
        workload as a fresh pod (the controller-recreates-the-evictee
        model), which the next cycles pack onto the remaining nodes."""
        self.truth.delete(pod.key())
        with self._lock:
            self.repack_q.append(pod)
            self.dead.add(pod.key())
            self.repack_evicted += 1

    def _relay_victims(self) -> None:
        with self._lock:
            victims, self.victim_q = self.victim_q, []
            repacked, self.repack_q = self.repack_q, []
        for v in victims:
            self.rt.loop.ingest(self.sched.on_pod_delete, v)
            self.rt.hub.publish(("DELETED", v.key()))
            self.preempt_relayed += 1
        for p in repacked:
            self.rt.loop.ingest(self.sched.on_pod_delete, p)
            self.rt.hub.publish(("DELETED", p.key()))
            self.deleted += 1
            # recreate as a singleton at the evictee's priority: a
            # lone re-created gang MEMBER would park forever at the
            # min-available gate (its siblings are already bound)
            self.seq += 1
            repl = make_pod(f"soak-{self.seq}", cpu_milli=POD_CPU,
                            memory=POD_MEM, priority=p.priority)
            self.truth.register(repl)
            self.rt.loop.ingest(self.sched.on_pod_add, repl)
            self.rt.hub.publish(("ADDED", repl.key()))
            self.created += 1

    def _relay_binds(self, res) -> None:
        """Bind confirmations fan back as watch MODIFIEDs through the
        injected network: duplicated, reordered, occasionally dropped
        (the net-fault phase's closing reconcile re-delivers drops).
        With no watch rules armed this is a clean, ordered relay."""
        events = []
        for key, node in res.assignments.items():
            kind = self.injector.pick("watch:event")
            if kind == "drop":
                self.dropped_confirms.append(key)
                continue
            events.append((key, node))
            if kind == "duplicate":
                events.append((key, node))
        if len(events) > 1 and self.injector.pick("watch:batch") == "reorder":
            self.rng.shuffle(events)
        for key, node in events:
            spec = self.truth.get_spec(key)
            if spec is None:  # deleted before its confirm relayed
                continue
            old = _dc.replace(spec, node_name="")
            new = _dc.replace(spec, node_name=node)
            self.rt.loop.ingest(self.sched.on_pod_update, old, new)

    def on_cycle(self, res) -> None:
        # victims first: their capacity must release before the next
        # batch of confirmations lands on the same nodes
        self._relay_victims()
        self._relay_binds(res)
        with self._lock:
            for k in res.assignments:
                self.bound_backlog.append(k)
            self.lats.extend(res.e2e_latency_s.values())
        for k in res.assignments:
            self.rt.hub.publish(("BOUND", k))
        if self.chaos is not None:
            self.chaos.observe(res, time.monotonic())

    def take_lats(self) -> list:
        with self._lock:
            out, self.lats = self.lats, []
        return out


def _p99(lats) -> float:
    return (round(float(np.percentile(np.asarray(lats), 99)), 4)
            if lats else None)


def quiesce(rt, traffic, timeout_s: float) -> bool:
    """Drive the runtime to TRUE quiescence: pending queue empty
    (backoff/unschedulable parks re-activated — a park with no cluster
    event to wake it would otherwise sit out the clock), relay queues
    drained, and no cycle in flight. Phase disarms run this so the
    boundary counter reads and the clean-phase sentinel samples never
    race a straddling cycle — and the final drain uses it too, because
    bench_churn's ``drain`` only watches the ACTIVE queue."""
    sched = rt.sched
    deadline = time.monotonic() + timeout_s
    streak = 0
    while time.monotonic() < deadline:
        rt.loop.ingest(sched.queue.move_all_to_active)
        with rt.loop.lock:  # no solve/bind cycle mid-flight while held
            pending = sched.state_sizes()["queue_pending"]
        relays = len(traffic.victim_q) + len(traffic.repack_q)
        if pending == 0 and relays == 0:
            streak += 1
            if streak >= 3:
                return True
        else:
            streak = 0
        time.sleep(0.15)
    return False


def build_soak(args):
    """One composed replica with EVERYTHING on: mesh backend,
    incremental solve, consolidation pack (cascades in-batch), ledger
    objectives armed so the SLO watchdog is live, auditor sweeping,
    recovery config for the shard-loss cooloff, leader election."""
    injector = FaultInjector(seed=11)
    truth = SoakTruth(injector)
    binder = truth.binder()
    sched = Scheduler(
        enable_preemption=True,
        solver="batch",
        binder=binder,
        pod_reader=truth.reader(),
        fault_injector=injector,
        victim_deleter=None,  # wired to the traffic relay below
        parallel=ParallelConfig(mesh=args.mesh),
        incremental=IncrementalConfig(enabled=True),
        recovery=RecoveryConfig(device_reset_limit=1,
                                device_cooloff_s=args.cooloff),
        scenario=ScenarioConfig(pack="consolidation",
                                repack_interval_s=0.0,
                                repack_max_pods=32),
        observability=ObservabilityConfig(
            audit_interval_s=args.audit_interval,
            ledger=LedgerConfig(e2e_p99_objective_s=args.p99_objective,
                                cost_drift_ratio=20.0),
            # runtime lock sanitizer armed for the whole soak: every
            # obs/cache/serving lock is instrumented; the clean-window
            # contract below requires zero order cycles and zero
            # guard violations. The hold budget is generous — the soak
            # runs compilation-heavy phases on CPU jax where a cycle
            # under the serving lock legitimately takes seconds.
            lock_sanitizer=LockSanitizerConfig(enabled=True,
                                               hold_budget_s=0.0)),
        warmup=WarmupConfig(enabled=True,
                            pod_buckets=tuple(args.warm_buckets)),
    )
    for i in range(args.nodes):
        sched.on_node_add(make_node(f"node-{i}", cpu_milli=NODE_CPU,
                                    memory=256 * 2**30, pods=500))
    serving_cfg = ServingConfig(
        enabled=True, min_wait_s=0.002, max_wait_s=0.05,
        target_bucket=64 if not args.smoke else 16,
        idle_wait_s=0.1, watch_buffer=1024)
    rt = ServingRuntime(sched, serving_cfg)
    t0 = time.monotonic()
    compiled = rt.warm_if_pending(
        sample_pods=[make_pod("warm-sample", cpu_milli=POD_CPU,
                              memory=POD_MEM)])
    warm_s = time.monotonic() - t0
    chaos = MeshChaos(sched)
    traffic = SoakTraffic(rt, truth, injector, chaos=chaos)
    sched.victim_deleter = traffic.victim_deleter
    sched.repack_evictor = traffic.repack_evictor
    rt.loop.on_cycle = traffic.on_cycle
    # leader election: the soak replica holds the lease; the kill phase
    # fences it with an intruder record and later releases it
    lease = LeaderElectionConfig(lease_duration_s=args.lease,
                                 renew_deadline_s=args.lease * 0.7,
                                 retry_period_s=args.lease * 0.15)
    lock = InMemoryLock()
    elector = LeaderElector("soak", lock, lease)
    rt.attach_elector(elector, lister=truth.list_pods)
    assert elector.tick()
    return rt, truth, binder, injector, chaos, traffic, lock, elector, \
        lease, compiled, warm_s


def build_phases(args, rt, truth, injector, chaos, traffic, lock):
    """The scripted day. Durations come pre-scaled on ``args``."""
    sched = rt.sched
    capacity = PODS_PER_NODE * args.nodes
    resident = int(capacity * 0.55)
    # sized so resident + cascade load lands at ~110% of capacity:
    # the tier-100 wave MUST preempt ~10% of the tier-0 residents to
    # fit, and every preemptor still eventually binds
    cascade_total = int(capacity * 0.55)

    def paced(st, rate, elapsed, cap=None, tiers=True, gang_every=24):
        """Create up to rate*elapsed units this phase; delete overflow
        beyond the resident target unless the phase holds capacity."""
        target = int(rate * elapsed)
        if cap is not None:
            target = min(target, cap)
        while st["made"] < target:
            i = st["units"]
            st["units"] += 1
            if tiers and gang_every and i % gang_every == gang_every - 1:
                st["made"] += traffic.spawn(priority=50, gang=4)
            elif tiers:
                pr = 0 if i % 10 < 6 else (50 if i % 10 < 9 else 100)
                st["made"] += traffic.spawn(priority=pr)
            else:
                st["made"] += traffic.spawn(priority=100)

    def traffic_phase(name, dur, rate, kind="traffic", p99_key="p99_s",
                      arm=None, disarm=None, extra_tick=None,
                      hold_capacity=False, cap=None, high_only=False):
        st = {"made": 0, "units": 0}

        def tick(elapsed):
            paced(st, rate, elapsed, cap=cap, tiers=not high_only)
            if not hold_capacity:
                traffic.trim(resident)
            if extra_tick is not None:
                extra_tick(elapsed)

        def dis():
            if disarm is not None:
                disarm()
            traffic.trim(resident)
            quiesce(rt, traffic, args.quiesce_s)

        def probe():
            lats = traffic.take_lats()
            return {p99_key: _p99(lats), "latency_samples": len(lats),
                    "created_in_phase": st["made"],
                    "resident": traffic.resident()}

        return SoakPhase(name=name, duration_s=dur, kind=kind, arm=arm,
                         disarm=dis, tick=tick, probe=probe)

    def clean_phase(name, dur):
        def tick(elapsed):
            pass

        def probe():
            traffic.take_lats()  # clean windows never feed the drift
            return {"resident": traffic.resident(),
                    "queue": len(sched.queue)}

        return SoakPhase(name=name, duration_s=dur, kind="clean",
                         tick=tick, probe=probe)

    # -- repack arm/disarm: cadence gate lives on the live config ------
    def repack_arm():
        sched.scenario.repack_interval_s = args.repack_interval

    def repack_disarm():
        sched.scenario.repack_interval_s = 0.0
        sched._last_repack_at = None

    # -- leader kill plan: two depose/re-acquire cycles ----------------
    kill_dur = args.kill_duration

    def steal():
        now = time.monotonic()
        prev = lock.get()
        lock._record = LeaderElectionRecord(
            holder_identity="soak-intruder",
            lease_duration_s=args.lease,
            acquire_time=now, renew_time=now,
            leader_transitions=(prev.leader_transitions + 1
                                if prev else 1))

    def release():
        rec = lock.get()
        if rec is not None and rec.holder_identity == "soak-intruder":
            lock._record = _dc.replace(
                rec, renew_time=time.monotonic() - 3 * args.lease)

    # re-acquire after a release costs a FULL lease (the elector must
    # observe the released record unchanged for lease_duration_s), so
    # every release needs >= lease + margin of phase left; smoke's
    # compressed phase fits one depose/re-acquire cycle, full fits two
    if args.smoke:
        kill_plan = [(kill_dur * 0.15, steal), (kill_dur * 0.45, release)]
    else:
        kill_plan = [(kill_dur * 0.10, steal), (kill_dur * 0.35, release),
                     (kill_dur * 0.50, steal), (kill_dur * 0.75, release)]
    kill_state = {"next": 0}

    def kill_tick(elapsed):
        while (kill_state["next"] < len(kill_plan)
               and elapsed >= kill_plan[kill_state["next"]][0]):
            kill_plan[kill_state["next"]][1]()
            kill_state["next"] += 1

    # -- shard loss: fire once at 25% of the phase ---------------------
    shard_state = {"fired": False}

    def shard_tick(elapsed):
        if not shard_state["fired"] and elapsed >= args.shard_duration * 0.25:
            chaos.lose_shard(time.monotonic())
            shard_state["fired"] = True

    # -- net faults: the PR-15 load, phase-scoped ----------------------
    def net_arm():
        arm_net_fault_load(injector)

    def net_disarm():
        disarm_net_fault_load(injector)
        # a closing relist heals the dropped confirmations and adopts
        # any ambiguous bind the protocol parked (the outage ENDS)
        rt.loop.ingest(lambda: sched.reconcile(truth.list_pods()))

    def final_disarm():
        rt.loop.ingest(lambda: sched.reconcile(truth.list_pods()))

    phases = [
        traffic_phase("traffic", args.traffic_duration, args.rate),
        clean_phase("clean-1", args.clean_duration),
        traffic_phase("repack", args.traffic_duration, args.rate,
                      p99_key="phase_p99_s",
                      arm=repack_arm, disarm=repack_disarm),
        clean_phase("clean-2", args.clean_duration),
        traffic_phase("cascade", args.cascade_duration,
                      args.cascade_rate, kind="chaos",
                      p99_key="phase_p99_s", hold_capacity=True,
                      cap=cascade_total, high_only=True),
        clean_phase("clean-3", args.clean_duration),
        traffic_phase("leader-kill", kill_dur, args.rate / 2,
                      kind="chaos", p99_key="phase_p99_s",
                      extra_tick=kill_tick),
        clean_phase("clean-4", args.clean_duration),
        traffic_phase("shard-loss", args.shard_duration, args.rate / 2,
                      kind="chaos", p99_key="phase_p99_s",
                      extra_tick=shard_tick),
        clean_phase("clean-5", args.clean_duration),
        traffic_phase("net-faults", args.traffic_duration, args.rate,
                      kind="chaos", p99_key="phase_p99_s",
                      arm=net_arm, disarm=net_disarm),
        clean_phase("clean-6", args.clean_duration),
        traffic_phase("traffic-2", args.traffic2_duration, args.rate),
        SoakPhase(name="clean-final", duration_s=args.final_duration,
                  kind="clean", arm=final_disarm,
                  probe=lambda: {"resident": traffic.resident(),
                                 "queue": len(sched.queue)}),
    ]
    if args.phases:
        wanted = [p.strip() for p in args.phases.split(",") if p.strip()]
        phases = [ph for ph in phases if ph.name in wanted]
    return phases


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--minutes", type=float, default=17.0,
                    help="target soak length; every phase scales "
                         "proportionally (default 17)")
    ap.add_argument("--phases", default="",
                    help="comma-separated phase names to run (default "
                         "all; shared scaler with the committed record)")
    ap.add_argument("--rate", type=float, default=25.0,
                    help="mixed-traffic creates/sec (default 25)")
    ap.add_argument("--nodes", type=int, default=16)
    ap.add_argument("--mesh", type=int, default=2)
    ap.add_argument("--lease", type=float, default=2.0)
    ap.add_argument("--cooloff", type=float, default=2.0)
    ap.add_argument("--audit-interval", type=float, default=0.5)
    ap.add_argument("--p99-objective", type=float, default=2.0,
                    help="ledger e2e p99 objective, seconds — ARMS the "
                         "SLO watchdog (clean phases must burn 0)")
    ap.add_argument("--repack-interval", type=float, default=3.0)
    ap.add_argument("--sample-every", type=float, default=10.0,
                    help="sentinel cadence-sample interval, seconds")
    ap.add_argument("--p99-drift-bound", type=float, default=1.0,
                    help="allowed fractional p99 growth, first vs last "
                         "plain-traffic phase (default 1.0 — a shared "
                         "CPU host is noisy; the LEAK signal is the "
                         "sentinels, drift is the backstop)")
    ap.add_argument("--smoke", action="store_true",
                    help="~40 s sanity run (tiny phases, small cluster)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    scale = args.minutes / 17.0
    args.traffic_duration = 120.0 * scale
    args.traffic2_duration = 60.0 * scale
    args.clean_duration = 40.0 * scale
    args.cascade_duration = 90.0 * scale
    args.kill_duration = 120.0 * scale
    args.shard_duration = 90.0 * scale
    args.final_duration = 60.0 * scale
    args.cascade_rate = 6.0
    args.warm_buckets = (8, 16, 32, 64, 128, 256)
    args.step_s = 0.25
    args.quiesce_s = 45.0
    if args.smoke:
        args.nodes = min(args.nodes, 8)
        args.rate = min(args.rate, 12.0)
        args.cascade_rate = 30.0  # tier-100 wave must outrun the 5 s phase
        args.traffic_duration = 5.0
        args.traffic2_duration = 4.0
        args.clean_duration = 2.5
        args.cascade_duration = 5.0
        args.kill_duration = 8.0
        args.shard_duration = 8.0
        args.final_duration = 5.0
        args.cooloff = 1.0
        args.sample_every = 1.0
        # 64 covers the cascade re-solve pad (batch + displaced pods
        # exceed the 32-pod batch cap; an unwarmed pad = a retrace)
        args.warm_buckets = (8, 16, 32, 64)
        args.quiesce_s = 10.0
    if args.out is None:
        args.out = os.path.join(REPO_ROOT, "benchres", "soak_r01.json")

    print(f"soak: {args.minutes:g} min plan, {args.nodes} nodes, "
          f"mesh={args.mesh}, rate={args.rate:g}/s"
          + (" (smoke)" if args.smoke else ""), file=sys.stderr)
    (rt, truth, binder, injector, chaos, traffic, lock, elector, lease,
     compiled, warm_s) = build_soak(args)
    sched = rt.sched
    phases = build_phases(args, rt, truth, injector, chaos, traffic, lock)

    sentinels = SoakSentinels(
        sched=sched,
        registry=sched.metrics.registry,
        fresh_gauges=["scheduler_pending_pods"],
        # CPU-jax arenas settle for minutes after the burst phases; the
        # STRUCTURE sentinels (all at default tolerance) carry the leak
        # verdict, RSS is the coarse backstop
        tolerance={"rss_kb": 196608.0})
    counters = standard_counters(
        sched, auditor=rt.auditor,
        extra={
            "double_binds": lambda: float(binder.double_bind_attempts),
            "preempted": lambda: float(
                sched.metrics.preemption_victims.value()),
            "repacks": lambda: float(
                sched.metrics.scenario_repacks.value()),
            "takeovers": lambda: float(
                sched.metrics.recovery_takeovers.value()),
            "lock_order_cycles": lambda: float(
                sched.lock_sanitizer.counts().get("order-cycle", 0)),
            "lock_guard_violations": lambda: float(
                sched.lock_sanitizer.counts().get("guard-violation", 0)),
        })
    engine = SoakEngine(
        phases, sentinels, counters=counters,
        clean_zero=("slo_burns", "auditor_violations", "double_binds",
                    "retraces", "fenced_binds", "preempted",
                    "lock_order_cycles", "lock_guard_violations",
                    # a clean window must not capture incident bundles
                    # nor drop journeys at the pending cap
                    "incidents", "journey_drops"),
        step_s=args.step_s, sample_every_s=args.sample_every,
        p99_drift_bound=args.p99_drift_bound,
        log=lambda m: print(f"  {m}", file=sys.stderr))
    engine.attach(sched)
    # the maintenance composition the tentpole exists to prove: the
    # audit sweep (attached by ServingRuntime) AND a sentinel cadence
    # hook chain on one loop without knowing about each other
    maint_state = {"next": 0.0}

    def sentinel_maintenance():
        now = time.monotonic()
        if now >= maint_state["next"]:
            maint_state["next"] = now + args.sample_every
            sentinels.sample(tag="maintenance", phase=engine.current,
                             clock=now)

    rt.add_maintenance(sentinel_maintenance)

    stop = threading.Event()
    loop_t = threading.Thread(
        target=rt.run, args=(stop,),
        kwargs={"elector": elector, "retry_period_s": args.lease * 0.15},
        daemon=True)
    t0 = time.monotonic()
    loop_t.start()

    record = {
        "name": "soak",
        "minutes": args.minutes,
        "smoke": bool(args.smoke),
        "nodes": args.nodes,
        "mesh": args.mesh,
        "rate_ops_s": args.rate,
        "capacity_pods": PODS_PER_NODE * args.nodes,
        "warm_buckets": list(args.warm_buckets),
        "warmup": {"compiled": compiled, "seconds": round(warm_s, 1)},
        "phases_run": [ph.name for ph in phases],
        "platform": {"python": sys.version.split()[0]},
        "errors": [],
    }
    try:
        import jax

        record["platform"]["jax_backend"] = jax.default_backend()
        record["platform"]["devices"] = len(jax.devices())
        record["platform"]["device_kind"] = jax.devices()[0].device_kind
    except Exception:
        pass

    try:
        soak_out = engine.run()
    except Exception as e:  # a crashed soak is a recorded bench error
        import traceback

        traceback.print_exc()
        record["errors"].append(f"soak: {e!r}")
        soak_out = {"verdict": {"ok": False}, "phases": engine.reports}
    drained = quiesce(rt, traffic, 60.0)
    wall = time.monotonic() - t0
    stop.set()
    loop_t.join(timeout=15)
    # settled truth-mode double audit (the two-strike checks need a
    # confirming pass on a stable state)
    final_violations = 0
    if rt.auditor is not None:
        with rt.loop.lock:
            for _ in range(2):
                final_violations += len(rt.auditor.audit(
                    sched, truth_pods=truth.list_pods()))

    ambiguous = binder.timeouts_committed + binder.timeouts_uncommitted
    verdict = soak_out.get("verdict", {})
    record.update({
        "wall_s": round(wall, 2),
        "soak": soak_out,
        "drained": drained,
        "created": traffic.created,
        "deleted": traffic.deleted,
        "bound_truth": len(truth.bound),
        "resident": traffic.resident(),
        "preempted": int(sched.metrics.preemption_victims.value()),
        "repacks": int(sched.metrics.scenario_repacks.value()),
        "repack_drained": int(
            sched.metrics.scenario_repack_drained.value()),
        "repack_evicted": traffic.repack_evicted,
        "takeovers": int(sched.metrics.recovery_takeovers.value()),
        "fenced_binds": int(sched.metrics.recovery_fenced_binds.value()),
        "double_bind_attempts": binder.double_bind_attempts,
        "ambiguous_bind_timeouts": ambiguous,
        "dropped_confirmations": len(traffic.dropped_confirms),
        "audits": rt.auditor.audits if rt.auditor else 0,
        "invariant_violations": (rt.auditor.violations_total
                                 if rt.auditor else -1),
        "final_truth_audit_violations": final_violations,
        "leaked_assumptions": len(sched.cache.assumed_keys()),
        "parked_ambiguous": len(sched._ambiguous_binds),
        "retraces_total": sched.obs.jax.retrace_total(),
        "retraces_by_site": dict(sched.obs.jax.retraces),
        "faults_fired": {f"{s}:{k}": n
                         for (s, k), n in injector.fired.items()},
        "shard": chaos.report(),
        "leaking": verdict.get("leaking", []),
        "state_sizes_final": sched.state_sizes(),
        "ledger": (rt.ledger.arm_summary()
                   if rt.ledger is not None and rt.ledger.enabled
                   else None),
        "memory": (sched.obs.memledger.arm_summary()
                   if getattr(sched.obs, "memledger", None) is not None
                   and sched.obs.memledger.enabled else None),
        "lock_sanitizer": (sched.lock_sanitizer.snapshot()
                           if sched.lock_sanitizer is not None else None),
    })
    ran = set(record["phases_run"])
    full = not args.phases  # criteria that need a specific phase gate
    # on its presence, so --phases subsets stay honest, not vacuous
    record["criteria"] = {
        "soak_phases_ok": bool(verdict.get("phases_ok")),
        "soak_sentinels_flat": bool(verdict.get("sentinels_flat")),
        "soak_p99_drift_ok": bool(verdict.get("p99_drift_ok", True)),
        "soak_all_bound": bool(
            drained
            and record["bound_truth"] == record["created"]
            and record["leaked_assumptions"] == 0
            and record["parked_ambiguous"] == 0),
        "soak_no_double_binds": record["double_bind_attempts"] == 0,
        "soak_zero_violations": bool(
            record["invariant_violations"] == 0
            and record["final_truth_audit_violations"] == 0
            and record["audits"] > 0),
        "soak_zero_retraces": record["retraces_total"] == 0,
        "soak_repack_engaged": bool(
            "repack" not in ran or record["repacks"] > 0),
        "soak_cascade_engaged": bool(
            "cascade" not in ran or record["preempted"] > 0),
        # initial acquisition reconciles once; each depose/re-acquire
        # cycle adds one more (smoke runs one cycle, full runs two)
        "soak_takeover_ok": bool(
            "leader-kill" not in ran
            or record["takeovers"] >= (2 if args.smoke else 3)),
        "soak_shard_healed": bool(
            "shard-loss" not in ran
            or record["shard"].get("healed_sharded")),
        "soak_net_faults_fired": bool(
            "net-faults" not in ran
            or (record["ambiguous_bind_timeouts"] > 0
                and record["faults_fired"].get(
                    "watch:event:duplicate", 0) > 0)),
        "soak_min_duration_ok": bool(
            args.smoke or not full
            or record["wall_s"] >= args.minutes * 60 * 0.85),
        # absolute, not delta: one deadlock-shaped acquisition order or
        # one false assert_held anywhere in the run is a bug
        "soak_lock_sanitizer_clean": bool(
            record["lock_sanitizer"] is not None
            and record["lock_sanitizer"]["counts"].get(
                "order-cycle", 0) == 0
            and record["lock_sanitizer"]["counts"].get(
                "guard-violation", 0) == 0),
    }
    _write_record(record, args.out)
    print(json.dumps({"verdict": verdict,
                      "criteria": record["criteria"]}, indent=1))
    ok = all(record["criteria"].values()) and not record["errors"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
