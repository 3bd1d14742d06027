"""Day-in-the-life soak (ISSUE 16): the composed phase engine, the
leak-sentinel layer, and the scheduler fixes the soak surfaced.

Four layers, cheapest first:

1. sentinel mechanics — the growth verdict over clean-phase boundary
   samples (monotonic ratchet = leak; plateau / sawtooth = fine), the
   tolerance prefix table, and gauge freshness via the WRITE counter
   (a gauge maintained every cycle but sampled at drained moments must
   read fresh — the fingerprint-only version regressed exactly that);
2. regression pins for the unbounded structures and livelocks the
   soak found — the reflector tombstone LRU bound, pod-keyed side
   state returning to baseline on every exit path, the gang-member
   rebind livelock (a member whose bind failed transiently re-queues
   alone and must still pass the minMember gate by crediting its
   already-placed siblings), and the nominated-pods solve variant
   joining the warmup sweep (the first post-preemption cycle must not
   pay a hot-path compile);
3. the steady-state consolidation re-pack
   (``scenario.repack_interval_s``): off-cadence no-op, fragmentation
   strictly decreases after a drain + re-solve, foreign/in-flight
   pods pin their node;
4. the composed fake-clock soak (seeds 1/2/3): the full phase
   sequence — traffic, clean, rpc chaos, clean, preemption cascade,
   clean — compressed into seconds, with 0 double binds, 0 auditor
   violations, clean-phase counter deltas all 0, and flat sentinel
   curves over the clean boundaries.
"""

from __future__ import annotations

import dataclasses
import random
from types import SimpleNamespace

import pytest

from kubernetes_tpu.config import ScenarioConfig, WarmupConfig
from kubernetes_tpu.faults import FaultInjector, RPCError, RPCTimeout
from kubernetes_tpu.metrics import Gauge, Registry
from kubernetes_tpu.obs.audit import StateAuditor
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.soak import (
    DEFAULT_TOLERANCE,
    SoakEngine,
    SoakPhase,
    SoakSentinels,
    standard_counters,
)
from kubernetes_tpu.testing import make_node, make_pod


class Clock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class Truth:
    """Minimal CAS'd hub truth (the test_net_chaos pattern, plus the
    spec registry the auditor's truth view needs): binding an
    already-bound key is the never-double-place violation, counted and
    refused."""

    def __init__(self, injector: FaultInjector = None) -> None:
        self.bound: dict = {}
        self.spec: dict = {}
        self.double_bind_attempts = 0
        self.commits = 0
        self.script: list = []

    def register(self, pod) -> None:
        self.spec[pod.key()] = pod

    def delete(self, key: str) -> None:
        self.spec.pop(key, None)
        self.bound.pop(key, None)

    def unbind(self, key: str) -> None:
        self.bound.pop(key, None)

    def bind(self, pod, node_name: str) -> None:
        self.spec.setdefault(pod.key(), pod)
        action = self.script.pop(0) if self.script else "ok"
        if action == "error":
            raise RPCError("injected: definitely not committed")
        if pod.key() in self.bound:
            self.double_bind_attempts += 1
            raise RuntimeError(f"{pod.key()} already bound")
        self.bound[pod.key()] = node_name
        self.commits += 1

    def read(self, key: str):
        spec = self.spec.get(key)
        if spec is None:
            return None
        return SimpleNamespace(uid=spec.uid,
                               node_name=self.bound.get(key, ""))

    def list_pods(self):
        return [dataclasses.replace(p, node_name=self.bound.get(k, ""),
                                    deletion_timestamp=0.0)
                for k, p in self.spec.items()]


def _sched(truth: Truth, clock=None, **kw):
    clock = clock or Clock()
    kw.setdefault("enable_preemption", False)
    s = Scheduler(binder=truth, clock=clock,
                  retry_sleep=lambda _s: None, jitter_seed=1,
                  pod_reader=truth.read, **kw)
    return s, clock


def _confirm(s, res) -> None:
    """Relay the bind confirmations a watch stream would deliver: the
    assumed pods flip to watch-confirmed BOUND (cache state machine),
    exactly what the soak driver's hub relay does."""
    for key, node in dict(res.assignments).items():
        cached = s.cache.pod(key)
        if cached is None:
            continue
        new = dataclasses.replace(cached, node_name=node)
        s.on_pod_update(cached, new)


# ---------------------------------------------------------------------------
# sentinel mechanics
# ---------------------------------------------------------------------------


def _stub_sched(sizes: dict):
    return SimpleNamespace(state_sizes=lambda: dict(sizes))


def test_growth_verdict_flags_monotonic_ratchet():
    """A clean-boundary series that never decreases, rises twice, and
    exceeds tolerance is a leak; a plateau or a sawtooth is not."""
    sizes = {"why_pending": 10}
    sent = SoakSentinels(sched=_stub_sched(sizes), rss_reader=lambda: 0)
    for v in (10, 13, 17):
        sizes["why_pending"] = v
        sent.sample(tag="phase-end", clean=True)
    assert "sched.why_pending" in sent.leaking()
    rep = sent.growth_report()["sched.why_pending"]
    assert rep["judged"] and rep["growing"] and rep["growth"] == 7

    # sawtooth (state that drains) is NOT a leak
    sizes2 = {"why_pending": 10}
    sent2 = SoakSentinels(sched=_stub_sched(sizes2), rss_reader=lambda: 0)
    for v in (10, 17, 11):
        sizes2["why_pending"] = v
        sent2.sample(tag="phase-end", clean=True)
    assert sent2.leaking() == []

    # flat plateau is NOT a leak
    sizes3 = {"why_pending": 10}
    sent3 = SoakSentinels(sched=_stub_sched(sizes3), rss_reader=lambda: 0)
    for _ in range(3):
        sent3.sample(tag="phase-end", clean=True)
    assert sent3.leaking() == []


def test_growth_verdict_needs_three_clean_samples():
    sizes = {"why_pending": 0}
    sent = SoakSentinels(sched=_stub_sched(sizes), rss_reader=lambda: 0)
    for v in (0, 50):
        sizes["why_pending"] = v
        sent.sample(tag="phase-end", clean=True)
    # two clean points cannot be judged — growing stays False
    assert sent.leaking() == []
    assert not sent.growth_report()["sched.why_pending"]["judged"]


def test_tolerance_prefix_matching_and_override():
    """Plateauing series within their tolerance row pass; driver
    overrides merge over the defaults; prefix rows (``reflector.``)
    cover every instance-numbered key."""
    sizes = {"interned_items": 0}
    sent = SoakSentinels(sched=_stub_sched(sizes), rss_reader=lambda: 0,
                         tolerance={"rss_kb": 999999.0})
    for v in (0, 100, 200):  # within the 256 interner tolerance
        sizes["interned_items"] = v
        sent.sample(tag="phase-end", clean=True)
    assert sent.leaking() == []
    assert sent.tolerance["rss_kb"] == 999999.0  # override merged
    assert sent.tolerance["sched.interned_items"] == \
        DEFAULT_TOLERANCE["sched.interned_items"]
    # traffic-phase samples never join the clean series
    sizes["interned_items"] = 10 ** 6
    sent.sample(tag="cadence", clean=False)
    assert sent.leaking() == []


def test_gauge_freshness_counts_writes_not_value_changes():
    """Regression pin (soak finding): scheduler_pending_pods is set on
    every queue mutation but reads 0 at every drained sample point — a
    value-only fingerprint called it stale. The write counter joins
    the fingerprint, so maintained-and-idle reads FRESH while a gauge
    nobody writes still goes stale."""
    reg = Registry()
    maintained = reg.register(Gauge("maintained", ""))
    abandoned = reg.register(Gauge("abandoned", ""))
    maintained.set(0.0)
    abandoned.set(3.0)
    sent = SoakSentinels(registry=reg,
                         fresh_gauges=["maintained", "abandoned"],
                         rss_reader=lambda: 0)
    sent.sample()        # idx 0: first sight fingerprints both
    maintained.set(0.0)  # a WRITE of the same value
    sent.sample()        # idx 1
    maintained.set(0.0)
    sent.sample()        # idx 2
    # value-only fingerprinting would read BOTH as unchanged since 0
    assert sent.stale_since(1) == ["abandoned"]


# ---------------------------------------------------------------------------
# regression pins: the structures and livelocks the soak surfaced
# ---------------------------------------------------------------------------


def test_reflector_tombstone_lru_bounded():
    """Deleted-object dedupe floors migrate to a bounded LRU: the live
    map stays sized to the live set and the tombstone set can never
    grow past its capacity, however many deletes churn through."""
    from kubernetes_tpu.sim import HollowCluster, Reflector

    hub = HollowCluster(seed=3,
                        scheduler_kw={"enable_preemption": False})
    hub.add_node(make_node("n0", cpu_milli=64000))
    sink = Scheduler(clock=hub.clock, enable_preemption=False)
    r = Reflector(hub, sink)
    r.tombstone_capacity = 8
    r.list_and_watch()
    for i in range(50):
        hub.create_pod(make_pod(f"t{i}", cpu_milli=10))
        hub.delete_pod(f"default/t{i}")
        r.pump()
    assert len(r._gone_rev) <= 8
    # live floors track the live set only (node + nothing else)
    assert all(not k.startswith("pods/default/t")
               for k in r._obj_rev)


def test_pod_side_state_returns_to_baseline_on_exit():
    """Exit-path parity: every pod-keyed side structure must pop on
    every exit (bind, delete) — the leak class the sentinels watch at
    zero tolerance."""
    t = Truth()
    s, clock = _sched(t)
    s.on_node_add(make_node("n0", cpu_milli=8000))
    for i in range(4):
        p = make_pod(f"p{i}", cpu_milli=100)
        t.register(p)
        s.on_pod_add(p)
    res = s.schedule_cycle()
    assert res.scheduled == 4
    _confirm(s, res)
    for i in range(4):
        key = f"default/p{i}"
        pod = s.cache.pod(key)
        t.delete(key)
        s.on_pod_delete(pod)
    clock.advance(120.0)
    s.schedule_cycle()
    sizes = s.state_sizes()
    for key in ("why_pending", "ambiguous_binds", "cycle_states",
                "waiting_pods", "queue_pending", "cache_assumed",
                "cache_pods", "packer_pod_refs", "packer_pod_index"):
        assert sizes[key] == 0, (key, sizes)


def test_gang_member_rebind_is_not_livelocked():
    """Regression pin (soak finding): a gang member whose bind failed
    transiently re-queues ALONE. The minMember gate must credit its
    already-placed siblings (cache.group_members) — counting only
    batch-present members parks the straggler at GangIncomplete
    forever while the rest of its gang runs."""
    t = Truth()
    s, clock = _sched(t)
    s.on_node_add(make_node("n0", cpu_milli=8000))
    s.on_node_add(make_node("n1", cpu_milli=8000))
    gang = [make_pod(f"g{i}", cpu_milli=100, pod_group="job",
                     pod_group_min_available=3) for i in range(3)]
    t.script = ["ok", "ok", "error"]  # third member's bind RPC fails
    for p in gang:
        t.register(p)
        s.on_pod_add(p)
    res = s.schedule_cycle()
    assert len(t.bound) == 2 and res.bind_errors == 1
    assert s.cache.group_members("job") == 2
    # the straggler retries ALONE once its backoff elapses — and binds
    for _ in range(30):
        clock.advance(10.0)
        if s.schedule_cycle().scheduled:
            break
    assert len(t.bound) == 3 and t.double_bind_attempts == 0


def test_warmup_registers_nominated_solve_variant():
    """Regression pin (soak finding): with preemption enabled the
    cycle after a preemption carries a (P, N) nominated-pods mask and
    ``extra_mask is None`` flips in the solve digest — a different
    compiled program. The warmup sweep must register BOTH variants, or
    the first post-preemption cycle pays a hot-path compile exactly
    when capacity is tightest."""
    captured = []

    def _capture(s):
        orig = s.obs.jax.record_call

        def spy(site, *trees, static=None, warmup=False):
            if site == "solve" and warmup and static is not None:
                captured.append(static)
            return orig(site, *trees, static=static, warmup=warmup)

        s.obs.jax.record_call = spy

    t = Truth()
    s, _ = _sched(t, enable_preemption=True,
                  warmup=WarmupConfig(enabled=True, pod_buckets=(4,),
                                      include_filter=False))
    s.on_node_add(make_node("n0", cpu_milli=8000))
    _capture(s)
    assert s.warmup(sample_pods=[make_pod("w", cpu_milli=100)]) > 0
    assert any(st[8] is False for st in captured), \
        "masked (nominated) solve variant never warmed"
    assert any(st[8] is True for st in captured)

    # without preemption no nomination can ever arise — the masked
    # variant is NOT warmed (no compile budget spent on a dead shape)
    captured.clear()
    t2 = Truth()
    s2, _ = _sched(t2, enable_preemption=False,
                   warmup=WarmupConfig(enabled=True, pod_buckets=(4,),
                                       include_filter=False))
    s2.on_node_add(make_node("n0", cpu_milli=8000))
    _capture(s2)
    s2.warmup(sample_pods=[make_pod("w", cpu_milli=100)])
    assert all(st[8] is True for st in captured)


# ---------------------------------------------------------------------------
# steady-state consolidation re-pack
# ---------------------------------------------------------------------------


def _repack_sched(interval: float = 5.0):
    t = Truth()
    s, clock = _sched(
        t, scenario=ScenarioConfig(pack="consolidation",
                                   repack_interval_s=interval,
                                   repack_max_pods=8))
    for i in range(3):
        s.on_node_add(make_node(f"n{i}", cpu_milli=8000, pods=32))

    def evictor(p):
        # hub-integration seam: unbind at the truth, then converge
        # local state (what the soak driver's watch relay does)
        t.unbind(p.key())
        s.cache.remove_pod(p.key())
        s.queue.add_if_not_present(dataclasses.replace(
            p, node_name="", deletion_timestamp=0.0))

    s.repack_evictor = evictor
    return s, t, clock


def _nodes_used(t: Truth) -> int:
    return len(set(t.bound.values()))


def test_repack_consolidates_fragmented_cluster():
    """Quality pin: churn strands a straggler on its own node (the
    post-churn shape admission-time consolidation never revisits); the
    cadence re-pack drains it and the next cycle's consolidation
    objective packs it onto the occupied node — nodes-used strictly
    decreases, and no bind RPC ever re-binds a still-bound key."""
    s, t, clock = _repack_sched(interval=5.0)
    # the fragmented state arrives via the informer: 5 pods bound on
    # n0, one straggler alone on n1 (assigned pods enter the cache
    # whoever bound them; watch-confirmed, so they are movable)
    for i in range(5):
        p = make_pod(f"c{i}", cpu_milli=1000, node_name="n0")
        t.register(p)
        t.bound[p.key()] = "n0"
        s.on_pod_add(p)
    straggler = make_pod("straggler", cpu_milli=1000, node_name="n1")
    t.register(straggler)
    t.bound[straggler.key()] = "n1"
    s.on_pod_add(straggler)
    before = _nodes_used(t)
    assert before == 2
    # cadence: first observation arms, a full interval later it drains
    assert s.maybe_repack() == 0
    clock.advance(6.0)
    drained = s.maybe_repack()
    assert drained == 1
    assert s.metrics.scenario_repacks.value() == 1
    assert t.bound.get("default/straggler") is None  # evicted at truth
    res = s.schedule_cycle()
    assert res.scheduled == 1
    _confirm(s, res)
    assert _nodes_used(t) < before, dict(t.bound)
    assert t.double_bind_attempts == 0
    # the drained pod is bound again — repack never loses a pod
    assert sum(s.queue.pending_counts().values()) == 0


def test_repack_off_cadence_and_packless_are_noops():
    s, t, clock = _repack_sched(interval=0.0)
    assert s.maybe_repack() == 0  # interval 0 = disabled
    s2, t2, clock2 = _repack_sched(interval=5.0)
    assert s2.maybe_repack() == 0  # arms the cadence
    clock2.advance(1.0)
    assert s2.maybe_repack() == 0  # within the interval


def test_repack_skips_nodes_with_assumed_pods():
    """In-flight (assumed, not yet watch-confirmed) pods pin their
    node: draining a pod whose bind is still settling would race the
    confirmation."""
    s, t, clock = _repack_sched(interval=5.0)
    pods = [make_pod(f"a{i}", cpu_milli=1000) for i in range(3)]
    for p in pods:
        t.register(p)
        s.on_pod_add(p)
    res = s.schedule_cycle()
    assert res.scheduled == 3
    # NO confirmation relay: everything stays assumed
    assert s.maybe_repack() == 0
    clock.advance(6.0)
    assert s.maybe_repack() == 0
    assert s.metrics.scenario_repacks.value() == 0


# ---------------------------------------------------------------------------
# the composed fake-clock soak (seeds 1/2/3)
# ---------------------------------------------------------------------------


class MiniSoak:
    """The driver's day-in-the-life arc compressed to a fake clock:
    one scheduler, one truth, scripted traffic/chaos/cascade phases,
    auditor + sentinels armed throughout. Single-threaded, so every
    phase boundary is exact (no in-flight cycles straddling it)."""

    def __init__(self, seed: int) -> None:
        self.rng = random.Random(seed)
        self.clock = Clock()
        self.injector = FaultInjector(seed=seed)
        self.truth = Truth()
        self.sched, _ = _sched(
            self.truth, clock=self.clock, enable_preemption=True,
            fault_injector=self.injector,
            scenario=ScenarioConfig(pack="consolidation",
                                    repack_interval_s=0.0,
                                    repack_max_pods=8))
        for i in range(2):
            self.sched.on_node_add(
                make_node(f"n{i}", cpu_milli=8000, pods=64))
        self.auditor = self.sched.attach_auditor(StateAuditor())
        self.victims: list = []
        self.sched.victim_deleter = self.victims.append
        self.seq = 0
        self.created = 0

    def spawn(self, priority: int = 0, group: str = "",
              min_available: int = 0) -> None:
        self.seq += 1
        p = make_pod(f"m{self.seq}", cpu_milli=1000, priority=priority,
                     pod_group=group,
                     pod_group_min_available=min_available)
        self.truth.register(p)
        self.sched.on_pod_add(p)
        self.created += 1

    def cycle(self) -> None:
        res = self.sched.schedule_cycle()
        # victim deletes relay AFTER the cycle (watch-stream order)
        for v in self.victims:
            self.truth.delete(v.key())
            self.sched.on_pod_delete(v)
        self.victims.clear()
        for key, node in dict(res.assignments).items():
            cached = self.sched.cache.pod(key)
            if cached is not None:
                self.sched.on_pod_update(
                    cached, dataclasses.replace(cached, node_name=node))

    def drain(self) -> None:
        """True quiescence: advance past every backoff until the queue
        is empty (the driver's quiesce())."""
        for _ in range(40):
            if sum(self.sched.queue.pending_counts().values()) == 0:
                return
            self.clock.advance(10.0)
            self.sched.queue.move_all_to_active()
            self.cycle()

    def audit(self) -> None:
        self.auditor.audit(self.sched,
                           truth_pods=self.truth.list_pods())


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fake_clock_soak_sequence(seed):
    """The full arc in seconds: trace-driven mixed traffic (priority
    tiers + a gang), an rpc-error chaos window, a preemption cascade
    over capacity — each followed by a clean phase where the
    clean-zero counters must not move and the sentinel boundary sample
    joins the growth series. End of life: every surviving pod bound,
    zero double binds, zero auditor violations, flat sentinels."""
    m = MiniSoak(seed)
    sent = SoakSentinels(
        sched=m.sched, registry=m.sched.metrics.registry,
        fresh_gauges=["scheduler_pending_pods"],
        rss_reader=lambda: 0)  # deterministic: structures only
    counters = standard_counters(
        m.sched, auditor=m.auditor,
        extra={"double_binds":
               lambda: float(m.truth.double_bind_attempts),
               "preempted":
               lambda: float(m.sched.metrics.preemption_victims.value())})
    engine = SoakEngine(
        phases=[], sentinels=sent, counters=counters,
        clean_zero=("slo_burns", "auditor_violations", "double_binds",
                    "retraces", "fenced_binds", "preempted"),
        clock=m.clock, sleep=m.clock.advance, step_s=1.0,
        sample_every_s=4.0)

    def traffic_tick(_elapsed):
        m.spawn(priority=self_prio(m.rng))
        m.cycle()

    def self_prio(rng):
        r = rng.random()
        return 0 if r < 0.6 else (50 if r < 0.9 else 100)

    def gang_tick(elapsed):
        if int(elapsed) == 2 and not getattr(gang_tick, "done", False):
            gang_tick.done = True
            m.spawn(group="mgang", min_available=2)
            m.spawn(group="mgang", min_available=2)
        traffic_tick(elapsed)

    def clean_tick(_elapsed):
        m.cycle()

    def chaos_arm():
        m.injector.arm("rpc:bind", "rpc_error", rate=0.3)

    def chaos_disarm():
        m.injector.rules.clear()
        m.drain()

    def cascade_tick(_elapsed):
        m.spawn(priority=100)
        m.cycle()

    def clean_probe():
        m.audit()
        return {"resident": len(m.truth.bound),
                "queue": sum(m.sched.queue.pending_counts().values())}

    engine.phases = [
        SoakPhase("traffic", 8.0, "traffic", tick=gang_tick,
                  disarm=m.drain),
        SoakPhase("clean-1", 4.0, "clean", tick=clean_tick,
                  probe=clean_probe),
        SoakPhase("rpc-chaos", 6.0, "chaos", arm=chaos_arm,
                  tick=traffic_tick, disarm=chaos_disarm),
        SoakPhase("clean-2", 4.0, "clean", tick=clean_tick,
                  probe=clean_probe),
        SoakPhase("cascade", 4.0, "chaos", tick=cascade_tick,
                  disarm=m.drain),
        SoakPhase("clean-3", 4.0, "clean", tick=clean_tick,
                  probe=clean_probe),
    ]
    record = engine.run()

    assert m.truth.double_bind_attempts == 0
    assert m.auditor.violations_total == 0 and m.auditor.audits >= 3
    for rep in record["phases"]:
        assert rep["ok"], rep["violations"]
    assert record["verdict"]["sentinels_flat"], \
        record["verdict"]["leaking"]
    assert record["verdict"]["ok"]
    # end of life: everything surviving is bound, nothing parked
    assert sum(m.sched.queue.pending_counts().values()) == 0
    assert not m.sched.cache.assumed_keys()
    assert len(m.truth.bound) == len(m.truth.spec)
    # capacity arithmetic: 16 slots, >16 ever created — the cascade
    # demonstrably preempted (hub-deleter mode: victims deleted)
    if m.created > 16:
        assert m.sched.metrics.preemption_victims.value() > 0
