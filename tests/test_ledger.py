"""Perf ledger + SLO watchdog (obs/ledger.py) — the tier-1 acceptance
suite:

- driven cycles produce ledger entries whose per-phase sums reconcile
  with the trace's span wall time (the grouping is lossless);
- model efficiency is populated on single-device AND mesh={2,8} cycles,
  and the mesh prediction folds in EXACTLY parallel/costmodel.py's
  ``model_efficiency`` (the bench/runtime parity pin — ROADMAP item 1's
  falsification instrument has ONE model);
- a fake-clock latency regression trips the fast-window burn (event
  emitted, ``backend_pressure`` engaged) and recovery clears it;
- ``/debug/ledger`` serves the thread-safe snapshot; the config block
  round-trips native AND v1alpha1 and ``validate_config`` gates it;
- the bench_compare ``ledger`` gate family honors its contract
  (efficiency floor, clean-arm burns, phase-share sanity, absence
  tolerance);
- ledger overhead stays under 2% of a contended cycle, zero new
  retraces, and graftlint stays clean over the module.
"""

import json
import time
import urllib.request

import pytest

from kubernetes_tpu.config import (
    LedgerConfig,
    ObservabilityConfig,
    ParallelConfig,
)
from kubernetes_tpu.obs.ledger import (
    CycleCostModel,
    PerfLedger,
    SLOWatchdog,
    parse_batch_shape,
    phase_of,
)
from kubernetes_tpu.scheduler import CycleResult, Scheduler
from kubernetes_tpu.testing import make_node, make_pod


class FakeClock:
    def __init__(self, t: float = 1000.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _scheduler(n_nodes=4, pods_cpu=100, **kw):
    s = Scheduler(enable_preemption=False, **kw)
    for i in range(n_nodes):
        s.on_node_add(make_node(f"n{i}", cpu_milli=16000))
    return s


def _drive(s, n_pods=8, cycles=2, prefix="p"):
    out = []
    for c in range(cycles):
        for i in range(n_pods):
            s.on_pod_add(make_pod(f"{prefix}{c}-{i}", cpu_milli=50))
        out.append(s.schedule_cycle())
    return out


# ---------------------------------------------------------------------------
# measured side: phase grouping + reconciliation
# ---------------------------------------------------------------------------


def test_phase_grouping_vocabulary():
    assert phase_of("solve:batch") == "solve"
    assert phase_of("solve:restricted") == "solve"
    assert phase_of("pipeline:pack@3") == "pack"
    assert phase_of("pipeline:dispatch@0") == "dispatch"
    assert phase_of("pipeline:readback@reasons") == "readback"
    assert phase_of("pipeline:bind@2") == "bind"
    assert phase_of("snapshot") == "snapshot"
    assert phase_of("validate") == "validate"
    assert phase_of("extender:filter") == "extenders"
    assert phase_of("Scheduling cycle") == ""  # the root is the total
    assert phase_of("something-new") == "other"
    assert parse_batch_shape("P4096xN65536+topo+mesh8") == (4096, 65536)
    assert parse_batch_shape("") == (0, 0)


def test_driven_cycles_produce_reconciling_entries():
    s = _scheduler()
    _drive(s, n_pods=8, cycles=3)
    snap = s.obs.ledger.snapshot()
    assert snap["retained"] == 3
    for entry, rec in zip(snap["entries"], s.obs.recorder.records()):
        phases = entry["phases"]
        assert phases.get("solve", 0) > 0
        assert phases.get("snapshot", 0) > 0
        # phases are DISJOINT slices of the cycle wall (child-exclusive
        # attribution): their sum reconciles with — never exceeds —
        # the measured cycle
        assert sum(phases.values()) <= entry["measured_s"] * 1.05
        # and the regrouping is lossless against the trace: for this
        # driven shape, validate nests inside solve:batch, so the
        # exclusive solve + validate phases rebuild the INCLUSIVE
        # solve:batch span the flight record keeps
        # snapshot phases are rounded to 6 decimals (±5e-7 each), so a
        # k-phase sum may deviate up to k·5e-7 from the raw spans —
        # the tolerance must cover the rounding budget or this flakes
        assert phases["solve"] + phases.get("validate", 0) == \
            pytest.approx(rec.spans["solve:batch"], rel=1e-6, abs=2e-6)
        top_level = (rec.spans["pop"] + rec.spans["snapshot"]
                     + rec.spans["solve:batch"] + rec.spans.get("bind", 0.0))
        assert sum(phases.values()) == pytest.approx(
            top_level, rel=1e-6, abs=5e-7 * (len(phases) + 1))
    # rolling distributions exist per (phase, scope, mesh)
    assert any(k.startswith("solve|full|mesh0")
               for k in snap["distributions"])


def test_model_efficiency_populated_single_device():
    s = _scheduler()
    results = _drive(s, n_pods=8, cycles=3)
    for r in results:
        assert r.model_efficiency >= 0, "CycleResult must carry the verdict"
        assert r.modeled_s >= 0
    recs = s.obs.recorder.records()
    assert all(r.model_efficiency >= 0 for r in recs)
    # warm cycles sit near the best-observed rate (the anchor), far
    # from the clipped extremes a poisoned anchor would produce
    assert 0.2 <= recs[-1].model_efficiency <= 8.0
    # the flight-recorder dump shows the eff= flag (SIGUSR2 surface)
    assert "eff=" in s.obs.recorder.dump()


@pytest.mark.parametrize("mesh", [2, 8])
def test_model_efficiency_populated_on_mesh(mesh):
    s = _scheduler(n_nodes=8, parallel=ParallelConfig(mesh=mesh))
    _drive(s, n_pods=8, cycles=2, prefix=f"m{mesh}-")
    recs = s.obs.recorder.records()
    assert recs, "mesh cycles must record"
    for rec in recs:
        assert rec.mesh == mesh
        assert rec.model_efficiency >= 0, (
            f"efficiency must populate on mesh={mesh} cycles")
    ent = s.obs.ledger.snapshot()["entries"][-1]
    assert ent["mesh"] == mesh and ent["model_efficiency"] >= 0


def test_mesh_prediction_parity_with_costmodel():
    """The runtime's mesh prediction must fold in EXACTLY
    parallel/costmodel.model_efficiency — one model, bench and runtime
    agreeing by construction."""
    from kubernetes_tpu.parallel.costmodel import model_efficiency

    m = CycleCostModel()
    assert m.record_anchor("full", 256, 1024, 0, 0.010, rounds=1)
    single, _ = m.predict(256, 1024, 0, "full", rounds=1)
    meshed, _ = m.predict(256, 1024, 8, "full", rounds=1)
    eff = model_efficiency(8, 256, 1024)
    assert meshed == pytest.approx(single / 8 / eff, rel=1e-9)
    # and the unified helper itself: 1.0 single-device, the collective
    # model's figure beyond
    assert model_efficiency(1, 30000, 5000) == 1.0
    assert 0 < model_efficiency(8, 30000, 5000) <= 1.0


def test_bench_mesh_scale_delegates_to_costmodel():
    """The satellite pin: scripts/bench_mesh_scale.py no longer carries
    its own model_efficiency — it delegates to the one implementation
    the ledger predicts with."""
    import os

    src_path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                            "bench_mesh_scale.py")
    with open(src_path) as f:
        src = f.read()
    assert "from kubernetes_tpu.parallel.costmodel import model_efficiency" \
        in src
    assert "CollectiveCostModel(" not in src, (
        "bench_mesh_scale must not rebuild the model locally")


def test_best_rate_anchor_never_rebases_upward():
    m = CycleCostModel()
    assert m.record_anchor("full", 64, 64, 0, 0.010)
    # a slower observation (same shape, more seconds) must NOT replace
    assert not m.record_anchor("full", 64, 64, 0, 0.050)
    # a faster one must
    assert m.record_anchor("full", 64, 64, 0, 0.004)
    pred, basis = m.predict(64, 64, 0, "full")
    assert pred == pytest.approx(0.004)
    assert basis == "calibrated"


def test_restricted_scope_scales_with_batch_not_nodes():
    m = CycleCostModel()
    m.record_anchor("restricted", 64, 1024, 0, 0.002)
    small, _ = m.predict(64, 1024, 0, "restricted")
    grown_nodes, _ = m.predict(64, 8192, 0, "restricted")
    grown_pods, _ = m.predict(256, 1024, 0, "restricted")
    # the candidate bucket is a fixed static shape: node-axis growth is
    # free, batch growth is linear
    assert grown_nodes == pytest.approx(small)
    assert grown_pods == pytest.approx(small * 4)


# ---------------------------------------------------------------------------
# SLO watchdog: burn, pressure, recovery (fake clock throughout)
# ---------------------------------------------------------------------------


def _ledger_cfg(**kw):
    base = dict(e2e_p99_objective_s=0.05, fast_window_s=60.0,
                slow_window_s=600.0, burn_threshold=1.0)
    base.update(kw)
    return LedgerConfig(**base)


def _feed_cycle(s, clk, cycle, latencies, solve_s=0.001):
    obs = s.obs
    obs.begin_cycle(cycle)
    obs.note_batch_shape("P8xN8")
    with obs.span("solve:batch"):
        clk.advance(solve_s)
    res = CycleResult(
        attempted=max(len(latencies), 1), scheduled=len(latencies),
        rounds=1, solver_tier="batch",
        e2e_latency_s={f"e{cycle}-{i}": v
                       for i, v in enumerate(latencies)})
    return obs.end_cycle(res)


def test_latency_regression_trips_fast_burn_and_recovers():
    clk = FakeClock()
    events = []
    s = Scheduler(
        enable_preemption=False, clock=clk,
        observability=ObservabilityConfig(ledger=_ledger_cfg()),
        event_sink=lambda reason, obj, msg: events.append(
            (reason, obj.key(), msg)),
    )
    s.on_node_add(make_node("n0", cpu_milli=4000))
    # queue depth for the pressure probe (pod parked, never scheduled
    # in this test — we drive the obs layer directly)
    s.queue.add(make_pod("parked", cpu_milli=100))
    assert s.backend_pressure() == 1.0

    # healthy traffic: latencies under the 50ms objective
    for c in range(3):
        rec = _feed_cycle(s, clk, c, [0.01, 0.02])
        clk.advance(1.0)
        assert rec.slo == ""
    assert not s.obs.ledger.watchdog.burning()

    # regression: every pod over the objective -> burn rate 100x budget
    rec = _feed_cycle(s, clk, 10, [0.2, 0.3, 0.4])
    assert rec.slo == "e2e_p99"
    assert s.obs.ledger.watchdog.burning()
    burn_events = [e for e in events if e[0] == "SchedulerSLOBurn"]
    assert burn_events and "e2e_p99" in burn_events[0][1]
    # sustained burn reads degraded: APF sheds earlier at the same depth
    assert s.is_degraded()
    assert s.backend_pressure(degraded_factor=4.0) == 4.0
    # the flight record carries the SLO state (SIGUSR2 surface)
    assert "slo=e2e_p99" in s.obs.recorder.dump()
    # the metric exports both windows
    assert s.metrics.slo_burn_rate.value(
        objective="e2e_p99", window="fast") >= 1.0

    # recovery: the violating samples age out of the fast window
    clk.advance(120.0)
    rec = _feed_cycle(s, clk, 20, [0.01, 0.01])
    assert rec.slo == ""
    assert not s.obs.ledger.watchdog.burning()
    assert [e for e in events if e[0] == "SchedulerSLORecovered"]
    assert not s.is_degraded()
    assert s.backend_pressure() == 1.0


def test_burn_recovers_while_idle_without_eventful_cycles():
    """A burn must not freeze when traffic stops: observe_cycle only
    runs on eventful cycles, so recovery rides the idle tick and the
    pressure probe's lazy re-evaluation instead."""
    clk = FakeClock()
    events = []
    s = Scheduler(
        enable_preemption=False, clock=clk,
        observability=ObservabilityConfig(ledger=_ledger_cfg()),
        event_sink=lambda reason, obj, msg: events.append(reason),
    )
    s.queue.add(make_pod("parked", cpu_milli=100))
    _feed_cycle(s, clk, 1, [0.5, 0.5])
    assert s.obs.ledger.watchdog.burning()
    assert s.backend_pressure(degraded_factor=4.0) == 4.0
    # the queue drains; NO eventful cycle ever runs again — the idle
    # tick alone must clear the burn once the fast window empties
    clk.advance(120.0)
    s.idle_tick()
    assert not s.obs.ledger.watchdog.burning()
    assert "SchedulerSLORecovered" in events
    assert s.backend_pressure(degraded_factor=4.0) == 1.0
    # and the pressure probe alone also recovers (request threads read
    # it without any scheduler-loop help)
    _feed_cycle(s, clk, 2, [0.5, 0.5])
    assert s.obs.ledger.watchdog.burning()
    clk.advance(120.0)
    assert s.backend_pressure(degraded_factor=4.0) == 1.0
    assert not s.obs.ledger.watchdog.burning()


def test_burn_never_trips_on_stale_window_drainage():
    """The soak's clean-window flap: after a loud phase, the fast
    window drains oldest-first, so the violating FRACTION of what
    remains can cross the threshold with zero new traffic (the good
    bulk expires before a bad tail). The clock-driven evaluations
    (idle tick, pressure probe, sample-free cycles) are recovery-only:
    a burn may only START on fresh evidence."""
    clk = FakeClock()
    wd = SLOWatchdog(_ledger_cfg(), clock=clk)
    good, bad = 0.01, 0.2
    # chaos phase: legitimately trips on fresh evidence, then recovers
    # once the violating bulk leaves the 60s fast window
    wd.observe_cycle(0.0, [good] * 50 + [bad] * 50, 0.0, "full")
    assert wd.burning() and wd.burns.get("e2e_p99") == 1
    wd.observe_cycle(30.0, [good] * 200, 0.0, "full")
    wd.observe_cycle(90.0, [good] * 200, 0.0, "full")
    wd.observe_cycle(95.0, [good, bad], 0.0, "full")
    assert not wd.burning()
    # clean phase: traffic stops. Past t=150 the t=90 good bulk has
    # expired from the fast window, whose survivors are 1 bad of 2 —
    # and the slow window still holds the whole chaos phase, so BOTH
    # windows read over threshold on stale samples alone.
    for t in range(96, 152, 5):
        wd.evaluate(float(t), allow_trip=False)  # the idle-tick path
        assert not wd.burning(), f"tripped on stale drainage at t={t}"
    assert wd.burns.get("e2e_p99") == 1
    # an eventful cycle that folds NOTHING in is clock, not evidence
    wd.observe_cycle(152.0, [], 0.0, "full")
    assert not wd.burning()
    # positive control: the window STATE is trip-capable right now
    # (fast = the bad tail alone, slow = the whole chaos phase) — only
    # the evidence-freshness gate held the flap back
    wd.evaluate(153.0)
    assert wd.burning() and wd.burns.get("e2e_p99") == 2


def test_efficiency_gauge_freshness_on_solve_free_cycle():
    """A solve-free eventful cycle writes the -1 sentinel instead of
    leaving a stale verdict on the wire (gauge freshness rule)."""
    from kubernetes_tpu.metrics import SchedulerMetrics
    from kubernetes_tpu.obs.recorder import CycleRecord

    metrics = SchedulerMetrics()
    ledger = PerfLedger(LedgerConfig(), metrics=metrics)
    ledger.observe_cycle(CycleRecord(
        cycle=1, batch_shape="P8xN8", tier="batch", elapsed_s=0.02,
        spans={"solve:batch": 0.01}))
    assert metrics.cycle_model_efficiency.value() >= 0
    ledger.observe_cycle(CycleRecord(
        cycle=2, batch_shape="", elapsed_s=0.001, spans={}))
    assert metrics.cycle_model_efficiency.value() == -1.0
    assert metrics.cycle_modeled_cost.value() == -1.0


def test_self_anchored_cycle_labeled_anchor_basis():
    s = _scheduler()
    _drive(s, n_pods=8, cycles=1)
    entries = s.obs.ledger.snapshot()["entries"]
    # the cycle that IS the reference says so
    assert entries[0]["model_basis"] == "anchor"
    # best-rate-wins means a faster-than-ever cycle re-bases and is
    # labeled "anchor" again — so pin an unbeatable speed-of-light
    # anchor: the next cycles CANNOT re-base and must be judged
    # against it, which is what "calibrated" means
    s.obs.ledger.model.record_anchor("full", 8, 4, 0, 1e-9)
    _drive(s, n_pods=8, cycles=2)
    entries = s.obs.ledger.snapshot()["entries"]
    assert all(e["model_basis"] == "calibrated" for e in entries[1:])


def test_cost_drift_objective_burns_on_sustained_slowdown():
    clk = FakeClock()
    s = Scheduler(
        enable_preemption=False, clock=clk,
        observability=ObservabilityConfig(ledger=_ledger_cfg(
            e2e_p99_objective_s=0.0, cost_drift_ratio=2.0,
            baseline_decay=0.01)),
    )
    # build the baseline at ~1ms solves
    for c in range(5):
        _feed_cycle(s, clk, c, [], solve_s=0.001)
        clk.advance(1.0)
    assert not s.obs.ledger.watchdog.burning()
    # cycles now cost 10x the rolling baseline -> drift violations
    burned = False
    for c in range(10, 16):
        rec = _feed_cycle(s, clk, c, [], solve_s=0.010)
        clk.advance(1.0)
        burned = burned or rec.slo == "cost_drift"
    assert burned, "sustained cost drift must trip the watchdog"


def test_engage_pressure_false_keeps_degraded_out():
    clk = FakeClock()
    s = Scheduler(
        enable_preemption=False, clock=clk,
        observability=ObservabilityConfig(ledger=_ledger_cfg(
            engage_pressure=False)),
    )
    _feed_cycle(s, clk, 1, [0.5, 0.5])
    assert s.obs.ledger.watchdog.burning()
    assert not s.is_degraded(), (
        "engage_pressure=false must keep the burn out of APF")


# ---------------------------------------------------------------------------
# /debug/ledger + config round-trips + bench_compare contract
# ---------------------------------------------------------------------------


def test_debug_ledger_endpoint():
    from kubernetes_tpu.server import serve_scheduler

    s = _scheduler()
    _drive(s, n_pods=4, cycles=2)
    srv = serve_scheduler(s, port=0)
    try:
        host, port = srv.server_address[:2]
        body = urllib.request.urlopen(
            f"http://{host}:{port}/debug/ledger", timeout=5).read()
        doc = json.loads(body)
        assert doc["retained"] == 2
        assert doc["entries"][-1]["model_efficiency"] >= 0
        assert "anchors" in doc["model"]
        assert "burns" in doc["slo"]
    finally:
        srv.shutdown()


def test_ledger_config_native_and_v1alpha1_round_trip():
    from kubernetes_tpu.api.config_v1alpha1 import decode, encode
    from kubernetes_tpu.cli import decode_config, validate_config

    # native nested block, strict unknown-field rejection
    cfg = decode_config({"observability": {"ledger": {
        "e2e_p99_objective_s": 0.25, "cost_drift_ratio": 2.0,
        "fast_window_s": 30.0}}})
    lg = cfg.observability.ledger
    assert (lg.e2e_p99_objective_s, lg.cost_drift_ratio,
            lg.fast_window_s) == (0.25, 2.0, 30.0)
    from kubernetes_tpu.cli import ConfigError
    with pytest.raises(ConfigError):
        decode_config({"observability": {"ledger": {"bogus": 1}}})

    # v1alpha1: camelCase + duration strings, encode(decode) is stable
    doc = {"apiVersion": "kubescheduler.config.k8s.io/v1alpha1",
           "kind": "KubeSchedulerConfiguration",
           "observability": {"ledger": {"e2eP99Objective": "250ms",
                                        "costDriftRatio": 2.0,
                                        "fastWindow": "30s"}}}
    internal = decode(doc)
    vlg = internal.observability.ledger
    assert vlg.e2e_p99_objective_s == pytest.approx(0.25)
    assert vlg.fast_window_s == pytest.approx(30.0)
    assert vlg.slow_window_s == pytest.approx(600.0)  # default
    again = decode(encode(internal))
    assert again.observability.ledger == vlg

    # validate_config gates the block with field paths
    import dataclasses
    bad = dataclasses.replace(
        internal, observability=dataclasses.replace(
            internal.observability, ledger=dataclasses.replace(
                vlg, baseline_decay=5.0, fast_window_s=-1.0,
                history=0)))
    errs = validate_config(bad)
    assert any("ledger.baselineDecay" in e for e in errs)
    assert any("ledger.fastWindow" in e for e in errs)
    assert any("ledger.history" in e for e in errs)


def _load_bench_compare():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "bench_compare",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "bench_compare.py"))
    bc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bc)
    return bc


def _churn_record(eff_p50=0.9, burns=0, shares=None, with_ledger=True):
    led = {"cycles": 50,
           "model_efficiency": {"n": 50, "p50": eff_p50, "p99": 1.0},
           "phase_share": shares if shares is not None
           else {"snapshot": 0.2, "solve": 0.5, "bind": 0.1},
           "slo": {"burns": burns, "burning": False}}
    arm = {"p50_s": 0.01, "p99_s": 0.05, "ops_per_sec": 500.0,
           "jax": {"retraces": 0}}
    if with_ledger:
        arm["ledger"] = led
    return {"name": "churn", "arms": {"serving": dict(arm),
                                      "overload": dict(arm)},
            "errors": []}


def test_bench_compare_ledger_gate_contract(tmp_path):
    bc = _load_bench_compare()
    # registered in --list-gates
    assert any(n == "ledger" for n, _, _ in bc.GATE_FAMILIES)

    # clean record passes
    v = bc.compare_ledger(_churn_record())
    assert v["regressions"] == [] and v["checks"]

    # efficiency collapse fails the floor
    v = bc.compare_ledger(_churn_record(eff_p50=0.05))
    assert any(r["check"] == "ledger.serving.model_efficiency_p50"
               for r in v["regressions"])

    # burns on a CLEAN arm fail; the overload arm's burns are tolerated
    v = bc.compare_ledger(_churn_record(burns=2))
    assert any(r["check"] == "ledger.serving.slo_burns"
               for r in v["regressions"])
    assert not any("overload.slo_burns" in r["check"]
                   for r in v["regressions"])

    # phase-share double counting fails sanity
    v = bc.compare_ledger(_churn_record(
        shares={"solve": 1.0, "snapshot": 0.9}))
    assert any(r["check"].endswith("phase_share_sum")
               for r in v["regressions"])

    # absence-tolerant: a pre-ledger record warns, never fails
    v = bc.compare_ledger(_churn_record(with_ledger=False))
    assert v["regressions"] == [] and v["warnings"]

    # end to end through main(): one churn record on disk is enough for
    # the absolute ledger gates
    p = tmp_path / "churn_r01.json"
    p.write_text(json.dumps(_churn_record()))
    assert bc.main(["--dir", str(tmp_path)]) == 0
    p.write_text(json.dumps(_churn_record(eff_p50=0.01)))
    assert bc.main(["--dir", str(tmp_path)]) == 1


# ---------------------------------------------------------------------------
# budgets: overhead < 2% of a contended cycle, zero retraces, lint
# ---------------------------------------------------------------------------


def test_ledger_overhead_under_budget_on_contended_cycle():
    """The explain-overhead-style budget: the ledger's whole per-cycle
    cost (observe_cycle — grouping, prediction, watchdog, metrics) must
    stay under 2% of a CONTENDED cycle's measured wall time."""
    s = _scheduler(n_nodes=8)
    for i in range(192):
        s.on_pod_add(make_pod(f"w{i}", cpu_milli=50))
    s.schedule_cycle()  # cold (compiles)
    for i in range(192):
        s.on_pod_add(make_pod(f"x{i}", cpu_milli=50))
    res = s.schedule_cycle()  # warm, contended
    rec = s.obs.recorder.records()[-1]
    assert rec.elapsed_s > 0

    fresh = PerfLedger(LedgerConfig(), metrics=s.metrics,
                       clock=time.monotonic)
    n = 200
    t0 = time.perf_counter()
    for _ in range(n):
        fresh.observe_cycle(rec, res)
    per_observe = (time.perf_counter() - t0) / n
    overhead = per_observe / rec.elapsed_s
    assert overhead < 0.02, (
        f"ledger costs {overhead:.2%} of a contended cycle "
        f"({per_observe*1e6:.0f}us vs {rec.elapsed_s*1e3:.1f}ms)")


def test_zero_new_retraces_with_ledger_on():
    s = _scheduler()
    _drive(s, n_pods=8, cycles=4)
    assert s.obs.jax.retrace_total() == 0, (
        "the ledger must not perturb the solve signatures")


def test_warmup_anchors_the_cost_model():
    from kubernetes_tpu.config import WarmupConfig

    s = _scheduler(warmup=WarmupConfig(enabled=True, pod_buckets=(8,)))
    compiled = s.warmup(sample_pods=[make_pod("w", cpu_milli=50)])
    assert compiled >= 1
    anchors = s.obs.ledger.model.snapshot()["anchors"]
    assert "full" in anchors, "warmup must install the rate anchor"
    assert anchors["full"]["solve_s"] > 0
    # the first live cycle then predicts from the warmup anchor
    r = _drive(s, n_pods=4, cycles=1)[0]
    assert r.model_efficiency >= 0


def test_ledger_module_lints_clean():
    """graftlint over obs/ledger.py: parse + the device-discipline
    rules (R2 host syncs, R3 jit-in-loop, R7 undeclared readbacks, R8
    sharded gathers) — the module is host code by construction, so its
    real jit roots (none) must stay empty AND nothing may smell like a
    device boundary."""
    import kubernetes_tpu.obs.ledger as ledger_mod
    from kubernetes_tpu.testing import lint_clean

    lint_clean(ledger_mod, rules=("R2", "R3", "R7", "R8"), jit_all=False)


def test_chrome_trace_carries_efficiency_counter_track():
    s = _scheduler()
    _drive(s, n_pods=4, cycles=2)
    doc = s.obs.chrome_trace()
    counters = [e for e in doc["traceEvents"] if e.get("ph") == "C"]
    assert counters, "the ledger must stamp a Perfetto counter track"
    assert counters[0]["name"] == "model_efficiency"
    assert "eff" in counters[0]["args"]
