"""The scheduler driver — the batched analog of the reference's control
loop (``pkg/scheduler/scheduler.go:256`` Run / ``:462`` scheduleOne).

Where the reference pops ONE pod, filters/scores all nodes for it, assumes,
and binds asynchronously, this driver pops the WHOLE activeQ, solves the
batch on device (filter mask + score matrix + assignment rounds, see
``ops/assign.py``), then assumes + binds every placed pod and routes every
unplaced pod through the same error path as the reference
(record backoff → AddUnschedulableIfNotPresent, ``factory.go``
MakeDefaultErrorFunc):

    cycle():
      queue.tick(); cache.cleanup_expired()          # wait.Until loops
      batch = queue.pop_batch()                      # NextPod, batched
      snapshot = cache.snapshot()                    # UpdateNodeInfoSnapshot
      assigned = solve(batch, snapshot)              # Schedule(), batched
      for pod, node in assigned:
        cache.assume_pod(pod, node)                  # scheduler.go:538
        binder.bind(pod, node)                       # scheduler.go:598
        cache.finish_binding(...)                    # async part, inlined
      for pod in unassigned:
        queue.record_failure(pod)                    # podBackoff.BackoffPod
        queue.add_unschedulable_if_not_present(...)  # scheduler.go:493 error path

Binding is synchronous here because in-process binders are function calls;
a driver integrating with a real control plane wraps its RPC in the Binder
and may run it on a thread pool — the cache's assume/expire machinery
already tolerates that (it exists for exactly that asynchrony).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Dict, List, NamedTuple, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.api.types import Pod
from kubernetes_tpu.cache import SchedulerCache
from kubernetes_tpu.obs.trace import annotate
from kubernetes_tpu.ops.arrays import (
    attach_owner_columns,
    nodes_to_device,
    pods_to_device,
    selectors_to_device,
    topology_to_device,
)
from kubernetes_tpu.ops.predicates import run_predicates
from kubernetes_tpu.ops.priorities import solver_gates
from kubernetes_tpu.queue import SchedulingQueue
from kubernetes_tpu.snapshot import owner_width
from kubernetes_tpu.utils import klog
from kubernetes_tpu.utils.interner import Interner, bucket_size


@jax.jit
def _filter_pass(dp, dn, ds, dt, dv=None, sv=None, em=None):
    """One standalone filter evaluation (reasons + mask) — used for the
    nominated-pods pass-A mask and for failure-reason reporting."""
    return run_predicates(dp, dn, ds, dt, dv, sv, em)


def _new_cycle_state():
    from kubernetes_tpu.framework import CycleState

    return CycleState()


@partial(jax.jit, static_argnames=("weights_key",))
def _score_pass(dp, dn, ds, dt, mask, weights_key):
    """Standalone priority evaluation for the exact host solver."""
    from kubernetes_tpu.ops.priorities import run_priorities

    w = dict(weights_key) if weights_key is not None else None
    return run_priorities(dp, dn, ds, mask, w, dt)


@jax.jit
def _static_vol_pass(dp, dn, ds, dv):
    """Usage-independent volume reasons, computed once per cycle and shared
    by the solver rounds and the reporting passes."""
    from kubernetes_tpu.ops.predicates import static_volume_reasons

    return static_volume_reasons(dp, dn, ds, dv)


#: ladder tiers that re-pin every operand onto one device (the mesh
#: ladder's single-device rung and the host-backend fallback)
_REPIN_TIERS = ("batch-single", "batch-cpu")
#: routes that solve a (P, C) candidate frame, not the dense plane
_FRAME_TIERS = ("restricted", "partitioned")


class SolveKey(NamedTuple):
    """Which compiled solve program a cycle runs: the scheduler's solver
    settings and the cycle's host-side gates (ops/priorities.
    solver_gates), fixed once per cycle and once per warmup sweep.
    Every route and every warmup site reads the solver's keywords and
    its retrace signature from here, so the served and the warmed
    signatures cannot drift apart."""

    solver: str
    skip_prio: tuple
    no_ports: bool
    no_pod_aff: bool
    no_spread: bool
    pred_mask: Optional[int]
    per_node_cap: int
    max_rounds: int
    weights: Optional[dict]
    mesh_live: bool
    #: Sinkhorn convergence telemetry rides the solve
    stats: bool
    #: early-exit tolerance of a warm-started frame solve (None = cold)
    warm_tol: Optional[float]

    def signature(self, frame: bool, no_mask: bool, no_score: bool,
                  no_init: bool) -> tuple:
        """The static half of the ``solve`` site's retrace digest.
        extra_mask/extra_score None-ness joins it because a clean batch
        routes to the fused lean round path (ops/assign.py), a different
        compiled program; mesh liveness joins it because sharding is
        part of XLA's compile key but invisible to the shape digest. A
        frame solve forces the port/topology gates on and keys on its
        warm-start operand instead."""
        if frame:
            return ("restricted", self.solver, self.skip_prio,
                    self.pred_mask, self.per_node_cap, self.max_rounds,
                    no_init, no_score, self.mesh_live)
        return (self.solver, self.skip_prio, self.no_ports,
                self.no_pod_aff, self.no_spread, self.pred_mask,
                self.per_node_cap, self.max_rounds, no_mask, no_score,
                self.mesh_live)

    def batch_kwargs(self, tier: str) -> dict:
        """batch_assign's static keywords on one tier — also what the
        AOT cost and memory analyses lower. The re-pinning tiers solve
        without the transport plan and without its telemetry."""
        if tier in _FRAME_TIERS:
            sk = self.solver == "sinkhorn"
            gates, stats = (True, True, True), self.stats and sk
        else:
            sk = tier == "sinkhorn"
            gates = (self.no_ports, self.no_pod_aff, self.no_spread)
            stats = self.stats and tier not in _REPIN_TIERS
        return dict(max_rounds=self.max_rounds,
                    per_node_cap=self.per_node_cap,
                    enabled_mask=self.pred_mask, use_sinkhorn=sk,
                    skip_priorities=self.skip_prio, no_ports=gates[0],
                    no_pod_affinity=gates[1], no_spread=gates[2],
                    stats_out=stats)


class Binder(Protocol):
    """The scheduler's only write — POST pods/{name}/binding
    (registry/core/pod/storage/storage.go:154 BindingREST.Create)."""

    def bind(self, pod: Pod, node_name: str) -> None: ...


class RecordingBinder:
    """Test binder capturing bindings (the mock binder of
    scheduler_test.go:1031)."""

    def __init__(self) -> None:
        self.bindings: List[Tuple[str, str]] = []

    def bind(self, pod: Pod, node_name: str) -> None:
        self.bindings.append((pod.key(), node_name))


@dataclass
class CycleResult:
    """What one driver cycle did (inputs to metrics + events)."""

    attempted: int = 0
    scheduled: int = 0
    unschedulable: int = 0
    bind_errors: int = 0
    rounds: int = 0
    assignments: Dict[str, str] = field(default_factory=dict)  # pod key -> node
    failure_reasons: Dict[str, Tuple[str, ...]] = field(default_factory=dict)
    #: pod key -> FitError.Error()-shaped message with per-reason node
    #: counts (only for pods that failed the filter pass)
    fit_errors: Dict[str, str] = field(default_factory=dict)
    preempted: int = 0  # victims deleted this cycle
    nominations: Dict[str, str] = field(default_factory=dict)  # pod -> node
    waiting: int = 0  # pods parked by Permit plugins this cycle
    elapsed_s: float = 0.0
    #: which degradation-ladder tier produced this cycle's placements
    #: ("" = empty cycle; "batch" is the healthy fast path)
    solver_tier: str = ""
    #: tier-to-tier fallbacks taken this cycle (0 on the healthy path)
    solver_fallbacks: int = 0
    #: per-cycle UnschedulableReport (obs/explain.py) — why the
    #: residual pods stayed pending: per-pod reason node counts, the
    #: cluster reason histogram, one-bit-away relaxations. None when the
    #: explainer is off or the cycle ended before the solve.
    explain: Optional[object] = None
    #: how the cycle's snapshot was produced: full | delta | clean
    #: (device-resident modes), "host" = legacy full host pack + upload
    #: (device_resident_snapshot off), "" = the cycle ended before the
    #: snapshot (empty queue / all-prefilter batches)
    snapshot_mode: str = ""
    #: distinct owner sets the cycle's pods spread by (SelectorSpread):
    #: the owner columns its solve read, before padding
    owner_columns: int = 0
    #: which solve the cycle ran: "restricted" = the incremental
    #: candidate-column solve over the cached score plane (O(churn));
    #: "full" = the cold dense solve; "" = the cycle ended before any
    #: solve. The cold solve is the correctness fallback — a restricted
    #: attempt that under-places or fails validation re-solves "full"
    #: in the SAME cycle and reports "full" here.
    solve_scope: str = ""
    #: fraction of the score plane's node columns REUSED from the cache
    #: this cycle (1 - recomputed/live; 0.0 on full solves) — the
    #: "cost proportional to churn" provenance
    reuse_frac: float = 0.0
    #: capacity-balanced blocks the PARTITIONED cold solve ran (0 =
    #: not a partitioned cycle): solve_scope == "partitioned" cycles
    #: solved B fixed-width restricted frames instead of the dense
    #: (P, N) plane — the sparsity-first cold path (docs/perf.md)
    cold_blocks: int = 0
    #: device solve time for the cycle (the span total the scheduling_
    #: algorithm histogram observes) — split by solve_scope in the
    #: churn bench so warm-start wins are visible per cycle
    solve_s: float = 0.0
    #: sub-batches the pipelined executor ran (0 = monolithic cycle)
    pipeline_chunks: int = 0
    #: per-pod create-to-bind latency (pod key -> seconds, queue-add
    #: stamp to bind) for every pod bound this cycle — the admission
    #: timestamps the serving mode's p99 rides; each value lands in
    #: scheduler_e2e_scheduling_duration_seconds
    e2e_latency_s: Dict[str, float] = field(default_factory=dict)
    #: what flushed the micro-batch window into this cycle
    #: ("bucket-fill" | "quiet" | "max-wait"; "" = not a serving-loop
    #: cycle)
    flush_trigger: str = ""
    #: how long the micro-batch window accumulated before flushing
    window_s: float = 0.0
    #: scenario-pack placement-quality scores for this cycle (empty =
    #: scenario mode off / quality gated off): the device-reduced
    #: nodes_used / headroom / fragmentation vector plus the pack's
    #: host-side gang bookkeeping (docs/scenarios.md quality table)
    scenario_quality: Dict[str, float] = field(default_factory=dict)
    #: perf-ledger verdict (obs/ledger.py), stamped at end_cycle: the
    #: cost model's predicted solve seconds for this cycle's batch
    #: shape and the modeled/measured efficiency (-1 = not populated —
    #: no solve ran, or the ledger is off)
    modeled_s: float = -1.0
    model_efficiency: float = -1.0


class Scheduler:
    """Batched scheduling driver over a cache + queue + device solver."""

    def __init__(
        self,
        cache: Optional[SchedulerCache] = None,
        queue: Optional[SchedulingQueue] = None,
        binder: Optional[Binder] = None,
        weights: Optional[Dict[str, float]] = None,
        solver: str = "batch",
        per_node_cap: int = 4,
        max_rounds: int = 128,
        max_batch: int = 8192,
        clock: Callable[[], float] = time.monotonic,
        event_sink: Optional[Callable[[str, Pod, str], None]] = None,
        enable_preemption: bool = True,
        enable_non_preempting: bool = False,
        max_preemptions_per_cycle: int = 16,
        pdb_lister: Optional[Callable[[], List]] = None,
        victim_deleter: Optional[Callable[[Pod], None]] = None,
        repack_evictor: Optional[Callable[[Pod], None]] = None,
        framework=None,
        pred_mask: Optional[int] = None,
        extenders=(),
        metrics=None,
        trace_threshold_s: float = 1.0,
        percentage_of_nodes_to_score: Optional[int] = None,
        volume_binder=None,
        scheduler_name: str = "default-scheduler",
        robustness=None,
        recovery=None,
        fault_injector=None,
        retry_sleep: Callable[[float], None] = time.sleep,
        pod_reader: Optional[Callable[[str], Optional[Pod]]] = None,
        jitter_seed: Optional[int] = None,
        observability=None,
        pipeline_depth: int = 2,
        pipeline_chunk: int = 4096,
        device_resident_snapshot: bool = True,
        snapshot_max_dirty_frac: Optional[float] = None,
        warmup=None,
        parallel=None,
        scenario=None,
        incremental=None,
    ) -> None:
        from kubernetes_tpu.config import (
            ObservabilityConfig,
            RecoveryConfig,
            RobustnessConfig,
        )
        from kubernetes_tpu.faults import CircuitBreaker, RetryPolicy
        from kubernetes_tpu.framework import Framework
        from kubernetes_tpu.metrics import SchedulerMetrics
        from kubernetes_tpu.nodetree import NodeTree
        from kubernetes_tpu.obs import Observability

        #: which pods this scheduler is responsible for
        #: (eventhandlers.go:328 responsibleForPod — the multi-scheduler
        #: seam): unassigned pods naming another scheduler never enter the
        #: queue; assigned pods ALWAYS enter the cache, whoever bound them,
        #: because their capacity is consumed either way (the reference's
        #: assigned-pod informer carries no scheduler-name filter)
        self.scheduler_name = scheduler_name
        self.framework = framework or Framework(clock=clock)
        #: HTTPExtender list (core/extender.go), called after the built-in
        #: filter/score passes for interested pods
        self.extenders = list(extenders)
        self.metrics = metrics or SchedulerMetrics()
        obs_config = (observability if observability is not None
                      else ObservabilityConfig(
                          trace_threshold_s=trace_threshold_s))
        #: instrumented-lock runtime sanitizer (kubernetes_tpu/sanitize):
        #: armed by observability.lockSanitizer.enabled. When on, every
        #: lock the scheduler's obs stack / cache / serving loop builds
        #: is wrapped to maintain the acquisition-order graph; findings
        #: increment scheduler_lock_sanitizer_findings_total{kind} and
        #: mark the cycle eventful in the flight record. getattr:
        #: duck-typed config fakes without the field stay valid.
        self.lock_sanitizer = None
        ls_config = getattr(obs_config, "lock_sanitizer", None)
        if ls_config is not None and ls_config.enabled:
            from kubernetes_tpu.sanitize import LockSanitizer

            self.lock_sanitizer = LockSanitizer(
                ls_config, clock=clock,
                on_finding=lambda kind: (
                    self.metrics.lock_sanitizer_findings.inc(kind=kind)))
        #: observability layer (kubernetes_tpu/obs): cycle tracer + flight
        #: recorder + runtime JAX telemetry, on the scheduler's clock
        self.obs = Observability(
            obs_config, metrics=self.metrics, clock=clock,
            lock_sanitizer=self.lock_sanitizer,
        )
        #: degradation-ladder knobs (config.RobustnessConfig): per-cycle
        #: deadline, bounded retries, breaker thresholds, fallback chain,
        #: result validation — the resilience layer for an out-of-process
        #: (TPU-service) solver that may time out, crash, or lie
        self.robustness = (robustness if robustness is not None
                           else RobustnessConfig())
        #: crash/failover/device-loss knobs (config.RecoveryConfig):
        #: fenced binds, takeover reconciliation, resident rebuild
        self.recovery = recovery if recovery is not None else RecoveryConfig()
        #: the bind fence (LeaderElector via attach_elector, or any
        #: object with allow_bind()/epoch): None = unfenced (single-
        #: writer deployments, tests)
        self.fence = None
        #: truth lister for takeover reconciliation (attach_elector):
        #: () -> iterable of hub-truth Pods; None = local-only reconcile
        self._lister = None
        #: host-mode snapshot fallback window after a device-loss
        #: recovery exhausted its per-cycle rebuild budget (monotonic
        #: deadline; 0 = device considered healthy)
        self._device_cooloff_until = 0.0
        #: faults.FaultInjector (or None): the seeded chaos harness wired
        #: into the solver entry and the extender/shim transports
        self.fault_injector = fault_injector
        rc = self.robustness
        # PER-REPLICA jitter seed at the hub seam (full jitter): two
        # replicas sharing one RetryPolicy CONFIG must not share the
        # jitter STREAM — a shared default seed makes their backoff
        # trains lockstep, so every retry wave from every replica lands
        # on a recovering hub at the same instant. Derived from process
        # + instance identity unless the caller pins one (tests).
        if jitter_seed is None:
            import os as _os
            import random as _random

            jitter_seed = (_random.SystemRandom().randrange(1 << 30)
                           ^ _os.getpid() ^ (id(self) & 0xFFFF))
        self._jitter_seed = int(jitter_seed)
        #: bounded-backoff policy shared by the transport seams; ``sleep``
        #: injectable so fake-clock tests never block
        self._transport_retry = RetryPolicy(
            max_retries=rc.transport_retries,
            base_s=rc.retry_backoff_base_s,
            max_s=rc.retry_backoff_max_s,
            jitter=rc.retry_jitter,
            seed=self._jitter_seed,
            sleep=retry_sleep,
        )
        #: hub GET for the ambiguous-bind read-your-write verification
        #: (``key -> Pod | None``, raising on transport failure). None =
        #: no reader: an ambiguous bind parks on the assume TTL instead
        #: (the watch confirm / TTL reap resolve it eventually).
        self.pod_reader = pod_reader
        #: bounded verification GETs per ambiguous bind, full jitter on
        #: the same per-replica stream
        self._bind_verify_retry = RetryPolicy(
            max_retries=rc.bind_verify_retries,
            base_s=rc.retry_backoff_base_s,
            max_s=rc.retry_backoff_max_s,
            jitter=rc.retry_jitter,
            seed=self._jitter_seed + 1,
            sleep=retry_sleep,
        )
        #: ambiguous binds whose verification GET was itself unreachable:
        #: key -> (pod, node_name, cycle_state). The pod stays ASSUMED
        #: (capacity held, no TTL) — requeueing it could re-bind a pod
        #: the hub already committed — and every cycle / idle tick
        #: re-probes until the hub answers (_verify_ambiguous_binds).
        self._ambiguous_binds: Dict[str, Tuple] = {}
        #: state-conservation auditor (obs/audit.py) — None until
        #: attach_auditor; when attached, legitimate pod exits (watch
        #: deletes, terminating skips, reconcile drops) are reported so
        #: conservation never counts them lost
        self.auditor = None
        for e in self.extenders:
            # wire retry + fault + observability hooks into transports
            # that expose the seam (HTTPExtender); duck-typed so test
            # fakes stay valid
            if getattr(e, "retry", "absent") is None:
                e.retry = self._transport_retry
            if (fault_injector is not None
                    and getattr(e, "fault_injector", "absent") is None):
                e.fault_injector = fault_injector
            if getattr(e, "obs", "absent") is None:
                e.obs = self.obs
            if getattr(e, "_clock_defaulted", False):
                e._clock = clock
                e._clock_defaulted = False
        #: per-target circuit breakers ("solver:batch",
        #: "extender:<url>"), created lazily against this clock
        self._breakers: Dict[str, CircuitBreaker] = {}
        #: absolute deadline of the cycle in flight (None = unbounded)
        self._cycle_deadline: Optional[float] = None
        #: cycles slower than this log their step trace (utiltrace
        #: LogIfLong; default is cycle-scale, not the reference's per-pod
        #: 100ms, since one cycle schedules a whole batch). A provided
        #: ObservabilityConfig owns the knob; the legacy ctor param stays
        #: the fallback.
        self.trace_threshold_s = (
            observability.trace_threshold_s if observability is not None
            else trace_threshold_s
        )
        #: enabled-predicate bitmask (config.Policy.predicate_mask);
        #: None = every implemented predicate enforced
        self.pred_mask = pred_mask
        #: per-pod CycleState, alive from prefilter to bind/fail
        self._cycle_states: Dict[str, object] = {}
        self.cache = cache or SchedulerCache(
            clock=clock,
            lock_factory=(self.lock_sanitizer.factory()
                          if self.lock_sanitizer is not None else None))
        # the device-snapshot chaos seam rides the same injector as the
        # solver/transport seams (duck-typed attach, like the extenders)
        if (fault_injector is not None
                and getattr(self.cache, "fault_injector", "absent") is None):
            self.cache.fault_injector = fault_injector
        # the device-memory ledger's cache seam (obs/memledger.py):
        # resident-table / score-plane byte registrations ride the
        # cache's own upload/drop edges (duck-typed attach, like the
        # injector above — cache fakes without the attribute stay valid)
        if getattr(self.cache, "memledger", "absent") is None:
            self.cache.memledger = self.obs.memledger
        #: pipelined cycle executor: batches larger than pipeline_chunk
        #: split into fixed-size chunks; depth >= 2 overlaps host packing
        #: of chunk k+1 and binding of chunk k-1 with chunk k's device
        #: solve (JAX async dispatch). Depth 1 keeps today's monolithic
        #: cycle — the seqref-parity mode.
        self.pipeline_depth = pipeline_depth
        self.pipeline_chunk = pipeline_chunk
        #: device-resident snapshot: keep the packed NodeTable on device
        #: across cycles, patching dirty rows with a jitted scatter
        self.device_resident_snapshot = device_resident_snapshot
        if snapshot_max_dirty_frac is not None:
            self.cache.max_dirty_frac = snapshot_max_dirty_frac
        #: AOT warmup config (config.WarmupConfig or None)
        from kubernetes_tpu.config import (
            IncrementalConfig,
            ParallelConfig,
            WarmupConfig,
        )

        self.warmup_config = warmup if warmup is not None else WarmupConfig()
        #: incremental solve (config.IncrementalConfig): steady-state
        #: cycles cost O(churn) — candidate columns come from the
        #: device-resident score cache (cache.score_summary, patched per
        #: delta), the solve restricts to a bounded (P, C) plane, and
        #: Sinkhorn potentials warm-start across cycles. The cold dense
        #: solve stays the correctness fallback (docs/perf.md).
        self.incremental = (incremental if incremental is not None
                            else IncrementalConfig())
        #: warm Sinkhorn potential carry: (key, (u, v)) where key is
        #: (pod bucket, candidate bucket, cache.summary_generation) —
        #: any invalidation edge (takeover, device loss, epoch growth,
        #: full rebuild) bumps the generation and the carry dies with it
        self._sk_warm_pot = None
        #: restricted service has engaged since the last invalidation —
        #: the signal that makes an invalidation drop COUNTABLE (see
        #: _drop_incremental)
        self._incr_active = False
        #: candidate-bucket auto-tuner state (incremental.auto_tune):
        #: the warmed-C ladder _warm_incremental compiled (the tuner
        #: may ONLY pick from this set — an unwarmed C would retrace on
        #: the hot path, breaking the zero-retrace contract), the
        #: recent raw micro-batch sizes (sliding window, host ints),
        #: and the deepest candidate-frame position any restricted
        #: solve placed into (the placement-rank telemetry — one
        #: device-side scalar riding the solve-result readback)
        self._warmed_cbuckets: set = set()
        self._tuner_batch_obs: List[int] = []
        self._tuner_depth_max = 0
        #: sharded execution backend (config.ParallelConfig): when the
        #: mesh is on, the node axis of the resident snapshot — and with
        #: it the (P, N) plane of every solve/validate/explain kernel —
        #: shards across a 1-D device mesh built HERE, at construction;
        #: pods/selector/topology/volume tables replicate (_place) and
        #: GSPMD inserts the collectives (parallel/mesh.py design). Off
        #: ("off", the default) never touches the backend.
        self.parallel = parallel if parallel is not None else ParallelConfig()
        from kubernetes_tpu.parallel.mesh import mesh_from_spec, mesh_size

        self.mesh = mesh_from_spec(self.parallel.mesh)
        set_mesh = getattr(self.cache, "set_mesh", None)
        if set_mesh is not None:  # duck-typed: cache fakes stay valid
            set_mesh(self.mesh)
        mesh_gauge = getattr(self.metrics, "mesh_devices", None)
        if mesh_gauge is not None:  # duck-typed: metrics fakes stay valid
            mesh_gauge.set(mesh_size(self.mesh))
        self.obs.note_mesh(mesh_size(self.mesh))
        #: whether THIS cycle's device tables live on the mesh (False
        #: during the device-loss cooloff, when snapshots fall back to
        #: single-device host mode — a lost shard must not keep pulling
        #: the whole mesh into every upload)
        self._mesh_live = False
        # explicit None check: SchedulingQueue defines __len__, so a
        # caller-provided EMPTY queue is falsy and `queue or ...` would
        # silently replace it with a fresh one
        self.queue = queue if queue is not None else SchedulingQueue(
            clock=clock, less=self.framework.queue_sort_less(),
            metrics=self.metrics,
        )
        # an externally built queue gets this scheduler's metrics so the
        # queue-observability surface (incoming counters, sub-queue age
        # histograms, mutation-fresh pending_pods gauges) stays live;
        # duck-typed so queue fakes without the attribute stay valid
        if getattr(self.queue, "metrics", "absent") is None:
            self.queue.metrics = self.metrics
        # the journey tracer rides the queue's residency seams (add /
        # sub-queue transitions / pop) — same duck attach as metrics
        if getattr(self.queue, "journeys", "absent") is None:
            self.queue.journeys = self.obs.journeys
        # the incident bundles embed the queue depths at trigger time
        if getattr(self.obs, "incidents", None) is not None:
            self.obs.incidents.queue_snapshot = self.queue.pending_counts
        #: latest explanation per still-pending pod (the /debug/why
        #: surface): updated each cycle from the UnschedulableReport,
        #: dropped when the pod binds or leaves
        self.why_pending: Dict[str, object] = {}
        #: the most recent cycle's UnschedulableReport (cluster summary)
        self.last_explain = None
        #: reason labels ever exported on the unschedulable gauges —
        #: lets a cycle zero out reasons that stopped firing
        self._explain_reasons_seen: set = set()
        #: node-search truncation (percentageOfNodesToScore): None =
        #: evaluate every node (the dense solver's natural mode); 0 =
        #: the reference's adaptive 50%→5% rule; 1-99 = fixed percent.
        #: Truncated cycles restrict the solve to the next K nodes in
        #: zone round-robin order (NodeTree) so consecutive cycles sweep
        #: different zones, like the reference's resumable enumeration.
        self.percentage_of_nodes_to_score = percentage_of_nodes_to_score
        self.node_tree = NodeTree()
        self.binder = binder or RecordingBinder()
        self.weights = weights
        self.solver = solver
        #: scenario pack (config.ScenarioConfig -> scenarios.resolve_pack):
        #: a pack swaps the solve objective — its weight override lands
        #: HERE so every ladder tier (and warmup) sees the scenario
        #: weights, and its (P, N) cost term joins extra_score per cycle
        #: (docs/scenarios.md). None = stock objective, zero overhead.
        from kubernetes_tpu.config import ScenarioConfig
        from kubernetes_tpu.scenarios import resolve_pack

        self.scenario = scenario if scenario is not None else ScenarioConfig()
        self.scenario_pack = resolve_pack(self.scenario)
        if self.scenario_pack is not None:
            self.weights = self.scenario_pack.weights(self.weights)
        #: score labels ever exported on the scenario-quality gauge —
        #: lets a cycle zero scores that stopped being reported (e.g.
        #: gang_locality after a gangless cycle), same freshness rule
        #: as the explain reason gauges
        self._scenario_scores_seen: set = set()
        if self.incremental.enabled:
            # arm the device-resident score cache, pinned to THIS
            # scheduler's Policy and objective: candidate eligibility
            # honors the node-condition predicates only when the Policy
            # enforces them (a permissive Policy's cold solve admits
            # pressured nodes — candidates must too), and the ranking
            # flips fullest-first under a packing objective
            from kubernetes_tpu.ops.predicates import BIT as _BIT
            from kubernetes_tpu.ops.priorities import DEFAULT_WEIGHTS

            cond_names = ("CheckNodeCondition", "CheckNodeUnschedulable",
                          "CheckNodeMemoryPressure",
                          "CheckNodeDiskPressure", "CheckNodePIDPressure")
            honor = self.pred_mask is None or all(
                self.pred_mask & (1 << _BIT[n]) for n in cond_names)
            w = self.weights if self.weights is not None else DEFAULT_WEIGHTS
            packed = (w.get("MostRequestedPriority", 0)
                      > w.get("LeastRequestedPriority", 0))
            self._summary_flags = {"honor_conditions": honor,
                                   "prefer_packed": packed}
            enable = getattr(self.cache, "enable_score_cache", None)
            if enable is not None:  # duck-typed: cache fakes stay valid
                enable(honor_conditions=honor, prefer_packed=packed)
        else:
            self._summary_flags = {"honor_conditions": True,
                                   "prefer_packed": False}
        #: count of exact->round auto-fallbacks (port/volume/topology batches)
        self.exact_fallbacks = 0
        #: NonPreemptingPriority feature gate: honor preemption_policy=Never
        self.enable_non_preempting = enable_non_preempting
        self.per_node_cap = per_node_cap
        self.max_rounds = max_rounds
        self.max_batch = max_batch
        self.clock = clock
        #: event_sink(reason, pod, message) — Scheduled / FailedScheduling /
        #: Preempted (scheduler.go:274,:335,:457); wired to the events
        #: recorder by the host shim.
        self.event_sink = event_sink or (lambda *_: None)
        # the SLO watchdog (obs/ledger.py) emits SchedulerSLOBurn /
        # SchedulerSLORecovered through the same recorder sink as every
        # other scheduler event — late-bound so a sink attached after
        # construction still receives them
        self.obs.ledger.event_sink = (
            lambda reason, obj, msg: self.event_sink(reason, obj, msg))
        self.enable_preemption = enable_preemption
        self.max_preemptions_per_cycle = max_preemptions_per_cycle
        #: PDBs come from a lister (the disruption controller maintains
        #: their status in the reference; here the hub/sim supplies them)
        self.pdb_lister = pdb_lister or (lambda: [])
        #: victim_deleter(pod): issue the victim's deletion. Default: mark
        #: terminating and remove from cache immediately (grace period 0).
        #: A hub integration instead posts the delete and lets the watch
        #: remove it, keeping the victim visible as terminating meanwhile.
        self.victim_deleter = victim_deleter
        #: repack_evictor(pod): issue a steady-state re-pack drain for a
        #: BOUND pod (scenario.repack_interval_s). Default: unbind
        #: locally and requeue (sim-style, zero-grace). A hub
        #: integration instead posts the unbind/delete+recreate and
        #: lets the watch stream converge the local state.
        self.repack_evictor = repack_evictor
        #: clock of the last re-pack sweep; None = cadence not started
        #: (the first interval elapses before the first drain)
        self._last_repack_at: Optional[float] = None
        #: delayed-binding PVC lifecycle (volume_binder.go:30): assume at
        #: assume time, commit at bind time, roll back on any forget
        from kubernetes_tpu.volumes import VolumeBinder

        self.volume_binder = volume_binder or VolumeBinder(self.cache.packer)
        #: serving doorbell (serving/doorbell.py) — None until a serving
        #: loop attaches one via attach_doorbell
        self.doorbell = None
        #: the ladder tier that produced the most recent non-empty
        #: cycle ("" before the first solve) and how many tier-to-tier
        #: fallbacks that cycle took — the backend_pressure probe reads
        #: the FALLBACK count to tell a healthy backend from a limping
        #: one (tier NAME comparison would misread the exact solver's
        #: deliberate hazard routing to "batch" as degradation)
        self.last_solver_tier = ""
        self.last_solver_fallbacks = 0

    @classmethod
    def from_config(cls, cfg, **kw) -> "Scheduler":
        """Build a Scheduler from a KubeSchedulerConfiguration — the
        CreateFromProvider / CreateFromConfig seam (factory.go:346,:356)."""
        from kubernetes_tpu.config import (
            default_predicate_mask,
            default_priority_weights,
        )

        if cfg.policy is not None:
            kw.setdefault("pred_mask", cfg.policy.predicate_mask)
            kw.setdefault("weights", dict(cfg.policy.priority_weights))
            if cfg.policy.extenders:
                from kubernetes_tpu.extender import build_extenders

                kw.setdefault("extenders", build_extenders(cfg.policy.extenders))
        else:
            kw.setdefault("pred_mask", default_predicate_mask(cfg.feature_gates))
            kw.setdefault("weights", default_priority_weights(cfg.feature_gates))
        kw.setdefault("solver", cfg.solver)
        kw.setdefault(
            "enable_non_preempting",
            cfg.feature_gates.enabled("NonPreemptingPriority"),
        )
        kw.setdefault("per_node_cap", cfg.per_node_cap)
        kw.setdefault("max_rounds", cfg.max_rounds)
        kw.setdefault("max_batch", cfg.max_batch)
        kw.setdefault("scheduler_name", cfg.scheduler_name)
        kw.setdefault("robustness", cfg.robustness)
        kw.setdefault("recovery", cfg.recovery)
        kw.setdefault("observability", cfg.observability)
        kw.setdefault("pipeline_depth", cfg.pipeline_depth)
        kw.setdefault("pipeline_chunk", cfg.pipeline_chunk)
        kw.setdefault("device_resident_snapshot", cfg.device_resident_snapshot)
        kw.setdefault("snapshot_max_dirty_frac", cfg.snapshot_max_dirty_frac)
        kw.setdefault("warmup", cfg.warmup)
        kw.setdefault("parallel", cfg.parallel)
        kw.setdefault("scenario", cfg.scenario)
        kw.setdefault("incremental", cfg.incremental)
        if getattr(cfg, "plugins", ()) and "framework" not in kw:
            # config-driven framework assembly (the NewFramework path,
            # framework.go:88: registry factories + per-plugin args from
            # PluginConfig). Unknown names fail loudly like the
            # reference's NewFramework does.
            from kubernetes_tpu.framework import PLUGIN_REGISTRY, Framework

            built = []
            for name in cfg.plugins:
                factory = PLUGIN_REGISTRY.get(name)
                if factory is None:
                    raise ValueError(
                        f"plugins: {name!r} is not registered "
                        f"(known: {sorted(PLUGIN_REGISTRY)})"
                    )
                built.append(factory(dict(cfg.plugin_config.get(name, {}))))
            kw["framework"] = Framework(
                built, clock=kw.get("clock", time.monotonic))
        # 100 (the config default) = no truncation; 0 = the reference's
        # adaptive rule; 1-99 fixed — passed through verbatim so the
        # adaptive mode stays expressible from config
        kw.setdefault(
            "percentage_of_nodes_to_score",
            None
            if cfg.percentage_of_nodes_to_score >= 100
            else cfg.percentage_of_nodes_to_score,
        )
        return cls(**kw)

    # -- ingestion (AddAllEventHandlers analog; the informer pump or test
    # drives these) --------------------------------------------------------

    def responsible_for(self, pod: Pod) -> bool:
        """eventhandlers.go:328 responsibleForPod: spec.schedulerName must
        name THIS scheduler for its unassigned pods to be queued here."""
        return pod.scheduler_name == self.scheduler_name

    def on_pod_add(self, pod: Pod) -> None:
        """eventhandlers.go:215/:256 — unassigned pods queue for scheduling
        (only this scheduler's, per the informer FilterFunc); assigned pods
        enter the cache whoever bound them, and may unblock affinity
        waiters. Terminal pods never enter: the reference scheduler's pod
        informer lists with ``status.phase!=Succeeded,status.phase!=Failed``
        (factory.go NewPodInformer nonTerminatedPodSelector) — enforced at
        this sink so EVERY feed (in-process emit, Reflector, gRPC bridge)
        gets the same view without each needing the selector."""
        from kubernetes_tpu.api.types import is_pod_terminated

        with annotate("on_pod_add"):
            if is_pod_terminated(pod):
                return
            if pod.node_name:
                self.cache.add_pod(pod)
                self.queue.assigned_pod_added(pod)
            elif self.responsible_for(pod):
                self.queue.add(pod)

    def on_pod_update(self, old: Pod, new: Pod) -> None:
        from kubernetes_tpu.api.types import is_pod_terminated

        with annotate("on_pod_update"):
            if is_pod_terminated(new):
                # terminal phase hop: the field-selected informer delivers
                # this as a DELETE (the pod left the selector) — its node
                # capacity is released even on feeds without the selector
                # (the gRPC snapshot bridge, a selector-less Reflector)
                self.on_pod_delete(new)
                return
            if new.node_name:
                # a Permit-parked pod bound by another writer must leave the
                # waiting map BEFORE cache.add_pod flips its state to ADDED —
                # otherwise _process_waiting later calls forget_pod on a
                # non-assumed pod and aborts the whole cycle (same cleanup
                # on_pod_delete does for parked pods)
                wp = self.framework.waiting.get(new.key())
                if wp is not None:
                    self.framework.waiting.remove(new.key())
                    self.volume_binder.forget_pod_volumes(new.key())
                    self.framework.run_unreserve(
                        self._cycle_states.get(new.key())
                        or _new_cycle_state(),
                        wp.pod, wp.node_name,
                    )
                self._cycle_states.pop(new.key(), None)
                # add_pod (not update_pod): an unassigned->assigned transition
                # must CONFIRM a pending assumption, or the TTL would expire a
                # successfully bound pod and double-book its capacity
                self.cache.add_pod(new)
                # ... and must LEAVE the scheduling queue: the reference's
                # unassigned-pod informer filter turns this transition into a
                # queue delete (eventhandlers.go addAllEventHandlers pod
                # FilterFunc). Without it, a pod bound by another writer (HA
                # peer, competing scheduler) would be scheduled again here and
                # double-booked.
                self.queue.delete(new.key())
                # journey: our own bind already completed it at the success
                # tail (this no-ops); a COMPETING writer's bind closes it
                # here as gone — it never bound through this scheduler
                self.obs.journeys.note_gone(new.key())
                # AssignedPodUpdated: wake only affinity-matching waiters, not
                # the whole unschedulableQ (eventhandlers.go)
                self.queue.assigned_pod_added(new)
            elif self.responsible_for(new):
                if new != old:
                    # a pending pod updated IN PLACE (same uid — labels or
                    # selector edited through PATCH): the packer's per-pod
                    # ref cache and the pack-table memo are keyed by
                    # (key, uid) + universe signature, and a changed spec
                    # whose values are all already interned moves neither —
                    # forget the pod so the next pack re-interns and the
                    # memoized tables (pack epoch) invalidate
                    self.cache.packer.forget_pod(new.key())
                self.queue.update(old.key(), new)
            elif self.responsible_for(old):
                # responsible -> not-responsible transition: the reference's
                # FilteringResourceEventHandler turns this into a Delete, so
                # the stale spec must leave our queues (schedulerName is
                # immutable in the real API, but this ingestion surface takes
                # arbitrary updates). Pod-keyed side state must leave with it
                # or it outlives the pod (the soak sentinels watch exactly
                # these dicts for monotonic growth)
                self.queue.delete(old.key())
                self._cycle_states.pop(old.key(), None)
                self.why_pending.pop(old.key(), None)
                self._note_gone(old.key())

    def on_pod_delete(self, pod: Pod) -> None:
        with annotate("on_pod_delete"):
            key = pod.key()
            self._note_gone(key)
            # a bind whose ambiguous verification was parked resolves by
            # deletion: the pod is gone whatever the RPC did — release the
            # held assumption (parked pods carry no TTL, so nothing else
            # would free this capacity)
            parked = self._ambiguous_binds.pop(key, None)
            if parked is not None and self.cache.is_assumed(key):
                apod, anode, ast = parked
                self.cache.forget_pod(key)
                self.volume_binder.forget_pod_volumes(key)
                self.framework.run_unreserve(
                    ast or _new_cycle_state(), apod, anode)
            # a Permit-parked pod is assumed in the cache and holds
            # capacity — deletion must release both the wait entry and the
            # assumption
            wp = self.framework.waiting.get(key)
            if wp is not None:
                self.framework.waiting.remove(key)
                self.cache.forget_pod(key)
                self.volume_binder.forget_pod_volumes(key)
                self.framework.run_unreserve(
                    self._cycle_states.get(key) or _new_cycle_state(), wp.pod,
                    wp.node_name,
                )
            if pod.node_name:
                with annotate("cache.remove_pod"):
                    self.cache.remove_pod(key)
                with annotate("queue.move_all_to_active"):
                    self.queue.move_all_to_active()
            else:
                self.queue.delete(key)
            with annotate("packer.forget_pod"):
                self.cache.packer.forget_pod(key)
            self._cycle_states.pop(key, None)
            self.why_pending.pop(key, None)

    def on_node_add(self, node) -> None:
        with annotate("on_node_add"):
            self.cache.add_node(node)
            self.node_tree.add_node(node)
            self.queue.move_all_to_active()

    def on_node_update(self, node) -> None:
        with annotate("on_node_update"):
            old = self.cache.node(node.name)
            if old is not None:
                self.node_tree.remove_node(old)
            self.cache.update_node(node)
            self.node_tree.add_node(node)
            self.queue.move_all_to_active()

    def on_node_delete(self, name: str) -> None:
        with annotate("on_node_delete"):
            old = self.cache.node(name)
            if old is not None:
                self.node_tree.remove_node(old)
            self.cache.remove_node(name)

    def set_volume_state(self, pvcs=(), pvs=(), classes=()) -> None:
        """PV/PVC/StorageClass informer feed. Any volume-state change can
        make pods schedulable, so the unschedulable queue resweeps (the
        reference moves on PV/PVC add/update events, eventhandlers.go).
        The cached node snapshot is invalidated: scheduled pods' volume
        tokens (NodeTable.pd_mh/csi_mh/vol_*_mh) depend on PVC->PV
        resolution, which just changed under them."""
        self.cache.packer.set_volume_state(pvcs, pvs, classes)
        self.cache.invalidate_snapshot()
        self.queue.move_all_to_active()

    def set_attached_residue(self, residue) -> None:
        """Actual-state feed from the attach-detach controller
        (attach_detach_controller.go:102): per-node PV names attached
        WITHOUT a live pod deriving them (detach-grace stragglers). They
        occupy attach-limit slots, so the snapshot is invalidated and —
        since a detach can free a slot a pending pod was waiting on —
        unschedulables resweep like any volume-state change."""
        self.cache.packer.attached_residue = dict(residue)
        self.cache.invalidate_snapshot()
        self.queue.move_all_to_active()

    # -- crash / failover / device-loss recovery ---------------------------

    def attach_elector(self, elector, lister=None):
        """Wire leader election into the scheduler's recovery protocol:
        the elector becomes the bind fence (its ``allow_bind`` gates
        every hub write when ``recovery.fenced_binds``), gaining
        leadership runs takeover reconciliation (:meth:`reconcile`), and
        losing it drains in-flight state (:meth:`on_stopped_leading`).
        ``lister`` (optional, ``() -> iterable of truth Pods``) gives the
        reconciliation an authoritative relist source; without one the
        informer feed is trusted and reconciliation is local-only.
        Pre-existing elector callbacks are preserved (chained after
        ours). Returns the elector."""
        self.fence = elector
        self._lister = lister
        prev_start = elector.on_started_leading
        prev_stop = elector.on_stopped_leading

        def started():
            self.on_started_leading()
            prev_start()

        def stopped():
            self.on_stopped_leading()
            prev_stop()

        elector.on_started_leading = started
        elector.on_stopped_leading = stopped
        return elector

    def attach_auditor(self, auditor):
        """Wire a state-conservation auditor (obs/audit.py): the
        scheduler reports legitimate pod exits (watch deletes,
        terminating skips, reconcile drops) via ``note_gone`` so the
        auditor's per-audit conservation rule never counts an explained
        exit as a lost pod. Attaches metrics / event sink / obs when the
        auditor has none. Returns the auditor."""
        self.auditor = auditor
        if getattr(auditor, "metrics", "absent") is None:
            auditor.metrics = self.metrics
        if getattr(auditor, "event_sink", "absent") is None:
            auditor.event_sink = (
                lambda reason, obj, msg: self.event_sink(reason, obj, msg))
        if getattr(auditor, "obs", "absent") is None:
            auditor.obs = self.obs
        return auditor

    def _note_gone(self, key: str) -> None:
        """A pod legitimately left the state machine — tell the
        attached auditor (no-op without one) and close its journey."""
        if self.auditor is not None:
            self.auditor.note_gone(key)
        self.obs.journeys.note_gone(key)

    def on_started_leading(self) -> None:
        """OnStartedLeading (app/server.go:261): this incarnation just
        became the writer. Reconcile before the first cycle so a crash
        of the previous leader between its hub commit and its local
        ``finish_binding`` converges instead of leaking."""
        if not self.recovery.reconcile_on_takeover:
            return
        pods = None
        if self._lister is not None:
            pods = list(self._lister())
        self.reconcile(pods)

    def on_stopped_leading(self) -> None:
        """Deposed (lease lost or released): drain in-flight cycle
        state. Permit-parked pods are rejected and requeued (their
        capacity would otherwise be held forever — the fence blocks
        their eventual bind anyway), local assumptions are forgotten and
        their pods requeued (if the bind DID commit at the hub, the
        watch MODIFIED event deletes them from the queue; if it did
        not, the new leader — or this one, re-elected — binds them).
        The queues themselves stay: informers run on standbys."""
        import dataclasses as _dc

        fw = self.framework
        drained = 0
        res = CycleResult()
        for wp in list(fw.waiting.items()):
            key = wp.pod.key()
            fw.waiting.remove(key)
            self.cache.forget_pod(key)
            self.volume_binder.forget_pod_volumes(key)
            fw.run_unreserve(
                self._cycle_states.get(key) or _new_cycle_state(),
                wp.pod, wp.node_name)
            self._fail(wp.pod, self.queue.scheduling_cycle, res,
                       ("Permit:lost leadership",))
            self._cycle_states.pop(key, None)
            drained += 1
        # ambiguous-bind parks are assumed pods too: the sweep below
        # drains the assumption; the NEW leader's reconcile resolves
        # what the hub actually committed (its relist truth is the
        # read-your-write answer)
        self._ambiguous_binds.clear()
        for key in self.cache.assumed_keys():
            pod = self.cache.pod(key)
            self.cache.forget_pod(key)
            self.volume_binder.forget_pod_volumes(key)
            # the per-attempt cycle state dies with the assumption: the
            # requeued pod starts a fresh attempt, and a row kept here
            # survives every later leadership flip (leak, sentinel-pinned)
            self._cycle_states.pop(key, None)
            if pod is not None and self.responsible_for(pod):
                self.queue.add_if_not_present(
                    _dc.replace(pod, node_name=""))
            drained += 1
        if drained:
            klog.warning("stopped leading: drained %d in-flight pods",
                         drained)
            self.metrics.recovery_drained.inc(drained)
            self._record_metrics(res)

    def reconcile(self, pods=None) -> Dict[str, int]:
        """Takeover / cold-start reconciliation — converge local state
        with the hub truth so the invariant triple holds across a crash:
        no pod double-bound, no assumption leaked, every schedulable pod
        eventually bound.

        With ``pods`` (the relisted truth): adopt bound pods this cache
        does not know (bound by a dead incarnation or another writer),
        forget assumptions the API contradicts (pod gone, recreated
        under a new uid, or bound elsewhere), and requeue responsible
        unbound pods that fell out of the queues. Always: resweep the
        unschedulable queue, drop + rebuild the device-resident
        snapshot (a new leader's resident arrays may predate the old
        leader's last commits; after a crash they don't exist), and
        re-arm the AOT warmup. Returns the action counts."""
        from kubernetes_tpu.api.types import is_pod_terminated

        adopted = forgotten = requeued = 0
        if pods is not None:
            # the relisted truth IS the read-your-write answer for any
            # parked ambiguous bind — the assumed-keys sweep below
            # settles them (adopt or forget), so the parks are moot.
            # Truthless reconciles keep them parked: clearing without a
            # verdict would leak the TTL-less assumption forever.
            self._ambiguous_binds.clear()
            truth = {p.key(): p for p in pods}
            for key in list(self.cache.assumed_keys()):
                cached = self.cache.pod(key)
                tp = truth.get(key)
                ok = (
                    tp is not None
                    and tp.node_name
                    and cached is not None
                    and tp.uid == cached.uid
                    and tp.node_name == cached.node_name
                )
                if ok:
                    # truth agrees with the assumption: the bind DID
                    # commit (possibly by our dead predecessor) —
                    # confirm it instead of waiting out the TTL
                    self.cache.add_pod(tp)
                    adopted += 1
                else:
                    self.cache.forget_pod(key)
                    self.volume_binder.forget_pod_volumes(key)
                    forgotten += 1
            for key, tp in truth.items():
                if is_pod_terminated(tp):
                    continue
                if tp.node_name:
                    cached = self.cache.pod(key)
                    if cached is None or cached.uid != tp.uid \
                            or cached.node_name != tp.node_name:
                        if cached is not None:
                            self.cache.remove_pod(key)
                        self.cache.add_pod(tp)
                        adopted += 1
                    # bound at the hub: whatever a stale queue thinks,
                    # this pod must never be scheduled again here — and
                    # its pending-explanation row retires with it (the
                    # normal bind paths pop it; adoption must too)
                    self.queue.delete(key)
                    self.why_pending.pop(key, None)
                    self._cycle_states.pop(key, None)
                    # a reconcile-adopted bind never went through THIS
                    # incarnation's bind tail: close the journey as
                    # gone (no bogus e2e sample, no bound outcome)
                    self.obs.journeys.note_gone(key)
                elif self.responsible_for(tp):
                    queued = self.queue.pod(key)
                    if (queued is not None and queued.uid == tp.uid) \
                            or self.framework.waiting.get(key) is not None:
                        continue  # already queued/parked with the live uid
                    if self.cache.pod(key) is not None:
                        # we think it's placed, the API says unbound:
                        # a half-crashed bind — forget and retry
                        if self.cache.is_assumed(key):
                            self.volume_binder.forget_pod_volumes(key)
                        self.cache.remove_pod(key)
                        forgotten += 1
                    if queued is not None:
                        # recreated under the same key with a new uid:
                        # the stale queued object must never be adopted
                        # or bound — the truth object replaces it
                        self.queue.delete(key)
                    self.queue.add_if_not_present(tp)
                    requeued += 1
            # pods the truth no longer contains must leave the queues
            # (duck-typed: queue fakes without the dump surface skip)
            pp = getattr(self.queue, "pending_pods", None)
            if pp is not None:
                for qpods in pp().values():
                    for p in qpods:
                        if p.key() not in truth:
                            self.queue.delete(p.key())
                            self._note_gone(p.key())
                            # exit path parity with on_pod_delete: the
                            # pod-keyed side state leaves with the pod,
                            # or churn between relists grows it forever
                            self._cycle_states.pop(p.key(), None)
                            self.why_pending.pop(p.key(), None)
                            self.cache.packer.forget_pod(p.key())
        # local convergence, truth or not: resweep parked pods (this
        # incarnation may have missed move events), rebuild the
        # device-resident snapshot from the host mirror, re-warm
        self.queue.move_all_to_active()
        self.cache.invalidate_snapshot()
        self.cache.drop_device_snapshot()
        # warm-solve state (score cache already died with the resident
        # table; potentials must die too — they summarize a plane the
        # old incarnation solved, not the relisted truth)
        self._drop_incremental("takeover")
        self._device_cooloff_until = 0.0
        epoch = getattr(self.fence, "epoch", 0) or 1
        self.metrics.recovery_takeovers.inc()
        if adopted:
            self.metrics.recovery_adopted.inc(adopted)
        if forgotten:
            self.metrics.recovery_forgotten.inc(forgotten)
        if requeued:
            self.metrics.recovery_requeued.inc(requeued)
        self.obs.note_takeover(epoch)
        klog.V(2).info(
            "takeover reconciliation (epoch %d): adopted=%d forgotten=%d "
            "requeued=%d", epoch, adopted, forgotten, requeued)
        if self.warmup_config.enabled and self.cache.node_count():
            # re-arm AOT warmup: the jit cache survives in-process
            # re-election (cheap no-op), but a cold-started incarnation
            # recompiles here instead of on the first cycle's hot path
            pp = getattr(self.queue, "pending_pods", None)
            sample = pp().get("active", [])[:64] if pp else []
            self.warmup(sample_pods=sample)
        return {"adopted": adopted, "forgotten": forgotten,
                "requeued": requeued}

    def _fence_ok(self) -> bool:
        """May a hub write (assume -> bind) go out now? Unfenced
        schedulers (no elector attached / fencing disabled) always may."""
        if self.fence is None or not self.recovery.fenced_binds:
            return True
        return self.fence.allow_bind()

    def _fenced(self, pod: Pod, cycle: int, res: CycleResult) -> None:
        """Abort one pod's bind at the fence: count it, flag the flight
        record, requeue through the standard error path (the NEW leader
        binds it; this one must not race the hub CAS)."""
        self.metrics.recovery_fenced_binds.inc()
        self.obs.note_fenced_bind()
        self.obs.journeys.note_fenced(pod.key())
        self._fail(pod, cycle, res, ("FencedBind:lease lost",))

    def _reap_expired_assumptions(self) -> None:
        """Drive cache TTL expiry and HANDLE the result (satellite of
        the recovery PR — both call sites previously discarded it): log,
        count, emit an AssumptionExpired event, and converge the pod.

        An expired assumption is the SAME ambiguity class as a timed-out
        bind: the commit very likely landed and only the watch
        confirmation was lost. With a ``pod_reader`` the expiry resolves
        by read-your-write verification — adopt a hub-confirmed binding,
        requeue only when verified unbound, park (re-assumed, no TTL)
        while the hub is unreachable — so the reap never blind-requeues
        a pod whose retry would re-bind at the hub. Without a reader the
        legacy optimistic path remains: requeue, and if the pod actually
        IS bound (watch merely slow) the eventual MODIFIED event deletes
        it from the queue; until then a re-bind attempt fails the hub
        CAS harmlessly."""
        import dataclasses as _dc

        expired = self.cache.pop_expired()
        if not expired:
            return
        self.metrics.cache_expired_assumptions.inc(len(expired))
        for p in expired:
            key = p.key()
            if self.pod_reader is not None:
                resolution = self._resolve_ambiguous_bind(p, p.node_name)
                self.metrics.bind_ambiguous.inc(
                    resolution=f"expired-{resolution or 'deferred'}")
                if resolution == "adopted":
                    # the hub HAS our binding — the confirmation was
                    # merely lost; re-add bound (capacity re-held)
                    self.cache.add_pod(p)
                    klog.V(2).info(
                        "assumed pod %s expired but the hub confirms "
                        "the binding to %s — adopted, not requeued",
                        key, p.node_name)
                    continue
                if resolution is None:
                    # verification unreachable too: park assumed (no
                    # TTL) and re-probe each cycle / idle tick — a
                    # requeue during a hub outage is exactly the blind
                    # retry the protocol forbids
                    self.cache.assume_pod(p, p.node_name)
                    self._ambiguous_binds[key] = (p, p.node_name, None)
                    self.obs.journeys.note_ambiguous_park(
                        key, "assume-expired")
                    klog.warning(
                        "assumed pod %s expired and verification is "
                        "unreachable; parked assumed", key)
                    continue
                if resolution in ("conflict", "gone"):
                    # deleted, recreated under a new uid, or bound by
                    # another writer: drop the stale local copy — the
                    # watch/relist delivers the truth object
                    self.volume_binder.forget_pod_volumes(key)
                    self._note_gone(key)
                    continue
                # "requeued": verified unbound — safe to retry below
            klog.warning(
                "assumed pod %s on %s expired (bind confirmation never "
                "arrived within %.0fs); requeueing", key, p.node_name,
                self.cache.ttl_s)
            self.volume_binder.forget_pod_volumes(key)
            pending = _dc.replace(p, node_name="")
            self.event_sink(
                "AssumptionExpired", pending,
                f"binding to {p.node_name} was never confirmed within "
                f"{self.cache.ttl_s:.0f}s; capacity freed, pod requeued")
            if self.responsible_for(pending):
                self.queue.add_if_not_present(pending)

    def _device_snapshot_recovering(self):
        """``cache.device_snapshot()`` with device-loss recovery: any
        error from the resident path (a lost/OOMed accelerator — or the
        injected ``snapshot:device`` chaos rules standing in for one)
        drops the resident arrays and rebuilds them from the host
        mirror, up to ``recovery.device_reset_limit`` attempts per
        cycle; past the budget the scheduler falls back to host-mode
        snapshots for ``device_cooloff_s`` (the ladder meanwhile absorbs
        solve failures: batch -> batch-cpu -> greedy), then probes the
        device again. Returns ``(table, dev_or_None, mode)`` exactly
        like the call sites expect (``dev=None`` + mode "host" on the
        fallback path)."""
        if self.clock() < self._device_cooloff_until:
            return self.cache.snapshot(), None, "host"
        attempts = 0
        while True:
            try:
                out = self.cache.device_snapshot()
                if attempts:
                    klog.V(2).info("device snapshot rebuilt after %d "
                                   "reset(s)", attempts)
                return out
            except Exception as e:
                attempts += 1
                self.metrics.recovery_device_resets.inc()
                self.obs.note_device_reset()
                # forensic snapshot BEFORE the drop below deregisters
                # the residents — the ranked record must show what was
                # on the device at the moment it was lost
                ml = self.obs.memledger
                if ml.enabled:
                    oomrec = ml.record_oom(
                        "snapshot:device", error=str(e),
                        cycle=self.queue.scheduling_cycle)
                    self.obs.note_oom_forensic(ml.oom_flag(oomrec))
                klog.warning("device snapshot failed (%s); dropping "
                             "resident table (reset %d/%d)", e, attempts,
                             self.recovery.device_reset_limit)
                self.cache.drop_device_snapshot()
                # the score cache died with the resident table; the
                # potential carry must not survive the device either
                self._drop_incremental("device-loss")
                if attempts > self.recovery.device_reset_limit:
                    self._device_cooloff_until = (
                        self.clock() + self.recovery.device_cooloff_s)
                    klog.warning(
                        "device snapshot rebuild budget exhausted; "
                        "host-mode snapshots for %.1fs",
                        self.recovery.device_cooloff_s)
                    return self.cache.snapshot(), None, "host"

    def _with_owner_columns(self, dn, pt):
        """``dn`` carrying the owner columns of pod batch ``pt``: per-node
        counts of the owner sets its pods spread by, from the cache's
        incrementally kept counts (SchedulerCache.owner_columns)."""
        width = int(pt.owner_match_mh.shape[1])
        if width == int(dn.owner_counts.shape[1]) and not len(pt.owner_cols):
            return dn
        with self.obs.span("snapshot:owner_columns"):
            cols = self.cache.owner_columns(pt.owner_cols, width,
                                            int(dn.valid.shape[0]))
            return attach_owner_columns(dn, cols)

    def _place(self, t):
        """Replicate a device pytree across the node-axis mesh —
        identity when the sharded backend is off OR this cycle fell
        back to single-device host-mode snapshots (device cooloff).
        The pod/selector/topology/volume tables all ride this: the
        (P, N) kernels then see replicated-P x sharded-N operands and
        GSPMD partitions them along N."""
        if t is None or not self._mesh_live:
            return t
        from kubernetes_tpu.parallel.mesh import replicate

        return replicate(t, self.mesh)

    # -- the cycle ---------------------------------------------------------

    def schedule_cycle(self, flush_trigger: str = "",
                       window_s: float = 0.0) -> CycleResult:
        """One batched scheduling pass over everything in activeQ.

        ``flush_trigger``/``window_s`` are the serving loop's micro-batch
        provenance (what flushed the accumulation window and how long it
        held) — threaded onto the CycleResult and the flight record so a
        latency incident can distinguish window time from solve time."""
        from kubernetes_tpu.ops.assign import (
            _apply_batch,
            nodes_with_usage,
            usage_from_nodes,
        )
        from kubernetes_tpu.ops.predicates import decode_reasons

        from kubernetes_tpu.framework import CycleState

        t0 = self.clock()
        res = CycleResult(flush_trigger=flush_trigger, window_s=window_s)
        # per-cycle deadline (robustness.cycle_deadline_s): propagated to
        # the solver ladder (skip-to-oracle once blown) and the extender
        # calls (shed) so one wedged dependency can't stall the queue
        self._cycle_deadline = (
            t0 + self.robustness.cycle_deadline_s
            if self.robustness.cycle_deadline_s > 0 else None
        )
        trace = self.obs.begin_cycle(self.queue.scheduling_cycle)
        if flush_trigger:
            self.obs.note_microbatch(flush_trigger, window_s)
        # everything before the snapshot: queue upkeep, the pop, PreFilter
        pop_span = trace.begin_span("pop")
        self.queue.tick()
        self._reap_expired_assumptions()
        self._verify_ambiguous_binds()
        # cadence re-pack BEFORE the batch pops: pods drained here
        # re-enter this same cycle's solve under the consolidation
        # objective instead of waiting out another interval
        self.maybe_repack()
        self._process_waiting(res)
        batch = self.queue.pop_batch(self.max_batch)
        if not batch:
            trace.end_span(pop_span)
            res.elapsed_s = self.clock() - t0
            self._record_metrics(res)
            self._explain_retire_if_drained()
            self.obs.end_cycle(res)
            return res
        cycle = self.queue.scheduling_cycle
        self.obs.note_cycle(cycle)
        # skipPodSchedule (scheduler.go:335): a pod already marked for
        # deletion is dropped from the cycle, not retried — its DELETED
        # event (kubelet kill or pod-GC) is the terminal outcome; the
        # auditor's conservation rule learns the exit NOW so the window
        # until that event is not read as a lost pod
        for p in batch:
            if p.deletion_timestamp:
                self._note_gone(p.key())
        batch = [p for p in batch if not p.deletion_timestamp]
        res.attempted = len(batch)
        fw = self.framework

        # PreFilter (framework.go RunPrefilterPlugins): any non-success
        # aborts that pod's cycle before it reaches the device
        kept = []
        for p in batch:
            st = CycleState()
            self._cycle_states[p.key()] = st
            status = fw.run_prefilter(st, p)
            if status.is_success():
                kept.append(p)
            else:
                self._fail(p, cycle, res, (f"PreFilter:{status.message}",))
        batch = kept
        trace.end_span(pop_span)
        if not batch:
            # every popped pod failed PreFilter: they still get report
            # rows (status reasons, no device analytics) and the reason
            # gauges roll over to this cycle instead of going stale
            res.elapsed_s = self.clock() - t0
            if getattr(self.obs.config, "explain", True):
                self._build_explain_report(
                    cycle, [], [], None, self.cache.node_count(), res)
            self._record_metrics(res)
            self.obs.end_cycle(res)
            return res

        # pack: pods first (their programs grow universes), then snapshot
        with self.obs.span("snapshot"):
            pk = self.cache.packer
            batch_keys = {p.key() for p in batch}
            nominated = self._nominated_pods(exclude=batch_keys)
            for p in batch:
                pk.intern_pod(p)
            for p, _ in nominated:
                pk.intern_pod(p)
            if self.device_resident_snapshot:
                # incremental device-resident snapshot: the packed node
                # table lives on device across cycles; dirty rows patch
                # in with a jitted scatter, full rebuilds only on shape/
                # width changes or explicit invalidation (cache.py).
                # Device errors recover via drop + host-mirror rebuild
                # (_device_snapshot_recovering — "host" mode fallback
                # while the device is cooling off)
                nt, dn, snap_mode = self._device_snapshot_recovering()
            else:
                nt = self.cache.snapshot()
                dn = None
                snap_mode = "host"
            node_order = self.cache.node_order()
            pt = pk.pack_pods(batch)
            # host-side feature gates: priorities whose inputs are absent
            # from THIS snapshot are replaced by their exact constants
            # inside the solve, and the port-conflict matmuls are skipped
            # for port-free batches (static jit keys;
            # ops/priorities.empty_priorities,
            # ops/predicates.pods_have_no_ports)
            gates = solver_gates(nt, pt)
            # mesh liveness for THIS cycle: resident snapshots come back
            # already sharded (cache.set_mesh); the legacy per-cycle
            # host pack re-places onto the mesh below; only the device-
            # loss cooloff (resident on, dev None) stays single-device —
            # a lost shard must not be re-engaged until the heal probe
            self._mesh_live = (self.mesh is not None
                               and (dn is not None
                                    or not self.device_resident_snapshot))
            key = self._solve_key(self.solver, gates)
            self.obs.note_mesh_cycle(
                int(self.mesh.devices.size) if self._mesh_live else 0)
            if dn is None:
                if self._mesh_live:
                    from kubernetes_tpu.parallel.mesh import (
                        place_node_table,
                    )

                    dn = place_node_table(nt, self.mesh)
                else:
                    dn = nodes_to_device(nt)
            use_pipeline = self._pipeline_eligible(batch, nominated)
            # capacity preflight (obs/memledger.py): check this cycle's
            # padded solve shape against the warmed per-bucket peak
            # table BEFORE materialising the padded pod batch. An
            # over-budget shape splits down to the largest warmed
            # bucket that fits (tail requeued for the next cycle) or
            # sheds the whole batch — a deliberate requeue beats a
            # device OOM mid-solve. Pipelined cycles solve at the chunk
            # shape, so they preflight that and shed rather than split
            # (the chunk is already the smallest unit).
            preflight_shed = False
            pad_p = 0  # preflight's dp-padding override (0 = default)
            ml = self.obs.memledger
            if ml.preflight_on and batch:
                eff_p = bucket_size(max(
                    min(len(batch), self.pipeline_chunk) if use_pipeline
                    else len(batch), 1))
                act, split_p, verdict = ml.preflight(
                    eff_p, int(dn.valid.shape[0]),
                    int(self.mesh.devices.size) if self._mesh_live else 0)
                self.obs.note_preflight(act)
                if act == "split" and not use_pipeline and split_p > 0:
                    if split_p < len(batch):
                        for p in batch[split_p:]:
                            self._cycle_states.pop(p.key(), None)
                            self.queue.add_if_not_present(p)
                        trace.step(
                            f"preflight split {len(batch)} -> {split_p}"
                            f" pods ({verdict})")
                        batch = batch[:split_p]
                        res.attempted = len(batch)
                        # pt was packed for the full batch above — re-
                        # pack at the trimmed shape (the gate flags from
                        # the superset pack stay valid: they can only be
                        # conservative for a subset)
                        pt = pk.pack_pods(batch)
                    # the default padding (bucket_size of the remaining
                    # batch) may still round UP past the budget — e.g. a
                    # 4-pod batch whose geometric bucket is 8 when only
                    # the warmed P=4 shape fits. Pin dp to the warmed
                    # bucket the preflight actually cleared.
                    pad_p = split_p
                elif act == "shed" or (act == "split" and use_pipeline):
                    for p in batch:
                        self._cycle_states.pop(p.key(), None)
                        self.queue.add_if_not_present(p)
                    trace.step(f"preflight shed {len(batch)} pods"
                               f" ({verdict})")
                    batch = []
                    res.attempted = 0
                    preflight_shed = True
                    use_pipeline = False
            res.owner_columns = len(pt.owner_cols)
            if not preflight_shed:
                dn = self._with_owner_columns(dn, pt)
            dp = (None if use_pipeline or preflight_shed else self._place(
                  pods_to_device(pt, pad_to=(
                      pad_p or bucket_size(max(len(batch), 1))))))
            ds = self._place(selectors_to_device(pk.pack_selector_tables()))
            dt = self._place(topology_to_device(pk.pack_topology_tables())
                             if _has_topo(pk.u) else None)
            dv = sv = None
            if dp is not None and any(p.volumes for p in batch):
                from kubernetes_tpu.ops.arrays import volumes_to_device

                dv = self._place(volumes_to_device(pk.pack_volume_tables(batch)))
                sv = _static_vol_pass(dp, dn, ds, dv)
            if ml.enabled:
                # this cycle's padded operand tables (pods + selector/
                # topology/volume memos) — re-registered every cycle at
                # the current shape, popped when the cycle sheds
                ml.register_tree("scheduler.pod_batch", dp, ds, dt, dv)
            trace.step(f"snapshot packed ({len(batch)} pods, {nt.n} nodes,"
                       f" {snap_mode})")
        res.snapshot_mode = snap_mode
        # host mode never touches the cache's device bookkeeping: it
        # packs+uploads the whole table right here, every cycle
        snap_rows = (nt.n if snap_mode == "host"
                     else self.cache.last_upload_rows)
        self.metrics.snapshot_packs.inc(mode=snap_mode)
        self.metrics.snapshot_rows_packed.inc(snap_rows)
        self.obs.note_snapshot(snap_mode, snap_rows)
        # h2d accounting (only what actually crossed the boundary: full
        # uploads count the whole resident table, delta cycles count the
        # scattered rows via the cache's byte ledger, clean cycles count
        # nothing) + the batch-shape digest for the flight recorder
        uploads = [t for t in (dp, ds, dt, dv) if t is not None]
        if snap_mode in ("host", "full"):
            uploads.append(dn)  # the whole node table crossed over
        elif self.cache.last_upload_nbytes:
            # delta: only the scattered rows crossed — charge the
            # cache's byte ledger, not the resident table's full size
            self.obs.jax.record_transfer(
                "snapshot", "h2d", self.cache.last_upload_nbytes)
        self.obs.jax.record_upload("snapshot", *uploads)
        self.obs.note_batch_shape(
            f"P{dp.valid.shape[0] if dp is not None else len(batch)}"
            f"xN{dn.valid.shape[0]}"
            + ("+topo" if dt is not None else "")
            + ("+vol" if dv is not None else "")
            + (f"+pipe{self.pipeline_chunk}" if use_pipeline else "")
            + (f"+mesh{int(self.mesh.devices.size)}"
               if self._mesh_live else "")
        )
        if preflight_shed:
            # the whole batch was requeued by the capacity preflight —
            # the cycle still flushes its snapshot accounting and flight
            # record (action=shed rode note_preflight above), it just
            # never builds the padded batch or touches the solver
            res.elapsed_s = self.clock() - t0
            self._record_metrics(res)
            self.obs.end_cycle(res)
            return res

        if use_pipeline:
            # the pipelined cycle executor owns the rest of the cycle on
            # the clean fast path (no extenders / host plugins / gang /
            # nominated pods — _pipeline_eligible)
            return self._pipelined_tail(
                batch, cycle, res, t0, trace, nt, dn, ds, dt, node_order,
                key, pt.owner_cols,
            )

        # incremental solve: a steady-state micro-batch on a clean/delta
        # resident snapshot solves RESTRICTED — candidate columns from
        # the cached score plane instead of the full (P, N) dense pass.
        # A declined/under-placed/invalid attempt falls through to the
        # cold solve below (the correctness fallback).
        if self._incremental_eligible(batch, nominated, dn, dt, dv,
                                      snap_mode, key, nt):
            inc_out = self._restricted_tail(
                batch, cycle, res, t0, trace, nt, dn, ds, dp, node_order,
                key)
            if inc_out is not None:
                return inc_out

        # sparsity-first PRIMARY mode: cycles the restricted warm route
        # did not take (full-snapshot rebuilds, oversized batches,
        # declined attempts) solve PARTITIONED — capacity-balanced
        # fixed-width column blocks through the warmed restricted
        # program — before the dense plane is ever materialized. The
        # dense ladder below stays the correctness oracle: a
        # partitioned attempt that cannot place its whole batch binds
        # nothing and falls through.
        if self._partitioned_cold_eligible(batch, nominated, dn, dt, dv,
                                           key):
            cold_out = self._partitioned_cold_tail(
                batch, cycle, res, t0, trace, nt, dn, ds, dp, node_order,
                key)
            if cold_out is not None:
                return cold_out

        # framework Filter/Score contributions: device batch plugins give
        # whole (P, N) matrices; host plugins evaluate per (pod, nodeName)
        # once per cycle (the non-tensorizable escape hatch)
        extra_score = None
        batch_state = CycleState()
        fw_mask = fw.run_filter_batch(batch_state, dp, dn, ds)
        fw_score = fw.run_score_batch(batch_state, dp, dn, ds)
        if fw_score is not None:
            extra_score = fw_score
        early_fail: Dict[int, str] = {}
        if fw.has_host_filters() or fw.has_host_scores():
            hm = np.ones((dp.valid.shape[0], dn.valid.shape[0]), bool)
            hs = np.zeros((dp.valid.shape[0], dn.valid.shape[0]), np.float32)
            for i, p in enumerate(batch):
                st = self._cycle_states[p.key()]
                try:
                    for j, name in enumerate(node_order):
                        if fw.has_host_filters():
                            hm[i, j] = fw.run_host_filter(st, p, name).is_success()
                        if fw.has_host_scores() and hm[i, j]:
                            hs[i, j] = fw.run_host_score(st, p, name)
                except Exception as e:
                    # ANY host-plugin failure (a raising Filter or Score
                    # plugin included) aborts only THIS pod's cycle — the
                    # reference converts plugin errors into a per-pod
                    # error status (RunFilterPlugins/PrioritizeNodes
                    # return an error for that pod; other pods proceed);
                    # letting it propagate would abort the whole batch
                    # with popped pods never requeued
                    hm[i, :] = False
                    early_fail[i] = f"HostPlugin:{e}"
            if fw.has_host_filters():
                m = jnp.asarray(hm)
                fw_mask = m if fw_mask is None else (fw_mask & m)
            if fw.has_host_scores():
                extra_score = (
                    jnp.asarray(hs)
                    if extra_score is None
                    else extra_score + jnp.asarray(hs)
                )

        # node-search truncation: restrict this cycle's solve to the next
        # K nodes in zone rotation (numFeasibleNodesToFind semantics)
        if self.percentage_of_nodes_to_score is not None:
            from kubernetes_tpu.nodetree import num_feasible_nodes_to_find

            k = num_feasible_nodes_to_find(
                nt.n, self.percentage_of_nodes_to_score
            )
            if k < nt.n:
                subset = set(self.node_tree.take(k))
                col = np.zeros((dn.valid.shape[0],), bool)
                for j, name in enumerate(node_order):
                    col[j] = name in subset
                cm = jnp.asarray(col)[None, :]
                fw_mask = cm if fw_mask is None else (fw_mask & cm)

        # one shared built-in filter pass against the initial usage, used
        # by the extender path and the exact solver (avoid re-evaluating)
        base_fr = None
        if self.extenders or self.solver == "exact":
            base_fr = _filter_pass(dp, dn, ds, dt, dv, sv, self.pred_mask)

        # scheduler extenders (generic_scheduler.go:539-566: after built-in
        # predicates; prioritize adds weight*score to the totals :799-829)
        if self.extenders:
            with self.obs.span("extenders"):
                em, es = self._run_extenders(
                    batch, base_fr, node_order, early_fail)
            if em is not None:
                fw_mask = em if fw_mask is None else (fw_mask & em)
            if es is not None:
                extra_score = es if extra_score is None else extra_score + es
            trace.step("extenders done")

        # scenario-pack objective: the pack's (P, N) cost term joins the
        # framework/extender score seam, so it rides every ladder tier
        # (sharded batch, batch-single, batch-cpu, the greedy oracle)
        # AND the exact solver unchanged — objective selection through
        # the ladder, not a solver fork (docs/scenarios.md)
        if self.scenario_pack is not None:
            with self.obs.span("scenario:cost"):
                sc_cost = self.scenario_pack.cost(batch, nt, node_order,
                                                  dp, dn)
            if sc_cost is not None:
                extra_score = (sc_cost if extra_score is None
                               else extra_score + sc_cost)

        # nominated-pods pass A (podFitsOnNode two-pass rule,
        # generic_scheduler.go:610): feasibility must ALSO hold with the
        # nominated pods counted onto their nodes. Divergence from the
        # reference, documented: ALL nominated pods are added, not only
        # those of higher/equal priority — strictly more conservative (a
        # pod may wait one extra cycle; capacity is never double-promised).
        extra_mask = fw_mask
        if nominated:
            row_of = {name: i for i, name in enumerate(node_order)}
            nom_pods = [p for p, _ in nominated]
            dpn = self._place(pods_to_device(pk.pack_pods(
                nom_pods, owner_cols=pt.owner_cols)))
            nom_rows = np.zeros((dpn.valid.shape[0],), np.int32)
            nom_ok = np.zeros((dpn.valid.shape[0],), bool)
            for j, (_, node) in enumerate(nominated):
                r = row_of.get(node, -1)
                nom_rows[j], nom_ok[j] = max(r, 0), r >= 0
            u_nom = _apply_batch(
                usage_from_nodes(dn), dpn, jnp.asarray(nom_rows),
                jnp.asarray(nom_ok) & dpn.valid,
            )
            nom_mask = _filter_pass(
                dp, nodes_with_usage(dn, u_nom), ds, dt, dv, sv, self.pred_mask
            ).mask
            extra_mask = nom_mask if extra_mask is None else (extra_mask & nom_mask)

        if key.solver == "exact":
            # The exact Hungarian models capacity as per-node SLOTS only:
            # in-batch coupling through host ports, volumes, or topology
            # terms is not in its constraint matrix, so two co-admitted
            # pods could silently conflict. Round 2 documented the blind
            # spot in a docstring; now it's structural — hazardous batches
            # auto-fall back to the round solver, which models all three
            # (one-per-node-per-round guards in ops/assign.py).
            hazards = []
            # batch-scoped: in-batch coupling needs THIS batch's pods to
            # carry terms (dt reflects the monotonic universe — one
            # affinity pod ever seen would disable exact forever)
            if dt is not None and any(
                p.affinity.pod_affinity_required
                or p.affinity.pod_anti_affinity_required
                or p.affinity.pod_affinity_preferred
                or p.affinity.pod_anti_affinity_preferred
                or p.topology_spread
                for p in batch
            ):
                hazards.append("topology")
            if dv is not None:
                hazards.append("volumes")
            if not key.no_ports:  # host-side gate knows; no device sync
                hazards.append("host-ports")
            if hazards:
                self.exact_fallbacks += 1
                klog.V(4).info("exact solver unsafe (%s); using round "
                               "solver", "+".join(hazards))
                trace.step(
                    f"exact solver unsafe with {'+'.join(hazards)}; "
                    "using round solver"
                )
                key = key._replace(solver="batch")
        ladder = self._solve_ladder(
            key, batch, dp, dn, ds, dt, dv, sv, base_fr, extra_mask,
            extra_score, res, record=True,
        )
        if ladder is None:
            # every tier failed (even the in-process oracle — a total
            # solver outage): fail the whole batch through the standard
            # error path so pods requeue with backoff instead of the
            # cycle stalling or binding garbage
            for pod in batch:
                self._fail(pod, cycle, res, ("SolverUnavailable",))
            res.elapsed_s = self.clock() - t0
            self._record_metrics(res)
            trace.log_if_long(self.trace_threshold_s)
            self.obs.end_cycle(res)
            return res
        assigned, usage, rounds, tier_used = ladder
        res.solver_tier = tier_used
        # the ladder already read the (validated) answer back as ONE
        # fused d2h transfer — slice off the padding rows, writable copy
        assigned = assigned[: len(batch)].copy()

        # gang scheduling (PodGroup all-or-nothing; the coscheduling-plugin
        # semantics BASELINE config 4 targets): a group binds only when ALL
        # its present members placed AND at least minMember members are
        # present (pod_group_min_available — guards against group fragments
        # straddling batches); otherwise every member rolls back
        gang_failed: Dict[int, str] = {}
        gang_groups: Dict[str, List[int]] = {}
        for gi, gp in enumerate(batch):
            if gp.pod_group:
                gang_groups.setdefault(gp.pod_group, []).append(gi)
        for gname, idxs in gang_groups.items():
            need = max([batch[gi].pod_group_min_available for gi in idxs] + [0])
            # members the cache already placed in EARLIER cycles count
            # toward minMember: a member whose bind failed transiently
            # re-queues ALONE, and crediting only batch-present members
            # would park it at this gate forever (GangIncomplete every
            # cycle) while its siblings run — a livelock, not a guard
            placed = self.cache.group_members(gname)
            incomplete = (len(idxs) + placed < need
                          or any(assigned[gi] < 0 for gi in idxs))
            if incomplete:
                for gi in idxs:
                    if assigned[gi] >= 0:
                        assigned[gi] = -1
                        gang_failed[gi] = f"GangIncomplete:{gname}"
        if gang_failed:
            # rebuild usage from the FINAL assignment: the solver's usage
            # still contains the rolled-back members, and phantom occupancy
            # would poison the failure-reason pass and preemption
            pad_assigned = np.full((dp.valid.shape[0],), -1, np.int64)
            pad_assigned[: len(batch)] = assigned
            usage = _apply_batch(
                usage_from_nodes(dn), dp,
                jnp.asarray(np.maximum(pad_assigned, 0)),
                jnp.asarray(pad_assigned >= 0) & dp.valid,
            )
        # scenario quality: dispatch the device reduction NOW (final
        # usage + final assignment, gang rollbacks applied) so it
        # executes while the host binds; its ~28 B vector is read back
        # after the bind loop alongside the failure readbacks
        q_dev = self._quality_dispatch(assigned, usage, dp, dn)

        res.rounds = int(rounds)
        solve_s = trace.total_s()
        trace.step(f"solve done ({res.rounds} rounds)")
        self.metrics.algorithm_duration.observe(solve_s)

        # reasons for the unplaced: one more filter pass against the
        # post-assignment usage (what the serial loop would have seen
        # last). EVERYTHING the host needs — per-pod reason bits,
        # per-reason node counts, the per-resource Insufficient splits,
        # the one-bit-away relaxations — is reduced ON DEVICE
        # (obs/explain.explain_reduce) and read back as one small
        # transfer; the raw (P, N) reasons matrix never crosses the
        # boundary. Only preemption still needs per-node bits, gathered
        # for exactly the pods that will attempt it (readback
        # proportional to the answer, not the problem).
        failed_idx = [i for i, a in enumerate(assigned) if a < 0]
        preemptable_idx = [i for i in failed_idx if i not in gang_failed]
        reasons_row: Dict[int, Tuple[str, ...]] = {}
        fit_msgs: Dict[int, str] = {}
        ex = None
        ex_host = None
        preempt_rows_dev = None
        if failed_idx:
            from kubernetes_tpu.obs.explain import explain_reduce

            fr = _filter_pass(
                dp, nodes_with_usage(dn, usage), ds, dt, dv, sv, self.pred_mask
            )
            fm = np.zeros((dp.valid.shape[0],), bool)
            fm[failed_idx] = True
            ex = explain_reduce(
                fr.reasons, dn.valid, jnp.asarray(fm), dp.req,
                dn.allocatable - usage.requested, dn.ready,
                dn.network_unavailable)
            if self.enable_preemption and preemptable_idx:
                preempt_rows_dev = jnp.take(
                    fr.reasons,
                    jnp.asarray(preemptable_idx, dtype=jnp.int32), axis=0)

        bind_span = trace.begin_span("bind")
        # bind the placed pods FIRST: admission is pure host work, so it
        # overlaps the failure reductions still executing on device (JAX
        # async dispatch — the monolithic cycle's readback overlap; the
        # pipelined executor's pipeline:readback@k spans are the
        # chunked analog)
        for i, pod in enumerate(batch):
            if int(assigned[i]) >= 0:
                self._admit_pod(pod, node_order[int(assigned[i])], cycle,
                                res)
        if ex is not None:
            from kubernetes_tpu.ops.predicates import (
                fit_error_message_from_counts,
            )
            from kubernetes_tpu.snapshot import FIXED_RESOURCE_NAMES

            with self.obs.span("pipeline:readback@reasons"):
                ex_host = self.obs.jax.readback("explain", ex)._asdict()
            res_names = (list(FIXED_RESOURCE_NAMES)
                         + pk.u.scalar_resources.items())[: pt.req.shape[1]]
            for i in failed_idx:
                # a pod's reason set = union over valid nodes of failed
                # bits, reduced on device (zero when no node is valid)
                bits = int(ex_host["pod_bits"][i])
                reasons_row[i] = decode_reasons(bits)
                if bits:
                    # FitError-shaped event text with per-reason node
                    # counts ("2 Insufficient cpu, 3 node(s) had
                    # taints...") — byte-identical to the raw-matrix
                    # construction, from the reductions alone
                    fit_msgs[i] = fit_error_message_from_counts(
                        ex_host["per_pod"][i], ex_host["insufficient"][i],
                        ex_host["not_ready"][i], ex_host["net_unavail"][i],
                        nt.n, pt.req[i], res_names,
                    )
        for i, pod in enumerate(batch):
            if int(assigned[i]) >= 0:
                continue
            if i in early_fail:
                reasons = (early_fail[i],)
            elif i in gang_failed:
                reasons = (gang_failed[i],)
            else:
                reasons = reasons_row.get(i, ())
            # only filter-pass failures carry the FitError text; gang
            # rollbacks and plugin failures keep their own status (a
            # gang member may fit everywhere — a fabricated "0/N nodes
            # are available" would be a lie)
            msg = (fit_msgs.get(i)
                   if i not in early_fail and i not in gang_failed
                   else None)
            self._fail(pod, cycle, res, reasons, message=msg)

        trace.end_span(bind_span)
        trace.step(f"bound {res.scheduled}, failed {res.unschedulable}")

        if q_dev is not None:
            self._quality_read(q_dev, res, batch, assigned, nt)

        # schedulability explainer: decode the read-back reduction into
        # the cycle's UnschedulableReport — every _fail'd pod gets a row
        # (filter failures carry device analytics; plugin/gang/bind
        # failures carry their status reasons), feeding /debug/why, the
        # flight recorder's top-K, and the unschedulable metrics
        if getattr(self.obs.config, "explain", True):
            self._build_explain_report(
                cycle, batch, failed_idx, ex_host, nt.n, res)

        # preemption (scheduler.go:493 -> preempt, §3.3): failed pods try to
        # evict lower-priority pods; winners get a nominated node and retry.
        # The per-node reason bits preemption needs are gathered on device
        # for exactly the preemptable rows — (F, N) across the boundary,
        # zero bytes on cycles where nothing failed
        if (self.enable_preemption and preemptable_idx
                and preempt_rows_dev is not None):
            with self.obs.span("pipeline:readback@preempt"):
                rows = self.obs.jax.readback("preempt-reasons",
                                             preempt_rows_dev)
            rmat = np.zeros((len(batch), rows.shape[1]), rows.dtype)
            rmat[preemptable_idx] = rows
            pt0 = self.clock()
            with self.obs.span("preemption"):
                if (self.scenario_pack is not None
                        and self.scenario_pack.wants_cascade):
                    # scenario packs: victims + displaced pods re-enter
                    # ONE dense solve in this same cycle instead of the
                    # per-pod nominate-and-wait loop
                    self._run_preemption_cascade(
                        batch, preemptable_idx, rmat, node_order, res)
                else:
                    self._run_preemption(
                        batch, preemptable_idx, rmat, node_order, res)
            self.metrics.preemption_duration.observe(self.clock() - pt0)
            trace.step(f"preemption ({res.preempted} victims)")
        return self._finish_cycle(res, cycle, t0, solve_s, trace)

    def _finish_cycle(self, res: CycleResult, cycle: int, t0: float,
                      solve_s: float, trace, label: str = "") -> CycleResult:
        """The shared end-of-cycle bookkeeping (monolithic AND pipelined
        paths): elapsed stamp, summary log, metrics, slow-cycle trace
        log, flight record. New finalization steps belong HERE so the
        two executors cannot silently diverge."""
        res.elapsed_s = self.clock() - t0
        res.solve_s = solve_s
        if res.solver_tier and not res.solve_scope:
            res.solve_scope = "full"
        if res.solve_scope:
            self.obs.note_solve_scope(res.solve_scope, res.reuse_frac)
            if self.incremental.enabled:
                m = getattr(self.metrics, "incremental_cycles", None)
                if m is not None:
                    m.inc(scope=res.solve_scope)
                g = getattr(self.metrics, "incremental_reuse_fraction",
                            None)
                if g is not None:
                    g.set(res.reuse_frac)
        if res.solver_tier:
            self.last_solver_tier = res.solver_tier
            self.last_solver_fallbacks = res.solver_fallbacks
        klog.V(3).info(
            "cycle %d%s: attempted=%d scheduled=%d unschedulable=%d "
            "rounds=%d %.3fs", cycle, label, res.attempted, res.scheduled,
            res.unschedulable, res.rounds, res.elapsed_s,
        )
        # backfill the ladder tier + solve scope onto the journey
        # attempt rows this cycle touched (known only now)
        self.obs.journeys.finish_cycle(cycle, res.solver_tier,
                                       res.solve_scope)
        self._record_metrics(res, solve_s)
        trace.log_if_long(self.trace_threshold_s)
        self.obs.end_cycle(res)
        return res

    def _record_metrics(self, res: CycleResult, solve_s: float = 0.0) -> None:
        """pkg/scheduler/metrics names; per-pod attempt counts, cycle-level
        durations, queue-depth gauges. Bind errors already passed scheduling
        and count ONLY under "error" (the reference's result labels are
        disjoint per attempt)."""
        m = self.metrics
        m.schedule_attempts.inc(res.scheduled, result=m.SCHEDULED)
        m.schedule_attempts.inc(
            max(res.unschedulable - res.bind_errors, 0), result=m.UNSCHEDULABLE
        )
        m.schedule_attempts.inc(res.bind_errors, result=m.ERROR)
        # e2e latency is PER POD create-to-bind (the reference's
        # scheduleOne observes once per pod): every bound pod's
        # queue-add -> bind delta lands in the histogram. Cycles that
        # attempted but bound nothing keep the legacy cycle-elapsed
        # observation so failure latency stays visible. The fallback is
        # gated on res.attempted ALONE: off-cycle callers (the parked
        # ambiguous-bind verifier, the stopped-leading Permit drain)
        # hand in a fresh CycleResult whose elapsed_s was never stamped,
        # and their unschedulable/bind_errors counts must not emit a
        # bogus near-zero e2e sample.
        if res.e2e_latency_s:
            for v in res.e2e_latency_s.values():
                m.e2e_scheduling_duration.observe(v)
        elif res.attempted:
            m.e2e_scheduling_duration.observe(res.elapsed_s)
        if res.attempted or res.scheduled or res.unschedulable:
            m.scheduling_duration.observe(solve_s, operation="scheduling_algorithm")
        # pending_pods gauge freshness is the QUEUE's job (set in one
        # place per mutation — _sync_gauges); the cycle-boundary call
        # here only covers queue fakes without the metrics plumbing
        sync = getattr(self.queue, "_sync_gauges", None)
        if sync is not None:
            sync()
        else:
            for q, depth in self.queue.pending_counts().items():
                m.pending_pods.set(depth, queue=q)

    def _explain_retire_if_drained(self) -> None:
        """An idle cycle popped nothing: when every pod the last report
        analyzed has since left (bind and delete both drop their
        why_pending rows), the cluster summary and the reason gauges
        would otherwise keep reporting them forever — retire the report
        and zero the gauges. Pods merely parked in backoff/unschedulable
        still hold why_pending rows, so their analysis stays visible
        between retries."""
        if not getattr(self.obs.config, "explain", True):
            return
        if self.why_pending or self.last_explain is None:
            return
        if not (self.last_explain.pods
                or self.last_explain.reason_node_counts):
            return
        from kubernetes_tpu.obs.explain import UnschedulableReport

        self.last_explain = UnschedulableReport(
            cycle=self.queue.scheduling_cycle,
            n_nodes=self.last_explain.n_nodes)
        for reason in self._explain_reasons_seen:
            self.metrics.unschedulable_node_counts.set(0, reason=reason)

    def _build_explain_report(self, cycle, batch, failed_idx, ex_host,
                              n_nodes, res: CycleResult) -> None:
        """Assemble the cycle's UnschedulableReport from the read-back
        explain arrays + the driver-level failure reasons, then fan it
        out: CycleResult, /debug/why state, flight record, metrics."""
        from kubernetes_tpu.obs.explain import PodExplanation, build_report

        top_k = getattr(self.obs.config, "explain_top_k", 3)
        keys = [p.key() for p in batch]
        report = build_report(cycle, n_nodes, keys, failed_idx, ex_host,
                              top_k)
        # pods that failed OUTSIDE the filter pass (prefilter, plugins,
        # gang rollback, volume/permit/bind errors) still get a row
        for key in res.failure_reasons:
            if key not in report.pods:
                report.pods[key] = PodExplanation(key=key)
        now = self.clock()
        for key, pe in report.pods.items():
            pe.reasons = res.failure_reasons.get(key, ())
            pe.message = res.fit_errors.get(key, "")
            pe.attempts = self.queue.backoff_map.attempts(key)
            pod = self.queue.pod(key)
            if pod is not None:
                # the queue stamps queued_at on add (0.0 is a valid
                # fake-clock enqueue time, not "unset")
                pe.queue_residency_s = max(
                    now - getattr(pod, "queued_at", now), 0.0)
        res.explain = report
        self.last_explain = report
        for key, pe in report.pods.items():
            self.why_pending[key] = pe
        self.obs.note_explain(report)
        m = self.metrics
        for reason, npods in report.reason_pods.items():
            m.unschedulable_pods.inc(npods, reason=reason)
        # gauges show THIS cycle's exclusion counts; reasons that fired
        # before but not now drop to zero instead of going stale
        for reason in self._explain_reasons_seen - set(
                report.reason_node_counts):
            m.unschedulable_node_counts.set(0, reason=reason)
        for reason, pairs in report.reason_node_counts.items():
            m.unschedulable_node_counts.set(pairs, reason=reason)
            self._explain_reasons_seen.add(reason)

    # -- degradation ladder ------------------------------------------------

    def _breaker(self, target: str):
        """Lazily create the circuit breaker for a ladder tier or
        extender endpoint, wired to the breaker-state gauge and the
        SchedulerDegraded/SchedulerRecovered events."""
        br = self._breakers.get(target)
        if br is None:
            from functools import partial as _partial

            from kubernetes_tpu.faults import CircuitBreaker

            rc = self.robustness
            br = CircuitBreaker(
                failure_threshold=rc.breaker_failure_threshold,
                open_duration_s=rc.breaker_open_duration_s,
                half_open_probes=rc.breaker_half_open_probes,
                clock=self.clock,
                on_transition=_partial(self._on_breaker_transition, target),
            )
            self._breakers[target] = br
            self.metrics.breaker_state.set(0, target=target)
        return br

    def _on_breaker_transition(self, target: str, old: str, new: str) -> None:
        from kubernetes_tpu.events import (
            REASON_DEGRADED,
            REASON_RECOVERED,
            ObjectRef,
        )
        from kubernetes_tpu.faults import CLOSED, OPEN, STATE_CODE

        self.metrics.breaker_state.set(STATE_CODE[new], target=target)
        self.obs.note_breaker(target, old, new)
        ref = ObjectRef(name=self.scheduler_name, involved_kind="Scheduler")
        if new == OPEN:
            klog.warning("circuit breaker %s: %s -> open (degraded mode)",
                         target, old)
            self.event_sink(
                REASON_DEGRADED, ref,
                f"circuit breaker for {target} opened; "
                "routing around it (degraded mode)",
            )
        elif new == CLOSED and old != CLOSED:
            klog.V(2).info("circuit breaker %s: %s -> closed", target, old)
            self.event_sink(
                REASON_RECOVERED, ref,
                f"circuit breaker for {target} closed; full service restored",
            )

    def _solve_key(self, solver: str, gates) -> SolveKey:
        """The solve key of this cycle (or warmup sweep): ``solver`` and
        ``gates`` — solver_gates' (skip_priorities, no_ports,
        no_pod_affinity, no_spread) — plus the scheduler's settings and
        the current mesh liveness."""
        skip_prio, no_ports, no_pod_aff, no_spread = gates
        inc = self.incremental
        return SolveKey(
            solver, tuple(skip_prio), no_ports, no_pod_aff, no_spread,
            self.pred_mask, self.per_node_cap, self.max_rounds,
            self.weights, self._mesh_live,
            bool(self.obs.config.sinkhorn_telemetry),
            inc.warm_tol if (inc.warm_potentials and solver == "sinkhorn")
            else None)

    def _solve(self, key: SolveKey, tier: str, dp, dn, ds, *, topo=None,
               vol=None, static_vol=None, extra_mask=None, extra_score=None,
               base_fr=None, sk_init=None, n_pods: int = 0,
               record: bool = True, warmup: bool = False):
        """THE solver call of every cycle route, every warmup site and
        the cost-model anchor. ``tier`` picks the solver and names the
        fault site ``solve:<tier>``: greedy, exact, a dense batch tier,
        or a candidate-frame route (restricted, partitioned — ``dn`` is
        then the gathered frame). ``record`` first classifies the call's
        abstract signature with the retrace telemetry, at the host
        boundary before the jitted call (a new signature at a warmed
        site means XLA recompiles underneath; the digest reads
        shape/dtype metadata only). ``warmup`` registers it as an
        ahead-of-time compile and leaves the fault hook and the
        Sinkhorn telemetry out.

        Returns ``(assigned, usage, rounds)`` — plus the final
        potentials of a warm-started frame solve; the greedy oracle
        reports ``n_pods`` rounds."""
        from kubernetes_tpu.ops.assign import batch_assign, greedy_assign

        frame = tier in _FRAME_TIERS
        if record:
            trees = (dp, dn, ds) if frame else (dp, dn, ds, topo, vol)
            self.obs.jax.record_call("solve", *trees, static=key.signature(
                frame, extra_mask is None, extra_score is None,
                sk_init is None), warmup=warmup)
        hook = (self.fault_injector.solver_hook
                if self.fault_injector is not None and not warmup else None)
        site = f"solve:{tier}"
        if tier == "exact":
            out = self._exact_solve(dp, dn, ds, topo, base_fr, extra_mask,
                                    extra_score)
            return out if hook is None else hook(site, *out,
                                                 dn.valid.shape[0])
        if tier == "greedy":
            a, u = greedy_assign(
                dp, dn, ds, key.weights, topo=topo, extra_mask=extra_mask,
                vol=vol, static_vol=static_vol, enabled_mask=key.pred_mask,
                extra_score=extra_score, skip_priorities=key.skip_prio,
                no_ports=key.no_ports, no_pod_affinity=key.no_pod_aff,
                no_spread=key.no_spread, fault_hook=hook, fault_site=site)
            return a, u, n_pods
        kw = key.batch_kwargs(tier)
        if frame:
            kw.update(sk_init=sk_init, sk_tol=key.warm_tol,
                      potentials_out=key.warm_tol is not None)
        out = batch_assign(
            dp, dn, ds, key.weights, topo=topo, extra_mask=extra_mask,
            vol=vol, static_vol=static_vol, extra_score=extra_score,
            fault_hook=hook, fault_site=site, **kw)
        if kw["stats_out"]:
            # sinkhorn convergence telemetry rides the solve as a (2,)
            # device pair (stays on device; obs reads it back once at
            # cycle end)
            if not warmup:
                self.obs.note_sinkhorn(out[3])
            out = out[:3] + out[4:]
        return out

    def _run_tier(self, key, tier, batch, dp, dn, ds, dt, dv, sv, base_fr,
                  extra_mask, extra_score, record):
        """One solve attempt on one ladder tier. Returns
        ``((assigned, usage, rounds), dp_used, dn_used)`` — the re-
        pinning tiers (batch-single, batch-cpu) hand back the tables
        they actually solved against, so the fused validator never
        mixes a single-device result with mesh-sharded tables in one
        jitted call. Exceptions propagate to the ladder."""
        ops = dict(topo=dt, extra_mask=extra_mask, vol=dv, static_vol=sv,
                   extra_score=extra_score)
        if tier in _REPIN_TIERS:
            if tier == "batch-single":
                # mesh-ladder rung: the identical solve re-pinned onto
                # ONE device of the mesh — survives a sick collective /
                # wedged shard without leaving the accelerator class
                # (batch-cpu and greedy remain unchanged below it)
                one = (self.mesh.devices.flat[0] if self.mesh is not None
                       else jax.devices()[0])
            else:
                # host-backend fallback: re-pin every input to the local
                # CPU device so the identical solve re-runs
                # off-accelerator (on a CPU-only install this is a clean
                # re-execution — the seam a TPU deployment uses to
                # survive a wedged chip)
                one = jax.local_devices(backend="cpu")[0]

            def put(t):
                return (None if t is None else jax.tree_util.tree_map(
                    lambda x: jax.device_put(x, one), t))

            dp, dn, ds = put(dp), put(dn), put(ds)
            ops = {k: put(v) for k, v in ops.items()}
        out = self._solve(key, tier, dp, dn, ds, base_fr=base_fr,
                          n_pods=len(batch), record=record, **ops)
        return out, dp, dn

    def _validated_readback(self, tier, out, dp, dn, cand=None, extra=None,
                            site="solve-result"):
        """Validate one solve's result and read it back as ONE d2h
        transfer — the fused solve+validate boundary of every route.
        The verdict is computed ON DEVICE (ops/assign.device_validate:
        range / invalid-node / finiteness / capacity recomputation,
        never trusting the solver's claimed usage) and rides the same
        readback as the assignment and round count, so a healthy
        cycle's solve path syncs exactly once. The host checker
        (ops/assign.validate_solution) remains the trust floor: it takes
        over when the result can't even reach the device (shape),
        whenever ``robustness.host_validate`` forces it, and as the
        parity oracle in tests/test_fused_validate.py.

        A frame route passes its candidate column map ``cand``: the
        frame-local answer is validated against the frame ``dn`` and
        mapped to global node rows on device before the readback.
        ``extra`` device entries ride the same readback under the
        readback site ``site``.

        Returns ``(assigned_host, usage_dev, rounds_int, extra_host)``
        or raises SolverResultInvalid with the same reason vocabulary
        the host checker uses (the verdict gates binding exactly as
        before)."""
        from kubernetes_tpu.faults import SolverResultInvalid
        from kubernetes_tpu.ops.arrays import map_restricted_assignment
        from kubernetes_tpu.ops.assign import (
            VALIDATE_REASONS,
            device_validate,
            validate_solution,
        )

        rc = self.robustness
        a_dev, u_dev, rounds = out[:3]

        def reject(why):
            self.metrics.solver_rejections.inc(tier=tier, reason=why)
            raise SolverResultInvalid(f"{tier}: {why}")

        dv_out = None
        if rc.validate_results:
            with self.obs.span("validate"):
                if not rc.host_validate:
                    dv_out = device_validate(a_dev, u_dev, dp, dn,
                                             self.pred_mask)
                if dv_out is None:
                    # forced, or not array-shaped enough to reach the
                    # device: the host checker renders the verdict
                    ok, why = validate_solution(a_dev, u_dev, dp, dn,
                                                self.pred_mask)
                    if not ok:
                        reject(why)
        payload = dict(extra or {}, rounds=rounds, assigned=(
            a_dev if cand is None
            else map_restricted_assignment(a_dev, cand)))
        if dv_out is not None:
            payload["code"], payload["valid"] = dv_out
        host = self.obs.jax.readback(site, payload)
        code = int(host.get("code", 0))
        if code:
            reject(VALIDATE_REASONS[code])
        # device_get already materialized host["assigned"] as numpy
        return (host["assigned"], u_dev, int(host["rounds"]),
                {k: host[k] for k in (extra or ())})

    def _solve_ladder(self, key, batch, dp, dn, ds, dt, dv, sv, base_fr,
                      extra_mask, extra_score, res, record=False):
        """The degradation ladder: try the configured solver tier, then
        each tier of ``robustness.fallback_chain`` (TPU batch → CPU-JAX
        batch → the greedy sequential oracle), with per-tier circuit
        breakers, bounded in-cycle retries, deadline-aware skip-to-oracle,
        and result validation so a lying solver can never bind an
        infeasible pod. Returns (assigned_host, usage, rounds, tier) —
        the assignment ALREADY read back (one fused d2h transfer, see
        :meth:`_validated_readback`) — or None when every tier failed
        (the caller requeues the whole batch). ``record`` registers the
        cycle's solve signature once, on the first attempt."""
        rc = self.robustness
        solver = key.solver
        tiers = [solver]
        if self._mesh_live and solver in ("batch", "sinkhorn", "greedy"):
            # the mesh-aware rung: a failing SHARDED solve retries on
            # one device (same backend, inputs re-pinned off the mesh)
            # before the ladder leaves the accelerator entirely —
            # sharded -> single-device -> batch-cpu -> greedy
            tiers.append("batch-single")
        for t in rc.fallback_chain:
            if t not in tiers:
                tiers.append(t)
        if "greedy" in tiers:
            # the sequential oracle is the trust floor — nothing below it
            tiers = tiers[: tiers.index("greedy") + 1]
        terminal = tiers[-1]
        m = self.metrics
        deadline = self._cycle_deadline
        deadline_counted = False

        i = 0
        while i < len(tiers):
            tier = tiers[i]
            if (deadline is not None and tier != terminal
                    and self.clock() >= deadline):
                # budget blown: no time for intermediate tiers — jump to
                # the oracle floor so the cycle still makes progress
                if not deadline_counted:
                    m.deadline_exceeded.inc()
                    self.obs.note_deadline_exceeded()
                    deadline_counted = True
                m.solver_fallbacks.inc(from_tier=tier, to_tier=terminal)
                res.solver_fallbacks += 1
                i = len(tiers) - 1
                continue
            br = self._breaker(f"solver:{tier}")
            if not br.allow() and i + 1 < len(tiers):
                # open breaker sheds the tier without burning latency;
                # the terminal tier is always attempted regardless
                m.solver_fallbacks.inc(from_tier=tier, to_tier=tiers[i + 1])
                res.solver_fallbacks += 1
                i += 1
                continue
            attempts = 1 + max(0, rc.solver_retries)
            result = last_err = None
            for attempt in range(attempts):
                ts = self.clock()
                with self.obs.span(f"solve:{tier}", attempt=attempt):
                    rec, record = record, False
                    try:
                        out, dp_t, dn_t = self._run_tier(
                            key, tier, batch, dp, dn, ds, dt, dv, sv,
                            base_fr, extra_mask, extra_score, rec,
                        )
                        # fused validate + single readback (raises
                        # SolverResultInvalid on a lying solver, exactly
                        # as the host checker did) — against the tables
                        # THIS tier solved on (a re-pinning tier's
                        # result must not meet mesh-sharded tables)
                        result = self._validated_readback(tier, out,
                                                          dp_t, dn_t)
                    except Exception as e:
                        last_err = e
                    finally:
                        m.solver_tier_duration.observe(
                            self.clock() - ts, tier=tier)
                if result is not None:
                    break
                if attempt + 1 < attempts and not (
                        deadline is not None and self.clock() >= deadline):
                    m.solver_retries.inc(tier=tier)
                    self.obs.note_retry()
                    continue
                break
            if result is not None:
                br.record_success()
                usage = result[1]
                if self._mesh_live and tier in ("batch-single",
                                                "batch-cpu"):
                    # a re-pinned tier's usage lives on one device; the
                    # cycle's failure-reason pass recombines it with the
                    # SHARDED node table — re-place it onto the mesh
                    from kubernetes_tpu.parallel.mesh import shard_usage

                    usage = shard_usage(usage, self.mesh)
                return result[0], usage, int(result[2]), tier
            br.record_failure()
            klog.warning("solver tier %s failed (%s); falling back",
                         tier, last_err)
            if i + 1 < len(tiers):
                m.solver_fallbacks.inc(from_tier=tier, to_tier=tiers[i + 1])
                res.solver_fallbacks += 1
            i += 1
        return None

    # graftlint: disable-scope=R2,R7,R8 -- host oracle by design: the exact
    # tier runs the Hungarian solver on CPU, so the one filter+score result
    # is read back wholesale here (a deliberate full gather when the mesh is
    # on); the ladder only enters this tier when quality beats wall-clock
    # (gang/offline packing)
    def _exact_solve(self, dp, dn, ds, dt, base_fr, extra_mask, extra_score):
        """Exact one-shot assignment: device filter+score once, then the
        native Hungarian solver with per-node slot capacities
        (native/ktpu.cc; SURVEY.md §7.2 step 5's exact branch). Maximizes
        the batch's total score instead of auction rounds — for gang /
        offline packing where quality beats wall-clock. Multi-resource
        feasibility beyond slot counts is validated sequentially in queue
        order; in-batch coupling of ports/volumes/topology is NOT modeled
        here (use the round solver for such workloads)."""
        from kubernetes_tpu import native
        from kubernetes_tpu.ops.assign import _apply_batch, usage_from_nodes
        from kubernetes_tpu.ops.predicates import BIT
        from kubernetes_tpu.snapshot import RES_PODS

        mask = np.asarray(base_fr.mask)
        if extra_mask is not None:
            mask = mask & np.asarray(extra_mask)
        wkey = (
            tuple(sorted(self.weights.items()))
            if self.weights is not None
            else None
        )
        score = np.asarray(_score_pass(dp, dn, ds, dt, jnp.asarray(mask), wkey))
        if extra_score is not None:
            score = score + np.asarray(extra_score)

        alloc = np.asarray(dn.allocatable)
        node_valid = np.asarray(dn.valid)
        valid = np.asarray(dp.valid)
        preq = np.asarray(dp.req)
        order = np.lexsort((np.asarray(dp.order), -np.asarray(dp.priority)))

        # assign -> validate rounds: the slot capacity only encodes the pod
        # count; multi-resource feasibility is enforced by sequential
        # validation, and rejected pods re-solve against the updated usage
        # until a fixpoint (usually 1-2 rounds)
        # a Policy bypassing PodFitsResources also bypasses the resource
        # gating here (mirrors the batch solver's admission-guard bypass)
        res_on = self.pred_mask is None or bool(
            self.pred_mask & (1 << BIT["PodFitsResources"])
        )
        P = mask.shape[0]
        assigned_final = np.full((P,), -1, np.int32)
        used = np.asarray(dn.requested).copy()
        active = valid.copy()
        rounds = 0
        for _ in range(16):
            if not active.any():
                break
            rounds += 1
            fit = np.all(
                used[None, :, :] + preq[:, None, :] <= alloc[None, :, :] + 1e-6,
                axis=2,
            ) if res_on else np.ones((P, alloc.shape[0]), bool)
            # slot capacity: the pod-count column is exact; the other
            # resource columns bound the count via the SMALLEST active
            # request (an upper bound — still validated below — that keeps
            # the Hungarian from piling far more pods on a node than any
            # resource could admit, which would burn a round per few pods)
            free = np.maximum(alloc - used, 0.0)  # (N, R)
            min_req = np.where(
                active[:, None], preq, np.inf
            ).min(axis=0)  # (R,) smallest request per resource
            with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
                per_res = np.where(
                    min_req > 0, np.floor(free / np.maximum(min_req, 1e-30)), np.inf
                )
            cap = np.where(
                node_valid, np.nanmin(per_res, axis=1), 0
            )
            cap = np.where(np.isfinite(cap), cap, free[:, RES_PODS]).astype(np.int64)
            if not res_on:
                cap = np.where(node_valid, P, 0).astype(np.int64)
            m = mask & fit & active[:, None]
            a = native.exact_assign(score, m, cap)
            progress = False
            for p in order:
                if not active[p] or a[p] < 0:
                    continue
                t = a[p]
                if not res_on or np.all(used[t] + preq[p] <= alloc[t] + 1e-6):
                    used[t] += preq[p]
                    assigned_final[p] = t
                    active[p] = False
                    progress = True
            if not progress:
                break
        acc = jnp.asarray(assigned_final >= 0) & dp.valid
        usage = _apply_batch(
            usage_from_nodes(dn), dp,
            jnp.asarray(np.maximum(assigned_final, 0)), acc,
        )
        return jnp.asarray(assigned_final), usage, rounds

    # -- pipelined cycle executor ------------------------------------------

    def _pipeline_eligible(self, batch, nominated) -> bool:
        """The pipelined executor covers the clean high-throughput path:
        features that need whole-batch host coupling (extenders, host/
        batch plugins, gang groups, nominated-pod pass A, node-search
        truncation) or a host-resident solver (exact) keep the monolithic
        cycle. Depth 1 is the explicit off switch — today's behavior."""
        if self.pipeline_depth < 2 or self.pipeline_chunk < 1:
            return False
        if len(batch) <= self.pipeline_chunk:
            return False
        if self.solver not in ("batch", "sinkhorn", "greedy"):
            return False
        if self.extenders or nominated:
            return False
        fw = self.framework
        if (fw.has_host_filters() or fw.has_host_scores()
                or fw.has_batch_filters() or fw.has_batch_scores()):
            return False
        if self.percentage_of_nodes_to_score is not None:
            return False
        if self.scenario_pack is not None and (
                not self.scenario_pack.restricted_ok
                or self.scenario.quality):
            # capability-driven (mirrors _incremental_eligible): a
            # restricted_ok pack's cost term is per-column, so it
            # evaluates per CHUNK bit-for-bit and rides the pipeline.
            # The quality reduction is the remaining whole-batch
            # coupling — it wants the final monolithic usage — so
            # quality-on scenario cycles keep the monolithic executor,
            # as does any pack needing global cross-column structure.
            return False
        if any(p.pod_group for p in batch):
            # gangs stay monolithic: all-or-nothing groups straddling
            # chunk boundaries would need cross-chunk rollback
            return False
        return True

    # -- incremental solve (restricted candidate-column cycles) ------------

    def _drop_incremental(self, reason: str) -> None:
        """One invalidation edge for ALL warm-solve state: the cached
        score plane drops (rebuilt lazily from the resident table) and
        the Sinkhorn potential carry dies with the generation bump. The
        next cycle solves cold. Reasons: takeover | device-loss |
        dirty-frac | full-snapshot (epoch/interner growth and node-set
        changes all surface as full snapshot rebuilds).

        Counted only when warm state actually EXISTED to drop (the
        cache held a summary, a potential carry was live, or restricted
        service had engaged) — a scheduler whose every cycle takes full
        uploads must not mint one phantom invalidation per cycle."""
        has = getattr(self.cache, "has_score_summary", None)
        had = (self._incr_active or self._sk_warm_pot is not None
               or bool(has() if has is not None else False))
        self._sk_warm_pot = None
        # release the scheduler-side ledger registrations (the warm
        # potential carry AND the last pod-batch upload): on a
        # device-loss edge those arrays are gone; on the other edges
        # the next cycle re-registers what it re-uploads
        self.obs.memledger.deregister_prefix("scheduler.")
        self._incr_active = False
        drop = getattr(self.cache, "drop_score_summary", None)
        if drop is not None and (has is None or has()):
            # drop only a LIVE summary: the takeover/device-loss paths
            # arrive after drop_device_snapshot already cleared it (and
            # bumped the generation) — a second bump would be noise
            drop()
        if had and self.incremental.enabled:
            m = getattr(self.metrics, "incremental_invalidations", None)
            if m is not None:
                m.inc(reason=reason)

    def _note_tuner_batch(self, raw: int) -> None:
        """Feed one observed raw micro-batch size into the candidate
        auto-tuner's sliding window (last 64 cycles)."""
        self._tuner_batch_obs.append(raw)
        if len(self._tuner_batch_obs) > 64:
            del self._tuner_batch_obs[:-64]

    def _candidate_bucket(self, n_pad: int) -> int:
        """The restricted solve's candidate-column bucket: the config
        value snapped UP to a power of two so the (P, C) solve shapes
        stay inside the warmed grid.

        With ``incremental.autoTune`` on AND a warmed C ladder, the
        bucket is instead READ from observed telemetry: the smallest
        warmed C that (a) admits the recent micro-batch sizes under
        maxBatchFrac and (b) leaves 2x headroom over the deepest
        candidate-frame position restricted solves actually placed
        into (pods landing deep in the frame means the rank order is
        being fought — widen before under-placement starts declining
        cycles). Every ladder rung was compiled (and its signatures
        pre-registered) by _warm_incremental, so a tuner move NEVER
        retraces; without a warmed ladder the tuner stays pinned to
        the configured bucket."""
        inc = self.incremental
        c0 = bucket_size(max(inc.candidate_bucket, 1))
        if not inc.auto_tune or not self._warmed_cbuckets:
            return c0
        need = max(
            max(self._tuner_batch_obs, default=1)
            / max(inc.max_batch_frac, 1e-6),
            2 * self._tuner_depth_max,
            1,
        )
        ladder = sorted(self._warmed_cbuckets)
        for c in ladder:
            if c >= need:
                return c
        return ladder[-1]

    def _incremental_eligible(self, batch, nominated, dn, dt, dv,
                              snap_mode, key, nt) -> bool:
        """May THIS cycle take the restricted solve? The gates mirror
        the fused lean route's trace-time facts (whole-batch host
        coupling and cross-node constraint classes need the full plane)
        plus the incremental-specific ones: a live resident snapshot in
        clean/delta mode (a full rebuild recomputed the whole score
        plane — nothing to reuse), a micro-batch small enough for the
        candidate bucket, and a dirty frontier under the blowout
        threshold. Ineligible cycles take the cold solve; blowouts also
        drop the cache (the documented invalidation edges)."""
        inc = self.incremental
        if not inc.enabled:
            return False
        if self.solver not in ("batch", "sinkhorn"):
            return False
        if snap_mode == "full":
            # the whole plane was just recomputed (node-set change,
            # interner/pack-epoch growth, explicit invalidation, dirty
            # blowout at the snapshot layer) — warm state is dead
            self._drop_incremental("full-snapshot")
            return False
        if snap_mode not in ("clean", "delta") or dn is None:
            return False
        if self.extenders or nominated:
            return False
        fw = self.framework
        if (fw.has_host_filters() or fw.has_host_scores()
                or fw.has_batch_filters() or fw.has_batch_scores()):
            return False
        if self.percentage_of_nodes_to_score is not None:
            return False
        if (self.scenario_pack is not None
                and not self.scenario_pack.restricted_ok):
            # capability-driven, not blanket: a pack whose cost term is
            # per-column (restricted_ok — it survives restriction to a
            # gathered (P, C) frame bit-for-bit) rides the restricted
            # path, its cost joining the frame's extra_score and its
            # candidate_hint reserving quota columns; packs that need
            # global cross-column structure keep the dense oracle
            return False
        # gangs RIDE the restricted path (their members' candidates
        # union in the frame; the gang-topology pack's hint reserves
        # home-slice columns): _restricted_tail re-checks all-or-
        # nothing after the solve and declines to the dense ladder —
        # which owns rollback + failure analytics — on any incomplete
        # group. No blanket exclusion.
        # constraint classes that couple across the FULL node axis:
        # ports/volumes couple in-batch per node (excluded outright);
        # topology masks reduce over whole topology groups — only safe
        # to drop when the batch-scoped gates prove them vacuous
        if dv is not None or not key.no_ports:
            return False
        if dt is not None and not (key.no_pod_aff and key.no_spread):
            return False
        # the tuner observes the RAW batch size BEFORE the C compare:
        # batches bounced for being too big are exactly the evidence
        # that should widen the bucket next cycle
        self._note_tuner_batch(len(batch))
        n_pad = dn.valid.shape[0]
        C = self._candidate_bucket(n_pad)
        if C >= n_pad:
            return False  # restriction would not shrink the plane
        if len(batch) > inc.max_batch_frac * C:
            return False
        dirty = len(getattr(self.cache, "last_patched_idx", ()))
        if dirty > inc.max_dirty_frac * max(nt.n, 1):
            self._drop_incremental("dirty-frac")
            return False
        return True

    def _restricted_tail(self, batch, cycle, res, t0, trace, nt, dn, ds,
                         dp, node_order, key):
        """The incremental cycle's solve + bind tail: pick candidate
        columns from the cached score plane (O(N log C) — the only
        full-N work), gather them into a (P, C) view, solve with the
        stock kernels, validate on device, and read back ONE global
        assignment vector (the candidate index list never crosses —
        d2h stays at the answer-sized budget). The result is accepted
        only when EVERY pod placed: an under-placed batch (a pod may be
        feasible on a non-candidate column, and the failure analytics
        need the full plane) and any solve/validation error return None
        so the caller re-solves cold — the PR-1 ladder's correctness
        fallback, unchanged."""
        from kubernetes_tpu.ops.arrays import gather_candidates

        inc = self.incremental
        # gang minMember pre-check (host ints only): a group that cannot
        # meet its quorum even counting cache-placed members will be
        # rolled back whoever solves it — decline NOW so the dense
        # ladder produces the proper per-pod GangIncomplete analytics
        # instead of burning a restricted solve first
        gang_need: Dict[str, List[int]] = {}
        for gp in batch:
            if gp.pod_group:
                g = gang_need.setdefault(gp.pod_group, [0, 0])
                g[0] += 1
                g[1] = max(g[1], gp.pod_group_min_available)
        for gname, (cnt, need) in gang_need.items():
            if cnt + self.cache.group_members(gname) < need:
                m = getattr(self.metrics, "incremental_cycles", None)
                if m is not None:
                    m.inc(scope="declined")
                return None
        summary = None
        get_summary = getattr(self.cache, "score_summary", None)
        if get_summary is not None:
            summary = get_summary()
        if summary is None:
            return None
        n_pad = dn.valid.shape[0]
        C = self._candidate_bucket(n_pad)
        idxs = [int(i) for i in getattr(self.cache, "last_patched_idx",
                                        ())]
        dirty = np.zeros((n_pad,), bool)
        if idxs:
            dirty[idxs] = True  # host ints from the cache's delta ledger
        # a post-drop lazy rebuild recomputed the WHOLE plane this
        # cycle — honest reuse is zero, not 1 - dirty/live
        if getattr(self.cache, "last_summary_rebuilt", False):
            reuse = 0.0
        else:
            reuse = max(0.0, 1.0 - len(idxs) / max(nt.n, 1))
        warm = key.warm_tol is not None
        gen = getattr(self.cache, "summary_generation", 0)
        pot_key = (dp.valid.shape[0], C, gen)
        sk_init = None
        if warm and self._sk_warm_pot is not None \
                and self._sk_warm_pot[0] == pot_key:
            sk_init = self._sk_warm_pot[1]
        # mesh-sharded candidate pick: per-shard local top-C over the
        # node-sharded resident plane, replicated merge of the (S, C)
        # winners — bit-identical to the single-pass pick (the parity
        # suite pins it across mesh {1, 2, 4, 8})
        ns = int(self.mesh.devices.size) if self._mesh_live else 1
        # group-quota hint: the pack's candidate columns (a gang's home
        # slice) get a RESERVED split of the frame, capped at
        # groupQuotaFrac so a whole hinted zone can never crowd the
        # plain-ranked candidates out
        hint = hq = None
        if self.scenario_pack is not None:
            hm = self.scenario_pack.candidate_hint(batch, nt, node_order)
            if hm is not None:
                h = np.zeros((n_pad,), bool)
                h[: hm.shape[0]] = hm
                hint = jnp.asarray(h)
                hq = max(int(inc.group_quota_frac * C), 1)
        # retrace telemetry: the candidate/gather program and the
        # restricted solve program are distinct compiled sites — both
        # registered so the zero-retrace contract covers them
        self.obs.jax.record_call(
            "incremental", summary.rank,
            static=(C, n_pad, self._mesh_live, ns, hint is None, hq))
        try:
            with self.obs.span("solve:restricted"):
                cand, sub_dn = gather_candidates(
                    summary, jnp.asarray(dirty), dn, C, hint_mask=hint,
                    num_shards=ns, hint_quota=(hq or 0))
                # restricted_ok pack cost on the GATHERED frame: the
                # term is per-column by the capability contract, so
                # cost over sub_dn equals the dense term restricted to
                # the candidate columns — the objective survives the
                # sparsity-first route unchanged
                extra_score = None
                if self.scenario_pack is not None:
                    with self.obs.span("scenario:cost"):
                        extra_score = self.scenario_pack.cost(
                            batch, nt, node_order, dp, sub_dn)
                out = self._solve(key, "restricted", dp, sub_dn, ds,
                                  extra_score=extra_score, sk_init=sk_init)
                a_local, u_local = out[0], out[1]
                potentials = out[3] if warm else None
                # placement-rank telemetry: the deepest candidate-frame
                # position any pod placed into, reduced to ONE device
                # scalar riding the existing solve-result readback (a
                # (P,) position vector would cost +4 B/pod and breach
                # the answer-sized budget). The auto-tuner reads it to
                # decide when the frame is running hot.
                depth = jnp.max(jnp.where(dp.valid & (a_local >= 0),
                                          a_local, jnp.int32(-1)))
                assigned, _, rounds, ext = self._validated_readback(
                    "restricted", out, dp, sub_dn, cand=cand,
                    extra={"depth": depth})
        except Exception as e:
            # ANY restricted failure — a lying solver, a device error,
            # a validation verdict — declines the attempt; the caller
            # re-solves cold through the full ladder (which owns the
            # breaker/retry/fallback machinery)
            klog.warning("restricted solve declined (%s); cold solve", e)
            self._drop_incremental("restricted-error")
            m = getattr(self.metrics, "incremental_cycles", None)
            if m is not None:
                m.inc(scope="declined")
            return None
        self._tuner_depth_max = max(self._tuner_depth_max,
                                    int(ext["depth"]) + 1)
        placed = assigned[: len(batch)]
        if (placed < 0).any():
            # a pod the candidate set could not place might fit on a
            # non-candidate column — only the cold solve can say (and
            # produce the failure analytics / preemption inputs). For a
            # gang member this is ALSO the all-or-nothing edge: the
            # dense re-solve owns the rollback + GangIncomplete
            # analytics, so one decline covers both contracts.
            m = getattr(self.metrics, "incremental_cycles", None)
            if m is not None:
                m.inc(scope="under-placed")
            return None
        # ledger coverage for the cycle's candidate-frame residents:
        # the (C, ·) gathered sub-table + the (C,) index map (top-k
        # temporaries are XLA-internal — the warmup memory_analysis
        # capture accounts those). Re-registered per restricted cycle
        # (same name = overwrite); the scheduler. prefix dies on every
        # invalidation edge with the rest of the warm state.
        self.obs.memledger.register_tree(
            "scheduler.candidate_frame", sub_dn, cand,
            shape=f"C{C}of{n_pad}")
        if warm and potentials is not None:
            self._sk_warm_pot = (pot_key, potentials)
            # the carry is device-resident state: on the ledger until
            # the next invalidation edge (_drop_incremental)
            self.obs.memledger.register_tree(
                "scheduler.sk_warm_potentials", potentials,
                shape=f"P{pot_key[0]}xC{pot_key[1]}")
        self._incr_active = True
        # scenario quality on the restricted route: the reduction runs
        # over the CANDIDATE FRAME (every placement lands inside it, so
        # nodes_used/gang stats are exact; the capacity-shaped scores
        # are frame-local — docs/scenarios.md). Dispatched now so the
        # device works while the host binds, read back after.
        q_dev = self._quality_dispatch(a_local, u_local, dp, sub_dn)
        res.rounds = rounds
        res.solver_tier = self.solver
        res.solve_scope = "restricted"
        res.reuse_frac = round(reuse, 4)
        solve_s = trace.total_s()
        trace.step(f"restricted solve done ({res.rounds} rounds, "
                   f"C={C}, reuse={reuse:.3f})")
        self.metrics.algorithm_duration.observe(solve_s)
        bind_span = trace.begin_span("bind")
        for i, pod in enumerate(batch):
            self._admit_pod(pod, node_order[int(placed[i])], cycle, res)
        trace.end_span(bind_span)
        trace.step(f"bound {res.scheduled}, failed {res.unschedulable}")
        if q_dev is not None:
            self._quality_read(q_dev, res, batch, assigned, nt)
        if getattr(self.obs.config, "explain", True):
            # no filter-pass failures by construction (everything
            # placed), but admission-tail failures still get report
            # rows and the reason gauges roll over to this cycle
            self._build_explain_report(cycle, batch, [], None, nt.n, res)
        return self._finish_cycle(res, cycle, t0, solve_s, trace,
                                  label=" (restricted)")

    def _cold_blocks(self, n_pad: int, C: int) -> int:
        """How many capacity-balanced blocks the partitioned cold solve
        runs: ``incremental.coldBlocks``, or (0 = auto) the padded node
        bucket over the candidate bucket capped at 8 — wide enough that
        B·C covers thousands of columns at 50k nodes, bounded so cold
        latency stays a handful of fixed-size solves. Always clamped so
        B·C fits the table (the top-(B·C) pick must be a real cut)."""
        inc = self.incremental
        b = inc.cold_blocks or min(8, n_pad // max(C, 1))
        return max(min(b, n_pad // max(C, 1)), 0)

    def _partitioned_cold_eligible(self, batch, nominated, dn, dt, dv,
                                   key) -> bool:
        """May THIS cycle take the PARTITIONED cold solve (sparsity-
        first primary mode)? Engages when the restricted warm route did
        not take the cycle — a full-snapshot rebuild, an oversized
        batch, a declined/under-placed restricted attempt — and the
        same trace-time facts hold that make a candidate frame
        complete: no whole-batch host coupling, no cross-node
        constraint classes, no gangs or scenario packs (both keep the
        dense oracle's monolithic cold semantics — gang rollback and
        pack quality want the full plane when solving cold). The dense
        solve remains the correctness fallback: a partitioned attempt
        that cannot place its whole batch declines rather than binding
        a partial answer."""
        inc = self.incremental
        if not (inc.enabled and inc.primary):
            return False
        if self.solver not in ("batch", "sinkhorn"):
            return False
        if dn is None or not batch:
            return False
        if self.extenders or nominated:
            return False
        fw = self.framework
        if (fw.has_host_filters() or fw.has_host_scores()
                or fw.has_batch_filters() or fw.has_batch_scores()):
            return False
        if self.percentage_of_nodes_to_score is not None:
            return False
        if self.scenario_pack is not None:
            return False
        if any(p.pod_group for p in batch):
            return False
        if dv is not None or not key.no_ports:
            return False
        if dt is not None and not (key.no_pod_aff and key.no_spread):
            return False
        n_pad = dn.valid.shape[0]
        C = self._candidate_bucket(n_pad)
        return C < n_pad and self._cold_blocks(n_pad, C) >= 2

    def _partitioned_cold_tail(self, batch, cycle, res, t0, trace, nt,
                               dn, ds, dp, node_order, key):
        """The partitioned cold solve: rank every column once (the only
        full-N work), deal the top B·C columns round-robin into B
        capacity-balanced blocks of WIDTH C — the restricted path's
        candidate bucket, so every block runs the ALREADY-COMPILED
        (P, C) restricted program and a cold cycle adds zero new solver
        shapes — then solve the blocks in sequence, masking placed pods
        out of each next block's pod validity (blocks are column-
        disjoint, so no cross-block usage updates exist to miss).
        Unplaced remainder takes ONE final restricted pass over a fresh
        top-C pick from the usage-overlaid table (earlier placements
        debited). Placements accumulate HOST-side and bind only when
        the WHOLE batch placed; anything less declines to the dense
        ladder, which owns failure analytics and preemption. Cold cost:
        O(N log(B·C)) selection + (≤ B + 1) fixed (P, C) solves —
        sublinear in N, vs the dense plane's O(P·N)."""
        from kubernetes_tpu.ops.arrays import (
            gather_candidates,
            gather_node_rows,
        )
        from kubernetes_tpu.ops.assign import (
            _apply_batch,
            nodes_with_usage,
            usage_from_nodes,
        )
        from kubernetes_tpu.ops.fused_score import (
            node_summary,
            partition_columns,
        )

        n_pad = dn.valid.shape[0]
        P_pad = dp.valid.shape[0]
        C = self._candidate_bucket(n_pad)
        B = self._cold_blocks(n_pad, C)
        ns = int(self.mesh.devices.size) if self._mesh_live else 1
        flags = self._summary_flags
        get_summary = getattr(self.cache, "score_summary", None)
        summary = get_summary() if get_summary is not None else None
        if summary is None:
            # no live cache (full rebuild just invalidated it): one
            # fresh O(N) summary pass — still nothing (P, N)-shaped
            summary = node_summary(dn, **flags)
        self.obs.jax.record_call(
            "partition", summary.rank,
            static=(B, C, n_pad, ns, self._mesh_live))
        pending = np.zeros((P_pad,), bool)
        pending[: len(batch)] = True
        assigned = np.full((len(batch),), -1, np.int64)
        zeros_dirty = jnp.zeros((n_pad,), bool)

        def solve_frame(valid, sub_dn, cand):
            """One (P, C) frame solve + validate + global mapping over
            the still-pending pods; ONE readback per frame (the declared
            cold-block boundary). Placements accumulate host-side."""
            dp_f = dp._replace(valid=dp.valid & jnp.asarray(valid))
            out = self._solve(key, "partitioned", dp_f, sub_dn, ds)
            got, _, rounds, _ = self._validated_readback(
                "partitioned", out, dp_f, sub_dn, cand=cand,
                site="cold-block")
            res.rounds += rounds
            for i in range(len(batch)):
                if pending[i] and got[i] >= 0:
                    assigned[i] = got[i]
                    pending[i] = False

        try:
            with self.obs.span("solve:partitioned", blocks=B):
                blocks = partition_columns(summary, zeros_dirty, B, C,
                                           ns)
                for b in range(B):
                    if not pending[: len(batch)].any():
                        break
                    solve_frame(pending, gather_node_rows(dn, blocks[b]),
                                blocks[b])
                if pending[: len(batch)].any():
                    # remainder pass: one fresh top-C frame over the
                    # usage-OVERLAID table (every block placement
                    # debited — blocks were column-disjoint, so this is
                    # the first moment cross-block state must meet)
                    acc = np.full((P_pad,), -1, np.int64)
                    acc[: len(batch)] = assigned
                    u = _apply_batch(
                        usage_from_nodes(dn), dp,
                        jnp.asarray(np.maximum(acc, 0)),
                        jnp.asarray(acc >= 0) & dp.valid)
                    dn_u = nodes_with_usage(dn, u)
                    sum_u = node_summary(dn_u, **flags)
                    self.obs.jax.record_call(
                        "incremental", sum_u.rank,
                        static=(C, n_pad, self._mesh_live, ns, True,
                                None))
                    cand, sub_dn = gather_candidates(
                        sum_u, zeros_dirty, dn_u, C, num_shards=ns)
                    solve_frame(pending, sub_dn, cand)
        except Exception as e:
            # any failure — a lying solver, device error, validation
            # verdict — declines the whole attempt; the dense ladder
            # owns breakers/retries/fallbacks (nothing bound yet, so
            # the decline is free of rollback)
            klog.warning("partitioned cold solve declined (%s); dense "
                         "solve", e)
            m = getattr(self.metrics, "incremental_cycles", None)
            if m is not None:
                m.inc(scope="declined")
            return None
        if pending[: len(batch)].any():
            # under-placed: a remainder pod may fit on a column outside
            # every frame — only the dense solve can say, and the
            # failure analytics / preemption inputs need the full plane
            m = getattr(self.metrics, "incremental_cycles", None)
            if m is not None:
                m.inc(scope="under-placed")
            return None
        res.solver_tier = self.solver
        res.solve_scope = "partitioned"
        res.cold_blocks = B
        res.reuse_frac = 0.0
        solve_s = trace.total_s()
        trace.step(f"partitioned cold solve done ({res.rounds} rounds, "
                   f"B={B}, C={C})")
        self.metrics.algorithm_duration.observe(solve_s)
        bind_span = trace.begin_span("bind")
        for i, pod in enumerate(batch):
            self._admit_pod(pod, node_order[int(assigned[i])], cycle,
                            res)
        trace.end_span(bind_span)
        trace.step(f"bound {res.scheduled}, failed {res.unschedulable}")
        if getattr(self.obs.config, "explain", True):
            self._build_explain_report(cycle, batch, [], None, nt.n,
                                       res)
        return self._finish_cycle(res, cycle, t0, solve_s, trace,
                                  label=" (partitioned)")

    def _pipelined_tail(self, batch, cycle, res, t0, trace, nt, dn, ds, dt,
                        node_order, key, owner_cols) -> CycleResult:
        """Double-buffered pack→solve→readback→bind pipeline over fixed
        sub-batches (SURVEY §7.2 step 9): while chunk k's solve runs on
        device (JAX async dispatch), the host packs chunk k+1 and applies
        chunk k−1's binds. Chunking and the usage-chain data dependencies
        are identical at every depth ≥ 2 — only host scheduling overlaps —
        so placements are depth-invariant by construction (pinned by
        tests/test_pipeline.py). Every chunk pads to ONE bucket, so the
        whole cycle runs a single solver jit signature. Every chunk packs
        against ``owner_cols``, the whole batch's owner columns (the ones
        ``dn`` carries), so a chunk's placements count for the next."""
        import numpy as np

        from kubernetes_tpu.ops.assign import nodes_with_usage
        from kubernetes_tpu.ops.arrays import volumes_to_device
        from kubernetes_tpu.ops.predicates import decode_reasons
        from kubernetes_tpu.snapshot import FIXED_RESOURCE_NAMES

        pk = self.cache.packer
        C = self.pipeline_chunk
        chunks = [batch[i:i + C] for i in range(0, len(batch), C)]
        res.pipeline_chunks = len(chunks)
        self.metrics.pipeline_chunks.inc(len(chunks))
        chunk_pad = bucket_size(C)
        explain_on = getattr(self.obs.config, "explain", True)
        solver = key.solver
        # a restricted_ok scenario pack's per-column cost term joins
        # each chunk's solve as extra_score (the _pipeline_eligible
        # capability contract)
        pack = self.scenario_pack

        dn_cur = dn
        solve_s = 0.0
        tier_last = solver
        failed_global: List[int] = []
        reasons_row: Dict[int, Tuple[str, ...]] = {}
        fit_msgs: Dict[int, str] = {}
        rmat_rows: Dict[int, np.ndarray] = {}
        ex_parts: List[Tuple[int, int, dict]] = []  # (offset, n, ex dict)

        def pack_chunk(k):
            with self.obs.span(f"pipeline:pack@{k}", pods=len(chunks[k])):
                dp_c = self._place(pods_to_device(
                    pk.pack_pods(chunks[k], owner_cols=owner_cols),
                    pad_to=chunk_pad))
                dv_c = sv_c = None
                if any(p.volumes for p in chunks[k]):
                    dv_c = self._place(
                        volumes_to_device(pk.pack_volume_tables(chunks[k])))
                    sv_c = _static_vol_pass(dp_c, dn, ds, dv_c)
                # per-chunk h2d accounting: the pod tables are the
                # steady-state cycle's largest upload
                self.obs.jax.record_upload(
                    "snapshot", dp_c,
                    *([dv_c] if dv_c is not None else []))
                return dp_c, dv_c, sv_c

        def dispatch(k, packed, dn_in):
            """Queue chunk k's solve on device (async); returns the
            device triple or None when the breaker/deadline sheds it
            straight to the ladder."""
            dp_c, dv_c, sv_c = packed
            if not self._breaker(f"solver:{solver}").allow():
                return None
            if (self._cycle_deadline is not None
                    and self.clock() >= self._cycle_deadline):
                return None
            sc = None
            if pack is not None:
                # per-chunk pack cost on THIS chunk's pod table against
                # the chunk's node view — per-column by the
                # restricted_ok contract, so chunking preserves the
                # objective exactly
                with self.obs.span(f"scenario:cost@{k}"):
                    sc = pack.cost(chunks[k], nt, node_order, dp_c,
                                   dn_in)
            with self.obs.span(f"pipeline:dispatch@{k}", tier=solver):
                # the same key as the monolithic tier, so warmed/
                # monolithic/pipelined solves share ONE compiled program
                # per shape; the last chunk's sinkhorn stats ride to
                # end_cycle like the monolith's single solve
                return self._solve(key, solver, dp_c, dn_in, ds, topo=dt,
                                   vol=dv_c, static_vol=sv_c,
                                   extra_score=sc, n_pods=len(chunks[k]))

        def settle(k, packed, out, dn_in):
            """Block on chunk k's result — validated on device, verdict
            riding the chunk's ONE readback (_validated_readback) — and
            fall back to the full degradation ladder on any failure (the
            chunk then runs with depth-1 semantics). Returns (assigned
            host array or None, usage, tier)."""
            nonlocal solve_s
            chunk = chunks[k]
            dp_c, dv_c, sv_c = packed
            br = self._breaker(f"solver:{solver}")
            ts = self.clock()
            if out is not None:
                try:
                    with self.obs.span(f"pipeline:readback@{k}"):
                        a, u_dev, rounds, _ = self._validated_readback(
                            solver, out, dp_c, dn_in)
                    a = a[: len(chunk)].copy()
                    br.record_success()
                    res.rounds += rounds
                    solve_s += self.clock() - ts
                    return a, u_dev, solver
                except Exception as e:
                    br.record_failure()
                    klog.warning(
                        "pipelined chunk %d solve failed (%s); ladder", k, e)
            # shed (open breaker / blown deadline) or failed readback:
            # this chunk re-solves through the full ladder — retries,
            # CPU fallback, greedy oracle, per-tier breakers included.
            # The pack cost is rebuilt so the objective survives the
            # fallback tiers exactly as it does the monolithic ladder.
            sc = (pack.cost(chunk, nt, node_order, dp_c, dn_in)
                  if pack is not None else None)
            ladder = self._solve_ladder(
                key, chunk, dp_c, dn_in, ds, dt, dv_c, sv_c, None, None,
                sc, res)
            if ladder is None:
                for pod in chunk:
                    self._fail(pod, cycle, res, ("SolverUnavailable",))
                solve_s += self.clock() - ts
                return None, None, ""
            a_host, u_dev, rounds, tier = ladder
            a = a_host[: len(chunk)].copy()
            res.rounds += int(rounds)
            solve_s += self.clock() - ts
            return a, u_dev, tier

        def chunk_failures(k, offset, a, packed):
            """Failure reasons + explain for chunk k's unplaced pods,
            evaluated against the post-chunk usage view (what the serial
            loop would have seen last). Everything is reduced on device
            (obs/explain.explain_reduce) and read back small; per-node
            bit rows are gathered for the failed pods only — preemption
            fodder proportional to the failures, not the chunk."""
            failed_idx = [i for i, t in enumerate(a) if t < 0]
            if not failed_idx:
                return
            dp_c, dv_c, sv_c = packed
            from kubernetes_tpu.obs.explain import explain_reduce
            from kubernetes_tpu.ops.predicates import (
                fit_error_message_from_counts,
            )

            fr = _filter_pass(dp_c, dn_cur, ds, dt, dv_c, sv_c,
                              self.pred_mask)
            fm = np.zeros((dp_c.valid.shape[0],), bool)
            fm[failed_idx] = True
            ex = explain_reduce(
                fr.reasons, dn_cur.valid, jnp.asarray(fm), dp_c.req,
                dn_cur.allocatable - dn_cur.requested, dn_cur.ready,
                dn_cur.network_unavailable)
            rows_dev = None
            if self.enable_preemption:
                rows_dev = jnp.take(
                    fr.reasons, jnp.asarray(failed_idx, dtype=jnp.int32),
                    axis=0)
            ex_h = self.obs.jax.readback("explain", ex)._asdict()
            if explain_on:
                ex_parts.append((offset, len(chunks[k]), ex_h))
            if rows_dev is not None:
                rows = self.obs.jax.readback("preempt-reasons", rows_dev)
            n_valid = nt.n
            # host rows (pack memo hit)
            pt_c = pk.pack_pods(chunks[k], owner_cols=owner_cols)
            res_names = (list(FIXED_RESOURCE_NAMES)
                         + pk.u.scalar_resources.items())[: pt_c.req.shape[1]]
            for j, i in enumerate(failed_idx):
                g = offset + i
                bits = int(ex_h["pod_bits"][i])
                reasons_row[g] = decode_reasons(bits)
                if rows_dev is not None:
                    rmat_rows[g] = rows[j]
                failed_global.append(g)
                if bits:
                    fit_msgs[g] = fit_error_message_from_counts(
                        ex_h["per_pod"][i], ex_h["insufficient"][i],
                        ex_h["not_ready"][i], ex_h["net_unavail"][i],
                        n_valid, pt_c.req[i], res_names)

        def bind_chunk(k, offset, a):
            with self.obs.span(f"pipeline:bind@{k}"):
                for i, pod in enumerate(chunks[k]):
                    t = int(a[i])
                    if t < 0:
                        g = offset + i
                        self._fail(pod, cycle, res, reasons_row.get(g, ()),
                                   message=fit_msgs.get(g))
                    else:
                        self._admit_pod(pod, node_order[t], cycle, res)

        # ---- the pipeline proper ----
        offset = 0
        packed = pack_chunk(0)
        pend = (packed, dispatch(0, packed, dn_cur), dn_cur)
        for k in range(len(chunks)):
            # pack chunk k+1 NOW: the host packs while chunk k's solve
            # runs on device (the overlap the executor exists for)
            nxt = (pack_chunk(k + 1)
                   if k + 1 < len(chunks) else None)
            packed_k, out_k, dn_in = pend
            a, u_dev, tier = settle(k, packed_k, out_k, dn_in)
            if tier:
                tier_last = tier
            if u_dev is not None:
                dn_cur = nodes_with_usage(dn_in, u_dev)
            if a is not None:
                # the failure passes ride the device queue BEFORE chunk
                # k+1's solve so their readback never waits behind it
                chunk_failures(k, offset, a, packed_k)
            if nxt is not None:
                pend = (nxt, dispatch(k + 1, nxt, dn_cur), dn_cur)
            if a is not None:
                # bind on host while chunk k+1 solves on device
                bind_chunk(k, offset, a)
            offset += len(chunks[k])

        res.solver_tier = tier_last
        self.metrics.algorithm_duration.observe(solve_s)
        trace.step(
            f"pipeline done ({len(chunks)} chunks, {res.rounds} rounds)")

        if explain_on:
            ex_host = None
            if ex_parts:
                P = len(batch)
                B = int(ex_parts[0][2]["pair_hist"].shape[0])
                ex_host = {
                    "per_pod": np.zeros((P, B), np.int32),
                    "one_bit": np.zeros((P, B), np.int32),
                    "best_bit": np.zeros((P,), np.int32),
                    "best_gain": np.zeros((P,), np.int32),
                    "feasible": np.zeros((P,), np.int32),
                    "pair_hist": np.zeros((B,), np.int64),
                    "pods_blocked": np.zeros((B,), np.int64),
                }
                for off, n, part in ex_parts:
                    # parts are host arrays already (readback output)
                    for f in ("per_pod", "one_bit", "best_bit",
                              "best_gain", "feasible"):
                        ex_host[f][off:off + n] = part[f][:n]
                    ex_host["pair_hist"] += part["pair_hist"].astype(
                        np.int64)
                    ex_host["pods_blocked"] += part["pods_blocked"].astype(
                        np.int64)
            self._build_explain_report(
                cycle, batch, sorted(failed_global), ex_host, nt.n, res)

        preempt_idx = [g for g in sorted(failed_global) if g in rmat_rows]
        if self.enable_preemption and preempt_idx:
            width = next(iter(rmat_rows.values())).shape[0]
            rmat_full = np.zeros((len(batch), width), np.int64)
            for g, row in rmat_rows.items():
                rmat_full[g] = row
            pt0 = self.clock()
            with self.obs.span("preemption"):
                self._run_preemption(
                    batch, preempt_idx, rmat_full, node_order, res)
            self.metrics.preemption_duration.observe(self.clock() - pt0)
            trace.step(f"preemption ({res.preempted} victims)")

        return self._finish_cycle(res, cycle, t0, solve_s, trace,
                                  label=f" (pipelined x{len(chunks)})")

    def _run_extenders(self, batch, base_fr, node_order, early_fail):
        """Call each extender's Filter then Prioritize for interested pods
        against the built-in-feasible node set (``base_fr`` — the shared
        per-cycle filter pass). Ignorable extenders drop out on error;
        others fail the pod (generic_scheduler.go:539-566)."""
        from kubernetes_tpu.extender import ExtenderError

        interested = [
            (i, p) for i, p in enumerate(batch)
            if any(e.is_interested(p) for e in self.extenders)
        ]
        if not interested:
            return None, None
        # the built-in-feasible mask crosses to host for the extender
        # HTTP fan-out — a real d2h boundary, declared + accounted
        base = self.obs.jax.readback("extender-mask", base_fr.mask)
        rows = {n: j for j, n in enumerate(node_order)}
        nodes_by_name = {nd.name: nd for nd in self.cache.nodes()}
        em = np.ones(base.shape, bool)
        es = np.zeros(base.shape, np.float32)
        rc = self.robustness
        for i, pod in interested:
            feasible = [n for n in node_order if base[i, rows[n]]]
            allowed = set(feasible)
            for ext in self.extenders:
                if not ext.is_interested(pod):
                    continue
                ename = ext.name() if hasattr(ext, "name") else repr(ext)
                br = self._breaker(f"extender:{ename}")
                # degraded mode: an open breaker (the endpoint is known
                # down) or a blown cycle deadline sheds the call — the
                # pod schedules on built-in filters alone rather than
                # failing for as long as the remote is dead
                shed = (self._cycle_deadline is not None
                        and self.clock() >= self._cycle_deadline)
                if shed or not br.allow():
                    # ROADMAP bug (a): a config-Ignorable extender must
                    # never fail pods — shedding it is exactly the
                    # "unreachable Ignorable extender" case the flag
                    # covers (extender.go:124), independent of the
                    # degrade-to-ignorable robustness override
                    if rc.extender_degrade_to_ignorable or ext.is_ignorable():
                        self.metrics.extender_degraded.inc(extender=ename)
                        continue
                    allowed = set()
                    early_fail[i] = f"Extender:{ename} unavailable"
                    break
                # clamp the transport timeout to the remaining cycle
                # budget (deadline propagation across the HTTP seam);
                # re-armed per verb group — and explicitly CLEARED on
                # unbounded cycles so a clamp from a deadline-bearing
                # cycle can't leak into this one (ROADMAP bug (b))
                if hasattr(ext, "set_call_budget"):
                    if self._cycle_deadline is not None:
                        ext.set_call_budget(
                            max(self._cycle_deadline - self.clock(), 1e-3))
                    else:
                        ext.set_call_budget(None)
                try:
                    names, _failed = ext.filter(
                        pod, [n for n in feasible if n in allowed], nodes_by_name
                    )
                    allowed &= set(names)
                    scores, weight = ext.prioritize(
                        pod, sorted(allowed), nodes_by_name
                    )
                    br.record_success()
                    for n, sc in scores.items():
                        if n in rows:
                            es[i, rows[n]] += weight * sc
                except ExtenderError as e:
                    br.record_failure()
                    if ext.is_ignorable():
                        continue  # skip this extender (extender.go:124)
                    allowed = set()
                    early_fail[i] = f"Extender:{e}"
                    break
            keep = np.zeros(base.shape[1], bool)
            for n in allowed:
                keep[rows[n]] = True
            em[i] = keep
        return jnp.asarray(em), jnp.asarray(es)

    def _admit_pod(self, pod: Pod, node_name: str, cycle: int,
                   res: CycleResult) -> None:
        """The per-pod admission tail for a PLACED pod: AssumePodVolumes →
        Reserve → cache assume → Permit → bind. Shared by the monolithic
        bind loop and the pipelined executor's per-chunk bind stage."""
        from kubernetes_tpu.framework import WAIT as _WAIT, CycleState

        if not self._fence_ok():
            # deposed mid-cycle: abort BEFORE assuming — the new leader
            # owns this pod now; racing its bind at the hub CAS is the
            # exact split-brain window the fence closes
            self._fenced(pod, cycle, res)
            return
        fw = self.framework
        st = self._cycle_states.get(pod.key()) or CycleState()
        # AssumePodVolumes (scheduler.go:523 assumeVolumes, before
        # Reserve): reserve a PV per unbound delayed-binding claim for
        # THIS node; a racing claimant earlier in the batch may have
        # taken the last one — then this pod fails and requeues.
        # A reservation held from a PREVIOUS cycle (Permit-parked pod
        # popped again) must survive this attempt's failure paths.
        vols_held_before = pod.key() in self.volume_binder.assumed
        vok, vmsg = self.volume_binder.assume_pod_volumes(
            pod, self.cache.node(node_name)
        )
        if not vok:
            self._fail(pod, cycle, res, (f"VolumeBinding:{vmsg}",))
            return
        # Reserve (scheduler.go:531 RunReservePlugins, before assume)
        rs = fw.run_reserve(st, pod, node_name)
        if not rs.is_success():
            if not vols_held_before:
                self.volume_binder.forget_pod_volumes(pod.key())
            fw.run_unreserve(st, pod, node_name)
            self._fail(pod, cycle, res, (f"Reserve:{rs.message}",))
            return
        try:
            self.cache.assume_pod(pod, node_name)
        except Exception:
            # already in cache (e.g. duplicate queue entry) — requeue
            if not vols_held_before:
                self.volume_binder.forget_pod_volumes(pod.key())
            fw.run_unreserve(st, pod, node_name)
            self._fail(pod, cycle, res, ("AssumeError",))
            return
        # Permit (scheduler.go:561): Wait parks the pod (still assumed,
        # capacity held) until allow/reject/timeout
        ps = fw.run_permit(st, pod, node_name)
        if ps.code == _WAIT:
            res.waiting += 1
            self.obs.journeys.note_permit_park(pod.key())
            return
        if not ps.is_success():
            self.cache.forget_pod(pod.key())
            self.volume_binder.forget_pod_volumes(pod.key())
            fw.run_unreserve(st, pod, node_name)
            self._fail(pod, cycle, res, (f"Permit:{ps.message}",))
            return
        self._bind_pod(pod, node_name, st, res)

    def _bind_pod(self, pod: Pod, node_name: str, st, res: CycleResult) -> bool:
        """PreBind -> Bind (plugins, else default binder) -> PostBind —
        the tail of the reference's async binding goroutine
        (scheduler.go:580,:598,:442-457). Any failure forgets the
        assumption and requeues."""
        from kubernetes_tpu.framework import SKIP as _SKIP

        fw = self.framework
        cycle = self.queue.scheduling_cycle

        if not self._fence_ok():
            # the Permit-resume path reaches here without _admit_pod's
            # gate; the assumption is already held — release it, then
            # take the shared fenced-abort path (the bind RPC itself
            # must never leave a deposed leader)
            self.cache.forget_pod(pod.key())
            self.volume_binder.forget_pod_volumes(pod.key())
            fw.run_unreserve(st, pod, node_name)
            self._fenced(pod, cycle, res)
            self._cycle_states.pop(pod.key(), None)
            return False

        def reject(reason: str) -> bool:
            klog.warning("bind of %s to %s failed: %s", pod.key(),
                         node_name, reason)
            self.cache.forget_pod(pod.key())
            self.volume_binder.forget_pod_volumes(pod.key())
            res.bind_errors += 1
            fw.run_unreserve(st, pod, node_name)
            self._fail(pod, cycle, res, (reason,))
            self._cycle_states.pop(pod.key(), None)
            return False

        # BindPodVolumes (scheduler.go:550 bindVolumes, first step of the
        # async binding phase): commit the assumed PVC->PV claims; a write
        # failure forgets the pod AND releases un-committed reservations
        try:
            committed = self.volume_binder.bind_pod_volumes(pod)
        except Exception as e:
            return reject(f"VolumeBinding:{e}")
        if committed:
            # the pod's volume tokens (zone labels, attach counts of its
            # now-bound PVs) changed; the packed node snapshot must rebuild
            self.cache.invalidate_snapshot()
        s = fw.run_prebind(st, pod, node_name)
        if not s.is_success():
            return reject(f"PreBind:{s.message}")
        self.obs.journeys.note_bind_start(pod.key())
        bt0 = self.clock()
        bs = fw.run_bind(st, pod, node_name)
        if bs.code == _SKIP:
            # an interested binder-extender takes the binding over the
            # default binder (extender.go:360,:382)
            binder = self.binder
            for ext in self.extenders:
                if ext.is_binder() and ext.is_interested(pod):
                    binder = ext
                    break
            # ROADMAP bug (b): re-arm the transport budget for the BIND
            # verb from the remaining cycle deadline — without this the
            # bind call inherits whatever clamp the filter verb left
            # behind (stale, and from a different point in the cycle)
            if hasattr(binder, "set_call_budget"):
                if self._cycle_deadline is not None:
                    binder.set_call_budget(
                        max(self._cycle_deadline - self.clock(), 1e-3))
                else:
                    binder.set_call_budget(None)
            try:
                binder.bind(pod, node_name)
            except Exception as e:
                if self._bind_ambiguous(e):
                    # the AMBIGUOUS class: the hub may have committed
                    # before the response was lost. NEVER blind-retry —
                    # resolve by read-your-write verification instead
                    # (GET the pod, compare uid+nodeName, adopt or
                    # requeue; park when the GET itself is unreachable).
                    verdict = self._handle_ambiguous_bind(
                        pod, node_name, st, res, e, reject)
                    if verdict is not True:
                        return bool(verdict)
                    # adopted: the bind DID land — fall through to the
                    # normal success tail (finish_binding, events, ...)
                else:  # definite failure -> Forget + retry
                    return reject(f"BindError:{e}")
        elif not bs.is_success():
            return reject(f"Bind:{bs.message}")
        self.metrics.binding_duration.observe(self.clock() - bt0)
        self.cache.finish_binding(pod.key())
        self.queue.nominated.delete(pod)
        # scheduling-attempt count for the landed pod (failures recorded
        # in the backoff map + this successful try), then reset so a
        # recreated pod with the same key starts fresh
        self.metrics.pod_scheduling_attempts.observe(
            self.queue.backoff_map.attempts(pod.key()) + 1)
        self.queue.backoff_map.clear_pod(pod.key())
        self.why_pending.pop(pod.key(), None)
        res.scheduled += 1
        res.assignments[pod.key()] = node_name
        # admission timestamp -> bind: the pod's create-to-bind latency
        # (queued_at is the queue-add stamp on this scheduler's clock;
        # 0.0 is a valid fake-clock enqueue time, not "unset")
        res.e2e_latency_s[pod.key()] = max(
            self.clock() - getattr(pod, "queued_at", self.clock()), 0.0)
        self.obs.journeys.note_bound(pod.key(), cycle)
        fw.run_postbind(st, pod, node_name)
        self._cycle_states.pop(pod.key(), None)
        self.event_sink("Scheduled", pod, node_name)
        return True

    # -- ambiguous-outcome bind protocol (network-fault robustness) --------

    def _bind_ambiguous(self, e: Exception) -> bool:
        """Is this bind failure the AMBIGUOUS class (the hub may have
        committed before the response was lost)? ``faults.RPCTimeout``
        always is; raw transport timeouts (socket.timeout /
        TimeoutError) are too, but only a scheduler WITH a hub reader
        can do better than the legacy reject-and-requeue for them — so
        without one their behavior stays exactly as before."""
        import socket

        from kubernetes_tpu.faults import RPCTimeout

        if isinstance(e, RPCTimeout):
            return True
        return (self.pod_reader is not None
                and isinstance(e, (socket.timeout, TimeoutError)))

    def _resolve_ambiguous_bind(self, pod: Pod, node_name: str):
        """Read-your-write verification of an ambiguously timed-out
        bind: GET the pod from the hub (bounded retries, full jitter on
        the per-replica stream) and compare uid + nodeName.

        Returns ``"adopted"`` (the hub HAS our binding — confirm, never
        re-bind), ``"requeued"`` (verified unbound — a retry through
        the normal requeue path is safe), ``"conflict"`` (bound
        elsewhere or recreated under a new uid), ``"gone"`` (deleted
        mid-bind), ``"ttl-parked"`` (no reader attached — fall back to
        the assume TTL / watch confirmation), or ``None`` when the
        verification GET itself stayed unreachable (the caller parks
        the pod and re-probes later)."""
        if self.pod_reader is None:
            return "ttl-parked"
        key = pod.key()
        # the cycle deadline bounds IN-CYCLE verification; on the idle
        # paths (parked re-probes, TTL-expiry verification) the last
        # cycle's absolute deadline is stale — already in the past —
        # and would silently zero the retry budget
        deadline = self._cycle_deadline
        if deadline is not None and self.clock() >= deadline:
            deadline = None
        try:
            cur = self._bind_verify_retry.call(
                lambda: self.pod_reader(key),
                deadline_s=deadline, clock=self.clock)
        except Exception as e:
            klog.warning("ambiguous bind of %s -> %s: verification GET "
                         "failed (%s); parking", key, node_name, e)
            return None
        if cur is None:
            return "gone"
        if getattr(cur, "uid", None) != pod.uid:
            return "conflict"
        if cur.node_name == node_name:
            return "adopted"
        if cur.node_name:
            return "conflict"
        return "requeued"

    def _handle_ambiguous_bind(self, pod: Pod, node_name: str, st, res,
                               exc: Exception, reject) -> object:
        """Resolve one in-cycle ambiguous bind timeout. Returns ``True``
        when the hub turned out to have committed (the caller proceeds
        to the normal success tail), ``False`` when the pod was
        requeued, parked, or dropped here."""
        key = pod.key()
        self.obs.note_ambiguous_bind()
        resolution = self._resolve_ambiguous_bind(pod, node_name)
        self.metrics.bind_ambiguous.inc(
            resolution=resolution or "deferred")
        if resolution is None:
            # the hub is unreachable for verification too: the pod
            # stays ASSUMED (capacity held, NO TTL — a TTL reap would
            # requeue and risk re-binding a committed pod) and every
            # cycle / idle tick re-probes until the hub answers
            klog.warning("bind of %s -> %s timed out ambiguously and "
                         "verification is unreachable; parked assumed",
                         key, node_name)
            self._ambiguous_binds[key] = (pod, node_name, st)
            self.obs.journeys.note_ambiguous_park(key, "bind-timeout")
            self._cycle_states.pop(key, None)
            return False
        if resolution == "adopted":
            klog.V(2).info("ambiguous bind of %s -> %s resolved: hub "
                           "committed — adopted, not re-bound",
                           key, node_name)
            return True
        if resolution == "ttl-parked":
            # no reader: optimistic fallback — arm the assume TTL; the
            # watch MODIFIED confirms a committed bind, the TTL reap
            # requeues an uncommitted one (a late re-bind then fails
            # the hub CAS harmlessly)
            self.cache.finish_binding(key)
            self._cycle_states.pop(key, None)
            return False
        if resolution == "requeued":
            reject(f"BindAmbiguous:verified not committed ({exc})")
            return False
        # conflict / gone: same forget-and-requeue path as a definite
        # bind error; the watch (or reconcile) drops stale queue entries
        reject(f"BindError:ambiguous bind resolved as {resolution}: "
               f"{exc}")
        return False

    def _verify_ambiguous_binds(self) -> None:
        """Re-probe every parked ambiguous bind (cycle path AND
        idle_tick): the watch may have settled it meanwhile (confirmed
        add or delete), else the verification GET is retried and the
        pod adopted / requeued exactly like the in-cycle resolution."""
        if not self._ambiguous_binds:
            return
        res = CycleResult()
        resolved = False
        for key, (pod, node_name, st) in list(
                self._ambiguous_binds.items()):
            # st is None ONLY for a park made by the TTL reap — that
            # pod's ORIGINAL bind already ran the success tail
            # (postbind, Scheduled event, scheduling metrics), so an
            # adoption here must confirm the cache and nothing else;
            # its verdicts keep the expired-* metric labeling so the
            # TTL-expiry series stays distinguishable from in-cycle
            # bind timeouts
            reap_origin = st is None
            watch_settled = not self.cache.is_assumed(key)
            if watch_settled:
                # the watch answered first: a confirmed add flipped the
                # assumption to bound (a delete pops the park in
                # on_pod_delete and reconcile clears parks wholesale,
                # so bound is the only live way here) — an adoption
                # whose read-your-write answer is the hub's own stream;
                # an IN-CYCLE park still owes the full success tail,
                # which its original bind never reached
                del self._ambiguous_binds[key]
                if self.cache.pod(key) is None:
                    continue  # settled out-of-band; nothing to finish
                resolution = "adopted"
            else:
                resolution = self._resolve_ambiguous_bind(pod, node_name)
                if resolution is None:
                    continue  # hub still unreachable: stay parked
                del self._ambiguous_binds[key]
            self.metrics.bind_ambiguous.inc(
                resolution=(f"expired-{resolution}" if reap_origin
                            else resolution))
            resolved = True
            st = st or _new_cycle_state()
            if resolution in ("adopted", "ttl-parked"):
                if resolution == "ttl-parked":
                    # reader detached: back to TTL semantics
                    self.cache.finish_binding(key)
                    continue
                if not watch_settled:
                    # the verification GET is hub truth exactly like a
                    # relist — confirm the binding outright
                    # (reconcile's adopt), never arm a TTL whose reap
                    # would requeue a pod we just PROVED the hub bound
                    self.cache.add_pod(self.cache.pod(key) or pod)
                if reap_origin:
                    klog.V(2).info("parked expired assumption of %s -> "
                                   "%s resolved: adopted", key, node_name)
                    continue
                self.queue.nominated.delete(pod)
                self.metrics.pod_scheduling_attempts.observe(
                    self.queue.backoff_map.attempts(key) + 1)
                self.queue.backoff_map.clear_pod(key)
                self.why_pending.pop(key, None)
                res.scheduled += 1
                res.assignments[key] = node_name
                res.e2e_latency_s[key] = max(
                    self.clock() - getattr(pod, "queued_at",
                                           self.clock()), 0.0)
                self.obs.journeys.note_bound(
                    key, self.queue.scheduling_cycle)
                self.framework.run_postbind(st, pod, node_name)
                self.event_sink("Scheduled", pod, node_name)
                klog.V(2).info("parked ambiguous bind of %s -> %s "
                               "resolved: adopted", key, node_name)
            else:
                self.cache.forget_pod(key)
                self.volume_binder.forget_pod_volumes(key)
                self.framework.run_unreserve(st, pod, node_name)
                res.bind_errors += 1
                if resolution == "requeued":
                    reasons = ("BindAmbiguous:verified not committed",)
                else:
                    reasons = ("BindError:ambiguous bind resolved as "
                               f"{resolution}",)
                self._fail(pod, self.queue.scheduling_cycle, res, reasons)
        if resolved:
            self._record_metrics(res)

    def _process_waiting(self, res: CycleResult) -> None:
        """Resolve Permit waits (waiting_pods_map.go consumers): allowed
        pods proceed to binding; rejected or timed-out pods are forgotten
        and requeued — the reference rejects on timeout
        (framework.go RunPermitPlugins wait loop)."""
        from kubernetes_tpu.framework import CycleState

        fw = self.framework
        now = self.clock()
        for wp in fw.waiting.items():
            key = wp.pod.key()
            st = self._cycle_states.get(key) or CycleState()
            if wp.rejected is not None or (not wp.allowed and now >= wp.deadline):
                fw.waiting.remove(key)
                self.cache.forget_pod(key)
                self.volume_binder.forget_pod_volumes(key)
                fw.run_unreserve(st, wp.pod, wp.node_name)
                reason = wp.rejected or "permit timeout"
                self._fail(
                    wp.pod, self.queue.scheduling_cycle, res,
                    (f"Permit:{reason}",),
                )
                self._cycle_states.pop(key, None)
            elif wp.allowed:
                fw.waiting.remove(key)
                self._bind_pod(wp.pod, wp.node_name, st, res)

    def _nominated_pods(self, exclude) -> List[Tuple[Pod, str]]:
        """(pod, node) for every nominated pod not in the current batch and
        whose node still exists."""
        out: List[Tuple[Pod, str]] = []
        for node_name, pods in self.queue.nominated.items():
            if self.cache.node(node_name) is None:
                continue
            for p in pods:
                if p.key() not in exclude:
                    out.append((p, node_name))
        return out

    def _run_preemption(self, batch, failed_idx, rmat, node_order, res) -> None:
        from kubernetes_tpu.preemption import preempt

        nodes = self.cache.nodes()
        node_pods_of = {nd.name: self.cache.pods_on(nd.name) for nd in nodes}
        pdbs = list(self.pdb_lister())
        order = sorted(failed_idx, key=lambda i: -batch[i].priority)
        done = 0
        for i in order:
            if done >= self.max_preemptions_per_cycle:
                break
            pod = batch[i]
            reason_bits = {
                name: int(rmat[i, r])
                for r, name in enumerate(node_order)
                if name
            }
            self.metrics.preemption_attempts.inc()
            result = preempt(
                pod, nodes, node_pods_of, reason_bits, pdbs,
                nominated_pods_of=dict(self.queue.nominated.items()),
                vol_state=self.cache.packer.resolve_volumes,
                extenders=[e for e in self.extenders if e.supports_preemption()],
                enable_non_preempting=self.enable_non_preempting,
            )
            if result is None:
                continue
            self.metrics.preemption_victims.inc(len(result.victims))
            now = self.clock()
            for v in result.victims:
                v.deletion_timestamp = now
                self.event_sink("Preempted", v, f"by {pod.key()}")
                self.obs.journeys.note_evicted(v.key(), pod.key())
                if self.victim_deleter is not None:
                    # deletion goes through the hub; the victim stays in the
                    # cache as terminating until the watch delete arrives
                    self.victim_deleter(v)
                else:
                    self.cache.remove_pod(v.key())
                # either way, later preemptors in this cycle must not
                # re-select (and re-delete) the same victims
                node_pods_of[result.node_name] = [
                    p
                    for p in node_pods_of[result.node_name]
                    if p.key() != v.key()
                ]
            # clear lower-priority nominations on the chosen node
            # (scheduler.go:330 getLowerPriorityNominatedPods)
            for p in result.clear_nominations:
                p.nominated_node_name = ""
                self.queue.nominated.delete(p)
            pod.nominated_node_name = result.node_name
            self.queue.nominated.add(pod, result.node_name)
            res.preempted += len(result.victims)
            res.nominations[pod.key()] = result.node_name
            done += 1
        if res.preempted and self.victim_deleter is None:
            # the victims' delete "events" happened inline (grace 0); the
            # reference's watch delete -> MoveAllToActiveQueue wakeup must
            # happen here too or the nominated preemptor sits in
            # unschedulableQ until the 60 s leftover flush
            self.queue.move_all_to_active()

    def _run_preemption_cascade(self, batch, failed_idx, rmat, node_order,
                                res) -> None:
        """In-batch preemption cascade (scenario packs; docs/scenarios.md):
        victim SELECTION runs the exact per-node machinery from
        preemption.py — shared state across preemptors, so earlier
        evictions are visible to later ones — and then victims AND
        displaced pods re-enter one dense solve in THIS cycle
        (:meth:`_cascade_solve`) instead of the stock path's per-pod
        nominate-and-wait loop. Single-pod batches select bit-identical
        victim sets to :meth:`_run_preemption` by construction (pinned
        by the seeded parity test in tests/test_scenarios.py)."""
        import dataclasses as _dc

        from kubernetes_tpu.scenarios.cascade import select_cascade

        nodes = self.cache.nodes()
        node_pods_of = {nd.name: self.cache.pods_on(nd.name)
                        for nd in nodes}
        pdbs = list(self.pdb_lister())
        order = sorted(failed_idx, key=lambda i: -batch[i].priority)
        preemptors = [(batch[i], {
            name: int(rmat[i, r])
            for r, name in enumerate(node_order) if name
        }) for i in order]
        sel = select_cascade(
            preemptors, nodes, node_pods_of, pdbs,
            nominated_pods_of=dict(self.queue.nominated.items()),
            vol_state=self.cache.packer.resolve_volumes,
            extenders=[e for e in self.extenders
                       if e.supports_preemption()],
            enable_non_preempting=self.enable_non_preempting,
            max_preemptions=self.max_preemptions_per_cycle,
            # same per-processed-pod accounting as the stock loop
            on_attempt=self.metrics.preemption_attempts.inc,
        )
        if not sel.chosen:
            return
        now = self.clock()
        if sel.victims:
            self.metrics.preemption_victims.inc(len(sel.victims))
            self.metrics.scenario_cascade_victims.inc(len(sel.victims))
        # preemptors that actually RE-SOLVE this cycle (gang members
        # never do — binding one member solo would sidestep the
        # all-or-nothing rollback; they keep stock nominations)
        solve_keys = {batch[i].key() for i in order
                      if batch[i].key() in sel.chosen
                      and not batch[i].pod_group}
        displaced = []
        requeue_only = []
        for v in sel.victims:
            v.deletion_timestamp = now
            self.event_sink(
                "Preempted", v, f"by {sel.victim_of[v.key()]} (cascade)")
            self.obs.journeys.note_evicted(
                v.key(), sel.victim_of[v.key()])
            if self.victim_deleter is not None:
                # deletion goes through the hub; the victim holds its
                # capacity as terminating until the watch delete lands,
                # so it CANNOT re-enter this cycle's solve — the
                # preemptors keep the stock nomination semantics below
                self.victim_deleter(v)
            else:
                self.cache.remove_pod(v.key())
                if not self.responsible_for(v):
                    continue
                pending = _dc.replace(v, node_name="",
                                      deletion_timestamp=0.0)
                if sel.victim_of[v.key()] in solve_keys:
                    displaced.append(pending)
                else:
                    # the evacuated capacity is PROMISED to a
                    # nominated-only preemptor — re-solving this victim
                    # now could retake it (the cascade solve has no
                    # pass-A phantom occupancy); requeue instead, like
                    # the stock path's victims-then-retry flow
                    requeue_only.append(pending)
        for p in sel.clear_nominations:
            p.nominated_node_name = ""
            self.queue.nominated.delete(p)
        res.preempted += len(sel.victims)
        # the cascade re-solve: preemptors first (priority order is the
        # queue comparator inside the solve anyway), displaced victims
        # riding the same dense batch, bounded by the config budget.
        # GANG preemptors are excluded: binding one member through the
        # cascade would sidestep the all-or-nothing rollback and could
        # leave a partially-bound gang — they keep the stock nomination
        # semantics (victims evicted now, the whole gang re-solves next
        # cycle under the gang check). Displaced gang members may still
        # re-place: their gang-mates remain bound, so migration keeps
        # the group whole (the stock path would just kill them).
        resolve_pods = [batch[i] for i in order
                        if batch[i].key() in solve_keys]
        budget = max(self.scenario.cascade_max_pods, 1)
        overflow = (resolve_pods + displaced)[budget:]
        resolve_pods = (resolve_pods + displaced)[:budget]
        if self.victim_deleter is not None or not sel.victims:
            # nothing newly USABLE was freed: in hub-deleter mode the
            # victims hold their capacity as terminating, and a
            # victimless win (pick_one_node's no-victims fast path)
            # evacuated nothing — the re-solve could not place anything
            # the main solve didn't, so skip straight to the
            # nominations instead of paying a second full ladder solve
            placed, q2 = set(), None
        else:
            placed, q2 = self._cascade_solve(resolve_pods, res)
        for p in requeue_only:
            self._fail(p, self.queue.scheduling_cycle, res,
                       ("CascadeUnplaced",))
        for p in overflow:
            # a displaced pod the budget truncated was already evicted
            # from its node — it MUST requeue through the standard
            # error path, not silently vanish (preemptors in the
            # overflow keep their existing failure row + nomination)
            if p.key() not in res.failure_reasons:
                self._fail(p, self.queue.scheduling_cycle, res,
                           ("CascadeUnplaced",))
        for p in displaced:
            if p.key() in placed:
                self.metrics.scenario_displaced_replaced.inc()
        if q2:
            # the cascade changed the cluster: re-publish the
            # CLUSTER-STATE quality fields from the cascade solve's
            # final usage (nodes_used/headroom/fragmentation/free);
            # batch-relative fields (placed, nodes_used_batch,
            # priority_headroom) keep describing the main solve
            for k in ("nodes_used", "headroom", "fragmentation",
                      "free_cpu_frac"):
                res.scenario_quality[k] = q2[k]
            self._publish_scenario_quality(res.scenario_quality)
        # preemptors the re-solve could not place (victimless win,
        # hub-delete mode, or a cascade interaction took their spot)
        # keep the stock semantics: nominated onto the chosen node,
        # retried next cycle
        for i in order:
            key = batch[i].key()
            if key in sel.chosen and key not in placed:
                batch[i].nominated_node_name = sel.chosen[key]
                self.queue.nominated.add(batch[i], sel.chosen[key])
                res.nominations[key] = sel.chosen[key]
        if sel.victims and self.victim_deleter is None:
            # inline victim deletes (grace 0): the watch-delete wakeup
            # the stock path performs must happen here too
            self.queue.move_all_to_active()

    def _quality_dispatch(self, assigned, usage, dp, dn):
        """THE scenario-quality step, first half: dispatch the device
        reduction over a final assignment (a host answer sliced to the
        batch is padded back to the pod bucket as unplaced rows), so it
        executes while the host binds. Returns the ~28 B device vector,
        or None when no pack reports quality."""
        from kubernetes_tpu.ops.scenario_cost import quality_reduce

        if self.scenario_pack is None or not self.scenario.quality:
            return None
        n = dp.valid.shape[0]
        if isinstance(assigned, np.ndarray) and assigned.shape[0] < n:
            pad = np.full((n,), -1, np.int32)
            pad[: assigned.shape[0]] = assigned
            assigned = pad
        return quality_reduce(jnp.asarray(assigned, jnp.int32),
                              usage.requested, dp, dn)

    def _quality_read(self, q_dev, res=None, batch=(), assigned=None,
                      nt=None) -> Dict[str, float]:
        """Second half: read the vector back and decode it. Given the
        cycle's ``res``, the pack's host-side scores over the final
        assignment join it and the cycle publishes it."""
        from kubernetes_tpu.scenarios.quality import decode_quality

        with self.obs.span("pipeline:readback@quality"):
            quality = decode_quality(
                self.obs.jax.readback("scenario-quality", q_dev))
        if res is not None:
            quality.update(
                self.scenario_pack.quality_host(batch, assigned, nt))
            res.scenario_quality = quality
            self._publish_scenario_quality(quality)
        return quality

    def _publish_scenario_quality(self, quality) -> None:
        """Fan one cycle's quality dict out to the flight record and
        the gauge family — scores that stopped being reported (a
        gangless cycle after a gang cycle) drop to zero instead of
        going stale, the explain-gauge freshness rule."""
        self.obs.note_scenario(quality)
        for k in self._scenario_scores_seen - set(quality):
            self.metrics.scenario_quality.set(0.0, score=k)
        for k, v in quality.items():
            self.metrics.scenario_quality.set(float(v), score=k)
            self._scenario_scores_seen.add(k)

    def maybe_repack(self) -> int:
        """Steady-state consolidation re-pack
        (``scenario.repackInterval``): every interval, drain the pods
        off the least-utilized FULLY-emptiable nodes — nodes holding
        only this scheduler's bound, non-assumed, non-terminating pods,
        whose load the rest of the occupied cluster can absorb — and
        requeue them, so the next cycles' consolidation objective packs
        them tight again. Admission-time consolidation alone ratchets:
        sustained churn strands capacity on nodes that emptied BELOW
        the pack's fill order after their pods bound, and nothing ever
        revisits them. Bounded per sweep by ``scenario.repackMaxPods``;
        returns the number of pods drained (0 off-cadence / packless).

        Callers: the serving maintenance hook (between cycles, under
        the loop lock) and :meth:`idle_tick` for the legacy loop."""
        import dataclasses as _dc

        interval = self.scenario.repack_interval_s
        if interval <= 0 or self.scenario_pack is None:
            return 0
        now = self.clock()
        if self._last_repack_at is None:
            # cadence starts at first observation: a full interval of
            # real churn elapses before the first drain
            self._last_repack_at = now
            return 0
        if now - self._last_repack_at < interval:
            return 0
        self._last_repack_at = now
        free: Dict[str, Tuple[float, int]] = {}
        occupied = []
        for nd in self.cache.nodes():
            pods = self.cache.pods_on(nd.name)
            used = sum(p.requests.cpu_milli for p in pods)
            free[nd.name] = (nd.allocatable.cpu_milli - used,
                             nd.allocatable.pods - len(pods))
            if pods:
                occupied.append(
                    (used / max(nd.allocatable.cpu_milli, 1.0),
                     nd.name, pods))
        if len(occupied) < 2:
            return 0  # nothing to consolidate INTO
        occupied.sort(key=lambda t: (t[0], t[1]))
        budget = max(self.scenario.repack_max_pods, 1)
        emptied: set = set()
        drained = 0
        for _util, name, pods in occupied:
            movable = [
                p for p in pods
                if self.responsible_for(p)
                and not self.cache.is_assumed(p.key())
                and not p.deletion_timestamp
            ]
            if len(movable) != len(pods):
                continue  # foreign / in-flight pods pin the node
            if not movable or len(movable) > budget - drained:
                continue
            need_cpu = sum(p.requests.cpu_milli for p in movable)
            # feasibility heuristic only — the SOLVER places; this just
            # avoids draining pods the remaining occupied nodes cannot
            # possibly hold (they would bounce back, or worse, land on
            # the node just emptied)
            absorb_cpu = absorb_slots = 0
            for _u2, n2, pods2 in occupied:
                if n2 == name or n2 in emptied:
                    continue
                c, s = free[n2]
                absorb_cpu += max(c, 0)
                absorb_slots += max(s, 0)
            if need_cpu > absorb_cpu or len(movable) > absorb_slots:
                continue
            for p in movable:
                if self.repack_evictor is not None:
                    # hub integration: post the unbind and let the
                    # watch stream converge local state
                    self.repack_evictor(p)
                else:
                    self.cache.remove_pod(p.key())
                    self.queue.add_if_not_present(_dc.replace(
                        p, node_name="", deletion_timestamp=0.0))
            emptied.add(name)
            drained += len(movable)
            if drained >= budget:
                break
        if drained:
            self.metrics.scenario_repacks.inc()
            self.metrics.scenario_repack_drained.inc(drained)
            self.queue.move_all_to_active()
            klog.V(2).info(
                "steady-state re-pack: drained %d pods off %d nodes",
                drained, len(emptied))
        return drained

    def _cascade_pad(self, n: int) -> int:
        """Pod-bucket for a cascade re-solve. With warmup on, snap UP
        to a bucket the warm sweep covered (the smallest explicit
        bucket that fits, or at least ``min_bucket`` for the geometric
        default sweep) so a cascade never pays a hot-path compile —
        cascades bigger than every warmed bucket keep their natural
        bucket (a one-time compile, logged by the retrace telemetry)."""
        pad = bucket_size(max(n, 1))
        wu = self.warmup_config
        if not wu.enabled:
            return pad
        explicit = sorted(b for b in wu.pod_buckets if b >= pad)
        if explicit:
            return explicit[0]
        if not wu.pod_buckets:
            return max(pad, bucket_size(max(min(wu.min_bucket,
                                                self.max_batch), 1)))
        return pad

    def _cascade_solve(self, pods_list, res: CycleResult):
        """One dense solve over the cascade's preemptors + displaced
        pods against the evacuated cluster — a fresh snapshot (the
        victims' rows are dirty, so the resident path patches them with
        the usual delta scatter), the full degradation ladder with
        fused validation, the pack's cost term, and the standard
        admission tail per placed pod. Returns ``(placed_keys,
        quality_or_None)`` — the quality vector re-reduced from the
        cascade's FINAL usage, so cascade cycles report the true
        post-cascade cluster state."""
        from kubernetes_tpu.ops.arrays import volumes_to_device

        placed: set = set()
        if not pods_list:
            return placed, None
        pk = self.cache.packer
        for p in pods_list:
            pk.intern_pod(p)
        if self.device_resident_snapshot:
            nt, dn, _ = self._device_snapshot_recovering()
        else:
            nt = self.cache.snapshot()
            dn = None
        node_order = self.cache.node_order()
        pt = pk.pack_pods(pods_list)
        key = self._solve_key(
            self.solver if self.solver != "exact" else "batch",
            solver_gates(nt, pt))
        if dn is None:
            if self._mesh_live:
                from kubernetes_tpu.parallel.mesh import place_node_table

                dn = place_node_table(nt, self.mesh)
            else:
                dn = nodes_to_device(nt)
        dn = self._with_owner_columns(dn, pt)
        dp = self._place(pods_to_device(
            pt, pad_to=self._cascade_pad(len(pods_list))))
        ds = self._place(selectors_to_device(pk.pack_selector_tables()))
        dt = self._place(topology_to_device(pk.pack_topology_tables())
                         if _has_topo(pk.u) else None)
        dv = sv = None
        if any(p.volumes for p in pods_list):
            dv = self._place(volumes_to_device(
                pk.pack_volume_tables(pods_list)))
            sv = _static_vol_pass(dp, dn, ds, dv)
        extra_score = None
        if self.scenario_pack is not None:
            extra_score = self.scenario_pack.cost(
                pods_list, nt, node_order, dp, dn)
        ladder = self._solve_ladder(
            key, pods_list, dp, dn, ds, dt, dv, sv, None, None,
            extra_score, res, record=True)
        cycle = self.queue.scheduling_cycle
        if ladder is None:
            for p in pods_list:
                if p.key() not in res.failure_reasons:
                    self._fail(p, cycle, res, ("SolverUnavailable",))
            return placed, None
        assigned, usage2, _rounds, _tier = ladder
        assigned = assigned[: len(pods_list)]
        q_dev = self._quality_dispatch(assigned, usage2, dp, dn)
        q2 = self._quality_read(q_dev) if q_dev is not None else None
        for i, p in enumerate(pods_list):
            t = int(assigned[i])
            if t < 0:
                # displaced pods requeue through the standard error
                # path; an unplaced preemptor keeps the failure row the
                # main bind loop already recorded (no double count) and
                # gets its nomination from the caller
                if p.key() not in res.failure_reasons:
                    self._fail(p, cycle, res, ("CascadeUnplaced",))
                continue
            # a preemptor was already _fail'd by the main bind loop —
            # its stale queue entry and failure row are superseded by
            # the cascade bind
            self.queue.delete(p.key())
            had_row = p.key() in res.failure_reasons
            before_sched = res.scheduled
            before_unsched = res.unschedulable
            before_wait = res.waiting
            self._admit_pod(p, node_order[t], cycle, res)
            if res.scheduled > before_sched or res.waiting > before_wait:
                # bound — or PARKED by a Permit plugin (assumed in
                # cache, capacity held): either way the pod left the
                # unschedulable state and must NOT also be nominated
                # (a nominated + assumed pod would double-count its
                # capacity in next cycle's pass A)
                placed.add(p.key())
                if had_row:
                    res.unschedulable -= 1
                    res.failure_reasons.pop(p.key(), None)
                    res.fit_errors.pop(p.key(), None)
                    self.why_pending.pop(p.key(), None)
            elif had_row and res.unschedulable > before_unsched:
                # the admission tail _fail'd a pod the main bind loop
                # already counted — one pod, one unschedulable
                res.unschedulable -= 1
        return placed, q2

    def _fail(self, pod: Pod, cycle: int, res: CycleResult, reasons,
              message: str = None) -> None:
        res.unschedulable += 1
        res.failure_reasons[pod.key()] = tuple(reasons)
        if message is not None:
            res.fit_errors[pod.key()] = message
        # journey attempt row (tier/scope backfilled at _finish_cycle);
        # the queue re-add below then closes the solve phase
        self.obs.journeys.note_attempt_failed(
            pod.key(), cycle, reasons[0] if reasons else "")
        self._cycle_states.pop(pod.key(), None)  # cycle over for this pod
        self.queue.record_failure(pod)
        self.queue.add_unschedulable_if_not_present(pod, cycle)
        # events carry the FitError-shaped per-reason node counts when the
        # failure came from the filter pass (FitError.Error parity,
        # generic_scheduler.go:105-122); plugin/gang failures keep their
        # status text
        self.event_sink("FailedScheduling", pod,
                        message if message is not None else ",".join(reasons))

    def warmup(self, sample_pods=(), node_count: Optional[int] = None) -> int:
        """AOT warmup (config.WarmupConfig): compile the solver — and the
        standalone filter pass — at every bucketed pod-batch shape the
        driver can hit, so first-pod latency never pays an XLA compile
        and queue-length churn across bucket boundaries causes no
        retraces (`scheduler_jax_retrace_total` stays flat).

        ``sample_pods`` (optional but recommended) seeds the universes
        and derives the host-side solver gates exactly as real cycles
        will; without a sample the clean-batch gate set is warmed. The
        node axis uses the cache's current cluster (its bucket is fixed
        per cluster) or ``node_count`` before any node has synced.
        Signatures are pre-registered with the JAX telemetry, so the
        first real cycle classifies as a cache hit, not a compile.
        Returns the number of bucketed shapes compiled."""
        wu = self.warmup_config
        pk = self.cache.packer
        sample = list(sample_pods)
        for p in sample:
            pk.intern_pod(p)
        self._mesh_live = self.mesh is not None
        if self.cache.node_count():
            if self.device_resident_snapshot:
                nt, dn, _ = self._device_snapshot_recovering()
                if dn is None:  # device cooling off: warm on host tables
                    dn = nodes_to_device(nt)
                    self._mesh_live = False
            else:
                nt = self.cache.snapshot()
                if self._mesh_live:
                    from kubernetes_tpu.parallel.mesh import (
                        place_node_table,
                    )

                    dn = place_node_table(nt, self.mesh)
                else:
                    dn = nodes_to_device(nt)
        elif node_count:
            # no cluster yet: widths-complete zero-row table, padded to
            # the caller's expected node bucket (and at least the mesh
            # size, so the warmed shapes match the sharded cycle's)
            nt = pk.pack_nodes([])
            pad = bucket_size(max(node_count, 1))
            if self._mesh_live:
                from kubernetes_tpu.parallel.mesh import place_node_table

                dn = place_node_table(nt, self.mesh, pad_to=pad)
            else:
                dn = nodes_to_device(nt, pad_to=pad)
        else:
            # no cluster AND no expected size: warming now would compile
            # (and pre-register) shapes with an empty-cluster node bucket
            # no real cycle can match — the first solve would then pay a
            # hot-path compile AND read as a retrace. Callers defer until
            # the informer has synced (cli.run warms lazily).
            klog.warning("warmup skipped: no nodes synced and no "
                         "node_count given — call again after the first "
                         "node sync")
            return 0
        ds = self._place(selectors_to_device(pk.pack_selector_tables()))
        dt = self._place(topology_to_device(pk.pack_topology_tables())
                         if _has_topo(pk.u) else None)
        key = self._solve_key(
            self.solver if self.solver != "exact" else "batch",
            solver_gates(nt, pk.pack_pods(sample)))
        buckets = tuple(wu.pod_buckets)
        if not buckets:
            # geometric x2 steps up to bucket_size(max_batch) — the
            # largest shape ANY cycle can present. Pipelined cycles pad
            # chunks to bucket_size(pipeline_chunk) (a power of two, so
            # it's in this sweep), but feature batches forced monolithic
            # (extenders, gang, nominated pods...) still pad the whole
            # batch, so capping at the chunk bucket would leave their
            # first cycle paying a hot-path compile
            top = bucket_size(max(self.max_batch, 1))
            out = []
            b = bucket_size(max(min(wu.min_bucket, top), 1))
            while b <= top:
                out.append(b)
                b *= 2
            buckets = tuple(out)
        has_vol_sample = any(p.volumes for p in sample)
        compiled = 0
        for P in buckets:
            try:
                if self.fault_injector is not None:
                    # device-loss chaos seam for the compile below
                    self.fault_injector.device_hook("warmup:compile")
                compiled += self._warm_bucket(
                    P, pk, sample, nt, dn, ds, dt, key, has_vol_sample, wu,
                    anchor=(compiled == 0))
            except Exception as e:
                # a lost/OOMed device during an AOT compile (injected
                # OR a real XLA runtime error — warmup runs inside the
                # takeover reconciliation, where crashing the new
                # leader is the worst outcome): abort cleanly with what
                # compiled so far. The hot path degrades via
                # _device_snapshot_recovering / the ladder, and the
                # next re-arm (reconcile, lazy-warm gate) retries.
                self.metrics.recovery_device_resets.inc()
                self.obs.note_device_reset()
                ml = self.obs.memledger
                if ml.enabled:
                    # ranked forensic record BEFORE the drops deregister
                    # the residents (parks on _pending_oom — warmup runs
                    # between cycles)
                    oomrec = ml.record_oom(
                        "warmup:compile", error=str(e),
                        shapes=f"P{P}xN{int(dn.valid.shape[0])}",
                        cycle=self.queue.scheduling_cycle)
                    self.obs.note_oom_forensic(ml.oom_flag(oomrec))
                self.cache.drop_device_snapshot()
                # the drop above only kills the resident node table; the
                # score cache and warm-potential carry reference device
                # state and must not survive it into the cooloff cycles
                self._drop_incremental("device-loss")
                klog.warning("warmup aborted at bucket %d: %s", P, e)
                return compiled
        if self.device_resident_snapshot and self.mesh is None:
            # pre-compile the PR-5 delta scatter at the dirty-row
            # buckets steady churn presents — left to first sight it
            # costs a ~0.5s XLA compile on the hot path, exactly the
            # p99 spike the warmup contract exists to kill (mesh mode
            # keeps first-sight: the replicated-sub/sharded-resident
            # layout is built per mesh and cheap to compile there)
            try:
                self._warm_delta_scatter(dn)
            except Exception as e:
                klog.warning("delta-scatter warmup aborted: %s", e)
        if self.incremental.enabled:
            # pre-compile the restricted-solve signatures (candidate
            # pick + gather + (P, C) solve + fused validate + global
            # mapping) so incremental cycles stay zero-retrace: the
            # serving loop's micro-batches flush at warmed pod buckets,
            # and the candidate bucket C is one static shape
            try:
                compiled += self._warm_incremental(buckets, pk, sample,
                                                   nt, dn, ds, key)
            except Exception as e:
                klog.warning("incremental warmup aborted: %s", e)
        if wu.host_fallback and self.mesh is not None and self._mesh_live:
            # ALSO warm the single-device host-mode signatures — the
            # shapes a device-loss cooloff cycle presents (resident
            # table dropped, host-mirror pack, _mesh_live False). The
            # composed serving mode turns this on so a shard lost
            # mid-churn degrades to host-mode WITHOUT a hot-path
            # compile: the cooloff cycles hit the jit cache and the
            # retrace counter stays flat through the whole
            # loss -> cooloff -> heal-sharded arc.
            self._mesh_live = False
            try:
                host_n = nt.n if nt.n else (node_count or 1)
                dn_h = nodes_to_device(nt,
                                       pad_to=bucket_size(max(host_n, 1)))
                ds_h = selectors_to_device(pk.pack_selector_tables())
                dt_h = (topology_to_device(pk.pack_topology_tables())
                        if _has_topo(pk.u) else None)
                key_h = key._replace(mesh_live=False)
                for P in buckets:
                    try:
                        if self.fault_injector is not None:
                            self.fault_injector.device_hook(
                                "warmup:compile")
                        compiled += self._warm_bucket(
                            P, pk, sample, nt, dn_h, ds_h, dt_h, key_h,
                            has_vol_sample, wu)
                    except Exception as e:
                        self.metrics.recovery_device_resets.inc()
                        self.obs.note_device_reset()
                        ml = self.obs.memledger
                        if ml.enabled:
                            oomrec = ml.record_oom(
                                "warmup:compile", error=str(e),
                                shapes=f"P{P}(host)",
                                cycle=self.queue.scheduling_cycle)
                            self.obs.note_oom_forensic(ml.oom_flag(oomrec))
                        self.cache.drop_device_snapshot()
                        # same contract as the sharded sweep above: the
                        # score/potential carries die with the table
                        self._drop_incremental("device-loss")
                        klog.warning("host-fallback warmup aborted at "
                                     "bucket %d: %s", P, e)
                        return compiled
                if self.incremental.enabled:
                    # the restricted signatures in host mode too — the
                    # heal boundary's first post-cooloff cycles must not
                    # pay a hot-path compile either
                    try:
                        compiled += self._warm_incremental(
                            buckets, pk, sample, nt, dn_h, ds_h, key_h)
                    except Exception as e:
                        klog.warning("incremental host-fallback warmup "
                                     "aborted: %s", e)
            finally:
                self._mesh_live = self.mesh is not None
        klog.V(2).info("warmup: compiled %d bucketed solve shapes "
                       "(nodes bucket %d)", compiled, dn.valid.shape[0])
        return compiled

    @staticmethod
    def _warm_owner_pad(P, pk, sample) -> int:
        """The owner-column width a real batch of bucket ``P`` will
        present: that of the sample's first P pods, where a sample
        shorter than P whose every pod spreads by an owner set of its
        own (density.go's latency pods, each its own
        ReplicationController) stands for P pods bringing P sets."""
        n = len(pk.owner_cols_of(sample[:P]))
        return owner_width(P if 0 < n == len(sample) < P else n)

    def _warm_bucket(self, P, pk, sample, nt, dn, ds, dt, key,
                     has_vol_sample, wu, anchor: bool = False) -> int:
        """Compile one bucketed solve shape (the body of the warmup
        sweep); returns 1. Split out so the sweep's device-loss
        handling wraps the WHOLE per-bucket compile — injected chaos
        AND real XLA runtime errors abort the sweep identically.

        ``anchor=True`` (the sweep's first bucket) additionally feeds
        the perf ledger's model side (obs/ledger.py): the compiled
        signature's XLA ``cost_analysis`` flops and ONE timed warm
        replay as the per-round rate anchor every live prediction
        scales from."""
        import jax

        pt = pk.pack_pods(sample[:P], owner_pad=self._warm_owner_pad(
            P, pk, sample))
        dn = self._with_owner_columns(dn, pt)
        dp = self._place(pods_to_device(pt, pad_to=P))
        dv = sv = None
        if has_vol_sample:
            # a volume-bearing sample warms the volume-bearing solve
            # signature real cycles will record (dv in the digest);
            # row-table shapes scale with the batch's volume rows, so
            # coverage is exact only when the sample is representative
            from kubernetes_tpu.ops.arrays import volumes_to_device

            dv = self._place(volumes_to_device(pk.pack_volume_tables(
                sample[:P])))
            sv = _static_vol_pass(dp, dn, ds, dv)
        extra_score = None
        if self.scenario_pack is not None:
            # the pack's cost kernel builds the warm extra_score through
            # the SAME jitted path real cycles use (dtype + sharding
            # included) — a zeros placeholder would warm a different
            # compiled program and the first scenario cycle would
            # recompile on the hot path
            extra_score = self.scenario_pack.cost(
                sample[:P], nt, self.cache.node_order(), dp, dn)
        # the signature's extra-score flag follows what the WARM cost
        # call actually produced (a pack whose cost() returns None must
        # not pre-register a signature no real cycle presents)
        ops = dict(topo=dt, vol=dv, static_vol=sv, extra_score=extra_score)
        a, wu_usage, _ = self._solve(key, key.solver, dp, dn, ds,
                                     warmup=True, **ops)
        if key.solver != "greedy":
            if anchor and self.obs.ledger.enabled:
                self._anchor_cost_model(key, dp, dn, ds, a, ops)
            if self.obs.memledger.preflight_on:
                # EVERY bucket feeds the capacity preflight's per-shape
                # peak table (the anchor above only samples the first) —
                # the preflight can only split down to shapes it has a
                # measured budget for
                self._capture_bucket_memory(key, key.solver, dp, dn, ds,
                                            ops)
        self._warm_validate(a, wu_usage, dp, dn)
        q_dev = self._quality_dispatch(np.full((P,), -1, np.int32),
                                       wu_usage, dp, dn)
        if q_dev is not None:
            # the per-cycle quality reduction rides every scenario
            # cycle's readback — compile its program per bucket here
            # too, with the host-built assignment vector real cycles
            # upload (same placement, same signature)
            jax.block_until_ready(q_dev)
        jax.block_until_ready(a)
        fr_mask = None
        if wu.include_filter:
            fr = _filter_pass(dp, dn, ds, dt, dv, sv,
                              self.pred_mask)
            jax.block_until_ready(fr.mask)
            fr_mask = fr.mask
        if wu.nominated_variant and self.enable_preemption:
            # the nominated-pods variant (podFitsOnNode pass A): the
            # cycle after a preemption feeds a (P, N) feasibility mask
            # and ``extra_mask is None`` flips in the solve digest — a
            # DIFFERENT compiled program. Warm it here or the first
            # post-preemption cycle pays the compile on the hot path
            # (and the stall can blow the lease-freshness fence, turning
            # one preemption into fenced binds). The mask comes from the
            # same filter pass the real nominated path runs, so dtype,
            # shape, and sharding match the live signature exactly.
            if fr_mask is None:
                fr_mask = _filter_pass(dp, dn, ds, dt, dv, sv,
                                       self.pred_mask).mask
            jax.block_until_ready(self._solve(
                key, key.solver, dp, dn, ds, extra_mask=fr_mask,
                warmup=True, **ops)[0])
        self.metrics.warmup_compiles.inc()
        return 1

    def _warm_validate(self, a, usage, dp, dn) -> None:
        """Compile the fused validator at this shape: it rides every
        served cycle's readback, so left to first sight the first real
        cycle would pay its compile on the hot path."""
        import jax

        from kubernetes_tpu.ops.assign import device_validate

        rc = self.robustness
        if rc.validate_results and not rc.host_validate:
            dv_out = device_validate(a, usage, dp, dn, self.pred_mask)
            if dv_out is not None:
                jax.block_until_ready(dv_out[0])

    def _anchor_cost_model(self, key, dp, dn, ds, warm_a, ops) -> None:
        """The perf ledger's model-side warmup capture (obs/ledger.py):
        (a) the compiled solve signature's XLA ``cost_analysis`` flops /
        bytes-accessed (best-effort AOT — some backends decline), and
        (b) one TIMED warm replay of the just-compiled solve as the
        per-round rate anchor. The replay solves the real warmup
        sample over the full (P, N) plane and can take more than one
        assignment round, so the anchor records the EXECUTED round
        count (one warmup-only scalar readback) — crediting a
        multi-round wall to rounds=1 would inflate the per-round rate
        and flatter every live prediction. Failures are swallowed: the
        ledger self-anchors on the first live cycle instead, and
        warmup must never die for its accountant."""
        import time as _time  # perf_counter only (graftlint R4)

        import jax

        from kubernetes_tpu.ops.assign import solve_cost_analysis

        ledger = self.obs.ledger
        P_pad = int(dp.valid.shape[0])
        N_pad = int(dn.valid.shape[0])
        mesh = int(self.mesh.devices.size) if self._mesh_live else 0
        try:
            ca = solve_cost_analysis(dp, dn, ds, key.weights,
                                     **key.batch_kwargs(key.solver), **ops)
            if ca is not None:
                ledger.model.record_signature(
                    P_pad, N_pad, ca["flops"], ca["bytes_accessed"])
            jax.block_until_ready(warm_a)  # the compile, not the replay
            t0 = _time.perf_counter()
            out = self._solve(key, key.solver, dp, dn, ds, record=False,
                              warmup=True, **ops)
            jax.block_until_ready(out[0])
            elapsed = _time.perf_counter() - t0
            # batch_assign's 3rd output is the executed round count —
            # the replay solves real sample pods and can take >1 round,
            # and an R-round wall credited to rounds=1 would inflate
            # the per-round rate R× (warmup-only scalar, declared site)
            rounds = int(self.obs.jax.readback("ledger-anchor", out[2]))
            ledger.model.record_anchor(
                "full", P_pad, N_pad, mesh,
                elapsed, rounds=max(rounds, 1))
        except Exception as e:
            klog.V(2).info("ledger cost-model capture skipped: %s", e)

    def _capture_bucket_memory(self, key, tier, dp, dn, ds, ops) -> None:
        """The memory ledger's per-bucket peak capture (obs/memledger):
        AOT-lower the solve at THIS warmed (P, N) shape and read the
        compiled program's ``memory_analysis`` — argument/output/temp
        bytes — into the preflight's per-shape peak table. Rides the
        warmup sweep only (one extra AOT compile per bucket, zero
        hot-path cost); failures are swallowed like the cost-model
        capture above — the preflight simply reports those shapes
        ``unwarmed`` and never splits TO them."""
        from kubernetes_tpu.ops.assign import solve_memory_analysis

        ml = self.obs.memledger
        try:
            ma = solve_memory_analysis(dp, dn, ds, key.weights,
                                       **key.batch_kwargs(tier), **ops)
            if ma is not None:
                ml.record_bucket_memory(
                    int(dp.valid.shape[0]), int(dn.valid.shape[0]),
                    int(self.mesh.devices.size) if self._mesh_live else 0,
                    ma)
        except Exception as e:
            klog.V(2).info("memledger bucket capture skipped: %s", e)

    def _warm_delta_scatter(self, dn) -> int:
        """Compile the donated delta-scatter programs for the small
        dirty-row buckets (the same geometric family the cache's delta
        path buckets to). The resident template is a throwaway COPY of
        the warm table — the scatter donates its buffers, and donating
        the cache's real resident arrays would invalidate them."""
        import jax

        from kubernetes_tpu.ops.arrays import (
            gather_node_rows,
            scatter_node_rows,
        )

        n_pad = dn.valid.shape[0]
        compiled = 0
        for dpb in (4, 8, 16, 32, 64):
            sub = gather_node_rows(dn, jnp.zeros((dpb,), jnp.int32))
            resident = jax.tree_util.tree_map(jnp.copy, dn)
            out = scatter_node_rows(resident, sub,
                                    np.full((dpb,), n_pad, np.int32))
            jax.block_until_ready(out.requested)
            compiled += 1
        return compiled

    def _warm_incremental(self, buckets, pk, sample, nt, dn, ds,
                          key) -> int:
        """Pre-compile the restricted-solve programs for every pod
        bucket that can take the incremental route: the (mesh-sharded)
        candidate pick — top-k over the cached plane, per-shard local
        pick + replicated merge when the mesh is live — the node-row
        gather, the (P, C) solve — cold AND (for the sinkhorn solver)
        warm-started AND (for a restricted_ok scenario pack) cost-fed —
        the fused validator, the global mapping, one delta-bucket
        summary patch, the group-quota hint split, and (primary mode)
        the partitioned cold selection. Signatures pre-register with
        the telemetry so the first incremental cycle classifies as a
        cache hit.

        With ``incremental.autoTune`` the sweep compiles a C LADDER —
        {C/2, C, 2C} snapped to legal sizes — and records it in
        ``_warmed_cbuckets``: the auto-tuner may only ever move between
        warmed rungs, which is what makes a tuner move retrace-free by
        construction. Each warmed (P, C) shape also feeds the memory
        ledger's preflight peak table, so the capacity preflight can
        split an over-budget dense solve DOWN to a restricted shape it
        has a measured budget for."""
        import jax

        from kubernetes_tpu.ops.arrays import (
            gather_candidates,
            gather_node_rows,
            map_restricted_assignment,
        )
        from kubernetes_tpu.ops.fused_score import (
            node_summary,
            partition_columns,
            patch_node_summary,
        )

        inc = self.incremental
        n_pad = dn.valid.shape[0]
        c0 = bucket_size(max(inc.candidate_bucket, 1))
        ladder = [c0]
        if inc.auto_tune:
            ladder = sorted({max(c0 // 2, 16), c0, c0 * 2})
        ladder = [c for c in ladder if c < n_pad]
        if not ladder:
            return 0
        ns = int(self.mesh.devices.size) if self._mesh_live else 1
        flags = self._summary_flags
        summary = node_summary(dn, **flags)
        zeros_dirty = jnp.zeros((n_pad,), bool)
        # summary patches at the delta buckets steady churn actually
        # presents (the scatter programs bucket geometrically exactly
        # like the PR-5 snapshot delta — an unwarmed bucket would
        # compile mid-churn and spike that cycle's latency)
        for dpb in (4, 8, 16, 32, 64):
            sub = gather_node_rows(dn, jnp.zeros((dpb,), jnp.int32))
            patched = patch_node_summary(
                node_summary(dn, **flags), node_summary(sub, **flags),
                np.full((dpb,), n_pad, np.int32))
            jax.block_until_ready(patched.rank)
        warm = key.warm_tol is not None
        pack = (self.scenario_pack
                if (self.scenario_pack is not None
                    and self.scenario_pack.restricted_ok) else None)
        node_order = self.cache.node_order()
        compiled = 0
        smallest_bucket = bucket_size(1)
        dps: Dict[int, object] = {}
        for C in ladder:
            self.obs.jax.record_call(
                "incremental", summary.rank,
                static=(C, n_pad, self._mesh_live, ns, True, None),
                warmup=True)
            cand, sub_dn = gather_candidates(
                summary, zeros_dirty, dn, C, num_shards=ns)
            if pack is not None:
                # the group-quota hint split is a DIFFERENT compiled
                # pick (two disjoint segment top-k's) — warm it with a
                # placeholder mask so the first hinted cycle hits cache
                hq = max(int(inc.group_quota_frac * C), 1)
                self.obs.jax.record_call(
                    "incremental", summary.rank,
                    static=(C, n_pad, self._mesh_live, ns, False, hq),
                    warmup=True)
                jax.block_until_ready(gather_candidates(
                    summary, zeros_dirty, dn, C,
                    hint_mask=jnp.zeros((n_pad,), bool),
                    num_shards=ns, hint_quota=hq)[0])
            part_warm = False
            if inc.primary:
                # partitioned cold selection: the block deal + one
                # block gather (block solves reuse the (P, C) cold
                # programs compiled below — identical shapes)
                B = self._cold_blocks(n_pad, C)
                if B >= 2:
                    part_warm = True
                    self.obs.jax.record_call(
                        "partition", summary.rank,
                        static=(B, C, n_pad, ns, self._mesh_live),
                        warmup=True)
                    blocks = partition_columns(summary, zeros_dirty, B,
                                               C, ns)
                    jax.block_until_ready(
                        gather_node_rows(dn, blocks[0]).requested)
            limit = inc.max_batch_frac * C
            for P in buckets:
                # warm P iff SOME eligible batch pads to it: the
                # runtime gate compares the RAW batch size
                # (<= maxBatchFrac*C) before padding, so the bucket
                # covering floor(limit) must be warmed even when the
                # bucket itself exceeds the limit
                smallest_in_bucket = (1 if P <= smallest_bucket
                                      else P // 2 + 1)
                over_limit = smallest_in_bucket > limit
                if over_limit and not part_warm:
                    continue  # no eligible batch can pad to this bucket
                # over-limit buckets are still reachable through the
                # PARTITIONED route: block frames solve the FULL batch
                # against a C-wide block (the maxBatchFrac gate is
                # restricted-only), so their cold (P, C) program must
                # compile here too — but only the cold variant;
                # partitioned never warm-starts or feeds extra_score
                if P not in dps:
                    pt_w = pk.pack_pods(sample[:P], owner_pad=(
                        self._warm_owner_pad(P, pk, sample)))
                    dps[P] = (self._place(pods_to_device(pt_w, pad_to=P)),
                              pt_w)
                dp, pt_w = dps[P]
                sub_w = sub_dn
                if pt_w.owner_match_mh.shape[1]:
                    # the frame carries the batch's owner columns,
                    # gathered at the candidates like every other row
                    sub_w = gather_node_rows(
                        self._with_owner_columns(dn, pt_w), cand)
                extra = None
                if pack is not None and not over_limit:
                    # the pack's cost on the gathered frame — same
                    # jitted kernel, dtype and sharding as real
                    # restricted cycles feed extra_score with
                    extra = pack.cost(sample[:P], nt, node_order, dp,
                                      sub_w)
                variants = [dict(sk_init=None, extra_score=None)]
                if warm and not over_limit:
                    # the warm-started program is a DIFFERENT signature
                    # (potential operands join the trace) — compile it
                    # too or the second incremental cycle retraces
                    zp = (jnp.zeros((P,), jnp.float32),
                          jnp.zeros((C,), jnp.float32))
                    variants.append(dict(sk_init=zp, extra_score=None))
                if extra is not None:
                    variants.append(dict(sk_init=None,
                                         extra_score=extra))
                    if warm:
                        zp = (jnp.zeros((P,), jnp.float32),
                              jnp.zeros((C,), jnp.float32))
                        variants.append(dict(sk_init=zp,
                                             extra_score=extra))
                for var in variants:
                    out = self._solve(key, "restricted", dp, sub_w, ds,
                                      warmup=True, **var)
                    a, wu_usage = out[0], out[1]
                    self._warm_validate(a, wu_usage, dp, sub_w)
                    jax.block_until_ready(
                        map_restricted_assignment(a, cand))
                if pack is not None and not over_limit:
                    # the frame-local quality reduction rides every
                    # scenario restricted cycle's readback — compile
                    # its (P, C) program here too
                    q_dev = self._quality_dispatch(a, wu_usage, dp, sub_dn)
                    if q_dev is not None:
                        jax.block_until_ready(q_dev)
                if self.obs.memledger.preflight_on:
                    # the preflight's peak table learns the restricted
                    # shapes too — (P, C) rows are what an over-budget
                    # dense 50k solve splits DOWN to instead of OOMing
                    # (warm-start extras are solve-only knobs the AOT
                    # analysis signature does not take)
                    self._capture_bucket_memory(key, "restricted", dp,
                                                sub_dn, ds, {})
                compiled += 1
                self.metrics.warmup_compiles.inc()
            self._warmed_cbuckets.add(C)
        klog.V(2).info("incremental warmup: compiled %d restricted "
                       "solve shapes (C ladder %s)", compiled, ladder)
        return compiled

    def is_degraded(self) -> bool:
        """Is the backend limping? True while the device is in its
        post-loss cooloff (host-mode snapshots), while the most recent
        solve had to FALL THROUGH the ladder to reach a result, or
        while the configured tier's circuit breaker is open. The
        fallback COUNT is the signal, not the tier name: the exact
        solver deliberately routes hazardous batches to the round
        solver as a healthy path, and that must not read as
        degradation. A sustained SLO burn (obs/ledger.py watchdog,
        ``ledger.engage_pressure``) also reads degraded: eroding
        create-to-bind p99 means the backend clears its queue slower
        than admission assumes, so shedding must engage EARLIER at the
        same depth. The APF saturation probe reads this so shedding
        engages from the scheduler's ACTUAL state, not only from queue
        length."""
        from kubernetes_tpu.faults import OPEN

        if self.clock() < self._device_cooloff_until:
            return True
        if self.obs.ledger.pressure_engaged():
            return True
        if self.last_solver_fallbacks > 0:
            return True
        br = self._breakers.get(f"solver:{self.solver}")
        return br is not None and br.state == OPEN

    def backend_pressure(self, degraded_factor: float = 4.0) -> float:
        """Backend-pressure probe for APF shedding
        (serving/fairness.FlowController.set_saturation): the active-
        queue depth, multiplied by ``degraded_factor`` while
        :meth:`is_degraded` — a solver running on a fallback tier (or a
        device cooling off after a shard loss) clears its queue slower,
        so admission must shed EARLIER at the same depth. Cheap enough
        to call per mutating request (two dict reads and a clock)."""
        depth = float(self.queue.pending_counts().get("active", 0))
        if depth and self.is_degraded():
            depth *= max(degraded_factor, 1.0)
        return depth

    def attach_doorbell(self, bell):
        """Wire a serving doorbell into this scheduler: the queue rings
        it on every work-adding incoming event (which covers the
        informer paths — node/volume events ring through their
        move-to-active sweeps), and it gains this scheduler's metrics
        for scheduler_doorbell_rings_total. Returns the bell."""
        self.doorbell = bell
        if getattr(bell, "metrics", "absent") is None:
            bell.metrics = self.metrics
        # duck-typed like the metrics attach: queue fakes without the
        # attribute stay valid
        if getattr(self.queue, "doorbell", "absent") is None:
            self.queue.doorbell = bell
        return bell

    def idle_tick(self) -> None:
        """Queue maintenance WITHOUT a scheduling cycle — the idle path
        of both serve loops (legacy fixed-interval and serving mode).
        Runs the periodic flushes (backoff-complete, unschedulable-
        leftover — each rings the doorbell when it moves pods), expires
        stale cache assumptions, and resolves Permit waits, but begins
        no cycle: no trace, no CycleRecord, no solve, no metrics churn.
        This is what stops an idle cluster from minting empty cycle
        artifacts every --cycle-interval."""
        self.queue.tick()
        self._reap_expired_assumptions()
        self._verify_ambiguous_binds()
        self.maybe_repack()
        # keep the SLO burn-rate windows (and the recovery transition)
        # live while idle — eventful cycles may never come to run the
        # watchdog's state machine after the queue drains
        self.obs.ledger.tick()
        # memory ledger's idle-path sample: the other declared measured
        # boundary besides cycle end (interval-gated inside)
        self.obs.memledger.tick()
        res = CycleResult()
        self._process_waiting(res)
        if res.unschedulable or res.scheduled:
            # a Permit wait resolved while idle: its outcome must still
            # reach the metrics (the cycle path records via
            # _record_metrics; the idle path owns that here)
            self._record_metrics(res)

    def state_sizes(self) -> Dict[str, int]:
        """Sizes of every unbounded-unless-maintained structure this
        scheduler owns — the leak-sentinel surface (soak.SoakSentinels).
        Pure dict-length reads: cheap enough for a maintenance-cadence
        sample, and safe under the serving loop's lock (the soak calls
        it from the maintenance hook, which already holds it). Keys are
        stable: the soak record and /debug/soak serialize them as-is,
        and the flatness gate in bench_compare diffs them by name."""
        packer = self.cache.packer
        u = packer.u
        interned = sum(
            len(v) for v in vars(u).values() if isinstance(v, Interner))
        return {
            # per-pod side state — exit paths must pop these
            "why_pending": len(self.why_pending),
            "ambiguous_binds": len(self._ambiguous_binds),
            "cycle_states": len(self._cycle_states),
            "waiting_pods": len(self.framework.waiting),
            # bounded-by-construction state — watched anyway, because a
            # bound that silently stopped binding is exactly what only
            # a soak catches
            "breakers": len(self._breakers),
            "explain_reasons_seen": len(self._explain_reasons_seen),
            "sk_warm_potentials": 0 if self._sk_warm_pot is None else 1,
            "queue_pending": sum(self.queue.pending_counts().values()),
            "cache_assumed": len(self.cache.assumed_keys()),
            "cache_pods": self.cache.pod_count(),
            # packer per-pod caches (forget_pod-cleaned) + LRU memos
            "packer_pod_refs": len(packer._pod_refs),
            "packer_vol_cache": len(packer._vol_cache),
            "packer_vol_pods": len(packer._vol_pods),
            "packer_vec_cache": len(packer._vec_cache),
            "packer_pod_index": len(packer._uids_by_key),
            "packer_pod_table_memo": len(packer._pod_table_memo),
            "packer_vol_table_memo": len(packer._vol_table_memo),
            # interner dedupe floors: grow with VOCABULARY (distinct
            # labels/images/selectors), not with churn — a churn-shaped
            # slope here means something interns per-pod-unique tokens
            "interned_items": interned,
            "universe_matcher_memo": len(u._matcher_row_memo),
            "universe_owner_index": len(u.owner_index),
            # pod-keyed owner upkeep: filed labels and nonzero counts
            # must RETURN to baseline when the pods leave
            "cache_label_index": len(self.cache._label_pods),
            "cache_owner_counts": sum(
                len(c) for c in self.cache._owner_counts.values()),
            # device-side state — the drop edges (drop_device_snapshot,
            # _drop_incremental, host-mode demotion) must zero these;
            # a resident surviving its drop is device-memory leaked
            # even when host dict lengths stay flat
            "dev_node_table": (
                1 if self.cache.has_device_snapshot() else 0),
            "dev_score_summary": (
                1 if self.cache.has_score_summary() else 0),
            "mem_residents": self.obs.memledger.resident_count(),
            "mem_census_arrays": self.obs.memledger.census_count(),
            # journey/incident retention — pending journeys must DRAIN
            # with traffic, the completed tiers and the incident ring
            # must plateau at their caps
            **self.obs.journeys.sizes(),
            **self.obs.incidents.sizes(),
        }

    def run_until_settled(self, max_cycles: int = 50) -> List[CycleResult]:
        """Drive cycles until nothing schedules (tests + sim harness)."""
        out = []
        for _ in range(max_cycles):
            r = self.schedule_cycle()
            out.append(r)
            if r.scheduled == 0 and r.attempted == 0:
                break
        return out


def _has_topo(u) -> bool:
    return bool(
        len(u.aff_programs)
        or len(u.pref_aff_programs)
        or len(u.spread_hard_programs)
        or len(u.spread_soft_programs)
        or len(u.anti_terms)
        or len(u.sym_terms)
    )
