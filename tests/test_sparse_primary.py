"""Sparsity-first solve (PR 20 tentpole): the sharded top-C candidate
route as the PRIMARY path, with the dense plane demoted to
oracle/fallback.

What this suite pins:

- mesh-parity of the sharded candidate pick: ``_sharded_topk`` /
  ``candidate_columns`` / ``partition_columns`` are BIT-IDENTICAL
  across shard counts {1, 2, 4, 8}, including tie-heavy score planes
  (the lexicographic (value desc, index asc) merge contract);
- candidate semantics: the dirty-frontier boost always wins a slot,
  the group-hint boost rides behind it, ``hint_quota`` switches to a
  reserved DISJOINT split, ineligible columns come out as the padding
  sentinel and a hint can never resurrect one;
- the partitioned cold deal: blocks are column-disjoint, round-robin
  capacity-balanced (block b holds ranks b, b+B, ...), block 0 owns
  the best column;
- delta-vs-rebuild parity on the candidate state: a
  ``patch_node_summary`` of changed rows equals a full
  ``node_summary`` rebuild bit-for-bit;
- routing: with ``incremental.primary`` on, a full-snapshot cold
  cycle takes scope ``partitioned`` (restricted correctly declines
  the rebuild), steady delta cycles go back to ``restricted``, an
  under-placeable batch declines to the dense ladder (the
  correctness fallback), and gangs/scenario-packs keep the dense
  cold semantics;
- the candidate-bucket auto-tuner: pinned without a warmed ladder,
  widened by observed micro-batch sizes and placement-depth
  telemetry, never past the widest warmed rung (a tuner move must
  never retrace);
- config plumbing for ``primary`` / ``autoTune`` / ``coldBlocks``;
- memory-ledger coverage of the candidate frame residents;
- the bench_compare ``sparse`` gate family contract.
"""

import random

import numpy as np
import pytest

from kubernetes_tpu.config import IncrementalConfig, WarmupConfig
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.testing import make_node, make_pod


def _jnp():
    import jax.numpy as jnp

    return jnp


def _summary(rank, eligible):
    from kubernetes_tpu.ops.fused_score import NodeSummary, _NEG

    jnp = _jnp()
    rank = np.asarray(rank, np.float32)
    eligible = np.asarray(eligible, bool)
    return NodeSummary(
        eligible=jnp.asarray(eligible),
        rank=jnp.asarray(np.where(eligible, rank, _NEG)))


# ---------------------------------------------------------------------------
# sharded top-C: mesh parity, tie-break discipline
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sharded_topk_parity_fuzz(seed):
    """The two-stage (per-shard local top-k, replicated merge) pick is
    bit-identical to the single-pass pick on every shard count — ties
    included (duplicated values force the index tie-break)."""
    from kubernetes_tpu.ops.fused_score import _sharded_topk

    jnp = _jnp()
    rng = np.random.default_rng(seed)
    n, k = 256, 24
    # tie-heavy: scores drawn from a tiny alphabet so most values repeat
    score = jnp.asarray(
        rng.choice(np.linspace(0.0, 1.0, 7), size=n).astype(np.float32))
    ref_v, ref_i = _sharded_topk(score, k, 1)
    for shards in (2, 4, 8):
        v, i = _sharded_topk(score, k, shards)
        assert np.array_equal(np.asarray(v), np.asarray(ref_v)), shards
        assert np.array_equal(np.asarray(i), np.asarray(ref_i)), shards


def test_sharded_topk_uneven_shapes_fall_back():
    """Shapes that cannot shard evenly (or k too large for a lossless
    local pick) take the single-pass path — same answer, no error."""
    from kubernetes_tpu.ops.fused_score import _sharded_topk

    jnp = _jnp()
    score = jnp.asarray(np.arange(100, dtype=np.float32))
    ref_v, ref_i = _sharded_topk(score, 10, 1)
    v, i = _sharded_topk(score, 10, 3)  # 100 % 3 != 0
    assert np.array_equal(np.asarray(v), np.asarray(ref_v))
    assert np.array_equal(np.asarray(i), np.asarray(ref_i))
    # k > n // shards: a lossless local pick is impossible
    v, i = _sharded_topk(score, 60, 2)
    rv, ri = _sharded_topk(score, 60, 1)
    assert np.array_equal(np.asarray(v), np.asarray(rv))
    assert np.array_equal(np.asarray(i), np.asarray(ri))


@pytest.mark.parametrize("seed", [0, 1])
def test_candidate_columns_mesh_parity_fuzz(seed):
    """candidate_columns is bit-identical across shard counts under
    every variant combination: contended (tie-heavy) planes, dirty
    frontiers, hint masks, and the reserved-quota split."""
    from kubernetes_tpu.ops.fused_score import candidate_columns

    jnp = _jnp()
    rng = np.random.default_rng(100 + seed)
    n, k = 128, 16
    s = _summary(rng.choice(np.linspace(0, 1, 5), size=n),
                 rng.random(n) > 0.2)
    dirty = jnp.asarray(rng.random(n) > 0.9)
    hint = jnp.asarray(rng.random(n) > 0.8)
    for kwargs in (
            dict(),
            dict(hint_mask=hint),
            dict(hint_mask=hint, hint_quota=4),
    ):
        ref = np.asarray(candidate_columns(s, dirty, k, num_shards=1,
                                           **kwargs))
        for shards in (2, 4, 8):
            got = np.asarray(candidate_columns(s, dirty, k,
                                               num_shards=shards,
                                               **kwargs))
            assert np.array_equal(ref, got), (shards, kwargs)


def test_candidate_columns_dirty_always_survives_cut():
    """A dirty eligible column with the WORST rank still wins a slot —
    the churn frontier is guaranteed representation."""
    from kubernetes_tpu.ops.fused_score import candidate_columns

    jnp = _jnp()
    n, k = 64, 4
    rank = np.linspace(1.0, 0.0, n)  # column 63 ranks dead last
    s = _summary(rank, np.ones(n, bool))
    dirty = np.zeros(n, bool)
    dirty[63] = True
    idx = np.asarray(candidate_columns(s, jnp.asarray(dirty), k))
    assert 63 in idx
    # and a dirty INELIGIBLE column stays out (boost cannot resurrect)
    s2 = _summary(rank, np.arange(n) != 63)
    idx2 = np.asarray(candidate_columns(s2, jnp.asarray(dirty), k))
    assert 63 not in idx2


def test_candidate_columns_hint_quota_reserved_split():
    """hint_quota reserves the FIRST hq slots for hinted columns and
    fills the rest from unhinted ones — disjoint by construction, and
    a too-small hint set pads its quota slots with the sentinel."""
    from kubernetes_tpu.ops.fused_score import candidate_columns

    jnp = _jnp()
    n, k, hq = 64, 8, 4
    rank = np.linspace(1.0, 0.0, n)
    s = _summary(rank, np.ones(n, bool))
    zeros = jnp.zeros((n,), bool)
    hint = np.zeros(n, bool)
    hint[40:60] = True  # 20 hinted columns, all LOW rank
    idx = np.asarray(candidate_columns(
        s, zeros, k, hint_mask=jnp.asarray(hint), hint_quota=hq))
    # quota slots: best hinted columns; the rest: best unhinted
    assert list(idx[:hq]) == [40, 41, 42, 43]
    assert list(idx[hq:]) == [0, 1, 2, 3]
    # a hint set smaller than the quota pads with the sentinel
    tiny = np.zeros(n, bool)
    tiny[50] = True
    idx = np.asarray(candidate_columns(
        s, zeros, k, hint_mask=jnp.asarray(tiny), hint_quota=hq))
    assert idx[0] == 50
    assert list(idx[1:hq]) == [n, n, n]
    assert list(idx[hq:]) == [0, 1, 2, 3]


def test_partition_columns_disjoint_round_robin():
    """The cold deal: top B*C columns dealt round-robin — block b holds
    ranks b, b+B, ... (capacity-balanced), blocks are disjoint, block 0
    owns the single best column, ineligible slots pad with the
    sentinel."""
    from kubernetes_tpu.ops.fused_score import partition_columns

    jnp = _jnp()
    n, B, C = 64, 4, 8
    rank = np.linspace(1.0, 0.0, n)  # rank order == index order
    s = _summary(rank, np.ones(n, bool))
    blocks = np.asarray(partition_columns(s, jnp.zeros((n,), bool), B, C))
    assert blocks.shape == (B, C)
    flat = blocks.reshape(-1)
    assert len(set(flat.tolist())) == B * C  # disjoint
    for b in range(B):
        assert list(blocks[b]) == list(range(b, B * C, B))
    assert blocks[0, 0] == 0  # best column in block 0
    # with only 3 eligible columns the rest of the deal is sentinel
    s2 = _summary(rank, np.arange(n) < 3)
    blocks2 = np.asarray(partition_columns(s2, jnp.zeros((n,), bool),
                                           B, C))
    assert sorted(set(blocks2.reshape(-1).tolist())) == [0, 1, 2, n]


@pytest.mark.parametrize("shards", [2, 4, 8])
def test_partition_columns_mesh_parity(shards):
    from kubernetes_tpu.ops.fused_score import partition_columns

    jnp = _jnp()
    rng = np.random.default_rng(7)
    n = 128
    s = _summary(rng.choice(np.linspace(0, 1, 5), size=n),
                 rng.random(n) > 0.3)
    zeros = jnp.zeros((n,), bool)
    ref = np.asarray(partition_columns(s, zeros, 4, 8, 1))
    got = np.asarray(partition_columns(s, zeros, 4, 8, shards))
    assert np.array_equal(ref, got)


# ---------------------------------------------------------------------------
# delta-vs-rebuild parity on the candidate state
# ---------------------------------------------------------------------------


def test_summary_patch_equals_full_rebuild():
    """After churn, patching the changed rows into the resident summary
    equals a from-scratch rebuild bit-for-bit — the candidate state has
    no drift channel (satellite of the delta-after-churn == rebuild
    contract)."""
    import jax

    from kubernetes_tpu.ops.arrays import gather_node_rows
    from kubernetes_tpu.ops.fused_score import (
        node_summary,
        patch_node_summary,
    )

    jnp = _jnp()
    s = _build()  # the suite's shared 96-node shape (one compile set)
    _churn(s, 4, "a")
    s.schedule_cycle()
    _tbl, dn, _mode = s.cache.device_snapshot()
    base = node_summary(dn)
    # mutate a few rows through the real churn path, then patch ONLY
    # those rows vs rebuild the whole plane
    _churn(s, 3, "b")
    s.schedule_cycle()
    _tbl, dn2, _mode = s.cache.device_snapshot()
    idx = np.asarray(sorted(set(range(0, 96, 5))), np.int32)
    sub = node_summary(gather_node_rows(dn2, jnp.asarray(idx)))
    # rows outside idx did not change rank in this churn? — patch ALL
    # rows to make the parity unconditional
    all_idx = np.arange(dn2.valid.shape[0], dtype=np.int32)
    sub_all = node_summary(gather_node_rows(dn2, jnp.asarray(all_idx)))
    patched = patch_node_summary(base, sub_all, all_idx)
    rebuilt = node_summary(dn2)
    assert np.array_equal(np.asarray(patched.eligible),
                          np.asarray(rebuilt.eligible))
    assert np.array_equal(np.asarray(patched.rank),
                          np.asarray(rebuilt.rank))
    # and a partial patch changes exactly the patched rows
    part = patch_node_summary(rebuilt, sub, idx)
    jax.block_until_ready(part.rank)


# ---------------------------------------------------------------------------
# routing: partitioned primary, fallback polarity
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _build(n_nodes=96, candidate_bucket=32, warm=False, **kw):
    """A primary-mode scheduler over a cluster larger than the bucket
    (bucket_size(96) = 128 > C = 32, cold blocks = 4)."""
    inc = kw.pop("incremental", None) or IncrementalConfig(
        enabled=True, primary=True, candidate_bucket=candidate_bucket)
    wu = ({"warmup": WarmupConfig(enabled=True, pod_buckets=(4, 8))}
          if warm else {})
    s = Scheduler(enable_preemption=False, incremental=inc,
                  clock=FakeClock(), **wu, **kw)
    for i in range(n_nodes):
        s.on_node_add(make_node(f"n{i}", cpu_milli=64000,
                                memory=256 * 2**30, pods=500))
    if warm:
        s.warmup(sample_pods=[make_pod("warm-sample", cpu_milli=50,
                                       memory=128 * 2**20)])
    return s


def _churn(s, n, tag, cpu=50, mem=128 * 2**20):
    for i in range(n):
        s.on_pod_add(make_pod(f"{tag}-{i}", cpu_milli=cpu, memory=mem))


def test_partitioned_engages_on_cold_cycle_then_restricted():
    """Primary mode: the first (full-snapshot) cycle rides the
    PARTITIONED cold route — restricted correctly declines the rebuild
    — and the next delta cycle goes back to restricted. Provenance
    reaches the CycleResult and the metrics."""
    s = _build()
    _churn(s, 6, "a")
    r1 = s.schedule_cycle()
    assert r1.snapshot_mode == "full"
    assert r1.solve_scope == "partitioned"
    assert r1.cold_blocks == 4
    assert r1.scheduled == 6
    for _key, node in r1.assignments.items():
        assert s.cache.node(node) is not None
    assert s.metrics.incremental_cycles.value(scope="partitioned") == 1
    _churn(s, 4, "b")
    r2 = s.schedule_cycle()
    assert r2.snapshot_mode in ("clean", "delta")
    assert r2.solve_scope == "restricted"
    assert r2.scheduled == 4


def test_partitioned_reengages_after_node_churn():
    """A node delete mid-steady-state forces the full-snapshot rebuild;
    the NEXT cold cycle rides partitioned again (the bench probe's
    shape), and placements never land on the dead node."""
    s = _build()
    _churn(s, 4, "a")
    assert s.schedule_cycle().solve_scope == "partitioned"
    _churn(s, 4, "b")
    assert s.schedule_cycle().solve_scope == "restricted"
    s.on_node_delete("n95")
    _churn(s, 4, "c")
    r = s.schedule_cycle()
    assert r.snapshot_mode == "full"
    assert r.solve_scope == "partitioned"
    assert r.scheduled == 4
    assert "n95" not in set(r.assignments.values())


def test_partitioned_under_placed_declines_to_dense():
    """A pod nothing can host: the partitioned attempt under-places,
    binds NOTHING, and the same cycle re-solves dense with full failure
    analytics — the correctness fallback."""
    s = _build()
    _churn(s, 2, "a")
    s.on_pod_add(make_pod("giant", cpu_milli=10_000_000))
    r = s.schedule_cycle()
    assert r.solve_scope == "full"  # fell through to the dense ladder
    assert r.scheduled == 2
    assert r.unschedulable == 1
    assert "default/giant" in r.failure_reasons
    assert s.metrics.incremental_cycles.value(
        scope="under-placed") >= 1


def _assert_placements_valid(nodes, pods, assignments):
    """Every placement of the run, checked jointly against the empty
    cluster by the host checker (ops/assign.validate_solution)."""
    from kubernetes_tpu.ops.arrays import nodes_to_device, pods_to_device
    from kubernetes_tpu.ops.assign import usage_from_nodes, validate_solution
    from kubernetes_tpu.snapshot import SnapshotPacker

    pk = SnapshotPacker()
    for p in pods:
        pk.intern_pod(p)
    dn = nodes_to_device(pk.pack_nodes(nodes))
    dp = pods_to_device(pk.pack_pods(pods))
    row = {nd.name: i for i, nd in enumerate(nodes)}
    a = np.full((dp.valid.shape[0],), -1, np.int32)
    a[: len(pods)] = [row[assignments[p.key()]] for p in pods]
    assert validate_solution(a, usage_from_nodes(dn), dp, dn) == (True, "")


@pytest.mark.parametrize("host_validate", [False, True],
                         ids=["fused", "host"])
@pytest.mark.parametrize("route", ["restricted", "partitioned"])
def test_lying_frame_solver_declines_to_dense(route, host_validate):
    """A frame route's solver that lies — every pod of the batch on the
    frame's first column, four 25-core pods on one 64-core node — is
    caught by the shared validated readback under fused AND forced-host
    validation alike: the route declines, binds nothing, and the dense
    ladder places the batch in the same cycle."""
    from kubernetes_tpu.config import RobustnessConfig
    from kubernetes_tpu.faults import FaultInjector

    fi = FaultInjector(seed=3)
    s = _build(incremental=IncrementalConfig(
        enabled=True, primary=(route == "partitioned"),
        candidate_bucket=32),
        robustness=RobustnessConfig(host_validate=host_validate),
        fault_injector=fi)
    nodes = [s.cache.node(f"n{i}") for i in range(96)]
    pods, assignments = [], {}
    if route == "restricted":
        # an honest cold cycle first: the restricted route needs a warm
        # resident snapshot
        pods = [make_pod(f"warm-{i}", cpu_milli=50) for i in range(4)]
        for p in pods:
            s.on_pod_add(p)
        r0 = s.schedule_cycle()
        assert r0.scheduled == 4
        assignments.update(r0.assignments)
    fi.arm(f"solve:{route}", "infeasible")
    heavy = [make_pod(f"heavy-{i}", cpu_milli=25_000) for i in range(4)]
    for p in heavy:
        s.on_pod_add(p)
    r = s.schedule_cycle()
    assert fi.fired_total(f"solve:{route}") >= 1
    assert s.metrics.incremental_cycles.value(scope="declined") >= 1
    assert r.solve_scope == "full"
    assert r.scheduled == 4
    pods += heavy
    assignments.update(r.assignments)
    _assert_placements_valid(nodes, pods, assignments)


def test_warmed_partitioned_then_restricted_never_retrace():
    """Warmup and the served frame routes build their solve signature
    from one key: after warmup, a cold PARTITIONED cycle (a node delete
    forces the full-snapshot rebuild) and then restricted cycles across
    the warmed pod buckets add zero retraces at any site."""
    s = _build(warm=True)
    _churn(s, 3, "a")
    assert s.schedule_cycle().solve_scope == "restricted"
    s.on_node_delete("n95")
    _churn(s, 3, "cold")
    r = s.schedule_cycle()
    assert r.solve_scope == "partitioned"
    assert r.scheduled == 3
    for n, tag in ((7, "b"), (2, "c"), (4, "d")):
        _churn(s, n, tag)
        r = s.schedule_cycle()
        assert r.solve_scope == "restricted"
        assert r.scheduled == n
    assert s.obs.jax.calls.get("solve", 0) >= 4
    assert s.obs.jax.retrace_total() == 0


def test_gangs_and_packs_keep_dense_cold_semantics():
    """Cold-route polarity: a gang batch keeps the dense oracle's
    monolithic cold solve (rollback + failure analytics want the full
    plane when solving cold), even in primary mode."""
    s = _build()
    for i in range(2):
        s.on_pod_add(make_pod(f"g{i}", cpu_milli=10, pod_group="gang",
                              pod_group_min_available=2))
    r = s.schedule_cycle()
    assert r.solve_scope == "full"
    assert r.scheduled == 2


def test_restricted_ok_pack_rides_restricted_both_polarities():
    """Capability-driven eligibility, NOT blanket scenario exclusion:
    a ``restricted_ok`` pack (quality off — the quality reduction is
    whole-batch coupling) rides the restricted path on a steady cycle;
    flipping the capability off sends the same cycle shape back to the
    dense oracle."""
    from kubernetes_tpu.config import ScenarioConfig

    s = _build(scenario=ScenarioConfig(pack="consolidation",
                                       quality=False))
    _churn(s, 4, "a")
    s.schedule_cycle()  # cold cycle warms the cache
    _churn(s, 4, "b")
    r = s.schedule_cycle()
    assert r.solve_scope == "restricted"
    assert r.scheduled == 4
    s.scenario_pack.restricted_ok = False  # needs the full plane now
    _churn(s, 4, "c")
    r2 = s.schedule_cycle()
    assert r2.solve_scope == "full"
    assert r2.scheduled == 4


def test_pipeline_eligibility_is_capability_driven():
    """Same contract on the pipelined executor's gate: restricted_ok +
    quality-off rides, quality-on or a non-restricted_ok pack keeps
    the monolithic cycle."""
    from kubernetes_tpu.config import ScenarioConfig

    s = Scheduler(enable_preemption=False, pipeline_depth=2,
                  pipeline_chunk=4,
                  scenario=ScenarioConfig(pack="consolidation",
                                          quality=False))
    batch = [make_pod(f"p{i}", cpu_milli=10) for i in range(8)]
    assert s._pipeline_eligible(batch, []) is True
    s.scenario.quality = True  # whole-batch coupling -> monolithic
    assert s._pipeline_eligible(batch, []) is False
    s.scenario.quality = False
    s.scenario_pack.restricted_ok = False
    assert s._pipeline_eligible(batch, []) is False


def test_primary_off_keeps_dense_cold():
    """Polarity pin: without ``primary`` the cold cycle stays dense —
    the partitioned route is opt-in."""
    s = _build(incremental=IncrementalConfig(
        enabled=True, primary=False, candidate_bucket=32))
    _churn(s, 4, "a")
    r = s.schedule_cycle()
    assert r.solve_scope == "full"
    assert r.scheduled == 4


def test_partitioned_ledger_covers_candidate_frames():
    """Memory-ledger coverage (PR-18 seams): a restricted cycle
    registers the candidate frame residents under the scheduler.
    prefix; every invalidation edge drops them."""
    s = _build()
    _churn(s, 4, "a")
    s.schedule_cycle()
    _churn(s, 4, "b")
    r = s.schedule_cycle()
    assert r.solve_scope == "restricted"
    names = [n for n, _b, _s in s.obs.memledger.ranked_residents()]
    assert "scheduler.candidate_frame" in names
    s._drop_incremental("test")
    names = [n for n, _b, _s in s.obs.memledger.ranked_residents()]
    assert "scheduler.candidate_frame" not in names


def test_peak_table_learns_frames_and_dense_fallback_splits():
    """Capacity-preflight coverage, end to end: warmup on a PRIMARY
    scheduler lands BOTH the dense (P, n_pad) buckets and the
    restricted (P, C) frame rows in the peak table (visible through
    /debug/memory), and with a limit only the small dense bucket
    clears, an over-budget batch that must take the dense fallback
    (a gang cold cycle keeps the dense oracle) preflight-SPLITS to
    the warmed bucket instead of OOMing the device."""
    s = _build(warm=True)
    ml = s.obs.memledger
    table = ml.bucket_table()
    n_pad = 128  # bucket_size(96)
    dense = sorted(k for k in table if k[1] == n_pad)
    frames = sorted(k for k in table if k[1] < n_pad)
    assert [p for p, _n, _m in dense] == [4, 8]
    assert frames and all(n in (16, 32, 64) for _p, n, _m in frames)
    p0, n0, _m0 = frames[0]
    assert f"P{p0}xN{n0}" in ml.snapshot()["buckets"]
    (k4, k8) = dense
    # budget exactly covers the P4 dense bucket, not the P8 one
    ml.config.limit_bytes = int(
        table[k4]["total_bytes"] / ml.config.headroom_frac) + 2
    assert ml.preflight(*k8)[0] == "split"
    for i in range(8):
        # host ports couple in-batch across the full node axis, so the
        # batch is restricted-ineligible and MUST take the dense
        # fallback — the over-budget route the preflight protects
        s.on_pod_add(make_pod(f"hp{i}", cpu_milli=10,
                              host_ports=(("TCP", "", 8080 + i),)))
    r1 = s.schedule_cycle()
    assert r1.solve_scope == "full"  # dense fallback, preflight-split
    assert (r1.attempted, r1.scheduled) == (4, 4)
    r2 = s.schedule_cycle()  # the requeued half lands next cycle
    assert r2.scheduled == 4
    assert ml.preflights["split"] >= 1
    assert s.metrics.memory_preflight.value(action="split") >= 1
    assert ml.oom_records() == []


# ---------------------------------------------------------------------------
# the candidate-bucket auto-tuner
# ---------------------------------------------------------------------------


def _tuner(auto_tune=True, candidate_bucket=32, **kw):
    s = Scheduler(enable_preemption=False,
                  incremental=IncrementalConfig(
                      enabled=True, auto_tune=auto_tune,
                      candidate_bucket=candidate_bucket, **kw),
                  clock=FakeClock())
    return s


def test_tuner_pinned_without_warmed_ladder():
    """No warmed C ladder -> the tuner stays pinned to the configured
    bucket (a tuner move must NEVER retrace, and unwarmed rungs
    would)."""
    s = _tuner()
    s._note_tuner_batch(60)
    assert s._candidate_bucket(1024) == 32
    s2 = _tuner(auto_tune=False)
    s2._warmed_cbuckets.update({16, 32, 64})
    s2._note_tuner_batch(60)
    assert s2._candidate_bucket(1024) == 32


def test_tuner_widens_on_observed_batches():
    """Observed micro-batches widen the bucket: the smallest warmed
    rung admitting the recent batches under maxBatchFrac wins; demand
    past the widest rung saturates there (never an unwarmed shape)."""
    s = _tuner()
    s._warmed_cbuckets.update({16, 32, 64})
    assert s._candidate_bucket(1024) == 16  # no observations: smallest
    s._note_tuner_batch(20)  # need 40 -> rung 64
    assert s._candidate_bucket(1024) == 64
    s._note_tuner_batch(500)  # past the widest rung: saturate
    assert s._candidate_bucket(1024) == 64


def test_tuner_depth_telemetry_widens():
    """Placement-rank telemetry: pods landing deep in the candidate
    frame (the rank order being fought) demand 2x headroom."""
    s = _tuner()
    s._warmed_cbuckets.update({16, 32, 64})
    s._tuner_depth_max = 20  # need 40 -> rung 64
    assert s._candidate_bucket(1024) == 64


def test_tuner_observation_window_slides():
    s = _tuner()
    s._warmed_cbuckets.update({16, 32, 64})
    for _ in range(80):
        s._note_tuner_batch(2)
    assert len(s._tuner_batch_obs) == 64
    s._note_tuner_batch(30)
    assert s._candidate_bucket(1024) == 64


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------


def test_config_sparse_fields_round_trip():
    from kubernetes_tpu.api.config_v1alpha1 import decode, encode
    from kubernetes_tpu.config import KubeSchedulerConfiguration

    cfg = KubeSchedulerConfiguration(
        incremental=IncrementalConfig(
            enabled=True, primary=True, auto_tune=True, cold_blocks=6,
            group_quota_frac=0.3))
    doc = encode(cfg)
    inc = doc["incremental"]
    assert inc["primary"] is True
    assert inc["autoTune"] is True
    assert inc["coldBlocks"] == 6
    assert inc["groupQuotaFrac"] == pytest.approx(0.3)
    back = decode(doc)
    assert back.incremental == cfg.incremental


def test_cold_blocks_auto_and_clamp():
    s = _tuner()
    # auto: n_pad // C capped at 8
    assert s._cold_blocks(1024, 64) == 8
    assert s._cold_blocks(256, 64) == 4
    # explicit config clamps so B*C fits the table
    s2 = _tuner(cold_blocks=16)
    assert s2._cold_blocks(256, 64) == 4


# ---------------------------------------------------------------------------
# the bench_compare `sparse` gate family
# ---------------------------------------------------------------------------


def _load_bc(name="bench_compare_sparse"):
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(__file__), "..", "scripts",
                           "bench_compare.py"))
    bc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bc)
    return bc


def _sparse_record(sparse_growth=0.95, cold_ratio=0.2, retraces=0,
                   bpp=6.0, restricted=1.0, scopes=("partitioned",),
                   qdelta=0.001, placed_equal=True, smoke=False):
    return {
        "name": "churn_sparse",
        "smoke": smoke,
        "sizes": [2048, 50000],
        "quality_bound": 0.02,
        "flatness": {"sparse_growth": sparse_growth,
                     "dense_growth": 2.5},
        "cold_slope": {"ratio": cold_ratio},
        "cells": {
            "sparse_2048": {"retraces_total": retraces,
                            "readback_bytes_per_pod": bpp,
                            "restricted_frac": restricted,
                            "steady_mean_solve_s": 0.02},
            "dense_2048": {"retraces_total": 0,
                           "readback_bytes_per_pod": 8.0,
                           "restricted_frac": 0.0,
                           "steady_mean_solve_s": 0.02},
        },
        "cold": {"sparse_2048": {"scopes": list(scopes)},
                 "dense_2048": {"scopes": ["full", "full"]}},
        "quality": {"placed_equal": placed_equal,
                    "restricted_engaged": True,
                    "score_delta_frac_max": qdelta},
    }


def test_bench_compare_sparse_gates():
    bc = _load_bc()
    ok = bc.compare_churn_sparse({}, _sparse_record(), 0.10)
    assert not ok["regressions"]
    # flatness blown: the tentpole scale claim
    bad = bc.compare_churn_sparse({}, _sparse_record(sparse_growth=2.0),
                                  0.10)
    assert any(r["check"] == "sparse.flatness.sparse_growth"
               for r in bad["regressions"])
    # partitioned cold slope no longer sublinear vs the dense oracle
    bad = bc.compare_churn_sparse({}, _sparse_record(cold_ratio=0.9),
                                  0.10)
    assert any(r["check"] == "sparse.cold_slope.ratio"
               for r in bad["regressions"])
    # a retrace anywhere is absolute
    bad = bc.compare_churn_sparse({}, _sparse_record(retraces=1), 0.10)
    assert any("retraces" in r["check"] for r in bad["regressions"])
    # engagement collapsed / silent dense fall-through on a cold probe
    bad = bc.compare_churn_sparse({}, _sparse_record(restricted=0.5),
                                  0.10)
    assert any("restricted_frac" in r["check"]
               for r in bad["regressions"])
    bad = bc.compare_churn_sparse(
        {}, _sparse_record(scopes=("partitioned", "full")), 0.10)
    assert any("cold_partitioned" in r["check"]
               for r in bad["regressions"])
    # readback blowout
    bad = bc.compare_churn_sparse({}, _sparse_record(bpp=99.0), 0.10)
    assert any("readback_budget" in r["check"]
               for r in bad["regressions"])
    # quality delta over the documented bound
    bad = bc.compare_churn_sparse({}, _sparse_record(qdelta=0.5), 0.10)
    assert any(r["check"] == "sparse.quality.score_delta"
               for r in bad["regressions"])
    # delta gate: sparse steady cost regressed vs the previous record
    prev, cur = _sparse_record(), _sparse_record()
    cur["cells"]["sparse_2048"]["steady_mean_solve_s"] = 0.2
    v = bc.compare_churn_sparse(prev, cur, 0.10)
    assert any(r["check"] == "sparse.sparse_2048.steady_mean_solve_s"
               for r in v["regressions"])
    # the family is registered
    assert any(n == "sparse" for n, _g, _e in bc.GATE_FAMILIES)


def test_bench_compare_sparse_smoke_skips_scale_absolutes():
    """A smoke record skips the scale-claim absolutes (flatness, cold
    slope, readback) with a WARNING — engagement and retrace gates
    still bite."""
    bc = _load_bc("bench_compare_sparse_smoke")
    rec = _sparse_record(sparse_growth=9.0, cold_ratio=9.0, bpp=99.0,
                         smoke=True)
    v = bc.compare_churn_sparse({}, rec, 0.10)
    assert not v["regressions"]
    assert any("smoke" in w for w in v["warnings"])
    bad = bc.compare_churn_sparse(
        {}, _sparse_record(smoke=True, retraces=1), 0.10)
    assert any("retraces" in r["check"] for r in bad["regressions"])


def test_list_gates_includes_sparse(capsys):
    bc = _load_bc("bench_compare_sparse_list")
    assert bc.main(["--list-gates"]) == 0
    out = capsys.readouterr().out
    assert "sparse" in out and "churn_sparse_r*.json" in out


# ---------------------------------------------------------------------------
# lint discipline over the changed kernels
# ---------------------------------------------------------------------------


def test_sparse_kernels_lint_clean():
    """The candidate/partition kernels keep the kernel discipline
    (R2/R3/R5 via lint_clean's default set; R7-R10 are enforced
    module-wide by the tier-1 graftlint gate in
    test_static_analysis)."""
    import kubernetes_tpu.ops.fused_score as fs
    from kubernetes_tpu.testing import lint_clean

    lint_clean(fs)
