"""Pallas TPU kernels for the fused scoring normalize — the single-pass
masked NormalizeReduce pair (VERDICT r4 item 3).

The two hoisted-raw priorities (NodeAffinity forward, TaintToleration
reverse — priorities/reduce.go NormalizeReduce semantics over the
filtered node list, generic_scheduler.go:684) each cost a full (P, N)
masked row-max plus a full (P, N) scale per round. XLA:CPU fuses the
elementwise chains but still materializes per-kernel temporaries and
separate accumulate passes (benchres/solver_profile_cpu.json: the
normalize-reduce family was ~2/3 of scoring). These kernels restructure
the pair into two HBM-minimal passes shared across BOTH priorities:

  pass 1 (_pair_max_kernel): one streaming read of raw_fwd, raw_rev and
      the mask produces both per-pod feasible maxima — tile-accumulated
      in VMEM, never materializing the masked (P, N) temporaries;
  pass 2 (_pair_scale_kernel): one streaming read of both raws scales,
      floors, reverses and WEIGHT-COMBINES into a single (P, N) output —
      the weighted pair lands as one accumulate term.

Total HBM traffic ≈ 5 f32 matrices + 1 bool vs ~9 for the unfused
chain. Per-element arithmetic replicates ops/priorities._idiv and
_normalize_reduce exactly; the row max is computed tile-wise, and f32
max is exact under any association, so the result is bit-identical to
the jnp path (pinned by tests/test_priorities.py in interpret mode and
by chip_smoke.py compiled on the chip).

Routing is static, as in ops/sinkhorn.py: the blocks always shrink to a
slab inside VMEM_SLAB_BUDGET, so on a TPU the pair always runs as Pallas
and a Mosaic error propagates out of the caller's jit.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

MAX_PRIORITY = 10.0
_EPS = 1e-5

BLOCK_P, BLOCK_N = 256, 512
#: per-slab VMEM budget (see ops/sinkhorn.py VMEM_SLAB_BUDGET: Mosaic
#: double-buffers each block against a 16 MiB scoped-VMEM stack; 2 MiB
#: slabs stay inside it with four live inputs)
VMEM_SLAB_BUDGET = 2 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _block_shapes(P0: int, N0: int, block_p: int = BLOCK_P,
                  block_n: int = BLOCK_N):
    """(bp, bn, padded P, padded N) — one place for block/padding math
    (sinkhorn._block_shapes pattern). Both dims multiples of 128; blocks
    shrink until a (bp, bn) f32 slab fits the budget, which the 128x128
    floor (64 KiB) always does — so unlike Sinkhorn's (bp, N) slabs this
    pair has no shape that must route to jnp."""
    bp = min(block_p, _round_up(P0, 128))
    bn = min(block_n, _round_up(N0, 128))
    while bp > 128 and bp * bn * 4 > VMEM_SLAB_BUDGET:
        bp -= 128
    while bn > 128 and bp * bn * 4 > VMEM_SLAB_BUDGET:
        bn -= 128
    return bp, bn, _round_up(P0, bp), _round_up(N0, bn)


def _idiv(num, den):
    """ops/priorities._idiv verbatim (Go integer division in f32)."""
    return jnp.floor(num / jnp.maximum(den, 1e-30) + _EPS)


def _pair_max_kernel(rf_ref, rr_ref, m_ref, mxf_ref, mxr_ref):
    """Tile-accumulated masked row maxima for both raws at once."""
    from jax.experimental import pallas as pl

    j = pl.program_id(1)
    m = m_ref[...]
    mf = jnp.max(jnp.where(m, rf_ref[...], 0.0), axis=1)
    mr = jnp.max(jnp.where(m, rr_ref[...], 0.0), axis=1)

    @pl.when(j == 0)
    def _init():
        mxf_ref[0, :] = mf
        mxr_ref[0, :] = mr

    @pl.when(j > 0)
    def _acc():
        mxf_ref[0, :] = jnp.maximum(mxf_ref[0, :], mf)
        mxr_ref[0, :] = jnp.maximum(mxr_ref[0, :], mr)


def _make_pair_scale_kernel(w_fwd: float, w_rev: float):
    def _pair_scale_kernel(rf_ref, rr_ref, mxf_ref, mxr_ref, o_ref):
        rf = rf_ref[...]
        rr = rr_ref[...]
        mxf = mxf_ref[0, :][:, None]
        mxr = mxr_ref[0, :][:, None]
        sf = _idiv(MAX_PRIORITY * rf, jnp.where(mxf > 0, mxf, 1.0))
        sf = jnp.where(mxf > 0, sf, 0.0)
        sr = _idiv(MAX_PRIORITY * rr, jnp.where(mxr > 0, mxr, 1.0))
        sr = jnp.where(mxr > 0, sr, 0.0)
        sr = jnp.where(mxr > 0, MAX_PRIORITY - sr, MAX_PRIORITY)
        o_ref[...] = w_fwd * sf + w_rev * sr

    return _pair_scale_kernel


def _pair_pallas(raw_fwd, raw_rev, mask, w_fwd, w_rev,
                 block_p=BLOCK_P, block_n=BLOCK_N, interpret=False):
    from jax.experimental import pallas as pl

    P0, N0 = raw_fwd.shape
    bp, bn, P, N = _block_shapes(P0, N0, block_p, block_n)
    if (P, N) != (P0, N0):
        # padded rows/cols: mask False -> excluded from maxima; their
        # output values are garbage-free (scale of 0 raws) and sliced off
        raw_fwd = jnp.pad(raw_fwd, ((0, P - P0), (0, N - N0)))
        raw_rev = jnp.pad(raw_rev, ((0, P - P0), (0, N - N0)))
        mask = jnp.pad(mask, ((0, P - P0), (0, N - N0)))
    mxf, mxr = pl.pallas_call(
        _pair_max_kernel,
        grid=(P // bp, N // bn),
        in_specs=[
            pl.BlockSpec((bp, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bp, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bp, bn), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bp), lambda i, j: (0, i)),
            pl.BlockSpec((1, bp), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, P), raw_fwd.dtype),
            jax.ShapeDtypeStruct((1, P), raw_fwd.dtype),
        ],
        interpret=interpret,
    )(raw_fwd, raw_rev, mask)
    out = pl.pallas_call(
        _make_pair_scale_kernel(float(w_fwd), float(w_rev)),
        grid=(P // bp, N // bn),
        in_specs=[
            pl.BlockSpec((bp, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bp, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, bp), lambda i, j: (0, i)),
            pl.BlockSpec((1, bp), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((bp, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((P, N), raw_fwd.dtype),
        interpret=interpret,
    )(raw_fwd, raw_rev, mxf, mxr)
    return out[:P0, :N0]


def use_pallas() -> bool:
    """On by default on real TPU; KTPU_PALLAS=1 forces interpret mode
    (testing), =0 disables (same policy as ops/sinkhorn.use_pallas)."""
    env = os.environ.get("KTPU_PALLAS", "")
    if env == "0":
        return False
    if env == "1":
        return True
    return jax.default_backend() == "tpu"


def fused_pair_normalize_device(raw_fwd, raw_rev, mask, w_fwd, w_rev):
    """Backend-routing entry: the Pallas two-pass pair under the Pallas
    policy (compiled on a TPU, interpreted under KTPU_PALLAS=1
    elsewhere), else None — the caller (priorities._fused_pair_normalize)
    then runs its fused jnp expression. The route is recorded at trace
    time (obs.jaxtel.kernel_routes)."""
    from kubernetes_tpu.obs.jaxtel import record_kernel_route

    if not use_pallas():
        record_kernel_route("fused_pair", "jnp")
        return None
    interp = jax.default_backend() != "tpu"
    record_kernel_route("fused_pair", "interpret" if interp else "pallas")
    return _pair_pallas(raw_fwd, raw_rev, mask, w_fwd, w_rev,
                        interpret=interp)


# ---------------------------------------------------------------------------
# Incremental-solve score/feasibility cache (docs/perf.md "incremental
# solve"): a device-resident per-node summary of the score plane, kept
# coherent with the resident NodeTable by the SAME full-vs-delta
# discipline (SchedulerCache maintains it right where it maintains the
# snapshot — full rebuilds recompute it wholesale, delta cycles patch
# exactly the scattered rows with a donated scatter, clean cycles touch
# nothing). The restricted solve then picks its candidate node columns
# from this cached plane in O(N log C) instead of re-scoring the full
# (P, N) plane: clean columns are REUSED across cycles; only dirty
# columns (bind/delete/update-touched nodes) were recomputed.
# ---------------------------------------------------------------------------

from typing import NamedTuple


class NodeSummary(NamedTuple):
    """The cached per-node slice of the score/feasibility plane.

    ``eligible`` — the pod-independent feasibility column: node valid,
    schedulable, condition-clean (when the Policy enforces the
    condition predicates), and with at least one free pod slot. The
    pod-CONDITIONED predicate residual (selectors, taints, resources
    against the actual request) is re-evaluated by the restricted solve
    itself on the gathered candidate columns — this column only decides
    which columns are worth gathering.

    ``rank`` — the candidate ranking score (generic lean objective over
    free-capacity fractions; sign flipped under a packing objective).
    Ineligible columns carry ``-inf`` so they can never out-rank a live
    one."""

    eligible: jnp.ndarray  # (N,) bool
    rank: jnp.ndarray  # (N,) f32, -inf on ineligible columns


#: rank boost that guarantees dirty columns survive the top-k cut —
#: finite (padding-safe) but far above any free-fraction rank in [0, 1]
DIRTY_BOOST = 1e6

#: rank boost for group-hinted columns (a gang's home-slice columns, a
#: scenario pack's candidate hint): guaranteed a slot ahead of every
#: plain rank but BELOW the dirty boost — the churn frontier always
#: wins the quota contest (docs/perf.md "Sparsity-first solve")
HINT_BOOST = 1e5

_NEG = -3e38  # ineligible-column rank (finite: top_k handles -inf fine,
# but a finite sentinel keeps the padded-index arithmetic NaN-free)


@functools.partial(jax.jit, static_argnames=("honor_conditions",
                                             "prefer_packed"))
def node_summary(nodes, honor_conditions=True, prefer_packed=False):
    """Compute the per-node summary from a DeviceNodes table (full
    rebuild) or from a delta sub-table (whose rows then scatter in via
    :func:`patch_node_summary`). One streaming pass over the (N, R)
    usage columns and the (N,) condition bits; no (P, N) work.

    ``honor_conditions`` mirrors whether the Policy enforces the node
    condition predicates — when it does not, pressured/not-ready nodes
    stay candidate-eligible exactly as the cold solve would admit them.
    ``prefer_packed`` flips the ranking for packing-style objectives
    (MostRequestedPriority outweighing LeastRequested): fullest-first
    instead of freest-first."""
    from kubernetes_tpu.snapshot import RES_CPU, RES_MEM, RES_PODS

    free = nodes.allocatable - nodes.requested  # (N, R)
    eligible = nodes.valid
    if honor_conditions:
        eligible = (eligible & nodes.schedulable & nodes.ready
                    & ~nodes.network_unavailable & ~nodes.mem_pressure
                    & ~nodes.disk_pressure & ~nodes.pid_pressure)
    # a column with no free pod slot cannot admit anything this cycle —
    # not worth a candidate slot even under a packing objective
    eligible = eligible & (free[:, RES_PODS] >= 1.0)

    def frac(col):
        cap = nodes.allocatable[:, col]
        return jnp.where(cap > 0, jnp.maximum(free[:, col], 0.0)
                         / jnp.maximum(cap, 1e-30), 0.0)

    rank = 0.5 * (frac(RES_CPU) + frac(RES_MEM))
    if prefer_packed:
        rank = 1.0 - rank
    return NodeSummary(eligible=eligible,
                       rank=jnp.where(eligible, rank, _NEG))


@functools.partial(jax.jit, donate_argnums=(0,))
def _patch_node_summary_donated(summary, sub, idx):
    """Scatter delta rows into the resident summary — the same donated
    single-scatter discipline as ops/arrays._scatter_node_rows_donated
    (XLA aliases the output onto the existing buffers, preserving the
    resident sharding on a mesh; padded idx slots point out of bounds
    and drop)."""
    return NodeSummary(
        eligible=summary.eligible.at[idx].set(sub.eligible, mode="drop"),
        rank=summary.rank.at[idx].set(sub.rank, mode="drop"),
    )


def patch_node_summary(summary, sub, idx):
    """Jitted row-patch entry: ``idx`` (D,) host indices aligned with
    ``sub``'s rows; entries >= the resident row count drop (padding).
    The resident ``summary``'s buffers are donated — do not reuse."""
    return _patch_node_summary_donated(summary, sub,
                                       jnp.asarray(idx, jnp.int32))


def _candidate_score(summary, dirty_mask, hint_mask):
    """The shared candidate ranking: plain rank + the dirty-frontier
    boost + (optionally) the group-quota hint boost. Hinted ineligible
    columns stay at the sentinel — a quota can widen the cut, never
    resurrect a dead column."""
    score = summary.rank + jnp.where(dirty_mask & summary.eligible,
                                     DIRTY_BOOST, 0.0)
    if hint_mask is not None:
        score = score + jnp.where(hint_mask & summary.eligible,
                                  HINT_BOOST, 0.0)
    return score


def _merge_local_topk(vals, idx, k):
    """Replicated merge of the per-shard winners: lexicographic sort by
    (value desc, global index asc) over the (shards * k,) pool, take
    the first k. The index tie-break matches ``jax.lax.top_k``'s
    (lower index first), which is what makes the sharded pick
    bit-identical to the single-pass one."""
    neg, sidx = jax.lax.sort((jnp.negative(vals.reshape(-1)),
                              idx.reshape(-1)), num_keys=2)
    return jnp.negative(neg[:k]), sidx[:k]


def _sharded_topk(score, k, num_shards):
    """Top-``k`` of a (N,) score plane, mesh-shardable: ``num_shards >
    1`` selects the two-stage pick — the plane reshapes to (S, N/S), a
    zero-collective VIEW of the node-sharded resident layout (each row
    one shard's contiguous block), each shard top-k's LOCALLY, and only
    the (S, k) winner frame merges replicated
    (:func:`_merge_local_topk`). The global top-k set can take at most
    k entries from any one shard, and both stages break ties on the
    lower global index, so the result is BIT-IDENTICAL to the
    single-pass pick on any shard count — the mesh-parity contract the
    fuzz suite pins. A dense (S, N) or (P, N) plane never
    materializes. Shapes that cannot shard evenly (or k too large for
    a lossless local pick) take the single-pass path."""
    n = score.shape[0]
    if num_shards > 1 and n % num_shards == 0 and k <= n // num_shards:
        local = n // num_shards
        lvals, lidx = jax.lax.top_k(score.reshape(num_shards, local), k)
        offs = (jnp.arange(num_shards, dtype=jnp.int32) * local)[:, None]
        return _merge_local_topk(lvals, lidx.astype(jnp.int32) + offs, k)
    return jax.lax.top_k(score, k)


@functools.partial(jax.jit,
                   static_argnames=("k", "num_shards", "hint_quota"))
def candidate_columns(summary, dirty_mask, k, hint_mask=None,
                      num_shards=1, hint_quota=0):
    """Top-``k`` candidate node columns for the restricted solve: the
    best-ranked eligible columns, with every DIRTY eligible column
    (bind/delete/update-touched this cycle — the churn frontier)
    guaranteed a slot via a rank boost, and every HINTED eligible
    column (a gang's home-slice quota, a scenario pack's candidate
    hint) a slot right behind it. O(N log k), the only full-N work an
    incremental cycle performs. Returns (k,) int32 column indices;
    slots that fell on ineligible columns point one past the table
    (== N) so downstream gathers treat them as padding.

    ``hint_quota > 0`` switches the hint from a boost to a RESERVED
    SPLIT: the first ``hint_quota`` slots hold the top hinted columns
    (dirty boost still applies within the segment), the remaining
    ``k - hint_quota`` hold the top UNHINTED columns — disjoint by
    construction, so a large hint set (a whole home slice) can never
    crowd plain-ranked candidates out of the frame. Quota slots a
    too-small hint set cannot fill come out as padding sentinels
    (harmless: gathered rows reject every predicate).

    The pick shards on the mesh via :func:`_sharded_topk` — per-shard
    local top-k, replicated merge of the (S, k) winners, bit-identical
    to single-pass on any shard count."""
    n = summary.rank.shape[0]
    if hint_mask is not None and 0 < hint_quota < k:
        base = _candidate_score(summary, dirty_mask, None)
        hv, hi = _sharded_topk(jnp.where(hint_mask, base, _NEG),
                               hint_quota, num_shards)
        uv, ui = _sharded_topk(jnp.where(hint_mask, _NEG, base),
                               k - hint_quota, num_shards)
        vals = jnp.concatenate([hv, uv])
        idx = jnp.concatenate([hi, ui])
    else:
        score = _candidate_score(summary, dirty_mask, hint_mask)
        vals, idx = _sharded_topk(score, k, num_shards)
    return jnp.where(vals > _NEG / 2, idx.astype(jnp.int32),
                     jnp.int32(n))


@functools.partial(jax.jit,
                   static_argnames=("n_blocks", "block_width",
                                    "num_shards"))
def partition_columns(summary, dirty_mask, n_blocks, block_width,
                      num_shards=1):
    """Capacity-balanced column blocks for the PARTITIONED COLD solve
    (docs/perf.md "Sparsity-first solve"): take the top
    ``n_blocks * block_width`` columns by rank (one sharded top-k —
    still nothing (P, N)-shaped) and deal them round-robin into
    ``n_blocks`` blocks of ``block_width`` columns each. Two things
    follow from the shape choice:

    - ``block_width`` is the restricted path's candidate bucket C, so
      every block solves through the ALREADY-COMPILED (P, C)
      restricted program — a partitioned cold cycle adds zero new
      solver shapes (the zero-retrace contract);
    - the round-robin deal balances capacity: block b holds ranks
      b, b+B, b+2B, ... so every block spans the rank spectrum and
      block 0 owns the single best column — the first block solve
      places most of a cold batch on an uncontended frame.

    Cold cost stops scaling linearly with N: O(N log(B·C)) selection
    plus B fixed-size (P, C) solves, vs the dense solve's O(P·N)
    plane. Ineligible columns map to the padding sentinel (== N)
    exactly like :func:`candidate_columns` slots. Returns
    (n_blocks, block_width) int32."""
    n = summary.rank.shape[0]
    score = _candidate_score(summary, dirty_mask, None)
    vals, order = _sharded_topk(score, n_blocks * block_width, num_shards)
    idx = jnp.where(vals > _NEG / 2, order.astype(jnp.int32),
                    jnp.int32(n))
    return idx.reshape(block_width, n_blocks).T
