"""Device-side array bundles (jit-able pytrees) mirroring the host columnar
tables, padded to power-of-two row buckets so XLA shapes stay stable as the
cluster and pending queue grow/shrink (SURVEY.md §7.3.6 — the bucketing
policy that avoids recompilation storms the way the reference avoids
re-listing via incremental snapshots, cache.go:211)."""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.snapshot import (
    NodeTable,
    PodTable,
    SelectorTables,
    TopologyTables,
    VolumeTables,
)
from kubernetes_tpu.utils.interner import bucket_size


class DeviceNodes(NamedTuple):
    """Padded columnar NodeInfo on device. Rows >= n_valid are padding and
    are marked unschedulable so every predicate rejects them."""

    valid: jnp.ndarray  # (N,) bool
    name_id: jnp.ndarray  # (N,) i32
    allocatable: jnp.ndarray  # (N, R) f32
    requested: jnp.ndarray  # (N, R) f32
    nonzero_req: jnp.ndarray  # (N, 2) f32
    pair_mh: jnp.ndarray  # (N, Up) f32 (f32 so memberships ride the MXU)
    key_mh: jnp.ndarray  # (N, Uk) f32
    key_val: jnp.ndarray  # (N, Uk) f32
    key_num: jnp.ndarray  # (N, Uk) f32 — 1 when label parsed as integer
    taint_hard_mh: jnp.ndarray  # (N, Ut) f32
    taint_soft_mh: jnp.ndarray  # (N, Ut) f32
    port_any_mh: jnp.ndarray  # (N, Upp) f32
    port_wild_mh: jnp.ndarray  # (N, Upp) f32
    port_spec_mh: jnp.ndarray  # (N, Upip) f32
    image_mh: jnp.ndarray  # (N, Ui) f32
    #: (N, Ob) f32 — per-node counts of the solve's pod batch's owner
    #: columns (PodTable.owner_cols), set per batch by
    #: attach_owner_columns; (N, 0) on a table no batch was attached to
    owner_counts: jnp.ndarray
    zone_id: jnp.ndarray  # (N,) i32
    zone_valid: jnp.ndarray  # (Z,) bool — static shape = padded zone count
    avoid_mh: jnp.ndarray  # (N, Uu) f32
    ready: jnp.ndarray  # (N,) bool
    network_unavailable: jnp.ndarray  # (N,) bool
    schedulable: jnp.ndarray  # (N,) bool
    mem_pressure: jnp.ndarray  # (N,) bool
    disk_pressure: jnp.ndarray  # (N,) bool
    pid_pressure: jnp.ndarray  # (N,) bool
    topo_pair_id: jnp.ndarray  # (N, K) i32 — -1 = key absent
    matcher_counts: jnp.ndarray  # (N, M) f32
    anti_counts: jnp.ndarray  # (N, Ua) f32
    sym_counts: jnp.ndarray  # (N, Us) f32
    aff_pod_count: jnp.ndarray  # (N,) f32
    vol_any_mh: jnp.ndarray  # (N, Uv) f32
    vol_rw_mh: jnp.ndarray  # (N, Uv) f32
    pd_mh: jnp.ndarray  # (N, Uvd) f32
    pd_limit: jnp.ndarray  # (N, 4) f32
    csi_mh: jnp.ndarray  # (N, Uvc) f32
    csi_limit: jnp.ndarray  # (N, Dc) f32 — +inf = no limit
    has_zone_label: jnp.ndarray  # (N,) bool

    @property
    def n(self) -> int:
        return self.name_id.shape[0]


class DevicePods(NamedTuple):
    valid: jnp.ndarray  # (P,) bool
    req: jnp.ndarray  # (P, R) f32
    nonzero_req: jnp.ndarray  # (P, 2) f32
    selprog_id: jnp.ndarray  # (P,) i32
    prefprog_id: jnp.ndarray  # (P,) i32
    tolset_id: jnp.ndarray  # (P,) i32
    name_req: jnp.ndarray  # (P,) i32
    priority: jnp.ndarray  # (P,) i32
    port_wild_pp: jnp.ndarray  # (P, Upp) f32
    port_spec_pp: jnp.ndarray  # (P, Upp) f32
    port_spec_pip: jnp.ndarray  # (P, Upip) f32
    image_mh: jnp.ndarray  # (P, Ui) f32
    owner_id: jnp.ndarray  # (P,) i32 — column of owner_counts, -1 none
    owner_uid_id: jnp.ndarray  # (P,) i32
    owner_match_mh: jnp.ndarray  # (P, Ob) f32
    order: jnp.ndarray  # (P,) i32
    matcher_mh: jnp.ndarray  # (P, M) f32
    affprog_id: jnp.ndarray  # (P,) i32
    prefaffprog_id: jnp.ndarray  # (P,) i32
    spread_hard_id: jnp.ndarray  # (P,) i32
    spread_soft_id: jnp.ndarray  # (P,) i32
    self_aff_match: jnp.ndarray  # (P,) bool
    anti_term_mh: jnp.ndarray  # (P, Ua) f32
    sym_term_mh: jnp.ndarray  # (P, Us) f32
    has_aff: jnp.ndarray  # (P,) bool
    vol_any_mh: jnp.ndarray  # (P, Uv) f32
    vol_rw_mh: jnp.ndarray  # (P, Uv) f32
    pd_mh: jnp.ndarray  # (P, Uvd) f32
    csi_mh: jnp.ndarray  # (P, Uvc) f32
    vol_error: jnp.ndarray  # (P,) bool
    limits: jnp.ndarray  # (P, 2) f32 cpu/mem limits

    @property
    def n(self) -> int:
        return self.selprog_id.shape[0]


class DeviceSelectors(NamedTuple):
    """Flattened selector programs + toleration tables. Padded rows carry
    explicit valid masks; AND/OR segment reductions use neutral fills."""

    expr_valid: jnp.ndarray  # (E,) bool
    expr_term: jnp.ndarray  # (E,) i32
    expr_op: jnp.ndarray  # (E,) i32
    expr_pairs_mh: jnp.ndarray  # (E, Up) f32
    expr_key: jnp.ndarray  # (E,) i32
    expr_lit: jnp.ndarray  # (E,) f32
    term_valid: jnp.ndarray  # (T,) bool
    term_prog: jnp.ndarray  # (T,) i32
    p_expr_valid: jnp.ndarray
    p_expr_term: jnp.ndarray
    p_expr_op: jnp.ndarray
    p_expr_pairs_mh: jnp.ndarray
    p_expr_key: jnp.ndarray
    p_expr_lit: jnp.ndarray
    p_term_valid: jnp.ndarray
    p_term_prog: jnp.ndarray
    p_term_weight: jnp.ndarray  # (Tp,) f32
    tol_hard_mh: jnp.ndarray  # (S, Ut) f32
    tol_soft_mh: jnp.ndarray  # (S, Ut) f32
    image_sizes: jnp.ndarray  # (Ui,) f32
    # program-count masks: their STATIC shapes carry the padded program
    # counts into segment reductions (ints in a pytree would be traced).
    prog_valid: jnp.ndarray  # (G,) bool
    p_prog_valid: jnp.ndarray  # (Gp,) bool


class DeviceTopology(NamedTuple):
    """Padded inter-pod-affinity / topology-spread term tables. Row tables
    carry valid masks; padded rows point their ``*_prog`` at the dump
    program (index = padded program count) so segment reductions stay
    neutral. ``*_m_onehot`` matrices turn matcher-id gathers into MXU
    matmuls against the (N, M) / (P, M) count matrices."""

    pair_valid: jnp.ndarray  # (Utp,) bool
    # required (anti)affinity rows
    ra_valid: jnp.ndarray  # (Ta,) bool
    ra_prog: jnp.ndarray  # (Ta,) i32 — pad rows -> Ga (dump)
    ra_key: jnp.ndarray  # (Ta,) i32
    ra_m_onehot: jnp.ndarray  # (Ta, M) f32
    ra_anti: jnp.ndarray  # (Ta,) bool
    ga_valid: jnp.ndarray  # (Ga,) bool
    # preferred rows
    rp_valid: jnp.ndarray
    rp_prog: jnp.ndarray
    rp_key: jnp.ndarray
    rp_m_onehot: jnp.ndarray
    rp_w: jnp.ndarray  # (Tp,) f32 signed, pad 0
    gp_valid: jnp.ndarray  # (Gp,) bool
    # anti-term columns
    at_key: jnp.ndarray  # (Ua,) i32
    at_m_onehot: jnp.ndarray  # (Ua, M) f32
    # sym-term columns
    st_key: jnp.ndarray  # (Us,) i32
    st_m_onehot: jnp.ndarray  # (Us, M) f32
    st_w: jnp.ndarray  # (Us,) f32
    st_hard: jnp.ndarray  # (Us,) f32
    # spread hard
    sh_valid: jnp.ndarray  # (Tsh,) bool
    sh_prog: jnp.ndarray  # (Tsh,) i32 — pad -> Gsh
    sh_key: jnp.ndarray
    sh_m_onehot: jnp.ndarray  # (Tsh, M)
    sh_skew: jnp.ndarray  # (Tsh,) f32
    shp_selprog: jnp.ndarray  # (Gsh,) i32, -1 = unconstrained
    shp_valid: jnp.ndarray  # (Gsh,) bool
    # spread soft
    ss_valid: jnp.ndarray
    ss_prog: jnp.ndarray
    ss_key: jnp.ndarray
    ss_m_onehot: jnp.ndarray
    ssp_selprog: jnp.ndarray
    ssp_valid: jnp.ndarray


class DeviceVolumes(NamedTuple):
    """Volume-constraint tables: universe metadata (token kinds/escapes)
    plus this batch's VolumeZone rows and VolumeBinding CNF clauses."""

    conflict_escape: jnp.ndarray  # (Uv,) f32
    pd_type_onehot: jnp.ndarray  # (Uvd, 4) f32
    csi_driver_onehot: jnp.ndarray  # (Uvc, Dc) f32
    vz_valid: jnp.ndarray  # (Rv,) bool
    vz_pod: jnp.ndarray  # (Rv,) i32 — pad rows -> 0 with valid False
    vz_pairs_mh: jnp.ndarray  # (Rv, Up) f32
    vb_row_valid: jnp.ndarray  # (Rb,) bool
    vb_row_clause: jnp.ndarray  # (Rb,) i32
    vb_row_prog: jnp.ndarray  # (Rb,) i32
    vb_clause_valid: jnp.ndarray  # (Cb,) bool
    vb_clause_pod: jnp.ndarray  # (Cb,) i32
    vb_clause_bound: jnp.ndarray  # (Cb,) bool


def _pad_rows(a: np.ndarray, rows: int, fill=0) -> np.ndarray:
    if a.shape[0] == rows:
        return a
    out = np.full((rows,) + a.shape[1:], fill, a.dtype)
    out[: a.shape[0]] = a
    return out


def nodes_to_device(t: NodeTable, pad_to: int | None = None,
                    owner_counts: np.ndarray | None = None) -> DeviceNodes:
    """Upload a host NodeTable. ``owner_counts`` (t.n, Ob) are the owner
    columns of the pod batch this table will be solved with
    (SnapshotPacker.pack_owner_columns / SchedulerCache.owner_columns);
    without them the table carries none, and a batch that spreads by an
    owner must be attached with :func:`attach_owner_columns`."""
    n_pad = pad_to or bucket_size(max(t.n, 1))
    valid = np.zeros((n_pad,), bool)
    valid[: t.n] = True
    f32 = lambda a: jnp.asarray(_pad_rows(a.astype(np.float32), n_pad))
    if owner_counts is None:
        owner_counts = np.zeros((t.n, 0), np.float32)
    return DeviceNodes(
        valid=jnp.asarray(valid),
        name_id=jnp.asarray(_pad_rows(t.name_id, n_pad, -1)),
        allocatable=f32(t.allocatable),
        requested=f32(t.requested),
        nonzero_req=f32(t.nonzero_req),
        pair_mh=f32(t.pair_mh),
        key_mh=f32(t.key_mh),
        key_val=f32(t.key_val),
        key_num=f32(t.key_num),
        taint_hard_mh=f32(t.taint_hard_mh),
        taint_soft_mh=f32(t.taint_soft_mh),
        port_any_mh=f32(t.port_any_mh),
        port_wild_mh=f32(t.port_wild_mh),
        port_spec_mh=f32(t.port_spec_mh),
        image_mh=f32(t.image_mh),
        owner_counts=f32(owner_counts),
        zone_id=jnp.asarray(_pad_rows(t.zone_id, n_pad, -1)),
        zone_valid=jnp.asarray(t.zone_valid),
        avoid_mh=f32(t.avoid_mh),
        ready=jnp.asarray(_pad_rows(t.ready, n_pad, False)),
        network_unavailable=jnp.asarray(_pad_rows(t.network_unavailable, n_pad, True)),
        schedulable=jnp.asarray(_pad_rows(t.schedulable, n_pad, False)),
        mem_pressure=jnp.asarray(_pad_rows(t.mem_pressure, n_pad, True)),
        disk_pressure=jnp.asarray(_pad_rows(t.disk_pressure, n_pad, True)),
        pid_pressure=jnp.asarray(_pad_rows(t.pid_pressure, n_pad, True)),
        topo_pair_id=jnp.asarray(_pad_rows(t.topo_pair_id, n_pad, -1)),
        matcher_counts=f32(t.matcher_counts),
        anti_counts=f32(t.anti_counts),
        sym_counts=f32(t.sym_counts),
        aff_pod_count=f32(t.aff_pod_count),
        vol_any_mh=f32(t.vol_any_mh),
        vol_rw_mh=f32(t.vol_rw_mh),
        pd_mh=f32(t.pd_mh),
        pd_limit=jnp.asarray(_pad_rows(t.pd_limit.astype(np.float32), n_pad, 0.0)),
        csi_mh=f32(t.csi_mh),
        csi_limit=jnp.asarray(
            _pad_rows(t.csi_limit.astype(np.float32), n_pad, np.inf)
        ),
        has_zone_label=jnp.asarray(_pad_rows(t.has_zone_label, n_pad, False)),
    )


def pods_to_device(t: PodTable, pad_to: int | None = None) -> DevicePods:
    p_pad = pad_to or bucket_size(max(t.n, 1))
    valid = np.zeros((p_pad,), bool)
    valid[: t.n] = True
    f32 = lambda a: jnp.asarray(_pad_rows(a.astype(np.float32), p_pad))
    i32 = lambda a, fill=-1: jnp.asarray(_pad_rows(a, p_pad, fill))
    return DevicePods(
        valid=jnp.asarray(valid),
        req=f32(t.req),
        nonzero_req=f32(t.nonzero_req),
        selprog_id=i32(t.selprog_id),
        prefprog_id=i32(t.prefprog_id),
        tolset_id=i32(t.tolset_id),
        name_req=i32(t.name_req),
        priority=i32(t.priority, 0),
        port_wild_pp=f32(t.port_wild_pp),
        port_spec_pp=f32(t.port_spec_pp),
        port_spec_pip=f32(t.port_spec_pip),
        image_mh=f32(t.image_mh),
        owner_id=i32(t.owner_id),
        owner_uid_id=i32(t.owner_uid_id),
        owner_match_mh=f32(t.owner_match_mh),
        order=i32(t.order, -1),
        matcher_mh=f32(t.matcher_mh),
        affprog_id=i32(t.affprog_id),
        prefaffprog_id=i32(t.prefaffprog_id),
        spread_hard_id=i32(t.spread_hard_id),
        spread_soft_id=i32(t.spread_soft_id),
        self_aff_match=jnp.asarray(_pad_rows(t.self_aff_match, p_pad, False)),
        anti_term_mh=f32(t.anti_term_mh),
        sym_term_mh=f32(t.sym_term_mh),
        has_aff=jnp.asarray(_pad_rows(t.has_aff, p_pad, False)),
        vol_any_mh=f32(t.vol_any_mh),
        vol_rw_mh=f32(t.vol_rw_mh),
        pd_mh=f32(t.pd_mh),
        csi_mh=f32(t.csi_mh),
        vol_error=jnp.asarray(_pad_rows(t.vol_error, p_pad, False)),
        limits=f32(t.limits),
    )


def attach_owner_columns(nodes: DeviceNodes,
                         counts: np.ndarray) -> DeviceNodes:
    """``nodes`` with its owner columns replaced by host ``counts``
    (rows <= N, Ob): padded to the node axis and placed like the
    table's other (N, ·) arrays (sharded along the mesh's node axis
    when the table is). The resident table itself is untouched."""
    n = nodes.requested.shape[0]
    a = _pad_rows(counts.astype(np.float32, copy=False), n)
    sharding = getattr(nodes.requested, "sharding", None)
    return nodes._replace(owner_counts=(jax.device_put(a, sharding)
                                        if sharding is not None
                                        else jnp.asarray(a)))


#: DeviceNodes fields that are NOT (N,)-row-shaped and therefore must not
#: be row-scattered by the delta patch: ``valid`` is resident state (row
#: membership only changes on full rebuilds), ``zone_valid`` is
#: universe-shaped and is refreshed wholesale from the delta pack.
_NODE_NON_ROW_FIELDS = ("valid", "zone_valid")


@partial(jax.jit, donate_argnums=(0,))
def _scatter_node_rows_donated(resident: DeviceNodes, sub: DeviceNodes,
                               idx: jnp.ndarray) -> DeviceNodes:
    """Patch dirty rows of the resident device NodeTable in place.

    ``sub`` carries the re-packed rows (any padding rows beyond the real
    dirty count point their ``idx`` out of bounds and XLA ``mode="drop"``
    discards them); ``resident`` is donated so XLA aliases the output
    onto the existing buffers — the steady-state cycle never reallocates
    or re-uploads the full table. The caller (SchedulerCache) is the sole
    owner of the resident arrays, which is what makes donation safe."""
    out = {}
    with jax.named_scope("delta_scatter"):
        for name in DeviceNodes._fields:
            if name in _NODE_NON_ROW_FIELDS:
                continue
            r = getattr(resident, name)
            s = getattr(sub, name)
            out[name] = r.at[idx].set(s, mode="drop")
    return DeviceNodes(valid=resident.valid, zone_valid=sub.zone_valid,
                       **out)


def scatter_node_rows(resident: DeviceNodes, sub: DeviceNodes,
                      idx: np.ndarray) -> DeviceNodes:
    """Jitted row-scatter entry: ``idx`` (D,) host indices aligned with
    ``sub``'s rows; entries >= resident row count are dropped (padding).
    Returns the patched DeviceNodes; the resident argument's buffers are
    donated and must not be used afterwards."""
    return _scatter_node_rows_donated(resident, sub,
                                      jnp.asarray(idx, jnp.int32))


@jax.jit
def gather_node_rows(nodes: DeviceNodes, idx: jnp.ndarray) -> DeviceNodes:
    """The restricted solve's candidate-column view: gather ``idx``
    (C,) node rows out of the (possibly mesh-resident) table into a
    small (C, ·) DeviceNodes the existing solver kernels run on
    unchanged. Out-of-range indices (the candidate_columns padding
    sentinel == N) fill with zeros — ``valid`` fills False, so padded
    rows reject every predicate exactly like bucket-padding rows do.
    ``zone_valid`` is universe-shaped and passes through whole. The
    output is answer-sized (C ≤ the candidate bucket), so under a mesh
    the implied cross-shard gather moves O(C·R) bytes, never the
    (P, N) plane — the readback-budget contract holds."""
    out = {}
    for name in DeviceNodes._fields:
        a = getattr(nodes, name)
        if name == "zone_valid":
            out[name] = a
            continue
        out[name] = jnp.take(a, idx, axis=0, mode="fill", fill_value=0)
    return DeviceNodes(**out)


@partial(jax.jit, static_argnames=("k", "num_shards", "hint_quota"))
def gather_candidates(summary, dirty_mask: jnp.ndarray,
                      nodes: DeviceNodes, k: int, hint_mask=None,
                      num_shards: int = 1, hint_quota: int = 0):
    """Fused candidate pick + row gather — ONE dispatch for the
    restricted solve's column selection (ops/fused_score.
    candidate_columns composed with :func:`gather_node_rows`; separate
    dispatches measurably tax small-cluster cycles on CPU). Returns
    ``(cand_idx, sub_nodes)``. ``hint_mask`` reserves group-quota
    columns (gang home slices / pack hints) — with ``hint_quota > 0``
    as a reserved split capped at quota slots; ``num_shards > 1`` takes
    the mesh-sharded two-stage pick — per-shard local top-k, then a
    replicated merge of only the (S, k) winner frame, never a dense
    plane (bit-identical to the single-pass pick on any shard
    count)."""
    from kubernetes_tpu.ops.fused_score import candidate_columns

    cand = candidate_columns(summary, dirty_mask, k, hint_mask,
                             num_shards, hint_quota)
    return cand, gather_node_rows(nodes, cand)


@jax.jit
def map_restricted_assignment(assigned_local: jnp.ndarray,
                              cand_idx: jnp.ndarray) -> jnp.ndarray:
    """Candidate-local assignment rows -> global node rows, on device:
    the mapped vector rides the cycle's single solve-result readback so
    the candidate index list itself never crosses the host boundary
    (keeping d2h at the answer-sized ~4 B/pod budget)."""
    safe = jnp.clip(assigned_local, 0, cand_idx.shape[0] - 1)
    return jnp.where(assigned_local >= 0,
                     cand_idx[safe].astype(jnp.int32), jnp.int32(-1))


def selectors_to_device(t: SelectorTables) -> DeviceSelectors:
    def pack(n_e, n_t, e_term, e_op, e_pairs, e_key, e_lit, t_prog, t_w=None):
        e_pad = bucket_size(max(n_e, 1))
        t_pad = bucket_size(max(n_t, 1))
        ev = np.zeros((e_pad,), bool)
        ev[:n_e] = True
        tv = np.zeros((t_pad,), bool)
        tv[:n_t] = True
        out = dict(
            expr_valid=jnp.asarray(ev),
            expr_term=jnp.asarray(_pad_rows(e_term, e_pad, 0)),
            expr_op=jnp.asarray(_pad_rows(e_op, e_pad, 0)),
            expr_pairs_mh=jnp.asarray(_pad_rows(e_pairs.astype(np.float32), e_pad)),
            expr_key=jnp.asarray(_pad_rows(e_key, e_pad, -1)),
            expr_lit=jnp.asarray(_pad_rows(e_lit, e_pad, 0.0)),
            term_valid=jnp.asarray(tv),
            term_prog=jnp.asarray(_pad_rows(t_prog, t_pad, 0)),
        )
        if t_w is not None:
            out["term_weight"] = jnp.asarray(_pad_rows(t_w, t_pad, 0.0))
        return out

    r = pack(t.n_exprs, t.n_terms, t.expr_term, t.expr_op, t.expr_pairs_mh,
             t.expr_key, t.expr_lit, t.term_prog)
    p = pack(t.p_n_exprs, t.p_n_terms, t.p_expr_term, t.p_expr_op,
             t.p_expr_pairs_mh, t.p_expr_key, t.p_expr_lit, t.p_term_prog,
             t.p_term_weight)
    s_pad = bucket_size(max(t.tol_hard_mh.shape[0], 1))
    return DeviceSelectors(
        expr_valid=r["expr_valid"],
        expr_term=r["expr_term"],
        expr_op=r["expr_op"],
        expr_pairs_mh=r["expr_pairs_mh"],
        expr_key=r["expr_key"],
        expr_lit=r["expr_lit"],
        term_valid=r["term_valid"],
        term_prog=r["term_prog"],
        p_expr_valid=p["expr_valid"],
        p_expr_term=p["expr_term"],
        p_expr_op=p["expr_op"],
        p_expr_pairs_mh=p["expr_pairs_mh"],
        p_expr_key=p["expr_key"],
        p_expr_lit=p["expr_lit"],
        p_term_valid=p["term_valid"],
        p_term_prog=p["term_prog"],
        p_term_weight=p["term_weight"],
        tol_hard_mh=jnp.asarray(_pad_rows(t.tol_hard_mh.astype(np.float32), s_pad)),
        tol_soft_mh=jnp.asarray(_pad_rows(t.tol_soft_mh.astype(np.float32), s_pad)),
        image_sizes=jnp.asarray(t.image_sizes),
        prog_valid=jnp.asarray(
            _pad_rows(np.ones((t.n_progs,), bool), bucket_size(max(t.n_progs, 1)), False)
        ),
        p_prog_valid=jnp.asarray(
            _pad_rows(np.ones((t.p_n_progs,), bool), bucket_size(max(t.p_n_progs, 1)), False)
        ),
    )


def volumes_to_device(t: VolumeTables) -> DeviceVolumes:
    from kubernetes_tpu.volumes import N_PD_FILTERS

    def onehot(idx: np.ndarray, width: int) -> jnp.ndarray:
        oh = np.zeros((len(idx), width), np.float32)
        if len(idx):
            oh[np.arange(len(idx)), np.clip(idx, 0, width - 1)] = 1.0
        return jnp.asarray(oh)

    def valid(n: int, rows: int) -> jnp.ndarray:
        v = np.zeros((rows,), bool)
        v[:n] = True
        return jnp.asarray(v)

    Rv = bucket_size(max(t.vz_n_rows, 1), 4)
    Rb = bucket_size(max(t.vb_n_rows, 1), 4)
    Cb = bucket_size(max(t.vb_n_clauses, 1), 4)
    Dc = bucket_size(max(t.n_csi_drivers, 1), 4)
    return DeviceVolumes(
        conflict_escape=jnp.asarray(t.conflict_escape),
        pd_type_onehot=onehot(t.pd_type, N_PD_FILTERS),
        csi_driver_onehot=onehot(t.csi_driver, Dc),
        vz_valid=valid(t.vz_n_rows, Rv),
        vz_pod=jnp.asarray(_pad_rows(t.vz_pod, Rv, 0)),
        vz_pairs_mh=jnp.asarray(_pad_rows(t.vz_pairs_mh.astype(np.float32), Rv)),
        vb_row_valid=valid(t.vb_n_rows, Rb),
        vb_row_clause=jnp.asarray(_pad_rows(t.vb_row_clause, Rb, 0)),
        vb_row_prog=jnp.asarray(_pad_rows(t.vb_row_prog, Rb, 0)),
        vb_clause_valid=valid(t.vb_n_clauses, Cb),
        vb_clause_pod=jnp.asarray(_pad_rows(t.vb_clause_pod, Cb, 0)),
        vb_clause_bound=jnp.asarray(_pad_rows(t.vb_clause_bound, Cb, False)),
    )


def topology_to_device(t: TopologyTables) -> DeviceTopology:
    M = t.n_matchers

    def onehot(m_idx: np.ndarray, rows: int) -> jnp.ndarray:
        # negative ids (padding) get an all-zero row, NOT a clipped alias
        # of matcher 0 — the pm_* matmuls are the only validity gate the
        # at/st tables have
        oh = np.zeros((rows, M), np.float32)
        ok = np.asarray(m_idx) >= 0  # graftlint: disable=R7 -- host pack input, never a device value
        r = np.arange(len(m_idx))[ok]
        if len(r):
            oh[r, np.clip(np.asarray(m_idx)[ok], 0, M - 1)] = 1.0  # graftlint: disable=R7 -- host pack input
        return jnp.asarray(oh)

    def valid(n: int, rows: int) -> jnp.ndarray:
        v = np.zeros((rows,), bool)
        v[:n] = True
        return jnp.asarray(v)

    Ta = bucket_size(max(t.ra_n_rows, 1), 4)
    Ga = bucket_size(max(t.ra_n_progs, 1), 4)
    Tp = bucket_size(max(t.rp_n_rows, 1), 4)
    Gp = bucket_size(max(t.rp_n_progs, 1), 4)
    Tsh = bucket_size(max(t.sh_n_rows, 1), 4)
    Gsh = bucket_size(max(t.sh_n_progs, 1), 4)
    Tss = bucket_size(max(t.ss_n_rows, 1), 4)
    Gss = bucket_size(max(t.ss_n_progs, 1), 4)
    n_pairs_pad = bucket_size(max(t.n_pairs, 1))
    i32 = lambda a, rows, fill: jnp.asarray(_pad_rows(a, rows, fill))
    return DeviceTopology(
        pair_valid=valid(t.n_pairs, n_pairs_pad),
        ra_valid=valid(t.ra_n_rows, Ta),
        ra_prog=i32(t.ra_prog, Ta, Ga),
        ra_key=i32(t.ra_key, Ta, 0),
        ra_m_onehot=onehot(_pad_rows(t.ra_m, Ta, 0), Ta),
        ra_anti=jnp.asarray(_pad_rows(t.ra_anti, Ta, False)),
        ga_valid=valid(t.ra_n_progs, Ga),
        rp_valid=valid(t.rp_n_rows, Tp),
        rp_prog=i32(t.rp_prog, Tp, Gp),
        rp_key=i32(t.rp_key, Tp, 0),
        rp_m_onehot=onehot(_pad_rows(t.rp_m, Tp, 0), Tp),
        rp_w=jnp.asarray(_pad_rows(t.rp_w, Tp, 0.0)),
        gp_valid=valid(t.rp_n_progs, Gp),
        at_key=jnp.asarray(t.at_key),
        at_m_onehot=onehot(t.at_m, t.at_m.shape[0]),
        st_key=jnp.asarray(t.st_key),
        st_m_onehot=onehot(t.st_m, t.st_m.shape[0]),
        st_w=jnp.asarray(t.st_w),
        st_hard=jnp.asarray(t.st_hard),
        sh_valid=valid(t.sh_n_rows, Tsh),
        sh_prog=i32(t.sh_prog, Tsh, Gsh),
        sh_key=i32(t.sh_key, Tsh, 0),
        sh_m_onehot=onehot(_pad_rows(t.sh_m, Tsh, 0), Tsh),
        sh_skew=jnp.asarray(_pad_rows(t.sh_skew, Tsh, 0.0)),
        shp_selprog=i32(t.shp_selprog, Gsh, -1),
        shp_valid=valid(t.sh_n_progs, Gsh),
        ss_valid=valid(t.ss_n_rows, Tss),
        ss_prog=i32(t.ss_prog, Tss, Gss),
        ss_key=i32(t.ss_key, Tss, 0),
        ss_m_onehot=onehot(_pad_rows(t.ss_m, Tss, 0), Tss),
        ssp_selprog=i32(t.ssp_selprog, Gss, -1),
        ssp_valid=valid(t.ss_n_progs, Gss),
    )
