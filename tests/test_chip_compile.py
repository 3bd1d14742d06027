"""Chipless compiles for one described TPU v5e chip (the on-chip
measurement guide, section 2): the main path's kernels at real widths
go through the chip's own compiler here, with no chip attached, so a
Mosaic or XLA refusal fails tier-1 instead of a chip run.

The topology is described inside a module-scoped fixture — never at
import, in a ``skipif`` or in ``parametrize``, and no fixture here is
autouse — so every xdist worker collects the same tests and only the
worker running this file loads the TPU compiler. Compiles are never
executed: results and times come from ``chip_smoke.py`` on the chip.
"""

import functools

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from kubernetes_tpu.ops.fused_score import _pair_pallas
from kubernetes_tpu.ops.sinkhorn import _scale_pallas

#: the headline bucket: an 8192-pod batch over 5,000 nodes (the node
#: axis pads to 5120 for the kernels, to bucket 8192 in the solver)
P, N = 8192, 5120
#: config-5's 50k nodes: past Sinkhorn's VMEM slab budget
N_WIDE = 51200


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a chipless compile is written to an enabled persistent cache but
    # cannot be read back without a chip: keep this module's compiles
    # out of it
    was = jax.config.values["jax_enable_compilation_cache"]
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


_pair = jax.jit(lambda a, b, m: _pair_pallas(a, b, m, 1.0, 1.0))
_sinkhorn = jax.jit(functools.partial(_scale_pallas, iters=2))


def _compile_pair(one_chip, p, n):
    return _pair.lower(
        _spec(one_chip, (p, n)), _spec(one_chip, (p, n)),
        _spec(one_chip, (p, n), jnp.bool_)).compile()


def _compile_sinkhorn(one_chip, p, n):
    return _sinkhorn.lower(
        _spec(one_chip, (p, n)), _spec(one_chip, (p,)),
        _spec(one_chip, (n,))).compile()


def test_fused_pair_compiles_for_v5e(one_chip):
    compiled = _compile_pair(one_chip, P, N)
    assert "tpu_custom_call" in compiled.as_text()


def test_sinkhorn_compiles_for_v5e(one_chip):
    from kubernetes_tpu.ops.sinkhorn import _block_shapes, pallas_fits

    assert pallas_fits(P, N)
    assert _block_shapes(P, N) == (128, 128, P, N)
    compiled = _compile_sinkhorn(one_chip, P, N)
    assert "tpu_custom_call" in compiled.as_text()


def test_wide_sinkhorn_routes_to_jnp_and_mosaic_refuses_it(one_chip):
    """8192x51200: the static rule says jnp, and it is right to — the
    v5e compiler refuses the Pallas u-kernel's 26 MB (bp, N) slab."""
    from kubernetes_tpu.obs.jaxtel import kernel_routes
    from kubernetes_tpu.ops.sinkhorn import pallas_fits, sinkhorn_plan

    assert not pallas_fits(P, N_WIDE)
    with pytest.raises(Exception, match="vmem"):
        _compile_sinkhorn(one_chip, P, N_WIDE)
    before = kernel_routes().get("sinkhorn:jnp:vmem", 0)
    jax.eval_shape(functools.partial(sinkhorn_plan, pallas=True,
                                     interpret=False),
                   jax.ShapeDtypeStruct((P, N_WIDE), jnp.float32),
                   jax.ShapeDtypeStruct((P, N_WIDE), jnp.bool_),
                   jax.ShapeDtypeStruct((N_WIDE,), jnp.float32))
    assert kernel_routes()["sinkhorn:jnp:vmem"] == before + 1


def test_headline_batch_assign_step_compiles_for_v5e(one_chip):
    """One whole jitted ``batch_assign`` step at the headline bucket
    (8192 pods x bucket 8192 nodes), with the scheduler's default
    weights, predicate mask and solver gates."""
    from kubernetes_tpu.config import (
        default_predicate_mask,
        default_priority_weights,
    )
    from kubernetes_tpu.models.cluster import make_nodes, make_pods
    from kubernetes_tpu.ops.arrays import (
        nodes_to_device,
        pods_to_device,
        selectors_to_device,
    )
    from kubernetes_tpu.ops.assign import _batch_impl, _batch_impl_call
    from kubernetes_tpu.ops.priorities import solver_gates
    from kubernetes_tpu.snapshot import SnapshotPacker

    nodes = make_nodes(5000, zones=10)
    existing = make_pods(1000, name_prefix="existing",
                         assigned_round_robin_over=5000)
    pending = make_pods(64)
    pk = SnapshotPacker()
    for p in existing + pending:
        pk.intern_pod(p)
    nt = pk.pack_nodes(nodes, existing)
    pt = pk.pack_pods(pending)
    skip, no_ports, no_aff, no_spread = solver_gates(nt, pt)
    # shapes only: the host tables never leave for a device
    def spec(tree):
        return jax.tree_util.tree_map(
            lambda x: _spec(one_chip, x.shape, x.dtype), tree)

    dn = spec(nodes_to_device(nt, pad_to=8192))
    dp = spec(pods_to_device(pt, pad_to=8192))
    ds = spec(selectors_to_device(pk.pack_selector_tables()))
    args, kw = _batch_impl_call(
        dp, dn, ds, default_priority_weights(), 128, 4, None, None, None,
        None, default_predicate_mask(), None, False, skip, no_ports,
        no_aff, no_spread, False, True, False)
    compiled = _batch_impl.lower(*args, **kw).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30
