"""The driver entry points in ``__graft_entry__``: the jittable step must
trace on the example it builds, and the sharded dryrun must run, so a
standalone pack whose owner columns do not pair with its pods cannot
pass unseen (the public-surface test only checks they are callable)."""

import jax
import pytest

import __graft_entry__ as ge


def test_entry_example_carries_the_batch_owner_columns():
    _fn, (pods, nodes, _sel, _topo) = ge.entry()
    width = pods.owner_match_mh.shape[1]
    assert width > 0  # the example's spread pods name owner sets
    assert nodes.owner_counts.shape == (nodes.valid.shape[0], width)


def test_entry_step_traces():
    fn, args = ge.entry()
    assigned, usage, rounds, _stats = jax.eval_shape(fn, *args)
    pods, nodes = args[0], args[1]
    assert assigned.shape == pods.valid.shape
    assert usage.owner_counts.shape == nodes.owner_counts.shape
    assert rounds.shape == ()


def test_dryrun_multichip_runs_small(monkeypatch, tmp_path, capsys):
    # the dryrun pins the CPU platform and a device count in the
    # environment: setenv restores both after the test
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "")
    monkeypatch.setenv("KTPU_DRYRUN_NODES", "128")
    monkeypatch.setenv("KTPU_DRYRUN_PODS", "48")
    monkeypatch.setenv("KTPU_DRYRUN_CHECK", "1")
    # its cost-model document lands beside this path (a missing
    # directory is skipped), not in the checkout
    monkeypatch.setattr(ge, "__file__", str(tmp_path / "entry.py"))
    if len(jax.devices()) < 2:
        pytest.fail("tests run with 8 virtual CPU devices (conftest)")
    ge.dryrun_multichip(2)
    out = capsys.readouterr().out
    assert "2-device mesh, 128 nodes" in out
    assert "sharded==single placements verified" in out
