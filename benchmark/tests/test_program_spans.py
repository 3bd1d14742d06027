"""The per-layer metrics read from the program's own spans: the tiny
backlog and open cells, traced, report each of them; a trace without
the program's annotations gives every reader nothing to read."""

import time

import pytest

import run
from trace_reduce import DeviceTrace

NEW = {"sp5k-base.backlog": ("pod_add_us.backlog", "pod_confirm_us.backlog",
                             "pod_delete_us.backlog",
                             "pop_ms_per_kpod.backlog"),
       "sp5k-base.open": ("ingest_wait_ms.open", "cycle_wait_ms.open")}


@pytest.mark.parametrize("cell", sorted(NEW))
def test_traced_tiny_cell_reports_the_program_span_metrics(tiny_root, cell):
    spec = run.load_spec(tiny_root)
    out = run.run_cell(spec, cell, 2**31 + 11, 1.5, True, require_tpu=False,
                       root=tiny_root, t_start=time.perf_counter())
    assert out["correct"], out["checks"]
    for name in NEW[cell]:
        assert out["metrics"][name]["value"] > 0, name


@pytest.mark.parametrize("name", [n for ns in NEW.values() for n in ns])
def test_reader_finds_nothing_without_the_program_spans(tiny_root, name):
    """What a program without the spans leaves: cycles whose traces hold
    the older spans only, and the harness's own host annotations."""
    from driver import CycleLog

    class View:
        trace = DeviceTrace(ops={}, host=[("informer", 0.0, 1e9)])
        cycles = [CycleLog(1.0, 0, 8, 8, 8, (), 0.0, True,
                           {"durations": {"snapshot": 0.1}})]

    assert run.load_reader(name, tiny_root).read(View) is None
