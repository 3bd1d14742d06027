"""The program's spans on the profiler's clock: the cycle trace's spans,
the event handlers and the serving loop's waits mirrored into
``jax.profiler`` while a session is active (and nothing built while none
is), and the process-wide XLA compile counter stamped per cycle."""

import dataclasses
import glob
import threading
import time

import jax
import pytest

import kubernetes_tpu.obs.trace as obs_trace
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.serving import Doorbell
from kubernetes_tpu.serving.microbatch import ServingLoop
from kubernetes_tpu.testing import make_node, make_pod


def _scheduler(n_nodes=4):
    s = Scheduler(enable_preemption=False)
    for i in range(n_nodes):
        s.on_node_add(make_node(f"n{i}", cpu_milli=16000))
    return s


def _host_events(logdir):
    """Every host event of the newest profile under ``logdir``, as
    ``(name, start_ns, end_ns)``."""
    files = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    assert files, "the profiler wrote no trace"
    pd = jax.profiler.ProfileData.from_file(sorted(files)[-1])
    return [(ev.name, ev.start_ns, ev.end_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


class _Profile:
    """A ``jax.profiler`` session over a ``with`` block, Python tracer
    off; ``events`` holds the host events once it closes."""

    def __init__(self, logdir):
        self.logdir = str(logdir)
        self.events = []

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        self.events = _host_events(self.logdir)

    def named(self, name):
        return [e for e in self.events if e[0] == name]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _one_of(prof, name):
    evs = prof.named(name)
    assert len(evs) == 1, (name, evs)
    return evs[0]


def test_profile_carries_cycle_and_handler_spans(tmp_path):
    s = _scheduler()
    s.on_pod_add(make_pod("warm", cpu_milli=100))
    s.schedule_cycle()  # compiles outside the profile
    pod = make_pod("a", cpu_milli=100)
    with _Profile(tmp_path) as prof:
        s.on_pod_add(pod)
        res = s.schedule_cycle()
        bound = dataclasses.replace(
            pod, node_name=res.assignments["default/a"])
        s.on_pod_update(pod, bound)
        s.on_pod_delete(bound)
    cycle = _one_of(prof, "Scheduling cycle")
    for child in ("pop", "snapshot", "bind"):
        assert _inside(_one_of(prof, child), cycle), child
    pop, snap = _one_of(prof, "pop"), _one_of(prof, "snapshot")
    assert pop[2] <= snap[1], "pop ends before the snapshot starts"
    assert [n for n, *_ in prof.events if n.startswith("solve:")]
    delete = _one_of(prof, "on_pod_delete")
    for child in ("cache.remove_pod", "queue.move_all_to_active",
                  "packer.forget_pod"):
        assert _inside(_one_of(prof, child), delete), child
    add, update = _one_of(prof, "on_pod_add"), _one_of(prof, "on_pod_update")
    # the handlers run between cycles, in the order they were called
    assert add[2] <= cycle[1] <= cycle[2] <= update[1] <= update[2] \
        <= delete[1]


def test_no_profiler_builds_no_annotation(tmp_path, monkeypatch):
    built = []

    class Counting(obs_trace.TraceAnnotation):
        def __init__(self, name, **kw):
            built.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(obs_trace, "TraceAnnotation", Counting)
    s = _scheduler()
    loop = ServingLoop(s, Doorbell())

    def drive(tag):
        pod = make_pod(f"p-{tag}", cpu_milli=100)
        loop.ingest(s.on_pod_add, pod)
        res = s.schedule_cycle()
        bound = dataclasses.replace(
            pod, node_name=res.assignments[f"default/p-{tag}"])
        s.on_pod_update(pod, bound)
        s.on_pod_delete(bound)
        s.on_node_update(make_node("n0", cpu_milli=16000))

    drive("off")
    assert built == []
    # the same calls under a profiler build one per span: the patch is live
    with _Profile(tmp_path):
        drive("on")
    for name in ("serving:ingest-wait", "serving:ingest", "on_pod_add",
                 "Scheduling cycle", "pop", "snapshot", "on_pod_update",
                 "on_pod_delete", "packer.forget_pod", "on_node_update"):
        assert name in built, name


def test_ingest_wait_and_hold_are_separate_spans(tmp_path):
    s = _scheduler()
    loop = ServingLoop(s, Doorbell())
    held, hold_s = threading.Event(), 0.2

    def holder():
        with loop.lock:
            held.set()
            time.sleep(hold_s)

    with _Profile(tmp_path) as prof:
        th = threading.Thread(target=holder)
        th.start()
        held.wait(5)
        loop.ingest(s.on_pod_add, make_pod("late", cpu_milli=100))
        th.join()
    wait, hold = _one_of(prof, "serving:ingest-wait"), \
        _one_of(prof, "serving:ingest")
    assert (wait[2] - wait[1]) * 1e-9 >= hold_s / 2
    assert wait[2] <= hold[1]
    assert _inside(_one_of(prof, "on_pod_add"), hold)
    assert s.queue.pending_counts()["active"] == 1


def test_compile_counter_counts_real_compiles_per_cycle():
    jax.clear_caches()  # shapes other tests compiled compile again
    s = _scheduler(n_nodes=5)

    def cycle(n, tag):
        for i in range(n):
            s.on_pod_add(make_pod(f"{tag}-{i}", cpu_milli=10))
        before = s.obs.jax.compiles_total()
        s.schedule_cycle()
        after = s.obs.jax.compiles_total()
        rec = s.obs.recorder.records()[-1]
        assert rec.compiles == after[0] - before[0]
        assert rec.compile_s == pytest.approx(after[1] - before[1])
        return rec

    first = cycle(3, "a")
    assert first.compiles >= 1 and first.compile_s > 0
    steady = [cycle(3, f"b{k}").compiles for k in range(4)]
    assert steady[-1] == 0 and steady[-2] == 0, steady
    grown = cycle(24, "c")  # a new pod bucket
    assert grown.compiles >= 1
    assert f"compiles={grown.compiles}:" in s.obs.recorder.dump()
    assert s.obs.recorder.to_json()["records"][-1]["compiles"] == \
        grown.compiles
    tr = s.obs.last_trace
    assert ("compiles", {"n": float(grown.compiles),
                         "s": pytest.approx(grown.compile_s)}) in \
        [(n, v) for _, n, v in tr.counters]
