"""Streaming serving mode (kubernetes_tpu/serving): doorbell wake-on-
event, the adaptive micro-batch accumulation window, APF-style load
shedding, and watch fan-out hardening.

Deterministic: the window logic runs on a fake clock (no threads), flow
control sheds are reached with ``queue_timeout_s=0``, and the only
real-time pieces are the bounded serving-loop smoke tests (~2 s of
synthetic churn, the tier-1 end-to-end pin of the acceptance criteria).
"""

import dataclasses
import http.client
import json
import threading
import time

import pytest

from kubernetes_tpu.config import ServingConfig, WarmupConfig
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.serving import (
    Doorbell,
    FlowController,
    FlowSchema,
    MicroBatchWindow,
    RequestRejected,
    ServingLoop,
    WatcherGone,
    WatchHub,
)
from kubernetes_tpu.testing import make_node, make_pod


class FakeClock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _scheduler(n_nodes=8, clock=None, **kw):
    kw.setdefault("enable_preemption", False)
    if clock is not None:
        kw["clock"] = clock
    s = Scheduler(**kw)
    for i in range(n_nodes):
        s.on_node_add(make_node(f"n{i}", cpu_milli=16000,
                                memory=64 * 2**30, pods=250))
    return s


# ---------------------------------------------------------------------------
# doorbell
# ---------------------------------------------------------------------------


def test_doorbell_ring_pending_consume():
    bell = Doorbell()
    assert bell.pending() == 0 and bell.consume() == 0
    bell.ring("queue:PodAdd")
    bell.ring("rest:create")
    assert bell.pending() == 2
    assert bell.rings_total == 2
    assert bell.rings_by_reason == {"queue:PodAdd": 1, "rest:create": 1}
    assert bell.consume() == 2
    assert bell.pending() == 0
    # a ring BEFORE the wait is remembered (level-triggered): the
    # lost-wakeup race between depth check and wait cannot drop work
    bell.ring()
    assert bell.wait(timeout=0) is True
    # clean timeout with nothing pending
    assert bell.wait(timeout=0) is False


def test_doorbell_wakes_waiter_across_threads():
    bell = Doorbell()
    out = {}

    def waiter():
        out["rung"] = bell.wait(timeout=5.0)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.02)
    bell.ring("x")
    t.join(timeout=5.0)
    assert out["rung"] is True


def test_queue_rings_doorbell_on_work_not_on_failures():
    clk = FakeClock()
    s = _scheduler(n_nodes=1, clock=clk)
    bell = s.attach_doorbell(Doorbell())
    assert s.queue.doorbell is bell
    s.queue.add(make_pod("a", cpu_milli=100))
    assert bell.rings_by_reason.get("queue:PodAdd") == 1
    # the scheduler's own failure output must NOT ring (it would spin
    # the serving loop against pods no cluster event has touched)
    p = make_pod("b", cpu_milli=100)
    before = bell.rings_total
    s.queue.record_failure(p)
    # cycle 1 > move_request_cycle, so the pod parks in unschedulableQ
    s.queue.add_unschedulable_if_not_present(p, 1)
    assert bell.rings_total == before
    # ...but the event that can un-stick them does ring
    s.queue.move_all_to_active()
    assert bell.rings_by_reason.get("queue:MoveAllToActive") == 1
    # metrics mirror (scheduler_doorbell_rings_total{reason})
    assert s.metrics.doorbell_rings.value(reason="queue:PodAdd") == 1


def test_node_event_rings_through_move_sweep():
    clk = FakeClock()
    s = _scheduler(n_nodes=1, clock=clk)
    bell = s.attach_doorbell(Doorbell())
    p = make_pod("stuck", cpu_milli=100)
    s.queue.record_failure(p)
    s.queue.add_unschedulable_if_not_present(p, 1)
    clk.advance(30.0)  # past max backoff, so the sweep goes to activeQ
    before = bell.rings_total
    s.on_node_add(make_node("n-new", cpu_milli=4000))
    assert bell.rings_total > before  # informer path rang via the sweep


# ---------------------------------------------------------------------------
# micro-batch window (pure decision logic, fake clock)
# ---------------------------------------------------------------------------


def test_window_opens_and_flushes_on_max_wait():
    clk = FakeClock()
    w = MicroBatchWindow(clock=clk, min_wait_s=0.005, max_wait_s=0.05,
                         target_bucket=256)
    assert not w.observe(0).flush and not w.open
    d = w.observe(5)  # opens; 5 pods never fill a bucket
    assert w.open and not d.flush and d.wait_s == pytest.approx(0.005)
    clk.advance(0.01)
    d = w.observe(5)
    assert not d.flush and d.wait_s == pytest.approx(0.04)
    clk.advance(0.05)
    d = w.observe(5)
    assert d.flush and d.trigger == "max-wait"
    assert w.close() == pytest.approx(0.06)
    assert not w.open


def test_window_flushes_when_warmed_bucket_fills():
    clk = FakeClock()
    w = MicroBatchWindow(clock=clk, min_wait_s=0.005, max_wait_s=0.05,
                         target_bucket=256)
    w.observe(3)
    clk.advance(0.006)  # past min_wait
    # 13 is not a power-of-two boundary -> keep accumulating
    assert not w.observe(13).flush
    # 16 sits exactly on the warmed bucket grid -> zero padding waste
    d = w.observe(16)
    assert d.flush and d.trigger == "bucket-fill"


def test_window_bucket_fill_respects_min_wait_and_floor():
    clk = FakeClock()
    w = MicroBatchWindow(clock=clk, min_wait_s=0.005, max_wait_s=0.05,
                         target_bucket=256)
    # boundary depth BEFORE min_wait: the debounce holds (a burst in
    # flight may carry the window to a bigger bucket)
    assert not w.observe(16).flush
    # sub-floor depths (below the padding grid's smallest bucket) never
    # "fill" — 4 pods pad to 8 regardless
    clk.advance(0.006)
    assert not w.observe(4).flush


def test_window_target_cap_flushes_immediately_and_snaps_down():
    clk = FakeClock()
    w = MicroBatchWindow(clock=clk, min_wait_s=0.005, max_wait_s=0.05,
                         target_bucket=1000)
    assert w.target_bucket == 512  # snapped DOWN to the warmed grid
    d = w.observe(512)  # cap reached: flush even before min_wait
    assert d.flush and d.trigger == "bucket-fill"


def test_window_closes_when_queue_drains_externally():
    clk = FakeClock()
    w = MicroBatchWindow(clock=clk, min_wait_s=0.0, max_wait_s=0.05,
                         target_bucket=64)
    w.observe(5)
    assert w.open
    # the pods left by another path (delete / competing binder): the
    # window must close, not flush an empty cycle at max_wait
    assert not w.observe(0).flush
    assert not w.open


def test_window_rejects_inverted_waits():
    with pytest.raises(ValueError):
        MicroBatchWindow(min_wait_s=0.1, max_wait_s=0.05)


def test_window_flushes_quiet_when_burst_ends_off_boundary():
    clk = FakeClock()
    w = MicroBatchWindow(clock=clk, min_wait_s=0.005, max_wait_s=0.05,
                         target_bucket=256)
    d = w.observe(40)  # one whole burst; 40 is no power of two
    assert not d.flush and d.wait_s == pytest.approx(0.005)
    clk.advance(0.002)
    d = w.observe(40)  # no growth: the wait runs to last growth + min_wait
    assert not d.flush and clk.t + d.wait_s == pytest.approx(0.005)
    clk.advance(d.wait_s)
    d = w.observe(40)
    assert d.flush and d.trigger == "quiet"
    assert w.close() == pytest.approx(0.005)
    assert not w.open and w.grew_at is None


def test_window_quiet_waits_for_a_burst_arriving_in_parts():
    clk = FakeClock()
    w = MicroBatchWindow(clock=clk, min_wait_s=0.005, max_wait_s=0.05,
                         target_bucket=256)
    assert not w.observe(24).flush
    clk.advance(0.003)
    d = w.observe(40)  # the burst's second part
    assert not d.flush
    clk.advance(0.002)  # the first part's quiet deadline has passed...
    d = w.observe(40)
    # ...but the second part pushed it back to 0.003 + min_wait
    assert not d.flush and clk.t + d.wait_s == pytest.approx(0.008)
    clk.advance(d.wait_s)
    d = w.observe(40)
    assert d.flush and d.trigger == "quiet"
    assert w.close() == pytest.approx(0.008)


def test_window_steady_growth_never_goes_quiet():
    clk = FakeClock()
    w = MicroBatchWindow(clock=clk, min_wait_s=0.005, max_wait_s=0.05,
                         target_bucket=256)
    depth = 9
    while clk.t < 0.05:  # a look every 4 ms, each three pods deeper
        d = w.observe(depth)
        assert not d.flush and d.wait_s <= 0.005 + 1e-12
        clk.advance(0.004)
        depth += 3
    d = w.observe(depth)
    assert d.flush and d.trigger == "max-wait"


def test_window_below_min_bucket_still_waits_to_max_wait():
    clk = FakeClock()
    w = MicroBatchWindow(clock=clk, min_wait_s=0.005, max_wait_s=0.05,
                         target_bucket=256)
    w.observe(5)
    clk.advance(0.02)  # quiet for 4 min_waits, but 5 pods pad to 8 anyway
    d = w.observe(5)
    assert not d.flush and d.wait_s == pytest.approx(0.03)
    clk.advance(d.wait_s)
    d = w.observe(5)
    assert d.flush and d.trigger == "max-wait"


# ---------------------------------------------------------------------------
# e2e admission-to-bind latency threading
# ---------------------------------------------------------------------------


def test_e2e_latency_is_per_pod_create_to_bind():
    clk = FakeClock()
    s = _scheduler(n_nodes=2, clock=clk)
    s.on_pod_add(make_pod("early", cpu_milli=100))
    clk.advance(0.2)
    s.on_pod_add(make_pod("late", cpu_milli=100))
    clk.advance(0.05)
    r = s.schedule_cycle()
    assert r.scheduled == 2
    # queue-add stamp -> bind, per pod (the serving p99's raw material)
    assert r.e2e_latency_s["default/early"] == pytest.approx(0.25)
    assert r.e2e_latency_s["default/late"] == pytest.approx(0.05)
    # each value landed in the e2e histogram (per-pod, not per-cycle)
    assert s.metrics.e2e_scheduling_duration.count() == 2


def test_e2e_histogram_falls_back_to_cycle_elapsed_when_nothing_bound():
    clk = FakeClock()
    s = _scheduler(n_nodes=1, clock=clk)
    s.on_pod_add(make_pod("huge", cpu_milli=10**9))
    r = s.schedule_cycle()
    assert r.scheduled == 0 and r.unschedulable == 1
    assert not r.e2e_latency_s
    assert s.metrics.e2e_scheduling_duration.count() == 1


def test_flush_provenance_reaches_flight_record():
    clk = FakeClock()
    s = _scheduler(n_nodes=2, clock=clk)
    s.on_pod_add(make_pod("p", cpu_milli=100))
    r = s.schedule_cycle(flush_trigger="bucket-fill", window_s=0.012)
    assert r.flush_trigger == "bucket-fill" and r.window_s == 0.012
    rec = s.obs.recorder.records()[-1]
    assert rec.flush_trigger == "bucket-fill"
    assert rec.window_s == pytest.approx(0.012)
    assert rec.to_json()["microbatch"] == {"trigger": "bucket-fill",
                                           "window_s": 0.012}
    assert "win=bucket-fill" in s.obs.recorder.dump()


def test_idle_tick_mints_no_cycle_artifacts():
    clk = FakeClock()
    s = _scheduler(n_nodes=1, clock=clk)
    for _ in range(50):
        s.idle_tick()
        clk.advance(0.25)
    assert s.obs.recorder.recorded == 0
    assert len(s.obs.traces) == 0
    assert s.metrics.e2e_scheduling_duration.count() == 0
    # ...while still doing queue maintenance: a backed-off pod
    # resurfaces (and rings the doorbell) without a cycle
    bell = s.attach_doorbell(Doorbell())
    p = make_pod("parked", cpu_milli=100)
    s.queue.record_failure(p)
    # move_request_cycle (-1) >= the pod's cycle (-10): goes to backoffQ
    s.queue.add_unschedulable_if_not_present(p, -10)
    bell.consume()
    clk.advance(30.0)
    s.idle_tick()
    assert s.queue.pending_counts()["active"] == 1
    assert bell.rings_by_reason.get("queue:BackoffComplete") == 1


# ---------------------------------------------------------------------------
# no-retrace-under-churn (jaxtel counters)
# ---------------------------------------------------------------------------


def test_churn_over_warmed_buckets_never_retraces():
    """The serving contract: warm the small-bucket grid once, then
    create/delete churn presenting varying micro-batch depths classifies
    every solve as a jit-cache hit — retraces stay 0."""
    s = _scheduler(n_nodes=8, warmup=WarmupConfig(enabled=True,
                                                  pod_buckets=(8, 16, 32)))
    sample = [make_pod("warm", cpu_milli=100, memory=256 * 2**20)]
    assert s.warmup(sample_pods=sample) == 3
    assign_map = {}
    for i, n in enumerate((5, 12, 30, 3, 16)):  # buckets 8,16,32,8,16
        for j in range(n):
            s.on_pod_add(make_pod(f"c{i}-{j}", cpu_milli=100,
                                  memory=256 * 2**20))
        r = s.schedule_cycle()
        assert r.scheduled == n
        assign_map.update(r.assignments)
        # churn the other direction too: deletes dirty the node table
        # (delta snapshot path) without moving the node bucket
        for key in list(assign_map)[: n // 2]:
            ns, name = key.split("/", 1)
            pod = make_pod(name, cpu_milli=100, memory=256 * 2**20)
            pod.namespace = ns
            pod.node_name = assign_map.pop(key)
            s.on_pod_delete(pod)
    sites = s.obs.jax.snapshot()["sites"]["solve"]
    assert sites["retraces"] == 0
    assert s.obs.jax.retrace_total() == 0
    assert sites["hits"] >= 5


# ---------------------------------------------------------------------------
# APF-style flow control (shed/429 contract)
# ---------------------------------------------------------------------------


def test_flow_controller_seats_and_bounded_queue():
    ctrl = FlowController(flows=[
        FlowSchema("mutating", concurrency=2, queue_length=1,
                   queue_timeout_s=0.0)],
        retry_after_s=3.0)
    s1 = ctrl.acquire("mutating")
    s2 = ctrl.acquire("mutating")
    # seats full, queue bounded at 1, timeout 0 -> immediate shed
    with pytest.raises(RequestRejected) as ei:
        ctrl.acquire("mutating")
    assert ei.value.reason == "timeout" and ei.value.retry_after_s == 3.0
    ctrl.release(s1)
    s3 = ctrl.acquire("mutating")  # freed seat admits again
    ctrl.release(s2)
    ctrl.release(s3)
    st = ctrl.stats()
    assert st["inflight"]["mutating"] == 0
    assert st["admitted"]["mutating"] == 3
    assert st["rejected"] == {"mutating/timeout": 1}


def test_flow_controller_queue_full_rejects_without_waiting():
    ctrl = FlowController(flows=[
        FlowSchema("readonly", concurrency=1, queue_length=0,
                   queue_timeout_s=5.0)])
    s1 = ctrl.acquire("readonly")
    t0 = time.monotonic()
    with pytest.raises(RequestRejected) as ei:
        ctrl.acquire("readonly")
    assert time.monotonic() - t0 < 1.0  # queue-full is instant, no wait
    assert ei.value.reason == "queue-full"
    ctrl.release(s1)


def test_flow_controller_fifo_drain():
    ctrl = FlowController(flows=[
        FlowSchema("mutating", concurrency=1, queue_length=8,
                   queue_timeout_s=5.0)])
    seat = ctrl.acquire("mutating")
    order = []
    lock = threading.Lock()

    def worker(i):
        s = ctrl.acquire("mutating")
        with lock:
            order.append(i)
        ctrl.release(s)

    threads = []
    for i in range(3):
        t = threading.Thread(target=worker, args=(i,))
        t.start()
        time.sleep(0.02)  # establish FIFO arrival order
        threads.append(t)
    ctrl.release(seat)
    for t in threads:
        t.join(timeout=5.0)
    assert order == [0, 1, 2]  # bounded queue drains FIFO


def test_flow_controller_saturation_sheds_mutating_traffic():
    depth = {"v": 0}
    ctrl = FlowController(flows=[
        FlowSchema("mutating", concurrency=16, queue_length=16,
                   queue_timeout_s=0.0)])
    ctrl.set_saturation("mutating", lambda: depth["v"], maximum=100)
    ctrl.release(ctrl.acquire("mutating"))
    depth["v"] = 101  # backend drowning -> shed at admission
    with pytest.raises(RequestRejected) as ei:
        ctrl.acquire("mutating")
    assert ei.value.reason == "saturated"
    depth["v"] = 10
    ctrl.release(ctrl.acquire("mutating"))  # recovers


def test_flow_classification():
    c = FlowController.classify
    assert c("GET", "/healthz") == "exempt"
    assert c("GET", "/metrics") == "exempt"
    assert c("GET", "/debug/flightrecorder") == "exempt"
    assert c("GET", "/api/v1/watch/pods?resourceVersion=3") == "watch"
    assert c("GET", "/api/v1/pods") == "readonly"
    assert c("POST", "/api/v1/namespaces/default/pods") == "mutating"
    assert c("DELETE", "/api/v1/nodes/n0") == "mutating"
    # a pod literally named "watch" is not a watch request
    assert c("GET", "/api/v1/namespaces/watch/pods") == "readonly"


def test_rest_server_sheds_with_429_and_retry_after():
    from kubernetes_tpu.restapi import RestServer
    from kubernetes_tpu.sim import HollowCluster

    hub = HollowCluster(seed=11, scheduler_kw={"enable_preemption": False})
    ctrl = FlowController(flows=[
        FlowSchema("exempt", exempt=True),
        FlowSchema("watch", concurrency=1, queue_length=0,
                   queue_timeout_s=0.0),
        FlowSchema("readonly", concurrency=0, queue_length=0,
                   queue_timeout_s=0.0),
        FlowSchema("mutating", concurrency=4, queue_length=2,
                   queue_timeout_s=0.0)],
        retry_after_s=2.0)
    srv = RestServer(hub, fairness=ctrl)
    port = srv.serve()

    def req(method, path, body=None):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request(method, path, json.dumps(body) if body else None)
        r = conn.getresponse()
        raw = r.read()
        conn.close()
        return r.status, dict(r.getheaders()), json.loads(raw)

    try:
        # zero readonly seats: list traffic sheds 429 + Retry-After,
        # with the metav1.Status shape intact
        st, hdr, doc = req("GET", "/api/v1/pods")
        assert st == 429 and doc["reason"] == "TooManyRequests"
        assert hdr.get("Retry-After") == "2"
        # the diagnostic surface survives the overload (exempt flow)
        assert req("GET", "/openapi/v2")[0] == 200
        # mutating flow still has seats: writes proceed
        st, _, _ = req("POST", "/api/v1/namespaces/default/pods",
                       {"metadata": {"name": "w"},
                        "spec": {"containers": []}})
        assert st == 201
        assert ctrl.stats()["rejected"].get("readonly/queue-full") == 1
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# watch fan-out hardening
# ---------------------------------------------------------------------------


def test_watch_hub_bounded_buffer_evicts_slow_watcher():
    hub = WatchHub(buffer=4)
    fast, slow = hub.register(), hub.register()
    for i in range(4):
        hub.publish(("ADDED", i))
        assert len(fast.poll()) == 1  # fast consumer keeps draining
    assert slow.lag() == 4
    hub.publish(("ADDED", 4))  # overflows slow's send buffer
    # the slow watcher is cut loose, not the hub: publish kept working
    assert fast.poll() == [("ADDED", 4)]
    with pytest.raises(WatcherGone):
        slow.poll()
    st = hub.stats()
    assert st["evicted"] == 1 and st["watchers"] == 2
    # re-registering after the Gone (the relist) works
    slow.close()
    again = hub.register()
    hub.publish(("ADDED", 5))
    assert again.poll() == [("ADDED", 5)]


def test_watch_hub_eviction_accounting_and_reasons():
    """Eviction is never a silent drop (the takeover satellite): the
    buffered events an eviction discards are COUNTED (per watcher and
    hub-wide), and the WatcherGone message names the eviction's actual
    reason plus the relist hint — an overflow reads differently from a
    takeover relist."""
    hub = WatchHub(buffer=2)
    slow = hub.register()
    for i in range(3):
        hub.publish(("ADDED", i))  # third publish overflows slow
    with pytest.raises(WatcherGone) as ei:
        slow.poll()
    msg = str(ei.value)
    assert "send buffer overflowed" in msg and "relist" in msg
    assert "2 buffered events dropped" in msg  # the cleared buffer
    assert slow.dropped == 2
    st = hub.stats()
    assert st["events_dropped"] == 2 and st["evicted"] == 1
    # takeover relist: evict_all carries ITS reason into the 410
    w = hub.register()
    hub.publish(("ADDED", 9))
    assert hub.evict_all("leadership change (takeover): relist") == 1
    with pytest.raises(WatcherGone) as ei:
        w.poll()
    assert "leadership change (takeover)" in str(ei.value)
    assert "relist" in str(ei.value)
    assert hub.stats()["events_dropped"] == 3


def test_watch_hub_eviction_races_concurrent_takeover_drain():
    """The race the satellite pins: watchers drained by consumer
    threads WHILE the standby's takeover reconciliation broadcasts the
    relist eviction (evict_all). No interleaving may end with a
    watcher that neither saw WatcherGone nor kept its events: every
    published event is either delivered or counted dropped, and every
    watcher observes the sticky Gone with the relist hint."""
    hub = WatchHub(buffer=10_000)
    n_watchers, n_events = 8, 400
    watchers = [hub.register() for _ in range(n_watchers)]
    delivered = [0] * n_watchers
    gone_msgs: list = [None] * n_watchers
    start = threading.Barrier(n_watchers + 2)

    def consume(i):
        start.wait()
        while True:
            try:
                delivered[i] += len(watchers[i].poll())
            except WatcherGone as e:
                gone_msgs[i] = str(e)
                return

    def publish():
        start.wait()
        for k in range(n_events):
            hub.publish(("BOUND", k))

    threads = [threading.Thread(target=consume, args=(i,))
               for i in range(n_watchers)]
    threads.append(threading.Thread(target=publish))
    for t in threads:
        t.start()
    start.wait()  # everyone running: reconcile fires mid-stream
    hub.evict_all("takeover: relist")
    for t in threads:
        t.join(timeout=10)
    assert all(not t.is_alive() for t in threads)
    # every watcher got the sticky Gone with the relist hint — none
    # ended as a silent empty stream
    assert all(m is not None and "takeover: relist" in m
               for m in gone_msgs)
    # accounting closes: per watcher, delivered + dropped == what the
    # hub appended to its buffer before the eviction cut it off
    st = hub.stats()
    for i, w in enumerate(watchers):
        assert delivered[i] + w.dropped <= n_events
    assert st["events_dropped"] == sum(w.dropped for w in watchers)
    assert st["evicted"] == n_watchers


def test_rest_watch_drain_bound_evicts_lagging_watcher():
    from kubernetes_tpu.restapi import RestServer
    from kubernetes_tpu.sim import HollowCluster

    from kubernetes_tpu.metrics import SchedulerMetrics

    hub = HollowCluster(seed=12, scheduler_kw={"enable_preemption": False})
    metrics = SchedulerMetrics()
    srv = RestServer(hub, watch_max_drain=3, metrics=metrics)
    port = srv.serve()

    def req(path, body=None, method="GET"):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request(method, path, json.dumps(body) if body else None)
        r = conn.getresponse()
        raw = r.read()
        conn.close()
        return r.status, raw

    try:
        st, raw = req("/api/v1/nodes")
        rv0 = int(json.loads(raw)["metadata"]["resourceVersion"])
        for i in range(8):
            req("/api/v1/namespaces/default/pods",
                {"metadata": {"name": f"p{i}"}, "spec": {"containers": []}},
                method="POST")
        st, raw = req(f"/api/v1/watch/pods?resourceVersion={rv0}")
        doc = json.loads(raw)
        assert st == 410 and doc["reason"] == "Expired"
        assert "relist" in doc["message"]
        assert srv.watch_evictions == 1
        assert metrics.watch_evictions.value() == 1
        # a caught-up watcher still streams normally
        st, raw = req("/api/v1/nodes")
        rv1 = int(json.loads(raw)["metadata"]["resourceVersion"])
        req("/api/v1/namespaces/default/pods",
            {"metadata": {"name": "tail"}, "spec": {"containers": []}},
            method="POST")
        st, raw = req(f"/api/v1/watch/pods?resourceVersion={rv1}")
        assert st == 200
        assert len([l for l in raw.splitlines() if l]) == 1
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------


def test_serving_config_v1alpha1_round_trip():
    from kubernetes_tpu.api.config_v1alpha1 import decode, encode
    from kubernetes_tpu.config import KubeSchedulerConfiguration

    cfg = dataclasses.replace(
        KubeSchedulerConfiguration(),
        serving=ServingConfig(enabled=True, min_wait_s=0.002,
                              max_wait_s=0.1, target_bucket=128,
                              flow_concurrency=4, watch_buffer=99))
    doc = encode(cfg)
    assert doc["serving"]["enabled"] is True
    assert doc["serving"]["minWait"] == "2ms"
    assert doc["serving"]["maxWait"] == "100ms"
    back = decode(doc)
    assert back.serving == cfg.serving


def test_serving_config_validation_gates():
    from kubernetes_tpu.cli import decode_config, validate_config
    from kubernetes_tpu.config import KubeSchedulerConfiguration

    bad = dataclasses.replace(
        KubeSchedulerConfiguration(),
        serving=ServingConfig(min_wait_s=0.2, max_wait_s=0.1,
                              target_bucket=0, watch_buffer=0,
                              watch_concurrency=0))
    errs = validate_config(bad)
    assert any("serving.maxWait" in e for e in errs)
    assert any("serving.targetBucket" in e for e in errs)
    assert any("serving.watchBuffer" in e for e in errs)
    # the watch-seat violation names ITS field, not flowConcurrency
    assert any("serving.watchConcurrency" in e for e in errs)
    assert not any("serving.flowConcurrency" in e for e in errs)
    # native decode accepts the block and rejects unknown fields
    cfg = decode_config({"serving": {"enabled": True, "max_wait_s": 0.2}})
    assert cfg.serving.enabled and cfg.serving.max_wait_s == 0.2
    from kubernetes_tpu.cli import ConfigError

    with pytest.raises(ConfigError):
        decode_config({"serving": {"nope": 1}})


def test_serving_cli_flag_overlay():
    from kubernetes_tpu.cli import build_parser, resolve_config

    args = build_parser().parse_args(
        ["--serving", "true", "--serving-max-wait", "0.02"])
    cfg = resolve_config(args)
    assert cfg.serving.enabled is True
    assert cfg.serving.max_wait_s == 0.02


# ---------------------------------------------------------------------------
# serve loops end-to-end (bounded real time)
# ---------------------------------------------------------------------------


def test_legacy_run_skips_solve_while_idle(monkeypatch):
    """ROADMAP satellite: cli.run's legacy loop must not mint cycle
    artifacts while the queue is empty and no doorbell has rung — and
    must still schedule promptly once work arrives."""
    from kubernetes_tpu import cli as cli_mod
    from kubernetes_tpu.config import KubeSchedulerConfiguration, \
        LeaderElectionConfig

    sched = _scheduler(n_nodes=1)
    cycles = {"n": 0}
    orig = sched.schedule_cycle

    def counting_cycle(*a, **kw):
        cycles["n"] += 1
        return orig(*a, **kw)

    sched.schedule_cycle = counting_cycle
    monkeypatch.setattr(Scheduler, "from_config",
                        classmethod(lambda cls, cfg, **kw: sched))
    cfg = dataclasses.replace(
        KubeSchedulerConfiguration(),
        leader_election=LeaderElectionConfig(leader_elect=False))
    args = cli_mod.build_parser().parse_args(
        ["--port", "0", "--cycle-interval", "0.01"])
    stop = threading.Event()
    t = threading.Thread(target=cli_mod.run, args=(cfg, args, stop))
    t.start()
    try:
        time.sleep(0.3)  # ~30 idle intervals
        assert cycles["n"] == 0
        assert sched.obs.recorder.recorded == 0
        sched.on_pod_add(make_pod("wake", cpu_milli=100))  # rings
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and len(sched.queue) > 0:
            time.sleep(0.02)
        assert cycles["n"] >= 1
        assert len(sched.queue) == 0  # the wake pod got scheduled
    finally:
        stop.set()
        t.join(timeout=10)
    assert not t.is_alive()


def test_serving_loop_flushes_ended_burst_quiet():
    """A 40-pod burst ingested whole through the real doorbell flushes
    once it stops growing, not at max_wait."""
    s = _scheduler(n_nodes=8,
                   warmup=WarmupConfig(enabled=True, pod_buckets=(64,)))
    s.warmup(sample_pods=[make_pod("w", cpu_milli=50,
                                   memory=128 * 2**20)])
    bell = s.attach_doorbell(Doorbell())
    cfg = ServingConfig(enabled=True, min_wait_s=0.005, max_wait_s=1.0,
                        target_bucket=256)
    loop = ServingLoop(s, bell, cfg)

    def burst():
        for i in range(40):
            s.on_pod_add(make_pod(f"b-{i}", cpu_milli=50,
                                  memory=128 * 2**20))

    loop.ingest(burst)
    results = []
    deadline = time.monotonic() + 10
    while not results and time.monotonic() < deadline:
        res = loop.run_once()
        if res is not None:
            results.append(res)
    assert loop.cycles == 1 and len(results) == 1
    res = results[0]
    assert res.flush_trigger == "quiet" and res.scheduled == 40
    assert res.window_s < cfg.max_wait_s / 4
    assert s.metrics.microbatch_flushes.value(trigger="quiet") == 1
    assert s.metrics.microbatch_flushes.value(trigger="max-wait") == 0


def test_serving_loop_churn_smoke():
    """~2 s of synthetic create/delete churn through the event-driven
    serving loop end-to-end: everything binds, windows flush on both
    triggers or max-wait at least, and the warmed solve site never
    retraces (the acceptance criteria's tier-1 pin)."""
    s = _scheduler(n_nodes=8,
                   warmup=WarmupConfig(enabled=True, pod_buckets=(8, 16)))
    s.warmup(sample_pods=[make_pod("w", cpu_milli=50,
                                   memory=128 * 2**20)])
    bell = s.attach_doorbell(Doorbell())
    results = []
    loop = ServingLoop(
        s, bell,
        ServingConfig(enabled=True, min_wait_s=0.002, max_wait_s=0.02,
                      target_bucket=16, idle_wait_s=0.05),
        on_cycle=results.append)
    stop = threading.Event()
    t = threading.Thread(target=loop.run, args=(stop,))
    t.start()
    created = 0
    bound_backlog = []
    seen = 0
    try:
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            for _ in range(3):
                loop.ingest(s.on_pod_add,
                            make_pod(f"churn-{created}", cpu_milli=50,
                                     memory=128 * 2**20))
                created += 1
            while seen < len(results):
                bound_backlog.extend(results[seen].assignments.items())
                seen += 1
            while len(bound_backlog) > 40:
                key, node = bound_backlog.pop(0)
                ns, name = key.split("/", 1)
                p = make_pod(name, cpu_milli=50, memory=128 * 2**20)
                p.node_name = node
                loop.ingest(s.on_pod_delete, p)
            time.sleep(0.02)
        # drain: wait for the loop to finish the tail
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and len(s.queue) > 0:
            time.sleep(0.02)
    finally:
        stop.set()
        t.join(timeout=10)
    assert created >= 100
    total_bound = sum(r.scheduled for r in results)
    assert total_bound == created, (total_bound, created)
    assert len(s.queue) == 0
    # micro-batch provenance made it through
    assert all(r.flush_trigger in ("bucket-fill", "quiet", "max-wait")
               for r in results)
    assert s.metrics.microbatch_flushes.value(trigger="max-wait") \
        + s.metrics.microbatch_flushes.value(trigger="bucket-fill") \
        + s.metrics.microbatch_flushes.value(trigger="quiet") \
        == len(results)
    # per-pod create-to-bind latencies are bounded by window + solve
    lats = [v for r in results for v in r.e2e_latency_s.values()]
    assert len(lats) == created
    assert max(lats) < 2.0
    # the serving contract: churn over warmed buckets never retraces
    assert s.obs.jax.retrace_total() == 0


# ---------------------------------------------------------------------------
# bench_compare churn gates (contract test)
# ---------------------------------------------------------------------------


def _load_bench_compare():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "bench_compare",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", "bench_compare.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _churn_rec(p99, shed_rate):
    return {
        "arms": {
            "serving": {"p99_s": p99, "ops_per_sec": 520.0},
            "fixed": {"p99_s": p99 * 4},
            "overload": {"shed_rate": shed_rate, "p99_s": p99 * 1.5},
        },
    }


def test_bench_compare_churn_gates(tmp_path):
    bc = _load_bench_compare()
    ok = bc.compare_churn(_churn_rec(0.06, 0.5), _churn_rec(0.061, 0.52),
                          threshold=0.10)
    assert not ok["regressions"], ok
    # p99 create-to-bind regression trips the gate
    bad = bc.compare_churn(_churn_rec(0.06, 0.5), _churn_rec(0.09, 0.5),
                           threshold=0.10)
    assert any("serving.p99_s" in r["check"] for r in bad["regressions"])
    # shed-rate regression (sheds exploding) trips too
    bad = bc.compare_churn(_churn_rec(0.06, 0.2), _churn_rec(0.06, 0.9),
                           threshold=0.10)
    assert any("shed_rate" in r["check"] for r in bad["regressions"])
    # takeover gate: one lease-retry tick (0.15 x lease_duration_s) of
    # absolute slack — the standby only attempts acquisition every
    # retry tick, so a delta inside one tick is phase alignment, not a
    # regression; a delta past the tick still trips.
    def _fo(takeover):
        rec = _churn_rec(0.06, 0.5)
        rec["arms"]["failover"] = {"takeover_s": takeover,
                                   "lease_duration_s": 2.0}
        return rec
    ok = bc.compare_churn(_fo(2.488), _fo(2.77), threshold=0.10)
    assert not any("takeover" in r["check"] for r in ok["regressions"]), ok
    bad = bc.compare_churn(_fo(2.488), _fo(2.80), threshold=0.10)
    assert any("takeover" in r["check"] for r in bad["regressions"])
    # absence tolerance: zero or one churn record must not fail the gate
    assert bc.find_churn_records(str(tmp_path)) == []
    (tmp_path / "churn_r01.json").write_text(json.dumps(_churn_rec(0.06,
                                                                   0.5)))
    assert len(bc.find_churn_records(str(tmp_path))) == 1
    # main() with a single churn record and no bench records: exit 0
    assert bc.main(["--dir", str(tmp_path)]) == 0
