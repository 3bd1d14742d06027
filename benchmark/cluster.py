"""The cluster of one configuration file, made from the seed.

Copied from the program's scheduler_perf fixtures (``models/cluster.py``
base node and base pod, ``chip_smoke.constraint_feed``) so that a later
change to the program's generators cannot change the benchmark's inputs.
Every seed gets the same sizes and the same counts (nodes per zone,
tainted nodes, pods per service, pods per preferred zone, existing pods
per node and per controller); the seed only chooses the order in which
they are dealt.

Keys of a configuration's ``cluster`` beyond the first benchmark's, each
optional (absent, :func:`build` makes what it made before them, draw
for draw):

- ``existing_pod``: ``{cpu_milli, memory_bytes}`` of the existing pods;
  default ``pod``.
- ``existing_controllers``: ``{count, name, labels}``: the existing pods
  are dealt over ``count`` ReplicationControllers, equal counts, by a
  seed permutation drawn after every other draw. A pod of controller
  ``c`` is labelled ``name: <name>-<c>`` plus ``labels``, points at the
  controller's uid and spreads by the selector ``name: <name>-<c>``.
  Default: no controller, no labels.
- ``pod_controller``: ``"none"`` (default) or ``"own"``: every pool slot
  is the only replica of a controller of its own (labelled ``name: <slot
  name>``, spread by that selector). Not with ``services``: a pod of a
  service and a controller spreads by both selectors at once, which the
  reference does not implement.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass
from typing import List

import numpy as np

ZONE_KEY = "failure-domain.beta.kubernetes.io/zone"
HOST_KEY = "kubernetes.io/hostname"


@dataclass
class Cluster:
    """Host objects of one run plus the arrays the reference reads."""

    nodes: list
    existing: list  # bound before the run, never deleted
    node_zone: np.ndarray  # (N,) zone id
    node_tainted: np.ndarray  # (N,) bool: PreferNoSchedule taint
    slot_service: np.ndarray  # (S,) service id, -1 = none
    slot_zone_pref: np.ndarray  # (S,) preferred zone, -1 = none
    existing_node: np.ndarray  # (E,) node index of each existing pod
    pod_cpu: float
    pod_mem: float
    existing_cpu: float
    existing_mem: float
    #: (E,) controller of each existing pod, -1 = none
    existing_controller: np.ndarray
    n_controllers: int  # existing pods' controllers
    own_controller: bool  # pod_controller "own"

    def slot_owner(self, s: int) -> int:
        """The owner of pool slot ``s`` in one numbering with the existing
        pods' controllers (``0 .. n_controllers-1``): then the services,
        or one controller per slot; -1 = none."""
        if self.own_controller:
            return self.n_controllers + s
        svc = int(self.slot_service[s % len(self.slot_service)])
        return self.n_controllers + svc if svc >= 0 else -1


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) % (2**63))


def build(config: dict, seed: int, n_slots: int) -> Cluster:
    """Nodes, existing pods and per-slot pod attributes."""
    from kubernetes_tpu.api.types import (
        EFFECT_PREFER_NO_SCHEDULE,
        LabelSelector,
        Node,
        Pod,
        Resources,
        Taint,
    )

    c = config["cluster"]
    rng = _rng(seed)
    n, zones = int(c["nodes"]), int(c["zones"])
    node_zone = np.arange(n) % zones if zones else np.full(n, -1)
    node_tainted = np.zeros(n, bool)
    if c["taint_every"]:
        node_tainted[rng.permutation(n)[: n // int(c["taint_every"])]] = True
    nodes = []
    nd = c["node"]
    for i in range(n):
        labels = {HOST_KEY: f"node-{i}"}
        if zones:
            labels[ZONE_KEY] = f"zone-{node_zone[i]}"
        taints = ((Taint("dedicated", "batch", EFFECT_PREFER_NO_SCHEDULE),)
                  if node_tainted[i] else ())
        nodes.append(Node(name=f"node-{i}", labels=labels, taints=taints,
                          allocatable=Resources(cpu_milli=nd["cpu_milli"],
                                                memory=nd["memory_bytes"],
                                                pods=nd["pods"])))
    pod = c["pod"]
    epod = c.get("existing_pod", pod)
    n_exist = int(c["existing_pods"])
    existing_node = rng.permutation(n)[np.arange(n_exist) % n]
    services = int(c["services"])
    slot_service = (rng.permutation(n_slots) % services if services
                    else np.full(n_slots, -1))
    slot_zone_pref = (rng.permutation(n_slots) % zones
                      if c["zone_affinity_weight"] and zones
                      else np.full(n_slots, -1))
    own = c.get("pod_controller", "none")
    if own not in ("none", "own"):
        raise ValueError(f"pod_controller {own!r}: 'none' or 'own'")
    if own == "own" and services:
        raise ValueError("pod_controller 'own' with services: a pod would "
                         "spread by two selectors at once")
    ctl = c.get("existing_controllers", {"count": 0})
    k = int(ctl["count"])
    existing_controller = (rng.permutation(n_exist) % k if k
                           else np.full(n_exist, -1))
    sels = [LabelSelector(match_labels={"name": f"{ctl['name']}-{j}"})
            for j in range(k)]
    existing = []
    for i in range(n_exist):
        kw = {}
        j = int(existing_controller[i])
        if j >= 0:
            kw = {"labels": {**ctl.get("labels", {}),
                             "name": f"{ctl['name']}-{j}"},
                  "owner_uid": f"rc-{ctl['name']}-{j}",
                  "spread_selectors": (sels[j],)}
        existing.append(Pod(name=f"existing-{i}",
                            node_name=f"node-{existing_node[i]}",
                            requests=Resources(cpu_milli=epod["cpu_milli"],
                                               memory=epod["memory_bytes"]),
                            **kw))
    return Cluster(nodes, existing, node_zone, node_tainted, slot_service,
                   slot_zone_pref, existing_node, float(pod["cpu_milli"]),
                   float(pod["memory_bytes"]), float(epod["cpu_milli"]),
                   float(epod["memory_bytes"]), existing_controller, k,
                   own == "own")


class PodPool:
    """Pod objects made in set-up and recycled, so that the measured
    window allocates none. Slot ``s`` holds a pending form and a bound
    form with the same spec. An incarnation ``i`` (one pod as the API
    server would see it, named ``<prefix><i>``) takes a free slot; after
    its delete the slot is free again and its objects are renamed for the
    next incarnation. The scheduler copies what it keeps of a pending pod
    (its cache assumes a copy), and the bound form leaves the cache on
    delete, so a renamed object is never one the scheduler still holds."""

    def __init__(self, config: dict, cluster: Cluster, n_slots: int,
                 prefix: str = "p") -> None:
        self.config = config
        self.cluster = cluster
        self.prefix = prefix
        self._key_off = len("default/") + len(prefix)
        self.pending: List = []
        self.bound: List = []
        for s in range(n_slots):
            self._grow(s)
        self.free = deque(range(n_slots))
        #: incarnation -> slot
        self.slot_of: List[int] = []
        #: objects made after set-up because every slot was in use
        self.made_late = 0

    def _grow(self, s: int) -> None:
        from kubernetes_tpu.api.types import (
            Affinity,
            LabelSelector,
            NodeSelectorTerm,
            Pod,
            PreferredSchedulingTerm,
            Requirement,
            Resources,
        )

        c = self.config["cluster"]
        cl = self.cluster
        svc = int(cl.slot_service[s % len(cl.slot_service)])
        zp = int(cl.slot_zone_pref[s % len(cl.slot_zone_pref)])
        name = f"{self.prefix}-slot{s}"
        forms = []
        for _ in range(2):
            kw = {}
            if svc >= 0:
                kw["labels"] = {"app": f"svc-{svc}"}
                kw["spread_selectors"] = (
                    LabelSelector(match_labels={"app": f"svc-{svc}"}),)
            if cl.own_controller:
                kw["labels"] = {"name": name}
                kw["owner_uid"] = f"rc-{name}"
                kw["spread_selectors"] = (
                    LabelSelector(match_labels={"name": name}),)
            if zp >= 0:
                kw["affinity"] = Affinity(node_preferred=(
                    PreferredSchedulingTerm(
                        weight=int(c["zone_affinity_weight"]),
                        preference=NodeSelectorTerm((Requirement(
                            ZONE_KEY, "In", (f"zone-{zp}",)),))),))
            forms.append(Pod(name=name,
                             requests=Resources(cpu_milli=cl.pod_cpu,
                                                memory=cl.pod_mem), **kw))
        self.pending.append(forms[0])
        self.bound.append(forms[1])

    def take(self):
        """A new incarnation in pending form: ``(incarnation, pod)``."""
        if self.free:
            s = self.free.popleft()
        else:
            s = len(self.pending)
            self._grow(s)
            self.made_late += 1
        inc = len(self.slot_of)
        self.slot_of.append(s)
        p = self.pending[s]
        p.name = f"{self.prefix}{inc}"
        p.queued_at = 0.0
        p.node_name = ""
        p.nominated_node_name = ""
        return inc, p

    def inc_of(self, key: str) -> int:
        return int(key[self._key_off:])

    def pending_form(self, inc: int):
        return self.pending[self.slot_of[inc]]

    def bound_form(self, inc: int, node: str):
        b = self.bound[self.slot_of[inc]]
        b.name = f"{self.prefix}{inc}"
        b.node_name = node
        return b

    def release(self, inc: int) -> None:
        self.free.append(self.slot_of[inc])
