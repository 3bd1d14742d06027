"""``cluster.build``: the same seed builds the same objects.

``sp5k-base`` (and the same nodes with every preference the generator
knows) is pinned by a digest of what the scheduler is fed: names,
labels, taints, requests, owners, spread selectors, preferred affinity
and node assignment of nodes, existing pods and a pool of pod slots. A
change to ``build`` that moves one draw, or one object, of a
configuration that states none of the newer keys fails here."""

import hashlib
import json
import os

import pytest

from conftest import BENCH, TINY_PREFS

import cluster as C


def _selector(sel):
    return (sorted(sel.match_labels.items()),
            [repr(r) for r in sel.match_expressions])


def _pod(p):
    return (p.name, p.namespace, sorted(p.labels.items()), p.node_name,
            p.requests.cpu_milli, p.requests.memory, p.owner_uid,
            [_selector(s) for s in p.spread_selectors],
            repr(p.affinity.node_preferred), repr(p.affinity.node_required),
            repr(p.tolerations))


def _node(n):
    return (n.name, sorted(n.labels.items()),
            [(t.key, t.value, t.effect) for t in n.taints],
            n.allocatable.cpu_milli, n.allocatable.memory,
            n.allocatable.pods)


def digest(config: dict, seed: int, n_slots: int = 1024) -> str:
    cl = C.build(config, seed, n_slots)
    pool = C.PodPool(config, cl, n_slots)
    h = hashlib.sha256()
    for part in ([_node(n) for n in cl.nodes],
                 [_pod(p) for p in cl.existing],
                 [_pod(p) for p in pool.pending],
                 [_pod(p) for p in pool.bound]):
        h.update(repr(part).encode())
    return h.hexdigest()


def _config(prefs: bool) -> dict:
    with open(os.path.join(BENCH, "configs", "sp5k-base.json")) as f:
        cfg = json.load(f)
    if prefs:
        cfg["cluster"].update(TINY_PREFS)
    return cfg


#: computed with ``build`` as it was before configurations could
#: state controller-owned existing pods and a pod shape of their own
PINNED = {
    ("sp5k-base", 1):
        "75f7c166fb0fa19b5566dee044a5e5baeaca2084bdc6249cee4827efc8962370",
    ("sp5k-base", 2**31 + 12345):
        "020e4231208fca5515448b4a076a450633c82d7214a1b6ae171ebd01bd864ba1",
    ("prefs", 1):
        "6e18ca4aeb49df50ad563f5dd294141b1348bd0842e6fa8cb015052cf26de6d1",
    ("prefs", 2**31 + 12345):
        "d9127a268b6dfd82b3c634fb66080d1a37f0eb8c941329ac28b1adb2e9db5efa",
}


@pytest.mark.parametrize("name,seed", sorted(PINNED))
def test_inputs_of_a_configuration_without_new_keys_are_pinned(name, seed):
    assert digest(_config(name == "prefs"), seed) == PINNED[(name, seed)]
