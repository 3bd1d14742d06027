"""Mean host microseconds per owner-count upkeep in the pod handlers
(the program's ``cache.owner_counts`` annotation, nested in
``on_pod_add`` / ``on_pod_update`` / ``on_pod_delete``): filing a pod
under its labels and counting it into or out of the owner sets it
matches. None where the program has no such annotation."""
from host_spans import mean_s


def read(view):
    s = mean_s(view, "cache.owner_counts")
    return None if s is None else s * 1e6
