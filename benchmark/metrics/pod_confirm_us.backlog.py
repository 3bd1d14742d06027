"""Mean host microseconds per ``on_pod_update`` call (the confirmation
of a binding), from the program's own annotation in the traced sub-window."""
from host_spans import mean_s


def read(view):
    s = mean_s(view, "on_pod_update")
    return None if s is None else s * 1e6
