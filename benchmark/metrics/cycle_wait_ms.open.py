"""Mean host ms per ``serving:cycle-wait`` event: the serving loop's wait
for its ingest lock between a window's flush and the cycle, from the
program's own annotation in the traced sub-window."""
from host_spans import mean_s


def read(view):
    s = mean_s(view, "serving:cycle-wait")
    return None if s is None else s * 1e3
