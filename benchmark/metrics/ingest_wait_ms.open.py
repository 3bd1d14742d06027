"""Mean host ms per ``serving:ingest-wait`` event: a producer's wait for
the serving loop's ingest lock before its event handlers run, from the
program's own annotation in the traced sub-window."""
from host_spans import mean_s


def read(view):
    s = mean_s(view, "serving:ingest-wait")
    return None if s is None else s * 1e3
