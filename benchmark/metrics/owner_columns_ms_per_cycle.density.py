"""Host ms per cycle building and placing the cycle's owner columns (the
program's ``snapshot:owner_columns`` span, nested in ``snapshot``), over
the window's traced cycles. None where no cycle has the span."""

NAME = "snapshot:owner_columns"


def read(view):
    total, n, seen = 0.0, 0, False
    for c in view.cycles:
        if not c.spans:
            continue
        n += 1
        s = c.spans["durations"].get(NAME)
        if s is not None:
            total += s
            seen = True
    return total * 1e3 / n if seen else None
