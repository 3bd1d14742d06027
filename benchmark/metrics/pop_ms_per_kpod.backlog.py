"""Host ms per 1,000 pods bound in the ``pop`` span: queue upkeep, the
pop and PreFilter, from the cycle start to the snapshot. None when the
cycles carry no such span."""
from readers import per_kpod_ms


def read(view):
    if not any(c.spans and "pop" in c.spans["durations"]
               for c in view.cycles):
        return None
    return per_kpod_ms(view, names=("pop",))
