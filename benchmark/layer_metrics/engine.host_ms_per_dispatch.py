"""Host self-time of the engine per decode dispatch: the phases admit,
dispatch (pages nests inside it) and emit of ``/debug/prof``, without
``sync`` (where the device's time lands). Host clock around dispatches:
never device time. The traced run stamps every step
(``--prof-sample 1``)."""
from counters import phase_delta


def read(ctx):
    dispatches = phase_delta(ctx, "dispatch", "count")
    if not dispatches:
        return None
    return sum(phase_delta(ctx, p, "sum")
               for p in ("admit", "dispatch", "emit")) / dispatches
