"""Device time of one decode step: the decode program's module events in
the trace over the steps they ran (dispatches x the block's steps)."""
from counters import decode_step_ms as read  # noqa: F401
