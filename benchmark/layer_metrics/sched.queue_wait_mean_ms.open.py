"""``sched.queue_wait_mean_ms`` in an open-loop cell, where no time to
first token carries a bound: a request that waits in the queue is
admitted among live streams later, so it moves ``tpot_p50_ms``."""
from serve_counters import queue_wait_mean_ms as read  # noqa: F401
