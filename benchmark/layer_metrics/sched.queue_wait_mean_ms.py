"""Mean wait of a request between its submission and the scheduler
handing it to the engine (``serve.queue_wait_ms`` over the window): the
wait for a slot and for undelivered rows. In a closed loop it is the
first of the two legs of ``ttft_mean_ms``."""
from serve_counters import queue_wait_mean_ms as read  # noqa: F401
