"""Mean time from the scheduler handing a request to the engine to its
first emitted token (``serve.admit_to_first_ms`` over the window):
admission, prefill and the block the stream joined. The second leg of
``ttft_mean_ms``; with ``sched.queue_wait_mean_ms`` it adds up to the
server's own ``serve.ttft_ms``."""
from serve_counters import admit_to_first_mean_ms as read  # noqa: F401
