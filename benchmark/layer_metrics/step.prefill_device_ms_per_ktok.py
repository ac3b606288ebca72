"""Device time of the admission programs per thousand prompt tokens
admitted while the trace was open. In a closed loop an admission is most
of a request's wait for its first token: it moves ``ttft_mean_ms``."""
from counters import prefill_ms_per_ktok as read  # noqa: F401
