"""``engine.admit_to_first_mean_ms`` in an open-loop cell: there an
admission runs between the live streams' blocks, so what it takes is
what it stalls them by, and it moves ``tpot_p50_ms``."""
from serve_counters import admit_to_first_mean_ms as read  # noqa: F401
