"""``step.prefill_device_ms_per_ktok`` in an open-loop cell, where no
time to first token carries a bound: there an admission's device time is
what it stalls the live streams by, so it moves ``tpot_p50_ms``."""
from counters import prefill_ms_per_ktok as read  # noqa: F401
