"""Seconds of the window lost to scheduler passes longer than a second
(``prof.slow_pass_ms`` over the window): 0 in a clean run. A decode
block takes ~0.2 s and the longest admission ~0.15 s, so a pass of
seconds is a stall, and ``/debug/prof`` ``slow_passes`` keeps which part
of the pass held it."""
from counters import series_delta


def read(ctx):
    ms = series_delta(ctx, "prof.slow_pass_ms")
    return None if ms is None else ms / 1000.0
