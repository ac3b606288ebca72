"""What the readers of a learned sparse attention's metrics share
(``layer_metrics/kernel.dsa_*``): the device time and the calls of the
trace's operations a pattern names, a program counter's mean a call, and
a count's share of a peak over the operations' time. The counts are the
architecture's (``ctx["arch"].dsa_*``). A capture closes seconds after the
traced span ends and the program runs on meanwhile, so a counter's growth
"around the capture" covers more calls than the trace holds: a reader
takes the counter's MEAN a call (its growth over the growth of the
program's own count of calls) times the calls the trace holds."""

from __future__ import annotations

import re


def named_seconds(ctx: dict, pattern: str) -> float | None:
    """Device seconds of the first device's operations whose names match
    ``pattern`` (the reduced trace keeps the operations of most device
    time); None without a trace or where none matches."""
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    seconds = sum(s for name, s, _ in trace["devices"][0]["ops"]
                  if re.match(pattern, name))
    return seconds or None


def named_calls(ctx: dict, pattern: str) -> int:
    """How many events of the first device's operations match
    ``pattern``."""
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return 0
    return sum(c for name, _, c in trace["devices"][0]["ops"]
               if re.match(pattern, name))


def mean_a_call(ctx: dict, series: str, calls: str) -> float | None:
    """Growth of the counter ``series`` over the growth of the counter
    ``calls`` (the program's own count of the calls ``series`` sums
    over); None where either is missing or nothing was called."""
    from counters import series_delta

    total, n = series_delta(ctx, series), series_delta(ctx, calls)
    if total is None or not n:
        return None
    return total / n


def share_of_peak(ctx: dict, pattern: str, need: float | None,
                  peak: str, unit: float) -> float | None:
    """``need`` (bytes or operations) over the chip's ``peak`` (a key of
    ``peaks.json``, in ``unit`` a second) over the device time of the
    operations ``pattern`` names, in %; None where any of them is
    missing."""
    seconds = named_seconds(ctx, pattern)
    if not seconds or not need or not ctx.get("peaks"):
        return None
    return 100.0 * need / (ctx["peaks"][peak] * unit) / seconds
