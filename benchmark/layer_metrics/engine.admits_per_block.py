"""Admissions landed per block period over the window (counter
``engine.admissions_landed`` over the count of
``engine.block_period_ms``): how many admissions ride a live stream's
wait for its next block. (period - clear period) / this is what one
admission costs every live stream. Nothing under a program without the
series, or where no period closed."""
from counters import series_delta


def read(ctx):
    landed = series_delta(ctx, "engine.admissions_landed")
    periods = series_delta(ctx, "engine.block_period_ms", "count")
    if landed is None or not periods:
        return None
    return landed / periods
