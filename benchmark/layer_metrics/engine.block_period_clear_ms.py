"""Mean period of the decode blocks between whose landings no admission
landed (histogram ``engine.block_period_clear_ms``): the block's steps on
the device and the boundary. What ``engine.block_period_ms`` reads above
it is what admissions cost the live streams. Nothing under a program
without the series."""
from serve_counters import hist_mean_ms


def read(ctx):
    return hist_mean_ms(ctx, "engine.block_period_clear_ms")
