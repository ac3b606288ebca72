"""Mean host time from an admission's first token on the host to its
splice program enqueued, per landed admission over the window (histogram
``engine.admit_to_splice_ms``): the device has nothing to run while it
lasts. Nothing under a program without the series."""
from serve_counters import hist_mean_ms


def read(ctx):
    return hist_mean_ms(ctx, "engine.admit_to_splice_ms")
