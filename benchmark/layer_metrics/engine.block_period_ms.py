"""Mean period of a decode block over the window (histogram
``engine.block_period_ms``): a block's landing less the previous block's,
what a live stream waits for its next ``decode_block`` tokens; over
``decode_block`` it is the mean token gap on the engine's side. An idle
engine's wait for a request closes no period. Nothing under a program
without the series."""
from serve_counters import hist_mean_ms


def read(ctx):
    return hist_mean_ms(ctx, "engine.block_period_ms")
