"""Mean host time at a block boundary over the window (histogram
``engine.boundary_ms``, observed once per landed block): from the block's
fetch returning to the return of the engine call that enqueued the
device's next program, the next block or a waiting arrival's prefill.
While it lasts the device has nothing to run. Nothing under a program
without the series."""
from serve_counters import hist_mean_ms


def read(ctx):
    return hist_mean_ms(ctx, "engine.boundary_ms")
