"""Third part of the host time at a block boundary at which the device
waited (histogram ``engine.boundary_enqueue_ms``, mean over the window):
the engine call that enqueues was entered -> its program call returned
(the call's preamble, an admission tick, the frontiers' uploads, the
dispatch). From there the device has its program. See
``engine.boundary_emit_ms`` for which boundaries are observed. Nothing
under a program without the series."""
from serve_counters import hist_mean_ms


def read(ctx):
    return hist_mean_ms(ctx, "engine.boundary_enqueue_ms")
