"""First part of the host time at a block boundary at which the device
WAITED (histogram ``engine.boundary_emit_ms``, mean over the window): the
block's fetch returned -> the landing engine call returned, i.e. the
recording of the block's rows. Observed only where a later engine call
enqueued the device's next program: a boundary that an admission
launched under the running block closed at once leaves nothing, so the
mean is a clear boundary's. With ``engine.boundary_pass_ms`` and
``engine.boundary_enqueue_ms`` it adds up to what ``engine.boundary_ms``
observes for that boundary, less the hand-out after the enqueue. Nothing
under a program without the series."""
from serve_counters import hist_mean_ms


def read(ctx):
    return hist_mean_ms(ctx, "engine.boundary_emit_ms")
