"""Second part of the host time at a block boundary at which the device
waited (histogram ``engine.boundary_pass_ms``, mean over the window): the
landing engine call returned -> the engine call that enqueues was
entered, i.e. the scheduler's pass between the two (deliver, retire, its
sweeps and stats snapshot, the wait for its lock, admitting from the
queue). See ``engine.boundary_emit_ms`` for which boundaries are
observed. Nothing under a program without the series."""
from serve_counters import hist_mean_ms


def read(ctx):
    return hist_mean_ms(ctx, "engine.boundary_pass_ms")
