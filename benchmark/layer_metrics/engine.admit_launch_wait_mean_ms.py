"""Mean time an arrival waited before the device had its prompt, per
landed admission over the window (histogram
``engine.admit_launch_wait_ms``): from ``enqueue()`` to the return of its
first prefill dispatch. No slot was free, or another admission was
staged; the device is busy with everybody else meanwhile. The first of
an admission's four stages, which add up to
``engine.admit_to_first_mean_ms`` less the first token's way to the
session. Nothing under a program without the series."""
from serve_counters import hist_mean_ms


def read(ctx):
    return hist_mean_ms(ctx, "engine.admit_launch_wait_ms")
