"""Mean time the engine's thread waited for an admission's first token,
per landed admission over the window (histogram ``engine.admit_land_ms``):
from the engine coming back for it to the token on the host: what was
left of the prefill, the sampling program, the fetch. No block is
enqueued while an admission is staged, so every live stream waits too.
Far above the traced tail's ``prof.admit`` gaps, it is the prefill's time
and not the fetch's: admissions chain. Nothing under a program without
the series."""
from serve_counters import hist_mean_ms


def read(ctx):
    return hist_mean_ms(ctx, "engine.admit_land_ms")
