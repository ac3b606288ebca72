"""What fetching an expert model's per-block counts costs the engine's
thread (histogram ``engine.landing_counts_fetch_ms``, mean over the
window): the device-to-host round trips that feed ``moe.local_pairs`` and
``moe.experts_hit``, once per engine call that fetched some. The program
makes them after the device's next program is enqueued, so this is host
time under a running block, not idle time of the device. Nothing where no
decode program counts, and under a program without the series."""
from serve_counters import hist_mean_ms


def read(ctx):
    return hist_mean_ms(ctx, "engine.landing_counts_fetch_ms")
