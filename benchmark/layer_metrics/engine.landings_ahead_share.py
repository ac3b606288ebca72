"""Share of the window's landings that left the device nothing to wait
for (counter ``engine.landings_ahead`` over counter
``engine.admit_launches``, percent): the splice and the device's next
program (the next arrival's prefill, else the next decode block) were
enqueued before the host fetched the landing's first token. 100 where
every landing goes so; a guided arrival's does not. Nothing under a
program without the series (one that fetches the token first), or where
nothing was launched."""
from counters import series_delta


def read(ctx):
    ahead = series_delta(ctx, "engine.landings_ahead")
    launches = series_delta(ctx, "engine.admit_launches")
    if ahead is None or not launches:
        return None
    return 100.0 * ahead / launches
