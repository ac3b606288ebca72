"""Share of the window's landings whose device half left before the rows
recorded before it were out (counter ``engine.landings_before_rows`` over
counter ``engine.admit_launches``, percent): the first tokens' sampler, the
splice and the device's next program were enqueued while the landed
block's rows were still being handed out, and the stream's install and its
first token followed those rows. Near 100 where every landing meets rows
that are going out; 0 where the engine keeps the two halves together (no
staging row fits beside the landing's; the paged layout). Nothing under a
program without the series (one whose landing waits for the rows), or
where nothing was launched."""
from counters import series_delta


def read(ctx):
    before_rows = series_delta(ctx, "engine.landings_before_rows")
    launches = series_delta(ctx, "engine.admit_launches")
    if before_rows is None or not launches:
        return None
    return 100.0 * before_rows / launches
