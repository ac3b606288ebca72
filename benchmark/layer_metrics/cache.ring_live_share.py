"""Share of the window layers' ring rows a decode step reads that hold a
key its query may see (%): the program's counters ``attn.ring_rows_live``
(over every slot, decode step and window layer, the position + 1 rows the
stream has written, or the window's if fewer, of the batch as dispatched)
over ``attn.ring_rows_swept``
(the ring whole: ``cache.ring_rows`` a slot, step and window layer, which
is what a step's attention reads of it whatever the stream holds) across
the window. Under 100 it is what a ring step that read live rows alone
would save: short streams whose rings are part empty, and slots without a
live stream. A program without the counters (no layer attends through a
ring, or an older program) gives nothing."""
from counters import series_delta


def read(ctx):
    live = series_delta(ctx, "attn.ring_rows_live")
    swept = series_delta(ctx, "attn.ring_rows_swept")
    if live is None or not swept:
        return None
    return 100.0 * live / swept
