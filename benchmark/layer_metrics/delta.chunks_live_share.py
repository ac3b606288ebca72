"""Share of the chunks the delta-rule layers' admission scans ran through
that held a true token (%): the program's counters ``delta.chunks_live``
over ``delta.chunks_swept`` across the window (a launch: delta-rule layers
x its rows x ceil(bucket / 64) swept, the same with each row's own length
live). The scan is serial, so a bucket's padding costs its chunks where
attention would skip blocks: under 100 it is what a scan that stopped at a
row's true length, or a bucket nearer the prompt, would save. A program
without the counters (no delta-rule layer, or an older program) gives
nothing."""
from counters import series_delta


def read(ctx):
    live = series_delta(ctx, "delta.chunks_live")
    swept = series_delta(ctx, "delta.chunks_swept")
    if live is None or not swept:
        return None
    return 100.0 * live / swept
