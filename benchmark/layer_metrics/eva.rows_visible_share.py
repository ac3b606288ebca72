"""Of the rows a decode step's EVA attention FETCHES (ring and summary
plane together), the share its queries attend (%, higher is better): the
program's counters ``attn.eva_window_rows_live`` +
``attn.eva_summary_rows_visible`` over ``attn.eva_rows_read`` in the
measured window, all three from the positions as dispatched. Near 100 where
the step reads each buffer to its own frontier (what is left is the part
of each frontier's block past it); ~50 where both buffers are swept whole.
Nothing against a program that lacks the counters."""
from counters import series_delta


def read(ctx):
    live = series_delta(ctx, "attn.eva_window_rows_live")
    seen = series_delta(ctx, "attn.eva_summary_rows_visible")
    read_ = series_delta(ctx, "attn.eva_rows_read")
    if live is None or seen is None or not read_:
        return None
    return 100.0 * (live + seen) / read_
