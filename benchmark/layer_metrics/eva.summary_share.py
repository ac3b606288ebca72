"""Of the rows a decode step's EVA attention attends, the share that are
SUMMARIES (%): ``attn.eva_summary_rows_visible`` over itself plus
``attn.eva_window_rows_live`` in the measured window. It says how much of
a step's attention the traffic puts on the mechanism: 0 where no stream
has a completed window behind it (the model is then a dense multi-head
decoder over a short window), about a third in a mix whose streams stand
at 4k-15k positions. Nothing against a program that lacks the counters."""
from counters import series_delta


def read(ctx):
    live = series_delta(ctx, "attn.eva_window_rows_live")
    seen = series_delta(ctx, "attn.eva_summary_rows_visible")
    if live is None or seen is None or not live + seen:
        return None
    return 100.0 * seen / (live + seen)
