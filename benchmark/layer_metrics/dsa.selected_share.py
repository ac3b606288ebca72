"""Share of a stream's cached rows a decode step attends under a learned
sparse attention (%): the program's counters ``dsa.rows_selected`` over
``dsa.rows_live`` across the window (a layer, a step and a stream:
the rows chosen, at most the model's own count, over ``frontier + 1``, from the
positions as dispatched). 100: every stream is under that count
and the choice drops nothing; lower: how sparse the traffic makes a step.
A program without the counters gives nothing."""
from counters import series_delta


def read(ctx):
    live = series_delta(ctx, "dsa.rows_live")
    chosen = series_delta(ctx, "dsa.rows_selected")
    if not live or chosen is None:
        return None
    return 100.0 * chosen / live
