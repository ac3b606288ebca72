"""Share of an expert model's admitted prompt rows whose expert block
computed the routed pairs only (%): the program's counters
``moe.admit_rows_sorted`` over ``moe.admit_rows`` across the window. The
engine adds a bucket's rows to the first on every admission dispatch, and
to the second where the expert block recorded the sorted form when that
bucket's program was traced (``cake_tpu/ops/moe.py`` ``expert_form``: a
function of the call's rows and the stacks' type). 100: every bucket the
traffic met runs the sorted form; 0: every one runs every held expert
over every row. A program without the counters, or a window that admitted
nothing, gives nothing."""
from counters import series_delta


def read(ctx):
    rows = series_delta(ctx, "moe.admit_rows")
    ordered = series_delta(ctx, "moe.admit_rows_sorted")
    if not rows or ordered is None:
        return None
    return 100.0 * ordered / rows
