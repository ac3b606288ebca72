"""Share of the (row, chosen expert) pair rows handed to the expert
block's sorted form that it neither read nor wrote (%): 100 x (1 -
``moe.sorted_pair_rows_live`` / ``moe.sorted_pair_rows``) across the
window. The program counts both on the device, a sorted-form call and
expert layer (``cake_tpu/ops/moe.py`` ``ExpertCount``): the pair rows the
call was handed, ``rows x top_k``, and the rows of the row tiles it
touched, those that hold a pair on a held expert (the pairs are sorted
held ones first, so they are the leading ``live tiles x row tile``); the
engine fetches an admission's counts once its program has run and a
decode block's with the block. 0: every expert the router scores is held,
every tile is live; a chip that holds one share in sixteen of the experts
skips about fifteen sixteenths of an admission's rows, and every tile but
one of a 32-row step's two. A program without the counters, or a window
whose expert calls all took another form, gives nothing."""
from counters import series_delta


def read(ctx):
    handed = series_delta(ctx, "moe.sorted_pair_rows")
    live = series_delta(ctx, "moe.sorted_pair_rows_live")
    if not handed or live is None:
        return None
    return 100.0 * (1.0 - live / handed)
