"""Share of the decode steps' routed pairs that fell on zero-compute
outputs of the router (%): the program's counters ``moe.zero_pairs`` over
``moe.routed_pairs`` across the window (both over a decode step's live
rows x top-k x expert layers: the first counted on the device a batch row
and added up over the rows live at dispatch, the second reckoned from the
live rows). A pair on such an output costs no expert: no row tile, no
matrix read, one multiply-add of the token's own input. About the
zero-compute outputs' share of the router's (a third at 256 of 768) under
uniform routing; what says a later change still skips them. A program
without either counter (a router that scores experts alone reports no
``moe.zero_pairs``) gives nothing."""
from counters import series_delta


def read(ctx):
    zero = series_delta(ctx, "moe.zero_pairs")
    routed = series_delta(ctx, "moe.routed_pairs")
    if zero is None or not routed:
        return None
    return 100.0 * zero / routed
