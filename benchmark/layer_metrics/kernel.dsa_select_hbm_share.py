"""Share of the memory roofline a decode step's choice of rows reaches (%):
the bytes it must move (``ctx["arch"].dsa_select_bytes``: every live row's
float32 score read, each chosen row's score and number written: the
program's counters ``dsa.rows_live`` and ``dsa.rows_selected`` over
``dsa.decode_calls``, a (layer, step) call's, times the calls the trace
holds) over the chip's peak bandwidth, over the device time of the
trace's operations that make the choice (the architecture's
``dsa_trace_ops``: a kernel of the program's own or, where XLA makes it,
its sort of the decode batch's scores, ``sort.N f32[slots,capacity]``: the
shape is the configuration's, nothing else in the program sorts it). A sort moves its
rows many times over, so this reads low: that is the finding, and what a
threshold in place of the sort is held to. Bound: memory. Nothing where
the trace holds no such operation or the program no such counters."""
from dsa_counters import mean_a_call, named_calls, share_of_peak


def read(ctx):
    arch = ctx["arch"]
    live = mean_a_call(ctx, "dsa.rows_live", "dsa.decode_calls")
    chosen = mean_a_call(ctx, "dsa.rows_selected", "dsa.decode_calls")
    if not live or not chosen or not hasattr(arch, "dsa_select_bytes"):
        return None
    ops = arch.dsa_trace_ops(ctx["cfg"])["select"]
    calls = named_calls(ctx, ops)
    return share_of_peak(
        ctx, ops, arch.dsa_select_bytes(ctx["cfg"], live * calls,
                                        chosen * calls),
        "hbm_gb_per_s", 1e9)
