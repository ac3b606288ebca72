"""Share of the memory roofline a decode step's index scoring reaches (%):
the bytes its calls must read (``ctx["arch"].dsa_index_bytes``: one index
key for every row up to a stream's frontier: the program's counters
``dsa.rows_live`` over ``dsa.decode_calls``, the frontiers' sum a (layer,
step) call as dispatched, times the calls the trace holds) over the
chip's peak bandwidth
(``peaks.json``), over the device time of the trace's operations the
architecture names for it (``dsa_trace_ops``: the kernel that scores a
stream's rows up to its frontier).
Bound: memory. Nothing where the trace holds no such operation (a program
without the kernel), the program has no such counter, or the architecture
counts no such bytes."""
from dsa_counters import mean_a_call, named_calls, share_of_peak


def read(ctx):
    arch = ctx["arch"]
    live = mean_a_call(ctx, "dsa.rows_live", "dsa.decode_calls")
    if not live or not hasattr(arch, "dsa_index_bytes"):
        return None
    ops = arch.dsa_trace_ops(ctx["cfg"])["index"]
    return share_of_peak(
        ctx, ops, arch.dsa_index_bytes(ctx["cfg"],
                                       live * named_calls(ctx, ops)),
        "hbm_gb_per_s", 1e9)
