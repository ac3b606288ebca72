"""Share of the memory roofline a decode step's attention over its chosen
rows reaches (%): the bytes it must read (``ctx["arch"].dsa_attend_bytes``:
the latent row of every CHOSEN row, once: the program's counters
``dsa.rows_selected`` over ``dsa.decode_calls``, a (layer, step) call's,
at most the model's own count of rows a stream whatever its frontier,
times the kernel's calls the trace holds) over the chip's peak bandwidth,
over the device time of the trace's operations that fetch and attend them
(the architecture's ``dsa_trace_ops``: the kernel over the chosen rows and
the gather that brings them out of the carried buffer, which XLA names by
its result's shape). A whole sweep of the latent buffer to the frontiers
would read ``dsa.rows_live`` rows instead: ``dsa.selected_share`` says how
many times more. Bound: memory. Nothing where the trace holds no such
operation or the program no such counter."""
from dsa_counters import mean_a_call, named_calls, share_of_peak


def read(ctx):
    arch, cfg = ctx["arch"], ctx["cfg"]
    chosen = mean_a_call(ctx, "dsa.rows_selected", "dsa.decode_calls")
    if not chosen or not hasattr(arch, "dsa_attend_bytes"):
        return None
    ops = arch.dsa_trace_ops(cfg)
    chosen *= named_calls(ctx, ops["attend_calls"])
    return share_of_peak(ctx, ops["attend"],
                         arch.dsa_attend_bytes(cfg, chosen),
                         "hbm_gb_per_s", 1e9)
