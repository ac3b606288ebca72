"""Share of the memory roofline EVA attention's decode kernel reaches (%):
the bytes the model attends (``ctx["arch"].eva_decode_bytes``: keys and
values of every head for the LIVE rows of each stream's window and the
summary rows it SEES, once a layer and step: the program's counters
``attn.eva_window_rows_live`` and ``attn.eva_summary_rows_visible`` over
``attn.eva_decode_calls``, a (layer, step) call's mean over all slots,
times the kernel's calls the trace holds) over the chip's peak bandwidth,
over the device time of the trace's operations the architecture names for
it (``eva_trace_ops``: the kernel ``eva_decode``). The kernel fetches whole
blocks of 128 rows of each buffer, a dead slot's one block too, and walks
the streams one after another: the share reads under 100 by as much
(PERF.md says by how much). Bound: memory. Nothing where the trace holds
no such operation (a program whose step sweeps both buffers by XLA) or
the program no such counters."""
from dsa_counters import mean_a_call, named_calls, share_of_peak


def read(ctx):
    arch, cfg = ctx["arch"], ctx["cfg"]
    calls = "attn.eva_decode_calls"
    window = mean_a_call(ctx, "attn.eva_window_rows_live", calls)
    summaries = mean_a_call(ctx, "attn.eva_summary_rows_visible", calls)
    if not window or summaries is None or not hasattr(
            arch, "eva_decode_bytes"):
        return None
    ops = arch.eva_trace_ops(cfg)["decode"]
    held = named_calls(ctx, ops)
    return share_of_peak(
        ctx, ops, arch.eva_decode_bytes(cfg, window * held, summaries * held),
        "hbm_gb_per_s", 1e9)
