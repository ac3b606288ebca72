"""Share of the chip's peak matrix throughput an admission's sparse
attention path reaches (%): the operations it must do at the rows' TRUE
lengths (``ctx["arch"].dsa_prefill_flops``: an index score for every
(layer, query row, row at or before it) pair and every head's score and
value products for every pair a row attends (the model's own count of
rows at most): the program's counters ``dsa.admit_pairs_scored`` and
``dsa.admit_pairs_attended`` over ``dsa.admit_calls``, a (layer, dispatch)
call's mean, times the masked sweep's calls the trace holds) over the
chip's peak
(``peaks.json`` ``bf16_tflops``), over the device time of the trace's
operations the architecture names for it (``dsa_trace_ops``: the kernel
that makes a block of rows' index scores, thresholds and masks, and the
masked flash sweep). The kernels compute a bucket's padding and every
causal pair, chosen or not, and the thresholds' bisection is vector work
the count leaves out: the share reads low by as much, and PERF.md says by
how much. Bound: compute. Nothing where the trace holds no such operation (no
admission in the span, or a program without the kernels) or the program
no such counters."""
from dsa_counters import mean_a_call, named_calls, share_of_peak


def read(ctx):
    arch = ctx["arch"]
    scored = mean_a_call(ctx, "dsa.admit_pairs_scored", "dsa.admit_calls")
    attended = mean_a_call(ctx, "dsa.admit_pairs_attended", "dsa.admit_calls")
    if not scored or not attended or not hasattr(arch, "dsa_prefill_flops"):
        return None
    ops = arch.dsa_trace_ops(ctx["cfg"])
    calls = named_calls(ctx, ops["prefill_calls"])
    return share_of_peak(
        ctx, ops["prefill"],
        arch.dsa_prefill_flops(ctx["cfg"], scored * calls, attended * calls),
        "bf16_tflops", 1e12)
