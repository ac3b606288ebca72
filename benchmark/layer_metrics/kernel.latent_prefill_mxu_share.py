"""Share of the chip's peak matrix throughput a blocked latent admission's
own-chunk attention reaches (%): the operations it must do at the rows'
TRUE lengths and the heads' TRUE widths (``ctx["arch"]
.latent_prefill_flops``: every head's 192-wide score and 128-wide value
product for every causal pair) over the chip's peak (``peaks.json``
``bf16_tflops``), over the device time of the trace's operations the
architecture names for it (``latent_trace_ops``: the flash prefill kernel
over the expanded keys, ``latent_prefill``). The pairs: each traced call's
causal pairs at its BUCKET's length, read off the operation's own shape
(``arch.latent_prefill_pairs_handed``), times the share of the handed
pairs that were a true token's (the program's counters
``attn.latent_admit_pairs`` over ``attn.latent_admit_pairs_handed``: a
capture closes long after the traced span, so a counter's growth covers
more calls than the trace holds, of other buckets too; a ratio does not
care). The kernel also computes the diagonal blocks whole and 64 zero
channels a key beside the 192 true ones: the share reads low by as much
(PERF.md says by how much). Bound: compute. Nothing where the trace holds
no such operation (no blocked admission in the span) or the program no
such counters."""
import re

from counters import series_delta
from dsa_counters import share_of_peak


def read(ctx):
    arch, cfg, trace = ctx["arch"], ctx["cfg"], ctx.get("trace")
    true = series_delta(ctx, "attn.latent_admit_pairs")
    handed = series_delta(ctx, "attn.latent_admit_pairs_handed")
    if (not true or not handed or not trace or not trace["devices"]
            or not hasattr(arch, "latent_prefill_flops")):
        return None
    ops = arch.latent_trace_ops(cfg)["prefill"]
    pairs = sum(calls * arch.latent_prefill_pairs_handed(name)
                for name, _, calls in trace["devices"][0]["ops"]
                if re.match(ops, name))
    return share_of_peak(
        ctx, ops, arch.latent_prefill_flops(cfg, pairs * true / handed),
        "bf16_tflops", 1e12)
