"""Share of the memory roofline the plain latent decode kernel reaches (%):
the bytes it must read (``ctx["arch"].latent_decode_bytes``: the latent
row ``[c | k_pe]`` of every live stream up to its frontier, once a plane
and step: the program's counters ``attn.latent_rows_live`` over
``attn.latent_decode_calls``, a (plane, step) call's mean, times the
kernel's calls the trace holds) over the chip's peak bandwidth, over the
device time of the trace's operations the architecture names for it
(``latent_trace_ops``: the kernel ``latent_decode``). The kernel fetches
whole blocks of 512 rows, a dead slot's one block too, and its two
products hide behind the fetch only where a block is full: the share reads
under 100 by as much (PERF.md says by how much). Bound: memory. Nothing
where the trace holds no such operation (a program whose step sweeps by
XLA) or the program no such counters."""
from dsa_counters import mean_a_call, named_calls, share_of_peak


def read(ctx):
    arch, cfg = ctx["arch"], ctx["cfg"]
    rows = mean_a_call(ctx, "attn.latent_rows_live",
                       "attn.latent_decode_calls")
    if not rows or not hasattr(arch, "latent_decode_bytes"):
        return None
    ops = arch.latent_trace_ops(cfg)["decode"]
    return share_of_peak(
        ctx, ops, arch.latent_decode_bytes(cfg, rows * named_calls(ctx, ops)),
        "hbm_gb_per_s", 1e9)
