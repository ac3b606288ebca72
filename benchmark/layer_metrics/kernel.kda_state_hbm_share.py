"""Share of the memory roofline the delta-rule decode kernel reaches: the
bytes its calls must move (``ctx["arch"].kda_decode_bytes``: one read and
one write of each head's float32 state and the step's decay, k, beta k, q,
v and o, for every slot the program carries) over the chip's peak
bandwidth (``peaks.json``), over the kernel's own device time in the
traced span (the trace's operations named ``kda_decode``). Bound: memory.
Nothing where the trace holds no such operation (a program without the
kernel) or the architecture counts no such bytes."""
import re


def read(ctx):
    trace, arch = ctx.get("trace"), ctx["arch"]
    if (not trace or not trace["devices"] or not ctx["peaks"]
            or not hasattr(arch, "kda_decode_bytes")):
        return None
    calls = [(seconds, count) for name, seconds, count
             in trace["devices"][0]["ops"] if re.match(r"kda_decode\b", name)]
    seconds = sum(s for s, _ in calls)
    if not seconds:
        return None
    need = arch.kda_decode_bytes(ctx["cfg"], ctx["cfg"]["bench"]["slots"])
    floor_s = sum(c for _, c in calls) * need / (
        ctx["peaks"]["hbm_gb_per_s"] * 1e9)
    return 100.0 * floor_s / seconds
