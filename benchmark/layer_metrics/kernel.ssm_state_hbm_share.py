"""Share of the memory roofline the state-space decode kernel reaches: the
bytes its calls must move (``ctx["arch"].ssm_decode_bytes``: one read and
one write of each slot's float32 state and the step's delta, x, B, C and
y, for every slot the program carries) over the chip's peak bandwidth
(``peaks.json``), over the kernel's own device time in the traced span
(the trace's operations named ``ssm_decode``). Bound: memory. Nothing
where the trace holds no such operation (a program without the kernel)
or the architecture counts no such bytes."""
import re


def read(ctx):
    trace, arch = ctx.get("trace"), ctx["arch"]
    if (not trace or not trace["devices"] or not ctx["peaks"]
            or not hasattr(arch, "ssm_decode_bytes")):
        return None
    calls = [(seconds, count) for name, seconds, count
             in trace["devices"][0]["ops"] if re.match(r"ssm_decode\b", name)]
    seconds = sum(s for s, _ in calls)
    if not seconds:
        return None
    need = arch.ssm_decode_bytes(ctx["cfg"], ctx["cfg"]["bench"]["slots"])
    floor_s = sum(c for _, c in calls) * need / (
        ctx["peaks"]["hbm_gb_per_s"] * 1e9)
    return 100.0 * floor_s / seconds
