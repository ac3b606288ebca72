"""Share of the memory roofline a decode step reaches: the bytes a step
must read (``ctx["arch"].decode_step_bytes``, the count of the
configuration's architecture: weights as stored, the routed experts, the
live streams' cached state) over the chips' peak bandwidth
(``peaks.json``), over the step's device time. Bound: memory."""
from counters import decode_step_ms, series_delta


def read(ctx):
    step_ms = decode_step_ms(ctx)
    tokens = series_delta(ctx, "serve.tokens_emitted")
    dispatches = series_delta(ctx, "serve.decode_dispatch_ms", "count")
    if not step_ms or not tokens or not dispatches or not ctx["peaks"]:
        return None
    b = ctx["cfg"]["bench"]
    rows = tokens / dispatches / b["decode_block"]  # live streams a step
    done = [r for r in ctx["records"] if r["ok"]]
    if not done:
        return None
    context = sum(r["prompt_len"] + r["asked"] / 2 for r in done) / len(done)
    need = ctx["arch"].decode_step_bytes(
        ctx["cfg"], b["weights"]["layout"], rows, context, b["serve_dtype"])
    floor_s = need / (ctx["chips"] * ctx["peaks"]["hbm_gb_per_s"] * 1e9)
    return 100.0 * floor_s / (step_ms / 1e3)
