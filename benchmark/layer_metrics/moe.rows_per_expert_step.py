"""Rows a held expert sees in a decode step: the live rows' routed (row,
expert) pairs that fell on the experts held here (``moe.local_pairs``:
counted on the device a batch row inside the decode program, added up
over the rows that were live when the block was dispatched) over the held
experts, the expert layers and the decode steps counted
(``moe.decode_steps``). The configuration's architecture says how many
experts are held and how many layers route (``ctx["arch"].held_experts``,
``expert_layers``). At uniform routing it is live rows x top-k / the
router's experts: how near the deployment's load the held experts are."""
from counters import series_delta


def read(ctx):
    local = series_delta(ctx, "moe.local_pairs")
    steps = series_delta(ctx, "moe.decode_steps")
    arch = ctx["arch"]
    if local is None or not steps or not hasattr(arch, "held_experts"):
        return None
    held = len(arch.held_experts(ctx["cfg"]))
    layers = arch.expert_layers(ctx["cfg"])
    if not held or not layers:
        return None
    return local / (held * layers * steps)
