"""Share of the held expert matrices that a decode step's expert block
reads (%): the program's counter ``moe.experts_hit`` (the distinct held
experts that some row of a step chose, counted on the device inside the
decode program over every row that goes through it, a dead slot's too,
and summed over the expert layers) over held experts x expert layers x
the decode steps counted (``moe.decode_steps``) across the window. The
gauge ``moe.decode_sorted`` says what the expert block chose when the
decode block's program was traced (``cake_tpu/ops/moe.py``
``expert_form``): the sorted form, which reads the matrices of the
experts that have rows and no others, or (0) the dense form, which reads
every held expert whatever the routing: 100. The configuration's
architecture says how many experts are held and how many layers route
(``ctx["arch"].held_experts``, ``expert_layers``). A program without the
counter or the gauge gives nothing."""
from counters import series_delta


def read(ctx):
    hit = series_delta(ctx, "moe.experts_hit")
    steps = series_delta(ctx, "moe.decode_steps")
    form = ctx["after"]["status"]["metrics"].get("moe.decode_sorted")
    arch = ctx["arch"]
    if hit is None or not steps or form is None or not hasattr(
            arch, "held_experts"):
        return None
    if not form.get("value"):
        return 100.0
    held = len(arch.held_experts(ctx["cfg"]))
    layers = arch.expert_layers(ctx["cfg"])
    if not held or not layers:
        return None
    return 100.0 * hit / (held * layers * steps)
