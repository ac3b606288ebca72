"""Share of a whole cache that the row buffers hold (%), where some layers
attend through a sliding window: the program's gauges ``cache.rows_bytes``
(what the row buffers of both kinds hold as allocated: the full layers'
rows at the capacity and the window layers' rings of ``cache.ring_rows``
rows a stream) over ``cache.rows_bytes_full`` (what they would hold were
every window layer a full one at the capacity). With ``f`` full and ``w``
window layers at a capacity of ``S`` rows it is ``(f S + w R) / ((f + w)
S)``: what the rings leave of the cache, and so how many streams of how
many rows fit beside the weights. A program without the gauges (no layer
attends through a ring) gives nothing."""


def read(ctx):
    metrics = ctx["after"]["status"]["metrics"]
    held = metrics.get("cache.rows_bytes")
    whole = metrics.get("cache.rows_bytes_full")
    if held is None or whole is None or not whole.get("value"):
        return None
    return 100.0 * held["value"] / whole["value"]
