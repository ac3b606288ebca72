"""Bytes the serving cache really holds for one token of one layer (the
program's gauge ``cache.row_bytes``: the allocated buffers' bytes over
layers x slots x window):
per-head keys and values, or latent attention's one shared row."""


def read(ctx):
    series = ctx["after"]["status"]["metrics"].get("cache.row_bytes")
    if not series or not series.get("value"):
        return None
    return series["value"]
