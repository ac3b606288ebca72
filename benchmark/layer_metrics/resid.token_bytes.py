"""Bytes one token's residual state holds between sub-layers, in the
serving type (the program's gauge ``resid.token_bytes``: as many hidden
vectors as the stream is wide): what every sub-layer reads and writes of a
row, four times a plain model's where the stream is four hidden vectors
wide. Nothing under a program without the gauge."""


def read(ctx):
    series = ctx["after"]["status"]["metrics"].get("resid.token_bytes")
    if not series or not series.get("value"):
        return None
    return series["value"]
