"""Bytes the serving cache's row buffers really hold for one token of one
stream, every plane of it (the program's gauge ``cache.token_bytes``: the
allocated row buffers' bytes over slots x window): a plane a layer that
keeps rows, and a plane a PASS too where the layers run several times a
token. What an int8 cache or one plane shared by the passes would move,
and what sets how many streams a chip holds. Nothing under a program
without the gauge."""


def read(ctx):
    series = ctx["after"]["status"]["metrics"].get("cache.token_bytes")
    if not series or not series.get("value"):
        return None
    return series["value"]
