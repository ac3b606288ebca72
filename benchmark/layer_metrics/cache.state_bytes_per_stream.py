"""Bytes of recurrent state a stream costs, whatever its length (the
program's gauge ``cache.state_bytes_per_stream``: the allocated state and
convolution-tail buffers' bytes over the slots): a delta-rule layer's
float32 state a head and the last inputs of its convolutions. Nothing
under a program without the gauge, or whose cache holds no such state."""


def read(ctx):
    series = ctx["after"]["status"]["metrics"].get(
        "cache.state_bytes_per_stream")
    if not series or not series.get("value"):
        return None
    return series["value"]
