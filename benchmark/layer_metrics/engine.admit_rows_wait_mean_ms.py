"""Mean time a launched admission waited behind the running block and its
rows, per landed admission over the window (histogram
``engine.admit_rows_wait_ms``): from its first prefill dispatch returning
to the engine coming back for its first token (a landed block's rows all
go out first). Its prefill runs on the device meanwhile; a chunked
admission's later chunks are in here. Nothing under a program without the
series."""
from serve_counters import hist_mean_ms


def read(ctx):
    return hist_mean_ms(ctx, "engine.admit_rows_wait_ms")
