"""Tokens the engine emitted per decode dispatch over the window
(``serve.tokens_emitted`` over the count of ``serve.decode_dispatch_ms``):
live rows times the steps of a block."""
from counters import series_delta


def read(ctx):
    tokens = series_delta(ctx, "serve.tokens_emitted")
    dispatches = series_delta(ctx, "serve.decode_dispatch_ms", "count")
    if not tokens or not dispatches:
        return None
    return tokens / dispatches
