"""Deepest admission queue seen while the window was open (``queued`` of
``/healthz``, the number behind ``serve.queue_depth``, polled 4x a second
by the parent)."""


def read(ctx):
    t0, t1 = ctx["window"]
    depths = [q for t, q, _ in ctx["poll"] if t0 <= t < t1]
    return max(depths) if depths else None
