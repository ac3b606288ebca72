"""What the readers of the serving plane's own latency counters share
(PR 24; ``counters.py`` is the accepted benchmark's and stays as it is;
this file lies beside it because readers import from ``benchmark/``).
Each returns None where the program has no such series, as a program
older than PR 24 has not."""
from counters import series_delta


def hist_mean_ms(ctx, name):
    """Mean of a ``/metrics`` histogram's observations over the window:
    growth of its sum over growth of its count."""
    count = series_delta(ctx, name, "count")
    total = series_delta(ctx, name, "sum")
    if not count or total is None:
        return None
    return total / count


def queue_wait_mean_ms(ctx):
    """``serve.queue_wait_ms``: submit -> handed to the engine, observed
    by the scheduler where it admits a request."""
    return hist_mean_ms(ctx, "serve.queue_wait_ms")


def admit_to_first_mean_ms(ctx):
    """``serve.admit_to_first_ms``: handed to the engine -> first token
    emitted, observed by the session at its first token."""
    return hist_mean_ms(ctx, "serve.admit_to_first_ms")
