"""Median time to first token from the instant a request was due, in an
open-loop cell. There a request meets the decode blocks at a random
point of their 0.2 s cycle, so over a hundred requests the median moves
by 5-9% between two runs of the same traffic and 18% between seeds (PR
23): it cannot carry a bound."""
import metrics


def read(ctx):
    return metrics.ttft_percentile_ms(ctx["records"], 50)
