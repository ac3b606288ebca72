"""Mean of the slowest 1% of all gaps between consecutive tokens of a
stream, pooled over the window: the stutter a reader sees when an
admission or a stall lands between decode blocks. Which admissions share
a pause depends on the order of the requests (8-9% between seeds on the
chip, PR 23): no bound."""
import metrics


def read(ctx):
    return metrics.gap_tail1_ms(ctx["records"], ctx["window"])
