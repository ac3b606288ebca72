"""90th percentile of the time to first token from the instant a request
was due: the requests that met a queue or a long admission. Which ones do
depends on where a burst of arrivals meets a run of long prompts, that
is on the seed's order: no bound."""
import metrics


def read(ctx):
    return metrics.ttft_percentile_ms(ctx["records"], 90)
