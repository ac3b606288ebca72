"""Mean time to first token, from the due instant, of the slowest tenth
of the requests: how bad the waits of ``sched.ttft_p90_ms`` get."""
import metrics


def read(ctx):
    return metrics.ttft_tail10_ms(ctx["records"])
