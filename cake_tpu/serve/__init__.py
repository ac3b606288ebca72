"""Request-serving plane: HTTP API + SLO-aware scheduling over the engine.

The reference is strictly single-request and in-process; the engine here
(``runtime.batch_generator.BatchGenerator``) already out-builds it —
continuous batching, shared-prefix reuse, adaptive decode blocks, the
device's next program enqueued before a block's rows are handed out,
batched speculation — but an engine only becomes a
*service* with a serving front end (the Orca / vLLM lesson: request
queueing, admission, streaming, cancellation are their own subsystem).
That front end is this package, stdlib-only:

- :mod:`cake_tpu.serve.session` — per-request state: prompt intake
  (text or ``prompt_ids``), SSE framing, TTFT/TPOT measurement feeding
  the ``serve.*`` registry series and flight records.
- :mod:`cake_tpu.serve.scheduler` — the single engine-owner thread:
  bounded FIFO admission with deadlines, token fan-out to per-request
  queues, retirement on EOS / ``max_tokens`` / disconnect / deadline,
  429-style backpressure with an observed-throughput Retry-After.
- :mod:`cake_tpu.serve.engine` — one-slot BatchGenerator facade over the
  single-stream generators, so serving also runs over the cross-host
  ``--topology`` path.
- :mod:`cake_tpu.serve.api` — threaded HTTP server: ``POST
  /v1/completions`` (JSON or SSE), ``GET /v1/models``, ``GET /healthz``,
  plus the mounted ``/`` + ``/metrics`` statusd surface.

CLI surface: ``--mode serve --serve-port/--serve-bind --max-concurrent
--queue-depth --request-timeout``; ``python -m cake_tpu.tools.loadgen``
drives it. See README "Serving over HTTP".
"""

from cake_tpu.serve.api import ApiServer, start_api_server  # noqa: F401
from cake_tpu.serve.engine import SingleStreamEngine  # noqa: F401
from cake_tpu.serve.scheduler import (  # noqa: F401
    Draining,
    QueueFull,
    Scheduler,
)
from cake_tpu.serve.session import Session  # noqa: F401
