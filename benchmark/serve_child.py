"""The process that holds the chip: ``cake_tpu.cli --mode serve``,
in-process, and -- only in a traced run -- a thread that opens and closes
a ``jax.profiler`` trace when the parent asks.

    python benchmark/serve_child.py [--bench-trace-dir DIR] <cli arguments>

``--mode serve`` has no profiler hook, and only the process that holds
the chip can trace it, so the hook lives here, outside the program. The
parent asks by creating ``DIR/start`` and ``DIR/stop``; the thread
answers with ``DIR/started`` and ``DIR/done`` (JSON: the host's clocks at
those instants, to put the trace and the parent's timeline on one
clock). Without ``--bench-trace-dir`` no thread is started and nothing
but ``cli.main`` runs.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _clocks() -> dict:
    return {"unix_ns": time.time_ns(), "perf_s": time.perf_counter()}


def _trace_on_request(ctl: Path, jax) -> None:
    def wait_for(name: str) -> None:
        while not (ctl / name).exists():
            time.sleep(0.02)

    wait_for("start")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # runtime spans, no Python frames
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(ctl / "profile"), profiler_options=options)
    (ctl / "started").write_text(json.dumps(_clocks()))
    wait_for("stop")
    at_stop = _clocks()
    jax.profiler.stop_trace()
    (ctl / "done").write_text(json.dumps(at_stop))


def main(argv: list[str]) -> int:
    sys.path.insert(0, str(ROOT))
    if argv and argv[0] == "--bench-trace-dir":
        import jax  # here, not in the thread: two threads importing race

        ctl = Path(argv[1])
        argv = argv[2:]
        threading.Thread(target=_trace_on_request, args=(ctl, jax),
                         daemon=True, name="bench-trace").start()
    from cake_tpu import cli

    return cli.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
