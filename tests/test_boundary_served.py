"""The order of work at a block boundary, seen through the serving plane
(PR 31): the scheduler's pass over an engine that enqueues the device's
next program before it hands out a landed block's rows, the three
``engine.*`` series on ``/metrics``, and the benchmark's reader of them
(``benchmark/layer_metrics/engine.boundary_ms.py``).
"""

import importlib.util
import json
import subprocess
import sys
import threading
import urllib.request
from pathlib import Path

import jax
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import tiny
from cake_tpu.obs import catalog
from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.runtime.batch_generator import BatchGenerator
from cake_tpu.serve.api import start_api_server
from cake_tpu.serve.scheduler import Scheduler

ROOT = Path(__file__).resolve().parent.parent
CFG = tiny(max_seq_len=64, eos_token_id=-1)
GREEDY = dict(temperature=0.0, repeat_penalty=1.1)
SERIES = ("engine.boundaries", "engine.boundaries_ahead",
          "engine.boundary_ms")
# where a boundary's host time is spent (PR 53): a histogram a part
PARTS = ("engine.boundary_emit_ms", "engine.boundary_pass_ms",
         "engine.boundary_enqueue_ms")
# budgets that end inside a block of 4, at its edge, and after one token
REQUESTS = [("hello", 7), ("world", 8), ("abcde", 9), ("zyx", 1),
            ("hellothere", 14), ("cake", 5)]


class _SpacedTok:
    """Toy tokenizer whose every third id ends in a space: the streaming
    detokenizer withholds such a token's text until the next one, so a
    stream's end leaves a tail for ``decode_rest()``."""

    def decode(self, ids):
        return "".join(chr(ord("a") + i % 26) + (" " if i % 3 == 0 else "")
                       for i in ids)

    def encode(self, text):
        return [ord(c) - ord("a") for c in text if c != " "]


def _post_sse(srv, body: dict) -> list:
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/v1/completions",
        data=json.dumps(dict(body, stream=True)).encode(),
        headers={"Content-Type": "application/json"})
    events: list = []
    with urllib.request.urlopen(req, timeout=120.0) as r:
        for raw in r:
            raw = raw.strip()
            if raw.startswith(b"data: "):
                data = raw[len(b"data: "):]
                events.append(data.decode() if data == b"[DONE]"
                              else json.loads(data))
    return events


def _ids_of(events) -> list[int]:
    return [e["token"] for e in events
            if isinstance(e, dict) and "token" in e]


def _done_of(events) -> dict:
    done = [e for e in events if isinstance(e, dict) and e.get("done")]
    assert len(done) == 1, f"expected one terminal event, got {events}"
    return done[0]


def _text_of(events) -> tuple[str, str]:
    """(all the text the client got, the terminal event's tail of it)."""
    parts = [e["text"] for e in events
             if isinstance(e, dict) and "token" in e and e["text"]]
    tail = _done_of(events).get("text") or ""
    return "".join(parts) + tail, tail


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(7))


def _serve(params, **kw):
    gen = BatchGenerator(CFG, params, tokenizer=_SpacedTok(),
                         settings=SamplerSettings(**GREEDY), **kw)
    sched = Scheduler(gen, queue_depth=8, request_timeout_s=120)
    sched.start(max_concurrent=3)
    return start_api_server(sched), sched


@pytest.fixture(scope="module")
def served(params):
    """Every request answered twice: by a server of fused blocks of 4 (3
    slots for 6 clients at once, so admissions meet landed blocks) and,
    one request at a time, by a server of single steps."""
    out = {}
    for name, kw in (("blocks", dict(block_size=4)), ("single", {})):
        before = {n: _value(n) for n in SERIES + PARTS}
        sums = {n: _sum(n) for n in SERIES[2:] + PARTS}
        srv, sched = _serve(params, **kw)
        try:
            res: dict = {}

            def client(p, n):
                res[p] = _post_sse(srv, {"prompt": p, "max_tokens": n})

            if name == "blocks":
                threads = [threading.Thread(target=client, args=r)
                           for r in REQUESTS]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=120)
            else:
                for r in REQUESTS:
                    client(*r)
        finally:
            srv.close()
            sched.close()
        out[name] = (res, {n: _value(n) - before[n] for n in SERIES + PARTS},
                     {n: _sum(n) - v for n, v in sums.items()})
    return out


def _value(name: str) -> float:
    snap = obs_metrics.registry().snapshot().get(name, {})
    return snap.get("value", snap.get("count", 0))


def _sum(name: str) -> float:
    return obs_metrics.registry().snapshot().get(name, {}).get("sum", 0.0)


@pytest.mark.parametrize("prompt,budget", REQUESTS)
def test_served_ids_and_text_are_those_of_single_steps(served, prompt,
                                                       budget):
    """A token budget that ends inside a block retires the stream when
    its row is delivered, after the next block has left: the client still
    gets exactly the ids, the text and the detokenizer's tail that single
    steps give -- nothing of the rows past its budget."""
    got, want = served["blocks"][0][prompt], served["single"][0][prompt]
    assert _ids_of(got) == _ids_of(want) and len(_ids_of(got)) == budget
    assert _text_of(got) == _text_of(want)
    text, _ = _text_of(got)
    assert text == _SpacedTok().decode(_ids_of(got))  # all of it, no more
    assert _done_of(got)["finish_reason"] == "length"
    assert (_done_of(got)["usage"]["completion_tokens"] == budget)


def test_some_stream_ended_on_a_withheld_tail(served):
    """The requests above do exercise the tail: at least one budget ends
    on a token whose text the detokenizer was still holding."""
    tails = [_text_of(ev)[1] for ev in served["blocks"][0].values()]
    assert any(tails), tails


def test_every_served_boundary_enqueued_ahead(served):
    """Through the scheduler's pass (``_admit`` before ``engine.step``, a
    landing that delivers nothing) every landed block's next program left
    before its rows did, and ``engine.boundary_ms`` was observed once a
    boundary; single steps land no block."""
    blocks, single = served["blocks"][1], served["single"][1]
    assert blocks["engine.boundaries"] >= 4
    assert (blocks["engine.boundaries"] == blocks["engine.boundaries_ahead"]
            == blocks["engine.boundary_ms"])
    assert not any(single[n] for n in SERIES)


def test_the_boundarys_parts_are_observed_where_the_device_waited(served):
    """Through the scheduler's pass: ``engine.boundary_emit_ms``,
    ``_pass_ms`` and ``_enqueue_ms`` hold one observation each a boundary
    that the NEXT pass's ``step()`` closed, and none for one that an
    admission launched under the running block closed at once (six
    clients on three slots: some are), so each counts
    ``engine.boundary_ms``'s observations less those; together they took
    no longer than ``engine.boundary_ms`` did. Single steps land no
    block."""
    _, blocks, sums = served["blocks"]
    waited = blocks[PARTS[0]]
    assert 1 <= waited <= blocks["engine.boundary_ms"]
    assert all(blocks[n] == waited for n in PARTS)
    assert all(sums[n] > 0.0 for n in PARTS)
    assert sum(sums[n] for n in PARTS) <= sums["engine.boundary_ms"]
    assert not any(served["single"][1][n] for n in PARTS)


def test_the_series_are_declared_and_on_metrics():
    assert catalog.kind_of("engine.boundary_ms") == catalog.HISTOGRAM
    assert catalog.kind_of("engine.boundaries") == catalog.COUNTER
    assert catalog.kind_of("engine.boundaries_ahead") == catalog.COUNTER
    snap = obs_metrics.registry().snapshot()
    for name in SERIES + PARTS + ("engine.landing_counts_fetch_ms",):
        assert snap[name]["type"] == catalog.kind_of(name), name


def _bench_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run_for_tests", ROOT / "benchmark" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("with_series,want", [(True, 2.5), (False, None)])
def test_the_reader_reads_the_mean_and_nothing_from_an_older_program(
        with_series, want):
    """``benchmark/layer_metrics/engine.boundary_ms.py``: the growth of the
    histogram's sum over the growth of its count across the window; None
    (the metric is left out of the line, no error) from a program that
    has no such series, as the parent commit has not."""
    def hist(count, total):
        return {"type": "histogram", "count": count, "sum": total}

    before = {"serve.ttft_ms": hist(10, 2000.0)}
    after = {"serve.ttft_ms": hist(110, 27000.0)}
    if with_series:
        before["engine.boundary_ms"] = hist(40, 400.0)
        after["engine.boundary_ms"] = hist(540, 1650.0)
    ctx = {"before": {"status": {"metrics": before}},
           "after": {"status": {"metrics": after}}}
    path = list(sys.path)  # run.py puts benchmark/ first: not for tier-1
    try:
        got = _bench_run().load_reader("engine.boundary_ms")(ctx)
    finally:
        sys.path[:] = path
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_benchmark_declares_the_metric_for_every_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    # appended by PR 31 as the 25th: nothing before it moved, and later
    # PRs append after it
    entry = bench["per_layer"][24]
    assert entry == {"name": "engine.boundary_ms", "unit": "ms",
                     "better": "lower", "source": "program_counter",
                     "layer": "engine", "moves": "tpot_p50_ms"}
    # no `workloads` key: every cell reports tpot_p50_ms, and lands blocks
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == "tpot_p50_ms")
    assert "workloads" not in moved


def test_a_rehearsed_cell_would_report_the_metric():
    """The whole control flow at tiny size on the CPU, traced tail
    included: the new program's line carries ``engine.boundary_ms``
    (a counter of the measured window)."""
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         "mistral7b-int8.chat-r80", "--rehearse", "--seed", "3100000077",
         "--seconds", "2", "--trace", "2"], capture_output=True, text=True,
        timeout=300, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert "engine.boundary_ms" in last["would_report"]
