"""The request-serving plane (cake_tpu/serve): HTTP API + scheduler over
the continuous-batching engine.

`make serve-smoke` acceptance: concurrent SSE clients stream to completion
with per-stream output identical to their solo runs, a mid-run arrival is
admitted without stalling running streams, a disconnected client's slot is
reused, saturation answers 429 + Retry-After, drain finishes in-flight
requests while refusing new ones, the serve.* series land in /metrics, and
the tokenizer-less checkpoint path serves prompt_ids end to end.
"""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import jax
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import tiny
from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.runtime.batch_generator import BatchGenerator
from cake_tpu.runtime.generator import LlamaGenerator
from cake_tpu.serve import session as serve_session
from cake_tpu.serve.api import start_api_server
from cake_tpu.serve.engine import SingleStreamEngine
from cake_tpu.serve.scheduler import Scheduler

# eos disabled (-1 never sampled): stream lengths are deterministic, so
# every test can assert exact token counts
CFG = tiny(max_seq_len=64, eos_token_id=-1)
GREEDY = dict(temperature=0.0, repeat_penalty=1.1)


class _FakeTok:
    """Deterministic toy tokenizer: id -> letter (every decode is alnum,
    so the streaming detok emits text on every token)."""

    def decode(self, ids):
        return "".join(chr(ord("a") + (i % 26)) for i in ids)

    def encode(self, text):
        return [ord(c) - ord("a") for c in text]


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def tok_server(params):
    """BatchGenerator + tokenizer behind the HTTP API: 4 slots, a 2-deep
    admission queue (small on purpose — the saturation test needs it)."""
    gen = BatchGenerator(CFG, params, tokenizer=_FakeTok(),
                         settings=SamplerSettings(**GREEDY))
    sched = Scheduler(gen, queue_depth=2, request_timeout_s=120)
    sched.start(max_concurrent=4)
    srv = start_api_server(sched)
    yield srv
    srv.close()
    sched.close()


@pytest.fixture(scope="module")
def ids_server(params):
    """The tokenizer-less path: a checkpoint dir without tokenizer.json
    must still serve prompt_ids requests (token ids come back instead of
    text)."""
    gen = BatchGenerator(CFG, params, tokenizer=None,
                         settings=SamplerSettings(**GREEDY))
    sched = Scheduler(gen, queue_depth=4, request_timeout_s=120)
    sched.start(max_concurrent=2)
    srv = start_api_server(sched)
    yield srv
    srv.close()
    sched.close()


def _url(srv) -> str:
    return f"http://127.0.0.1:{srv.port}"


def _post(srv, body: dict, timeout: float = 120.0):
    req = urllib.request.Request(
        _url(srv) + "/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _post_sse(srv, body: dict, timeout: float = 120.0,
              on_event=None) -> list[dict | str]:
    body = dict(body, stream=True)
    req = urllib.request.Request(
        _url(srv) + "/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    events: list = []
    with urllib.request.urlopen(req, timeout=timeout) as r:
        assert r.headers["Content-Type"] == "text/event-stream"
        for raw in r:
            raw = raw.strip()
            if not raw.startswith(b"data: "):
                continue
            data = raw[len(b"data: "):]
            ev = data.decode() if data == b"[DONE]" else json.loads(data)
            events.append(ev)
            if on_event:
                on_event(ev)
    return events


def _ids_of(events) -> list[int]:
    return [e["token"] for e in events
            if isinstance(e, dict) and "token" in e]


def _done_of(events) -> dict:
    done = [e for e in events if isinstance(e, dict) and e.get("done")]
    assert len(done) == 1, f"expected one terminal event, got {events}"
    return done[0]


def _text_of(events) -> str:
    parts = [e["text"] for e in events
             if isinstance(e, dict) and "token" in e and e["text"]]
    tail = _done_of(events).get("text")
    return "".join(parts) + (tail or "")


PROMPTS = ["abcd", "bcde", "cdef", "defg"]


def test_concurrent_sse_clients_match_solo_runs(tok_server):
    """≥4 concurrent SSE clients stream to completion, each with exactly
    the tokens/text its prompt yields when served alone — the engine's
    batch-composition invariance, observed through the full HTTP plane."""
    solo = {}
    for p in PROMPTS:  # sequential solo runs: the reference streams
        ev = _post_sse(tok_server, {"prompt": p, "max_tokens": 8})
        solo[p] = (_ids_of(ev), _text_of(ev))
        assert len(solo[p][0]) == 8
        assert _done_of(ev)["finish_reason"] == "length"

    results: dict[str, list] = {}

    def client(p: str) -> None:
        results[p] = _post_sse(tok_server, {"prompt": p, "max_tokens": 8})

    threads = [threading.Thread(target=client, args=(p,)) for p in PROMPTS]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    for p in PROMPTS:
        assert _ids_of(results[p]) == solo[p][0], f"stream for {p!r} diverged"
        assert _text_of(results[p]) == solo[p][1]
        usage = _done_of(results[p])["usage"]
        assert usage["completion_tokens"] == 8
        assert usage["ttft_ms"] > 0


def test_mid_run_arrival_admitted_without_stalling(tok_server):
    """Continuous batching through HTTP: while two long streams run, a
    late arrival is admitted and completes BEFORE they finish — and their
    token streams are unperturbed by the admission."""
    long_events: dict[str, list] = {"a": [], "b": []}
    started = threading.Event()
    counts = {"a": 0, "b": 0}

    def long_client(key: str) -> None:
        def on_event(ev):
            if isinstance(ev, dict) and "token" in ev:
                counts[key] += 1
                if counts["a"] >= 2 and counts["b"] >= 2:
                    started.set()
        long_events[key] = _post_sse(
            tok_server, {"prompt": "abab", "max_tokens": 40},
            on_event=on_event)

    threads = [threading.Thread(target=long_client, args=(k,))
               for k in ("a", "b")]
    for t in threads:
        t.start()
    assert started.wait(timeout=60), "long streams never started"
    # the arrival: admitted into a free slot while both streams decode
    out = _post(tok_server, {"prompt": "zzzz", "max_tokens": 4})
    assert out["usage"]["completion_tokens"] == 4
    # it finished while the long streams were still mid-flight
    assert counts["a"] < 40 and counts["b"] < 40
    for t in threads:
        t.join(timeout=120)
    assert len(_ids_of(long_events["a"])) == 40
    assert _ids_of(long_events["a"]) == _ids_of(long_events["b"])


def test_saturation_yields_429_with_retry_after(tok_server):
    """4 slots live + 2 queued = saturated: the next submit answers 429
    with an observed-throughput Retry-After, and never blocks the accept
    loop (serve.rejected moves)."""
    rejected0 = serve_session.REJECTED.value
    live = threading.Event()
    seen = [0, 0, 0, 0]
    results: list = [None] * 6

    def long_client(i: int) -> None:
        def on_event(ev):
            if isinstance(ev, dict) and "token" in ev:
                seen[i] += 1
                if all(n >= 1 for n in seen):
                    live.set()
        results[i] = _post_sse(
            tok_server, {"prompt": "abcd", "max_tokens": 48},
            on_event=on_event)

    threads = [threading.Thread(target=long_client, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    assert live.wait(timeout=60), "slots never filled"

    def queued_client(i: int) -> None:
        results[i] = _post(tok_server, {"prompt": "dcba", "max_tokens": 2})

    qthreads = [threading.Thread(target=queued_client, args=(i,))
                for i in (4, 5)]
    for t in qthreads:
        t.start()
    # wait until both actually sit in the admission queue
    deadline = time.time() + 30
    while time.time() < deadline:
        st = json.loads(urllib.request.urlopen(
            _url(tok_server) + "/healthz", timeout=10).read())
        if st["queued"] >= 2:
            break
        time.sleep(0.02)
    assert st["queued"] >= 2, f"queue never filled: {st}"

    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(tok_server, {"prompt": "aaaa", "max_tokens": 2})
    assert exc.value.code == 429
    assert int(exc.value.headers["Retry-After"]) >= 1
    assert serve_session.REJECTED.value > rejected0

    for t in threads + qthreads:
        t.join(timeout=180)
    assert all(len(_ids_of(r)) == 48 for r in results[:4])
    assert all(r["usage"]["completion_tokens"] == 2 for r in results[4:])


def test_disconnected_client_frees_slot(tok_server):
    """A client that walks away mid-stream must not pin its slot: the
    write failure cancels the session, finish() retires the stream (KV row
    back to the admission pool), serve.cancelled moves, and the next
    request is served."""
    cancelled0 = serve_session.CANCELLED.value
    body = json.dumps({"prompt": "abcd", "max_tokens": 56,
                       "stream": True}).encode()
    s = socket.create_connection(("127.0.0.1", tok_server.port), timeout=30)
    s.sendall(b"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
              b"Content-Type: application/json\r\n"
              b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n"
              + body)
    buf = b""
    while buf.count(b"data: ") < 2:  # two token events, then vanish
        chunk = s.recv(4096)
        assert chunk, "server closed early"
        buf += chunk
    s.close()

    deadline = time.time() + 30
    while time.time() < deadline:
        if serve_session.CANCELLED.value > cancelled0:
            status = json.loads(urllib.request.urlopen(
                _url(tok_server) + "/", timeout=10).read())
            eng = status["scheduler"]["engine"]
            if eng["streams_live"] == 0:
                break
        time.sleep(0.05)
    assert serve_session.CANCELLED.value > cancelled0, "no cancellation seen"
    assert eng["streams_live"] == 0, f"slot still live: {eng}"
    # the freed slot serves the next request
    out = _post(tok_server, {"prompt": "abcd", "max_tokens": 3})
    assert out["usage"]["completion_tokens"] == 3


def test_sampler_knobs_must_match_server(tok_server):
    """The engine compiles ONE sampler; a mismatched per-request knob is
    refused loudly (400) instead of silently ignored, a matching one is
    accepted."""
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(tok_server, {"prompt": "abcd", "max_tokens": 2,
                           "temperature": 0.9})
    assert exc.value.code == 400
    assert "temperature" in json.loads(exc.value.read())["error"]
    out = _post(tok_server, {"prompt": "abcd", "max_tokens": 2,
                             "temperature": 0.0})
    assert out["usage"]["completion_tokens"] == 2


def test_serve_metrics_on_shared_port(tok_server):
    """One port serves traffic AND observability: /metrics carries the
    serve.* series in Prometheus text, / the JSON status embedding the
    registry, /healthz and /v1/models answer."""
    text = urllib.request.urlopen(
        _url(tok_server) + "/metrics", timeout=10).read().decode()
    for series in ("cake_serve_ttft_ms", "cake_serve_tpot_ms",
                   "cake_serve_queue_depth", "cake_serve_rejected",
                   "cake_serve_cancelled"):
        assert series in text, f"{series} missing from /metrics"
    status = json.loads(urllib.request.urlopen(
        _url(tok_server) + "/", timeout=10).read())
    assert status["role"] == "serve"
    assert "serve.ttft_ms" in status["metrics"]
    assert status["metrics"]["serve.ttft_ms"]["count"] > 0
    models = json.loads(urllib.request.urlopen(
        _url(tok_server) + "/v1/models", timeout=10).read())
    assert models["data"][0]["max_concurrent"] == 4
    health = json.loads(urllib.request.urlopen(
        _url(tok_server) + "/healthz", timeout=10).read())
    assert health["ok"] is True


def _ms(name: str) -> dict:
    return obs_metrics.registry().snapshot()[name]


def test_queue_wait_and_admit_to_first_add_up_to_ttft(tok_server):
    """The two legs of a request's wait for its first token, each
    observed where it ends (the scheduler hands the session to the
    engine; the session emits its first token), are the whole of it."""
    names = ("serve.queue_wait_ms", "serve.admit_to_first_ms",
             "serve.ttft_ms")
    _post(tok_server, {"prompt": "abcd", "max_tokens": 2})  # series exist
    before = {n: _ms(n) for n in names}
    _post(tok_server, {"prompt": "bcde", "max_tokens": 3})
    d = {n: {f: _ms(n)[f] - before[n][f] for f in ("count", "sum")}
         for n in names}
    assert [d[n]["count"] for n in names] == [1, 1, 1]
    assert d["serve.queue_wait_ms"]["sum"] >= 0
    assert d["serve.admit_to_first_ms"]["sum"] > 0
    assert (d["serve.queue_wait_ms"]["sum"]
            + d["serve.admit_to_first_ms"]["sum"]
            == pytest.approx(d["serve.ttft_ms"]["sum"], abs=1e-6))


def test_queue_wait_counts_the_wait_for_a_slot(params):
    """With one slot, a second request's queue wait is the first one's
    whole stream, and its admit-to-first is not."""
    gen = BatchGenerator(CFG, params, tokenizer=_FakeTok(),
                         settings=SamplerSettings(**GREEDY))
    sched = Scheduler(gen, queue_depth=4, request_timeout_s=120)
    sched.start(max_concurrent=1)
    try:
        a = serve_session.Session([1, 2, 3], max_tokens=24)
        b = serve_session.Session([4, 5, 6], max_tokens=2)
        sched.submit(a)
        sched.submit(b)
        for sess in (a, b):
            while sess.events.get(timeout=60)[0] == "token":
                pass
    finally:
        sched.close()
    wait_a = (a.t_admit - a.t_submit) * 1e3
    wait_b = (b.t_admit - b.t_submit) * 1e3
    assert wait_b > wait_a and wait_b > 0.5 * b.ttft_ms
    assert b.ttft_ms == pytest.approx(
        wait_b + (b._t_last - b.t_admit) * 1e3, abs=50.0)


def test_new_series_are_declared_and_pass_the_catalog_checker():
    from pathlib import Path

    from cake_tpu.analysis import core
    from cake_tpu.analysis.metrics_catalog import MetricsCatalogChecker
    from cake_tpu.obs import catalog

    assert catalog.kind_of("serve.queue_wait_ms") == catalog.HISTOGRAM
    assert catalog.kind_of("serve.admit_to_first_ms") == catalog.HISTOGRAM
    assert catalog.kind_of("prof.slow_pass_ms") == catalog.COUNTER
    assert catalog.kind_of("prof.slow_passes") == catalog.COUNTER
    root = Path(__file__).resolve().parent.parent
    files = [str(root / "cake_tpu" / f) for f in (
        "serve/session.py", "serve/scheduler.py", "obs/prof.py")]
    assert core.run_checkers([MetricsCatalogChecker()], roots=files,
                             repo_root=root) == []
    snap = obs_metrics.registry().snapshot()
    for name in ("serve.queue_wait_ms", "serve.admit_to_first_ms",
                 "prof.slow_pass_ms", "prof.slow_passes"):
        assert snap[name]["type"] == catalog.kind_of(name), name


def test_status_surface_byte_identical_with_statusd(tok_server):
    """The API server's / + /metrics must stay byte-identical with a
    standalone obs.statusd page over the same status_fn — both build
    through statusd.status_response (the factoring this test pins)."""
    from cake_tpu.obs import statusd
    from cake_tpu.serve.api import ApiServer

    def fixed_status():
        return {"role": "parity", "n": 42}

    httpd, port = statusd.start_status_server(fixed_status)
    api = ApiServer(tok_server.scheduler, status_fn=fixed_status).start()

    def fetch(at: int, path: str):
        with urllib.request.urlopen(f"http://127.0.0.1:{at}{path}",
                                    timeout=10) as r:
            return r.read(), r.headers["Content-Type"]

    try:
        for path in ("/", "/metrics"):
            # both pages render the process's ONE registry, and the
            # module's scheduler is live: its thread ticks series between
            # two fetches (the histogram ``prof.phase_ms.idle_park``: a
            # sample every time the idle engine wakes, count, sum and a
            # bucket, about once in two 50 ms intervals; found by reading
            # one server twice, PR 51). What this test is about is
            # the rendering, so a pair is compared only where the registry
            # stood still around it: the API server read the same bytes
            # before and after the standalone page was read
            for _ in range(50):
                before = fetch(api.port, path)
                alone = fetch(port, path)
                if fetch(api.port, path) == before:
                    break
            else:
                pytest.fail(f"{path}: the registry never stood still")
            assert before[0] == alone[0], f"{path} bodies diverge"
            assert before[1] == alone[1]
    finally:
        api.close()
        httpd.shutdown()
        httpd.server_close()


def test_prompt_ids_serving_without_tokenizer(ids_server):
    """A checkpoint without tokenizer.json still serves: prompt_ids in,
    token ids out (no text field), both unary and SSE; a text prompt is
    refused with a clear 400."""
    out = _post(ids_server, {"prompt_ids": [1, 5, 9, 2], "max_tokens": 5})
    assert len(out["token_ids"]) == 5
    assert "text" not in out
    ev = _post_sse(ids_server,
                   {"prompt_ids": [1, 5, 9, 2], "max_tokens": 5})
    assert _ids_of(ev) == out["token_ids"]
    assert all(e["text"] is None for e in ev
               if isinstance(e, dict) and "token" in e)
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(ids_server, {"prompt": "hello", "max_tokens": 2})
    assert exc.value.code == 400
    assert "tokenizer" in json.loads(exc.value.read())["error"]


def test_single_stream_engine_drain(params):
    """The one-slot adapter (the --topology serve path) + graceful drain:
    an in-flight stream runs to completion through a drain, a submit
    during the drain answers 503, and the engine thread parks."""
    gen = LlamaGenerator(CFG, params, settings=SamplerSettings(**GREEDY))
    engine = SingleStreamEngine(gen)
    sched = Scheduler(engine, queue_depth=2, request_timeout_s=60)
    sched.start(max_concurrent=1)
    assert sched.max_concurrent == 1  # the adapter serializes
    srv = start_api_server(sched)
    try:
        events: list = []
        got_two = threading.Event()

        def on_event(ev):
            if isinstance(ev, dict) and "token" in ev:
                if len([e for e in events if "token" in e]) >= 1:
                    got_two.set()
            events.append(ev)

        t = threading.Thread(target=lambda: _post_sse(
            srv, {"prompt_ids": [1, 5, 9], "max_tokens": 12},
            on_event=on_event))
        t.start()
        assert got_two.wait(timeout=60)

        drainer = threading.Thread(
            target=lambda: sched.stop(drain=True, timeout_s=60))
        drainer.start()
        # new work is refused while the in-flight stream keeps going
        deadline = time.time() + 10
        code = None
        while time.time() < deadline and code != 503:
            try:
                _post(srv, {"prompt_ids": [2, 4], "max_tokens": 2},
                      timeout=10)
            except urllib.error.HTTPError as e:
                code = e.code
        assert code == 503
        t.join(timeout=60)
        drainer.join(timeout=60)
        done = [e for e in events if isinstance(e, dict) and e.get("done")]
        assert done and done[0]["usage"]["completion_tokens"] == 12
        assert not sched._thread.is_alive()
    finally:
        srv.close()
        sched.close()


def test_engine_fault_stops_accepting(params):
    """A dead engine must refuse work, not queue it forever: an engine
    fault aborts every in-flight session with an error event, flips the
    scheduler to draining (submit -> Draining, /healthz -> 503), and the
    queue cannot grow behind a thread that will never serve it."""
    from cake_tpu.serve.scheduler import Draining
    from cake_tpu.serve.session import Session

    class BoomEngine:
        config = CFG
        tokenizer = None
        settings = SamplerSettings(**GREEDY)
        max_seq = 64

        def __init__(self):
            from cake_tpu.serve.engine import _Slot

            self.streams = [_Slot(stream_id=-1, prompt=[], done=True)]

        def _encode(self, p):
            return list(p)

        def enqueue(self, ids, sid):
            pass

        def pending_admissions(self):
            return 0

        def finish(self, sid):
            return False

        def step(self):
            raise RuntimeError("boom")

        def stats(self):
            return {}

    sched = Scheduler(BoomEngine(), queue_depth=2)
    sched.start(max_concurrent=1)
    sess = Session([1], max_tokens=2)
    sched.submit(sess)  # wakes the engine thread; step() explodes
    ev = sess.events.get(timeout=30)
    assert ev[0] == "error" and ev[1] == 503
    assert "boom" in ev[2]
    deadline = time.time() + 10
    while time.time() < deadline and not sched.stats()["draining"]:
        time.sleep(0.02)
    assert sched.stats()["draining"]
    with pytest.raises(Draining):
        sched.submit(Session([1], max_tokens=2))
    # and it says why: /healthz forwards this, and `--mode serve` exits
    # non-zero on it instead of logging a clean drain
    assert sched.fault == "RuntimeError: boom"
    assert sched.stats()["fault"] == sched.fault


def test_loadgen_closed_and_open_loop(tok_server):
    """The load generator (the serve-smoke driver): closed loop completes
    every request with sane percentiles; open loop fires Poisson arrivals
    without error."""
    from cake_tpu.tools import loadgen

    stats = loadgen.run_load(_url(tok_server), 6, concurrency=3,
                             max_tokens=4, prompt_lens=[4, 8], vocab=200,
                             seed=3)
    assert stats["completed"] == 6 and stats["errors"] == 0
    assert stats["tokens"] == 24 and stats["tok_s"] > 0
    assert stats["ttft_ms"]["p50"] > 0
    stats = loadgen.run_load(_url(tok_server), 4, max_tokens=3, rate=50.0,
                             prompt_lens=[4], vocab=200, seed=4)
    assert stats["completed"] == 4 and stats["errors"] == 0


def test_a_burst_of_connections_is_all_accepted(ids_server):
    """Every client of a closed loop can connect in the same instant (a
    load generator's start; streams that end at one block boundary). The
    listener's backlog holds the burst: with the stdlib's 5, what
    overflows is dropped and offered again a second later, or reset."""
    assert ids_server.httpd.request_queue_size >= 128
    n, took, errors = 64, [], []
    gate = threading.Barrier(n)

    def one():
        gate.wait()
        t0 = time.perf_counter()
        try:
            with urllib.request.urlopen(_url(ids_server) + "/healthz",
                                        timeout=30) as r:
                assert json.loads(r.read())["ok"]
        except (OSError, AssertionError) as e:
            errors.append(e)
        took.append(time.perf_counter() - t0)

    for _ in range(3):
        threads = [threading.Thread(target=one) for _ in range(n)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    assert not errors
    assert len(took) == 3 * n
