"""The engine profiling plane (cake_tpu/obs/prof).

`make prof-smoke` acceptance: profiling never changes the stream (prof-on
vs prof-off streams bit-identical), a sampled step records the per-phase
breakdown plus the recent-step ring, the retrace sentinel counts backend
compiles and flags exactly the steady-state decode-phase ones (warn by
default, raise under CAKE_PROF_STRICT=1), /debug/prof answers live on a
serve replica, and a --trace run nests prof.* phase spans under the
request spans in one timeline.
"""

import json
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import tiny
from cake_tpu.obs import prof
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.runtime.batch_generator import BatchGenerator
from cake_tpu.serve.api import start_api_server
from cake_tpu.serve.scheduler import Scheduler

# eos disabled (-1 never sampled): stream lengths are deterministic
CFG = tiny(max_seq_len=64, eos_token_id=-1)
GREEDY = dict(temperature=0.0, repeat_penalty=1.1)


class _FakeTok:
    def decode(self, ids):
        return "".join(chr(ord("a") + (i % 26)) for i in ids)

    def encode(self, text):
        return [ord(c) - ord("a") for c in text]


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(7))


@pytest.fixture
def prof_env():
    """Save/restore the process-singleton profiler + sentinel around each
    test (sampling stride is a global knob; findings/steady are global
    state the next suite must not inherit)."""
    p, s = prof.profiler(), prof.sentinel()
    prev = p.sample_every
    yield
    p.set_sample(prev)
    p.reset()
    s.reset()


def _collect(gen, prompt, sid, steps):
    # prime like the scheduler does: a live batch of retired slots, so
    # enqueue rides the continuous-admission path
    gen.set_prompts([[0], [0]])
    for s in gen.streams:
        s.done = True
    gen.enqueue(prompt, sid)
    out = []
    for _ in range(steps):
        for t in gen.step():
            if t is not None:
                out.append(t.id)
    return out


# -- step-phase profiler ------------------------------------------------------

def test_prof_on_off_streams_bit_identical(params, prof_env):
    """Sampling every step must not perturb the emitted stream — the
    profiler reads clocks, it never touches engine state."""
    prompt = [3, 1, 4, 1, 5, 9]

    prof.profiler().set_sample(0)
    g_off = BatchGenerator(CFG, params, tokenizer=_FakeTok(),
                           settings=SamplerSettings(**GREEDY))
    ids_off = _collect(g_off, prompt, sid=1, steps=20)

    prof.profiler().set_sample(1)
    g_on = BatchGenerator(CFG, params, tokenizer=_FakeTok(),
                          settings=SamplerSettings(**GREEDY))
    ids_on = _collect(g_on, prompt, sid=1, steps=20)

    assert ids_off and ids_off == ids_on


def test_sampled_step_records_phases_and_ring(params, prof_env):
    prof.profiler().reset()
    prof.profiler().set_sample(1)
    gen = BatchGenerator(CFG, params, tokenizer=_FakeTok(),
                         settings=SamplerSettings(**GREEDY))
    _collect(gen, [2, 7, 1, 8], sid=1, steps=12)

    rep = prof.report()
    assert rep["sample_every"] == 1
    assert rep["sampled_steps"] >= 12
    # the decode hot path stamps these on every sampled pass
    for name in ("dispatch", "sync", "emit"):
        assert rep["phases"][name]["count"] > 0, name
    # admission ran at least once (the enqueue's prefill chunks)
    assert rep["phases"]["admit"]["count"] > 0
    ring = rep["recent_steps"]
    assert ring and all(
        r["engine"] == "batch" and "total_ms" in r for r in ring)
    assert any(r["phases"] for r in ring)
    # memory arm: host watermarks always resolve on Linux
    assert rep["memory"]["host"]["rss_bytes"] > 0


def test_disabled_profiler_records_nothing(params, prof_env):
    prof.profiler().reset()
    prof.profiler().set_sample(0)
    gen = BatchGenerator(CFG, params, tokenizer=_FakeTok(),
                         settings=SamplerSettings(**GREEDY))
    _collect(gen, [2, 7, 1, 8], sid=1, steps=8)
    rep = prof.report()
    assert rep["sampled_steps"] == 0
    assert rep["recent_steps"] == []


# -- retrace sentinel ---------------------------------------------------------

def test_retrace_sentinel_flags_steady_decode_compile(prof_env):
    sent = prof.sentinel()
    sent.install()
    sent.reset()
    f = jax.jit(lambda x: x * 2 + 1)
    a4, a8, a16 = jnp.zeros((4,)), jnp.zeros((8,)), jnp.zeros((16,))

    # warmup compile inside the decode phase: counted, not a finding
    with sent.decode_phase():
        f(a4)
    assert sent.compiles.value >= 1
    assert sent.retraces.value == 0

    sent.mark_steady()
    # steady compile OUTSIDE a decode dispatch (a new prompt-bucket
    # prefill, say) is legitimate — still not a finding
    f(a8)
    assert sent.retraces.value == 0

    # steady + decode-phase + new shape = the retrace finding
    with sent.decode_phase():
        f(a16)
    assert sent.retraces.value == 1
    findings = sent.findings()
    assert len(findings) == 1
    assert findings[0]["compile_ms"] > 0

    # the cache-hit path must not re-flag: same shape again, no compile
    with sent.decode_phase():
        f(a16)
    assert sent.retraces.value == 1


def test_retrace_sentinel_strict_raises(prof_env, monkeypatch):
    sent = prof.sentinel()
    sent.install()
    sent.reset()
    g = jax.jit(lambda x: x - 3)
    b4, b8 = jnp.zeros((4,)), jnp.zeros((8,))
    with sent.decode_phase():
        g(b4)
    sent.mark_steady()
    monkeypatch.setenv("CAKE_PROF_STRICT", "1")
    with pytest.raises(prof.RetraceError):
        with sent.decode_phase():
            g(b8)
    assert sent.retraces.value == 1


# -- live /debug/prof ---------------------------------------------------------

def test_debug_prof_served_live(params, prof_env):
    prof.profiler().set_sample(1)
    gen = BatchGenerator(CFG, params, tokenizer=_FakeTok(),
                         settings=SamplerSettings(**GREEDY))
    sched = Scheduler(gen, queue_depth=4, request_timeout_s=120)
    sched.start(max_concurrent=2)
    srv = start_api_server(sched)
    try:
        url = f"http://127.0.0.1:{srv.port}"
        req = urllib.request.Request(
            url + "/v1/completions",
            data=json.dumps({"prompt": "abcd", "max_tokens": 8}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.status == 200
            r.read()
        with urllib.request.urlopen(url + "/debug/prof", timeout=30) as r:
            assert r.status == 200
            assert r.headers["Content-Type"] == "application/json"
            rep = json.loads(r.read())
    finally:
        srv.close()
        sched.close()
    for key in ("phases", "recent_steps", "compiles", "retraces",
                "memory", "sample_every"):
        assert key in rep, key
    assert rep["phases"]["dispatch"]["count"] > 0
    assert rep["compiles"] >= 0


# -- trace nesting ------------------------------------------------------------

def test_phase_spans_nest_under_request_spans(params, prof_env):
    """One --trace timeline carries BOTH the reqtrace request spans and
    the prof.* phase spans, with the phases inside the request window."""
    from cake_tpu.obs import trace as obs_trace

    prof.profiler().set_sample(1)
    tr = obs_trace.tracer()
    tr.start()
    try:
        gen = BatchGenerator(CFG, params, tokenizer=_FakeTok(),
                             settings=SamplerSettings(**GREEDY))
        sched = Scheduler(gen, queue_depth=4, request_timeout_s=120)
        sched.start(max_concurrent=2)
        srv = start_api_server(sched)
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/v1/completions",
                data=json.dumps(
                    {"prompt": "abcd", "max_tokens": 10}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                assert r.status == 200
                r.read()
        finally:
            srv.close()
            sched.close()
    finally:
        tr.stop()
    doc = tr.to_chrome_trace()
    tr.clear()
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    prof_evs = [e for e in evs if e["name"].startswith("prof.")]
    req_evs = [e for e in evs
               if e["name"] in ("serve.queue", "engine.prefill",
                                "session.emit")]
    assert prof_evs, "no prof.* phase spans in the trace"
    assert req_evs, "no request spans in the trace"
    lo = min(e["ts"] for e in req_evs)
    hi = max(e["ts"] + e.get("dur", 0) for e in req_evs)
    inside = [e for e in prof_evs if lo <= e["ts"] <= hi]
    assert inside, "no phase span inside the request window"


# -- the capture control (POST /debug/trace) ----------------------------------

def _post(url, body, timeout=120):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


@pytest.fixture
def served(params, prof_env):
    """A tiny serve replica: (base url, scheduler)."""
    gen = BatchGenerator(CFG, params, tokenizer=_FakeTok(),
                         settings=SamplerSettings(**GREEDY))
    sched = Scheduler(gen, queue_depth=4, request_timeout_s=120)
    sched.start(max_concurrent=2)
    srv = start_api_server(sched)
    try:
        yield f"http://127.0.0.1:{srv.port}", sched
    finally:
        if prof.capture().active:
            prof.capture_stop()
        srv.close()
        sched.close()


@pytest.fixture
def captured(served):
    """One capture around one request on the live replica: the answers
    of start and stop, and the state the control must put back."""
    import threading

    from cake_tpu.obs import trace as obs_trace

    url, _ = served
    prof.profiler().set_sample(7)
    threads_before = {t.name for t in threading.enumerate()}
    code0, started = _post(url + "/debug/trace", {"action": "start"})
    during = {"stride": prof.profiler().sample_every,
              "tracer": obs_trace.tracer().enabled,
              "annotations": obs_trace.tracer().xla_annotations}
    code_again, again = _post(url + "/debug/trace", {"action": "start"})
    code_req, _ = _post(url + "/v1/completions",
                        {"prompt": "abcd", "max_tokens": 10,
                         "stream": False})
    code1, stopped = _post(url + "/debug/trace", {"action": "stop"})
    yield {"codes": (code0, code_again, code_req, code1),
           "started": started, "again": again, "stopped": stopped,
           "during": during, "threads_before": threads_before, "url": url}
    import shutil

    shutil.rmtree(started.get("dir", ""), ignore_errors=True)


def test_capture_answers_carry_the_directory_and_both_clocks(captured):
    assert captured["codes"] == (200, 409, 200, 200)
    a, b = captured["started"], captured["stopped"]
    assert set(a) == {"dir", "unix_ns", "perf_s"}
    assert set(b) == {"dir", "unix_ns", "perf_s", "steps", "spans",
                      "dropped"}
    assert a["dir"] == b["dir"]
    assert b["perf_s"] > a["perf_s"] and b["unix_ns"] > a["unix_ns"]
    # the two clocks of one answer name one instant
    skew = (b["unix_ns"] - a["unix_ns"]) / 1e9 - (b["perf_s"] - a["perf_s"])
    assert abs(skew) < 0.05
    assert b["steps"] > 0 and b["spans"] > 0 and b["dropped"] == 0
    assert "already open" in captured["again"]["error"]


def test_capture_writes_the_profile_and_the_programs_spans(captured):
    from pathlib import Path

    d = Path(captured["stopped"]["dir"])
    assert list(d.rglob("*.xplane.pb")), "no profile written"
    doc = json.loads((d / "spans.trace.json").read_text())
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert {"prof.dispatch", "prof.sync", "prof.deliver"} <= names
    # the request's admission: its launch and its landing, inside admit
    evs = {n: [e for e in doc["traceEvents"] if e["name"] == n]
           for n in ("prof.admit", "prof.admit_launch", "prof.admit_land")}
    assert all(evs.values()), {n: len(v) for n, v in evs.items()}
    for inner in evs["prof.admit_launch"] + evs["prof.admit_land"]:
        assert any(o["tid"] == inner["tid"] and o["ts"] <= inner["ts"]
                   and inner["ts"] + inner["dur"] <= o["ts"] + o["dur"] + 1
                   for o in evs["prof.admit"]), inner
    # the origin the spans' ts count from, on the answers' perf clock
    origin = doc["otherData"]["perf_origin_s"]
    assert origin <= captured["started"]["perf_s"]
    first = min(e["ts"] for e in doc["traceEvents"] if e.get("ph") == "X")
    assert origin + first / 1e6 < captured["stopped"]["perf_s"]


def test_capture_restores_the_stride_and_the_tracer(captured):
    import threading

    from cake_tpu.obs import trace as obs_trace

    assert captured["during"] == {"stride": 1, "tracer": True,
                                  "annotations": True}
    assert prof.profiler().sample_every == 7
    tr = obs_trace.tracer()
    assert not tr.enabled and not tr.xla_annotations
    assert tr.event_count() == 0
    assert not prof.capture().active
    # off, the control keeps no thread of its own
    left = {t.name for t in threading.enumerate()}
    assert "cake-capture-autostop" not in left
    rep = json.loads(urllib.request.urlopen(
        captured["url"] + "/debug/prof", timeout=30).read())
    assert rep["capturing"] is False and rep["sample_every"] == 7


def test_capture_puts_the_phases_on_the_traces_host_plane(captured):
    """The point of the control: a device idle gap can be named by the
    engine phase the host was in, because every phase is an event of the
    profiler's own trace."""
    from pathlib import Path

    from jax.profiler import ProfileData

    pb = sorted(Path(captured["stopped"]["dir"]).rglob("*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(pb))
    names = {e.name for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("prof.")}
    assert {"prof.dispatch", "prof.sync", "prof.admit", "prof.admit_launch",
            "prof.admit_land"} <= names, sorted(names)


def test_stop_without_a_capture_and_bad_bodies(served):
    url, _ = served
    assert _post(url + "/debug/trace", {"action": "stop"})[0] == 409
    assert _post(url + "/debug/trace", {"action": "pause"})[0] == 400
    assert _post(url + "/debug/trace", {})[0] == 400


def test_a_capture_nobody_stops_is_stopped_by_the_program(
        served, monkeypatch, tmp_path):
    import time

    from cake_tpu.obs import trace as obs_trace

    monkeypatch.setattr(prof, "CAPTURE_MAX_S", 0.3)
    monkeypatch.setattr(prof.capture(), "directory", str(tmp_path))
    prof.profiler().set_sample(5)
    started = prof.capture_start()
    assert started["dir"] == str(tmp_path) and prof.capture().active
    deadline = time.monotonic() + 30
    while prof.capture().active and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not prof.capture().active
    assert prof.profiler().sample_every == 5
    assert not obs_trace.tracer().enabled
    assert (tmp_path / "spans.trace.json").exists()
    with pytest.raises(prof.CaptureIdle):
        prof.capture_stop()


def test_capture_keeps_a_tracer_that_was_already_running(
        served, monkeypatch, tmp_path):
    """``--trace`` runs the tracer for the whole process: a capture
    passes its spans through and leaves it, and its buffer, alone."""
    from cake_tpu.obs import trace as obs_trace

    monkeypatch.setattr(prof.capture(), "directory", str(tmp_path))
    tr = obs_trace.tracer()
    tr.start()
    try:
        with obs_trace.span("before.capture"):
            pass
        prof.capture_start(auto_stop=False)
        assert tr.xla_annotations
        prof.capture_stop()
        assert tr.enabled and not tr.xla_annotations
        assert tr.event_count() >= 1
    finally:
        tr.stop()
        tr.clear()


# -- timestamps, scheduler pass parts, slow passes ----------------------------

def test_recent_steps_carry_clocks_and_phase_offsets(params, prof_env):
    import time

    prof.profiler().set_sample(1)
    t_before = time.time_ns()
    p_before = time.perf_counter()
    gen = BatchGenerator(CFG, params, settings=SamplerSettings(**GREEDY))
    _collect(gen, [3, 1, 4, 1, 5, 9], sid=5, steps=12)
    steps = prof.profiler().recent_steps()
    assert steps
    for rec in steps:
        assert t_before <= rec["t_unix_ns"] <= time.time_ns()
        assert p_before <= rec["t_perf_s"] <= time.perf_counter()
        assert set(rec["at_ms"]) == set(rec["phases"])
        for name, at in rec["at_ms"].items():
            assert 0 <= at <= rec["total_ms"], (name, rec)
    both = [r for r in steps if {"dispatch", "sync"} <= set(r["at_ms"])]
    assert both and all(r["at_ms"]["dispatch"] <= r["at_ms"]["sync"]
                        for r in both)


def test_phase_spans_on_unsampled_steps_while_the_tracer_runs(
        params, prof_env):
    """One stamp per interval: the dispatch phase is the dispatch span
    (it carries ``steps`` and ``batch``), on every step of a traced run,
    sampled or not."""
    from cake_tpu.obs import trace as obs_trace

    prof.profiler().set_sample(0)
    tr = obs_trace.tracer()
    tr.start()
    try:
        gen = BatchGenerator(CFG, params,
                             settings=SamplerSettings(**GREEDY))
        _collect(gen, [3, 1, 4], sid=5, steps=10)
    finally:
        tr.stop()
    evs = [e for e in tr.to_chrome_trace()["traceEvents"]
           if e.get("ph") == "X"]
    tr.clear()
    names = {e["name"] for e in evs}
    assert "prof.dispatch" in names and "decode.dispatch" not in names
    d = next(e for e in evs if e["name"] == "prof.dispatch")
    assert d["args"]["steps"] >= 1 and d["args"]["batch"] == 2
    assert prof.profiler().recent_steps() == []  # nothing was sampled


@pytest.mark.parametrize("name", ["sched_admit", "deliver", "retire",
                                  "idle_park", "pass_rest", "sync_counts"])
def test_scheduler_pass_parts_are_declared_phases(name):
    from cake_tpu.obs import catalog

    assert name in prof.PHASES
    assert catalog.kind_of(f"prof.phase_ms.{name}") == catalog.HISTOGRAM


@pytest.mark.parametrize("name", ["admit_launch", "admit_land"])
def test_an_admissions_launch_and_landing_are_phases_inside_admit(
        params, prof_env, name):
    """Declared, stamped once per admission on a sampled step, and
    attributing their parent's time: ``admit`` keeps its own histogram,
    which holds what they hold."""
    from cake_tpu.obs import catalog

    assert name in prof.PHASES
    assert catalog.kind_of(f"prof.phase_ms.{name}") == catalog.HISTOGRAM
    prof.profiler().reset()
    prof.profiler().set_sample(1)
    gen = BatchGenerator(CFG, params, settings=SamplerSettings(**GREEDY))
    _collect(gen, [2, 7, 1, 8], sid=1, steps=6)
    phases = prof.report()["phases"]
    assert phases[name]["count"] == 1
    assert phases["admit"]["count"] >= 1
    inner = phases["admit_launch"]["sum"] + phases["admit_land"]["sum"]
    assert inner <= phases["admit"]["sum"] + 0.01
    with_both = [r for r in prof.profiler().recent_steps()
                 if {"admit", name} <= set(r["phases"])]
    assert with_both and all(
        r["phases"][name] <= r["phases"]["admit"] + 0.01
        and r["at_ms"]["admit"] <= r["at_ms"][name] for r in with_both)


def test_a_slow_pass_leaves_its_parts(prof_env, monkeypatch):
    """A scheduler pass over the limit adds its length to
    ``prof.slow_pass_ms`` and keeps which part held it."""
    import time

    from cake_tpu.runtime.generator import Token
    from cake_tpu.serve.engine import _Slot
    from cake_tpu.serve.session import Session

    class SlowStep:
        """One slot; every step of a live stream takes 60 ms."""
        config = CFG
        tokenizer = None
        eos_ids = ()

        def __init__(self):
            self.streams = [_Slot(stream_id=-1, prompt=[], done=True)]

        def enqueue(self, ids, sid):
            self.streams[0] = _Slot(stream_id=sid, prompt=list(ids))

        def pending_admissions(self):
            return 0

        def finish(self, sid):
            self.streams[0].done = True

        def step(self):
            if self.streams[0].done:
                return [None]
            time.sleep(0.06)
            return [Token(id=7, text=None, is_end_of_stream=False)]

        def stats(self):
            return {}

    monkeypatch.setattr(prof, "SLOW_PASS_MS", 50.0)
    p = prof.profiler()
    sched = Scheduler(SlowStep(), queue_depth=4)
    t0 = time.time_ns()  # a pass is stamped when it starts: it may be waiting
    sched.start(max_concurrent=1)
    try:
        sess = Session([1, 2], max_tokens=3)
        sched.submit(sess)
        while True:
            ev = sess.events.get(timeout=30)
            if ev[0] != "token":
                break
        assert ev[0] == "done" and len(sess.generated) == 3
    finally:
        sched.close()
    slow = p.slow_passes()
    assert len(slow) == 3
    for rec in slow:
        assert set(rec) == {"t_unix_ns", "total_ms", "admit_ms", "step_ms",
                            "deliver_ms", "rest_ms", "queued", "running",
                            "cpu_ms", "fetch_ms", "fetch_of"}
        # the engine's thread slept through the step: it neither ran nor
        # waited for a device fetch (this engine keeps none)
        assert rec["cpu_ms"] < 0.5 * rec["total_ms"]
        assert rec["fetch_ms"] == 0.0 and rec["fetch_of"] == ""
        assert rec["step_ms"] >= 50.0 > rec["admit_ms"] + rec["deliver_ms"]
        assert rec["total_ms"] == pytest.approx(
            rec["admit_ms"] + rec["step_ms"] + rec["deliver_ms"]
            + rec["rest_ms"], abs=0.01)
        assert t0 <= rec["t_unix_ns"] <= time.time_ns()
    assert slow[0]["queued"] == 1 and slow[-1]["running"] == 1
    rep = prof.report()
    assert rep["slow_passes"] == slow
    assert p._slow_ms.value == pytest.approx(
        sum(r["total_ms"] for r in slow), abs=0.01)
    assert p._slow_n.value == len(slow)
    # parked time is not part of a pass: an idle scheduler stalls nothing
    assert all(r["total_ms"] < 1000 for r in slow)


def test_startup_reports_its_four_numbers(prof_env):
    assert "startup" not in prof.report() or prof.report()["startup"]
    prof.set_startup(params_s=1.23456, engine_s=0.5, warm_s=2.0,
                     loaded_s=4.0)
    try:
        assert prof.report()["startup"] == {
            "params_s": 1.235, "engine_s": 0.5, "warm_s": 2.0,
            "loaded_s": 4.0}
    finally:
        prof._STARTUP.clear()
    assert "startup" not in prof.report()


def test_a_slow_pass_says_its_parts_in_the_log(prof_env, caplog):
    """The ``slow_passes`` ring dies with the process; the server's log
    is what a benchmark run keeps. A pass over the limit leaves its
    parts there, a pass under it leaves nothing."""
    p = prof.profiler()
    parts = {"admit_ms": 1.0, "step_ms": 2400.0, "deliver_ms": 2.0}
    with caplog.at_level("WARNING", logger="cake_tpu.obs.prof"):
        p.note_pass(prof.SLOW_PASS_MS - 1.0, parts, queued=0, running=1)
        assert not caplog.records
        p.note_pass(2410.0, parts, queued=3, running=32)
    (rec,) = caplog.records
    said = rec.getMessage()
    assert said.startswith("slow scheduler pass: ")
    for part in ("'total_ms': 2410.0", "'step_ms': 2400.0", "'rest_ms': 7.0",
                 "'queued': 3", "'running': 32", "'cpu_ms': 0.0",
                 "'fetch_ms': 0.0", "'fetch_of': ''"):
        assert part in said


def test_a_slow_pass_says_whether_the_thread_ran_or_waited(
        params, prof_env, monkeypatch, caplog):
    """``cpu_ms`` and ``fetch_ms`` beside the parts, in the ring and in
    the log: a pass the device held has ``fetch_ms`` near its
    ``step_ms``, one that Python held has ``cpu_ms`` near it. The engine
    keeps the longest fetch of the current ``step()``."""
    import time

    from cake_tpu.serve.session import Session

    p = prof.profiler()
    with caplog.at_level("WARNING", logger="cake_tpu.obs.prof"):
        p.note_pass(1500.0, {"admit_ms": 1.0, "step_ms": 1490.0,
                             "deliver_ms": 2.0}, queued=0, running=8,
                    cpu_ms=12.3456, fetch_ms=1480.0)
    (rec,) = p.slow_passes()
    assert (rec["cpu_ms"], rec["fetch_ms"]) == (12.346, 1480.0)
    assert rec["rest_ms"] == 7.0  # neither is a part of the pass
    assert "'cpu_ms': 12.346, 'fetch_ms': 1480.0" in caplog.text
    # the engine: a block's fetch that takes 80 ms is the step's longest
    gen = BatchGenerator(CFG, params, settings=SamplerSettings(**GREEDY),
                         block_size=4)
    gen.set_prompts([[3, 1, 4], [1, 5, 9]])
    gen.step()
    real = gen._host

    def slow_host(x):
        time.sleep(0.08)
        return real(x)

    monkeypatch.setattr(gen, "_host", slow_host)
    seen = []
    for _ in range(7):
        gen.step()
        seen.append(gen.step_fetch_ms)
    assert max(seen) >= 80.0 and min(seen) == 0.0  # reset every step()
    # through the scheduler's pass: such a step is a slow pass whose
    # fetch_ms says the device (here the sleeping fetch) held it
    monkeypatch.setattr(prof, "SLOW_PASS_MS", 50.0)
    p.reset()
    sched = Scheduler(gen, queue_depth=4)
    sched.start(max_concurrent=2)
    try:
        sess = Session([2, 7, 1], max_tokens=6)
        sched.submit(sess)
        while sess.events.get(timeout=60)[0] == "token":
            pass
    finally:
        sched.close()
    slow = p.slow_passes()
    waited = [r for r in slow if r["fetch_ms"] >= 0.9 * r["total_ms"]]
    assert waited and all(80.0 <= r["fetch_ms"] <= r["step_ms"]
                          and r["cpu_ms"] < 0.2 * r["total_ms"]
                          for r in waited)
    # the others are the admission's compiles: no fetch held those
    assert all(r["fetch_ms"] < 0.5 * r["total_ms"]
               for r in slow if r not in waited), slow


def test_a_slow_pass_says_which_fetch_held_it(params, prof_env, monkeypatch,
                                              caplog):
    """``fetch_of`` beside ``fetch_ms``, in the ring and in the log: the
    longest wait for the device inside the engine's step was a block's
    fetch (``block:<steps>``) or an admission's first token
    (``admit_land:<bucket>``). The engine keeps it with
    ``step_fetch_ms``, reset every ``step()``, and the scheduler's pass
    hands both to ``note_pass``."""
    import time

    from cake_tpu.serve.session import Session

    gen = BatchGenerator(CFG, params, settings=SamplerSettings(**GREEDY),
                         block_size=4)
    gen.warm_admission(16)
    gen.set_prompts([[3, 1, 4], [1, 5, 9]])
    gen.streams[1].done = True
    gen.step()
    real = gen._host

    def slow_host(x):
        time.sleep(0.06)
        return real(x)

    monkeypatch.setattr(gen, "_host", slow_host)
    gen.enqueue([2, 7, 1], 7)
    seen = []
    for _ in range(12):
        gen.step()
        seen.append((gen.step_fetch_of, gen.step_fetch_ms))
    of = {o for o, _ in seen}
    assert {"", "block:4", "admit_land:16"} <= of, seen
    assert all((o == "") == (ms == 0.0) for o, ms in seen)
    assert all(ms >= 60.0 for o, ms in seen if o)
    # through the scheduler: the slow pass's record and log line say it
    monkeypatch.setattr(prof, "SLOW_PASS_MS", 50.0)
    p = prof.profiler()
    p.reset()
    sched = Scheduler(gen, queue_depth=4)
    sched.start(max_concurrent=2)
    try:
        with caplog.at_level("WARNING", logger="cake_tpu.obs.prof"):
            sess = Session([2, 7, 1], max_tokens=6)
            sched.submit(sess)
            while sess.events.get(timeout=60)[0] == "token":
                pass
    finally:
        sched.close()
    held = {r["fetch_of"] for r in p.slow_passes()
            if r["fetch_ms"] >= 0.9 * r["total_ms"]}
    assert "block:4" in held and held <= {"block:4", "admit_land:16"}, held
    assert "'fetch_of': 'block:4'" in caplog.text


# -- leaves, not parents, on the profile's host plane --------------------------

class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: every enter and
    exit, by thread, in order."""

    def __init__(self):
        import threading

        self.log: list = []  # (thread, "+" | "-", name)
        outer = self

        class Annotation:
            def __init__(self, name, **kw):
                self.name = name

            def __enter__(self):
                outer.log.append((threading.get_ident(), "+", self.name))
                return self

            def __exit__(self, *exc):
                outer.log.append((threading.get_ident(), "-", self.name))
                return False

        self.cls = Annotation

    def flat(self) -> list[str]:
        """The names in the order they were open, after checking that a
        thread never had two open and that every enter has its exit."""
        open_by_thread: dict = {}
        names = []
        for tid, what, name in self.log:
            if what == "+":
                assert tid not in open_by_thread, (
                    f"{name} opened under {open_by_thread[tid]}")
                open_by_thread[tid] = name
                names.append(name)
            else:
                assert open_by_thread.pop(tid) == name
        assert not open_by_thread, f"left open: {open_by_thread}"
        return names


@pytest.fixture
def annotated(prof_env, monkeypatch):
    """The span tracer as a capture runs it, the profiler's annotation
    replaced by a recorder."""
    import jax.profiler

    from cake_tpu.obs import trace as obs_trace

    rec = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec.cls)
    prof.profiler().set_sample(1)
    tr = obs_trace.tracer()
    tr.start(xla_annotations=True)
    yield rec, tr
    tr.stop()
    tr.clear()


_NESTINGS = {
    "admit>admit_land>dispatch": (
        ("admit", "admit_land", "dispatch"),
        ["prof.admit", "prof.admit_land", "prof.dispatch",
         "prof.admit_land", "prof.admit"]),
    "dispatch>pages": (
        ("dispatch", "pages"),
        ["prof.dispatch", "prof.pages", "prof.dispatch"]),
    "emit>guide": (
        ("emit", "guide"), ["prof.emit", "prof.guide", "prof.emit"]),
}


@pytest.mark.parametrize("case", sorted(_NESTINGS))
def test_the_profile_gets_the_leaf_and_the_parent_again_after_it(
        annotated, case):
    """While a capture is open at most ONE annotation is open a thread,
    the innermost phase's: a phase closes the annotation of the one it
    nests in and that one's name re-appears when it ends, so a reader
    that names a stretch by the annotation covering most of it names the
    leaf. The tracer's own records stay nested (``parent``)."""
    rec, tr = annotated
    nest, want = _NESTINGS[case]
    p = prof.profiler()
    p.step_begin("batch")
    try:
        def enter(names):
            if names:
                with p.phase(names[0]):
                    enter(names[1:])

        enter(nest)
    finally:
        p.step_end()
    assert rec.flat() == want
    evs = {e["name"]: e for e in tr.to_chrome_trace()["traceEvents"]
           if e.get("ph") == "X"}
    for outer, inner in zip(nest, nest[1:]):
        assert evs[f"prof.{inner}"]["args"]["parent"] == f"prof.{outer}"
        o, i = evs[f"prof.{outer}"], evs[f"prof.{inner}"]
        assert o["ts"] <= i["ts"] and i["ts"] + i["dur"] <= o["ts"] + o["dur"]


def test_a_request_span_around_a_step_is_closed_under_its_phases(annotated):
    """A request span around a step is a parent like any other: the
    profile shows it only where no phase is open under it, on this
    thread; another thread's annotations are its own."""
    import threading

    from cake_tpu.obs import trace as obs_trace

    rec, _ = annotated
    p = prof.profiler()
    other = threading.Thread(target=lambda: obs_trace.span(
        "elsewhere").__enter__().__exit__(None, None, None))
    with obs_trace.span("engine.prefill", sid=3):
        p.step_begin("batch")
        with p.phase("admit"):
            other.start()
            other.join()
            with p.phase("admit_launch"):
                pass
        p.step_end()
        with p.pass_part("deliver"):
            pass
    assert rec.flat() == [
        "engine.prefill", "prof.admit", "elsewhere", "prof.admit_launch",
        "prof.admit", "engine.prefill", "prof.deliver", "engine.prefill"]


def test_a_phase_that_raises_leaves_no_annotation_open(annotated):
    """Every enter has its exit when a phase raises, at any depth, and
    the thread's next span opens with nothing over it."""
    rec, _ = annotated
    p = prof.profiler()
    p.step_begin("batch")
    with pytest.raises(RuntimeError, match="boom"):
        try:
            with p.phase("admit"):
                with p.phase("admit_land"):
                    with p.phase("dispatch"):
                        raise RuntimeError("boom")
        finally:
            p.step_end()
    with p.pass_part("retire"):
        pass
    assert rec.flat() == [
        "prof.admit", "prof.admit_land", "prof.dispatch", "prof.admit_land",
        "prof.admit", "prof.retire"]


def test_a_capture_that_closes_under_a_span_leaves_no_annotation_open(
        annotated):
    """The capture's stop turns the pass-through off while the engine's
    thread is inside a phase: that phase's exit still closes what is
    open, and nothing is opened after it."""
    rec, tr = annotated
    p = prof.profiler()
    with p.pass_part("sched_admit"):
        with p.pass_part("deliver"):
            tr.xla_annotations = False
    with p.pass_part("retire"):
        pass
    assert rec.flat() == ["prof.sched_admit", "prof.deliver"]
    assert len([e for e in tr.to_chrome_trace()["traceEvents"]
                if e.get("ph") == "X"]) == 3
