"""SLO-aware scheduling (cake_tpu/serve, ISSUE 20): priority classes,
preemption with host-RAM KV spill, per-tenant fairness.

`make slo-smoke` acceptance: an interactive arrival jumps queued batch
work and — on a saturated paged engine — preempts a batch victim into
the bounded host-RAM spill store, with the victim's stream resuming
BIT-IDENTICALLY (greedy, sampled, and constrained mid-grammar) when
pressure drops; the spill chaos matrix (resume-storm, spill-store-full,
victim-finishes-during-spill) leaves every stream intact; admission
deferral under spill pressure counts exactly once per deferred
admission; unknown ``class``/``tenant`` values 400 at the serve plane
and classed requests ride through the gateway untouched; ``/v1/batch``
runs N prompts to one resumable JSON result set; and over-budget
tenants queue behind in-budget arrivals of the same class.
"""

import contextlib
import json
import threading
import time
import urllib.error
import urllib.request

import jax
import pytest

from cake_tpu.disagg import peek_xfer_id
from cake_tpu.gateway.api import start_gateway
from cake_tpu.gateway.health import Backend, HealthMonitor
from cake_tpu.gateway.policy import make_policy, pick_batch
from cake_tpu.models import llama
from cake_tpu.models.config import tiny
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.runtime.batch_generator import BatchGenerator
from cake_tpu.serve.api import start_api_server
from cake_tpu.serve.scheduler import THROTTLED, Scheduler
from cake_tpu.serve.spill import SpillFull, SpillStore
from cake_tpu.testing.chaos import (
    SpillChaos,
    SpillFault,
    spill_schedule_from_seed,
)

# eos disabled (-1 never sampled): deterministic stream lengths, so the
# preempt/resume round trips can compare exact token sequences
CFG = tiny(max_seq_len=64, eos_token_id=-1)
GREEDY = dict(temperature=0.0, repeat_penalty=1.1)
SAMPLED = dict(temperature=0.9, seed=5)

# the canonical preemption victim: long enough that an interactive
# arrival injected after its first tokens always finds it mid-decode
VICTIM = {"prompt": "abcd", "max_tokens": 32, "class": "batch"}
INTERACTIVE = {"prompt": "zz", "max_tokens": 4, "class": "interactive"}


class _FakeTok:
    """id -> letter (alnum decodes, the test_serve convention)."""

    def decode(self, ids):
        return "".join(chr(ord("a") + (i % 26)) for i in ids)

    def encode(self, text):
        return [ord(c) - ord("a") for c in text]


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(7))


def _egen(params, pool=None, tokenizer=None, **settings):
    """Bare paged engine (no serve stack) for the admit-defer test."""
    kw = {"kv_pool_pages": pool} if pool else {}
    return BatchGenerator(
        CFG, params, tokenizer=tokenizer,
        settings=SamplerSettings(**(settings or GREEDY)),
        kv_layout="paged", kv_page_size=16, **kw)


def _tokens(gen, sid):
    for s in gen.streams:
        if s.active and not s.done and s.stream_id == sid:
            return list(s.generated)
    return None


def _drive(gen, sid, want, max_steps=400):
    """step() until stream ``sid`` holds ``want`` tokens; returns them."""
    for _ in range(max_steps):
        got = _tokens(gen, sid)
        if got is not None and len(got) >= want \
                and not gen.pending_admissions():
            return got[:want]
        gen.step()
    raise AssertionError(f"stream {sid} never reached {want} tokens")


@contextlib.contextmanager
def _stack(params, *, max_concurrent=1, queue_depth=16, settings=None,
           **sched_kw):
    """One paged serve replica: engine + scheduler + HTTP API. ONE slot
    by default — preemption needs a saturated engine, and one slot makes
    "saturated" deterministic."""
    gen = BatchGenerator(CFG, params, tokenizer=_FakeTok(),
                         settings=SamplerSettings(**(settings or GREEDY)),
                         kv_layout="paged", kv_page_size=16)
    sched = Scheduler(gen, queue_depth=queue_depth, request_timeout_s=120,
                      **sched_kw)
    sched.start(max_concurrent=max_concurrent)
    srv = start_api_server(sched)
    try:
        yield srv, sched
    finally:
        srv.close()
        sched.close()


def _url(srv) -> str:
    return f"http://127.0.0.1:{srv.port}"


def _get(url: str, timeout: float = 30.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _post(srv_or_url, body: dict, path: str = "/v1/completions",
          timeout: float = 120.0):
    base = srv_or_url if isinstance(srv_or_url, str) else _url(srv_or_url)
    req = urllib.request.Request(
        base.rstrip("/") + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _post_sse(srv_or_url, body: dict, timeout: float = 120.0,
              on_event=None):
    """Stream one request; returns (parsed events, raw data-line bytes)."""
    base = srv_or_url if isinstance(srv_or_url, str) else _url(srv_or_url)
    body = dict(body, stream=True)
    req = urllib.request.Request(
        base.rstrip("/") + "/v1/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    events, raw_lines = [], []
    with urllib.request.urlopen(req, timeout=timeout) as r:
        assert r.headers["Content-Type"] == "text/event-stream"
        for raw in r:
            raw = raw.strip()
            if not raw.startswith(b"data: "):
                continue
            raw_lines.append(raw)
            data = raw[len(b"data: "):]
            ev = data.decode() if data == b"[DONE]" else json.loads(data)
            events.append(ev)
            if on_event:
                on_event(ev)
    return events, raw_lines


def _ids_of(events):
    return [e["token"] for e in events
            if isinstance(e, dict) and "token" in e]


def _wait_queued(srv, n, timeout=30.0):
    """Poll /healthz until >= n requests sit in the admission queue —
    the ordering tests need BOTH contenders queued while the slot
    holder is still running, or there is nothing to reorder."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        if _get(_url(srv) + "/healthz")["queued"] >= n:
            return True
        time.sleep(0.01)
    return False


def _preempt_run(srv, victim_body, interactive=None, n_before=2):
    """Start the victim stream, inject an interactive arrival once the
    victim is mid-decode (``n_before`` tokens seen), run the arrival to
    completion, then drain the victim. Returns (victim token ids,
    interactive unary result)."""
    state = {"n": 0}
    mid_decode = threading.Event()

    def on_event(ev):
        if isinstance(ev, dict) and "token" in ev:
            state["n"] += 1
            if state["n"] >= n_before:
                mid_decode.set()

    def run():
        state["events"], _ = _post_sse(srv, victim_body,
                                       on_event=on_event)

    t = threading.Thread(target=run)
    t.start()
    assert mid_decode.wait(60), "victim never reached steady decode"
    res = _post(srv, interactive or INTERACTIVE)
    t.join(timeout=120)
    assert not t.is_alive(), "victim stream never completed"
    return _ids_of(state["events"]), res


@pytest.fixture(scope="module")
def greedy_base(params):
    """The victim's unpreempted greedy stream — the bit-identity
    reference every preemption/chaos case compares against."""
    with _stack(params) as (srv, _):
        events, _ = _post_sse(srv, VICTIM)
    ids = _ids_of(events)
    assert len(ids) == VICTIM["max_tokens"]
    return ids


@pytest.fixture(scope="module")
def server(params):
    """Shared 2-slot replica for the API-surface tests (validation,
    healthz, /v1/batch) — nothing here depends on preemption timing."""
    gen = BatchGenerator(CFG, params, tokenizer=_FakeTok(),
                         settings=SamplerSettings(**GREEDY),
                         kv_layout="paged", kv_page_size=16)
    sched = Scheduler(gen, queue_depth=16, request_timeout_s=120)
    sched.start(max_concurrent=2)
    srv = start_api_server(sched)
    yield srv
    srv.close()
    sched.close()


# -- spill store + chaos units (no engine) -----------------------------------


class TestSpillStore:
    def test_claim_lifecycle_and_capacity(self):
        st = SpillStore(max_bytes=100)
        c = st.spill_begin("a", 60, pages=2)
        # reservations count against capacity before the payload lands
        with pytest.raises(SpillFull):
            st.spill_begin("b", 60, pages=1)
        with pytest.raises(ValueError):
            st.spill_begin("a", 10, pages=1)  # duplicate key
        st.spill_commit(c, b"x" * 60)
        assert len(st) == 1
        assert st.stats()["bytes"] == 60 and st.stats()["pages"] == 2
        # abort releases the reservation for the next claim
        st.spill_abort(st.spill_begin("b", 40, pages=1))
        c2 = st.spill_begin("b", 40, pages=1)
        st.spill_commit(c2, b"y" * 40)
        assert st.take("a") == b"x" * 60
        assert st.take("a") is None  # take pops
        assert st.discard("b") and not st.discard("b")
        assert len(st) == 0

    def test_rejects_bad_capacity(self):
        with pytest.raises(ValueError):
            SpillStore(max_bytes=0)

    def test_commit_without_claim_raises(self):
        st = SpillStore(max_bytes=100)
        c = st.spill_begin("a", 10, pages=1)
        st.spill_abort(c)
        with pytest.raises(ValueError):
            st.spill_commit(c, b"z" * 10)


class TestSpillChaos:
    def test_fault_fires_at_exact_consult(self):
        c = SpillChaos([SpillFault("spill_full", at=2)])
        assert not c.fire("spill_full")   # consult 1: not yet
        assert c.fire("spill_full")       # consult 2: fires (and pops)
        assert not c.fire("spill_full")   # consult 3: spent
        assert c.events == [("spill_full@2", 2)]

    def test_kind_validation_and_seeded_schedule(self):
        with pytest.raises(ValueError):
            SpillFault("bogus", 1)
        with pytest.raises(ValueError):
            SpillFault("spill_full", 0)
        a, b = spill_schedule_from_seed(7), spill_schedule_from_seed(7)
        assert a == b and len(a) == 3
        assert all(f.kind != "none" and f.at >= 1 for f in a)
        assert spill_schedule_from_seed(8) != a


# -- request validation + surfaces -------------------------------------------


def test_class_and_tenant_validation(server):
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(server, {"prompt": "ab", "max_tokens": 2,
                       "class": "premium"})
    assert e.value.code == 400
    for bad in (7, "", "x" * 65):
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(server, {"prompt": "ab", "max_tokens": 2,
                           "tenant": bad})
        assert e.value.code == 400
    out = _post(server, {"prompt": "ab", "max_tokens": 2,
                         "class": "batch", "tenant": "acme"})
    assert len(out["token_ids"]) == 2


def test_healthz_and_metrics_carry_spill_series(server):
    h = _get(_url(server) + "/healthz")
    assert h["spilled"] == 0 and h["preemptions"] == 0
    text = urllib.request.urlopen(
        _url(server) + "/metrics", timeout=30).read().decode()
    for series in ("cake_serve_preemptions", "cake_serve_spill_bytes",
                   "cake_serve_tenant_throttled"):
        assert series in text, f"/metrics missing {series}"


def test_pick_batch_counts_spilled_load():
    """The batch-class route is least-outstanding-work-per-slot, and a
    replica's spilled victims are outstanding work: they come back."""
    a = Backend("pb0", "127.0.0.1:9991")
    b = Backend("pb1", "127.0.0.1:9992")
    a.probe_ok({"queued": 1, "running": 0, "max_concurrent": 2},
               up_after=1)
    b.probe_ok({"queued": 0, "running": 0, "max_concurrent": 2},
               up_after=1)
    assert pick_batch([a, b]) is b
    b.probe_ok({"queued": 0, "running": 0, "max_concurrent": 2,
                "spilled": 4}, up_after=1)
    assert pick_batch([a, b]) is a


# -- class-priority admission ordering ---------------------------------------


def test_interactive_jumps_queued_batch(params):
    """spill_mb=0: class ordering WITHOUT preemption — the queued
    interactive arrival must still finish before the batch request that
    arrived ahead of it."""
    with _stack(params, spill_mb=0.0) as (srv, sched):
        assert sched.stats().get("spill") is None
        first_token = threading.Event()
        occ = threading.Thread(target=_post_sse, args=(
            srv, {"prompt": "abcd", "max_tokens": 48,
                  "class": "interactive"}),
            kwargs={"on_event": lambda ev: first_token.set()})
        occ.start()
        assert first_token.wait(60)
        order, lock = [], threading.Lock()

        def client(name, body):
            _post(srv, body)
            with lock:
                order.append(name)

        tb = threading.Thread(target=client, args=(
            "batch", {"prompt": "bb", "max_tokens": 2, "class": "batch"}))
        ti = threading.Thread(target=client, args=(
            "inter", {"prompt": "ii", "max_tokens": 2,
                      "class": "interactive"}))
        tb.start()
        assert _wait_queued(srv, 1)  # batch queues first...
        ti.start()
        assert _wait_queued(srv, 2)  # ...and interactive must jump it
        for t in (tb, ti, occ):
            t.join(timeout=120)
            assert not t.is_alive()
        assert order[0] == "inter", f"batch served first: {order}"


def test_fifo_policy_keeps_arrival_order(params):
    """Under ``fifo`` the queued batch request is handed to the engine
    before the interactive one that arrived behind it. The order is the
    scheduler's own (``_admit_one``, where a session leaves the queue):
    the order in which two client threads see their two-token answers
    end is a race between those threads once the machine is busy (2 of
    20 runs beside five busy workers read it reversed)."""
    with _stack(params, sched_policy="fifo") as (srv, sched):
        assert sched.stats()["sched_policy"] == "fifo"
        admitted, admit_one = [], sched._admit_one

        def spy(sess):
            admitted.append(sess.cls)
            return admit_one(sess)

        sched._admit_one = spy
        first_token = threading.Event()
        occ = threading.Thread(target=_post_sse, args=(
            srv, {"prompt": "abcd", "max_tokens": 48,
                  "class": "interactive"}),
            kwargs={"on_event": lambda ev: first_token.set()})
        occ.start()
        assert first_token.wait(60)
        tb = threading.Thread(target=_post, args=(
            srv, {"prompt": "bb", "max_tokens": 2, "class": "batch"}))
        ti = threading.Thread(target=_post, args=(
            srv, {"prompt": "ii", "max_tokens": 2, "class": "interactive"}))
        tb.start()
        assert _wait_queued(srv, 1)
        ti.start()
        assert _wait_queued(srv, 2)
        for t in (tb, ti, occ):
            t.join(timeout=120)
            assert not t.is_alive()
        # the slot's holder, then the two in the order they arrived
        assert admitted == ["interactive", "batch", "interactive"], (
            f"fifo reordered arrivals: {admitted}")
        with pytest.raises(ValueError, match="sched_policy"):
            sched.set_policy("lifo")


# -- preemption + spill round trips (the tentpole) ---------------------------


def test_preempt_resume_bit_identical_greedy(params, greedy_base):
    with _stack(params) as (srv, sched):
        ids, res = _preempt_run(srv, VICTIM)
        st = sched.stats()
        assert st["preemptions"] >= 1, "interactive never preempted"
        assert st["spilled"] == 0, "victim left in the spill store"
        assert st["sched_policy"] == "slo"
        assert st["spill"]["streams"] == 0
        assert st["spill"]["max_bytes"] == 64 << 20
        assert len(res["token_ids"]) == INTERACTIVE["max_tokens"]
        assert ids == greedy_base
        h = _get(_url(srv) + "/healthz")
        assert h["preemptions"] == st["preemptions"]


def test_preempt_resume_bit_identical_sampled(params):
    """The sampler key is folded from the PREFILL stream id and rides
    the spill snapshot, so the resumed sid does not matter — but the
    victim must prefill as the same sid in both stacks (first
    submission on a fresh stack, both here and in the baseline)."""
    with _stack(params, settings=SAMPLED) as (srv, _):
        base, _raw = _post_sse(srv, VICTIM)
    base_ids = _ids_of(base)
    assert len(base_ids) == VICTIM["max_tokens"]
    with _stack(params, settings=SAMPLED) as (srv, sched):
        ids, _res = _preempt_run(srv, VICTIM)
        assert sched.stats()["preemptions"] >= 1
        assert ids == base_ids


def test_preempt_resume_constrained_mid_grammar(params):
    body = dict(VICTIM, prompt="ab", max_tokens=20,
                response_format={"type": "regex",
                                 "pattern": "[a-d]{20}"})
    with _stack(params) as (srv, _):
        base, _raw = _post_sse(srv, body)
    base_ids = _ids_of(base)
    assert len(base_ids) == 20
    with _stack(params) as (srv, sched):
        ids, _res = _preempt_run(srv, body)
        assert sched.stats()["preemptions"] >= 1
        assert ids == base_ids
        tok = _FakeTok()
        assert all(c in "abcd" for c in tok.decode(ids))


# -- spill chaos matrix ------------------------------------------------------


@pytest.mark.parametrize("kind", ["victim_finish", "spill_full"])
def test_chaos_aborted_preemption_leaves_victim_intact(
        params, greedy_base, kind):
    """A preemption attempt that dies at the worst protocol point —
    the victim retires under the scheduler's feet, or the spill store
    reports full — must leave the victim stream bit-identical and the
    interactive request served (by a retried preemption or by simply
    waiting out the victim)."""
    with _stack(params) as (srv, sched):
        chaos = SpillChaos([SpillFault(kind, at=1)])
        sched.spill_chaos = chaos
        ids, res = _preempt_run(srv, VICTIM)
        assert ids == greedy_base
        assert len(res["token_ids"]) == INTERACTIVE["max_tokens"]
        assert chaos.events == [(f"{kind}@1", 1)]
        assert sched.stats()["spilled"] == 0


def test_chaos_resume_storm_bit_identical(params, greedy_base):
    """The storm forces every spilled victim back through the import
    path at once — while the engine is still saturated, so the resumes
    queue as deferred imports instead of landing — and the victim's
    stream must still come back byte-for-byte."""
    with _stack(params) as (srv, sched):
        chaos = SpillChaos([SpillFault("resume_storm", at=1)])
        sched.spill_chaos = chaos
        ids, res = _preempt_run(srv, VICTIM)
        assert ids == greedy_base
        assert len(res["token_ids"]) == INTERACTIVE["max_tokens"]
        assert sched.stats()["preemptions"] >= 1
        assert ("resume_storm@1", 1) in chaos.events
        assert sched.stats()["spilled"] == 0


# -- admission deferral under spill pressure (satellite) ---------------------


def test_admit_defer_counts_once_per_deferred_admission(params):
    """kvpool.admit_defers is per deferred ADMISSION, not per deferring
    tick: a spilled stream resuming into a full pool defers across many
    steps but counts exactly once, and the eventual landing does not
    recount."""
    donor = _egen(params)
    donor.set_prompts([[1] * 40])
    _drive(donor, 0, 12)
    snap = donor.export_stream(0)  # the spill payload shape

    # 3 streams x 4 pages fill the 16-page pool: the 4-page resume must
    # wait for a retirement
    b = _egen(params, pool=16)
    b.set_prompts([[1] * 40, [2] * 40, [3] * 40])
    for sid in (0, 1, 2):
        _drive(b, sid, 12)
    d0 = b._pagepool._defer_ctr.value
    b.import_begin(snap)
    b.import_attach(peek_xfer_id(snap), 7)
    for _ in range(6):
        b.step()
    assert b.imports_pending() == 1
    assert b._pagepool._defer_ctr.value == d0 + 1, \
        "deferral must count once per admission, not once per tick"
    ref = _drive(donor, 0, 18)
    b.finish(2)  # pressure drops: 4 pages + a slot free up
    assert _drive(b, 7, 18) == ref  # resumed bit-identically
    assert b._pagepool._defer_ctr.value == d0 + 1, \
        "the landing recounted the deferral"


# -- per-tenant fairness -----------------------------------------------------


def test_over_budget_tenant_queues_behind(params):
    """A tenant that just burned a large token share queues behind an
    in-budget arrival of the SAME class that arrived later, and the
    bypass shows up on serve.tenant_throttled."""
    with _stack(params, spill_mb=0.0, fairness_factor=0.5) as (srv, _):
        # hog earns its share first (the accountant decays over ~10s,
        # far longer than this test)
        _post(srv, {"prompt": "abcd", "max_tokens": 24,
                    "class": "batch", "tenant": "hog"})
        t0 = THROTTLED.value
        first_token = threading.Event()
        occ = threading.Thread(target=_post_sse, args=(
            srv, {"prompt": "dcba", "max_tokens": 40,
                  "class": "interactive"}),
            kwargs={"on_event": lambda ev: first_token.set()})
        occ.start()
        assert first_token.wait(60)
        order, lock = [], threading.Lock()

        def client(name, body):
            _post(srv, body)
            with lock:
                order.append(name)

        th = threading.Thread(target=client, args=(
            "hog", {"prompt": "bb", "max_tokens": 2, "class": "batch",
                    "tenant": "hog"}))
        tf = threading.Thread(target=client, args=(
            "fair", {"prompt": "cc", "max_tokens": 2, "class": "batch",
                     "tenant": "fair"}))
        th.start()
        assert _wait_queued(srv, 1)  # hog queues first; fair must jump it
        tf.start()
        assert _wait_queued(srv, 2)
        for t in (th, tf, occ):
            t.join(timeout=120)
            assert not t.is_alive()
        assert order[0] == "fair", f"over-budget tenant served first: " \
                                   f"{order}"
        assert THROTTLED.value > t0


# -- /v1/batch bulk endpoint -------------------------------------------------


def test_batch_endpoint_resumable_roundtrip(server):
    body = {"prompts": ["abcd", "bcde", "cdef"], "max_tokens": 4,
            "id": "batch-t1"}
    out = _post(server, body, path="/v1/batch")
    assert out["id"] == "batch-t1" and out["object"] == "batch"
    assert out["status"] == "done" and out["n"] == 3 and out["done"] == 3
    for p, r in zip(body["prompts"], out["results"]):
        assert r["finish_reason"] == "length"
        solo = _post(server, {"prompt": p, "max_tokens": 4,
                              "class": "batch"})
        assert r["token_ids"] == solo["token_ids"]
        assert r["text"] == solo["text"]
    # resumable by id after a disconnect...
    again = _get(_url(server) + "/v1/batch/batch-t1")
    assert again["results"] == out["results"]
    # ...and via an idempotent re-POST (answered from the registry)
    re_post = _post(server, body, path="/v1/batch")
    assert re_post["results"] == out["results"]
    with pytest.raises(urllib.error.HTTPError) as e:
        _get(_url(server) + "/v1/batch/no-such-batch")
    assert e.value.code == 404


def test_batch_endpoint_validation(server):
    for bad in ({}, {"prompts": []}, {"prompts": "abcd"},
                {"prompts": ["ab"], "id": ""}):
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(server, bad, path="/v1/batch")
        assert e.value.code == 400
    # a bad prompt becomes a result row, not a failed batch
    out = _post(server, {"prompts": ["abcd", ["not", "ints"]],
                         "max_tokens": 2}, path="/v1/batch")
    assert out["status"] == "done"
    assert out["results"][0]["finish_reason"] == "length"
    assert out["results"][1]["status"] == 400


def test_batch_endpoint_self_throttles_past_queue_depth(params):
    """More prompts than slots + queue: the endpoint must drain and
    retry instead of surfacing QueueFull."""
    with _stack(params, max_concurrent=1, queue_depth=2) as (srv, _):
        out = _post(srv, {"prompts": [f"a{chr(98 + i)}" for i in range(8)],
                          "max_tokens": 2}, path="/v1/batch")
        assert out["status"] == "done" and out["done"] == 8
        assert all(r["finish_reason"] == "length" for r in out["results"])


# -- gateway: classed requests ride through untouched ------------------------


def test_gateway_vs_direct_classed_parity(params):
    """The gateway forwards class/tenant bodies byte-for-byte: an SSE
    stream through the gateway is token-line-identical to a direct
    connection, for both classes, and batch-class unary responses
    match. pick_batch itself routes to the least-loaded replica."""
    stacks = []
    for _ in range(2):
        gen = BatchGenerator(CFG, params, tokenizer=_FakeTok(),
                             settings=SamplerSettings(**GREEDY),
                             kv_layout="paged", kv_page_size=16)
        sched = Scheduler(gen, queue_depth=8, request_timeout_s=120)
        sched.start(max_concurrent=2)
        srv = start_api_server(sched)
        stacks.append((srv, sched))
    backends = [Backend(f"slo{i}", f"127.0.0.1:{srv.port}")
                for i, (srv, _) in enumerate(stacks)]
    mon = HealthMonitor(backends, probe_interval=0.2, up_after=1)
    mon.start(initial_probe=True)
    gw = start_gateway(mon, make_policy("prefix", prefix_block=8),
                       connect_timeout=1.0, read_timeout=60.0)
    try:
        direct = f"http://127.0.0.1:{stacks[0][0].port}"
        gw_url = f"http://127.0.0.1:{gw.port}"
        for cls in ("interactive", "batch"):
            body = {"prompt": "abcd", "max_tokens": 6, "class": cls,
                    "tenant": "acme"}
            _d_ev, d_raw = _post_sse(direct, body)
            _g_ev, g_raw = _post_sse(gw_url, body)
            assert [r for r in g_raw if b'"token"' in r] \
                == [r for r in d_raw if b'"token"' in r], \
                f"gateway reframed a {cls} stream"
            d_out = _post(direct, body)
            g_out = _post(gw_url, body)
            assert g_out["token_ids"] == d_out["token_ids"]
        # unknown class 400s identically through the gateway
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(gw_url, {"prompt": "ab", "max_tokens": 2,
                           "class": "premium"})
        assert e.value.code == 400
    finally:
        gw.close()
        mon.stop()
        for srv, sched in stacks:
            srv.close()
            sched.close()
