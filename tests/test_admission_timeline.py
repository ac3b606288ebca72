"""An admission's stages and a block's period, timed where they happen
(PR 36): the four ``engine.admit_*_ms`` histograms and
``engine.admissions_landed`` (once per landed prompt admission), the two
``engine.block_period*_ms`` histograms (once per landed block), the
request's own timeline of them through the serving plane, and the
benchmark's seven readers (``benchmark/layer_metrics/engine.*.py``).
"""

import importlib.util
import json
import os
import sys
import time
import urllib.request
from pathlib import Path

import jax
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import tiny
from cake_tpu.obs import catalog
from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.obs import reqtrace
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.runtime import batch_generator as bg
from cake_tpu.runtime.batch_generator import BatchGenerator
from cake_tpu.serve.api import start_api_server
from cake_tpu.serve.scheduler import Scheduler

ROOT = Path(__file__).resolve().parent.parent
CFG = tiny(max_seq_len=64, eos_token_id=-1)
GREEDY = dict(temperature=0.0, repeat_penalty=1.1)
STAGES = ("launch_wait", "rows_wait", "land", "to_splice")
STAGE_HISTS = tuple(f"engine.admit_{s}_ms" for s in STAGES)
PERIODS = ("engine.block_period_ms", "engine.block_period_clear_ms")
NEW_SERIES = STAGE_HISTS + PERIODS + ("engine.admissions_landed",)
READERS = {  # metric -> (series it reads, its value on _made_up_ctx)
    "engine.admit_launch_wait_mean_ms": ("engine.admit_launch_wait_ms", 40.0),
    "engine.admit_rows_wait_mean_ms": ("engine.admit_rows_wait_ms", 50.0),
    "engine.admit_land_mean_ms": ("engine.admit_land_ms", 20.0),
    "engine.admit_to_splice_mean_ms": ("engine.admit_to_splice_ms", 1.5),
    "engine.block_period_ms": ("engine.block_period_ms", 130.0),
    "engine.block_period_clear_ms": ("engine.block_period_clear_ms", 75.0),
    "engine.admits_per_block": ("engine.admissions_landed", 1.9),
    # PR 37: admissions landed over admission programs launched
    "engine.admits_per_launch": ("engine.admit_launches", 2.5),
    # PR 54: device halves that left before the rows, over launches
    "engine.landings_before_rows_share": ("engine.landings_before_rows",
                                          50.0),
}
SYSTEM = [(i * 7) % 100 + 2 for i in range(16)]  # a shared 16-token prefix


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(11))


def _counts() -> dict:
    snap = obs_metrics.registry().snapshot()
    return {n: snap.get(n, {}).get("count", snap.get(n, {}).get("value", 0))
            for n in NEW_SERIES}


def _sums() -> dict:
    snap = obs_metrics.registry().snapshot()
    return {n: snap.get(n, {}).get("sum", 0.0) for n in STAGE_HISTS}


def _grown(before: dict) -> dict:
    return {n: v - before[n] for n, v in _counts().items()}


def _engine(params, **kw) -> BatchGenerator:
    g = BatchGenerator(CFG, params, settings=SamplerSettings(**GREEDY), **kw)
    g.set_prompts([[4, 4, 4], [6, 6, 6]])
    g.step()
    return g


def _admit_all(g, arrivals) -> None:
    """Each arrival in turn into a freed slot, stepped until it landed,
    then a few steps more so that rows and blocks follow it."""
    for prompt, sid in arrivals:
        live = next(s for s in g.streams if s.active and not s.done)
        g.finish(live.stream_id)
        g.enqueue(list(prompt), stream_id=sid)
        while g.pending_admissions():
            g.step()
        for _ in range(3):
            g.step()


# -- an admission's stages ---------------------------------------------------

@pytest.mark.parametrize("name,kw,arrivals", [
    ("plain", dict(block_size=4),
     [([2, 8, 1, 7, 6], 10), ([3, 1, 4], 11)]),
    ("chunked", dict(block_size=4, admit_chunk=4),
     [([2, 8, 1, 7, 6, 5, 4, 3, 9], 10)]),  # three chunks of 4
    ("prefix-hit", dict(admit_chunk=8, prefix_share_min=8, prefix_block=8),
     [(SYSTEM + [5, 9, 2], 10), (SYSTEM + [8, 8, 4], 11)]),
    ("paged", dict(block_size=4, kv_layout="paged", kv_page_size=8),
     [([2, 8, 1, 7, 6], 10), (SYSTEM + [3, 1], 11)]),
    ("slot-single-steps", dict(), [([2, 8, 1, 7, 6], 10)]),
])
def test_each_prompt_admission_observes_each_stage_once(params, name, kw,
                                                        arrivals):
    g = _engine(params, **kw)
    before = _counts()
    _admit_all(g, arrivals)
    grown = _grown(before)
    for series in STAGE_HISTS + ("engine.admissions_landed",):
        assert grown[series] == len(arrivals), (name, series, grown)
    if name == "prefix-hit":
        assert g.stats()["prefix_hits"] == 1
    for _, sid in arrivals:
        stages = g.take_admission_stages(sid)
        assert [s[0] for s in stages] == list(STAGES)
        assert all(ms >= 0.0 for _, _, ms in stages)
        # each stage starts where the one before ended
        for (_, t0, ms), (_, t1, _) in zip(stages, stages[1:]):
            assert t1 == pytest.approx(t0 + ms / 1e3, abs=1e-6)
        assert g.take_admission_stages(sid) is None  # dropped when read


def test_imports_attaches_and_admit_observe_no_stage(params):
    g = _engine(params, block_size=4, kv_layout="paged", kv_page_size=8)
    for _ in range(6):
        g.step()
    before = _counts()
    snap = g.export_stream(0)
    g.finish(0)
    g.import_stream(snap, stream_id=20)  # an import and its attach
    g.finish(1)
    slot, tok = g.admit([2, 8, 1, 7], stream_id=21)  # synchronous
    assert tok is not None and g.streams[slot].stream_id == 21
    for _ in range(6):
        g.step()
    grown = _grown(before)
    assert not any(grown[n] for n in STAGE_HISTS), grown
    assert grown["engine.admissions_landed"] == 0
    assert g.take_admission_stages(20) is None
    assert g.take_admission_stages(21) is None


class _Ticks:
    """The engine module's ``time``: every ``perf_counter()`` is one
    second after the one before."""

    def __init__(self):
        self.now = 1000.0

    def perf_counter(self) -> float:
        self.now += 1.0
        return self.now

    monotonic = staticmethod(time.monotonic)


@pytest.mark.parametrize("kw", [dict(block_size=4),
                                dict(block_size=4, admit_chunk=4)])
def test_stamps_are_ordered_and_stages_sum_to_enqueue_to_first_token(
        params, monkeypatch, kw):
    g = _engine(params, **kw)
    for _ in range(5):
        g.step()
    clock = _Ticks()
    monkeypatch.setattr(bg, "time", clock)
    sums = _sums()
    g.finish(0)
    t_before = clock.now
    g.enqueue([2, 8, 1, 7, 6, 5, 4, 3, 9], stream_id=10)
    while not any(s.stream_id == 10 and s.generated for s in g.streams):
        g.step()  # the step in which it lands records its first token
    stages = g.take_admission_stages(10)
    starts = [t0 for _, t0, _ in stages]
    end = starts[-1] + stages[-1][2] / 1e3
    # enqueued < launched < land_begin < landed, whole ticks; the splice
    # left before the token was fetched, so the last stage is empty
    assert starts[0] == t_before + 1.0
    assert starts == sorted(set(starts)) and end == starts[-1]
    assert stages[-1][2] == 0.0
    assert end <= clock.now
    # the stages leave nothing out between enqueue() and the first token
    # on the host, which is recorded without another look at the clock
    assert sum(ms for _, _, ms in stages) == pytest.approx(
        (end - starts[0]) * 1e3)
    grew = {n: v - sums[n] for n, v in _sums().items()}
    for (stage, _, ms), series in zip(stages, STAGE_HISTS):
        assert grew[series] == pytest.approx(ms), stage


def test_a_landing_before_its_rows_keeps_the_stamps_in_stage_order(
        params, monkeypatch):
    """The device half leaves while the landed block's rows go out (PR
    54): ``land_begin`` is its entry and ``landed`` the first token on
    the host, after those rows, so the rows' time is the ``land`` stage's
    and no longer ``rows_wait``'s; the stages still leave nothing out."""
    g = _engine(params, block_size=4)
    while not g._pending_rows:  # a block has landed: 4 rows to go out
        g.step()
    clock = _Ticks()
    monkeypatch.setattr(bg, "time", clock)
    before = obs_metrics.registry().snapshot()[
        "engine.landings_before_rows"]["value"]
    g.finish(0)
    t_before = clock.now
    g.enqueue([2, 8, 1, 7, 6, 5, 4, 3, 9], stream_id=10)
    g.step()  # launch and device half at the boundary; a row goes out
    assert g._landed and len(g._pending_rows) == 3
    t_device_half = clock.now
    while not any(s.stream_id == 10 and s.generated for s in g.streams):
        g.step()
    assert obs_metrics.registry().snapshot()[
        "engine.landings_before_rows"]["value"] == before + 1
    stages = g.take_admission_stages(10)
    assert [s[0] for s in stages] == list(STAGES)
    starts = [t0 for _, t0, _ in stages]
    end = starts[-1] + stages[-1][2] / 1e3
    assert starts[0] == t_before + 1.0
    assert starts == sorted(set(starts)) and stages[-1][2] == 0.0
    # launched and land_begin within the step() of the device half, the
    # first token on the host after the three rows that followed it
    assert starts[2] < t_device_half < starts[3]
    assert sum(ms for _, _, ms in stages) == pytest.approx(
        (end - starts[0]) * 1e3)
    for (_, t0, ms), (_, t1, _) in zip(stages, stages[1:]):
        assert t1 == pytest.approx(t0 + ms / 1e3, abs=1e-6)


# -- a block's period --------------------------------------------------------

def _count_landings(g) -> list:
    landings = []
    real = g._land_block

    def land():
        landings.append(1)
        return real()

    g._land_block = land
    return landings


def test_periods_count_landed_blocks_less_one_and_clear_ones_apart(params):
    g = _engine(params, block_size=4)
    landings = _count_landings(g)
    before = _counts()
    while len(landings) < 4:
        g.step()
    grown = _grown(before)
    assert grown["engine.block_period_ms"] == 3
    assert grown["engine.block_period_clear_ms"] == 3  # nobody was admitted
    # one admission lands: the period that holds it is not clear
    g.finish(0)
    g.enqueue([2, 8, 1, 7, 6], stream_id=10)
    while g.pending_admissions():
        g.step()
    n_at_landing = len(landings)
    while len(landings) < n_at_landing + 3:
        g.step()
    grown = _grown(before)
    assert grown["engine.admissions_landed"] == 1
    assert grown["engine.block_period_ms"] == len(landings) - 1
    assert grown["engine.block_period_clear_ms"] == len(landings) - 2


def test_an_engine_that_went_idle_starts_no_period_across_the_idleness(
        params, monkeypatch):
    g = _engine(params, block_size=4)
    landings = _count_landings(g)
    before = _counts()
    sum0 = obs_metrics.registry().snapshot().get(
        "engine.block_period_ms", {}).get("sum", 0.0)
    while len(landings) < 3:
        g.step()
    # every stream ends where its caller saw it end (a token budget: the
    # next block has left already), and nobody steps an engine without
    # work: it waits for a request, here for an hour
    for s in g.streams:
        g.finish(s.stream_id)
    clock = _Ticks()
    clock.now = time.perf_counter() + 3600.0
    monkeypatch.setattr(bg, "time", clock)
    g.enqueue([2, 8, 1, 7, 6], stream_id=10)
    while len(landings) < 6:
        g.step()
    grown = _grown(before)
    # two busy stretches: each one's first landing closes no period
    assert grown["engine.block_period_ms"] == len(landings) - 2
    # and no period holds the hour (the patched clock makes every later
    # look at it a second: the periods since are tens of those)
    snap = obs_metrics.registry().snapshot()["engine.block_period_ms"]
    assert snap["sum"] - sum0 < 3600.0 * 1e3


def test_single_steps_and_rounds_observe_no_period(params):
    before = _counts()
    for kw in (dict(), dict(spec_k=2)):
        g = _engine(params, **kw)
        for _ in range(6):
            g.step()
    grown = _grown(before)
    assert not grown["engine.block_period_ms"]
    assert not grown["engine.block_period_clear_ms"]


# -- through the serving plane -----------------------------------------------

def _stream(url, prompt_ids, max_tokens, headers=None) -> list[int]:
    req = urllib.request.Request(
        url + "/v1/completions",
        data=json.dumps({"prompt_ids": prompt_ids, "max_tokens": max_tokens,
                         "stream": True}).encode(),
        headers=dict({"Content-Type": "application/json"}, **(headers or {})))
    ids = []
    with urllib.request.urlopen(req, timeout=120) as r:
        for raw in r:
            raw = raw.strip()
            if raw.startswith(b"data: ") and raw != b"data: [DONE]":
                ev = json.loads(raw[len(b"data: "):])
                assert "error" not in ev, ev
                if "token" in ev:
                    ids.append(ev["token"])
    return ids


@pytest.fixture(scope="module")
def served(params):
    """Two traced requests through a served scheduler over blocks of 4;
    what the series grew by, ``/metrics`` and both timelines."""
    gen = BatchGenerator(CFG, params, settings=SamplerSettings(**GREEDY),
                         block_size=4)
    sched = Scheduler(gen, queue_depth=8, request_timeout_s=120)
    before = _counts()
    sched.start(max_concurrent=2)
    srv = start_api_server(sched)
    url = f"http://127.0.0.1:{srv.port}"
    out = {"timelines": []}
    try:
        for prompt in ([1, 2, 3, 4, 5], [9, 8, 7]):
            tid = os.urandom(16).hex()
            header = f"00-{tid}-{os.urandom(8).hex()}-01"
            assert len(_stream(url, prompt, 9,
                               {reqtrace.HEADER: header})) == 9
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                tl = reqtrace.request_log().get(tid)
                if tl is not None and any(
                        s["name"] == "session.emit" for s in tl["spans"]):
                    break
                time.sleep(0.05)
            with urllib.request.urlopen(
                    f"{url}/v1/requests/{tl['request_id']}", timeout=30) as r:
                out["timelines"].append(json.loads(r.read()))
        out["metrics"] = urllib.request.urlopen(
            url + "/metrics", timeout=10).read().decode()
        out["status"] = json.loads(urllib.request.urlopen(
            url + "/", timeout=10).read())
    finally:
        srv.close()
        sched.close()
    out["grown"] = _grown(before)
    return out


def test_the_series_are_on_metrics_through_a_served_scheduler(served):
    assert served["grown"]["engine.admissions_landed"] == 2
    for series in STAGE_HISTS:
        assert served["grown"][series] == 2, series
    assert served["grown"]["engine.block_period_ms"] >= 2
    for series in NEW_SERIES:
        assert "cake_" + series.replace(".", "_") in served["metrics"], series
        assert series in served["status"]["metrics"], series
    assert "admit_chunk_ms" not in served["metrics"]


def test_a_requests_timeline_holds_its_admissions_stages(served):
    """``GET /v1/requests/<id>``: the four stages under the request's
    ``engine.prefill`` span, with the request's id, in order, inside the
    span that caused them."""
    for tl in served["timelines"]:
        spans = {s["name"]: s for s in tl["spans"]}
        prefill = spans["engine.prefill"]
        stages = [spans[f"engine.admit.{s}"] for s in STAGES]
        for s in stages:
            assert s["parent"] == prefill["span"]
            assert s["args"]["request"] == tl["request_id"]
        assert [s["t"] for s in stages] == sorted(s["t"] for s in stages)
        # handed to the engine -> first token holds all four (the clocks
        # are tied once a request: a millisecond of room)
        assert stages[0]["t"] >= prefill["t"] - 1e-3
        assert (stages[-1]["t"] + stages[-1]["ms"] / 1e3
                <= prefill["t"] + prefill["ms"] / 1e3 + 1e-3)
        assert sum(s["ms"] for s in stages) <= prefill["ms"] + 1.0


# -- the catalog, the phases, the readers ------------------------------------

def test_every_new_series_is_declared_and_the_removed_one_is_in_none():
    for series in STAGE_HISTS + PERIODS:
        assert catalog.kind_of(series) == catalog.HISTOGRAM, series
    assert catalog.kind_of("engine.admissions_landed") == catalog.COUNTER
    assert not catalog.is_declared("serve.admit_chunk_ms")
    assert "serve.admit_chunk_ms" not in obs_metrics.registry().snapshot()
    for path in ("cake_tpu/runtime/batch_generator.py", "README.md",
                 "cake_tpu/obs/catalog.py"):
        assert "admit_chunk_ms" not in (ROOT / path).read_text(), path


def _bench_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run_for_tests", ROOT / "benchmark" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _made_up_ctx(without: str | None = None) -> dict:
    """A window of 100 admissions and 52 block periods (19 of them
    clear), as ``GET /`` gives the registry on either side of it."""
    def hist(count, total):
        return {"type": "histogram", "count": count, "sum": total}

    def counter(value):
        return {"type": "counter", "value": value}

    before = {"engine.admit_launch_wait_ms": hist(10, 900.0),
              "engine.admit_rows_wait_ms": hist(10, 100.0),
              "engine.admit_land_ms": hist(10, 50.0),
              "engine.admit_to_splice_ms": hist(10, 5.0),
              "engine.block_period_ms": hist(8, 1000.0),
              "engine.block_period_clear_ms": hist(1, 70.0),
              "engine.admissions_landed": counter(10),
              "engine.admit_launches": counter(9),
              "engine.landings_before_rows": counter(4)}
    after = {"engine.admit_launch_wait_ms": hist(110, 4900.0),
             "engine.admit_rows_wait_ms": hist(110, 5100.0),
             "engine.admit_land_ms": hist(110, 2050.0),
             "engine.admit_to_splice_ms": hist(110, 155.0),
             "engine.block_period_ms": hist(58, 7500.0),
             "engine.block_period_clear_ms": hist(21, 1570.0),
             "engine.admissions_landed": counter(105),
             "engine.admit_launches": counter(47),
             "engine.landings_before_rows": counter(23)}
    for side in (before, after):
        side["serve.ttft_ms"] = hist(5, 500.0)
        side.pop(without, None)
    return {"before": {"status": {"metrics": before}},
            "after": {"status": {"metrics": after}}}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_reader_reads_its_series_and_nothing_from_an_older_program(metric):
    series, want = READERS[metric]
    path = list(sys.path)  # run.py puts benchmark/ first: not for tier-1
    try:
        read = _bench_run().load_reader(metric)
        assert read(_made_up_ctx()) == pytest.approx(want)
        # the parent commit's program has no such series: no value, no error
        assert read(_made_up_ctx(without=series)) is None
    finally:
        sys.path[:] = path


def test_the_benchmark_declares_the_seven_at_the_end_of_per_layer():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    # the closed-loop cells that report a time to first token (a later
    # cell whose first-token wait spreads too widely between seeds does
    # not: PR 40)
    closed = next(m for m in bench["end_to_end"]
                  if m["name"] == "ttft_mean_ms")["workloads"]
    assert "mistral7b-int8.chat-r80" not in closed and len(closed) >= 5
    added = bench["per_layer"][30:37]
    assert [m["name"] for m in added] == [
        "engine.admit_launch_wait_mean_ms", "engine.admit_rows_wait_mean_ms",
        "engine.admit_land_mean_ms", "engine.admit_to_splice_mean_ms",
        "engine.block_period_ms", "engine.block_period_clear_ms",
        "engine.admits_per_block"]
    for m in added:
        assert (m["source"], m["layer"]) == ("program_counter", "engine")
        assert m["better"] == "lower"
        if m["moves"] == "ttft_mean_ms":  # a closed loop's metric
            assert m["workloads"] == closed and "admit_" in m["name"]
        else:  # every cell reports tpot_p50_ms and lands blocks
            assert m["moves"] == "tpot_p50_ms" and "workloads" not in m
    assert {m["unit"] for m in added} == {"ms", "admissions"}


def test_the_benchmark_declares_admits_per_launch_after_them():
    """PR 37's one metric: the last entry of its day (later PRs append
    after it), reported in every cell (each reports ``tpot_p50_ms`` and
    launches admissions), more is better."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["per_layer"][37] == {
        "name": "engine.admits_per_launch", "unit": "admissions",
        "better": "higher", "source": "program_counter", "layer": "engine",
        "moves": "tpot_p50_ms"}
    assert len(bench["per_layer"]) >= 38
    assert catalog.kind_of("engine.admit_launches") == catalog.COUNTER


def test_the_benchmark_declares_the_share_of_landings_before_rows_last():
    """PR 54's one metric, appended: reported in every cell (each reports
    ``tpot_p50_ms`` and launches admissions), more is better."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("engine.landings_before_rows_share")
    assert at >= 47 and names[46] == "engine.landing_counts_fetch_ms"
    assert bench["per_layer"][at] == {
        "name": "engine.landings_before_rows_share", "unit": "%",
        "better": "higher", "source": "program_counter", "layer": "engine",
        "moves": "tpot_p50_ms"}
    assert (ROOT / "benchmark" / "layer_metrics"
            / "engine.landings_before_rows_share.py").is_file()
