"""Observability layer (cake_tpu/obs): metrics registry, span tracer with
Chrome trace-event export, per-token flight recorder, and the instrumented
runtime — a loopback master↔worker run whose wire byte counters must agree
across the master's flight records, the worker's status page, and the
registry; plus the CLI smoke (`make trace-smoke`) that validates every
``--trace``/``--metrics-out``/``--flight-log`` artifact parses."""

import json
import threading
import urllib.request

import jax
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import tiny
from cake_tpu.obs import flight, metrics, trace
from cake_tpu.obs.metrics import Histogram, Registry
from cake_tpu.obs.trace import span
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.parallel.topology import Topology
from cake_tpu.runtime.master import DistributedGenerator, build_runners
from cake_tpu.runtime.worker import Worker

CFG = tiny(max_seq_len=32)


# -- metrics registry --------------------------------------------------------

def test_counter_concurrent_increments():
    r = Registry(enabled=True)
    c = r.counter("hits")
    n_threads, n_inc = 8, 500

    def worker():
        for _ in range(n_inc):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_inc
    assert r.counter("hits") is c  # get-or-create returns the same series


def test_histogram_concurrent_observes_and_bucketing():
    h = Histogram("lat", buckets=(1.0, 10.0, 100.0))

    def worker():
        for _ in range(100):
            h.observe(0.5)
            h.observe(5.0)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count == 800
    assert h.min == 0.5 and h.max == 5.0
    snap = h.snapshot()
    assert snap["count"] == 800
    assert snap["buckets"]["1.0"] == 400  # every 0.5 lands in le=1.0
    assert snap["buckets"]["10.0"] == 400


def test_histogram_percentiles_within_bucket_bounds():
    h = Histogram("p", buckets=(1.0, 10.0, 100.0))
    for _ in range(50):
        h.observe(0.5)
    for _ in range(40):
        h.observe(5.0)
    for _ in range(10):
        h.observe(50.0)
    assert 0.5 <= h.percentile(0.5) <= 1.0
    assert 10.0 <= h.percentile(0.99) <= 50.0
    # clamped to the observed range, never past max
    assert h.percentile(1.0) == 50.0
    assert Histogram("empty").percentile(0.5) == 0.0


def test_registry_type_conflict_and_disabled_nulls():
    r = Registry(enabled=True)
    r.counter("x")
    with pytest.raises(TypeError):
        r.gauge("x")
    off = Registry(enabled=False)
    null = off.counter("y")
    null.inc()  # no-op, no error
    null.observe(1.0)
    assert off.snapshot() == {}


def test_registry_json_and_prometheus_dumps(tmp_path):
    r = Registry(enabled=True)
    r.counter("wire.bytes_out").inc(123)
    r.gauge("hbm.used_gib").set(1.5)
    h = r.histogram("step_ms", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    p = tmp_path / "metrics.json"
    r.dump_json(str(p))
    snap = json.loads(p.read_text())
    assert snap["wire.bytes_out"] == {"type": "counter", "value": 123}
    assert snap["step_ms"]["count"] == 2
    assert "p50" in snap["step_ms"] and "p99" in snap["step_ms"]
    prom = r.to_prometheus()
    assert "cake_wire_bytes_out 123" in prom
    assert 'cake_step_ms_bucket{le="1.0"} 1' in prom
    assert "cake_step_ms_count 2" in prom


# -- span tracer -------------------------------------------------------------

def test_span_disabled_is_shared_noop():
    tr = trace.tracer()
    assert not tr.enabled
    s1, s2 = span("a"), span("b", k=1)
    assert s1 is s2  # the shared null context manager
    with s1:
        pass


def test_chrome_trace_export_is_valid_trace_event_json():
    tr = trace.tracer()
    tr.start()
    try:
        with span("outer", seg=0):
            with span("inner"):
                pass

        def other_thread():
            with span("threaded"):
                pass

        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
    finally:
        tr.stop()
    doc = json.loads(json.dumps(tr.to_chrome_trace()))  # JSON round-trip
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} >= {"outer", "inner", "threaded"}
    # complete events only (no unmatched B/E), sorted ts, sane durations
    assert all(e["ph"] in ("X", "M") for e in evs)
    ts = [e["ts"] for e in xs]
    assert ts == sorted(ts)
    assert all(e["dur"] >= 0 for e in xs)
    assert all(isinstance(e["pid"], int) and isinstance(e["tid"], int)
               for e in xs)
    inner = next(e for e in xs if e["name"] == "inner")
    assert inner["args"]["parent"] == "outer"  # per-thread span stack
    threaded = next(e for e in xs if e["name"] == "threaded")
    assert "parent" not in threaded.get("args", {})
    tr.clear()


def test_tracer_event_cap_counts_drops():
    tr = trace.tracer()
    tr.start(max_events=2)
    try:
        for _ in range(5):
            with span("s"):
                pass
    finally:
        tr.stop()
    assert len(tr.to_chrome_trace()["traceEvents"]) >= 2
    assert tr.dropped == 3
    tr.clear()


# -- flight recorder ---------------------------------------------------------

def test_flight_recorder_ring_totals_and_jsonl(tmp_path):
    rec = flight.FlightRecorder(capacity=4)
    rec.record(index=0, kind="decode")  # disabled: dropped
    assert rec.records() == []
    p = tmp_path / "flight.jsonl"
    rec.enable(path=str(p))
    rec.record(index=0, kind="prefill", total_ms=3.0, wire_bytes_out=7,
               segments_ms=[1.0, 2.0])
    for i in range(1, 6):
        rec.record(index=i, kind="decode", total_ms=1.0, wire_bytes_out=10,
                   segments_ms=[0.25, 0.5], recovery=i == 3)
    rows = rec.records()
    assert len(rows) == 4  # bounded ring: oldest aged out
    assert all(r["kind"] == "decode" for r in rows)
    totals = rec.totals()
    assert totals["records"] == 4 and totals["by_kind"] == {"decode": 4}
    assert totals["wire_bytes_out"] == 40
    assert totals["recovery"] == 1
    assert totals["segments_ms"] == [1.0, 2.0]
    # the JSONL stream kept every record (writes flush in batches; close()
    # drains the tail), one parseable object per line
    rec.close()
    lines = [json.loads(ln) for ln in p.read_text().splitlines()]
    assert len(lines) == 6
    assert lines[0]["kind"] == "prefill" and lines[0]["t"] > 0
    rec.record(index=9, kind="decode")  # closed: dropped again
    assert len(rec.records()) == 4


# -- instrumented runtime: loopback master <-> worker ------------------------

@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(7))


def _loader(params):
    return lambda lo, hi: jax.tree.map(lambda a: a[lo:hi], params["layers"])


def test_loopback_wire_bytes_consistent_and_spans_recorded(params):
    """Two-segment decode (remote worker layers 0-1, local layers 2-3):
    the master's flight-recorder wire totals must equal the worker's own
    payload byte counters, the status page must expose nonzero wire
    metrics, and the Chrome trace must hold the canonical span set."""
    w = Worker("w1", CFG, Topology.from_dict(
        {"w1": {"layers": ["model.layers.0-1"]}}), _loader(params),
        address="127.0.0.1:0", max_seq=CFG.max_seq_len)
    w.serve_in_background()
    status_port = w.start_status_server(0)
    topo = Topology.from_dict({
        "w1": {"host": f"127.0.0.1:{w.port}",
               "layers": ["model.layers.0-1"]},
    })
    tr = trace.tracer()
    rec = flight.recorder()
    rec.clear()
    rec.enable()
    tr.start()
    # the registry is the process's: a chaos test that ran earlier on this
    # worker leaves its CRC failures in it, so hold this run to adding none
    crc_before = metrics.registry().snapshot(prefix="wire.crc_failures").get(
        "wire.crc_failures", {}).get("value", 0)
    try:
        runners = build_runners(CFG, topo, _loader(params))
        g = DistributedGenerator(
            CFG, {k: params[k] for k in ("embed", "norm_f", "lm_head")},
            runners,
            settings=SamplerSettings(temperature=0.0, repeat_penalty=1.1),
        )
        g.set_prompt([3, 5, 7])
        for i in range(4):
            g.next_token(i)

        stats = g.runner_stats()
        assert [s["layers"] for s in stats] == ["0-1", "2-3"]
        # 4 forwards per segment, first is warm-up -> 3 histogram samples
        assert all(s["calls"] == 3 for s in stats)
        assert all(s["avg_ms"] > 0 and s["warmup_ms"] > 0 for s in stats)
        assert all(s["p50_ms"] > 0 and s["p99_ms"] >= s["p50_ms"]
                   for s in stats)
        assert g.tokens_per_sec() is None or g.tokens_per_sec() > 0

        totals = rec.totals()
        assert totals["by_kind"] == {"prefill": 1, "decode": 3}
        assert len(totals["segments_ms"]) == 2  # one slot per segment
        assert totals["wire_bytes_out"] > 0 and totals["wire_bytes_in"] > 0

        with urllib.request.urlopen(
            f"http://127.0.0.1:{status_port}/", timeout=10
        ) as r:
            st = json.loads(r.read())
        # payload-level agreement: every byte the master's flight records
        # say went out arrived as worker bytes_in, and vice versa
        assert st["bytes_in"] == totals["wire_bytes_out"] > 0
        assert st["bytes_out"] == totals["wire_bytes_in"] > 0
        m = st["metrics"]
        assert m["wire.bytes_out"]["value"] > 0
        assert m["wire.bytes_in"]["value"] > 0
        assert m["wire.crc_failures"]["value"] == crc_before
        # 4 forwards: the first op of each activation shape (prefill and
        # the first decode — both compile) lands in the warmup gauge, the
        # steady-state rest in the histogram
        assert m["worker.forward_ms"]["count"] >= 2
        assert m["worker.warmup_ms"]["value"] > 0
        assert m["wire.serialize_ms"]["count"] >= 4

        with urllib.request.urlopen(
            f"http://127.0.0.1:{status_port}/metrics", timeout=10
        ) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            prom = r.read().decode()
        assert "cake_wire_bytes_out" in prom

        g.close()
        # the exit-time --metrics-out dump runs after close(): the
        # per-segment series must still be in the registry
        reg_snap = metrics.registry().snapshot(prefix="master.segment")
        assert reg_snap["master.segment0.decode_ms"]["count"] == 3
        assert reg_snap["master.segment1.warmup_ms"]["value"] > 0
    finally:
        tr.stop()
        rec.disable()
        w.shutdown()

    names = {e["name"] for e in tr.to_chrome_trace()["traceEvents"]
             if e["ph"] == "X"}
    assert names >= {"prefill", "decode.step", "decode.segment",
                     "wire.send", "wire.recv", "segment.remote_rtt",
                     "segment.local_scan", "sample", "worker.forward"}
    tr.clear()
    rec.clear()


# -- CLI smoke (`make trace-smoke`) ------------------------------------------

@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    from cake_tpu.utils.weights import save_llama_params

    d = tmp_path_factory.mktemp("obsmodel")
    p = llama.init_params(tiny(), jax.random.PRNGKey(0), dtype="float32")
    save_llama_params(p, d)
    (d / "config.json").write_text(json.dumps(tiny().to_hf_dict()))
    return d


def test_trace_smoke_cli_artifacts_parse(model_dir, tmp_path):
    """Tiny CPU-only decode with every obs flag: the Chrome trace, metrics
    JSON, and flight JSONL must all parse and hold the expected series.
    Runs cli.main in-process (the flag wiring and the exit-time artifact
    writes are the same code path; a subprocess would spend ~20s of suite
    budget re-importing jax for no extra coverage — test_cli.py already
    pins the subprocess surface)."""
    from cake_tpu import cli, obs

    trace_p = tmp_path / "trace.json"
    metrics_p = tmp_path / "metrics.json"
    flight_p = tmp_path / "flight.jsonl"
    obs.registry().reset(prefix="generator.")
    rc = cli.main([
        "--model", str(model_dir), "--prompt-ids", "3,5,7", "-n", "4",
        "--temperature", "0", "--max-seq", "32", "--cpu",
        "--log-level", "debug", "--trace", str(trace_p),
        "--metrics-out", str(metrics_p), "--flight-log", str(flight_p),
    ])
    # the in-process --log-level debug reconfigured root logging; put it
    # back before the rest of the suite runs (jax debug logs are chatty)
    obs.setup_logging("info")
    assert rc == 0

    doc = json.loads(trace_p.read_text())
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert "prefill" in names
    assert names & {"decode.step", "decode.block"}

    snap = json.loads(metrics_p.read_text())
    assert snap["generator.prefill_ms"]["count"] == 1
    assert snap["generator.decode_ms"]["count"] >= 1

    recs = [json.loads(ln) for ln in flight_p.read_text().splitlines()]
    assert recs[0]["kind"] == "prefill"
    assert any(rec["kind"] == "decode" for rec in recs)
    # the exit path stopped the tracer and closed the flight recorder
    assert not trace.tracer().enabled
    assert not flight.recorder().enabled
    trace.tracer().clear()
    flight.recorder().clear()
