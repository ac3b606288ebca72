#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chips this machine holds.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1|2>
    python benchmark/run.py --workload <cell> --sweep 1.5,2,2.5,3   (find a rate)
    python benchmark/run.py --workload <cell> --rehearse            (tiny, CPU)

The parent (this file: standard library and numpy, never JAX) finds the
cell's configuration, its architecture, traffic mix and per-layer readers
BY NAME under ``benchmark/``, writes the configuration's seeded checkpoint
once per checkout (the architecture's writer), starts the real server
(``cake_tpu.cli --mode serve``, through ``serve_child.py``) on the cell's
chips, warms the shapes the mix can draw, probes correctness, measures
for ``--seconds``, SIGTERMs the server and holds it to a clean drain. Its
last line of output is the result (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``): the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics (and ``breakdown``) with ``--trace
1``, both side by side with ``--trace 2``.
A ``--trace 2`` run IS a ``--trace 0`` run up to the moment its window
closes; only then does it ask the server (``POST /debug/trace``, the
program's capture control) to trace a few seconds of the same mix.
``--trace 1`` asks the same control in the middle of the window. A run
that finds no TPU, a server that answered from the CPU or did not
drain: non-zero exit and no result.
README.md in this directory says how to add a cell, a configuration or
a decoder this file has never seen without touching a file that is here.
"""

from __future__ import annotations

import argparse
import hashlib
import importlib.util
import json
import os
import random
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

T_START = time.perf_counter()

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import client  # noqa: E402
import metrics  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402

CACHE = ROOT / ".bench_cache"
READY_TIMEOUT_S = 900.0  # a first run loads 7-15 GB and compiles
PROBE_TOKENS = 16  # two decode blocks after the admission's token
TAIL_LEAD_S = 3.0  # --trace 2: traffic before the capture opens, so that
#                    slots are full again or arrivals are in flight


class BenchFailure(Exception):
    """The run cannot give a result: exit non-zero, print none."""


def say(**row) -> None:
    """An earlier line of output: one JSON object, for whoever reads the
    log. Only the LAST line is the result."""
    print(json.dumps(row), flush=True)


# ---------------------------------------------------------------------------
# the cell's files, by name
# ---------------------------------------------------------------------------

def load_cell(name: str) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchFailure(f"no workload {name!r} in BENCHMARK.json "
                           f"(has {sorted(cells)})")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())

    def here(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return {"cell": cell, "cfg": cfg, "mix": mix,
            "run_seconds": bench["run_seconds"],
            "end_to_end": [m for m in bench["end_to_end"] if here(m)],
            "per_layer": [m for m in bench["per_layer"] if here(m)]}


def overlay(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = overlay(base[k], v) if (
            isinstance(v, dict) and isinstance(base.get(k), dict)) else v
    return out


def _load_by_path(module: str, path: Path):
    spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    """``layer_metrics/<metric name>.py``, loaded by path: its ``read(ctx)``
    returns the metric's value, or None for nothing to read."""
    path = HERE / "layer_metrics" / f"{name}.py"
    if not path.exists():
        return None
    return _load_by_path(f"bench_layer_{name}", path).read


def load_arch(name: str):
    """``arch/<name>.py``, loaded by path: the module that knows one
    decoder's tensors and mathematics (README.md, "Adding an
    architecture", lists what it defines). A configuration file names it
    under ``bench.arch``."""
    path = HERE / "arch" / f"{name}.py"
    if not path.exists():
        has = sorted(p.stem for p in (HERE / "arch").glob("*.py"))
        raise BenchFailure(f"no architecture {name!r}: benchmark/arch/ "
                           f"has {has}")
    return _load_by_path(f"bench_arch_{name}", path)


def peaks_for(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise BenchFailure(f"no published peaks for device kind {kind!r} in "
                           "benchmark/peaks.json; add them with their source")
    return table[kind]


# ---------------------------------------------------------------------------
# checkpoint, once per checkout
# ---------------------------------------------------------------------------

def checkpoint_key(cfg: dict, arch) -> str:
    """What a checkpoint's directory is named by: the model's own sizes,
    how the weights are made, and the architecture's writer."""
    sizes = json.dumps(weights.hf_config(cfg, arch.HF_KEYS), sort_keys=True)
    return hashlib.sha256(
        f"{sizes}|{cfg['bench']['weights']}|{arch.WRITER_VERSION}"
        .encode()).hexdigest()[:12]


def ensure_checkpoint(cfg: dict, arch, tag: str,
                      cache: Path) -> tuple[Path, float]:
    """The configuration's seeded checkpoint: found, or written now by
    its architecture's writer. Returns (directory, seconds spent
    writing)."""
    w = cfg["bench"]["weights"]
    model_dir = cache / "ckpt" / f"{tag}-{checkpoint_key(cfg, arch)}"
    if (model_dir / "DONE").exists():
        return model_dir, 0.0
    need = arch.checkpoint_bytes(cfg, w["layout"])
    (cache / "ckpt").mkdir(parents=True, exist_ok=True)
    shutil.rmtree(model_dir, ignore_errors=True)
    if shutil.disk_usage(cache).free < 1.2 * need:
        # other configurations' checkpoints can be written again
        for other in (cache / "ckpt").iterdir():
            shutil.rmtree(other, ignore_errors=True)
    t0 = time.perf_counter()
    info = arch.write_checkpoint(cfg, w["layout"], w["seed"], model_dir)
    (model_dir / "DONE").write_text(json.dumps(info))
    took = time.perf_counter() - t0
    say(phase="checkpoint_written", dir=str(model_dir.relative_to(ROOT)),
        seconds=took, **info)
    return model_dir, took


# ---------------------------------------------------------------------------
# the server child
# ---------------------------------------------------------------------------

class Server:
    def __init__(self, cfg: dict, model_dir: Path, run_dir: Path,
                 chips: int, rehearse: bool, every_step: bool):
        b = cfg["bench"]
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.url = f"http://127.0.0.1:{self.port}"
        self.log_path = run_dir / "server.log"
        self.captures: list[str] = []  # directories the control named
        fill = dict(b, eos=cfg["eos_token_id"])
        args = [str(a).format(**fill) for a in b["server_args"]]
        if every_step:
            # --trace 1: every engine step of the window stamps its
            # phases (a --trace 2 run starts like --trace 0; its capture
            # stamps every step while it is open)
            args += ["--prof-sample", "1"]
        cmd = [sys.executable, str(HERE / "serve_child.py"),
               "--mode", "serve", "--model", str(model_dir),
               "--serve-port", str(self.port), *args]
        env = dict(os.environ, PYTHONPATH=str(ROOT))
        # the compile cache: a fixed directory inside this checkout, and
        # every program in it, however quickly it compiled
        env["JAX_COMPILATION_CACHE_DIR"] = str(
            CACHE / ("jax_cache_rehearsal" if rehearse else "jax_cache"))
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
        env.pop("BENCH_RUN", None)
        if rehearse:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host"
                                f"_platform_device_count={chips}").strip()
        self.log = open(self.log_path, "w")
        self.proc = subprocess.Popen(cmd, env=env, cwd=ROOT, stdout=self.log,
                                     stderr=subprocess.STDOUT,
                                     start_new_session=True)

    def log_tail(self, n: int = 30) -> str:
        self.log.flush()
        return "\n".join(self.log_path.read_text().splitlines()[-n:])

    def wait_ready(self) -> None:
        deadline = time.monotonic() + READY_TIMEOUT_S
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise BenchFailure(f"server exited {self.proc.returncode} "
                                   f"before it was ready:\n{self.log_tail()}")
            try:
                if client.get_json(self.url + "/healthz", 2.0).get("ok"):
                    return
            except (OSError, ValueError):
                pass
            time.sleep(0.25)
        raise BenchFailure(f"server not ready after {READY_TIMEOUT_S:.0f} s:"
                           f"\n{self.log_tail()}")

    def loaded_s(self) -> float | None:
        m = re.search(r"model loaded in ([\d.]+)s", self.log_path.read_text())
        return float(m.group(1)) if m else None

    def status(self) -> dict:
        return client.get_json(self.url + "/", 30.0)

    def prof(self) -> dict:
        return client.get_json(self.url + "/debug/prof", 30.0)

    def snapshot(self) -> dict:
        """The server's counters at this instant: what a reader's
        ``before`` and ``after`` hold."""
        return {"status": self.status(), "prof": self.prof()}

    def capture(self, action: str) -> dict:
        """Ask the program's capture control (``POST /debug/trace``) to
        ``start`` or ``stop``: the answer carries the directory and the
        server's clocks at that instant (``unix_ns``, ``perf_s``)."""
        req = urllib.request.Request(
            self.url + "/debug/trace",
            data=json.dumps({"action": action}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=280.0) as r:
                out = json.loads(r.read())
        except (OSError, ValueError) as e:
            detail = e.read().decode(errors="replace")[:300] if isinstance(
                e, urllib.error.HTTPError) else ""
            raise BenchFailure(f"capture {action} failed: {e} {detail}")
        if out["dir"] not in self.captures:
            self.captures.append(out["dir"])
        return out

    def drop_captures(self) -> None:
        """Delete every trace the control wrote (they are large)."""
        for d in self.captures:
            shutil.rmtree(d, ignore_errors=True)
        self.captures.clear()

    def idle(self, timeout_s: float) -> bool:
        """Wait until nothing is queued or running."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            h = client.get_json(self.url + "/healthz", 5.0)
            if not h.get("queued") and not h.get("running"):
                return True
            time.sleep(0.1)
        return False

    def stop(self) -> None:
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise BenchFailure(f"server ignored SIGTERM:\n{self.log_tail()}")
        tail = self.log_tail(3)
        if rc != 0 or "drained; bye" not in tail:
            raise BenchFailure(f"server exit {rc}, log ends:\n{tail}")

    def kill(self) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
        self.log.close()


def check_device(dev: dict, chips: int, rehearse: bool) -> None:
    if rehearse:
        return
    if dev["platform"] != "tpu":
        raise BenchFailure(f"the server answered on {dev['platform']!r} "
                           f"({dev['kind']}), not on a TPU")
    if dev["count"] < chips:
        raise BenchFailure(f"the cell needs {chips} chip(s); the server "
                           f"sees {dev['count']}")


# ---------------------------------------------------------------------------
# warm-up and the correctness probe
# ---------------------------------------------------------------------------

def must(rec: dict, what: str) -> dict:
    if not rec["ok"]:
        raise BenchFailure(f"{what} failed or came back short: "
                           f"{rec['error'] or rec['finish_reason']} "
                           f"({len(rec['ids'])}/{rec['asked']} tokens)")
    return rec


def warm_up(srv: Server, schedule, cfg: dict, vocab: int) -> None:
    """One request per admission bucket the mix can draw, then as many
    at once as there are slots: every program the window will use."""
    b = cfg["bench"]
    rng = random.Random("warm")
    buckets = schedule.admission_buckets(b["kv_capacity"])
    block = 2 * b.get("decode_block", 8)
    for n in buckets:
        must(client.one_request(srv.url, traffic.tokens(rng, n, vocab),
                                block), f"warm-up ({n} tokens)")
    recs: list[dict] = []
    threads = [threading.Thread(target=lambda: recs.append(
        client.one_request(srv.url, traffic.tokens(rng, buckets[0], vocab),
                           block))) for _ in range(b["slots"])]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=300)
    if len(recs) != b["slots"]:
        raise BenchFailure("a warm-up request did not come back")
    for r in recs:
        must(r, "warm-up (all slots)")


def probe(srv: Server, cfg: dict, vocab: int) -> list[dict]:
    """The probe prompts (the same for every run seed: they come from the
    weights' seed), served by the program the window runs: no logprobs
    are asked for, because a server that offers them (``--serve-logprobs``)
    decodes by another program. The first prompt is sent twice."""
    b = cfg["bench"]
    rng = random.Random(f"probe/{b['weights']['seed']}")
    out = []
    for n in b["probe_lens"]:
        ids = traffic.tokens(rng, n, vocab)
        r = must(client.one_request(srv.url, ids, PROBE_TOKENS),
                 f"probe ({n} tokens)")
        out.append({"prompt": ids, "ids": r["ids"]})
    again = must(client.one_request(srv.url, out[0]["prompt"], PROBE_TOKENS),
                 "probe (repeat)")
    out[0]["repeat_same"] = again["ids"] == out[0]["ids"]
    return out


def check_reference(probes: list[dict], cfg: dict, arch, tag: str,
                    model_dir: Path, cache: Path) -> tuple[bool, float]:
    """Hold the tokens the server chose to the architecture's float32
    reference, teacher-forced on them: at every place the reference's own
    best token may lie above the server's choice by at most
    ``bench.margin_tol`` nats (0 where they agree). The reference's
    answer is kept in the checkout, keyed by configuration, weights and
    ids: only a first run or a changed program computes it."""
    tol, worst = cfg["bench"]["margin_tol"], 0.0
    pairs = [[p["prompt"], p["ids"]] for p in probes]
    key = hashlib.sha256(json.dumps([
        tag, model_dir.name, arch.REFERENCE_VERSION, pairs]).encode()
    ).hexdigest()
    path = cache / "reference" / f"{key[:20]}.json"
    if path.exists():
        refs = json.loads(path.read_text())
    else:
        t0 = time.perf_counter()
        refs = arch.chosen_logprobs(cfg, model_dir, pairs)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(refs))
        say(phase="reference_computed", tokens=[len(p) for p, _ in pairs],
            seconds=time.perf_counter() - t0)
    for p, ref in zip(probes, refs):
        # where the reference prefers another token, by how much
        margin = [bl - l for bl, l in zip(ref["best_logprob"], ref["logprob"])]
        worst = max(worst, *margin)
        say(phase="probe", prompt_len=len(p["prompt"]),
            agrees=sum(a == b for a, b in zip(p["ids"], ref["best"])),
            of=len(p["ids"]), margin_to_reference_best=margin,
            chosen_logprob=ref["logprob"],
            routing_margin=ref["routing_margin"], tolerance=tol,
            repeat_same=p.get("repeat_same"))
    same = all(p.get("repeat_same", True) for p in probes)
    return (worst <= tol and same), worst


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------

def measure(srv: Server, schedule, mix: dict, seconds: float,
            capture: tuple[float, float] | None = None,
            around_capture: bool = False) -> dict:
    """Open a window: run the mix and poll the queue. With ``capture``
    (seconds after the window opens at which to start and to stop), ask
    the server for a trace meanwhile. ``before`` and ``after`` hold the
    server's counters on either side of the window or, with
    ``around_capture``, on either side of the capture (where every
    engine step stamps its phases)."""
    got: dict = {}

    def run_capture(t0: float) -> None:
        try:
            time.sleep(max(0.0, t0 + capture[0] - time.perf_counter()))
            if around_capture:
                got["before"] = srv.snapshot()
            t_ask = time.perf_counter()
            got["started"] = srv.capture("start")
            got["start_took_s"] = time.perf_counter() - t_ask
            time.sleep(max(0.0, t0 + capture[1] - time.perf_counter()))
            t_ask = time.perf_counter()
            got["stopped"] = srv.capture("stop")
            got["stop_took_s"] = time.perf_counter() - t_ask
            if around_capture:
                got["after"] = srv.snapshot()
        except (BenchFailure, OSError, ValueError) as e:
            got["error"] = e

    before = srv.snapshot()
    if capture:
        th = threading.Thread(target=run_capture, daemon=True,
                              args=(time.perf_counter(),))
        th.start()
    with client.Poller(srv.url) as poll:
        if schedule.open:
            records, window = client.run_open(
                srv.url, schedule, seconds, mix["drain_limit_s"])
        else:
            records, window = client.run_closed(
                srv.url, schedule, schedule.clients, seconds,
                mix["drain_limit_s"])
    trace_span = None
    if capture:
        # the profiler collects the device's trace as it stops, which
        # can outlast the drain: ask the server nothing meanwhile
        th.join(timeout=300)
        if "error" in got or "stopped" not in got:
            raise BenchFailure("the trace was not written: "
                               f"{got.get('error', 'the capture hangs')}")
        trace_span = [got["started"], got["stopped"]]
    srv.idle(mix["drain_limit_s"])
    after = srv.snapshot()
    return {"records": records, "window": window, "poll": poll.samples,
            "before": got.get("before", before),
            "after": got.get("after", after), "trace_span": trace_span,
            "capture_took_s": [got.get("start_took_s"),
                               got.get("stop_took_s")]}


def trace_tail(srv: Server, a, mix: dict, cfg: dict, vocab: int) -> dict:
    """``--trace 2``, after the measured window has closed and drained:
    start and stop the profiler once and throw that trace away (the
    cost of the first start falls into no number), then run a tail of
    the same mix -- a fresh schedule of the same seed -- and trace
    ``trace_s`` of it after a lead-in. Returns what ``measure`` returns
    for a traced window, counters taken around the capture."""
    srv.capture("start")
    srv.capture("stop")
    srv.drop_captures()
    span = min(mix.get("trace_s", 4.0), a.seconds / 2)
    lead = min(TAIL_LEAD_S, a.seconds / 2)
    seconds = lead + span + 0.5
    schedule = traffic.Schedule(mix, a.seed, seconds, vocab,
                                cfg["bench"]["slots"])
    return measure(srv, schedule, mix, seconds, (lead, lead + span),
                   around_capture=True)


def reduce_trace(trace_dir: str, run_dir: Path) -> dict | None:
    """After the server has exited: a child of its own reads the trace
    (it imports JAX, held to the CPU) and writes the reduction."""
    out = run_dir / "trace_reduced.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    rc = subprocess.run([sys.executable, str(HERE / "trace_reduce.py"),
                         trace_dir, str(out)], env=env, cwd=ROOT).returncode
    if rc != 0:
        raise BenchFailure(f"trace_reduce.py exited {rc}")
    return json.loads(out.read_text())


def context_for(metric: dict, window: dict, tail: dict | None) -> dict:
    """Which context a per-layer reader gets, by the metric's ``source``.
    In a ``--trace 2`` run (``tail`` given) a ``device_trace`` or
    ``program_span`` metric reads the traced tail (the device trace, and
    counters taken around the capture, where every step is stamped); a
    ``host_clock`` or ``program_counter`` metric reads the measured
    window (all its records and counter deltas, taken with no profiler
    open). A ``--trace 1`` run has the one context."""
    if tail is not None and metric["source"] in ("device_trace",
                                                 "program_span"):
        return tail
    return window


def breakdown(reduced: dict, m: dict) -> dict:
    """The device operations that took most time and the longest idle
    gaps, of the device that was idle most. A gap is named by the host
    runtime span that covers most of it, or "no request" where the
    parent's poll of the server shows nothing queued and nothing running
    (trace times count from when the trace opened, which the child
    stamped on the parent's clock)."""
    dev = min(reduced["devices"], key=lambda d: d["busy_s"])
    opened = m["trace_span"][0]["perf_s"]

    def label(gap: dict) -> str:
        mid = opened + (gap["start_ns"] / 1e9 + gap["seconds"] / 2)
        near = min(m["poll"], key=lambda p: abs(p[0] - mid), default=None)
        if near and abs(near[0] - mid) < 0.5 and not near[1] and not near[2]:
            return "no request (queue and slots empty)"
        return gap["host"]

    return {"device_ops": [[n, s] for n, s, _ in dev["ops"][:10]],
            "idle_gaps": [[label(g), g["seconds"]] for g in dev["gaps"][:10]]}


# ---------------------------------------------------------------------------

def run_cell(a, cell: dict) -> dict:
    cfg, mix, chips = cell["cfg"], cell["mix"], cell["cell"]["chips"]
    if a.rehearse:
        cfg = overlay(cfg, cfg["bench"]["rehearsal"])
        mix = overlay(mix, mix.get("rehearsal", {}))
    cache = CACHE / "rehearsal" if a.rehearse else CACHE
    tag = cell["cell"]["config"]
    arch = load_arch(cfg["bench"].get("arch"))
    vocab = cfg["vocab_size"]
    run_dir = cache / "runs" / f"{cell['cell']['name']}-t{a.trace}"
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    model_dir, wrote_s = ensure_checkpoint(cfg, arch, tag, cache)
    schedule = traffic.Schedule(mix, a.seed, a.seconds, vocab,
                                cfg["bench"]["slots"])
    srv = Server(cfg, model_dir, run_dir, chips, a.rehearse,
                 every_step=a.trace == 1)
    try:
        srv.wait_ready()
        dev = srv.status()["device"]
        check_device(dev, chips, a.rehearse)
        say(phase="ready", seconds=time.perf_counter() - T_START,
            loaded_s=srv.loaded_s(), compiles=srv.prof()["compiles"],
            platform=dev["platform"], kind=dev["kind"], count=dev["count"])
        warm_up(srv, schedule, cfg, vocab)
        probes = probe(srv, cfg, vocab)
        srv.idle(30.0)
        if a.sweep:
            return sweep(a, srv, mix, cfg, vocab)
        setup_s = time.perf_counter() - T_START
        mid = None
        if a.trace == 1:  # the profiler open in the middle of the window
            span = min(mix.get("trace_s", 4.0), a.seconds / 2)
            mid = ((a.seconds - span) / 2, (a.seconds + span) / 2)
        m = measure(srv, schedule, mix, a.seconds, mid)
        # --trace 2: the window is closed and every end-to-end number is
        # taken; only now is anything of the profiler started
        tail = trace_tail(srv, a, mix, cfg, vocab) if a.trace == 2 else None
        loaded_s = srv.loaded_s()
        srv.stop()  # the server has exited: the chip is free
        traced = tail or (m if a.trace else None)
        reduced = reduce_trace(traced["trace_span"][0]["dir"],
                               run_dir) if traced else None
    finally:
        srv.kill()
        srv.drop_captures()
    ref_ok, worst = check_reference(probes, cfg, arch, tag, model_dir,
                                    cache)

    records = m["records"]
    whole = all(len(r["ids"]) == r["asked"] for r in records
                if not r.get("error"))
    dev = m["after"]["status"]["device"]
    late = metrics.lateness_ms(records)
    gaps = metrics.gaps_ms(records, m["window"])
    say(phase="window", attempted=len(records),
        tokens_in_window=metrics.tokens_in_window(records, m["window"]),
        generator_late_median_ms=late["median_ms"],
        generator_late_worst_ms=late["worst_ms"],
        ttft_samples_in_tail10=metrics.samples_beyond(len(records), 90),
        gap_ms={**{f"p{q}": metrics.percentile(gaps, q)
                   for q in (50, 90, 95, 99, 99.5, 99.9)},
                "tail1": metrics.tail_mean(gaps, 0.01),
                "tail05": metrics.tail_mean(gaps, 0.005), "n": len(gaps)},
        ttft_ms={**{f"p{q}": metrics.ttft_percentile_ms(records, q)
                    for q in (50, 90, 95)},
                 "tail10": metrics.ttft_tail10_ms(records),
                 "mean": metrics.ttft_mean_ms(records)},
        compiles_in_window=(m["after"]["prof"]["compiles"]
                            - m["before"]["prof"]["compiles"]),
        checkpoint_written_s=wrote_s, worst_margin=worst)

    shared = dict(cfg=cfg, arch=arch, mix=mix, chips=chips,
                  open_loop=schedule.open, loaded_s=loaded_s, setup_s=setup_s,
                  peaks=None if a.rehearse else peaks_for(dev["kind"]))
    ctx = dict(m, trace=reduced if a.trace == 1 else None, **shared)
    tail_ctx = dict(tail, trace=reduced, **shared) if tail else None
    if tail:
        t0, t1 = (c["perf_s"] for c in tail["trace_span"])
        say(phase="tail", attempted=len(tail["records"]),
            failed=sum(not r["ok"] for r in tail["records"]),
            traced_s=t1 - t0,
            tokens_per_s_traced=metrics.tokens_per_s(tail["records"],
                                                     (t0, t1)),
            capture=tail["trace_span"][1],
            capture_took_s=tail["capture_took_s"])
        dev = tail["after"]["status"]["device"]  # the whole run's peak
    values = {}
    if a.trace != 1:  # a --trace 1 window ran under the profiler
        e2e = dict(metrics.end_to_end(records, m["window"]), setup_s=setup_s)
        values.update((mt["name"], e2e.get(mt["name"]))
                      for mt in cell["end_to_end"])
    if a.trace:
        for mt in cell["per_layer"]:
            read = load_reader(mt["name"])
            if read is None:
                raise BenchFailure(f"no reader benchmark/layer_metrics/"
                                   f"{mt['name']}.py")
            values[mt["name"]] = read(context_for(mt, ctx, tail_ctx))
    units = {mt["name"]: mt["unit"]
             for mt in cell["end_to_end"] + cell["per_layer"]}
    result = {
        "correct": bool(ref_ok and whole),
        "attempted": len(records),
        "failed": sum(not r["ok"] for r in records),
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items() if v is not None},
        "device": {"platform": dev["platform"], "kind": dev["kind"],
                   "count": dev["count"], "memory_peak_bytes": max(
                       (d["peak_bytes_in_use"] or 0) for d in dev["devices"])},
    }
    if reduced and reduced["devices"]:
        busy = [d["busy_s"] for d in reduced["devices"][:chips]]
        result["device"]["busy_s"] = sum(busy) / len(busy)
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = breakdown(reduced, traced)
    if a.rehearse:
        # a rehearsal proves the paths; its times are a CPU's and are
        # never printed under a metric's name
        result["rehearsal"] = True
        result["would_report"] = sorted(result.pop("metrics"))
        result["metrics"] = {}
    return result


def sweep(a, srv: Server, mix: dict, cfg: dict, vocab: int) -> dict:
    """Load once, offer a few rates one after another, print a row each.
    The knee is the highest rate at which the queue is no deeper at the
    end of the window than a third of the way in, and nothing failed."""
    for rate in a.sweep:
        m2 = dict(mix, rate_rps=rate)
        sched = traffic.Schedule(m2, a.seed, a.seconds, vocab,
                                 cfg["bench"]["slots"])
        m = measure(srv, sched, m2, a.seconds)
        t0, t1 = m["window"]
        third = [q for t, q, _ in m["poll"] if t0 + (t1 - t0) * 0.30 <= t
                 < t0 + (t1 - t0) * 0.40]
        end = [q for t, q, _ in m["poll"] if t1 - (t1 - t0) * 0.10 <= t < t1]
        recs = m["records"]
        say(phase="sweep", rate_rps=rate, attempted=len(recs),
            failed=sum(not r["ok"] for r in recs),
            queue_mean_at_third=sum(third) / max(1, len(third)),
            queue_mean_at_end=sum(end) / max(1, len(end)),
            queue_max=max((q for _, q, _ in m["poll"]), default=0),
            running_mean=sum(r for _, _, r in m["poll"])
            / max(1, len(m["poll"])),
            ttft_p90_ms=metrics.ttft_percentile_ms(recs, 90),
            generator_late_worst_ms=metrics.lateness_ms(recs)["worst_ms"],
            **metrics.end_to_end(recs, m["window"]))
        srv.idle(120.0)
    srv.stop()
    return {}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=[0, 1, 2], default=0,
                    help="1: per-layer metrics from a window traced in its "
                    "middle; 2: a --trace 0 run, then a traced tail: both "
                    "kinds of metric in one line")
    ap.add_argument("--sweep", type=lambda s: [float(x) for x in s.split(",")],
                    default=None, help="rates (requests/s) to offer one "
                    "after another, to find an open-loop cell's rate")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU: proves the paths; its last "
                    "line names the metrics and carries no value")
    a = ap.parse_args(argv)
    if not (ROOT / "cake_tpu" / "cli.py").exists():
        sys.stderr.write("benchmark/run.py: no cake_tpu/ in this checkout: "
                         "the benchmark drives the program, it is not it\n")
        return 2
    held = os.environ.get("JAX_PLATFORMS", "")
    if not a.rehearse and held and "tpu" not in held.split(","):
        sys.stderr.write(f"benchmark/run.py: JAX_PLATFORMS={held!r} keeps "
                         "JAX off the TPU; a cell runs on the chip (or pass "
                         "--rehearse)\n")
        return 2
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        cell = load_cell(a.workload)
        if a.seconds is None:
            a.seconds = 4.0 if a.rehearse else float(cell["run_seconds"])
        result = run_cell(a, cell)
    except BenchFailure as e:
        sys.stderr.write(f"benchmark/run.py: FAILED: {e}\n")
        return 1
    if "jax" in sys.modules:
        sys.stderr.write("benchmark/run.py: FAILED: the parent imported JAX\n")
        return 1
    if result:
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
