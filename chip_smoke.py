#!/usr/bin/env python3
"""chip_smoke.py: the serving path, end to end, on the chip -- or a failure.

    python chip_smoke.py                  one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4        the sharded path only, on four chips
    python chip_smoke.py --rehearse       tiny size, CPU, interpreted kernels
    python chip_smoke.py --chips 4 --rehearse    ... on 4 virtual CPU devices

What it runs, through the entry points a user would call:

1. a child writes a seeded random-weight checkpoint at the published widths
   of Mistral-7B-v0.1 (hidden 4096, FFN 14336, 32 q / 8 kv heads of 128,
   vocab 32000), all 32 layers, pre-quantized int8, streamed layer by
   layer (utils/weights.write_random_q8_checkpoint);
2. ``python -m cake_tpu.cli --mode serve`` as a child loads it through the
   real loader and serves it: 8 slots, 2048-token KV capacity. The parent
   drives ``POST /v1/completions`` (prompt_ids, temperature 0, streamed)
   with tools/loadgen: one warm request per admission bucket, then prompts
   of 64..1500 tokens, two of them arriving while others decode, one sent
   twice. Random weights would now and then pick the EOS id, so the server
   runs with ``--logit-bias <eos>:-100`` and every answer must come back
   whole. SIGTERM; the log must end "drained; bye", exit code 0;
3. after the server has exited (a chip belongs to one process), a second
   child runs the compiled-kernel parity of tools/kernel_check and times
   one warmed decode dispatch to ``block_until_ready`` against a host fetch.

``--chips 4`` runs only this instead of 2-3: the same checkpoint served
with ``--stages 2 --tp 2`` (one process, four devices: the stage ring's
ppermute and the tp psum in one run), every device's ``bytes_in_use``
checked against its share, then -- after that child has exited -- the
one-chip server, same prompts; ids and logprobs are compared.

The parent never imports JAX (or cake_tpu, which does): the device facts
in the last line are what the process that SERVED reported in its status
(``GET /`` -> "device"). Every line printed is one JSON object; the last,
only on success, is ``{"ok": true, "device": {platform, kind, count}}``.
Any phase that fails, a server that answers on the CPU, a kernel that ran
interpreted, a request that errored or came back short: non-zero exit and
no such line. Without ``--rehearse``, a run that finds no TPU fails.
"""

from __future__ import annotations

import argparse
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
import tempfile
import threading
import time
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Full size: what the one-chip AOT memory analysis says fits 16 GiB next to
# 6.87 GiB of int8 weights (tests/test_chip_compile.py; CHANGES.md PR 21):
# 8 slots x 2048 with a bf16 cache is 8.87 GiB of arguments + 2.75 GiB of
# temporaries for the block-decode program; 4096 sits on the limit.
FULL = dict(slots=8, window=2048, dtype="bf16", max_tokens=64,
            long_tokens=96, warm_lens=[250, 1000, 1400],
            prompt_lens=[1500, 64, 700, 200],
            mesh_lens=[64, 300, 1100], mesh_tokens=32)
TINY = dict(slots=4, window=128, dtype="f32", max_tokens=8, long_tokens=60,
            warm_lens=[30, 100], prompt_lens=[60, 40, 100, 24],
            mesh_lens=[16, 40, 90], mesh_tokens=8)

# --chips 4: the largest gap allowed between the two candidates' logprobs
# where the four-chip and one-chip servers first pick different tokens,
# and between the logprobs of the tokens they agree on before that. Both
# run the same int8 weights in bf16; what differs is the order of the
# reductions (tp=2 splits every row-parallel matmul into two partial sums
# joined by a bf16 psum; one chip accumulates in one pass), so logits
# differ by bf16 rounding carried through 32 layers, and with random
# weights the top two logits are often closer than that. Measured on the
# chip (PR 21): 0.051 nats at most over 96 compared tokens, and 0.031
# between the two candidates at the one place the ids parted. Three times
# that is allowed; a wrong shard, a missing psum or a lower precision
# moves logprobs by whole nats.
LOGPROB_TOL = 0.15

# a cold one-chip start took 45 s and a four-chip one 59 s (PR 21); the
# rest of this is for a machine that reads the checkpoint slowly, inside
# the 1200 s the whole run may take
READY_TIMEOUT_S = 700.0


class SmokeFailure(Exception):
    pass


REHEARSAL = "--rehearse" in sys.argv


def emit(**row) -> None:
    """One JSON line. A rehearsal says so on every line: its times are a
    CPU's and belong in no record."""
    print(json.dumps(dict(row, rehearsal=True) if REHEARSAL else row),
          flush=True)


# ---------------------------------------------------------------------------
# children: the only code here that imports cake_tpu (and so JAX)
# ---------------------------------------------------------------------------

def child_write(a) -> int:
    """Write the checkpoint; print the sizes and what was cut."""
    import dataclasses

    from cake_tpu.models.config import mistral_7b, tiny
    from cake_tpu.utils.memory import hbm_budget
    from cake_tpu.utils.weights import write_random_q8_checkpoint

    config = (tiny(model_type="mistral", sliding_window=64)
              if a.rehearse else mistral_7b())
    published_layers = config.num_hidden_layers
    per_layer = (hbm_budget(config, quant="int8")["layers"]
                 / published_layers)  # int8 bytes + scales of one layer
    free = shutil.disk_usage(a.model_dir).free
    layers, cuts = published_layers, []
    while layers > 2 and 1.2 * layers * per_layer > free:
        layers //= 2  # depth only, never a width; stays divisible by 2
    if layers != published_layers:
        cuts.append(f"depth {layers} of {published_layers} layers: "
                    f"{free / 2**30:.1f} GiB free under {a.model_dir}")
        config = dataclasses.replace(config, num_hidden_layers=layers)
    size = TINY if a.rehearse else FULL
    if not a.rehearse:
        cuts.append(
            f"KV capacity {size['window']} of the published 4096 sliding "
            "window: 4096 x 8 slots with a bf16 cache leaves no room "
            "beside the decode program's cache-sized temporaries")
    t0 = time.perf_counter()
    info = write_random_q8_checkpoint(config, a.model_dir, seed=a.seed)
    emit(phase="sizes", model="mistral-7b-v0.1" if not a.rehearse
         else "tiny-mistral (rehearsal)", hidden=config.hidden_size,
         ffn=config.intermediate_size, heads=config.num_attention_heads,
         kv_heads=config.num_key_value_heads, head_dim=config.head_dim,
         vocab=config.vocab_size, sliding_window=config.sliding_window,
         layers_served=layers, layers_published=published_layers,
         quantize="int8", slots=size["slots"], kv_capacity=size["window"],
         seed=a.seed, cuts=cuts)
    emit(phase="checkpoint", write_s=round(time.perf_counter() - t0, 2),
         bytes=info["bytes"], files=info["files"], format="q8 per layer")
    return 0


def child_kernels(a) -> int:
    """Compiled-kernel parity (tools/kernel_check) and the sync check."""
    from cake_tpu.utils.compile_cache import configure

    configure()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cake_tpu.tools.kernel_check import check_kernels

    dev = jax.devices()[0]
    if not a.rehearse and dev.platform != "tpu":
        raise SmokeFailure(f"kernel child runs on {dev.platform!r}, not tpu")
    results, ok = check_kernels(
        dtype=jnp.float32 if a.rehearse else jnp.bfloat16,
        shrink=16 if a.rehearse else 1)  # prints one row per kernel
    compiled = all(r["compiled"] for r in results)
    emit(phase="kernels", rows=len(results), all_within_tol=bool(ok),
         all_compiled=compiled, platform=dev.platform, kind=dev.device_kind)
    if not ok or (not a.rehearse and not compiled):
        raise SmokeFailure("a kernel row is out of tolerance or interpreted")

    # One warmed decode dispatch, timed two ways: around a host fetch and
    # around block_until_ready. On an earlier machine block_until_ready
    # returned before the work was done; if the two agree beside the chip,
    # either is a fair way to time a dispatch.
    from cake_tpu.models.config import mistral_7b, tiny
    from cake_tpu.models.llama import init_params_int8
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.parallel.mesh import (MeshPlan, init_cache_on_mesh,
                                        shard_params)
    from cake_tpu.parallel.pipeline import build_sharded_decode

    size = TINY if a.rehearse else FULL
    config = (tiny(max_seq_len=size["window"]) if a.rehearse else
              mistral_7b(max_seq_len=size["window"], num_hidden_layers=2))
    plan = MeshPlan.build(config, devices=jax.devices()[:1])
    params = shard_params(init_params_int8(config, jax.random.PRNGKey(a.seed)),
                          plan.mesh)
    settings = SamplerSettings(temperature=0.0)
    b, steps = size["slots"], 8
    prog = build_sharded_decode(config, settings, plan, params_like=params,
                                steps=steps, per_row=True)
    cache = init_cache_on_mesh(config, plan.mesh, batch=b,
                               max_seq=size["window"])
    state = [jnp.zeros((b,), jnp.int32), cache,
             jnp.full((b, settings.repeat_last_n), -1, jnp.int32),
             jnp.zeros((b,), jnp.int32)]
    keys = jnp.zeros((b, 2), jnp.uint32)
    pos = 16

    def dispatch():
        nonlocal pos
        toks, state[1], state[2], state[3] = prog(
            params, state[0], state[1], jnp.full((b,), pos, jnp.int32), keys,
            state[2], state[3], jnp.full((b,), pos, jnp.int32))
        state[0] = toks[-1]
        pos += steps
        return toks

    for _ in range(3):
        np.asarray(dispatch())  # compile + warm
    ready, fetch = [], []
    for _ in range(5):
        t0 = time.perf_counter()
        toks = dispatch()
        jax.block_until_ready(toks)
        ready.append(time.perf_counter() - t0)
        t1 = time.perf_counter()
        np.asarray(toks)  # what is left to wait for after "ready"
        left = time.perf_counter() - t1
        t0 = time.perf_counter()
        np.asarray(dispatch())
        fetch.append((time.perf_counter() - t0, left))
    def med_ms(xs):
        return sorted(xs)[len(xs) // 2] * 1e3

    emit(phase="sync_check", platform=dev.platform,
         layers=config.num_hidden_layers, steps=steps,
         block_until_ready_ms=med_ms(ready),
         host_fetch_ms=med_ms([f for f, _ in fetch]),
         fetch_after_ready_ms=med_ms([left for _, left in fetch]),
         note="one 8-step block decode dispatch, median of 5; "
              "block_until_ready is enough when the first two agree and "
              "the third is near zero")
    return 0


# ---------------------------------------------------------------------------
# parent: stdlib only
# ---------------------------------------------------------------------------

def _loadgen():
    """tools/loadgen is stdlib-only, but importing it as a package member
    would import cake_tpu (and JAX): load the file itself."""
    spec = importlib.util.spec_from_file_location(
        "cake_loadgen", ROOT / "cake_tpu" / "tools" / "loadgen.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _child_env(a) -> dict:
    env = dict(os.environ, PYTHONPATH=str(ROOT))
    if a.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        if a.chips > 1:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host"
                                f"_platform_device_count={a.chips}").strip()
    return env


def _run_child(a, role: str, *extra: str) -> None:
    """Run this file in a child role; the child prints its own JSON
    lines on the stdout it inherits."""
    cmd = [sys.executable, str(ROOT / "chip_smoke.py"), "--child", role,
           "--seed", str(a.seed), *(["--rehearse"] if a.rehearse else []),
           *extra]
    rc = subprocess.run(cmd, env=_child_env(a), cwd=ROOT).returncode
    if rc != 0:
        raise SmokeFailure(f"{role} child exited {rc}")


def _get(url: str, timeout: float = 10.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


class Server:
    """One ``python -m cake_tpu.cli --mode serve`` child."""

    def __init__(self, a, model_dir: str, log_path: Path, *flags: str):
        size = TINY if a.rehearse else FULL
        with socket.socket() as s:  # a free port, released for the child
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.url = f"http://127.0.0.1:{self.port}"
        self.log_path = log_path
        eos = json.loads(
            (Path(model_dir) / "config.json").read_text())["eos_token_id"]
        cmd = [
            sys.executable, "-m", "cake_tpu.cli", "--mode", "serve",
            "--model", model_dir, "--quantize", "int8",
            "--logit-bias", f"{eos}:-100",
            "--max-seq", str(size["window"]),
            "--max-concurrent", str(size["slots"]),
            "--dtype", size["dtype"], "--temperature", "0",
            "--serve-port", str(self.port), *flags]
        self.t0 = time.perf_counter()
        self.log = open(log_path, "w")
        self.proc = subprocess.Popen(
            cmd, env=_child_env(a), cwd=ROOT, stdout=self.log,
            stderr=subprocess.STDOUT, start_new_session=True)

    def log_tail(self, n: int = 40) -> str:
        self.log.flush()
        return "\n".join(self.log_path.read_text().splitlines()[-n:])

    def wait_ready(self) -> float:
        deadline = time.monotonic() + READY_TIMEOUT_S
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server exited {self.proc.returncode} before it was "
                    f"ready:\n{self.log_tail()}")
            try:
                if _get(self.url + "/healthz", timeout=2.0).get("ok"):
                    return time.perf_counter() - self.t0
            except (OSError, ValueError):
                pass
            time.sleep(0.5)
        raise SmokeFailure(f"server not ready after {READY_TIMEOUT_S:.0f}s:\n"
                           f"{self.log_tail()}")

    def loaded_s(self) -> float | None:
        """The server's own count, from its main to serving: checkpoint
        load, engine build and its warm admission ("model loaded in")."""
        m = re.search(r"model loaded in ([\d.]+)s", self.log_path.read_text())
        return float(m.group(1)) if m else None

    def compiles(self) -> dict:
        prof = _get(self.url + "/debug/prof")
        return {"compiles": prof["compiles"], "retraces": prof["retraces"]}

    def device(self) -> dict:
        return _get(self.url + "/")["device"]

    def tokens_emitted(self) -> int:
        return _get(self.url + "/")["scheduler"]["engine"]["tokens_emitted"]

    def stop(self) -> None:
        """SIGTERM, and hold the server to a clean drain."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"server ignored SIGTERM:\n{self.log_tail()}")
        tail = self.log_tail(3)
        if rc != 0 or "drained; bye" not in tail:
            raise SmokeFailure(f"server exit {rc}, log ends:\n{tail}")

    def kill(self) -> None:
        """Whatever happened, leave no process behind."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
        self.log.close()


def _prompt(rng: random.Random, n: int, vocab: int) -> list[int]:
    return [rng.randrange(3, vocab) for _ in range(n)]


def _request(lg, srv: Server, name: str, ids: list[int], max_tokens: int,
             logprobs: int = 0) -> dict:
    body = {"prompt_ids": ids, "max_tokens": max_tokens, "stream": True,
            "temperature": 0.0}
    if logprobs:
        body["logprobs"] = logprobs
    t0 = time.perf_counter()
    r = lg._one_request(srv.url, body, timeout=300.0)
    r.update(name=name, prompt_len=len(ids), asked=max_tokens, t0=t0)
    return r


def _report_request(r: dict) -> None:
    """Print one request's line; a request that errored or came back
    short fails the smoke."""
    ok = ("error" not in r and "status" not in r
          and r.get("tokens") == r["asked"]
          and r.get("finish_reason") == "length")
    emit(phase="request", name=r["name"], prompt_len=r["prompt_len"],
         tokens=r.get("tokens"), finish_reason=r.get("finish_reason"),
         ttft_s=r.get("ttft_s"), total_s=r.get("wall_s"), ok=bool(ok),
         **({"error": r.get("error") or r.get("status")} if not ok else {}))
    if not ok:
        raise SmokeFailure(f"request {r['name']} failed or came back short")


def _check_device(a, dev: dict) -> None:
    if a.rehearse:
        return
    if dev["platform"] != "tpu":
        raise SmokeFailure(f"the server answered on {dev['platform']!r} "
                           f"({dev['kind']}), not on a TPU")
    if dev["count"] < a.chips:
        raise SmokeFailure(f"--chips {a.chips} but the server sees "
                           f"{dev['count']} device(s)")


def run_one_chip(a, model_dir: str, work: Path, vocab: int) -> dict:
    size = TINY if a.rehearse else FULL
    lg = _loadgen()
    srv = Server(a, model_dir, work / "serve.log")
    try:
        ready_s = srv.wait_ready()
        dev = srv.device()
        _check_device(a, dev)
        emit(phase="server_ready", ready_s=round(ready_s, 2),
             loaded_s=srv.loaded_s(), **srv.compiles(),
             platform=dev["platform"])
        # warm-up: one request per admission bucket the timed prompts
        # will use (the server itself warms only the 64-token bucket)
        rng = random.Random(a.seed)
        for n in size["warm_lens"]:
            _report_request(_request(lg, srv, f"warm_{n}", _prompt(
                rng, n, vocab), 8))
        warm = srv.compiles()
        emit(phase="warmup", warmup_s=round(time.perf_counter() - srv.t0, 2),
             compile_cache=os.environ.get("JAX_COMPILATION_CACHE_DIR")
             or str(ROOT / ".jax_cache"), **warm)

        # the timed requests: two first, two more once those are running
        prompts = [_prompt(rng, n, vocab) for n in size["prompt_lens"]]
        results: list[dict] = []

        def fire(name, ids, max_tokens):
            results.append(_request(lg, srv, name, ids, max_tokens))

        waves = [[("long", prompts[0], size["long_tokens"]),
                  ("short", prompts[1], size["max_tokens"])],
                 [("late_a", prompts[2], size["max_tokens"]),
                  ("late_b", prompts[3], size["max_tokens"])]]
        threads = []
        emitted = srv.tokens_emitted()
        for i, wave in enumerate(waves):
            if i:  # wait until the first wave has begun to decode
                deadline = time.monotonic() + 120
                while (srv.tokens_emitted() == emitted
                       and time.monotonic() < deadline
                       and any(t.is_alive() for t in threads)):
                    time.sleep(0.01)
            for spec in wave:
                t = threading.Thread(target=fire, args=spec)
                t.start()
                threads.append(t)
        for t in threads:
            t.join(timeout=600)
        if len(results) != 4:
            raise SmokeFailure("a request thread did not come back")
        by = {r["name"]: r for r in results}
        for name in ("long", "short", "late_a", "late_b"):
            _report_request(by[name])
        again = _request(lg, srv, "short_again", prompts[1],
                         size["max_tokens"])
        _report_request(again)
        # arrivals while others decode: a late request started after some
        # first-wave request's first token and before its last
        overlapped = [
            late for late in ("late_a", "late_b") if any(
                by[f]["t0"] + by[f]["ttft_s"] < by[late]["t0"]
                < by[f]["t0"] + by[f]["wall_s"] for f in ("long", "short"))]
        same = again["ids"] == by["short"]["ids"]
        after = srv.compiles()
        dev = srv.device()
        emit(phase="steady", arrived_while_decoding=overlapped,
             repeat_same_ids=same,
             compiles_after_warmup=after["compiles"] - warm["compiles"],
             retraces=after["retraces"])
        emit(phase="device", **dev)
        if len(overlapped) < 2 and not a.rehearse:
            # (the tiny rehearsal decodes a whole answer between two polls)
            raise SmokeFailure("fewer than two requests arrived while "
                               f"others decoded: {overlapped}")
        if not same:
            raise SmokeFailure("the same prompt gave different ids")
        if after["compiles"] != warm["compiles"]:
            raise SmokeFailure("compiled after warm-up: "
                               f"{after['compiles'] - warm['compiles']}")
        srv.stop()
        emit(phase="shutdown", rc=0, drained=True)
    finally:
        srv.kill()
    _run_child(a, "kernels")
    return dev


def _serve_mesh_prompts(a, srv: Server, lg, vocab: int, tag: str) -> list:
    size = TINY if a.rehearse else FULL
    rng = random.Random(a.seed + 1)
    out = []
    for n in size["mesh_lens"]:
        r = _request(lg, srv, f"{tag}_{n}", _prompt(rng, n, vocab),
                     size["mesh_tokens"], logprobs=4)
        _report_request(r)
        out.append(r)
    return out


def run_four_chips(a, model_dir: str, work: Path, vocab: int) -> dict:
    lg = _loadgen()
    runs = {}
    for tag, flags in (("mesh", ["--stages", "2", "--tp", "2"]), ("one", [])):
        srv = Server(a, model_dir, work / f"serve_{tag}.log",
                     "--serve-logprobs", "4", *flags)
        try:
            ready_s = srv.wait_ready()
            dev = srv.device()
            _check_device(a, dev)
            emit(phase="server_ready", server=tag, flags=flags,
                 ready_s=round(ready_s, 2), loaded_s=srv.loaded_s(),
                 **srv.compiles())
            answers = _serve_mesh_prompts(a, srv, lg, vocab, tag)
            dev = srv.device()
            emit(phase="device", server=tag, **dev)
            if tag == "mesh" and not a.rehearse:
                used = [d["bytes_in_use"] for d in dev["devices"][:a.chips]]
                share = sum(used) / len(used)
                if not all(0.5 * share <= u <= 2 * share for u in used):
                    raise SmokeFailure(
                        f"a device holds under half or over twice its "
                        f"share ({share / 2**30:.2f} GiB): "
                        f"{[round(u / 2**30, 2) for u in used]} GiB")
            srv.stop()
            runs[tag] = (answers, dev)
        finally:
            srv.kill()
    worst = 0.0
    for m, o in zip(runs["mesh"][0], runs["one"][0]):
        n = min(len(m["ids"]), len(o["ids"]))
        part = next((i for i in range(n) if m["ids"][i] != o["ids"][i]), None)
        upto = n if part is None else part
        # logprob of the token both chose, position by position
        agree = max((abs(m["logprobs"][i][0]["logprob"]
                         - o["logprobs"][i][0]["logprob"])
                     for i in range(upto)), default=0.0)
        gap = None
        if part is not None:
            # each server's margin between its own pick and the other's
            gaps = []
            for mine, other in ((m, o), (o, m)):
                top = {e["id"]: e["logprob"] for e in mine["logprobs"][part]}
                theirs = top.get(other["ids"][part])
                gaps.append(None if theirs is None
                            else top[mine["ids"][part]] - theirs)
            gap = (max(gaps) if None not in gaps else float("inf"))
        emit(phase="compare", prompt_len=m["prompt_len"], tokens=n,
             ids_part_at=part, logprob_gap_there=gap,
             max_logprob_diff_before=agree, tolerance=LOGPROB_TOL)
        worst = max(worst, agree, gap or 0.0)
    if worst > LOGPROB_TOL:
        raise SmokeFailure(f"four-chip and one-chip answers differ by "
                           f"{worst:.4f} nats > {LOGPROB_TOL}")
    return runs["mesh"][1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4: only the sharded path and its one-chip "
                         "comparison (run by the builder, never the driver)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU, kernels interpreted: "
                         "proves this script's paths, never the chip")
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--child", choices=["write", "kernels"],
                    help=argparse.SUPPRESS)
    ap.add_argument("--model-dir", help=argparse.SUPPRESS)
    a = ap.parse_args()
    if a.child:
        sys.path.insert(0, str(ROOT))
        try:
            return {"write": child_write,
                    "kernels": child_kernels}[a.child](a)
        except SmokeFailure as e:
            sys.stderr.write(f"chip_smoke.py [{a.child}]: FAILED: {e}\n")
            return 1

    if not (ROOT / "cake_tpu" / "cli.py").exists():
        sys.stderr.write("chip_smoke.py: no cake_tpu/ beside this script; "
                         "it drives the program, it is not the program\n")
        return 2
    held = os.environ.get("JAX_PLATFORMS", "")
    if not a.rehearse and held and "tpu" not in held.split(","):
        sys.stderr.write(
            f"chip_smoke.py: JAX_PLATFORMS={held!r} keeps JAX off the TPU; "
            "this run needs the chip (run it through the chip tool, or "
            "pass --rehearse for the tiny CPU rehearsal)\n")
        return 2
    # a SIGTERM (a caller's time limit) unwinds through the finally
    # blocks below, which stop the children; the default would orphan them
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    work = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    try:
        model_dir = str(work / "model")
        os.makedirs(model_dir)
        _run_child(a, "write", "--model-dir", model_dir)
        vocab = json.loads(
            (work / "model" / "config.json").read_text())["vocab_size"]
        run = run_four_chips if a.chips == 4 else run_one_chip
        dev = run(a, model_dir, work, vocab)
    except SmokeFailure as e:
        sys.stderr.write(f"chip_smoke.py: FAILED: {e}\n")
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    if "jax" in sys.modules:  # the parent must never hold the chip
        sys.stderr.write("chip_smoke.py: FAILED: the parent imported JAX\n")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
