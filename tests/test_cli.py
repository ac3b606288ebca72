"""CLI surface: flag parity with the reference + end-to-end subprocess runs."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from cake_tpu.cli import build_parser
from cake_tpu.models import llama
from cake_tpu.models.config import tiny
from cake_tpu.utils.weights import save_llama_params

CFG = tiny()
REPO = Path(__file__).resolve().parents[1]


def test_defaults_match_reference():
    """Flag defaults mirror cake-core/src/lib.rs:15-64."""
    args = build_parser().parse_args(["--model", "x"])
    assert args.seed == 299792458
    assert args.sample_len == 100
    assert args.temperature == 1.0
    assert args.repeat_penalty == 1.1
    assert args.repeat_last_n == 128
    assert args.address == "127.0.0.1:10128"
    assert args.mode == "master"
    assert args.top_k is None and args.top_p is None


def test_short_n_flag():
    args = build_parser().parse_args(["--model", "x", "-n", "7"])
    assert args.sample_len == 7


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("climodel")
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype="float32")
    save_llama_params(params, d)
    (d / "config.json").write_text(json.dumps(CFG.to_hf_dict()))
    return d


def _run_cli(argv, timeout=240, devices=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO)
    env["JAX_PLATFORMS"] = "cpu"
    if devices:
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={devices}"
        ).strip()
    return subprocess.run(
        [sys.executable, "-m", "cake_tpu.cli"] + argv,
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


def test_local_generation_subprocess(model_dir):
    r = _run_cli([
        "--model", str(model_dir), "--prompt-ids", "3,5,7",
        "-n", "4", "--temperature", "0", "--max-seq", "32", "--cpu",
    ])
    assert r.returncode == 0, r.stderr
    assert "tok/s" in r.stderr


def test_mesh_pipeline_generation_subprocess(model_dir):
    """--stages/--tp drive the single-program mesh pipeline end-to-end."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    r = subprocess.run(
        [sys.executable, "-m", "cake_tpu.cli", "--model", str(model_dir),
         "--prompt-ids", "3,5,7", "-n", "4", "--temperature", "0",
         "--max-seq", "32", "--cpu", "--stages", "2", "--tp", "2"],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr
    assert "tok/s" in r.stderr


def test_device_ordinal_selection(model_dir):
    """--device N pins jax_default_device (reference --device, lib.rs:17-19)."""
    r = _run_cli([
        "--model", str(model_dir), "--prompt-ids", "3,5", "-n", "2",
        "--temperature", "0", "--max-seq", "32", "--cpu", "--device", "0",
    ])
    assert r.returncode == 0, r.stderr
    r = _run_cli([
        "--model", str(model_dir), "--prompt-ids", "3,5", "-n", "2",
        "--cpu", "--device", "99",
    ])
    assert r.returncode != 0
    assert "out of range" in r.stderr


def test_mesh_and_host_topology_flags_conflict(model_dir, tmp_path):
    topo = tmp_path / "t.yml"
    topo.write_text("w1:\n  host: 127.0.0.1:10128\n  layers:\n"
                    "    - model.layers.0-1\n")
    r = _run_cli([
        "--model", str(model_dir), "--prompt-ids", "1", "-n", "1",
        "--stages", "2", "--topology", str(topo),
    ])
    assert r.returncode != 0
    assert "mutually exclusive" in r.stderr


def test_device_topology_drives_mesh_path(model_dir, tmp_path):
    """A topology whose nodes carry `device:` indices selects the
    single-program mesh pipeline from YAML (the reference's one-config-plane
    contract, topology.rs:41-84) — no --stages flag needed."""
    topo = tmp_path / "mesh.yml"
    topo.write_text(
        "s0:\n  device: 0\n  layers:\n    - model.layers.0-1\n"
        "s1:\n  device: 1\n  layers:\n    - model.layers.2-3\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    r = subprocess.run(
        [sys.executable, "-m", "cake_tpu.cli", "--model", str(model_dir),
         "--prompt-ids", "3,5,7", "-n", "4", "--temperature", "0",
         "--max-seq", "32", "--cpu", "--topology", str(topo)],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr
    assert "mesh plan from topology: 2 stages" in r.stderr
    assert "tok/s" in r.stderr


def test_mixed_host_device_topology_rejected(model_dir, tmp_path):
    """Half-migrated YAML (some nodes device-indexed, some host-addressed)
    must fail loudly, not silently drop the host workers."""
    topo = tmp_path / "mixed.yml"
    topo.write_text(
        "s0:\n  device: 0\n  layers:\n    - model.layers.0-1\n"
        "w1:\n  host: 127.0.0.1:10128\n  layers:\n    - model.layers.2-3\n"
    )
    r = _run_cli([
        "--model", str(model_dir), "--prompt-ids", "1", "-n", "1",
        "--topology", str(topo),
    ])
    assert r.returncode != 0
    assert "mixes mesh nodes" in r.stderr


def test_device_topology_conflicts_with_stages(model_dir, tmp_path):
    topo = tmp_path / "mesh.yml"
    topo.write_text(
        "s0:\n  device: 0\n  layers:\n    - model.layers.0-1\n"
        "s1:\n  device: 1\n  layers:\n    - model.layers.2-3\n"
    )
    r = _run_cli([
        "--model", str(model_dir), "--prompt-ids", "1", "-n", "1",
        "--stages", "2", "--topology", str(topo),
    ])
    assert r.returncode != 0
    assert "--stages conflicts" in r.stderr


def test_prompts_file_serves_batch(model_dir, tmp_path):
    """--prompts-file decodes N prompts concurrently over the batched mesh
    pipeline and prints one output line per stream."""
    pf = tmp_path / "prompts.txt"
    pf.write_text("3,5,7\n2,4\n9,1,6,2\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    r = subprocess.run(
        [sys.executable, "-m", "cake_tpu.cli", "--model", str(model_dir),
         "--prompts-file", str(pf), "--prompts-ids", "-n", "4",
         "--temperature", "0",
         "--max-seq", "32", "--cpu", "--dp", "2", "--stages", "2", "-v"],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr
    lines = [l for l in r.stdout.splitlines() if l.startswith("[")]
    assert len(lines) == 3 and lines[0].startswith("[0] ")
    assert "3 streams" in r.stderr and "aggregate" in r.stderr


def test_prompts_file_numeric_text_needs_explicit_mode(model_dir, tmp_path):
    """A numeric-looking line is NEVER silently id-parsed: without
    --prompts-ids it is a text prompt (and errors without a tokenizer);
    serving also rejects flags it would silently ignore
    (--prefill-chunks)."""
    pf = tmp_path / "prompts.txt"
    pf.write_text("1, 2, 3\n")
    r = _run_cli(["--model", str(model_dir), "--prompts-file", str(pf),
                  "-n", "2", "--cpu"])
    assert r.returncode != 0
    assert "tokenizer" in r.stderr
    # (--sp composes with serving since r4 — covered by
    # test_prompts_file_serves_over_sp_window)
    r = _run_cli(["--model", str(model_dir), "--prompts-file", str(pf),
                  "--prompts-ids", "-n", "2", "--cpu",
                  "--prefill-chunks", "2"])
    assert r.returncode != 0 and "--prefill-chunks" in r.stderr
    pf.write_text("hello world\n")
    r = _run_cli(["--model", str(model_dir), "--prompts-file", str(pf),
                  "--prompts-ids", "-n", "2", "--cpu"])
    assert r.returncode != 0
    assert "not a comma-separated id list" in r.stderr


def test_speculate_flag_runs_and_guards(model_dir):
    """--speculate K drives the n-gram speculative generator end-to-end —
    greedy AND sampled (r4: rejection sampling makes temperature > 0
    legal) — and still rejects paths that would ignore it."""
    r = _run_cli([
        "--model", str(model_dir), "--prompt-ids", "3,5,7,3,5,7",
        "-n", "8", "--temperature", "0", "--max-seq", "64", "--cpu",
        "--speculate", "4",
    ])
    assert r.returncode == 0, r.stderr
    assert any(l and all(c.isdigit() or c == "," for c in l)
               for l in r.stdout.splitlines())
    r = _run_cli([
        "--model", str(model_dir), "--prompt-ids", "3,5,7", "-n", "2",
        "--cpu", "--speculate", "4",  # default temperature 1.0: rejection
    ])                                # sampling path — runs fine now
    assert r.returncode == 0, r.stderr
    r = _run_cli([
        "--model", str(model_dir), "--prompt-ids", "3,5,7", "-n", "2",
        "--temperature", "0", "--cpu", "--speculate", "4", "--sp", "2",
    ])
    assert r.returncode != 0 and "--speculate" in r.stderr


def test_speculate_runs_on_mesh_pipeline(model_dir):
    """--speculate composes with --stages/--tp: the verification pass runs
    as one program over the mesh and the token stream matches the plain
    mesh run."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    argv = ["--model", str(model_dir), "--prompt-ids", "3,5,7,3,5,7",
            "-n", "8", "--temperature", "0", "--max-seq", "64", "--cpu",
            "--stages", "2", "--tp", "2"]
    plain = subprocess.run(
        [sys.executable, "-m", "cake_tpu.cli"] + argv,
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    spec = subprocess.run(
        [sys.executable, "-m", "cake_tpu.cli"] + argv + ["--speculate", "4"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert plain.returncode == 0, plain.stderr
    assert spec.returncode == 0, spec.stderr

    def toks(out):
        return [l for l in out.splitlines()
                if l and all(c.isdigit() or c == "," for c in l)][-1]

    assert toks(spec.stdout) == toks(plain.stdout)


def test_profile_flag_writes_trace(model_dir, tmp_path):
    trace_dir = tmp_path / "trace"
    r = _run_cli([
        "--model", str(model_dir), "--prompt-ids", "3,5", "-n", "3",
        "--temperature", "0", "--max-seq", "32", "--cpu",
        "--profile", str(trace_dir),
    ])
    assert r.returncode == 0, r.stderr
    assert trace_dir.exists() and any(trace_dir.rglob("*.xplane.pb"))
    # opened through the capture control (obs/prof): the run's own spans
    # lie beside the profile, the phases of every step among them
    spans = json.loads((trace_dir / "spans.trace.json").read_text())
    names = {e["name"] for e in spans["traceEvents"]}
    assert any(n.startswith("prof.") or n.startswith("decode")
               for n in names), sorted(names)


def test_missing_config_errors(tmp_path):
    r = _run_cli(["--model", str(tmp_path), "--prompt-ids", "1", "-n", "1"])
    assert r.returncode != 0
    assert "config.json not found" in r.stderr


def test_failure_domain_flags_need_host_topology(model_dir):
    """--recover-deadline/--connect-retries/--op-timeout/--chaos drive
    cross-host worker links; anywhere else they must error loudly instead
    of being silently ignored (in-process: the exit fires right after
    config load)."""
    from cake_tpu import cli

    for flags, frag in (
        (["--op-timeout", "5"], "--op-timeout"),
        (["--chaos", "kill@1"], "--chaos"),
        (["--connect-retries", "2", "--recover-deadline", "9"],
         "--connect-retries"),
    ):
        with pytest.raises(SystemExit) as e:
            cli.main(["--model", str(model_dir), "--prompt-ids", "1",
                      "--cpu", "-n", "1"] + flags)
        assert frag in str(e.value) and "topology" in str(e.value)


def test_op_timeout_zero_rejected(model_dir, tmp_path):
    """--op-timeout 0 is NOT a 'no deadline' mode (0 would mean disabled
    to SO_RCVTIMEO but non-blocking to settimeout) — reject it before it
    can silently reopen the hung-peer hole."""
    from cake_tpu import cli

    topo = tmp_path / "t.yml"
    topo.write_text("w:\n  host: 127.0.0.1:1\n  layers: [model.layers.0-3]\n")
    for flag, val in (("--op-timeout", "0"), ("--recover-deadline", "-1")):
        with pytest.raises(SystemExit) as e:
            cli.main(["--model", str(model_dir), "--topology", str(topo),
                      "--prompt-ids", "1", "--cpu", "-n", "1", flag, val])
        assert "must exceed 0" in str(e.value)


def test_failure_domain_flags_rejected_in_worker_mode(model_dir):
    from cake_tpu import cli

    with pytest.raises(SystemExit) as e:
        cli.main(["--model", str(model_dir), "--mode", "worker", "--name",
                  "w", "--topology", "whatever.yml", "--cpu",
                  "--chaos", "seed=1"])
    assert "master process" in str(e.value)


def test_kv_layout_flags_validated(model_dir):
    """--kv-layout paged rides the batched serving engine (serve /
    --prompts-file); elsewhere — and for the page knobs without paged —
    the CLI errors loudly instead of silently ignoring the layout."""
    from cake_tpu import cli

    with pytest.raises(SystemExit) as e:
        cli.main(["--model", str(model_dir), "--prompt-ids", "1", "--cpu",
                  "-n", "1", "--kv-layout", "paged"])
    assert "--kv-layout paged" in str(e.value)
    for flag, val in (("--kv-page-size", "8"), ("--kv-pool-pages", "64")):
        with pytest.raises(SystemExit) as e:
            cli.main(["--model", str(model_dir), "--prompt-ids", "1",
                      "--cpu", "-n", "1", flag, val])
        assert "--kv-layout paged" in str(e.value)


def test_serve_flags_need_serve_mode(model_dir):
    """--serve-port/--max-concurrent/... configure the HTTP serving plane;
    on the one-shot master/worker paths they must error loudly instead of
    being silently ignored (and --mode serve refuses the one-shot prompt
    sources, which arrive over HTTP instead)."""
    from cake_tpu import cli

    for flags, frag in (
        (["--serve-port", "8080"], "--serve-port"),
        (["--max-concurrent", "4", "--queue-depth", "8"],
         "--max-concurrent"),
        (["--request-timeout", "30"], "--request-timeout"),
    ):
        with pytest.raises(SystemExit) as e:
            cli.main(["--model", str(model_dir), "--prompt-ids", "1",
                      "--cpu", "-n", "1"] + flags)
        assert frag in str(e.value) and "--mode serve" in str(e.value)
    with pytest.raises(SystemExit) as e:
        cli.main(["--model", str(model_dir), "--mode", "serve", "--cpu",
                  "--prompt-ids", "1"])
    assert "over HTTP" in str(e.value)
    for flags in (["--prefill-chunks", "2"], ["--top"]):
        with pytest.raises(SystemExit) as e:
            cli.main(["--model", str(model_dir), "--mode", "serve",
                      "--cpu"] + flags)
        assert "silently ignored" in str(e.value)
    for flag, val in (("--max-concurrent", "0"), ("--queue-depth", "0"),
                      ("--request-timeout", "0")):
        with pytest.raises(SystemExit) as e:
            cli.main(["--model", str(model_dir), "--mode", "serve",
                      "--cpu", flag, val])
        assert "must" in str(e.value)


@pytest.mark.slow
def test_serve_mode_e2e_with_drain(model_dir):
    """--mode serve end to end through the real CLI: SSE completion over
    HTTP, then SIGTERM drains and exits 0 (the serving plane's acceptance
    loop; the in-process surface is covered by tests/test_serve.py)."""
    import signal
    import socket
    import time
    import urllib.request

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "cake_tpu.cli", "--model", str(model_dir),
         "--mode", "serve", "--cpu", "--max-seq", "32",
         "--serve-port", str(port), "--max-concurrent", "2",
         "--queue-depth", "4", "--request-timeout", "60",
         "--temperature", "0"],
        env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    try:
        for _ in range(240):
            if proc.poll() is not None:
                pytest.fail(f"serve died rc={proc.returncode}: "
                            f"{proc.stderr.read().decode()[-2000:]}")
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=1)
                break
            except OSError:
                time.sleep(0.5)
        else:
            pytest.fail("serve never came up")
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions",
            data=json.dumps({"prompt_ids": [3, 5, 7], "max_tokens": 4,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            body = r.read()
        assert body.count(b"data: ") == 6  # 4 tokens + done + [DONE]
        assert b"[DONE]" in body
        # the parts of "model loaded in", as numbers the program reports
        rep = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/prof", timeout=30).read())
        up = rep["startup"]
        assert set(up) == {"params_s", "engine_s", "warm_s", "loaded_s"}
        assert all(v >= 0 for v in up.values())
        assert (up["params_s"] + up["engine_s"] + up["warm_s"]
                <= up["loaded_s"] + 0.01)
        # the capture control, in the process that holds the device
        answers = []
        for action in ("start", "stop"):
            ctl = urllib.request.Request(
                f"http://127.0.0.1:{port}/debug/trace",
                data=json.dumps({"action": action}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(ctl, timeout=120) as r:
                answers.append(json.loads(r.read()))
        trace_dir = Path(answers[1]["dir"])
        try:
            assert answers[0]["dir"] == answers[1]["dir"]
            assert answers[1]["perf_s"] > answers[0]["perf_s"]
            assert list(trace_dir.rglob("*.xplane.pb"))
            assert (trace_dir / "spans.trace.json").exists()
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0
        assert b"drained" in proc.stderr.read()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


def test_string_prompt_without_tokenizer_errors(model_dir):
    r = _run_cli([
        "--model", str(model_dir), "--prompt", "hello", "-n", "1", "--cpu",
    ])
    assert r.returncode != 0
    assert "--prompt-ids" in r.stderr


def test_worker_requires_name(model_dir):
    r = _run_cli(["--model", str(model_dir), "--mode", "worker"])
    assert r.returncode != 0
    assert "--name" in r.stderr


def test_master_worker_loopback_via_cli(model_dir, tmp_path):
    """The full reference deployment shape driven through the real CLI:
    `--mode worker` serves its topology-assigned layers over TCP, the
    master walks local + remote segments and streams tokens (main.rs
    master/worker dispatch, end to end)."""
    import socket
    import time

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    topo = tmp_path / "topo.yml"
    topo.write_text(
        f"w1:\n  host: 127.0.0.1:{port}\n  layers:\n    - model.layers.2-3\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO)
    env["JAX_PLATFORMS"] = "cpu"
    worker_log = tmp_path / "worker.log"
    with open(worker_log, "wb") as logf:
        worker = subprocess.Popen(
            [sys.executable, "-m", "cake_tpu.cli", "--model", str(model_dir),
             "--mode", "worker", "--name", "w1", "--topology", str(topo),
             "--address", f"127.0.0.1:{port}", "--max-seq", "32", "--cpu"],
            env=env, stdout=logf, stderr=logf,  # file: no pipe-full deadlock
        )
    try:
        # wait for the worker to listen
        for _ in range(120):
            if worker.poll() is not None:
                pytest.fail(f"worker died rc={worker.returncode}: "
                            f"{worker_log.read_text()[-2000:]}")
            try:
                probe = socket.create_connection(("127.0.0.1", port),
                                                 timeout=1)
                probe.close()
                break
            except OSError:
                time.sleep(0.5)
        else:
            pytest.fail("worker never started listening: "
                        f"{worker_log.read_text()[-2000:]}")
        r = _run_cli([
            "--model", str(model_dir), "--prompt-ids", "3,5,7", "-n", "4",
            "--temperature", "0", "--max-seq", "32", "--cpu",
            "--topology", str(topo), "-v",
        ])
        assert r.returncode == 0, r.stderr
        assert "tok/s" in r.stderr
        assert f"127.0.0.1:{port}" in r.stderr  # remote segment stats logged
    finally:
        worker.terminate()
        try:
            worker.wait(timeout=30)
        except subprocess.TimeoutExpired:
            worker.kill()  # don't mask the real failure or leak the process


def test_prompts_file_serves_over_sp_window(model_dir, tmp_path):
    """--prompts-file --sp 2 (r4): the serving batch decodes against a
    sequence-sharded KV window; streams identical to the sp=1 run."""
    pf = tmp_path / "prompts.txt"
    pf.write_text("3,5,7\n2,4\n")

    def run(extra):
        r = _run_cli(["--model", str(model_dir), "--prompts-file", str(pf),
                      "--prompts-ids", "-n", "4", "--temperature", "0",
                      "--max-seq", "32", "--cpu"] + extra, devices=8)
        assert r.returncode == 0, r.stderr
        return [l for l in r.stdout.splitlines() if l.startswith("[")]

    assert run(["--sp", "2"]) == run([])
    # --speculate stays the sp == 1 serving path
    r = _run_cli(["--model", str(model_dir), "--prompts-file", str(pf),
                  "--prompts-ids", "--cpu", "--sp", "2", "--speculate", "4"],
                 timeout=120, devices=8)
    assert r.returncode != 0 and "--sp 1" in r.stderr
    # --max-seq not divisible by --sp: clean error, not a traceback
    r = _run_cli(["--model", str(model_dir), "--prompts-file", str(pf),
                  "--prompts-ids", "--cpu", "--sp", "2", "--max-seq", "31"],
                 timeout=120, devices=8)
    assert r.returncode != 0 and r.stderr.startswith("error:")
    assert "sp 2" in r.stderr and "Traceback" not in r.stderr


def test_window_override(tmp_path):
    """--window grants/narrows the attention window from the CLI; 0
    disables a checkpoint's own window."""
    import dataclasses
    import json

    import jax

    from cake_tpu.models import llama as L
    from cake_tpu.models.config import tiny
    from cake_tpu.utils.weights import save_llama_params

    cfg = tiny(max_seq_len=64)
    save_llama_params(L.init_params(cfg, jax.random.PRNGKey(0)), tmp_path,
                      cfg.num_hidden_layers)
    (tmp_path / "config.json").write_text(json.dumps(cfg.to_hf_dict()))
    base = ["--model", str(tmp_path), "--prompt-ids", "3,5,7,9,2,8,1,4",
            "-n", "6", "--temperature", "0", "--max-seq", "64", "--cpu",
            "--dtype", "f32"]
    def toks(argv):
        r = _run_cli(argv)
        assert r.returncode == 0, r.stderr[-2000:]
        return r.stdout.strip().splitlines()[-1]

    plain = toks(base)
    windowed = toks(base + ["--window", "4"])
    assert plain != windowed  # the override genuinely narrows attention
    assert toks(base + ["--window", "0"]) == plain  # 0 == no window

    # a mistral config's own window applies by default and is disabled
    # by --window 0
    mcfg = dataclasses.replace(cfg, model_type="mistral", sliding_window=4)
    (tmp_path / "config.json").write_text(json.dumps(mcfg.to_hf_dict()))
    assert toks(base + ["--window", "0"]) == plain
    assert toks(base) == windowed


def test_lookahead_on_a_batched_path_is_taken_and_says_so(model_dir,
                                                          tmp_path):
    """The batched engine has one order of work at a block boundary (the
    next block before the landed rows), so ``--lookahead`` has nothing to
    switch there: it is accepted -- with ``--decode-block 1`` too, which
    the switch used to refuse -- says so, and changes no id."""
    pf = tmp_path / "prompts.txt"
    pf.write_text("3,5,7\n2,4\n")
    base = ["--model", str(model_dir), "--prompts-file", str(pf),
            "--prompts-ids", "-n", "6", "--temperature", "0",
            "--max-seq", "32", "--cpu"]
    env = dict(os.environ, PYTHONPATH=str(REPO), JAX_PLATFORMS="cpu")
    runs = [subprocess.Popen(
        [sys.executable, "-m", "cake_tpu.cli"] + base + extra,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        cwd=REPO) for extra in ([], ["--lookahead"],
                                ["--lookahead", "--decode-block", "1"])]
    outs = [p.communicate(timeout=240) + (p.returncode,) for p in runs]
    ids = [[l for l in out.splitlines() if l.startswith("[")]
           for out, _, _ in outs]
    assert all(rc == 0 for _, _, rc in outs), [e[-400:] for _, e, _ in outs]
    assert len(ids[0]) == 2 and ids[0] == ids[1] == ids[2]
    assert "--lookahead changes nothing here" not in outs[0][1]
    assert "--lookahead changes nothing here" in outs[1][1]
    assert "--lookahead changes nothing here" in outs[2][1]


def test_lookahead_and_wire_codec_flag_guards(model_dir):
    """--lookahead with --decode-block 1 on the single-stream path (where
    the flag keeps its meaning: runtime/generator.py) and a compressing
    --wire-codec on a non-topology run are rejected loudly (not silently
    ignored); spelling out the default --wire-codec none anywhere is a
    harmless no-op."""
    r = _run_cli([
        "--model", str(model_dir), "--prompt-ids", "3,5", "-n", "2",
        "--temperature", "0", "--max-seq", "32", "--cpu",
        "--lookahead", "--decode-block", "1",
    ])
    assert r.returncode != 0
    assert "requires --decode-block > 1" in r.stderr
    r = _run_cli([
        "--model", str(model_dir), "--prompt-ids", "3,5", "-n", "2",
        "--temperature", "0", "--max-seq", "32", "--cpu",
        "--wire-codec", "int8",
    ])
    assert r.returncode != 0
    assert "host-addressed --topology" in r.stderr
    r = _run_cli([
        "--model", str(model_dir), "--prompt-ids", "3,5", "-n", "2",
        "--temperature", "0", "--max-seq", "32", "--cpu",
        "--wire-codec", "none", "--lookahead", "--decode-block", "4",
    ])
    assert r.returncode == 0, r.stderr
