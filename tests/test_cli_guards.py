"""CLI surface, continued from ``tests/test_cli.py`` (in a file of its own
since PR 59): what the parser and the entry point refuse, and the runs
through the master/worker loopback, a sequence-parallel window, a
window override and the lookahead. Shared: ``tests/cli_kit.py``.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from cake_tpu.models.config import tiny
from cake_tpu.utils.weights import save_llama_params

from cli_kit import REPO, _run_cli, model_dir  # noqa: F401


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
