"""CLI surface: flag parity with the reference + end-to-end subprocess
runs (local, mesh, device topologies, a prompts file, speculation, a
profile). The flag guards and the worker, sequence-parallel, window and
lookahead runs are ``tests/test_cli_guards.py`` since PR 59; what the two
share is ``tests/cli_kit.py``.
"""

import json
import os
import subprocess
import sys

from cake_tpu.cli import build_parser

from cli_kit import REPO, _run_cli, model_dir  # noqa: F401


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
