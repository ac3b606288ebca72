"""chip_smoke.py's contract, as far as a sandbox without a chip can show.

The script's rehearsal option (tiny size, CPU, interpreted kernels) runs
the same control flow as the chip run: checkpoint writer child, `--mode
serve` child driven over HTTP, SIGTERM drain, kernel child; with
`--chips 4`, the sharded server on four virtual CPU devices and its
one-chip comparison. Without the option a run that finds no TPU must
exit non-zero and print no result. Those runs and the two compile-cache
probes are subprocesses that need nothing from pytest: the `runs`
fixture starts them together when the file's first test starts, the
tests that need none of them come first and run meanwhile, and the rest
wait for the one they read.

Beside them: the peaks table raising for a device it does not know, the
native wire library's content stamp, and the seeded checkpoint writer.
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

# what utils/compile_cache.configure() returns, what JAX then holds, and
# which config keys the call itself set
_CACHE_PROBE = """
import json, jax
calls, update = [], jax.config.update
jax.config.update = lambda name, val: (calls.append(name), update(name, val))
from cake_tpu.utils.compile_cache import configure
print(json.dumps([configure(), jax.config.jax_compilation_cache_dir, calls]))
"""


@pytest.fixture(scope="module", autouse=True)
def runs():
    """name -> (returncode, parsed stdout JSON lines, stderr text), waiting
    for that subprocess only when a test asks for it."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    smoke = [sys.executable, str(ROOT / "chip_smoke.py")]
    probe = [sys.executable, "-c", _CACHE_PROBE]
    given = tempfile.mkdtemp(prefix="cake_cache_given_")
    procs = {}
    for name, cmd, extra in (
            ("rehearse", smoke + ["--rehearse"], {}),
            ("rehearse4", smoke + ["--rehearse", "--chips", "4"], {}),
            ("bare", smoke, {}),
            ("cache_unset", probe, {}),
            ("cache_given", probe, {"JAX_COMPILATION_CACHE_DIR": given})):
        out, err = tempfile.TemporaryFile("w+"), tempfile.TemporaryFile("w+")
        procs[name] = (subprocess.Popen(cmd, env={**env, **extra}, cwd=ROOT,
                                        stdout=out, stderr=err), out, err)

    def result(name):
        proc, out, err = procs[name]
        try:
            proc.wait(timeout=300)
        except subprocess.TimeoutExpired:
            proc.terminate()
            proc.wait()
        out.seek(0)
        err.seek(0)
        return (proc.returncode,
                [json.loads(line) for line in out.read().splitlines() if line],
                err.read())

    yield result
    for proc, *_ in procs.values():
        if proc.poll() is None:  # its tests never ran
            proc.terminate()  # chip_smoke.py stops its children on SIGTERM
            proc.wait()
    shutil.rmtree(given, ignore_errors=True)


def _phases(rows, phase):
    return [r for r in rows if r.get("phase") == phase]


# -- no fallback that hides the device (nothing here waits on a subprocess) ---

def test_chips_raises_for_unknown_device_kind():
    from types import SimpleNamespace

    from cake_tpu.utils.chips import HBM_GBPS, HBM_GIB, device_spec

    v5e = SimpleNamespace(device_kind="TPU v5 lite")
    assert device_spec(v5e, HBM_GBPS) == 819.0
    assert device_spec(v5e, HBM_GIB) == 16.0
    for kind in ("cpu", "TPU v9 imaginary"):
        with pytest.raises(KeyError, match="no published peaks"):
            device_spec(SimpleNamespace(device_kind=kind), HBM_GBPS)


# -- built from what git holds ------------------------------------------------

def test_wire_library_is_rebuilt_when_not_from_this_source(monkeypatch,
                                                          tmp_path):
    """A binary counts as current by its source's content hash, not its
    mtime (a copy of the tree keeps no mtimes): a stray library with no
    stamp, or another revision's stamp, is rebuilt, never loaded."""
    from cake_tpu.runtime import wire

    so, stamp = tmp_path / "libcakewire.so", tmp_path / "libcakewire.so.stamp"
    monkeypatch.setattr(wire, "_SO", so)
    monkeypatch.setattr(wire, "_STAMP", stamp)
    so.write_bytes(b"not a library: a stray file from another checkout")
    lib = wire._load_native()
    assert not isinstance(lib, str), lib  # rebuilt and loaded
    assert stamp.read_text() == wire._src_stamp()
    built = so.read_bytes()
    stamp.write_text("0" * 64)  # another revision's stamp
    assert not isinstance(wire._load_native(), str)
    assert stamp.read_text() == wire._src_stamp()
    assert so.read_bytes()[:4] == built[:4] == b"\x7fELF"


# -- the seeded streaming checkpoint writer -----------------------------------

def test_seeded_q8_checkpoint_is_deterministic_and_loads(tmp_path):
    """Bytes depend only on (config, seed) -- not on the worker count --
    and the real direct-to-mesh loader reads them back as the quantized
    pytree the engine serves."""
    import jax
    import numpy as np

    from cake_tpu.models.config import LlamaConfig, tiny
    from cake_tpu.ops.quant import QuantizedLinear
    from cake_tpu.parallel.mesh import MeshPlan
    from cake_tpu.utils.sharded_load import load_llama_params_on_mesh
    from cake_tpu.utils.weights import write_random_q8_checkpoint

    cfg = tiny(model_type="mistral", sliding_window=64)
    a, b, c = (tmp_path / n for n in "abc")
    write_random_q8_checkpoint(cfg, a, seed=3, workers=1)
    write_random_q8_checkpoint(cfg, b, seed=3, workers=4)
    write_random_q8_checkpoint(cfg, c, seed=4)
    name = "model-layer-00002.safetensors"
    assert (a / name).read_bytes() == (b / name).read_bytes()
    assert (a / name).read_bytes() != (c / name).read_bytes()

    loaded = LlamaConfig.from_hf_json(a / "config.json", dtype="float32")
    assert loaded.sliding_window == 64 and loaded.model_type == "mistral"
    plan = MeshPlan.build(loaded, devices=jax.devices()[:1])
    params = load_llama_params_on_mesh(a, loaded, plan.mesh, quantize="int8")
    wq = params["layers"]["wq"]
    assert isinstance(wq, QuantizedLinear)
    assert wq.q.shape == (4, 64, 64) and wq.q.dtype == np.int8
    # the one quantization convention: every column's absmax maps to 127
    assert int(np.abs(np.asarray(wq.q)).max(axis=1).min()) == 127
    w = np.asarray(wq.q[0], np.float32) * np.asarray(wq.scale[0])
    assert 0.08 < w.std() < 0.17  # normal / sqrt(64)


# -- chip_smoke.py itself -------------------------------------------------------

def test_fails_beside_nothing_of_the_repo(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else it must
    fail at once, whatever the machine."""
    (tmp_path / "chip_smoke.py").write_text(
        (ROOT / "chip_smoke.py").read_text())
    r = subprocess.run([sys.executable, "chip_smoke.py", "--rehearse"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=60)
    assert r.returncode != 0 and '"ok"' not in r.stdout
    assert "no cake_tpu/ beside this script" in r.stderr


def test_without_a_tpu_and_without_the_option_it_fails(runs):
    rc, rows, err = runs("bare")
    assert rc != 0
    assert not any(r.get("ok") for r in rows)
    assert "needs the chip" in err


def test_cache_env_set_code_sets_nothing(runs):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself: what the config holds
    is the environment's directory, and configure() touched no key."""
    rc, rows, err = runs("cache_given")
    assert rc == 0, err[-2000:]
    (given, held, set_by_code), = rows
    assert given == held and "cake_cache_given_" in given
    assert set_by_code == []


def test_cache_env_unset_fixed_checkout_path(runs):
    rc, rows, err = runs("cache_unset")
    assert rc == 0, err[-2000:]
    want = str(ROOT / ".jax_cache")
    assert rows == [[want, want, ["jax_compilation_cache_dir"]]]
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_four_chip_rehearsal_on_virtual_devices(runs):
    rc, rows, err = runs("rehearse4")
    assert rc == 0, err[-3000:]
    assert rows[-1]["device"]["count"] == 4
    # only the sharded path and what it is compared with: no other phase
    assert not _phases(rows, "kernels") and not _phases(rows, "steady")
    ready = _phases(rows, "server_ready")
    assert [r["flags"] for r in ready] == [["--stages", "2", "--tp", "2"], []]
    cmp_rows = _phases(rows, "compare")
    assert len(cmp_rows) == 3
    # float32 on the CPU: the two servers agree to rounding
    assert all(r["ids_part_at"] is None
               and r["max_logprob_diff_before"] < 1e-3 for r in cmp_rows)


def test_rehearsal_runs_to_the_end(runs):
    rc, rows, err = runs("rehearse")
    assert rc == 0, err[-3000:]
    assert rows[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    # every earlier line says it is a rehearsal (a kernel row: that it
    # ran interpreted): no CPU time can pass for a device's
    assert all(r.get("rehearsal") or r.get("compiled") is False
               for r in rows[:-1])
    reqs = _phases(rows, "request")
    assert [r["name"] for r in reqs][-5:] == [
        "long", "short", "late_a", "late_b", "short_again"]
    assert all(r["ok"] and r["finish_reason"] == "length" for r in reqs)
    (steady,) = _phases(rows, "steady")
    assert steady["compiles_after_warmup"] == 0
    assert steady["repeat_same_ids"] is True
    assert _phases(rows, "shutdown") == [
        {"phase": "shutdown", "rc": 0, "drained": True, "rehearsal": True}]


def test_rehearsal_kernel_child_checks_q8_and_sync(runs):
    _, rows, _ = runs("rehearse")
    kernels = [r for r in rows if "kernel" in r]
    assert any(r["kernel"].startswith("flash_attention_q8") for r in kernels)
    assert len(kernels) == 9 and all(r["ok"] for r in kernels)
    # interpreted rows carry no times: they are not a device's
    assert all(r["compiled"] is False and r["pallas_ms"] == "not measured"
               for r in kernels)
    (sync,) = _phases(rows, "sync_check")
    assert sync["block_until_ready_ms"] > 0 and sync["host_fetch_ms"] > 0


def test_rehearsal_reports_the_device_that_served(runs):
    _, rows, _ = runs("rehearse")
    (dev,) = _phases(rows, "device")
    assert (dev["platform"], dev["count"]) == ("cpu", 1)
    assert [d["id"] for d in dev["devices"]] == [0]
