"""70B weight-plane rehearsal at FILE scale (r4: verdict item 7).

The offline weight plane of the reference is `cake-split-model`
(cake-split-model/src/main.rs:144-223): read a sharded safetensors index,
keep only the bytes a node owns. The mesh-path equivalent here is
`utils/sharded_load.load_llama_params_on_mesh` over a REAL multi-shard
`model.safetensors.index.json` — this test rehearses the full 70B file
geometry (80 stacked layers, multiple shard files, pre-quantized `.q8`
tensors from tools/quantize_model) at tiny dims and proves, by byte
accounting, that

- each of the 16 pipeline stages' layer bytes is exactly 1/16 of the
  stacked-layer total (a stage reads its 5 layers, nothing else), and
- the loader reads the checkpoint once: total bytes ~= the checkpoint's
  tensor payload (no per-shard read amplification from the 16-way mesh),

and times the load (a host-side number, printed by the test and recorded
nowhere)."""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

INNER = r"""
import json, re, time
from pathlib import Path

import jax
assert len(jax.devices()) == 16, jax.devices()
import numpy as np

from cake_tpu.models import llama
from cake_tpu.models.config import tiny
from cake_tpu.parallel.mesh import MeshPlan
from cake_tpu.tools.quantize_model import quantize_checkpoint
from cake_tpu.utils import sharded_load
from cake_tpu.utils.weights import save_llama_params

cfg = tiny(num_hidden_layers=80, num_attention_heads=8,
           num_key_value_heads=4, hidden_size=64, intermediate_size=128,
           vocab_size=256, max_seq_len=32)
root = Path(r"{tmp}")
bf = root / "bf16"
params = llama.init_params(cfg, jax.random.PRNGKey(0))
save_llama_params(params, bf, cfg.num_hidden_layers)

# pre-quantized multi-shard checkpoint (~1 MiB shards -> several files,
# the real 70B index geometry at miniature scale)
q8 = root / "q8"
quantize_checkpoint(bf, q8, shard_bytes=1 << 20)
index = json.loads((q8 / "model.safetensors.index.json").read_text())
shard_files = sorted(set(index["weight_map"].values()))
assert len(shard_files) >= 3, shard_files
payload = index["metadata"]["total_size"]

# per-stage byte attribution: bucket every read by the layer index in the
# tensor name (stage s owns layers [5s, 5s+5) at stage=16 over 80 layers)
stage_bytes = [0] * 16
other_bytes = [0]
layer_re = re.compile(r"model\.layers\.(\d+)\.")

def account(name, nbytes):
    m = layer_re.match(name)
    if m:
        stage_bytes[int(m.group(1)) // 5] += nbytes
    else:
        other_bytes[0] += nbytes

orig1, orig2 = (sharded_load.CheckpointReader.read1d,
                sharded_load.CheckpointReader.read2d)

def read1d(self, name, sl=slice(None)):
    out = orig1(self, name, sl)
    account(name, out.nbytes)
    return out

def read2d(self, name, rows, cols, transpose):
    out = orig2(self, name, rows, cols, transpose)
    account(name, out.nbytes)
    return out

sharded_load.CheckpointReader.read1d = read1d
sharded_load.CheckpointReader.read2d = read2d

plan = MeshPlan.build(cfg, num_stages=16, devices=jax.devices())
t0 = time.perf_counter()
loaded = sharded_load.load_llama_params_on_mesh(
    q8, cfg, plan.mesh, quantize="int8")
for leaf in jax.tree.leaves(loaded):
    leaf.block_until_ready()
dt = time.perf_counter() - t0

total_layer = sum(stage_bytes)
# every stage's layer bytes == exactly 1/16 of the stacked-layer total
for s, b in enumerate(stage_bytes):
    assert b == total_layer // 16, (s, b, total_layer)
# read-once: total attributed bytes ~= the checkpoint payload. The int8
# path re-derives nothing (pre-quantized), and replicated leaves
# (embed/norm/head) are memoized to one read despite 16 addressable
# shards. Scales are f32 in both. Allow a few % for dtype/layout edges.
grand = total_layer + other_bytes[0]
assert abs(grand - payload) / payload < 0.05, (grand, payload)

q = loaded["layers"]["wq"].q
assert q.shape == (80, 64, 64) and str(q.dtype) == "int8"
print(json.dumps({
    "shards": len(shard_files),
    "payload_bytes": payload,
    "stage_layer_bytes": stage_bytes[0],
    "load_s": round(dt, 3),
    "mb_per_s": round(payload / dt / 1e6, 1),
}))
print("fileplane ok")
"""


def test_80layer_multishard_q8_load_stage16(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=16"]
    )
    r = subprocess.run(
        [sys.executable, "-c", INNER.replace("{tmp}", str(tmp_path))],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "fileplane ok" in r.stdout
    stats = json.loads(r.stdout.strip().splitlines()[-2])
    assert stats["shards"] >= 3
    assert stats["load_s"] > 0
