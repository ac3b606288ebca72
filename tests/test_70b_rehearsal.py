"""70B-on-16 dress rehearsal (BASELINE.json configs 4/5).

Three planes, no pod required:
- divisibility: the real Llama-3-70B geometry shards onto the v5e-16 layouts
  of record (validate_shardable);
- HBM budget: the per-chip arithmetic (utils.memory.hbm_budget) shows bf16
  does NOT fit a 16 GiB chip at the serving window while int8 does — the
  SURVEY §7 "int8 is load-bearing" claim, now checkable;
- execution: an 80-layer model (tiny dims, the 70B layer/stage geometry)
  runs prefill + decode on a 16-virtual-device CPU mesh at stage=16 and
  stage=8 x tp=2, int8-quantized, matching the single-device oracle
  token-for-token.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

from cake_tpu.models.config import llama3_70b
from cake_tpu.parallel.mesh import validate_shardable
from cake_tpu.utils.memory import hbm_budget

REPO = Path(__file__).resolve().parents[1]
V5E_USABLE = 14.5 * 2**30  # 16 GiB HBM minus ~1.5 GiB runtime reserve (measured)


@pytest.mark.parametrize(
    "stages,tp,sp",
    [(16, 1, 1), (8, 2, 1), (4, 4, 1), (16, 1, 2), (8, 2, 2)],
)
def test_70b_divisibility_on_16(stages, tp, sp):
    """80 layers / 64 heads / 8 kv heads / 28672 intermediate divide into
    every 16-chip layout of record."""
    validate_shardable(llama3_70b(max_seq_len=8192), stages, tp, sp)


def test_70b_hbm_budget_configs_4_and_5():
    """Config 4 (bf16) vs config 5 (int8) on v5e-16 at an 8K window
    (planning arithmetic of utils.memory.hbm_budget, not a measurement).

    bf16 per chip: 5 layers x 1.6 GiB + 2 GiB replicated embed + 2 GiB
    lm_head + KV = ~12 GiB — fits the ~14.5 GiB usable, but with only
    ~2.5 GiB for activations/workspace/fragmentation. int8 (config 5)
    halves the linears to ~7.1 GiB — the comfortable serving tier, and the
    one that leaves room to grow batch/window.
    """
    cfg = llama3_70b(max_seq_len=8192)
    bf16 = hbm_budget(cfg, num_stages=16, tp=1)
    int8 = hbm_budget(cfg, num_stages=16, tp=1, quant="int8")
    assert bf16["total"] < V5E_USABLE, "bf16 70B/16 fits, tightly"
    assert bf16["total"] > 0.75 * V5E_USABLE, "…with little headroom"
    assert int8["total"] < 0.55 * V5E_USABLE, "int8 70B/16 fits comfortably"
    # KV at the full window stays a minor term in this layout
    assert int8["kv_cache"] < 0.5 * 2**30
    # config 5 with tp=2 x stage=8 also fits (lm_head/linears shard further,
    # embed replication is the floor)
    int8_tp2 = hbm_budget(cfg, num_stages=8, tp=2, quant="int8")
    assert int8_tp2["total"] < 0.55 * V5E_USABLE
    # serving tier at batch 32 / 8K window: the int8 KV cache returns
    # multi-GiB of per-chip headroom that bf16 KV burns
    bf16_kv = hbm_budget(cfg, num_stages=16, tp=1, quant="int8", batch=32)
    int8_kv = hbm_budget(cfg, num_stages=16, tp=1, quant="int8", batch=32,
                         cache_bytes_per_el=1)
    assert bf16_kv["total"] - int8_kv["total"] > 2.0 * 2**30
    assert int8_kv["total"] < 0.75 * V5E_USABLE


_SCRIPT = r"""
import jax
from cake_tpu.models import llama
from cake_tpu.models.config import tiny
from cake_tpu.ops.quant import quantize_params
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.runtime.generator import LlamaGenerator
from cake_tpu.runtime.mesh_generator import MeshGenerator

assert len(jax.devices()) == 16, jax.devices()
cfg = tiny(num_hidden_layers=80, max_seq_len=64)
params = quantize_params(llama.init_params(cfg, jax.random.PRNGKey(0)))
settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
g_local = LlamaGenerator(cfg, params, settings=settings)
g_local.set_prompt([5, 9, 2, 11])
want = [g_local.next_token(i).id for i in range(6)]
for stages, tp in ((16, 1), (8, 2)):
    g = MeshGenerator(cfg, params, settings=settings, num_stages=stages, tp=tp)
    g.set_prompt([5, 9, 2, 11])
    got = [g.next_token(i).id for i in range(6)]
    assert got == want, (stages, tp, got, want)
    print(f"stage={stages} tp={tp} ok", flush=True)
# config-5 serving tier: int8 weights + int8 KV on the 16-stage layout,
# parity with the single-device int8-KV oracle
g_local8 = LlamaGenerator(cfg, params, settings=settings, kv_quant="int8")
g_local8.set_prompt([5, 9, 2, 11])
want8 = [g_local8.next_token(i).id for i in range(6)]
g8 = MeshGenerator(cfg, params, settings=settings, num_stages=16,
                   kv_quant="int8")
g8.set_prompt([5, 9, 2, 11])
got8 = [g8.next_token(i).id for i in range(6)]
assert got8 == want8, (got8, want8)
print("stage=16 int8-kv ok", flush=True)
print("70b-geometry rehearsal ok")
"""


def test_70b_geometry_runs_on_16_device_mesh():
    """80 layers, int8, stage=16 and stage=8 x tp=2 on 16 virtual CPU
    devices: prefill + 6 decode tokens, greedy parity with the single-device
    oracle. (Subprocess: the suite's own mesh is pinned to 8 devices.)"""
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
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, timeout=540, env=env, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "70b-geometry rehearsal ok" in r.stdout
