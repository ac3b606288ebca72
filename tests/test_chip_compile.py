"""The chip's compiler on the main path's kernels, at real widths.

Interpret mode (every other test here) accepts programs Mosaic refuses:
an unaligned slice, a primitive with no TPU lowering, more VMEM than a
kernel may use. The TPU compiler is installed in the sandbox and compiles
for a v5e that is *described*, not attached
(``/opt/skills/guides/on-chip-measurement`` section 2.3), so each case
here is one AOT ``lower().compile()`` of a kernel the serving path
dispatches, at Mistral-7B widths (32 q / 8 kv heads of 128, hidden 4096,
FFN 14336, vocab 32000). Nothing runs: these prove "the chip's compiler
accepts it", never a result or a time.
"""

import os
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from cake_tpu.ops import pallas as pk
from cake_tpu.ops.pallas import (
    flash_attention,
    flash_attention_q8,
    flash_decode,
    quant4_matmul_pallas,
    quant_matmul_pallas,
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e topology description: {e}")


H, KVH, D = 32, 8, 128
HID, FFN, VOCAB = 4096, 14336, 32000
BF16, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


def _flash(t, s, window):
    return (partial(flash_attention, window=window, interpret=False),
            [((1, H, t, D), BF16), ((1, KVH, s, D), BF16),
             ((1, KVH, s, D), BF16), ((), I32)])


def _flash_q8(t, s, window):
    return (partial(flash_attention_q8, window=window, interpret=False),
            [((1, H, t, D), BF16), ((1, KVH, s, D), I8), ((1, KVH, s), F32),
             ((1, KVH, s, D), I8), ((1, KVH, s), F32), ((), I32)])


def _decode(b, s, window):
    return (partial(flash_decode, window=window, interpret=False),
            [((b, H, 1, D), BF16), ((b, KVH, s, D), BF16),
             ((b, KVH, s, D), BF16), ((b,), I32)])


def _qmm(m, k, n):
    return (partial(quant_matmul_pallas, interpret=False),
            [((m, k), BF16), ((k, n), I8), ((n,), F32)])


def _q4mm(m, k, n, group):
    scale = ((k // group, n) if group else (n,), F32)
    return (partial(quant4_matmul_pallas, interpret=False),
            [((m, k), BF16), ((k // 2, n), I8), scale])


KERNELS = {
    "flash_t256_s2048": _flash(256, 2048, None),
    "flash_t512_s4096": _flash(512, 4096, None),
    "flash_win4096_t256_s2048": _flash(256, 2048, 4096),
    "flash_win4096_t512_s4096": _flash(512, 4096, 4096),
    "flash_q8_t256_s2048": _flash_q8(256, 2048, None),
    # the acceptance case: --kv-quant int8, a 512-token chunk, 4096 window
    "flash_q8_win4096_t512_s4096": _flash_q8(512, 4096, 4096),
    "flash_decode_b8_s4096_per_row": _decode(8, 4096, 4096),
    "qmm_m64_4096x14336": _qmm(64, HID, FFN),
    "qmm_m64_14336x4096": _qmm(64, FFN, HID),
    "qmm_m64_4096x32000": _qmm(64, HID, VOCAB),
    "q4mm_m1_4096x14336_per_channel": _q4mm(1, HID, FFN, 0),
    "q4mm_m1_4096x14336_g256": _q4mm(1, HID, FFN, 256),
}


@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_for_v5e(topo, name):
    fn, shapes = KERNELS[name]
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # the kernel is in the program (not silently an XLA fallback)
    assert "tpu_custom_call" in compiled.as_text()


def _block_decode_bytes(topo, layers: int) -> tuple[int, int]:
    """(arguments, temporaries) of the engine's fused 8-step per-row block
    decode -- BatchGenerator's ``build_sharded_decode(steps=8,
    per_row=True)`` -- for ``layers`` layers at Mistral-7B widths, int8
    weights, 8 slots at a 2048 window (chip_smoke.py's sizes), compiled
    for one described v5e from ``jax.eval_shape`` shapes."""
    from jax.sharding import NamedSharding

    from cake_tpu.models.config import mistral_7b
    from cake_tpu.models.llama import init_params_int8
    from cake_tpu.ops.kvcache import init_cache
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.parallel.mesh import MeshPlan, cache_specs, param_specs
    from cake_tpu.parallel.pipeline import build_sharded_decode

    batch, window = 8, 2048
    config = mistral_7b(max_seq_len=window, num_hidden_layers=layers)
    plan = MeshPlan.build(config, devices=topo.devices[:1])

    def placed(shapes, specs):
        return jax.tree.map(
            lambda s, spec: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(plan.mesh, spec)),
            shapes, specs)

    params = jax.eval_shape(lambda k: init_params_int8(config, k),
                            jax.random.PRNGKey(0))
    params = placed(params, param_specs(params))
    cache = placed(
        jax.eval_shape(lambda: init_cache(config, batch=batch,
                                          max_seq=window)),
        cache_specs(None))
    settings = SamplerSettings(temperature=0.0)
    rep = NamedSharding(plan.mesh, jax.sharding.PartitionSpec())

    def arg(shape, dtype=I32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    prog = build_sharded_decode(config, settings, plan, params_like=params,
                                steps=8, per_row=True)
    compiled = prog.lower(
        params, arg((batch,)), cache, arg((batch,)),
        arg((batch, 2), jnp.uint32),
        arg((batch, settings.repeat_last_n)), arg((batch,)), arg((batch,)),
    ).compile()
    m = compiled.memory_analysis()
    # the cache is donated, so outputs alias arguments
    assert m.alias_size_in_bytes >= m.output_size_in_bytes * 0.99
    return m.argument_size_in_bytes, m.temp_size_in_bytes


def test_block_decode_program_fits_one_chip(topo, monkeypatch):
    """One whole engine program on one described device, by the
    compiler's own memory analysis. Compiled at depth 1 and 2 (a second
    each; the layer loop is a scan). The two-layer program fits; its
    arguments grow exactly linearly with depth, so they are carried to
    the 32 layers the smoke serves; and the temporaries are held to what
    PR 21's rehearsal found: this program keeps about one KV cache of
    temporaries beside the donated cache (1.0x at depth 2, 1.4x at depth
    32; CHANGES.md). A change that adds another cache-sized temporary is
    caught here before the 32-layer server meets the allocator on the
    chip."""
    from cake_tpu.utils.chips import HBM_GIB

    # code under trace asks jax.default_backend() and would take its CPU
    # branch; steer it here, in the test, as the guide says -- never
    # through an option of the program
    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    gib = 2**30
    hbm = HBM_GIB["v5 lite"] * gib
    a1, _ = _block_decode_bytes(topo, 1)
    a2, t2 = _block_decode_bytes(topo, 2)
    assert a2 + t2 < hbm
    cache_per_layer = 8 * 8 * 2048 * 128 * 2 * 2  # B, KVH, S, D, k+v, bf16
    assert t2 <= 1.5 * 2 * cache_per_layer + 0.05 * gib, t2 / gib
    args32 = a2 + 30 * (a2 - a1)
    assert 8.8 * gib < args32 < 8.95 * gib, args32 / gib  # 6.87 + 2.0
    # with 1.5 caches of temporaries and a GiB for the admission staging
    # row and the allocator, the 32-layer server still fits
    assert args32 + 1.5 * 32 * cache_per_layer + 1.0 * gib < hbm
