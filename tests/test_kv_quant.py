"""Int8 KV cache (kvcache.QuantizedKV): quantize-on-write, dequant-in-attend.

The serving-side long-context lever the reference's f16-only cache
(cache.rs:106-135) has no answer to: half the cache HBM, so batch x window
roughly doubles on a fixed budget (utils/memory.hbm_budget prices it).
Held to greedy-token parity with the bf16 cache at tiny scale across the
local, mesh, and serving execution paths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import tiny
from cake_tpu.ops.kvcache import (
    QuantizedKV,
    dequant_kv,
    init_cache,
    quant_kv,
    update_layer,
)
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.runtime.generator import LlamaGenerator

CFG = tiny(max_seq_len=64)
GREEDY = dict(temperature=0.0, repeat_penalty=1.1)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(9))


def test_quant_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 8, 16), jnp.bfloat16)
    deq = dequant_kv(quant_kv(x), jnp.float32)
    err = jnp.max(jnp.abs(deq - x.astype(jnp.float32)))
    # symmetric int8: error <= absmax/127 per (token, head) channel
    assert float(err) <= float(jnp.max(jnp.abs(x.astype(jnp.float32)))) / 127 + 1e-6


def test_init_cache_int8_halves_bytes():
    bf = init_cache(CFG, batch=2, max_seq=64)
    q8 = init_cache(CFG, batch=2, max_seq=64, quant="int8")
    bf_bytes = sum(x.nbytes for x in jax.tree.leaves(bf))
    q8_bytes = sum(x.nbytes for x in jax.tree.leaves(q8))
    assert isinstance(q8.k, QuantizedKV)
    assert q8_bytes < 0.75 * bf_bytes  # int8 + scales vs bf16


def test_update_layer_int8_slots_and_gate():
    """Writes land at the right slots with per-slot scales; the SPMD write
    gate predicates both the int8 bytes and the scales."""
    s, t = 16, 3
    cfg = tiny(max_seq_len=s)
    kh, d = cfg.num_key_value_heads, cfg.head_dim
    cache = init_cache(cfg, batch=1, max_seq=s, quant="int8")
    k_layer, v_layer = jax.tree.map(lambda x: x[0], (cache.k, cache.v))
    k_new = jax.random.normal(jax.random.PRNGKey(1), (1, kh, t, d), jnp.bfloat16)
    v_new = jax.random.normal(jax.random.PRNGKey(2), (1, kh, t, d), jnp.bfloat16)
    k2, v2 = update_layer(k_layer, v_layer, k_new, v_new, jnp.int32(5))
    deq = dequant_kv(k2, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(deq[:, :, 5:5 + t]), np.asarray(k_new, np.float32),
        atol=0.05,
    )
    assert np.asarray(deq[:, :, :5]).max() == 0  # untouched slots stay zero
    # gated off: nothing lands
    k3, _ = update_layer(k_layer, v_layer, k_new, v_new, jnp.int32(5),
                         gate=jnp.asarray(False))
    assert np.asarray(dequant_kv(k3, jnp.float32)).max() == 0


def _greedy(gen, prompt, n):
    gen.set_prompt(prompt)
    return [gen.next_token(i).id for i in range(n)]


def test_local_generator_int8_kv_matches_bf16(params):
    settings = SamplerSettings(**GREEDY)
    ref = _greedy(LlamaGenerator(CFG, params, settings=settings), [5, 9, 2], 8)
    got = _greedy(
        LlamaGenerator(CFG, params, settings=settings, kv_quant="int8"),
        [5, 9, 2], 8,
    )
    assert got == ref


def test_local_generator_int8_kv_block_decode(params):
    settings = SamplerSettings(**GREEDY)
    ref = _greedy(
        LlamaGenerator(CFG, params, settings=settings, kv_quant="int8"),
        [3, 1, 4], 8,
    )
    got = _greedy(
        LlamaGenerator(CFG, params, settings=settings, kv_quant="int8",
                       block_size=4),
        [3, 1, 4], 8,
    )
    assert got == ref


def test_mesh_generator_int8_kv(params):
    from cake_tpu.runtime.mesh_generator import MeshGenerator

    settings = SamplerSettings(**GREEDY)
    ref = _greedy(LlamaGenerator(CFG, params, settings=settings), [7, 7, 2], 6)
    gen = MeshGenerator(CFG, params, settings=settings, num_stages=2, tp=2,
                        kv_quant="int8")
    assert _greedy(gen, [7, 7, 2], 6) == ref


@pytest.mark.parametrize("stages", [1, 2])
def test_int8_kv_composes_with_sequence_parallelism(params, stages):
    """The long-context plane and the quantized cache compose: sp=2 ring
    prefill + distributed decode over int8 KV matches the single-device
    int8-KV oracle token-for-token (the sp paths quantize-on-write and the
    ring attends the same round-tripped values the cache holds)."""
    from cake_tpu.runtime.mesh_generator import MeshGenerator

    settings = SamplerSettings(**GREEDY)
    prompt = [5, 9, 2, 11, 3, 8]
    want = _greedy(LlamaGenerator(CFG, params, settings=settings,
                                  kv_quant="int8"), prompt, 8)
    g = MeshGenerator(CFG, params, settings=settings, num_stages=stages,
                      sp=2, kv_quant="int8")
    assert _greedy(g, prompt, 8) == want


def test_int8_kv_sp_long_prompt_chunked_write(params):
    """A prompt long enough to exercise the chunked sp cache write (bucket
    < window) with quantized halves riding the all-gather."""
    from cake_tpu.runtime.mesh_generator import MeshGenerator

    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    prompt = list(range(2, 2 + 20))  # buckets to 32 < max_seq 64
    want = _greedy(LlamaGenerator(CFG, params, settings=settings,
                                  kv_quant="int8"), prompt, 6)
    g = MeshGenerator(CFG, params, settings=settings, sp=2,
                      kv_quant="int8")
    assert _greedy(g, prompt, 6) == want


def test_batch_generator_int8_kv_serving_and_admit(params):
    """The serving plane with int8 KV: every concurrent greedy stream is
    bit-identical to its own solo int8 run (the per-stream independence
    contract — int8-vs-bf16 drift compounds over long runs, so cross-dtype
    parity is only held at short range by the local-path test above), and
    admit() splices a quantized KV row correctly."""
    from cake_tpu.runtime.batch_generator import BatchGenerator

    settings = SamplerSettings(**GREEDY)
    prompts = [[5, 9, 2, 11], [3, 1, 4, 1, 5, 9], [7, 7, 2]]

    g = BatchGenerator(CFG, params, settings=settings, dp=1,
                       block_size=4, kv_quant="int8")
    g.set_prompts(prompts)
    got = g.generate(8)
    for i, prompt in enumerate(prompts):
        solo = BatchGenerator(CFG, params, settings=settings, dp=1,
                              block_size=4, kv_quant="int8")
        solo.set_prompts([prompt], stream_ids=[i])
        assert got[i] == solo.generate(8)[0]

    # finish stream 2 artificially, then admit a new prompt into its slot
    g.streams[2].done = True
    slot, first = g.admit([2, 8, 1], stream_id=9)
    assert slot == 2
    outs = [g.step() for _ in range(8)]
    admitted = [first.id] + [r[2].id for r in outs if r[2] is not None]
    solo = BatchGenerator(CFG, params, settings=settings, dp=1,
                          block_size=4, kv_quant="int8")
    solo.set_prompts([[2, 8, 1]], stream_ids=[9])
    want = solo.generate(len(admitted))[0][: len(admitted)]
    assert admitted == want


def test_hbm_budget_prices_int8_kv():
    from cake_tpu.utils.memory import hbm_budget

    cfg = tiny(max_seq_len=4096)
    bf = hbm_budget(cfg, batch=32, max_seq=4096)["kv_cache"]
    q8 = hbm_budget(cfg, batch=32, max_seq=4096,
                    cache_bytes_per_el=1)["kv_cache"]
    assert q8 < 0.75 * bf
    # scales are priced: strictly more than the bare int8 bytes
    assert q8 > bf / 2 * 0.99
