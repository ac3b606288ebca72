"""Parity tests: Pallas kernels vs the pure-JAX reference math.

Kernels run in interpret mode on the CPU test mesh (conftest forces
``jax_platforms=cpu``); the pure-JAX ops in :mod:`cake_tpu.ops` are the
oracle (themselves golden-tested against HF transformers in
test_hf_parity.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.ops.attention import attend
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.pallas import flash_attention, flash_decode


def _qkv(key, b, h, kvh, t, s, d, dtype=jnp.float32, pos=0):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, t, d), dtype)
    # Fill the cache only up to the causal frontier; beyond it is garbage
    # that both impls must mask out identically.
    k_all = jax.random.normal(kk, (b, kvh, s, d), dtype)
    v_all = jax.random.normal(kv, (b, kvh, s, d), dtype)
    return q, k_all, v_all


@pytest.mark.parametrize("pos", [0, 3])
@pytest.mark.parametrize("group", [1, 4])
def test_flash_prefill_matches_xla(pos, group):
    b, kvh, t, s, d = 2, 2, 8, 32, 16
    h = kvh * group
    q, k_all, v_all = _qkv(jax.random.PRNGKey(0), b, h, kvh, t, s, d, pos=pos)
    ref = attend(q, k_all, v_all, pos)
    out = flash_attention(q, k_all, v_all, pos, block_q=4, block_k=8,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_prefill_ignores_future_kv():
    """KV content beyond the causal frontier must not affect the output."""
    b, kvh, group, t, s, d = 1, 2, 2, 4, 16, 8
    h = kvh * group
    pos = 2
    q, k_all, v_all = _qkv(jax.random.PRNGKey(1), b, h, kvh, t, s, d)
    out1 = flash_attention(q, k_all, v_all, pos, block_q=2, block_k=4,
                           interpret=True)
    frontier = pos + t
    k_poison = k_all.at[:, :, frontier:].set(1e6)
    v_poison = v_all.at[:, :, frontier:].set(-1e6)
    out2 = flash_attention(q, k_poison, v_poison, pos, block_q=2, block_k=4,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


@pytest.mark.parametrize("pos", [0, 3])
@pytest.mark.parametrize("group", [1, 4])
def test_flash_prefill_q8_matches_dequant_oracle(pos, group):
    """Int8-KV flash kernel vs the XLA path over trace-level-dequantized
    buffers — identical quantized inputs, so the only difference is
    accumulation order."""
    from cake_tpu.ops.kvcache import dequant_kv, quant_kv
    from cake_tpu.ops.pallas import flash_attention_q8

    b, kvh, t, s, d = 2, 2, 8, 32, 16
    h = kvh * group
    q, k_all, v_all = _qkv(jax.random.PRNGKey(3), b, h, kvh, t, s, d)
    kq, vq = quant_kv(k_all), quant_kv(v_all)
    ref = attend(q, dequant_kv(kq, q.dtype), dequant_kv(vq, q.dtype), pos,
                 impl="xla")
    out = flash_attention_q8(q, kq.q, kq.scale, vq.q, vq.scale, pos,
                             block_q=4, block_k=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_prefill_q8_ignores_future_kv():
    from cake_tpu.ops.kvcache import quant_kv
    from cake_tpu.ops.pallas import flash_attention_q8

    b, kvh, group, t, s, d = 1, 2, 2, 4, 16, 8
    h = kvh * group
    pos = 2
    q, k_all, v_all = _qkv(jax.random.PRNGKey(4), b, h, kvh, t, s, d)
    kq, vq = quant_kv(k_all), quant_kv(v_all)
    out1 = flash_attention_q8(q, kq.q, kq.scale, vq.q, vq.scale, pos,
                              block_q=2, block_k=4, interpret=True)
    frontier = pos + t
    kq2 = quant_kv(k_all.at[:, :, frontier:].set(1e6))
    vq2 = quant_kv(v_all.at[:, :, frontier:].set(-1e6))
    out2 = flash_attention_q8(q, kq2.q, kq2.scale, vq2.q, vq2.scale, pos,
                              block_q=2, block_k=4, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


def test_int8_kv_long_prefill_routes_to_q8_kernel(monkeypatch):
    """With an int8 cache and a flash-regime window, self_attention_block
    dispatches the quantization-aware kernel (never plain flash, whose
    operand would be a materialized bf16 KV buffer)."""
    import cake_tpu.ops.attention as attn
    from cake_tpu.ops import pallas as pk
    from cake_tpu.ops.attention import PREFILL_FLASH_MIN_S, PREFILL_FLASH_MIN_T

    monkeypatch.setattr(pk, "kernels_enabled", lambda: True)
    monkeypatch.setattr(pk, "force_kernels", lambda: False)
    monkeypatch.setattr(pk, "interpret_default", lambda: True)
    calls = []
    monkeypatch.setattr(
        attn.pk, "flash_attention_q8",
        lambda q, kq, ks, vq, vs, pos, **kw: (calls.append("q8"), q)[1])
    from cake_tpu.ops.kvcache import init_cache
    from cake_tpu.models.config import tiny

    cfg = tiny(max_seq_len=PREFILL_FLASH_MIN_S)
    cache = init_cache(cfg, batch=1, max_seq=PREFILL_FLASH_MIN_S,
                       quant="int8")
    x = jnp.zeros((1, PREFILL_FLASH_MIN_T, cfg.hidden_size), jnp.bfloat16)
    wq = jnp.zeros((cfg.hidden_size,
                    cfg.num_attention_heads * cfg.head_dim), jnp.bfloat16)
    wkv = jnp.zeros((cfg.hidden_size,
                     cfg.num_key_value_heads * cfg.head_dim), jnp.bfloat16)
    wo = jnp.zeros((cfg.num_attention_heads * cfg.head_dim,
                    cfg.hidden_size), jnp.bfloat16)
    from cake_tpu.ops.rope import rope_tables

    cos, sin = rope_tables(cfg.head_dim, PREFILL_FLASH_MIN_S,
                           cfg.rope_theta)
    attn.self_attention_block(
        x, wq, wkv, wkv, wo, jax.tree.map(lambda a: a[0], cache.k),
        jax.tree.map(lambda a: a[0], cache.v), cos, sin, jnp.int32(0),
        cfg.num_attention_heads, cfg.num_key_value_heads,
    )
    assert calls == ["q8"]


@pytest.mark.parametrize("pos", [0, 5, 30])
@pytest.mark.parametrize("group", [1, 4])
def test_flash_decode_matches_xla(pos, group):
    b, kvh, s, d = 1, 2, 32, 16
    h = kvh * group
    q, k_all, v_all = _qkv(jax.random.PRNGKey(2), b, h, kvh, 1, s, d)
    ref = attend(q, k_all, v_all, pos)
    out = flash_decode(q, k_all, v_all, pos, block_k=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_decode_bf16():
    b, kvh, group, s, d = 1, 2, 4, 32, 16
    h = kvh * group
    q, k_all, v_all = _qkv(jax.random.PRNGKey(3), b, h, kvh, 1, s, d,
                           dtype=jnp.bfloat16)
    ref = attend(q, k_all, v_all, 7)
    out = flash_decode(q, k_all, v_all, 7, block_k=8, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_flash_decode_per_row_positions():
    """pos [B]: each batch row attends to its own causal frontier (the
    multi-stream serving path) — parity with per-row XLA attention and with
    per-row single-stream kernel calls."""
    b, kvh, group, s, d = 3, 2, 2, 32, 16
    h = kvh * group
    q, k_all, v_all = _qkv(jax.random.PRNGKey(6), b, h, kvh, 1, s, d)
    pos = jnp.asarray([2, 17, 30], jnp.int32)
    out = flash_decode(q, k_all, v_all, pos, block_k=8, interpret=True)
    ref = attend(q, k_all, v_all, pos, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    for i in range(b):
        one = flash_decode(q[i:i + 1], k_all[i:i + 1], v_all[i:i + 1],
                           int(pos[i]), block_k=8, interpret=True)
        np.testing.assert_allclose(np.asarray(out[i:i + 1]), np.asarray(one),
                                   rtol=1e-5, atol=1e-5)


def test_flash_under_jit_static_pos_variants():
    """pos is a traced scalar: one compile serves every position."""
    b, kvh, group, s, d = 1, 1, 2, 16, 8
    h = kvh * group
    q, k_all, v_all = _qkv(jax.random.PRNGKey(4), b, h, kvh, 1, s, d)

    @jax.jit
    def step(q, k, v, pos):
        return flash_decode(q, k, v, pos, block_k=4, interpret=True)

    for pos in (0, 3, 11):
        ref = attend(q, k_all, v_all, pos)
        out = step(q, k_all, v_all, jnp.int32(pos))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)




def test_generator_greedy_parity_with_kernels(monkeypatch, tiny_config, tiny_params):
    """End-to-end: the full generator produces identical greedy tokens with
    Pallas kernels forced on (interpreted) vs the XLA path."""
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.generator import LlamaGenerator

    prompt = [1, 5, 9, 2]

    def run():
        gen = LlamaGenerator(
            tiny_config, tiny_params,
            settings=SamplerSettings(temperature=0.0), max_seq=64,
        )
        gen.set_prompt(prompt)
        return [gen.next_token(i).id for i in range(6)]

    monkeypatch.setenv("CAKE_PALLAS", "0")
    ids_xla = run()
    monkeypatch.setenv("CAKE_PALLAS", "1")
    ids_flash = run()
    assert ids_xla == ids_flash


def test_dispatch_policy(monkeypatch):
    from cake_tpu.ops import pallas as pk

    monkeypatch.setenv("CAKE_PALLAS", "0")
    assert not pk.kernels_enabled()
    monkeypatch.setenv("CAKE_PALLAS", "1")
    assert pk.kernels_enabled()
    assert pk.force_kernels()
    monkeypatch.setenv("CAKE_PALLAS", "auto")
    assert not pk.force_kernels()
    assert pk.kernels_enabled() == (jax.default_backend() == "tpu")


def test_auto_dispatch_measured_crossover(monkeypatch):
    """impl='auto' follows the measured crossover (tools/flash_sweep.py on
    v5e): prefill routes to flash only from S >= PREFILL_FLASH_MIN_S;
    short-context prefill and decode at a head size the kernel does not
    serve run XLA (what decides decode:
    test_attend_picks_the_decode_kernel_by_what_it_sees).
    CAKE_PALLAS=1 still forces the kernels everywhere."""
    import cake_tpu.ops.attention as attn
    from cake_tpu.ops import pallas as pk
    from cake_tpu.ops.attention import (
        PREFILL_FLASH_MIN_S,
        PREFILL_FLASH_MIN_T,
        attend,
    )

    monkeypatch.setattr(pk, "kernels_enabled", lambda: True)
    monkeypatch.setattr(pk, "force_kernels", lambda: False)
    monkeypatch.setattr(pk, "interpret_default", lambda: True)
    calls = []
    monkeypatch.setattr(
        attn.pk, "flash_attention",
        lambda q, k, v, pos, **kw: (calls.append("prefill"), q)[1])
    monkeypatch.setattr(
        attn.pk, "flash_decode",
        lambda q, k, v, pos, **kw: (calls.append("decode"), q)[1])

    b, h, kvh, d = 1, 2, 1, 8
    key = jax.random.PRNGKey(0)

    def run(t, s):
        q = jax.random.normal(key, (b, h, t, d), jnp.bfloat16)
        k = jax.random.normal(key, (b, kvh, s, d), jnp.bfloat16)
        v = jax.random.normal(key, (b, kvh, s, d), jnp.bfloat16)
        attend(q, k, v, jnp.int32(s - t - 1))

    run(PREFILL_FLASH_MIN_T, PREFILL_FLASH_MIN_S)  # long prefill -> flash
    assert calls == ["prefill"]
    calls.clear()
    run(PREFILL_FLASH_MIN_T, PREFILL_FLASH_MIN_S // 2)  # short -> XLA
    run(8, PREFILL_FLASH_MIN_S)  # tiny T (speculative verify) -> XLA
    run(1, 4096)  # decode at D = 8 -> XLA at any S
    assert calls == []
    monkeypatch.setattr(pk, "force_kernels", lambda: True)
    run(1, 512)  # forced -> flash decode regardless of the crossover
    assert calls == ["decode"]


def _row_frontiers_against_xla(kvh, group, s, d, bk, frontiers, window=None,
                               stacked=True, layers=3, **kernel):
    """The decode kernel at per-row ``frontiers`` against the XLA oracle,
    bf16, on the stacked ``[L, B, KVH, S, D]`` cache with a TRACED layer
    index (or one layer's own buffer).

    The kernel reads the blocks of ``decode_block_range`` (what the
    engine's ``attn.kv_blocks_*`` counters sum) and no others: every
    block outside a stream's range, and the stacked form's other layers,
    hold NaN in the kernel's copy of the cache (a fetched NaN survives
    the mask: 0 x NaN), and every block inside the range holds a row the
    oracle attends."""
    from cake_tpu.ops.attention import _attend_xla
    from cake_tpu.ops.pallas import decode_block_range

    b, h = len(frontiers), kvh * group
    pos = jnp.asarray(frontiers, jnp.int32)
    q, k_one, v_one = _qkv(jax.random.PRNGKey(11), b, h, kvh, 1, s, d,
                           dtype=jnp.bfloat16)
    ref = _attend_xla(q, k_one, v_one, pos, window=window)
    lo, hi = decode_block_range(np.asarray(frontiers), bk, s // bk, window,
                                xp=np)
    assert ((hi - lo + 1) >= 1).all()
    block = np.arange(s) // bk
    counted = (block >= lo[:, None]) & (block <= hi[:, None])  # [B, S]
    counted = jnp.asarray(counted)[:, None, :, None]
    k_one = jnp.where(counted, k_one, jnp.nan)
    v_one = jnp.where(counted, v_one, jnp.nan)
    if stacked:
        k_all = jnp.full((layers,) + k_one.shape, jnp.nan, k_one.dtype)
        v_all = jnp.full((layers,) + v_one.shape, jnp.nan, v_one.dtype)

        @jax.jit
        def run(layer):
            return flash_decode(
                q, k_all.at[layer].set(k_one), v_all.at[layer].set(v_one),
                pos, layer=layer, block_k=bk, window=window, interpret=True,
                **kernel)

        out = run(jnp.int32(1))
    else:
        out = flash_decode(q, k_one, v_one, pos, block_k=bk, window=window,
                           interpret=True, **kernel)
    assert out.dtype == q.dtype and out.shape == q.shape
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("group", [4, 1])
@pytest.mark.parametrize("window", [None, 200])
@pytest.mark.parametrize("stacked", [True, False])
def test_flash_decode_row_frontiers_on_carried_cache(stacked, window, group):
    """The decode kernel (all KV heads of a stream's block at once)
    against the XLA oracle (:func:`_row_frontiers_against_xla`): on the
    stacked cache the layer loop carries and on one layer's own buffer;
    per-row frontiers at the first row, the block's edges, 703 (what
    ``decode-full`` fills at most) and the buffer's end, mixed in one
    batch; with and without a window shorter than the buffer; a group of
    query rows a KV head and ONE (the form whose keys stream)."""
    s, bk = 1024, 128
    _row_frontiers_against_xla(2, group, s, 16, bk,
                               [0, 1, bk - 1, bk, 703, s - 1],
                               window=window, stacked=stacked)


@pytest.mark.parametrize("frontiers", [(0, 127, 128, 255, 256, 767),
                                       (511, 703, 767, 0, 384, 383)])
@pytest.mark.parametrize("bk", [None, 128, 256, 384])
def test_flash_decode_one_query_row_at_the_looped_cells_shape(bk, frontiers):
    """The one-query-row form at the shape ``ouro-2p6b.decode-full``
    serves (KVH 16 x G 1, D 128, 6 slots x 768 rows, the plane's index
    traced): every block size of the sweep and the one
    ``decode_block_k`` gives this row of heads, frontiers at the first
    row, at every block's two edges, at row 703 (what a stream of the
    cell fills at most) and at the buffer's end, mixed in one batch."""
    from cake_tpu.ops.pallas import decode_block_k

    kvh, s, d = 16, 768, 128
    bk = bk or decode_block_k(s, kvh, d, 2, 1)
    _row_frontiers_against_xla(kvh, 1, s, d, bk, list(frontiers), layers=2)


@pytest.mark.parametrize("stacked", [True, False])
@pytest.mark.parametrize("bk, window, form", [
    (None, None, {}), (128, None, {}), (256, 300, {}), (512, 300, {}),
    (256, None, {"batched": True}),
])
def test_flash_decode_heads_of_64_two_to_a_lane_tile(bk, window, form,
                                                     stacked):
    """Heads HALF a lane tile wide at the shape ``lfm2-8b-a1b-cut``
    serves (KVH 8 x G 4, D 64): the kernel is handed the cache's rows as
    columns, ``[.., KVH / 2, 128, S]``, and the query rows of a pair of
    heads block-diagonal, and gives what XLA's attention gives over
    ``[.., KVH, S, 64]``; on the stacked cache and on one layer's buffer,
    at every block of the sweep and the one ``decode_block_k`` gives the
    shape, frontiers at row 0, a block's two edges, row 703 and the
    buffer's last row mixed in one batch, with and without a window,
    the heads a pair at a time and in one batched call."""
    from cake_tpu.ops.pallas import decode_block_k

    kvh, group, s, d = 8, 4, 1024, 64
    bk = bk or decode_block_k(s, kvh, d, 2, group)
    _row_frontiers_against_xla(kvh, group, s, d, bk,
                               [0, 1, bk - 1, bk, 703, s - 1],
                               window=window, stacked=stacked, layers=2,
                               **form)


@pytest.mark.parametrize("pos,steps,window,want", [
    ([0, 1, 511, 512, 703, 2047], 1, None, (1 + 1 + 1 + 2 + 2 + 4, 24)),
    ([300], 8, None, (8, 32)),  # one block a step
    ([508], 8, None, (4 + 4 * 2, 32)),  # crosses into the second block
    ([5000], 2, None, (8, 8)),  # a retired slot past the buffer: all of it
    ([600], 8, 100, (16, 32)),  # window inside blocks 0-1
    ([1300], 1, 200, (1, 4)),  # window inside block 2 alone
    ([5000], 2, 100, (2, 8)),  # window past the buffer: the last block
])
def test_decode_blocks_read_counts_what_the_kernel_fetches(pos, steps,
                                                           window, want):
    """The engine's ``attn.kv_blocks_*`` arithmetic (host side) against
    the kernel's own block range: per slot and step, the blocks from the
    window's lower bound to the frontier, clamped into the buffer."""
    from cake_tpu.ops.pallas import decode_blocks_read

    assert decode_blocks_read(pos, steps, 2048, 512, window) == want


@pytest.mark.parametrize("bk,pos,steps,want", [
    # 6 slots x 768 rows, as ``ouro-2p6b.decode-full`` holds them
    (128, [0, 127, 128, 383, 384, 703], 1, (1 + 1 + 2 + 3 + 4 + 6, 36)),
    (256, [0, 127, 128, 383, 384, 703], 1, (1 + 1 + 1 + 2 + 2 + 3, 18)),
    (384, [0, 127, 128, 383, 384, 703], 1, (1 + 1 + 1 + 1 + 2 + 2, 12)),
    (384, [380], 8, (4 + 4 * 2, 16)),  # crosses into the second block
    (512, [0, 703], 1, (2, 2)),  # the default's block: one of one, 100%
])
def test_decode_blocks_read_in_the_one_row_forms_blocks(bk, pos, steps,
                                                        want):
    """The same arithmetic in the blocks a 768-row cache is fetched in:
    counted in 512-row blocks (which do not divide it) every frontier
    reads one block of one, whatever the kernel fetches."""
    from cake_tpu.ops.pallas import decode_blocks_read

    assert decode_blocks_read(pos, steps, 768, block_k=bk) == want


def _latent_rows(key, b, h, s, dc, dr, dtype):
    ks = jax.random.split(key, 4)
    return (jax.random.normal(ks[0], (b, h, 1, dc), dtype),
            jax.random.normal(ks[1], (b, h, 1, dr), dtype),
            jax.random.normal(ks[2], (b, 1, s, dc), dtype),
            jax.random.normal(ks[3], (b, 1, s, dr), dtype))


def _masked_oracle(q_c, q_pe, c_one, r_one, pos, scale):
    """``ops/mla.py``'s XLA form: the whole buffer, masked at ``pos [B]``:
    row maximum, normalizer and un-normalized latent output."""
    from cake_tpu.ops.mla import masked_sweep

    s = c_one.shape[2]
    valid = (jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, s), 3)
             <= pos[:, None, None, None])
    m, p, o_c = masked_sweep(q_c, q_pe, c_one[:, 0], r_one[:, 0], valid,
                             scale)
    return m, jnp.sum(p, axis=-1, keepdims=True), o_c


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("heads", [64, 32])
@pytest.mark.parametrize("stacked", [True, False])
def test_latent_decode_row_frontiers_on_carried_cache(stacked, heads, dtype):
    """The latent decode kernel against the einsums of ``ops/mla.py``'s
    ``cached()`` (``masked_sweep``), for A.X-K1's 64 and Ling's 32 heads a
    row over the one shared "head" of the latent cache: on the stacked
    ``[L, B, 1, S, dc]`` + ``[L, B, 1, S, dr]`` buffers the layer loop
    carries, with a TRACED layer index, and on one layer's own; per-row
    frontiers at row 0, inside the first block, on a block's edges, across
    several blocks, at the buffer's end, and a dead slot dispatched at row
    0, mixed in one batch. It returns what ``cached()`` hands on: row
    maximum, normalizer and the UN-normalized float32 latent output.

    The kernel reads the blocks of ``decode_block_range`` (what the
    engine's ``attn.kv_blocks_*`` counters sum) and no others: every block
    past a stream's frontier block, and the stacked form's other layers,
    hold NaN in the kernel's copy of both buffers (a fetched NaN survives
    the mask: 0 x NaN). In float32 a stream inside one block differs from
    the einsums only in the order of a product's sums."""
    from cake_tpu.ops.pallas import decode_block_range, latent_decode

    layers, s, dc, dr, bk = 3, 1024, 128, 64, 128
    frontiers = [0, 1, 77, bk - 1, bk, 3 * bk + 5, 703, s - 1, 0]
    b = len(frontiers)
    pos = jnp.asarray(frontiers, jnp.int32)
    scale = 0.07
    q_c, q_pe, c_one, r_one = _latent_rows(jax.random.PRNGKey(13), b, heads,
                                           s, dc, dr, dtype)
    m_ref, l_ref, o_ref = _masked_oracle(q_c, q_pe, c_one, r_one, pos, scale)
    lo, hi = decode_block_range(np.asarray(frontiers), bk, s // bk, None,
                                xp=np)
    assert (lo == 0).all() and hi.max() == s // bk - 1 and hi.min() == 0
    counted = jnp.asarray(np.arange(s) // bk <= hi[:, None])[:, None, :, None]
    c_one = jnp.where(counted, c_one, jnp.nan)
    r_one = jnp.where(counted, r_one, jnp.nan)
    if stacked:
        c_all = jnp.full((layers,) + c_one.shape, jnp.nan, dtype)
        r_all = jnp.full((layers,) + r_one.shape, jnp.nan, dtype)

        @jax.jit
        def run(layer):
            return latent_decode(
                q_c[:, :, 0], q_pe[:, :, 0], c_all.at[layer].set(c_one),
                r_all.at[layer].set(r_one), pos, scale=scale, layer=layer,
                block_k=bk, interpret=True)

        m, l, o_c = run(jnp.int32(1))
    else:
        m, l, o_c = latent_decode(q_c[:, :, 0], q_pe[:, :, 0], c_one, r_one,
                                  pos, scale=scale, block_k=bk,
                                  interpret=True)
    assert m.shape == l.shape == (b, heads, 1, 1)
    assert o_c.shape == (b, heads, 1, dc)
    assert m.dtype == l.dtype == o_c.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(m), np.asarray(m_ref))
    exact = dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(l), np.asarray(l_ref),
                               rtol=1e-5 if exact else 2e-3)
    np.testing.assert_allclose(
        np.asarray(o_c / l), np.asarray(o_ref / l_ref),
        rtol=0, atol=2e-5 if exact else 2e-2)


@pytest.mark.parametrize("frontier", [0, 300, 2047])
def test_latent_decode_scalar_frontier(frontier):
    """A scalar frontier (the single-stream generators') is every row's,
    at the served block of 512 rows: inside the first block, and at the
    buffer's end."""
    from cake_tpu.ops.pallas import latent_decode

    b, heads, s, dc, dr, scale = 2, 8, 2048, 128, 64, 0.1
    q_c, q_pe, c_one, r_one = _latent_rows(jax.random.PRNGKey(17), b, heads,
                                           s, dc, dr, jnp.float32)
    want = _masked_oracle(q_c, q_pe, c_one, r_one,
                          jnp.full((b,), frontier, jnp.int32), scale)
    got = latent_decode(q_c[:, :, 0], q_pe[:, :, 0], c_one, r_one,
                        jnp.int32(frontier), scale=scale, interpret=True)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5,
                                   atol=2e-5)


LATENT_DISPATCH = {
    # name: (rows, kv_lora_rank, rope, CAKE_PALLAS, on the chip) -> expected
    "axk1_and_ling_cells": ((4096, 512, 64, None, True), "kernel"),
    "at_the_floor": ((1024, 512, 64, None, True), "kernel"),
    "below_the_floor": ((512, 512, 64, None, True), "xla"),
    "not_whole_blocks": ((4096 + 128, 512, 64, None, True), "xla"),
    "latent_rows_off_the_lanes": ((4096, 192, 64, None, True), "xla"),
    "rope_half_a_lane_tile_wide": ((4096, 512, 128, None, True), "xla"),
    "off_the_chip": ((4096, 512, 64, None, False), "xla"),
    "kernels_off": ((4096, 512, 64, "0", True), "xla"),
    "forced_below_the_floor": ((512, 512, 64, "1", True), "kernel"),
    "forced_interpreted_any_shape": ((64, 16, 8, "1", False), "kernel"),
    "forced_unserved_on_the_chip": ((64, 16, 8, "1", True), "xla"),
}


@pytest.mark.parametrize("case", list(LATENT_DISPATCH))
def test_latent_decode_choice_by_what_it_sees(case, monkeypatch):
    """``ops.mla.latent_decode_choice``: whole 512-row blocks from
    ``LATENT_DECODE_MIN_S`` rows up, latent rows that fill their lanes and
    a rope half under a lane tile (what the chip stores rows-on-lanes)
    take the kernel on the chip; nothing but the shapes and the kernels'
    switch decides."""
    from cake_tpu.ops import mla
    from cake_tpu.ops import pallas as pk

    (s, dc, dr, env, chip), want = LATENT_DISPATCH[case]
    if env is None:
        monkeypatch.delenv("CAKE_PALLAS", raising=False)
    else:
        monkeypatch.setenv("CAKE_PALLAS", env)
    monkeypatch.setattr(pk, "on_tpu", lambda: chip)
    assert mla.latent_decode_choice(s, dc, dr) == want


DECODE_DISPATCH = {
    # name: (T, per-row frontiers, S, D, KV heads, int8 cache, stacked)
    #       -> expected
    "served_stacked": ((1, True, 2048, 128, 8, False, True), "decode"),
    "served_one_layer": ((1, True, 4096, 128, 8, False, False), "decode"),
    "scalar_frontier": ((1, False, 2048, 128, 8, False, True), "decode"),
    "at_the_floor": ((1, True, 1024, 128, 8, False, True), "decode"),
    "local_heads_of_a_tp_mesh": ((1, True, 2048, 128, 2, False, True),
                                 "decode"),
    "int8_cache": ((1, True, 2048, 128, 8, True, True), "xla"),
    "per_row_chunk": ((8, True, 2048, 128, 8, False, True), "xla"),
    # heads of 64 go two to a lane tile (PR 52); an odd number of them,
    # heads of 32 and a chunk over heads of 64 stay on XLA
    "head_64": ((1, True, 2048, 64, 8, False, True), "decode"),
    "head_64_one_layer": ((1, True, 2048, 64, 8, False, False), "decode"),
    "head_64_odd_kv_heads": ((1, True, 2048, 64, 3, False, True), "xla"),
    "head_64_below_the_floor": ((1, True, 512, 64, 8, False, True), "xla"),
    "head_32": ((1, True, 2048, 32, 8, False, True), "xla"),
    "below_the_floor": ((1, True, 512, 128, 8, False, True), "xla"),
    "not_whole_blocks": ((1, True, 2048 + 128, 128, 8, False, True), "xla"),
    # rows of heads whose 512-row blocks overflow VMEM: llama2_7b's 32 x
    # 128 (also at batch 1, a single stream) and gemma_7b's 16 x 256
    "mha_32_heads": ((1, True, 2048, 128, 32, False, True), "xla"),
    "mha_32_heads_one_stream": ((1, False, 4096, 128, 32, False, True),
                                "xla"),
    "heads_of_256": ((1, True, 2048, 256, 16, False, True), "xla"),
}


@pytest.mark.parametrize("case", list(DECODE_DISPATCH))
def test_attend_picks_the_decode_kernel_by_what_it_sees(case, monkeypatch):
    """``attend`` under ``auto`` on the chip (``pk.on_tpu`` steered here,
    in the test): single-token attention over a plain cache with a
    128-multiple head, whole blocks of ``DECODE_BLOCK_K`` rows that fit
    the kernel's VMEM and at least ``DECODE_FLASH_MIN_S`` rows takes the
    kernel, handed the stacked buffers and the layer index themselves; an
    int8 cache, a per-row chunk (speculation verify), a short cache, a
    ragged one and a row of heads too wide for VMEM stay on XLA. A head of
    64 takes the kernel too where the KV heads pair up (two to a lane
    tile), from its own floor; an odd number of them, and narrower heads,
    stay on XLA. Nothing but the input decides, and what was decided for the
    program being traced is published (``attn.decode_kernel``)."""
    import cake_tpu.ops.attention as attn
    from cake_tpu.obs import metrics
    from cake_tpu.ops import pallas as pk
    from cake_tpu.ops.kvcache import QuantizedKV

    (t, per_row, s, d, kvh, int8, stacked), want = DECODE_DISPATCH[case]
    monkeypatch.delenv("CAKE_PALLAS", raising=False)
    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    calls = []

    def kernel(q, k, v, pos, layer=None, window=None):
        calls.append(("decode", k.shape, layer is not None))
        return q

    monkeypatch.setattr(attn.pk, "flash_decode", kernel)
    monkeypatch.setattr(
        attn, "_attend_xla",
        lambda q, k, v, pos, window=None: (calls.append(("xla", k.shape)),
                                           q)[1])
    layers, b, h = 2, 2, 2 * kvh
    shape = ((layers,) if stacked else ()) + (b, kvh, s, d)
    if int8:
        buf = QuantizedKV(q=jnp.zeros(shape, jnp.int8),
                          scale=jnp.ones(shape[:-1], jnp.float32))
    else:
        buf = jnp.zeros(shape, jnp.bfloat16)
    q = jnp.zeros((b, h, t, d), jnp.bfloat16)
    pos = jnp.zeros((b,), jnp.int32) if per_row else jnp.int32(0)
    layer = jnp.int32(1) if stacked else None
    gauge = metrics.registry().gauge("attn.decode_kernel")
    gauge.set(-1)
    jax.eval_shape(lambda: attn.attend(q, buf, buf, pos, layer=layer))
    if want == "decode":
        assert calls == [("decode", shape, stacked)]
    else:  # XLA reads one layer's view, whatever came in
        assert calls == [("xla", (b, kvh, s, d))]
    # a decode trace says which attention it took; a chunk's says nothing
    assert gauge.value == (-1 if t > 1 else int(want == "decode"))
    if not int8:  # the policy for a plain cache, whatever T the caller has
        assert attn.flash_decode_choice(s, d, kvh, h // kvh) == (
            "flash" if want == "decode" or t > 1 else "xla")


NARROW_BLOCK, NARROW_FLOOR = 512, 1024  # PR 52's sweep, ops/attention.py

DECODE_POLICY = {
    # the decode shape of every accepted cell that attends through
    # ``ops/attention.py``: (rows, KV heads, group, head size) -> (choice,
    # rows of the kernel's block). Every row with a head of 128 or 256 is
    # what PR 50's tree answered (the grouped ones PR 47's);
    # ``axk1-ep16-cut``, ``ling3flash-ep4-cut`` and ``xing4-29b-cut``
    # attend through ``ops/mla.py`` / ``ops/kda.py`` and never ask.
    "mistral7b-int8": ((2048, 8, 4, 128), ("flash", 512)),
    "mixtral8x7b-cut": ((4096, 8, 4, 128), ("flash", 512)),
    "jamba2-3b": ((2048, 1, 20, 128), ("flash", 512)),
    "kexaone-ep8-cut_full_layer": ((4096, 8, 8, 128), ("flash", 512)),
    # heads of 64 (PR 52): XLA until then; the kernel takes them two to a
    # lane tile, in the block and from the floor its own sweep gave
    "lfm2-8b-a1b-cut": ((2048, 8, 4, 64), ("flash", NARROW_BLOCK)),
    "heads_of_64_at_the_floor": ((NARROW_FLOOR, 8, 4, 64),
                                 ("flash", NARROW_BLOCK)),
    "heads_of_64_under_the_floor": ((NARROW_FLOOR // 2, 8, 4, 64),
                                    ("xla", NARROW_FLOOR // 2)),
    "heads_of_64_tinyllama": ((2048, 4, 8, 64), ("flash", NARROW_BLOCK)),
    "heads_of_64_odd_kv_heads": ((2048, 3, 3, 64), ("xla", 512)),
    "heads_of_64_one_pair": ((2048, 2, 7, 64), ("xla", NARROW_BLOCK)),
    "heads_of_32": ((2048, 8, 4, 32), ("xla", 512)),
    # rows of heads beside the cells', as they were: a tp=2 mesh's local
    # heads, a group at 768 rows (256-row blocks: XLA), an MHA 7B's 32 x
    # 128 and Gemma-7B's 16 x 256 (blocks that overflow VMEM shrink: XLA)
    "local_heads_of_a_tp_mesh": ((2048, 4, 4, 128), ("flash", 512)),
    "a_group_at_768_rows": ((768, 8, 2, 128), ("xla", 256)),
    # ONE query row a KV head (PR 50): the kernel's batched form in 128-row
    # blocks, at the looped cell's 768 rows (XLA until then: 256-row blocks
    # of the loop over heads), at 2048 (the loop at 512 rows until then),
    # at an MHA 7B's 32 x 128 and Gemma-7B's 16 x 256 (XLA until then: the
    # loop's blocks overflowed VMEM at 512 rows); not under 768 rows, nor
    # at a row of heads wider than the sweep has
    "ouro-2p6b": ((768, 16, 1, 128), ("flash", 128)),
    "one_row_at_2048": ((2048, 16, 1, 128), ("flash", 128)),
    "mha_32_heads": ((2048, 32, 1, 128), ("flash", 128)),
    "heads_of_256": ((2048, 16, 1, 256), ("flash", 128)),
    "one_row_under_768": ((640, 16, 1, 128), ("xla", 128)),
    "one_row_wider_than_swept": ((2048, 64, 1, 128), ("xla", 128)),
    "one_row_heads_of_64": ((2048, 16, 1, 64), ("xla", 128)),
}


@pytest.mark.parametrize("cell", list(DECODE_POLICY))
def test_decode_policy_of_every_accepted_cell(cell, monkeypatch):
    """``flash_decode_choice`` and ``decode_block_k`` on the chip
    (``pk.on_tpu`` steered here), from the shapes and nothing else: a
    group of query rows a KV head keeps the answer and the 512-row block
    it had; ONE row a KV head takes the kernel in its own block, from
    768 rows up and to the widest row of heads the sweep has; a group
    over an even number of 64-wide heads takes it from its own floor."""
    import cake_tpu.ops.attention as attn
    from cake_tpu.ops import pallas as pk

    monkeypatch.delenv("CAKE_PALLAS", raising=False)
    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    (s, kvh, group, d), want = DECODE_POLICY[cell]
    assert (attn.flash_decode_choice(s, d, kvh, group),
            pk.decode_block_k(s, kvh, d, 2, group)) == want


GROUPED_KERNELS = {
    # sha256[:16] of the traced kernel (``jax.make_jaxpr`` of
    # ``flash_decode`` on the stacked cache: the call, its block and its
    # body), taken on PR 47's tree (commit f251505) at the decode shape of
    # every accepted cell that runs it: (slots, rows, heads, KV heads,
    # head size, window)
    "mistral7b-int8": ((8, 2048, 32, 8, 128, 4096), "20617ef0422db597"),
    "mixtral8x7b-cut": ((8, 4096, 32, 8, 128, None), "8eecfc325017978f"),
    "jamba2-3b": ((32, 2048, 20, 1, 128, None), "6c02703a1ae8d65d"),
    "kexaone-ep8-cut_full_layer": ((32, 4096, 64, 8, 128, None),
                                   "7084585ffe2da0ef"),
    "local_heads_of_a_tp_mesh": ((8, 2048, 16, 4, 128, None),
                                 "f02784abd9283111"),
}


@pytest.mark.parametrize("cell", list(GROUPED_KERNELS))
def test_a_group_of_query_rows_keeps_the_kernel_it_had(cell):
    """The one-query-row form (PR 50) is a branch of the decode kernel
    that a group of query rows a KV head never takes: where G >= 2 the
    traced kernel, block and body, is the text PR 47's tree gave, so the
    five cells that run it are measured on the program they had. A PR
    that changes the grouped form on purpose replaces these, and says
    so."""
    import hashlib

    (b, s, h, kvh, d, window), want = GROUPED_KERNELS[cell]

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype)

    text = str(jax.make_jaxpr(
        lambda q, k, v, pos, layer: flash_decode(
            q, k, v, pos, layer=layer, window=window, interpret=False))(
        shape(b, h, 1, d), shape(2, b, kvh, s, d), shape(2, b, kvh, s, d),
        shape(b, dtype=jnp.int32), shape(dtype=jnp.int32)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want


@pytest.mark.parametrize("pos", [0, 5])
@pytest.mark.parametrize("window", [3, 8, 17, 1000])
def test_flash_prefill_windowed_matches_xla(pos, window):
    """Sliding-window flash prefill vs the windowed XLA oracle — windows
    smaller than / spanning / exceeding the block size, and far larger
    than the history (degenerates to full causal)."""
    from cake_tpu.ops.attention import _attend_xla

    b, kvh, group, t, s, d = 2, 2, 4, 8, 32, 16
    h = kvh * group
    q, k_all, v_all = _qkv(jax.random.PRNGKey(2), b, h, kvh, t, s, d,
                           pos=pos)
    ref = _attend_xla(q, k_all, v_all, pos, window=window)
    out = flash_attention(q, k_all, v_all, pos, block_q=4, block_k=8,
                          window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_windowed_skips_out_of_window_blocks():
    """A KV block entirely below the window must not influence the
    output: poison it with NaNs and require a finite, oracle-exact
    result (proves the block skip is real, not just masking)."""
    from cake_tpu.ops.attention import _attend_xla

    b, h, kvh, t, s, d = 1, 2, 2, 4, 32, 16
    pos, window = 20, 4
    q, k_all, v_all = _qkv(jax.random.PRNGKey(3), b, h, kvh, t, s, d)
    # rows [0, 8) are >= window behind every query (frontier 20..23):
    # two full 8-wide blocks below the lower bound
    k_all = k_all.at[:, :, :8, :].set(jnp.nan)
    v_all = v_all.at[:, :, :8, :].set(jnp.nan)
    out = flash_attention(q, k_all, v_all, pos, block_q=4, block_k=8,
                          window=window, interpret=True)
    assert bool(jnp.isfinite(out).all())
    ref = _attend_xla(
        q, jnp.nan_to_num(k_all), jnp.nan_to_num(v_all), pos, window=window
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_windowed_prefill_dispatch(monkeypatch):
    """attend() with a window routes long prefill to the flash kernel at
    the measured crossover and decode/per-row to XLA."""
    import cake_tpu.ops.attention as A

    calls = []
    real = A.pk.flash_attention

    def spy(*a, **kw):
        calls.append(kw.get("window"))
        return real(*a, interpret=True, **kw)

    monkeypatch.setattr(A.pk, "flash_attention", spy)
    monkeypatch.setattr(A.pk, "kernels_enabled", lambda: True)
    monkeypatch.setattr(A, "PREFILL_FLASH_MIN_S", 32)
    monkeypatch.setattr(A, "PREFILL_FLASH_MIN_T", 8)
    monkeypatch.setattr(A, "_flash_ok", lambda t, s, d: True)
    b, h, kvh, t, s, d = 1, 2, 2, 8, 32, 16
    q, k_all, v_all = _qkv(jax.random.PRNGKey(4), b, h, kvh, t, s, d)
    A.attend(q, k_all, v_all, 0, window=8)
    assert calls == [8]
    # decode with window below the decode kernel's floor: auto stays XLA
    # (and never the prefill kernel)...
    q1 = q[:, :, :1, :]
    xla_out = A.attend(q1, k_all, v_all, 20, window=8)
    assert calls == [8]
    # ...but an explicit impl='flash' reaches the windowed decode kernel
    flash_out = A.attend(q1, k_all, v_all, 20, window=8, impl="flash")
    np.testing.assert_allclose(np.asarray(flash_out), np.asarray(xla_out),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pos", [6, 20, 31])
@pytest.mark.parametrize("window", [3, 8, 17, 1000])
def test_flash_decode_windowed_matches_xla(pos, window):
    from cake_tpu.ops.attention import _attend_xla

    b, kvh, group, s, d = 2, 2, 4, 32, 16
    h = kvh * group
    q, k_all, v_all = _qkv(jax.random.PRNGKey(5), b, h, kvh, 1, s, d)
    ref = _attend_xla(q, k_all, v_all, pos, window=window)
    out = flash_decode(q, k_all, v_all, pos, block_k=8, window=window,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_decode_windowed_per_row_and_block_skip():
    """Per-row frontiers with a window: each row's lower bound is its own;
    NaN-poisoned out-of-window blocks must not leak (real skip)."""
    from cake_tpu.ops.attention import _attend_xla

    b, kvh, group, s, d = 2, 2, 2, 32, 16
    h = kvh * group
    window = 4
    pos = jnp.asarray([20, 29], jnp.int32)
    q, k_all, v_all = _qkv(jax.random.PRNGKey(6), b, h, kvh, 1, s, d)
    # rows far below both windows: blocks [0, 16) dead for both rows
    k_all = k_all.at[:, :, :16, :].set(jnp.nan)
    v_all = v_all.at[:, :, :16, :].set(jnp.nan)
    out = flash_decode(q, k_all, v_all, pos, block_k=8, window=window,
                       interpret=True)
    assert bool(jnp.isfinite(out).all())
    ref = _attend_xla(q, jnp.nan_to_num(k_all), jnp.nan_to_num(v_all), pos,
                      window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [3, 8, 17])
def test_flash_prefill_q8_windowed_matches_dequant_oracle(window):
    """Windowed int8-KV flash prefill vs the windowed XLA path over the
    same quantized buffers (Mistral long-context on the quantized cache)."""
    from cake_tpu.ops.attention import _attend_xla
    from cake_tpu.ops.kvcache import dequant_kv, quant_kv
    from cake_tpu.ops.pallas import flash_attention_q8

    b, kvh, group, t, s, d = 2, 2, 4, 8, 32, 16
    h = kvh * group
    pos = 5
    q, k_all, v_all = _qkv(jax.random.PRNGKey(7), b, h, kvh, t, s, d)
    kq, vq = quant_kv(k_all), quant_kv(v_all)
    ref = _attend_xla(q, dequant_kv(kq, q.dtype), dequant_kv(vq, q.dtype),
                      pos, window=window)
    out = flash_attention_q8(q, kq.q, kq.scale, vq.q, vq.scale, pos,
                             block_q=4, block_k=8, window=window,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# The expert block's sorted form (ops/pallas/moe.py): the fused gate, up
# and SwiGLU call, and the live row tiles' gather and sum.
# ---------------------------------------------------------------------------

MOE_TM = 8  # a small row tile: groups straddle and span tiles at few rows

# group sizes over 4 experts -> what the case is there for
MOE_GROUPS = {
    "straddle": (5, 6, 0, 9),  # groups 1 and 3 lie across a tile's edge
    "long": (20, 0, 3, 1),  # group 0 spans three tiles, group 1 has no row
    "boundary": (4, 4, 6, 2),  # the grouped rows end exactly on a tile's edge
    "none": (0, 0, 0, 0),  # no pair on a held expert at all
    "all": (8, 8, 8, 8),  # every row grouped: every tile live
}


def _moe_tiles(sizes, rows=32):
    from cake_tpu.ops.pallas import group_tiles

    tiles = group_tiles(jnp.asarray(sizes, jnp.int32), rows, MOE_TM)
    assert int(tiles.live[0]) == -(-sum(sizes) // MOE_TM)
    return tiles, np.repeat(np.arange(len(sizes)), sizes)


@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("groups", ["straddle", "long", "boundary"])
def test_grouped_swiglu_is_both_products_and_the_swiglu(groups, kind):
    """Gate, up and the SwiGLU in ONE call: every grouped row is
    ``silu(x @ gate[g]) * (x @ up[g])`` of its group's matrices, both
    products float32 and the result rounded once (to the rows' type),
    whichever tiles a group's rows lie in and however many it spans; the
    whole ``[L, E, ..]`` stacks with a layer's index give what that
    layer's own stacks give. Against float64, and against the two
    separate grouped products it replaces."""
    from cake_tpu.ops.pallas import grouped_matmul, grouped_swiglu
    from cake_tpu.ops.quant import dequantize_linear, quantize_linear

    sizes = MOE_GROUPS[groups]
    tiles, group_of = _moe_tiles(sizes)
    k, n, e = 32, 256, len(sizes)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(ks[0], (32, k)).astype(jnp.bfloat16)
    stacks = [jax.random.normal(key, (2, e, k, n)) / 4 for key in ks[1:]]
    if kind == "int8":
        q = [jax.vmap(jax.vmap(quantize_linear))(w) for w in stacks]
        plain = [np.asarray(dequantize_linear(w, jnp.float32), np.float64)
                 for w in q]
        gate, up = (w.q for w in q)
        scales = dict(gate_scale=q[0].scale, up_scale=q[1].scale)
    else:
        gate, up = (w.astype(jnp.bfloat16) for w in stacks)
        plain = [np.asarray(w, np.float64) for w in (gate, up)]
        scales = {}
    got = grouped_swiglu(x, gate, up, tiles, layer=jnp.int32(1), tm=MOE_TM,
                         block_n=128, interpret=True, **scales)
    assert got.dtype == jnp.bfloat16
    x64 = np.asarray(x, np.float64)
    rows = len(group_of)
    g = np.einsum("rk,rkn->rn", x64[:rows], plain[0][1][group_of])
    u = np.einsum("rk,rkn->rn", x64[:rows], plain[1][1][group_of])
    want = g / (1 + np.exp(-g)) * u
    np.testing.assert_allclose(np.asarray(got, np.float64)[:rows], want,
                               atol=2 ** -7 * np.abs(want).max(), rtol=0)
    # one layer's own stacks: the same call, bit for bit
    alone = grouped_swiglu(
        x, gate[1], up[1], tiles, tm=MOE_TM, block_n=128, interpret=True,
        **{name: s[1] for name, s in scales.items()})
    np.testing.assert_array_equal(np.asarray(alone)[:rows],
                                  np.asarray(got)[:rows])
    # ... and the two float32 products it replaces, the SwiGLU between
    parts = [grouped_matmul(x, w, tiles, layer=jnp.int32(1), tm=MOE_TM,
                            scale=scales.get(name), out_dtype=jnp.float32,
                            block_n=128, interpret=True)
             for w, name in ((gate, "gate_scale"), (up, "up_scale"))]
    two = (jax.nn.silu(parts[0]) * parts[1]).astype(jnp.bfloat16)
    np.testing.assert_allclose(
        np.asarray(two, np.float64)[:rows], np.asarray(got, np.float64)[:rows],
        atol=2 ** -7 * np.abs(want).max(), rtol=0)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("groups", list(MOE_GROUPS))
def test_live_tiles_are_gathered_and_summed_alone(groups, dtype):
    """The rows of the live row tiles are ``x[token]`` exactly, and the sum
    adds each grouped row under its weight into its token's row in
    float32: a token no grouped row names gets exactly zero, and nothing
    past the grouped rows is read (NaN lies there, in the live tile's tail
    and in every tile behind it): with no pair held at all, with every
    pair held, with the grouped rows ending on a tile's edge."""
    from cake_tpu.ops.pallas import combine_rows, gather_rows

    sizes = MOE_GROUPS[groups]
    tiles, group_of = _moe_tiles(sizes)
    total, n, h = len(group_of), 11, 256
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(ks[0], (n, h)).astype(dtype)
    token = jax.random.randint(ks[1], (32,), 0, n - 1)  # row n - 1: unnamed
    weight = jax.random.uniform(ks[2], (32,))
    picked = gather_rows(x, token, tiles, tm=MOE_TM, interpret=True)
    live = int(tiles.live[0]) * MOE_TM
    np.testing.assert_array_equal(np.asarray(picked)[:live],
                                  np.asarray(x)[np.asarray(token)[:live]])
    y = jax.random.normal(ks[3], (32, h))
    y = y.at[total:].set(jnp.nan)  # never written: never read
    got = combine_rows(y, token, weight, tiles, n, out_dtype=dtype,
                       tm=MOE_TM, block_h=128, interpret=True)
    assert got.dtype == dtype and got.shape == (n, h)
    want = np.zeros((n, h))
    for r in range(total):
        want[int(token[r])] += float(weight[r]) * np.asarray(
            y[r], np.float64)
    tol = 2 ** -7 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               atol=tol * max(np.abs(want).max(), 1), rtol=0)
    named = np.zeros(n, bool)
    named[np.asarray(token)[:total]] = True
    assert (np.asarray(got)[~named] == 0).all()


# dtype, width -> is a row a whole number of the chip's tiles of words?
FETCH_WIDTHS = {"bf16": (jnp.bfloat16, 2048, True),
                "f32": (jnp.float32, 1024, True),
                "bf16_2560": (jnp.bfloat16, 2560, False),  # 10 rows of words
                "f32_256": (jnp.float32, 256, False)}


@pytest.mark.parametrize("width", list(FETCH_WIDTHS))
@pytest.mark.parametrize("groups", list(MOE_GROUPS))
def test_live_tiles_are_fetched_by_address(groups, width):
    """The fetch form of the gather is ``x[token]`` on every live tile, BIT
    for bit whatever the bits are (a denormal, an infinity and a NaN lie
    in a fetched row: the one-hot product would smear them over the
    tile), in bfloat16 (two halves of a row packed a word) and float32:
    with no pair held at all (``live`` 0: nothing written), with every
    pair held, with a group across a tile's edge and the grouped rows
    ending on one. Nothing past the live tiles is read: their tokens
    name no row of ``x`` at all. A width whose row is no whole number of
    tiles takes the one-hot product all the same."""
    from cake_tpu.ops.pallas import gather_rows, rows_fetchable
    from cake_tpu.ops.pallas import moe as pm

    dtype, h, fetchable = FETCH_WIDTHS[width]
    assert rows_fetchable(h, dtype) == fetchable
    tiles, _ = _moe_tiles(MOE_GROUPS[groups])
    live, n = int(tiles.live[0]) * MOE_TM, 11
    bits_t = np.uint16 if dtype == jnp.bfloat16 else np.uint32
    x = jax.random.normal(jax.random.PRNGKey(5), (n, h)).astype(dtype)
    if fetchable:
        odd = {np.uint16: [0x0001, 0x8001, 0x7F80, 0xFF80, 0x7FC0, 0x8000],
               np.uint32: [1, 0x80000001, 0x7F800000, 0xFF800000,
                           0x7FC00000, 0x80000000]}[bits_t]
        raw = np.asarray(x).view(bits_t).copy()
        raw[0, : len(odd)] = raw[0, h - len(odd):] = odd
        x = jnp.asarray(raw.view(np.asarray(x).dtype))
    token = jax.random.randint(jax.random.PRNGKey(6), (32,), 0, n)
    token = token.at[0].set(0).at[live:].set(2 ** 30)  # never fetched
    calls = []
    real = pm._fetch_rows
    pm._fetch_rows = lambda *a: calls.append(1) or real(*a)
    try:
        picked = gather_rows(x, token, tiles, fetch=True, tm=MOE_TM,
                             interpret=True)
    finally:
        pm._fetch_rows = real
    assert len(calls) == fetchable
    assert picked.shape == (32, h) and picked.dtype == dtype
    np.testing.assert_array_equal(
        np.asarray(picked)[:live].view(bits_t),
        np.asarray(x)[np.asarray(token)[:live]].view(bits_t))
