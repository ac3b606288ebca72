"""Gated short convolutions beside grouped-query attention (LFM2-MoE's keys:
conv layers that hold the last two inputs of a 3-tap depthwise convolution
and no cache row beside one roped, QK-normed attention layer in three or
four, two leading dense layers, then all the sigmoid-routed experts held,
their choice corrected by a bias, no shared expert, a tied head) against
the plain reference ``cake_tpu/testing/reference_lfm2_moe.py``, on seeded
random weights at tiny widths that keep the published pattern
(``models.config.tiny_lfm2_moe``: ``c c A c c A c c A c``).

Tolerances. Everything here is float32 on the CPU, where XLA's matmuls are
full precision. Program and reference differ in the order of sums only
(the convolution over a cached tail against three shifted copies, grouped
against repeated key/value heads, the dense expert form against a Python
loop over the experts): measured 4e-6 to 8e-6 on logits of magnitude ~1
through ten layers over 96 tokens. ``TIGHT`` is 1e-4, over ten times the
worst; one tap fewer, a missing gate, the routing bias ignored or attention
without rotation each move the logits by 0.2 and more (checked below), so
leaving a piece of the mathematics out fails.
"""

from __future__ import annotations

import importlib.util
import json
import sys
import zlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import families, llama
from cake_tpu.models.config import LlamaConfig, lfm2_8b_a1b, tiny_lfm2_moe
from cake_tpu.obs import metrics
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.rope import rope_tables_for
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.parallel.mesh import validate_shardable
from cake_tpu.testing import reference_lfm2_moe as ref
from cake_tpu.utils.weights import (latent_hf_tensors, load_llama_params,
                                    save_llama_params)

TIGHT = 1e-4
CFG = tiny_lfm2_moe(max_seq_len=256, eos_token_id=-1)
TOKENS = np.random.default_rng(43).integers(3, 250, 96).astype(np.int32)
GREEDY = dict(temperature=0.0, repeat_penalty=1.0)


def _params(cfg=CFG, seed=0):
    """Seeded weights whose norm scales (the heads' q and k norms among
    them) are not all ones and whose routing bias is large enough to
    change choices: what is applied twice, not at all, after the rotation
    or to the weights shows. The head is the embedding."""
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def jitter(path, leaf):
        name = path[-1].key
        k = jax.random.fold_in(  # (crc32: str hashes differ by process)
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
        if name.endswith("norm") or name == "norm_f":
            return leaf * (1.0 + 0.25 * jax.random.uniform(
                k, leaf.shape, minval=-1.0))
        if name == "b_router":
            return 0.1 * jax.random.normal(k, leaf.shape)
        return leaf

    params = jax.tree_util.tree_map_with_path(jitter, params)
    return dict(params, lm_head=params["embed"].T)


@pytest.fixture(scope="module")
def params():
    return _params()


@pytest.fixture(scope="module")
def tensors(params):
    return latent_hf_tensors(params, CFG)


@pytest.fixture(scope="module")
def want(tensors):
    """The reference's logits at every position of TOKENS."""
    return np.asarray(ref.logits(CFG.to_hf_dict(), tensors, TOKENS))


def _logits(params, cfg, tokens, cache, pos, valid=None):
    """Logits at every position of one call, and the cache it leaves."""
    cos, sin = rope_tables_for(cfg, cache.max_seq)
    x = llama.embed_tokens(params, jnp.asarray(tokens), cfg)
    x, cache = llama.forward_layers(params["layers"], x, cache, cos, sin, pos,
                                    cfg, valid=valid)
    x = rms_norm(x, params["norm_f"], cfg.rms_norm_eps)
    return x @ params["lm_head"], cache


_STEP = jax.jit(_logits, static_argnums=(1,))


def _through_the_cache(params, tokens, prefill, chunk, bucket=None,
                       cfg=CFG, cache=None):
    """Logits at every position through the cache: a prefill of
    ``prefill`` tokens in chunks of ``chunk`` (each padded to ``bucket``
    rows, its true length told), then a step a token."""
    if cache is None:
        cache = init_cache(cfg, batch=1, max_seq=256)
    out = []
    for lo in range(0, prefill, chunk):
        n = min(chunk, prefill - lo)
        rows = np.full((1, bucket or chunk), 7, np.int32)
        rows[0, :n] = tokens[lo:lo + n]
        logits, cache = _STEP(params, cfg, rows, cache, jnp.int32(lo),
                              jnp.asarray([n], jnp.int32))
        out.append(np.asarray(logits[0, :n]))
    for i in range(prefill, len(tokens)):
        logits, cache = _STEP(params, cfg, tokens[None, i:i + 1], cache,
                              jnp.asarray([i], jnp.int32))
        out.append(np.asarray(logits[0]))
    return np.concatenate(out), cache


# -- against the reference -----------------------------------------------------

@pytest.mark.parametrize("context, prefill, chunk, bucket", [
    (24, 11, 11, 16),  # a bucket's padding behind the true tokens
    (48, 40, 4, None),  # chunks barely longer than the tail
    (48, 30, 1, None),  # an admission a token at a time: all tail
    (96, 70, 70, 128),  # one padded chunk, then 26 steps
    (96, 64, 32, None),  # two bands: the tail crosses the boundary
], ids=["padded", "chunks-of-4", "chunks-of-1", "one-chunk-padded",
        "bands-of-32"])
def test_prefill_then_decode_through_the_cache_match_reference(
        params, want, context, prefill, chunk, bucket):
    """Prefill (whole, in bands, padded) and then decoding through the
    cache give the reference's full forward pass at every position, in
    logits: the conv layers' tails carry what the convolution needs from
    band to band and step to step, the attention layers' rows the rest."""
    got, cache = _through_the_cache(params, TOKENS[:context], prefill, chunk,
                                    bucket)
    np.testing.assert_allclose(got, want[:context], atol=TIGHT, rtol=0)
    assert cache.state is None and cache.conv.shape == (7, 1, 2, 64)


def test_chunked_admission_equals_whole_admission(params):
    """The tail a band leaves is what the next band convolves: admitting
    64 tokens in bands of 16 leaves the cache that admitting them whole
    leaves (to a product's rounding: a band's rows are another matmul),
    and the same logits behind it."""
    whole, c1 = _through_the_cache(params, TOKENS[:70], 64, 64)
    bands, c2 = _through_the_cache(params, TOKENS[:70], 64, 16)
    np.testing.assert_allclose(np.asarray(c2.conv), np.asarray(c1.conv),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(c2.k), np.asarray(c1.k),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(bands, whole, atol=2e-5, rtol=0)


def test_a_padded_row_never_enters_a_tail(params):
    """A bucket's padding lies behind the true tokens: the tail is taken
    at the true length, so the cache is the same bit for bit WHATEVER the
    padding holds, and (to a product's rounding: other rows, another
    matmul) the one an unpadded chunk leaves."""
    _, plain = _through_the_cache(params, TOKENS[:11], 11, 11)

    def padded_with(token):
        rows = np.full((1, 32), token, np.int32)
        rows[0, :11] = TOKENS[:11]
        return _STEP(params, CFG, rows, init_cache(CFG, 1, 256),
                     jnp.int32(0), jnp.asarray([11], jnp.int32))[1]

    one, other = padded_with(201), padded_with(17)
    np.testing.assert_array_equal(np.asarray(one.conv),
                                  np.asarray(other.conv))
    np.testing.assert_allclose(np.asarray(one.conv), np.asarray(plain.conv),
                               atol=1e-5, rtol=0)
    assert np.abs(np.asarray(one.conv)).sum() > 0


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_a_piece_of_the_mathematics_left_out_fails_the_tolerance(
        tensors, want, wrong):
    """One tap fewer, the gate behind the convolution missing, the routing
    bias ignored, attention without rotation: each moves the logits far
    beyond ``TIGHT``, so the comparison above would not pass a program
    that left it out."""
    bad = np.asarray(ref.logits(CFG.to_hf_dict(), tensors, TOKENS[:48],
                                wrong=wrong))
    assert np.abs(bad - want[:48]).max() > 1000 * TIGHT, wrong


def test_attention_layers_rotate_the_whole_head(params, want):
    """Unlike the window family's full layers, this family's rotate: the
    table covers the whole 16-wide head, and the layer loop hands it to
    the attention segments (the program equals the reference that rotates,
    and the one that does not lies 0.2 and more away: above)."""
    cos, sin = rope_tables_for(CFG, 64)
    assert cos.shape[-1] * 2 == CFG.head_dim == CFG.rope_dim == 16
    got, _ = _through_the_cache(params, TOKENS[:24], 24, 24)
    np.testing.assert_allclose(got, want[:24], atol=TIGHT, rtol=0)


def test_the_mixer_is_the_published_equations(params):
    """``conv_mixer_block`` alone against the three shifted copies, with a
    tail carried in: gates on both sides, no activation, float32 sums."""
    from cake_tpu.ops.shortconv import conv_mixer_block

    layer = {k: v[0] for k, v in params["layers"]["conv_dense"].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 64))
    tail = jax.random.normal(jax.random.PRNGKey(4), (2, 2, 64))
    out, new = conv_mixer_block(x, layer, tail)
    b, c, u = np.split(np.asarray(x @ layer["w_in"]), 3, axis=-1)
    z = np.concatenate([np.asarray(tail), b * u], axis=1)
    w = np.asarray(layer["conv_w"])
    y = sum(z[:, j:j + 9] * w[j] for j in range(3))
    np.testing.assert_allclose(out, (c * y) @ np.asarray(layer["w_out"]),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(new, z[:, -2:], atol=1e-6, rtol=0)
    # a row's true length: the tail stops there
    _, short = conv_mixer_block(x, layer, tail,
                                valid=jnp.asarray([9, 4], jnp.int32))
    np.testing.assert_allclose(short[1], z[1, 4:6], atol=1e-6, rtol=0)
    np.testing.assert_allclose(short[0], z[0, -2:], atol=1e-6, rtol=0)


# -- the engine ------------------------------------------------------------------

def _engine(params, prompts, ids=None, cfg=CFG, **kw):
    from cake_tpu.runtime.batch_generator import BatchGenerator

    kw.setdefault("block_size", 4)
    bg = BatchGenerator(cfg, params, settings=SamplerSettings(**GREEDY),
                        max_seq=256, **kw)
    bg.set_prompts(prompts, stream_ids=ids)
    return bg


def _run(bg, events=(), steps=40):
    """Step the engine; ``events``: ``{step: callable(bg)}``. Returns every
    stream's generated ids by stream id."""
    events = dict(events)
    out: dict[int, list[int]] = {}
    for i in range(steps):
        if i in events:
            events[i](bg)
        bg.step()
        for s in bg.streams:
            if s.active and s.stream_id >= 0:
                out[s.stream_id] = list(s.generated)
    return out


def _is_the_references_argmax(tensors, prompt, out, cfg=CFG):
    """Every token of ``out`` is the single-stream reference's own best
    continuation of what came before it, to ``TIGHT``."""
    full = np.array(list(prompt) + list(out))
    logits = np.asarray(ref.logits(cfg.to_hf_dict(), tensors, full))
    for j, tok in enumerate(out):
        at = logits[len(prompt) - 1 + j]
        assert at.max() - at[tok] <= TIGHT, (len(prompt), j)


_RNG = np.random.default_rng(7)
PROMPTS = [[int(t) for t in _RNG.integers(3, 250, n)]
           for n in (5, 37, 70, 21, 100, 12)]


def test_batch_generator_streams_match_reference(params, tensors):
    """Three streams of different lengths through BatchGenerator: a
    bucketed batch prefill whose padding may not enter a tail, per-row
    positions, block decode; each stream's tokens are the reference's
    argmax. The gauges count the tails where there is no state."""
    reg = metrics.registry()
    bg = _engine(params, PROMPTS[:3])
    outs = bg.generate(27)
    for prompt, out in zip(PROMPTS[:3], outs):
        _is_the_references_argmax(tensors, prompt, list(out)[:27])
    row = 2 * 2 * 16 * 4  # k and v, two heads of 16 float32 values
    tails = 7 * 2 * 64 * 4  # seven conv layers, two rows of 64 float32
    assert reg.gauge("cache.row_bytes").value == row
    assert reg.gauge("cache.state_bytes_per_stream").value == tails
    assert reg.gauge("cache.state_bytes").value == 3 * tails
    assert reg.gauge("cache.bytes").value == 3 * (3 * 256 * row + tails)
    # the CPU pads nothing: what the buffers occupy is what they hold
    assert reg.gauge("cache.device_bytes").value == reg.gauge(
        "cache.bytes").value


def test_streams_match_reference_through_the_decode_kernel(monkeypatch):
    """HEADS OF 64 (the published width; four over two KV heads here):
    with the kernels on (``CAKE_PALLAS=1``, interpreted) the engine's
    decode programs attend through ``flash_decode``, which packs the pair
    of heads into one lane tile and reads the carried cache's rows as
    columns; each stream's tokens stay the reference's argmax, through
    admissions (XLA's attention) and block decode alike. The gauge says
    the kernel is in the program, and the block counters count the rows
    of the block the kernel fetches of THIS shape (128 here, so that the
    256-row window is two): a stream reads the second only once its
    frontier has passed row 127."""
    from cake_tpu.ops.pallas import flash

    monkeypatch.setenv("CAKE_PALLAS", "1")
    monkeypatch.setattr(flash, "NARROW_BLOCK_K", 128)
    cfg = tiny_lfm2_moe(max_seq_len=256, eos_token_id=-1, hidden_size=256,
                        num_attention_heads=4, num_key_value_heads=2)
    assert cfg.head_dim == 64 and flash.narrow_heads(64, 2)
    params = _params(cfg)
    tensors = latent_hf_tensors(params, cfg)
    reg = metrics.registry()
    read, reserved = (reg.counter("attn.kv_blocks_read"),
                      reg.counter("attn.kv_blocks_reserved"))
    reg.gauge("attn.decode_kernel").set(-1)
    prompts = [PROMPTS[0], PROMPTS[4], PROMPTS[3]]  # 5, 100 and 21 tokens
    bg = _engine(params, prompts, cfg=cfg)
    assert bg._kv_block == 128
    r0, v0 = read.value, reserved.value
    outs = bg.generate(40)  # the 100-token stream crosses row 127
    assert reg.gauge("attn.decode_kernel").value == 1
    for prompt, out in zip(prompts, outs):
        _is_the_references_argmax(tensors, prompt, list(out)[:40], cfg)
    got, held = read.value - r0, reserved.value - v0
    # every step of every stream reserves both blocks; the short streams
    # read one each, the long one two from row 128 on
    assert held % 6 == 0 and held // 2 < got < held
    steps = held // 6
    assert got - 3 * steps in range(steps - 40, steps - 20)


@pytest.mark.parametrize("admit_chunk", [None, 4],
                         ids=["one-chunk", "chunks-of-4"])
def test_a_reused_slot_sees_nothing_of_the_former_stream(params, tensors,
                                                         admit_chunk):
    """SLOT REUSE: a short stream admitted into the slot a long one left
    starts from a zero tail (a fresh staging row, spliced over the
    slot's) and gives the reference's tokens, whether its admission is
    one chunk or chunks of 4 that carry the tail between them;
    ``conv.state_resets`` counts the admission. The neighbour never
    notices."""
    resets = metrics.registry().counter("conv.state_resets")
    long, short = PROMPTS[4], PROMPTS[5]
    bg = _engine(params, [long, PROMPTS[3]], ids=[1, 2],
                 admit_chunk=admit_chunk)
    before = resets.value
    got = _run(bg, {6: lambda e: (e.finish(1), e.enqueue(short, 3))},
               steps=30)
    assert resets.value - before == 1
    assert len(got[3]) >= 10
    _is_the_references_argmax(tensors, short, got[3][:10])
    _is_the_references_argmax(tensors, PROMPTS[3], got[2][:12])


def test_admissions_among_live_streams_and_a_chained_one(params, tensors,
                                                         monkeypatch):
    """An admission among live streams, then two arrivals that wait
    together and ride ONE prefill program of two rows (PR 37's chain: a
    staging cache of two rows, tails and all, one splice): each stream's
    tokens are the single-stream reference's."""
    from cake_tpu.runtime import batch_generator as engine

    monkeypatch.setattr(engine, "GROUP_SHAPES", ((2, 64),))
    launches = metrics.registry().counter("engine.admit_launches")
    bg = _engine(params, [PROMPTS[1], PROMPTS[0], [4, 4, 4], [4, 4, 5]],
                 ids=[10, 11, 90, 91])
    bg.warm_admission(40)
    before = launches.value
    events = {
        2: lambda e: (e.finish(90), e.enqueue(PROMPTS[3], 12)),
        8: lambda e: (e.finish(91), e.finish(11),
                      e.enqueue(PROMPTS[2][:40], 13),
                      e.enqueue(PROMPTS[5], 14)),
    }
    got = _run(bg, events, steps=36)
    assert launches.value - before == 2  # 12 alone, 13 and 14 together
    for sid, prompt in ((10, PROMPTS[1]), (12, PROMPTS[3]),
                        (13, PROMPTS[2][:40]), (14, PROMPTS[5])):
        assert len(got[sid]) >= 10, sid
        _is_the_references_argmax(tensors, prompt, got[sid][:10])


def test_prefix_reuse_is_off_for_a_tail(params):
    """A stored row's tail is the tail at the END of the prompt that left
    it: the engine keeps no prefix store for this family, whatever it was
    asked for."""
    bg = _engine(params, [[5, 9, 2, 11]], prefix_cache_entries=4)
    assert bg._prefix_entries == 0 and bg._prefix_share_min == 0


def test_the_mixer_runs_under_its_named_scope(params):
    """``mixer.conv`` and ``attn.full`` are in the lowered program's
    ``op_name``s: what a device trace's operations are told apart by."""
    text = jax.jit(_logits, static_argnums=(1,)).lower(
        params, CFG, TOKENS[None, :8], init_cache(CFG, 1, 64), jnp.int32(0),
        None).as_text(debug_info=True)
    assert "mixer.conv" in text and "attn.full" in text
    assert "attn.swa" not in text


# -- the configuration, the plan, the loaders -----------------------------------

def _catalog() -> dict:
    """The catalog's ``config`` of LFM2-8B-A1B (the published
    ``config.json`` without the keys that say nothing of its shape)."""
    attention = (2, 6, 10, 14, 18, 21)
    return {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168,
        "layer_types": ["full_attention" if i in attention else "conv"
                        for i in range(24)],
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536,
    }


def test_the_catalogs_keys_are_read_and_round_trip():
    """``from_hf_dict`` reads every published key (its own spelling of the
    dense layers, the experts, the norm's epsilon and the bias), the
    preset is the same configuration, and ``to_hf_dict`` writes what reads
    back under the same keys."""
    published = _catalog()
    whole = LlamaConfig.from_hf_dict(published, max_seq_len=128000,
                                     bos_token_id=1, eos_token_id=7)
    assert whole == lfm2_8b_a1b()
    assert whole.family is families.SHORT_CONV
    assert whole.family.recurrent_mixer == "conv"
    assert whole.layer_kinds[:4] == (
        ("conv", "dense"), ("conv", "dense"), ("gqa", "moe"),
        ("conv", "moe"))
    assert whole.cache_plan == {"rows": (6, 8, 64, 64),
                                "conv": (18, 2, 2048)}
    assert "state" not in whole.cache_plan
    assert (whole.head_dim, whole.rope_dim, whole.qk_norm, whole.router_bias,
            whole.n_routed_experts, whole.router_experts,
            whole.n_shared_experts, whole.first_k_dense_replace,
            whole.tie_word_embeddings, whole.family.topk_norm_eps,
            whole.rms_norm_eps) == (
        64, 64, True, True, 32, 32, 0, 2, True, 1e-6, 1e-5)
    back = whole.to_hf_dict()
    for key, value in published.items():
        if key != "max_position_embeddings":  # the server's --max-seq
            assert back[key] == value, key
    assert LlamaConfig.from_hf_dict(back, max_seq_len=128000) == whole
    cut = lfm2_8b_a1b(num_hidden_layers=16, max_seq_len=2048)
    assert cut.cache_plan == {"rows": (4, 8, 64, 64), "conv": (12, 2, 2048)}
    assert LlamaConfig.from_hf_dict(cut.to_hf_dict(),
                                    max_seq_len=2048) == cut
    tiny_back = LlamaConfig.from_hf_dict(CFG.to_hf_dict(), dtype="float32",
                                         max_seq_len=256, eos_token_id=-1)
    assert tiny_back == CFG
    assert CFG.cache_plan == {"rows": (3, 2, 16, 16), "conv": (7, 2, 64)}
    # the other families' readings stand
    assert LlamaConfig.from_hf_dict({
        "model_type": "qwen2", "num_hidden_layers": 2,
        "layer_types": ["full_attention"] * 2}).layer_types is None


def _plan(cfg):
    return [(r.repeats, [(s.name, s.mixer, s.first, s.count, s.cache_first,
                          s.cache_stride) for s in r.segments])
            for r in llama.layer_plan(cfg)]


def test_layer_plan_of_the_published_layers_and_of_the_cut():
    """The published 24 layers are two leading dense conv layers, then an
    attention layer and a stretch of conv layers by turns (3, 3, 3, 3, 2,
    2), each a scanned segment of its own: NO repeated period where the
    layers hold routed experts beside short convolutions (the chip's
    compiler re-lays a period's expert stacks for the dense form's product
    from 128 rows on: ``layer_plan``). The 16-layer cut is the same plan's
    first nine segments, its last conv stretch cut to one layer. A
    segment's cache index counts the layers of ITS mixer before it: an
    attention layer's into the rows, a conv layer's into the tails."""
    whole = [("conv_dense", "conv", 0, 2, 0, 0)]
    for n, (first, convs) in enumerate(
            ((2, 3), (6, 3), (10, 3), (14, 3), (18, 2), (21, 2))):
        tag = "" if n == 0 else f"_{n + 1}"
        tails = 2 + sum(c for _, c in ((2, 3), (6, 3), (10, 3), (14, 3),
                                       (18, 2), (21, 2))[:n])
        whole += [(f"gqa_moe{tag}", "gqa", first, 1, n, 0),
                  (f"conv_moe{tag}", "conv", first + 1, convs, tails, 0)]
    assert _plan(lfm2_8b_a1b()) == [(1, [seg]) for seg in whole]
    cut = lfm2_8b_a1b(num_hidden_layers=16)
    assert _plan(cut) == [(1, [seg]) for seg in whole[:8]] + [
        (1, [("conv_moe_4", "conv", 15, 1, 11, 0)])]
    assert sum(llama.stack_layers(lfm2_8b_a1b()).values()) == 24
    assert llama.stack_layers(cut) == {
        "conv_dense": 2, "gqa_moe": 1, "conv_moe": 3, "gqa_moe_2": 1,
        "conv_moe_2": 3, "gqa_moe_3": 1, "conv_moe_3": 3, "gqa_moe_4": 1,
        "conv_moe_4": 1}
    shapes = llama.stack_shapes(cut)
    assert shapes["conv_moe"]["w_in"](cut) == (2048, 6144)
    assert shapes["conv_moe"]["conv_w"](cut) == (3, 2048)
    assert shapes["conv_moe"]["w_gate"](cut) == (32, 2048, 1792)
    assert shapes["gqa_moe"]["q_norm"](cut) == (64,)
    assert shapes["gqa_moe"]["router"](cut) == (2048, 32)
    assert "b_router" in shapes["conv_moe_2"]
    assert "ws_gate" not in shapes["conv_moe"]  # no shared expert
    assert shapes["conv_dense"]["w_gate"](cut) == (2048, 7168)
    # the tiny fixture: c c | A | c c | A | c c | A | c
    assert [(s.name, s.first, s.count, s.cache_first)
            for _, s in llama.plan_segments(CFG)] == [
        ("conv_dense", 0, 2, 0), ("gqa_moe", 2, 1, 0),
        ("conv_moe", 3, 2, 2), ("gqa_moe_2", 5, 1, 1),
        ("conv_moe_2", 6, 2, 4), ("gqa_moe_3", 8, 1, 2),
        ("conv_moe_3", 9, 1, 6)]
    # without experts the same layers ARE a period (nothing to re-lay)
    dense = tiny_lfm2_moe(n_routed_experts=0, router_bias=False,
                          num_hidden_layers=10)
    assert [(r.repeats, [s.name for s in r.segments])
            for r in llama.layer_plan(dense)] == [
        (3, ["conv_dense", "gqa_dense"]), (1, ["conv_dense_2"])]


def test_hbm_budget_counts_tails_rows_of_four_layers_and_the_tied_matrix():
    """The benchmark's cut (layers 0-15, every expert, the whole
    vocabulary) at 32 slots x 2048, held to ISSUE 43's arithmetic: 5.399 B
    parameters once, 10.31 GiB with the tied matrix held twice; the rows
    of the FOUR attention layers alone (0.5 GiB) and 98,304 bytes of tail
    a stream."""
    from cake_tpu.utils.memory import hbm_budget

    cfg = lfm2_8b_a1b(num_hidden_layers=16, max_seq_len=2048)
    b = hbm_budget(cfg, batch=32, max_seq=2048)
    h = 2048
    norms = 2 * h
    sparse = h * 32 + 32 + 32 * 3 * h * 1792
    dense = 3 * h * 7168
    conv = h * 3 * h + h * h + 3 * h
    attention = 2 * h * h + 2 * h * 512 + 2 * 64
    assert (sparse, conv, attention, dense) == (
        352387104, 16783360, 10485888, 44040192)
    once = (14 * sparse + 2 * dense + 12 * conv + 4 * attention
            + 16 * norms + 65536 * h + h)
    assert abs(once - 5.399e9) < 1e6
    assert b["layers"] == 2 * (once - 65536 * h - h)
    assert b["embed_replicated"] == 2 * 65536 * h
    assert b["head"] == 2 * (65536 * h + h)
    assert b["kv_cache"] == 32 * (4 * 2048 * 2048 + 98304)
    weights = b["total"] - b["kv_cache"]
    assert abs(weights / 2**30 - 10.31) < 0.01
    assert abs(b["kv_cache"] / 2**30 - 0.503) < 0.001
    with pytest.raises(ValueError, match="not wired"):
        hbm_budget(cfg, quant="int8")


def test_checkpoint_round_trip_with_the_tied_head(tmp_path, params, want):
    """Through the real writer and loader: the same pytree, the same
    logits; the names the configuration assumes (``operator_norm``,
    ``conv.conv.weight`` as torch's depthwise ``[C, 1, 3]``,
    ``self_attn.out_proj``, ``feed_forward.expert_bias``, every expert by
    its id), no ``lm_head.weight`` stored and the embedding loaded in its
    place."""
    from safetensors.numpy import load_file

    save_llama_params(params, tmp_path, config=CFG)
    (tmp_path / "config.json").write_text(json.dumps(CFG.to_hf_dict()))
    stored = load_file(tmp_path / "model.safetensors")
    assert "lm_head.weight" not in stored
    assert stored["model.embedding_norm.weight"].shape == (64,)
    assert stored["model.layers.0.operator_norm.weight"].shape == (64,)
    assert stored["model.layers.0.conv.conv.weight"].shape == (64, 1, 3)
    assert stored["model.layers.0.conv.in_proj.weight"].shape == (192, 64)
    assert stored["model.layers.0.feed_forward.w1.weight"].shape == (128, 64)
    assert stored["model.layers.2.self_attn.out_proj.weight"].shape == (
        64, 64)
    assert stored["model.layers.2.self_attn.q_layernorm.weight"].shape == (
        16,)
    assert stored["model.layers.2.feed_forward.gate.weight"].shape == (8, 64)
    assert stored["model.layers.3.feed_forward.expert_bias"].shape == (8,)
    held = sorted(int(n.split(".")[5]) for n in stored
                  if n.startswith("model.layers.3.feed_forward.experts.")
                  and n.endswith("w3.weight"))
    assert held == list(range(8))
    assert "model.layers.0.feed_forward.gate.weight" not in stored
    assert "model.layers.3.self_attn.q_proj.weight" not in stored
    cfg = LlamaConfig.from_hf_json(tmp_path / "config.json", dtype="float32",
                                   max_seq_len=256, eos_token_id=-1)
    assert cfg == CFG
    loaded = load_llama_params(tmp_path, cfg.num_hidden_layers,
                               dtype="float32")
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    got, _ = _through_the_cache(loaded, TOKENS[:24], 24, 24, 32)
    np.testing.assert_allclose(got, want[:24], atol=TIGHT, rtol=0)
    with pytest.raises(NotImplementedError, match="serve it in bf16"):
        load_llama_params(tmp_path, cfg.num_hidden_layers, quantize="int8")


def _hf(**over):
    return dict(CFG.to_hf_dict(), **over)


@pytest.mark.parametrize("what, match", [
    (lambda p: validate_shardable(CFG, 2, 1), "one stage"),
    (lambda p: validate_shardable(CFG, 1, 2), "tail under stages, tp"),
    (lambda p: validate_shardable(CFG, 1, 1, 2), "sp = 1"),
    (lambda p: validate_shardable(CFG, 1, 1, 1, 2), "no share is cut"),
    (lambda p: _engine(p, [[1, 2]], kv_layout="paged"),
     "no convolution's tail"),
    (lambda p: _engine(p, [[1, 2]], spec_k=2), "convolution's tail"),
    (lambda p: init_cache(CFG, quant="int8"), "int8 cache"),
    (lambda p: init_cache(CFG, num_layers=2), "cached whole"),
    (lambda p: llama.layer_shapes(CFG), "stack a kind"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(layer_types=["conv"])),
     "1 entries for 10 layers"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(conv_bias=True)), "no bias"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(conv_L_cache=1)),
     "2 or more taps"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(num_shared_experts=1)),
     "num_shared_experts"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(n_group=4)), "n_group"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(scoring_func="softmax")),
     "scoring_func"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(rope_scaling={
        "rope_type": "yarn", "factor": 4.0})), "rope type 'yarn'"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(hidden_act="gelu")),
     "hidden_act"),
    (lambda p: tiny_lfm2_moe(layer_types=("conv",) * 10),
     "without a full_attention layer"),
    (lambda p: tiny_lfm2_moe(layer_types=("full_attention",) * 10),
     "without a conv layer"),
    (lambda p: tiny_lfm2_moe(layer_types=("sliding_attention",) * 10),
     "needs one of"),
    (lambda p: tiny_lfm2_moe(sliding_window=64), "no sliding_window"),
    (lambda p: tiny_lfm2_moe(attention_bias=True), "no projection bias"),
    (lambda p: tiny_lfm2_moe(n_shared_experts=1), "no shared expert"),
], ids=["stages", "tp", "sp", "ep", "paged", "speculation", "int8-cache",
        "layer-range", "one-stack", "types-short", "conv-bias", "one-tap",
        "shared-expert", "groups", "scoring", "rope-type", "activation",
        "no-full-layer", "no-conv-layer", "unknown-type", "window", "bias",
        "shared-preset"])
def test_family_limits_are_refused_with_a_message(params, what, match):
    with pytest.raises(ValueError, match=match):
        what(params)


# -- the benchmark's copy of the reference ------------------------------------

def _bench_arch():
    """``benchmark/arch/conv_gqa_moe.py``, loaded as the harness loads it
    (its directory's shared modules on the path)."""
    root = Path(__file__).resolve().parent.parent / "benchmark"
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    spec = importlib.util.spec_from_file_location(
        "bench_arch_conv_gqa_moe_under_test",
        root / "arch" / "conv_gqa_moe.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_numpy_reference_agrees_with_the_jax_one(tmp_path):
    """``benchmark/arch/conv_gqa_moe.py`` writes a seeded checkpoint under
    the names the loader reads, and its numpy reference (what decides a
    cell's ``correct``) gives the ``jax.numpy`` reference's log-softmax on
    the same tensors: best tokens and their log-probabilities. Its byte
    counts are the arithmetic's."""
    arch = _bench_arch()
    cfg = dict(CFG.to_hf_dict(), hidden_size=128, num_attention_heads=4,
               num_key_value_heads=2, vocab_size=512,
               max_position_embeddings=128, torch_dtype="float32")
    written = arch.write_checkpoint(cfg, "bf16", 43, tmp_path)
    assert written["bytes"] == arch.checkpoint_bytes(cfg, "bf16")
    from safetensors import safe_open

    tensors = {}
    for name in sorted({f for f in json.loads(
            (tmp_path / "model.safetensors.index.json").read_text())[
                "weight_map"].values()}):
        with safe_open(tmp_path / name, framework="np") as f:
            for key in f.keys():
                raw = f.get_slice(key)
                tensors[key] = f.get_tensor(key) if raw.get_dtype() != (
                    "BF16") else None
    if any(v is None for v in tensors.values()):
        ck = arch.Checkpoint(tmp_path)  # bfloat16 through the harness's reader
        tensors = {k: ck.f32(k) for k in tensors}
    prompt = [int(t) for t in TOKENS[:40] % 512]
    chosen = [int(t) for t in TOKENS[40:48] % 512]
    got = arch.chosen_logprobs(cfg, tmp_path, [(prompt, chosen)])[0]
    logits = np.asarray(ref.logits(cfg, tensors, prompt + chosen[:-1]),
                        np.float64)[len(prompt) - 1:]
    logp = logits - np.log(np.exp(
        logits - logits.max(-1, keepdims=True)).sum(-1, keepdims=True)) - (
        logits.max(-1, keepdims=True))
    assert got["best"] == [int(b) for b in logp.argmax(-1)]
    np.testing.assert_allclose(
        got["logprob"], logp[np.arange(8), chosen], atol=2e-4, rtol=0)
    np.testing.assert_allclose(got["best_logprob"], logp.max(-1), atol=2e-4,
                               rtol=0)
    assert min(got["routing_margin"]) > 0.5  # no choice hangs on rounding
    # the loader reads what the writer wrote, and the program agrees too
    loaded = load_llama_params(tmp_path, cfg["num_hidden_layers"],
                               dtype="float32")
    served = LlamaConfig.from_hf_json(tmp_path / "config.json",
                                      dtype="float32", max_seq_len=128)
    program, _ = _STEP(loaded, served, np.asarray([prompt + chosen[:-1]]),
                       init_cache(served, 1, 128), jnp.int32(0), None)
    np.testing.assert_allclose(np.asarray(program[0, len(prompt) - 1:]),
                               logits, atol=2e-4, rtol=0)


def test_the_benchmarks_byte_counts_are_the_arithmetic():
    """``arch/conv_gqa_moe.py`` at the cell's configuration: the weights
    the device holds are the budget's, a stream's tails 98,304 bytes, a
    step reads the live rows of the four attention layers and (at 32 rows)
    nearly every expert."""
    arch = _bench_arch()
    cfg = json.loads((Path(__file__).resolve().parent.parent / "benchmark"
                      / "configs" / "lfm2-8b-a1b-cut.json").read_text())
    assert list(arch.held_experts(cfg)) == list(range(32))
    assert (arch.expert_layers(cfg), arch.conv_layers(cfg)) == (14, 12)
    assert arch.state_bytes_per_stream(cfg) == 98304
    held = arch.weight_bytes(cfg, "bf16")
    assert abs(held / 2**30 - 10.31) < 0.01
    assert arch.kv_bytes(cfg, 400, 32) == 32 * 400 * 4 * 2048
    step = arch.decode_step_bytes(cfg, "bf16", 32, 400)
    assert 10.7e9 < step < 10.95e9
    experts = 14 * 3 * 2048 * 1792 * 2 * arch.held_experts_hit(cfg, 32)
    assert 0.89 < experts / step < 0.91
    for key, value in _catalog().items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["layer_types"] == _catalog()["layer_types"][:16]
