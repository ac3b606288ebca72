"""The looped decoder (Ouro's keys, ``model_type`` ``ouro``: sandwich-normed
layers run ``total_ut_steps`` times a token over ONE set of weights, the
model's last norm between passes and none before the head, a cache plane a
layer AND a pass) against the plain reference
``cake_tpu/testing/reference_ouro.py``, on seeded random weights at tiny
widths (``models.config.tiny_ouro``: three layers run three times, nine
planes; as many passes as layers, so that neither count gives a mix-up away
and only the ORDER ``u * L + i`` is right).

Tolerances. Everything here is float32 on the CPU, where XLA's matmuls are
full precision. Program and reference differ in the order of sums only
(attention through a cache plane against the whole sequence at once):
measured 1e-6 to 3e-6 on logits of magnitude ~1 through nine layer
applications over 96 tokens. ``TIGHT`` is 1e-4, thirty times the worst; one
pass fewer, no norm between passes, a second norm before the head, one
plane for all passes or the output norms left out each move the logits by
0.1 and more (checked below), so leaving a piece of the mathematics out
fails.
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
from cake_tpu.models.config import LlamaConfig, ouro_2_6b, tiny_ouro
from cake_tpu.obs import metrics
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.ops.rope import rope_tables_for
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.parallel.mesh import validate_shardable
from cake_tpu.testing import reference_ouro as ref
from cake_tpu.utils.weights import (latent_hf_tensors, load_llama_params,
                                    save_llama_params)

TIGHT = 1e-4
CFG = tiny_ouro(max_seq_len=256, eos_token_id=-1)
TOKENS = np.random.default_rng(47).integers(3, 250, 96).astype(np.int32)
GREEDY = dict(temperature=0.0, repeat_penalty=1.0)
ROOT = Path(__file__).resolve().parent.parent


def _params(cfg=CFG, seed=0):
    """Seeded weights whose norm scales (the four of a layer and the
    model's last) are not all ones: a norm applied twice, not at all, or
    in another place shows."""
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def jitter(path, leaf):
        name = path[-1].key
        k = jax.random.fold_in(  # (crc32: str hashes differ by process)
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
        if name.endswith("norm") or name == "norm_f":
            return leaf * (1.0 + 0.25 * jax.random.uniform(
                k, leaf.shape, minval=-1.0))
        return leaf

    return jax.tree_util.tree_map_with_path(jitter, params)


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


def _logits(params, cfg, tokens, cache, pos):
    """Logits at every position of one call, and the cache it leaves: the
    layer loop norms each pass, so the head norms nothing."""
    cos, sin = rope_tables_for(cfg, cache.max_seq)
    x = llama.embed_tokens(params, jnp.asarray(tokens), cfg)
    x, cache = llama.forward_layers(
        params["layers"], x, cache, cos, sin, pos, cfg,
        pass_norm=llama.pass_norm(params, cfg))
    return llama.head_norm(params, x, cfg) @ params["lm_head"], cache


_STEP = jax.jit(_logits, static_argnums=(1,))


def _through_the_cache(params, tokens, prefill, chunk, bucket=None, cfg=CFG,
                       cache=None):
    """Logits at every position through the cache: a prefill of
    ``prefill`` tokens in chunks of ``chunk`` (each padded to ``bucket``
    rows; the padding lies behind the true tokens, where the causal mask
    and the next write cover it), then a step a token."""
    if cache is None:
        cache = init_cache(cfg, batch=1, max_seq=256)
    out = []
    for lo in range(0, prefill, chunk):
        n = min(chunk, prefill - lo)
        rows = np.full((1, bucket or chunk), 7, np.int32)
        rows[0, :n] = tokens[lo:lo + n]
        logits, cache = _STEP(params, cfg, rows, cache, jnp.int32(lo))
        out.append(np.asarray(logits[0, :n]))
    for i in range(prefill, len(tokens)):
        logits, cache = _STEP(params, cfg, tokens[None, i:i + 1], cache,
                              jnp.asarray([i], jnp.int32))
        out.append(np.asarray(logits[0]))
    return np.concatenate(out), cache


# -- against the reference -----------------------------------------------------

@pytest.mark.parametrize("context, prefill, chunk, bucket", [
    (24, 11, 11, 16),  # a bucket's padding behind the true tokens
    (48, 40, 4, None),  # every chunk runs all its passes before the next
    (48, 30, 1, None),  # an admission a token at a time
    (96, 70, 70, 128),  # one padded chunk, then 26 steps
    (96, 64, 32, None),  # two bands
], ids=["padded", "chunks-of-4", "chunks-of-1", "one-chunk-padded",
        "bands-of-32"])
def test_prefill_then_decode_through_the_cache_match_reference(
        params, want, context, prefill, chunk, bucket):
    """Prefill (whole, in bands, padded) and then decoding through the
    cache give the reference's full forward pass at every position, in
    logits: pass ``u`` of a later token or chunk attends over the plane
    that pass ``u`` of the earlier ones wrote, which is complete by then
    because a chunk runs all its passes before the next chunk."""
    got, cache = _through_the_cache(params, TOKENS[:context], prefill, chunk,
                                    bucket)
    np.testing.assert_allclose(got, want[:context], atol=TIGHT, rtol=0)
    assert cache.k.shape == cache.v.shape == (9, 1, 4, 256, 16)
    assert cache.state is None and cache.conv is None


def test_chunked_admission_equals_whole_admission(params):
    """Admitting 64 tokens in bands of 16 leaves, in every one of the nine
    planes, the rows that admitting them whole leaves (to a product's
    rounding: a band's rows are another matmul), and the same logits
    behind them."""
    whole, c1 = _through_the_cache(params, TOKENS[:70], 64, 64)
    bands, c2 = _through_the_cache(params, TOKENS[:70], 64, 16)
    for a, b in ((c1.k, c2.k), (c1.v, c2.v)):
        np.testing.assert_allclose(np.asarray(b)[..., :70, :],
                                   np.asarray(a)[..., :70, :], atol=1e-5,
                                   rtol=0)
    np.testing.assert_allclose(bands, whole, atol=2e-5, rtol=0)


def test_every_pass_writes_a_plane_of_its_own(params):
    """Layer ``i`` in pass ``u`` writes plane ``u * L + i``: after one
    admission every one of the nine planes holds rows, no two planes hold
    the same rows (a pass's input differs from the last one's), and pass
    0's and pass 1's planes are what a TWO-pass model over the same layers
    writes into its six (the same layers, the same inputs: planes 0-5)."""
    _, cache = _through_the_cache(params, TOKENS[:20], 20, 20)
    k = np.asarray(cache.k)[:, 0, :, :20]
    assert all(np.abs(k[p]).sum() > 0 for p in range(9))
    for a in range(9):
        for b in range(a + 1, 9):
            assert np.abs(k[a] - k[b]).max() > 1e-3, (a, b)
    assert np.abs(np.asarray(cache.k)[:, 0, :, 20:]).sum() == 0
    # two passes of a two-pass model are the first two of three: planes 0-5
    plain = tiny_ouro(total_ut_steps=2, max_seq_len=256, eos_token_id=-1)
    _, two = _through_the_cache(params, TOKENS[:20], 20, 20, cfg=plain,
                                cache=init_cache(plain, 1, 256))
    assert two.k.shape[0] == 6
    np.testing.assert_array_equal(np.asarray(two.k)[:6],
                                  np.asarray(cache.k)[:6])


def test_a_reused_slot_sees_nothing_of_the_former_stream_in_any_plane(
        params, want):
    """A stream admitted over the rows a longer one left (all nine planes
    full of its keys and values, far past the new frontier) gives, bit for
    bit, the logits it gives on a fresh cache: no plane is read past the
    frontier, and every plane's rows up to it are written anew."""
    _, stale = _through_the_cache(params, TOKENS[:90][::-1].copy(), 90, 90)
    k = np.asarray(stale.k)[:, 0, :, 30:90]
    assert all(np.abs(k[p]).sum() > 0 for p in range(9))
    fresh, _ = _through_the_cache(params, TOKENS[:30], 20, 20)
    reused, after = _through_the_cache(params, TOKENS[:30], 20, 20,
                                       cache=stale)
    np.testing.assert_array_equal(reused, fresh)
    np.testing.assert_allclose(reused, want[:30], atol=TIGHT, rtol=0)
    # the former stream's rows past the frontier lie where they lay
    np.testing.assert_array_equal(np.asarray(after.k)[..., 30:, :],
                                  np.asarray(stale.k)[..., 30:, :])


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_a_piece_of_the_mathematics_left_out_fails_the_tolerance(
        tensors, want, wrong):
    """One pass fewer, no norm between passes, a second norm before the
    head, one plane shared by the passes, the output norms left out: each
    moves the logits far beyond ``TIGHT``, so the comparison above would
    not pass a program that did it."""
    bad = np.asarray(ref.logits(CFG.to_hf_dict(), tensors, TOKENS[:48],
                                wrong=wrong))
    assert np.abs(bad - want[:48]).max() > 1000 * TIGHT, wrong


def test_the_exit_gate_is_read_and_changes_no_logit(tensors):
    """The reference's exit distribution reads the gate's two tensors: a
    distribution over the three passes at every token, each pass with some
    share; at ``early_exit_threshold`` 1 the logits are the last pass's
    whatever the gate holds."""
    p = np.asarray(ref.exit_distribution(CFG.to_hf_dict(), tensors,
                                         TOKENS[:24]))
    assert p.shape == (3, 24)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    assert (p > 0.01).all()
    other = dict(tensors)
    other["model.early_exit_gate.weight"] = -3.0 * np.asarray(
        tensors["model.early_exit_gate.weight"])
    q = np.asarray(ref.exit_distribution(CFG.to_hf_dict(), other,
                                         TOKENS[:24]))
    assert np.abs(q - p).max() > 0.01
    np.testing.assert_array_equal(
        np.asarray(ref.logits(CFG.to_hf_dict(), other, TOKENS[:24])),
        np.asarray(ref.logits(CFG.to_hf_dict(), tensors, TOKENS[:24])))
    with pytest.raises(ValueError, match="threshold 1"):
        ref.logits(dict(CFG.to_hf_dict(), early_exit_threshold=0.5), tensors,
                   TOKENS[:4])
    with pytest.raises(ValueError, match="wrong must be one of"):
        ref.logits(CFG.to_hf_dict(), tensors, TOKENS[:4], wrong="norm")


def test_no_caller_norms_twice_before_the_head(params, want):
    """``llama.forward`` (and with it every caller that goes through
    ``head_norm``) gives the reference's logits; ``head_norm`` is the
    identity for this family and the last norm for every other; a layer
    loop without its closing norm is an error, not a silent plain pass."""
    logits, _ = llama.forward(params, TOKENS[None, :24],
                              init_cache(CFG, 1, 64), 0, CFG)
    np.testing.assert_allclose(np.asarray(logits[0]), want[23], atol=TIGHT,
                               rtol=0)
    x = jnp.ones((1, 64))
    assert llama.head_norm(params, x, CFG) is x
    from cake_tpu.models.config import tiny

    plain = tiny()
    assert llama.pass_norm(params, plain) is None
    assert llama.head_norm(params, x, plain) is not x
    with pytest.raises(ValueError, match="pass_norm"):
        llama.forward_layers(
            params["layers"], jnp.zeros((1, 1, 64)), init_cache(CFG, 1, 64),
            *rope_tables_for(CFG, 64), 0, CFG)


def test_the_passes_run_under_their_named_scopes(params):
    """``loop.pass`` and ``loop.norm`` are in the lowered program's
    ``op_name``s: what a device trace's operations are told apart by."""
    text = jax.jit(_logits, static_argnums=(1,)).lower(
        params, CFG, TOKENS[None, :8], init_cache(CFG, 1, 64),
        jnp.int32(0)).as_text(debug_info=True)
    assert "loop.pass" in text and "loop.norm" in text


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


def _is_the_references_argmax(tensors, prompt, out):
    """Every token of ``out`` is the single-stream reference's own best
    continuation of what came before it, to ``TIGHT``."""
    full = np.array(list(prompt) + list(out))
    logits = np.asarray(ref.logits(CFG.to_hf_dict(), tensors, full))
    for j, tok in enumerate(out):
        at = logits[len(prompt) - 1 + j]
        assert at.max() - at[tok] <= TIGHT, (len(prompt), j)


_RNG = np.random.default_rng(7)
PROMPTS = [[int(t) for t in _RNG.integers(3, 250, n)]
           for n in (5, 37, 70, 21, 100, 12)]


def test_batch_generator_streams_match_reference(params, tensors):
    """Three streams of different lengths through BatchGenerator: a
    bucketed batch prefill, per-row positions, block decode; each stream's
    tokens are the reference's argmax. The gauges say what the cache holds:
    nine planes, a token's bytes over all of them; the block counters
    count a layer's planes, one a pass."""
    reg = metrics.registry()
    read, reserved = (reg.counter("attn.kv_blocks_read"),
                      reg.counter("attn.kv_blocks_reserved"))
    bg = _engine(params, PROMPTS[:3])
    r0, v0 = read.value, reserved.value
    outs = bg.generate(27)
    for prompt, out in zip(PROMPTS[:3], outs):
        _is_the_references_argmax(tensors, prompt, list(out)[:27])
    row = 2 * 4 * 16 * 4  # k and v, four heads of 16 float32 values
    assert reg.gauge("cache.row_bytes").value == row
    assert reg.gauge("cache.layer_planes").value == 9
    assert reg.gauge("model.loop_passes").value == 3
    assert reg.gauge("cache.token_bytes").value == 9 * row
    assert reg.gauge("cache.token_bytes").value == CFG.cache_token_bytes
    assert reg.gauge("cache.bytes").value == 3 * 256 * 9 * row
    assert reg.gauge("cache.state_bytes").value == 0
    # ONE query row a KV head: the decode kernel's blocks are 128 rows
    # (``pk.decode_block_k``), so a 256-row window is two. Every step of
    # every stream reserves both and reads the one its frontier lies in
    # (no stream here passes row 127), a plane: three planes a layer
    steps = (read.value - r0) / 3
    assert steps == int(steps) and steps >= 3 * 26
    assert reserved.value - v0 == 2 * (read.value - r0)


@pytest.mark.parametrize("admit_chunk", [None, 4],
                         ids=["one-chunk", "chunks-of-4"])
def test_a_reused_slot_gives_the_references_tokens(params, tensors,
                                                   admit_chunk):
    """SLOT REUSE in the engine: a short stream admitted into the slot a
    long one left gives the reference's tokens, whether its admission is
    one chunk or chunks of 4 (each chunk all three passes before the
    next); the splice covers all nine planes. The neighbour never
    notices."""
    long, short = PROMPTS[4], PROMPTS[5]
    bg = _engine(params, [long, PROMPTS[3]], ids=[1, 2],
                 admit_chunk=admit_chunk)
    got = _run(bg, {6: lambda e: (e.finish(1), e.enqueue(short, 3))},
               steps=30)
    assert len(got[3]) >= 10
    _is_the_references_argmax(tensors, short, got[3][:10])
    _is_the_references_argmax(tensors, PROMPTS[3], got[2][:12])


@pytest.mark.parametrize("staging_bytes, launched", [
    (2**30, 2),  # 12 alone, 13 and 14 together
    (2 * 256 * CFG.cache_token_bytes - 1, 3),  # two rows do not fit
], ids=["two-ride-one-program", "one-at-a-time"])
def test_admissions_among_live_streams_and_a_chained_one(
        params, tensors, monkeypatch, staging_bytes, launched):
    """An admission among live streams, then two arrivals that wait
    together and ride ONE prefill program of two rows (a staging cache of
    two rows and nine planes, one splice): each stream's tokens are the
    single-stream reference's. Where two staging rows pass
    ``GROUP_STAGING_BYTES`` (at the published sizes a row is 1.125 GiB:
    192 planes x 768 rows) each arrival launches alone and no landing
    hands the device its next program before its own row is released, to
    the same tokens."""
    from cake_tpu.runtime import batch_generator as engine

    monkeypatch.setattr(engine, "GROUP_SHAPES", ((2, 64),))
    monkeypatch.setattr(engine, "GROUP_STAGING_BYTES", staging_bytes)
    launches = metrics.registry().counter("engine.admit_launches")
    ahead = metrics.registry().counter("engine.landings_ahead")
    bg = _engine(params, [PROMPTS[1], PROMPTS[0], [4, 4, 4], [4, 4, 5]],
                 ids=[10, 11, 90, 91])
    bg.warm_admission(40)
    before, ahead_before = launches.value, ahead.value
    events = {
        2: lambda e: (e.finish(90), e.enqueue(PROMPTS[3], 12)),
        8: lambda e: (e.finish(91), e.finish(11),
                      e.enqueue(PROMPTS[2][:40], 13),
                      e.enqueue(PROMPTS[5], 14)),
    }
    got = _run(bg, events, steps=36)
    assert launches.value - before == launched
    # no second staging row fits: the device's next program follows the
    # landing's fetch (the row released first), never runs ahead of it
    assert (ahead.value > ahead_before) == (launched == 2)
    for sid, prompt in ((10, PROMPTS[1]), (12, PROMPTS[3]),
                        (13, PROMPTS[2][:40]), (14, PROMPTS[5])):
        assert len(got[sid]) >= 10, sid
        _is_the_references_argmax(tensors, prompt, got[sid][:10])
    big = ouro_2_6b(max_seq_len=768)
    assert 2 * 768 * big.cache_token_bytes > 2**30  # 2.25 GiB


def test_the_single_stream_generators_match_reference(params, tensors):
    """What is kept beside the engine: ``LlamaGenerator`` (bucketed
    prefill, block decode) and ``SpeculativeGenerator`` (n-gram proposals
    verified in one forward pass over every plane, rejected rows rolled
    back by position in all of them) give the reference's argmax."""
    from cake_tpu.runtime.generator import LlamaGenerator
    from cake_tpu.runtime.speculative import SpeculativeGenerator

    prompt = PROMPTS[3]
    for make in (
            lambda: LlamaGenerator(CFG, params, settings=SamplerSettings(
                **GREEDY), max_seq=256, block_size=4),
            lambda: SpeculativeGenerator(CFG, params, settings=SamplerSettings(
                **GREEDY), max_seq=256, spec_k=3)):
        gen = make()
        gen.set_prompt(prompt)
        out = [gen.next_token(i).id for i in range(14)]
        _is_the_references_argmax(tensors, prompt, out)


def test_prefix_reuse_is_off_for_a_plane_a_pass(params):
    """A stored row would be a plane a layer AND a pass and a hit over
    them has not been compared with the reference: the engine keeps no
    prefix store for this family, whatever it was asked for."""
    bg = _engine(params, [[5, 9, 2, 11]], prefix_cache_entries=4)
    assert bg._prefix_entries == 0 and bg._prefix_share_min == 0


# -- the configuration, the plan, the loaders -----------------------------------

def _catalog() -> dict:
    """The catalog's ``config`` of Ouro-2.6B (the published ``config.json``
    without the keys that say nothing of its shape)."""
    return {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152,
    }


def test_the_catalogs_keys_are_read_and_round_trip():
    """``from_hf_dict`` reads every published key, the preset is the same
    configuration, and ``to_hf_dict`` writes what reads back under the
    same keys with the same values."""
    published = _catalog()
    whole = LlamaConfig.from_hf_dict(published, max_seq_len=65536,
                                     bos_token_id=1, eos_token_id=2)
    assert whole == ouro_2_6b()
    assert whole.family is families.LOOPED and whole.family.loops
    assert families.BY_MODEL_TYPE["ouro"] is families.LOOPED
    assert (whole.total_ut_steps, whole.early_exit_threshold,
            whole.head_dim, whole.num_key_value_heads, whole.sliding_window,
            whole.rope_scaling, whole.tie_word_embeddings,
            whole.attention_bias) == (4, 1.0, 128, 16, None, None, False,
                                      False)
    back = whole.to_hf_dict()
    for key, value in published.items():
        if key != "max_position_embeddings":  # the server's --max-seq
            assert back[key] == value, key
    assert LlamaConfig.from_hf_dict(back, max_seq_len=65536) == whole
    tiny_back = LlamaConfig.from_hf_dict(CFG.to_hf_dict(), dtype="float32",
                                         max_seq_len=256, eos_token_id=-1)
    assert tiny_back == CFG
    # no other family reads or writes this one's keys
    from cake_tpu.models.config import tiny

    assert "total_ut_steps" not in tiny().to_hf_dict()
    assert tiny().family is families.GQA and not tiny().family.loops


def test_cache_plan_and_init_cache_hold_a_plane_a_layer_and_a_pass():
    """At the published sizes: 192 planes of 16 heads x (128 + 128), 8,192
    bytes a plane and 1,572,864 a token in bf16; the cell's cache (8 slots
    x 768 rows) is two buffers of 4.5 GiB; one stack of 48 layers whatever
    the passes; the tiny fixture's nine."""
    whole = ouro_2_6b(max_seq_len=768)
    assert whole.cache_plan == {"rows": (192, 16, 128, 128)}
    assert whole.cache_token_bytes == 1572864 == 192 * 8192
    cache = jax.eval_shape(lambda: init_cache(whole, batch=8, max_seq=768))
    assert cache.k.shape == cache.v.shape == (192, 8, 16, 768, 128)
    assert cache.k.dtype == jnp.bfloat16
    assert 2 * np.prod(cache.k.shape) == 4.5 * 2**30
    assert llama.stack_layers(whole) == {"dense": 48}
    shapes = llama.stack_shapes(whole)["dense"]
    assert shapes["attn_post_norm"](whole) == shapes["mlp_post_norm"](
        whole) == (2048,)
    assert shapes["wk"](whole) == (2048, 2048)
    assert CFG.cache_plan == {"rows": (9, 4, 16, 16)}
    params = jax.eval_shape(
        lambda: llama.init_params(whole, jax.random.PRNGKey(0)))
    assert params["exit_gate"]["weight"].shape == (1, 2048)
    assert params["exit_gate"]["bias"].shape == (1,)
    assert params["lm_head"].shape == (2048, 49152)
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert count == 2667974657  # ISSUE 47: 2,667.97 M parameters


def test_hbm_budget_counts_192_planes_and_the_weights_once():
    """The cell's deployment held to ISSUE 47's arithmetic: 51,388,416
    parameters a layer, 4.97 GiB of weights read four times a token and
    held once, 9.00 GiB of cache at 8 slots x 768 rows (6.75 at 6), 13.97
    GiB in all."""
    from cake_tpu.utils.memory import hbm_budget

    cfg = ouro_2_6b(max_seq_len=768)
    b = hbm_budget(cfg, batch=8, max_seq=768)
    h = 2048
    layer = 4 * h * h + 3 * h * 5632 + 4 * h
    assert layer == 51388416
    assert b["layers"] == 2 * 48 * layer
    assert b["embed_replicated"] == 2 * 49152 * h
    assert b["head"] == 2 * (49152 * h + h)
    assert b["kv_cache"] == 8 * 768 * 1572864 == 9 * 2**30
    weights = b["total"] - b["kv_cache"]
    assert abs(weights / 2**30 - 4.97) < 0.005
    assert abs(b["total"] / 2**30 - 13.97) < 0.005
    assert hbm_budget(cfg, batch=6, max_seq=768)["kv_cache"] == 6.75 * 2**30
    with pytest.raises(ValueError, match="not wired"):
        hbm_budget(cfg, quant="int8")


def test_checkpoint_round_trip_with_the_gate_and_the_untied_head(
        tmp_path, params, want):
    """Through the real writer and loader: the same pytree, the same
    logits; the names the configuration assumes (a sub-layer's second norm
    under its first one's name with ``_2``, ``model.early_exit_gate`` as a
    linear of one output, kept and not skipped), the head stored beside
    the embedding."""
    from safetensors.numpy import load_file

    save_llama_params(params, tmp_path, config=CFG)
    (tmp_path / "config.json").write_text(json.dumps(CFG.to_hf_dict()))
    stored = load_file(tmp_path / "model.safetensors")
    for name, shape in {
            "lm_head.weight": (256, 64), "model.embed_tokens.weight": (256, 64),
            "model.norm.weight": (64,),
            "model.early_exit_gate.weight": (1, 64),
            "model.early_exit_gate.bias": (1,),
            "model.layers.0.input_layernorm.weight": (64,),
            "model.layers.0.input_layernorm_2.weight": (64,),
            "model.layers.2.post_attention_layernorm.weight": (64,),
            "model.layers.2.post_attention_layernorm_2.weight": (64,),
            "model.layers.1.self_attn.k_proj.weight": (64, 64),
            "model.layers.1.mlp.down_proj.weight": (64, 128)}.items():
        assert stored[name].shape == shape, name
    assert len(stored) == 5 + 3 * 11
    assert not np.array_equal(stored["lm_head.weight"],
                              stored["model.embed_tokens.weight"])
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
    (lambda p: validate_shardable(CFG, 1, 2), "a plane a layer"),
    (lambda p: validate_shardable(CFG, 1, 1, 2), "sp = 1"),
    (lambda p: validate_shardable(CFG, 1, 1, 1, 2), "ep = 1"),
    (lambda p: _engine(p, [[1, 2]], kv_layout="paged"),
     "kv_layout='paged' is not wired for a looped model"),
    (lambda p: _engine(p, [[1, 2]], spec_k=2),
     r"speculation \(spec_k\) is not wired for a looped model"),
    (lambda p: _engine(p, [[1, 2]], kv_quant="int8"),
     "kv_quant='int8' is not wired for a looped model"),
    (lambda p: init_cache(CFG, quant="int8"), "int8 cache"),
    (lambda p: init_cache(CFG, num_layers=2), "cached whole"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(early_exit_threshold=0.9)),
     "early_exit_threshold = 0.9 is not wired"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(
        layer_types=["full_attention", "sliding_attention",
                     "full_attention"])),
     r"layer_types entries \['sliding_attention'\]"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(rope_scaling={
        "rope_type": "yarn", "factor": 4.0})), "rope_scaling"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(use_sliding_window=True)),
     "use_sliding_window"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(sliding_window=4096)),
     "sliding_window"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(total_ut_steps=1)),
     "2 or more times"),
    (lambda p: tiny_ouro(early_exit_threshold=0.5),
     "early_exit_threshold = 0.5 is not wired"),
    (lambda p: tiny_ouro(tie_word_embeddings=True), "no tied head"),
    (lambda p: tiny_ouro(attention_bias=True), "no projection bias"),
    (lambda p: tiny_ouro(sliding_window=64), "no sliding_window"),
    (lambda p: tiny_ouro(num_local_experts=4), "no experts"),
], ids=["stages", "tp", "sp", "ep", "paged", "speculation", "int8-cache",
        "int8-cache-init", "layer-range", "early-exit", "swa-layer",
        "rope-scaling", "use-window", "window", "one-pass", "early-preset",
        "tied", "bias", "window-preset", "experts"])
def test_family_limits_are_refused_with_a_message(params, what, match):
    with pytest.raises(ValueError, match=match):
        what(params)


# -- the benchmark's copy of the reference ------------------------------------

def _bench_arch():
    """``benchmark/arch/looped_gqa.py``, loaded as the harness loads it
    (its directory's shared modules on the path)."""
    root = ROOT / "benchmark"
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    spec = importlib.util.spec_from_file_location(
        "bench_arch_looped_gqa_under_test", root / "arch" / "looped_gqa.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_numpy_reference_agrees_with_the_jax_one(tmp_path):
    """``benchmark/arch/looped_gqa.py`` writes a seeded checkpoint under
    the names the loader reads, and its numpy reference (what decides a
    cell's ``correct``) gives the ``jax.numpy`` reference's log-softmax on
    the same tensors: best tokens and their log-probabilities; the
    program, given the loader's reading of the same files, agrees too."""
    arch = _bench_arch()
    cfg = dict(CFG.to_hf_dict(), hidden_size=128, vocab_size=512,
               max_position_embeddings=128, torch_dtype="float32")
    written = arch.write_checkpoint(cfg, "bf16", 47, tmp_path)
    assert written["bytes"] == arch.checkpoint_bytes(cfg, "bf16")
    ck = arch.Checkpoint(tmp_path)  # bfloat16 through the harness's reader
    names = json.loads((tmp_path / "model.safetensors.index.json")
                       .read_text())["weight_map"]
    tensors = {k: ck.f32(k) for k in names}
    assert tensors["model.early_exit_gate.weight"].shape == (1, 128)
    prompt = [int(t) for t in TOKENS[:40] % 512]
    chosen = [int(t) for t in TOKENS[40:48] % 512]
    got = arch.chosen_logprobs(cfg, tmp_path, [(prompt, chosen)])[0]
    logits = np.asarray(ref.logits(cfg, tensors, prompt + chosen[:-1]),
                        np.float64)[len(prompt) - 1:]
    top = logits.max(-1, keepdims=True)
    logp = logits - top - np.log(np.exp(logits - top).sum(-1, keepdims=True))
    assert got["best"] == [int(b) for b in logp.argmax(-1)]
    np.testing.assert_allclose(
        got["logprob"], logp[np.arange(8), chosen], atol=2e-4, rtol=0)
    np.testing.assert_allclose(got["best_logprob"], logp.max(-1), atol=2e-4,
                               rtol=0)
    assert got["routing_margin"] == [None] * 8
    # blocks of query rows change nothing
    arch.QUERY_ROWS = 16
    again = arch.chosen_logprobs(cfg, tmp_path, [(prompt, chosen)])[0]
    np.testing.assert_allclose(again["logprob"], got["logprob"], atol=1e-5,
                               rtol=0)
    # the loader reads what the writer wrote, and the program agrees too
    loaded = load_llama_params(tmp_path, cfg["num_hidden_layers"],
                               dtype="float32")
    served = LlamaConfig.from_hf_json(tmp_path / "config.json",
                                      dtype="float32", max_seq_len=128)
    assert served.family is families.LOOPED
    program, _ = _STEP(loaded, served, np.asarray([prompt + chosen[:-1]]),
                       init_cache(served, 1, 128), jnp.int32(0))
    np.testing.assert_allclose(np.asarray(program[0, len(prompt) - 1:]),
                               logits, atol=2e-4, rtol=0)


def test_the_benchmark_refuses_a_program_without_the_family(tmp_path):
    """The tensors carry Llama's names, so a program from before this
    family serves the checkpoint as a plain decoder (one pass, no sandwich
    norms) instead of failing: ``arch/looped_gqa.py`` asks the checkout's
    source for the family's ``model_type`` and fails at once without it."""
    arch = _bench_arch()
    arch.require_family(ROOT)
    models = tmp_path / "cake_tpu" / "models"
    models.mkdir(parents=True)
    (models / "families.py").write_text('MODEL_TYPES = ("llama", "mistral")')
    with pytest.raises(RuntimeError, match="declares model_type 'ouro'"):
        arch.require_family(tmp_path)


def test_the_benchmarks_byte_counts_are_the_arithmetic():
    """``arch/looped_gqa.py`` at the cell's configuration: the device holds
    4.97 GiB of weights; a step touches each once (``weight_bytes`` with
    ``rows``: what the harness holds to the device's) and READS the layers
    once a PASS (``step_weight_reads``: 19.73 GB) and the head once, and
    the LIVE rows of all 192 planes; a token costs
    1,572,864 bytes of cache; the file holds every published size."""
    arch = _bench_arch()
    cfg = json.loads((ROOT / "benchmark" / "configs" / "ouro-2p6b.json")
                     .read_text())
    held = arch.weight_bytes(cfg, "bf16")
    assert held == 2 * 2667974657
    assert abs(held / 2**30 - 4.97) < 0.005
    layers = 2 * 48 * 51388416
    once = arch.weight_bytes(cfg, "bf16", "bf16", rows=8)
    assert once == layers + 2 * (49152 * 2048 + 2048 + 8 * 2048) < held
    step = arch.step_weight_reads(cfg, "bf16", 8)
    assert step == 4 * layers + 2 * (49152 * 2048 + 4 * 2048 + 8 * 2048)
    assert abs(4 * layers / 1e9 - 19.73) < 0.01
    assert arch.token_bytes(cfg, "bf16") == 1572864
    assert arch.kv_bytes(cfg, 330, 8) == 8 * 330 * 1572864
    assert arch.decode_step_bytes(cfg, "bf16", 8, 330) == step + arch.kv_bytes(
        cfg, 330, 8)
    for key, value in _catalog().items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["max_position_embeddings"]
    assert cfg["published"] == {"max_position_embeddings": 65536}
    b = cfg["bench"]
    assert (b["arch"], b["chips"], b["kv_capacity"], b["decode_block"],
            b["weights"]) == ("looped_gqa", 1, 768, 8,
                              {"layout": "bf16", "seed": 47})
    served = LlamaConfig.from_hf_dict(
        {k: cfg[k] for k in arch.HF_KEYS if k in cfg}, max_seq_len=768)
    assert served == ouro_2_6b(max_seq_len=768)
    assert b["slots"] * b["kv_capacity"] * served.cache_token_bytes <= (
        9 * 2**30)
    r = cfg["bench"]["rehearsal"]
    assert (r["total_ut_steps"], r["num_hidden_layers"]) == (3, 3)
