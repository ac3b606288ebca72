"""The state-space + attention hybrid (AI21 Jamba's keys: Mamba-1 mixers
that hold a ``[d_state, d_inner]`` recurrent state beside one rope-less
multi-query attention layer in ``attn_layer_period``, a dense SwiGLU in
every layer, a tied head) against the plain reference
``cake_tpu/testing/reference_jamba.py``, on seeded random weights at tiny
widths that keep the published family's pattern
(``models.config.tiny_jamba``: M A M M, twice).

Tolerances. Everything here is float32 on the CPU, where XLA's matmuls are
full precision. Program and reference differ in the order of sums only
(the state laid out ``[d_state, d_inner]`` against ``[d_inner, d_state]``,
a ``lax.scan`` or the interpreted kernel against a Python loop, grouped
against repeated key/value heads): measured 4e-6 to 1.5e-5 on logits of
magnitude ~3 through eight layers over 24 tokens (and over 150, once,
while this file was written). ``TIGHT`` is 1e-4, six times the worst, and over a hundred times under what bfloat16
activations or a bfloat16 state cost (both checked below), so a lowered
precision fails. The recurrence itself is held to 1e-5
(``test_scan_is_the_recurrence_over_chunk_boundaries``).
"""

from __future__ import annotations

import dataclasses
import json
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import families, llama
from cake_tpu.models.config import LlamaConfig, jamba2_3b, tiny_jamba
from cake_tpu.obs import metrics
from cake_tpu.ops import mamba
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.rope import rope_tables_for
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.parallel.mesh import validate_shardable
from cake_tpu.testing import reference_jamba as ref
from cake_tpu.utils.weights import (latent_hf_tensors, load_llama_params,
                                    save_llama_params)

TIGHT = 1e-4
CFG = tiny_jamba(max_seq_len=256, eos_token_id=-1)
TOKENS = np.array([3, 5, 7, 9, 11, 200, 100, 50, 25, 12, 6, 1, 99, 42, 17, 8,
                   33, 64, 128, 255, 2, 4, 77, 31], np.int32)
GREEDY = dict(temperature=0.0, repeat_penalty=1.0)


def _params(cfg=CFG, seed=0):
    """Seeded weights whose norm scales are not all ones and whose skip,
    convolution bias and decay rates differ by channel: what is applied
    twice, not at all or to the wrong thing shows. The head is the
    embedding."""
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def jitter(path, leaf):
        name = path[-1].key
        k = jax.random.fold_in(  # (crc32: str hashes differ by process)
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
        if name.endswith("norm") or name in ("norm_f", "d_skip"):
            return leaf * (1.0 + 0.25 * jax.random.uniform(
                k, leaf.shape, minval=-1.0))
        if name == "conv_b":
            return 0.2 * jax.random.normal(k, leaf.shape)
        if name == "a_log":
            return leaf + 0.3 * jax.random.normal(k, leaf.shape)
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


def _decode_all(params, cfg, tokens, prefill: int, chunk: int | None = None):
    """Logits at positions ``prefill - 1 ..`` through the cache: a prefill
    of ``prefill`` tokens (in chunks of ``chunk``), then one step a token."""
    cache = init_cache(cfg, batch=1, max_seq=64)
    step = jax.jit(lambda p, t, c, pos: llama.forward(p, t, c, pos, cfg))
    chunk = chunk or prefill
    for lo in range(0, prefill, chunk):
        logits, cache = step(params, jnp.asarray(tokens[None, lo:lo + chunk]),
                             cache, lo)
    out = [logits[0]]
    for i in range(prefill, len(tokens)):
        logits, cache = step(params, jnp.asarray(tokens[None, i:i + 1]),
                             cache, i)
        out.append(logits[0])
    return np.stack(out), cache


def _all_logits(params, cfg, tokens, max_seq=256, valid=None):
    """Logits at every position of one prefill, and the cache it leaves."""
    cos, sin = rope_tables_for(cfg, max_seq)
    x = llama.embed_tokens(params, jnp.asarray(tokens)[None], cfg)
    x, cache = llama.forward_layers(
        params["layers"], x, init_cache(cfg, 1, max_seq), cos, sin, 0, cfg,
        valid=valid)
    x = rms_norm(x, params["norm_f"], cfg.rms_norm_eps)
    return np.asarray(x[0] @ params["lm_head"]), cache


def _ssm_inputs(b, t, n, c, layers=3, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(ks[0], (b, t, c)),
            jax.nn.softplus(jax.random.normal(ks[1], (b, t, c)) - 3.0),
            jax.random.normal(ks[2], (b, t, n)),
            jax.random.normal(ks[3], (b, t, n)),
            -jnp.exp(jax.random.normal(ks[4], (n, c))),
            jax.random.normal(ks[5], (c,)),
            jax.random.normal(ks[6], (layers, b, n, c)))


# -- against the reference -------------------------------------------------------

def test_prefill_logits_match_reference_at_every_position(params, want):
    """One prefill of the 24 tokens, against the token-by-token reference:
    no rotation anywhere (no table is built), so position comes from the
    recurrence alone."""
    assert rope_tables_for(CFG, 64) == (None, None)
    got, _ = _all_logits(params, CFG, TOKENS)
    np.testing.assert_allclose(got, want, atol=TIGHT, rtol=0)


@pytest.mark.parametrize("chunk", [None, 4], ids=["one-chunk", "chunks-of-4"])
def test_prefill_then_16_decode_steps_match_reference(params, want, chunk):
    """Prefill (entering and leaving through the state and the
    convolution's tail) then 16 decode steps through the cache: the logits
    at every position against the reference's full forward."""
    got, _ = _decode_all(params, CFG, TOKENS, prefill=8, chunk=chunk)
    assert got.shape[0] == 17
    np.testing.assert_allclose(got, want[7:], atol=TIGHT, rtol=0)


def test_bfloat16_activations_fail_the_tolerance(params, want):
    """The tolerance is tight enough that a lowered precision fails it."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    got, _ = _decode_all(low, cfg, TOKENS, prefill=8)
    assert np.abs(got - want[7:]).max() > 100 * TIGHT


def test_state_in_bfloat16_fails_the_tolerance(tensors, want):
    """The control the benchmark runs on the chip, at the small size: the
    reference with its state rounded to bfloat16 between tokens is another
    model by this tolerance."""
    low = np.asarray(ref.logits(CFG.to_hf_dict(), tensors, TOKENS,
                                state_dtype=jnp.bfloat16))
    assert np.abs(low - want).max() > 100 * TIGHT


def test_scan_is_the_recurrence_over_chunk_boundaries():
    """150 tokens in chunks of 64, 64 and 22, each entering through the
    state the last left, against one recurrence over all 150 and against a
    Python loop of steps: the scan holds nothing across a boundary but the
    state."""
    x, delta, bm, cm, a, d, state = _ssm_inputs(2, 150, 8, 128)
    y_want, s_want = mamba.ssm_recurrence(x, delta, bm, cm, a, d, state[0])
    s, ys = state[0], []
    for lo, hi in ((0, 64), (64, 128), (128, 150)):
        y, s = mamba.ssm_recurrence(x[:, lo:hi], delta[:, lo:hi],
                                    bm[:, lo:hi], cm[:, lo:hi], a, d, s)
        ys.append(y)
    np.testing.assert_allclose(jnp.concatenate(ys, 1), y_want, atol=1e-5,
                               rtol=0)
    np.testing.assert_allclose(s, s_want, atol=1e-5, rtol=0)
    s = state[0]
    for i in range(5):
        y, s = mamba.ssm_step(x[:, i], delta[:, i], bm[:, i], cm[:, i], a, d,
                              s)
        np.testing.assert_allclose(y, y_want[:, i], atol=1e-5, rtol=0)


# -- the cache: two kinds of state -------------------------------------------

def test_cache_holds_state_for_mamba_rows_for_attention_and_nothing_else(
        params):
    _, cache = _decode_all(params, CFG, TOKENS, prefill=8)
    n, di = CFG.mamba_d_state, CFG.mamba_d_inner
    assert (n, di) == (8, 128)
    assert CFG.cache_plan == {"rows": (2, 1, 16, 16), "state": (6, n, di),
                              "conv": (6, 3, di)}
    assert cache.k.shape == cache.v.shape == (2, 1, 1, 64, 16)
    assert cache.state.shape == (6, 1, n, di)  # channels last: the lanes
    assert cache.state.dtype == jnp.float32
    assert cache.conv.shape == (6, 1, 3, di)
    assert len(jax.tree.leaves(cache)) == 4
    # the two attention layers wrote their rows, and only the rows fed;
    # every state-space layer's state and tail moved
    written = np.asarray(jnp.abs(cache.k).sum(-1) > 0)[:, 0, 0]
    assert written[:, :len(TOKENS)].all()
    assert not written[:, len(TOKENS):].any()
    assert (np.abs(np.asarray(cache.state)).reshape(6, -1).max(1) > 0).all()
    assert (np.abs(np.asarray(cache.conv)).reshape(6, -1).max(1) > 0).all()


def test_padded_rows_leave_state_and_tail_untouched(params):
    """A bucketed chunk: 11 true tokens padded to 16. With the true length
    told, state and tail are those of the 11 tokens alone (a padded token
    has ``delta = 0``: it neither decays nor writes), and the logits of
    the true positions are unchanged; untold, the padding advances them."""
    want, alone = _all_logits(params, CFG, TOKENS[:11], 64)
    padded = np.concatenate([TOKENS[:11], np.full(5, 7, np.int32)])
    got, told = _all_logits(params, CFG, padded, 64,
                            valid=jnp.asarray([11], jnp.int32))
    np.testing.assert_allclose(got[:11], want, atol=2e-5, rtol=0)
    np.testing.assert_array_equal(np.asarray(told.state),
                                  np.asarray(alone.state))
    np.testing.assert_array_equal(np.asarray(told.conv),
                                  np.asarray(alone.conv))
    _, untold = _all_logits(params, CFG, padded, 64)
    assert float(jnp.abs(untold.state - alone.state).max()) > 1e-3


def test_hbm_budget_counts_state_and_rows_of_the_preset():
    """The published sizes whole: 5.95 GiB of weights with the tied matrix
    held twice (embedding and head), and at 64 slots x 2048
    rows 9,318,400 bytes of state and tails a stream and 1,024 bytes of
    rows a token."""
    from cake_tpu.utils.memory import hbm_budget

    cfg = jamba2_3b(max_seq_len=2048)
    b = hbm_budget(cfg, batch=64, max_seq=2048)
    mixer = (2560 * 10240 + 5120 * 2560 + 5120 * 192 + 160 * 5120
             + 4 * 5120 + 3 * 5120 + 16 * 5120 + 160 + 32)
    layers = (26 * mixer + 2 * (2 * 2560 * 2560 + 2 * 2560 * 128)
              + 28 * (3 * 2560 * 8192 + 2 * 2560))
    assert (mixer, layers) == (41241792, 2861562752)
    assert b["layers"] == 2 * layers
    assert b["embed_replicated"] == 2 * 65536 * 2560
    per_stream = 26 * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert per_stream == 9318400  # cache.state_bytes_per_stream
    assert b["kv_cache"] == 64 * per_stream + 64 * 2048 * 2 * 2 * 128 * 2
    with pytest.raises(ValueError, match="no int8 form"):
        hbm_budget(cfg, quant="int8")


# -- the engine --------------------------------------------------------------------

def _engine(params, prompts, ids=None, cfg=CFG, **kw):
    from cake_tpu.runtime.batch_generator import BatchGenerator

    kw.setdefault("block_size", 4)
    bg = BatchGenerator(cfg, params, settings=SamplerSettings(**GREEDY),
                        max_seq=64, **kw)
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


_ALONE: dict = {}


def _alone(params, prompt, n):
    """A stream's first ``n`` greedy tokens from an engine of its own
    (24 are generated once a prompt and kept; a fresh ``set_prompts`` on
    one engine starts from a fresh cache)."""
    key = tuple(prompt)
    if key not in _ALONE:
        if "engine" not in _ALONE:  # one engine's programs for all of them
            _ALONE["engine"] = _engine(params, [prompt])
        else:
            _ALONE["engine"].set_prompts([prompt])
        _ALONE[key] = _ALONE["engine"].generate(24)[0]
    return _ALONE[key][:n]


PROMPTS = [[5, 9, 2, 11], [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5], [7, 7, 2],
           [8, 6, 7, 5, 3, 0, 9]]


def test_batch_generator_streams_match_reference(params, tensors):
    """Four streams of different lengths through BatchGenerator (a bucketed
    batch prefill whose padding may not touch a state, per-row positions,
    block decode): each stream's greedy tokens are the reference's own
    greedy continuation, by its logits' argmax with a margin check; the
    gauges count the state."""
    reg = metrics.registry()
    bg = _engine(params, PROMPTS)
    outs = bg.generate(20)
    # the first two streams, each with as many of its tokens as make 24
    # (one sequence length: the reference runs op by op, and every new
    # shape compiles anew); the others against engines of their own below
    for prompt, out in zip(PROMPTS[:2], outs):
        out = list(out)[:24 - len(prompt)]
        full = np.array(prompt + out)
        logits = np.asarray(ref.logits(CFG.to_hf_dict(), tensors, full))
        for j, tok in enumerate(out):
            at = logits[len(prompt) - 1 + j]
            assert at.max() - at[tok] <= TIGHT, (prompt, j)
    assert bg.stats()["tokens_emitted"] == 4 * 20
    per_stream = 6 * (8 * 128 * 4 + 3 * 128 * 4)
    assert reg.gauge("cache.state_bytes_per_stream").value == per_stream
    assert reg.gauge("cache.state_bytes").value == 4 * per_stream
    assert reg.gauge("cache.row_bytes").value == 2 * 16 * 4  # one KV head
    assert reg.gauge("cache.bytes").value == (
        4 * per_stream + 2 * 4 * 64 * 2 * 16 * 4)
    assert reg.gauge("ssm.decode_kernel").value == 0  # XLA off the chip
    for prompt, out in zip(PROMPTS[2:], outs[2:]):
        assert list(out) == _alone(params, prompt, 20)


@pytest.mark.parametrize("admit_chunk", [None, 4],
                         ids=["one-chunk", "chunks-of-4"])
def test_slot_reuse_starts_from_a_fresh_state(params, admit_chunk):
    """SLOT REUSE: a short stream admitted into the slot a long one left
    gives the tokens a fresh engine gives it (the slot's state and tail
    have no frontier that would hide the old stream's), whether its
    admission is one chunk or chunks of 4 that carry state and tail
    between them; ``ssm.state_resets`` counts the admission."""
    long, short = PROMPTS[1] * 3, [4, 8, 15, 16, 23, 42, 10]
    resets = metrics.registry().counter("ssm.state_resets")
    before = resets.value
    bg = _engine(params, [long, PROMPTS[0]], ids=[1, 2],
                 admit_chunk=admit_chunk)
    got = _run(bg, {6: lambda e: (e.finish(1), e.enqueue(short, 3))},
               steps=30)
    assert resets.value - before == 1
    assert len(got[3]) >= 8
    assert got[3][:8] == _alone(params, short, 8)
    # the neighbour never noticed
    assert got[2][:12] == _alone(params, PROMPTS[0], 12)


def test_four_streams_with_admissions_mid_flight_equal_each_alone(params):
    events = {
        3: lambda e: e.enqueue(PROMPTS[2], 12),
        5: lambda e: e.finish(10),
        9: lambda e: (e.finish(11), e.enqueue(PROMPTS[3], 13)),
    }
    bg = _engine(params, PROMPTS[:2], ids=[10, 11], admit_chunk=4)
    got = _run(bg, events, steps=36)
    for sid, prompt in ((12, PROMPTS[2]), (13, PROMPTS[3])):
        assert len(got[sid]) >= 8
        assert got[sid][:8] == _alone(params, prompt, 8), sid
    assert got[10] == _alone(params, PROMPTS[0], 9)[:len(got[10])]
    assert got[11] == _alone(params, PROMPTS[1], 24)[:len(got[11])]


# -- the layer plan ------------------------------------------------------------

def test_layer_plan_finds_the_period_of_14():
    """M7 A M6, twice: maximal stretches (M7 A M13 A M6) would hide the
    period; the plan ends a stretch where a repetition of the whole
    model's period ends, so the program holds ONE run of three segments
    scanned over two repetitions, not five segments."""
    def shape(cfg):
        return [(r.repeats, r.stride, [
            (s.name, s.mixer, s.count, s.cache_first, s.cache_stride)
            for s in r.segments]) for r in llama.layer_plan(cfg)]

    full = jamba2_3b()
    assert [i for i, (m, _) in enumerate(full.layer_kinds)
            if m == "gqa"] == [7, 21]
    assert shape(full) == [(2, 14, [
        ("mamba_dense", "mamba", 7, 0, 13), ("gqa_dense", "gqa", 1, 0, 1),
        ("mamba_dense_2", "mamba", 6, 7, 13)])]
    assert sum(llama.stack_layers(full).values()) == 28
    run, = llama.layer_plan(full)
    ids = run.layer_ids(run.segments[2])
    assert ids.shape == (2, 6) and ids[0, 0] == 8 and ids[1, 5] == 27
    assert shape(CFG) == [(2, 4, [
        ("mamba_dense", "mamba", 1, 0, 3), ("gqa_dense", "gqa", 1, 0, 1),
        ("mamba_dense_2", "mamba", 2, 1, 3)])]
    # a depth that is no whole number of periods falls back to stretches
    odd = tiny_jamba(num_hidden_layers=7)
    assert [len(r.segments) for r in llama.layer_plan(odd)] == [1, 1, 1, 1,
                                                                1]


def test_tiny_fixture_scans_as_a_period(params):
    """The period's stacks lead ``[2, layers]`` and are scanned over their
    repetitions: the lowered program holds one outer loop around the three
    segments' own, and a second repetition's layers index the cache buffers
    of their kind where the first's stopped."""
    assert params["layers"]["mamba_dense_2"]["w_in"].shape[:2] == (2, 2)
    assert params["layers"]["gqa_dense"]["wq"].shape[:2] == (2, 1)
    assert params["layers"]["mamba_dense"]["a_log"].shape == (2, 1, 8, 128)
    cache = init_cache(CFG, 1, 64)
    text = jax.jit(lambda p, t, c: llama.forward(p, t, c, 0, CFG)).lower(
        params, jnp.asarray(TOKENS[None, :1]), cache).as_text()
    assert text.count("stablehlo.while") == 4  # the period, its 3 segments


# -- loader, writer, configuration -----------------------------------------------

def test_checkpoint_round_trip_under_hf_names_with_a_tied_head(
        tmp_path, params, want):
    """Through the real writer and loader: the same pytree, the same
    logits; Hugging Face's Jamba names, the convolution's taps stored as
    torch depthwise ``[C, 1, K]`` beside its bias, ``A_log`` as ``[d_inner,
    d_state]``, no ``lm_head.weight`` (the head is the embedding)."""
    from safetensors.numpy import load_file

    save_llama_params(params, tmp_path, config=CFG)
    (tmp_path / "config.json").write_text(json.dumps(CFG.to_hf_dict()))
    stored = load_file(tmp_path / "model.safetensors")
    m = "model.layers.0.mamba."
    assert stored[m + "conv1d.weight"].shape == (128, 1, 4)
    assert stored[m + "conv1d.bias"].shape == (128,)
    assert stored[m + "A_log"].shape == (128, 8)
    assert stored[m + "in_proj.weight"].shape == (256, 64)
    assert stored[m + "x_proj.weight"].shape == (8 + 2 * 8, 128)
    assert stored[m + "dt_proj.weight"].shape == (128, 8)
    assert stored[m + "dt_proj.bias"].shape == stored[m + "D"].shape == (128,)
    for n in ("dt", "b", "c"):
        assert f"{m}{n}_layernorm.weight" in stored
    assert stored["model.layers.1.self_attn.k_proj.weight"].shape == (16, 64)
    assert "model.layers.1.mamba.in_proj.weight" not in stored
    assert "model.layers.3.feed_forward.gate_proj.weight" in stored
    assert "model.layers.3.pre_ff_layernorm.weight" in stored
    assert "model.final_layernorm.weight" in stored
    assert "lm_head.weight" not in stored and "model.norm.weight" not in stored
    cfg = LlamaConfig.from_hf_json(tmp_path / "config.json", dtype="float32",
                                   max_seq_len=CFG.max_seq_len)
    assert cfg == dataclasses.replace(CFG, eos_token_id=cfg.eos_token_id)
    loaded = load_llama_params(tmp_path, cfg.num_hidden_layers,
                               dtype="float32")
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    cache = init_cache(cfg, batch=1, max_seq=64)
    logits, _ = llama.forward(loaded, jnp.asarray(TOKENS[None]), cache, 0, cfg)
    np.testing.assert_allclose(logits[0], want[-1], atol=TIGHT, rtol=0)
    with pytest.raises(NotImplementedError, match="no int8 form"):
        load_llama_params(tmp_path, cfg.num_hidden_layers, quantize="int8")


def test_preset_holds_the_catalogs_widths_and_round_trips():
    """``jamba2_3b()``: the published file's numbers (the catalog's row),
    and the file a checkpoint of it carries reads back as the same
    config."""
    cfg = jamba2_3b()
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size) == (
        28, 2560, 65536)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim, cfg.intermediate_size) == (20, 1, 128, 8192)
    assert (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
            cfg.mamba_dt_rank, cfg.mamba_conv_bias) == (5120, 16, 4, 160,
                                                        True)
    assert (cfg.attn_layer_period, cfg.attn_layer_offset) == (14, 7)
    assert cfg.tie_word_embeddings and cfg.rope_dim == 0
    assert cfg.family is families.STATE_SPACE and cfg.segmented
    assert cfg.cache_row == (1, 128, 128)
    assert cfg.cache_plan == {"rows": (2, 1, 128, 128),
                              "state": (26, 16, 5120),
                              "conv": (26, 3, 5120)}
    assert cfg.attn_scale == pytest.approx(128 ** -0.5)
    hf = cfg.to_hf_dict()
    assert (hf["model_type"], hf["num_experts"], hf["mamba_proj_bias"]) == (
        "jamba", 1, False)
    assert LlamaConfig.from_hf_dict(
        hf, max_seq_len=cfg.max_seq_len, dtype=cfg.dtype) == cfg
    # the published file as the catalog holds it: "auto" rank included
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
        "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None,
        "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536}
    read = LlamaConfig.from_hf_dict(published, max_seq_len=262144,
                                    bos_token_id=1, eos_token_id=2)
    assert read == cfg
    assert LlamaConfig.from_hf_dict(
        dict(published, mamba_dt_rank="auto")).mamba_dt_rank == 160


def _hf(**over):
    return dict(CFG.to_hf_dict(), **over)


@pytest.mark.parametrize("what, match", [
    (lambda p: validate_shardable(CFG, 2, 1), "one stage"),
    (lambda p: validate_shardable(CFG, 1, 2), "tp over d_inner"),
    (lambda p: validate_shardable(CFG, 1, 1, 1, 2), "ep = 1"),
    (lambda p: _engine(p, [[1, 2]], kv_layout="paged"), "slot layout"),
    (lambda p: _engine(p, [[1, 2]], spec_k=2), "recurrent state"),
    (lambda p: init_cache(CFG, quant="int8"), "int8 cache"),
    (lambda p: init_cache(CFG, num_layers=2), "cached whole"),
    (lambda p: llama.layer_shapes(CFG), "stack a kind"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(num_experts=16)), "num_experts"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(sliding_window=4096)),
     "sliding_window"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(mamba_proj_bias=True)),
     "mamba_proj_bias"),
    (lambda p: tiny_jamba(kv_lora_rank=16, qk_rope_head_dim=8,
                          v_head_dim=16), "no latent keys"),
    (lambda p: tiny_jamba(attn_layer_offset=4), "outside the period"),
], ids=["stages", "tp", "ep", "paged", "speculation", "int8-cache",
        "layer-range", "one-stack", "experts", "window", "proj-bias",
        "latent-keys", "offset"])
def test_family_limits_are_refused_with_a_message(params, what, match):
    with pytest.raises(ValueError, match=match):
        what(params)


def test_prefix_reuse_is_off_for_a_recurrent_state(params):
    """A stored row's state is the state at the END of the prompt that left
    it: the engine keeps no prefix store for this family, whatever it was
    asked for."""
    bg = _engine(params, [[5, 9, 2, 11]], prefix_cache_entries=4)
    assert bg._prefix_entries == 0


# -- the decode kernel -----------------------------------------------------------

@pytest.mark.parametrize("slots, n, c, chan", [(8, 16, 256, 128),
                                               (3, 8, 128, 128)],
                         ids=["b8-n16", "b3-n8"])
def test_ssm_decode_kernel_is_the_step(slots, n, c, chan):
    """``ops.pallas.mamba.ssm_decode`` (interpreted here) against
    ``ssm_step``: the chosen layer of the stacked state advances in place
    and no other layer is touched."""
    from cake_tpu.ops.pallas.mamba import ssm_decode

    x, delta, bm, cm, a, d, state = _ssm_inputs(slots, 1, n, c, seed=1)
    y_want, s_want = mamba.ssm_step(x[:, 0], delta[:, 0], bm[:, 0], cm[:, 0],
                                    a, d, state[1])
    y, s = ssm_decode(x[:, 0], delta[:, 0], bm[:, 0], cm[:, 0], a, d, state,
                      jnp.int32(1), chan_block=chan, interpret=True)
    np.testing.assert_allclose(y, y_want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(s[1], s_want, atol=2e-6, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(s[0]), np.asarray(state[0]))
    np.testing.assert_array_equal(np.asarray(s[2]), np.asarray(state[2]))


def test_ssm_scan_kernel_is_the_recurrence():
    """``ops.pallas.mamba.ssm_scan`` (interpreted) against the recurrence:
    48 tokens in token blocks of 16 and channel blocks of 128, each slot
    from its own state, padded tokens (``delta = 0``) leaving it alone."""
    from cake_tpu.ops.pallas.mamba import ssm_scan

    x, delta, bm, cm, a, d, state = _ssm_inputs(2, 48, 8, 256, seed=2)
    delta = delta.at[1, 40:].set(0.0)  # row 1 has 40 true tokens
    y_want, s_want = mamba.ssm_recurrence(x, delta, bm, cm, a, d, state[2])
    y, s = ssm_scan(x, delta, bm, cm, a, d, state, jnp.int32(2),
                    chan_block=128, token_block=16, interpret=True)
    np.testing.assert_allclose(y, y_want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(s[2], s_want, atol=2e-6, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(s[0]), np.asarray(state[0]))
    _, s40 = mamba.ssm_recurrence(x[1:, :40], delta[1:, :40], bm[1:, :40],
                                  cm[1:, :40], a, d, state[2, 1:])
    np.testing.assert_allclose(s[2, 1:], s40, atol=2e-6, rtol=1e-6)


def test_decode_and_admission_through_the_kernels_match_reference(
        params, want, monkeypatch):
    """With kernels forced (``CAKE_PALLAS=1``: interpreted off the chip)
    the admission chunk goes through ``ssm_scan`` and the decode steps
    through ``ssm_decode`` on the carried state, and the logits are still
    the reference's; the gauge says which step the program holds."""
    monkeypatch.setenv("CAKE_PALLAS", "1")
    assert mamba.ssm_decode_choice(8, 128) == "kernel"
    assert mamba.ssm_scan_choice(8, 8, 128) == "kernel"
    assert mamba.ssm_scan_choice(12, 8, 128) == "xla"  # no whole groups
    got, _ = _decode_all(params, CFG, TOKENS[:14], prefill=8)
    np.testing.assert_allclose(got, want[7:14], atol=TIGHT, rtol=0)
    assert metrics.registry().gauge("ssm.decode_kernel").value == 1
    monkeypatch.setenv("CAKE_PALLAS", "0")
    assert mamba.ssm_decode_choice(16, 5120) == "xla"
