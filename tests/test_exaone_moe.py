"""Window and full grouped-query attention mixed by layer (K-EXAONE's keys:
window layers that rotate q and k and hold a ring of ``R`` rows a stream
beside one full, rope-less layer in four, QK-normed heads, a told share of
sigmoid-routed experts with a routing bias beside a shared one) against
the plain reference ``cake_tpu/testing/reference_exaone_moe.py``, on seeded
random weights at tiny widths that keep the published pattern
(``models.config.tiny_exaone_moe``: ``LLLG`` twice, a window of 8, a ring
of 16 rows, a leading dense layer).

Tolerances. Everything here is float32 on the CPU, where XLA's matmuls are
full precision. Program and reference differ in the order of sums only
(grouped against repeated key/value heads, a band of blocks or a ring in
row order against a whole masked score matrix, the dense expert form
against a Python loop over the experts): measured 5e-6 to 9e-6 on logits
of magnitude ~3 through eight layers over 200 tokens. ``TIGHT`` is 1e-4,
ten times the worst; a window of one key more or fewer moves the logits
by 1e-2 and more (checked below), so a mask that is off by one fails.
"""

from __future__ import annotations

import json
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import (LlamaConfig, kexaone_ep8,
                                    tiny_exaone_moe, tiny_mellum)
from cake_tpu.obs import metrics
from cake_tpu.ops import attention, kvcache, moe
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.rope import rope_tables_for
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.parallel.mesh import validate_shardable
from cake_tpu.testing import reference_exaone_moe as ref
from cake_tpu.utils.weights import (latent_hf_tensors, load_llama_params,
                                    save_llama_params)

TIGHT = 1e-4
WINDOW = 8
CFG = tiny_exaone_moe(max_seq_len=256, eos_token_id=-1)
TOKENS = np.random.default_rng(40).integers(3, 250, 20 * WINDOW).astype(
    np.int32)
GREEDY = dict(temperature=0.0, repeat_penalty=1.0)


def _params(cfg=CFG, seed=0):
    """Seeded weights whose norm scales (the heads' q and k norms among
    them) are not all ones and whose routing bias is large enough to
    change choices: what is applied twice, not at all, after the rotation
    or to the weights shows."""
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

    return jax.tree_util.tree_map_with_path(jitter, params)


@pytest.fixture(scope="module")
def params():
    return _params()


@pytest.fixture(scope="module")
def tensors(params):
    return latent_hf_tensors(params, CFG)


@pytest.fixture(scope="module")
def want(tensors):
    """The reference's logits at every position of TOKENS (20 windows)."""
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
                       cfg=CFG):
    """Logits at every position through the cache: a prefill of
    ``prefill`` tokens in chunks of ``chunk`` (the last padded to
    ``bucket`` rows, its true length told), then a step a token."""
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
    (3 * WINDOW, 11, 11, 16),  # a bucket's padding, inside the first ring
    (9 * WINDOW, 40, 4, None),  # chunks shorter than the window
    (9 * WINDOW, 48, 24, None),  # a chunk that is no whole block of R rows
    (20 * WINDOW, 100, 100, 128),  # one chunk of eight blocks, padded
    (20 * WINDOW, 64, 32, None),  # chunks of whole blocks after a ring
], ids=["3-windows-padded", "9-windows-chunks-of-4", "9-windows-chunks-of-24",
        "20-windows-one-chunk-padded", "20-windows-chunks-of-32"])
def test_prefill_then_decode_through_the_cache_match_reference(
        params, want, context, prefill, chunk, bucket):
    """Prefill then decode through rings and rows, against the reference's
    one forward over the whole sequence under explicit masks, at every
    position up to ``context``: the rings wrap 1 to 10 times."""
    got, cache = _through_the_cache(params, TOKENS[:context], prefill, chunk,
                                    bucket)
    np.testing.assert_allclose(got, want[:context], atol=TIGHT, rtol=0)
    assert cache.ring_k.shape == (6, 1, 2, 16, 16)
    assert cache.k.shape == (2, 1, 2, 256, 16)


@pytest.mark.parametrize("window", [WINDOW - 1, WINDOW + 1])
def test_a_window_of_one_key_more_or_fewer_fails_the_tolerance(
        tensors, want, window):
    """The control of the mechanism: the reference with 7 or 9 keys a
    window layer is another model by far more than ``TIGHT``."""
    off = np.asarray(ref.logits(CFG.to_hf_dict(), tensors, TOKENS[:72],
                                window=window))
    assert np.abs(off[WINDOW:] - want[WINDOW:72]).max() > 100 * TIGHT
    # before the window is full the two agree: the masks are the same
    np.testing.assert_allclose(off[:WINDOW - 1], want[:WINDOW - 1],
                               atol=TIGHT, rtol=0)


def _whole_rows_logits(params, cfg, tokens):
    """The same model with every window layer's rows held WHOLE and masked
    by position (``ops.attention``'s windowed path over an ``S``-row
    buffer, as a model with one window for all layers runs): prefill of
    all but the last 8 tokens, then a step a token."""
    eps, s = cfg.rms_norm_eps, 256
    cos, sin = rope_tables_for(cfg, s)
    at = {int(i): (seg.name, j) for run, seg in llama.plan_segments(cfg)
          for j, i in enumerate(run.layer_ids(seg))}
    caches = [tuple(jnp.zeros((1, 2, s, 16)) for _ in "kv")
              for _ in range(cfg.num_hidden_layers)]

    def forward(tokens, pos, caches):
        x = llama.embed_tokens(params, jnp.asarray(tokens)[None], cfg)
        new = []
        for i, (mixer, _) in enumerate(cfg.layer_kinds):
            name, j = at[i]
            layer = jax.tree.map(lambda w: w[j], params["layers"][name])
            h = rms_norm(x, layer["attn_norm"], eps)
            swa = mixer == "swa"
            out, k, v = attention.self_attention_block(
                h, layer["wq"], layer["wk"], layer["wv"], layer["wo"],
                *caches[i], cos[mixer], sin[mixer], pos,
                4, 2, window=WINDOW if swa else None,
                qk_norm=(layer["q_norm"], layer["k_norm"], eps))
            new.append((k, v))
            x, _ = llama._shared_feed_forward(layer, x + out, cfg, None,
                                              None, False, None)
        x = rms_norm(x, params["norm_f"], eps)
        return x[0] @ params["lm_head"], new

    n = len(tokens) - 8
    first, caches = forward(tokens[:n], 0, caches)
    out = [np.asarray(first)]
    for i in range(n, len(tokens)):
        logits, caches = forward(tokens[i:i + 1], i, caches)
        out.append(np.asarray(logits))
    return np.concatenate(out)


def test_the_ring_is_storage_not_mathematics(params):
    """The served model with rings gives the logits of the same model with
    its window layers' rows held whole and masked: 6 x 16 ring rows a
    stream against 6 x 256."""
    tokens = TOKENS[:72]
    got, _ = _through_the_cache(params, tokens, 64, 64)
    np.testing.assert_allclose(got, _whole_rows_logits(params, CFG, tokens),
                               atol=2e-5, rtol=0)


def test_padded_rows_never_enter_a_ring(params):
    """A bucketed chunk: 11 true tokens padded to 16. With the true length
    told, the rings are those of the 11 tokens alone; untold, the padding
    has taken five rows of every ring."""
    pad = np.concatenate([TOKENS[:11], np.full(5, 7, np.int32)])[None]
    fresh = init_cache(CFG, batch=1, max_seq=256)
    _, alone = _STEP(params, CFG, TOKENS[None, :11], fresh, jnp.int32(0))
    _, told = _STEP(params, CFG, pad, fresh, jnp.int32(0),
                    jnp.asarray([11], jnp.int32))
    _, untold = _STEP(params, CFG, pad, fresh, jnp.int32(0))
    for leaf in ("ring_k", "ring_v"):
        np.testing.assert_allclose(getattr(told, leaf), getattr(alone, leaf),
                                   atol=1e-6, rtol=0)
        assert not np.asarray(getattr(told, leaf))[:, :, :, 11:].any()
        assert np.abs(np.asarray(getattr(untold, leaf))[:, :, :, 11:]).max() > 0


def test_ring_positions_and_chunk_writes():
    """Position ``p`` lives at row ``p % R``: after a write up to ``last``
    every row holds the largest ``p <= last`` congruent to it (negative:
    never written); a chunk's newest ``R`` true rows land, older ones and
    padding do not, and rows the chunk does not reach keep what they
    held."""
    np.testing.assert_array_equal(
        kvcache.ring_positions(jnp.int32(5), 4), [4, 5, 2, 3])
    np.testing.assert_array_equal(
        kvcache.ring_positions(jnp.asarray([1, 9]), 4),
        [[0, 1, -2, -1], [8, 9, 6, 7]])
    ring = -jnp.ones((2, 2, 1, 4, 1))
    new = jnp.arange(100, 112, dtype=jnp.float32).reshape(2, 1, 6, 1)
    k, v = kvcache.ring_write(ring, ring, new, -new, jnp.int32(3),
                              jnp.int32(1), valid=jnp.asarray([6, 2]))
    assert (np.asarray(k[0]) == -1).all()  # the other layer: untouched
    # row 0 of the batch: positions 3..8 arrive, 5..8 stay (8 % 4 == 0)
    np.testing.assert_array_equal(k[1, 0, 0, :, 0], [105, 102, 103, 104])
    # row 1: two true tokens (positions 3, 4), the rest is padding
    np.testing.assert_array_equal(k[1, 1, 0, :, 0], [107, -1, -1, 106])
    np.testing.assert_array_equal(v[1, 0], -k[1, 0])
    np.testing.assert_array_equal(v[1, 1, 0, :, 0], [-107, -1, -1, -106])
    one = jnp.full((2, 1, 1, 1), 7.0)
    k, _ = kvcache.ring_write(ring, ring, one, one, jnp.asarray([6, 1]),
                              jnp.int32(0))
    np.testing.assert_array_equal(k[0, :, 0, :, 0],
                                  [[-1, -1, 7, -1], [-1, 7, -1, -1]])


@pytest.mark.parametrize("make, full_rotates", [
    (tiny_exaone_moe, False), (tiny_mellum, True)],
    ids=["exaone_moe", "mellum"])
def test_full_layers_do_not_rotate_and_window_layers_do(make, full_rotates):
    """The rotation is the layer KIND's, read from the file
    (``LlamaConfig.layer_rope``). K-EXAONE: a full layer takes no table:
    whatever positions the tables hold (shifted by a constant, or doubled)
    its output is the same to the bit. Mellum: a full layer rotates, by a
    table of its own (YaRN's, another than the window layers'). A window
    layer rotates in both: doubled positions move its output, and a
    constant shift does not (rotation is relative). The heads' norms come
    BEFORE the rotation: a weight that differs between a pair's two
    channels does not commute with it, and the reference agrees
    (``test_prefill_then_decode...``) with such weights."""
    cfg = make(max_seq_len=256, eos_token_id=-1)
    params = _params(cfg)
    cos, sin = rope_tables_for(cfg, 256)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 16, cfg.hidden_size))
    cache = init_cache(cfg, batch=1, max_seq=256)

    def block(name, mixer, change=lambda t: t):
        """The first layer of stack ``name`` under the tables with this
        kind's changed (None stays None)."""
        run, seg = next((r, s) for r, s in llama.plan_segments(cfg)
                        if s.name == name)
        lead = run.layer_ids(seg).ndim  # 2 inside a repeated period
        layer = jax.tree.map(lambda w: w[(0,) * lead], params["layers"][name])
        tables = [dict(t, **{mixer: t[mixer] if t[mixer] is None
                             else change(t[mixer])}) for t in (cos, sin)]
        return llama._typed_block(
            layer, x, cache, mixer, *tables, 0, cfg, None, None, None,
            jnp.int32(0), False, None)[0]

    def shifted(t):
        return t[7:]

    def doubled(t):
        return t[::2]

    assert (cos["gqa"] is not None) == full_rotates
    for seg_name, mixer in (("gqa_moe", "gqa"), ("swa_moe", "swa")):
        base = block(seg_name, mixer)
        if mixer == "gqa" and not full_rotates:
            for change in (shifted, doubled):
                np.testing.assert_array_equal(
                    block(seg_name, mixer, change), base)
            continue
        np.testing.assert_allclose(block(seg_name, mixer, shifted), base,
                                   atol=2e-5, rtol=0)
        assert np.abs(block(seg_name, mixer, doubled) - base).max() > 1e-2
    if full_rotates:  # two tables in one program, and they differ
        assert np.abs(cos["gqa"] - cos["swa"]).max() > 0.1
    q = params["layers"]["swa_moe"]["q_norm"].reshape(-1, cfg.head_dim)[0]
    assert np.abs(q[:8] - q[8:]).max() > 0.05  # the pairs' weights differ


# -- the share ------------------------------------------------------------------

def _expert_layer(params, cfg, h, first, count):
    """The program's expert layer of the first sparse layer: the routed
    part of a told share, and the shared expert."""
    layer = jax.tree.map(lambda a: a[0], params["layers"]["swa_moe"])
    routed = moe.moe_swiglu(
        h, layer["router"], layer["w_gate"][first:first + count],
        layer["w_up"][first:first + count],
        layer["w_down"][first:first + count], top_k=cfg.num_experts_per_tok,
        routing=moe.GroupRouting(cfg.n_group, cfg.topk_group,
                                 cfg.norm_topk_prob,
                                 cfg.routed_scaling_factor,
                                 layer["b_router"]),
        held=(first, count))
    from cake_tpu.ops.mlp import swiglu

    return routed, swiglu(h, layer["ws_gate"], layer["ws_up"],
                          layer["ws_down"])


@pytest.mark.parametrize("rows", [6, 96], ids=["step-rows", "chunk-rows"])
def test_shares_add_up_to_the_uncut_layer(rows):
    """THE SHARE TEST: the routed parts that all 4 ``ep`` shares (4 of 16
    experts each) give, plus the shared expert counted once, add up to the
    uncut reference layer, and one share alone is the reference's same
    share; the routing bias enters the choice only."""
    whole_cfg = tiny_exaone_moe(max_seq_len=256, n_routed_experts=16,
                                first_expert=0)
    params = _params(whole_cfg)
    h = jax.random.normal(jax.random.PRNGKey(9),
                          (1, rows, whole_cfg.hidden_size))
    parts = [_expert_layer(params, whole_cfg, h, 4 * r, 4) for r in range(4)]
    total = sum(p[0] for p in parts) + parts[0][1]
    tensors = latent_hf_tensors(params, whole_cfg)
    hf = whole_cfg.to_hf_dict()
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(hf, tensors, "model.layers.1.", h[0])
        one = ref.expert_layer(hf, tensors, "model.layers.1.", h[0],
                               only=range(4, 8))
        unbiased = ref.expert_layer(
            hf, {k: v for k, v in tensors.items()
                 if "e_score_correction_bias" not in k},
            "model.layers.1.", h[0])
    np.testing.assert_allclose(total[0], want, atol=TIGHT, rtol=0)
    np.testing.assert_allclose(parts[1][0][0] + parts[1][1][0], one,
                               atol=TIGHT, rtol=0)
    assert float(jnp.abs(parts[1][0]).max()) > 0.01  # a share is something
    assert np.abs(unbiased - want).max() > 0.01  # ... and so is the bias


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
    """Three streams of different lengths (inside the first ring, 4 and 8
    windows) through BatchGenerator: a bucketed batch prefill whose
    padding may not enter a ring, per-row positions, block decode over
    rings that wrap; each stream's tokens are the reference's argmax. The
    gauges count both kinds of rows."""
    reg = metrics.registry()
    bg = _engine(params, PROMPTS[:3])
    outs = bg.generate(27)
    for prompt, out in zip(PROMPTS[:3], outs):
        _is_the_references_argmax(tensors, prompt, list(out)[:27])
    row = 2 * 2 * 16 * 4  # k and v, two heads of 16 float32 values
    assert reg.gauge("cache.row_bytes").value == row
    assert reg.gauge("cache.ring_rows").value == 16
    assert reg.gauge("cache.rows_bytes").value == 3 * row * (
        2 * 256 + 6 * 16)
    assert reg.gauge("cache.rows_bytes_full").value == 3 * row * 8 * 256
    assert reg.gauge("cache.bytes").value == reg.gauge(
        "cache.rows_bytes").value
    assert reg.gauge("attn.layers_swa").value == 6
    assert reg.gauge("attn.layers_full").value == 2
    assert reg.gauge("cache.state_bytes").value == 0


@pytest.mark.parametrize("admit_chunk", [None, 4],
                         ids=["one-chunk", "chunks-of-4"])
def test_a_reused_slot_sees_no_row_of_the_former_stream(params, tensors,
                                                        admit_chunk):
    """SLOT REUSE: a short stream admitted into the slot a long one left
    (whose rings had wrapped) gives the reference's tokens: the rows the
    long stream left in the rings are told from the new stream's by
    position alone, whether its admission is one chunk or chunks of 4
    that read the ring between them. The neighbour never notices."""
    long, short = PROMPTS[4], PROMPTS[5]
    bg = _engine(params, [long, PROMPTS[3]], ids=[1, 2],
                 admit_chunk=admit_chunk)
    got = _run(bg, {6: lambda e: (e.finish(1), e.enqueue(short, 3))},
               steps=30)
    assert len(got[3]) >= 10
    _is_the_references_argmax(tensors, short, got[3][:10])
    _is_the_references_argmax(tensors, PROMPTS[3], got[2][:12])


def test_admissions_among_live_streams_and_a_chained_one(params, tensors,
                                                         monkeypatch):
    """An admission among live streams, then two arrivals that wait
    together and ride ONE prefill program of two rows (PR 37's chain: a
    staging cache of two rows, rings and all, one splice): each stream's
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


def test_prefix_reuse_is_off_for_a_ring(params):
    """A stored row's ring is the ring at the END of the prompt that left
    it: the engine keeps no prefix store for this family, whatever it was
    asked for."""
    bg = _engine(params, [[5, 9, 2, 11]], prefix_cache_entries=4)
    assert bg._prefix_entries == 0 and bg._prefix_share_min == 0


# -- the configuration, the plan, the loaders -----------------------------------

def _catalog() -> dict:
    """The catalog's ``config`` of K-EXAONE-236B-A23B (the published
    ``config.json`` without the keys that say nothing of its shape)."""
    types = ["sliding_attention"] * 3 + ["full_attention"]
    return {
        "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 6144, "intermediate_size": 18432,
        "layer_types": types * 12, "max_position_embeddings": 262144,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47,
        "model_type": "exaone_moe", "moe_intermediate_size": 2048,
        "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0],
        "n_group": 1, "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 8,
        "num_nextn_predict_layers": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "sliding_window": 128, "sliding_window_pattern": "LLLG",
        "sliding_windows": [128, 128, 128, 0] * 12,
        "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 153600,
    }


def test_the_catalogs_keys_are_read_and_round_trip():
    """``from_hf_dict`` reads the published keys (its own spelling of the
    expert counts, the per-layer lists, ``rope_parameters``; the
    prediction block read and ignored), the preset is the same
    configuration with a chip's share told, and ``to_hf_dict`` writes what
    reads back."""
    published = _catalog()
    whole = LlamaConfig.from_hf_dict(published, max_seq_len=262144,
                                     bos_token_id=0, eos_token_id=1)
    assert (whole.n_routed_experts, whole.router_experts) == (128, 128)
    assert whole.layer_kinds[:5] == (
        ("swa", "dense"), ("swa", "moe"), ("swa", "moe"), ("gqa", "moe"),
        ("swa", "moe"))
    assert whole.cache_plan == {"rows": (12, 8, 128, 128),
                                "ring": (36, 8, 128, 128, 128)}
    assert (whole.qk_norm, whole.router_bias, whole.rope_theta,
            whole.rope_dim, whole.ring_rows) == (True, True, 1e6, 128, 128)
    share = dict(published, num_experts=16, expert_share={
        "n_routed_experts": 128, "ep": 8, "rank": 0})
    cut = LlamaConfig.from_hf_dict(share, max_seq_len=262144, bos_token_id=0,
                                   eos_token_id=1)
    assert cut == kexaone_ep8()
    back = cut.to_hf_dict()
    for key in ("layer_types", "mlp_layer_types", "sliding_windows",
                "rope_parameters", "num_shared_experts", "sliding_window",
                "first_k_dense_replace", "expert_share", "num_experts",
                "head_dim", "moe_intermediate_size", "scoring_func",
                "n_group", "topk_group", "routed_scaling_factor"):
        assert back[key] == share[key], key
    assert LlamaConfig.from_hf_dict(back, max_seq_len=262144) == cut
    # Hugging Face writes the list for every family; only this one reads it
    assert LlamaConfig.from_hf_dict({
        "model_type": "qwen2", "num_hidden_layers": 2,
        "layer_types": ["full_attention"] * 2}).layer_types is None
    tiny_back = LlamaConfig.from_hf_dict(CFG.to_hf_dict(), dtype="float32",
                                         max_seq_len=256, eos_token_id=-1)
    assert tiny_back == CFG
    # a window of 8 is kept in whole (16, 128) tiles; the published 128 is
    assert (CFG.ring_rows, CFG.cache_plan) == (
        16, {"rows": (2, 2, 16, 16), "ring": (6, 2, 16, 16, 16)})


def test_layer_plan_of_the_published_layers():
    """The published 48 layers (a leading dense layer, ``W W G``, then ``W
    W W G`` eleven times) are a dense window layer, a run of two sparse
    window layers, then a full layer and three window layers by turns, each
    stretch a scanned segment of its own: no period of expert layers is
    repeated (an admission of 128-256 rows takes the expert block's dense
    form, whose stacks the chip's compiler re-lays whole inside a period:
    ``models/llama.py`` ``layer_plan``). Every window segment runs the one
    window body and every full segment the one full body. The cache
    indices count the layers of a segment's own mixer's kind."""
    full = llama.layer_plan(kexaone_ep8())
    assert {r.repeats for r in full} == {1}
    segs = [(s.name, s.mixer, s.first, s.count, s.cache_first)
            for r in full for s in r.segments]
    assert segs[:4] == [
        ("swa_dense", "swa", 0, 1, 0), ("swa_moe", "swa", 1, 2, 1),
        ("gqa_moe", "gqa", 3, 1, 0), ("swa_moe_2", "swa", 4, 3, 3)]
    assert segs[-2:] == [("swa_moe_12", "swa", 44, 3, 33),
                         ("gqa_moe_12", "gqa", 47, 1, 11)]
    assert len(segs) == 25
    assert sum(llama.stack_layers(kexaone_ep8()).values()) == 48
    shapes = llama.stack_shapes(kexaone_ep8())
    assert shapes["gqa_moe"]["q_norm"](kexaone_ep8()) == (128,)
    assert shapes["swa_moe"]["router"](kexaone_ep8()) == (6144, 128)
    assert shapes["swa_moe"]["w_gate"](kexaone_ep8()) == (16, 6144, 2048)
    assert "b_router" in shapes["swa_moe_2"]
    assert "router" not in shapes["swa_dense"]
    # the benchmark's cut: the model's own first seven layers
    seven = llama.layer_plan(kexaone_ep8(num_hidden_layers=7))
    assert [(s.name, s.first, s.count, s.cache_first)
            for r in seven for s in r.segments] == [
        ("swa_dense", 0, 1, 0), ("swa_moe", 1, 2, 1), ("gqa_moe", 3, 1, 0),
        ("swa_moe_2", 4, 3, 3)]
    # the tiny fixture: W(dense) W W G, then W W W G
    assert [s.name for _, s in llama.plan_segments(CFG)] == [
        "swa_dense", "swa_moe", "gqa_moe", "swa_moe_2", "gqa_moe_2"]


def test_hbm_budget_counts_rings_and_rows_of_the_cut():
    """The benchmark's cut (7 layers, 16 of 128 experts, 19200 rows of the
    vocabulary) at 32 slots x 4096: 9.73 GiB of weights; one full layer's
    rows (0.5 GiB) and six rings of 128 rows (0.094 GiB) where whole
    window layers would hold 3.5 GiB: under a fifth."""
    from cake_tpu.utils.memory import hbm_budget

    cfg = kexaone_ep8(num_hidden_layers=7, vocab_size=19200, max_seq_len=4096)
    b = hbm_budget(cfg, batch=32, max_seq=4096)
    attn = 6144 * 8192 + 2 * 6144 * 1024 + 8192 * 6144 + 2 * 128 + 2 * 6144
    sparse = attn + 6144 * 128 + 128 + (16 + 1) * 3 * 6144 * 2048
    dense = attn + 3 * 6144 * 18432
    assert b["layers"] == 2 * (6 * sparse + dense)
    row = 2 * 8 * 128 * 2
    assert b["kv_cache"] == 32 * row * (4096 + 6 * 128)
    assert b["kv_cache"] < 0.75 * 2**30
    assert b["kv_cache"] / (32 * row * 7 * 4096) < 0.21
    assert 9.7 * 2**30 < b["total"] - b["kv_cache"] < 9.8 * 2**30
    with pytest.raises(ValueError, match="not wired"):
        hbm_budget(cfg, quant="int8")


def test_checkpoint_round_trip_skips_the_prediction_block(tmp_path, params,
                                                         want):
    """Through the real writer and loader: the same pytree, the same
    logits; the names the configuration assumes (``self_attn.q_norm``,
    ``mlp.gate.e_score_correction_bias``, the held experts under their
    GLOBAL ids 4-7); a next-token prediction block's ``mtp.*`` tensors are
    not read, and counted."""
    from safetensors.numpy import load_file, save_file

    save_llama_params(params, tmp_path, config=CFG)
    (tmp_path / "config.json").write_text(json.dumps(
        dict(CFG.to_hf_dict(), num_nextn_predict_layers=1)))
    stored = load_file(tmp_path / "model.safetensors")
    assert stored["model.layers.3.self_attn.q_norm.weight"].shape == (16,)
    assert stored["model.layers.0.mlp.gate_proj.weight"].shape == (128, 64)
    assert stored["model.layers.1.mlp.gate.weight"].shape == (16, 64)
    assert stored[
        "model.layers.1.mlp.gate.e_score_correction_bias"].shape == (16,)
    held = sorted(int(n.split(".")[5]) for n in stored
                  if n.startswith("model.layers.2.mlp.experts.")
                  and n.endswith("up_proj.weight"))
    assert held == [4, 5, 6, 7]
    assert "model.layers.0.mlp.gate.weight" not in stored
    stored.update({"mtp.layers.0.self_attn.q_proj.weight": np.ones((4, 4)),
                   "mtp.norm.weight": np.ones(4)})
    save_file({k: np.ascontiguousarray(v, np.float32)
               for k, v in stored.items()}, tmp_path / "model.safetensors")
    index = json.loads((tmp_path / "model.safetensors.index.json").read_text())
    index["weight_map"].update({k: "model.safetensors" for k in stored})
    (tmp_path / "model.safetensors.index.json").write_text(json.dumps(index))
    cfg = LlamaConfig.from_hf_json(tmp_path / "config.json", dtype="float32",
                                   max_seq_len=256, eos_token_id=-1)
    assert cfg == CFG
    skipped = metrics.registry().counter("load.tensors_skipped")
    before = skipped.value
    loaded = load_llama_params(tmp_path, cfg.num_hidden_layers,
                               dtype="float32")
    assert skipped.value - before == 2
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
    (lambda p: validate_shardable(CFG, 1, 2), "under tp or stages"),
    (lambda p: validate_shardable(CFG, 1, 1, 2), "sp = 1"),
    (lambda p: _engine(p, [[1, 2]], kv_layout="paged"), "slot layout"),
    (lambda p: _engine(p, [[1, 2]], spec_k=2), "overwritten a ring row"),
    (lambda p: init_cache(CFG, quant="int8"), "int8 cache"),
    (lambda p: init_cache(CFG, num_layers=2), "cached whole"),
    (lambda p: llama.layer_shapes(CFG), "stack a kind"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(
        sliding_windows=[8, 8, 8, 0, 8, 8, 4, 0])), "disagrees"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(layer_types=["full_attention"])),
     "1 entries for 8 layers"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(n_group=2, topk_group=3)),
     "group-limited"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(n_group=3)), "in 3 groups"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(rope_parameters={
        "rope_type": "yarn", "rope_theta": 1e6, "factor": 4.0})),
     "rope type 'yarn'"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(mlp_layer_types=[
        "dense", "sparse", "dense"] + ["sparse"] * 5,
        first_k_dense_replace=None)), "dense layers lead"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(first_k_dense_replace=2)),
     "dense layers lead"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(scoring_func="softmax")),
     "scoring_func"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(hidden_act="gelu")),
     "hidden_act"),
    (lambda p: tiny_exaone_moe(layer_types=("sliding_attention",) * 8),
     "without a full_attention layer"),
    (lambda p: tiny_exaone_moe(layer_types=("chunked_attention",) * 8),
     "needs one of"),
    (lambda p: tiny_exaone_moe(sliding_window=4), "8 or more"),
    (lambda p: tiny_exaone_moe(attention_bias=True), "no projection bias"),
    (lambda p: tiny_exaone_moe(first_expert=14), "held of 16"),
    (lambda p: LlamaConfig.from_hf_dict({
        "model_type": "qwen2", "num_hidden_layers": 8, "sliding_window": 64,
        "use_sliding_window": True, "max_window_layers": 4}),
     "per-layer layer_types"),
    (lambda p: LlamaConfig.from_hf_dict({
        "model_type": "jamba", "sliding_window": 64}),
     "by layer_types"),
], ids=["stages", "tp", "sp", "paged", "speculation", "int8-cache",
        "layer-range", "one-stack", "windows-disagree", "types-short",
        "topk-group", "groups", "rope-type", "dense-inside", "dense-count",
        "scoring", "activation", "no-full-layer", "unknown-type",
        "window-small", "bias", "share-outside", "partial-depth-window",
        "state-space-window"])
def test_family_limits_are_refused_with_a_message(params, what, match):
    with pytest.raises(ValueError, match=match):
        what(params)
