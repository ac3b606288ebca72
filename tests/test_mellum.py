"""Window and full grouped-query attention mixed by layer under TWO
rotations (Mellum2's keys: window layers that rotate q and k plainly and
hold a ring of ``R`` rows a stream beside one full layer in four that
rotates under YaRN with an explicit ``attention_factor``, QK-normed heads,
every layer routing over all softmax-scored experts with the chosen shares
renormalised, no shared expert, no bias) against the plain reference
``cake_tpu/testing/reference_mellum.py``, on seeded random weights at tiny
widths that keep the published pattern (``models.config.tiny_mellum``:
``LLLG`` twice, a window of 8, a ring of 16 rows, YaRN of factor 4 over an
original 16 positions, 16 experts top-4).

Tolerances. Everything here is float32 on the CPU, where XLA's matmuls are
full precision. Program and reference differ in the order of sums only
(grouped against repeated key/value heads, a band of blocks or a ring in
row order against a whole masked score matrix, softmax over the chosen
logits against softmax over all then renormalised, the dense expert form
against a Python loop over the experts): measured 5.3e-6 to 9.3e-6 on
logits of magnitude ~4.6 through eight layers over 160 tokens. ``TIGHT`` is
1e-4, ten times the worst. The wrong-mathematics controls move the logits
by 3.4 and 3.3 (a window of one key fewer or more), 1.2 (ONE rotation for
both kinds) and 0.5 (an ``attention_factor`` of 1), each checked below to
pass a hundred times ``TIGHT``: a mask that is off by one, a full layer
handed the window layers' table, or YaRN's amplitude left off cos and sin
fails.
"""

from __future__ import annotations

import json
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import LlamaConfig, mellum2_12b, tiny_mellum
from cake_tpu.obs import metrics
from cake_tpu.ops import attention, moe
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.rope import rope_tables_for
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.parallel.mesh import validate_shardable
from cake_tpu.testing import reference_mellum as ref
from cake_tpu.utils.weights import (latent_hf_tensors, load_llama_params,
                                    save_llama_params)

TIGHT = 1e-4
WINDOW = 8
ORIGINAL = 16  # YaRN's original_max_position_embeddings in the fixture
CFG = tiny_mellum(max_seq_len=256, eos_token_id=-1)
TOKENS = np.random.default_rng(55).integers(3, 250, 20 * WINDOW).astype(
    np.int32)
GREEDY = dict(temperature=0.0, repeat_penalty=1.0)


def _params(cfg=CFG, seed=0):
    """Seeded weights whose norm scales (the heads' q and k norms among
    them) are not all ones: what is applied twice, not at all or after
    the rotation shows. The router's logits are scaled up so that the
    softmax shares of the chosen experts differ by far more than
    rounding."""
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def jitter(path, leaf):
        name = path[-1].key
        k = jax.random.fold_in(  # (crc32: str hashes differ by process)
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
        if name.endswith("norm") or name == "norm_f":
            return leaf * (1.0 + 0.25 * jax.random.uniform(
                k, leaf.shape, minval=-1.0))
        if name == "router":
            return 3.0 * leaf
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
    """The reference's logits at every position of TOKENS (20 windows,
    ten times YaRN's original positions)."""
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
                       cfg=CFG, step=_STEP):
    """Logits at every position through the cache: a prefill of
    ``prefill`` tokens in chunks of ``chunk`` (the last padded to
    ``bucket`` rows, its true length told), then a step a token."""
    cache = init_cache(cfg, batch=1, max_seq=256)
    out = []
    for lo in range(0, prefill, chunk):
        n = min(chunk, prefill - lo)
        rows = np.full((1, bucket or chunk), 7, np.int32)
        rows[0, :n] = tokens[lo:lo + n]
        logits, cache = step(params, cfg, rows, cache, jnp.int32(lo),
                             jnp.asarray([n], jnp.int32))
        out.append(np.asarray(logits[0, :n]))
    for i in range(prefill, len(tokens)):
        logits, cache = step(params, cfg, tokens[None, i:i + 1], cache,
                             jnp.asarray([i], jnp.int32))
        out.append(np.asarray(logits[0]))
    return np.concatenate(out), cache


# -- against the reference -----------------------------------------------------

CASES = [
    (3 * WINDOW, 11, 11, 16),  # a bucket's padding, inside the first ring
    (9 * WINDOW, 40, 4, None),  # chunks shorter than the window
    (9 * WINDOW, 48, 24, None),  # a chunk that is no whole block of R rows
    (20 * WINDOW, 100, 100, 128),  # one chunk of eight blocks, padded
    (20 * WINDOW, 64, 32, None),  # chunks of whole blocks after a ring
]
CASE_IDS = ["3-windows-padded", "9-windows-chunks-of-4",
            "9-windows-chunks-of-24", "20-windows-one-chunk-padded",
            "20-windows-chunks-of-32"]


@pytest.mark.parametrize("context, prefill, chunk, bucket", CASES,
                         ids=CASE_IDS)
def test_prefill_then_decode_through_the_cache_match_reference(
        params, want, context, prefill, chunk, bucket):
    """Prefill then decode through rings and rows, each kind under its own
    rotation, against the reference's one forward over the whole sequence
    under explicit masks, at every position up to ``context``: the rings
    wrap 1 to 10 times and the positions run to ten times YaRN's original
    ``ORIGINAL``, where the two tables differ most."""
    assert context > 4 * ORIGINAL or bucket == 16
    got, cache = _through_the_cache(params, TOKENS[:context], prefill, chunk,
                                    bucket)
    np.testing.assert_allclose(got, want[:context], atol=TIGHT, rtol=0)
    assert cache.ring_k.shape == (6, 1, 2, 16, 16)
    assert cache.k.shape == (2, 1, 2, 256, 16)


@pytest.mark.parametrize("context, prefill, chunk, bucket", CASES[2:],
                         ids=CASE_IDS[2:])
def test_the_band_through_the_flash_kernel_matches_reference(
        params, want, monkeypatch, context, prefill, chunk, bucket):
    """The same comparison with every chunk's attention through the flash
    prefill kernel (interpreted), as the chip takes it for rings of 1024
    rows from chunks of 1024 on: the window layers' chunk over the
    ring-then-chunk buffer (``ops.attention._attend_ring_flash``: from
    position 0, from inside the first ring and after the rings have
    wrapped), the full layers' over their rows."""
    monkeypatch.setattr(attention, "_flash_prefill_choice",
                        lambda t, s, d: "flash")
    got, _ = _through_the_cache(
        params, TOKENS[:context], prefill, chunk, bucket,
        step=jax.jit(_logits, static_argnums=(1,)))
    np.testing.assert_allclose(got, want[:context], atol=TIGHT, rtol=0)


@pytest.mark.parametrize("pos", [0, 5, 16, 37], ids=lambda p: f"from-{p}")
def test_ring_then_chunk_buffer_of_the_flash_band_is_the_band(pos):
    """One layer's chunk over its ring, the flash form against the XLA
    band, from a position before the ring is full (rows the stream never
    wrote must not be seen), at its edge and after it has wrapped."""
    rows, t, kh, g, d = 16, 32, 2, 2, 16
    k0, k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(pos), 5)
    q = jax.random.normal(k0, (1, kh * g, t, d))
    k = jax.random.normal(k1, (1, kh, t, d))
    v = jax.random.normal(k2, (1, kh, t, d))
    # a ring that earlier positions (and an earlier stream) have written
    ring_k = jax.random.normal(k3, (1, kh, rows, d))
    ring_v = jax.random.normal(k4, (1, kh, rows, d))

    def ahead(ring, new):
        return jnp.concatenate([jnp.roll(ring, -pos, axis=2), new], axis=2)

    band = attention._attend_band(q, ahead(ring_k, k), ahead(ring_v, v),
                                  pos, WINDOW, rows)
    flash = attention._attend_ring_flash(q, ring_k, ring_v, k, v,
                                         jnp.int32(pos), WINDOW)
    np.testing.assert_allclose(flash, band, atol=2e-5, rtol=0)


def test_the_chips_policy_takes_the_kernel_for_long_rings_only(monkeypatch):
    """Which form a chunk's window attention takes is the prefill policy's
    answer at ONE band's shape (``R`` queries over ``2R`` keys): rings of
    1024 rows take the kernel from chunks of 1024 on and the XLA band
    below; rings of 128 rows (K-EXAONE) stay on the XLA band at every
    chunk, as before."""
    from cake_tpu.ops import pallas as pk

    monkeypatch.setattr(pk, "on_tpu", lambda: True)

    def form(rows, t):
        blk = rows if t % rows == 0 else t
        return attention._flash_prefill_choice(blk, rows + blk, 128)

    assert [form(1024, t) for t in (256, 512, 1024, 2048, 8192)] == [
        "xla", "xla", "flash", "flash", "flash"]
    assert {form(128, t) for t in (128, 256, 2048, 4096)} == {"xla"}


@pytest.mark.parametrize("control", [
    dict(window=WINDOW - 1), dict(window=WINDOW + 1),
    dict(one_rotation=True), dict(attention_factor=1.0)],
    ids=["window-7", "window-9", "one-rotation", "attention-factor-1"])
def test_wrong_mathematics_fails_the_tolerance(tensors, want, control):
    """The controls of the mechanisms: the reference with 7 or 9 keys a
    window layer, with the window layers' table on the full layers too (no
    YaRN, no ``attention_factor``), or with YaRN's amplitude left at 1 is
    another model by far more than ``TIGHT``."""
    off = np.asarray(ref.logits(CFG.to_hf_dict(), tensors, TOKENS[:72],
                                **control))
    assert np.abs(off[WINDOW:] - want[WINDOW:72]).max() > 100 * TIGHT


def test_the_two_tables_are_the_files(params):
    """``rope_tables_for`` builds a table a layer kind from the file's
    ``rope_parameters``: the window layers' is the plain one, the full
    layers' blends the frequencies over the correction dimensions and
    carries ``attention_factor`` on cos AND sin at position 0 already; the
    gauge says two."""
    cos, sin = rope_tables_for(CFG, 64)
    assert metrics.registry().gauge("rope.tables").value == 2
    yarn = CFG.rotation("full_attention")
    np.testing.assert_allclose(cos["swa"][0], 1.0)
    np.testing.assert_allclose(cos["gqa"][0], yarn["attention_factor"],
                               rtol=1e-6)
    for mixer, kind in (("swa", "sliding_attention"),
                        ("gqa", "full_attention")):
        want_cos, want_sin = ref.rotation(CFG.rotation(kind), 64,
                                          CFG.head_dim)
        np.testing.assert_allclose(cos[mixer], want_cos, atol=1e-6)
        np.testing.assert_allclose(sin[mixer], want_sin, atol=1e-6)
    # pair 0 turns fast and keeps its frequency; the slow pairs are
    # stretched by the factor 4
    angle = np.arctan2(np.asarray(sin["gqa"][1]), np.asarray(cos["gqa"][1]))
    plain = np.arctan2(np.asarray(sin["swa"][1]), np.asarray(cos["swa"][1]))
    np.testing.assert_allclose(angle[0], plain[0], rtol=1e-5)
    np.testing.assert_allclose(angle[2:], plain[2:] / 4.0, rtol=1e-4)


# -- the routing and the share ---------------------------------------------------

def test_softmax_over_all_then_renormalised_is_the_programs_routing():
    """THE IDENTITY the program uses: softmax over all the router's
    experts, the largest ``k`` kept and their shares divided by the kept
    shares' sum (the reference's long form) equals softmax over the ``k``
    largest logits (``router_topk``'s ``routing=None`` form), choice and
    weights, ties to the lower index in both."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    w = jnp.asarray(3.0 * rng.normal(size=(32, 16)), jnp.float32)
    w = w.at[:, 5].set(w[:, 4])  # two experts tied on every row
    _, got_w, got_idx = moe.router_topk(x, w, 4, None)
    want_idx, want_w = ref.route({"num_experts_per_tok": 4}, x @ w)
    np.testing.assert_array_equal(got_idx, want_idx)
    np.testing.assert_allclose(got_w, want_w, atol=1e-6, rtol=0)
    assert float(jnp.abs(got_w - 0.25).max()) > 0.2  # shares that differ
    tied = np.asarray(got_idx == 5).any(-1)
    assert tied.any() and (np.asarray(got_idx == 4).any(-1) >= tied).all()


def _expert_layer(params, cfg, h, first, count):
    """The program's expert layer of the first layer: the routed part of
    a told share of the experts."""
    layer = jax.tree.map(lambda a: a[0], params["layers"]["swa_moe"])
    return moe.moe_swiglu(
        h, layer["router"], layer["w_gate"][first:first + count],
        layer["w_up"][first:first + count],
        layer["w_down"][first:first + count], top_k=cfg.num_experts_per_tok,
        routing=None, held=(first, count))


@pytest.mark.parametrize("rows", [6, 96], ids=["step-rows", "chunk-rows"])
def test_shares_add_up_to_the_uncut_layer(params, tensors, rows):
    """THE SHARE TEST: the routed parts that all 4 ``ep`` shares (4 of 16
    experts each, as 16 of 64 at the published size) give add up to the
    uncut reference layer, and one share alone is the reference's same
    share; there is no shared expert to count once."""
    h = jax.random.normal(jax.random.PRNGKey(9), (1, rows, CFG.hidden_size))
    parts = [_expert_layer(params, CFG, h, 4 * r, 4) for r in range(4)]
    hf = CFG.to_hf_dict()
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(hf, tensors, "model.layers.0.", h[0])
        one = ref.expert_layer(hf, tensors, "model.layers.0.", h[0],
                               only=range(4, 8))
    np.testing.assert_allclose(sum(parts)[0], want, atol=TIGHT, rtol=0)
    np.testing.assert_allclose(parts[1][0], one, atol=TIGHT, rtol=0)
    assert float(jnp.abs(parts[1]).max()) > 0.01  # a share is something
    # a told share is a configuration: rank 1 of 4 holds experts 4-7
    share = tiny_mellum(n_routed_experts=4, router_experts=16, first_expert=4)
    assert share.to_hf_dict()["expert_share"] == {
        "n_routed_experts": 16, "ep": 4, "rank": 1}
    assert llama.stack_shapes(share)["swa_moe"]["w_gate"](share) == (
        4, 64, 32)
    assert "ws_gate" not in llama.stack_shapes(share)["swa_moe"]
    assert "b_router" not in llama.stack_shapes(share)["swa_moe"]


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
    continuation of what came before it, to ``TIGHT`` (logits are
    compared, not tokens: a near tie may go either way)."""
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
    padding may not enter a ring, per-row positions under both tables,
    block decode over rings that wrap; each stream's tokens are the
    reference's argmax. The gauges count both kinds of rows and both
    rotations, the counters the rings' live rows against the rows a step
    sweeps, and the expert counters count in this family."""
    reg = metrics.registry()
    live, swept = (reg.counter(f"attn.ring_rows_{n}")
                   for n in ("live", "swept"))
    routed, hit = reg.counter("moe.routed_pairs"), reg.counter(
        "moe.experts_hit")
    before = live.value, swept.value, routed.value, hit.value
    bg = _engine(params, PROMPTS[:3])
    outs = bg.generate(27)
    for prompt, out in zip(PROMPTS[:3], outs):
        _is_the_references_argmax(tensors, prompt, list(out)[:27])
    row = 2 * 2 * 16 * 4  # k and v, two heads of 16 float32 values
    assert reg.gauge("cache.row_bytes").value == row
    assert reg.gauge("cache.ring_rows").value == 16
    assert reg.gauge("cache.rows_bytes").value == 3 * row * (
        2 * 256 + 6 * 16)
    assert reg.gauge("attn.layers_swa").value == 6
    assert reg.gauge("attn.layers_full").value == 2
    assert reg.gauge("rope.tables").value == 2
    # 26 decode steps of 3 slots (the first token is the prefill's): a
    # ring of 16 rows swept whole, of which at most the window's 8 hold a
    # key the query may see (fewer while the 5-token stream is short)
    steps = (swept.value - before[1]) // (6 * 16 * 3)
    assert steps >= 26
    seen = live.value - before[0]
    assert 0.4 < seen / (swept.value - before[1]) <= WINDOW / 16
    assert seen < 6 * 3 * steps * WINDOW  # the short stream's first steps
    # (the counts of a block are fetched when its tokens have landed: the
    # block enqueued ahead at the end is dispatched and not yet counted)
    counted, rest = divmod(routed.value - before[2], 3 * 4 * 8)
    assert 26 <= counted <= steps and rest == 0
    assert 0 < hit.value - before[3] <= counted * 8 * 16


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
    together and ride ONE prefill program of two rows (a staging cache of
    two rows, rings and all, one splice): each stream's tokens are the
    single-stream reference's."""
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


# -- the configuration, the plan, the budget, the loaders -----------------------

def _catalog() -> dict:
    """The catalog's ``config`` of Mellum2-12B-A2.5B-Instruct (the
    published ``config.json`` without the keys that say nothing of its
    shape)."""
    return {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 7,
        "mlp_layer_types": ["sparse"] * 28,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        "sliding_window": 1024, "tie_word_embeddings": False,
        "vocab_size": 98304, "use_sliding_window": True,
    }


def test_the_catalogs_keys_are_read_and_round_trip():
    """``from_hf_dict`` reads the published keys (Qwen3-MoE's spelling of
    the expert counts, the per-layer lists, ``rope_parameters`` keyed by
    layer kind), the preset is the same configuration, ``to_hf_dict``
    writes what reads back, and the 28 published layers parse, plan and
    budget."""
    published = _catalog()
    whole = LlamaConfig.from_hf_dict(published, max_seq_len=131072,
                                     bos_token_id=0, eos_token_id=1)
    assert whole == mellum2_12b()
    assert (whole.n_routed_experts, whole.router_experts,
            whole.n_shared_experts, whole.first_k_dense_replace) == (
                64, 64, 0, 0)
    assert (whole.scoring_func, whole.router_bias, whole.qk_norm,
            whole.norm_topk_prob, whole.ring_rows) == (
                "softmax", False, True, True, 1024)
    assert whole.layer_kinds[:5] == (("swa", "moe"),) * 3 + (
        ("gqa", "moe"), ("swa", "moe"))
    assert whole.cache_plan == {"rows": (7, 4, 128, 128),
                                "ring": (21, 4, 1024, 128, 128)}
    assert whole.rotation("sliding_attention") == {
        "rope_type": "default", "rope_theta": 500000}
    assert whole.rotation("full_attention") == published[
        "rope_parameters"]["full_attention"]
    back = whole.to_hf_dict()
    for key in ("layer_types", "mlp_layer_types", "rope_parameters",
                "sliding_window", "num_experts", "num_experts_per_tok",
                "head_dim", "moe_intermediate_size", "norm_topk_prob",
                "use_sliding_window", "max_window_layers",
                "intermediate_size", "vocab_size", "tie_word_embeddings"):
        assert back[key] == published[key], key
    for key in ("num_shared_experts", "first_k_dense_replace",
                "scoring_func", "n_group", "routed_scaling_factor",
                "sliding_windows", "expert_share", "rope_theta"):
        assert key not in back, key
    assert LlamaConfig.from_hf_dict(back, max_seq_len=131072) == whole
    tiny_back = LlamaConfig.from_hf_dict(CFG.to_hf_dict(), dtype="float32",
                                         max_seq_len=256, eos_token_id=-1)
    assert tiny_back == CFG and hash(tiny_back) == hash(CFG)
    # the published 28 layers: three window layers and one full one by
    # turns, each stretch a scanned segment of its own (no period of expert
    # layers is repeated: models/llama.py layer_plan says why)
    plan = llama.layer_plan(whole)
    assert {r.repeats for r in plan} == {1} and len(plan) == 14
    assert [(s.name, s.mixer, s.first, s.count, s.cache_first)
            for r in plan[:3] for s in r.segments] == [
        ("swa_moe", "swa", 0, 3, 0), ("gqa_moe", "gqa", 3, 1, 0),
        ("swa_moe_2", "swa", 4, 3, 3)]
    assert sum(llama.stack_layers(whole).values()) == 28
    shapes = llama.stack_shapes(whole)["gqa_moe"]
    assert shapes["router"](whole) == (2304, 64)
    assert shapes["w_gate"](whole) == (64, 2304, 896)
    assert shapes["q_norm"](whole) == (128,)
    assert not {"ws_gate", "b_router"} & set(shapes)
    # the benchmark's cut: the model's own first eight layers
    assert [s.name for _, s in llama.plan_segments(
        mellum2_12b(num_hidden_layers=8))] == [
        "swa_moe", "gqa_moe", "swa_moe_2", "gqa_moe_2"]

def test_hbm_budget_holds_the_cut_and_the_published_model():
    """The benchmark's cut (8 of 28 layers, all 64 experts, the whole
    vocabulary) at 32 slots x 8192: 7.07 GiB of weights; two full layers'
    rows (1.0 GiB) and six rings of 1024 rows (0.375 GiB) where whole
    window layers would hold 3.0 GiB more. The published 28 layers budget
    too (not run): 12.15 B parameters."""
    from cake_tpu.utils.memory import hbm_budget

    cfg = mellum2_12b(num_hidden_layers=8, max_seq_len=8192)
    b = hbm_budget(cfg, batch=32, max_seq=8192)
    attn = (2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304 + 2 * 128
            + 2 * 2304)
    layer = attn + 2304 * 64 + 64 * 3 * 2304 * 896
    assert b["layers"] == 2 * 8 * layer
    row = 2 * 4 * 128 * 2
    assert b["kv_cache"] == 32 * row * (2 * 8192 + 6 * 1024)
    assert b["kv_cache"] == int(1.375 * 2**30)
    weights = b["total"] - b["kv_cache"]
    assert 7.06 * 2**30 < weights < 7.08 * 2**30
    assert abs(2 * 98304 * 2304 * 2 - (weights - b["layers"])) < 2 * 2304 * 2
    whole = hbm_budget(mellum2_12b(), batch=1, max_seq=8192)
    params = (whole["total"] - whole["kv_cache"]) / 2
    assert 12.1e9 < params < 12.2e9
    with pytest.raises(ValueError, match="not wired"):
        hbm_budget(cfg, quant="int8")


def test_checkpoint_round_trip_reads_the_files_names(tmp_path, params, want):
    """Through the real writer and loader: the same pytree, the same
    logits, under the names the configuration assumes
    (``self_attn.q_norm``, ``mlp.gate.weight``, every expert under its id,
    no shared expert and no bias tensor), found by the loader from the
    checkpoint's own names."""
    from safetensors.numpy import load_file

    save_llama_params(params, tmp_path, config=CFG)
    (tmp_path / "config.json").write_text(json.dumps(CFG.to_hf_dict()))
    stored = load_file(tmp_path / "model.safetensors")
    assert stored["model.layers.3.self_attn.q_norm.weight"].shape == (16,)
    assert stored["model.layers.0.mlp.gate.weight"].shape == (16, 64)
    assert stored[
        "model.layers.7.mlp.experts.15.down_proj.weight"].shape == (64, 32)
    assert not [n for n in stored if "shared_experts" in n
                or "e_score_correction_bias" in n or ".mlp.gate_proj" in n]
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


def _rope(**over):
    """The fixture's ``rope_parameters`` with the full layers' changed."""
    by_kind = CFG.to_hf_dict()["rope_parameters"]
    return dict(by_kind, full_attention=dict(by_kind["full_attention"],
                                             **over))


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
        rope_parameters=_rope(rope_type="llama3"))), "rope type 'llama3'"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(rope_parameters={
        "rope_type": "default", "rope_theta": 1e4})), "keyed by layer kind"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(rope_parameters={
        k: v for k, v in _rope().items() if k != "full_attention"})),
     "keyed by layer kind"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(scoring_func="sigmoid")),
     "scoring_func"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(norm_topk_prob=False)),
     "renormalised"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(num_shared_experts=1)),
     "num_shared_experts"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(use_sliding_window=False)),
     "use_sliding_window"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(attention_bias=True)),
     "attention_bias"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(mlp_layer_types=[
        "sparse", "dense"] + ["sparse"] * 6)), "dense layers lead"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(layer_types=["full_attention"])),
     "1 entries for 8 layers"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(hidden_act="gelu")),
     "hidden_act"),
    (lambda p: tiny_mellum(routed_scaling_factor=2.5), "renormalised"),
    (lambda p: tiny_mellum(router_bias=True), "renormalised"),
    (lambda p: tiny_mellum(scoring_func="tanh"), "scoring_func"),
    (lambda p: tiny_mellum(layer_rope={"full_attention": None}),
     "for each layer kind"),
    (lambda p: tiny_mellum(layer_types=("sliding_attention",) * 8),
     "without a full_attention layer"),
    (lambda p: tiny_mellum(sliding_window=4), "8 or more"),
    (lambda p: tiny_mellum(n_routed_experts=4, router_experts=16,
                           first_expert=14), "held of 16"),
], ids=["stages", "tp", "sp", "paged", "speculation", "int8-cache",
        "layer-range", "one-stack", "rope-type", "rope-flat",
        "rope-kind-missing", "scoring", "unnormalised-shares",
        "shared-expert", "window-off", "bias", "dense-inside",
        "types-short", "activation", "scaling-factor", "routing-bias",
        "scoring-unknown", "rotation-missing", "no-full-layer",
        "window-small", "share-outside"])
def test_family_limits_are_refused_with_a_message(params, what, match):
    with pytest.raises(ValueError, match=match):
        what(params)
