"""The delta-rule + latent-attention hybrid (Ling-3.0's keys: KDA layers
that hold a recurrent state beside one latent (MLA) layer in
``layer_group_size``, a direct query projection, a head-wise gate, bias-
corrected sigmoid group routing over a told share of the experts beside a
shared one) against the plain reference
``cake_tpu/testing/reference_kda_mla_moe.py``, on seeded random weights at
tiny widths that keep the published family's ratios
(``models.config.tiny_kda_hybrid``: K K M K, one leading dense layer).

Tolerances. Everything here is float32 on the CPU, where XLA's matmuls
are full precision. Program and reference differ in the order of sums
(the chunked WY form against the token-by-token recurrence, absorbed
against expanded attention, one einsum against a loop of experts); a KDA
layer then passes its outputs through two normalisations (L2 on q and k,
RMS on o; a head's output is tiny while its state is young, and the RMS
norm multiplies the rounding of a tiny vector): measured 2e-5 to 8e-5 on
logits of magnitude ~4 through four layers over 24 tokens, and 3.2e-4 at
one logit of 38,400 over 150 tokens. ``TIGHT`` is 1e-3, three times the
worst, and thirty times under what computing in bfloat16 costs (checked
below), so a lowered precision fails. The recurrence itself is held to
1e-5 (``test_kda_chunk_is_the_recurrence``).

The engine's section is ``tests/test_kda_hybrid_engine.py`` since PR 59;
what the two files share is ``tests/kda_hybrid_kit.py``.
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import (
    LlamaConfig, ling3flash_ep4, tiny_kda_hybrid, tiny_mla_moe,
)
from cake_tpu.ops import kda, moe
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.ops.rope import rope_tables_for
from cake_tpu.parallel.mesh import validate_shardable
from cake_tpu.testing import reference_kda_mla_moe as ref
from cake_tpu.utils.weights import (
    latent_hf_tensors, load_llama_params, save_llama_params,
)

from kda_hybrid_kit import (  # noqa: F401
    CFG, TIGHT, TOKENS, _all_logits, _decode_all, _engine, _params, params,
    tensors, want,
)


# -- against the reference -------------------------------------------------------

def test_prefill_logits_match_reference_over_chunk_boundaries(params,
                                                              tensors):
    """150 tokens in one prefill: two whole chunks of 64 and a part of a
    third in the WY form, against the token-by-token recurrence."""
    tokens = np.random.default_rng(5).integers(0, 256, 150)
    got, _ = _all_logits(params, CFG, tokens)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(CFG.to_hf_dict(), tensors, tokens))
    np.testing.assert_allclose(got, want, atol=TIGHT, rtol=0)


@pytest.mark.parametrize("chunk", [None, 4], ids=["one-chunk", "chunks-of-4"])
def test_prefill_then_16_decode_steps_match_reference(params, want, chunk):
    """Prefill (the chunked form, entering and leaving through the state
    and the convolutions' tail) then 16 decode steps through the cache:
    the logits at every position against the reference's full forward."""
    got, _ = _decode_all(params, CFG, TOKENS, prefill=8, chunk=chunk)
    assert got.shape[0] == 17
    np.testing.assert_allclose(got, want[7:], atol=TIGHT, rtol=0)


def test_bfloat16_fails_the_tolerance(params, want):
    """The tolerance is tight enough that a lowered precision fails it."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    got, _ = _decode_all(low, cfg, TOKENS, prefill=8)
    assert np.abs(got - want[7:]).max() > 30 * TIGHT


def test_state_in_bfloat16_fails_the_tolerance(tensors, want):
    """The control the benchmark runs on the chip, at the small size: the
    reference with its state rounded to bfloat16 between tokens is another
    model by this tolerance."""
    low = np.asarray(ref.logits(CFG.to_hf_dict(), tensors, TOKENS,
                                state_dtype=jnp.bfloat16))
    assert np.abs(low - want).max() > 30 * TIGHT


@pytest.mark.parametrize("case", ["random", "decay-floor", "no-decay",
                                  "repeated-keys", "padded-tail"])
def test_kda_chunk_is_the_recurrence(case):
    """The WY form against the recurrence it rewrites, over 150 tokens
    (chunk boundaries inside) from a nonzero state: at random gates, at
    ``g`` within 1e-3 of the lower bound -5 with ``beta`` within 1e-3 of 1
    (64 steps of decay are e^-320: nothing may be divided by that), with
    no decay at all, with no decay and ONE key a head at every token
    (``beta tril(K K^T, -1)`` all ones under the diagonal: its powers grow
    before they vanish, and an inverse summed from them cancels to
    nothing), and over 192 tokens (three whole chunks) of which the rows
    hold 130 and 64 true ones (``_advance`` with ``valid``: padded tokens
    write nothing and decay nothing)."""
    rs = np.random.default_rng(0)
    b, t, h, d = 2, 192 if case == "padded-tail" else 150, 3, 16

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = unit(rs.normal(size=(b, t, h, d))) * d ** -0.5
    k, v = unit(rs.normal(size=(b, t, h, d))), rs.normal(size=(b, t, h, d))
    if case == "repeated-keys":
        k = np.broadcast_to(k[:, :1], k.shape)
    random = (-5 / (1 + np.exp(-rs.normal(size=(b, t, h, d)))),
              1 / (1 + np.exp(-rs.normal(size=(b, t, h)))))
    no_decay = (-1e-3 * rs.random((b, t, h, d)),
                1 - 1e-3 * rs.random((b, t, h)))
    g, beta = {
        "random": random, "padded-tail": random,
        "decay-floor": (-5 + 1e-3 * rs.random((b, t, h, d)),
                        1 - 1e-3 * rs.random((b, t, h))),
        "no-decay": no_decay, "repeated-keys": no_decay,
    }[case]
    args = [jnp.asarray(a, jnp.float32)
            for a in (q, k, v, g, beta, rs.normal(size=(b, h, d, d)))]
    if case == "padded-tail":
        valid = np.array([130, 64], np.int32)
        o_got, s_got = kda._advance(*args, jnp.asarray(valid), None, "kda")
        for row, n in enumerate(valid):
            o_want, s_want = kda.kda_recurrence(
                *(a[row:row + 1, :n] for a in args[:5]),
                args[5][row:row + 1])
            np.testing.assert_allclose(o_got[row:row + 1, :n], o_want,
                                       atol=1e-5, rtol=0)
            np.testing.assert_allclose(s_got[row:row + 1], s_want,
                                       atol=1e-5, rtol=0)
        return
    o_want, s_want = kda.kda_recurrence(*args)
    o_got, s_got = kda.kda_chunk(*args)
    assert np.isfinite(np.asarray(o_got)).all()
    np.testing.assert_allclose(o_got, o_want, atol=1e-5, rtol=0)
    np.testing.assert_allclose(s_got, s_want, atol=1e-5, rtol=0)


def test_direct_query_gated_latent_layer_matches_reference(params, tensors):
    """The latent layer alone (model layer 2): one direct query projection
    and a sigmoid gate a head, against the reference's expanded
    attention."""
    from cake_tpu.ops.mla import latent_attention_block

    layer = jax.tree.map(lambda a: a[0], params["layers"]["mla_moe"])
    assert "wq" in layer and "wq_a" not in layer and layer["wg"].shape == (
        CFG.hidden_size, CFG.num_attention_heads)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 12, CFG.hidden_size))
    cos, sin = rope_tables_for(CFG, 64)
    cache = init_cache(CFG, 1, 64)
    got, _, _ = latent_attention_block(x, layer, cache.k[0], cache.v[0], cos,
                                       sin, 0, CFG)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(CFG.to_hf_dict(), tensors, "model.layers.2.",
                             x[0])
    np.testing.assert_allclose(got[0], want, atol=3e-5, rtol=0)
    # without the gate it is another function
    ungated = {k: v for k, v in layer.items() if k != "wg"}
    other, _, _ = latent_attention_block(x, ungated, cache.k[0], cache.v[0],
                                         cos, sin, 0, CFG)
    assert float(jnp.abs(other[0] - want).max()) > 1e-2


# -- the cache: two kinds of state -------------------------------------------

def test_cache_holds_state_for_kda_rows_for_mla_and_nothing_else(params):
    _, cache = _decode_all(params, CFG, TOKENS, prefill=8)
    h, d = CFG.num_attention_heads, CFG.head_dim
    assert CFG.cache_plan == {"rows": (1, 1, 16, 8), "state": (3, h, d, d),
                              "conv": (3, 3, 3 * h * d)}
    assert cache.k.shape == (1, 1, 1, 64, CFG.kv_lora_rank)
    assert cache.v.shape == (1, 1, 1, 64, CFG.qk_rope_head_dim)
    assert cache.state.shape == (3, 1, h, d, d)
    assert cache.state.dtype == jnp.float32
    assert cache.conv.shape == (3, 1, 3, 3 * h * d)
    assert len(jax.tree.leaves(cache)) == 4
    # the one latent layer wrote its rows, and only the rows fed; every
    # delta-rule layer's state and tail moved
    written = np.asarray(jnp.abs(cache.k).sum(-1) > 0)[0, 0, 0]
    assert written[:len(TOKENS)].all() and not written[len(TOKENS):].any()
    assert (np.abs(np.asarray(cache.state)).reshape(3, -1).max(1) > 0).all()
    assert (np.abs(np.asarray(cache.conv)).reshape(3, -1).max(1) > 0).all()
    # a model of one kind of layer holds rows and nothing else
    plain = init_cache(tiny_mla_moe(), 1, 64)
    assert plain.state is None and plain.conv is None


def test_padded_rows_leave_state_and_tail_untouched(params):
    """A bucketed chunk: 11 true tokens padded to 16. With the true length
    told, state and tail are those of the 11 tokens alone, and the logits
    of the true positions are unchanged; untold, the padding advances
    them."""
    want, alone = _all_logits(params, CFG, TOKENS[:11], 64)
    padded = np.concatenate([TOKENS[:11], np.full(5, 7, np.int32)])
    got, told = _all_logits(params, CFG, padded, 64,
                            valid=jnp.asarray([11], jnp.int32))
    np.testing.assert_allclose(got[:11], want, atol=2e-5, rtol=0)
    np.testing.assert_allclose(told.state, alone.state, atol=1e-5, rtol=0)
    np.testing.assert_allclose(told.conv, alone.conv, atol=1e-5, rtol=0)
    _, untold = _all_logits(params, CFG, padded, 64)
    assert float(jnp.abs(untold.state - alone.state).max()) > 1e-3


def test_hbm_budget_counts_state_and_rows_of_the_preset():
    """The published widths cut as the benchmark's configuration is (1
    dense + 6 expert layers, 128 of 512 experts, a quarter of the
    vocabulary): 9.75 GiB of weights, and at the cell's 32 slots x 4096
    rows 384 MiB of state, 13.5 MiB of tails and 144 MiB of latent rows."""
    from cake_tpu.utils.memory import hbm_budget

    cfg = ling3flash_ep4(num_hidden_layers=7, first_k_dense_replace=1,
                         vocab_size=39296, max_seq_len=4096)
    assert [m for m, _ in cfg.layer_kinds] == [
        "kda", "kda", "kda", "kda", "kda", "mla", "kda"]
    assert cfg.cache_plan == {"rows": (1, 1, 512, 64),
                              "state": (6, 32, 128, 128),
                              "conv": (6, 3, 12288)}
    g = 1 << 30
    b = hbm_budget(cfg, batch=32, max_seq=4096)
    weights = b["layers"] + b["embed_replicated"] + b["head"]
    assert weights / g == pytest.approx(9.75, rel=0.005)
    state = 32 * 6 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
    assert (state, state / 32) == (384 * 2**20 + 27 * 2**19, 13025280)  # cache.state_bytes_per_stream
    assert b["kv_cache"] == state + 32 * 4096 * 1152
    # ep divides the held experts only
    half = hbm_budget(cfg, batch=32, max_seq=4096, ep=2)
    experts = 6 * 128 * 3 * 2560 * 768 * 2
    assert b["layers"] - half["layers"] == experts // 2


# -- the expert layer ----------------------------------------------------------

def _expert_layer(params, cfg, h, first, count):
    """The program's expert layer (routed part of a told share + the
    shared expert), model layer 1."""
    from cake_tpu.ops.mlp import swiglu

    layer = jax.tree.map(lambda a: a[0], params["layers"]["kda_moe"])
    routed = moe.moe_swiglu(
        h, layer["router"], layer["w_gate"][first:first + count],
        layer["w_up"][first:first + count],
        layer["w_down"][first:first + count], top_k=cfg.num_experts_per_tok,
        routing=moe.GroupRouting(cfg.n_group, cfg.topk_group,
                                 cfg.norm_topk_prob,
                                 cfg.routed_scaling_factor,
                                 layer["b_router"]),
        held=(first, count))
    return routed, swiglu(h, layer["ws_gate"], layer["ws_up"],
                          layer["ws_down"])


@pytest.mark.parametrize("rows", [6, 96], ids=["step-rows", "chunk-rows"])
def test_shares_add_up_to_the_uncut_layer(params, tensors, rows):
    """THE SHARE TEST: the routed parts that the 4 shares of ``ep`` 4 give
    (4 of 16 experts each, the router and its bias whole), plus the shared
    expert counted once, add up to the uncut layer, in the program and
    against the reference's uncut layer, at a decode step's few rows and
    at an admission chunk's many."""
    h = jax.random.normal(jax.random.PRNGKey(9), (1, rows, CFG.hidden_size))
    whole, shared = _expert_layer(params, CFG, h, 0, 16)
    parts = [_expert_layer(params, CFG, h, 4 * r, 4)[0] for r in range(4)]
    total = sum(parts) + shared
    np.testing.assert_allclose(total, whole + shared, atol=3e-5, rtol=0)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(CFG.to_hf_dict(), tensors, "model.layers.1.",
                                h[0])
        one = ref.expert_layer(CFG.to_hf_dict(), tensors, "model.layers.1.",
                               h[0], only=range(4, 8))
    np.testing.assert_allclose(total[0], want, atol=3e-5, rtol=0)
    np.testing.assert_allclose(parts[1][0] + shared[0], one, atol=3e-5,
                               rtol=0)
    assert float(jnp.abs(parts[1]).max()) > 0.01  # a share is not nothing


def _route_oracle(scores, bias, k, groups, keep, scale):
    """Loop-written routing: the choice on ``score + bias``, the weights
    from the scores, ties to the lower index."""
    out_idx, out_w = [], []
    for row in scores:
        corrected = row + bias
        size = len(row) // groups
        gscore = []
        for g in range(groups):
            top2 = sorted(corrected[g * size:(g + 1) * size],
                          reverse=True)[:2]
            gscore.append(float(np.float32(top2[0]) + np.float32(top2[1])))
        kept = sorted(range(groups), key=lambda g: (-gscore[g], g))[:keep]
        allowed = [i for i in range(len(row)) if i // size in kept]
        chosen = sorted(allowed, key=lambda i: (-corrected[i], i))[:k]
        w = np.array([row[i] for i in chosen], np.float32)
        out_idx.append(chosen)
        out_w.append(w / (w.sum() + np.float32(1e-20)) * np.float32(scale))
    return np.array(out_idx), np.array(out_w)


@pytest.mark.parametrize("ties", [False, True], ids=["random", "ties"])
def test_router_bias_enters_the_choice_and_not_the_weights(ties):
    rng = np.random.default_rng(3)
    logits = rng.normal(size=(40, 16)).astype(np.float32)
    bias = rng.normal(size=16).astype(np.float32) * (0.5 if ties else 0.05)
    if ties:  # equal corrected scores inside a group, across groups
        logits = np.round(logits * 2) / 2
        bias = np.round(bias * 2) / 2
        logits[0] = 0.0
        logits[1, :8] = 1.0
        bias[:8] = 0.0
    routing = moe.GroupRouting(4, 2, True, 2.5, jnp.asarray(bias))
    # an identity router so that the logits are exactly these
    combine, w, idx = moe.router_topk(jnp.asarray(logits), jnp.eye(16), 4,
                                      routing)
    scores = np.asarray(jax.nn.sigmoid(jnp.asarray(logits)))
    want_idx, want_w = _route_oracle(scores, bias, 4, 4, 2, 2.5)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-6)
    dense = np.zeros((40, 16), np.float32)
    np.put_along_axis(dense, want_idx, want_w, axis=1)
    np.testing.assert_allclose(np.asarray(combine), dense, rtol=1e-6)
    # the bias changed choices, and where the choice is the same the
    # weights are the unbiased router's
    _, w0, idx0 = moe.router_topk(jnp.asarray(logits), jnp.eye(16), 4,
                                  moe.GroupRouting(4, 2, True, 2.5))
    same = (np.asarray(idx0) == want_idx).all(axis=1)
    assert 0 < same.sum() < 40
    np.testing.assert_allclose(np.asarray(w0)[same], want_w[same], rtol=1e-6)
    # the reference's router is the same function
    ridx, rw = ref.route(CFG.to_hf_dict(), jnp.asarray(scores),
                         jnp.asarray(bias))
    np.testing.assert_array_equal(np.asarray(ridx), want_idx)
    np.testing.assert_allclose(np.asarray(rw), want_w, rtol=1e-6)


# -- the layer plan ------------------------------------------------------------

def test_layer_plan_instances():
    """One plan for every latent-family model: "leading dense layers,
    then expert layers" is two segments under the names the stacks always
    had; the hybrid's kinds follow its period; a repeated period is one
    run."""
    def names(cfg):
        return [(r.repeats, [(s.name, s.count, s.cache_first)
                             for s in r.segments])
                for r in llama.layer_plan(cfg)]

    assert names(tiny_mla_moe()) == [(1, [("dense", 1, 0)]),
                                     (1, [("moe", 2, 1)])]
    assert names(CFG) == [
        (1, [("kda_dense", 1, 0)]), (1, [("kda_moe", 1, 1)]),
        (1, [("mla_moe", 1, 0)]), (1, [("kda_moe_2", 1, 2)])]
    full = llama.layer_plan(ling3flash_ep4())
    assert [(r.repeats, [(s.mixer, s.ffn, s.count) for s in r.segments])
            for r in full] == [
        (1, [("kda", "dense", 2)]), (1, [("kda", "moe", 3)]),
        (6, [("mla", "moe", 1), ("kda", "moe", 5)]),
        (1, [("mla", "moe", 1)])]
    assert sum(llama.stack_layers(ling3flash_ep4()).values()) == 42
    ids = full[2].layer_ids(full[2].segments[1])
    assert ids.shape == (6, 5) and ids[0, 0] == 6 and ids[5, 4] == 40


def test_repeated_period_scans_as_a_period_and_matches_reference():
    """Ten layers of period 3: K K (M K K) x 2 M K. The period's stacks
    lead ``[2, layers]`` and are scanned over their repetitions; prefill
    and decode through the cache are the reference's, and the checkpoint
    round-trips through writer and loader."""
    cfg = tiny_kda_hybrid(num_hidden_layers=10, max_seq_len=64,
                          eos_token_id=-1)
    params = _params(cfg, seed=3)
    assert params["layers"]["kda_moe_2"]["kda_q"].shape[:2] == (2, 2)
    assert params["layers"]["mla_moe"]["wkv_a"].shape[:2] == (2, 1)
    tensors = latent_hf_tensors(params, cfg)
    want = np.asarray(ref.logits(cfg.to_hf_dict(), tensors, TOKENS[:14]))
    got, cache = _decode_all(params, cfg, TOKENS[:14], prefill=8, chunk=4)
    np.testing.assert_allclose(got, want[7:], atol=2 * TIGHT, rtol=0)
    assert cache.state.shape[0] == 7 and cache.k.shape[0] == 3
    assert (np.abs(np.asarray(cache.state)).reshape(7, -1).max(1) > 0).all()


# -- loader, writer, configuration -----------------------------------------------

def test_checkpoint_writer_reader_roundtrip_skips_the_mtp_block(
        tmp_path, params, want):
    """Through the real writer and loader: the same pytree, the same
    logits, the convolutions' taps stored as torch depthwise ``[C, 1,
    K]``; the tensors of a next-token prediction block (a layer past the
    trunk's depth, with names of its own) are never read."""
    from safetensors.numpy import load_file, save_file

    save_llama_params(params, tmp_path, config=CFG)
    (tmp_path / "config.json").write_text(json.dumps(dict(
        CFG.to_hf_dict(), num_nextn_predict_layers=1)))
    stored = load_file(tmp_path / "model.safetensors")
    h, d = CFG.num_attention_heads, CFG.head_dim
    assert stored["model.layers.0.self_attn.q_conv1d.weight"].shape == (
        h * d, 1, 4)
    assert stored["model.layers.1.mlp.gate.expert_bias"].shape == (16,)
    mtp = {f"model.layers.{CFG.num_hidden_layers}.{k}": v for k, v in {
        "eh_proj.weight": np.ones((64, 128), np.float32),
        "enorm.weight": np.ones((64,), np.float32),
        "self_attn.q_proj.weight": np.full((96, 64), np.nan, np.float32),
        "input_layernorm.weight": np.full((64,), np.nan, np.float32),
    }.items()}
    save_file({**stored, **mtp}, tmp_path / "model.safetensors")
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


def test_preset_holds_the_published_widths_and_round_trips():
    """``ling3flash_ep4()``: the published file's numbers (ISSUE 32 lists
    them), one chip's share of 4, and the file a checkpoint of it carries
    reads back as the same config."""
    cfg = ling3flash_ep4()
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size) == (
        42, 2560, 157184)
    assert (cfg.num_attention_heads, cfg.head_dim, cfg.q_lora_rank) == (
        32, 128, None)
    assert cfg.cache_row == (1, 512, 64)
    assert cfg.attn_scale == pytest.approx(192 ** -0.5)
    assert (cfg.router_experts, cfg.n_routed_experts, cfg.first_expert) == (
        512, 128, 0)
    assert sum(m == "mla" for m, _ in cfg.layer_kinds) == 7
    assert [f for _, f in cfg.layer_kinds[:3]] == ["dense", "dense", "moe"]
    hf = cfg.to_hf_dict()
    assert hf["expert_share"] == {"n_routed_experts": 512, "ep": 4,
                                  "rank": 0}
    assert (hf["num_experts"], hf["topk_method"], hf["model_type"]) == (
        128, "noaux_tc", "bailing_hybrid")
    assert LlamaConfig.from_hf_dict(
        hf, max_seq_len=cfg.max_seq_len, dtype=cfg.dtype) == cfg


def _hf(**over):
    return dict(CFG.to_hf_dict(), **over)


@pytest.mark.parametrize("what, match", [
    (lambda p: validate_shardable(CFG, 2, 1), "one stage"),
    (lambda p: validate_shardable(CFG, 1, 2), "tp = 1"),
    (lambda p: _engine(p, [[1, 2]], kv_layout="paged"), "slot layout"),
    (lambda p: _engine(p, [[1, 2]], spec_k=2), "recurrent state"),
    (lambda p: init_cache(CFG, quant="int8"), "int8 cache"),
    (lambda p: init_cache(CFG, num_layers=2), "cached whole"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(
        expert_swiglu_limit_list=[0, 0, 0, 4])), "expert_swiglu_limit_list"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(
        share_expert_swiglu_limit_list=[0, 5, 0, 0])), "clamp"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(kda_safe_gate=False)),
     "kda_safe_gate"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(use_kda_lora=True)),
     "use_kda_lora"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(topk_method="none")),
     "disagree"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(
        gated_attention_proj_granularity_type="channel_wise")), "head-wise"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(
        moe_shared_expert_intermediate_size=48)), "another width"),
], ids=["stages", "tp", "paged", "speculation", "int8-cache", "layer-range",
        "expert-clamp", "shared-clamp", "safe-gate", "kda-lora",
        "bias-disagrees", "gate-granularity", "shared-width"])
def test_family_limits_are_refused_with_a_message(params, what, match):
    with pytest.raises(ValueError, match=match):
        what(params)


def test_swiglu_limit_past_the_served_depth_is_not_refused():
    """The published lists are nonzero from layer 34 on; a cut that stops
    before them serves."""
    limits = [0] * 35 + [4] * 7
    cfg = LlamaConfig.from_hf_dict(_hf(expert_swiglu_limit_list=limits,
                                       share_expert_swiglu_limit_list=limits),
                                   dtype="float32")
    assert cfg.num_hidden_layers == 4 and "state" in cfg.cache_plan


# -- the decode kernel -----------------------------------------------------------

@pytest.mark.parametrize("heads, d, block", [(16, 128, 8), (4, 16, 4)],
                         ids=["h16-d128", "h4-d16"])
def test_kda_decode_kernel_is_the_step(heads, d, block):
    """``ops.pallas.kda.kda_decode`` (interpreted here) against
    ``kda_step``: the chosen layer of the stacked state advances in place
    and no other layer is touched."""
    from cake_tpu.ops.pallas import kda_decode

    rs = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rs.normal(size=(3, heads, d)), jnp.float32)
               for _ in range(3))
    g = -5 * jax.nn.sigmoid(jnp.asarray(rs.normal(size=(3, heads, d)),
                                        jnp.float32))
    beta = jax.nn.sigmoid(jnp.asarray(rs.normal(size=(3, heads)),
                                      jnp.float32))
    state = jnp.asarray(rs.normal(size=(2, 3, heads, d, d)), jnp.float32)
    o_want, s_want = kda.kda_step(q, k, v, g, beta, state[1])
    o, s = kda_decode(q, k, v, g, beta, state, jnp.int32(1),
                      head_block=block, interpret=True)
    np.testing.assert_allclose(o, o_want, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(s[1], s_want, atol=2e-6, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(s[0]), np.asarray(state[0]))


def test_decode_through_the_kernel_matches_reference(params, want,
                                                     monkeypatch):
    """With kernels forced (``CAKE_PALLAS=1``: interpreted off the chip)
    the decode steps of the layer loop go through ``kda_decode`` on the
    carried state, and the logits are still the reference's."""
    monkeypatch.setenv("CAKE_PALLAS", "1")
    assert kda.kda_decode_choice(16, 16) == "kernel"
    got, _ = _decode_all(params, CFG, TOKENS[:14], prefill=8)
    np.testing.assert_allclose(got, want[7:14], atol=TIGHT, rtol=0)
    monkeypatch.setenv("CAKE_PALLAS", "0")
    assert kda.kda_decode_choice(128, 128) == "xla"
