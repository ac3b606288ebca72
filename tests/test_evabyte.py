"""EvaByte's EVA attention against the plain reference
(``tests/evabyte_kit.py`` has the account and the tolerances): prefill and
decode through a cache with no row a position, the visibility rule row by
row, the step's kernel against the two merged products, the summaries,
the controls that must fail, the configuration's keys and every refusal.
The engine's half is ``tests/test_evabyte_engine.py``.
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import families, llama
from cake_tpu.models.config import (LlamaConfig, evabyte_6p5b, tiny,
                                    tiny_evabyte)
from cake_tpu.ops import eva
from cake_tpu.ops import kvcache as kv
from cake_tpu.ops.pallas.eva import eva_block_counts, eva_decode
from cake_tpu.testing import reference_evabyte as ref

from evabyte_kit import (  # noqa: F401
    CFG, TIGHT, TOKENS, WIDE, _admit, _decode_all, _steps, params, tensors,
    want,
)


# -- prefill, then decode, against the whole forward pass -------------------------------

@pytest.mark.parametrize("prefill", [32, 64],
                         ids=["a-window-exactly", "two-windows"])
def test_prefill_then_decode_is_the_references_forward(params, want,
                                                       prefill):
    """A prompt of whole windows admitted from position 0 (its summaries
    are what the first step already sees), then one step a token to 90:
    the answer crosses the reset at 64 and a chunk end every four tokens.
    Every step's logits are the reference's at that position, the
    admission's own last row among them."""
    got, _ = _decode_all(params, CFG, TOKENS, prefill)
    np.testing.assert_allclose(got, want[prefill - 1:], atol=TIGHT, rtol=0)


@pytest.mark.parametrize("prefill", [27, 3],
                         ids=["mid-chunk-mid-window", "under-a-chunk"])
def test_a_padded_admission_then_decode_is_the_references_forward(
        params, want, prefill):
    """A prompt that ends mid-chunk and mid-window (27 tokens in a bucket
    of 32; 3 in one of 16: no chunk complete), told its true length, then
    one step a token to 90: the answer finishes the prompt's last chunk,
    crosses the resets at 32 and 64 and fifteen chunk ends; every step's
    logits are the reference's."""
    bucket = 32 if prefill > 16 else 16
    tokens = np.concatenate([TOKENS[:prefill],
                             np.full(bucket - prefill, 9, np.int32)])
    cache = _admit(params, tokens, prefill)
    got = _steps(params, CFG, TOKENS, cache, prefill)
    np.testing.assert_allclose(got, want[prefill:], atol=TIGHT, rtol=0)


def test_a_buckets_padding_enters_neither_ring_nor_summary(params):
    """A 27-token prompt in a 32-row bucket: whatever the padding holds,
    the admission leaves the same cache (chunk 6 holds three true tokens
    and one padding row, which takes no part), and the padding wrote no
    ring row."""
    padded, other = (
        _admit(params, np.concatenate([TOKENS[:27],
                                       np.full(5, fill, np.int32)]), 27)
        for fill in (9, 200))
    for a, b in zip(jax.tree.leaves(padded), jax.tree.leaves(other)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.asarray(padded.ring_k[:, :, :, 27:]).any()
    assert np.asarray(padded.sum_k[:, :, :, :7]).any()


# -- the visibility rule, row by row -----------------------------------------------------

def test_two_frontiers_are_the_two_sets_of_the_equations():
    """For every position of four windows: the keys ``m`` with ``m // W ==
    n // W`` and ``m <= n`` are ring rows ``0 .. n % W`` (row ``r`` holding
    position ``(n // W) W + r``), and the chunks ``c`` with ``(c + 1) C <=
    (n // W) W`` are summary rows ``0 .. (n // W)(W // C) - 1``: the two
    prefixes the step attends, against a mask written from the set
    definitions."""
    w, c, t = 32, 4, 128
    local, remote = ref.visibility(t, w, c)
    for n in range(t):
        at, visible = n % w, (n // w) * (w // c)
        held = (n // w) * w + np.arange(at + 1)  # positions of rows 0..at
        assert sorted(np.flatnonzero(np.asarray(local[n]))) == list(held)
        assert sorted(np.flatnonzero(np.asarray(remote[n]))) == list(
            range(visible))
    ring, summary = eva_block_counts(np.array([0, 7, 8, 31]),
                                     np.array([0, 8, 9, 24]), 8, xp=np)
    assert list(ring) == [1, 1, 2, 4] and list(summary) == [0, 1, 2, 3]


def _buffers(seed=0, layers=2, b=3, h=4, w=32, rows=16, d=16):
    rng = np.random.default_rng(seed)

    def buf(n):
        return jnp.asarray(rng.normal(size=(layers, b, h, n, d)), jnp.float32)

    q = jnp.asarray(rng.normal(size=(b, h, 1, d)), jnp.float32)
    return q, buf(w), buf(w), buf(rows), buf(rows)


@pytest.mark.parametrize("at,visible", [
    ((0, 0, 0), (0, 8, 16)), ((31, 5, 17), (16, 0, 8)),
    ((7, 8, 9), (8, 8, 8))], ids=["right-after-a-reset", "mixed", "block-edges"])
def test_the_step_attends_each_buffer_to_its_own_frontier(at, visible):
    """One softmax over ring rows ``0 .. at`` and summary rows ``0 ..
    visible - 1``, a stream each its own: the two masked products merged
    by their statistics AND the kernel (interpreted, blocks of 8 rows:
    one to four of the ring, none to two of the plane) against the dense
    softmax over the concatenation under the mask."""
    q, rk, rv, sk, sv = _buffers()
    layer = 1
    at, visible = np.asarray(at, np.int32), np.asarray(visible, np.int32)
    keys = jnp.concatenate([rk[layer], sk[layer]], axis=2)
    vals = jnp.concatenate([rv[layer], sv[layer]], axis=2)
    seen = np.concatenate([np.arange(32)[None] <= at[:, None],
                           np.arange(16)[None] < visible[:, None]], axis=1)
    scores = jnp.einsum("bhtd,bhsd->bhts", q, keys) * 16 ** -0.5
    dense = jax.nn.softmax(
        jnp.where(seen[:, None, None, :], scores, -jnp.inf), -1) @ vals
    merged = eva.eva_attend(q, rk, rv, sk, sv, jnp.asarray(at),
                            jnp.asarray(visible), layer)
    kernel = eva_decode(q, rk, rv, sk, sv, at, visible, layer, block_k=8,
                        interpret=True)
    np.testing.assert_allclose(merged, dense, atol=2e-6)
    np.testing.assert_allclose(kernel, dense, atol=2e-6)


def test_a_summary_is_its_chunks_softmax_and_mean():
    """``summarise`` against the two sums written out a chunk at a time;
    ``valid`` keeps a position at or past it out of both (the chunk that
    holds the frontier is a summary of its true positions alone, a chunk
    past it is nobody's)."""
    rng = np.random.default_rng(3)
    k, v = (jnp.asarray(rng.normal(size=(2, 4, 16, 8)), jnp.float32)
            for _ in range(2))
    phi, mu = (jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
               for _ in range(2))
    k_sum, v_sum = eva.summarise(k, v, phi, mu, 4, valid=jnp.asarray([16, 6]))
    for b, true in ((0, 16), (1, 6)):
        for c in range(-(-true // 4)):
            rows = slice(4 * c, min(4 * c + 4, true))
            share = jax.nn.softmax(
                jnp.einsum("hcd,hd->hc", k[b, :, rows], phi) * 8 ** -0.5, -1)
            np.testing.assert_allclose(
                v_sum[b, :, c], jnp.einsum("hc,hcd->hd", share,
                                           v[b, :, rows]), atol=1e-6)
            np.testing.assert_allclose(
                k_sum[b, :, c], k[b, :, rows].mean(1) + mu, atol=1e-6)
    assert not np.asarray(v_sum[1, :, 2:]).any()


# -- the controls ------------------------------------------------------------------------

@pytest.mark.parametrize("wrong", ref.WRONG)
def test_each_control_moves_the_logits(tensors, want, wrong):
    """One piece of the mathematics got wrong moves some logit by more
    than a hundred tolerances: no summaries (window only), a window that
    slides, a chunk visible as soon as it is made, values pooled
    uniformly, no ``mu``, norm weights taken as stored."""
    bad = np.asarray(ref.logits(CFG.to_hf_dict(), tensors, TOKENS,
                                wrong=wrong))
    assert np.abs(bad - want).max() > TIGHT * WIDE


def test_a_lower_precision_summary_fails_tight(params, want, monkeypatch):
    """Summaries rounded through bfloat16 on their way into the plane
    (what an un-widened sum or a cache of a lower type would do) move the
    logits past ``TIGHT``: the tolerance sees the summaries' precision."""
    exact = eva.summarise

    def rounded(*args, **kw):
        return tuple(x.astype(jnp.bfloat16).astype(x.dtype)
                     for x in exact(*args, **kw))

    monkeypatch.setattr(eva, "summarise", rounded)
    cfg = dataclasses.replace(CFG, rms_norm_eps=1.0001e-5)  # a fresh trace
    got, _ = _decode_all(params, cfg, TOKENS, 64)
    assert np.abs(got - want[63:]).max() > TIGHT


# -- the head, the plan, the keys ----------------------------------------------------------

def test_the_served_head_is_block_0_of_the_stored_one(params, tensors,
                                                      tmp_path):
    """The stored head holds ``num_pred_heads`` blocks of ``vocab_size``
    rows; the loaders read block 0, the model's own next token, whatever
    the others hold."""
    from safetensors.numpy import save_file

    from cake_tpu.parallel.mesh import make_mesh
    from cake_tpu.utils.sharded_load import load_llama_params_on_mesh

    stored = dict(tensors)
    head = np.array(stored["lm_head.weight"])
    assert head.shape == (2 * 256, 64) and not head[256:].any()
    head[256:] = 7.0  # block 1: another token's, never read
    stored["lm_head.weight"] = head
    save_file({k: np.ascontiguousarray(v) for k, v in stored.items()},
              tmp_path / "model.safetensors")
    (tmp_path / "config.json").write_text(json.dumps(CFG.to_hf_dict()))
    loaded = load_llama_params_on_mesh(tmp_path, CFG, make_mesh())
    assert loaded["lm_head"].shape == (64, 256)
    np.testing.assert_array_equal(np.asarray(loaded["lm_head"]),
                                  np.asarray(params["lm_head"]))
    both = np.asarray(ref.logits(CFG.to_hf_dict(), stored, TOKENS[:8],
                                 pred_head=1))
    assert np.ptp(both, axis=-1).max() < 1e-3  # block 1 is the constant one


def test_the_cache_holds_a_ring_and_a_summary_plane_and_no_rows():
    plan = CFG.cache_plan
    assert plan == {"ring": (3, 4, 32, 16, 16), "summary": (3, 4, 4, 16, 16)}
    cache = kv.init_cache(CFG, batch=2, max_seq=128)
    assert cache.k.shape == (0, 2, 4, 128, 16) and cache.max_seq == 128
    assert cache.num_layers == 0
    assert cache.ring_k.shape == cache.ring_v.shape == (3, 2, 4, 32, 16)
    assert cache.sum_k.shape == cache.sum_v.shape == (3, 2, 4, 32, 16)
    # one summary row for every 4 positions: 3 layers x 4 heads x 32 values
    # x 4 B over 4 positions
    assert CFG.cache_token_bytes == 3 * 4 * 32 * 4 // 4
    assert CFG.stream_bytes(128) == sum(
        x.nbytes for x in jax.tree.leaves(cache)) // 2
    big = evabyte_6p5b(num_hidden_layers=8)
    assert big.cache_token_bytes == 8 * 16384 // 16
    assert big.stream_bytes(16384) == 8 * (32 + 16) * 2 ** 20
    with pytest.raises(ValueError, match="whole number of windows"):
        kv.init_cache(CFG, batch=1, max_seq=48)
    with pytest.raises(ValueError, match="cached whole"):
        kv.init_cache(CFG, batch=1, max_seq=128, num_layers=2)


def test_the_budget_counts_what_the_cache_holds():
    from cake_tpu.utils.memory import hbm_budget

    cache = kv.init_cache(CFG, batch=3, max_seq=64)
    held = sum(x.nbytes for x in jax.tree.leaves(cache))
    assert hbm_budget(CFG, max_seq=64, batch=3,
                      cache_bytes_per_el=4)["kv_cache"] == held


def test_the_files_keys_round_trip():
    d = CFG.to_hf_dict()
    assert d["model_type"] == "evabyte" and d["attention_class"] == "eva"
    assert (d["window_size"], d["chunk_size"], d["num_pred_heads"]) == (
        32, 4, 2)
    assert d["norm_add_unit_offset"] is True and d["num_chunks"] is None
    back = LlamaConfig.from_hf_dict(d, dtype="float32", max_seq_len=128,
                                    eos_token_id=-1)
    assert back == CFG and back.family is families.EVA
    assert [m for m, _ in back.layer_kinds] == ["eva"] * 3
    assert [s.name for _, s in llama.plan_segments(back)] == ["dense"]
    published = evabyte_6p5b()
    assert published.cache_plan["ring"] == (32, 32, 2048, 128, 128)
    assert published.head_dim == 128 and published.rope_dim == 128


@pytest.mark.parametrize("change,says", [
    (dict(attention_class="performer"), "attention_class = 'performer'"),
    (dict(num_chunks=64), "num_chunks = 64 is not wired"),
    (dict(rope_scaling={"type": "linear", "factor": 2.0}),
     "rope_scaling = .* is not wired"),
    (dict(norm_add_unit_offset=False), "norm_add_unit_offset = False"),
    (dict(attention_bias=True), "attention_bias = True"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings = True"),
    (dict(num_key_value_heads=2), "grouped-query heads beside EVA"),
    (dict(window_size=30), "whole number of chunks"),
    (dict(chunk_size=0), "whole number of chunks"),
    (dict(num_pred_heads=0), "one or more blocks"),
], ids=lambda x: next(iter(x)) if isinstance(x, dict) else None)
def test_the_reader_refuses_what_nothing_computes(change, says):
    with pytest.raises(ValueError, match=says):
        LlamaConfig.from_hf_dict({**CFG.to_hf_dict(), **change})


@pytest.mark.parametrize("make,says", [
    (lambda: tiny(attention_class="longformer"), "only 'eva'"),
    (lambda: tiny(window_size=32), "keys of attention_class 'eva'"),
    (lambda: tiny(num_pred_heads=2), "keys of attention_class 'eva'"),
    (lambda: tiny_evabyte(sliding_window=16), "no sliding_window"),
    (lambda: tiny_evabyte(num_local_experts=4), "dense feed-forward"),
    (lambda: tiny_evabyte(kv_lora_rank=8, qk_nope_head_dim=8,
                          qk_rope_head_dim=8, v_head_dim=8),
     "attention_class 'eva'"),
    (lambda: tiny_evabyte(total_ut_steps=2), "attention_class 'eva' beside"),
], ids=["another-class", "window-alone", "heads-alone", "sliding", "experts",
        "latent", "loop"])
def test_a_configuration_refuses_evas_keys_out_of_place(make, says):
    with pytest.raises(ValueError, match=says):
        make()


def test_a_model_of_windows_alone_is_still_refused_by_the_plan():
    """The rule that asked for a full layer now asks ``cache_plan``: EVA's
    plan answers with ``summary``; a model of sliding windows alone has no
    kind that grows with the capacity."""
    from cake_tpu.models.config import tiny_mellum

    with pytest.raises(ValueError, match="nothing that grows with the "
                                         "capacity"):
        tiny_mellum(layer_types=("sliding_attention",) * 4)
    families.check_capacity(CFG)


def test_the_admissions_flash_branch_is_the_references_forward(
        params, tensors, monkeypatch):
    """With the kernels forced (interpreted on the CPU) an admission's
    windows go through the flash prefill kernel, each over a buffer of
    the summaries before it and then its own keys, padded behind the
    queries to whole key blocks: a prompt of three windows less a chunk
    (the last window's buffer holds 16 summaries ahead of 28 keys), then
    steps across the reset at 96 through the step's kernel-or-merge
    choice. Every logit is the reference's."""
    monkeypatch.setenv("CAKE_PALLAS", "1")
    cfg = dataclasses.replace(CFG, rms_norm_eps=1.0002e-5)  # a fresh trace
    tokens = np.concatenate([TOKENS, TOKENS[:14]])  # 104 tokens
    ref_logits = np.asarray(ref.logits(cfg.to_hf_dict(), tensors, tokens))
    cache = _admit(params, np.concatenate([tokens[:92],
                                           np.full(36, 9, np.int32)]), 92,
                   cfg)
    got = _steps(params, cfg, tokens, cache, 92)
    np.testing.assert_allclose(got, ref_logits[92:], atol=TIGHT, rtol=0)

