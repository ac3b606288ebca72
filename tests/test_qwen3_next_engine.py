"""Scalar-gated delta-rule layers beside gated attention (Qwen3-Next's
keys) through the serving engine, the configuration's readers and writers,
the budget, the loaders and every refusal: the second half of
``tests/test_qwen3_next.py`` (a file of its own so that the driver's
workers share the two; fixtures, tolerance and reasons are that file's).
"""

from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import (LlamaConfig, qwen3next_ep4, tiny,
                                    tiny_kda_hybrid, tiny_qwen3_next)
from cake_tpu.obs import metrics
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.parallel.mesh import validate_shardable
from cake_tpu.testing import reference_qwen3_next as ref
from cake_tpu.utils.weights import (latent_hf_tensors, load_llama_params,
                                    save_llama_params)
from test_qwen3_next import (CFG, GREEDY, HF, TIGHT, TOKENS, _params,
                             _through_the_cache)


@pytest.fixture(scope="module")
def params():
    return _params()


@pytest.fixture(scope="module")
def tensors(params):
    return latent_hf_tensors(params, CFG)


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
    logits = np.asarray(ref.logits(HF, tensors, full))
    for j, tok in enumerate(out):
        at = logits[len(prompt) - 1 + j]
        assert at.max() - at[tok] <= TIGHT, (len(prompt), j)


_RNG = np.random.default_rng(7)
PROMPTS = [[int(t) for t in _RNG.integers(3, 250, n)]
           for n in (5, 37, 70, 21, 100, 12)]


def test_batch_generator_streams_and_a_reused_slot_match_reference(params,
                                                                  tensors):
    """Three streams of different lengths through BatchGenerator (a
    bucketed batch prefill whose padding may not touch a state, block
    decode over the carried state), then a short stream admitted in
    chunks of 16 into the slot a long one left: each stream's tokens are
    the reference's argmax, so the slot's state and tail were reset. The
    gauges count the state a stream holds and the rows a token holds, the
    counters the chunks the admission's scans swept against those that
    held a true token."""
    reg = metrics.registry()
    swept, live, resets = (reg.counter(n) for n in (
        "delta.chunks_swept", "delta.chunks_live", "kda.state_resets"))
    before = swept.value, live.value, resets.value
    bg = _engine(params, [PROMPTS[4], PROMPTS[1], PROMPTS[0]],
                 ids=[1, 2, 3], admit_chunk=16)
    # what a stream's state costs: 6 layers x (4 heads of 16 x 8 float32 +
    # a tail of 3 rows of 96 float32)
    assert reg.gauge("cache.state_bytes_per_stream").value == 6 * (
        4 * 16 * 8 * 4 + 3 * 96 * 4)
    assert reg.gauge("cache.token_bytes").value == 2 * 2 * 2 * 16 * 4
    got = _run(bg, {6: lambda e: (e.finish(1), e.enqueue(PROMPTS[3], 4))},
               steps=30)
    for sid, prompt in ((2, PROMPTS[1]), (3, PROMPTS[0]), (4, PROMPTS[3])):
        assert len(got[sid]) >= 8, sid
        _is_the_references_argmax(tensors, prompt, got[sid][:8])
    # the admission of 21 tokens in chunks of 16: two dispatches of one
    # 64-token chunk each in six layers, both of which hold a true token
    assert swept.value - before[0] == 6 * 2
    assert live.value - before[1] == 6 * 2
    assert resets.value - before[2] == 1


def test_a_launchs_longest_row_sets_the_chunks_of_the_scan(params):
    """An admission of 70 tokens in ONE dispatch of its 128-row bucket
    sweeps two chunks a layer and both are live; one of 12 tokens beside it
    in a two-row program sweeps two a row where one of the short row's
    holds a token (the scan is serial over the launch's rows: it stops at
    the longest's last live chunk); and the same two rows in a 256-row
    bucket sweep no more: a bucket's padding costs no chunk."""
    from cake_tpu.runtime import batch_generator as engine

    reg = metrics.registry()
    swept, live = (reg.counter(n) for n in ("delta.chunks_swept",
                                            "delta.chunks_live"))
    bg = _engine(params, [[4, 4, 4], [4, 4, 5]], ids=[90, 91])
    before = swept.value, live.value
    bg._count_delta_chunks(128, [70])
    assert (swept.value - before[0], live.value - before[1]) == (12, 12)
    bg._count_delta_chunks(128, [70, 12])
    assert (swept.value - before[0], live.value - before[1]) == (36, 30)
    bg._count_delta_chunks(256, [70, 12])
    assert (swept.value - before[0], live.value - before[1]) == (60, 48)
    # a later chunk of a chunked admission: a row that ended before it
    # holds nothing of it, the other's 200 left fill its four chunks
    bg._count_delta_chunks(256, [200, -30])
    assert (swept.value - before[0], live.value - before[1]) == (108, 72)
    assert engine._DELTA_CHUNKS_SWEPT is swept
    # a model without delta-rule layers counts nothing
    bg._delta_layers = 0
    bg._count_delta_chunks(128, [70])
    assert swept.value - before[0] == 108


def test_engine_counts_the_chunks_that_ran_in_the_scan_kernel(params,
                                                              monkeypatch):
    """``delta.chunks_kernel`` is ``delta.chunks_swept`` over the
    dispatches whose bucket's program holds the scan kernel and stays
    where it was over the others. An arrival of 100 tokens is admitted in
    chunks of 32 (a bucket no other test of this process traces) with the
    scan chosen as the kernel for a decay a head (interpreted; the choice
    is ``ops.kda``'s, steered here as a chip would answer it): the gauge
    says a traced admission holds the kernel, the counter takes every
    swept chunk, and the stream's tokens are those of the same engine
    under XLA's loop, whose dispatches the counter leaves alone."""
    from cake_tpu.ops import kda

    reg = metrics.registry()
    swept, inside = (reg.counter(n) for n in ("delta.chunks_swept",
                                              "delta.chunks_kernel"))

    def served(chunk):
        bg = _engine(params, [PROMPTS[0], PROMPTS[3]], ids=[1, 2],
                     admit_chunk=chunk)
        before = swept.value, inside.value
        got = _run(bg, {1: lambda e: (e.finish(1), e.enqueue(PROMPTS[4], 7))},
                   steps=14)
        return got[7][:8], swept.value - before[0], inside.value - before[1]

    plain = served(64)
    assert plain[1:] == (6 * 2, 0)  # two dispatches of a chunk, six layers
    monkeypatch.setattr(
        kda, "kda_chunk_choice",
        lambda t, dk, dv, scalar: "kernel" if scalar and t == 32 else "xla")
    # four dispatches of one (half) chunk each in six layers
    assert served(32) == (plain[0], 6 * 4, 6 * 4)
    assert kda.chunk_form_traced(32) == "kernel"
    assert kda.chunk_form_traced(64) == "xla"
    assert reg.gauge("delta.chunk_kernel").value == 1


# -- the configuration, the plan, the budget, the loaders -----------------------

def _catalog() -> dict:
    """The catalog's ``config`` of Qwen3-Next-80B-A3B-Instruct (the
    published ``config.json`` without the keys that say nothing of its
    shape)."""
    return {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936,
    }


def test_the_catalogs_keys_are_read_and_round_trip():
    """The published file reads into the preset's fields (all 512 experts
    held), the cut's ``expert_share`` into a told share, and what is
    written reads back; every key of the catalog comes back as it went
    in."""
    ids = dict(bos_token_id=0, eos_token_id=1)  # the tokenizer's, not here
    cfg = LlamaConfig.from_hf_dict(_catalog(), max_seq_len=262144, **ids)
    assert cfg == qwen3next_ep4(n_routed_experts=512, router_experts=512)
    assert cfg.family.model_types == ("qwen3_next",)
    assert cfg.layer_types[:4] == ("linear_attention",) * 3 + (
        "full_attention",)
    assert cfg.layer_types.count("full_attention") == 12
    assert cfg.cache_plan == {"rows": (12, 2, 256, 256),
                              "state": (36, 32, 128, 128),
                              "conv": (36, 3, 8192)}
    assert cfg.rope_dim == 64 and cfg.cache_row == (2, 256, 256)
    assert cfg.delta_rule == (16, 32, 128, 128, 4)
    written = cfg.to_hf_dict()
    for key, value in _catalog().items():
        # (the cli's --max-seq; silu is the default and is not written)
        if key not in ("max_position_embeddings", "hidden_act"):
            assert written[key] == value, key
    cut = dict(_catalog(), num_hidden_layers=8, num_experts=128,
               vocab_size=37984,
               expert_share={"n_routed_experts": 512, "ep": 4, "rank": 0})
    share = LlamaConfig.from_hf_dict(cut, max_seq_len=8192, **ids)
    assert share == qwen3next_ep4(num_hidden_layers=8, vocab_size=37984,
                                  max_seq_len=8192)
    assert LlamaConfig.from_hf_dict(share.to_hf_dict(),
                                    max_seq_len=8192) == share  # (ids kept)
    assert share.to_hf_dict()["expert_share"] == cut["expert_share"]
    # the file's own layer_types, where it carries them, must agree
    types = list(share.layer_types)
    assert LlamaConfig.from_hf_dict(dict(cut, layer_types=types),
                                    max_seq_len=8192, **ids) == share


def test_hbm_budget_holds_the_cut_and_the_published_model():
    """The benchmark's cut (8 of 48 layers, 128 of 512 experts, a quarter
    of the vocabulary) at 32 slots x 8192: 6.83 GiB of weights (ISSUE 57's
    count); two full layers' rows (1.0 GiB), six layers' state (384 MiB)
    and tails (9 MiB). The published 48 layers budget too (not run): 80 B
    parameters less the prediction block."""
    from cake_tpu.utils.memory import hbm_budget

    cfg = qwen3next_ep4(num_hidden_layers=8, vocab_size=37984,
                        max_seq_len=8192)
    b = hbm_budget(cfg, batch=32, max_seq=8192)
    experts = 128 * 3 * 2048 * 512 + 3 * 2048 * 512 + 2048 * 512 + 2048
    delta = (2048 * 12288 + 2048 * 64 + 4 * 8192 + 2 * 32 + 128
             + 4096 * 2048 + 2 * 2048)
    full = (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
            + 2 * 2048)
    assert b["layers"] == 2 * (6 * delta + 2 * full + 8 * experts)
    assert b["kv_cache"] == 32 * (2 * 8192 * 2 * 512 * 2
                                  + 6 * (32 * 128 * 128 * 4 + 3 * 8192 * 2))
    weights = b["total"] - b["kv_cache"]
    assert 6.82 * 2**30 < weights < 6.85 * 2**30, weights / 2**30
    whole = hbm_budget(qwen3next_ep4(n_routed_experts=512), batch=1,
                       max_seq=8192)
    count = (whole["total"] - whole["kv_cache"]) / 2
    assert 79e9 < count < 80.5e9, count
    with pytest.raises(ValueError, match="not wired"):
        hbm_budget(cfg, quant="int8")


def test_checkpoint_round_trip_reads_the_files_names(tmp_path, params,
                                                     tensors):
    """Through the real writer and loader: the same pytree, the same
    logits, under Hugging Face's names (``linear_attn.in_proj_qkvz``,
    ``mlp.shared_expert_gate``, every held expert under its global id),
    found by the loader from the checkpoint's own names; a stored
    prediction block is skipped."""
    from safetensors.numpy import load_file, save_file

    save_llama_params(params, tmp_path, config=CFG)
    (tmp_path / "config.json").write_text(json.dumps(HF))
    stored = load_file(tmp_path / "model.safetensors")
    a = "model.layers.0.linear_attn."
    assert stored[a + "in_proj_qkvz.weight"].shape == (128, 64)
    assert stored[a + "in_proj_ba.weight"].shape == (8, 64)
    assert stored[a + "conv1d.weight"].shape == (96, 1, 4)
    assert stored[a + "A_log"].shape == stored[a + "dt_bias"].shape == (4,)
    assert stored[a + "norm.weight"].shape == (8,)
    assert stored["model.layers.3.self_attn.q_proj.weight"].shape == (128, 64)
    assert stored["model.layers.3.mlp.shared_expert_gate.weight"].shape == (
        1, 64)
    assert sorted(n.split(".")[5] for n in stored if n.startswith(
        "model.layers.7.mlp.experts.") and "up_proj" in n) == list("4567")
    stored["mtp.layers.0.input_layernorm.weight"] = np.zeros(64, np.float32)
    save_file(stored, tmp_path / "model.safetensors")
    (tmp_path / "model.safetensors.index.json").unlink()  # the old names
    cfg = LlamaConfig.from_hf_json(tmp_path / "config.json", dtype="float32",
                                   max_seq_len=256, eos_token_id=-1)
    assert cfg == CFG
    skipped = metrics.registry().counter("load.tensors_skipped")
    before = skipped.value
    loaded = load_llama_params(tmp_path, cfg.num_hidden_layers,
                               dtype="float32")
    assert skipped.value - before == 1
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for got, held in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(held),
                                   atol=1e-6, rtol=0)  # (w - 1) + 1
    got, _ = _through_the_cache(loaded, TOKENS[:24], 24, 24, 32)
    want = np.asarray(ref.logits(HF, tensors, TOKENS[:24]))
    np.testing.assert_allclose(got, want, atol=TIGHT, rtol=0)
    with pytest.raises(NotImplementedError, match="serve it in bf16"):
        load_llama_params(tmp_path, cfg.num_hidden_layers, quantize="int8")


def test_the_single_stream_path_keeps_padding_out_of_the_state(params,
                                                               tensors):
    """``LlamaGenerator`` (the cli's local path) prefills a 7-token prompt
    in its 16-row bucket: the padding may not touch the state, so its
    tokens are the reference's, and a second prompt starts from zero."""
    from cake_tpu.runtime.generator import LlamaGenerator

    gen = LlamaGenerator(CFG, params, tokenizer=None,
                         settings=SamplerSettings(**GREEDY), max_seq=64)
    for prompt in (PROMPTS[0] + [9, 9], PROMPTS[5]):
        gen.set_prompt(prompt)
        out = [gen.next_token(i).id for i in range(6)]
        _is_the_references_argmax(tensors, prompt, out)


def _hf(**over):
    return dict(HF, **over)


@pytest.mark.parametrize("what, match", [
    (lambda p: validate_shardable(CFG, 2, 1), "one stage"),
    (lambda p: validate_shardable(CFG, 1, 2), "under tp"),
    (lambda p: validate_shardable(CFG, 1, 1, 2), "sp = 1"),
    (lambda p: _engine(p, [[1, 2]], kv_layout="paged"), "slot layout"),
    (lambda p: _engine(p, [[1, 2]], spec_k=2), "recurrent state"),
    (lambda p: init_cache(CFG, quant="int8"), "int8 cache"),
    (lambda p: init_cache(CFG, num_layers=2), "cached whole"),
    (lambda p: llama.layer_shapes(CFG), "stack a kind"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(decoder_sparse_step=2)),
     "decoder_sparse_step"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(mlp_only_layers=[0])),
     "mlp_only_layers"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(rope_scaling={
        "rope_type": "yarn", "factor": 4.0})), "rope_scaling"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(attention_bias=True)),
     "attention_bias"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(norm_topk_prob=False)),
     "norm_topk_prob"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(
        shared_expert_intermediate_size=64)), "another width"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(layer_types=[
        "full_attention"] * 8)), "disagrees with full_attention_interval"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(hidden_act="gelu")),
     "hidden_act"),
    (lambda p: tiny_qwen3_next(linear_num_value_heads=3), "whole groups"),
    (lambda p: tiny_qwen3_next(rope_fraction=0.3), "leading channels"),
    (lambda p: tiny_qwen3_next(attn_gate=None), "attn_gate 'elementwise'"),
    (lambda p: tiny_qwen3_next(shared_expert_gate=False),
     "shared_expert_gate"),
    (lambda p: tiny_qwen3_next(scoring_func="sigmoid"), "scoring_func"),
    (lambda p: tiny_qwen3_next(routed_scaling_factor=2.5), "renormalised"),
    (lambda p: tiny_qwen3_next(layer_types=("linear_attention",) * 8),
     "without a full_attention layer"),
    (lambda p: tiny_qwen3_next(layer_types=("full_attention",) * 8),
     "without a linear_attention layer"),
    (lambda p: tiny_qwen3_next(sliding_window=8), "no sliding_window"),
    (lambda p: tiny_qwen3_next(first_expert=14), "held of 16"),
    (lambda p: tiny(rope_fraction=0.5), "alone"),
    (lambda p: tiny_kda_hybrid(shared_expert_gate=True), "alone"),
    (lambda p: tiny(attn_gate="elementwise"), "alone"),
], ids=["stages", "tp", "sp", "paged", "speculation", "int8-cache",
        "layer-range", "one-stack", "sparse-step", "mlp-only", "rope-scaling",
        "bias", "unnormalised-shares", "shared-width", "types-disagree",
        "activation", "ragged-groups", "odd-rotation", "no-gate",
        "shared-ungated", "scoring", "scaling-factor", "no-full-layer",
        "no-linear-layer", "window", "share-outside", "rotation-elsewhere",
        "shared-gate-elsewhere", "gate-elsewhere"])
def test_family_limits_are_refused_with_a_message(params, what, match):
    with pytest.raises(ValueError, match=match):
        what(params)


def test_single_stream_speculation_is_refused_over_a_state(params):
    from cake_tpu.runtime.speculative import SpeculativeGenerator

    with pytest.raises(ValueError, match="conv, state"):
        SpeculativeGenerator(CFG, params, tokenizer=None,
                             settings=SamplerSettings(**GREEDY), max_seq=64,
                             spec_k=2)
