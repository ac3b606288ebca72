"""A residual stream four hidden vectors wide over the latent family
(``model_type`` ``xing4_0``, manifold-constrained hyper-connections): the
program against the plain reference
(``cake_tpu/testing/reference_mhc_mla_moe.py``) on seeded weights, tiny
sizes, CPU, float32.

- ``ops/hyper.py``: the doubly stochastic ``H_res``, the clamp, the forms
  against the reference's ``sum(axis)`` rounds;
- a prompt's logits, then decode through the cache token by token;
- the same through ``BatchGenerator``: block decode, admissions in buckets
  and in bands, streams of different length, a slot reused;
- the tie to the shared code: with coefficients that make stream 0 the
  plain residual the logits are the plain latent model's;
- the configuration (the catalog's keys, the round trip, every refusal),
  the loader (float32 ``hc`` tensors, a written prediction block skipped
  and counted), the engine's gauges, the benchmark's copy of the reference.

Sections (c), the engine, and the latent family's extras are
``tests/test_xing4_engine.py`` and ``tests/test_xing4_latent.py`` since
PR 59; what the three share is ``tests/xing4_kit.py``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import families, llama
from cake_tpu.models.config import (
    LlamaConfig, tiny, tiny_jamba, tiny_kda_hybrid, tiny_xing4, xing4_29b,
)
from cake_tpu.obs import metrics
from cake_tpu.ops import hyper
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.parallel.mesh import validate_shardable
from cake_tpu.testing import reference_mhc_mla_moe as ref
from cake_tpu.utils.weights import load_llama_params, save_llama_params

from xing4_kit import (  # noqa: F401
    CFG, CFG20, ROOT, TIGHT, TOKENS, _STEP, _STEPS, _decode_all, _engine,
    _is_the_references_argmax, _run, params, tensors, want,
)


# -- (a) ops/hyper.py --------------------------------------------------------------

def _stream(rows=(3, 5), n=4, c=64, seed=4, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), rows + (n, c)).astype(
        dtype)


def _hc_of(params, part="attn", layer=1):
    stack = params["layers"]["moe"]
    return tuple(stack[f"hc_{part}_{t}"][layer - 1]
                 for t in ("fn", "base", "scale"))


def test_h_res_is_doubly_stochastic_and_the_references(params, tensors):
    """After 20 rounds ``H_res`` is non-negative with rows and columns
    summing to 1 within 1e-5 (logits of a modest spread; the columns, the
    last division, always; with the seeded biases' spread a token in a
    hundred is still 1e-2 off in a row: 20 rounds are what the file
    gives), and the cells' adds are the reference's ``sum(axis)`` rounds
    to 1e-6; so are ``H_pre`` and ``H_post``."""
    mild = jnp.asarray(np.random.default_rng(5).normal(
        size=(64, 4, 4)) * 0.25 + 2 * np.eye(4), jnp.float32)
    mild = np.asarray(hyper.sinkhorn(mild, 20, 1e-6, (-30.0, 30.0)))
    assert mild.min() > 0
    np.testing.assert_allclose(mild.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(mild.sum(-2), 1.0, atol=1e-5)
    x = _stream()
    co = hyper.coefficients(hyper.split(x), _hc_of(params), CFG20)
    res = np.asarray([[np.asarray(c) for c in row] for row in co.res])
    res = np.moveaxis(res, (0, 1), (-2, -1))  # [3, 5, n, n]
    assert res.min() >= 0 and 0.05 < res.std()
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-5)
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=5e-2)
    assert np.median(np.abs(res.sum(-1) - 1.0)) < 1e-3
    # neither the identity nor uniform, and another matrix a token
    assert np.abs(res - np.eye(4)).max() > 0.3
    assert np.abs(res[0, 0] - res[1, 2]).max() > 0.05
    with jax.default_matmul_precision("highest"):
        pre, post, want_res = ref.coefficients(
            CFG20.to_hf_dict(), tensors, "model.layers.1.", "attn",
            x.reshape(15, 4, 64))
    np.testing.assert_allclose(res.reshape(15, 4, 4), want_res, atol=1e-6)
    np.testing.assert_allclose(
        np.stack([np.asarray(c) for c in co.pre], -1).reshape(15, 4), pre,
        atol=1e-6)
    np.testing.assert_allclose(
        np.stack([np.asarray(c) for c in co.post], -1).reshape(15, 4), post,
        atol=1e-6)


def test_sinkhorn_reaches_the_clamp_and_matches_sum_axis():
    """The public ``sinkhorn`` is the reference's rounds on the clamped
    logits, and a logit of 40 is clamped to 30: the cells beside it in its
    row are left ``e^-30`` of it and not ``e^-40`` (a ratio of ``e^10``
    that 20 rounds of scaling rows and columns keep)."""
    logits = jnp.asarray(np.random.default_rng(2).normal(size=(6, 4, 4)),
                         jnp.float32).at[0, 1, 2].set(40.0).at[3, 0, 0].set(
                             -40.0)
    got = np.asarray(hyper.sinkhorn(logits, 20, 1e-6, (-30.0, 30.0)))
    want = np.asarray(ref.sinkhorn(jnp.exp(jnp.clip(logits, -30.0, 30.0)),
                                   20, 1e-6))
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(got.sum(-2), 1.0, atol=1e-5)
    lone = jnp.zeros((1, 4, 4), jnp.float32).at[0, 1, 2].set(40.0)
    clamped = np.asarray(hyper.sinkhorn(lone, 20, 0.0, (-30.0, 30.0)))
    free = np.asarray(hyper.sinkhorn(lone, 20, 0.0, (-50.0, 50.0)))
    assert clamped[0, 1, 2] > 0.9 and free[0, 1, 2] > 0.9
    ratio = clamped[0, 1, 0] / free[0, 1, 0]
    assert np.exp(9.0) < ratio < np.exp(11.0), ratio


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_mixes_are_the_references_einsums(params, tensors, dtype):
    """``pre_mix`` and ``post_mix`` (sums written out over the streams)
    against the reference's ``einsum`` over the axis of streams; a
    bfloat16 stream meets ``phi`` in three exact parts, so its
    coefficients are the float32 ones of the SAME (rounded) stream."""
    x = _stream(dtype=dtype)
    y = jax.random.normal(jax.random.PRNGKey(9), (3, 5, 64)).astype(dtype)
    hc = _hc_of(params, "ffn", 2)
    co = hyper.coefficients(hyper.split(x), hc, CFG20)
    u = hyper.pre_mix(hyper.split(x), co)
    out = hyper.join(hyper.post_mix(hyper.split(x), y, co))
    assert u.dtype == out.dtype == dtype and out.shape == x.shape
    xf, yf = (np.asarray(a.astype(jnp.float32)).reshape((15,) + a.shape[2:])
              for a in (x, y))
    with jax.default_matmul_precision("highest"):
        pre, post, res = ref.coefficients(
            CFG20.to_hf_dict(), tensors, "model.layers.2.", "ffn",
            jnp.asarray(xf))
        want_u = jnp.einsum("tj,tjc->tc", pre, xf)
        want = post[:, :, None] * yf[:, None, :] + jnp.einsum(
            "tij,tjc->tic", res, xf)
    tol = 1e-5 if dtype == jnp.float32 else 0.04  # one bfloat16 rounding
    np.testing.assert_allclose(
        np.asarray(u.astype(jnp.float32)).reshape(15, 64), want_u, atol=tol)
    np.testing.assert_allclose(
        np.asarray(out.astype(jnp.float32)).reshape(15, 4, 64), want,
        atol=tol)
    got_res = np.stack([np.stack([np.asarray(c) for c in row], -1)
                        for row in co.res], -2).reshape(15, 4, 4)
    np.testing.assert_allclose(got_res, res, atol=2e-6)


def test_the_ends_widen_and_sum():
    x = jnp.arange(24.0).reshape(2, 3, 4)
    wide = hyper.widen(x, 4)
    assert wide.shape == (2, 3, 4, 4)
    np.testing.assert_array_equal(hyper.narrow(wide, 4), 4 * x)
    np.testing.assert_array_equal(hyper.join(hyper.split(wide)), wide)
    assert hyper.widen(x, 1) is x and hyper.narrow(x, 1) is x


# -- (b) the layer loop against the reference --------------------------------------

def test_prompt_logits_match_reference(params, want):
    cache = init_cache(CFG, batch=1, max_seq=64)
    logits, _ = llama.forward(params, jnp.asarray(TOKENS[None]), cache, 0, CFG)
    np.testing.assert_allclose(logits[0], want[-1], atol=TIGHT, rtol=0)


@pytest.fixture(params=["xla", "kernels-forced"])
def decode_path(request, monkeypatch):
    """The step as the CPU takes it, and with kernels forced
    (``CAKE_PALLAS=1``: the interpreted ``latent_decode`` kernel over the
    carried latent cache and the expert block's sorted form)."""
    forced = request.param == "kernels-forced"
    monkeypatch.setenv("CAKE_PALLAS", "1" if forced else "auto")
    _STEPS.clear()  # the gauge is set where a step is TRACED
    gauge = metrics.registry().gauge("attn.decode_kernel")
    gauge.set(-1)
    yield
    assert gauge.value == int(forced)


@pytest.mark.parametrize("chunk", [None, 4], ids=["one-chunk", "chunks-of-4"])
def test_prefill_then_decode_through_the_cache_match_reference(
        params, want, chunk, decode_path):
    """A prefill of 8 tokens (whole, or in chunks of 4), then a step a
    token through the latent cache: the logits at every position against
    the reference's full forward pass."""
    got, cache = _decode_all(params, CFG, TOKENS[:24], prefill=8, chunk=chunk)
    assert got.shape[0] == 17
    np.testing.assert_allclose(got, want[7:24], atol=TIGHT, rtol=0)
    assert sorted(a.shape for a in jax.tree.leaves(cache)) == sorted([
        (3, 1, 1, 64, 16), (3, 1, 1, 64, 8)])  # the latent row, as axk1's


def test_bfloat16_fails_the_tolerance(params, want):
    """The tolerance is tight enough that a lowered precision fails it."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    low = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
        params)
    got, _ = _decode_all(low, cfg, TOKENS[:16], prefill=8)
    assert np.abs(got - want[7:16]).max() > 20 * TIGHT


def test_h_res_as_the_identity_fails_the_tolerance(tensors, want):
    """The benchmark's second control: four plain residual streams
    (``H_res = I``) are another model."""
    off = np.asarray(ref.logits(CFG.to_hf_dict(), tensors, TOKENS,
                                identity_res=True))
    assert np.abs(off - want).max() > 100 * TIGHT


def test_the_mixes_run_under_their_named_scopes(params):
    """``mhc.coeff``, ``mhc.pre`` and ``mhc.post`` are in the lowered
    program's ``op_name``s: what a device trace's operations are told
    apart by."""
    text = jax.jit(lambda p, t, c: llama.forward(p, t, c, 0, CFG)).lower(
        params, TOKENS[None, :8], init_cache(CFG, 1, 64)).as_text(
            debug_info=True)
    for scope in ("mhc.coeff", "mhc.pre", "mhc.post", "mla", "moe.shared"):
        assert scope in text, scope


# -- (d) the tie to the shared code -------------------------------------------------

def test_stream_zero_as_the_plain_residual_is_the_plain_latent_model(params):
    """With gains of 0, ``b_res`` +30 on the diagonal and -30 off it,
    ``b_pre`` = (+30, -30, -30, -30) and ``b_post`` = 0, ``H_res`` is the
    identity, ``H_pre`` picks stream 0 and every stream takes ``y`` whole:
    stream 0 is the plain residual, every stream equals it, and the last
    norm forgives the factor 4. The logits are those of the SAME weights
    served as a plain latent model (``hc_mult`` 1)."""
    n = CFG.hc_mult
    base = np.full((n * (n + 2),), -30.0, np.float32)
    base[0] = 30.0
    base[n:2 * n] = 0.0
    base[2 * n + (n + 1) * np.arange(n)] = 30.0
    tied = dict(params, layers={
        name: {k: (jnp.zeros_like(v) if k.endswith("_scale")
                   and k.startswith("hc_") else
                   jnp.broadcast_to(base, v.shape) if k.endswith("_base")
                   and k.startswith("hc_") else v)
               for k, v in stack.items()}
        for name, stack in params["layers"].items()})
    plain_cfg = dataclasses.replace(CFG, hc_mult=1)
    plain = dict(params, layers={
        name: {k: v for k, v in stack.items() if not k.startswith("hc_")}
        for name, stack in params["layers"].items()})
    assert plain_cfg.family is families.LATENT
    assert jax.tree.structure(plain) == jax.tree.structure(
        jax.eval_shape(lambda k: llama.init_params(plain_cfg, k),
                       jax.random.PRNGKey(0)))
    got, _ = _decode_all(tied, CFG, TOKENS[:16], prefill=8)
    want, _ = _decode_all(plain, plain_cfg, TOKENS[:16], prefill=8)
    np.testing.assert_allclose(got, want, atol=TIGHT, rtol=0)
    # and with the seeded coefficients it is another model
    other, _ = _decode_all(params, CFG, TOKENS[:16], prefill=8)
    assert np.abs(other - want).max() > 100 * TIGHT


# -- (e) the configuration -----------------------------------------------------------

def _catalog() -> dict:
    """The catalog's ``config`` of Xing4.0-29B-A4B, verbatim (the published
    ``config.json`` without the keys that say nothing of its shape)."""
    return {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
        "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
        "kv_lora_rank": 512, "max_position_embeddings": 262144,
        "model_type": "xing4_0", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30, "q_lora_rank": 768,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072,
    }


def test_the_catalogs_keys_are_read_and_round_trip():
    """``from_hf_dict`` reads the published keys verbatim (the bias the
    choice is made on, the ``hc`` keys, the prediction block read and
    ignored), the preset is the same configuration, and ``to_hf_dict``
    writes what reads back."""
    published = _catalog()
    whole = LlamaConfig.from_hf_dict(published, max_seq_len=262144,
                                     bos_token_id=0, eos_token_id=1)
    assert whole == xing4_29b()
    assert whole.family is families.LATENT
    assert (whole.hc_mult, whole.hc_sinkhorn_iters, whole.hc_eps,
            whole.hc_res_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert whole.router_bias and (whole.n_group, whole.topk_group) == (1, 1)
    assert whole.layer_kinds[:3] == (("mla", "dense"), ("mla", "dense"),
                                     ("mla", "moe"))
    assert whole.cache_plan == {"rows": (40, 1, 512, 64)}
    assert whole.cache_row_values * 2 == 1152
    assert whole.resid_token_bytes == 28672
    assert xing4_29b(hc_mult=1).resid_token_bytes == 7168
    back = whole.to_hf_dict()
    for key in ("hc_mult", "hc_sinkhorn_iters", "hc_eps",
                "mhc_h_res_clamp_min", "mhc_h_res_clamp_max", "topk_method",
                "n_group", "topk_group", "first_k_dense_replace",
                "n_routed_experts", "n_shared_experts", "q_lora_rank",
                "kv_lora_rank", "rope_scaling", "routed_scaling_factor",
                "scoring_func", "model_type", "vocab_size", "hidden_size"):
        assert back[key] == published[key], key
    assert "router_bias" not in back and "hc_res_clamp" not in back
    assert LlamaConfig.from_hf_dict(back, max_seq_len=262144) == whole
    assert LlamaConfig.from_hf_dict(
        CFG.to_hf_dict(), dtype="float32", max_seq_len=256,
        eos_token_id=-1) == CFG
    # the plain residual writes none of the keys, and a biased choice with
    # DeepSeek-V3's own model_type round-trips too
    assert not {k for k in tiny_xing4(hc_mult=1).to_hf_dict()
                if "hc_" in k}
    v3 = tiny_xing4(hc_mult=1, model_type="deepseek_v3")
    assert v3.to_hf_dict()["topk_method"] == "noaux_tc"
    assert LlamaConfig.from_hf_dict(v3.to_hf_dict(), dtype="float32",
                                    max_seq_len=128) == v3
    # the shapes the published widths give
    shapes = llama.stack_shapes(whole)
    assert shapes["moe"]["hc_attn_fn"](whole) == (14336, 24)
    assert shapes["dense"]["hc_ffn_base"](whole) == (24,)
    assert shapes["moe"]["hc_ffn_scale"](whole) == (3,)
    assert shapes["moe"]["b_router"](whole) == (64,)
    assert "hc_attn_fn" not in llama.stack_shapes(xing4_29b(hc_mult=1))["moe"]


def test_hbm_budget_counts_the_float32_hc_tensors():
    """The benchmark's cut (1 dense + 6 expert layers, every expert, the
    whole vocabulary): ISSUE 51's 5,537.7 M parameters, the ``hc`` tensors
    at 4 bytes, and 0.98 GiB of latent rows."""
    from cake_tpu.utils.memory import hbm_budget

    cfg = xing4_29b(num_hidden_layers=7, first_k_dense_replace=1,
                    max_seq_len=4096)
    b = hbm_budget(cfg, batch=32, max_seq=4096)
    hc = 7 * 2 * (14336 * 24 + 24 + 3)
    params = 6 * 744_989_046 + 128_196_918 + 2 * 469_762_048 + 3584
    assert b["layers"] + b["embed_replicated"] + b["head"] == (
        2 * params + 2 * hc)
    assert b["kv_cache"] == 32 * 4096 * 7 * 1152
    plain = hbm_budget(dataclasses.replace(cfg, hc_mult=1), batch=32,
                       max_seq=4096)
    assert b["layers"] - plain["layers"] == 4 * hc


def _hf(**over):
    return dict(CFG.to_hf_dict(), **over)


@pytest.mark.parametrize("what, match", [
    (lambda p: tiny(hc_mult=4), "latent-attention family alone"),
    (lambda p: tiny_kda_hybrid(hc_mult=4), "latent-attention family alone"),
    (lambda p: tiny_jamba(hc_mult=2), "latent-attention family alone"),
    (lambda p: LlamaConfig.from_hf_dict({
        "model_type": "llama", "hc_mult": 4, "num_hidden_layers": 2}),
     "latent-attention family alone"),
    (lambda p: tiny_xing4(hc_sinkhorn_iters=0), "Sinkhorn rounds"),
    (lambda p: tiny_xing4(hc_res_clamp=(3.0, -3.0)), "Sinkhorn rounds"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(topk_method="seq_aux")),
     "topk_method 'seq_aux' is not wired"),
    (lambda p: LlamaConfig.from_hf_dict(_hf(moe_layer_freq=2)),
     "moe_layer_freq"),
    (lambda p: validate_shardable(CFG, 2, 1), "one stage"),
    (lambda p: validate_shardable(CFG, 1, 2), "tp = 1"),
    (lambda p: validate_shardable(CFG, 1, 1, 2), "sp = 1"),
    (lambda p: _engine(p, [[1, 2]], kv_layout="paged"), "slot layout"),
    (lambda p: _engine(p, [[1, 2]], kv_quant="int8"), "int8 cache"),
    (lambda p: init_cache(CFG, quant="int8"), "int8 cache"),
    (lambda p: _engine(p, [[1, 2]]).export_stream(0), "paged"),
], ids=["bare-stack", "hybrid", "state-space", "file-of-another-family",
        "no-rounds", "clamp", "topk-method", "layer-freq", "stages", "tp",
        "sp", "pages", "int8-cache", "int8-cache-init", "export"])
def test_what_is_not_wired_is_refused_with_a_message(params, what, match):
    with pytest.raises((ValueError, NotImplementedError, RuntimeError),
                       match=match):
        what(params)


def test_a_topology_that_splits_the_layers_is_refused(params):
    """The wire ships ``[B, T, hidden]`` between a topology's layer
    ranges: the master and the worker refuse a wide stream before they
    plan a walk, and the loader has no layer ranges for the family."""
    from cake_tpu.parallel.topology import Topology
    from cake_tpu.runtime.master import build_runners
    from cake_tpu.runtime.worker import Worker

    topo = Topology.from_dict({"w0": {"host": "127.0.0.1:1",
                                      "layers": ["model.layers.1-2"]}})
    with pytest.raises(ValueError, match="not wired across the wire"):
        build_runners(CFG, topo, lambda lo, hi: None)
    with pytest.raises(ValueError, match="not wired across the wire"):
        Worker("w0", CFG, topo, lambda lo, hi: None, address="127.0.0.1:0")
    # the plain residual passes the check (and fails later, at the loader)
    from cake_tpu.runtime import protocol

    protocol.check_stream_width(dataclasses.replace(CFG, hc_mult=1))


def test_an_arrival_through_a_prefix_hit_matches_reference(params, tensors):
    """Prefix reuse is the latent family's as it was (a stored row is
    latent rows and no part of the wide stream): an arrival that opens
    with a banked prefix prefills its remainder alone and gives the
    reference's tokens."""
    sysp = [(i * 7) % 100 + 2 for i in range(16)]
    bg = _engine(params, [sysp + [5, 9, 2], sysp + [3, 1, 4]], ids=[0, 1],
                 admit_chunk=8, prefix_share_min=8, prefix_block=8)
    assert bg._prefix_entries > 0
    for _ in range(3):
        bg.step()
    bg.streams[1].done = True
    arrival = sysp + [8, 8, 4]
    d0 = bg.stats()["admit_dispatches"]
    bg.enqueue(list(arrival), stream_id=9)
    while bg.pending_admissions():
        bg.step()
    assert bg.stats()["admit_dispatches"] - d0 == 1  # the remainder alone
    assert bg.stats()["prefix_hits"] >= 1
    got = _run(bg, steps=10)
    _is_the_references_argmax(tensors, arrival, got[9][:8])


# -- (f) the loader --------------------------------------------------------------------

def test_checkpoint_round_trip_keeps_hc_float32_and_skips_the_mtp_block(
        tmp_path, params, want):
    """Through the real writer and loader: the ``hc`` tensors are stored
    under their names (``fn`` a torch linear ``[24, n C]``) and load as
    float32 under ``--dtype bf16`` while everything else is cast; the
    tensors of a written next-token prediction block (the layer past the
    trunk's depth and ``mtp.*``) are never read, and counted."""
    from safetensors.numpy import load_file, save_file

    save_llama_params(params, tmp_path, config=CFG)
    (tmp_path / "config.json").write_text(json.dumps(dict(
        CFG.to_hf_dict(), num_nextn_predict_layers=1)))
    stored = load_file(tmp_path / "model.safetensors")
    assert stored["model.layers.0.hc_attn_fn"].shape == (24, 256)
    assert stored["model.layers.2.hc_ffn_base"].shape == (24,)
    assert stored["model.layers.1.hc_ffn_scale"].dtype == np.float32
    assert stored["model.layers.1.mlp.gate.e_score_correction_bias"].shape == (
        16,)
    block = {f"model.layers.{CFG.num_hidden_layers}.{k}": v for k, v in {
        "eh_proj.weight": np.ones((64, 128), np.float32),
        "enorm.weight": np.ones((64,), np.float32),
        "hc_attn_fn": np.full((24, 256), np.nan, np.float32),
        "self_attn.kv_a_proj_with_mqa.weight": np.full((24, 64), np.nan,
                                                       np.float32),
    }.items()}
    block["mtp.norm.weight"] = np.ones((64,), np.float32)
    save_file({**stored, **block}, tmp_path / "model.safetensors")
    index = json.loads((tmp_path / "model.safetensors.index.json").read_text())
    index["weight_map"].update({k: "model.safetensors" for k in block})
    (tmp_path / "model.safetensors.index.json").write_text(json.dumps(index))
    cfg = LlamaConfig.from_hf_json(tmp_path / "config.json", dtype="float32",
                                   max_seq_len=CFG.max_seq_len)
    assert cfg == dataclasses.replace(CFG, eos_token_id=cfg.eos_token_id)
    skipped = metrics.registry().counter("load.tensors_skipped")
    before = skipped.value
    loaded = load_llama_params(tmp_path, cfg.num_hidden_layers,
                               dtype="float32")
    assert skipped.value - before == len(block)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    cache = init_cache(cfg, batch=1, max_seq=64)
    logits, _ = llama.forward(loaded, jnp.asarray(TOKENS[None]), cache, 0, cfg)
    np.testing.assert_allclose(logits[0], want[-1], atol=TIGHT, rtol=0)
    low = load_llama_params(tmp_path, cfg.num_hidden_layers,
                            dtype="bfloat16")
    for name, leaf in low["layers"]["moe"].items():
        kind = jnp.float32 if name in llama.HC_TENSORS else jnp.bfloat16
        assert leaf.dtype == kind, name
    np.testing.assert_array_equal(low["layers"]["dense"]["hc_attn_fn"],
                                  params["layers"]["dense"]["hc_attn_fn"])


# -- the benchmark's copy of the reference ----------------------------------------------

def _bench_arch():
    """``benchmark/arch/mhc_mla_moe.py``, loaded as the harness loads it
    (its directory's shared modules on the path)."""
    root = ROOT / "benchmark"
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    spec = importlib.util.spec_from_file_location(
        "bench_arch_mhc_mla_moe_under_test",
        root / "arch" / "mhc_mla_moe.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _bench_cfg() -> dict:
    return dict(CFG.to_hf_dict(), hidden_size=128, vocab_size=512,
                max_position_embeddings=128, torch_dtype="float32",
                num_nextn_predict_layers=1)


def test_the_numpy_reference_agrees_with_the_jax_one(tmp_path):
    """``benchmark/arch/mhc_mla_moe.py`` writes a seeded checkpoint under
    the names the loader reads (the ``hc`` tensors float32 in the bf16
    layout), and its numpy reference (what decides a cell's ``correct``)
    gives the ``jax.numpy`` reference's log-softmax on the same tensors;
    the program, given the loader's reading of the same files, agrees too.
    The seeded coefficients make the mechanism work: ``H_res`` is neither
    the identity nor uniform, and the routing margins stand."""
    arch = _bench_arch()
    cfg = _bench_cfg()
    written = arch.write_checkpoint(cfg, "bf16", 51, tmp_path)
    assert written["bytes"] == arch.checkpoint_bytes(cfg, "bf16")
    ck = arch.Checkpoint(tmp_path)
    names = json.loads((tmp_path / "model.safetensors.index.json")
                       .read_text())["weight_map"]
    assert not [n for n in names if n.startswith(("mtp.", "model.layers.3."))]
    tensors = {k: ck.f32(k) for k in names}
    assert ck.raw("model.layers.0.hc_attn_fn")[1] == "F32"
    assert tensors["model.layers.2.hc_ffn_fn"].shape == (24, 512)
    prompt = [int(t) for t in TOKENS[:30] % 512]
    chosen = [int(t) for t in TOKENS[30:38] % 512]
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
    assert min(got["routing_margin"]) > 1.0  # spreads: no rounding crosses
    layer = arch.Layer(ck)
    x = np.repeat(tensors["model.embed_tokens.weight"][prompt][:, None], 4, 1)
    _, _, res = arch.mixing(cfg, layer, "model.layers.1.", "ffn", x)
    off = res * (1 - np.eye(4))
    assert 0.15 < off.max(axis=(1, 2)).mean() < 0.6
    assert np.abs(res[0] - res[1]).max() > 0.02
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-5)
    # the loader reads what the writer wrote, and the program agrees too
    loaded = load_llama_params(tmp_path, cfg["num_hidden_layers"],
                               dtype="float32")
    served = LlamaConfig.from_hf_json(tmp_path / "config.json",
                                      dtype="float32", max_seq_len=128)
    assert served.family is families.LATENT and served.hc_mult == 4
    program, _ = _STEP(loaded, np.asarray([prompt + chosen[:-1]]),
                       init_cache(served, 1, 128), 0, served)
    np.testing.assert_allclose(np.asarray(program[0]), logits[-1],
                               atol=2e-4, rtol=0)


def test_the_benchmark_refuses_a_program_without_the_family(tmp_path):
    arch = _bench_arch()
    arch.require_family(ROOT)
    models = tmp_path / "cake_tpu" / "models"
    models.mkdir(parents=True)
    (models / "families.py").write_text(
        'model_types=("deepseek_v3", "axk1")')
    with pytest.raises(RuntimeError, match="declares model_type 'xing4_0'"):
        arch.require_family(tmp_path)


def test_the_benchmarks_byte_counts_are_the_arithmetic():
    """``arch/mhc_mla_moe.py`` at the cell's configuration: ISSUE 51's
    11.08 GB of weights; a step's 32 rows hit 0.87 of a layer's 64
    experts; the wide stream's passes and the ``hc`` tensors are counted
    in; a mix moves ``(2 n + 1) C`` values a row."""
    arch = _bench_arch()
    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / "xing4-29b-cut.json").read_text())
    held = arch.weight_bytes(cfg, "bf16")
    params = 6 * 744_989_046 + 128_196_918 + 2 * 469_762_048 + 3584
    hc = 7 * 2 * (14336 * 24 + 24 + 3)
    assert held == 2 * params + 2 * hc
    assert held / 1e9 == pytest.approx(11.08, abs=0.01)
    assert arch.checkpoint_bytes(cfg, "bf16") == held
    assert arch.resid_token_bytes(cfg) == 28672
    hit = arch.mla_moe.held_experts_hit(cfg, 32)
    assert hit / 64 == pytest.approx(0.873, abs=0.002)
    step = arch.decode_step_bytes(cfg, "bf16", 32, 400, "bf16")
    experts = 6 * 3 * 3584 * 1024 * 2
    assert step == pytest.approx(
        held - (64 - hit) * experts - (131072 - 32) * 3584 * 2
        + 32 * 400 * 7 * 1152 + 32 * 14 * 18 * 3584 * 2, rel=1e-9)
    assert arch.stream_bytes(cfg, 512) == 512 * 14 * 18 * 3584 * 2
    assert arch.mhc_mix_bytes(cfg, 512) == 512 * 9 * 3584 * 2
