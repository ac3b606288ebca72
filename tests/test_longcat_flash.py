"""LongCat-Flash's shortcut-connected double layer against the plain
reference (``tests/longcat_flash_kit.py`` has the family's account): the
logits of a prompt and of decoding through the cache, a bucket's padding,
the blocked admission against the unblocked one, the controls, the expert
block's three forms under zero-compute outputs, the shares of an ``ep``
deployment, the router's fourth scoring form, and the configuration
(refusals, ``config.json``, the catalog's row).
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import families, llama
from cake_tpu.models.config import (LlamaConfig, longcat_flash_ep32,
                                    tiny_longcat_flash, tiny_mla_moe)
from cake_tpu.ops import mla, moe
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.testing import reference_longcat_flash as ref

from longcat_flash_kit import ROOT as ROOT_DIR
from longcat_flash_kit import (  # noqa: F401
    CFG, TIGHT, TOKENS, WIDE, _STEP, _decode_all, params, tensors, want,
)


# -- (a) the program is the reference ---------------------------------------------

@pytest.mark.parametrize("n", [1, 5, 40], ids=["one", "few", "all"])
def test_a_prompts_logits_are_the_references(params, want, n):
    """A prompt of ``n`` tokens from position 0 in one chunk: the logits at
    its last position are the reference's (the two Folds applied once: the
    tensors the reference reads are the loader's inverse of the program's)."""
    logits, _ = _STEP(params, TOKENS[None, :n], init_cache(CFG, 1, 64), 0)
    np.testing.assert_allclose(np.asarray(logits[0]), want[n - 1],
                               atol=TIGHT, rtol=0)


@pytest.mark.parametrize("prefill", [1, 17], ids=["steps", "chunk-then-steps"])
def test_decode_through_the_cache_is_the_reference(params, want, prefill):
    """A prefill, then a step a token: every step writes and reads BOTH
    planes of every layer (plane ``2 l + j``), and gives the reference's
    logits."""
    got, cache = _decode_all(params, CFG, TOKENS, prefill)
    np.testing.assert_allclose(got, want[prefill - 1:], atol=TIGHT, rtol=0)
    # two planes a layer, written up to the frontier and nowhere past it
    assert cache.k.shape == (6, 1, 1, 64, CFG.kv_lora_rank)
    assert cache.v.shape == (6, 1, 1, 64, CFG.qk_rope_head_dim)
    held = np.asarray(jnp.abs(cache.k).sum((1, 2, 4)) > 0)  # [planes, S]
    assert held[:, :len(TOKENS)].all() and not held[:, len(TOKENS):].any()


def test_a_buckets_padding_changes_no_true_row(params, want):
    """A bucketed admission: 21 true tokens in a 32-row chunk told its
    true length (``valid``), against the unpadded chunk: the same logits at
    the last true row, and the cached rows of the true tokens the same (a
    padding row's routed result is zero and its identity part nobody's)."""
    n, bucket = 21, 32
    tokens = np.concatenate([TOKENS[:n], np.zeros(bucket - n, np.int32)])

    def run(toks, true):
        from cake_tpu.ops.rope import rope_tables_for

        cache = init_cache(CFG, 1, 64)
        cos, sin = rope_tables_for(CFG, 64)
        x = llama.embed_tokens(params, jnp.asarray(toks[None]), CFG)
        valid, expert_valid = llama.true_rows(
            CFG, (1, len(toks)), jnp.asarray([true - 1]))
        assert valid is None and expert_valid is not None
        x, cache = llama.forward_layers(
            params["layers"], x, cache, cos, sin, 0, CFG, valid=valid,
            expert_valid=expert_valid)
        return llama.head_norm(params, x, CFG)[0, true - 1], cache

    padded, cache_p = jax.jit(lambda: run(tokens, n))()
    plain, cache_u = jax.jit(lambda: run(TOKENS[:n], n))()
    np.testing.assert_allclose(np.asarray(padded), np.asarray(plain),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(cache_p.k[:, :, :, :n]),
                               np.asarray(cache_u.k[:, :, :, :n]),
                               atol=1e-5, rtol=0)


# -- (b) the blocked admission ------------------------------------------------------

@pytest.fixture
def blocked(monkeypatch):
    """The blocked admission from 16 rows on, in strips of 8 query rows
    (several strips a chunk)."""
    monkeypatch.setattr(mla, "LATENT_ADMIT_BLOCK_MIN_T", 16)
    monkeypatch.setattr(mla, "ADMIT_STRIP", 8)
    assert mla.latent_admit_choice(32, 24) == "strip"
    assert mla.latent_admit_choice(8, 24) == "whole"


@pytest.mark.parametrize("history", [False, True],
                         ids=["first-chunk", "with-history"])
def test_the_blocked_admission_is_the_unblocked_one(params, want, blocked,
                                                    history):
    """A 32-row chunk blocked by query rows (no ``[B, H, T, T]`` array)
    gives the logits the chunk in one piece gives, alone and with 8 rows
    of history behind it in the cache (the absorbed sweep of the history a
    strip at a time, merged into the strip's own softmax)."""
    cache = init_cache(CFG, 1, 64)
    at = 0
    if history:
        _, cache = _STEP(params, TOKENS[None, :8], cache, 0)
        at = 8
    logits, _ = _STEP(params, TOKENS[None, at:at + 32], cache,
                      jnp.asarray([at], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[0]), want[at + 31],
                               atol=TIGHT, rtol=0)


def test_the_blocked_first_chunk_by_the_interpreted_kernel(params, want,
                                                           monkeypatch):
    """Where kernels run, a blocked first chunk's own tokens go through the
    flash prefill kernel over the expanded keys, zero-padded to whole lane
    tiles under the TRUE width's scale (interpreted here)."""
    monkeypatch.setenv("CAKE_PALLAS", "1")
    monkeypatch.setattr(mla, "LATENT_ADMIT_BLOCK_MIN_T", 16)
    assert mla.latent_admit_choice(32, 24) == "flash"
    logits, _ = _STEP(params, TOKENS[None, :32], init_cache(CFG, 1, 64), 0)
    np.testing.assert_allclose(np.asarray(logits[0]), want[31], atol=TIGHT,
                               rtol=0)


def test_the_plain_latent_family_blocks_at_the_same_floor():
    """The choice is a function of the chunk's rows alone: under the floor
    the form every plain-latent cell compiles today, from it on blocked."""
    floor = mla.LATENT_ADMIT_BLOCK_MIN_T
    assert mla.latent_admit_choice(512, 192) == "whole"
    assert mla.latent_admit_choice(floor - 1, 192) == "whole"
    assert mla.latent_admit_choice(floor, 192) != "whole"
    assert mla._strip_rows(1, 64, 8192, 8192) == 128
    assert mla._strip_rows(4, 64, 8192, 8192) == 32


# -- (c) the controls -----------------------------------------------------------------

def _fp8(tensors):
    """Every linear rounded through float8_e4m3 (norms, the router and its
    bias, the embedding as they are)."""
    keep = ("layernorm", "norm.weight", "router", "embed_tokens")
    return {k: v if any(s in k for s in keep) else np.asarray(
        jnp.asarray(v, jnp.float8_e4m3fn).astype(jnp.float32))
        for k, v in tensors.items()}


CONTROLS = {
    "shortcut-early": dict(control=dict(early=True)),
    "identity-dropped": dict(control=dict(identity=False)),
    "shares-renormalised": dict(cfg=dict(norm_topk_prob=True)),
    "mla-factors-left-out": dict(cfg=dict(mla_scale_q_lora=False,
                                          mla_scale_kv_lora=False)),
    "float8-linears": dict(tensors=_fp8),
}


@pytest.mark.parametrize("name", list(CONTROLS))
def test_every_control_fails_by_a_wide_factor(params, tensors, name):
    """The reference with one thing misplaced, left out or rounded (the
    expert block's result added where it is computed; the zero-compute
    outputs' part; the chosen shares renormalised; the two MLA factors;
    every linear through float8_e4m3) is NOT what the program computes:
    some logit of the last position moves by more than a hundred times the
    tolerance of the comparison that passes."""
    how = CONTROLS[name]
    logits, _ = _STEP(params, TOKENS[None], init_cache(CFG, 1, 64), 0)
    other = np.asarray(ref.logits(
        {**CFG.to_hf_dict(), **how.get("cfg", {})},
        how.get("tensors", lambda t: t)(tensors), TOKENS,
        **how.get("control", {})))[-1]
    assert np.abs(np.asarray(logits[0]) - other).max() > TIGHT * WIDE


# -- (d) the router and the expert block ---------------------------------------------

def test_the_fourth_scoring_form_is_the_references():
    """Softmax over ALL outputs, the choice on share + bias (a tie to the
    lower id), the chosen shares times the scale and NOT renormalised."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(9, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 24)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=24) * 0.05, jnp.float32)
    routing = moe.GroupRouting(1, 1, False, 6.0, bias, scoring="softmax")
    _, weights, idx = moe.router_topk(x, w, 4, routing)
    want_idx, want_w = ref.route(
        {"moe_topk": 4, "routed_scaling_factor": 6.0}, x @ w, bias)
    assert np.asarray(idx).tolist() == np.asarray(want_idx).tolist()
    np.testing.assert_allclose(weights, want_w, atol=1e-6, rtol=0)
    assert float(weights.sum(-1).max()) < 6.0  # no renormalisation
    tie = jnp.zeros((1, 24))  # every share equal: the lowest ids win
    _, _, idx = moe.router_topk(tie, jnp.zeros((24, 24)), 4, routing._replace(
        bias=None))
    assert np.asarray(idx).tolist() == [[0, 1, 2, 3]]


def _expert_block(params, h, form, monkeypatch, bias):
    """Layer 1's expert block of ``h [B, T, H]`` in one ``form``."""
    layer = jax.tree.map(lambda a: a[1], params["layers"]["moe"])
    monkeypatch.setattr(moe, "expert_form", lambda *a, **k: form)
    if form == "sorted":
        monkeypatch.setenv("CAKE_PALLAS", "1")
    routing = moe.GroupRouting(1, 1, False, 6.0, bias, scoring="softmax")
    return moe.moe_swiglu(
        h, layer["router"], layer["w_gate"], layer["w_up"], layer["w_down"],
        top_k=4, routing=routing, held=(0, 16), count_local=True,
        zero_experts=8)


@pytest.mark.parametrize("case", ["none", "some", "all"])
def test_the_three_forms_agree_under_zero_compute_outputs(params, case,
                                                          monkeypatch):
    """``gather``, ``dense`` and ``sorted`` give one result and one count
    when none, some and all of a row's choices are identities (a bias that
    lifts the experts, nothing, the zero-compute outputs): the sorted form
    sorts such a pair to the tail, the dense form gives it a zero weight,
    the gather form never indexes a stack by it; the identity part is
    added once."""
    lift = {"none": np.r_[np.ones(16), np.zeros(8)] * 2.0,
            "some": np.zeros(24), "all": np.r_[np.zeros(16), np.ones(8)] * 2.0}
    bias = jnp.asarray(lift[case], jnp.float32)
    h = jnp.asarray(np.random.default_rng(5).normal(size=(2, 1, 64)),
                    jnp.float32)
    outs = {}
    for form in ("gather", "dense", "sorted"):
        with monkeypatch.context() as patch:
            outs[form] = _expert_block(params, h, form, patch, bias)
    zero = np.asarray(outs["dense"][1].zero)
    assert {"none": (zero == 0).all(), "all": (zero == 4).all(),
            "some": 0 < zero.sum() < 8}[case], zero
    for form in ("gather", "sorted"):
        np.testing.assert_allclose(outs[form][0], outs["dense"][0],
                                   atol=1e-5, rtol=0)
        assert np.asarray(outs[form][1].zero).tolist() == zero.tolist()
        assert (np.asarray(outs[form][1].pairs)
                == np.asarray(outs["dense"][1].pairs)).all()
    # pairs on experts and on zero-compute outputs are all of a row's
    assert (np.asarray(outs["dense"][1].pairs) + zero == 4).all()
    if case == "all":  # nothing but the identity part: z h
        z = 6.0 * np.asarray(jax.nn.softmax(
            h[:, 0] @ jax.tree.map(lambda a: a[1], params["layers"]["moe"])[
                "router"], axis=-1))[:, 16:]
        top = np.sort(z + 2.0, axis=-1)[:, -4:] - 2.0
        np.testing.assert_allclose(
            outs["dense"][0][:, 0], top.sum(-1, keepdims=True) * h[:, 0],
            atol=1e-5, rtol=0)


def test_the_form_reckons_with_every_output_the_router_scores():
    """32 rows x 12 of 768 scored hit 0.39 of the held 16: the sorted form,
    as a decode step of the cell takes; a handful of pairs with every
    expert here and the rest of the outputs zero-compute gather."""
    assert round(moe.hit_share(32, 12, 768), 2) == 0.39
    assert moe.expert_form(32, 12, False, 16, 768, 256) in ("sorted", "dense")
    assert moe.expert_form(2, 4, False, 16, 24, 8) == "gather"
    assert moe.expert_form(2, 4, False, 4, 24, 8) != "gather"


def test_four_shares_add_up_to_the_uncut_layer(tensors):
    """Over ``ep`` = 4 shares of a 16-expert + 8-identity layer: the shares'
    routed parts, with the identity part (every rank's alike) counted ONCE,
    add up to what the uncut reference gives for the whole layer; and the
    program, told a share, gives that share's part and the identity part."""
    cfg = CFG.to_hf_dict()
    h = jnp.asarray(np.random.default_rng(9).normal(size=(24, 64)),
                    jnp.float32)
    p = "model.layers.1."
    with jax.default_matmul_precision("highest"):
        uncut = ref.expert_layer(cfg, tensors, p, h)
        parts = [ref.expert_layer(cfg, tensors, p, h, share=(4 * r, 4),
                                  identity=False) for r in range(4)]
        identity = ref.expert_layer(cfg, tensors, p, h, share=(0, 0))
    np.testing.assert_allclose(sum(parts) + identity, uncut, atol=TIGHT,
                               rtol=0)
    assert float(jnp.abs(identity).max()) > 0.01  # the part is something
    # the program, told share 2 of 4 (experts 8-11 of the same tensors)
    share = dataclasses.replace(CFG, n_routed_experts=4, router_experts=16,
                                first_expert=8)
    full = llama.init_params(CFG, jax.random.PRNGKey(0))["layers"]["moe"]
    layer = {k: v[1] for k, v in full.items()}
    held = {k: layer[k][8:12] for k in ("w_gate", "w_up", "w_down")}
    got = llama._routed({**layer, **held}, h[None], share, None, None, False,
                        None, None)[0]
    from cake_tpu.utils.weights import latent_hf_tensors

    plain = latent_hf_tensors(
        llama.init_params(CFG, jax.random.PRNGKey(0)), CFG)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(cfg, plain, p, h, share=(8, 4))
    np.testing.assert_allclose(got, want, atol=TIGHT, rtol=0)


# -- (e) the configuration ---------------------------------------------------------------

def _catalog_row() -> dict:
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        lines = open(path).read().splitlines()
    except OSError:
        pytest.skip("no catalog beside the guides here")
    return next(row for row in map(json.loads, lines)
                if row["name"] == "LongCat-Flash-Omni")


def test_the_catalogs_file_reads_as_the_preset_and_round_trips():
    """The catalog's ``config`` of LongCat-Flash-Omni (the language
    model's; the file's own ``model_type`` is ASSUMED to name this decoder)
    reads as ``longcat_flash_ep32()`` with all 512 experts; what
    ``to_hf_dict`` writes carries LongCat's own keys and reads back as the
    same configuration."""
    row = _catalog_row()
    file = {**row["config"], "model_type": "longcat_flash"}
    served = LlamaConfig.from_hf_dict(file, max_seq_len=row["context_length"])
    assert served == longcat_flash_ep32(
        n_routed_experts=512, bos_token_id=128000, eos_token_id=128001)
    assert served.family is families.SHORTCUT
    assert served.router_outputs == 768 and served.router_experts == 512
    assert served.cache_plan == {"rows": (56, 1, 512, 64)}
    assert served.cache_token_bytes == 56 * 576 * 2
    assert served.attn_scale == 192 ** -0.5
    written = served.to_hf_dict()
    for key, value in row["config"].items():
        if key not in ("attention_bias", "max_position_embeddings"):
            assert written[key] == value, key
    assert not {"num_hidden_layers", "intermediate_size",
                "num_experts_per_tok", "norm_topk_prob"} & set(written)
    assert LlamaConfig.from_hf_dict(
        written, max_seq_len=row["context_length"]) == served
    # a plain latent model writes none of this family's keys
    assert not {"zero_expert_num", "mla_scale_q_lora", "moe_topk",
                "num_layers"} & set(tiny_mla_moe().to_hf_dict())


def test_a_share_round_trips_through_config_json():
    share = tiny_longcat_flash(n_routed_experts=4, router_experts=16,
                               first_expert=8)
    written = share.to_hf_dict()
    assert written["expert_share"] == {"n_routed_experts": 16, "ep": 4,
                                       "rank": 2}
    assert written["zero_expert_num"] == 8
    assert LlamaConfig.from_hf_dict(written, max_seq_len=128,
                                    dtype="float32") == share
    assert share.router_outputs == 24


REFUSALS = {
    "zero_expert_type": (dict(zero_expert_type="copy"),
                         "only 'identity'"),
    "held-past-the-real": (dict(n_routed_experts=4, router_experts=16,
                                first_expert=14), "held of 16"),
    "norm_topk_prob": (dict(norm_topk_prob=True), "renormalised"),
    "groups": (dict(n_group=2), "one group"),
    "no-bias": (dict(router_bias=False), "chosen on share"),
    "shared-expert": (dict(n_shared_experts=1), "no shared expert"),
    "no-q-latent": (dict(q_lora_rank=None), "q_lora_rank"),
    "an-indexer": (dict(index_topk=4, index_n_heads=2, index_head_dim=8),
                   "latent-attention family alone"),
    "a-wide-residual": (dict(hc_mult=2), "latent-attention family alone"),
}


@pytest.mark.parametrize("name", list(REFUSALS))
def test_the_record_refuses_what_nothing_computes(name):
    overrides, says = REFUSALS[name]
    with pytest.raises(ValueError, match=says):
        tiny_longcat_flash(**overrides)


def test_a_file_is_refused_by_its_own_keys():
    """``attention_method`` other than MLA, a rope scaling: refused where
    the file is read. An ``mla_scale_*`` key that is false folds no factor:
    computed, not refused."""
    file = CFG.to_hf_dict()
    with pytest.raises(ValueError, match="attention_method = 'MHA'"):
        LlamaConfig.from_hf_dict({**file, "attention_method": "MHA"})
    with pytest.raises(ValueError, match="rope_scaling"):
        LlamaConfig.from_hf_dict({**file, "rope_scaling": {"factor": 2}})
    plain = LlamaConfig.from_hf_dict({**file, "mla_scale_kv_lora": False})
    fold = families.SHORTCUT.tensor_names["s0_kv_norm"][2]
    assert float(fold.load(plain, np.float32(1.0))) == 1.0
    assert float(fold.load(CFG, np.float32(1.0))) == 2.0  # (64 / 16)^0.5
    q_fold = families.SHORTCUT.tensor_names["s1_q_norm"][2]
    assert float(q_fold.save(CFG, q_fold.load(CFG, np.float32(3.0)))) == (
        pytest.approx(3.0))


@pytest.mark.parametrize("key", ["zero_expert_num", "mla_scale_q_lora",
                                 "mla_scale_kv_lora"])
def test_another_family_refuses_this_familys_keys(key):
    with pytest.raises(ValueError, match="'longcat_flash' alone"):
        tiny_mla_moe(**{key: 2 if key == "zero_expert_num" else True})


def test_the_plan_is_one_scanned_segment_of_two_planes_a_layer():
    (run,) = llama.layer_plan(CFG)
    (seg,) = run.segments
    assert (seg.mixer, seg.ffn, seg.count, run.repeats) == (
        "mla2", "moe", 3, 1)
    shapes = llama.segment_shapes(CFG, seg)
    assert shapes["router"](CFG) == (64, 24) and shapes["b_router"](CFG) == (
        24,)
    assert shapes["s1_wkv_b"](CFG) == shapes["s0_wkv_b"](CFG) == (16, 4 * 32)
    assert shapes["s0_w_gate"](CFG) == (64, 128)
    assert shapes["w_gate"](CFG) == (16, 64, 32)
    assert CFG.cache_plan == {"rows": (6, 1, 16, 8)}


# -- (f) the benchmark's copy of the reference ---------------------------------------------

def _bench_arch():
    """``benchmark/arch/scmoe_mla.py``, loaded as the harness loads it (its
    directory's shared modules on the path)."""
    import importlib.util
    import sys

    root = ROOT_DIR / "benchmark"
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    spec = importlib.util.spec_from_file_location(
        "bench_arch_scmoe_mla_under_test", root / "arch" / "scmoe_mla.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_numpy_reference_agrees_with_the_jax_one(tmp_path):
    """``benchmark/arch/scmoe_mla.py`` writes a seeded checkpoint of a SHARE
    (4 of 16 experts beside 8 identities) under the names the loader
    reads, and its numpy reference (what decides a cell's ``correct``:
    blocked by query rows, no folding) gives the ``jax.numpy`` reference's
    log-softmax on the same tensors; the program, given the loader's
    reading of the same files (the two Folds applied), agrees too, and the
    routing margin holds through the layers."""
    from cake_tpu.utils.weights import load_llama_params

    arch = _bench_arch()
    cfg = dict(CFG.to_hf_dict(), hidden_size=128, vocab_size=512,
               max_position_embeddings=128, torch_dtype="float32",
               n_routed_experts=4,
               expert_share={"n_routed_experts": 16, "ep": 4, "rank": 1})
    arch.REFERENCE_BLOCK = 16  # several blocks of query rows
    written = arch.write_checkpoint(cfg, "bf16", 64, tmp_path)
    assert written["bytes"] == arch.checkpoint_bytes(cfg, "bf16")
    ck = arch.Checkpoint(tmp_path)
    names = json.loads((tmp_path / "model.safetensors.index.json")
                       .read_text())["weight_map"]
    assert "model.layers.2.mlp.experts.7.down_proj.weight" in names
    assert "model.layers.2.mlp.experts.8.down_proj.weight" not in names
    tensors = {k: ck.f32(k) for k in names}
    prompt = [int(t) for t in TOKENS % 512]
    chosen = [int(t) for t in TOKENS[30:38] % 512]
    got = arch.chosen_logprobs(cfg, tmp_path, [(prompt, chosen)])[0]
    logits = np.asarray(ref.logits(cfg, tensors, prompt + chosen[:-1]),
                        np.float64)[len(prompt) - 1:]
    top = logits.max(-1, keepdims=True)
    logp = logits - top - np.log(np.exp(logits - top).sum(-1, keepdims=True))
    assert got["best"] == [int(b) for b in logp.argmax(-1)]
    np.testing.assert_allclose(
        got["logprob"], logp[np.arange(8), chosen], atol=2e-4, rtol=0)
    assert min(got["routing_margin"]) > 1.0  # spreads: no rounding crosses
    loaded = load_llama_params(tmp_path, cfg["num_layers"], dtype="float32")
    served = LlamaConfig.from_hf_json(tmp_path / "config.json",
                                      dtype="float32", max_seq_len=128)
    assert served.family is families.SHORTCUT
    assert (served.first_expert, served.n_routed_experts,
            served.router_outputs) == (4, 4, 24)
    program, _ = _STEP(loaded, np.asarray([prompt + chosen[:-1]]),
                       init_cache(served, 1, 128), 0, served)
    np.testing.assert_allclose(np.asarray(program[0]), logits[-1],
                               atol=2e-4, rtol=0)
    # the marks: 4 a token id, over all 24 outputs, some of them identities
    marks = arch.routing_channels(np.arange(512), cfg, 64)
    assert marks.shape == (512, 4) and marks.max() == 23
    assert all(len(set(row)) == 4 for row in marks[:64].tolist())
    assert 0.25 < (marks >= 16).mean() < 0.42  # a third on average


def test_the_benchmark_refuses_a_program_without_the_family(tmp_path):
    """A checkout whose program names no ``longcat_flash`` (the parent
    commit) fails at once, before a checkpoint is written."""
    arch = _bench_arch()
    models = tmp_path / "cake_tpu" / "models"
    models.mkdir(parents=True)
    (models / "families.py").write_text('model_types=("deepseek_v3",)\n')
    with pytest.raises(RuntimeError, match="shortcut-connected double layer"):
        arch.require_family(tmp_path)
    arch.require_family(ROOT_DIR)


def test_the_benchmarks_counts_are_the_arithmetic():
    """The cell's sizes as the issue reckons them: 9.64 GiB of weights,
    9,216 B of cache a token, 0.39 of 16 held experts hit by a step, the
    decode kernel's least bytes and the admission's true operations."""
    arch = _bench_arch()
    cfg = json.loads((ROOT_DIR / "benchmark" / "configs"
                      / "longcat-flash-ep32-cut.json").read_text())
    assert arch.router_outputs(cfg) == 768 and arch.real_experts(cfg) == 512
    assert list(arch.held_experts(cfg)) == list(range(16))
    assert arch.expert_layers(cfg) == 4 and arch.cache_planes(cfg) == 8
    assert arch.cache_token_bytes(cfg) == 9216
    assert round(arch.weight_bytes(cfg, "bf16") / 2**30, 1) == 9.6
    assert round(arch.held_experts_hit(cfg, 32) / 16, 2) == 0.4
    # a step's least bytes grow with the rows and with the context
    assert arch.latent_decode_bytes(cfg, 1000) == 1000 * 576 * 2
    assert arch.kv_bytes(cfg, 2200, 32) == 32 * 2200 * 8 * 1152
    # 64 heads x (192 + 128) x 2 a causal pair, the TRUE widths
    assert arch.latent_prefill_flops(cfg, 10) == 10 * 2 * 64 * 320
    assert arch.prefill_pairs(8192) == 8192 * 8193 // 2
    assert arch.latent_prefill_pairs_handed(
        "latent_prefill.3 bf16[2,64,1024,128]") == 2 * 1024 * 1025 // 2
    assert arch.latent_prefill_pairs_handed("fusion.3 f32[32]") == 0
    ops = arch.latent_trace_ops(cfg)
    import re
    assert re.match(ops["decode"], "latent_decode.3 f32[32,64,512]")
    assert re.match(ops["prefill"], "latent_prefill bf16[1,64,8192,128]")
    assert not re.match(ops["decode"], "latent_decoder")



def test_the_three_readers_on_a_made_up_trace():
    """``benchmark/layer_metrics/``'s three new readers: the zero pairs'
    share is a ratio of two counters; the decode kernel's share a MEAN call
    (a capture closes long after its span) times the calls the trace
    holds; the admission kernel's share reads each traced call's pairs off
    its own shape and scales them by the counters' share of true pairs, so
    a trace that holds short buckets alone is not reckoned at the window's
    mean; nothing (and no raise) without a trace or against a program
    without the counters."""
    _bench_arch()  # puts benchmark/ on the path
    import run

    cfg = json.loads((ROOT_DIR / "benchmark" / "configs"
                      / "longcat-flash-ep32-cut.json").read_text())
    arch = run.load_arch(cfg["bench"]["arch"])
    trace = {"devices": [{"ops": [
        ["latent_decode.28 f32[32,64,128]", 0.150, 1000],
        ["latent_prefill.2 bf16[1,64,1024,128]", 0.004, 8],
        ["latent_prefill.3 bf16[1,64,8192,128]", 0.120, 8],
        ["fusion.9 bf16[32,12288]", 9.0, 1000]]}]}

    def count(v):
        return {"type": "counter", "value": v}

    calls = 40000  # forty times what the trace holds
    after = {"moe.zero_pairs": count(1000), "moe.routed_pairs": count(3000),
             "attn.latent_decode_calls": count(calls),
             "attn.latent_rows_live": count(calls * 32 * 2200),
             "attn.latent_admit_calls": count(800),
             "attn.latent_admit_pairs": count(6 * 10**9),
             "attn.latent_admit_pairs_handed": count(10**10)}
    ctx = {"before": {"status": {"metrics": {}}},
           "after": {"status": {"metrics": after}}, "trace": trace,
           "cfg": cfg, "arch": arch,
           "peaks": {"hbm_gb_per_s": 819.0, "bf16_tflops": 197.0}}
    names = ("moe.zero_pairs_share", "kernel.latent_decode_hbm_share",
             "kernel.latent_prefill_mxu_share")
    got = {name: run.load_reader(name)(ctx) for name in names}
    assert got["moe.zero_pairs_share"] == pytest.approx(100 / 3)
    assert got["kernel.latent_decode_hbm_share"] == pytest.approx(
        100 * 1000 * 32 * 2200 * 1152 / 819e9 / 0.150)
    handed = 8 * (1024 * 1025 // 2 + 8192 * 8193 // 2)
    assert got["kernel.latent_prefill_mxu_share"] == pytest.approx(
        100 * 0.6 * handed * 2 * 64 * 320 / 197e12 / 0.124)
    assert all(0 < v < 100 for v in got.values())
    for lacking in (dict(ctx, trace=None),
                    dict(ctx, after={"status": {"metrics": {}}})):
        assert [run.load_reader(n)(lacking) for n in names[1:]] == [None] * 2
    assert run.load_reader(names[0])(
        dict(ctx, after={"status": {"metrics": {}}})) is None
