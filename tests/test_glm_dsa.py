"""Latent attention under a learned sparse attention (``model_type``
``glm_moe_dsa``, GLM-5's keys): the program against the plain reference
(``cake_tpu/testing/reference_glm_dsa.py``) on seeded weights, tiny sizes,
CPU, float32, on LOGITS (``tests/glm_dsa_kit.py`` has the tolerances and
their reasons).

- the whole forward pass, then a prefill and decode through the cache, at
  contexts under, at and several times ``index_topk``;
- the chosen sets equal the reference's, ties by the stated rule;
- every control of the reference fails the same comparison by a wide
  factor;
- a stream under ``index_topk`` rows gives what the plain latent path
  gives;
- the kernels, interpreted, against the ``jnp`` forms;
- THE SHARE TEST: sixteen ``ep`` ranks' routed parts + the shared expert
  once = the uncut layer;
- the configuration (the catalog's keys, the round trip), the benchmark's
  copy of the reference and its counts.

The engine, the loaders and the refusals are
``tests/test_glm_dsa_engine.py``.
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
from cake_tpu.models.config import LlamaConfig, glm5_ep16
from cake_tpu.ops import dsa, moe
from cake_tpu.ops import pallas as pk
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.ops.norms import rms_norm
from cake_tpu.testing import reference_glm_dsa as ref
from cake_tpu.utils.weights import load_llama_params

from glm_dsa_kit import (  # noqa: F401
    CFG, ROOT, TIGHT, TOKENS, TOPK, WIDE, _STEP, _decode_all, form, params,
    tensors, want,
)


# -- (a) logits: the whole pass, then through the cache ------------------------------

@pytest.mark.parametrize("n", [5, TOPK, TOPK + 1, 40],
                         ids=["under", "at", "one-past", "five-times"])
def test_a_prompts_logits_are_the_references(params, want, n):
    """One chunk from position 0 (the admission's form: each row under its
    own mask) gives the reference's logits at its last position."""
    logits, _ = _STEP(params, TOKENS[None, :n], init_cache(CFG, 1, 64), 0,
                      CFG)
    np.testing.assert_allclose(np.asarray(logits[0]), want[n - 1],
                               atol=TIGHT, rtol=0)


@pytest.mark.parametrize("prefill", [3, TOPK, 24],
                         ids=["under", "at", "three-times"])
def test_decode_through_the_cache_is_the_reference(params, want, prefill,
                                                   form):
    """A prefill, then a step a token to five times ``index_topk``: every
    step scores the cached index keys, chooses and attends (the decode's
    two forms: a threshold and the sweep of the buffer under the mask, or
    a sort, the gather and the copies), and gives the reference's logits;
    a stream that starts under ``index_topk`` rows crosses it on the
    way."""
    got, cache = _decode_all(params, CFG, TOKENS, prefill)
    np.testing.assert_allclose(got, want[prefill - 1:], atol=TIGHT, rtol=0)
    # a third kind of row: one index key a token a layer, written beside
    # the latent row ([c | k_pe] in ONE row of the first buffer, the second
    # empty) and nowhere past the frontier
    assert cache.index.shape == (3, 1, 1, 64, CFG.index_head_dim)
    assert cache.k.shape == (3, 1, 1, 64, 128) and cache.v.size == 0
    held = np.asarray(jnp.abs(cache.index).sum((0, 1, 2, 4)) > 0)
    assert held[:len(TOKENS)].all() and not held[len(TOKENS):].any()


# -- (b) the chosen sets -------------------------------------------------------------

def _layer0(params, tokens):
    """Layer 0's normed input, query latent and indexer parts of
    ``tokens``, by the program's own functions."""
    from cake_tpu.ops import quant
    from cake_tpu.ops.rope import rope_tables_for

    layer = jax.tree.map(lambda a: a[0], params["layers"]["dense"])
    x = rms_norm(llama.embed_tokens(params, jnp.asarray(tokens[None]), CFG),
                 layer["attn_norm"], CFG.rms_norm_eps)
    c_q = rms_norm(quant.dense(x, layer["wq_a"]), layer["q_norm"],
                   CFG.rms_norm_eps)
    cos, sin = rope_tables_for(CFG, 64)
    return dsa.index_projections(x, c_q, layer, cos, sin, 0, CFG)


def _decode_choice(scores, pos, k, form):
    """The rows (ascending) a decode step of ``form`` attends, a stream:
    what the sweep keeps, or what the gather's ``top_k`` names (a choice
    scored ``-inf`` is no row)."""
    if form == "sweep":
        kept = np.asarray(dsa.keep_chosen(scores, jnp.asarray(pos), k))
        return [np.flatnonzero(row > -np.inf).tolist() for row in kept]
    values, rows = (np.asarray(a) for a in dsa.choose(scores, k))
    return [sorted(r[v > -np.inf].tolist()) for v, r in zip(values, rows)]


def test_the_chosen_sets_are_the_references(params, tensors, form):
    """Layer 0 of the 40 tokens: the admission's mask row by row, and the
    decode's choice for the last row (by either form), are the
    reference's ``S_t``."""
    chosen: list = []
    ref.logits(CFG.to_hf_dict(), tensors, TOKENS, chosen=chosen)
    q_i, k_i, w = _layer0(params, TOKENS)
    mask = np.asarray(dsa.prefill_mask(q_i, w, k_i[:, 0], TOPK))[0]
    for t, rows in enumerate(chosen[0]):
        assert len(rows) == min(t + 1, TOPK)
        assert np.flatnonzero(mask[t]).tolist() == rows, t
    scores = dsa.index_scores(q_i[:, :, -1:], w[:, -1:], k_i[:, 0])[:, 0]
    assert _decode_choice(scores, [len(TOKENS) - 1], TOPK, form) == [
        chosen[0][-1]]


def test_a_tie_goes_to_the_lower_row(form):
    """Equal scores: the mask and the decode's choice (by either form)
    keep the LOWER rows, as the reference's stable sort does; rows of
    ``-inf`` (past a frontier, above the diagonal) are never chosen,
    however few are left."""
    scores = jnp.asarray([[1.0, 3.0, 1.0, 3.0, 1.0, 0.5, 1.0, -jnp.inf],
                          [2.0, -jnp.inf, -jnp.inf, -jnp.inf, -jnp.inf,
                           -jnp.inf, -jnp.inf, -jnp.inf]])
    mask = np.asarray(dsa.chosen_mask(scores, 4))
    assert np.flatnonzero(mask[0]).tolist() == [0, 1, 2, 3]
    assert np.flatnonzero(mask[1]).tolist() == [0]
    assert _decode_choice(scores, [6, 0], 4, form) == [[0, 1, 2, 3], [0]]
    values, rows = dsa.choose(scores, 4)
    assert np.asarray(rows)[0].tolist() == [1, 3, 0, 2]
    assert np.asarray(values)[1].tolist() == [2.0] + [-np.inf] * 3
    want_rows = ref.chosen_rows(scores[0], 6, 4)
    assert np.flatnonzero(np.asarray(want_rows)).tolist() == [0, 1, 2, 3]


# -- (c) the controls ----------------------------------------------------------------

@pytest.mark.parametrize("control", [
    dict(select=False), dict(index_rope=False), dict(k_norm=False),
    dict(topk=TOPK // 2)], ids=lambda c: next(iter(c)))
def test_every_control_fails_by_a_wide_factor(params, tensors, control):
    """The reference with one thing left out (the choice: every row
    attended; the indexer's rope; the key's LayerNorm; another count) is
    NOT what the program computes: some logit of the last position moves
    by more than a thousand times the tolerance of the comparison that
    passes."""
    logits, _ = _STEP(params, TOKENS[None], init_cache(CFG, 1, 64), 0, CFG)
    other = np.asarray(ref.logits(CFG.to_hf_dict(), tensors, TOKENS,
                                  **control))[-1]
    assert np.abs(np.asarray(logits[0]) - other).max() > TIGHT * WIDE


# -- (d) the tie to the shared code ----------------------------------------------------

def test_a_stream_under_topk_rows_is_the_plain_latent_path(params, form):
    """The same weights without the indexer, served as a plain latent
    model (``index_topk`` 0: ``ops/mla.py``'s own forms), give the sparse
    model's logits (by either form of its decode step) while a stream
    holds no more than ``index_topk`` rows, one chunk or step by step, and
    other logits from the next row on."""
    plain_cfg = dataclasses.replace(
        CFG, model_type="deepseek_v3", index_topk=0, index_n_heads=0,
        index_head_dim=0)
    assert "index" not in plain_cfg.cache_plan
    plain = dict(params, layers={
        stack: {k: v for k, v in layers.items() if not k.startswith("idx_")}
        for stack, layers in params["layers"].items()})
    got, _ = _decode_all(params, CFG, TOKENS[:TOPK + 4], 3)
    base, _ = _decode_all(plain, plain_cfg, TOKENS[:TOPK + 4], 3)
    np.testing.assert_allclose(got[:TOPK - 2], base[:TOPK - 2], atol=TIGHT,
                               rtol=0)
    assert np.abs(got[TOPK:] - base[TOPK:]).max() > TIGHT * WIDE


# -- (e) the kernels, interpreted ------------------------------------------------------

def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape).astype(dtype)


def _close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert (np.isinf(got) == np.isinf(want)).all()
    np.testing.assert_allclose(np.where(np.isinf(want), 0, got),
                               np.where(np.isinf(want), 0, want),
                               atol=tol, rtol=0)


def _index_case(dtype, tol):
    b, j, d, s, layers = 2, 4, 128, 1024, 2
    q_i, w = _rand(0, (b, j, 1, d), dtype), _rand(1, (b, 1, j))
    i_cache = _rand(2, (layers, b, 1, s, d), dtype)
    pos = jnp.asarray([5, 700])
    _close(pk.dsa_index(q_i[:, :, 0], w[:, 0], i_cache, pos, layer=1,
                        interpret=True),
           jnp.where(jnp.arange(s)[None] <= pos[:, None],
                     dsa.index_scores(q_i, w, i_cache[1, :, 0])[:, 0],
                     -jnp.inf), tol)


def _attend_gathered_case(dtype, tol):
    """The chosen rows' absorbed attention, some choices no row at all."""
    from cake_tpu.ops.mla import masked_sweep

    b, h, dc, dr, k = 2, 8, 128, 64, 1024
    q_c, q_pe = _rand(3, (b, h, 1, dc), dtype), _rand(4, (b, h, 1, dr), dtype)
    c, r = _rand(5, (b, k, dc), dtype), _rand(6, (b, k, dr), dtype)
    values = jnp.where(jnp.arange(k)[None] < jnp.asarray([[300], [k]]), 1.0,
                       -jnp.inf)
    m, p, o = masked_sweep(q_c, q_pe, c, r,
                           (values > -jnp.inf)[:, None, None], 0.1)
    m2, l2, o2 = pk.dsa_attend_gathered(
        q_c[:, :, 0], q_pe[:, :, 0], jnp.concatenate([c, r], -1), values,
        scale=0.1, interpret=True)
    _close(m2, m, tol)
    _close(o2 / l2, o / p.sum(-1, keepdims=True), tol)


def _prefill_select_case(dtype, tol):
    """An admission's choice: every row's mask, ties among them (rows
    64-127 hold the keys of rows 192-255, so a later query scores them
    alike)."""
    b, j, d, t = 2, 4, 128, 1024
    q_t, w_t = _rand(7, (b, j, t, d), dtype), _rand(8, (b, t, j))
    k_i = _rand(9, (b, t, d), dtype)
    k_i = k_i.at[:, 64:128].set(k_i[:, 192:256])
    for topk in (96, 2048):
        got = pk.dsa_prefill_select(q_t, w_t, k_i, topk, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(dsa.prefill_mask(q_t, w_t, k_i, topk)))
        assert (np.asarray(got).sum(-1)
                == np.minimum(np.arange(t) + 1, topk)).all()


def _prefill_attend_case(dtype, tol):
    """The flash sweep under each row's mask (a row may see ONE key)."""
    b, h, d, t = 2, 8, 128, 1024
    q, kk, v = (_rand(n, (b, h, t, d), dtype) for n in (10, 11, 12))
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    mask = dsa.chosen_mask(
        jnp.where(causal[None], _rand(13, (b, t, t)), -jnp.inf), 100
    ).astype(jnp.int8)
    assert int(mask.sum(-1).min()) == 1 and int(mask.sum(-1).max()) == 100
    _close(pk.dsa_prefill_attend(q, kk, v, mask, scale=d ** -0.5,
                                 interpret=True),
           dsa.prefill_attend(q, kk, v, mask, scale=d ** -0.5), tol)


def _select_case(dtype, tol):
    """The sweep's choice against ``chosen_mask``, cell for cell: nine
    streams over 4096 scores (two stretches of columns) rounded to
    quarters, so that dozens tie at every threshold (``room`` several),
    and one stream built so that ONE of three at the threshold fits
    (``room`` 1); frontiers under, at and past ``topk`` rows, inside a
    stretch and at the buffer's end; what lies past a frontier is FINITE
    here (stale scores) and never kept, nor is a live row of ``-inf``."""
    s, topk = 4096, 256
    scores = jnp.round(_rand(14, (9, s), dtype).astype(jnp.float32) * 4) / 4
    pos = jnp.asarray([0, 5, topk - 2, topk - 1, topk, 2100, s - 1, 999,
                       s - 1])
    scores = scores.at[5, 17].set(-jnp.inf)
    # stream 8: 200 rows of 1.0, then zeros of BOTH signs by turns: -0.0
    # ties with 0.0 (``==``), so the first 56 of them are kept, whatever
    # their sign
    zeros = jnp.where(jnp.arange(s) % 2 == 0, -0.0, 0.0)
    scores = scores.at[8].set(jnp.where(jnp.arange(s) < 200, 1.0, zeros))
    # stream 7: 255 rows above 1.0, then three AT it: rows 400, 600, 800
    one = jnp.where(jnp.arange(s) < 255, 2.0 + jnp.arange(s) % 7, 0.0)
    scores = scores.at[7].set(one.at[jnp.asarray([400, 600, 800])].set(1.0))
    live = jnp.arange(s)[None] <= pos[:, None]
    want = np.asarray(dsa.chosen_mask(jnp.where(live, scores, -jnp.inf),
                                      topk))
    got = np.asarray(pk.dsa_select(scores, pos, topk, interpret=True))
    np.testing.assert_array_equal(got > -np.inf, want)
    np.testing.assert_array_equal(got[want], np.asarray(scores)[want])
    assert want.sum(-1).tolist() == [1, 6, 255, 256, 256, 256, 256, 256, 256]
    assert want[7, 400] and not want[7, 600] and not want[5, 17]
    assert np.flatnonzero(want[8]).tolist() == list(range(256))
    # a threshold shared by dozens: the lower rows of them, no more
    theta = np.where(want[6], np.asarray(scores[6]), np.inf).min()
    ties = np.flatnonzero(np.asarray(scores[6]) == theta)
    kept = int(want[6, ties].sum())
    assert 1 < kept < len(ties) and want[6, ties[:kept]].all()
    # topk at or past the buffer: every live row (a tiny model's case)
    short = pk.dsa_select(scores[:, :128], jnp.minimum(pos, 127), 128,
                          interpret=True)
    np.testing.assert_array_equal(
        np.asarray(short) > -np.inf, np.asarray(
            (jnp.arange(128)[None] <= jnp.minimum(pos, 127)[:, None])
            & (scores[:, :128] > -jnp.inf)))


def _attend_swept_case(dtype, tol):
    """The sweep's attention over the carried buffer against
    ``masked_attend`` over the same rows: frontiers that end in different
    blocks (a stream of ONE row, one inside a block, one at the buffer's
    end), a stream whose first two blocks hold no kept row, the buffer
    stacked (``layer`` a traced value) and not."""
    b, h, dc, dr, s, layers = 4, 8, 128, 64, 2048, 2
    q_c, q_pe = _rand(15, (b, h, 1, dc), dtype), _rand(16, (b, h, 1, dr),
                                                      dtype)
    rows_all = _rand(17, (layers, b, 1, s, 256), dtype)
    pos = jnp.asarray([0, 700, s - 1, 1500])
    scores = _rand(18, (b, s)).at[3, :1024].set(-1e3)
    kept = dsa.keep_chosen(
        jnp.where(jnp.arange(s)[None] <= pos[:, None], scores, -jnp.inf),
        pos, 256)
    assert (np.asarray(kept > -jnp.inf).sum(-1) == [1, 256, 256, 256]).all()
    assert not np.asarray(kept[3, :1024] > -jnp.inf).any()
    for layer, buffer in ((1, rows_all), (None, rows_all[0])):
        view = buffer[layer, :, 0] if layer is not None else buffer[:, 0]
        m, l, o = dsa.masked_attend(q_c, q_pe, view, kept, 0.1)
        m2, l2, o2 = jax.jit(lambda layer: pk.dsa_attend(
            q_c[:, :, 0], q_pe[:, :, 0], buffer, kept, pos, scale=0.1,
            layer=layer, block_k=512, interpret=True))(layer)
        _close(m2, m, tol)
        _close(o2 / l2, o / l, tol)


@pytest.mark.parametrize("kernel", [
    "index", "attend-gathered", "prefill-select", "prefill-attend", "select",
    "attend-swept"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_the_kernels_are_the_jnp_forms(dtype, tol, kernel):
    """``ops/pallas/dsa.py``'s six kernels in interpret mode against the
    ``jnp`` forms of ``ops/dsa.py`` (same operands, float32 products: the
    tolerance is a sum's order; bfloat16 operands round the probabilities
    once more). A choice, a bisection on the scores' bits where the
    ``jnp`` form sorts, is the same mask cell for cell: an admission's
    (``dsa_prefill_select``) and a decode step's (``dsa_select``)."""
    {"index": _index_case, "attend-gathered": _attend_gathered_case,
     "prefill-select": _prefill_select_case,
     "prefill-attend": _prefill_attend_case, "select": _select_case,
     "attend-swept": _attend_swept_case}[kernel](dtype, tol)


def test_the_program_under_interpreted_kernels_is_the_reference(
        params, want, monkeypatch, form):
    """``CAKE_PALLAS=1`` on the CPU: the admission's strips and masked
    sweep and the decode's index scores, choice and attention (by either
    form) run through the interpreted kernels, and the logits are still
    the reference's."""
    monkeypatch.setenv("CAKE_PALLAS", "1")
    assert dsa.index_kernel_choice(64, CFG.index_head_dim) == "kernel"
    assert dsa.prefill_kernel_choice(24, 24, 24, 16) == "kernel"
    assert dsa.attend_kernel_choice(8, 16) == "kernel"
    assert dsa.select_kernel_choice(64) == "kernel"
    got, _ = _decode_all(params, CFG, TOKENS[:30], 24)
    np.testing.assert_allclose(got, want[23:30], atol=TIGHT, rtol=0)


# -- (f) the share test ------------------------------------------------------------------

def _expert_layer(params, h, first, count):
    """The program's expert layer (the routed part of a told share, and
    the shared expert), layer 0 of the expert stack."""
    from cake_tpu.ops.mlp import swiglu

    layer = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    routed = moe.moe_swiglu(
        h, layer["router"], layer["w_gate"][first:first + count],
        layer["w_up"][first:first + count],
        layer["w_down"][first:first + count], top_k=CFG.num_experts_per_tok,
        routing=moe.GroupRouting(
            CFG.n_group, CFG.topk_group, CFG.norm_topk_prob,
            CFG.routed_scaling_factor, layer["b_router"],
            CFG.family.topk_norm_eps),
        held=(first, count))
    return routed, swiglu(h, layer["ws_gate"], layer["ws_up"],
                          layer["ws_down"])


@pytest.mark.parametrize("rows", [6, 96], ids=["step-rows", "chunk-rows"])
def test_sixteen_shares_add_up_to_the_uncut_layer(params, tensors, rows):
    """THE SHARE TEST: the routed parts that sixteen ``ep`` ranks give (one
    of the 16 experts each, the router at its whole width, the choice on
    score + bias), plus the shared expert counted once, add up to the
    uncut layer, in the program and against the reference's uncut layer;
    one rank's part is the reference's same share (``held=``)."""
    h = jax.random.normal(jax.random.PRNGKey(9), (1, rows, CFG.hidden_size))
    whole, shared = _expert_layer(params, h, 0, 16)
    parts = [_expert_layer(params, h, r, 1)[0] for r in range(16)]
    total = sum(parts) + shared
    np.testing.assert_allclose(total, whole + shared, atol=TIGHT, rtol=0)
    cfg = CFG.to_hf_dict()
    with jax.default_matmul_precision("highest"):
        uncut = ref.expert_layer(cfg, tensors, "model.layers.1.", h[0])
        one = ref.expert_layer(cfg, tensors, "model.layers.1.", h[0],
                               held=[3])
    np.testing.assert_allclose(total[0], uncut, atol=TIGHT, rtol=0)
    np.testing.assert_allclose(parts[3][0] + shared[0], one, atol=TIGHT,
                               rtol=0)
    if rows > 16:
        assert float(jnp.abs(parts[3]).max()) > 0.01  # a share is not nothing


# -- (g) the configuration ---------------------------------------------------------------

def _catalog_row() -> dict:
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        lines = open(path).read().splitlines()
    except OSError:
        pytest.skip("no catalog beside the guides here")
    return next(row for row in map(json.loads, lines)
                if row["name"] == "GLM-5")


def test_the_catalogs_file_reads_as_the_preset_and_round_trips():
    """The catalog's ``config.json`` of GLM-5 reads as ``glm5_ep16()`` with
    the published depth, experts and vocabulary; what ``to_hf_dict`` writes
    reads back as the same configuration, and carries the sparse family's
    own keys (the rope base nested, the indexer's)."""
    row = _catalog_row()
    served = LlamaConfig.from_hf_dict(row["config"],
                                      max_seq_len=row["context_length"])
    preset = glm5_ep16(n_routed_experts=256, bos_token_id=128000,
                       eos_token_id=128001)
    assert served == preset
    assert served.family is families.LATENT
    assert (served.index_n_heads, served.index_head_dim,
            served.index_topk) == (32, 128, 2048)
    assert served.rope_theta == 1e6 and served.rope_scaling is None
    assert served.router_bias and served.attn_scale == 256 ** -0.5
    # [c | k_pe] in ONE row, padded to whole lane tiles (576 -> 640)
    assert served.cache_plan == {"rows": (78, 1, 640, 0),
                                 "index": (78, 1, 128)}
    assert served.cache_token_bytes == 78 * (640 + 128) * 2
    written = served.to_hf_dict()
    for key in ("index_n_heads", "index_head_dim", "index_topk",
                "indexer_rope_interleave", "rope_parameters", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "topk_method", "n_group", "topk_group",
                "routed_scaling_factor", "first_k_dense_replace"):
        assert written[key] == row["config"][key], key
    assert "rope_theta" not in written
    assert LlamaConfig.from_hf_dict(
        written, max_seq_len=row["context_length"]) == served
    # a plain latent model writes none of the sparse family's keys
    from cake_tpu.models.config import tiny_mla_moe

    assert not {"index_topk", "rope_parameters",
                "indexer_rope_interleave"} & set(tiny_mla_moe().to_hf_dict())


# -- (h) the benchmark's copy of the reference ---------------------------------------------

def _bench_arch():
    """``benchmark/arch/dsa_mla_moe.py``, loaded as the harness loads it
    (its directory's shared modules on the path)."""
    root = ROOT / "benchmark"
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    spec = importlib.util.spec_from_file_location(
        "bench_arch_dsa_mla_moe_under_test",
        root / "arch" / "dsa_mla_moe.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_numpy_reference_agrees_with_the_jax_one(tmp_path):
    """``benchmark/arch/dsa_mla_moe.py`` writes a seeded checkpoint under
    the names the loader reads, and its numpy reference (what decides a
    cell's ``correct``: blocked by query rows, each row's choice a stable
    sort) gives the ``jax.numpy`` reference's log-softmax on the same
    tensors, 48 rows under an ``index_topk`` of 8; the program, given the
    loader's reading of the same files, agrees too."""
    arch = _bench_arch()
    cfg = dict(CFG.to_hf_dict(), hidden_size=128, vocab_size=512,
               max_position_embeddings=128, torch_dtype="float32",
               num_nextn_predict_layers=1)
    arch.REFERENCE_BLOCK = 16  # several blocks of query rows
    written = arch.write_checkpoint(cfg, "bf16", 61, tmp_path)
    assert written["bytes"] == arch.checkpoint_bytes(cfg, "bf16")
    ck = arch.Checkpoint(tmp_path)
    names = json.loads((tmp_path / "model.safetensors.index.json")
                       .read_text())["weight_map"]
    assert "model.layers.2.self_attn.indexer.k_norm.bias" in names
    assert not [n for n in names if n.startswith(("mtp.", "model.layers.3."))]
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
    loaded = load_llama_params(tmp_path, cfg["num_hidden_layers"],
                               dtype="float32")
    served = LlamaConfig.from_hf_json(tmp_path / "config.json",
                                      dtype="float32", max_seq_len=128)
    assert served.family is families.LATENT and served.index_topk == TOPK
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
        'model_types=("deepseek_v3", "axk1", "xing4_0")')
    with pytest.raises(RuntimeError,
                       match="declares model_type 'glm_moe_dsa'"):
        arch.require_family(tmp_path)


def test_the_benchmarks_counts_are_the_arithmetic():
    """``arch/dsa_mla_moe.py`` at the cell's configuration: ISSUE 61's
    7.82 GB of weights and 7,040 B of cache a token; a step reads index
    keys to the frontier and the latent rows of 2048, not of the
    frontier; an admission's operations are the lower triangle's."""
    arch = _bench_arch()
    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / "glm5-ep16-cut.json").read_text())
    held = arch.weight_bytes(cfg, "bf16")
    attention = 165_019_648 + 9_371_648 + 256  # MLA, the indexer, k_norm
    expert_layer = (attention + 2 * 6144 + 2048 + 512 + 37_748_736
                    + 256 * 6144 + 256 + 16 * 37_748_736)
    dense_layer = attention + 2 * 6144 + 2048 + 512 + 3 * 6144 * 12288
    params = 4 * expert_layer + dense_layer + 2 * 19360 * 6144 + 6144
    assert held == 2 * params
    assert held / 1e9 == pytest.approx(7.82, abs=0.01)
    assert arch.checkpoint_bytes(cfg, "bf16") == held
    # 7,040 B of values a token; the program's row is padded to whole
    # lane tiles (576 -> 640), so its buffers hold 7,680
    assert arch.cache_token_bytes(cfg) == 7040
    assert LlamaConfig.from_hf_dict(
        {k: cfg[k] for k in arch.HF_KEYS if k in cfg}).cache_token_bytes == 7680
    rows, frontier = 16, 12_000
    step = arch.decode_step_bytes(cfg, "bf16", rows, frontier, "bf16")
    assert step - arch.weight_bytes(cfg, "bf16", "bf16", rows) == (
        rows * 5 * (frontier * 256 + 2048 * 1152))
    assert arch.dsa_attend_bytes(cfg, 2048) == 2048 * 1152
    assert arch.dsa_index_bytes(cfg, 12_000) == 12_000 * 256
    assert arch.prefill_pairs(cfg, 1000) == (500_500, 500_500)
    scored, attended = arch.prefill_pairs(cfg, 8192)
    assert attended == 2048 * 2049 // 2 + (8192 - 2048) * 2048
    assert arch.dsa_prefill_flops(cfg, scored, attended) == (
        scored * 2 * 32 * 128 + attended * 2 * 64 * 512)


def test_the_readers_take_a_mean_a_call_times_the_traces_calls():
    """The five readers under ``benchmark/layer_metrics/``: a device-trace
    share is the architecture's count of a MEAN call (a counter's growth
    over the growth of the program's own count of calls: a capture closes
    long after its span and counters around it cover more calls than the
    trace holds) times the calls the trace holds, over the peak, over the
    operations' time; the gather XLA names flat by its result's shape
    counts with the attention; nothing (and no raise) without a trace,
    against a program without the counters, or where no call was counted."""
    _bench_arch()  # puts benchmark/ on the path
    import run

    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / "glm5-ep16-cut.json").read_text())
    arch = run.load_arch(cfg["bench"]["arch"])
    trace = {"devices": [{"ops": [
        ["dsa_index.7 f32[16,1,16384]", 0.010, 100],
        ["sort.3 f32[16,16384]", 0.020, 100],
        ["sort.4 f32[16,256]", 9.0, 100],  # the router's: not the choice
        ["fusion.9 bf16[32768,640]", 0.050, 100],
        ["dsa_attend.7 f32[16,64,128]", 0.005, 100],
        ["dsa_prefill_select.2 s8[1,8192,8192]", 0.05, 10],
        ["dsa_prefill_attend.2 bf16[1,64,8192,256]", 0.20, 10]]}]}

    def count(v):
        return {"type": "counter", "value": v}

    calls = 4000  # forty times what the trace holds
    scored, attended = arch.prefill_pairs(cfg, 8000)
    after = {"dsa.decode_calls": count(calls),
             "dsa.rows_live": count(calls * 16 * 8000),
             "dsa.rows_selected": count(calls * 16 * 2048),
             "dsa.admit_calls": count(50),
             "dsa.admit_pairs_scored": count(50 * scored),
             "dsa.admit_pairs_attended": count(50 * attended)}
    ctx = {"before": {"status": {"metrics": {}}},
           "after": {"status": {"metrics": after}}, "trace": trace,
           "cfg": cfg, "arch": arch,
           "peaks": {"hbm_gb_per_s": 819.0, "bf16_tflops": 197.0}}
    got = {name: run.load_reader(name)(ctx) for name in (
        "kernel.dsa_index_hbm_share", "kernel.dsa_select_hbm_share",
        "kernel.dsa_attend_hbm_share", "kernel.dsa_prefill_mxu_share",
        "dsa.selected_share")}
    gbs = 819e9
    assert got["kernel.dsa_index_hbm_share"] == pytest.approx(
        100 * 100 * 16 * 8000 * 256 / gbs / 0.010)
    assert got["kernel.dsa_select_hbm_share"] == pytest.approx(
        100 * 100 * 16 * (4 * 8000 + 8 * 2048) / gbs / 0.020)
    assert got["kernel.dsa_attend_hbm_share"] == pytest.approx(
        100 * 100 * 16 * 2048 * 1152 / gbs / 0.055)
    assert got["kernel.dsa_prefill_mxu_share"] == pytest.approx(
        100 * 10 * arch.dsa_prefill_flops(cfg, scored, attended)
        / 197e12 / 0.25)
    assert got["dsa.selected_share"] == pytest.approx(25.6)
    assert all(v < 100 for v in got.values())
    for lacking in (dict(ctx, trace=None),
                    dict(ctx, after={"status": {"metrics": {}}}),
                    dict(ctx, after={"status": {"metrics": {
                        k: count(0) for k in after}}})):
        for name in got:
            if name == "dsa.selected_share" and lacking.get("trace") is None:
                continue
            assert run.load_reader(name)(lacking) is None, name
