"""Architecture ``dsa_mla_moe``: ``mla_moe``'s decoder (multi-head latent
attention, leading dense layers, then a shared expert beside sigmoid-scored
routed ones) under a LEARNED SPARSE ATTENTION (DeepSeek sparse attention):
every layer holds an indexer whose score of each cached row chooses the
``index_topk`` rows a query attends. GLM-5's ``config.json`` keys
(``model_type`` ``glm_moe_dsa``: DeepSeek-V3's and ``index_n_heads``,
``index_head_dim``, ``index_topk``, ``indexer_rope_interleave``, the rope
base nested in ``rope_parameters``, no rope scaling), the choice of
experts corrected by a bias an expert (``topk_method`` ``noaux_tc``:
``arch/kda_mla_moe.py``'s ``route``).

Per token ``x_t`` of a layer (after the input norm), with ``c_q,t =
rmsnorm(x_t W_qa)`` the query latent ``mla_moe``'s attention makes:

    q^I_t,j = (c_q,t W^I_qb)_j              j = 1..index_n_heads heads of index_head_dim
    k^I_t   = LayerNorm(x_t W^I_k)          weight and bias, eps 1e-6
              rope (the attention's angles, interleaved pairs (2i, 2i+1)) on
              the FIRST qk_rope_head_dim channels of every q^I_t,j and of k^I_t
    w_t     = (x_t W^I_w) * index_n_heads^-0.5 * index_head_dim^-0.5
    I_t,s   = sum_j w_t,j relu(q^I_t,j . k^I_s)              s <= t
    S_t     = the index_topk rows s <= t of largest I_t,s (all of them where
              t + 1 <= index_topk; a tie goes to the lower s)
    out_t   = the attention's causal softmax over the rows of S_t alone

``num_nextn_predict_layers``: a next-token prediction block that takes no
part in the model's own logits; neither written nor read.

Numpy and the standard library only (the parent of a chip run never
imports JAX). The reference is written from the equations ISSUE 61 states
(Motivation), not from the program: the whole sequence at once, no cache,
expanded attention, a block of query rows at a time so that a probe of
several thousand tokens fits the host (``[heads, block, t]`` scores), each
row's choice a stable sort of its own scores. What the published file does
not settle is the configuration's ``assumed``.

Seeded weights that make the mechanism work (``weights.py`` says why the
router's channels are what they are; they are ``mla_moe``'s here): the
query latent's and the key latent's norm weights are DOUBLED
(``LATENT_NORM_GAIN``), so that a head's scores spread four times as wide
as plain seeded weights give and its softmax leans on a few rows: a
query that loses a fifth of its rows then loses, one time in five, a row
it leaned on, and the comparison with the reference SEES whether the
choice was made. The indexer's own tensors are plain seeded ones (its
ranking does not depend on their scale) and are kept unquantized in both
layouts, as the router is. The correction bias is ``kda_mla_moe``'s.
"""

from __future__ import annotations

import math
from pathlib import Path

import numpy as np

from arch import kda_mla_moe, mla_moe
from reference import Layer, rms_norm, score_pairs, swiglu
from shapes import PLAIN_BYTES
from weights import (Checkpoint, File, hf_config, linear, norm, plain, rngs,
                     small, write_files)

WRITER_VERSION = 1  # part of the key a checkpoint's directory is named by
REFERENCE_VERSION = 1  # part of the key under which answers are kept

# keys of a configuration file that are the model's own config.json (what
# the server reads); everything else in the file is the benchmark's
HF_KEYS = (
    "architectures", "model_type", "attention_bias", "ep_size",
    "first_k_dense_replace", "hidden_act", "head_dim", "hidden_size",
    "index_head_dim", "index_n_heads", "index_topk",
    "indexer_rope_interleave", "intermediate_size", "kv_lora_rank",
    "max_position_embeddings", "moe_intermediate_size", "moe_layer_freq",
    "n_group", "n_routed_experts", "n_shared_experts", "norm_topk_prob",
    "num_attention_heads", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "num_nextn_predict_layers", "q_lora_rank",
    "qk_head_dim", "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps",
    "rope_interleave", "rope_parameters", "routed_scaling_factor",
    "scoring_func", "tie_word_embeddings", "topk_group", "topk_method",
    "v_head_dim", "vocab_size", "expert_share", "bos_token_id",
    "eos_token_id",
)

MODEL_TYPE = "glm_moe_dsa"
LATENT_NORM_GAIN = 2.0  # on q_a_layernorm and kv_a_layernorm
K_NORM_EPS = 1e-6
REFERENCE_BLOCK = 256  # query rows the reference attends at a time


def require_family(checkout: Path) -> None:
    """Refuse a checkout whose program does not name this family, at once
    (``arch/looped_gqa.py`` says why a guard stands here). A program from
    before the family has a latent-attention record that selects ANY file
    with ``kv_lora_rank`` set: it would find every tensor it asks for,
    leave the indexer's unread and serve the checkpoint as a plain latent
    model that attends every row. Such a checkout cannot run this
    configuration, and a run on it fails here, before a checkpoint is
    written, and measures nothing under the cell's name. Asked of the
    source: the parent of a chip run imports neither JAX nor ``cake_tpu``."""
    models = checkout / "cake_tpu" / "models"
    if not any(f'"{MODEL_TYPE}"' in path.read_text()
               for path in sorted(models.glob("*.py"))):
        raise RuntimeError(
            f"no module under {models} declares model_type {MODEL_TYPE!r}: "
            "this program has no learned sparse attention (an indexer, the "
            "choice of index_topk rows, a cache row for the index key: "
            "cake_tpu/ops/dsa.py); the cell needs it")


require_family(Path(__file__).resolve().parents[2])


# -- sizes -----------------------------------------------------------------------

# what the readers of the expert layers' counters ask of an architecture
held_experts = mla_moe.held_experts
expert_layers = mla_moe.expert_layers


def rope_cfg(cfg: dict) -> dict:
    """``cfg`` with the rope base where ``mla_moe.rope_angles`` reads it."""
    return {**cfg, "rope_theta": cfg["rope_parameters"]["rope_theta"],
            "rope_scaling": None}


def indexer_tensors(cfg: dict) -> dict[str, tuple[int, ...]]:
    """HF suffix -> shape (torch's ``[out, in]``) of a layer's indexer."""
    heads, dim = cfg["index_n_heads"], cfg["index_head_dim"]
    a = "self_attn.indexer."
    return {a + "wq_b.weight": (heads * dim, cfg["q_lora_rank"]),
            a + "wk.weight": (dim, cfg["hidden_size"]),
            a + "k_norm.weight": (dim,),
            a + "k_norm.bias": (dim,),
            a + "weights_proj.weight": (heads, cfg["hidden_size"])}


def _extra_values(cfg: dict) -> int:
    """Unquantized values this family's checkpoint and device hold beyond
    ``mla_moe``'s tensors: an indexer a layer and the router's bias an
    expert layer."""
    indexer = sum(math.prod(s) for s in indexer_tensors(cfg).values())
    return (cfg["num_hidden_layers"] * indexer
            + mla_moe.expert_layers(cfg) * mla_moe.router_width(cfg))


def checkpoint_bytes(cfg: dict, layout: str) -> int:
    """Bytes the checkpoint will take on disk (to see that it fits)."""
    return mla_moe.checkpoint_bytes(cfg, layout) + _extra_values(cfg) * (
        4 if layout == "q8" else 2)


def cache_token_bytes(cfg: dict, serve_dtype: str = "bf16") -> int:
    """Bytes the cache holds for one token of one stream: the latent row
    and the index key, every layer."""
    return (cfg["num_hidden_layers"] * PLAIN_BYTES[serve_dtype]
            * (mla_moe.cache_row_values(cfg) + cfg["index_head_dim"]))


# -- the checkpoint --------------------------------------------------------------

def router_bias(cfg: dict) -> np.ndarray:
    """The correction bias: -1 for one expert in sixteen, else 0."""
    e = np.arange(mla_moe.router_width(cfg))
    every, at = kda_mla_moe.SUPPRESSED
    return np.where(e % every == at, -1.0, 0.0).astype(np.float32)


def write_checkpoint(cfg: dict, layout: str, seed: int, model_dir: Path,
                     workers: int = 8) -> dict:
    """Write the checkpoint of configuration ``cfg`` (a configuration
    file's dict) into ``model_dir``; returns {"bytes", "files"}."""
    if layout not in ("q8", "bf16"):
        raise ValueError(f"unknown checkpoint layout {layout!r}")
    model_dir.mkdir(parents=True, exist_ok=True)
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    layers = cfg["num_hidden_layers"]
    routed = bool(cfg.get("n_routed_experts"))
    width = mla_moe.router_width(cfg) if routed else 0
    if width > h // 2:
        raise ValueError(f"{width} routing channels of {h}")
    gain = np.float32(LATENT_NORM_GAIN)

    def layer(i: int):
        f = File(model_dir / f"model-layer-{i:05d}.safetensors")
        r = rngs(seed, i)
        p = f"model.layers.{i}."
        plain(f, layout, p + "input_layernorm.weight", norm(next(r), h))
        plain(f, layout, p + "post_attention_layernorm.weight",
              norm(next(r), h))
        plain(f, layout, p + "self_attn.q_a_layernorm.weight",
              norm(next(r), cfg["q_lora_rank"]) * gain)
        plain(f, layout, p + "self_attn.kv_a_layernorm.weight",
              norm(next(r), cfg["kv_lora_rank"]) * gain)
        for suffix, shape in indexer_tensors(cfg).items():
            rng = next(r)
            if suffix.endswith("k_norm.weight"):
                values = norm(rng, shape[0])
            elif suffix.endswith("k_norm.bias"):
                values = small(rng, shape, 0.25)
            else:
                values = small(rng, shape, 1.0 / math.sqrt(shape[1]))
            plain(f, layout, p + suffix, values)
        if mla_moe.is_expert_layer(cfg, i):  # row e reads channel e alone
            plain(f, layout, p + "mlp.gate.weight",
                  np.eye(width, h, dtype=np.float32))
            plain(f, layout, p + "mlp.gate.e_score_correction_bias",
                  router_bias(cfg))
        for suffix, (fan_in, out) in mla_moe.layer_linears(cfg, i).items():
            writes_residual = suffix.endswith(("o_proj.weight",
                                               "down_proj.weight"))
            linear(f, mla_moe._tensor_rng(seed, i, suffix), layout,
                   p + suffix, fan_in, out,
                   zero_rows=width if writes_residual else 0)
        return f.write()

    def ends():
        f = File(model_dir / "model-ends.safetensors")
        r = rngs(seed, layers)
        embed = small(next(r), (v, h), 1.0 / math.sqrt(h))
        if routed:
            mla_moe.routing_embed(embed, cfg)
        plain(f, layout, "model.embed_tokens.weight", embed)
        plain(f, layout, "model.norm.weight", norm(next(r), h))
        linear(f, next(r), layout, "lm_head.weight", h, v)
        return f.write()

    jobs = [ends] + [lambda i=i: layer(i) for i in range(layers)]
    return write_files(model_dir, layout, jobs, hf_config(cfg, HF_KEYS),
                       workers)


# -- the float32 reference -----------------------------------------------------

def _layer_norm(x, w, b):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return ((x - mean) / np.sqrt(var + np.float32(K_NORM_EPS)) * w + b
            ).astype(np.float32)


def _rope_first(x, cos, sin, width: int):
    """Rotate the first ``width`` channels of ``x [..., t, d]``."""
    out = x.copy()
    out[..., :width] = mla_moe.rope_pairs(
        np.ascontiguousarray(x[..., :width]), cos, sin)
    return out


def chosen_rows(scores: np.ndarray, first: int, topk: int) -> np.ndarray:
    """The choice of each row of ``scores [n, t]`` (query rows ``first ..
    first + n - 1`` against every row) as a mask ``[n, t]``: the ``topk``
    rows ``s <= t`` of largest score, a tie to the lower ``s``."""
    n, t = scores.shape
    rows = first + np.arange(n)
    causal = np.arange(t)[None, :] <= rows[:, None]
    if first + n <= topk:
        return causal
    ranked = np.argsort(-np.where(causal, scores, -np.inf), axis=-1,
                        kind="stable")[:, :topk]
    mask = np.zeros((n, t), bool)
    np.put_along_axis(mask, ranked, True, axis=1)
    return mask & causal


def _attention(cfg: dict, ck: Layer, p: str, x: np.ndarray) -> np.ndarray:
    t = x.shape[0]
    nh = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, dc = cfg["v_head_dim"], cfg["kv_lora_rank"]
    heads, dim = cfg["index_n_heads"], cfg["index_head_dim"]
    eps, topk = cfg["rms_norm_eps"], cfg["index_topk"]
    a = p + "self_attn."
    c_q = rms_norm(x @ ck.f32(a + "q_a_proj.weight").T,
                   ck.f32(a + "q_a_layernorm.weight"), eps)
    q = (c_q @ ck.f32(a + "q_b_proj.weight").T).reshape(t, nh, dn + dr)
    ckv = x @ ck.f32(a + "kv_a_proj_with_mqa.weight").T
    c = rms_norm(ckv[:, :dc], ck.f32(a + "kv_a_layernorm.weight"), eps)
    kv = (c @ ck.f32(a + "kv_b_proj.weight").T).reshape(t, nh, dn + dv)
    cos, sin = mla_moe.rope_angles(rope_cfg(cfg), t)
    q = q.transpose(1, 0, 2)  # [H, t, dn + dr]
    q_pe = mla_moe.rope_pairs(np.ascontiguousarray(q[..., dn:]), cos, sin)
    k_pe = mla_moe.rope_pairs(np.ascontiguousarray(ckv[:, dc:]), cos, sin)
    k_nope = kv[:, :, :dn].transpose(1, 0, 2)
    v = kv[:, :, dn:].transpose(1, 0, 2)
    # the indexer
    q_i = (c_q @ ck.f32(a + "indexer.wq_b.weight").T).reshape(t, heads, dim)
    q_i = _rope_first(np.ascontiguousarray(q_i.transpose(1, 0, 2)), cos, sin,
                      dr)  # [J, t, D]
    k_i = _rope_first(_layer_norm(
        x @ ck.f32(a + "indexer.wk.weight").T,
        ck.f32(a + "indexer.k_norm.weight"),
        ck.f32(a + "indexer.k_norm.bias")), cos, sin, dr)  # [t, D]
    w_i = (x @ ck.f32(a + "indexer.weights_proj.weight").T) * np.float32(
        heads ** -0.5 * dim ** -0.5)  # [t, J]
    scale = np.float32((dn + dr) ** -0.5)
    out = np.empty((t, nh * dv), np.float32)
    for lo in range(0, t, REFERENCE_BLOCK):
        hi = min(lo + REFERENCE_BLOCK, t)
        dots = np.maximum(q_i[:, lo:hi] @ k_i[:hi].T[None], 0.0)  # [J, n, hi]
        index = np.einsum("jns,nj->ns", dots, w_i[lo:hi])
        seen = chosen_rows(index, lo, topk)  # [n, hi]
        scores = (q[:, lo:hi, :dn] @ k_nope[:, :hi].transpose(0, 2, 1)
                  + q_pe[:, lo:hi] @ k_pe[:hi].T[None]) * scale
        scores = np.where(seen[None], scores, np.float32(-np.inf))
        scores = scores - scores.max(-1, keepdims=True)
        w = np.exp(scores)
        w /= w.sum(-1, keepdims=True)
        out[lo:hi] = (w @ v[:, :hi]).transpose(1, 0, 2).reshape(
            hi - lo, nh * dv)
    return out @ ck.f32(a + "o_proj.weight").T


def _feed_forward(cfg: dict, ck: Layer, p: str, i: int, x: np.ndarray,
                  margins: list) -> np.ndarray:
    """Layer ``i``'s feed-forward block: a dense SwiGLU, or ``shared(h) +
    the sum over the chosen experts HELD here of w_e expert_e(h)``, the
    choice on ``score + e_score_correction_bias``; ``margins`` gains each
    token's routing margin."""
    def mlp(prefix: str, rows: np.ndarray) -> np.ndarray:
        return swiglu(rows, ck.f32(prefix + "gate_proj.weight"),
                      ck.f32(prefix + "up_proj.weight"),
                      ck.f32(prefix + "down_proj.weight"))

    if not mla_moe.is_expert_layer(cfg, i):
        return mlp(p + "mlp.", x)
    logits = x @ ck.f32(p + "mlp.gate.weight").T  # [t, E]
    bias = (ck.f32(p + "mlp.gate.e_score_correction_bias")
            if cfg.get("topk_method") == "noaux_tc"
            else np.zeros(logits.shape[1], np.float32))
    idx, weight, margin = kda_mla_moe.route(
        cfg, (1.0 / (1.0 + np.exp(-logits))).astype(np.float32), bias)
    margins.append(margin)
    out = np.zeros_like(x)
    for e in mla_moe.held_experts(cfg):
        rows, slot = np.nonzero(idx == e)
        if len(rows):
            out[rows] += weight[rows, slot][:, None] * mlp(
                f"{p}mlp.experts.{e}.", x[rows])
    if cfg.get("n_shared_experts"):
        out += mlp(p + "mlp.shared_experts.", x)
    return out


def chosen_logprobs(cfg: dict, model_dir, pairs: list[tuple]) -> list[dict]:
    """For each (prompt, chosen) pair: the log-probabilities the reference
    gives the ``chosen`` continuation of ``prompt``, token by token, and
    its own best token at each place (``reference.score_pairs``), given
    the same share of the experts as the server. A layer at a time and a
    block of query rows at a time, so that the published widths and a
    probe of several thousand tokens fit the host."""
    ck = Checkpoint(model_dir)
    eps = cfg["rms_norm_eps"]
    embed = ck.f32("model.embed_tokens.weight")
    xs = [embed[np.asarray(list(prompt) + list(chosen[:-1]), np.int64)]
          for prompt, chosen in pairs]
    del embed
    margins: list[list] = [[] for _ in pairs]
    for i in range(cfg["num_hidden_layers"]):
        p, layer = f"model.layers.{i}.", Layer(ck)
        for n, x in enumerate(xs):
            x = x + _attention(cfg, layer, p, rms_norm(
                x, layer.f32(p + "input_layernorm.weight"), eps))
            xs[n] = x + _feed_forward(cfg, layer, p, i, rms_norm(
                x, layer.f32(p + "post_attention_layernorm.weight"), eps),
                margins[n])
    return score_pairs(ck, eps, pairs, xs, margins)


# -- bytes and operations ----------------------------------------------------------

def weight_bytes(cfg: dict, layout: str, serve_dtype: str = "bf16",
                 rows: float | None = None) -> float:
    """``mla_moe.weight_bytes`` and this family's tensors beside them (the
    indexers and the routers' biases: read whole every step at any
    batch)."""
    return (mla_moe.weight_bytes(cfg, layout, serve_dtype, rows)
            + _extra_values(cfg) * PLAIN_BYTES[serve_dtype])


def dsa_trace_ops(cfg: dict) -> dict[str, str]:
    """Patterns (``re.match`` on a reduced trace's operation names, ``<HLO
    name> <type>[<shape>]``) of the sparse attention path's operations, as
    the program names its kernels and as XLA names what the program leaves
    to it, by the configuration's shapes: ``index`` (a decode step's index
    scores), ``select`` (its choice: a kernel of that name or XLA's sort of
    the batch's scores), ``attend`` (the gather of the chosen rows, which
    XLA names by its result ``[slots, index_topk, row width]``, and the
    or flat, ``[slots x index_topk, row width]``, the width the program's
    padded one, and the attention over them) with ``attend_calls`` (the attention alone: one a
    layer and step), ``prefill`` (an admission's choice and masked sweep)
    with ``prefill_calls`` (the sweep alone: one a layer and dispatch)."""
    bench = cfg["bench"]
    scores = rf"{bench['slots']},{bench['kv_capacity']}"
    # the program keeps [c | k_pe] in one row of whole 128-lane tiles, and
    # XLA may name the gather's result flat: [slots x index_topk, width]
    rows, width = bench["slots"], -(-mla_moe.cache_row_values(cfg) // 128) * 128
    chosen = (rf"(?:{rows},{cfg['index_topk']}|{rows * cfg['index_topk']}),"
              rf"{width}")
    return {"index": r"dsa_index\b",
            "select": rf"dsa_select\b|sort\.\d+ f32\[{scores}\]",
            "attend": rf"dsa_attend\b|[\w\-]+\.\d+ \w+\[{chosen}\]",
            "attend_calls": r"dsa_attend\b",
            "prefill": r"dsa_prefill",
            "prefill_calls": r"dsa_prefill_attend\b"}


def dsa_index_bytes(cfg: dict, rows_live: float,
                    cache_dtype: str = "bf16") -> float:
    """The least the decode steps' index scoring reads: one index key for
    every (layer, step, row up to a stream's frontier), ``rows_live`` of
    them in all (the program's ``dsa.rows_live``)."""
    return rows_live * cfg["index_head_dim"] * PLAIN_BYTES[cache_dtype]


def dsa_select_bytes(cfg: dict, rows_live: float,
                     rows_selected: float) -> float:
    """The least the decode steps' choice moves: every live row's float32
    score read once, each chosen row's score and number (int32) written."""
    return 4.0 * rows_live + 8.0 * rows_selected


def dsa_attend_bytes(cfg: dict, rows_selected: float,
                     cache_dtype: str = "bf16") -> float:
    """The least the decode steps' attention reads: the latent row of
    every (layer, step, chosen row), ``rows_selected`` in all (the
    program's ``dsa.rows_selected``), once."""
    return (rows_selected * mla_moe.cache_row_values(cfg)
            * PLAIN_BYTES[cache_dtype])


def dsa_prefill_flops(cfg: dict, pairs_scored: float,
                      pairs_attended: float) -> float:
    """Operations of an admission's sparse attention path at the rows'
    TRUE lengths: an index score for every (layer, query row, row at or
    before it) pair (``index_n_heads`` dot products of ``index_head_dim``)
    and, for every pair a row attends (``min(t + 1, index_topk)`` a query
    row), every head's score and value products."""
    index = 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"]
    attend = 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    return pairs_scored * index + pairs_attended * attend


def prefill_pairs(cfg: dict, length: int) -> tuple[int, int]:
    """``(scored, attended)`` pairs of ONE layer for a prompt of ``length``
    rows: the lower triangle, and ``min(t + 1, index_topk)`` a row."""
    k = min(cfg["index_topk"], length)
    return (length * (length + 1) // 2,
            k * (k + 1) // 2 + (length - k) * cfg["index_topk"])


def kv_bytes(cfg: dict, context: float, rows: float,
             cache_dtype: str = "bf16") -> float:
    """Bytes ``rows`` streams at a mean position of ``context`` read of
    the cache in one step: every layer's index keys to the frontier and
    the latent rows of the ``min(context, index_topk)`` chosen."""
    layers = cfg["num_hidden_layers"]
    return (dsa_index_bytes(cfg, rows * context * layers, cache_dtype)
            + dsa_attend_bytes(
                cfg, rows * min(context, cfg["index_topk"]) * layers,
                cache_dtype))


def decode_step_bytes(cfg: dict, layout: str, rows: float, context: float,
                      serve_dtype: str = "bf16") -> float:
    """The least one decode step reads: the weights for ``rows`` live
    streams, their index keys to a mean position of ``context`` and the
    latent rows they choose."""
    return (weight_bytes(cfg, layout, serve_dtype, rows)
            + kv_bytes(cfg, context, rows, serve_dtype))
