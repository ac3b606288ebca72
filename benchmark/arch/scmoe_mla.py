"""Architecture ``scmoe_mla``: a decoder of SHORTCUT-CONNECTED DOUBLE LAYERS
over multi-head latent attention, with ZERO-COMPUTE EXPERTS in the router:
LongCat-Flash's ``config.json`` keys (``model_type`` ``longcat_flash``:
``num_layers``, ``ffn_hidden_size``, ``expert_ffn_hidden_size``,
``moe_topk``, ``zero_expert_num``, ``zero_expert_type``,
``mla_scale_q_lora``, ``mla_scale_kv_lora``, ``attention_method``, beside
DeepSeek-V3's latent keys). A configuration may hold a chip's share of an
expert-parallel deployment, as ``arch/mla_moe.py`` says:
``n_routed_experts`` experts are HELD here, global experts ``rank *
n_routed_experts ..`` of the ``expert_share.n_routed_experts`` real ones
the router scores beside its ``zero_expert_num`` identities.

One published layer, ``x`` the residual stream, ``RMS`` an RMSNorm (its own
weight)::

    a0 = x  + MLA_0(RMS(x;  input_layernorm.0))
    h  = RMS(a0; post_attention_layernorm.0)
    s  = MoE(h)                                # computed here, NOT added here
    b0 = a0 + FFN_0(h)                         # dense SwiGLU, ffn_hidden_size
    a1 = b0 + MLA_1(RMS(b0; input_layernorm.1))
    b1 = a1 + FFN_1(RMS(a1; post_attention_layernorm.1)) + s

``MoE(h)``: ``p = softmax(h W_r)`` over ALL ``experts + zero_expert_num``
outputs; the ``moe_topk`` of largest ``p + e_score_correction_bias`` (a tie
to the lower id); ``w_e = routed_scaling_factor p_e``, NOT renormalised;
``s = sum over chosen held experts of w_e SwiGLU_e(h) + (sum over chosen
zero-compute outputs of w_e) h``. ``MLA_j``: ``mla_moe``'s attention with
``q`` times ``(hidden / q_lora_rank)^0.5`` and the normed latent ``c`` times
``(hidden / kv_lora_rank)^0.5`` where the two ``mla_scale_*`` keys say so
(``k_pe`` is not scaled), plain rope on interleaved pairs.

Numpy and the standard library only (the parent of a chip run never
imports JAX). The reference is written from the equations ISSUE 64 states
(Motivation), not from the program: the whole sequence at once, no cache,
expanded attention, no folding of the two factors, a block of query rows
at a time so that a probe of several thousand tokens fits the host
(``[heads, block, t]`` scores). What the published file does not settle is
the configuration's ``assumed``.

Seeded weights that make the mechanism work (``weights.py`` says why a
router gets channels of its own): the first ``experts + zero_expert_num``
channels of the residual stream belong to the router; the embedding marks
``moe_topk`` of them a token id, DRAWN UNIFORMLY over all of them (so 0 to
``moe_topk`` of a token's choices are identities, a third on average: the
compute a token costs varies); no linear writes to them; the router's row
``e`` reads channel ``e`` alone. A marked output's logit is ``mark / rms >
0``, every other's exactly 0, so the marked ones are chosen whatever the
rounding. The marks are ``ROUTE_MARK`` = 128, not ``weights.ROUTE_MARK``'s
4, and the router's row ``e`` reads its channel with ``ROUTER_GAIN`` =
3/16: the marks then carry most of the residual stream's norm at every
depth, so a marked logit is ``~22.6 x 3/16 x`` the norm's weight ``= 3.7
to 5.3`` at layer 0 as at layer 3 (measured with the float32 reference at
the published widths: PERF.md section 6, PR 64), whatever the stream has
grown to. That matters because this router's weights are UNNORMALISED
softmax shares (``6 p``): with logits that size the twelve chosen outputs
hold about half of the probability beside the 756 others, a token's
weights add up to ~3 (0.1 to 0.7 each), the identity part ``z h`` (z ~ 1)
and the held experts' part are something the comparison with the
reference SEES, and so is a renormalisation of the shares (it would
double them): with plain unit marks the shares are ~0.001 and the whole
expert block vanishes from the logits; with the router's weight at 1 the
chosen take all the probability and renormalising changes nothing. The identity part
writes the routing channels too (``z h``: a marked channel grows, an
unmarked one stays exactly 0), so the margin holds through the layers;
the reference reports it. The correction bias is ``-1`` for the outputs
with ``e % 48 == 37`` and 0 elsewhere: a marked output with that bias falls
under every unmarked one and is replaced by the lowest-indexed unmarked,
unbiased output (all tied; ties go to the lower id here and in the
program), which changes a choice for about one token in four, by a margin
no rounding crosses.
"""

from __future__ import annotations

import math
from pathlib import Path

import numpy as np

from arch import mla_moe
from reference import Layer, rms_norm, score_pairs, swiglu
from shapes import PLAIN_BYTES, expected_experts, linear_bytes
from weights import (Checkpoint, File, hf_config, linear, norm, plain, rngs,
                     small, write_files)

WRITER_VERSION = 1  # part of the key a checkpoint's directory is named by
REFERENCE_VERSION = 1  # part of the key under which answers are kept

# keys of a configuration file that are the model's own config.json (what
# the server reads); everything else in the file is the benchmark's
HF_KEYS = (
    "architectures", "model_type", "attention_bias", "attention_method",
    "vocab_size", "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
    "num_layers", "num_attention_heads", "kv_lora_rank", "q_lora_rank",
    "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim", "mla_scale_q_lora",
    "mla_scale_kv_lora", "routed_scaling_factor", "n_routed_experts",
    "max_position_embeddings", "rms_norm_eps", "rope_theta",
    "zero_expert_num", "zero_expert_type", "moe_topk", "tie_word_embeddings",
    "expert_share", "bos_token_id", "eos_token_id",
)

MODEL_TYPE = "longcat_flash"
ROUTE_MARK = 128.0  # what the embedding writes into a token's routing channels
ROUTER_GAIN = 0.1875  # the router's row e reads channel e with this weight
SUPPRESSED = (48, 37)  # the bias is -1 where e % 48 == 37, else 0
REFERENCE_BLOCK = 256  # query rows the reference attends at a time


def require_family(checkout: Path) -> None:
    """Refuse a checkout whose program does not name this family, at once
    (``arch/looped_gqa.py`` says why a guard stands here). A program from
    before the family reads ``model_type`` "longcat_flash" as the bare
    grouped-query stack, meets ``kv_lora_rank`` and refuses the file only
    after a checkpoint of ten gigabytes is written. Such a checkout cannot
    run this configuration, and a run on it fails here, before a
    checkpoint is written, and measures nothing under the cell's name.
    Asked of the source: the parent of a chip run imports neither JAX nor
    ``cake_tpu``."""
    models = checkout / "cake_tpu" / "models"
    if not any(f'"{MODEL_TYPE}"' in path.read_text()
               for path in sorted(models.glob("*.py"))):
        raise RuntimeError(
            f"no module under {models} declares model_type {MODEL_TYPE!r}: "
            "this program has no shortcut-connected double layer (two latent "
            "attentions and two dense feed-forwards a layer, two cache "
            "planes, zero-compute experts in the router: "
            "cake_tpu/models/llama.py _double_block); the cell needs it")


require_family(Path(__file__).resolve().parents[2])


# -- sizes -----------------------------------------------------------------------

held_experts = mla_moe.held_experts  # global ids of the experts held here


def real_experts(cfg: dict) -> int:
    """The router's outputs that are experts (the published count)."""
    return mla_moe.router_width(cfg)


def router_outputs(cfg: dict) -> int:
    """All the router scores: the experts and the zero-compute outputs."""
    return real_experts(cfg) + cfg.get("zero_expert_num", 0)


def expert_layers(cfg: dict) -> int:
    """Layers that route: every double layer holds one expert block."""
    return cfg["num_layers"]


def cache_planes(cfg: dict) -> int:
    """Planes of latent rows the cache keeps: two a double layer."""
    return 2 * cfg["num_layers"]


def _mlp(prefix: str, h: int, f: int) -> dict[str, tuple[int, int]]:
    return {f"{prefix}gate_proj.weight": (h, f),
            f"{prefix}up_proj.weight": (h, f),
            f"{prefix}down_proj.weight": (f, h)}


def attention_linears(cfg: dict, a: str) -> dict[str, tuple[int, int]]:
    """HF suffix -> (fan_in, out) of one latent attention under ``a``."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, dc, ql = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["q_lora_rank"]
    return {a + "q_a_proj.weight": (h, ql),
            a + "q_b_proj.weight": (ql, nh * (dn + dr)),
            a + "kv_a_proj_with_mqa.weight": (h, dc + dr),
            a + "kv_b_proj.weight": (dc, nh * (dn + dv)),
            a + "o_proj.weight": (nh * dv, h)}


def layer_linears(cfg: dict) -> dict[str, tuple[int, int]]:
    """HF suffix -> (fan_in, out) of a double layer's linears."""
    h = cfg["hidden_size"]
    lin = {}
    for j in (0, 1):
        lin.update(attention_linears(cfg, f"self_attn.{j}."))
        lin.update(_mlp(f"mlps.{j}.", h, cfg["ffn_hidden_size"]))
    for e in held_experts(cfg):
        lin.update(_mlp(f"mlp.experts.{e}.", h, cfg["expert_ffn_hidden_size"]))
    return lin


def _plain_values(cfg: dict) -> int:
    """Unquantized values of a double layer: its eight norms, its router
    and the router's bias."""
    outputs = router_outputs(cfg)
    return (4 * cfg["hidden_size"] + 2 * cfg["q_lora_rank"]
            + 2 * cfg["kv_lora_rank"] + outputs * cfg["hidden_size"]
            + outputs)


def checkpoint_bytes(cfg: dict, layout: str) -> int:
    """Bytes the checkpoint will take on disk (to see that it fits)."""
    per = 1 if layout == "q8" else 2
    unq = 4 if layout == "q8" else 2  # an unquantized tensor's bytes
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    total = v * h * unq + h * unq + v * h * per + (
        4 * v if layout == "q8" else 0)
    a_layer = _plain_values(cfg) * unq + sum(
        a * b * per + (4 * b if layout == "q8" else 0)
        for a, b in layer_linears(cfg).values())
    return total + cfg["num_layers"] * a_layer


def cache_token_bytes(cfg: dict, serve_dtype: str = "bf16") -> int:
    """Bytes the cache holds for one token of one stream: a latent row a
    plane."""
    return (cache_planes(cfg) * mla_moe.cache_row_values(cfg)
            * PLAIN_BYTES[serve_dtype])


# -- routing that rounding cannot flip ---------------------------------------------

def routing_channels(ids: np.ndarray, cfg: dict, seed: int) -> np.ndarray:
    """[len(ids), moe_topk] distinct channels (= router outputs) for each
    token id, drawn uniformly over ALL the router's outputs (experts and
    identities alike): the ``moe_topk`` smallest of a row of seeded
    uniforms."""
    outputs, k = router_outputs(cfg), cfg["moe_topk"]
    rng = np.random.Generator(np.random.SFC64([seed, 0x5C30E, outputs]))
    draws = rng.random((len(ids), outputs), dtype=np.float32)
    return np.argpartition(draws, k, axis=-1)[:, :k]


def routing_embed(embed: np.ndarray, cfg: dict, seed: int) -> None:
    """Give the first ``router_outputs`` channels of the embedding to the
    router."""
    ids = np.arange(embed.shape[0])
    embed[:, :router_outputs(cfg)] = 0.0
    for col in routing_channels(ids, cfg, seed).T:
        embed[ids, col] = ROUTE_MARK


def router_bias(cfg: dict) -> np.ndarray:
    """The correction bias: -1 for one output in forty-eight, else 0."""
    e = np.arange(router_outputs(cfg))
    every, at = SUPPRESSED
    return np.where(e % every == at, -1.0, 0.0).astype(np.float32)


# -- the checkpoint --------------------------------------------------------------

def write_checkpoint(cfg: dict, layout: str, seed: int, model_dir: Path,
                     workers: int = 8) -> dict:
    """Write the checkpoint of configuration ``cfg`` (a configuration
    file's dict) into ``model_dir``; returns {"bytes", "files"}."""
    if layout not in ("q8", "bf16"):
        raise ValueError(f"unknown checkpoint layout {layout!r}")
    model_dir.mkdir(parents=True, exist_ok=True)
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    layers, width = cfg["num_layers"], router_outputs(cfg)
    if width > h // 2:
        raise ValueError(f"{width} routing channels of {h}")

    def layer(i: int):
        f = File(model_dir / f"model-layer-{i:05d}.safetensors")
        r = rngs(seed, i)
        p = f"model.layers.{i}."
        for j in (0, 1):
            plain(f, layout, f"{p}input_layernorm.{j}.weight",
                  norm(next(r), h))
            plain(f, layout, f"{p}post_attention_layernorm.{j}.weight",
                  norm(next(r), h))
            plain(f, layout, f"{p}self_attn.{j}.q_a_layernorm.weight",
                  norm(next(r), cfg["q_lora_rank"]))
            plain(f, layout, f"{p}self_attn.{j}.kv_a_layernorm.weight",
                  norm(next(r), cfg["kv_lora_rank"]))
        # row e reads routing channel e alone
        plain(f, layout, p + "mlp.router.classifier.weight",
              np.eye(width, h, dtype=np.float32) * np.float32(ROUTER_GAIN))
        plain(f, layout, p + "mlp.router.e_score_correction_bias",
              router_bias(cfg))
        for suffix, (fan_in, out) in layer_linears(cfg).items():
            # each tensor's generator is named by its place in the layer of
            # the UNCUT model, so that a share's experts are the same
            # tensors whichever share holds them
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
        routing_embed(embed, cfg, seed)
        plain(f, layout, "model.embed_tokens.weight", embed)
        plain(f, layout, "model.norm.weight", norm(next(r), h))
        linear(f, next(r), layout, "lm_head.weight", h, v)
        return f.write()

    jobs = [ends] + [lambda i=i: layer(i) for i in range(layers)]
    return write_files(model_dir, layout, jobs, hf_config(cfg, HF_KEYS),
                       workers)


# -- the float32 reference -----------------------------------------------------

def _attention(cfg: dict, ck: Layer, a: str, x: np.ndarray) -> np.ndarray:
    """``MLA_j`` under ``a`` of one sequence, a block of query rows at a
    time; the two factors where the equations put them."""
    t, hidden = x.shape
    nh = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, dc = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    c_q = rms_norm(x @ ck.f32(a + "q_a_proj.weight").T,
                   ck.f32(a + "q_a_layernorm.weight"), eps)
    q = (c_q @ ck.f32(a + "q_b_proj.weight").T).reshape(t, nh, dn + dr)
    if cfg.get("mla_scale_q_lora"):
        q = q * np.float32((hidden / cfg["q_lora_rank"]) ** 0.5)
    ckv = x @ ck.f32(a + "kv_a_proj_with_mqa.weight").T
    c = rms_norm(ckv[:, :dc], ck.f32(a + "kv_a_layernorm.weight"), eps)
    if cfg.get("mla_scale_kv_lora"):
        c = c * np.float32((hidden / dc) ** 0.5)
    kv = (c @ ck.f32(a + "kv_b_proj.weight").T).reshape(t, nh, dn + dv)
    cos, sin = mla_moe.rope_angles({**cfg, "rope_scaling": None}, t)
    q = q.transpose(1, 0, 2)  # [H, t, dn + dr]
    q_pe = mla_moe.rope_pairs(np.ascontiguousarray(q[..., dn:]), cos, sin)
    k_pe = mla_moe.rope_pairs(np.ascontiguousarray(ckv[:, dc:]), cos, sin)
    k_nope = kv[:, :, :dn].transpose(1, 0, 2)
    v = kv[:, :, dn:].transpose(1, 0, 2)
    scale = np.float32((dn + dr) ** -0.5)
    out = np.empty((t, nh * dv), np.float32)
    for lo in range(0, t, REFERENCE_BLOCK):
        hi = min(lo + REFERENCE_BLOCK, t)
        scores = (q[:, lo:hi, :dn] @ k_nope[:, :hi].transpose(0, 2, 1)
                  + q_pe[:, lo:hi] @ k_pe[:hi].T[None]) * scale
        seen = np.arange(hi)[None, :] <= np.arange(lo, hi)[:, None]
        scores = np.where(seen[None], scores, np.float32(-np.inf))
        scores = scores - scores.max(-1, keepdims=True)
        w = np.exp(scores)
        w /= w.sum(-1, keepdims=True)
        out[lo:hi] = (w @ v[:, :hi]).transpose(1, 0, 2).reshape(
            hi - lo, nh * dv)
    return out @ ck.f32(a + "o_proj.weight").T


def route(cfg: dict, logits: np.ndarray, bias: np.ndarray):
    """``logits [t, outputs]`` -> (chosen ``[t, k]``, weights ``[t, k]``,
    margin ``[t]``): ``p`` softmax over all outputs in float32; the
    ``moe_topk`` of largest ``p + bias``, a tie to the lower id; the
    weights ``routed_scaling_factor p``, not renormalised. The margin is
    how far the last output chosen lies above the first one left out (on
    ``p + bias``), in units of the token's shares' spread."""
    k = cfg["moe_topk"]
    z = logits.astype(np.float32)
    z = np.exp(z - z.max(-1, keepdims=True))
    p = z / z.sum(-1, keepdims=True)
    choice = p + bias
    ranked = np.argsort(-choice, axis=-1, kind="stable")
    idx = ranked[:, :k]
    by_rank = np.take_along_axis(choice, ranked, -1)
    margin = (by_rank[:, k - 1] - by_rank[:, k]) / (p.std(-1) + 1e-9)
    w = np.take_along_axis(p, idx, -1)
    if cfg.get("norm_topk_prob", False):
        w = w / w.sum(-1, keepdims=True)
    return idx, w * np.float32(cfg.get("routed_scaling_factor", 1.0)), margin


def _experts(cfg: dict, ck: Layer, p: str, h: np.ndarray,
             margins: list) -> np.ndarray:
    """``MoE(h)``: the chosen experts HELD here and the identity part;
    ``margins`` gains each token's routing margin."""
    logits = h @ ck.f32(p + "mlp.router.classifier.weight").T
    idx, weight, margin = route(
        cfg, logits, ck.f32(p + "mlp.router.e_score_correction_bias"))
    margins.append(margin)
    out = np.zeros_like(h)
    for e in held_experts(cfg):
        rows, slot = np.nonzero(idx == e)
        if len(rows):
            q = f"{p}mlp.experts.{e}."
            out[rows] += weight[rows, slot][:, None] * swiglu(
                h[rows], ck.f32(q + "gate_proj.weight"),
                ck.f32(q + "up_proj.weight"), ck.f32(q + "down_proj.weight"))
    if cfg.get("zero_expert_type", "identity") != "identity":
        raise ValueError(cfg["zero_expert_type"])
    z = np.where(idx >= real_experts(cfg), weight, np.float32(0.0)).sum(-1)
    return out + z[:, None] * h  # a zero-compute expert returns its input


def _double_layer(cfg: dict, ck: Layer, p: str, x: np.ndarray,
                  margins: list) -> np.ndarray:
    eps = cfg["rms_norm_eps"]

    def normed(x, name):
        return rms_norm(x, ck.f32(f"{p}{name}.weight"), eps)

    def ffn(j, h):
        q = f"{p}mlps.{j}."
        return swiglu(h, ck.f32(q + "gate_proj.weight"),
                      ck.f32(q + "up_proj.weight"),
                      ck.f32(q + "down_proj.weight"))

    a0 = x + _attention(cfg, ck, p + "self_attn.0.",
                        normed(x, "input_layernorm.0"))
    h = normed(a0, "post_attention_layernorm.0")
    late = _experts(cfg, ck, p, h, margins)  # the shortcut: added below
    b0 = a0 + ffn(0, h)
    a1 = b0 + _attention(cfg, ck, p + "self_attn.1.",
                         normed(b0, "input_layernorm.1"))
    return a1 + ffn(1, normed(a1, "post_attention_layernorm.1")) + late


def chosen_logprobs(cfg: dict, model_dir, pairs: list[tuple]) -> list[dict]:
    """For each (prompt, chosen) pair: the log-probabilities the reference
    gives the ``chosen`` continuation of ``prompt``, token by token, and
    its own best token at each place (``reference.score_pairs``), given
    the same share of the experts as the server. A layer at a time and a
    block of query rows at a time, so that the published widths and a
    probe of several thousand tokens fit the host."""
    if cfg.get("attention_method", "MLA") != "MLA":
        raise ValueError(cfg["attention_method"])
    ck = Checkpoint(model_dir)
    embed = ck.f32("model.embed_tokens.weight")
    xs = [embed[np.asarray(list(prompt) + list(chosen[:-1]), np.int64)]
          for prompt, chosen in pairs]
    del embed
    margins: list[list] = [[] for _ in pairs]
    for i in range(cfg["num_layers"]):
        p, layer = f"model.layers.{i}.", Layer(ck)
        for n, x in enumerate(xs):
            xs[n] = _double_layer(cfg, layer, p, x, margins[n])
    return score_pairs(ck, cfg["rms_norm_eps"], pairs, xs, margins)


# -- bytes and operations ----------------------------------------------------------

def held_experts_hit(cfg: dict, rows: float) -> float:
    """How many of the experts held here some row is routed to, with
    ``rows`` rows each choosing ``moe_topk`` of ALL the router's outputs
    uniformly."""
    outputs = router_outputs(cfg)
    return (expected_experts(outputs, cfg["moe_topk"], rows)
            * cfg["n_routed_experts"] / outputs)


def weight_bytes(cfg: dict, layout: str, serve_dtype: str = "bf16",
                 rows: float | None = None) -> float:
    """Bytes of weights one decode step reads (``rows`` live streams: the
    non-expert weights once, of the HELD experts those some row is routed
    to, the routers, the head's slice), or with ``rows=None`` all the
    weights the device holds, embedding included: the number a parameter
    count checks."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    plain_b = PLAIN_BYTES[serve_dtype]
    held = cfg["n_routed_experts"]
    a_layer = _plain_values(cfg) * plain_b
    for suffix, (fan_in, out) in layer_linears(cfg).items():
        b = linear_bytes(fan_in, out, layout)
        if ".experts." in suffix and rows is not None:
            b *= held_experts_hit(cfg, rows) / held
        a_layer += b
    embed_rows = v if rows is None else rows
    return (cfg["num_layers"] * a_layer + embed_rows * h * plain_b
            + h * plain_b + linear_bytes(h, v, layout))


def kv_bytes(cfg: dict, context: float, rows: float,
             cache_dtype: str = "bf16") -> float:
    """Bytes of latent rows ``rows`` streams at a mean position of
    ``context`` read in one step: one row a token a PLANE, for all heads."""
    return (rows * context * cache_planes(cfg)
            * mla_moe.cache_row_values(cfg) * PLAIN_BYTES[cache_dtype])


def decode_step_bytes(cfg: dict, layout: str, rows: float, context: float,
                      serve_dtype: str = "bf16") -> float:
    """The least one decode step reads: the weights for ``rows`` live
    streams and their latent rows at a mean position of ``context``."""
    return (weight_bytes(cfg, layout, serve_dtype, rows)
            + kv_bytes(cfg, context, rows, serve_dtype))


def latent_trace_ops(cfg: dict) -> dict[str, str]:
    """Patterns (``re.match`` on a reduced trace's operation names) of the
    plain latent path's kernels, as the program names them: ``decode`` (a
    decode step's sweep of a plane's rows to each frontier: one call a
    plane and step), ``prefill`` (a blocked admission's own-chunk
    attention over the expanded keys: one call a plane and dispatch)."""
    return {"decode": r"latent_decode\b", "prefill": r"latent_prefill\b"}


def latent_decode_bytes(cfg: dict, rows_live: float,
                        cache_dtype: str = "bf16") -> float:
    """The least the latent decode kernel must read: the latent row ``[c |
    k_pe]`` of every (plane, step, row up to a stream's frontier),
    ``rows_live`` of them in all, once. (The kernel reads whole blocks of
    rows: the share reads under 100 by as much.)"""
    return rows_live * mla_moe.cache_row_values(cfg) * PLAIN_BYTES[cache_dtype]


def latent_prefill_flops(cfg: dict, pairs: float) -> float:
    """Operations of a blocked admission's own-chunk attention at the rows'
    TRUE lengths and the heads' TRUE widths (a score of ``nope + rope``
    channels and a value product of ``v_head_dim`` for every head and
    causal pair; the kernel's padding channels and a bucket's padding rows
    are not counted), ``pairs`` (plane, query row, row at or before it)
    pairs in all."""
    return pairs * 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def prefill_pairs(length: int) -> int:
    """Causal pairs of ONE plane for a prompt of ``length`` rows."""
    return length * (length + 1) // 2


def latent_prefill_pairs_handed(op_name: str) -> int:
    """Causal pairs one call of the operation ``op_name`` (a reduced
    trace's ``<HLO name> <type>[<rows>,<heads>,<T>,<width>]``: the
    kernel's result) was handed: ``rows x T (T + 1) / 2``, the bucket's
    padding included; 0 for a name that carries no such shape."""
    import re

    shape = re.search(r"\[(\d+),\d+,(\d+),\d+\]", op_name)
    if not shape:
        return 0
    rows, t = int(shape.group(1)), int(shape.group(2))
    return rows * prefill_pairs(t)
