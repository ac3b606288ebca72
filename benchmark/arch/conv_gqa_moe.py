"""Architecture ``conv_gqa_moe``: a decoder whose layers are gated short
convolutions (``layer_types[i] == "conv"``: ``[B | C | x] = u W_in``, ``y =
conv(B * x)`` causal and depthwise over ``conv_L_cache`` taps with NO
activation, ``out = (C * y) W_out``) but some, which are grouped-query
attention over every earlier token (``"full_attention"``: each head of q
and k RMS-normed over its ``hidden / heads`` channels, then BOTH rotated);
``num_dense_layers`` leading dense SwiGLU layers, then expert layers of
``num_experts`` sigmoid-scored routed experts, ALL of them held, whose
CHOICE of ``num_experts_per_tok`` is corrected by a bias an expert and
whose weights are the chosen scores over their sum ``+ 1e-6``; no shared
expert; a tied head behind ``embedding_norm``: LFM2-8B-A1B's
``config.json`` keys (``model_type`` ``lfm2_moe``).

Numpy and the standard library only (the parent of a chip run never
imports JAX). What this family shares with ``mla_moe`` and ``kda_mla_moe``
(the routing channels, the bias that changes a choice by a margin no
rounding crosses, the generator a tensor is drawn from) is taken from those
modules, loaded by path. The writer puts the tensors under the names the
program's loader reads; they are ASSUMED (the configuration's
``assumed.tensor_names``): Hugging Face's Lfm2Moe modules. The head is the
embedding: the file holds that matrix under both names, as the program's
loader holds it on the device (an embedding to gather from and a head to
multiply by), as ``arch/mamba_gqa.py``'s does.

The reference is written from the equations ISSUE 43 states (Motivation):
pre-norm sublayers, the whole sequence at once, no cache and no tail: the
convolution is the sum over ``conv_L_cache`` shifted copies of ``B * x``
from zeros before the first token (``_short_conv``), attention the scores
under the explicit causal mask, taken a block of ``QUERY_ROWS`` query rows
and a key/value head at a time so that 1500 tokens fit the host
(``_attention``), the experts a loop over all of them (``_feed_forward``).

What the cache holds and a step reads: an ATTENTION layer keeps every row
and a step reads ``context`` of them (``kv_bytes``: the LIVE rows, as the
other architectures count them; a program that sweeps the whole
reservation pays for it in its share of the roofline); a CONV layer keeps
``conv_L_cache - 1`` values of ``B * x`` a channel a stream whatever its
length and no row (``state_bytes_per_stream``), read and written once a
step.

A random router must not hang on rounding (``weights.py`` says why), and
the bias must CHANGE choices without hanging on rounding either: the first
``num_experts`` channels of the residual stream belong to the router (the
embedding marks ``num_experts_per_tok`` of them per token id, no linear
writes to them, the router's row ``e`` reads channel ``e`` alone); the bias
is ``kda_mla_moe``'s (``-1`` where ``e % 16 == 5`` and ``0`` elsewhere), so
a marked expert so biased gives way to the lowest-indexed unmarked,
unbiased one, tied at exactly ``1/2``, which enters with its own score as
its weight. The head is the embedding, whose routing channels would
otherwise vote for the tokens that share the input's marks: the last
norm's weight is ZERO on those channels, so that the logits are the seeded
part's alone.
"""

from __future__ import annotations

import importlib.util
import math
import sys
from pathlib import Path

import numpy as np

from reference import Layer, rms_norm, rope, score_pairs, swiglu
from shapes import PLAIN_BYTES, linear_bytes
from weights import (Checkpoint, File, bf16_bits, hf_config, linear, norm,
                     plain, pow2_scale, rngs, small, write_files)


def _sibling(name: str):
    """``arch/<name>.py``, loaded by path as the harness loads this file."""
    key = f"bench_arch_{name}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, Path(__file__).with_name(f"{name}.py"))
        sys.modules[key] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[key])
    return sys.modules[key]


_mla = _sibling("mla_moe")
_kda = _sibling("kda_mla_moe")

WRITER_VERSION = 1  # part of the key a checkpoint's directory is named by
REFERENCE_VERSION = 1  # part of the key under which answers are kept

# keys of a configuration file that are the model's own config.json (what
# the server reads); everything else in the file is the benchmark's
HF_KEYS = (
    "architectures", "model_type", "conv_L_cache", "conv_bias",
    "hidden_size", "intermediate_size", "layer_types",
    "max_position_embeddings", "moe_intermediate_size", "norm_eps",
    "norm_topk_prob", "num_attention_heads", "num_dense_layers",
    "num_experts", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "rope_theta", "routed_scaling_factor",
    "use_expert_bias", "vocab_size", "tie_word_embeddings", "torch_dtype",
    "bos_token_id", "eos_token_id",
)

QUERY_ROWS = 512  # query rows a block of the reference's scores
TOPK_EPS = 1e-6  # what the chosen scores are normalised over, beside their sum


# -- sizes -----------------------------------------------------------------------

def _as_mla(cfg: dict) -> dict:
    """The configuration under the keys ``mla_moe``'s helpers read."""
    return dict(cfg, n_routed_experts=cfg.get("num_experts", 0),
                n_shared_experts=0,
                first_k_dense_replace=cfg.get("num_dense_layers", 0))


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def held_experts(cfg: dict) -> range:
    """Global ids of the experts held here: all of them."""
    return range(cfg.get("num_experts", 0))


def is_conv_layer(cfg: dict, i: int) -> bool:
    return cfg["layer_types"][i] == "conv"


def is_expert_layer(cfg: dict, i: int) -> bool:
    return bool(cfg.get("num_experts")) and i >= cfg.get(
        "num_dense_layers", 0)


def expert_layers(cfg: dict) -> int:
    return sum(is_expert_layer(cfg, i)
               for i in range(cfg["num_hidden_layers"]))


def conv_layers(cfg: dict) -> int:
    return sum(is_conv_layer(cfg, i)
               for i in range(cfg["num_hidden_layers"]))


def cache_row_values(cfg: dict) -> int:
    """Values an attention layer's cache holds for one token: keys and
    values of every key/value head."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg)


def state_bytes_per_stream(cfg: dict, cache_dtype: str = "bf16") -> int:
    """Bytes a stream holds whatever its length: the last ``conv_L_cache -
    1`` inputs of the convolution, a conv layer; no state."""
    return (conv_layers(cfg) * (cfg["conv_L_cache"] - 1)
            * cfg["hidden_size"] * PLAIN_BYTES[cache_dtype])


def _mlp(prefix: str, h: int, f: int) -> dict[str, tuple[int, int]]:
    return {f"{prefix}w1.weight": (h, f), f"{prefix}w3.weight": (h, f),
            f"{prefix}w2.weight": (f, h)}


def layer_linears(cfg: dict, i: int) -> dict[str, tuple[int, int]]:
    """HF suffix -> (fan_in, out) of layer ``i``'s linears."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    if is_conv_layer(cfg, i):
        lin = {"conv.in_proj.weight": (h, 3 * h),
               "conv.out_proj.weight": (h, h)}
    else:
        a = "self_attn."
        lin = {a + "q_proj.weight": (h, nh * d),
               a + "k_proj.weight": (h, nkv * d),
               a + "v_proj.weight": (h, nkv * d),
               a + "out_proj.weight": (nh * d, h)}
    if is_expert_layer(cfg, i):
        for e in held_experts(cfg):
            lin.update(_mlp(f"feed_forward.experts.{e}.", h,
                            cfg["moe_intermediate_size"]))
    else:
        lin.update(_mlp("feed_forward.", h, cfg["intermediate_size"]))
    return lin


def _plain_values(cfg: dict, i: int) -> int:
    """Unquantized values of layer ``i``: its two norms, a conv layer's
    taps or an attention layer's q and k norms, the router and its
    bias."""
    h = cfg["hidden_size"]
    n = 2 * h + (cfg["conv_L_cache"] * h if is_conv_layer(cfg, i)
                 else 2 * head_dim(cfg))
    if is_expert_layer(cfg, i):
        n += cfg["num_experts"] * (h + 1)
    return n


def checkpoint_bytes(cfg: dict, layout: str) -> int:
    """Bytes the checkpoint will take on disk (to see that it fits): the
    tied matrix under both its names."""
    per = 1 if layout == "q8" else 2
    unq = 4 if layout == "q8" else 2  # an unquantized tensor's bytes
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    total = v * h * unq + h * unq + v * h * per + (
        4 * v if layout == "q8" else 0)
    for i in range(cfg["num_hidden_layers"]):
        total += _plain_values(cfg, i) * unq + sum(
            a * b * per + (4 * b if layout == "q8" else 0)
            for a, b in layer_linears(cfg, i).values())
    return total


# -- the checkpoint --------------------------------------------------------------

def router_bias(cfg: dict) -> np.ndarray:
    """The correction bias: -1 for one expert in sixteen, else 0."""
    return _kda.router_bias(_as_mla(cfg))


def write_checkpoint(cfg: dict, layout: str, seed: int, model_dir: Path,
                     workers: int = 8) -> dict:
    """Write the checkpoint of configuration ``cfg`` (a configuration
    file's dict) into ``model_dir``; returns {"bytes", "files"}."""
    if layout not in ("q8", "bf16"):
        raise ValueError(f"unknown checkpoint layout {layout!r}")
    model_dir.mkdir(parents=True, exist_ok=True)
    h, v, d = cfg["hidden_size"], cfg["vocab_size"], head_dim(cfg)
    layers, taps = cfg["num_hidden_layers"], cfg["conv_L_cache"]
    width = cfg["num_experts"] if expert_layers(cfg) else 0
    if width > h // 2:
        raise ValueError(f"{width} routing channels of {h}")

    def layer(i: int):
        f = File(model_dir / f"model-layer-{i:05d}.safetensors")
        r = rngs(seed, i)
        p = f"model.layers.{i}."
        plain(f, layout, p + "operator_norm.weight", norm(next(r), h))
        plain(f, layout, p + "ffn_norm.weight", norm(next(r), h))
        if is_conv_layer(cfg, i):  # torch depthwise conv1d: [C, 1, K]
            plain(f, layout, p + "conv.conv.weight",
                  small(next(r), (h, 1, taps), 0.5))
        else:
            plain(f, layout, p + "self_attn.q_layernorm.weight",
                  norm(next(r), d))
            plain(f, layout, p + "self_attn.k_layernorm.weight",
                  norm(next(r), d))
        if is_expert_layer(cfg, i):  # row e reads routing channel e alone
            plain(f, layout, p + "feed_forward.gate.weight",
                  np.eye(width, h, dtype=np.float32))
            plain(f, layout, p + "feed_forward.expert_bias",
                  router_bias(cfg))
        for suffix, (fan_in, out) in layer_linears(cfg, i).items():
            writes_residual = suffix.endswith(("out_proj.weight",
                                               "w2.weight"))
            linear(f, _mla._tensor_rng(seed, i, suffix), layout, p + suffix,
                   fan_in, out, zero_rows=width if writes_residual else 0)
        return f.write()

    def ends():
        f = File(model_dir / "model-ends.safetensors")
        r = rngs(seed, layers)
        base = pow2_scale(1.0 / math.sqrt(h))
        embed = small(next(r), (v, h), 1.0 / math.sqrt(h))
        head = embed.copy()
        head[:, :width] = 0.0
        if width:
            _mla.routing_embed(embed, _as_mla(cfg))
        plain(f, layout, "model.embed_tokens.weight", embed)
        last = norm(next(r), h)
        last[:width] = 0.0  # the tied head reads no routing channel
        plain(f, layout, "model.embedding_norm.weight", last)
        # the tied matrix again, as a head, as the program holds it on the
        # device (the loader, told the head is tied, reads the embedding;
        # jamba2-3b's file does the same): the same values but on the
        # routing channels, whose marks no int8 times the base holds and
        # which the last norm's zero weight silences: left zero there
        if layout == "q8":
            f.add("lm_head.weight.q8", "I8", (v, h),
                  np.round(head / np.float32(base)).astype(np.int8))
            f.add("lm_head.weight.scale", "F32", (v,),
                  np.full((v,), base, np.float32))
        else:
            f.add("lm_head.weight", "BF16", (v, h), bf16_bits(head))
        return f.write()

    jobs = [ends] + [lambda i=i: layer(i) for i in range(layers)]
    return write_files(model_dir, layout, jobs, hf_config(cfg, HF_KEYS),
                       workers)


# -- the float32 reference -----------------------------------------------------

def _sigmoid(x: np.ndarray) -> np.ndarray:
    return (1.0 / (1.0 + np.exp(-x))).astype(np.float32)


def _short_conv(cfg: dict, ck: Layer, p: str, u: np.ndarray) -> np.ndarray:
    """Layer ``p``'s gated short convolution over one whole sequence: the
    sum over the taps of shifted copies of ``B * x``, zeros before the
    first token, no activation."""
    t, h = u.shape
    taps = cfg["conv_L_cache"]
    gate_in, gate_out, x = np.split(
        u @ ck.f32(p + "conv.in_proj.weight").T, 3, axis=-1)
    w = ck.f32(p + "conv.conv.weight")[:, 0, :]  # [C, K]
    padded = np.concatenate([np.zeros((taps - 1, h), np.float32),
                             gate_in * x])
    y = sum(padded[j:j + t] * w[:, j] for j in range(taps))
    return (gate_out * y) @ ck.f32(p + "conv.out_proj.weight").T


def _attention(cfg: dict, ck: Layer, p: str, u: np.ndarray) -> np.ndarray:
    """Layer ``p``'s attention over one whole sequence: q and k normed a
    head and then rotated, scores under the explicit causal mask, a block
    of query rows and a key/value head at a time."""
    t = u.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    a = p + "self_attn."

    def heads(name: str, n: int, normed: bool) -> np.ndarray:
        y = (u @ ck.f32(a + f"{name}_proj.weight").T).reshape(t, n, d)
        if normed:
            y = rms_norm(y, ck.f32(a + f"{name}_layernorm.weight"),
                         cfg["norm_eps"])
        return np.ascontiguousarray(y.transpose(1, 0, 2))  # [n, t, d]

    theta = float(cfg["rope_theta"])
    q, k = rope(heads("q", nh, True), theta), rope(heads("k", nkv, True),
                                                   theta)
    v = heads("v", nkv, False)
    g = nh // nkv
    out = np.empty((t, nh, d), np.float32)
    at = np.arange(t)
    for lo in range(0, t, QUERY_ROWS):
        rows = at[lo:lo + QUERY_ROWS]
        seen = at[None, :] <= rows[:, None]
        for kh in range(nkv):
            s = (q[kh * g:(kh + 1) * g, rows] @ k[kh].T) * np.float32(
                d ** -0.5)  # [g, rows, t]
            s = np.where(seen[None], s, np.float32(-np.inf))
            s = s - s.max(-1, keepdims=True)
            w = np.exp(s)
            w /= w.sum(-1, keepdims=True)
            out[rows, kh * g:(kh + 1) * g] = (w @ v[kh]).transpose(1, 0, 2)
    return out.reshape(t, nh * d) @ ck.f32(a + "out_proj.weight").T


def route(cfg: dict, scores: np.ndarray, bias: np.ndarray):
    """``scores [t, E]`` (sigmoid), ``bias [E]`` -> (chosen ``[t, k]``,
    weights ``[t, k]``, margin ``[t]``): the choice is made on ``scores +
    bias``, the weights are the chosen experts' own scores over their sum
    (``+ 1e-6``) times ``routed_scaling_factor``. Ties go to the lower
    index. The margin is how far the last expert chosen lies above the
    first one left out (or, where the two tie exactly and the index
    decides, how far the nearest other corrected score lies from the tied
    level), in units of the token's scores' spread."""
    k = cfg["num_experts_per_tok"]
    choice = scores + bias
    ranked = np.argsort(-choice, axis=-1, kind="stable")
    idx = ranked[:, :k]
    by_rank = np.take_along_axis(choice, ranked, -1)
    last, out = by_rank[:, k - 1], by_rank[:, k]
    below = np.where(by_rank < last[:, None], by_rank,
                     np.float32(-np.inf)).max(-1)
    above = np.where(by_rank > last[:, None], by_rank,
                     np.float32(np.inf)).min(-1)
    gap = np.where(last == out, np.minimum(last - below, above - last),
                   last - out)
    margin = gap / (scores.std(-1) + 1e-9)
    w = np.take_along_axis(scores, idx, -1)
    if cfg.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + np.float32(TOPK_EPS))
    return idx, w * np.float32(cfg.get("routed_scaling_factor", 1.0)), margin


def _feed_forward(cfg: dict, ck: Layer, p: str, i: int, m: np.ndarray,
                  margins: list) -> np.ndarray:
    """Layer ``i``'s feed-forward block: a dense SwiGLU, or the sum over
    the chosen experts of ``w_e expert_e(m)``, a loop over all of them;
    ``margins`` gains each token's routing margin."""
    def mlp(prefix: str, rows: np.ndarray) -> np.ndarray:
        return swiglu(rows, ck.f32(prefix + "w1.weight"),
                      ck.f32(prefix + "w3.weight"),
                      ck.f32(prefix + "w2.weight"))

    f = p + "feed_forward."
    if not is_expert_layer(cfg, i):
        return mlp(f, m)
    logits = m @ ck.f32(f + "gate.weight").T  # [t, E]
    bias = (ck.f32(f + "expert_bias") if cfg.get("use_expert_bias")
            else np.zeros(logits.shape[1], np.float32))
    idx, weight, margin = route(cfg, _sigmoid(logits), bias)
    margins.append(margin)
    out = np.zeros_like(m)
    for e in held_experts(cfg):
        rows, slot = np.nonzero(idx == e)
        if len(rows):
            out[rows] += weight[rows, slot][:, None] * mlp(
                f"{f}experts.{e}.", m[rows])
    return out


class _Ends:
    """The checkpoint under the names ``reference.score_pairs`` asks for:
    this family's last norm is ``model.embedding_norm``, and its head is
    the embedding."""

    NAMES = {"model.norm.weight": "model.embedding_norm.weight",
             "lm_head.weight": "model.embed_tokens.weight"}

    def __init__(self, ck: Checkpoint):
        self.ck = ck

    def f32(self, name: str) -> np.ndarray:
        return self.ck.f32(self.NAMES.get(name, name))


def chosen_logprobs(cfg: dict, model_dir, pairs: list[tuple]) -> list[dict]:
    """For each (prompt, chosen) pair: the log-probabilities the reference
    gives the ``chosen`` continuation of ``prompt``, token by token, and
    its own best token at each place (``reference.score_pairs``). A layer
    at a time, so that the published widths fit the host."""
    ck = Checkpoint(model_dir)
    eps = cfg["norm_eps"]
    embed = ck.f32("model.embed_tokens.weight")
    xs = [embed[np.asarray(list(prompt) + list(chosen[:-1]), np.int64)]
          for prompt, chosen in pairs]
    del embed
    margins: list[list] = [[] for _ in pairs]
    for i in range(cfg["num_hidden_layers"]):
        p, layer = f"model.layers.{i}.", Layer(ck)
        mixer = _short_conv if is_conv_layer(cfg, i) else _attention
        for n, x in enumerate(xs):
            x = x + mixer(cfg, layer, p, rms_norm(
                x, layer.f32(p + "operator_norm.weight"), eps))
            xs[n] = x + _feed_forward(cfg, layer, p, i, rms_norm(
                x, layer.f32(p + "ffn_norm.weight"), eps), margins[n])
    return score_pairs(_Ends(ck), eps, pairs, xs, margins)


# -- bytes a decode step must move ---------------------------------------------

def held_experts_hit(cfg: dict, rows: float) -> float:
    """How many of the experts some row is routed to (every expert is
    held here)."""
    return _mla.held_experts_hit(_as_mla(cfg), rows)


def weight_bytes(cfg: dict, layout: str, serve_dtype: str = "bf16",
                 rows: float | None = None) -> float:
    """Bytes of weights one decode step reads (``rows`` live streams: the
    non-expert weights once, of the experts those some row is routed to,
    the routers, the head once, an embedding row a stream: the tied matrix
    is read once, as the head), or with ``rows=None`` all the weights the
    device holds, the tied matrix as the program holds it (an embedding
    and a head): the number a parameter count checks."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    plain_b = PLAIN_BYTES[serve_dtype]
    held = cfg.get("num_experts") or 0
    total = 0.0
    for i in range(cfg["num_hidden_layers"]):
        total += _plain_values(cfg, i) * plain_b
        for suffix, (fan_in, out) in layer_linears(cfg, i).items():
            b = linear_bytes(fan_in, out, layout)
            if ".experts." in suffix and rows is not None:
                b *= held_experts_hit(cfg, rows) / held
            total += b
    embed_rows = v if rows is None else rows
    return (total + embed_rows * h * plain_b + h * plain_b
            + linear_bytes(h, v, layout))


def state_bytes(cfg: dict, rows: float, cache_dtype: str = "bf16") -> float:
    """Bytes of convolution tail ``rows`` streams move in one step: every
    conv layer's tail read and written once."""
    return 2.0 * rows * state_bytes_per_stream(cfg, cache_dtype)


def kv_bytes(cfg: dict, context: float, rows: float,
             cache_dtype: str = "bf16") -> float:
    """Bytes of keys and values ``rows`` streams at a mean position of
    ``context`` read in one step: the LIVE rows of the ATTENTION layers
    alone."""
    attention = cfg["num_hidden_layers"] - conv_layers(cfg)
    return (rows * context * attention * cache_row_values(cfg)
            * PLAIN_BYTES[cache_dtype])


def decode_step_bytes(cfg: dict, layout: str, rows: float, context: float,
                      serve_dtype: str = "bf16") -> float:
    """The least one decode step moves: the weights for ``rows`` live
    streams (the tied matrix once), their keys and values at a mean
    position of ``context``, and their convolution tails once in and once
    out."""
    return (weight_bytes(cfg, layout, serve_dtype, rows)
            + kv_bytes(cfg, context, rows, serve_dtype)
            + state_bytes(cfg, rows, serve_dtype))
