"""Architecture ``gqa``: a dense grouped-query decoder with an optional
sliding window (Mistral-7B-v0.1), and the same decoder with Mixtral-style
sparse experts (Mixtral-8x7B-v0.1): the router's top-k over all experts
with a softmax over the selected logits. A configuration file names it
under ``bench.arch``; ``run.load_arch`` finds this file by that name, and
the harness uses of it what README.md lists under "Adding an
architecture" and nothing else.

Numpy and the standard library only (the parent of a chip run never
imports JAX). The writer puts the tensors under the names the program's
loader reads: Mixtral's experts under
``block_sparse_moe.experts.{e}.w1|w2|w3.weight``, the router under
``block_sparse_moe.gate.weight``. The reference is written from the
published descriptions, as Hugging Face transformers implements them, not
from the program: grouped-query causal attention with rotary embeddings
and the sliding window, SwiGLU, top-k routing. What it shares with other
architectures it imports from ``weights``, ``reference`` and ``shapes``.
"""

from __future__ import annotations

import math
from pathlib import Path

import numpy as np

from reference import Layer, rms_norm, rope, score_pairs, swiglu
from shapes import PLAIN_BYTES, expected_experts, linear_bytes
from weights import (Checkpoint, File, hf_config, linear, norm, plain,
                     rngs, routing_embed, small, write_files)

WRITER_VERSION = 2  # part of the key a checkpoint's directory is named by
REFERENCE_VERSION = 3  # part of the key under which answers are kept

# keys of a configuration file that are the model's own config.json (what
# the server reads); everything else in the file is the benchmark's
HF_KEYS = (
    "architectures", "model_type", "vocab_size", "hidden_size",
    "intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "hidden_act",
    "max_position_embeddings", "rms_norm_eps", "rope_theta",
    "sliding_window", "tie_word_embeddings", "num_local_experts",
    "num_experts_per_tok", "bos_token_id", "eos_token_id",
    "attention_bias",
)


# -- the checkpoint ------------------------------------------------------------

def layer_linears(cfg: dict) -> dict[str, tuple[int, int]]:
    """HF suffix -> (fan_in, out) of one layer's linears."""
    h = cfg["hidden_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    f = cfg["intermediate_size"]
    lin = {"self_attn.q_proj.weight": (h, q),
           "self_attn.k_proj.weight": (h, kv),
           "self_attn.v_proj.weight": (h, kv),
           "self_attn.o_proj.weight": (q, h)}
    experts = cfg.get("num_local_experts") or 0
    if experts:
        for e in range(experts):
            p = f"block_sparse_moe.experts.{e}"
            lin[f"{p}.w1.weight"] = (h, f)
            lin[f"{p}.w3.weight"] = (h, f)
            lin[f"{p}.w2.weight"] = (f, h)
    else:
        lin["mlp.gate_proj.weight"] = (h, f)
        lin["mlp.up_proj.weight"] = (h, f)
        lin["mlp.down_proj.weight"] = (f, h)
    return lin


def checkpoint_bytes(cfg: dict, layout: str) -> int:
    """Bytes the checkpoint will take on disk (to see that it fits)."""
    per = 1 if layout == "q8" else 2
    unq = 4 if layout == "q8" else 2  # an unquantized tensor's bytes
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    layer = sum(i * o * per + (4 * o if layout == "q8" else 0)
                for i, o in layer_linears(cfg).values())
    layer += 2 * h * unq + (cfg.get("num_local_experts") or 0) * h * unq
    head = v * h * per + (4 * v if layout == "q8" else 0)
    return cfg["num_hidden_layers"] * layer + v * h * unq + h * unq + head


def write_checkpoint(cfg: dict, layout: str, seed: int, model_dir: Path,
                     workers: int = 8) -> dict:
    """Write the checkpoint of configuration ``cfg`` (a configuration
    file's dict) into ``model_dir``; returns {"bytes", "files"}."""
    if layout not in ("q8", "bf16"):
        raise ValueError(f"unknown checkpoint layout {layout!r}")
    model_dir.mkdir(parents=True, exist_ok=True)
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    layers = cfg["num_hidden_layers"]
    experts = cfg.get("num_local_experts") or 0
    linears = layer_linears(cfg)

    def layer(i: int):
        f = File(model_dir / f"model-layer-{i:05d}.safetensors")
        r = rngs(seed, i)
        p = f"model.layers.{i}."
        plain(f, layout, p + "input_layernorm.weight", norm(next(r), h))
        plain(f, layout, p + "post_attention_layernorm.weight",
              norm(next(r), h))
        if experts:  # row e reads routing channel e alone
            plain(f, layout, p + "block_sparse_moe.gate.weight",
                  np.eye(experts, h, dtype=np.float32))
        for suffix, (fan_in, out) in linears.items():
            writes_residual = suffix.endswith(("o_proj.weight", "w2.weight"))
            linear(f, next(r), layout, p + suffix, fan_in, out,
                   zero_rows=experts if writes_residual else 0)
        return f.write()

    def ends():
        f = File(model_dir / "model-ends.safetensors")
        r = rngs(seed, layers)
        embed = small(next(r), (v, h), 1.0 / math.sqrt(h))
        if experts:
            routing_embed(embed, experts, cfg["num_experts_per_tok"])
        plain(f, layout, "model.embed_tokens.weight", embed)
        plain(f, layout, "model.norm.weight", norm(next(r), h))
        linear(f, next(r), layout, "lm_head.weight", h, v)
        return f.write()

    jobs = [ends] + [lambda i=i: layer(i) for i in range(layers)]
    return write_files(model_dir, layout, jobs, hf_config(cfg, HF_KEYS),
                       workers)


# -- the float32 reference -----------------------------------------------------

def _attention(cfg: dict, ck: Layer, p: str, x: np.ndarray):
    t = x.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // nh
    q = (x @ ck.f32(p + "self_attn.q_proj.weight").T).reshape(t, nh, d)
    k = (x @ ck.f32(p + "self_attn.k_proj.weight").T).reshape(t, nkv, d)
    v = (x @ ck.f32(p + "self_attn.v_proj.weight").T).reshape(t, nkv, d)
    q = rope(q.transpose(1, 0, 2), cfg["rope_theta"])
    k = rope(k.transpose(1, 0, 2), cfg["rope_theta"])
    v = v.transpose(1, 0, 2)
    k = np.repeat(k, nh // nkv, axis=0)  # kv head g serves q heads g*r..
    v = np.repeat(v, nh // nkv, axis=0)
    scores = q @ k.transpose(0, 2, 1) / np.float32(np.sqrt(d))
    qi, ki = np.arange(t)[:, None], np.arange(t)[None, :]
    ok = ki <= qi
    window = cfg.get("sliding_window")
    if window:
        ok &= ki > qi - window
    scores = np.where(ok[None], scores, np.float32(-np.inf))
    scores = scores - scores.max(-1, keepdims=True)
    w = np.exp(scores)
    w /= w.sum(-1, keepdims=True)
    out = (w @ v).transpose(1, 0, 2).reshape(t, nh * d)
    return out @ ck.f32(p + "self_attn.o_proj.weight").T


def _feed_forward(cfg: dict, ck: Layer, p: str, x: np.ndarray,
                  margins: list):
    """The layer's feed-forward block. For a sparse layer, ``margins``
    gains each token's routing margin: how far the last expert chosen
    lies above the first one left out, in units of that token's router
    logits' spread. Near 0 the choice hangs on rounding."""
    experts = cfg.get("num_local_experts") or 0
    if not experts:
        return swiglu(x, ck.f32(p + "mlp.gate_proj.weight"),
                      ck.f32(p + "mlp.up_proj.weight"),
                      ck.f32(p + "mlp.down_proj.weight"))
    top_k = cfg["num_experts_per_tok"]
    logits = x @ ck.f32(p + "block_sparse_moe.gate.weight").T  # [T, E]
    ranked = np.argsort(-logits, axis=-1, kind="stable")
    order = ranked[:, :top_k]
    by_rank = np.take_along_axis(logits, ranked, -1)
    margins.append((by_rank[:, top_k - 1] - by_rank[:, top_k])
                   / (logits.std(-1) + 1e-9))
    sel = np.take_along_axis(logits, order, -1)
    sel = np.exp(sel - sel.max(-1, keepdims=True))
    weight = sel / sel.sum(-1, keepdims=True)  # softmax over the selected
    out = np.zeros_like(x)
    for e in range(experts):
        rows, slot = np.nonzero(order == e)
        if not len(rows):
            continue
        q = f"{p}block_sparse_moe.experts.{e}."
        y = swiglu(x[rows], ck.f32(q + "w1.weight"), ck.f32(q + "w3.weight"),
                   ck.f32(q + "w2.weight"))
        out[rows] += weight[rows, slot][:, None] * y
    return out


def chosen_logprobs(cfg: dict, model_dir, pairs: list[tuple]) -> list[dict]:
    """For each (prompt, chosen) pair: the log-probabilities the
    reference gives the ``chosen`` continuation of ``prompt``, token by
    token, and its own best token at each place:
    ``{"logprob": [...], "best": [...], "best_logprob": [...],
    "routing_margin": [...]}``. The last is, for a sparse model, the
    smallest routing margin over the layers at the position each chosen
    token was predicted from (None for a dense model)."""
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
            xs[n] = x + _feed_forward(cfg, layer, p, rms_norm(
                x, layer.f32(p + "post_attention_layernorm.weight"), eps),
                margins[n])
    return score_pairs(ck, eps, pairs, xs, margins)


# -- bytes a decode step must read ---------------------------------------------

def weight_bytes(cfg: dict, layout: str, serve_dtype: str = "bf16",
                 rows: float | None = None) -> float:
    """Bytes of weights one decode step reads (``rows`` live streams), or
    with ``rows=None`` all the weights the device holds for decoding,
    embedding included: the number a parameter count checks."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    plain_b = PLAIN_BYTES[serve_dtype]
    experts = cfg.get("num_local_experts") or 0
    layer = 2 * h * plain_b  # the two norms
    for suffix, (fan_in, out) in layer_linears(cfg).items():
        b = linear_bytes(fan_in, out, layout)
        if experts and ".experts." in suffix and rows is not None:
            b *= expected_experts(experts, cfg["num_experts_per_tok"],
                                  rows) / experts
        layer += b
    if experts:
        layer += experts * h * plain_b  # router
    embed_rows = v if rows is None else rows
    return (cfg["num_hidden_layers"] * layer + embed_rows * h * plain_b
            + h * plain_b + linear_bytes(h, v, layout))


def kv_bytes(cfg: dict, context: float, rows: float,
             cache_dtype: str = "bf16") -> float:
    """Bytes of keys and values ``rows`` streams at a mean position of
    ``context`` read in one step, clipped to the sliding window."""
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    window = cfg.get("sliding_window")
    if window:
        context = min(context, window)
    return (rows * context * cfg["num_hidden_layers"] * 2
            * cfg["num_key_value_heads"] * d * PLAIN_BYTES[cache_dtype])


def decode_step_bytes(cfg: dict, layout: str, rows: float, context: float,
                      serve_dtype: str = "bf16") -> float:
    """The least one decode step reads: the weights for ``rows`` live
    streams and their keys and values at a mean position of ``context``."""
    return (weight_bytes(cfg, layout, serve_dtype, rows)
            + kv_bytes(cfg, context, rows, serve_dtype))
