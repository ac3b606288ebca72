"""Architecture ``mhc_mla_moe``: ``mla_moe``'s decoder (multi-head latent
attention, YaRN, leading dense layers, then a shared expert beside
sigmoid-scored routed ones) under a residual stream ``hc_mult`` hidden
vectors wide that every sub-layer mixes by coefficients the token's own
state chooses (manifold-constrained hyper-connections), the choice of
experts corrected by a bias an expert: Xing4.0-29B-A4B's ``config.json``
keys (``model_type`` ``xing4_0``: DeepSeek-V3's and ``hc_mult``,
``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min/max``).

``n = hc_mult``, ``C = hidden_size``; a token's state is ``X [n, C]``.

- ``X_0[j] = E[token]`` for every stream; after the last layer ``h = sum_j
  X[j]``, then ``RMS(h; model.norm)`` and the untied head.
- A sub-layer ``F`` (a layer has two, attention then feed-forward, each
  with its OWN ``fn [n^2 + 2n, n C]``, ``base [n^2 + 2n]``, ``scale [3]``):
  ``x~ = vec(X)``; ``r = x~ / sqrt(mean(x~^2) + rms_norm_eps)`` (no
  weight); ``m = r fn^T``, split into pre (n), post (n), res (n x n,
  row-major); ``H_pre = sigmoid(a_pre m_pre + b_pre)``; ``H_post = 2
  sigmoid(a_post m_post + b_post)``; ``M = exp(clamp(a_res m_res + b_res,
  mhc_h_res_clamp_min, mhc_h_res_clamp_max))`` through ``hc_sinkhorn_iters``
  rounds of ``M <- M / (rowsum(M) + hc_eps)``, ``M <- M / (colsum(M) +
  hc_eps)``: ``H_res``; ``u = sum_j H_pre[j] X[j]``; ``y = F(RMS(u; the
  sub-layer's norm))``; ``X'[i] = H_post[i] y + sum_j H_res[i, j] X[j]``.
- Attention and the feed-forward are ``arch/mla_moe.py``'s; the choice of
  experts is made on ``score + mlp.gate.e_score_correction_bias``
  (``topk_method`` ``noaux_tc``: ``arch/kda_mla_moe.py``'s ``route``), the
  weights the unbiased scores normalised over the chosen.
- ``num_nextn_predict_layers``: a next-token prediction block that takes
  no part in the model's own logits; neither written nor read.

Numpy and the standard library only (the parent of a chip run never
imports JAX). The reference is written from the equations ISSUE 51 states
(Motivation), not from the program: the whole sequence at once, no cache,
the Sinkhorn rounds a Python loop of ``sum(axis)``, the mixes ``einsum``
over the axis of streams. The ``hc`` tensors' names are ASSUMED (the
configuration's ``assumed``) and they are float32 in BOTH layouts.

Seeded weights that make the mechanism work: gains of 1, ``fn`` of std
``1 / sqrt(n C)`` (logits of std 1 on the normed stream), biases of std 1
with 2 more on ``H_res``'s diagonal: ``H_res`` is neither the identity nor
uniform and differs from token to token. The routing channels are
``mla_moe``'s (``weights.py`` says why): the embedding writes them alike
into all ``n`` streams; every sub-layer writes zeros there and a doubly
stochastic ``H_res`` keeps what all streams share, so they stay what they
were (to the rounds' ``hc_eps``); a positive ``sum(H_pre)`` scales ``u``
whole, which the sub-layer's norm forgives: a marked expert scores over
``1/2`` and every other exactly ``1/2``. The correction bias is
``kda_mla_moe``'s: ``-1`` where ``e % 16 == 5``, which CHANGES a choice for
one token in four by a margin no rounding crosses.
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
HF_KEYS = mla_moe.HF_KEYS + (
    "num_nextn_predict_layers", "hc_mult", "hc_sinkhorn_iters", "hc_eps",
    "mhc_h_res_clamp_min", "mhc_h_res_clamp_max",
)

MODEL_TYPE = "xing4_0"
PARTS = ("attn", "ffn")  # a layer's two sub-layers
RES_DIAGONAL = 2.0  # added to b_res[i, i]


def require_family(checkout: Path) -> None:
    """Refuse a checkout whose program does not name this family, at once
    (``arch/looped_gqa.py`` says why a guard stands here: a program that
    reads the file as another model must measure nothing under the cell's
    name). A program from before the family refuses ``topk_method``
    ``noaux_tc`` on load by itself; this fails before a checkpoint is
    written. Asked of the source: the parent of a chip run imports neither
    JAX nor ``cake_tpu``."""
    models = checkout / "cake_tpu" / "models"
    if not any(f'"{MODEL_TYPE}"' in path.read_text()
               for path in sorted(models.glob("*.py"))):
        raise RuntimeError(
            f"no module under {models} declares model_type {MODEL_TYPE!r}: "
            "this program has no residual stream several hidden vectors "
            "wide (hc_mult, cake_tpu/ops/hyper.py); the cell needs it")


require_family(Path(__file__).resolve().parents[2])


# -- sizes -----------------------------------------------------------------------

# what the readers of the expert layers' counters ask of an architecture
held_experts = mla_moe.held_experts
expert_layers = mla_moe.expert_layers


def coefficients(cfg: dict) -> int:
    """Mixing coefficients a token and a sub-layer: ``n^2 + 2 n``."""
    return cfg["hc_mult"] * (cfg["hc_mult"] + 2)


def hc_values(cfg: dict) -> int:
    """Float32 values of one sub-layer's ``fn``, ``base`` and ``scale``."""
    k = coefficients(cfg)
    return k * cfg["hc_mult"] * cfg["hidden_size"] + k + 3


def resid_token_bytes(cfg: dict, serve_dtype: str = "bf16") -> int:
    """Bytes one token's residual state holds between sub-layers."""
    return cfg["hc_mult"] * cfg["hidden_size"] * PLAIN_BYTES[serve_dtype]


def _extra_bytes(cfg: dict, plain_bytes: int) -> int:
    """What this family's checkpoint and device hold beyond ``mla_moe``'s
    tensors: two sub-layers' float32 ``hc`` tensors a layer and the
    router's bias an expert layer."""
    return (cfg["num_hidden_layers"] * len(PARTS) * hc_values(cfg) * 4
            + mla_moe.expert_layers(cfg) * mla_moe.router_width(cfg)
            * plain_bytes)


def checkpoint_bytes(cfg: dict, layout: str) -> int:
    """Bytes the checkpoint will take on disk (to see that it fits)."""
    return mla_moe.checkpoint_bytes(cfg, layout) + _extra_bytes(
        cfg, 4 if layout == "q8" else 2)


# -- the checkpoint --------------------------------------------------------------

def router_bias(cfg: dict) -> np.ndarray:
    """The correction bias: -1 for one expert in sixteen, else 0."""
    e = np.arange(mla_moe.router_width(cfg))
    every, at = kda_mla_moe.SUPPRESSED
    return np.where(e % every == at, -1.0, 0.0).astype(np.float32)


def _hc_tensors(cfg: dict, rng) -> dict[str, np.ndarray]:
    """One sub-layer's ``fn``, ``base``, ``scale`` (float32)."""
    n, k = cfg["hc_mult"], coefficients(cfg)
    width = n * cfg["hidden_size"]
    base = small(rng, (k,), 1.0)
    base[2 * n + (n + 1) * np.arange(n)] += np.float32(RES_DIAGONAL)
    return {"fn": small(rng, (k, width), 1.0 / math.sqrt(width)),
            "base": base, "scale": np.ones(3, np.float32)}


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

    def layer(i: int):
        f = File(model_dir / f"model-layer-{i:05d}.safetensors")
        r = rngs(seed, i)
        p = f"model.layers.{i}."
        plain(f, layout, p + "input_layernorm.weight", norm(next(r), h))
        plain(f, layout, p + "post_attention_layernorm.weight",
              norm(next(r), h))
        plain(f, layout, p + "self_attn.q_a_layernorm.weight",
              norm(next(r), cfg["q_lora_rank"]))
        plain(f, layout, p + "self_attn.kv_a_layernorm.weight",
              norm(next(r), cfg["kv_lora_rank"]))
        for part in PARTS:  # float32 in both layouts
            for name, values in _hc_tensors(cfg, next(r)).items():
                f.add(f"{p}hc_{part}_{name}", "F32", values.shape,
                      np.ascontiguousarray(values, np.float32))
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

def _sigmoid(x: np.ndarray) -> np.ndarray:
    return (1.0 / (1.0 + np.exp(-x))).astype(np.float32)


def sinkhorn(m: np.ndarray, iters: int, eps: float) -> np.ndarray:
    """``iters`` rounds of row, then column normalisation of ``m [t, n,
    n]``."""
    eps = np.float32(eps)
    for _ in range(iters):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
    return m


def mixing(cfg: dict, ck: Layer, p: str, part: str, x: np.ndarray):
    """``(H_pre [t, n], H_post [t, n], H_res [t, n, n])`` of sub-layer
    ``part`` of layer ``p`` on the stream ``x [t, n, C]``."""
    n, t = cfg["hc_mult"], x.shape[0]
    flat = x.reshape(t, -1)
    r = flat / np.sqrt(np.mean(flat * flat, -1, keepdims=True)
                       + np.float32(cfg["rms_norm_eps"]))
    m = r @ ck.f32(f"{p}hc_{part}_fn").T
    b = ck.f32(f"{p}hc_{part}_base")
    a_pre, a_post, a_res = ck.f32(f"{p}hc_{part}_scale")
    pre = _sigmoid(a_pre * m[:, :n] + b[:n])
    post = 2.0 * _sigmoid(a_post * m[:, n:2 * n] + b[n:2 * n])
    logit = np.clip(a_res * m[:, 2 * n:] + b[2 * n:],
                    np.float32(cfg["mhc_h_res_clamp_min"]),
                    np.float32(cfg["mhc_h_res_clamp_max"]))
    res = sinkhorn(np.exp(logit).reshape(t, n, n).astype(np.float32),
                   cfg["hc_sinkhorn_iters"], cfg["hc_eps"])
    return pre, post, res


def _sub_layer(cfg, ck, p, part, norm_name, x, f):
    pre, post, res = mixing(cfg, ck, p, part, x)
    u = np.einsum("tj,tjc->tc", pre, x)
    y = f(rms_norm(u, ck.f32(p + norm_name), cfg["rms_norm_eps"]))
    return (post[:, :, None] * y[:, None, :]
            + np.einsum("tij,tjc->tic", res, x)).astype(np.float32)


def _feed_forward(cfg: dict, ck: Layer, p: str, i: int, x: np.ndarray,
                  margins: list) -> np.ndarray:
    """Layer ``i``'s feed-forward block: a dense SwiGLU, or ``shared(h) +
    the sum over the chosen experts of w_e expert_e(h)``, the choice on
    ``score + e_score_correction_bias``; ``margins`` gains each token's
    routing margin."""
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
    idx, weight, margin = kda_mla_moe.route(cfg, _sigmoid(logits), bias)
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
    its own best token at each place (``reference.score_pairs``). A layer
    at a time, so that the published widths fit the host."""
    ck = Checkpoint(model_dir)
    n = cfg["hc_mult"]
    embed = ck.f32("model.embed_tokens.weight")
    xs = [np.repeat(embed[np.asarray(list(prompt) + list(chosen[:-1]),
                                     np.int64)][:, None, :], n, axis=1)
          for prompt, chosen in pairs]
    del embed
    margins: list[list] = [[] for _ in pairs]
    for i in range(cfg["num_hidden_layers"]):
        p, layer = f"model.layers.{i}.", Layer(ck)
        for k, x in enumerate(xs):
            x = _sub_layer(
                cfg, layer, p, "attn", "input_layernorm.weight", x,
                lambda h: mla_moe._attention(cfg, layer, p, h))
            xs[k] = _sub_layer(
                cfg, layer, p, "ffn", "post_attention_layernorm.weight", x,
                lambda h: _feed_forward(cfg, layer, p, i, h, margins[k]))
    return score_pairs(ck, cfg["rms_norm_eps"], pairs,
                       [x.sum(axis=1) for x in xs], margins)


# -- bytes a decode step must read ---------------------------------------------

def weight_bytes(cfg: dict, layout: str, serve_dtype: str = "bf16",
                 rows: float | None = None) -> float:
    """``mla_moe.weight_bytes`` and this family's tensors beside them (the
    float32 ``hc`` tensors and the routers' biases: read whole every step
    at any batch)."""
    return (mla_moe.weight_bytes(cfg, layout, serve_dtype, rows)
            + _extra_bytes(cfg, PLAIN_BYTES[serve_dtype]))


def stream_bytes(cfg: dict, rows: float, serve_dtype: str = "bf16") -> float:
    """Bytes of the residual stream ``rows`` rows move in one pass over the
    layers, done well: a sub-layer reads the stream for the statistics and
    ``m``, again for ``u`` and again, with ``y``, for ``X'``, and writes
    ``u`` and ``X'``: ``(4 n + 2) C`` values a row."""
    n = cfg["hc_mult"]
    return (rows * cfg["num_hidden_layers"] * len(PARTS) * (4 * n + 2)
            * cfg["hidden_size"] * PLAIN_BYTES[serve_dtype])


def mhc_mix_bytes(cfg: dict, rows: float, serve_dtype: str = "bf16") -> float:
    """Bytes ONE post-and-residual mix moves for ``rows`` rows: ``X`` and
    ``y`` read, ``X'`` written, ``(2 n + 1) C`` values a row (what a fused
    ``mhc_mix`` kernel would be held to; the program has none: PERF.md
    section 7)."""
    return (rows * (2 * cfg["hc_mult"] + 1) * cfg["hidden_size"]
            * PLAIN_BYTES[serve_dtype])


kv_bytes = mla_moe.kv_bytes


def decode_step_bytes(cfg: dict, layout: str, rows: float, context: float,
                      serve_dtype: str = "bf16") -> float:
    """The least one decode step reads: the weights for ``rows`` live
    streams, their latent rows at a mean position of ``context``, and the
    wide stream's passes."""
    return (weight_bytes(cfg, layout, serve_dtype, rows)
            + kv_bytes(cfg, context, rows, serve_dtype)
            + stream_bytes(cfg, rows, serve_dtype))
