"""Architecture ``swa_gqa_moe``: a decoder whose layers are grouped-query
attention through a sliding window (``layer_types[i] ==
"sliding_attention"``: q and k rotated, query ``t`` sees keys ``j`` with
``0 <= t - j < sliding_window``) but some, which attend fully and carry NO
position embedding (``"full_attention"``); every head of q and k
RMS-normed before the rotation; ``mlp_layer_types`` leading dense layers,
then expert layers of one shared expert beside sigmoid-scored routed ones
whose CHOICE is corrected by a bias an expert: K-EXAONE's ``config.json``
keys (``model_type`` ``exaone_moe``). A configuration may hold a chip's
share of an expert-parallel deployment, as ``arch/mla_moe.py`` says:
``num_experts`` experts are HELD here, global experts ``rank * num_experts
..`` of the ``expert_share.n_routed_experts`` the router scores.

Numpy and the standard library only (the parent of a chip run never
imports JAX). What this family shares with ``mla_moe`` and ``kda_mla_moe``
(the routing channels, the corrected choice with its margin, the
generator a tensor is drawn from) is taken from those modules, loaded by
path. The writer puts the tensors under the names the program's loader
reads; they are ASSUMED (the configuration's ``assumed.tensor_names``):
Llama's for the attention with ``self_attn.q_norm`` / ``k_norm`` (one
``[head_dim]`` weight each), DeepSeek-V3's for the expert layers with
``mlp.gate.e_score_correction_bias``. The next-token prediction block
(``num_nextn_predict_layers``, ``mtp.*``) takes no part in the model's own
logits: it is neither written nor read.

The reference is written from the equations ISSUE 40 states (Motivation):
pre-norm sublayers (an ``assumed`` reading), the whole sequence at once
under explicit masks, no cache and no ring: see ``_attention`` and
``_feed_forward``. Scores are taken a block of query rows and a key/value
head at a time, so that 1500 tokens of 64 heads fit the host.

What the cache holds and a step reads (``kv_bytes``): a FULL layer keeps
every row and a step reads ``context`` of them; a WINDOW layer keeps a
ring of ``R >= sliding_window`` rows a stream whatever the capacity and a
step reads ``min(context, sliding_window)``: the same work whatever
implements it. The program's counters ``attn.kv_blocks_read`` /
``_reserved`` count a FULL layer (a ring is read whole); its gauges
``cache.rows_bytes`` / ``cache.rows_bytes_full`` count both kinds
(``layer_metrics/cache.rows_held_share.py``).

A random router must not hang on rounding (``weights.py`` says why), and
the correction bias must CHANGE choices without hanging on rounding
either: the routing channels and the bias are ``kda_mla_moe``'s (the
first ``E`` channels of the residual stream belong to the router, the
embedding marks ``num_experts_per_tok`` of them per token id, no linear
writes to them, the router's row ``e`` reads channel ``e`` alone; the bias
is ``-1`` where ``e % 16 == 5`` and ``0`` elsewhere, so a marked expert so
biased gives way to the lowest-indexed unmarked, unbiased one, tied at
exactly ``1/2``, which enters with its own score as its weight).
"""

from __future__ import annotations

import importlib.util
import math
import sys
from pathlib import Path

import numpy as np

from reference import Layer, rms_norm, rope, score_pairs, swiglu
from shapes import PLAIN_BYTES, linear_bytes
from weights import (Checkpoint, File, hf_config, linear, norm, plain, rngs,
                     small, write_files)


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
    "architectures", "model_type", "first_k_dense_replace", "head_dim",
    "hidden_act", "hidden_size", "intermediate_size", "layer_types",
    "max_position_embeddings", "mlp_layer_types", "moe_intermediate_size",
    "mtp_layer_types", "mtp_sliding_windows", "n_group", "norm_topk_prob",
    "num_attention_heads", "num_experts", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "num_nextn_predict_layers",
    "num_shared_experts", "rms_norm_eps", "rope_parameters",
    "routed_scaling_factor", "scoring_func", "sliding_window",
    "sliding_window_pattern", "sliding_windows", "tie_word_embeddings",
    "topk_group", "vocab_size", "torch_dtype", "expert_share",
    "bos_token_id", "eos_token_id",
)

QUERY_ROWS = 512  # query rows a block of the reference's scores


# -- sizes -----------------------------------------------------------------------

def _as_mla(cfg: dict) -> dict:
    """The configuration under the keys ``mla_moe``'s helpers read."""
    return dict(cfg, n_routed_experts=cfg.get("num_experts", 0),
                n_shared_experts=cfg.get("num_shared_experts", 0))


def router_width(cfg: dict) -> int:
    return _mla.router_width(_as_mla(cfg))


def held_experts(cfg: dict) -> range:
    """Global ids of the experts held here."""
    return _mla.held_experts(_as_mla(cfg))


def is_window_layer(cfg: dict, i: int) -> bool:
    return cfg["layer_types"][i] == "sliding_attention"


def is_expert_layer(cfg: dict, i: int) -> bool:
    return cfg["mlp_layer_types"][i] == "sparse"


def expert_layers(cfg: dict) -> int:
    return sum(is_expert_layer(cfg, i)
               for i in range(cfg["num_hidden_layers"]))


def window_layers(cfg: dict) -> int:
    return sum(is_window_layer(cfg, i)
               for i in range(cfg["num_hidden_layers"]))


def cache_row_values(cfg: dict) -> int:
    """Values an attention layer's cache holds for one token: keys and
    values of every key/value head."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def _mlp(prefix: str, h: int, f: int) -> dict[str, tuple[int, int]]:
    return {f"{prefix}gate_proj.weight": (h, f),
            f"{prefix}up_proj.weight": (h, f),
            f"{prefix}down_proj.weight": (f, h)}


def layer_linears(cfg: dict, i: int) -> dict[str, tuple[int, int]]:
    """HF suffix -> (fan_in, out) of layer ``i``'s linears."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    a = "self_attn."
    lin = {a + "q_proj.weight": (h, nh * d), a + "k_proj.weight": (h, nkv * d),
           a + "v_proj.weight": (h, nkv * d), a + "o_proj.weight": (nh * d, h)}
    if is_expert_layer(cfg, i):
        f = cfg["moe_intermediate_size"]
        if cfg.get("num_shared_experts"):
            lin.update(_mlp("mlp.shared_experts.", h,
                            cfg["num_shared_experts"] * f))
        for e in held_experts(cfg):
            lin.update(_mlp(f"mlp.experts.{e}.", h, f))
    else:
        lin.update(_mlp("mlp.", h, cfg["intermediate_size"]))
    return lin


def _plain_values(cfg: dict, i: int) -> int:
    """Unquantized values of layer ``i``: its two norms, the heads' q and k
    norms, the router and its bias."""
    n = 2 * cfg["hidden_size"] + 2 * cfg["head_dim"]
    if is_expert_layer(cfg, i):
        n += router_width(cfg) * (cfg["hidden_size"] + 1)
    return n


def checkpoint_bytes(cfg: dict, layout: str) -> int:
    """Bytes the checkpoint will take on disk (to see that it fits)."""
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
    h, v, d = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    layers = cfg["num_hidden_layers"]
    routed = expert_layers(cfg) > 0
    width = router_width(cfg) if routed else 0
    if width > h // 2:
        raise ValueError(f"{width} routing channels of {h}")

    def layer(i: int):
        f = File(model_dir / f"model-layer-{i:05d}.safetensors")
        r = rngs(seed, i)
        p = f"model.layers.{i}."
        plain(f, layout, p + "input_layernorm.weight", norm(next(r), h))
        plain(f, layout, p + "post_attention_layernorm.weight",
              norm(next(r), h))
        plain(f, layout, p + "self_attn.q_norm.weight", norm(next(r), d))
        plain(f, layout, p + "self_attn.k_norm.weight", norm(next(r), d))
        if is_expert_layer(cfg, i):  # row e reads routing channel e alone
            plain(f, layout, p + "mlp.gate.weight",
                  np.eye(width, h, dtype=np.float32))
            plain(f, layout, p + "mlp.gate.e_score_correction_bias",
                  router_bias(cfg))
        for suffix, (fan_in, out) in layer_linears(cfg, i).items():
            # each tensor's generator is named by its place in the layer of
            # the UNCUT model, so that a share's experts are the same
            # tensors whichever share holds them
            writes_residual = suffix.endswith(("o_proj.weight",
                                               "down_proj.weight"))
            linear(f, _mla._tensor_rng(seed, i, suffix), layout, p + suffix,
                   fan_in, out, zero_rows=width if writes_residual else 0)
        return f.write()

    def ends():
        f = File(model_dir / "model-ends.safetensors")
        r = rngs(seed, layers)
        embed = small(next(r), (v, h), 1.0 / math.sqrt(h))
        if routed:
            _mla.routing_embed(embed, _as_mla(cfg))
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


def _attention(cfg: dict, ck: Layer, p: str, i: int, x: np.ndarray,
               window: int | None = None) -> np.ndarray:
    """Layer ``i``'s attention over one whole sequence: q and k normed a
    head, rotated on a window layer and there alone, scores under the
    explicit mask (``0 <= t - j < window`` on a window layer, ``j <= t``
    on a full one). ``window`` overrides the configuration's, for the
    control that must fail."""
    t = x.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, a = cfg["rms_norm_eps"], p + "self_attn."

    def heads(name: str, n: int, normed: bool) -> np.ndarray:
        y = (x @ ck.f32(a + f"{name}_proj.weight").T).reshape(t, n, d)
        if normed:
            y = rms_norm(y, ck.f32(a + f"{name}_norm.weight"), eps)
        return np.ascontiguousarray(y.transpose(1, 0, 2))  # [n, t, d]

    q, k, v = heads("q", nh, True), heads("k", nkv, True), heads(
        "v", nkv, False)
    windowed = is_window_layer(cfg, i)
    if windowed:
        theta = float(cfg["rope_parameters"]["rope_theta"])
        q, k = rope(q, theta), rope(k, theta)
        span = window or cfg["sliding_window"]
    g = nh // nkv
    out = np.empty((t, nh, d), np.float32)
    at = np.arange(t)
    for lo in range(0, t, QUERY_ROWS):
        rows = at[lo:lo + QUERY_ROWS]
        behind = rows[:, None] - at[None, :]  # t - j
        seen = behind >= 0
        if windowed:
            seen &= behind < span
        for kh in range(nkv):
            s = (q[kh * g:(kh + 1) * g, rows] @ k[kh].T) * np.float32(
                d ** -0.5)  # [g, rows, t]
            s = np.where(seen[None], s, np.float32(-np.inf))
            s = s - s.max(-1, keepdims=True)
            w = np.exp(s)
            w /= w.sum(-1, keepdims=True)
            out[rows, kh * g:(kh + 1) * g] = (w @ v[kh]).transpose(1, 0, 2)
    return out.reshape(t, nh * d) @ ck.f32(a + "o_proj.weight").T


def _feed_forward(cfg: dict, ck: Layer, p: str, i: int, x: np.ndarray,
                  margins: list) -> np.ndarray:
    """Layer ``i``'s feed-forward block: a dense SwiGLU, or ``shared(h) +
    the sum over the chosen experts HELD here of w_e expert_e(h)``, the
    choice made on ``sigmoid(h W_r) + b`` and the weights from the scores
    (``kda_mla_moe.route``: the group step is the identity at ``n_group``
    1); ``margins`` gains each token's routing margin."""
    def mlp(prefix: str, rows: np.ndarray) -> np.ndarray:
        return swiglu(rows, ck.f32(prefix + "gate_proj.weight"),
                      ck.f32(prefix + "up_proj.weight"),
                      ck.f32(prefix + "down_proj.weight"))

    if not is_expert_layer(cfg, i):
        return mlp(p + "mlp.", x)
    logits = x @ ck.f32(p + "mlp.gate.weight").T  # [t, E]
    idx, weight, margin = _kda.route(
        cfg, _sigmoid(logits), ck.f32(p + "mlp.gate.e_score_correction_bias"))
    margins.append(margin)
    out = np.zeros_like(x)
    for e in held_experts(cfg):
        rows, slot = np.nonzero(idx == e)
        if len(rows):
            out[rows] += weight[rows, slot][:, None] * mlp(
                f"{p}mlp.experts.{e}.", x[rows])
    if cfg.get("num_shared_experts"):
        out += mlp(p + "mlp.shared_experts.", x)
    return out


def chosen_logprobs(cfg: dict, model_dir, pairs: list[tuple],
                    window: int | None = None) -> list[dict]:
    """For each (prompt, chosen) pair: the log-probabilities the reference
    gives the ``chosen`` continuation of ``prompt``, token by token, and
    its own best token at each place (``reference.score_pairs``), given
    the same share of the experts as the server. A layer at a time, so
    that the published widths fit the host."""
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
            x = x + _attention(cfg, layer, p, i, rms_norm(
                x, layer.f32(p + "input_layernorm.weight"), eps), window)
            xs[n] = x + _feed_forward(cfg, layer, p, i, rms_norm(
                x, layer.f32(p + "post_attention_layernorm.weight"), eps),
                margins[n])
    return score_pairs(ck, eps, pairs, xs, margins)


# -- bytes a decode step must move ---------------------------------------------

def held_experts_hit(cfg: dict, rows: float) -> float:
    """How many of the experts held here some row is routed to
    (``shapes.expected_experts`` over the router's width, the held
    share of it)."""
    return _mla.held_experts_hit(_as_mla(cfg), rows)


def weight_bytes(cfg: dict, layout: str, serve_dtype: str = "bf16",
                 rows: float | None = None) -> float:
    """Bytes of weights one decode step reads (``rows`` live streams: the
    non-expert weights once, of the HELD experts those some row is routed
    to, the routers, the head's slice), or with ``rows=None`` all the
    weights the device holds, embedding included: the number a parameter
    count checks."""
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


def kv_bytes(cfg: dict, context: float, rows: float,
             cache_dtype: str = "bf16") -> float:
    """Bytes of cached rows ``rows`` streams at a mean position of
    ``context`` read in one step: a full layer ``context`` rows, a window
    layer the ``min(context, sliding_window)`` its query sees."""
    windowed = window_layers(cfg)
    full = cfg["num_hidden_layers"] - windowed
    seen = full * context + windowed * min(context, cfg["sliding_window"])
    return rows * seen * cache_row_values(cfg) * PLAIN_BYTES[cache_dtype]


def decode_step_bytes(cfg: dict, layout: str, rows: float, context: float,
                      serve_dtype: str = "bf16") -> float:
    """The least one decode step moves: the weights for ``rows`` live
    streams and their cached rows at a mean position of ``context``."""
    return (weight_bytes(cfg, layout, serve_dtype, rows)
            + kv_bytes(cfg, context, rows, serve_dtype))
