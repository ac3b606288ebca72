"""Architecture ``kda_mla_moe``: a decoder whose layers are delta-rule
linear attention (KDA: a float32 state a head and a stream, whatever the
stream's length) but every ``layer_group_size``-th, which is multi-head
latent attention (one cached row a token, a direct query projection, a
sigmoid gate a head); ``first_k_dense_replace`` leading dense layers, then
expert layers of one shared expert beside sigmoid-scored, group-limited
routed ones whose CHOICE is corrected by a bias an expert: Ling-3.0's
``config.json`` keys (``model_type`` ``bailing_hybrid``). A configuration
may hold a chip's share of an expert-parallel deployment, as
``arch/mla_moe.py`` says: ``num_experts`` experts are HELD here, global
experts ``rank * num_experts ..`` of the ``expert_share.n_routed_experts``
the router scores.

Numpy and the standard library only (the parent of a chip run never
imports JAX). What this family shares with ``mla_moe`` (the routing
channels, rope on interleaved pairs, the generator a tensor is drawn
from) is taken from that module, loaded by path. The writer puts the
tensors under the names the program's loader reads; they are ASSUMED
(the configuration's ``assumed.tensor_names``): FLA's KDA module under
``self_attn.`` (``q_proj``, ``k_proj``, ``v_proj``, ``{q,k,v}_conv1d``
as torch depthwise ``[C, 1, K]``, ``f_proj``, ``A_log``, ``dt_bias``,
``b_proj``, ``g_proj``, ``o_norm``, ``o_proj``), DeepSeek-V3's names for
the latent layers (``q_proj`` direct, ``g_proj`` the gate) and the expert
layers, ``mlp.gate.expert_bias`` for the correction bias.

The reference is written from the equations ISSUE 32 states (Tentpole
1): the delta rule token by token, expanded attention, no cache: see
``_kda``, ``_attention`` and ``_feed_forward``.

A random router must not hang on rounding (``weights.py`` says why), and
here the correction bias must CHANGE choices without hanging on rounding
either. The routing channels are ``mla_moe``'s (the first ``E`` channels
of the residual stream belong to the router, the embedding marks
``num_experts_per_tok`` of them per token id, no linear writes to them,
the router's row ``e`` reads channel ``e`` alone), so a marked expert
scores ``sigmoid(mark / rms) > 1/2`` and every other exactly ``1/2``. The
bias is ``-1`` for the experts with ``e % 16 == 5`` and ``0`` for the
rest: a marked expert with that bias falls to ``score - 1 < 0`` and is
replaced by the lowest-indexed unmarked, unbiased expert of the kept
groups (all tied at exactly ``1/2``: ties go to the lower index in the
program and here), which enters with ITS OWN score ``1/2`` as its weight:
about two tokens in five have a choice changed, by a margin no rounding
crosses.
"""

from __future__ import annotations

import importlib.util
import math
import sys
from pathlib import Path

import numpy as np

from reference import Layer, rms_norm, score_pairs, swiglu
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

WRITER_VERSION = 1  # part of the key a checkpoint's directory is named by
REFERENCE_VERSION = 1  # part of the key under which answers are kept

# keys of a configuration file that are the model's own config.json (what
# the server reads); everything else in the file is the benchmark's
HF_KEYS = (
    "architectures", "model_type", "first_k_dense_replace",
    "gated_attention_proj_granularity_type", "group_norm_size", "head_dim",
    "hidden_act", "hidden_size", "intermediate_size", "kda_lower_bound",
    "kda_safe_gate", "kv_lora_rank", "layer_group_size", "linear_silu",
    "max_position_embeddings", "moe_intermediate_size",
    "moe_router_enable_expert_bias", "moe_shared_expert_intermediate_size",
    "n_group", "no_kda_lora", "norm_topk_prob", "num_attention_heads",
    "num_experts", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "num_kv_heads_for_linear_attn",
    "num_nextn_predict_layers", "num_shared_experts", "partial_rotary_factor",
    "q_lora_rank", "qk_head_dim", "qk_nope_head_dim", "qk_rope_head_dim",
    "rms_norm_eps", "rope_interleave", "rope_scaling", "rope_theta",
    "rotary_dim", "routed_scaling_factor", "scale_router_input",
    "score_function", "scoring_func", "short_conv_kernel_size",
    "tie_word_embeddings", "topk_group", "topk_method", "up_proj_norm",
    "use_bias", "use_kda_lora", "use_mla_nope", "use_nGPT", "use_qk_norm",
    "use_qkv_bias", "v_head_dim", "value_norm", "vocab_size",
    "expert_swiglu_limit_list", "share_expert_swiglu_limit_list",
    "expert_share", "bos_token_id", "eos_token_id",
)

SUPPRESSED = (16, 5)  # the bias is -1 where e % 16 == 5, else 0
DT_BIAS = -4.0  # decays of 0.8-0.95 a token: a state that remembers


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


def is_latent_layer(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["layer_group_size"] == 0


def is_expert_layer(cfg: dict, i: int) -> bool:
    return bool(cfg.get("num_experts")) and i >= cfg.get(
        "first_k_dense_replace", 0)


def expert_layers(cfg: dict) -> int:
    return sum(is_expert_layer(cfg, i)
               for i in range(cfg["num_hidden_layers"]))


def kda_layers(cfg: dict) -> int:
    return sum(not is_latent_layer(cfg, i)
               for i in range(cfg["num_hidden_layers"]))


def cache_row_values(cfg: dict) -> int:
    """Values a latent layer's cache holds for one token."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def state_bytes_per_stream(cfg: dict, cache_dtype: str = "bf16") -> int:
    """Bytes of recurrent state a stream holds, whatever its length: a
    float32 ``[H, d, d]`` state and the last ``taps - 1`` inputs of the q,
    k and v convolutions, a delta-rule layer."""
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    tail = (cfg["short_conv_kernel_size"] - 1) * 3 * h * d
    return kda_layers(cfg) * (h * d * d * 4 + tail * PLAIN_BYTES[cache_dtype])


def _mlp(prefix: str, h: int, f: int) -> dict[str, tuple[int, int]]:
    return {f"{prefix}gate_proj.weight": (h, f),
            f"{prefix}up_proj.weight": (h, f),
            f"{prefix}down_proj.weight": (f, h)}


def layer_linears(cfg: dict, i: int) -> dict[str, tuple[int, int]]:
    """HF suffix -> (fan_in, out) of layer ``i``'s linears."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    a = "self_attn."
    if is_latent_layer(cfg, i):
        dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
        dv, dc = cfg["v_head_dim"], cfg["kv_lora_rank"]
        lin = {a + "q_proj.weight": (h, nh * (dn + dr)),
               a + "kv_a_proj_with_mqa.weight": (h, dc + dr),
               a + "kv_b_proj.weight": (dc, nh * (dn + dv)),
               a + "g_proj.weight": (h, nh),
               a + "o_proj.weight": (nh * dv, h)}
    else:
        c = nh * cfg["head_dim"]
        lin = {a + f"{n}_proj.weight": (h, c) for n in "qkvfg"}
        lin[a + "b_proj.weight"] = (h, nh)
        lin[a + "o_proj.weight"] = (c, h)
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
    """Unquantized values of layer ``i``: its norms, a delta-rule layer's
    taps, rates and decay bias, a latent layer's inner norm, the router
    and its bias."""
    h, nh, d = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    n = 2 * h
    if is_latent_layer(cfg, i):
        n += cfg["kv_lora_rank"]
    else:
        n += (3 * cfg["short_conv_kernel_size"] + 1) * nh * d + nh + d
    if is_expert_layer(cfg, i):
        n += router_width(cfg) * (h + 1)
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
    e = np.arange(router_width(cfg))
    return np.where(e % SUPPRESSED[0] == SUPPRESSED[1], -1.0, 0.0).astype(
        np.float32)


def write_checkpoint(cfg: dict, layout: str, seed: int, model_dir: Path,
                     workers: int = 8) -> dict:
    """Write the checkpoint of configuration ``cfg`` (a configuration
    file's dict) into ``model_dir``; returns {"bytes", "files"}."""
    if layout not in ("q8", "bf16"):
        raise ValueError(f"unknown checkpoint layout {layout!r}")
    model_dir.mkdir(parents=True, exist_ok=True)
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    nh, d = cfg["num_attention_heads"], cfg["head_dim"]
    layers = cfg["num_hidden_layers"]
    routed = bool(cfg.get("num_experts"))
    width = router_width(cfg) if routed else 0
    if width > h // 2:
        raise ValueError(f"{width} routing channels of {h}")

    def layer(i: int):
        f = File(model_dir / f"model-layer-{i:05d}.safetensors")
        r = rngs(seed, i)
        p = f"model.layers.{i}."
        a = p + "self_attn."
        plain(f, layout, p + "input_layernorm.weight", norm(next(r), h))
        plain(f, layout, p + "post_attention_layernorm.weight",
              norm(next(r), h))
        if is_latent_layer(cfg, i):
            plain(f, layout, a + "kv_a_layernorm.weight",
                  norm(next(r), cfg["kv_lora_rank"]))
        else:
            for n in "qkv":  # torch depthwise conv1d: [C, 1, K]
                plain(f, layout, a + f"{n}_conv1d.weight", small(
                    next(r), (nh * d, 1, cfg["short_conv_kernel_size"]), 0.5))
            plain(f, layout, a + "A_log", small(next(r), (nh,), 0.5))
            # -5 .. -3 in eighths: exact in bfloat16
            plain(f, layout, a + "dt_bias", np.float32(DT_BIAS) + next(
                r).integers(-8, 9, size=nh * d).astype(np.float32) / 8)
            plain(f, layout, a + "o_norm.weight", norm(next(r), d))
        if is_expert_layer(cfg, i):  # row e reads routing channel e alone
            plain(f, layout, p + "mlp.gate.weight",
                  np.eye(width, h, dtype=np.float32))
            plain(f, layout, p + "mlp.gate.expert_bias", router_bias(cfg))
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


def _silu(x: np.ndarray) -> np.ndarray:
    return x * _sigmoid(x)


def _kda(cfg: dict, ck: Layer, p: str, x: np.ndarray) -> np.ndarray:
    """Delta-rule attention of one sequence, a token at a time from a zero
    state, float32."""
    t = x.shape[0]
    nh, d = cfg["num_attention_heads"], cfg["head_dim"]
    taps = cfg["short_conv_kernel_size"]
    a = p + "self_attn."

    def conv(name: str) -> np.ndarray:
        y = x @ ck.f32(a + f"{name}_proj.weight").T  # [t, H d]
        w = ck.f32(a + f"{name}_conv1d.weight")[:, 0, :]  # [C, K]
        padded = np.concatenate([np.zeros((taps - 1, y.shape[1]),
                                          np.float32), y])
        out = sum(padded[j:j + t] * w[:, j] for j in range(taps))
        return _silu(out).reshape(t, nh, d)

    def l2(v: np.ndarray) -> np.ndarray:
        return v / np.sqrt((v * v).sum(-1, keepdims=True) + np.float32(1e-6))

    q = l2(conv("q")) * np.float32(d ** -0.5)
    k, v = l2(conv("k")), conv("v")
    rate = np.exp(ck.f32(a + "A_log"))[:, None]
    g = np.float32(cfg["kda_lower_bound"]) * _sigmoid(rate * (
        x @ ck.f32(a + "f_proj.weight").T
        + ck.f32(a + "dt_bias")).reshape(t, nh, d))
    decay = np.exp(g)
    beta = _sigmoid(x @ ck.f32(a + "b_proj.weight").T)  # [t, H]
    s = np.zeros((nh, d, d), np.float32)
    o = np.empty((t, nh, d), np.float32)
    for i in range(t):
        s *= decay[i][:, :, None]
        ks = np.einsum("hk,hkv->hv", k[i], s)
        s += (beta[i][:, None] * k[i])[:, :, None] * (v[i] - ks)[:, None, :]
        o[i] = np.einsum("hk,hkv->hv", q[i], s)
    o = rms_norm(o, ck.f32(a + "o_norm.weight"), cfg["rms_norm_eps"])
    gate = _sigmoid(x @ ck.f32(a + "g_proj.weight").T)
    return (o.reshape(t, nh * d) * gate) @ ck.f32(a + "o_proj.weight").T


def _attention(cfg: dict, ck: Layer, p: str, x: np.ndarray) -> np.ndarray:
    t = x.shape[0]
    nh = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, dc = cfg["v_head_dim"], cfg["kv_lora_rank"]
    a = p + "self_attn."
    q = (x @ ck.f32(a + "q_proj.weight").T).reshape(t, nh, dn + dr)
    ckv = x @ ck.f32(a + "kv_a_proj_with_mqa.weight").T
    c = rms_norm(ckv[:, :dc], ck.f32(a + "kv_a_layernorm.weight"),
                 cfg["rms_norm_eps"])
    kv = (c @ ck.f32(a + "kv_b_proj.weight").T).reshape(t, nh, dn + dv)
    cos, sin = _mla.rope_angles(cfg, t)
    q = q.transpose(1, 0, 2)  # [H, t, dn + dr]
    q_pe = _mla.rope_pairs(np.ascontiguousarray(q[..., dn:]), cos, sin)
    k_pe = _mla.rope_pairs(np.ascontiguousarray(ckv[:, dc:]), cos, sin)
    scores = (q[..., :dn] @ kv[:, :, :dn].transpose(1, 2, 0)
              + q_pe @ k_pe.T[None]) * np.float32((dn + dr) ** -0.5)
    ok = np.arange(t)[None, :] <= np.arange(t)[:, None]
    scores = np.where(ok[None], scores, np.float32(-np.inf))
    scores = scores - scores.max(-1, keepdims=True)
    w = np.exp(scores)
    w /= w.sum(-1, keepdims=True)
    out = (w @ kv[:, :, dn:].transpose(1, 0, 2)).transpose(1, 0, 2)
    if cfg.get("gated_attention_proj_granularity_type") == "head_wise":
        out = out * _sigmoid(x @ ck.f32(a + "g_proj.weight").T)[:, :, None]
    return out.reshape(t, nh * dv) @ ck.f32(a + "o_proj.weight").T


def route(cfg: dict, scores: np.ndarray, bias: np.ndarray):
    """``scores [t, E]`` (sigmoid), ``bias [E]`` -> (chosen ``[t, k]``,
    weights ``[t, k]``, margin ``[t]``): the choice is made on ``scores +
    bias`` (a group's score is the sum of its 2 highest; the
    ``topk_group`` best groups stay; top-k inside them), the weights are
    the chosen experts' own scores over their sum (+1e-20) times
    ``routed_scaling_factor``. Ties go to the lower index. The margin is
    how far the last expert chosen lies above the first one left out (or,
    where the two tie exactly and the index decides, how far the nearest
    other corrected score lies from the tied level), in units of the
    token's scores' spread."""
    t, e = scores.shape
    groups, keep = cfg.get("n_group", 1), cfg.get("topk_group", 1)
    k = cfg["num_experts_per_tok"]
    choice = scores + bias
    if groups > 1:
        grouped = choice.reshape(t, groups, e // groups)
        group_score = np.sort(grouped, axis=-1)[..., -2:].sum(-1)
        kept = np.argsort(-group_score, axis=-1, kind="stable")[:, :keep]
        in_kept = np.zeros((t, groups), bool)
        np.put_along_axis(in_kept, kept, True, axis=1)
        choice = np.where(in_kept[..., None], grouped,
                          np.float32(-np.inf)).reshape(t, e)
    ranked = np.argsort(-choice, axis=-1, kind="stable")
    idx = ranked[:, :k]
    by_rank = np.take_along_axis(choice, ranked, -1)
    last, out = by_rank[:, k - 1], by_rank[:, k]
    # where the cut falls inside an exact tie (unmarked experts at 1/2
    # plus equal biases), the index settles it; what rounding could move
    # is the nearest other value on either side of the tied level
    below = np.where(by_rank < last[:, None], by_rank,
                     np.float32(-np.inf)).max(-1)
    above = np.where(by_rank > last[:, None], by_rank,
                     np.float32(np.inf)).min(-1)
    gap = np.where(last == out, np.minimum(last - below, above - last),
                   last - out)
    margin = gap / (scores.std(-1) + 1e-9)
    w = np.take_along_axis(scores, idx, -1)
    if cfg.get("norm_topk_prob", True) and k > 1:
        w = w / (w.sum(-1, keepdims=True) + np.float32(1e-20))
    return idx, w * np.float32(cfg.get("routed_scaling_factor", 1.0)), margin


def _feed_forward(cfg: dict, ck: Layer, p: str, i: int, x: np.ndarray,
                  margins: list) -> np.ndarray:
    """Layer ``i``'s feed-forward block: a dense SwiGLU, or ``shared(h) +
    the sum over the chosen experts HELD here of w_e expert_e(h)``;
    ``margins`` gains each token's routing margin."""
    def mlp(prefix: str, rows: np.ndarray) -> np.ndarray:
        return swiglu(rows, ck.f32(prefix + "gate_proj.weight"),
                      ck.f32(prefix + "up_proj.weight"),
                      ck.f32(prefix + "down_proj.weight"))

    if not is_expert_layer(cfg, i):
        return mlp(p + "mlp.", x)
    logits = x @ ck.f32(p + "mlp.gate.weight").T  # [t, E]
    bias = (ck.f32(p + "mlp.gate.expert_bias")
            if cfg.get("moe_router_enable_expert_bias")
            else np.zeros(logits.shape[1], np.float32))
    idx, weight, margin = route(cfg, _sigmoid(logits), bias)
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


def chosen_logprobs(cfg: dict, model_dir, pairs: list[tuple]) -> list[dict]:
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
        mixer = _attention if is_latent_layer(cfg, i) else _kda
        for n, x in enumerate(xs):
            x = x + mixer(cfg, layer, p, rms_norm(
                x, layer.f32(p + "input_layernorm.weight"), eps))
            xs[n] = x + _feed_forward(cfg, layer, p, i, rms_norm(
                x, layer.f32(p + "post_attention_layernorm.weight"), eps),
                margins[n])
    return score_pairs(ck, eps, pairs, xs, margins)


# -- bytes a decode step must move ---------------------------------------------

def held_experts_hit(cfg: dict, rows: float) -> float:
    """How many of the experts held here some row is routed to."""
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


def kda_decode_bytes(cfg: dict, rows: float) -> float:
    """Bytes one delta-rule layer's decode step must move for ``rows``
    streams (the kernel ``kda_decode``): one read and one write of each
    head's float32 state, and the step's decay, k, beta k, q and v in and
    o out (float32)."""
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    return 4.0 * rows * h * (2 * d * d + 6 * d)


def kda_decode_flops(cfg: dict, rows: float) -> float:
    """Operations of the same step: decay, ``k^T S``, the rank-one update
    and ``q^T S``, two a state element each."""
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    return 8.0 * rows * h * d * d


def state_bytes(cfg: dict, rows: float, cache_dtype: str = "bf16") -> float:
    """Bytes of recurrent state ``rows`` streams move in one step: every
    delta-rule layer's state read and written once, its convolution tail
    read and written once."""
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    tail = (cfg["short_conv_kernel_size"] - 1) * 3 * h * d
    return rows * kda_layers(cfg) * 2 * (
        h * d * d * 4 + tail * PLAIN_BYTES[cache_dtype])


def kv_bytes(cfg: dict, context: float, rows: float,
             cache_dtype: str = "bf16") -> float:
    """Bytes of latent rows ``rows`` streams at a mean position of
    ``context`` read in one step: one row a token a LATENT layer."""
    latent = cfg["num_hidden_layers"] - kda_layers(cfg)
    return (rows * context * latent * cache_row_values(cfg)
            * PLAIN_BYTES[cache_dtype])


def decode_step_bytes(cfg: dict, layout: str, rows: float, context: float,
                      serve_dtype: str = "bf16") -> float:
    """The least one decode step moves: the weights for ``rows`` live
    streams, their latent rows at a mean position of ``context``, and
    their recurrent state once in and once out."""
    return (weight_bytes(cfg, layout, serve_dtype, rows)
            + kv_bytes(cfg, context, rows, serve_dtype)
            + state_bytes(cfg, rows, serve_dtype))
