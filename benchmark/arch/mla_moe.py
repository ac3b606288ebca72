"""Architecture ``mla_moe``: a decoder with multi-head latent attention
(one cached row of ``kv_lora_rank + qk_rope_head_dim`` values a token a
layer, shared by all heads), YaRN-scaled rotary embeddings on a part of
each head, ``first_k_dense_replace`` leading dense layers, and expert
layers of ``n_shared_experts`` shared experts beside sigmoid-scored,
group-limited routed ones: DeepSeek-V3's ``config.json`` keys, whichever
model carries them. A configuration may hold a chip's share of an
expert-parallel deployment: ``n_routed_experts`` experts are HELD here,
global experts ``rank * n_routed_experts ..`` of the
``expert_share.n_routed_experts`` the router scores; the result is the
held experts' part (plus the shared experts), in the server and in the
reference alike.

Numpy and the standard library only (the parent of a chip run never
imports JAX). The writer puts the tensors under the names the program's
loader reads (DeepSeek-V3's: ``self_attn.q_a_proj`` ... ``mlp.gate``,
``mlp.experts.{e}.gate_proj|up_proj|down_proj`` by GLOBAL expert id,
``mlp.shared_experts.*``). The reference is written from the published
equations (as Hugging Face's DeepSeek-V3 implements them), expanded
attention, no cache: see ``_attention`` and ``_feed_forward``. The
readings the published file does not settle are the configuration's
``assumed``: ``topk_method`` as the group-limited choice with no
correction bias, rope on interleaved pairs ``(2i, 2i+1)``.

A random router must not hang on rounding (``weights.py`` says why). This
family's router scores with a sigmoid and chooses inside groups, so the
routing channels are given out so: the first ``E`` channels of the
residual stream (``E`` = the router's width) belong to the router, the
embedding marks ``num_experts_per_tok`` of them per token id, spread
evenly over ``topk_group`` distinct groups, no linear writes to them, and
the router's row ``e`` reads channel ``e`` alone. The marked experts'
scores are ``sigmoid(mark / rms) > 1/2``, every other's is exactly
``1/2``: the marked groups and the marked experts win by a margin no
rounding crosses, uniformly over the experts.
"""

from __future__ import annotations

import math
import zlib
from pathlib import Path

import numpy as np

from reference import Layer, rms_norm, score_pairs, swiglu
from shapes import PLAIN_BYTES, expected_experts, linear_bytes
from weights import (ROUTE_MARK, Checkpoint, File, hf_config, linear, norm,
                     plain, rngs, small, write_files)

WRITER_VERSION = 1  # part of the key a checkpoint's directory is named by
REFERENCE_VERSION = 1  # part of the key under which answers are kept

# keys of a configuration file that are the model's own config.json (what
# the server reads); everything else in the file is the benchmark's
HF_KEYS = (
    "architectures", "model_type", "attention_bias", "ep_size",
    "first_k_dense_replace", "hidden_act", "hidden_size",
    "intermediate_size", "kv_lora_rank", "max_position_embeddings",
    "moe_intermediate_size", "moe_layer_freq", "n_group",
    "n_routed_experts", "n_shared_experts", "norm_topk_prob",
    "num_attention_heads", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "q_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "rms_norm_eps", "rope_theta", "rope_scaling",
    "routed_scaling_factor", "scoring_func", "seq_aux",
    "tie_word_embeddings", "topk_group", "topk_method", "v_head_dim",
    "vocab_size", "expert_share", "bos_token_id", "eos_token_id",
)


# -- sizes -----------------------------------------------------------------------

def router_width(cfg: dict) -> int:
    """Experts the router scores (the published count)."""
    return (cfg.get("expert_share") or {}).get(
        "n_routed_experts", cfg["n_routed_experts"])


def held_experts(cfg: dict) -> range:
    """Global ids of the experts held here."""
    held = cfg["n_routed_experts"]
    first = (cfg.get("expert_share") or {}).get("rank", 0) * held
    return range(first, first + held)


def is_expert_layer(cfg: dict, i: int) -> bool:
    return bool(cfg.get("n_routed_experts")) and i >= cfg.get(
        "first_k_dense_replace", 0)


def expert_layers(cfg: dict) -> int:
    return sum(is_expert_layer(cfg, i)
               for i in range(cfg["num_hidden_layers"]))


def cache_row_values(cfg: dict) -> int:
    """Values the latent cache holds for one token of one layer."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def _mlp(prefix: str, h: int, f: int) -> dict[str, tuple[int, int]]:
    return {f"{prefix}gate_proj.weight": (h, f),
            f"{prefix}up_proj.weight": (h, f),
            f"{prefix}down_proj.weight": (f, h)}


def layer_linears(cfg: dict, i: int) -> dict[str, tuple[int, int]]:
    """HF suffix -> (fan_in, out) of layer ``i``'s linears."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, dc, ql = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["q_lora_rank"]
    lin = {"self_attn.q_a_proj.weight": (h, ql),
           "self_attn.q_b_proj.weight": (ql, nh * (dn + dr)),
           "self_attn.kv_a_proj_with_mqa.weight": (h, dc + dr),
           "self_attn.kv_b_proj.weight": (dc, nh * (dn + dv)),
           "self_attn.o_proj.weight": (nh * dv, h)}
    if is_expert_layer(cfg, i):
        f = cfg["moe_intermediate_size"]
        if cfg.get("n_shared_experts"):
            lin.update(_mlp("mlp.shared_experts.", h,
                            cfg["n_shared_experts"] * f))
        for e in held_experts(cfg):
            lin.update(_mlp(f"mlp.experts.{e}.", h, f))
    else:
        lin.update(_mlp("mlp.", h, cfg["intermediate_size"]))
    return lin


def _plain_values(cfg: dict, i: int) -> int:
    """Unquantized values of layer ``i``: its four norms and its router."""
    n = 2 * cfg["hidden_size"] + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    if is_expert_layer(cfg, i):
        n += router_width(cfg) * cfg["hidden_size"]
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


# -- routing that rounding cannot flip ---------------------------------------------

def routing_channels(ids: np.ndarray, cfg: dict) -> np.ndarray:
    """[len(ids), top_k] distinct channels (= global experts) for each token
    id: ``top_k / topk_group`` experts in each of ``topk_group`` distinct
    groups, uniform over groups and over the experts of a group."""
    e, k = router_width(cfg), cfg["num_experts_per_tok"]
    groups, keep = cfg.get("n_group", 1), cfg.get("topk_group", 1)
    size, per = e // groups, k // keep
    if k % keep or per > size or keep > groups or e % groups:
        raise ValueError(f"cannot mark {k} of {e} experts in {keep} of "
                         f"{groups} groups evenly")
    # groups: a start and an odd step (distinct modulo a power of two) or a
    # step of one; experts in a group: a start and consecutive offsets
    step = 1 + 2 * ((ids // groups) % max(groups // 2, 1)) if (
        groups & (groups - 1)) == 0 else np.ones_like(ids)
    cols = []
    for j in range(keep):
        group = (ids + j * step) % groups
        start = (ids // (groups * max(groups // 2, 1)) + 5 * j) % size
        for m in range(per):
            cols.append(group * size + (start + m) % size)
    ch = np.stack(cols, -1)
    probe = ch[: min(len(ch), 4 * e * e)]
    if any(len(set(row)) < k or len({c // size for c in row}) != keep
           for row in probe):
        raise ValueError(f"routing channels of {k} in {keep} groups collide")
    return ch


def routing_embed(embed: np.ndarray, cfg: dict) -> None:
    """Give the first ``router_width`` channels of the embedding to the
    router."""
    ids = np.arange(embed.shape[0])
    embed[:, :router_width(cfg)] = 0.0
    for col in routing_channels(ids, cfg).T:
        embed[ids, col] = ROUTE_MARK


# -- the checkpoint --------------------------------------------------------------

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
        plain(f, layout, p + "self_attn.q_a_layernorm.weight",
              norm(next(r), cfg["q_lora_rank"]))
        plain(f, layout, p + "self_attn.kv_a_layernorm.weight",
              norm(next(r), cfg["kv_lora_rank"]))
        linears = layer_linears(cfg, i)
        if is_expert_layer(cfg, i):  # row e reads routing channel e alone
            plain(f, layout, p + "mlp.gate.weight",
                  np.eye(width, h, dtype=np.float32))
        for suffix, (fan_in, out) in linears.items():
            # each tensor's generator is named by its place in the layer of
            # the UNCUT model, so that a share's experts are the same
            # tensors whichever share holds them
            writes_residual = suffix.endswith(("o_proj.weight",
                                               "down_proj.weight"))
            linear(f, _tensor_rng(seed, i, suffix), layout, p + suffix,
                   fan_in, out, zero_rows=width if writes_residual else 0)
        return f.write()

    def ends():
        f = File(model_dir / "model-ends.safetensors")
        r = rngs(seed, layers)
        embed = small(next(r), (v, h), 1.0 / math.sqrt(h))
        if routed:
            routing_embed(embed, cfg)
        plain(f, layout, "model.embed_tokens.weight", embed)
        plain(f, layout, "model.norm.weight", norm(next(r), h))
        linear(f, next(r), layout, "lm_head.weight", h, v)
        return f.write()

    jobs = [ends] + [lambda i=i: layer(i) for i in range(layers)]
    return write_files(model_dir, layout, jobs, hf_config(cfg, HF_KEYS),
                       workers)


def _tensor_rng(seed: int, layer: int, suffix: str) -> np.random.Generator:
    """The generator of one linear: (seed, layer, the tensor's name)."""
    return np.random.Generator(np.random.SFC64(
        [seed, layer, zlib.crc32(suffix.encode())]))


# -- the float32 reference -----------------------------------------------------

def yarn_m(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_angles(cfg: dict, t: int) -> tuple[np.ndarray, np.ndarray]:
    """(cos, sin) ``[t, rope/2]`` for positions ``0..t-1``: YaRN blends
    pair ``i``'s frequency ``theta^(-2i/d)`` with that divided by
    ``factor`` along a linear ramp between the correction dims of
    ``beta_fast`` and ``beta_slow`` over the original window; both tables
    times ``m(mscale) / m(mscale_all_dim)``."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    amp = 1.0
    rs = cfg.get("rope_scaling")
    if rs:
        factor = float(rs["factor"])
        orig = float(rs["original_max_position_embeddings"])

        def corr(turns: float) -> float:
            return (d * math.log(orig / (turns * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(corr(rs.get("beta_fast", 32))), 0)
        high = min(math.ceil(corr(rs.get("beta_slow", 1))), d - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
        inv = inv / factor * ramp + inv * (1.0 - ramp)
        amp = (yarn_m(factor, rs.get("mscale", 1.0))
               / yarn_m(factor, rs.get("mscale_all_dim", 0.0)))
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    return ((np.cos(ang) * amp).astype(np.float32),
            (np.sin(ang) * amp).astype(np.float32))


def rope_pairs(x: np.ndarray, cos: np.ndarray, sin: np.ndarray) -> np.ndarray:
    """``x [..., t, d]``: rotate the interleaved pairs ``(x[2i], x[2i+1])``."""
    even, odd = x[..., 0::2], x[..., 1::2]
    out = np.empty_like(x)
    out[..., 0::2] = even * cos - odd * sin
    out[..., 1::2] = even * sin + odd * cos
    return out


def _attention(cfg: dict, ck: Layer, p: str, x: np.ndarray) -> np.ndarray:
    t = x.shape[0]
    nh = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, dc = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    a = p + "self_attn."
    c_q = rms_norm(x @ ck.f32(a + "q_a_proj.weight").T,
                   ck.f32(a + "q_a_layernorm.weight"), eps)
    q = (c_q @ ck.f32(a + "q_b_proj.weight").T).reshape(t, nh, dn + dr)
    ckv = x @ ck.f32(a + "kv_a_proj_with_mqa.weight").T
    c = rms_norm(ckv[:, :dc], ck.f32(a + "kv_a_layernorm.weight"), eps)
    kv = (c @ ck.f32(a + "kv_b_proj.weight").T).reshape(t, nh, dn + dv)
    cos, sin = rope_angles(cfg, t)
    q = q.transpose(1, 0, 2)  # [H, t, dn + dr]
    q_pe = rope_pairs(np.ascontiguousarray(q[..., dn:]), cos, sin)
    k_pe = rope_pairs(np.ascontiguousarray(ckv[:, dc:]), cos, sin)  # [t, dr]
    k_nope = kv[:, :, :dn].transpose(1, 0, 2)
    v = kv[:, :, dn:].transpose(1, 0, 2)
    scale = (dn + dr) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        scale *= yarn_m(float(rs["factor"]), rs["mscale_all_dim"]) ** 2
    scores = (q[..., :dn] @ k_nope.transpose(0, 2, 1)
              + q_pe @ k_pe.T[None]) * np.float32(scale)
    ok = np.arange(t)[None, :] <= np.arange(t)[:, None]
    scores = np.where(ok[None], scores, np.float32(-np.inf))
    scores = scores - scores.max(-1, keepdims=True)
    w = np.exp(scores)
    w /= w.sum(-1, keepdims=True)
    out = (w @ v).transpose(1, 0, 2).reshape(t, nh * dv)
    return out @ ck.f32(a + "o_proj.weight").T


def route(cfg: dict, scores: np.ndarray):
    """``scores [t, E]`` (sigmoid) -> (chosen ``[t, k]``, weights ``[t, k]``,
    margin ``[t]``): a group's score is the sum of its 2 highest; the
    ``topk_group`` best groups stay; top-k of the scores inside them;
    weights are those scores over their sum (+1e-20) times
    ``routed_scaling_factor``. Ties go to the lower index. The margin is
    how far the last expert chosen lies above the first one left out, in
    units of the token's scores' spread."""
    t, e = scores.shape
    groups, keep = cfg.get("n_group", 1), cfg.get("topk_group", 1)
    k = cfg["num_experts_per_tok"]
    choice = scores
    if groups > 1:
        grouped = scores.reshape(t, groups, e // groups)
        group_score = np.sort(grouped, axis=-1)[..., -2:].sum(-1)
        kept = np.argsort(-group_score, axis=-1, kind="stable")[:, :keep]
        in_kept = np.zeros((t, groups), bool)
        np.put_along_axis(in_kept, kept, True, axis=1)
        choice = np.where(in_kept[..., None], grouped,
                          np.float32(-1.0)).reshape(t, e)
    ranked = np.argsort(-choice, axis=-1, kind="stable")
    idx = ranked[:, :k]
    by_rank = np.take_along_axis(choice, ranked, -1)
    margin = (by_rank[:, k - 1] - by_rank[:, k]) / (scores.std(-1) + 1e-9)
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
    idx, weight, margin = route(cfg, 1.0 / (1.0 + np.exp(-logits)))
    margins.append(margin)
    out = np.zeros_like(x)
    for e in held_experts(cfg):
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
            x = x + _attention(cfg, layer, p, rms_norm(
                x, layer.f32(p + "input_layernorm.weight"), eps))
            xs[n] = x + _feed_forward(cfg, layer, p, i, rms_norm(
                x, layer.f32(p + "post_attention_layernorm.weight"), eps),
                margins[n])
    return score_pairs(ck, eps, pairs, xs, margins)


# -- bytes a decode step must read ---------------------------------------------

def held_experts_hit(cfg: dict, rows: float) -> float:
    """How many of the experts held here some row is routed to, with
    ``rows`` rows each choosing ``num_experts_per_tok`` of the router's
    experts uniformly."""
    width = router_width(cfg)
    return (expected_experts(width, cfg["num_experts_per_tok"], rows)
            * cfg["n_routed_experts"] / width)


def weight_bytes(cfg: dict, layout: str, serve_dtype: str = "bf16",
                 rows: float | None = None) -> float:
    """Bytes of weights one decode step reads (``rows`` live streams: the
    non-expert weights once, of the HELD experts those some row is routed
    to, the routers, the head's slice), or with ``rows=None`` all the
    weights the device holds, embedding included: the number a parameter
    count checks."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    plain_b = PLAIN_BYTES[serve_dtype]
    held = cfg.get("n_routed_experts") or 0
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
    """Bytes of latent rows ``rows`` streams at a mean position of
    ``context`` read in one step: one row a token a layer, for all heads."""
    return (rows * context * cfg["num_hidden_layers"]
            * cache_row_values(cfg) * PLAIN_BYTES[cache_dtype])


def decode_step_bytes(cfg: dict, layout: str, rows: float, context: float,
                      serve_dtype: str = "bf16") -> float:
    """The least one decode step reads: the weights for ``rows`` live
    streams and their latent rows at a mean position of ``context``."""
    return (weight_bytes(cfg, layout, serve_dtype, rows)
            + kv_bytes(cfg, context, rows, serve_dtype))
