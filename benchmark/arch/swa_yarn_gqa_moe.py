"""Architecture ``swa_yarn_gqa_moe``: a decoder whose layers are
grouped-query attention through a sliding window (``layer_types[i] ==
"sliding_attention"``: query ``t`` sees keys ``j`` with ``0 <= t - j <
sliding_window``) but some, which attend fully (``"full_attention"``), and
whose two KINDS of layer rotate q and k differently:
``rope_parameters[kind]`` gives each its own rotation, the default one or
YaRN with an explicit ``attention_factor`` on cos and sin. Every head of q
and k is RMS-normed before the rotation. Every layer's feed-forward routes
over ``num_experts`` softmax-scored experts, ALL of them held (or a chip's
share, ``expert_share``, as ``arch/mla_moe.py`` says), the
``num_experts_per_tok`` largest shares renormalised over their sum; no
shared expert, no bias, no scaling factor: Mellum2's ``config.json`` keys
(``model_type`` ``mellum``).

Numpy and the standard library only (the parent of a chip run never
imports JAX). The sizes, the linears' names and what a cache holds are
``arch/swa_gqa_moe.py``'s (the same tensors but the shared expert and the
bias, which this family has not); the routing channels and the generator a
tensor is drawn from are ``arch/mla_moe.py``'s, through it. The writer puts
the tensors under the names the program's loader reads; they are ASSUMED
(the configuration's ``assumed.tensor_names``): Qwen3-MoE's, which are
Llama's for the attention with ``self_attn.q_norm`` / ``k_norm`` (one
``[head_dim]`` weight each) and ``mlp.gate.weight``, ``mlp.experts.{e}.*``
for the experts.

The reference is written from the equations ISSUE 55 states (Tentpole):
pre-norm sublayers, the whole sequence at once under explicit masks, no
cache and no ring, each kind's table built from its own
``rope_parameters`` entry (``rotation``), the scores a block of
``QUERY_ROWS`` query rows and a key/value head at a time so that 5000
tokens fit the host, softmax over ALL experts and then the renormalised
top-k (the long form; the program takes softmax over the chosen logits), a
loop over the experts.

What the cache holds and a step reads (``swa_gqa_moe.kv_bytes``): a FULL
layer keeps every row and a step reads ``context`` of them; a WINDOW layer
keeps a ring of ``R >= sliding_window`` rows a stream whatever the capacity
and a step reads the ``min(context, sliding_window)`` its query sees: the
same work whatever implements it (a program that sweeps the whole ring
pays for it in its share of the roofline; its counters
``attn.ring_rows_live`` / ``_swept`` say how much, ``layer_metrics/
cache.ring_live_share.py``).

A random router must not hang on rounding (``weights.py`` says why): the
first ``num_experts`` channels of the residual stream belong to the router,
the embedding marks ``num_experts_per_tok`` of them per token id, no linear
writes to them, the router's row ``e`` reads channel ``e`` alone. A marked
channel's logit is ``ROUTE_MARK`` over the token's root mean square times
the norm's weight there (0.875-1.25), an unmarked one's exactly 0: the
chosen experts are a function of the token, their softmax shares differ by
the norm's weights, and the margin is a marked logit's height.

Random heads must not average their keys either (``HEAD_NORM_GAIN``): the
heads' q and k norm weights are twice the other norms' (1.75-2.5 in
quarters, exact in bfloat16), so that a score spreads ~4.5 and attention
is as peaked as a trained model's; otherwise ``correct`` could not tell a
window of 1023 keys, or one rotation for both kinds, from the model.
"""

from __future__ import annotations

import math
from pathlib import Path

import numpy as np

from arch import swa_gqa_moe as swa
from reference import Layer, rms_norm, score_pairs, swiglu
from shapes import PLAIN_BYTES, linear_bytes
from weights import (Checkpoint, File, hf_config, linear, norm, plain, rngs,
                     small, write_files)

WRITER_VERSION = 1  # part of the key a checkpoint's directory is named by
REFERENCE_VERSION = 1  # part of the key under which answers are kept

# keys of a configuration file that are the model's own config.json (what
# the server reads); everything else in the file is the benchmark's
HF_KEYS = (
    "architectures", "model_type", "attention_bias", "head_dim",
    "hidden_act", "hidden_size", "intermediate_size", "layer_types",
    "mlp_layer_types", "max_position_embeddings", "max_window_layers",
    "moe_intermediate_size", "norm_topk_prob", "num_attention_heads",
    "num_experts", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "rms_norm_eps", "rope_parameters",
    "sliding_window", "tie_word_embeddings", "vocab_size",
    "use_sliding_window", "torch_dtype", "expert_share", "bos_token_id",
    "eos_token_id",
)

QUERY_ROWS = 512  # query rows a block of the reference's scores
MODEL_TYPE = "mellum"
# what the heads' q and k norm weights (0.875-1.25 in eighths, as every
# norm's) are multiplied by: at 1 a score has a spread of ~1 and a head's
# softmax over a thousand keys is nearly their mean, so that what a query
# may see, and under which rotation, hardly reaches the logits (a window of
# 1023 keys moved no probe token and no logit by more than 0.015 nats: my
# chip run and host readings, PR 55); at 2 the scores spread ~4.5, a head
# has a few keys it attends to, as a trained model's has, and the same
# control moves the logits by 0.3-0.5 nats. The work is the same.
HEAD_NORM_GAIN = 2.0


def require_family(checkout: Path) -> None:
    """Refuse a checkout whose program does not name this family. A
    program from before it reads ``model_type`` "mellum" as a bare stack
    of one kind of layer (a window on every layer, one rotation, Mixtral's
    expert names): whatever it would make of the checkpoint is not this
    model. Such a checkout cannot run this configuration, and a run on it
    fails here, at once, and measures nothing under the cell's name. Asked
    of the source (the parent of a chip run imports neither JAX nor
    ``cake_tpu``): a family is declared by its ``model_type`` under
    ``cake_tpu/models/``."""
    models = checkout / "cake_tpu" / "models"
    if not any(f'"{MODEL_TYPE}"' in path.read_text()
               for path in sorted(models.glob("*.py"))):
        raise RuntimeError(
            f"no module under {models} declares model_type {MODEL_TYPE!r}: "
            "this program has no rotation a layer kind and no "
            "softmax-scored told-share expert layer; the cell needs the "
            "program's window family to read this model_type "
            "(cake_tpu/models/families.py)")


require_family(Path(__file__).resolve().parents[2])


# -- sizes -----------------------------------------------------------------------

def held_experts(cfg: dict) -> range:
    """Global ids of the experts held here (all of them, or a share)."""
    return swa.held_experts(cfg)


def expert_layers(cfg: dict) -> int:
    """Layers that route: every one."""
    return swa.expert_layers(cfg)


def _plain_values(cfg: dict, i: int) -> int:
    """Unquantized values of layer ``i``: its two norms, the heads' q and k
    norms and the router (no bias)."""
    return (2 * cfg["hidden_size"] + 2 * cfg["head_dim"]
            + swa.router_width(cfg) * cfg["hidden_size"])


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
            for a, b in swa.layer_linears(cfg, i).values())
    return total


# -- the checkpoint --------------------------------------------------------------

def write_checkpoint(cfg: dict, layout: str, seed: int, model_dir: Path,
                     workers: int = 8) -> dict:
    """Write the checkpoint of configuration ``cfg`` (a configuration
    file's dict) into ``model_dir``; returns {"bytes", "files"}."""
    if layout not in ("q8", "bf16"):
        raise ValueError(f"unknown checkpoint layout {layout!r}")
    if not all(swa.is_expert_layer(cfg, i)
               for i in range(cfg["num_hidden_layers"])):
        raise ValueError("every layer of this family is sparse")
    model_dir.mkdir(parents=True, exist_ok=True)
    h, v, d = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    layers, width = cfg["num_hidden_layers"], swa.router_width(cfg)
    if width > h // 2:
        raise ValueError(f"{width} routing channels of {h}")

    def layer(i: int):
        f = File(model_dir / f"model-layer-{i:05d}.safetensors")
        r = rngs(seed, i)
        p = f"model.layers.{i}."
        plain(f, layout, p + "input_layernorm.weight", norm(next(r), h))
        plain(f, layout, p + "post_attention_layernorm.weight",
              norm(next(r), h))
        plain(f, layout, p + "self_attn.q_norm.weight",
              norm(next(r), d) * np.float32(HEAD_NORM_GAIN))
        plain(f, layout, p + "self_attn.k_norm.weight",
              norm(next(r), d) * np.float32(HEAD_NORM_GAIN))
        # row e reads routing channel e alone
        plain(f, layout, p + "mlp.gate.weight",
              np.eye(width, h, dtype=np.float32))
        for suffix, (fan_in, out) in swa.layer_linears(cfg, i).items():
            # each tensor's generator is named by its place in the layer of
            # the UNCUT model, so that a share's experts are the same
            # tensors whichever share holds them
            writes_residual = suffix.endswith(("o_proj.weight",
                                               "down_proj.weight"))
            linear(f, swa._mla._tensor_rng(seed, i, suffix), layout,
                   p + suffix, fan_in, out,
                   zero_rows=width if writes_residual else 0)
        return f.write()

    def ends():
        f = File(model_dir / "model-ends.safetensors")
        r = rngs(seed, layers)
        embed = small(next(r), (v, h), 1.0 / math.sqrt(h))
        swa._mla.routing_embed(embed, swa._as_mla(cfg))
        plain(f, layout, "model.embed_tokens.weight", embed)
        plain(f, layout, "model.norm.weight", norm(next(r), h))
        linear(f, next(r), layout, "lm_head.weight", h, v)
        return f.write()

    jobs = [ends] + [lambda i=i: layer(i) for i in range(layers)]
    return write_files(model_dir, layout, jobs, hf_config(cfg, HF_KEYS),
                       workers)


# -- the float32 reference -----------------------------------------------------

def rotation(rope: dict, t: int, d: int) -> tuple[np.ndarray, np.ndarray]:
    """``(cos, sin) [t, d/2]`` of one layer kind's ``rope_parameters``
    entry for positions ``0 .. t-1``. ``default``: pair ``j`` turns by
    ``theta^(-2j/d)`` a position. ``yarn``: that frequency where the pair
    turns more than ``beta_fast`` times over the original window, divided
    by ``factor`` where it turns fewer than ``beta_slow`` times, blended
    linearly between (floor and ceil of the two correction dimensions),
    and both tables times ``attention_factor`` (``0.1 ln(factor) + 1``
    where the file gives none) at every position."""
    theta = float(rope["rope_theta"])
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    amp = 1.0
    kind = rope.get("rope_type", "default")
    if kind == "yarn":
        factor = float(rope["factor"])
        orig = float(rope["original_max_position_embeddings"])

        def correction(turns: float) -> float:
            return (d * math.log(orig / (turns * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(correction(rope.get("beta_fast", 32))), 0)
        high = min(math.ceil(correction(rope.get("beta_slow", 1))), d - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
        inv = inv / factor * ramp + inv * (1.0 - ramp)
        amp = rope.get("attention_factor")
        if amp is None:
            amp = 0.1 * math.log(factor) + 1.0
    elif kind != "default":
        raise ValueError(f"rope type {kind!r}")
    angle = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    return ((np.cos(angle) * amp).astype(np.float32),
            (np.sin(angle) * amp).astype(np.float32))


def rotate(x: np.ndarray, cos: np.ndarray, sin: np.ndarray) -> np.ndarray:
    """``x [heads, t, d]``: the pairs ``(x[j], x[j + d/2])``."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(cfg: dict, ck: Layer, p: str, i: int,
               x: np.ndarray) -> np.ndarray:
    """Layer ``i``'s attention over one whole sequence: q and k normed a
    head, rotated by the layer KIND's table, scores under the explicit
    mask (``0 <= t - j < sliding_window`` on a window layer, ``j <= t`` on
    a full one), a block of query rows and a key/value head at a time."""
    t = x.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, a = cfg["rms_norm_eps"], p + "self_attn."

    def heads(name: str, n: int, normed: bool) -> np.ndarray:
        y = (x @ ck.f32(a + f"{name}_proj.weight").T).reshape(t, n, d)
        if normed:
            y = rms_norm(y, ck.f32(a + f"{name}_norm.weight"), eps)
        return np.ascontiguousarray(y.transpose(1, 0, 2))  # [n, t, d]

    cos, sin = rotation(cfg["rope_parameters"][cfg["layer_types"][i]], t, d)
    q = rotate(heads("q", nh, True), cos, sin)
    k = rotate(heads("k", nkv, True), cos, sin)
    v = heads("v", nkv, False)
    windowed = swa.is_window_layer(cfg, i)
    g = nh // nkv
    out = np.empty((t, nh, d), np.float32)
    at = np.arange(t)
    for lo in range(0, t, QUERY_ROWS):
        rows = at[lo:lo + QUERY_ROWS]
        behind = rows[:, None] - at[None, :]  # t - j
        seen = behind >= 0
        if windowed:
            seen &= behind < cfg["sliding_window"]
        for kh in range(nkv):
            s = (q[kh * g:(kh + 1) * g, rows] @ k[kh].T) * np.float32(
                d ** -0.5)  # [g, rows, t]
            s = np.where(seen[None], s, np.float32(-np.inf))
            s = s - s.max(-1, keepdims=True)
            w = np.exp(s)
            w /= w.sum(-1, keepdims=True)
            out[rows, kh * g:(kh + 1) * g] = (w @ v[kh]).transpose(1, 0, 2)
    return out.reshape(t, nh * d) @ ck.f32(a + "o_proj.weight").T


def route(cfg: dict, logits: np.ndarray):
    """``logits [t, E]`` -> (chosen ``[t, k]``, weights ``[t, k]``, margin
    ``[t]``): softmax over ALL experts, the ``k`` largest shares (ties to
    the lower index), each over the chosen ones' sum. The margin is how
    far the last expert chosen lies above the first one left out, in
    units of the token's logits' spread."""
    k = cfg["num_experts_per_tok"]
    z = logits - logits.max(-1, keepdims=True)
    share = np.exp(z)
    share /= share.sum(-1, keepdims=True)
    ranked = np.argsort(-share, axis=-1, kind="stable")
    idx = ranked[:, :k]
    by_rank = np.take_along_axis(logits, ranked, -1)
    margin = (by_rank[:, k - 1] - by_rank[:, k]) / (logits.std(-1) + 1e-9)
    w = np.take_along_axis(share, idx, -1)
    return idx, w / w.sum(-1, keepdims=True), margin


def _feed_forward(cfg: dict, ck: Layer, p: str, m: np.ndarray,
                  margins: list) -> np.ndarray:
    """The sum over the chosen experts HELD here of ``w_e expert_e(m)``, a
    loop over them; ``margins`` gains each token's routing margin."""
    idx, weight, margin = route(cfg, m @ ck.f32(p + "mlp.gate.weight").T)
    margins.append(margin)
    out = np.zeros_like(m)
    for e in held_experts(cfg):
        rows, slot = np.nonzero(idx == e)
        if len(rows):
            q = f"{p}mlp.experts.{e}."
            out[rows] += weight[rows, slot][:, None] * swiglu(
                m[rows], ck.f32(q + "gate_proj.weight"),
                ck.f32(q + "up_proj.weight"), ck.f32(q + "down_proj.weight"))
    return out


def chosen_logprobs(cfg: dict, model_dir, pairs: list[tuple]) -> list[dict]:
    """For each (prompt, chosen) pair: the log-probabilities the reference
    gives the ``chosen`` continuation of ``prompt``, token by token, and
    its own best token at each place (``reference.score_pairs``). A layer
    at a time, so that the published widths fit the host."""
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
                x, layer.f32(p + "input_layernorm.weight"), eps))
            xs[n] = x + _feed_forward(cfg, layer, p, rms_norm(
                x, layer.f32(p + "post_attention_layernorm.weight"), eps),
                margins[n])
    return score_pairs(ck, eps, pairs, xs, margins)


# -- bytes a decode step must move ---------------------------------------------

def weight_bytes(cfg: dict, layout: str, serve_dtype: str = "bf16",
                 rows: float | None = None) -> float:
    """Bytes of weights one decode step reads (``rows`` live streams: the
    attention and the routers once, of the HELD experts those some row is
    routed to, ``shapes.expected_experts`` through
    ``swa_gqa_moe.held_experts_hit``, the head, an embedding row a
    stream), or with ``rows=None`` all the weights the device holds,
    embedding included: the number a parameter count checks."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    plain_b = PLAIN_BYTES[serve_dtype]
    held = cfg["num_experts"]
    total = 0.0
    for i in range(cfg["num_hidden_layers"]):
        total += _plain_values(cfg, i) * plain_b
        for suffix, (fan_in, out) in swa.layer_linears(cfg, i).items():
            b = linear_bytes(fan_in, out, layout)
            if ".experts." in suffix and rows is not None:
                b *= swa.held_experts_hit(cfg, rows) / held
            total += b
    embed_rows = v if rows is None else rows
    return (total + embed_rows * h * plain_b + h * plain_b
            + linear_bytes(h, v, layout))


def kv_bytes(cfg: dict, context: float, rows: float,
             cache_dtype: str = "bf16") -> float:
    """Bytes of cached rows ``rows`` streams at a mean position of
    ``context`` read in one step: a full layer ``context`` rows, a window
    layer's ring the ``min(context, sliding_window)`` its query sees."""
    return swa.kv_bytes(cfg, context, rows, cache_dtype)


def decode_step_bytes(cfg: dict, layout: str, rows: float, context: float,
                      serve_dtype: str = "bf16") -> float:
    """The least one decode step moves: the weights for ``rows`` live
    streams and their cached rows at a mean position of ``context``."""
    return (weight_bytes(cfg, layout, serve_dtype, rows)
            + kv_bytes(cfg, context, rows, serve_dtype))
