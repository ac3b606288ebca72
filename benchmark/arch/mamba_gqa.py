"""Architecture ``mamba_gqa``: a decoder whose layers are selective
state-space mixers (Mamba-1: ``d_inner = mamba_expand * hidden_size``
channels, each a float32 state of ``mamba_d_state`` values a stream,
whatever the stream's length) but one in ``attn_layer_period``, which is
grouped-query attention with NO rotary or other position embedding; a
dense SwiGLU in every layer; a tied head: AI21 Jamba's ``config.json`` keys
(``model_type`` ``jamba``, ``num_experts`` 1).

Numpy and the standard library only (the parent of a chip run never
imports JAX). The writer puts the tensors under the names the program's
loader reads, which are Hugging Face ``modeling_jamba``'s (the
configuration's ``assumed.tensor_names``: ``config.json`` does not carry
them): ``mamba.{in_proj,x_proj,dt_proj,out_proj}.weight``,
``mamba.conv1d.{weight,bias}`` (torch depthwise ``[C, 1, K]``),
``mamba.dt_proj.bias``, ``mamba.A_log`` (``[d_inner, d_state]``),
``mamba.D``, ``mamba.{dt,b,c}_layernorm.weight``;
``self_attn.{q,k,v,o}_proj.weight``;
``feed_forward.{gate,up,down}_proj.weight``; ``input_layernorm``,
``pre_ff_layernorm``; ``model.final_layernorm``. The head is the embedding:
the file holds that matrix under both names (``model.embed_tokens.weight``
and ``lm_head.weight``, the same values), as the program's loader holds it
on the device (an embedding to gather from and a head to multiply by).

The reference is written from the equations ISSUE 34 states (Motivation;
``modeling_jamba``'s slow path): the recurrence token by token from a zero
state, expanded attention without rotation, no cache: see ``_mamba`` and
``_attention``.

What is no linear follows Mamba's own initialisation, rounded to what
bfloat16 holds (so that both layouts carry the same numbers and the
server's cast loses nothing): ``A_log = log(1..d_state)`` a channel, the
step size's bias the inverse softplus of a step log-uniform in 0.001-0.1
(so a token decays a state by 0.2-0.999 and a state remembers tens to
hundreds of tokens), ``D = 1``, taps of std 0.5, a convolution bias of std
0.25.
"""

from __future__ import annotations

import math
from pathlib import Path

import numpy as np

from reference import Layer, rms_norm, score_pairs, silu, swiglu
from shapes import PLAIN_BYTES, linear_bytes
from weights import (Checkpoint, File, bf16_bits, hf_config, linear, norm,
                     plain, pow2_scale, rngs, small, write_files)

WRITER_VERSION = 1  # part of the key a checkpoint's directory is named by
REFERENCE_VERSION = 1  # part of the key under which answers are kept

# keys of a configuration file that are the model's own config.json (what
# the server reads); everything else in the file is the benchmark's
HF_KEYS = (
    "architectures", "model_type", "attn_layer_offset", "attn_layer_period",
    "expert_layer_offset", "expert_layer_period", "hidden_act",
    "hidden_size", "intermediate_size", "mamba_conv_bias", "mamba_d_conv",
    "mamba_d_state", "mamba_dt_rank", "mamba_expand", "mamba_proj_bias",
    "max_position_embeddings", "num_attention_heads", "num_experts",
    "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads",
    "num_logits_to_keep", "rms_norm_eps", "sliding_window",
    "tie_word_embeddings", "use_mamba_kernels", "vocab_size", "torch_dtype",
    "bos_token_id", "eos_token_id",
)

STEP_RANGE = (0.001, 0.1)  # the step sizes the bias is the inverse softplus of


# -- sizes -----------------------------------------------------------------------

def is_attention_layer(cfg: dict, i: int) -> bool:
    return i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def mamba_layers(cfg: dict) -> int:
    return sum(not is_attention_layer(cfg, i)
               for i in range(cfg["num_hidden_layers"]))


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_linears(cfg: dict, i: int) -> dict[str, tuple[int, int]]:
    """HF suffix -> (fan_in, out) of layer ``i``'s linears."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    if is_attention_layer(cfg, i):
        q = cfg["num_attention_heads"] * head_dim(cfg)
        kv = cfg["num_key_value_heads"] * head_dim(cfg)
        lin = {"self_attn.q_proj.weight": (h, q),
               "self_attn.k_proj.weight": (h, kv),
               "self_attn.v_proj.weight": (h, kv),
               "self_attn.o_proj.weight": (q, h)}
    else:
        c, n, r = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"]
        lin = {"mamba.in_proj.weight": (h, 2 * c),
               "mamba.x_proj.weight": (c, r + 2 * n),
               "mamba.dt_proj.weight": (r, c),
               "mamba.out_proj.weight": (c, h)}
    lin.update({"feed_forward.gate_proj.weight": (h, f),
                "feed_forward.up_proj.weight": (h, f),
                "feed_forward.down_proj.weight": (f, h)})
    return lin


def _plain_values(cfg: dict, i: int) -> int:
    """Unquantized values of layer ``i``: its two norms, and a state-space
    layer's taps and their bias, step bias, ``A_log``, ``D`` and three
    inner norms."""
    n = 2 * cfg["hidden_size"]
    if not is_attention_layer(cfg, i):
        c, s = d_inner(cfg), cfg["mamba_d_state"]
        bias = 1 if cfg.get("mamba_conv_bias", True) else 0
        n += (c * (cfg["mamba_d_conv"] + bias + 2 + s)
              + cfg["mamba_dt_rank"] + 2 * s)
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


def state_bytes_per_stream(cfg: dict, cache_dtype: str = "bf16") -> int:
    """Bytes of recurrent state a stream holds, whatever its length: a
    float32 ``[d_state, d_inner]`` state and the last ``taps - 1`` inputs
    of the convolution, a state-space layer."""
    c = d_inner(cfg)
    return mamba_layers(cfg) * c * (
        cfg["mamba_d_state"] * 4
        + (cfg["mamba_d_conv"] - 1) * PLAIN_BYTES[cache_dtype])


# -- the checkpoint --------------------------------------------------------------

def _bf16(x: np.ndarray) -> np.ndarray:
    """float32 values rounded (to nearest, ties to even) to what bfloat16
    holds."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) >> 16 << 16
    return u.astype(np.uint32).view(np.float32)


def step_bias(rng, c: int) -> np.ndarray:
    """The inverse softplus of a step log-uniform in ``STEP_RANGE``, a
    channel."""
    lo, hi = (math.log(s) for s in STEP_RANGE)
    step = np.exp(rng.random(c) * (hi - lo) + lo)
    return _bf16(step + np.log(-np.expm1(-step)))


def write_checkpoint(cfg: dict, layout: str, seed: int, model_dir: Path,
                     workers: int = 8) -> dict:
    """Write the checkpoint of configuration ``cfg`` (a configuration
    file's dict) into ``model_dir``; returns {"bytes", "files"}."""
    if layout not in ("q8", "bf16"):
        raise ValueError(f"unknown checkpoint layout {layout!r}")
    model_dir.mkdir(parents=True, exist_ok=True)
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    c, n = d_inner(cfg), cfg["mamba_d_state"]
    layers = cfg["num_hidden_layers"]

    def layer(i: int):
        f = File(model_dir / f"model-layer-{i:05d}.safetensors")
        r = rngs(seed, i)
        p = f"model.layers.{i}."
        plain(f, layout, p + "input_layernorm.weight", norm(next(r), h))
        plain(f, layout, p + "pre_ff_layernorm.weight", norm(next(r), h))
        if not is_attention_layer(cfg, i):
            m = p + "mamba."
            plain(f, layout, m + "conv1d.weight",  # torch depthwise [C, 1, K]
                  small(next(r), (c, 1, cfg["mamba_d_conv"]), 0.5))
            if cfg.get("mamba_conv_bias", True):
                plain(f, layout, m + "conv1d.bias", small(next(r), (c,), 0.25))
            plain(f, layout, m + "dt_proj.bias", step_bias(next(r), c))
            plain(f, layout, m + "A_log", np.tile(_bf16(np.log(
                np.arange(1, n + 1, dtype=np.float32))), (c, 1)))
            plain(f, layout, m + "D", np.ones((c,), np.float32))
            plain(f, layout, m + "dt_layernorm.weight",
                  norm(next(r), cfg["mamba_dt_rank"]))
            plain(f, layout, m + "b_layernorm.weight", norm(next(r), n))
            plain(f, layout, m + "c_layernorm.weight", norm(next(r), n))
        for suffix, (fan_in, out) in layer_linears(cfg, i).items():
            linear(f, next(r), layout, p + suffix, fan_in, out)
        return f.write()

    def ends():
        f = File(model_dir / "model-ends.safetensors")
        r = rngs(seed, layers)
        base = pow2_scale(1.0 / math.sqrt(h))
        embed = small(next(r), (v, h), 1.0 / math.sqrt(h))
        plain(f, layout, "model.embed_tokens.weight", embed)
        plain(f, layout, "model.final_layernorm.weight", norm(next(r), h))
        # the tied matrix again, as a head: the same values (an int8 times
        # one power of two, so the q8 layout holds them exactly too)
        if layout == "q8":
            f.add("lm_head.weight.q8", "I8", (v, h),
                  np.round(embed / np.float32(base)).astype(np.int8))
            f.add("lm_head.weight.scale", "F32", (v,),
                  np.full((v,), base, np.float32))
        else:
            f.add("lm_head.weight", "BF16", (v, h), bf16_bits(embed))
        return f.write()

    jobs = [ends] + [lambda i=i: layer(i) for i in range(layers)]
    return write_files(model_dir, layout, jobs, hf_config(cfg, HF_KEYS),
                       workers)


# -- the float32 reference -----------------------------------------------------

def _softplus(x: np.ndarray) -> np.ndarray:
    return (np.maximum(x, 0) + np.log1p(np.exp(-np.abs(x)))).astype(
        np.float32)


def _mamba(cfg: dict, ck: Layer, p: str, u: np.ndarray) -> np.ndarray:
    """The state-space mixer of one sequence, a token at a time from a
    zero state, float32."""
    t = u.shape[0]
    c, n, r = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    taps, eps = cfg["mamba_d_conv"], cfg["rms_norm_eps"]
    m = p + "mamba."
    xz = u @ ck.f32(m + "in_proj.weight").T
    x, z = xz[:, :c], xz[:, c:]
    w = ck.f32(m + "conv1d.weight")[:, 0, :]  # [C, K]
    padded = np.concatenate([np.zeros((taps - 1, c), np.float32), x])
    x = sum(padded[j:j + t] * w[:, j] for j in range(taps))
    if cfg.get("mamba_conv_bias", True):
        x = x + ck.f32(m + "conv1d.bias")
    x = silu(x).astype(np.float32)
    dbc = x @ ck.f32(m + "x_proj.weight").T
    dt = rms_norm(dbc[:, :r], ck.f32(m + "dt_layernorm.weight"), eps)
    bm = rms_norm(dbc[:, r:r + n], ck.f32(m + "b_layernorm.weight"), eps)
    cm = rms_norm(dbc[:, r + n:], ck.f32(m + "c_layernorm.weight"), eps)
    delta = _softplus(dt @ ck.f32(m + "dt_proj.weight").T
                      + ck.f32(m + "dt_proj.bias"))  # [t, C]
    a = -np.exp(ck.f32(m + "A_log"))  # [C, N]
    s = np.zeros((c, n), np.float32)
    y = np.empty((t, c), np.float32)
    for i in range(t):
        s = np.exp(delta[i][:, None] * a) * s + (
            delta[i] * x[i])[:, None] * bm[i][None, :]
        y[i] = s @ cm[i]
    y = y + ck.f32(m + "D") * x
    return (y * silu(z)).astype(np.float32) @ ck.f32(m + "out_proj.weight").T


def _attention(cfg: dict, ck: Layer, p: str, x: np.ndarray) -> np.ndarray:
    """Grouped-query causal attention with no position embedding."""
    t = x.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    a = p + "self_attn."
    q = (x @ ck.f32(a + "q_proj.weight").T).reshape(t, nh, d)
    k = (x @ ck.f32(a + "k_proj.weight").T).reshape(t, nkv, d)
    v = (x @ ck.f32(a + "v_proj.weight").T).reshape(t, nkv, d)
    q, k, v = (w.transpose(1, 0, 2) for w in (q, k, v))
    k = np.repeat(k, nh // nkv, axis=0)  # kv head g serves q heads g*r..
    v = np.repeat(v, nh // nkv, axis=0)
    scores = q @ k.transpose(0, 2, 1) / np.float32(np.sqrt(d))
    ok = np.arange(t)[None, :] <= np.arange(t)[:, None]
    scores = np.where(ok[None], scores, np.float32(-np.inf))
    scores = scores - scores.max(-1, keepdims=True)
    w = np.exp(scores)
    w /= w.sum(-1, keepdims=True)
    out = (w @ v).transpose(1, 0, 2).reshape(t, nh * d)
    return out @ ck.f32(a + "o_proj.weight").T


class _Ends:
    """The checkpoint under the names ``reference.score_pairs`` asks for:
    this family's last norm is ``model.final_layernorm``, and its head is
    the embedding."""

    NAMES = {"model.norm.weight": "model.final_layernorm.weight",
             "lm_head.weight": "model.embed_tokens.weight"}

    def __init__(self, ck: Checkpoint):
        self.ck = ck

    def f32(self, name: str) -> np.ndarray:
        return self.ck.f32(self.NAMES.get(name, name))


def chosen_logprobs(cfg: dict, model_dir, pairs: list[tuple]) -> list[dict]:
    """For each (prompt, chosen) pair: the log-probabilities the reference
    gives the ``chosen`` continuation of ``prompt``, token by token, and
    its own best token at each place (``reference.score_pairs``;
    ``routing_margin`` None: nothing is routed). A layer at a time, so
    that the published widths fit the host."""
    ck = Checkpoint(model_dir)
    eps = cfg["rms_norm_eps"]
    embed = ck.f32("model.embed_tokens.weight")
    xs = [embed[np.asarray(list(prompt) + list(chosen[:-1]), np.int64)]
          for prompt, chosen in pairs]
    del embed
    for i in range(cfg["num_hidden_layers"]):
        p, layer = f"model.layers.{i}.", Layer(ck)
        mixer = _attention if is_attention_layer(cfg, i) else _mamba
        f = p + "feed_forward."
        for n, x in enumerate(xs):
            x = x + mixer(cfg, layer, p, rms_norm(
                x, layer.f32(p + "input_layernorm.weight"), eps))
            xs[n] = x + swiglu(
                rms_norm(x, layer.f32(p + "pre_ff_layernorm.weight"), eps),
                layer.f32(f + "gate_proj.weight"),
                layer.f32(f + "up_proj.weight"),
                layer.f32(f + "down_proj.weight"))
    return score_pairs(_Ends(ck), eps, pairs, xs, [[] for _ in pairs])


# -- bytes a decode step must move ---------------------------------------------

def weight_bytes(cfg: dict, layout: str, serve_dtype: str = "bf16",
                 rows: float | None = None) -> float:
    """Bytes of weights one decode step reads (``rows`` live streams: every
    layer once, the head once, an embedding row a stream: the tied matrix
    is read once, as the head; the embedding is a gather), or with
    ``rows=None`` all the weights the device holds, the tied matrix as the
    program holds it (an embedding and a head): the number a parameter
    count checks."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    plain_b = PLAIN_BYTES[serve_dtype]
    total = 0.0
    for i in range(cfg["num_hidden_layers"]):
        total += _plain_values(cfg, i) * plain_b + sum(
            linear_bytes(fan_in, out, layout)
            for fan_in, out in layer_linears(cfg, i).values())
    embed_rows = v if rows is None else rows
    return (total + embed_rows * h * plain_b + h * plain_b
            + linear_bytes(h, v, layout))


def ssm_decode_bytes(cfg: dict, rows: float) -> float:
    """Bytes one state-space layer's decode step must move for ``rows``
    streams (the kernel ``ssm_decode``): one read and one write of each
    stream's float32 ``[d_state, d_inner]`` state, and the step's delta,
    x, B and C in and y out (float32)."""
    c, n = d_inner(cfg), cfg["mamba_d_state"]
    return 4.0 * rows * (2 * n * c + 3 * c + 2 * n)


def state_bytes(cfg: dict, rows: float, cache_dtype: str = "bf16") -> float:
    """Bytes of recurrent state ``rows`` streams move in one step: every
    state-space layer's state and convolution tail read and written
    once."""
    return 2.0 * rows * state_bytes_per_stream(cfg, cache_dtype)


def kv_bytes(cfg: dict, context: float, rows: float,
             cache_dtype: str = "bf16") -> float:
    """Bytes of keys and values ``rows`` streams at a mean position of
    ``context`` read in one step: the ATTENTION layers' alone."""
    attention = cfg["num_hidden_layers"] - mamba_layers(cfg)
    return (rows * context * attention * 2 * cfg["num_key_value_heads"]
            * head_dim(cfg) * PLAIN_BYTES[cache_dtype])


def decode_step_bytes(cfg: dict, layout: str, rows: float, context: float,
                      serve_dtype: str = "bf16") -> float:
    """The least one decode step moves: the weights for ``rows`` live
    streams (the tied matrix once), their keys and values at a mean
    position of ``context``, and their recurrent state once in and once
    out."""
    return (weight_bytes(cfg, layout, serve_dtype, rows)
            + kv_bytes(cfg, context, rows, serve_dtype)
            + state_bytes(cfg, rows, serve_dtype))
