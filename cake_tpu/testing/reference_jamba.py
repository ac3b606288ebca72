"""Plain reference of the state-space + attention hybrid decoder (AI21's
Jamba ``config.json`` keys; ``model_type`` ``jamba``): the whole forward
pass of one sequence in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.

Written from the equations the issue states (ISSUE 34, Motivation; Hugging
Face ``modeling_jamba``'s slow path is the published description), not from
``cake_tpu/ops``: the recurrence token by token from a zero state (no scan
primitive, no chunks), expanded attention, no cache, no batching, no
kernels. It reads a checkpoint's tensors by their Hugging Face names
(``tensors[name]``, torch layouts) and the model's ``config.json`` as a
dict, so it also checks the loader's naming.

Layer ``i`` (0-based) is attention if ``i % attn_layer_period ==
attn_layer_offset`` and a Mamba mixer otherwise; every layer is ``h +=
mixer(rmsnorm(h)); h += swiglu(rmsnorm(h))``.

- Mamba (``d_inner = mamba_expand * hidden_size`` channels, ``N =
  mamba_d_state``): ``[x | z] = u W_in``; ``x = silu(conv(x) + b)``, a
  causal depthwise convolution of ``mamba_d_conv`` taps over time (zeros
  before the sequence); ``[dt | B | C] = x W_x``, each RMS-normed (Jamba's
  three inner norms); ``delta = softplus(dt W_dt + b_dt)``; ``A =
  -exp(A_log)`` ``[d_inner, N]``; ``S_t = exp(delta_t A) S_{t-1} + delta_t
  x_t B_t^T`` from ``S_0 = 0``; ``y_t = S_t C_t + D x_t``; ``out = (y *
  silu(z)) W_out``.
- attention: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads, no bias, NO rotary or other
  position embedding, scale ``head_dim^-0.5``, causal softmax.
- the head is the embedding where the checkpoint stores no
  ``lm_head.weight`` (``tie_word_embeddings``); the last norm is
  ``model.final_layernorm``.

Departures from the published description: none in the mathematics. The
published code holds activations in the checkpoint's type (bfloat16) and
the state in float32; here everything is float32. ``state_dtype`` and
``linear_dtype`` exist for the controls that must FAIL the tolerance (the
state rounded after every step; every linear's operands rounded).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _f32(tensors, name):
    return jnp.asarray(np.asarray(tensors[name], np.float32))


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def is_attention_layer(cfg: dict, i: int) -> bool:
    return i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


class _Linears:
    """``x @ W^T`` by the stored name, the operands rounded to
    ``linear_dtype`` first (float32: not at all)."""

    def __init__(self, tensors, linear_dtype):
        self.tensors, self.dtype = tensors, linear_dtype

    def __call__(self, x, name):
        w = _f32(self.tensors, name)
        if self.dtype != jnp.float32:
            x, w = (v.astype(self.dtype).astype(jnp.float32) for v in (x, w))
        return x @ w.T


def mamba(cfg: dict, tensors, lin, p: str, u, state_dtype=jnp.float32):
    t = u.shape[0]
    n, taps = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    di = cfg["mamba_expand"] * cfg["hidden_size"]
    r = cfg["mamba_dt_rank"]
    eps = cfg["rms_norm_eps"]
    m = p + "mamba."
    xz = lin(u, m + "in_proj.weight")
    x, z = xz[:, :di], xz[:, di:]
    w = _f32(tensors, m + "conv1d.weight")[:, 0, :]  # [C, K]
    padded = jnp.concatenate([jnp.zeros((taps - 1, di)), x])
    x = sum(padded[j:j + t] * w[:, j] for j in range(taps))
    if cfg.get("mamba_conv_bias", True):
        x = x + _f32(tensors, m + "conv1d.bias")
    x = jax.nn.silu(x)
    dbc = lin(x, m + "x_proj.weight")
    dt = rmsnorm(dbc[:, :r], _f32(tensors, m + "dt_layernorm.weight"), eps)
    bm = rmsnorm(dbc[:, r:r + n], _f32(tensors, m + "b_layernorm.weight"),
                 eps)
    cm = rmsnorm(dbc[:, r + n:], _f32(tensors, m + "c_layernorm.weight"),
                 eps)
    delta = jax.nn.softplus(lin(dt, m + "dt_proj.weight")
                            + _f32(tensors, m + "dt_proj.bias"))  # [t, C]
    a = -jnp.exp(_f32(tensors, m + "A_log"))  # [C, N]
    s = jnp.zeros((di, n))
    ys = []
    for i in range(t):  # the recurrence as written, a token at a time
        s = (jnp.exp(delta[i][:, None] * a) * s
             + (delta[i] * x[i])[:, None] * bm[i][None, :])
        s = s.astype(state_dtype).astype(jnp.float32)
        ys.append(s @ cm[i])
    y = jnp.stack(ys) + _f32(tensors, m + "D") * x
    return lin(y * jax.nn.silu(z), m + "out_proj.weight")


def attention(cfg: dict, lin, p: str, x):
    t = x.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // nh
    a = p + "self_attn."
    q = lin(x, a + "q_proj.weight").reshape(t, nh, d).transpose(1, 0, 2)
    k = lin(x, a + "k_proj.weight").reshape(t, nkv, d).transpose(1, 0, 2)
    v = lin(x, a + "v_proj.weight").reshape(t, nkv, d).transpose(1, 0, 2)
    k = jnp.repeat(k, nh // nkv, axis=0)  # kv head g serves q heads g*r..
    v = jnp.repeat(v, nh // nkv, axis=0)
    scores = q @ k.transpose(0, 2, 1) * d ** -0.5  # no rotation
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = (jax.nn.softmax(scores, axis=-1) @ v).transpose(1, 0, 2)
    return lin(out.reshape(t, nh * d), a + "o_proj.weight")


def hidden_states(cfg: dict, tensors, tokens, state_dtype=jnp.float32,
                  linear_dtype=jnp.float32):
    """Last hidden states ``[t, hidden]`` (before the final norm) of one
    sequence."""
    eps = cfg["rms_norm_eps"]
    lin = _Linears(tensors, linear_dtype)
    x = _f32(tensors, "model.embed_tokens.weight")[jnp.asarray(tokens)]
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        h = rmsnorm(x, _f32(tensors, p + "input_layernorm.weight"), eps)
        if is_attention_layer(cfg, i):
            x = x + attention(cfg, lin, p, h)
        else:
            x = x + mamba(cfg, tensors, lin, p, h, state_dtype)
        h = rmsnorm(x, _f32(tensors, p + "pre_ff_layernorm.weight"), eps)
        f = p + "feed_forward."
        x = x + lin(jax.nn.silu(lin(h, f + "gate_proj.weight"))
                    * lin(h, f + "up_proj.weight"), f + "down_proj.weight")
    return x


def logits(cfg: dict, tensors, tokens, state_dtype=jnp.float32,
           linear_dtype=jnp.float32):
    """``[t, vocab]`` float32 logits at every position of ``tokens``."""
    with jax.default_matmul_precision("highest"):
        x = rmsnorm(hidden_states(cfg, tensors, tokens, state_dtype,
                                  linear_dtype),
                    _f32(tensors, "model.final_layernorm.weight"),
                    cfg["rms_norm_eps"])
        head = ("lm_head.weight" if "lm_head.weight" in tensors
                else "model.embed_tokens.weight")
        return _Linears(tensors, linear_dtype)(x, head)
