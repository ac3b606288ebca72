"""Plain reference of the window + full attention decoder with shared and
routed experts (K-EXAONE's ``config.json`` keys; ``model_type``
``exaone_moe``): the whole forward pass of one sequence in ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")``.

Written from the equations the issue states (ISSUE 40, Motivation), not
from ``cake_tpu/ops``: the whole sequence at once under explicit masks, no
cache, no ring, no kernel, no batching, a Python loop over the experts. It
reads a checkpoint's tensors by their Hugging Face names
(``tensors[name]``, torch layouts) and the model's ``config.json`` as a
dict, so it also checks the loader's naming.

Layer ``i`` (0-based), ``u = rmsnorm(h)``:

- ``q = rmsnorm_head(u W_q)``, ``k = rmsnorm_head(u W_k)`` (over each
  head's ``head_dim`` channels, ONE weight ``[head_dim]`` for all heads of
  q and one for k), ``v = u W_v``; ``num_attention_heads`` query heads
  over ``num_key_value_heads`` key/value heads, no bias, scale
  ``head_dim^-0.5``.
- ``layer_types[i] == "sliding_attention"``: q and k are rotated (the
  half-rotation of ``(x[j], x[j + d/2])``, base ``rope_parameters
  .rope_theta``, the whole head, no scaling) and query ``t`` sees keys
  ``j`` with ``0 <= t - j < sliding_window``.
- ``"full_attention"``: NO rotation, query ``t`` sees every ``j <= t``.
- ``h += attention W_o``; ``m = rmsnorm(h)``; a dense layer
  (``mlp_layer_types[i] == "dense"``) adds ``swiglu(m)``, a sparse one
  ``shared(m) + sum over chosen experts e that the checkpoint HOLDS of w_e
  expert_e(m)`` with ``s = sigmoid(m W_r^T)`` over all the router's
  experts, the CHOICE of ``num_experts_per_tok`` made on ``s + b``
  (group-limited by ``n_group`` / ``topk_group``: the identity at 1 / 1)
  and the WEIGHTS ``s`` of the chosen, normalised (``+ 1e-20``), times
  ``routed_scaling_factor``. Ties go to the lower index.

Departures from the published description, each a reading the published
``config.json`` does not settle (the benchmark configuration's
``assumed``):

- norm placement: the sublayers' INPUTS are normed (``input_layernorm``,
  ``post_attention_layernorm``), DeepSeek-V3's arrangement, whose key set
  this config carries; the family's ancestor normed the sublayers'
  outputs. Same bytes and operations either way.
- the routing bias ``b`` (``mlp.gate.e_score_correction_bias``) enters the
  choice only; a checkpoint without the tensor is the zero-bias reading.
- the next-token prediction block (``num_nextn_predict_layers``, ``mtp.*``
  tensors) takes no part in the model's own logits and is not read.

``window`` overrides the window the masks are built with, for the control
that must FAIL (a window of one key more or fewer).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cake_tpu.testing.reference_kda_mla_moe import route
from cake_tpu.testing.reference_mla_moe import (_f32, held_experts, rmsnorm,
                                                swiglu)


def rope(cfg: dict, x):
    """``x [heads, t, d]``: rotate the pairs ``(x[j], x[j + d/2])`` of
    position ``t`` by ``t * theta^(-2j/d)``."""
    _, t, d = x.shape
    theta = cfg["rope_parameters"]["rope_theta"]
    angle = jnp.arange(t)[:, None] * theta ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(cfg: dict, tensors, p: str, x, windowed: bool, window=None):
    t = x.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    a = p + "self_attn."

    def heads(name, n, norm=None):
        y = (x @ _f32(tensors, a + f"{name}_proj.weight").T).reshape(t, n, d)
        if norm:
            y = rmsnorm(y, _f32(tensors, a + f"{norm}.weight"), eps)
        return y.transpose(1, 0, 2)  # [n, t, d]

    q, k, v = heads("q", nh, "q_norm"), heads("k", nkv, "k_norm"), heads(
        "v", nkv)
    behind = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]  # t - j
    seen = behind >= 0
    if windowed:
        q, k = rope(cfg, q), rope(cfg, k)
        seen &= behind < (window or cfg["sliding_window"])
    k, v = (jnp.repeat(y, nh // nkv, axis=0) for y in (k, v))
    scores = jnp.where(seen[None], q @ k.transpose(0, 2, 1) * d ** -0.5,
                       -jnp.inf)
    out = (jax.nn.softmax(scores, axis=-1) @ v).transpose(1, 0, 2)
    return out.reshape(t, nh * d) @ _f32(tensors, a + "o_proj.weight").T


def expert_layer(cfg: dict, tensors, p: str, h, only=None):
    """``shared(h) + routed part``. ``only``: restrict the routed part to
    these global expert ids (a share of the held experts; the shared
    expert is still added): the share test's handle."""
    gate = _f32(tensors, p + "mlp.gate.weight")  # [E, hidden]
    name = p + "mlp.gate.e_score_correction_bias"
    bias = (_f32(tensors, name) if name in tensors
            else jnp.zeros((gate.shape[0],)))
    idx, w = route(cfg, jax.nn.sigmoid(h @ gate.T), bias)
    out = jnp.zeros_like(h)
    for e in held_experts(tensors, p, gate.shape[0]):
        if only is not None and e not in only:
            continue
        w_e = jnp.where(idx == e, w, 0.0).sum(-1)  # 0 where not chosen
        out = out + w_e[:, None] * swiglu(h, tensors, f"{p}mlp.experts.{e}.")
    if cfg.get("num_shared_experts"):
        out = out + swiglu(h, tensors, p + "mlp.shared_experts.")
    return out


def hidden_states(cfg: dict, tensors, tokens, window=None):
    """Last hidden states ``[t, hidden]`` (before the final norm) of one
    sequence."""
    eps = cfg["rms_norm_eps"]
    x = _f32(tensors, "model.embed_tokens.weight")[jnp.asarray(tokens)]
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        h = rmsnorm(x, _f32(tensors, p + "input_layernorm.weight"), eps)
        x = x + attention(cfg, tensors, p, h,
                          cfg["layer_types"][i] == "sliding_attention",
                          window)
        h = rmsnorm(x, _f32(tensors, p + "post_attention_layernorm.weight"),
                    eps)
        if cfg["mlp_layer_types"][i] == "sparse":
            x = x + expert_layer(cfg, tensors, p, h)
        else:
            x = x + swiglu(h, tensors, p + "mlp.")
    return x


def logits(cfg: dict, tensors, tokens, window=None):
    """``[t, vocab]`` float32 logits at every position of ``tokens``."""
    with jax.default_matmul_precision("highest"):
        x = rmsnorm(hidden_states(cfg, tensors, tokens, window),
                    _f32(tensors, "model.norm.weight"), cfg["rms_norm_eps"])
        return x @ _f32(tensors, "lm_head.weight").T
