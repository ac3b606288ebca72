"""Plain reference of the short-convolution + attention decoder with routed
experts (LFM2-8B-A1B's ``config.json`` keys; ``model_type`` ``lfm2_moe``):
the whole forward pass of one sequence in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.

Written from the equations the issue states (ISSUE 43, Motivation), not
from ``cake_tpu/ops``: the whole sequence at once, no cache, no tail, no
kernel, no batching, the causal convolution as the sum over three shifted
copies, a Python loop over all the experts. It reads a checkpoint's tensors
by their Hugging Face names (``tensors[name]``, torch layouts) and the
model's ``config.json`` as a dict, so it also checks the loader's naming.

Every norm is an RMSNorm with a plain weight and ``norm_eps``. Layer ``i``
(0-based), ``u = operator_norm(h)``:

- ``layer_types[i] == "conv"`` (``L = conv_L_cache`` taps, no bias):
  ``[B | C | x] = u W_in`` (three chunks of ``hidden``, in that order),
  ``z = B * x``, ``y_t[c] = sum_{j < L} w[c, j] z_{t - (L - 1) + j}[c]``
  (depthwise, causal, zeros before the first token, NO activation),
  ``h += (C * y) W_out``.
- ``"full_attention"``: ``q = rmsnorm_head(u W_q)``, ``k = rmsnorm_head(u
  W_k)`` (over each head's ``hidden / heads`` channels, ONE weight for all
  heads of q and one for k), BOTH then rotated (the half-rotation of
  ``(x[j], x[j + d/2])``, base ``rope_theta``, the whole head, no
  scaling), ``v = u W_v``; ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads, no bias, scale ``d^-0.5``,
  every ``j <= t`` seen; ``h += attention W_o``.
- ``m = ffn_norm(h)``; layers below ``num_dense_layers`` add ``w2(silu(w1
  m) * w3 m)``, the others ``sum over the chosen experts e of w_e
  expert_e(m)`` with ``s = sigmoid(m W_gate^T)`` over all ``num_experts``,
  the CHOICE of ``num_experts_per_tok`` made on ``s + expert_bias``
  (``use_expert_bias``) and the WEIGHTS ``s`` of the chosen, divided by
  their sum ``+ 1e-6`` (``norm_topk_prob``), times
  ``routed_scaling_factor``. Ties go to the lower index. No shared expert.
- at the end ``embedding_norm``, then the head, the embedding itself where
  the checkpoint stores no ``lm_head.weight``.

Departures from the published description: none. What ``config.json`` does
not settle (the chunk order ``B | C | x``, no activation in the mixer, the
``+ 1e-6``, a tied head) is the benchmark configuration's ``assumed``.

``wrong`` names ONE piece of the mathematics to get wrong, for the controls
that must FAIL: ``"taps"`` (the oldest tap dropped: two taps where the
model has three), ``"gate"`` (the gate ``C`` behind the convolution left
out), ``"bias"`` (the choice of experts made on ``s`` alone),
``"rotation"`` (attention layers rotate nothing).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cake_tpu.testing.reference_mla_moe import _f32, rmsnorm

WRONG = ("taps", "gate", "bias", "rotation")


def rope(cfg: dict, x):
    """``x [heads, t, d]``: rotate the pairs ``(x[j], x[j + d/2])`` of
    position ``t`` by ``t * theta^(-2j/d)``."""
    _, t, d = x.shape
    angle = jnp.arange(t)[:, None] * float(cfg["rope_theta"]) ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def short_conv(cfg: dict, tensors, p: str, u, wrong=None):
    """The gated short convolution over ``u [t, hidden]``."""
    t, hidden = u.shape
    taps = cfg["conv_L_cache"]
    gate_in, gate_out, x = jnp.split(
        u @ _f32(tensors, p + "conv.in_proj.weight").T, 3, axis=-1)
    z = gate_in * x
    w = _f32(tensors, p + "conv.conv.weight")[:, 0, :]  # [hidden, taps]
    padded = jnp.concatenate([jnp.zeros((taps - 1, hidden)), z])
    y = jnp.zeros_like(z)
    for j in range(1 if wrong == "taps" else 0, taps):
        y = y + w[:, j] * padded[j:j + t]  # z shifted by taps - 1 - j
    if wrong != "gate":
        y = gate_out * y
    return y @ _f32(tensors, p + "conv.out_proj.weight").T


def attention(cfg: dict, tensors, p: str, u, wrong=None):
    t = u.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // nh
    a = p + "self_attn."

    def heads(name, n, norm=None):
        y = (u @ _f32(tensors, a + f"{name}_proj.weight").T).reshape(t, n, d)
        if norm:
            y = rmsnorm(y, _f32(tensors, a + f"{norm}.weight"),
                        cfg["norm_eps"])
        return y.transpose(1, 0, 2)  # [n, t, d]

    q, k, v = (heads("q", nh, "q_layernorm"), heads("k", nkv, "k_layernorm"),
               heads("v", nkv))
    if wrong != "rotation":
        q, k = rope(cfg, q), rope(cfg, k)
    k, v = (jnp.repeat(y, nh // nkv, axis=0) for y in (k, v))
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scores = jnp.where(seen[None], q @ k.transpose(0, 2, 1) * d ** -0.5,
                       -jnp.inf)
    out = (jax.nn.softmax(scores, axis=-1) @ v).transpose(1, 0, 2)
    return out.reshape(t, nh * d) @ _f32(tensors, a + "out_proj.weight").T


def swiglu(x, tensors, prefix):
    gate = x @ _f32(tensors, prefix + "w1.weight").T
    up = x @ _f32(tensors, prefix + "w3.weight").T
    return (jax.nn.silu(gate) * up) @ _f32(tensors, prefix + "w2.weight").T


def route(cfg: dict, scores, bias):
    """``scores [t, E]`` (sigmoid), ``bias [E]`` -> ``(idx [t, k], weight
    [t, k])``: the choice on ``scores + bias``, the weights from
    ``scores``."""
    k = cfg["num_experts_per_tok"]
    # stable sort of the negated scores: ties go to the lower index
    idx = jnp.argsort(-(scores + bias), axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    return idx, w * cfg.get("routed_scaling_factor", 1.0)


def expert_layer(cfg: dict, tensors, p: str, m, wrong=None):
    f = p + "feed_forward."
    gate = _f32(tensors, f + "gate.weight")  # [E, hidden]
    bias = jnp.zeros((gate.shape[0],))
    if cfg.get("use_expert_bias") and wrong != "bias":
        bias = _f32(tensors, f + "expert_bias")
    idx, w = route(cfg, jax.nn.sigmoid(m @ gate.T), bias)
    out = jnp.zeros_like(m)
    for e in range(cfg["num_experts"]):
        w_e = jnp.where(idx == e, w, 0.0).sum(-1)  # 0 where not chosen
        out = out + w_e[:, None] * swiglu(m, tensors, f"{f}experts.{e}.")
    return out


def hidden_states(cfg: dict, tensors, tokens, wrong=None):
    """Last hidden states ``[t, hidden]`` (before the final norm) of one
    sequence."""
    eps = cfg["norm_eps"]
    h = _f32(tensors, "model.embed_tokens.weight")[jnp.asarray(tokens)]
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        u = rmsnorm(h, _f32(tensors, p + "operator_norm.weight"), eps)
        mixer = short_conv if cfg["layer_types"][i] == "conv" else attention
        h = h + mixer(cfg, tensors, p, u, wrong)
        m = rmsnorm(h, _f32(tensors, p + "ffn_norm.weight"), eps)
        if i < cfg.get("num_dense_layers", 0):
            h = h + swiglu(m, tensors, p + "feed_forward.")
        else:
            h = h + expert_layer(cfg, tensors, p, m, wrong)
    return h


def logits(cfg: dict, tensors, tokens, wrong=None):
    """``[t, vocab]`` float32 logits at every position of ``tokens``."""
    if wrong not in (None,) + WRONG:
        raise ValueError(f"wrong must be one of {WRONG}, got {wrong!r}")
    head = ("lm_head.weight" if "lm_head.weight" in tensors
            else "model.embed_tokens.weight")
    with jax.default_matmul_precision("highest"):
        x = rmsnorm(hidden_states(cfg, tensors, tokens, wrong),
                    _f32(tensors, "model.embedding_norm.weight"),
                    cfg["norm_eps"])
        return x @ _f32(tensors, head).T
