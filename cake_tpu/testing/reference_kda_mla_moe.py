"""Plain reference of the delta-rule + latent-attention hybrid decoder
(Ling-3.0's ``config.json`` keys; ``model_type`` ``bailing_hybrid``): the
whole forward pass of one sequence in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.

Written from the equations the issue states (ISSUE 32, Tentpole 1), not
from ``cake_tpu/ops``: the delta rule token by token (no chunks), expanded
attention, no cache, no batching, no kernels, a Python loop over the
experts. It reads a checkpoint's tensors by their Hugging Face names
(``tensors[name]``, torch layouts) and the model's ``config.json`` as a
dict, so it also checks the loader's naming. What the published file does
not settle is a reading, listed in the benchmark configuration's
``assumed``.

Layer ``i`` (0-based) attends through latent attention if ``(i + 1) %
layer_group_size == 0`` and through KDA otherwise; its feed-forward is a
dense SwiGLU for ``i < first_k_dense_replace`` and the expert layer after.

- KDA (``H`` heads of ``d = head_dim``): ``[q | k | v] = silu(conv(x W_q |
  x W_k | x W_v))``, a causal depthwise convolution of
  ``short_conv_kernel_size`` taps over time (zeros before the sequence);
  ``q, k`` L2-normalised a head (``x / sqrt(sum x^2 + 1e-6)``), ``q``
  times ``d^-0.5``; ``g = kda_lower_bound * sigmoid(exp(A_log_h) * (x W_f +
  dt_bias))`` a channel; ``beta = sigmoid(x W_b)`` a head; per head ``S_t
  = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T``
  from ``S_0 = 0``, ``o_t = S_t^T q_t``; ``y = (rmsnorm_head(o) * sigmoid(x
  W_g)) W_o``.
- latent attention: ``q = x W_q`` directly (``q_lora_rank`` null), ``[c |
  k_pe] = x W_kva``, ``c`` normed, ``[k_nope | v]_h = c W_kvb``, rope on
  interleaved pairs of the rope dims (theta as given, no scaling), scale
  ``(nope + rope)^-0.5``, causal softmax, each head's output times
  ``sigmoid(x W_g)_h``, then ``W_o``.
- an expert layer: ``s = sigmoid(h W_r^T)`` over all the router's experts;
  the CHOICE is made on ``s + b`` (a group's score is the sum of its 2
  highest, the ``topk_group`` best groups stay, top-k inside them) and the
  WEIGHTS are ``s`` of the chosen, normalised (``+ 1e-20``), times
  ``routed_scaling_factor``; ``y = shared(h) + sum over chosen experts e
  that the checkpoint HOLDS of w_e expert_e(h)``. Ties go to the lower
  index. The next-token prediction block is not part of the trunk.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cake_tpu.testing.reference_mla_moe import (_f32, held_experts, rmsnorm,
                                                rope, rope_angles, swiglu)


def is_latent_layer(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["layer_group_size"] == 0


def kda(cfg: dict, tensors, p: str, x, state_dtype=jnp.float32):
    """``state_dtype``: what the state is held in between tokens (float32
    as stated; a lower precision is the control that must fail)."""
    t = x.shape[0]
    nh, d = cfg["num_attention_heads"], cfg["head_dim"]
    taps = cfg["short_conv_kernel_size"]
    a = p + "self_attn."

    def conv(name):
        y = x @ _f32(tensors, a + f"{name}_proj.weight").T  # [t, H d]
        w = _f32(tensors, a + f"{name}_conv1d.weight")[:, 0, :]  # [C, K]
        padded = jnp.concatenate([jnp.zeros((taps - 1, y.shape[1])), y])
        out = sum(padded[j:j + t] * w[:, j] for j in range(taps))
        return jax.nn.silu(out).reshape(t, nh, d)

    def l2(v):
        return v / jnp.sqrt(jnp.sum(v * v, -1, keepdims=True) + 1e-6)

    q, k, v = l2(conv("q")) * d ** -0.5, l2(conv("k")), conv("v")
    rate = jnp.exp(_f32(tensors, a + "A_log"))[:, None]
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(rate * (
        x @ _f32(tensors, a + "f_proj.weight").T
        + _f32(tensors, a + "dt_bias")).reshape(t, nh, d))
    beta = jax.nn.sigmoid(x @ _f32(tensors, a + "b_proj.weight").T)  # [t, H]

    s = jnp.zeros((nh, d, d))
    outs = []
    for i in range(t):  # the recurrence as written, a token at a time
        s = s * jnp.exp(g[i])[:, :, None]
        ks = jnp.einsum("hk,hkv->hv", k[i], s)
        s = s + beta[i][:, None, None] * k[i][:, :, None] * (
            v[i] - ks)[:, None, :]
        s = s.astype(state_dtype).astype(jnp.float32)
        outs.append(jnp.einsum("hk,hkv->hv", q[i], s))
    o = rmsnorm(jnp.stack(outs), _f32(tensors, a + "o_norm.weight"),
                cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(x @ _f32(tensors, a + "g_proj.weight").T)
    return (o.reshape(t, nh * d) * gate) @ _f32(tensors,
                                                a + "o_proj.weight").T


def attention(cfg: dict, tensors, p: str, x):
    t = x.shape[0]
    nh = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, dc = cfg["v_head_dim"], cfg["kv_lora_rank"]
    a = p + "self_attn."
    q = (x @ _f32(tensors, a + "q_proj.weight").T).reshape(t, nh, dn + dr)
    ckv = x @ _f32(tensors, a + "kv_a_proj_with_mqa.weight").T
    c = rmsnorm(ckv[:, :dc], _f32(tensors, a + "kv_a_layernorm.weight"),
                cfg["rms_norm_eps"])
    kv = (c @ _f32(tensors, a + "kv_b_proj.weight").T).reshape(t, nh, dn + dv)
    cos, sin = rope_angles(cfg, t)
    q_pe = rope(q[:, :, dn:].transpose(1, 0, 2), cos, sin)  # [H, t, dr]
    k_pe = rope(ckv[:, dc:], cos, sin)  # [t, dr], one for all heads
    scores = (q[:, :, :dn].transpose(1, 0, 2)
              @ kv[:, :, :dn].transpose(1, 2, 0)
              + q_pe @ k_pe.T[None]) * (dn + dr) ** -0.5  # [H, t, t]
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jax.nn.softmax(scores, axis=-1) @ kv[:, :, dn:].transpose(1, 0, 2)
    out = out.transpose(1, 0, 2)  # [t, H, dv]
    if cfg.get("gated_attention_proj_granularity_type") == "head_wise":
        gate = jax.nn.sigmoid(x @ _f32(tensors, a + "g_proj.weight").T)
        out = out * gate[:, :, None]
    return out.reshape(t, nh * dv) @ _f32(tensors, a + "o_proj.weight").T


def route(cfg: dict, scores, bias):
    """``scores [t, E]`` (sigmoid), ``bias [E]`` -> ``(idx [t, k], weight
    [t, k])``: the choice on ``scores + bias``, the weights from
    ``scores``."""
    t, e = scores.shape
    groups, keep = cfg.get("n_group", 1), cfg.get("topk_group", 1)
    k = cfg["num_experts_per_tok"]
    choice = scores + bias
    if groups > 1:
        grouped = choice.reshape(t, groups, e // groups)
        group_score = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)
        # stable sort of the negated scores: ties go to the lower index
        kept = jnp.argsort(-group_score, axis=-1, stable=True)[:, :keep]
        in_kept = (jnp.arange(groups)[None, :, None]
                   == kept[:, None, :]).any(-1)
        choice = jnp.where(in_kept[..., None], grouped,
                           -jnp.inf).reshape(t, e)
    idx = jnp.argsort(-choice, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True) and k > 1:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * cfg.get("routed_scaling_factor", 1.0)


def expert_layer(cfg: dict, tensors, p: str, h, only=None):
    """``shared(h) + routed part``. ``only``: restrict the routed part to
    these global expert ids (a share of the held experts; the shared
    expert is still added) - the share test's handle."""
    gate = _f32(tensors, p + "mlp.gate.weight")  # [E, hidden]
    bias = (_f32(tensors, p + "mlp.gate.expert_bias")
            if cfg.get("moe_router_enable_expert_bias")
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


def hidden_states(cfg: dict, tensors, tokens, state_dtype=jnp.float32):
    """Last hidden states ``[t, hidden]`` (before the final norm) of one
    sequence."""
    eps = cfg["rms_norm_eps"]
    x = _f32(tensors, "model.embed_tokens.weight")[jnp.asarray(tokens)]
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        h = rmsnorm(x, _f32(tensors, p + "input_layernorm.weight"), eps)
        if is_latent_layer(cfg, i):
            x = x + attention(cfg, tensors, p, h)
        else:
            x = x + kda(cfg, tensors, p, h, state_dtype)
        h = rmsnorm(x, _f32(tensors, p + "post_attention_layernorm.weight"),
                    eps)
        if cfg.get("num_experts") and i >= cfg.get("first_k_dense_replace",
                                                   0):
            x = x + expert_layer(cfg, tensors, p, h)
        else:
            x = x + swiglu(h, tensors, p + "mlp.")
    return x


def logits(cfg: dict, tensors, tokens, state_dtype=jnp.float32):
    """``[t, vocab]`` float32 logits at every position of ``tokens``."""
    with jax.default_matmul_precision("highest"):
        x = rmsnorm(hidden_states(cfg, tensors, tokens, state_dtype),
                    _f32(tensors, "model.norm.weight"), cfg["rms_norm_eps"])
        return x @ _f32(tensors, "lm_head.weight").T
