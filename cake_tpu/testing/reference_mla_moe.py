"""Plain reference of the latent-attention, shared-expert decoder
(DeepSeek-V3's ``config.json`` keys; ``model_type`` ``deepseek_v3``,
``axk1``): the whole forward pass of one sequence in ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")``.

Written from the published equations, not from ``cake_tpu/ops``: expanded
attention (every head's keys and values are materialised), no cache, no
batching, no kernels, a Python loop over the experts. It reads a
checkpoint's tensors by their Hugging Face names in torch's ``[out, in]``
layout (``tensors[name]``), and the model's ``config.json`` as a dict, so
it also checks the loader's naming.

Per token ``x`` of a layer (``rmsnorm`` with the layer's weights):

- attention: ``c_q = rmsnorm(x W_qa)``; ``[q_nope | q_pe]_h = c_q W_qb``;
  ``[c | k_pe] = x W_kva``; ``c = rmsnorm(c)``; ``q_pe, k_pe = rope(.)`` on
  interleaved pairs ``(2i, 2i+1)``, ``k_pe`` shared by all heads;
  ``[k_nope | v]_h = c W_kvb``; ``score_h = (q_nope_h . k_nope_h + q_pe_h .
  k_pe) * (nope + rope)^-0.5 * m^2`` with ``m = 0.1 * mscale_all_dim *
  ln(factor) + 1``; causal softmax; ``out = concat_h(softmax . v_h) W_o``.
- YaRN: pair ``i``'s frequency is ``theta^(-2i/d)`` blended with that
  divided by ``factor`` along a linear ramp between the correction dims of
  ``beta_fast`` and ``beta_slow`` over the original window; cos and sin are
  multiplied by ``m(mscale) / m(mscale_all_dim)``.
- the first ``first_k_dense_replace`` layers: SwiGLU of ``intermediate_size``.
- an expert layer: ``s = sigmoid(h W_r^T)`` over all the router's experts;
  a group's score is the sum of its 2 highest ``s``; the ``topk_group`` best
  groups stay; top-k of ``s`` inside them; ``w = s[top] / (sum + 1e-20) *
  routed_scaling_factor``; ``y = shared(h) + sum over chosen experts e that
  the checkpoint HOLDS of w_e expert_e(h)``. A cut checkpoint (a chip's
  share of an expert-parallel deployment) holds some of the global experts
  ``mlp.experts.{e}``; what the absent ones would add is left out, here as
  in the program. ``topk_method`` is read as this group-limited choice with
  no correction bias. Ties go to the lower index.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _f32(tensors, name):
    return jnp.asarray(np.asarray(tensors[name], np.float32))


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_m(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_angles(cfg: dict, t: int):
    """``(cos, sin) [t, rope/2]`` for positions ``0..t-1``."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    amp = 1.0
    rs = cfg.get("rope_scaling")
    if rs:
        factor = float(rs["factor"])
        orig = float(rs["original_max_position_embeddings"])

        def corr(turns):
            return (d * math.log(orig / (turns * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(corr(rs.get("beta_fast", 32))), 0)
        high = min(math.ceil(corr(rs.get("beta_slow", 1))), d - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                        / (high - low), 0.0, 1.0)
        inv = inv / factor * ramp + inv * (1.0 - ramp)
        amp = (yarn_m(factor, rs.get("mscale", 1.0))
               / yarn_m(factor, rs.get("mscale_all_dim", 0.0)))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang) * amp, jnp.sin(ang) * amp


def rope(x, cos, sin):
    """``x [..., t, d]``: rotate the pairs ``(x[2i], x[2i+1])``."""
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


def swiglu(x, tensors, prefix):
    g = x @ _f32(tensors, prefix + "gate_proj.weight").T
    u = x @ _f32(tensors, prefix + "up_proj.weight").T
    return (jax.nn.silu(g) * u) @ _f32(tensors, prefix + "down_proj.weight").T


def attention(cfg: dict, tensors, p: str, x):
    t = x.shape[0]
    nh = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, dc = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    a = p + "self_attn."
    c_q = rmsnorm(x @ _f32(tensors, a + "q_a_proj.weight").T,
                  _f32(tensors, a + "q_a_layernorm.weight"), eps)
    q = (c_q @ _f32(tensors, a + "q_b_proj.weight").T).reshape(t, nh, dn + dr)
    ckv = x @ _f32(tensors, a + "kv_a_proj_with_mqa.weight").T
    c = rmsnorm(ckv[:, :dc], _f32(tensors, a + "kv_a_layernorm.weight"), eps)
    kv = (c @ _f32(tensors, a + "kv_b_proj.weight").T).reshape(t, nh, dn + dv)
    cos, sin = rope_angles(cfg, t)
    q_pe = rope(q[:, :, dn:].transpose(1, 0, 2), cos, sin)  # [H, t, dr]
    k_pe = rope(ckv[:, dc:], cos, sin)  # [t, dr], one for all heads
    q_nope = q[:, :, :dn].transpose(1, 0, 2)
    k_nope = kv[:, :, :dn].transpose(1, 0, 2)
    v = kv[:, :, dn:].transpose(1, 0, 2)
    scale = (dn + dr) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        scale *= yarn_m(float(rs["factor"]), rs["mscale_all_dim"]) ** 2
    scores = (q_nope @ k_nope.transpose(0, 2, 1)
              + q_pe @ k_pe.T[None]) * scale  # [H, t, t]
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jax.nn.softmax(scores, axis=-1) @ v  # [H, t, dv]
    out = out.transpose(1, 0, 2).reshape(t, nh * dv)
    return out @ _f32(tensors, a + "o_proj.weight").T


def route(cfg: dict, scores):
    """``scores [t, E]`` (sigmoid) -> ``(idx [t, k], weight [t, k])``."""
    t, e = scores.shape
    groups, keep = cfg.get("n_group", 1), cfg.get("topk_group", 1)
    k = cfg["num_experts_per_tok"]
    choice = scores
    if groups > 1:
        grouped = scores.reshape(t, groups, e // groups)
        group_score = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)
        # stable sort of the negated scores: ties go to the lower index
        kept = jnp.argsort(-group_score, axis=-1, stable=True)[:, :keep]
        in_kept = (jnp.arange(groups)[None, :, None]
                   == kept[:, None, :]).any(-1)
        choice = jnp.where(in_kept[..., None], grouped, -1.0).reshape(t, e)
    idx = jnp.argsort(-choice, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True) and k > 1:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * cfg.get("routed_scaling_factor", 1.0)


def held_experts(tensors, p: str, width: int) -> list[int]:
    """The global ids of the experts this checkpoint holds in layer ``p``."""
    return [e for e in range(width)
            if f"{p}mlp.experts.{e}.gate_proj.weight" in tensors]


def expert_layer(cfg: dict, tensors, p: str, h, only=None):
    """``shared(h) + routed part``. ``only``: restrict the routed part to
    these global expert ids (a share of the held experts; the shared
    experts are still added) - the share test's handle."""
    gate = _f32(tensors, p + "mlp.gate.weight")  # [E, hidden]
    idx, w = route(cfg, jax.nn.sigmoid(h @ gate.T))
    out = jnp.zeros_like(h)
    for e in held_experts(tensors, p, gate.shape[0]):
        if only is not None and e not in only:
            continue
        w_e = jnp.where(idx == e, w, 0.0).sum(-1)  # 0 where not chosen
        out = out + w_e[:, None] * swiglu(h, tensors, f"{p}mlp.experts.{e}.")
    if cfg.get("n_shared_experts"):
        out = out + swiglu(h, tensors, p + "mlp.shared_experts.")
    return out


def hidden_states(cfg: dict, tensors, tokens):
    """Last hidden states ``[t, hidden]`` (before the final norm) of one
    sequence."""
    eps = cfg["rms_norm_eps"]
    x = _f32(tensors, "model.embed_tokens.weight")[jnp.asarray(tokens)]
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        x = x + attention(cfg, tensors, p, rmsnorm(
            x, _f32(tensors, p + "input_layernorm.weight"), eps))
        h = rmsnorm(x, _f32(tensors, p + "post_attention_layernorm.weight"),
                    eps)
        if (cfg.get("n_routed_experts")
                and i >= cfg.get("first_k_dense_replace", 0)):
            x = x + expert_layer(cfg, tensors, p, h)
        else:
            x = x + swiglu(h, tensors, p + "mlp.")
    return x


def logits(cfg: dict, tensors, tokens):
    """``[t, vocab]`` float32 logits at every position of ``tokens``."""
    with jax.default_matmul_precision("highest"):
        x = rmsnorm(hidden_states(cfg, tensors, tokens),
                    _f32(tensors, "model.norm.weight"), cfg["rms_norm_eps"])
        head = ("model.embed_tokens.weight"
                if cfg.get("tie_word_embeddings") else "lm_head.weight")
        return x @ _f32(tensors, head).T
