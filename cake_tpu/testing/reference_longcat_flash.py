"""Plain reference of the shortcut-connected double-layer decoder with
zero-compute experts (LongCat-Flash's ``config.json`` keys; ``model_type``
``longcat_flash``): the whole forward pass of one sequence in ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")``.

Written from the equations ISSUE 64 states (from the catalog's ``config``
and the family's published modelling code for what the keys name), not
from ``cake_tpu/ops``: expanded attention (every head's keys and values
are materialised), no cache, no batching, no kernels, NO FOLDING (the two
``mla_scale_*`` factors are applied where the equations put them), a Python
loop over the experts. It reads a checkpoint's tensors by their Hugging
Face names in torch's ``[out, in]`` layout (``tensors[name]``) and the
model's ``config.json`` as a dict, so it also checks the loader's naming
and its two ``Fold``s.

One published layer is a DOUBLE layer; ``x`` is the residual stream,
``RMS`` an RMSNorm with its own weight::

    a0 = x  + MLA_0(RMS(x;  input_layernorm.0))
    h  = RMS(a0; post_attention_layernorm.0)
    s  = MoE(h)                               # the shortcut: NOT added here
    b0 = a0 + FFN_0(h)                        # dense SwiGLU, ffn_hidden_size
    a1 = b0 + MLA_1(RMS(b0; input_layernorm.1))
    b1 = a1 + FFN_1(RMS(a1; post_attention_layernorm.1)) + s

``MoE(h)``: ``logits = h W_r`` over ``n_routed_experts + zero_expert_num``
outputs (a share's file: ``expert_share.n_routed_experts`` experts, of
which it holds some); ``p = softmax(logits)`` over ALL of them; the
``moe_topk`` outputs of largest ``p + e_score_correction_bias`` are chosen,
a tie to the lower id; ``w_e = routed_scaling_factor p_e``, NOT
renormalised; ``s = sum over chosen experts e that the checkpoint HOLDS of
w_e SwiGLU_e(h) + (sum over chosen zero-compute outputs of w_e) h``
(``zero_expert_type`` "identity": such an expert returns its input). A cut
checkpoint holds some of the experts ``mlp.experts.{e}``; what the absent
ones would add is left out, here as in the program, and the identity part
is kept.

``MLA_j``: DeepSeek-V3's latent attention (``reference_mla_moe.py``) with
two factors: ``mla_scale_q_lora``: ``q`` (both parts, behind ``W_qb``) times
``(hidden / q_lora_rank)^0.5``; ``mla_scale_kv_lora``: the normed latent
``c`` times ``(hidden / kv_lora_rank)^0.5`` before ``W_kvb`` (``k_pe`` is
not scaled). Interleaved rope pairs, no scaling, softmax scale ``(nope +
rope)^-0.5``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cake_tpu.testing.reference_mla_moe import (_f32, rmsnorm, rope,
                                                rope_angles)


def swiglu(x, tensors, prefix):
    g = x @ _f32(tensors, prefix + "gate_proj.weight").T
    u = x @ _f32(tensors, prefix + "up_proj.weight").T
    return (jax.nn.silu(g) * u) @ _f32(tensors, prefix + "down_proj.weight").T


def attention(cfg: dict, tensors, a: str, x):
    """``MLA_j`` of one sequence; ``a``: ``model.layers.{l}.self_attn.{j}.``"""
    t, hidden = x.shape
    nh = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, dc = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    c_q = rmsnorm(x @ _f32(tensors, a + "q_a_proj.weight").T,
                  _f32(tensors, a + "q_a_layernorm.weight"), eps)
    q = (c_q @ _f32(tensors, a + "q_b_proj.weight").T).reshape(t, nh, dn + dr)
    if cfg.get("mla_scale_q_lora"):
        q = q * (hidden / cfg["q_lora_rank"]) ** 0.5
    ckv = x @ _f32(tensors, a + "kv_a_proj_with_mqa.weight").T
    c = rmsnorm(ckv[:, :dc], _f32(tensors, a + "kv_a_layernorm.weight"), eps)
    if cfg.get("mla_scale_kv_lora"):
        c = c * (hidden / dc) ** 0.5
    kv = (c @ _f32(tensors, a + "kv_b_proj.weight").T).reshape(t, nh, dn + dv)
    cos, sin = rope_angles({**cfg, "rope_scaling": None}, t)
    q_pe = rope(q[:, :, dn:].transpose(1, 0, 2), cos, sin)  # [H, t, dr]
    k_pe = rope(ckv[:, dc:], cos, sin)  # [t, dr], one for all heads
    q_nope = q[:, :, :dn].transpose(1, 0, 2)
    k_nope = kv[:, :, :dn].transpose(1, 0, 2)
    v = kv[:, :, dn:].transpose(1, 0, 2)
    scores = (q_nope @ k_nope.transpose(0, 2, 1)
              + q_pe @ k_pe.T[None]) * (dn + dr) ** -0.5  # [H, t, t]
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jax.nn.softmax(scores, axis=-1) @ v  # [H, t, dv]
    out = out.transpose(1, 0, 2).reshape(t, nh * dv)
    return out @ _f32(tensors, a + "o_proj.weight").T


def route(cfg: dict, logits, bias):
    """``logits [t, outputs]`` -> ``(idx [t, k], weight [t, k])``: softmax
    over all outputs, the choice on ``p + bias`` (a tie to the lower id:
    a stable sort of the negated scores), the weights ``p`` of the chosen
    times ``routed_scaling_factor``, not renormalised."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx = jnp.argsort(-(p + bias), axis=-1, stable=True)[:, :cfg["moe_topk"]]
    w = jnp.take_along_axis(p, idx, axis=-1)
    if cfg.get("norm_topk_prob", False):
        w = w / w.sum(-1, keepdims=True)
    return idx, w * cfg.get("routed_scaling_factor", 1.0)


def real_experts(cfg: dict) -> int:
    """The router's outputs that are experts (the published count; a
    share's file holds ``n_routed_experts`` of them)."""
    return (cfg.get("expert_share") or {}).get(
        "n_routed_experts", cfg["n_routed_experts"])


def expert_layer(cfg: dict, tensors, p: str, h, share=None, identity=True):
    """``MoE(h)``: the held experts' part and the identity part. ``share =
    (first, count)``: restrict the routed part to global experts ``first ..
    first + count - 1`` (a share of the held experts); ``identity`` False
    leaves the zero-compute outputs' part out (the share test adds it
    once)."""
    router = _f32(tensors, p + "mlp.router.classifier.weight")
    real = real_experts(cfg)
    assert router.shape[0] == real + cfg.get("zero_expert_num", 0), (
        router.shape, real)
    idx, w = route(cfg, h @ router.T,
                   _f32(tensors, p + "mlp.router.e_score_correction_bias"))
    first, count = share or (0, real)
    out = jnp.zeros_like(h)
    for e in range(first, first + count):
        if f"{p}mlp.experts.{e}.gate_proj.weight" not in tensors:
            continue  # an absent expert's part is left out
        w_e = jnp.where(idx == e, w, 0.0).sum(-1)  # 0 where not chosen
        out = out + w_e[:, None] * swiglu(h, tensors, f"{p}mlp.experts.{e}.")
    if identity:  # a zero-compute expert returns its input
        if cfg.get("zero_expert_type", "identity") != "identity":
            raise ValueError(cfg["zero_expert_type"])
        z = jnp.where(idx >= real, w, 0.0).sum(-1)
        out = out + z[:, None] * h
    return out


def double_layer(cfg: dict, tensors, p: str, x, share=None, identity=True,
                 early=False):
    """One published layer of one sequence, ``x [t, hidden]``. ``early``
    (a control, not the model): the expert block's result added where it
    is computed, at the end of the first sub-layer."""
    eps = cfg["rms_norm_eps"]

    def norm(x, name):
        return rmsnorm(x, _f32(tensors, f"{p}{name}.weight"), eps)

    a0 = x + attention(cfg, tensors, p + "self_attn.0.",
                       norm(x, "input_layernorm.0"))
    h = norm(a0, "post_attention_layernorm.0")
    s = expert_layer(cfg, tensors, p, h, share, identity)
    b0 = a0 + swiglu(h, tensors, p + "mlps.0.")
    if early:
        b0, s = b0 + s, 0.0
    a1 = b0 + attention(cfg, tensors, p + "self_attn.1.",
                        norm(b0, "input_layernorm.1"))
    return a1 + swiglu(norm(a1, "post_attention_layernorm.1"), tensors,
                       p + "mlps.1.") + s


def hidden_states(cfg: dict, tensors, tokens, share=None, **control):
    """Last hidden states ``[t, hidden]`` (before the final norm) of one
    sequence."""
    x = _f32(tensors, "model.embed_tokens.weight")[jnp.asarray(tokens)]
    for i in range(cfg["num_layers"]):
        x = double_layer(cfg, tensors, f"model.layers.{i}.", x, share,
                         **control)
    return x


def logits(cfg: dict, tensors, tokens, share=None, **control):
    """``[t, vocab]`` float32 logits at every position of ``tokens``;
    ``share = (first, count)``: only those experts' routed parts (and the
    identity part) are kept. ``control``: :func:`double_layer`'s
    ``identity=False`` / ``early=True``, what the tests' controls leave
    out or misplace."""
    if cfg.get("attention_method", "MLA") != "MLA":
        raise ValueError(cfg["attention_method"])
    with jax.default_matmul_precision("highest"):
        x = rmsnorm(hidden_states(cfg, tensors, tokens, share, **control),
                    _f32(tensors, "model.norm.weight"), cfg["rms_norm_eps"])
        return x @ _f32(tensors, "lm_head.weight").T
