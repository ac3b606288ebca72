"""Plain reference of the latent-attention, shared-expert decoder under a
residual stream several hidden vectors wide (manifold-constrained
hyper-connections; ``model_type`` ``xing4_0``: DeepSeek-V3's ``config.json``
keys and ``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps``,
``mhc_h_res_clamp_min/max``): the whole forward pass of one sequence in
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``.

Written from the equations ISSUE 51 states (from the catalog's keys and
the two papers they name, "Hyper-Connections", arXiv:2409.19606, and "mHC",
arXiv:2512.24880), not from ``cake_tpu/ops``: no cache, no kernels, no
batching; the Sinkhorn rounds a Python loop of ``sum(axis)``; the mixes
``einsum`` over the axis of streams; expanded attention and the routed
feed-forward are ``reference_mla_moe``'s, the biased choice
``reference_kda_mla_moe``'s. It reads a checkpoint's tensors by their
Hugging Face names in torch's ``[out, in]`` layout and the model's
``config.json`` as a dict, so it also checks the loader's naming.

``n = hc_mult``, ``C = hidden_size``; a token's state is ``X [n, C]``.

- entry: ``X_0[j] = E[token]`` for every stream; exit: ``h = sum_j X[j]``,
  then ``rmsnorm(h; model.norm)`` and the untied head.
  DEPARTURE (assumed): arXiv:2409.19606 sums the streams at the exit; a
  learned contraction, if the published model has one, is not in the
  catalog's keys.
- a sub-layer ``F`` (attention, then feed-forward; each with its own
  ``hc_<part>_fn [n^2 + 2n, n C]``, ``hc_<part>_base [n^2 + 2n]``,
  ``hc_<part>_scale [3]``): ``x~ = vec(X)``; ``r = x~ / sqrt(mean(x~^2) +
  rms_norm_eps)`` with no weight (assumed: the papers norm the flattened
  state before the projection and give it no weight of its own); ``m = r
  fn^T``, its columns in the order pre (n), post (n), res (n x n,
  row-major: assumed); ``H_pre = sigmoid(a_pre m_pre + b_pre)``; ``H_post =
  2 sigmoid(a_post m_post + b_post)``; ``M = exp(clamp(a_res m_res + b_res,
  clamp_min, clamp_max))`` and ``hc_sinkhorn_iters`` rounds of ``M <- M /
  (rowsum + hc_eps)``, ``M <- M / (colsum + hc_eps)`` (rows first, the eps
  in both divisions, the clamp on the logits: assumed); ``u = sum_j
  H_pre[j] X[j]``; ``y = F(rmsnorm(u; the sub-layer's norm))``; ``X'[i] =
  H_post[i] y + sum_j H_res[i, j] X[j]``.
- the choice of experts on ``score + mlp.gate.e_score_correction_bias``
  (``topk_method`` ``noaux_tc``), the weights the unbiased scores
  normalised over the chosen.
- the next-token prediction block (``num_nextn_predict_layers``) takes no
  part in the model's own logits and is not read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cake_tpu.testing.reference_kda_mla_moe import route
from cake_tpu.testing.reference_mla_moe import (_f32, attention,
                                                held_experts, rmsnorm,
                                                swiglu)


def sinkhorn(m, iters: int, eps: float):
    """``iters`` rounds of row, then column normalisation of ``m [.., n,
    n]`` (rows: axis -2's entries sum over axis -1)."""
    for _ in range(iters):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
    return m


def coefficients(cfg: dict, tensors, p: str, part: str, x):
    """``(H_pre [t, n], H_post [t, n], H_res [t, n, n])`` of sub-layer
    ``part`` ("attn" | "ffn") of layer ``p`` on the stream ``x [t, n, C]``."""
    n, t = cfg["hc_mult"], x.shape[0]
    flat = x.reshape(t, -1)
    r = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                             + cfg["rms_norm_eps"])
    m = r @ _f32(tensors, f"{p}hc_{part}_fn").T
    b = _f32(tensors, f"{p}hc_{part}_base")
    a_pre, a_post, a_res = _f32(tensors, f"{p}hc_{part}_scale")
    pre = jax.nn.sigmoid(a_pre * m[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a_post * m[:, n:2 * n] + b[n:2 * n])
    logit = jnp.clip(a_res * m[:, 2 * n:] + b[2 * n:],
                     cfg.get("mhc_h_res_clamp_min", -30.0),
                     cfg.get("mhc_h_res_clamp_max", 30.0))
    res = sinkhorn(jnp.exp(logit).reshape(t, n, n),
                   cfg.get("hc_sinkhorn_iters", 20), cfg.get("hc_eps", 1e-6))
    return pre, post, res


def sub_layer(cfg: dict, tensors, p: str, part: str, norm: str, x, f,
              identity_res: bool = False):
    """``X' = H_post y + H_res X`` with ``y = f(rmsnorm(H_pre X))``.
    ``identity_res``: ``H_res = I`` (four plain residual streams: the
    benchmark's control, which must NOT agree with the model)."""
    pre, post, res = coefficients(cfg, tensors, p, part, x)
    if identity_res:
        res = jnp.broadcast_to(jnp.eye(cfg["hc_mult"]), res.shape)
    u = jnp.einsum("tj,tjc->tc", pre, x)
    y = f(rmsnorm(u, _f32(tensors, p + norm), cfg["rms_norm_eps"]))
    return post[:, :, None] * y[:, None, :] + jnp.einsum(
        "tij,tjc->tic", res, x)


def expert_layer(cfg: dict, tensors, p: str, h):
    """``shared(h) + sum over the chosen experts of w_e expert_e(h)``, the
    choice on ``score + e_score_correction_bias``."""
    gate = _f32(tensors, p + "mlp.gate.weight")  # [E, hidden]
    bias = (_f32(tensors, p + "mlp.gate.e_score_correction_bias")
            if cfg.get("topk_method") == "noaux_tc"
            else jnp.zeros((gate.shape[0],)))
    idx, w = route(cfg, jax.nn.sigmoid(h @ gate.T), bias)
    out = jnp.zeros_like(h)
    for e in held_experts(tensors, p, gate.shape[0]):
        w_e = jnp.where(idx == e, w, 0.0).sum(-1)  # 0 where not chosen
        out = out + w_e[:, None] * swiglu(h, tensors, f"{p}mlp.experts.{e}.")
    if cfg.get("n_shared_experts"):
        out = out + swiglu(h, tensors, p + "mlp.shared_experts.")
    return out


def hidden_states(cfg: dict, tensors, tokens, identity_res: bool = False):
    """The streams' sum ``[t, hidden]`` after the last layer (before the
    final norm) of one sequence."""
    n = cfg["hc_mult"]
    e = _f32(tensors, "model.embed_tokens.weight")[jnp.asarray(tokens)]
    x = jnp.broadcast_to(e[:, None, :], (e.shape[0], n, e.shape[1]))
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        x = sub_layer(cfg, tensors, p, "attn", "input_layernorm.weight", x,
                      lambda h: attention(cfg, tensors, p, h), identity_res)
        if (cfg.get("n_routed_experts")
                and i >= cfg.get("first_k_dense_replace", 0)):
            feed = lambda h: expert_layer(cfg, tensors, p, h)  # noqa: E731
        else:
            feed = lambda h: swiglu(h, tensors, p + "mlp.")  # noqa: E731
        x = sub_layer(cfg, tensors, p, "ffn",
                      "post_attention_layernorm.weight", x, feed,
                      identity_res)
    return x.sum(axis=1)


def logits(cfg: dict, tensors, tokens, identity_res: bool = False):
    """``[t, vocab]`` float32 logits at every position of ``tokens``."""
    with jax.default_matmul_precision("highest"):
        x = rmsnorm(hidden_states(cfg, tensors, tokens, identity_res),
                    _f32(tensors, "model.norm.weight"), cfg["rms_norm_eps"])
        return x @ _f32(tensors, "lm_head.weight").T
