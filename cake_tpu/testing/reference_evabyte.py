"""Plain reference of the EVA decoder (EvaByte's ``config.json`` keys;
``model_type`` ``evabyte``): the whole forward pass of one sequence in
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``.

Written from the equations the issue states (ISSUE 66, Motivation), not
from ``cake_tpu/ops``: the whole sequence at once, no cache, no kernel, no
batching, every layer's attention ONE masked softmax over the sequence's
own keys and the summaries of its own chunks. It reads a checkpoint's
tensors by their Hugging Face names (``tensors[name]``, torch layouts) and
the model's ``config.json`` as a dict, so it also checks the loader's
naming.

- ``RMS(x; w) = x / rms(x) * (1 + w)`` (``norm_add_unit_offset``), eps
  ``rms_norm_eps``; ``x += EVA(RMS(x; input_layernorm)) W_o``; ``x +=
  SwiGLU(RMS(x; post_attention_layernorm))``; the head reads ``RMS(x;
  model.norm)`` and gives ``num_pred_heads`` blocks of ``vocab_size``
  logits, block 0 the model's own next token.
- EVA (``W = window_size``, ``C = chunk_size``, ``s = head_dim^-0.5``;
  ``phi_h``, ``mu_h`` a head from ``self_attn.adaptive_phi`` /
  ``adaptive_mu_k [1, H, 1, 1, D]``; q and k rotated over the whole head,
  half-split pairs, base ``rope_theta``): chunk ``c``'s summary is ``v~_c =
  sum_m softmax_m(s phi_h . k_m) v_m`` and ``k~_c = mean_m k_m + mu_h`` over
  ``m`` in ``[cC, (c + 1)C)``; query ``n`` sees keys ``m`` with ``m // W ==
  n // W`` and ``m <= n``, and summaries ``c`` with ``(c + 1) C <= (n // W)
  W``; ONE softmax over both sets.

What ``config.json`` does not settle (that ``omega_c`` is the learned
``phi_h`` with no random draw and no ``-|k|^2 / 2`` term, that keys are
pooled uniformly and after the rotation, that a chunk becomes visible when
its WINDOW completes, the tensor names) is the benchmark configuration's
``assumed``.

``wrong`` names ONE piece of the mathematics to get wrong, for the controls
that must FAIL: ``"window_only"`` (no summaries: what left the window is
dropped), ``"sliding"`` (the last ``W`` positions in place of the query's
own window, summaries of the chunks before them), ``"early_chunk"`` (a
chunk visible as soon as it is complete, its positions then counted
twice), ``"uniform_v"`` (``v~`` the chunk's plain mean), ``"no_mu"``
(``k~`` without ``mu``), ``"plain_norm"`` (norm weights taken as stored,
no ``1 +``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cake_tpu.testing.reference_lfm2_moe import rope
from cake_tpu.testing.reference_mla_moe import _f32, swiglu

WRONG = ("window_only", "sliding", "early_chunk", "uniform_v", "no_mu",
         "plain_norm")


def visibility(t: int, window: int, chunk: int, wrong=None):
    """``(local [t, t], remote [t, t // chunk])``: which keys and which
    chunk summaries each query of a ``t``-token sequence sees, straight
    from the two set definitions."""
    n = jnp.arange(t)[:, None]
    m = jnp.arange(t)[None, :]
    c = jnp.arange(t // chunk)[None, :]
    if wrong == "sliding":
        local = (m <= n) & (m > n - window)
        remote = (c + 1) * chunk <= n - window + 1
    else:
        local = (m <= n) & (m // window == n // window)
        remote = (c + 1) * chunk <= (n // window) * window
    if wrong == "early_chunk":
        remote = (c + 1) * chunk <= n + 1
    if wrong == "window_only":
        remote = jnp.zeros_like(remote)
    return local, remote


def rmsnorm(x, w, eps: float, wrong=None):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (w if wrong == "plain_norm" else 1.0 + w)


def attention(cfg: dict, tensors, p: str, x, wrong=None):
    """EVA attention over ``x [t, hidden]`` (``t`` padded by the caller to
    a whole number of chunks; a query only sees chunks that are
    complete)."""
    t = x.shape[0]
    h = cfg["num_attention_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // h
    w, c = cfg["window_size"], cfg["chunk_size"]
    a = p + "self_attn."

    def heads(name):
        y = (x @ _f32(tensors, a + f"{name}_proj.weight").T).reshape(t, h, d)
        return y.transpose(1, 0, 2)  # [h, t, d]

    q, k, v = rope(cfg, heads("q")), rope(cfg, heads("k")), heads("v")
    phi = _f32(tensors, a + "adaptive_phi").reshape(h, d)
    mu = _f32(tensors, a + "adaptive_mu_k").reshape(h, d)
    scale = d ** -0.5
    kc = k.reshape(h, t // c, c, d)
    vc = v.reshape(h, t // c, c, d)
    share = jax.nn.softmax(
        jnp.einsum("hncd,hd->hnc", kc, phi) * scale, axis=-1)
    if wrong == "uniform_v":
        share = jnp.full_like(share, 1.0 / c)
    v_sum = jnp.einsum("hnc,hncd->hnd", share, vc)
    k_sum = kc.mean(axis=2) + (0.0 if wrong == "no_mu" else mu[:, None, :])
    local, remote = visibility(t, w, c, wrong)
    scores = jnp.concatenate([
        jnp.where(local[None], q @ k.transpose(0, 2, 1) * scale, -jnp.inf),
        jnp.where(remote[None], q @ k_sum.transpose(0, 2, 1) * scale,
                  -jnp.inf)], axis=-1)
    out = jax.nn.softmax(scores, axis=-1) @ jnp.concatenate([v, v_sum], 1)
    return out.transpose(1, 0, 2).reshape(t, h * d) @ _f32(
        tensors, a + "o_proj.weight").T


def hidden(cfg: dict, tensors, tokens, wrong=None):
    """The last layer's output ``[t, hidden]`` of one sequence."""
    eps = cfg["rms_norm_eps"]
    t, c = len(tokens), cfg["chunk_size"]
    x = _f32(tensors, "model.embed_tokens.weight")[jnp.asarray(tokens)]
    # whole chunks: the rows behind the sequence are seen by nobody
    x = jnp.pad(x, ((0, -t % c), (0, 0)))
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        x = x + attention(cfg, tensors, p, rmsnorm(
            x, _f32(tensors, p + "input_layernorm.weight"), eps, wrong),
            wrong)
        x = x + swiglu(rmsnorm(
            x, _f32(tensors, p + "post_attention_layernorm.weight"), eps,
            wrong), tensors, p + "mlp.")
    return x[:t]


def logits(cfg: dict, tensors, tokens, wrong=None, pred_head: int = 0):
    """``[t, vocab]`` float32 logits at every position of ``tokens``:
    block ``pred_head`` of the stored head (0: the model's own next
    token)."""
    if wrong not in (None,) + WRONG:
        raise ValueError(f"wrong must be one of {WRONG}, got {wrong!r}")
    with jax.default_matmul_precision("highest"):
        x = rmsnorm(hidden(cfg, tensors, tokens, wrong),
                    _f32(tensors, "model.norm.weight"), cfg["rms_norm_eps"],
                    wrong)
        v = cfg["vocab_size"]
        head = _f32(tensors, "lm_head.weight")[pred_head * v:
                                               (pred_head + 1) * v]
        return x @ head.T
