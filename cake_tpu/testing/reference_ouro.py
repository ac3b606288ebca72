"""Plain reference of the looped decoder (Ouro-2.6B's ``config.json`` keys;
``model_type`` ``ouro``): the whole forward pass of one sequence in
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``.

Written from the equations the issue states (ISSUE 47, Motivation), not
from ``cake_tpu/ops``: the whole sequence at once, no cache, no kernel, no
batching, the passes a Python loop over a Python loop over the layers,
full causal attention over the whole sequence in each (layer, pass). It
reads a checkpoint's tensors by their Hugging Face names (``tensors[name]``,
torch layouts) and the model's ``config.json`` as a dict, so it also checks
the loader's naming.

Every norm is an RMSNorm with a plain weight and ``rms_norm_eps``.

- ``h = E[tokens]``; for ``u`` in ``0 .. total_ut_steps - 1``: the
  ``num_hidden_layers`` layers in order, THE SAME weights every pass; then
  ``h = RMS(h; model.norm)``. The normed state is pass ``u``'s output AND
  pass ``u + 1``'s input.
- Layer ``i`` (sandwich norm): ``a = Attn_i(RMS(h; input_layernorm))``,
  ``h += RMS(a; input_layernorm_2)``, ``m = SwiGLU_i(RMS(h;
  post_attention_layernorm))``, ``h += RMS(m; post_attention_layernorm_2)``.
- Attention: q, k, v, o with no bias, ``num_attention_heads`` query heads
  over ``num_key_value_heads`` key/value heads of ``head_dim``, q and k
  rotated (the half-rotation of ``(x[j], x[j + d/2])``, base ``rope_theta``,
  the whole head, the token's position in every pass), scale ``d^-0.5``,
  every ``j <= t`` seen. A pass attends over the keys and values THAT PASS
  computed (what a cache plane a (pass, layer) pair holds).
- The exit gate: ``lambda_u = sigmoid(h_u . w + b)`` on pass ``u``'s normed
  output (``model.early_exit_gate``); ``p_u = lambda_u * prod_{v < u} (1 -
  lambda_v)`` for ``u`` short of the last pass, the last pass takes the
  rest. A token takes the first pass whose cumulative ``p`` reaches
  ``early_exit_threshold``; at the published 1 that is the last pass, so
  ``logits = h_last W_head`` with NO further norm, and the gate changes no
  logit (:func:`exit_distribution` is what reads its tensors).

Departures from the published description: none. What ``config.json`` does
not settle (the sandwich order and its names, the norm between passes and
none before the head, the gate's form) is the benchmark configuration's
``assumed``.

``wrong`` names ONE piece of the mathematics to get wrong, for the controls
that must FAIL: ``"passes"`` (one pass fewer), ``"pass_norm"`` (no norm
between passes: the last pass alone is normed, as a head's norm would),
``"head_norm"`` (a second norm before the head), ``"shared_plane"`` (every
pass attends over pass 0's keys and values: one cache for the loop),
``"post_norms"`` (the two output norms left out).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cake_tpu.testing.reference_lfm2_moe import rope
from cake_tpu.testing.reference_mla_moe import _f32, rmsnorm, swiglu

WRONG = ("passes", "pass_norm", "head_norm", "shared_plane", "post_norms")


def attention(cfg: dict, tensors, p: str, x, shared=None):
    """Full causal attention over ``x [t, hidden]``. ``shared``: the keys
    and values to attend over in place of this call's own (the
    ``shared_plane`` control); returns ``(out, (k, v))``."""
    t = x.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // nh
    a = p + "self_attn."

    def heads(name, n):
        y = (x @ _f32(tensors, a + f"{name}_proj.weight").T).reshape(t, n, d)
        return y.transpose(1, 0, 2)  # [n, t, d]

    q, k, v = rope(cfg, heads("q", nh)), rope(cfg, heads("k", nkv)), heads(
        "v", nkv)
    own = (k, v)
    if shared is not None:
        k, v = shared
    k, v = (jnp.repeat(y, nh // nkv, axis=0) for y in (k, v))
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scores = jnp.where(seen[None], q @ k.transpose(0, 2, 1) * d ** -0.5,
                       -jnp.inf)
    out = (jax.nn.softmax(scores, axis=-1) @ v).transpose(1, 0, 2)
    return out.reshape(t, nh * d) @ _f32(tensors, a + "o_proj.weight").T, own


def pass_outputs(cfg: dict, tensors, tokens, wrong=None):
    """Every pass's output ``[t, hidden]`` (normed, as the next pass and
    the head take it) of one sequence."""
    eps = cfg["rms_norm_eps"]
    passes = cfg["total_ut_steps"] - (wrong == "passes")

    def norm(x, name):
        return rmsnorm(x, _f32(tensors, name), eps)

    def post(x, name):  # a sub-layer's output norm
        return x if wrong == "post_norms" else norm(x, name)

    h = _f32(tensors, "model.embed_tokens.weight")[jnp.asarray(tokens)]
    first = {}  # layer -> pass 0's keys and values
    outs = []
    for u in range(passes):
        for i in range(cfg["num_hidden_layers"]):
            p = f"model.layers.{i}."
            a, kv = attention(
                cfg, tensors, p, norm(h, p + "input_layernorm.weight"),
                first.get(i) if wrong == "shared_plane" else None)
            first.setdefault(i, kv)
            h = h + post(a, p + "input_layernorm_2.weight")
            m = swiglu(norm(h, p + "post_attention_layernorm.weight"),
                       tensors, p + "mlp.")
            h = h + post(m, p + "post_attention_layernorm_2.weight")
        if wrong != "pass_norm" or u == passes - 1:
            h = norm(h, "model.norm.weight")
        outs.append(h)
    return outs


def exit_distribution(cfg: dict, tensors, tokens):
    """``[passes, t]``: the probability that each token leaves the loop
    after each pass."""
    with jax.default_matmul_precision("highest"):
        w = _f32(tensors, "model.early_exit_gate.weight")  # [1, hidden]
        b = _f32(tensors, "model.early_exit_gate.bias")  # [1]
        stay = 1.0
        p = []
        for h in pass_outputs(cfg, tensors, tokens)[:-1]:
            lam = jax.nn.sigmoid(h @ w[0] + b[0])
            p.append(lam * stay)
            stay = stay * (1.0 - lam)
        return jnp.stack(p + [stay * jnp.ones_like(p[0])])


def logits(cfg: dict, tensors, tokens, wrong=None):
    """``[t, vocab]`` float32 logits at every position of ``tokens``: the
    last pass's output through the head, every token taking every pass
    (``early_exit_threshold`` 1)."""
    if wrong not in (None,) + WRONG:
        raise ValueError(f"wrong must be one of {WRONG}, got {wrong!r}")
    if cfg.get("early_exit_threshold", 1) < 1:
        raise ValueError("this reference takes every pass (threshold 1)")
    with jax.default_matmul_precision("highest"):
        x = pass_outputs(cfg, tensors, tokens, wrong)[-1]
        if wrong == "head_norm":
            x = rmsnorm(x, _f32(tensors, "model.norm.weight"),
                        cfg["rms_norm_eps"])
        return x @ _f32(tensors, "lm_head.weight").T
