"""Plain reference of the window + full attention decoder whose two layer
kinds rotate differently, over softmax-scored experts (Mellum2's
``config.json`` keys; ``model_type`` ``mellum``): the whole forward pass of
one sequence in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.

Written from the equations the issue states (ISSUE 55, Tentpole), not from
``cake_tpu/ops``: the whole sequence at once under explicit masks, no
cache, no ring, no kernel, no batching, a Python loop over the experts. It
reads a checkpoint's tensors by their Hugging Face names
(``tensors[name]``, torch layouts: ``self_attn.{q,k,v,o}_proj``,
``self_attn.{q,k}_norm``, ``mlp.gate.weight``,
``mlp.experts.{e}.{gate,up,down}_proj``) and the model's ``config.json`` as
a dict, so it also checks the loader's naming and the file's reading.

Layer ``i`` (0-based), ``u = rmsnorm(h)``:

- ``q = rmsnorm_head(u W_q)``, ``k = rmsnorm_head(u W_k)`` (over each
  head's ``head_dim`` channels, ONE weight ``[head_dim]`` for all heads of
  q and one for k), ``v = u W_v``; ``num_attention_heads`` query heads
  over ``num_key_value_heads`` key/value heads, no bias, scale
  ``head_dim^-0.5``.
- q and k are rotated by the table of the layer's KIND
  (``rope_parameters[layer_types[i]]``): the half-rotation of ``(x[j], x[j
  + d/2])`` of position ``t`` by ``t * f_j``. ``rope_type`` ``default``:
  ``f_j = theta^(-2j/d)``. ``yarn``: ``f_j`` kept where pair ``j`` turns
  more than ``beta_fast`` times over ``original_max_position_embeddings``,
  divided by ``factor`` where it turns fewer than ``beta_slow`` times,
  blended linearly between (floor / ceil of the two correction
  dimensions), and cos and sin TIMES ``attention_factor`` at every position
  (``0.1 ln(factor) + 1`` where the file gives none).
- ``"sliding_attention"``: query ``t`` sees keys ``j`` with ``0 <= t - j <
  sliding_window``. ``"full_attention"``: every ``j <= t``.
- ``h += attention W_o``; ``m = rmsnorm(h)``; ``p = softmax(m W_r^T)`` over
  ALL the router's experts; the ``num_experts_per_tok`` largest (ties to
  the lower index), with weights ``p_e / sum of the chosen p`` (the long
  form: the program takes softmax over the chosen logits, and the tests
  hold the two together); ``h += sum over chosen experts e that the
  checkpoint HOLDS of w_e W_down,e (silu(m W_gate,e) * (m W_up,e))``. No
  shared expert, no bias, no scaling factor.

Departures from the published description, each a reading the published
``config.json`` does not settle (the benchmark configuration's
``assumed``):

- the per-head norms of q and k and the softmax scoring with renormalised
  shares are the convention of the key set the file carries (Qwen3-MoE's:
  ``max_window_layers``, ``use_sliding_window``, ``norm_topk_prob``,
  ``moe_intermediate_size``, ``num_experts``); the file names neither.
- pre-norm sublayers (``input_layernorm``, ``post_attention_layernorm``).
- ``intermediate_size`` is read and used by no layer (``mlp_layer_types``
  is ``sparse`` throughout); a next-token prediction head (the model
  card's, no key of the file) is not read.

The handles of the controls that must FAIL: ``window`` overrides the
window the masks are built with (one key more or fewer), ``one_rotation``
gives BOTH kinds the window layers' table (no YaRN, no
``attention_factor``), ``attention_factor`` overrides the file's.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from cake_tpu.testing.reference_mla_moe import (_f32, held_experts, rmsnorm,
                                                swiglu)


def rotation(rope: dict, t: int, d: int, attention_factor=None):
    """``(cos, sin) [t, d/2]`` of one layer kind's ``rope_parameters``
    entry."""
    theta = float(rope["rope_theta"])
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    amp = 1.0
    if rope.get("rope_type", "default") == "yarn":
        factor = float(rope["factor"])
        orig = float(rope["original_max_position_embeddings"])

        def correction(turns):
            return (d * math.log(orig / (turns * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(correction(rope.get("beta_fast", 32))), 0)
        high = min(math.ceil(correction(rope.get("beta_slow", 1))), d - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                        / (high - low), 0.0, 1.0)
        inv = inv / factor * ramp + inv * (1.0 - ramp)
        amp = rope.get("attention_factor")
        if amp is None:
            amp = 0.1 * math.log(factor) + 1.0
        if attention_factor is not None:
            amp = attention_factor
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(angle) * amp, jnp.sin(angle) * amp


def rotate(x, cos, sin):
    """``x [heads, t, d]``: the pairs ``(x[j], x[j + d/2])``."""
    d = x.shape[-1]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(cfg: dict, tensors, p: str, x, kind: str, window=None,
              one_rotation=False, attention_factor=None):
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
    rope = cfg["rope_parameters"][
        "sliding_attention" if one_rotation else kind]
    cos, sin = rotation(rope, t, d, attention_factor)
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    behind = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]  # t - j
    seen = behind >= 0
    if kind == "sliding_attention":
        seen &= behind < (window or cfg["sliding_window"])
    k, v = (jnp.repeat(y, nh // nkv, axis=0) for y in (k, v))
    scores = jnp.where(seen[None], q @ k.transpose(0, 2, 1) * d ** -0.5,
                       -jnp.inf)
    out = (jax.nn.softmax(scores, axis=-1) @ v).transpose(1, 0, 2)
    return out.reshape(t, nh * d) @ _f32(tensors, a + "o_proj.weight").T


def route(cfg: dict, logits):
    """``logits [t, E]`` -> ``(idx [t, k], weight [t, k])``: softmax over
    ALL experts, the ``k`` largest (a stable sort of the negated shares:
    ties go to the lower index), their shares over the chosen ones' sum."""
    p = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argsort(-p, axis=-1, stable=True)[:, :cfg["num_experts_per_tok"]]
    w = jnp.take_along_axis(p, idx, axis=-1)
    return idx, w / w.sum(-1, keepdims=True)


def expert_layer(cfg: dict, tensors, p: str, h, only=None):
    """The routed part (there is no other). ``only``: restrict it to these
    global expert ids (a share of the experts): the share test's handle."""
    gate = _f32(tensors, p + "mlp.gate.weight")  # [E, hidden]
    idx, w = route(cfg, h @ gate.T)
    out = jnp.zeros_like(h)
    for e in held_experts(tensors, p, gate.shape[0]):
        if only is not None and e not in only:
            continue
        w_e = jnp.where(idx == e, w, 0.0).sum(-1)  # 0 where not chosen
        out = out + w_e[:, None] * swiglu(h, tensors, f"{p}mlp.experts.{e}.")
    return out


def hidden_states(cfg: dict, tensors, tokens, **controls):
    """Last hidden states ``[t, hidden]`` (before the final norm) of one
    sequence."""
    eps = cfg["rms_norm_eps"]
    x = _f32(tensors, "model.embed_tokens.weight")[jnp.asarray(tokens)]
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        h = rmsnorm(x, _f32(tensors, p + "input_layernorm.weight"), eps)
        x = x + attention(cfg, tensors, p, h, cfg["layer_types"][i],
                          **controls)
        h = rmsnorm(x, _f32(tensors, p + "post_attention_layernorm.weight"),
                    eps)
        x = x + expert_layer(cfg, tensors, p, h)
    return x


def logits(cfg: dict, tensors, tokens, **controls):
    """``[t, vocab]`` float32 logits at every position of ``tokens``."""
    with jax.default_matmul_precision("highest"):
        x = rmsnorm(hidden_states(cfg, tensors, tokens, **controls),
                    _f32(tensors, "model.norm.weight"), cfg["rms_norm_eps"])
        return x @ _f32(tensors, "lm_head.weight").T
