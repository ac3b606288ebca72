"""Plain reference of the scalar-gated delta-rule + gated attention decoder
over softmax-scored experts beside a gated shared one (Qwen3-Next's
``config.json`` keys; ``model_type`` ``qwen3_next``): the whole forward
pass of one sequence in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.

Written from the equations the issue states (ISSUE 57, Motivation), not
from ``cake_tpu/ops``: the delta rule token by token from a zero state,
attention over the whole sequence under an explicit mask, no cache, no
chunk form, no kernel, no batching, a Python loop over the experts. It
reads a checkpoint's tensors by their Hugging Face names (``tensors[name]``,
torch layouts, the fused projections a key head's group at a time, the
norms as ``w - 1``) and the model's ``config.json`` as a dict, so it also
checks the loader's naming, its folds and the file's reading.

``N(x; w) = x * rsqrt(mean x^2 + eps) * (1 + w)``: the family's zero-centred
norm, every norm but the delta rule's output norm, which is a PLAIN weight.
Layer ``i`` (0-based) is full attention where ``(i + 1) %
full_attention_interval == 0`` and Gated DeltaNet otherwise; ``h +=
Mixer_i(N(h))``, ``h += MoE(N(h))``.

- **Gated DeltaNet** (``Hk = linear_num_key_heads`` under ``Hv =
  linear_num_value_heads``, ``r = Hv / Hk``): ``x W_qkvz`` is, a key head
  ``j`` at a time, ``[q_j (d_k) | k_j (d_k) | v of value heads j r .. j r
  + r - 1 (r d_v) | z likewise]``; ``x W_ba`` is ``[b (r) | a (r)]`` a key
  head. ``[q | k | v] = silu(conv([q | k | v]))``: causal, depthwise, no
  bias, ``linear_conv_kernel_dim`` taps over all heads' q, then k, then v.
  Each head of q and k is L2-normalised (``x * rsqrt(sum x^2 + 1e-6)``), q
  times ``d_k^-0.5``; value head ``h`` uses key head ``h // r``. ``beta_h =
  sigmoid(b_h)``, ``g_h = -exp(A_log_h) * softplus(a_h + dt_bias_h)``.
  State ``S_h [d_k, d_v]`` float32 from zero: ``S_h <- e^{g_h} S_h``; ``S_h
  <- S_h + beta_h k (v_h - S_h^T k)^T``; ``o_h = S_h^T q``. ``y =
  concat_h(rms(o_h; w) * silu(z_h)) W_o``.
- **Gated attention**: ``x W_q`` is a head's ``[q_h | gamma_h]`` side by
  side; ``q_h <- N(q_h)``, ``k_j <- N(k_j)`` over ``head_dim``; the FIRST
  ``partial_rotary_factor * head_dim`` channels of q and k rotate
  (half-split pairs ``(c, c + r / 2)``, ``rope_theta``, no scaling), the
  rest stay; causal softmax attention at ``head_dim^-0.5``, query head
  ``h`` over key/value head ``h // (heads / kv heads)``; ``y =
  concat_h(a_h * sigmoid(gamma_h)) W_o``.
- **Experts**: ``p = softmax(x W_r)`` over ALL the router's experts, the
  ``num_experts_per_tok`` largest (ties to the lower index), their shares
  over their sum (the long form: the program takes softmax over the chosen
  logits, and the tests hold the two together); ``y = sum over the chosen
  experts e that the checkpoint HOLDS of p_e E_e(x) + sigmoid(x w_sg) *
  E_shared(x)``.
- Head: ``N(h; model.norm) W_head``. The multi-token-prediction block
  (``mtp.*``) is not read.

The handles of the controls that must FAIL: ``gate=False`` leaves the
attention's output gate out, ``rotate_all=True`` rotates the whole head,
``shared_gate=False`` adds the shared expert unweighted, ``state_dtype``
rounds the state after every token (``jnp.bfloat16``), ``decay=False``
leaves the delta rule's decay out, ``grouped=False`` gives value head ``h``
key head ``h % Hk``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

L2_EPS = 1e-6


def _f32(tensors, name):
    return jnp.asarray(np.asarray(tensors[name], np.float32))


def norm(x, w, eps):
    """The family's zero-centred RMS norm: ``(1 + w)``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w)


def is_full_layer(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg.get("full_attention_interval", 4) == 0


def delta_net(cfg: dict, tensors, p: str, x, state_dtype=None, decay=True,
              grouped=True):
    """One Gated DeltaNet layer over a whole sequence ``x [t, hidden]``,
    token by token from a zero state."""
    t = x.shape[0]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps, r = cfg["linear_conv_kernel_dim"], hv // hk
    a = p + "linear_attn."
    # a key head's group at a time: [q | k | v (r heads) | z (r heads)]
    qkvz = (x @ _f32(tensors, a + "in_proj_qkvz.weight").T).reshape(
        t, hk, 2 * dk + 2 * r * dv)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv].reshape(t, hv, dv)
    z = qkvz[..., 2 * dk + r * dv:].reshape(t, hv, dv)
    ba = (x @ _f32(tensors, a + "in_proj_ba.weight").T).reshape(t, hk, 2 * r)
    b, decay_in = ba[..., :r].reshape(t, hv), ba[..., r:].reshape(t, hv)
    # ONE causal depthwise convolution over [all q | all k | all v]
    mixed = jnp.concatenate([y.reshape(t, -1) for y in (q, k, v)], axis=-1)
    w = _f32(tensors, a + "conv1d.weight")[:, 0, :]  # [C, K]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, mixed.shape[1]), jnp.float32), mixed])
    mixed = jax.nn.silu(sum(padded[j:j + t] * w[:, j] for j in range(taps)))

    def l2(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + L2_EPS)

    q = l2(mixed[:, :hk * dk].reshape(t, hk, dk)) * dk ** -0.5
    k = l2(mixed[:, hk * dk:2 * hk * dk].reshape(t, hk, dk))
    v = mixed[:, 2 * hk * dk:].reshape(t, hv, dv)
    if grouped:  # h -> h // r
        q, k = jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1)
    else:  # a control: h -> h % Hk
        q, k = jnp.tile(q, (1, r, 1)), jnp.tile(k, (1, r, 1))
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(_f32(tensors, a + "A_log")) * jax.nn.softplus(
        decay_in + _f32(tensors, a + "dt_bias"))  # [t, Hv]
    if not decay:  # a control
        g = jnp.zeros_like(g)

    def token(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[:, None, None]
        ks = jnp.einsum("hk,hkv->hv", k_t, s)
        s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - ks)[:, None, :]
        if state_dtype is not None:  # a control: the state's precision
            s = s.astype(state_dtype).astype(jnp.float32)
        return s, jnp.einsum("hk,hkv->hv", q_t, s)

    _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    # the output norm's weight is PLAIN (not 1 + w)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg["rms_norm_eps"]) * _f32(
        tensors, a + "norm.weight")
    o = (o * jax.nn.silu(z)).reshape(t, hv * dv)
    return o @ _f32(tensors, a + "out_proj.weight").T


def rotate(x, theta: float, width: int):
    """``x [heads, t, d]``: the first ``width`` channels rotated, pairs
    ``(c, c + width / 2)`` of position ``t`` by ``t * theta^(-2c / width)``;
    the rest left as they are."""
    t = x.shape[1]
    inv = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :width // 2], x[..., width // 2:width]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., width:]], axis=-1)


def attention(cfg: dict, tensors, p: str, x, gate=True, rotate_all=False):
    t = x.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, a = cfg["rms_norm_eps"], p + "self_attn."
    qg = (x @ _f32(tensors, a + "q_proj.weight").T).reshape(t, nh, 2 * d)
    q, gamma = qg[..., :d], qg[..., d:]  # a head's [q | gate]
    k = (x @ _f32(tensors, a + "k_proj.weight").T).reshape(t, nkv, d)
    v = (x @ _f32(tensors, a + "v_proj.weight").T).reshape(t, nkv, d)
    q = norm(q, _f32(tensors, a + "q_norm.weight"), eps).transpose(1, 0, 2)
    k = norm(k, _f32(tensors, a + "k_norm.weight"), eps).transpose(1, 0, 2)
    width = d if rotate_all else int(
        d * cfg.get("partial_rotary_factor", 1.0))
    theta = float(cfg["rope_theta"])
    q, k = rotate(q, theta, width), rotate(k, theta, width)
    k, v = (jnp.repeat(y, nh // nkv, axis=0) for y in (k, v.transpose(
        1, 0, 2)))
    seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(seen[None], q @ k.transpose(0, 2, 1) * d ** -0.5,
                       -jnp.inf)
    out = (jax.nn.softmax(scores, axis=-1) @ v).transpose(1, 0, 2)  # [t,H,d]
    if gate:
        out = out * jax.nn.sigmoid(gamma)
    return out.reshape(t, nh * d) @ _f32(tensors, a + "o_proj.weight").T


def swiglu(x, tensors, prefix):
    g = x @ _f32(tensors, prefix + "gate_proj.weight").T
    u = x @ _f32(tensors, prefix + "up_proj.weight").T
    return (jax.nn.silu(g) * u) @ _f32(tensors, prefix + "down_proj.weight").T


def route(cfg: dict, logits):
    """``logits [t, E]`` -> ``(idx [t, k], weight [t, k])``: softmax over
    ALL experts, the ``k`` largest (a stable sort of the negated shares:
    ties go to the lower index), their shares over the chosen ones' sum."""
    p = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argsort(-p, axis=-1, stable=True)[:, :cfg["num_experts_per_tok"]]
    w = jnp.take_along_axis(p, idx, axis=-1)
    return idx, w / w.sum(-1, keepdims=True)


def expert_layer(cfg: dict, tensors, p: str, h, held=None, shared=True,
                 shared_gate=True):
    """``routed part + sigmoid(h w_sg) * shared(h)``. ``held``: a range of
    global expert ids to restrict the routed part to (a chip's share of
    the experts; None: every expert the checkpoint stores); ``shared``:
    whether the shared expert is added: the share test's handles, which
    counts it once."""
    gate = _f32(tensors, p + "mlp.gate.weight")  # [E, hidden]
    idx, w = route(cfg, h @ gate.T)
    out = jnp.zeros_like(h)
    for e in range(gate.shape[0]):
        if f"{p}mlp.experts.{e}.gate_proj.weight" not in tensors or (
                held is not None and e not in held):
            continue
        w_e = jnp.where(idx == e, w, 0.0).sum(-1)  # 0 where not chosen
        out = out + w_e[:, None] * swiglu(h, tensors, f"{p}mlp.experts.{e}.")
    if shared:
        y = swiglu(h, tensors, p + "mlp.shared_expert.")
        if shared_gate:
            y = y * jax.nn.sigmoid(
                h @ _f32(tensors, p + "mlp.shared_expert_gate.weight").T)
        out = out + y
    return out


def hidden_states(cfg: dict, tensors, tokens, gate=True, rotate_all=False,
                  shared_gate=True, **delta):
    """Last hidden states ``[t, hidden]`` (before the final norm) of one
    sequence."""
    eps = cfg["rms_norm_eps"]
    x = _f32(tensors, "model.embed_tokens.weight")[jnp.asarray(tokens)]
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        h = norm(x, _f32(tensors, p + "input_layernorm.weight"), eps)
        if is_full_layer(cfg, i):
            x = x + attention(cfg, tensors, p, h, gate, rotate_all)
        else:
            x = x + delta_net(cfg, tensors, p, h, **delta)
        h = norm(x, _f32(tensors, p + "post_attention_layernorm.weight"),
                 eps)
        x = x + expert_layer(cfg, tensors, p, h, shared_gate=shared_gate)
    return x


def logits(cfg: dict, tensors, tokens, **controls):
    """``[t, vocab]`` float32 logits at every position of ``tokens``."""
    with jax.default_matmul_precision("highest"):
        x = norm(hidden_states(cfg, tensors, tokens, **controls),
                 _f32(tensors, "model.norm.weight"), cfg["rms_norm_eps"])
        return x @ _f32(tensors, "lm_head.weight").T
