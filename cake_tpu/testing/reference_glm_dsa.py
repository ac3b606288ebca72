"""Plain reference of the latent-attention, shared-expert decoder under a
learned sparse attention (DeepSeek sparse attention; ``model_type``
``glm_moe_dsa``: DeepSeek-V3's ``config.json`` keys and ``index_n_heads``,
``index_head_dim``, ``index_topk``, ``indexer_rope_interleave``, the rope
base nested in ``rope_parameters``): the whole forward pass of one
sequence in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.

Written from the equations ISSUE 61 states (from the catalog's keys and
DeepSeek-V3.2's published layer, whose keys ``glm_moe_dsa`` carries), not
from ``cake_tpu/ops``: no cache, no kernels, no batching, the attention
TOKEN BY TOKEN (a Python loop over the query rows: each scores the rows at
or before it, sorts them, keeps its best and attends those alone with
every head's keys and values materialised; the rows it dropped enter its
softmax at minus infinity, so that every token's step has one shape), a
Python loop over the experts. It reads a checkpoint's tensors by their
Hugging Face names in torch's ``[out, in]`` layout and the model's
``config.json`` as a dict, so it also checks the loader's naming.

Per token ``x_t`` of a layer (after the input norm), with ``c_q,t =
rmsnorm(x_t W_qa)`` the query latent of ``reference_mla_moe``'s attention:

    q^I_t,j = (c_q,t W^I_qb)_j              j = 1..index_n_heads heads of index_head_dim
    k^I_t   = LayerNorm(x_t W^I_k)          weight and bias, eps 1e-6
              rope (the attention's angles, interleaved pairs (2i, 2i+1)) on
              the FIRST qk_rope_head_dim channels of every q^I_t,j and of k^I_t
    w_t     = (x_t W^I_w) * index_n_heads^-0.5 * index_head_dim^-0.5
    I_t,s   = sum_j w_t,j relu(q^I_t,j . k^I_s)              s <= t
    S_t     = the index_topk rows s <= t of largest I_t,s (all of them where
              t + 1 <= index_topk; a tie goes to the lower s)
    out_t   = the attention's causal softmax over the rows of S_t alone

ASSUMED (the configuration's ``assumed`` says so too): the rotated slice
is the head's first ``qk_rope_head_dim`` channels; ``k_norm`` is a
LayerNorm with a bias; the published inference code's Hadamard rotation of
``q^I`` and ``k^I`` (orthogonal: products unchanged) and its FP8 storage
of them are left out. The choice of experts is on ``score +
mlp.gate.e_score_correction_bias`` (``topk_method`` ``noaux_tc``), the
weights the unbiased scores normalised over the chosen. The next-token
prediction block takes no part in the model's own logits and is not read.

Controls for the tests (each must NOT agree with the model): ``select=
False`` (every row at or before the query attended), ``index_rope=False``
(the indexer's q and k not rotated), ``k_norm=False`` (the key's LayerNorm
left out), ``topk=`` another count.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from cake_tpu.testing.reference_kda_mla_moe import route
from cake_tpu.testing.reference_mla_moe import (_f32, held_experts, rmsnorm,
                                                rope, rope_angles, swiglu)

K_NORM_EPS = 1e-6


def rope_cfg(cfg: dict) -> dict:
    """``cfg`` with the rope base where ``reference_mla_moe.rope_angles``
    reads it (``glm_moe_dsa`` nests it in ``rope_parameters``)."""
    if "rope_theta" in cfg:
        return cfg
    return {**cfg, "rope_theta": cfg["rope_parameters"]["rope_theta"]}


def layer_norm(x, w, b, eps=K_NORM_EPS):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def rope_first(x, cos, sin, width: int):
    """Rotate the first ``width`` channels of ``x [..., t, d]``."""
    return jnp.concatenate([rope(x[..., :width], cos, sin), x[..., width:]],
                           -1)


def index_scores(cfg: dict, tensors, p: str, x, c_q, *, index_rope=True,
                 k_norm=True):
    """``I [t, t]`` float32: row ``t``'s index score of every row ``s``
    (unmasked)."""
    t = x.shape[0]
    heads, dim = cfg["index_n_heads"], cfg["index_head_dim"]
    dr = cfg["qk_rope_head_dim"]
    a = p + "self_attn.indexer."
    q = (c_q @ _f32(tensors, a + "wq_b.weight").T).reshape(t, heads, dim)
    q = q.transpose(1, 0, 2)  # [J, t, D]
    k = x @ _f32(tensors, a + "wk.weight").T  # [t, D]
    if k_norm:
        k = layer_norm(k, _f32(tensors, a + "k_norm.weight"),
                       _f32(tensors, a + "k_norm.bias"))
    if index_rope:
        cos, sin = rope_angles(rope_cfg(cfg), t)
        q, k = rope_first(q, cos, sin, dr), rope_first(k, cos, sin, dr)
    w = (x @ _f32(tensors, a + "weights_proj.weight").T
         ) * heads ** -0.5 * dim ** -0.5  # [t, J]
    dots = jax.nn.relu(q @ k.T[None])  # [J, t, t]
    return jnp.einsum("jts,tj->ts", dots, w)


def chosen_rows(scores_t, t: int, topk: int):
    """``S_t`` as a mask over ``scores_t``'s rows: the ``topk`` rows ``s <=
    t`` of largest score, a tie to the lower ``s`` (a stable sort of the
    negated scores; rows past ``t`` sort last and are never taken)."""
    n = scores_t.shape[0]
    allowed = jnp.arange(n) <= t
    order = jnp.argsort(-jnp.where(allowed, scores_t, -jnp.inf), stable=True)
    taken = jnp.zeros((n,), bool).at[order[:topk]].set(True)
    return taken & allowed


def attention(cfg: dict, tensors, p: str, x, *, select=True, topk=None,
              index_rope=True, k_norm=True, chosen=None):
    """The layer's attention, token by token. ``chosen`` (a list) gains
    each query row's ``S_t`` as a list of ints."""
    t = x.shape[0]
    nh = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, dc = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    topk = cfg["index_topk"] if topk is None else topk
    a = p + "self_attn."
    c_q = rmsnorm(x @ _f32(tensors, a + "q_a_proj.weight").T,
                  _f32(tensors, a + "q_a_layernorm.weight"), eps)
    q = (c_q @ _f32(tensors, a + "q_b_proj.weight").T).reshape(t, nh, dn + dr)
    ckv = x @ _f32(tensors, a + "kv_a_proj_with_mqa.weight").T
    c = rmsnorm(ckv[:, :dc], _f32(tensors, a + "kv_a_layernorm.weight"), eps)
    kv = (c @ _f32(tensors, a + "kv_b_proj.weight").T).reshape(t, nh, dn + dv)
    cos, sin = rope_angles(rope_cfg(cfg), t)
    q_pe = rope(q[:, :, dn:].transpose(1, 0, 2), cos, sin)  # [H, t, dr]
    k_pe = rope(ckv[:, dc:], cos, sin)  # [t, dr], one for all heads
    q_nope = q[:, :, :dn].transpose(1, 0, 2)  # [H, t, dn]
    k_nope = kv[:, :, :dn].transpose(1, 0, 2)
    v = kv[:, :, dn:].transpose(1, 0, 2)  # [H, t, dv]
    scale = (dn + dr) ** -0.5
    index = index_scores(cfg, tensors, p, x, c_q, index_rope=index_rope,
                         k_norm=k_norm)
    rows = []
    for i in range(t):
        out, s_i = _token(i, index[i], q_nope[:, i], q_pe[:, i], k_nope, k_pe,
                          v, topk=topk, select=select, scale=scale)
        if chosen is not None:
            chosen.append([int(s) for s in jnp.flatnonzero(s_i)])
        rows.append(out)
    return jnp.stack(rows) @ _f32(tensors, a + "o_proj.weight").T


@functools.partial(jax.jit, static_argnames=("topk", "select", "scale"))
def _token(i, index_i, q_nope_i, q_pe_i, k_nope, k_pe, v, *, topk, select,
           scale):
    """One query row ``i``: its ``S_i`` as a mask over all ``t`` rows and
    its attention's output over them (a row outside ``S_i`` gets the weight
    exp(-inf) = 0, which IS the softmax over the rows of ``S_i`` alone;
    every token's step has the same shapes, so one compiled step serves
    the loop)."""
    t = k_pe.shape[0]
    s_i = chosen_rows(index_i, i, topk) if select else jnp.arange(t) <= i
    score = (jnp.einsum("hn,hsn->hs", q_nope_i, k_nope)
             + q_pe_i @ k_pe.T) * scale  # [H, t]
    weight = jax.nn.softmax(jnp.where(s_i[None], score, -jnp.inf), -1)
    return jnp.einsum("hs,hsv->hv", weight, v).reshape(-1), s_i


def expert_layer(cfg: dict, tensors, p: str, h, held=None):
    """``shared(h) + sum over the chosen experts of w_e expert_e(h)``, the
    choice on ``score + e_score_correction_bias``. ``held``: restrict the
    routed part to these global expert ids (a share of the experts the
    checkpoint holds; the shared expert is still added): the share test's
    handle."""
    gate = _f32(tensors, p + "mlp.gate.weight")  # [E, hidden]
    bias = (_f32(tensors, p + "mlp.gate.e_score_correction_bias")
            if cfg.get("topk_method") == "noaux_tc"
            else jnp.zeros((gate.shape[0],)))
    idx, w = route(cfg, jax.nn.sigmoid(h @ gate.T), bias)
    out = jnp.zeros_like(h)
    for e in held_experts(tensors, p, gate.shape[0]):
        if held is not None and e not in held:
            continue
        w_e = jnp.where(idx == e, w, 0.0).sum(-1)  # 0 where not chosen
        out = out + w_e[:, None] * swiglu(h, tensors, f"{p}mlp.experts.{e}.")
    if cfg.get("n_shared_experts"):
        out = out + swiglu(h, tensors, p + "mlp.shared_experts.")
    return out


def hidden_states(cfg: dict, tensors, tokens, *, held=None, chosen=None,
                  **controls):
    """Last hidden states ``[t, hidden]`` (before the final norm) of one
    sequence. ``chosen`` (a list) gains a list a layer of each query row's
    ``S_t``; ``controls``: ``select``, ``topk``, ``index_rope``,
    ``k_norm`` of :func:`attention`."""
    eps = cfg["rms_norm_eps"]
    x = _f32(tensors, "model.embed_tokens.weight")[jnp.asarray(tokens)]
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        of_layer = None if chosen is None else []
        x = x + attention(cfg, tensors, p, rmsnorm(
            x, _f32(tensors, p + "input_layernorm.weight"), eps),
            chosen=of_layer, **controls)
        if chosen is not None:
            chosen.append(of_layer)
        h = rmsnorm(x, _f32(tensors, p + "post_attention_layernorm.weight"),
                    eps)
        if (cfg.get("n_routed_experts")
                and i >= cfg.get("first_k_dense_replace", 0)):
            x = x + expert_layer(cfg, tensors, p, h, held)
        else:
            x = x + swiglu(h, tensors, p + "mlp.")
    return x


def logits(cfg: dict, tensors, tokens, **kw):
    """``[t, vocab]`` float32 logits at every position of ``tokens``."""
    with jax.default_matmul_precision("highest"):
        x = rmsnorm(hidden_states(cfg, tensors, tokens, **kw),
                    _f32(tensors, "model.norm.weight"), cfg["rms_norm_eps"])
        return x @ _f32(tensors, "lm_head.weight").T
