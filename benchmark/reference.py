"""The plain reference: a decoder forward pass in float32 numpy.

Written from the published descriptions (Mistral-7B-v0.1 and
Mixtral-8x7B-v0.1 as Hugging Face transformers implements them), not
from the program: RMSNorm, rotary embeddings in the rotate-half
convention, grouped-query causal attention with the sliding window,
SwiGLU, and for sparse layers the router's top-k over all experts with a
softmax over the selected logits. No cache, no batching, no kernels, and
none of ``cake_tpu``: it reads the checkpoint the server was given, layer
by layer, and dequantizes int8 tensors as stored (q * scale per output
channel). Everything is float32; the server computes in the
configuration's serving type, and the tolerance in the configuration file
is what that is allowed to cost.

``chosen_logprobs`` is teacher-forced: given a prompt and the tokens the
server chose, one pass over prompt + tokens gives the log-probability the
reference assigns to each chosen token, and the reference's own best
token there. Several (prompt, tokens) pairs go through the layers
together, each a sequence of its own, so that a layer's weights are read
and dequantized once: that, not the arithmetic, is most of the time.
"""

from __future__ import annotations

import numpy as np

from weights import Checkpoint

VERSION = 3  # part of the key under which answers are kept


class _Layer:
    """One layer's tensors as float32, each read once for all the
    sequences."""

    def __init__(self, ck: Checkpoint):
        self.ck, self.held = ck, {}

    def f32(self, name: str) -> np.ndarray:
        if name not in self.held:
            self.held[name] = self.ck.f32(name)
        return self.held[name]


def _rms_norm(x: np.ndarray, w: np.ndarray, eps: float) -> np.ndarray:
    var = np.mean(x * x, axis=-1, keepdims=True)
    return x / np.sqrt(var + np.float32(eps)) * w


def _rope(x: np.ndarray, theta: float) -> np.ndarray:
    """x [heads, T, d], positions 0..T-1, rotate-half convention."""
    _, t, d = x.shape
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = np.outer(np.arange(t, dtype=np.float32), inv)  # [T, d/2]
    cos, sin = np.cos(ang), np.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _silu(x: np.ndarray) -> np.ndarray:
    return x / (1.0 + np.exp(-x))


def _swiglu(x, w_gate, w_up, w_down):
    """Weights in torch's [out, in]."""
    return (_silu(x @ w_gate.T) * (x @ w_up.T)) @ w_down.T


def _attention(cfg: dict, ck: _Layer, p: str, x: np.ndarray):
    t = x.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // nh
    q = (x @ ck.f32(p + "self_attn.q_proj.weight").T).reshape(t, nh, d)
    k = (x @ ck.f32(p + "self_attn.k_proj.weight").T).reshape(t, nkv, d)
    v = (x @ ck.f32(p + "self_attn.v_proj.weight").T).reshape(t, nkv, d)
    q = _rope(q.transpose(1, 0, 2), cfg["rope_theta"])
    k = _rope(k.transpose(1, 0, 2), cfg["rope_theta"])
    v = v.transpose(1, 0, 2)
    k = np.repeat(k, nh // nkv, axis=0)  # kv head g serves q heads g*r..
    v = np.repeat(v, nh // nkv, axis=0)
    scores = q @ k.transpose(0, 2, 1) / np.float32(np.sqrt(d))
    qi, ki = np.arange(t)[:, None], np.arange(t)[None, :]
    ok = ki <= qi
    window = cfg.get("sliding_window")
    if window:
        ok &= ki > qi - window
    scores = np.where(ok[None], scores, np.float32(-np.inf))
    scores = scores - scores.max(-1, keepdims=True)
    w = np.exp(scores)
    w /= w.sum(-1, keepdims=True)
    out = (w @ v).transpose(1, 0, 2).reshape(t, nh * d)
    return out @ ck.f32(p + "self_attn.o_proj.weight").T


def _feed_forward(cfg: dict, ck: _Layer, p: str, x: np.ndarray,
                  margins: list):
    """The layer's feed-forward block. For a sparse layer, ``margins``
    gains each token's routing margin: how far the last expert chosen
    lies above the first one left out, in units of that token's router
    logits' spread. Near 0 the choice hangs on rounding."""
    experts = cfg.get("num_local_experts") or 0
    if not experts:
        return _swiglu(x, ck.f32(p + "mlp.gate_proj.weight"),
                       ck.f32(p + "mlp.up_proj.weight"),
                       ck.f32(p + "mlp.down_proj.weight"))
    top_k = cfg["num_experts_per_tok"]
    logits = x @ ck.f32(p + "block_sparse_moe.gate.weight").T  # [T, E]
    ranked = np.argsort(-logits, axis=-1, kind="stable")
    order = ranked[:, :top_k]
    by_rank = np.take_along_axis(logits, ranked, -1)
    margins.append((by_rank[:, top_k - 1] - by_rank[:, top_k])
                   / (logits.std(-1) + 1e-9))
    sel = np.take_along_axis(logits, order, -1)
    sel = np.exp(sel - sel.max(-1, keepdims=True))
    weight = sel / sel.sum(-1, keepdims=True)  # softmax over the selected
    out = np.zeros_like(x)
    for e in range(experts):
        rows, slot = np.nonzero(order == e)
        if not len(rows):
            continue
        q = f"{p}block_sparse_moe.experts.{e}."
        y = _swiglu(x[rows], ck.f32(q + "w1.weight"), ck.f32(q + "w3.weight"),
                    ck.f32(q + "w2.weight"))
        out[rows] += weight[rows, slot][:, None] * y
    return out


def chosen_logprobs(cfg: dict, model_dir, pairs: list[tuple]) -> list[dict]:
    """For each (prompt, chosen) pair: the log-probabilities the
    reference gives the ``chosen`` continuation of ``prompt``, token by
    token, and its own best token at each place:
    ``{"logprob": [...], "best": [...], "best_logprob": [...],
    "routing_margin": [...]}``. The last is, for a sparse model, the
    smallest routing margin over the layers at the position each chosen
    token was predicted from (None for a dense model)."""
    ck = Checkpoint(model_dir)
    eps = cfg["rms_norm_eps"]
    embed = ck.f32("model.embed_tokens.weight")
    xs = [embed[np.asarray(list(prompt) + list(chosen[:-1]), np.int64)]
          for prompt, chosen in pairs]
    del embed
    margins: list[list] = [[] for _ in pairs]
    for i in range(cfg["num_hidden_layers"]):
        p, layer = f"model.layers.{i}.", _Layer(ck)
        for n, x in enumerate(xs):
            x = x + _attention(cfg, layer, p, _rms_norm(
                x, layer.f32(p + "input_layernorm.weight"), eps))
            xs[n] = x + _feed_forward(cfg, layer, p, _rms_norm(
                x, layer.f32(p + "post_attention_layernorm.weight"), eps),
                margins[n])
    norm, head = ck.f32("model.norm.weight"), ck.f32("lm_head.weight")
    out = []
    for (prompt, chosen), x, margin in zip(pairs, xs, margins):
        last = _rms_norm(x[len(prompt) - 1:], norm, eps)
        logits = (last @ head.T).astype(np.float64)
        logits -= logits.max(-1, keepdims=True)
        logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
        best = logp.argmax(-1)
        at = np.arange(len(chosen))
        routing = (np.min(margin, axis=0)[len(prompt) - 1:] if margin
                   else [None] * len(chosen))
        out.append({"logprob": [float(v) for v in logp[at, chosen]],
                    "best": [int(b) for b in best],
                    "best_logprob": [float(v) for v in logp[at, best]],
                    "routing_margin": [None if m is None else float(m)
                                       for m in routing]})
    return out
