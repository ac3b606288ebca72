"""The plain reference: what every architecture's float32 numpy forward
pass shares (``arch/<arch>.py`` holds a decoder's own equations).

Written from published descriptions (as Hugging Face transformers
implements them), not from the program: RMSNorm, rotary embeddings in the
rotate-half convention, SwiGLU, and the end of every decoder: final norm,
output head, log-softmax. No cache, no batching, no kernels, and none of
``cake_tpu``: a reference reads the checkpoint the server was given,
layer by layer, and dequantizes int8 tensors as stored (q * scale per
output channel). Everything is float32; the server computes in the
configuration's serving type, and the tolerance in the configuration
file is what that is allowed to cost.

An architecture's ``chosen_logprobs`` is teacher-forced: given a prompt
and the tokens the server chose, one pass over prompt + tokens gives the
log-probability the reference assigns to each chosen token, and the
reference's own best token there. Several (prompt, tokens) pairs go
through the layers together, each a sequence of its own, so that a
layer's weights are read and dequantized once (``Layer``): that, not the
arithmetic, is most of the time.
"""

from __future__ import annotations

import numpy as np

from weights import Checkpoint


class Layer:
    """One layer's tensors as float32, each read once for all the
    sequences."""

    def __init__(self, ck: Checkpoint):
        self.ck, self.held = ck, {}

    def f32(self, name: str) -> np.ndarray:
        if name not in self.held:
            self.held[name] = self.ck.f32(name)
        return self.held[name]


def rms_norm(x: np.ndarray, w: np.ndarray, eps: float) -> np.ndarray:
    var = np.mean(x * x, axis=-1, keepdims=True)
    return x / np.sqrt(var + np.float32(eps)) * w


def rope(x: np.ndarray, theta: float) -> np.ndarray:
    """x [heads, T, d], positions 0..T-1, rotate-half convention."""
    _, t, d = x.shape
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = np.outer(np.arange(t, dtype=np.float32), inv)  # [T, d/2]
    cos, sin = np.cos(ang), np.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def silu(x: np.ndarray) -> np.ndarray:
    return x / (1.0 + np.exp(-x))


def swiglu(x, w_gate, w_up, w_down):
    """Weights in torch's [out, in]."""
    return (silu(x @ w_gate.T) * (x @ w_up.T)) @ w_down.T


def score_pairs(ck: Checkpoint, eps: float, pairs: list[tuple], xs: list,
                margins: list) -> list[dict]:
    """The end of ``chosen_logprobs``, from each pair's last hidden states
    ``xs``: final norm, output head, log-softmax in float64, and per pair
    ``{"logprob", "best", "best_logprob", "routing_margin"}`` at the
    places the chosen tokens were predicted from. ``margins[n]`` holds
    pair ``n``'s routing margins, one array a sparse layer (empty for a
    dense model: ``routing_margin`` is then None everywhere)."""
    norm, head = ck.f32("model.norm.weight"), ck.f32("lm_head.weight")
    out = []
    for (prompt, chosen), x, margin in zip(pairs, xs, margins):
        last = rms_norm(x[len(prompt) - 1:], norm, eps)
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
