"""A gated short convolution in place of attention (LFM2's `conv` layers).

The reference serves one mixer, attention over cached keys and values
(`cake-core/src/model/attention.rs`); this is the layer that keeps neither
rows nor a state. Per token ``u`` (the normed input), with ``L`` taps:

    [B | C | x] = u W_in                       three chunks of hidden, that order
    z           = B * x                        the gate before the convolution
    y_t[c]      = sum_j w[c, j] z_{t-L+1+j}[c] causal, depthwise, NO activation
    out         = (C * y) W_out                the gate behind it

**Cached:** the last ``L - 1`` values of ``z`` a stream, whatever its
length, in ``KVCache.conv [L_conv, B, L - 1, hidden]`` (``LlamaConfig.
cache_plan``: ``conv`` and no ``state``), read and written in place on the
carried cache as rows are. An admission starts from a zeroed staging row
(zeros before a stream's first token ARE the convolution's padding) and the
splice copies the tail over the slot's; a chunk's tail is taken at each
row's true length (``valid``), so a bucket's padding never enters it and
admitting in bands equals admitting whole.

``z`` is formed and kept in the serving type, so the tail a step reads is
bit for bit what the chunk before it convolved; the sum over the taps is
float32 (:func:`cake_tpu.ops.kda.causal_conv`, shared with the delta-rule
and state-space layers). At decode this is two products and three
element-wise operations over ``[B, hidden]``: no kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cake_tpu.ops import kvcache as kv
from cake_tpu.ops import quant
from cake_tpu.ops.kda import causal_conv


def conv_mixer_block(
    x: jax.Array,  # [B, T, hidden], normed
    layer: dict,
    conv: jax.Array,  # [(L,) B, taps - 1, hidden]
    valid: jax.Array | None = None,  # [B] true tokens of each row
    layer_idx: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One gated short-convolution sublayer incl. the tail's update.
    Returns ``(out [B, T, hidden], conv)``; the buffer comes back whole.
    No write gate: a model that holds a tail runs as one pipeline stage
    (``mesh.validate_shardable``), whose writes always land."""
    with jax.named_scope("mixer.conv"):
        gate_in, gate_out, u = jnp.split(
            quant.dense(x, layer["w_in"]), 3, axis=-1)
        y, tail = causal_conv(gate_in * u, kv.layer_view(conv, layer_idx),
                              layer["conv_w"], valid)
        y = (gate_out.astype(jnp.float32) * y).astype(x.dtype)
        conv = kv.layer_store(conv, tail.astype(conv.dtype), layer_idx)
        return quant.dense(y, layer["w_out"]), conv
