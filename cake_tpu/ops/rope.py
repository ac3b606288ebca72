"""Rotary position embeddings.

Equivalent of the reference's precomputed cos/sin tables + rope application
(`cache.rs:31-50` builds ``theta_i = rope_theta^(-2i/d)`` tables for
MAX_SEQ_LEN positions; `attention.rs:17-27` slices them by ``index_pos`` and
applies ``candle_nn::rotary_emb::rope``). Here the tables are a small constant
pytree computed once per model; slicing by position is a
``dynamic_slice`` so the decode step stays a single compiled program.

The rotation convention matches candle's ``rotary_emb::rope`` (non-interleaved
half-rotation, the HF Llama convention): split head_dim into two halves,
rotate ``(x1, x2) -> (x1*cos - x2*sin, x1*sin + x2*cos)``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from cake_tpu.obs import metrics as obs_metrics


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature ``m = 0.1 * mscale * ln(factor) + 1``
    (1 where nothing is stretched). Latent attention multiplies its softmax
    scale by ``m(mscale_all_dim)^2`` (``LlamaConfig.attn_scale``)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def _yarn_inv_freq(inv_freq: jnp.ndarray, scaling: dict,
                   theta: float) -> jnp.ndarray:
    """YaRN (Peng et al. 2023, as DeepSeek-V3's ``config.json`` keys name
    it): pair ``i`` keeps ``theta^(-2i/d)`` where it turns more than
    ``beta_fast`` times over the original window, is divided by ``factor``
    where it turns fewer than ``beta_slow`` times, and is blended linearly
    over the pair indices in between (the "correction dims")."""
    half = inv_freq.shape[0]
    dim = 2 * half
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def correction_dim(turns: float) -> float:
        return (dim * math.log(orig / (turns * 2.0 * math.pi))
                / (2.0 * math.log(theta)))

    low = max(math.floor(correction_dim(float(scaling.get("beta_fast", 32)))),
              0)
    high = min(math.ceil(correction_dim(float(scaling.get("beta_slow", 1)))),
               dim - 1)
    if low == high:
        high += 0.001  # the published guard against a zero-width ramp
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inv_freq / factor * ramp + inv_freq * (1.0 - ramp)


def _scale_inv_freq(inv_freq: jnp.ndarray, scaling: dict,
                    theta: float | None = None) -> jnp.ndarray:
    """Apply HF ``rope_scaling`` to the base frequencies.

    Supports ``linear`` (uniform 1/factor), ``yarn`` (:func:`_yarn_inv_freq`)
    and Llama-3.1's ``llama3`` rule:
    wavelengths shorter than ``original_max/high_freq_factor`` keep their
    frequency, longer than ``original_max/low_freq_factor`` are divided by
    ``factor``, and the band between interpolates smoothly. (The reference
    predates rope scaling — cache.rs:31-50 is the unscaled table only — but
    Llama-3.1 checkpoints require it.)
    """
    kind = scaling.get("rope_type", scaling.get("type"))
    if kind is None:
        raise ValueError(
            f"rope_scaling config has no 'rope_type'/'type' key: {scaling}"
        )
    factor = float(scaling["factor"])
    if kind == "linear":
        return inv_freq / factor
    if kind == "yarn":  # the one rule that needs the base itself
        return _yarn_inv_freq(inv_freq, scaling, theta)
    if kind == "llama3":
        lo = float(scaling["low_freq_factor"])
        hi = float(scaling["high_freq_factor"])
        orig = float(scaling["original_max_position_embeddings"])
        wavelen = 2.0 * jnp.pi / inv_freq
        smooth = (orig / wavelen - lo) / (hi - lo)
        interp = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
        scaled = jnp.where(wavelen > orig / lo, inv_freq / factor, interp)
        return jnp.where(wavelen < orig / hi, inv_freq, scaled)
    raise ValueError(f"unsupported rope_scaling type '{kind}'")


def rope_tables(head_dim: int, max_seq: int, theta: float, dtype=jnp.float32,
                scaling: dict | None = None):
    """Precompute ``cos/sin [max_seq, head_dim // 2]`` (cache.rs:31-50)."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    amp = 1.0
    if scaling is not None:
        inv_freq = _scale_inv_freq(inv_freq, scaling, theta)
        if scaling.get("rope_type", scaling.get("type")) == "yarn":
            # cos and sin carry the file's explicit ``attention_factor``
            # at every position (a grouped-query head under YaRN, as
            # Hugging Face's ``yarn`` applies it) or, where it gives none,
            # m(mscale) / m(mscale_all_dim): 1 wherever the two are equal,
            # as in every published latent-attention config (the
            # temperature then sits in the softmax scale)
            factor = float(scaling["factor"])
            amp = scaling.get("attention_factor")
            if amp is None:
                amp = (yarn_mscale(factor, float(scaling.get("mscale", 1.0)))
                       / yarn_mscale(factor, float(
                           scaling.get("mscale_all_dim", 0.0))))
            amp = float(amp)
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # [max_seq, head_dim/2]
    return ((jnp.cos(freqs) * amp).astype(dtype),
            (jnp.sin(freqs) * amp).astype(dtype))


def rope_tables_for(config, max_seq: int, dtype=jnp.float32):
    """The tables of a model configuration: its rotary width
    (``config.rope_dim``: the whole head, or latent attention's rope part),
    base and scaling. What every execution path calls. ``(None, None)`` for
    a model with no position embedding (``rope_dim`` 0): no table is
    built, and :func:`apply_rope` rotates nothing.

    Where the file gives a rotation a layer KIND (``config.layer_rope``:
    window and full attention mixed by layer) ``cos`` and ``sin`` are each
    a dict by the kind's mixer (``"swa"``, ``"gqa"``), a table or None (a
    kind that rotates nothing): ONE program carries as many pairs of
    tables as kinds rotate, and the layer loop hands each layer its
    kind's (``models.llama._typed_block``). The gauge ``rope.tables``
    says how many the program being traced carries."""
    count = obs_metrics.gauge("rope.tables")
    if config.layer_rope:
        cos, sin = {}, {}
        for kind, mixer in config.family.layer_mixers.items():
            rope = config.rotation(kind)
            cos[mixer], sin[mixer] = (None, None) if rope is None else (
                rope_tables(config.head_dim, max_seq,
                            float(rope["rope_theta"]), dtype=dtype,
                            scaling=None if rope["rope_type"] == "default"
                            else rope))
        count.set(sum(t is not None for t in cos.values()))
        return cos, sin
    count.set(int(bool(config.rope_dim)))
    if not config.rope_dim:
        return None, None
    return rope_tables(config.rope_dim, max_seq, config.rope_theta,
                       dtype=dtype, scaling=config.rope_scaling)


def apply_rope(
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    pos: jax.Array,
    interleaved: bool = False,
) -> jax.Array:
    """Rotate ``x [batch, heads, T, head_dim]`` for absolute positions
    ``pos .. pos+T`` (the reference's ``cosine/sine(index_pos, seq_len)``
    slice, cache.rs:71-78).

    ``pos`` may be a scalar (shared by all batch rows) or ``[batch]``
    (per-row positions — the multi-stream serving path).

    ``interleaved``: the pairs are ``(x[2i], x[2i+1])`` (the GPT-J / DeepSeek
    checkpoint convention) instead of ``(x[i], x[i + d/2])``. The result is
    left de-interleaved (pair ``i`` at ``i`` and ``i + d/2``), as the
    published implementation leaves it: queries and keys get the same
    permutation, so their dot products are those of the interleaved
    rotation.

    Tables narrower than the head (``cos [S, r / 2]`` with ``r < D``: a
    rotation over PART of a head, ``LlamaConfig.rope_fraction``) rotate
    its first ``r`` channels, pairs ``(c, c + r / 2)``, and leave the rest
    as they are."""
    if cos is None:  # no position embedding (rope_tables_for)
        return x
    if 2 * cos.shape[-1] < x.shape[-1]:
        r = 2 * cos.shape[-1]
        return jnp.concatenate(
            [apply_rope(x[..., :r], cos, sin, pos, interleaved), x[..., r:]],
            axis=-1)
    b, h, t, d = x.shape
    half = d // 2
    if interleaved:
        x = x.reshape(b, h, t, half, 2)
        x = jnp.concatenate([x[..., 0], x[..., 1]], axis=-1)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        cos_t = jax.lax.dynamic_slice_in_dim(cos, pos, t, axis=0)
        sin_t = jax.lax.dynamic_slice_in_dim(sin, pos, t, axis=0)
        cos_t = cos_t[None, None, :, :]  # [1,1,T,half]
        sin_t = sin_t[None, None, :, :]
    else:
        def rows(table):  # [B, 1, T, half] — per-row table slices
            return jax.vmap(
                lambda p: jax.lax.dynamic_slice_in_dim(table, p, t, axis=0)
            )(pos)[:, None, :, :]

        cos_t, sin_t = rows(cos), rows(sin)
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos_t - x2 * sin_t, x1 * sin_t + x2 * cos_t], axis=-1
    )
    return rotated.astype(x.dtype)
