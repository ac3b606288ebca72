"""Static-shape KV cache for autoregressive decode.

TPU-native redesign of the reference cache (`cake-core/src/model/cache.rs`).
The reference appends K/V per token with `Tensor::cat` along the sequence axis
(cache.rs:106-135) — a realloc-per-step pattern that would force an XLA retrace
on every decode step. Here the cache is a preallocated
``[num_layers, batch, num_kv_heads, max_seq, head_dim]`` pytree, donated
across steps, so every decode step compiles once and reuses the same HBM
buffers.

What is carried and what is written: the whole stacked pytree is the CARRY
of the layer loop (:func:`cake_tpu.models.llama.forward_layers`), of the
staged pipeline loop and of the fused multi-step decode loop around it, so
the buffer a program is given is the buffer it returns. A layer writes
only its ``T`` new rows per stream (:func:`update_layer` with ``layer``: a
``lax.dynamic_update_slice`` of ``[1, 1, KH, T, D]`` on the carried
buffer, in place) and attention reads that layer's keys and values out of
the same buffer (:func:`layer_view`, a slice the consumer fuses). No
per-layer slab and no second cache is allocated, copied or written back.

The reference's other two cache jobs are relocated where XLA wants them:
RoPE tables (cache.rs:31-50) live in :mod:`cake_tpu.ops.rope`; causal masks
(cache.rs:81-103) are folded into attention via iota comparison (no
memoization needed — the mask is fused by XLA, or folded into the Pallas
flash kernel).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from cake_tpu.models.config import LlamaConfig


@partial(jax.tree_util.register_dataclass, data_fields=["q", "scale"],
         meta_fields=[])
@dataclasses.dataclass
class QuantizedKV:
    """Int8 KV buffer half: ``q [..., KH, S, D] int8`` + per-token-per-head
    f32 ``scale [..., KH, S]`` (symmetric absmax over the head_dim channel,
    written alongside each token's KV slot). Halves cache HBM — the lever
    that lets multi-stream serving and long windows coexist on 16 GiB chips
    (the reference's f16 cache has no quantized tier, cache.rs:106-135)."""

    q: jax.Array
    scale: jax.Array


def _kv_data(x) -> jax.Array:
    return x.q if isinstance(x, QuantizedKV) else x


def dequant_kv(x, dtype) -> jax.Array:
    """Materialize (trace-level — XLA fuses the convert+mul into the
    attention dot's operand read) a full-precision view of a KV buffer."""
    if isinstance(x, QuantizedKV):
        return (x.q.astype(jnp.float32) * x.scale[..., None]).astype(dtype)
    return x


def quant_kv(x: jax.Array) -> QuantizedKV:
    """Per-token-per-head symmetric int8 over the head_dim channel."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return QuantizedKV(q=q, scale=scale)


@partial(jax.tree_util.register_dataclass,
         data_fields=["k", "v", "state", "conv", "ring_k", "ring_v",
                      "index", "sum_k", "sum_v"],
         meta_fields=[])
@dataclasses.dataclass
class KVCache:
    """Preallocated per-layer key/value buffers.

    Shapes: ``k, v: [num_layers, batch, num_kv_heads, max_seq, head_dim]``;
    for latent attention ``k: [L, B, 1, S, kv_lora_rank]`` (the normed
    latent) and ``v: [L, B, 1, S, qk_rope_head_dim]`` (the roped key part
    every head shares), and nothing per head (``LlamaConfig.cache_row``).
    The leading layer axis is indexed by the layer loop, which carries the
    whole cache beside the scanned layer weights, and is shardable along a
    pipeline-stage mesh axis.

    ``k``/``v`` may each be a plain array or a :class:`QuantizedKV` (int8
    storage + per-slot scales); every consumer goes through
    :func:`dequant_kv` / :func:`update_layer`, which handle both.

    A model whose layers are of two kinds holds two kinds of state
    (``LlamaConfig.cache_plan``): ``k``/``v`` have a layer for each layer
    that keeps rows and nothing for the others, and ``state`` (float32: a
    delta-rule layer's ``[L_rec, B, H, d_k, d_v]``, ops/kda.py, or a
    state-space layer's ``[L_rec, B, d_state, d_inner]``, ops/mamba.py)
    and ``conv [L_rec, B, taps - 1, channels]`` hold what such a layer
    keeps a stream, whatever its length. Both are None where no layer is
    recurrent; a gated short convolution (ops/shortconv.py) keeps ``conv
    [L_conv, B, taps - 1, hidden]`` and NO ``state``, so what a cache
    holds is asked of ``LlamaConfig.cache_plan``, not of ``state``. Where
    some layers attend through a window
    (a ``ring`` in ``cache_plan``), ``k``/``v`` have a layer for each FULL
    layer, and ``ring_k``/``ring_v [L_window, B, KH, R, D]`` hold the
    window layers' rows: ``R = config.ring_rows`` rows a stream whatever
    ``max_seq``, position ``p`` at row ``p % R`` (:func:`ring_write`). A
    ring is never zeroed: what an earlier stream or chunk left in it is
    told from the live rows by position (:func:`ring_positions`). Under a
    learned sparse attention (an ``index`` in ``cache_plan``, ops/dsa.py)
    ``index [L, B, 1, S, index_head_dim]`` holds the indexer's one key a
    token a layer, normed and rotated, in the serving type, row for row
    beside ``k``/``v`` and written as they are (:func:`update_layer`);
    such a model keeps ``[c | k_pe]`` in ONE row of ``k``, padded to whole
    lane tiles, and an empty ``v`` (``LlamaConfig.cache_row``: a step
    gathers its chosen rows, and a gather costs a row whatever its width);
    a row past a stream's frontier is an earlier stream's or a bucket's
    padding and is never scored. Under EVA attention (a ``summary``
    in ``cache_plan``, ops/eva.py) NO layer keeps a row a position:
    ``k``/``v`` have no layer (``[0, B, KH, S, D]``: the capacity ``S`` is
    still theirs to say, ``max_seq``), ``ring_k``/``ring_v [L, B, KH, W,
    D]`` hold the rows of the stream's CURRENT window (position ``p`` at
    row ``p % W``; the window resets, so the live rows are ``0 .. p % W``)
    and ``sum_k``/``sum_v [L, B, KH, S // C, D]`` one summary row for every
    ``C`` positions, chunk ``c`` at row ``c``, written when the chunk is
    (:func:`cake_tpu.ops.eva.eva_attention_block`) and read once its
    window is complete. Every
    buffer is ``[layers of its kind, batch, ...]``, so a slot's whole
    state is index ``b`` of axis 1 of every leaf.
    """

    k: jax.Array | QuantizedKV
    v: jax.Array | QuantizedKV
    state: jax.Array | None = None
    conv: jax.Array | None = None
    ring_k: jax.Array | None = None
    ring_v: jax.Array | None = None
    index: jax.Array | None = None
    sum_k: jax.Array | None = None
    sum_v: jax.Array | None = None

    @property
    def num_layers(self) -> int:
        return _kv_data(self.k).shape[0]

    @property
    def batch(self) -> int:
        return _kv_data(self.k).shape[1]

    @property
    def max_seq(self) -> int:
        return _kv_data(self.k).shape[3]

    def as_new(self) -> "KVCache":
        """Fresh zeroed cache with identical shapes.

        Mirrors the reference's per-connection isolation clone
        (`cache.rs:138-146`): same geometry, reset contents.
        """
        return jax.tree.map(jnp.zeros_like, self)


def init_cache(
    config: LlamaConfig,
    batch: int = 1,
    max_seq: int | None = None,
    dtype=None,
    num_layers: int | None = None,
    quant: str | None = None,
) -> KVCache:
    """Allocate a zeroed cache. ``num_layers`` overrides the config count so a
    pipeline stage / worker can hold buffers for only its own layers
    (the reference worker keeps a cache indexed by *global* block_idx,
    cache.rs:17,58 — here each stage's cache is dense over its local layers).

    ``quant="int8"`` allocates int8 storage + per-slot f32 scales
    (:class:`QuantizedKV`): ~half the cache HBM, quantize-on-write."""
    if quant not in (None, "int8"):
        raise ValueError(f"unsupported kv quant={quant!r}")
    if quant and quant not in config.family.cache_tiers:
        raise ValueError(config.family.cache_why)
    plan = config.cache_plan
    # the depth of the row buffers is the plan's: the layers that keep
    # rows, and for a looped model a plane a layer AND a pass
    L = plan.get("rows", (0,))[0] if num_layers is None else num_layers
    S = max_seq or config.max_seq_len
    dt = dtype or config.jax_dtype
    # the row comes from the configuration alone (LlamaConfig.cache_row):
    # per-head keys and values, or latent attention's one shared row
    # (normed latent in ``k``, roped key part in ``v``)
    heads, k_width, v_width = config.cache_row
    rec = {}
    if num_layers is not None and (set(plan) - {"rows"}
                                   or config.family.loops):
        raise ValueError("a model that holds a recurrent state, a "
                         "convolution's tail, a ring of rows, an index key, "
                         "a summary row or a plane a pass is cached whole "
                         "(no layer ranges)")
    if "conv" in plan:  # layers that carry a tail, and a state or none
        if "state" in plan:
            n, *shape = plan["state"]
            rec["state"] = jnp.zeros((n, batch, *shape), jnp.float32)
        n, *shape = plan["conv"]
        rec["conv"] = jnp.zeros((n, batch, *shape), dt)
    if "ring" in plan:
        n, kvh, r, kw, vw = plan["ring"]
        rec["ring_k"] = jnp.zeros((n, batch, kvh, r, kw), dt)
        rec["ring_v"] = jnp.zeros((n, batch, kvh, r, vw), dt)
    if "index" in plan:  # a sparse attention's key a token, beside the rows
        n, heads_i, width = plan["index"]
        rec["index"] = jnp.zeros((n, batch, heads_i, S, width), dt)
    if "summary" in plan:  # one row for every ``chunk`` positions
        n, kvh, chunk, kw, vw = plan["summary"]
        if S % chunk or S % plan["ring"][2]:
            raise ValueError(
                f"a capacity of {S} positions is not a whole number of "
                f"windows of {plan['ring'][2]} (chunks of {chunk}): a "
                "summary row stands for a whole chunk and a window "
                "becomes visible whole")
        rec["sum_k"] = jnp.zeros((n, batch, kvh, S // chunk, kw), dt)
        rec["sum_v"] = jnp.zeros((n, batch, kvh, S // chunk, vw), dt)
    if quant == "int8":
        def half(width):
            shape = (L, batch, heads, S, width)
            return QuantizedKV(q=jnp.zeros(shape, jnp.int8),
                               scale=jnp.zeros(shape[:-1], jnp.float32))

        return KVCache(k=half(k_width), v=half(v_width))
    return KVCache(k=jnp.zeros((L, batch, heads, S, k_width), dt),
                   v=jnp.zeros((L, batch, heads, S, v_width), dt), **rec)


def layer_view(cache, layer):
    """Layer ``layer``'s ``[B, KH, S(, D)]`` keys or values read out of a
    stacked ``[L, B, KH, S(, D)]`` buffer (plain or :class:`QuantizedKV`);
    ``layer`` None: ``cache`` already is one layer's buffer."""
    if layer is None:
        return cache
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        cache)


def layer_store(cache, value, layer):
    """Inverse of :func:`layer_view`: put one layer's whole buffer back."""
    if layer is None:
        return value
    return jax.tree.map(
        lambda a, v: jax.lax.dynamic_update_index_in_dim(a, v, layer, 0),
        cache, value)


def update_layer(
    k_cache: jax.Array,
    v_cache: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    pos: jax.Array,
    gate: jax.Array | None = None,
    layer: jax.Array | None = None,
    index: tuple[jax.Array, jax.Array] | None = None,
) -> tuple[jax.Array, ...]:
    """Write ``k_new/v_new [batch, kv_heads, T, head_dim]`` into one layer's
    ``T`` slots at sequence offset ``pos``, and nothing else. ``index``
    ``(cache, new)``: a sparse attention's index keys, written the same
    way into their own buffer, which then comes back third.

    ``layer`` None: the buffers are that layer's own ``[batch, kv_heads,
    max_seq, head_dim]``. ``layer`` an index: they are the stacked
    ``[L, batch, kv_heads, max_seq, head_dim]`` cache that the layer loop
    carries (:func:`cake_tpu.models.llama.forward_layers`), and the rows go
    to ``[layer, b, :, pos_b : pos_b + T, :]`` of it: a
    ``dynamic_update_slice`` of ``T`` rows on the carried buffer, which XLA
    performs in place, so no layer's slab is copied out or back.

    Replaces the reference's `process_kv` concat (cache.rs:106-135) — including
    *not* reproducing its axis-confused trimming bug (length checks on the
    heads axis, narrow on head_dim; see SURVEY.md §2).

    ``gate`` (scalar bool): predicated write for SPMD-uniform pipelines — when
    false the current slot contents are rewritten unchanged, so every device
    executes the identical program (collectives stay uniform) and only the
    active pipeline stage commits. Gated off, the touched region is just the
    ``T`` slots, not the whole buffer.

    ``pos`` may be a scalar (all batch rows write at the same offset — the
    single-stream paths) or ``[batch]`` (each row at its own offset — the
    multi-stream serving path, where right-padded prompts of different
    lengths decode concurrently).
    """
    pos = jnp.asarray(pos, jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    lead = () if layer is None else (jnp.asarray(layer, jnp.int32),)

    def put(cache, new, idx):
        """``new`` at ``idx`` (offsets of the axes after the layer axis)."""
        idx = lead + idx
        new = new.reshape((1,) * len(lead) + new.shape)
        if gate is not None:
            new = jnp.where(gate, new,
                            jax.lax.dynamic_slice(cache, idx, new.shape))
        return jax.lax.dynamic_update_slice(cache, new, idx)

    def write_buf(cache, new):
        """Plain arrays and int8 ``q`` carry a trailing head_dim axis;
        scales are the same layout minus that axis."""
        tail = (zero,) * (new.ndim - 3)
        if pos.ndim == 0:
            return put(cache, new, (zero, zero, pos) + tail)

        # One update per stream, unrolled (the batch is static and small).
        # Not a loop of its own: a while body that holds nothing but this
        # row write is laid out first by the TPU compiler, which then
        # gives the whole carried cache the layout a [KH, 1, D] row likes
        # (KH beside D) and re-lays the cache on the way into and out of
        # every program, and each layer's keys before its scores.
        for b in range(new.shape[0]):
            cache = put(cache, new[b:b + 1],
                        (jnp.asarray(b, jnp.int32), zero, pos[b]) + tail)
        return cache

    def write(cache, new):
        if isinstance(cache, QuantizedKV):
            qn = quant_kv(new)  # quantize-on-write
            return QuantizedKV(q=write_buf(cache.q, qn.q),
                               scale=write_buf(cache.scale, qn.scale))
        return write_buf(cache, new.astype(cache.dtype))

    out = write(k_cache, k_new), write(v_cache, v_new)
    return out if index is None else out + (write(*index),)


def ring_positions(last: jax.Array, rows: int) -> jax.Array:
    """The position each row of a ring of ``rows`` rows holds once
    position ``last`` (scalar or ``[B]``) has been written: the largest
    ``p <= last`` with ``p % rows == row`` (``[rows]`` or ``[B, rows]``).
    Negative: the stream has not written that row yet, and what it holds
    is an earlier stream's, which the reader masks."""
    last = jnp.asarray(last, jnp.int32)[..., None]
    row = jnp.arange(rows, dtype=jnp.int32)
    return last - jnp.mod(last - row, rows)


def ring_write(
    ring_k: jax.Array,  # [L, B, KH, R, D], the carried buffers
    ring_v: jax.Array,
    k_new: jax.Array,  # [B, KH, T, D]
    v_new: jax.Array,
    pos: jax.Array,  # scalar, or [B] where T == 1
    layer: jax.Array,
    valid: jax.Array | None = None,  # [B]: the chunk's true tokens
) -> tuple[jax.Array, jax.Array]:
    """Write the rows of positions ``pos .. pos + T - 1`` into layer
    ``layer`` of the carried rings, each at its position modulo ``R``, in
    place. One token (``T == 1``): a ``dynamic_update_slice`` of one row a
    stream, as :func:`update_layer` writes it. A chunk: of its first
    ``valid[b]`` tokens (a bucket's padding never enters a ring) the
    newest ``R`` land, every ring row taking the one position it is due
    (``ring_positions`` of the chunk's last true token) or keeping what it
    held where that position lies before the chunk."""
    pos = jnp.asarray(pos, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    b, _, t, _ = k_new.shape
    rows = ring_k.shape[3]
    zero = jnp.zeros((), jnp.int32)
    if t == 1:
        at = jnp.broadcast_to(jnp.mod(pos, rows), (b,))

        def put(ring, new):
            new = new.astype(ring.dtype)
            for i in range(b):  # unrolled: see update_layer
                ring = jax.lax.dynamic_update_slice(
                    ring, new[None, i:i + 1],
                    (layer, jnp.asarray(i, jnp.int32), zero, at[i], zero))
            return ring

        return put(ring_k, k_new), put(ring_v, v_new)
    if pos.ndim:
        raise ValueError("a chunk enters a ring from one position for "
                         "all its rows (per-row chunk frontiers are not "
                         "wired for window layers)")
    count = (jnp.full((b,), t, jnp.int32) if valid is None
             else jnp.asarray(valid, jnp.int32))
    due = ring_positions(pos + count - 1, rows)  # [B, R]
    src = jnp.clip(due - pos, 0, t - 1)[:, None, :, None]
    fresh = (due >= pos)[:, None, :, None]

    def put(ring, new):
        old = jax.lax.dynamic_index_in_dim(ring, layer, 0, keepdims=False)
        rows_new = jnp.take_along_axis(new.astype(ring.dtype), src, axis=2)
        return jax.lax.dynamic_update_index_in_dim(
            ring, jnp.where(fresh, rows_new, old), layer, 0)

    return put(ring_k, k_new), put(ring_v, v_new)
