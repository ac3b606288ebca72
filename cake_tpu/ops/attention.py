"""GQA causal self-attention (reference math path).

Equivalent of `cake-core/src/model/attention.rs`: no-bias q/k/v/o projections
sized by head counts (attention.rs:92-109), RoPE from precomputed tables
(:17-27), KV append (:57), GQA key/value sharing (:59-60,84-89), **scores in
f32 regardless of model dtype** (:62-77), causal masking, softmax, weighted
sum, o_proj.

TPU-first redesign decisions:

- The cache is a fixed ``max_seq`` buffer; attention always reads the full
  buffer and masks out positions beyond the causal frontier. This keeps every
  decode step the same static shape (one compiled program) instead of the
  reference's growing-concat shapes.
- GQA is computed with a grouped einsum (``[B, kv_heads, group, T, D]``)
  instead of materializing ``repeat_kv`` copies (attention.rs:84-89) — XLA
  maps the group axis onto the MXU batch dimension for free, where a
  materialized repeat would burn HBM bandwidth.
- The memoized mask cache of the reference (cache.rs:81-103) is replaced by an
  iota comparison fused into the softmax by XLA.

On TPU, :func:`attend` dispatches to the fused Pallas flash kernels
(:mod:`cake_tpu.ops.pallas.flash`) — blockwise online softmax, causal mask in
registers, no HBM score materialization, KV blocks past the frontier never
fetched — at the shapes where the measured sweep says they win: prefill from
``PREFILL_FLASH_MIN_S`` context up (tools/flash_sweep.py). Below the
crossover, and for single-token decode, XLA's fused attention is faster and
``auto`` picks it. The XLA path also remains the parity oracle
(``CAKE_PALLAS=0`` forces it everywhere; ``CAKE_PALLAS=1`` forces the
kernels everywhere).
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp

from cake_tpu.ops import kvcache as kv
from cake_tpu.ops import pallas as pk
from cake_tpu.ops import quant
from cake_tpu.ops.rope import apply_rope

log = logging.getLogger("cake_tpu.attention")

NEG_INF = -1e30


def _flash_ok(t: int, s: int, d: int) -> bool:
    """Shapes the compiled (non-interpret) kernels handle efficiently:
    lane-aligned head_dim and a KV buffer divisible into aligned blocks."""
    return d % 128 == 0 and s % 128 == 0


# Measured context-length crossover for ``impl="auto"`` (tools/flash_sweep.py
# on v5 lite, 8B geometry H=32/KVH=8/D=128 — same treatment quant_matmul's
# m>=16 gate got):
#
# - prefill: flash wins from S >= 2048 (1.5x at T=512/S=2048, 2.2-2.3x at
#   S=4096, 50x at S=8192 where XLA materializes the f32 score matrix) and
#   loses below it (0.77x at T=512/S=1024, 0.87x at T=256/S=512).
# - decode (T=1): XLA wins at every measured shape — 0.99x at S=512 falling
#   to 0.82x at S=8192, and 0.72-0.90x at serving batches 8/32 — the
#   [B, H, 1, S] score row is tiny, so XLA's fused masked gemv is already
#   bandwidth-optimal at the frontier-near-full worst case. The one regime
#   with a structural case for flash decode (it reads KV blocks only up to
#   the frontier; XLA sweeps the whole buffer) is an EARLY frontier in a
#   long window — tools/flash_sweep.py's (s, pos) decode rows measure it;
#   until a measured win lands in KERNELS_TPU.json, auto stays XLA and
#   CAKE_PALLAS=1 remains the only way to force the kernel.
PREFILL_FLASH_MIN_S = 2048
# T floor for the flash prefill: the sweep's smallest measured chunk is
# T=256; far below it the q-block degenerates (_pick_block of a tiny/odd T
# -> 1-row blocks) and the grid re-fetches the whole KV buffer per q-block
# — a speculative-verify dispatch (T ~ 9) would read S bytes T times.
# Real prefill buckets are powers of two >= 256 whenever S is in the flash
# regime, so the floor costs nothing on the prompt path.
PREFILL_FLASH_MIN_T = 256


def _flash_prefill_choice(t: int, s: int, d: int) -> str:
    """Measured-crossover dispatch for a prefill-shaped (T>1, scalar-pos)
    attention — shared by the plain and int8-KV paths so there is exactly
    one policy. Returns ``"flash"`` or ``"xla"``; warns when the kernels
    were wanted but the shape is not lane-aligned."""
    enabled = pk.kernels_enabled()
    want = enabled and (
        pk.force_kernels()
        or (t >= PREFILL_FLASH_MIN_T and s >= PREFILL_FLASH_MIN_S)
    )
    if not want:
        return "xla"
    if pk.interpret_default() or _flash_ok(t, s, d):
        return "flash"
    # Runs at trace time (once per compiled shape): a misaligned config
    # must not silently lose the kernels.
    log.warning(
        "flash kernels enabled but shape (T=%d, S=%d, D=%d) is not "
        "lane-aligned (need D%%128==0 and S%%128==0); falling back to the "
        "XLA attention path", t, s, d,
    )
    return "xla"


def attend(
    q: jax.Array,  # [B, n_heads, T, D] (already roped)
    k_all: jax.Array,  # [B, kv_heads, S, D] (full cache buffer)
    v_all: jax.Array,  # [B, kv_heads, S, D]
    pos,  # scalar: absolute position of q[..., 0, :]
    impl: str = "auto",  # auto | xla | flash
    window: int | None = None,  # sliding-window width (Mistral); None=full
) -> jax.Array:
    """Masked GQA attention over a fixed-size KV buffer. Returns [B,H,T,D].

    ``pos`` may be scalar or ``[B]`` (per-row causal frontiers — the
    multi-stream serving path; per-row is supported by the XLA path and the
    flash decode kernel, T>1 per-row routes to XLA).

    ``window``: sliding-window attention — key positions more than
    ``window`` behind the query are masked out. Both flash kernels fold
    the window lower bound into their block sweeps (out-of-window KV
    blocks are neither fetched nor computed). Prefill rides the kernel at
    the measured crossover; decode under ``impl="auto"`` stays XLA until
    a measured win lands (flash_sweep ``decode_win*`` rows), with
    ``impl="flash"``/``CAKE_PALLAS=1`` forcing the windowed kernel.
    Per-row prefill (T>1 with ``[B]`` pos) stays XLA — not a
    kernel-served shape.
    """
    t, d = q.shape[2], q.shape[3]
    s = k_all.shape[2]
    per_row = jnp.asarray(pos).ndim == 1
    if window is not None:
        # Windowed PREFILL rides the flash kernel at the measured
        # crossover (the lower bound is folded into its block sweep — KV
        # blocks outside the window are never fetched). Windowed DECODE
        # supports the kernel too (same lower-bound skip: ~W KV bytes vs
        # XLA's full-buffer sweep) but auto stays XLA until a measured
        # win lands (flash_sweep decode_win4096 rows); CAKE_PALLAS=1 or
        # impl='flash' forces it. Per-row prefill stays XLA (not a
        # kernel-served shape, windowed or not).
        if per_row and t > 1:
            impl = "xla"
        elif t == 1:
            if impl == "auto":
                force = pk.kernels_enabled() and pk.force_kernels()
                ok = pk.interpret_default() or _flash_ok(t, s, d)
                impl = "flash" if force and ok else "xla"
        elif impl == "auto":
            impl = _flash_prefill_choice(t, s, d)
        if impl == "flash":
            if t == 1:
                return pk.flash_decode(q, k_all, v_all, pos, window=window)
            return pk.flash_attention(q, k_all, v_all, pos, window=window)
        return _attend_xla(q, k_all, v_all, pos, window=window)
    if per_row and t > 1 and impl != "xla":
        impl = "xla"  # per-row prefill: XLA only (not a served path)
    if impl == "auto":
        if t > 1:
            impl = _flash_prefill_choice(t, s, d)
        elif pk.kernels_enabled() and pk.force_kernels():
            # decode: XLA wins at every measured shape (crossover notes
            # above); CAKE_PALLAS=1 still forces the kernel
            if pk.interpret_default() or _flash_ok(t, s, d):
                impl = "flash"
            else:
                impl = "xla"
                log.warning(
                    "flash kernels forced (CAKE_PALLAS=1) but decode shape "
                    "(T=%d, S=%d, D=%d) is not lane-aligned (need D%%128==0 "
                    "and S%%128==0); falling back to the XLA attention path",
                    t, s, d,
                )
        else:
            impl = "xla"
    if impl == "flash":
        if t == 1:
            return pk.flash_decode(q, k_all, v_all, pos)
        return pk.flash_attention(q, k_all, v_all, pos)
    return _attend_xla(q, k_all, v_all, pos, window=window)


def _attend_xla(
    q: jax.Array,
    k_all: jax.Array,
    v_all: jax.Array,
    pos,
    window: int | None = None,
) -> jax.Array:
    """Reference-math XLA path (full [T, S] scores, mask by iota compare).
    ``pos`` scalar or ``[B]`` (per-row causal frontier)."""
    b, n_heads, t, d = q.shape
    kv_heads, s = k_all.shape[1], k_all.shape[2]
    group = n_heads // kv_heads

    qg = q.reshape(b, kv_heads, group, t, d)
    # f32 scores regardless of model dtype (attention.rs:62-77).
    scores = jnp.einsum(
        "bkgtd,bksd->bkgts", qg, k_all, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.float32(d))

    # Causal frontier: key position valid iff kpos <= pos + t_idx.
    pos = jnp.asarray(pos, jnp.int32)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (t, s), 1)
    qpos = jax.lax.broadcasted_iota(jnp.int32, (t, s), 0)
    if pos.ndim == 0:
        mask = (kpos <= qpos + pos)[None, None, None]  # [1,1,1,T,S]
        if window is not None:
            # sliding window: keys more than `window` behind the query are
            # out (key valid iff qpos+pos-window < kpos <= qpos+pos)
            mask &= (kpos > qpos + pos - window)[None, None, None]
    else:
        mask = (kpos[None] <= qpos[None] + pos[:, None, None])[
            :, None, None
        ]  # [B,1,1,T,S]
        if window is not None:
            mask &= (kpos[None] > qpos[None] + pos[:, None, None] - window)[
                :, None, None
            ]
    scores = jnp.where(mask, scores, NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgts,bksd->bkgtd", probs.astype(v_all.dtype), v_all,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, n_heads, t, d).astype(q.dtype)


def self_attention_block(
    x: jax.Array,  # [B, T, hidden]
    wq: jax.Array,  # [hidden, n_heads * D]
    wk: jax.Array,  # [hidden, kv_heads * D]
    wv: jax.Array,  # [hidden, kv_heads * D]
    wo: jax.Array,  # [n_heads * D, hidden]
    k_cache: jax.Array,  # [B, kv_heads, S, D]
    v_cache: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    pos,
    num_heads: int,
    num_kv_heads: int,
    tp_axis: str | None = None,
    sp_axis: str | None = None,
    sp_size: int = 1,
    write_gate: jax.Array | None = None,
    sp_prefill: bool | None = None,
    sp_chunk: bool = False,
    bq: jax.Array | None = None,  # q/k/v projection biases (Qwen2 family)
    bk: jax.Array | None = None,
    bv: jax.Array | None = None,
    bo: jax.Array | None = None,  # o_proj bias (HF llama-arch attention_bias)
    window: int | None = None,  # sliding-window width (Mistral family)
    layer: jax.Array | None = None,  # index into a stacked [L, ...] cache
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One attention sublayer incl. cache update.

    Returns ``(attn_out [B,T,hidden], new_k_cache, new_v_cache)``.
    Mirrors `attention.rs:30-90` + `cache.process_kv` (:57).

    ``layer``: ``k_cache``/``v_cache`` are the stacked ``[L, B, kv_heads, S,
    D]`` buffers the layer loop carries, and this is layer ``layer`` of
    them: only its ``T`` new rows are written (in place,
    :func:`cake_tpu.ops.kvcache.update_layer`) and attention reads its keys
    and values out of the same buffers; the buffers come back whole. None:
    they are one layer's own ``[B, kv_heads, S, D]``.

    ``tp_axis``: when run inside shard_map with heads sharded over a tensor-
    parallel mesh axis (Megatron-style: column-parallel qkv, row-parallel
    o_proj), pass the axis name — the o_proj partial sums are psum-reduced
    over it. ``num_heads``/``num_kv_heads`` are then the *local* counts.

    ``sp_axis``: sequence/context parallelism (:mod:`cake_tpu.ops.ring`).
    The cache's sequence axis is sharded over this mesh axis; shard *i* owns
    global positions ``[i*S_l, (i+1)*S_l)``. Two modes:

    - prefill: ``x`` holds this shard's chunk of the (bucketed) prompt —
      ring attention over the sp ring, chunked cache write.
    - decode (``T == 1``): ``x`` is replicated; the owner shard commits the
      new KV slot and exact softmax is reassembled from per-shard partials
      (distributed flash decoding).

    ``sp_prefill`` selects the mode explicitly (the pipeline builders pass
    it); ``None`` falls back to the ``T > 1`` heuristic, which is WRONG for
    one-token-per-shard prefill chunks — callers that can produce
    ``T_local == 1`` prefill must pass the flag.

    ``sp_chunk`` selects a third sp mode (overriding both): chunked OFFSET
    prefill against committed history — ``x`` is the full chunk replicated
    on every sp shard, positioned at ``pos`` (scalar: the admission /
    shared-prefix serving path; ``[B]``: per-row chunk frontiers, the
    sp serving speculation-verification path).

    ``write_gate`` (scalar bool): when running inside an SPMD-uniform pipeline
    loop every stage executes this code every step (collectives must be
    uniform across devices — a conditional ppermute/psum deadlocks); the gate
    makes the KV commit predicated so only the active stage's write lands.
    """
    b, t, hidden = x.shape
    d = quant.out_features(wq) // num_heads

    q = quant.dense(x, wq)
    k = quant.dense(x, wk)
    v = quant.dense(x, wv)
    if bq is not None:
        q = q + bq
    if bk is not None:
        k = k + bk
    if bv is not None:
        v = v + bv
    q = q.reshape(b, t, num_heads, d).transpose(0, 2, 1, 3)
    k = k.reshape(b, t, num_kv_heads, d).transpose(0, 2, 1, 3)
    v = v.reshape(b, t, num_kv_heads, d).transpose(0, 2, 1, 3)

    if sp_axis is not None and sp_size > 1:
        from cake_tpu.ops import ring

        # the sp writes select over a shard's whole window slice, so they
        # take this layer's buffers out of the stacked cache and put them
        # back (no served cell runs sp; the slot-sized write is the plain
        # branch's)
        k_stack, v_stack = k_cache, v_cache
        k_cache = kv.layer_view(k_stack, layer)
        v_cache = kv.layer_view(v_stack, layer)
        quantized = isinstance(k_cache, kv.QuantizedKV)
        s_l = kv._kv_data(k_cache).shape[2]
        sp_idx = jax.lax.axis_index(sp_axis)
        is_prefill = (not sp_chunk) and (
            sp_prefill if sp_prefill is not None else t > 1
        )
        # pos may be [B] (multi-stream sp serving: per-row frontiers) on
        # the decode path; the prefill path positions by chunk offset and
        # never reads it
        if is_prefill:
            # Sequence-parallel prefill: the prompt (bucketed to a multiple
            # of sp) is sharded over the ring; ring attention costs are
            # prompt-proportional, not window-proportional.
            if jnp.asarray(pos).ndim:
                # this branch positions by chunk offset and never reads
                # pos — a caller passing per-row positions here would get
                # silently wrong RoPE/causal offsets
                raise ValueError(
                    "per-row positions are not supported by sp prefill "
                    "(rows share the chunk-offset position layout)"
                )
            if t > s_l:
                raise ValueError(
                    f"sp prefill chunk (T_local {t}) exceeds the cache "
                    f"window per shard (S_local {s_l})"
                )
            my_off = sp_idx * t  # global position of this shard's token 0
            q = apply_rope(q, cos, sin, my_off)
            k = apply_rope(k, cos, sin, my_off)
            if quantized:
                # attention must see exactly what the cache will hold:
                # round-trip the chunk through the int8 quantization before
                # the ring (the same values the sp_*_write paths store), so
                # sp output matches the single-device int8-KV oracle
                k_att = kv.dequant_kv(kv.quant_kv(k), q.dtype)
                v_att = kv.dequant_kv(kv.quant_kv(v), q.dtype)
            else:
                k_att, v_att = k, v
            if t == s_l:
                # chunk layout == cache layout: write in place, no gather
                k_cache, v_cache = kv.update_layer(k_cache, v_cache, k, v, 0,
                                                   gate=write_gate)
            else:
                k_cache, v_cache = ring.sp_chunked_cache_write(
                    k_cache, v_cache, k, v, sp_axis, sp_size, gate=write_gate
                )
            out = ring.ring_attention(q, k_att, v_att, sp_axis, sp_size,
                                      q_off=my_off, window=window)
        elif sp_chunk:
            # Chunked offset prefill over the sp-sharded window (the
            # continuous-batching admission / shared-prefix remainder
            # path): the chunk's T tokens run REPLICATED on every sp
            # shard from global position ``pos`` against the committed
            # history already in the range-sharded cache — owner-masked
            # range write, then the exact softmax reassembled from
            # per-shard partials (the T>1 generalization of distributed
            # flash decode).
            q = apply_rope(q, cos, sin, pos)
            k = apply_rope(k, cos, sin, pos)
            shard_start = sp_idx * s_l
            k_cache, v_cache = ring.sp_range_cache_write(
                k_cache, v_cache, k, v, pos, shard_start, gate=write_gate
            )
            out = ring.sp_decode_attend(
                q, kv.dequant_kv(k_cache, q.dtype),
                kv.dequant_kv(v_cache, q.dtype), pos, sp_axis, shard_start,
                window=window,
            )
        else:
            q = apply_rope(q, cos, sin, pos)
            k = apply_rope(k, cos, sin, pos)
            shard_start = sp_idx * s_l
            k_cache, v_cache = ring.sp_cache_write(
                k_cache, v_cache, k, v, pos, shard_start, gate=write_gate
            )
            out = ring.sp_decode_attend(
                q, kv.dequant_kv(k_cache, q.dtype),
                kv.dequant_kv(v_cache, q.dtype), pos, sp_axis, shard_start,
                window=window,
            )
        k_cache = kv.layer_store(k_stack, k_cache, layer)
        v_cache = kv.layer_store(v_stack, v_cache, layer)
    else:
        q = apply_rope(q, cos, sin, pos)
        k = apply_rope(k, cos, sin, pos)
        k_cache, v_cache = kv.update_layer(k_cache, v_cache, k, v, pos,
                                           gate=write_gate, layer=layer)
        k_l = kv.layer_view(k_cache, layer)
        v_l = kv.layer_view(v_cache, layer)
        if isinstance(k_l, kv.QuantizedKV):
            # int8 KV. Long-context prefill (the measured flash regime,
            # S >= PREFILL_FLASH_MIN_S) routes to the quantization-aware
            # flash kernel, which folds the per-token scales into the
            # score columns / probabilities and reads only int8 bytes.
            # Everything else — decode, short prefill — dequantizes at
            # trace level on the XLA path, where the convert+mul fuses
            # into the attention dot's operand read. (A plain-flash-kernel
            # operand would be a materialized bf16 KV buffer in HBM,
            # losing the bandwidth win, so plain flash is never used with
            # the quantized cache.)
            s_len = k_l.q.shape[2]
            use_q8_flash = (
                t > 1
                and jnp.asarray(pos).ndim == 0
                and _flash_prefill_choice(t, s_len, d) == "flash"
            )
            if use_q8_flash:
                out = pk.flash_attention_q8(
                    q, k_l.q, k_l.scale, v_l.q, v_l.scale,
                    pos, window=window,
                )
            else:
                out = attend(q, kv.dequant_kv(k_l, q.dtype),
                             kv.dequant_kv(v_l, q.dtype), pos,
                             impl="xla", window=window)
        else:
            out = attend(q, k_l, v_l, pos, window=window)  # [B,H,T,D]

    out = out.transpose(0, 2, 1, 3).reshape(b, t, num_heads * d)
    out = quant.dense(out, wo)
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)
    if bo is not None:
        # after the tp reduction: the bias belongs to the full (summed)
        # projection, not to each rank's partial
        out = out + bo
    return out, k_cache, v_cache
