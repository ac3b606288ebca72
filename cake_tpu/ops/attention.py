"""GQA causal self-attention (reference math path).

Equivalent of `cake-core/src/model/attention.rs`: no-bias q/k/v/o projections
sized by head counts (attention.rs:92-109), RoPE from precomputed tables
(:17-27), KV append (:57), GQA key/value sharing (:59-60,84-89), **scores in
f32 regardless of model dtype** (:62-77), causal masking, softmax, weighted
sum, o_proj.

TPU-first redesign decisions:

- The cache is a fixed ``max_seq`` buffer, so every decode step has the
  same static shape (one compiled program) instead of the reference's
  growing-concat shapes. How much of the buffer a step READS is the
  attention implementation's: the XLA path sweeps all of it and masks
  positions beyond the causal frontier; the decode kernel fetches each
  stream's blocks up to its frontier and nothing else.
- GQA is computed with a grouped einsum (``[B, kv_heads, group, T, D]``)
  instead of materializing ``repeat_kv`` copies (attention.rs:84-89) — XLA
  maps the group axis onto the MXU batch dimension for free, where a
  materialized repeat would burn HBM bandwidth.
- The memoized mask cache of the reference (cache.rs:81-103) is replaced by an
  iota comparison fused into the softmax by XLA.

On TPU, :func:`attend` dispatches to the fused Pallas flash kernels
(:mod:`cake_tpu.ops.pallas.flash`) — blockwise online softmax, causal mask in
registers, no HBM score materialization, KV blocks past the frontier never
fetched — at the shapes where the measured sweep says they win
(tools/flash_sweep.py): prefill from ``PREFILL_FLASH_MIN_S`` context up,
single-token decode over a plain cache from ``DECODE_FLASH_MIN_S`` rows up
(heads of 128 and wider, and heads of 64 two to a lane tile).
Below the crossovers XLA's fused attention is faster and ``auto`` picks it.
The XLA path also remains the parity oracle (``CAKE_PALLAS=0`` forces it
everywhere; ``CAKE_PALLAS=1`` forces the kernels everywhere).
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp

from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.ops import kvcache as kv
from cake_tpu.ops import pallas as pk
from cake_tpu.ops import quant
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.rope import apply_rope

log = logging.getLogger("cake_tpu.attention")

NEG_INF = -1e30


def _flash_ok(t: int, s: int, d: int) -> bool:
    """Shapes the compiled (non-interpret) PREFILL kernels handle
    efficiently: lane-aligned head_dim and a KV buffer divisible into
    aligned blocks. (The decode kernel's shapes, heads of 64 among them,
    are :func:`flash_decode_choice`'s.)"""
    return d % 128 == 0 and s % 128 == 0


# Measured context-length crossover for ``impl="auto"`` (tools/flash_sweep.py
# on v5 lite, 8B geometry H=32/KVH=8/D=128 — same treatment quant_matmul's
# m>=16 gate got):
#
# - prefill: flash wins from S >= 2048 (1.5x at T=512/S=2048, 2.2-2.3x at
#   S=4096, 50x at S=8192 where XLA materializes the f32 score matrix) and
#   loses below it (0.77x at T=512/S=1024, 0.87x at T=256/S=512).
# - decode (T=1): see DECODE_FLASH_MIN_S below.
PREFILL_FLASH_MIN_S = 2048
# T floor for the flash prefill: the sweep's smallest measured chunk is
# T=256; far below it the q-block degenerates (_pick_block of a tiny/odd T
# -> 1-row blocks) and the grid re-fetches the whole KV buffer per q-block
# — a speculative-verify dispatch (T ~ 9) would read S bytes T times.
# Real prefill buckets are powers of two >= 256 whenever S is in the flash
# regime, so the floor costs nothing on the prompt path.
PREFILL_FLASH_MIN_T = 256
# Decode (T == 1) over a plain cache: XLA's fused masked gemv sweeps the
# whole reserved buffer at bandwidth whatever the frontier; the kernel
# leaves the cache in HBM and fetches, all KV heads at once, only each
# stream's blocks of ``pk.DECODE_BLOCK_K`` rows up to its frontier,
# straight out of the stacked cache the layer loop carries.
# tools/flash_sweep.py --only served-decode on v5 lite (B 8, KVH 8, G 4,
# D 128, a layer's time inside a walk over 8 layers, PR 29), kernel / XLA
# (ONE walk a dispatch then: the call's dispatch stands as a floor of ~30
# us under these lines' early-frontier times; since PR 50 the tool walks 16
# times a dispatch):
#
# - S 2048: 48 / 105 us at the frontiers a `decode-full` batch has (64-700),
#   105 / 105 us with every frontier at the buffer's end (1.00x: the cost
#   side, nothing to skip); S 4096: 43 / 200 and 196 / 197 us. In the
#   served dense step the kernel takes 27 us a layer where XLA's two
#   fusions took 88 (PERF.md section 5).
# - S 1024: 44 / 63 us early, 61 / 61 at the end: still ahead. S 512 is
#   one block, so there is nothing to skip: 46-53 us both, and XLA stays.
# - blocks of 256 rows read a fifth fewer bytes at early frontiers (40 us)
#   and cost 1.5x XLA at the buffer's end (157 us: a block's eight 4-row
#   products no longer hide behind its fetch); 1024 reads 1024 rows for a
#   300-row stream (60 us). So 512.
# - other rows of heads (B 8, S 2048): KVH 4 x G 4, a tp=2 mesh's local
#   heads: 46 / 63 us mixed, 64 / 63 at the end. KVH 32 x G 1 (an MHA 7B)
#   fits VMEM only at 256 rows and pays for it on a full cache: 122 / 394
#   mixed, 559 / 394 (1.42x) at the end (KVH 16 x D 256 at 256 rows: 86
#   / 375 and 376 / 374): the loop over heads, on XLA until PR 50's form
#   (below). One stream (B 1): 43-49 us both, at every frontier.
#
# ONE query row a KV head (a multi-head model: G == 1). The loop over heads
# does a head's two products and its softmax as one dependent chain, and
# sixteen such chains of single rows do not hide behind a block's fetch
# (PR 47 left the shape on XLA for it). tools/flash_sweep.py --only one-row
# on v5 lite (PR 50, my chip run; us a plane inside 16 walks over 8 layers a
# dispatch, at the frontiers a `decode-full` batch has / at row 703 / at the
# buffer's end), blocks of 128 | 256 | 384 (512 at S 2048) rows:
#
# - B 6, S 768, KVH 16 x D 128 (`ouro-2p6b`'s planes), XLA 56.7 / 56.7 / 56.7:
#   * the heads' products in ONE batched call, the running maximum, sum and
#     accumulator one [KVH, 1, ..] update a block (taken):
#     26.9 | 28.2 | 36.8 mixed, 53.2 | 53.2 | 53.4 at row 703,
#     53.0 | 53.3 | 53.5 at the end (0.94x XLA: the fetch alone);
#   * the loop over heads: 42.6 | 45.0 | 45.7 mixed, 84.1 | 83.3 | 64.7 at
#     the end (1.14-1.48x);
#   * two forms that are not in the tree (ISSUE 50's: the same chain a head,
#     another product). K streamed through the matrix unit against q stood
#     in every column, the values' product elementwise on the vector unit:
#     28.2 | 29.2 | 38.2 mixed, 55.5 | 54.0 | 55.0 at the end. Both products
#     on the vector unit, a lane reduction a key row: 30.1 | 30.1 | 38 mixed,
#     59.5 | 55.1 | 55.8 at the end. Neither beats the batched call at any
#     block: what cost was the sixteen chains, not which operand the matrix
#     unit holds.
# - B 8, S 2048, XLA 185.7 (KVH 16 x D 128), 367.1 (KVH 32 x D 128), 363.5
#   (KVH 16 x D 256), batched at 128 | 256 | 512 rows: KVH 16 29.5 | 36.7 |
#   48.3 mixed, 180.6 | 180.7 | 181.2 at the end (the loop at 512 rows, taken
#   until PR 50: 51.3 and 184.4); KVH 32 56.5 | 70.7 mixed, 358.4 | 358.9 at
#   the end (0.98x; the loop 92.2 | 110.8 and 575 | 545: why it stood on
#   XLA); KVH 16 x D 256 56.4 | 70.7 and 358.3 | 358.7.
# - So 128 rows (``pk.ONE_ROW_BLOCK_K``: a block is computed inside its fetch
#   at any size, and the shortest skips most), from 768 rows up and to the
#   widest row of heads the sweep has (KVH x D 4096). A block of all 768
#   rows (12 MiB double-buffered: over ``pk.DECODE_KV_VMEM``, timed in a
#   scratch run with a raised limit and one walk a dispatch) reads 65-70 us
#   at every frontier against XLA's 68-73 there: nothing to skip.
#
# Heads HALF a lane tile wide (D == 64: LFM2, Llama-3.2-1B, TinyLlama)
# under a group of query rows. The chip stores ``[.., S, 64]`` with the rows
# on the lanes and the kernel is handed that buffer as it lies, ``[.., KVH /
# 2, 128, S]``, a PAIR of heads one 128-deep contraction (``pk.flash_decode``;
# asked for ``[KVH, BK, 64]`` blocks Mosaic refuses: it sees rows padded to
# 128 lanes). tools/flash_sweep.py --only narrow on v5 lite (PR 52, my chip
# run; us a layer inside 16 walks over 8 layers a dispatch, at the frontiers
# a `decode-full` batch has / at row 703 / at the buffer's end), blocks of
# 128 | 256 | 512 rows:
#
# - B 32, S 2048, KVH 8 x G 4 (`lfm2-8b-a1b-cut`), XLA 188.5 / 188.6 / 189.3:
#   * the pairs' products in ONE batched call (taken):
#     66.5 | 54.2 | 59.8 mixed, 127.6 | 87.5 | 98.5 at row 703,
#     322.2 | 221.8 | 187.0 at the end (1.70 | 1.17 | 0.99x XLA);
#   * the loop over pairs: 68.1 | 60.5 | 67.0 mixed, 327.5 | 253.2 | 208.5
#     at the end (1.73 | 1.34 | 1.10x): a 512-row block is 1 MiB here, half
#     the dense cell's, and four chains of eight rows no longer hide behind
#     its fetch.
#   So 512 rows (``pk.NARROW_BLOCK_K``), batched: 256 reads a tenth less at
#   the served frontiers and costs 1.17x XLA on a full cache, over PR 29's
#   bar of 1.10x; at 512 a block's 1.46 us is its fetch at 718 GB/s.
# - the same row of heads, batched at 512 rows, kernel / XLA mixed and at the
#   end: S 4096 61.0 / 365.2 and 367.0 / 364.4; S 1024 60.8 / 100.4 and 99.4
#   / 100.3; S 512 (one block, nothing to skip) 52.7 / 52.3: XLA stays under
#   1024 rows (``NARROW_FLASH_MIN_S``). B 8 x S 2048: 17.7 / 52.7 and 58.0 /
#   52.9 (1.10x: a call's ~11 us stand over 32 blocks).
# - other rows of heads, B 8 x S 2048, batched at 512: KVH 4 x G 8
#   (TinyLlama) 10.9 / 30.1 and 30.8 / 30.0; KVH 2 x G 7 (Qwen2.5-0.5B: ONE
#   pair, a block of 256 KiB) 8.5 / 20.3 and 23.2 / 20.2 (1.15x: over the
#   bar, so XLA under ``NARROW_MIN_KV_HEADS``).
#
# The frontier is data, so a nearly full cache runs the kernel too, at
# XLA's cost. An int8 cache stays on XLA (the dequantize fuses into its
# dot; a kernel operand would be a written-out bf16 buffer).
DECODE_FLASH_MIN_S = 1024
ONE_ROW_FLASH_MIN_S = 768
ONE_ROW_MAX_WIDTH = 4096
NARROW_FLASH_MIN_S = 1024
NARROW_MIN_KV_HEADS = 4


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
        "flash kernels enabled but prefill shape (T=%d, S=%d, D=%d) is not "
        "lane-aligned (a chunk's kernel needs D%%128==0 and S%%128==0; "
        "only the single-token kernel takes heads of 64); falling back to "
        "the XLA attention path", t, s, d,
    )
    return "xla"


def flash_decode_choice(s: int, d: int, kv_heads: int, group: int,
                        itemsize: int = 2) -> str:
    """``"flash"`` or ``"xla"`` for a single-token (T == 1) attention
    of ``group`` query rows a KV head over a plain ``S``-row cache of
    ``kv_heads`` heads of size ``d`` — THE decode policy, from what a trace
    can see of its input (the shapes; the frontier is data): heads that are
    a multiple of a lane tile, and heads of half a tile in pairs, each in
    the block and from the floor its own sweep gave. :func:`attend` asks
    it, once for each decode program traced, and publishes the answer
    (``attn.decode_kernel``)."""
    if not pk.kernels_enabled():
        return "xla"
    bk = pk.decode_block_k(s, kv_heads, d, itemsize, group)
    # heads of 64, an even number of them, go two to a lane tile
    narrow = pk.narrow_heads(d, kv_heads)
    if pk.force_kernels():
        if pk.interpret_default() or (
                bk is not None and s % 128 == 0 and (d % 128 == 0 or narrow)):
            return "flash"
        log.warning(
            "flash kernels forced (CAKE_PALLAS=1) but decode shape "
            "(T=1, S=%d, D=%d, KVH=%d) is not lane-aligned (need S%%128==0 "
            "and D%%128==0, or D==64 over an even number of KV heads) or "
            "its KV blocks do not fit VMEM; falling back to the XLA "
            "attention path", s, d, kv_heads,
        )
        return "xla"
    if narrow:
        # a group of query rows over pairs of 64-wide heads (the table
        # above); ONE row a head of 64 has no line in a sweep
        fits = (bk == pk.NARROW_BLOCK_K and group > 1
                and kv_heads >= NARROW_MIN_KV_HEADS)
        return "flash" if fits and s >= NARROW_FLASH_MIN_S else "xla"
    if d % 128:
        return "xla"
    if group == 1:
        # ONE query row a KV head: the batched form, at the rows of heads
        # and windows the sweep has (the table above)
        fits = (bk == pk.ONE_ROW_BLOCK_K
                and kv_heads * d <= ONE_ROW_MAX_WIDTH)
        return "flash" if fits and s >= ONE_ROW_FLASH_MIN_S else "xla"
    # whole blocks of the measured size, which must fit VMEM: a wider row
    # of heads would need shorter ones (the table above)
    fits = bk == pk.DECODE_BLOCK_K
    return "flash" if fits and s >= DECODE_FLASH_MIN_S else "xla"


def attend(
    q: jax.Array,  # [B, n_heads, T, D] (already roped)
    k_all,  # [B, kv_heads, S, D] (full cache buffer), or [L, ...] stacked
    v_all,
    pos,  # scalar: absolute position of q[..., 0, :]
    impl: str = "auto",  # auto | xla | flash
    window: int | None = None,  # sliding-window width (Mistral); None=full
    layer=None,  # index into a stacked [L, B, kv_heads, S, D] cache
) -> jax.Array:
    """Masked GQA attention over a fixed-size KV buffer. Returns [B,H,T,D].

    ``pos`` may be scalar or ``[B]`` (per-row causal frontiers — the
    multi-stream serving path; per-row is supported by the XLA path and the
    flash decode kernel, T>1 per-row routes to XLA).

    ``k_all``/``v_all`` are plain arrays or :class:`kvcache.QuantizedKV`
    halves; with ``layer`` they are the stacked cache the layer loop
    carries, and this is layer ``layer`` of it. The decode kernel takes
    the stacked buffers themselves (it fetches its blocks out of that
    layer); every other path reads :func:`kvcache.layer_view`, a slice
    its consumer fuses.

    ``impl="auto"`` picks by what the trace can see (T, S, D, the cache's
    kind): prefill by :func:`_flash_prefill_choice`, decode by
    :func:`flash_decode_choice`. The int8 cache has a kernel of its own
    for long prefill (scales folded into the score columns, int8 bytes
    read) and dequantizes on the XLA path for everything else.

    ``window``: sliding-window attention — key positions more than
    ``window`` behind the query are masked out. The flash kernels fold
    the window lower bound into their block sweeps (out-of-window KV
    blocks are neither fetched nor computed). Per-row prefill (T>1 with
    ``[B]`` pos) stays XLA — not a kernel-served shape.
    """
    t, d = q.shape[2], q.shape[3]
    quantized = isinstance(k_all, kv.QuantizedKV)
    data = kv._kv_data(k_all)
    kvh, s = data.shape[-3], data.shape[-2]
    per_row = jnp.asarray(pos).ndim == 1
    if (per_row and t > 1) or (quantized and t == 1):
        impl = "xla"  # not kernel-served shapes, whatever was asked
    elif impl == "auto":
        impl = (_flash_prefill_choice(t, s, d) if t > 1
                else flash_decode_choice(s, d, kvh, q.shape[1] // kvh,
                                         data.dtype.itemsize))
    if t == 1:
        # trace time: which attention the decode program being built
        # holds (the benchmark reads it beside the engine's block counts)
        obs_metrics.gauge("attn.decode_kernel").set(int(impl == "flash"))
    if impl == "flash" and t == 1:
        return pk.flash_decode(q, k_all, v_all, pos, layer=layer,
                               window=window)
    k_l, v_l = kv.layer_view(k_all, layer), kv.layer_view(v_all, layer)
    if quantized and impl == "flash":
        return pk.flash_attention_q8(q, k_l.q, k_l.scale, v_l.q, v_l.scale,
                                     pos, window=window)
    if impl == "flash":
        return pk.flash_attention(q, k_l, v_l, pos, window=window)
    # int8 KV dequantizes at trace level, where the convert+mul fuses into
    # the attention dot's operand read
    return _attend_xla(q, kv.dequant_kv(k_l, q.dtype),
                       kv.dequant_kv(v_l, q.dtype), pos, window=window)


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
    # ONE query row a key/value head (a decode step of a multi-head model:
    # T == 1, G == 1) goes into the products as a group of two equal rows,
    # of which the first is kept. With a single row the chip's compiler
    # multiplies q and K elementwise, wants the heads on the sublanes for
    # it, and re-lays the CARRIED CACHE to ``[.., S, KVH, D]`` on the way
    # into and out of the step: two more caches of temporaries (9 GiB at
    # 192 planes x 8 slots x 768 rows; tests/test_chip_compile.py). Two
    # rows take the product every grouped-query model takes, which reads
    # the cache where it lies. The cache's bytes, not the rows, are the
    # step's cost.
    lone = group == 1 and t == 1
    if lone:
        qg = jnp.concatenate([qg, qg], axis=2)
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
    if lone:
        out = out[:, :, :1]
    return out.reshape(b, n_heads, t, d).astype(q.dtype)


def _project_heads(x, wq, wk, wv, num_heads: int, num_kv_heads: int,
                   bq=None, bk=None, bv=None, qk_norm: tuple | None = None,
                   rotated: bool = True, gated: bool = False):
    """``x [B, T, hidden]`` through the q, k and v projections (with their
    biases, where a family has them), as heads ``[B, heads, T, D]``; with
    ``qk_norm`` ``(q weight [D], k weight [D], eps)`` each head of q and k
    RMS-normed (one weight for all heads), which comes before any
    rotation. ``rotated``: the caller rotates q and k behind this (its
    ``cos`` is not None). ``gated``: ``wq`` gives a head's ``[q | gate]``
    side by side; a fourth value comes back, the gate ``[B, T, heads *
    D]`` as the output lies before ``wo`` (None where not gated)."""
    b, t, _ = x.shape
    d = quant.out_features(wq) // num_heads // (2 if gated else 1)
    q = quant.dense(x, wq)
    k = quant.dense(x, wk)
    v = quant.dense(x, wv)
    if bq is not None:
        q = q + bq
    if bk is not None:
        k = k + bk
    if bv is not None:
        v = v + bv
    if rotated or qk_norm is not None:
        # Keep each product apart from the per-head operations behind it.
        # Fused with the reshape to heads, the norm over D and the
        # rotation, a product takes their layout, and the chip's compiler
        # answers by re-laying the WEIGHT instead of the activation: a
        # layer's wq and wk sliced out of their stack, written out and
        # copied transposed before every product (100 MB a layer and step
        # at 6144 x 8192), an int8 stack transposed whole once a dispatch;
        # v's slice written out in an admission. Apart, the products read
        # the stacked parameter where it lies, as wo and the feed-forward
        # do, at the cost of one pass over q, k and v
        # (tests/test_chip_compile.py test_program_moves_no_projection
        # guards it). Where only the reshape follows (no norm, no
        # rotation: jamba2-3b's 2560 x 2560) the compiler stages a layer's
        # slice in fast memory and the step is 0.4% faster that way than
        # behind a barrier, so those stay fused (PERF.md section 6, PR 41).
        q, k, v = jax.lax.optimization_barrier((q, k, v))
    gate = None
    if gated:
        q, gate = (a.reshape(b, t, num_heads * d) for a in jnp.split(
            q.reshape(b, t, num_heads, 2 * d), 2, axis=-1))
    q = q.reshape(b, t, num_heads, d).transpose(0, 2, 1, 3)
    k = k.reshape(b, t, num_kv_heads, d).transpose(0, 2, 1, 3)
    v = v.reshape(b, t, num_kv_heads, d).transpose(0, 2, 1, 3)
    if qk_norm is not None:
        q = rms_norm(q, qk_norm[0], qk_norm[2])
        k = rms_norm(k, qk_norm[1], qk_norm[2])
    return q, k, v, gate


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
    qk_norm: tuple | None = None,  # (q weight [D], k weight [D], eps)
    gated: bool = False,  # wq gives a head's [q | gate]
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

    ``qk_norm``: an RMSNorm over each head of q and k (one weight for all
    heads), before the rotation. ``gated``: ``wq`` gives each head a gate a
    channel beside its query, and the heads' output is multiplied by its
    sigmoid before ``wo`` (named scope ``attn.gate``).
    """
    b, t, hidden = x.shape
    q, k, v, gate = _project_heads(
        x, wq, wk, wv, num_heads, num_kv_heads, bq, bk, bv, qk_norm,
        rotated=cos is not None, gated=gated)
    d = q.shape[-1]

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
        out = attend(q, k_cache, v_cache, pos, window=window,
                     layer=layer)  # [B,H,T,D]

    out = out.transpose(0, 2, 1, 3).reshape(b, t, num_heads * d)
    if gate is not None:
        with jax.named_scope("attn.gate"):
            out = (out * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(
                out.dtype)
    out = quant.dense(out, wo)
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)
    if bo is not None:
        # after the tp reduction: the bias belongs to the full (summed)
        # projection, not to each rank's partial
        out = out + bo
    return out, k_cache, v_cache


def _attend_blocks(qb: jax.Array, k_blocks: jax.Array, v_blocks: jax.Array,
                   mask: jax.Array) -> jax.Array:
    """Grouped-query attention a block of queries at a time: ``qb [B, KH,
    G, N, Q, D]`` against ``k_blocks``/``v_blocks [B, KH, N, S, D]`` under
    ``mask`` (broadcast to ``[B, KH, G, N, Q, S]``); float32 scores and
    softmax, as :func:`_attend_xla`. Returns ``[B, KH, G, N, Q, D]``."""
    d = qb.shape[-1]
    scores = jnp.einsum("bkgnqd,bknsd->bkgnqs", qb, k_blocks,
                        preferred_element_type=jnp.float32
                        ) / jnp.sqrt(jnp.float32(d))
    probs = jax.nn.softmax(jnp.where(mask, scores, NEG_INF), axis=-1)
    out = jnp.einsum("bkgnqs,bknsd->bkgnqd", probs.astype(v_blocks.dtype),
                     v_blocks, preferred_element_type=jnp.float32)
    return out.astype(qb.dtype)


def _attend_band(
    q: jax.Array,  # [B, H, T, D]
    k_buf: jax.Array,  # [B, KH, R + T, D]: row s holds position pos - R + s
    v_buf: jax.Array,
    pos,  # scalar: position of q[..., 0, :]
    window: int,
    rows: int,  # R: rows of history ahead of the chunk's own
) -> jax.Array:
    """Windowed attention of a chunk over a buffer of contiguous positions
    (``rows`` rows of history, then the chunk's own keys): query ``t``
    sees keys ``j`` with ``0 <= t - j < window`` and ``j >= 0``. Where the
    chunk is whole blocks of ``rows`` queries (``window <= rows``), a
    block's scores are taken against its own keys and the block before it
    alone: ``T x 2R`` scores a head, not ``T x (R + T)``, at any chunk
    length. Returns ``[B, H, T, D]``."""
    b, n_heads, t, d = q.shape
    kvh = k_buf.shape[1]
    blk = rows if t % rows == 0 else t
    nb = t // blk
    qb = q.reshape(b, kvh, n_heads // kvh, nb, blk, d)

    def blocks(buf):
        """``[B, KH, nb, R + blk, D]``: block ``n``'s keys are buffer rows
        ``n * blk .. n * blk + R + blk``."""
        if nb == 1:
            return buf[:, :, None]
        parts = buf.reshape(b, kvh, nb + 1, blk, d)
        return jnp.concatenate([parts[:, :, :-1], parts[:, :, 1:]], axis=3)

    span = rows + blk
    qi = jax.lax.broadcasted_iota(jnp.int32, (nb, blk, span), 1)
    si = jax.lax.broadcasted_iota(jnp.int32, (nb, blk, span), 2)
    first = jax.lax.broadcasted_iota(jnp.int32, (nb, blk, span), 0) * blk
    behind = rows + qi - si  # query position - key position
    kpos = jnp.asarray(pos, jnp.int32) - rows + first + si
    mask = (behind >= 0) & (behind < window) & (kpos >= 0)
    out = _attend_blocks(qb, blocks(k_buf), blocks(v_buf), mask)
    return out.reshape(b, n_heads, t, d)


def _attend_ring_flash(
    q: jax.Array,  # [B, H, T, D]
    ring_k: jax.Array,  # [B, KH, R, D]: one layer's ring, position p at p % R
    ring_v: jax.Array,
    k: jax.Array,  # [B, KH, T, D]: the chunk's own keys
    v: jax.Array,
    pos,  # scalar: position of q[..., 0, :]
    window: int,
) -> jax.Array:
    """Windowed attention of a chunk over its ring and its own keys
    through the flash prefill kernel (:func:`pk.flash_attention` with
    ``window``: the bare stack's, Mistral's path), with no score in HBM.
    The kernel wants a buffer whose row ``s`` holds position ``base + s``
    and no row of another stream before the queries: ``base = max(pos - R,
    0)``, the ring rolled so that its row of position ``base`` comes first
    (its ``R`` rows are then positions ``base .. base + R - 1``, those
    the stream has written among them), the chunk's keys written over it
    from row ``pos - base = min(pos, R)`` on. What lies behind the chunk
    (stale rows, where ``pos < R``) is later than every query and masked
    causally. Returns ``[B, H, T, D]``."""
    rows = ring_k.shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    at = jnp.minimum(pos, rows)  # the first query's row of the buffer

    def buffer(ring, new):
        new = new.astype(ring.dtype)
        buf = jnp.concatenate([jnp.roll(ring, -(pos - at), axis=2), new],
                              axis=2)
        return jax.lax.dynamic_update_slice_in_dim(buf, new, at, axis=2)

    return pk.flash_attention(q, buffer(ring_k, k), buffer(ring_v, v), at,
                              window=window)


def window_attention_block(
    x: jax.Array,  # [B, T, hidden]
    wq: jax.Array,
    wk: jax.Array,
    wv: jax.Array,
    wo: jax.Array,
    ring_k: jax.Array,  # [L, B, kv_heads, R, D]: the carried rings
    ring_v: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    pos,
    num_heads: int,
    num_kv_heads: int,
    window: int,
    layer: jax.Array,
    qk_norm: tuple | None = None,  # (q weight [D], k weight [D], eps)
    valid: jax.Array | None = None,  # [B]: a bucketed chunk's true tokens
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One attention sublayer through a sliding window, over a ring of
    ``R`` rows a stream (:func:`cake_tpu.ops.kvcache.ring_write`): query
    ``t`` sees keys ``j`` with ``0 <= t - j < window``. Returns
    ``(attn_out [B, T, hidden], ring_k, ring_v)``, the rings whole and
    written in place.

    One token (``T == 1``; ``pos`` scalar or ``[B]``): the new row is
    written, then the ring is read whole: each row's position follows
    from its index and the stream's position
    (:func:`cake_tpu.ops.kvcache.ring_positions`), and a row the stream
    has not written, or one outside the window, is masked: a ring is never
    zeroed. XLA's attention over ``R`` rows and no kernel: a ring of 128
    rows a stream (K-EXAONE) is a quarter of the decode kernel's one
    block; one of 1024 (Mellum) is two of them, swept whole whatever the
    stream holds (``attn.ring_rows_swept`` against ``attn.ring_rows_live``
    say what a step that followed the live rows would save: a later
    kernel's, PERF.md section 7).

    A chunk (``T > 1``, ``pos`` scalar): the ring's rows are put in
    position order ahead of the chunk's own keys and the chunk attends
    that buffer: band by band in XLA (:func:`_attend_band`, ``T x 2R``
    float32 scores a head) or, where the prefill policy takes the kernels
    at a band's shape (:func:`_flash_prefill_choice` of ``R`` queries over
    ``2R`` keys: rings of 1024 rows from chunks of 1024 on, where the
    band's scores would be 2 GB a layer at 8192 rows), through the flash
    prefill kernel with the window's lower bound folded into its sweep
    (:func:`_attend_ring_flash`: no score leaves the chip's fast memory).
    Then the chunk's newest ``R`` true rows are written (``valid``: a
    bucket's padding never enters a ring)."""
    b, t, _ = x.shape
    rows = ring_k.shape[3]
    q, k, v, _ = _project_heads(x, wq, wk, wv, num_heads, num_kv_heads,
                                qk_norm=qk_norm)
    d = q.shape[-1]
    q = apply_rope(q, cos, sin, pos)
    k = apply_rope(k, cos, sin, pos)
    pos = jnp.asarray(pos, jnp.int32)
    if t == 1:
        ring_k, ring_v = kv.ring_write(ring_k, ring_v, k, v, pos, layer)
        held = kv.ring_positions(pos, rows)  # [R] or [B, R]
        seen = (held >= 0) & (pos[..., None] - held < window)
        out = _attend_blocks(  # one block of one query, R keys
            q.reshape(b, num_kv_heads, -1, 1, 1, d),
            kv.layer_view(ring_k, layer)[:, :, None],
            kv.layer_view(ring_v, layer)[:, :, None],
            jnp.broadcast_to(seen, (b, rows))[:, None, None, None, None, :])
        out = out.reshape(b, num_heads, 1, d)
    else:
        blk = rows if t % rows == 0 else t  # a band's queries
        if _flash_prefill_choice(blk, rows + blk, d) == "flash":
            out = _attend_ring_flash(
                q, kv.layer_view(ring_k, layer), kv.layer_view(ring_v, layer),
                k, v, pos, window)
        else:
            def ahead(ring, new):
                """The ring's rows in position order (``pos - R .. pos -
                1``), then the chunk's own."""
                old = jnp.roll(kv.layer_view(ring, layer), -pos, axis=2)
                return jnp.concatenate([old, new.astype(ring.dtype)], axis=2)

            out = _attend_band(q, ahead(ring_k, k), ahead(ring_v, v), pos,
                               window, rows)
        ring_k, ring_v = kv.ring_write(ring_k, ring_v, k, v, pos, layer,
                                       valid=valid)
    out = out.transpose(0, 2, 1, 3).reshape(b, t, num_heads * d)
    return quant.dense(out, wo), ring_k, ring_v
