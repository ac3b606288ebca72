"""Blockwise (flash) causal GQA attention as Pallas TPU kernels.

Replaces the reference's materialized-scores attention for long sequences
(`cake-core/src/model/attention.rs:59-80`: repeat_kv + full [T, S] score
matrix + memoized masks, cache.rs:81-103). Here the causal mask is folded
into an online-softmax blockwise sweep over the KV buffer — scores never hit
HBM, the mask is an iota comparison computed in registers, and KV blocks
entirely beyond the causal frontier are never even DMA'd from HBM (their
block index is clamped so the pipeline re-uses the previous fetch, and the
compute is predicated off).

Two kernels share the math:

- :func:`flash_attention` — prefill: ``q [B, H, T, D]`` against the full
  ``[B, KVH, S, D]`` cache buffers, grid over (batch, head, q-block,
  kv-block) with f32 running max / sum / accumulator scratch.
- :func:`flash_decode` — decode (T == 1): the GQA head group is folded into
  the q-row axis (``[B, KVH, group, D]``) so the MXU sees a [group, D] x
  [D, BK] matmul a head (the heads' matmuls one batched call where the
  group is ONE row, a multi-head model, and where heads of 64 go two to a
  lane tile). One invocation leaves the cache
  in HBM (one
  layer's ``[B, KVH, S, D]`` or the stacked ``[L, B, KVH, S, D]`` the
  layer loop carries, the layer a scalar operand) and walks each stream's
  live KV blocks, all KV heads of a block at once, with its own
  double-buffered DMA: only blocks at or before a stream's frontier
  ``pos`` (and inside its window) are read.

Numerics match :func:`cake_tpu.ops.attention.attend`: f32 scores and
accumulation regardless of model dtype (attention.rs:62-77), probabilities
cast to the value dtype for the PV matmul.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128


def _pick_block(n: int, preferred: int) -> int:
    """Largest power-of-two block <= preferred that divides n."""
    b = 1
    while b * 2 <= min(n, preferred) and n % (b * 2) == 0:
        b *= 2
    return b


def _kv_block_bounds(pos, qb, block_q: int, block_k: int,
                     window: int | None):
    """(min_kb, max_kb) of the live KV-block range for q block ``qb`` at
    frontier ``pos`` — THE one definition of the causal upper bound and
    the sliding-window lower bound, shared by the kernels' live-range
    gates and the BlockSpec index maps so fetch clamp and compute mask
    can never desynchronize. (Decode's single row has its own,
    :func:`decode_block_range`, which the host counts with too.)"""
    max_kb = jax.lax.div(pos + (qb + 1) * block_q - 1, block_k)
    if window is None:
        return 0, max_kb
    lo = jnp.maximum(0, pos + qb * block_q - window + 1)
    return jax.lax.div(lo, block_k), max_kb


# ---------------------------------------------------------------------------
# Prefill kernel
# ---------------------------------------------------------------------------


def _prefill_kernel(
    pos_ref,  # scalar prefetch: [1] int32
    q_ref,  # [1, 1, BQ, D]
    k_ref,  # [1, 1, BK, D]
    v_ref,  # [1, 1, BK, D]
    o_ref,  # [1, 1, BQ, D]
    acc_ref,  # VMEM [BQ, D] f32
    m_ref,  # VMEM [BQ, LANES] f32  (running max, lanes replicated)
    l_ref,  # VMEM [BQ, LANES] f32  (running denom)
    *,
    block_q: int,
    block_k: int,
    scale: float,
    num_kv_blocks: int,
    window: int | None = None,
):
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    pos = pos_ref[0]

    @pl.when(kb == 0)
    def _init():
        m_ref[:] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

    # Sliding window (Mistral): blocks entirely below the q block's
    # lowest valid key position are skipped — the block sweep is
    # window-proportional, not history-proportional.
    min_kb, max_kb = _kv_block_bounds(pos, qb, block_q, block_k, window)
    live = (kb >= min_kb) & (kb <= max_kb)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]  # [BQ, D]
        k = k_ref[0, 0]  # [BK, D]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * scale  # [BQ, BK] f32

        qpos = (
            pos
            + qb * block_q
            + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        )
        kpos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:]  # [BQ, LANES]
        l_prev = l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)  # [BQ, LANES]
        p = jnp.exp(s - m_new[:, :1])  # [BQ, BK] f32
        l_ref[:] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha[:, :1] + pv

    @pl.when(kb == num_kv_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # [B, H, T, D] (already roped)
    k_all: jax.Array,  # [B, KVH, S, D] full cache buffer
    v_all: jax.Array,
    pos,  # scalar int: absolute position of q[..., 0, :]
    *,
    block_q: int = 512,
    block_k: int | None = None,
    window: int | None = None,
    interpret: bool | None = None,
    scale: float | None = None,
    name: str | None = None,
) -> jax.Array:
    """Causal flash attention over a fixed KV buffer. Returns [B, H, T, Dv]
    (``Dv``: the values' width, which may differ from the keys' ``D``).
    ``scale``: the scores' factor where it is not ``D^-0.5`` (heads padded
    with zero channels to whole lane tiles keep their own); ``name``: the
    call's name in a device trace where a reader must tell it from the
    grouped-query prefill's.

    Default blocks from a v5e sweep (8B geometry, D=128): bq=512
    throughout; bk=1024 once the KV buffer is long enough to amortize the
    bigger fetch (S >= 4096 — 1.5x faster there than bk=512), bk=512 below
    (where bk=1024 loses ~35%).

    ``window``: sliding-window attention (Mistral) — the lower mask bound
    is folded into the block sweep, so KV blocks entirely outside the
    window are neither fetched nor computed (the XLA fallback sweeps and
    masks the whole history instead).
    """
    b, h, t, d = q.shape
    kvh, s = k_all.shape[1], k_all.shape[2]
    dv = v_all.shape[-1]
    group = h // kvh
    if block_k is None:
        block_k = 1024 if s >= 4096 else 512
    bq = _pick_block(t, block_q)
    bk = _pick_block(s, block_k)
    nq, nk = t // bq, s // bk
    if interpret is None:
        from cake_tpu.ops.pallas import interpret_default

        interpret = interpret_default()
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    def q_map(bi, hi, qb, kb, pos_ref):
        return (bi, hi, qb, 0)

    def kv_map(bi, hi, qb, kb, pos_ref):
        # Clamp to the causal frontier (and, windowed, to the window's
        # lower bound): fully-masked blocks re-use a live block index, so
        # the pipeline skips their HBM fetch.
        min_kb, max_kb = _kv_block_bounds(pos_ref[0], qb, bq, bk, window)
        return (bi, hi // group, jnp.clip(kb, min_kb, max_kb), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_map),
            pl.BlockSpec((1, 1, bk, d), kv_map),
            pl.BlockSpec((1, 1, bk, dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dv), q_map),
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _prefill_kernel, block_q=bq, block_k=bk, scale=scale,
        num_kv_blocks=nk, window=window,
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, h, t, dv), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * t * s * (d + dv),
            bytes_accessed=(q.size + k_all.size + v_all.size
                            + b * h * t * dv) * q.dtype.itemsize,
            transcendentals=b * h * t * s,
        ),
        interpret=interpret,
        **({"name": name} if name else {}),
    )(pos_arr, q, k_all, v_all)


# ---------------------------------------------------------------------------
# Prefill kernel over an int8 KV cache (kvcache.QuantizedKV layout)
# ---------------------------------------------------------------------------


def _prefill_q8_kernel(
    pos_ref,  # scalar prefetch: [1] int32
    q_ref,  # [1, 1, BQ, D]
    kq_ref,  # [1, 1, BK, D] int8
    ks_ref,  # [1, KVH, BK] f32 (per-token-per-head scales, full head axis)
    vq_ref,  # [1, 1, BK, D] int8
    vs_ref,  # [1, KVH, BK] f32
    o_ref,  # [1, 1, BQ, D]
    acc_ref,  # VMEM [BQ, D] f32
    m_ref,  # VMEM [BQ, LANES] f32
    l_ref,  # VMEM [BQ, LANES] f32
    *,
    block_q: int,
    block_k: int,
    scale: float,
    num_kv_blocks: int,
    group: int,
    window: int | None = None,
):
    """Same online softmax as :func:`_prefill_kernel`, reading int8 KV. The
    per-token dequant scale is constant along D, so it factors OUT of both
    matmuls: ``q . (s_j * kq_j) = s_j * (q . kq_j)`` folds into the score
    column, and ``p @ diag(vs) @ vq = (p * vs) @ vq`` folds into the
    probabilities — the kernel never materializes dequantized KV, and HBM
    reads stay at the int8 bytes + one f32 scale per token.

    The scale blocks carry the FULL kv-head axis: a (1, 1, BK) block would
    put a size-1 block over that axis, which Mosaic's sublane rule rejects
    on real TPUs whenever KVH > 1. The kernel reads its head's row with a
    dynamic index on the ref (a ``dynamic_slice`` of the loaded value has
    no Mosaic lowering) — the stripe is a few KB."""
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    hk = pl.program_id(1) // group  # this grid cell's kv head
    pos = pos_ref[0]

    @pl.when(kb == 0)
    def _init():
        m_ref[:] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

    min_kb, max_kb = _kv_block_bounds(pos, qb, block_q, block_k, window)
    live = (kb >= min_kb) & (kb <= max_kb)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]  # [BQ, D]
        kq = kq_ref[0, 0].astype(q.dtype)  # [BK, D] (VMEM convert)
        s = jax.lax.dot_general(
            q, kq, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ks_row = ks_ref[0, pl.ds(hk, 1), :]  # [1, BK]
        s = s * scale * ks_row  # fold key scales per column

        qpos = (
            pos
            + qb * block_q
            + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        )
        kpos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:]
        l_prev = l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])  # [BQ, BK] f32
        l_ref[:] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_new
        vq = vq_ref[0, 0].astype(q.dtype)
        vs_row = vs_ref[0, pl.ds(hk, 1), :]  # [1, BK]
        pv = jax.lax.dot_general(
            (p * vs_row).astype(q.dtype), vq,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha[:, :1] + pv

    @pl.when(kb == num_kv_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)


def flash_attention_q8(
    q: jax.Array,  # [B, H, T, D] (already roped)
    k_q: jax.Array,  # [B, KVH, S, D] int8
    k_scale: jax.Array,  # [B, KVH, S] f32
    v_q: jax.Array,  # [B, KVH, S, D] int8
    v_scale: jax.Array,  # [B, KVH, S] f32
    pos,  # scalar int
    *,
    block_q: int = 512,
    block_k: int | None = None,
    window: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Causal flash attention over an int8 KV buffer (quantize-on-write
    layout of :class:`cake_tpu.ops.kvcache.QuantizedKV`). Returns
    ``[B, H, T, D]``. Keeps the long-context flash plane available to the
    int8 cache: the XLA fallback would materialize dequantized KV (or full
    scores) in HBM at exactly the window sizes the int8 cache exists for."""
    b, h, t, d = q.shape
    kvh, s = k_q.shape[1], k_q.shape[2]
    group = h // kvh
    if block_k is None:
        block_k = 1024 if s >= 4096 else 512
    bq = _pick_block(t, block_q)
    bk = _pick_block(s, block_k)
    nq, nk = t // bq, s // bk
    if interpret is None:
        from cake_tpu.ops.pallas import interpret_default

        interpret = interpret_default()
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)
    scale = 1.0 / math.sqrt(d)

    def q_map(bi, hi, qb, kb, pos_ref):
        return (bi, hi, qb, 0)

    def _kb_idx(qb, kb, pos_ref):
        min_kb, max_kb = _kv_block_bounds(pos_ref[0], qb, bq, bk, window)
        return jnp.clip(kb, min_kb, max_kb)

    def kv_map(bi, hi, qb, kb, pos_ref):
        return (bi, hi // group, _kb_idx(qb, kb, pos_ref), 0)

    def scale_map(bi, hi, qb, kb, pos_ref):
        # full kv-head axis per block (see the kernel docstring); only
        # batch and the (clamped) S block vary
        return (bi, 0, _kb_idx(qb, kb, pos_ref))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_map),
            pl.BlockSpec((1, 1, bk, d), kv_map),
            pl.BlockSpec((1, kvh, bk), scale_map),
            pl.BlockSpec((1, 1, bk, d), kv_map),
            pl.BlockSpec((1, kvh, bk), scale_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _prefill_q8_kernel, block_q=bq, block_k=bk, scale=scale,
        num_kv_blocks=nk, group=group, window=window,
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * t * s * d,
            bytes_accessed=(
                2 * q.size * q.dtype.itemsize
                + 2 * k_q.size
                + 2 * k_scale.size * 4
            ),
            transcendentals=b * h * t * s,
        ),
        interpret=interpret,
    )(pos_arr, q, k_q, k_scale, v_q, v_scale)


# ---------------------------------------------------------------------------
# Decode kernel (T == 1)
# ---------------------------------------------------------------------------

# Rows of a KV block the decode kernel fetches at once (all KV heads of a
# stream). From the v5e sweep of tools/flash_sweep.py at the served shapes
# (the table is beside ops.attention.DECODE_FLASH_MIN_S).
DECODE_BLOCK_K = 512
# ... and where a KV head has ONE query row (the kernel's batched form: a
# block is computed inside its fetch at any size, so the shortest block,
# which skips most, wins: ``--only one-row``, the same table)
ONE_ROW_BLOCK_K = 128
# ... and where a group of query rows sits over heads HALF a lane tile
# wide, which the kernel takes two to the tile (``--only narrow``, the same
# table)
NARROW_BLOCK_K = 512
# What the kernel's K and V blocks may take of VMEM, double-buffered:
# 4 x KVH x rows x D x itemsize (q, o and the accumulators are small beside
# them: B 128 compiles at KVH 8). v5e's compiler gives a kernel 16 MiB: 8
# compile (KVH 32 / D 128 and KVH 16 / D 256 at 256 rows, test_chip_compile)
# and 16 do not.
DECODE_KV_VMEM = 8 << 20


def decode_block_k(s: int, kv_heads: int, d: int, itemsize: int,
                   group: int, block_k: int | None = None) -> int | None:
    """Rows of the KV block :func:`flash_decode` fetches at these shapes:
    ``block_k`` (None: what the sweep gave this row of heads,
    ``ONE_ROW_BLOCK_K`` for one query row a KV head, ``NARROW_BLOCK_K`` for
    a group of them over pairs of 64-wide heads and ``DECODE_BLOCK_K`` for
    a group over wider ones) where it divides ``s``, else the largest power
    of two up to it that does, halved (down to 128 rows) until the blocks fit
    ``DECODE_KV_VMEM``; ``None`` where not even those fit, and the kernel
    cannot be built."""
    if block_k is None:
        block_k = (ONE_ROW_BLOCK_K if group == 1 else
                   NARROW_BLOCK_K if narrow_heads(d, kv_heads) else
                   DECODE_BLOCK_K)
    bk = block_k if s % block_k == 0 else _pick_block(s, block_k)

    def need(rows):
        return 4 * kv_heads * rows * d * itemsize

    while bk > 128 and need(bk) > DECODE_KV_VMEM:
        bk = _pick_block(s, bk // 2)
    return bk if need(bk) <= DECODE_KV_VMEM else None


def narrow_heads(d: int, kv_heads: int) -> bool:
    """Heads HALF a lane tile wide, an even number of them: what
    :func:`flash_decode` takes two to the tile (:func:`_pair_heads`)."""
    return 2 * d == _LANES and kv_heads % 2 == 0


def decode_block_range(pos, block_k: int, num_kv_blocks: int,
                       window: int | None, xp=jnp):
    """(lo, hi), inclusive: the KV blocks of ``block_k`` rows that a
    single-token attention at frontier ``pos`` reads of a buffer of
    ``num_kv_blocks`` — from the sliding window's lower bound up to the
    frontier, inside the buffer (a frontier may have left it) and never
    empty. THE one definition: the kernel walks it (``pos`` a traced
    scalar) and the engine's ``attn.kv_blocks_*`` counters sum it
    (``xp=np``, ``pos`` the host's array), so what is counted is what is
    fetched."""
    hi = xp.minimum(pos // block_k, num_kv_blocks - 1)
    if window is None:
        return 0 * hi, hi
    return xp.minimum(xp.maximum(pos - window + 1, 0) // block_k, hi), hi


def decode_blocks_read(pos, steps: int, s: int, block_k: int = DECODE_BLOCK_K,
                       window: int | None = None) -> tuple[int, int]:
    """(read, reserved): the KV blocks of ``block_k`` rows that
    ``steps`` decode steps from the host-side frontiers ``pos [B]`` make
    :func:`flash_decode` fetch of one layer (:func:`decode_block_range`
    of every stream at every step), and the blocks the ``[B, S]``
    reservation holds for those steps."""
    nk = max(1, s // block_k)
    at = np.asarray(pos, np.int64)[:, None] + np.arange(steps)  # [B, steps]
    lo, hi = decode_block_range(at, block_k, nk, window, xp=np)
    return int((hi - lo + 1).sum()), at.size * nk


def _decode_kernel(
    pos_ref,  # [B] int32 (per-row causal frontier; row b reads pos_ref[b])
    *refs,  # [layer_ref ([1] int32) when ``stacked``,] then:
    # q_ref [B, KVH, G, D] (VMEM), k_hbm / v_hbm: the whole cache, left
    # where it is, o_ref [B, KVH, G, D], kbuf / vbuf VMEM [2, KVH, BK, D],
    # sem DMA [2, 2], acc_ref VMEM [KVH, G, D] f32, m_ref / l_ref VMEM
    # [KVH, G, LANES] f32
    stacked: bool,
    batch: int,
    kv_heads: int,
    group: int,
    block_k: int,
    scale: float,
    num_kv_blocks: int,
    window: int | None = None,
    batched: bool = False,
    rows_on_lanes: bool = False,
):
    """One invocation walks every stream's LIVE KV blocks in turn, (row 0:
    lo..hi), (row 1: lo..hi), ...: the block after the one being computed
    is already on its way into the other buffer, across the change of
    row too, so no fetch waits on a skipped grid step and none is paid
    for.

    ``rows_on_lanes``: the cache is ``[.., KVH, D, S]`` (a head's rows its
    columns: how the chip lays out heads narrower than a lane tile, see
    :func:`flash_decode`), a block ``[KVH, D, BK]``, and the two products
    contract the other axis of it; nothing else differs.

    ``batched``: a block's products are one batched call over the heads
    and its softmax bookkeeping one update of ``[KVH, G, ..]`` arrays.
    The loop over heads makes a head's two products and its softmax one
    dependent chain; with ONE query row a head (``group`` 1) sixteen such
    chains of single rows take 1.5 times a block's fetch (84 us a plane at
    B 6 x S 768 x KVH 16 with every frontier at the buffer's end, 53 as
    one call: the fetch alone). A group of rows over heads of 128 keeps
    the loop, which hides behind 512-row fetches, and its compiled kernel;
    over PAIRS of 64-wide heads a 512-row block is half those bytes, and
    the four pairs' chains no longer hide behind it (209 us a layer at B 32
    x S 2048 with every frontier at the buffer's end, 187 as one call, XLA
    189: the table beside ``ops.attention.DECODE_FLASH_MIN_S``)."""
    lead = ()
    if stacked:
        layer_ref, *refs = refs
        lead = (layer_ref[0],)
    q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, acc_ref, m_ref, l_ref = refs

    def bounds(b):
        return decode_block_range(pos_ref[b], block_k, num_kv_blocks, window)

    # the axis of a head's K block that the scores contract (its D) and
    # of its V block that the values' product contracts (its rows)
    k_dim, v_dim = (0, 1) if rows_on_lanes else (1, 0)

    def copies(b, kb, slot):
        rows = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
        at = lead + (b, slice(None)) + (
            (slice(None), rows) if rows_on_lanes else (rows, slice(None)))
        return (pltpu.make_async_copy(k_hbm.at[at], kbuf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[at], vbuf.at[slot],
                                      sem.at[1, slot]))

    def step(carry):
        b, kb, slot = carry
        pos = pos_ref[b]
        lo, hi = bounds(b)
        last = kb == hi
        b_next = jnp.where(last, b + 1, b)
        lo_next, _ = bounds(jnp.minimum(b_next, batch - 1))
        kb_next = jnp.where(last, lo_next, kb + 1)

        @pl.when(b_next < batch)
        def _prefetch():
            for c in copies(b_next, kb_next, 1 - slot):
                c.start()

        @pl.when(kb == lo)
        def _init():
            m_ref[:] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
            l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

        for c in copies(b, kb, slot):
            c.wait()
        kpos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (group, block_k), 1
        )
        mask = kpos <= pos
        if window is not None:
            # sliding window: this row attends keys in (pos-window, pos]
            mask &= kpos > pos - window
        if batched:
            # every head's product in ONE batched call and one update of
            # the running maximum, sum and accumulator as [KVH, G, ..]
            # arrays: nothing of a head waits for another head's softmax
            k = kbuf[slot]  # [KVH, BK, D] ([KVH, D, BK] rows on lanes)
            v = vbuf[slot]
            s = jax.lax.dot_general(
                q_ref[b], k, (((2,), (1 + k_dim,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            s = jnp.where(mask, s * scale, NEG_INF)  # [KVH, G, BK]
            m_prev = m_ref[:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, :, :1])
            l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=2, keepdims=True)
            m_ref[:] = m_new
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1 + v_dim,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            acc_ref[:] = acc_ref[:] * alpha[:, :, :1] + pv
        else:
            # one [G, D] x [D, BK] product a KV head (unrolled: the heads
            # are independent, so the scheduler overlaps them)
            for h in range(kv_heads):
                q = q_ref[b, h]  # [G, D]
                k = kbuf[slot, h]  # [BK, D] ([D, BK] rows on lanes)
                v = vbuf[slot, h]
                s = jax.lax.dot_general(
                    q, k, (((1,), (k_dim,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                s = jnp.where(mask, s * scale, NEG_INF)  # [G, BK]
                m_prev = m_ref[h]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new[:, :1])
                l_ref[h] = (alpha * l_ref[h]
                            + jnp.sum(p, axis=1, keepdims=True))
                m_ref[h] = m_new
                pv = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (v_dim,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                acc_ref[h] = acc_ref[h] * alpha[:, :1] + pv

        @pl.when(last)
        def _finish():
            o_ref[b] = (acc_ref[:] / l_ref[:, :, :1]).astype(o_ref.dtype)

        return b_next, kb_next, 1 - slot

    lo0, _ = bounds(0)
    for c in copies(0, lo0, 0):
        c.start()
    jax.lax.while_loop(lambda c: c[0] < batch, step,
                       (jnp.int32(0), lo0, jnp.int32(0)))


def _pair_heads(qg, k_all, v_all):
    """The operands :func:`flash_decode` hands its kernel where a head is
    HALF a lane tile wide (:func:`narrow_heads`: two heads of 64 fill the
    128 lanes).

    The chip lays such a cache ``[.., KVH, S, D]`` out with the ROWS on the
    lanes and a head's ``D`` channels on the sublanes (its default layout
    of the shape, ``{3,4,2,1,0:T(8,128)(2,1)}``: nothing is padded), and a
    kernel that asks for ``[KVH, BK, D]`` blocks of it is refused (Mosaic
    sees a buffer padded to 128 lanes, which XLA would have to write). So
    the kernel is handed the view that lies as the buffer does, ``[..,
    KVH / 2, 2 * D, S]``: a transpose and a merge of axes that are a
    bitcast on the chip (tests/test_chip_compile.py holds the compiled
    step to it), a pair of heads' channels one 128-deep contraction.

    The query rows of a pair go block-diagonal, ``[B, KVH / 2, 2 * G, 2 *
    D]`` with head ``i``'s ``G`` rows in columns ``i * D`` on and zeros
    beside them: a row's scores are its own head's, its softmax its own,
    and of the values' product ``[2 * G, 2 * D]`` the diagonal blocks are
    the heads' results (the caller keeps them). The matrix unit's passes
    are counted in 128 x 128 tiles of K and V whatever ``D`` is, so a pair
    costs what ONE head of 128 costs."""
    b, kvh, group, d = qg.shape
    eye = jnp.eye(2, dtype=qg.dtype)[:, None, :, None]  # [2, 1, 2, 1]
    qg = qg.reshape(b, kvh // 2, 2, group, 1, d) * eye
    qg = qg.reshape(b, kvh // 2, 2 * group, 2 * d)

    def view(x):
        x = jnp.swapaxes(x, -1, -2)  # [.., KVH, D, S]
        return x.reshape(x.shape[:-3] + (kvh // 2, 2 * d, x.shape[-1]))

    return qg, view(k_all), view(v_all)


def flash_decode(
    q: jax.Array,  # [B, H, 1, D] (already roped)
    k_all: jax.Array,  # [B, KVH, S, D], or stacked [L, B, KVH, S, D]
    v_all: jax.Array,
    pos,  # scalar int or [B]
    *,
    layer=None,  # index into the stacked form's leading axis
    block_k: int | None = None,
    window: int | None = None,
    interpret: bool | None = None,
    batched: bool | None = None,
) -> jax.Array:
    """Single-position flash attention. Returns [B, H, 1, D].

    The cache stays in HBM and the kernel fetches, for each stream, only
    the KV blocks ``[KVH, BK, D]`` (all KV heads at once) from its
    window's lower bound up to its frontier, double-buffered, the next
    block in flight while this one is computed: a stream whose cache is
    a seventh full costs a seventh of the sweep, and rows nobody wrote
    are neither read nor computed. The GQA group is folded into q rows,
    so a head is one [group, D] x [D, BK] matmul; with ONE query row a KV
    head the heads' matmuls are one batched call and the blocks shorter;
    heads of 64 go two to a lane tile (:func:`_pair_heads`), the pairs'
    matmuls one batched call too (``batched``, None: where ``group`` is 1
    or the heads are paired; ``block_k``, None: :func:`decode_block_k`'s
    own; the sweep tool passes both to time either form at any block).
    ``pos`` may be scalar
    (shared frontier) or ``[B]`` (per-row frontiers — multi-stream
    serving).

    ``layer``: ``k_all``/``v_all`` are the stacked ``[L, B, KVH, S, D]``
    cache the layer loop carries and ``layer`` (traced) picks the layer
    the blocks are fetched from, as a second scalar-prefetch operand: the
    kernel reads straight out of the carried buffers and no layer's slab
    is written out for it.

    ``window``: sliding-window attention — blocks below the window's lower
    bound are likewise neither fetched nor computed, so a W-window decode
    against a long buffer reads ~W of KV bytes instead of ~pos.
    """
    b, h, t, d = q.shape
    assert t == 1, "flash_decode requires T == 1"
    stacked = layer is not None
    assert k_all.ndim == (5 if stacked else 4), (k_all.shape, layer)
    kvh, s = k_all.shape[-3], k_all.shape[-2]
    group = h // kvh
    bk = decode_block_k(s, kvh, d, k_all.dtype.itemsize, group, block_k)
    assert bk is not None, ("no KV block fits the kernel's VMEM", kvh, d)
    nk = s // bk
    if interpret is None:
        from cake_tpu.ops.pallas import interpret_default

        interpret = interpret_default()
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    prefetch = [pos_arr]
    if stacked:
        prefetch.append(jnp.asarray(layer, jnp.int32).reshape(1))
    qg = q.reshape(b, kvh, group, d)
    # heads of half a lane tile, two to the tile (any other width goes as
    # it is: the chip's compiler takes multiples of 128, the interpreter all)
    paired = narrow_heads(d, kvh)
    if paired:
        qg, k_all, v_all = _pair_heads(qg, k_all, v_all)
    kh, kg, kd = qg.shape[1:]  # the heads, query rows and width the kernel sees

    def whole(i, *prefetched):
        return (0, 0, 0, 0)

    block = (2, kh, kd, bk) if paired else (2, kh, bk, kd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(1,),
        in_specs=[
            pl.BlockSpec((b, kh, kg, kd), whole),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((b, kh, kg, kd), whole),
        scratch_shapes=[
            pltpu.VMEM(block, k_all.dtype),
            pltpu.VMEM(block, v_all.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((kh, kg, kd), jnp.float32),
            pltpu.VMEM((kh, kg, _LANES), jnp.float32),
            pltpu.VMEM((kh, kg, _LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, stacked=stacked, batch=b, kv_heads=kh, group=kg,
        block_k=bk, scale=1.0 / math.sqrt(d), num_kv_blocks=nk, window=window,
        batched=(group == 1 or paired) if batched is None else batched,
        rows_on_lanes=paired,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, kh, kg, kd), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * s * d,
            bytes_accessed=2 * b * kvh * s * d * k_all.dtype.itemsize,
            transcendentals=b * h * s,
        ),
        name="flash_decode",
        interpret=interpret,
    )(*prefetch, qg, k_all, v_all)
    if paired:
        # a paired head's own rows and columns: the diagonal blocks
        out = out.reshape(b, kh, 2, group, 2, d)
        out = jnp.stack([out[:, :, i, :, i] for i in range(2)], axis=2)
    return out.reshape(b, h, 1, d)
