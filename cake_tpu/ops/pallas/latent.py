"""Pallas TPU kernel for a decode step's absorbed latent attention (MLA).

The XLA form of :func:`cake_tpu.ops.mla.latent_attention_block`'s
``T == 1`` branch sweeps the whole ``[B, S, kv_lora_rank]`` latent buffer
twice (scores, then values) and masks what lies past each stream's
frontier: the frontier is data, so XLA cannot skip by it. Here the two
latent buffers stay in HBM, as :func:`cake_tpu.ops.pallas.flash.flash_decode`
leaves its cache, and each stream's blocks from row 0 up to its frontier
(:func:`cake_tpu.ops.pallas.flash.decode_block_range`, the one the engine's
``attn.kv_blocks_*`` counters sum) are fetched ONCE, double-buffered across
the change of stream, and used as key and as value: the cache's one "head"
is every query head's, so a block is one ``[H, dc] x [dc, BK]`` product
(plus the rope half's ``[H, dr] x [dr, BK]``) and one ``[H, BK] x [BK, dc]``.

Numerics are the einsums': bfloat16 operands where they have them, float32
scores, maximum, normalizer and accumulator, probabilities rounded to the
buffer's type for the value product. What comes back is what the XLA sweep
hands on: row maximum, normalizer and the UN-normalized float32 output in
latent space, so every rounding point around the sweep stays where it is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cake_tpu.ops.pallas.flash import (DECODE_BLOCK_K, NEG_INF, _LANES,
                                       _pick_block, decode_block_range)


def _kernel(
    pos_ref,  # [B] int32 (per-row frontier; grid step b reads pos_ref[b])
    *refs,  # [layer_ref ([1] int32) when ``stacked``,] then:
    # qc_ref [1, H, dc], qr_ref [1, H, dr] (this stream's absorbed and
    # roped query), c_hbm [(L,) B, 1, S, dc] / rt_hbm [(L,) B, 1, dr, S]:
    # the whole latent buffers, left where they are, m_ref / l_ref [1, H,
    # LANES] f32, o_ref [1, H, dc] f32 (the outputs ARE the running
    # maximum, normalizer and accumulator), cbuf VMEM [2, BK, dc], rbuf
    # VMEM [2, dr, BK], sem DMA [2, 2], slot_ref SMEM [1]: the buffer half
    # the next block to compute lies in
    stacked: bool,
    batch: int,
    block_k: int,
    scale: float,
    num_kv_blocks: int,
):
    """Grid step ``b`` walks stream ``b``'s live blocks 0..hi; the block
    after the one being computed is already on its way into the other half
    of the buffers, across the change of stream (and so of grid step) too:
    the last block of stream ``b`` starts the fetch of stream ``b + 1``'s
    first."""
    lead = ()
    if stacked:
        layer_ref, *refs = refs
        lead = (layer_ref[0],)
    (qc_ref, qr_ref, c_hbm, rt_hbm, m_ref, l_ref, o_ref, cbuf, rbuf, sem,
     slot_ref) = refs
    b = pl.program_id(0)

    def copies(row, kb, slot):
        rows = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
        return (pltpu.make_async_copy(
                    c_hbm.at[lead + (row, 0, rows, slice(None))],
                    cbuf.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(
                    rt_hbm.at[lead + (row, 0, slice(None), rows)],
                    rbuf.at[slot], sem.at[1, slot]))

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        for c in copies(0, 0, 0):
            c.start()

    pos = pos_ref[b]
    _, hi = decode_block_range(pos, block_k, num_kv_blocks, None)
    m_ref[0] = jnp.full(m_ref.shape[1:], -jnp.inf, jnp.float32)
    l_ref[0] = jnp.zeros(l_ref.shape[1:], jnp.float32)
    o_ref[0] = jnp.zeros(o_ref.shape[1:], jnp.float32)
    q_c, q_r = qc_ref[0], qr_ref[0]  # [H, dc], [H, dr]

    def block(kb, slot):
        last = kb == hi
        row_next = jnp.where(last, b + 1, b)

        @pl.when(row_next < batch)
        def _prefetch():
            for c in copies(row_next, jnp.where(last, 0, kb + 1), 1 - slot):
                c.start()

        for c in copies(b, kb, slot):
            c.wait()
        c_blk, rt_blk = cbuf[slot], rbuf[slot]  # [BK, dc], [dr, BK]
        s = (jax.lax.dot_general(q_c, c_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             + jnp.dot(q_r, rt_blk, preferred_element_type=jnp.float32))
        kpos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= pos, s * scale, NEG_INF)  # [H, BK]
        m_prev = m_ref[0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[0] = alpha * l_ref[0] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[0] = m_new
        pv = jax.lax.dot_general(
            p.astype(c_blk.dtype), c_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0] = o_ref[0] * alpha[:, :1] + pv
        return 1 - slot

    slot_ref[0] = jax.lax.fori_loop(0, hi + 1, block, slot_ref[0])


def latent_decode(
    q_c: jax.Array,  # [B, H, dc]: q_nope through W_kvb's key half
    q_pe: jax.Array,  # [B, H, dr] (already roped)
    c_all: jax.Array,  # [B, 1, S, dc], or stacked [L, B, 1, S, dc]
    r_all: jax.Array,  # [B, 1, S, dr], or stacked
    pos,  # scalar int or [B]
    *,
    scale: float,
    layer=None,  # index into the stacked form's leading axis
    block_k: int = DECODE_BLOCK_K,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single-position absorbed latent attention against the cached rows
    up to ``pos`` inclusive. Returns ``(m [B, H, 1, 1], l [B, H, 1, 1],
    o_c [B, H, 1, dc])``, float32: the scaled scores' row maximum, the
    normalizer ``sum exp(score - m)`` and the un-normalized output ``sum
    exp(score - m) c`` (probabilities rounded to the buffer's type first).

    ``layer``: ``c_all`` / ``r_all`` are the stacked buffers the layer loop
    carries and ``layer`` (traced) picks the layer the blocks are fetched
    from, as a second scalar-prefetch operand; no layer's slab is written
    out for the kernel. The rope half goes in with its last two axes
    swapped, rows last: the chip lays a bfloat16 ``[.., S, dr]`` with ``dr``
    under a lane tile out with the ROWS on the lanes (``{3,4,2,1,0}``), so
    there the swap is a bitcast and a block ``[dr, BK]`` a lane-aligned
    slice, where ``[BK, dr]`` would make the compiler re-lay the whole
    buffer for the call (tests/test_chip_compile.py holds the served
    programs to that). A stream reads ``pos // block_k + 1`` blocks of
    ``block_k`` rows; a slot without a stream goes out at row 0 and reads
    one."""
    b, h, dc = q_c.shape
    dr = q_pe.shape[-1]
    stacked = layer is not None
    assert c_all.ndim == (5 if stacked else 4), (c_all.shape, layer)
    assert c_all.shape[-3] == 1, c_all.shape
    s = c_all.shape[-2]
    bk = _pick_block(s, block_k)
    nk = s // bk
    if interpret is None:
        from cake_tpu.ops.pallas import interpret_default

        interpret = interpret_default()
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    prefetch = [pos_arr]
    if stacked:
        prefetch.append(jnp.asarray(layer, jnp.int32).reshape(1))

    rt_all = jnp.swapaxes(r_all, -1, -2)
    if not interpret:
        # the buffers stay in HBM: left to choose, the compiler moves the
        # rope half into VMEM whole ahead of the call (16 MB a layer at
        # the served shapes), rows nobody reads among them
        c_all = pltpu.with_memory_space_constraint(c_all, pltpu.HBM)
        rt_all = pltpu.with_memory_space_constraint(rt_all, pltpu.HBM)

    def row(width):
        return pl.BlockSpec((1, h, width), lambda i, *prefetched: (i, 0, 0))

    f32 = jnp.float32
    m, l, o_c = pl.pallas_call(
        functools.partial(_kernel, stacked=stacked, batch=b, block_k=bk,
                          scale=scale, num_kv_blocks=nk),
        out_shape=(jax.ShapeDtypeStruct((b, h, _LANES), f32),
                   jax.ShapeDtypeStruct((b, h, _LANES), f32),
                   jax.ShapeDtypeStruct((b, h, dc), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b,),
            in_specs=[row(dc), row(dr),
                      pl.BlockSpec(memory_space=pltpu.HBM),
                      pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=(row(_LANES), row(_LANES), row(dc)),
            scratch_shapes=[
                pltpu.VMEM((2, bk, dc), c_all.dtype),
                pltpu.VMEM((2, dr, bk), r_all.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        # one stream after another: a step waits for the fetch the step
        # before it started
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * s * (2 * dc + dr),
            bytes_accessed=b * s * (dc + dr) * c_all.dtype.itemsize,
            transcendentals=b * h * s,
        ),
        name="latent_decode",
        interpret=interpret,
    )(*prefetch, q_c, q_pe, c_all, rt_all)
    return m[:, :, None, :1], l[:, :, None, :1], o_c[:, :, None]
