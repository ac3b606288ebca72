"""EVA attention's decode step as a Pallas TPU kernel: ONE softmax over two
buffers of different grain, each read to its own frontier.

A stream's query attends the live rows of its window's ring (rows ``0 ..
at``, ``at = p % W``: the window resets, so the live rows are a prefix) and
the summary rows of the windows completed before it (rows ``0 .. visible -
1`` of the summary plane, ``visible = (p // W) * (W // C)``). Both buffers
stay in HBM, the stacked ``[L, B, H, rows, D]`` arrays the layer loop
carries, and the kernel walks, stream after stream, the ring's blocks up
to ``at`` and then the plane's blocks up to ``visible`` under one running
maximum, sum and accumulator, double-buffered across the change of buffer
and of stream: a row nobody can see is neither fetched nor computed, and
nothing is concatenated or written out. The walk is
:func:`cake_tpu.ops.pallas.flash.flash_decode`'s batched form (one query row
a head: the heads' products one batched call a block) with a second source.

:func:`eva_block_counts` is THE definition of what is fetched: the kernel
walks it and the engine's ``attn.eva_rows_read`` counter sums it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cake_tpu.ops.pallas.flash import _LANES, NEG_INF

# Rows of a block of either buffer (all heads of a stream at once). One
# query row a head makes a block's products cheap beside its fetch at any
# size, so the shortest block, which skips most, wins (flash_decode's
# ``ONE_ROW_BLOCK_K``, the same row of heads); 32 heads of 128 in bfloat16
# are 1 MiB a block, 4 MiB double-buffered for keys and values. On a v5e
# (my chip run, PR 66: 16 streams, 32 heads of 128, a ring of 2048 and a
# plane of 1024 rows, bfloat16; us a layer inside 16 walks over 8 layers,
# this kernel at 128 | 256 rows a block, then XLA's two masked products
# over both buffers whole, merged by their statistics):
#
# - frontiers as ``agent-long`` draws them (positions 4096-15360: 24,204
#   of the 49,152 held rows attended): 560 | 599, XLA 1154 (2.06x; the
#   attended rows' bytes at 708 GB/s of the chip's 819);
# - right after a reset, 4 windows behind (8,208 rows): 233 | 278, XLA 1153;
# - every window full, 7 behind (47,104 rows: the cost side, nothing to
#   skip): 1033 | 1078, XLA 1153 (0.90x of XLA's time).
EVA_BLOCK_K = 128


def eva_block_counts(at, visible, block_k: int = EVA_BLOCK_K, xp=jnp):
    """(ring blocks, summary blocks) the kernel fetches for a stream whose
    newest ring row is ``at`` and which sees ``visible`` summary rows: the
    ring's blocks up to and with ``at``'s, the plane's up to the last
    visible row's, none where no window is complete."""
    return at // block_k + 1, (visible + block_k - 1) // block_k


def _kernel(
    at_ref,  # [B] int32: the newest ring row of each stream (p % W)
    vis_ref,  # [B] int32: summary rows each stream sees
    layer_ref,  # [1] int32
    q_ref,  # [B, H, G, D] (VMEM)
    rk_hbm, rv_hbm,  # the rings [L, B, H, W, D], left where they are
    sk_hbm, sv_hbm,  # the summary planes [L, B, H, S // C, D]
    o_ref,  # [B, H, G, D]
    kbuf, vbuf,  # VMEM [2, H, BK, D]
    sem,  # DMA [2, 2]
    acc_ref,  # VMEM [H, G, D] f32
    m_ref, l_ref,  # VMEM [H, G, LANES] f32
    *,
    batch: int,
    block_k: int,
    scale: float,
):
    layer = layer_ref[0]

    def counts(b):
        return eva_block_counts(at_ref[b], vis_ref[b], block_k)

    def copies(k_hbm, v_hbm, b, kb, slot):
        rows = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
        at = (layer, b, slice(None), rows, slice(None))
        return (pltpu.make_async_copy(k_hbm.at[at], kbuf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[at], vbuf.at[slot],
                                      sem.at[1, slot]))

    def each(b, j, slot, act):
        """``act`` on the two copies of stream ``b``'s item ``j``: a ring
        block while ``j`` counts the ring's, a summary block behind them."""
        n_ring, _ = counts(b)

        @pl.when(j < n_ring)
        def _ring():
            for c in copies(rk_hbm, rv_hbm, b, j, slot):
                act(c)

        @pl.when(j >= n_ring)
        def _summary():
            for c in copies(sk_hbm, sv_hbm, b, j - n_ring, slot):
                act(c)

    def step(carry):
        b, j, slot = carry
        n_ring, n_sum = counts(b)
        last = j == n_ring + n_sum - 1
        b_next = jnp.where(last, b + 1, b)
        j_next = jnp.where(last, 0, j + 1)

        @pl.when(b_next < batch)
        def _prefetch():
            each(jnp.minimum(b_next, batch - 1), j_next, 1 - slot,
                 lambda c: c.start())

        @pl.when(j == 0)
        def _init():
            m_ref[:] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
            l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

        each(b, j, slot, lambda c: c.wait())
        # a ring row is live up to and with ``at``, a summary row below
        # ``visible``
        in_ring = j < n_ring
        first = jnp.where(in_ring, j, j - n_ring) * block_k
        limit = jnp.where(in_ring, at_ref[b] + 1, vis_ref[b])
        group = q_ref.shape[2]
        row = first + jax.lax.broadcasted_iota(
            jnp.int32, (group, block_k), 1)
        mask = row < limit
        k = kbuf[slot]  # [H, BK, D]
        v = vbuf[slot]
        s = jax.lax.dot_general(
            q_ref[b], k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        s = jnp.where(mask, s * scale, NEG_INF)  # [H, G, BK]
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :, :1])
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=2, keepdims=True)
        m_ref[:] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha[:, :, :1] + pv

        @pl.when(last)
        def _finish():
            o_ref[b] = (acc_ref[:] / l_ref[:, :, :1]).astype(o_ref.dtype)

        return b_next, j_next, 1 - slot

    each(0, 0, 0, lambda c: c.start())
    jax.lax.while_loop(lambda c: c[0] < batch, step,
                       (jnp.int32(0), jnp.int32(0), jnp.int32(0)))


def eva_decode(
    q: jax.Array,  # [B, H, 1, D] (rotated)
    ring_k: jax.Array,  # [L, B, H, W, D]: the carried rings
    ring_v: jax.Array,
    sum_k: jax.Array,  # [L, B, H, S // C, D]: the carried summary planes
    sum_v: jax.Array,
    at,  # [B]: each stream's newest ring row (written already)
    visible,  # [B]: summary rows each stream sees
    layer,  # index into the leading axis of all four
    *,
    block_k: int = EVA_BLOCK_K,
    interpret: bool | None = None,
) -> jax.Array:
    """One token's EVA attention a stream: softmax over ring rows ``0 ..
    at`` and summary rows ``0 .. visible - 1`` together. Returns ``[B, H,
    1, D]``. The four buffers stay in HBM; the kernel fetches of each the
    ``block_k``-row blocks up to its own frontier (:func:`eva_block_counts`),
    all heads of a block at once."""
    b, h, t, d = q.shape
    assert t == 1, "eva_decode requires T == 1"
    assert ring_k.ndim == 5 and ring_k.shape[2] == h, (ring_k.shape, h)
    w, rows = ring_k.shape[3], sum_k.shape[3]
    assert w % block_k == 0 and rows % block_k == 0, (w, rows, block_k)
    if interpret is None:
        from cake_tpu.ops.pallas import interpret_default

        interpret = interpret_default()

    def prefetch(x):
        return jnp.broadcast_to(jnp.asarray(x, jnp.int32).reshape(-1), (b,))

    def whole(i, *prefetched):
        return (0, 0, 0, 0)

    block = (2, h, block_k, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[pl.BlockSpec((b, h, 1, d), whole)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * 4,
        out_specs=pl.BlockSpec((b, h, 1, d), whole),
        scratch_shapes=[
            pltpu.VMEM(block, ring_k.dtype),
            pltpu.VMEM(block, ring_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((h, 1, d), jnp.float32),
            pltpu.VMEM((h, 1, _LANES), jnp.float32),
            pltpu.VMEM((h, 1, _LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, batch=b, block_k=block_k,
                               scale=1.0 / math.sqrt(d))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * (w + rows) * d,
            bytes_accessed=2 * b * h * (w + rows) * d
            * ring_k.dtype.itemsize,
            transcendentals=b * h * (w + rows),
        ),
        name="eva_decode",
        interpret=interpret,
    )(prefetch(at), prefetch(visible),
      jnp.asarray(layer, jnp.int32).reshape(1), q, ring_k, ring_v, sum_k,
      sum_v)
