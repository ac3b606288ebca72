"""Pallas int8-weight matmul: ``y = (x @ q_int8) * scale`` fused.

The int8 weights stream HBM→VMEM at half the bf16 bytes (the decode
bottleneck), are converted to the activation dtype in VMEM, hit the MXU with
f32 accumulation, and the per-output-channel dequant scale is applied in the
epilogue — the dequantized weights never exist in HBM (the XLA fallback in
:func:`cake_tpu.ops.quant.quant_matmul_xla` relies on convert-into-dot
fusion instead).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pick_block(n: int, preferred: int) -> int:
    b = 1
    while b * 2 <= min(n, preferred) and n % (b * 2) == 0:
        b *= 2
    return b


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, num_k_blocks: int):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

    x = x_ref[:]  # [BM, BK] activation dtype
    w = q_ref[:].astype(x.dtype)  # [BK, BN] int8 -> activation dtype in VMEM
    acc_ref[:] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(kb == num_k_blocks - 1)
    def _finish():
        o_ref[:] = (acc_ref[:] * s_ref[:]).astype(o_ref.dtype)


def quant_matmul_pallas(
    x: jax.Array,  # [M, K]
    q: jax.Array,  # [K, N] int8
    scale: jax.Array,  # [N] f32
    *,
    block_m: int = 256,
    block_n: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused int8-weight matmul with per-channel dequant epilogue."""
    m, k = x.shape
    n = q.shape[1]
    bm = _pick_block(m, block_m)
    bn = _pick_block(n, block_n)
    bk = _pick_block(k, block_k)
    if interpret is None:
        from cake_tpu.ops.pallas import interpret_default

        interpret = interpret_default()

    out = pl.pallas_call(
        functools.partial(_kernel, num_k_blocks=k // bk),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kb: (i, kb)),
            pl.BlockSpec((bk, bn), lambda i, j, kb: (kb, j)),
            pl.BlockSpec((1, bn), lambda i, j, kb: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kb: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * k,
            bytes_accessed=m * k * x.dtype.itemsize + k * n + m * n * x.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(x, q, scale.reshape(1, n).astype(jnp.float32))
    return out


def _kernel4(
    xlo_ref, xhi_ref, qp_ref, s_ref, o_ref, acc_ref, *,
    num_k_blocks: int, grouped: bool, blocks_per_group: int,
    unpack: str = "int32",
):
    """Packed-int4 matmul kernel. ``grouped`` is a Python static: per-channel
    applies the scale once in the epilogue; grouped multiplies each K
    block's f32 partial by its group's scale before accumulating (every K
    block lies inside one group — bk2 divides group_size/2) — same math as
    the grouped XLA einsum path up to f32 summation order.

    Grouped ``s_ref`` holds the FULL ``[ngroups, BN]`` scale column: a
    per-K-block scale BlockSpec would need a (1, BN) block over the group
    axis, which Mosaic rejects whenever ngroups isn't the whole axis (the
    sublane-divisibility rule — caught on real v5e, r4). The kernel
    dynamically indexes its group's row instead; scales are tiny, so
    re-fetching the column per N block costs nothing."""
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

    x_lo = xlo_ref[:]  # [BM, BK2] activation dtype (even K rows)
    x_hi = xhi_ref[:]  # [BM, BK2] (odd K rows)
    # Unpack both nibbles of the SAME packed block (adjacent-pair layout,
    # ops/quant.py:pack_int4). The shift width is a tunable (`unpack`):
    # int32 is the VPU's native lane width; int16 halves the unpacked
    # temporary's VMEM footprint at skinny M where the [BK2, BN] weight
    # temporaries dominate VMEM — tools/int4_sweep.py measures which wins
    # per shape. The int8 bytes are what streamed from HBM either way.
    if unpack == "int16":
        p = qp_ref[:].astype(jnp.int16)  # [BK2, BN]
        w_lo = ((p << 12) >> 12).astype(x_lo.dtype)
    else:
        p = qp_ref[:].astype(jnp.int32)  # [BK2, BN]
        w_lo = ((p << 28) >> 28).astype(x_lo.dtype)
    w_hi = (p >> 4).astype(x_lo.dtype)
    partial = jax.lax.dot_general(
        x_lo, w_lo, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) + jax.lax.dot_general(
        x_hi, w_hi, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    if grouped:
        s_row = s_ref[pl.ds(kb // blocks_per_group, 1), :]  # [1, BN]
        acc_ref[:] += partial * s_row
    else:
        acc_ref[:] += partial

    @pl.when(kb == num_k_blocks - 1)
    def _finish():
        if grouped:
            o_ref[:] = acc_ref[:].astype(o_ref.dtype)
        else:
            o_ref[:] = (acc_ref[:] * s_ref[:]).astype(o_ref.dtype)


def _sublane(dtype) -> int:
    """Minimum second-to-last tile dim for ``dtype`` on TPU."""
    return {2: 16, 4: 8}.get(jnp.dtype(dtype).itemsize, 32)


def quant4_matmul_pallas(
    x: jax.Array,  # [M, K]
    qp: jax.Array,  # [K/2, N] int8 packed (two int4 per byte)
    scale: jax.Array,  # [N] f32 per-channel, or [ngroups, N] grouped
    *,
    block_m: int = 256,
    block_n: int = 512,
    block_k: int = 512,
    unpack: str = "int32",
    skinny_widen: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused packed-int4 matmul: quarter the bf16 weight bytes from HBM.

    ``skinny_widen=False`` disables the skinny-M block widening so an
    explicit ``block_n``/``block_k`` is honored verbatim (modulo divisor
    clamping) — tools/int4_sweep.py uses it to measure the sub-1024
    configs the default policy would silently override.

    ``y = (x[:, 0::2] @ lo(qp) + x[:, 1::2] @ hi(qp)) * scale`` with the
    even/odd activation slices materialized OUTSIDE the kernel (M x K/2
    each, activation-sized), so the K-axis grid walks packed weight rows
    directly and the weight side never strides or interleaves. A grouped
    ``scale [ngroups, N]`` caps the K block at half a group and applies
    each group's scale to its own f32 partial.

    Decode (skinny M): M below the dtype sublane is zero-padded up to it —
    a sub-sublane block would make Mosaic mask every weight tile, and the
    padded rows cost only activation-sized traffic. The weight stream (the
    bandwidth bound) is unchanged, so the kernel's win over the XLA
    fallback (which re-materializes bf16 weights every step, 4x the bytes)
    holds at M=1; blocks are widened in the skinny regime to amortize
    per-grid-step overhead over the ~0.5 byte/weight stream."""
    m, k = x.shape
    k2, n = qp.shape
    if k != 2 * k2:
        raise ValueError(f"x in-dim {k} != 2 * packed rows {k2}")
    if unpack not in ("int32", "int16"):
        raise ValueError(f"unpack must be 'int32' or 'int16', got {unpack!r}")
    grouped = scale.ndim == 2
    pad_m = 0
    sub = _sublane(x.dtype)
    if m < sub:
        pad_m = sub - m
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
        m = sub
    if m <= 32 and skinny_widen:
        # skinny regime: fewer, larger grid steps (weights dominate VMEM
        # and HBM; the activation block is tiny either way)
        block_n = max(block_n, 1024)
        block_k = max(block_k, 1024)
    bm = _pick_block(m, block_m)
    bn = _pick_block(n, block_n)
    if grouped:
        g2 = k2 // scale.shape[0]  # packed rows per group
        bk2 = _pick_block(g2, block_k)
    else:
        g2 = k2
        bk2 = _pick_block(k2, block_k)
    if interpret is None:
        from cake_tpu.ops.pallas import interpret_default

        interpret = interpret_default()

    s_in = (
        scale.astype(jnp.float32)
        if grouped
        else scale.reshape(1, n).astype(jnp.float32)
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel4,
            num_k_blocks=k2 // bk2,
            grouped=grouped,
            blocks_per_group=g2 // bk2,
            unpack=unpack,
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid=(m // bm, n // bn, k2 // bk2),
        in_specs=[
            pl.BlockSpec((bm, bk2), lambda i, j, kb: (i, kb)),
            pl.BlockSpec((bm, bk2), lambda i, j, kb: (i, kb)),
            pl.BlockSpec((bk2, bn), lambda i, j, kb: (kb, j)),
            # grouped: the whole group axis rides in the block (a (1, bn)
            # block over it fails Mosaic's sublane rule on real TPUs); the
            # kernel picks its row. Per-channel: scale is [1, n].
            pl.BlockSpec(
                (s_in.shape[0], bn), lambda i, j, kb: (0, j)
            ),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kb: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * k,
            bytes_accessed=m * k * x.dtype.itemsize
            + k2 * n
            + m * n * x.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(
        x[:, 0::2],
        x[:, 1::2],
        qp,
        s_in,
    )
    return out[: m - pad_m] if pad_m else out
