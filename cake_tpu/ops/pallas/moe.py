"""Pallas TPU grouped matmul for the expert block's sorted form.

``out[r] = lhs[r] @ rhs[group of r]`` where the rows of ``lhs`` are sorted
by group and each group's rows are contiguous (``megablox``'s problem;
:func:`cake_tpu.ops.moe.moe_swiglu` sorts a call's (row, chosen expert)
pairs by expert: an admission's, or a decode step's where few experts are
hit). Only the row tiles a group touches are visited: the grid's second
axis runs over ``(group, row tile)`` *visits*, whose number is data (a
dynamic grid bound); rows past the last group's are never computed, and a
group without a row is never visited, so its matrix is never read (what a
decode step of 32 rows x top-8 over 512 scored experts gains: 0.39 of the
held experts have a row).

What differs from ``jax.experimental.pallas.ops.tpu.megablox.gmm``:

- **the expert stacks are read where they lie.** ``rhs`` may be the whole
  ``[L, E, K, N]`` stack the layer loop closes over, with the layer's
  index as a prefetched scalar (as ``flash_decode`` takes the carried
  cache): the block's index map picks ``(layer, group)``, so no layer's
  slice is written out before the call.
- **int8 stacks stream as int8.** The block is converted to the
  activations' type in VMEM and the per-channel scale multiplies the
  output tile; no dequantised copy of a stack exists anywhere.
- **a group's weights are fetched once.** A block holds the whole
  contraction (``K``) of ``block_n`` output columns, and the visits of one
  group are consecutive, so the pipeline re-uses the block it holds (and
  an int8 block's converted form, kept in scratch) for every row tile of
  the group: weights cost one read however many tiles a group spans,
  which is what lets the row tile be small (few rows an expert is the
  rule: 128 at Mixtral's top-2 of 8 over 512 rows, 8 at 128 held of 512).
  ``ROW_TILE`` 128 at every row count: at 8-256 rows tiles of 16-64 are
  within 1% of it over bf16 stacks (``tools/moe_sweep.py --row-tile``, my
  chip runs, PR 35; int8 stacks would take 64 under 256 rows, 3.34x the
  dense form against 3.00x at 128 rows: PERF.md section 7).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 128
# an rhs block's bytes as stored (a second one is in flight, and an int8
# block's converted copy is twice its size)
BLOCK_BYTES = 4 * 2**20
VMEM_LIMIT = 96 * 2**20


class GroupTiles(NamedTuple):
    """The visits of a grouped matmul over ``m`` sorted rows in tiles of
    ``tm``: visit ``v`` multiplies row tile ``tile[v]`` by group
    ``group[v]``'s matrix and keeps the rows inside ``[offsets[g],
    offsets[g + 1])``. ``count`` visits are real (int32 ``[1]``)."""

    offsets: jax.Array  # [E + 1]
    group: jax.Array  # [m // tm + E - 1]
    tile: jax.Array
    count: jax.Array


def group_tiles(group_sizes: jax.Array, m: int, tm: int = ROW_TILE
                ) -> GroupTiles:
    """Visits in group order, a group's tiles ascending: consecutive
    visits of one row tile (two groups that share it) and of one group
    are adjacent, which the kernel's block re-use rests on. An empty
    group has no visit."""
    assert m % tm == 0, (m, tm)
    e = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    visits = jnp.arange(m // tm + e - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.searchsorted(visit_ends, visits, side="right"), e - 1
    ).astype(jnp.int32)
    tile = first[group] + visits - (visit_ends - tiles)[group]
    return GroupTiles(
        jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]), group,
        jnp.clip(tile, 0, m // tm - 1).astype(jnp.int32),
        visit_ends[-1:].astype(jnp.int32))


def _block_n(k: int, n: int, itemsize: int) -> int:
    """Output columns a block: the most whole lanes (128) that divide
    ``n`` and keep a ``[k, block]`` block within ``BLOCK_BYTES``."""
    if n % 128:
        return n
    best = 128
    for bn in range(128, n + 1, 128):
        if n % bn == 0 and k * bn * itemsize <= BLOCK_BYTES:
            best = bn
    return best


def _kernel(layer_ref, offsets_ref, group_ref, tile_ref, lhs_ref, rhs_ref,
            *rest, tm: int, quantized: bool):
    del layer_ref  # the index maps'
    v = pl.program_id(1)
    g = group_ref[v]
    if quantized:
        scale_ref, out_ref, w_ref = rest

        # a group's visits are consecutive: convert its block once
        @pl.when((v == 0) | (g != group_ref[jnp.maximum(v - 1, 0)]))
        def _convert():
            w_ref[...] = rhs_ref[...].astype(w_ref.dtype)

        acc = jnp.dot(lhs_ref[...], w_ref[...],
                      preferred_element_type=jnp.float32)
        acc = acc * scale_ref[pl.ds(g, 1), :]
    else:
        (out_ref,) = rest
        acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                      preferred_element_type=jnp.float32)
    rows = tile_ref[v] * tm + jax.lax.broadcasted_iota(
        jnp.int32, acc.shape, 0)
    mine = (rows >= offsets_ref[g]) & (rows < offsets_ref[g + 1])
    # a tile two groups share is visited by one after the other and stays
    # in VMEM between: each keeps the other's rows
    out_ref[...] = jnp.where(
        mine, acc, out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def grouped_matmul(lhs, rhs, tiles: GroupTiles, *, layer=None, scale=None,
                   out_dtype=None, tm: int = ROW_TILE,
                   block_n: int | None = None,
                   interpret: bool | None = None):
    """``lhs [M, K]`` (rows sorted by group, ``M % tm == 0``) times ``rhs
    [E, K, N]``, or ``[L, E, K, N]`` with ``layer`` (traced) picking the
    layer; ``scale [(L,) E, N]`` float32 makes ``rhs`` int8 with a scale
    per output channel. Returns ``[M, N]`` in ``out_dtype`` (``lhs``'s).
    Rows past the last group's end are NOT written: mask them."""
    m, k = lhs.shape
    if rhs.ndim == 3:
        rhs, layer = rhs[None], 0
        scale = None if scale is None else scale[None]
    e, n = rhs.shape[1], rhs.shape[3]
    assert rhs.shape[2] == k and m % tm == 0, (lhs.shape, rhs.shape, tm)
    quantized = scale is not None
    bn = block_n or _block_n(k, n, rhs.dtype.itemsize)
    assert n % bn == 0, (n, bn)
    if interpret is None:
        from cake_tpu.ops.pallas import interpret_default

        interpret = interpret_default()
    out_dtype = out_dtype or lhs.dtype

    # index maps see the grid's indices (column block, visit), then the
    # prefetched scalars (layer, offsets, group, tile)
    def lhs_map(j, v, layer, offsets, group, tile):
        return tile[v], 0

    def rhs_map(j, v, layer, offsets, group, tile):
        return layer[0], group[v], 0, j

    def scale_map(j, v, layer, offsets, group, tile):
        return layer[0], 0, j

    def out_map(j, v, layer, offsets, group, tile):
        return tile[v], j

    in_specs = [pl.BlockSpec((tm, k), lhs_map),
                pl.BlockSpec((None, None, k, bn), rhs_map)]
    operands = [lhs, rhs]
    scratch = []
    if quantized:
        in_specs.append(pl.BlockSpec((None, e, bn), scale_map))
        operands.append(scale.astype(jnp.float32))
        scratch.append(pltpu.VMEM((k, bn), lhs.dtype))
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, quantized=quantized),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // bn, tiles.count[0]),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tm, bn), out_map),
            scratch_shapes=scratch,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n,
            bytes_accessed=grouped_matmul_bytes(
                m, k, n, e, rhs.dtype.itemsize, lhs.dtype.itemsize,
                jnp.dtype(out_dtype).itemsize, bn),
            transcendentals=0),
        name="moe_grouped_matmul",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), tiles.offsets, tiles.group,
      tiles.tile, *operands)


def grouped_matmul_bytes(m: int, k: int, n: int, e: int, w_itemsize: int,
                         x_itemsize: int = 2, out_itemsize: int = 2,
                         block_n: int | None = None) -> int:
    """Bytes one call moves when every group has rows: each matrix once,
    the rows once for each block of output columns, the result once."""
    bn = block_n or _block_n(k, n, w_itemsize)
    return (e * k * n * w_itemsize + (n // bn) * m * k * x_itemsize
            + m * n * out_itemsize)
