"""Pallas TPU kernels for the expert block's sorted form.

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

Four calls make a sorted expert block, and none of them reads or writes a
row tile past the LIVE ones (the grouped rows are the leading ones, so
the live tiles are ``0 .. GroupTiles.live - 1``: on a chip that holds one
share in sixteen of the scored experts, a sixteenth of the pairs):

- :func:`gather_rows`: the live tiles' rows out of ``[N, H]``, by a
  one-hot product where ``N`` is small and by address where it is not;
- :func:`grouped_swiglu`: gate, up and the SwiGLU in one call (one fetch
  of a row tile serves both products; nothing is rounded between the
  float32 products and the SwiGLU; no ``[N*k, F]`` float32 leaves it);
- :func:`grouped_matmul`: the down product, left in float32;
- :func:`combine_rows`: each grouped row added under its routing weight
  into its token's row, in float32.

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
# combine_rows' float32 sums and its two output buffers, a block of columns
COMBINE_BYTES = 24 * 2**20
VMEM_LIMIT = 96 * 2**20
# a block of a 32-bit vector held in scalar memory (the chip lays such an
# operand out in tiles of 1024)
SMEM_BLOCK = 1024
# copies of a fetched row started (and waited for) a loop step
FETCH_UNROLL = 8
# rows of the bucket a step of the pass that re-lays it as words
FETCH_PACK_ROWS = 256


class GroupTiles(NamedTuple):
    """The visits of a grouped matmul over ``m`` sorted rows in tiles of
    ``tm``: visit ``v`` multiplies row tile ``tile[v]`` by group
    ``group[v]``'s matrix and keeps the rows inside ``[offsets[g],
    offsets[g + 1])``. ``count`` visits are real (int32 ``[1]``)."""

    offsets: jax.Array  # [E + 1]
    group: jax.Array  # [m // tm + E - 1]
    tile: jax.Array
    count: jax.Array
    # row tiles that hold a grouped row: 0 .. live - 1 (int32 [1]; the
    # groups' rows are the first ``offsets[-1]`` of the sorted ones)
    live: jax.Array


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
        visit_ends[-1:].astype(jnp.int32),
        ((ends[-1:] + tm - 1) // tm).astype(jnp.int32))


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


def _masked_store(out_ref, acc, offsets_ref, g, tile, tm: int):
    """Keep ``acc``'s rows that are group ``g``'s; a tile two groups share
    is visited by one after the other and stays in VMEM between: each
    keeps the other's rows."""
    rows = tile * tm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
    mine = (rows >= offsets_ref[g]) & (rows < offsets_ref[g + 1])
    out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), out_ref[...])


def _first_visit(group_ref, v):
    """A group's visits are consecutive: is this its first?"""
    return (v == 0) | (group_ref[v] != group_ref[jnp.maximum(v - 1, 0)])


def _kernel(layer_ref, offsets_ref, group_ref, tile_ref, lhs_ref, rhs_ref,
            *rest, tm: int, quantized: bool):
    del layer_ref  # the index maps'
    v = pl.program_id(1)
    g = group_ref[v]
    if quantized:
        scale_ref, out_ref, w_ref = rest

        @pl.when(_first_visit(group_ref, v))
        def _convert():
            w_ref[...] = rhs_ref[...].astype(w_ref.dtype)

        acc = jnp.dot(lhs_ref[...], w_ref[...],
                      preferred_element_type=jnp.float32)
        acc = acc * scale_ref[pl.ds(g, 1), :]
    else:
        (out_ref,) = rest
        acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                      preferred_element_type=jnp.float32)
    _masked_store(out_ref, acc, offsets_ref, g, tile_ref[v], tm)


def _swiglu_kernel(layer_ref, offsets_ref, group_ref, tile_ref, lhs_ref,
                   gate_ref, up_ref, *rest, tm: int, quantized: bool):
    del layer_ref  # the index maps'
    v = pl.program_id(1)
    g = group_ref[v]
    lhs = lhs_ref[...]
    if quantized:
        gate_scale_ref, up_scale_ref, out_ref, wg_ref, wu_ref = rest

        @pl.when(_first_visit(group_ref, v))
        def _convert():
            wg_ref[...] = gate_ref[...].astype(wg_ref.dtype)
            wu_ref[...] = up_ref[...].astype(wu_ref.dtype)

        gate = jnp.dot(lhs, wg_ref[...], preferred_element_type=jnp.float32)
        gate = gate * gate_scale_ref[pl.ds(g, 1), :]
        up = jnp.dot(lhs, wu_ref[...], preferred_element_type=jnp.float32)
        up = up * up_scale_ref[pl.ds(g, 1), :]
    else:
        (out_ref,) = rest
        gate = jnp.dot(lhs, gate_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(lhs, up_ref[...], preferred_element_type=jnp.float32)
    # both products and the SwiGLU in float32: ONE rounding, of its result
    _masked_store(out_ref, jax.nn.silu(gate) * up, offsets_ref, g,
                  tile_ref[v], tm)


def _stacked(rhs, scale, layer):
    """``rhs``, its scale and the layer's index as a ``[L, E, K, N]``
    stack's (a lone layer's ``[E, K, N]`` is a stack of one)."""
    if rhs.ndim == 3:
        rhs, layer = rhs[None], 0
        scale = None if scale is None else scale[None]
    return rhs, scale, jnp.asarray(layer, jnp.int32).reshape(1)


def _interpret(interpret: bool | None) -> bool:
    if interpret is None:
        from cake_tpu.ops.pallas import interpret_default

        interpret = interpret_default()
    return interpret


# index maps see the grid's indices (column block, visit), then the
# prefetched scalars (layer, offsets, group, tile)
def _lhs_map(j, v, layer, offsets, group, tile):
    return tile[v], 0


def _rhs_map(j, v, layer, offsets, group, tile):
    return layer[0], group[v], 0, j


def _scale_map(j, v, layer, offsets, group, tile):
    return layer[0], 0, j


def _out_map(j, v, layer, offsets, group, tile):
    return tile[v], j


def _grouped_call(kernel, name, lhs, stacks, scales, layer, tiles, n, bn,
                  out_dtype, tm, cost, interpret):
    """One call over the ``(column block, visit)`` grid: ``lhs``'s row
    tile against the visit's group's ``[K, bn]`` block of each of
    ``stacks`` (int8 with ``scales``: a converted block each in scratch)."""
    m, k = lhs.shape
    e = stacks[0].shape[1]
    in_specs = [pl.BlockSpec((tm, k), _lhs_map)]
    in_specs += [pl.BlockSpec((None, None, k, bn), _rhs_map)] * len(stacks)
    in_specs += [pl.BlockSpec((None, e, bn), _scale_map)] * len(scales)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // bn, tiles.count[0]),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tm, bn), _out_map),
            scratch_shapes=[pltpu.VMEM((k, bn), lhs.dtype)] * len(scales),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=cost,
        name=name,
        interpret=interpret,
    )(layer, tiles.offsets, tiles.group, tiles.tile, lhs, *stacks,
      *[s.astype(jnp.float32) for s in scales])


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
    rhs, scale, layer = _stacked(rhs, scale, layer)
    e, n = rhs.shape[1], rhs.shape[3]
    assert rhs.shape[2] == k and m % tm == 0, (lhs.shape, rhs.shape, tm)
    bn = block_n or _block_n(k, n, rhs.dtype.itemsize)
    assert n % bn == 0, (n, bn)
    out_dtype = out_dtype or lhs.dtype
    return _grouped_call(
        functools.partial(_kernel, tm=tm, quantized=scale is not None),
        "moe_grouped_matmul", lhs, [rhs], [] if scale is None else [scale],
        layer, tiles, n, bn, out_dtype, tm,
        pl.CostEstimate(
            flops=2 * m * k * n,
            bytes_accessed=grouped_matmul_bytes(
                m, k, n, e, rhs.dtype.itemsize, lhs.dtype.itemsize,
                jnp.dtype(out_dtype).itemsize, bn),
            transcendentals=0),
        _interpret(interpret))


def grouped_swiglu(lhs, gate, up, tiles: GroupTiles, *, layer=None,
                   gate_scale=None, up_scale=None, tm: int = ROW_TILE,
                   block_n: int | None = None,
                   interpret: bool | None = None):
    """``silu(lhs @ gate[group]) * (lhs @ up[group])`` in ONE call: a
    visit takes the group's gate block and up block at the same columns,
    makes both products in float32 over one fetched row tile, and writes
    the SwiGLU's result rounded ONCE, to ``lhs``'s type. Operands as
    :func:`grouped_matmul`'s (``gate`` and ``up`` ``[(L,) E, K, N]`` of
    one shape and type). Returns ``[M, N]``; rows past the last group's
    end are NOT written."""
    m, k = lhs.shape
    gate, gate_scale, layer = _stacked(gate, gate_scale, layer)
    up, up_scale, _ = _stacked(up, up_scale, 0)
    e, n = gate.shape[1], gate.shape[3]
    assert gate.shape == up.shape and gate.dtype == up.dtype
    assert gate.shape[2] == k and m % tm == 0, (lhs.shape, gate.shape, tm)
    assert (gate_scale is None) == (up_scale is None)
    bn = block_n or _block_n(k, n, gate.dtype.itemsize)
    assert n % bn == 0, (n, bn)
    quantized = gate_scale is not None
    return _grouped_call(
        functools.partial(_swiglu_kernel, tm=tm, quantized=quantized),
        "moe_grouped_swiglu", lhs, [gate, up],
        [gate_scale, up_scale] if quantized else [],
        layer, tiles, n, bn, lhs.dtype, tm,
        pl.CostEstimate(
            flops=4 * m * k * n,
            bytes_accessed=grouped_swiglu_bytes(
                m, k, n, e, gate.dtype.itemsize, lhs.dtype.itemsize, bn),
            transcendentals=m * n),
        _interpret(interpret))


def _gather_kernel(live_ref, token_ref, x_ref, out_ref):
    del live_ref  # the grid's bound
    # a one-hot product on the MXU: exact (one 1.0 a row), and a row of
    # ``x`` is read where it lies in VMEM
    cols = jax.lax.broadcasted_iota(
        jnp.int32, (out_ref.shape[0], x_ref.shape[0]), 1)
    pick = (cols == token_ref[...]).astype(x_ref.dtype)
    out_ref[...] = jnp.dot(
        pick, x_ref[...], preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST
                   if x_ref.dtype == jnp.float32 else None),
    ).astype(out_ref.dtype)


def rows_fetchable(h: int, dtype) -> bool:
    """Can a row of ``[N, h]`` be fetched by address (:func:`gather_rows`'s
    ``fetch`` form)? Where it is a whole number of the chip's ``(8, 128)``
    tiles of 32-bit words (a copy out of a tiled array takes aligned
    slices alone): ``h`` a multiple of 2048 in bfloat16 (2048, 6144; not
    2560's 10 rows of words nor 7168's 28) and of 1024 in float32."""
    itemsize = jnp.dtype(dtype).itemsize
    return itemsize in (2, 4) and not h * itemsize % (8 * 128 * 4)


def _row_span(x) -> int:
    """Rows of 128 32-bit words a row of ``x [N, H]`` makes."""
    return x.shape[1] * x.dtype.itemsize // (128 * 4)


def _words_kernel(x_ref, out_ref, *, span: int):
    rows, h = x_ref.shape
    for s in range(span):
        at = pl.ds(s * 128, 128)
        if x_ref.dtype.itemsize == 4:
            words = jax.lax.bitcast_convert_type(x_ref[:, at], jnp.uint32)
        else:
            low = jax.lax.bitcast_convert_type(x_ref[:, at], jnp.uint16)
            high = jax.lax.bitcast_convert_type(
                x_ref[:, pl.ds(h // 2 + s * 128, 128)], jnp.uint16)
            words = (low.astype(jnp.uint32)
                     | (high.astype(jnp.uint32) << 16))
        out_ref[pl.ds(s, rows, stride=span), :] = words


def _row_words(x, interpret: bool):
    """``x [N, H]`` as 32-bit words, a token's row ``span = H * itemsize /
    512`` consecutive rows of 128 (whole tiles: what a copy by address
    takes): the bits themselves in float32; in bfloat16 element ``j`` in a
    word's low half and ``j + H/2`` in its high half, so that 128
    consecutive elements of a row lie in each half of a row of words. One
    pass over ``x`` at the memory's rate (XLA's own convert, shift and
    reshape took five times as long: PERF.md section 6, PR 63)."""
    n, h = x.shape
    span = _row_span(x)
    rows = min(n, FETCH_PACK_ROWS)
    return pl.pallas_call(
        functools.partial(_words_kernel, span=span),
        out_shape=jax.ShapeDtypeStruct((n * span, 128), jnp.uint32),
        grid=(pl.cdiv(n, rows),),
        in_specs=[pl.BlockSpec((rows, h), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows * span, 128), lambda i: (i, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=0, bytes_accessed=2 * n * h * x.dtype.itemsize,
            transcendentals=0),
        name="moe_row_words",
        interpret=interpret,
    )(x)


def _fetch_kernel(live_ref, token_ref, words_hbm, out_ref, buf, sem, *,
                  tm: int, span: int):
    """Grid step ``t`` writes live tile ``t``: its ``tm`` rows, ``span``
    rows of words each, come by one copy a row out of ``words_hbm`` (left
    where it is) into half ``t % 2`` of ``buf``; the next tile's copies are
    started before this tile's are waited for."""
    t = pl.program_id(0)
    slot = t % 2

    def copies(tile, slot, act):
        def some(i, carry):
            for j in range(FETCH_UNROLL):
                r = i * FETCH_UNROLL + j
                at = pl.multiple_of(token_ref[tile * tm + r] * span, 8)
                act(pltpu.make_async_copy(
                    words_hbm.at[pl.ds(at, span)],
                    buf.at[slot, pl.ds(pl.multiple_of(r * span, 8), span)],
                    sem.at[slot]))
            return carry

        jax.lax.fori_loop(0, tm // FETCH_UNROLL, some, 0)

    @pl.when(t == 0)
    def _first():
        copies(0, 0, lambda copy: copy.start())

    @pl.when(t + 1 < live_ref[0])
    def _ahead():
        copies(t + 1, 1 - slot, lambda copy: copy.start())

    copies(t, slot, lambda copy: copy.wait())
    half = out_ref.shape[1] // 2
    for s in range(span):
        # row ``s`` of every token's span: 128 words a token
        words = buf[slot, pl.ds(s, tm, stride=span), :]
        cols = pl.ds(s * 128, 128)
        if out_ref.dtype.itemsize == 4:
            out_ref[:, cols] = jax.lax.bitcast_convert_type(
                words, out_ref.dtype)
        else:  # integer halves, so exact whatever the bits are
            out_ref[:, cols] = jax.lax.bitcast_convert_type(
                (words & 0xFFFF).astype(jnp.uint16), out_ref.dtype)
            out_ref[:, pl.ds(half + s * 128, 128)] = (
                jax.lax.bitcast_convert_type(
                    (words >> 16).astype(jnp.uint16), out_ref.dtype))


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _fetch_rows(x, token, tiles: GroupTiles, tm: int, interpret: bool):
    """:func:`gather_rows`'s fetch form; jitted, so that a program whose
    layer segments each call it traces the two kernels once a shape."""
    n, h = x.shape
    (m,) = token.shape
    assert tm % FETCH_UNROLL == 0, tm
    span = _row_span(x)
    moved = m * h * x.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_fetch_kernel, tm=tm, span=span),
        out_shape=jax.ShapeDtypeStruct((m, h), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles.live[0],),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tm, h), lambda t, live, token: (t, 0)),
            scratch_shapes=[pltpu.VMEM((2, tm * span, 128), jnp.uint32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=0, bytes_accessed=2 * moved, transcendentals=0),
        name="moe_fetch_rows",
        interpret=interpret,
    )(tiles.live, token, _row_words(x, interpret))


def gather_rows(x, token, tiles: GroupTiles, *, fetch: bool = False,
                tm: int = ROW_TILE, interpret: bool | None = None):
    """``out[r] = x[token[r]]`` for the rows of the LIVE row tiles alone
    (``tiles.live``, a dynamic grid bound). ``token [M]`` int32, every
    entry a row of ``x [N, H]``. Returns ``[M, H]``; the tiles past the
    live ones are NOT written. Two forms of the one gather, both exact,
    the caller's to choose by ``N`` (:func:`cake_tpu.ops.moe.gather_form`):

    - the one-hot product: a block of ``x``'s columns stays in VMEM while
      the live tiles pass, and a tile's rows are picked by a ``[tm, N]``
      one-hot on the MXU: ``2 x live rows x N x H`` operations, nothing
      at a step's or a short bucket's ``N``, 4 ms a layer at 8192 rows;
    - ``fetch``: a row comes by its address, one copy a row out of ``x``
      re-laid as 32-bit words a whole tile a row (:func:`_row_words`, one
      pass over ``x``), a tile's copies in flight behind the tile being
      written: the live rows' bytes and no more. A width whose row is no
      whole number of tiles (:func:`rows_fetchable`) takes the one-hot
      product all the same."""
    n, h = x.shape
    (m,) = token.shape
    assert m % tm == 0, (m, tm)
    if fetch and rows_fetchable(h, x.dtype):
        return _fetch_rows(x, token, tiles, tm, _interpret(interpret))
    hb = _block_n(n, h, x.dtype.itemsize)
    return pl.pallas_call(
        _gather_kernel,
        out_shape=jax.ShapeDtypeStruct((m, h), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h // hb, tiles.live[0]),
            in_specs=[pl.BlockSpec((tm, 1), lambda j, t, live: (t, 0)),
                      pl.BlockSpec((n, hb), lambda j, t, live: (0, j))],
            out_specs=pl.BlockSpec((tm, hb), lambda j, t, live: (t, j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * h,
            bytes_accessed=(n + m) * h * x.dtype.itemsize,
            transcendentals=0),
        name="moe_gather_rows",
        interpret=_interpret(interpret),
    )(tiles.live, token.reshape(m, 1), x)


def _combine_block(n: int, h: int, out_itemsize: int) -> int:
    """Output columns a block of :func:`combine_rows`: the most whole
    lanes that divide ``h`` and keep the ``[n, block]`` float32 sums and
    the two output buffers within ``COMBINE_BYTES``."""
    if h % 128:
        return h
    best = 128
    for hb in range(128, h + 1, 128):
        if h % hb == 0 and n * hb * (4 + 2 * out_itemsize) <= COMBINE_BYTES:
            best = hb
    return best


def _combine_kernel(live_ref, total_ref, token_ref, weight_ref, y_ref,
                    out_ref, sum_ref, *, tm: int):
    t = pl.program_id(1)
    base = t % (SMEM_BLOCK // tm) * tm  # the tile's place in its block

    @pl.when(t == 0)
    def _zero():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    def add(r, carry):
        at = pl.ds(token_ref[base + r], 1)
        sum_ref[at, :] = (sum_ref[at, :]
                          + weight_ref[base + r] * y_ref[pl.ds(r, 1), :])
        return carry

    # the grouped rows of this tile: what lies past them was never written
    jax.lax.fori_loop(0, jnp.clip(total_ref[0] - t * tm, 0, tm), add, 0)

    @pl.when(t == jnp.maximum(live_ref[0], 1) - 1)
    def _round():
        out_ref[...] = sum_ref[...].astype(out_ref.dtype)


def combine_rows(y, token, weight, tiles: GroupTiles, n: int, *,
                 out_dtype=None, tm: int = ROW_TILE,
                 block_h: int | None = None,
                 interpret: bool | None = None):
    """``out[token[r]] += weight[r] * y[r]`` over the grouped rows (the
    first ``tiles.offsets[-1]``) of the LIVE row tiles alone, summed in
    float32 and rounded once: ``y [M, H]`` float32, ``token [M]`` int32
    (rows of ``out``), ``weight [M]`` float32. Returns ``[n, H]`` in
    ``out_dtype``; a row of ``out`` that no grouped row names is exactly
    zero, and no row past the grouped ones is read."""
    m, h = y.shape
    assert m % tm == 0 and y.dtype == jnp.float32, (y.shape, y.dtype, tm)
    assert SMEM_BLOCK % tm == 0, tm
    out_dtype = jnp.dtype(out_dtype or y.dtype)
    hb = block_h or _combine_block(n, h, out_dtype.itemsize)
    assert h % hb == 0, (h, hb)
    pad = (0, -m % SMEM_BLOCK)
    token = jnp.pad(token, pad)
    weight = jnp.pad(weight.astype(jnp.float32), pad)

    def rows_map(j, t, live, total):
        return (t // (SMEM_BLOCK // tm),)

    return pl.pallas_call(
        functools.partial(_combine_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((n, h), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # one visit at least: the sums of a call with no grouped row
            # are zeros all the same
            grid=(h // hb, jnp.maximum(tiles.live[0], 1)),
            in_specs=[
                pl.BlockSpec((SMEM_BLOCK,), rows_map,
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((SMEM_BLOCK,), rows_map,
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((tm, hb), lambda j, t, live, total: (t, j))],
            out_specs=pl.BlockSpec((n, hb), lambda j, t, live, total: (0, j)),
            scratch_shapes=[pltpu.VMEM((n, hb), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * h,
            bytes_accessed=m * h * 4 + n * h * out_dtype.itemsize,
            transcendentals=0),
        name="moe_combine_rows",
        interpret=_interpret(interpret),
    )(tiles.live, tiles.offsets[-1:], token, weight, y)


def grouped_matmul_bytes(m: int, k: int, n: int, e: int, w_itemsize: int,
                         x_itemsize: int = 2, out_itemsize: int = 2,
                         block_n: int | None = None) -> int:
    """Bytes one call moves when every group has rows: each matrix once,
    the rows once for each block of output columns, the result once."""
    bn = block_n or _block_n(k, n, w_itemsize)
    return (e * k * n * w_itemsize + (n // bn) * m * k * x_itemsize
            + m * n * out_itemsize)


def grouped_swiglu_bytes(m: int, k: int, n: int, e: int, w_itemsize: int,
                         x_itemsize: int = 2,
                         block_n: int | None = None) -> int:
    """Bytes one fused call moves when every group has rows: each gate
    and up matrix once, the rows once for each block of output columns
    (one fetch serves both products), the SwiGLU's result once, in the
    rows' type."""
    bn = block_n or _block_n(k, n, w_itemsize)
    return (2 * e * k * n * w_itemsize + (n // bn) * m * k * x_itemsize
            + m * n * x_itemsize)
