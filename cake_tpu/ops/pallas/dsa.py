"""Pallas TPU kernels for a learned sparse attention over the latent cache
(:mod:`cake_tpu.ops.dsa` has the mathematics and the ``jnp`` forms these
are held against).

- :func:`dsa_index`: a decode step's index scores. The carried index
  buffer stays where it is; stream ``b``'s key blocks are fetched from
  row 0 up to its frontier and no further (a block past it re-names the
  frontier's block, which the pipeline does not fetch again), each one
  ``[J, D] x [D, BK]`` product, ``relu``, the heads' weighted sum.
- :func:`dsa_attend`: a decode step's absorbed attention over the rows a
  stream chose (gathered out of the carried buffer by XLA, ``[B, K,
  width]``: ``[c | k_pe | padding]`` a row): each block fetched once, used
  as key and as value, the online softmax of
  :func:`cake_tpu.ops.pallas.latent.latent_decode`.
- :func:`dsa_prefill_select`: an admission's choice as a mask: a block of
  query rows' index scores against the chunk's own keys, each row's
  threshold by bisection and the rows at or above it, all in VMEM: neither
  the heads' products nor the scores are written out, and nothing is
  sorted.
- :func:`dsa_prefill_attend`: the flash prefill sweep
  (:func:`cake_tpu.ops.pallas.flash.flash_attention`'s) under a mask a
  (query row, key row) pair, for heads as wide for keys as for values:
  several heads a grid step, which share the step's mask tile.

Numerics are the ``jnp`` forms': operands in the serving type, float32
products, scores, maxima, normalizers and accumulators.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cake_tpu.ops.pallas.flash import (DECODE_BLOCK_K, NEG_INF, _LANES,
                                       _pick_block)


def _interpret(interpret):
    if interpret is None:
        from cake_tpu.ops.pallas import interpret_default

        return interpret_default()
    return interpret


# ---------------------------------------------------------------------------
# decode: index scores up to each stream's frontier
# ---------------------------------------------------------------------------

# Rows of index keys a grid step scores (``tools/dsa_sweep.py`` on v5 lite,
# PR 61, 16 streams x 16,384 rows of 128, us a layer with every frontier
# at 2048 / 8192 / 16000): 512-row blocks 208 / 265 / 350 (a step's fixed
# cost over 512 grid steps), 2048-row blocks 154 / 213 / 230; in the served
# step, at the cell's frontiers, 66 us a layer (my chip runs, PR 61).
INDEX_BLOCK_K = 2048


def _index_kernel(pos_ref, *refs, stacked: bool, block_k: int):
    if stacked:
        _, *refs = refs  # the layer: the index maps' alone
    q_ref, w_ref, k_ref, o_ref = refs
    b, kb = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b]

    @pl.when(kb * block_k > pos)
    def _dead():
        o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, jnp.float32)

    @pl.when(kb * block_k <= pos)
    def _live():
        q = q_ref[0]  # [J, D]
        k = k_ref[...].reshape(block_k, q.shape[-1])  # [BK, D]
        dots = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        score = jnp.sum(jnp.maximum(dots, 0.0) * w_ref[0], axis=0,
                        keepdims=True)  # [1, BK]
        kpos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, score.shape, 1)
        o_ref[0] = jnp.where(kpos <= pos, score, -jnp.inf)


def dsa_index(
    q_i: jax.Array,  # [B, J, D] (already roped)
    w: jax.Array,  # [B, J] float32, scaled
    i_all: jax.Array,  # [B, 1, S, D], or stacked [L, B, 1, S, D]
    pos,  # [B] int32: each stream's frontier
    *,
    layer=None,
    block_k: int = INDEX_BLOCK_K,
    interpret: bool | None = None,
) -> jax.Array:
    """``I [B, S]`` float32: ``sum_j w[b, j] relu(q_i[b, j] . i_all[b,
    s])`` for ``s <= pos[b]``, ``-inf`` past it. Stream ``b`` reads ``pos[b]
    // block_k + 1`` blocks of its ``S // block_k``."""
    b, j, d = q_i.shape
    stacked = layer is not None
    assert i_all.ndim == (5 if stacked else 4), (i_all.shape, layer)
    s = i_all.shape[-2]
    bk = _pick_block(s, block_k)
    prefetch = [jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1),
                                 (b,))]
    if stacked:
        prefetch.append(jnp.asarray(layer, jnp.int32).reshape(1))

    def k_map(bi, kb, pos_ref, *layer_ref):
        at = (bi, 0, jnp.minimum(kb, pos_ref[bi] // bk), 0)
        return ((layer_ref[0][0],) + at) if stacked else at

    out = pl.pallas_call(
        functools.partial(_index_kernel, stacked=stacked, block_k=bk),
        out_shape=jax.ShapeDtypeStruct((b, 1, s), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, s // bk),
            in_specs=[
                pl.BlockSpec((1, j, d), lambda bi, kb, *_: (bi, 0, 0)),
                pl.BlockSpec((1, j, 1), lambda bi, kb, *_: (bi, 0, 0)),
                pl.BlockSpec(((1,) if stacked else ()) + (1, 1, bk, d),
                             k_map),
            ],
            out_specs=pl.BlockSpec((1, 1, bk), lambda bi, kb, *_: (bi, 0, kb)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * j * s * d,
            bytes_accessed=b * s * (d * i_all.dtype.itemsize + 4),
            transcendentals=0),
        name="dsa_index",
        interpret=_interpret(interpret),
    )(*prefetch, q_i, w.astype(jnp.float32)[..., None], i_all)
    return out[:, 0]


# ---------------------------------------------------------------------------
# decode: the absorbed attention over the chosen rows
# ---------------------------------------------------------------------------

def _attend_kernel(qc_ref, qr_ref, rows_ref, ok_ref, m_ref, l_ref, o_ref, *,
                   scale: float, dc: int, dr: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    rows = rows_ref[0]  # [BK, width]: [c | k_pe | padding]
    c, r = rows[:, :dc], rows[:, dc:dc + dr]
    s = (jax.lax.dot_general(qc_ref[0], c, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
         + jax.lax.dot_general(qr_ref[0], r, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32))
    s = jnp.where(ok_ref[0] > -jnp.inf, s * scale, NEG_INF)  # [H, BK]
    m_prev = m_ref[0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])
    l_ref[0] = alpha * l_ref[0] + jnp.sum(p, axis=1, keepdims=True)
    m_ref[0] = m_new
    o_ref[0] = o_ref[0] * alpha[:, :1] + jax.lax.dot_general(
        p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def dsa_attend(
    q_c: jax.Array,  # [B, H, dc]: q_nope through W_kvb's key half
    q_pe: jax.Array,  # [B, H, dr] (already roped)
    chosen: jax.Array,  # [B, K, >= dc + dr]: the chosen rows, [c | k_pe | 0..]
    values: jax.Array,  # [B, K] float32: their index scores, -inf = no row
    *,
    scale: float,
    block_k: int = DECODE_BLOCK_K,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single-position absorbed attention over the rows a stream chose,
    each block read once and used as key and as value. Returns what
    :func:`cake_tpu.ops.pallas.latent.latent_decode` returns: ``(m [B, H,
    1, 1], l [B, H, 1, 1], o_c [B, H, 1, dc])`` float32. The first choice
    is a true row (a stream holds its own new row at least), so the
    running maximum is real from the first block on."""
    b, h, dc = q_c.shape
    k, dr, width = chosen.shape[1], q_pe.shape[-1], chosen.shape[-1]
    assert width >= dc + dr, (chosen.shape, dc, dr)
    bk = _pick_block(k, block_k)
    f32 = jnp.float32

    def row(width):
        return pl.BlockSpec((1, h, width), lambda bi, kb: (bi, 0, 0))

    m, l, o_c = pl.pallas_call(
        functools.partial(_attend_kernel, scale=scale, dc=dc, dr=dr),
        out_shape=(jax.ShapeDtypeStruct((b, h, _LANES), f32),
                   jax.ShapeDtypeStruct((b, h, _LANES), f32),
                   jax.ShapeDtypeStruct((b, h, dc), f32)),
        grid=(b, k // bk),
        in_specs=[row(dc), row(dr),
                  pl.BlockSpec((1, bk, width), lambda bi, kb: (bi, kb, 0)),
                  pl.BlockSpec((1, 1, bk), lambda bi, kb: (bi, 0, kb))],
        out_specs=(row(_LANES), row(_LANES), row(dc)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * k * (2 * dc + dr),
            bytes_accessed=b * k * width * chosen.dtype.itemsize,
            transcendentals=b * h * k),
        name="dsa_attend",
        interpret=_interpret(interpret),
    )(q_c, q_pe, chosen, values.astype(f32)[:, None, :])
    return m[:, :, None, :1], l[:, :, None, :1], o_c[:, :, None]


# ---------------------------------------------------------------------------
# admission: each row's index scores, its threshold and its mask
# ---------------------------------------------------------------------------

_INT_MIN, _INT_MAX = -2**31, 2**31 - 1
_COLS = 2048  # columns a pass over the scores handles at a time


def _ordered(x):
    """float32 -> int32 whose signed order is the floats' (``-inf`` first;
    no NaN is expected): the key a threshold is bisected on."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & _INT_MAX)


def _prefill_select_kernel(q_ref, w_ref, k_ref, o_ref, key_ref, *,
                           block_q: int, block_k: int, heads: int,
                           topk: int, t: int):
    qb = pl.program_id(1)
    row0 = qb * block_q
    cols = min(_COLS, t)
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    # 1. the rows' index scores, a key block at a time up to the diagonal,
    #    as ordered keys; what lies above the diagonal stays -inf's key
    key_ref[...] = jnp.full(key_ref.shape, _INT_MIN, jnp.int32)
    w = w_ref[0]  # [BQ, J] float32

    def score_block(kb, _):
        at = pl.multiple_of(kb * block_k, block_k)
        k = k_ref[0, pl.ds(at, block_k), :]  # [BK, D]
        acc = jnp.zeros((block_q, block_k), jnp.float32)
        for j in range(heads):
            dots = jax.lax.dot_general(
                q_ref[0, j], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(dots, 0.0) * w[:, j:j + 1]
        kpos = at + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
        key_ref[:, pl.ds(at, block_k)] = jnp.where(
            kpos <= rows, _ordered(acc), _INT_MIN)
        return 0

    jax.lax.fori_loop(0, (row0 + block_q - 1) // block_k + 1, score_block, 0)

    def count(pred):
        """Rows' counts ``[BQ, 1]`` of the columns ``pred(keys, first
        column)`` holds for, a stretch of columns at a time."""
        def some(c, total):
            at = pl.multiple_of(c * cols, cols)
            hit = pred(key_ref[:, pl.ds(at, cols)], at)
            return total + jnp.sum(hit.astype(jnp.int32), axis=1,
                                   keepdims=True)

        return jax.lax.fori_loop(0, t // cols, some,
                                 jnp.zeros((block_q, 1), jnp.int32))

    def midpoint(lo, hi):  # ceil((lo + hi) / 2) without overflow
        return (lo >> 1) + (hi >> 1) + ((lo | hi) & 1)

    # 2. a row's threshold: the largest key that topk of its keys reach
    def narrow(_, bounds):
        lo, hi = bounds
        mid = midpoint(lo, hi)
        enough = count(lambda keys, at: keys >= mid) >= topk
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid - 1)

    full = (block_q, 1)
    theta, _ = jax.lax.fori_loop(
        0, 32, narrow, (jnp.full(full, _INT_MIN, jnp.int32),
                        jnp.full(full, _INT_MAX, jnp.int32)))
    above = count(lambda keys, at: keys > theta)
    room = topk - above  # how many keys AT the threshold a row may take

    # 3. of the keys at the threshold the lowest columns: the last column
    #    a row takes one at (a tie is rare: one pass says whether any row
    #    holds more of them than it has room for)
    def column(at, shape):
        return at + jax.lax.broadcasted_iota(jnp.int32, shape, 1)

    at_theta = count(lambda keys, at: keys == theta)

    def last_tie(_):
        def narrow_column(_, bounds):
            lo, hi = bounds  # the smallest column with room ties up to it
            mid = (lo + hi) >> 1
            enough = count(lambda keys, at: (keys == theta) & (
                column(at, keys.shape) <= mid)) >= room
            return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

        return jax.lax.fori_loop(
            0, max(1, (t - 1).bit_length()), narrow_column,
            (jnp.zeros(full, jnp.int32), jnp.full(full, t - 1, jnp.int32)))[0]

    crowded = jnp.max((at_theta > room).astype(jnp.int32)) > 0
    edge = jax.lax.cond(crowded, last_tie,
                        lambda _: jnp.full(full, t - 1, jnp.int32), 0)

    def write(c, _):
        at = pl.multiple_of(c * cols, cols)
        keys = key_ref[:, pl.ds(at, cols)]
        col = column(at, keys.shape)
        chosen = (keys > theta) | ((keys == theta) & (col <= edge))
        o_ref[0, :, pl.ds(at, cols)] = (chosen & (col <= rows)).astype(
            jnp.int8)
        return 0

    jax.lax.fori_loop(0, t // cols, write, 0)


def dsa_prefill_select(
    q_i: jax.Array,  # [B, J, T, D] (already roped)
    w: jax.Array,  # [B, T, J] float32, scaled
    k_i: jax.Array,  # [B, T, D]: the chunk's own index keys
    topk: int,
    *,
    block_q: int = 128,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """A chunk's chosen rows from position 0 as a mask ``[B, T, T]`` int8:
    row ``t`` may attend row ``s`` where ``s <= t`` and ``s`` is among the
    ``topk`` rows of largest ``I[t, s] = sum_j w relu(q . k)``, a tie to
    the lower ``s`` (every ``s <= t`` while ``t + 1 <= topk``). A block of
    query rows a grid step: its scores are made a key block at a time up
    to the diagonal and live in VMEM alone, as int32 keys of the floats'
    order; the rows' thresholds are found by bisection on those keys (32
    passes of compare and count, no sort), a tie's last column by
    bisection on the columns. The chunk's keys are fetched once a batch
    row."""
    b, heads, t, d = q_i.shape
    bq, bk = _pick_block(t, block_q), _pick_block(t, block_k)
    assert t % min(_COLS, t) == 0, t
    return pl.pallas_call(
        functools.partial(_prefill_select_kernel, block_q=bq, block_k=bk,
                          heads=heads, topk=min(topk, t), t=t),
        out_shape=jax.ShapeDtypeStruct((b, t, t), jnp.int8),
        grid=(b, t // bq),
        in_specs=[
            pl.BlockSpec((1, heads, bq, d), lambda bi, qb: (bi, 0, qb, 0)),
            pl.BlockSpec((1, bq, heads), lambda bi, qb: (bi, qb, 0)),
            pl.BlockSpec((1, t, d), lambda bi, qb: (bi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, t), lambda bi, qb: (bi, qb, 0)),
        scratch_shapes=[pltpu.VMEM((bq, t), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2**20),
        cost_estimate=pl.CostEstimate(
            flops=b * heads * t * t * d,  # the lower triangle's products
            bytes_accessed=(q_i.size + k_i.size) * q_i.dtype.itemsize
            + b * t * t,
            transcendentals=0),
        name="dsa_prefill_select",
        interpret=_interpret(interpret),
    )(q_i, w.astype(jnp.float32), k_i)


# ---------------------------------------------------------------------------
# admission: the flash sweep under each row's mask
# ---------------------------------------------------------------------------

def _prefill_attend_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, acc_ref,
                           m_ref, l_ref, *, block_q: int, block_k: int,
                           group: int, scale: float, num_kv_blocks: int):
    qb, kb = pl.program_id(2), pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    # the chunk starts at position 0: a key block wholly above the
    # diagonal holds nothing any row of the tile may see
    @pl.when(kb * block_k <= (qb + 1) * block_q - 1)
    def _compute():
        seen = mask_ref[0].astype(jnp.int32) != 0  # [BQ, BK], every head's
        for g in range(group):
            q, k, v = q_ref[0, g], k_ref[0, g], v_ref[0, g]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(seen, s, NEG_INF)
            m_prev, l_prev = m_ref[g], l_ref[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # (a row with nothing chosen in the tiles so far has m_new =
            # NEG_INF and p = 1 on masked keys: the first tile that holds
            # a chosen key, and every row has one, takes alpha to 0)
            p = jnp.exp(s - m_new[:, :1])
            l_ref[g] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            m_ref[g] = m_new
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[g] = acc_ref[g] * alpha[:, :1] + pv

    @pl.when(kb == num_kv_blocks - 1)
    def _finish():
        for g in range(group):
            o_ref[0, g] = (acc_ref[g] / l_ref[g][:, :1]).astype(o_ref.dtype)


def dsa_prefill_attend(
    q: jax.Array,  # [B, H, T, D] (already roped)
    k: jax.Array,  # [B, H, T, D]: the chunk's own keys, expanded
    v: jax.Array,  # [B, H, T, D]
    mask: jax.Array,  # [B, T, T] int8: row t may see row s (causal AND chosen)
    *,
    scale: float,
    block_q: int = 512,
    block_k: int = 1024,
    group: int = 4,
    interpret: bool | None = None,
) -> jax.Array:
    """Softmax attention of a chunk from position 0 under ``mask``.
    Returns ``[B, H, T, D]`` in ``q``'s type. ``group`` heads a grid step
    share the step's mask tile (the mask is read ``H / group`` times);
    every row must see at least one key (it sees itself or its choice).
    Tiles: ``tools/dsa_sweep.py --attend-blocks`` on v5 lite (PR 61, 64
    heads of 256, ms a layer at 8192 / 16,384 rows): 256 x 512 x 4 heads
    21.5 / 77.8; 512 x 512 x 4 18.8 / 67.7; 512 x 1024 x 2 19.5 / 67.5;
    **512 x 1024 x 4 18.3 / 63.5**; 256 x 1024 x 4 20.3 / 69.8; 512 x 512 x
    8 18.5 / 66.8 (the vector unit's work on a tile's scores, not the
    products, sets the time: PERF.md section 6)."""
    b, h, t, d = q.shape
    assert k.shape == v.shape == q.shape, (q.shape, k.shape, v.shape)
    bq, bk = _pick_block(t, block_q), _pick_block(t, block_k)
    while h % group:
        group //= 2
    nk = t // bk

    def last_kb(qb):
        return ((qb + 1) * bq - 1) // bk

    def q_map(bi, hg, qb, kb):
        return (bi, hg, qb, 0)

    def kv_map(bi, hg, qb, kb):
        return (bi, hg, jnp.minimum(kb, last_kb(qb)), 0)

    def mask_map(bi, hg, qb, kb):
        return (bi, qb, jnp.minimum(kb, last_kb(qb)))

    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_prefill_attend_kernel, block_q=bq, block_k=bk,
                          group=group, scale=scale, num_kv_blocks=nk),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(b, h // group, t // bq, nk),
        in_specs=[
            pl.BlockSpec((1, group, bq, d), q_map),
            pl.BlockSpec((1, group, bk, d), kv_map),
            pl.BlockSpec((1, group, bk, d), kv_map),
            pl.BlockSpec((1, bq, bk), mask_map),
        ],
        out_specs=pl.BlockSpec((1, group, bq, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((group, bq, d), f32),
            pltpu.VMEM((group, bq, _LANES), f32),
            pltpu.VMEM((group, bq, _LANES), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=64 * 2**20),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * t * t * d,  # the lower triangle, both products
            bytes_accessed=4 * q.size * q.dtype.itemsize
            + (h // group) * mask.size,
            transcendentals=b * h * t * t // 2),
        name="dsa_prefill_attend",
        interpret=_interpret(interpret),
    )(q, k, v, mask)
