"""Pallas TPU kernels for a learned sparse attention over the latent cache
(:mod:`cake_tpu.ops.dsa` has the mathematics and the ``jnp`` forms these
are held against).

- :func:`dsa_index`: a decode step's index scores. The carried index
  buffer stays where it is; stream ``b``'s key blocks are fetched from
  row 0 up to its frontier and no further (a block past it re-names the
  frontier's block, which the pipeline does not fetch again), each one
  ``[J, D] x [D, BK]`` product, ``relu``, the heads' weighted sum.
- a decode step's choice and attention, in one of two forms a program
  (:func:`cake_tpu.ops.dsa.attend_form_choice`, by the buffer's rows):

  - **the sweep** (PR 62). :func:`dsa_select`: the choice as a threshold:
    every stream's ``index_topk``-th largest score by bisection on the
    scores' ordered bits, all streams at once in VMEM (a row of scores a
    sublane), then each score kept or ``-inf``: nothing is sorted and no
    row number is made. :func:`dsa_attend`: the carried row buffer stays
    where it is; stream ``b``'s blocks ``[BK, width]`` (``[c | k_pe |
    padding]`` a row) are fetched from row 0 to its frontier, ONE copy a
    block, double-buffered across the change of stream
    (:func:`cake_tpu.ops.pallas.latent.latent_decode`'s walk), and each is
    attended under its slice of the kept scores: the rows a stream did
    not choose are read and masked, because a block is a DMA's unit and a
    row is none.
  - **the gather**. XLA's ``lax.top_k`` and gather of the chosen rows
    (``[B, K, width]``), then :func:`dsa_attend_gathered` over them: each
    block fetched once, used as key and as value. It costs a ROW, not a
    byte (~15-20 ns each), whatever the buffer's length.

  Both attentions run the online softmax of ``latent_decode`` and are
  named ``dsa_attend`` in a trace (a program holds one of the two).
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
# a choice as a threshold: bisection on the scores' ordered bits
# ---------------------------------------------------------------------------

_INT_MIN, _INT_MAX = -2**31, 2**31 - 1
_COLS = 2048  # columns a pass over the scores handles at a time


def _ordered(x):
    """float32 -> int32 whose signed order is the floats' (``-inf`` first;
    no NaN is expected): the key a threshold is bisected on."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & _INT_MAX)


def _column(at, shape):
    return at + jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _threshold(count, topk: int, full):
    """``(theta, room) [R, 1]`` of ``R`` rows of ordered keys: a row's
    threshold, the largest key that ``topk`` of its keys reach (32 passes
    of compare and count; ``_INT_MIN`` where a row holds fewer), and how
    many keys AT the threshold the row may take. ``count(pred)``: the
    rows' counts ``[R, 1]`` of the columns ``pred(keys, first column)``
    holds for."""
    def midpoint(lo, hi):  # ceil((lo + hi) / 2) without overflow
        return (lo >> 1) + (hi >> 1) + ((lo | hi) & 1)

    def narrow(_, bounds):
        lo, hi = bounds
        mid = midpoint(lo, hi)
        enough = count(lambda keys, at: keys >= mid) >= topk
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid - 1)

    theta, _ = jax.lax.fori_loop(
        0, 32, narrow, (jnp.full(full, _INT_MIN, jnp.int32),
                        jnp.full(full, _INT_MAX, jnp.int32)))
    return theta, topk - count(lambda keys, at: keys > theta)


def _last_tie(count, theta, room, full, s: int):
    """Of the keys at the threshold the lowest columns: the last column
    ``[R, 1]`` a row takes one at, by bisection on the columns ``0..s -
    1``."""
    def narrow_column(_, bounds):
        lo, hi = bounds  # the smallest column with room ties up to it
        mid = (lo + hi) >> 1
        enough = count(lambda keys, at: (keys == theta) & (
            _column(at, keys.shape) <= mid)) >= room
        return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

    return jax.lax.fori_loop(
        0, max(1, (s - 1).bit_length()), narrow_column,
        (jnp.zeros(full, jnp.int32), jnp.full(full, s - 1, jnp.int32)))[0]


# ---------------------------------------------------------------------------
# decode: the absorbed attention over the chosen rows (both forms)
# ---------------------------------------------------------------------------

def _attend_block(q_c, q_r, rows, ok, m_ref, l_ref, o_ref, *, scale: float,
                  dc: int, dr: int):
    """One block ``rows [BK, width]`` (``[c | k_pe | padding]`` a row) of
    the absorbed attention's online softmax, on the running maximum,
    normalizer and accumulator ``m_ref`` / ``l_ref`` / ``o_ref``; ``ok [1,
    BK]`` float32: ``-inf`` where a row is not attended. (A block with no
    attended row before the first that has one leaves ``m = NEG_INF`` and
    ``p = 1`` on its rows: the first attended row takes ``alpha`` to 0.)"""
    c, r = rows[:, :dc], rows[:, dc:dc + dr]
    s = (jax.lax.dot_general(q_c, c, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
         + jax.lax.dot_general(q_r, r, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32))
    s = jnp.where(ok > -jnp.inf, s * scale, NEG_INF)  # [H, BK]
    m_prev = m_ref[0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])
    l_ref[0] = alpha * l_ref[0] + jnp.sum(p, axis=1, keepdims=True)
    m_ref[0] = m_new
    o_ref[0] = o_ref[0] * alpha[:, :1] + jax.lax.dot_general(
        p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _attend_gathered_kernel(qc_ref, qr_ref, rows_ref, ok_ref, m_ref, l_ref,
                            o_ref, *, scale: float, dc: int, dr: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    _attend_block(qc_ref[0], qr_ref[0], rows_ref[0], ok_ref[0], m_ref, l_ref,
                  o_ref, scale=scale, dc=dc, dr=dr)


def dsa_attend_gathered(
    q_c: jax.Array,  # [B, H, dc]: q_nope through W_kvb's key half
    q_pe: jax.Array,  # [B, H, dr] (already roped)
    chosen: jax.Array,  # [B, K, >= dc + dr]: the chosen rows, [c | k_pe | 0..]
    values: jax.Array,  # [B, K] float32: their index scores, -inf = no row
    *,
    scale: float,
    block_k: int = DECODE_BLOCK_K,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The gather form's attention: single-position absorbed attention
    over the rows a stream chose, gathered out of the carried buffer by
    XLA, each block read once and used as key and as value. Returns what
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
        functools.partial(_attend_gathered_kernel, scale=scale, dc=dc,
                          dr=dr),
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
# decode, the sweep: the choice as a threshold, the attention over the
# carried buffer under it
# ---------------------------------------------------------------------------

def _select_kernel(pos_ref, s_ref, o_ref, key_ref, *, topk: int, s: int):
    """Every stream at once, a stream a sublane: ``pos_ref [B, 1]``, ``s_ref
    / o_ref [B, S]`` float32, ``key_ref [B, S]`` int32. Only the stretches
    of columns up to the batch's furthest frontier are keyed, counted and
    chosen among; what lies past it is written ``-inf`` and never read."""
    cols = min(_COLS, s)
    pos = pos_ref[...]
    full = pos.shape
    live = jnp.minimum(jnp.max(pos) // cols + 1, s // cols)

    def stretch(c):
        return pl.ds(pl.multiple_of(c * cols, cols), cols)

    def key(c, _):
        score = s_ref[:, stretch(c)]
        seen = (_column(c * cols, score.shape) <= pos) & (score > -jnp.inf)
        # (-0.0 ties with 0.0, as ``==`` has it in the jnp form: one key)
        ordered = jnp.where(score == 0.0, 0, _ordered(score))
        key_ref[:, stretch(c)] = jnp.where(seen, ordered, _INT_MIN)
        return 0

    jax.lax.fori_loop(0, live, key, 0)

    # a pass's counts are summed a lane tile at a time, elementwise, and
    # across the lanes once a pass: the passes are a chain, each waiting
    # for the last one's sums
    fold = _LANES if cols % _LANES == 0 else cols

    def count(pred):
        def some(c, lanes):
            hit = pred(key_ref[:, stretch(c)], c * cols).astype(jnp.int32)
            for at in range(0, cols, fold):
                lanes = lanes + hit[:, at:at + fold]
            return lanes

        return jnp.sum(jax.lax.fori_loop(
            0, live, some, jnp.zeros((full[0], fold), jnp.int32)),
            axis=1, keepdims=True)

    theta, room = _threshold(count, topk, full)
    # a stream under topk rows keeps them all: its threshold is the dead
    # columns' key, which no choice takes, so its ties are nobody's
    at_theta = count(lambda keys, at: keys == theta)
    crowded = jnp.max(((at_theta > room) & (theta > _INT_MIN))
                      .astype(jnp.int32)) > 0
    edge = jax.lax.cond(
        crowded, lambda _: _last_tie(count, theta, room, full, s),
        lambda _: jnp.full(full, s - 1, jnp.int32), 0)

    def keep(c, _):
        keys = key_ref[:, stretch(c)]
        kept = ((keys > theta) | ((keys == theta) & (
            _column(c * cols, keys.shape) <= edge))) & (keys > _INT_MIN)
        o_ref[:, stretch(c)] = jnp.where(kept, s_ref[:, stretch(c)],
                                         -jnp.inf)
        return 0

    jax.lax.fori_loop(0, live, keep, 0)

    def past(c, _):
        o_ref[:, stretch(c)] = jnp.full((full[0], cols), -jnp.inf,
                                        jnp.float32)
        return 0

    jax.lax.fori_loop(live, s // cols, past, 0)


def dsa_select(
    scores: jax.Array,  # [B, S] float32: dsa_index's
    pos,  # [B] int32: each stream's frontier
    topk: int,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """A decode step's choice as kept scores ``[B, S]`` float32: ``scores[b,
    s]`` where row ``s <= pos[b]`` is among stream ``b``'s ``topk`` rows of
    largest score, a tie to the lower ``s``, ``-inf`` elsewhere (whatever
    lies past a frontier, and a row scored ``-inf``, is never kept; a
    stream with ``pos + 1 <= topk`` keeps every live row):
    :func:`cake_tpu.ops.dsa.chosen_mask`'s rows. Each stream's threshold
    by bisection on the scores' ordered bits, all streams a pass (32
    passes of compare and count over the columns up to the furthest
    frontier, 2 more for the threshold's own keys, ``log2 S`` more where
    a stream holds more of them than it has room for): no sort."""
    b, s = scores.shape
    assert s % min(_COLS, s) == 0, s
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=min(topk, s), s=s),
        out_shape=jax.ShapeDtypeStruct((b, s), jnp.float32),
        in_specs=[vmem, vmem],
        out_specs=vmem,
        scratch_shapes=[pltpu.VMEM((b, s), jnp.int32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 * 2**20),
        cost_estimate=pl.CostEstimate(  # ~36 passes: compare, cast, add
            flops=36 * 3 * b * s, bytes_accessed=8 * b * s,
            transcendentals=0),
        name="dsa_select",
        interpret=_interpret(interpret),
    )(jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))[:, None],
      scores.astype(jnp.float32))


# Rows of the carried buffer a fetch of the swept attention brings
# (``tools/dsa_sweep.py --attend-block`` on v5 lite, PR 62, 16 streams x
# 16,384 rows of 640, us a layer with every frontier at 2048 / 8192 /
# 16,000 / spread over the cell's 4096-15,360): 256-row fetches 233 / 513 /
# 890 / 583; 512 213 / 409 / 685 / 472; 1024 195 / 363 / 599 / 415; 2048
# 199 / 373 / 601 / 416; in the served step, at the cell's frontiers, 224
# us a layer: its bytes' time (my chip runs, PR 62).
ATTEND_BLOCK_K = 1024


def dsa_attend_block(s: int) -> int:
    """Rows a fetch of :func:`dsa_attend` brings of a buffer of ``s``."""
    return _pick_block(s, ATTEND_BLOCK_K)


def _attend_swept_kernel(pos_ref, *refs, stacked: bool, batch: int,
                         block_k: int, num_blocks: int, scale: float,
                         dc: int, dr: int):
    """Grid step ``b`` walks stream ``b``'s blocks 0..its frontier's; the
    block after the one being computed is already on its way into the
    other half of ``buf``, across the change of stream too
    (:func:`cake_tpu.ops.pallas.latent.latent_decode`'s walk). ``pos_ref
    [B]``, then ``layer_ref [1]`` when ``stacked``, ``qc_ref [1, H, dc]``,
    ``qr_ref [1, H, dr]``, ``ok_ref [1, 1, S]`` (the stream's kept scores),
    ``rows_hbm [(L,) B, 1, S, width]`` left where it is, ``m_ref`` /
    ``l_ref`` / ``o_ref`` (the outputs ARE the running values), ``buf``
    VMEM ``[2, BK, width]``, ``sem`` DMA ``[2]``, ``slot_ref`` SMEM ``[1]``:
    the half the next block to compute lies in."""
    lead = ()
    if stacked:
        layer_ref, *refs = refs
        lead = (layer_ref[0],)
    (qc_ref, qr_ref, ok_ref, rows_hbm, m_ref, l_ref, o_ref, buf, sem,
     slot_ref) = refs
    b = pl.program_id(0)

    def rows_of(kb):
        return pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)

    def copy(row, kb, slot):
        return pltpu.make_async_copy(
            rows_hbm.at[lead + (row, 0, rows_of(kb), slice(None))],
            buf.at[slot], sem.at[slot])

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        copy(0, 0, 0).start()

    hi = jnp.minimum(pos_ref[b] // block_k, num_blocks - 1)
    m_ref[0] = jnp.full(m_ref.shape[1:], -jnp.inf, jnp.float32)
    l_ref[0] = jnp.zeros(l_ref.shape[1:], jnp.float32)
    o_ref[0] = jnp.zeros(o_ref.shape[1:], jnp.float32)
    q_c, q_r = qc_ref[0], qr_ref[0]  # [H, dc], [H, dr]

    def block(kb, slot):
        last = kb == hi
        row_next = jnp.where(last, b + 1, b)

        @pl.when(row_next < batch)
        def _prefetch():
            copy(row_next, jnp.where(last, 0, kb + 1), 1 - slot).start()

        copy(b, kb, slot).wait()
        _attend_block(q_c, q_r, buf[slot], ok_ref[0, :, rows_of(kb)], m_ref,
                      l_ref, o_ref, scale=scale, dc=dc, dr=dr)
        return 1 - slot

    slot_ref[0] = jax.lax.fori_loop(0, hi + 1, block, slot_ref[0])


def dsa_attend(
    q_c: jax.Array,  # [B, H, dc]: q_nope through W_kvb's key half
    q_pe: jax.Array,  # [B, H, dr] (already roped)
    rows_all: jax.Array,  # [B, 1, S, >= dc + dr], or stacked [L, B, 1, S, ..]
    kept: jax.Array,  # [B, S] float32: dsa_select's, -inf = not attended
    pos,  # [B] int32: each stream's frontier
    *,
    scale: float,
    layer=None,  # index into the stacked form's leading axis
    block_k: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The sweep form's attention: single-position absorbed attention over
    the rows of the carried buffer ``rows_all`` (``[c | k_pe | padding]``
    a row) that ``kept`` keeps. The buffer stays in HBM; stream ``b`` reads
    ``pos[b] // block_k + 1`` blocks of ``block_k`` rows, one copy each,
    and masks what it did not choose. Returns what
    :func:`cake_tpu.ops.pallas.latent.latent_decode` returns: ``(m [B, H,
    1, 1], l [B, H, 1, 1], o_c [B, H, 1, dc])`` float32. Some row up to
    the frontier is kept (a stream holds its own new row at least)."""
    b, h, dc = q_c.shape
    dr, width = q_pe.shape[-1], rows_all.shape[-1]
    stacked = layer is not None
    assert rows_all.ndim == (5 if stacked else 4), (rows_all.shape, layer)
    assert rows_all.shape[-3] == 1 and width >= dc + dr, rows_all.shape
    s = rows_all.shape[-2]
    bk = _pick_block(s, block_k) if block_k else dsa_attend_block(s)
    interpret = _interpret(interpret)
    prefetch = [jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1),
                                 (b,))]
    if stacked:
        prefetch.append(jnp.asarray(layer, jnp.int32).reshape(1))
    if not interpret:  # left to choose, the compiler may move it to VMEM
        rows_all = pltpu.with_memory_space_constraint(rows_all, pltpu.HBM)

    def row(width):
        return pl.BlockSpec((1, h, width), lambda i, *prefetched: (i, 0, 0))

    f32 = jnp.float32
    m, l, o_c = pl.pallas_call(
        functools.partial(_attend_swept_kernel, stacked=stacked, batch=b,
                          block_k=bk, num_blocks=s // bk, scale=scale, dc=dc,
                          dr=dr),
        out_shape=(jax.ShapeDtypeStruct((b, h, _LANES), f32),
                   jax.ShapeDtypeStruct((b, h, _LANES), f32),
                   jax.ShapeDtypeStruct((b, h, dc), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b,),
            in_specs=[row(dc), row(dr),
                      pl.BlockSpec((1, 1, s), lambda i, *_: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=(row(_LANES), row(_LANES), row(dc)),
            scratch_shapes=[
                pltpu.VMEM((2, bk, width), rows_all.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        # one stream after another: a step waits for the fetch the step
        # before it started
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * s * (2 * dc + dr),
            bytes_accessed=b * s * (width * rows_all.dtype.itemsize + 4),
            transcendentals=b * h * s),
        name="dsa_attend",
        interpret=interpret,
    )(*prefetch, q_c, q_pe, kept.astype(f32)[:, None, :], rows_all)
    return m[:, :, None, :1], l[:, :, None, :1], o_c[:, :, None]


# ---------------------------------------------------------------------------
# admission: each row's index scores, its threshold and its mask
# ---------------------------------------------------------------------------

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

    # 2. a row's threshold: the largest key that topk of its keys reach
    full = (block_q, 1)
    theta, room = _threshold(count, topk, full)

    # 3. of the keys at the threshold the lowest columns: the last column
    #    a row takes one at (a tie is rare: one pass says whether any row
    #    holds more of them than it has room for)
    at_theta = count(lambda keys, at: keys == theta)
    crowded = jnp.max((at_theta > room).astype(jnp.int32)) > 0
    edge = jax.lax.cond(
        crowded, lambda _: _last_tie(count, theta, room, full, t),
        lambda _: jnp.full(full, t - 1, jnp.int32), 0)

    def write(c, _):
        at = pl.multiple_of(c * cols, cols)
        keys = key_ref[:, pl.ds(at, cols)]
        col = _column(at, keys.shape)
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
