"""A learned sparse attention over the latent cache (DeepSeek sparse
attention, as `model_type` "glm_moe_dsa" carries it).

Beside MLA's projections (:mod:`cake_tpu.ops.mla`) a layer holds an
*indexer*. Per token ``x_t`` (after the input norm), with ``c_q,t`` the
query latent MLA already makes:

    q^I_t,j = (c_q,t W^I_qb)_j            j = 1..index_n_heads, index_head_dim wide
    k^I_t   = LayerNorm(x_t W^I_k)        one key a token (weight + bias, eps 1e-6)
              rope (MLA's tables, interleaved pairs) on the FIRST
              qk_rope_head_dim channels of every q^I_t,j and of k^I_t
    w_t     = (x_t W^I_w) * heads^-0.5 * dim^-0.5            float32
    I_t,s   = sum_j w_t,j relu(q^I_t,j . k^I_s)   for s <= t  float32
    S_t     = the index_topk rows s <= t of largest I_t,s (every row where
              t + 1 <= index_topk; a tie goes to the lower s)
    out_t   = MLA's softmax attention over the rows of S_t alone

**Cached a token a layer:** MLA's ``[c | k_pe]``, here ONE row of the
first buffer (``LlamaConfig.cache_row``), and ``k^I`` (after the norm,
after rope), in ``KVCache.index`` (``LlamaConfig.cache_plan``'s ``index``),
written row for row beside it.

Two programs, as for MLA:

- a **decode step** (``T == 1``, :func:`decode_attend`): index scores of
  each stream's rows up to its frontier (``dsa.index``), the choice
  (``dsa.select``) and the absorbed attention over the chosen rows
  (``dsa.attend``), in one of two forms a program, picked by the
  buffer's rows (:func:`attend_form_choice`; the same set of rows and the
  same softmax either way):

  - **the sweep** (buffers up to ``SWEEP_MAX_ROWS`` rows): the choice is
    a threshold, each stream's ``index_topk``-th largest score by
    bisection on the scores' bits (:func:`chosen_mask` is the definition:
    a tie to the lower row), and the attention sweeps the carried buffer's
    blocks to each frontier under that mask: nothing is sorted, no row is
    copied, and the rows a step did not choose are read and masked (a
    block is a DMA's unit, a row is none: the chosen rows cost more to
    fetch one at a time than every row costs to stream);
  - **the gather** (longer buffers): ``lax.top_k``, which keeps the lower
    row of a tie, the chosen rows gathered out of the carried buffer, and
    the attention over the copies: the latent rows a step did not choose
    are not read. It costs ``index_topk`` fetches a stream whatever the
    frontier, and a sort of the buffer's rows.

  A stream under ``index_topk`` rows chooses all its rows (under the
  gather the surplus choices are rows past its frontier, scored ``-inf``
  and masked): the same code, no second program.
- an **admission** (``T > 1`` from position 0, :func:`prefill_attend`):
  the chunk's own index keys, the scores of a block of query rows at a
  time (never ``[heads, T, T]``, nor ``[T, T]``), each row's threshold (its
  ``index_topk``-th largest score) and from it the row's mask, then the
  expanded attention under that mask, blocked by query rows: on the chip
  the kernels of :mod:`cake_tpu.ops.pallas.dsa` (the threshold a
  bisection, no sort), elsewhere the ``jnp`` forms here. Rows ``t <
  index_topk`` are plainly causal. A bucket's padding lies past every true
  row, so no true row can choose it.
  A chunk that has history behind it (``pos > 0``) is NOT computed: the
  engine admits such a model a whole bucket at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.ops import pallas as pk
from cake_tpu.ops import quant
from cake_tpu.ops.attention import NEG_INF
from cake_tpu.ops.rope import apply_rope

K_NORM_EPS = 1e-6  # the indexer's LayerNorm (DeepSeek-V3.2's published layer)

# Query rows an admission scores, chooses and masks at a time where no
# kernel runs: a strip's scores are ``[heads, STRIP, T]`` float32.
STRIP = 128


def _strip(t: int) -> int:
    """The largest strip of at most ``STRIP`` rows that divides ``t``."""
    strip = min(t, STRIP)
    while t % strip:
        strip //= 2
    return strip


def layer_norm(x, weight, bias, eps: float = K_NORM_EPS):
    """LayerNorm over the last axis, in float32."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def index_projections(x, c_q, layer, cos, sin, pos, config):
    """The indexer's part of a token: ``(q_i [B, J, T, D], k_i [B, 1, T,
    D], w [B, T, J] float32)``: queries from the query latent ``c_q``, the
    one key behind its LayerNorm, both rotated on their first
    ``qk_rope_head_dim`` channels, and the heads' weights scaled."""
    b, t, _ = x.shape
    heads, dim = config.index_n_heads, config.index_head_dim
    q_i = quant.dense(c_q, layer["idx_wq_b"])
    # (the three products stand before a barrier, so that each is ONE
    # product on the matrix unit and the LayerNorm's sums and the rotation
    # read its result: the chip's compiler may fold a product into what
    # follows it. Measured, my chip runs, PR 61: a block's clear period
    # 94.05 -> 92.99 ms, a run each: inside the cell's spread)
    q_i, k_i, w = jax.lax.optimization_barrier((
        q_i, quant.dense(x, layer["idx_wk"]),
        quant.dense(x, layer["idx_w"])))
    q_i = apply_rope(q_i.reshape(b, t, heads, dim).transpose(0, 2, 1, 3),
                     cos, sin, pos, interleaved=True)
    k_i = layer_norm(k_i, layer["idx_k_norm"], layer["idx_k_bias"])[:, None]
    k_i = apply_rope(k_i, cos, sin, pos, interleaved=True)
    w = w.astype(jnp.float32) * (heads ** -0.5 * dim ** -0.5)
    return q_i, k_i, w


def index_scores(q_i, w, k_i):
    """``I [B, T, S]`` float32: ``sum_j w[b, t, j] relu(q_i[b, j, t] .
    k_i[b, s])``, unmasked. ``q_i [B, J, T, D]``, ``w [B, T, J]``, ``k_i
    [B, S, D]``."""
    dots = jnp.einsum("bjtd,bsd->bjts", q_i, k_i,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("bjts,btj->bts", jax.nn.relu(dots), w)


def choose(scores, k: int):
    """``(values, rows) [.., k]`` of the ``k`` largest of ``scores [..,
    S]``, a tie to the lower row (``lax.top_k``'s order)."""
    return jax.lax.top_k(scores, min(k, scores.shape[-1]))


def chosen_mask(scores, k: int):
    """The rows of ``scores [.., S]`` (``-inf`` where a row may not be
    chosen) among its ``k`` largest, a tie to the lower row, as a mask
    ``[.., S]``: those above the ``k``-th largest value, and of those
    equal to it the first until ``k`` are chosen. Rows of ``-inf`` are
    never chosen."""
    s = scores.shape[-1]
    if k >= s:
        return scores > -jnp.inf
    theta = jax.lax.top_k(scores, k)[0][..., -1:]
    above = scores > theta
    ties = scores == theta
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    first = jnp.cumsum(ties, axis=-1) <= room
    return (above | (ties & first)) & (scores > -jnp.inf)


def decode_attend(q_c, q_pe, q_i, w, row_cache, i_cache, pos, layer, *,
                  scale: float, topk: int):
    """One decode step's sparse absorbed attention. ``q_c [B, H, 1, dc]``
    (``q_nope`` through ``W_kvb``'s key half), ``q_pe [B, H, 1, dr]``,
    ``q_i [B, J, 1, D]``, ``w [B, 1, J]``; the carried buffers ``row_cache
    [L, B, 1, S, >= dc + dr]`` (``[c | k_pe | padding]`` a row) and
    ``i_cache [L, B, 1, S, D]`` with this step's rows written; ``pos [B]``
    or a scalar: each stream's frontier (its new row's position). Returns
    ``(m [B, H, 1, 1], l [B, H, 1, 1], o_c [B, H, 1, dc])`` float32 over
    the chosen rows: the scaled scores' maximum, the normalizer and the
    un-normalized output in latent space. The chosen rows reach the
    attention as a mask over the buffer or as gathered copies
    (:func:`attend_form_choice`): the same rows, the same softmax."""
    b, s = q_c.shape[0], row_cache.shape[-2]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    sweep = attend_form_choice(s, topk) == "sweep"
    # trace time: which form the decode program being built holds (read
    # beside the engine's dsa.rows_read / dsa.rows_selected)
    obs_metrics.gauge("dsa.attend_sweep").set(int(sweep))
    with jax.named_scope("dsa.index"):
        scores = decode_index_scores(q_i, w, i_cache, pos_b, layer)
    if sweep:
        with jax.named_scope("dsa.select"):
            kept = keep_chosen(scores, pos_b, topk)  # [B, S]
        with jax.named_scope("dsa.attend"):
            return attend_kept(q_c, q_pe, row_cache, kept, pos_b, layer,
                               scale)
    with jax.named_scope("dsa.select"):
        values, rows = choose(scores, topk)  # [B, K]
    with jax.named_scope("dsa.attend"):
        # the chosen rows alone leave the carried buffer: ONE gather of
        # [B, K] rows on the stacked buffer itself (no layer's slab is
        # sliced out for it; a gather costs a row whatever its width)
        lead = () if layer is None else (jnp.asarray(layer, jnp.int32),)
        at = lead + (jnp.arange(b, dtype=jnp.int32)[:, None], 0, rows)
        return attend_chosen(q_c, q_pe, row_cache[at], values, scale)


def keep_chosen(scores, pos_b, topk: int):
    """The sweep's choice: ``scores [B, S]`` (``-inf`` past a frontier)
    with every row that is not among its stream's ``topk`` made ``-inf``
    (:func:`chosen_mask`'s rows): the kernel's bisection where the shapes
    allow it, else the ``jnp`` form."""
    if select_kernel_choice(scores.shape[-1]) == "kernel":
        return pk.dsa_select(scores, pos_b, topk)
    return jnp.where(chosen_mask(scores, topk), scores, -jnp.inf)


def attend_kept(q_c, q_pe, row_cache, kept, pos_b, layer, scale: float):
    """The sweep's absorbed attention over the rows of the carried buffer
    that ``kept [B, S]`` keeps: the kernel, which reads each stream's
    blocks to its frontier, or XLA's form over the whole layer."""
    from cake_tpu.ops import kvcache as kv

    if attend_kernel_choice(row_cache.shape[-2], q_c.shape[-1]) == "kernel":
        return pk.dsa_attend(q_c[:, :, 0], q_pe[:, :, 0], row_cache, kept,
                             pos_b, scale=scale, layer=layer)
    return masked_attend(q_c, q_pe, kv.layer_view(row_cache, layer)[:, 0],
                         kept, scale)


def attend_chosen(q_c, q_pe, chosen, values, scale: float):
    """The gather's absorbed attention over the gathered rows ``chosen [B,
    K, row width]``: the kernel where the shapes allow it, else XLA's
    form."""
    if attend_kernel_choice(chosen.shape[1], q_c.shape[-1]) == "kernel":
        return pk.dsa_attend_gathered(q_c[:, :, 0], q_pe[:, :, 0], chosen,
                                      values, scale=scale)
    return masked_attend(q_c, q_pe, chosen, values, scale)


def masked_attend(q_c, q_pe, chosen, values, scale: float):
    """XLA's form of the absorbed attention over the rows ``chosen [B, K,
    >= dc + dr]`` (``[c | k_pe | padding]`` each; ``values [B, K]``: their
    index scores, ``-inf`` where one is not attended): the gathered rows,
    or a whole layer of the buffer under the kept scores. What
    :func:`cake_tpu.ops.pallas.dsa.dsa_attend` and ``dsa_attend_gathered``
    return."""
    from cake_tpu.ops.mla import masked_sweep

    dc, dr = q_c.shape[-1], q_pe.shape[-1]
    valid = (values > -jnp.inf)[:, None, None, :]
    m, p, o_c = masked_sweep(q_c, q_pe, chosen[..., :dc],
                             chosen[..., dc:dc + dr], valid, scale)
    return m, jnp.sum(p, axis=-1, keepdims=True), o_c


def decode_index_scores(q_i, w, i_cache, pos_b, layer):
    """``I [B, S]`` float32 of a decode step: each stream's query against
    the index keys of its rows up to its frontier ``pos_b [B]``, ``-inf``
    past it. On the chip the kernel reads the key blocks up to each
    frontier and no others; elsewhere XLA's einsum over the buffer,
    masked."""
    from cake_tpu.ops import kvcache as kv

    s, dim = i_cache.shape[-2], i_cache.shape[-1]
    kernel = index_kernel_choice(s, dim) == "kernel"
    obs_metrics.gauge("dsa.index_kernel").set(int(kernel))
    if kernel:
        return pk.dsa_index(q_i[:, :, 0], w[:, 0], i_cache, pos_b,
                            layer=layer)
    k_all = kv.layer_view(i_cache, layer)[:, 0]  # [B, S, D]
    scores = index_scores(q_i, w, k_all)[:, 0]
    live = jnp.arange(s, dtype=jnp.int32)[None, :] <= pos_b[:, None]
    return jnp.where(live, scores, -jnp.inf)


def index_kernel_choice(s: int, dim: int) -> str:
    """``"kernel"`` or ``"xla"`` for a decode step's index scores over
    ``s`` rows of ``dim``-wide keys, from the shapes a trace sees (the
    frontier is data)."""
    if not pk.kernels_enabled():
        return "xla"
    if pk.force_kernels() and pk.interpret_default():
        return "kernel"
    if s % pk.DECODE_BLOCK_K == 0 and dim % 128 == 0:
        return "kernel"
    return "xla"


# The buffer's rows ``S`` up to which a decode step sweeps the carried rows
# under a mask, and past which it gathers the chosen ones: the LARGEST
# buffer measured, not a crossing: the sweep won at every one
# (``tools/dsa_sweep.py --rows ..`` on v5 lite, PR 62, GLM-5's widths, the
# streams that hold 262,144 rows in all, every stream at the frontier; us
# a layer, index scores + choice + attention, the gather form -> the sweep):
#   S 16,384 (16 streams): frontier 2048 1320 -> 505; 8192 1298 -> 698;
#     16,000 1312 -> 989
#   S 32,768 (8): 8192 1004 -> 549; 16,000 1044 -> 709; 32,000 1079 -> 990
#   S 65,536 (4): 16,000 1099 -> 575; 32,000 1135 -> 741; 65,000 1163 -> 1098
#   S 131,072 (2): 16,000 1527 -> 506; 65,000 1544 -> 706; 131,000 1618 -> 1044
# (the gather's sort grows with S: 290 / 311 / 550 / 1037 us; the swept
# attention 36 us a thousand rows of frontier over 16 streams)
SWEEP_MAX_ROWS = 131072


def attend_form_choice(s: int, topk: int) -> str:
    """``"sweep"`` or ``"gather"``: how a decode step's chosen rows reach
    its attention over a buffer of ``s`` rows, from the shapes a trace
    sees (the frontier is data, and a branch on it in the scanned layer
    body is what the chip's compiler answers with copies of the carried
    buffers). ``"sweep"``: a threshold (no sort), then the buffer's blocks
    to each frontier under the mask. ``"gather"``: ``lax.top_k``, the
    gather of ``topk`` rows a stream, the attention over the copies."""
    return "sweep" if s <= max(topk, SWEEP_MAX_ROWS) else "gather"


def rows_fetched(live, s: int, topk: int):
    """The latent rows a decode step's attention fetches for a stream that
    holds ``live`` rows (a numpy array of them) of a buffer of ``s``: the
    sweep's whole blocks to the frontier, or the gather's chosen rows
    (the engine's ``dsa.rows_read``, from the positions as dispatched)."""
    live = np.asarray(live)
    if attend_form_choice(s, topk) == "gather":
        return np.minimum(live, topk)
    block = pk.dsa_attend_block(s)
    return ((live - 1) // block + 1) * block


def select_kernel_choice(s: int) -> str:
    """``"kernel"`` or ``"xla"`` for the sweep's choice among ``s``
    scores a stream."""
    if not pk.kernels_enabled():
        return "xla"
    if pk.force_kernels() and pk.interpret_default():
        return "kernel"
    # whole lane tiles, and whole stretches of the kernel's 2048 columns
    return "kernel" if s % 128 == 0 and s % min(2048, s) == 0 else "xla"


def attend_kernel_choice(k: int, dc: int) -> str:
    """``"kernel"`` or ``"xla"`` for a decode step's absorbed attention
    over ``k`` rows (the chosen ones, gathered, or the buffer's) of ``dc``
    latent values."""
    if not pk.kernels_enabled():
        return "xla"
    if pk.force_kernels() and pk.interpret_default():
        return "kernel"
    return "kernel" if k % 128 == 0 and dc % 128 == 0 else "xla"


def prefill_kernel_choice(t: int, d_qk: int, d_v: int, dim: int) -> str:
    """``"kernel"`` or ``"xla"`` for an admission chunk of ``t`` rows: the
    choice's kernel takes index keys of whole lane tiles and whole blocks
    of rows, the masked sweep's heads as wide for keys as for values; a
    program runs both or neither."""
    if not pk.kernels_enabled():
        return "xla"
    if pk.force_kernels() and pk.interpret_default():
        return "kernel" if d_qk == d_v else "xla"
    if d_qk == d_v and d_v % 128 == 0 and dim % 128 == 0 and t % 2048 == 0:
        return "kernel"
    return "xla"


def prefill_mask(q_i, w, k_i, topk: int, *, kernel: bool = False):
    """An admission chunk's chosen rows as a mask ``[B, T, T]`` int8 (row
    ``t`` may attend row ``s``), from position 0. ``q_i [B, J, T, D]``, ``w
    [B, T, J]``, ``k_i [B, T, D]``. ``kernel``: the kernel that scores a
    block of query rows, bisects each row's threshold and writes its mask
    with the scores in VMEM alone (``dsa_prefill_select``: nothing is
    sorted). Elsewhere a strip of query rows at a time: the strip's index
    scores ``[B, STRIP, T]`` (causal, ``-inf`` above the diagonal) and
    each row's choice of ``topk`` among them (:func:`chosen_mask`)."""
    b, j, t, dim = q_i.shape
    if kernel:
        with jax.named_scope("dsa.select"):
            return pk.dsa_prefill_select(q_i, w, k_i, topk)
    strip = _strip(t)
    n = t // strip
    col = jnp.arange(t, dtype=jnp.int32)

    def one(args):
        q_s, w_s, first = args  # [B, J, strip, D], [B, strip, J], []
        with jax.named_scope("dsa.index"):
            scores = index_scores(q_s, w_s, k_i)
            row = first + jnp.arange(strip, dtype=jnp.int32)
            scores = jnp.where(col[None, None, :] <= row[None, :, None],
                               scores, -jnp.inf)
        with jax.named_scope("dsa.select"):
            return chosen_mask(scores, topk).astype(jnp.int8)

    q_strips = q_i.reshape(b, j, n, strip, dim).transpose(2, 0, 1, 3, 4)
    w_strips = w.reshape(b, n, strip, j).transpose(1, 0, 2, 3)
    firsts = jnp.arange(n, dtype=jnp.int32) * strip
    masks = jax.lax.map(one, (q_strips, w_strips, firsts))  # [n, B, strip, T]
    return masks.transpose(1, 0, 2, 3).reshape(b, t, t)


def prefill_attend(q, k, v, mask, *, scale: float, kernel: bool = False):
    """An admission chunk's expanded attention under each row's choice.
    ``q [B, H, T, d_qk]``, ``k [B, H, T, d_qk]``, ``v [B, H, T, d_v]``,
    ``mask [B, T, T]`` int8 (causal and chosen). Returns ``[B, H, T,
    d_v]`` in ``q``'s type. ``kernel``: the flash sweep under the mask
    (``dsa_prefill_attend``); elsewhere a strip of query rows at a time,
    float32 scores ``[B, H, strip, T]``."""
    b, h, t, d_qk = q.shape
    d_v = v.shape[-1]
    with jax.named_scope("dsa.attend"):
        if kernel:
            return pk.dsa_prefill_attend(q, k, v, mask, scale=scale)
        strip = _strip(t)
        n = t // strip

        def one(args):
            q_s, m_s = args  # [B, H, strip, d_qk], [B, strip, T]
            sc = jnp.einsum("bhtd,bhsd->bhts", q_s, k,
                            preferred_element_type=jnp.float32) * scale
            sc = jnp.where(m_s[:, None] != 0, sc, NEG_INF)
            p = jax.nn.softmax(sc, axis=-1)
            return jnp.einsum("bhts,bhsv->bhtv", p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32
                              ).astype(q.dtype)

        q_strips = q.reshape(b, h, n, strip, d_qk).transpose(2, 0, 1, 3, 4)
        m_strips = mask.reshape(b, n, strip, t).transpose(1, 0, 2, 3)
        out = jax.lax.map(one, (q_strips, m_strips))  # [n, B, H, strip, dv]
        return out.transpose(1, 2, 0, 3, 4).reshape(b, h, t, d_v)
