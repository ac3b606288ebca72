"""Multi-head latent attention (DeepSeek-V2/V3's MLA) over a latent cache.

The reference serves one attention (`cake-core/src/model/attention.rs`,
per-head keys and values); this is the family whose cache is one row a
token a layer, shared by every head. Per token ``x``:

    c_q            = rmsnorm(x W_qa)                      [q_lora_rank]
    [q_nope|q_pe]_h = c_q W_qb                            heads x (nope + rope)
                     (or x W_q directly where q_lora_rank is null)
    [c | k_pe]     = x W_kva                              kv_lora_rank + rope
    c              = rmsnorm(c);  q_pe, k_pe = rope(.)    interleaved pairs
    [k_nope|v]_h   = c W_kvb                              heads x (nope + v)
    score_h        = (q_nope_h . k_nope_h + q_pe_h . k_pe) * scale
    out            = concat_h(softmax(score_h) v_h) W_o
                     (each head times sigmoid(x W_g)_h first, where the
                     layer has a head-wise output gate ``wg``)

``scale`` is ``(nope + rope)^-0.5`` times YaRN's ``mscale^2``
(``LlamaConfig.attn_scale``). **Cached: ``[c | k_pe]``** (after the norm,
after rope): ``kv_lora_rank + qk_rope_head_dim`` values a token a layer in
the two buffers of :class:`cake_tpu.ops.kvcache.KVCache` (``k`` holds ``c``,
``v`` holds ``k_pe``, one "head"), written in place on the carried stacked
cache exactly as per-head rows are (:func:`kvcache.update_layer`).

Two forms of the same mathematics, chosen at trace time:

- **absorbed** (against cached rows): the up-projection moves into the
  query and the output, ``q'_h = q_nope_h W_kvb,k,h^T`` scores against ``c``
  itself and ``o_h = (softmax . c) W_kvb,v,h``, so no key or value is ever
  expanded per head for a cached token. A decode step (``T == 1``) is this
  form: on the chip, from ``LATENT_DECODE_MIN_S`` rows up, the kernel
  :func:`cake_tpu.ops.pallas.latent.latent_decode` over each stream's
  blocks up to its frontier, fetched once out of the carried buffers;
  elsewhere XLA's einsums over the whole buffer, masked.
- **expanded** (a chunk's own tokens, ``T > 1``): the chunk's ``k_nope``
  and ``v`` are expanded from its own ``c`` and attended causally,
  ``T x T``. What the chunk has behind it in the cache (positions below
  ``pos``: a prefix hit, an earlier chunk) is attended in the absorbed
  form and the two partial softmaxes are merged exactly; that part is
  skipped at run time when there is no history (``lax.cond``), which is
  every first chunk. From ``LATENT_ADMIT_BLOCK_MIN_T`` rows on the chunk
  is **blocked by query rows** (:func:`latent_admit_choice`), so that no
  ``[B, H, T, T]`` array is built (float32: 1.07 GB at 2048 rows, 17 GB at
  8192): a first chunk's own tokens by the flash prefill kernel over the
  expanded keys, their 192 channels zero-padded to 256 (a zero channel
  adds nothing to a product; the values stay 128 wide); a chunk with
  history behind it, and every such chunk where no kernel runs, a strip
  of query rows at a time (:func:`_strips`: float32 ``[B, H, strip, T]``
  scores of its own tokens and ``[B, H, strip, S]`` of the history, the
  two merged exactly as above).

Where the layer holds an indexer (``idx_*``: a learned sparse attention,
:mod:`cake_tpu.ops.dsa`) both forms attend a CHOICE of the rows: a decode
step the ``index_topk`` rows of highest index score, gathered out of the
carried buffers (the absorbed form over them alone), a chunk from
position 0 under each row's mask (the expanded form, blocked by query
rows); the indexer's key of each token is written into a third buffer
beside the latent row, and ``[c | k_pe]`` lie in ONE row of the first
buffer, padded to whole lane tiles (``LlamaConfig.cache_row``: a gather
costs a row whatever its width, so a chosen row is fetched once; the
second buffer is empty).

Weights go through :func:`cake_tpu.ops.quant.dense` wherever they are used
as a plain projection; the absorbed form contracts ``W_kvb`` over its
other axis, so an int8 ``W_kvb`` is dequantized at trace level there (the
convert and multiply fuse into the einsum's operand read).
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp

from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.ops import dsa
from cake_tpu.ops import kvcache as kv
from cake_tpu.ops import pallas as pk
from cake_tpu.ops import quant
from cake_tpu.ops.attention import NEG_INF
from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.rope import apply_rope


log = logging.getLogger("cake_tpu.mla")

# A decode step (T == 1) over a latent cache: XLA's einsums sweep the whole
# reserved buffer, twice, whatever the frontier; the kernel
# (ops/pallas/latent.py) fetches each stream's blocks of
# ``pk.DECODE_BLOCK_K`` rows up to its frontier, once.
# tools/flash_sweep.py --only served-latent on v5 lite (B 32, 512 + 64
# values a row, a layer's time inside a walk over 8 layers, PR 44; two
# calls agree to 2 us), kernel at 512-row blocks / XLA, us a layer:
#
# - S 4096 (both latent cells): 64 heads 57 / 408 at the frontiers a
#   `decode-full` batch has (64-700; every frontier at 64: 51, at 300: 50,
#   at 704: 83), 275 / 414 with every frontier at the buffer's end (the
#   cost side: XLA reads the 134 MB latent buffer twice, the kernel once,
#   1.07 us a 0.66 MB block); 32 heads 51 / 402 and 261 / 402. In the
#   served `axk1-ep16-cut` step XLA's two fusions took 359 us a layer and
#   the call takes 39 (PERF.md section 5).
# - S 2048: 55 / 216 and 149 / 216 (64 heads), 52 / 213 and 140 / 211
#   (32); S 1024: 56 / 118 and 82 / 126, 50 / 117 and 78 / 117: ahead at
#   the end too, so the floor is where skipping begins: S 512 is one
#   block, read whole by either.
# - blocks of 256 rows: 59 at the served frontiers and 385 at the end of
#   4096 (a block's two products no longer hide behind its fetch); 1024
#   rows: 71 served (1024 rows read for a 300-row stream) and 229 at the
#   end. So 512, which is also what the engine's attn.kv_blocks_* count.
#
# The frontier is data, so a full cache runs the kernel too, at two
# thirds of XLA's cost.
LATENT_DECODE_MIN_S = 1024

# An admission chunk (T > 1) of a layer with no indexer: under this many
# rows the chunk's own tokens are attended in ONE piece (float32 scores
# ``[B, H, T, T]``: the form every plain-latent cell's 16-512-row buckets
# compile), from it on blocked by query rows.
# tools/flash_sweep.py --only latent-admit on v5 lite (B 1, 64 heads of
# 128 + 64 keys and 128 values, bf16, one set of operands, ms a call; my
# chip run, PR 64; PERF.md section 6), whole / strip / flash:
#
# - T 512: 0.310 / 0.176 / 0.209; T 1024: 2.087 / 0.572 / **0.547**; T
#   2048: 8.089 / 5.135 / **2.139**; T 4096: 31.73 / 35.26 / **5.586**; T
#   8192: - (17 GB of scores) / 150.1 / **17.74**. The three agree to one
#   bfloat16 step of the output (0.002).
# - the whole form grows with T^2 in HBM traffic (the float32 scores are
#   written and read twice) and so do the strips (150 ms at 8192: 0.8 GB a
#   strip of 128 rows); the kernel keeps a block's scores in VMEM and skips
#   the blocks above the diagonal: 62 TFLOP/s of the TRUE causal products
#   at 8192 rows (32% of the chip's peak; it also computes 64 zero
#   channels a key and the diagonal blocks whole).
# - both blocked forms are ahead at 512 rows too (1.5-1.8x); the floor
#   stands at 1024 all the same: 512 rows is where the cells of the three
#   older plain-latent configurations end, and their programs stay the
#   ones they were measured with.
LATENT_ADMIT_BLOCK_MIN_T = 1024
# float32 score bytes a strip of query rows may take (its own tokens' and
# its history's, each)
ADMIT_STRIP_BYTES = 256 << 20
ADMIT_STRIP = 128  # query rows a strip holds at most


def latent_decode_choice(s: int, dc: int, dr: int) -> str:
    """``"kernel"`` or ``"xla"`` for a single-token (T == 1) absorbed
    attention over an ``s``-row latent cache of ``dc`` latent and ``dr``
    rope values a row: THE latent decode policy, from what a trace can
    see of its input (the shapes; the frontier is data).
    :func:`latent_attention_block` asks it, once for each decode program
    traced, and publishes the answer (``attn.decode_kernel``)."""
    if not pk.kernels_enabled():
        return "xla"
    if pk.force_kernels() and pk.interpret_default():
        return "kernel"  # interpreted: any shape
    # whole blocks of the measured size (what the engine's
    # attn.kv_blocks_* count), latent rows that fill their lanes, and a
    # rope half under a lane tile, which the chip stores rows-on-lanes:
    # the layout the kernel takes it in (ops/pallas/latent.py)
    whole = (s % pk.DECODE_BLOCK_K == 0 and dc % 128 == 0
             and dr % 16 == 0 and dr < 128)
    if whole and (pk.force_kernels() or s >= LATENT_DECODE_MIN_S):
        return "kernel"
    if pk.force_kernels():
        log.warning(
            "kernels forced (CAKE_PALLAS=1) but the latent decode shape "
            "(S=%d, kv_lora_rank=%d, rope=%d) is not one the kernel "
            "serves (S%%%d==0, kv_lora_rank%%128==0, rope%%16==0 and "
            "under 128); falling back to the XLA path", s, dc, dr,
            pk.DECODE_BLOCK_K)
    return "xla"


def latent_admit_choice(t: int, d_qk: int) -> str:
    """``"whole"``, ``"flash"`` or ``"strip"`` for an admission chunk of
    ``t`` rows a stream, keys ``d_qk`` wide, of a latent layer with no
    indexer: THE plain latent admission's policy, from what a trace can see of its input (the
    shapes; whether a chunk has history behind it is data, and a blocked
    chunk that has is swept in strips at run time whatever this says).
    :func:`latent_attention_block` asks it, once for each admission
    program traced, and publishes the answer (``attn.admit_blocked``,
    ``attn.admit_blocked_min_rows``)."""
    if t < LATENT_ADMIT_BLOCK_MIN_T:
        return "whole"
    if not pk.kernels_enabled():
        return "strip"
    if pk.force_kernels() and pk.interpret_default():
        return "flash"
    # whole blocks of query rows; any key width pads to whole lane tiles
    return "flash" if t % 128 == 0 and d_qk <= 256 else "strip"


def _strip_rows(b: int, h: int, t: int, s: int) -> int:
    """Query rows a strip holds: the largest power of two up to
    ``ADMIT_STRIP`` that divides ``t`` and keeps a strip's float32 scores
    against ``s`` rows within ``ADMIT_STRIP_BYTES``."""
    strip = ADMIT_STRIP
    while strip > 1 and (t % strip or b * h * strip * s * 4
                         > ADMIT_STRIP_BYTES):
        strip //= 2
    return strip


def chunk_whole(q_nope, q_pe, k_nope, k_pe, v_own, scale):
    """A chunk's own tokens, expanded and causal among themselves, in one
    piece: ``q_nope [B, H, T, dn]``, ``q_pe [B, H, T, dr]``, ``k_nope [B,
    H, T, dn]``, ``k_pe [B, 1, T, dr]`` (one for all heads), ``v_own [B, H,
    T, dv]``. Returns the scaled scores' row maximum, the normalizer and
    the un-normalized output, float32."""
    t = q_nope.shape[2]

    def causal():
        qi = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
        return ki <= qi

    return _own_part(q_nope, q_pe, k_nope, k_pe, v_own, scale, causal)


def _own_part(q_nope, q_pe, k_nope, k_pe, v_own, scale, keep):
    """Query rows ``q_nope`` / ``q_pe`` (all of a chunk's, or a strip of
    them) against the chunk's expanded keys and values under the mask
    ``keep()`` (``[T', T]``, made once the scores are): row maximum,
    normalizer, un-normalized output, float32."""
    sc = (jnp.einsum("bhtn,bhun->bhtu", q_nope, k_nope,
                     preferred_element_type=jnp.float32)
          + jnp.einsum("bhtr,bur->bhtu", q_pe, k_pe[:, 0],
                       preferred_element_type=jnp.float32)) * scale
    sc = jnp.where(keep(), sc, NEG_INF)
    m_o = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.exp(sc - m_o)
    l_o = jnp.sum(p, axis=-1, keepdims=True)
    o_o = jnp.einsum("bhtu,bhuv->bhtv", p.astype(v_own.dtype), v_own,
                     preferred_element_type=jnp.float32)
    return m_o, l_o, o_o


def chunk_flash(q_nope, q_pe, k_nope, k_pe, v_own, scale):
    """A first chunk's own tokens by the flash prefill kernel over the
    expanded keys ``[k_nope | k_pe]``, queries and keys zero-padded to
    whole lane tiles (192 -> 256; the scale stays the true width's).
    Returns the normalized output ``[B, H, T, dv]`` in the inputs' type:
    the kernel keeps its running maximum and normalizer to itself, so a
    chunk with history takes :func:`_strips`."""
    b, h, t, dn = q_nope.shape
    dr = q_pe.shape[-1]
    pad = -(dn + dr) % 128
    zeros = jnp.zeros((b, h, t, pad), q_nope.dtype)
    q = jnp.concatenate([q_nope, q_pe, zeros], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (b, h, t, dr)), zeros], -1)
    return pk.flash_attention(q, k, v_own, 0, scale=scale,
                              name="latent_prefill")


def _strips(q_nope, q_pe, k_nope, k_pe, v_own, scale, history=None,
            behind: int = 0):
    """A chunk's attention a strip of query rows at a time: each strip's
    own-chunk part as :func:`chunk_whole` computes it (float32 ``[B, H,
    strip, T]``; the arguments are its) and, with ``history(q_nope_s,
    q_pe_s) -> (m, l, o)`` (the absorbed sweep of the ``behind`` rows the
    buffer holds), that part merged in. Returns the normalized output ``[B, H, T, dv]``, float32."""
    b, h, t, dn = q_nope.shape
    dr, dv = q_pe.shape[-1], v_own.shape[-1]
    strip = _strip_rows(b, h, t, max(t, behind))
    n = t // strip
    col = jnp.arange(t, dtype=jnp.int32)

    def one(args):
        qn_s, qp_s, first = args  # [B, H, strip, dn], [.., dr], []
        row = first + jnp.arange(strip, dtype=jnp.int32)
        m_o, l_o, o_o = _own_part(
            qn_s, qp_s, k_nope, k_pe, v_own, scale,
            lambda: col[None, :] <= row[:, None])
        if history is None:
            return o_o / l_o
        m_h, l_h, o_h = history(qn_s, qp_s)
        m = jnp.maximum(m_o, m_h)
        a_o, a_h = jnp.exp(m_o - m), jnp.exp(m_h - m)
        return (o_o * a_o + o_h * a_h) / (l_o * a_o + l_h * a_h)

    def strips(x):
        return x.reshape(b, h, n, strip, x.shape[-1]).transpose(2, 0, 1, 3, 4)

    out = jax.lax.map(one, (strips(q_nope), strips(q_pe),
                            jnp.arange(n, dtype=jnp.int32) * strip))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, t, dv)


def masked_sweep(q_c, q_pe, c_all, r_all, valid, scale):
    """XLA's form of the absorbed sweep: the absorbed query ``q_c [B, H, T,
    dc]`` and the roped ``q_pe [B, H, T, dr]`` against ALL of one layer's
    rows ``c_all [B, S, dc]`` / ``r_all [B, S, dr]``, masked to those
    ``valid`` (``[B|1, 1, T, S]``) admits. Returns the scaled scores' row
    maximum, the un-normalized probabilities ``[B, H, T, S]`` (the
    normalizer is their sum) and the un-normalized output in latent space
    ``[B, H, T, dc]``, all float32."""
    sc = (jnp.einsum("bhtc,bsc->bhts", q_c, c_all,
                     preferred_element_type=jnp.float32)
          + jnp.einsum("bhtr,bsr->bhts", q_pe, r_all,
                       preferred_element_type=jnp.float32)) * scale
    sc = jnp.where(valid, sc, NEG_INF)
    m = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.exp(sc - m)
    o_c = jnp.einsum("bhts,bsc->bhtc", p.astype(c_all.dtype), c_all,
                     preferred_element_type=jnp.float32)
    return m, p, o_c


def _plain(w, dtype):
    if isinstance(w, quant.QuantizedLinear):
        return quant.dequantize_linear(w, dtype)
    return w


def latent_attention_block(
    x: jax.Array,  # [B, T, hidden]
    layer: dict,  # wq_a, q_norm, wq_b, wkv_a, kv_norm, wkv_b, wo
    c_cache: jax.Array,  # [(L,) B, 1, S, kv_lora_rank]
    r_cache: jax.Array,  # [(L,) B, 1, S, qk_rope_head_dim]
    cos: jax.Array,
    sin: jax.Array,
    pos,  # scalar or [B]
    config,
    write_gate: jax.Array | None = None,
    layer_idx: jax.Array | None = None,
    i_cache: jax.Array | None = None,  # [(L,) B, 1, S, index_head_dim]
) -> tuple[jax.Array, ...]:
    """One latent-attention sublayer incl. the cache write. Returns
    ``(attn_out [B, T, hidden], c_cache, r_cache)``; the buffers come back
    whole with this layer's ``T`` new rows written. A layer that holds an
    indexer takes ``i_cache`` too and returns it fourth, its index keys
    written."""
    b, t, _ = x.shape
    nh = config.num_attention_heads
    dn, dr = config.qk_nope_head_dim, config.qk_rope_head_dim
    dv, dc = config.v_head_dim, config.kv_lora_rank
    eps, scale = config.rms_norm_eps, config.attn_scale

    if "wq_a" in layer:
        c_q = rms_norm(quant.dense(x, layer["wq_a"]), layer["q_norm"], eps)
        q = quant.dense(c_q, layer["wq_b"])
        if i_cache is not None:
            with jax.named_scope("dsa.index"):
                q_i, k_i, w_i = dsa.index_projections(
                    x, c_q, layer, cos, sin, pos, config)
    else:  # q_lora_rank null: one direct projection, no bottleneck
        q = quant.dense(x, layer["wq"])
    q = q.reshape(b, t, nh, dn + dr)
    q = q.transpose(0, 2, 1, 3)  # [B, H, T, nope + rope]
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    ckv = quant.dense(x, layer["wkv_a"])  # [B, T, dc + dr]
    c = rms_norm(ckv[..., :dc], layer["kv_norm"], eps)[:, None]  # [B,1,T,dc]
    q_pe = apply_rope(q_pe, cos, sin, pos, interleaved=True)
    k_pe = apply_rope(ckv[..., dc:][:, None], cos, sin, pos,
                      interleaved=True)  # [B, 1, T, dr], one for all heads

    if i_cache is None:
        c_cache, r_cache = kv.update_layer(c_cache, r_cache, c, k_pe, pos,
                                           gate=write_gate, layer=layer_idx)
    else:  # [c | k_pe | 0..] in ONE row of the first buffer, the second empty
        pad = jnp.zeros(c.shape[:-1] + (c_cache.shape[-1] - dc - dr,), c.dtype)
        c_cache, r_cache, i_cache = kv.update_layer(
            c_cache, r_cache, jnp.concatenate([c, k_pe, pad], -1),
            k_pe[..., :0], pos, gate=write_gate, layer=layer_idx,
            index=(i_cache, k_i))
    c_all = kv.layer_view(c_cache, layer_idx)[:, 0]  # [B, S, dc]
    r_all = kv.layer_view(r_cache, layer_idx)[:, 0]  # [B, S, dr]
    s = c_all.shape[1]

    w_kvb = _plain(layer["wkv_b"], x.dtype).reshape(dc, nh, dn + dv)
    w_k, w_v = w_kvb[..., :dn], w_kvb[..., dn:]
    pos = jnp.asarray(pos, jnp.int32)
    pos_b = pos[:, None, None, None] if pos.ndim else pos  # over [B,H,T,S]

    def cached(valid, q_nope=q_nope, q_pe=q_pe):
        """Absorbed attention against the cached rows ``valid`` admits
        (``[B|1, 1, T, S]``): row maximum, normalizer and the un-normalized
        output ``[B, H, T, dv]``, all float32. ``valid`` None (``T == 1``):
        the rows up to each stream's frontier, by the kernel, which reads
        those rows' blocks and no others, once, out of the carried
        buffers themselves; a mask sweeps the whole buffer, twice.
        ``q_nope`` / ``q_pe``: a strip of the chunk's query rows in place
        of all of them."""
        q_c = jnp.einsum("bhtn,chn->bhtc", q_nope, w_k)
        if valid is None:
            m, l, o_c = pk.latent_decode(q_c[:, :, 0], q_pe[:, :, 0], c_cache,
                                         r_cache, pos, scale=scale,
                                         layer=layer_idx)
        else:
            m, p, o_c = masked_sweep(q_c, q_pe, c_all, r_all, valid, scale)
        o = jnp.einsum("bhtc,chv->bhtv", o_c.astype(x.dtype), w_v,
                       preferred_element_type=jnp.float32)
        if valid is not None:  # summed here, where the lowered text had it
            l = jnp.sum(p, axis=-1, keepdims=True)
        return m, l, o

    kpos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, t, s), 3)
    if i_cache is not None and t == 1:
        # the rows the indexer chooses, and no others, out of the buffer
        q_c = jnp.einsum("bhtn,chn->bhtc", q_nope, w_k)
        m, l, o_c = dsa.decode_attend(
            q_c, q_pe, q_i, w_i, c_cache, i_cache, pos, layer_idx,
            scale=scale, topk=config.index_topk)
        out = jnp.einsum("bhtc,chv->bhtv", o_c.astype(x.dtype), w_v,
                         preferred_element_type=jnp.float32) / l
    elif i_cache is not None:
        # a chunk from position 0 (the engine admits such a model a whole
        # bucket at a time): the chunk's own keys and values expanded,
        # each row under its own choice
        kv_own = quant.dense(c[:, 0], layer["wkv_b"]).reshape(
            b, t, nh, dn + dv).transpose(0, 2, 1, 3)
        k_own = jnp.concatenate(
            [kv_own[..., :dn], jnp.broadcast_to(k_pe, (b, nh, t, dr))], -1)
        kernel = dsa.prefill_kernel_choice(
            t, dn + dr, dv, config.index_head_dim) == "kernel"
        mask = dsa.prefill_mask(q_i, w_i, k_i[:, 0], config.index_topk,
                                kernel=kernel)
        out = dsa.prefill_attend(
            jnp.concatenate([q_nope, q_pe], -1), k_own, kv_own[..., dn:],
            mask, scale=scale, kernel=kernel)
    elif t == 1:
        kernel = latent_decode_choice(s, dc, dr) == "kernel"
        # trace time: which attention the decode program being built
        # holds (read beside the engine's attn.kv_blocks_* counts)
        obs_metrics.gauge("attn.decode_kernel").set(int(kernel))
        m, l, o = cached(None if kernel else kpos <= pos_b)
        out = o / l
    else:
        # the chunk's own tokens, expanded and causal among themselves
        kv_own = quant.dense(c[:, 0], layer["wkv_b"]).reshape(
            b, t, nh, dn + dv).transpose(0, 2, 1, 3)
        own = (q_nope, q_pe, kv_own[..., :dn], k_pe, kv_own[..., dn:], scale)
        form = latent_admit_choice(t, dn + dr)
        # trace time: which admission the program being built holds
        obs_metrics.gauge("attn.admit_blocked").set(int(form != "whole"))
        if form == "whole":
            m_o, l_o, o_o = chunk_whole(*own)

            # what lies behind the chunk in the cache, absorbed; nothing
            # does on a first chunk, and then this sweep of the buffer is
            # not run
            def history(_):
                return cached(kpos < pos_b)

            def no_history(_):
                return (jnp.full((b, nh, t, 1), NEG_INF, jnp.float32),
                        jnp.zeros((b, nh, t, 1), jnp.float32),
                        jnp.zeros((b, nh, t, dv), jnp.float32))

            m_h, l_h, o_h = jax.lax.cond(jnp.any(pos > 0), history,
                                         no_history, None)
            m = jnp.maximum(m_o, m_h)
            a_o, a_h = jnp.exp(m_o - m), jnp.exp(m_h - m)
            out = (o_o * a_o + o_h * a_h) / (l_o * a_o + l_h * a_h)
        else:
            # ... and from how many rows the blocked one was taken
            gauge = obs_metrics.gauge("attn.admit_blocked_min_rows")
            gauge.set(min(t, gauge.value or t))
            with jax.named_scope("mla.admit_blocked"):
                behind = kpos[:, :, :1] < pos_b  # [B|1, 1, 1, S]: any row's

                def with_history(_):
                    return _strips(*own, history=lambda qn, qp: cached(
                        behind, qn, qp), behind=s)

                def first_chunk(_):
                    if form == "flash":
                        return chunk_flash(*own).astype(jnp.float32)
                    return _strips(*own)

                out = jax.lax.cond(jnp.any(pos > 0), with_history,
                                   first_chunk, None)

    out = out.astype(x.dtype).transpose(0, 2, 1, 3)  # [B, T, H, dv]
    if "wg" in layer:  # a sigmoid gate a head on the heads' outputs
        gate = jax.nn.sigmoid(quant.dense(x, layer["wg"]).astype(jnp.float32))
        out = (out * gate[..., None]).astype(x.dtype)
    out = out.reshape(b, t, nh * dv)
    out = quant.dense(out, layer["wo"])
    if i_cache is None:
        return out, c_cache, r_cache
    return out, c_cache, r_cache, i_cache
