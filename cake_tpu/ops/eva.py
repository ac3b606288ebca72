"""EVA attention: an exact window that RESETS and learned summaries of the
windows before it, under one softmax (EvaByte, ``model_type`` "evabyte").

With ``W = window_size``, ``C = chunk_size``, ``s = head_dim^-0.5`` and two
learned vectors a head, ``phi_h`` and ``mu_h`` (rotated keys ``k``, values
``v``; chunk ``c`` is positions ``[cC, (c + 1)C)``):

    summary of chunk c:  a_m = softmax over m in c of (s * phi_h . k_m)
                         v~_c = sum_m a_m v_m        k~_c = mean_m k_m + mu_h
    local  L_n = {m : m // W == n // W, m <= n}
    remote R_n = {c : (c + 1) C <= (n // W) W}
    out_n = softmax over L_n and R_n TOGETHER of (s q_n . k_m | s q_n . k~_c)
            applied to (v_m | v~_c)

(the final estimator of "Efficient Attention via Control Variates",
arXiv:2302.04542, in the causal, windowed form the release describes; the
readings the published file leaves open are the benchmark configuration's
``assumed``). Softmax statistics and the summaries' sums are float32.

What a stream holds (``LlamaConfig.cache_plan``): a ring of ``W`` rows a
layer, position ``p`` at row ``p % W`` (``ring``), and ONE summary row for
every ``C`` positions, chunk ``c`` at row ``c`` (``summary``). Because the
window resets, the ring's live rows are the prefix ``0 .. p % W``; because
a chunk becomes visible when its WINDOW completes, the visible summaries
are the prefix ``0 .. (p // W) (W // C) - 1``: two frontiers, no mask by
position.

- :func:`summarise`: chunks -> ``(k~, v~)``.
- the step (``T == 1``): write row ``p % W``, refresh summary row ``p // C``
  from the ring's rows of the current chunk (every step, no branch: a
  summary is masked until its window completes, by which time its last
  refresh saw the whole chunk), attend both buffers to their frontiers:
  the kernel :func:`cake_tpu.ops.pallas.eva.eva_decode`, or two masked
  products merged by their statistics (``ops/ring.py``) where no kernel
  is built.
- the admission (``T > 1``, from position 0: a prompt is admitted whole):
  the prompt's windows one after another, each a causal block whose
  buffer holds the summaries of the windows before it AHEAD of its own
  keys (:func:`eva_prefill`), through the flash prefill kernel from
  ``ops.attention``'s floor on; then the newest window's true rows enter
  the ring and every chunk's summary its row (a bucket's padding enters
  neither: ``valid``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.ops import kvcache as kv
from cake_tpu.ops import pallas as pk
from cake_tpu.ops import quant
from cake_tpu.ops import ring as stats
from cake_tpu.ops.attention import (_attend_xla, _flash_prefill_choice,
                                    _project_heads)
from cake_tpu.ops.pallas.eva import (EVA_BLOCK_K, eva_block_counts,
                                     eva_decode)
from cake_tpu.ops.rope import apply_rope

# rows a flash prefill's buffer is padded to (behind the queries: causally
# masked and, past the frontier's block, not fetched), so that its key
# blocks stay 512 rows whatever the number of summaries ahead
PREFILL_PAD_ROWS = 512


def summarise(k: jax.Array, v: jax.Array, phi: jax.Array, mu: jax.Array,
              chunk: int, valid=None) -> tuple[jax.Array, jax.Array]:
    """The summary rows of ``k``/``v [B, H, T, D]`` (rotated keys; ``T`` a
    whole number of chunks): ``(k~, v~) [B, H, T // chunk, D]`` in the
    inputs' type, the sums in float32. ``phi``/``mu [H, D]``. ``valid
    [B]``: the rows' true tokens; a position at or past it takes no part
    (a chunk with none gives ``v~ = 0`` and ``k~ = mu``: nobody's to
    read)."""
    b, h, t, d = k.shape
    n = t // chunk
    with jax.named_scope("eva_summarise"):
        kc = k.reshape(b, h, n, chunk, d).astype(jnp.float32)
        vc = v.reshape(b, h, n, chunk, d).astype(jnp.float32)
        logit = jnp.einsum("bhncd,hd->bhnc", kc,
                           phi.astype(jnp.float32)) * (d ** -0.5)
        if valid is None:
            live = jnp.ones((1, 1, n, chunk), bool)
        else:
            at = jnp.arange(t, dtype=jnp.int32).reshape(n, chunk)
            live = (at[None] < jnp.asarray(valid, jnp.int32).reshape(
                -1, 1, 1))[:, None]
        logit = jnp.where(live, logit, stats.NEG_INF)
        p = jnp.where(live, jnp.exp(
            logit - jnp.max(logit, axis=-1, keepdims=True)), 0.0)
        a = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        v_sum = jnp.einsum("bhnc,bhncd->bhnd", a, vc)
        count = jnp.maximum(jnp.sum(live, axis=-1, keepdims=True), 1)
        k_sum = (jnp.sum(jnp.where(live[..., None], kc, 0.0), axis=3)
                 / count.astype(jnp.float32)
                 + mu.astype(jnp.float32)[None, :, None, :])
    return k_sum.astype(k.dtype), v_sum.astype(v.dtype)


def eva_decode_choice(window: int, summary_rows: int, d: int) -> str:
    """``"kernel"`` or ``"xla"`` for a step's attention over a ring of
    ``window`` rows and a plane of ``summary_rows``: the kernel where
    kernels are on and both buffers are whole blocks of lane-wide heads
    (the interpreter takes any width)."""
    if not pk.kernels_enabled():
        return "xla"
    blocks = window % EVA_BLOCK_K == 0 and summary_rows % EVA_BLOCK_K == 0
    if blocks and (pk.interpret_default() or d % 128 == 0):
        return "kernel"
    return "xla"


def rows_fetched(at, visible, window: int, summary_rows: int, d: int):
    """The rows (ring and summary together) a step's attention fetches for
    streams whose newest ring rows are ``at`` and which see ``visible``
    summary rows (numpy arrays): the kernel's whole blocks to each
    frontier, or both buffers whole (the engine's ``attn.eva_rows_read``,
    from the positions as dispatched)."""
    at, visible = np.asarray(at), np.asarray(visible)
    if eva_decode_choice(window, summary_rows, d) != "kernel":
        return np.full(at.shape, window + summary_rows)
    ring, summary = eva_block_counts(at, visible, xp=np)
    return (ring + summary) * EVA_BLOCK_K


def eva_attend(q, ring_k, ring_v, sum_k, sum_v, at, visible, layer):
    """One token's attention over layer ``layer`` of the carried buffers:
    ring rows ``0 .. at`` and summary rows ``0 .. visible - 1`` (``[B]``
    each) under ONE softmax. Returns ``[B, H, 1, D]``."""
    d = q.shape[-1]
    choice = eva_decode_choice(ring_k.shape[3], sum_k.shape[3], d)
    # trace time: which form the decode program being built holds
    obs_metrics.gauge("attn.eva_decode_kernel").set(int(choice == "kernel"))
    with jax.named_scope("eva_decode"):
        if choice == "kernel":
            return eva_decode(q, ring_k, ring_v, sum_k, sum_v, at, visible,
                              layer)
        local = stats.attend_stats(
            q, kv.layer_view(ring_k, layer), kv.layer_view(ring_v, layer),
            at, 0)
        remote = stats.attend_stats(
            q, kv.layer_view(sum_k, layer), kv.layer_view(sum_v, layer),
            visible - 1, 0)
        return stats.finalize_stats(*stats.merge_stats(*local, *remote),
                                    q.dtype)


def eva_prefill(q, k, v, k_sum, v_sum, window: int, chunk: int):
    """A whole prompt's attention from position 0: ``q``/``k``/``v [B, H,
    T, D]`` and the prompt's own summaries ``[B, H, T // chunk, D]``.
    Window ``w`` (``window`` rows, or all ``T`` where the prompt is
    shorter) is one causal block over a buffer of the ``w * window //
    chunk`` summaries before it and then its own keys: every summary is
    at or before every query of the block, so the block's first query
    stands at row ``w * window // chunk`` and the kernel's causal frontier
    does the rest. Returns ``[B, H, T, D]``."""
    t, d = q.shape[2], q.shape[3]
    span = min(t, window)
    if t % span or span % chunk:
        raise ValueError(
            f"an admission of {t} rows is not a whole number of windows of "
            f"{window} (chunks of {chunk}): EVA attention admits a prompt "
            "in a bucket that is")
    per = span // chunk  # summary rows a completed window leaves
    outs = []
    with jax.named_scope("eva_prefill"):
        for w in range(t // span):
            rows = slice(w * span, (w + 1) * span)
            ahead = w * per
            k_buf = jnp.concatenate([k_sum[:, :, :ahead], k[:, :, rows]], 2)
            v_buf = jnp.concatenate([v_sum[:, :, :ahead], v[:, :, rows]], 2)
            s = ahead + span
            if _flash_prefill_choice(span, s, d) == "flash":
                pad = -s % PREFILL_PAD_ROWS
                if pad:
                    k_buf, v_buf = (jnp.pad(x, ((0, 0), (0, 0), (0, pad),
                                                (0, 0)))
                                    for x in (k_buf, v_buf))
                outs.append(pk.flash_attention(
                    q[:, :, rows], k_buf, v_buf, ahead, name="eva_prefill"))
            else:
                outs.append(_attend_xla(q[:, :, rows], k_buf, v_buf, ahead))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=2)


def _chunk_rows(ring, layer, first, chunk: int):
    """``[B, H, chunk, D]``: rows ``first[b] .. first[b] + chunk - 1`` of
    each stream's ring of layer ``layer``: one ``dynamic_slice`` a stream,
    unrolled as the row writes are (``ops.kvcache.update_layer``). As ONE
    gather over the streams the chip's compiler copied both carried rings
    whole, twice a step (2.0 GiB of temporaries at 16 streams of 8 layers;
    tests/test_chip_compile_eva.py)."""
    _, b, h, _, d = ring.shape
    zero = jnp.zeros((), jnp.int32)
    return jnp.concatenate([
        jax.lax.dynamic_slice(
            ring, (layer, jnp.asarray(i, jnp.int32), zero, first[i], zero),
            (1, 1, h, chunk, d))[0] for i in range(b)])


def _write_rows(plane, new, layer):
    """An admission's summary rows ``new [B, H, n, D]`` into rows ``0 ..
    n - 1`` of layer ``layer`` of the carried plane, as a SELECT over the
    layer's slab (read, the new rows taken where they lie, put back: what
    ``ring_write`` does for a chunk). As a ``dynamic_update_slice``, into
    the plane or into its slab, a bucket's one or two rows gave the plane
    the layout a ``[B, H, 1, D]`` update likes (rows outermost) for the
    whole program, and the chip's compiler re-laid both planes on the way
    in and out (3.3 GiB of temporaries at 16 streams;
    tests/test_chip_compile_eva.py)."""
    old = kv.layer_view(plane, layer)
    n, rows = new.shape[2], old.shape[2]
    padded = jnp.pad(new.astype(plane.dtype),
                     ((0, 0), (0, 0), (0, rows - n), (0, 0)))
    fresh = (jnp.arange(rows) < n)[None, None, :, None]
    return kv.layer_store(plane, jnp.where(fresh, padded, old), layer)


def eva_attention_block(
    x: jax.Array,  # [B, T, hidden]
    layer_params: dict,  # wq, wk, wv, wo, eva_phi [H * D], eva_mu [H * D]
    cache,  # KVCache: ring_k/ring_v and sum_k/sum_v, carried whole
    cos: jax.Array,
    sin: jax.Array,
    pos,
    num_heads: int,
    window: int,
    chunk: int,
    layer: jax.Array,
    valid: jax.Array | None = None,  # [B]: a bucketed prompt's true tokens
):
    """One EVA attention sublayer over the carried cache. Returns
    ``(attn_out [B, T, hidden], ring_k, ring_v, sum_k, sum_v)``, the four
    buffers whole and written in place. One token (``T == 1``; ``pos``
    scalar or ``[B]``) or a whole prompt from position 0 (``T > 1``; the
    module's docstring has both)."""
    b, t, _ = x.shape
    q, k, v, _ = _project_heads(
        x, layer_params["wq"], layer_params["wk"], layer_params["wv"],
        num_heads, num_heads)
    d = q.shape[-1]
    q = apply_rope(q, cos, sin, pos)
    k = apply_rope(k, cos, sin, pos)
    phi = layer_params["eva_phi"].reshape(num_heads, d)
    mu = layer_params["eva_mu"].reshape(num_heads, d)
    ring_k, ring_v = cache.ring_k, cache.ring_v
    sum_k, sum_v = cache.sum_k, cache.sum_v
    layer = jnp.asarray(layer, jnp.int32)
    if t == 1:
        at_pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        ring_k, ring_v = kv.ring_write(ring_k, ring_v, k, v, at_pos, layer)
        at = jnp.mod(at_pos, window)  # the new key's ring row
        first = at - jnp.mod(at, chunk)  # its chunk's first
        k_now, v_now = summarise(
            _chunk_rows(ring_k, layer, first, chunk),
            _chunk_rows(ring_v, layer, first, chunk), phi, mu, chunk,
            valid=at - first + 1)
        sum_k, sum_v = kv.update_layer(sum_k, sum_v, k_now, v_now,
                                       at_pos // chunk, layer=layer)
        visible = (at_pos // window) * (window // chunk)
        out = eva_attend(q, ring_k, ring_v, sum_k, sum_v, at, visible, layer)
    else:
        if jnp.asarray(pos).ndim:
            raise ValueError("EVA attention admits a prompt whole, from one "
                             "position for all its rows")
        k_all, v_all = summarise(k, v, phi, mu, chunk, valid=valid)
        out = eva_prefill(q, k.astype(ring_k.dtype), v.astype(ring_v.dtype),
                          k_all.astype(sum_k.dtype),
                          v_all.astype(sum_v.dtype), window, chunk)
        ring_k, ring_v = kv.ring_write(ring_k, ring_v, k, v, pos, layer,
                                       valid=valid)
        sum_k, sum_v = (_write_rows(plane, new, layer)
                        for plane, new in ((sum_k, k_all), (sum_v, v_all)))
    out = out.transpose(0, 2, 1, 3).reshape(b, t, num_heads * d)
    return (quant.dense(out, layer_params["wo"]), ring_k, ring_v, sum_k,
            sum_v)
