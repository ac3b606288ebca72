"""Pallas TPU kernels for the delta rule (ops/kda.py): one decode step
(:func:`kda_decode`: KDA's decay a channel, or the scalar-gated rule's decay
a head) and the serial scan of an admission's chunk form
(:func:`kda_chunk_scan`: the scalar-gated rule's).

**The decode step.**
The XLA form of :func:`cake_tpu.ops.kda.kda_step` sweeps a head's state
once for each of its three uses (``k^T S``, the rank-one update, ``q^T
S``). Here a block of heads' states ``[HB, d_k, d_v]`` is fetched into
VMEM once, decayed, updated and read out there, and written back to the
rows of the carried buffer it came from (``input_output_aliases``): one
read and one write of the state a step, which is all the step needs.

Layout. A state tile is ``[d_k, d_v]`` (``d_k`` on sublanes, ``d_v`` on
lanes), so ``v``, ``k^T S`` and ``o`` are rows and lie as they are
stored, while a channel's decay, ``k``, ``beta k`` and ``q`` multiply along
sublanes and are needed as columns. They arrive as rows ``[HB, d_k]`` and
are turned by the MXU: ``I @ R^T`` (a matmul with a transposed right
side, at the highest precision, against an exact identity) gives ``[d_k,
HB]``, whose column ``h`` broadcasts along lanes. A decay a HEAD is one
value: it arrives in scalar memory beside the layer's index (``[B * H]``
float32) and multiplies the tile as a scalar; no row of it is built, turned
or fetched. So does ``beta`` there, and ``beta k`` is made in the tile.

Fewer key heads than value heads: the rows of ``k`` and ``q`` stay ``Hk``
and a block of ``HB`` value heads fetches its ``HB / r`` key heads' (the
index maps), value head ``h`` reading column ``h // r``; the scalar-gated
rule repeats nothing in HBM.

**The scan** (PR 65). What a chunk of ``C`` tokens does to a value head's
state, ``u = u_hat - w S``, ``o = (q e^G) S + (Q K^T * mask) u``, ``S' =
e^{G_C} S + (k e^{G_C - G})^T u`` (``u_hat``, ``w``, ``Q K^T`` and the
decays' running sum ``G`` made for all chunks ahead, in XLA), is serial
over the chunks and was a ``fori_loop`` of small fusions around HBM. Here
it is ONE call a layer over the grid ``(B, Hv / HB, n)``, the chunk axis
last and ``"arbitrary"``: a block of ``HB`` value heads' float32 state
``[HB, d_k, d_v]`` is copied into VMEM scratch at a block's first chunk,
advanced there chunk by chunk, and written out after the last (the state's
buffer is the call's own output: ``input_output_aliases``), while Pallas's
pipeline fetches chunk ``m + 1``'s operands under chunk ``m``'s products.
Layout: every operand chunk-major and head-major, ``[n, B, H, C, d]``, a
head's chunk one ``[C, d]`` run of whole ``(8, 128)`` tiles: as the batched
inverse makes ``u_hat`` and ``w``, and as ``K K^T`` and ``Q K^T`` (a KEY
head, ``[n, B, Hk, C, C]``) read ``q`` and ``k`` (``[n, B, Hk, C, d_k]``:
a block takes the ``HB / r`` key heads of its value heads, by the index
maps, so grouped heads repeat nothing). **What leaves.** Handed the
layer's gate (``gate``: ``z [B, T, Hv d_v]`` as the fused projection left
it, the head norm's weight, its epsilon), the kernel's epilogue makes the
layer's output where it has a head's ``o [C, d_v]`` in hand, ``o *
rsqrt(mean(o^2) + eps) * norm * silu(z)``, and writes it a head's lanes of
a token, ``[B, T, Hv d_v]`` in ``z``'s type: what the output projection
reads. That is not a second mechanism but what the call's OUTPUT LAYOUT
costs otherwise: on the chip ``[T, Hv d_v]`` (a tile: 8 tokens of a head),
``[T, Hv, d_v]`` (8 heads of a token) and ``[n, Hv, C, d_v]`` are three
tilings, XLA's norm-and-gate fusion settles on the second whatever a custom
call returns, and copies ``o`` and the gate ``z`` into it first: 1.3, 0.7
and 0.9 ms a layer at 8192 rows for ``o`` returned in the three (my chip
runs, PR 65), against 0.6 ms that the scan itself saved. Without ``gate``
(tests, the sweep) ``o`` leaves float32 ``[B, T, Hv, d_v]``, a head's ``[C,
d_v]`` stored a sublane a token.
``G [n, B, Hv, C]`` arrives as rows (``G_s`` along the lanes) and is
turned once a grid step by the MXU (``I @ G^T``, exact) into the columns
``G_t`` that scale rows of ``q``, ``k`` and the mask ``e^{G_t - G_s}``:
the three decayed operands exist only in VMEM. Per head four products,
``w S``, ``(q e^G) S``, ``(Q K^T * mask) u`` and ``(k e^{G_C - G})^T u``
(the left side contracted over its rows: ``k`` handed over transposed by
XLA ran slower), float32 at ``Precision.HIGHEST`` throughout; the kernel
is bound by them (19 us a chunk of 32 heads where fetching its operands
alone takes 9: my chip runs, PR 65). The bound stays data:
``live`` is scalar-prefetched, a grid step at or past it does no product,
writes zeros to its chunk of ``o`` and fetches nothing (its index maps
name the last live chunk's blocks again).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEAD_BLOCK = 16


def _kernel(layer_ref, *refs, heads: int, rep: int, scalar: bool):
    del layer_ref  # used by the index maps
    # four operands before v either way: (decay, beta | k, q) with a decay a
    # head, (decay, k, beta k, q) with a decay a channel
    if scalar:  # a head's decay and beta, in scalar memory
        decay_ref, beta_ref, *rows = refs[:4]
        first = (pl.program_id(0) * pl.num_programs(1)
                 + pl.program_id(1)) * heads  # this block's first head
    else:  # rows: the decay a channel, k, beta k, q
        rows = refs[:4]
    v_ref, s_ref, o_ref, s_out_ref = refs[4:]
    dk = s_ref.shape[-2]
    at = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
    eye = (at == cols).astype(jnp.float32)

    def column(ref):  # [HB, d_k] rows -> [d_k, HB]
        return jax.lax.dot_general(
            eye, ref[0], (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    if scalar:
        k, q = (column(r) for r in rows)
    else:
        decay, k, kb, q = (column(r) for r in rows)
    for h in range(heads):
        c = h // rep  # the value head's key head, of this block's
        if scalar:
            s = s_ref[0, 0, h] * decay_ref[first + h]
            kb_h = k[:, c:c + 1] * beta_ref[first + h]
        else:
            s = s_ref[0, 0, h] * decay[:, h:h + 1]
            kb_h = kb[:, h:h + 1]
        ks = jnp.sum(s * k[:, c:c + 1], axis=0, keepdims=True)  # [1, d_v]
        s = s + kb_h * (v_ref[0, h:h + 1, :] - ks)
        s_out_ref[0, 0, h] = s
        o_ref[0, h:h + 1, :] = jnp.sum(s * q[:, c:c + 1], axis=0,
                                       keepdims=True)


def kda_decode(q, k, v, g, beta, state, layer, *,
               head_block: int = HEAD_BLOCK, interpret: bool | None = None):
    """One token of the delta rule over the stacked, carried state.
    ``q, k [B, Hk, d_k]``, ``v [B, H, d_v]``, ``g [B, H, d_k]`` (a decay a
    channel) or ``[B, H]`` (a head), ``beta [B, H]``, ``state [L, B, H,
    d_k, d_v]``, all float32; ``layer`` (traced) picks the layer. Value head
    ``h`` reads key head ``h // (H / Hk)``. Returns ``(o [B, H, d_v],
    state)``, the state being the buffer it was given with layer ``layer``
    advanced in place."""
    n_layers, b, h, dk, dv = state.shape
    hb = min(head_block, h)
    assert h % hb == 0, (h, hb)
    if interpret is None:
        from cake_tpu.ops.pallas import interpret_default

        interpret = interpret_default()
    f32 = jnp.float32
    scalar = g.ndim == beta.ndim
    rep = h // k.shape[1]
    assert hb % rep == 0, (hb, rep)
    decay = jnp.exp(g).astype(f32)
    prefetch = [jnp.asarray(layer, jnp.int32).reshape(1)]
    if scalar:
        prefetch += [decay.reshape(b * h), beta.astype(f32).reshape(b * h)]
        rows = [(k, hb // rep), (q, hb // rep)]
    else:  # beta k a VALUE head (KDA has as many key heads: no repeat)
        kb = beta[..., None] * (k if rep == 1 else jnp.repeat(k, rep, axis=1))
        rows = [(decay, hb), (k, hb // rep), (kb, hb), (q, hb // rep)]

    def vec(heads, width):
        return pl.BlockSpec((1, heads, width), lambda i, j, *_: (i, j, 0))

    state_spec = pl.BlockSpec(
        (1, 1, hb, dk, dv), lambda i, j, layer, *_: (layer[0], i, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kernel, heads=hb, rep=rep, scalar=scalar),
        out_shape=(jax.ShapeDtypeStruct((b, h, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, h // hb),
            in_specs=[vec(n, dk) for _, n in rows] + [vec(hb, dv),
                                                      state_spec],
            out_specs=(vec(hb, dv), state_spec),
        ),
        # operands count the scalar-prefetch ones: the state is the last
        input_output_aliases={len(prefetch) + len(rows) + 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=8 * b * h * dk * dv,
            bytes_accessed=kda_decode_bytes(b, h, dk, dv, scalar, rep),
            transcendentals=0),
        name="kda_decode",
        interpret=interpret,
    )(*prefetch, *(a.astype(f32) for a, _ in rows), v.astype(f32), state)
    return o, state


def kda_decode_bytes(b: int, h: int, dk: int, dv: int,
                     scalar: bool = False, rep: int = 1) -> int:
    """Bytes one call must move: one read and one write of the state, v in
    and o out, and the step's rows (float32): k and q a KEY head (``h /
    rep`` of them), and a value head either a decay a channel and beta k
    or, with ``scalar``, one decay and one beta."""
    rows = 2 * dk // rep + (2 if scalar else 2 * dk)
    return 4 * b * h * (2 * dk * dv + rows + 2 * dv)


# value heads a grid step (tools/kda_sweep --chunk --head-block 8,16: 16
# ahead by 1-2% from 1024 tokens on; 32 the same: my chip runs, PR 65)
SCAN_HEAD_BLOCK = 16


def _scan_kernel(live_ref, q_ref, k_ref, qk_ref, cum_ref, u_ref, w_ref, s_ref,
                 *refs, heads: int, rep: int, eps: float | None):
    if eps is None:  # ``o`` as the rule leaves it
        o_ref, s_out_ref, acc_ref = refs
    else:  # ... normed a head and gated: the layer's output
        z_ref, norm_ref, o_ref, s_out_ref, acc_ref = refs
    i = pl.program_id(2)
    c = cum_ref.shape[-1]
    dk, dv = acc_ref.shape[-2:]
    dot = functools.partial(jax.lax.dot_general,
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(i == 0)
    def _():
        acc_ref[...] = s_ref[0]

    @pl.when(i < live_ref[0])
    def _():
        at = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        to = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
        plain = (((1,), (0,)), ((), ()))  # [M, K] @ [K, N]
        rows = cum_ref[0, 0]  # [HB, C]: G_t along the lanes
        # ... and down the sublanes, turned by the MXU (I @ rows^T, exact)
        cols = dot((at == to).astype(jnp.float32), rows,
                   (((1,), (1,)), ((), ())))  # [C, HB]
        for h in range(heads):
            g = h // rep  # the value head's key head, of this block's
            col, row = cols[:, h:h + 1], rows[h:h + 1, :]
            last = col[c - 1:c, :]  # G_C [1, 1]
            # e^{G_t - G_s} for s <= t (exponents <= 0)
            mask = jnp.exp(jnp.where(at >= to, col - row, -jnp.inf))
            q = q_ref[0, 0, g] * jnp.exp(col)
            k = k_ref[0, 0, g] * jnp.exp(last - col)
            s = acc_ref[h]
            # (w S and (q e^G) S as one product of 2C rows took 2 us a
            # chunk of 32 heads MORE: the rows are copied together first)
            u = u_ref[0, 0, h] - dot(w_ref[0, 0, h], s, plain)
            o = dot(q, s, plain) + dot(qk_ref[0, 0, g] * mask, u, plain)
            if eps is None:
                # (a head's sublane of every token's tile: a strided store)
                o_ref[0, :, h, :] = o
            else:  # rmsnorm_head(o) * silu(z), a head's lanes of a token
                z = z_ref[0, :, h * dv:(h + 1) * dv].astype(jnp.float32)
                o = o * jax.lax.rsqrt(
                    jnp.mean(o * o, axis=-1, keepdims=True) + eps)
                o_ref[0, :, h * dv:(h + 1) * dv] = (
                    o * norm_ref[...] * (z * jax.nn.sigmoid(z))
                ).astype(o_ref.dtype)
            # (a [1, 1] is broadcast along the lanes, then down the sublanes)
            acc_ref[h] = s * jnp.exp(jnp.broadcast_to(last, (1, dv))) + dot(
                k, u, (((0,), (0,)), ((), ())))

    @pl.when(i >= live_ref[0])
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        s_out_ref[0] = acc_ref[...]


def kda_chunk_scan(q, k, qk, cum, u_hat, w, state, live, *, gate=None,
                   head_block: int | None = None,
                   interpret: bool | None = None):
    """The serial scan of the scalar-gated rule's chunk form (module
    docstring). ``q, k [n, B, Hk, C, d_k]``, ``qk [n, B, Hk, C, C]``
    (``Q K^T`` a chunk, undecayed), ``cum [n, B, Hv, C]`` (the log-decay
    summed from each chunk's start), ``u_hat [n, B, Hv, C, d_v]``, ``w
    [n, B, Hv, C, d_k]``, ``state [B, Hv, d_k, d_v]``, all float32;
    ``live`` (int32 ``[]``, traced): the leading chunks that do any work.
    Returns ``(o [B, n C, Hv, d_v], state)``: ``o`` zero from chunk
    ``live`` on, the state advanced by the live chunks. ``gate`` ``(z [B,
    n C, Hv d_v], norm [d_v], eps)``: ``o`` leaves as the layer's output
    ``rmsnorm_head(o; norm) * silu(z)``, ``[B, n C, Hv d_v]`` in ``z``'s
    type (zero where ``o`` is)."""
    n, b, hv, c = cum.shape
    hk = qk.shape[2]
    dv, dk = u_hat.shape[-1], w.shape[-1]
    rep = hv // hk
    hb = min(head_block or SCAN_HEAD_BLOCK, hv)
    assert hv % hb == 0 and hb % rep == 0, (hv, hb, rep)
    kb = hb // rep  # key heads a block
    if interpret is None:
        from cake_tpu.ops.pallas import interpret_default

        interpret = interpret_default()
    f32 = jnp.float32
    live = jnp.clip(jnp.asarray(live, jnp.int32), 0, n).reshape(1)

    def at(i, live):  # a chunk past the last live one fetches nothing new
        return jnp.minimum(i, jnp.maximum(live[0] - 1, 0))

    def chunked(heads, *tile):
        return pl.BlockSpec(
            (1, 1, heads) + tile,
            lambda i, j, m, live: (at(m, live), i, j) + (0,) * len(tile))

    state_spec = pl.BlockSpec((1, hb, dk, dv),
                              lambda i, j, m, live: (i, j, 0, 0))
    if gate is None:
        gated, gate_specs, eps = (), [], None
        out = jax.ShapeDtypeStruct((b, n * c, hv, dv), f32)
        out_spec = pl.BlockSpec((1, c, hb, dv),
                                lambda i, j, m, live: (i, m, j, 0))
    else:  # a head's d_v lanes of a token, as z lies and the layer reads
        z, norm, eps = gate
        gated = (z, norm.astype(f32).reshape(1, dv))
        gate_specs = [
            pl.BlockSpec((1, c, hb * dv),
                         lambda i, j, m, live: (i, at(m, live), j)),
            pl.BlockSpec((1, dv), lambda i, j, m, live: (0, 0))]
        out = jax.ShapeDtypeStruct((b, n * c, hv * dv), z.dtype)
        out_spec = pl.BlockSpec((1, c, hb * dv),
                                lambda i, j, m, live: (i, m, j))
    o, state = pl.pallas_call(
        functools.partial(_scan_kernel, heads=hb, rep=rep, eps=eps),
        out_shape=(out, jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hv // hb, n),
            in_specs=[chunked(kb, c, dk), chunked(kb, c, dk),
                      chunked(kb, c, c), chunked(hb, c), chunked(hb, c, dv),
                      chunked(hb, c, dk), state_spec] + gate_specs,
            out_specs=(out_spec, state_spec),
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), f32)],
        ),
        # operands count the scalar-prefetch one: the state is the 8th
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=kda_chunk_scan_flops(b, n, hv, c, dk, dv),
            bytes_accessed=kda_chunk_scan_bytes(b, n, hv, c, dk, dv, rep),
            transcendentals=b * n * hv * c * (c + 2)),
        name="kda_chunk_scan",
        interpret=interpret,
    )(live, q.astype(f32), k.astype(f32), qk.astype(f32), cum.astype(f32),
      u_hat.astype(f32), w.astype(f32), state, *gated)
    return o, state


def kda_chunk_scan_flops(b: int, n: int, h: int, c: int, dk: int,
                         dv: int) -> int:
    """Operations of ``n`` chunks' four products on the state a value
    head: ``w S``, ``(q e^G) S``, ``(Q K^T * mask) u``, ``(k e^{G_C -
    G})^T u``."""
    return 2 * b * n * h * c * dv * (3 * dk + c)


def kda_chunk_scan_bytes(b: int, n: int, h: int, c: int, dk: int, dv: int,
                         rep: int = 1) -> int:
    """Bytes one call must move (float32): the state once each way, and a
    chunk's ``u_hat``, ``w`` and decays in and ``o`` out a value head, its
    ``q``, ``k`` and ``Q K^T`` a KEY head."""
    chunk = c * (2 * dv + dk + 1) + c * (2 * dk + c) // rep
    return 4 * b * h * (2 * dk * dv + n * chunk)
