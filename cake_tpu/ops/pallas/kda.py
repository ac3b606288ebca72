"""Pallas TPU kernel for one decode step of the delta rule (KDA's decay a
channel, or the scalar-gated rule's decay a head: ops/kda.py).

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
