"""Pallas TPU kernel for one decode step of the delta rule (KDA).

The XLA form of :func:`cake_tpu.ops.kda.kda_step` sweeps a head's state
once for each of its three uses (``k^T S``, the rank-one update, ``q^T
S``). Here a block of heads' states ``[HB, d_k, d_v]`` is fetched into
VMEM once, decayed, updated and read out there, and written back to the
rows of the carried buffer it came from (``input_output_aliases``): one
read and one write of the state a step, which is all the step needs.

Layout. A state tile is ``[d_k, d_v]`` (``d_k`` on sublanes, ``d_v`` on
lanes), so ``v``, ``k^T S`` and ``o`` are rows and lie as they are
stored, while the decay, ``k``, ``beta k`` and ``q`` multiply along
sublanes and are needed as columns. They arrive as rows ``[HB, d_k]`` and
are turned by the MXU: ``I @ R^T`` (a matmul with a transposed right
side, at the highest precision, against an exact identity) gives ``[d_k,
HB]``, whose column ``h`` broadcasts along lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEAD_BLOCK = 16


def _kernel(layer_ref, decay_ref, k_ref, kb_ref, q_ref, v_ref, s_ref, o_ref,
            s_out_ref, *, heads: int):
    del layer_ref  # used by the index maps
    dk = s_ref.shape[-2]
    rows = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
    eye = (rows == cols).astype(jnp.float32)

    def column(ref):  # [HB, d_k] rows -> [d_k, HB]
        return jax.lax.dot_general(
            eye, ref[0], (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    decay, k, kb, q = (column(r) for r in (decay_ref, k_ref, kb_ref, q_ref))
    for h in range(heads):
        s = s_ref[0, 0, h] * decay[:, h:h + 1]
        ks = jnp.sum(s * k[:, h:h + 1], axis=0, keepdims=True)  # [1, d_v]
        s = s + kb[:, h:h + 1] * (v_ref[0, h:h + 1, :] - ks)
        s_out_ref[0, 0, h] = s
        o_ref[0, h:h + 1, :] = jnp.sum(s * q[:, h:h + 1], axis=0,
                                       keepdims=True)


def kda_decode(q, k, v, g, beta, state, layer, *,
               head_block: int = HEAD_BLOCK, interpret: bool | None = None):
    """One token of the delta rule over the stacked, carried state.
    ``q, k, g [B, H, d_k]``, ``v [B, H, d_v]``, ``beta [B, H]``, ``state
    [L, B, H, d_k, d_v]``, all float32; ``layer`` (traced) picks the layer.
    Returns ``(o [B, H, d_v], state)``, the state being the buffer it was
    given with layer ``layer`` advanced in place."""
    n_layers, b, h, dk, dv = state.shape
    hb = min(head_block, h)
    assert h % hb == 0, (h, hb)
    if interpret is None:
        from cake_tpu.ops.pallas import interpret_default

        interpret = interpret_default()
    f32 = jnp.float32
    rows = [jnp.exp(g).astype(f32), k.astype(f32),
            (beta[..., None] * k).astype(f32), q.astype(f32)]

    def vec(width):
        return pl.BlockSpec((1, hb, width), lambda i, j, layer: (i, j, 0))

    state_spec = pl.BlockSpec(
        (1, 1, hb, dk, dv), lambda i, j, layer: (layer[0], i, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kernel, heads=hb),
        out_shape=(jax.ShapeDtypeStruct((b, h, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // hb),
            in_specs=[vec(dk)] * 4 + [vec(dv), state_spec],
            out_specs=(vec(dv), state_spec),
        ),
        # operands count the scalar-prefetch one: the state is the 7th
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=8 * b * h * dk * dv,
            bytes_accessed=kda_decode_bytes(b, h, dk, dv),
            transcendentals=0),
        name="kda_decode",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), *rows, v.astype(f32), state)
    return o, state


def kda_decode_bytes(b: int, h: int, dk: int, dv: int) -> int:
    """Bytes one call must move: one read and one write of the state, the
    step's decay, k, beta k, q and v in and o out (float32)."""
    return 4 * b * h * (2 * dk * dv + 4 * dk + 2 * dv)
