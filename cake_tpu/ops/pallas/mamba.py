"""Pallas TPU kernels for the selective state-space recurrence (Mamba-1).

A state tile is ``[d_state, channels]``: the channels on the lanes, the
``d_state`` values of one channel down the sublanes. ``delta`` and ``x``
are rows ``[1, channels]`` and broadcast down the sublanes as they are
stored; ``B`` and ``C`` multiply along the sublanes and arrive as columns
``[d_state, 1]`` (the caller hands them over with a trailing axis of one),
which broadcast along the lanes.

- :func:`ssm_decode`: one token of every slot. The XLA form of
  :func:`cake_tpu.ops.mamba.ssm_step` sweeps the state once for the
  update and once more for the readout; here a block of slots' states is
  fetched into VMEM once, decayed, updated and read out there, and written
  back to the rows of the carried buffer it came from
  (``input_output_aliases``): one read and one write of the state a step.
- :func:`ssm_scan`: an admission chunk of one slot. A block of channels'
  state stays in VMEM while the chunk's tokens pass, eight at a time; the
  grid's last axis walks the chunk in blocks of tokens, so HBM sees ``x``,
  ``delta``, ``B``, ``C`` in and ``y`` out and the state once each way,
  and never a ``[T, d_state, d_inner]`` product.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SLOT_BLOCK = 8
DECODE_CHANNELS = 2560
SCAN_CHANNELS = 1280
SCAN_TOKENS = 64
GROUP = 8  # tokens unrolled inside the scan kernel's loop


def _update(s, a, delta, x, b_col, c_col):
    """``(S_t, sum_n S_t C_t)`` of one token: ``s, a [N, C]``, ``delta, x
    [1, C]``, ``b_col, c_col [N, 1]``."""
    s = jnp.exp(delta * a) * s + (delta * x) * b_col
    return s, jnp.sum(s * c_col, axis=0, keepdims=True)


def _decode_kernel(layer_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref,
                   s_ref, y_ref, s_out_ref, *, slots: int):
    del layer_ref  # used by the index maps
    a, d = a_ref[...], d_ref[...]
    for i in range(slots):
        x = x_ref[i:i + 1, :]
        s, y = _update(s_ref[0, i], a, dt_ref[i:i + 1, :], x, b_ref[i],
                       c_ref[i])
        s_out_ref[0, i] = s
        y_ref[i:i + 1, :] = y + d * x


def _interpret(interpret):
    if interpret is None:
        from cake_tpu.ops.pallas import interpret_default

        return interpret_default()
    return interpret


def _operands(layer, x, delta, bm, cm, a, d_skip, state):
    """A call's operands as the kernels take them: the layer index for the
    index maps, float32 rows, ``B`` and ``C`` as columns, ``D`` as a row."""
    f32 = jnp.float32
    return (jnp.asarray(layer, jnp.int32).reshape(1), x.astype(f32),
            delta.astype(f32), bm.astype(f32)[..., None],
            cm.astype(f32)[..., None], a.astype(f32),
            d_skip.astype(f32)[None], state)


def _block(size: int, want: int, unit: int) -> int:
    """The largest divisor of ``size`` that is a multiple of ``unit`` and at
    most ``want`` (``size`` itself where there is none)."""
    for cand in range(min(want, size) // unit * unit, 0, -unit):
        if size % cand == 0:
            return cand
    return size


def ssm_decode(x, delta, bm, cm, a, d_skip, state, layer, *,
               slot_block: int = SLOT_BLOCK,
               chan_block: int = DECODE_CHANNELS,
               interpret: bool | None = None):
    """One token of the recurrence over the stacked, carried state. ``x,
    delta [B, C]``, ``bm, cm [B, N]``, ``a [N, C]``, ``d_skip [C]``,
    ``state [L, B, N, C]``, all float32; ``layer`` (traced) picks the layer.
    Returns ``(y [B, C], state)``, the state being the buffer it was given
    with layer ``layer`` advanced in place."""
    n_layers, b, n, c = state.shape
    bb = _block(b, slot_block, 8)
    cb = _block(c, chan_block, 128)
    f32 = jnp.float32

    def row(width):
        return pl.BlockSpec((bb, width), lambda i, j, layer: (i, j))

    col = pl.BlockSpec((bb, n, 1), lambda i, j, layer: (i, 0, 0))
    state_spec = pl.BlockSpec(
        (1, bb, n, cb), lambda i, j, layer: (layer[0], i, 0, j))
    y, state = pl.pallas_call(
        functools.partial(_decode_kernel, slots=bb),
        out_shape=(jax.ShapeDtypeStruct((b, c), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b // bb, c // cb),
            in_specs=[row(cb), row(cb), col, col,
                      pl.BlockSpec((n, cb), lambda i, j, layer: (0, j)),
                      pl.BlockSpec((1, cb), lambda i, j, layer: (0, j)),
                      state_spec],
            out_specs=(row(cb), state_spec),
        ),
        # operands count the scalar-prefetch one: the state is the 8th
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=6 * b * n * c, bytes_accessed=ssm_decode_bytes(b, n, c),
            transcendentals=b * n * c),
        name="ssm_decode",
        interpret=_interpret(interpret),
    )(*_operands(layer, x, delta, bm, cm, a, d_skip, state))
    return y, state


def ssm_decode_bytes(b: int, n: int, c: int) -> int:
    """Bytes one decode call must move: one read and one write of the
    state, the step's delta, x, B and C in and y out (float32)."""
    return 4 * b * (2 * n * c + 3 * c + 2 * n)


def _scan_kernel(layer_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, s_ref,
                 y_ref, s_out_ref, acc_ref, *, groups: int):
    del layer_ref  # used by the index maps
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = s_ref[0, 0]

    a, d = a_ref[...], d_ref[...]

    def group(g, s):
        at = pl.multiple_of(g * GROUP, GROUP)
        xg = x_ref[0, pl.ds(at, GROUP), :]
        dtg = dt_ref[0, pl.ds(at, GROUP), :]
        ys = []
        for j in range(GROUP):
            s, y = _update(s, a, dtg[j:j + 1], xg[j:j + 1],
                           b_ref[0, at + j], c_ref[0, at + j])
            ys.append(y)
        y_ref[0, pl.ds(at, GROUP), :] = jnp.concatenate(ys, axis=0) + d * xg
        return s

    s = jax.lax.fori_loop(0, groups, group, acc_ref[...])
    acc_ref[...] = s

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        s_out_ref[0, 0] = s


def ssm_scan(x, delta, bm, cm, a, d_skip, state, layer, *,
             chan_block: int = SCAN_CHANNELS, token_block: int = SCAN_TOKENS,
             interpret: bool | None = None):
    """``T`` tokens (a multiple of eight) of the recurrence, each slot from
    its own state in the stacked, carried buffer. ``x, delta [B, T, C]``,
    ``bm, cm [B, T, N]``, ``a [N, C]``, ``d_skip [C]``, ``state [L, B, N,
    C]``, all float32. Returns ``(y [B, T, C], state)``, layer ``layer`` of
    the state advanced in place by the ``T`` tokens."""
    n_layers, b, n, c = state.shape
    t = x.shape[1]
    assert t % GROUP == 0, t
    cb = _block(c, chan_block, 128)
    tb = _block(t, token_block, GROUP)
    f32 = jnp.float32

    def row():
        return pl.BlockSpec((1, tb, cb), lambda i, j, k, layer: (i, k, j))

    col = pl.BlockSpec((1, tb, n, 1), lambda i, j, k, layer: (i, k, 0, 0))
    state_spec = pl.BlockSpec(
        (1, 1, n, cb), lambda i, j, k, layer: (layer[0], i, 0, j))
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, groups=tb // GROUP),
        out_shape=(jax.ShapeDtypeStruct((b, t, c), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, c // cb, t // tb),
            in_specs=[row(), row(), col, col,
                      pl.BlockSpec((n, cb), lambda i, j, k, layer: (0, j)),
                      pl.BlockSpec((1, cb), lambda i, j, k, layer: (0, j)),
                      state_spec],
            out_specs=(row(), state_spec),
            scratch_shapes=[pltpu.VMEM((n, cb), f32)],
        ),
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=6 * b * t * n * c, bytes_accessed=ssm_scan_bytes(b, t, n, c),
            transcendentals=b * t * n * c),
        name="ssm_scan",
        interpret=_interpret(interpret),
    )(*_operands(layer, x, delta, bm, cm, a, d_skip, state))
    return y, state


def ssm_scan_bytes(b: int, t: int, n: int, c: int) -> int:
    """Bytes one scan call must move: the state once each way, and a
    token's delta, x, B and C in and y out (float32)."""
    return 4 * b * (2 * n * c + t * (3 * c + 2 * n))
