"""Selective state-space mixer (Mamba-1) over a recurrent state.

The reference serves one attention, over cached keys and values
(`cake-core/src/model/attention.rs`); this, like :mod:`cake_tpu.ops.kda`,
is a layer that keeps none. A channel ``c`` of ``d_inner`` holds a float32
state of ``d_state`` values a stream, whatever the stream's length, and
per token ``u`` (the normed input):

    [x | z]      = u W_in
    x            = silu(conv(x) + b_conv)       causal, depthwise, K taps
    [dt | B | C] = x W_x
    dt, B, C     = rmsnorm(dt), rmsnorm(B), rmsnorm(C)
    delta        = softplus(dt W_dt + b_dt)                      a channel
    S_t[n, c]    = exp(delta_t[c] A[n, c]) S_{t-1}[n, c]
                   + delta_t[c] B_t[n] x_t[c]                    A = -exp(A_log)
    y_t[c]       = sum_n S_t[n, c] C_t[n] + D[c] x_t[c]
    out          = (y * silu(z)) W_out

**Cached:** ``S`` (float32, laid out ``[d_state, d_inner]``: the channels on
the lanes, where ``[d_inner, d_state]`` would pad 16 to 128 and cost eight
times the bytes) and the convolution's last ``K - 1`` inputs, in the two
recurrent buffers of :class:`cake_tpu.ops.kvcache.KVCache` (``state [L, B,
d_state, d_inner]``, ``conv [L, B, K - 1, d_inner]``), read and written in
place on the carried cache as rows are. ``A_log`` is held transposed the
same way (``[d_state, d_inner]``). A slot's state has no frontier that
hides what the last stream left: an admission starts from a zeroed staging
row and the splice copies state and tail over the slot's.

Two forms of the recurrence, chosen at trace time by ``T``:

- :func:`ssm_step` (``T == 1``, a decode step): the equations above. On the
  chip the step is the Pallas kernel
  :func:`cake_tpu.ops.pallas.mamba.ssm_decode` where
  :func:`ssm_decode_choice` says so (by the shapes, no knob): one read and
  one write of each slot's state, in place on the carried buffer; decay,
  update, readout and the ``D`` skip in one pass.
- :func:`ssm_recurrence` (``T > 1``, an admission chunk): the recurrence
  over the chunk's tokens, entering through the slot's state and leaving
  through it, so a chunked admission is exact. Where :func:`ssm_scan_choice` takes
  the shape it is the Pallas kernel
  :func:`cake_tpu.ops.pallas.mamba.ssm_scan` (a block of channels' state
  stays in VMEM for the whole chunk; only ``x``, ``delta``, ``B``, ``C``
  come in and ``y`` goes out), otherwise a ``lax.scan`` a token, which
  holds no ``[T, d_state, d_inner]`` product at all.

``valid [B]``: the true tokens of each row of a bucketed chunk. A padded
token gets ``delta = 0`` (it neither decays nor writes the state) and the
convolution's tail is taken at the true length.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.ops import kvcache as kv
from cake_tpu.ops import pallas as pk
from cake_tpu.ops import quant
from cake_tpu.ops.kda import causal_conv
from cake_tpu.ops.norms import rms_norm


def ssm_step(x, delta, bm, cm, a, d_skip, state):
    """One token. ``x, delta [B, C]``, ``bm, cm [B, N]``, ``a [N, C]``
    (negative), ``d_skip [C]``, ``state [B, N, C]``, all float32. Returns
    ``(y [B, C], state)``. Elementwise products and a sum over ``N``:
    exact float32 on any backend, one read and one write of the state."""
    s = (jnp.exp(delta[:, None, :] * a) * state
         + (delta * x)[:, None, :] * bm[:, :, None])
    return jnp.sum(s * cm[:, :, None], axis=1) + d_skip * x, s


def ssm_recurrence(x, delta, bm, cm, a, d_skip, state):
    """The recurrence token by token over ``[B, T, .]`` inputs: a
    ``lax.scan`` whose carry is the state."""
    def body(s, xs):
        y, s = ssm_step(*xs, a, d_skip, s)
        return s, y

    state, y = jax.lax.scan(
        body, state, tuple(jnp.moveaxis(v, 1, 0) for v in (x, delta, bm, cm)))
    return jnp.moveaxis(y, 0, 1), state


def _dense_f32(x, w):
    """``x @ w`` with the float32 accumulator as the result."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def ssm_decode_choice(d_state: int, d_inner: int) -> str:
    """``"kernel"`` or ``"xla"`` for a decode step over the stacked state:
    THE policy, from what a trace can see (the shapes). The kernel wants
    whole ``(8, 128)`` tiles of a slot's state; off the chip it runs
    interpreted, and only when kernels are forced (tests)."""
    if not pk.kernels_enabled():
        return "xla"
    if pk.interpret_default():
        return "kernel" if pk.force_kernels() else "xla"
    return "kernel" if d_state % 8 == 0 and d_inner % 128 == 0 else "xla"


def ssm_scan_choice(t: int, d_state: int, d_inner: int) -> str:
    """The same for an admission chunk of ``t`` tokens: the kernel walks
    the chunk in groups of eight tokens."""
    choice = ssm_decode_choice(d_state, d_inner)
    return choice if t % 8 == 0 else "xla"


def mamba_mixer_block(
    x: jax.Array,  # [B, T, hidden], normed
    layer: dict,
    state: jax.Array,  # [(L,) B, d_state, d_inner] float32
    conv: jax.Array,  # [(L,) B, K - 1, d_inner]
    config,
    valid: jax.Array | None = None,  # [B] true tokens of each row
    layer_idx: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One Mamba sublayer incl. the state's and the tail's update. Returns
    ``(out [B, T, hidden], state, conv)``; the buffers come back whole.
    No write gate: a model that holds a recurrent state runs as one
    pipeline stage (``mesh.validate_shardable``), whose writes always
    land."""
    b, t, _ = x.shape
    di, n, r = (config.mamba_d_inner, config.mamba_d_state,
                config.mamba_dt_rank)
    f32 = jnp.float32
    eps = config.rms_norm_eps
    with jax.named_scope("mamba.proj"):
        xz = quant.dense(x, layer["w_in"])
        xs, z = xz[..., :di], xz[..., di:]
    with jax.named_scope("mamba.conv"):
        u, tail = causal_conv(xs, kv.layer_view(conv, layer_idx),
                              layer["conv_w"], valid)
        if "conv_b" in layer:
            u = u + layer["conv_b"].astype(f32)
        u = jax.nn.silu(u)  # [B, T, di] float32
    with jax.named_scope("mamba.proj"):
        # the step's delta, B and C come out of their products in float32
        # (the accumulator's type: nothing is rounded that need not be; an
        # error in delta is multiplied by A, up to 16, in the exponent)
        dbc = _dense_f32(u.astype(x.dtype), layer["w_x"])
        dt = rms_norm(dbc[..., :r], layer["dt_norm"], eps)
        bm = rms_norm(dbc[..., r:r + n], layer["b_norm"], eps)
        cm = rms_norm(dbc[..., r + n:], layer["c_norm"], eps)
        delta = jax.nn.softplus(_dense_f32(dt.astype(x.dtype), layer["w_dt"])
                                + layer["dt_bias"].astype(f32))
        a = -jnp.exp(layer["a_log"].astype(f32))  # [N, C]
        d_skip = layer["d_skip"].astype(f32)
    if valid is not None:
        live = jnp.arange(t, dtype=jnp.int32)[None] < valid[:, None]
        delta = jnp.where(live[..., None], delta, 0.0)
    stacked = layer_idx is not None
    if t == 1:
        choice = ssm_decode_choice(n, di) if stacked else "xla"
        # trace time: which step the decode program being built holds
        obs_metrics.gauge("ssm.decode_kernel").set(int(choice == "kernel"))
    else:
        choice = ssm_scan_choice(t, n, di) if stacked else "xla"
    with jax.named_scope("mamba.step" if t == 1 else "mamba.scan"):
        if choice == "kernel":
            from cake_tpu.ops.pallas import mamba as pm

            if t == 1:
                y, state = pm.ssm_decode(u[:, 0], delta[:, 0], bm[:, 0],
                                         cm[:, 0], a, d_skip, state,
                                         layer_idx)
                y = y[:, None]
            else:
                y, state = pm.ssm_scan(u, delta, bm, cm, a, d_skip, state,
                                       layer_idx)
        else:
            s0 = kv.layer_view(state, layer_idx)
            if t == 1:
                y, s1 = ssm_step(u[:, 0], delta[:, 0], bm[:, 0], cm[:, 0],
                                 a, d_skip, s0)
                y = y[:, None]
            else:
                y, s1 = ssm_recurrence(u, delta, bm, cm, a, d_skip, s0)
            state = kv.layer_store(state, s1, layer_idx)
    conv = kv.layer_store(conv, tail.astype(conv.dtype), layer_idx)
    y = (y * jax.nn.silu(z.astype(f32))).astype(x.dtype)
    return quant.dense(y, layer["w_out"]), state, conv
