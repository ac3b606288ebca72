"""Delta-rule linear attention with a per-channel decay (KDA) over a
recurrent state.

The reference serves one attention, over cached keys and values
(`cake-core/src/model/attention.rs`); this is the layer that keeps none.
A head holds a float32 state ``S [d_k, d_v]`` a stream, whatever the
stream's length, and per token ``x``:

    [q | k | v] = silu(conv4([x W_q | x W_k | x W_v]))    causal, depthwise
    q, k        = l2norm_head(q) * d_k^-0.5, l2norm_head(k)
    g           = lower * sigmoid(exp(A_log_h) * (x W_f + dt_bias))   in (lower, 0)
    beta        = sigmoid(x W_b)                                      a head
    S_t         = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t         = S_t^T q_t
    y           = (rmsnorm_head(o) * sigmoid(x W_g)) W_o

**Cached:** ``S`` (float32) and the last ``taps - 1`` inputs of the three
convolutions, in the two recurrent buffers of
:class:`cake_tpu.ops.kvcache.KVCache` (``state [L, B, H, d_k, d_v]``,
``conv [L, B, taps - 1, 3 H d]``), read and written in place on the carried
cache as rows are. A slot's state has no frontier that hides what the last
stream left: an admission starts from a zeroed staging row and the splice
copies state and tail over the slot's.

Two forms of the recurrence, chosen at trace time by ``T``:

- :func:`kda_step` (``T == 1``, a decode step): the equations above. On
  the chip the step is the Pallas kernel
  :func:`cake_tpu.ops.pallas.kda.kda_decode` where
  :func:`kda_decode_choice` says so (by the shapes, no knob): one read and
  one write of each head's state, in place on the carried buffer, where
  XLA's fusions sweep the state once for each of its three uses.
- :func:`kda_chunk` (``T > 1``, an admission chunk): chunks of 64 tokens in
  the WY form. With ``G_t`` the log-decay summed from the chunk's start,
  ``u_t = beta_t (v_t - (k_t e^{G_t})^T S_0 - sum_{s<t} A_ts u_s)`` where
  ``A_ts = sum_c k_tc k_sc e^{G_tc - G_sc}``: one unit-triangular solve a
  chunk, then ``o_t = (q_t e^{G_t})^T S_0 + sum_{s<=t} A^q_ts u_s`` and
  ``S_C = e^{G_C} S_0 + sum_s (k_s e^{G_C - G_s}) u_s^T``. Every exponent is
  a difference ``G_t - G_s`` with ``s <= t``, so nothing overflows however
  near ``lower`` the decays are; the form is exact against the recurrence
  (float32, matmuls at the highest precision), enters through the slot's
  state and leaves through it, so a chunked admission is exact too.

``valid [B]``: the true tokens of each row of a bucketed chunk. A padded
token gets ``beta = 0`` and ``g = 0`` (it neither writes nor decays the
state) and the convolutions' tail is taken at the true length.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cake_tpu.ops import kvcache as kv
from cake_tpu.ops import pallas as pk
from cake_tpu.ops import quant
from cake_tpu.ops.norms import rms_norm

CHUNK = 64
L2_EPS = 1e-6


def kda_step(q, k, v, g, beta, state):
    """One token. ``q, k, g [B, H, d_k]``, ``v [B, H, d_v]``, ``beta [B,
    H]``, ``state [B, H, d_k, d_v]``, all float32. Returns ``(o [B, H,
    d_v], state)``. Elementwise products and sums: exact float32 on any
    backend, one read and one write of the state."""
    s = state * jnp.exp(g)[..., None]
    ks = jnp.sum(k[..., None] * s, axis=-2)  # k^T S
    s = s + (beta[..., None] * k)[..., None] * (v - ks)[..., None, :]
    return jnp.sum(q[..., None] * s, axis=-2), s


def kda_decode_choice(d_k: int, d_v: int) -> str:
    """``"kernel"`` or ``"xla"`` for a decode step over the stacked state:
    THE policy, from what a trace can see (the shapes). The kernel wants
    whole ``(8, 128)`` tiles of a head's state; off the chip it runs
    interpreted, and only when kernels are forced (tests)."""
    if not pk.kernels_enabled():
        return "xla"
    if pk.interpret_default():
        return "kernel" if pk.force_kernels() else "xla"
    return "kernel" if d_k % 128 == 0 and d_v % 128 == 0 else "xla"


def kda_recurrence(q, k, v, g, beta, state):
    """The recurrence token by token over ``[B, T, H, .]`` inputs: what
    :func:`kda_chunk` must equal."""
    def body(s, xs):
        o, s = kda_step(*xs, s)
        return s, o

    state, o = jax.lax.scan(
        body, state, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def kda_chunk(q, k, v, g, beta, state, chunk: int = CHUNK):
    """``T`` tokens in chunks of ``chunk`` (module docstring). ``q, k, g
    [B, T, H, d_k]``, ``v [B, T, H, d_v]``, ``beta [B, T, H]``, ``state [B,
    H, d_k, d_v]``, all float32. Returns ``(o [B, T, H, d_v], state)``."""
    b, t, h, dk = q.shape
    c = min(chunk, t)
    pad = -t % c
    if pad:  # tokens that neither write nor decay the state
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    n = (t + pad) // c

    def chunks(a):  # [B, T, H, ...] -> [n, B, H, C, ...]
        a = a.reshape((b, n, c) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    tri = jnp.tril(jnp.ones((c, c), jnp.bool_))

    def body(s0, xs):
        qc, kc, vc, gc, bc = xs  # [B, H, C, .]; bc [B, H, C]
        cum = jnp.cumsum(gc, axis=2)  # G_t, inclusive
        # e^{G_t - G_s} for s <= t, a channel: exponents <= 0
        decay = jnp.exp(jnp.where(
            tri[..., None], cum[:, :, :, None] - cum[:, :, None, :], -jnp.inf))
        kk = jnp.sum(kc[:, :, :, None] * kc[:, :, None, :] * decay, axis=-1)
        qk = jnp.sum(qc[:, :, :, None] * kc[:, :, None, :] * decay, axis=-1)
        into = jnp.exp(cum)  # e^{G_t}: the chunk's start seen from t
        out = jnp.exp(cum[:, :, -1:] - cum)  # e^{G_C - G_s}
        with jax.default_matmul_precision("highest"):
            rhs = bc[..., None] * (vc - jnp.einsum(
                "bhck,bhkv->bhcv", kc * into, s0))
            m = jnp.eye(c, dtype=kk.dtype) + bc[..., None] * jnp.tril(kk, -1)
            u = jax.scipy.linalg.solve_triangular(
                m, rhs, lower=True, unit_diagonal=True)
            o = (jnp.einsum("bhck,bhkv->bhcv", qc * into, s0)
                 + jnp.einsum("bhcs,bhsv->bhcv", qk, u))
            s1 = (s0 * into[:, :, -1, :, None]
                  + jnp.einsum("bhsk,bhsv->bhkv", kc * out, u))
        return s1, o

    state, o = jax.lax.scan(
        body, state, tuple(chunks(a) for a in (q, k, v, g, beta)))
    # [n, B, H, C, dv] -> [B, T, H, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(b, n * c, h, -1)
    return o[:, :t], state


def causal_conv(x, tail, taps, valid=None):
    """Depthwise causal convolution over time with the cached tail.
    ``x [B, T, C]`` (this chunk's inputs), ``tail [B, K - 1, C]`` (the
    inputs before it), ``taps [K, C]`` (tap ``K - 1`` multiplies the
    current token). Returns ``(y [B, T, C] float32, new_tail [B, K - 1,
    C])``, the tail being the last ``K - 1`` inputs up to each row's true
    length ``valid [B]`` (None: ``T``)."""
    k1 = tail.shape[1]
    t = x.shape[1]
    xs = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # [B, K-1+T, C]
    w = taps.astype(jnp.float32)
    y = sum(xs[:, j:j + t].astype(jnp.float32) * w[j] for j in range(k1 + 1))
    if valid is None:
        return y, xs[:, t:]
    new_tail = jax.vmap(
        lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, k1, 0))(xs, valid)
    return y, new_tail


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def kda_attention_block(
    x: jax.Array,  # [B, T, hidden], normed
    layer: dict,
    state: jax.Array,  # [(L,) B, H, d_k, d_v] float32
    conv: jax.Array,  # [(L,) B, K - 1, 3 H d]
    config,
    valid: jax.Array | None = None,  # [B] true tokens of each row
    layer_idx: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One KDA sublayer incl. the state's and the tail's update. Returns
    ``(out [B, T, hidden], state, conv)``; the buffers come back whole.
    No write gate: a model that holds a recurrent state runs as one
    pipeline stage (``mesh.validate_shardable``), whose writes always
    land."""
    b, t, _ = x.shape
    h, d = config.num_attention_heads, config.head_dim
    f32 = jnp.float32
    with jax.named_scope("kda.proj"):
        qkv = jnp.concatenate(
            [quant.dense(x, layer[n]) for n in ("kda_q", "kda_k", "kda_v")],
            axis=-1)
        decay_in = quant.dense(x, layer["w_decay"]).astype(f32)
        rate = jnp.exp(layer["a_log"].astype(f32))[:, None]  # [H, 1]
        g = config.kda_lower_bound * jax.nn.sigmoid(
            rate * (decay_in + layer["dt_bias"].astype(f32)).reshape(
                b, t, h, d))
        beta = jax.nn.sigmoid(quant.dense(x, layer["w_beta"]).astype(f32))
        gate = jax.nn.sigmoid(quant.dense(x, layer["wg"]).astype(f32))
    with jax.named_scope("kda.conv"):
        taps = jnp.concatenate(
            [layer[n] for n in ("conv_q", "conv_k", "conv_v")], axis=-1)
        y, tail = causal_conv(qkv, kv.layer_view(conv, layer_idx), taps,
                              valid)
        y = jax.nn.silu(y).reshape(b, t, 3, h, d)
        q = _l2norm(y[:, :, 0]) * d ** -0.5
        k, v = _l2norm(y[:, :, 1]), y[:, :, 2]
    if valid is not None:
        live = jnp.arange(t, dtype=jnp.int32)[None] < valid[:, None]
        g = jnp.where(live[..., None, None], g, 0.0)
        beta = jnp.where(live[..., None], beta, 0.0)
    if t == 1 and layer_idx is not None and kda_decode_choice(d, d) == (
            "kernel"):
        from cake_tpu.ops.pallas.kda import kda_decode

        with jax.named_scope("kda.step"):
            o, state = kda_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                  beta[:, 0], state, layer_idx)
            o = o[:, None]
    else:
        s0 = kv.layer_view(state, layer_idx)
        if t == 1:
            with jax.named_scope("kda.step"):
                o, s1 = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                 beta[:, 0], s0)
                o = o[:, None]
        else:
            with jax.named_scope("kda.chunk"):
                o, s1 = kda_chunk(q, k, v, g, beta, s0)
        state = kv.layer_store(state, s1, layer_idx)
    conv = kv.layer_store(conv, tail.astype(conv.dtype), layer_idx)
    o = rms_norm(o, layer["o_norm"].astype(f32), config.rms_norm_eps)
    o = (o * gate.reshape(b, t, h, d)).astype(x.dtype).reshape(b, t, h * d)
    return quant.dense(o, layer["wo"]), state, conv
