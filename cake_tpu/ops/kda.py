"""Delta-rule linear attention over a recurrent state: ONE step, ONE chunk
form and ONE decode kernel for the two rules this repo serves.

The reference serves one attention, over cached keys and values
(`cake-core/src/model/attention.rs`); this is the layer that keeps none.
A value head holds a float32 state ``S [d_k, d_v]`` a stream, whatever the
stream's length, and per token, with a log-decay ``g <= 0``:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

**KDA** (``bailing_hybrid``, configuration ``ling3flash-ep4-cut``;
:func:`kda_attention_block`): as many key heads as value heads, a decay a
CHANNEL, bounded, a sigmoid gate a channel on the output:

    [q | k | v] = silu(conv4([x W_q | x W_k | x W_v]))    causal, depthwise
    q, k        = l2norm_head(q) * d_k^-0.5, l2norm_head(k)
    g           = lower * sigmoid(exp(A_log_h) * (x W_f + dt_bias))   in (lower, 0)
    beta        = sigmoid(x W_b)                                      a head
    y           = (rmsnorm_head(o) * sigmoid(x W_g)) W_o

**The scalar-gated rule** (Gated DeltaNet; ``qwen3_next``, configuration
``qwen3next-ep4-cut``; :func:`gdn_attention_block`): ``Hk`` key heads
under ``Hv = r Hk`` value heads (value head ``h`` reads key head ``h //
r``), a decay a HEAD, unbounded below, one fused projection, a silu gate:

    [q | k | v | z] = x W_qkvz;  [b | a] = x W_ba
    [q | k | v]     = silu(conv4([q | k | v]))    over 2 Hk d_k + Hv d_v channels
    q, k            = l2norm_head(q) * d_k^-0.5, l2norm_head(k)
    g_h             = -exp(A_log_h) * softplus(a_h + dt_bias_h)      a scalar
    beta_h          = sigmoid(b_h)
    y               = (rmsnorm_head(o; w) * silu(z)) W_o

The functions below take either: ``q, k [.., Hk, d_k]`` under ``v [..,
Hv, d_v]``, and ``g`` shaped as ``q`` is over the VALUE heads (``[.., Hv,
d_k]``, a channel) or as ``beta`` is (``[.., Hv]``, a head). The scalar
case is the cheaper one, not the channel case fed a broadcast: a chunk's
decay is a ``[C, C]`` mask a head on ``K K^T`` and ``Q K^T`` (two matmuls a
KEY head) where the channel case sums a ``[C, C, d_k]`` tensor, and the
kernel reads one value a head where it reads a ``d_k``-wide row.

**Cached:** ``S`` (float32) and the last ``taps - 1`` inputs of the
convolutions, in the two recurrent buffers of
:class:`cake_tpu.ops.kvcache.KVCache` (``state [L, B, Hv, d_k, d_v]``,
``conv [L, B, taps - 1, 2 Hk d_k + Hv d_v]``: ``LlamaConfig.delta_rule``),
read and written in place on the carried cache as rows are. A slot's state
has no frontier that hides what the last stream left: an admission starts
from a zeroed staging row and the splice copies state and tail over the
slot's.

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
  fast the decays are; the form is exact against the recurrence
  (float32, matmuls at the highest precision), enters through the slot's
  state and leaves through it, so a chunked admission is exact too.

``valid [B]``: the true tokens of each row of a bucketed chunk. A padded
token gets ``beta = 0`` and ``g = 0`` (it neither writes nor decays the
state) and the convolutions' tail is taken at the true length. The scan is
serial all the same: a bucket's padding costs its chunks
(``delta.chunks_swept`` against ``delta.chunks_live``,
``runtime/batch_generator.py``).
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


def _over_value_heads(a, heads: int):
    """``a [B, Hk, ..]`` as the ``heads`` value heads read it: value head
    ``h`` takes key head ``h // (heads / Hk)``."""
    rep = heads // a.shape[1]
    return a if rep == 1 else jnp.repeat(a, rep, axis=1)


def kda_step(q, k, v, g, beta, state):
    """One token. ``q, k [B, Hk, d_k]``, ``v [B, Hv, d_v]``, ``g [B, Hv,
    d_k]`` (a decay a channel) or ``[B, Hv]`` (a head), ``beta [B, Hv]``,
    ``state [B, Hv, d_k, d_v]``, all float32. Returns ``(o [B, Hv, d_v],
    state)``. Elementwise products and sums: exact float32 on any backend,
    one read and one write of the state."""
    q, k = (_over_value_heads(a, v.shape[1]) for a in (q, k))
    decay = jnp.exp(g)
    s = state * (decay[..., None] if g.ndim == k.ndim
                 else decay[..., None, None])
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
    """``T`` tokens in chunks of ``chunk`` (module docstring). ``q, k [B, T,
    Hk, d_k]``, ``v [B, T, Hv, d_v]``, ``g [B, T, Hv, d_k]`` (a decay a
    channel) or ``[B, T, Hv]`` (a head), ``beta [B, T, Hv]``, ``state [B,
    Hv, d_k, d_v]``, all float32. Returns ``(o [B, T, Hv, d_v], state)``.
    Inside, the value heads lie ``[G, R]``: ``G = Hk`` key heads, each
    under its ``R`` value heads, so that what only q and k make (``K K^T``,
    ``Q K^T`` in the scalar case) is made once a KEY head."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    r = hv // hk
    scalar = g.ndim == beta.ndim
    c = min(chunk, t)
    pad = -t % c
    if pad:  # tokens that neither write nor decay the state
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    n = (t + pad) // c

    def chunks(a, grouped=True):
        """``[B, T, H, ..] -> [n, B, G, (R,) C, ..]``"""
        a = a.reshape((b, n, c) + ((hk, r) if grouped else (hk,))
                      + a.shape[3:])
        lead = 2 if grouped else 1  # head axes behind the chunk's
        return jnp.moveaxis(
            jnp.moveaxis(a, 2, 2 + lead), 1, 0)

    tri = jnp.tril(jnp.ones((c, c), jnp.bool_))

    @jax.default_matmul_precision("highest")
    def body(s0, xs):
        qc, kc, vc, gc, bc = xs  # qc, kc [B, G, C, dk]; the rest [B, G, R, C, .]
        cum = jnp.cumsum(gc, axis=3)  # G_t, inclusive
        if scalar:
            # e^{G_t - G_s} for s <= t, ONE [C, C] mask a head (exponents
            # <= 0) on the key head's two products
            mask = jnp.exp(jnp.where(
                tri, cum[..., :, None] - cum[..., None, :], -jnp.inf))
            kk = jnp.einsum("bgck,bgsk->bgcs", kc, kc)[:, :, None] * mask
            qk = jnp.einsum("bgck,bgsk->bgcs", qc, kc)[:, :, None] * mask
            into = jnp.exp(cum)[..., None]  # e^{G_t} [B, G, R, C, 1]
            out = jnp.exp(cum[..., -1:] - cum)[..., None]  # e^{G_C - G_s}
            carried = jnp.exp(cum[..., -1])[..., None, None]

            def from_state(a):  # (a_t e^{G_t})^T S_0
                return jnp.einsum("bgck,bgrkv->bgrcv", a, s0) * into

            def to_state(u):  # sum_s (k_s e^{G_C - G_s}) u_s^T
                return jnp.einsum("bgsk,bgrsv->bgrkv", kc, u * out)
        else:
            # ... a channel: a [C, C, dk] tensor a head
            decay = jnp.exp(jnp.where(
                tri[..., None],
                cum[:, :, :, :, None] - cum[:, :, :, None, :], -jnp.inf))
            k_t, k_s = kc[:, :, None, :, None], kc[:, :, None, None, :]
            kk = jnp.sum(k_t * k_s * decay, axis=-1)
            qk = jnp.sum(qc[:, :, None, :, None] * k_s * decay, axis=-1)
            into = jnp.exp(cum)  # [B, G, R, C, dk]
            out = jnp.exp(cum[:, :, :, -1:] - cum)
            carried = into[:, :, :, -1, :, None]

            def from_state(a):
                return jnp.einsum("bgrck,bgrkv->bgrcv",
                                  a[:, :, None] * into, s0)

            def to_state(u):
                return jnp.einsum("bgrsk,bgrsv->bgrkv",
                                  kc[:, :, None] * out, u)

        rhs = bc[..., None] * (vc - from_state(kc))
        m = jnp.eye(c, dtype=kk.dtype) + bc[..., None] * jnp.tril(kk, -1)
        u = jax.scipy.linalg.solve_triangular(
            m, rhs, lower=True, unit_diagonal=True)
        o = from_state(qc) + jnp.einsum("bgrcs,bgrsv->bgrcv", qk, u)
        return s0 * carried + to_state(u), o

    state, o = jax.lax.scan(
        body, state.reshape(b, hk, r, dk, dv),
        (chunks(q, False), chunks(k, False), chunks(v), chunks(g),
         chunks(beta)))
    # [n, B, G, R, C, dv] -> [B, T, Hv, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 4, 2).reshape(b, n * c, hv, dv)
    return o[:, :t], state.reshape(b, hv, dk, dv)


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
    o, state = _advance(q, k, v, g, beta, state, valid, layer_idx, "kda")
    conv = kv.layer_store(conv, tail.astype(conv.dtype), layer_idx)
    o = rms_norm(o, layer["o_norm"].astype(f32), config.rms_norm_eps)
    o = (o * gate.reshape(b, t, h, d)).astype(x.dtype).reshape(b, t, h * d)
    return quant.dense(o, layer["wo"]), state, conv


def _advance(q, k, v, g, beta, state, valid, layer_idx, scope: str):
    """The recurrence over a chunk's ``T`` tokens from layer ``layer_idx``
    of the carried ``state``, by the form ``T`` chooses (module docstring),
    under the named scope ``<scope>.step`` or ``<scope>.chunk``; padded
    tokens (``valid``) touch nothing. Returns ``(o [B, T, Hv, d_v],
    state)``, the buffer whole."""
    t = q.shape[1]
    if valid is not None:
        live = jnp.arange(t, dtype=jnp.int32)[None] < valid[:, None]
        g = jnp.where(live.reshape(live.shape + (1,) * (g.ndim - 2)), g, 0.0)
        beta = jnp.where(live[..., None], beta, 0.0)
    if t == 1 and layer_idx is not None and kda_decode_choice(
            *state.shape[-2:]) == "kernel":
        from cake_tpu.ops.pallas.kda import kda_decode

        with jax.named_scope(f"{scope}.step"):
            o, state = kda_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                  beta[:, 0], state, layer_idx)
        return o[:, None], state
    s0 = kv.layer_view(state, layer_idx)
    if t == 1:
        with jax.named_scope(f"{scope}.step"):
            o, s1 = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                             s0)
            o = o[:, None]
    else:
        with jax.named_scope(f"{scope}.chunk"):
            o, s1 = kda_chunk(q, k, v, g, beta, s0)
    return o, kv.layer_store(state, s1, layer_idx)


def gdn_attention_block(
    x: jax.Array,  # [B, T, hidden], normed
    layer: dict,
    state: jax.Array,  # [(L,) B, Hv, d_k, d_v] float32
    conv: jax.Array,  # [(L,) B, K - 1, 2 Hk d_k + Hv d_v]
    config,
    valid: jax.Array | None = None,  # [B] true tokens of each row
    layer_idx: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One scalar-gated delta-rule sublayer (module docstring) incl. the
    state's and the tail's update; as :func:`kda_attention_block` returns
    ``(out [B, T, hidden], state, conv)``, the buffers whole."""
    b, t, _ = x.shape
    hk, hv, dk, dv, _ = config.delta_rule
    width = config.delta_conv_width
    f32 = jnp.float32
    with jax.named_scope("gdn.proj"):
        qkvz = quant.dense(x, layer["w_qkvz"])
        ba = quant.dense(x, layer["w_ba"]).astype(f32)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(layer["a_log"].astype(f32)) * jax.nn.softplus(
            ba[..., hv:] + layer["dt_bias"].astype(f32))  # [B, T, Hv]
        z = qkvz[..., width:].astype(f32).reshape(b, t, hv, dv)
    with jax.named_scope("gdn.conv"):
        y, tail = causal_conv(qkvz[..., :width],
                              kv.layer_view(conv, layer_idx),
                              layer["conv_qkv"], valid)
        y = jax.nn.silu(y)
        q = _l2norm(y[..., :hk * dk].reshape(b, t, hk, dk)) * dk ** -0.5
        k = _l2norm(y[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk))
        v = y[..., 2 * hk * dk:].reshape(b, t, hv, dv)
    o, state = _advance(q, k, v, g, beta, state, valid, layer_idx, "gdn")
    conv = kv.layer_store(conv, tail.astype(conv.dtype), layer_idx)
    o = rms_norm(o, layer["o_norm"].astype(f32), config.rms_norm_eps)
    o = (o * jax.nn.silu(z)).astype(x.dtype).reshape(b, t, hv * dv)
    return quant.dense(o, layer["w_out"]), state, conv
