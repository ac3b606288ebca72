"""Delta-rule linear attention over a recurrent state: ONE step, ONE chunk
form, ONE decode kernel for the two rules this repo serves, and a kernel
for the chunk form's serial scan where the decay is a head's.

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
  ``A_ts = sum_c k_tc k_sc e^{G_tc - G_sc}``, i.e. ``(I + N) u = beta (v -
  (k e^G)^T S_0)`` with ``N = beta tril(A, -1)``; then ``o_t = (q_t
  e^{G_t})^T S_0 + sum_{s<=t} A^q_ts u_s`` and ``S_C = e^{G_C} S_0 + sum_s
  (k_s e^{G_C - G_s}) u_s^T``. Every exponent is a difference ``G_t - G_s``
  with ``s <= t``, so nothing overflows however fast the decays are; the
  form is exact against the recurrence (float32, matmuls at the highest
  precision), enters through the slot's state and leaves through it, so a
  chunked admission is exact too.

  **What is made ahead of the scan.** ``N`` is made of the chunk's keys,
  decays and ``beta`` alone; only the right-hand side reads the carried
  state. So ``T = (I + N)^-1`` (:func:`_unit_lower_inverse`) and the halves
  of ``u`` that do not read the state, ``u_hat = T (beta v)`` and ``w = T
  (beta k e^G)``, are made for ALL chunks of a bucket at once, batched over
  ``[n, B, G, R]``, and the serial scan over the chunks holds products on
  the state alone: ``u = u_hat - w S_0``, ``o = (q e^G) S_0 + A^q u``, ``S_C
  = e^{G_C} S_0 + (k e^{G_C - G})^T u``. **Who runs that scan** (PR 65;
  :func:`kda_chunk_choice`, from what a trace sees: the rule, the state's
  tiles, the bucket's tokens, no knob): the ``jnp`` loop below, a
  ``fori_loop`` whose every turn reads all heads' state from HBM, slices
  its six operands out of bucket-sized arrays and launches a handful of
  small fusions (44 us a chunk of 32 heads on the chip where the MXU's own
  time is ~14); or, for the scalar-gated rule on the chip from
  ``KDA_SCAN_MIN_T`` tokens on, ONE call a layer of the Pallas kernel
  :func:`cake_tpu.ops.pallas.kda.kda_chunk_scan`, in which a block of
  heads' state stays in VMEM from the first live chunk to the last, the
  next chunk's operands are fetched while this one's products run, and
  ``q e^G``, ``k e^{G_C - G}`` and the masked ``Q K^T`` are made there from
  ``q``, ``k``, ``Q K^T`` a KEY head and ``G``: three bucket-sized arrays
  a value head that the loop's form writes and reads back are never made.
  The same arithmetic (float32 operands, every product at the highest
  precision); the loop is the other branch and the oracle, and the decay
  a channel's only form. The kernel also ends the layer: given the gate
  it writes ``rmsnorm_head(o) * silu(z)`` as the output projection reads
  it (:func:`_gated` is the same in ``jnp``, for every other path),
  because whatever layout it returned ``o`` in, XLA copied ``o`` and ``z``
  into another tiling before gating them, which cost more than the scan
  saved (``ops/pallas/kda.py``). Where the decay is a channel's a
  chunk's ``[C, C, d_k]`` tensor is as large as all of a long bucket's
  ``[C, C]`` masks together (67 MB at 32 heads of 128): held for ``n``
  chunks it would not fit, so past ``HOIST_BYTES`` the same two functions
  run a chunk at a time inside the scan. One algorithm, placed by the
  shapes a trace sees.

  **How ``T`` is made** (PR 58). Until then a unit-triangular solve a chunk
  and layer inside the scan: on the chip a custom call of 161 us for 32
  systems of 64 x 64, the MXU idle under it, 36.5% of the admission
  programs' time where prompts are long (PERF.md section 6).
  :func:`_unit_lower_inverse` inverts the diagonal blocks of
  ``INVERSE_BLOCK`` rows by forward substitution and merges neighbours by
  products, ``T21 = -(T22 N21 T11)``. The Neumann product ``(I - N)(I +
  N^2)(I + N^4)..(I + N^32)`` would be products alone and was REFUSED:
  where keys repeat and the decay is near one (a prompt that repeats a
  token) ``N``'s powers grow before they vanish and cancel. Worst error
  against float64 (numpy float32 on the host, the test's and the model's
  widths, 20 draws a case, some with strongly correlated keys, ``beta``
  within 1e-3 of 1; ISSUE 58):

  ==========================================  ========  ===================
  form                                        no decay  random scalar decay
  ==========================================  ========  ===================
  forward substitution (what the solve does)  2.2e-6    4.0e-7
  blocks of 16, then two merges               1.6e-6    4.0e-7
  the Neumann product ("doubling")            1.4e11    4.0e-7
  ==========================================  ========  ===================

``valid [B]``: the true tokens of each row of a bucketed chunk. A padded
token gets ``beta = 0`` and ``g = 0`` (it neither writes nor decays the
state) and the convolutions' tail is taken at the true length. A chunk of
such tokens is the identity on the state (``u_hat = w = 0``, the carried
decay 1), so the serial loop stops at the last chunk that holds a true
token of SOME row of the launch (:func:`_advance` counts them, a traced
bound: one program a bucket whatever the prompts' lengths) and a bucket's
padding costs no chunk of it; ``o`` past that chunk is zero, and is
padding's. What is left is a launch's shorter rows, which ride to the
longest's end (``delta.chunks_swept`` against ``delta.chunks_live``,
``runtime/batch_generator.py``). What the chunks make ahead of the loop
is made for the whole bucket all the same: batched, off the serial path.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.ops import kvcache as kv
from cake_tpu.ops import pallas as pk
from cake_tpu.ops import quant
from cake_tpu.ops.norms import rms_norm

CHUNK = 64
# rows of a diagonal block of a chunk's inverse (tools/kda_sweep --chunk)
INVERSE_BLOCK = 16
# What the chunks may hold ahead of the scan, counted on the widest thing
# each makes: an 8192-row bucket's [C, C] a head at 32 heads; ONE chunk's
# decay at the same widths where it is a channel's ([C, C, d_k])
HOIST_BYTES = 1 << 26
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


# fewest tokens of a bucket whose scan takes the kernel: a layer 1.11x
# XLA's loop there, 1.24-1.26x at 2048, 1.49-1.51x at 4096 and 8192, and
# 1.04-1.09x at 256 and 512, under the 1.10x a second form must show
# (tools/kda_sweep --chunk --forms xla,kernel at live shares 1.0 and 0.67,
# my chip runs, PR 65; PERF.md section 6)
KDA_SCAN_MIN_T = 1024
# what each traced bucket's scan took, by its tokens (chunk_form_traced)
_traced: dict[int, str] = {}


def kda_chunk_choice(t: int, d_k: int, d_v: int, scalar: bool) -> str:
    """``"kernel"`` or ``"xla"`` for the serial scan of an admission's
    ``t`` tokens: THE policy, from what a trace can see. The kernel
    (:func:`cake_tpu.ops.pallas.kda.kda_chunk_scan`) is the scalar-gated
    rule's (a decay a head), wants whole ``(8, 128)`` tiles of a head's
    state and a bucket long enough to pay for its launch; off the chip it
    runs interpreted, and only when kernels are forced (tests)."""
    if not (scalar and pk.kernels_enabled()):
        return "xla"
    if pk.interpret_default():
        return "kernel" if pk.force_kernels() else "xla"
    return "kernel" if (d_k % 128 == 0 and d_v % 128 == 0
                        and t >= KDA_SCAN_MIN_T) else "xla"


def chunk_form_traced(t: int) -> str | None:
    """What the scan of the last ``t``-token admission traced took
    (``"kernel"`` | ``"xla"``; None: none was traced)."""
    return _traced.get(t)


def kda_recurrence(q, k, v, g, beta, state):
    """The recurrence token by token over ``[B, T, H, .]`` inputs: what
    :func:`kda_chunk` must equal."""
    def body(s, xs):
        o, s = kda_step(*xs, s)
        return s, o

    state, o = jax.lax.scan(
        body, state, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


@partial(jax.jit, static_argnums=1)
@jax.default_matmul_precision("highest")
def _unit_lower_inverse(n_mat, block: int):
    """``(I + N)^-1`` for ``N [.., C, C]`` strictly lower triangular, by
    products (module docstring): the diagonal blocks of ``block`` rows by
    forward substitution, unrolled, every step one multiply-add over all
    blocks of all matrices at once (the batch lies minor: whole lanes
    whatever ``block`` is); then ``log2(C / block)`` levels that merge
    neighbours, ``T21 = -(T22 N21 T11)``. Float32, as exact as the solve
    it replaced. A ``C`` that is no ``block * 2^m`` is completed by the
    identity. A function of its own to JAX, and the substitution's step
    written once: an admission program's layers and a server's buckets
    trace it once a shape, where sixteen steps of Python a call cost a
    warm start 2 s a program (PERF.md section 6, PR 58)."""
    c = n_mat.shape[-1]
    block = min(block, c)
    size = block
    while size < c:
        size *= 2
    if size > c:
        n_mat = jnp.pad(
            n_mat, ((0, 0),) * (n_mat.ndim - 2) + ((0, size - c),) * 2)

    def blocks(s, below=0):
        """The ``s``-row blocks on the diagonal (``below`` 1: under it, of
        every second) ``[.., size / (s << below), s, s]``"""
        return jnp.stack(
            [n_mat[..., i + below * s:i + (below + 1) * s, i:i + s]
             for i in range(0, size, s << below)], axis=-3)

    low = jnp.moveaxis(blocks(block), (-2, -1), (0, 1))  # [i, j, .., nb]
    eye = jnp.eye(block, dtype=n_mat.dtype).reshape(
        (block, block) + (1,) * (low.ndim - 2))

    def row(i, t):  # row i of T: e_i - sum_{j<i} N_ij T_j (N_ij = 0, j >= i)
        return jax.lax.dynamic_update_index_in_dim(
            t, eye[i] - jnp.sum(low[i][:, None] * t, axis=0), i, 0)

    t = jax.lax.fori_loop(1, block, row, jnp.broadcast_to(eye, low.shape),
                          unroll=True)
    t = jnp.moveaxis(t, (0, 1), (-2, -1))  # [.., nb, s, s]
    s = block
    while s < size:
        t11, t22 = t[..., 0::2, :, :], t[..., 1::2, :, :]
        t21 = -(t22 @ blocks(s, 1) @ t11)
        t = jnp.concatenate(
            [jnp.concatenate([t11, jnp.zeros_like(t11)], axis=-1),
             jnp.concatenate([t21, t22], axis=-1)], axis=-2)
        s *= 2
    return t[..., 0, :c, :c]


@partial(jax.jit, static_argnames=("chunk", "form", "eps"))
@jax.default_matmul_precision("highest")
def kda_chunk(q, k, v, g, beta, state, live=None, chunk: int = CHUNK,
              form: str = "xla", gate=None, eps: float = 0.0):
    """``T`` tokens in chunks of ``chunk`` (module docstring). ``q, k [B, T,
    Hk, d_k]``, ``v [B, T, Hv, d_v]``, ``g [B, T, Hv, d_k]`` (a decay a
    channel) or ``[B, T, Hv]`` (a head), ``beta [B, T, Hv]``, ``state [B,
    Hv, d_k, d_v]``, all float32. Returns ``(o [B, T, Hv, d_v], state)``.
    ``live`` (int32 ``[]``, traced; None: all): the leading chunks that
    hold a token which writes or decays the state (``beta`` and ``g`` are
    0 from there on). The serial loop stops there: the state is what all
    ``T`` tokens leave (a chunk past ``live`` is the identity on it),
    ``o`` is zero past ``live x chunk``. ``form``: who runs the serial
    scan, the ``jnp`` loop below (``"xla"``) or, for a decay a head, the
    Pallas kernel (``"kernel"``; :func:`kda_chunk_choice` for a layer),
    which with ``gate`` ``(z [B, T, Hv d_v], norm [d_v])`` and ``eps``
    returns the layer's output :func:`_gated` in ``o``'s place.
    Inside, the value heads lie ``[G, R]``: ``G = Hk`` key heads, each
    under its ``R`` value heads, so that what only q and k make (``K K^T``,
    ``Q K^T`` in the scalar case) is made once a KEY head. A function of
    its own to JAX: a program's stacks of delta-rule layers trace it
    once."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    r = hv // hk
    scalar = g.ndim == beta.ndim
    c = min(chunk, t)
    pad = -t % c
    assert gate is None or form == "kernel", form
    if pad:  # tokens that neither write nor decay the state
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
        if gate is not None:
            gate = (jnp.pad(gate[0], ((0, 0), (0, pad), (0, 0))), gate[1])
    n = (t + pad) // c

    def chunks(a, grouped=True):
        """``[B, T, H, ..] -> [n, B, G, (R,) C, ..]``"""
        a = a.reshape((b, n, c) + ((hk, r) if grouped else (hk,))
                      + a.shape[3:])
        lead = 2 if grouped else 1  # head axes behind the chunk's
        return jnp.moveaxis(
            jnp.moveaxis(a, 2, 2 + lead), 1, 0)

    tri = jnp.tril(jnp.ones((c, c), jnp.bool_))

    def ahead(xs):
        """What a chunk makes of its own tokens alone, the state unseen:
        ``qc, kc [B, G, C, dk]``, the rest ``[B, G, R, C, .]``."""
        qc, kc, vc, gc, bc = xs
        cum = jnp.cumsum(gc, axis=3)  # G_t, inclusive
        if scalar:
            # e^{G_t - G_s} for s <= t, ONE [C, C] mask a head (exponents
            # <= 0) on the key head's two products
            mask = jnp.exp(jnp.where(
                tri, cum[..., :, None] - cum[..., None, :], -jnp.inf))
            kk = jnp.einsum("bgck,bgsk->bgcs", kc, kc)[:, :, None] * mask
            qk = jnp.einsum("bgck,bgsk->bgcs", qc, kc)  # a KEY head
            if form != "kernel":  # which decays it where it is used
                qk = qk[:, :, None] * mask
            into = jnp.exp(cum)[..., None]  # e^{G_t} [B, G, R, C, 1]
            out = jnp.exp(cum[..., -1:] - cum)[..., None]  # e^{G_C - G_s}
            carried = jnp.exp(cum[..., -1])[..., None, None]
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
        # u = T (beta (v - (k e^G)^T S_0)), T = (I + beta tril(K K^T, -1))^-1:
        # the halves of the product on either side of the state
        inv = _unit_lower_inverse(bc[..., None] * jnp.tril(kk, -1),
                                  INVERSE_BLOCK)
        kc = kc[:, :, None]

        def halves():
            return (inv @ (bc[..., None] * vc),
                    inv @ (bc[..., None] * (kc * into)))

        if form == "kernel":  # which takes q and k themselves
            return qk, cum, *halves()
        return (qc[:, :, None] * into, kc * out, carried, qk, *halves())

    def advance(s0, xs):
        q_in, k_out, carried, qk, u_hat, w = xs
        u = u_hat - w @ s0
        o = q_in @ s0 + qk @ u
        return s0 * carried + jnp.einsum("bgrsk,bgrsv->bgrkv", k_out, u), o

    xs = (chunks(q, False), chunks(k, False), chunks(v), chunks(g),
          chunks(beta))
    if form == "kernel":
        assert scalar, "the scan kernel is the scalar-gated rule's"
        from cake_tpu.ops.pallas.kda import kda_chunk_scan

        # as many chunks a call as may be held ahead of it (all of a
        # bucket the engine launches: HOIST_BYTES is an 8192-row one's)
        per = max(1, HOIST_BYTES // (4 * b * hv * c * c))
        live = n if live is None else live
        outs = []
        for at in range(0, n, per):
            qk, cum, u_hat, w = jax.vmap(ahead)(
                tuple(a[at:at + per] for a in xs))
            m = cum.shape[0]
            o, state = kda_chunk_scan(
                xs[0][at:at + m], xs[1][at:at + m], qk,
                cum.reshape(m, b, hv, c), u_hat.reshape(m, b, hv, c, dv),
                w.reshape(m, b, hv, c, dk), state, live - at,
                gate=gate and (gate[0][:, at * c:(at + m) * c], gate[1], eps))
            outs.append(o)
        o = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
        return o[:, :t], state
    s0 = state.reshape(b, hk, r, dk, dv)
    # the widest thing a chunk makes ahead of the state: [C, C] a value
    # head, times d_k where the decay is a channel's
    widest = 4 * n * b * hv * c * c * (1 if scalar else dk)
    if widest <= HOIST_BYTES:  # every chunk at once, off the serial path
        step, xs = advance, jax.vmap(ahead)(xs)
    else:  # a chunk at a time, where it is used
        def step(s, x):
            return advance(s, ahead(x))
    if live is None:
        state, o = jax.lax.scan(step, s0, xs)
    else:
        # the same steps, as many as hold a token: the bound is data. The
        # loop writes ``o`` chunk by chunk into a buffer it carries, and a
        # second one zeroes the chunks past ``live``: each chunk of ``o``
        # is written once (a buffer of zeros filled first is 134 MB of an
        # 8192-row bucket, 3.6% of a full one's time; zeros selected in
        # afterwards are a pass over all of it, 7%: my chip runs, PR 60)
        live = jnp.clip(live, 0, n)

        def some(i, carry):
            s, o = carry
            s, here = step(s, jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False), xs))
            return s, jax.lax.dynamic_update_index_in_dim(o, here, i, 0)

        state, o = jax.lax.fori_loop(0, live, some, (
            s0, jax.lax.empty((n, b, hk, r, c, dv), v.dtype)))
        none = jnp.zeros(o.shape[1:], o.dtype)
        o = jax.lax.fori_loop(
            live, n,
            lambda i, o: jax.lax.dynamic_update_index_in_dim(o, none, i, 0),
            o)
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


def _gated(o, z, norm, eps: float):
    """The scalar-gated rule's output gate: ``rmsnorm_head(o; norm) *
    silu(z)`` for ``o [B, T, Hv, d_v]`` float32 and ``z [B, T, Hv d_v]``,
    as ``z`` lies and in its type."""
    o = rms_norm(o, norm.astype(jnp.float32), eps)
    gate = jax.nn.silu(z.astype(jnp.float32).reshape(o.shape))
    return (o * gate).astype(z.dtype).reshape(z.shape)


def _advance(q, k, v, g, beta, state, valid, layer_idx, scope: str,
             gate=None):
    """The recurrence over a chunk's ``T`` tokens from layer ``layer_idx``
    of the carried ``state``, by the form ``T`` chooses (module docstring),
    under the named scope ``<scope>.step`` or ``<scope>.chunk``; padded
    tokens (``valid``) touch nothing. Returns ``(o [B, T, Hv, d_v],
    state)``, the buffer whole; with ``gate`` ``(z, norm, eps)`` the
    layer's output :func:`_gated` ``[B, T, Hv d_v]`` in ``o``'s place
    (the scan kernel makes it where it has ``o`` in hand: what it returns
    for XLA to gate, XLA first copies into another tiling)."""
    t = q.shape[1]
    chunks = None
    if valid is not None:
        live = jnp.arange(t, dtype=jnp.int32)[None] < valid[:, None]
        g = jnp.where(live.reshape(live.shape + (1,) * (g.ndim - 2)), g, 0.0)
        beta = jnp.where(live[..., None], beta, 0.0)
        # the chunks that hold a true token of some row of the launch
        chunks = (jnp.max(valid) + CHUNK - 1) // CHUNK
    if t == 1 and layer_idx is not None and kda_decode_choice(
            *state.shape[-2:]) == "kernel":
        from cake_tpu.ops.pallas.kda import kda_decode

        with jax.named_scope(f"{scope}.step"):
            o, state = kda_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                  beta[:, 0], state, layer_idx)
        o = o[:, None]
        return (o if gate is None else _gated(o, *gate)), state
    s0 = kv.layer_view(state, layer_idx)
    if t == 1:
        with jax.named_scope(f"{scope}.step"):
            o, s1 = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                             s0)
            o = o[:, None]
    else:
        # trace time: which scan the admission program being built holds
        form = kda_chunk_choice(t, *state.shape[-2:], g.ndim == beta.ndim)
        _traced[t] = form
        gauge = obs_metrics.gauge("delta.chunk_kernel")
        gauge.set(max(gauge.value or 0, int(form == "kernel")))
        with jax.named_scope(f"{scope}.chunk"):
            if gate is not None and form == "kernel":  # whose epilogue it is
                o, s1 = kda_chunk(q, k, v, g, beta, s0, chunks, form=form,
                                  gate=gate[:2], eps=gate[2])
                gate = None
            else:
                o, s1 = kda_chunk(q, k, v, g, beta, s0, chunks, form=form)
    if gate is not None:
        o = _gated(o, *gate)
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
    with jax.named_scope("gdn.conv"):
        y, tail = causal_conv(qkvz[..., :width],
                              kv.layer_view(conv, layer_idx),
                              layer["conv_qkv"], valid)
        y = jax.nn.silu(y)
        q = _l2norm(y[..., :hk * dk].reshape(b, t, hk, dk)) * dk ** -0.5
        k = _l2norm(y[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk))
        v = y[..., 2 * hk * dk:].reshape(b, t, hv, dv)
    o, state = _advance(
        q, k, v, g, beta, state, valid, layer_idx, "gdn",
        gate=(qkvz[..., width:], layer["o_norm"], config.rms_norm_eps))
    conv = kv.layer_store(conv, tail.astype(conv.dtype), layer_idx)
    return quant.dense(o, layer["w_out"]), state, conv
