"""Manifold-constrained hyper-connections: a residual stream ``n`` hidden
vectors wide, mixed round every sub-layer by coefficients the token's own
state chooses ("Hyper-Connections", arXiv:2409.19606; "mHC", arXiv:2512.24880;
``LlamaConfig.hc_mult``).

A token's state between sub-layers is ``X [n, C]``. A sub-layer ``F`` with
its own ``phi [n C, n^2 + 2 n]``, ``b [n^2 + 2 n]`` and ``alpha [3]``
(float32 whatever the serving type) runs as

    x~ = vec(X);  m = (x~ phi) * rsqrt(mean(x~^2) + norm_eps)
    H_pre  = sigmoid(alpha_pre m_pre + b_pre)                     [n]
    H_post = 2 sigmoid(alpha_post m_post + b_post)                [n]
    H_res  = sinkhorn(exp(clamp(alpha_res m_res + b_res)))        [n, n]
    u = sum_j H_pre[j] X[j];   y = F(RMS(u));
    X'[i] = H_post[i] y + sum_j H_res[i, j] X[j]

(:func:`coefficients`, :func:`pre_mix`, :func:`post_mix`; the columns of
``phi`` in the order pre, post, res row-major). ``H_res`` is non-negative
with rows and columns summing to 1 after the rounds of :func:`sinkhorn`, a
convex mixture of permutations: the stream neither grows nor dies with
depth.

Between the model's ends the stream is ``[B, T, n, C]`` (:func:`widen`,
:func:`narrow`); the layer loop carries it as its ``n`` hidden vectors, a
``[B, T, C]`` array each (:func:`split`, :func:`join`), and a coefficient
is an array a ROW (``[B T]``), never an axis of ``n``. These are the
forms the chip's compiler took best (my AOT compiles, PR 51;
``tests/test_chip_compile.py``; PERF.md section 7). With an axis of 4 in
the loop's carry it holds the stream in ``(4, 128)`` tiles and, every
sub-layer, writes it out again as float32 with the streams apart for the
product with ``phi`` and the mixes (29 MB a 512-row admission where the
stream is 14.7); a ``concatenate`` of the mixed streams is a pass of its
own. With the streams apart every slice is free, ``x~ phi`` is ``n``
products over the arrays as they lie, and both mixes are one elementwise
expression an output, the sum over ``j`` written out (no product
contracting an axis of ``n``). The Sinkhorn rounds' row and column sums
are adds of the sixteen cells, so a chain is elementwise and holds no
reduction (as ``sum(axis)`` over a ``[.., 4, 4]`` array a round is four
fusions and more, slices and broadcasts of such an array the same). The
24 numbers a token are computed in float32 from the stream as it is held
(``phi`` in three bfloat16 parts where the stream is bfloat16: the
products are then exact and the stream is never converted); both mixes
accumulate in float32 and round once.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

class Coefficients(NamedTuple):
    """One sub-layer's mixing coefficients, an array a ROW (``[B T]``,
    float32) each: ``pre[j]``, ``post[i]`` and ``res[i][j]``."""

    pre: tuple
    post: tuple
    res: tuple


def widen(x: jax.Array, n: int) -> jax.Array:
    """The entry: ``X_0[j] = x`` for every stream (``[B, T, C] -> [B, T,
    n, C]``; the plain residual, ``n`` 1, stays as it is)."""
    if n == 1:
        return x
    return jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (n, x.shape[-1]))


def narrow(x: jax.Array, n: int) -> jax.Array:
    """The exit: ``h = sum_j X[j]`` (``[.., n, C] -> [.., C]``; a float32
    sum, rounded once)."""
    if n == 1:
        return x
    return jnp.sum(x.astype(jnp.float32), axis=-2).astype(x.dtype)


def split(x: jax.Array) -> tuple:
    """``[B, T, n, C]`` -> its ``n`` hidden vectors, what the layer loop
    carries."""
    return tuple(x[..., j, :] for j in range(x.shape[-2]))


def join(parts) -> jax.Array:
    """The inverse of :func:`split`."""
    return jnp.stack(parts, axis=-2)


def sinkhorn_cells(cells, iters: int, eps: float):
    """``iters`` rounds of ``M <- M / (rowsum(M) + eps)``, ``M <- M /
    (colsum(M) + eps)`` on a matrix held as its cells (``cells[i][j]``, an
    array each): the sums are adds of the cells, so the chain is
    elementwise."""
    n = len(cells)
    cells = [list(row) for row in cells]
    for _ in range(iters):
        for i in range(n):
            total = sum(cells[i][1:], cells[i][0]) + eps
            cells[i] = [c / total for c in cells[i]]
        for j in range(n):
            total = sum((cells[i][j] for i in range(1, n)), cells[0][j]) + eps
            for i in range(n):
                cells[i][j] = cells[i][j] / total
    return cells


def sinkhorn(logits: jax.Array, iters: int, eps: float,
             clamp: tuple[float, float]) -> jax.Array:
    """``[..., n, n]`` logits -> the doubly stochastic ``[..., n, n]``:
    :func:`sinkhorn_cells` of ``exp(clamp(logits))``."""
    n = logits.shape[-1]
    m = jnp.exp(jnp.clip(logits.astype(jnp.float32), *clamp))
    cells = sinkhorn_cells(
        [[m[..., i, j] for j in range(n)] for i in range(n)], iters, eps)
    return jnp.stack([jnp.stack(row, -1) for row in cells], -2)


def _rows(x: jax.Array) -> jax.Array:
    """``[B, T, C] -> [B T, C]``: the mixes and the coefficients know rows
    alone. (A coefficient a ``[B, T]`` array stalls the chip's compiler
    where both axes are long: the ``[32, 16]`` batch prefill a server
    starts with did not compile in 15 minutes, the same 512 rows as ``[1,
    512]`` in 18 s: my AOT compiles, PR 51.)"""
    return x.reshape(-1, x.shape[-1])


def _project(rows, phi: jax.Array) -> jax.Array:
    """``x~ phi`` in float32 (``[R, n^2 + 2 n]``): a product a stream
    with its ``C`` rows of ``phi``. A bfloat16 stream meets ``phi`` as its
    three bfloat16 parts side by side (hi + mid + lo is ``phi`` to the
    last bit, a bfloat16 times a bfloat16 is exact in float32, and the MXU
    adds in float32), one pass over the stream as it lies; any other type
    takes the float32 product at the highest precision."""
    c, k = rows[0].shape[-1], phi.shape[-1]
    phi = phi.astype(jnp.float32)
    if rows[0].dtype != jnp.bfloat16:
        return sum(jnp.einsum(
            "rc,ck->rk", p.astype(jnp.float32), phi[j * c:(j + 1) * c],
            precision=jax.lax.Precision.HIGHEST)
            for j, p in enumerate(rows))
    pieces, rest = [], phi
    for _ in range(3):
        pieces.append(rest.astype(jnp.bfloat16))
        rest = rest - pieces[-1].astype(jnp.float32)
    wide = jnp.concatenate(pieces, axis=-1)  # [n C, 3 k]
    m = sum(jnp.einsum("rc,ck->rk", p, wide[j * c:(j + 1) * c],
                       preferred_element_type=jnp.float32)
            for j, p in enumerate(rows))
    return m[:, :k] + m[:, k:2 * k] + m[:, 2 * k:]


def coefficients(parts, hc, config) -> Coefficients:
    """The ``n^2 + 2 n`` coefficients a token of the stream ``parts`` (its
    ``n`` hidden vectors ``[B, T, C]``), an array ``[B T]`` each; ``hc =
    (phi, b, alpha)``. The norm scales the product's 24 outputs, not its
    ``n C`` inputs."""
    phi, b, alpha = hc
    n = len(parts)
    with jax.named_scope("mhc.coeff"):
        rows = [_rows(p) for p in parts]
        m = _project(rows, phi)
        squares = sum(jnp.sum(jnp.square(p.astype(jnp.float32)), axis=-1)
                      for p in rows)
        inv = jax.lax.rsqrt(squares / (n * rows[0].shape[-1])
                            + config.rms_norm_eps)
        # alpha_pre n times, alpha_post n times, alpha_res n^2 times
        gain = alpha.astype(jnp.float32)[
            np.repeat(np.arange(3), (n, n, n * n))]
        m = m * inv[:, None] * gain + b.astype(jnp.float32)
        cell = [m[:, k] for k in range(n * n + 2 * n)]
        pre = tuple(jax.nn.sigmoid(c) for c in cell[:n])
        post = tuple(2.0 * jax.nn.sigmoid(c) for c in cell[n:2 * n])
        lo, hi = config.hc_res_clamp
        res = sinkhorn_cells(
            [[jnp.exp(jnp.clip(cell[2 * n + i * n + j], lo, hi))
              for j in range(n)] for i in range(n)],
            config.hc_sinkhorn_iters, config.hc_eps)
        return Coefficients(pre, post, tuple(tuple(row) for row in res))


def pre_mix(parts, co: Coefficients) -> jax.Array:
    """``u = sum_j H_pre[j] X[j]`` (``[B, T, C]``)."""
    with jax.named_scope("mhc.pre"):
        u = sum(h[:, None] * _rows(p).astype(jnp.float32)
                for h, p in zip(co.pre, parts))
        return u.astype(parts[0].dtype).reshape(parts[0].shape)


def post_mix(parts, y: jax.Array, co: Coefficients) -> tuple:
    """``X'[i] = H_post[i] y + sum_j H_res[i, j] X[j]``: one elementwise
    expression a stream, the sum over ``j`` written out."""
    with jax.named_scope("mhc.post"):
        wide = [_rows(p).astype(jnp.float32) for p in parts]
        yf = _rows(y).astype(jnp.float32)
        return tuple(
            (co.post[i][:, None] * yf
             + sum(co.res[i][j][:, None] * wide[j]
                   for j in range(len(wide)))).astype(
                       parts[0].dtype).reshape(parts[0].shape)
            for i in range(len(wide)))
