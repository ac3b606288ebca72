"""Device-resident KV page pool + the gather/scatter programs over it.

Physical layout: one pooled buffer per cache half,

    ``[num_layers, num_pages, kv_heads, page_size, head_dim]``

(int8 KV adds the per-slot scale half minus the trailing ``head_dim``,
mirroring :class:`cake_tpu.ops.kvcache.QuantizedKV`). The page axis is
UNSHARDED — pages are the allocation unit, addressed by value through
per-stream page tables — while layers shard over ``stage`` and kv heads
over ``tp`` exactly like the contiguous cache, so a pool page's HBM
placement matches the cache rows it replaces.

Inside a compiled decode step the pool is addressed through two small
int32 operands (shapes static -> no retrace, same discipline as the
constrain mask tables):

- ``page_map [B, pages_per_stream]`` — each stream's logical->physical
  page list, sink-padded past its frontier. The step GATHERS these pages
  into the standard contiguous ``[L, B, KH, S, D]`` view and runs the
  unchanged attention/KV-write body over it, so paged streams are
  bit-identical to slot streams by construction (the gathered view IS
  the slot cache's contents).
- ``scatter_ids [B, W]`` — the physical pages receiving this dispatch's
  KV writes (the ``W`` pages covering ``[pos, pos+steps)`` per row; sink
  for retired/dummy/overrun rows). Only these pages scatter back —
  admission and retirement never touch the pool tensor at all.

The host-called programs (``row_gather`` / ``row_scatter`` /
``batch_scatter``) move whole staged rows between the admission plane's
contiguous staging caches and pool pages; each compiles once per
geometry and is memoized exactly like ``mesh.init_cache_on_mesh``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from cake_tpu.models.config import LlamaConfig
from cake_tpu.ops.kvcache import KVCache, QuantizedKV
from cake_tpu.parallel.mesh import STAGE, TP, cache_specs

# Thread domain (cakelint CK-THREAD): the compiled-program memo
# (_POOL_PROGRAMS) and every host-called pool program dispatch are
# engine-thread work — same single-writer contract as the page tables
# these programs move rows for.
_THREAD_DOMAIN = "engine"


def pool_specs(kv_quant: str | None = None):
    """PartitionSpec pytree for the pool: layers over stage, kv heads
    over tp, the page axis replicated (pages are addressed by value —
    sharding them would need per-shard id spaces)."""
    spec = P(STAGE, None, TP, None, None)
    if kv_quant == "int8":
        half = QuantizedKV(q=spec, scale=P(STAGE, None, TP, None))
        return KVCache(k=half, v=half)
    return KVCache(k=spec, v=spec)


def _pool_shardings(mesh, kv_quant):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        pool_specs(kv_quant),
                        is_leaf=lambda x: isinstance(x, P))


def page_size_of(pool: KVCache) -> int:
    k = pool.k.q if isinstance(pool.k, QuantizedKV) else pool.k
    return k.shape[3]


def num_pages_of(pool: KVCache) -> int:
    k = pool.k.q if isinstance(pool.k, QuantizedKV) else pool.k
    return k.shape[1]


def writeback_width(steps: int, page_size: int, pages_per_stream: int) -> int:
    """Pages a ``steps``-token dispatch can touch per row: the span of
    ``steps`` consecutive positions crosses at most this many page
    boundaries regardless of alignment."""
    return min(pages_per_stream, 1 + (steps + page_size - 2) // page_size)


# compiled pool programs, memoized by geometry (a fresh jit closure per
# call would retrace per admission — the stall the slot path's splice
# already taught this repo to kill)
_POOL_PROGRAMS: dict = {}


def init_pool_on_mesh(config: LlamaConfig, mesh, num_pages: int,
                      page_size: int, quant: str | None = None) -> KVCache:
    """Allocate a zeroed, mesh-sharded page pool (same no-host-copy
    contract as ``init_cache_on_mesh``: zeros come out of a compiled
    program with explicit output shardings)."""
    if config.segmented:
        raise ValueError(
            "the page pool holds per-head keys and values of every layer; "
            "a model of several layer stacks is served with the slot layout")
    L = config.cache_plan["rows"][0]  # the cache's depth is the plan's
    key = ("init", mesh, L,
           config.num_key_value_heads, config.head_dim, str(config.dtype),
           num_pages, page_size, quant)
    make = _POOL_PROGRAMS.get(key)
    if make is None:
        KH = config.num_key_value_heads
        D = config.head_dim
        dt = config.jax_dtype
        shape = (L, num_pages, KH, page_size, D)

        def zeros():
            if quant == "int8":
                def half():
                    return QuantizedKV(q=jnp.zeros(shape, jnp.int8),
                                       scale=jnp.zeros(shape[:-1],
                                                       jnp.float32))

                return KVCache(k=half(), v=half())
            return KVCache(k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt))

        make = jax.jit(zeros, out_shardings=_pool_shardings(mesh, quant))
        _POOL_PROGRAMS[key] = make
    return make()


# -- trace-level helpers (used INSIDE compiled programs) ---------------------
def _gather_buf(buf: jax.Array, page_map: jax.Array) -> jax.Array:
    """``[L, P, KH, ps(, D)]`` pool half + ``[B, Ppp]`` page map ->
    contiguous ``[L, B, KH, S(, D)]`` view (S = Ppp * ps)."""
    g = jnp.take(buf, page_map, axis=1)  # [L, B, Ppp, KH, ps(, D)]
    g = jnp.moveaxis(g, 2, 3)            # [L, B, KH, Ppp, ps(, D)]
    sh = g.shape
    return g.reshape(sh[:3] + (sh[3] * sh[4],) + sh[5:])


def gather_view(pool: KVCache, page_map: jax.Array) -> KVCache:
    """Materialize the standard contiguous cache view of every stream's
    pages — the unchanged decode body (attention, per-row KV writes) runs
    over this, which is what makes paged streams bit-identical to slot
    streams."""
    return jax.tree.map(lambda b: _gather_buf(b, page_map), pool)


def scatter_back(pool: KVCache, view: KVCache, first_page: jax.Array,
                 scatter_ids: jax.Array) -> KVCache:
    """Write each row's touched pages from the contiguous view back into
    the pool at ``scatter_ids [B, W]`` (sink ids absorb retired/dummy/
    overrun rows — the sink's content is never attendable, so duplicate
    sink writes are harmless)."""
    w = scatter_ids.shape[1]
    ids = scatter_ids.reshape(-1)

    def one(pbuf, vbuf):
        ps = pbuf.shape[3]
        sh = vbuf.shape
        L, B, KH, S = sh[:4]
        paged = vbuf.reshape((L, B, KH, S // ps, ps) + sh[4:])
        rows = jnp.moveaxis(paged, 1, 0)  # [B, L, KH, Ppp, ps(, D)]

        def slice_row(row, fp):  # row [L, KH, Ppp, ps(, D)]
            return jax.lax.dynamic_slice_in_dim(row, fp, w, axis=2)

        u = jax.vmap(slice_row)(rows, first_page)  # [B, L, KH, w, ps(, D)]
        u = jnp.moveaxis(u, 0, 1)                  # [L, B, KH, w, ps(, D)]
        u = jnp.moveaxis(u, 3, 2)                  # [L, B, w, KH, ps(, D)]
        u = u.reshape((L, B * w) + u.shape[3:])    # [L, B*w, KH, ps(, D)]
        return pbuf.at[:, ids].set(u)

    return jax.tree.map(one, pool, view)


# -- host-called staged-row programs -----------------------------------------
def _builders(config: LlamaConfig, mesh, quant: str | None):
    """The three staged-row programs for one (mesh, geometry), compiled
    lazily and memoized: row_gather (pool pages -> a batch-1 staging
    cache: the prefix-hit admission start), row_scatter (a finished
    staging row -> its allocated pages: the admission 'splice', now a
    page write instead of a batch-cache scatter), and batch_scatter
    (a whole prefilled batch cache -> per-row pages: set_prompts
    pageification)."""
    key = ("progs", mesh, config.cache_plan["rows"][0],
           config.num_key_value_heads, config.head_dim, str(config.dtype),
           quant)
    progs = _POOL_PROGRAMS.get(key)
    if progs is not None:
        return progs
    pool_sh = _pool_shardings(mesh, quant)
    stage_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        cache_specs(quant, batch_replicated=True),
        is_leaf=lambda x: isinstance(x, P))

    @partial(jax.jit, out_shardings=stage_sh)
    def row_gather(pool, ids):  # ids [Ppp] int32 (sink-padded)
        def one(pbuf):
            g = jnp.take(pbuf, ids, axis=1)   # [L, Ppp, KH, ps(, D)]
            g = jnp.moveaxis(g, 1, 2)         # [L, KH, Ppp, ps(, D)]
            sh = g.shape
            return g.reshape((sh[0], 1, sh[1], sh[2] * sh[3]) + sh[4:])

        return jax.tree.map(one, pool)

    @partial(jax.jit, out_shardings=pool_sh, donate_argnums=(0,))
    def row_scatter(pool, staging, ids):  # ids [Ppp] (sink = keep)
        def one(pbuf, sbuf):
            ps = pbuf.shape[3]
            sh = sbuf.shape
            L, _, KH, S = sh[:4]
            paged = sbuf.reshape((L, KH, S // ps, ps) + sh[4:])
            u = jnp.moveaxis(paged, 2, 1)     # [L, Ppp, KH, ps(, D)]
            return pbuf.at[:, ids].set(u)

        return jax.tree.map(one, pool, staging)

    @partial(jax.jit, out_shardings=pool_sh, donate_argnums=(0,))
    def batch_scatter(pool, cache, ids):  # ids [B*Ppp] (sink = keep)
        def one(pbuf, cbuf):
            ps = pbuf.shape[3]
            sh = cbuf.shape
            L, B, KH, S = sh[:4]
            paged = cbuf.reshape((L, B, KH, S // ps, ps) + sh[4:])
            u = jnp.moveaxis(paged, 3, 2)     # [L, B, Ppp, KH, ps(, D)]
            u = u.reshape((L, B * (S // ps)) + u.shape[3:])
            return pbuf.at[:, ids].set(u)

        return jax.tree.map(one, pool, cache)

    progs = {"row_gather": row_gather, "row_scatter": row_scatter,
             "batch_scatter": batch_scatter}
    _POOL_PROGRAMS[key] = progs
    return progs


def row_gather_prog(config, mesh, quant):
    return _builders(config, mesh, quant)["row_gather"]


def row_scatter_prog(config, mesh, quant):
    return _builders(config, mesh, quant)["row_scatter"]


def batch_scatter_prog(config, mesh, quant):
    return _builders(config, mesh, quant)["batch_scatter"]
