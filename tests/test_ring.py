"""Ring attention / sequence-parallel decode parity vs the XLA oracle.

The oracle is `cake_tpu.ops.attention._attend_xla` (reference-math full-score
attention). Ring/SP paths must reproduce it up to f32 reduction order on the
virtual 8-device CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cake_tpu.ops import ring
from cake_tpu.ops.attention import _attend_xla


def _qkv(key, b=1, heads=4, kv_heads=2, t=16, s=16, d=8, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, heads, t, d), dtype)
    k = jax.random.normal(kk, (b, kv_heads, s, d), dtype)
    v = jax.random.normal(kv, (b, kv_heads, s, d), dtype)
    return q, k, v


def test_stats_match_oracle_full_block():
    q, k, v = _qkv(jax.random.PRNGKey(0))
    o, m, l = ring.attend_stats(q, k, v, q_off=0, k_off=0)
    got = ring.finalize_stats(o, m, l, q.dtype)
    want = _attend_xla(q, k, v, pos=0)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_stats_merge_over_chunks():
    q, k, v = _qkv(jax.random.PRNGKey(1), t=8, s=32)
    want = _attend_xla(q, k, v, pos=24)  # q positions 24..31, all 32 keys live
    chunk = 8
    o = jnp.zeros(q.shape, jnp.float32)
    m = jnp.full(q.shape[:3], ring.NEG_INF, jnp.float32)
    l = jnp.zeros(q.shape[:3], jnp.float32)
    for c0 in range(0, 32, chunk):
        o_p, m_p, l_p = ring.attend_stats(
            q, k[:, :, c0:c0 + chunk], v[:, :, c0:c0 + chunk],
            q_off=24, k_off=c0,
        )
        o, m, l = ring.merge_stats(o, m, l, o_p, m_p, l_p)
    got = ring.finalize_stats(o, m, l, q.dtype)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_fully_masked_rows_are_finite():
    q, k, v = _qkv(jax.random.PRNGKey(2), t=4, s=8)
    # k_off far beyond the causal frontier: nothing attends.
    o, m, l = ring.attend_stats(q, k, v, q_off=0, k_off=1000)
    out = ring.finalize_stats(o, m, l, q.dtype)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out), 0.0)


@pytest.mark.parametrize("sp", [2, 4, 8])
def test_ring_attention_parity(sp):
    t_total = 32
    t_l = t_total // sp
    q, k, v = _qkv(jax.random.PRNGKey(3), t=t_total, s=t_total)
    want = _attend_xla(q, k, v, pos=0)

    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    spec = P(None, None, "sp", None)

    def f(q, k, v):
        my = jax.lax.axis_index("sp")
        return ring.ring_attention(
            q, k, v, "sp", sp, q_off=my * t_l,
        )

    got = jax.jit(
        shard_map(
            f, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
    )(q, k, v)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


def test_ring_attention_restores_kv_layout():
    """After the full rotation, each shard's KV block is back home: verify by
    returning k from inside the shard_map and comparing to the input."""
    sp, t_l = 4, 8
    q, k, v = _qkv(jax.random.PRNGKey(4), t=sp * t_l, s=sp * t_l)
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    spec = P(None, None, "sp", None)

    def f(q, k, v):
        my = jax.lax.axis_index("sp")
        out = ring.ring_attention(q, k, v, "sp", sp, q_off=my * t_l)
        return out, k

    _, k_after = jax.jit(
        shard_map(f, mesh=mesh, in_specs=(spec,) * 3,
                      out_specs=(spec, spec), check_vma=False)
    )(q, k, v)
    np.testing.assert_array_equal(np.asarray(k_after), np.asarray(k))


@pytest.mark.parametrize("pos", [0, 5, 31])
def test_sp_decode_parity(pos):
    sp = 4
    s_total = 32
    s_l = s_total // sp
    q, k, v = _qkv(jax.random.PRNGKey(5), t=1, s=s_total)
    want = _attend_xla(q, k, v, pos=pos)

    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    kv_spec = P(None, None, "sp", None)

    def f(q, k, v):
        my = jax.lax.axis_index("sp")
        return ring.sp_decode_attend(q, k, v, pos, "sp", my * s_l)

    got = jax.jit(
        shard_map(
            f, mesh=mesh,
            in_specs=(P(None), kv_spec, kv_spec),
            out_specs=P(None),
            check_vma=False,
        )
    )(q, k, v)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("pos", [0, 7, 8, 30])
def test_sp_cache_write_owner_only(pos):
    sp, s_l = 4, 8
    b, kh, d = 1, 2, 4
    k_cache = jnp.zeros((b, kh, sp * s_l, d))
    v_cache = jnp.zeros_like(k_cache)
    k_new = jnp.ones((b, kh, 1, d))
    v_new = jnp.full((b, kh, 1, d), 2.0)

    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    kv_spec = P(None, None, "sp", None)

    def f(kc, vc, kn, vn):
        my = jax.lax.axis_index("sp")
        return ring.sp_cache_write(kc, vc, kn, vn, pos, my * s_l)

    kc, vc = jax.jit(
        shard_map(
            f, mesh=mesh,
            in_specs=(kv_spec, kv_spec, P(None), P(None)),
            out_specs=(kv_spec, kv_spec),
            check_vma=False,
        )
    )(k_cache, v_cache, k_new, v_new)
    kc = np.asarray(kc)
    vc = np.asarray(vc)
    assert (kc[:, :, pos] == 1.0).all()
    assert (vc[:, :, pos] == 2.0).all()
    mask = np.ones(sp * s_l, bool)
    mask[pos] = False
    assert (kc[:, :, mask] == 0.0).all()
    assert (vc[:, :, mask] == 0.0).all()
