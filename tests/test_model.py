import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import tiny
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.ops.rope import rope_tables


def _full_logits(config, params, tokens):
    """Forward the whole sequence at once (fresh cache), logits at last pos."""
    cache = init_cache(config, batch=1, max_seq=config.max_seq_len)
    logits, _ = llama.forward(params, tokens, cache, 0, config)
    return logits


def _mha_tiny():
    """Llama-2-class MHA geometry (kv_heads == heads, GQA group 1) at tiny
    dims — exercises the group=1 attention path."""
    from cake_tpu.models.config import llama2_7b

    return llama2_7b(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        max_seq_len=32, dtype="float32",
    )


@pytest.mark.parametrize("family", ["gqa", "mha"])
def test_prefill_then_decode_matches_full_forward(tiny_config, tiny_params,
                                                  family):
    """KV-cache correctness: incremental decode must equal full-context
    forward, for both GQA (Llama-3) and MHA/group-1 (Llama-2) attention.
    This is the core invariant the reference never tests (SURVEY.md §4)."""
    if family == "gqa":
        cfg, params = tiny_config, tiny_params
    else:
        cfg = _mha_tiny()
        assert cfg.num_attention_heads == cfg.num_key_value_heads
        params = llama.init_params(cfg, jax.random.PRNGKey(2))
    rs = np.random.RandomState(0)
    ids = rs.randint(0, cfg.vocab_size, size=10).tolist()

    # Incremental: prefill 6 tokens, then decode 4 one at a time.
    cache = init_cache(cfg, batch=1, max_seq=cfg.max_seq_len)
    logits, cache = llama.forward(
        params, jnp.asarray([ids[:6]], jnp.int32), cache, 0, cfg
    )
    for i in range(6, 10):
        logits, cache = llama.forward(
            params, jnp.asarray([[ids[i]]], jnp.int32), cache, i, cfg
        )

    full = _full_logits(cfg, params, jnp.asarray([ids + []], jnp.int32))
    # logits after feeding ids[9] at pos 9 == full-forward last-position logits
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full), rtol=2e-4, atol=2e-4
    )


def test_scan_matches_python_loop(tiny_config, tiny_params):
    """lax.scan over stacked layers == explicit per-layer loop."""
    cfg, params = tiny_config, tiny_params
    x = jax.random.normal(
        jax.random.PRNGKey(5), (1, 7, cfg.hidden_size), jnp.float32
    )
    cache = init_cache(cfg, batch=1, max_seq=cfg.max_seq_len)
    cos, sin = rope_tables(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)

    scanned, _ = llama.forward_layers(params["layers"], x, cache, cos, sin, 0, cfg)

    h = x
    for i in range(cfg.num_hidden_layers):
        layer_i = jax.tree.map(lambda a: a[i], params["layers"])
        h, _, _ = llama.block_forward(
            layer_i, h, cache.k[i], cache.v[i], cos, sin, 0, cfg
        )
    np.testing.assert_allclose(np.asarray(scanned), np.asarray(h), rtol=1e-5, atol=1e-5)


def test_causal_mask_future_independence(tiny_config, tiny_params):
    """Changing a future token must not change logits at an earlier position
    of the same full-sequence forward (true causality, not just finiteness)."""
    from cake_tpu.runtime.generator import prefill_fn

    cfg, params = tiny_config, tiny_params
    ids_a = [3, 5, 7, 9, 11]
    ids_b = [3, 5, 7, 9, 200]  # same prefix, different final token

    def logits_at(ids, index):
        cache = init_cache(cfg, batch=1, max_seq=cfg.max_seq_len)
        logits, _ = prefill_fn(
            params,
            jnp.asarray([ids], jnp.int32),
            cache,
            jnp.asarray([index], jnp.int32),
            cfg,
        )
        return np.asarray(logits)

    # At position 3 (before the differing token) logits must be identical.
    np.testing.assert_array_equal(logits_at(ids_a, 3), logits_at(ids_b, 3))
    # At the final position they must differ.
    assert not np.allclose(logits_at(ids_a, 4), logits_at(ids_b, 4))


def test_forward_layers_subset_composes(tiny_config, tiny_params):
    """Running layers [0,2) then [2,4) equals running [0,4) — the invariant
    behind topology layer-sharding (worker executes its range only,
    worker.rs:208-219)."""
    cfg, params = tiny_config, tiny_params
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 5, cfg.hidden_size))
    cos, sin = rope_tables(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    cache = init_cache(cfg, batch=1, max_seq=cfg.max_seq_len)

    full, _ = llama.forward_layers(params["layers"], x, cache, cos, sin, 0, cfg)

    first = jax.tree.map(lambda a: a[:2], params["layers"])
    second = jax.tree.map(lambda a: a[2:], params["layers"])
    from cake_tpu.ops.kvcache import KVCache

    c1 = KVCache(k=cache.k[:2], v=cache.v[:2])
    c2 = KVCache(k=cache.k[2:], v=cache.v[2:])
    h, _ = llama.forward_layers(first, x, c1, cos, sin, 0, cfg)
    h, _ = llama.forward_layers(second, h, c2, cos, sin, 0, cfg)
    np.testing.assert_allclose(np.asarray(full), np.asarray(h), rtol=1e-5, atol=1e-5)


def test_logits_are_f32(tiny_config, tiny_params):
    cfg, params = tiny_config, tiny_params
    cache = init_cache(cfg, batch=1, max_seq=cfg.max_seq_len)
    logits, _ = llama.forward(
        params, jnp.asarray([[1, 2, 3]], jnp.int32), cache, 0, cfg
    )
    assert logits.dtype == jnp.float32
    assert logits.shape == (1, cfg.vocab_size)


def test_llama2_7b_preset_real_geometry():
    from cake_tpu.models.config import llama2_7b

    cfg = llama2_7b()
    assert (cfg.vocab_size, cfg.hidden_size, cfg.intermediate_size) == (
        32000, 4096, 11008)
    assert cfg.num_attention_heads == cfg.num_key_value_heads == 32
    assert cfg.head_dim == 128 and cfg.rope_theta == 10000.0


@functools.lru_cache(maxsize=None)
def _carried_loop_case(moe: bool, quant: str | None):
    """Config, layer weights, a cache whose every row holds something, and
    the rope tables, for the layer-loop parity cases (built once a
    family x cache kind)."""
    from cake_tpu.models.config import tiny_moe
    from cake_tpu.ops.kvcache import KVCache, QuantizedKV

    make = tiny_moe if moe else tiny
    cfg = make(num_hidden_layers=3, max_seq_len=24, dtype="bfloat16")
    layers = llama.init_params(cfg, jax.random.PRNGKey(7))["layers"]
    assert ("router" in layers) == moe
    shape = (3, 3, cfg.num_key_value_heads, cfg.max_seq_len, cfg.head_dim)
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 4))

    def half():
        if quant is None:
            return jax.random.normal(next(keys), shape, jnp.bfloat16)
        return QuantizedKV(
            q=jax.random.randint(next(keys), shape, -127, 128, jnp.int8),
            scale=jax.random.uniform(next(keys), shape[:-1], jnp.float32,
                                     0.005, 0.02))

    cache = KVCache(k=half(), v=half())
    cos, sin = rope_tables(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    return cfg, layers, cache, cos, sin


@pytest.mark.parametrize("family", ["dense", "router"])
@pytest.mark.parametrize("gate", [None, True, False])
@pytest.mark.parametrize("t", [1, 5])
@pytest.mark.parametrize("pos_kind", ["scalar", "per_row"])
@pytest.mark.parametrize("quant", [None, "int8"])
def test_forward_layers_is_the_block_loop_bit_for_bit(quant, pos_kind, t,
                                                      gate, family):
    """``forward_layers`` carries the stacked cache through its scan and
    writes each stream's ``T`` rows in place; a plain Python loop of
    ``block_forward`` over each layer's own slices is what it has to
    equal, bit for bit: the activations (so every id picked from them)
    and every cache leaf. And the rows no stream wrote are bitwise what
    they were (all of them when the gate is off)."""
    cfg, layers, cache, cos, sin = _carried_loop_case(family == "router",
                                                      quant)
    batch = 3
    x = jax.random.normal(jax.random.PRNGKey(3), (batch, t, cfg.hidden_size),
                          jnp.bfloat16)
    pos = (jnp.int32(6) if pos_kind == "scalar"
           else jnp.asarray([0, 9, cfg.max_seq_len - t], jnp.int32))
    write_gate = None if gate is None else jnp.asarray(gate)

    @jax.jit
    def carried(layers, x, cache):
        return llama.forward_layers(layers, x, cache, cos, sin, pos, cfg,
                                    write_gate=write_gate)

    @jax.jit
    def block(layer, h, kc, vc):
        return llama.block_forward(layer, h, kc, vc, cos, sin, pos, cfg,
                                   write_gate=write_gate)

    got_x, got = carried(layers, x, cache)

    h, ks, vs = x, [], []
    for i in range(cfg.num_hidden_layers):
        layer_i, kc, vc = jax.tree.map(lambda a: a[i],
                                       (layers, cache.k, cache.v))
        h, kc, vc = block(layer_i, h, kc, vc)
        ks.append(kc)
        vs.append(vc)
    want = type(cache)(k=jax.tree.map(lambda *a: jnp.stack(a), *ks),
                       v=jax.tree.map(lambda *a: jnp.stack(a), *vs))

    np.testing.assert_array_equal(np.asarray(got_x, np.float32),
                                  np.asarray(h, np.float32))
    # which slots [B, S] of each stream were written
    slot = np.arange(cfg.max_seq_len)
    starts = np.broadcast_to(np.asarray(pos), (batch,))
    written = ((slot[None] >= starts[:, None])
               & (slot[None] < starts[:, None] + t))
    if gate is False:
        written[:] = False
    for g, w, before in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                            jax.tree.leaves(cache)):
        assert g.dtype == w.dtype == before.dtype
        g, w, before = (np.asarray(a, np.float32) for a in (g, w, before))
        np.testing.assert_array_equal(g, w)
        keep = ~written[None, :, None, :]  # leaves are [L, B, KH, S(, D)]
        keep = keep[..., None] if g.ndim == 5 else keep
        keep = np.broadcast_to(keep, g.shape)
        np.testing.assert_array_equal(g[keep], before[keep])
        if gate is not False:
            # the new rows did land: random rows never equal the old ones
            assert (g[~keep] != before[~keep]).any()
