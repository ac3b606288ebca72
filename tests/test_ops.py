import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.ops.norms import rms_norm
from cake_tpu.ops.rope import rope_tables, apply_rope
from cake_tpu.ops.mlp import swiglu
from cake_tpu.ops import sampling
from cake_tpu.ops.sampling import SamplerSettings, sample_token
from cake_tpu.ops.kvcache import init_cache, update_layer
from cake_tpu.models.config import tiny


def test_rms_norm_matches_numpy():
    x = np.random.RandomState(0).randn(2, 5, 16).astype(np.float32)
    w = np.random.RandomState(1).randn(16).astype(np.float32)
    eps = 1e-5
    expected = x / np.sqrt((x**2).mean(-1, keepdims=True) + eps) * w
    got = rms_norm(jnp.asarray(x), jnp.asarray(w), eps)
    np.testing.assert_allclose(np.asarray(got), expected, rtol=1e-5, atol=1e-5)


def test_rope_zero_position_of_first_token_is_identity():
    cos, sin = rope_tables(head_dim=8, max_seq=16, theta=10000.0)
    x = jnp.ones((1, 2, 1, 8))
    out = apply_rope(x, cos, sin, pos=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=1e-6)


def test_rope_slice_matches_offset():
    """apply_rope(x, pos=k) on one token == apply_rope over k+1 tokens, last."""
    cos, sin = rope_tables(head_dim=8, max_seq=16, theta=10000.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 5, 8))
    full = apply_rope(x, cos, sin, pos=0)
    last = apply_rope(x[:, :, 4:5, :], cos, sin, pos=4)
    np.testing.assert_allclose(np.asarray(full[:, :, 4:5]), np.asarray(last), atol=1e-5)


def test_rope_preserves_norm():
    cos, sin = rope_tables(head_dim=16, max_seq=32, theta=10000.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 7, 16))
    out = apply_rope(x, cos, sin, pos=3)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-4,
    )


LLAMA31_SCALING = {
    "rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
    "high_freq_factor": 4.0, "original_max_position_embeddings": 8192,
}


def test_rope_llama3_scaling_matches_hf():
    """Golden: Llama-3.1 frequency scaling matches transformers' llama3 rule."""
    from types import SimpleNamespace

    torch = pytest.importorskip("torch")
    from transformers.modeling_rope_utils import ROPE_INIT_FUNCTIONS

    from cake_tpu.ops.rope import _scale_inv_freq

    head_dim, theta = 128, 500000.0
    hf_cfg = SimpleNamespace(
        rope_theta=theta, head_dim=head_dim, hidden_size=32 * head_dim,
        num_attention_heads=32, partial_rotary_factor=1.0,
        max_position_embeddings=8192, rope_scaling=LLAMA31_SCALING,
    )
    expected, _ = ROPE_INIT_FUNCTIONS["llama3"](hf_cfg, "cpu")
    base = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    got = _scale_inv_freq(jnp.asarray(base, jnp.float32), LLAMA31_SCALING)
    np.testing.assert_allclose(
        np.asarray(got), expected.numpy(), rtol=1e-6, atol=0
    )


def test_rope_linear_scaling():
    from cake_tpu.ops.rope import _scale_inv_freq

    base = jnp.asarray([1.0, 0.1, 0.01], jnp.float32)
    got = _scale_inv_freq(base, {"rope_type": "linear", "factor": 4.0})
    np.testing.assert_allclose(np.asarray(got), np.asarray(base) / 4.0)
    with pytest.raises(ValueError, match="unsupported"):
        _scale_inv_freq(base, {"rope_type": "dynamic", "factor": 2.0})
    # (yarn is wired since PR 28: tests/test_mla_moe.py holds its tables
    # to the closed form)
    # a malformed scaling dict with no type key must fail loudly, not be
    # silently applied as linear interpolation
    with pytest.raises(ValueError, match="no 'rope_type'"):
        _scale_inv_freq(base, {"factor": 4.0})


def test_config_carries_rope_scaling_to_generation():
    """from_hf_dict picks up rope_scaling and a scaled model generates
    (different positional geometry => different stream than unscaled)."""
    from cake_tpu.models.llama import init_params
    from cake_tpu.runtime.generator import LlamaGenerator

    scaling = dict(LLAMA31_SCALING, original_max_position_embeddings=32)
    cfg = tiny(max_seq_len=64)
    scaled = tiny(max_seq_len=64, rope_scaling=scaling)
    assert scaled.from_hf_dict(scaled.to_hf_dict()).rope_scaling == scaling
    params = init_params(cfg, jax.random.PRNGKey(0))
    streams = []
    for c in (cfg, scaled):
        g = LlamaGenerator(c, params,
                           settings=SamplerSettings(temperature=0.0))
        g.set_prompt(list(range(24)))
        streams.append([g.next_token(i).id for i in range(6)])
    assert streams[0] != streams[1]


def test_swiglu_matches_manual():
    rs = np.random.RandomState(0)
    x = rs.randn(2, 3, 8).astype(np.float32)
    wg = rs.randn(8, 16).astype(np.float32)
    wu = rs.randn(8, 16).astype(np.float32)
    wd = rs.randn(16, 8).astype(np.float32)
    g = x @ wg
    expected = ((g / (1 + np.exp(-g))) * (x @ wu)) @ wd
    got = swiglu(jnp.asarray(x), jnp.asarray(wg), jnp.asarray(wu), jnp.asarray(wd))
    np.testing.assert_allclose(np.asarray(got), expected, rtol=1e-4, atol=1e-4)


# -- KV cache ---------------------------------------------------------------

def test_kvcache_update_writes_at_pos():
    cfg = tiny()
    cache = init_cache(cfg, batch=1, max_seq=16)
    k_new = jnp.ones((1, cfg.num_key_value_heads, 2, cfg.head_dim))
    v_new = 2 * k_new
    k, v = update_layer(cache.k[0], cache.v[0], k_new, v_new, pos=3)
    assert float(k[0, 0, 3, 0]) == 1.0
    assert float(k[0, 0, 2, 0]) == 0.0
    assert float(v[0, 0, 4, 0]) == 2.0
    assert float(v[0, 0, 5, 0]) == 0.0


def test_kvcache_as_new_resets():
    cfg = tiny()
    cache = init_cache(cfg, batch=1, max_seq=8)
    k, v = update_layer(cache.k[0], cache.v[0],
                        jnp.ones((1, cfg.num_key_value_heads, 1, cfg.head_dim)),
                        jnp.ones((1, cfg.num_key_value_heads, 1, cfg.head_dim)),
                        pos=0)
    cache2 = cache.as_new()
    assert float(jnp.sum(cache2.k)) == 0.0
    assert cache2.k.shape == cache.k.shape


# -- Sampling ---------------------------------------------------------------

def test_repeat_penalty_matches_candle_semantics():
    logits = jnp.asarray([2.0, -2.0, 1.0, 0.5], jnp.float32)
    history = jnp.asarray([0, 1, -1, -1], jnp.int32)
    out = sampling.apply_repeat_penalty(logits, history, 2.0)
    np.testing.assert_allclose(
        np.asarray(out), [1.0, -4.0, 1.0, 0.5], rtol=1e-6
    )


def test_greedy_is_argmax():
    logits = jnp.asarray([0.1, 5.0, 0.2, 0.3], jnp.float32)
    history = jnp.full((4,), -1, jnp.int32)
    s = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    tok = sample_token(logits, jax.random.PRNGKey(0), history, s)
    assert int(tok) == 1


def test_top_k_restricts_support():
    logits = jnp.asarray([10.0, 9.0, 8.0, -5.0, -6.0], jnp.float32)
    history = jnp.full((4,), -1, jnp.int32)
    s = SamplerSettings(temperature=1.0, top_k=2, repeat_penalty=1.0)
    toks = {
        int(sample_token(logits, jax.random.PRNGKey(i), history, s))
        for i in range(50)
    }
    assert toks <= {0, 1}


def test_top_p_restricts_support():
    logits = jnp.asarray([10.0, 1.0, 0.0, -1.0], jnp.float32)
    history = jnp.full((4,), -1, jnp.int32)
    s = SamplerSettings(temperature=1.0, top_p=0.5, repeat_penalty=1.0)
    toks = {
        int(sample_token(logits, jax.random.PRNGKey(i), history, s))
        for i in range(50)
    }
    assert toks == {0}  # top token alone has > 0.5 of the mass


def test_sampling_is_seed_deterministic():
    logits = jax.random.normal(jax.random.PRNGKey(3), (100,))
    history = jnp.full((8,), -1, jnp.int32)
    s = SamplerSettings(temperature=0.8, top_k=10, repeat_penalty=1.0)
    a = int(sample_token(logits, jax.random.PRNGKey(7), history, s))
    b = int(sample_token(logits, jax.random.PRNGKey(7), history, s))
    assert a == b


def test_history_ring_buffer_wraps():
    hist, slot = sampling.init_history(4)
    for t in range(6):
        hist, slot = sampling.push_history(hist, slot, jnp.int32(t))
    assert sorted(np.asarray(hist).tolist()) == [2, 3, 4, 5]
