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


def _heads_reference(x, wq, wk, wv, num_heads, num_kv_heads, bq, bk, bv,
                     qk_norm):
    """The mathematics `_project_heads` stands for, written out: ``x @ w``
    (an int8 weight widened into the product and scaled after it), the
    bias, the reshape to heads, heads ahead, then each head of q and k
    normed in float32."""
    from cake_tpu.ops.quant import QuantizedLinear

    def project(w, bias, heads):
        if isinstance(w, QuantizedLinear):
            y = jnp.dot(x, w.q.astype(x.dtype),
                        preferred_element_type=jnp.float32)
            y = (y * w.scale).astype(x.dtype)
        else:
            y = x @ w
        if bias is not None:
            y = y + bias
        b, t, _ = x.shape
        return y.reshape(b, t, heads, -1).transpose(0, 2, 1, 3)

    def normed(y, weight, eps):
        yf = y.astype(jnp.float32)
        yf = yf * jax.lax.rsqrt(jnp.mean(yf * yf, -1, keepdims=True) + eps)
        return (yf * weight.astype(jnp.float32)).astype(y.dtype)

    q = project(wq, bq, num_heads)
    k = project(wk, bk, num_kv_heads)
    v = project(wv, bv, num_kv_heads)
    if qk_norm is not None:
        q = normed(q, qk_norm[0], qk_norm[2])
        k = normed(k, qk_norm[1], qk_norm[2])
    return q, k, v


def _first_attention_layer(layers):
    """Layer 0's tensors out of a params tree's stacked layers (one stack,
    or a segmented model's first stack that has a ``wq``)."""
    if "wq" not in layers:
        layers = next(seg for seg in jax.tree.leaves(
            layers, is_leaf=lambda n: isinstance(n, dict) and "wq" in n)
            if isinstance(seg, dict))
    return jax.tree.map(lambda a: a[0], layers)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["dense", "int8", "qwen2_bias", "head_norm",
                                  "tp2", "unrotated"])
def test_project_heads_is_the_plain_projection(case, dtype):
    """`ops.attention._project_heads` keeps the three products apart from
    the per-head operations behind them (an optimization barrier, for the
    chip's compiler: tests/test_chip_compile.py `_projection_moves`); it
    is the same mathematics: bit for bit in float32, within one bfloat16
    ulp in bfloat16, for a plain, an int8, a biased (Qwen2), a head-normed
    (K-EXAONE's fixture) and a column-sharded (``tp=2``: the local heads)
    projection, and for one that nothing rotates or norms (Jamba's: no
    barrier)."""
    from jax.sharding import Mesh, PartitionSpec as P

    from cake_tpu.models.config import tiny_exaone_moe
    from cake_tpu.models.llama import init_params
    from cake_tpu.ops.attention import _project_heads
    from cake_tpu.ops.quant import quantize_linear

    make = tiny_exaone_moe if case == "head_norm" else tiny
    cfg = make(dtype=dtype, attention_bias=case == "qwen2_bias")
    layer = _first_attention_layer(
        init_params(cfg, jax.random.PRNGKey(7))["layers"])
    dt = cfg.jax_dtype
    x = jax.random.normal(jax.random.PRNGKey(8), (3, 5, cfg.hidden_size),
                          jnp.float32).astype(dt)
    ws = [layer[n] for n in ("wq", "wk", "wv")]
    if case == "int8":
        ws = [quantize_linear(w) for w in ws]
    biases = [layer.get(n) for n in ("bq", "bk", "bv")]
    if case == "qwen2_bias":  # init_params zeroes them: make them count
        biases = [jax.random.normal(jax.random.PRNGKey(9 + i), b.shape,
                                    jnp.float32).astype(dt)
                  for i, b in enumerate(biases)]
    assert (biases[0] is not None) == (case == "qwen2_bias")
    norm = ((layer["q_norm"] * 1.5, layer["k_norm"] * 0.75, cfg.rms_norm_eps)
            if case == "head_norm" else None)
    heads = (cfg.num_attention_heads, cfg.num_key_value_heads)
    reference = jax.jit(_heads_reference, static_argnums=(4, 5))
    if case == "tp2":  # each shard's own columns, its heads side by side
        halves = [[w[:, i * w.shape[1] // 2:(i + 1) * w.shape[1] // 2]
                   for w in ws] for i in (0, 1)]
        want = [jnp.concatenate(pair, axis=1) for pair in zip(*(
            reference(x, *half, heads[0] // 2, heads[1] // 2, *biases, norm)
            for half in halves))]
    else:
        want = reference(x, *ws, *heads, *biases, norm)

    if case == "tp2":
        # Megatron-style: q/k/v column-sharded, the head counts local
        mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
        got = jax.jit(jax.shard_map(
            lambda x, wq, wk, wv: _project_heads(
                x, wq, wk, wv, heads[0] // 2, heads[1] // 2),
            mesh=mesh, in_specs=(P(), P(None, "tp"), P(None, "tp"),
                                 P(None, "tp")),
            out_specs=P(None, "tp")))(x, *ws)
    else:
        def project(x, ws, biases, norm):
            return _project_heads(x, *ws, *heads, *biases, qk_norm=norm,
                                  rotated=case != "unrotated")

        got = jax.jit(project)(x, ws, biases, norm)
        # the barrier stands wherever a norm or a rotation follows
        assert ("optimization_barrier" in str(jax.make_jaxpr(project)(
            x, ws, biases, norm))) == (case != "unrotated")

    for name, g, w in zip("qkv", got, want):
        assert g.shape == w.shape and g.dtype == w.dtype == dt, name
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        if dtype == "float32":
            np.testing.assert_array_equal(g, w, err_msg=name)
        else:  # one ulp of bfloat16: 2^-7 of the value's power of two
            ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(w), 1e-30)))
                          - 7)
            assert (np.abs(g - w) <= ulp).all(), name
