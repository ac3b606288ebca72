"""Packed int4 weight quantization: pack/unpack round-trip, kernel parity,
model quality, loader equivalence, and sharded execution.

The int4 tier is a capability the TPU build adds beyond the reference's
f16/bf16 dtype plane (`cake/mod.rs:56-62`): decode is HBM-bandwidth-bound,
so halving the int8 bytes again roughly doubles the single-stream roofline
(the roofline, not the rate: not measured on the chip tool). The
adjacent-pair packing convention (ops/quant.py) is load-bearing for tensor
parallelism — tested explicitly here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import tiny
from cake_tpu.ops import quant
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.ops.pallas.quant import quant4_matmul_pallas
from cake_tpu.ops.quant import (
    Quantized4Linear,
    dense,
    dequantize_linear4,
    pack_int4,
    quantize_linear4,
    quantize_linear4_np,
    quantize_params,
    unpack_int4,
)


def test_pack_unpack_round_trip():
    rng = np.random.default_rng(0)
    q = rng.integers(-7, 8, size=(16, 8), dtype=np.int8)
    packed = pack_int4(jnp.asarray(q))
    assert packed.shape == (8, 8) and packed.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed)), q)


def test_pack_adjacent_pair_layout():
    """Byte i holds rows 2i (low nibble) and 2i+1 (high) — the layout that
    makes contiguous packed-row ranges contiguous original-row ranges."""
    q = jnp.asarray([[1], [-2], [3], [-4]], jnp.int8)  # K=4, N=1
    p = np.asarray(pack_int4(q))[:, 0]
    # byte 0 = rows 0,1; byte 1 = rows 2,3
    assert p[0] == np.int8((1 & 0xF) | (np.int8(-2) << 4))
    assert p[1] == np.int8((3 & 0xF) | (np.int8(-4) << 4))
    # shard the packed rows: rows [1, 2) must decode to original rows [2, 4)
    shard = pack_int4(q)[1:2]
    np.testing.assert_array_equal(
        np.asarray(unpack_int4(shard)), np.asarray(q)[2:4]
    )


def test_pack_odd_k_rejected():
    with pytest.raises(ValueError, match="even"):
        pack_int4(jnp.zeros((3, 4), jnp.int8))


def test_quantize4_round_trip():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
    ql = quantize_linear4(w)
    assert ql.qp.shape == (32, 32) and ql.qp.dtype == jnp.int8
    assert ql.scale.shape == (32,)
    back = dequantize_linear4(ql, jnp.float32)
    # max error bounded by half a quantization step per channel
    step = np.asarray(ql.scale)
    err = np.abs(np.asarray(back) - np.asarray(w))
    assert (err <= 0.5 * step[None, :] + 1e-7).all()


def test_quantize4_np_matches_jax():
    w = np.random.default_rng(1).standard_normal((48, 16)).astype(np.float32)
    ql = quantize_linear4(jnp.asarray(w))
    qp, scale = quantize_linear4_np(w)
    np.testing.assert_array_equal(qp, np.asarray(ql.qp))
    np.testing.assert_allclose(scale, np.asarray(ql.scale), rtol=1e-6)


def test_quantize4_stacked_scale_axes():
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 8), jnp.float32)
    ql = quantize_linear4(w)
    assert ql.qp.shape == (3, 8, 8)
    assert ql.scale.shape == (3, 8)


def test_quant4_matmul_xla_matches_dequant():
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (4, 64), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (64, 32), jnp.float32)
    ql = quantize_linear4(w)
    ref = x @ dequantize_linear4(ql, jnp.float32)
    out = quant.quant4_matmul_xla(x, ql.qp, ql.scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_quant4_matmul_pallas_matches_xla():
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (8, 64), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (64, 32), jnp.float32)
    ql = quantize_linear4(w)
    ref = quant.quant4_matmul_xla(x, ql.qp, ql.scale)
    out = quant4_matmul_pallas(x, ql.qp, ql.scale, block_m=4, block_n=8,
                               block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_dense_dispatch_int4():
    x = jnp.ones((2, 8), jnp.float32)
    w = jnp.ones((8, 4), jnp.float32)
    ql = quantize_linear4(w)
    assert quant.out_features(ql) == 4
    np.testing.assert_allclose(np.asarray(dense(x, ql)), 8.0, rtol=1e-2)


def test_pinned_impl_applies_to_int4():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 256), jnp.bfloat16)
    w = quantize_linear4(
        jax.random.normal(jax.random.PRNGKey(2), (256, 256), jnp.float32))
    y_xla = quant.quant4_matmul(x, w.qp, w.scale, impl="xla")
    with quant.pinned_impl("xla"):
        np.testing.assert_array_equal(
            quant.quant4_matmul(x, w.qp, w.scale), y_xla)
    assert quant.pinned() is None


@pytest.fixture(scope="module")
def cfg():
    return tiny(max_seq_len=32)


@pytest.fixture(scope="module")
def params(cfg):
    return llama.init_params(cfg, jax.random.PRNGKey(0))


def test_quantize_params_bits4(cfg, params):
    qparams = quantize_params(params, bits=4)
    assert isinstance(qparams["layers"]["wq"], Quantized4Linear)
    assert isinstance(qparams["lm_head"], Quantized4Linear)
    assert not isinstance(qparams["layers"]["attn_norm"], Quantized4Linear)
    with pytest.raises(ValueError, match="bits"):
        quantize_params(params, bits=5)


def _logits_cosine(cfg, params, qparams) -> float:
    ids = [3, 1, 4, 1, 5, 9, 2, 6]
    tokens = jnp.asarray([ids], jnp.int32)
    logits_f, _ = llama.forward(
        params, tokens, init_cache(cfg, 1, cfg.max_seq_len), 0, cfg
    )
    logits_q, _ = llama.forward(
        qparams, tokens, init_cache(cfg, 1, cfg.max_seq_len), 0, cfg
    )
    a = np.asarray(logits_f[0], np.float64)
    b = np.asarray(logits_q[0], np.float64)
    return float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))


def test_int4_model_logits_close(cfg, params):
    """Per-channel int4 is the bandwidth tier: coarse but usable."""
    cos = _logits_cosine(cfg, params, quantize_params(params, bits=4))
    assert cos > 0.9, f"cosine similarity {cos}"


def test_int4_grouped_recovers_accuracy():
    """Group-wise scales are the accuracy tier. On iid-gaussian weights
    grouping buys nothing (absmax is uniform across rows — measured, the
    model-level cosine is ~identical), so this exercises the case grouping
    exists for: heterogeneous row magnitudes (real checkpoints' outlier
    structure). Per-channel absmax is then dominated by the loud rows and
    quiet rows quantize to ~0; per-group scales isolate them."""
    rng = np.random.default_rng(3)
    w = rng.standard_normal((64, 32)).astype(np.float32)
    w[:16] *= 50.0  # one loud 16-row band, three quiet ones

    def rel_err(ql):
        back = np.asarray(dequantize_linear4(ql, jnp.float32))
        return np.abs(back - w)[16:].max() / np.abs(w[16:]).max()

    err_pc = rel_err(quantize_linear4(jnp.asarray(w)))
    err_g = rel_err(quantize_linear4(jnp.asarray(w), group_size=16))
    assert err_pc > 0.5  # quiet rows destroyed by the loud band's scale
    assert err_g < 0.1, f"grouped rel err {err_g}"
    # model-level: grouped int4 stays in the per-channel fidelity envelope
    # on iid weights (sanity that grouping never hurts)
    assert err_g < err_pc


def test_quantize4_grouped_round_trip():
    w = jax.random.normal(jax.random.PRNGKey(5), (64, 32), jnp.float32)
    ql = quantize_linear4(w, group_size=16)
    assert ql.qp.shape == (32, 32)
    assert ql.scale.shape == (4, 32)
    assert ql.group_size == 16
    back = dequantize_linear4(ql, jnp.float32)
    step = np.asarray(ql.scale)  # [4, 32] — per (group, channel) step
    err = np.abs(np.asarray(back) - np.asarray(w)).reshape(4, 16, 32)
    assert (err <= 0.5 * step[:, None, :] + 1e-7).all()
    # numpy variant agrees
    qp_np, s_np = quantize_linear4_np(np.asarray(w), group_size=16)
    np.testing.assert_array_equal(qp_np, np.asarray(ql.qp))
    np.testing.assert_allclose(s_np, np.asarray(ql.scale), rtol=1e-6)


def test_quant4_grouped_matmul_paths_agree():
    key = jax.random.PRNGKey(6)
    x = jax.random.normal(key, (8, 64), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (64, 32), jnp.float32)
    ql = quantize_linear4(w, group_size=16)
    ref = x @ dequantize_linear4(ql, jnp.float32)
    y_xla = quant.quant4_matmul_xla(x, ql.qp, ql.scale)
    np.testing.assert_allclose(np.asarray(y_xla), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    y_pl = quant4_matmul_pallas(x, ql.qp, ql.scale, block_m=4, block_n=8,
                                block_k=4, interpret=True)
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_xla),
                               rtol=1e-5, atol=1e-5)
    # dense dispatches on the scale rank alone
    y_d = dense(x, ql)
    np.testing.assert_allclose(np.asarray(y_d), np.asarray(y_xla),
                               rtol=1e-5, atol=1e-5)


def test_quant4_grouped_matmul_bf16_activations():
    """The grouped fallback runs with bf16 activations on CPU (the CPU
    batched-dot thunk rejects bf16 x bf16 -> f32, so the fallback computes
    in f32) — the dtype every real CLI flow uses."""
    key = jax.random.PRNGKey(12)
    x = jax.random.normal(key, (2, 64), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (64, 32), jnp.float32)
    ql = quantize_linear4(w, group_size=16)
    y = jax.jit(quant.quant4_matmul_xla)(x, ql.qp, ql.scale)
    assert y.dtype == jnp.bfloat16
    ref = (x.astype(jnp.float32)
           @ dequantize_linear4(ql, jnp.float32)).astype(jnp.bfloat16)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(ref, np.float32),
        rtol=0.05, atol=0.05)


def test_quantize4_group_size_validation():
    w = jnp.zeros((64, 8), jnp.float32)
    with pytest.raises(ValueError, match="group_size"):
        quantize_linear4(w, group_size=24)  # does not divide 64
    with pytest.raises(ValueError, match="group_size"):
        quantize_linear4(w, group_size=3)  # odd
    with pytest.raises(ValueError, match="group_size"):
        quantize_params({"lm_head": w}, bits=8, group_size=16)


def test_int4_generation_runs(cfg, params):
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.generator import LlamaGenerator

    g = LlamaGenerator(cfg, quantize_params(params, bits=4),
                       settings=SamplerSettings(temperature=0.0))
    g.set_prompt([3, 1, 4])
    ids = [g.next_token(i).id for i in range(6)]
    assert len(ids) == 6
    assert all(0 <= t < cfg.vocab_size for t in ids)


def test_int4_block_decode_matches_single(cfg, params):
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.generator import LlamaGenerator

    qp = quantize_params(params, bits=4)
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    a = LlamaGenerator(cfg, qp, settings=settings)
    a.set_prompt([5, 9, 2])
    single = [a.next_token(i).id for i in range(9)]
    b = LlamaGenerator(cfg, qp, settings=settings, block_size=4)
    b.set_prompt([5, 9, 2])
    assert [b.next_token(i).id for i in range(9)] == single


def test_init_params_int4_structure(cfg):
    p = llama.init_params_int4(cfg, jax.random.PRNGKey(7))
    assert isinstance(p["layers"]["wq"], Quantized4Linear)
    assert isinstance(p["lm_head"], Quantized4Linear)
    h = cfg.hidden_size
    assert p["layers"]["wq"].qp.shape[1] == h // 2
    # generation works end-to-end from the packed init
    logits, _ = llama.forward(
        p, jnp.asarray([[1, 2, 3]], jnp.int32),
        init_cache(cfg, 1, cfg.max_seq_len), 0, cfg,
    )
    assert np.isfinite(np.asarray(logits, np.float32)).all()


def test_int4_sharded_pipeline_matches_local(cfg, params):
    """int4 params shard over (stage, tp) — the adjacent-pair packing makes
    the row-parallel (in-axis) tp shards decode the right values — and the
    one-program mesh decode agrees with the unsharded int4 model."""
    from cake_tpu.ops import sampling
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.parallel.mesh import MeshPlan, shard_cache, shard_params
    from cake_tpu.parallel.pipeline import build_sharded_decode

    qparams = quantize_params(params, bits=4)
    plan = MeshPlan.build(cfg, num_stages=2, tp=2)
    sp = shard_params(qparams, plan.mesh)
    settings = SamplerSettings(temperature=0.0)
    decode = build_sharded_decode(cfg, settings, plan, params_like=qparams)
    cache = shard_cache(init_cache(cfg, 1, cfg.max_seq_len), plan.mesh)
    history, hist_slot = sampling.init_history(settings.repeat_last_n)
    tok, cache, history, hist_slot = decode(
        sp, jnp.asarray([5], jnp.int32), cache, jnp.int32(0),
        jax.random.PRNGKey(0), history[None, :], hist_slot,
    )
    logits_ref, _ = llama.forward(
        qparams, jnp.asarray([[5]], jnp.int32),
        init_cache(cfg, 1, cfg.max_seq_len), 0, cfg,
    )
    assert int(tok[0]) == int(jnp.argmax(logits_ref[0]))


def test_int4_grouped_sharded_pipeline_matches_local(cfg, params):
    """Grouped-scale int4 params shard over (stage, tp): the group axis
    shards with the in axis (mesh.param_specs), and the mesh decode agrees
    with the unsharded grouped model."""
    from cake_tpu.ops import sampling
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.parallel.mesh import MeshPlan, shard_cache, shard_params
    from cake_tpu.parallel.pipeline import build_sharded_decode

    qparams = quantize_params(params, bits=4, group_size=16)
    plan = MeshPlan.build(cfg, num_stages=2, tp=2)
    sp = shard_params(qparams, plan.mesh)
    settings = SamplerSettings(temperature=0.0)
    decode = build_sharded_decode(cfg, settings, plan, params_like=qparams)
    cache = shard_cache(init_cache(cfg, 1, cfg.max_seq_len), plan.mesh)
    history, hist_slot = sampling.init_history(settings.repeat_last_n)
    tok, cache, history, hist_slot = decode(
        sp, jnp.asarray([5], jnp.int32), cache, jnp.int32(0),
        jax.random.PRNGKey(0), history[None, :], hist_slot,
    )
    logits_ref, _ = llama.forward(
        qparams, jnp.asarray([[5]], jnp.int32),
        init_cache(cfg, 1, cfg.max_seq_len), 0, cfg,
    )
    assert int(tok[0]) == int(jnp.argmax(logits_ref[0]))


def test_head_chunk_grouped_scale_slices_vocab_axis():
    """_head_chunk on a grouped-int4 lm_head slices the vocab (last) scale
    axis, not the group axis — each stage's chunk decodes exactly like the
    matching column slice of the full head."""
    from cake_tpu.parallel.pipeline import _head_chunk

    w = jax.random.normal(jax.random.PRNGKey(8), (32, 64), jnp.float32)
    ql = quantize_linear4(w, group_size=8)  # scale [4, 64]
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 32), jnp.float32)
    full = np.asarray(dense(x, ql))
    S = 4
    for stage in range(S):
        chunk = _head_chunk(ql, stage, S)
        assert chunk.scale.shape == (4, 64 // S)
        np.testing.assert_allclose(
            np.asarray(dense(x, chunk)),
            full[:, stage * (64 // S):(stage + 1) * (64 // S)],
            rtol=1e-5, atol=1e-5,
        )


def test_int4_tp_shard_values_match_slice(cfg, params):
    """The sharded qp's per-device row-parallel blocks are exactly the pack
    of that shard's original-row slice (the property the packing layout
    exists for)."""
    from cake_tpu.parallel.mesh import MeshPlan, shard_params

    qparams = quantize_params(params, bits=4)
    plan = MeshPlan.build(cfg, num_stages=1, tp=2)
    sharded = shard_params(qparams, plan.mesh)
    full = np.asarray(qparams["layers"]["w_down"].qp)
    k2 = full.shape[1]
    for shard in sharded["layers"]["w_down"].qp.addressable_shards:
        a = shard.index[1].indices(k2)[0]
        b = shard.index[1].indices(k2)[1]
        np.testing.assert_array_equal(np.asarray(shard.data), full[:, a:b])


def test_int4_quantize_during_load_matches_posthoc(cfg, params, tmp_path):
    from cake_tpu.utils.weights import load_llama_params, save_llama_params

    save_llama_params(params, tmp_path)
    loaded_q = load_llama_params(
        tmp_path, cfg.num_hidden_layers, dtype="float32", quantize="int4"
    )
    posthoc = quantize_params(
        load_llama_params(tmp_path, cfg.num_hidden_layers, dtype="float32"),
        bits=4,
    )
    for name in ("wq", "w_down"):
        np.testing.assert_array_equal(
            np.asarray(loaded_q["layers"][name].qp),
            np.asarray(posthoc["layers"][name].qp),
        )
        np.testing.assert_allclose(
            np.asarray(loaded_q["layers"][name].scale),
            np.asarray(posthoc["layers"][name].scale), rtol=1e-6,
        )
    np.testing.assert_array_equal(
        np.asarray(loaded_q["lm_head"].qp), np.asarray(posthoc["lm_head"].qp)
    )


def test_int4_mesh_load_matches_host_load(cfg, params, tmp_path):
    """Direct-to-mesh int4 load (packed-row sharding) is bitwise equal to
    host-load + shard (the loader's contract)."""
    from cake_tpu.parallel.mesh import MeshPlan, shard_params
    from cake_tpu.utils.sharded_load import load_llama_params_on_mesh
    from cake_tpu.utils.weights import load_llama_params, save_llama_params

    save_llama_params(params, tmp_path)
    plan = MeshPlan.build(cfg, num_stages=2, tp=2)
    mesh_q = load_llama_params_on_mesh(
        tmp_path, cfg, plan.mesh, quantize="int4",
    )
    host_q = shard_params(
        load_llama_params(tmp_path, cfg.num_hidden_layers,
                          dtype=cfg.dtype, quantize="int4"),
        plan.mesh,
    )
    for name in ("wq", "wo", "w_down"):
        np.testing.assert_array_equal(
            np.asarray(mesh_q["layers"][name].qp),
            np.asarray(host_q["layers"][name].qp),
        )
        np.testing.assert_allclose(
            np.asarray(mesh_q["layers"][name].scale),
            np.asarray(host_q["layers"][name].scale), rtol=1e-6,
        )
    np.testing.assert_array_equal(
        np.asarray(mesh_q["lm_head"].qp), np.asarray(host_q["lm_head"].qp)
    )


def test_int4_prequantized_checkpoint_round_trip(cfg, params, tmp_path):
    """quantize_model --bits 4 writes .q4 tensors; loading the pre-quantized
    checkpoint equals quantize-on-load from the bf16 original."""
    from cake_tpu.tools.quantize_model import quantize_checkpoint
    from cake_tpu.utils.weights import load_llama_params, save_llama_params

    src = tmp_path / "src"
    dst = tmp_path / "q4"
    save_llama_params(params, src)
    quantize_checkpoint(src, dst, bits=4)
    pre = load_llama_params(dst, cfg.num_hidden_layers, dtype=cfg.dtype,
                            quantize="int4")
    onload = load_llama_params(src, cfg.num_hidden_layers, dtype=cfg.dtype,
                               quantize="int4")
    for name in ("wq", "w_down"):
        np.testing.assert_array_equal(
            np.asarray(pre["layers"][name].qp),
            np.asarray(onload["layers"][name].qp),
        )
    np.testing.assert_array_equal(
        np.asarray(pre["lm_head"].qp), np.asarray(onload["lm_head"].qp)
    )
    # tier mismatch is rejected, not silently mis-decoded
    with pytest.raises(ValueError, match="int4"):
        load_llama_params(dst, cfg.num_hidden_layers, quantize="int8")


def test_parse_quant_spec():
    from cake_tpu.ops.quant import parse_quant_spec

    assert parse_quant_spec(None) == (None, None)
    assert parse_quant_spec("int8") == ("int8", None)
    assert parse_quant_spec("int4") == ("int4", None)
    assert parse_quant_spec("int4:g128") == ("int4", 128)
    with pytest.raises(ValueError, match="quantize spec"):
        parse_quant_spec("int2")
    with pytest.raises(ValueError, match="quantize spec"):
        parse_quant_spec("int8:g64")
    with pytest.raises(ValueError, match="quantize spec"):
        parse_quant_spec("int4:g0")  # \\d+ matches 0; must not pass


def test_int4_grouped_on_load_matches_posthoc(cfg, params, tmp_path):
    """quantize='int4:gN' on the host loader equals quantize_params with
    the same group size — the grouped tier is reachable from a plain
    checkpoint with one flag."""
    from cake_tpu.utils.weights import load_llama_params, save_llama_params

    save_llama_params(params, tmp_path)
    loaded = load_llama_params(
        tmp_path, cfg.num_hidden_layers, dtype="float32",
        quantize="int4:g16",
    )
    posthoc = quantize_params(
        load_llama_params(tmp_path, cfg.num_hidden_layers, dtype="float32"),
        bits=4, group_size=16,
    )
    assert loaded["layers"]["wq"].group_size == 16
    for name in ("wq", "w_down"):
        np.testing.assert_array_equal(
            np.asarray(loaded["layers"][name].qp),
            np.asarray(posthoc["layers"][name].qp),
        )
        np.testing.assert_allclose(
            np.asarray(loaded["layers"][name].scale),
            np.asarray(posthoc["layers"][name].scale), rtol=1e-6,
        )


def test_int4_grouped_prequantized_checkpoint(cfg, params, tmp_path):
    """quantize_model --bits 4 --group-size writes grouped .q4 scales; both
    loaders read them back (grouping detected from the stored scale shape),
    and the direct-to-mesh load equals host-load + shard."""
    from cake_tpu.parallel.mesh import MeshPlan, shard_params
    from cake_tpu.tools.quantize_model import quantize_checkpoint
    from cake_tpu.utils.sharded_load import load_llama_params_on_mesh
    from cake_tpu.utils.weights import load_llama_params, save_llama_params

    src = tmp_path / "src"
    dst = tmp_path / "q4g"
    save_llama_params(params, src)
    quantize_checkpoint(src, dst, bits=4, group_size=16)
    pre = load_llama_params(dst, cfg.num_hidden_layers, dtype=cfg.dtype,
                            quantize="int4")
    assert pre["layers"]["wq"].group_size == 16
    onload = load_llama_params(src, cfg.num_hidden_layers, dtype=cfg.dtype,
                               quantize="int4:g16")
    np.testing.assert_array_equal(
        np.asarray(pre["layers"]["w_down"].qp),
        np.asarray(onload["layers"]["w_down"].qp),
    )
    np.testing.assert_allclose(
        np.asarray(pre["layers"]["w_down"].scale),
        np.asarray(onload["layers"]["w_down"].scale), rtol=1e-6,
    )
    plan = MeshPlan.build(cfg, num_stages=2, tp=2)
    mesh_q = load_llama_params_on_mesh(dst, cfg, plan.mesh, quantize="int4")
    host_q = shard_params(pre, plan.mesh)
    for name in ("wq", "wo", "w_down"):
        np.testing.assert_array_equal(
            np.asarray(mesh_q["layers"][name].qp),
            np.asarray(host_q["layers"][name].qp),
        )
        np.testing.assert_allclose(
            np.asarray(mesh_q["layers"][name].scale),
            np.asarray(host_q["layers"][name].scale), rtol=1e-6,
        )
    np.testing.assert_array_equal(
        np.asarray(mesh_q["lm_head"].qp), np.asarray(host_q["lm_head"].qp))
    np.testing.assert_allclose(
        np.asarray(mesh_q["lm_head"].scale),
        np.asarray(host_q["lm_head"].scale), rtol=1e-6)


def test_int4_grouped_tied_head_loaders_agree(cfg, params, tmp_path):
    """A tied lm_head on a grouped pre-quantized checkpoint is quantized
    at the checkpoint's DETECTED group size by both loaders — host and
    direct-to-mesh heads are bit-equal (the loaders' equality contract)."""
    from cake_tpu.parallel.mesh import MeshPlan
    from cake_tpu.tools.quantize_model import quantize_checkpoint
    from cake_tpu.utils.sharded_load import load_llama_params_on_mesh
    from cake_tpu.utils.weights import load_llama_params, save_llama_params

    src = tmp_path / "src"
    dst = tmp_path / "q4g"
    save_llama_params(params, src)
    quantize_checkpoint(src, dst, bits=4, group_size=16)
    host = load_llama_params(dst, cfg.num_hidden_layers, dtype=cfg.dtype,
                             quantize="int4", tie_word_embeddings=True)
    # tied head fell back to on-the-fly quantize at the stored G, not
    # per-channel: grouped scale rank
    assert host["lm_head"].scale.ndim == 2
    assert host["lm_head"].group_size == 16
    plan = MeshPlan.build(cfg, num_stages=1, tp=2)
    mesh = load_llama_params_on_mesh(dst, cfg, plan.mesh, quantize="int4",
                                     tie_word_embeddings=True)
    np.testing.assert_array_equal(
        np.asarray(mesh["lm_head"].qp), np.asarray(host["lm_head"].qp))
    np.testing.assert_allclose(
        np.asarray(mesh["lm_head"].scale),
        np.asarray(host["lm_head"].scale), rtol=1e-6)


def test_int4_grouped_spec_mismatch_rejected_on_host(cfg, params, tmp_path):
    """Asking the host loader for g8 on a g16 checkpoint errors instead of
    silently dropping the request (parity with the sharded loader)."""
    from cake_tpu.tools.quantize_model import quantize_checkpoint
    from cake_tpu.utils.weights import load_llama_params, save_llama_params

    src = tmp_path / "src"
    dst = tmp_path / "q4g"
    save_llama_params(params, src)
    quantize_checkpoint(src, dst, bits=4, group_size=16)
    with pytest.raises(ValueError, match="group_size=16"):
        load_llama_params(dst, cfg.num_hidden_layers, quantize="int4:g8")


def test_int4_grouped_mesh_onload_rejected(cfg, params, tmp_path):
    """On-the-fly grouped quantize on the direct-to-mesh path points at the
    offline tool instead of silently degrading the tier."""
    from cake_tpu.parallel.mesh import MeshPlan
    from cake_tpu.utils.sharded_load import load_llama_params_on_mesh
    from cake_tpu.utils.weights import save_llama_params

    save_llama_params(params, tmp_path)
    plan = MeshPlan.build(cfg, num_stages=1, tp=1)
    with pytest.raises(ValueError, match="quantize_model"):
        load_llama_params_on_mesh(tmp_path, cfg, plan.mesh,
                                  quantize="int4:g16")


def test_int4_mesh_spec_vs_perchannel_checkpoint_rejected(cfg, params,
                                                         tmp_path):
    """Mesh loader: asking g16 of a PER-CHANNEL .q4 checkpoint errors
    (parity with the host loader) instead of silently loading coarse."""
    from cake_tpu.parallel.mesh import MeshPlan
    from cake_tpu.tools.quantize_model import quantize_checkpoint
    from cake_tpu.utils.sharded_load import load_llama_params_on_mesh
    from cake_tpu.utils.weights import save_llama_params

    src = tmp_path / "src"
    dst = tmp_path / "q4pc"
    save_llama_params(params, src)
    quantize_checkpoint(src, dst, bits=4)  # per-channel
    plan = MeshPlan.build(cfg, num_stages=1, tp=1)
    with pytest.raises(ValueError, match="per-channel"):
        load_llama_params_on_mesh(dst, cfg, plan.mesh, quantize="int4:g16")


def test_hbm_budget_prices_grouped_scales():
    """Grouped int4 scale bytes scale with in_dim/group — a near-limit
    config must see them (the planning arithmetic of utils.memory)."""
    from cake_tpu.models.config import LlamaConfig
    from cake_tpu.utils.memory import hbm_budget

    c = LlamaConfig(
        vocab_size=1024, hidden_size=256, intermediate_size=512,
        num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=4,
        max_seq_len=128,
    )
    pc = hbm_budget(c, quant="int4")["total"]
    g = hbm_budget(c, quant="int4:g64")["total"]
    g_small = hbm_budget(c, quant="int4:g16")["total"]
    assert g > pc  # in_dim/64 scales per channel > 1 per channel
    assert g_small > g  # smaller groups, more scales


def test_int4_gate_guards_sublane_k_blocks(monkeypatch):
    """On a (simulated) compiled-TPU dispatch, grouped int4 whose K block
    would be sub-lane (g2 < 128) must fall back to XLA — the pin contract
    says pallas must never be chosen where it cannot lower."""
    from cake_tpu.ops import pallas as pk
    from cake_tpu.ops.pallas import quant as pq

    monkeypatch.setattr(pk, "kernels_enabled", lambda: True)
    monkeypatch.setattr(pk, "interpret_default", lambda: False)

    def boom(*a, **k):
        raise AssertionError("pallas kernel chosen for sub-lane K block")

    monkeypatch.setattr(pq, "quant4_matmul_pallas", boom)
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 256), jnp.float32)
    ql = quantize_linear4(w, group_size=128)  # g2 = 64: not tileable
    with quant.pinned_impl("pallas"):
        y = quant.quant4_matmul(x, ql.qp, ql.scale)
    np.testing.assert_allclose(
        np.asarray(y),
        np.asarray(quant.quant4_matmul_xla(x, ql.qp, ql.scale)),
        rtol=1e-6)
    # per-channel at the same shapes IS tileable and would pick pallas
    ql_pc = quantize_linear4(w)
    with pytest.raises(AssertionError, match="sub-lane"):
        with quant.pinned_impl("pallas"):
            quant.quant4_matmul(x, ql_pc.qp, ql_pc.scale)


def test_int4_weights_compose_with_int8_kv(cfg):
    """int4 weights x int8 KV cache: both quantization planes in one
    serving instance, token streams identical to the bf16-KV int4 oracle
    within the int8-KV rounding envelope (here: greedy, same argmax)."""
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.batch_generator import BatchGenerator

    c = tiny(max_seq_len=64, eos_token_id=-1)
    qparams = quantize_params(
        llama.init_params(c, jax.random.PRNGKey(4)), bits=4)

    def run(kv_quant):
        gen = BatchGenerator(c, qparams, kv_quant=kv_quant,
                             settings=SamplerSettings(temperature=0.0))
        gen.set_prompts([[5, 9, 2], [3, 3, 1]])
        out = []
        for _ in range(5):
            out.append([int(t.id) for t in gen.step()])
        return out

    bf16_kv = run(None)
    int8_kv = run("int8")
    assert len(int8_kv) == 5 and all(len(r) == 2 for r in int8_kv)
    # greedy streams agree on this tiny config (int8-KV rounding is below
    # the argmax margin here; regression-guards the composition wiring)
    assert int8_kv == bf16_kv


def test_int4_serving_batch_generator(cfg):
    """BatchGenerator serves int4 params (pin machinery included)."""
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.batch_generator import BatchGenerator

    c = tiny(max_seq_len=64, eos_token_id=-1)
    qparams = quantize_params(
        llama.init_params(c, jax.random.PRNGKey(4)), bits=4)
    gen = BatchGenerator(c, qparams,
                         settings=SamplerSettings(temperature=0.0))
    gen.set_prompts([[5, 9, 2], [3, 3, 1]])
    assert gen._params_quantized  # int4 counts as quantized for pinning
    out = []
    for _ in range(4):
        row = gen.step()
        out.append([None if t is None else int(t.id) for t in row])
    assert all(len(r) == 2 for r in out)


def test_int16_unpack_variant_matches_int32():
    """The kernel's `unpack` width knob (tools/int4_sweep.py's variant
    axis) must not change the math — int16 sign-extension of a nibble is
    exact."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cake_tpu.ops.pallas.quant import quant4_matmul_pallas
    from cake_tpu.ops.quant import quantize_linear4

    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (4, 256), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (256, 512),
                          jnp.float32)
    for gs in (None, 64):
        q4 = quantize_linear4(w, group_size=gs)
        a = quant4_matmul_pallas(x, q4.qp, q4.scale, unpack="int32",
                                 interpret=True)
        b = quant4_matmul_pallas(x, q4.qp, q4.scale, unpack="int16",
                                 interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
