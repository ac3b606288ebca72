"""Interleaved-microbatch serving decode (parallel/pipeline.py
build_interleaved_decode): the decode twin of pipelined prefill.

Contrast anchor (SURVEY.md §2): the reference's pipeline — and the plain
staged decode here — keeps upstream workers idle (inactive stages compute
into a discarded select) for every token. The interleaved schedule
round-robins the dp batch's S microbatches over the S stages so every
stage does useful layer work every cycle. The contract proven here:

1. emitted streams, cache contents, and sampler state are BIT-IDENTICAL
   to the serialized per-row decode (same keys, positions, history);
2. the compiled interleaved block does ~1/S of the serialized block's
   layer work a cycle (FLOPs and bytes by XLA's cost analysis of the two
   programs: no clock is read on the CPU);
3. BatchGenerator picks the schedule automatically and falls back to the
   serialized program when the batch does not divide by the stage count.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.config import tiny
from cake_tpu.models.llama import init_params
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.parallel.mesh import (
    MeshPlan,
    init_cache_on_mesh,
    shard_params,
)
from cake_tpu.parallel.pipeline import (
    build_interleaved_decode,
    build_sharded_decode,
    build_sharded_prefill,
)


def _cfg(**kw):
    base = dict(max_seq_len=64, num_hidden_layers=8, hidden_size=64,
                intermediate_size=128, num_attention_heads=8,
                num_key_value_heads=4, vocab_size=96, dtype="bfloat16")
    base.update(kw)
    return tiny(**base)


def _run_decode(cfg, plan, params, build, batch, steps, settings,
                kv_quant=None, **kw):
    p = shard_params(params, plan.mesh)
    cache = init_cache_on_mesh(cfg, plan.mesh, batch=batch,
                               max_seq=cfg.max_seq_len, quant=kv_quant)
    prefill = build_sharded_prefill(cfg, plan, params_like=p,
                                    kv_quant=kv_quant)
    prompt = jnp.asarray([[1, 5, 9, 14, 3, 8, 2, 4]] * batch, jnp.int32)
    logits, cache = prefill(p, prompt, cache,
                            jnp.full((batch,), 7, jnp.int32))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    keys = jnp.stack([jax.random.fold_in(jax.random.PRNGKey(0), i)
                      for i in range(batch)])
    pos = jnp.full((batch,), 8, jnp.int32)
    hist = jnp.full((batch, 16), -1, jnp.int32)
    slot = jnp.zeros((batch,), jnp.int32)
    idx = jnp.ones((batch,), jnp.int32)
    dec = build(cfg, settings, plan, params_like=p, steps=steps,
                kv_quant=kv_quant, **kw)
    toks, cache, hist, slot = dec(p, tok, cache, pos, keys, hist, slot, idx)
    flat = [np.asarray(x) for x in jax.tree.leaves(cache)]
    return np.asarray(toks), flat, np.asarray(hist), np.asarray(slot)


@pytest.mark.parametrize("mesh_kw,batch", [
    (dict(num_stages=4, tp=1, dp=1), 8),
    (dict(num_stages=2, tp=2, dp=2), 8),
    (dict(num_stages=2, tp=1, dp=1), 2),  # microbatch of one row
])
def test_bit_identical_to_serialized(mesh_kw, batch):
    """Sampled streams + cache + sampler state match the serialized
    per-row program exactly, across pipeline/tp/dp layouts."""
    cfg = _cfg()
    n = mesh_kw["num_stages"] * mesh_kw["tp"] * mesh_kw["dp"]
    plan = MeshPlan.build(cfg, devices=jax.devices()[:n], **mesh_kw)
    params = init_params(cfg, jax.random.PRNGKey(0))
    settings = SamplerSettings(temperature=0.9, top_k=20,
                               repeat_penalty=1.1)
    t1, c1, h1, s1 = _run_decode(
        cfg, plan, params, build_sharded_decode, batch, 4, settings,
        per_row=True)
    t2, c2, h2, s2 = _run_decode(
        cfg, plan, params, build_interleaved_decode, batch, 4, settings)
    np.testing.assert_array_equal(t1, t2)
    for a, b in zip(c1, c2):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(h1, h2)
    np.testing.assert_array_equal(s1, s2)


def test_bit_identical_int8_kv():
    """The quantize-on-write KV tier composes with the interleaved
    schedule (row-sliced QuantizedKV buffers round-trip exactly)."""
    cfg = _cfg()
    plan = MeshPlan.build(cfg, num_stages=4, devices=jax.devices()[:4])
    params = init_params(cfg, jax.random.PRNGKey(1))
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    t1, c1, *_ = _run_decode(cfg, plan, params, build_sharded_decode, 8, 4,
                             settings, kv_quant="int8", per_row=True)
    t2, c2, *_ = _run_decode(cfg, plan, params, build_interleaved_decode,
                             8, 4, settings, kv_quant="int8")
    np.testing.assert_array_equal(t1, t2)
    for a, b in zip(c1, c2):
        np.testing.assert_array_equal(a, b)


def test_steps1_signature():
    """steps=1 returns [B] like the serialized per-row single-step."""
    cfg = _cfg()
    plan = MeshPlan.build(cfg, num_stages=2, devices=jax.devices()[:2])
    params = init_params(cfg, jax.random.PRNGKey(2))
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    t1, *_ = _run_decode(cfg, plan, params, build_sharded_decode, 4, 1,
                         settings, per_row=True)
    t2, *_ = _run_decode(cfg, plan, params, build_interleaved_decode, 4, 1,
                         settings)
    assert t1.shape == t2.shape == (4,)
    np.testing.assert_array_equal(t1, t2)


def test_indivisible_batch_rejected():
    cfg = _cfg()
    plan = MeshPlan.build(cfg, num_stages=4, devices=jax.devices()[:4])
    params = init_params(cfg, jax.random.PRNGKey(0))
    settings = SamplerSettings()
    with pytest.raises(ValueError, match="divisible"):
        _run_decode(cfg, plan, params, build_interleaved_decode, 6, 2,
                    settings)


def test_throughput_scales_on_virtual_mesh():
    """Aggregate serving throughput beats the serialized loop when dp-batch
    >= stages, by what makes it so: the serialized schedule burns S× the
    layer work per token (every stage computes the full batch at every
    hop, one result kept), the interleaved one runs B/S rows a stage and
    cycle. Read from the two compiled programs, not from a clock (a
    wall-clock ratio on shared host cores is a CPU number under a device
    claim, and it moved with the machine's load): XLA's cost analysis
    counts a loop's body once, and both blocks are one loop whose body
    runs S times a token (a hop; a cycle), so the bodies' ratio is the
    ratio a token. At S=4 the interleaved body must cost at most half the
    serialized one's FLOPs and bytes; the arithmetic says ~1/S (0.21 and
    0.27 here: the head and the sampler do not shrink with the stages)."""
    cfg = _cfg(max_seq_len=256, hidden_size=256, intermediate_size=512,
               vocab_size=1024)
    S, B, steps = 4, 16, 8
    plan = MeshPlan.build(cfg, num_stages=S, devices=jax.devices()[:S])
    params = init_params(cfg, jax.random.PRNGKey(0))
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    p = shard_params(params, plan.mesh)

    def cost(build, **kw):
        cache = init_cache_on_mesh(cfg, plan.mesh, batch=B, max_seq=256)
        tok = jnp.ones((B,), jnp.int32)
        keys = jnp.stack([jax.random.fold_in(jax.random.PRNGKey(0), i)
                          for i in range(B)])
        pos = jnp.full((B,), 8, jnp.int32)
        hist = jnp.full((B, 16), -1, jnp.int32)
        slot = jnp.zeros((B,), jnp.int32)
        idx = jnp.ones((B,), jnp.int32)
        dec = build(cfg, settings, plan, params_like=p, steps=steps, **kw)
        analysis = dec.lower(p, tok, cache, pos, keys, hist, slot,
                             idx).compile().cost_analysis()
        return analysis["flops"], analysis["bytes accessed"]

    serial = cost(build_sharded_decode, per_row=True)
    interleaved = cost(build_interleaved_decode)
    for name, a, b in zip(("flops", "bytes accessed"), interleaved, serial):
        assert 0 < a <= b / 2, (
            f"interleaved {name} {a:.3g} against serialized {b:.3g} "
            f"(ratio {a / b:.2f}, ~1/{S} expected)")


def test_batch_generator_auto_interleave():
    """BatchGenerator swaps the interleaved program in when the batch
    divides by the stage count and the streams match the serialized
    output; an indivisible batch silently uses the serialized fallback."""
    from cake_tpu.runtime.batch_generator import BatchGenerator

    cfg = _cfg(eos_token_id=-1)
    prompts = [[1, 5, 9, 2], [7, 3, 8, 1], [2, 2, 4, 4], [9, 8, 7, 6]]

    def run(interleave, n_prompts=4):
        plan = MeshPlan.build(cfg, num_stages=2, devices=jax.devices()[:2])
        gen = BatchGenerator(cfg, init_params(cfg, jax.random.PRNGKey(3)),
                             plan=plan,
                             settings=SamplerSettings(temperature=0.8,
                                                      top_k=20, seed=7),
                             block_size=2, interleave=interleave)
        gen.set_prompts([list(x) for x in prompts[:n_prompts]])
        out = [[] for _ in range(n_prompts)]
        for _ in range(6):
            for i, t in enumerate(gen.step()):
                if t is not None:
                    out[i].append(int(t.id) if hasattr(t, "id") else int(t))
        return out

    il = run(interleave=True)
    serial = run(interleave=False)
    assert il == serial
    # odd batch: the picker must fall back (still correct output)
    il3 = run(interleave=True, n_prompts=3)
    serial3 = run(interleave=False, n_prompts=3)
    assert il3 == serial3


def test_bit_identical_int8_weights_under_pin():
    """Int8 WEIGHTS (quantized linears + lm_head): streams match the
    serialized program bit-for-bit under a pinned quant backend — the
    BatchGenerator contract (it always pins before tracing). Covers the
    vocab-split head's backend-class guard."""
    from cake_tpu.ops import quant
    from cake_tpu.ops.quant import quantize_params

    cfg = _cfg(vocab_size=96)
    plan = MeshPlan.build(cfg, num_stages=4, devices=jax.devices()[:4])
    qparams = quantize_params(init_params(cfg, jax.random.PRNGKey(5)))
    settings = SamplerSettings(temperature=0.9, top_k=20, repeat_penalty=1.1)
    with quant.pinned_impl("xla"):
        t1, c1, h1, s1 = _run_decode(
            cfg, plan, qparams, build_sharded_decode, 8, 4, settings,
            per_row=True)
        t2, c2, h2, s2 = _run_decode(
            cfg, plan, qparams, build_interleaved_decode, 8, 4, settings)
    np.testing.assert_array_equal(t1, t2)
    for a, b in zip(c1, c2):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(h1, h2)


# -- interleaved verification (serving speculation on stages > 1) -------------

@pytest.mark.parametrize("mesh_kw,batch,kv_quant", [
    (dict(num_stages=4, tp=1, dp=1), 8, None),
    (dict(num_stages=2, tp=2, dp=2), 8, None),
    (dict(num_stages=4, tp=1, dp=1), 8, "int8"),  # quantized staging cache
])
def test_interleaved_verify_bit_identical(mesh_kw, batch, kv_quant):
    """build_interleaved_verify_rows: logits at every position and the KV
    writes (incl. QuantizedKV q/scale slicing) match the serialized
    per-row verify exactly."""
    from cake_tpu.parallel.pipeline import (
        build_interleaved_verify_rows,
        build_sharded_verify_rows,
    )

    cfg = _cfg()
    n = mesh_kw["num_stages"] * mesh_kw["tp"] * mesh_kw["dp"]
    plan = MeshPlan.build(cfg, devices=jax.devices()[:n], **mesh_kw)
    params = init_params(cfg, jax.random.PRNGKey(0))
    p = shard_params(params, plan.mesh)

    def run(build):
        cache = init_cache_on_mesh(cfg, plan.mesh, batch=batch, max_seq=64,
                                   quant=kv_quant)
        prefill = build_sharded_prefill(cfg, plan, params_like=p,
                                        kv_quant=kv_quant)
        prompt = jnp.asarray([[1, 5, 9, 14, 3, 8, 2, 4]] * batch, jnp.int32)
        _, cache = prefill(p, prompt, cache,
                           jnp.full((batch,), 7, jnp.int32))
        fed = jnp.asarray(
            np.random.default_rng(1).integers(1, 90, (batch, 5)), jnp.int32)
        pos = jnp.asarray([8, 9, 8, 10, 8, 9, 11, 8][:batch], jnp.int32)
        v = build(cfg, plan, params_like=p, kv_quant=kv_quant)
        logits, cache = v(p, fed, cache, pos)
        return (np.asarray(logits),
                [np.asarray(x) for x in jax.tree.leaves(cache)])

    l1, c1 = run(build_sharded_verify_rows)
    l2, c2 = run(build_interleaved_verify_rows)
    np.testing.assert_array_equal(l1, l2)
    for a, b in zip(c1, c2):
        np.testing.assert_array_equal(a, b)


def test_spec_serving_on_stages_uses_interleaved_verify():
    """BatchGenerator with spec_k on a staged mesh: the interleaved verify
    (and interleaved decode fallback) serve the rounds; streams match the
    1-stage serving oracle bit-for-bit."""
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.batch_generator import BatchGenerator

    cfg = _cfg(eos_token_id=-1)
    params = init_params(cfg, jax.random.PRNGKey(3))
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    prompts = [[5, 9, 2, 5, 9, 2], [3, 1, 4, 1, 3, 1]]

    flat = BatchGenerator(cfg, params, settings=settings, spec_k=4)
    flat.set_prompts([list(p) for p in prompts])
    want = flat.generate(10)

    plan = MeshPlan.build(cfg, num_stages=2, devices=jax.devices()[:2])
    staged = BatchGenerator(cfg, params, plan=plan, settings=settings,
                            spec_k=4)
    staged.set_prompts([list(p) for p in prompts])
    assert staged.generate(10) == want
    assert staged.stats()["spec_dispatches"] >= 1
    # the interleaved verify program was actually built and used
    assert staged._BatchGenerator__verify_rows_il is not None


def test_interleaved_verify_int8_weights_under_pin():
    """Int8 WEIGHTS through the interleaved verify's vocab-split head:
    logits bit-identical to the serialized verify under a pinned backend
    (the QuantizedLinear q/scale sub-head slice path)."""
    from cake_tpu.ops import quant
    from cake_tpu.ops.quant import quantize_params
    from cake_tpu.parallel.pipeline import (
        build_interleaved_verify_rows,
        build_sharded_verify_rows,
    )

    cfg = _cfg(vocab_size=96)
    plan = MeshPlan.build(cfg, num_stages=4, devices=jax.devices()[:4])
    qparams = quantize_params(init_params(cfg, jax.random.PRNGKey(6)))
    p = shard_params(qparams, plan.mesh)
    batch = 8

    def run(build):
        cache = init_cache_on_mesh(cfg, plan.mesh, batch=batch, max_seq=64)
        prefill = build_sharded_prefill(cfg, plan, params_like=p)
        prompt = jnp.asarray([[1, 5, 9, 14, 3, 8, 2, 4]] * batch, jnp.int32)
        _, cache = prefill(p, prompt, cache,
                           jnp.full((batch,), 7, jnp.int32))
        fed = jnp.asarray(
            np.random.default_rng(2).integers(1, 90, (batch, 4)), jnp.int32)
        pos = jnp.asarray([8, 9, 8, 10, 8, 9, 11, 8], jnp.int32)
        v = build(cfg, plan, params_like=p)
        logits, _ = v(p, fed, cache, pos)
        return np.asarray(logits)

    with quant.pinned_impl("xla"):
        l1 = run(build_sharded_verify_rows)
        l2 = run(build_interleaved_verify_rows)
    np.testing.assert_array_equal(l1, l2)
