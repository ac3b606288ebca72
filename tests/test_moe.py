"""MoE routing/compute unit tests (no torch oracle needed — f64 numpy loop
is the reference math; HF golden parity lives in test_families.py), and
MoE over the mesh pipeline. The sorted form's sections are
``tests/test_moe_sorted.py`` (the op) and ``tests/test_moe_sorted_engine.py``
(the layer loop, the engine's counters, the sweep tool, the reader)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cake_tpu.ops import moe
from cake_tpu.ops.moe import (
    GATHER_MAX_ROWS,
    SORTED_MIN_ROWS_INT8,
    _moe_dense,
    _moe_gather,
    moe_swiglu,
    router_topk,
)


def _fixtures(n=3, h=16, f=32, e=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (n, h))
    rw = jax.random.normal(ks[1], (h, e))
    wg = jax.random.normal(ks[2], (e, h, f)) / 4
    wu = jax.random.normal(ks[3], (e, h, f)) / 4
    wd = jax.random.normal(ks[4], (e, f, h)) / 6
    return x, rw, wg, wu, wd


def _oracle(x, rw, wg, wu, wd, k):
    """Per-token f64 loop: top-k by logit, softmax over selected, routed
    SwiGLU sum."""
    x64 = np.asarray(x, np.float64)
    logits = x64 @ np.asarray(rw, np.float64)
    out = np.zeros_like(x64)

    def silu(v):
        return v / (1 + np.exp(-v))

    for n in range(x64.shape[0]):
        top = np.argsort(-logits[n], kind="stable")[:k]
        w = np.exp(logits[n][top] - logits[n][top].max())
        w /= w.sum()
        for wgt, e in zip(w, top):
            hidden = silu(x64[n] @ np.asarray(wg[e], np.float64)) * (
                x64[n] @ np.asarray(wu[e], np.float64)
            )
            out[n] += wgt * (hidden @ np.asarray(wd[e], np.float64))
    return out


def test_router_combine_weights_normalized():
    x, rw, *_ = _fixtures()
    combine, w, idx = router_topk(x, rw, 2)
    np.testing.assert_allclose(np.asarray(combine.sum(-1)), 1.0, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)
    # combine's nonzeros sit exactly at the top-k indices
    nz = np.asarray(combine) > 0
    for n in range(x.shape[0]):
        assert set(np.nonzero(nz[n])[0]) == set(np.asarray(idx[n]))


def test_dense_and_gather_agree_with_oracle():
    x, rw, wg, wu, wd = _fixtures()
    combine, w, idx = router_topk(x, rw, 2)
    dense = _moe_dense(x, combine, wg, wu, wd)
    gather = _moe_gather(x, w, idx, wg, wu, wd)
    oracle = _oracle(x, rw, wg, wu, wd, 2)
    np.testing.assert_allclose(np.asarray(dense), oracle, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(gather), oracle, rtol=2e-5, atol=2e-5)


def test_auto_strategy_crossover_consistency():
    """The same inputs produce the same outputs whichever side of the
    gather/dense crossover N lands on (pad the batch to push it across)."""
    x, rw, wg, wu, wd = _fixtures(n=2)
    small = moe_swiglu(x[None], rw, wg, wu, wd, 2)  # N*k=4 -> gather
    big_n = GATHER_MAX_ROWS  # N*k = 2*GATHER_MAX_ROWS -> dense
    xb = jnp.concatenate([x, jnp.zeros((big_n - 2, x.shape[1]), x.dtype)])
    big = moe_swiglu(xb[None], rw, wg, wu, wd, 2)
    np.testing.assert_allclose(np.asarray(small[0]), np.asarray(big[0, :2]),
                               rtol=2e-5, atol=2e-5)


def test_moe_swiglu_shapes_and_finite():
    x, rw, wg, wu, wd = _fixtures(n=12)  # N*k=24 -> dense path
    out = moe_swiglu(x.reshape(3, 4, -1), rw, wg, wu, wd, 2)
    assert out.shape == (3, 4, x.shape[-1])
    assert bool(jnp.isfinite(out).all())


def test_top1_routing():
    """Switch-style top-1: softmax over one logit = weight 1.0 on the
    argmax expert."""
    x, rw, wg, wu, wd = _fixtures()
    out = moe_swiglu(x[None], rw, wg, wu, wd, 1)
    oracle = _oracle(x, rw, wg, wu, wd, 1)
    np.testing.assert_allclose(np.asarray(out[0]), oracle, rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("ep", [2, 4])
def test_expert_parallel_matches_single_device(ep):
    """Experts sharded over an ep mesh axis via shard_map: the psum'd
    combine must equal the unsharded op bit-for-bit in structure (same
    routing) and numerically."""
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    x, rw, wg, wu, wd = _fixtures(n=4, e=4)
    devs = jax.devices()[:ep]
    mesh = Mesh(np.array(devs), ("ep",))
    spec_w = P("ep")  # expert axis sharded
    repl = P()

    def f(x, rw, wg, wu, wd):
        return moe_swiglu(x, rw, wg, wu, wd, 2, ep_axis="ep", ep_size=ep)

    sharded = shard_map(
        f, mesh=mesh,
        in_specs=(repl, repl, spec_w, spec_w, spec_w),
        out_specs=repl,
    )
    got = sharded(x[None], rw,
                  jax.device_put(wg, NamedSharding(mesh, spec_w)),
                  jax.device_put(wu, NamedSharding(mesh, spec_w)),
                  jax.device_put(wd, NamedSharding(mesh, spec_w)))
    want = moe_swiglu(x[None], rw, wg, wu, wd, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# MoE over the mesh pipeline: the full generator surface with the expert
# axis sharded (stage x ep x tp), token-identical to the all-local stream.
# ---------------------------------------------------------------------------

from cake_tpu.models import llama  # noqa: E402
from cake_tpu.models.config import tiny_moe  # noqa: E402
from cake_tpu.ops.sampling import SamplerSettings  # noqa: E402
from cake_tpu.runtime.generator import LlamaGenerator  # noqa: E402
from cake_tpu.runtime.mesh_generator import MeshGenerator  # noqa: E402

from moe_kit import GREEDY  # noqa: E402

MOE_CFG = tiny_moe(max_seq_len=64)


@pytest.fixture(scope="module")
def moe_params():
    return llama.init_params(MOE_CFG, jax.random.PRNGKey(5))


@pytest.mark.parametrize(
    "axes",
    [
        dict(ep=2),
        dict(ep=4),
        dict(num_stages=2, ep=2),
        dict(num_stages=2, ep=2, tp=2),
    ],
    ids=lambda a: "-".join(f"{k}{v}" for k, v in a.items()),
)
def test_moe_mesh_greedy_parity_with_local(moe_params, axes):
    settings = SamplerSettings(**GREEDY)
    ref = LlamaGenerator(MOE_CFG, moe_params, settings=settings)
    ref.set_prompt([5, 9, 2, 11])
    want = [ref.next_token(i).id for i in range(6)]

    g = MeshGenerator(MOE_CFG, moe_params, settings=settings, **axes)
    g.set_prompt([5, 9, 2, 11])
    assert [g.next_token(i).id for i in range(6)] == want


@pytest.mark.parametrize(
    "axes", [dict(num_stages=2, ep=2), dict(ep=2, tp=2)],
    ids=lambda a: "-".join(f"{k}{v}" for k, v in a.items()))
def test_sorted_form_on_the_mesh_matches_local(axes, monkeypatch):
    """A prompt of the int8 threshold's bucket through the mesh programs
    (stages x ep, ep x tp): each rank's layer loop closes over ITS slice
    of the expert stacks whole, the sorted form computes the pairs that
    fall on its experts (and, under ``tp``, its slice of their width),
    and the one ``psum`` sums the parts: the all-local dense stream."""
    from cake_tpu.ops.quant import quantize_params

    cfg = tiny_moe(max_seq_len=256)
    params = quantize_params(llama.init_params(cfg, jax.random.PRNGKey(3)))
    prompt = [t % 250 + 1 for t in range(SORTED_MIN_ROWS_INT8 + 2)]
    settings = SamplerSettings(**GREEDY)
    ref = LlamaGenerator(cfg, params, settings=settings)
    ref.set_prompt(prompt)
    want = [ref.next_token(i).id for i in range(4)]
    assert moe.form_traced(2 * SORTED_MIN_ROWS_INT8) == "dense"

    monkeypatch.setenv("CAKE_PALLAS", "1")
    g = MeshGenerator(cfg, params, settings=settings, **axes)
    g.set_prompt(prompt)
    assert [g.next_token(i).id for i in range(4)] == want
    assert moe.form_traced(2 * SORTED_MIN_ROWS_INT8) == "sorted"


def test_ep_requires_moe_config():
    from cake_tpu.models.config import tiny
    from cake_tpu.parallel.mesh import MeshPlan

    with pytest.raises(ValueError, match="num_local_experts"):
        MeshPlan.build(tiny(), ep=2)
    with pytest.raises(ValueError, match="divisible"):
        MeshPlan.build(tiny_moe(), ep=3)


def test_moe_serving_batch_generator_parity(moe_params):
    """MoE serves multi-stream on an ep x stage mesh: every stream must
    reproduce its solo all-local run token-for-token (the BatchGenerator
    bar, test_batch_generator.py, now with routed experts under ep)."""
    from cake_tpu.runtime.batch_generator import BatchGenerator

    settings = SamplerSettings(**GREEDY)
    prompts = [[5, 9, 2, 11], [3, 1, 4, 1, 5], [7, 7, 2]]

    solo = []
    for p in prompts:
        g = LlamaGenerator(MOE_CFG, moe_params, settings=settings)
        g.set_prompt(p)
        solo.append([g.next_token(i).id for i in range(6)])

    bg = BatchGenerator(MOE_CFG, moe_params, settings=settings,
                        num_stages=2, ep=2, block_size=2)
    bg.set_prompts(prompts)
    outs = bg.generate(6)
    assert [list(o) for o in outs] == solo


def test_moe_int8_experts_match_dequantized_oracle():
    """moe_swiglu over int8 expert stacks equals the same op over the
    explicitly dequantized arrays bit-for-bit (both strategies)."""
    from cake_tpu.ops.quant import dequantize_linear, quantize_linear

    x, rw, wg, wu, wd = _fixtures(n=2)
    qg, qu, qd = (quantize_linear(w) for w in (wg, wu, wd))
    dg, du, dd = (dequantize_linear(q, jnp.float32) for q in (qg, qu, qd))
    got_g = moe_swiglu(x[None], rw, qg, qu, qd, 2)  # gather path (N*k=4)
    want_g = moe_swiglu(x[None], rw, dg, du, dd, 2)
    np.testing.assert_array_equal(np.asarray(got_g), np.asarray(want_g))
    xb = jnp.concatenate([x, jnp.zeros((8, x.shape[1]), x.dtype)])
    got_d = moe_swiglu(xb[None], rw, qg, qu, qd, 2)  # dense path
    want_d = moe_swiglu(xb[None], rw, dg, du, dd, 2)
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))


def test_moe_int8_mesh_parity_with_local():
    """int8 expert stacks shard over ep (q takes the weight spec, scale
    [L, E, F] drops the in axis) and the mesh stream matches all-local."""
    from cake_tpu.ops.quant import quantize_params

    qparams = quantize_params(
        llama.init_params(MOE_CFG, jax.random.PRNGKey(5)), bits=8
    )
    settings = SamplerSettings(**GREEDY)
    ref = LlamaGenerator(MOE_CFG, qparams, settings=settings)
    ref.set_prompt([5, 9, 2, 11])
    want = [ref.next_token(i).id for i in range(6)]

    g = MeshGenerator(MOE_CFG, qparams, settings=settings, num_stages=2,
                      ep=2)
    g.set_prompt([5, 9, 2, 11])
    assert [g.next_token(i).id for i in range(6)] == want


def test_moe_int8_init_params():
    from cake_tpu.models import llama as L
    from cake_tpu.ops.quant import QuantizedLinear

    p = L.init_params_int8(MOE_CFG, jax.random.PRNGKey(0))
    assert isinstance(p["layers"]["w_gate"], QuantizedLinear)
    assert p["layers"]["w_gate"].q.ndim == 4  # [L, E, H, F]
    assert p["layers"]["router"].dtype == MOE_CFG.jax_dtype
    with pytest.raises(NotImplementedError, match="int4"):
        L.init_params_int4(MOE_CFG, jax.random.PRNGKey(0))


def test_mixtral_hbm_budget():
    """Budget arithmetic prices MoE expert stacks (x num_experts / ep) —
    the planning plane behind serving Mixtral-8x7B on a v5e-16."""
    from cake_tpu.models.config import mixtral_8x7b
    from cake_tpu.utils.memory import hbm_budget

    g = 1 << 30
    m = mixtral_8x7b(max_seq_len=4096)
    one = hbm_budget(m, quant="int8")
    sharded = hbm_budget(m, num_stages=4, ep=4, quant="int8")
    # experts dominate: 16-way expert-bytes split must shrink the total
    # close to 1/16 of the expert bytes (+ replicated embed/router floor)
    assert one["total"] / g > 40  # ~45 GB of int8 experts on one chip
    assert sharded["total"] / g < 4
    # ep shards ONLY the expert bytes: the ep=1 vs ep=4 layer-byte delta
    # must equal exactly (1 - 1/ep) of the expert bytes — a regression
    # that divided attention/norm bytes by ep would break this equality
    b = hbm_budget(m, num_stages=4, ep=1, quant="int8")
    e = m.num_local_experts
    expert_bytes = (
        m.num_hidden_layers / 4  # layers per stage
        * e
        * (3 * m.hidden_size * m.intermediate_size * 1  # int8 q bytes
           + (2 * m.intermediate_size + m.hidden_size) * 4)  # f32 scales
    )
    assert b["layers"] - sharded["layers"] == pytest.approx(
        expert_bytes * (1 - 1 / 4), rel=1e-6
    )


def test_moe_distributed_worker_parity(moe_params):
    """The cross-host master/worker runtime serves MoE layers unchanged —
    expert stacks slice by layer range like any stacked weight, and the
    TCP-shipped activations reproduce the all-local stream exactly."""
    from cake_tpu.parallel.topology import Topology
    from cake_tpu.runtime.master import DistributedGenerator, build_runners
    from cake_tpu.runtime.worker import Worker

    def loader(lo, hi):
        return jax.tree.map(lambda a: a[lo:hi], moe_params["layers"])

    w = Worker(
        "w", MOE_CFG,
        Topology.from_dict({"w": {"layers": ["model.layers.2-3"]}}),
        loader, address="127.0.0.1:0", max_seq=MOE_CFG.max_seq_len,
    )
    w.serve_in_background()
    try:
        topo = Topology.from_dict({
            "w": {"host": f"127.0.0.1:{w.port}",
                  "layers": ["model.layers.2-3"]},
        })
        settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
        runners = build_runners(MOE_CFG, topo, loader)
        head = {k: moe_params[k] for k in ("embed", "norm_f", "lm_head")}
        g = DistributedGenerator(MOE_CFG, head, runners, settings=settings)
        g.set_prompt([5, 9, 2])
        got = [g.next_token(i).id for i in range(6)]
        ref = LlamaGenerator(MOE_CFG, moe_params, settings=settings)
        ref.set_prompt([5, 9, 2])
        assert got == [ref.next_token(i).id for i in range(6)]
        g.close()
    finally:
        w.shutdown()


def test_moe_mesh_speculation_parity(moe_params):
    """Speculation over the ep mesh: the verification program (one pass
    over stage x ep) must reproduce the plain MoE stream bit for bit —
    the greedy exactness contract of speculative decoding."""
    from cake_tpu.runtime.speculative import MeshSpeculativeGenerator

    settings = SamplerSettings(**GREEDY)
    # repetitive prompt: n-gram proposals actually fire
    prompt = [5, 9, 2, 5, 9, 2, 5, 9, 2]
    ref = LlamaGenerator(MOE_CFG, moe_params, settings=settings)
    ref.set_prompt(prompt)
    want = [ref.next_token(i).id for i in range(8)]

    g = MeshSpeculativeGenerator(MOE_CFG, moe_params, settings=settings,
                                 num_stages=2, ep=2, spec_k=4)
    g.set_prompt(prompt)
    assert [g.next_token(i).id for i in range(8)] == want
    assert g.dispatches < 8  # speculation actually engaged


def test_moe_serving_int8kv_interleaved_parity(moe_params):
    """MoE x int8 KV cache x interleaved-microbatch decode (batch divides
    stages, so BatchGenerator auto-selects the GPipe-streamed schedule):
    every stream still reproduces its solo bf16-KV-free run... rather,
    its solo int8-KV oracle, token for token."""
    from cake_tpu.runtime.batch_generator import BatchGenerator

    settings = SamplerSettings(**GREEDY)
    prompts = [[5, 9, 2, 11], [3, 1, 4, 1], [7, 7, 2], [9, 8, 7, 6]]

    solo = []
    for p in prompts:
        g = LlamaGenerator(MOE_CFG, moe_params, settings=settings,
                          kv_quant="int8")
        g.set_prompt(p)
        solo.append([g.next_token(i).id for i in range(6)])

    bg = BatchGenerator(MOE_CFG, moe_params, settings=settings,
                        num_stages=2, ep=2, block_size=2, kv_quant="int8")
    bg.set_prompts(prompts)
    assert bg._interleave  # 4 streams over 2 stages: GPipe schedule on
    outs = bg.generate(6)
    assert [list(o) for o in outs] == solo
