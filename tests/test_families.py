"""Model families beyond Llama: Mistral (sliding window), Qwen2 (q/k/v
bias), Mixtral (MoE).

The reference serves exactly one family through its Generator seam
(`model/mod.rs:21-29`, llama.rs); these tests prove the same functional
decoder serves the other families' architectural deltas, each anchored
golden against HF transformers (the strongest offline oracle, SURVEY.md §4).
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from cake_tpu.models import llama  # noqa: E402
from cake_tpu.models.config import LlamaConfig, tiny, tiny_moe  # noqa: E402
from cake_tpu.ops.kvcache import init_cache  # noqa: E402
from cake_tpu.utils.weights import (  # noqa: E402
    load_llama_params,
    params_from_hf_tensors,
    save_llama_params,
)

IDS = [5, 17, 42, 99, 7, 3, 88, 120]


def _port(model, cfg):
    sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    return params_from_hf_tensors(
        sd.__getitem__, cfg.num_hidden_layers, dtype="float32",
        num_experts=cfg.num_local_experts, attention_bias=cfg.attention_bias,
        tie_word_embeddings=cfg.tie_word_embeddings,
    )


def _parity_prefill_then_decode(model, cfg, rtol=2e-4, atol=2e-4):
    """Prefill 4 tokens then decode the rest incrementally; every step's
    logits must match the full-context HF forward at that position."""
    params = _port(model, cfg)
    with torch.no_grad():
        ref_all = model(torch.tensor([IDS])).logits[0].numpy()
    cache = init_cache(cfg, batch=1, max_seq=cfg.max_seq_len)
    logits, cache = llama.forward(
        params, jnp.asarray([IDS[:4]], jnp.int32), cache, 0, cfg
    )
    np.testing.assert_allclose(np.asarray(logits[0]), ref_all[3],
                               rtol=rtol, atol=atol)
    for i in range(4, len(IDS)):
        logits, cache = llama.forward(
            params, jnp.asarray([[IDS[i]]], jnp.int32), cache, i, cfg
        )
        np.testing.assert_allclose(np.asarray(logits[0]), ref_all[i],
                                   rtol=rtol, atol=atol)


def test_mistral_sliding_window_parity():
    # window=4 < len(IDS)=8 so the window genuinely narrows the mask
    hf_cfg = transformers.MistralConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5, rope_theta=10000.0,
        sliding_window=4, tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = transformers.MistralForCausalLM(hf_cfg).eval()
    cfg = LlamaConfig.from_hf_dict(hf_cfg.to_dict(), dtype="float32",
                                   max_seq_len=128)
    assert cfg.model_type == "mistral" and cfg.sliding_window == 4
    _parity_prefill_then_decode(model, cfg)


def test_mistral_window_differs_from_full():
    """The window must actually change the math (guards against a mask
    that silently degrades to full causal)."""
    hf_cfg = transformers.MistralConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        rope_theta=10000.0, sliding_window=4,
    )
    torch.manual_seed(0)
    model = transformers.MistralForCausalLM(hf_cfg).eval()
    cfg = LlamaConfig.from_hf_dict(hf_cfg.to_dict(), dtype="float32",
                                   max_seq_len=128)
    params = _port(model, cfg)
    cache = init_cache(cfg, batch=1, max_seq=cfg.max_seq_len)
    win, _ = llama.forward(params, jnp.asarray([IDS], jnp.int32), cache, 0, cfg)
    import dataclasses

    full_cfg = dataclasses.replace(cfg, sliding_window=None)
    cache = init_cache(full_cfg, batch=1, max_seq=cfg.max_seq_len)
    full, _ = llama.forward(params, jnp.asarray([IDS], jnp.int32), cache, 0,
                            full_cfg)
    assert float(jnp.abs(win - full).max()) > 1e-3


def test_qwen2_bias_parity():
    hf_cfg = transformers.Qwen2Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False, attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = transformers.Qwen2ForCausalLM(hf_cfg).eval()
    # HF zero-inits projection biases; randomize them so the bias path is
    # genuinely exercised (a loader that dropped them would still "match"
    # against zeros)
    with torch.no_grad():
        for name, p in model.named_parameters():
            if name.endswith("proj.bias"):
                p.normal_(0.0, 0.1)
    cfg = LlamaConfig.from_hf_dict(hf_cfg.to_dict(), dtype="float32",
                                   max_seq_len=128)
    # Qwen2's q/k/v bias is implied by the family, not spelled in the config
    assert cfg.model_type == "qwen2" and cfg.attention_bias
    assert float(model.state_dict()["model.layers.0.self_attn.q_proj.bias"]
                 .abs().max()) > 0
    _parity_prefill_then_decode(model, cfg)


def test_mixtral_moe_parity():
    hf_cfg = transformers.MixtralConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5, rope_theta=10000.0,
        num_local_experts=4, num_experts_per_tok=2,
        sliding_window=None, tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = transformers.MixtralForCausalLM(hf_cfg).eval()
    cfg = LlamaConfig.from_hf_dict(hf_cfg.to_dict(), dtype="float32",
                                   max_seq_len=128)
    assert cfg.num_local_experts == 4 and cfg.num_experts_per_tok == 2
    # prefill (dense-dispatch path: N*k > GATHER_MAX_ROWS) and incremental
    # decode (gather path: N=1) both run against the same HF oracle
    _parity_prefill_then_decode(model, cfg)


def test_family_checkpoint_round_trip(tmp_path):
    """save -> load through the real safetensors path for a biased MoE
    params pytree (both family extensions at once)."""
    cfg = tiny_moe(attention_bias=True)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    save_llama_params(params, tmp_path, cfg.num_hidden_layers)
    loaded = load_llama_params(
        tmp_path, cfg.num_hidden_layers, dtype="float32",
        num_experts=cfg.num_local_experts, attention_bias=True,
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=0, atol=0),
        params, loaded,
    )


def test_moe_int4_load_rejected_int8_loads(tmp_path):
    """int4 expert packing is not wired (rejected loudly); int8 expert
    stacks load and match quantize_params applied to the host pytree."""
    from cake_tpu.ops.quant import quantize_params

    cfg = tiny_moe()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    save_llama_params(params, tmp_path, cfg.num_hidden_layers)
    with pytest.raises(NotImplementedError, match="int4"):
        load_llama_params(tmp_path, cfg.num_hidden_layers, quantize="int4",
                          num_experts=cfg.num_local_experts)
    loaded = load_llama_params(tmp_path, cfg.num_hidden_layers,
                               dtype="float32", quantize="int8")
    want = quantize_params(params, bits=8)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        loaded, want,
    )


def test_config_family_round_trip():
    for make in (lambda: tiny(model_type="mistral", sliding_window=4),
                 lambda: tiny(model_type="qwen2", attention_bias=True),
                 lambda: tiny_moe()):
        cfg = make()
        again = LlamaConfig.from_hf_dict(cfg.to_hf_dict(), dtype=cfg.dtype,
                                         max_seq_len=cfg.max_seq_len)
        assert again == cfg


def test_family_sharded_load_matches_host_load(tmp_path):
    """Direct-to-mesh loading of a biased MoE checkpoint (family tensors
    auto-detected from the stored names) equals host-load + shard_params,
    with the expert axis genuinely sharded over ep."""
    from cake_tpu.parallel.mesh import EP, MeshPlan, shard_params

    cfg = tiny_moe(attention_bias=True)
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    save_llama_params(params, tmp_path, cfg.num_hidden_layers)

    from cake_tpu.utils.sharded_load import load_llama_params_on_mesh

    plan = MeshPlan.build(cfg, num_stages=2, ep=2)
    got = load_llama_params_on_mesh(tmp_path, cfg, plan.mesh)
    want = shard_params(
        load_llama_params(tmp_path, cfg.num_hidden_layers, dtype="float32"),
        plan.mesh,
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        got, want,
    )
    # the expert stacks are actually ep-sharded, not replicated
    spec = got["layers"]["w_gate"].sharding.spec
    assert EP in spec, spec


def test_llama_arch_attention_bias_parity():
    """HF llama-arch `attention_bias: true` biases q/k/v AND o_proj; the
    o_proj bias must load and apply (review finding: silently dropped)."""
    hf_cfg = transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5, rope_theta=10000.0,
        attention_bias=True, mlp_bias=False, tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(hf_cfg).eval()
    with torch.no_grad():
        for name, p in model.named_parameters():
            if name.endswith("proj.bias"):
                p.normal_(0.0, 0.1)
    assert float(model.state_dict()["model.layers.0.self_attn.o_proj.bias"]
                 .abs().max()) > 0
    cfg = LlamaConfig.from_hf_dict(hf_cfg.to_dict(), dtype="float32",
                                   max_seq_len=128)
    assert cfg.attention_bias
    # port through the auto-detecting checkpoint path so bo is exercised
    params = _port_o(model, cfg)
    with torch.no_grad():
        ref_all = model(torch.tensor([IDS])).logits[0].numpy()
    cache = init_cache(cfg, batch=1, max_seq=cfg.max_seq_len)
    logits, cache = llama.forward(
        params, jnp.asarray([IDS[:4]], jnp.int32), cache, 0, cfg
    )
    np.testing.assert_allclose(np.asarray(logits[0]), ref_all[3],
                               rtol=2e-4, atol=2e-4)


def _port_o(model, cfg):
    sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    return params_from_hf_tensors(
        sd.__getitem__, cfg.num_hidden_layers, dtype="float32",
        attention_bias=True, o_bias=True,
        tie_word_embeddings=cfg.tie_word_embeddings,
    )


def test_qwen2_partial_window_rejected():
    """A use_sliding_window=true config with a partial max_window_layers
    depth must be rejected, not silently served with a uniform window."""
    d = tiny().to_hf_dict()
    d.update(model_type="qwen2", sliding_window=4, use_sliding_window=True,
             max_window_layers=2)
    with pytest.raises(ValueError, match="max_window_layers"):
        LlamaConfig.from_hf_dict(d)
    # gated off -> no window regardless of the value
    d.update(use_sliding_window=False)
    assert LlamaConfig.from_hf_dict(d).sliding_window is None
    # full depth (0) -> uniform window, supported
    d.update(use_sliding_window=True, max_window_layers=0)
    assert LlamaConfig.from_hf_dict(d).sliding_window == 4


def test_quantize_model_moe_int8_round_trip(tmp_path):
    """Offline int8 pre-quantization of an MoE checkpoint: expert tensors
    get .q8/.scale, the pre-quantized load is bit-equal to quantize-on-load,
    and int4 is rejected up front."""
    from cake_tpu.tools.quantize_model import quantize_checkpoint

    cfg = tiny_moe()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    save_llama_params(params, tmp_path / "src", cfg.num_hidden_layers)
    with pytest.raises(NotImplementedError, match="int4"):
        quantize_checkpoint(tmp_path / "src", tmp_path / "q4", bits=4)
    out = quantize_checkpoint(tmp_path / "src", tmp_path / "q8", bits=8)
    pre = load_llama_params(out, cfg.num_hidden_layers, dtype="float32",
                            quantize="int8")
    onfly = load_llama_params(tmp_path / "src", cfg.num_hidden_layers,
                              dtype="float32", quantize="int8")
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        pre, onfly,
    )


def test_family_sharded_load_int8_moe_matches_host(tmp_path):
    """Direct-to-mesh int8 MoE: quantize-on-load expert stacks (and a
    pre-quantized .q8 checkpoint) equal host-load + shard_params bit for
    bit, with the expert q/scale leaves genuinely ep-sharded."""
    from cake_tpu.parallel.mesh import EP, MeshPlan, shard_params
    from cake_tpu.tools.quantize_model import quantize_checkpoint
    from cake_tpu.utils.sharded_load import load_llama_params_on_mesh

    cfg = tiny_moe()
    params = llama.init_params(cfg, jax.random.PRNGKey(7))
    save_llama_params(params, tmp_path / "src", cfg.num_hidden_layers)
    # tp=2 exercises the expert callbacks' SLICED reads: column-parallel
    # w_gate/w_up quantize a column slice, row-parallel w_down reads its
    # row shard against the memoized full-in-axis scale
    plan = MeshPlan.build(cfg, num_stages=2, ep=2, tp=2)

    want = shard_params(
        load_llama_params(tmp_path / "src", cfg.num_hidden_layers,
                          dtype="float32", quantize="int8"),
        plan.mesh,
    )
    got = load_llama_params_on_mesh(tmp_path / "src", cfg, plan.mesh,
                                    quantize="int8")
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        got, want,
    )
    assert EP in got["layers"]["w_gate"].q.sharding.spec
    assert EP in got["layers"]["w_down"].scale.sharding.spec

    # pre-quantized .q8 checkpoint through the same path
    out = quantize_checkpoint(tmp_path / "src", tmp_path / "q8", bits=8)
    pre = load_llama_params_on_mesh(out, cfg, plan.mesh, quantize="int8")
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        pre, want,
    )


def test_gemma_parity():
    """Gemma: explicit head_dim (heads x head_dim != hidden), GeGLU,
    (1+w) RMSNorm, sqrt(hidden)-scaled embeddings, tied head — the
    structurally different family, held to the same HF golden bar."""
    hf_cfg = transformers.GemmaConfig(
        vocab_size=256, hidden_size=48, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16,  # 4 x 16 = 64 != hidden 48
        max_position_embeddings=128, rms_norm_eps=1e-6, rope_theta=10000.0,
        hidden_activation="gelu_pytorch_tanh", tie_word_embeddings=True,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = transformers.GemmaForCausalLM(hf_cfg).eval()
    cfg = LlamaConfig.from_hf_dict(hf_cfg.to_dict(), dtype="float32",
                                   max_seq_len=128)
    assert cfg.model_type == "gemma"
    assert cfg.head_dim == 16 and cfg.hidden_act == "gelu_tanh"
    assert cfg.rms_norm_offset and cfg.embed_scale and cfg.tie_word_embeddings
    _parity_prefill_then_decode(model, cfg)


def test_gemma_config_round_trip():
    from cake_tpu.models.config import gemma_7b

    cfg = gemma_7b(max_seq_len=64)
    again = LlamaConfig.from_hf_dict(cfg.to_hf_dict(), dtype=cfg.dtype,
                                     max_seq_len=64)
    assert again == cfg
    # a non-default head_dim survives the round trip explicitly
    assert again.head_dim == 256


def test_gemma_mesh_parity():
    """Gemma over the mesh pipeline (stage x tp): token-identical to the
    all-local stream — the embed scaling / norm offset / GeGLU deltas ride
    the one shared code path, so sharding cannot diverge from local."""
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.generator import LlamaGenerator
    from cake_tpu.runtime.mesh_generator import MeshGenerator

    cfg = tiny(model_type="gemma", hidden_act="gelu_tanh",
               rms_norm_offset=True, embed_scale=True, head_dim=8,
               max_seq_len=64)
    assert cfg.head_dim == 8  # explicit, != hidden/heads = 16
    params = llama.init_params(cfg, jax.random.PRNGKey(11))
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    ref = LlamaGenerator(cfg, params, settings=settings)
    ref.set_prompt([5, 9, 2, 11])
    want = [ref.next_token(i).id for i in range(6)]

    g = MeshGenerator(cfg, params, settings=settings, num_stages=2, tp=2)
    g.set_prompt([5, 9, 2, 11])
    assert [g.next_token(i).id for i in range(6)] == want


def test_tied_head_auto_detected(tmp_path):
    """A checkpoint with no stored lm_head.weight (Gemma/Llama-3.2-1B
    style) can only be tied — both loaders must detect that instead of
    KeyError-ing when a call site forgets the flag (CLI repro)."""
    from safetensors.numpy import save_file

    from cake_tpu.parallel.mesh import MeshPlan
    from cake_tpu.utils.sharded_load import load_llama_params_on_mesh
    from cake_tpu.utils.weights import _LAYER_MAP

    cfg = tiny(model_type="gemma", hidden_act="gelu_tanh",
               rms_norm_offset=True, embed_scale=True, head_dim=8,
               max_seq_len=64, tie_word_embeddings=True)
    p = llama.init_params(cfg, jax.random.PRNGKey(2))
    tensors = {
        "model.embed_tokens.weight": np.asarray(p["embed"], np.float32),
        "model.norm.weight": np.asarray(p["norm_f"], np.float32),
    }
    for ours, (suffix, transpose) in _LAYER_MAP.items():
        st = np.asarray(p["layers"][ours], np.float32)
        for i in range(cfg.num_hidden_layers):
            w = st[i]
            tensors[f"model.layers.{i}.{suffix}"] = np.ascontiguousarray(
                w.T if transpose else w)
    save_file(tensors, tmp_path / "model.safetensors")
    (tmp_path / "model.safetensors.index.json").write_text(
        __import__("json").dumps({"metadata": {"total_size": 0},
                                  "weight_map": {k: "model.safetensors"
                                                 for k in tensors}}))

    # the flag is NOT passed: detection must kick in on both loaders
    host = load_llama_params(tmp_path, cfg.num_hidden_layers,
                             dtype="float32")
    np.testing.assert_array_equal(np.asarray(host["lm_head"]),
                                  np.asarray(host["embed"]).T)
    plan = MeshPlan.build(cfg, num_stages=2, tp=2)
    mesh_p = load_llama_params_on_mesh(tmp_path, cfg, plan.mesh)
    # head_dim != hidden//heads flows through the mesh loader's shapes
    assert mesh_p["layers"]["wq"].shape == (
        cfg.num_hidden_layers, cfg.hidden_size,
        cfg.num_attention_heads * 8)
    np.testing.assert_array_equal(np.asarray(mesh_p["lm_head"]),
                                  np.asarray(host["lm_head"]))


def test_gemma_distributed_worker_parity():
    """The TCP master/worker path must apply the Gemma embed scaling too
    (review repro: the master's raw embed lookup skipped it)."""
    from cake_tpu.parallel.topology import Topology
    from cake_tpu.runtime.master import DistributedGenerator, build_runners
    from cake_tpu.runtime.worker import Worker
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.generator import LlamaGenerator

    cfg = tiny(model_type="gemma", hidden_act="gelu_tanh",
               rms_norm_offset=True, embed_scale=True, head_dim=8,
               max_seq_len=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(4))

    def loader(lo, hi):
        return jax.tree.map(lambda a: a[lo:hi], params["layers"])

    w = Worker("w", cfg,
               Topology.from_dict({"w": {"layers": ["model.layers.2-3"]}}),
               loader, address="127.0.0.1:0", max_seq=cfg.max_seq_len)
    w.serve_in_background()
    try:
        topo = Topology.from_dict({
            "w": {"host": f"127.0.0.1:{w.port}",
                  "layers": ["model.layers.2-3"]},
        })
        settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
        runners = build_runners(cfg, topo, loader)
        head = {k: params[k] for k in ("embed", "norm_f", "lm_head")}
        g = DistributedGenerator(cfg, head, runners, settings=settings)
        g.set_prompt([5, 9, 2])
        got = [g.next_token(i).id for i in range(6)]
        ref = LlamaGenerator(cfg, params, settings=settings)
        ref.set_prompt([5, 9, 2])
        assert got == [ref.next_token(i).id for i in range(6)]
        g.close()
    finally:
        w.shutdown()


def test_prequantized_untied_head_not_falsely_tied(tmp_path):
    """Pre-quantized untied checkpoints store the head as
    lm_head.weight.q8 — the tied-head probe must count that as a stored
    head (review repro: it falsely tied and served embedding logits)."""
    from cake_tpu.ops.quant import quantize_params
    from cake_tpu.tools.quantize_model import quantize_checkpoint

    cfg = tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(6))
    save_llama_params(params, tmp_path / "src", cfg.num_hidden_layers)
    out = quantize_checkpoint(tmp_path / "src", tmp_path / "q8", bits=8)
    loaded = load_llama_params(out, cfg.num_hidden_layers, dtype="float32",
                               quantize="int8")
    want = quantize_params(params, bits=8)
    np.testing.assert_array_equal(np.asarray(loaded["lm_head"].q),
                                  np.asarray(want["lm_head"].q))


def test_mistral_serving_batch_generator_parity():
    """Sliding-window family through the multi-stream serving plane
    (per-row frontiers use the windowed per-row XLA mask): every stream
    reproduces its solo run token for token."""
    from cake_tpu.runtime.batch_generator import BatchGenerator
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.generator import LlamaGenerator

    cfg = tiny(model_type="mistral", sliding_window=8, max_seq_len=64)
    params = llama.init_params(cfg, jax.random.PRNGKey(9))
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    prompts = [[5, 9, 2, 11, 4, 3, 8, 7, 1, 2], [3, 1, 4, 1], [7, 7, 2]]

    solo = []
    for p in prompts:
        g = LlamaGenerator(cfg, params, settings=settings)
        g.set_prompt(p)
        solo.append([g.next_token(i).id for i in range(12)])

    bg = BatchGenerator(cfg, params, settings=settings, num_stages=2,
                        block_size=2)
    bg.set_prompts(prompts)
    outs = bg.generate(12)
    assert [list(o) for o in outs] == solo


def test_mistral_int8_kv_window_composition():
    """Sliding window x int8 KV cache, with real oracles:

    - a window WIDER than everything the stream ever attends must be a
      no-op — stream identical to the unwindowed config on the same
      quantized cache (the sharp equality: the windowed code path
      degenerates exactly);
    - the narrow window must actually change the stream (the mask is not
      silently dropped on the dequant path)."""
    import dataclasses

    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.generator import LlamaGenerator

    prompt = [5, 9, 2, 11, 4, 3, 8, 7, 1, 2]
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)

    def stream(cfg):
        g = LlamaGenerator(cfg, params, settings=settings, kv_quant="int8")
        g.set_prompt(prompt)
        return [g.next_token(i).id for i in range(10)]

    base = tiny(model_type="mistral", sliding_window=None, max_seq_len=64)
    params = llama.init_params(base, jax.random.PRNGKey(10))
    unwindowed = stream(base)
    wide = stream(dataclasses.replace(base, sliding_window=1000))
    assert wide == unwindowed  # window >= history: exact degeneration
    narrow = stream(dataclasses.replace(base, sliding_window=4))
    assert narrow != unwindowed  # the mask genuinely applies


# -- a family is declared once (models/families.py) ----------------------------

FAMILY_PREDICATES = {"latent", "state_space", "windowed", "short_conv",
                     "recurrent", "recurrent_mixer"}
# The only reads of ``.segmented`` outside cake_tpu/models/: the three
# places that branch on the LAYOUT of ``params["layers"]`` (one bare stack
# or a dict of stacks), which is ROADMAP D4's second half.
SEGMENTED_SITES = {
    "kvpool/pool.py": "the page pool allocates rows for every layer of one "
                      "bare stack and refuses a dict of stacks",
    "utils/sharded_load.py": "a dict of stacks has its own loader",
    "utils/memory.py": "a dict of stacks has its own budget arithmetic",
}


def test_no_module_outside_models_asks_which_family_this_is():
    """Outside ``cake_tpu/models/`` nothing reads a family predicate of
    ``LlamaConfig``: what is wired is asked of ``config.family``
    (``models/families.py``), what a cache holds of ``config.cache_plan``.
    ``.segmented`` is read at its three layout sites and nowhere else."""
    import ast
    from pathlib import Path

    root = Path(llama.__file__).resolve().parent.parent
    found, segmented = [], set()
    for path in sorted(root.rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        if rel.startswith("models/"):
            continue
        for node in ast.walk(ast.parse(path.read_text())):
            if not (isinstance(node, ast.Attribute)
                    and isinstance(node.ctx, ast.Load)):
                continue
            if node.attr in FAMILY_PREDICATES:
                found.append(f"{rel}:{node.lineno} .{node.attr}")
            elif node.attr == "segmented":
                segmented.add(rel)
    assert found == []
    assert segmented == set(SEGMENTED_SITES)


def _family_fixture(name):
    from cake_tpu.models import config

    return {"dense": tiny, "sparse": tiny_moe, "latent": config.tiny_mla_moe,
            "hybrid": config.tiny_kda_hybrid, "state_space": config.tiny_jamba,
            "windowed": config.tiny_exaone_moe,
            "short_conv": config.tiny_lfm2_moe,
            "gated_delta": config.tiny_qwen3_next,
            "sparse_latent": config.tiny_glm_dsa}[name]()


# What each family is refused and accepted at PR 45's tree (commit
# 545f5a9), written out: the mesh axes ``validate_shardable`` refuses at
# size 2 (the dense decoder's ``ep`` by the rule that ep needs experts),
# whether ``hbm_budget`` prices quantized linears, whether ``init_cache``
# allocates an int8 cache, and what the loader does with ``--quantize``
# (int4 beside experts is ``quant.reject_int4_moe``'s, whatever the family).
WIRED_AT_PR45 = {
    "dense": (("ep",), True, True, {"int8": True, "int4": True}),
    "sparse": ((), True, True, {"int8": True, "int4": False}),
    "latent": (("stages", "tp", "sp"), True, False,
               {"int8": True, "int4": False}),
    "hybrid": (("stages", "tp", "sp"), True, False,
               {"int8": True, "int4": False}),
    "state_space": (("stages", "tp", "sp", "ep"), False, False,
                    {"int8": False, "int4": False}),
    "windowed": (("stages", "tp", "sp"), False, False,
                 {"int8": False, "int4": False}),
    "short_conv": (("stages", "tp", "sp", "ep"), False, False,
                   {"int8": False, "int4": False}),
    # (PR 57's family, at the tree that brought it)
    "gated_delta": (("stages", "tp", "sp"), False, False,
                    {"int8": False, "int4": False}),
    # (PR 61: the latent family under a learned sparse attention is
    # refused and accepted what the latent family is)
    "sparse_latent": (("stages", "tp", "sp"), True, False,
                      {"int8": True, "int4": False}),
}


@pytest.mark.parametrize("name", list(WIRED_AT_PR45))
def test_a_family_is_refused_and_accepted_what_it_was(name, tmp_path):
    """The mesh, the budget, the cache and the loader refuse and accept
    for each family exactly what they did before its record declared it,
    with the exception types they had."""
    import json

    from cake_tpu.parallel.mesh import make_mesh, validate_shardable
    from cake_tpu.utils.memory import hbm_budget
    from cake_tpu.utils.sharded_load import load_llama_params_on_mesh

    cfg = _family_fixture(name)
    no_axes, budget, cache, tiers = WIRED_AT_PR45[name]

    def accepted(call, error):
        try:
            call()
        except error:
            return False
        return True

    sizes = {"stages": (2, 1, 1, 1), "tp": (1, 2, 1, 1),
             "sp": (1, 1, 2, 1), "ep": (1, 1, 1, 2)}
    assert tuple(a for a, s in sizes.items() if not accepted(
        lambda: validate_shardable(cfg, *s), ValueError)) == no_axes
    for quant in ("int8", "int4"):
        assert accepted(lambda: hbm_budget(cfg, quant=quant),
                        ValueError) == budget
    assert accepted(lambda: init_cache(cfg, batch=1, max_seq=32,
                                       quant="int8"), ValueError) == cache
    save_llama_params(llama.init_params(cfg, jax.random.PRNGKey(0)),
                      tmp_path, cfg.num_hidden_layers, config=cfg)
    (tmp_path / "config.json").write_text(json.dumps(cfg.to_hf_dict()))
    for quant, loads in tiers.items():
        assert accepted(lambda: load_llama_params_on_mesh(
            tmp_path, cfg, make_mesh(), quantize=quant),
            NotImplementedError) == loads


# -- a learned sparse attention's indexer (PR 61) --------------------------------------

def _glm_file(**changes) -> dict:
    from cake_tpu.models.config import tiny_glm_dsa

    return {**tiny_glm_dsa().to_hf_dict(), "max_position_embeddings": 128,
            **changes}


@pytest.mark.parametrize("make,says", [
    (lambda: tiny(index_topk=8, index_n_heads=4, index_head_dim=16),
     "indexer over the cache.*latent-attention family alone"),
    (lambda: LlamaConfig.from_hf_dict(
        {**tiny().to_hf_dict(), "index_topk": 8, "index_n_heads": 4,
         "index_head_dim": 16}),
     "indexer over the cache.*latent-attention family alone"),
    (lambda: __import__("cake_tpu.models.config", fromlist=["x"])
     .tiny_kda_hybrid(index_topk=8, index_n_heads=4, index_head_dim=16),
     "indexer over the cache.*not for the layers of model_type "
     "'bailing_hybrid'"),
    (lambda: LlamaConfig.from_hf_dict(_glm_file(index_topk=0)),
     "index_topk 0 is no count of rows a sparse attention's indexer"),
    (lambda: LlamaConfig.from_hf_dict(_glm_file(index_topk=129)),
     "index_topk 129 is no count of rows.*max_position_embeddings 128"),
    (lambda: LlamaConfig.from_hf_dict(
        _glm_file(indexer_rope_interleave=False)),
     "indexer_rope_interleave false.*is not wired"),
    (lambda: LlamaConfig.from_hf_dict(_glm_file(
        rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"})),
     "rope type 'yarn' is not wired"),
    (lambda: LlamaConfig(**{**_glm_fields(), "index_topk": 0,
                            "index_n_heads": 0, "index_head_dim": 0}),
     "index_topk 0 names no indexer"),
    (lambda: LlamaConfig(**{**_glm_fields(), "index_head_dim": 4}),
     "narrower than the 8 channels the indexer rotates"),
    (lambda: LlamaConfig(**{**_glm_fields(), "q_lora_rank": None}),
     "indexer .index_topk 8. needs index_n_heads.*over a query latent"),
    (lambda: LlamaConfig(**{**_glm_fields(), "hc_mult": 4}),
     "indexer .index_topk > 0. is wired over plain latent attention alone"),
], ids=["bare-stack-fields", "bare-stack-file", "another-family",
        "topk-zero", "topk-past-the-window", "half-rotation", "rope-scaling",
        "no-indexer", "narrow-head", "no-query-latent", "wide-stream"])
def test_an_indexer_is_refused_where_nothing_computes_it(make, says):
    """A sparse attention's keys in any other family, an ``index_topk`` of
    0 or above ``max_position_embeddings``, a rotation or a width nothing
    here computes: each fails where the configuration is made, with a
    message that names the mechanism."""
    with pytest.raises(ValueError, match=says):
        make()


def _glm_fields() -> dict:
    import dataclasses

    from cake_tpu.models.config import tiny_glm_dsa

    return dataclasses.asdict(tiny_glm_dsa())
