"""LongCat-Flash's double layer through the serving path (the second file
of ``tests/test_longcat_flash.py``'s account; shared:
``tests/longcat_flash_kit.py``): ``BatchGenerator``'s block decode and
admissions (a padded bucket, rows of unequal length, a blocked admission),
the single-stream generator, the counters and gauges, both loaders with
the two ``Fold``s applied once, and every refusal, each against the plain
reference or with the message it owes.
"""

from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from cake_tpu.models import families
from cake_tpu.obs import catalog, metrics
from cake_tpu.ops import mla
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.utils.weights import save_llama_params

from longcat_flash_kit import (  # noqa: F401
    CFG, GREEDY, PROMPTS, _engine, _is_the_references_argmax, params,
    tensors,
)


def _count(name):
    return metrics.registry().counter(name)


# -- the engine ------------------------------------------------------------------------

def test_batch_generator_streams_match_reference(params, tensors):
    """Three streams of 5, 37 and 8 rows through BatchGenerator: a bucketed
    batch prefill of rows of unequal length (a padding row's routed result
    is zero and its identity part nobody's to read), per-row positions,
    block decode over two planes a layer; each stream's tokens are the
    reference's argmax. The counters say how many pairs cost no expert
    and that rows are counted in planes."""
    reg = metrics.registry()
    routed, zero = _count("moe.routed_pairs"), _count("moe.zero_pairs")
    local = _count("moe.local_pairs")
    reserved = _count("attn.kv_blocks_reserved")
    before = routed.value, zero.value, local.value, reserved.value
    bg = _engine(params, PROMPTS[:3])
    outs = bg.generate(13)
    for prompt, out in zip(PROMPTS[:3], outs):
        _is_the_references_argmax(tensors, prompt, list(out)[:13])
    routed_d, zero_d, local_d, reserved_d = (
        c.value - b for c, b in zip((routed, zero, local, reserved), before))
    # every routed pair fell on a held expert or on a zero-compute output
    # (all 16 experts are held), about a third on the latter (8 of 24)
    assert routed_d > 0 and zero_d + local_d == routed_d
    assert 0.15 < zero_d / routed_d < 0.55
    assert reg.gauge("model.planes_a_layer").value == 2
    assert reserved_d % 2 == 0 and reserved_d > 0  # planes, not layers
    assert reg.gauge("cache.token_bytes").value == CFG.cache_token_bytes == (
        6 * 24 * 4)
    for series in ("moe.zero_pairs", "model.planes_a_layer",
                   "attn.admit_blocked", "attn.admit_blocked_min_rows"):
        assert catalog.is_declared(series), series
    assert catalog.kind_of("moe.zero_pairs") == catalog.COUNTER


def test_a_blocked_admission_through_the_engine(params, tensors,
                                                monkeypatch):
    """A 60-token prompt admitted through a 64-row bucket that takes the
    blocked form (strips of 16 query rows), beside a short one that does
    not: both streams are the reference's, and the gauges say which form a
    program took and from how many rows."""
    monkeypatch.setattr(mla, "LATENT_ADMIT_BLOCK_MIN_T", 64)
    monkeypatch.setattr(mla, "ADMIT_STRIP", 16)
    reg = metrics.registry()
    bg = _engine(params, [PROMPTS[4], PROMPTS[0]])
    outs = bg.generate(6)
    for prompt, out in zip((PROMPTS[4], PROMPTS[0]), outs):
        _is_the_references_argmax(tensors, prompt, list(out)[:6])
    # (the fewest rows any program of this process took it at: another
    # test's lower floor may have left a smaller number)
    assert 0 < reg.gauge("attn.admit_blocked_min_rows").value <= 64


def test_the_single_stream_generator_gives_the_engines_ids(params, tensors):
    """``runtime/generator.py`` (a bucketed prefill whose padding lies past
    the frontier, block decode) gives the engine's ids, which are the
    reference's argmax (ROADMAP D5: the single-stream path is a path of
    its own; here a padding row's identity part is nobody's to read)."""
    from cake_tpu.runtime.generator import LlamaGenerator

    prompt = PROMPTS[3]
    gen = LlamaGenerator(CFG, params, settings=SamplerSettings(**GREEDY),
                         max_seq=128, block_size=4)
    gen.set_prompt(prompt)
    alone = [gen.next_token(i).id for i in range(12)]
    bg = _engine(params, [prompt, PROMPTS[0]])
    served = [int(t) for t in bg.generate(12)[0][:12]]
    assert alone == served
    _is_the_references_argmax(tensors, prompt, alone)


def test_the_ep_axis_splits_the_held_experts(params):
    """Under a real ep axis the held experts are split once more, the
    identity part is every rank's alike and added once after the psum: the
    mesh stream is the single-device one."""
    from cake_tpu.parallel.mesh import MeshPlan

    prompts = [[5, 9, 2, 11], [int(t) for t in PROMPTS[1][:20]]]
    outs = []
    for ep in (1, 2):
        plan = MeshPlan.build(CFG, ep=ep, devices=jax.devices()[:ep])
        bg = _engine(params, prompts, plan=plan)
        outs.append([list(o) for o in bg.generate(8)])
    assert outs[0] == outs[1]


# -- the loaders ---------------------------------------------------------------------------

def test_both_loaders_read_the_doubled_names_and_fold_once(params, tmp_path):
    """A written checkpoint stores a layer's tensors under the doubled
    names (``self_attn.{0,1}.*``, ``mlps.{0,1}.*``, the four norms, the
    router's classifier and bias, the experts by global id) with the two
    latent norms UNSCALED (the ``Fold``'s inverse); both loaders give the
    program's tensors back, the factors folded in once."""
    from safetensors.numpy import load_file

    from cake_tpu.models.config import LlamaConfig
    from cake_tpu.parallel.mesh import make_mesh
    from cake_tpu.utils.sharded_load import load_llama_params_on_mesh
    from cake_tpu.utils.weights import load_llama_params

    save_llama_params(params, tmp_path, config=CFG)
    (tmp_path / "config.json").write_text(json.dumps(CFG.to_hf_dict()))
    cfg = LlamaConfig.from_hf_json(tmp_path / "config.json", dtype="float32",
                                   max_seq_len=128, eos_token_id=-1)
    assert cfg == CFG and cfg.family is families.SHORTCUT
    index = json.loads((tmp_path / "model.safetensors.index.json")
                       .read_text())["weight_map"]
    for name in ("self_attn.0.q_a_proj.weight", "self_attn.1.kv_b_proj.weight",
                 "mlps.0.gate_proj.weight", "mlps.1.down_proj.weight",
                 "input_layernorm.1.weight",
                 "post_attention_layernorm.0.weight",
                 "mlp.router.classifier.weight",
                 "mlp.router.e_score_correction_bias",
                 "mlp.experts.15.up_proj.weight"):
        assert f"model.layers.2.{name}" in index, name
    name = "model.layers.1.self_attn.1.kv_a_layernorm.weight"
    stored = load_file(tmp_path / index[name])[name]
    ours = np.asarray(params["layers"]["moe"]["s1_kv_norm"][1])
    np.testing.assert_allclose(stored * 2.0, ours, rtol=1e-6)  # (64/16)^0.5
    name = "model.layers.0.self_attn.0.q_a_layernorm.weight"
    stored = load_file(tmp_path / index[name])[name]
    np.testing.assert_allclose(
        stored * (64 / 24) ** 0.5,
        np.asarray(params["layers"]["moe"]["s0_q_norm"][0]), rtol=1e-6)
    on_mesh = load_llama_params_on_mesh(tmp_path, cfg, make_mesh())
    host = load_llama_params(tmp_path, cfg.num_hidden_layers,
                             dtype="float32")
    for a, b in zip(jax.tree.leaves(on_mesh), jax.tree.leaves(host)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    loaded = dict(jax.tree_util.tree_flatten_with_path(on_mesh)[0])
    for path, leaf in flat:
        np.testing.assert_allclose(np.asarray(loaded[path]),
                                   np.asarray(leaf), rtol=2e-6, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))


# -- the refusals, a case each -----------------------------------------------------------

@pytest.mark.parametrize("kw,says", [
    (dict(kv_layout="paged"), "no latent row"),
    (dict(kv_quant="int8"), "int8 cache is not wired for latent"),
], ids=["paged", "int8-cache"])
def test_the_engine_refuses_what_two_latent_planes_cannot_hold(params, kw,
                                                              says):
    with pytest.raises(ValueError, match=says):
        _engine(params, [[5, 9, 2]], **kw)


@pytest.mark.parametrize("axis,sizes", [
    ("stages", (2, 1, 1, 1)), ("tp", (1, 2, 1, 1)), ("sp", (1, 1, 2, 1))])
def test_the_mesh_refuses_to_split_it(axis, sizes):
    """A double layer under stages, ``tp`` or ``sp`` is refused with the
    family's sentence; ``ep`` is accepted."""
    from cake_tpu.parallel.mesh import validate_shardable

    with pytest.raises(ValueError, match="two cache planes a layer"):
        validate_shardable(CFG, *sizes)
    validate_shardable(CFG, 1, 1, 1, 2)


def test_int8_linears_are_refused_with_the_familys_sentence(params,
                                                            tmp_path):
    from cake_tpu.parallel.mesh import make_mesh
    from cake_tpu.utils.sharded_load import load_llama_params_on_mesh

    save_llama_params(params, tmp_path, config=CFG)
    with pytest.raises(NotImplementedError,
                       match="folded mla_scale factor"):
        load_llama_params_on_mesh(tmp_path, CFG, make_mesh(),
                                  quantize="int8")


def test_a_cache_holds_two_planes_a_layer():
    cache = init_cache(CFG, batch=2, max_seq=32)
    assert cache.k.shape == (6, 2, 1, 32, 16)
    assert cache.v.shape == (6, 2, 1, 32, 8)


# -- the sweep tool ------------------------------------------------------------------------

def test_latent_admit_sweep_rows_at_tiny_shapes(capsys):
    """``tools/flash_sweep.py --only latent-admit`` on the CPU at a tiny
    shape: a row with the three forms' times (a CPU's: not read) and the
    forms' agreement on one set of operands."""
    from cake_tpu.tools import flash_sweep

    rows: list = []
    flash_sweep.latent_admit_rows(rows, shapes=((64, 2),), dn=16, dr=8,
                                  dv=16)
    (row,) = rows
    assert row["path"] == "latent_admit" and row["auto_impl"] == "whole"
    assert all(f"{form}_ms" in row for form in ("whole", "strip", "flash"))
    assert row["whole_max_abs_diff_from_strip"] < 0.05
    assert row["flash_max_abs_diff_from_strip"] < 0.05
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == row
