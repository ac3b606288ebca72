"""EvaByte's EVA attention through the serving path (the second file of
``tests/test_evabyte.py``'s account; shared: ``tests/evabyte_kit.py``):
``BatchGenerator``'s block decode and admissions (padded buckets, rows of
unequal length, streams that meet a window reset inside a block), the
single-stream generator, the counters and gauges, both loaders with the
folds applied once, and every refusal, each against
the plain reference or with the message it owes.
"""

from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from cake_tpu.models import families
from cake_tpu.obs import catalog, metrics
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.utils.weights import save_llama_params

from evabyte_kit import (  # noqa: F401
    CFG, GREEDY, PROMPTS, _engine, _is_the_references_argmax, params,
    tensors,
)
from glm_dsa_kit import _run

EVA_SERIES = (
    "attn.eva_window_rows_live", "attn.eva_summary_rows_visible",
    "attn.eva_rows_read", "attn.eva_decode_calls", "eva.window_resets",
    "eva.chunks_summarised.step", "eva.chunks_summarised.admit")


def _counts():
    reg = metrics.registry()
    return {name: reg.counter(name).value for name in EVA_SERIES}


# -- the engine ------------------------------------------------------------------------

def test_batch_generator_streams_match_reference(params, tensors):
    """Four streams of 5, 37, 30 and 61 rows through BatchGenerator:
    admissions padded to buckets of 16, 64, 32 and 64 rows (a bucket's
    padding enters neither ring nor summary), per-row positions, block
    decode of 4 steps; the third stream's window resets two tokens into
    its answer and the fourth's three tokens in, inside a block. Each
    stream's 14 tokens are the reference's argmax, and the counters say
    what a step attended."""
    before = _counts()
    bg = _engine(params, PROMPTS[:4])
    outs = bg.generate(14)
    for prompt, out in zip(PROMPTS[:4], outs):
        _is_the_references_argmax(tensors, prompt, list(out)[:14])
    d = {k: v - before[k] for k, v in _counts().items()}
    calls = d["attn.eva_decode_calls"]
    assert calls > 0 and calls % 3 == 0  # three layers a step
    assert d["eva.chunks_summarised.step"] == 4 * calls  # a stream a call
    # the third stream crosses position 32 and the fourth 64 (a layer each)
    assert d["eva.window_resets"] == 2 * 3
    assert d["attn.eva_summary_rows_visible"] > 0
    # no kernel at a ring of 32 rows: both buffers whole, a stream a call
    assert d["attn.eva_rows_read"] == 4 * calls * (32 + 32)
    assert (d["attn.eva_window_rows_live"]
            + d["attn.eva_summary_rows_visible"]) < d["attn.eva_rows_read"]
    reg = metrics.registry()
    assert reg.gauge("cache.token_bytes").value == CFG.cache_token_bytes
    assert reg.gauge("cache.eva_window_rows").value == 32
    assert reg.gauge("cache.eva_summary_rows").value == 32
    assert reg.gauge("cache.row_bytes").value == 0  # no row a position
    assert reg.gauge("attn.eva_decode_kernel").value == 0
    for series in EVA_SERIES + ("cache.eva_window_rows",
                                "cache.eva_summary_rows",
                                "attn.eva_decode_kernel"):
        assert catalog.is_declared(series), series


def test_admissions_in_buckets_and_a_several_row_launch(params, tensors,
                                                        monkeypatch):
    """An admission among live streams in a padded bucket (30 rows in 32)
    into the slot a longer stream left (its ring's and plane's rows past
    the new frontiers are the former stream's: nobody's to read), then two
    arrivals that wait together and ride ONE prefill program of two rows of
    unequal length (37 and 12 in 64, the shorter's padding a whole window
    and more): each stream's tokens are the single-stream reference's, and
    the counter says how many chunks the launches summarised."""
    from cake_tpu.runtime import batch_generator as engine

    monkeypatch.setattr(engine, "GROUP_SHAPES", ((2, 64),))
    launches = metrics.registry().counter("engine.admit_launches")
    bg = _engine(params, [PROMPTS[3], PROMPTS[0], [4, 4, 4], [4, 4, 5]],
                 ids=[10, 11, 90, 91])
    bg.warm_admission(40)
    before, at = _counts(), launches.value
    events = {
        2: lambda e: (e.finish(10), e.enqueue(PROMPTS[2], 12)),
        8: lambda e: (e.finish(91), e.finish(90),
                      e.enqueue(PROMPTS[1], 13),
                      e.enqueue(PROMPTS[4], 14)),
    }
    got = _run(bg, events, steps=36)
    assert launches.value - at == 2  # 12 alone, 13 and 14 together
    # three layers x (a 32-row bucket + two rows of a 64-row one) in chunks
    # of 4
    assert (_counts()["eva.chunks_summarised.admit"]
            - before["eva.chunks_summarised.admit"]) == 3 * (8 + 2 * 16)
    for sid, prompt in ((11, PROMPTS[0]), (12, PROMPTS[2]),
                        (13, PROMPTS[1]), (14, PROMPTS[4])):
        assert len(got[sid]) >= 10, sid
        _is_the_references_argmax(tensors, prompt, got[sid][:10])


@pytest.mark.parametrize("rows,bucket", [(32, 32), (64, 64), (96, 128)])
def test_a_prompt_of_whole_windows_in_its_bucket(params, tensors, rows,
                                                 bucket):
    """A prompt of exactly one, two and three windows, the last padded to
    a bucket of four (the cell's second probe: 6144 rows, three windows,
    in a bucket of 8192): its first token comes from the admission's last
    row of a FULL window, whose chunk is the bucket's last true one, and
    its first step follows a reset (ring row 0, every chunk of the prompt
    visible). Admitted among live streams and, again, as the batch's own
    prompt: each answer is the reference's argmax to ``TIGHT``."""
    prompt = [int(t) for t in np.random.default_rng(rows).integers(
        3, 250, rows)]
    bg = _engine(params, [PROMPTS[0], [4, 4, 4]], ids=[10, 90])
    assert bg._admission_chunk_for(rows) == bucket
    got = _run(bg, {1: lambda e: (e.finish(90), e.enqueue(prompt, 11))},
               steps=20)
    assert len(got[11]) >= 10
    _is_the_references_argmax(tensors, prompt, got[11][:10])
    own = [int(t) for t in _engine(params, [prompt, PROMPTS[0]]).generate(
        10)[0][:10]]
    assert own == got[11][:10]


def test_the_single_stream_generator_gives_the_engines_ids(params, tensors):
    """``runtime/generator.py`` (a bucketed prefill whose padding enters
    neither ring nor summary, block decode) gives the engine's ids, which
    are the reference's argmax (ROADMAP D5: the single-stream path is a
    path of its own): a 61-token prompt in a 64-row bucket, whose padding
    would otherwise fill the last chunk of the second window."""
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


def test_a_stream_is_retired_when_its_capacity_is_full(params):
    """The capacity is the plan's (128 positions: 32 summary rows of 4),
    with no row buffer to read it off: a stream that reaches it ends with
    the window-full reason and the engine goes on."""
    prompt = [int(t) for t in np.random.default_rng(5).integers(3, 250, 120)]
    bg = _engine(params, [prompt, PROMPTS[0]])
    outs = bg.generate(16)
    assert len(outs[0]) == 128 - 120  # positions 120..127, then full
    assert len(outs[1]) == 16


# -- the loaders ---------------------------------------------------------------------------

def test_both_loaders_read_the_names_and_fold_once(params, tmp_path):
    """A written checkpoint stores the norms as ``w - 1``
    (``norm_add_unit_offset``), the two learned vectors a head as ``[1,
    heads, 1, 1, head_dim]`` and a head of two blocks; both loaders give
    the program's tensors back, the one added once."""
    from safetensors.numpy import load_file

    from cake_tpu.models.config import LlamaConfig
    from cake_tpu.parallel.mesh import make_mesh
    from cake_tpu.utils.sharded_load import load_llama_params_on_mesh
    from cake_tpu.utils.weights import load_llama_params

    save_llama_params(params, tmp_path, config=CFG)
    (tmp_path / "config.json").write_text(json.dumps(CFG.to_hf_dict()))
    cfg = LlamaConfig.from_hf_json(tmp_path / "config.json", dtype="float32",
                                   max_seq_len=128, eos_token_id=-1)
    assert cfg == CFG and cfg.family is families.EVA
    index = json.loads((tmp_path / "model.safetensors.index.json")
                       .read_text())["weight_map"]
    stored = load_file(tmp_path / index["lm_head.weight"])
    layer = "model.layers.1."
    assert stored[layer + "self_attn.adaptive_phi"].shape == (1, 4, 1, 1, 16)
    assert stored[layer + "self_attn.adaptive_mu_k"].shape == (1, 4, 1, 1, 16)
    assert stored["lm_head.weight"].shape == (512, 64)
    for name, ours in ((layer + "input_layernorm.weight",
                        params["layers"]["dense"]["attn_norm"][1]),
                       ("model.norm.weight", params["norm_f"])):
        np.testing.assert_allclose(stored[name] + 1.0, np.asarray(ours),
                                   rtol=1e-6)
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
    (dict(kv_layout="paged"), "no ring row, no summary row"),
    (dict(kv_quant="int8"), "int8 cache is not wired for EVA attention"),
    (dict(spec_k=2), "ring row and a summary row"),
    (dict(admit_chunk=16), "admit_chunk is not wired for EVA attention"),
], ids=["paged", "int8-cache", "speculation", "admit-chunk"])
def test_the_engine_refuses_what_a_summary_plane_cannot_do(params, kw, says):
    with pytest.raises(ValueError, match=says):
        _engine(params, [[5, 9, 2]], **kw)


def test_the_engine_refuses_a_capacity_that_is_no_whole_window(params):
    from cake_tpu.runtime.batch_generator import BatchGenerator

    with pytest.raises(ValueError, match="whole number of windows"):
        BatchGenerator(CFG, params, settings=SamplerSettings(**GREEDY),
                       max_seq=80)


def test_prefix_reuse_is_off(params, tensors):
    """A summary row has no prefix to share: the store is off, and a
    second prompt that opens like the first is prefilled whole and still
    the reference's."""
    bg = _engine(params, [PROMPTS[1]], prefix_cache_entries=4)
    assert bg._prefix_entries == 0
    _is_the_references_argmax(tensors, PROMPTS[1],
                              list(bg.generate(6)[0])[:6])


@pytest.mark.parametrize("axis,sizes", [
    ("stages", (2, 1, 1, 1)), ("tp", (1, 2, 1, 1)), ("sp", (1, 1, 2, 1)),
    ("ep", (1, 1, 1, 2))])
def test_the_mesh_refuses_to_split_it(axis, sizes):
    from cake_tpu.parallel.mesh import validate_shardable

    with pytest.raises(ValueError, match="no row a position"):
        validate_shardable(CFG, *sizes)
    validate_shardable(CFG, 1, 1, 1, 1)


def test_int8_linears_are_refused_with_the_familys_sentence(params,
                                                            tmp_path):
    from cake_tpu.parallel.mesh import make_mesh
    from cake_tpu.utils.sharded_load import load_llama_params_on_mesh

    save_llama_params(params, tmp_path, config=CFG)
    with pytest.raises(NotImplementedError,
                       match="ahead of a learned summary"):
        load_llama_params_on_mesh(tmp_path, CFG, make_mesh(),
                                  quantize="int8")
