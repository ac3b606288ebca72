"""GLM-5's learned sparse attention through the serving path (the second
file of ``tests/test_glm_dsa.py``'s account; shared:
``tests/glm_dsa_kit.py``): ``BatchGenerator``'s block decode and
admissions (a padded bucket, rows of unequal length, a several-row
launch), a reused slot, the single-stream generator, the counters and
gauges, the loaders, and every refusal, each against the plain reference
or with the message it owes.
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.obs import metrics
from cake_tpu.ops import dsa
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.utils.weights import save_llama_params

from glm_dsa_kit import (  # noqa: F401
    CFG, GREEDY, PROMPTS, TOPK, _engine, _is_the_references_argmax, _run,
    params, tensors,
)


def _count(name):
    return metrics.registry().counter(name)


# -- the engine ------------------------------------------------------------------------

def test_batch_generator_streams_match_reference(params, tensors):
    """Three streams of 5, 37 and 8 rows (under, over and at
    ``index_topk``) through BatchGenerator: a bucketed batch prefill of
    rows of unequal length (each row's own mask; the shorter rows' padding
    lies past their frontiers), per-row positions, block decode with a
    choice a stream a step; each stream's tokens are the reference's
    argmax. The gauges and counters say what the cache holds and what a
    step scored and attended."""
    reg = metrics.registry()
    live, chosen = _count("dsa.rows_live"), _count("dsa.rows_selected")
    read = _count("dsa.rows_read")
    before = live.value, chosen.value, read.value
    bg = _engine(params, PROMPTS[:3])
    outs = bg.generate(13)
    for prompt, out in zip(PROMPTS[:3], outs):
        _is_the_references_argmax(tensors, prompt, list(out)[:13])
    assert reg.gauge("dsa.index_topk").value == TOPK
    assert reg.gauge("cache.index_row_bytes").value == 16 * 4
    # the latent row [c | k_pe] padded to whole lane tiles (24 -> 128)
    assert reg.gauge("cache.row_bytes").value == 4 * 128
    # cache.token_bytes counts the index plane: 3 layers x (128 + 16) values
    assert reg.gauge("cache.token_bytes").value == 3 * 144 * 4
    assert CFG.cache_token_bytes == 3 * 144 * 4
    # decode steps in blocks of 4 from frontiers 5, 37, 8 (the first token
    # is the prefill's; the engine may have a block in flight past the 12
    # it handed out): step j scores pos + j rows and attends min(pos + j,
    # 8) of them, a layer
    def rows(steps, of):
        return 3 * sum(int(of(n + np.arange(1, steps + 1)).sum())
                       for n in (5, 37, 8))

    steps = next(j for j in (12, 16, 20)
                 if rows(j, lambda r: r) == live.value - before[0])
    assert chosen.value - before[1] == rows(
        steps, lambda r: np.minimum(r, TOPK))
    # the program traced the sweep (128 rows a stream lie under
    # SWEEP_MAX_ROWS), whose attention fetches whole blocks to a frontier:
    # here the one block a 128-row buffer is
    assert reg.gauge("dsa.attend_sweep").value == 1
    assert read.value - before[2] == rows(steps, lambda r: 0 * r + 128)
    assert dsa.rows_fetched(np.array([1, 1024, 1025]), 16384, 2048).tolist() \
        == [1024, 1024, 2048]
    assert dsa.rows_fetched(np.array([1, 5000]), 2 * dsa.SWEEP_MAX_ROWS,
                            2048).tolist() == [1, 2048]
    assert bg.stats()["tokens_emitted"] == 3 * 13


def test_admissions_in_buckets_and_a_several_row_launch(params, tensors,
                                                        monkeypatch):
    """An admission among live streams in a padded bucket (21 rows in 32),
    then two arrivals that wait together and ride ONE prefill program of
    two rows of unequal length (40 and 12 in 64): each stream's tokens are
    the single-stream reference's, and the launch counters say what the
    programs were handed and what of it was prompt."""
    from cake_tpu.runtime import batch_generator as engine

    monkeypatch.setattr(engine, "GROUP_SHAPES", ((2, 64),))
    launches = _count("engine.admit_launches")
    rows, true = _count("dsa.admit_rows"), _count("dsa.admit_rows_true")
    scored = _count("dsa.admit_pairs_scored")
    attended = _count("dsa.admit_pairs_attended")
    bg = _engine(params, [PROMPTS[1], PROMPTS[0], [4, 4, 4], [4, 4, 5]],
                 ids=[10, 11, 90, 91])
    bg.warm_admission(40)
    before = [c.value for c in (launches, rows, true, scored, attended)]
    events = {
        2: lambda e: (e.finish(90), e.enqueue(PROMPTS[3], 12)),
        8: lambda e: (e.finish(91), e.finish(11),
                      e.enqueue(PROMPTS[4][:40], 13),
                      e.enqueue(PROMPTS[5], 14)),
    }
    got = _run(bg, events, steps=36)
    after = [c.value for c in (launches, rows, true, scored, attended)]
    assert after[0] - before[0] == 2  # 12 alone, 13 and 14 together
    assert after[1] - before[1] == 32 + 2 * 64
    assert after[2] - before[2] == 21 + 40 + 12

    def pairs(n):
        k = min(n, TOPK)
        return n * (n + 1) // 2, k * (k + 1) // 2 + (n - k) * TOPK

    assert after[3] - before[3] == 3 * sum(pairs(n)[0] for n in (21, 40, 12))
    assert after[4] - before[4] == 3 * sum(pairs(n)[1] for n in (21, 40, 12))
    for sid, prompt in ((10, PROMPTS[1]), (12, PROMPTS[3]),
                        (13, PROMPTS[4][:40]), (14, PROMPTS[5])):
        assert len(got[sid]) >= 10, sid
        _is_the_references_argmax(tensors, prompt, got[sid][:10])


def test_a_reused_slot_and_a_buckets_padding_are_never_chosen(params,
                                                              tensors):
    """SLOT REUSE: a 12-row stream admitted (in a bucket of 16) into the
    slot a 60-row one left gives the reference's tokens. And what lies
    past a frontier is nobody's: with every row past each live stream's
    frontier made LOUD in both buffers (index keys and latent rows a
    thousand times their size: a former stream's rows, a bucket's
    padding), the streams still give the reference's tokens, so no such
    row was scored into a choice or attended."""
    long, short = PROMPTS[4], PROMPTS[5]
    bg = _engine(params, [long, PROMPTS[3]], ids=[1, 2])

    def poison(e):
        past = (jnp.arange(e.max_seq)[None, :]
                > jnp.asarray(e._decode_pos())[:, None])  # [B, S]
        loud = jax.random.normal(jax.random.PRNGKey(3), (e.max_seq,)) * 1e3

        def shout(buf):
            return jnp.where(past[None, :, None, :, None],
                             loud[None, None, None, :, None].astype(buf.dtype),
                             buf)

        e.drain()
        e.cache = dataclasses.replace(
            e.cache, k=shout(e.cache.k), index=shout(e.cache.index))

    got = _run(bg, {6: lambda e: (e.finish(1), e.enqueue(short, 3)),
                    12: poison}, steps=30)
    assert len(got[3]) >= 10
    _is_the_references_argmax(tensors, short, got[3][:10])
    _is_the_references_argmax(tensors, PROMPTS[3], got[2][:14])


def test_the_single_stream_generator_gives_the_engines_ids(params, tensors):
    """``runtime/generator.py`` (a bucketed prefill whose padding lies past
    the frontier, block decode) gives the engine's ids, which are the
    reference's argmax (PR 57's lesson: the single-stream path is a path
    of its own)."""
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
    """Under a real ep axis the sparse attention is computed alike on
    every rank (activations and the cache are replicated over ep): the
    mesh stream is the single-device one."""
    from cake_tpu.parallel.mesh import MeshPlan

    prompts = [[5, 9, 2, 11], [int(t) for t in PROMPTS[1][:20]]]
    outs = []
    for ep in (1, 2):
        plan = MeshPlan.build(CFG, ep=ep, devices=jax.devices()[:ep])
        bg = _engine(params, prompts, plan=plan)
        outs.append([list(o) for o in bg.generate(8)])
    assert outs[0] == outs[1]


# -- the loaders ---------------------------------------------------------------------

def test_the_loaders_read_the_indexer_and_skip_a_prediction_block(
        params, tmp_path):
    """A written checkpoint (the indexer under DeepSeek-V3.2's names, a
    next-token prediction block beside the layers) loads onto a mesh as
    the host loader reads it, the block skipped and counted; without one
    of the indexer's tensors the load fails and names it."""
    from safetensors.numpy import load_file, save_file

    from cake_tpu.models.config import LlamaConfig
    from cake_tpu.parallel.mesh import make_mesh
    from cake_tpu.utils.sharded_load import load_llama_params_on_mesh
    from cake_tpu.utils.weights import load_llama_params

    save_llama_params(params, tmp_path, config=CFG)
    hf = dict(CFG.to_hf_dict(), num_nextn_predict_layers=1)
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg = LlamaConfig.from_hf_json(tmp_path / "config.json",
                                   dtype="float32", max_seq_len=128,
                                   eos_token_id=-1)
    assert cfg == CFG
    index = json.loads((tmp_path / "model.safetensors.index.json")
                       .read_text())
    name = "model.layers.1.self_attn.indexer.k_norm.bias"
    file = tmp_path / index["weight_map"][name]
    stored = load_file(file)
    assert stored[name].shape == (16,)
    extra = "model.layers.3.self_attn.indexer.wk.weight"
    save_file({**stored, extra: np.zeros((16, 64), np.float32)}, file)
    index["weight_map"][extra] = file.name
    (tmp_path / "model.safetensors.index.json").write_text(json.dumps(index))
    skipped = _count("load.tensors_skipped")
    before = skipped.value
    on_mesh = load_llama_params_on_mesh(tmp_path, cfg, make_mesh())
    assert skipped.value - before == 1
    host = load_llama_params(tmp_path, cfg.num_hidden_layers,
                             dtype="float32")
    for a, b in zip(jax.tree.leaves(on_mesh), jax.tree.leaves(host)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(on_mesh["layers"]["moe"]["idx_k_bias"][0]),
        np.asarray(params["layers"]["moe"]["idx_k_bias"][0]))
    save_file({k: v for k, v in stored.items() if k != name}, file)
    del index["weight_map"][name]
    (tmp_path / "model.safetensors.index.json").write_text(json.dumps(index))
    with pytest.raises(ValueError, match="stores no model.layers.1.self_attn"
                       ".indexer.k_norm.bias: a model under a learned sparse"):
        load_llama_params_on_mesh(tmp_path, cfg, make_mesh())


# -- the refusals, a case each -----------------------------------------------------------

@pytest.mark.parametrize("kw,says", [
    (dict(kv_layout="paged"), "sparse attention's index key"),
    (dict(spec_k=2), "sparse attention's index key"),
    (dict(admit_chunk=16), "learned sparse attention"),
    (dict(kv_quant="int8"), "int8 cache is not wired for latent"),
], ids=["paged", "speculation", "chunked-admission", "int8-cache"])
def test_the_engine_refuses_what_an_index_row_cannot_hold(params, kw, says):
    """The page pool (and with it the prefix tree, the export and the
    spill tier), speculation, a chunked admission and an int8 cache are
    refused at construction, each with a message that names the
    mechanism."""
    with pytest.raises(ValueError, match=says):
        _engine(params, [[5, 9, 2]], **kw)


def test_prefix_reuse_is_off_and_single_stream_speculation_refused(params):
    from cake_tpu.runtime.speculative import SpeculativeGenerator

    bg = _engine(params, [[5, 9, 2]], prefix_cache_entries=2)
    assert bg.stats()["prefix_hits"] == 0 and bg._prefix_entries == 0
    with pytest.raises(ValueError, match="beside rows .index."):
        SpeculativeGenerator(CFG, params, settings=SamplerSettings(**GREEDY),
                             max_seq=128, spec_k=3)


@pytest.mark.parametrize("axis,sizes", [
    ("stages", (2, 1, 1, 1)), ("tp", (1, 2, 1, 1)), ("sp", (1, 1, 2, 1))])
def test_the_mesh_refuses_to_split_it(axis, sizes):
    """The indexer's heads over ``tp``, rows over ``sp`` and layers over
    stages are refused as the latent family's are (one cache row and one
    index key for all heads); ``ep`` is accepted."""
    from cake_tpu.parallel.mesh import validate_shardable

    with pytest.raises(ValueError, match="one cache row for all heads"):
        validate_shardable(CFG, *sizes)
    validate_shardable(CFG, 1, 1, 1, 2)


def test_a_cache_of_some_layers_is_refused():
    with pytest.raises(ValueError, match="an index key"):
        init_cache(CFG, batch=1, max_seq=32, num_layers=2)


# -- the sweep tool ------------------------------------------------------------------------

def test_dsa_sweep_rows_at_tiny_shapes(monkeypatch, capsys):
    """``tools/dsa_sweep.py --tiny`` on the CPU: a row a part of a decode
    step and of an admission, the shapes of the tool's rehearsal (its
    times are a CPU's and are not read)."""
    from cake_tpu.tools import dsa_sweep

    monkeypatch.setattr(dsa_sweep, "REPEATS", 1)
    monkeypatch.setattr(dsa_sweep, "LAYERS", 2)
    assert dsa_sweep.main(["--tiny", "--frontier", "300", "--attend-block",
                           "128,512", "--buckets", "512"]) == 0
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [r["part"] for r in rows] == [
        "index", "select", "gather", "gather_in_row_order", "attend",
        "select_threshold", "full_sweep", "attend_swept[128]",
        "attend_swept[512]", "gather_path", "sweep_path",
        "prefill_select", "prefill_attend", "sorted_strips"]
    assert all(r["us_per_layer"] > 0 for r in rows)
