"""The sorted form of the expert block where the layer loop and the
engine meet it: whole stacks handed to the kernel, the engine's counters
by form, ``tools/moe_sweep.py`` at tiny shapes, the benchmark's reader
of the experts a decode step reads. The second half of the sorted-form
section of ``tests/test_moe.py`` (the op itself: ``tests/test_moe_sorted.py``),
in a file of its own since PR 59.
"""

import numpy as np
import jax
import pytest

from cake_tpu.ops import moe
from cake_tpu.ops.moe import (
    SORTED_MIN_ROWS, SORTED_MIN_ROWS_INT8, compacts, expert_form,
)
from cake_tpu.models import llama
from cake_tpu.models.config import tiny_moe
from cake_tpu.ops.sampling import SamplerSettings

from moe_kit import GREEDY, kernels  # noqa: F401


def _family(name):
    from cake_tpu.models.config import tiny_kda_hybrid, tiny_mla_moe
    from cake_tpu.ops.quant import quantize_params

    cfg = {"mixtral": lambda: tiny_moe(max_seq_len=512),
           "mixtral-int8": lambda: tiny_moe(max_seq_len=512),
           "latent": lambda: tiny_mla_moe(max_seq_len=512),
           # K K (M K K) x 2 M K: a repeated period's stacks lead [2, n]
           "hybrid": lambda: tiny_kda_hybrid(num_hidden_layers=10,
                                             max_seq_len=512)}[name]()
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    if name == "mixtral-int8":
        params = quantize_params(params)
    return cfg, params


@pytest.mark.parametrize("shape", ["prefill", "step"])
@pytest.mark.parametrize("name", ["mixtral", "mixtral-int8", "latent",
                                  "hybrid"])
def test_layer_loop_hands_the_sorted_form_whole_stacks(name, shape,
                                                       monkeypatch):
    """Through the layer loop of each family, a prefill of the
    threshold's rows (128 int8, 512 else) and a step of 3 rows (one token
    each: Mixtral's 6 pairs gather, the 12 pairs over 16 scored experts
    hit 0.54 of them and are sorted): where the expert block takes the
    sorted form the scan slices everything of a layer but its expert
    matrices, which stay whole beside a layer index (a repeated period's
    index runs over its repetitions too), and where it does not the scan
    slices them too: the loop and the block ask ONE rule. Logits as
    without kernels."""
    from cake_tpu.ops.kvcache import init_cache

    cfg, params = _family(name)
    if shape == "prefill":
        rows = (SORTED_MIN_ROWS_INT8 if name == "mixtral-int8"
                else SORTED_MIN_ROWS)
        batch, forms = 1, ("dense", "sorted")
    else:
        rows = batch = 3
        forms = (("gather", "gather") if name.startswith("mixtral")
                 else ("dense", "sorted"))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (batch, rows // batch),
                                0, cfg.vocab_size)

    def logits(force):
        monkeypatch.setenv("CAKE_PALLAS", force)
        moe._traced.clear()
        out, _ = jax.jit(lambda p, t: llama.forward(
            p, t, init_cache(cfg, batch=batch, max_seq=512), 0, cfg))(
            params, tokens)
        return np.asarray(out), moe.form_traced(rows)

    want, form = logits("0")
    assert form == forms[0]
    got, form = logits("1")
    assert form == forms[1]
    np.testing.assert_allclose(got, want, atol=2e-3 * np.abs(want).max())


def test_engine_counts_admitted_rows_by_form(monkeypatch):
    """Per admission dispatch the engine adds the bucket's rows to
    ``moe.admit_rows`` and, where that bucket's program took the sorted
    form when it was traced, to ``moe.admit_rows_sorted``; the gauge
    ``moe.sorted_from_rows`` holds the smallest such bucket."""
    from cake_tpu.obs import metrics
    from cake_tpu.runtime.batch_generator import BatchGenerator

    monkeypatch.setenv("CAKE_PALLAS", "1")
    cfg = tiny_moe(max_seq_len=512, eos_token_id=-1)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    reg = metrics.registry()
    rows, ordered = (reg.counter(f"moe.admit_rows{s}") for s in ("", "_sorted"))
    reg.gauge("moe.sorted_from_rows").set(0)
    before = rows.value, ordered.value
    bg = BatchGenerator(cfg, params, settings=SamplerSettings(**GREEDY))
    bg.set_prompts([[3, 5, 7], [2, 4]], stream_ids=[0, 1])
    for sid, prompt in ((2, range(1, 21)),  # bucket 32: dense
                        (3, range(1, 301))):  # bucket 512: sorted
        assert bg.finish(sid - 2)
        bg.admit([t % 250 + 1 for t in prompt], stream_id=sid)
    assert rows.value - before[0] == 32 + 512
    assert ordered.value - before[1] == 512
    assert reg.gauge("moe.sorted_from_rows").value == 512
    assert all(row is None or row.id >= 0 for row in bg.step())


def test_engine_counts_the_pair_rows_the_sorted_form_touches(monkeypatch):
    """An expert model told its share (4 held of 16 scored, top-4) counts
    on the device, a sorted-form call and expert layer, the pair rows the
    call was handed (``rows x top_k``) and those of the row tiles it
    touched; the engine brings both home with the counts it already
    fetches: an admission's once its program has run, a decode step's
    with its block. A 512-row program (the bucket's, and the two-row
    ones the engine warms behind it) hands 2048 pair rows a layer to the
    sorted form, of which a quarter or so are held: 6 tiles of 16 at
    most."""
    from cake_tpu.models.config import tiny_mla_moe
    from cake_tpu.obs import metrics
    from cake_tpu.runtime.batch_generator import BatchGenerator

    monkeypatch.setenv("CAKE_PALLAS", "1")
    cfg = tiny_mla_moe(max_seq_len=512, eos_token_id=-1, n_routed_experts=4,
                       router_experts=16, first_expert=4)
    layers = sum(ffn == "moe" for _, ffn in cfg.layer_kinds)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    reg = metrics.registry()
    handed, live = (reg.counter(f"moe.sorted_pair_rows{s}")
                    for s in ("", "_live"))
    bg = BatchGenerator(cfg, params, settings=SamplerSettings(**GREEDY))
    bg.set_prompts([[3, 5, 7], [2, 4]], stream_ids=[0, 1])
    bg.drain()
    before = handed.value, live.value
    assert bg.finish(1)
    bg.admit([t % 250 + 1 for t in range(1, 301)], stream_id=3)
    bg.drain()
    assert moe.form_traced(512) == "sorted"
    programs = (handed.value - before[0]) / (512 * 4 * layers)
    assert programs >= 1 and programs == int(programs)
    touched = live.value - before[1]
    assert touched % 128 == 0
    assert 128 <= touched / (programs * layers) <= 6 * 128
    # a step of 2 rows x 4 of 16 scored hits 0.4 of them: sorted, one
    # tile of 128 for its 8 pair rows where a pair is held, else none
    before = handed.value, live.value
    for _ in range(4):
        bg.step()
    bg.drain()
    steps = (handed.value - before[0]) / (2 * 4 * layers)
    assert steps >= 1 and steps == int(steps)
    assert 0 <= live.value - before[1] <= steps * layers * 128


@pytest.mark.parametrize("hidden", [1024, 64])
def test_engine_counts_the_live_rows_an_admission_fetched(hidden,
                                                          monkeypatch):
    """``moe.gather_rows_fetched``: the live rows of the admissions whose
    bucket gathered them by address, known from the bucket alone (the
    trace recorded its form; no new device output). With the rule's row
    count brought down to the tiny model's 512-row bucket: a model whose
    float32 row of 1024 is a whole tile of words counts every live row of
    its admission and nothing of its decode steps (a step's rows are
    picked by the one-hot product); one whose row of 64 is no tile counts
    nothing at all."""
    from cake_tpu.models.config import tiny_mla_moe
    from cake_tpu.obs import metrics
    from cake_tpu.runtime.batch_generator import BatchGenerator

    monkeypatch.setenv("CAKE_PALLAS", "1")
    monkeypatch.setattr(moe, "GATHER_FETCH_MIN_ROWS", 512)
    monkeypatch.setattr(moe, "_fetched", set())
    cfg = tiny_mla_moe(max_seq_len=512, eos_token_id=-1, n_routed_experts=4,
                       router_experts=16, first_expert=4, hidden_size=hidden)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    reg = metrics.registry()
    live, fetched = (reg.counter(f"moe.{name}") for name in (
        "sorted_pair_rows_live", "gather_rows_fetched"))
    bg = BatchGenerator(cfg, params, settings=SamplerSettings(**GREEDY))
    bg.set_prompts([[3, 5, 7], [2, 4]], stream_ids=[0, 1])
    bg.drain()
    before = live.value, fetched.value
    assert bg.finish(1)
    bg.admit([t % 250 + 1 for t in range(1, 301)], stream_id=3)
    bg.drain()
    assert moe.form_traced(512) == "sorted"
    assert moe.fetch_traced(512) == (hidden == 1024)
    touched = live.value - before[0]
    assert touched >= 128
    assert fetched.value - before[1] == (touched if hidden == 1024 else 0)
    before = live.value, fetched.value
    for _ in range(4):
        bg.step()
    bg.drain()
    assert not moe.fetch_traced(2) and fetched.value == before[1]


@pytest.mark.parametrize("tokens", [300, 513])
@pytest.mark.parametrize("name", ["all-held", "share"])
def test_an_admission_told_its_length_starts_as_it_did(name, tokens,
                                                       monkeypatch):
    """A prompt of 300 tokens in its 512-row bucket and one of 513 in its
    1024-row one, through the admission program of a model that holds
    every scored expert (the cache holds rows alone: the expert block is
    told the length all the same) and of one told its share (4 of 16: the
    live tiles' kernels): the logits at the prompt's last token are, bit
    for bit, those of the program that tells its expert blocks nothing
    (the parent's: ``llama.true_rows`` gives them no length), while the
    sorted form touches fewer pair rows; and the engine's first token is
    their argmax."""
    import jax.numpy as jnp

    from cake_tpu.models.config import tiny_mla_moe
    from cake_tpu.ops.kvcache import init_cache
    from cake_tpu.parallel.mesh import MeshPlan
    from cake_tpu.parallel.pipeline import build_admit_prefill
    from cake_tpu.runtime.batch_generator import BatchGenerator

    monkeypatch.setenv("CAKE_PALLAS", "1")
    cfg = (tiny_moe(max_seq_len=1024, eos_token_id=-1) if name == "all-held"
           else tiny_mla_moe(max_seq_len=1024, eos_token_id=-1,
                             n_routed_experts=4, router_experts=16,
                             first_expert=4))
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    prompt = [t * 7 % 250 + 1 for t in range(tokens)]
    bucket = 512 if tokens <= 512 else 1024
    # (a bucket's padding is whatever lies there: ids the router spreads)
    padded = jnp.asarray([prompt + [t * 11 % 250 + 1 for t in range(
        bucket - tokens)]], jnp.int32)
    plan = MeshPlan.build(cfg, devices=jax.devices()[:1])

    def admitted():
        logits, _, *rows = build_admit_prefill(cfg, plan, params_like=params)(
            params, padded, init_cache(cfg, batch=1, max_seq=1024),
            jnp.int32(0), jnp.asarray([tokens - 1], jnp.int32))
        assert moe.form_traced(bucket) == "sorted"
        return np.asarray(logits), [int(r) for r in rows]

    told, told_rows = admitted()
    real = llama.true_rows
    with monkeypatch.context() as mp:
        mp.setattr(llama, "true_rows", lambda *a: (real(*a)[0], None))
        untold, untold_rows = admitted()
    np.testing.assert_array_equal(told, untold)
    if name == "share":  # it counts: as many handed, fewer touched
        assert told_rows[0] == untold_rows[0]
        assert told_rows[1] < untold_rows[1]
    bg = BatchGenerator(cfg, params, max_seq=1024, settings=SamplerSettings(
        temperature=0.0, repeat_penalty=1.0))
    bg.set_prompts([[3, 5, 7], [2, 4]], stream_ids=[0, 1])
    assert bg.finish(1)
    _, first = bg.admit(prompt, stream_id=2)
    assert first.id == int(untold[0].argmax())


def test_moe_sweep_rows_at_tiny_shapes(monkeypatch, kernels):
    """tools/moe_sweep.py's machinery on the CPU (interpreted kernel, no
    device time): a row per shape and row count, each form timed through
    ``moe_swiglu`` as the layer loop calls it (``compact``: the live
    tiles' gather and sum kernels where every expert is held too), the
    bytes each moves beside the weights by its shapes, and the program's
    own choices restored afterwards."""
    from cake_tpu.tools import moe_sweep

    monkeypatch.setattr(moe_sweep, "SHAPES", {
        "tiny": (4, 4, 2, 32, 128, False, None),
        "tiny-int8-share": (4, 16, 2, 32, 128, True, (4, 2))})
    out = list(moe_sweep.sweep(["tiny", "tiny-int8-share"], [16, 128],
                               ["dense", "sorted", "compact"], [128]))
    assert [(r["shape"], r["rows"]) for r in out] == [
        ("tiny", 16), ("tiny", 128), ("tiny-int8-share", 16),
        ("tiny-int8-share", 128)]
    for r in out:
        assert r["dense_us_per_layer"] > 0 and r["sorted_us_per_layer"] > 0
        assert r["compact_us_per_layer"] > 0
        # a quarter of the pairs are held: the sorted form moves less
        assert (r["sorted_moved_mb"] == r["compact_moved_mb"]) == (
            r["shape"] == "tiny-int8-share")
    assert out[3]["sorted_moved_mb"] < out[3]["dense_moved_mb"]
    assert {r["valid_share"] for r in out} == {1.0}
    # ``--valid-share``: a row a share, the block told that the leading
    # share of its rows is true (the dense form, which skips nothing, is
    # timed once and stands in both)
    out = list(moe_sweep.sweep(["tiny-int8-share"], [128],
                               ["dense", "sorted"], [128], [1.0, 0.5]))
    assert [r["valid_share"] for r in out] == [1.0, 0.5]
    assert out[0]["dense_us_per_layer"] == out[1]["dense_us_per_layer"]
    assert all(r["sorted_us_per_layer"] > 0 for r in out)
    assert moe.expert_form is expert_form and moe.compacts is compacts


def test_kda_sweep_chunk_rows_at_tiny_shapes(monkeypatch):
    """tools/kda_sweep.py ``--chunk`` on the CPU (no device time): a row a
    shape and share of true tokens, the chunk form entered through
    ``_advance`` with each row's length as a layer enters it; with
    ``--forms`` a row a form too, the kernel (interpreted) for the decay a
    head alone and once a head block, and the choice and the block are the
    checkout's again afterwards."""
    from cake_tpu.ops import kda
    from cake_tpu.ops.pallas import kda as pallas_kda
    from cake_tpu.tools import kda_sweep

    monkeypatch.setattr(kda_sweep, "CHUNK_SHAPES", (
        ("scalar", 1, 160, 2, 4, 16), ("channel", 2, 96, 2, 2, 16)))
    monkeypatch.setattr(kda_sweep, "CHUNK_LAYERS", 2)
    out = list(kda_sweep.chunk_rows([0], [1.0, 0.4]))
    assert [(r["decay"], r["tokens"], r["live_share"]) for r in out] == [
        ("scalar", 160, 1.0), ("scalar", 160, 0.4), ("channel", 96, 1.0),
        ("channel", 96, 0.4)]
    assert all(r["us_per_layer"] > 0 and r["block"] is None
               and r["form"] == "xla" and r["head_block"] is None
               for r in out)
    choice, block = kda.kda_chunk_choice, pallas_kda.SCAN_HEAD_BLOCK
    out = list(kda_sweep.chunk_rows([0], [0.4], ("xla", "kernel"), (2, 4)))
    assert [(r["decay"], r["form"], r["head_block"]) for r in out] == [
        ("scalar", "xla", None), ("channel", "xla", None),
        ("scalar", "kernel", 2), ("scalar", "kernel", 4)]
    assert all(r["us_per_layer"] > 0 for r in out)
    assert kda.kda_chunk_choice is choice
    assert pallas_kda.SCAN_HEAD_BLOCK == block


@pytest.mark.parametrize("form,hit,want", [
    (0, 5000, 100.0),  # the dense form reads every held expert
    (1, 6144, 40.0),  # 6144 of 128 held x 6 layers x 20 steps
    (1, None, None),  # the parent's program: no such counter
    (None, 6144, None),  # nor the gauge
], ids=["dense", "sorted", "no-counter", "no-gauge"])
def test_reader_of_the_experts_a_decode_step_reads(form, hit, want):
    """``benchmark/layer_metrics/moe.decode_experts_read_share.py``, loaded
    by path as the benchmark loads it: 100 where the gauge
    ``moe.decode_sorted`` is 0; else the growth of ``moe.experts_hit``
    over held experts x expert layers x the growth of
    ``moe.decode_steps``; nothing (the line leaves the metric out, no
    error) from a program without the counter or the gauge."""
    import importlib.util
    import sys
    import types
    from pathlib import Path

    bench = Path(__file__).resolve().parent.parent / "benchmark"

    def counter(value):
        return {"type": "counter", "value": value}

    before = {"moe.decode_steps": counter(100)}
    after = {"moe.decode_steps": counter(120)}
    if hit is not None:
        before["moe.experts_hit"] = counter(1000)
        after["moe.experts_hit"] = counter(1000 + hit)
    if form is not None:
        after["moe.decode_sorted"] = {"type": "gauge", "value": form}
    arch = types.SimpleNamespace(held_experts=lambda cfg: range(128, 256),
                                 expert_layers=lambda cfg: 6)
    ctx = {"before": {"status": {"metrics": before}},
           "after": {"status": {"metrics": after}}, "arch": arch, "cfg": {}}
    path = list(sys.path)  # the readers import their helpers by bare name
    sys.path.insert(0, str(bench))
    try:
        spec = importlib.util.spec_from_file_location(
            "reader_for_tests",
            bench / "layer_metrics" / "moe.decode_experts_read_share.py")
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        got = reader.read(ctx)
    finally:
        sys.path[:] = path
        sys.modules.pop("counters", None)
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_benchmark_declares_the_read_share_for_the_two_cells():
    import json
    from pathlib import Path

    bench = json.loads((Path(__file__).resolve().parent.parent
                        / "BENCHMARK.json").read_text())
    # appended by PR 35: nothing before it moved, later PRs append after
    metric = next(m for m in bench["per_layer"]
                  if m["name"] == "moe.decode_experts_read_share")
    # ... and a later configuration's cell is appended to its list (PR 40)
    cells = metric.pop("workloads")
    assert cells[:2] == ["axk1-ep16-cut.decode-full",
                         "ling3flash-ep4-cut.decode-full"]
    assert metric == {
        "name": "moe.decode_experts_read_share", "unit": "%",
        "better": "lower", "source": "program_counter", "layer": "kernels",
        "moves": "tpot_p50_ms"}
