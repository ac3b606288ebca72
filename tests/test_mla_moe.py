"""The latent-attention, shared-expert family (DeepSeek-V3's keys: MLA over
a latent cache, YaRN, leading dense layers, sigmoid group-limited routing
over a told share of the experts beside shared ones) against the plain
reference ``cake_tpu/testing/reference_mla_moe.py``, on seeded random
weights at tiny widths that keep every ratio of the published family
(``models.config.tiny_mla_moe``).

Tolerances. Everything here is float32 on the CPU, where XLA's matmuls
are full precision; program and reference then differ only in the order
of sums (absorbed against expanded attention, one einsum against a loop
of experts), which measures ~3e-6 on logits of magnitude ~3. ``TIGHT``
is 3e-5: ten times that, and three hundred times under what computing in
bfloat16 costs (~1e-2, checked below), so a lowered precision fails.
"""

from __future__ import annotations

import dataclasses
import json
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import LlamaConfig, axk1_ep16, tiny_mla_moe
from cake_tpu.ops import moe
from cake_tpu.ops.kvcache import init_cache
from cake_tpu.ops.rope import rope_tables_for, yarn_mscale
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.testing import reference_mla_moe as ref
from cake_tpu.utils.weights import (latent_hf_tensors, load_llama_params,
                                    save_llama_params)

TIGHT = 3e-5
CFG = tiny_mla_moe()
TOKENS = np.array([3, 5, 7, 9, 11, 200, 100, 50, 25, 12, 6, 1, 99, 42, 17, 8,
                   33, 64, 128, 255, 2, 4, 77, 31], np.int32)
GREEDY = dict(temperature=0.0, repeat_penalty=1.0)


def _params(cfg=CFG, seed=0):
    """Seeded weights with norm scales that are not all ones, so a norm
    applied twice or not at all shows."""
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def jitter(path, leaf):
        if not path[-1].key.endswith("norm") and path[-1].key != "norm_f":
            return leaf
        k = jax.random.fold_in(  # (crc32: str hashes differ by process)
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
        return leaf * (1.0 + 0.25 * jax.random.uniform(k, leaf.shape,
                                                       minval=-1.0))

    return jax.tree_util.tree_map_with_path(jitter, params)


@pytest.fixture(scope="module")
def params():
    return _params()


@pytest.fixture(scope="module")
def want(params):
    """The reference's logits at every position of TOKENS."""
    return np.asarray(ref.logits(CFG.to_hf_dict(),
                                 latent_hf_tensors(params, CFG), TOKENS))


def _decode_all(params, cfg, tokens, prefill: int, chunk: int | None = None):
    """Logits at positions ``prefill - 1 ..`` through the cache: a prefill
    of ``prefill`` tokens (in chunks of ``chunk``), then one step a token."""
    cache = init_cache(cfg, batch=1, max_seq=64)
    step = jax.jit(lambda p, t, c, pos: llama.forward(p, t, c, pos, cfg))
    chunk = chunk or prefill
    for lo in range(0, prefill, chunk):
        logits, cache = step(params, jnp.asarray(tokens[None, lo:lo + chunk]),
                             cache, lo)
    out = [logits[0]]
    for i in range(prefill, len(tokens)):
        logits, cache = step(params, jnp.asarray(tokens[None, i:i + 1]),
                             cache, i)
        out.append(logits[0])
    return np.stack(out), cache


def test_prefill_logits_match_reference(params, want):
    cache = init_cache(CFG, batch=1, max_seq=64)
    logits, _ = llama.forward(params, jnp.asarray(TOKENS[None]), cache, 0, CFG)
    np.testing.assert_allclose(logits[0], want[-1], atol=TIGHT, rtol=0)


@pytest.fixture(params=["xla", "kernels-forced"])
def decode_path(request, monkeypatch):
    """The decode step's absorbed attention as the CPU takes it (XLA's
    masked einsums) and with kernels forced (``CAKE_PALLAS=1``: the
    interpreted ``latent_decode`` kernel over the carried latent cache,
    and the expert block's sorted form); the gauge says which."""
    from cake_tpu.obs import metrics

    forced = request.param == "kernels-forced"
    monkeypatch.setenv("CAKE_PALLAS", "1" if forced else "auto")
    gauge = metrics.registry().gauge("attn.decode_kernel")
    gauge.set(-1)
    yield
    assert gauge.value == int(forced)


@pytest.mark.parametrize("chunk", [None, 4], ids=["one-chunk", "chunks-of-4"])
def test_prefill_then_16_decode_steps_match_reference(params, want, chunk,
                                                      decode_path):
    """Prefill (expanded on the chunk, absorbed against what is behind it)
    then 16 absorbed decode steps through the latent cache: the logits at
    every position against the reference's full forward."""
    got, _ = _decode_all(params, CFG, TOKENS, prefill=8, chunk=chunk)
    assert got.shape[0] == 17
    np.testing.assert_allclose(got, want[7:], atol=TIGHT, rtol=0)


def test_bfloat16_fails_the_tolerance(params, want):
    """The tolerance is tight enough that a lowered precision fails it."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    got, _ = _decode_all(low, cfg, TOKENS, prefill=8)
    assert np.abs(got - want[7:]).max() > 100 * TIGHT


def test_cache_holds_the_latent_row_and_nothing_else(params):
    _, cache = _decode_all(params, CFG, TOKENS, prefill=8)
    row = CFG.kv_lora_rank + CFG.qk_rope_head_dim
    assert CFG.cache_row_values == row
    leaves = jax.tree.leaves(cache)
    assert sorted(a.shape for a in leaves) == sorted([
        (CFG.num_hidden_layers, 1, 1, 64, CFG.kv_lora_rank),
        (CFG.num_hidden_layers, 1, 1, 64, CFG.qk_rope_head_dim)])
    assert sum(a.size for a in leaves) == CFG.num_hidden_layers * 64 * row
    # every layer of BOTH stacks wrote its rows, and only the rows fed
    written = np.asarray(jnp.abs(cache.k).sum(-1) > 0)[:, 0, 0]
    assert written[:, :len(TOKENS)].all() and not written[:, len(TOKENS):].any()


def test_batch_generator_four_streams_match_reference(params, decode_path):
    """Four streams of different lengths through BatchGenerator (slot
    cache, per-row positions, block decode, an admission into a freed
    slot): each stream's greedy tokens are the reference's own greedy
    continuation, by its logits' argmax with a margin check."""
    from cake_tpu.runtime.batch_generator import BatchGenerator

    cfg = dataclasses.replace(CFG, eos_token_id=-1)
    prompts = [[5, 9, 2, 11], [3, 1, 4, 1, 5, 9, 2, 6], [7, 7, 2],
               [8, 6, 7, 5, 3, 0, 9]]
    bg = BatchGenerator(cfg, params, settings=SamplerSettings(**GREEDY),
                        block_size=4, max_seq=64)
    bg.set_prompts(prompts)
    outs = bg.generate(9)
    tensors = latent_hf_tensors(params, cfg)
    for prompt, out in zip(prompts, outs):
        full = np.array(prompt + list(out))
        logits = np.asarray(ref.logits(cfg.to_hf_dict(), tensors, full))
        for j, tok in enumerate(out):
            at = logits[len(prompt) - 1 + j]
            assert at.max() - at[tok] <= TIGHT, (prompt, j)
    stats = bg.stats()
    assert stats["tokens_emitted"] == 4 * 9


@pytest.mark.parametrize("share", ["cut", "whole"])
def test_moe_counters_and_cache_gauges(params, share):
    """``moe.*`` count the LIVE rows' pairs. With every expert held
    (``whole``) each routed pair is a local one, so the two counters are
    equal to the pair, before and after a slot is retired: that is the
    exact check. With a share held (``cut``: experts 4-11, two groups of
    four) the local pairs are a part of the routed ones; how large a part
    depends on which tokens the greedy streams happen to emit, and on a
    loaded machine XLA's CPU reductions round differently and random tiny
    weights emit other tokens (the driver's run of PR 31 met 8 steps of one
    live row that never chose a held expert), so after the retirement only
    ``local <= routed`` is held."""
    from cake_tpu.obs import metrics
    from cake_tpu.runtime.batch_generator import BatchGenerator

    reg = metrics.registry()
    before = {n: reg.counter(n).value for n in
              ("moe.local_pairs", "moe.routed_pairs", "moe.decode_steps",
               "moe.experts_hit")}
    cfg, p = dataclasses.replace(CFG, eos_token_id=-1), params
    if share == "cut":
        cfg, p = _cut_share(params)
    bg = BatchGenerator(cfg, p, settings=SamplerSettings(**GREEDY),
                        block_size=4, max_seq=64)
    bg.set_prompts([[5, 9, 2], [3, 1, 4, 1]])

    def counted():
        got = {n: reg.counter(n).value - v for n, v in before.items()}
        before.update({n: reg.counter(n).value for n in before})
        return got

    def held_part(got):
        if share == "whole":
            assert got["moe.local_pairs"] == got["moe.routed_pairs"]
        else:
            assert got["moe.local_pairs"] <= got["moe.routed_pairs"]

    bg.generate(9)
    got = counted()
    steps = got["moe.decode_steps"]
    assert steps >= 8
    assert got["moe.routed_pairs"] == steps * 2 * 4 * 2  # rows x k x layers
    held_part(got)
    # two live rows' choices: at least the one's, at most the pairs'
    assert (got["moe.local_pairs"] / 2 <= got["moe.experts_hit"]
            <= got["moe.local_pairs"])
    assert reg.gauge("moe.decode_sorted").value == 0  # no kernels here
    if share == "cut":  # 32 routings over 2 of 4 groups: some hit, some miss
        assert 0 < got["moe.local_pairs"] < got["moe.routed_pairs"]
    # a retired slot's row still goes through the program, and is no load:
    # only the rows live at dispatch are counted
    bg.drain()
    counted()
    bg.finish(bg.streams[1].stream_id)
    bg.generate(8)
    bg.drain()
    got = counted()
    assert got["moe.decode_steps"] >= 8
    assert got["moe.routed_pairs"] == got["moe.decode_steps"] * 1 * 4 * 2
    held_part(got)
    # the dead slot's row goes through the program too, and the experts
    # it chose are read: not fewer than the live row's distinct choices
    assert got["moe.local_pairs"] <= got["moe.experts_hit"] <= (
        got["moe.decode_steps"] * 2 * 4 * 2)
    assert reg.gauge("cache.row_bytes").value == 4 * (16 + 8)
    assert reg.gauge("cache.bytes").value == 3 * 2 * 64 * 4 * (16 + 8)


@pytest.mark.parametrize("end", ["drain", "idle"])
def test_counts_fetched_after_the_enqueue_are_those_of_a_fetch_at_the_landing(
        params, end):
    """A landed block's counts are fetched once the device has its next
    program (``_fetch_moe_counts`` at the return of a ``step()`` that
    leaves no boundary open), not between the tokens' fetch and the
    rows' recording. That moves WHEN they are fetched, not what is
    counted: against an engine that fetches them as each block lands the
    four ``moe.*`` counters come out equal, after ``drain()`` (the block
    in flight lands there, and its counts with it) and after the engine
    went idle with a landed block's counts still queued (every stream
    retired between the landing and the next ``step()``)."""
    from cake_tpu.obs import metrics
    from cake_tpu.runtime.batch_generator import BatchGenerator

    reg = metrics.registry()
    names = ("moe.local_pairs", "moe.experts_hit", "moe.routed_pairs",
             "moe.decode_steps")
    cfg, p = _cut_share(params)

    def run(at_the_landing: bool) -> dict:
        before = {n: reg.counter(n).value for n in names}
        fetches = reg.histogram("engine.landing_counts_fetch_ms").count
        bg = BatchGenerator(cfg, p, settings=SamplerSettings(**GREEDY),
                            block_size=4, max_seq=64)
        if at_the_landing:
            land = bg._land_block

            def land_and_fetch():
                landed = land()
                bg._fetch_moe_counts()
                return landed

            bg._land_block = land_and_fetch
        bg.set_prompts([[5, 9, 2], [3, 1, 4, 1]])
        bg.generate(9)
        bg.finish(bg.streams[1].stream_id)  # a dead slot's row is no load
        landings = 0
        while landings < 2:
            landings += not any(t is not None for t in bg.step())
        # a block has just landed: its rows are recorded, nothing follows
        # it on the device yet, and its counts wait for that
        assert bg._moe_landed == (0 if at_the_landing else 1)
        if end == "drain":
            bg.step()  # the next block leaves ...
            assert bg._inflight is not None and bg._moe_landed == 0
            bg.drain()  # ... and lands here, its counts with it
        else:
            bg.finish(bg.streams[0].stream_id)
            bg.step()  # nothing live: no program follows, the engine idles
            assert bg._inflight is None
        assert bg._moe_landed == 0 and not bg._moe_pending
        got = {n: reg.counter(n).value - v for n, v in before.items()}
        got["fetches"] = reg.histogram(
            "engine.landing_counts_fetch_ms").count - fetches
        return got

    deferred, at_once = run(False), run(True)
    assert deferred == at_once
    assert deferred["moe.decode_steps"] == deferred["fetches"] * 4 >= 16
    assert 0 < deferred["moe.local_pairs"] < deferred["moe.routed_pairs"]
    assert deferred["moe.experts_hit"] > 0


def _cut_share(params):
    """Experts 4-11 of 16 scored (two groups of four) held."""
    cfg = dataclasses.replace(CFG, eos_token_id=-1, n_routed_experts=8,
                              router_experts=16, first_expert=4)
    p = dict(params, layers=dict(params["layers"]))
    p["layers"]["moe"] = {
        k: (v[:, 4:12] if k in ("w_gate", "w_up", "w_down") else v)
        for k, v in p["layers"]["moe"].items()}
    return cfg, p


@pytest.mark.parametrize("kernels", ["1", "0"], ids=["sorted", "dense"])
def test_engine_counts_the_held_experts_a_step_hits(params, kernels,
                                                    monkeypatch):
    """``moe.experts_hit`` against a host count: the one row of a
    one-slot engine chooses ``top_k`` distinct experts, so the held
    experts its steps hit are its pairs on held experts, layer by layer
    and step by step: the two counters grow alike, through the same
    fetch. The gauge ``moe.decode_sorted`` says which form the decode
    program's expert calls took when it was traced: with kernels the one
    row's 4 pairs over 16 scored experts (0.23 of them hit) are sorted,
    without kernels every held expert runs."""
    from cake_tpu.obs import metrics
    from cake_tpu.ops import moe
    from cake_tpu.runtime.batch_generator import BatchGenerator

    monkeypatch.setenv("CAKE_PALLAS", kernels)
    reg = metrics.registry()
    names = ("moe.local_pairs", "moe.experts_hit", "moe.decode_steps")
    before = {n: reg.counter(n).value for n in names}
    cfg, p = _cut_share(params)
    bg = BatchGenerator(cfg, p, settings=SamplerSettings(**GREEDY),
                        block_size=4, max_seq=64)
    bg.set_prompts([[5, 9, 2]])
    bg.generate(9)
    bg.drain()
    got = {n: reg.counter(n).value - before[n] for n in names}
    assert got["moe.decode_steps"] >= 8
    assert 0 < got["moe.experts_hit"] == got["moe.local_pairs"]
    assert got["moe.experts_hit"] < 8 * 2 * got["moe.decode_steps"]
    assert moe.form_traced(1) == ("sorted" if kernels == "1" else "dense")
    assert reg.gauge("moe.decode_sorted").value == int(kernels)


def test_checkpoint_writer_reader_roundtrip(tmp_path, params, want):
    """Through the real writer and both loaders (host and direct-to-mesh
    are one code path for this family): the same pytree, the same logits."""
    save_llama_params(params, tmp_path, config=CFG)
    (tmp_path / "config.json").write_text(json.dumps(CFG.to_hf_dict()))
    cfg = LlamaConfig.from_hf_json(tmp_path / "config.json", dtype="float32",
                                   max_seq_len=CFG.max_seq_len)
    assert cfg == CFG
    loaded = load_llama_params(tmp_path, cfg.num_hidden_layers,
                               dtype="float32")
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    cache = init_cache(cfg, batch=1, max_seq=64)
    logits, _ = llama.forward(loaded, jnp.asarray(TOKENS[None]), cache, 0, cfg)
    np.testing.assert_allclose(logits[0], want[-1], atol=TIGHT, rtol=0)


def test_cut_checkpoint_holds_experts_by_global_id(tmp_path, params):
    """A share of the experts (global ids 8..11 of 16) is written under
    those ids, found by them, and config.json carries the share."""
    cfg = dataclasses.replace(CFG, n_routed_experts=4, router_experts=16,
                              first_expert=8)
    cut = dict(params, layers=dict(params["layers"]))
    cut["layers"]["moe"] = {
        k: (v[:, 8:12] if k in ("w_gate", "w_up", "w_down") else v)
        for k, v in params["layers"]["moe"].items()}
    names = latent_hf_tensors(cut, cfg)
    held = ref.held_experts(names, "model.layers.1.", 16)
    assert held == [8, 9, 10, 11]
    save_llama_params(cut, tmp_path, config=cfg)
    hf = cfg.to_hf_dict()
    assert hf["n_routed_experts"] == 4
    assert hf["expert_share"] == {"n_routed_experts": 16, "ep": 4, "rank": 2}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    again = LlamaConfig.from_hf_json(tmp_path / "config.json",
                                     dtype="float32", max_seq_len=128)
    assert again == cfg
    loaded = load_llama_params(tmp_path, cfg.num_hidden_layers,
                               dtype="float32")
    np.testing.assert_array_equal(
        np.asarray(loaded["layers"]["moe"]["w_up"]),
        np.asarray(cut["layers"]["moe"]["w_up"]))
    # and the cut model agrees with the reference given the same share
    want = np.asarray(ref.logits(hf, names, TOKENS))
    cache = init_cache(cfg, batch=1, max_seq=64)
    logits, _ = llama.forward(loaded, jnp.asarray(TOKENS[None]), cache, 0, cfg)
    np.testing.assert_allclose(logits[0], want[-1], atol=TIGHT, rtol=0)


def test_yarn_tables_match_the_closed_form():
    cfg = tiny_mla_moe(qk_rope_head_dim=16, rope_scaling={
        "type": "yarn", "factor": 8.0, "beta_fast": 32, "beta_slow": 1,
        "mscale": 0.8, "mscale_all_dim": 1.0,
        "original_max_position_embeddings": 16}, rope_theta=100.0)
    cos, sin = rope_tables_for(cfg, 64)
    d, theta, factor, orig = 16, 100.0, 8.0, 16.0
    corr = lambda turns: (d * math.log(orig / (turns * 2 * math.pi))  # noqa: E731
                          / (2 * math.log(theta)))
    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), d - 1)
    assert 0 <= low < high  # the ramp is inside the pairs at this size
    amp = (0.1 * 0.8 * math.log(8) + 1) / (0.1 * 1.0 * math.log(8) + 1)
    for i in range(d // 2):
        base = theta ** (-2 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        inv = base / factor * ramp + base * (1 - ramp)
        np.testing.assert_allclose(cos[:, i], amp * np.cos(np.arange(64) * inv),
                                   atol=2e-5)
        np.testing.assert_allclose(sin[:, i], amp * np.sin(np.arange(64) * inv),
                                   atol=2e-5)
    # fast pairs keep their frequency, the slowest is divided by the factor
    assert yarn_mscale(8.0, 1.0) == pytest.approx(0.1 * math.log(8) + 1)
    assert yarn_mscale(1.0, 1.0) == 1.0
    assert cfg.attn_scale == pytest.approx(
        (cfg.qk_nope_head_dim + 16) ** -0.5 * yarn_mscale(8.0, 1.0) ** 2)
    # and the reference's own tables are the same numbers
    rcos, rsin = ref.rope_angles(cfg.to_hf_dict(), 64)
    np.testing.assert_allclose(cos, rcos, atol=1e-6)
    np.testing.assert_allclose(sin, rsin, atol=1e-6)


def _route_oracle(scores, k, groups, keep, norm, scale):
    """Loop-written routing: ties to the lower index."""
    out_idx, out_w = [], []
    for row in scores:
        e = len(row)
        size = e // groups
        gscore = []
        for g in range(groups):
            top2 = sorted(row[g * size:(g + 1) * size], reverse=True)[:2]
            gscore.append(float(np.float32(top2[0]) + np.float32(top2[1])))
        kept = sorted(range(groups), key=lambda g: (-gscore[g], g))[:keep]
        allowed = [i for i in range(e) if i // size in kept]
        chosen = sorted(allowed, key=lambda i: (-row[i], i))[:k]
        w = np.array([row[i] for i in chosen], np.float32)
        if norm:
            w = w / (w.sum() + np.float32(1e-20))
        out_idx.append(chosen)
        out_w.append(w * np.float32(scale))
    return np.array(out_idx), np.array(out_w)


@pytest.mark.parametrize("ties", [False, True], ids=["random", "ties"])
def test_router_matches_loop_oracle(ties):
    rng = np.random.default_rng(3)
    logits = rng.normal(size=(40, 16)).astype(np.float32)
    if ties:  # equal scores inside a group, across groups, at the cut
        logits = np.round(logits * 2) / 2
        logits[0] = 0.0
        logits[1, :8] = 1.0
    routing = moe.GroupRouting(4, 2, True, 2.5)
    # an identity router so that the logits are exactly these
    combine, w, idx = moe.router_topk(jnp.asarray(logits), jnp.eye(16), 4,
                                      routing)
    scores = np.asarray(jax.nn.sigmoid(jnp.asarray(logits)))
    want_idx, want_w = _route_oracle(scores, 4, 4, 2, True, 2.5)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-6)
    dense = np.zeros((40, 16), np.float32)
    np.put_along_axis(dense, want_idx, want_w, axis=1)
    np.testing.assert_allclose(np.asarray(combine), dense, rtol=1e-6)
    # the reference's router is the same function of the scores
    ridx, rw = ref.route(CFG.to_hf_dict(), jnp.asarray(scores))
    np.testing.assert_array_equal(np.asarray(ridx), want_idx)
    np.testing.assert_allclose(np.asarray(rw), want_w, rtol=1e-6)


def _expert_layer(params, cfg, h, first, count):
    """The program's expert layer (routed part of a told share + shared
    experts), layer 0 of the expert stack."""
    layer = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    routed = moe.moe_swiglu(
        h, layer["router"], layer["w_gate"][first:first + count],
        layer["w_up"][first:first + count],
        layer["w_down"][first:first + count], top_k=cfg.num_experts_per_tok,
        routing=moe.GroupRouting(cfg.n_group, cfg.topk_group,
                                 cfg.norm_topk_prob,
                                 cfg.routed_scaling_factor),
        held=(first, count))
    from cake_tpu.ops.mlp import swiglu

    return routed, swiglu(h, layer["ws_gate"], layer["ws_up"],
                          layer["ws_down"])


@pytest.mark.parametrize("rows", [6, 96], ids=["step-rows", "chunk-rows"])
def test_shares_add_up_to_the_uncut_layer(params, rows):
    """THE SHARE TEST: the routed parts that all 4 shares (4 of 16 experts
    each) give, plus the shared expert counted once, add up to the uncut
    layer, in the program and against the reference's uncut layer, at a
    decode step's few rows and at an admission chunk's many."""
    h = jax.random.normal(jax.random.PRNGKey(9), (1, rows, CFG.hidden_size))
    whole, shared = _expert_layer(params, CFG, h, 0, 16)
    parts = [_expert_layer(params, CFG, h, 4 * r, 4)[0] for r in range(4)]
    total = sum(parts) + shared
    np.testing.assert_allclose(total, whole + shared, atol=TIGHT, rtol=0)
    tensors = latent_hf_tensors(params, CFG)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(CFG.to_hf_dict(), tensors, "model.layers.1.",
                                h[0])
        one = ref.expert_layer(CFG.to_hf_dict(), tensors, "model.layers.1.",
                               h[0], only=range(4, 8))
    np.testing.assert_allclose(total[0], want, atol=TIGHT, rtol=0)
    # and one share alone is the reference's same share
    np.testing.assert_allclose(parts[1][0] + shared[0], one, atol=TIGHT,
                               rtol=0)
    assert float(jnp.abs(parts[1]).max()) > 0.01  # a share is not nothing


def test_shares_add_up_when_every_row_picks_the_same_experts(params):
    """The most skewed routing there is (96 equal rows, so 4 experts take
    every pair and 12 take none): two halves of the experts still add up
    to the whole, and equal rows get equal results."""
    h = jnp.broadcast_to(
        jax.random.normal(jax.random.PRNGKey(2), (1, 1, CFG.hidden_size)),
        (1, 96, CFG.hidden_size))
    routed, _ = _expert_layer(params, CFG, h, 0, 8)
    whole, _ = _expert_layer(params, CFG, h, 0, 16)
    rest, _ = _expert_layer(params, CFG, h, 8, 8)
    np.testing.assert_allclose(routed + rest, whole, atol=TIGHT, rtol=0)
    np.testing.assert_allclose(routed[0, 0], routed[0, -1], atol=1e-6)


def test_ep_axis_splits_the_told_share(params, want):
    """Under a real ep axis the same entry point takes the split from the
    axis: the mesh stream is the single-device stream."""
    from cake_tpu.runtime.batch_generator import BatchGenerator

    cfg = dataclasses.replace(CFG, eos_token_id=-1)
    prompts = [[5, 9, 2, 11], [3, 1, 4, 1, 5]]
    outs = []
    for ep in (1, 2):
        bg = BatchGenerator(cfg, params, settings=SamplerSettings(**GREEDY),
                            block_size=2, max_seq=64, ep=ep)
        bg.set_prompts(prompts)
        outs.append(bg.generate(6))
    assert outs[0] == outs[1]


def test_int8_projections_match_dequantized_oracle(params):
    """--quantize int8 at the small size: every projection through
    quant.dense (the absorbed form dequantizes W_kvb at trace level)
    against the same model over the explicitly dequantized weights."""
    from cake_tpu.ops.quant import (QuantizedLinear, dequantize_linear,
                                    quantize_params)

    q = quantize_params(params, bits=8)
    moe_stack = q["layers"]["moe"]
    assert isinstance(moe_stack["wkv_b"], QuantizedLinear)
    assert isinstance(moe_stack["ws_down"], QuantizedLinear)
    assert not isinstance(moe_stack["router"], QuantizedLinear)
    deq = jax.tree.map(
        lambda a: dequantize_linear(a, jnp.float32)
        if isinstance(a, QuantizedLinear) else a, q,
        is_leaf=lambda a: isinstance(a, QuantizedLinear))
    got, _ = _decode_all(q, CFG, TOKENS[:12], prefill=8)
    oracle, _ = _decode_all(deq, CFG, TOKENS[:12], prefill=8)
    np.testing.assert_allclose(got, oracle, atol=TIGHT, rtol=0)
    want = np.asarray(ref.logits(CFG.to_hf_dict(),
                                 latent_hf_tensors(deq, CFG), TOKENS[:12]))
    np.testing.assert_allclose(got, want[7:], atol=TIGHT, rtol=0)


def test_family_limits_are_refused_with_a_message(params):
    from cake_tpu.parallel.mesh import validate_shardable
    from cake_tpu.runtime.batch_generator import BatchGenerator

    with pytest.raises(ValueError, match="one stage"):
        validate_shardable(CFG, 2, 1)
    with pytest.raises(ValueError, match="slot layout"):
        BatchGenerator(CFG, params, settings=SamplerSettings(**GREEDY),
                       kv_layout="paged", max_seq=64)
    with pytest.raises(ValueError, match="int8 cache"):
        init_cache(CFG, quant="int8")
    # a routing correction bias is read since PR 51 (`noaux_tc`: the
    # choice on score + mlp.gate.e_score_correction_bias); a method
    # nothing here computes is still refused
    assert LlamaConfig.from_hf_dict(dict(
        CFG.to_hf_dict(), topk_method="noaux_tc")).router_bias
    assert not LlamaConfig.from_hf_dict(CFG.to_hf_dict()).router_bias
    with pytest.raises(ValueError, match="topk_method"):
        LlamaConfig.from_hf_dict(dict(CFG.to_hf_dict(),
                                      topk_method="seq_aux"))
    with pytest.raises(ValueError, match="latent-attention keys"):
        LlamaConfig.from_hf_dict(dict(CFG.to_hf_dict(), model_type="llama"))


def test_axk1_preset_holds_the_published_widths():
    """``axk1_ep16()``: the published file's numbers (ISSUE 28 lists them),
    one chip's share of 16, and what follows from them."""
    cfg = axk1_ep16()
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size) == (
        61, 7168, 163840)
    assert cfg.cache_row == (1, 512, 64)
    assert cfg.cache_row_values * cfg.jax_dtype.itemsize == 1152
    assert (cfg.router_experts, cfg.n_routed_experts, cfg.first_expert) == (
        192, 12, 0)
    m = 0.1 * 1 * math.log(32) + 1
    assert cfg.attn_scale == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    # the file a checkpoint of it carries reads back as the same config
    hf = cfg.to_hf_dict()
    assert hf["expert_share"] == {"n_routed_experts": 192, "ep": 16,
                                  "rank": 0}
    assert LlamaConfig.from_hf_dict(
        hf, max_seq_len=cfg.max_seq_len, dtype=cfg.dtype) == cfg


def test_hbm_budget_counts_the_latent_row_and_held_experts():
    """The published widths cut as the benchmark's configuration is (1
    dense + 7 expert layers, 12 of 192 experts, an eighth of the
    vocabulary): within 5% of 10.27 GiB of weights + 1.125 GiB of cache."""
    from cake_tpu.utils.memory import hbm_budget

    cfg = axk1_ep16(num_hidden_layers=8, vocab_size=20480, max_seq_len=4096)
    g = 1 << 30
    b = hbm_budget(cfg, batch=32, max_seq=4096)
    weights = b["layers"] + b["embed_replicated"] + b["head"]
    assert weights / g == pytest.approx(10.27, rel=0.05)
    assert b["kv_cache"] / g == pytest.approx(1.125, rel=1e-6)
    assert b["kv_cache"] == 32 * 4096 * 8 * 1152
    # ep divides the held experts only
    half = hbm_budget(cfg, batch=32, max_seq=4096, ep=2)
    experts = 7 * 12 * 3 * 7168 * 2048 * 2
    assert b["layers"] - half["layers"] == experts // 2
