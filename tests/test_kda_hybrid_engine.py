"""The delta-rule + latent-attention hybrid through ``BatchGenerator``
(the engine section of ``tests/test_kda_hybrid.py``, in a file of its own
since PR 59): streams against the reference, a reused slot's fresh
state, admissions mid-flight, the state gauges and expert counters, the
``ep`` axis. Shared: ``tests/kda_hybrid_kit.py``.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from cake_tpu.obs import metrics
from cake_tpu.testing import reference_kda_mla_moe as ref

from kda_hybrid_kit import CFG, TIGHT, _engine, params, tensors  # noqa: F401


# -- the engine --------------------------------------------------------------------


def _run(bg, events=(), steps=40):
    """Step the engine; ``events``: ``{step: callable(bg)}``. Returns every
    stream's generated ids by stream id."""
    events = dict(events)
    out: dict[int, list[int]] = {}
    for i in range(steps):
        if i in events:
            events[i](bg)
        bg.step()
        for s in bg.streams:
            if s.active and s.stream_id >= 0:
                out[s.stream_id] = list(s.generated)
    return out


def _alone(params, prompt, n, **kw):
    bg = _engine(params, [prompt], **kw)
    return bg.generate(n)[0]


PROMPTS = [[5, 9, 2, 11], [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5], [7, 7, 2],
           [8, 6, 7, 5, 3, 0, 9]]


def test_batch_generator_streams_match_reference(params, tensors):
    """Four streams of different lengths through BatchGenerator (a bucketed
    batch prefill whose padding may not touch a state, per-row positions,
    block decode): each stream's greedy tokens are the reference's own
    greedy continuation, by its logits' argmax with a margin check."""
    bg = _engine(params, PROMPTS)
    outs = bg.generate(9)
    for prompt, out in zip(PROMPTS, outs):
        full = np.array(prompt + list(out))
        logits = np.asarray(ref.logits(CFG.to_hf_dict(), tensors, full))
        for j, tok in enumerate(out):
            at = logits[len(prompt) - 1 + j]
            assert at.max() - at[tok] <= TIGHT, (prompt, j)
    assert bg.stats()["tokens_emitted"] == 4 * 9


@pytest.mark.parametrize("admit_chunk", [None, 4],
                         ids=["one-chunk", "chunks-of-4"])
def test_slot_reuse_starts_from_a_fresh_state(params, admit_chunk):
    """SLOT REUSE: a short stream admitted into the slot a long one left
    gives the tokens a fresh engine gives it (the slot's state and tail
    have no frontier that would hide the old stream's), whether its
    admission is one chunk or chunks of 4 that carry state and tail
    between them; ``kda.state_resets`` counts the admission."""
    long, short = PROMPTS[1] * 3, [4, 8, 15, 16, 23, 42, 10]
    resets = metrics.registry().counter("kda.state_resets")
    before = resets.value
    bg = _engine(params, [long, PROMPTS[0]], ids=[1, 2],
                 admit_chunk=admit_chunk)
    got = _run(bg, {6: lambda e: (e.finish(1), e.enqueue(short, 3))},
               steps=30)
    assert resets.value - before == 1
    assert len(got[3]) >= 8
    assert got[3][:8] == _alone(params, short, 8)
    # the neighbour never noticed
    assert got[2][:12] == _alone(params, PROMPTS[0], 12)


def test_four_streams_with_admissions_mid_flight_equal_each_alone(params):
    events = {
        3: lambda e: e.enqueue(PROMPTS[2], 12),
        5: lambda e: e.finish(10),
        9: lambda e: (e.finish(11), e.enqueue(PROMPTS[3], 13)),
    }
    bg = _engine(params, PROMPTS[:2], ids=[10, 11], admit_chunk=4)
    got = _run(bg, events, steps=36)
    for sid, prompt in ((12, PROMPTS[2]), (13, PROMPTS[3])):
        assert len(got[sid]) >= 8
        assert got[sid][:8] == _alone(params, prompt, 8), sid
    assert got[10] == _alone(params, PROMPTS[0], 9)[:len(got[10])]
    assert got[11] == _alone(params, PROMPTS[1], 24)[:len(got[11])]


def test_state_gauges_and_moe_counters(params):
    """The new family's counters go through the same path as the latent
    family's: pairs of live rows only, and the cache's gauges read off the
    allocated buffers (rows over the latent layer alone)."""
    reg = metrics.registry()
    names = ("moe.local_pairs", "moe.routed_pairs", "moe.decode_steps")
    cfg = dataclasses.replace(CFG, n_routed_experts=4, router_experts=16,
                              first_expert=4)
    p = dict(params, layers={
        name: {k: (v[:, 4:8] if k in ("w_gate", "w_up", "w_down")
                   and "router" in stack else v) for k, v in stack.items()}
        for name, stack in params["layers"].items()})
    bg = _engine(p, [[5, 9, 2], [3, 1, 4, 1]], cfg=cfg)
    before = {n: reg.counter(n).value for n in names}
    bg.generate(9)
    bg.drain()
    got = {n: reg.counter(n).value - before[n] for n in names}
    steps = got["moe.decode_steps"]
    assert steps >= 8
    assert got["moe.routed_pairs"] == steps * 2 * 4 * 3  # rows x k x layers
    assert 0 < got["moe.local_pairs"] < got["moe.routed_pairs"]
    h, d = cfg.num_attention_heads, cfg.head_dim
    per_stream = 3 * (h * d * d * 4 + 3 * 3 * h * d * 4)
    assert reg.gauge("cache.state_bytes_per_stream").value == per_stream
    assert reg.gauge("cache.state_bytes").value == 2 * per_stream
    assert reg.gauge("cache.row_bytes").value == 4 * (16 + 8)
    assert reg.gauge("cache.bytes").value == (
        2 * per_stream + 1 * 2 * 64 * 4 * (16 + 8))


def test_ep_axis_splits_the_told_share(params):
    """Under a real ep axis the same entry point takes the split from the
    axis: the mesh stream is the single-device stream."""
    prompts = [[5, 9, 2, 11], [3, 1, 4, 1, 5]]
    outs = []
    for ep in (1, 2):
        bg = _engine(params, prompts, block_size=2, ep=ep)
        outs.append(bg.generate(6))
    assert outs[0] == outs[1]
