"""Xing4's wide residual stream through ``BatchGenerator`` (section (c) of
``tests/test_xing4.py``, in a file of its own since PR 59): block decode,
admissions in buckets and in bands, streams of different length, a slot
reused, each against the plain reference. Shared: ``tests/xing4_kit.py``.
"""

from __future__ import annotations

import jax
import pytest

from cake_tpu.models import llama
from cake_tpu.obs import metrics

from xing4_kit import (  # noqa: F401
    CFG, PROMPTS, _engine, _is_the_references_argmax, _run, params, tensors,
)


# -- (c) the engine ------------------------------------------------------------------


def test_batch_generator_streams_match_reference(params, tensors):
    """Three streams of different lengths through BatchGenerator: a
    bucketed batch prefill, per-row positions, block decode; each stream's
    tokens are the reference's argmax. The gauges say what a token holds:
    four hidden vectors between sub-layers, the latent row in the cache."""
    reg = metrics.registry()
    bg = _engine(params, PROMPTS[:3])
    outs = bg.generate(13)
    for prompt, out in zip(PROMPTS[:3], outs):
        _is_the_references_argmax(tensors, prompt, list(out)[:13])
    assert reg.gauge("model.hc_mult").value == 4
    assert reg.gauge("resid.token_bytes").value == 4 * 64 * 4
    assert CFG.resid_token_bytes == 4 * 64 * 4
    assert reg.gauge("cache.row_bytes").value == 4 * (16 + 8)
    assert reg.gauge("cache.layer_planes").value == 3
    assert reg.gauge("model.loop_passes").value == 1
    assert bg.stats()["tokens_emitted"] == 3 * 13


def test_every_other_model_reads_one_hidden_vector(params):
    from cake_tpu.models.config import tiny_mla_moe

    cfg = tiny_mla_moe(max_seq_len=256, eos_token_id=-1)
    bg = _engine(llama.init_params(cfg, jax.random.PRNGKey(0)), [[5, 9, 2]],
                 cfg=cfg)
    bg.generate(2)
    reg = metrics.registry()
    assert reg.gauge("model.hc_mult").value == 1
    assert reg.gauge("resid.token_bytes").value == 64 * 4


@pytest.mark.parametrize("admit_chunk", [None, 4],
                         ids=["one-bucket", "bands-of-4"])
def test_a_reused_slot_gives_the_references_tokens(params, tensors,
                                                   admit_chunk):
    """SLOT REUSE in the engine: a short stream admitted into the slot a
    long one left gives the reference's tokens, whether its admission is
    one bucket or bands of 4 rows (a prompt longer than a chunk: the wide
    stream of a band is carried by nothing but the cache); the neighbour
    never notices."""
    long, short = PROMPTS[4], PROMPTS[5]
    bg = _engine(params, [long, PROMPTS[3]], ids=[1, 2],
                 admit_chunk=admit_chunk)
    got = _run(bg, {6: lambda e: (e.finish(1), e.enqueue(short, 3))},
               steps=30)
    assert len(got[3]) >= 10
    _is_the_references_argmax(tensors, short, got[3][:10])
    _is_the_references_argmax(tensors, PROMPTS[3], got[2][:12])


def test_admissions_among_live_streams_ride_one_program(params, tensors,
                                                        monkeypatch):
    """An admission among live streams, then two arrivals that wait
    together and ride ONE prefill program of two rows: each stream's
    tokens are the single-stream reference's."""
    from cake_tpu.runtime import batch_generator as engine

    monkeypatch.setattr(engine, "GROUP_SHAPES", ((2, 64),))
    launches = metrics.registry().counter("engine.admit_launches")
    bg = _engine(params, [PROMPTS[1], PROMPTS[0], [4, 4, 4], [4, 4, 5]],
                 ids=[10, 11, 90, 91])
    bg.warm_admission(40)
    before = launches.value
    events = {
        2: lambda e: (e.finish(90), e.enqueue(PROMPTS[3], 12)),
        8: lambda e: (e.finish(91), e.finish(11),
                      e.enqueue(PROMPTS[2][:40], 13),
                      e.enqueue(PROMPTS[5], 14)),
    }
    got = _run(bg, events, steps=36)
    assert launches.value - before == 2  # 12 alone, 13 and 14 together
    for sid, prompt in ((10, PROMPTS[1]), (12, PROMPTS[3]),
                        (13, PROMPTS[2][:40]), (14, PROMPTS[5])):
        assert len(got[sid]) >= 10, sid
        _is_the_references_argmax(tensors, prompt, got[sid][:10])
