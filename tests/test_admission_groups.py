"""A launch admits every arrival that waits, in one prefill program
(PR 37): every member of a launch gets the tokens, the first-token
logits and (where layers hold one) the recurrent state of its admission
alone, in each family the benchmark serves. What the cases share, the
tolerances and the other sections' files: ``tests/admission_kit.py``.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.runtime.batch_generator import BatchGenerator

from admission_kit import (  # noqa: F401
    FAMILIES, PROMPTS, TIGHT, _counts, _engine, _grown, _run, dense,
    every_waiting_arrival_rides, family,
)


# -- every member gets what its admission alone gives it ---------------------

@pytest.mark.parametrize("n", [2, 3])
@pytest.mark.parametrize("name", list(FAMILIES))
def test_each_member_of_a_launch_equals_its_admission_alone(family, name, n):
    cfg, params, alone = family(name)
    g = _engine(cfg, params)
    before = _counts()
    got = _run(g, [(p, 10 + i) for i, p in enumerate(PROMPTS[:n])], True)
    grown = _grown(before)
    assert grown["engine.admit_launches"] == 1, grown
    assert grown["engine.admissions_landed"] == n
    rows = 2 if n == 2 else 4  # three run as four, the first row twice
    assert (rows, 64 if n == 3 else 32) in g._warmed
    if any(ffn == "moe" for _, ffn in cfg.layer_kinds):
        assert grown["moe.admit_rows"] == rows * (64 if n == 3 else 32)
    for sid in range(10, 10 + n):
        want, have = alone[sid], got[sid]
        assert have["tokens"] == want["tokens"], (name, sid)
        np.testing.assert_allclose(have["logits"], want["logits"],
                                   atol=TIGHT, rtol=TIGHT)
        t = len(PROMPTS[sid - 10])
        kv = lambda c: [np.asarray(x)[..., :t, :] for x in
                        jax.tree.leaves((c.k, c.v))]
        for a, b in zip(kv(have["cache"]), kv(want["cache"])):
            np.testing.assert_allclose(a, b, atol=TIGHT, rtol=TIGHT)
        if "state" in cfg.cache_plan:
            # a short member's state and convolution tail: the bucket's
            # padding (its own 16 or 32 against the launch's 64) and the
            # repeated row have touched nothing
            for leaf in ("state", "conv"):
                a = getattr(have["cache"], leaf)
                b = getattr(want["cache"], leaf)
                assert np.abs(b).max() > 0
                np.testing.assert_allclose(a, b, atol=TIGHT, rtol=TIGHT)
        if "ring" in cfg.cache_plan:
            # a member's rings: its own newest rows and nothing of the
            # bucket's padding or of the repeated row
            for leaf in ("ring_k", "ring_v"):
                a = getattr(have["cache"], leaf)
                b = getattr(want["cache"], leaf)
                assert np.abs(b).max() > 0
                np.testing.assert_allclose(a, b, atol=TIGHT, rtol=TIGHT)


def test_sampled_members_draw_under_their_own_keys(dense):
    """Temperature 0.8: a stream's tokens depend on (seed, stream id,
    prompt) alone, whichever launch brought it in."""
    cfg, params = dense
    outs = []
    for together in (False, True):
        g = BatchGenerator(cfg, params, block_size=4, settings=SamplerSettings(
            temperature=0.8, top_k=40, seed=7))
        g.set_prompts([[4, 4, 4]] * 4)
        g.warm_admission(9)
        g.step()
        for s in g.streams:
            g.finish(s.stream_id)
        got = _run(g, [(p, 10 + i) for i, p in enumerate(PROMPTS[:3])],
                   together)
        outs.append({sid: got[sid]["tokens"] for sid in (10, 11, 12)})
    assert outs[0] == outs[1]
