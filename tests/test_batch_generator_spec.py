"""Batched serving speculation in ``BatchGenerator`` (a section of
``tests/test_batch_generator.py``, in a file of its own since PR 59):
greedy bit-identity, sampled invariance to composition, the window's
edge, admissions, an int8 cache, staged prefill, the prefix store, and
the fused chain of rounds. Shared: ``tests/batch_generator_kit.py``.
"""

import jax
import pytest

from cake_tpu.models.config import tiny
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.runtime.batch_generator import BatchGenerator
from cake_tpu.runtime.batch_generator import BatchGenerator as BG

from batch_generator_kit import CFG, GREEDY, params  # noqa: F401


# -- batched serving speculation ----------------------------------------------

def test_serving_speculation_greedy_bit_identical(params):
    """spec_k > 0: every live stream's n-gram proposals verified in one
    per-row dispatch; greedy streams are bit-identical to plain serving
    decode with tokens-per-dispatch > 1 on repeating streams."""
    prompts = [[5, 9, 2, 5, 9, 2, 5, 9], [3, 1, 4, 1, 3, 1, 4, 1],
               [7, 7, 2, 8]]
    for penalty in (1.0, 1.1):
        settings = SamplerSettings(temperature=0.0, repeat_penalty=penalty)
        plain = BG(CFG, params, settings=settings)
        plain.set_prompts([list(p) for p in prompts])
        want = plain.generate(10)
        spec = BG(CFG, params, settings=settings, spec_k=4)
        spec.set_prompts([list(p) for p in prompts])
        got = spec.generate(10)
        assert got == want, penalty
        st = spec.stats()
        assert st["spec_dispatches"] >= 1
        assert st["tokens_per_dispatch"] > 1.0


def test_serving_speculation_sampled_invariant_to_composition(params):
    """temperature > 0 with spec_k: a stream's rejection-sampling draws
    derive only from (its key, its positions, its context), so the same
    (seed, stream_id, prompt) emits identical tokens in any batch
    composition."""
    settings = SamplerSettings(temperature=0.9, top_k=20, seed=5)
    target = [5, 9, 2, 5, 9, 2, 5, 9]

    def run(other_prompts):
        g = BG(CFG, params, settings=settings, spec_k=4)
        g.set_prompts([list(target)] + [list(p) for p in other_prompts],
                      stream_ids=[42] + list(range(1, len(other_prompts) + 1)))
        return g.generate(8)[0]

    a = run([[3, 1, 4, 1]])
    b = run([[8, 8], [2, 6, 4], [9, 1, 1]])
    assert a == b
    assert all(0 <= t < CFG.vocab_size for t in a)


def test_serving_speculation_window_edge_falls_back(params):
    """A live stream too close to its window for K+1 fed slots forces the
    plain decode path — correct output, no overrun."""
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    long_prompt = [(i * 5) % 90 + 2 for i in range(56)]  # 56 of 64 window
    plain = BG(CFG, params, settings=settings)
    plain.set_prompts([list(long_prompt)])
    want = plain.generate(7)
    spec = BG(CFG, params, settings=settings, spec_k=6)
    spec.set_prompts([list(long_prompt)])
    got = spec.generate(7)
    assert got == want


_SPEC_ADMIT_STREAMS = ((0, [5, 9, 2, 5, 9, 2]), (9, [8, 2, 8, 2, 8, 2]))


def _drive_spec_admission(params, settings, plan=None):
    """Shared scaffold: spec serving, retire a slot, admit an arrival,
    decode on; returns the generator (the _SPEC_ADMIT_STREAMS ids live)."""
    g = BG(CFG, params, plan=plan, settings=settings, spec_k=4,
           admit_chunk=8)
    g.set_prompts([list(_SPEC_ADMIT_STREAMS[0][1]), [3, 1, 4, 1]],
                  stream_ids=[0, 1])
    for _ in range(3):
        g.step()
    g.streams[1].done = True
    g.enqueue(list(_SPEC_ADMIT_STREAMS[1][1]), stream_id=9)
    while g.pending_admissions():
        g.step()
    for _ in range(14):
        g.step()
    return g


def _assert_matches_solo_spec(params, settings, g, sid, prompt):
    got = next(s for s in g.streams
               if s.active and s.stream_id == sid).generated
    solo = BG(CFG, params, settings=settings, spec_k=4)
    solo.set_prompts([list(prompt)], stream_ids=[sid])
    want = solo.generate(len(got))[0]
    assert got == want[: len(got)] and got, sid


def test_serving_speculation_composes_with_admission(params):
    """enqueue during spec serving: the admitted stream's tokens match the
    same (seed, stream_id, prompt) served solo with speculation."""
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    g = _drive_spec_admission(params, settings)
    _assert_matches_solo_spec(params, settings, g,
                              *_SPEC_ADMIT_STREAMS[1])


def test_serving_speculation_with_int8_kv(params):
    """spec_k composes with the quantized KV cache."""
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    prompts = [[5, 9, 2, 5, 9, 2], [3, 1, 4, 1]]
    plain = BG(CFG, params, settings=settings, kv_quant="int8")
    plain.set_prompts([list(p) for p in prompts])
    want = plain.generate(8)
    spec = BG(CFG, params, settings=settings, kv_quant="int8", spec_k=4)
    spec.set_prompts([list(p) for p in prompts])
    assert spec.generate(8) == want


def test_generate_is_incremental(params):
    """Repeated generate(N) calls continue the streams — N MORE tokens
    each call (the pre-r4 contract, preserved by the ragged-emission
    rewrite)."""
    settings = SamplerSettings(**GREEDY)
    g = BG(CFG, params, settings=settings)
    g.set_prompts([[5, 9, 2], [3, 1, 4]])
    first = [list(s) for s in g.generate(4)]
    assert all(len(s) == 4 for s in first)
    second = g.generate(3)
    assert all(len(s) == 7 for s in second)
    for a, b in zip(first, second):
        assert b[:4] == a
    # same for the speculative path
    gs = BG(CFG, params, settings=settings, spec_k=4)
    gs.set_prompts([[5, 9, 2, 5, 9, 2], [3, 1, 4, 1]])
    f = [list(s) for s in gs.generate(4)]
    s2 = gs.generate(3)
    assert all(len(x) == 7 for x in s2)
    for a, b in zip(f, s2):
        assert b[:4] == a


@pytest.mark.parametrize("kv_quant", [None, "int8"])
def test_staged_batch_prefill_uses_pipelined_chunks(params, kv_quant):
    """On a staged mesh, set_prompts' batch prefill streams prompt chunks
    through the stages (GPipe microbatch mode) when the bucket divides —
    streams bit-identical to the 1-stage serving oracle, with and without
    the quantized KV cache."""
    from cake_tpu.parallel.mesh import MeshPlan

    settings = SamplerSettings(**GREEDY)
    prompts = [[5, 9, 2, 11, 3, 8], [3, 1, 4, 1, 5, 9], [7, 7, 2, 4]]
    flat = BG(CFG, params, settings=settings, kv_quant=kv_quant)
    flat.set_prompts([list(p) for p in prompts])
    want = flat.generate(8)
    plan = MeshPlan.build(CFG, num_stages=2, devices=jax.devices()[:2])
    staged = BG(CFG, params, plan=plan, settings=settings,
                kv_quant=kv_quant)
    staged.set_prompts([list(p) for p in prompts])
    assert staged._BatchGenerator__prefill_pipelined is not None
    assert staged.generate(8) == want


def test_spec_admission_staged_mesh_triple_composition(params):
    """The full r4 serving stack at once: staged mesh (interleaved verify +
    decode fallback), batched speculation, and continuous admission — the
    admitted stream and the survivors all match their solo oracles."""
    from cake_tpu.parallel.mesh import MeshPlan

    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    plan = MeshPlan.build(CFG, num_stages=2, devices=jax.devices()[:2])
    g = _drive_spec_admission(params, settings, plan=plan)
    assert g.stats()["spec_dispatches"] >= 1
    for sid, prompt in _SPEC_ADMIT_STREAMS:
        _assert_matches_solo_spec(params, settings, g, sid, prompt)


def test_spec_with_block_decode_preserves_emission_order(params):
    """spec_k composed with block_size > 1 (the CLI serving default): a
    spec round must never run while fused-block rows are still buffered,
    or later tokens would emit before buffered earlier ones (r4 review
    repro — the proposal-less first steps fall to the block path, then
    proposals appear mid-drain)."""
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    prompts = [[5, 9, 2, 5, 9, 2, 5, 9], [7, 7, 2, 8]]
    plain = BG(CFG, params, settings=settings)
    plain.set_prompts([list(p) for p in prompts])
    want = plain.generate(12)
    for block in (2, 4):
        g = BG(CFG, params, settings=settings, spec_k=4, block_size=block)
        g.set_prompts([list(p) for p in prompts])
        assert g.generate(12) == want, block


def test_generate_quota_under_skewed_acceptance(params):
    """One repetitive stream banking K+1 tokens per round must not starve
    a non-repetitive stream of its generate(N) quota (the safety cap
    scales with spec_k)."""
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    g = BG(CFG, params, settings=settings, spec_k=8)
    g.set_prompts([[5, 9, 2, 5, 9, 2, 5, 9], [7, 3, 8, 1]])
    outs = g.generate(6)
    assert all(len(o) == 6 for o in outs), [len(o) for o in outs]


def test_warm_admission_requires_pin_with_int8(params):
    from cake_tpu.ops.quant import quantize_params

    qp = quantize_params(params)
    settings = SamplerSettings(temperature=0.9, top_k=10)
    g = BG(CFG, qp, settings=settings)
    with pytest.raises(ValueError, match="backend pin"):
        g.warm_admission(8)
    # explicit pin or set_prompts-first both unblock it
    g2 = BG(CFG, qp, settings=settings, quant_backend="xla")
    g2.warm_admission(8)
    g3 = BG(CFG, qp, settings=settings)
    g3.set_prompts([[5, 9, 2]])
    g3.warm_admission(8)


def test_spec_serving_with_prefix_store_hit(params):
    """Speculation x prefix store: an arrival admitted through a prefix-
    cache HIT joins a speculating batch and still matches its solo spec
    oracle (the banked prefix row and the spec verify touch the same
    cache rows)."""
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    sysp = [(i * 7) % 100 + 2 for i in range(16)]
    g = BG(CFG, params, settings=settings, spec_k=4, admit_chunk=8,
           prefix_share_min=8, prefix_block=8)
    g.set_prompts([sysp + [5, 9, 2], sysp + [3, 1, 4]], stream_ids=[0, 1])
    for _ in range(3):
        g.step()
    g.streams[1].done = True
    new_prompt = sysp + [8, 8, 4]
    d0 = g.stats()["admit_dispatches"]
    g.enqueue(list(new_prompt), stream_id=9)
    while g.pending_admissions():
        g.step()
    assert g.stats()["admit_dispatches"] - d0 == 1  # prefix hit: 1 chunk
    assert g.stats()["prefix_hits"] >= 1
    for _ in range(10):
        g.step()
    _assert_matches_solo_spec(params, settings, g, 9, new_prompt)


def test_spec_chain_syncs_once_per_rounds_and_matches_host_loop(params):
    """spec_rounds=8 (fused chain) must emit the same greedy streams as
    spec_rounds=1 (per-round host loop) with ~rounds fewer syncs, and the
    chain must actually engage (spec_chains > 0)."""
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.batch_generator import BatchGenerator

    cfg = tiny(max_seq_len=256, eos_token_id=-1)
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    prompts = [[5, 9, 2, 5, 9, 2, 5, 9], [7, 1, 3, 7, 1, 3, 7, 1]]

    def run(rounds):
        g = BatchGenerator(cfg, params, settings=settings, spec_k=4,
                           spec_rounds=rounds)
        g.set_prompts([list(p) for p in prompts])
        for _ in range(30):
            g.step()
        return [list(s.generated[:28]) for s in g.streams], g.stats()

    want, st_host = run(1)
    got, st_fused = run(8)
    # the chain banks more tokens per step() call, so 30 steps yield
    # different counts; greedy bit-identity is on the common prefix
    for g_row, w_row in zip(got, want):
        n = min(len(g_row), len(w_row))
        assert n >= 20
        assert g_row[:n] == w_row[:n]
    assert st_host["spec_chains"] == 0
    assert st_fused["spec_chains"] >= 1
