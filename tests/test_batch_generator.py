"""Multi-stream serving: concurrent batch decode must reproduce each stream's
single-run output exactly (the per-row positions + per-stream keys contract).

The reference is single-request only (SURVEY.md §0); these tests hold the
TPU-native batch plane to the strongest bar available: stream output depends
only on (seed, stream_id, prompt) — invariant to batch composition, dp
layout, block size, and the other streams in the batch.
"""

import jax
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import tiny
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.runtime.batch_generator import BatchGenerator
from cake_tpu.runtime.generator import LlamaGenerator
from cake_tpu.runtime.batch_generator import BatchGenerator as BG

CFG = tiny(max_seq_len=64)
GREEDY = dict(temperature=0.0, repeat_penalty=1.1)
PROMPTS = [[5, 9, 2, 11], [3, 1, 4, 1, 5, 9], [7, 7, 2]]


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(5))


def _single_stream(params, prompt, n, settings):
    g = LlamaGenerator(CFG, params, settings=settings)
    g.set_prompt(prompt)
    out = []
    for i in range(n):
        t = g.next_token(i)
        out.append(t.id)
        if t.is_end_of_stream:
            break
    return out


def _batch_run(params, prompts, n, settings, stream_ids=None, **kw):
    g = BatchGenerator(CFG, params, settings=settings, **kw)
    g.set_prompts(prompts, stream_ids=stream_ids)
    return g.generate(n)


@pytest.mark.parametrize("dp,stages,tp", [(1, 1, 1), (2, 1, 1), (2, 2, 2),
                                          (4, 2, 1)])
def test_greedy_batch_matches_single_runs(params, dp, stages, tp):
    """Different-length prompts decode concurrently; every stream's greedy
    tokens equal its standalone single-stream run (positions are per-row, so
    right-padding another stream's prompt cannot shift RoPE/mask geometry)."""
    settings = SamplerSettings(**GREEDY)
    got = _batch_run(params, PROMPTS, 8, settings, dp=dp, num_stages=stages,
                     tp=tp)
    for prompt, stream in zip(PROMPTS, got):
        assert stream == _single_stream(params, prompt, 8, settings)


def test_greedy_block_decode_matches(params):
    settings = SamplerSettings(**GREEDY)
    want = [_single_stream(params, p, 9, settings) for p in PROMPTS]
    got = _batch_run(params, PROMPTS, 9, settings, dp=2, block_size=4)
    assert got == want


def test_sampled_stream_invariant_to_batch_composition(params):
    """A sampled stream is keyed by (seed, stream_id): running it alone,
    with different companions, or on a different dp layout yields the same
    tokens."""
    settings = SamplerSettings(temperature=0.9, top_k=20, seed=11)
    full = _batch_run(params, PROMPTS, 8, settings, dp=1)
    # same streams, different layout
    assert _batch_run(params, PROMPTS, 8, settings, dp=2) == full
    # stream 1 alone, pinned to its stream_id
    alone = _batch_run(params, [PROMPTS[1]], 8, settings, stream_ids=[1], dp=1)
    assert alone == [full[1]]
    # different companion set, same ids for the survivors
    pair = _batch_run(params, [PROMPTS[0], PROMPTS[2]], 8, settings,
                      stream_ids=[0, 2], dp=2)
    assert pair == [full[0], full[2]]


def test_sampled_block_size_invariant(params):
    settings = SamplerSettings(temperature=0.9, top_k=20, seed=11)
    assert (
        _batch_run(params, PROMPTS, 8, settings, dp=1, block_size=4)
        == _batch_run(params, PROMPTS, 8, settings, dp=1)
    )


def test_eos_stops_stream_independently(params):
    """A stream hitting EOS goes quiet while others continue."""
    settings = SamplerSettings(**GREEDY)
    g = BG(CFG, params, settings=settings, dp=1)
    # find the greedy continuation of prompt 0 and use its 3rd token as EOS
    ref = _single_stream(params, PROMPTS[0], 6, settings)
    eos_cfg = tiny(max_seq_len=64, eos_token_id=ref[2])
    g = BG(eos_cfg, params, settings=settings, dp=1)
    g.set_prompts([PROMPTS[0], PROMPTS[1]])
    outs = [g.step() for _ in range(6)]
    # stream 0 emitted exactly 3 tokens, the last flagged EOS
    s0 = [row[0] for row in outs if row[0] is not None]
    assert len(s0) == 3 and s0[-1].is_end_of_stream
    # stream 1 kept decoding its own (unchanged) stream
    s1 = [row[1].id for row in outs if row[1] is not None]
    assert s1 == _single_stream(params, PROMPTS[1], 6, settings)[:len(s1)]
    assert len(s1) == 6


def test_short_stream_survives_long_stream_window_exhaustion(params):
    """A long stream hitting max_seq goes quiet (window_full => done); the
    short stream keeps decoding into its own remaining KV room, with tokens
    identical to its standalone run (code-review r2 regression)."""
    settings = SamplerSettings(**GREEDY)
    cfg = tiny(max_seq_len=32)
    long_prompt = list(range(2, 28))  # 26 tokens -> only 6 slots left
    short_prompt = [5, 9, 2]
    for block_size in (1, 4):
        g = BG(cfg, params, settings=settings, dp=1, block_size=block_size)
        g.set_prompts([long_prompt, short_prompt])
        outs = g.generate(20)
        assert len(outs[0]) == 32 - len(long_prompt)  # filled its window
        assert len(outs[1]) == 20  # unbothered
        solo = BG(cfg, params, settings=settings, dp=1, block_size=block_size)
        solo.set_prompts([short_prompt], stream_ids=[1])
        assert solo.generate(20)[0] == outs[1]


def test_window_edge_stream_keeps_batch_on_block_dispatch(params):
    """Fused-block eligibility is per-row: one stream 2 tokens from its
    window must NOT force the whole batch into single-step dispatches.
    Dispatch count stays ~N/block_size, the edge stream
    fills its window with exactly its solo tokens, and mid-window streams
    are bit-identical to their solo runs."""
    settings = SamplerSettings(**GREEDY)
    cfg = tiny(max_seq_len=32)
    block = 4
    edge_prompt = list(range(2, 28))  # 26 tokens -> 6 slots left (< 2 blocks)
    mids = [[5, 9, 2], [3, 1, 4], [7, 7, 2], [2, 8, 1]]
    g = BG(cfg, params, settings=settings, dp=1, block_size=block)
    g.set_prompts([edge_prompt] + mids)
    calls = {"block": 0, "single": 0}
    real_block, real_single = g._decode_block, g._decode_single

    def count_block(*a, **k):
        calls["block"] += 1
        return real_block(*a, **k)

    def count_single(*a, **k):
        calls["single"] += 1
        return real_single(*a, **k)

    g._decode_block, g._decode_single = count_block, count_single
    n = 20
    outs = g.generate(n)
    assert calls["single"] == 0
    # first token from prefill; one more block is in flight, enqueued
    # before the last landed rows went out
    assert calls["block"] == -(-(n - 1) // block) + 1
    assert g._inflight is not None
    assert len(outs[0]) == 32 - len(edge_prompt)  # edge filled its window
    solo_edge = _single_stream(params, edge_prompt, n, settings)
    # solo run raises window exhaustion at the same boundary; compare prefix
    assert outs[0] == solo_edge[: len(outs[0])]
    for prompt, got in zip(mids, outs[1:]):
        assert got == _single_stream(params, prompt, n, settings)


@pytest.mark.parametrize("block_size", [1, 4])
def test_admit_refills_finished_slot(params, block_size):
    """Continuous-batching-lite: when a stream finishes, admit() splices a
    new prompt into its slot mid-run. The admitted stream reproduces its
    solo run exactly (per-row positions AND per-row token indices), and the
    untouched neighbor stream is bit-identical to its own solo run."""
    settings = SamplerSettings(temperature=0.9, top_k=20, seed=11)
    cfg = tiny(max_seq_len=32)
    long_prompt = list(range(2, 28))  # 26 tokens -> done after 6
    g = BG(cfg, params, settings=settings, dp=1, block_size=block_size)
    g.set_prompts([long_prompt, PROMPTS[1]], stream_ids=[0, 1])
    for _ in range(12):  # until its last token has been handed out (a
        if not g.streams[0].done or g._pending_rows:  # landing hands out
            g.step()                                  # no row)
    assert g.streams[0].done and not g.streams[1].done

    slot, first = g.admit(PROMPTS[2], stream_id=7)
    assert slot == 0
    collected = [first.id]
    for _ in range(12):
        row = g.step()
        if row[0] is not None:
            collected.append(row[0].id)

    solo = BG(cfg, params, settings=settings, dp=1, block_size=block_size)
    solo.set_prompts([PROMPTS[2]], stream_ids=[7])
    assert collected == solo.generate(24)[0][: len(collected)]

    s1 = g.streams[1].generated
    solo1 = BG(cfg, params, settings=settings, dp=1, block_size=block_size)
    solo1.set_prompts([PROMPTS[1]], stream_ids=[1])
    assert s1 == solo1.generate(24)[0][: len(s1)]


def test_admit_into_dummy_slot_before_first_step(params):
    """admit() may claim a dp-padding dummy slot before the first step();
    the admitted stream's first token is returned by admit() once, not
    re-emitted by the first step() (code-review r2 regression)."""
    settings = SamplerSettings(**GREEDY)
    g = BG(CFG, params, settings=settings, dp=2)
    g.set_prompts(PROMPTS)  # 3 prompts -> 4 rows, slot 3 is a dummy
    slot, first = g.admit(PROMPTS[0], stream_id=9)
    assert slot == 3
    rows = [g.step() for _ in range(8)]
    got = [first.id] + [r[slot].id for r in rows if r[slot] is not None]
    want = _single_stream(params, PROMPTS[0], len(got), settings)
    assert got == want
    # exactly one copy of the first token
    assert g.streams[slot].generated == got


def test_admit_flush_preserves_streamed_tokens(params):
    """Tokens buffered by block decode at admission time still reach the
    streaming step() consumer (queued rows), not just the generated lists."""
    settings = SamplerSettings(**GREEDY)
    cfg = tiny(max_seq_len=32)
    long_prompt = list(range(2, 28))
    g = BG(cfg, params, settings=settings, dp=1, block_size=4)
    g.set_prompts([long_prompt, PROMPTS[1]], stream_ids=[0, 1])
    received = {0: [], 1: [], 7: []}

    def collect(row, admitted_slot=None):
        for i, t in enumerate(row):
            if t is not None:
                sid = g.streams[i].stream_id
                received[sid].append(t.id)

    for _ in range(12):  # until its last token has been handed out (a
        if not g.streams[0].done or g._pending_rows:  # landing hands out
            collect(g.step())                         # no row)
    slot, first = g.admit(PROMPTS[2], stream_id=7)
    received[7].append(first.id)
    for _ in range(8):
        collect(g.step())
    g.drain()
    while g._pending_rows:
        collect(g.step())
    # every recorded token reached the streaming consumer, in order
    for s in g.streams:
        assert received[s.stream_id] == s.generated


def test_admit_requires_free_slot(params):
    settings = SamplerSettings(**GREEDY)
    g = BG(CFG, params, settings=settings, dp=1)
    g.set_prompts(PROMPTS)
    with pytest.raises(RuntimeError, match="no free slot"):
        g.admit([1, 2, 3], stream_id=9)


def test_enqueue_interleaves_admission_with_decode(params):
    """Real continuous batching: a queued arrival's prefill advances one
    chunk per step ALONGSIDE the decode dispatches (the running batch never
    stalls behind a full prompt pass), as one replicated row (no dp
    discarded copies). The admitted stream and the untouched neighbor are
    both bit-identical to their solo runs."""
    settings = SamplerSettings(**GREEDY)
    new_prompt = [2, 8, 1, 7, 6, 5, 4, 3]  # 8 tokens -> 2 chunks of 4
    g = BG(CFG, params, settings=settings, dp=1, admit_chunk=4)
    g.set_prompts(PROMPTS[:2])
    rows = [g.step(), g.step()]  # first token + one decode
    g.streams[0].done = True  # slot 0 frees up

    decode_calls = {"n": 0}
    real_single = g._decode_single

    def count_single(*a, **k):
        decode_calls["n"] += 1
        return real_single(*a, **k)

    g._decode_single = count_single
    admit_calls = {"n": 0}
    real_admit = g._admit_prefill  # property: compiles the program

    def count_admit(*a, **k):
        admit_calls["n"] += 1
        return real_admit(*a, **k)

    g._BatchGenerator__admit_prefill = count_admit

    g.enqueue(new_prompt, stream_id=7)
    assert g.pending_admissions() == 1
    rows.append(g.step())  # chunk 1 of the admission + a decode dispatch
    assert admit_calls["n"] == 1 and decode_calls["n"] == 1
    assert rows[-1][1] is not None  # the neighbor stream kept decoding
    rows.append(g.step())  # chunk 2 (final): emits the first token row
    assert admit_calls["n"] == 2 and g.pending_admissions() == 0
    assert rows[-1][0] is not None and rows[-1][1] is None
    for _ in range(4):
        rows.append(g.step())

    admitted = [r[0].id for r in rows[3:] if r[0] is not None]
    solo = BG(CFG, params, settings=settings, dp=1)
    solo.set_prompts([new_prompt], stream_ids=[7])
    assert admitted == solo.generate(len(admitted))[0][: len(admitted)]

    neighbor = [r[1].id for r in rows if r[1] is not None]
    assert neighbor == _single_stream(params, PROMPTS[1], len(neighbor),
                                      settings)


def test_enqueue_waits_for_free_slot_and_drains_fifo(params):
    """Arrivals queue FIFO; admission starts only once a slot frees."""
    settings = SamplerSettings(**GREEDY)
    g = BG(CFG, params, settings=settings, dp=1)
    g.set_prompts(PROMPTS[:2])
    g.step()
    g.enqueue([2, 8, 1], stream_id=5)
    g.enqueue([4, 4, 4], stream_id=6)
    g.step()
    assert g.pending_admissions() == 2  # no free slot yet
    g.streams[0].done = True
    g.step()  # whole bucketed prompt in one dispatch (admit_chunk=None)
    assert g.pending_admissions() == 1  # first arrival admitted
    sids = sorted(s.stream_id for s in g.streams)
    assert 5 in sids and 6 not in sids
    g.streams[1].done = True
    g.step()
    assert g.pending_admissions() == 0
    assert sorted(s.stream_id for s in g.streams) == [5, 6]


def test_finish_retires_stream_and_frees_slot(params):
    """The public retirement API (the serving plane's slot free): finish()
    stops the stream's emission, makes its slot admissible to the next
    arrival, and reports retirement races honestly (False on an unknown or
    already-done id — normal for a server, not an error)."""
    settings = SamplerSettings(**GREEDY)
    g = BG(CFG, params, settings=settings, dp=1)
    g.set_prompts(PROMPTS[:2])
    g.step()
    assert g.finish(0) is True
    assert g.streams[0].done
    assert g.finish(0) is False  # already retired
    assert g.finish(42) is False  # never admitted
    row = g.step()
    assert row[0] is None and row[1] is not None  # retired slot is silent
    g.enqueue([2, 8, 1], stream_id=5)
    g.step()
    assert g.pending_admissions() == 0  # admitted into the freed slot
    assert g.streams[0].stream_id == 5
    # the neighbor stream was never perturbed
    neighbor = [r[1].id for r in [row] if r[1] is not None]
    assert neighbor == _single_stream(params, PROMPTS[1], 2, settings)[1:2]


def test_finish_cancels_queued_and_staging_arrivals(params):
    """finish() covers the arrival's WHOLE lifecycle: an id still waiting
    in the FIFO, or mid-admission in the staging cache, is dropped before
    it can splice in — a server cancelling a request whose prefill never
    completed must not leak an ownerless stream into a slot."""
    settings = SamplerSettings(**GREEDY)
    g = BG(CFG, params, settings=settings, dp=1, admit_chunk=4)
    g.set_prompts(PROMPTS[:2])
    g.step()
    # queued, never started: no slot free, the arrival sits in the FIFO
    g.enqueue([2, 8, 1, 7], stream_id=9)
    assert g.pending_admissions() == 1
    assert g.finish(9) is True
    assert g.pending_admissions() == 0
    # mid-staging: free a slot, let one 4-token chunk of an 8-token
    # arrival dispatch, then retire it before the final chunk
    g.finish(0)
    g.enqueue([2, 8, 1, 7, 6, 5, 4, 3], stream_id=10)
    g.step()  # chunk 1 of 2 into the staging cache
    assert g.pending_admissions() == 1  # in flight
    assert g.finish(10) is True
    assert g.pending_admissions() == 0
    for _ in range(3):
        g.step()
    assert all(s.stream_id != 10 for s in g.streams)  # never spliced
    # the freed slot still serves the next arrival
    g.enqueue([4, 4, 4], stream_id=11)
    g.step()
    assert any(s.stream_id == 11 for s in g.streams)


def test_admit_chunk_must_divide_max_seq(params):
    """A chunk that doesn't divide the window is rejected at construction:
    a near-window prompt would round up PAST max_seq and the final chunk's
    clamped KV write would silently corrupt committed slots (repro'd:
    admit_chunk=6/max_seq=32 with a 31-token prompt flipped the admitted
    stream's first token)."""
    cfg = tiny(max_seq_len=32)
    with pytest.raises(ValueError, match="positive divisor of"):
        BG(cfg, params, settings=SamplerSettings(**GREEDY), dp=1,
           admit_chunk=6)
    with pytest.raises(ValueError, match="positive divisor of"):
        BG(cfg, params, settings=SamplerSettings(**GREEDY), dp=1,
           admit_chunk=0)
    with pytest.raises(ValueError, match="positive divisor of"):
        BG(cfg, params, settings=SamplerSettings(**GREEDY), dp=1,
           admit_chunk=-4)
    # dividing chunk + near-window prompt: exact admission
    settings = SamplerSettings(**GREEDY)
    near = list(range(2, 2 + 29))  # 29 tokens into a 32 window
    g = BG(cfg, params, settings=settings, dp=1, admit_chunk=8)
    g.set_prompts([[5, 9, 2]])
    g.step()
    g.streams[0].done = True
    g.enqueue(near, stream_id=3)
    rows = [g.step() for _ in range(6)]
    got = [r[0].id for r in rows if r[0] is not None]
    solo = BG(cfg, params, settings=settings, dp=1)
    solo.set_prompts([near], stream_ids=[3])
    assert got == solo.generate(len(got))[0][: len(got)]


def test_admit_with_queued_arrivals_exceeding_slots_raises(params):
    """admit() with more arrivals than free slots must raise, not hang:
    the drain loop detects a stuck queue head (no staging, no free slot)
    and removes the caller's arrival (regression: infinite busy loop)."""
    settings = SamplerSettings(**GREEDY)
    g = BG(CFG, params, settings=settings, dp=1)
    g.set_prompts(PROMPTS[:2])
    g.step()
    g.streams[0].done = True  # exactly one free slot
    g.enqueue([2, 8, 1], stream_id=5)  # will take the only slot
    with pytest.raises(RuntimeError, match="no free slot"):
        g.admit([4, 4, 4], stream_id=6)
    # the queued arrival was admitted on the way; ours was removed
    assert g.pending_admissions() == 0
    assert 5 in [s.stream_id for s in g.streams]
    assert 6 not in [s.stream_id for s in g.streams]


def test_enqueue_with_dp_sharded_batch(params):
    """The admission row is replicated over dp (batch_replicated staging
    cache), so continuous admission works on a dp-sharded batch too."""
    settings = SamplerSettings(**GREEDY)
    new_prompt = [2, 8, 1]
    g = BG(CFG, params, settings=settings, dp=2, admit_chunk=4)
    g.set_prompts(PROMPTS[:2])
    g.step()
    g.streams[0].done = True
    g.enqueue(new_prompt, stream_id=11)
    rows = [g.step() for _ in range(6)]
    admitted = [r[0].id for r in rows if r[0] is not None]
    assert admitted
    solo = BG(CFG, params, settings=settings, dp=2)
    solo.set_prompts([new_prompt], stream_ids=[11])
    assert admitted == solo.generate(len(admitted))[0][: len(admitted)]


def test_shared_prefix_prefilled_once_bit_identical(params):
    """Prompts sharing a long common prefix (the system-prompt case):
    the prefix is prefilled ONCE as a single replicated row and broadcast,
    remainders prefill at the offset — every stream's tokens are
    bit-identical to the unshared path, and the batched prefill sees only
    the remainder lengths."""
    settings = SamplerSettings(**GREEDY)
    sys_prompt = [7, 3, 9, 1, 4, 8, 2, 6] * 2  # 16 shared tokens
    prompts = [sys_prompt + tail
               for tail in ([5, 9, 2], [3, 1, 4, 1], [8, 8])]

    def run(share_min):
        g = BG(CFG, params, settings=settings, dp=1, block_size=4,
               prefix_share_min=share_min)
        calls = {}
        orig = g._prefill

        def spy(p, toks, cache, last, *rest):
            calls["prefill_T"] = toks.shape[1]
            return orig(p, toks, cache, last, *rest)

        g._prefill = spy
        g.set_prompts(prompts)
        return g.generate(8), calls, g

    unshared, calls_u, _ = run(share_min=0)
    shared, calls_s, g = run(share_min=8)
    assert shared == unshared
    # unshared path buckets the FULL prompts; shared path never calls the
    # plain prefill at all (prefix row + offset remainder program)
    assert calls_u["prefill_T"] >= 19
    assert "prefill_T" not in calls_s
    assert g.stats()["admit_dispatches"] >= 1  # the prefix row dispatch


def test_shared_prefix_skips_when_prefix_short_or_absent(params):
    settings = SamplerSettings(**GREEDY)
    g = BG(CFG, params, settings=settings, dp=1, prefix_share_min=32)
    g.set_prompts([[5, 9, 2], [5, 9, 3]])  # 2-token prefix < threshold
    out = g.generate(6)
    for prompt, got in zip([[5, 9, 2], [5, 9, 3]], out):
        assert got == _single_stream(params, prompt, 6, settings)


def test_arrival_reuses_cached_prefix_row(params):
    """An enqueued arrival that opens with the batch's shared prefix
    starts from a copy of the cached prefix KV row and prefills only its
    remainder — fewer admission dispatches, tokens bit-identical to the
    from-scratch admission."""
    settings = SamplerSettings(**GREEDY)
    prefix = [(i * 7) % 100 + 2 for i in range(16)]
    prompts = [prefix + [5, 9, 2], prefix + [3, 1, 4]]
    new_prompt = prefix + [8, 8, 4]

    def run(share_min):
        g = BG(CFG, params, settings=settings, dp=1, admit_chunk=8,
               prefix_share_min=share_min)
        g.set_prompts(prompts)
        g.step()
        g.streams[0].done = True
        d0 = g.stats()["admit_dispatches"]
        g.enqueue(list(new_prompt), stream_id=9)
        rows = [g.step() for _ in range(8)]
        toks = [r[0].id for r in rows if r[0] is not None]
        return toks, g.stats()["admit_dispatches"] - d0

    toks_scratch, n_scratch = run(share_min=0)
    toks_reuse, n_reuse = run(share_min=8)
    solo = BG(CFG, params, settings=settings, dp=1)
    solo.set_prompts([list(new_prompt)], stream_ids=[9])
    want = solo.generate(12)[0]
    # same stream either way; the reuse run admits earlier so the same
    # step budget yields MORE of it
    assert toks_scratch == want[: len(toks_scratch)]
    assert toks_reuse == want[: len(toks_reuse)]
    assert len(toks_reuse) >= len(toks_scratch)
    # scratch prefills ceil(19/8)=3 chunks; reuse only the 3-token
    # remainder (1 chunk)
    assert n_scratch == 3 and n_reuse == 1
    # non-matching arrival falls back to from-scratch admission
    g = BG(CFG, params, settings=settings, dp=1, admit_chunk=8,
           prefix_share_min=8)
    g.set_prompts(prompts)
    g.step()
    g.streams[0].done = True
    g.enqueue([4, 4, 4, 4], stream_id=7)
    rows = [g.step() for _ in range(6)]
    toks = [r[0].id for r in rows if r[0] is not None]
    solo = BG(CFG, params, settings=settings, dp=1)
    solo.set_prompts([[4, 4, 4, 4]], stream_ids=[7])
    assert toks == solo.generate(len(toks))[0][: len(toks)]


def test_shared_prefix_near_window_does_not_overrun(params):
    """The remainder bucket is capped at the room above the prefix: a long
    shared prefix with near-window prompts must not clamp-overwrite
    committed prefix KV (regression: t_pad bucketed past max_seq - lcp)."""
    settings = SamplerSettings(**GREEDY)
    cfg = tiny(max_seq_len=64)
    prefix = [(i * 7) % 100 + 2 for i in range(40)]
    prompts = [prefix + [5, 9, 2] * 7 + [1, 2],   # 63 tokens total
               prefix + [3, 1, 4]]
    g = BG(cfg, params, settings=settings, dp=1, prefix_share_min=16)
    g.set_prompts(prompts)
    out = g.generate(4)
    for prompt, got in zip(prompts, out):
        solo = BG(cfg, params, settings=settings, dp=1, prefix_share_min=0)
        solo.set_prompts([prompt], stream_ids=[prompts.index(prompt)])
        assert got == solo.generate(4)[0][: len(got)]


def test_shared_prefix_with_identical_prompts(params):
    """All-identical prompts (the dummy-padding shape): lcp caps one short
    of the prompt so every row keeps a remainder token."""
    settings = SamplerSettings(**GREEDY)
    p = [7, 3, 9, 1, 4, 8, 2, 6, 5, 9, 2, 4]
    g = BG(CFG, params, settings=settings, dp=2, prefix_share_min=4)
    g.set_prompts([list(p), list(p), list(p)])  # pads to 4 with a dummy
    out = g.generate(6)
    want = _single_stream(params, p, 6, settings)
    for got in out:
        assert got == want


def test_serving_stats_track_dispatches_and_tokens(params):
    """stats() reports the serving counters: emitted tokens, decode and
    admission dispatch counts, tokens-per-dispatch, and throughput."""
    settings = SamplerSettings(**GREEDY)
    g = BG(CFG, params, settings=settings, dp=1, block_size=4)
    g.set_prompts(PROMPTS[:2])
    for _ in range(9):
        g.step()
    g.streams[0].done = True
    g.enqueue([2, 8, 1], stream_id=5)
    for _ in range(12):  # it is launched at the next block boundary
        g.step()
    st = g.stats()
    assert st["tokens_emitted"] > 0
    # 2 streams x 9 steps + admission-era rows, all accounted
    assert st["decode_dispatches"] >= 2  # ceil(8/4) blocks at minimum
    assert st["admit_dispatches"] == 1
    assert st["tokens_per_dispatch"] > 1  # block fusion amortizes
    assert st["busy_s"] > 0 and st["wall_s"] >= st["busy_s"] * 0.5
    assert st["aggregate_tok_s"] > 0
    assert st["streams_live"] >= 1 and st["pending_admissions"] == 0


def test_batch_padding_to_dp_multiple(params):
    """3 prompts on dp=2 pad to 4 rows with an inactive dummy; outputs still
    match, dummy never surfaces."""
    settings = SamplerSettings(**GREEDY)
    got = _batch_run(params, PROMPTS, 6, settings, dp=2)
    assert len(got) == 3
    for prompt, stream in zip(PROMPTS, got):
        assert stream == _single_stream(params, prompt, 6, settings)


def test_arrivals_with_distinct_prefixes_each_hit_their_own_row(params):
    """Generalized prefix store (r4): TWO different system prompts among
    arrivals each hit their OWN cached prefix row — not just the batch's
    single shared prefix. Every admitted arrival banks its block-aligned
    prefix, so the second arrival per system prompt prefills only its
    remainder (1 chunk instead of 3), bit-identical to a solo run."""
    settings = SamplerSettings(**GREEDY)
    sys_a = [(i * 7) % 100 + 2 for i in range(16)]
    sys_b = [(i * 11) % 100 + 3 for i in range(16)]
    arrivals = [
        (sys_a + [5, 9, 2], 10),   # scratch; banks sys_a
        (sys_b + [3, 1, 4], 11),   # scratch; banks sys_b
        (sys_a + [8, 8, 4], 12),   # hits the sys_a row
        (sys_b + [6, 2, 7], 13),   # hits the sys_b row
    ]

    g = BG(CFG, params, settings=settings, dp=1, admit_chunk=8,
           prefix_share_min=8, prefix_block=8)
    g.set_prompts([[4, 4, 4], [6, 6, 6]])
    g.step()
    admit_cost, emitted = {}, {}
    for prompt, sid in arrivals:
        for s in g.streams:
            s.done = True  # free a slot for the next arrival
        d0 = g.stats()["admit_dispatches"]
        g.enqueue(list(prompt), stream_id=sid)
        while g.pending_admissions():
            g.step()
        admit_cost[sid] = g.stats()["admit_dispatches"] - d0
        for _ in range(4):  # decode a few tokens before the slot is reused
            g.step()
        s = next(s for s in g.streams if s.active and s.stream_id == sid)
        emitted[sid] = list(s.generated)
    # first-of-a-prefix pays the full ceil(19/8)=3 chunks; repeats pay 1
    assert admit_cost[10] == 3 and admit_cost[11] == 3
    assert admit_cost[12] == 1 and admit_cost[13] == 1
    assert g.stats()["prefix_hits"] == 2
    assert g.stats()["prefix_entries"] == 2

    # bit-identity: each arrival's emitted tokens match a solo run of the
    # same (seed, stream_id, prompt) — hit or miss, any admission order
    for prompt, sid in arrivals:
        got = emitted[sid]
        assert got, sid
        solo = BG(CFG, params, settings=settings, dp=1)
        solo.set_prompts([list(prompt)], stream_ids=[sid])
        want = solo.generate(len(got))[0]
        assert got == want[: len(got)], (sid, got, want)


def test_prefix_store_lru_eviction(params):
    """The store is capped: a third distinct prefix evicts the least
    recently used row and later arrivals with the evicted prefix prefill
    from scratch again (correct, just unaided)."""
    settings = SamplerSettings(**GREEDY)
    mk = lambda seed: [(i * seed) % 90 + 2 for i in range(16)]
    g = BG(CFG, params, settings=settings, dp=1, admit_chunk=8,
           prefix_share_min=8, prefix_block=8, prefix_cache_entries=2)
    g.set_prompts([[4, 4, 4], [6, 6, 6]])
    g.step()
    sid = 20
    for seed in (7, 11, 13):  # third insert evicts the seed-7 row
        for s in g.streams:
            s.done = True
        g.enqueue(mk(seed) + [1, 2], stream_id=sid)
        sid += 1
        while g.pending_admissions():
            g.step()
    assert g.stats()["prefix_entries"] == 2
    for s in g.streams:
        s.done = True
    d0 = g.stats()["admit_dispatches"]
    g.enqueue(mk(7) + [9, 9], stream_id=sid)  # evicted: full prefill
    while g.pending_admissions():
        g.step()
    assert g.stats()["admit_dispatches"] - d0 == 3
    assert g.stats()["prefix_hits"] == 0


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


def test_adaptive_block_bit_identical(params):
    """The adaptive ladder (block doubling on an empty arrival queue) must
    not change any stream's greedy output — same per-row positions and
    in-program key schedule regardless of dispatch granularity."""
    settings = SamplerSettings(**GREEDY)
    want = [_single_stream(params, p, 12, settings) for p in PROMPTS]
    got = _batch_run(params, PROMPTS, 12, settings, dp=1, block_size=2,
                     block_size_max=8)
    assert got == want


def test_adaptive_block_sampled_invariant(params):
    """Sampled streams too: the per-row absolute token index keys every
    draw, so ladder growth cannot perturb the sampling schedule."""
    settings = SamplerSettings(temperature=0.9, top_k=20, seed=11)
    assert (
        _batch_run(params, PROMPTS, 8, settings, dp=1, block_size=2,
                   block_size_max=8)
        == _batch_run(params, PROMPTS, 8, settings, dp=1)
    )


def test_adaptive_block_grows_then_snaps_back_on_arrival(params):
    """The ladder doubles while no arrival waits and snaps back to the
    base block the moment one is queued (admission latency stays one base
    block), then the admitted stream is bit-identical to its solo run."""
    settings = SamplerSettings(**GREEDY)
    cfg = tiny(max_seq_len=64, eos_token_id=-1)
    g = BG(cfg, params, settings=settings, block_size=2, block_size_max=8)
    g.set_prompts([list(PROMPTS[0]), list(PROMPTS[1])])
    for _ in range(8):
        g.step()
    # queue empty for several dispatches: the ladder grew past the base
    assert g._adaptive > g.block_size
    g.streams[0].done = True
    g.enqueue(list(PROMPTS[2]), stream_id=7)
    live_pos = [g._pos[i] for i, s in enumerate(g.streams)
                if s.active and not s.done]
    assert g._pick_block_size(live_pos) == g.block_size  # snap-back
    for _ in range(40):
        g.step()
        if all(s.done or not s.active for s in g.streams):
            break
        if g.streams[0].stream_id == 7 and len(
                g.streams[0].generated) >= 6:
            break
    admitted = next(s for s in g.streams if s.stream_id == 7)
    gen7 = LlamaGenerator(cfg, params, settings=settings)
    gen7.set_prompt(list(PROMPTS[2]))
    # stream_id drives the key; greedy here so id does not matter
    want = [gen7.next_token(i).id for i in range(len(admitted.generated))]
    assert admitted.generated == want[:len(admitted.generated)]
    assert len(admitted.generated) >= 4


def test_adaptive_block_headroom_cap_near_window(params):
    """Streams near their window edge must halve the grown block back down
    the ladder instead of dispatching mostly clamped overrun writes; every
    stream still fills its window exactly."""
    settings = SamplerSettings(**GREEDY)
    cfg = tiny(max_seq_len=32, eos_token_id=-1)
    g = BG(cfg, params, settings=settings, block_size=2, block_size_max=16)
    g.set_prompts([[5, 9, 2, 11], [3, 1, 4, 1]])
    single = LlamaGenerator(cfg, params, settings=settings)
    single.set_prompt([5, 9, 2, 11])
    n = 32 - 4  # window minus prompt
    want = [single.next_token(i).id for i in range(n)]
    out = g.generate(n)
    assert out[0] == want
    assert all(s.done for s in g.streams)  # window-full, cleanly


def test_warm_blocks_precompiles_ladder(params):
    """warm_blocks compiles every ladder rung outside the serving window
    and leaves the live state untouched (outputs discarded)."""
    settings = SamplerSettings(**GREEDY)
    g = BG(CFG, params, settings=settings, block_size=2, block_size_max=8)
    g.set_prompts([list(p) for p in PROMPTS])
    before = [list(s.generated) for s in g.streams]
    g.warm_blocks()
    assert [list(s.generated) for s in g.streams] == before
    progs = g._BatchGenerator__block_progs
    assert {s for s, _ in progs} == {4, 8}
    want = [_single_stream(params, p, 10, settings) for p in PROMPTS]
    assert g.generate(10) == want


def test_block_size_max_rounds_down_to_ladder(params):
    """A non-power-of-two max rounds down to base*2^k so the headroom
    halving always lands on a compiled rung."""
    settings = SamplerSettings(**GREEDY)
    g = BG(CFG, params, settings=settings, block_size=3, block_size_max=13)
    assert g.block_size_max == 12
    g = BG(CFG, params, settings=settings, block_size=4, block_size_max=4)
    assert g.block_size_max == 4
    g = BG(CFG, params, settings=settings, block_size=4)
    assert g.block_size_max == 4


def _boundary_counters():
    from cake_tpu.obs import metrics

    reg = metrics.registry()
    return (reg.counter("engine.boundaries").value,
            reg.counter("engine.boundaries_ahead").value,
            reg.histogram("engine.boundary_ms").snapshot().get("count", 0))


def test_one_order_bit_identical_with_admission(params):
    """The order of work at a block boundary (the next block is dispatched
    from the device's feedback token before the landed rows go out; an
    arrival's prefill is launched before them and lands after them) must
    not change any stream's tokens against single-step dispatches, chunked
    admission included -- the device feedback token is exactly the host's,
    and an admission drains an in-flight block's rows before the slot
    changes meaning."""
    settings = SamplerSettings(**GREEDY)
    new_prompt = [2, 8, 1, 7, 6, 5, 4, 3]

    def run(**kw):
        g = BG(CFG, params, settings=settings, **kw)
        g.set_prompts([list(PROMPTS[0]), list(PROMPTS[1])])
        engaged = False
        for _ in range(8):
            g.step()
            engaged |= g._inflight is not None
        g.streams[0].done = True
        g.enqueue(list(new_prompt), stream_id=7)
        for _ in range(24):
            g.step()
        return engaged, {s.stream_id: list(s.generated) for s in g.streams}

    _, want = run(block_size=1)
    for kw in (dict(block_size=2, block_size_max=8),
               dict(block_size=2, block_size_max=8, admit_chunk=4),
               dict(block_size=4)):
        engaged, got = run(**kw)
        assert engaged, kw  # a block was in flight while rows went out
        assert set(got) == set(want) == {1, 7}
        for sid in got:
            n = min(len(got[sid]), len(want[sid]))
            assert n >= 4 and got[sid][:n] == want[sid][:n], (kw, sid)


@pytest.mark.parametrize("case", ["spec", "block1"])
def test_nothing_is_enqueued_ahead_where_the_host_acts_between_steps(
        params, case):
    """Batched speculation runs rounds between fetches, and ``block_size``
    1 has no block to enqueue: the engine knows both from its own state
    and keeps the order dispatch, fetch, hand out (a live guide does the
    same: tests/test_constrain.py). No switch says so, and none is left:
    ``BatchGenerator(lookahead=...)`` is gone."""
    settings = SamplerSettings(**GREEDY)
    with pytest.raises(TypeError, match="lookahead"):
        BG(CFG, params, settings=settings, lookahead=True)
    kw = dict(spec_k=4, block_size=4) if case == "spec" else dict(
        block_size=1)
    g = BG(CFG, params, settings=settings, **kw)
    g.set_prompts([list(p) for p in PROMPTS])
    b0, a0, _ = _boundary_counters()
    got = {i: [] for i in range(len(PROMPTS))}
    for _ in range(40):
        row = g.step()
        assert g._inflight is None  # nothing left in flight by a step()
        for i, tok in enumerate(row):
            if tok is not None:
                got[i].append(tok.id)
    b1, a1, _ = _boundary_counters()
    assert a1 == a0  # no boundary's next program left before its rows
    if case == "block1":
        assert b1 == b0  # no block ever landed
    for i, p in enumerate(PROMPTS):
        assert len(got[i]) >= 10
        assert got[i] == _single_stream(params, p, len(got[i]), settings)


def test_drain_records_the_inflight_block(params):
    """drain() at a measurement/shutdown boundary fetches the in-flight
    block without dispatching more; its tokens continue the stream's
    oracle sequence exactly, and reach a consumer that keeps stepping."""
    settings = SamplerSettings(**GREEDY)
    g = BG(CFG, params, settings=settings, block_size=2, block_size_max=4)
    g.set_prompts([list(PROMPTS[0])])
    handed = []
    for _ in range(4):
        handed += [t.id for t in g.step() if t is not None]
    assert g._inflight is not None
    dispatches_before = g.stats()["decode_dispatches"]
    before = len(g.streams[0].generated)
    g.drain()
    assert g._inflight is None
    got = list(g.streams[0].generated)
    assert len(got) > before
    assert g.stats()["decode_dispatches"] == dispatches_before  # no new work
    want = _single_stream(params, PROMPTS[0], len(got), settings)
    assert got == want[: len(got)]
    # recorded is not handed out: the rows wait for whoever steps on, and
    # are counted as emitted when they leave
    assert g.stats()["tokens_emitted"] == len(handed) < len(got)
    while len(handed) < len(got):
        handed += [t.id for t in g.step() if t is not None]
    assert handed[: len(got)] == got


def _recording(g, log):
    """Wrap the two calls that enqueue a device program so that each
    leaves its name in ``log``; step() results are logged by the caller."""
    block, prefill = g._dispatch_block, g._admit_prefill

    def dispatch_block(size):
        log.append("block")
        return block(size)

    def admit_prefill(*args):
        log.append("prefill")
        return prefill(*args)

    g._dispatch_block = dispatch_block
    g._BatchGenerator__admit_prefill = admit_prefill
    return g


@pytest.mark.parametrize("arrival", [False, True])
def test_the_next_program_is_enqueued_before_a_landed_row_leaves(params,
                                                                 arrival):
    """At a block boundary the device gets its next program first: the
    call in which a block lands hands out nothing (so the server's pass
    up to the enqueue carries no delivery), the next call enqueues the
    next block -- or, when an arrival waits and a slot is free, launches
    its prefill -- and only then returns the landed block's first row. An
    admission's device half (sampler, splice, the block that follows)
    leaves behind its prefill in that same call, before any of those rows
    (PR 54); its stream is installed and its first token queued after the
    block's last row has gone out."""
    settings = SamplerSettings(**GREEDY)
    log: list = []
    g = BG(CFG, params, settings=settings, block_size=4)
    g.warm_admission(8)
    g.set_prompts([list(PROMPTS[0]), list(PROMPTS[1])], stream_ids=[0, 1])
    if arrival:
        g.streams[1].done = True  # a slot retired, as the scheduler does
    _recording(g, log)
    g._landing_runs = lambda: False  # (the prefill has run: no block held)
    b0, a0, n0 = _boundary_counters()

    def pump(n):
        for _ in range(n):
            row = g.step()
            log.append(("row", [g.streams[i].stream_id
                                for i, t in enumerate(row) if t is not None]))

    pump(2)  # first tokens; then block 1 is dispatched, lands, all-None
    assert log[-2:] == ["block", ("row", [])]
    if arrival:
        g.enqueue([2, 8, 1], stream_id=7)  # came in while block 1 ran
    del log[:]
    pump(1)
    first = ["prefill", "block"] if arrival else ["block"]
    live = [0] if arrival else [0, 1]
    assert log == [*first, ("row", live)]  # enqueued, THEN a row left
    del log[:]
    pump(3)
    assert log == [("row", live)] * 3  # rows only
    if arrival:
        # spliced and served, not installed: the old stream's rows go out
        assert g._staging is None and g._landed and g._inflight is not None
        assert g.streams[1].stream_id == 1 and g._live()[1]
        del log[:]
        pump(1)  # rows are out: the stream is installed, its first token
        assert log == [("row", [7])]  # leaves; block 2 left long before
        assert g.streams[1].stream_id == 7 and g._inflight is not None
    del log[:]
    pump(1)  # block 2 lands: hands out nothing
    assert log == [("row", [])]
    b1, a1, n1 = _boundary_counters()
    assert b1 - b0 == a1 - a0 == n1 - n0 == 1  # one boundary, ahead


def test_an_arrival_during_a_block_gets_its_first_token_after_that_block(
        params):
    """The decision "another block or an admission?" is taken at the
    boundary, after the caller has had its turn to enqueue: an arrival
    that came in while block N ran is launched in place of block N+1 (it
    waits at most the running block, as before), not one block later.
    Counted in dispatches, not seconds."""
    settings = SamplerSettings(**GREEDY)
    log: list = []
    g = BG(CFG, params, settings=settings, block_size=4)
    g.warm_admission(8)
    g.set_prompts([list(PROMPTS[0]), [1]], stream_ids=[0, 99])
    g.streams[1].done = True
    _recording(g, log)
    g.step()
    g.step()  # block 1 runs and lands inside this call
    assert log == ["block"]
    g.enqueue([2, 8, 1], stream_id=7)  # the server's _admit(), next pass
    first = None
    for _ in range(12):
        for slot, tok in enumerate(g.step()):
            if tok is not None and g.streams[slot].stream_id == 7 \
                    and first is None:
                first = list(log)
    # by its first token: its prefill, and the block that follows it was
    # enqueued before that token's row left; block 2 did not run first
    assert first == ["block", "prefill", "block"]
    got = g.streams[1].generated
    assert got == _single_stream(params, [2, 8, 1], len(got), settings)


def test_an_arrival_under_a_running_block_is_launched_behind_it(params):
    """An arrival handed over while a block is in flight (its client came
    back during the hand-out of the block before) is launched at once:
    its prefill follows the running block on the device with no host time
    between them, which is what a decision at that block's boundary would
    have chosen too. Its device half (splice, the next block) leaves in
    the first step() after that block has landed, before the block's rows
    (PR 54); its stream is installed after they have all gone out (PR
    21's gate, on the host half alone), the landing's boundary counts as
    enqueued ahead, and every stream gets the ids single steps give."""
    settings = SamplerSettings(**GREEDY)
    log: list = []
    g = BG(CFG, params, settings=settings, block_size=4)
    g.warm_admission(8)
    g.set_prompts([list(PROMPTS[0]), [1]], stream_ids=[0, 99])
    g.streams[1].done = True
    _recording(g, log)
    g._landing_runs = lambda: False  # (the prefill has run: no block held)
    got: dict[int, list[int]] = {}

    def pump(n):
        for _ in range(n):
            row = g.step()
            log.append(("row", [g.streams[i].stream_id
                                for i, t in enumerate(row) if t is not None]))
            for i, t in enumerate(row):
                if t is not None:
                    got.setdefault(g.streams[i].stream_id, []).append(t.id)

    pump(3)  # first tokens; block 1 lands; block 2 leaves, then row 1
    assert log[-2:] == ["block", ("row", [0])] and g._inflight is not None
    g.enqueue([2, 8, 1], stream_id=7)  # block 2 is running
    b0, a0, n0 = _boundary_counters()
    del log[:]
    pump(1)
    assert log == ["prefill", ("row", [0])]  # launched behind block 2
    pump(2)  # block 1's last rows
    pump(1)  # block 2 lands: its successor is enqueued already
    assert log[-1] == ("row", []) and _boundary_counters() == (
        b0 + 1, a0 + 1, n0 + 1)
    assert g._staging is not None and g.streams[1].stream_id == 99
    del log[:]
    pump(4)  # splice and block 3 leave behind the prefill, then the rows
    assert log == ["block"] + [("row", [0])] * 4
    assert g._landed and g.streams[1].stream_id == 99
    pump(1)  # they are out: its stream is installed, its first token leaves
    assert log[-1] == ("row", [7]) and "block" not in log[1:]
    pump(12)
    for sid, prompt in ((0, PROMPTS[0]), (7, [2, 8, 1])):
        assert len(got[sid]) >= 4
        assert got[sid] == _single_stream(params, prompt, len(got[sid]),
                                          settings)


def _served(g, n_steps):
    """Pump step() as a server does, mapping a row's slots to streams
    when it gets the row."""
    got: dict[int, list[int]] = {}
    for _ in range(n_steps):
        for slot, tok in enumerate(g.step()):
            if tok is not None:
                got.setdefault(g.streams[slot].stream_id, []).append(tok.id)
    return got


@pytest.mark.parametrize("case", ["eos", "finish", "window", "paged",
                                  "prefix"])
def test_the_one_order_gives_the_ids_single_steps_give(params, case):
    """EOS inside a block, ``finish()`` mid-block, a stream at its
    window's edge, the paged layout and the prefix store: with the next
    program enqueued before the landed rows go out, every stream is
    handed exactly the ids that single-step dispatches hand it."""
    import dataclasses

    settings = SamplerSettings(**GREEDY)
    cfg, kw, prompts = CFG, {}, PROMPTS
    arrivals = [([2, 8, 1, 7], 7)]
    if case == "eos":
        solo = _single_stream(params, PROMPTS[0], 12, settings)
        k = next(i for i in range(3, 9) if solo[i] not in solo[:i])
        cfg = dataclasses.replace(CFG, eos_token_id=solo[k])
    elif case == "window":
        cfg = tiny(max_seq_len=16)
    elif case == "paged":
        kw = dict(kv_layout="paged", kv_page_size=8)
    elif case == "prefix":
        shared = list(range(3, 40))
        prompts = [shared + [5], [3, 1, 4]]
        arrivals = [(shared + [9, 2], 7), (shared + [4], 8)]
        kw = dict(prefix_share_min=8, prefix_block=8)

    def run(block_size):
        g = BG(cfg, params, settings=settings, block_size=block_size, **kw)
        g.set_prompts([list(p) for p in prompts])
        got = _served(g, 6)  # block 4: one of its rows is still to go out
        retire = 1
        frozen = {}
        for ids, sid in arrivals:
            g.finish(retire)
            frozen[retire] = len(got.get(retire, []))
            g.enqueue(list(ids), stream_id=sid)
            for s, toks in _served(g, 30).items():
                got.setdefault(s, []).extend(toks)
            retire = sid
        for s, n in frozen.items():  # nothing reached a retired stream
            assert len(got.get(s, [])) == n, (s, block_size)
        return got, {s.stream_id: (s.done, list(s.generated))
                     for s in g.streams}

    want, _ = run(1)
    got, recorded = run(4)
    assert set(got) == set(want)
    for sid in want:
        n = min(len(got[sid]), len(want[sid]))
        assert n >= 3 and got[sid][:n] == want[sid][:n], (case, sid)
    for sid, (_, ids) in recorded.items():
        # what a stream was handed is what the engine recorded for it
        assert got[sid] == ids[: len(got[sid])], sid
    if case == "eos":  # ended inside a block, where single steps end it
        assert got[0] == want[0] and got[0][-1] == cfg.eos_token_id
    if case == "window":  # filled its window, and not a row more
        for sid in (0, 2):
            assert got[sid] == want[sid]
            assert len(PROMPTS[sid]) + len(got[sid]) == cfg.max_seq_len


def test_finish_mid_block_leaves_no_trace_of_what_was_not_handed_out(params):
    """A landed block's rows are recorded at once; ``finish()`` on a stream
    whose rows still wait takes them back: they are neither handed out,
    nor counted as emitted, nor in ``generated``, nor in the
    detokenizer's state (a server's ``decode_rest()`` tail must not hold
    text of tokens past the budget)."""
    class Tok:
        def decode(self, ids):
            return "".join(chr(ord("a") + i % 26) + " " for i in ids)

    settings = SamplerSettings(**GREEDY)
    g = BG(CFG, params, settings=settings, tokenizer=Tok(), block_size=8)
    g.set_prompts([list(PROMPTS[0]), list(PROMPTS[1])], stream_ids=[0, 1])
    handed = _served(g, 5)  # first tokens, the landing call, three rows
    assert len(handed[0]) == 4 and len(g.streams[0].generated) == 9
    e0 = g.stats()["tokens_emitted"]
    assert g.finish(0) is True
    assert g.streams[0].generated == handed[0]
    assert g.streams[0].detok.tokens == handed[0]
    assert g.finish(0) is False  # over, as its caller has seen
    more = _served(g, 8)
    assert 0 not in more and len(more[1]) >= 5
    assert g.stats()["tokens_emitted"] == e0 + len(more[1])


def test_carried_or_uploaded_is_no_new_program_signature(params):
    """A steady boundary feeds the block program the frontiers and token
    indices the last block returned (device values: no upload); after a
    retirement or a splice they are uploaded again. Both come under one
    sharding, so a retirement WITHOUT an admission behind it -- which a
    warm-up need not contain -- compiles nothing in the serving window."""
    settings = SamplerSettings(**GREEDY)
    g = BG(CFG, params, settings=settings, block_size=4)
    g.set_prompts([list(p) for p in PROMPTS])
    uploads = []
    real = jax.device_put
    _served(g, 12)
    assert g._carry[0] is not None
    n0 = g._decode_block_jit._cache_size()
    import unittest.mock as mock
    with mock.patch.object(jax, "device_put",
                           lambda x, *a, **k: (uploads.append(1),
                                               real(x, *a, **k))[1]):
        _served(g, 10)
        assert not uploads  # steady: one program call, nothing uploaded
        g.finish(1)
        got = _served(g, 12)
        assert uploads  # a row changed: its frontier goes out again
    assert g._decode_block_jit._cache_size() == n0
    for sid in (0, 2):
        full = g.streams[sid].generated
        assert full == _single_stream(params, PROMPTS[sid], len(full),
                                      settings)
    assert 1 not in got


def test_plain_run_enqueues_ahead_at_every_boundary(params):
    """``engine.boundaries_ahead == engine.boundaries`` on a plain run:
    every landed block's successor (a block or an arrival's prefill) left
    before any of its rows did, and ``engine.boundary_ms`` was observed
    once a boundary."""
    settings = SamplerSettings(**GREEDY)
    b0, a0, n0 = _boundary_counters()
    g = BG(CFG, params, settings=settings, block_size=4)
    g.set_prompts([list(p) for p in PROMPTS])
    _served(g, 12)
    g.finish(1)
    g.enqueue([2, 8, 1, 7], stream_id=7)
    _served(g, 24)
    b1, a1, n1 = _boundary_counters()
    assert b1 - b0 == a1 - a0 == n1 - n0 >= 5


class _BoundaryLog(list):
    """What ``engine.boundary_ms`` and the three histograms of its parts
    observed, ``[(series, ms)]``, taken after each ``step()`` from the
    growth of their counts and sums (a step observes each at most
    once)."""

    NAMES = ("engine.boundary_ms", "engine.boundary_emit_ms",
             "engine.boundary_pass_ms", "engine.boundary_enqueue_ms")

    def __init__(self):
        from cake_tpu.obs import metrics

        self._hists = [metrics.registry().histogram(n) for n in self.NAMES]
        self._at = [(h.count, h.sum) for h in self._hists]

    def step(self, g):
        row = g.step()
        for k, h in enumerate(self._hists):
            count, total = self._at[k]
            assert h.count - count in (0, 1)
            if h.count > count:
                self.append((h.name, h.sum - total))
            self._at[k] = (h.count, h.sum)
        return row


@pytest.fixture
def boundary_log():
    return _BoundaryLog()


def test_a_boundary_a_later_step_closed_leaves_its_three_parts(
        params, boundary_log):
    """Where the device waited for its next program (the ``step()``
    after the landing one enqueued it), the boundary leaves one
    observation in each of ``engine.boundary_emit_ms``, ``_pass_ms`` and
    ``_enqueue_ms``: recording the rows, the caller's pass between the
    two calls, the enqueuing call up to its program. Boundary by boundary
    they add up to no more than ``engine.boundary_ms`` took (it runs on
    to the enqueuing step()'s return), and the caller's pass is in the
    second part."""
    import time

    g = BG(CFG, params, settings=SamplerSettings(**GREEDY), block_size=4)
    g.set_prompts([list(p) for p in PROMPTS])
    for _ in range(22):
        row = boundary_log.step(g)
        if not any(t is not None for t in row):
            time.sleep(0.02)  # the caller's pass after a landing
    closed = [i for i, (name, _) in enumerate(boundary_log)
              if name == "engine.boundary_ms"]
    assert len(closed) >= 4 and len(boundary_log) == 4 * len(closed)
    for i in closed:  # the whole first, then its parts
        (_, whole), *parts = boundary_log[i:i + 4]
        assert [n for n, _ in parts] == [
            "engine.boundary_emit_ms", "engine.boundary_pass_ms",
            "engine.boundary_enqueue_ms"]
        emit, between, enqueue = (ms for _, ms in parts)
        assert min(emit, between, enqueue) > 0.0
        assert emit + between + enqueue <= whole
        assert 20.0 <= between < whole


def test_a_boundary_an_admission_was_launched_ahead_of_leaves_no_part(
        params, boundary_log):
    """A block that lands with an arrival's prefill launched behind it
    closes its boundary at once: ``engine.boundary_ms`` observes it all
    the same, the three parts nothing, so their means are those of the
    boundaries at which the device waited."""
    g = BG(CFG, params, settings=SamplerSettings(**GREEDY), block_size=4)
    g.warm_admission(8)
    g.set_prompts([list(PROMPTS[0]), [1]], stream_ids=[0, 99])
    g.streams[1].done = True
    for _ in range(3):  # first tokens; block 1 lands; block 2 leaves
        boundary_log.step(g)
    assert g._inflight is not None
    waited = [n for n, _ in boundary_log]
    assert waited.count("engine.boundary_ms") == 1 and len(waited) == 4
    g.enqueue([2, 8, 1], stream_id=7)  # launched under block 2
    del boundary_log[:]
    for _ in range(4):  # block 1's rows go out, block 2 lands
        boundary_log.step(g)
    assert g._staging is not None and "logits" in g._staging
    assert [n for n, _ in boundary_log] == ["engine.boundary_ms"]
    for _ in range(12):  # the landing, then boundaries that wait again
        boundary_log.step(g)
    names = [n for n, _ in boundary_log]
    assert names.count("engine.boundary_ms") >= 3
    for part in ("emit", "pass", "enqueue"):
        assert names.count(f"engine.boundary_{part}_ms") == (
            names.count("engine.boundary_ms") - 1)


def test_slot_not_reclaimed_while_its_rows_are_undelivered(params):
    """A server maps a row's slots to streams when step() RETURNS the row.
    An admission's splice emits the buffered block rows early (into the
    pending queue); if one of them is a stream's EOS, its slot is free
    inside the engine before the caller has seen those tokens, and a
    second arrival queued right behind would take the slot -- the old
    stream's tail then reaches the new stream (found by chip_smoke.py's
    rehearsal, PR 21: a request that timed out short while a later one
    ended on an EOS it never sampled). Every token must reach the stream
    that sampled it."""
    import dataclasses

    settings = SamplerSettings(**GREEDY)
    p_a, p_b, p_c = [5, 9, 4, 11], [3, 1, 4, 1, 5, 9], [7, 7, 3]
    g = BatchGenerator(dataclasses.replace(CFG, eos_token_id=-1), params,
                       settings=settings, block_size=8)
    g.set_prompts([p_a, [1]], stream_ids=[0, 99])
    solo_a = g.generate(10)[0]
    # end stream A by EOS inside the first fused block (tokens 2..9) but
    # at least two rows into it, so the EOS row is still queued behind
    # another when the second arrival could claim: at A's first token
    # from the 4th on that it has not produced before
    k = next(i for i in range(3, 9) if solo_a[i] not in solo_a[:i])
    # the EOS ids are host-side bookkeeping (no program closes over
    # them): the same generator, and its compiled programs, serve again
    g._eos_ids = {solo_a[k]}

    g.set_prompts([p_a, [1]], stream_ids=[0, 99])
    g.streams[1].done = True  # a retired slot, as the scheduler primes
    got: dict[int, list[int]] = {}
    seen = {}  # every stream object that ever held a slot, by id

    def pump():
        for slot, tok in enumerate(g.step()):
            seen[g.streams[slot].stream_id] = g.streams[slot]
            if tok is not None:
                got.setdefault(g.streams[slot].stream_id, []).append(tok.id)

    pump()  # A's first token
    pump()  # dispatches the block: 8 rows buffered, one emitted
    g.enqueue(p_b, 7)  # splices into the free slot, draining the buffer:
    g.enqueue(p_c, 8)  # A's EOS is now emitted but not handed out
    for _ in range(24):
        pump()
    assert got[0] == solo_a[: k + 1]
    # what each stream was handed is what the engine recorded for it
    for sid in (0, 7, 8):
        assert got[sid] == seen[sid].generated[: len(got[sid])], sid
        assert len(got[sid]) >= min(6, len(seen[sid].generated))


@pytest.mark.parametrize("tp", [1, 2])
def test_decode_kernel_in_the_engine_and_its_counters(params, tp,
                                                      monkeypatch):
    """``CAKE_PALLAS=1``: the engine's decode programs attend through the
    decode kernel, reading the carried stacked cache under per-row
    frontiers (interpreted here; under ``tp`` with the local head counts
    inside ``shard_map``), and give the streams XLA gives. Every decode
    dispatch adds the KV blocks its steps read up to each slot's frontier
    and the blocks reserved (``attn.kv_blocks_*``); the gauge
    ``attn.decode_kernel`` says which attention the programs hold, from
    where it was chosen (``ops.attention.attend``, as they were traced)."""
    from cake_tpu.obs import metrics
    from cake_tpu.ops.pallas import DECODE_BLOCK_K

    settings = SamplerSettings(**GREEDY)
    reg = metrics.registry()
    names = ("attn.kv_blocks_read", "attn.kv_blocks_reserved")

    def run(mode):
        monkeypatch.setenv("CAKE_PALLAS", mode)
        before = [reg.counter(n).value for n in names]
        reg.gauge("attn.decode_kernel").set(-1)  # the engine sets nothing
        g = BatchGenerator(CFG, params, settings=settings, tp=tp,
                           block_size=4)
        g.set_prompts(PROMPTS)
        out = g.generate(9)
        g.drain()
        return (out, reg.gauge("attn.decode_kernel").value,
                [reg.counter(n).value - b for n, b in zip(names, before)],
                g.stats()["decode_dispatches"])

    want, gauge, _, _ = run("0")
    assert gauge == 0
    got, gauge, (read, reserved), dispatches = run("1")
    assert got == want and gauge == 1
    # a 64-row window is one block of DECODE_BLOCK_K rows: every slot
    # reads the one block it has, every step
    assert CFG.max_seq_len <= DECODE_BLOCK_K
    assert read == reserved > 0
    assert reserved % (len(PROMPTS) * 4) == 0 and dispatches >= 2


@pytest.mark.parametrize("tp", [1, 2])
def test_kv_blocks_are_counted_in_the_block_the_kernel_fetches(
        params, tp, monkeypatch):
    """``attn.kv_blocks_*`` count in the rows of the block that the decode
    kernel fetches of THIS cache's shape: the engine asks
    ``pk.decode_block_k`` what the kernel asks it (the window, the LOCAL
    KV heads of a tp mesh, the head size, the cache's bytes an element and
    the query rows a KV head) and hands the answer to
    ``pk.decode_blocks_read``; a shape no kernel is built for counts in
    the default block."""
    from cake_tpu.ops import pallas as pk

    asked, counted = [], []
    answer = [16]
    monkeypatch.setattr(pk, "decode_block_k",
                        lambda *a: (asked.append(a), answer[0])[1])
    real = pk.decode_blocks_read
    monkeypatch.setattr(
        pk, "decode_blocks_read",
        lambda pos, steps, s, **kw: (counted.append(kw["block_k"]),
                                     real(pos, steps, s, **kw))[1])
    settings = SamplerSettings(**GREEDY)
    g = BatchGenerator(CFG, params, settings=settings, tp=tp, block_size=4)
    assert asked == [(CFG.max_seq_len, CFG.num_key_value_heads // tp,
                      CFG.head_dim, CFG.jax_dtype.itemsize,
                      CFG.num_attention_heads // CFG.num_key_value_heads)]
    g.set_prompts(PROMPTS)
    g.generate(5)
    g.drain()
    assert counted and set(counted) == {16}
    answer[0] = None  # no block fits: the default's count
    g = BatchGenerator(CFG, params, settings=settings, tp=tp, block_size=4)
    assert g._kv_block == pk.DECODE_BLOCK_K


def test_a_dead_slot_decodes_at_row_zero(params, monkeypatch):
    """A slot without a live stream (retired here by ``finish``) still
    goes through every decode program, but at frontier 0, not at a
    frontier that keeps advancing: its attention reads one KV block, and
    the ``attn.kv_blocks_*`` counters are fed the frontiers as
    dispatched. Its writes at rows 0.. touch only its own cache row: the
    neighbour streams decode as if nothing had happened, and a stream
    admitted into the slot afterwards as if it were alone."""
    from cake_tpu.ops import pallas as pk

    settings = SamplerSettings(**GREEDY)
    counted = []
    real = pk.decode_blocks_read
    monkeypatch.setattr(
        pk, "decode_blocks_read",
        lambda pos, steps, s, **kw: (counted.append(list(pos)),
                                     real(pos, steps, s, **kw))[1])
    g = BatchGenerator(CFG, params, settings=settings, block_size=4)
    g.set_prompts(PROMPTS)
    got = {i: [] for i in range(len(PROMPTS))}

    def steps(n):
        for _ in range(n):
            for slot, tok in enumerate(g.step()):
                if tok is not None:
                    got.setdefault(g.streams[slot].stream_id, []).append(
                        tok.id)

    steps(2)
    assert g.finish(1) is True
    g.drain()
    counted.clear()
    steps(8)
    # dispatched: the live streams' own frontiers, the dead slot's pinned
    assert counted and all(pos[1] == 0 for pos in counted)
    assert all(pos[0] > len(PROMPTS[0]) and pos[2] > len(PROMPTS[2])
               for pos in counted)
    assert list(g._decode_pos()) == [int(g._pos[0]), 0, int(g._pos[2])]
    g.enqueue([2, 8, 1], stream_id=5)
    steps(20)
    for sid, prompt in ((0, PROMPTS[0]), (2, PROMPTS[2]), (5, [2, 8, 1])):
        want = _single_stream(params, prompt, len(got[sid]), settings)
        assert len(got[sid]) >= 6 and got[sid] == want, sid
