"""What ``BatchGenerator`` does between blocks (the last third of
``tests/test_batch_generator.py``, in a file of its own since PR 59): the
adaptive block ladder, the one order of work at a block boundary (the
next program enqueued before a landed row leaves; the boundary's three
parts), slots and rows not yet delivered, the decode kernel in the
engine and its block counters. Shared: ``tests/batch_generator_kit.py``.
"""

import jax
import pytest

from cake_tpu.models.config import tiny
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.runtime.batch_generator import BatchGenerator
from cake_tpu.runtime.generator import LlamaGenerator
from cake_tpu.runtime.batch_generator import BatchGenerator as BG

from batch_generator_kit import (  # noqa: F401
    CFG, GREEDY, PROMPTS, _batch_run, _single_stream, params,
)


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
