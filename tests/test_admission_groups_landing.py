"""A landing's device half leaves before the rows recorded before it
(PR 54), the last section of the admission-group tests
(``tests/test_admission_groups.py``). Shared helpers:
``tests/admission_kit.py``.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from cake_tpu.models.config import tiny
from cake_tpu.obs import catalog
from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.runtime import batch_generator as bg
from cake_tpu.runtime.batch_generator import BatchGenerator

from admission_kit import (  # noqa: F401
    GREEDY, PROMPTS, SHIPPED, TIGHT, _FULL, _LONG, _letters_guide,
    _record_events, dense, every_waiting_arrival_rides,
)


# -- a landing's device half leaves before the rows recorded before it -------
# (PR 54) The sampler, the splice and the device's next program need none
# of the rows that are still going out; the stream's install and its
# first token follow those rows, because the caller maps a row's slot to
# its stream through ``g.streams`` when it GETS the row. The engine below
# has three streams end inside one block (their window fills at its third,
# second and first row), so that the landings at that block's boundary
# take slots whose old streams still have rows to go out.

class _PosTok:
    """A token's text depends on where it stands in its stream's
    detokenizer (its first token reads otherwise than the same id later):
    a row handed to another stream's detokenizer would read wrong."""

    def decode(self, ids):
        return "".join(chr(97 + (t + k) % 26) for k, t in enumerate(ids))


_ENDING = [[int(t) for t in np.random.default_rng(54).integers(3, 200, 600)]
           for _ in range(3)]


def _ending_engine(params, window=128, eos=-1, **kw) -> BatchGenerator:
    """Stream 0 decodes on; streams 1, 2, 3 fill their window with the
    8th, 7th and 6th token they generate: rows 3, 2 and 1 of the second
    block of 4. ``step()`` 7 lands that block."""
    g = BatchGenerator(tiny(max_seq_len=window, eos_token_id=eos), params,
                       block_size=4, settings=SamplerSettings(**GREEDY),
                       tokenizer=_PosTok(), **kw)
    g.set_prompts([[4, 4, 4]] + [_ENDING[i][:window - 8 + i]
                                 for i in range(3)])
    return g


def _take_all(out: dict, g, row) -> None:
    """A row as its caller sees it: each token under the stream that
    ``g.streams`` names for its slot at that moment."""
    for i, tok in enumerate(row):
        if tok is not None:
            out.setdefault(g.streams[i].stream_id, []).append(
                (tok.id, tok.text, tok.is_end_of_stream, tok.logprobs))


def _note_rows(g, events: list) -> None:
    hand_out = g._hand_out

    def noting(row):
        events.append("row")
        return hand_out(row)

    g._hand_out = noting


BEFORE_ROWS = {
    # case: (arrivals, engine keywords, landings, of which before rows)
    "at-the-boundary": ([(PROMPTS[1], 10)], {}, 1, 1),
    "behind-a-running-block": ([(PROMPTS[1], 10)], {}, 1, 1),
    "chain-of-three": ([(PROMPTS[0], 10), (PROMPTS[1], 11),
                        (PROMPTS[3], 12)], {}, 3, 3),
    "pair-2x256": ([(_LONG[0], 10), (_LONG[1], 11)], {}, 1, 1),
    # (the two ride in one launch: the one that ends, and its neighbour)
    "first-token-is-eos": ([(PROMPTS[3], 10), (PROMPTS[0], 11)], {}, 1, 1),
    "fills-the-window": ([(_FULL, 10), (PROMPTS[0], 11)], {}, 2, 2),
    "logprobs": ([(PROMPTS[1], 10)], dict(logprobs=3), 1, 1),
    "finish-old": ([(PROMPTS[1], 10)], {}, 1, 1),
    "finish-new": ([(PROMPTS[1], 10), (PROMPTS[0], 11)], {}, 2, 2),
    "drain": ([(PROMPTS[1], 10)], {}, 1, 1),
    # (the second comes through admit(), while the first is between its
    # halves)
    "admit-behind-a-landing": ([(PROMPTS[1], 10), (PROMPTS[0], 11)], {}, 2, 1),
    # the next block waits while the device still runs the prefill: an
    # arrival that comes meanwhile is launched in its place; else the
    # block leaves when the tokens are there, or before the host waits
    "block-held-for-an-arrival": ([(PROMPTS[1], 10), (PROMPTS[0], 11)], {},
                                  2, 2),
    "block-held-until-the-rows-are-out": ([(PROMPTS[1], 10)], {}, 1, 1),
    # today's order is kept, and why is what the engine sees
    "paged-export": ([(PROMPTS[1], 10)],
                     dict(kv_layout="paged", kv_page_size=8), 1, 0),
    "guided": ([(PROMPTS[1], 10)], {}, 1, 0),
    "admit": ([(PROMPTS[1], 10)], {}, 1, 0),
    "speculation": ([(PROMPTS[1], 10)], dict(spec_k=2), 1, 0),
    "no-room-beside-the-row": ([(PROMPTS[1], 10)], {}, 1, 0),
    # (the second's row would be a third beside the first's, whose token
    # is not fetched yet, and the launch's that follows: it waits for the
    # block's rows, and leaves before the first's one row)
    "room-for-two-rows": ([(PROMPTS[0], 10), (PROMPTS[1], 11),
                           (PROMPTS[3], 12)], {}, 3, 3),
}
_ONE_BY_ONE = ("chain-of-three", "finish-new", "room-for-two-rows")
_HELD = ("block-held-for-an-arrival", "block-held-until-the-rows-are-out")


def _tok(tok) -> tuple:
    return tok.id, tok.text, tok.is_end_of_stream, tok.logprobs


@pytest.mark.parametrize("case", list(BEFORE_ROWS))
def test_a_landing_before_its_rows_serves_the_same_tokens(
        dense, monkeypatch, case):
    arrivals, kw, landings, early = BEFORE_ROWS[case]
    _, params = dense
    window = 512 if case == "pair-2x256" else 128
    if case == "pair-2x256":
        monkeypatch.setattr(bg, "GROUP_SHAPES", SHIPPED)
    elif case in _ONE_BY_ONE:
        monkeypatch.setattr(bg, "GROUP_SHAPES", ())
    if case == "no-room-beside-the-row":
        monkeypatch.setattr(bg, "GROUP_STAGING_BYTES", 0)
    if case == "room-for-two-rows":
        monkeypatch.setattr(bg, "GROUP_STAGING_BYTES",
                            2 * dense[0].cache_token_bytes * window)

    def engine(eos=-1):
        g = _ending_engine(params, window, eos, **kw)
        for prompt, _ in arrivals:
            g.warm_admission(len(prompt))
        return g

    # the reference: the old streams as a batch that nobody joins, then
    # the arrivals through the synchronous admit() into the freed slots
    # (a guided arrival has no such reference: its tokens are letters)
    eos = -1
    if case == "first-token-is-eos":
        ref = engine()
        ref.step()
        for s in ref.streams[1:]:
            ref.finish(s.stream_id)
        eos = ref.admit(list(arrivals[0][0]), 10)[1].id
    ref, want = engine(eos), {}
    for _ in range(40):
        _take_all(want, ref, ref.step())
    if case != "guided":
        for prompt, sid in arrivals:
            want[sid] = [_tok(ref.admit(list(prompt), sid)[1])]
        for _ in range(24):
            _take_all(want, ref, ref.step())

    g = engine(eos)
    old = {s.stream_id: s for s in g.streams}
    events = _record_events(g)
    _note_rows(g, events)
    # whether the device "still runs the landing's prefill" is the test's
    # to say (the CPU's answer depends on its threads): not, except in
    # the two cases of a block held back
    prefill_runs = [case in _HELD]
    g._landing_runs = lambda: bool(prefill_runs[0] and g._landed)
    before = obs_metrics.registry().snapshot()
    got: dict = {}
    cut: set = set()  # old streams that finish() or admit() cut short

    def step(n=1):
        for _ in range(n):
            _take_all(got, g, g.step())

    # (admit()'s cases bring their second arrival through admit(), the
    # held block's one comes back later)
    later = 1 if case.startswith("admit") or case in _HELD else len(arrivals)

    def enqueue(some=arrivals[:later]):
        for prompt, sid in some:
            g.enqueue(list(prompt), sid,
                      guide=_letters_guide() if case == "guided" else None)

    # up to the landing of the block in which streams 1 to 3 end
    if case == "behind-a-running-block":
        step(3)  # the second block has left; its predecessor's rows go out
        g.finish(3)
        cut.add(3)
        enqueue()  # launched behind the running block, into stream 3's slot
        step(4)
    elif case == "speculation":  # (rounds: no block, and rows of banks)
        while not all(s.done for s in g.streams[1:]):
            step()
        enqueue()
    elif case == "admit":
        step(7)
    else:
        enqueue()  # no slot is free: they wait for the block's landing
        step(7)
    if case != "speculation":
        assert len(g._pending_rows) == 4 and g._inflight is None
    n_at_landing = len(events)

    # the step() in which the device half leaves, where it may
    if case == "admit":
        slot, tok = g.admit(list(arrivals[0][0]), 10)
        got[10] = [_tok(tok)]
        cut.add(old[slot].stream_id)  # admit() waits for no row
    else:
        step()
    if early:
        assert g._landed and g.pending_admissions() >= 1
        assert len(g._pending_rows) == 3
        slot = g._landed[0].members[0].slot
        # the slot is served and taken, and its old stream still answers
        # for the rows that are going out
        assert g._live()[slot] and slot not in g._free_slots()
        assert g.streams[slot] is old[g.streams[slot].stream_id]
        assert g._decode_pos()[slot] >= len(arrivals[0][0])
    if case in _HELD:
        # splice, and no block behind it: the device has the prefill
        assert events[-2:] == ["splice", "row"] and g._inflight is None
    if case == "block-held-for-an-arrival":
        enqueue(arrivals[1:])  # a client whose answer ended in these rows
        step()
        assert events[-3:] == ["prefill", "splice", "row"]  # in its place
        prefill_runs[0] = False  # both first tokens are there
        step()
        assert events[-2:] == ["block", "row"] and g._inflight is not None
    if case == "block-held-until-the-rows-are-out":
        step(3)
        assert events[-3:] == ["row"] * 3 and g._inflight is None
        step()  # the host half is due: the block, THEN the token's fetch
        assert events[-3:] == ["block", "fetch", "row"], events[-6:]
    if case == "finish-old":
        sid_old = g.streams[slot].stream_id
        assert g.finish(sid_old)  # its rows 2 and 3 are never handed out
        cut.add(sid_old)
    if case == "finish-new":
        assert g.finish(10) and g.pending_admissions() == 1
        del want[10]
    if case == "drain":
        g.drain()  # the block behind the landing stays in flight
        assert g._inflight is not None and len(g._pending_rows) == 3
    if case == "paged-export":
        assert g._staging is not None  # launched; it lands after the rows
        assert g.export_stream(0)
    if case == "admit-behind-a-landing":
        slot2, tok = g.admit(list(arrivals[1][0]), 11)
        got[11] = [_tok(tok)]
        # the landing before it is finished first, and what the two
        # slots' old streams had still to be handed is gone
        assert not g._landed and g.streams[slot].stream_id == 10
        cut.update((old[slot].stream_id, old[slot2].stream_id))
    step(40)
    if case == "drain":
        g.drain()
        assert g._inflight is None

    grown = {n: obs_metrics.registry().snapshot()[n]["value"]
             - before[n]["value"]
             for n in ("engine.landings_before_rows", "engine.landings_ahead",
                       "engine.admit_launches")}
    assert grown["engine.landings_before_rows"] == early, (case, grown)
    assert catalog.kind_of("engine.landings_before_rows") == catalog.COUNTER
    assert not g._landed and not g.pending_admissions()
    if case == "finish-new":
        assert 10 not in got

    # every stream, old and new, got its own tokens, text and end, the
    # old ones row for row up to the window's (unless cut short)
    for sid, toks in want.items():
        have = got.get(sid, [])
        ended = sid in old and sid != 0
        if sid in cut:
            toks = toks[:len(have)]
        n = len(toks) if ended else min(len(toks), len(have))
        assert n >= (1 if toks[0][2] else 2), (case, sid)
        assert [t[:3] for t in have[:n]] == [t[:3] for t in toks[:n]], (
            case, sid)
        if ended:
            assert len(have) == n and old[sid].handed == n, (case, sid)
            assert have[-1][2] == (sid not in cut), (case, sid)
        if case == "logprobs":
            for a, b in zip(have[:n], toks[:n]):
                assert [i for i, _ in a[3]] == [i for i, _ in b[3]]
                np.testing.assert_allclose([v for _, v in a[3]],
                                           [v for _, v in b[3]], atol=TIGHT)
    if case == "first-token-is-eos":
        assert [t[::2] for t in got[10]] == [(eos, True)]
    if case == "fills-the-window":
        assert len(got[10]) == 1 and got[10][0][2]
    if case == "guided":
        assert all(chr(t[0]).islower() or t[0] == 2 for t in got[10])

    # the order: a device half that leaves before the rows has its splice
    # and the device's next program enqueued while the rows recorded
    # before it are still to go out, and its token fetched after the last
    # of them; where today's order is kept the splice follows the rows
    tail = events[n_at_landing:]
    splices = [i for i, e in enumerate(tail) if e == "splice"]
    assert len(splices) == landings, (case, tail)
    rows_before = [i for i, e in enumerate(tail) if e == "row"][:4]
    if early:
        assert splices[0] < rows_before[0], (case, tail)
        assert case in _HELD or tail[splices[0] + 1] in (
            "block", "prefill"), (case, tail)
        if case != "admit-behind-a-landing":
            assert tail.index("fetch") > rows_before[-1], (case, tail)
        if case in _ONE_BY_ONE:  # a chain's second follows in the same way
            assert (splices[1] < rows_before[-1]) == (
                case != "room-for-two-rows"), (case, tail)
        if case == "chain-of-three":
            # one host half a step(): a first token leaves before the
            # next landing's, whose prefill may still run, is waited for
            at = rows_before[-1] + 1
            assert tail[at:at + 6] == ["fetch", "row"] * 3, (case, tail)
    elif case == "admit":
        assert tail[splices[0] + 1] == "fetch", (case, tail)
    elif case != "speculation":
        assert splices[0] > rows_before[-1], (case, tail)
    if case == "guided":
        assert tail[splices[0] - 1] == "fetch", (case, tail)


def _live_state(g) -> list:
    return [np.asarray(x) for x in jax.tree.leaves(
        (g.cache, g._keys, g._history, g._hist_slot, g._last_tokens))]


@pytest.mark.parametrize("kw", [dict(), dict(kv_layout="paged",
                                             kv_page_size=8)],
                         ids=["slot", "paged"])
def test_warming_a_landing_mid_stream_leaves_the_live_state_as_it_was(
        dense, kw):
    """``_warm_bucket`` and ``_warm_landing`` run the DONATING splice
    against the live batch: cache, keys, history, ring slots and last
    tokens come back bit for bit, and the streams decode on as an engine
    that was never warmed."""
    cfg, params = dense
    engines = []
    for _ in range(2):
        g = BatchGenerator(cfg, params, block_size=4,
                           settings=SamplerSettings(**GREEDY), **kw)
        g.set_prompts([list(p) for p in PROMPTS[:3]])
        for _ in range(6):
            g.step()
        g.drain()
        engines.append(g)
    g, plain = engines
    before = _live_state(g)
    g.warm_admission(20)  # bucket 32: the one-row landing, and two rows'
    g._landing_warmed.clear()
    g._warm_landing()
    assert g._landing_warmed == {1}
    for a, b in zip(before, _live_state(g)):
        np.testing.assert_array_equal(a, b)
    for _ in range(12):
        a, b = g.step(), plain.step()
        assert [t and t.id for t in a] == [t and t.id for t in b]
