"""A launch of several arrivals (PR 37), continued from
``tests/test_admission_groups.py``: first tokens reach their own
streams; what may not ride splits the run and nobody is overtaken; the
counters count members and launches and a launch of a bucket that has
been met compiles nothing; which programs there are and how much
padding they may carry; a landing is device work only (PR 45). Shared
helpers: ``tests/admission_kit.py``.
"""

from __future__ import annotations

import numpy as np
import pytest

from cake_tpu.models.config import tiny
from cake_tpu.obs import catalog
from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.runtime import batch_generator as bg
from cake_tpu.runtime.batch_generator import BatchGenerator

from admission_kit import (  # noqa: F401
    GREEDY, PROMPTS, SHIPPED, STAGE_HISTS, TIGHT, _FULL, _LONG, _counts,
    _engine, _grown, _letters_guide, _record_events, _watch, dense,
    every_waiting_arrival_rides, family,
)


# -- first tokens reach their own streams ------------------------------------

def test_a_member_whose_first_token_is_eos_ends_alone(dense, family):
    _, params, alone = family("gqa")
    eos = alone[11]["tokens"][0]
    assert eos not in (alone[10]["tokens"][0], alone[12]["tokens"][0])
    cfg = tiny(max_seq_len=128, eos_token_id=eos)
    g = _engine(cfg, params)
    seen = _watch(g)
    for i, p in enumerate(PROMPTS[:3]):
        g.enqueue(list(p), 10 + i)
    rows = []
    while g.pending_admissions():
        rows.append(g.step())
    landing = next(r for r in rows if any(t is not None for t in r))
    for sid in (10, 11, 12):
        tok = landing[seen[sid]["slot"]]
        assert tok.id == alone[sid]["tokens"][0]
        assert tok.is_end_of_stream == (sid == 11)
    ended = g.streams[seen[11]["slot"]]
    assert ended.done and ended.end_reason == "eos"
    assert g._free_slot() is not None  # its slot is free again at once
    for _ in range(8):
        g.step()
    for sid in (10, 12):
        s = g.streams[seen[sid]["slot"]]
        # (under the changed EOS id only the first tokens are comparable
        # beyond doubt: the others until one of them is the EOS)
        n = len(s.generated)
        assert n > 1 and s.generated == alone[sid]["tokens"][:n]


def test_finish_cancels_one_staged_member_and_the_others_land(dense, family):
    cfg, params, alone = family("gqa")
    g = _engine(cfg, params, slots=6, live=2)
    for _ in range(3):
        g.step()  # a block has landed: rows wait to be handed out
    assert g._pending_rows
    before = _counts()
    for i, p in enumerate(PROMPTS[:3]):
        g.enqueue(list(p), 10 + i)
    g.step()  # launched behind the rows, not landed
    st = g._staging
    assert st is not None and "logits" in st
    assert [m.sid for m in st["members"]] == [10, 11, 12]
    assert g.pending_admissions() == 3
    free = [m.slot for m in st["members"]]
    assert g.finish(11) is True
    assert g.pending_admissions() == 2
    assert g.finish(11) is False  # gone
    while g.pending_admissions():
        g.step()
    for _ in range(8):
        g.step()
    grown = _grown(before)
    assert grown["engine.admit_launches"] == 1
    assert grown["engine.admissions_landed"] == 2
    for hist in STAGE_HISTS:
        assert grown[hist] == 2
    by_sid = {s.stream_id: (i, s) for i, s in enumerate(g.streams)}
    assert 11 not in by_sid
    for sid, slot in ((10, free[0]), (12, free[2])):
        i, s = by_sid[sid]
        assert i == slot
        n = min(len(s.generated), 10)
        assert n > 2 and s.generated[:n] == alone[sid]["tokens"][:n]
    # the cancelled member's slot serves the next arrival
    assert g._free_slot() == free[1]
    g.enqueue(list(PROMPTS[1]), 21)
    while g.pending_admissions():
        g.step()
    for _ in range(6):
        g.step()
    s = g.streams[free[1]]
    assert s.stream_id == 21
    assert s.generated[:6] == alone[11]["tokens"][:6]
    # the two streams that were live all along never noticed
    assert [g.streams[i].stream_id for i in (0, 1)] == [0, 1]


def test_finish_of_every_staged_member_drops_the_launch(dense):
    cfg, params = dense
    g = _engine(cfg, params, slots=4, live=1)
    for _ in range(3):
        g.step()
    g.enqueue(list(PROMPTS[0]), 10)
    g.enqueue(list(PROMPTS[1]), 11)
    g.step()
    assert g.pending_admissions() == 2 and g._staging is not None
    assert g.finish(10) and g.finish(11)
    assert g._staging is None and g.pending_admissions() == 0
    for _ in range(6):
        g.step()
    assert {s.stream_id for s in g.streams if not s.done} == {0}


# -- who rides ---------------------------------------------------------------


SYSTEM = [(i * 7) % 100 + 3 for i in range(32)]  # a shared 32-token prefix


def _landing_order(g) -> list:
    """``(stream id, slot)`` in the order the landings installed them."""
    order = []
    install = g._install

    def spy(m):
        order.append((m.sid, m.slot))
        return install(m)

    g._install = spy
    return order


@pytest.mark.parametrize("case,kw,middle,launches", [
    ("guide", dict(), dict(prompt=PROMPTS[1], guide=True), 3),
    ("prefix-hit", dict(prefix_share_min=16, prefix_block=16),
     dict(prompt=SYSTEM + [5, 9, 2]), 3),
    ("chunked", dict(admit_chunk=32), dict(prompt=PROMPTS[2]), 3),
    # (alone behind the head, it starts from the head's row; the third
    # cannot ride with a prompt that starts from a stored prefix)
    ("same-prefix-as-the-head", dict(prefix_share_min=16, prefix_block=16),
     dict(prompt=None), 3),
    ("plain", dict(), dict(prompt=PROMPTS[1]), 1),
])
def test_what_cannot_ride_splits_the_run_in_fifo_order(dense, case, kw,
                                                       middle, launches):
    cfg, params = dense
    g = _engine(cfg, params, slots=5, **kw)
    if case == "prefix-hit":
        g.enqueue(SYSTEM + [8, 8, 4, 1], 9)  # leaves SYSTEM in the store
        while g.pending_admissions():
            g.step()
        g.finish(9)
    head = SYSTEM + [7, 7, 7] if case == "same-prefix-as-the-head" \
        else PROMPTS[0]
    prompt = middle["prompt"] or SYSTEM + [6, 1, 6, 1]
    order = _landing_order(g)
    before, hits = _counts(), g.stats()["prefix_hits"]
    g.enqueue(list(head), 10)
    g.enqueue(list(prompt), 11,
              guide=_letters_guide() if middle.get("guide") else None)
    g.enqueue(list(PROMPTS[3]), 12)
    dispatches = g.stats()["admit_dispatches"]
    while g.pending_admissions():
        g.step()
    grown = _grown(before)
    assert [sid for sid, _ in order] == [10, 11, 12], case
    assert grown["engine.admit_launches"] == launches, (case, grown)
    assert grown["engine.admissions_landed"] == 3
    want_hits = {"prefix-hit": 1, "same-prefix-as-the-head": 1}.get(case, 0)
    assert g.stats()["prefix_hits"] - hits == want_hits
    # a chunked admission's 40 tokens go in two dispatches of 32
    want = launches + (1 if case == "chunked" else 0)
    assert g.stats()["admit_dispatches"] - dispatches == want
    # slots in FIFO order too: nobody was overtaken to a lower slot
    assert [slot for _, slot in order] == [0, 1, 2]


def test_paged_layout_and_an_import_between_two_prompts_launch_alone(dense):
    cfg, params = dense
    g = _engine(cfg, params, slots=4, live=1, kv_layout="paged",
                kv_page_size=8)
    for _ in range(5):
        g.step()
    snap = g.export_stream(0)
    g.finish(0)
    order = _landing_order(g)
    before = _counts()
    g.enqueue(list(PROMPTS[0]), 10)
    meta = g.import_begin(snap)
    g.import_attach(meta["xfer_id"], 20)
    g.enqueue(list(PROMPTS[1]), 11)
    g.enqueue(list(PROMPTS[3]), 12)
    while g.pending_admissions():
        g.step()
    grown = _grown(before)
    assert order == [(10, 0), (11, 2), (12, 3)]  # the attach took slot 1
    assert grown["engine.admit_launches"] == 3
    assert grown["engine.admissions_landed"] == 3
    assert g.streams[1].stream_id == 20


def test_synchronous_admit_takes_its_own_row_behind_a_launch(dense, family):
    cfg, params, alone = family("gqa")
    g = _engine(cfg, params, slots=4)
    g.enqueue(list(PROMPTS[0]), 10)
    g.enqueue(list(PROMPTS[1]), 11)
    slot, tok = g.admit(list(PROMPTS[2]), 12)
    assert g.streams[slot].stream_id == 12
    assert tok.id == alone[12]["tokens"][0]
    # the two ahead of it landed together, and their row still waits
    (row,) = g._pending_rows
    got = {g.streams[i].stream_id: t.id for i, t in enumerate(row)
           if t is not None}
    assert got == {10: alone[10]["tokens"][0], 11: alone[11]["tokens"][0]}


@pytest.mark.parametrize("free,arrivals,want", [
    (5, 6, [4, 1]),  # the cap, then the slot that is left; one stays queued
    (2, 3, [2]),     # a slot each; the third stays queued
    (3, 3, [3]),
    (1, 2, [1]),
])
def test_more_arrivals_than_slots_or_than_the_cap_stay_queued(
        dense, free, arrivals, want):
    cfg, params = dense
    g = _engine(cfg, params, slots=free + 1, live=1)
    sizes = []
    start = g._start_arrival

    def spy(wait=True):
        ok = start(wait)
        if ok:
            sizes.append(len(g._staging["members"]))
            assert len(g._staging["rows"]) in (1, 2, 4)
        return ok

    g._start_arrival = spy
    for i in range(arrivals):
        g.enqueue(list(PROMPTS[i % 4]), 10 + i)
    for _ in range(12):
        g.step()
    assert sizes == want
    assert g.pending_admissions() == arrivals - sum(want)
    assert [a[1] for a in g._arrivals] == list(
        range(10 + sum(want), 10 + arrivals))
    live = {s.stream_id for s in g.streams if not s.done}
    assert live == {0} | set(range(10, 10 + sum(want)))
    if arrivals > sum(want):  # admitted once a slot frees
        g.finish(10)
        for _ in range(6):
            g.step()
        assert any(s.stream_id == 10 + sum(want) for s in g.streams)


# -- counters, compiles ------------------------------------------------------

def test_counters_count_members_and_launches(dense):
    cfg, params = dense
    g = _engine(cfg, params, slots=5)
    before = _counts()
    for i in range(3):
        g.enqueue(list(PROMPTS[i]), 10 + i)
    while g.pending_admissions():
        g.step()
    g.enqueue(list(PROMPTS[3]), 13)
    while g.pending_admissions():
        g.step()
    grown = _grown(before)
    assert grown["engine.admit_launches"] == 2
    assert grown["engine.admissions_landed"] == 4
    for hist in STAGE_HISTS:
        assert grown[hist] == 4, hist
    assert grown["moe.admit_rows"] == 0  # no expert layer here
    for i in range(4):
        stages = g.take_admission_stages(10 + i)
        assert [s[0] for s in stages] == ["launch_wait", "rows_wait",
                                          "land", "to_splice"]
    # the members of one launch share every stamp but the first
    assert catalog.kind_of("engine.admit_launches") == catalog.COUNTER


def test_a_launch_of_one_is_the_one_row_program(dense):
    cfg, params = dense
    g = _engine(cfg, params, slots=4, warm=False)
    shapes = []
    prefill = g._admit_prefill
    g._BatchGenerator__admit_prefill = lambda p, tokens, *rest: (
        shapes.append(tokens.shape), prefill(p, tokens, *rest))[1]
    g.enqueue(list(PROMPTS[1]), 10)
    while g.pending_admissions():
        g.step()
    # its own launch, then the programs it could have ridden in, compiled
    # behind it
    assert shapes == [(1, 32), (2, 32), (4, 64)]
    assert g._group_shapes() == [(2, 32), (4, 64)]
    assert g._splice_fn()._cache_size() == 3


def test_a_landing_samples_with_one_program_a_row_count(dense):
    """A landing's keys and first tokens are ONE jitted program a row count
    (eager calls are a dispatch a primitive: host time that every live
    stream waits for where the prefill is too short to hide it), compiled
    with the landing and never again."""
    cfg, params = dense
    g = _engine(cfg, params, slots=5, live=1)  # warm: 1, 2 and 4 rows
    sampler = g._BatchGenerator__first_tokens
    assert sampler._cache_size() == 3
    for n, base in ((1, 10), (2, 20), (3, 30), (1, 40)):
        for i in range(n):
            g.enqueue(list(PROMPTS[0]), base + i)
        while g.pending_admissions():
            g.step()
        for i in range(n):
            g.finish(base + i)
    assert g._BatchGenerator__first_tokens is sampler
    assert sampler._cache_size() == 3


def test_a_met_buckets_launch_compiles_nothing(dense):
    """Where a bucket's one-row program compiles (its first admission, or
    ``warm_admission``) its other row counts and their landing do too: a
    later launch of two, three or four of that bucket compiles nothing,
    eager operations included."""
    cfg, params = dense
    g = _engine(cfg, params, slots=5, live=1)
    g.warm_admission(30)  # bucket 32
    g.enqueue(list(PROMPTS[0]), 9)  # bucket 16, by its first admission
    while g.pending_admissions():
        g.step()
    for _ in range(6):
        g.step()
    g.finish(9)
    for n, base in ((2, 20), (3, 30), (4, 40), (1, 50)):
        before = _counts()
        for i in range(n):
            # lengths of both buckets: the launch takes the larger
            g.enqueue(list(PROMPTS[1 if i == n - 1 else 0]), base + i)
        while g.pending_admissions():
            g.step()
        for _ in range(5):
            g.step()
        grown = _grown(before)
        assert grown["engine.admit_launches"] == 1
        assert grown["prof.compiles"] == 0, (n, grown)
        for i in range(n):
            g.finish(base + i)


# -- which programs there are, and how much padding they may carry -----------

def _launch_sizes(g) -> list:
    """How many members each launch from here on takes."""
    sizes = []
    start = g._start_arrival

    def spy(wait=True):
        ok = start(wait)
        if ok:
            sizes.append(len(g._staging["members"]))
        return ok

    g._start_arrival = spy
    return sizes


@pytest.mark.parametrize("lengths,want", [
    # PROMPTS' buckets: 9 and 12 tokens 16, 20 tokens 32, 40 tokens 64;
    # the one program of several rows: two rows of 32
    ((20, 20), [2]),
    ((9, 20), [2]),
    ((9, 12), [2]),            # each padded to 32
    ((20, 12, 20), [2, 1]),    # two rows: the third goes next
    ((40, 20, 9), [1, 2]),     # no program holds 40 tokens a row
    ((20, 40, 9), [1, 1, 1]),
])
def test_a_launch_takes_riders_into_a_program_that_holds_them(
        dense, monkeypatch, lengths, want):
    """Riders are taken into a several-row program that has a row each and
    holds the longest; who is left goes in a later launch, in FIFO
    order."""
    monkeypatch.setattr(bg, "GROUP_SHAPES", ((2, 32),))
    cfg, params = dense
    g = _engine(cfg, params, slots=5)
    sizes, order = _launch_sizes(g), _landing_order(g)
    by_len = {len(p): p for p in PROMPTS}
    for i, n in enumerate(lengths):
        g.enqueue(list(by_len[n]), 10 + i)
    while g.pending_admissions():
        g.step()
    assert sizes == want
    assert [sid for sid, _ in order] == list(range(10, 10 + len(lengths)))


def test_a_launch_takes_riders_only_into_a_compiled_program(dense):
    """Nothing was warmed: the first arrival's launch compiles its
    bucket's program and, behind it, the several-row ones that hold it;
    the arrival that waited with it goes alone, the next two together."""
    cfg, params = dense
    g = _engine(cfg, params, slots=5, warm=False)
    sizes = _launch_sizes(g)
    assert not g._warmed
    for i in range(2):
        g.enqueue(list(PROMPTS[1]), 10 + i)
    while g.pending_admissions():
        g.step()
    assert sizes == [1, 1]
    assert g._warmed == {(1, 32), (2, 32), (4, 64)}
    for i in range(2):
        g.enqueue(list(PROMPTS[0]), 20 + i)  # bucket 16: (1, 16) compiles
    while g.pending_admissions():
        g.step()
    assert sizes == [1, 1, 2]


@pytest.mark.parametrize("own,want", [
    ([256, 256], (2, 256)),
    ([128, 256], (2, 256)),
    ([64, 128], (2, 256)),
    ([512, 256], None),       # a program of 512 rows is its arithmetic
    ([256, 512], None),
    ([256, 256, 256], None),  # two rows
])
def test_the_shipped_program_takes_two_prompts_of_up_to_256_tokens(
        monkeypatch, own, want):
    """What the sweep chose (PERF.md section 6, PR 37)."""
    monkeypatch.setattr(bg, "GROUP_SHAPES", SHIPPED)
    assert SHIPPED == ((2, 256),)
    assert bg._group_shape(own) == want


# -- a landing is device work only (PR 45) -----------------------------------
# The splice takes the first tokens where the sampler left them, on the
# device, and the device's next program is enqueued before the host reads
# them. Nothing about a stream's tokens may depend on that order: the
# synchronous ``admit()`` (sampler, splice, fetch, nothing enqueued for
# later: the parent's order) is the reference, arrival by arrival.


def _take(out: dict, g, row) -> None:
    for i, tok in enumerate(row):
        if tok is not None:
            out.setdefault(g.streams[i].stream_id, []).append(
                (tok.id, tok.is_end_of_stream, tok.logprobs))


def _served(g, arrivals, steps: int) -> dict:
    """``{stream id: [(token, ended, logprobs), ...]}`` of arrivals that
    are enqueued together and served by ``step()``."""
    out: dict = {}
    for prompt, sid, guide in arrivals:
        g.enqueue(list(prompt), sid, guide=guide)
    for _ in range(steps):
        _take(out, g, g.step())
    return {sid: out[sid] for _, sid, _ in arrivals}


def _synchronous(g, arrivals, steps: int) -> dict:
    """The same arrivals through ``admit()``, one after the other."""
    out: dict = {}
    for prompt, sid, _ in arrivals:
        slot, tok = g.admit(list(prompt), sid)
        out[sid] = [(tok.id, tok.is_end_of_stream, tok.logprobs)]
    for _ in range(steps):
        _take(out, g, g.step())
    return {sid: out[sid] for _, sid, _ in arrivals}


WRAPS = dict(temperature=0.0, repeat_penalty=1.3, repeat_last_n=8)
LANDINGS = {
    # case: (arrivals [(prompt, sid)], engine keywords, landings)
    "one-row": ([(PROMPTS[1], 10)], {}, 1),
    "pair-2x256": ([(_LONG[0], 10), (_LONG[1], 11)], {}, 1),
    "chain-of-three": ([(PROMPTS[0], 10), (PROMPTS[1], 11),
                        (PROMPTS[3], 12)], {}, 3),
    # (the two ride in one launch: the one that ends, and its neighbour)
    "first-token-is-eos": ([(PROMPTS[1], 10), (PROMPTS[0], 11)], {}, 1),
    "fills-the-window": ([(_FULL, 10), (PROMPTS[0], 11)], {}, 2),
    "logprobs": ([(PROMPTS[1], 10)], dict(logprobs=3), 1),
    "history-wraps": ([(PROMPTS[2], 10)], {}, 1),
    "paged": ([(PROMPTS[1], 10), (PROMPTS[0], 11)],
              dict(kv_layout="paged", kv_page_size=8), 2),
    "guided": ([(PROMPTS[1], 10)], {}, 1),
}


@pytest.mark.parametrize("case", list(LANDINGS))
def test_a_landing_ahead_of_its_token_serves_the_same_tokens(
        dense, monkeypatch, case):
    arrivals, kw, landings = LANDINGS[case]
    _, params = dense
    window = 512 if case == "pair-2x256" else 128
    if case == "pair-2x256":
        monkeypatch.setattr(bg, "GROUP_SHAPES", SHIPPED)
    elif case == "chain-of-three":
        monkeypatch.setattr(bg, "GROUP_SHAPES", ())
    settings = WRAPS if case == "history-wraps" else GREEDY

    def engine(eos=-1):
        g = BatchGenerator(tiny(max_seq_len=window, eos_token_id=eos), params,
                           block_size=4, settings=SamplerSettings(**settings),
                           **kw)
        g.set_prompts([[4, 4, 4 + i] for i in range(4)])
        for prompt, *_ in arrivals:
            g.warm_admission(len(prompt))
        g.step()
        for s in g.streams[1:]:  # stream 0 decodes on, beside the landings
            g.finish(s.stream_id)
        return g

    if case == "guided":
        # (admit() takes no guide: the reference is the stream as a batch's
        # own member, which no admission brings in)
        ref = BatchGenerator(tiny(max_seq_len=window, eos_token_id=-1), params,
                             block_size=4, settings=SamplerSettings(**GREEDY))
        ref.set_prompts([list(arrivals[0][0])], stream_ids=[10],
                        guides=[_letters_guide()])
        want: dict = {}
        for _ in range(6):
            _take(want, ref, ref.step())
        arrivals = [(p, sid, _letters_guide()) for p, sid in arrivals]
        eos = -1
    else:
        arrivals = [(p, sid, None) for p, sid in arrivals]
        eos = -1
        if case == "first-token-is-eos":
            eos = _synchronous(engine(), arrivals[:1], 0)[10][0][0]
        want = _synchronous(engine(eos), arrivals, 24)
    g = engine(eos)
    events = _record_events(g)
    before = obs_metrics.registry().snapshot()["engine.landings_ahead"]["value"]
    got = _served(g, arrivals, 40)
    ahead = obs_metrics.registry().snapshot()[
        "engine.landings_ahead"]["value"] - before

    for sid, toks in want.items():
        n = min(len(toks), len(got[sid]))
        assert n >= (1 if toks[0][1] else 3), (case, sid)
        assert [t[:2] for t in got[sid][:n]] == [t[:2] for t in toks[:n]], (
            case, sid)
        if case == "logprobs":
            for have, ref_lp in zip(got[sid][:n], toks[:n]):
                assert [i for i, _ in have[2]] == [i for i, _ in ref_lp[2]]
                np.testing.assert_allclose([v for _, v in have[2]],
                                           [v for _, v in ref_lp[2]],
                                           atol=TIGHT)
    if case == "first-token-is-eos":
        assert got[10] == [(eos, True, None)]
    if case == "fills-the-window":
        assert len(got[10]) == 1 and got[10][0][1]
    if case == "history-wraps":
        assert len(got[10]) > 8  # the ring of 8 has gone round
    if case == "guided":
        assert all(chr(t[0]).islower() or t[0] == 2 for t in got[10])

    # the order: a landing's splice, then the device's next program, then
    # the host's wait for the token; under a guide the token first
    splices = [i for i, e in enumerate(events) if e == "splice"]
    assert len(splices) == landings, (case, events)
    for i in splices:
        if case == "guided":
            assert events[i - 1] == "fetch", (case, events)
        else:
            assert events[i + 1] in ("block", "prefill"), (case, events)
            assert events[i + 2] == "fetch", (case, events)
    assert ahead == (0 if case == "guided" else landings), case
    assert catalog.kind_of("engine.landings_ahead") == catalog.COUNTER
    stages = g.take_admission_stages(10)
    if case != "guided":
        assert stages[-1][0] == "to_splice" and stages[-1][2] == 0.0
