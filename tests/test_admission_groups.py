"""A launch admits every arrival that waits, in one prefill program
(PR 37): ``BatchGenerator._start_arrival`` takes the head of the FIFO and
the plain prompts behind it, a free slot and a staging row each;
``_finish_admission`` samples, splices and installs them together.

What is held here: every member of a launch gets the tokens, the
first-token logits and (where layers hold one) the recurrent state of its
admission alone, in each family the benchmark serves; what may not ride
splits the run and nobody is overtaken; the counters count members and
launches; a launch of a bucket that has been met compiles nothing.

Tolerances. Everything is float32 on the CPU. A row of a several-row
program differs from the same row alone in the order of sums only (XLA
blocks a ``[2, C]`` product otherwise than a ``[1, C]`` one; an expert
block sums a row's experts in the order its call's rows select): measured
0 to 6e-6 on logits of magnitude ~3. ``TIGHT`` is 1e-4, as in the
families' own tests against their references.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from cake_tpu.constrain import Guide, build_token_dfa
from cake_tpu.models import llama
from cake_tpu.models.config import (tiny, tiny_exaone_moe, tiny_jamba,
                                    tiny_kda_hybrid, tiny_mla_moe, tiny_moe)
from cake_tpu.obs import catalog
from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.runtime import batch_generator as bg
from cake_tpu.runtime.batch_generator import BatchGenerator

TIGHT = 1e-4
GREEDY = dict(temperature=0.0, repeat_penalty=1.1)
FAMILIES = {"gqa": tiny, "mixtral": tiny_moe, "mla_moe": tiny_mla_moe,
            "kda_hybrid": tiny_kda_hybrid, "jamba": tiny_jamba,
            "exaone_moe": tiny_exaone_moe}
_RNG = np.random.default_rng(37)
# one prompt per bucket (16, 32, 64), and a fourth of the first's
PROMPTS = [[int(t) for t in _RNG.integers(3, 200, n)] for n in (9, 20, 40, 12)]
STAGE_HISTS = tuple(f"engine.admit_{s}_ms" for s in
                    ("launch_wait", "rows_wait", "land", "to_splice"))
COUNTED = STAGE_HISTS + ("engine.admissions_landed", "engine.admit_launches",
                         "moe.admit_rows", "prof.compiles")


SHIPPED = bg.GROUP_SHAPES


@pytest.fixture(autouse=True)
def every_waiting_arrival_rides(monkeypatch):
    """The cases of the mechanism run with a program of two rows at the
    second bucket and one of four at the third: whoever waits rides (a
    prompt of each bucket in one launch, three as four). Which programs
    there are (``GROUP_SHAPES``) has its own cases at the end."""
    monkeypatch.setattr(bg, "GROUP_SHAPES", ((2, 32), (4, 64)))


def _counts() -> dict:
    snap = obs_metrics.registry().snapshot()
    return {n: snap.get(n, {}).get("count", snap.get(n, {}).get("value", 0))
            for n in COUNTED}


def _grown(before: dict) -> dict:
    return {n: v - before[n] for n, v in _counts().items()}


def _engine(cfg, params, slots=4, live=0, warm=True, **kw) -> BatchGenerator:
    """``slots`` slots of which the first ``live`` hold a running stream
    (ids 0..) and the others are free; ``warm``: the first bucket's
    program and the several-row ones are compiled, as after a server's
    warm-up."""
    kw.setdefault("block_size", 4)
    g = BatchGenerator(cfg, params, settings=SamplerSettings(**GREEDY), **kw)
    g.set_prompts([[4, 4, 4 + i] for i in range(slots)])
    if warm:
        g.warm_admission(9)
    g.step()
    for s in g.streams[live:]:
        g.finish(s.stream_id)
    return g


def _watch(g) -> dict:
    """Record, a landing: each member's first-token logits and what the
    splice left in its slot (every cache leaf's row)."""
    seen: dict = {}
    first_tokens, finish = g._first_tokens, g._finish_admission

    def spy_first(logits, sids, hist, mask=None):
        seen["logits"] = (np.asarray(logits), list(sids))
        return first_tokens(logits, sids, hist, mask=mask)

    def spy_splice(*args):
        # the slots as the splice leaves them, before any program that
        # follows it has advanced a state (the last argument: the slots)
        out = splice(*args)
        seen["spliced"] = {
            int(slot): jax.tree.map(lambda x: np.asarray(x[:, slot]), out[0])
            for slot in np.asarray(args[-1])}
        return out

    def spy_finish(wait=True):
        members = list(g._staging["members"])
        finish(wait)
        logits, sids = seen.pop("logits")
        spliced = seen.pop("spliced")
        for m in members:
            seen[m.sid] = dict(
                slot=m.slot, logits=logits[sids.index(m.sid)],
                cache=spliced[m.slot])

    splice = g._splice_fn()
    g._splice_fn = lambda: spy_splice
    g._first_tokens, g._finish_admission = spy_first, spy_finish
    return seen


def _run(g, arrivals, together: bool, steps=10) -> dict:
    """Admit ``[(prompt, sid), ...]`` all at once or each after the one
    before has landed, then decode on; ``{sid: its record}`` with the
    stream's first ``steps`` tokens."""
    seen = _watch(g)
    if together:
        for prompt, sid in arrivals:
            g.enqueue(list(prompt), sid)
    for prompt, sid in arrivals:
        if not together:
            g.enqueue(list(prompt), sid)
        while g.pending_admissions():
            g.step()
    while any(len(s.generated) < steps and not s.done for s in g.streams
              if s.stream_id in seen):
        g.step()
    for s in g.streams:
        if s.stream_id in seen:
            seen[s.stream_id]["tokens"] = s.generated[:steps]
    return seen


@pytest.fixture(scope="module")
def family():
    """``family(name) -> (cfg, params, each prompt's record admitted
    alone)``, made once a family."""
    made: dict = {}

    def get(name):
        if name not in made:
            cfg = FAMILIES[name](max_seq_len=128, eos_token_id=-1)
            params = llama.init_params(cfg, jax.random.PRNGKey(3))
            alone = _run(_engine(cfg, params),
                         [(p, 10 + i) for i, p in enumerate(PROMPTS)], False)
            made[name] = cfg, params, alone
        return made[name]

    return get


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


@pytest.fixture(scope="module")
def dense():
    cfg = tiny(max_seq_len=128, eos_token_id=-1)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(3))


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

def _letters_guide():
    vocab = [chr(i) if 32 <= i < 127 else "" for i in range(256)]
    return Guide(build_token_dfa("[a-z]{2,4}", vocab, eos_ids=(2,)))


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

def _record_events(g) -> list:
    """The order in which the engine enqueues device programs and waits
    for the device: ``"prefill"``, ``"splice"``, ``"block"``,
    ``"fetch"``."""
    events: list = []

    def noting(name, fn):
        return lambda *a, **k: (events.append(name), fn(*a, **k))[1]

    g._host = noting("fetch", g._host)
    splice = noting("splice", g._splice_small_fn() if g.paged
                    else g._splice_fn())
    if g.paged:
        g._splice_small_fn = lambda: splice
    else:
        g._splice_fn = lambda: splice
    g._dispatch_block = noting("block", g._dispatch_block)
    g._BatchGenerator__admit_prefill = noting("prefill", g._admit_prefill)
    return events


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


_LONG = [[int(t) for t in _RNG.integers(3, 200, n)] for n in (150, 200)]
_FULL = [int(t) for t in _RNG.integers(3, 200, 127)]  # window 128, less one
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
