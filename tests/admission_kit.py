"""What the parts of the admission-group tests share (PR 59 split
``tests/test_admission_groups.py`` along its section headings into
``test_admission_groups.py``, ``test_admission_groups_riders.py`` and
``test_admission_groups_landing.py``, so that no one file sets tier-1's
wall clock): the families and prompts, the counters, an engine with live
streams and free slots, the spies on a landing, and the ``family`` and
``dense`` fixtures (built once a part that asks for them). A plain module
the parts import, not a conftest plugin.

A launch admits every arrival that waits, in one prefill program
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


# -- what more than one section uses ------------------------------------------


@pytest.fixture(scope="module")
def dense():
    cfg = tiny(max_seq_len=128, eos_token_id=-1)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(3))


def _letters_guide():
    vocab = [chr(i) if 32 <= i < 127 else "" for i in range(256)]
    return Guide(build_token_dfa("[a-z]{2,4}", vocab, eos_ids=(2,)))


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


_LONG = [[int(t) for t in _RNG.integers(3, 200, n)] for n in (150, 200)]
_FULL = [int(t) for t in _RNG.integers(3, 200, 127)]  # window 128, less one
