"""Paged KV-cache pool (cake_tpu/kvpool): the page pool must be an
invisible layout change.

The contract under test: ``BatchGenerator(kv_layout="paged")`` produces
BIT-IDENTICAL token streams to the slot layout across every serving
scenario — steady batch, mid-run admission, retire-and-reuse,
shared-prefix fan-out, constrained (ISSUE 8) streams — while admission
and retirement touch only host-side page tables (no retrace: the page
map and scatter ids are data operands), n same-prefix streams share
physical prefill pages (``kvpool.pages_shared`` > 0 with engine
``prefix_hits`` >= n-1), and the pool self-manages under pressure
(prefix-tree eviction, admission deferral).
"""

import json
import urllib.request

import jax
import pytest

from cake_tpu.kvpool import PagePool, PoolExhausted, PrefixLRU, PrefixTree
from cake_tpu.models import llama
from cake_tpu.models.config import tiny
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.runtime.batch_generator import BatchGenerator

CFG = tiny(max_seq_len=64)
GREEDY = dict(temperature=0.0, repeat_penalty=1.1)
PROMPTS = [[5, 9, 2, 11], [3, 1, 4, 1, 5, 9], [7, 7, 2]]
# a 36-token system prompt: >= prefix_share_min (32) and > 2 full
# 16-token pages, so both sharing paths (set_prompts + admission) engage
PREFIX = list(range(3, 39))


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(5))


def _drive(gen, want_tokens=6, max_steps=300):
    """step() until every live/queued stream has ``want_tokens`` (or is
    done) and no admission is pending — generate() can't drive a batch
    whose live set starts empty."""
    for _ in range(max_steps):
        gen.step()
        if gen.pending_admissions():
            continue
        if all((not s.active) or s.done or len(s.generated) >= want_tokens
               for s in gen.streams):
            break
    return {s.stream_id: list(s.generated)[:want_tokens]
            for s in gen.streams if s.active}


# -- host-side units ---------------------------------------------------------
class TestPagePool:
    def test_alloc_free_refcounts(self):
        p = PagePool(8, 4)
        a = p.alloc()
        assert p.refcount(a) == 1 and p.free_count == 6  # sink excluded
        p.ref(a)
        assert p.shared_count == 1
        assert not p.unref(a)          # still stream-held
        assert p.shared_count == 0
        assert p.unref(a)              # back on the free list
        assert p.free_count == 7

    def test_sink_is_pinned_and_exhaustion_raises(self):
        p = PagePool(4, 4)
        got = {p.alloc() for _ in range(3)}
        assert 0 not in got            # the sink page is never allocated
        with pytest.raises(PoolExhausted):
            p.alloc()

    def test_pow2_and_size_validation(self):
        with pytest.raises(ValueError):
            PagePool(12, 4)            # not a power of two
        with pytest.raises(ValueError):
            PagePool(8, 0)

    def test_unref_free_page_raises(self):
        p = PagePool(8, 4)
        with pytest.raises(ValueError):
            p.unref(3)


class TestPrefixTree:
    def _tree(self, pages=16, ps=4):
        pool = PagePool(pages, ps)
        return pool, PrefixTree(pool)

    def test_insert_match_page_aligned(self):
        pool, t = self._tree()
        ids = list(range(1, 11))       # 10 tokens, ps=4 -> 2 full pages
        pages = [pool.alloc(), pool.alloc()]
        t.insert(ids, pages)
        assert pool.refcount(pages[0]) == 2  # alloc claim + tree claim
        base, got = t.match(ids)
        assert (base, got) == (8, pages)

    def test_match_strictly_shorter_than_prompt(self):
        pool, t = self._tree()
        ids = [1, 2, 3, 4, 5, 6, 7, 8]  # exactly 2 full pages
        t.insert(ids, [pool.alloc(), pool.alloc()])
        # a full-coverage match would leave no remainder token to prefill
        base, got = t.match(ids)
        assert base == 4 and len(got) == 1

    def test_divergent_prefixes_fork(self):
        pool, t = self._tree()
        a, b = pool.alloc(), pool.alloc()
        t.insert([1, 2, 3, 4, 9], [a])
        t.insert([1, 2, 3, 5, 9], [b])
        assert t.match([1, 2, 3, 4, 8, 8])[1] == [a]
        assert t.match([1, 2, 3, 5, 8, 8])[1] == [b]

    def test_eviction_is_lru_and_frees_pages(self):
        pool, t = self._tree()
        a, b = pool.alloc(), pool.alloc()
        t.insert([1, 2, 3, 4, 9], [a])
        t.insert([5, 6, 7, 8, 9], [b])
        t.match([1, 2, 3, 4, 9, 9])    # bump chain a: b is now LRU
        free0 = pool.free_count
        assert t.evict_one()
        assert pool.free_count == free0  # b still holds its alloc claim
        pool.unref(b)                    # stream-side claim drops -> free
        assert pool.free_count == free0 + 1
        assert t.match([5, 6, 7, 8, 9, 9]) == (0, [])
        assert t.match([1, 2, 3, 4, 9, 9])[1] == [a]

    def test_evict_until_free(self):
        pool, t = self._tree(pages=8)
        chains = []
        for k in range(3):
            pid = pool.alloc()
            t.insert([10 * k + 1, 10 * k + 2, 10 * k + 3, 10 * k + 4, 0],
                     [pid])
            pool.unref(pid)            # tree is the only claim
            chains.append(pid)
        assert pool.free_count == 4
        assert t.evict_until_free(6)
        assert pool.free_count >= 6


class TestPrefixLRU:
    """Regression for the legacy slot store's LRU semantics (the old
    dict pop-reinsert / next(iter(...)) idiom, now an explicit type)."""

    def test_evicts_least_recently_used_past_cap(self):
        lru = PrefixLRU(2)
        lru.put((1, 2), "a")
        lru.put((3, 4), "b")
        lru.match([1, 2, 9])           # bump (1,2): (3,4) is now LRU
        lru.put((5, 6), "c")
        assert (3, 4) not in lru
        assert (1, 2) in lru and (5, 6) in lru

    def test_put_refreshes_existing_key(self):
        lru = PrefixLRU(2)
        lru.put((1,), "a")
        lru.put((2,), "b")
        lru.put((1,), "a2")            # refresh: (2,) becomes LRU
        lru.put((3,), "c")
        assert (2,) not in lru and lru.match([1, 9]) == (1, "a2")

    def test_match_requires_strictly_shorter_prefix(self):
        lru = PrefixLRU(2)
        lru.put((1, 2, 3), "a")
        assert lru.match([1, 2, 3]) == (0, None)
        assert lru.match([1, 2, 3, 4]) == (3, "a")

    def test_zero_cap_disables(self):
        lru = PrefixLRU(0)
        lru.put((1,), "a")
        assert len(lru) == 0


# -- paged vs slot bit-identity ----------------------------------------------
class TestParity:
    def _pair(self, params, settings=None, **kw):
        st = settings or SamplerSettings(**GREEDY)
        return (BatchGenerator(CFG, params, settings=st, **kw),
                BatchGenerator(CFG, params, settings=st, kv_layout="paged",
                               **kw))

    def test_steady_batch_greedy_and_sampled(self, params):
        for st in (SamplerSettings(**GREEDY),
                   SamplerSettings(temperature=0.9, top_k=20, seed=11)):
            slot, paged = self._pair(params, settings=st)
            slot.set_prompts(PROMPTS)
            paged.set_prompts(PROMPTS)
            assert slot.generate(8) == paged.generate(8)

    def test_fused_blocks_and_adaptive_ladder(self, params):
        # every fused block goes out before the landed block's rows do
        # (pages are then allocated a boundary earlier): the layouts stay
        # bit-identical, against each other and against single steps
        single = BatchGenerator(CFG, params,
                                settings=SamplerSettings(**GREEDY))
        single.set_prompts(PROMPTS)
        want = single.generate(9)
        for kw in (dict(block_size=4),
                   dict(block_size=4, block_size_max=16),
                   dict(block_size=2, block_size_max=4)):
            slot, paged = self._pair(params, **kw)
            slot.set_prompts(PROMPTS)
            paged.set_prompts(PROMPTS)
            assert slot.generate(9) == paged.generate(9) == want, kw
            assert paged._inflight is not None  # enqueued ahead
            assert (paged.stats()["decode_dispatches"]
                    == slot.stats()["decode_dispatches"])

    def test_midrun_admission_and_retire_reuse(self, params):
        outs = {}
        for layout in ("slot", "paged"):
            g = BatchGenerator(CFG, params,
                               settings=SamplerSettings(**GREEDY),
                               kv_layout=layout)
            g.set_prompts([[5, 9, 2, 11], [3, 1, 4, 1, 5, 9]])
            g.generate(4)
            g.enqueue([7, 7, 2], 5)        # mid-run admission
            g.generate(4)
            g.finish(0)                    # server-side retire
            g.enqueue([9, 9, 1, 4], 6)     # the freed slot is reused
            g.generate(4)
            outs[layout] = {s.stream_id: list(s.generated)
                            for s in g.streams if s.active}
        assert outs["slot"] == outs["paged"]

    def test_window_exhaustion_per_stream(self, params):
        cfg = tiny(max_seq_len=32)
        p = llama.init_params(cfg, jax.random.PRNGKey(5))
        res = {}
        for layout in ("slot", "paged"):
            g = BatchGenerator(cfg, p, settings=SamplerSettings(**GREEDY),
                               kv_layout=layout, kv_page_size=8)
            g.set_prompts([list(range(2, 28)), [5, 9, 2]])
            res[layout] = g.generate(20)
        assert res["slot"] == res["paged"]

    def test_int8_kv_pool(self, params):
        slot, paged = self._pair(params, kv_quant="int8")
        slot.set_prompts(PROMPTS)
        paged.set_prompts(PROMPTS)
        assert slot.generate(6) == paged.generate(6)

    def test_constrained_streams_ride_paged(self, params):
        from cake_tpu.constrain import (
            Guide,
            build_token_dfa,
            json_schema_to_regex,
        )

        cfg = tiny(max_seq_len=128)
        p = llama.init_params(cfg, jax.random.PRNGKey(7))

        class AsciiTok:
            def decode(self, ids):
                return "".join(chr(32 + (i % 95)) for i in ids)

            def encode(self, text):
                return [ord(c) - 32 for c in text]

        vocab = [AsciiTok().decode([i]) for i in range(cfg.vocab_size)]
        schema = {"type": "object",
                  "properties": {"a": {"type": "integer"},
                                 "ok": {"type": "boolean"}},
                  "required": ["a", "ok"]}

        def guide():
            return Guide(build_token_dfa(json_schema_to_regex(schema),
                                         vocab,
                                         eos_ids=(cfg.eos_token_id,)))

        outs = {}
        for layout in ("slot", "paged"):
            gen = BatchGenerator(cfg, p, tokenizer=AsciiTok(),
                                 settings=SamplerSettings(**GREEDY),
                                 kv_layout=layout)
            gen.set_prompts([[5, 6, 7], [8, 9, 10]],
                            guides=[None, guide()])
            out = gen.generate(40)
            gen.finish(0)
            gen.enqueue([11, 12, 13], 9, guide=guide())  # admitted guide
            for _ in range(120):
                gen.step()
                s9 = next((s for s in gen.streams if s.stream_id == 9),
                          None)
                if s9 is not None and s9.done:
                    break
            outs[layout] = (out, {s.stream_id: list(s.generated)
                                  for s in gen.streams if s.active})
        assert outs["slot"] == outs["paged"]
        # the constrained admitted stream really produced valid JSON
        gen9 = outs["paged"][1][9]
        text = AsciiTok().decode(
            [t for t in gen9 if t != cfg.eos_token_id])
        json.loads(text)


# -- sharing, eviction, deferral ---------------------------------------------
class TestSharing:
    def test_set_prompts_shared_prefix_shares_pages(self, params):
        prompts = [PREFIX + [5, 9], PREFIX + [7], PREFIX + [2, 4, 6]]
        slot = BatchGenerator(CFG, params,
                              settings=SamplerSettings(**GREEDY))
        paged = BatchGenerator(CFG, params,
                               settings=SamplerSettings(**GREEDY),
                               kv_layout="paged")
        slot.set_prompts(prompts)
        paged.set_prompts(prompts)
        assert slot.generate(6) == paged.generate(6)
        kp = paged.stats()["kvpool"]
        # 36-token prefix = 2 full 16-token pages, physically shared by
        # all 3 streams + the tree; the unaligned tail page is a private
        # copy-on-write materialization per stream
        assert kp["pages_shared"] == 2
        assert paged._pagepool.refcount(paged._tables[0][0]) == 4
        assert paged._tables[0][0] == paged._tables[1][0] \
            == paged._tables[2][0]
        assert paged._tables[0][2] != paged._tables[1][2]  # CoW boundary

    def test_admission_fanout_hits_and_shares(self, params):
        """The acceptance shape: n same-system-prompt arrivals through
        the admission path — prefix_hits >= n-1 (the SAME counter the
        gateway's prefix-affinity policy scores against) and physical
        pages shared, streams bit-identical to the slot layout."""
        n = 4
        outs = {}
        for layout in ("slot", "paged"):
            g = BatchGenerator(CFG, params,
                               settings=SamplerSettings(**GREEDY),
                               kv_layout=layout)
            g.set_prompts([[1]] * n)
            for s in g.streams:
                s.done = True
            for k, tail in enumerate(([5, 9], [7], [2, 4, 6], [8, 8])):
                g.enqueue(PREFIX + tail, 10 + k)
            outs[layout] = (_drive(g, want_tokens=6), g)
        assert outs["slot"][0] == outs["paged"][0]
        st = outs["paged"][1].stats()
        assert st["prefix_hits"] >= n - 1
        assert st["kvpool"]["pages_shared"] > 0

    def test_prefix_cache_disabled_skips_tree_but_batch_still_shares(
            self, params):
        """prefix_cache_entries=0 disables the prefix TREE (same contract
        as the slot store's '0 disables reuse') — no dead tree claims, no
        admission matching — but the batch's own shared-prefix pages are
        still one physical copy, freed when the last sharer retires
        (review regression)."""
        prompts = [PREFIX + [5, 9], PREFIX + [7]]
        g = BatchGenerator(CFG, params, settings=SamplerSettings(**GREEDY),
                           kv_layout="paged", prefix_cache_entries=0)
        ref = BatchGenerator(CFG, params,
                             settings=SamplerSettings(**GREEDY),
                             prefix_cache_entries=0)
        g.set_prompts(prompts)
        ref.set_prompts(prompts)
        assert g.generate(5) == ref.generate(5)
        st = g.stats()
        assert st["prefix_entries"] == 0          # tree never fed
        assert st["kvpool"]["pages_shared"] == 2  # batch still shares
        shared_pid = g._tables[0][0]
        assert g._pagepool.refcount(shared_pid) == 2  # streams only
        g.finish(g.streams[0].stream_id)
        g.finish(g.streams[1].stream_id)
        assert g._pagepool.refcount(shared_pid) == 0  # freed with them

    def test_retired_sharer_keeps_pages_alive_for_tree(self, params):
        g = BatchGenerator(CFG, params, settings=SamplerSettings(**GREEDY),
                           kv_layout="paged")
        g.set_prompts([[1], [1]])
        for s in g.streams:
            s.done = True
        g.enqueue(PREFIX + [5, 9], 10)
        _drive(g, want_tokens=4)
        g.finish(10)  # the only sharer retires; the tree keeps the pages
        g.enqueue(PREFIX + [7], 11)
        _drive(g, want_tokens=4)
        assert g.stats()["prefix_hits"] >= 1

    def test_eviction_under_pressure_and_deferral(self, params):
        # pool sized to the bare minimum (2 streams x 4 pages + sink ->
        # 16): prefix-tree claims must evict to keep admissions flowing
        g = BatchGenerator(CFG, params, settings=SamplerSettings(**GREEDY),
                           kv_layout="paged", kv_pool_pages=16)
        g.set_prompts([[1], [1]])
        for s in g.streams:
            s.done = True
        sid = 10
        for k in range(7):
            # distinct 35-token prompts: each stores 2 full pages in the
            # tree, so the accumulated chains must eventually evict to
            # keep admissions flowing through the 16-page pool
            g.enqueue([k + 40] + PREFIX[:32] + [k, 9], sid)
            _drive(g, want_tokens=3)
            g.finish(sid)
            sid += 1
        assert g._pagepool.free_count > 0
        assert g._pagepool._evict_ctr.value > 0

    def test_pool_sizing_validation(self, params):
        g = BatchGenerator(CFG, params, settings=SamplerSettings(**GREEDY),
                           kv_layout="paged", kv_pool_pages=8)
        with pytest.raises(ValueError, match="kv_pool_pages"):
            g.set_prompts(PROMPTS)  # 3 streams x 4 pages + sink > 8

    def test_constructor_validation(self, params):
        with pytest.raises(ValueError, match="paged"):
            BatchGenerator(CFG, params, kv_layout="paged", spec_k=4)
        with pytest.raises(ValueError, match="kv_page_size"):
            BatchGenerator(CFG, params, kv_layout="paged", kv_page_size=7)
        with pytest.raises(ValueError, match="kv_layout"):
            BatchGenerator(CFG, params, kv_layout="blocks")
        # a malformed pool size fails AT CONSTRUCTION (where the CLI's
        # ValueError guard makes it a clean exit), not at set_prompts
        # (review regression); only the batch-dependent bound waits
        with pytest.raises(ValueError, match="power of two"):
            BatchGenerator(CFG, params, kv_layout="paged",
                           kv_pool_pages=100)


# -- no-retrace pin ----------------------------------------------------------
class TestCompilePin:
    def test_page_table_churn_never_retraces(self, params):
        """Page-table updates (growth across boundaries, admission,
        retirement) are DATA, not shapes: the paged decode program's
        compile count matches the slot layout's under the identical
        drive, and stays flat once the admission path has run once."""
        counts = {}
        for layout in ("slot", "paged"):
            g = BatchGenerator(CFG, params,
                               settings=SamplerSettings(**GREEDY),
                               kv_layout=layout)
            g.set_prompts([[5, 9, 2, 11], [3, 1, 4, 1, 5, 9]])
            g.generate(20)  # crosses the 16-token page boundary
            sizes = [g._decode_single_jit._cache_size()]
            for k in range(3):
                for s in g.streams:
                    s.done = True
                g.enqueue([3 + k, 5, 9, 2], 100 + k)
                _drive(g, want_tokens=3)
                sizes.append(g._decode_single_jit._cache_size())
            counts[layout] = sizes
        assert counts["paged"] == counts["slot"]
        # flat after the first admission cycle: later admissions, page
        # allocations and retirements add ZERO compiles
        assert counts["paged"][1] == counts["paged"][-1]

    def test_masked_paged_program_pinned_like_slot(self, params):
        """The masked (constrained) decode program: a second grammar, a
        guide attached through the admission path, and paged page-table
        churn add no compiles beyond what the SLOT layout pays under the
        identical drive — and a fresh same-shape batch adds none at all
        (the per-shape pin of the constrain suite, on paged)."""
        from cake_tpu.constrain import Guide, build_token_dfa

        cfg = tiny(max_seq_len=64)
        p = llama.init_params(cfg, jax.random.PRNGKey(7))
        vocab = [chr(32 + (i % 95)) for i in range(cfg.vocab_size)]
        d1 = build_token_dfa("[0-9]{1,8}", vocab,
                             eos_ids=(cfg.eos_token_id,))
        d2 = build_token_dfa("[a-f]{1,6}", vocab,
                             eos_ids=(cfg.eos_token_id,))
        counts = {}
        for layout in ("slot", "paged"):
            g = BatchGenerator(cfg, p, settings=SamplerSettings(**GREEDY),
                               kv_layout=layout)
            g.set_prompts([[5, 6, 7], [8, 9, 10]],
                          guides=[Guide(d1), None])
            g.generate(6)
            c1 = g._masked_jit._cache_size()
            g.finish(0)
            g.enqueue([5, 6, 7], 9, guide=Guide(d2))  # admission splice
            _drive(g, want_tokens=4)
            c2 = g._masked_jit._cache_size()
            # a different grammar in a FRESH same-shape batch: no compile
            g.set_prompts([[5, 6, 7], [8, 9, 10]],
                          guides=[None, Guide(d2)])
            g.generate(4)
            counts[layout] = (c1, c2, g._masked_jit._cache_size())
            assert counts[layout][2] == counts[layout][1]
        assert counts["paged"] == counts["slot"]


# -- serving plane + churn workload ------------------------------------------
class TestServe:
    @pytest.fixture(scope="class")
    def paged_server(self, params):
        from cake_tpu.serve.api import start_api_server
        from cake_tpu.serve.scheduler import Scheduler

        cfg = tiny(max_seq_len=64, eos_token_id=-1)
        p = llama.init_params(cfg, jax.random.PRNGKey(7))
        gen = BatchGenerator(cfg, p, settings=SamplerSettings(**GREEDY),
                             kv_layout="paged")
        sched = Scheduler(gen, queue_depth=8, request_timeout_s=120)
        sched.start(max_concurrent=2, warm_prompt_len=8)
        srv = start_api_server(sched)
        yield srv
        srv.close()
        sched.close()

    def test_healthz_reports_pool_pressure(self, paged_server):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{paged_server.port}/healthz",
                timeout=30) as r:
            body = json.loads(r.read())
        assert body["ok"] and "kv_pages_free" in body
        assert body["kv_pages_free"] > 0

    def test_loadgen_churn_workload_over_paged_server(self, paged_server):
        """The churn regime over real HTTP: Poisson arrivals, short/long
        prompt mix, early disconnects — the paged server reaps
        disconnected slots and completes everything else."""
        from cake_tpu.tools.loadgen import run_load

        stats = run_load(f"http://127.0.0.1:{paged_server.port}", n=8,
                         max_tokens=12, vocab=CFG.vocab_size,
                         seed=3, timeout=120.0, workload="churn",
                         rate=6.0, prompt_lens=[4, 20],
                         disconnect_every=3)
        assert stats["errors"] == 0
        assert stats["disconnected"] >= 2   # every 3rd of 8 walked away
        assert stats["completed"] == 8      # disconnects still streamed

    def test_churn_disconnect_zero_really_disables(self, paged_server):
        """--disconnect-every 0 under the churn workload means NEVER, as
        the help promises — 0 must not be mistaken for the unset sentinel
        that triggers the churn default of 4 (review regression)."""
        from cake_tpu.tools.loadgen import run_load

        stats = run_load(f"http://127.0.0.1:{paged_server.port}", n=4,
                         max_tokens=6, vocab=CFG.vocab_size, seed=5,
                         timeout=120.0, workload="churn", rate=8.0,
                         prompt_lens=[4], disconnect_every=0)
        assert stats["errors"] == 0 and stats["disconnected"] == 0

    def test_churn_workload_validation(self):
        from cake_tpu.tools.loadgen import run_load

        with pytest.raises(ValueError, match="churn"):
            run_load("http://127.0.0.1:1", n=1, workload="churn",
                     stream=False)
        with pytest.raises(ValueError, match="workload"):
            run_load("http://127.0.0.1:1", n=1, workload="nope")
