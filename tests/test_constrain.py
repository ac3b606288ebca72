"""Structured generation (cake_tpu/constrain): grammar-constrained
decoding, stop sequences, and logprobs across the engine and serve plane.

`make constrain-smoke` acceptance: regex/JSON-schema -> token-DFA -> mask
round trips (unicode/byte-level tokenizer edges included), the disk-cache
hit path, schema-constrained serve requests returning valid JSON through
the full HTTP plane, the masked decode step compiling once per shape (no
retrace per token OR per grammar), stop-string holdback across SSE chunk
boundaries, logprobs against a numpy softmax reference, and the
determinism guard: unconstrained streams are bit-identical whether or not
the mask/logprob plumbing is active around them.
"""

import json
import re
import threading
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from cake_tpu.constrain import fsm as fsm_mod
from cake_tpu.constrain import (
    Guide,
    RegexError,
    build_token_dfa,
    json_schema_to_regex,
)
from cake_tpu.constrain.guide import DEAD_ENDS
from cake_tpu.models import llama
from cake_tpu.models.config import tiny
from cake_tpu.ops import sampling
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.runtime.batch_generator import BatchGenerator
from cake_tpu.runtime.generator import LlamaGenerator
from cake_tpu.serve import session as serve_session
from cake_tpu.serve.api import start_api_server
from cake_tpu.serve.engine import SingleStreamEngine
from cake_tpu.serve.scheduler import Scheduler
from cake_tpu.serve.session import Session

# EOS *enabled* (unlike test_serve): constrained streams must be able to
# terminate exactly when their grammar completes
CFG = tiny(max_seq_len=128, eos_token_id=2)
GREEDY = dict(temperature=0.0, repeat_penalty=1.1)
EOS = 2


class AsciiTok:
    """id -> one printable-ASCII char (mod 95). Many-to-one on purpose:
    several ids share each char, like merged BPE vocab entries."""

    def decode(self, ids):
        return "".join(chr(32 + (i % 95)) for i in ids)

    def encode(self, text):
        return [ord(c) - 32 for c in text]


def _ascii_vocab(n=CFG.vocab_size):
    t = AsciiTok()
    return [t.decode([i]) for i in range(n)]


# small hand-rolled vocab for DFA unit tests: single chars + multi-char +
# unicode + an empty-string token (undecodable id)
TOY_VOCAB = [chr(c) for c in range(32, 127)] + ["ab", "12", "é", "∑x", ""]
TOY_EOS = (3,)  # id 3 = '#': its TEXT must never satisfy a transition


def tid(s: str) -> int:
    return TOY_VOCAB.index(s)


SCHEMA = {
    "type": "object",
    "properties": {
        "a": {"type": "integer"},
        "ok": {"type": "boolean"},
    },
    "required": ["a", "ok"],
}


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def server(params):
    """BatchGenerator with tokenizer + logprob capacity 3 behind the
    HTTP API — the full structured-output serving surface."""
    gen = BatchGenerator(CFG, params, tokenizer=AsciiTok(),
                         settings=SamplerSettings(**GREEDY), logprobs=3)
    sched = Scheduler(gen, queue_depth=4, request_timeout_s=120)
    sched.start(max_concurrent=2)
    srv = start_api_server(sched)
    yield srv
    srv.close()
    sched.close()


def _post(srv, body: dict, timeout: float = 120.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/v1/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _post_sse(srv, body: dict, timeout: float = 120.0):
    body = dict(body, stream=True)
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/v1/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    events = []
    with urllib.request.urlopen(req, timeout=timeout) as r:
        for raw in r:
            raw = raw.strip()
            if not raw.startswith(b"data: "):
                continue
            data = raw[len(b"data: "):]
            events.append(data.decode() if data == b"[DONE]"
                          else json.loads(data))
    return events


# -- regex -> token DFA ---------------------------------------------------

class TestTokenDfa:
    def test_digit_run_masks_transitions_accepting(self):
        d = build_token_dfa("[0-9]+", TOY_VOCAB, eos_ids=TOY_EOS)
        m0 = d.mask_bool(0)
        allowed = {TOY_VOCAB[i] for i in range(len(TOY_VOCAB)) if m0[i]}
        assert allowed == set("0123456789") | {"12"}  # multi-char token
        assert not d.accepting[0]
        s1 = int(d.trans[0, tid("7")])
        assert d.accepting[s1]
        assert d.mask_bool(s1)[TOY_EOS[0]]  # EOS allowed once accepting
        s2 = int(d.trans[0, tid("12")])  # two chars in one token
        assert d.accepting[s2]

    def test_empty_string_token_never_allowed(self):
        d = build_token_dfa(".*", TOY_VOCAB, eos_ids=TOY_EOS)
        empty = len(TOY_VOCAB) - 1
        assert TOY_VOCAB[empty] == ""
        assert not d.mask_bool(0)[empty]  # zero-width = infinite no-op

    def test_eos_id_never_matches_as_text(self):
        # id 3 decodes to '#'; pattern '#' must be satisfied only by the
        # OTHER '#' token, never by the EOS id
        d = build_token_dfa("#", TOY_VOCAB, eos_ids=TOY_EOS)
        m0 = d.mask_bool(0)
        assert not m0[TOY_EOS[0]]
        assert m0[tid("#")] or True  # '#' is id 3 itself in TOY_VOCAB?
        # TOY_VOCAB has exactly one '#', which IS the eos id -> dead end
        assert tid("#") == TOY_EOS[0]
        assert not m0.any()

    def test_unicode_tokens_walk_the_dfa(self):
        d = build_token_dfa("é+(∑x)?", TOY_VOCAB, eos_ids=TOY_EOS)
        m0 = d.mask_bool(0)
        assert m0[tid("é")]
        assert not m0[tid("a")]
        s1 = int(d.trans[0, tid("é")])
        assert d.accepting[s1]
        assert d.mask_bool(s1)[tid("∑x")]  # 2-codepoint token in one hop
        s2 = int(d.trans[s1, tid("∑x")])
        assert d.accepting[s2]
        # grammar exhausted: only EOS remains
        m2 = d.mask_bool(s2)
        assert {i for i in range(len(TOY_VOCAB)) if m2[i]} == {TOY_EOS[0]}

    def test_quantifiers_classes_alternation(self):
        d = build_token_dfa("(a|b){2,3}[^0-9x]?", TOY_VOCAB,
                            eos_ids=TOY_EOS)
        s = 0
        for ch in "ab":
            s = int(d.trans[s, tid(ch)])
            assert s >= 0
        assert d.accepting[s]
        m = d.mask_bool(s)
        assert m[tid("a")] and m[tid("q")] and not m[tid("5")]
        assert not m[tid("x")]

    def test_guide_advance_and_dead_end(self):
        d = build_token_dfa("A\x07", TOY_VOCAB, eos_ids=TOY_EOS)
        g = Guide(d)
        assert g.allows(tid("A")) and not g.dead_end
        assert g.advance(tid("A"))
        # \x07 (BEL) exists in no vocab string: nothing can be emitted
        assert g.dead_end
        assert not g.advance(tid("B"))

    def test_regex_errors(self):
        for bad in ("(a", "a)", "[z-a]", "*a", "a{3,1}"):
            with pytest.raises(RegexError):
                build_token_dfa(bad, TOY_VOCAB, eos_ids=TOY_EOS)


class TestJsonSchema:
    def test_lowering_matches_python_re(self):
        pat = json_schema_to_regex(SCHEMA)
        assert re.fullmatch(pat, '{"a": -42, "ok": true}')
        assert re.fullmatch(pat, '{"a": 0, "ok": false}')
        assert not re.fullmatch(pat, '{"a": 1.5, "ok": true}')
        assert not re.fullmatch(pat, '{"ok": true, "a": 1}')

    def test_types_enum_array_string(self):
        assert re.fullmatch(json_schema_to_regex({"type": "null"}), "null")
        num = json_schema_to_regex({"type": "number"})
        assert re.fullmatch(num, "-3.25") and re.fullmatch(num, "17")
        en = json_schema_to_regex({"enum": ["hi", 3, None]})
        for lit in ('"hi"', "3", "null"):
            assert re.fullmatch(en, lit)
        arr = json_schema_to_regex(
            {"type": "array", "items": {"type": "boolean"},
             "maxItems": 2})
        for lit in ("[]", "[true]", "[true, false]"):
            assert re.fullmatch(arr, lit)
        assert not re.fullmatch(arr, "[true, true, true]")
        s = json_schema_to_regex({"type": "string", "maxLength": 3})
        assert re.fullmatch(s, '"ab"') and not re.fullmatch(s, '"abcd"')

    def test_bounded_termination(self):
        # the lowered automaton is acyclic: greedily walking ANY allowed
        # path must reach only-EOS within a bounded number of tokens
        pat = json_schema_to_regex(SCHEMA)
        d = build_token_dfa(pat, _ascii_vocab(), eos_ids=(EOS,))
        g = Guide(d)
        for _ in range(64):
            m = g.mask_bool()
            choices = np.flatnonzero(m)
            assert len(choices)
            if list(choices) == [EOS]:
                break
            nxt = next(int(c) for c in choices if c != EOS)
            assert g.advance(nxt)
        else:
            pytest.fail("schema DFA did not terminate in 64 tokens")

    def test_unsupported_schema_raises(self):
        with pytest.raises(RegexError):
            json_schema_to_regex({"type": "object",
                                  "properties": {"x": {"$ref": "#/x"}}})
        with pytest.raises(RegexError):
            json_schema_to_regex({"oneOf": []})


class TestDiskCache:
    def test_disk_cache_hit_path(self, tmp_path):
        vocab = TOY_VOCAB
        hits0 = fsm_mod.FSM_CACHE_HITS.value
        miss0 = fsm_mod.FSM_CACHE_MISSES.value
        fsm_mod._MEMO.clear()
        d1 = fsm_mod.compile_constraint("[a-f]{2,4}", vocab,
                                        eos_ids=TOY_EOS,
                                        cache_dir=str(tmp_path))
        assert fsm_mod.FSM_CACHE_MISSES.value == miss0 + 1
        assert list(tmp_path.glob("*.npz"))
        fsm_mod._MEMO.clear()  # force the DISK path, not the memo
        d2 = fsm_mod.compile_constraint("[a-f]{2,4}", vocab,
                                        eos_ids=TOY_EOS,
                                        cache_dir=str(tmp_path))
        assert fsm_mod.FSM_CACHE_HITS.value == hits0 + 1
        np.testing.assert_array_equal(d1.trans, d2.trans)
        np.testing.assert_array_equal(d1.mask_bits, d2.mask_bits)
        np.testing.assert_array_equal(d1.accepting, d2.accepting)
        # memo path counts as a hit too
        fsm_mod.compile_constraint("[a-f]{2,4}", vocab, eos_ids=TOY_EOS,
                                   cache_dir=str(tmp_path))
        assert fsm_mod.FSM_CACHE_HITS.value == hits0 + 2


# -- engine integration ---------------------------------------------------

def _json_guide(vocab=None):
    pat = json_schema_to_regex(SCHEMA)
    return Guide(build_token_dfa(pat, vocab or _ascii_vocab(),
                                 eos_ids=(EOS,)))


class TestEngine:
    def test_constrained_stream_valid_json_others_bit_identical(self,
                                                                params):
        base = BatchGenerator(CFG, params, tokenizer=AsciiTok(),
                              settings=SamplerSettings(**GREEDY))
        base.set_prompts([[5, 6, 7], [8, 9, 10]])
        ref = base.generate(24)

        gen = BatchGenerator(CFG, params, tokenizer=AsciiTok(),
                             settings=SamplerSettings(**GREEDY))
        gen.set_prompts([[5, 6, 7], [8, 9, 10]],
                        guides=[None, _json_guide()])
        out = gen.generate(40)
        # the unconstrained neighbor is bit-identical to its solo run —
        # mask plumbing (row 0 = all-ones) must not perturb it
        assert out[0][:24] == ref[0]
        s1 = gen.streams[1]
        assert s1.end_reason == "eos"
        text = AsciiTok().decode([t for t in s1.generated if t != EOS])
        obj = json.loads(text)
        assert isinstance(obj["a"], int) and isinstance(obj["ok"], bool)

    def test_a_live_guide_keeps_the_host_between_steps(self, params):
        """The DFA advance is host-side between steps, so while a guide
        is live nothing is enqueued ahead and no block lands; the engine
        knows it from its own state (no switch), and the moment the
        constrained stream ends fused blocks go out again, each before
        the landed block's rows (``engine.boundaries_ahead``)."""
        from cake_tpu.obs import metrics

        reg = metrics.registry()

        def counters():
            return (reg.counter("engine.boundaries").value,
                    reg.counter("engine.boundaries_ahead").value)

        base = BatchGenerator(CFG, params, tokenizer=AsciiTok(),
                              settings=SamplerSettings(**GREEDY))
        base.set_prompts([[5, 6, 7], [8, 9, 10]])
        ref = base.generate(40)[0]
        gen = BatchGenerator(CFG, params, tokenizer=AsciiTok(),
                             settings=SamplerSettings(**GREEDY),
                             block_size=4)
        gen.set_prompts([[5, 6, 7], [8, 9, 10]],
                        guides=[None, _json_guide()])
        b0, a0 = counters()
        got, guided_steps = [], 0
        for _ in range(60):
            live = gen._guides_live()
            row = gen.step()
            if row[0] is not None:
                got.append(row[0].id)
            if live:
                guided_steps += 1
                assert gen._inflight is None and counters() == (b0, a0)
        b1, a1 = counters()
        assert guided_steps >= 8 and not gen._guides_live()
        assert b1 - b0 == a1 - a0 >= 2  # blocks again, each ahead
        assert len(got) >= 40 and got[:40] == ref

    def test_logprobs_engine_streams_bit_identical(self, params):
        base = BatchGenerator(CFG, params,
                              settings=SamplerSettings(**GREEDY))
        base.set_prompts([[5, 6, 7], [8, 9, 10]])
        ref = base.generate(16)
        gen = BatchGenerator(CFG, params,
                             settings=SamplerSettings(**GREEDY),
                             logprobs=4)
        gen.set_prompts([[5, 6, 7], [8, 9, 10]])
        assert gen.generate(16) == ref

    def test_greedy_top1_logprob_is_emitted_token(self, params):
        # repeat_penalty 1.0: raw-logit argmax IS the sampled token, so
        # the reported top-1 id must equal the emitted id every step
        gen = BatchGenerator(
            CFG, params,
            settings=SamplerSettings(temperature=0.0, repeat_penalty=1.0),
            logprobs=2)
        gen.set_prompts([[5, 6, 7]])
        rows = [gen.step() for _ in range(6)]
        toks = [r[0] for r in rows if r[0] is not None]
        assert toks
        for t in toks:
            assert t.logprobs is not None and len(t.logprobs) == 2
            assert t.logprobs[0][0] == t.id
            assert t.logprobs[0][1] <= 0.0

    def test_masked_program_compiles_once_per_shape(self, params):
        """The acceptance pin: N constrained tokens across TWO different
        grammars = zero retraces beyond the initial compile(s) for the
        (batch, table-capacity) shape."""
        gen = BatchGenerator(CFG, params, tokenizer=AsciiTok(),
                             settings=SamplerSettings(**GREEDY))
        gen.set_prompts([[5, 6], [7, 8]])
        for s in gen.streams:
            s.done = True
        gen.enqueue([5, 6, 7], 10, guide=_json_guide())
        sl = None
        for _ in range(80):
            gen.step()
            sl = next((s for s in gen.streams if s.stream_id == 10), None)
            if sl is not None and sl.done:
                break
        assert sl is not None and sl.done and sl.end_reason == "eos"
        c1 = gen._masked_jit._cache_size()
        assert c1 <= 2  # first dispatch + committed-sharding steady state
        # a different grammar, same table capacity: NO new compile
        g2 = Guide(build_token_dfa("x=[0-9]{1,4};", _ascii_vocab(),
                                   eos_ids=(EOS,)))
        gen.enqueue([5, 6, 7], 11, guide=g2)
        sl = None
        for _ in range(80):
            gen.step()
            sl = next((s for s in gen.streams if s.stream_id == 11), None)
            if sl is not None and sl.done:
                break
        assert sl is not None and sl.done
        text = AsciiTok().decode([t for t in sl.generated if t != EOS])
        assert re.fullmatch(r"x=[0-9]{1,4};", text)
        assert gen._masked_jit._cache_size() == c1

    def test_dead_end_sets_constraint_reason_and_counter(self, params):
        dead0 = DEAD_ENDS.value
        # after 'A', the grammar demands \x07 — no vocab string has it
        g = Guide(build_token_dfa("A\x07B", _ascii_vocab(),
                                  eos_ids=(EOS,)))
        gen = BatchGenerator(CFG, params, tokenizer=AsciiTok(),
                             settings=SamplerSettings(**GREEDY))
        gen.set_prompts([[5, 6, 7]], guides=[g])
        gen.generate(4)
        s = gen.streams[0]
        assert s.done and s.end_reason == "constraint"
        assert DEAD_ENDS.value == dead0 + 1
        assert not gen._guides  # guide released with the stream

    def test_logit_bias_forces_token_and_validates(self, params):
        st = SamplerSettings(temperature=0.0, repeat_penalty=1.0,
                             logit_bias=((7, 1e4),))
        gen = BatchGenerator(CFG, params, settings=st)
        gen.set_prompts([[5, 6]])
        out = gen.generate(3)
        assert out[0] == [7, 7, 7]
        with pytest.raises(ValueError, match="out of range"):
            BatchGenerator(CFG, params, settings=SamplerSettings(
                logit_bias=((CFG.vocab_size, 1.0),)))

    def test_eos_ids_public_property(self, params):
        gen = BatchGenerator(CFG, params,
                             settings=SamplerSettings(**GREEDY))
        assert gen.eos_ids == frozenset(CFG.eos_ids())
        sse = SingleStreamEngine(
            LlamaGenerator(CFG, params, settings=SamplerSettings(**GREEDY)))
        assert sse.eos_ids == frozenset(CFG.eos_ids())

    def test_guides_do_not_compose_with_speculation(self, params):
        gen = BatchGenerator(CFG, params, tokenizer=AsciiTok(),
                             settings=SamplerSettings(**GREEDY), spec_k=4)
        with pytest.raises(ValueError, match="speculation"):
            gen.set_prompts([[5, 6, 7]], guides=[_json_guide()])
        # the serve path: enqueue must raise IMMEDIATELY (scheduler turns
        # ValueError into a 400) — deferring to the attach inside step()
        # would read as an engine fault and drain the whole server
        gen.set_prompts([[5, 6, 7]])
        for s in gen.streams:
            s.done = True
        with pytest.raises(ValueError, match="speculation"):
            gen.enqueue([5, 6], 9, guide=_json_guide())

    def test_warm_constrain_precompiles_masked_program(self, params):
        gen = BatchGenerator(CFG, params, tokenizer=AsciiTok(),
                             settings=SamplerSettings(**GREEDY))
        sched = Scheduler(gen, queue_depth=2)
        sched.start(max_concurrent=2, warm_prompt_len=8,
                    warm_constrain=True)
        try:
            assert gen._masked_jit is not None
            assert gen._masked_jit._cache_size() >= 1
        finally:
            sched.stop(drain=False, timeout_s=10)

    def test_logprobs_with_adaptive_block_ladder(self, params):
        # ladder rungs must carry the logprob outputs too (a 4-tuple
        # rung under logprobs_k>0 crashed the unpack)
        gen = BatchGenerator(CFG, params,
                             settings=SamplerSettings(**GREEDY),
                             logprobs=2, block_size=2, block_size_max=8)
        gen.set_prompts([[5, 6, 7]])
        rows = [gen.step() for _ in range(18)]  # a landing hands out none
        toks = [r[0] for r in rows if r and r[0] is not None]
        assert len(toks) >= 12
        assert all(t.logprobs is not None for t in toks)

    def test_single_stream_generator_guide(self, params):
        gen = LlamaGenerator(CFG, params, tokenizer=AsciiTok(),
                             settings=SamplerSettings(**GREEDY))
        gen.set_prompt([5, 6, 7])
        gen.set_guide(Guide(build_token_dfa("ok=[a-z]{2,5}!",
                                            _ascii_vocab(),
                                            eos_ids=(EOS,))))
        toks = []
        for i in range(24):
            t = gen.next_token(i)
            if t.is_end_of_stream:
                break
            toks.append(t.id)
        text = AsciiTok().decode(toks)
        assert re.fullmatch(r"ok=[a-z]{2,5}!", text)

    def test_unsupported_generator_refuses_guide(self, params):
        from cake_tpu.runtime.mesh_generator import MeshGenerator

        gen = MeshGenerator(CFG, params,
                            settings=SamplerSettings(**GREEDY))
        with pytest.raises(ValueError, match="constrained"):
            gen.set_guide(_json_guide())


# -- stop-string holdback -------------------------------------------------

class TestStopHoldback:
    def _drain_tokens(self, sess):
        out = []
        while not sess.events.empty():
            ev = sess.events.get_nowait()
            if ev[0] == "token":
                out.append((ev[1], ev[2]))
        return out

    def test_match_across_token_boundaries_never_leaks(self):
        sess = Session([1], max_tokens=32, stop=["bcd"])
        for tok, txt in ((10, "a"), (11, "b"), (12, "c")):
            sess.on_token(tok, txt)
        # "abc" could still become "a" + "bcd": only 'a' may flush
        assert self._drain_tokens(sess) == [(10, "a")]
        sess.on_token(13, "d")
        assert sess.stop_hit
        assert self._drain_tokens(sess) == []  # b,c,d are the stop string
        assert sess.generated == [10]
        sess.finish("length")
        done = sess.events.get_nowait()
        assert done[0] == "done" and done[1] == "stop" and done[3] is None

    def test_partial_prefix_flushes_when_disproved(self):
        sess = Session([1], max_tokens=32, stop=["XYZ"])
        sess.on_token(1, "X")
        sess.on_token(2, "Y")
        assert self._drain_tokens(sess) == []  # plausible prefix: held
        sess.on_token(3, "Q")  # "XYQ" can no longer match
        assert self._drain_tokens(sess) == [(1, "X"), (2, "Y"), (3, "Q")]
        assert not sess.stop_hit

    def test_straddling_token_contributes_pre_match_tail(self):
        sess = Session([1], max_tokens=32, stop=["bc"])
        sess.on_token(1, "ab")  # 'a' is output, 'b' opens the match
        sess.on_token(2, "cd")
        assert sess.stop_hit
        assert self._drain_tokens(sess) == []
        assert sess.generated == []  # both ids straddle/contain the stop
        sess.finish("length")
        done = sess.events.get_nowait()
        assert done[1] == "stop" and done[3] == "a"

    def test_zero_width_events_hold_with_following_text(self):
        # detok withheld text: the None-text token's chars surface later
        # attributed to the next token — its id must not leak early
        sess = Session([1], max_tokens=32, stop=["mn"])
        sess.on_token(1, "k")
        sess.on_token(2, None)
        sess.on_token(3, "m")  # could open "mn"
        assert self._drain_tokens(sess) == [(1, "k")]
        sess.on_token(4, "np")
        assert sess.stop_hit
        assert sess.generated == [1]

    def test_match_inside_detok_tail(self):
        sess = Session([1], max_tokens=32, stop=["uv"])
        sess.on_token(1, "s")
        sess.finish("length", tail_text="tuvw")
        assert sess.stop_hit and sess.finish_reason == "stop"
        evs = []
        while not sess.events.empty():
            evs.append(sess.events.get_nowait())
        assert evs[0][:3] == ("token", 1, "s")
        assert evs[-1][0] == "done" and evs[-1][1] == "stop"
        assert evs[-1][3] == "t"  # tail truncated at the match


# -- serve plane ----------------------------------------------------------

class TestServe:
    def test_schema_constrained_request_returns_valid_json(self, server):
        out = _post(server, {
            "prompt_ids": [5, 6, 7], "max_tokens": 48,
            "response_format": {"type": "json_schema", "schema": SCHEMA},
        })
        assert out["finish_reason"] == "eos"
        obj = json.loads(out["text"])
        assert isinstance(obj["a"], int) and isinstance(obj["ok"], bool)
        # and streaming: assembled SSE text parses too
        evs = _post_sse(server, {
            "prompt_ids": [5, 6, 7], "max_tokens": 48,
            "response_format": {"type": "json_schema", "schema": SCHEMA},
        })
        text = "".join(e.get("text") or "" for e in evs
                       if isinstance(e, dict) and not e.get("done"))
        text += next(e.get("text") or "" for e in evs
                     if isinstance(e, dict) and e.get("done"))
        assert json.loads(text) == obj

    def test_regex_response_format(self, server):
        out = _post(server, {
            "prompt_ids": [8, 9], "max_tokens": 24,
            "response_format": {"type": "regex",
                                "pattern": "v=[0-9]{1,3}(\\.[0-9])?"},
        })
        assert out["finish_reason"] == "eos"
        assert re.fullmatch(r"v=[0-9]{1,3}(\.[0-9])?", out["text"])

    def test_dead_end_finish_reason_constraint(self, server):
        out = _post(server, {
            "prompt_ids": [5, 6], "max_tokens": 8,
            "response_format": {"type": "regex", "pattern": "Q\x07Z"},
        })
        assert out["finish_reason"] == "constraint"

    def test_stop_string_sse_holdback(self, server):
        full = _post(server, {"prompt_ids": [5, 6, 7],
                              "max_tokens": 16})["text"]
        sub = full[3:6]
        assert len(sub) == 3
        evs = _post_sse(server, {"prompt_ids": [5, 6, 7],
                                 "max_tokens": 16, "stop": [sub]})
        done = next(e for e in evs
                    if isinstance(e, dict) and e.get("done"))
        assert done["finish_reason"] == "stop"
        streamed = "".join(e.get("text") or "" for e in evs
                           if isinstance(e, dict) and "token" in e)
        text = streamed + (done.get("text") or "")
        assert sub not in text
        assert text == full[:3]
        # eos still reports "eos", distinct from stop-string "stop"
        out = _post(server, {
            "prompt_ids": [5, 6], "max_tokens": 24,
            "response_format": {"type": "regex", "pattern": "[a-z]{1,4}"},
        })
        assert out["finish_reason"] == "eos"

    def test_logprobs_in_events_and_usage(self, server):
        evs = _post_sse(server, {"prompt_ids": [5, 6, 7],
                                 "max_tokens": 4, "logprobs": 2})
        toks = [e for e in evs if isinstance(e, dict) and "token" in e]
        assert len(toks) == 4
        for e in toks:
            assert len(e["logprobs"]) == 2
            assert e["logprobs"][0]["logprob"] <= 0.0
        done = next(e for e in evs
                    if isinstance(e, dict) and e.get("done"))
        assert len(done["usage"]["logprobs"]) == 4

    def test_structured_knob_rejections(self, server):
        for body, frag in (
            ({"logprobs": 9}, "capacity"),
            ({"logit_bias": {"999999": 1.0}}, "out of range"),
            ({"logit_bias": {"5": 2.0}}, "compiles one sampler"),
            ({"response_format": {"type": "nope"}}, "response_format"),
            ({"response_format": {"type": "regex", "pattern": "(a"}},
             "response_format"),
            ({"stop": []}, "stop"),
            ({"stop": "x" * 9 * 9, "extra_stop": None}, None),
        ):
            if frag is None:
                continue
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(server, dict({"prompt_ids": [5], "max_tokens": 2},
                                   **body))
            assert exc.value.code == 400
            assert frag in json.loads(exc.value.read())["error"]

    def test_stop_matches_counter_moves(self, server):
        before = serve_session.STOP_MATCHES.value
        full = _post(server, {"prompt_ids": [8, 9, 10],
                              "max_tokens": 12})["text"]
        _post(server, {"prompt_ids": [8, 9, 10], "max_tokens": 12,
                       "stop": [full[2:4]]})
        assert serve_session.STOP_MATCHES.value > before

    def test_concurrent_constrained_and_plain_clients(self, server):
        """A constrained and an unconstrained stream share the batch; the
        plain stream's ids match its solo run (composition invariance
        through the masked program's row-0 path)."""
        solo = _post(server, {"prompt_ids": [11, 12, 13],
                              "max_tokens": 10})
        results = {}

        def plain():
            results["plain"] = _post(server, {
                "prompt_ids": [11, 12, 13], "max_tokens": 10})

        def constrained():
            results["json"] = _post(server, {
                "prompt_ids": [5, 6, 7], "max_tokens": 48,
                "response_format": {"type": "json_schema",
                                    "schema": SCHEMA}})

        threads = [threading.Thread(target=f)
                   for f in (plain, constrained)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert results["plain"]["token_ids"] == solo["token_ids"]
        json.loads(results["json"]["text"])


# -- logprob math ---------------------------------------------------------

def test_topk_logprobs_vs_numpy_reference():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(3, 64)).astype(np.float32) * 3
    vals, ids = sampling.topk_logprobs(jax.numpy.asarray(logits), 5)
    vals, ids = np.asarray(vals), np.asarray(ids)
    ref = logits - np.log(np.exp(
        logits - logits.max(-1, keepdims=True)).sum(-1, keepdims=True)) \
        - logits.max(-1, keepdims=True)
    for b in range(3):
        order = np.argsort(ref[b])[::-1][:5]
        np.testing.assert_array_equal(ids[b], order)
        np.testing.assert_allclose(vals[b], ref[b][order], rtol=1e-5,
                                   atol=1e-5)


def test_unpack_mask_bits_round_trip():
    rng = np.random.default_rng(1)
    for v in (8, 13, 256):
        mask = rng.integers(0, 2, size=(4, v)).astype(np.uint8)
        packed = np.packbits(mask, axis=1, bitorder="little")
        out = np.asarray(sampling.unpack_mask_bits(
            jax.numpy.asarray(packed), v))
        np.testing.assert_array_equal(out, mask.astype(bool))
