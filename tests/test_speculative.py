"""N-gram speculative decoding (runtime/speculative).

The bar: greedy output BIT-IDENTICAL to plain decode on every stream
(speculation may only change how many tokens land per dispatch, never which
tokens), with tokens-per-dispatch > 1 on self-repeating streams."""

import jax
import numpy as np
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import tiny
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.runtime.generator import LlamaGenerator
from cake_tpu.runtime.speculative import SpeculativeGenerator, ngram_propose

CFG = tiny(max_seq_len=128)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(2))


# -- proposal machinery -------------------------------------------------------

def test_ngram_propose_copies_after_last_match():
    #                     0  1  2  3  4  5  6  7
    ctx = [7, 1, 2, 3, 9, 1, 2, 3]
    # trailing 3-gram (1,2,3) matched at position 1; continuation is [9, 1, 2]
    assert ngram_propose(ctx, n_max=3, k=3) == [9, 1, 2]


def test_ngram_propose_backs_off_to_shorter_ngrams():
    ctx = [5, 8, 5, 9, 5]  # trailing (9,5) unseen; trailing (5) -> after idx 2
    assert ngram_propose(ctx, n_max=2, k=2) == [9, 5]


def test_ngram_propose_no_match_or_degenerate():
    assert ngram_propose([1, 2, 3], n_max=3, k=4) == []
    assert ngram_propose([4], n_max=3, k=4) == []
    assert ngram_propose([], n_max=3, k=4) == []


def test_ngram_propose_most_recent_match_wins():
    ctx = [1, 2, 7, 1, 2, 8, 1, 2]
    assert ngram_propose(ctx, n_max=2, k=1) == [8]  # the later occurrence


# -- greedy exactness ---------------------------------------------------------

def _plain(params, prompt, n, settings):
    g = LlamaGenerator(CFG, params, settings=settings)
    g.set_prompt(prompt)
    out = []
    for i in range(n):
        t = g.next_token(i)
        out.append(t.id)
        if t.is_end_of_stream:
            break
    return out


def _spec(params, prompt, n, settings, **kw):
    g = SpeculativeGenerator(CFG, params, settings=settings, **kw)
    g.set_prompt(prompt)
    out = []
    for i in range(n):
        t = g.next_token(i)
        out.append(t.id)
        if t.is_end_of_stream:
            break
    return out, g


@pytest.mark.parametrize("prompt", [
    [5, 9, 2, 5, 9, 2, 5, 9],          # self-repeating: high acceptance
    [3, 1, 4, 1, 5, 9, 2, 6],          # mixed
    [11, 7],                           # short, nothing to match at first
])
def test_greedy_tokens_bit_identical_to_plain_decode(params, prompt):
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    want = _plain(params, prompt, 24, settings)
    got, _ = _spec(params, prompt, 24, settings, spec_k=6)
    assert got == want


def test_no_repeat_penalty_path_also_exact(params):
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    prompt = [2, 8, 2, 8, 2, 8]
    want = _plain(params, prompt, 24, settings)
    got, _ = _spec(params, prompt, 24, settings, spec_k=8)
    assert got == want


def test_speculation_reduces_dispatches_on_repeating_stream(params):
    """A greedy stream that cycles (tiny random models loop readily; the
    prompt seeds the loop) must land >1 token per dispatch on average."""
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    prompt = [5, 9, 2, 5, 9, 2, 5, 9]
    got, g = _spec(params, prompt, 32, settings, spec_k=6)
    # accepted tokens either streamed out or are still buffered
    assert g.emitted == len(got) + len(g._block_buf)
    assert g.dispatches < g.emitted  # strictly fewer dispatches than tokens
    assert got == _plain(params, prompt, 32, settings)


def test_eos_inside_speculation_stops_stream(params):
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    prompt = [5, 9, 2, 5, 9, 2, 5, 9]
    ref = _plain(params, prompt, 12, settings)
    eos_cfg = tiny(max_seq_len=128, eos_token_id=ref[5])
    g = SpeculativeGenerator(eos_cfg, params,
                             settings=settings, spec_k=8)
    g.set_prompt(prompt)
    out = []
    for i in range(12):
        t = g.next_token(i)
        out.append(t.id)
        if t.is_end_of_stream:
            break
    assert out == ref[:6]
    assert out[-1] == ref[5]


def test_window_edge_falls_back_to_single_steps(params):
    """Near max_seq the verification round would overrun the window: the
    generator falls back to plain single steps and still matches."""
    cfg = tiny(max_seq_len=32)
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    prompt = [5, 9, 2, 5, 9, 2] * 3  # 18 tokens, 14 slots left
    plain = LlamaGenerator(cfg, params, settings=settings)
    plain.set_prompt(prompt)
    want = [plain.next_token(i).id for i in range(13)]
    g = SpeculativeGenerator(cfg, params, settings=settings, spec_k=8)
    g.set_prompt(prompt)
    got = [g.next_token(i).id for i in range(13)]
    assert got == want


# -- rejection sampling (temperature > 0) -------------------------------------

def test_rejection_accept_preserves_distribution():
    """Statistical contract of accept_sampled_fn: each emitted token's
    conditional distribution equals the plain sampler's categorical p,
    whether the proposal is likely, unlikely, or a -1 pad. Empirical TV
    distance over many independent round keys vs the exact p."""
    import jax.numpy as jnp

    from cake_tpu.ops import sampling
    from cake_tpu.runtime.speculative import accept_sampled_fn

    v, k, n = 32, 3, 8000
    settings = SamplerSettings(temperature=1.0, top_k=12,
                               repeat_penalty=1.0)
    logits = jax.random.normal(jax.random.PRNGKey(0), (k + 1, v),
                               jnp.float32) * 2.0
    history = jnp.full((settings.repeat_last_n,), -1, jnp.int32)
    hist_slot = jnp.zeros((), jnp.int32)
    eos = jnp.asarray([-1], jnp.int32)
    p0 = np.asarray(jax.nn.softmax(
        sampling.processed_logits(logits[0], history, settings)))
    p1 = np.asarray(jax.nn.softmax(
        sampling.processed_logits(logits[1], history, settings)))

    def run(proposals):
        keys = jax.random.split(jax.random.PRNGKey(7), n)
        toks, count, _, _ = jax.vmap(
            lambda key: accept_sampled_fn(
                logits, proposals, history, hist_slot, eos, key,
                settings=settings)
        )(keys)
        return np.asarray(toks), np.asarray(count)

    for prop0 in (int(np.argmax(p0)),     # likely proposal
                  int(np.argmin(p0)),     # unlikely (often masked: p=0)
                  -1):                    # pad row: no proposal
        props = jnp.asarray([prop0, 5, -1], jnp.int32)
        toks, count = run(props)
        # token 0 marginal == p0 regardless of the proposal
        freq = np.bincount(toks[:, 0], minlength=v) / n
        assert np.abs(freq - p0).sum() < 0.08, (prop0, np.abs(freq - p0).sum())
        assert (count >= 1).all()
        # token 1, conditioned on the round reaching it (row keys are
        # independent, so conditioning on acceptance at row 0 is unbiased)
        sel = toks[count >= 2, 1]
        if sel.size > 500:
            freq1 = np.bincount(sel, minlength=v) / sel.size
            assert np.abs(freq1 - p1).sum() < 0.12


def test_sampled_spec_stream_distribution(params):
    """End-to-end: SpeculativeGenerator with temperature > 0 emits streams
    whose per-position token frequencies match plain decode over many
    seeds (distribution-identical, not sample-path-identical)."""
    settings = SamplerSettings(temperature=1.0, top_k=8, repeat_penalty=1.1)
    prompt = [5, 9, 2, 5, 9, 2, 5, 9]
    trials, steps = 250, 5

    plain = LlamaGenerator(CFG, params, settings=settings)
    spec = SpeculativeGenerator(CFG, params, settings=settings, spec_k=4)

    def streams(gen):
        out = np.zeros((trials, steps), np.int64)
        for t in range(trials):
            gen._key = jax.random.PRNGKey(10_000 + t)
            gen.set_prompt(list(prompt))
            for i in range(steps):
                out[t, i] = gen.next_token(i).id
        return out

    a, b = streams(plain), streams(spec)
    # per-position unigram TV distance (first position is the most
    # constrained; later positions accumulate prefix divergence but remain
    # draws from the same process)
    for i in range(steps):
        va = np.bincount(a[:, i], minlength=CFG.vocab_size) / trials
        vb = np.bincount(b[:, i], minlength=CFG.vocab_size) / trials
        tv = 0.5 * np.abs(va - vb).sum()
        assert tv < 0.22, (i, tv)
    # speculation still lands > 1 token per dispatch on this repeating
    # stream even with sampling in the loop
    assert spec.emitted > spec.dispatches


def test_sampled_spec_accepts_and_matches_greedy_when_peaked(params):
    """Sanity: with temperature > 0 the generator runs, emits in-range
    tokens, and the greedy regression (temperature 0) is untouched."""
    settings = SamplerSettings(temperature=0.7, top_k=4, repeat_penalty=1.1)
    out, g = _spec(params, [5, 9, 2, 5, 9, 2, 5, 9], 10, settings)
    assert len(out) == 10 and all(0 <= t < CFG.vocab_size for t in out)
    assert g.emitted >= g.dispatches


@pytest.mark.parametrize("stages,tp", [(2, 1), (2, 2)])
def test_mesh_speculation_bit_identical_and_fewer_dispatches(params,
                                                             stages, tp):
    """Speculation over the (stage, tp) mesh pipeline: one verification
    program per round across all chips, tokens bit-identical to the plain
    mesh run, tokens-per-dispatch > 1 on a repeating stream."""
    from cake_tpu.runtime.mesh_generator import MeshGenerator
    from cake_tpu.runtime.speculative import MeshSpeculativeGenerator

    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    prompt = [5, 9, 2, 5, 9, 2, 5, 9]
    ref = MeshGenerator(CFG, params, settings=settings, num_stages=stages,
                        tp=tp)
    ref.set_prompt(prompt)
    want = [ref.next_token(i).id for i in range(24)]
    g = MeshSpeculativeGenerator(CFG, params, settings=settings,
                                 num_stages=stages, tp=tp, spec_k=6)
    g.set_prompt(prompt)
    got = [g.next_token(i).id for i in range(24)]
    assert got == want
    assert g.dispatches < g.emitted


def test_mesh_speculation_composes_with_pipelined_prefill(params):
    """--prefill-chunks (GPipe prompt overlap for TTFT) and speculation
    (decode) touch different phases; together they match the plain run."""
    from cake_tpu.runtime.mesh_generator import MeshGenerator
    from cake_tpu.runtime.speculative import MeshSpeculativeGenerator

    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    prompt = [5, 9, 2, 5, 9, 2, 5, 9]
    ref = MeshGenerator(CFG, params, settings=settings, num_stages=2)
    ref.set_prompt(prompt)
    want = [ref.next_token(i).id for i in range(16)]
    g = MeshSpeculativeGenerator(CFG, params, settings=settings,
                                 num_stages=2, spec_k=4, prefill_chunks=2)
    g.set_prompt(prompt)
    assert [g.next_token(i).id for i in range(16)] == want


def test_mesh_speculation_with_int8_kv(params):
    from cake_tpu.runtime.speculative import MeshSpeculativeGenerator

    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    prompt = [5, 9, 2, 5, 9, 2]
    g = MeshSpeculativeGenerator(CFG, params, settings=settings,
                                 num_stages=2, kv_quant="int8", spec_k=4)
    g.set_prompt(prompt)
    got = [g.next_token(i).id for i in range(12)]
    # parity with the single-chip int8-KV speculative run (same numerics:
    # both paths quantize-on-write the same values)
    s = SpeculativeGenerator(CFG, params, settings=settings,
                             kv_quant="int8", spec_k=4)
    s.set_prompt(prompt)
    assert got == [s.next_token(i).id for i in range(12)]


def test_int8_kv_composes_with_speculation(params):
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    prompt = [5, 9, 2, 5, 9, 2]
    want, _ = _spec(params, prompt, 16, settings, spec_k=4)
    got, _ = _spec(params, prompt, 16, settings, spec_k=4, kv_quant="int8")
    # int8 KV changes numerics slightly; the contract here is that the two
    # SPECULATIVE runs each match their own plain-decode twins
    g = LlamaGenerator(CFG, params, settings=settings, kv_quant="int8")
    g.set_prompt(prompt)
    plain_int8 = [g.next_token(i).id for i in range(16)]
    assert got == plain_int8[: len(got)]


def test_rejection_accept_preserves_distribution_top_p():
    """Same statistical contract through the top-p (nucleus) transform —
    the masked-out tail must stay at zero probability through acceptance
    AND residual sampling."""
    import jax.numpy as jnp

    from cake_tpu.ops import sampling
    from cake_tpu.runtime.speculative import accept_sampled_fn

    v, k, n = 24, 2, 6000
    settings = SamplerSettings(temperature=0.8, top_p=0.7,
                               repeat_penalty=1.0)
    logits = jax.random.normal(jax.random.PRNGKey(3), (k + 1, v),
                               jnp.float32) * 2.0
    history = jnp.full((settings.repeat_last_n,), -1, jnp.int32)
    eos = jnp.asarray([-1], jnp.int32)
    p0 = np.asarray(jax.nn.softmax(
        sampling.processed_logits(logits[0], history, settings)))
    prop = int(np.argsort(p0)[-2])  # second-most-likely: real accept/reject mix
    props = jnp.asarray([prop, -1], jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(9), n)
    toks, count, _, _ = jax.vmap(
        lambda key: accept_sampled_fn(
            logits, props, history, jnp.zeros((), jnp.int32), eos, key,
            settings=settings)
    )(keys)
    toks, count = np.asarray(toks), np.asarray(count)
    freq = np.bincount(toks[:, 0], minlength=v) / n
    assert np.abs(freq - p0).sum() < 0.08
    # nucleus-masked tokens never appear
    assert freq[p0 == 0].sum() == 0.0


# -- fused multi-round speculation (device-side propose) ----------------------

def test_device_propose_matches_host():
    """ngram_propose_device must reproduce the host proposer's -1-padded
    array bit-for-bit (longest-n-first, most-recent hit, end clamp)."""
    import jax.numpy as jnp

    from cake_tpu.runtime.speculative import ngram_propose_device

    rng = np.random.default_rng(7)
    for _ in range(40):
        L = int(rng.integers(2, 40))
        ctx_list = rng.integers(0, 6, size=L).tolist()  # small vocab: hits
        k, n_max = 5, 3
        want = np.full((k,), -1, np.int64)
        prop = ngram_propose(ctx_list, n_max, k)
        want[: len(prop)] = prop
        buf = np.zeros((64,), np.int32)
        buf[:L] = ctx_list
        got = np.asarray(
            ngram_propose_device(jnp.asarray(buf), jnp.int32(L),
                                 n_max=n_max, k=k)
        )
        assert got.tolist() == want.tolist(), (ctx_list, got, want)


def test_fused_matches_host_loop_and_syncs_less(params):
    """spec_rounds=8 (fused) must emit the same greedy stream as
    spec_rounds=1 (per-round host loop) with ~rounds/dispatch fewer
    dispatches."""
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    prompt = [5, 9, 2, 5, 9, 2, 5, 9]
    want, host = _spec(params, prompt, 40, settings, spec_k=6,
                       spec_rounds=1)
    got, fused = _spec(params, prompt, 40, settings, spec_k=6,
                       spec_rounds=8)
    assert got == want
    assert fused._spec_block is not None and host._spec_block is None
    # one device sync per 8 rounds: far fewer dispatches for the same
    # emission count
    assert fused.dispatches < host.dispatches
    assert fused.emitted >= host.emitted  # fused block may overshoot n


def test_fused_sampled_stream_invariant_to_rounds_per_dispatch(params):
    """temperature>0: the fused key schedule depends only on the stream
    position (fold_in(fold_in(key, 0x5bec), pos)), never on how rounds are
    grouped into dispatches — so any spec_rounds>1 settings yield the SAME
    sampled stream bit-for-bit. (Host-loop parity can't be bitwise in
    sampled mode: its no-proposal rounds fall back to the single-step
    program whose keys live in the fold_in(key, index) domain;
    test_sampled_spec_stream_distribution covers that equivalence at the
    distribution level.)"""
    settings = SamplerSettings(temperature=0.7, repeat_penalty=1.0,
                               seed=11)
    prompt = [5, 9, 2, 5, 9, 2, 5, 9, 2, 5, 9, 2]
    want, _ = _spec(params, prompt, 24, settings, spec_k=4, spec_rounds=2)
    got, _ = _spec(params, prompt, 24, settings, spec_k=4, spec_rounds=8)
    assert got == want


def test_fused_eos_freezes_trailing_rounds(params):
    """EOS inside a fused block: rounds after the EOS round emit nothing
    and the stream's tokens match the host loop's exactly."""
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    ref = _plain(params, [5, 9, 2, 5, 9, 2, 5, 9], 24, settings)
    eos_cfg = tiny(max_seq_len=128, eos_token_id=ref[5])
    g = SpeculativeGenerator(eos_cfg, params, settings=settings, spec_k=6,
                             spec_rounds=8)
    g.set_prompt([5, 9, 2, 5, 9, 2, 5, 9])
    out = []
    for i in range(24):
        t = g.next_token(i)
        out.append(t.id)
        if t.is_end_of_stream:
            break
    assert out == ref[:6]


def test_fused_device_ctx_tracks_true_context(params):
    """After fused dispatches the device ctx buffer must hold EXACTLY
    prompt + every device-emitted token (ctx[pos] = last): a shifted or
    clobbered buffer silently degrades proposals (r4 review repro)."""
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    g = SpeculativeGenerator(CFG, params, settings=settings, spec_k=6,
                             spec_rounds=4)
    g.set_prompt([5, 9, 2, 5, 9, 2, 5, 9])
    for i in range(20):
        g.next_token(i)
    assert g._ctx is not None and g._ctx_synced_pos == g._pos
    true_ctx = g._prompt_tokens + g._generated + list(g._block_buf)
    got = np.asarray(g._ctx)[: g._pos + 1].tolist()
    assert got == true_ctx


def test_fused_ctx_invalidated_on_new_prompt(params):
    """set_prompt must drop the device ctx: a second stream whose prefill
    position collides with the first stream's synced position must not
    propose from the first stream's tokens."""
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    g = SpeculativeGenerator(CFG, params, settings=settings, spec_k=6,
                             spec_rounds=4)
    g.set_prompt([5, 9, 2, 5, 9, 2, 5, 9])
    for i in range(12):
        g.next_token(i)
    assert g._ctx is not None
    g.set_prompt([7, 1, 3, 7, 1, 3, 7, 1])
    assert g._ctx is None and g._ctx_synced_pos == -1
    out = [g.next_token(i).id for i in range(12)]
    assert out == _plain(params, [7, 1, 3, 7, 1, 3, 7, 1], 12, settings)


def test_spec_replay_teacher_forced_counts_match_host_reference(params):
    """r5: the fused corpus replay (``spec_replay_fn``) must
    accept exactly the run lengths a host-side teacher-forced simulation
    of the same n-gram proposer produces on the same stream — the device
    proposer, the forced accept, and the position bookkeeping all agree;
    and the logits checksum is finite (the verify forward was not DCE'd)."""
    import jax.numpy as jnp
    from functools import partial

    from cake_tpu.ops.kvcache import init_cache
    from cake_tpu.runtime.generator import prefill_fn
    from cake_tpu.runtime.speculative import spec_replay_fn
    from cake_tpu.utils.corpus import corpus_tokens

    k, n_max, rounds, prompt_len = 4, 3, 6, 16
    toks = corpus_tokens(CFG.vocab_size)[: CFG.max_seq_len]

    cache = init_cache(CFG, batch=1, max_seq=CFG.max_seq_len)
    prefill = jax.jit(partial(prefill_fn, config=CFG),
                      donate_argnames=("cache",))
    _, cache = prefill(params, jnp.asarray(toks[None, :prompt_len]), cache,
                       jnp.asarray([prompt_len - 1], jnp.int32))
    replay = jax.jit(
        partial(spec_replay_fn, config=CFG, k=k, n_max=n_max, rounds=rounds),
        donate_argnames=("cache",),
    )
    counts, pos, cache, acc = replay(
        params, jnp.asarray(toks), jnp.int32(prompt_len), cache,
        jnp.float32(0.0),
    )
    counts = np.asarray(counts)

    # host reference: same propose convention (slots 0..p valid), forced
    # accept = leading proposal/corpus matches + 1
    p = prompt_len
    want = []
    for _ in range(rounds):
        props = ngram_propose(toks[: p + 1].tolist(), n_max, k)
        props = props + [-1] * (k - len(props))
        c = 1
        for i in range(k):
            if props[i] == int(toks[p + 1 + i]):
                c += 1
            else:
                break
        want.append(c)
        p += c

    assert counts.tolist() == want
    assert int(pos) == p
    assert 1 <= counts.min() and counts.max() <= k + 1
    assert np.isfinite(float(acc))
