"""N-gram speculative decoding (prompt-lookup): multi-token greedy decode.

A capability beyond the reference (whose decode loop is strictly one token
per step, `master.rs:36-48`): propose the next K tokens by matching the
context's trailing n-gram against its own history (prompt-lookup decoding —
no draft model), then *verify* all K in ONE model dispatch and accept the
longest correct prefix plus one bonus token. Greedy output is bit-identical
to plain decode by construction — the model's own (repeat-penalized) argmax
decides every emitted token; proposals only decide how many land per
dispatch.

Why this is TPU-shaped: single-token decode reads every weight byte from
HBM per token (weights-bound). Verification
feeds K+1 tokens through the same weights in one pass — the MXU loves the
wider matmuls and the weight read amortizes over every accepted token, so
acceptance rate converts directly into tok/s. On repetitive stretches
(code, quotes, structured text) prompt-lookup acceptance is high; worst
case costs one dispatch per token, like plain decode.

Greedy streams (``temperature == 0``) are bit-identical to plain decode.
Sampled streams (``temperature > 0``, the serving default) use REJECTION
SAMPLING (:func:`accept_sampled_fn`): each emitted token's conditional
distribution given the prefix is exactly the plain sampler's categorical —
distribution-preserving, not sample-path-preserving (a fixed seed yields a
different but identically-distributed stream than plain decode).
"""

from __future__ import annotations

import threading
from collections import deque
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.models import llama
from cake_tpu.models.config import LlamaConfig
from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.ops import quant, sampling
from cake_tpu.ops.kvcache import KVCache
from cake_tpu.ops.rope import rope_tables_for
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.runtime.generator import LlamaGenerator
from cake_tpu.runtime.mesh_generator import MeshGenerator

# process-wide acceptance accounting: every speculative path (the host
# per-round loop, the fused chain, the single-stream mixin) reports its
# proposal/acceptance totals here, so one pair of counters and one EMA
# gauge describe speculation quality regardless of which engine ran it.
_ACCEPT_EMA_ALPHA = 0.2
_accept_lock = threading.Lock()
_accept_ema: float | None = None


def record_acceptance(proposed: int, accepted: int) -> None:
    """Fold one dispatch's speculation outcome into the process counters:
    ``spec.proposed`` / ``spec.accepted`` plus the ``spec.accept_rate_ema``
    gauge (EMA over dispatches, not tokens — a smoothed answer to "is
    speculation paying for itself right now"). No-op when nothing was
    proposed, so pure-fallback steps don't drag the EMA toward zero."""
    global _accept_ema
    if proposed <= 0:
        return
    obs_metrics.counter("spec.proposed").inc(int(proposed))
    obs_metrics.counter("spec.accepted").inc(int(accepted))
    rate = min(1.0, max(0.0, accepted / proposed))
    with _accept_lock:
        _accept_ema = (rate if _accept_ema is None else
                       _ACCEPT_EMA_ALPHA * rate
                       + (1.0 - _ACCEPT_EMA_ALPHA) * _accept_ema)
        obs_metrics.gauge("spec.accept_rate_ema").set(_accept_ema)


def ngram_propose(context: list[int], n_max: int, k: int) -> list[int]:
    """Propose up to ``k`` continuation tokens by finding the most recent
    earlier occurrence of the context's trailing n-gram (longest n first)
    and copying what followed it. Returns [] when nothing matches."""
    L = len(context)
    if L < 2 or k < 1:
        return []
    arr = np.asarray(context, np.int64)
    for n in range(min(n_max, L - 1), 0, -1):
        pat = arr[L - n:]
        # candidate starts 0..L-1-n: pattern ends before the final position,
        # so a continuation token always exists inside the context
        windows = np.lib.stride_tricks.sliding_window_view(arr[: L - 1], n)
        hits = np.nonzero((windows == pat).all(axis=1))[0]
        if hits.size:
            j = int(hits[-1])
            return arr[j + n: j + n + k].tolist()
    return []


def _verify_forward(params, tokens, cache: KVCache, pos, cos, sin,
                    config: LlamaConfig):
    """The verification forward shared by :func:`verify_fn` (host loop) and
    :func:`spec_rounds_fn` (fused) — ONE definition so the fused path can
    never drift from the host-loop oracle the bit-identity tests pin."""
    x = llama.embed_tokens(params, tokens, config)
    x, cache = llama.forward_layers(params["layers"], x, cache, cos, sin,
                                    pos, config,
                                    pass_norm=llama.pass_norm(params, config))
    x = llama.head_norm(params, x, config)
    logits = quant.dense(x[0], params["lm_head"]).astype(jnp.float32)
    return logits, cache


def verify_fn(params, tokens, cache: KVCache, pos, config: LlamaConfig):
    """Forward ``tokens [1, T]`` from position ``pos`` returning logits at
    EVERY position (``[T, vocab] f32``) — the speculation-verification pass.
    KV for all T slots is written; slots past the accepted frontier hold
    rejected garbage that later steps overwrite before it becomes
    attendable (the same invariant as bucketed-prefill padding)."""
    cos, sin = rope_tables_for(config, cache.max_seq)
    return _verify_forward(params, tokens, cache, pos, cos, sin, config)


def accept_fn(
    logits,  # [T, vocab] f32 (T = K + 1)
    proposals,  # [K] int32, -1-padded
    history,
    hist_slot,
    eos_ids,  # [E] int32 (-1-padded when fewer)
    settings: SamplerSettings,
):
    """Greedy accept scan. Row ``i``'s (repeat-penalized) argmax ``g_i`` is
    emitted while the stream is alive; the stream stays alive while each
    ``g_i`` equals its proposal and is not EOS. Returns
    ``(tokens [T], count, history, hist_slot)`` — the first ``count``
    tokens are exactly what plain greedy decode would have produced, with
    history advanced by exactly those tokens."""
    k = proposals.shape[0]
    dummy_key = jax.random.PRNGKey(0)  # unused at temperature 0

    def body(carry, i):
        alive, count, history, hist_slot = carry
        g = sampling.sample_token(logits[i], dummy_key, history, settings)
        nh, ns = sampling.push_history(history, hist_slot, g)
        history = jnp.where(alive, nh, history)
        hist_slot = jnp.where(alive, ns, hist_slot)
        count = count + alive.astype(jnp.int32)
        is_eos = (g == eos_ids).any()
        matched = jnp.where(i < k, g == proposals[jnp.minimum(i, k - 1)],
                            False)
        alive = alive & matched & ~is_eos
        return (alive, count, history, hist_slot), g

    (_, count, history, hist_slot), toks = jax.lax.scan(
        body,
        (jnp.asarray(True), jnp.int32(0), history, hist_slot),
        jnp.arange(logits.shape[0], dtype=jnp.int32),
    )
    return toks, count, history, hist_slot


def accept_sampled_fn(
    logits,  # [T, vocab] f32 (T = K + 1)
    proposals,  # [K] int32, -1-padded
    history,
    hist_slot,
    eos_ids,  # [E] int32 (-1-padded when fewer)
    round_key,  # PRNG key for this verification round
    settings: SamplerSettings,
):
    """Rejection-sampling accept scan for ``temperature > 0``.

    The prompt-lookup draft is DETERMINISTIC (q is a point mass on the
    proposal), so the standard speculative-sampling rule (Leviathan et al.;
    Chen et al.) reduces cleanly: accept proposal ``x`` with probability
    ``p(x)`` (p = the plain sampler's penalized/temperature-scaled/top-k/
    top-p categorical, via ``sampling.processed_logits``); on rejection,
    sample the replacement from the residual ``norm(max(p - q, 0))`` — p
    with the proposal's mass zeroed. If all K proposals are accepted, the
    bonus row samples from its p directly. Per emitted token the
    conditional distribution given the prefix is exactly p: acceptance
    contributes ``p(x)·1[y=x]`` and rejection ``(1-p(x))·p(y)/(1-p(x))``
    for ``y != x``.

    Returns ``(tokens [T], count, history, hist_slot)`` like
    :func:`accept_fn`; the stream stops at the first rejection, EOS, or the
    bonus token. A -1 pad row never accepts (it behaves as "no proposal":
    sample from full p and stop)."""
    k = proposals.shape[0]
    keys = jax.random.split(round_key, logits.shape[0])

    def body(carry, i):
        alive, count, history, hist_slot = carry
        lg = sampling.processed_logits(logits[i], history, settings)
        ku, kr = jax.random.split(keys[i])
        is_bonus = i >= k
        prop = proposals[jnp.minimum(i, k - 1)]
        p_prop = jax.nn.softmax(lg)[jnp.maximum(prop, 0)]
        accept = (~is_bonus) & (prop >= 0) & (
            jax.random.uniform(ku) < p_prop
        )
        # residual: p with the rejected proposal removed, renormalized
        lg_res = jnp.where(
            jnp.arange(lg.shape[0], dtype=jnp.int32) == prop,
            jnp.float32(-1e30), lg,
        )
        g_rej = jax.random.categorical(kr, lg_res).astype(jnp.int32)
        g_bonus = jax.random.categorical(kr, lg).astype(jnp.int32)
        g = jnp.where(accept, prop, jnp.where(is_bonus, g_bonus, g_rej))
        nh, ns = sampling.push_history(history, hist_slot, g)
        history = jnp.where(alive, nh, history)
        hist_slot = jnp.where(alive, ns, hist_slot)
        count = count + alive.astype(jnp.int32)
        is_eos = (g == eos_ids).any()
        # a rejection/bonus row emits its sample and ends the round
        alive = alive & accept & ~is_eos
        return (alive, count, history, hist_slot), g

    (_, count, history, hist_slot), toks = jax.lax.scan(
        body,
        (jnp.asarray(True), jnp.int32(0), history, hist_slot),
        jnp.arange(logits.shape[0], dtype=jnp.int32),
    )
    return toks, count, history, hist_slot


def accept_fn_rows(logits, proposals, history, hist_slot, eos_ids,
                   settings: SamplerSettings):
    """Batched greedy accept: vmap of :func:`accept_fn` over serving rows.
    ``logits [B, T, V]``, ``proposals [B, K]`` (-1-padded), per-row
    history/hist_slot. Returns ``(tokens [B, T], count [B], history,
    hist_slot)``."""
    return jax.vmap(
        lambda l, p, h, s: accept_fn(l, p, h, s, eos_ids, settings)
    )(logits, proposals, history, hist_slot)


def accept_sampled_fn_rows(logits, proposals, history, hist_slot, eos_ids,
                           round_keys, settings: SamplerSettings):
    """Batched rejection-sampling accept: vmap of
    :func:`accept_sampled_fn` over serving rows with per-row round keys
    (``[B, 2] uint32``)."""
    return jax.vmap(
        lambda l, p, h, s, k: accept_sampled_fn(l, p, h, s, eos_ids, k,
                                                settings)
    )(logits, proposals, history, hist_slot, round_keys)


def ngram_propose_device(ctx, pos, *, n_max: int, k: int):
    """Device twin of :func:`ngram_propose`: ``ctx [S] int32`` holds the
    stream's tokens at slots ``0..pos-1`` (later slots are garbage — every
    read below is masked by ``pos``), ``pos`` is a traced int32. Returns
    ``[k] int32`` proposals, -1-padded, matching the host version's
    ``padded`` array bit-for-bit: same longest-n-first / most-recent-hit
    tie-breaking, same end-of-context clamp.

    Vectorization: for each static shift ``d``, ``shifted_d[j] = ctx[j+d]``
    (a static slice + pad), so "window at j matches the trailing n-gram"
    is an AND of n elementwise compares — no gather over windows. n_max is
    tiny (3 by default): the whole propose costs a few S-length VPU ops,
    which is noise next to the verification forward it precedes."""
    S = ctx.shape[0]
    iota = jnp.arange(S, dtype=jnp.int32)
    shifted = [
        jnp.concatenate(
            [ctx[d:], jnp.full((d,), -2, ctx.dtype)]) if d else ctx
        for d in range(n_max)
    ]
    best_j = jnp.int32(-1)
    best_n = jnp.int32(0)
    # ascending n: a longer match overwrites a shorter one, reproducing the
    # host's longest-n-first preference
    for n in range(1, n_max + 1):
        match = iota <= pos - 1 - n  # window ends before the final token
        for d in range(n):
            pat_d = ctx[jnp.maximum(pos - n + d, 0)]
            match = match & (shifted[d] == pat_d)
        j_n = jnp.max(jnp.where(match, iota, -1))
        found = (j_n >= 0) & (pos >= n + 1)
        best_j = jnp.where(found, j_n, best_j)
        best_n = jnp.where(found, jnp.int32(n), best_n)
    start = best_j + best_n
    idx = start + jnp.arange(k, dtype=jnp.int32)
    props = jnp.take(ctx, idx, mode="clip")
    return jnp.where((best_j >= 0) & (idx < pos), props, jnp.int32(-1))


def spec_rounds_fn(
    params,
    last_tok,  # [] int32 — the token feeding position pos
    ctx,  # [S] int32 stream context (slots 0..pos valid, ctx[pos]=last)
    pos,  # [] int32
    cache: KVCache,
    history,
    hist_slot,
    base_key,  # PRNG key (ignored under greedy)
    config: LlamaConfig,
    settings: SamplerSettings,
    eos_ids,  # [E] int32
    k: int,
    n_max: int,
    rounds: int,
):
    """``rounds`` propose→verify→accept rounds fused into ONE program.

    The host loop in :class:`SpeculativeMixin` pays a full host↔device
    round trip per round (the accepted-count sync; what it costs beside
    the forward: not measured on the chip tool). Here the n-gram propose
    runs on device
    (:func:`ngram_propose_device`), so consecutive rounds chain inside one
    ``lax.scan`` and the host syncs once per ``rounds``.

    Per round: propose from ``ctx``, forward ``[last, proposals] [1, K+1]``
    from ``pos`` (same KV-garbage-overwrite invariant as :func:`verify_fn`),
    accept via the greedy or rejection-sampling scan, append the emitted
    tokens to ``ctx``, advance ``pos``. A round that hits EOS freezes the
    carry (``done``): later rounds still compute (scan bodies always run)
    but write nothing. Greedy emissions are bit-identical to the host loop
    and therefore to plain decode; sampled rounds derive the same
    ``fold_in(fold_in(key, 0x5BEC), pos)`` round keys as the host loop.

    Returns ``(tokens [rounds, K+1], counts [rounds], last, ctx, pos,
    cache, history, hist_slot)`` — row ``r``'s first ``counts[r]`` tokens
    are that round's emissions. The caller must guarantee
    ``pos + rounds*(K+1) <= max_seq`` (the scan writes K+1 KV slots per
    round unconditionally)."""
    cos, sin = rope_tables_for(config, cache.max_seq)
    greedy = settings.greedy

    def round_body(carry, _):
        last, ctx, pos, cache, history, hist_slot, done = carry
        props = ngram_propose_device(ctx, pos + 1, n_max=n_max, k=k)
        fed = jnp.concatenate([last[None], jnp.maximum(props, 0)])[None, :]
        logits, cache = _verify_forward(params, fed, cache, pos, cos, sin,
                                        config)
        if greedy:
            toks, count, h2, s2 = accept_fn(
                logits, props, history, hist_slot, eos_ids, settings)
        else:
            round_key = jax.random.fold_in(
                jax.random.fold_in(base_key, 0x5BEC), pos)
            toks, count, h2, s2 = accept_sampled_fn(
                logits, props, history, hist_slot, eos_ids, round_key,
                settings)
        count = jnp.where(done, 0, count)
        history = jax.tree.map(
            lambda new, old: jnp.where(done, old, new), h2, history)
        hist_slot = jnp.where(done, hist_slot, s2)
        # append emissions at pos+1..pos+T: ctx[pos] holds the token that
        # FED this round (the context convention is "slots 0..pos valid,
        # ctx[pos] = last"), so g_0 — the token at stream index pos+1 —
        # lands at pos+1. Junk rows beyond count (or a frozen round's
        # whole row) land entirely in the invalid region (> new pos) and
        # every later read is masked. The caller's headroom contract
        # (pos + rounds*(K+1) < S) rules out start-index clamping.
        ctx = jax.lax.dynamic_update_slice(ctx, toks, (pos + 1,))
        new_last = toks[jnp.maximum(count - 1, 0)]
        last = jnp.where(done, last, new_last)
        emitted_eos = (
            (toks[:, None] == eos_ids[None, :]).any(-1)
            & (jnp.arange(toks.shape[0]) < count)
        ).any()
        pos = pos + count
        done = done | emitted_eos
        return (last, ctx, pos, cache, history, hist_slot, done), (
            toks, count)

    (last, ctx, pos, cache, history, hist_slot, _), (tokens, counts) = (
        jax.lax.scan(
            round_body,
            (last_tok, ctx, pos, cache, history, hist_slot,
             jnp.asarray(False)),
            None,
            length=rounds,
        )
    )
    return tokens, counts, last, ctx, pos, cache, history, hist_slot


def spec_replay_fn(
    params,
    corpus,  # [S] int32 — the REAL token stream being replayed
    pos,  # [] int32: corpus[0..pos-1] in the KV cache; corpus[pos] is the
    #     last "emitted" token, NOT yet cached — this round's fed[0]
    #     writes its KV at `pos` (callers prefill corpus[:P], pass pos=P)
    cache: KVCache,
    acc,  # [] f32 logits checksum carry (see below)
    config: LlamaConfig,
    k: int,
    n_max: int,
    rounds: int,
):
    """``rounds`` TEACHER-FORCED propose→verify rounds fused into one
    program — the honest companion to :func:`spec_rounds_fn`'s synthetic
    self-repeating stream (r4 verdict: "no measured row on realistic text
    exists").

    The decoded stream is forced to the corpus: each round proposes with
    the same device n-gram lookup production uses
    (:func:`ngram_propose_device` over the replayed prefix), runs the REAL
    ``[1, K+1]`` verification forward (same cost as live speculation), and
    accepts the run where proposals match the corpus's actual next tokens
    — so tokens/dispatch and the acceptance rate measure the proposer
    against real text statistics while tok/s includes the true verify
    FLOPs/bytes. What it does not measure: the model's own agreement with
    its proposals (that needs trained weights; with random bench weights a
    live run degenerates to noise — the forced replay is the honest
    alternative, and is labeled as such in the bench row).

    ``acc`` accumulates a logits checksum; without it the teacher-forced
    accept never reads the logits and XLA would dead-code-eliminate the
    lm_head (and with it the bench's verify cost). Caller guarantees
    ``pos + rounds*(k+1) < min(len(corpus), max_seq)``.

    Returns ``(counts [rounds], pos, cache, acc)``.
    """
    cos, sin = rope_tables_for(config, cache.max_seq)

    def round_body(carry, _):
        pos, cache, acc = carry
        props = ngram_propose_device(corpus, pos + 1, n_max=n_max, k=k)
        last = corpus[pos]
        fed = jnp.concatenate([last[None], jnp.maximum(props, 0)])[None, :]
        logits, cache = _verify_forward(params, fed, cache, pos, cos, sin,
                                        config)
        # teacher-forced accept: the "model output" at slot i is the
        # corpus's true next token; the run survives while proposals match
        # (-1 pads never match) — same run-length semantics as accept_fn.
        truth = jax.lax.dynamic_slice(corpus, (pos + 1,), (k,))
        lead = jnp.cumprod((props == truth).astype(jnp.int32))
        count = 1 + lead.sum()
        acc = acc + logits.sum()  # forces the lm_head to materialize
        return (pos + count, cache, acc), count

    (pos, cache, acc), counts = jax.lax.scan(
        round_body, (pos, cache, acc), None, length=rounds,
    )
    return counts, pos, cache, acc


class SpeculativeMixin:
    """The speculation loop, shared by the single-chip and mesh
    generators. Subclasses build ``self._verify`` (a compiled
    ``(params, tokens [1, T], cache, pos) -> (logits [T, vocab], cache)``
    program) in their constructors and inherit a ``GeneratorBase``-family
    ``next_token`` used for the prefill step and the no-proposal
    fallback."""

    def _verify_dispatch(self, fed: np.ndarray, pos: int) -> jax.Array:
        logits, self.cache = self._verify(
            self.params, jnp.asarray(fed), self.cache, jnp.int32(pos)
        )
        return logits

    def _spec_init(self, spec_k: int, spec_ngram: int,
                   spec_rounds: int = 1) -> None:
        self.spec_k = int(spec_k)
        self.spec_ngram = int(spec_ngram)
        self.spec_rounds = int(spec_rounds)
        if self.spec_k < 1:
            raise ValueError("spec_k must be >= 1")
        if self.spec_rounds < 1:
            raise ValueError("spec_rounds must be >= 1")
        held = sorted(set(self.config.cache_plan) - {"rows"})
        if held:
            # the serving engine's rule (runtime/batch_generator.py): a
            # rejected proposal cannot be undone where it is no row
            raise ValueError(
                "speculation is not wired for this model: a rejected "
                "proposal has already advanced or overwritten what its "
                f"cache holds beside rows ({', '.join(held)}) and nothing "
                "restores it; run it with no speculation")
        eos = sorted(self._eos_ids) or [-1]
        self._eos_arr = jnp.asarray(eos, jnp.int32)
        # greedy: exact match accept (bit-identical streams); sampled:
        # rejection sampling (distribution-identical streams)
        accept = accept_fn if self.settings.greedy else accept_sampled_fn
        self._accept = jax.jit(partial(accept, settings=self.settings))
        # fused multi-round program (subclasses that support it assign
        # _spec_block after calling this); the device-side ctx buffer is
        # rebuilt lazily whenever a non-fused path advanced the stream
        self._spec_block = None
        self._ctx = None
        self._ctx_synced_pos = -1
        self.dispatches = 0
        self.rounds = 0
        self.emitted = 0

    def _on_new_prompt(self) -> None:
        """A fresh prompt invalidates the device-side ctx buffer: without
        this, a new stream whose prefill position happens to equal the old
        stream's last synced position would silently propose from the OLD
        stream's tokens (correctness survives — verification gates every
        token — but acceptance collapses)."""
        super()._on_new_prompt()
        self._ctx = None
        self._ctx_synced_pos = -1

    def _dispatch_fused(self):
        """One fused multi-round dispatch (:func:`spec_rounds_fn`): sync
        with the device once, harvest every round's emissions."""
        if self._ctx_synced_pos != self._pos or self._ctx is None:
            context = self._prompt_tokens + self._generated
            buf = np.zeros((self.max_seq,), np.int32)
            buf[: len(context)] = context
            self._ctx = jnp.asarray(buf)
        tokens, counts, _, ctx, _, cache, history, hist_slot = (
            self._spec_block(
                self.params, jnp.int32(self._last_token), self._ctx,
                jnp.int32(self._pos), self.cache, self._history,
                self._hist_slot, self._key,
            )
        )
        self.cache = cache
        self._ctx = ctx
        self._history, self._hist_slot = history, hist_slot
        # one combined fetch: two np.asarray calls would pay a second
        # host sync per dispatch
        counts_np, toks_np = jax.device_get((counts, tokens))
        emitted: list[int] = []
        for r in range(counts_np.shape[0]):
            emitted.extend(toks_np[r, : int(counts_np[r])].tolist())
        self.dispatches += 1
        self.rounds += int((counts_np > 0).sum())
        self.emitted += len(emitted)
        # device proposer: per-round proposal lengths stay on device, so
        # proposed is the K-per-live-round upper bound (see batch chain)
        record_acceptance(
            self.spec_k * int((counts_np > 0).sum()),
            int(np.maximum(counts_np - 1, 0).sum()))
        self._pos += len(emitted)
        self._ctx_synced_pos = self._pos
        self._block_buf = deque(emitted[1:])
        return self._finish_token(emitted[0])

    def next_token(self, index: int):
        if index == 0 or self._block_buf:
            tok = super().next_token(index)
            if index == 0:
                self.dispatches += 1
                self.rounds += 1
                self.emitted += 1
            return tok
        self._check_capacity()
        if (
            self._spec_block is not None
            and self._pos + self.spec_rounds * (self.spec_k + 1)
            < self.max_seq
        ):
            return self._dispatch_fused()
        context = self._prompt_tokens + self._generated
        proposal = ngram_propose(context, self.spec_ngram, self.spec_k)
        if not proposal or self._pos + self.spec_k + 1 > self.max_seq:
            self.dispatches += 1
            self.rounds += 1
            self.emitted += 1
            return super().next_token(index)

        fed = np.full((1, self.spec_k + 1), 0, np.int32)
        fed[0, 0] = self._last_token
        fed[0, 1: 1 + len(proposal)] = proposal
        padded = np.full((self.spec_k,), -1, np.int32)
        padded[: len(proposal)] = proposal
        logits = self._verify_dispatch(fed, self._pos)
        if self.settings.greedy:
            toks, count, self._history, self._hist_slot = self._accept(
                logits, jnp.asarray(padded), self._history, self._hist_slot,
                self._eos_arr,
            )
        else:
            # One fresh key per round: _pos strictly increases between
            # dispatches, so round keys never repeat within a stream. The
            # round key lives in its own fold domain (0x5bec) — the plain
            # single-step fallback samples with fold_in(self._key, index)
            # (generator.py), and reusing that exact derivation here would
            # correlate a round's draws with a fallback step's.
            round_key = jax.random.fold_in(
                jax.random.fold_in(self._key, 0x5BEC), self._pos
            )
            toks, count, self._history, self._hist_slot = self._accept(
                logits, jnp.asarray(padded), self._history, self._hist_slot,
                self._eos_arr, round_key,
            )
        n = int(count)
        emitted = np.asarray(toks[:n]).tolist()
        self.dispatches += 1
        self.rounds += 1
        self.emitted += n
        record_acceptance(len(proposal), n - 1)
        # cache holds KV for the fed tokens at pos..pos+K; the accepted
        # region pos..pos+n-1 is [last, g_0..g_{n-2}] — correct by the
        # match condition. The next round feeds g_{n-1} at pos+n.
        self._pos += n
        self._block_buf = deque(emitted[1:])
        return self._finish_token(emitted[0])


class SpeculativeGenerator(SpeculativeMixin, LlamaGenerator):
    """Single-stream generator with prompt-lookup speculation.

    ``spec_k`` tokens are proposed per round (n-grams up to ``spec_ngram``
    long); each round is one verification dispatch emitting 1..K+1 tokens.
    When no proposal exists (or the window tail is near), falls back to the
    plain single-step program. ``dispatches``/``emitted`` counters expose
    the speedup structure (tokens-per-dispatch > 1 is the win).

    Greedy streams are bit-identical to plain decode; ``temperature > 0``
    streams are distribution-identical via rejection sampling
    (:func:`accept_sampled_fn`), so speculation composes with the serving
    default sampler."""

    def __init__(
        self,
        config: LlamaConfig,
        params,
        tokenizer=None,
        settings: SamplerSettings | None = None,
        max_seq: int | None = None,
        kv_quant: str | None = None,
        spec_k: int = 8,
        spec_ngram: int = 3,
        spec_rounds: int = 8,
    ):
        settings = settings or SamplerSettings(temperature=0.0)
        super().__init__(config, params, tokenizer=tokenizer,
                         settings=settings, max_seq=max_seq,
                         kv_quant=kv_quant, block_size=1)
        self._spec_init(spec_k, spec_ngram, spec_rounds)
        self._verify = jax.jit(partial(verify_fn, config=config),
                               donate_argnames=("cache",))
        # fused multi-round program: propose on device, sync once per
        # spec_rounds rounds (spec_rounds=1 keeps the per-round host loop,
        # which is also the reference oracle in tests)
        if self.spec_rounds > 1:
            self._spec_block = jax.jit(
                partial(
                    spec_rounds_fn,
                    config=config,
                    settings=self.settings,
                    eos_ids=self._eos_arr,
                    k=self.spec_k,
                    n_max=self.spec_ngram,
                    rounds=self.spec_rounds,
                ),
                donate_argnames=("ctx", "cache"),
            )


class MeshSpeculativeGenerator(SpeculativeMixin, MeshGenerator):
    """Prompt-lookup speculation over the single-program mesh pipeline:
    the verification pass runs as ONE compiled program across the
    (stage, tp) mesh (``parallel.pipeline.build_sharded_verify``), so
    multi-chip decode also lands 1..K+1 tokens per dispatch. Same
    exactness contract as the single-chip variant: greedy bit-identical,
    sampled distribution-identical (rejection sampling)."""

    def __init__(
        self,
        config: LlamaConfig,
        params,
        plan=None,
        tokenizer=None,
        settings: SamplerSettings | None = None,
        max_seq: int | None = None,
        num_stages: int = 1,
        tp: int = 1,
        sp: int = 1,
        ep: int = 1,
        devices=None,
        kv_quant: str | None = None,
        spec_k: int = 8,
        spec_ngram: int = 3,
        prefill_chunks: int = 1,
    ):
        from cake_tpu.parallel.pipeline import build_sharded_verify

        settings = settings or SamplerSettings(temperature=0.0)
        # sp > 1 (r5): the verification pass runs chunk-replicated over
        # the sequence-sharded cache (build_sharded_verify's sp path), so
        # single-stream speculation composes with the long-context plane.
        super().__init__(config, params, plan=plan, tokenizer=tokenizer,
                         settings=settings, max_seq=max_seq,
                         num_stages=num_stages, tp=tp, sp=sp, ep=ep,
                         devices=devices, block_size=1, kv_quant=kv_quant,
                         prefill_chunks=prefill_chunks)
        self._spec_init(spec_k, spec_ngram)
        self._verify = build_sharded_verify(
            config, self.plan, params_like=self.params, kv_quant=kv_quant
        )
