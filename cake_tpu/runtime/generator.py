"""Autoregressive generation loop (single-host, all-local path).

Equivalent of the reference's `Generator` trait + `LLama::next_token`
(`cake-core/src/model/mod.rs:21-29,46-58`, `model/llama.rs:223-272`):
``next_token(index) -> Token{id, text, is_end_of_stream}``, ``last()`` flushes
the detokenizer tail, ``generated_tokens()`` counts. The KV-cache context
windowing matches llama.rs:228-232 — the full prompt is fed once (prefill),
every later step feeds exactly one token.

TPU-first design:

- **Two compiled programs**: ``prefill`` (prompt at bucketed lengths) and
  ``decode_step``. The decode step fuses the *entire* per-token pipeline —
  embed -> all layers -> ln_f -> lm_head -> repeat penalty -> sampling — into
  one XLA program with the cache donated, so each token costs one dispatch
  and zero host round-trips except the sampled id (the reference downloads
  full logits to the CPU sampler every token, llama.rs:241-265).
- **Prompt bucketing**: prompts are right-padded to a power-of-two bucket so
  prefill compiles O(log max_seq) times, not per prompt length. Padded
  positions write garbage K/V beyond the prompt, which is invisible under the
  causal mask and overwritten by subsequent decode steps before it ever
  enters the frontier.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial

import jax
import jax.numpy as jnp

from cake_tpu.models.config import LlamaConfig
from cake_tpu.models import llama
from cake_tpu.obs import flight as obs_flight
from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.obs.trace import span
from cake_tpu.ops import quant
from cake_tpu.ops.kvcache import KVCache, init_cache
from cake_tpu.ops.rope import rope_tables_for
from cake_tpu.ops import sampling
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.utils.token_stream import TokenOutputStream


@dataclasses.dataclass
class Token:
    """Mirror of the reference ``Token`` (model/mod.rs:46-52), plus the
    serving plane's optional per-token top-k logprob report: a list of
    ``(token_id, logprob)`` pairs over the raw model distribution, None
    when the engine was not built with ``logprobs``."""

    id: int
    text: str | None
    is_end_of_stream: bool
    logprobs: list[tuple[int, float]] | None = None


def encode_prompt(prompt, tokenizer, config, max_seq: int) -> list[int]:
    """THE prompt-intake rules, shared by every serving surface (the
    single-stream generators, the batch engine, and the HTTP plane's
    adapters): strings tokenize with a BOS prepend, id lists pass through
    as-is; reject empty prompts, prompts that fill the window, and
    out-of-range ids (which would clamp in the embed gather and silently
    corrupt just this stream)."""
    if isinstance(prompt, str):
        if tokenizer is None:
            raise ValueError("string prompt requires a tokenizer")
        enc = tokenizer.encode(prompt)
        ids = list(getattr(enc, "ids", enc))
        if config.bos_token_id is not None and (
            not ids or ids[0] != config.bos_token_id
        ):
            ids = [config.bos_token_id] + ids
    else:
        ids = list(prompt)
    if not ids:
        raise ValueError("empty prompt")
    if len(ids) >= max_seq:
        raise ValueError(f"prompt length {len(ids)} >= max_seq {max_seq}")
    bad = [t for t in ids if not (0 <= t < config.vocab_size)]
    if bad:
        raise ValueError(
            f"prompt token ids out of range [0, {config.vocab_size}): "
            f"{bad[:5]}"
        )
    return ids


def _bucket(n: int, max_seq: int, floor: int = 16) -> int:
    b = floor
    while b < n:
        b *= 2
    return min(b, max_seq)


def _lm_head(params, x_last: jax.Array, config: LlamaConfig) -> jax.Array:
    x_last = llama.head_norm(params, x_last, config)
    return quant.dense(x_last, params["lm_head"]).astype(jnp.float32)


def prefill_fn(params, tokens, cache: KVCache, last_index, config: LlamaConfig):
    """Prompt pass. ``tokens [B, T_pad]``; logits read at ``last_index``
    (the last *real* prompt position). Returns (logits [B, vocab], cache)."""
    cos, sin = rope_tables_for(config, cache.max_seq)
    x = llama.embed_tokens(params, tokens, config)
    valid, expert_valid = llama.true_rows(config, tokens.shape, last_index)
    if valid is not None:
        # a recurrent state, a tail or a ring has no frontier that hides
        # what is not the prompt's: the bucket's padding must not touch it
        # (``valid``), and what the last prompt left goes
        cache = dataclasses.replace(cache, **{
            name: jnp.zeros_like(getattr(cache, name))
            for name in ("state", "conv") if getattr(cache, name) is not None})
    x, cache = llama.forward_layers(
        params["layers"], x, cache, cos, sin, 0, config,
        pass_norm=llama.pass_norm(params, config), valid=valid,
        expert_valid=expert_valid)
    # (rank-agnostic: a wide residual stream is [B, T, hc_mult, hidden])
    x_last = jnp.take_along_axis(
        x, last_index.reshape((-1,) + (1,) * (x.ndim - 1)).astype(jnp.int32),
        axis=1)[:, 0, :]
    return _lm_head(params, x_last, config), cache


def decode_step_fn(
    params,
    token,  # [B] int32 — previous sampled token
    cache: KVCache,
    pos,  # scalar int32
    key,
    history,  # [repeat_last_n] int32
    hist_slot,
    config: LlamaConfig,
    settings: SamplerSettings,
    mask_table=None,  # [M, ceil(V/8)] uint8 packed constraint masks
    mask_row=None,  # scalar int32 — current DFA-state row
):
    """One fused decode step: forward one token + sample the next. The
    optional trailing mask operands are the constrained-decoding path
    (constrain/): a gather from the device-resident packed bitmask table
    + one jnp.where inside the same compiled program. Calls without them
    trace the exact pre-constraint program — unconstrained streams stay
    bit-identical."""
    cos, sin = rope_tables_for(config, cache.max_seq)
    x = llama.embed_tokens(params, token[:, None], config)
    x, cache = llama.forward_layers(
        params["layers"], x, cache, cos, sin, pos, config,
        pass_norm=llama.pass_norm(params, config))
    logits = _lm_head(params, x[:, -1, :], config)
    mask = None
    if mask_table is not None:
        mask = sampling.unpack_mask_bits(mask_table[mask_row],
                                         config.vocab_size)
    next_tok = sampling.sample_token(logits[0], key, history, settings,
                                     mask=mask)
    history, hist_slot = sampling.push_history(history, hist_slot, next_tok)
    return next_tok, cache, history, hist_slot


def decode_scan_fn(
    params,
    token,  # [1] int32 — previous sampled token
    cache: KVCache,
    pos,  # scalar int32 — position of `token`'s KV slot
    key0,  # BASE stream key (unfolded); see key schedule note below
    history,
    hist_slot,
    config: LlamaConfig,
    settings: SamplerSettings,
    steps: int,
    index0=0,  # absolute token index of the first emitted token
):
    """``steps`` fused decode steps in ONE dispatch (lax.scan over
    decode_step_fn). Sampling is already on-device, so the token feedback
    loop needs no host round-trip; emitting K tokens per dispatch amortizes
    the per-dispatch host sync (its size beside the chip: not measured on
    the chip tool).

    Key schedule: step ``i`` samples with ``fold_in(key0, index0 + i)`` —
    the SAME schedule as the single-step path (``fold_in(base_key, index)``),
    so a given seed produces an identical stochastic stream at every block
    size. Returns (tokens [steps], cache, history, hist_slot)."""

    def body(carry, i):
        token, cache, pos, history, hist_slot = carry
        tok, cache, history, hist_slot = decode_step_fn(
            params, token, cache, pos,
            jax.random.fold_in(key0, jnp.asarray(index0, jnp.int32) + i),
            history, hist_slot, config=config, settings=settings,
        )
        return (tok.reshape(1), cache, pos + 1, history, hist_slot), tok

    (_, cache, _, history, hist_slot), toks = jax.lax.scan(
        body,
        (token, cache, jnp.asarray(pos, jnp.int32), history, hist_slot),
        jnp.arange(steps, dtype=jnp.int32),
    )
    return toks, cache, history, hist_slot


class GeneratorBase:
    """Shared Generator-trait state machine (model/mod.rs:21-29,46-58):
    prompt validation + per-stream reset, repeat-penalty history seeding,
    token bookkeeping, EOS detection, streaming detok, counters. Subclasses
    implement the model execution (`next_token`)."""

    def __init__(
        self,
        config: LlamaConfig,
        tokenizer=None,
        settings: SamplerSettings | None = None,
        max_seq: int | None = None,
    ):
        self.config = config
        self.settings = settings or SamplerSettings()
        self.max_seq = max_seq or config.max_seq_len
        self.tokenizer = tokenizer
        self.stream = TokenOutputStream(tokenizer) if tokenizer is not None else None
        self._key = jax.random.PRNGKey(self.settings.seed)
        self._history, self._hist_slot = sampling.init_history(
            self.settings.repeat_last_n
        )
        self._prompt_tokens: list[int] = []
        self._generated: list[int] = []
        self._pos = 0
        self._last_token: int | None = None
        self._eos_ids = set(config.eos_ids())
        sampling.validate_logit_bias(self.settings, config.vocab_size)
        # Constrained decoding (cake_tpu/constrain): a Guide set via
        # set_guide() masks every sampling step. Subclasses that can
        # apply the mask flip supports_guide; the base refuses, so a
        # serve adapter can never silently ignore a constraint.
        self.guide = None
        self.guide_dead = False  # DFA dead end hit (end_reason constraint)
        # fused block-decode buffer (subclasses with block_size > 1);
        # deque: the per-token pop is O(1), not the O(n) list.pop(0)
        self.block_size = 1
        self._block_buf: deque[int] = deque()

    # -- prompt handling ----------------------------------------------------
    def set_prompt(self, prompt: str | list[int]) -> None:
        ids = encode_prompt(prompt, self.tokenizer, self.config,
                            self.max_seq)
        self._prompt_tokens = ids
        # Reset all per-stream state so a generator can serve a new prompt
        # (the stale KV beyond the new prompt is invisible under the causal
        # mask and overwritten as decode advances, so the cache itself does
        # not need zeroing).
        self._generated.clear()
        self._pos = 0
        self._last_token = None
        if self.stream is not None:
            self.stream.clear()
        # Seed the repeat-penalty window with the prompt tail (llama.rs:250-259
        # penalizes over all generated context; we include the prompt tail) —
        # one vectorized write, not a per-token device loop.
        self._history, self._hist_slot = sampling.init_history(
            self.settings.repeat_last_n
        )
        tail = ids[-self.settings.repeat_last_n :]
        if tail:
            idx = jnp.arange(len(tail), dtype=jnp.int32)
            self._history = self._history.at[idx].set(
                jnp.asarray(tail, jnp.int32)
            )
            self._hist_slot = jnp.int32(len(tail))
        self._block_buf = deque()
        self.guide = None  # constraints are per-request: re-set_guide
        self.guide_dead = False
        self._on_new_prompt()

    def _on_new_prompt(self) -> None:
        """Hook for subclasses (e.g. reset remote runner caches)."""

    # -- constrained decoding -----------------------------------------------
    supports_guide = False

    @property
    def eos_ids(self) -> frozenset:
        """Public EOS-id surface (the serve facade contract)."""
        return frozenset(self._eos_ids)

    def set_guide(self, guide) -> None:
        """Attach (or clear, with None) a constrain.Guide for the CURRENT
        prompt — call after set_prompt, before next_token(0). Every
        sampled token is then masked to the grammar's allowed set and
        advances the host-side DFA cursor."""
        if guide is not None and not self.supports_guide:
            raise ValueError(
                f"{type(self).__name__} does not support constrained "
                "decoding (no masked sampling path)")
        if guide is not None:
            guide.reset()
        self.guide = guide
        self.guide_dead = False
        self._on_guide()

    def _on_guide(self) -> None:
        """Hook: upload/refresh device-side mask state for self.guide."""

    # -- shared bookkeeping --------------------------------------------------
    def _require_prompt(self) -> None:
        if not self._prompt_tokens:
            raise RuntimeError("set_prompt first")

    def _check_capacity(self) -> None:
        if self._pos >= self.max_seq:
            raise RuntimeError(
                f"KV cache exhausted: position {self._pos} >= max_seq "
                f"{self.max_seq} (raise max_seq or shorten the stream)"
            )

    def _finish_token(self, tok_id: int) -> Token:
        self._last_token = tok_id
        self._generated.append(tok_id)
        is_eos = tok_id in self._eos_ids
        if self.guide is not None and not is_eos:
            # host-side DFA advance between compiled steps; a dead end
            # (no emittable token at the new state) ends the stream
            if not self.guide.advance(tok_id) or self.guide.dead_end:
                from cake_tpu.constrain.guide import DEAD_ENDS

                self.guide_dead = True
                DEAD_ENDS.inc()
        # the EOS id is an end marker, not text (toy tokenizers map it to
        # an arbitrary printable char)
        text = (self.stream.next_token(tok_id)
                if self.stream is not None and not is_eos else None)
        return Token(id=tok_id, text=text,
                     is_end_of_stream=is_eos or self.guide_dead)

    def _decode_next(self, index: int, run_block, run_single) -> Token:
        """Shared block-decode control flow: pop the buffer, else collect
        an in-flight lookahead block, else dispatch a fused
        ``block_size``-step block (``run_block(index) -> list[int]``,
        which must advance ``_pos``/history), else a single step
        (``run_single(index) -> int``) for block_size == 1 or the tail of
        the KV window. The in-flight check runs BEFORE the capacity check:
        a lookahead block dispatched up to the window edge has already
        advanced ``_pos`` to ``max_seq``, and its tokens must still be
        delivered."""
        if self._block_buf:
            return self._finish_token(self._block_buf.popleft())
        toks = self._take_inflight(index)
        if toks is not None:
            self._block_buf.extend(toks)
            return self._finish_token(self._block_buf.popleft())
        self._check_capacity()
        if (self.block_size > 1 and self.guide is None
                and self._pos + self.block_size <= self.max_seq):
            # a live guide forces single-step dispatch: the in-block
            # feedback tokens would sample against a stale mask row
            self._block_buf.extend(run_block(index))
            return self._finish_token(self._block_buf.popleft())
        return self._finish_token(run_single(index))

    def _take_inflight(self, index: int) -> list[int] | None:
        """Hook: tokens already computed (or computing) on device from a
        lookahead dispatch. Default: none."""
        return None

    # -- Generator trait surface --------------------------------------------
    def next_token(self, index: int) -> Token:  # pragma: no cover - abstract
        raise NotImplementedError

    def last(self) -> str | None:
        """Flush residual detokenizer text (model/mod.rs `last`,
        llama.rs via token_output_stream.rs:55-69)."""
        return self.stream.decode_rest() if self.stream else None

    def generated_tokens(self) -> int:
        return len(self._generated)

    @property
    def generated_ids(self) -> list[int]:
        return list(self._generated)

    def close(self) -> None:
        pass


class LlamaGenerator(GeneratorBase):
    """Single-stream generator over an all-local model. (The distributed,
    topology-sharded equivalent — runtime.master.DistributedGenerator —
    shares this base and swaps the execution path for a runner walk.)

    Supports constrained decoding (``set_guide``): the guide's packed DFA
    mask table uploads once per prompt (rows padded to a pow2 capacity so
    the masked trace is stable across grammars), the decode step gathers
    the current state's row on device, and the DFA cursor advances
    host-side in ``_finish_token``. While a guide is live, fused
    block/lookahead dispatch is bypassed — tokens 2..K of a block would
    sample against a stale mask row."""

    supports_guide = True

    def __init__(
        self,
        config: LlamaConfig,
        params,
        tokenizer=None,
        settings: SamplerSettings | None = None,
        max_seq: int | None = None,
        cache_dtype=None,
        block_size: int = 1,
        kv_quant: str | None = None,
        lookahead: bool = False,
    ):
        """``block_size > 1`` fuses that many decode steps into one dispatch
        (lax.scan; sampling stays on-device) and streams the buffered tokens
        one at a time — dispatch latency amortizes ~K-fold (its share of
        a single-token step: not measured on the chip tool). The sampling key
        schedule is block-size-invariant (absolute token index), so a given
        seed yields the same stream at any block size.

        ``lookahead`` (needs block_size > 1) dispatches block N+1 from the
        DEVICE-side feedback token before block N's rows are fetched to the
        host, hiding the device->host readback + detok + emission behind
        device compute (JAX async dispatch). Token streams are bit-identical
        to the non-lookahead path: the feedback token is exactly the one the
        host would have fed back, and the key schedule is absolute-index
        based.

        ``kv_quant="int8"`` stores the KV cache as int8 + per-slot scales
        (half the cache HBM; quantize-on-write, kvcache.QuantizedKV)."""
        super().__init__(config, tokenizer, settings, max_seq)
        self.params = params
        self.block_size = max(1, block_size)
        self._lookahead = bool(lookahead) and self.block_size > 1
        self._inflight = None  # un-fetched [steps] device tokens
        self._guide_table = None  # device mask table (set_guide uploads)
        # per-token dispatch latency (block dispatches record ms/token so
        # the series is comparable across block sizes) and prompt-pass ms
        self._decode_hist = obs_metrics.Histogram("generator.decode_ms")
        self._prefill_hist = obs_metrics.Histogram("generator.prefill_ms")
        obs_metrics.registry().publish(self._decode_hist, self._prefill_hist)
        self.cache = init_cache(config, batch=1, max_seq=self.max_seq,
                                dtype=cache_dtype, quant=kv_quant)
        self._prefill = jax.jit(
            partial(prefill_fn, config=config),
            donate_argnames=("cache",),
        )
        # single-step program: block_size 1, and the tail of the KV window
        self._decode_single = jax.jit(
            partial(decode_step_fn, config=config, settings=self.settings),
            donate_argnames=("cache",),
        )
        self._decode = (
            jax.jit(
                partial(decode_scan_fn, config=config, settings=self.settings,
                        steps=self.block_size),
                donate_argnames=("cache",),
            )
            if self.block_size > 1 else self._decode_single
        )

    def _on_new_prompt(self) -> None:
        # an in-flight lookahead block belongs to the previous stream; its
        # stale KV writes sit beyond the new prompt's causal frontier (the
        # same invariant set_prompt documents for the cache itself)
        self._inflight = None

    def _on_guide(self) -> None:
        """Upload the guide's packed mask table (pow2-padded rows: one
        masked-program trace per capacity, not per grammar)."""
        if self.guide is None:
            self._guide_table = None
            return
        bits = self.guide.dfa.mask_bits
        cap = 64
        while cap < bits.shape[0]:
            cap *= 2
        table = jnp.zeros((cap, bits.shape[1]), jnp.uint8)
        self._guide_table = table.at[: bits.shape[0]].set(
            jnp.asarray(bits))

    def _dispatch_block(self, token_dev, index0: int):
        """Async-dispatch one fused ``block_size``-step block and advance
        the host-side position; the ``[steps]`` device token rows return
        UN-fetched so the caller chooses when to pay the host sync."""
        toks, self.cache, self._history, self._hist_slot = self._decode(
            self.params,
            token_dev,
            self.cache,
            jnp.int32(self._pos),
            self._key,  # base key; scan folds with the absolute index
            self._history,
            self._hist_slot,
            index0=jnp.int32(index0),
        )
        self._pos += self.block_size
        return toks

    def _run_block(self, index: int) -> list[int]:
        t0 = time.perf_counter()
        with span("decode.block", index=index, steps=self.block_size):
            if self._inflight is not None:
                toks = self._inflight  # block already computing on device
                self._inflight = None
            else:
                toks = self._dispatch_block(
                    jnp.asarray([self._last_token], jnp.int32), index
                )
            if self._lookahead and self._pos + self.block_size <= self.max_seq:
                # enqueue block N+1 from the DEVICE feedback token (exactly
                # the token the host would feed back) BEFORE block N's host
                # fetch — the device computes ahead while the host detoks
                # and emits; measured wall below is therefore mostly the
                # residual fetch wait, not the block's math
                self._inflight = self._dispatch_block(
                    toks[-1].reshape(1).astype(jnp.int32),
                    index + self.block_size,
                )
            out = [int(t) for t in toks]
        dt_ms = (time.perf_counter() - t0) * 1e3
        self._decode_hist.observe(dt_ms / self.block_size)
        rec = obs_flight.recorder()
        if rec.enabled:
            rec.record(
                index=index, kind="decode", total_ms=round(dt_ms, 3),
                steps=self.block_size, lookahead=self._lookahead,
            )
        return out

    def _take_inflight(self, index: int) -> list[int] | None:
        if self._inflight is None:
            return None
        return self._run_block(index)

    def _run_single(self, index: int) -> int:
        t0 = time.perf_counter()
        # constrained streams ride the same jitted step with the two mask
        # operands added (a separate trace; the unconstrained trace is
        # untouched). mask_row is the only per-token upload — the table
        # went up once at set_guide.
        kwargs = (
            dict(mask_table=self._guide_table,
                 mask_row=jnp.int32(self.guide.state))
            if self.guide is not None else {}
        )
        with span("decode.step", index=index):
            tok, self.cache, self._history, self._hist_slot = (
                self._decode_single(
                    self.params,
                    jnp.asarray([self._last_token], jnp.int32),
                    self.cache,
                    jnp.int32(self._pos),
                    jax.random.fold_in(self._key, index),
                    self._history,
                    self._hist_slot,
                    **kwargs,
                )
            )
            self._pos += 1
            out = int(tok)
        dt_ms = (time.perf_counter() - t0) * 1e3
        self._decode_hist.observe(dt_ms)
        rec = obs_flight.recorder()
        if rec.enabled:
            rec.record(
                index=index, kind="decode", total_ms=round(dt_ms, 3), steps=1,
            )
        return out

    def next_token(self, index: int) -> Token:
        """index 0: prefill the whole prompt; index>0: one-token decode
        (context windowing per llama.rs:228-232), or pop from the current
        fused block when block_size > 1."""
        if index == 0:
            self._require_prompt()
            n = len(self._prompt_tokens)
            t0 = time.perf_counter()
            with span("prefill", tokens=n):
                t_pad = _bucket(n, self.max_seq)
                padded = self._prompt_tokens + [0] * (t_pad - n)
                tokens = jnp.asarray([padded], jnp.int32)
                logits, self.cache = self._prefill(
                    self.params, tokens, self.cache,
                    jnp.asarray([n - 1], jnp.int32)
                )
                step_key = jax.random.fold_in(self._key, 0)
                tok = sampling.sample_token(
                    logits[0], step_key, self._history, self.settings,
                    mask=(jnp.asarray(self.guide.mask_bool())
                          if self.guide is not None else None),
                )
                self._history, self._hist_slot = sampling.push_history(
                    self._history, self._hist_slot, tok
                )
                self._pos = n
                tok_id = int(tok)
            dt_ms = (time.perf_counter() - t0) * 1e3
            self._prefill_hist.observe(dt_ms)
            rec = obs_flight.recorder()
            if rec.enabled:
                rec.record(
                    index=0, kind="prefill", total_ms=round(dt_ms, 3),
                    tokens=n,
                )
            return self._finish_token(tok_id)
        return self._decode_next(index, self._run_block, self._run_single)
