"""Multi-stream serving: N prompts decode concurrently over the mesh batch.

The reference is strictly single-request — "no batching of concurrent
requests" (SURVEY.md §0; one master walks one stream, master.rs:21-65). This
is the TPU-native capability on top of the same pipeline: the batch axis of
the fused mesh program (parallel/pipeline.py) shards over the ``dp`` mesh
axis, and every decode dispatch advances *all* streams by one token (or one
``block_size`` block).

Per-stream independence is real, not cosmetic:

- **positions**: prompts are right-padded to a shared bucket but each stream
  decodes at its own position (``pos [B]`` — per-row RoPE slices, KV writes,
  and causal frontiers down through the Pallas decode kernel), so a token's
  positional geometry is identical to a single-stream run of the same prompt.
- **sampling keys**: stream ``s`` owns ``fold_in(PRNGKey(seed), stream_id)``,
  stepped by the absolute token index inside the compiled program
  (pipeline per_row mode). A stream's stochastic output depends only on
  (seed, stream_id, prompt) — invariant to batch composition, dp layout, and
  block size.
- **repeat-penalty history**: per-stream ring buffers seeded with each
  prompt's tail, with per-stream ring slots (``hist_slot [B]``).
- **EOS / detok**: tracked per stream; a finished stream stops emitting while
  the batch keeps running (its rows keep computing into discarded outputs —
  the SPMD analogue of the pipeline's gated inactive stages).

Sequence parallelism (r4): on an ``sp > 1`` plan the KV window is sharded
across the sp axis and every stream still decodes at its own frontier —
the per-row positions flow through the owner-masked sp cache write and the
per-row-masked distributed flash decode (ops/ring.py). This is the
many-LONG-streams composition: window HBM splits over sp while the batch
splits over dp. Continuous admission, the prefix store, batched
speculation, AND the interleaved schedules all compose with ``sp > 1``
too (r5): staged/fed token blocks run chunk-replicated over sp against
the sequence-sharded cache (owner-masked range writes — per-row for the
verification plane — plus the T>1 distributed-flash chunk attend), the
slot splice is sharding-agnostic, and the interleaved cycle loop's
resident microbatch decodes against its sequence-sharded KV rows. The
one remaining sp == 1 path is GPipe microbatch PREFILL (prompts at
sp > 1 ride the ring prefill instead).

Continuous batching: arrivals ``enqueue`` into a FIFO and are admitted into
freed slots without stalling the batch — the head arrival's prefill is
*launched* the moment a slot is free (a replicated row into a staging
cache, ``parallel.pipeline.build_admit_prefill``; a long prompt by one
chunk dispatch per ``step()`` alongside the running decode dispatches),
together with the plain prompt that waits behind it where one compiled
program holds both, a free slot and a staging row each (``_start_arrival``,
``GROUP_SHAPES``: the weights are read once a launch, not once an
arrival), and *lands* once the block that was running has landed: the
finished rows are spliced into their slots and the device goes on, while
the streams are installed once the rows recorded before have been handed
out. ``admit()`` is the synchronous variant. Admission timing never
changes a stream's output (per-row positions + per-row token indices; for
an expert model up to the order of summation its call's rows select:
``enqueue``).

One order of work at a block boundary: when a block's tokens have landed
on the host, the device gets its next program — the next block, or a
waiting arrival's prefill — before the landed rows are handed out, and
delivery, retirement and bookkeeping run while it works
(``BatchGenerator.step``, ``_enqueue_block``, ``_admission_tick``). A
landing is device work only: behind the prefill the first tokens'
sampler, the splice (it takes the tokens on the device and writes the
donated cache in place) and the next program are enqueued back to back,
and the host reads the first token afterwards (``_finish_admission``),
after the rows recorded before the landing have gone out
(``_land_host``): those rows are the host's to deliver, not the device's
to wait for.

Int8-weight determinism: ``ops.quant.quant_matmul``'s measured m>=16
crossover would pick its backend per shape, so the SAME stream could see
different low-order logit bits between batch-size buckets or between
prefix-hit and prefix-miss admission prefills. An instance therefore PINS
one backend for its whole lifetime (``quant.pinned_impl``): explicitly via
``quant_backend=``, else chosen at first ``set_prompts`` from the dp-local
batch geometry against the measured crossover. Every program the instance
dispatches traces under that pin, so WITHIN an instance sampled int8
streams are invariant to batch-size buckets, admission timing, and
prefix-cache hits. Across two *differently sized* instances that land on
opposite sides of the crossover the pins (and low-order logit bits) can
still differ — pass the same explicit ``quant_backend`` to both when
cross-instance bit-reproducibility matters more than the measured
crossover's throughput.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from cake_tpu.kvpool import (
    SINK,
    PagePool,
    PoolExhausted,
    PrefixLRU,
    PrefixTree,
)
from cake_tpu.kvpool import pool as kvpool_pool
from cake_tpu.models.config import LlamaConfig
from cake_tpu.obs import flight as obs_flight
from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.obs import prof as obs_prof
from cake_tpu.obs.trace import span
from cake_tpu.ops import pallas as pk
from cake_tpu.ops import dsa, eva, quant, sampling
from cake_tpu.ops.kda import CHUNK
from cake_tpu.ops.kda import chunk_form_traced as delta_form_traced
from cake_tpu.ops.mla import latent_admit_choice
from cake_tpu.ops.moe import fetch_traced as moe_fetch_traced
from cake_tpu.ops.moe import form_traced as moe_form_traced
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.parallel.mesh import (
    DP,
    MeshPlan,
    init_cache_on_mesh,
    shard_params,
)
from cake_tpu.parallel.pipeline import (
    build_admit_prefill,
    build_interleaved_decode,
    build_sharded_decode,
    moe_counted,
    build_sharded_prefill,
)
from cake_tpu.runtime.generator import Token, _bucket, encode_prompt
from cake_tpu.runtime import threadcheck
from cake_tpu.utils.token_stream import TokenOutputStream


@dataclasses.dataclass
class _Stream:
    stream_id: int
    prompt: list[int]
    generated: list[int] = dataclasses.field(default_factory=list)
    # how many of ``generated`` a caller has been handed; the rest are
    # recorded rows that still wait (a landed block's)
    handed: int = 0
    done: bool = False
    active: bool = True  # False: batch-padding dummy, never emitted
    detok: TokenOutputStream | None = None
    # why the stream ended: "eos" | "length" (window full) | "constraint"
    # (grammar dead end) — the serve scheduler's finish_reason source
    end_reason: str | None = None


@dataclasses.dataclass(eq=False)
class _Staged:
    """One prompt arrival of a launched admission."""
    ids: list[int]
    sid: int
    slot: int
    guide: object | None
    # the admission's stamps (_ADMIT_STAGES), carried on from the arrival;
    # None where nothing is timed (admit())
    stamps: list | None


@dataclasses.dataclass(eq=False)
class _Landed:
    """A landing between its halves: the device has the sampler, the
    splice and its next program; the members' streams are not installed
    and their first tokens not fetched (``_finish_admission``)."""
    members: list[_Staged]  # finish() takes a cancelled one out
    rows: list[_Staged]  # the program's rows: which token is whose
    toks: object  # the first tokens [R], on the device
    lp: tuple | None  # their top-k logprobs, on the device
    booking: tuple  # the last prefill chunk's (_admit_dispatched)
    land_begin: float
    spliced: float
    # the count of rows handed out (``_rows_out``) from which the rows
    # recorded before the device half are all with the caller
    due: int


# The several-row admission programs there are, ``(rows, bucket)``: a
# launch takes riders only into one of these, and only once it is compiled
# (``_warm_bucket``: with the first one-row program of a bucket it holds).
# One shape, because a program costs set-up 1.5-5 s whenever a server
# starts, compile cache or not (its trace, its lowering, its load), and
# this one, because it is where a rider pays most: ``tools/admit_sweep.py``
# on the chip (PERF.md section 6, PR 37) has an expert model's admission at
# 21 / 29 / 34 / 41 ms for 64 / 128 / 256 / 512 rows in one row and at 43 /
# 59 for two rows of 256 / 512: below ~500 rows in all a program is the
# read of the held weights, above it the rows' arithmetic, which a second
# row doubles. (A dense model's admission is arithmetic from 128 rows on:
# there a launch of two saves a landing and pays for what it pads.)
GROUP_SHAPES = ((2, 256),)
# ... as far as its staging rows fit: a launch holds a staging row a member
# (a whole stream's reservation each) beside the live cache, a launch ahead
# of a landing one more beside the landing's, and the chip's compiler may
# keep a copy of a row's keys beside each (an admission on XLA's attention
# re-lays them on the way in and out). A dense cache's row is 0.25 GiB at
# 2048 rows; a cache with a plane a layer AND a pass holds 1.125 GiB a row
# at 768, and two of those with their temporaries beside 11.7 GiB of
# weights and live cache are 15.1 of the chip's 15.75 GiB (PERF.md section
# 6, PR 47): such a model stages one row at a time.
GROUP_STAGING_BYTES = 2**30


def _group_shape(own: list[int]) -> tuple[int, int] | None:
    """The several-row program for members whose own buckets are ``own``:
    the smallest of ``GROUP_SHAPES`` with a row each and a bucket that
    holds the longest (None: there is none)."""
    fits = [(r * c, r, c) for r, c in GROUP_SHAPES
            if r >= len(own) and c >= max(own)]
    return min(fits)[1:] if fits else None


# initial device mask-table capacity (rows); grows by doubling as guides
# attach, so the masked decode program compiles once per pow2 table shape
_MASK_CAP0 = 64

# Disaggregated-serving counters (cake_tpu/disagg): KV-page snapshots
# leaving and entering this engine's pool. Process-wide get-or-create —
# the serve scheduler and the gateway's tier map read the same story.
_EXPORTS = obs_metrics.counter("disagg.exports")
_IMPORTS = obs_metrics.counter("disagg.imports")
_RESUMES = obs_metrics.counter("disagg.resumes")
_IMPORT_ABORTS = obs_metrics.counter("disagg.import_aborts")
_MOE_LOCAL = obs_metrics.counter("moe.local_pairs")
_MOE_HIT = obs_metrics.counter("moe.experts_hit")
_MOE_DECODE_SORTED = obs_metrics.gauge("moe.decode_sorted")
_MOE_ROUTED = obs_metrics.counter("moe.routed_pairs")
_MOE_ZERO = obs_metrics.counter("moe.zero_pairs")
_MOE_STEPS = obs_metrics.counter("moe.decode_steps")
_MOE_ADMIT_ROWS = obs_metrics.counter("moe.admit_rows")
_MOE_ADMIT_SORTED = obs_metrics.counter("moe.admit_rows_sorted")
_MOE_SORTED_ROWS = obs_metrics.counter("moe.sorted_pair_rows")
_MOE_SORTED_LIVE = obs_metrics.counter("moe.sorted_pair_rows_live")
_MOE_GATHER_FETCHED = obs_metrics.counter("moe.gather_rows_fetched")
# by the mixer whose layers hold the state or the tail
# (LlamaConfig.layer_kinds)
# (both rules of ops/kda.py count as one: a delta-rule state)
_STATE_RESETS = {"kda": obs_metrics.counter("kda.state_resets"),
                 "gdn": obs_metrics.counter("kda.state_resets"),
                 "mamba": obs_metrics.counter("ssm.state_resets"),
                 "conv": obs_metrics.counter("conv.state_resets")}
# A delta-rule layer's admission is a serial loop over ops.kda.CHUNK-token
# chunks that stops at the launch's last live one: what a launch's rows
# cost at its longest row's length, and what they would at each row's own
# (a bucket's padding costs no chunk; a shorter row rides to the longest's
# end); and the swept chunks of the dispatches whose program runs the loop
# as ONE kernel call a layer (ops.kda.kda_chunk_choice, by the bucket)
_DELTA_CHUNKS_SWEPT = obs_metrics.counter("delta.chunks_swept")
_DELTA_CHUNKS_LIVE = obs_metrics.counter("delta.chunks_live")
_DELTA_CHUNKS_KERNEL = obs_metrics.counter("delta.chunks_kernel")
_SPEC_NGRAM = 3  # the longest n-gram a batched proposal is looked up by
# what a cache may hold beside rows (cache_plan's keys), as a refusal says it
_HELD = {"state": "recurrent state", "conv": "convolution's tail",
         "ring": "ring row", "index": "sparse attention's index key",
         "summary": "summary row"}
# The order of work at a block boundary (BatchGenerator._close_boundary):
# host time from a block's fetch returning to the return of the step()
# call that enqueued the device's next program, once per landed block,
# and how often that program left before any of the block's rows did.
_BOUNDARY_MS = obs_metrics.histogram("engine.boundary_ms")
_BOUNDARIES = obs_metrics.counter("engine.boundaries")
_BOUNDARIES_AHEAD = obs_metrics.counter("engine.boundaries_ahead")
# Where that host time is spent, of the boundaries at which the device
# WAITED, i.e. a later step() than the landing one enqueued the program
# (one that an admission launched ahead closes at once and leaves nothing
# here): the fetch's return -> the landing step()'s return (the rows'
# recording) -> the entry of the step() that enqueues (the caller's pass)
# -> the program call's return. They end where the device has its
# program; engine.boundary_ms runs on to that step()'s return.
_BOUNDARY_PARTS_MS = (obs_metrics.histogram("engine.boundary_emit_ms"),
                      obs_metrics.histogram("engine.boundary_pass_ms"),
                      obs_metrics.histogram("engine.boundary_enqueue_ms"))
# an expert model's counts of the landed blocks, fetched at the return of
# a step() that leaves no boundary open (_fetch_moe_counts): how long
_COUNTS_FETCH_MS = obs_metrics.histogram("engine.landing_counts_fetch_ms")
# An admission's stages, stamped on perf_counter where each boundary is
# crossed (BatchGenerator._observe_admission, once per landed prompt
# admission), and a block's period, landing to landing (_land_block):
# what stands between the decode step and a client's token gap.
# The stamps, in stage order: enqueued (enqueue()), launched (the first
# prefill dispatch returned), land_begin (_finish_admission entered: the
# landing's device half), landed (the first token on the host: its host
# half, after the rows recorded before the landing), spliced (the splice
# program enqueued). A stage runs from one stamp to the next; the splice
# leaves before the token is fetched (all but a guided arrival's), so the
# last stage reads 0 there.
_ADMIT_STAGES = (
    ("launch_wait", obs_metrics.histogram("engine.admit_launch_wait_ms")),
    ("rows_wait", obs_metrics.histogram("engine.admit_rows_wait_ms")),
    ("land", obs_metrics.histogram("engine.admit_land_ms")),
    ("to_splice", obs_metrics.histogram("engine.admit_to_splice_ms")),
)
_ADMISSIONS_LANDED = obs_metrics.counter("engine.admissions_landed")
# prompt admission programs launched (one for every arrival that rode):
# landed / launches is how many admissions a launch carries
_ADMIT_LAUNCHES = obs_metrics.counter("engine.admit_launches")
# landings whose splice AND the device's next program (the next arrival's
# prefill, else the next block) were enqueued before the first token was
# fetched: landings_ahead / admit_launches
_LANDINGS_AHEAD = obs_metrics.counter("engine.landings_ahead")
# landings whose device half (sampler, splice, next program) was enqueued
# while rows recorded before it were still to be handed out:
# landings_before_rows / admit_launches
_LANDINGS_BEFORE_ROWS = obs_metrics.counter("engine.landings_before_rows")
_BLOCK_PERIOD_MS = obs_metrics.histogram("engine.block_period_ms")
_BLOCK_PERIOD_CLEAR_MS = obs_metrics.histogram("engine.block_period_clear_ms")
_KV_BLOCKS_READ = obs_metrics.counter("attn.kv_blocks_read")
_RING_ROWS_LIVE = obs_metrics.counter("attn.ring_rows_live")
_RING_ROWS_SWEPT = obs_metrics.counter("attn.ring_rows_swept")
_KV_BLOCKS_RESERVED = obs_metrics.counter("attn.kv_blocks_reserved")
# EVA attention (ops/eva.py), from the positions as dispatched, a layer a
# step: the ring rows of a stream's own window (0 .. p % W) and the summary
# rows it sees (the windows completed before: (p // W) (W // C)), what the
# step's attention FETCHES of both (the kernel's blocks to each frontier,
# or both buffers whole), how many (layer, step) calls that was, the
# windows that reset; and the chunks summarised, a step's (one a stream and
# layer: the current chunk's row refreshed) and an admission's (every chunk
# of the bucket) apart
_EVA_WINDOW_ROWS_LIVE = obs_metrics.counter("attn.eva_window_rows_live")
_EVA_SUMMARY_ROWS_VISIBLE = obs_metrics.counter(
    "attn.eva_summary_rows_visible")
_EVA_ROWS_READ = obs_metrics.counter("attn.eva_rows_read")
_EVA_DECODE_CALLS = obs_metrics.counter("attn.eva_decode_calls")
_EVA_WINDOW_RESETS = obs_metrics.counter("eva.window_resets")
_EVA_CHUNKS_STEP = obs_metrics.counter("eva.chunks_summarised.step")
_EVA_CHUNKS_ADMIT = obs_metrics.counter("eva.chunks_summarised.admit")
# A learned sparse attention (ops/dsa.py), from the positions as
# dispatched: the rows a decode step's indexer scores (each stream's, to
# its frontier), those it attends (at most index_topk of them) and those
# its attention fetches (the sweep's blocks to the frontier, or the
# gather's chosen rows), a layer a step, and how many (layer, step) calls
# that was; the rows an admission
# launch's programs were handed (buckets), those that were prompt tokens,
# and how many (layer, dispatch) calls that was
_DSA_DECODE_CALLS = obs_metrics.counter("dsa.decode_calls")
_DSA_ADMIT_CALLS = obs_metrics.counter("dsa.admit_calls")
_DSA_ROWS_LIVE = obs_metrics.counter("dsa.rows_live")
_DSA_ROWS_SELECTED = obs_metrics.counter("dsa.rows_selected")
_DSA_ROWS_READ = obs_metrics.counter("dsa.rows_read")
_DSA_ADMIT_ROWS = obs_metrics.counter("dsa.admit_rows")
_DSA_ADMIT_ROWS_TRUE = obs_metrics.counter("dsa.admit_rows_true")
# ... and, at the rows' true lengths, the (query row, row at or before it)
# pairs a launch's indexers score and those its queries attend
# (min(t + 1, index_topk) a row), a layer
_DSA_PAIRS_SCORED = obs_metrics.counter("dsa.admit_pairs_scored")
_DSA_PAIRS_ATTENDED = obs_metrics.counter("dsa.admit_pairs_attended")
# Plain latent attention (ops/mla.py), from the positions as dispatched:
# the rows a decode step's sweep must read (each stream's, to its
# frontier), a plane a step, and how many (plane, step) calls that was;
# the causal pairs, at the rows' true lengths and at the bucket's (what the
# kernel was handed), of the first chunks whose own tokens the blocked
# admission's kernel attended, a plane, and how many (plane, dispatch)
# calls that was
_LATENT_DECODE_CALLS = obs_metrics.counter("attn.latent_decode_calls")
_LATENT_ROWS_LIVE = obs_metrics.counter("attn.latent_rows_live")
_LATENT_ADMIT_CALLS = obs_metrics.counter("attn.latent_admit_calls")
_LATENT_ADMIT_PAIRS = obs_metrics.counter("attn.latent_admit_pairs")
_LATENT_ADMIT_PAIRS_HANDED = obs_metrics.counter(
    "attn.latent_admit_pairs_handed")

# arrival-queue entry kinds (4th tuple field): None marks a plain prompt
# arrival; imports ride the SAME FIFO so pool-pressure deferral stays
# FIFO-fair between admissions and KV-page imports. The 5th field is when
# enqueue() took a prompt (perf_counter): the first of its admission's
# stamps; None for what is not timed (imports, attaches, admit()).
_ARR_IMPORT = "import"  # (xfer_id, None, None, _ARR_IMPORT, None)
_ARR_ATTACH = "attach"  # (xfer_id, sid, None, _ARR_ATTACH, None)


def _device_bytes(tree) -> int | None:
    """What a pytree's buffers occupy on their devices, tile padding
    included, a replica counted once; None where the runtime does not
    say."""
    try:
        return sum(
            shard.data.on_device_size_in_bytes()
            for x in jax.tree.leaves(tree) for shard in x.addressable_shards
            if shard.replica_id == 0)
    except (AttributeError, NotImplementedError, RuntimeError):
        return None


def _carrying(prog, steps: int, rows):
    """A fused block program that also returns, as device values, what
    the next block's dispatch feeds back: the last token row
    (``toks[-1]``), the frontiers and the token indices advanced by
    ``steps``. A steady boundary then issues ONE program call: no eager
    slice of the un-fetched tokens (2.6 ms of host time on the chip with
    no other thread awake, PERF.md PR 31) and no upload. Returns ``(the
    program's outputs, (last, pos, index))``; a slot that goes out at row
    0 (no live stream: ``BatchGenerator._decode_pos``) stays at row 0.
    The three come back under ``rows``, the sharding an upload of a
    per-row vector is given too, so that which of the two a dispatch
    takes is no new program signature. The function keeps the programs'
    name (``jit_step`` in a trace)."""
    def step(params, token, cache, pos, keys, history, hist_slot, index,
             *rest):
        out = prog(params, token, cache, pos, keys, history, hist_slot,
                   index, *rest)
        return out, (out[0][-1].astype(jnp.int32),
                     jnp.where(pos > 0, pos + steps, 0), index + steps)

    return jax.jit(step, donate_argnums=(2,),
                   out_shardings=(None, (rows, rows, rows)))


def _splice_rows(bufs: tuple, key, hist_row, used, tok, slot):
    """Inside a landing's splice program: make each staged arrival's row
    of the sampler state and write it into ``bufs`` (keys, history, ring
    slots, feedback tokens) at ``slot[i]`` (traced), along the buffers'
    first axis. ``tok [R]`` is the first token as the sampler left it on
    the device; ``hist_row [R, N]`` and ``used [R]`` are the prompt's tail
    and its length: the token is pushed into the ring here, on the
    device, so that no host fetch stands between sampler and splice."""
    tok = tok.astype(jnp.int32)
    hist_row, used = sampling.push_history_batched(hist_row, used, tok)
    vals = (key, hist_row, used, tok)
    for i in range(slot.shape[0]):
        bufs = tuple(jax.lax.dynamic_update_index_in_dim(b, v[i], slot[i], 0)
                     for b, v in zip(bufs, vals))
    return bufs


def build_splice(state_shardings: tuple, paged: bool = False):
    """A landing's splice as ONE jitted program with the slot indices
    TRACED: ``splice(cache, row, keys, history, hist_slot, last, key,
    hist_row, used, tok, slot)`` writes every staged row of ``row`` into
    ``cache`` at ``slot[i]`` and the rows of the sampler state beside it
    (``_splice_rows``). The live cache and the four state arrays are
    DONATED: the staged rows are written in place, no second cache is
    alive while it runs, and the caller keeps the outputs. ``row`` (the
    staging cache, which the prefix store may retain) is not. ``paged``:
    the state alone (the KV hand-off is a page write): ``splice(keys,
    history, hist_slot, last, key, hist_row, used, tok, slot)``. The
    function keeps the programs' name (``jit_splice`` in a trace)."""
    if paged:
        def splice(keys, history, hist_slot, last, key, hist_row, used, tok,
                   slot):
            return _splice_rows((keys, history, hist_slot, last), key,
                                hist_row, used, tok, slot)

        return jax.jit(splice, donate_argnums=(0, 1, 2, 3),
                       out_shardings=state_shardings)

    def splice(cache, row, keys, history, hist_slot, last, key, hist_row,
               used, tok, slot):
        for i in range(slot.shape[0]):
            cache = jax.tree.map(
                lambda c, r: jax.lax.dynamic_update_index_in_dim(
                    c, r[:, i], slot[i], 1),
                cache, row,
            )
        return (cache,) + _splice_rows(
            (keys, history, hist_slot, last), key, hist_row, used, tok, slot)

    return jax.jit(splice, donate_argnums=(0, 2, 3, 4, 5),
                   out_shardings=(None,) + state_shardings)


class BatchGenerator:
    """Serve N prompts concurrently over one sharded model instance.

    ``batch`` rows are sharded over the plan's dp axis (``N`` is padded up to
    a multiple of dp with inactive dummy rows). ``block_size > 1`` fuses that
    many decode steps per dispatch, same key schedule.
    """

    # Thread domain, machine-checked by cakelint CK-THREAD (the
    # declarative generalization of CK-ENGINE's single-writer rule):
    # every un-listed method runs on the engine-owner thread only —
    # annotated caller code (serve/gateway handler threads, transfer
    # receivers) must route through the scheduler's crossing points.
    # `_encode` is this class's own crossing point: a stateless
    # tokenizer pass the scheduler's handler-facing encode_prompt uses.
    # Instances travel as `self.engine` handles, hence the alias. The
    # runtime twin (CAKE_THREAD_STRICT=1, runtime/threadcheck) asserts
    # the same contract: the scheduler stamps its engine thread into
    # _domain_stamp at start and the annotated mutators check it.
    _THREAD_DOMAIN = "engine"
    _THREAD_ALIASES = ("engine",)
    _THREAD_SAFE = ("_encode",)

    def __init__(
        self,
        config: LlamaConfig,
        params,
        plan: MeshPlan | None = None,
        tokenizer=None,
        settings: SamplerSettings | None = None,
        max_seq: int | None = None,
        num_stages: int = 1,
        tp: int = 1,
        dp: int = 1,
        ep: int = 1,
        devices=None,
        block_size: int = 1,
        block_size_max: int = 0,
        kv_quant: str | None = None,
        admit_chunk: int | None = None,
        prefix_share_min: int = 32,
        interleave: bool | None = None,
        prefix_cache_entries: int = 2,
        prefix_block: int = 64,
        quant_backend: str | None = None,
        spec_k: int = 0,
        spec_rounds: int = 8,
        logprobs: int = 0,
        kv_layout: str = "slot",
        kv_page_size: int = 16,
        kv_pool_pages: int | None = None,
    ):
        if plan is None:
            plan = MeshPlan.build(config, num_stages=num_stages, tp=tp,
                                  dp=dp, sp=1, ep=ep, devices=devices)
        # sp > 1 (r4): multi-stream serving over a sequence-sharded window —
        # per-row frontiers flow through the sp owner-masked KV write and
        # per-row-masked distributed flash decode. Admission, the prefix
        # store, batched speculation, and the interleaved schedules all
        # compose with sp > 1 (r5, chunk-replicated programs + sp-aware
        # cycle loops); only GPipe microbatch prefill stays sp == 1
        # (_pick_prefill serializes it).
        # spec_k composes with sp > 1 (r5): the per-row verification
        # program runs each row's fed block chunk-replicated over sp
        # (pipeline.build_sharded_verify_rows) with per-row range writes.
        # (r5: the interleaved schedules compose with sp > 1 too — the
        # resident microbatch's decode/verify runs against its
        # sequence-sharded KV rows inside the cycle loop)
        self.config = config
        self.plan = plan
        # engine-owner thread stamp (runtime twin of CK-THREAD): the
        # serve scheduler stamps its engine thread here at start and
        # clears it on exit; unstamped, every check is vacuous, so
        # single-threaded drives (bench, examples, tests) run unchanged
        # even under CAKE_THREAD_STRICT=1
        self._domain_stamp = threadcheck.DomainStamp("engine")
        self.settings = settings or SamplerSettings()
        sampling.validate_logit_bias(self.settings, config.vocab_size)
        # Per-token top-k logprob reporting (serve `logprobs: N`): the
        # decode programs additionally return the top-k log-softmax of
        # the raw logits. Pure extra outputs — the sampled streams are
        # bit-identical with it on or off.
        self.logprobs_k = max(0, int(logprobs))
        if self.logprobs_k and spec_k:
            raise ValueError("logprobs do not compose with batched "
                             "speculation (spec_k): accepted runs have no "
                             "per-step logits to report")
        self.max_seq = max_seq or config.max_seq_len
        if plan.sp > 1 and self.max_seq % plan.sp:
            raise ValueError(
                f"max_seq {self.max_seq} must divide by sp {plan.sp} (the "
                "KV window shards over the sp axis)"
            )
        # Paged KV (cake_tpu/kvpool): the per-slot contiguous cache is
        # replaced by a pooled page array addressed through per-stream
        # page tables fed into the compiled decode step as gather
        # indices. Admission and retirement become host-side page-table
        # edits (plus a one-page-per-stream write-back per dispatch)
        # instead of cache-tensor splices, and refcounted pages turn the
        # prefix store into a real shared-prefix tree — n streams with
        # the same system prompt share physical prefill pages.
        if kv_layout not in ("slot", "paged"):
            raise ValueError(
                f"kv_layout must be 'slot' or 'paged', got {kv_layout!r}")
        self._paged = kv_layout == "paged"
        # One rule a mechanism, asked of what the cache holds
        # (``cache_plan``): the page pool holds per-head key and value rows
        # of every layer and nothing else; a rejected proposal cannot undo
        # a state, a tail or a ring.
        held = [_HELD.get(k, k)
                for k in sorted(set(config.cache_plan) - {"rows"})]
        per_head = config.cache_row == (
            config.num_key_value_heads, config.head_dim, config.head_dim)
        if self._paged and (held or not per_head):
            lacks = held + ([] if per_head else ["latent row"])
            raise ValueError(
                "kv_layout='paged' is not wired for this model: the page "
                "pool, and with it the disagg snapshot and the spill tier, "
                "hold per-head keys and values of every layer at every "
                f"position, and no {', no '.join(lacks)}, which its cache "
                "holds; serve this family with the slot layout")
        if spec_k and held:
            raise ValueError(
                "speculation (spec_k) is not wired for this model: a "
                "rejected proposal has already advanced or overwritten a "
                f"{' and a '.join(held)}, which its cache holds and nothing "
                "restores; serve this family with no speculation")
        # a looped model's cache holds a plane a layer AND a pass: what
        # counts a plane a layer, or was never compared with the
        # reference over a loop of passes, is refused
        if config.family.loops and (self._paged or spec_k or kv_quant):
            asked = ("kv_layout='paged'" if self._paged else
                     "speculation (spec_k)" if spec_k else
                     f"kv_quant={kv_quant!r}")
            raise ValueError(
                f"{asked} is not wired for a looped model: its cache "
                f"holds {config.cache_plan['rows'][0]} planes, one a "
                f"layer AND a pass of {config.total_ut_steps}, where the "
                "page pool (and with it the disagg snapshot and the spill "
                "tier) holds one a layer, and neither a verify round nor "
                "an int8 plane has been compared with the reference over "
                "a loop of passes; serve this family with the slot "
                "layout, no speculation and a cache in the serving type")
        # what _count_kv_blocks counts through: beside a ring, a full layer
        self._kv_window = (None if "ring" in config.cache_plan
                           else config.sliding_window)
        # ... in blocks of how many rows: what the decode kernel fetches
        # of a cache of this shape (the local heads of a tp mesh), asked
        # of the function the kernel asks; a latent row's kernel, and a
        # shape no kernel is built for, count in the default
        self._kv_block = (per_head and pk.decode_block_k(
            self.max_seq, config.num_key_value_heads // plan.tp,
            config.head_dim, config.jax_dtype.itemsize,
            config.num_attention_heads // config.num_key_value_heads)
        ) or pk.DECODE_BLOCK_K
        # ... and how many planes a layer it counts (a looped model's
        # passes, a double layer's two attentions: each reads and reserves
        # a plane of its own)
        self._kv_planes = config.total_ut_steps * config.family.planes_a_layer
        obs_metrics.gauge("model.planes_a_layer").set(
            config.family.planes_a_layer)
        # the layers under a learned sparse attention, and how many rows a
        # query of theirs attends at most (_count_kv_blocks, _launch)
        self._dsa = ((config.cache_plan["index"][0], config.index_topk)
                     if "index" in config.cache_plan else None)
        # the planes of plain latent rows (no indexer over them), and the
        # widths its admission's choice reads (_count_kv_blocks, _launch)
        self._latent_planes = (
            config.cache_plan["rows"][0]
            if config.kv_lora_rank and self._dsa is None else 0)
        # the layers whose admission is a scan of chunks (_count_delta_chunks)
        self._delta_layers = sum(
            m in ("kda", "gdn") for m, _ in config.layer_kinds)
        # the window layers' rings, if any: (layers, rows R, window)
        self._rings = (
            (config.cache_plan["ring"][0], config.ring_rows,
             config.sliding_window)
            if "ring" in config.cache_plan
            and "summary" not in config.cache_plan else None)
        # EVA layers, if any: (layers, window W, chunk C); their ring
        # resets, and what a step reads of it and of the summary plane is
        # counted apart (_count_kv_blocks)
        self._eva = (
            (config.cache_plan["summary"][0], config.window_size,
             config.chunk_size)
            if "summary" in config.cache_plan else None)
        if self._eva and self.max_seq % config.window_size:
            raise ValueError(
                f"max_seq {self.max_seq} is not a whole number of windows "
                f"of {config.window_size}: EVA attention's summaries become "
                "visible a window at a time")
        # how many staging rows (a stream's whole reservation each) may
        # live at once beside the cache: a launch of several rows, and a
        # launch ahead of the landing before it (GROUP_STAGING_BYTES)
        self._staging_rows_fit = GROUP_STAGING_BYTES // max(
            1, config.stream_bytes(self.max_seq))
        self._page_size = int(kv_page_size)
        self._pool_pages_req = kv_pool_pages
        if self._paged:
            if plan.dp != 1 or plan.sp != 1:
                raise ValueError(
                    "kv_layout='paged' requires dp == 1 and sp == 1 (the "
                    "page axis is unsharded; batch/sequence sharding of "
                    "pooled pages is future work)")
            if spec_k:
                raise ValueError(
                    "kv_layout='paged' does not compose with batched "
                    "speculation (spec_k): the fused verify rounds write "
                    "K+1 slots per row outside the page write-back plan")
            if self._page_size < 1 or self.max_seq % self._page_size:
                raise ValueError(
                    f"kv_page_size {self._page_size} must be a positive "
                    f"divisor of max_seq {self.max_seq}")
            if kv_pool_pages is not None and (
                    kv_pool_pages < 2
                    or kv_pool_pages & (kv_pool_pages - 1)):
                # shape validation belongs HERE with the other paged
                # knobs (the CLI's try/except turns ctor ValueErrors into
                # clean exits); only the batch-dependent >= need bound
                # waits for set_prompts (_init_pool)
                raise ValueError(
                    f"kv_pool_pages must be a power of two >= 2, got "
                    f"{kv_pool_pages}")
            self._ppp = self.max_seq // self._page_size  # pages per stream
        self._pagepool = None          # host free-list/refcounts (kvpool)
        self._prefix_tree = None       # page-granular shared-prefix trie
        self._tables: list[list[int]] = []  # per-slot physical page lists
        # KV-page imports (cake_tpu/disagg): xfer_id -> record. Pages of
        # a begun-but-unattached import are PINNED in the pool (a claim
        # outside stream tables and the prefix tree — kvpool pin/unpin),
        # so eviction storms under pressure can never free them before
        # the resume attaches or the import is aborted.
        self._imports: dict[str, dict] = {}
        self._attach_failures: list[int] = []  # sids whose attach missed
        self._page_map_dev = None      # memoized device page map (tables
        #                                change rarely; scatter ids do not)
        self._staged_prefix = None     # set_prompts staged prefix row
        self._admit_deferred = False   # last tick deferred on pool pressure
        self.tokenizer = tokenizer
        self.block_size = max(1, block_size)
        # Adaptive decode blocks (the continuous-batching dispatch lever):
        # with block_size_max > block_size, the fused block DOUBLES each
        # dispatch while the arrival queue is empty — amortizing the
        # per-dispatch host sync over more tokens — and snaps back to
        # block_size the moment an arrival waits, so admission latency
        # stays one base block. Grown sizes live on a doubling ladder
        # (base*2^k) so the window-headroom cap below can halve back onto
        # a compiled program; block_size_max is rounded down to the
        # ladder. warm_blocks() compiles the ladder outside the serving
        # window. What a dispatch's host sync costs beside the chip, and
        # so what ladder height pays: not measured on the chip tool.
        bmax = max(0, int(block_size_max))
        if bmax > self.block_size:
            k = (bmax // self.block_size).bit_length() - 1
            self.block_size_max = self.block_size * (1 << k)
        else:
            self.block_size_max = self.block_size
        self._adaptive = self.block_size
        self.__block_progs: dict = {}
        # The order of work at a block boundary: when a block's tokens have
        # landed on the host, the device gets its next program first (the
        # next block, dispatched from the device-side feedback token
        # toks[-1] into ``_inflight``, or a waiting arrival's prefill) and
        # the landed rows are handed out, one a step(), while it runs.
        # Token streams are unchanged: the feedback token is exactly the
        # one the host would have fed back, and rows computed past a
        # stream's EOS/retirement are discarded per-row like every other
        # overrun (the admission splice drains an in-flight block's rows
        # BEFORE a slot changes meaning -- _finish_admission). Where the
        # host must act between steps (a live guide, batched speculation,
        # block_size 1, a chunked admission under way) nothing is
        # enqueued ahead and the order is dispatch, fetch, hand out.
        self._inflight: tuple | None = None  # (toks [steps,B], lpv, lpi,
        #                                       steps, dispatched at)
        # an open boundary: when the last block's fetch returned, whether a
        # row of it has been handed out since, and (once the device's next
        # program is enqueued) whether that came first -- engine.boundary_*
        self._landed_at: float | None = None
        self._landed_rows_out = False
        self._next_ahead: bool | None = None
        # an open boundary's later stamps (engine.boundary_*_ms's edges):
        # when the landing step() returned, when the current step() was
        # entered, when the next program's call returned inside it
        self._returned_at: float | None = None
        self._step_at: float | None = None
        self._enqueued_at: float | None = None
        # a block's period, landing to landing: when the previous block
        # landed (forgotten where the engine goes idle, so the wait for
        # the next request is no period) and whether an admission has
        # landed since -- engine.block_period_*
        self._period_from: float | None = None
        self._period_admitted = False
        # the longest wait for the device inside the current step() (a
        # block's fetch, an admission's first token): a slow scheduler
        # pass says with it whether the engine's thread ran or waited,
        # and for which fetch: "block:<steps>", "admit_land:<bucket>",
        # "counts:<blocks>"
        self.step_fetch_ms = 0.0
        self.step_fetch_of = ""
        # landed admissions' stages by stream id, until the scheduler
        # takes them for the request's own timeline
        # (take_admission_stages); the oldest go where nobody does
        self._admit_stages: dict[int, list] = {}
        # int8 KV roughly doubles servable batch x window on a fixed HBM
        # budget (quantize-on-write per slot, kvcache.QuantizedKV) — the
        # serving-side long-context lever
        self.kv_quant = kv_quant
        self.params = shard_params(params, plan.mesh)
        # Int8 backend pin: explicit (quant_backend=) or decided once at
        # first set_prompts from the dp-local batch geometry (measured
        # m>=16 crossover), then applied to every program dispatch for the
        # instance's lifetime — see the module docstring's determinism
        # contract and its cross-instance scope note.
        if quant_backend not in (None, "xla", "pallas"):
            raise ValueError(
                f"quant_backend must be 'xla' or 'pallas', got "
                f"{quant_backend!r}"
            )
        self._quant_pin: str | None = quant_backend

        def _has_quant(p, kinds) -> bool:
            if isinstance(p, dict):
                return any(_has_quant(v, kinds) for v in p.values())
            return isinstance(p, kinds)

        self._params_quantized = _has_quant(
            self.params, (quant.QuantizedLinear, quant.Quantized4Linear)
        )
        self._params_int4 = _has_quant(self.params, quant.Quantized4Linear)
        self._prefill = self._pinned(build_sharded_prefill(
            config, plan, params_like=self.params, kv_quant=kv_quant))
        # a per-row [B] vector's sharding, as the decode programs take it
        self._rows = NamedSharding(plan.mesh, PartitionSpec(DP))
        # ... and the sampler state's (keys, history, ring slots, feedback
        # token), as they leave it: set_prompts and the admission splice
        # leave it so too, so a splice warmed on a fresh batch is the
        # program every later landing runs
        rows2 = NamedSharding(plan.mesh, PartitionSpec(DP, None))
        self._state_shardings = (rows2, rows2, self._rows, self._rows)
        # raw jit handle kept so tests can pin the compile count — the
        # paged layout's page-table operands are DATA, so table churn
        # (admission, retirement, page growth) must never retrace
        self._decode_single_jit = build_sharded_decode(
            config, self.settings, plan, params_like=self.params,
            per_row=True, kv_quant=kv_quant, logprobs_k=self.logprobs_k,
            paged=self._paged,
        )
        self._decode_single = self._pinned(self._decode_single_jit)
        # (the raw jitted callable stays reachable, as _decode_single_jit
        # does, so that tests can pin its signatures)
        self._decode_block_jit = (
            _carrying(build_sharded_decode(
                config, self.settings, plan, params_like=self.params,
                steps=self.block_size, per_row=True, kv_quant=kv_quant,
                logprobs_k=self.logprobs_k, paged=self._paged),
                self.block_size, self._rows)
            if self.block_size > 1 else None
        )
        self._decode_block = (self._pinned(self._decode_block_jit)
                              if self.block_size > 1 else None)
        # Interleaved-microbatch schedule (pipeline.build_interleaved_decode):
        # with num_stages > 1 every stage decodes a different microbatch each
        # cycle instead of (S-1)/S of the mesh computing into a discarded
        # select. Output streams are bit-identical, so it swaps in at
        # dispatch whenever the batch divides by the stage count; serialized
        # programs remain the fallback (programs compile lazily on first
        # use, so the unused path costs nothing).
        self._interleave = (
            plan.num_stages > 1 if interleave is None
            else interleave and plan.num_stages > 1
        )
        if self.logprobs_k:
            # the interleaved schedule has no logprob outputs (its head
            # runs vocab-split per stage); serialized programs are
            # bit-identical, so logprob serving just uses those
            self._interleave = False
        if self._paged:
            # the interleaved schedule has no paged twin yet; serialized
            # paged programs are bit-identical, so paged serving uses
            # those (same fallback contract as logprobs)
            self._interleave = False
        self._decode_single_il = (
            self._pinned(build_interleaved_decode(
                config, self.settings, plan, params_like=self.params,
                steps=1, kv_quant=kv_quant))
            if self._interleave else None
        )
        self._decode_block_il = (
            self._pinned(_carrying(build_interleaved_decode(
                config, self.settings, plan, params_like=self.params,
                steps=self.block_size, kv_quant=kv_quant),
                self.block_size, self._rows))
            if self._interleave and self.block_size > 1 else None
        )
        self._base_key = jax.random.PRNGKey(self.settings.seed)
        self.streams: list[_Stream] = []
        self._eos_ids = set(config.eos_ids())
        # Constrained decoding (cake_tpu/constrain): per-slot Guide
        # cursors advanced host-side between steps; their DFAs' packed
        # mask rows live concatenated in ONE device-resident uint8 table
        # (row 0 = all-ones for unconstrained streams) that the masked
        # decode program gathers from by the per-slot mask_row vector.
        # The table re-uploads only when a guide attaches; its row
        # capacity grows by doubling so the masked program compiles once
        # per pow2 shape (compile-count pinned by test).
        self._guides: dict[int, object] = {}       # slot -> Guide
        self._guide_rows: dict[int, int] = {}      # slot -> table base row
        self._mask_table = None                    # jnp [cap, ceil(V/8)] u8
        self.__masked = None                       # _pinned masked program
        self._masked_jit = None                    # raw jit (compile count)
        self._first_lp = None                      # first-token logprobs
        # Continuous-batching admission: arrivals queue here (enqueue) and
        # prefill ONE chunk per step() interleaved with decode dispatches,
        # as a single replicated row in a staging cache — no dp discarded
        # copies, no multi-dispatch stall of the running batch.
        # ``admit_chunk`` sets the per-dispatch chunk length (None: the
        # whole bucketed prompt in one dispatch). It must divide max_seq:
        # otherwise a near-window prompt rounds up PAST the window and the
        # final chunk's clamped dynamic_update_slice would silently
        # overwrite committed KV slots (wrong tokens, no error).
        if admit_chunk is not None and (
            admit_chunk < 1 or self.max_seq % admit_chunk
        ):
            raise ValueError(
                f"admit_chunk {admit_chunk} must be a positive divisor of "
                f"max_seq {self.max_seq} (a chunk round-up past the window "
                "would clamp-overwrite committed KV)"
            )
        if admit_chunk is not None and self._eva:
            raise ValueError(
                "admit_chunk is not wired for EVA attention: a chunk that "
                "has history behind it would attend the cached ring and "
                "summaries beside its own, which no program here computes; "
                "such a prompt is admitted a whole bucket at a time")
        if admit_chunk is not None and "index" in config.cache_plan:
            raise ValueError(
                "admit_chunk is not wired for a model under a learned "
                "sparse attention (index_topk > 0): a chunk that has "
                "history behind it would choose among cached rows and its "
                "own under one mask, which no program here computes; such "
                "a prompt is admitted a whole bucket at a time")
        self._admit_chunk = admit_chunk
        # Shared-prefix serving: when every prompt in a batch opens with
        # the same >= prefix_share_min tokens (the system-prompt case), the
        # prefix is prefilled once instead of once per stream (0 disables).
        self._prefix_share_min = max(0, prefix_share_min)
        self._arrivals: list[tuple] = []  # see _ARR_IMPORT
        # the launched admission: ONE prefill program over the staging
        # rows of every arrival that rode with the head (_start_arrival)
        self._staging: dict | None = None
        # landings between their halves, in order: spliced, their slots
        # served by the device's next program, their streams installed
        # once the rows recorded before them are out (_land_host)
        self._landed: list[_Landed] = []
        self._rows_out = 0  # recorded rows handed out by step(), ever
        # (rows, chunk) of the admission programs compiled so far, and the
        # row counts whose landing (sampler, splice) is: _warm_bucket
        self._warmed: set[tuple[int, int]] = set()
        self._landing_warmed: set[int] = set()
        self._warm_pending: list[int] = []  # chunks warmed before a batch
        self.__admit_prefill = None
        self.__prefill_offset = None
        self.__broadcast_progs: dict = {}
        self.__splice = None  # slot-traced admission splice
        self.__row_of = None  # one staged row out of a launch's cache
        self.__first_tokens = None  # a landing's keys and first tokens
        self.__splice_small = None  # paged: sampler-state-only splice
        self._contiguous_cache = None  # set_prompts -> _pageify_batch hand-off
        # Generalized prefix store (slot layout): staged batch-1 KV rows
        # keyed by their token prefix in an explicit LRU
        # (kvpool.PrefixLRU). Populated by the set_prompts shared prefix
        # AND by every completed admission (its prefix truncated to a
        # prefix_block boundary), so arrivals with DIFFERENT system
        # prompts each hit their own cached prefix. A row may hold donor
        # KV past the match length — positions >= the match base are
        # beyond the reusing stream's causal frontier until its own
        # remainder prefill/decode overwrites them, the same
        # never-attendable invariant as bucketed-prefill padding. Entries
        # cost one batch-1 cache each; prefix_cache_entries caps HBM
        # (0 disables reuse). The paged layout replaces this whole-row
        # store with the page-granular shared-prefix tree (_prefix_tree):
        # hits SHARE physical pages via refcounts instead of copying a
        # staged row, and eviction is pool-pressure-driven.
        self._prefix_entries = max(0, prefix_cache_entries)
        if (held or config.family.loops) and (
                self._prefix_entries or prefix_share_min):
            # a stored row's recurrent state (or window layers' ring) is
            # the one at the END of the prompt that left it, not at the
            # shared prefix's end: a hit would start from the wrong state
            # (from a ring whose newest rows lie past the prefix). Every
            # prompt of such a model is prefilled whole. A looped model's
            # stored row is a plane a layer AND a pass (a whole stream's
            # reservation, 1.1 GiB at 192 planes of 768 rows) and a hit
            # over them has not been compared with the reference.
            logging.getLogger("cake_tpu.batch_generator").info(
                "prefix reuse is off: a recurrent state, a convolution's "
                "tail, a ring of rows or a plane a pass has no prefix "
                "to share")
            self._prefix_entries = self._prefix_share_min = 0
        self._prefix_store = PrefixLRU(self._prefix_entries)
        self._prefix_block = max(1, prefix_block)
        self._prefix_hits = 0
        # Batched n-gram speculation (spec_k > 0): each dispatch verifies
        # every live stream's K prompt-lookup proposals in ONE per-row
        # pass (pipeline.build_sharded_verify_rows) and banks the accepted
        # run — 1..K+1 tokens per stream per dispatch. Greedy streams stay
        # bit-identical to plain serving decode (the accept emits the same
        # repeat-penalized argmaxes); sampled streams are distribution-
        # identical via the per-row rejection-sampling accept. A row with
        # no proposal still advances exactly one token (-1 pads never
        # match), so the batched verify subsumes a plain decode step.
        self._spec_k = max(0, int(spec_k))
        self._spec_bank: list[list[int]] = []
        self._n_spec_dispatches = 0
        self._n_spec_chains = 0
        # Fused round chaining (spec_rounds > 1): per-round device programs
        # — device n-gram propose, the (mesh) verify, accept+state-update —
        # are dispatched back-to-back with NO host fetch between rounds;
        # banks are fetched once per chain: the serving twin of the
        # single-stream fused scan (runtime/speculative.spec_rounds_fn).
        # The per-round host sync this saves, against the verify forward:
        # not measured on the chip tool.
        self._spec_rounds = max(1, int(spec_rounds))
        self._spec_ctx = None  # [B, max_seq] int32 device context rows
        self._spec_ctx_pos: np.ndarray | None = None  # host pos at sync
        self.__spec_propose = None
        self.__spec_update = None
        self.__verify_rows = None
        self.__verify_rows_il = None
        self.__accept_rows = None
        self.__prefill_pipelined = None
        # Serving observability (the worker-side ops/s + master tok/s story
        # of the reference, on the batch plane): dispatch and token
        # counters plus busy wall-clock, reported by stats().
        self._n_decode_dispatches = 0
        self._n_admit_dispatches = 0
        self._n_emitted = 0
        self._busy_s = 0.0
        self._t_start: float | None = None
        # per-instance obs instruments (Registry.publish pattern): stats()
        # percentiles must reflect THIS generator, not samples a
        # predecessor in the same process left in a shared series
        self._dispatch_hist = obs_metrics.Histogram("serve.decode_dispatch_ms")
        self._emitted_ctr = obs_metrics.Counter("serve.tokens_emitted")
        obs_metrics.registry().publish(
            self._dispatch_hist, self._emitted_ctr)
        # an expert model's load on the experts held here: the decode
        # programs of a model told its share return each row's routed
        # pairs that fell on held experts as one more value
        # (pipeline.moe_counted), fetched with the block's tokens
        self._moe_counted = moe_counted(config)
        # (local pairs a row, steps, live rows) of dispatches not yet
        # fetched, and how many of them (the oldest) have landed: their
        # counts are ready, and are fetched once the device has its next
        # program (_fetch_moe_counts), not while it waits for one
        self._moe_pending: deque = deque()
        # admission dispatches' (sorted pair rows, live ones), un-fetched
        self._moe_admitted: deque = deque()
        self._moe_landed = 0
        # engine profiling plane (obs/prof): sampled step-phase stamps +
        # the runtime retrace sentinel watching this engine's dispatches
        self._prof = obs_prof.profiler()
        self._sentinel = obs_prof.sentinel()
        self._sentinel.install()

    @property
    def _prefill_offset(self):
        """Offset prefill program (shared-prefix remainders), compiled on
        first use."""
        if self.__prefill_offset is None:
            self.__prefill_offset = self._pinned(build_sharded_prefill(
                self.config, self.plan, params_like=self.params,
                kv_quant=self.kv_quant, with_offset=True,
            ))
        return self.__prefill_offset

    def _prefill_shared_prefix(self, prefix: list[int], b: int) -> None:
        """Prefill the common prefix ONCE as a single replicated row (the
        admission-prefill program, chunked) and broadcast the staged KV
        into all ``b`` batch rows of ``self.cache``."""
        chunk = self._admission_chunk_for(len(prefix))
        t_pad = -(-len(prefix) // chunk) * chunk
        toks = np.zeros((1, t_pad), np.int32)
        toks[0, : len(prefix)] = prefix
        staging = init_cache_on_mesh(
            self.config, self.plan.mesh, batch=1, max_seq=self.max_seq,
            quant=self.kv_quant, batch_replicated=True,
        )
        for pos in range(0, t_pad, chunk):
            _, staging = self._admit_prefill(
                self.params, jnp.asarray(toks[:, pos: pos + chunk]),
                staging, jnp.int32(pos),
                jnp.asarray([max(0, len(prefix) - 1 - pos)], jnp.int32),
            )
            self._n_admit_dispatches += 1
        if self._paged:
            # the staged row's full pages become SHARED pool pages at
            # pageification (_pageify_batch) — keep the row until then
            self._staged_prefix = (list(prefix), staging)
        else:
            # keep the staged prefix row: arrivals opening with the same
            # prefix start from a copy of it instead of re-prefilling
            self._store_prefix(list(prefix), staging)
        self.cache = self._broadcast_prog(b)(staging)

    def _broadcast_prog(self, b: int):
        """Compiled prefix-row -> batch-cache broadcast, memoized per batch
        size (a fresh jit closure per call would retrace and recompile on
        every shared-prefix batch admission)."""
        prog = self.__broadcast_progs.get(b)
        if prog is None:
            from functools import partial

            from cake_tpu.parallel.mesh import cache_specs

            out_sh = jax.tree.map(
                lambda s: NamedSharding(self.plan.mesh, s),
                cache_specs(self.kv_quant),
                is_leaf=lambda x: isinstance(x, PartitionSpec),
            )

            @partial(jax.jit, out_shardings=out_sh)
            def prog(r):
                return jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        x, (x.shape[0], b) + x.shape[2:]
                    ),
                    r,
                )

            self.__broadcast_progs[b] = prog
        return prog

    @property
    def _admit_prefill(self):
        """Admission-prefill program, compiled on first use (callers that
        never admit mid-run pay nothing)."""
        if self.__admit_prefill is None:
            prog = self._pinned(build_admit_prefill(
                self.config, self.plan, params_like=self.params,
                kv_quant=self.kv_quant,
            ))
            if self._moe_counted:
                prog = self._admissions_counted(prog)
            self.__admit_prefill = prog
        return self.__admit_prefill

    def _admissions_counted(self, prog):
        """An expert model's admission program returns two more values,
        the pair rows its sorted-form calls were handed and touched: kept
        un-fetched until the program has run (``_fetch_moe_counts``)."""
        def counted(params, tokens, *args):
            logits, cache, handed, live = prog(params, tokens, *args)
            # the bucket says how its live rows were gathered
            self._moe_admitted.append(
                (handed, live, moe_fetch_traced(tokens.size)))
            return logits, cache
        return counted

    @property
    def _verify_rows(self):
        """Per-row speculation-verification program, compiled on first use."""
        if self.__verify_rows is None:
            from cake_tpu.parallel.pipeline import build_sharded_verify_rows

            self.__verify_rows = self._pinned(build_sharded_verify_rows(
                self.config, self.plan, params_like=self.params,
                kv_quant=self.kv_quant,
            ))
        return self.__verify_rows

    def _pick_verify(self):
        """Serialized vs interleaved verification for this dispatch (the
        same schedule choice _pick_decode makes): interleaved needs
        num_stages > 1 and the dp-local batch divisible by the stage
        count; logits are bit-identical either way."""
        S = self.plan.num_stages
        if not self._interleave or S < 2:
            return self._verify_rows
        if (len(self.streams) // self.plan.dp) % S:
            return self._verify_rows
        if self.__verify_rows_il is None:
            from cake_tpu.parallel.pipeline import (
                build_interleaved_verify_rows,
            )

            self.__verify_rows_il = self._pinned(
                build_interleaved_verify_rows(
                    self.config, self.plan, params_like=self.params,
                    kv_quant=self.kv_quant,
                ))
        return self.__verify_rows_il

    @property
    def _accept_rows(self):
        """Batched accept scan (greedy exact-match or rejection sampling),
        jitted on first use."""
        if self.__accept_rows is None:
            from functools import partial

            from cake_tpu.runtime.speculative import (
                accept_fn_rows,
                accept_sampled_fn_rows,
            )

            eos = jnp.asarray(sorted(self._eos_ids) or [-1], jnp.int32)
            accept = (accept_fn_rows if self.settings.greedy
                      else accept_sampled_fn_rows)
            self.__accept_rows = jax.jit(partial(
                accept, eos_ids=eos, settings=self.settings))
        return self.__accept_rows

    @staticmethod
    def _host(x) -> np.ndarray:
        """Device->host fetch that stays valid when the dp axis spans
        PROCESSES (multi-host serving): every host runs the identical
        serving loop and needs the full row for emission bookkeeping, so a
        non-fully-addressable array is process_allgather'd (these are tiny
        [B]-shaped token/count arrays)."""
        try:
            return np.asarray(x)
        except RuntimeError:
            from jax.experimental import multihost_utils

            return np.asarray(multihost_utils.process_allgather(x,
                                                                tiled=True))

    def _pinned(self, fn):
        """Wrap a compiled program so every dispatch — and therefore its
        trace, which happens on first call — runs under this instance's
        pinned int8 matmul backend (``quant.pinned_impl``). A no-op until
        the pin is decided and for bf16 weights."""
        def wrapped(*args):
            with quant.pinned_impl(self._quant_pin):
                return fn(*args)
        return wrapped

    # -- constrained decoding (cake_tpu/constrain) ---------------------------
    @property
    def eos_ids(self) -> frozenset:
        """Public EOS-id surface of the engine facade — what the serve
        scheduler maps finish reasons with (no private-attr reaches)."""
        return frozenset(self._eos_ids)

    @property
    def _decode_single_masked(self):
        """The constrained single-step decode program, compiled on first
        use (unconstrained serving never pays for it). ``_masked_jit``
        keeps the raw jitted callable so tests can pin its compile count
        — exactly one compile per (batch, table-capacity) shape."""
        if self.__masked is None:
            self._masked_jit = build_sharded_decode(
                self.config, self.settings, self.plan,
                params_like=self.params, per_row=True,
                kv_quant=self.kv_quant, masked=True,
                logprobs_k=self.logprobs_k, paged=self._paged,
            )
            self.__masked = self._pinned(self._masked_jit)
        return self.__masked

    def _check_guide_ok(self, guide) -> None:
        """Constraint-compatibility gate, raised where callers can turn
        it into a client error (enqueue / set_prompts) — NOT on the
        engine thread mid-step, where it would read as an engine fault
        and drain the server."""
        if guide is not None and self._spec_k:
            raise ValueError(
                "constrained decoding does not compose with batched "
                "speculation (spec_k): the fused verify rounds cannot "
                "advance the host-side DFA between tokens")

    def _attach_guide(self, slot: int, guide, rebuild: bool = True) -> None:
        """Bind a Guide to a batch slot and (by default) refresh the
        device mask table. Engine-thread only (like every other
        mutation); batch attachers pass rebuild=False and rebuild once."""
        self._check_guide_ok(guide)
        guide.reset()
        self._guides[slot] = guide
        if rebuild:
            self._rebuild_mask_table()

    def _drop_guide(self, slot: int) -> None:
        self._guides.pop(slot, None)
        self._guide_rows.pop(slot, None)
        # stale table rows are simply never referenced again; the table
        # re-packs at the next attach

    def _rebuild_mask_table(self) -> None:
        """Re-pack every attached guide's DFA mask rows into one device
        table: [row 0 = all-ones] + each guide's block. One host->device
        upload per ATTACH, never per token; capacity doubles so the
        masked program's traced shape is stable across attachments."""
        v8 = (self.config.vocab_size + 7) // 8
        blocks = [np.full((1, v8), 0xFF, np.uint8)]
        base = 1
        self._guide_rows = {}
        for slot in sorted(self._guides):
            bits = self._guides[slot].dfa.mask_bits
            self._guide_rows[slot] = base
            blocks.append(bits)
            base += bits.shape[0]
        cap = _MASK_CAP0
        while cap < base:
            cap *= 2
        table = np.zeros((cap, v8), np.uint8)
        table[:base] = np.concatenate(blocks)
        self._mask_table = jnp.asarray(table)

    def _guides_live(self) -> bool:
        return any(
            self.streams[i].active and not self.streams[i].done
            for i in self._guides
        )

    def _mask_rows_np(self) -> np.ndarray:
        """Per-slot mask-row vector for the next dispatch: row 0
        (all-ones) for unconstrained/done slots, the guide's current
        DFA-state row otherwise."""
        rows = np.zeros((len(self.streams),), np.int32)
        for slot, g in self._guides.items():
            s = self.streams[slot]
            if s.active and not s.done:
                rows[slot] = self._guide_rows[slot] + g.state
        return rows

    def _first_mask(self, b: int):
        """[B, V] bool constraint mask for the post-prefill first-token
        sampling (host-path), or None when no stream is constrained."""
        if not self._guides:
            return None
        mask = np.ones((b, self.config.vocab_size), bool)
        for slot, g in self._guides.items():
            mask[slot] = g.mask_bool()
        return jnp.asarray(mask)

    def _advance_guide(self, slot: int, s: _Stream, tok_id: int) -> None:
        """Host-side DFA advance for one emitted token; a dead end (no
        emittable token at the new state, not even EOS) retires the
        stream with end_reason 'constraint'."""
        g = self._guides.get(slot)
        if g is None:
            return
        # "guide" nests inside "emit" — sub-phase attribution, not
        # additional step time (obs/prof module doc)
        with self._prof.phase("guide"):
            if s.done:
                self._drop_guide(slot)
                return
            if not g.advance(tok_id) or g.dead_end:
                from cake_tpu.constrain.guide import DEAD_ENDS

                s.done = True
                s.end_reason = "constraint"
                self._drop_guide(slot)
                DEAD_ENDS.inc()

    def warm_constrain(self) -> None:
        """Compile the masked decode program against the live batch
        shapes outside the serving window (same contract as
        ``warm_blocks``/``warm_admission``: the first constrained request
        must not pay XLA compilation mid-serving). Uses a sacrificial
        cache copy; live state untouched."""
        if not self.streams:
            raise RuntimeError("set_prompts first")
        table = self._mask_table
        if table is None:
            v8 = (self.config.vocab_size + 7) // 8
            t = np.zeros((_MASK_CAP0, v8), np.uint8)
            t[0] = 0xFF
            table = jnp.asarray(t)
            self._mask_table = table
        cache = jax.tree.map(lambda x: x.copy(), self.cache)
        out = self._decode_single_masked(
            self.params, self._last_tokens, cache, jnp.asarray(self._pos),
            self._keys, self._history, self._hist_slot,
            jnp.asarray(self._index), table,
            jnp.zeros((len(self.streams),), jnp.int32),
            *self._paged_args_warm(1),
        )
        jax.block_until_ready(out)

    # -- prompt intake -------------------------------------------------------
    def _encode(self, p) -> list[int]:
        """Tokenize/validate one prompt (the shared single-stream
        set_prompt rules: BOS prepend, non-empty, fits the window, ids in
        vocab range — ``generator.encode_prompt``)."""
        return encode_prompt(p, self.tokenizer, self.config, self.max_seq)

    def set_prompts(
        self,
        prompts: list[list[int] | str],
        stream_ids: list[int] | None = None,
        guides: list | None = None,
    ) -> None:
        """Admit a batch of prompts. ``stream_ids`` pin each stream's
        sampling-key identity (default: its index) — the handle that makes a
        stream reproducible in any batch composition. ``guides`` (optional,
        aligned with ``prompts``; None entries = unconstrained) attach a
        constrain.Guide per stream — its grammar masks every sampling step
        including this call's first token."""
        self._domain_stamp.check("BatchGenerator.set_prompts")
        if not prompts:
            raise ValueError("empty batch")
        ids_list = [self._encode(p) for p in prompts]
        if stream_ids is None:
            stream_ids = list(range(len(ids_list)))
        if len(stream_ids) != len(ids_list):
            raise ValueError("stream_ids/prompts length mismatch")
        if guides is not None and len(guides) != len(ids_list):
            raise ValueError("guides/prompts length mismatch")
        if self._paged and self._imports:
            # the pool is rebuilt below (_init_pool): pending KV imports
            # reference pages of the OLD pool and cannot survive
            for xid in list(self._imports):
                self.import_abort(xid)
        self._guides = {}
        self._guide_rows = {}

        # pad the batch to a dp multiple with inactive dummies (they compute,
        # they are never emitted)
        n_active = len(ids_list)
        dp = self.plan.dp
        batch = -(-n_active // dp) * dp
        if self._quant_pin is None:
            # instance-lifetime backend choice, decided before any program
            # traces so every bucket and admission path sees the same
            # backend. int8: the m>=16 crossover (ops/quant.quant_matmul).
            # int4: the kernel wins at every geometry (the XLA fallback
            # streams 4x the packed bytes — ops/quant.py), so pin pallas
            # unconditionally.
            self._quant_pin = (
                "pallas"
                if self._params_int4 or batch // dp >= 16
                else "xla"
            )
        self.streams = [
            _Stream(
                stream_id=sid, prompt=ids,
                detok=TokenOutputStream(self.tokenizer)
                if self.tokenizer else None,
            )
            for sid, ids in zip(stream_ids, ids_list)
        ]
        for _ in range(batch - n_active):
            self.streams.append(
                _Stream(stream_id=-1, prompt=list(ids_list[0]), active=False)
            )
        b = len(self.streams)
        if guides is not None:
            for i, g in enumerate(guides):
                if g is not None:
                    self._attach_guide(i, g, rebuild=False)
            if self._guides:
                self._rebuild_mask_table()  # one repack+upload per batch

        # (the prefix store survives set_prompts: rows depend only on
        # params/config, both fixed for the instance's lifetime)
        # Shared-prefix detection: a common system prompt is prefilled ONCE
        # (single replicated row) and broadcast into every stream's cache
        # rows; only the per-stream remainders go through the batched
        # prefill, at offset lcp. Capped one short of the shortest prompt so
        # every row keeps >= 1 remainder token. Bit-identical output —
        # positions and tokens are unchanged, only the redundancy goes.
        lcp = 0
        if b > 1 and self._prefix_share_min:
            first = self.streams[0].prompt
            lcp = min(len(s.prompt) for s in self.streams) - 1
            for i in range(lcp):
                if any(s.prompt[i] != first[i] for s in self.streams):
                    lcp = i
                    break
            if lcp < self._prefix_share_min:
                lcp = 0

        # shared prompt bucket; per-stream true positions (remainder-
        # relative when a prefix is shared). The remainder bucket is capped
        # at the room left above the prefix: a write at offset lcp must
        # never extend past max_seq, or the clamped dynamic_update_slice
        # would silently overwrite committed prefix KV (the same failure
        # the admit_chunk divisibility check prevents on the admission
        # path). The cap still covers every remainder (n_max < max_seq).
        n_max = max(len(s.prompt) for s in self.streams)
        t_pad = min(_bucket(n_max - lcp, self.max_seq), self.max_seq - lcp)
        if self.plan.sp > 1 and lcp == 0 and t_pad % self.plan.sp:
            # sp prefill shards the bucket over the ring: round up to a
            # multiple of sp (junk slots stay beyond every frontier). The
            # shared-prefix remainder path (lcp > 0) runs chunk-replicated
            # over sp instead — no divisibility requirement, and rounding
            # up could push the bucket past max_seq - lcp.
            t_pad = min(-(-t_pad // self.plan.sp) * self.plan.sp,
                        self.max_seq)
        tokens = np.zeros((b, t_pad), np.int32)
        last = np.zeros((b,), np.int32)
        for i, s in enumerate(self.streams):
            rem = s.prompt[lcp:]
            tokens[i, : len(rem)] = rem
            last[i] = len(rem) - 1
        self._pos = np.asarray([len(s.prompt) for s in self.streams], np.int32)

        # per-stream keys + histories seeded with each prompt's tail
        keys = [
            jax.random.fold_in(self._base_key, max(s.stream_id, 0))
            for s in self.streams
        ]
        self._keys = jnp.stack(keys)  # [B, 2] uint32
        n_hist = self.settings.repeat_last_n
        hist = np.full((b, n_hist), -1, np.int32)
        slots = np.zeros((b,), np.int32)
        for i, s in enumerate(self.streams):
            tail = s.prompt[-n_hist:]
            hist[i, : len(tail)] = tail
            slots[i] = len(tail)
        self._history = jnp.asarray(hist)
        self._hist_slot = jnp.asarray(slots)

        self._n_decode_dispatches = 0
        self._n_admit_dispatches = 0
        self._n_emitted = 0
        self._busy_s = 0.0
        self._t_start = time.perf_counter()
        if lcp:
            # broadcast of the staged prefix row IS the batch cache
            self._prefill_shared_prefix(first[:lcp], b)
            logits, self.cache = self._prefill_offset(
                self.params, jnp.asarray(tokens), self.cache,
                jnp.asarray(last), jnp.int32(lcp),
            )
        else:
            self.cache = init_cache_on_mesh(
                self.config, self.plan.mesh, batch=b, max_seq=self.max_seq,
                quant=self.kv_quant,
            )
            logits, self.cache = self._pick_prefill(tokens.shape[1])(
                self.params, jnp.asarray(tokens), self.cache,
                jnp.asarray(last)
            )

        # what the cache really holds (any family), read off the buffers
        # that were allocated: all of it, and for one token of one layer
        held = sum(x.nbytes for x in jax.tree.leaves(self.cache))
        state = sum(x.nbytes for x in jax.tree.leaves(
            (self.cache.state, self.cache.conv)))
        rings = sum(x.nbytes for x in jax.tree.leaves(
            (self.cache.ring_k, self.cache.ring_v)))
        obs_metrics.gauge("cache.bytes").set(held)
        on_device = _device_bytes(self.cache)
        if on_device is not None:
            obs_metrics.gauge("cache.device_bytes").set(on_device)
        # (a sparse attention's index keys lie beside the rows: counted in
        # cache.token_bytes and in cache.index_row_bytes, not here)
        index = 0 if self.cache.index is None else self.cache.index.nbytes
        summaries = sum(x.nbytes for x in jax.tree.leaves(
            (self.cache.sum_k, self.cache.sum_v)))
        # (a model none of whose layers holds a row a position has no
        # layer of rows: 0)
        obs_metrics.gauge("cache.row_bytes").set(
            (held - state - rings - index - summaries)
            / max(1, self.cache.num_layers * self.cache.batch
                  * self.cache.max_seq))
        if summaries:
            # EVA layers: a window's rows and the summary rows a stream
            # holds, a layer
            obs_metrics.gauge("cache.eva_window_rows").set(
                self.cache.ring_k.shape[3])
            obs_metrics.gauge("cache.eva_summary_rows").set(
                self.cache.sum_k.shape[3])
        elif rings:
            # window layers beside full ones: what the row buffers of both
            # kinds hold, and what they would were every window layer a
            # full one at the capacity
            n_ring = self.cache.ring_k.shape[0]
            obs_metrics.gauge("cache.ring_rows").set(
                self.cache.ring_k.shape[3])
            obs_metrics.gauge("cache.rows_bytes").set(held - state)
            obs_metrics.gauge("cache.rows_bytes_full").set(
                (held - state - rings)
                * (1 + n_ring / self.cache.num_layers))
            obs_metrics.gauge("attn.layers_swa").set(n_ring)
            obs_metrics.gauge("attn.layers_full").set(self.cache.num_layers)
        if index:
            obs_metrics.gauge("cache.index_row_bytes").set(
                index / (self.cache.index.shape[0] * self.cache.batch
                         * self.cache.max_seq))
            obs_metrics.gauge("dsa.index_topk").set(self.config.index_topk)
        obs_metrics.gauge("cache.state_bytes").set(state)
        obs_metrics.gauge("cache.state_bytes_per_stream").set(
            state / self.cache.batch)
        # what the row buffers hold for one token of one stream, every
        # plane of it, and how the planes come about
        obs_metrics.gauge("cache.token_bytes").set(
            (held - state - rings) / (self.cache.batch * self.cache.max_seq))
        obs_metrics.gauge("cache.layer_planes").set(self.cache.num_layers)
        obs_metrics.gauge("model.loop_passes").set(
            self.config.total_ut_steps)
        # what one token's residual state holds between sub-layers
        obs_metrics.gauge("model.hc_mult").set(self.config.hc_mult)
        obs_metrics.gauge("resid.token_bytes").set(
            self.config.resid_token_bytes)
        # first token per stream: fold_in(stream_key, 0) — the same absolute
        # token-index schedule the in-program decode steps continue
        keys0 = jax.vmap(lambda k: jax.random.fold_in(k, 0))(self._keys)
        toks = sampling.sample_tokens_keyed(
            logits, keys0, self._history, self.settings,
            mask=self._first_mask(b),
        )
        self._first_lp = None
        if self.logprobs_k:
            lpv, lpi = sampling.topk_logprobs(logits, self.logprobs_k)
            self._first_lp = (self._host(lpv), self._host(lpi))
        self._history, self._hist_slot = sampling.push_history_batched(
            self._history, self._hist_slot, toks
        )
        (self._keys, self._history, self._hist_slot,
         self._last_tokens) = jax.device_put(
            (self._keys, self._history, self._hist_slot,
             toks.astype(jnp.int32)), self._state_shardings)
        # per-stream absolute token index of the NEXT token (per-row so a
        # stream admitted later starts its own schedule at 1)
        self._index = np.ones((b,), np.int32)
        self._emitted_first = False
        self._spec_bank = [[] for _ in self.streams]
        self._spec_ctx = None  # fresh prompts: device ctx rows are stale
        self._spec_ctx_pos = None
        # rows already recorded against their streams (a landed block's,
        # all at once; an admission's first token) but not yet handed to
        # a step() caller, which is when a token is counted and gets its
        # text
        self._pending_rows: list[list[Token | None]] = []
        self._landed = []  # ... and so is a landing that waited for them
        self._inflight = None  # any prior in-flight block is stale now
        self._fetch_moe_counts()  # a landed block's counts stay counted
        self._moe_pending.clear()  # ... the stale block's go with it
        self._landed_at = self._period_from = None
        # (device value, what it holds) of the frontiers and the token
        # indices the last block program returned: _carried
        self._carry: list = [None, None]
        if self._paged:
            # hand the freshly prefilled contiguous cache to the pool:
            # from here on self.cache IS the page array and every decode
            # dispatch addresses it through the per-stream page tables
            self._contiguous_cache = self.cache
            self._pageify_batch(
                lcp, self.streams[0].prompt[:lcp] if lcp else [])
        # warm_admission ran before this set_prompts: its buckets' landing,
        # and their launches of several rows, need the batch state that
        # only now exists
        pending, self._warm_pending = self._warm_pending, []
        for chunk in pending:
            self._warm_landing()
            self._warm_bucket(chunk)

    def _live(self) -> list[bool]:
        """Which slots the device's next program serves: a slot whose
        stream runs, and a slot a landing has spliced an arrival into
        whose stream is not installed yet (``_landed``). Between a
        landing's halves ``self.streams[slot]`` is still the stream whose
        recorded rows are going out: it says whose token a row holds,
        this says what a dispatch reads (the frontiers, the block size,
        the counts) and which slots are taken."""
        live = [s.active and not s.done for s in self.streams]
        for ld in self._landed:
            for m in ld.members:
                live[m.slot] = True
        return live

    def _free_slots(self):
        return (i for i, live in enumerate(self._live()) if not live)

    def _free_slot(self) -> int | None:
        return next(self._free_slots(), None)

    def enqueue(self, prompt, stream_id: int, guide=None) -> None:
        """Queue a prompt for continuous admission. Each subsequent
        ``step()`` advances its prefill by ONE chunk dispatch (a single
        replicated row into a staging cache) alongside the running batch's
        decode dispatch — arrivals never stall the batch for a full prompt
        pass. When the prefill completes, the stream's first token is
        emitted in that step's row and the stream joins the batch.
        Arrivals that wait together are admitted by one prefill program
        (``_start_arrival``), a row each. For a model without routed
        experts the output is bit-identical to the same (seed, stream_id,
        prompt) in any other batch or admission timing (per-row positions
        + per-row token indices; rows of a program do not see each
        other). An expert model's block takes the form its call's rows
        select (``ops.moe.expert_form``), across admission buckets and
        across launches of one and of several rows alike: the same
        product in another order of summation, so logits equal up to
        that order and, in the tests, the same tokens under greedy.
        Composes with ``sp > 1`` (r5): the staged row's chunks
        run replicated over sp against the sequence-sharded staging cache
        (owner-masked range writes + the chunk attend,
        pipeline.build_admit_prefill). ``guide`` (a constrain.Guide)
        attaches grammar-constrained decoding to the stream: its mask
        applies from the admission's first sampled token on. Guide
        compatibility is checked HERE (a serve scheduler turns the
        ValueError into a 400) rather than at attach time on the engine
        thread (where it would read as an engine fault)."""
        self._domain_stamp.check("BatchGenerator.enqueue")
        self._check_guide_ok(guide)
        self._arrivals.append((self._encode(prompt), stream_id, guide, None,
                               time.perf_counter()))

    @property
    def paged(self) -> bool:
        """Paged KV layout (the disagg plane's capability gate: KV moves
        between engines as pool pages)."""
        return self._paged

    def pending_admissions(self) -> int:
        """Arrivals not yet fully admitted (queued + in-flight + spliced
        with their stream still to be installed)."""
        staged = self._staging["members"] if self._staging else ()
        return (len(self._arrivals) + len(staged)
                + sum(len(ld.members) for ld in self._landed))

    def _store_prefix(self, ids: list[int], row) -> None:
        """Slot layout: insert a staged batch-1 KV row under its token
        prefix, LRU-capped at ``prefix_cache_entries`` rows (the
        eviction policy lives in :class:`cake_tpu.kvpool.PrefixLRU`)."""
        if self._prefix_entries <= 0 or len(ids) < self._prefix_share_min:
            return
        self._prefix_store.put(tuple(ids), row)

    def _match_prefix(self, ids: list[int]):
        """Slot layout: longest stored prefix STRICTLY shorter than the
        prompt (at least one remainder token must produce the first-token
        logits). Returns ``(base, row)``; a hit becomes LRU-most-recent."""
        return self._prefix_store.match(ids)

    # -- paged KV layout (cake_tpu/kvpool) -----------------------------------
    def _init_pool(self, b: int) -> None:
        """(Re)build the page pool for a ``b``-row batch: the device page
        array, the host free-list/refcounts, and a fresh prefix tree.
        Sizing guarantees mid-decode allocation can NEVER fail: with
        ``pages >= b * pages_per_stream + 1`` (sink included), live
        streams can all fill their windows and the only other claims —
        prefix-tree nodes — are evictable."""
        ps = self._page_size
        need = b * self._ppp + 1
        pages = self._pool_pages_req
        if pages is None:
            want = need + 2 * self._ppp  # headroom: tree-held warm prefixes
            pages = 1 << (want - 1).bit_length()
        if pages < need:
            raise ValueError(
                f"kv_pool_pages {pages} < {need} required for batch {b} x "
                f"{self._ppp} pages/stream + sink: a live batch could "
                "exhaust the pool mid-decode")
        self._pagepool = PagePool(pages, ps)
        # the pool shares its engine's domain stamp: page claims are
        # engine-thread mutations wherever they happen
        self._pagepool._domain_stamp = self._domain_stamp
        self._prefix_tree = PrefixTree(self._pagepool)
        self._tables = [[] for _ in range(b)]
        self._page_map_dev = None
        self.cache = kvpool_pool.init_pool_on_mesh(
            self.config, self.plan.mesh, pages, ps, self.kv_quant)
        mesh = self.plan.mesh
        self._row_gather = kvpool_pool.row_gather_prog(
            self.config, mesh, self.kv_quant)
        self._row_scatter = kvpool_pool.row_scatter_prog(
            self.config, mesh, self.kv_quant)
        self._batch_scatter = kvpool_pool.batch_scatter_prog(
            self.config, mesh, self.kv_quant)

    def _alloc_page(self) -> int:
        """One free page, evicting prefix-tree claims under pressure (the
        tree is a cache; live streams are not)."""
        try:
            return self._pagepool.alloc()
        except PoolExhausted:
            if self._prefix_tree.evict_until_free(1):
                return self._pagepool.alloc()
            raise

    def _release_pages(self, slot: int) -> None:
        """Retire a slot's page claims — the whole KV free is this loop
        over a host list (pages shared with the prefix tree or other
        streams survive until their last reference drops)."""
        if not self._paged or slot >= len(self._tables):
            return
        if self._tables[slot]:
            self._page_map_dev = None
        for pid in self._tables[slot]:
            self._pagepool.unref(pid)
        self._tables[slot] = []

    def _ensure_pages(self, size: int) -> None:
        """Grow each live stream's page table to cover the ``size``
        positions this dispatch writes — the one allocation point of the
        steady-state decode path (a handful of list appends per page
        boundary crossed; no device work)."""
        ps = self._page_size
        for i, live in enumerate(self._live()):
            if not live:
                continue
            t = self._tables[i]
            last = min(int(self._pos[i]) + size - 1, self.max_seq - 1) // ps
            while len(t) <= last:
                t.append(self._alloc_page())
                self._page_map_dev = None

    def _page_map_np(self) -> np.ndarray:
        """[B, pages_per_stream] logical->physical map, sink-padded past
        each stream's allocated frontier."""
        m = np.full((len(self.streams), self._ppp), SINK, np.int32)
        for i, t in enumerate(self._tables):
            if t:
                m[i, : len(t)] = t
        return m

    def _scatter_ids_np(self, size: int) -> np.ndarray:
        """[B, W] physical pages receiving this dispatch's KV writes:
        the pages covering ``[pos, pos+size)`` per live row, the sink for
        retired/dummy rows and in-page overrun slots (their writes are
        discarded garbage either way — same invariant as the slot
        layout's clamped overrun writes)."""
        ps = self._page_size
        w = kvpool_pool.writeback_width(size, ps, self._ppp)
        ids = np.full((len(self.streams), w), SINK, np.int32)
        for i, live in enumerate(self._live()):
            if not live:
                continue
            t = self._tables[i]
            pos = int(self._pos[i])
            first = min(pos // ps, self._ppp - w)
            last = min(pos + size - 1, self.max_seq - 1) // ps
            for j in range(w):
                p = first + j
                if first + j <= last and p < len(t):
                    ids[i, j] = t[p]
        return ids

    def _paged_args(self, size: int) -> tuple:
        """The two extra decode operands of the paged layout (empty in
        slot mode, so dispatch sites splat unconditionally). Allocates
        the pages the dispatch will write first. The page map re-uploads
        only when a table actually changed (admission, retirement, page
        growth) — steady-state dispatches reuse the device array; the
        tiny [B, W] scatter-id vector is genuinely per-dispatch."""
        if not self._paged:
            return ()
        # "pages" nests inside "dispatch" (host prep on the dispatch path)
        with self._prof.phase("pages"):
            self._ensure_pages(size)
            if self._page_map_dev is None:
                self._page_map_dev = jnp.asarray(self._page_map_np())
            return (self._page_map_dev,
                    jnp.asarray(self._scatter_ids_np(size)))

    def _paged_args_warm(self, size: int) -> tuple:
        """Warm-path variant: current page map, all-sink write-back (the
        warm dispatch must not allocate pages or touch live content)."""
        if not self._paged:
            return ()
        w = kvpool_pool.writeback_width(size, self._page_size, self._ppp)
        return (jnp.asarray(self._page_map_np()),
                jnp.zeros((len(self.streams), w), jnp.int32))

    def _pageify_batch(self, lcp: int, prefix_ids: list[int]) -> None:
        """Move a freshly prefilled contiguous batch cache into pool
        pages (set_prompts only — every later admission writes pages
        directly). Full pages of a shared prefix become ONE physical copy
        referenced by every stream + the prefix tree; each stream's
        unaligned boundary page (prefix tail + its own remainder) is a
        private copy-on-write materialization."""
        ps = self._page_size
        b = len(self.streams)
        self._init_pool(b)
        pool, contiguous = self.cache, self._contiguous_cache
        n_full = lcp // ps
        shared: list[int] = []
        if n_full:
            _, staging = self._staged_prefix
            ids_vec = np.zeros((self._ppp,), np.int32)
            shared = [self._pagepool.alloc() for _ in range(n_full)]
            # the pages are held only by this local until the per-stream
            # tables take their refs below — release them on the error
            # path (cakelint CK-CLAIM: the scatter dispatch can raise,
            # and stranded alloc claims would pin pool pages forever)
            try:
                ids_vec[:n_full] = shared
                pool = self._row_scatter(pool, staging,
                                         jnp.asarray(ids_vec))
                if self._prefix_entries > 0:
                    # register for future ADMISSION reuse only when the
                    # prefix cache is enabled (0 disables it, same
                    # contract as the slot store) — the batch itself
                    # still shares the physical pages either way, and
                    # without the tree claim they free when the last
                    # sharer retires
                    self._prefix_tree.insert(prefix_ids[: n_full * ps],
                                             shared)
            except BaseException:
                for pid in shared:
                    self._pagepool.unref(pid)
                raise
        self._staged_prefix = None
        ids = np.zeros((b * self._ppp,), np.int32)
        cow = 0
        for i, s in enumerate(self.streams):
            if not s.active:
                continue
            for pid in shared:
                self._pagepool.ref(pid)
            t = list(shared)
            last_page = (len(s.prompt) - 1) // ps
            for p in range(n_full, last_page + 1):
                pid = self._alloc_page()
                t.append(pid)
                ids[i * self._ppp + p] = pid
            if lcp % ps and last_page >= n_full:
                cow += 1  # boundary page: private copy of shared tail
            self._tables[i] = t
        for pid in shared:
            self._pagepool.unref(pid)  # hand the alloc claim off
        if cow:
            self._pagepool.count_cow(cow)
        self.cache = self._batch_scatter(pool, contiguous, jnp.asarray(ids))
        self._contiguous_cache = None

    def _splice_small_fn(self):
        """The paged admission splice: only the per-stream sampler state
        (keys/history/ring slots/feedback token) splices — KV moved by
        the page write-back (``row_scatter``), never by a cache-sized
        scatter. Slot index traced; compiles once; the four state arrays
        are donated (``build_splice``)."""
        if self.__splice_small is None:
            self.__splice_small = build_splice(self._state_shardings,
                                               paged=True)
        return self.__splice_small

    # -- KV-page export/import (cake_tpu/disagg) -----------------------------
    def _disagg_fingerprint(self) -> dict:
        """Geometry a snapshot must match to land in this engine's pool
        (the import-side twin of the worker handshake's max_seq check)."""
        cfg = self.config
        return {
            "layers": cfg.cache_plan["rows"][0],  # the cache's depth
            "kv_heads": cfg.num_key_value_heads,
            "head_dim": cfg.head_dim,
            "dtype": str(cfg.dtype),
            "kv_quant": self.kv_quant,
            "page_size": self._page_size,
            "max_seq": self.max_seq,
            "vocab": cfg.vocab_size,
            "repeat_last_n": self.settings.repeat_last_n,
        }

    def _require_paged(self, what: str) -> None:
        if not self._paged:
            raise ValueError(
                f"{what} needs kv_layout='paged': KV moves between "
                "engines as pool pages (construct with kv_layout='paged' "
                "/ --kv-layout paged)")

    def export_stream(self, stream_id: int, codec: str = "none",
                      trace: dict | None = None) -> bytes:
        """Snapshot a LIVE stream's KV pages + sampler/cursor state into
        versioned, self-describing bytes (cake_tpu/disagg/snapshot) —
        the suspend half of session suspend/resume and the payload the
        prefill tier ships to a decode replica. Engine-thread only.

        Buffered device rows are emitted first (the snapshot must
        reflect the emitted state, not a mid-block one); the stream
        itself keeps running — callers that hand the stream off call
        ``finish(stream_id)`` after. Pages are PINNED for the gather
        (kvpool pin/unpin: a claim outside stream tables and the prefix
        tree), so nothing — not an eviction storm, not the stream
        retiring mid-call — can free one mid-export. ``codec`` rides
        each page through the wire activation codec (``--wire-codec``);
        round trips are bit-identical whenever the codec is lossless for
        the cache dtype (none always; bf16 on a bf16 cache; int8 on an
        int8-quantized pool). ``trace`` (an ``obs.reqtrace`` wire dict)
        rides the snapshot's JSON metadata so the importing tier joins
        the request's trace."""
        from cake_tpu.disagg import snapshot as _snapshot

        self._domain_stamp.check("BatchGenerator.export_stream")
        self._require_paged("export_stream")
        self._drain_buffered_rows()
        slot = next(
            (i for i, s in enumerate(self.streams)
             if s.active and not s.done and s.stream_id == stream_id),
            None)
        if slot is None:
            raise ValueError(f"no live stream with id {stream_id}")
        s = self.streams[slot]
        ps = self._page_size
        n_kv = int(self._pos[slot])
        n_pages = (n_kv - 1) // ps + 1
        table = self._tables[slot][:n_pages]
        guide = self._guides.get(slot)
        guide_spec = getattr(guide, "spec", None) if guide else None
        if guide is not None and guide_spec is None:
            raise ValueError(
                "cannot export a constrained stream whose Guide carries "
                "no grammar spec (build it via constrain.guide_for, or "
                "Guide(dfa, spec=...)) — the importer must recompile "
                "the DFA to resume the cursor")
        import uuid

        for pid in table:
            self._pagepool.pin(pid)
        try:
            ids_vec = np.zeros((self._ppp,), np.int32)
            ids_vec[:n_pages] = table
            staging = self._row_gather(self.cache, jnp.asarray(ids_vec))
            host = jax.tree.map(np.asarray, staging)
        finally:
            for pid in table:
                self._pagepool.unpin(pid)
        pages = []
        for j in range(n_pages):
            lo, hi = j * ps, (j + 1) * ps
            if self.kv_quant == "int8":
                pages.append({
                    "kq": host.k.q[:, 0, :, lo:hi],
                    "ks": host.k.scale[:, 0, :, lo:hi],
                    "vq": host.v.q[:, 0, :, lo:hi],
                    "vs": host.v.scale[:, 0, :, lo:hi],
                })
            else:
                pages.append({"k": host.k[:, 0, :, lo:hi],
                              "v": host.v[:, 0, :, lo:hi]})
        data = _snapshot.encode_snapshot(
            xfer_id=uuid.uuid4().hex,
            fingerprint=self._disagg_fingerprint(),
            codec=codec,
            stream_id=s.stream_id,
            prompt=s.prompt,
            generated=s.generated,
            pos=n_kv,
            index=int(self._index[slot]),
            last_token=int(self._last_tokens[slot]),
            key=np.asarray(self._keys[slot]),
            history=np.asarray(self._history[slot]),
            hist_slot=int(self._hist_slot[slot]),
            guide_spec=guide_spec,
            guide_state=guide.state if guide is not None else 0,
            pages=pages,
            trace=trace,
        )
        # the original stream id rides along so a same-seed resume can
        # keep the identity (the raw key above is what bit-identity
        # actually needs — it survives differing seeds/sids)
        _EXPORTS.inc()
        return data

    def import_begin(self, data) -> dict:
        """Parse + register an inbound snapshot (engine-thread only).
        Validation — magic/version/layout, model fingerprint — happens
        HERE, so a transfer listener can ACK/REJECT before the pages
        land; the pool work itself queues as an arrival in the SAME FIFO
        as prompt admissions (pool pressure defers it FIFO-fair, never
        drops it). Idempotent by transfer id: a duplicate send (retry
        after a lost ACK) returns the existing registration. Returns the
        resume metadata ``{"xfer_id", "stream_id", "prompt",
        "generated", "texts", "n_kv"}`` (``texts`` = the incremental
        detok replay of the generated tokens, what a serve session
        replays to its client)."""
        from cake_tpu.disagg import snapshot as _snapshot

        self._domain_stamp.check("BatchGenerator.import_begin")
        self._require_paged("import_begin")
        if not self.streams:
            raise RuntimeError("set_prompts first")
        snap = _snapshot.decode_snapshot(data)
        if snap.xfer_id in self._imports:
            return self._imports[snap.xfer_id]["meta"]
        snap.check_fingerprint(self._disagg_fingerprint())
        ps = self._page_size
        if snap.n_pages != (snap.pos - 1) // ps + 1:
            raise _snapshot.SnapshotError(
                f"snapshot carries {snap.n_pages} pages for pos "
                f"{snap.pos} at page_size {ps}")
        if not 0 < snap.pos < self.max_seq:
            raise _snapshot.SnapshotError(
                f"snapshot pos {snap.pos} outside (0, {self.max_seq}) — "
                "only live streams export")
        shapes = self._page_shapes()
        for page in snap.pages:
            for k, want in shapes.items():
                got = page.get(k)
                if got is None or got.shape != want[0] \
                        or got.dtype != want[1]:
                    raise _snapshot.SnapshotError(
                        f"page tensor {k!r} is "
                        f"{None if got is None else (got.shape, got.dtype)}"
                        f", expected {want}")
        if snap.guide_spec is not None and self.tokenizer is None:
            raise _snapshot.SnapshotError(
                "snapshot carries a constrained-decoding cursor but this "
                "engine has no tokenizer to recompile its grammar")
        detok = TokenOutputStream(self.tokenizer) if self.tokenizer \
            else None
        texts = [detok.next_token(t) if detok is not None else None
                 for t in snap.generated]
        meta = {
            "xfer_id": snap.xfer_id,
            "stream_id": snap.stream_id,
            "prompt": list(snap.prompt),
            "generated": list(snap.generated),
            "texts": texts,
            "n_kv": snap.pos,
        }
        if snap.trace:
            # the exporter's request-trace context (obs/reqtrace) —
            # surfaced so the scheduler can land a disagg.import span in
            # the same causal tree
            meta["trace"] = snap.trace
        self._imports[snap.xfer_id] = {
            "snap": snap, "pages": None, "detok": detok, "meta": meta,
            "deferred": False, "t": time.monotonic(),
        }
        self._arrivals.append((snap.xfer_id, None, None, _ARR_IMPORT, None))
        return meta

    def _page_shapes(self) -> dict:
        """Expected (shape, dtype) per page tensor for this geometry."""
        cfg = self.config
        L, KH, D = (cfg.cache_plan["rows"][0], cfg.num_key_value_heads,
                    cfg.head_dim)
        ps = self._page_size
        if self.kv_quant == "int8":
            return {
                "kq": ((L, KH, ps, D), np.dtype(np.int8)),
                "ks": ((L, KH, ps), np.dtype(np.float32)),
                "vq": ((L, KH, ps, D), np.dtype(np.int8)),
                "vs": ((L, KH, ps), np.dtype(np.float32)),
            }
        dt = np.dtype(cfg.jax_dtype)
        return {"k": ((L, KH, ps, D), dt), "v": ((L, KH, ps, D), dt)}

    def _import_begin_tick(self) -> None:
        """Head-of-queue import: land its pages in the pool, or defer
        FIFO-fair under pool pressure (the arrival stays at the head,
        re-priced next tick — same discipline as a prompt admission)."""
        xid = self._arrivals[0][0]
        rec = self._imports.get(xid)
        if rec is None:  # aborted while queued
            self._arrivals.pop(0)
            return
        snap = rec["snap"]
        need = snap.n_pages
        if (self._pagepool.free_count < need
                and not self._prefix_tree.evict_until_free(need)):
            if not rec["deferred"]:
                rec["deferred"] = True
                self._pagepool.count_defer()
            self._admit_deferred = True
            return
        self._admit_deferred = False
        self._arrivals.pop(0)
        staging = self._import_staging(snap)
        pages = []
        for _ in range(need):
            pid = self._alloc_page()
            # reclassify the alloc claim as a transfer PIN: until a
            # stream attaches (or the import aborts), these pages are
            # held by neither a stream table nor the prefix tree, and
            # must still survive any eviction storm
            self._pagepool.pin(pid)
            self._pagepool.unref(pid)
            pages.append(pid)
        # the import record owns the pins from HERE (cakelint CK-CLAIM):
        # if the scatter dispatch below raises, import_abort / the TTL
        # sweep can still unpin — pins held only by the local would leak
        # forever
        rec["pages"] = pages
        ids_vec = np.zeros((self._ppp,), np.int32)
        ids_vec[:need] = pages
        self.cache = self._row_scatter(self.cache, staging,
                                       jnp.asarray(ids_vec))
        _IMPORTS.inc()

    def _import_staging(self, snap) -> object:
        """Snapshot pages -> the batch-1 staging cache ``row_scatter``
        scatters from (host assembly + one upload; positions past the
        snapshot's pages stay zero — beyond the resumed frontier, never
        attendable)."""
        from cake_tpu.ops.kvcache import KVCache, QuantizedKV

        cfg = self.config
        L, KH, D = (cfg.cache_plan["rows"][0], cfg.num_key_value_heads,
                    cfg.head_dim)
        S, ps = self.max_seq, self._page_size
        if self.kv_quant == "int8":
            bufs = {"kq": np.zeros((L, 1, KH, S, D), np.int8),
                    "ks": np.zeros((L, 1, KH, S), np.float32),
                    "vq": np.zeros((L, 1, KH, S, D), np.int8),
                    "vs": np.zeros((L, 1, KH, S), np.float32)}
        else:
            dt = np.dtype(cfg.jax_dtype)
            bufs = {"k": np.zeros((L, 1, KH, S, D), dt),
                    "v": np.zeros((L, 1, KH, S, D), dt)}
        for j, page in enumerate(snap.pages):
            lo, hi = j * ps, (j + 1) * ps
            for k, arr in page.items():
                bufs[k][:, 0, :, lo:hi] = arr
        if self.kv_quant == "int8":
            return KVCache(
                k=QuantizedKV(q=jnp.asarray(bufs["kq"]),
                              scale=jnp.asarray(bufs["ks"])),
                v=QuantizedKV(q=jnp.asarray(bufs["vq"]),
                              scale=jnp.asarray(bufs["vs"])))
        return KVCache(k=jnp.asarray(bufs["k"]), v=jnp.asarray(bufs["v"]))

    def import_attach(self, xfer_id: str, stream_id: int) -> None:
        """Queue the attach of a begun import: when it reaches the FIFO
        head with a free slot, the imported pages become the stream's
        table (page-table edit — ref then unpin, no cache tensor moves)
        and its sampler/cursor state splices in. Decode then continues
        bit-identically to the exporting engine's next step."""
        self._domain_stamp.check("BatchGenerator.import_attach")
        self._require_paged("import_attach")
        if xfer_id not in self._imports:
            raise KeyError(f"unknown or expired transfer {xfer_id!r}")
        self._arrivals.append(
            (xfer_id, stream_id, None, _ARR_ATTACH, None))

    def _import_attach_tick(self) -> None:
        xid, sid = self._arrivals.pop(0)[:2]
        rec = self._imports.pop(xid, None)
        if rec is None or rec["pages"] is None:
            # aborted/expired between queue and tick (rec["pages"] is
            # None only if the begin was aborted while queued — FIFO
            # order guarantees the begin tick ran before this one)
            if rec is not None:
                _IMPORT_ABORTS.inc()
            self._attach_failures.append(sid)
            return
        snap, pages = rec["snap"], rec["pages"]
        slot = self._free_slot()
        self._release_pages(slot)
        # rows computed under the slot's previous meaning are recorded
        # before the attach changes it (same rule as the admission splice)
        self._drain_buffered_rows()
        for pid in pages:
            self._pagepool.ref(pid)    # the stream table's claim...
            self._pagepool.unpin(pid)  # ...replaces the transfer pin
        self._tables[slot] = list(pages)
        self._page_map_dev = None
        (self._keys, self._history, self._hist_slot,
         self._last_tokens) = self._splice_small_fn()(
            self._keys, self._history, self._hist_slot,
            self._last_tokens, jnp.asarray([snap.key], jnp.uint32),
            jnp.asarray([snap.history], jnp.int32),
            # the program pushes the token at ``used``: one back, it
            # rewrites the ring's newest entry, which IS the last token
            # (every program that samples one pushes it)
            jnp.asarray([snap.hist_slot - 1], jnp.int32),
            jnp.asarray([snap.last_token], jnp.int32),
            jnp.asarray([slot], jnp.int32),
        )
        self._pos = np.asarray(self._pos).copy()
        self._pos[slot] = snap.pos
        self._index = np.asarray(self._index).copy()
        self._index[slot] = snap.index
        s = _Stream(stream_id=sid, prompt=list(snap.prompt),
                    detok=rec["detok"])
        s.generated = list(snap.generated)
        s.handed = len(s.generated)  # its caller replays them itself
        self.streams[slot] = s
        self._drop_guide(slot)
        if snap.guide_spec is not None:
            from cake_tpu.constrain.guide import guide_for

            g = guide_for(snap.guide_spec, self.tokenizer, self.config)
            self._attach_guide(slot, g)  # resets the cursor...
            g.state = snap.guide_state   # ...then resume mid-grammar
        _RESUMES.inc()

    def import_abort(self, xfer_id: str) -> bool:
        """Drop a begun import and release its page pins (resume never
        came — gateway died, TTL expired, client cancelled). Returns
        False when the id is unknown (already attached or aborted)."""
        self._domain_stamp.check("BatchGenerator.import_abort")
        rec = self._imports.pop(xfer_id, None)
        if rec is None:
            return False
        if rec["pages"] is not None:
            for pid in rec["pages"]:
                self._pagepool.unpin(pid)
        else:
            self._arrivals = [a for a in self._arrivals
                              if not (a[3] == _ARR_IMPORT
                                      and a[0] == xfer_id)]
        _IMPORT_ABORTS.inc()
        return True

    def expire_imports(self, ttl_s: float) -> int:
        """Abort begun-but-unattached imports older than ``ttl_s``; the
        serve scheduler sweeps this so an orphaned transfer cannot pin
        pool pages forever. Returns the number aborted."""
        self._domain_stamp.check("BatchGenerator.expire_imports")
        if not self._imports:
            return 0
        now = time.monotonic()
        expired = [xid for xid, rec in self._imports.items()
                   if now - rec["t"] > ttl_s]
        for xid in expired:
            self.import_abort(xid)
        return len(expired)

    def take_attach_failures(self) -> list[int]:
        """Stream ids whose attach found its import gone (aborted or
        expired) — the serve scheduler fails those sessions with a
        resumable-elsewhere status instead of letting them hang."""
        out, self._attach_failures = self._attach_failures, []
        return out

    def imports_pending(self) -> int:
        """Begun-but-unattached imports (pages pinned or queued) — the
        ``kv_transfers_inflight`` signal /healthz exposes."""
        return len(self._imports)

    def import_stream(self, data, stream_id: int | None = None,
                      ) -> tuple[int, str]:
        """Synchronous import: begin + attach + drive admission ticks to
        completion (the ``admit()`` of the disagg plane — tests and
        single-process suspend/resume). Returns ``(slot, xfer_id)``.
        Raises when the attach cannot complete without outside help (no
        retirable slot, pool exhausted with nothing evictable)."""
        meta = self.import_begin(data)
        xid = meta["xfer_id"]
        sid = meta["stream_id"] if stream_id is None else stream_id
        self.import_attach(xid, sid)

        def ours_pending() -> bool:
            return any(a[3] in (_ARR_IMPORT, _ARR_ATTACH) and a[0] == xid
                       for a in self._arrivals)

        while ours_pending():
            head = self._arrivals[0]
            # admit()'s no-busy-loop rule, FIFO-wide: any head that needs
            # a slot to start (an attach, ours or not, or a queued
            # prompt — everything but a pages-only import admission)
            # blocks the whole queue when every stream is live, so raise
            # instead of spinning on a no-op tick
            if (head[3] != _ARR_IMPORT and self._staging is None
                    and self._free_slot() is None):
                self.import_abort(xid)
                raise RuntimeError(
                    "no free slot: every stream is still live")
            self._admission_tick(wait=False)
            # a pool-deferred head — whoever owns it — can only unblock
            # via retires that never happen inside this synchronous loop
            if self._staging is None and self._admit_deferred:
                self.import_abort(xid)
                raise RuntimeError(
                    "kv page pool exhausted: import deferred (retire "
                    "streams, or grow kv_pool_pages)")
        if sid in self._attach_failures:
            self._attach_failures.remove(sid)
            raise RuntimeError(f"import {xid} was aborted before attach")
        slot = next(i for i, s in enumerate(self.streams)
                    if s.active and not s.done and s.stream_id == sid
                    and s.generated[:len(meta["generated"])]
                    == meta["generated"])
        return slot, xid

    def _admission_chunk_for(self, prompt_len: int) -> int:
        """The per-dispatch admission chunk for a prompt of this length:
        the configured interleave granularity, but never padded past the
        prompt's own bucket. Both bounds keep t_pad <= max_seq (the bucket
        by construction, admit_chunk by the constructor's divisibility
        check)."""
        bucket = _bucket(prompt_len, self.max_seq)
        return min(self._admit_chunk, bucket) if self._admit_chunk else bucket

    def warm_admission(self, prompt_len: int) -> None:
        """Compile the admission-prefill programs (and staging-cache zeros
        programs) for prompts of this length, outside any serving-critical
        window — benchmarks/servers call this once so the first real
        ``enqueue`` does not pay XLA compilation mid-run. The compiled
        shapes depend only on the chunk for ``prompt_len`` and on the
        several-row programs such a prompt can ride in (``GROUP_SHAPES``);
        with prefix sharing active, call again with the expected REMAINDER
        length (arrival length minus the shared prefix), since that is the
        shape a prefix-cache hit dispatches.

        With int8 weights, call AFTER ``set_prompts`` (or pass
        ``quant_backend=`` at construction): the warm trace is permanent
        in the jit cache, so tracing before the instance's backend pin is
        decided would bake the per-shape gate in and silently void the
        determinism contract — enforced below."""
        if self._params_quantized and self._quant_pin is None:
            raise ValueError(
                "warm_admission with int8 weights needs the backend pin "
                "decided first: call set_prompts before warming, or pass "
                "quant_backend= at construction"
            )
        self._warm_bucket(self._admission_chunk_for(prompt_len))

    def _group_shapes(self) -> list[tuple[int, int]]:
        """``GROUP_SHAPES`` as far as this engine can launch them: the
        slot layout, a slot a row, the whole bucket in one dispatch, the
        members' staging rows within ``GROUP_STAGING_BYTES``."""
        if self._paged:
            return []
        return [(r, c) for r, c in GROUP_SHAPES
                if r <= len(self.streams) and self._admission_chunk_for(c) == c
                and r <= self._staging_rows_fit]

    def _warm_bucket(self, chunk: int) -> None:
        """Compile what a launch of ``chunk``-token prompts can dispatch
        and has not yet: the one-row prefill program of that bucket, the
        several-row programs such a prompt can ride in (``_group_shapes``
        whose bucket holds it) and, a row count, the landing (the first
        tokens' sampler, the splice). Outputs are discarded, nothing is
        donated: the live state is untouched. Called where a bucket's
        program compiles anyway (``warm_admission``, a bucket's first
        admission); a launch takes riders only into a program that has
        been compiled here (``_take_riders``), so no launch compiles
        where its one-row program would not have.

        Before set_prompts the batch state (and its B dimension) doesn't
        exist yet, so the rest is deferred to the next set_prompts — never
        silently dropped (the compile would otherwise land inside the
        serving window, the exact stall _splice_fn exists to kill)."""
        live = getattr(self, "cache", None) is not None
        if not live and chunk not in self._warm_pending:
            self._warm_pending.append(chunk)
        for rows, bucket in [(1, chunk)] + [
                shape for shape in self._group_shapes() if chunk <= shape[1]]:
            if (rows, bucket) in self._warmed:
                continue
            self._warmed.add((rows, bucket))
            staging = self._staging_cache(rows)
            logits, staging = self._admit_prefill(
                self.params, jnp.zeros((rows, bucket), jnp.int32), staging,
                jnp.int32(0), jnp.zeros((rows,), jnp.int32),
            )
            if live:
                self._warm_landing(logits, staging)
            else:
                # the sampler needs no batch state: on the program's own
                # logits, as a landing runs it
                self._first_tokens(logits, [0] * rows, np.full(
                    (rows, self.settings.repeat_last_n), -1, np.int32))
            np.asarray(logits.ravel()[:1])  # synchronize

    def _staging_cache(self, rows: int):
        """A zeroed staging cache of ``rows`` rows."""
        return init_cache_on_mesh(
            self.config, self.plan.mesh, batch=rows, max_seq=self.max_seq,
            quant=self.kv_quant, batch_replicated=True,
        )

    def _warm_landing(self, logits=None, staging=None) -> None:
        """Compile the admission-completion programs of a launch of as
        many rows as ``staging`` has (one where None) against the live
        batch state, which is handed back as it was: the splice donates
        the live cache and the sampler state, so the cache's is a splice
        of slot 0's OWN rows into slot 0, kept, and the small state is
        put back from a host copy taken first.
        Slot: the first tokens' sampler, the slot-traced cache splice and,
        with a prefix store, the program that takes one row of several.
        Paged: the row gather/scatter page programs plus the small
        sampler-state splice — the page programs warmed on pool/staging
        COPIES (both donate their first argument) with all-sink ids, so
        no live page is read or written."""
        if staging is None:
            staging = self._staging_cache(1)
        rows = jax.tree.leaves(staging)[0].shape[1]
        if rows in self._landing_warmed:
            return
        self._landing_warmed.add(rows)
        if logits is None:
            logits = jnp.zeros((rows, self.config.vocab_size), jnp.float32)
        n_hist = self.settings.repeat_last_n
        hist = np.full((rows, n_hist), -1, np.int32)
        keys, toks = self._first_tokens(logits, [0] * rows, hist)
        vec = jnp.asarray(np.zeros((rows,), np.int32))
        state = (self._keys, self._history, self._hist_slot,
                 self._last_tokens)
        kept = [np.asarray(x) for x in state]
        vectors = (keys, jnp.asarray(hist), vec, toks, vec)
        if self._paged:
            sink = jnp.zeros((self._ppp,), jnp.int32)
            pool_copy = jax.tree.map(lambda x: x.copy(), self.cache)
            out_pool = self._row_scatter(pool_copy, staging, sink)
            out_row = self._row_gather(self.cache, sink)
            out = self._splice_small_fn()(*state, *vectors)
            jax.block_until_ready((out_pool, out_row, out))
        else:
            if rows > 1 and self._prefix_entries > 0:
                jax.block_until_ready(self._row_of(staging, jnp.int32(0)))
            # slot 0's rows, as a staging cache of this many rows
            own = jax.jit(
                lambda cache: jax.tree.map(
                    lambda c: jnp.repeat(c[:, :1], rows, 1), cache),
                out_shardings=jax.tree.map(lambda x: x.sharding, staging),
            )(self.cache)
            self.cache = self._splice_fn()(
                self.cache, own, *state, *vectors)[0]
            jax.block_until_ready(self.cache)
        (self._keys, self._history, self._hist_slot,
         self._last_tokens) = jax.device_put(
            tuple(kept), self._state_shardings)

    def _admission_due(self) -> bool:
        """Whether step() has admission work this call (so an idle or
        waiting batch does not flood the admit histogram with ~0 ms
        ticks): a landing's host half that the rows have made room for,
        a launched admission that can land (``_can_land``), a chunk to
        dispatch, an arrival to start."""
        if self._host_half_due():
            return True
        st = self._staging
        if st is not None:
            return "logits" not in st or self._can_land(st)
        if not self._arrivals:
            return False
        if self._arrivals[0][3] is None:  # a prompt: launched at once
            return self._free_slot() is not None
        return not self._pending_rows

    def _host_half_due(self) -> bool:
        """Whether the rows recorded before the oldest landing's device
        half are all with the caller: its host half may run."""
        return bool(self._landed) and self._landed[0].due <= self._rows_out

    def _rows_wait(self) -> bool:
        """Whether rows computed before a launched admission are still to
        be handed out (recorded ones, or a block in flight): a landing
        that installs its stream at once lands after them."""
        return bool(self._pending_rows) or self._inflight is not None

    def _lands_before_rows(self, st: dict) -> bool:
        """Whether the staged launch's device half may leave while rows
        recorded before it are still going out, from what the engine
        sees: not where the host acts between the halves' programs (a
        guided arrival's token is fetched before anything follows;
        batched speculation runs rounds), not in the paged layout (the
        landing edits the slot's page table and the pool, which are the
        old stream's until its rows are out), and only where the staging
        rows of every landing whose token has not been fetched, this
        launch's and one more fit side by side (a program's memory is
        taken when it is enqueued)."""
        held = len(st["rows"]) + sum(len(ld.rows) for ld in self._landed)
        return (st["members"][0].guide is None and not self._paged
                and not self._spec_k and self._staging_rows_fit > held)

    def _landing_runs(self) -> bool:
        """Whether the device still has the prefill or the sampler of a
        landing between its halves to run (the newest one's tokens are
        not there yet). The next BLOCK waits for that while the host is
        back in a step() every row: enqueued at once it would hold the
        device for its eight steps, and an arrival that comes meanwhile
        (a client whose answer ended in the rows that are going out)
        would wait behind it, where launched in the block's place its
        prefill follows the landing's own, as it did when a landing
        waited for the rows. The block leaves once the tokens are there
        (``step``) or, at the latest, before the host waits for them
        (``_admission_tick``)."""
        return bool(self._landed) and not self._landed[-1].toks.is_ready()

    def _can_land(self, st: dict) -> bool:
        """Whether the launched admission's landing may start in this
        step(): no block is in flight (the landed block's rows are
        recorded, so every retirement it holds is known) and, where both
        halves go together, every recorded row has been handed out."""
        if self._lands_before_rows(st):
            return self._inflight is None
        return not self._rows_wait()

    def _admission_tick(self, wait: bool = True) -> None:
        """Advance the admission plane by one tick: finish the landings
        whose rows have gone out (``_land_host``), *land* a launched
        admission, *launch* the next queued arrival if a slot is free
        (and with it every arrival that can ride: ``_start_arrival``),
        or dispatch the in-flight admission's next chunk. KV-page imports
        (cake_tpu/disagg) ride the same FIFO: a begin lands the pages in
        the pool (deferring FIFO-fair under pool pressure exactly like a
        prompt admission), an attach installs the resumed stream into a
        free slot -- each one tick, no prefill dispatches.

        Launch and land are apart so that the prefill runs while rows go
        out. Launch matches the prefix, builds the staging row and
        dispatches the prefill; it touches no slot and no
        ``self.streams`` entry, so it may run while ``_pending_rows`` is
        non-empty, and while a block is in flight: the prefill then
        follows that block on the device with no host time between them
        (which program follows the running block is what a decision at
        the boundary would have made it; only the enqueue is earlier).

        A landing (``_finish_admission``) has two halves. The DEVICE
        half -- sample, splice, count the slot live, give the device its
        next program (the next arrival's launch if one can start, else
        the next block) -- needs nothing of the rows that are still going
        out, so it leaves in the first step() in which the logits are
        staged and no block is in flight: at the boundary itself where
        the launch happens there, at the block's landing where the launch
        went out behind the running block. The HOST half -- fetch the
        first token, install the stream, queue its row -- waits until
        every row recorded before the device half has been handed out:
        those rows can hold a stream's EOS, which frees its slot in here
        while the caller -- who maps a row's slots to streams through
        ``self.streams`` when it GETS the row, as ``_hand_out`` does for
        the text -- has not seen those tokens yet; installing then would
        hand the old stream's tail to the new one. Where the halves may
        not be apart (``_lands_before_rows``) the whole landing waits for
        the rows, and for a block in flight. Of the device half's next
        program the next BLOCK alone is held back while the prefill
        still runs (``_landing_runs``): an arrival that comes meanwhile
        is launched in its place, and the block leaves when the tokens
        are there or, at the latest, before the host half of the newest
        landing waits for them.
        ``wait=False`` (the synchronous ``admit()``, whose caller is told
        the slot) lands at once, behind the landings before it."""
        # one host half a tick: its row goes out in this step(), and a
        # later landing's token, whose prefill may still run, is waited
        # for when the rows that are here have left (admit(): all now)
        due = int(self._host_half_due()) if wait else len(self._landed)
        started = False
        if due:
            with self._prof.phase("admit_land"):
                if wait and self._staging is None and len(self._landed) == 1:
                    # the host comes for the newest landing's token: the
                    # device's next program first, if it was held back
                    started = self._launch() or self._enqueue_block()
                for _ in range(due):
                    self._land_next(drop_rows=not wait)
        st = self._staging
        if not started and (st is None or "logits" not in st):
            self._launch(wait)
            st = self._staging
        if (st is not None and "logits" in st
                and (not wait or self._can_land(st))):
            with self._prof.phase("admit_land"):
                self._finish_admission(wait)

    def _launch(self, wait: bool = True) -> bool:
        """The launch half of a tick: start the next queued arrival if a
        slot is free, and dispatch the staged admission's next chunk (the
        last one leaves its logits staged, to land). Whether a program
        was dispatched."""
        if self._staging is None and not self._arrivals:
            return False  # nothing to launch
        with self._prof.phase("admit_launch"):
            if self._staging is None and not self._start_arrival(wait):
                return False
            st = self._staging
            pos, chunk, base = st["pos"], st["chunk"], st["base"]
            final = pos + chunk >= st["tokens"].shape[1]
            t0 = time.perf_counter()
            with span("admit.chunk", pos=base + pos, chunk=chunk):
                logits, st["cache"] = self._admit_prefill(
                    self.params,
                    jnp.asarray(st["tokens"][:, pos: pos + chunk]),
                    st["cache"],
                    jnp.int32(base + pos),
                    # a row: the in-chunk index of its prompt's last
                    # token; in an earlier chunk the chunk's own last
                    # (every token of it is true: what a recurrent state
                    # may be advanced by)
                    jnp.asarray(np.asarray(
                        [min(len(m.ids) - 1 - base - pos, chunk - 1)
                         for m in st["rows"]], np.int32)),
                )
                self._note_enqueued()
                members = st["members"]
                if members[0].stamps is not None and pos == 0:
                    # launched: the first dispatch has returned, from
                    # here on the device has the prompts (a chunked
                    # admission's later chunks go under rows_wait)
                    launched = time.perf_counter()
                    for m in members:
                        m.stamps.append(launched)
                    _ADMIT_LAUNCHES.inc()
                if not final:
                    # sync: busy_s must include compute (the last chunk's
                    # is waited for where it lands)
                    np.asarray(logits.ravel()[:1])
            self._n_admit_dispatches += 1
            rows = len(st["rows"])
            self._count_admit_rows(rows * chunk)
            if self._dsa:
                self._count_dsa_admission(
                    chunk, [min(max(len(m.ids) - base - pos, 0), chunk)
                            for m in st["rows"]])
            if self._latent_planes and base + pos == 0:
                self._count_latent_admission(
                    chunk, [min(len(m.ids), chunk) for m in st["rows"]])
            self._count_delta_chunks(
                chunk, [len(m.ids) - base - pos for m in st["rows"]])
            if self._eva:  # every chunk of the bucket, a row and layer
                _EVA_CHUNKS_ADMIT.inc(
                    self._eva[0] * rows * (chunk // self._eva[2]))
            st["pos"] = pos + chunk
            if not final:
                self._admit_dispatched(t0, chunk, base + pos)
                return True
            st["logits"], st["booking"] = logits, (t0, chunk, base + pos)
            if rows == 1 and st["tokens"].shape[1] == chunk:
                # a whole prompt a dispatch: where this bucket's program
                # has just compiled, so do the several-row programs it
                # can ride in, behind this one on the device
                self._warmed.add((1, chunk))
                self._warm_bucket(chunk)
        return True

    def _count_admit_rows(self, rows: int) -> None:
        """An expert model's admission dispatch of ``rows`` rows (the
        bucket's, times the launch's staging rows): add them to
        ``moe.admit_rows`` and, where the expert block recorded the
        sorted form when a call of that many rows was traced, to
        ``moe.admit_rows_sorted``."""
        if not any(ffn == "moe" for _, ffn in self.config.layer_kinds):
            return
        _MOE_ADMIT_ROWS.inc(rows)
        if moe_form_traced(rows) == "sorted":
            _MOE_ADMIT_SORTED.inc(rows)

    def _count_dsa_admission(self, chunk: int, true: list[int]) -> None:
        """An admission dispatch of ``chunk`` rows a member, ``true`` of
        them each member's prompt tokens, over a model under a learned
        sparse attention: the rows handed and true, and the pairs its
        indexers score and its queries attend at the true lengths."""
        layers, topk = self._dsa
        _DSA_ADMIT_CALLS.inc(layers)
        _DSA_ADMIT_ROWS.inc(len(true) * chunk)
        _DSA_ADMIT_ROWS_TRUE.inc(sum(true))
        for n in true:
            k = min(n, topk)
            _DSA_PAIRS_SCORED.inc(layers * (n * (n + 1) // 2))
            _DSA_PAIRS_ATTENDED.inc(
                layers * (k * (k + 1) // 2 + (n - k) * topk))

    def _count_latent_admission(self, chunk: int, true: list[int]) -> None:
        """A first admission dispatch of ``chunk`` rows a member, ``true`` of
        them each member's prompt tokens, over plain latent planes: where
        the program's own-chunk attention is the blocked form's kernel
        (``ops.mla.latent_admit_choice``, from the shapes), the calls and
        the causal pairs at the true lengths, a plane."""
        c = self.config
        if latent_admit_choice(
                chunk, c.qk_nope_head_dim + c.qk_rope_head_dim) != "flash":
            return
        _LATENT_ADMIT_CALLS.inc(self._latent_planes)
        _LATENT_ADMIT_PAIRS.inc(
            self._latent_planes * sum(n * (n + 1) // 2 for n in true))
        _LATENT_ADMIT_PAIRS_HANDED.inc(
            self._latent_planes * len(true) * (chunk * (chunk + 1) // 2))

    def _count_delta_chunks(self, chunk: int, left: list[int]) -> None:
        """An admission dispatch of ``chunk`` tokens a row over a model
        with delta-rule layers: add the chunks their scans sweep (the
        launch's longest row's, every row: the scan stops at the last
        chunk that holds a true token of some row, ``ops.kda._advance``)
        to ``delta.chunks_swept`` and those that hold a true token
        (``left``: each row's prompt tokens from this dispatch's first
        on) to ``delta.chunks_live``; the swept ones to
        ``delta.chunks_kernel`` too where the scan recorded the kernel
        when a layer of that many tokens was traced."""
        if not self._delta_layers:
            return
        live = [-(-min(max(n, 0), chunk) // CHUNK) for n in left]
        swept = self._delta_layers * len(left) * max(live)
        _DELTA_CHUNKS_SWEPT.inc(swept)
        _DELTA_CHUNKS_LIVE.inc(self._delta_layers * sum(live))
        if delta_form_traced(chunk) == "kernel":
            _DELTA_CHUNKS_KERNEL.inc(swept)

    def _admit_dispatched(self, t0: float, chunk: int, pos: int) -> None:
        """Book one admission chunk whose compute has been waited for."""
        dt = time.perf_counter() - t0
        self._busy_s += dt
        rec = obs_flight.recorder()
        if rec.enabled:
            rec.record(kind="admit", total_ms=round(dt * 1e3, 3),
                       chunk=chunk, pos=pos)

    def _start_arrival(self, wait: bool = True) -> bool:
        """Take the head of the arrival FIFO: an import or an attach runs
        whole and leaves nothing staged (False); a prompt with a free
        slot is *launched*: its staging row built, ``self._staging`` set
        (True). A plain prompt from position 0 takes with it the plain
        prompts that wait behind it (``_take_riders``): one staging cache
        of a row each, one prefill program over all of them, so that the
        weights are read once for the launch and not once an arrival. A
        launch of one is the same program over one row."""
        if not self._arrivals:
            return False
        if not any(self._live()):
            # nobody was decoding until this arrival came (an engine
            # without work is not stepped): the next landing closes no
            # block period
            self._period_from = None
        kind = self._arrivals[0][3]
        if kind in (_ARR_IMPORT, _ARR_ATTACH):
            # these edit the pool and a slot: not under undelivered rows
            if wait and self._pending_rows:
                return False
            if kind == _ARR_IMPORT:
                self._import_begin_tick()
            elif self._free_slot() is not None:
                self._import_attach_tick()
            return False
        slots = list(self._free_slots())
        if not slots:
            return False
        slot = slots.pop(0)
        if self._paged:
            # claim point: the slot's previous stream (retired by ANY
            # path, including a caller writing s.done directly) frees
            # its page claims before the arrival's needs are priced
            self._release_pages(slot)
        ids, sid, guide, _, enqueued = self._arrivals.pop(0)
        # Prefix reuse: an arrival whose opening tokens match a stored
        # prefix (a staged row in the slot layout, a page chain in the
        # paged one) starts from that content and prefills only its
        # remainder — re-prefilling a known prefix is exactly the
        # waste the store exists to kill. Falls back to a from-scratch
        # prefill when the remainder's bucket would not fit above the
        # prefix.
        row = None
        shared_pages: list[int] = []
        if self._paged:
            base = 0
            if self._prefix_entries > 0:
                base, shared_pages = self._prefix_tree.match(ids)
        else:
            base, row = self._match_prefix(ids)
        rem = len(ids) - base
        chunk = self._admission_chunk_for(rem)
        t_pad = -(-rem // chunk) * chunk
        if base and base + t_pad > self.max_seq:
            base, row, shared_pages = 0, None, []
            rem = len(ids)
            chunk = self._admission_chunk_for(rem)
            t_pad = -(-rem // chunk) * chunk
        if self._paged:
            # hold the matched pages BEFORE any eviction can touch
            # them, then price the remainder; when its pages cannot
            # be found even by evicting warm prefixes, the arrival
            # defers (stays FIFO head) until retirements free pages
            for pid in shared_pages:
                self._pagepool.ref(pid)
            ps = self._page_size
            need = (len(ids) - 1) // ps + 1 - len(shared_pages)
            if (self._pagepool.free_count < need
                    and not self._prefix_tree.evict_until_free(need)):
                for pid in shared_pages:
                    self._pagepool.unref(pid)
                if not self._admit_deferred:
                    # count DEFERRED ADMISSIONS, not re-priced ticks
                    # (the head arrival is re-tried every step while
                    # it waits). Unreachable under the enforced pool
                    # sizing — reachable the moment in-flight KV
                    # transfers pin pages outside stream tables
                    # (cake_tpu/disagg imports).
                    self._pagepool.count_defer()
                self._admit_deferred = True
                self._arrivals.insert(0, (ids, sid, guide, None, enqueued))
                return False
            self._admit_deferred = False
        stamps = None if enqueued is None else [enqueued]
        members, n_rows = [_Staged(ids, sid, slot, guide, stamps)], 1
        if not base and t_pad == chunk and guide is None and stamps:
            riders, shape = self._take_riders(members[0], slots)
            if riders:
                members += riders
                n_rows, chunk = shape
                t_pad = chunk
        # the program's rows: the members, then the first again up to the
        # program's row count (the same values into the same slot:
        # nothing tells its rows apart)
        rows = members + members[:1] * (n_rows - len(members))
        tokens = np.zeros((len(rows), t_pad), np.int32)
        for i, m in enumerate(rows):
            tokens[i, :len(m.ids) - base] = m.ids[base:]
        if base:
            self._prefix_hits += 1
            if self._paged:
                # the staging starts as a GATHER of the shared pages
                # (prefix KV the remainder chunks attend), not a copy
                # of a stored row — the pages themselves stay shared
                ids_vec = np.zeros((self._ppp,), np.int32)
                ids_vec[: len(shared_pages)] = shared_pages
                cache = self._row_gather(self.cache,
                                         jnp.asarray(ids_vec))
            else:
                # copy: the admission program donates its cache
                # argument, and the stored row must survive for
                # future hits
                cache = jax.tree.map(lambda x: x.copy(), row)
        else:
            cache = self._staging_cache(len(rows))
            # the zeroed row IS the reset: the splice copies its state
            # and convolution tail over the slot's
            for mixer in set(_STATE_RESETS).intersection(
                    m for m, _ in self.config.layer_kinds):
                _STATE_RESETS[mixer].inc(len(members))
        self._staging = {
            # the arrivals this launch admits, in FIFO order (finish()
            # takes a cancelled one out), and the program's rows
            "members": members, "rows": rows,
            "tokens": tokens, "pos": 0, "chunk": chunk, "base": base,
            "cache": cache, "shared": shared_pages,
        }
        return True

    def _take_riders(self, head: _Staged, slots: list[int]):
        """Pop the arrivals that ride with ``head``'s launch, and say in
        which program: ``(riders, (rows, bucket))``, or ``([], None)``. Of
        the run of plain prompts right behind the head (FIFO: nobody is
        overtaken), a free slot each, as many as a compiled several-row
        program holds (``_group_shape``). The run ends at what has to be
        launched alone: an import or an attach (they edit the pool, not a
        staging row), a synchronous ``admit()`` (its caller takes the
        landing's row for its own), a guide (its mask goes with one first
        token), a prompt of more than one dispatch (``admit_chunk``: its
        chunks interleave with decode), a prompt that starts from a stored
        prefix or would from a rider's ahead of it (it prefills its
        remainder alone: less work than riding). The paged layout prices
        and writes pages an arrival: no riders."""
        most = max((r for r, _ in self._group_shapes()), default=1)
        run = [head]
        for ids, sid, guide, kind, enqueued in self._arrivals[
                :min(len(slots), most - 1)]:
            if kind is not None or guide is not None or enqueued is None:
                break
            if len(ids) > self._admission_chunk_for(len(ids)):
                break
            if self._match_prefix(ids)[0] or any(
                    self._shares_prefix(ids, m) for m in run):
                break
            run.append(_Staged(ids, sid, -1, None, [enqueued]))
        own = [self._admission_chunk_for(len(m.ids)) for m in run]
        for n in range(len(run), 1, -1):
            shape = _group_shape(own[:n])
            if shape in self._warmed:
                riders = run[1:n]
                for m in riders:
                    self._arrivals.pop(0)
                    m.slot = slots.pop(0)
                return riders, shape
        return [], None

    def _stored_prefix_len(self, ids: list[int]) -> int:
        """How much of a landed prompt the prefix store keeps: up to a
        ``prefix_block`` boundary short of its last token (0: nothing)."""
        base = (len(ids) - 1) // self._prefix_block * self._prefix_block
        if self._prefix_entries <= 0 or base < max(1, self._prefix_share_min):
            return 0
        return base

    def _shares_prefix(self, ids: list[int], ahead: _Staged) -> bool:
        """Whether a prompt would start from what ``ahead`` leaves in the
        prefix store once it has landed."""
        base = self._stored_prefix_len(ahead.ids)
        return 0 < base < len(ids) and ids[:base] == ahead.ids[:base]

    def _splice_fn(self):
        """The admission splice as ONE jitted program with the slot
        indices TRACED: splicing with host-side ``.at[:, slot].set`` bakes
        the slot as a constant, so every distinct slot compiled a fresh
        cache-sized scatter (plus four small-state scatters) *inside the
        serving window*. One traced program a row count serves every slot
        and is warmed with the bucket (``_warm_bucket``). Every staged row
        goes to ``slot[i]``: a launch's repeated first row to the same
        slot with the same values, a cancelled arrival's to the free slot
        it had been given (a slot without a stream is overwritten by the
        next admission). It donates the live cache and state
        (``build_splice``): assign what it returns."""
        if self.__splice is None:
            self.__splice = build_splice(self._state_shardings)
        return self.__splice

    @property
    def _row_of(self):
        """Row ``i`` (traced) of a several-row staging cache as a cache of
        one row: what the prefix store keeps of a launch. The program's
        name keeps it among the admission's programs in a trace."""
        if self.__row_of is None:
            def splice(cache, i):
                return jax.tree.map(
                    lambda x: jax.lax.dynamic_slice_in_dim(x, i, 1, 1), cache)

            self.__row_of = jax.jit(splice)
        return self.__row_of

    def _first_tokens(self, logits, sids: list[int], hist_rows: np.ndarray,
                      mask=None):
        """Each staged row's stream key and its first token, sampled from
        the row's own logits under that key and the row's own history:
        ``(keys [R, 2], tokens [R])``, un-fetched. One program a row count
        (a landing's host time is one dispatch: where the prefill is
        short, every live stream waits for what the host does here)."""
        if self.__first_tokens is None:
            def first(logits, sids, hist, mask):
                keys = jax.vmap(
                    lambda sid: jax.random.fold_in(self._base_key, sid))(sids)
                at0 = jax.vmap(lambda key: jax.random.fold_in(key, 0))(keys)
                return keys, sampling.sample_tokens_keyed(
                    logits, at0, hist, self.settings, mask=mask)

            self.__first_tokens = jax.jit(first)
        return self.__first_tokens(
            logits, np.asarray(sids, np.uint32), hist_rows, mask)

    def _finish_admission(self, wait: bool = True) -> None:
        """Land the launched admission. The DEVICE half, here: the first
        tokens' sampler on the last chunk's logits, the splice of the
        staged rows into their slots (it takes the tokens where the
        sampler left them, on the device), what the next dispatch reads
        of the new streams (their frontiers and token indices, their
        slots counted live: ``_live``), the prefix store's feed, and the
        device's next program (the next arrival's launch if one can
        start, else the next block, which waits for the prefill's end
        while rows go out: ``_landing_runs``), dispatched back to back
        behind the prefill. The HOST half (``_land_host``) reads the tokens
        afterwards, while the device works, installs the streams and
        queues the members' row. A stream that its first token ends has
        then a block dispatched with its row live, which ``_record``
        discards like any overrun.

        The host half follows at once where no recorded row is still to
        be handed out. Where some are (``_can_land`` has seen that the
        halves may be apart) it waits for them in ``_landed``: until then
        ``self.streams[slot]`` is the stream those rows belong to, for
        ``_hand_out`` and for the caller, and ``_live`` alone knows the
        slot is taken and served.

        What the engine sees decides the order, nothing else: a guided
        arrival's token is fetched before anything follows it (its guide
        advances on the host, and no block goes out under a live guide);
        the synchronous ``admit()`` (``wait=False``) hands its caller the
        token, so nothing is enqueued for later before the fetch."""
        land_begin = time.perf_counter()
        st, self._staging = self._staging, None
        members, rows = st["members"], st["rows"]
        logits = st["logits"]
        # rows still to go out: the streams they belong to stay where
        # they are until the host half
        before_rows = wait and bool(self._pending_rows)
        # An in-flight block (dispatched between a chunked admission's
        # ticks) belongs to the pre-admission state: fetch and record its
        # rows before the slots' columns change meaning, so streaming
        # step() consumers still receive every Token.
        self._drain_buffered_rows()

        # a slot's previous stream is gone; its guide (if any) with it,
        # and (only under a synchronous admit(), which does not wait for
        # the rows to go out) whatever of it was still to be handed out:
        # it must not reach the new stream
        for m in members:
            if not wait:
                self._drop_rows(m.slot)
            self._drop_guide(m.slot)
            if m.guide is not None:
                self._attach_guide(m.slot, m.guide)
        guide = members[0].guide  # a guided arrival is launched alone
        n_hist = self.settings.repeat_last_n
        hist = np.full((len(rows), n_hist), -1, np.int32)
        used = np.zeros((len(rows),), np.int32)
        for i, m in enumerate(rows):
            tail = m.ids[-n_hist:]
            hist[i, : len(tail)] = tail
            used[i] = len(tail)
        # dispatched while the prefill still runs (it was launched before
        # the rows that have just gone out): the host time from here to
        # the fetch hides behind it
        keys, toks = self._first_tokens(
            logits, [m.sid for m in rows], hist,
            mask=jnp.asarray(guide.mask_bool())[None] if guide is not None
            else None,
        )
        lp = (sampling.topk_logprobs(logits, self.logprobs_k)
              if self.logprobs_k else None)
        booking = st["booking"]
        fetched = (self._fetch_first(toks, booking[1]) if guide is not None
                   else None)
        # every live stream's next block waits behind this admission: the
        # period that holds it is not clear
        self._period_admitted = True
        vectors = (keys, jnp.asarray(hist), jnp.asarray(used), toks,
                   jnp.asarray(np.asarray([m.slot for m in rows], np.int32)))

        if self._paged:
            # the paged "splice": scatter the staged row's NEW pages into
            # the pool (shared prefix pages are already there — their
            # id-vector slots stay sink, so refcounted pages are never
            # rewritten) and install the table. Only the small sampler
            # state splices as tensors; the KV hand-off is a page write.
            ids, slot = members[0].ids, members[0].slot
            ps = self._page_size
            shared = st.get("shared", [])
            n_shared = len(shared)
            last_page = (len(ids) - 1) // ps
            ids_vec = np.zeros((self._ppp,), np.int32)
            # the fresh pages are held only by this local until the
            # table install below — release them on the error path
            # (cakelint CK-CLAIM). The alloc loop itself sits INSIDE
            # the try: the admission pre-check ran steps ago (chunked
            # prefill), and an import landing in between can pin pages
            # past it, so a mid-loop PoolExhausted must release what
            # this row already took, same as a raising scatter dispatch.
            new_pages: list[int] = []
            try:
                for _ in range(last_page + 1 - n_shared):
                    new_pages.append(self._alloc_page())
                ids_vec[n_shared: last_page + 1] = new_pages
                self.cache = self._row_scatter(self.cache, st["cache"],
                                               jnp.asarray(ids_vec))
            except BaseException:
                for pid in new_pages:
                    self._pagepool.unref(pid)
                raise
            self._release_pages(slot)  # idempotent (freed at claim too)
            self._tables[slot] = shared + new_pages
            self._page_map_dev = None
            (self._keys, self._history, self._hist_slot,
             self._last_tokens) = self._splice_small_fn()(
                self._keys, self._history, self._hist_slot,
                self._last_tokens, *vectors,
            )
        else:
            (self.cache, self._keys, self._history, self._hist_slot,
             self._last_tokens) = self._splice_fn()(
                self.cache, st["cache"], self._keys, self._history,
                self._hist_slot, self._last_tokens, *vectors,
            )
        ld = _Landed(members, rows, toks, lp, booking, land_begin,
                     spliced=time.perf_counter(),
                     due=self._rows_out + len(self._pending_rows))
        # everything a next program's dispatch reads of the arrivals: the
        # frontier, the token index, a live slot
        self._pos = np.asarray(self._pos).copy()
        self._index = np.asarray(self._index).copy()
        for m in members:
            self._pos[m.slot] = len(m.ids)
            self._index[m.slot] = 1
        self._landed.append(ld)
        # Feed the store: an arrival's prefix becomes reusable by future
        # arrivals with the same opening, the next launch among them.
        # Paged: the stream's FULL prompt pages register in the prefix
        # tree (zero copies — the tree just takes references; a later
        # same-prefix arrival shares the physical pages, which is the
        # copy-on-write fan-out). Slot: the staging row is retained under
        # the prefix truncated to a prefix_block boundary (the splice
        # above copied values out and does not donate it, so retaining it
        # costs no extra dispatch; of a launch of several rows, the rows
        # the store has room for are taken out: the last).
        if self._paged:
            n_full = len(ids) // self._page_size
            if (self._prefix_entries > 0 and n_full
                    and n_full * self._page_size
                    >= max(1, self._prefix_share_min)):
                self._prefix_tree.insert(ids, self._tables[slot][:n_full])
        else:
            kept = [(m, n) for m in members
                    if (n := self._stored_prefix_len(m.ids))]
            for m, n in kept[-self._prefix_entries:]:
                self._store_prefix(
                    m.ids[:n],
                    st["cache"] if len(rows) == 1 else self._row_of(
                        st["cache"], jnp.int32(rows.index(m))))
        try:
            # the device's next program, before the host waits for this
            # one's token: the landing is ahead if there is one. Only
            # where one more staging row fits beside this one's, which the
            # splice still reads, and beside both prefills' temporaries (a
            # program's memory is taken when it is enqueued): else the
            # next program follows the fetch, this row released first
            ahead = (wait and fetched is None
                     and self._staging_rows_fit > len(rows))
            if ahead:
                # (while rows go out the next block waits for the
                # prefill to have run: _landing_runs)
                _ = self._launch() or (
                    not before_rows and self._enqueue_block())
            if before_rows and members[0].stamps is not None:
                _LANDINGS_BEFORE_ROWS.inc()
        finally:
            if not before_rows:
                self._landed.remove(ld)
                self._land_host(ld, fetched)
        if not wait or guide is not None:
            self._launch(wait)  # the next arrival starts in this tick too
        elif not ahead:
            del st["cache"]  # the token is here: the splice has read it
            _ = self._launch() or self._enqueue_block()

    def _drop_rows(self, slot: int) -> None:
        """A slot's recorded tokens that nobody has been handed go with
        its stream."""
        for row in self._pending_rows:
            row[slot] = None

    def _fetch_first(self, toks, bucket: int) -> tuple:
        """A landing's one wait for the device: ``(first tokens [R] on
        the host, when they came)``."""
        t_fetch = time.perf_counter()
        tok_ids = self._host(toks)
        landed = time.perf_counter()
        self._note_fetch((landed - t_fetch) * 1e3, "admit_land", bucket)
        return tok_ids, landed

    def _land_next(self, drop_rows: bool = False) -> None:
        """The oldest landing's host half. ``drop_rows`` (under the
        synchronous ``admit()``, which waits for no row): what is left of
        the rows before it in its slots goes, as it does for ``admit()``'s
        own slot."""
        ld = self._landed.pop(0)
        if drop_rows:
            for m in ld.members:
                self._drop_rows(m.slot)
        self._land_host(ld)

    def _land_host(self, ld: _Landed, fetched: tuple | None = None) -> None:
        """A landing's host half: the first tokens come to the host
        (long ready where the rows before them took their time), the
        members become their slots' streams, one token long, and their
        row is queued behind the rows recorded before. From here on
        ``self.streams`` says what ``_live`` said of these slots."""
        if (fetched is None and ld.members[0].stamps is not None
                and (self._inflight is not None or self._staging is not None
                     or self._landed)):
            # the device has its next program (a block, a prefill, or
            # the programs of the landings behind this one)
            _LANDINGS_AHEAD.inc()
        tok_ids, landed = fetched or self._fetch_first(ld.toks,
                                                       ld.booking[1])
        self._admit_dispatched(*ld.booking)
        lp_rows = None
        if ld.lp is not None:
            lp_rows = [[(int(i), float(v)) for v, i in zip(vs, top)]
                       for vs, top in zip(*map(np.asarray, ld.lp))]
        row: list[Token | None] = [None] * len(self.streams)
        for m in ld.members:
            i = ld.rows.index(m)
            self._install(m)
            if m.stamps is not None:
                self._observe_admission(
                    m.sid, m.stamps + [ld.land_begin, landed, ld.spliced])
            row[m.slot] = self._first_token(
                m.slot, int(tok_ids[i]), lp_rows[i] if lp_rows else None)
            if self._paged and self.streams[m.slot].done:
                # first sampled token ended the stream: free its claims
                # (the tree store has taken its references)
                self._release_pages(m.slot)
        self._pending_rows.append(row)

    def _install(self, m: _Staged) -> None:
        """A spliced arrival becomes its slot's stream, for the rows
        that are recorded from here on and for whoever is handed them."""
        self.streams[m.slot] = _Stream(
            stream_id=m.sid, prompt=m.ids,
            detok=TokenOutputStream(self.tokenizer) if self.tokenizer else None,
        )
        if self._spec_k:
            self._spec_bank[m.slot] = []  # the slot's old stream is gone
            # the device ctx row still holds the OLD stream's tokens; a
            # pos-coincidence could otherwise pass the staleness check
            self._spec_ctx = None
            self._spec_ctx_pos = None

    def _first_token(self, slot: int, tok_id: int, lp_row) -> Token:
        """An installed stream's first token has come: the stream is one
        token long, and over where that token ends it; returns the token
        for the landing's row."""
        s = self.streams[slot]
        s.generated.append(tok_id)
        window_full = len(s.prompt) + 1 >= self.max_seq
        is_eos = tok_id in self._eos_ids
        s.done = is_eos or window_full
        if s.done:
            s.end_reason = "eos" if is_eos else "length"
        self._advance_guide(slot, s, tok_id)
        return Token(id=tok_id, text=None, is_end_of_stream=s.done,
                     logprobs=lp_row)

    def _observe_admission(self, stream_id: int, stamps: list) -> None:
        """A prompt admission has landed and its splice is enqueued:
        observe its four stages from its five stamps
        (``engine.admit_*_ms``: what the arrival waited for before the
        device had it, what it waited for behind the running block and
        its rows while its prefill ran, what was left of the prefill,
        the sampling and the fetch when the host came for it, and the
        host work after which the device had its next program again)
        and keep them for the request's own timeline."""
        stages = []
        for (name, hist), t0, t1 in zip(_ADMIT_STAGES, stamps, stamps[1:]):
            ms = max(0.0, (t1 - t0) * 1e3)  # to_splice: spliced came first
            hist.observe(ms)
            stages.append((name, t0, ms))
        _ADMISSIONS_LANDED.inc()
        kept = self._admit_stages
        kept[stream_id] = stages
        if len(kept) > 2 * len(self.streams):
            del kept[next(iter(kept))]

    def take_admission_stages(self, stream_id: int) -> list | None:
        """``[(stage, its start on perf_counter, ms), ...]`` of the
        admission that brought this stream in (``launch_wait``,
        ``rows_wait``, ``land``, ``to_splice``, each starting where the
        one before ended), once: the scheduler takes them where it
        delivers the stream's first token. None for a stream that came
        another way (``admit()``, an import)."""
        return self._admit_stages.pop(stream_id, None)

    def finish(self, stream_id: int) -> bool:
        """Retire the stream with this ``stream_id`` at ANY point in its
        lifecycle. Live: it stops emitting and its slot (batch row + KV
        rows) becomes admissible to the next ``enqueue``/``admit`` arrival
        — the admission splice overwrites the row in place, so retirement
        IS the KV free on the batch plane. Still queued in the arrival
        FIFO, or mid-admission in the staging cache: the arrival is
        dropped before it can splice in (a server cancelling a request
        whose prefill never finished must not leak an ownerless stream
        into a slot). The public serving-side retirement API (a server
        ending a stream at its token budget, client disconnect, or
        deadline); EOS/window exhaustion retire streams the same way
        internally. Returns False when the id is unknown (already done,
        or never admitted) — retirement races are normal for a server,
        not errors. Tokens the device already computed for the stream
        are discarded like any other past-EOS overrun: recorded rows not
        yet handed out lose them here (and ``generated`` with them: the
        stream ends where its caller saw it end), an in-flight block's
        and banked speculation runs at emission."""
        self._domain_stamp.check("BatchGenerator.finish")
        for i, s in enumerate(self.streams):
            if not s.active or s.stream_id != stream_id:
                continue
            undelivered = len(s.generated) - s.handed
            if s.done and not undelivered:
                continue  # over already, as its caller has seen
            if undelivered:
                for row in self._pending_rows:
                    row[i] = None
                del s.generated[s.handed:]
                s.end_reason = None  # an end recorded past this point
            s.done = True
            self._drop_guide(i)
            # paged: retirement IS the KV free — a host-side unref
            # loop over the slot's page list, no cache tensor touched
            self._release_pages(i)
            return True
        for ld in self._landed:
            for m in ld.members:
                if m.sid != stream_id:
                    continue
                # spliced, not installed: no stream comes of it, its slot
                # is free again, and what a block computed for its row is
                # discarded like any overrun (the slot's stream is done)
                ld.members.remove(m)
                if not ld.members:
                    self._landed.remove(ld)
                return True
        st = self._staging
        for m in st["members"] if st is not None else ():
            if m.sid != stream_id:
                continue
            # its staged row stays among the program's and is spliced
            # into the free slot it was given; no stream comes of it
            st["members"].remove(m)
            if not st["members"]:
                if self._paged:
                    for pid in st.get("shared", []):
                        self._pagepool.unref(pid)
                self._staging = None  # the staged rows are dropped
            return True
        n0 = len(self._arrivals)
        # a cancelled resume drops its queued attach AND aborts the
        # import behind it (the pinned pages must not wait out the TTL)
        drop_xfers = [a[0] for a in self._arrivals
                      if a[1] == stream_id and a[3] == _ARR_ATTACH]
        self._arrivals = [a for a in self._arrivals if a[1] != stream_id]
        for xid in drop_xfers:
            self.import_abort(xid)
        return len(self._arrivals) != n0

    def admit(self, prompt, stream_id: int) -> tuple[int, Token]:
        """Admit a new prompt into a finished slot of a RUNNING batch,
        synchronously: the chunked one-row admission prefill runs to
        completion here and the first token is returned (recorded;
        subsequent ``step()`` calls carry the stream forward). Use
        ``enqueue`` to interleave the prefill with decode instead. Raises
        if no stream is done."""
        if not self.streams:
            raise RuntimeError("set_prompts first")
        ids = self._encode(prompt)
        self._arrivals.append((ids, stream_id, None, None, None))
        # Drain until OUR arrival (tracked by list identity — FIFO order
        # admits anything queued ahead of it first) is fully admitted. If
        # the queue head cannot start because every stream is live, raise
        # instead of busy-looping on a no-op tick.
        while (any(a[0] is ids for a in self._arrivals)
               or (self._staging is not None
                   and self._staging["members"][0].ids is ids)):
            if self._staging is None and self._free_slot() is None:
                self._arrivals = [a for a in self._arrivals
                                  if a[0] is not ids]
                raise RuntimeError("no free slot: every stream is still live")
            self._admission_tick(wait=False)
            if self._staging is None and self._admit_deferred:
                # paged pool pressure: nothing inside a synchronous
                # admit() will retire streams and free pages, so busy-
                # looping on the deferred head would never terminate
                self._arrivals = [a for a in self._arrivals
                                  if a[0] is not ids]
                raise RuntimeError(
                    "kv page pool exhausted: admission deferred (retire "
                    "streams via step()/finish(), or grow kv_pool_pages)")
        # the emission row just queued duplicates the returned Token: drop it
        row = self._hand_out(self._pending_rows.pop())
        slot = next(i for i, t in enumerate(row) if t is not None)
        return slot, row[slot]

    # -- stepping ------------------------------------------------------------
    def _emit(self, row: np.ndarray, skip: list[bool] | None = None,
              lp=None) -> list[Token | None]:
        """Record one [B] token row and hand it out at once (a step()
        that returns the row it just fetched)."""
        return self._hand_out(self._record(row, skip=skip, lp=lp))

    def _hand_out(self, row: list[Token | None]) -> list[Token | None]:
        """A recorded row leaves for the caller: its tokens are counted
        as emitted and get their text now, not when they were recorded,
        so a stream retired mid-block (``finish``) has neither counted
        nor detokenized what its caller never saw."""
        emitted = 0
        with self._prof.phase("emit"):
            for i, tok in enumerate(row):
                if tok is None:
                    continue
                emitted += 1
                s = self.streams[i]
                s.handed += 1
                # the EOS id is an end marker, not text: detokenizing it
                # would append its (toy tokenizers: arbitrary) surface form
                if s.detok is not None and tok.id not in self._eos_ids:
                    tok.text = s.detok.next_token(tok.id)
        self._n_emitted += emitted
        self._emitted_ctr.inc(emitted)
        return row

    def _record(self, row: np.ndarray, skip: list[bool] | None = None,
                lp=None) -> list[Token | None]:
        """Turn one [B] token row into per-stream Tokens (None when done or
        dummy; their text comes at ``_hand_out``), updating per-stream
        bookkeeping. ``skip[i]`` excludes a stream from this row without
        marking it done. ``lp`` is the optional per-row top-k logprob pair
        ``(vals [B, K], ids [B, K])``.
        Constrained streams advance their host-side DFA cursor here —
        the one host-side step per token the no-retrace design needs."""
        lpv, lpi = lp if lp is not None else (None, None)
        out: list[Token | None] = []
        with self._prof.phase("emit"):
            for i, s in enumerate(self.streams):
                if not s.active or s.done or (skip is not None and skip[i]):
                    out.append(None)
                    continue
                tok_id = int(row[i])
                s.generated.append(tok_id)
                window_full = (len(s.prompt) + len(s.generated)
                               >= self.max_seq)
                is_eos = tok_id in self._eos_ids
                s.done = is_eos or window_full
                if s.done:
                    s.end_reason = "eos" if is_eos else "length"
                self._advance_guide(i, s, tok_id)
                if s.done and self._paged:
                    # EOS/window/constraint retirement frees the pages
                    # here — the slot is admissible the moment the row
                    # is emitted
                    self._release_pages(i)
                lp_i = None
                if lpv is not None:
                    lp_i = [(int(lpi[i, j]), float(lpv[i, j]))
                            for j in range(lpi.shape[1])]
                out.append(Token(id=tok_id, text=None,
                                 is_end_of_stream=s.done, logprobs=lp_i))
        return out

    def step(self) -> list[Token | None]:
        """Hand out one row: one entry per stream slot (None for
        finished/dummy streams), one token further for every live stream.
        The call in which a fused block lands records all of its rows and
        returns an all-None row; the calls after it first give the device
        its next program (the next block, or a queued arrival's prefill:
        ``_admission_tick``) and then hand the rows out one by one. A
        chunked admission (``admit_chunk``) advances by one chunk per
        call, interleaved with the decode dispatches."""
        self._domain_stamp.check("BatchGenerator.step")
        if not self.streams:
            raise RuntimeError("set_prompts first")
        prof = self._prof
        prof.step_begin("batch")
        self.step_fetch_ms, self.step_fetch_of = 0.0, ""
        self._note_step()
        try:
            if not self._emitted_first:
                self._emitted_first = True
                # skip streams that already recorded tokens — a stream
                # admit()ed into a dummy slot before the first step() had
                # its first token returned by admit(), and must not be
                # double-recorded here
                return self._emit(
                    self._host(self._last_tokens),
                    skip=[bool(s.generated) for s in self.streams],
                    lp=self._first_lp,
                )
            if self._inflight is not None and not any(self._live()):
                # every stream the in-flight block was dispatched for has
                # been retired since: nothing of it is anyone's, and an
                # arrival must not wait for a fetch of it
                self._inflight = None
                if self._moe_pending:
                    self._moe_pending.pop()
            if self._admission_due():
                with prof.phase("admit"):
                    self._admission_tick()
            if self._pending_rows:
                # first the device's next program, then a row
                if not self._landing_runs():
                    self._enqueue_block()
                if self._landed_at is not None:
                    self._landed_rows_out = True
                self._rows_out += 1
                return self._hand_out(self._pending_rows.pop(0))
            return self._step_decode()
        finally:
            self._close_boundary()
            if self._landed_at is None:  # the device waits for nothing
                self._fetch_moe_counts()
            prof.step_end()

    def _note_step(self) -> None:
        """A step() was entered: with a boundary open it may be the one
        that enqueues (``engine.boundary_pass_ms`` ends here)."""
        if self._landed_at is not None:
            self._step_at = time.perf_counter()

    def _note_fetch(self, ms: float, of: str, size: int) -> None:
        """A wait for the device inside this step() took ``ms``: keep the
        longest, and which it was (a slow scheduler pass says both)."""
        if ms > self.step_fetch_ms:
            self.step_fetch_ms, self.step_fetch_of = ms, f"{of}:{size}"

    def _note_enqueued(self) -> None:
        """A device program was just enqueued: if a landed block's
        boundary is open, this is its next program."""
        if self._landed_at is not None and self._next_ahead is None:
            self._next_ahead = not self._landed_rows_out
            # inside a step() entered with the boundary open (not the
            # synchronous admit()'s launch): the third part's far edge
            if self._step_at is not None:
                self._enqueued_at = time.perf_counter()

    def _close_boundary(self) -> None:
        """At the return of a step(): if this call enqueued the next
        program after a landed block, the boundary is over --
        ``engine.boundary_ms`` takes the host time since the fetch
        returned, ``engine.boundaries_ahead`` whether the program left
        before any of the block's rows did. If the device WAITED for
        that program (a later step() than the landing one enqueued it;
        not where an admission launched while the block ran was the next
        program already), ``engine.boundary_emit_ms``, ``_pass_ms`` and
        ``_enqueue_ms`` take where the wait was spent: recording the
        rows, the caller's pass between the two step() calls, and the
        enqueuing step() up to its program call's return. Their sum is
        what ``engine.boundary_ms`` observes less what that step() does
        after the enqueue (a row's hand-out)."""
        if self._landed_at is None:
            return
        if self._next_ahead is None:
            if self._returned_at is None:  # the landing step() returns
                self._returned_at, self._step_at = time.perf_counter(), None
            return
        _BOUNDARY_MS.observe((time.perf_counter() - self._landed_at) * 1e3)
        _BOUNDARIES.inc()
        if self._next_ahead:
            _BOUNDARIES_AHEAD.inc()
        if self._returned_at is not None and self._enqueued_at is not None:
            edges = (self._landed_at, self._returned_at, self._step_at,
                     self._enqueued_at)
            for hist, t0, t1 in zip(_BOUNDARY_PARTS_MS, edges, edges[1:]):
                hist.observe((t1 - t0) * 1e3)
        self._landed_at = self._next_ahead = None

    def _spec_emit_or_round(self):
        """Drain the per-stream accepted-token banks one row per call;
        when empty, run one batched verification round. Returns None — the
        caller falls through to the plain decode path (single or fused
        block) — when speculation cannot or should not run:

        - no live streams;
        - a live stream within K+1 slots of its window (its fed row's
          per-row KV write would clamp-overwrite committed slots). This
          gate is batch-global but BOUNDED: such a stream fills its window
          and goes done within <= K+1 plain dispatches, after which spec
          rounds resume;
        - greedy with no proposal on any live stream: a proposal-less
          round is a (K+1)-wide forward that advances every stream exactly
          one token — strictly worse than a plain dispatch, and for greedy
          the outputs are identical either way. Sampled streams keep the
          always-verify path: their round draws live in the spec fold
          domain, and skipping rounds based on OTHER streams' proposals
          would break composition invariance."""
        if any(self._spec_bank):
            return self._emit_spec_bank()
        live = [i for i, s in enumerate(self.streams)
                if s.active and not s.done]
        if not live:
            return None
        if (self._spec_rounds > 1
                and all(int(self._pos[i])
                        + self._spec_rounds * (self._spec_k + 1)
                        < self.max_seq for i in live)):
            # fused chain: R rounds, one sync. A proposal-less greedy round
            # inside the chain costs one weight sweep for one token — the
            # same per-token HBM cost as the plain path — so the chain
            # skips the host-side "all proposals empty" probe (which would
            # itself force the per-round sync the chain exists to avoid).
            self._spec_chain(live)
            return self._emit_spec_bank()
        if any(int(self._pos[i]) + self._spec_k + 1 > self.max_seq
               for i in live):
            return None
        from cake_tpu.runtime.speculative import ngram_propose

        b = len(self.streams)
        k = self._spec_k
        props = np.full((b, k), -1, np.int32)
        with self._prof.phase("spec_propose"):
            for i in live:
                s = self.streams[i]
                pr = ngram_propose(s.prompt + s.generated,
                                   _SPEC_NGRAM, k)
                props[i, : len(pr)] = pr
        if self.settings.greedy and (props < 0).all():
            return None
        self._spec_round(live, props)
        return self._emit_spec_bank()

    def _spec_round(self, live: list[int], props: np.ndarray) -> None:
        b = len(self.streams)
        k = self._spec_k
        fed = np.zeros((b, k + 1), np.int32)
        fed[:, 0] = self._host(self._last_tokens)
        fed[:, 1:] = np.maximum(props, 0)  # -1 pads embed as 0; never match
        self._period_from = None  # rounds between blocks: no period
        t0 = time.perf_counter()
        with self._prof.phase("spec_verify"), self._sentinel.decode_phase():
            logits, self.cache = self._pick_verify()(
                self.params, jnp.asarray(fed), self.cache,
                jnp.asarray(self._pos),
            )
        self._note_enqueued()
        with self._prof.phase("spec_accept"), self._sentinel.decode_phase():
            if self.settings.greedy:
                (toks, count, self._history,
                 self._hist_slot) = self._accept_rows(
                    logits, jnp.asarray(props), self._history,
                    self._hist_slot)
            else:
                # per-row round keys in their own fold domain (0x5bec),
                # keyed by the row's position — unique per round, disjoint
                # from the plain per-token-index sampling schedule
                rkeys = jax.vmap(lambda kk, p: jax.random.fold_in(
                    jax.random.fold_in(kk, 0x5BEC), p))(
                        self._keys, jnp.asarray(self._pos))
                (toks, count, self._history,
                 self._hist_slot) = self._accept_rows(
                    logits, jnp.asarray(props), self._history,
                    self._hist_slot, round_keys=rkeys)
            toks = self._host(toks)
            count = self._host(count)
        self._n_decode_dispatches += 1
        self._n_spec_dispatches += 1
        self._busy_s += time.perf_counter() - t0
        live_mask = np.zeros((b,), bool)
        live_mask[live] = True
        # non-live rows advance exactly one slot (parity with the plain
        # path's clamped discarded writes); live rows bank their run
        n = np.where(live_mask, np.maximum(count, 1), 1)
        from cake_tpu.runtime import speculative as _spec_obs

        _spec_obs.record_acceptance(
            int((props[live] >= 0).sum()),
            int(sum(max(0, int(n[i]) - 1) for i in live)))
        for i in live:
            self._spec_bank[i] = toks[i, : n[i]].tolist()
        self._pos = np.asarray(self._pos) + n
        self._index = np.asarray(self._index) + n
        last = toks[np.arange(b), n - 1]
        # fed[:, 0] already holds this round's pre-fetched last tokens —
        # no second device fetch (on multi-host each fetch is a collective)
        self._last_tokens = jnp.asarray(
            np.where(live_mask, last, fed[:, 0]), jnp.int32,
        )

    @property
    def _spec_propose(self):
        """Jitted batched device proposer: per-row prompt-lookup over the
        device ctx rows + fed assembly — the host proposer never runs
        inside a fused chain."""
        if self.__spec_propose is None:
            from functools import partial

            from cake_tpu.runtime.speculative import ngram_propose_device

            def propose(ctx, pos, last, *, n_max, k):
                props = jax.vmap(
                    lambda c, p: ngram_propose_device(
                        c, p + 1, n_max=n_max, k=k)
                )(ctx, pos)
                fed = jnp.concatenate(
                    [last[:, None], jnp.maximum(props, 0)], axis=1)
                return props, fed

            self.__spec_propose = jax.jit(partial(
                propose, n_max=_SPEC_NGRAM, k=self._spec_k))
        return self.__spec_propose

    @property
    def _spec_update(self):
        """Jitted accept + state update for one fused round: batched accept
        scan, per-row freeze (``done``), ctx append, pos/last advance. The
        same key schedule as :meth:`_spec_round` (fold domain 0x5bec keyed
        by the row's position), so sampled streams are bit-identical to the
        per-round host loop."""
        if self.__spec_update is None:
            from functools import partial

            from cake_tpu.runtime.speculative import (
                accept_fn_rows,
                accept_sampled_fn_rows,
            )

            eos = jnp.asarray(sorted(self._eos_ids) or [-1], jnp.int32)
            greedy = self.settings.greedy
            settings = self.settings

            def update(logits, props, ctx, pos, history, hist_slot, done,
                       last, keys):
                if greedy:
                    toks, count, h2, s2 = accept_fn_rows(
                        logits, props, history, hist_slot, eos, settings)
                else:
                    rkeys = jax.vmap(lambda kk, p: jax.random.fold_in(
                        jax.random.fold_in(kk, 0x5BEC), p))(keys, pos)
                    toks, count, h2, s2 = accept_sampled_fn_rows(
                        logits, props, history, hist_slot, eos, rkeys,
                        settings)
                n = jnp.where(done, 0, count)
                history = jnp.where(done[:, None], history, h2)
                hist_slot = jnp.where(done, hist_slot, s2)
                # append each row's run at pos+1 (ctx[i, pos_i] holds the
                # token that fed this round). Frozen rows write junk past
                # their frontier — masked by pos everywhere; a frozen row
                # parked near the window end may clamp-write inside its own
                # dead row, which is never proposed from again.
                ctx = jax.vmap(
                    lambda c, t, p: jax.lax.dynamic_update_slice(
                        c, t, (p + 1,))
                )(ctx, toks, pos)
                t_idx = jnp.arange(toks.shape[1], dtype=jnp.int32)
                eos_hit = (
                    (toks[:, :, None] == eos[None, None, :]).any(-1)
                    & (t_idx[None, :] < n[:, None])
                ).any(axis=1)
                new_last = jnp.take_along_axis(
                    toks, jnp.maximum(n - 1, 0)[:, None], axis=1)[:, 0]
                last = jnp.where(done, last, new_last)
                pos = pos + n
                done = done | eos_hit
                return toks, n, ctx, pos, history, hist_slot, done, last

            self.__spec_update = self._pinned(jax.jit(update))
        return self.__spec_update

    def _spec_chain(self, live: list[int]) -> None:
        """Run ``spec_rounds`` propose→verify→accept rounds with a single
        host↔device sync at the end (async dispatch pipelines the chained
        programs). The caller guarantees every live row has
        ``pos + spec_rounds*(K+1) < max_seq`` headroom."""
        b = len(self.streams)
        if (self._spec_ctx is None or self._spec_ctx_pos is None
                or not np.array_equal(self._spec_ctx_pos,
                                      np.asarray(self._pos))):
            buf = np.zeros((b, self.max_seq), np.int32)
            for i, s in enumerate(self.streams):
                ctx_i = (s.prompt + s.generated + self._spec_bank[i]
                         if s.active else [0])
                buf[i, : len(ctx_i)] = ctx_i
            self._spec_ctx = jnp.asarray(buf)
        self._period_from = None  # rounds between blocks: no period
        t0 = time.perf_counter()
        ctx = self._spec_ctx
        pos = jnp.asarray(np.asarray(self._pos, np.int32))
        done = jnp.asarray(np.asarray(
            [not (s.active and not s.done) for s in self.streams]))
        last = self._last_tokens
        verify = self._pick_verify()
        toks_rounds, n_rounds = [], []
        with self._prof.phase("spec_verify"), self._sentinel.decode_phase():
            for _ in range(self._spec_rounds):
                props, fed = self._spec_propose(ctx, pos, last)
                logits, self.cache = verify(
                    self.params, fed, self.cache, pos)
                (toks, n, ctx, pos, self._history, self._hist_slot, done,
                 last) = self._spec_update(
                    logits, props, ctx, pos, self._history, self._hist_slot,
                    done, last, self._keys)
                toks_rounds.append(toks)
                n_rounds.append(n)
        self._note_enqueued()
        # one combined fetch — two sequential _host calls would pay a
        # second host sync, the very latency the chain amortizes
        # (cross-process dp still takes the allgather path per array)
        with self._prof.phase("spec_accept"):
            try:
                toks_all, n_all = jax.device_get(
                    (jnp.stack(toks_rounds), jnp.stack(n_rounds))
                )  # [R, B, K+1], [R, B]
            except RuntimeError:
                toks_all = self._host(jnp.stack(toks_rounds))
                n_all = self._host(jnp.stack(n_rounds))
        self._n_decode_dispatches += self._spec_rounds
        self._n_spec_dispatches += self._spec_rounds
        self._n_spec_chains += 1
        self._busy_s += time.perf_counter() - t0
        from cake_tpu.runtime import speculative as _spec_obs

        # device proposer — actual per-row proposal lengths never reach the
        # host, so proposed is the K×rows×rounds upper bound (accept_rate is
        # a lower bound on the chain path, exact on the per-round path)
        _spec_obs.record_acceptance(
            self._spec_k * len(live) * n_all.shape[0],
            int(sum(max(0, int(n_all[r, i]) - 1)
                    for r in range(n_all.shape[0]) for i in live)))
        for i in live:
            self._spec_bank[i] = [
                int(t)
                for r in range(n_all.shape[0])
                for t in toks_all[r, i, : n_all[r, i]]
            ]
        adv = n_all.sum(axis=0)
        self._pos = np.asarray(self._pos) + adv
        self._index = np.asarray(self._index) + adv
        self._last_tokens = last
        self._spec_ctx = ctx
        self._spec_ctx_pos = np.asarray(self._pos).copy()

    def _emit_spec_bank(self) -> list:
        row = np.zeros((len(self.streams),), np.int64)
        skip = []
        for i, bank in enumerate(self._spec_bank):
            if bank:
                row[i] = bank.pop(0)
                skip.append(False)
            else:
                skip.append(True)
        return self._emit(row, skip=skip)

    def _pick_prefill(self, t: int):
        """Serialized vs GPipe-pipelined batch prefill: on a staged mesh a
        prompt bucket divisible into num_stages chunks streams through the
        stages concurrently (~S× prompt throughput once the pipeline
        fills, identical results — parallel.pipeline microbatch mode);
        anything else uses the serialized program."""
        S = self.plan.num_stages
        if not self._interleave or S < 2 or t % S or self.plan.sp != 1:
            # sp > 1 prompts ride the ring prefill (GPipe microbatching
            # over a sequence-sharded prompt remains unimplemented — the
            # one schedule x sp combination left)
            return self._prefill
        if self.__prefill_pipelined is None:
            self.__prefill_pipelined = self._pinned(build_sharded_prefill(
                self.config, self.plan, params_like=self.params,
                microbatch=S, kv_quant=self.kv_quant,
            ))
        return self.__prefill_pipelined

    def _pick_decode(self, block: bool):
        """Serialized vs interleaved schedule for this dispatch: the
        interleaved program needs the dp-local batch divisible by the stage
        count; outputs are bit-identical either way."""
        serial = self._decode_block if block else self._decode_single
        il = self._decode_block_il if block else self._decode_single_il
        if il is None:
            return serial
        local = len(self.streams) // self.plan.dp
        return il if local % self.plan.num_stages == 0 else serial

    def _block_prog(self, steps: int):
        """The fused decode program for an adaptive-ladder block size
        (compiled lazily, memoized per (steps, schedule)); the base size
        reuses the constructor's programs."""
        if steps == self.block_size and self._decode_block is not None:
            return self._pick_decode(block=True)
        il_ok = (
            self._decode_single_il is not None
            and (len(self.streams) // self.plan.dp)
            % self.plan.num_stages == 0
        )
        key = (steps, il_ok)
        prog = self.__block_progs.get(key)
        if prog is None:
            if il_ok:
                prog = build_interleaved_decode(
                    self.config, self.settings, self.plan,
                    params_like=self.params, steps=steps,
                    kv_quant=self.kv_quant)
            else:
                prog = build_sharded_decode(
                    self.config, self.settings, self.plan,
                    params_like=self.params, steps=steps, per_row=True,
                    kv_quant=self.kv_quant,
                    logprobs_k=self.logprobs_k, paged=self._paged)
            prog = self._pinned(_carrying(prog, steps, self._rows))
            self.__block_progs[key] = prog
        return prog

    def _pick_block_size(self, live_pos) -> int:
        """Adaptive block size for this dispatch. Base-block behavior when
        the ladder is off. With the ladder on: snap to the base block the
        moment an arrival waits (admission latency stays one base block),
        otherwise dispatch the current ladder rung and double it for next
        time. The window-headroom cap halves back down the ladder so a
        stream near its window edge doesn't buy a dispatch that is mostly
        clamped overrun writes."""
        base = self.block_size
        if self.block_size_max <= base:
            return base
        if self._arrivals or self._staging is not None:
            self._adaptive = base
            return base
        size = self._adaptive
        if self._adaptive < self.block_size_max:
            self._adaptive = min(self._adaptive * 2, self.block_size_max)
        headroom = self.max_seq - int(min(live_pos))
        while size > max(1, base) and size > headroom:
            size //= 2
        return max(size, base)

    def warm_blocks(self) -> None:
        """Compile every adaptive-ladder program against the live batch
        shapes OUTSIDE the serving window (sacrificial state copies are
        donated and discarded; the live state is untouched). Servers and
        benches call this once after set_prompts, for the same reason
        warm_admission exists: a ladder rung's first use must not pay XLA
        compilation mid-serving."""
        if not self.streams:
            raise RuntimeError("set_prompts first")
        size = self.block_size
        while size < self.block_size_max:
            size = min(size * 2, self.block_size_max)
            prog = self._block_prog(size)
            cache = jax.tree.map(lambda x: x.copy(), self.cache)
            out = prog(
                self.params, self._last_tokens, cache,
                jnp.asarray(self._pos), self._keys, self._history,
                self._hist_slot, jnp.asarray(self._index),
                *self._paged_args_warm(size),
            )
            jax.block_until_ready(out)

    def drain(self) -> None:
        """RECORD everything the device has already computed -- the
        in-flight block -- without dispatching further work. The
        shutdown / measurement boundary: tokens are recorded against
        their streams immediately (same `_record` path as stepping); the
        Token rows land in the pending queue for any consumer still
        calling step(), which is where they are counted as emitted. An
        expert model's queued counts are fetched too: ``moe.*`` hold
        every landed block. A block that left behind a landing whose
        rows are still going out stays in flight
        (``_drain_buffered_rows``)."""
        self._domain_stamp.check("BatchGenerator.drain")
        while self._host_half_due():
            self._land_next()
        self._drain_buffered_rows()
        self._fetch_moe_counts()

    def _drain_buffered_rows(self) -> None:
        """Fetch an in-flight block and record its rows into the pending
        queue -- shared by drain(), the admission splice, the import
        attach, and export (all points where a slot's column is about to
        change meaning or the recorded state must be complete). Not a
        block that left behind a landing whose stream is not installed
        yet (``_landed``): its rows are that stream's too, and
        ``self.streams`` is the one's whose rows are still going out; the
        step() that follows those rows records it."""
        if self._inflight is not None and not self._landed:
            self._land_block()

    def _land_block(self) -> float:
        """Fetch the in-flight block's ``[steps, B]`` tokens (the host
        round trip) and record all of its rows at once into the pending
        queue: EOS and window retirements are known from here on, so the
        next dispatch goes out with the right frontiers and a freed slot
        can be launched into. Returns when the fetch returned."""
        toks, lpv, lpi, size, t0 = self._inflight
        self._inflight = None
        t_fetch = time.perf_counter()
        with self._prof.phase("sync"):
            rows = self._host(toks)  # [steps, B]
            lp = ((self._host(lpv), self._host(lpi))
                  if lpv is not None else None)
        landed = time.perf_counter()
        self._note_fetch((landed - t_fetch) * 1e3, "block", size)
        if self._moe_counted:
            self._moe_landed += 1  # its counts are ready: fetched later
        if self._period_from is not None:
            # landing to landing: what a live stream waits for its next
            # block of tokens; clear where no admission landed in between
            period_ms = (landed - self._period_from) * 1e3
            _BLOCK_PERIOD_MS.observe(period_ms)
            if not self._period_admitted:
                _BLOCK_PERIOD_CLEAR_MS.observe(period_ms)
        self._period_from, self._period_admitted = landed, False
        dt = landed - t0
        self._busy_s += dt
        # per-token ms so the series is comparable across block sizes
        self._dispatch_hist.observe(dt * 1e3 / max(1, size))
        rec = obs_flight.recorder()
        if rec.enabled:
            rec.record(
                kind="decode", total_ms=round(dt * 1e3, 3), steps=size,
                batch=len(self.streams),
            )
        with self._prof.phase("emit"):
            for i in range(rows.shape[0]):
                self._pending_rows.append(self._record(
                    rows[i], lp=(lp[0][i], lp[1][i]) if lp else None))
        return landed

    def _enqueue_block(self, ahead: bool = True) -> bool:
        """Give the device its next fused block, into ``_inflight``, and
        say whether that happened. ``ahead`` (rows are still to be handed
        out): not if the device has a program already (a block in
        flight, an admission under way: its prefill is the next program,
        and a chunked one keeps its one tick a step) and not where the
        host must act between steps, which the engine knows from its own
        state: batched speculation (rounds). Never under a live guide
        (the DFA advance is host-side, so a block would sample tokens
        2..K against a stale mask row), without a block program
        (block_size 1) or without a live stream."""
        if ahead and (self._inflight is not None
                      or self._staging is not None or self._spec_k):
            return False
        if self._guides_live():
            return False
        # Capacity is per-stream: a finished stream's row keeps advancing
        # (its clamped writes touch only its own cache row, whose output is
        # discarded), so only LIVE streams gate block decode and exhaustion —
        # a long stream hitting its window must not kill shorter ones.
        live = [self._pos[i] for i, on in enumerate(self._live()) if on]
        if not live:
            # nothing follows: no boundary, and the wait for the next
            # request is no period
            self._landed_at = self._period_from = None
            return False
        if (self._decode_block is None
                and self.block_size_max <= self.block_size):
            return False
        # Fused-block eligibility is per-row, not batch-global: a stream
        # that fills its window inside the block only clamp-writes its OWN
        # cache row past the frontier (per-row dynamic_update_slice), and
        # _record marks it done at the window-filling token so the overrun
        # outputs are discarded — one long stream near its edge must not
        # force every stream to single-step dispatches.
        size = self._pick_block_size(live)
        if size <= 1:
            return False
        self._inflight = self._dispatch_block(size)
        return True

    def _dispatch_block(self, size: int) -> tuple:
        """Dispatch one fused decode block (async): the device-side state
        (cache / history / feedback token futures) and the host-side
        pos/index advance immediately; the ``[size, B]`` token rows (and
        top-k logprob rows when enabled) return UN-fetched, as the
        ``_inflight`` entry, so that rows already recorded go out while
        the device works and ``_land_block`` pays the host round trip
        when they have."""
        t0 = time.perf_counter()
        with self._prof.phase("dispatch", steps=size,
                              batch=len(self.streams)), \
                self._sentinel.decode_phase():
            pos = self._decode_pos()
            out, (last, pos_next, index_next) = self._block_prog(size)(
                self.params, self._last_tokens, self.cache,
                self._carried(0, pos), self._keys, self._history,
                self._hist_slot, self._carried(1, self._index),
                *self._paged_args(size),
            )
            out = self._take_moe_count(out, size)
            if self.logprobs_k:
                (toks, self.cache, self._history, self._hist_slot,
                 lpv, lpi) = out
            else:
                toks, self.cache, self._history, self._hist_slot = out
                lpv = lpi = None
        self._note_enqueued()
        self._n_decode_dispatches += 1
        self._count_kv_blocks(pos, size)
        self._pos = self._pos + size
        self._index = self._index + size
        self._last_tokens = last
        self._carry = [(pos_next, np.where(pos > 0, pos + size, 0)),
                       (index_next, self._index)]
        return toks, lpv, lpi, size, t0

    def _carried(self, which: int, want: np.ndarray):
        """The frontiers (0) or token indices (1) a block goes out with,
        as a device value: the one the last block program returned if it
        still holds what the host wants (nothing changed a row since: no
        splice, retirement, ``finish()``, single step or round), else an
        upload of ``want``."""
        held = self._carry[which]
        if held is not None and np.array_equal(held[1], want):
            return held[0]
        return jax.device_put(want, self._rows)

    def _decode_pos(self) -> np.ndarray:
        """The frontiers a decode dispatch goes out with: a live stream's
        own, and row 0 for a slot without one (retired, or never filled).
        Such a slot's row goes through the program all the same; its
        output is discarded and its cache row is overwritten by the next
        admission, so where it writes is free, and at row 0 its attention
        reads one KV block instead of following a frontier that nobody
        stops."""
        return np.where(self._live(), self._pos, 0).astype(np.int32)

    def _count_kv_blocks(self, pos: np.ndarray, steps: int) -> None:
        """Add what ``steps`` decode steps from the frontiers ``pos`` (as
        dispatched) read of a layer's cache, in the decode kernel's
        blocks, and what is reserved (``attn.kv_blocks_*``). Where window
        and full layers are mixed, a full layer's: a ring is read whole,
        and ``attn.ring_rows_live`` / ``_swept`` say how much of it held a
        key the step's query could see.
        Where the layers run several times a token, a layer's planes: one
        a pass. Under EVA attention no layer holds such rows: the ring's
        live rows, the summaries a query sees and what the step fetches
        of both are counted in their place (``attn.eva_*``)."""
        if "rows" in self.config.cache_plan:  # some layer holds a row a
            read, reserved = pk.decode_blocks_read(  # position
                pos, steps, self.max_seq, block_k=self._kv_block,
                window=self._kv_window)
            _KV_BLOCKS_READ.inc(read * self._kv_planes)
            _KV_BLOCKS_RESERVED.inc(reserved * self._kv_planes)
        if self._latent_planes:
            # step j of the dispatch sweeps the rows 0..pos + j of a stream
            live = pos[:, None] + np.arange(1, steps + 1)[None, :]
            _LATENT_DECODE_CALLS.inc(self._latent_planes * steps)
            _LATENT_ROWS_LIVE.inc(self._latent_planes * int(live.sum()))
        if self._dsa:
            # step j of the dispatch scores the rows 0..pos + j of a stream
            # and attends index_topk of them, or all while it has fewer
            layers, topk = self._dsa
            live = pos[:, None] + np.arange(1, steps + 1)[None, :]
            _DSA_DECODE_CALLS.inc(layers * steps)
            _DSA_ROWS_LIVE.inc(layers * int(live.sum()))
            _DSA_ROWS_SELECTED.inc(layers * int(np.minimum(live, topk).sum()))
            _DSA_ROWS_READ.inc(layers * int(dsa.rows_fetched(
                live, self.max_seq, topk).sum()))
        if self._eva:
            # step j of the dispatch writes position pos + j at ring row
            # (pos + j) % W and attends that row's window and the
            # summaries of the windows before it
            layers, window, chunk = self._eva
            at = pos[:, None] + np.arange(steps)[None, :]
            live, visible = at % window + 1, at // window * (window // chunk)
            _EVA_DECODE_CALLS.inc(layers * steps)
            _EVA_WINDOW_ROWS_LIVE.inc(layers * int(live.sum()))
            _EVA_SUMMARY_ROWS_VISIBLE.inc(layers * int(visible.sum()))
            _EVA_ROWS_READ.inc(layers * int(eva.rows_fetched(
                live - 1, visible, window, self.max_seq // chunk,
                self.config.head_dim).sum()))
            # (position 0 is a stream's start, or a slot without one)
            _EVA_WINDOW_RESETS.inc(
                layers * int(((at % window == 0) & (at > 0)).sum()))
            _EVA_CHUNKS_STEP.inc(layers * at.size)
        if self._rings:
            # a window layer's step reads its ring whole; the rows that
            # hold a key its query may see are the window's, or fewer
            # while the stream is shorter than the window
            layers, rows, window = self._rings
            seen = np.minimum(
                pos[:, None] + np.arange(1, steps + 1)[None, :], window)
            _RING_ROWS_LIVE.inc(layers * int(seen.sum()))
            _RING_ROWS_SWEPT.inc(layers * rows * pos.size * steps)

    def _take_moe_count(self, out: tuple, steps: int) -> tuple:
        """Strip the trailing :class:`ExpertCount` off a decode
        program's outputs (present for an expert model told its share)
        and queue them, un-fetched, beside the dispatch they belong to and
        the rows that were live when it left (a dead slot's row still
        goes through the program; its pairs are no load)."""
        if not self._moe_counted:
            return out
        self._moe_pending.append((out[-1], steps, np.array(self._live())))
        return out[:-1]

    def _fetch_moe_counts(self) -> None:
        """Fetch the counts of every dispatch whose tokens have landed
        (they are ready) into ``moe.*``. Feeding two per-layer metrics is
        all these round trips are for, so they are made where the device
        does not wait for the host: at the return of a step() that
        leaves no boundary open (the next program is enqueued, or
        nothing follows), and in ``drain()``."""
        blocks, self._moe_landed = self._moe_landed, 0
        # ... and of every admission dispatch that has run (in order)
        admitted = 0
        while (admitted < len(self._moe_admitted)
               and self._moe_admitted[admitted][1].is_ready()):
            admitted += 1
        if not blocks and not admitted:
            return
        t0 = time.perf_counter()
        with self._prof.phase("sync_counts"):
            for _ in range(blocks):
                self._record_moe_count()
            for _ in range(admitted):
                self._record_sorted_rows(*self._moe_admitted.popleft())
        ms = (time.perf_counter() - t0) * 1e3
        _COUNTS_FETCH_MS.observe(ms)
        self._note_fetch(ms, "counts", blocks)

    def _record_moe_count(self) -> None:
        """Fetch the oldest queued counts and add the rows' that were
        live when their dispatch left into ``moe.*``."""
        count, steps, live = self._moe_pending.popleft()
        for leaf in jax.tree.leaves(count):  # one wait for all, not one each
            leaf.copy_to_host_async()
        _MOE_LOCAL.inc(int(self._host(count.pairs)[live].sum()))
        if count.zero is not None:  # the router scores zero-compute outputs
            _MOE_ZERO.inc(int(self._host(count.zero)[live].sum()))
        # every row that went through the program, a dead slot's too: what
        # the sorted form reads of the stacks
        _MOE_HIT.inc(int(self._host(count.hit)))
        self._record_sorted_rows(count.sorted_rows, count.live_rows)
        _MOE_DECODE_SORTED.set(int(moe_form_traced(live.size) == "sorted"))
        _MOE_ROUTED.inc(
            steps * int(live.sum()) * self.config.num_experts_per_tok
            * sum(ffn == "moe" for _, ffn in self.config.layer_kinds))
        _MOE_STEPS.inc(steps)

    def _record_sorted_rows(self, handed, live, fetched=False) -> None:
        """One dispatch's pair rows ``handed`` to sorted-form calls and
        those in a row tile the calls touched, fetched, into ``moe.*``
        (``fetched``: its calls gathered those rows by address)."""
        _MOE_SORTED_ROWS.inc(int(self._host(handed)))
        live = int(self._host(live))
        _MOE_SORTED_LIVE.inc(live)
        if fetched:
            _MOE_GATHER_FETCHED.inc(live)

    def _step_decode(self):
        """No recorded row is left to hand out. Spec rounds, if any; else
        the device's block: the one enqueued while the last rows went out
        (or, where nothing is enqueued ahead, dispatched now) lands, all
        of its rows are recorded, and the call returns an all-None row:
        the caller's pass between "tokens landed" and "next program
        enqueued" then carries nothing (no handler is woken, an arrival
        that came in during the block reaches ``_arrivals``), and the
        next step() enqueues before it hands out a row. Else one
        single-step dispatch."""
        if self._spec_k:
            row = self._spec_emit_or_round()
            if row is not None:
                return row
        if self._inflight is not None or self._enqueue_block(ahead=False):
            self._landed_at = self._land_block()
            self._landed_rows_out = False
            self._returned_at = self._enqueued_at = None
            # an admission launched while the block ran is the device's
            # next program already
            launched = self._staging is not None and "logits" in self._staging
            self._next_ahead = True if launched else None
            return [None] * len(self.streams)
        live = [self._pos[i] for i, on in enumerate(self._live()) if on]
        if not live:
            return [None] * len(self.streams)
        constrained = self._guides_live()

        if int(max(live)) >= self.max_seq:  # unreachable: _record marks
            raise RuntimeError("KV cache exhausted")  # window-full streams done
        self._period_from = None  # single steps between blocks: no period
        t0 = time.perf_counter()
        pos = self._decode_pos()
        args = (
            self.params, self._last_tokens, self.cache,
            jnp.asarray(pos), self._keys, self._history,
            self._hist_slot, jnp.asarray(self._index),
        )
        with self._prof.phase("dispatch", steps=1,
                              batch=len(self.streams)), \
                self._sentinel.decode_phase():
            if constrained:
                # gather-and-mask runs inside this compiled program;
                # the per-slot row vector is the only per-step upload
                out = self._decode_single_masked(
                    *args, self._mask_table,
                    jnp.asarray(self._mask_rows_np()),
                    *self._paged_args(1),
                )
            else:
                out = self._pick_decode(block=False)(
                    *args, *self._paged_args(1))
        self._note_enqueued()
        out = self._take_moe_count(out, 1)
        if self.logprobs_k:
            (tok, self.cache, self._history, self._hist_slot,
             lpv_d, lpi_d) = out
        else:
            tok, self.cache, self._history, self._hist_slot = out
            lpv_d = lpi_d = None
        # sync: dispatch is async, busy_s needs compute
        with self._prof.phase("sync"):
            row = self._host(tok)
            lp_h = ((self._host(lpv_d), self._host(lpi_d))
                    if lpv_d is not None else None)
        if self._moe_counted:
            self._moe_landed += 1
        self._n_decode_dispatches += 1
        self._count_kv_blocks(pos, 1)
        dt = time.perf_counter() - t0
        self._busy_s += dt
        self._dispatch_hist.observe(dt * 1e3)
        rec = obs_flight.recorder()
        if rec.enabled:
            rec.record(
                kind="decode", total_ms=round(dt * 1e3, 3), steps=1,
                batch=len(self.streams),
            )
        self._pos = self._pos + 1
        self._index = self._index + 1
        self._last_tokens = tok.astype(jnp.int32)
        return self._emit(row, lp=lp_h)

    def stats(self) -> dict:
        """Serving counters (the reference's worker ops/s + master tok/s
        observability, on the batch plane): dispatch counts, emitted
        tokens, dispatch-busy seconds vs wall clock, aggregate tok/s, and
        tokens-per-dispatch (the dispatch-amortization the fused block and
        admission interleave buy)."""
        wall = (time.perf_counter() - self._t_start
                if self._t_start is not None else 0.0)
        dispatches = self._n_decode_dispatches + self._n_admit_dispatches
        return {
            "streams_live": sum(
                1 for s in self.streams if s.active and not s.done
            ),
            "streams_done": sum(
                1 for s in self.streams if s.active and s.done
            ),
            "pending_admissions": self.pending_admissions(),
            "constrained_live": sum(
                1 for i in self._guides
                if self.streams[i].active and not self.streams[i].done
            ),
            "tokens_emitted": self._n_emitted,
            "decode_dispatches": self._n_decode_dispatches,
            "admit_dispatches": self._n_admit_dispatches,
            "prefix_hits": self._prefix_hits,
            "prefix_entries": (
                len(self._prefix_tree) if self._paged
                and self._prefix_tree is not None
                else len(self._prefix_store)
            ),
            "kv_layout": "paged" if self._paged else "slot",
            **({"kvpool": self._pagepool.stats(),
                "imports_pending": self.imports_pending()}
               if self._paged and self._pagepool is not None else {}),
            "spec_dispatches": self._n_spec_dispatches,
            "spec_chains": self._n_spec_chains,
            "tokens_per_dispatch": (
                round(self._n_emitted / dispatches, 2) if dispatches else None
            ),
            "dispatch_p50_ms": round(self._dispatch_hist.percentile(0.5), 3),
            "dispatch_p99_ms": round(self._dispatch_hist.percentile(0.99), 3),
            "busy_s": round(self._busy_s, 3),
            "wall_s": round(wall, 3),
            "aggregate_tok_s": (
                round(self._n_emitted / wall, 2) if wall > 0 else None
            ),
        }

    def generate(self, max_new_tokens: int) -> list[list[int]]:
        """Run all streams to EOS or ``max_new_tokens`` MORE tokens each
        (repeated calls continue where the last left off); returns
        per-stream generated ids (active streams only, in prompt order).
        With batched speculation the emission is ragged (a stream banks
        1..K+1 accepted tokens per dispatch), so the loop runs until every
        live stream has this call's quota instead of a fixed step count —
        identical behavior on the plain one-token-per-step path. A stream
        admitted into a slot mid-call starts its quota from zero."""
        # counted in tokens handed out by step(): a landed block's rows
        # are recorded at once, ahead of the calls that hand them out
        start = {i: (s, s.handed) for i, s in enumerate(self.streams)}

        def quota_met() -> bool:
            for i, s in enumerate(self.streams):
                if not s.active or s.done:
                    continue
                s0, b = start.get(i, (None, 0))
                base = b if s0 is s else 0
                if s.handed - base < max_new_tokens:
                    return False
            return True

        # Worst-case steps per slow-stream token: draining another stream's
        # full K+1 bank costs up to spec_k+1 step() calls while the slow
        # stream gains one token — size the safety cap to that skew, not
        # just 2x (r4 review: a 2-stream spec_k=8 run could hit the old
        # 2x cap and silently under-deliver).
        per_tok = max(2, self._spec_k + 2)
        cap = per_tok * max_new_tokens * max(1, len(self.streams)) + 8
        for _ in range(cap):
            if quota_met():
                break
            self.step()
        out = []
        for i, s in enumerate(self.streams):
            if not s.active:
                continue
            s0, b = start.get(i, (None, 0))
            base = b if s0 is s else 0
            out.append(s.generated[: base + max_new_tokens])
        return out

    def texts(self) -> list[str | None]:
        """Each active stream's full generated text (None w/o tokenizer)."""
        return [
            self.tokenizer.decode(s.generated) if self.tokenizer else None
            for s in self.streams
            if s.active
        ]
