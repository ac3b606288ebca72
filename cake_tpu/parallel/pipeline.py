"""Single-program pipeline + tensor-parallel execution over a device mesh.

This replaces the reference's entire distributed hot path. There, the master
walks decoder blocks per token and ships activations to workers over TCP with
length-prefixed bitcode frames (`llama.rs:88-119`, `client.rs:101-126`,
`worker.rs:180-224`) — one socket round-trip per contiguous layer group per
token. Here the *whole* per-token step (embed -> all pipeline stages -> norm
-> lm_head -> sample) is ONE compiled XLA program over the mesh:

- the stacked layer axis is sharded over the ``stage`` mesh axis (the
  equivalent of topology layer ranges, topology.rs:46-69);
- activations travel stage-to-stage by ``lax.ppermute`` — compiler-scheduled
  ICI DMA, the TPU-native replacement for `RawTensor` TCP serialization
  (proto/message.rs:11-34), which disappears entirely on-pod;
- within each stage, attention heads and the MLP intermediate dim shard over
  the ``tp`` axis (Megatron column/row parallelism, psum on the row-parallel
  outputs) — parallelism the reference does not have (SURVEY.md §2);
- the KV cache shards over (stage, dp, tp): each stage holds only its own
  layers' cache, like the reference workers (worker.rs:52-61), and each tp
  shard holds only its heads.

Pipeline schedule: single-stream autoregressive decode is inherently
sequential across layers, so the loop runs stages in turn (`lax.fori_loop`
over S steps with a ppermute between steps; after S steps the fully-processed
activation has returned to stage 0). For SPMD validity every stage executes
the layer math every step — collectives may not sit behind a per-stage
branch — and only the active stage's effects land, via a gated KV write and
an activation select (see `_pipeline_layers`). Wall-clock matches the
reference's "upstream workers idle while downstream compute" semantics
(SURVEY.md §2); inactive stages compute into a discarded select instead of
idling.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from cake_tpu.models.config import LlamaConfig
from cake_tpu.models import llama
from cake_tpu.ops import quant, sampling
from cake_tpu.ops.kvcache import KVCache
from cake_tpu.ops.moe import ExpertCount
from cake_tpu.ops.rope import rope_tables_for
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.parallel.mesh import (
    DP,
    EP,
    SP,
    STAGE,
    TP,
    MeshPlan,
    cache_specs,
    param_specs,
)


def _local_counts(config: LlamaConfig, tp: int) -> tuple[int, int]:
    return config.num_attention_heads // tp, config.num_key_value_heads // tp


def _pipeline_layers(
    x: jax.Array,  # [Bl, T, hidden] local activation
    params,  # local weights; its "layers" the stacked [L/S, ...] ones
    cache: KVCache,  # local cache, k and v [L/S, Bl, KVl, S, D]
    cos: jax.Array,
    sin: jax.Array,
    pos,
    config: LlamaConfig,
    num_stages: int,
    heads_l: int,
    kv_heads_l: int,
    sp: int = 1,
    sp_prefill: bool = False,
    sp_chunk: bool = False,
    count_local: bool = False,
    valid: jax.Array | None = None,
    expert_valid: jax.Array | None = None,
):
    """Run the staged pipeline loop. Returns (x_on_stage0, cache); with
    ``count_local`` (an expert model of the latent family, which runs as
    one stage) a third value, the :class:`ExpertCount` of the experts
    held here (:func:`llama.forward_layers`). ``valid [B]``: the
    true tokens of each row of a bucketed chunk (what alone may touch a
    recurrent state); ``expert_valid [B]``: the same for an expert block,
    whatever the cache holds (:func:`llama.true_rows`).

    SPMD-uniformity: every stage executes the layer math (and therefore every
    collective — tp psum, sp ring ppermute, sp decode psum/pmax) on every
    step. Collectives inside a per-stage ``lax.cond`` are invalid SPMD — XLA's
    CollectivePermute is a whole-program rendezvous, so divergent branches
    deadlock or pair mismatched iterations. Instead the *effects* are
    predicated: the KV write is gated on ``step == my_stage`` and the
    activation is selected. Wall-clock cost is identical — single-stream
    pipeline stages are serialized either way ("upstream workers idle",
    SURVEY.md §2); inactive stages just compute concurrently into a discarded
    select instead of idling.
    """
    my_stage = jax.lax.axis_index(STAGE)
    perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    def body(step, carry):
        x, cache, *local = carry
        active = step == my_stage
        h, new_cache, *now = llama.forward_layers(
            params["layers"], x, cache, cos, sin, pos, config,
            num_heads=heads_l, num_kv_heads=kv_heads_l, tp_axis=TP, ep_axis=EP,
            sp_axis=SP, sp_size=sp, write_gate=active, sp_prefill=sp_prefill,
            sp_chunk=sp_chunk, count_local=count_local, valid=valid,
            pass_norm=llama.pass_norm(params, config),
            expert_valid=expert_valid,
        )
        x = jnp.where(active, h, x)
        x = jax.lax.ppermute(x, STAGE, perm)
        return (x, new_cache, *(a + b for a, b in zip(local, now)))

    carry = (x, cache) + (
        (ExpertCount.zeros(x.shape[0], config.zero_expert_num > 0),)
        if count_local else ())
    return jax.lax.fori_loop(0, num_stages, body, carry)


def _pipelined_prefill_layers(
    x_chunks: jax.Array,  # [M, B, C, hidden] embedded chunks (stage 0's feed)
    layers,
    ck: jax.Array,
    cv: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    config: LlamaConfig,
    num_stages: int,
    heads_l: int,
    kv_heads_l: int,
):
    """GPipe-style pipelined prefill: prompt chunks stream through the
    stages so all stages compute concurrently.

    The reference has "no micro-batching and no pipelining overlap" —
    upstream workers idle while downstream compute (SURVEY.md §2), and the
    plain staged prefill here inherits that wall-clock shape (S serialized
    passes over the full prompt). Prefill is MXU-bound, so overlap is real
    throughput: chunk ``j`` enters stage 0 at iteration ``j`` and stage
    ``s`` processes it at iteration ``j + s``; once the pipeline fills,
    every stage works every iteration — ~S× prefill/TTFT on S stages,
    minus the (S-1)-iteration fill/drain bubble.

    Causality holds by construction: chunks traverse each stage in order,
    so when chunk ``j`` reaches a stage, that stage's KV rows for chunks
    ``0..j-1`` are already written; attention over the fixed cache buffer
    at ``pos = j*C`` masks everything beyond the frontier as usual.

    Returns ``(y [M, B, C, hidden] — final activations, valid on stage 0
    only), ck, cv``.
    """
    my_stage = jax.lax.axis_index(STAGE)
    perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
    m_chunks, b, c, hidden = x_chunks.shape

    y0 = jnp.zeros_like(x_chunks)
    x0 = jnp.zeros((b, c, hidden), x_chunks.dtype)

    def body(t, carry):
        x, ck, cv, y = carry
        # 1) collect: the permuted-in x on stage 0 is chunk t-S, finished
        j_done = jnp.clip(t - num_stages, 0, m_chunks - 1)
        collect = (my_stage == 0) & (t >= num_stages)
        cur = jax.lax.dynamic_slice_in_dim(y, j_done, 1, axis=0)
        y = jax.lax.dynamic_update_slice_in_dim(
            y, jnp.where(collect, x[None], cur), j_done, axis=0
        )
        # 2) inject: stage 0 feeds chunk t into the pipeline
        j_in = jnp.clip(t, 0, m_chunks - 1)
        xin = jax.lax.dynamic_slice_in_dim(x_chunks, j_in, 1, axis=0)[0]
        x = jnp.where((my_stage == 0) & (t < m_chunks), xin, x)
        # 3) compute: this stage holds chunk j = t - my_stage (SPMD-uniform;
        # invalid iterations compute into a discarded select, gated KV)
        j = t - my_stage
        valid = (j >= 0) & (j < m_chunks)
        pos = jnp.clip(j, 0, m_chunks - 1) * c
        h, new_cache = llama.forward_layers(
            layers, x, KVCache(k=ck, v=cv), cos, sin, pos, config,
            num_heads=heads_l, num_kv_heads=kv_heads_l, tp_axis=TP, ep_axis=EP,
            write_gate=valid,
        )
        x = jnp.where(valid, h, x)
        x = jax.lax.ppermute(x, STAGE, perm)
        return x, new_cache.k, new_cache.v, y

    # M injections + S iterations for the last chunk to traverse and land
    # back on stage 0 (collection happens at the top of the iteration)
    _, ck, cv, y = jax.lax.fori_loop(
        0, m_chunks + num_stages, body, (x0, ck, cv, y0)
    )
    return y, ck, cv


def _true_rows(config: LlamaConfig, tokens: jax.Array,
               last_index: jax.Array, whole: bool = True) -> dict:
    """``valid`` and ``expert_valid`` of :func:`_pipeline_layers` for a
    bucketed chunk (:func:`llama.true_rows`). ``whole`` False: ``tokens``
    are a shard of each row (ring prefill), whose places are not the
    bucket's: no length for the expert block."""
    valid, expert_valid = llama.true_rows(config, tokens.shape, last_index)
    return {"valid": valid,
            "expert_valid": expert_valid if whole else None}


def _select_stage0(x: jax.Array) -> jax.Array:
    """Broadcast stage 0's value to all stages (the activation is only valid
    where the pipeline completed)."""
    my_stage = jax.lax.axis_index(STAGE)
    return jax.lax.psum(jnp.where(my_stage == 0, x, jnp.zeros_like(x)), STAGE)


def _select_last_sp(x: jax.Array, last_index: jax.Array, sp: int) -> jax.Array:
    """Pick the hidden state at per-batch global position ``last_index`` from
    a sequence-sharded activation ``x [B, T_l, H]`` (``[B, T_l, hc_mult,
    H]`` under a wide residual stream); the owner shard contributes,
    everyone else zero, reassembled by psum over sp."""
    idx = last_index.reshape((-1,) + (1,) * (x.ndim - 1)).astype(jnp.int32)
    if sp == 1:
        return jnp.take_along_axis(x, idx, axis=1)[:, 0, :]
    t_l = x.shape[1]
    local = idx - jax.lax.axis_index(SP) * t_l
    ok = (local >= 0) & (local < t_l)
    val = jnp.take_along_axis(x, jnp.clip(local, 0, t_l - 1), axis=1)[:, 0, :]
    val = jnp.where(ok[:, 0, :], val, jnp.zeros_like(val))
    return jax.lax.psum(val, SP)


def _head_logits(params, x_last: jax.Array, config: LlamaConfig) -> jax.Array:
    """ln_f (where the layer loop has not applied it: ``llama.head_norm``)
    + vocab-sharded lm_head; full logits gathered over tp."""
    x_last = llama.head_norm(params, x_last, config)
    logits_local = quant.dense(x_last, params["lm_head"]).astype(jnp.float32)
    return jax.lax.all_gather(logits_local, TP, axis=-1, tiled=True)


def _dp_fold(key: jax.Array, dp: int) -> jax.Array:
    """Give each dp shard a distinct sampling key stream; identity at
    dp == 1 so the single-stream mesh path reproduces the local generator's
    key schedule exactly."""
    if dp == 1:
        return key
    return jax.random.fold_in(key, jax.lax.axis_index(DP))


def build_sharded_decode(
    config: LlamaConfig, settings: SamplerSettings, plan: MeshPlan,
    params_like: dict | None = None, steps: int = 1, per_row: bool = False,
    kv_quant: str | None = None, masked: bool = False, logprobs_k: int = 0,
    paged: bool = False,
):
    """Compile the fused multi-chip decode step.

    Signature: ``(params, token [B], cache, pos, key, history [B, N],
    hist_slot) -> (next_token, cache, history, hist_slot)`` for
    ``steps == 1``; with ``steps > 1`` the signature gains a trailing
    ``index0`` argument (absolute token index of the first emitted token)
    and ``next_token`` is ``[steps, B]``. The K-token loop — pipeline,
    sampling, token feedback — then runs inside the one compiled program
    (lax.scan), amortizing dispatch latency exactly like the single-chip
    ``decode_scan_fn``; per-step sampling keys are ``fold_in(key,
    index0 + i)``, the same token-index schedule as every other execution
    path, so one seed yields one stream regardless of sharding or block
    size. ``params_like``: pass the params pytree (or a structural twin)
    when some linears are int8-quantized so the shard_map specs match.

    ``per_row=True`` is the multi-stream serving mode: ``pos`` becomes
    ``[B]`` (each stream decodes at its own position — right-padded prompts
    of different lengths run concurrently), ``key`` becomes per-stream
    keys ``[B, 2] uint32``, and ``index0`` becomes ``[B]`` (each stream's
    absolute token index — a stream admitted into a running batch starts
    its own schedule at 1); the program folds each stream's token index
    into its key (``fold_in(row_key, index0[b] + i)``), so a stream's
    output depends only on (its key, its prompt) — invariant to batch
    composition, mesh layout, and admission time. The signature always
    ends with ``index0`` in this mode. ``per_row`` composes with ``sp > 1``
    (r4): each stream decodes at its own frontier against the
    sequence-sharded cache — the per-row positions flow through the sp
    owner-masked KV write and the per-row-masked distributed flash decode
    (ops/ring.py), which is what lets MULTI-stream serving ride a window
    sharded across chips.

    ``masked=True`` (requires ``per_row`` and ``steps == 1``) is the
    constrained-decoding variant (constrain/): the signature gains two
    trailing operands — ``mask_table [M, ceil(V/8)] uint8`` (the
    device-resident packed per-state allowed-token bitmasks; row 0 is
    all-ones for unconstrained streams) and ``mask_row [B] int32`` (each
    stream's current DFA-state row) — and the compiled body gathers each
    stream's row, unpacks it, and applies it inside the sampler. The DFA
    advance stays host-side between dispatches (CK-JIT: nothing
    stateful traces); both shapes are static, so constrained decode
    never retraces per token. Single-step only by design: a fused block
    would need the host-side DFA advance mid-program.

    ``logprobs_k > 0`` (requires ``per_row``) additionally returns the
    top-k log-softmax of the RAW logits per emitted token — outputs gain
    trailing ``(lp_vals, lp_ids)`` (``[B, k]``, or ``[steps, B, k]`` for
    fused blocks). The sampled stream is unchanged: the top-k is a pure
    extra read of logits the program already computed.

    ``paged=True`` (requires ``per_row``; composes with ``masked`` and
    ``logprobs_k``) is the page-pool layout (:mod:`cake_tpu.kvpool`):
    the ``cache`` operand becomes the pooled page array
    ``[L, P, KH, page_size, D]`` and the signature gains two trailing
    int32 operands — ``page_map [B, pages_per_stream]`` (each stream's
    logical->physical page list, sink-padded past its frontier) and
    ``scatter_ids [B, W]`` (the physical pages receiving this dispatch's
    KV writes; sink for retired/dummy rows). The body gathers each
    stream's pages into the standard contiguous view, runs the UNCHANGED
    decode math over it (bit-identity with the slot layout by
    construction), and scatters only the written pages back. Both
    operand shapes are static, so page-table churn never retraces —
    admitting or retiring a stream is a host-side table edit.
    Requires ``plan.dp == 1`` and ``plan.sp == 1`` (the page axis is
    unsharded; batch and sequence sharding of pooled pages is future
    work — ``BatchGenerator`` enforces this at construction).
    """
    heads_l, kv_heads_l = _local_counts(config, plan.tp)
    if masked and (not per_row or steps != 1):
        raise ValueError("masked decode requires per_row=True, steps=1 "
                         "(the DFA advance is host-side between steps)")
    if logprobs_k and not per_row:
        raise ValueError("logprobs_k requires the per_row serving mode")
    if paged and not per_row:
        raise ValueError("paged decode requires the per_row serving mode")
    if paged and (plan.dp != 1 or plan.sp != 1):
        raise ValueError("paged decode requires dp == 1 and sp == 1 "
                         "(the page axis is unsharded)")
    # the serving programs of an expert model of the latent family return
    # one more value, last, an ExpertCount: each batch row's routed (token,
    # expert) pairs of the dispatch that fell on experts held here, and
    # the held experts that some row chose, summed on the device over its
    # steps, its expert layers and the ep axis; the engine adds up the
    # live rows' pairs (obs: moe.local_pairs, moe.experts_hit)
    count_local = per_row and moe_counted(config)

    def one_step(params, token, cache, pos, key, history, hist_slot,
                 mask=None):
        # cache.max_seq inside shard_map is the per-shard slice; RoPE tables
        # must cover global positions.
        cos, sin = rope_tables_for(config, cache.max_seq * plan.sp)
        x = llama.embed_tokens(params, token[:, None], config)
        x, cache, *local = _pipeline_layers(
            x, params, cache, cos, sin, pos, config,
            plan.num_stages, heads_l, kv_heads_l, sp=plan.sp,
            sp_prefill=False, count_local=count_local,
        )
        x_last = _select_stage0(x[:, -1, :])
        logits = _head_logits(params, x_last, config)
        lp = sampling.topk_logprobs(logits, logprobs_k) if logprobs_k \
            else None
        local = jax.lax.psum(local[0], EP) if count_local else None
        if per_row:
            tok = sampling.sample_tokens_keyed(logits, key, history,
                                               settings, mask=mask)
        else:
            tok = sampling.sample_tokens(logits, _dp_fold(key, plan.dp),
                                         history, settings)
        history, hist_slot = sampling.push_history_batched(history, hist_slot, tok)
        return tok, cache, history, hist_slot, lp, local

    def fold_key(key, index):
        if per_row:  # key [B, 2], index [B] (per-stream schedules)
            return jax.vmap(jax.random.fold_in)(key, index)
        return jax.random.fold_in(key, index)

    if paged:
        from cake_tpu.kvpool import pool_specs

        kv_specs = pool_specs(kv_quant)
    else:
        kv_specs = cache_specs(kv_quant, held=config.cache_plan)
    in_specs = [
        param_specs(params_like),
        P(DP),
        kv_specs,
        P(DP) if per_row else P(),
        P(DP, None) if per_row else P(None),
        P(DP, None),
        P(DP) if per_row else P(),  # hist_slot: per-stream ring positions
    ]
    if steps == 1 and not per_row:
        def step(params, token, cache, pos, key, history, hist_slot):
            tok, cache, history, hist_slot, _, _ = one_step(
                params, token, cache, pos, key, history, hist_slot)
            return tok, cache, history, hist_slot
    else:
        def step(params, token, cache, pos, key, history, hist_slot,
                 index0, *rest):
            rest = list(rest)
            if masked:
                mask_table, mask_row = rest[0], rest[1]
                del rest[:2]
                # one gather + unpack per dispatch: each stream's current
                # DFA-state bitmask row, from the table uploaded once
                row_mask = sampling.unpack_mask_bits(
                    mask_table[mask_row], config.vocab_size)
            else:
                row_mask = None
            if paged:
                from cake_tpu import kvpool

                page_map, scatter_ids = rest
                pool_in = cache
                ps = kvpool.page_size_of(pool_in)
                ppp = page_map.shape[1]
                w = scatter_ids.shape[1]
                # the contiguous view of every stream's pages; the decode
                # body below is untouched, so paged streams reproduce the
                # slot layout's math bit for bit
                cache = kvpool.gather_view(pool_in, page_map)
                first_page = jnp.minimum(pos // ps, ppp - w)

            def body(carry, i):
                token, cache, history, hist_slot = carry
                tok, cache, history, hist_slot, lp, local = one_step(
                    params, token, cache, pos + i, fold_key(key, index0 + i),
                    history, hist_slot, mask=row_mask,
                )
                ys = (tok, lp[0], lp[1]) if logprobs_k else tok
                return ((tok, cache, history, hist_slot),
                        (ys, local) if count_local else (ys,))

            (_, cache, history, hist_slot), (ys, *local) = jax.lax.scan(
                body, (token, cache, history, hist_slot),
                jnp.arange(steps, dtype=jnp.int32),
            )
            local = jax.tree.map(lambda a: jnp.sum(a, axis=0), tuple(local))
            if paged:
                # only the pages this dispatch wrote go back to the pool
                cache = kvpool.scatter_back(pool_in, cache, first_page,
                                            scatter_ids)
            if logprobs_k:
                toks, lpv, lpi = ys
            else:
                toks, lpv, lpi = ys, None, None
            if steps == 1:
                out = (toks[0], cache, history, hist_slot)
                return out + ((lpv[0], lpi[0]) if logprobs_k else ()) + local
            out = (toks, cache, history, hist_slot)
            return out + ((lpv, lpi) if logprobs_k else ()) + local

        in_specs.append(P(DP) if per_row else P())  # index0
        if masked:
            in_specs.append(P(None, None))  # mask_table: replicated
            in_specs.append(P(DP))          # mask_row: per-stream
        if paged:
            in_specs.append(P(None, None))  # page_map
            in_specs.append(P(None, None))  # scatter_ids

    lp_specs = ()
    if logprobs_k:
        lp_specs = ((P(DP, None),) * 2 if steps == 1
                    else (P(None, DP, None),) * 2)
    sharded = shard_map(
        step,
        mesh=plan.mesh,
        in_specs=tuple(in_specs),
        out_specs=(
            P(DP) if steps == 1 else P(None, DP),
            kv_specs,
            P(DP, None),
            P(DP) if per_row else P(),
        ) + lp_specs + (
            (ExpertCount(P(DP), P(), P(), P(),
                         P(DP) if config.zero_expert_num else None),)
            if count_local else ()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(2,))


def moe_counted(config: LlamaConfig) -> bool:
    """Whether this model's serving decode programs count the routed pairs
    that fall on held experts (an expert model told its share)."""
    return config.family.counts_held_experts and config.n_routed_experts > 0


def _head_split_safe(hw, S: int) -> bool:
    """Whether vocab-splitting the lm_head over S stages cannot change
    which quant_matmul backend the program gets: the pallas kernel's
    256-column tileability gate sees ``chunk`` on a split head but
    ``v_local`` on the serialized full-width head, so a backend-divergent
    split would make interleaved and serialized programs' logits differ in
    low-order bits and break their bit-identity contract. Split when the
    backend provably cannot differ — all-XLA (kernels off or an "xla"
    pin), all-pallas (interpret mode), or both widths on the same side of
    the tileability gate. Evaluate at TRACE time so a BatchGenerator's pin
    (quant.pinned_impl around the dispatch) is visible. bf16 heads slice
    bitwise-safely at any width."""
    v_local = quant.out_features(hw)
    if v_local % S:
        return False
    if not isinstance(hw, (quant.QuantizedLinear, quant.Quantized4Linear)):
        return True
    from cake_tpu.ops import pallas as pk

    pin = quant.pinned()
    if not pk.kernels_enabled() or pin == "xla":
        return True  # everything runs XLA either way
    if pin == "pallas" and pk.interpret_default():
        return True  # everything runs (interpreted) pallas
    return ((v_local // S) % 256 == 0) == (v_local % 256 == 0)


def _head_chunk(hw, my_stage, S: int):
    """This stage's V/S column slice of the (possibly int8) lm_head — the
    one shared implementation behind every vocab-split head, so the
    schedules that must stay bit-identical can never drift apart."""
    chunk = quant.out_features(hw) // S
    start = my_stage * chunk
    if isinstance(hw, quant.QuantizedLinear):
        return quant.QuantizedLinear(
            q=jax.lax.dynamic_slice_in_dim(hw.q, start, chunk, 1),
            scale=jax.lax.dynamic_slice_in_dim(hw.scale, start, chunk, 0),
        )
    if isinstance(hw, quant.Quantized4Linear):
        # vocab (out) axis slice — the packed in-axis is untouched; the
        # out axis is the LAST scale axis for both per-channel [V] and
        # grouped [ngroups, V] scales
        return quant.Quantized4Linear(
            qp=jax.lax.dynamic_slice_in_dim(hw.qp, start, chunk, 1),
            scale=jax.lax.dynamic_slice_in_dim(
                hw.scale, start, chunk, hw.scale.ndim - 1),
        )
    return jax.lax.dynamic_slice_in_dim(hw, start, chunk, 1)


def build_interleaved_decode(
    config: LlamaConfig, settings: SamplerSettings, plan: MeshPlan,
    params_like: dict | None = None, steps: int = 1,
    kv_quant: str | None = None,
):
    """Compile the interleaved-microbatch serving decode: the decode twin of
    :func:`_pipelined_prefill_layers`.

    The plain staged decode (`build_sharded_decode`) serializes the S
    pipeline stages for every token — each of the S inner steps runs the
    layer math for the FULL batch on every stage and keeps one stage's
    result, so (S-1)/S of the mesh's compute and KV-cache reads are
    discarded every dispatch (the SPMD analogue of the reference's
    "upstream workers idle while downstream compute", SURVEY.md §2). Here
    the dp-local batch is split into S microbatches round-robined over the
    stages: at cycle ``t`` stage ``s`` runs its layers on microbatch
    ``(t - s) mod S``, so every stage does useful layer work on B/S rows
    every cycle — per-cycle layer FLOPs and KV traffic drop S×, and a
    microbatch finishing its token step re-enters stage 0 on the next
    cycle, keeping the pipeline full across the whole ``steps`` block
    (utilization ``steps*S / (steps*S + S)``; the one-token bubble is the
    fill/drain).

    Schedule (cycle ``t`` of ``S*(steps+1)``):

    - microbatch ``m = t mod S`` arrives finished at stage 0 (valid from
      ``t >= S``); its next token is sampled and re-injected the same cycle;
    - the head runs on every stage with the vocab split S ways
      (stage-0's hidden is psum-broadcast — [B/S, H], tiny — and each stage
      computes its ``V/(S*tp)`` logit slice from a dynamic slice of the
      replicated lm_head, reassembled by all_gather over stage then tp), so
      per-cycle head weight reads stay at the serialized schedule's average
      and sampling is computed bit-identically on every device — the
      sampled-token / history / position state stays replicated-uniform
      with no trailing cross-stage select;
    - sampling keys are ``fold_in(row_key, index0[row] + k)`` — the same
      per-stream token-index schedule as every other execution path, so the
      emitted streams are bit-identical to `build_sharded_decode(per_row)`.

    Same signature as ``build_sharded_decode(per_row=True)``:
    ``(params, token [B], cache, pos [B], keys [B,2], history, hist_slot,
    index0 [B])``; requires ``B_local % num_stages == 0`` (B_local =
    B/dp). ``plan.sp > 1`` (r5) composes: each cycle's resident
    microbatch decodes against its sequence-sharded KV rows (owner-masked
    sp write + distributed flash attend inside ``forward_layers``; the
    sp collectives run unconditionally every cycle, so SPMD uniformity
    holds), and the head/sampling state stays sp-replicated.

    Bit-identity scope: bf16 weights are bit-identical to the serialized
    program unconditionally. Int8 weights need a pinned quant backend
    (``quant.pinned_impl`` — BatchGenerator always pins): without a pin
    the m>=16 row-count gate sees B rows on the serialized head but B/S
    here and could pick different backends.
    """
    heads_l, kv_heads_l = _local_counts(config, plan.tp)
    S = plan.num_stages

    def step(params, token, cache, pos, keys, history, hist_slot, index0):
        b = token.shape[0]
        if b % S:
            raise ValueError(
                f"interleaved decode needs the dp-local batch ({b}) "
                f"divisible by num_stages ({S})"
            )
        bm = b // S
        cos, sin = rope_tables_for(config, cache.max_seq * plan.sp)
        my_stage = jax.lax.axis_index(STAGE)
        perm = [(i, (i + 1) % S) for i in range(S)]
        hw = params["lm_head"]
        v_local = quant.out_features(hw)
        split_safe = _head_split_safe(hw, S)  # trace-time: sees the pin

        def head_logits(x_n):
            """Full [bm, V] f32 logits with the vocab additionally split
            over the stage axis (falls back to per-stage full width when
            the local vocab does not divide or the split would change the
            quantized head's backend class)."""
            if S > 1 and split_safe:
                lg = quant.dense(x_n, _head_chunk(hw, my_stage, S)).astype(
                    jnp.float32)
                lg = jax.lax.all_gather(lg, STAGE, axis=-1, tiled=True)
            else:
                lg = quant.dense(x_n, hw).astype(jnp.float32)
            return jax.lax.all_gather(lg, TP, axis=-1, tiled=True)

        def body(t, carry):
            x, ck, cv, pos_all, history, hist_slot, toks = carry
            m_fin = jnp.mod(t, S)           # arriving at / injected by stage 0
            base_fin = m_fin * bm
            k_arr = jnp.maximum(t // S - 1, 0)  # token index of the arrival
            arriving = t >= S               # stage 0 holds a real finished mb
            injecting = t < steps * S

            # ---- head + sample (uniform on every device) ----
            x_fin = _select_stage0(x[:, -1, :])  # [bm, H]
            x_n = llama.head_norm(params, x_fin, config)
            logits = head_logits(x_n)            # [bm, V] f32
            key_rows = jax.lax.dynamic_slice_in_dim(keys, base_fin, bm, 0)
            idx_rows = jax.lax.dynamic_slice_in_dim(index0, base_fin, bm, 0)
            hist_rows = jax.lax.dynamic_slice_in_dim(history, base_fin, bm, 0)
            slot_rows = jax.lax.dynamic_slice_in_dim(hist_slot, base_fin, bm, 0)
            step_keys = jax.vmap(jax.random.fold_in)(key_rows,
                                                     idx_rows + k_arr)
            sampled = sampling.sample_tokens_keyed(logits, step_keys,
                                                   hist_rows, settings)

            # commit the arrival's token + history rows (uniform predication)
            cur = jax.lax.dynamic_slice(toks, (k_arr, base_fin), (1, bm))
            toks = jax.lax.dynamic_update_slice(
                toks, jnp.where(arriving, sampled[None], cur),
                (k_arr, base_fin),
            )
            h_new, s_new = sampling.push_history_batched(hist_rows, slot_rows,
                                                         sampled)
            history = jax.lax.dynamic_update_slice(
                history, jnp.where(arriving, h_new, hist_rows), (base_fin, 0))
            hist_slot = jax.lax.dynamic_update_slice(
                hist_slot, jnp.where(arriving, s_new, slot_rows), (base_fin,))

            # the re-injected microbatch decodes at its next position
            pos_rows = jax.lax.dynamic_slice_in_dim(pos_all, base_fin, bm, 0)
            pos_rows = jnp.where(arriving & injecting, pos_rows + 1, pos_rows)
            pos_all = jax.lax.dynamic_update_slice(pos_all, pos_rows,
                                                   (base_fin,))

            # stage 0 embeds + injects: the caller's token on first entry,
            # the just-sampled token thereafter
            tok_rows = jax.lax.dynamic_slice_in_dim(token, base_fin, bm, 0)
            tok_inj = jnp.where(arriving, sampled, tok_rows)
            x_inj = llama.embed_tokens(params, tok_inj[:, None], config)
            x = jnp.where((my_stage == 0) & injecting, x_inj, x)

            # ---- layer pass on this stage's resident microbatch ----
            m_res = jnp.mod(t - my_stage, S)
            base_res = m_res * bm
            valid = (t >= my_stage) & (t < my_stage + steps * S)
            pos_res = jax.lax.dynamic_slice_in_dim(pos_all, base_res, bm, 0)
            rows = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, base_res, bm, 1),
                KVCache(k=ck, v=cv),
            )
            h, rows = llama.forward_layers(
                params["layers"], x, rows, cos, sin, pos_res, config,
                num_heads=heads_l, num_kv_heads=kv_heads_l, tp_axis=TP, ep_axis=EP,
                sp_axis=SP, sp_size=plan.sp, sp_prefill=False,
                write_gate=valid,
            )
            x = jnp.where(valid, h, x)
            # gated-off forward_layers rewrites current contents unchanged,
            # so the row write-back is unconditional
            ck, cv = jax.tree.map(
                lambda buf, r: jax.lax.dynamic_update_slice_in_dim(
                    buf, r, base_res, 1),
                (ck, cv), (rows.k, rows.v),
            )
            x = jax.lax.ppermute(x, STAGE, perm)
            return x, ck, cv, pos_all, history, hist_slot, toks

        x0 = jnp.zeros((bm, 1, config.hidden_size), config.jax_dtype)
        toks0 = jnp.zeros((steps, b), jnp.int32)
        _, ck, cv, _, history, hist_slot, toks = jax.lax.fori_loop(
            0, S * (steps + 1), body,
            (x0, cache.k, cache.v, pos, history, hist_slot, toks0),
        )
        if steps == 1:
            return toks[0], KVCache(k=ck, v=cv), history, hist_slot
        return toks, KVCache(k=ck, v=cv), history, hist_slot

    sharded = shard_map(
        step,
        mesh=plan.mesh,
        in_specs=(
            param_specs(params_like),
            P(DP),
            cache_specs(kv_quant),
            P(DP),
            P(DP, None),
            P(DP, None),
            P(DP),
            P(DP),
        ),
        out_specs=(
            P(DP) if steps == 1 else P(None, DP),
            cache_specs(kv_quant),
            P(DP, None),
            P(DP),
        ),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(2,))


def build_admit_prefill(config: LlamaConfig, plan: MeshPlan,
                        params_like: dict | None = None,
                        kv_quant: str | None = None):
    """Compile the continuous-batching admission prefill: ONE prompt row
    (replicated over dp, not dp discarded copies) processed one chunk per
    dispatch into a standalone staging cache, so a running batch's decode
    dispatches interleave with a new prompt's prefill instead of stalling
    behind it.

    Signature: ``(params, tokens [1, C], cache1, pos0, last_local [1]) ->
    (logits [1, vocab] f32, cache1)`` where ``cache1`` is a batch-1 cache
    with the batch axis replicated over dp
    (``mesh.cache_specs(batch_replicated=True)``), ``pos0`` is the chunk's
    global position offset, and ``last_local`` is the in-chunk index of the
    prompt's final token (meaningful on the final chunk; ignored
    otherwise). Chunked prefill is exact: chunk ``j`` attends the staging
    cache's committed positions ``< pos0`` plus its own causal prefix, the
    same math as a single full-prompt pass.

    ``plan.sp > 1`` (r5): the chunk's tokens run REPLICATED over the sp
    axis against the sequence-sharded staging cache — owner-masked range
    write (``ring.sp_range_cache_write``) plus the T>1 distributed-flash
    chunk attend, so continuous admission composes with the
    sequence-sharded serving window.

    An expert model that counts its held experts (:func:`moe_counted`)
    returns two more values, int32 ``[]`` each: the pair rows its
    sorted-form expert calls were handed and those that lay in a row tile
    the calls touched (:class:`ExpertCount` ``sorted_rows``,
    ``live_rows``), summed over its expert layers and the ep axis.
    """
    heads_l, kv_heads_l = _local_counts(config, plan.tp)
    count_local = moe_counted(config)

    def step(params, tokens, cache, pos0, last_local):
        cos, sin = rope_tables_for(config, cache.max_seq * plan.sp)
        x = llama.embed_tokens(params, tokens, config)
        x, cache, *local = _pipeline_layers(
            x, params, cache, cos, sin, pos0, config,
            plan.num_stages, heads_l, kv_heads_l, sp=plan.sp,
            sp_chunk=plan.sp > 1, count_local=count_local,
            **_true_rows(config, tokens, last_local),
        )
        # the chunk activations are replicated over sp (every shard computes
        # the full chunk), so the sp==1 last-index selection applies
        x_last = _select_last_sp(x, last_local, 1)
        x_last = _select_stage0(x_last)
        logits = _head_logits(params, x_last, config)
        if not count_local:
            return logits, cache
        rows = jax.lax.psum(
            (local[0].sorted_rows, local[0].live_rows), EP)
        return (logits, cache) + rows

    sharded = shard_map(
        step,
        mesh=plan.mesh,
        in_specs=(
            param_specs(params_like),
            P(None, None),
            cache_specs(kv_quant, batch_replicated=True,
                        held=config.cache_plan),
            P(),
            P(None),
        ),
        out_specs=(
            P(None, None),
            cache_specs(kv_quant, batch_replicated=True,
                        held=config.cache_plan),
        ) + ((P(), P()) if count_local else ()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(2,))


def build_sharded_verify(config: LlamaConfig, plan: MeshPlan,
                         params_like: dict | None = None,
                         kv_quant: str | None = None):
    """Compile the speculation-verification pass over the mesh: forward
    ``tokens [1, T]`` (the last emitted token + K proposals) from position
    ``pos`` and return logits at EVERY position (``[T, vocab] f32``) — the
    multi-chip twin of :func:`cake_tpu.runtime.speculative.verify_fn`.
    KV for all T slots is written; slots past the accepted frontier hold
    rejected garbage that later steps overwrite before it becomes
    attendable. Requires ``plan.dp == 1`` (the single-stream speculation
    plane); ``plan.sp > 1`` (r5) runs the fed block chunk-replicated over
    sp against the sequence-sharded cache (range write + chunk attend).
    """
    heads_l, kv_heads_l = _local_counts(config, plan.tp)
    if plan.dp != 1:
        raise ValueError("speculative verification requires dp == 1 "
                         "(single-stream plane)")

    def step(params, tokens, cache, pos):
        cos, sin = rope_tables_for(config, cache.max_seq * plan.sp)
        x = llama.embed_tokens(params, tokens, config)
        x, cache = _pipeline_layers(
            x, params, cache, cos, sin, pos, config,
            plan.num_stages, heads_l, kv_heads_l, sp=plan.sp,
            sp_chunk=plan.sp > 1,
        )
        x = _select_stage0(x[0])  # [T, hidden], valid on stage 0
        logits = _head_logits(params, x, config)  # [T, vocab] f32
        return logits, cache

    sharded = shard_map(
        step,
        mesh=plan.mesh,
        in_specs=(
            param_specs(params_like),
            P(None, None),
            cache_specs(kv_quant),
            P(),
        ),
        out_specs=(
            P(None, None),
            cache_specs(kv_quant),
        ),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(2,))


def build_sharded_verify_rows(config: LlamaConfig, plan: MeshPlan,
                              params_like: dict | None = None,
                              kv_quant: str | None = None):
    """Compile the PER-ROW speculation-verification pass: forward
    ``tokens [B, T]`` (each row: its last emitted token + K proposals,
    0-padded) from per-row positions ``pos [B]`` and return logits at
    EVERY position for every row (``[B, T, vocab] f32``) — the serving
    twin of :func:`build_sharded_verify`. Each row writes its own K+1 KV
    slots at its own frontier; rejected slots hold garbage that the next
    round's fed range fully overwrites before it becomes attendable (the
    same invariant as the single-stream speculation plane). ``plan.sp > 1``
    (r5): every row's fed block runs chunk-replicated over sp against the
    sequence-sharded cache — per-row range writes
    (``ring.sp_range_cache_write`` with ``pos [B]``, rows may straddle
    shard boundaries) + the per-row-masked chunk attend.
    """
    heads_l, kv_heads_l = _local_counts(config, plan.tp)

    def step(params, tokens, cache, pos):
        cos, sin = rope_tables_for(config, cache.max_seq * plan.sp)
        x = llama.embed_tokens(params, tokens, config)
        x, cache = _pipeline_layers(
            x, params, cache, cos, sin, pos, config,
            plan.num_stages, heads_l, kv_heads_l, sp=plan.sp,
            sp_chunk=plan.sp > 1,
        )
        x = _select_stage0(x)  # [B, T, hidden], valid on stage 0
        logits = _head_logits(params, x, config)
        return logits, cache

    sharded = shard_map(
        step,
        mesh=plan.mesh,
        in_specs=(
            param_specs(params_like),
            P(DP, None),
            cache_specs(kv_quant),
            P(DP),
        ),
        out_specs=(
            P(DP, None, None),
            cache_specs(kv_quant),
        ),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(2,))


def build_interleaved_verify_rows(config: LlamaConfig, plan: MeshPlan,
                                  params_like: dict | None = None,
                                  kv_quant: str | None = None):
    """Interleaved-microbatch twin of :func:`build_sharded_verify_rows`.

    The serialized per-row verify runs S pipeline cycles with EVERY stage
    computing the full batch and one result kept. Here the dp-local batch's
    S microbatches stream through the stages GPipe-style (microbatch ``m``
    is at stage ``t - m`` on cycle ``t``; 2S-1 cycles total), so each cycle
    does B/S rows of useful layer work per stage — total layer FLOPs and KV
    traffic drop ~S/2× (one pass has a fill/drain bubble the steady-state
    interleaved decode does not). Stage S-1 collects each microbatch's
    final hidden states; the head (rms_norm + lm_head + tp gather) then
    runs on the reassembled ``[B, T, H]`` exactly like the serialized
    program, so logits are bit-identical per row.

    Same signature and specs as ``build_sharded_verify_rows``; requires
    ``B_local % num_stages == 0``. ``plan.sp > 1`` (r5) composes the same
    way as the serialized verify: each microbatch's fed block runs
    chunk-replicated over sp with per-row range writes. Int8 weights need
    a pinned quant backend for bit-identity with the serialized program
    (same contract as ``build_interleaved_decode``)."""
    heads_l, kv_heads_l = _local_counts(config, plan.tp)
    S = plan.num_stages

    def step(params, tokens, cache, pos):
        b, t = tokens.shape
        if b % S:
            raise ValueError(
                f"interleaved verify needs the dp-local batch ({b}) "
                f"divisible by num_stages ({S})"
            )
        bm = b // S
        cos, sin = rope_tables_for(config, cache.max_seq * plan.sp)
        my_stage = jax.lax.axis_index(STAGE)
        perm = [(i, (i + 1) % S) for i in range(S)]
        x_all = llama.embed_tokens(params, tokens, config)  # [B,T,H]

        def body(c_t, carry):
            x, ck, cv, y = carry
            # stage 0 injects microbatch c_t
            base_in = jnp.minimum(c_t, S - 1) * bm
            xin = jax.lax.dynamic_slice_in_dim(x_all, base_in, bm, 0)
            x = jnp.where((my_stage == 0) & (c_t < S), xin, x)
            # this stage's resident microbatch
            m_res = c_t - my_stage
            valid = (m_res >= 0) & (m_res < S)
            base = jnp.clip(m_res, 0, S - 1) * bm
            pos_rows = jax.lax.dynamic_slice_in_dim(pos, base, bm, 0)
            rows = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, base, bm, 1),
                KVCache(k=ck, v=cv),
            )
            h, rows = llama.forward_layers(
                params["layers"], x, rows, cos, sin, pos_rows, config,
                num_heads=heads_l, num_kv_heads=kv_heads_l, tp_axis=TP, ep_axis=EP,
                sp_axis=SP, sp_size=plan.sp, sp_chunk=plan.sp > 1,
                write_gate=valid,
            )
            x = jnp.where(valid, h, x)
            ck, cv = jax.tree.map(
                lambda buf, r: jax.lax.dynamic_update_slice_in_dim(
                    buf, r, base, 1),
                (ck, cv), (rows.k, rows.v),
            )
            # stage S-1 collects the finished microbatch's hidden states
            collect = valid & (my_stage == S - 1)
            cur = jax.lax.dynamic_slice_in_dim(y, base, bm, 0)
            y = jax.lax.dynamic_update_slice_in_dim(
                y, jnp.where(collect, x, cur), base, 0)
            x = jax.lax.ppermute(x, STAGE, perm)
            return x, ck, cv, y

        x0 = jnp.zeros((bm, t, config.hidden_size), config.jax_dtype)
        y0 = jnp.zeros((b, t, config.hidden_size), config.jax_dtype)
        _, ck, cv, y = jax.lax.fori_loop(
            0, 2 * S - 1,
            lambda c_t, carry: body(c_t, carry),
            (x0, cache.k, cache.v, y0),
        )
        # broadcast stage S-1's collection, then the head — vocab-split
        # over the stage axis when that cannot change the quant backend
        # class (same _head_split_safe gate as the interleaved decode), so
        # each stage reads V/S of the lm_head instead of all of it
        y = jax.lax.psum(
            jnp.where(my_stage == S - 1, y, jnp.zeros_like(y)), STAGE)
        y = llama.head_norm(params, y, config)
        hw = params["lm_head"]
        if S > 1 and _head_split_safe(hw, S):
            logits = quant.dense(y, _head_chunk(hw, my_stage, S)).astype(
                jnp.float32)
            logits = jax.lax.all_gather(logits, STAGE, axis=-1, tiled=True)
        else:
            logits = quant.dense(y, hw).astype(jnp.float32)
        logits = jax.lax.all_gather(logits, TP, axis=-1, tiled=True)
        return logits, KVCache(k=ck, v=cv)

    sharded = shard_map(
        step,
        mesh=plan.mesh,
        in_specs=(
            param_specs(params_like),
            P(DP, None),
            cache_specs(kv_quant),
            P(DP),
        ),
        out_specs=(
            P(DP, None, None),
            cache_specs(kv_quant),
        ),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(2,))


def build_sharded_prefill(config: LlamaConfig, plan: MeshPlan,
                          params_like: dict | None = None,
                          microbatch: int = 1,
                          kv_quant: str | None = None,
                          with_offset: bool = False):
    """Compile the multi-chip prompt pass.

    Signature: ``(params, tokens [B, T], cache, last_index [B]) ->
    (logits [B, vocab] f32, cache)``. With ``plan.sp == 1``, ``T`` may be any
    bucketed length; with sequence parallelism (``sp > 1``) ``T`` must be a
    multiple of sp no larger than max_seq — each sp shard runs ring attention
    over its ``T/sp`` chunk (:mod:`cake_tpu.ops.ring`), so prefill FLOPs and
    ring traffic scale with the prompt, not the window, and the roped KV is
    redistributed into the range-sharded cache layout
    (``ring.sp_chunked_cache_write``). Positions past the prompt hold zero KV
    that decode steps overwrite slot-by-slot before they ever become
    attendable.

    ``microbatch = M > 1`` (requires ``sp == 1``, ``num_stages > 1``,
    ``T % M == 0``) selects GPipe-style pipelined prefill: the prompt is
    split into M chunks that stream through the stages concurrently
    (:func:`_pipelined_prefill_layers`) — ~num_stages× prompt throughput
    once the pipeline fills, identical results.

    ``with_offset = True`` (requires ``microbatch == 1``) appends a
    trailing scalar ``pos0`` argument: the fed tokens occupy global
    positions ``pos0..pos0+T-1`` and attend the cache's committed
    positions below ``pos0`` — the shared-prefix serving path, where a
    common system prompt is prefilled once and each stream's remainder is
    prefilled at the prefix boundary. With ``sp > 1`` (r5) the remainder
    bucket runs REPLICATED over the sp axis against the range-sharded
    cache (``ring.sp_range_cache_write`` + the T>1 distributed-flash
    chunk attend) — sp× redundant FLOPs on the remainder in exchange for
    composing the prefix store with a sequence-sharded window.
    """
    heads_l, kv_heads_l = _local_counts(config, plan.tp)
    if microbatch > 1 and plan.sp != 1:
        raise ValueError("pipelined (microbatch) prefill requires sp == 1")
    if microbatch > 1 and plan.num_stages < 2:
        raise ValueError(
            "pipelined (microbatch) prefill requires num_stages > 1 — with "
            "one stage there is nothing to overlap, only per-chunk overhead"
        )
    if with_offset and microbatch > 1:
        raise ValueError("offset prefill requires microbatch == 1")
    chunk_mode = with_offset and plan.sp > 1

    def step(params, tokens, cache, last_index, *rest):
        pos0 = rest[0] if with_offset else 0
        cos, sin = rope_tables_for(config, cache.max_seq * plan.sp)
        x = llama.embed_tokens(params, tokens, config)
        if microbatch > 1:
            b, t = tokens.shape
            if t % microbatch:
                raise ValueError(
                    f"prompt bucket {t} not divisible into {microbatch} "
                    "pipeline chunks"
                )
            chunk = t // microbatch
            # [B, T, H] -> [M, B, C, H]
            x_chunks = x.reshape(b, microbatch, chunk, -1).transpose(
                1, 0, 2, 3
            )
            y, ck, cv = _pipelined_prefill_layers(
                x_chunks, params["layers"], cache.k, cache.v, cos, sin,
                config, plan.num_stages, heads_l, kv_heads_l,
            )
            cache = KVCache(k=ck, v=cv)
            # [M, B, C, H] -> [B, T, H] (valid on stage 0; selected below)
            x = y.transpose(1, 0, 2, 3).reshape(b, t, -1)
        else:
            # sp_prefill explicit: a bucketed prompt can give each shard a
            # ONE-token chunk, which the T>1 heuristic would misroute to the
            # decode branch (silently wrong logits — r2 code-review finding)
            x, cache = _pipeline_layers(
                x, params, cache, cos, sin, pos0,
                config, plan.num_stages, heads_l, kv_heads_l, sp=plan.sp,
                sp_prefill=not chunk_mode, sp_chunk=chunk_mode,
                **_true_rows(config, tokens, last_index,
                             whole=chunk_mode or plan.sp == 1),
            )
        # slice the wanted position first so the cross-stage select moves
        # [B, hidden], not the whole [B, T, hidden] activation
        # (chunk mode computes the bucket replicated over sp, so the sp==1
        # owner-select applies)
        x_last = _select_last_sp(x, last_index, 1 if chunk_mode else plan.sp)
        x_last = _select_stage0(x_last)
        logits = _head_logits(params, x_last, config)
        return logits, cache

    kv_specs = cache_specs(kv_quant, held=config.cache_plan)
    in_specs = [
        param_specs(params_like),
        P(DP, None) if chunk_mode else P(DP, SP),
        kv_specs,
        P(DP),
    ]
    if with_offset:
        in_specs.append(P())
    sharded = shard_map(
        step,
        mesh=plan.mesh,
        in_specs=tuple(in_specs),
        out_specs=(
            P(DP, None),
            kv_specs,
        ),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(2,))
