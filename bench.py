"""Benchmark: fused single-chip Llama-3-8B decode throughput.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The reference publishes no numbers (SURVEY.md: its deployment of record is
Llama-3-8B layer-split across a Titan X Pascal + M1 Max over an ngrok tunnel,
tokens/sec measured at runtime but never published — master.rs:57-65). With
no published baseline to divide by, ``vs_baseline`` reports the fraction of
the *HBM-bandwidth roofline* for this chip and model (ideal decode tok/s =
HBM bytes/s / model bytes; the closer to 1.0 the better). That makes the
number comparable across rounds and meaningful in absolute terms. The
peaks are published per-chip numbers (cake_tpu/utils/chips.py); on a
device that file has no row for -- a CPU included -- ``vs_baseline`` is
null and ``baseline`` says so.

The bench runs the preset it was asked for on the device JAX gives it and
names that device in every row (``platform``, ``device_kind``,
``device_count``). There is no probe, no step-down and no CPU re-run: when
device init or the preset fails, the traceback is the result and the exit
code is non-zero. The 8b and small presets are sized for a TPU and are
refused anywhere else; ``make bench`` is an explicit CPU smoke
(``JAX_PLATFORMS=cpu``, tiny preset); its rows say ``"platform": "cpu"``.

Knobs (env):
  CAKE_BENCH_PRESET  8b (default) | small | tiny  — model size
  CAKE_BENCH_STEPS   timed decode steps (default 128)
  CAKE_BENCH_SEQ     KV capacity (default 512)
  CAKE_BENCH_QUANT   int8 | int4 — quantize linear weights (per-channel
                     symmetric; int4 is packed two-per-byte)
  CAKE_BENCH_MULTISTEP  fused decode steps per dispatch (default 16; 1 =
                        one program per token like the reference's loop).
                        What each value reaches of the HBM roofline: not
                        measured on the chip tool.
  CAKE_BENCH_OBS=1   decode tok/s with observability off vs on (tracer +
                     flight recorder) through the generator hot path;
                     emits the overhead percentage (`make perf-smoke`
                     bounds the disabled-path micro-cost), plus a second
                     row repeating the off/on comparison through the
                     HTTP serve plane where tracing mints per-request
                     spans (reqtrace) — target within 3% of untraced.
  CAKE_BENCH_SERVE=1 end-to-end HTTP serving: loadgen clients against the
                     --mode serve plane (cake_tpu/serve) over the same
                     engine — aggregate tok/s through the socket plus
                     TTFT p50/p95, next to the in-process serving rows
                     (CAKE_BENCH_BATCH sets the client count).
  CAKE_BENCH_CONSTRAIN=1 grammar-constrained HTTP serving
                     (cake_tpu/constrain): loadgen --workload json
                     requests (response_format json_schema, responses
                     asserted json.loads-parseable) vs the same server
                     unconstrained — constrained tok/s with
                     vs_baseline = constrained/unconstrained.
  CAKE_BENCH_GATEWAY=1 routing-gateway overhead (cake_tpu/gateway): the
                     same loadgen workload against one serve replica
                     directly vs through a gateway fronting it —
                     gateway tok/s with vs_baseline = gateway/direct
                     plus the TTFT p50 the extra hop adds.
  CAKE_BENCH_KVPOOL=1 paged-KV churn (cake_tpu/kvpool): churn tok/s on
                     the paged layout vs the slot layout vs the paged
                     steady batch, legs interleaved A/B/A/B —
                     vs_baseline = churn_paged/steady_paged (ROADMAP's
                     within-25% churn target).
  CAKE_BENCH_DISAGG=1 disaggregated prefill/decode tiers
                     (cake_tpu/disagg): the mixed-prefill workload
                     against a tiered fleet (1 prefill + 1 decode, KV
                     pages over the transfer channel) vs 2 mixed
                     replicas, legs interleaved A/B/A/B — decode-tier
                     TPOT p95 with vs_baseline = tiered/mixed (< 1.0 =
                     the tier split wins), TTFT p95 split by prompt
                     bucket.
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _sync(x) -> None:
    """Synchronize by fetching a scalar of every leaf to the host: a
    device->host read of the dependency chain cannot return before the
    work is done. All leaves are fetched so async allocation failures
    surface here (inside the caller's try), and the slice happens
    on-device so only one element transfers. Whether
    ``block_until_ready`` alone would do beside the chip is what
    chip_smoke.py's ``sync_check`` line measures."""
    for leaf in jax.tree.leaves(x):
        np.asarray(leaf.ravel()[:1])

# the chip spec tables live in ONE place (cake_tpu/utils/chips.py) so
# bench.py and the measurement tools can never disagree on a roofline
# denominator
from cake_tpu.utils.chips import (  # noqa: E402
    HBM_GBPS as _HBM_GBPS,
    PEAK_TFLOPS as _PEAK_TFLOPS,
    device_spec as _device_spec,
)
from cake_tpu.utils.compile_cache import (  # noqa: E402
    configure as _configure_compile_cache,
)


def _no_peaks(dev) -> dict:
    return {"vs_baseline": None,
            "baseline": f"not measured: no published peaks for "
                        f"device_kind {dev.device_kind!r}"}


def _vs_roofline(dev, params, tok_s: float) -> dict:
    """``vs_baseline``/``baseline`` of a decode row: its fraction of the
    single-stream weights-bound ideal (HBM bytes/s / model bytes). A
    device without published peaks gets null, never an assumed chip."""
    try:
        gbps = _device_spec(dev, _HBM_GBPS)
    except KeyError:
        return _no_peaks(dev)
    roofline = gbps / (_param_bytes(params) / 1e9)
    return {"vs_baseline": round(tok_s / roofline, 4),
            "baseline": f"single_stream_hbm_roofline_{roofline:.1f}tok/s"}


def _vs_peak(dev, flops_per_s: float, label: str) -> dict:
    """``vs_baseline``/``baseline`` of a prefill row: its fraction of the
    chip's bf16 peak (null off a known chip, like :func:`_vs_roofline`)."""
    try:
        peak = _device_spec(dev, _PEAK_TFLOPS) * 1e12
    except KeyError:
        return _no_peaks(dev)
    return {"vs_baseline": round(flops_per_s / peak, 4),
            "baseline": f"{label}_{peak / 1e12:.0f}tflops"}


def _mtag(preset: str) -> str:
    """Metric model tag: family_preset ("llama_8b" by default; a
    CAKE_BENCH_FAMILY run tags its own family so family rows can never be
    mistaken for the llama numbers of record)."""
    fam = os.environ.get("CAKE_BENCH_FAMILY", "llama")
    return f"{fam}_{preset}"


def _wtag(quant: str, kv_quant: str | None) -> str:
    """Metric tag for the weight/KV dtype combination."""
    tag = quant if quant in ("int8", "int4") else "bf16"
    return tag + "_kv8" if kv_quant else tag


def _matmul_flops(params, config, t: int) -> float:
    """Matmul FLOPs of a T-token prompt pass: 2 * matmul-params * T. The
    embed table is a lookup, not a matmul, so it is excluded; attention
    FLOPs are also excluded — conservative for MFU-style ratios."""
    n = sum(x.size for x in jax.tree.leaves(params))
    return 2.0 * (n - config.vocab_size * config.hidden_size) * t


def _kv_quant() -> str | None:
    """CAKE_BENCH_KV=int8: run with the quantized KV cache (half the cache
    HBM -> roughly double the servable batch x window on a fixed budget).
    Honored by EVERY bench path (single-stream, batched, prefill,
    speculative) — the HBM preflight prices it, so the paths must actually
    allocate it."""
    kv = os.environ.get("CAKE_BENCH_KV", "") or None
    if kv not in (None, "int8"):
        sys.exit(f"error: CAKE_BENCH_KV must be 'int8', got {kv!r}")
    return kv


def _config(preset: str):
    """CAKE_BENCH_FAMILY=mistral|qwen2|gemma swaps the 8b preset's
    architecture for that family's 7B-class geometry (random weights —
    tok/s only): mistral prices the sliding-window mask + windowed flash
    plane on-chip; qwen2 the biased-GQA 3584/28-layer geometry; gemma the
    MHA/head_dim-256/GeGLU/tied-head shape (its 256k-vocab embed stays
    bf16, so CAKE_BENCH_QUANT=int8 is what fits a v5e). Default family:
    llama."""
    from cake_tpu.models.config import (LlamaConfig, gemma_7b, llama3_8b,
                                        mistral_7b, qwen2_7b, tiny)

    seq = int(os.environ.get("CAKE_BENCH_SEQ", "512"))
    fam = os.environ.get("CAKE_BENCH_FAMILY", "llama")
    if fam != "llama" and preset != "8b":
        # the small/tiny presets are llama geometry — benching them under
        # a family tag would mislabel the row
        sys.exit(f"error: CAKE_BENCH_FAMILY={fam} requires the 8b preset "
                 "(small and tiny are llama geometry)")
    if preset == "8b":
        if fam == "mistral":
            return mistral_7b(max_seq_len=seq)
        if fam == "qwen2":
            return qwen2_7b(max_seq_len=seq)
        if fam == "gemma":
            return gemma_7b(max_seq_len=seq)
        if fam != "llama":
            sys.exit(f"error: CAKE_BENCH_FAMILY must be llama|mistral|"
                     f"qwen2|gemma, got {fam!r}")
        return llama3_8b(max_seq_len=seq)
    if preset == "small":
        return LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=16, num_attention_heads=32,
            num_key_value_heads=8, max_seq_len=seq,
        )
    return tiny(max_seq_len=seq, dtype="bfloat16")


def _param_bytes(params) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))


def _ledger_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "bench_results.jsonl")


def _emit(row: dict, dev, baseline: str | None = None, **extra) -> None:
    """Print the benchmark row (ONE JSON line on stdout per invocation,
    flushed the moment the row lands) and append it to
    bench_results.jsonl with a timestamp. Every row names the device it
    ran on -- ``platform``, ``device_kind`` and ``device_count`` as JAX
    reports them -- so a CPU smoke can never be read as a chip number.
    The jsonl is a deliberately TRACKED measurement ledger (like
    KERNELS_TPU.json), which is why it is not in .gitignore.

    ``baseline`` names what ``vs_baseline`` divides by, so every row is
    self-describing; ``extra`` carries metric-family companions
    (tokens_per_dispatch, acceptance, p95_ms, busy_s ...) into both the
    stdout line and the ledger record."""
    if baseline is not None:
        row = dict(row, baseline=baseline)
    if dev.platform != "tpu":
        # a number from a CPU run never carries a chip metric's name
        row = dict(row, metric=row["metric"].replace(
            "_1chip", f"_{dev.platform}"))
    row = dict(row, **extra, platform=dev.platform,
               device_kind=dev.device_kind,
               device_count=len(jax.devices()))
    print(json.dumps(row), flush=True)
    # "device" is the key tools/benchdiff groups history by (and what the
    # 07-31 rows carry)
    rec = dict(row, device=dev.device_kind,
               stamp=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
    snap = _metrics_snapshot()
    if snap:
        rec["metrics"] = snap
    with open(_ledger_path(), "a") as f:
        f.write(json.dumps(rec) + "\n")


def _metrics_snapshot() -> dict:
    """Non-empty obs-registry series for the ledger record, so a bench row
    carries dispatch/admission percentiles and wire bytes alongside the
    single throughput number. The snapshot is the process-cumulative
    registry at emit time: one bench phase runs per process (main()
    dispatches exactly one _run_* path), so the
    only extra samples are that phase's own warm-up/compile dispatches.
    Zero-valued instruments created at import are dropped."""
    from cake_tpu.obs import metrics as obs_metrics

    out = {}
    for name, inst in obs_metrics.registry().snapshot().items():
        kind = inst.get("type")
        if kind == "histogram" and inst.get("count"):
            out[name] = inst
        elif kind in ("counter", "gauge") and inst.get("value"):
            out[name] = inst
    return out


def _run_prefill(config, params, preset, quant, dev) -> int:
    """Prefill (TTFT-side) throughput: tokens/s of one warm prompt pass at
    T = CAKE_BENCH_SEQ/2 against a CAKE_BENCH_SEQ KV window. This is where
    the Pallas flash kernel carries the long-context story (132x over
    XLA-materialized scores at T=2048/S=8192 on v5e — KERNELS_TPU.json);
    the reference hard-caps context at 4096 and materializes full score
    matrices (attention.rs:59-80)."""
    from cake_tpu.ops.kvcache import init_cache
    from cake_tpu.runtime.generator import prefill_fn

    kv_quant = _kv_quant()
    t = config.max_seq_len // 2
    prefill = jax.jit(partial(prefill_fn, config=config),
                      donate_argnames=("cache",))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, config.vocab_size, (1, t)),
        jnp.int32,
    )
    last = jnp.asarray([t - 1], jnp.int32)

    cache = init_cache(config, batch=1, max_seq=config.max_seq_len,
                       quant=kv_quant)
    t0 = time.perf_counter()
    logits, cache = prefill(params, tokens, cache, last)
    _sync(logits)
    ttft_cold = time.perf_counter() - t0  # includes compile

    # Each iteration's cache is allocated and synced OUTSIDE its timed
    # window (prefill donates the cache, so a fresh one is needed per
    # iteration). Timed per-iteration — NOT by pre-allocating all iters
    # caches at once, which at 8B/16K-window would be ~17 GB of cache and
    # OOM the chip before the bench starts.
    iters = 8
    dts = []
    for _ in range(iters):
        cache = init_cache(config, batch=1, max_seq=config.max_seq_len,
                           quant=kv_quant)
        _sync(cache)
        t0 = time.perf_counter()
        logits, cache = prefill(params, tokens, cache, last)
        _sync(logits)
        dts.append(time.perf_counter() - t0)
    dt = sum(dts) / iters

    wtag = _wtag(quant, kv_quant)
    # vs_baseline: fraction of the chip's bf16 peak the prompt pass sustains
    flops = _matmul_flops(params, config, t)
    _emit({
        "metric": f"prefill_tokens_per_sec_{_mtag(preset)}_{wtag}_1chip_t{t}",
        "value": round(t / dt, 3),
        "unit": "tokens/s",
    }, dev, **_vs_peak(dev, flops / dt, "mfu_vs_bf16_peak"))
    sys.stderr.write(
        f"device={dev.device_kind} T={t} window={config.max_seq_len} "
        f"warm_prefill={dt * 1e3:.1f}ms ttft_cold={ttft_cold:.2f}s\n"
    )
    return 0


def _run_batched(config, params, preset, quant, settings, dev,
                 batch, steps, multistep) -> int:
    """Multi-stream aggregate decode throughput (CAKE_BENCH_BATCH=N).

    Drives the serving stack itself — the per-row mesh decode program
    (parallel/pipeline per_row mode on a 1-device mesh), N streams at their
    own positions with per-stream keys. Weight reads amortize over the
    batch, so aggregate tok/s can exceed the single-stream weights-bound
    roofline (``vs_baseline > 1``) — the axis the single-request reference
    has no answer to (SURVEY.md §0: no batching of concurrent requests).
    """
    from cake_tpu.parallel.mesh import (
        MeshPlan,
        init_cache_on_mesh,
        shard_params,
    )
    from cake_tpu.parallel.pipeline import (
        build_sharded_decode,
        build_sharded_prefill,
    )

    kv_quant = _kv_quant()
    plan = MeshPlan.build(config, devices=jax.devices()[:1])
    params = shard_params(params, plan.mesh)
    cache = init_cache_on_mesh(config, plan.mesh, batch=batch,
                               max_seq=config.max_seq_len, quant=kv_quant)
    prefill = build_sharded_prefill(config, plan, params_like=params,
                                    kv_quant=kv_quant)
    decode = build_sharded_decode(config, settings, plan, params_like=params,
                                  steps=multistep, per_row=True,
                                  kv_quant=kv_quant)

    prompt_len = 8
    tokens = jnp.tile(
        jnp.asarray([[1, 5, 9, 14, 3, 8, 2, 4]], jnp.int32), (batch, 1)
    )
    t_pf0 = time.perf_counter()
    logits, cache = prefill(
        params, tokens, cache,
        jnp.full((batch,), prompt_len - 1, jnp.int32),
    )
    _sync(logits)
    ttft_s = time.perf_counter() - t_pf0

    base = jax.random.PRNGKey(settings.seed)
    keys = jnp.stack([jax.random.fold_in(base, i) for i in range(batch)])
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    pos = jnp.full((batch,), prompt_len, jnp.int32)
    history = jnp.full((batch, settings.repeat_last_n), -1, jnp.int32)
    hist_slot = jnp.zeros((batch,), jnp.int32)

    per = max(1, multistep)
    max_dispatches = (config.max_seq_len - prompt_len) // per - 3
    if max_dispatches < 1:
        sys.exit(
            f"error: CAKE_BENCH_SEQ={config.max_seq_len} too small for "
            f"CAKE_BENCH_MULTISTEP={multistep}"
        )
    dispatches = max(1, min(steps // per, max_dispatches))

    index = jnp.ones((batch,), jnp.int32)  # per-stream token indices

    def step_once(tok, cache, history, hist_slot, pos, index):
        toks, cache, history, hist_slot = decode(
            params, tok, cache, pos, keys, history, hist_slot, index,
        )
        # per_row decode returns [B] for steps==1, [steps, B] otherwise
        last = toks if per == 1 else toks[-1]
        return (last.astype(jnp.int32), cache, history, hist_slot,
                pos + per, index + per)

    for _ in range(3):  # compile + warm-up
        tok, cache, history, hist_slot, pos, index = step_once(
            tok, cache, history, hist_slot, pos, index
        )
    _sync(tok)
    t0 = time.perf_counter()
    for _ in range(dispatches):
        tok, cache, history, hist_slot, pos, index = step_once(
            tok, cache, history, hist_slot, pos, index
        )
    _sync(tok)
    dt = time.perf_counter() - t0

    agg_tok_s = dispatches * per * batch / dt
    model_gb = _param_bytes(params) / 1e9
    wtag = _wtag(quant, kv_quant)
    _emit({
        "metric": f"decode_tokens_per_sec_{_mtag(preset)}_{wtag}_1chip_b{batch}",
        "value": round(agg_tok_s, 3),
        "unit": "tokens/s",
    }, dev, **_vs_roofline(dev, params, agg_tok_s),
        per_stream_tok_s=round(agg_tok_s / batch, 3))
    sys.stderr.write(
        f"device={dev.device_kind} params={model_gb:.2f}GB batch={batch} "
        f"per-stream {agg_tok_s / batch:.1f}tok/s ttft_cold={ttft_s:.2f}s "
        f"timed_tokens={dispatches * per * batch} multistep={per}\n"
    )
    return 0


def _run_ttft(config, params, preset, quant, dev) -> int:
    """CAKE_BENCH_TTFT=1: p50/p95 time-to-first-token at CAKE_BENCH_SEQ/2
    prompt length — warm prefill + first-token sample per trial, the
    latency metric BASELINE.json names alongside tok/s (the reference
    never measures TTFT at all; its master only logs steady-state
    tokens/sec, master.rs:57-65)."""
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.generator import LlamaGenerator

    kv_quant = _kv_quant()
    trials = int(os.environ.get("CAKE_BENCH_TTFT_TRIALS", "16"))
    t = config.max_seq_len // 2
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    gen = LlamaGenerator(config, params, settings=settings,
                         kv_quant=kv_quant)
    rng = np.random.default_rng(0)
    prompt0 = rng.integers(1, config.vocab_size, t).tolist()
    gen.set_prompt(prompt0)
    gen.next_token(0)  # compile + warm
    lat = []
    for i in range(trials):
        prompt = rng.integers(1, config.vocab_size, t).tolist()
        gen.set_prompt(prompt)
        t0 = time.perf_counter()
        tok = gen.next_token(0)
        lat.append(time.perf_counter() - t0)
        assert tok.id >= 0
    lat.sort()
    p50 = lat[len(lat) // 2]
    p95 = lat[min(len(lat) - 1, int(len(lat) * 0.95))]
    wtag = _wtag(quant, kv_quant)
    # vs_baseline: how close the warm prompt pass runs to the chip's peak
    flops = _matmul_flops(params, config, t)
    _emit({
        "metric": f"ttft_p50_ms_{_mtag(preset)}_{wtag}_1chip_t{t}",
        "value": round(p50 * 1e3, 2),
        "unit": "ms",
    }, dev,
        **_vs_peak(dev, flops / p50, "prefill_mfu_at_p50_vs_bf16_peak"),
        p95_ms=round(p95 * 1e3, 2), prompt_tokens=t)
    sys.stderr.write(
        f"device={dev.device_kind} T={t} trials={trials} "
        f"p50={p50 * 1e3:.1f}ms p95={p95 * 1e3:.1f}ms\n"
    )
    return 0


def _run_obs_overhead(config, params, preset, quant, dev, steps) -> int:
    """CAKE_BENCH_OBS=1: decode tokens/sec with the observability planes
    OFF vs ON (tracer + flight recorder enabled, in-memory only) through
    the LlamaGenerator hot path — the single-stream loop that calls
    span()/record()/histogram per token. The figure of merit is the
    overhead percentage; the obs satellite contract is that OFF costs an
    attribute check per call site (`make perf-smoke` bounds that
    micro-cost; this row prices the enabled planes). A second row does
    the same off/on comparison through the HTTP serve plane, where the
    tracer additionally carries the per-request span set (serve.queue →
    session.emit, cake_tpu/obs/reqtrace); the design target is traced
    serve tok/s within 3% of untraced."""
    from cake_tpu.obs import flight, trace
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.generator import LlamaGenerator

    kv_quant = _kv_quant()
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    n = max(8, min(4 * steps, config.max_seq_len - 16))
    prompt = [1, 5, 9, 14, 3, 8, 2, 4]

    def run(label: str) -> float:
        gen = LlamaGenerator(config, params, settings=settings,
                             kv_quant=kv_quant)
        gen.set_prompt(prompt)
        # warm BOTH programs before the clock: next_token(0) compiles only
        # prefill, next_token(1) the decode step — a timed first decode
        # would put one ~600 ms XLA compile inside a ~120 ms measurement
        # window and swamp the obs delta being measured
        gen.next_token(0)
        gen.next_token(1)
        t0 = time.perf_counter()
        for i in range(2, n):
            gen.next_token(i)
        dt = time.perf_counter() - t0
        sys.stderr.write(f"obs={label}: {(n - 2) / dt:.1f} tok/s\n")
        return (n - 2) / dt

    def obs_leg(enabled: bool) -> float:
        if not enabled:
            return run("off")
        trace.tracer().start()
        flight.recorder().enable()
        try:
            return run("on")
        finally:
            trace.tracer().stop()
            flight.recorder().disable()
            flight.recorder().clear()
            trace.tracer().clear()

    # warm leg (pays the compiles), then ABBA: host throughput drifts
    # monotonically over a CPU bench, and a single off-then-on pair books
    # that drift as obs overhead — off-on-on-off cancels a linear drift
    obs_leg(False)
    obs_legs = [obs_leg(e) for e in (False, True, True, False)]
    off = (obs_legs[0] + obs_legs[3]) / 2
    on = (obs_legs[1] + obs_legs[2]) / 2
    overhead_pct = (off / on - 1.0) * 100.0
    wtag = _wtag(quant, kv_quant)
    _emit({
        "metric": f"decode_obs_overhead_pct_{_mtag(preset)}_{wtag}_1chip",
        "value": round(overhead_pct, 2),
        "unit": "%",
        "vs_baseline": round(on / off, 4),
    }, dev, baseline=f"obs_off_{off:.1f}tok/s",
        obs_off_tok_s=round(off, 2), obs_on_tok_s=round(on, 2),
        legs_tok_s=[round(x, 2) for x in obs_legs], timed_tokens=n - 2)

    # -- prof leg: step-phase profiler OFF vs ON (default coarse sampling)
    # through the BatchGenerator step loop — the engine that carries the
    # phase stamps. A/B/A/B interleaved: two off and two on windows
    # alternating over ONE engine (the profiler is a process singleton, so
    # re-pointing the stride needs no rebuild and no recompile), averaging
    # out drift that a single off-then-on pair would book as overhead.
    import dataclasses as _dc

    from cake_tpu.obs import prof as _prof
    from cake_tpu.runtime.batch_generator import BatchGenerator

    clients = 2
    # longer timed window than the trace legs: the prof delta is small, so
    # a ~70 ms window would drown it in scheduler noise
    k = max(64, min(4 * steps, config.max_seq_len - 48))
    cfg_prof = _dc.replace(config, eos_token_id=-1)  # streams never EOS
    pgen = BatchGenerator(cfg_prof, params, settings=settings,
                          kv_quant=kv_quant)
    # prime like the scheduler: a live batch of retired slots, so the
    # legs' enqueues ride continuous admission
    pgen.set_prompts([[1]] * clients)
    for s in pgen.streams:
        s.done = True
    sample0 = _prof.profiler().sample_every
    sample_on = sample0 if sample0 > 0 else 64

    def prof_leg(sample: int, sid0: int) -> float:
        _prof.profiler().set_sample(sample)
        for j in range(clients):
            pgen.enqueue(prompt, sid0 + j)
        for _ in range(4):  # admit + warm (first leg pays the compiles)
            pgen.step()
        t0 = time.perf_counter()
        for _ in range(k):
            pgen.step()
        dt = time.perf_counter() - t0
        # retire the slots the same way the priming idiom does, so the
        # next leg's enqueues admit into them fresh
        for s in pgen.streams:
            s.done = True
        pgen.step()
        return (k * clients) / dt

    try:
        prof_leg(0, sid0=990)  # warm: pays admission + decode compiles
        legs = []
        # ABBA order: host throughput decays monotonically over a CPU
        # bench (turbo/thermal), and off-on-off-on would book that decay
        # as profiler overhead; off-on-on-off cancels a linear drift
        for i, sample in enumerate((0, sample_on, sample_on, 0)):
            tok_s = prof_leg(sample, sid0=1000 + 10 * i)
            legs.append(round(tok_s, 2))
            sys.stderr.write(
                f"prof sample={sample}: {tok_s:.1f} tok/s\n")
    finally:
        _prof.profiler().set_sample(sample0)
    prof_off = (legs[0] + legs[3]) / 2
    prof_on = (legs[1] + legs[2]) / 2
    prof_pct = (prof_off / prof_on - 1.0) * 100.0
    _emit({
        "metric": f"decode_prof_overhead_pct_{_mtag(preset)}_{wtag}_1chip",
        "value": round(prof_pct, 2),
        "unit": "%",
        "vs_baseline": round(prof_on / prof_off, 4),
    }, dev, baseline=f"prof_off_{prof_off:.1f}tok/s",
        legs_tok_s=legs, sample_every=sample_on,
        timed_steps=k, clients=clients)

    # -- serve leg: the same off/on comparison through the HTTP plane,
    # where tracing also mints per-request spans (reqtrace) on every
    # queue/admit/prefill/emit transition rather than per-token records
    from cake_tpu.runtime.batch_generator import BatchGenerator
    from cake_tpu.serve.api import start_api_server
    from cake_tpu.serve.scheduler import Scheduler
    from cake_tpu.tools import loadgen

    clients = 2
    max_tokens = max(4, min(steps, config.max_seq_len - 16))
    gen = BatchGenerator(config, params, settings=settings,
                         kv_quant=kv_quant)
    sched = Scheduler(gen, queue_depth=4 * clients)
    sched.start(max_concurrent=clients, warm_prompt_len=8)
    srv = start_api_server(sched)
    url = f"http://127.0.0.1:{srv.port}"

    def serve_run(label: str, seed: int) -> float:
        # 4 requests/client: a longer window than the SERVE row's 2 —
        # the figure of merit here is a small DELTA, not the absolute
        stats = loadgen.run_load(
            url, 4 * clients, concurrency=clients, max_tokens=max_tokens,
            prompt_lens=[8], vocab=config.vocab_size - 1, seed=seed)
        if stats["completed"] != 4 * clients or stats["errors"]:
            raise RuntimeError(f"serve obs leg ({label}) failed: {stats}")
        sys.stderr.write(f"serve obs={label}: {stats['tok_s']:.1f} tok/s\n")
        return stats["tok_s"]

    try:
        # warm pass: first requests pay decode/admission compiles
        loadgen.run_load(url, clients, concurrency=clients, max_tokens=4,
                         prompt_lens=[8], vocab=config.vocab_size - 1,
                         seed=1)
        serve_off = serve_run("off", seed=2)
        trace.tracer().start()
        flight.recorder().enable()
        try:
            serve_on = serve_run("on", seed=3)
        finally:
            trace.tracer().stop()
            flight.recorder().disable()
            flight.recorder().clear()
            trace.tracer().clear()
    finally:
        srv.close()
        sched.close()
    serve_pct = (serve_off / serve_on - 1.0) * 100.0
    _emit({
        "metric": f"serve_trace_overhead_pct_{_mtag(preset)}_{wtag}_1chip",
        "value": round(serve_pct, 2),
        "unit": "%",
        "vs_baseline": round(serve_on / serve_off, 4),
    }, dev, baseline=f"trace_off_{serve_off:.1f}tok/s",
        serve_off_tok_s=round(serve_off, 2),
        serve_on_tok_s=round(serve_on, 2),
        clients=clients, max_tokens=max_tokens)
    return 0


def _run_serve_http(config, params, preset, quant, dev, batch,
                    steps) -> int:
    """CAKE_BENCH_SERVE=1: END-TO-END HTTP serving — the full network
    plane (cake_tpu/serve: HTTP accept, JSON/SSE framing, scheduler
    fan-out) over the same BatchGenerator the in-process serving rows
    measure. The figure of merit is aggregate tok/s THROUGH the socket
    plus TTFT p50/p95 as a loadgen client sees them; the gap to the
    in-process CAKE_BENCH_BATCH/CHURN rows is the serving plane's own
    overhead. Closed loop at CAKE_BENCH_BATCH concurrency (default floors
    at 2), 2 requests per client, CAKE_BENCH_STEPS tokens per request."""
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.batch_generator import BatchGenerator
    from cake_tpu.serve.api import start_api_server
    from cake_tpu.serve.scheduler import Scheduler
    from cake_tpu.tools import loadgen

    kv_quant = _kv_quant()
    batch = max(2, batch)
    max_tokens = max(4, min(steps, config.max_seq_len - 16))
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    gen = BatchGenerator(config, params, settings=settings,
                         kv_quant=kv_quant)
    sched = Scheduler(gen, queue_depth=4 * batch)
    sched.start(max_concurrent=batch, warm_prompt_len=8)
    srv = start_api_server(sched)
    url = f"http://127.0.0.1:{srv.port}"
    try:
        # warm pass: first requests pay decode/admission compiles
        loadgen.run_load(url, batch, concurrency=batch, max_tokens=4,
                         prompt_lens=[8], vocab=config.vocab_size - 1,
                         seed=1)
        stats = loadgen.run_load(
            url, 2 * batch, concurrency=batch, max_tokens=max_tokens,
            prompt_lens=[8], vocab=config.vocab_size - 1, seed=2)
    finally:
        srv.close()
        sched.close()
    if stats["completed"] != 2 * batch or stats["errors"]:
        sys.stderr.write(f"serve bench failed: {stats}\n")
        return 1
    wtag = _wtag(quant, kv_quant)
    _emit({
        "metric": (f"serve_http_tokens_per_sec_{_mtag(preset)}_{wtag}_"
                   f"1chip_c{batch}"),
        "value": stats["tok_s"],
        "unit": "tokens/s",
    }, dev, **_vs_roofline(dev, params, stats["tok_s"]),
        ttft_p50_ms=stats["ttft_ms"]["p50"],
        ttft_p95_ms=stats["ttft_ms"]["p95"],
        tpot_p50_ms=stats["tpot_ms"]["p50"],
        requests=stats["requests"], max_tokens=max_tokens)
    sys.stderr.write(
        f"device={dev.device_kind} clients={batch} "
        f"requests={stats['requests']} http_tok_s={stats['tok_s']} "
        f"ttft_p50={stats['ttft_ms']['p50']}ms "
        f"ttft_p95={stats['ttft_ms']['p95']}ms\n"
    )
    return 0


def _run_gateway_http(config, params, preset, quant, dev, batch,
                      steps) -> int:
    """CAKE_BENCH_GATEWAY=1: the routing gateway's own overhead — the
    same loadgen workload against one serve replica directly, then
    through a gateway (cake_tpu/gateway) fronting it. The figure of
    merit is gateway tok/s with vs_baseline = gateway/direct (the proxy
    hop, routing decision, and health bookkeeping are the whole gap; the
    design target is within 10% on the smoke config), plus the TTFT p50
    delta the extra hop adds."""
    from cake_tpu.gateway.api import start_gateway
    from cake_tpu.gateway.health import Backend, HealthMonitor
    from cake_tpu.gateway.policy import make_policy
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.batch_generator import BatchGenerator
    from cake_tpu.serve.api import start_api_server
    from cake_tpu.serve.scheduler import Scheduler
    from cake_tpu.tools import loadgen

    kv_quant = _kv_quant()
    batch = max(2, batch)
    max_tokens = max(4, min(steps, config.max_seq_len - 16))
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    gen = BatchGenerator(config, params, settings=settings,
                         kv_quant=kv_quant)
    sched = Scheduler(gen, queue_depth=4 * batch)
    sched.start(max_concurrent=batch, warm_prompt_len=8)
    srv = start_api_server(sched)
    direct_url = f"http://127.0.0.1:{srv.port}"
    monitor = HealthMonitor(
        [Backend("b0", f"127.0.0.1:{srv.port}")], probe_interval=0.5)
    monitor.start()
    gw = start_gateway(monitor, make_policy("p2c"))
    gw_url = f"http://127.0.0.1:{gw.port}"
    directs, via_gws = [], []
    try:
        # warm BOTH paths (compiles + the gateway's connect machinery),
        # then interleave the measured legs A/B/A/B — sequential legs
        # against the shared engine bias whichever runs later (EMA and
        # warmup drift exceed the ms-scale overhead being measured)
        loadgen.run_load(direct_url, batch, concurrency=batch,
                         max_tokens=4, prompt_lens=[8],
                         vocab=config.vocab_size - 1, seed=1)
        loadgen.run_load(gw_url, batch, concurrency=batch,
                         max_tokens=4, prompt_lens=[8],
                         vocab=config.vocab_size - 1, seed=1)
        for rep in range(2):
            directs.append(loadgen.run_load(
                direct_url, 2 * batch, concurrency=batch,
                max_tokens=max_tokens, prompt_lens=[8],
                vocab=config.vocab_size - 1, seed=2 + rep))
            via_gws.append(loadgen.run_load(
                gw_url, 2 * batch, concurrency=batch,
                max_tokens=max_tokens, prompt_lens=[8],
                vocab=config.vocab_size - 1, seed=2 + rep))
    finally:
        gw.close()
        monitor.stop()
        srv.close()
        sched.close()

    def _agg(legs):
        tokens = sum(s["tokens"] for s in legs)
        wall = sum(s["wall_s"] for s in legs)
        return {
            "tok_s": round(tokens / wall, 2) if wall else 0.0,
            "ttft_p50_ms": round(
                sum(s["ttft_ms"]["p50"] for s in legs) / len(legs), 1),
            "completed": sum(s["completed"] for s in legs),
            "errors": sum(s["errors"] for s in legs),
            "requests": sum(s["requests"] for s in legs),
        }

    direct, via_gw = _agg(directs), _agg(via_gws)
    if (direct["errors"] or via_gw["errors"]
            or direct["completed"] != 4 * batch
            or via_gw["completed"] != 4 * batch):
        sys.stderr.write(f"gateway bench failed: direct={direct} "
                         f"gateway={via_gw}\n")
        return 1
    ratio = via_gw["tok_s"] / direct["tok_s"] if direct["tok_s"] else 0.0
    wtag = _wtag(quant, kv_quant)
    _emit({
        "metric": (f"gateway_http_tokens_per_sec_{_mtag(preset)}_{wtag}_"
                   f"1chip_c{batch}"),
        "value": via_gw["tok_s"],
        "unit": "tokens/s",
        "vs_baseline": round(ratio, 4),
    }, dev,
        baseline=f"direct_http_{direct['tok_s']:.1f}tok/s",
        ttft_p50_ms=via_gw["ttft_p50_ms"],
        ttft_p50_direct_ms=direct["ttft_p50_ms"],
        ttft_added_p50_ms=round(via_gw["ttft_p50_ms"]
                                - direct["ttft_p50_ms"], 1),
        requests=via_gw["requests"], max_tokens=max_tokens,
        interleaved_reps=2)
    sys.stderr.write(
        f"device={dev.device_kind} clients={batch} "
        f"gateway_tok_s={via_gw['tok_s']} direct_tok_s={direct['tok_s']} "
        f"ratio={ratio:.3f} ttft_p50 {direct['ttft_p50_ms']} -> "
        f"{via_gw['ttft_p50_ms']} ms\n"
    )
    return 0


def _run_slo(config, params, preset, quant, dev, batch, steps) -> int:
    """CAKE_BENCH_SLO=1: class-aware scheduling (ISSUE 20) vs FIFO under
    the mixed-class flood — an interactive trickle (every 4th request)
    inside a batch flood against ONE paged serve stack, A/B/A/B'd by
    swapping the scheduler's policy between legs (same warmed engine,
    same compiled programs — the policy is the only variable). The
    figure of merit is interactive TTFT p95: under FIFO it is hostage
    to however many batch requests queued first; under "slo" the
    arrivals jump the queue and preempt batch victims to host-RAM
    spill. The row FAILS unless slo beats fifo."""
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.batch_generator import BatchGenerator
    from cake_tpu.serve.api import start_api_server
    from cake_tpu.serve.scheduler import Scheduler
    from cake_tpu.tools import loadgen

    kv_quant = _kv_quant()
    batch = max(2, batch)
    max_tokens = max(4, min(steps, config.max_seq_len - 16))
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    gen = BatchGenerator(config, params, settings=settings,
                        kv_quant=kv_quant, kv_layout="paged",
                        kv_page_size=16)
    n = 12 * batch  # per leg; every 4th request is interactive
    sched = Scheduler(gen, queue_depth=2 * n, sched_policy="slo")
    sched.start(max_concurrent=batch, warm_prompt_len=8)
    srv = start_api_server(sched)
    url = f"http://127.0.0.1:{srv.port}"
    # arrivals must decisively outpace service so the admission queue
    # builds — a drained queue has nothing for the policy to reorder,
    # and FIFO only loses when interactive arrivals find a deep queue.
    # A near-burst guarantees depth regardless of how fast this host
    # decodes the tiny model.
    rate = 100.0 * batch
    ttfts = {"fifo": [], "slo": []}
    counts = {"fifo": 0, "slo": 0}
    try:
        # warm pass: first requests pay decode/admission compiles
        loadgen.run_load(url, batch, concurrency=batch, max_tokens=4,
                         prompt_lens=[8], vocab=config.vocab_size - 1,
                         seed=1)
        for rep in range(2):  # interleaved A/B/A/B on one warmed stack
            for policy in ("fifo", "slo"):
                sched.set_policy(policy)
                leg = loadgen.run_load(
                    url, n, max_tokens=max_tokens, prompt_lens=[8],
                    vocab=config.vocab_size - 1, rate=rate,
                    seed=3 + rep, workload="mixed-class")
                if leg["errors"] or leg["completed"] != n:
                    sys.stderr.write(f"slo bench leg failed "
                                     f"({policy}): {leg}\n")
                    return 1
                counts[policy] += leg["completed"]
                ttfts[policy] += [
                    r["ttft_s"] * 1e3
                    for i, r in enumerate(leg["results"])
                    if i % 4 == 0 and r and r.get("ttft_s") is not None]
        st = sched.stats()
    finally:
        srv.close()
        sched.close()
    fifo_p95 = round(loadgen._percentile(ttfts["fifo"], 0.95), 1)
    slo_p95 = round(loadgen._percentile(ttfts["slo"], 0.95), 1)
    ratio = slo_p95 / fifo_p95 if fifo_p95 else 0.0
    wtag = _wtag(quant, kv_quant)
    _emit({
        "metric": (f"slo_interactive_ttft_p95_{_mtag(preset)}_{wtag}_"
                   f"1chip_c{batch}"),
        "value": slo_p95,
        "unit": "ms",
        "vs_baseline": round(ratio, 4),
    }, dev,
        baseline=f"fifo_interactive_ttft_p95_{fifo_p95:.1f}ms",
        interactive_n=len(ttfts["slo"]),
        requests=counts["fifo"] + counts["slo"],
        preemptions=st.get("preemptions", 0),
        max_tokens=max_tokens, interleaved_reps=2)
    sys.stderr.write(
        f"device={dev.device_kind} clients={batch} "
        f"interactive ttft_p95 fifo={fifo_p95}ms slo={slo_p95}ms "
        f"ratio={ratio:.3f} preemptions={st.get('preemptions', 0)}\n"
    )
    if slo_p95 >= fifo_p95:
        sys.stderr.write(
            "slo bench FAILED: class-aware interactive TTFT p95 "
            f"({slo_p95}ms) must beat the FIFO baseline "
            f"({fifo_p95}ms)\n")
        return 1
    return 0


def _run_disagg(config, params, preset, quant, dev, batch, steps) -> int:
    """CAKE_BENCH_DISAGG=1: the disaggregated prefill/decode tiers
    (cake_tpu/disagg) under the interference regime they exist for — the
    mixed-prefill workload (bimodal prompt lengths, Poisson arrivals)
    against a TIERED fleet (1 prefill + 1 decode replica, KV pages
    shipped over the transfer channel) vs 2 MIXED replicas, both behind
    a routing gateway, legs interleaved A/B/A/B. The figure of merit is
    the decode-tier TPOT p95 (long neighbors' prefill dispatches no
    longer interleave with anyone's decode) with vs_baseline =
    tiered/mixed (< 1.0 = the tier split pays for its transfer hop);
    TTFT p95 rides along split by prompt bucket."""
    from cake_tpu.disagg import TransferServer
    from cake_tpu.gateway.api import start_gateway
    from cake_tpu.gateway.health import Backend, HealthMonitor
    from cake_tpu.gateway.policy import make_policy
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.batch_generator import BatchGenerator
    from cake_tpu.serve.api import start_api_server
    from cake_tpu.serve.scheduler import Scheduler
    from cake_tpu.tools import loadgen

    kv_quant = _kv_quant()
    batch = max(2, batch)
    max_tokens = max(4, min(steps, 32))
    # the bimodal mix: chatty short prompts next to long-document ones
    # (the long bucket is capped so prompt + decode fits the window)
    short_len = 8
    long_len = max(short_len * 2,
                   min(512, config.max_seq_len - max_tokens - 8))
    n_req = 4 * batch
    rate = max(2.0, 1.5 * batch)
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)

    def _stack(role):
        gen = BatchGenerator(config, params, settings=settings,
                             kv_quant=kv_quant, kv_layout="paged")
        sched = Scheduler(gen, queue_depth=4 * batch, role=role)
        sched.start(max_concurrent=batch, warm_prompt_len=8)
        return start_api_server(sched), sched

    def _fleet(roles, tag):
        stacks = [_stack(r) for r in roles]
        xfers = []
        for _, sched in stacks:
            if sched.role == "decode":
                ts = TransferServer(sched).start()
                sched.transfer_port = ts.port
                xfers.append(ts)
        monitor = HealthMonitor(
            [Backend(f"{tag}{i}", f"127.0.0.1:{srv.port}")
             for i, (srv, _) in enumerate(stacks)],
            probe_interval=0.5).start()
        gw = start_gateway(monitor, make_policy("p2c"))
        deadline = time.monotonic() + 15.0
        want = {r for r in roles if r != "mixed"}
        while time.monotonic() < deadline and want:
            if want <= {b.role for b in monitor.routable()}:
                break
            time.sleep(0.05)

        def close():
            gw.close()
            monitor.stop()
            for ts in xfers:
                ts.stop()
            for srv, sched in stacks:
                srv.close()
                sched.close()

        return f"http://127.0.0.1:{gw.port}", close

    def _leg(url, seed):
        return loadgen.run_load(
            url, n_req, concurrency=batch, max_tokens=max_tokens,
            prompt_lens=[short_len, long_len],
            vocab=config.vocab_size - 1, rate=rate, seed=seed,
            workload="mixed-prefill")

    tiered_url, tiered_close = _fleet(["prefill", "decode"], "dt")
    mixed_url, mixed_close = _fleet(["mixed", "mixed"], "dm")
    tiered_legs, mixed_legs = [], []
    try:
        # warm both fleets (compiles, transfer channel, gateway probes),
        # then interleave the measured legs A/B/A/B
        _leg(tiered_url, 1)
        _leg(mixed_url, 1)
        for rep in range(2):
            tiered_legs.append(_leg(tiered_url, 2 + rep))
            mixed_legs.append(_leg(mixed_url, 2 + rep))
    finally:
        tiered_close()
        mixed_close()

    def _agg(legs):
        gaps = [g for s in legs for r in s["results"]
                if r for g in r.get("gaps_s", ())]
        ttfts = [r["ttft_s"] for s in legs for r in s["results"]
                 if r and r.get("ttft_s") is not None]
        gaps.sort()
        ttfts.sort()

        def pct(xs, q):
            return round(
                xs[min(len(xs) - 1, int(q * (len(xs) - 1) + 0.5))] * 1e3,
                2) if xs else 0.0

        by_len = {}
        for s in legs:
            for ln, st in s.get("ttft_ms_by_prompt_len", {}).items():
                by_len.setdefault(ln, []).append(st["p95"])
        return {
            "tpot_p95_ms": pct(gaps, 0.95),
            "tpot_p50_ms": pct(gaps, 0.5),
            "ttft_p95_ms": pct(ttfts, 0.95),
            "ttft_p95_by_len": {ln: round(max(v), 1)
                                for ln, v in sorted(by_len.items())},
            "completed": sum(s["completed"] for s in legs),
            "errors": sum(s["errors"] for s in legs),
            "tok_s": round(sum(s["tokens"] for s in legs)
                           / max(1e-9, sum(s["wall_s"] for s in legs)),
                           2),
        }

    tiered, mixed = _agg(tiered_legs), _agg(mixed_legs)
    if (tiered["errors"] or mixed["errors"]
            or tiered["completed"] != 2 * n_req
            or mixed["completed"] != 2 * n_req):
        sys.stderr.write(f"disagg bench failed: tiered={tiered} "
                         f"mixed={mixed}\n")
        return 1
    ratio = (tiered["tpot_p95_ms"] / mixed["tpot_p95_ms"]
             if mixed["tpot_p95_ms"] else 0.0)
    wtag = _wtag(quant, kv_quant)
    _emit({
        "metric": (f"disagg_decode_tpot_p95_ms_{_mtag(preset)}_{wtag}_"
                   f"1chip_c{batch}"),
        "value": tiered["tpot_p95_ms"],
        "unit": "ms",
        "vs_baseline": round(ratio, 4),
    }, dev,
        baseline=f"mixed_fleet_{mixed['tpot_p95_ms']}ms",
        tiered=tiered, mixed=mixed,
        prompt_lens=[short_len, long_len], max_tokens=max_tokens,
        requests_per_leg=n_req, rate_rps=rate, interleaved_reps=2)
    sys.stderr.write(
        f"device={dev.device_kind} clients={batch} "
        f"prompts={short_len}/{long_len} "
        f"tiered tpot_p95={tiered['tpot_p95_ms']}ms "
        f"ttft_p95={tiered['ttft_p95_ms']}ms | "
        f"mixed tpot_p95={mixed['tpot_p95_ms']}ms "
        f"ttft_p95={mixed['ttft_p95_ms']}ms | ratio={ratio:.3f}\n"
    )
    return 0


class _AsciiTok:
    """Printable-ASCII toy tokenizer for the constrained-serving row: id
    -> one printable char (mod 95), so grammar compilation has real vocab
    strings without shipping a tokenizer.json in the bench image."""

    def decode(self, ids):
        return "".join(chr(32 + (i % 95)) for i in ids)

    def encode(self, text):
        return [ord(c) - 32 for c in text]


def _run_serve_constrain(config, params, preset, quant, dev, batch,
                         steps) -> int:
    """CAKE_BENCH_CONSTRAIN=1: grammar-constrained HTTP serving
    (cake_tpu/constrain) vs the same server unconstrained. The
    constrained leg runs loadgen's --workload json mode — every request
    carries a response_format json_schema and every response must
    json.loads-parse — and the figure of merit is constrained tok/s with
    vs_baseline = constrained/unconstrained (the mask gather + host-side
    DFA advance + forced single-step dispatch are the whole gap; the
    design target is within 10% on the smoke config)."""
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.batch_generator import BatchGenerator
    from cake_tpu.serve.api import start_api_server
    from cake_tpu.serve.scheduler import Scheduler
    from cake_tpu.tools import loadgen

    kv_quant = _kv_quant()
    batch = max(2, batch)
    max_tokens = max(32, min(steps * 2, config.max_seq_len - 16))
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    gen = BatchGenerator(config, params, settings=settings,
                         kv_quant=kv_quant, tokenizer=_AsciiTok())
    sched = Scheduler(gen, queue_depth=4 * batch)
    sched.start(max_concurrent=batch, warm_prompt_len=8,
                warm_constrain=True)
    srv = start_api_server(sched)
    url = f"http://127.0.0.1:{srv.port}"
    try:
        # warm BOTH legs: plain decode/admission compiles, then the
        # masked-program compile (each leg must measure steady state)
        loadgen.run_load(url, batch, concurrency=batch, max_tokens=4,
                         prompt_lens=[8], vocab=config.vocab_size - 1,
                         seed=1)
        loadgen.run_load(url, batch, concurrency=batch, max_tokens=4,
                         prompt_lens=[8], vocab=config.vocab_size - 1,
                         seed=1, workload="json")
        plain = loadgen.run_load(
            url, 2 * batch, concurrency=batch, max_tokens=max_tokens,
            prompt_lens=[8], vocab=config.vocab_size - 1, seed=2)
        constrained = loadgen.run_load(
            url, 2 * batch, concurrency=batch, max_tokens=max_tokens,
            prompt_lens=[8], vocab=config.vocab_size - 1, seed=3,
            workload="json")
    finally:
        srv.close()
        sched.close()
    if (constrained["errors"] or constrained["json_invalid"]
            or plain["errors"]):
        sys.stderr.write(f"constrain bench failed: plain={plain} "
                         f"constrained={constrained}\n")
        return 1
    wtag = _wtag(quant, kv_quant)
    ratio = (constrained["tok_s"] / plain["tok_s"]
             if plain["tok_s"] else 0.0)
    _emit({
        "metric": (f"serve_constrained_tokens_per_sec_{_mtag(preset)}_"
                   f"{wtag}_1chip_c{batch}"),
        "value": constrained["tok_s"],
        "unit": "tokens/s",
        "vs_baseline": round(ratio, 4),
    }, dev,
        baseline=f"unconstrained_http_{plain['tok_s']:.1f}tok/s",
        json_valid=constrained["completed"] - constrained["json_invalid"],
        requests=constrained["requests"],
        ttft_p50_ms=constrained["ttft_ms"]["p50"])
    sys.stderr.write(
        f"device={dev.device_kind} clients={batch} "
        f"constrained_tok_s={constrained['tok_s']} "
        f"unconstrained_tok_s={plain['tok_s']} ratio={ratio:.3f} "
        f"json_valid={constrained['completed']}/"
        f"{constrained['requests']}\n"
    )
    return 0


def _admit_chunk(config) -> int:
    """Largest divisor of the window <= 512 (admit_chunk must divide
    max_seq) — shared by both churn rows so the admission-chunk policy
    cannot diverge between them."""
    return max(c for c in range(1, min(512, config.max_seq_len) + 1)
               if config.max_seq_len % c == 0)


def _churn_drive(gen, base, batch, steps, stream_len, admits,
                 next_sid, e0, churn=True) -> int:
    """The ONE churn-driving loop both churn rows share (`_run_churn`
    and `_run_kvpool`): retire each stream at ``stream_len`` tokens and
    enqueue a replacement through the chunked admission path
    (``churn=False``: plain steady stepping), until the token quota is
    met or everything drains. Returns the number of admissions made."""
    admitted = 0
    for _ in range(steps * 4):
        gen.step()
        if churn:
            for s in gen.streams:
                if (s.active and not s.done
                        and len(s.generated) >= stream_len):
                    s.done = True
                    if admitted < admits:
                        gen.enqueue(list(base), next_sid)
                        next_sid += 1
                        admitted += 1
        live = any(s.active and not s.done for s in gen.streams)
        if not live and gen.pending_admissions() == 0:
            break
        if gen.stats()["tokens_emitted"] - e0 >= steps * batch:
            break
    return admitted


def _run_churn(config, params, preset, quant, dev, batch, steps,
               multistep) -> int:
    """CAKE_BENCH_CHURN=1: serving under arrival churn. Streams that reach
    CAKE_BENCH_STREAM_LEN tokens retire and a queued arrival takes the slot
    via the chunked admission path (enqueue) — the continuous-batching
    regime. The figure of merit is aggregate tok/s with churn vs the
    fixed-batch row (CAKE_BENCH_BATCH alone): admission overhead shows up
    directly as the gap."""
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.batch_generator import BatchGenerator

    kv_quant = _kv_quant()
    stream_len = int(os.environ.get("CAKE_BENCH_STREAM_LEN", "64"))
    admits = int(os.environ.get("CAKE_BENCH_ADMITS", str(batch)))
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    admit_chunk = _admit_chunk(config)
    # Adaptive decode blocks (CAKE_BENCH_BLOCK_MAX, default 4x the base
    # block): the fused block doubles while no arrival waits and snaps
    # back on churn (what a dispatch's host sync costs beside the chip:
    # not measured on the chip tool). 0 disables.
    block_max = int(os.environ.get("CAKE_BENCH_BLOCK_MAX",
                                   str(4 * multistep)))
    # CAKE_BENCH_LOOKAHEAD=1: double-buffer the block dispatches (the
    # device computes block N+1 while block N's rows are fetched to the
    # host) — the second churn lever, orthogonal to block growth
    lookahead = os.environ.get("CAKE_BENCH_LOOKAHEAD") == "1"
    gen = BatchGenerator(config, params, settings=settings,
                         block_size=multistep, block_size_max=block_max,
                         lookahead=lookahead, kv_quant=kv_quant,
                         admit_chunk=admit_chunk)
    base = [5, 9, 2, 4, 8, 1, 3, 7]
    gen.set_prompts([list(base) for _ in range(batch)])
    for _ in range(3):  # compile + warm-up
        gen.step()
    # compile the admission-prefill program and the adaptive block ladder
    # outside the timed window
    gen.warm_admission(len(base))
    gen.warm_blocks()
    t0 = time.perf_counter()
    e0 = gen.stats()["tokens_emitted"]
    b0 = gen.stats()["busy_s"]  # exclude warm-up/compile busy time
    admitted = _churn_drive(gen, base, batch, steps, stream_len, admits,
                            next_sid=batch, e0=e0)
    # measurement boundary: tokens the device already computed (buffered
    # rows + any in-flight lookahead block) are emitted and counted — the
    # final sync pays their wall-clock either way, so dropping them would
    # under-report the lookahead arm
    gen.drain()
    _sync(gen._last_tokens)
    dt = time.perf_counter() - t0
    emitted = gen.stats()["tokens_emitted"] - e0
    agg = emitted / dt
    wtag = _wtag(quant, kv_quant)
    st = gen.stats()
    _emit({
        "metric": (f"decode_tokens_per_sec_{_mtag(preset)}_{wtag}_1chip_"
                   f"b{batch}_churn"),
        "value": round(agg, 3),
        "unit": "tokens/s",
    }, dev, **_vs_roofline(dev, params, agg),
        tokens_per_dispatch=st["tokens_per_dispatch"],
        busy_s=round(st["busy_s"] - b0, 3), wall_s=round(dt, 3))
    sys.stderr.write(
        f"device={dev.device_kind} batch={batch} stream_len={stream_len} "
        f"admitted={admitted} dispatches={st['decode_dispatches']}d+"
        f"{st['admit_dispatches']}a tokens/dispatch="
        f"{st['tokens_per_dispatch']} busy_s={st['busy_s'] - b0:.3f} "
        f"timed_s={dt:.3f}\n"
    )
    return 0


def _run_kvpool(config, params, preset, quant, dev, batch, steps,
                multistep) -> int:
    """CAKE_BENCH_KVPOOL=1: churn throughput, paged vs slot KV layout
    (cake_tpu/kvpool), plus the paged layout's own steady-batch row on
    the same config. Three legs per rep — steady/paged, churn/paged,
    churn/slot — INTERLEAVED across two reps (A/B/A/B) so warmup and
    EMA drift can't flatter one layout (the gateway row's lesson: a
    sequential comparison measured ordering bias bigger than the effect).
    Figures of merit: churn_paged/steady_paged (ROADMAP's within-25%
    target — admission/retirement as page-table edits instead of cache
    splices) and churn_paged/churn_slot."""
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.batch_generator import BatchGenerator

    kv_quant = _kv_quant()
    stream_len = int(os.environ.get("CAKE_BENCH_STREAM_LEN", "64"))
    admits = int(os.environ.get("CAKE_BENCH_ADMITS", str(batch)))
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    admit_chunk = _admit_chunk(config)
    block_max = int(os.environ.get("CAKE_BENCH_BLOCK_MAX",
                                   str(4 * multistep)))
    base = [5, 9, 2, 4, 8, 1, 3, 7]

    def build(layout):
        gen = BatchGenerator(config, params, settings=settings,
                             block_size=multistep, block_size_max=block_max,
                             kv_quant=kv_quant, admit_chunk=admit_chunk,
                             kv_layout=layout)
        gen.set_prompts([list(base) for _ in range(batch)])
        for _ in range(3):
            gen.step()
        gen.warm_admission(len(base))
        gen.warm_blocks()
        return gen

    def leg(layout, churn):
        gen = build(layout)
        t0 = time.perf_counter()
        e0 = gen.stats()["tokens_emitted"]
        _churn_drive(gen, base, batch, steps, stream_len, admits,
                     next_sid=batch, e0=e0, churn=churn)
        gen.drain()
        _sync(gen._last_tokens)
        dt = time.perf_counter() - t0
        return (gen.stats()["tokens_emitted"] - e0) / dt

    acc = {"steady_paged": [], "churn_paged": [], "churn_slot": []}
    for _ in range(2):  # interleaved reps: no leg owns the warm tail
        acc["steady_paged"].append(leg("paged", churn=False))
        acc["churn_paged"].append(leg("paged", churn=True))
        acc["churn_slot"].append(leg("slot", churn=True))
    mean = {k: sum(v) / len(v) for k, v in acc.items()}
    ratio_steady = (mean["churn_paged"] / mean["steady_paged"]
                    if mean["steady_paged"] else 0.0)
    ratio_slot = (mean["churn_paged"] / mean["churn_slot"]
                  if mean["churn_slot"] else 0.0)
    wtag = _wtag(quant, kv_quant)
    _emit({
        "metric": (f"decode_tokens_per_sec_{_mtag(preset)}_{wtag}_1chip_"
                   f"b{batch}_churn_paged"),
        "value": round(mean["churn_paged"], 3),
        "unit": "tokens/s",
        "vs_baseline": round(ratio_steady, 4),
    }, dev,
        baseline=f"steady_paged_{mean['steady_paged']:.1f}tok/s",
        churn_slot_tok_s=round(mean["churn_slot"], 3),
        ratio_paged_vs_slot=round(ratio_slot, 4),
        ratio_churn_vs_steady=round(ratio_steady, 4))
    sys.stderr.write(
        f"device={dev.device_kind} batch={batch} "
        f"steady_paged={mean['steady_paged']:.1f} "
        f"churn_paged={mean['churn_paged']:.1f} "
        f"churn_slot={mean['churn_slot']:.1f} tok/s "
        f"churn/steady={ratio_steady:.3f} paged/slot={ratio_slot:.3f}\n"
    )
    return 0


def _run_spec_serving(config, params, preset, quant, dev, batch, steps,
                      k) -> int:
    """CAKE_BENCH_SPEC=K with CAKE_BENCH_BATCH=N: batched serving
    speculation — every live stream's K n-gram proposals verified in ONE
    per-row dispatch (runtime/batch_generator spec_k plane). The figure of
    merit is aggregate tok/s on self-repeating streams plus
    tokens-per-dispatch; contrast with the plain CAKE_BENCH_BATCH row to
    see the dispatch amortization."""
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.batch_generator import BatchGenerator

    kv_quant = _kv_quant()
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    gen = BatchGenerator(config, params, settings=settings, spec_k=k,
                         kv_quant=kv_quant)
    base = [5, 9, 2, 5, 9, 2, 5, 9]
    gen.set_prompts([[(t + i) % (config.vocab_size - 1) + 1 for t in base]
                     for i in range(batch)])
    for _ in range(4):  # compile (verify program) + warm
        gen.step()
    t0 = time.perf_counter()
    e0 = gen.stats()["tokens_emitted"]
    for _ in range(steps * 4):
        gen.step()
        if gen.stats()["tokens_emitted"] - e0 >= steps * batch:
            break
    _sync(gen._last_tokens)
    dt = time.perf_counter() - t0
    emitted = gen.stats()["tokens_emitted"] - e0
    agg = emitted / dt
    wtag = _wtag(quant, kv_quant)
    st = gen.stats()
    _emit({
        "metric": (f"decode_tokens_per_sec_{_mtag(preset)}_{wtag}_1chip_"
                   f"b{batch}_spec{k}"),
        "value": round(agg, 3),
        "unit": "tokens/s",
    }, dev, **_vs_roofline(dev, params, agg),
        tokens_per_dispatch=st["tokens_per_dispatch"])
    sys.stderr.write(
        f"device={dev.device_kind} batch={batch} spec_k={k} "
        f"spec_dispatches={st['spec_dispatches']} "
        f"tokens/dispatch={st['tokens_per_dispatch']} "
        f"(self-repeating streams: favorable-regime acceptance)\n"
    )
    return 0


def _run_spec_corpus(config, params, preset, quant, dev, steps) -> int:
    """CAKE_BENCH_SPEC=K + CAKE_BENCH_SPEC_CORPUS=1: teacher-forced replay
    of the embedded REAL-text corpus (cake_tpu/utils/corpus.py) through the
    fused speculation machinery — the honest companion to the synthetic
    self-repeating row. Acceptance is decided by
    whether the n-gram proposals match the corpus's actual next tokens
    (real prose/code repetition statistics); every round still pays the
    true [1, K+1] verification forward, so tok/s carries the real
    dispatch + FLOP cost. The replay is capped at ONE corpus pass (a
    wrapped stream degenerates to the synthetic best case — see
    corpus.py). Row fields: tokens_per_round (the figure of merit),
    acceptance (mean accepted proposals / K)."""
    from cake_tpu.ops.kvcache import init_cache
    from cake_tpu.runtime.generator import prefill_fn
    from cake_tpu.runtime.speculative import spec_replay_fn
    from cake_tpu.utils.corpus import corpus_tokens

    k = int(os.environ.get("CAKE_BENCH_SPEC", "8"))
    rounds = int(os.environ.get("CAKE_BENCH_SPEC_ROUNDS", "8"))
    kv_quant = _kv_quant()
    if kv_quant:
        sys.exit("error: CAKE_BENCH_SPEC_CORPUS does not take CAKE_BENCH_KV "
                 "(the replay path uses the plain single-chip cache)")
    toks = corpus_tokens(config.vocab_size)  # ONE pass, no wrap
    window = min(config.max_seq_len, len(toks))
    prompt_len = min(64, window // 4)
    corpus_dev = jnp.asarray(toks[:window])

    cache = init_cache(config, batch=1, max_seq=config.max_seq_len)
    prefill = jax.jit(partial(prefill_fn, config=config),
                      donate_argnames=("cache",))
    logits, cache = prefill(
        params, corpus_dev[None, :prompt_len], cache,
        jnp.asarray([prompt_len - 1], jnp.int32),
    )
    _sync(logits)

    replay = jax.jit(
        partial(spec_replay_fn, config=config, k=k, n_max=3, rounds=rounds),
        donate_argnames=("cache",),
    )
    # corpus[0..prompt_len-1] is in the cache; the stream's next known
    # token corpus[prompt_len] feeds the first verify at that position
    # (its KV is written by that round's fed[0], like live speculation)
    pos = jnp.int32(prompt_len)
    acc = jnp.float32(0.0)
    counts, pos, cache, acc = replay(params, corpus_dev, pos, cache, acc)
    _sync(counts)  # compile + warm (positions advanced: replay continues)

    t0 = time.perf_counter()
    dispatches = 0
    all_counts = [np.asarray(counts)]
    pos_h = int(pos)
    headroom = rounds * (k + 1) + 1
    while pos_h + headroom < window and dispatches < steps:
        counts, pos, cache, acc = replay(params, corpus_dev, pos, cache, acc)
        pos_h = int(pos)  # the one sync per chain (by design)
        all_counts.append(np.asarray(counts))
        dispatches += 1
    _sync(acc)
    dt = time.perf_counter() - t0
    if dispatches == 0:
        sys.exit("error: corpus/window too short for one timed replay "
                 f"chain (window {window}, need {headroom} headroom)")

    counts_np = np.concatenate(all_counts[1:])  # timed rounds only
    emitted = int(counts_np.sum())
    tok_s = emitted / dt
    per_round = counts_np.mean()
    acceptance = (counts_np - 1).mean() / k
    wtag = _wtag(quant, kv_quant)
    _emit({
        "metric": (f"decode_tokens_per_sec_{_mtag(preset)}_{wtag}_1chip_"
                   f"spec{k}_corpus"),
        "value": round(tok_s, 3),
        "unit": "tokens/s",
    }, dev, **_vs_roofline(dev, params, tok_s),
        mode="teacher_forced_corpus_replay_bytes",
        tokens_per_round=round(float(per_round), 2),
        # per DISPATCH = per host sync (one replay chain of `rounds`
        # verifies), matching _run_speculative's definition
        tokens_per_dispatch=round(emitted / dispatches, 2),
        acceptance=round(float(acceptance), 4),
        rounds_per_dispatch=rounds)
    sys.stderr.write(
        f"device={dev.device_kind} spec_k={k} rounds={rounds} "
        f"corpus_window={window} dispatches={dispatches} "
        f"tokens/round={per_round:.2f} acceptance={acceptance:.3f} "
        f"(teacher-forced byte-level corpus replay — real-text n-gram "
        f"statistics, true verify cost)\n"
    )
    return 0


def _run_speculative(config, params, preset, quant, dev, steps) -> int:
    """CAKE_BENCH_SPEC=K: greedy decode with n-gram speculation on a
    self-repeating stream (the favorable regime — repetitive/structured
    text; acceptance is printed so the row is honest about it). The win is
    structural: tokens-per-dispatch > 1 amortizes the per-token HBM weight
    sweep that bounds plain decode."""
    from cake_tpu.ops.sampling import SamplerSettings
    from cake_tpu.runtime.speculative import SpeculativeGenerator

    k = int(os.environ.get("CAKE_BENCH_SPEC", "8"))
    # Fused rounds per host sync (default 8): more rounds amortize the
    # sync further, and the knob exists to measure that curve. What one
    # sync costs beside the chip: not measured on the chip tool.
    rounds = int(os.environ.get("CAKE_BENCH_SPEC_ROUNDS", "8"))
    kv_quant = _kv_quant()
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.0)
    gen = SpeculativeGenerator(config, params, settings=settings,
                               spec_k=k, spec_rounds=rounds,
                               kv_quant=kv_quant)
    prompt = [5, 9, 2, 5, 9, 2, 5, 9]
    gen.set_prompt(prompt)
    gen.next_token(0)  # prefill + compile
    warm = 8
    for i in range(1, warm):
        gen.next_token(i)
    d0, e0, r0 = gen.dispatches, gen.emitted, gen.rounds
    t0 = time.perf_counter()
    n = 0
    while gen.emitted - e0 < steps and gen._pos < config.max_seq_len - k - 1:
        gen.next_token(warm + n)
        n += 1
    _sync(gen._history)
    dt = time.perf_counter() - t0
    timed = gen.emitted - e0
    tok_s = timed / dt
    accept = timed / max(1, gen.dispatches - d0)
    per_round = timed / max(1, gen.rounds - r0)
    model_gb = _param_bytes(params) / 1e9
    wtag = _wtag(quant, kv_quant)
    rounds_per_dispatch = (gen.rounds - r0) / max(1, gen.dispatches - d0)
    _emit({
        "metric": f"decode_tokens_per_sec_{_mtag(preset)}_{wtag}_1chip_spec{k}",
        "value": round(tok_s, 3),
        "unit": "tokens/s",
    }, dev, **_vs_roofline(dev, params, tok_s),
        tokens_per_dispatch=round(accept, 2),
        tokens_per_round=round(per_round, 2),
        rounds_per_dispatch=round(rounds_per_dispatch, 2))
    sys.stderr.write(
        f"device={dev.device_kind} params={model_gb:.2f}GB spec_k={k} "
        f"rounds/dispatch={rounds_per_dispatch:.2f} "
        f"tokens/round={per_round:.2f} "
        f"tokens/dispatch={accept:.2f} timed_tokens={timed} "
        f"(self-repeating stream: favorable-regime acceptance)\n"
    )
    return 0


def main() -> int:
    _configure_compile_cache()
    preset = os.environ.get("CAKE_BENCH_PRESET", "8b")
    if preset not in ("8b", "small", "tiny"):
        sys.exit(f"error: CAKE_BENCH_PRESET must be 8b|small|tiny, got "
                 f"{preset!r}")
    quant = os.environ.get("CAKE_BENCH_QUANT", "")
    if quant not in ("", "int8", "int4"):
        sys.exit(
            f"error: CAKE_BENCH_QUANT must be 'int8' or 'int4', got {quant!r}"
        )
    steps = int(os.environ.get("CAKE_BENCH_STEPS", "128"))

    from cake_tpu.models.llama import init_params
    from cake_tpu.ops.kvcache import init_cache
    from cake_tpu.ops.sampling import SamplerSettings, init_history
    from cake_tpu.runtime.generator import (
        decode_scan_fn,
        decode_step_fn,
        prefill_fn,
    )

    # the device JAX gives this process: a failed init raises here and the
    # traceback, with a non-zero exit, is the result
    dev = jax.devices()[0]
    if preset != "tiny" and dev.platform != "tpu":
        # nothing re-runs a chip-sized preset smaller or elsewhere: with
        # no TPU the run fails. The explicit CPU smoke is the tiny preset
        sys.exit(f"error: the {preset!r} preset is sized for a TPU and this "
                 f"process runs on {dev.platform!r} ({dev.device_kind}); "
                 "CAKE_BENCH_PRESET=tiny is the CPU smoke (make bench)")
    key = jax.random.PRNGKey(0)

    # The preset that was asked for, or a failure: a model that does not
    # fit this device raises out of init/_sync with the allocator's own
    # message. (8B bf16 is 14.96 GiB of weights: it does not fit a 16 GiB
    # v5e; CAKE_BENCH_QUANT=int8 is the same model at half the bytes.)
    config = _config(preset)
    if quant == "int8":
        # generate-and-quantize per layer: peak HBM stays near the int8
        # total instead of bf16 + int8 (llama.init_params_int8)
        from cake_tpu.models.llama import init_params_int8

        params = init_params_int8(config, key)
    elif quant == "int4":
        from cake_tpu.models.llama import init_params_int4

        params = init_params_int4(config, key)
    else:
        params = init_params(config, key)
    _sync(params)

    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    multistep = int(os.environ.get("CAKE_BENCH_MULTISTEP", "16"))
    batch = int(os.environ.get("CAKE_BENCH_BATCH", "1"))
    if os.environ.get("CAKE_BENCH_PREFILL") == "1":
        return _run_prefill(config, params, preset, quant, dev)
    if os.environ.get("CAKE_BENCH_TTFT") == "1":
        return _run_ttft(config, params, preset, quant, dev)
    if os.environ.get("CAKE_BENCH_OBS") == "1":
        return _run_obs_overhead(config, params, preset, quant, dev, steps)
    if os.environ.get("CAKE_BENCH_SERVE") == "1":
        return _run_serve_http(config, params, preset, quant, dev, batch,
                               steps)
    if os.environ.get("CAKE_BENCH_CONSTRAIN") == "1":
        return _run_serve_constrain(config, params, preset, quant, dev,
                                    batch, steps)
    if os.environ.get("CAKE_BENCH_GATEWAY") == "1":
        return _run_gateway_http(config, params, preset, quant, dev,
                                 batch, steps)
    if os.environ.get("CAKE_BENCH_DISAGG") == "1":
        return _run_disagg(config, params, preset, quant, dev,
                           max(2, batch), steps)
    if os.environ.get("CAKE_BENCH_SLO") == "1":
        return _run_slo(config, params, preset, quant, dev,
                        max(2, batch), steps)
    if os.environ.get("CAKE_BENCH_SPEC"):
        k = int(os.environ["CAKE_BENCH_SPEC"])
        if os.environ.get("CAKE_BENCH_SPEC_CORPUS") == "1":
            return _run_spec_corpus(config, params, preset, quant, dev,
                                    steps)
        if batch > 1:
            return _run_spec_serving(config, params, preset, quant, dev,
                                     batch, steps, k)
        return _run_speculative(config, params, preset, quant, dev, steps)
    if os.environ.get("CAKE_BENCH_KVPOOL") == "1":
        return _run_kvpool(config, params, preset, quant, dev,
                           max(2, batch), steps, multistep)
    if os.environ.get("CAKE_BENCH_CHURN") == "1":
        return _run_churn(config, params, preset, quant, dev,
                          max(2, batch), steps, multistep)
    if batch > 1:
        return _run_batched(config, params, preset, quant, settings, dev,
                            batch, steps, multistep)
    kv_quant = _kv_quant()
    cache = init_cache(config, batch=1, max_seq=config.max_seq_len,
                       quant=kv_quant)
    history, hist_slot = init_history(settings.repeat_last_n)

    if multistep > 1:
        decode = jax.jit(
            partial(decode_scan_fn, config=config, settings=settings,
                    steps=multistep),
            donate_argnames=("cache",),
        )
    else:
        decode = jax.jit(
            partial(decode_step_fn, config=config, settings=settings),
            donate_argnames=("cache",),
        )

    # prefill a short prompt so decode runs from a warm cache
    prompt = jnp.asarray([[1, 5, 9, 14, 3, 8, 2, 4]], jnp.int32)
    prefill = jax.jit(partial(prefill_fn, config=config), donate_argnames=("cache",))
    t_pf0 = time.perf_counter()
    logits, cache = prefill(params, prompt, cache, jnp.asarray([7], jnp.int32))
    _sync(logits)
    ttft_s = time.perf_counter() - t_pf0  # includes compile (cold TTFT)

    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:1]
    pos = 8

    def step_once(tok, cache, history, hist_slot, pos):
        out = decode(
            params, tok.reshape(1), cache, jnp.int32(pos), key, history,
            hist_slot,
        )
        if multistep > 1:
            toks, cache, history, hist_slot = out
            return toks[-1], cache, history, hist_slot, pos + multistep
        tok, cache, history, hist_slot = out
        return tok, cache, history, hist_slot, pos + 1

    # never overrun the KV window: prompt(8) + 3 warm-up dispatches + timed
    # dispatches must fit max_seq (dynamic_update_slice would clamp silently
    # and the timed loop would rewrite the last slot at wrong positions).
    # Checked BEFORE warm-up so an invalid combination fails fast instead of
    # burning compiles on clamped writes.
    per = max(1, multistep)
    max_dispatches = (config.max_seq_len - 8) // per - 3
    if max_dispatches < 1:
        sys.exit(
            f"error: CAKE_BENCH_SEQ={config.max_seq_len} too small for "
            f"CAKE_BENCH_MULTISTEP={multistep}"
        )
    dispatches = max(1, min(steps // per, max_dispatches))

    # warm-up (compile + 2 dispatches)
    for _ in range(3):
        tok, cache, history, hist_slot, pos = step_once(
            tok, cache, history, hist_slot, pos
        )
    _sync(tok)

    t0 = time.perf_counter()
    for _ in range(dispatches):
        tok, cache, history, hist_slot, pos = step_once(
            tok, cache, history, hist_slot, pos
        )
    _sync(tok)
    dt = time.perf_counter() - t0

    timed_tokens = dispatches * per
    toks_per_s = timed_tokens / dt
    model_gb = _param_bytes(params) / 1e9
    wtag = _wtag(quant, kv_quant)
    _emit({
        "metric": f"decode_tokens_per_sec_{_mtag(preset)}_{wtag}_1chip",
        "value": round(toks_per_s, 3),
        "unit": "tokens/s",
    }, dev, **_vs_roofline(dev, params, toks_per_s))
    sys.stderr.write(
        f"device={dev.device_kind} params={model_gb:.2f}GB "
        f"ttft_cold={ttft_s:.2f}s "
        f"timed_tokens={timed_tokens} multistep={per}\n"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
