"""Master: distributed generation across topology-assigned runners.

Equivalent of the reference master + distributed LLama model
(`cake-core/src/cake/master.rs` + `model/llama.rs:61-219`): the master holds
the embedding, final norm, lm_head, tokenizer and sampler (llama.rs:61-76),
walks the decoder blocks in order with contiguous same-owner runs coalesced
into one call (llama.rs:88-119), and streams tokens with a tokens/sec report
that excludes the warm-up token (master.rs:36-65).

The walk is planned *statically* from the topology into segments
(topology.segments) — local segments run as one jitted scan on this host's
device, remote segments as one wire round-trip to their worker
(parallel/runner.py). This is the cross-host runtime; the on-pod equivalent
(whole pipeline in one compiled program over a mesh) is parallel/pipeline.py.
"""

from __future__ import annotations

import logging
import time
from functools import partial

import jax
import jax.numpy as jnp

from cake_tpu.models import llama
from cake_tpu.models.config import LlamaConfig
from cake_tpu.obs import flight as obs_flight
from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.obs.trace import span
from cake_tpu.ops import sampling
from cake_tpu.ops.sampling import SamplerSettings
from cake_tpu.parallel.runner import BlockRunner, LocalRunner, RemoteRunner
from cake_tpu.parallel.topology import Topology
from cake_tpu.runtime import protocol, wire
from cake_tpu.runtime.generator import GeneratorBase, Token, _bucket, _lm_head

log = logging.getLogger("cake_tpu.master")


def build_runners(
    config: LlamaConfig,
    topology: Topology,
    local_params_loader,  # callable (start, stop) -> stacked layers pytree
    max_seq: int | None = None,
    wire_codec: str = "none",
    op_timeout_s: float | None = None,
    connect_retries: int = 0,
    recover_deadline_s: float | None = None,
) -> list[BlockRunner]:
    """Plan the block walk: one runner per contiguous same-owner segment.
    Unassigned layers run locally on the master (llama.rs:177-193: topology
    decides Client vs local Transformer per layer). ``wire_codec`` selects
    the activation encoding for every remote hop (negotiated against each
    worker's advertised set at handshake). The failure-domain knobs pass
    straight through to every RemoteRunner: ``op_timeout_s``
    (``--op-timeout``) bounds each wire round trip, ``connect_retries``
    (``--connect-retries``) retries the initial handshake with backoff so
    a master can start before its workers, ``recover_deadline_s``
    (``--recover-deadline``) budgets each replica's mid-stream reconnect.
    A topology node whose ``host`` is a LIST hands the whole replica set
    to its runner (failover order)."""
    protocol.check_stream_width(config)
    runners: list[BlockRunner] = []
    for seg in topology.segments(config.num_hidden_layers):
        if seg.owner is None:
            runners.append(
                LocalRunner(
                    config, local_params_loader(seg.start, seg.stop),
                    seg.start, seg.stop, max_seq=max_seq or config.max_seq_len,
                )
            )
        else:
            node = topology[seg.owner]
            runner = RemoteRunner(
                node.hosts or node.host, seg.start, seg.stop,
                max_seq=max_seq or config.max_seq_len,
                wire_codec=wire_codec,
                op_timeout_s=op_timeout_s,
                connect_retries=connect_retries,
                recover_deadline_s=recover_deadline_s,
            )
            log.info("connected: %s", runner.info)
            runners.append(runner)
    return runners


class DistributedGenerator(GeneratorBase):
    """Generator-trait surface over a runner plan (shares GeneratorBase with
    the all-local runtime.generator.LlamaGenerator; only the execution path
    differs: embed + runner walk + head here, one fused program there)."""

    def __init__(
        self,
        config: LlamaConfig,
        head_params: dict,  # embed, norm_f, lm_head
        runners: list[BlockRunner],
        tokenizer=None,
        settings: SamplerSettings | None = None,
        max_seq: int | None = None,
    ):
        super().__init__(config, tokenizer, settings, max_seq)
        self.runners = runners
        # identities resolved once: span kwargs on the per-token walk must
        # not re-derive them (disabled-tracer cost stays near-zero)
        self._seg_idents = [r.ident() for r in runners]
        self.embed = head_params["embed"]
        self.norm_f = head_params["norm_f"]
        self.lm_head = head_params["lm_head"]
        # Same head math as the all-local path (generator._lm_head) — one
        # implementation, no drift between the fused and distributed runtimes.
        self._head_fn = jax.jit(
            partial(
                _lm_head,
                {"norm_f": self.norm_f, "lm_head": self.lm_head},
                config=config,
            )
        )
        self._sample_fn = jax.jit(
            partial(sampling.sample_token, settings=self.settings)
        )
        self._t_start: float | None = None
        # Per-segment forward-time histograms (the TPU-side analogue of the
        # reference's per-worker ops/s + handshake-latency stats, worker.rs:19);
        # the first call per runner per prompt (prefill + XLA compile) is kept
        # apart in a warmup gauge so the histogram holds steady-state decode
        # only, like tokens_per_sec. The instruments are per-instance (each
        # generator's runner_stats reads its own) and published into the
        # global registry under stable names, latest instance winning, so
        # --metrics-out and the Prometheus dump see the live generator.
        reg = obs_metrics.registry()
        self._seg_hist = [
            obs_metrics.Histogram(f"master.segment{i}.decode_ms")
            for i in range(len(runners))
        ]
        self._seg_warm = [
            obs_metrics.Gauge(f"master.segment{i}.warmup_ms")
            for i in range(len(runners))
        ]
        reg.publish(*self._seg_hist, *self._seg_warm)
        self._tokens_ctr = obs_metrics.counter("master.tokens_generated")
        self._recoveries_ctr = obs_metrics.counter("master.recoveries")
        self._failovers_ctr = obs_metrics.counter("master.failovers")
        self._last_seg_ms: list[float] = []  # per-segment ms of the last walk
        self._last_sample_ms = 0.0
        self.recoveries = 0  # successful mid-stream reconnect+replay count
        self.failovers = 0  # recoveries that landed on a different replica
        self._scraper = None  # lazy ClusterScraper (cluster_scraper())
        self._consec_recoveries = 0  # capped so a dead link can't loop forever
        self._timing_paused = False  # replay forwards are not decode samples

    MAX_CONSEC_RECOVERIES = 3

    def _on_new_prompt(self) -> None:
        self._t_start = None
        # the consecutive-recovery cap guards ONE stream's recovery loop;
        # carrying the count across prompts would let a long session
        # accumulate unrelated recoveries until a healthy stream trips
        # MAX_CONSEC_RECOVERIES spuriously
        self._consec_recoveries = 0
        # each prompt's first forward is a fresh prefill — re-classify it as
        # warm-up so avg_ms stays steady-state decode only
        for g in self._seg_warm:
            g.set(0.0)
        # recover(), not bare reset(): the per-prompt reconnect is the same
        # failure domain as a mid-stream one (a worker restarting between
        # prompts, a dead primary with a live replica) and must get the
        # same backoff budget + failover instead of dying on the first
        # refused connect
        self._recover_runners()

    def _recover_runners(self) -> None:
        """Bring every runner back (reconnect with backoff, possibly
        failing over to the next replica), keeping the failover counter
        and the per-segment identities in sync — span tags and
        runner_stats must show the live replica from the first
        post-recovery token."""
        for i, r in enumerate(self.runners):
            if r.recover():
                self.failovers += 1
                self._failovers_ctr.inc()
                self._seg_idents[i] = r.ident()

    # -- forward across runners --------------------------------------------
    def _forward(self, tokens: list[int], pos: int, last_index: int) -> jax.Array:
        # through the shared embedding entry point so family deltas (Gemma's
        # sqrt(hidden) embed scaling) hold on the distributed path too.
        # Device-resident walk: ``x`` stays a jax.Array across consecutive
        # LocalRunner segments (async dispatch, no host sync) and is only
        # materialized as numpy at remote boundaries — on a mixed topology
        # this removes two host copies per local segment per token (the
        # reference bounces every hop through host memory, llama.rs:100-119).
        # Per-segment timings therefore measure dispatch for local segments;
        # their compute lands in the next remote hop's encode sync or the
        # head fetch, which is exactly the overlap being bought.
        x = llama.embed_tokens({"embed": self.embed},
                               jnp.asarray([tokens], jnp.int32), self.config)
        self._last_seg_ms = []
        for i, runner in enumerate(self.runners):
            runner.last_call = {}
            t0 = time.perf_counter()
            with span("decode.segment", seg=i, ident=self._seg_idents[i]):
                x = runner.forward_jax(x, pos)
            dt = time.perf_counter() - t0
            self._last_seg_ms.append(dt * 1e3)
            # the periodic clock refresh (3 ping RTTs every 30s) and any
            # wait on the scraper's STATS round trip ride inside the
            # forward call; keep both out of the steady-state histogram so
            # the segment p99 measures the worker, not the estimator or
            # --top. The flight record keeps the full wall time.
            seg_ms = dt * 1e3 - runner.last_call.get(
                "clock_refresh_ms", 0.0) - runner.last_call.get(
                "lock_wait_ms", 0.0)
            if self._timing_paused:
                pass  # recovery replay: prefill-sized, not steady-state
            elif self._seg_warm[i].value == 0.0:
                self._seg_warm[i].set(seg_ms)
            else:
                self._seg_hist[i].observe(seg_ms)
        x_last = jnp.asarray(x[:, last_index, :])
        return self._head_fn(x_last)[0]

    def _replay_context(self) -> jax.Array:
        """Failure recovery the reference lacks (SURVEY §5: a dropped worker
        connection just ends the generation, client.rs:52-61): reconnect
        every segment — a fresh connection means a fresh worker-side KV
        cache (worker.rs:52-61) — and rebuild all segment caches by
        replaying prompt + generated-so-far in one pass. Each remote
        reconnect retries with backoff under the runner's recovery
        deadline and may FAIL OVER to the segment's next replica (the
        replay rebuilds KV there from scratch, so a replica needs no
        state transfer). Returns logits at the last context position,
        ready to sample the next token."""
        self._recover_runners()
        ctx = self._prompt_tokens + self._generated
        n = len(ctx)
        if n > self.max_seq:
            raise RuntimeError("cannot recover: context exceeds max_seq")
        t_pad = _bucket(n, self.max_seq)
        self._timing_paused = True
        try:
            with span("recover.replay", tokens=n):
                logits = self._forward(ctx + [0] * (t_pad - n), 0, n - 1)
        finally:
            self._timing_paused = False
        self._pos = n
        self.recoveries += 1
        self._recoveries_ctr.inc()
        return logits

    def _recover(self, e: Exception) -> jax.Array:
        """Recovery driver: reconnect+replay until logits land or the
        consecutive-recovery cap trips. The loop (rather than a single
        attempt) covers the replay ITSELF faulting — a worker that dies
        again mid-replay, or a replica that accepts the handshake and
        then drops — each round burning one unit of the cap. Transport
        failures only: a worker-reported op error
        (protocol.WorkerOpError) is deterministic — replaying the context
        would just re-run the same failing op at prefill cost."""
        while True:
            self._consec_recoveries += 1
            if self._consec_recoveries > self.MAX_CONSEC_RECOVERIES:
                raise RuntimeError(
                    f"giving up after {self.MAX_CONSEC_RECOVERIES} "
                    f"consecutive recovery attempts"
                ) from e
            log.warning("segment forward failed (%s); reconnecting "
                        "and replaying %d-token context", e,
                        len(self._prompt_tokens) + len(self._generated))
            try:
                return self._replay_context()
            except (OSError, wire.WireError) as e2:
                e = e2

    # -- Generator trait ----------------------------------------------------
    def next_token(self, index: int) -> Token:
        t_tok0 = time.perf_counter()
        recoveries0 = self.recoveries
        failovers0 = self.failovers
        if index == 0:
            self._require_prompt()
            n = len(self._prompt_tokens)
            t_pad = _bucket(n, self.max_seq)
            with span("prefill", tokens=n):
                # prefill recovers like decode (the seed only guarded
                # decode steps): the replay context IS the prompt at this
                # point, so _recover rebuilds exactly the prefill state
                try:
                    logits = self._forward(
                        self._prompt_tokens + [0] * (t_pad - n), 0, n - 1
                    )
                    self._pos = n
                except (OSError, wire.WireError) as e:
                    logits = self._recover(e)
                tok_id = self._sample(logits, index)
        else:
            self._check_capacity()
            with span("decode.step", index=index):
                try:
                    logits = self._forward([self._last_token], self._pos, 0)
                    self._pos += 1
                    self._consec_recoveries = 0
                except (OSError, wire.WireError) as e:
                    logits = self._recover(e)
                tok_id = self._sample(logits, index)

        if index == 0:
            # tokens/sec excludes the warm-up token (master.rs:37-40)
            self._t_start = time.perf_counter()
        self._tokens_ctr.inc()
        rec = obs_flight.recorder()
        if rec.enabled:
            wire_tot = {"wire_bytes_out": 0, "wire_bytes_in": 0,
                        "wire_bytes_raw": 0,
                        "serialize_ms": 0.0, "deserialize_ms": 0.0}
            for r in self.runners:
                for k in wire_tot:
                    wire_tot[k] += r.last_call.get(k, 0)
            rec.record(
                index=index,
                kind="prefill" if index == 0 else "decode",
                total_ms=round((time.perf_counter() - t_tok0) * 1e3, 3),
                segments_ms=[round(ms, 3) for ms in self._last_seg_ms],
                sample_ms=round(self._last_sample_ms, 3),
                recovery=self.recoveries > recoveries0,
                failover=self.failovers > failovers0,
                **{k: round(v, 3) if isinstance(v, float) else v
                   for k, v in wire_tot.items()},
            )
        return self._finish_token(tok_id)

    # Constrained decoding rides for free on the wire path: sampling (and
    # therefore masking) is master-side — workers only ever see
    # activations, so a grammar constrains a distributed topology without
    # any protocol change. The [V]-bit mask row uploads per token here
    # (the single-stream wire walk is host-loop-bound anyway; the batch
    # engine is where the device-resident-table design pays).
    supports_guide = True

    def _sample(self, logits: jax.Array, index: int) -> int:
        """Sample + history push, timed for the flight record (the int()
        fetch synchronizes, so sample_ms covers the real device work)."""
        t0 = time.perf_counter()
        with span("sample", index=index):
            step_key = jax.random.fold_in(self._key, index)
            if self.guide is not None:
                tok = self._sample_fn(
                    logits, step_key, self._history,
                    mask=jnp.asarray(self.guide.mask_bool()))
            else:
                tok = self._sample_fn(logits, step_key, self._history)
            self._history, self._hist_slot = sampling.push_history(
                self._history, self._hist_slot, tok
            )
            tok_id = int(tok)
        self._last_sample_ms = (time.perf_counter() - t0) * 1e3
        return tok_id

    def tokens_per_sec(self) -> float | None:
        """Decode throughput excluding the warm-up token (master.rs:57-65).
        None until two tokens landed, and None again if the clock has not
        measurably advanced (a sub-microsecond elapsed denominator would
        report garbage teraTokens/sec)."""
        if self._t_start is None or len(self._generated) < 2:
            return None
        dt = time.perf_counter() - self._t_start
        if dt < 1e-6:
            return None
        return (len(self._generated) - 1) / dt

    def runner_stats(self) -> list[dict]:
        """Per-segment steady-state decode latency percentiles from the
        registry histograms (warm-up call reported separately). Remote
        entries include the handshake RTT recorded at connect time
        (client.rs:72-86 shows the same in the reference's WorkerInfo) and,
        for capability-advertising workers, the ping-estimated link RTT and
        clock offset (obs.clock) behind the merged trace."""
        from cake_tpu.obs.cluster import runner_link

        stats = []
        for i, r in enumerate(self.runners):
            h = self._seg_hist[i]
            entry = {
                "ident": r.ident(),
                "layers": f"{r.start}-{r.stop - 1}",
                "calls": h.count,
                "avg_ms": h.mean,
                "p50_ms": h.percentile(0.5),
                "p99_ms": h.percentile(0.99),
                "warmup_ms": self._seg_warm[i].value,
            }
            info = getattr(r, "info", None)
            if info is not None and getattr(info, "latency_ms", None):
                entry["handshake_ms"] = round(info.latency_ms, 2)
            # full failover set (runner_link below contributes "replica",
            # the live-index view — one source of truth for its format)
            addrs = getattr(r, "addrs", None)
            if addrs and len(addrs) > 1:
                entry["replicas"] = list(addrs)
            # same rtt/offset definition as the cluster report (ping
            # estimate, handshake-RTT fallback) — one source of truth
            entry.update({k: v for k, v in runner_link(r).items()
                          if v is not None})
            stats.append(entry)
        return stats

    # -- cluster view --------------------------------------------------------
    def cluster_scraper(self, straggler_factor: float | None = None):
        """The ClusterScraper over this plan's remote segments: a
        WireSource per CAP_STATS worker (in-band, works without any worker
        status port); a worker without the capability but advertising a
        ``status_port`` in its handshake is scraped over HTTP at its
        connection host instead. Cached so ``--top`` and
        ``--cluster-report`` aggregate into the same ``cluster.*``
        series."""
        from cake_tpu.obs import cluster as obs_cluster
        from cake_tpu.runtime import protocol

        if getattr(self, "_scraper", None) is None:
            sources = []
            for r in self.runners:
                if not isinstance(r, RemoteRunner):
                    continue
                if protocol.CAP_STATS in r.caps:
                    sources.append(obs_cluster.WireSource(r))
                elif getattr(r.info, "status_port", 0):
                    # mixed-version/third-party peer: advertises a status
                    # page but not the in-band STATS dialect. Reachability
                    # is the operator's call — the page binds loopback
                    # unless the worker ran with --status-bind opened up.
                    host = r.addr.rsplit(":", 1)[0]
                    sources.append(obs_cluster.HttpSource(
                        f"http://{host}:{r.info.status_port}/",
                        name=r.info.name, runner=r))
            self._scraper = obs_cluster.ClusterScraper(
                sources,
                straggler_factor or obs_cluster.DEFAULT_STRAGGLER_FACTOR,
            )
        return self._scraper

    def cluster_report(self, straggler_factor: float | None = None) -> dict:
        """One aggregation pass over every remote worker plus this
        master's own per-segment view — the ``--cluster-report`` artifact."""
        report = self.cluster_scraper(straggler_factor).scrape()
        report["segments"] = self.runner_stats()
        report["tokens_per_sec"] = self.tokens_per_sec()
        report["recoveries"] = self.recoveries
        report["failovers"] = self.failovers
        return report

    def close(self) -> None:
        # The per-segment series stay registered after close: the CLI's
        # exit-time --metrics-out dump runs AFTER run_master closes the
        # generator, and those histograms are the dump's whole point. A
        # successor generator rebinds overlapping names via publish();
        # only a successor with FEWER segments can leave a predecessor's
        # high-index rows visible, and callers who care can
        # registry().unregister(name, inst) explicitly.
        for r in self.runners:
            r.close()
