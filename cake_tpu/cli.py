"""cake-tpu command line.

Equivalent of the reference CLI (`cake-cli/src/main.rs` + the clap Args in
`cake-core/src/lib.rs:15-64`): same flag surface and defaults — --model,
--topology, --prompt, --seed (299792458), -n/--sample-len (100),
--temperature (1.0), --top-p, --top-k, --repeat-penalty (1.1),
--repeat-last-n (128), --dtype, --mode master|worker, --name, --address
(127.0.0.1:10128). TPU additions: --max-seq (the reference hard-caps 4096),
--stages/--tp for the on-pod mesh pipeline instead of TCP workers.

Usage:
  python -m cake_tpu.cli --model /path/to/llama --prompt "..."          # local
  python -m cake_tpu.cli --mode worker --name w1 --model ... --topology t.yml
  python -m cake_tpu.cli --model ... --topology t.yml --prompt "..."    # master
"""

from __future__ import annotations

import argparse
import logging
import os
import sys
import time
from pathlib import Path

log = logging.getLogger("cake_tpu.cli")


def _quant_spec(s: str) -> str:
    """argparse validator for --quantize (int8 | int4 | int4:gN)."""
    from cake_tpu.ops.quant import parse_quant_spec

    try:
        parse_quant_spec(s)
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e))
    return s


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="cake-tpu",
        description="TPU-native distributed single-stream LLM inference",
    )
    p.add_argument("--model", default=None,
                   help="checkpoint directory (config.json + safetensors); "
                        "required in every mode except gateway (a gateway "
                        "holds no model — its backends do)")
    p.add_argument("--fetch", default=None, metavar="SRC",
                   help="populate --model first from hf://org/name[@rev] or "
                        "a local dir (idempotent; unlike the reference's "
                        "forced hub re-download, cake/mod.rs:88-96)")
    p.add_argument("--refetch", action="store_true",
                   help="with --fetch: re-copy/re-download even if --model "
                        "already holds a complete checkpoint")
    p.add_argument("--mode", choices=["master", "worker", "serve",
                                      "gateway"],
                   default="master",
                   help="master: one-shot generation (default); worker: "
                        "serve topology-assigned layers over the wire; "
                        "serve: network-facing request serving — an HTTP "
                        "API (POST /v1/completions with SSE streaming, "
                        "/v1/models, /healthz, plus the / + /metrics "
                        "status surface) over the continuous-batching "
                        "engine, with admission queueing, backpressure, "
                        "cancellation, and graceful SIGTERM drain; "
                        "gateway: route the same API across a fleet of "
                        "serve replicas (--backends) with health-checked "
                        "load-aware routing, transparent failover, and "
                        "SSE pass-through")
    p.add_argument("--name", default=None, help="worker name in the topology")
    p.add_argument("--address", default="127.0.0.1:10128",
                   help="worker bind address")
    p.add_argument("--topology", default=None, help="topology YAML path")
    p.add_argument("--status-port", type=int, default=None,
                   dest="status_port", metavar="PORT",
                   help="serve a live status page over HTTP (0 = ephemeral "
                        "port): worker mode exposes identity/layer/traffic "
                        "JSON on / (the headless equivalent of the "
                        "reference's worker GUI), master mode its own "
                        "registry incl. the merged cluster.* series; both "
                        "serve Prometheus text on /metrics")
    p.add_argument("--status-bind", default="127.0.0.1", dest="status_bind",
                   metavar="ADDR",
                   help="interface for --status-port (default 127.0.0.1: "
                        "the page exposes identity, layer assignment, and "
                        "traffic counters, so it stays host-local unless "
                        "you opt in; 0.0.0.0 serves every interface — do "
                        "that only on a trusted network, e.g. for a remote "
                        "master's cluster scraper or a Prometheus host)")
    p.add_argument("--prompt", default="Why is the sky blue?")
    p.add_argument("--prompt-ids", default=None, dest="prompt_ids",
                   help="comma-separated token ids (bypasses the tokenizer)")
    p.add_argument("--prompts-file", default=None, dest="prompts_file",
                   help="serve N prompts concurrently (one text prompt per "
                        "line, or comma-separated token-id lists with "
                        "--prompts-ids) over the batched mesh pipeline")
    p.add_argument("--prompts-ids", action="store_true", dest="prompts_ids",
                   help="treat every --prompts-file line as comma-separated "
                        "token ids (explicit per-file mode: a text prompt "
                        "that happens to look numeric, like '1, 2, 3', is "
                        "never silently id-parsed)")
    p.add_argument("--dp", type=int, default=1,
                   help="data-parallel width for --prompts-file serving")
    p.add_argument("--seed", type=int, default=299792458)
    p.add_argument("-n", "--sample-len", type=int, default=100, dest="sample_len")
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top-p", type=float, default=None, dest="top_p")
    p.add_argument("--top-k", type=int, default=None, dest="top_k")
    p.add_argument("--repeat-penalty", type=float, default=1.1,
                   dest="repeat_penalty")
    p.add_argument("--repeat-last-n", type=int, default=128,
                   dest="repeat_last_n")
    p.add_argument("--dtype", choices=["bf16", "f16", "f32"], default="bf16",
                   help="f16 maps to bf16 on TPU")
    p.add_argument("--quantize", type=_quant_spec, default=None,
                   metavar="{int8,int4,int4:gN}",
                   help="quantize linear weights on load (per-channel "
                        "symmetric; int4 is packed two-per-byte; int4:gN "
                        "uses N-row group-wise scales, the accuracy tier)")
    p.add_argument("--kv-quant", choices=["int8"], default=None,
                   dest="kv_quant",
                   help="store the KV cache as int8 + per-slot scales "
                        "(half the cache HBM — roughly doubles servable "
                        "batch x window, or doubles the --sp long-context "
                        "window; local and mesh paths)")
    p.add_argument("--kv-layout", choices=["slot", "paged"], default="slot",
                   dest="kv_layout",
                   help="KV cache layout for the batched serving engine: "
                        "'slot' (per-stream contiguous rows; default) or "
                        "'paged' (pooled fixed-size pages addressed through "
                        "per-stream page tables, with copy-on-write "
                        "shared-prefix pages — cake_tpu/kvpool; admission/"
                        "retirement touch page tables, not cache tensors). "
                        "--mode serve and --prompts-file batch runs")
    p.add_argument("--kv-page-size", type=int, default=None,
                   dest="kv_page_size", metavar="N",
                   help="--kv-layout paged: tokens per KV page (must divide "
                        "the window; default 16)")
    p.add_argument("--kv-pool-pages", type=int, default=None,
                   dest="kv_pool_pages", metavar="N",
                   help="--kv-layout paged: total pool pages (power of two, "
                        ">= batch x window/page_size + 1; default sized "
                        "from the batch plus prefix-tree headroom)")
    p.add_argument("--decode-block", type=int, default=None,
                   dest="decode_block",
                   help="fused decode steps per dispatch (all-local and mesh "
                        "paths; 1 = one program per token; default 8)")
    p.add_argument("--lookahead", action="store_true",
                   help="dispatch decode block N+1 from the device-side "
                        "feedback token BEFORE fetching block N's tokens to "
                        "the host — hides readback/detok/emission behind "
                        "device compute (the all-local fused-block path; "
                        "token streams are bit-identical to the "
                        "non-lookahead path). The batched engine "
                        "(--prompts-file, --mode serve) gives the device "
                        "its next block before it hands out a block's rows "
                        "by itself: there the flag changes nothing and "
                        "says so")
    p.add_argument("--wire-codec", choices=["none", "bf16", "int8"],
                   default=None, dest="wire_codec",
                   help="activation encoding for cross-host worker hops "
                        "(negotiated at handshake). Master: the codec every "
                        "remote segment uses (default none). Worker: "
                        "restrict what this worker accepts/mirrors "
                        "(default: all). bf16 ~2x fewer bytes on f32 runs; "
                        "int8 (per-row absmax scales) ~4x — both perturb "
                        "low-order logit bits like --kv-quant does")
    p.add_argument("--speculate", type=int, default=0, metavar="K",
                   help="n-gram speculative decoding: propose K tokens per "
                        "round from the context's own n-grams and verify "
                        "them in one dispatch (greedy streams bit-exact; "
                        "sampled streams distribution-exact via rejection "
                        "sampling; local, mesh --stages/--tp, and "
                        "--prompts-file serving paths — serving verifies "
                        "every stream's proposals per-row in one batched "
                        "pass. NOTE: with temperature > 0 serving rounds "
                        "always run the K+1-wide verify (skipping on other "
                        "streams' proposals would break per-stream "
                        "reproducibility), so sampled speculation only "
                        "pays off on repetitive/structured streams)")
    p.add_argument("--max-seq", type=int, default=None, dest="max_seq")
    p.add_argument("--window", type=int, default=None,
                   help="override the attention sliding window (tokens): "
                        "narrow a Mistral-family window, give any model "
                        "one, or 0 to disable the checkpoint's window")
    p.add_argument("--stages", type=int, default=1,
                   help="on-pod pipeline stages (mesh, not TCP)")
    p.add_argument("--tp", type=int, default=1, help="tensor-parallel width")
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel width (ring attention prefill)")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel width (MoE families: the expert "
                        "stacks shard over this mesh axis)")
    p.add_argument("--prefill-chunks", type=int, default=1,
                   dest="prefill_chunks",
                   help="pipeline the prompt pass through the stages in M "
                        "chunks (GPipe-style overlap; stages>1, sp=1)")
    p.add_argument("--device", type=int, default=None,
                   help="device ordinal (reference --device GPU ordinal, "
                        "lib.rs:17-19; here an index into jax.devices())")
    p.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="multi-host pod: jax.distributed coordinator "
                        "address (same command on every host; pairs with "
                        "--num-processes/--process-id, or auto-resolved on "
                        "Cloud TPU)")
    p.add_argument("--num-processes", type=int, default=None,
                   dest="num_processes")
    p.add_argument("--process-id", type=int, default=None, dest="process_id")
    p.add_argument("--cpu", action="store_true", help="force CPU backend")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="write a jax.profiler trace of generation to DIR "
                        "(with the run's prof.* phase spans on its host "
                        "plane and in DIR/spans.trace.json); in --mode "
                        "serve, the directory the capture control "
                        "(POST /debug/trace) writes into")
    # -- observability (cake_tpu/obs): spans, metrics, flight records ------
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="record runtime spans (prefill, decode.step, "
                        "decode.segment, wire.send/recv, ...) and write a "
                        "Chrome trace-event JSON on exit — load it in "
                        "Perfetto or chrome://tracing; with --profile the "
                        "spans also pass through to the XLA profile as "
                        "jax.profiler TraceAnnotations")
    p.add_argument("--metrics-out", default=None, dest="metrics_out",
                   metavar="PATH",
                   help="dump the metrics registry (counters, gauges, "
                        "latency histograms with p50/p99) as JSON on exit")
    p.add_argument("--flight-log", default=None, dest="flight_log",
                   metavar="PATH",
                   help="append flight-recorder JSON lines to PATH: one per "
                        "token on the per-token paths (kind, per-segment "
                        "ms, wire bytes, serialize/sample ms, recovery "
                        "events), one per dispatch on fused-block/batched "
                        "paths (with steps/batch fields)")
    p.add_argument("--cluster-report", default=None, dest="cluster_report",
                   metavar="PATH",
                   help="master+topology runs: write an end-of-run JSON "
                        "cluster report — per-worker segment forward "
                        "p50/p99, RTT and clock offset (ping-estimated), "
                        "byte/op counters, straggler flags, plus the "
                        "master's own per-segment stats")
    p.add_argument("--prof-sample", type=int, default=None,
                   dest="prof_sample", metavar="N",
                   help="engine profiling plane (cake_tpu/obs/prof): stamp "
                        "a full per-phase step breakdown every Nth engine "
                        "step (default 64; 0 disables sampling entirely, "
                        "1 stamps every step; a capture started with "
                        "POST /debug/trace stamps every step while it is "
                        "open). The report is served live at GET "
                        "/debug/prof and folded into --trace timelines as "
                        "prof.* spans")
    p.add_argument("--top", action="store_true",
                   help="master+topology runs: live ANSI cluster panel on "
                        "stderr while generating (per-worker p50/p99, RTT, "
                        "offset, straggler flags; plain escape-code "
                        "refresh, no curses; the token stream on stdout "
                        "stays clean)")
    # -- failure domain (runtime/retry, testing/chaos) ----------------------
    p.add_argument("--recover-deadline", type=float, default=None,
                   dest="recover_deadline", metavar="S",
                   help="master+topology runs: per-replica budget (seconds, "
                        "default 30) for a mid-stream reconnect — retried "
                        "with jittered exponential backoff, so a worker "
                        "restarting for a few seconds no longer kills the "
                        "stream; when a segment's topology entry lists "
                        "replica addresses, expiry fails over to the next "
                        "one and the context replay rebuilds its KV")
    p.add_argument("--connect-retries", type=int, default=0,
                   dest="connect_retries", metavar="N",
                   help="master+topology runs: retry each worker's INITIAL "
                        "handshake up to N times with backoff instead of "
                        "failing on the first refused connect — the master "
                        "can start before its workers (default 0: fail "
                        "fast)")
    p.add_argument("--op-timeout", type=float, default=None,
                   dest="op_timeout", metavar="S",
                   help="master+topology runs: per-op recv deadline "
                        "(seconds) on every forward/STATS/PING exchange; a "
                        "wedged worker then faults into reconnect+replay "
                        "instead of hanging the decode loop forever. "
                        "Default scales with segment size (120 + 2s/layer "
                        "— generous: it catches wedged peers, not slow "
                        "ones)")
    p.add_argument("--chaos", default=None, metavar="SPEC",
                   help="DEV: put a fault-injecting proxy "
                        "(cake_tpu.testing.chaos) in front of every worker "
                        "link. SPEC is comma-separated "
                        "kind[@[r]FRAME][=PARAM] directives — kill, "
                        "truncate, corrupt, stall (PARAM ms), blackhole, "
                        "refuse (PARAM conns) — applied to successive "
                        "connections per link, or seed=N for a "
                        "seed-reproducible random schedule. E.g. "
                        "--chaos kill@7 kills each link after its 7th "
                        "request frame; --chaos seed=1337 reproduces "
                        "exactly the run that failed under seed 1337")
    p.add_argument("--straggler-factor", type=float, default=2.0,
                   dest="straggler_factor", metavar="F",
                   help="flag a worker as straggler when its segment "
                        "forward p99 exceeds the median of its peers' "
                        "p99s by this factor (cluster report / --top / "
                        "cluster.* gauges; default 2.0)")
    # -- request serving (--mode serve: cake_tpu/serve) ---------------------
    p.add_argument("--serve-port", type=int, default=None, dest="serve_port",
                   metavar="PORT",
                   help="--mode serve: HTTP port for the serving API "
                        "(default 8080; 0 = ephemeral). The same port "
                        "serves / + /metrics, so one scrape sees traffic "
                        "and observability")
    p.add_argument("--serve-bind", default=None, dest="serve_bind",
                   metavar="ADDR",
                   help="--mode serve: bind interface (default 127.0.0.1 "
                        "— serving beyond the host is an explicit "
                        "decision, same policy as --status-bind)")
    p.add_argument("--max-concurrent", type=int, default=None,
                   dest="max_concurrent", metavar="N",
                   help="--mode serve: concurrently decoding streams — "
                        "the engine's batch slots (default 8; a "
                        "host-addressed --topology serializes at 1, the "
                        "single-stream wire path)")
    p.add_argument("--queue-depth", type=int, default=None,
                   dest="queue_depth", metavar="N",
                   help="--mode serve: bounded admission queue; a submit "
                        "past the bound answers 429 with a Retry-After "
                        "derived from observed tokens/sec (default 64)")
    p.add_argument("--request-timeout", type=float, default=None,
                   dest="request_timeout", metavar="S",
                   help="--mode serve: per-request deadline from arrival "
                        "(seconds, default 300): expired requests are "
                        "refused while queued (504) or retired mid-stream "
                        "(finish_reason 'timeout'), freeing the slot")
    p.add_argument("--serve-logprobs", type=int, default=0,
                   dest="serve_logprobs", metavar="K",
                   help="--mode serve: per-token top-K logprob capacity — "
                        "the decode programs also return the top-K "
                        "log-softmax, so requests may ask 'logprobs': N "
                        "for any N <= K (default 0: refused with 400; "
                        "needs the batched mesh engine)")
    p.add_argument("--role", choices=["mixed", "prefill", "decode"],
                   default="mixed",
                   help="--mode serve: replica tier (cake_tpu/disagg) — "
                        "mixed (default) runs the classic everything-"
                        "replica; prefill runs bucketed prefill only and "
                        "ships the finished KV pages to a decode replica "
                        "over the transfer channel; decode imports pages "
                        "and runs only the steady-state batched step "
                        "(both need --kv-layout paged)")
    p.add_argument("--transfer-port", type=int, default=None,
                   dest="transfer_port", metavar="PORT",
                   help="--mode serve: KV transfer-channel listener port "
                        "(0 = ephemeral; advertised on /healthz as "
                        "transfer_port so the gateway's tier map finds "
                        "it). Defaults to ephemeral for --role decode; "
                        "setting it on a mixed replica lets it accept "
                        "imports too (session resume without a tier "
                        "split)")
    p.add_argument("--transfer-codec", choices=["none", "bf16", "int8"],
                   default="none", dest="transfer_codec",
                   help="--mode serve: per-page codec for exported KV "
                        "snapshots (the --wire-codec path; default "
                        "none). Round trips are bit-identical whenever "
                        "the codec is lossless for the cache dtype — "
                        "none always, bf16 on a bf16 cache, int8 on an "
                        "int8-quantized pool")
    p.add_argument("--sched-policy", choices=["slo", "fifo"],
                   default="slo", dest="sched_policy",
                   help="--mode serve: admission policy (ISSUE 20) — "
                        "slo (default): priority classes ('class': "
                        "interactive|batch on /v1/completions), "
                        "preemption with host-RAM KV spill, per-tenant "
                        "fairness; fifo: strict arrival order, no "
                        "preemption (the single-tenant baseline)")
    p.add_argument("--spill-mb", type=float, default=64.0,
                   dest="spill_mb", metavar="MB",
                   help="--mode serve: host-RAM budget for preempted "
                        "stream snapshots (default 64; 0 disables "
                        "preemption — class ordering still applies). "
                        "Spilling needs the paged engine "
                        "(--kv-layout paged)")
    p.add_argument("--fairness-factor", type=float, default=2.0,
                   dest="fairness_factor", metavar="X",
                   help="--mode serve: a tenant is over budget when its "
                        "share of recent tokens exceeds X times its "
                        "fair share (default 2.0) — over-budget "
                        "tenants queue behind in-budget arrivals and "
                        "are preferred preemption victims ('tenant' "
                        "body field, defaults to the request class)")
    p.add_argument("--slo-ttft-ms", type=float, default=None,
                   dest="slo_ttft_ms", metavar="MS",
                   help="--mode serve/gateway: per-request time-to-first-"
                        "token SLO target. Completed requests are judged "
                        "good/bad against it (slo.good/slo.bad counters, "
                        "slo.burn_short/slo.burn_long burn-rate gauges on "
                        "/metrics and /healthz; per-request verdict on "
                        "GET /v1/requests/<id>)")
    p.add_argument("--slo-tpot-ms", type=float, default=None,
                   dest="slo_tpot_ms", metavar="MS",
                   help="--mode serve/gateway: per-request mean time-per-"
                        "output-token SLO target (same accounting as "
                        "--slo-ttft-ms; a request must meet BOTH set "
                        "targets to count good)")
    # -- routing gateway (--mode gateway: cake_tpu/gateway) ------------------
    p.add_argument("--backends", default=None, metavar="HOST:PORT,...",
                   help="--mode gateway: comma-separated serve-replica "
                        "addresses the gateway routes across (each runs "
                        "--mode serve; the gateway health-checks their "
                        "/healthz and proxies /v1/completions, /v1/models "
                        "to the fleet). These are STATIC SEED members; "
                        "replicas started with --register-with join "
                        "dynamically, so an empty --backends is fine")
    p.add_argument("--register-with", default=None, dest="register_with",
                   metavar="URL",
                   help="--mode serve: announce this replica to a gateway "
                        "(POST <URL>/v1/fleet/register) and heartbeat-"
                        "renew the membership lease at the cadence the "
                        "gateway asks for; SIGTERM deregisters FIRST, so "
                        "the gateway stops routing here before the drain "
                        "starts answering 503")
    p.add_argument("--lease-ttl", type=float, default=10.0,
                   dest="lease_ttl", metavar="S",
                   help="--mode gateway: registration lease TTL for "
                        "dynamically registered replicas (default 10). A "
                        "missed renewal demotes through the probe "
                        "hysteresis — never an instant delete — and only "
                        "a long-expired, non-UP member is garbage-"
                        "collected")
    p.add_argument("--admit-wait", type=float, default=0.5,
                   dest="admit_wait", metavar="S",
                   help="--mode gateway: when EVERY routable backend is "
                        "saturated, how long an interactive request may "
                        "queue at the front door for a slot to free "
                        "before being shed with a fleet-derived "
                        "Retry-After (default 0.5; 0 = always shed; "
                        "batch-class requests never queue)")
    p.add_argument("--admit-queue", type=int, default=32,
                   dest="admit_queue", metavar="N",
                   help="--mode gateway: how many saturated-fleet "
                        "requests may queue at once (default 32; past "
                        "that, shed immediately — a bounded queue, not "
                        "buffer bloat)")
    p.add_argument("--route-policy", choices=["p2c", "round_robin",
                                              "prefix"],
                   default="p2c", dest="route_policy",
                   help="--mode gateway: routing policy — p2c "
                        "(power-of-two-choices on the live /healthz load "
                        "signal; default), round_robin, or prefix "
                        "(prefix-affinity: same-prefix prompts land on "
                        "the replica whose engine prefix store already "
                        "holds their KV, p2c fallback when it is "
                        "saturated)")
    p.add_argument("--probe-interval", type=float, default=2.0,
                   dest="probe_interval", metavar="S",
                   help="--mode gateway: seconds between /healthz probe "
                        "passes (default 2.0); DOWN backends re-probe on "
                        "a jittered backoff instead (the circuit "
                        "breaker)")
    p.add_argument("--gateway-prefix-block", type=int, default=64,
                   dest="gateway_prefix_block", metavar="N",
                   help="--mode gateway: prefix-affinity alignment — the "
                        "routing key is the FIRST N tokens of the prompt "
                        "(characters for a text prompt), so prompts "
                        "sharing a system prefix route together whatever "
                        "their tail length; prompts shorter than N get "
                        "no preference (default 64, matching the "
                        "engine's prefix_block)")
    p.add_argument("--logit-bias", default=None, dest="logit_bias",
                   metavar="ID:BIAS[,ID:BIAS...]",
                   help="static token-id logit biases compiled into the "
                        "sampler (all modes; serve requests passing "
                        "logit_bias must match these values exactly)")
    p.add_argument("--log-level", default="info", dest="log_level",
                   choices=["debug", "info", "warning", "error"],
                   help="root log level for this process (master or worker "
                        "subprocess alike; -v forces debug)")
    p.add_argument("-v", "--verbose", action="store_true")
    return p


_DTYPES = {"bf16": "bfloat16", "f16": "bfloat16", "f32": "float32"}


def _load_config(args):
    from cake_tpu.models.config import LlamaConfig

    cfg_path = Path(args.model) / "config.json"
    if not cfg_path.exists():
        sys.exit(f"error: {cfg_path} not found")
    overrides = {"dtype": _DTYPES[args.dtype]}
    if args.max_seq:
        overrides["max_seq_len"] = args.max_seq
    if getattr(args, "window", None) is not None:
        # 0 disables the checkpoint's window; N narrows (or grants) one
        overrides["sliding_window"] = args.window or None
    config = LlamaConfig.from_hf_json(cfg_path, **overrides)
    if config.sliding_window and getattr(args, "sp", 1) > 1:
        sys.exit("error: sliding-window attention (this checkpoint's "
                 "family) does not compose with --sp; run with --sp 1")
    if getattr(args, "ep", 1) > 1 and not (config.num_local_experts
                                           or config.n_routed_experts):
        sys.exit("error: --ep requires an MoE checkpoint "
                 "(num_local_experts > 0 in config.json)")
    return config


def _mesh_params(args, config, plan):
    """Load checkpoint params onto the mesh, direct-to-mesh (each shard's
    bytes only — the reference worker's own-blocks-only contract,
    worker.rs:85-98 — including int8 MoE expert stacks)."""
    from cake_tpu.utils.sharded_load import load_llama_params_on_mesh

    try:
        return load_llama_params_on_mesh(
            args.model, config, plan.mesh, quantize=args.quantize,
            tie_word_embeddings=config.tie_word_embeddings)
    except NotImplementedError as e:  # e.g. int4 MoE: clean exit, no trace
        sys.exit(f"error: {e}")


def _load_tokenizer(model_dir: str):
    tok_path = Path(model_dir) / "tokenizer.json"
    if tok_path.exists():
        try:
            from tokenizers import Tokenizer

            return Tokenizer.from_file(str(tok_path))
        except Exception as e:
            log.warning("tokenizer load failed: %s", e)
    return None


def _settings(args):
    from cake_tpu.ops.sampling import SamplerSettings

    bias: tuple = ()
    if getattr(args, "logit_bias", None):
        try:
            bias = tuple(sorted(
                (int(tok), float(b))
                for tok, _, b in (pair.partition(":")
                                  for pair in args.logit_bias.split(","))
            ))
        except ValueError:
            sys.exit("error: --logit-bias wants ID:BIAS[,ID:BIAS...] "
                     f"(got {args.logit_bias!r})")
    return SamplerSettings(
        temperature=args.temperature,
        top_k=args.top_k,
        top_p=args.top_p,
        repeat_penalty=args.repeat_penalty,
        repeat_last_n=args.repeat_last_n,
        seed=args.seed,
        logit_bias=bias,
    )


def _failure_domain_flags(args) -> list[str]:
    """Names of the worker-link failure-domain flags the user actually set
    — they only mean something on a host-addressed topology master."""
    out = []
    if args.recover_deadline is not None:
        out.append("--recover-deadline")
    if args.connect_retries:
        out.append("--connect-retries")
    if args.op_timeout is not None:
        out.append("--op-timeout")
    if args.chaos:
        out.append("--chaos")
    return out


def run_worker(args) -> int:
    from cake_tpu.parallel.topology import Topology
    from cake_tpu.runtime.worker import Worker
    from cake_tpu.utils.memory import memory_report
    from cake_tpu.utils.weights import load_llama_params

    if not args.name:
        sys.exit("error: --mode worker requires --name")
    if not args.topology:
        sys.exit("error: --mode worker requires --topology")
    if args.cluster_report or args.top:
        sys.exit("error: --cluster-report/--top are master-side aggregation "
                 "views; pass them to the master process (they would "
                 "otherwise be silently ignored in worker mode)")
    if _failure_domain_flags(args):
        sys.exit("error: --recover-deadline/--connect-retries/--op-timeout/"
                 "--chaos drive the master's side of the worker links; pass "
                 "them to the master process (they would otherwise be "
                 "silently ignored in worker mode)")
    config = _load_config(args)
    topology = Topology.from_path(args.topology)

    def loader(lo, hi):
        return load_llama_params(
            args.model, config.num_hidden_layers, dtype=config.dtype,
            layer_range=(lo, hi), include_embed=False, include_head=False,
            quantize=args.quantize,
        )["layers"]

    worker = Worker(args.name, config, topology, loader,
                    address=args.address, max_seq=args.max_seq,
                    kv_quant=args.kv_quant, wire_codec=args.wire_codec)
    if args.status_port is not None:
        worker.start_status_server(args.status_port, bind=args.status_bind)
    log.info("worker ready (%s)", memory_report())
    try:
        worker.serve_forever()
    except KeyboardInterrupt:
        worker.shutdown()
    return 0


def run_serve(args) -> int:
    """Concurrent multi-prompt serving over the batched mesh pipeline
    (--prompts-file): capability the single-request reference does not have
    (SURVEY.md §0)."""
    from cake_tpu.runtime.batch_generator import BatchGenerator
    from cake_tpu.utils.memory import memory_report
    from cake_tpu.utils.weights import load_llama_params

    if args.topology:
        sys.exit("error: --prompts-file serving runs the mesh pipeline; "
                 "--topology (cross-host workers) is not supported here")
    # Reject flags this path would otherwise silently ignore (run_master
    # gives the same treatment to its invalid combinations). --sp composes
    # with serving since r4 (the KV window shards across the sp axis —
    # many long streams per chip set) except with --speculate, whose
    # verification programs are the sp == 1 path.
    if args.sp > 1 and args.speculate:
        sys.exit("error: --speculate requires --sp 1 on the serving path")
    if args.prefill_chunks > 1:
        sys.exit("error: --prefill-chunks is not supported with "
                 "--prompts-file serving")
    # "none" is the documented default — a semantic no-op, not a request
    # for compression; only a compressing codec is misplaced here
    if args.wire_codec not in (None, "none"):
        sys.exit("error: --wire-codec applies to cross-host worker hops "
                 "(master/worker --topology runs); serving rides the mesh")
    _lookahead_is_the_order(args)
    if args.cluster_report or args.top:
        sys.exit("error: --cluster-report/--top aggregate across cross-host "
                 "workers (master/worker --topology runs); serving rides "
                 "the mesh")
    flags = _failure_domain_flags(args)
    if flags:
        sys.exit(f"error: {'/'.join(flags)} apply to cross-host worker "
                 "links (master/worker --topology runs); serving rides "
                 "the mesh")
    config = _load_config(args)
    tokenizer = _load_tokenizer(args.model)
    settings = _settings(args)

    prompts: list = []
    with open(args.prompts_file) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            if args.prompts_ids:
                toks = [t.strip() for t in line.split(",")]
                if not all(t.isdigit() for t in toks):
                    sys.exit(f"error: --prompts-ids line is not a "
                             f"comma-separated id list: {line!r}")
                prompts.append([int(t) for t in toks])
            elif tokenizer is None:
                sys.exit("error: text prompts require a tokenizer.json; "
                         "pass --prompts-ids with comma-separated token ids "
                         "per line")
            else:
                prompts.append(line)
    if not prompts:
        sys.exit(f"error: no prompts in {args.prompts_file}")

    t0 = time.perf_counter()
    from cake_tpu.parallel.mesh import MeshPlan

    try:
        plan = MeshPlan.build(config, num_stages=args.stages, tp=args.tp,
                              dp=args.dp, sp=args.sp, ep=args.ep)
    except ValueError as e:
        sys.exit(f"error: {e}")
    params = _mesh_params(args, config, plan)
    # --decode-block composes with --speculate here: spec rounds replace
    # block dispatches while proposals/window allow, and the fused block
    # remains the fallback (e.g. a stream at its window edge)
    try:
        gen = BatchGenerator(config, params, plan=plan, tokenizer=tokenizer,
                             settings=settings, max_seq=args.max_seq,
                             block_size=(args.decode_block
                                         if args.decode_block is not None
                                         else 8),
                             kv_quant=args.kv_quant, spec_k=args.speculate,
                             **_kv_layout_kwargs(args))
    except ValueError as e:  # e.g. --max-seq not divisible by --sp
        sys.exit(f"error: {e}")
    gen.set_prompts(prompts)
    log.info("model loaded in %.1fs (%s); serving %d streams",
             time.perf_counter() - t0, memory_report(), len(prompts))
    t_gen0 = time.perf_counter()
    outs = gen.generate(args.sample_len)
    dt = time.perf_counter() - t_gen0
    total = sum(len(o) for o in outs)
    for i, o in enumerate(outs):
        # decode the quota-truncated ids, not gen.texts(): ragged
        # speculation can bank tokens past -n, and printed text must agree
        # with the token counts the log reports
        if tokenizer is not None:
            print(f"[{i}] {tokenizer.decode(o)}")
        else:
            print(f"[{i}] {','.join(map(str, o))}")
    log.info("%d streams, %d tokens, %.2f tok/s aggregate — %s",
             len(outs), total, total / dt, memory_report())
    st = gen.stats()
    log.info("serving stats: %d decode + %d admission dispatches, "
             "%.2f tokens/dispatch, busy %.2fs of %.2fs wall",
             st["decode_dispatches"], st["admit_dispatches"],
             st["tokens_per_dispatch"] or 0.0, st["busy_s"], st["wall_s"])
    return 0


def _kv_layout_kwargs(args) -> dict:
    """BatchGenerator kwargs for the --kv-layout flags (defaults stay the
    engine's own when the user did not set them)."""
    kw = {"kv_layout": args.kv_layout}
    if args.kv_page_size is not None:
        kw["kv_page_size"] = args.kv_page_size
    if args.kv_pool_pages is not None:
        kw["kv_pool_pages"] = args.kv_pool_pages
    return kw


def _lookahead_is_the_order(args) -> None:
    """``--lookahead`` on a batched path (``--prompts-file``, ``--mode
    serve``): the engine has one order of work at a block boundary -- the
    device's next block first, then the landed rows -- so the flag has
    nothing to switch; it is taken, and says so."""
    if args.lookahead:
        log.warning("--lookahead changes nothing here: the batched engine "
                    "enqueues the next decode block before it hands out a "
                    "block's rows by itself (see MIGRATING.md)")


def _serve_flags(args) -> list[str]:
    """Names of the --mode serve flags the user actually set — they mean
    nothing on the one-shot master/worker paths."""
    out = []
    if args.serve_port is not None:
        out.append("--serve-port")
    if args.serve_bind is not None:
        out.append("--serve-bind")
    if args.max_concurrent is not None:
        out.append("--max-concurrent")
    if args.queue_depth is not None:
        out.append("--queue-depth")
    if args.request_timeout is not None:
        out.append("--request-timeout")
    if args.serve_logprobs:
        out.append("--serve-logprobs")
    if args.role != "mixed":
        out.append("--role")
    if args.transfer_port is not None:
        out.append("--transfer-port")
    if args.transfer_codec != "none":
        out.append("--transfer-codec")
    if args.register_with is not None:
        out.append("--register-with")
    if args.slo_ttft_ms is not None:
        out.append("--slo-ttft-ms")
    if args.slo_tpot_ms is not None:
        out.append("--slo-tpot-ms")
    if args.sched_policy != "slo":
        out.append("--sched-policy")
    if args.spill_mb != 64.0:
        out.append("--spill-mb")
    if args.fairness_factor != 2.0:
        out.append("--fairness-factor")
    return out


def _slo_tracker(args):
    """SLO accounting shared by serve and gateway (obs/reqtrace): built
    only when a target is set, so untargeted runs pay nothing."""
    if args.slo_ttft_ms is None and args.slo_tpot_ms is None:
        return None
    from cake_tpu.obs.reqtrace import SloPolicy, SloTracker

    return SloTracker(SloPolicy(ttft_ms=args.slo_ttft_ms,
                                tpot_ms=args.slo_tpot_ms))


def run_http_serve(args) -> int:
    """--mode serve: the network-facing request-serving plane
    (cake_tpu/serve) — an HTTP API + SLO-aware scheduler over the
    continuous-batching engine. Runs over every execution path the
    one-shot master supports: all-local and mesh (--stages/--tp/--sp/--ep
    or a device-indexed topology) ride BatchGenerator; a host-addressed
    --topology rides the single-stream wire master behind a one-slot
    engine adapter (requests serialize, every failure-domain knob still
    applies)."""
    import signal
    import threading

    from cake_tpu import __version__, obs
    from cake_tpu.obs import metrics as obs_metrics
    from cake_tpu.obs import prof as obs_prof
    from cake_tpu.serve.api import start_api_server
    from cake_tpu.serve.scheduler import Scheduler
    from cake_tpu.utils.memory import device_report, memory_report

    serve_port = args.serve_port if args.serve_port is not None else 8080
    serve_bind = args.serve_bind or "127.0.0.1"
    max_concurrent = (args.max_concurrent
                      if args.max_concurrent is not None else 8)
    queue_depth = args.queue_depth if args.queue_depth is not None else 64
    request_timeout = (args.request_timeout
                       if args.request_timeout is not None else 300.0)
    if max_concurrent < 1:
        sys.exit("error: --max-concurrent must be >= 1")
    if queue_depth < 1:
        sys.exit("error: --queue-depth must be >= 1")
    if request_timeout <= 0:
        sys.exit("error: --request-timeout must exceed 0 (every request "
                 "needs a deadline; raise it instead of disabling it)")
    if args.prompts_file or args.prompt_ids:
        sys.exit("error: --mode serve takes prompts over HTTP "
                 "(POST /v1/completions); --prompts-file/--prompt-ids "
                 "belong to the one-shot paths")
    if args.cluster_report or args.top:
        sys.exit("error: --cluster-report/--top report on a one-shot "
                 "master run; --mode serve exposes the same data live on "
                 "/ and /metrics instead (they would otherwise be "
                 "silently ignored)")
    if args.prefill_chunks > 1:
        sys.exit("error: --prefill-chunks is not supported with --mode "
                 "serve (arrivals prefill chunk-by-chunk through the "
                 "admission path instead; it would otherwise be silently "
                 "ignored)")
    if args.role != "mixed" and args.kv_layout != "paged":
        sys.exit(f"error: --role {args.role} moves KV between replicas "
                 "as pool pages; it requires --kv-layout paged")
    if args.role == "prefill" and args.transfer_port is not None:
        sys.exit("error: --transfer-port opens the IMPORT listener; a "
                 "prefill replica only exports (its targets arrive "
                 "per-request from the gateway)")

    config = _load_config(args)
    tokenizer = _load_tokenizer(args.model)
    settings = _settings(args)
    t0 = time.perf_counter()
    # the parts of "model loaded in", reported as numbers at
    # GET /debug/prof ``startup`` (the mesh path fills the first two)
    startup: dict[str, float] = {}

    # topology: device-indexed drives the mesh plan, host-addressed the
    # cross-host wire path (same split as run_master)
    topology = None
    topo_mesh = False
    if args.topology:
        from cake_tpu.parallel.topology import Topology

        topology = Topology.from_path(args.topology)
        with_dev = [n.name for n in topology if n.device is not None]
        without = [n.name for n in topology if n.device is None]
        if with_dev and without:
            sys.exit(
                f"error: topology mixes mesh nodes (device: {with_dev}) "
                f"with host-addressed workers ({without}); a deployment is "
                "one or the other"
            )
        topo_mesh = bool(with_dev)

    if topology is not None and not topo_mesh:
        # host-addressed workers: the single-stream wire master behind the
        # one-slot engine adapter. Concurrency serializes at 1.
        from cake_tpu.serve.engine import SingleStreamEngine

        if args.stages > 1 or args.tp > 1 or args.sp > 1 or args.ep > 1:
            sys.exit("error: --stages/--tp/--sp/--ep (single-program mesh) "
                     "and a host-addressed --topology are mutually "
                     "exclusive in serve mode too")
        if args.speculate:
            sys.exit("error: --speculate is not supported on the "
                     "host-topology serve path")
        if args.decode_block is not None or args.lookahead:
            sys.exit("error: --decode-block/--lookahead need the batched "
                     "mesh engine; the host-topology serve path "
                     "single-steps the wire master (they would otherwise "
                     "be silently ignored)")
        if args.serve_logprobs:
            sys.exit("error: --serve-logprobs needs the batched mesh "
                     "engine; the host-topology serve path has no "
                     "logprob outputs (it would otherwise be silently "
                     "ignored)")
        if args.kv_layout == "paged":
            sys.exit("error: --kv-layout paged rides the batched mesh "
                     "engine; a host-addressed --topology serve runs "
                     "the single-stream wire master")
        if max_concurrent > 1:
            log.warning("--max-concurrent %d: a host-addressed --topology "
                        "serves over the single-stream wire master; "
                        "requests serialize through 1 slot",
                        max_concurrent)
        engine = SingleStreamEngine(_build_distributed_gen(
            args, config, topology, tokenizer, settings))
        warm_len = None
    else:
        from cake_tpu.parallel.mesh import MeshPlan
        from cake_tpu.runtime.batch_generator import BatchGenerator

        flags = _failure_domain_flags(args)
        if flags:
            sys.exit(f"error: {'/'.join(flags)} apply to cross-host worker "
                     "links (a host-addressed --topology); this serve "
                     "deployment rides the mesh")
        if args.wire_codec not in (None, "none"):
            sys.exit("error: --wire-codec applies to cross-host worker "
                     "hops; this serve deployment rides the mesh")
        if args.sp > 1 and args.speculate:
            sys.exit("error: --speculate requires --sp 1 on the serving "
                     "path")
        _lookahead_is_the_order(args)
        try:
            if topo_mesh:
                plan = MeshPlan.from_topology(config, topology, tp=args.tp,
                                              sp=args.sp, ep=args.ep)
            else:
                plan = MeshPlan.build(config, num_stages=args.stages,
                                      tp=args.tp, dp=args.dp, sp=args.sp,
                                      ep=args.ep)
        except ValueError as e:
            sys.exit(f"error: {e}")
        t_params = time.perf_counter()
        params = _mesh_params(args, config, plan)
        startup["params_s"] = time.perf_counter() - t_params
        try:
            engine = BatchGenerator(
                config, params, plan=plan, tokenizer=tokenizer,
                settings=settings, max_seq=args.max_seq,
                block_size=(args.decode_block
                            if args.decode_block is not None else 8),
                kv_quant=args.kv_quant,
                spec_k=args.speculate, logprobs=args.serve_logprobs,
                **_kv_layout_kwargs(args))
        except ValueError as e:
            sys.exit(f"error: {e}")
        startup["engine_s"] = (time.perf_counter() - t_params
                               - startup["params_s"])
        # compile the admission path outside the serving window (requests
        # of any length share the chunked program for this bucket)
        warm_len = min(64, engine.max_seq // 2)

    try:
        scheduler = Scheduler(engine, queue_depth=queue_depth,
                              request_timeout_s=request_timeout,
                              role=args.role,
                              transfer_codec=args.transfer_codec,
                              slo=_slo_tracker(args),
                              sched_policy=args.sched_policy,
                              spill_mb=args.spill_mb,
                              fairness_factor=args.fairness_factor)
    except ValueError as e:
        sys.exit(f"error: {e}")
    # warm the masked (constrained-decoding) program too when requests
    # could carry response_format — i.e. whenever a tokenizer is loaded
    # (grammars compile against the vocab's decoded strings)
    t_warm = time.perf_counter()
    scheduler.start(max_concurrent=max_concurrent, warm_prompt_len=warm_len,
                    warm_constrain=tokenizer is not None)
    startup["warm_s"] = time.perf_counter() - t_warm

    # KV transfer listener (cake_tpu/disagg): a decode replica always
    # accepts imports (ephemeral port unless pinned); a mixed replica
    # only when --transfer-port asked for one (session suspend/resume
    # without a tier split). Its port rides /healthz so the gateway's
    # tier map discovers it.
    xfer_server = None
    if args.role == "decode" or args.transfer_port is not None:
        from cake_tpu.disagg import TransferServer

        xfer_server = TransferServer(scheduler, bind=serve_bind,
                                     port=args.transfer_port or 0).start()
        scheduler.transfer_port = xfer_server.port
        log.info("KV transfer channel on %s:%d (--role %s)", serve_bind,
                 xfer_server.port, args.role)

    def serve_status():
        return {
            "role": "serve",
            "version": __version__,
            "model": str(args.model),
            # the backend that is actually serving, and what each device
            # holds: a server that came up on the CPU, or put a sharded
            # model on one chip, says so here
            "device": device_report(),
            "scheduler": scheduler.stats(),
            "metrics": obs_metrics.registry().snapshot(),
        }

    # graceful drain: SIGTERM/SIGINT — or a gateway-driven
    # POST /v1/fleet/drain (rolling restart) — stop admission, in-flight
    # streams finish or migrate, artifacts flush
    stop = threading.Event()

    server = start_api_server(scheduler, status_fn=serve_status,
                              bind=serve_bind, port=serve_port,
                              model_id=Path(args.model).name or "cake-tpu",
                              on_drain=stop.set)
    registrar = None
    if args.register_with:
        from cake_tpu.serve.register import Registrar

        registrar = Registrar(
            args.register_with, f"{serve_bind}:{server.port}",
            role=args.role,
            transfer_port=xfer_server.port if xfer_server else 0).start()
        log.info("registering with gateway %s as %s:%d",
                 args.register_with, serve_bind, server.port)
    status_httpd = None
    if args.status_port is not None:
        # optional standalone status page (byte-identical surface; the API
        # port already serves / + /metrics)
        from cake_tpu.obs import statusd

        status_httpd, bound = statusd.start_status_server(
            serve_status, bind=args.status_bind, port=args.status_port)
        log.info("status page on http://%s:%d/", args.status_bind, bound)
    startup["loaded_s"] = time.perf_counter() - t0
    obs_prof.set_startup(**startup)
    log.info("model loaded in %.1fs (%s); serving on http://%s:%d/ "
             "(%d slots, queue %d, %ss deadline)",
             startup["loaded_s"], memory_report(), serve_bind,
             server.port, scheduler.max_concurrent, queue_depth,
             request_timeout)

    def _on_signal(signum, frame):
        log.info("signal %d: draining (no new admissions; in-flight "
                 "streams finish)", signum)
        stop.set()

    for signum in (signal.SIGTERM, signal.SIGINT):
        signal.signal(signum, _on_signal)
    try:
        stop.wait()
    finally:
        # deregister BEFORE the drain starts answering 503s: the
        # gateway pins this member DRAINING immediately, so the probe
        # race window (up to one --probe-interval) can't route a
        # request into the exit
        if registrar is not None:
            registrar.deregister()
        server.drain(timeout_s=request_timeout)
        if xfer_server is not None:
            xfer_server.stop()
        if status_httpd is not None:
            status_httpd.shutdown()
            status_httpd.server_close()
        scheduler.close()
        obs.flush_artifacts()
        if scheduler.fault:
            log.error("engine thread died: %s", scheduler.fault)
        else:
            log.info("drained; bye")
    return 1 if scheduler.fault else 0


def _gateway_flags(args) -> list[str]:
    """Names of the --mode gateway flags the user actually set — they
    mean nothing on the single-process modes."""
    out = []
    if args.backends is not None:
        out.append("--backends")
    if args.route_policy != "p2c":
        out.append("--route-policy")
    if args.probe_interval != 2.0:
        out.append("--probe-interval")
    if args.gateway_prefix_block != 64:
        out.append("--gateway-prefix-block")
    if args.lease_ttl != 10.0:
        out.append("--lease-ttl")
    if args.admit_wait != 0.5:
        out.append("--admit-wait")
    if args.admit_queue != 32:
        out.append("--admit-queue")
    return out


def run_gateway(args) -> int:
    """--mode gateway: the multi-replica routing front door
    (cake_tpu/gateway) — health-checked, load-aware routing of the
    serving API across a fleet of --mode serve replicas. The gateway
    holds no model and touches no accelerator: it is pure fleet plumbing
    (probes, policy, proxy), so one host can front many."""
    import signal
    import threading

    from cake_tpu import __version__, obs
    from cake_tpu.gateway.api import parse_backends, start_gateway
    from cake_tpu.gateway.health import HealthMonitor
    from cake_tpu.gateway.policy import make_policy
    from cake_tpu.obs import metrics as obs_metrics

    if args.model:
        sys.exit("error: --model belongs to the serving/generation modes; "
                 "a gateway holds no model — point --backends at --mode "
                 "serve replicas instead")
    if args.topology:
        sys.exit("error: --topology describes a model deployment; the "
                 "gateway's fleet is --backends (each backend may itself "
                 "run a --topology)")
    if args.prompts_file or args.prompt_ids:
        sys.exit("error: --mode gateway takes requests over HTTP "
                 "(POST /v1/completions); --prompts-file/--prompt-ids "
                 "belong to the one-shot paths")
    if args.cluster_report or args.top:
        sys.exit("error: --cluster-report/--top aggregate a master's "
                 "workers; the gateway exposes its fleet view on / and "
                 "/metrics instead")
    flags = _failure_domain_flags(args)
    if flags:
        sys.exit(f"error: {'/'.join(flags)} drive a master's worker "
                 "links; the gateway's failure handling is built in "
                 "(probes, breaker, transparent retry)")
    engine_flags = [f for f, on in (
        ("--max-concurrent", args.max_concurrent is not None),
        ("--queue-depth", args.queue_depth is not None),
        ("--serve-logprobs", bool(args.serve_logprobs)),
        ("--role", args.role != "mixed"),
        ("--transfer-port", args.transfer_port is not None),
        ("--transfer-codec", args.transfer_codec != "none"),
        ("--register-with", args.register_with is not None),
    ) if on]
    if engine_flags:
        sys.exit(f"error: {'/'.join(engine_flags)} configure a serve "
                 "replica's engine; pass them to the --mode serve "
                 "processes behind --backends")
    if args.probe_interval <= 0:
        sys.exit("error: --probe-interval must exceed 0")
    if args.gateway_prefix_block < 1:
        sys.exit("error: --gateway-prefix-block must be >= 1")
    if args.request_timeout is not None and args.request_timeout <= 0:
        sys.exit("error: --request-timeout must exceed 0")
    if args.lease_ttl <= 0:
        sys.exit("error: --lease-ttl must exceed 0")
    if args.admit_wait < 0:
        sys.exit("error: --admit-wait must be >= 0")
    if args.admit_queue < 1:
        sys.exit("error: --admit-queue must be >= 1")

    serve_port = args.serve_port if args.serve_port is not None else 8080
    serve_bind = args.serve_bind or "127.0.0.1"
    request_timeout = (args.request_timeout
                       if args.request_timeout is not None else 300.0)
    try:
        backends = parse_backends(args.backends) if args.backends else []
    except ValueError as e:
        sys.exit(f"error: {e}")
    # an empty --backends is a valid start state: the fleet forms (or
    # RE-forms, after a gateway restart) from replica self-registrations
    monitor = HealthMonitor(backends, probe_interval=args.probe_interval,
                            lease_ttl_s=args.lease_ttl, allow_empty=True)
    policy = make_policy(args.route_policy,
                         prefix_block=args.gateway_prefix_block)
    monitor.start()

    def gateway_status():
        return {
            "role": "gateway",
            "version": __version__,
            "policy": args.route_policy,
            "backends": monitor.describe(),
            "metrics": obs_metrics.registry().snapshot(),
        }

    server = start_gateway(monitor, policy, bind=serve_bind,
                           port=serve_port,
                           prefix_block=args.gateway_prefix_block,
                           read_timeout=request_timeout,
                           status_fn=gateway_status,
                           slo=_slo_tracker(args),
                           admit_wait_s=args.admit_wait,
                           admit_queue=args.admit_queue)
    status_httpd = None
    if args.status_port is not None:
        from cake_tpu.obs import statusd

        status_httpd, bound = statusd.start_status_server(
            gateway_status, bind=args.status_bind, port=args.status_port)
        log.info("status page on http://%s:%d/", args.status_bind, bound)
    up = len(monitor.routable())
    log.info("gateway on http://%s:%d/ — %d backend(s), %d up, "
             "policy %s, probe every %gs",
             serve_bind, server.port, len(backends), up,
             args.route_policy, args.probe_interval)
    if not up:
        log.warning("no backend answered the initial probe; serving 503 "
                    "until one comes up")

    stop = threading.Event()

    def _on_signal(signum, frame):
        log.info("signal %d: draining (no new admissions; in-flight "
                 "proxied streams finish)", signum)
        stop.set()

    for signum in (signal.SIGTERM, signal.SIGINT):
        signal.signal(signum, _on_signal)
    try:
        stop.wait()
    finally:
        server.drain(timeout_s=request_timeout)
        if status_httpd is not None:
            status_httpd.shutdown()
            status_httpd.server_close()
        monitor.stop()
        obs.flush_artifacts()
        log.info("drained; bye")
    return 0


def _build_distributed_gen(args, config, topology, tokenizer, settings):
    """Cross-host master over a host-addressed topology (shared by the
    one-shot master and --mode serve's single-stream engine path): head
    params + per-segment loaders, optional --chaos proxy wiring, runner
    handshakes with the failure-domain knobs."""
    from cake_tpu.runtime.master import DistributedGenerator, build_runners
    from cake_tpu.utils.weights import load_llama_params

    if args.kv_quant:
        sys.exit("error: --kv-quant on the master applies to the local "
                 "and mesh paths; pass it to each worker process "
                 "instead (workers own their layers' caches)")
    head = load_llama_params(
        args.model, config.num_hidden_layers, dtype=config.dtype,
        layer_range=(0, 0), quantize=args.quantize,
    )

    def loader(lo, hi):
        return load_llama_params(
            args.model, config.num_hidden_layers, dtype=config.dtype,
            layer_range=(lo, hi), include_embed=False, include_head=False,
            quantize=args.quantize,
        )["layers"]

    if args.chaos:
        # DEV fault injection: one frame-aware chaos proxy per worker
        # address, each running the same seeded/explicit schedule, and
        # the topology rewired through them — any failure mode is
        # reproducible from the spec (or its seed) alone.
        from cake_tpu.testing import chaos as chaos_mod

        try:
            faults = chaos_mod.parse_spec(args.chaos)
        except ValueError as e:
            sys.exit(f"error: bad --chaos spec: {e}")
        log.warning("chaos enabled: %s — faults WILL be injected on "
                    "every worker link",
                    ", ".join(str(f) for f in faults))
        for node in topology:
            wrapped = []
            for a in (node.hosts or ([node.host] if node.host else [])):
                host, _, port = a.partition(":")
                proxy = chaos_mod.ChaosProxy(
                    host, int(port or 10128), faults).start()
                wrapped.append(proxy.addr)
                log.info("chaos proxy %s -> %s", proxy.addr, a)
            if wrapped:
                node.hosts = wrapped
                node.host = wrapped[0]

    try:
        runners = build_runners(config, topology, loader,
                                max_seq=args.max_seq,
                                wire_codec=args.wire_codec or "none",
                                op_timeout_s=args.op_timeout,
                                connect_retries=args.connect_retries,
                                recover_deadline_s=args.recover_deadline)
    except RuntimeError as e:  # e.g. worker rejects the codec
        sys.exit(f"error: {e}")
    return DistributedGenerator(config, head, runners, tokenizer=tokenizer,
                                settings=settings, max_seq=args.max_seq)


def run_master(args) -> int:
    from cake_tpu.utils.memory import memory_report
    from cake_tpu.utils.weights import load_llama_params

    config = _load_config(args)
    tokenizer = _load_tokenizer(args.model)
    settings = _settings(args)

    t0 = time.perf_counter()
    # One config plane drives both deployments (the reference's contract,
    # topology.rs:41-84): a topology whose nodes carry mesh `device:` indices
    # selects the single-program mesh pipeline (stage count and layer ranges
    # from the YAML via MeshPlan.from_topology); host-addressed nodes select
    # the cross-host master/worker runtime.
    topology = None
    topo_mesh = False
    if args.topology:
        from cake_tpu.parallel.topology import Topology

        topology = Topology.from_path(args.topology)
        with_dev = [n.name for n in topology if n.device is not None]
        without = [n.name for n in topology if n.device is None]
        if with_dev and without:
            sys.exit(
                f"error: topology mixes mesh nodes (device: {with_dev}) "
                f"with host-addressed workers ({without}); a deployment is "
                "one or the other"
            )
        topo_mesh = bool(with_dev)
    use_mesh = (args.stages > 1 or args.tp > 1 or args.sp > 1
                or args.ep > 1 or topo_mesh)
    if args.speculate and (args.sp > 1 or args.topology):
        sys.exit("error: --speculate runs the local or mesh (stages/tp) "
                 "paths; it is not supported with --sp or --topology (it "
                 "would otherwise be silently ignored)")
    if args.speculate and args.decode_block is not None:
        sys.exit("error: --decode-block does not compose with --speculate "
                 "(speculative rounds replace fused-block dispatches; the "
                 "flag would otherwise be silently ignored)")
    if args.wire_codec not in (None, "none") and (
        use_mesh or not args.topology
    ):
        # explicit "none" is the default spelled out — harmless anywhere
        sys.exit("error: --wire-codec applies to cross-host worker hops; "
                 "it needs a host-addressed --topology (it would otherwise "
                 "be silently ignored)")
    if (args.cluster_report or args.top) and (use_mesh or not args.topology):
        sys.exit("error: --cluster-report/--top aggregate across cross-host "
                 "workers; they need a host-addressed --topology (they "
                 "would otherwise be silently ignored)")
    _fd_flags = _failure_domain_flags(args)
    if _fd_flags and (use_mesh or not args.topology):
        sys.exit(f"error: {'/'.join(_fd_flags)} drive cross-host worker "
                 "links; they need a host-addressed --topology (they "
                 "would otherwise be silently ignored)")
    if args.straggler_factor <= 1.0:
        sys.exit("error: --straggler-factor must exceed 1.0 (a worker at "
                 "the median is not a straggler)")
    if args.op_timeout is not None and args.op_timeout <= 0:
        sys.exit("error: --op-timeout must exceed 0 (omit the flag for the "
                 "segment-scaled default; there is no 'no deadline' mode — "
                 "that is the hung-peer hole this flag closes)")
    if args.recover_deadline is not None and args.recover_deadline <= 0:
        sys.exit("error: --recover-deadline must exceed 0")
    if args.lookahead:
        # lookahead needs the fused-block programs (all-local path here,
        # BatchGenerator on the serving path); reject combinations that
        # would silently ignore it
        if args.speculate:
            sys.exit("error: --lookahead does not compose with --speculate "
                     "(the spec plane needs the host between dispatches)")
        if use_mesh or args.topology:
            sys.exit("error: --lookahead runs the all-local fused-block "
                     "path (or --prompts-file serving); it is not "
                     "supported with --stages/--tp/--sp or --topology")
        if args.decode_block == 1:
            sys.exit("error: --lookahead needs fused blocks to pipeline; "
                     "it requires --decode-block > 1 (it would otherwise "
                     "be silently ignored)")
    decode_block = args.decode_block if args.decode_block is not None else 8
    if args.prefill_chunks > 1:
        # Overlap needs stages to overlap across, and the sp plane owns
        # long-context prefill — reject combinations that would silently do
        # nothing (stages=1) or die in a traceback (sp>1). A device-indexed
        # topology resolves its stage count later; MeshGenerator/the
        # builders re-validate and the error is surfaced below.
        if args.sp > 1:
            sys.exit("error: --prefill-chunks requires --sp 1 (ring "
                     "attention is the sequence-parallel prefill plane)")
        if not (args.stages > 1 or topo_mesh):
            sys.exit(
                "error: --prefill-chunks pipelines the prompt across mesh "
                "stages; it requires --stages > 1 (or a device-indexed "
                "topology), otherwise it would be silently ignored"
            )
    if topo_mesh and args.stages > 1:
        sys.exit(
            "error: --stages conflicts with a device-indexed topology "
            "(the stage count comes from the topology's device entries)"
        )
    if use_mesh and topology is not None and not topo_mesh:
        sys.exit(
            "error: --stages/--tp/--sp (single-program mesh) and a "
            "host-addressed --topology (cross-host workers) are mutually "
            "exclusive; give topology nodes `device:` indices to drive the "
            "mesh from YAML"
        )
    if use_mesh:
        from cake_tpu.runtime.mesh_generator import MeshGenerator

        from cake_tpu.parallel.mesh import MeshPlan

        try:
            if topo_mesh:
                plan = MeshPlan.from_topology(config, topology, tp=args.tp,
                                              sp=args.sp, ep=args.ep)
                log.info("mesh plan from topology: %d stages x tp=%d x sp=%d"
                         " x ep=%d",
                         plan.num_stages, plan.tp, plan.sp, plan.ep)
            else:
                plan = MeshPlan.build(config, num_stages=args.stages,
                                      tp=args.tp, dp=1, sp=args.sp,
                                      ep=args.ep)
        except ValueError as e:
            sys.exit(f"error: {e}")
        params = _mesh_params(args, config, plan)
        try:
            if args.speculate:
                from cake_tpu.runtime.speculative import (
                    MeshSpeculativeGenerator,
                )

                gen = MeshSpeculativeGenerator(
                    config, params, plan=plan, tokenizer=tokenizer,
                    settings=settings, max_seq=args.max_seq,
                    kv_quant=args.kv_quant, spec_k=args.speculate,
                    prefill_chunks=args.prefill_chunks)
            else:
                gen = MeshGenerator(config, params, plan=plan,
                                    tokenizer=tokenizer, settings=settings,
                                    max_seq=args.max_seq,
                                    block_size=decode_block,
                                    prefill_chunks=args.prefill_chunks,
                                    kv_quant=args.kv_quant)
        except ValueError as e:
            sys.exit(f"error: {e}")
    elif args.topology:
        gen = _build_distributed_gen(args, config, topology, tokenizer,
                                     settings)
    else:
        params = load_llama_params(args.model, config.num_hidden_layers,
                                   dtype=config.dtype, quantize=args.quantize)
        if args.speculate:
            from cake_tpu.runtime.speculative import SpeculativeGenerator

            try:
                gen = SpeculativeGenerator(
                    config, params, tokenizer=tokenizer, settings=settings,
                    max_seq=args.max_seq, kv_quant=args.kv_quant,
                    spec_k=args.speculate)
            except ValueError as e:
                sys.exit(f"error: {e}")
        else:
            from cake_tpu.runtime.generator import LlamaGenerator

            gen = LlamaGenerator(config, params, tokenizer=tokenizer,
                                 settings=settings, max_seq=args.max_seq,
                                 block_size=decode_block,
                                 kv_quant=args.kv_quant,
                                 lookahead=args.lookahead)
    log.info("model loaded in %.1fs (%s)", time.perf_counter() - t0,
             memory_report())

    # Master-side status surface (satellite of the worker's): same handler
    # shape, but this registry also carries the merged cluster.* series
    # once the scraper has run — one Prometheus scrape sees the cluster.
    status_httpd = None
    if args.status_port is not None:
        from cake_tpu import __version__
        from cake_tpu.obs import metrics as obs_metrics
        from cake_tpu.obs import statusd

        def master_status():
            st = {
                "role": "master",
                "version": __version__,
                "model": str(args.model),
                "metrics": obs_metrics.registry().snapshot(),
            }
            if hasattr(gen, "runner_stats"):
                st["segments"] = gen.runner_stats()
            return st

        status_httpd, bound = statusd.start_status_server(
            master_status, bind=args.status_bind, port=args.status_port)
        log.info("master status page on http://%s:%d/", args.status_bind,
                 bound)

    top_view = None
    if args.top:
        from cake_tpu.obs.top import Top

        top_view = Top(gen.cluster_scraper(args.straggler_factor))
        top_view.start()

    if args.prompt_ids:
        gen.set_prompt([int(t) for t in args.prompt_ids.split(",")])
    else:
        if tokenizer is None:
            sys.exit(
                "error: no tokenizer.json in the model dir; pass --prompt-ids"
            )
        gen.set_prompt(args.prompt)
        print(args.prompt, end="", flush=True)
    t_gen0 = time.perf_counter()
    n_tokens = 0
    gen_error = None
    gen_ids: list[int] = []
    if args.profile:
        from cake_tpu.obs import prof as obs_prof

        # the one place that opens a profiler; this run's own ``finally``
        # closes it, however long generation takes
        obs_prof.capture_start(auto_stop=False)
    try:
        for i in range(args.sample_len):
            try:
                tok = gen.next_token(i)
            except Exception as e:
                # end the run with a clean newline instead of a traceback
                # (reference: cake-cli/main.rs:51-55)
                gen_error = e
                break
            n_tokens += 1
            gen_ids.append(tok.id)
            if tok.text:
                print(tok.text, end="", flush=True)
            if i == 0:
                t_warm = time.perf_counter()  # exclude warm-up (master.rs:37-40)
            if tok.is_end_of_stream:
                break
    finally:
        if args.profile:
            obs_prof.capture_stop()
            log.info("profiler trace written to %s", args.profile)
        if top_view is not None:
            top_view.stop()
    rest = gen.last()
    if rest:
        print(rest, end="")
    if tokenizer is None and gen_ids:
        # id-only runs (no tokenizer.json) still stream SOMETHING observable
        print(",".join(map(str, gen_ids)), end="")
    print()
    if n_tokens > 1:
        dt = time.perf_counter() - t_warm
        log.info("%d tokens, %.2f tok/s (excl. warm-up; TTFT %.2fs) — %s",
                 n_tokens, (n_tokens - 1) / dt,
                 t_warm - t_gen0, memory_report())
    if hasattr(gen, "runner_stats"):
        for s in gen.runner_stats():
            # link fields are each optional: a legacy peer has only the
            # handshake RTT fallback (no clock offset), a local segment
            # neither
            extra = "".join(
                f", {label} {s[key]} ms"
                for key, label in (("handshake_ms", "handshake"),
                                   ("rtt_ms", "rtt"),
                                   ("clock_offset_ms", "clock offset"))
                if key in s
            )
            log.info("segment %s @ %s: %d calls, %.2f ms avg "
                     "(p50 %.2f / p99 %.2f)%s",
                     s["layers"], s["ident"], s["calls"], s["avg_ms"],
                     s.get("p50_ms", 0.0), s.get("p99_ms", 0.0), extra)
    if args.cluster_report:
        # one final scrape while the worker connections are still open
        # (the STATS path rides them); written before close() by design
        import json as _json

        try:
            report = gen.cluster_report(args.straggler_factor)
            with open(args.cluster_report, "w") as f:
                _json.dump(report, f, indent=1)
                f.write("\n")
            log.info("cluster report written to %s", args.cluster_report)
            for name in report.get("stragglers", []):
                log.warning("straggler worker: %s", name)
        except OSError as e:
            log.error("could not write cluster report to %s: %s",
                      args.cluster_report, e)
    if status_httpd is not None:
        status_httpd.shutdown()
        status_httpd.server_close()
    if hasattr(gen, "close"):
        gen.close()
    if gen_error is not None:
        log.error("generation ended early: %s", gen_error)
        return 1
    return 0


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from cake_tpu import obs
    from cake_tpu.utils.compile_cache import configure

    configure()

    if args.mode != "gateway" and not args.model:
        sys.exit("error: --model is required (only --mode gateway runs "
                 "without a checkpoint)")
    if args.mode == "gateway" and args.fetch:
        sys.exit("error: --fetch populates --model, and a gateway holds "
                 "no model; fetch on the --mode serve replicas instead")
    obs.setup_logging("debug" if args.verbose else args.log_level)
    if args.trace:
        # a capture (--profile on the master path, POST /debug/trace on a
        # server) passes the spans through as TraceAnnotations while it is
        # open, which lines the two timelines up in one Perfetto view
        obs.tracer().start()
    if args.prof_sample is not None:
        obs.prof.profiler().set_sample(args.prof_sample)
    # where captures go; without --profile, a fresh temporary directory each
    obs.prof.capture().directory = args.profile
    if args.flight_log:
        try:
            obs.flight.recorder().enable(path=args.flight_log)
        except OSError as e:
            # fail before loading the model, not after a full run
            sys.exit(f"error: cannot open --flight-log {args.flight_log}: {e}")
    if args.flight_log or args.metrics_out:
        # durability: a SIGTERM/SIGINT'd run still lands the flight-log
        # tail and a metrics snapshot (the clean-exit writes below only
        # cover runs that reach them)
        obs.install_flush_handlers(metrics_out=args.metrics_out)
    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    if args.process_id is not None and not (args.coordinator
                                            or args.num_processes):
        sys.exit("error: --process-id requires --coordinator and/or "
                 "--num-processes (it would otherwise be silently ignored)")
    if (args.coordinator or args.num_processes
            or int(os.environ.get("CAKE_NUM_PROCESSES", "1")) > 1):
        from cake_tpu.parallel.distributed import initialize

        initialize(coordinator=args.coordinator,
                   num_processes=args.num_processes,
                   process_id=args.process_id)
    if args.device is not None:
        import jax

        devices = jax.devices()
        if not 0 <= args.device < len(devices):
            sys.exit(
                f"error: --device {args.device} out of range "
                f"(have {len(devices)} devices)"
            )
        jax.config.update("jax_default_device", devices[args.device])
    if args.fetch:
        from cake_tpu.utils.fetch import fetch_checkpoint

        try:
            fetch_checkpoint(args.fetch, args.model, force=args.refetch)
        except Exception as e:
            sys.exit(f"error: fetch from {args.fetch} failed: {e}")
    if args.kv_layout != "paged" and (args.kv_page_size is not None
                                      or args.kv_pool_pages is not None):
        sys.exit("error: --kv-page-size/--kv-pool-pages configure the "
                 "paged KV pool; they require --kv-layout paged")
    if args.kv_layout == "paged" and (
            args.mode in ("worker", "gateway")
            or (args.mode == "master" and not args.prompts_file)):
        sys.exit("error: --kv-layout paged applies to the batched serving "
                 "engine; it requires --mode serve or a --prompts-file "
                 "batch run (it would otherwise be silently ignored)")
    if args.mode not in ("serve", "gateway") and _serve_flags(args):
        sys.exit(f"error: {'/'.join(_serve_flags(args))} configure the "
                 "HTTP serving plane; they require --mode serve or "
                 "--mode gateway (they would otherwise be silently "
                 "ignored)")
    if args.mode != "gateway" and _gateway_flags(args):
        sys.exit(f"error: {'/'.join(_gateway_flags(args))} configure the "
                 "routing gateway; they require --mode gateway (they "
                 "would otherwise be silently ignored)")
    try:
        if args.mode == "worker":
            return run_worker(args)
        if args.mode == "serve":
            return run_http_serve(args)
        if args.mode == "gateway":
            return run_gateway(args)
        if args.prompts_file:
            return run_serve(args)
        return run_master(args)
    finally:
        # observability outputs land even on an early error/KeyboardInterrupt
        # — and a failing artifact write must never mask the run's own
        # outcome or the other artifacts
        if args.trace:
            obs.tracer().stop()
            try:
                obs.tracer().write_chrome_trace(args.trace)
                log.info("chrome trace written to %s", args.trace)
                if obs.tracer().dropped:
                    log.warning(
                        "trace buffer filled: %d span(s) dropped — the "
                        "timeline in %s is truncated",
                        obs.tracer().dropped, args.trace,
                    )
            except OSError as e:
                log.error("could not write trace to %s: %s", args.trace, e)
        if args.metrics_out:
            try:
                obs.registry().dump_json(args.metrics_out)
                log.info("metrics snapshot written to %s", args.metrics_out)
            except OSError as e:
                log.error("could not write metrics to %s: %s",
                          args.metrics_out, e)
        if args.flight_log:
            obs.flight.recorder().close()


if __name__ == "__main__":
    sys.exit(main())
