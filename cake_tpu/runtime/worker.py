"""Worker: serves its topology-assigned decoder layers over the wire.

Equivalent of `cake-core/src/cake/worker.rs`: look up own node by name
(worker.rs:73-83), load ONLY the assigned layers' weights (worker.rs:85-98),
accept master connections, give each connection a fresh KV cache
(worker.rs:52-61), and loop decoding SingleOp/Batch requests into forward
passes with a Tensor reply (worker.rs:180-224), logging throughput every
5 ops (worker.rs:19,244-254).

TPU-native differences:

- Layers are loaded as *stacked contiguous runs* and executed as one jitted
  `lax.scan` per run (no per-layer dispatch; the reference loops blocks
  sequentially per op, worker.rs:208-219).
- Request ops are grouped into those runs server-side, so a Batch covering a
  whole segment costs one XLA dispatch.
- Errors are reported to the master as Error messages instead of dropping
  the connection.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import struct
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.models.config import LlamaConfig
from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.obs.trace import span, tracer
from cake_tpu.ops.kvcache import KVCache, init_cache
from cake_tpu.parallel.topology import Topology
from cake_tpu.runtime import protocol, wire
from cake_tpu.runtime.protocol import MsgType, WorkerInfo

log = logging.getLogger("cake_tpu.worker")

STATS_EVERY = 5  # ops between throughput log lines (worker.rs:19)


def _contiguous_runs(indices: list[int]) -> list[tuple[int, int]]:
    """[0,1,2,7,8] -> [(0,3),(7,9)]."""
    runs: list[tuple[int, int]] = []
    for i in sorted(indices):
        if runs and runs[-1][1] == i:
            runs[-1] = (runs[-1][0], i + 1)
        else:
            runs.append((i, i + 1))
    return runs


class Worker:
    """Layer server. ``params_by_run`` maps (start, stop) -> stacked layer
    weights for that run (loaded via utils.weights.load_llama_params with
    layer_range, or sliced from a full params pytree)."""

    def __init__(
        self,
        name: str,
        config: LlamaConfig,
        topology: Topology,
        params_loader,  # callable (start, stop) -> stacked layers pytree
        address: str = "0.0.0.0:10128",
        max_seq: int | None = None,
        kv_quant: str | None = None,
        wire_codec: str | None = None,
    ):
        if name not in topology:
            raise ValueError(f"worker '{name}' not present in topology")
        protocol.check_stream_width(config)
        self.name = name
        self.config = config
        self.node = topology[name]
        self.max_seq = max_seq or config.max_seq_len
        # int8 per-connection KV caches: halves this worker's cache HBM
        # (each connection gets fresh quantized buffers, same isolation)
        self.kv_quant = kv_quant
        # Activation wire codecs advertised in the handshake. By default
        # every codec is on offer and the master picks per connection
        # (--wire-codec); setting one here restricts the offer to
        # {none, that codec} — the operator's lever to forbid lossy
        # compression on a worker regardless of master flags.
        if wire_codec is None:
            self.codecs = list(protocol.CODECS)
        else:
            protocol.check_codec(wire_codec)
            self.codecs = (["none"] if wire_codec == "none"
                           else ["none", wire_codec])
        indices = self.node.layer_indices()
        if not indices:
            raise ValueError(f"worker '{name}' has no layers assigned")
        self.runs = _contiguous_runs(indices)
        log.info("worker %s loading layers %s", name, self.runs)
        # Only the stacked weights are held long-term; KV caches are allocated
        # fresh per connection (worker.rs:52-61) — nothing idle pins HBM.
        self._layers = {
            (lo, hi): params_loader(lo, hi) for lo, hi in self.runs
        }
        from functools import partial

        from cake_tpu.models import llama

        self._fn = jax.jit(partial(llama.hidden_forward_layers, config=config))
        addr, port = address.rsplit(":", 1)
        self.listener = wire.Listener(addr, int(port))
        self.port = self.listener.port
        self._bind_host = addr
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        # live counters behind the status surface (the reference's worker
        # app renders this state in a SwiftUI view, ContentView.swift:28-56;
        # on a headless TPU VM the equivalent is an HTTP JSON endpoint)
        self._stat_lock = threading.Lock()
        self._conns_live = 0
        self._conns_total = 0
        self._started = time.time()
        self._status_httpd = None
        self._status_port = 0  # bound status-page port, advertised in _info()
        # Serving counters as per-instance obs instruments (the
        # Registry.publish pattern) — the single source of truth for both
        # status() and the registry dumps.
        self._ops_ctr = obs_metrics.Counter("worker.ops")
        self._bytes_in_ctr = obs_metrics.Counter("worker.bytes_in")
        self._bytes_out_ctr = obs_metrics.Counter("worker.bytes_out")
        # steady-state forward times only; each connection's first op
        # (prefill + possible XLA compile) lands in the warmup gauge — the
        # master's warmup/steady split, worker-side, so the cluster
        # straggler check compares decode behavior, not compile luck
        self._fwd_hist = obs_metrics.Histogram("worker.forward_ms")
        self._warm_gauge = obs_metrics.Gauge("worker.warmup_ms")
        self._prefill_hist = obs_metrics.Histogram("worker.prefill_ms")
        # Shapes whose XLA compile this PROCESS has already paid. Warmup
        # detection must share the compile cache's scope (jit caches per
        # process, not per connection): after a master reconnect the first
        # op of a shape on the NEW connection is a fast steady-state call
        # and belongs in the histogram, not the warmup gauge.
        self._warmed_shapes: set = set()
        obs_metrics.registry().publish(
            self._ops_ctr, self._bytes_in_ctr, self._bytes_out_ctr,
            self._fwd_hist, self._warm_gauge, self._prefill_hist)

    # -- serving ------------------------------------------------------------
    def serve_forever(self) -> None:
        log.info("worker %s listening on port %d", self.name, self.port)
        while not self._stop.is_set():
            try:
                conn = self.listener.accept()
            except Exception:
                if self._stop.is_set():
                    return
                raise
            if self._stop.is_set():  # woken by shutdown's dummy connect
                conn.close()
                return
            th = threading.Thread(
                target=self._handle_connection, args=(conn,), daemon=True
            )
            th.start()
            self._threads = [t for t in self._threads if t.is_alive()]
            self._threads.append(th)

    def serve_in_background(self) -> threading.Thread:
        th = threading.Thread(target=self.serve_forever, daemon=True)
        th.start()
        return th

    # -- status surface ------------------------------------------------------
    def status(self, include_metrics: bool = True) -> dict:
        """Live worker state as a plain dict: identity (the WorkerInfo
        handshake fields), assigned layer runs, and serving counters.
        ``include_metrics=False`` skips the full registry snapshot — the
        in-band STATS reply wants the cheap top-level fields only."""
        from cake_tpu.utils.memory import rss_bytes

        info = self._info()
        with self._stat_lock:
            st = {
                "name": info.name,
                "version": info.version,
                "os": info.os,
                "arch": info.arch,
                "device": info.device,
                "device_idx": info.device_idx,
                "dtype": info.dtype,
                "kv_quant": self.kv_quant,
                "wire_codecs": list(self.codecs),
                "wire_caps": info.caps,
                "max_seq": self.max_seq,
                "port": self.port,
                "layer_runs": [list(r) for r in self.runs],
                "uptime_s": round(time.time() - self._started, 1),
                "connections_live": self._conns_live,
                "connections_total": self._conns_total,
                "ops_total": self._ops_ctr.value,
                "bytes_in": self._bytes_in_ctr.value,
                "bytes_out": self._bytes_out_ctr.value,
                # THIS worker's segment forward-time distribution, from the
                # instance-owned histogram (the registry series of the same
                # name is last-publisher-wins when several Workers share a
                # process; the cluster scraper's per-worker p50/p99 must
                # not be)
                "forward_ms": self._fwd_hist.snapshot(),
                "prefill_ms": self._prefill_hist.snapshot(),
                "warmup_ms": self._warm_gauge.value,
                "rss_bytes": rss_bytes(),
            }
            if include_metrics:
                # full registry snapshot: wire frame/byte/CRC counters and
                # layer forward-time histograms with p50/p99, one page
                st["metrics"] = obs_metrics.registry().snapshot()
            return st

    def start_status_server(self, port: int = 0,
                            bind: str | None = None) -> int:
        """Serve ``status()`` as JSON over HTTP on ``port`` (0 = ephemeral;
        returns the bound port). The headless-deployment equivalent of the
        reference's worker GUI (`cake-ios-worker-app/Cake
        Worker/ContentView.swift:28-56` renders name/device/layers/state;
        here ``curl :port/`` or a browser does). ``bind`` defaults to
        loopback (CLI ``--status-bind``): the page leaks identity, layer
        assignments, and traffic counters, so exposure beyond the host is
        an explicit choice, independent of the serving ``--address``.
        Daemon-threaded; stopped by :meth:`shutdown`."""
        from cake_tpu.obs import statusd

        bind = bind if bind is not None else "127.0.0.1"
        self._status_httpd, bound = statusd.start_status_server(
            self.status, bind=bind, port=port)
        self._status_port = bound
        log.info("worker %s status page on http://%s:%d/", self.name,
                 bind, bound)
        return bound

    def shutdown(self) -> None:
        self._stop.set()
        if self._status_httpd is not None:
            self._status_httpd.shutdown()
            self._status_httpd.server_close()
            self._status_httpd = None
            self._status_port = 0
        # A blocked accept() does not return when the fd is closed from
        # another thread on Linux; wake it with a throwaway connection.
        try:
            wire.connect("127.0.0.1", self.port, timeout_ms=1000).close()
        except Exception:
            pass
        self.listener.close()

    # -- per-connection loop ------------------------------------------------
    def _info(self) -> WorkerInfo:
        dev = jax.devices()[0]
        return WorkerInfo(
            name=self.name,
            device=getattr(dev, "device_kind", str(dev)),
            device_idx=getattr(dev, "id", 0),
            dtype=self.config.dtype,
            max_seq=self.max_seq,
            codecs=list(self.codecs),
            caps=list(protocol.ALL_CAPS),
            status_port=self._status_port,
            layers=[
                f"model.layers.{i}"
                for lo, hi in self.runs
                for i in range(lo, hi)
            ],
        )

    def _handle_connection(self, conn: wire.Connection) -> None:
        """One master connection: Hello -> WorkerInfo, then op loop with a
        per-connection fresh cache (worker.rs:149-258)."""
        # fresh per-connection caches: isolation over synchronization.
        # Allocated lazily on the first op — a PING/STATS-only connection
        # (the cluster scraper, a health probe) must not pin cache HBM.
        caches: dict[tuple[int, int], KVCache] | None = None
        ops_done = 0
        t_window = time.perf_counter()
        bytes_in = bytes_out = 0
        with self._stat_lock:
            self._conns_live += 1
            self._conns_total += 1
        try:
            # timeout=None is a decision, not a default (cakelint CK-WIRE):
            # the accepted side legitimately waits forever for the master's
            # next request; TCP keepalive bounds the dead-peer case.
            t, _ = conn.recv(timeout=None)
            if t != MsgType.HELLO:
                conn.send(MsgType.ERROR, protocol.encode_error("expected HELLO"))
                return
            conn.send(MsgType.WORKER_INFO, self._info().to_bytes())
            while not self._stop.is_set():
                try:
                    t, payload = conn.recv(timeout=None)
                except wire.PeerClosed:
                    return
                if t == MsgType.GOODBYE:
                    return
                if t == MsgType.PING:
                    # clock probe (CAP_PING): echo the master's opaque
                    # timestamp back with this process's perf_counter so
                    # the master can estimate the inter-clock offset
                    conn.send(MsgType.PING, [
                        memoryview(payload),
                        struct.pack("<d", time.perf_counter()),
                    ])
                    continue
                if t == MsgType.STATS:
                    # status snapshot over the op connection (CAP_STATS) —
                    # the scrape path for workers that never opened a
                    # --status-port. The full registry snapshot stays on
                    # the HTTP page: the scraper reads only the top-level
                    # fields, and this reply is serialized against live
                    # forwards by the master's connection lock, so every
                    # byte here is decode stall.
                    conn.send(MsgType.STATS, json.dumps(
                        self.status(include_metrics=False)).encode())
                    continue
                if t not in (MsgType.SINGLE_OP, MsgType.BATCH):
                    conn.send(
                        MsgType.ERROR,
                        protocol.encode_error(f"unexpected message type {t}"),
                    )
                    continue
                bytes_in += len(payload)
                t_handle0 = time.perf_counter()
                try:
                    x, ops, codec, trailer = protocol.decode_ops_traced(
                        payload)
                    t_dec1 = time.perf_counter()
                    if codec not in self.codecs:
                        # enforce the advertised restriction server-side: a
                        # client that skipped the handshake check must not
                        # smuggle lossy compression onto a worker whose
                        # operator forbade it
                        raise ValueError(
                            f"wire codec '{codec}' not accepted by this "
                            f"worker (offers {self.codecs})"
                        )
                    if caches is None:
                        caches = {
                            (lo, hi): init_cache(
                                self.config, batch=1, max_seq=self.max_seq,
                                num_layers=hi - lo, quant=self.kv_quant,
                            )
                            for lo, hi in self.runs
                        }
                    t0 = time.perf_counter()
                    with span("worker.forward", ops=len(ops)):
                        out = self._run_ops(x, ops, caches)
                    t_fwd1 = time.perf_counter()
                    # XLA compiles per activation shape; the process-wide
                    # first op of each shape (prefill [1,T,H], then the
                    # first [1,1,H] decode) pays it. Those land in the
                    # warmup gauge so the histogram — and the cluster
                    # straggler check built on its p99 — holds steady-state
                    # decode behavior only, mirroring the master's
                    # warmup/steady split.
                    shape = tuple(np.shape(x))
                    with self._stat_lock:
                        warmed = shape in self._warmed_shapes
                        self._warmed_shapes.add(shape)
                    fwd_ms = (t_fwd1 - t0) * 1e3
                    if not warmed:
                        self._warm_gauge.set(fwd_ms)
                    elif len(shape) >= 2 and shape[1] > 1:
                        # warmed multi-token forward: a fresh prompt's
                        # prefill or the master's recovery replay. Real
                        # work, but ~100x a decode step — it mirrors the
                        # master's _timing_paused/_seg_warm exclusions
                        # into its own series so forward_ms (and the
                        # straggler p99 built on it) stays decode-only.
                        self._prefill_hist.observe(fwd_ms)
                    else:
                        self._fwd_hist.observe(fwd_ms)
                except Exception as e:  # report, keep serving
                    log.exception("op failed")
                    conn.send(MsgType.ERROR, protocol.encode_error(str(e)))
                    continue
                # the reply mirrors the request's codec (master chose it at
                # handshake against this worker's advertised set)
                reply = protocol.encode_activation_parts(out, codec)
                t_enc1 = time.perf_counter()
                tc = (trailer or {}).get("tc")
                if tc is not None:
                    # the request carried a Dapper-style trace context: ship
                    # back a compact span digest (this clock's timebase; the
                    # master rebases via its ClockSync) and mirror the same
                    # spans into this process's own tracer when it is on.
                    # No context -> byte-identical legacy reply.
                    digest_spans = [
                        ["ops.handle", t_handle0, t_enc1 - t_handle0],
                        ["ops.decode", t_handle0, t_dec1 - t_handle0],
                        ["ops.forward", t0, t_fwd1 - t0],
                        ["ops.encode", t_fwd1, t_enc1 - t_fwd1],
                    ]
                    reply.append(json.dumps({"digest": {
                        "name": self.name,
                        "seq": tc.get("seq"),
                        "spans": [[n, round(ts, 7), round(d, 7)]
                                  for n, ts, d in digest_spans],
                    }}).encode())
                    tr = tracer()
                    if tr.enabled:
                        args = {"trace_id": tc.get("tid"),
                                "parent_span_id": tc.get("psid"),
                                "seq": tc.get("seq")}
                        for n, ts, d in digest_spans:
                            tr.record(n, ts, d, args)
                reply_len = sum(len(p) for p in reply)
                bytes_out += reply_len
                # counted before the reply leaves: a master that reads the
                # status right after its answer finds this exchange in it
                self._ops_ctr.inc(len(ops))
                self._bytes_in_ctr.inc(len(payload))
                self._bytes_out_ctr.inc(reply_len)
                conn.send(MsgType.TENSOR, reply)
                ops_done += len(ops)
                if ops_done >= STATS_EVERY:
                    dt = time.perf_counter() - t_window
                    log.info(
                        "%s: %.1f ops/s, read %.1f MB/s, write %.1f MB/s",
                        self.name, ops_done / dt,
                        bytes_in / dt / 1e6, bytes_out / dt / 1e6,
                    )
                    t_window = time.perf_counter()
                    ops_done = 0
                    bytes_in = bytes_out = 0
        # A handler thread must never die silently: per-op failures are
        # answered with ERROR replies above, so anything arriving here is
        # connection-level (a master that vanished mid-reply, a poisoned
        # frame stream) or a genuine bug — log it and fall through to the
        # cleanup either way.
        except wire.PeerClosed:
            # abrupt close without GOODBYE (health probe, killed master):
            # routine from the server's side, not worth a warning
            log.debug("%s: peer closed without GOODBYE", self.name)
        except (wire.WireError, OSError) as e:
            log.warning("%s: connection lost (%s); dropping it", self.name, e)
        except Exception:
            log.exception("%s: connection handler crashed; dropping the "
                          "connection", self.name)
        finally:
            with self._stat_lock:
                self._conns_live -= 1
            # Drop this connection's KV caches NOW: the exception paths
            # above can keep the handler frame alive in traceback refs,
            # and HBM-backed cache buffers must not stay pinned until GC
            # gets around to them (a crash-looping client would otherwise
            # accumulate dead caches).
            if caches:
                caches.clear()
            conn.close()

    def _run_ops(
        self,
        x: np.ndarray,
        ops: list[tuple[str, int]],
        caches: dict[tuple[int, int], KVCache],
    ) -> np.ndarray:
        """Execute the requested layer ops in order, grouping into stored
        contiguous runs (one jitted scan per group)."""
        indices: list[tuple[int, int]] = []
        for name, pos in ops:
            if not name.startswith("model.layers."):
                raise ValueError(f"unknown layer name '{name}'")
            indices.append((int(name.rsplit(".", 1)[1]), int(pos)))

        h = jnp.asarray(x, self.config.jax_dtype)
        i = 0
        while i < len(indices):
            layer_idx, pos = indices[i]
            run = next(
                (r for r in self.runs if r[0] <= layer_idx < r[1]), None
            )
            if run is None:
                raise ValueError(
                    f"layer {layer_idx} not served by worker '{self.name}'"
                )
            # extend over consecutive ops staying in this run at same pos
            j = i
            while (
                j + 1 < len(indices)
                and indices[j + 1][0] == indices[j][0] + 1
                and indices[j + 1][0] < run[1]
                and indices[j + 1][1] == pos
            ):
                j += 1
            lo, hi = indices[i][0], indices[j][0] + 1
            run_layers = self._layers[run]
            cache = caches[run]
            if (lo, hi) == run:
                # fast path: the whole stored run in one jitted scan
                h, caches[run] = self._fn(
                    run_layers, h, cache, jnp.int32(pos)
                )
            else:
                # partial-run request: slice weights + cache, write back
                layers = jax.tree.map(
                    lambda a: a[lo - run[0] : hi - run[0]], run_layers
                )
                sub = KVCache(
                    k=cache.k[lo - run[0] : hi - run[0]],
                    v=cache.v[lo - run[0] : hi - run[0]],
                )
                h, sub = self._fn(layers, h, sub, jnp.int32(pos))
                caches[run] = KVCache(
                    k=cache.k.at[lo - run[0] : hi - run[0]].set(sub.k),
                    v=cache.v.at[lo - run[0] : hi - run[0]].set(sub.v),
                )
            i = j + 1
        return np.asarray(h)
