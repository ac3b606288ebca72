"""ici_probe: per-hop inter-stage latency/bandwidth over the mesh ring.

BASELINE.json names "inter-layer ICI latency" as a metric of record; the
reference's analogue is its per-connection handshake RTT + per-op TCP
timing (`client.rs:76-84`, `worker.rs:226-254`) — here the inter-stage
link is the compiler-scheduled `lax.ppermute` the pipeline rides
(`parallel/pipeline.py`), so the probe times exactly that collective over
the same ``stage`` ring the decoder uses.

Method: one jitted shard_map program scans R back-to-back ppermutes of a
[payload] activation-shaped buffer (scan amortizes dispatch, the data
dependency serializes hops), timed over the mesh's ``stage`` axis. Per
hop: ``dt / R``; bandwidth: ``payload_bytes / hop``. Run on a real pod
slice for ICI numbers; on the CPU test mesh it proves the machinery (the
numbers are host-memcpy, labeled as such).

Usage:  python -m cake_tpu.tools.ici_probe [--stages N] [--reps R]
            [--json-out PATH]
Prints one JSON line per payload size:
  {"payload_bytes", "hops", "per_hop_us", "gbps", "device", "n_stages"}
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from cake_tpu.parallel.mesh import STAGE, make_mesh


def _build_ring(mesh, n: int, reps: int):
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(x):
        def step(c, _):
            return jax.lax.ppermute(c, STAGE, perm), None

        out, _ = jax.lax.scan(step, x, None, length=reps)
        return out

    return jax.jit(shard_map(
        body, mesh=mesh, in_specs=P(STAGE), out_specs=P(STAGE),
        check_vma=False,
    ))


def probe(stages: int | None = None, reps: int = 64,
          json_out: str | None = None) -> list:
    devices = jax.devices()
    n = stages or len(devices)
    if n < 2:
        sys.stderr.write(
            "ici_probe needs >= 2 devices to form a ring (a single chip "
            "has no inter-stage link to measure)\n"
        )
        return []
    mesh = make_mesh(num_stages=n, devices=devices[:n])
    dev = devices[0]
    results = []
    for payload in (1 << 12, 1 << 16, 1 << 20, 1 << 24):
        elems = payload // 2  # bf16 activation-shaped payload
        per_shard = max(1, elems // n)
        x = jnp.zeros((per_shard * n,), jnp.bfloat16)
        fn = _build_ring(mesh, n, reps)
        out = fn(x)
        np.asarray(out.addressable_shards[0].data.ravel()[:1])  # compile+sync
        t0 = time.perf_counter()
        out = fn(x)
        np.asarray(out.addressable_shards[0].data.ravel()[:1])
        dt = time.perf_counter() - t0
        hop = dt / reps
        rec = {
            "payload_bytes": per_shard * 2,
            "hops": reps,
            "per_hop_us": round(hop * 1e6, 2),
            "gbps": round(per_shard * 2 / hop / 1e9, 3),
            "device": getattr(dev, "device_kind", "cpu"),
            "n_stages": n,
        }
        results.append(rec)
        print(json.dumps(rec), flush=True)
    if json_out:
        with open(json_out, "w") as f:
            json.dump(results, f, indent=1)
    return results


def main() -> int:
    from cake_tpu.utils.compile_cache import configure

    configure()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--stages", type=int, default=None)
    ap.add_argument("--reps", type=int, default=64)
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()
    probe(args.stages, args.reps, args.json_out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
