"""Flash-attention crossover sweep: pallas vs XLA across context lengths.

Measures the compiled flash kernels against the reference-math XLA oracle
(`cake-core/src/model/attention.rs:62-77` f32-scores convention) over a grid
of (T, S) shapes at Llama-3-8B attention geometry, to pick the context-length
crossover used by :func:`cake_tpu.ops.attention.attend`'s ``impl="auto"``
dispatch — the same measured-crossover treatment ``quant_matmul`` got for its
M>=16 gate (`ops/quant.py`).

Usage:  python -m cake_tpu.tools.flash_sweep [--json-out PATH]
(``--json-out`` is refused off a TPU: interpreted kernels, no device times.)

Prints one JSON line per shape:
  {"path": "prefill"|"decode", "t", "s", "pallas_ms", "xla_ms", "speedup"}
"""

from __future__ import annotations

import argparse
import json
import sys
from functools import partial

import jax
import jax.numpy as jnp

from cake_tpu.tools.kernel_check import _time_ms, refuse_offchip_record


def _audit(rec: dict) -> dict:
    """Annotate a sweep record with what ``impl='auto'`` dispatches at this
    shape and the resulting speedup over always-XLA (>= 1.0 everywhere is
    the dispatch-policy contract)."""
    from cake_tpu.ops.attention import PREFILL_FLASH_MIN_S

    auto = ("flash" if rec["path"] == "prefill"
            and rec["s"] >= PREFILL_FLASH_MIN_S else "xla")
    rec["auto_impl"] = auto
    rec["auto_speedup"] = rec["speedup"] if auto == "flash" else 1.0
    return rec


def sweep(json_out: str | None = None) -> list:
    from cake_tpu.ops.attention import _attend_xla
    from cake_tpu.ops.pallas import flash_attention, flash_decode, interpret_default

    compiled = not interpret_default()
    dev = jax.devices()[0]
    sys.stderr.write(f"device={dev.device_kind} compiled={compiled}\n")
    b, h, kvh, d = 1, 32, 8, 128
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)

    class _Flushed(list):
        """append() also rewrites json_out — a mid-sweep crash keeps
        the rows that already landed."""

        def append(self, rec) -> None:
            super().append(rec)
            if json_out:
                with open(json_out, "w") as f:
                    json.dump(list(self), f, indent=1)

    results = _Flushed()

    f_pal = jax.jit(partial(flash_attention, interpret=not compiled))
    fd_pal = jax.jit(partial(flash_decode, interpret=not compiled))
    f_xla = jax.jit(_attend_xla)

    # Decode: T=1 against a KV buffer of S. Frontier-near-the-end rows are
    # the worst case (XLA must sweep ~everything either way); the EARLY-
    # frontier rows in a long window are the one regime where flash decode
    # has a structural edge — it reads KV blocks only up to the frontier
    # while XLA's fused gemv sweeps the whole buffer. The early rows are
    # the measurement `ops/attention.py` used to claim without evidence;
    # they decide whether `auto` gets a frontier-aware dispatch or the
    # claim dies.
    for s, p in ((512, 488), (1024, 1000), (2048, 2024), (4096, 4072),
                 (8192, 8168),  # late frontier (s - 24)
                 (4096, 512), (8192, 512), (8192, 2048), (16384, 1024)):
        kv_k = jax.random.normal(ks[0], (b, kvh, s, d), jnp.bfloat16)
        kv_v = jax.random.normal(ks[1], (b, kvh, s, d), jnp.bfloat16)
        q = jax.random.normal(ks[2], (b, h, 1, d), jnp.bfloat16)
        pos = jnp.int32(p)
        p_ms = _time_ms(fd_pal, q, kv_k, kv_v, pos)
        x_ms = _time_ms(f_xla, q, kv_k, kv_v, pos)
        rec = _audit({"path": "decode", "t": 1, "s": s, "pos": p,
                      "pallas_ms": round(p_ms, 4), "xla_ms": round(x_ms, 4),
                      "speedup": round(x_ms / p_ms, 3)})
        results.append(rec)
        print(json.dumps(rec), flush=True)

    # Batched (serving) decode: per-row frontiers, the BatchGenerator shape
    for bb, s in ((8, 1024), (8, 4096), (32, 1024), (32, 4096)):
        kv_k = jax.random.normal(ks[0], (bb, kvh, s, d), jnp.bfloat16)
        kv_v = jax.random.normal(ks[1], (bb, kvh, s, d), jnp.bfloat16)
        q = jax.random.normal(ks[2], (bb, h, 1, d), jnp.bfloat16)
        pos = jnp.clip(
            jnp.arange(1, bb + 1, dtype=jnp.int32) * (s // (bb + 1)),
            16, s - 2,
        )
        p_ms = _time_ms(fd_pal, q, kv_k, kv_v, pos)
        x_ms = _time_ms(f_xla, q, kv_k, kv_v, pos)
        rec = _audit({"path": "decode", "t": 1, "s": s, "batch": bb,
                      "pallas_ms": round(p_ms, 4), "xla_ms": round(x_ms, 4),
                      "speedup": round(x_ms / p_ms, 3)})
        results.append(rec)
        print(json.dumps(rec), flush=True)

    # Prefill: chunk of T tokens against a window of S (T <= S); both the
    # full-prompt case (T = S/2, frontier mid-buffer) and the chunked case
    # (small T against a large populated window) appear in real runs.
    for t, s in ((256, 512), (512, 1024), (512, 2048), (1024, 2048),
                 (512, 4096), (2048, 4096), (2048, 8192), (512, 8192)):
        kv_k = jax.random.normal(ks[0], (b, kvh, s, d), jnp.bfloat16)
        kv_v = jax.random.normal(ks[1], (b, kvh, s, d), jnp.bfloat16)
        q = jax.random.normal(ks[2], (b, h, t, d), jnp.bfloat16)
        pos = jnp.int32(s - t - 8)  # frontier near the end: max valid keys
        inner = max(2, min(32, (2048 * 4096) // (t * s) * 4))
        p_ms = _time_ms(f_pal, q, kv_k, kv_v, pos, inner=inner)
        x_ms = _time_ms(f_xla, q, kv_k, kv_v, pos, inner=inner)
        rec = _audit({"path": "prefill", "t": t, "s": s,
                      "pallas_ms": round(p_ms, 4), "xla_ms": round(x_ms, 4),
                      "speedup": round(x_ms / p_ms, 3)})
        results.append(rec)
        print(json.dumps(rec), flush=True)

    # Windowed decode (Mistral sliding window): the kernel reads ~W of KV
    # bytes where XLA sweeps+masks the whole buffer — the structural case
    # grows with S/W. auto currently stays XLA (measured-crossover rule);
    # a winning row here is what flips it.
    @jax.jit
    def fd_pal_w(q, kk_, vv_, pos):
        return flash_decode(q, kk_, vv_, pos, window=4096,
                            interpret=not compiled)

    @jax.jit
    def fd_xla_w(q, kk_, vv_, pos):
        return _attend_xla(q, kk_, vv_, pos, window=4096)

    for s in (8192, 16384):
        kv_k = jax.random.normal(ks[0], (b, kvh, s, d), jnp.bfloat16)
        kv_v = jax.random.normal(ks[1], (b, kvh, s, d), jnp.bfloat16)
        q1 = jax.random.normal(ks[2], (b, h, 1, d), jnp.bfloat16)
        pos = jnp.int32(s - 8)
        p_ms = _time_ms(fd_pal_w, q1, kv_k, kv_v, pos)
        x_ms = _time_ms(fd_xla_w, q1, kv_k, kv_v, pos)
        rec = {"path": "decode_win4096", "t": 1, "s": s,
               "pallas_ms": round(p_ms, 4), "xla_ms": round(x_ms, 4),
               "speedup": round(x_ms / p_ms, 3),
               "auto_impl": "xla", "auto_speedup": 1.0}
        results.append(rec)
        print(json.dumps(rec), flush=True)

    # Windowed prefill (Mistral sliding window): the kernel's block sweep
    # is window-proportional (out-of-window KV blocks never fetched) vs
    # the XLA path's full-history sweep+mask. Window 4096 at an 8K/16K
    # frontier is the Mistral-7B geometry of record.
    @jax.jit
    def f_pal_w(q, kk_, vv_, pos):
        return flash_attention(q, kk_, vv_, pos, window=4096,
                               interpret=not compiled)

    @jax.jit
    def f_xla_w(q, kk_, vv_, pos):
        return _attend_xla(q, kk_, vv_, pos, window=4096)

    for t, s in ((2048, 8192), (512, 8192), (2048, 16384)):
        kv_k = jax.random.normal(ks[0], (b, kvh, s, d), jnp.bfloat16)
        kv_v = jax.random.normal(ks[1], (b, kvh, s, d), jnp.bfloat16)
        q = jax.random.normal(ks[2], (b, h, t, d), jnp.bfloat16)
        pos = jnp.int32(s - t - 8)
        inner = max(2, min(32, (2048 * 4096) // (t * s) * 4))
        p_ms = _time_ms(f_pal_w, q, kv_k, kv_v, pos, inner=inner)
        x_ms = _time_ms(f_xla_w, q, kv_k, kv_v, pos, inner=inner)
        full_ms = _time_ms(f_pal, q, kv_k, kv_v, pos, inner=inner)
        rec = {"path": "prefill_win4096", "t": t, "s": s,
               "pallas_ms": round(p_ms, 4), "xla_ms": round(x_ms, 4),
               "full_flash_ms": round(full_ms, 4),
               "speedup": round(x_ms / p_ms, 3),
               "auto_impl": "flash", "auto_speedup": round(x_ms / p_ms, 3)}
        results.append(rec)
        print(json.dumps(rec), flush=True)

    # Int8-KV prefill: the quantization-aware flash kernel vs the XLA path
    # over trace-level-dequantized buffers (what the dispatch uses below
    # the crossover) — the long-context plane of the quantized cache.
    from cake_tpu.ops.kvcache import dequant_kv, quant_kv
    from cake_tpu.ops.pallas import flash_attention_q8

    fq8 = jax.jit(partial(flash_attention_q8, interpret=not compiled))

    @jax.jit
    def xla_deq(q, kq, ksc, vq, vsc, pos):
        from cake_tpu.ops.kvcache import QuantizedKV

        return _attend_xla(q,
                           dequant_kv(QuantizedKV(q=kq, scale=ksc), q.dtype),
                           dequant_kv(QuantizedKV(q=vq, scale=vsc), q.dtype),
                           pos)

    for t, s in ((512, 2048), (2048, 4096), (2048, 8192)):
        kv_k = quant_kv(jax.random.normal(ks[0], (b, kvh, s, d), jnp.bfloat16))
        kv_v = quant_kv(jax.random.normal(ks[1], (b, kvh, s, d), jnp.bfloat16))
        q = jax.random.normal(ks[2], (b, h, t, d), jnp.bfloat16)
        pos = jnp.int32(s - t - 8)
        inner = max(2, min(32, (2048 * 4096) // (t * s) * 4))
        p_ms = _time_ms(fq8, q, kv_k.q, kv_k.scale, kv_v.q, kv_v.scale, pos,
                        inner=inner)
        x_ms = _time_ms(xla_deq, q, kv_k.q, kv_k.scale, kv_v.q, kv_v.scale,
                        pos, inner=inner)
        rec = {"path": "prefill_q8kv", "t": t, "s": s,
               "pallas_ms": round(p_ms, 4), "xla_ms": round(x_ms, 4),
               "speedup": round(x_ms / p_ms, 3),
               "auto_impl": "flash_q8", "auto_speedup": round(x_ms / p_ms, 3)}
        results.append(rec)
        print(json.dumps(rec), flush=True)

    return list(results)


def main() -> int:
    from cake_tpu.utils.compile_cache import configure

    configure()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()
    refuse_offchip_record(args.json_out)
    sweep(args.json_out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
