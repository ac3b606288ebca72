"""On-hardware Pallas kernel validation: compiled kernels vs XLA oracle.

The CPU test suite only ever runs the Pallas kernels *interpreted*
(tests/conftest.py forces the CPU platform; pallas.interpret_default),
and tests/test_chip_compile.py only proves the chip's compiler accepts
them. This harness proves the Mosaic-COMPILED kernels on a real chip:
numerical parity against the reference-math XLA implementations (the
f32-scores convention of `cake-core/src/model/attention.rs:62-77`) and
speed. ``chip_smoke.py`` runs :func:`check_kernels` in its kernel child.

Usage:  python -m cake_tpu.tools.kernel_check [--json-out PATH]

Prints one JSON line per kernel:
  {"kernel", "device", "compiled", "max_abs_err", "pallas_ms", "xla_ms",
   "speedup"}
plus an end-to-end decode comparison (CAKE_PALLAS=1 vs 0) when run on TPU.
Exit code is non-zero if any kernel's error exceeds its tolerance.

``--json-out`` is a device record: off a TPU the kernels run interpreted,
so the file is refused there (the rows still print to stdout).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _sync(x):
    for leaf in jax.tree.leaves(x):
        np.asarray(leaf.ravel()[:1])


def _time_ms(fn, *args, iters: int = 20, inner: int = 32, chain=None) -> float:
    """Per-call latency with dispatch amortized: each timed dispatch runs
    ``inner`` invocations inside one jitted program, so the per-dispatch
    host cost (not measured on the chip tool) cannot floor a sub-ms kernel.

    Each iteration's first argument is perturbed by ``prev_out * 1e-30``
    (``chain`` overrides how the output is folded back in) — a genuine data
    dependence, so XLA cannot hoist/CSE the loop body into a single call;
    the perturbation itself is rounded away and does not change the math.
    """
    if chain is None:
        def chain(out, a0):
            return a0 + (out * 1e-30).astype(a0.dtype)

    @jax.jit
    def repeated(*a):
        def body(a0, _):
            out = fn(a0, *a[1:])
            return chain(out, a0), out

        a0, out = jax.lax.scan(body, a[0], None, length=inner)
        return out

    out = repeated(*args)  # compile
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = repeated(*args)
    _sync(out)
    return (time.perf_counter() - t0) / (iters * inner) * 1e3


def _report(name: str, device: str, compiled: bool, err: float,
            p_ms: float | None, x_ms: float | None, tol: float,
            results: list) -> bool:
    """One parity row. Times are device times or nothing: an interpreted
    (off-chip) run passes None and the row says so."""
    ok = err <= tol
    timed = p_ms is not None and x_ms is not None
    rec = {
        "kernel": name,
        "device": device,
        "compiled": compiled,
        "max_abs_err": float(err),
        "tol": tol,
        "pallas_ms": round(p_ms, 4) if timed else "not measured",
        "xla_ms": round(x_ms, 4) if timed else "not measured",
        "speedup": round(x_ms / p_ms, 3) if timed and p_ms > 0 else None,
        "ok": ok,
    }
    results.append(rec)
    print(json.dumps(rec))
    return ok


def check_kernels(dtype=jnp.bfloat16, results: list | None = None,
                  shrink: int = 1) -> tuple[list, bool]:
    """Run every Pallas kernel at 8B-like shapes vs its XLA oracle.
    ``results``: pass a pre-built list (e.g. the crash-safe
    :class:`_FlushedResults`) to collect rows into. ``shrink`` divides
    every sequence and matrix dimension: the interpreted CPU rehearsal of
    chip_smoke.py, whose rows carry the shrunk shapes in their names.
    Kernels are timed only when compiled for the chip; an interpreted
    run checks parity and reports its times as "not measured"."""
    from cake_tpu.ops import kvcache, quant
    from cake_tpu.ops.attention import _attend_xla
    from cake_tpu.ops.pallas import (
        flash_attention,
        flash_attention_q8,
        flash_decode,
        interpret_default,
        quant4_matmul_pallas,
        quant_matmul_pallas,
    )

    device = jax.devices()[0].device_kind
    compiled = not interpret_default()
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    if results is None:
        results = []
    all_ok = True

    def check(name, pal, xla, args, tol, **time_kw):
        nonlocal all_ok
        got = pal(*args).astype(jnp.float32)
        want = xla(*args).astype(jnp.float32)
        err = float(jnp.max(jnp.abs(got - want)))
        p_ms = _time_ms(pal, *args, **time_kw) if compiled else None
        x_ms = _time_ms(xla, *args, **time_kw) if compiled else None
        all_ok &= _report(name, device, compiled, err, p_ms, x_ms, tol,
                          results)

    # Llama-3-8B / Mistral-7B attention geometry: 32 q heads, 8 kv heads,
    # head_dim 128. bf16 magnitude-1 inputs; KV buffers fully populated.
    b, h, kvh, d = 1, 32, 8, 128

    def qkv(t, s):
        return (jax.random.normal(ks[0], (b, h, t, d), dtype),
                jax.random.normal(ks[1], (b, kvh, s, d), dtype),
                jax.random.normal(ks[2], (b, kvh, s, d), dtype))

    f_pal = jax.jit(partial(flash_attention, interpret=not compiled))
    f_xla = jax.jit(_attend_xla)

    # -- flash_attention: a prefill chunk mid-buffer, then long context ------
    # (T=2048 against S=8192 is where the blockwise kernel earns its keep:
    # the XLA path materializes [H, T, S] f32 scores, 2 GiB there)
    for t, s, pos, kw in ((512, 1024, 137, {}), (2048, 8192, 0,
                                                {"inner": 8})):
        t, s, pos = t // shrink, s // shrink, pos // shrink
        check(f"flash_attention_prefill_t{t}_s{s}", f_pal, f_xla,
              (*qkv(t, s), jnp.int32(pos)), 0.05, **kw)

    # -- flash_attention_q8 (int8 KV: a 512-token chunk, 4096 window) --------
    # the shape --kv-quant int8 dispatches for a long prompt chunk against
    # a Mistral window (ops/attention.attend). Oracle: the XLA attention
    # over the SAME quantized cache, dequantized -- so the row prices the
    # kernel's scale folding, not the quantization itself.
    t, s = 512 // shrink, 4096 // shrink
    q8, k8, v8 = qkv(t, s)
    kq, vq = kvcache.quant_kv(k8), kvcache.quant_kv(v8)
    f8_pal = jax.jit(partial(flash_attention_q8, window=s,
                             interpret=not compiled))

    @jax.jit
    def f8_xla(q, kq_q, kq_s, vq_q, vq_s, pos):
        return _attend_xla(
            q, kvcache.dequant_kv(kvcache.QuantizedKV(kq_q, kq_s), q.dtype),
            kvcache.dequant_kv(kvcache.QuantizedKV(vq_q, vq_s), q.dtype),
            pos, window=s)

    check(f"flash_attention_q8_win{s}_t{t}_s{s}", f8_pal, f8_xla,
          (q8, kq.q, kq.scale, vq.q, vq.scale, jnp.int32(s - t - 8)), 0.05,
          inner=8)
    del q8, k8, v8, kq, vq

    # -- flash_decode (T=1 near the end of the buffer) -----------------------
    s = 1024 // shrink
    fd_pal = jax.jit(partial(flash_decode, interpret=not compiled))
    check(f"flash_decode_s{s}", fd_pal, f_xla,
          (*qkv(1, s), jnp.int32(s - 24)), 0.05)

    # -- quant_matmul (8B mlp up-proj slice: 4096 x 4096) --------------------
    # int8 dequant epilogue vs convert-into-dot: identical math modulo
    # accumulation order; bf16 output quantum at |y|~64 is ~0.5
    kk = n = 4096 // shrink
    x = jax.random.normal(ks[4], (8, kk), dtype)
    w = jax.random.normal(ks[5], (kk, n), dtype)
    ql = quant.quantize_linear(w)
    check(f"quant_matmul_{kk}x{n}_int8",
          jax.jit(partial(quant_matmul_pallas, interpret=not compiled)),
          jax.jit(quant.quant_matmul_xla), (x, ql.q, ql.scale), 1.0)

    # -- quant4_matmul: packed int4, per-channel and grouped -----------------
    # proves the Mosaic lowering of the int32 nibble-unpack shifts and the
    # grouped scale index map on real hardware (the CPU suite only ever
    # interprets), and measures the m=1 gemv regime that decides the decode
    # dispatch frontier
    q4 = quant.quantize_linear4(w)
    q4m_pal = jax.jit(partial(quant4_matmul_pallas, interpret=not compiled))
    q4m_xla = jax.jit(quant.quant4_matmul_xla)
    for rows in (8, 1, 16):
        xr = jax.random.normal(ks[6], (rows, kk), dtype)
        check(f"quant4_matmul_{kk}x{n}_m{rows}", q4m_pal, q4m_xla,
              (xr, q4.qp, q4.scale), 1.0)
    q4g = quant.quantize_linear4(w, group_size=256)  # g2=128: tileable
    check(f"quant4_matmul_{kk}x{n}_g256", q4m_pal, q4m_xla,
          (x, q4g.qp, q4g.scale), 1.0)

    return results, all_ok


def check_end_to_end(results: list) -> None:
    """Decode tok/s with kernels on (CAKE_PALLAS=1) vs off (=0), same process.

    The dispatch mode is read at trace time (pallas.kernels_enabled inside
    attend), so two fresh jit objects traced under different env values give
    the two paths.
    """
    from cake_tpu.models.config import LlamaConfig
    from cake_tpu.models.llama import init_params
    from cake_tpu.ops.kvcache import init_cache
    from cake_tpu.ops.sampling import SamplerSettings, init_history
    from cake_tpu.runtime.generator import decode_scan_fn

    # head_dim 128 (hidden/heads) so the flash gate (_flash_ok) routes the
    # attention to the compiled kernels — the point of the comparison
    config = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=8,
        max_seq_len=1024,
    )
    settings = SamplerSettings(temperature=0.0, repeat_penalty=1.1)
    params = init_params(config, jax.random.PRNGKey(0))
    steps = 16

    tok_s = {}
    toks_by_mode = {}
    for mode in ("1", "0"):
        os.environ["CAKE_PALLAS"] = mode
        decode = jax.jit(
            partial(decode_scan_fn, config=config, settings=settings,
                    steps=steps),
        )
        cache = init_cache(config, batch=1, max_seq=config.max_seq_len)
        history, hist_slot = init_history(settings.repeat_last_n)
        args = [params, jnp.asarray([7], jnp.int32), cache, jnp.int32(512),
                jax.random.PRNGKey(0), history, hist_slot]
        out = decode(*args)  # compile
        _sync(out)
        toks_by_mode[mode] = np.asarray(out[0])
        t0 = time.perf_counter()
        n = 0
        for _ in range(8):
            out = decode(*args)
            n += steps
        _sync(out)
        tok_s[mode] = n / (time.perf_counter() - t0)
    os.environ.pop("CAKE_PALLAS", None)

    rec = {
        "kernel": "e2e_decode_small_s1024",
        "device": jax.devices()[0].device_kind,
        "tok_s_pallas": round(tok_s["1"], 2),
        "tok_s_xla": round(tok_s["0"], 2),
        "speedup": round(tok_s["1"] / tok_s["0"], 3),
        "tokens_match": bool((toks_by_mode["1"] == toks_by_mode["0"]).all()),
    }
    results.append(rec)
    print(json.dumps(rec))


class _FlushedResults(list):
    """A results list whose append also rewrites ``--json-out``: a
    mid-run crash must never erase rows that already landed."""

    def __init__(self, path: str | None):
        super().__init__()
        self.path = path

    def append(self, rec) -> None:
        super().append(rec)
        if self.path:
            with open(self.path, "w") as f:
                json.dump(list(self), f, indent=1)


def refuse_offchip_record(json_out: str | None) -> None:
    """``--json-out`` files are device records. Off a TPU the kernels
    run interpreted, so writing one is refused; stdout still carries the
    rows. Shared by the sweep tools."""
    platform = jax.devices()[0].platform
    if json_out and platform != "tpu":
        sys.exit(
            f"error: --json-out records on-chip measurements, but this "
            f"process runs on {platform!r} (kernels interpreted, times "
            "meaningless). Run it on the chip, or drop --json-out and read "
            "the rows from stdout")


def main() -> int:
    from cake_tpu.utils.compile_cache import configure

    configure()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json-out", default=None,
                    help="also write all records to this file (rewritten "
                         "after every row — crash-safe); refused off-TPU")
    ap.add_argument("--e2e", action="store_true",
                    help="include the end-to-end decode comparison")
    args = ap.parse_args()
    refuse_offchip_record(args.json_out)

    dev = jax.devices()[0]
    sys.stderr.write(f"device={dev.device_kind} platform={dev.platform}\n")
    results, ok = check_kernels(results=_FlushedResults(args.json_out))
    if args.e2e or dev.platform == "tpu":
        check_end_to_end(results)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
