"""Int4 decode-gemv sweep: find why (and fix how) m=1 int4 runs under its
roofline.

The question: whether single-stream int4 decode reaches its weights-bound
roofline (half of int8's bytes), or whether the m=1 int4 kernel and not
HBM bounds it (not measured on the chip tool). Working hypothesis
(ops/pallas/quant.py:_kernel4): the per-byte nibble unpack (widen + shifts
+ converts over a [BK2, BN] block) is VPU-bound and its widened
temporaries pressure VMEM; both effects are block-size- and
width-dependent. This tool measures, per decode-critical 8B shape, the
kernel across {block_n} x {block_k} x {int32, int16} unpack variants plus
the XLA fallback and the int8 kernel (the byte-rate ceiling to beat),
reporting achieved packed-GB/s so the gap to the ~819 GB/s v5e HBM peak is
explicit.

Usage:  python -m cake_tpu.tools.int4_sweep [--json-out PATH] [--m M]
(``--json-out`` is refused off a TPU: interpreted kernels, no device times.)

One JSON line per row:
  {"k", "n", "variant", "block_n", "block_k", "ms", "gbps", "speedup_vs_xla"}

The winning (block, unpack) per shape is the measured config the kernel's
defaults should adopt (the same measured-crossover discipline as
quant_matmul's m>=16 gate and flash's PREFILL_FLASH_MIN_S).
"""

from __future__ import annotations

import argparse
import json
import sys
from functools import partial

import jax
import jax.numpy as jnp

from cake_tpu.tools.kernel_check import _time_ms, refuse_offchip_record


# Llama-3-8B decode linears (in, out): the per-token weight sweep.
SHAPES_8B = [
    (4096, 4096),    # wq / wo
    (4096, 14336),   # w_gate / w_up (the big pair)
    (14336, 4096),   # w_down
]


def sweep(json_out: str | None = None, m: int = 1) -> list:
    # probe the failure-prone setup BEFORE truncating the ledger: a bad
    # pallas import or a failed device init must not zero out the
    # previous run's rows (the modules stay cached for _sweep)
    import jax

    from cake_tpu.ops.pallas.quant import quant4_matmul_pallas  # noqa: F401
    from cake_tpu.ops.quant import quant4_matmul_xla  # noqa: F401

    jax.devices()
    # `with` owns the ledger file: a sweep dying mid-shape (OOM, ctrl-C)
    # must not lose buffered rows or leak the fd (cakelint CK-WIRE)
    if json_out:
        with open(json_out, "w") as out_f:
            return _sweep(out_f, m)
    return _sweep(None, m)


def _sweep(out_f, m: int = 1) -> list:
    from cake_tpu.ops.pallas import interpret_default
    from cake_tpu.ops.pallas.quant import (
        quant4_matmul_pallas,
        quant_matmul_pallas,
    )
    from cake_tpu.ops.quant import (
        quant4_matmul_xla,
        quantize_linear,
        quantize_linear4,
    )

    from cake_tpu.ops.pallas.quant import _pick_block

    compiled = not interpret_default()
    dev = jax.devices()[0]
    sys.stderr.write(f"device={dev.device_kind} compiled={compiled} m={m}\n")
    key = jax.random.PRNGKey(0)
    results = []

    def emit(rec):
        results.append(rec)
        print(json.dumps(rec), flush=True)
        if out_f:
            out_f.write(json.dumps(rec) + "\n")
            out_f.flush()

    # The timed loop's data-dependence fold must be shape-agnostic: the
    # default chain adds the (m, n) output into the (m, k) activation,
    # which only broadcasts when n == k — a scalar fold works everywhere.
    def chain(out, a0):
        return a0 + (out.ravel()[0] * 1e-30).astype(a0.dtype)

    # bf16 is the decode activation dtype of record; interpret mode (the
    # CPU smoke path) hits an interpreter bf16-in-scan limitation, so it
    # smokes in f32 — the real measurement is compiled-on-TPU either way.
    act_dt = jnp.bfloat16 if compiled else jnp.float32
    for k, n in SHAPES_8B:
        kx, kw = jax.random.split(jax.random.fold_in(key, k * n))
        x = jax.random.normal(kx, (m, k), act_dt)
        w = jax.random.normal(kw, (k, n), jnp.float32) / jnp.sqrt(k)
        q4 = quantize_linear4(w)
        q8 = quantize_linear(w)
        packed_mb = q4.qp.size / 1e6  # int8 bytes holding two nibbles each

        # baselines: the XLA unpack fallback and the int8 kernel byte rate
        try:
            xla_ms = _time_ms(jax.jit(quant4_matmul_xla), x, q4.qp,
                              q4.scale, chain=chain)
            emit(dict(k=k, n=n, variant="xla", block_n=0, block_k=0,
                      ms=xla_ms, gbps=packed_mb / xla_ms,
                      speedup_vs_xla=1.0))
        except Exception as e:
            sys.stderr.write(f"  k={k} n={n} xla baseline: "
                             f"{type(e).__name__}: {str(e)[:120]}\n")
            xla_ms = None
        try:
            int8_ms = _time_ms(
                jax.jit(partial(quant_matmul_pallas,
                                interpret=not compiled)),
                x, q8.q, q8.scale, chain=chain,
            )
            emit(dict(k=k, n=n, variant="int8_kernel", block_n=0,
                      block_k=0, ms=int8_ms,
                      gbps=2 * packed_mb / int8_ms,  # int8 bytes
                      speedup_vs_xla=(xla_ms / int8_ms) if xla_ms else None))
        except Exception as e:
            sys.stderr.write(f"  k={k} n={n} int8 baseline: "
                             f"{type(e).__name__}: {str(e)[:120]}\n")
            int8_ms = None

        # XLA-native s4: store the quantized values as a jnp.int4 array and
        # let XLA's own int4 support handle the unpack (TPU XLA carries
        # hardware-assisted s4 conversion; if it streams packed bytes this
        # beats any hand-written unpack). Same math as the kernel:
        # y = (x @ w4) * scale with the convert fused into the dot operand.
        try:
            from cake_tpu.ops.quant import unpack_int4

            w4 = jnp.asarray(unpack_int4(q4.qp), jnp.int8).astype(jnp.int4)

            def s4_matmul(x, w4, scale):
                y = jnp.einsum("mk,kn->mn", x, w4.astype(x.dtype),
                               preferred_element_type=jnp.float32)
                return (y * scale).astype(x.dtype)

            s4_ms = _time_ms(jax.jit(s4_matmul), x, w4, q4.scale,
                             chain=chain)
            emit(dict(k=k, n=n, variant="xla_s4", block_n=0, block_k=0,
                      ms=s4_ms, gbps=packed_mb / s4_ms,
                      speedup_vs_xla=(xla_ms / s4_ms) if xla_ms else None))
        except Exception as e:
            sys.stderr.write(f"  k={k} n={n} xla_s4: "
                             f"{type(e).__name__}: {str(e)[:160]}\n")

        # report configs by the blocks that actually EXECUTE: the grid
        # clamps to power-of-2 divisors (_pick_block), so distinct
        # requests can collapse; dedupe on the effective pair and disable
        # the skinny-M widening that would override sub-1024 requests.
        seen = set()
        for unpack in ("int32", "int16"):
            for bn in (512, 1024, 2048):
                for bk in (512, 1024, 2048):
                    if bn > n or bk > k // 2:
                        continue
                    bn_eff = _pick_block(n, bn)
                    bk_eff = _pick_block(k // 2, bk)
                    if (unpack, bn_eff, bk_eff) in seen:
                        continue
                    seen.add((unpack, bn_eff, bk_eff))
                    fn = jax.jit(partial(
                        quant4_matmul_pallas, block_n=bn_eff,
                        block_k=bk_eff, unpack=unpack, skinny_widen=False,
                        interpret=not compiled,
                    ))
                    try:
                        ms = _time_ms(fn, x, q4.qp, q4.scale, chain=chain)
                    except Exception as e:  # Mosaic lowering edge: record
                        sys.stderr.write(
                            f"  k={k} n={n} {unpack} bn={bn_eff} "
                            f"bk={bk_eff}: "
                            f"{type(e).__name__}: {str(e)[:120]}\n")
                        continue
                    emit(dict(k=k, n=n, variant=unpack, block_n=bn_eff,
                              block_k=bk_eff, ms=ms, gbps=packed_mb / ms,
                              speedup_vs_xla=(xla_ms / ms) if xla_ms
                              else None))

        best = max((r for r in results if r["k"] == k and r["n"] == n
                    and r["variant"] in ("int32", "int16")),
                   key=lambda r: r["gbps"], default=None)
        if best:
            sys.stderr.write(
                f"shape {k}x{n}: best {best['variant']} "
                f"bn={best['block_n']} bk={best['block_k']} "
                f"{best['gbps']:.0f} GB/s"
                + (f" (xla {packed_mb / xla_ms:.0f}" if xla_ms else " (")
                + (f", int8 kernel {2 * packed_mb / int8_ms:.0f} int8-GB/s)"
                   if int8_ms else ")")
                + "\n")

    return results


def main() -> int:
    from cake_tpu.utils.compile_cache import configure

    configure()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--m", type=int, default=1)
    args = ap.parse_args()
    refuse_offchip_record(args.json_out)
    sweep(args.json_out, m=args.m)
    return 0


if __name__ == "__main__":
    sys.exit(main())
