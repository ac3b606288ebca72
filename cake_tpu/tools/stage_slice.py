"""Price the 70B pipeline's PER-STAGE step on one real chip.

BASELINE.json configs 4/5 (Llama-3-70B layer-sharded over v5e-16) have been
budget-only: `utils.memory.hbm_budget` proves the bytes fit, and the
80-layer file plane is rehearsed at miniature dims
(tests/test_70b_rehearsal.py). This tool adds the missing MEASURED
rung: one v5e-16 stage is 5 of 80 layers, and a 5-layer
slice of the real 70B geometry (hidden 8192, 64 heads / 8 KV heads,
intermediate 28672) FITS one v5e chip — so its decode-step and prefill
wall-clock can be measured for real, and the full-pipeline numbers follow
by multiplication plus an ICI hop term.

What is measured vs projected (reported explicitly in the JSON):

- MEASURED: per-stage decode step time (B=1, T=1, the serialized pipeline
  regime), per-stage prefill time at T=2048, HBM in use.
- PROJECTED: the inter-stage hop. The activation is ``[1, 1, 8192]``
  bf16 = 16 KiB; public v5e ICI figures and the reference's own
  measurement ladder (tools/ici_probe.py — runs on any >=2-chip slice)
  put a neighbor ppermute of that payload at single-digit microseconds,
  vs the ~5 ms stage step: the hop term is noise. The projection is
  carried at a deliberately pessimistic 50 us so the headline cannot
  lean on the favorable assumption.

Single-stream v5e-16 projection: ``1 / (16 * t_stage + 16 * t_hop)``
(stages serialized per token — the reference's own wall-clock shape,
"upstream workers idle", SURVEY.md §2). The interleaved schedule
(parallel/pipeline.build_interleaved_decode) keeps every stage busy with
S=16 microbatches, so its aggregate upper bound is ``16x`` that — both
reported.

Run on one v5e chip: ``python -m cake_tpu.tools.stage_slice``
(``--json-out FILE`` to record; refused off-chip). ``--mini`` runs the
same machinery at tiny dims on CPU (the machinery-proof regression path,
like tests/test_ici_probe.py) and records nothing a device metric could
be mistaken for: its JSON is only written when ``--mini`` is named.
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

from cake_tpu.models.config import LlamaConfig
from cake_tpu.models import llama
from cake_tpu.ops.kvcache import KVCache, init_cache
from cake_tpu.ops.rope import rope_tables

from cake_tpu.utils.chips import HBM_GBPS, device_spec

# deliberately pessimistic inter-stage ppermute projection (see module
# docstring; measured single-digit us on real multi-chip slices)
HOP_S_PROJECTED = 50e-6


def slice_config(layers: int, window: int, mini: bool) -> LlamaConfig:
    """``layers`` of the Llama-3-70B geometry (config.json parity:
    hidden 8192, 64/8 heads, intermediate 28672, vocab 128256)."""
    if mini:
        return LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=layers, num_attention_heads=4,
            num_key_value_heads=2, max_seq_len=window, rope_theta=10000.0,
        )
    return LlamaConfig(
        vocab_size=128256, hidden_size=8192, intermediate_size=28672,
        num_hidden_layers=layers, num_attention_heads=64,
        num_key_value_heads=8, max_seq_len=window, rope_theta=500000.0,
    )


def _layer_params(cfg: LlamaConfig, quant: str | None):
    """Stacked layer weights only — a stage holds no embed/lm_head (those
    live replicated / vocab-sharded outside the stage loop; the budget
    table prices them separately)."""
    key = jax.random.PRNGKey(0)
    if quant == "int8":
        params = llama.init_params_int8(cfg, key)
    else:
        params = llama.init_params(cfg, key)
    layers = params["layers"]
    del params
    return layers


def _sync(x) -> None:
    for leaf in jax.tree.leaves(x):
        np.asarray(leaf.ravel()[:1])


def _param_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def measure_slice(quant: str | None, layers: int, window: int,
                  steps: int, mini: bool) -> dict:
    cfg = slice_config(layers, window, mini)
    dev = jax.devices()[0]
    layer_w = _layer_params(cfg, quant)
    _sync(layer_w)
    cos, sin = rope_tables(cfg.head_dim, window, cfg.rope_theta,
                           scaling=cfg.rope_scaling)

    decode = jax.jit(
        partial(_stage_decode, config=cfg), donate_argnames=("cache",),
    )
    cache = init_cache(cfg, batch=1, max_seq=window)
    x = jnp.ones((1, 1, cfg.hidden_size), cfg.jax_dtype)
    pos = window // 2  # mid-window frontier: representative mask work

    # compile + warm (2 dispatches)
    x_out, cache = decode(layer_w, x, cache, cos, sin, jnp.int32(pos))
    x_out, cache = decode(layer_w, x_out, cache, cos, sin, jnp.int32(pos + 1))
    _sync(x_out)
    t0 = time.perf_counter()
    for i in range(steps):
        x_out, cache = decode(layer_w, x_out, cache, cos, sin,
                              jnp.int32(pos + 2 + i))
        # activation feeds back so steps chain data-dependently (no
        # artificial pipelining of independent dispatches)
    _sync(x_out)
    t_stage = (time.perf_counter() - t0) / steps

    # prefill slice: one T=2048 chunk through the stage (TTFT side)
    t_pf = None
    pf_t = min(2048, window // 2)
    if pf_t >= 8:
        prefill = jax.jit(partial(_stage_decode, config=cfg),
                          donate_argnames=("cache",))
        cache2 = init_cache(cfg, batch=1, max_seq=window)
        xp = jnp.ones((1, pf_t, cfg.hidden_size), cfg.jax_dtype)
        xo, cache2 = prefill(layer_w, xp, cache2, cos, sin, jnp.int32(0))
        _sync(xo)
        cache2 = init_cache(cfg, batch=1, max_seq=window)
        t0 = time.perf_counter()
        xo, cache2 = prefill(layer_w, xp, cache2, cos, sin, jnp.int32(0))
        _sync(xo)
        t_pf = time.perf_counter() - t0

    gb = _param_bytes(layer_w) / 1e9
    # weights-bound floor for one decode step, against the chip's
    # published bandwidth; the --mini CPU proof has no chip to divide by
    roofline_s = None if mini else gb / device_spec(dev, HBM_GBPS)
    stats = dev.memory_stats()  # None on backends that keep no stats
    hbm = stats.get("bytes_in_use") if stats else None

    n_stages = 16 if not mini else 4
    t_tok_serial = n_stages * (t_stage + HOP_S_PROJECTED)
    row = {
        "quant": quant or "bf16",
        "layers_per_stage": layers,
        "window": window,
        "device": dev.device_kind,
        "platform": dev.platform,
        "stage_weight_gb": round(gb, 3),
        "stage_step_ms_measured": round(t_stage * 1e3, 3),
        "stage_step_ms_roofline": (
            round(roofline_s * 1e3, 3) if roofline_s is not None else None),
        "stage_prefill2048_ms_measured": (
            round(t_pf * 1e3, 1) if t_pf is not None else None),
        "hbm_bytes_in_use": hbm,
        "hop_s_projected": HOP_S_PROJECTED,
        "n_stages": n_stages,
        "single_stream_tok_s_projected": round(1.0 / t_tok_serial, 2),
        "interleaved_aggregate_tok_s_upper": round(
            n_stages / t_tok_serial, 2),
        "stamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    return row


def _stage_decode(layer_w, x, cache: KVCache, cos, sin, pos, *, config):
    """One pipeline stage's compute: forward this stage's stacked layers
    over the incoming activation (exactly what _pipeline_layers runs per
    active stage — parallel/pipeline.py; embed/head excluded)."""
    return llama.forward_layers(layer_w, x, cache, cos, sin, pos, config)


_NOTE = (
    "stage_step/prefill are MEASURED single-chip; the hop term and the "
    "v5e-16 tok/s are PROJECTIONS (no multi-chip hardware in this "
    "environment — tools/ici_probe.py is the measurement of record to "
    "run on a real slice)")


def _write_partial(json_out: str | None, rows: list) -> None:
    if not json_out:
        return
    with open(json_out, "w") as f:
        json.dump({"rows": rows, "note": _NOTE}, f, indent=1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--layers", type=int, default=5,
                    help="layers per stage (70B/v5e-16 = 80/16 = 5)")
    ap.add_argument("--window", type=int, default=8192)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--mini", action="store_true",
                    help="tiny dims (CPU machinery proof)")
    ap.add_argument("--json-out", default=None,
                    help="record the rows; refused off-TPU unless --mini "
                         "(whose rows name their tiny dims)")
    args = ap.parse_args(argv)
    from cake_tpu.tools.kernel_check import refuse_offchip_record
    from cake_tpu.utils.compile_cache import configure

    configure()
    if not args.mini:
        refuse_offchip_record(args.json_out)

    if args.mini:
        args.window = min(args.window, 128)
    rows = []
    # int8 (the 70B serving tier of record) runs FIRST and each row is
    # flushed to --json-out the moment it lands: the bf16 variant's ~13 GB
    # peak is tight on a 16 GiB chip, and a crash there must not erase the
    # int8 measurement. A variant that fails to compile or fit fails the
    # run: the exception, with the compiler's or allocator's own message,
    # is the result.
    for quant in ("int8", None):
        row = measure_slice(quant, args.layers, args.window, args.steps,
                            args.mini)
        rows.append(row)
        _write_partial(args.json_out, rows)
        sys.stderr.write(
            f"[{row['quant']}] stage({args.layers}L, win {args.window}) on "
            f"{row['device']}: step {row['stage_step_ms_measured']} ms "
            f"(roofline {row['stage_step_ms_roofline']} ms), "
            f"prefill2048 {row['stage_prefill2048_ms_measured']} ms -> "
            f"v5e-16 projection {row['single_stream_tok_s_projected']} "
            f"tok/s single-stream, "
            f"{row['interleaved_aggregate_tok_s_upper']} aggregate "
            f"(interleaved upper bound; hop term projected "
            f"{HOP_S_PROJECTED * 1e6:.0f} us pessimistic)\n"
        )
    print(json.dumps({"rows": rows, "note": _NOTE}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
