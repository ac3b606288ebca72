"""An admission's programs by how many arrivals a launch carries: N
launches of one row against one launch of N rows.

Builds the serving engine as ``--mode serve`` does (the same flags: a
checkpoint directory, ``--quantize``, ``--dtype``, ``--max-seq``,
``--max-concurrent``) and times, on the device, what
``BatchGenerator._start_arrival`` could dispatch for a bucket of ``C``
prompt tokens: the prefill program over ``[R, C]`` for each row count of
``--rows`` (the staging cache donated from call to call, as a launch
donates it) and the splice of ``R`` staged rows into the live cache (the
cache and the sampler state donated from call to call, as a landing
donates them: the rows are written in place). Where
``batch_generator.GROUP_SHAPES`` comes from: a launch of ``R`` rows pays
when ``[R, C]`` plus one splice costs less than its members' own
``[1, C']`` programs plus a splice each (PERF.md keeps the table).

Usage:  python -m cake_tpu.tools.admit_sweep --model DIR [serve flags]
            [--buckets 64,128,256,512] [--rows 1,2,4] [--json-out PATH]
(``--json-out`` is refused off a TPU: no device times.)

Prints one JSON line per bucket and row count: ``{"chunk", "rows",
"prefill_ms", "splice_ms", "singles": what as many one-row launches cost
(prefill and splice each), "ratio": this launch with its one splice over
them}``.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.tools.kernel_check import refuse_offchip_record


def _time_ms(call, iters: int) -> float:
    out = call()  # compile, and the first run
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = call()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1e3 / iters


def sweep(engine, buckets, row_counts=(1, 2, 4), iters: int = 6):
    """Yield a row per (bucket, row count); ``row_counts`` starts with 1."""
    rng = np.random.default_rng(0)
    vocab = engine.config.vocab_size
    splice_ms = {}
    for chunk in buckets:
        single = None
        for rows in row_counts:
            tokens = jnp.asarray(
                rng.integers(3, vocab, (rows, chunk)).astype(np.int32))
            last = jnp.asarray(np.full((rows,), chunk - 1, np.int32))
            state = {"cache": engine._staging_cache(rows)}

            def prefill():
                logits, state["cache"] = engine._admit_prefill(
                    engine.params, tokens, state["cache"], jnp.int32(0),
                    last)
                return logits

            prefill_ms = _time_ms(prefill, iters)
            if rows not in splice_ms:
                vec = jnp.asarray(np.zeros((rows,), np.int32))
                hist = jnp.asarray(np.full(
                    (rows, engine.settings.repeat_last_n), -1, np.int32))
                keys = jnp.asarray(np.zeros((rows, 2), np.uint32))

                def splice():
                    (engine.cache, engine._keys, engine._history,
                     engine._hist_slot, engine._last_tokens
                     ) = engine._splice_fn()(
                        engine.cache, state["cache"], engine._keys,
                        engine._history, engine._hist_slot,
                        engine._last_tokens, keys, hist, vec, vec, vec)
                    return engine.cache

                splice_ms[rows] = _time_ms(splice, iters)
            if rows == 1:
                single = prefill_ms + splice_ms[1]
            yield {"chunk": chunk, "rows": rows,
                   "prefill_ms": round(prefill_ms, 3),
                   "splice_ms": round(splice_ms[rows], 3),
                   "singles": round(rows * single, 3),
                   "ratio": round((prefill_ms + splice_ms[rows])
                                  / (rows * single), 3)}


def main(argv=None) -> int:
    from cake_tpu import cli
    from cake_tpu.parallel.mesh import MeshPlan
    from cake_tpu.runtime.batch_generator import BatchGenerator
    from cake_tpu.utils.compile_cache import configure

    ap = cli.build_parser()
    ap.description = __doc__.split("\n")[0]
    ap.add_argument("--buckets", default="64,128,256,512")
    ap.add_argument("--rows", default="1,2,4")
    ap.add_argument("--json-out")
    a = ap.parse_args(argv)
    configure()
    refuse_offchip_record(a.json_out)
    config = cli._load_config(a)
    plan = MeshPlan.build(config, num_stages=a.stages, tp=a.tp, dp=a.dp,
                          sp=a.sp, ep=a.ep)
    engine = BatchGenerator(
        config, cli._mesh_params(a, config, plan), plan=plan,
        settings=cli._settings(a), max_seq=a.max_seq,
        block_size=a.decode_block if a.decode_block is not None else 8,
        kv_quant=a.kv_quant)
    bos = config.bos_token_id if config.bos_token_id is not None else 0
    engine.set_prompts([[bos]] * (a.max_concurrent or 8))
    out = []
    for row in sweep(engine, [int(c) for c in a.buckets.split(",")],
                     [int(r) for r in a.rows.split(",")]):
        print(json.dumps(row), flush=True)
        out.append(row)
    if a.json_out:
        with open(a.json_out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
