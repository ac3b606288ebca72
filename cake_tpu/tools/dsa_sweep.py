"""A learned sparse attention's parts, timed a layer at the served shapes
(``ops/dsa.py``; GLM-5's cut: 16 streams over 16,384 rows, 64 heads over a
512 + 64 latent row, a 32-head indexer of 128, ``index_topk`` 2048).

A decode step's parts, each a program of its own over the stacked buffers
of ``LAYERS`` layers (the layer a loop value, as the layer loop hands it),
every stream at ``--frontier`` rows (``mix``: the streams spread evenly
over the cell's 4096-15,360): the index scores to the frontier
(``dsa_index``); then the two forms ``ops.dsa.attend_form_choice`` picks
between. The gather: the choice (``lax.top_k``: XLA's sort), the gather of
the chosen rows out of the carried buffer (``[c | k_pe]`` a row), the
absorbed attention over them (``dsa_attend_gathered``). The sweep: the
choice as a threshold (``dsa_select``), the attention over the carried
buffer to the frontier under the kept scores (``dsa_attend``, at each
``--attend-block`` rows a fetch). Beside them the whole sweep a plain
latent model runs (``latent_decode`` to the frontier), and both forms'
sums with the index scores (``gather_path``, ``sweep_path``: where
``SWEEP_MAX_ROWS`` comes from). ``--rows``: the buffer's rows a stream;
the streams are as many as hold 16 x 16,384 rows in all, and frontiers
past the rows are left out. An admission's two parts at each bucket: the
choice as a mask (``dsa_prefill_select``: scores, thresholds by bisection,
masks) and the masked flash sweep (``dsa_prefill_attend``); beside the
first, the ``jnp`` form's sort of a strip of 128 rows' scores, scaled to
the bucket.

Usage:  python -m cake_tpu.tools.dsa_sweep [--frontier 2048,8192,16000,mix]
                                           [--rows 16384,32768,65536]
                                           [--attend-block 512,1024]
                                           [--buckets 4096,8192,16384]
                                           [--attend-blocks 512x1024x2,..]
                                           [--tiny] [--json-out PATH]
(``--json-out`` is refused off a TPU: interpreted kernels, no device
times; ``--tiny`` runs small shapes, for a rehearsal on the CPU.)

Prints one JSON line per row: ``{"part", "rows", "frontier" | "bucket",
"us_per_layer", "gb_per_s" | "tflop_per_s"}`` (the rate: the least bytes
or the lower triangle's operations over the time).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from cake_tpu.ops import dsa
from cake_tpu.ops import pallas as pk
from cake_tpu.tools.kernel_check import refuse_offchip_record

LAYERS = 5
SERVED = dict(slots=16, rows=16384, heads=64, dc=512, dr=64, d_qk=256,
              index_heads=32, index_dim=128, topk=2048)
TINY = dict(slots=2, rows=1024, heads=4, dc=128, dr=64, d_qk=128,
            index_heads=4, index_dim=128, topk=256)
REPEATS = 10


def _time(fn, *args) -> float:
    """Median seconds of ``fn(*args)`` over ``REPEATS`` calls, each waited
    for (one warm-up call first)."""
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def _over_layers(one):
    """``one(layer, carry)`` over ``LAYERS`` layers in one program (a
    ``fori_loop``: the layer is a loop value, the result carried so that
    nothing is hoisted out)."""
    def run(*args):
        def body(i, acc):
            out = one(i, *args)
            return acc + sum(jnp.sum(o.astype(jnp.float32))
                             for o in jax.tree.leaves(out))
        return jax.lax.fori_loop(0, LAYERS, body, jnp.float32(0))
    return jax.jit(run)


def decode_rows(s: dict, frontier, dtype, blocks=()) -> list[dict]:
    b, rows, h = s["slots"], s["rows"], s["heads"]
    dc, dr, j, d, k = (s["dc"], s["dr"], s["index_heads"], s["index_dim"],
                       s["topk"])
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 16))

    def rand(shape, dt=dtype):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dt)

    # [c | k_pe | padding] a row, whole lane tiles
    row_cache = rand((LAYERS, b, 1, rows, -(-(dc + dr) // 128) * 128))
    c_cache, r_cache = rand((LAYERS, b, 1, rows, dc)), rand(
        (LAYERS, b, 1, rows, dr))  # a plain latent model's two buffers
    i_cache = rand((LAYERS, b, 1, rows, d))
    q_c, q_pe = rand((b, h, 1, dc)), rand((b, h, 1, dr))
    q_i, w = rand((b, j, 1, d)), rand((b, 1, j), jnp.float32)
    if frontier == "mix":  # the cell's streams: 4096-15,360 rows of 16,384
        pos = jnp.linspace(rows // 4, rows * 15 // 16, b).astype(jnp.int32) - 1
    else:
        pos = jnp.full((b,), frontier - 1, jnp.int32)
    live = float(jnp.sum(pos + 1))  # rows up to the frontiers, all streams
    chosen_rows = float(jnp.sum(jnp.minimum(pos + 1, k)))
    scores = dsa.decode_index_scores(q_i, w, i_cache, pos, 0)
    values, picked = dsa.choose(scores, k)
    kept = pk.dsa_select(scores, pos, k)
    item = jnp.dtype(dtype).itemsize
    chosen_bytes = chosen_rows * (dc + dr) * item

    def gather(layer, row_cache, picked):
        at = (layer, jnp.arange(b, dtype=jnp.int32)[:, None], 0, picked)
        return row_cache[at]

    chosen = gather(0, row_cache, picked)
    parts = {
        "index": (_over_layers(lambda i, q_i, w, i_cache, pos:
                               dsa.decode_index_scores(q_i, w, i_cache, pos,
                                                       i)),
                  (q_i, w, i_cache, pos), live * d * item),
        "select": (_over_layers(lambda i, scores: dsa.choose(
            scores + i.astype(jnp.float32), k)), (scores,),
            4 * live + 8 * chosen_rows),
        "gather": (_over_layers(gather), (row_cache, picked), chosen_bytes),
        "gather_in_row_order": (_over_layers(
            lambda i, row_cache, picked: gather(i, row_cache,
                                                jnp.sort(picked, axis=-1))),
            (row_cache, picked), chosen_bytes),
        "attend": (_over_layers(lambda i, q_c, q_pe, chosen, values:
                                dsa.attend_chosen(q_c, q_pe, chosen, values,
                                                  0.0625)),
                   (q_c, q_pe, chosen, values), chosen_bytes),
        "select_threshold": (_over_layers(lambda i, scores, pos: pk.dsa_select(
            scores + i.astype(jnp.float32), pos, k)), (scores, pos),
            4 * live + 8 * chosen_rows),
        "full_sweep": (_over_layers(lambda i, q_c, q_pe, c_cache, r_cache,
                                    pos: pk.latent_decode(
            q_c[:, :, 0], q_pe[:, :, 0], c_cache, r_cache, pos, scale=0.0625,
            layer=i)), (q_c, q_pe, c_cache, r_cache, pos),
            live * (dc + dr) * item),
    }
    for bk in blocks or (pk.dsa_attend_block(rows),):
        parts[f"attend_swept[{bk}]"] = (_over_layers(
            lambda i, q_c, q_pe, row_cache, kept, pos, bk=bk: pk.dsa_attend(
                q_c[:, :, 0], q_pe[:, :, 0], row_cache, kept, pos,
                scale=0.0625, layer=i, block_k=bk)),
            (q_c, q_pe, row_cache, kept, pos), chosen_bytes)
    out, us = [], {}
    for part, (fn, args, need) in parts.items():
        seconds = _time(fn, *args) / LAYERS
        us[part] = seconds * 1e6
        out.append({"part": part, "rows": rows, "frontier": frontier,
                    "us_per_layer": round(seconds * 1e6, 1),
                    "gb_per_s": round(need / seconds / 1e9, 1)})
    swept = min(v for p, v in us.items() if p.startswith("attend_swept"))
    for part, total in (
            ("gather_path", us["index"] + us["select"] + us["gather"]
             + us["attend"]),
            ("sweep_path", us["index"] + us["select_threshold"] + swept)):
        out.append({"part": part, "rows": rows, "frontier": frontier,
                    "us_per_layer": round(total, 1)})
    return out


def admit_rows(s: dict, bucket: int, dtype, blocks=()) -> list[dict]:
    h, d_qk, j, d, k = (s["heads"], s["d_qk"], s["index_heads"],
                        s["index_dim"], s["topk"])
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))

    def rand(shape, dt=dtype):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dt)

    q_i, w, k_i = (rand((1, j, bucket, d)), rand((1, bucket, j), jnp.float32),
                   rand((1, bucket, d)))
    q, kk, v = (rand((1, h, bucket, d_qk)) for _ in range(3))
    kernel = dsa.prefill_kernel_choice(bucket, d_qk, d_qk, d) == "kernel"
    select = jax.jit(lambda q_i, w, k_i: dsa.prefill_mask(
        q_i, w, k_i, k, kernel=kernel))
    mask = select(q_i, w, k_i)
    attend = jax.jit(lambda q, kk, v, mask: dsa.prefill_attend(
        q, kk, v, mask, scale=0.0625, kernel=kernel))
    strip = jax.jit(lambda q_s, w_s, k_i: dsa.chosen_mask(
        dsa.index_scores(q_s, w_s, k_i), k))
    pairs = bucket * (bucket + 1) // 2
    kept = min(k, bucket)
    attended = kept * (kept + 1) // 2 + (bucket - kept) * k
    rows = []
    variants = [(f"prefill_attend[{bq}x{bk}x{g}]", jax.jit(
        lambda q, kk, v, mask, bq=bq, bk=bk, g=g: pk.dsa_prefill_attend(
            q, kk, v, mask, scale=0.0625, block_q=bq, block_k=bk, group=g)),
        (q, kk, v, mask), 4.0 * h * d_qk * attended, 1)
        for bq, bk, g in blocks]
    for part, fn, args, flops, scale in [
            ("prefill_select", select, (q_i, w, k_i), 2.0 * j * d * pairs, 1),
            ("prefill_attend", attend, (q, kk, v, mask),
             4.0 * h * d_qk * attended, 1)] + variants + [
            ("sorted_strips", strip, (q_i[:, :, :dsa.STRIP],
                                      w[:, :dsa.STRIP], k_i),
             2.0 * j * d * pairs, bucket // dsa.STRIP)]:
        seconds = _time(fn, *args) * scale
        rows.append({"part": part, "bucket": bucket,
                     "us_per_layer": round(seconds * 1e6, 1),
                     "tflop_per_s": round(flops / seconds / 1e12, 2)})
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--frontier", default="2048,8192,16000",
                    help="rows every stream holds, or mix")
    ap.add_argument("--rows", default="",
                    help="the buffer's rows a stream (the served 16384)")
    ap.add_argument("--attend-block", default="",
                    help="rows a fetch of the swept attention brings")
    ap.add_argument("--buckets", default="4096,8192,16384")
    ap.add_argument("--attend-blocks", default="",
                    help="BQxBKxG,..: the masked sweep at other tiles")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--json-out")
    args = ap.parse_args(argv)
    from cake_tpu.utils.compile_cache import configure

    configure()
    refuse_offchip_record(args.json_out)
    shapes = TINY if args.tiny else SERVED
    dtype = jnp.float32 if args.tiny else jnp.bfloat16
    rows = []
    swept_blocks = [int(n) for n in args.attend_block.split(",") if n]
    for s in (int(n) for n in (args.rows or str(shapes["rows"])).split(",")):
        # as many streams as hold the served rows in all
        at = dict(shapes, rows=s, slots=max(
            1, shapes["slots"] * shapes["rows"] // s))
        for frontier in (f for f in args.frontier.split(",") if f):
            frontier = frontier if frontier == "mix" else int(frontier)
            if frontier == "mix" or frontier <= s:
                rows += decode_rows(at, frontier, dtype, swept_blocks)
    blocks = [tuple(int(n) for n in b.split("x"))
              for b in args.attend_blocks.split(",") if b]
    for bucket in (int(t) for t in args.buckets.split(",") if t):
        rows += admit_rows(shapes, min(bucket, shapes["rows"]), dtype,
                           blocks)
    for row in rows:
        print(json.dumps(row), flush=True)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"device": jax.devices()[0].device_kind, "rows": rows},
                      f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
