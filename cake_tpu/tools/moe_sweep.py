"""The expert block a layer: dense against sorted, from 8 rows to 16,384.

Times :func:`cake_tpu.ops.moe.moe_swiglu` in the two forms
:func:`cake_tpu.ops.moe.expert_form` chooses between for more than a
handful of pairs, as the layer loop calls it (a scan over ``L`` layers:
the dense form on the scan's slice of the stacks, the sorted form on the
whole stacks with the layer's index), at the eight expert cells' shapes
for 8 to 2048 rows (``--rows 4096,8192,16384`` for the long buckets of
the cells that have them): a decode step's rows (one a slot) and an
admission's buckets. Where the sorted form is at least 1.10x the dense
one is where the rule's constants come from: ``SORTED_MAX_HIT_SHARE*``
(the share of the experts a call of few rows may hit and still be
sorted) and
``SORTED_MIN_ROWS*`` (PERF.md keeps the table). ``--row-tile`` times the
sorted form at other row tiles of the kernel than the program's. The
sorted form's gather has forms of its own, side by side under ``--forms
onehot,fetch,take``: the live tiles' rows picked by a one-hot product, or
fetched by address (``ops.pallas.gather_rows``; ``sorted`` is whichever
``ops.moe.gather_form`` takes at the rows: where ``GATHER_FETCH_MIN_ROWS``
comes from), and XLA's ``take`` over ALL the pair rows as the control.
``--valid-share`` tells the block that this share of the rows, the
leading ones, are true tokens and the rest a bucket's padding
(``moe_swiglu``'s ``valid``; 1.0: a bucket that is full, told so), one
pass a share: what the sorted form saves of a bucket's padding, and what
being told costs a bucket that has none.

Usage:  python -m cake_tpu.tools.moe_sweep [--only NAME] [--rows 64,512]
            [--forms dense,sorted,compact,onehot,fetch,take]
            [--row-tile 32,128]
            [--valid-share 1.0,0.67] [--json-out PATH]
(``--json-out`` is refused off a TPU: interpreted kernels, no device times.)

Prints one JSON line per shape, row count, row tile and share:
``{"shape", "rows", "row_tile", "valid_share", "<form>_us_per_layer",
"<form>_roofline",
"<form>_moved_mb", "speedup"}``: a form's share of max(the chosen held
experts' bytes / 819 GB/s, the routed pairs' operations / 197 TFLOP/s)
(what the block needs whichever form computes it; the router's weights
give a near-uniform choice), and the bytes a layer's block moves BESIDE
the weights by its shapes (:func:`moved_bytes`: rows gathered, what lies
between the products, the results combined).
"""

from __future__ import annotations

import argparse
import contextlib
import itertools
import json
import sys
import time

import jax
import jax.numpy as jnp

from cake_tpu.ops import moe
from cake_tpu.ops import pallas as pk
from cake_tpu.ops.quant import QuantizedLinear
from cake_tpu.tools.kernel_check import refuse_offchip_record

# name -> (held, scored, top_k, hidden, width, int8, group routing)
SHAPES = {
    "mixtral8x7b-int8": (8, 8, 2, 4096, 14336, True, None),
    "axk1-ep16": (12, 192, 8, 7168, 2048, False, (8, 4)),
    "ling3flash-ep4": (128, 512, 8, 2560, 768, False, (8, 4)),
    # every scored expert held, no groups (the identity at 1 / 1)
    "lfm2-8b-a1b": (32, 32, 4, 2048, 1792, False, (1, 1)),
    "kexaone-ep8": (16, 128, 8, 6144, 2048, False, (1, 1)),
    "xing4-29b": (64, 64, 4, 3584, 1024, False, (1, 1)),
    # softmax over the chosen logits (Mixtral's convention)
    "mellum2-12b": (64, 64, 8, 2304, 896, False, None),
    "qwen3next-ep4": (128, 512, 10, 2048, 512, False, None),
    "glm5-ep16": (16, 256, 8, 6144, 2048, False, (1, 1)),
}
ROWS = (8, 16, 32, 64, 128, 256, 512, 1024, 2048)
LAYERS = 3
HBM_BYTES_S, BF16_FLOPS_S = 819e9, 197e12  # one v5e chip, published


def _weights(key, layers, held, scored, hidden, width, int8):
    keys = jax.random.split(key, 4)

    def stack(k, fan_in, fan_out):
        if int8:
            q = jax.random.randint(k, (layers, held, fan_in, fan_out), -127,
                                   128, jnp.int8)
            scale = jnp.full((layers, held, fan_out),
                             fan_in ** -0.5 / 64, jnp.float32)
            return QuantizedLinear(q=q, scale=scale)
        return (jax.random.normal(k, (layers, held, fan_in, fan_out),
                                  jnp.bfloat16) * fan_in ** -0.5)

    # logits of unit variance: sigmoid scores that do not saturate (ties
    # would all go to the lowest expert ids) and a near-uniform choice
    router = (jax.random.normal(keys[0], (layers, hidden, scored),
                                jnp.float32) * hidden ** -0.5
              ).astype(jnp.bfloat16)
    return (router, stack(keys[1], hidden, width),
            stack(keys[2], hidden, width), stack(keys[3], width, hidden))


@contextlib.contextmanager
def _steered(form: str, row_tile: int):
    """While a form is traced: the expert block takes it whatever the
    rows, at this row tile (``compact``: the sorted form with the live
    tiles' gather and sum kernels whatever share of the experts is held;
    ``onehot`` / ``fetch``: the sorted form with that gather whatever the
    rows; ``take``: with XLA's ``take`` over all the pair rows in the
    gather kernel's place). Steering in the tool: the program has no such
    knob."""
    real = (moe.expert_form, moe.compacts, moe.gather_form, pk.gather_rows,
            pk.MOE_ROW_TILE)
    moe.expert_form = lambda *a: "dense" if form == "dense" else "sorted"
    if form == "compact":
        moe.compacts = lambda *a: True
    if form in ("onehot", "fetch"):
        moe.gather_form = lambda *a: form
    if form == "take":
        pk.gather_rows = lambda x, token, tiles, **kw: jnp.take(
            x, token, axis=0)
    pk.MOE_ROW_TILE = row_tile
    try:
        yield
    finally:
        (moe.expert_form, moe.compacts, moe.gather_form, pk.gather_rows,
         pk.MOE_ROW_TILE) = real


def _layers_fn(form, name):
    held, scored, top_k, _, _, _, groups = SHAPES[name]
    routing = moe.GroupRouting(*groups, True, 2.5) if groups else None
    share = None if held == scored else (0, held)

    def fn(x, valid, router, w_gate, w_up, w_down):
        def body(acc, per_layer):
            if form == "dense":  # the stacks are the scan's slices
                r, g, u, d = per_layer
                y = moe.moe_swiglu(x, r, g, u, d, top_k, routing=routing,
                                   held=share, valid=valid)
            else:  # the whole stacks and an index
                r, i = per_layer
                y = moe.moe_swiglu(x, r, w_gate, w_up, w_down, top_k,
                                   routing=routing, held=share, layer=i,
                                   valid=valid)
            return acc + y, None

        xs = ((router, w_gate, w_up, w_down) if form == "dense" else
              (router, jnp.arange(router.shape[0], dtype=jnp.int32)))
        return jax.lax.scan(body, jnp.zeros_like(x), xs)[0]

    return jax.jit(fn)


def _time_us(form, name, rows, weights, row_tile, valid_share: float = 1.0,
             iters: int = 5) -> float:
    hidden = SHAPES[name][3]
    x = jax.random.normal(jax.random.PRNGKey(rows), (1, rows, hidden),
                          jnp.bfloat16)
    valid = jnp.asarray([round(rows * valid_share)], jnp.int32)
    fn = _layers_fn(form, name)
    with _steered(form, row_tile):
        jax.block_until_ready(fn(x, valid, *weights))  # trace and compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x, valid, *weights)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1e6 / (iters * LAYERS)


def floor_us(name: str, rows: int) -> float:
    """The least a layer's expert block can take at uniform routing: the
    bytes of the held experts some row chooses, once, or the arithmetic
    of the pairs routed here (rows x top-k x held / scored)."""
    held, scored, top_k, hidden, width, int8, _ = SHAPES[name]
    chosen = held * (1 - (1 - top_k / scored) ** rows)
    weight_bytes = 3 * chosen * hidden * width * (1 if int8 else 2)
    flops = 3 * 2 * hidden * width * rows * top_k * held / scored
    return max(weight_bytes / HBM_BYTES_S, flops / BF16_FLOPS_S) * 1e6


def moved_bytes(form: str, name: str, rows: int,
                row_tile: int = pk.MOE_ROW_TILE) -> int:
    """Bytes a layer's expert block moves beside the weights, by its
    shapes at uniform routing, each operand read once and each result
    written once. ``dense``: every held expert's gate, up, SwiGLU and down
    results over every row, and their weighted sum. ``sorted``: the LIVE
    row tiles alone (the pairs on held experts, rounded up to whole
    tiles): rows gathered from ``[rows, H]`` (``onehot``: the bucket read
    and the live rows written; ``fetch``: the bucket read and written as
    words, the live rows read and written; ``take``: every pair row read
    and written; ``sorted``: as :func:`cake_tpu.ops.moe.gather_form` has
    it at these rows), the SwiGLU's result in the rows' type (the row
    tile re-read a block of output columns), the down
    product in float32, and the sum into ``[rows, H]`` (where XLA sums,
    :func:`cake_tpu.ops.moe.compacts`, over a gathered copy of the
    product)."""
    held, scored, top_k, hidden, width, int8, _ = SHAPES[name]
    act = 2  # bfloat16 rows
    if form == "dense":
        between = held * rows * (3 * width + hidden) * act  # g, u, h, y
        return 2 * (rows * hidden * act + between)
    pairs = rows * top_k * held / scored
    live = min(-(-pairs // row_tile) * row_tile,
               -(-rows * top_k // row_tile) * row_tile)
    itemsize = 1 if int8 else 2
    gate_blocks = width // pk.moe._block_n(hidden, width, itemsize)
    down_blocks = hidden // pk.moe._block_n(width, hidden, itemsize)
    kernels = form == "compact" or moe.compacts(held, scored)
    gather = (form if form in ("onehot", "fetch", "take") else
              moe.gather_form(rows, hidden, jnp.bfloat16))
    if gather == "fetch" and not pk.rows_fetchable(hidden, jnp.bfloat16):
        gather = "onehot"  # what gather_rows falls to
    if not kernels:  # XLA's take of tiles that are all live moves as much
        gather = "onehot"
    gathered = {"onehot": rows * hidden * act + live * hidden * act,
                "fetch": 2 * rows * hidden * act + 2 * live * hidden * act,
                "take": 2 * rows * top_k * hidden * act}[gather]
    return int(
        gathered
        + gate_blocks * live * hidden * act + live * width * act  # SwiGLU
        + down_blocks * live * width * act + live * hidden * 4  # down
        + (0 if kernels else 2 * live * hidden * 4)  # XLA's gathered copy
        + live * hidden * 4 + rows * hidden * act)  # combined


def sweep(names, row_counts, forms, row_tiles, valid_shares=(1.0,)):
    """A row per shape, row count, row tile and share of true rows (the
    dense form has no tile and skips no padding: it is timed once a row
    count and stands in each tile's and share's row; the floor and the
    bytes moved are the whole bucket's at every share)."""
    for name in names:
        held, scored, _, hidden, width, int8, _ = SHAPES[name]
        weights = _weights(jax.random.PRNGKey(0), LAYERS, held, scored,
                           hidden, width, int8)
        for rows in row_counts:
            timed = {}
            for tile, share in itertools.product(row_tiles, valid_shares):
                row = {"shape": name, "rows": rows, "row_tile": tile,
                       "valid_share": share}
                for form in forms:
                    if form != "dense" or form not in timed:
                        timed[form] = _time_us(form, name, rows, weights,
                                               tile, share)
                    us = timed[form]
                    row[f"{form}_us_per_layer"] = round(us, 1)
                    row[f"{form}_roofline"] = round(
                        100 * floor_us(name, rows) / us, 1)
                    row[f"{form}_moved_mb"] = round(
                        moved_bytes(form, name, rows, tile) / 1e6, 2)
                if "dense" in timed and "sorted" in timed:
                    row["speedup"] = round(
                        timed["dense"] / timed["sorted"], 3)
                yield row
        del weights


def main() -> int:
    from cake_tpu.utils.compile_cache import configure

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--only", choices=sorted(SHAPES))
    ap.add_argument("--rows", type=lambda s: [int(v) for v in s.split(",")],
                    default=list(ROWS))
    ap.add_argument("--forms", type=lambda s: s.split(","),
                    default=["dense", "sorted"])
    ap.add_argument("--row-tile", type=lambda s: [int(v) for v in s.split(",")],
                    default=[pk.MOE_ROW_TILE])
    ap.add_argument("--valid-share", default=[1.0],
                    type=lambda s: [float(v) for v in s.split(",")],
                    help="the share of a call's rows that are true tokens")
    ap.add_argument("--json-out")
    a = ap.parse_args()
    configure()
    refuse_offchip_record(a.json_out)
    out = []
    for row in sweep([a.only] if a.only else list(SHAPES), a.rows, a.forms,
                     a.row_tile, a.valid_share):
        print(json.dumps(row), flush=True)
        out.append(row)
    if a.json_out:
        with open(a.json_out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
