"""The delta rule's two forms, timed: the decode step (the Pallas kernel
against XLA's fusions) and, with ``--chunk``, an admission's chunk form.

Times one decode step of every delta-rule layer of a model (the state
``[L, B, H, d, d]`` float32, carried and donated as the layer loop
carries it; a step is a scan over the ``L`` layers) in the two forms
:func:`cake_tpu.ops.kda.kda_decode_choice` chooses between, at the served
shape and around it. What decides whether the kernel stays: 1.10x over
XLA at the cell's shape (B 32, 6 layers; PERF.md keeps the table).

``--chunk`` times :func:`cake_tpu.ops.kda.kda_chunk` a layer (a scan over
``CHUNK_LAYERS`` layers' states a dispatch, as an admission walks them) at
the two delta-rule cells' shapes, for each size of the diagonal blocks its
inverse starts from (``--blocks``; 0 leaves ``ops.kda.INVERSE_BLOCK`` as
the checkout has it, which is how a tree without the constant is timed):
where that constant comes from (PERF.md keeps the table). The chunk form
is entered as a layer enters it (``ops.kda._advance`` with each row's
true length): ``--live-share`` makes that length a share of the tokens,
the rest a bucket's padding (1.0: a bucket that is full, told so), one
pass a share: what a scan that stops at the last live chunk saves, and
what its bound costs a bucket that has no padding. ``--forms`` times
the serial scan as the ``jnp`` loop (``xla``) and as the Pallas kernel
(``kernel``: a decay a head alone, once a head block of ``--head-block``),
whatever :func:`cake_tpu.ops.kda.kda_chunk_choice` would choose: where
``ops.kda.KDA_SCAN_MIN_T`` and ``ops.pallas.kda.SCAN_HEAD_BLOCK`` come
from. (Both forms return ``o`` here, as the rule leaves it; the kernel's
epilogue, a layer's norm and gate, is the served cell's to judge.)

Usage:  python -m cake_tpu.tools.kda_sweep [--chunk [--blocks 8,16,32]
                                           [--live-share 1.0,0.67]
                                           [--forms xla,kernel
                                            [--head-block 8,16]]]
                                           [--json-out PATH]
(``--json-out`` is refused off a TPU: interpreted kernels, no device times.)

Prints one JSON line per shape: ``{"batch", "layers", "heads", "d",
"head_block", "xla_us_per_layer", "kernel_us_per_layer", "speedup",
"kernel_hbm_share"}`` (the share: one read and one write of the state and
the step's vectors over 819 GB/s over the kernel's time); with ``--chunk``
``{"decay", "batch", "tokens", "live_share", "key_heads", "heads", "d",
"block", "form", "head_block", "us_per_layer", "us_per_chunk"}`` (a chunk:
one of the bucket's, live or not; ``head_block``: the kernel's, else
null).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp

from cake_tpu.ops import kda
from cake_tpu.ops.kda import kda_step
from cake_tpu.ops.pallas import kda as pallas_kda
from cake_tpu.ops.pallas.kda import kda_decode, kda_decode_bytes
from cake_tpu.tools.kernel_check import refuse_offchip_record

SHAPES = (  # (batch, layers, heads, d): the cell's first
    (32, 6, 32, 128), (48, 6, 32, 128), (8, 6, 32, 128), (1, 6, 32, 128),
    (48, 6, 16, 128))
HEAD_BLOCKS = (8, 16, 32)
STEPS = 8  # a block's steps in one program, as the engine dispatches them
# (decay, batch, tokens, key heads, value heads, d): qwen3next-ep4-cut's
# admission buckets, then ling3flash-ep4-cut's one- and two-row launches
CHUNK_SHAPES = tuple(("scalar", 1, t, 16, 32, 128)
                     for t in (256, 512, 1024, 2048, 4096, 8192)) + tuple(
    ("channel", b, t, 32, 32, 128) for b in (1, 2) for t in (128, 256, 512))
CHUNK_LAYERS = 6  # delta-rule layers of either cell


def _step_all_layers(form, state, q, k, v, g, beta):
    """``STEPS`` decode steps over all ``L`` layers, the state carried."""
    def layer(carry, i):
        state, acc = carry
        if form == "xla":
            o, s = kda_step(q, k, v, g, beta, state[i])
            state = jax.lax.dynamic_update_index_in_dim(state, s, i, 0)
        else:
            o, state = kda_decode(q, k, v, g, beta, state, i,
                                  head_block=form)
        return (state, acc + o), None

    def step(carry, _):
        carry, _ = jax.lax.scan(layer, carry,
                                jnp.arange(state.shape[0], dtype=jnp.int32))
        return carry, None

    (state, acc), _ = jax.lax.scan(step, (state, jnp.zeros_like(v)), None,
                                   length=STEPS)
    return state, acc


def _call_us(fn, state, args, iters: int) -> float:
    """Microseconds a call of ``fn(state, *args) -> (state, acc)`` once it
    has compiled, the state donated from call to call (``iters`` calls
    timed together, three times)."""
    state, acc = fn(state, *args)  # compile
    jax.block_until_ready(acc)
    best = float("inf")
    for _ in range(3):  # the best of three: one stall is not the form's
        t0 = time.perf_counter()
        for _ in range(iters):
            state, acc = fn(state, *args)
        jax.block_until_ready((state, acc))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6 / iters


def _time_us(form, b, n_layers, h, d, iters: int = 10) -> float:
    """Microseconds a layer and step, the state donated between calls."""
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k, v = (jax.random.normal(kk, (b, h, d), jnp.float32) * d ** -0.5
               for kk in keys[:3])
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(keys[3], (b, h, d)) - 4.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, h)))
    state = jax.random.normal(keys[5], (n_layers, b, h, d, d), jnp.float32)
    fn = jax.jit(partial(_step_all_layers, form), donate_argnums=(0,))
    return _call_us(fn, state, (q, k, v, g, beta), iters) / (STEPS * n_layers)


def rows():
    for b, n_layers, h, d in SHAPES:
        xla = _time_us("xla", b, n_layers, h, d)
        for hb in HEAD_BLOCKS:
            if hb > h:
                continue
            kernel = _time_us(hb, b, n_layers, h, d)
            floor_us = kda_decode_bytes(b, h, d, d) / 819e9 * 1e6
            yield {"batch": b, "layers": n_layers, "heads": h, "d": d,
                   "head_block": hb, "xla_us_per_layer": round(xla, 2),
                   "kernel_us_per_layer": round(kernel, 2),
                   "speedup": round(xla / kernel, 3),
                   "kernel_hbm_share": round(100 * floor_us / kernel, 1)}


def _chunk_us(decay, b, t, hk, hv, d, live_share: float = 1.0,
              iters: int = 5) -> float:
    """Microseconds a layer of ``kda_chunk`` over ``t`` tokens of which
    each row's leading ``live_share`` are true, the layers' states carried
    and donated as an admission carries them."""
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k = (kda._l2norm(jax.random.normal(kk, (b, t, hk, d), jnp.float32))
            * scale for kk, scale in zip(keys[:2], (d ** -0.5, 1.0)))
    v = jax.random.normal(keys[2], (b, t, hv, d), jnp.float32)
    g = -jax.nn.softplus(jax.random.normal(
        keys[3], (b, t, hv) + ((d,) if decay == "channel" else ())) - 2.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, hv)))
    state = jax.random.normal(keys[5], (CHUNK_LAYERS, b, hv, d, d),
                              jnp.float32)
    valid = jnp.full((b,), round(t * live_share), jnp.int32)

    def layers(state, q, k, v, g, beta, valid):
        def layer(acc, s0):
            o, s1 = kda._advance(q, k, v, g, beta, s0, valid, None, "kda")
            return acc + o, s1

        acc, state = jax.lax.scan(layer, jnp.zeros_like(v), state)
        return state, acc

    fn = jax.jit(layers, donate_argnums=(0,))
    return _call_us(fn, state, (q, k, v, g, beta, valid),
                    iters) / CHUNK_LAYERS


def chunk_rows(blocks, live_shares=(1.0,), forms=("xla",),
               head_blocks=(0,)):
    """``forms``: who runs a scalar shape's serial scan (a decay a channel
    has the ``jnp`` loop alone); ``head_blocks``: the kernel's value heads
    a grid step (0: the checkout's)."""
    choice, head_block = kda.kda_chunk_choice, pallas_kda.SCAN_HEAD_BLOCK
    settings = [(block, form, hb) for block in blocks for form in forms
                for hb in (head_blocks if form == "kernel" else (0,))]
    try:
        for block, form, hb in settings:
            # the three are read when a layer is traced (the choice
            # whatever the backend is), and a trace kept for these shapes
            # holds the old ones
            if block:
                kda.INVERSE_BLOCK = block
            kda.kda_chunk_choice = (
                lambda t, dk, dv, scalar, form=form:
                form if scalar else "xla")
            pallas_kda.SCAN_HEAD_BLOCK = hb or head_block
            jax.clear_caches()
            for decay, b, t, hk, hv, d in CHUNK_SHAPES:
                if form == "kernel" and decay != "scalar":
                    continue
                for share in live_shares:
                    us = _chunk_us(decay, b, t, hk, hv, d, share)
                    yield {"decay": decay, "batch": b, "tokens": t,
                           "live_share": share, "key_heads": hk,
                           "heads": hv, "d": d, "block": block or None,
                           "form": form,
                           "head_block": (min(hv, hb or head_block)
                                          if form == "kernel" else None),
                           "us_per_layer": round(us, 1),
                           "us_per_chunk": round(us / -(-t // kda.CHUNK), 2)}
    finally:
        kda.kda_chunk_choice = choice
        pallas_kda.SCAN_HEAD_BLOCK = head_block


def main() -> int:
    from cake_tpu.utils.compile_cache import configure

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chunk", action="store_true",
                    help="time the admission's chunk form, not the step")
    ap.add_argument("--blocks", default="8,16,32",
                    help="--chunk: diagonal block sizes (0: the checkout's)")
    ap.add_argument("--live-share", default="1.0",
                    help="--chunk: the share of each row's tokens that "
                         "are true, the rest a bucket's padding")
    ap.add_argument("--forms", default="xla,kernel",
                    help="--chunk: who runs the serial scan")
    ap.add_argument("--head-block", default="0",
                    help="--chunk: the kernel's value heads a grid step "
                         "(0: the checkout's)")
    ap.add_argument("--json-out")
    a = ap.parse_args()
    configure()
    refuse_offchip_record(a.json_out)
    out = []
    for row in (chunk_rows([int(x) for x in a.blocks.split(",")],
                           [float(x) for x in a.live_share.split(",")],
                           a.forms.split(","),
                           [int(x) for x in a.head_block.split(",")])
                if a.chunk else rows()):
        print(json.dumps(row), flush=True)
        out.append(row)
    if a.json_out:
        with open(a.json_out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
