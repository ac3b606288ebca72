"""The state-space recurrence: the Pallas kernels against XLA's forms.

Times, over the stacked float32 state ``[L, B, d_state, d_inner]`` carried
and donated as the layer loop carries it (a step is a scan over the ``L``
layers):

- one decode step of every state-space layer in the two forms
  :func:`cake_tpu.ops.mamba.ssm_decode_choice` chooses between (XLA's
  fusions of ``ssm_step``; the kernel ``ssm_decode`` at several slot and
  channel blocks), at the served shape and around it. What decides whether
  the kernel stays: 1.10x over XLA at the cell's shape (B 64, 26 layers;
  PERF.md keeps the table);
- one admission chunk of one stream in three forms: the token-by-token
  ``lax.scan`` (``ssm_recurrence``), ``lax.associative_scan`` inside chunks
  of 64 tokens (it holds a chunk's ``[64, d_state, d_inner]`` products),
  and the kernel ``ssm_scan`` with a block of channels' state in VMEM.

Usage:  python -m cake_tpu.tools.ssm_sweep [--only decode|scan] [--json-out PATH]
(``--json-out`` is refused off a TPU: interpreted kernels, no device times.)

Prints one JSON line per shape and form: ``{"what", "batch" | "tokens",
"layers", "form", "us_per_layer", "speedup" (over XLA's form),
"hbm_share"}`` (the share: the bytes the call must move over 819 GB/s over
its time; the scan is bound by the vector unit, so its share reads low).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp

from cake_tpu.ops.mamba import ssm_recurrence, ssm_step
from cake_tpu.ops.pallas.mamba import (ssm_decode, ssm_decode_bytes, ssm_scan,
                                       ssm_scan_bytes)
from cake_tpu.tools.kernel_check import refuse_offchip_record

N, C = 16, 5120  # the published d_state and d_inner
DECODE_SHAPES = ((64, 26), (32, 26), (8, 26), (1, 26))  # (batch, layers)
DECODE_BLOCKS = ((8, 640), (8, 1280), (8, 2560), (8, 5120))  # (slots, chans)
SCAN_TOKENS = (64, 256, 512)
SCAN_BLOCKS = ((512, 64), (1280, 64), (2560, 64), (5120, 64), (1280, 128))  # (chans, toks)
ASSOC_CHUNK = 64
STEPS = 8  # a block's steps in one program, as the engine dispatches them
HBM = 819e9


def _inputs(b, t, n_layers):
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    x = jax.random.normal(ks[0], (b, t, C), jnp.float32)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (b, t, C)) - 4.0)
    bm = jax.random.normal(ks[2], (b, t, N), jnp.float32)
    cm = jax.random.normal(ks[3], (b, t, N), jnp.float32)
    a = -jnp.broadcast_to(jnp.arange(1.0, N + 1)[:, None], (N, C))
    d = jnp.ones((C,), jnp.float32)
    state = jax.random.normal(ks[6], (n_layers, b, N, C), jnp.float32)
    return state, (x, delta, bm, cm, a, d)


def ssm_assoc(x, delta, bm, cm, a, d_skip, state, chunk: int = ASSOC_CHUNK):
    """The recurrence by ``lax.associative_scan`` inside chunks of
    ``chunk`` tokens (``(decay, input)`` pairs compose as ``(a2 a1, a2 b1 +
    b2)``), a ``lax.scan`` carrying the state between chunks."""
    b, t, c = x.shape
    k = min(chunk, t)
    assert t % k == 0, (t, k)

    def chunks(v):  # [B, T, ..] -> [T / k, k, B, ..]
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape((t // k, k) + v.shape[1:])

    def body(s, xs):
        xc, dc, bc, cc = xs  # [k, B, .]
        decay = jnp.exp(dc[:, :, None, :] * a)  # [k, B, N, C]
        inp = (dc * xc)[:, :, None, :] * bc[..., None]
        cum, acc = jax.lax.associative_scan(
            lambda l, r: (r[0] * l[0], r[0] * l[1] + r[1]), (decay, inp))
        states = cum * s + acc
        y = jnp.sum(states * cc[..., None], axis=2) + d_skip * xc
        return states[-1], y

    state, y = jax.lax.scan(body, state,
                            tuple(chunks(v) for v in (x, delta, bm, cm)))
    return jnp.moveaxis(y.reshape(t, b, c), 0, 1), state


def _all_layers(form, steps, state, x, delta, bm, cm, a, d):
    """``steps`` calls over all ``L`` layers, the state carried."""
    decode = x.shape[1] == 1

    def layer(carry, i):
        state, acc = carry
        if form[0] == "kernel" and decode:
            y, state = ssm_decode(x[:, 0], delta[:, 0], bm[:, 0], cm[:, 0],
                                  a, d, state, i, slot_block=form[1],
                                  chan_block=form[2])
            y = y[:, None]
        elif form[0] == "kernel":
            y, state = ssm_scan(x, delta, bm, cm, a, d, state, i,
                                chan_block=form[1], token_block=form[2])
        else:
            if decode:
                y, s = ssm_step(x[:, 0], delta[:, 0], bm[:, 0], cm[:, 0], a,
                                d, state[i])
                y = y[:, None]
            else:
                fn = ssm_assoc if form[0] == "assoc" else ssm_recurrence
                y, s = fn(x, delta, bm, cm, a, d, state[i])
            state = jax.lax.dynamic_update_index_in_dim(state, s, i, 0)
        return (state, acc + y), None

    def step(carry, _):
        carry, _ = jax.lax.scan(layer, carry,
                                jnp.arange(state.shape[0], dtype=jnp.int32))
        return carry, None

    (state, acc), _ = jax.lax.scan(step, (state, jnp.zeros_like(x)), None,
                                   length=steps)
    return state, acc


def _time_us(form, b, t, n_layers, steps, iters: int = 8) -> float:
    """Microseconds a layer and call, the state donated between calls."""
    state, args = _inputs(b, t, n_layers)
    fn = jax.jit(partial(_all_layers, form, steps), donate_argnums=(0,))
    state, acc = fn(state, *args)  # compile
    jax.block_until_ready(acc)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, acc = fn(state, *args)
    jax.block_until_ready((state, acc))
    return (time.perf_counter() - t0) * 1e6 / (iters * steps * n_layers)


def _row(what, size, n_layers, form, us, xla_us, need):
    return {"what": what, **size, "layers": n_layers,
            "form": "-".join(str(f) for f in form),
            "us_per_layer": round(us, 2), "speedup": round(xla_us / us, 3),
            "hbm_share": round(100 * need / HBM * 1e6 / us, 1)}


def decode_rows():
    for b, n_layers in DECODE_SHAPES:
        need = ssm_decode_bytes(b, N, C)
        xla = _time_us(("xla",), b, 1, n_layers, STEPS)
        yield _row("decode", {"batch": b}, n_layers, ("xla",), xla, xla, need)
        for bb, cb in DECODE_BLOCKS:
            if b % bb and bb != 8:
                continue
            us = _time_us(("kernel", bb, cb), b, 1, n_layers, STEPS)
            yield _row("decode", {"batch": b}, n_layers, ("kernel", bb, cb),
                       us, xla, need)


def scan_rows(n_layers: int = 13):
    for t in SCAN_TOKENS:
        need = ssm_scan_bytes(1, t, N, C)
        xla = _time_us(("xla",), 1, t, n_layers, 1)
        yield _row("scan", {"tokens": t}, n_layers, ("xla",), xla, xla, need)
        us = _time_us(("assoc",), 1, t, n_layers, 1)
        yield _row("scan", {"tokens": t}, n_layers, ("assoc",), us, xla, need)
        for cb, tb in SCAN_BLOCKS:
            us = _time_us(("kernel", cb, tb), 1, t, n_layers, 1)
            yield _row("scan", {"tokens": t}, n_layers, ("kernel", cb, tb),
                       us, xla, need)


def main() -> int:
    from cake_tpu.utils.compile_cache import configure

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--only", choices=["decode", "scan"])
    ap.add_argument("--json-out")
    a = ap.parse_args()
    configure()
    refuse_offchip_record(a.json_out)
    out = []
    for what, rows in (("decode", decode_rows), ("scan", scan_rows)):
        if a.only in (None, what):
            for row in rows():
                print(json.dumps(row), flush=True)
                out.append(row)
    if a.json_out:
        with open(a.json_out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
