"""Flash-attention crossover sweep: pallas vs XLA across context lengths.

Measures the compiled flash kernels against the reference-math XLA oracle
(`cake-core/src/model/attention.rs:62-77` f32-scores convention) over a grid
of (T, S) shapes at Llama-3-8B attention geometry, to pick the context-length
crossover used by :func:`cake_tpu.ops.attention.attend`'s ``impl="auto"``
dispatch — the same measured-crossover treatment ``quant_matmul`` got for its
M>=16 gate (`ops/quant.py`).

Usage:  python -m cake_tpu.tools.flash_sweep [--json-out PATH]
            [--only served-decode|served-latent|latent-admit|one-row|narrow]
(``--json-out`` is refused off a TPU: interpreted kernels, no device times.)

Prints one JSON line per shape:
  {"path": "prefill"|"decode", "t", "s", "pallas_ms", "xla_ms", "speedup"}

``--only served-decode`` runs just :func:`served_decode_rows` and
:func:`served_latent_rows`: the decode kernels against XLA on the stacked
cache the layer loop carries, at the served shapes and frontiers (what
decides ``DECODE_FLASH_MIN_S`` and ``DECODE_BLOCK_K``, and for the latent
cache ``ops.mla.LATENT_DECODE_MIN_S``; the tables stand beside those
constants). ``--only served-latent`` runs the latent rows alone;
``--only latent-admit`` the plain latent ADMISSION's own-chunk attention in
its three forms (:func:`latent_admit_rows`: what decides
``ops.mla.LATENT_ADMIT_BLOCK_MIN_T``);
``--only one-row`` the decode kernel's two forms (the heads' products a head
at a time, or in one batched call) at 128- to 512-row blocks on the rows of
heads with ONE query row a KV head (``ONE_ROW_SHAPES``: what decides
``ops.pallas.ONE_ROW_BLOCK_K`` and ``ONE_ROW_FLASH_MIN_S``); ``--only
narrow`` the same two forms at 128- to 512-row blocks over heads of 64, two
to a lane tile (``NARROW_SHAPES``: ``ops.pallas.NARROW_BLOCK_K`` and
``NARROW_FLASH_MIN_S``).
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
    shape (the policy functions themselves, at the sweep's D = 128) and
    the resulting speedup over always-XLA (>= 1.0 everywhere is the
    dispatch-policy contract)."""
    from cake_tpu.ops.attention import (_flash_prefill_choice,
                                        flash_decode_choice)

    auto = (_flash_prefill_choice(rec["t"], rec["s"], 128)
            if rec["path"].startswith("prefill")
            else flash_decode_choice(rec["s"], 128, kv_heads=8, group=4))
    rec["auto_impl"] = auto
    rec["auto_speedup"] = rec["speedup"] if auto == "flash" else 1.0
    return rec


def _served_frontiers(batch: int, seed: int = 5):
    """Per-row frontiers as the ``decode-full`` mix leaves them: a prompt
    log-uniform in 64-512 and a uniform share of an answer of 64-192."""
    import numpy as np

    rng = np.random.default_rng(seed)
    prompt = np.exp(rng.uniform(np.log(64), np.log(512), batch))
    answer = rng.uniform(64, 192, batch) * rng.uniform(0, 1, batch)
    return (prompt + answer).astype(np.int32)


# (batch, rows, q heads, KV heads, head size) of served_decode_rows: the
# dense and sparse cells' (8 slots x 2048 and x 4096), shorter windows
# (where the floor is), a batch of 32, one stream (the single-stream
# generators' scalar frontier), the local heads of a tp=2 mesh, and the
# rows of heads whose 512-row blocks overflow VMEM (an MHA 7B's 32 x 128,
# Gemma-7B's 16 x 256: the kernel shrinks its blocks, auto leaves them on
# XLA), and the looped cell's multi-head row (KVH 16 x G 1: 512-row blocks
# at exactly the VMEM budget) at its own 6 slots x 768 rows and at 2048
SERVED_DECODE_SHAPES = (
    (8, 2048, 32, 8, 128), (8, 4096, 32, 8, 128), (8, 1024, 32, 8, 128),
    (8, 512, 32, 8, 128), (32, 2048, 32, 8, 128),
    (1, 2048, 32, 8, 128), (1, 4096, 32, 8, 128),
    (8, 2048, 16, 4, 128),
    (8, 2048, 32, 32, 128), (8, 2048, 16, 16, 256),
    (6, 768, 16, 16, 128), (8, 2048, 16, 16, 128),
)


# walks over the layers in one dispatch of _layer_step: with one, a call's
# dispatch sets a floor of ~30-40 us under every line (8 layers a call)
WALKS = 16


def _layer_step(fn, layers: int):
    """``WALKS`` passes over ``layers`` layers, jitted: ``fn(q, pos, layer,
    *cache) -> q-shaped`` with the layer index traced, so no layer's rows
    stay in fast memory between calls. The frontiers are data: one
    compile times them all."""
    @jax.jit
    def step(q, pos, *cache):
        def body(q, layer):
            out = fn(q, pos, layer, *cache)
            return q + (out * 1e-30).astype(q.dtype), None

        order = jnp.tile(jnp.arange(layers, dtype=jnp.int32), WALKS)
        return jax.lax.scan(body, q, order)[0]

    return step


def _layer_ms(step, q, pos, layers: int, *cache, iters: int = 10) -> float:
    """A layer's milliseconds inside :func:`_layer_step`'s walks."""
    import time

    from cake_tpu.tools.kernel_check import _sync

    _sync(step(q, pos, *cache))  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        q = step(q, pos, *cache)
    _sync(q)
    return (time.perf_counter() - t0) / (iters * layers * WALKS) * 1e3


# the rows of SERVED_DECODE_SHAPES with ONE query row a KV head (G == 1):
# ``--only one-row`` times both forms of the kernel at every block on them
ONE_ROW_SHAPES = tuple(shape for shape in SERVED_DECODE_SHAPES
                       if shape[2] == shape[3])
# heads HALF a lane tile wide under a group of query rows (``--only
# narrow``): the cell lfm2-8b-a1b-cut's 32 slots x 2048 rows of 32 / 8 heads
# (Llama-3.2-1B's row of heads too), shorter and longer windows (where the
# floor is), 8 slots, and the rows of two more public decoders (TinyLlama's
# 32 / 4, Qwen2.5-0.5B's 14 / 2)
NARROW_SHAPES = (
    (32, 2048, 32, 8, 64), (32, 1024, 32, 8, 64), (32, 512, 32, 8, 64),
    (32, 4096, 32, 8, 64), (8, 2048, 32, 8, 64),
    (8, 2048, 32, 4, 64), (8, 2048, 14, 2, 64),
)
# flash_decode's ``batched``: the heads' products a head at a time, or in
# one batched call (None: the form the kernel takes itself)
FORMS = {None: "", False: "loop_", True: "batched_"}


def served_decode_rows(results: list, blocks=(128, 256, 512, 1024),
                       layers: int = 8, shapes=SERVED_DECODE_SHAPES,
                       forms=(None,), every_block: bool = False) -> None:
    """Decode (T == 1) on the STACKED cache ``[L, B, KVH, S, D]`` as a
    decode step meets it: one pass over ``layers`` layers, each attending
    its own slice with the layer index traced (so no layer's keys stay
    in fast memory between calls, which a loop over ONE layer's buffer
    allows XLA and which no model does); times are a layer's. The kernel
    at each block size that fits its VMEM (128 rows only where 512 do
    not, or with ``every_block``) against XLA's masked sweep of the
    layer's slice, at ``shapes``, over frontiers early (64, 300), at row
    703 (what a ``decode-full`` stream fills at most), mixed as
    ``decode-full`` draws them, and at the buffer's end (the cost side:
    nothing to skip). ``forms``: the kernel's forms to time (``FORMS``)."""
    from cake_tpu.ops import kvcache as kv
    from cake_tpu.ops.attention import _attend_xla, flash_decode_choice
    from cake_tpu.ops.pallas import (DECODE_BLOCK_K, decode_block_k,
                                     flash_decode, interpret_default)

    compiled = not interpret_default()
    ks = jax.random.split(jax.random.PRNGKey(0), 3)

    def xla(q, pos, layer, k, v):
        return _attend_xla(q, kv.layer_view(k, layer),
                           kv.layer_view(v, layer), pos)

    def kernel(bk, batched):
        def run(q, pos, layer, k, v):
            return flash_decode(q, k, v, pos, layer=layer, block_k=bk,
                                interpret=not compiled, batched=batched)
        return run

    for b, s, h, kvh, d in shapes:
        k = jax.random.normal(ks[0], (layers, b, kvh, s, d), jnp.bfloat16)
        v = jax.random.normal(ks[1], (layers, b, kvh, s, d), jnp.bfloat16)
        q = jax.random.normal(ks[2], (b, h, 1, d), jnp.bfloat16)
        fit = decode_block_k(s, kvh, d, 2, h // kvh)
        xla_step = _layer_step(xla, layers)
        steps = {f"pallas_{FORMS[form]}bk{bk}_ms":
                 _layer_step(kernel(bk, form), layers)
                 for form in forms for bk in blocks
                 if decode_block_k(s, kvh, d, 2, h // kvh, bk) == bk
                 and (bk >= 256 or fit < DECODE_BLOCK_K or every_block)}
        frontiers = {"64": 64, "300": 300, "703": 703, "end": s - 1,
                     "mixed": _served_frontiers(b)}
        for name, at in frontiers.items():
            pos = jnp.minimum(jnp.broadcast_to(jnp.asarray(at, jnp.int32),
                                               (b,)), s - 1)
            rec = {"path": "decode_stacked", "batch": b, "s": s,
                   "heads": h, "kv_heads": kvh, "d": d,
                   "layers": layers, "frontier": name,
                   "auto_impl": flash_decode_choice(s, d, kvh, h // kvh),
                   "xla_ms": round(_layer_ms(xla_step, q, pos, layers, k, v),
                                   4)}
            for key, step in steps.items():
                rec[key] = round(_layer_ms(step, q, pos, layers, k, v), 4)
            results.append(rec)
            print(json.dumps(rec), flush=True)


# (batch, rows, heads) of served_latent_rows at kv_lora_rank 512 + rope 64:
# the cells axk1-ep16-cut (64 heads) and ling3flash-ep4-cut (32) at their
# 32 slots x 4096 rows, and shorter windows (where the floor is)
SERVED_LATENT_SHAPES = (
    (32, 4096, 64), (32, 4096, 32), (32, 2048, 64), (32, 2048, 32),
    (32, 1024, 64), (32, 1024, 32),
)


def served_latent_rows(results: list, blocks=(256, 512, 1024),
                       layers: int = 8, dc: int = 512, dr: int = 64) -> None:
    """The latent decode step's absorbed sweep on the STACKED latent cache
    ``[L, B, 1, S, dc]`` + ``[L, B, 1, S, dr]``, as
    :func:`served_decode_rows` times the GQA one: the kernel
    (``ops/pallas/latent.py``) at each block size against XLA's two masked
    einsums over the layer's slice (``ops.mla.masked_sweep``), at
    ``SERVED_LATENT_SHAPES``, over the same frontiers; ``end`` is the cost
    side (XLA reads the ``c`` buffer twice, the kernel once, and nothing
    is skipped)."""
    from cake_tpu.ops import kvcache as kv
    from cake_tpu.ops.mla import latent_decode_choice, masked_sweep
    from cake_tpu.ops.pallas import interpret_default, latent_decode

    compiled = not interpret_default()
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    scale = (128 + dr) ** -0.5

    for b, s, h in SERVED_LATENT_SHAPES:
        c = jax.random.normal(ks[0], (layers, b, 1, s, dc), jnp.bfloat16)
        r = jax.random.normal(ks[1], (layers, b, 1, s, dr), jnp.bfloat16)
        q_pe = jax.random.normal(ks[2], (b, h, 1, dr), jnp.bfloat16)
        q = jax.random.normal(ks[3], (b, h, 1, dc), jnp.bfloat16)

        def xla(q_c, pos, layer, c, r):
            valid = (jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, s), 3)
                     <= pos[:, None, None, None])
            m, p, o_c = masked_sweep(
                q_c, q_pe, kv.layer_view(c, layer)[:, 0],
                kv.layer_view(r, layer)[:, 0], valid, scale)
            return o_c / jnp.sum(p, axis=-1, keepdims=True) + m

        def kernel(bk):
            def run(q_c, pos, layer, c, r):
                m, l, o_c = latent_decode(
                    q_c[:, :, 0], q_pe[:, :, 0], c, r, pos, scale=scale,
                    layer=layer, block_k=bk, interpret=not compiled)
                return o_c / l + m
            return run

        xla_step = _layer_step(xla, layers)
        steps = {bk: _layer_step(kernel(bk), layers)
                 for bk in blocks if bk <= s}
        frontiers = {"64": 64, "300": 300, "703": 703, "end": s - 1,
                     "mixed": _served_frontiers(b)}
        for name, at in frontiers.items():
            pos = jnp.minimum(jnp.broadcast_to(jnp.asarray(at, jnp.int32),
                                               (b,)), s - 1)
            rec = {"path": "latent_decode_stacked", "batch": b, "s": s,
                   "heads": h, "dc": dc, "dr": dr, "layers": layers,
                   "frontier": name,
                   "auto_impl": latent_decode_choice(s, dc, dr),
                   "xla_ms": round(_layer_ms(xla_step, q, pos, layers, c, r),
                                   4)}
            for bk, step in steps.items():
                ms = _layer_ms(step, q, pos, layers, c, r)
                rec[f"pallas_bk{bk}_ms"] = round(ms, 4)
            results.append(rec)
            print(json.dumps(rec), flush=True)


# (rows a chunk, heads) of latent_admit_rows at nope 128 + rope 64 keys and
# 128-wide values: LongCat-Flash's and A.X-K1's heads, a first chunk of one
# stream (the engine admits a prompt a bucket at a time)
LATENT_ADMIT_SHAPES = ((512, 64), (1024, 64), (2048, 64), (4096, 64),
                       (8192, 64))
# the whole form's float32 scores [H, T, T] past this do not fit beside the
# rest of a 16 GiB chip (17 GB at 8192 rows): not timed
_WHOLE_MAX_T = 4096


def latent_admit_rows(results: list, shapes=LATENT_ADMIT_SHAPES,
                      dn: int = 128, dr: int = 64, dv: int = 128) -> None:
    """A plain latent admission's own-chunk attention (``ops/mla.py``, a
    first chunk of ``T`` rows, ``B`` 1, bf16) in its three forms on the
    SAME expanded operands: ``whole`` (float32 scores ``[H, T, T]`` in one
    piece: the form under ``LATENT_ADMIT_BLOCK_MIN_T``), ``strip`` (a
    strip of query rows at a time), ``flash`` (the flash prefill kernel
    over the expanded keys, zero-padded 192 -> 256, values 128 wide). Each
    normalised to ``[1, H, T, dv]``; ms a call."""
    from cake_tpu.ops import mla
    from cake_tpu.ops.pallas import interpret_default

    compiled = not interpret_default()
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    scale = (dn + dr) ** -0.5

    def whole(*own):
        m, l, o = mla.chunk_whole(*own, scale)
        return (o / l).astype(jnp.bfloat16)

    def strip(*own):
        return mla._strips(*own, scale).astype(jnp.bfloat16)

    def flash(*own):
        return mla.chunk_flash(*own, scale)

    for t, h in shapes:
        own = (jax.random.normal(ks[0], (1, h, t, dn), jnp.bfloat16),
               jax.random.normal(ks[1], (1, h, t, dr), jnp.bfloat16),
               jax.random.normal(ks[2], (1, h, t, dn), jnp.bfloat16),
               jax.random.normal(ks[3], (1, 1, t, dr), jnp.bfloat16),
               jax.random.normal(ks[4], (1, h, t, dv), jnp.bfloat16))
        rec = {"path": "latent_admit", "t": t, "heads": h,
               "d_qk": dn + dr, "d_v": dv,
               "auto_impl": mla.latent_admit_choice(t, dn + dr)}
        outs = {}
        for name, fn in (("whole", whole), ("strip", strip),
                         ("flash", flash)):
            if name == "whole" and t > _WHOLE_MAX_T and compiled:
                continue
            try:
                f = jax.jit(fn)
                outs[name] = f(*own)
                # (the timing loop keeps every call's output: few a
                # dispatch at thousands of rows)
                rec[f"{name}_ms"] = round(_time_ms(
                    f, *own, iters=5, inner=4 if t >= 2048 else 16), 4)
            except Exception as e:  # a form the chip's compiler refuses
                rec[f"{name}_error"] = f"{type(e).__name__}: {e}"[:300]
        base = outs.get("strip")
        for name, out in outs.items():
            if base is not None and name != "strip":
                rec[f"{name}_max_abs_diff_from_strip"] = float(jnp.max(
                    jnp.abs(out.astype(jnp.float32)
                            - base.astype(jnp.float32))))
        results.append(rec)
        print(json.dumps(rec), flush=True)


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

    # Decode: T=1 against ONE layer's KV buffer of S, one stream.
    # Frontier-near-the-end rows are the worst case (both read nearly
    # everything); the early-frontier rows in a long window are where the
    # kernel reads KV blocks only up to the frontier while XLA's fused
    # gemv sweeps the whole buffer. (A loop over one layer's buffer lets
    # XLA keep a small one in fast memory between calls: the served
    # shapes are measured over a walk of layers, served_decode_rows.)
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

    served_decode_rows(results)
    served_latent_rows(results)

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
    # grows with S/W.
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
        rec = _audit({"path": "decode_win4096", "t": 1, "s": s,
                      "pallas_ms": round(p_ms, 4), "xla_ms": round(x_ms, 4),
                      "speedup": round(x_ms / p_ms, 3)})
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
    ap.add_argument("--only", choices=["served-decode", "served-latent",
                                       "latent-admit", "one-row", "narrow"],
                    default=None,
                    help="run one section instead of the whole sweep")
    args = ap.parse_args()
    refuse_offchip_record(args.json_out)
    if args.only:
        rows: list = []
        if args.only == "one-row":
            served_decode_rows(rows, blocks=(128, 256, 384, 512),
                               shapes=ONE_ROW_SHAPES, forms=(False, True))
        elif args.only == "narrow":
            served_decode_rows(rows, blocks=(128, 256, 512),
                               shapes=NARROW_SHAPES, forms=(False, True),
                               every_block=True)
        elif args.only == "latent-admit":
            latent_admit_rows(rows)
        else:
            if args.only == "served-decode":
                served_decode_rows(rows)
            served_latent_rows(rows)
        if args.json_out:
            with open(args.json_out, "w") as f:
                json.dump(rows, f, indent=1)
    else:
        sweep(args.json_out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
