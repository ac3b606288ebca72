"""Int8 weight quantization (per-output-channel, symmetric).

The reference runs f16/bf16 weights only (dtype plane, `cake/mod.rs:56-62`);
int8 is a capability the TPU build adds because it is load-bearing for the
70B-on-v5e-16 target (SURVEY.md §7: ~8.75 GB f16 weights + KV per 16 GB chip
leaves no headroom — int8 halves the weight bytes and decode is
HBM-bandwidth-bound, so it is also a throughput lever).

Scheme: symmetric per-output-channel absmax. For a weight ``w [in, out]``
(or stacked ``[L, in, out]``): ``scale = absmax(w, axis=in) / 127``,
``q = round(w / scale)`` in int8. Matmul dequantizes in the epilogue:
``y = (x @ q) * scale`` — the int8 weights stream from HBM at half the bf16
bytes and the MXU accumulates in f32 (on TPU via the Pallas kernel in
:mod:`cake_tpu.ops.pallas.quant`; elsewhere XLA fuses the int8→bf16 convert
into the dot).

Every linear site in the model goes through :func:`dense`, which accepts
either a plain array or a :class:`QuantizedLinear` — quantization is a pure
params-pytree transform (:func:`quantize_params`), no model code changes.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["q", "scale"],
    meta_fields=[],
)
@dataclasses.dataclass
class QuantizedLinear:
    """int8 weight + f32 per-output-channel scale.

    ``q: [..., in, out] int8``, ``scale: [..., out] f32`` (leading axes — the
    stacked layer axis — are shared)."""

    q: jax.Array
    scale: jax.Array


def quantize_linear(w: jax.Array) -> QuantizedLinear:
    """Symmetric per-output-channel int8 quantization of ``w [..., in, out]``."""
    wf = jnp.asarray(w, jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=-2)  # [..., out]
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(wf / scale[..., None, :]), -127, 127).astype(jnp.int8)
    return QuantizedLinear(q=q, scale=scale)


def quantize_linear_np(w) -> tuple:
    """Host-side (numpy) variant of :func:`quantize_linear` for quantize-
    during-load: the bf16 weight never reaches the device, so peak HBM is the
    int8 bytes, not bf16 + temporaries. Returns ``(q int8, scale f32)``."""
    import numpy as np

    wf = np.asarray(w, np.float32)
    absmax = np.max(np.abs(wf), axis=-2)
    scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.round(wf / scale[..., None, :]), -127, 127).astype(np.int8)
    # C-order outputs even when ``w`` is a transposed view (see the int4
    # twin below): raw-buffer serializers must never see F-ordered arrays
    return np.ascontiguousarray(q), np.ascontiguousarray(scale)


# Linear weight names eligible for quantization (norms/embed stay bf16; the
# embedding is a gather, not a matmul, and norm scales are tiny).
LAYER_LINEARS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
# the same for a layer of the latent-attention, shared-expert family (its
# router stays plain, like Mixtral's)
LATENT_LINEARS = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_gate", "w_up",
                  "w_down", "ws_gate", "ws_up", "ws_down")


def reject_int4_moe() -> None:
    """The ONE int4+MoE rejection, raised by every entry point (pytree
    quantize, random-init, both checkpoint loaders, the offline tool) so
    that wiring int4 expert packing later means deleting exactly one
    guard per site and this helper — no independently-worded copies to
    drift (the same single-source rule as tools' _LINEAR_SUFFIXES)."""
    raise NotImplementedError(
        "int4 MoE expert stacks are not wired (the nibble packing is 2D); "
        "use int8 for Mixtral-family quantization"
    )


def quantize_params(
    params: dict, bits: int = 8, group_size: int | None = None
) -> dict:
    """Quantize every linear in a params pytree (model or stage slice).

    Works on full params (embed/norm_f/lm_head + layers) and on bare stacked
    layer pytrees (a worker's slice). ``bits`` selects the tier: 8
    (:class:`QuantizedLinear`) or 4 (:class:`Quantized4Linear`, packed);
    ``group_size`` (int4 only) switches to group-wise scales along the in
    axis — the accuracy tier for real checkpoints."""
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    if group_size is not None and bits != 4:
        raise ValueError("group_size applies to bits=4 only")
    layer_tree = params.get("layers", params) if isinstance(params, dict) else {}
    if bits == 4 and isinstance(layer_tree, dict) and "router" in layer_tree:
        reject_int4_moe()
    if bits == 8:
        qfn = quantize_linear
    else:
        qfn = partial(quantize_linear4, group_size=group_size)
    out = dict(params)
    if "layers" in params and "wq" not in params["layers"]:
        # the latent family's two stacks (models/llama.py stack_shapes)
        if bits == 4:
            reject_int4_moe()
        out["layers"] = {
            name: {k: (qfn(v) if k in LATENT_LINEARS else v)
                   for k, v in stack.items()}
            for name, stack in params["layers"].items()}
    elif "layers" in params:
        out["layers"] = {
            k: (qfn(v) if k in LAYER_LINEARS else v)
            for k, v in params["layers"].items()
        }
    elif all(k in params for k in ("wq", "wo")):  # bare layer-stack pytree
        return {
            k: (qfn(v) if k in LAYER_LINEARS else v)
            for k, v in params.items()
        }
    if "lm_head" in params:
        out["lm_head"] = qfn(params["lm_head"])
    return out


def dequantize_linear(w: QuantizedLinear, dtype=jnp.bfloat16) -> jax.Array:
    return (w.q.astype(jnp.float32) * w.scale[..., None, :]).astype(dtype)


# ---------------------------------------------------------------------------
# int4 (packed) — half the int8 bytes again on the decode-dominating weight
# stream. Same per-output-channel symmetric scheme at absmax/7, values in
# [-7, 7], two values packed per int8 byte along the *in* (K) axis.
#
# Packing convention — ADJACENT pairs: byte i of ``qp [K/2, N]`` holds
# q(2i, n) in its low nibble and q(2i+1, n) in its high nibble. This makes
# the packed array **sharding-transparent on the K axis**: packed rows
# [a, b) always correspond to the contiguous original rows [2a, 2b), so a
# row-parallel (in-axis) tp shard of the globally packed weight is exactly
# the pack of that shard's slice. (A halves layout — k paired with
# k + K/2 — would pair rows living in different tp shards and silently
# break under parallel/mesh.py's in-axis partitioning.)
#
# The matmul splits the ACTIVATION instead, where striding is cheap
# (activations are M x K, weights are K x N):
#
#     y = x[:, 0::2] @ lo(qp) + x[:, 1::2] @ hi(qp)
#
# — both the XLA fallback and the Pallas kernel
# (ops/pallas/quant.py:quant4_matmul_pallas) use this form. Sign extension
# is pure arithmetic shifts: ``hi = p >> 4``, ``lo = (p << 4) >> 4``.
# ---------------------------------------------------------------------------


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["qp", "scale"],
    meta_fields=[],
)
@dataclasses.dataclass
class Quantized4Linear:
    """Packed int4 weight + f32 scales.

    ``qp: [..., in/2, out] int8`` (two nibbles per byte, adjacent-pair
    packing). ``scale`` is either ``[..., out]`` (per-output-channel) or
    ``[..., ngroups, out]`` (group-wise along the in axis, group size
    ``in / ngroups`` — the standard int4 accuracy fix; the tier is read
    off the scale's rank, no extra metadata)."""

    qp: jax.Array
    scale: jax.Array

    @property
    def group_size(self) -> int | None:
        """Group size along the in axis, or None for per-channel."""
        if self.scale.ndim == self.qp.ndim - 1:
            return None
        return 2 * self.qp.shape[-2] // self.scale.shape[-2]


def pack_int4(q: jax.Array) -> jax.Array:
    """Pack int4 values ``q [..., K, N]`` (in [-7, 7], any int dtype) into
    ``[..., K/2, N] int8`` with adjacent-pair nibble layout (byte i = rows
    2i low, 2i+1 high)."""
    k = q.shape[-2]
    if k % 2:
        raise ValueError(f"int4 packing needs an even in-dim, got {k}")
    q = q.astype(jnp.int8)
    lo = q[..., 0::2, :]
    hi = q[..., 1::2, :]
    return (lo & 0xF) | (hi << 4)


def unpack_int4(qp: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4`: ``[..., K/2, N] int8 -> [..., K, N]``
    int8 values in [-7, 7]."""
    lo = (qp << 4) >> 4
    hi = qp >> 4
    k2, n = qp.shape[-2], qp.shape[-1]
    return jnp.stack([lo, hi], axis=-2).reshape(*qp.shape[:-2], 2 * k2, n)


def quantize_linear4(
    w: jax.Array, group_size: int | None = None
) -> Quantized4Linear:
    """Symmetric int4 quantization of ``w [..., in, out]``.

    ``group_size=None``: one scale per output channel (absmax over the full
    in axis). ``group_size=G``: one scale per (G-row in-group, channel) —
    int4's dynamic range is 4 bits, so per-channel absmax wastes most of it
    on outlier rows; G of 64–128 recovers near-int8 fidelity (tested)."""
    wf = jnp.asarray(w, jnp.float32)
    k = wf.shape[-2]
    if group_size is None:
        absmax = jnp.max(jnp.abs(wf), axis=-2)  # [..., out]
        scale = jnp.where(absmax > 0, absmax / 7.0, 1.0)
        q = jnp.clip(jnp.round(wf / scale[..., None, :]), -7, 7)
        return Quantized4Linear(qp=pack_int4(q), scale=scale)
    if k % group_size or group_size % 2:
        raise ValueError(
            f"group_size {group_size} must be even and divide in-dim {k}"
        )
    g = k // group_size
    wg = wf.reshape(*wf.shape[:-2], g, group_size, wf.shape[-1])
    absmax = jnp.max(jnp.abs(wg), axis=-2)  # [..., g, out]
    scale = jnp.where(absmax > 0, absmax / 7.0, 1.0)
    q = jnp.clip(jnp.round(wg / scale[..., None, :]), -7, 7)
    q = q.reshape(*wf.shape[:-2], k, wf.shape[-1])
    return Quantized4Linear(qp=pack_int4(q), scale=scale)


def pack_int4_np(q) -> "np.ndarray":  # noqa: F821 — numpy is lazy here
    """Numpy twin of :func:`pack_int4` — THE one place the adjacent-pair
    nibble layout is written on the host side (the layout is load-bearing
    for tp sharding; a second hand-inlined copy could silently drift)."""
    lo = q[..., 0::2, :]
    hi = q[..., 1::2, :]
    return (lo & 0xF) | (hi << 4)


def quantize_linear4_np(w, group_size: int | None = None) -> tuple:
    """Host-side (numpy) variant of :func:`quantize_linear4` for quantize-
    during-load. Returns ``(qp int8 packed, scale f32)``."""
    import numpy as np

    wf = np.asarray(w, np.float32)
    k = wf.shape[-2]
    if k % 2:
        raise ValueError(f"int4 packing needs an even in-dim, got {k}")
    if group_size is None:
        absmax = np.max(np.abs(wf), axis=-2)
        scale = np.where(absmax > 0, absmax / 7.0, 1.0).astype(np.float32)
        q = np.clip(np.round(wf / scale[..., None, :]), -7, 7).astype(np.int8)
    else:
        if k % group_size or group_size % 2:
            raise ValueError(
                f"group_size {group_size} must be even and divide "
                f"in-dim {k}"
            )
        g = k // group_size
        wg = wf.reshape(*wf.shape[:-2], g, group_size, wf.shape[-1])
        absmax = np.max(np.abs(wg), axis=-2)
        scale = np.where(absmax > 0, absmax / 7.0, 1.0).astype(np.float32)
        q = np.clip(np.round(wg / scale[..., :, None, :]), -7, 7)
        q = q.reshape(*wf.shape[:-2], k, wf.shape[-1]).astype(np.int8)
    # elementwise ops inherit the INPUT's memory order: quantizing a
    # transposed view (the loaders pass w.T) yields F-ordered outputs,
    # which raw-buffer serializers (safetensors) would scramble
    return (np.ascontiguousarray(pack_int4_np(q)),
            np.ascontiguousarray(scale))


def parse_quant_spec(spec: str | None) -> tuple[str | None, int | None]:
    """Parse a quantize spec string into ``(tier, group_size)``.

    ``None`` → ``(None, None)``; ``"int8"``/``"int4"`` → per-channel;
    ``"int4:gN"`` → int4 with N-row groups along the in axis. The spec
    string is what rides the CLI ``--quantize`` flag and every loader's
    ``quantize=`` parameter, so the grouped tier needs no extra plumbing.
    (Loading a pre-quantized grouped ``.q4`` checkpoint needs only
    ``"int4"`` — the stored scale's shape carries the grouping.)"""
    if spec is None:
        return None, None
    if spec in ("int8", "int4"):
        return spec, None
    import re

    m = re.fullmatch(r"int4:g(\d+)", spec)
    if m and int(m.group(1)) > 0:
        return "int4", int(m.group(1))
    raise ValueError(
        f"unsupported quantize spec {spec!r} (want int8, int4, or int4:gN "
        f"with N >= 1)"
    )


def dequantize_linear4(w: Quantized4Linear, dtype=jnp.bfloat16) -> jax.Array:
    q = unpack_int4(w.qp).astype(jnp.float32)
    if w.group_size is None:
        return (q * w.scale[..., None, :]).astype(dtype)
    k, n = q.shape[-2], q.shape[-1]
    g = w.scale.shape[-2]
    qg = q.reshape(*q.shape[:-2], g, k // g, n) * w.scale[..., :, None, :]
    return qg.reshape(*q.shape[:-2], k, n).astype(dtype)


def quant4_matmul_xla(
    x: jax.Array, qp: jax.Array, scale: jax.Array
) -> jax.Array:
    """Fallback path. Per-channel (``scale [out]``): even/odd two-dot
    formulation — each shift-unpack chain feeds its dot directly (the
    weight side never interleaves); the strided slices touch only the small
    activation operand. Grouped (``scale [ngroups, out]``): per-group
    batched dot with the scale applied to the f32 partials before the
    group-sum, so quantization error never crosses group boundaries."""
    if scale.ndim == qp.ndim:  # grouped
        k2, n = qp.shape[-2], qp.shape[-1]
        g = scale.shape[-2]
        # f32 operands: the batched-dot thunk on CPU cannot mix
        # bf16 x bf16 -> f32, and f32 partials match the kernel's
        # accumulation; this fallback trades speed for fidelity (the hot
        # grouped path is the Pallas kernel)
        wg = unpack_int4(qp).astype(jnp.float32).reshape(
            g, (2 * k2) // g, n)
        xg = x.astype(jnp.float32).reshape(
            *x.shape[:-1], g, (2 * k2) // g)
        partial = jnp.einsum("...gk,gkn->...gn", xg, wg)
        return (partial * scale).sum(axis=-2).astype(x.dtype)
    w_lo = ((qp << 4) >> 4).astype(x.dtype)
    w_hi = (qp >> 4).astype(x.dtype)
    y = jnp.dot(
        x[..., 0::2], w_lo, preferred_element_type=jnp.float32
    ) + jnp.dot(x[..., 1::2], w_hi, preferred_element_type=jnp.float32)
    return (y * scale).astype(x.dtype)


def quant_matmul_xla(x: jax.Array, q: jax.Array, scale: jax.Array) -> jax.Array:
    """Fallback path: XLA fuses the int8→x.dtype convert into the dot."""
    y = jnp.dot(x, q.astype(x.dtype), preferred_element_type=jnp.float32)
    return (y * scale).astype(x.dtype)


# Trace-time backend pin (see pinned_impl). None = per-shape measured gate.
# A ContextVar, not a module global: two serving instances with different
# pins may dispatch (and therefore trace) from different threads
# concurrently — a plain global could bake the WRONG pin into another
# instance's jit cache for its whole lifetime.
_PINNED: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "cake_quant_pinned", default=None)


def pinned() -> str | None:
    """The active backend pin in this context (None = measured gate)."""
    return _PINNED.get()


@contextlib.contextmanager
def pinned_impl(impl: str | None):
    """Pin ``quant_matmul``'s auto dispatch for the dynamic extent.

    The measured m>=16 crossover gate picks the backend per SHAPE, so the
    same stream's logits can differ in their low-order bits between batch
    -size buckets or between prefix-hit and prefix-miss admission prefills
    (different row counts -> different backend), which with temperature > 0
    can flip a near-boundary sampled token. A serving instance closes that
    by tracing every one of its programs under one pinned backend
    (runtime/batch_generator.py) — the pin only needs to surround the jit
    CALLS (tracing happens on first call), and it overrides the
    interpret-mode default too so CPU tests exercise the same invariance.
    ``"pallas"`` still falls back to XLA when the kernels are disabled or
    the shape is not tileable (a pin must never crash a program the gate
    would have run)."""
    token = _PINNED.set(impl)
    try:
        yield
    finally:
        _PINNED.reset(token)


def quant_matmul(
    x: jax.Array,  # [..., in]
    q: jax.Array,  # [in, out] int8
    scale: jax.Array,  # [out] f32
    impl: str = "auto",
) -> jax.Array:
    from cake_tpu.ops import pallas as pk

    if impl == "auto":
        pin = _PINNED.get()
        if pin is not None:
            # instance-lifetime pin (pinned_impl): one backend for every
            # shape this trace sees; tileability still guards the kernel
            impl = (
                "pallas"
                if pin == "pallas"
                and pk.kernels_enabled()
                and (
                    pk.interpret_default()
                    or (q.shape[0] % 256 == 0 and q.shape[1] % 256 == 0)
                )
                else "xla"
            )
        else:
            # The compiled kernel needs enough rows to tile the MXU; skinny
            # inputs (single-stream and small-batch decode) run XLA's gemv
            # path. From 16 rows the kernel's int8-in-VMEM streaming is
            # expected to win; the crossover has not been measured on the
            # chip tool.
            m = x.size // x.shape[-1]
            impl = (
                "pallas"
                if pk.kernels_enabled()
                and (
                    pk.interpret_default()
                    or (
                        m >= 16
                        and q.shape[0] % 256 == 0
                        and q.shape[1] % 256 == 0
                    )
                )
                else "xla"
            )
    if impl == "pallas":
        from cake_tpu.ops.pallas.quant import quant_matmul_pallas

        lead_shape = x.shape[:-1]
        y = quant_matmul_pallas(x.reshape(-1, x.shape[-1]), q, scale)
        return y.reshape(*lead_shape, q.shape[1])
    return quant_matmul_xla(x, q, scale)


def quant4_matmul(
    x: jax.Array,  # [..., in]
    qp: jax.Array,  # [in/2, out] int8 packed
    scale: jax.Array,  # [out] or [ngroups, out] f32
    impl: str = "auto",
) -> jax.Array:
    """int4 twin of :func:`quant_matmul` — same pin/auto dispatch contract.

    The auto gate reuses the int8 m>=16 crossover as its prior (the kernels
    share the streaming structure); the int4 frontier has not been measured
    on the chip tool (tools/int4_sweep times it)."""
    from cake_tpu.ops import pallas as pk

    k2, n = qp.shape[-2], qp.shape[-1]
    # grouped scales cap the K block at half a group — the gate checks the
    # unit the kernel will actually tile. 128 is the Mosaic lane width: a
    # smaller K block would make the activation BlockSpec's last dim
    # sub-lane and fail to lower on a real TPU, so the gate must guarantee
    # bk2 >= 128 (the pin contract: never crash a program the gate would
    # have run). Grouped at group_size=128 (g2=64) therefore runs XLA.
    kunit = k2 // scale.shape[-2] if scale.ndim == qp.ndim else k2
    tileable = kunit % 128 == 0 and n % 256 == 0
    if impl == "auto":
        pin = _PINNED.get()
        if pin is not None:
            impl = (
                "pallas"
                if pin == "pallas"
                and pk.kernels_enabled()
                and (pk.interpret_default() or tileable)
                else "xla"
            )
        else:
            # Unlike int8 (where XLA's gemv fuses the convert and wins below
            # m=16), the int4 XLA fallback cannot fuse the shift-unpack into
            # the dot: it re-materializes bf16 weights every step — 4x the
            # packed bytes, i.e. the bf16 rate (not measured on the chip
            # tool). The kernel (with sublane M-padding) streams the packed
            # bytes, so tileability is the only gate.
            impl = (
                "pallas"
                if pk.kernels_enabled()
                and (pk.interpret_default() or tileable)
                else "xla"
            )
    if impl == "pallas":
        from cake_tpu.ops.pallas.quant import quant4_matmul_pallas

        lead_shape = x.shape[:-1]
        y = quant4_matmul_pallas(x.reshape(-1, x.shape[-1]), qp, scale)
        return y.reshape(*lead_shape, n)
    return quant4_matmul_xla(x, qp, scale)


def out_features(w) -> int:
    """Output width of a linear weight (plain or quantized)."""
    if isinstance(w, QuantizedLinear):
        return w.q.shape[-1]
    if isinstance(w, Quantized4Linear):
        return w.qp.shape[-1]
    return w.shape[-1]


def dense(x: jax.Array, w) -> jax.Array:
    """``x @ w`` for a plain array, :class:`QuantizedLinear`, or
    :class:`Quantized4Linear` — the single dispatch point every linear in
    the model routes through."""
    if isinstance(w, QuantizedLinear):
        return quant_matmul(x, w.q, w.scale)
    if isinstance(w, Quantized4Linear):
        return quant4_matmul(x, w.qp, w.scale)
    return x @ w
