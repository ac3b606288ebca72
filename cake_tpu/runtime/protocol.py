"""Message schema over the wire transport.

Equivalent of the reference's `Message` enum + `RawTensor`
(proto/message.rs:11-76): Hello / WorkerInfo / SingleOp / Batch / Tensor —
plus an explicit Error message (the reference just drops the connection,
worker.rs:180,256-258). The reference serializes with the Rust-specific
``bitcode`` (chosen over gRPC for speed, message.rs:104-105); here the
payloads are a fixed little-endian binary layout for tensors (schema below)
and JSON for the small control structures — language-neutral, zero-copy on
the tensor bytes, no codegen.

Tensor payload layout (little-endian):
  u8 dtype_code | u8 ndim | u32 dims[ndim] | raw bytes (C-order)

On-pod activations never use this path (they ride ICI inside the compiled
pipeline program); this is the cross-host control/data plane between the
master CLI and TPU-VM workers, where the reference's TCP semantics survive.
"""

from __future__ import annotations

import dataclasses
import json
import platform
import struct
from enum import IntEnum

import numpy as np

from cake_tpu import __version__
from cake_tpu.obs import metrics as _metrics


class MsgType(IntEnum):
    HELLO = 1
    WORKER_INFO = 2
    SINGLE_OP = 3
    BATCH = 4
    TENSOR = 5
    ERROR = 6
    GOODBYE = 7
    # Cluster-observability plane (capability-gated: the master only sends
    # these to a worker whose WorkerInfo.caps advertised them; an old
    # worker never sees them and an old master never sends them).
    PING = 8  # clock-offset probe: echo payload + worker perf_counter
    STATS = 9  # registry/status snapshot for workers without a status port


# WorkerInfo.caps entries — what this peer's wire dialect understands
# beyond the seed protocol. Old peers (no field in the handshake JSON)
# default to none of them, so every extension stays opt-in per connection.
CAP_TRACE = "trace"  # OPS trace-context trailer + span-digest replies
CAP_PING = "ping"  # MsgType.PING clock exchange
CAP_STATS = "stats"  # MsgType.STATS snapshot requests
ALL_CAPS = (CAP_TRACE, CAP_PING, CAP_STATS)


# dtype codes (u8). bf16 rides as raw uint16 payloads with its own code.
_DTYPES: list[tuple[int, str]] = [
    (0, "float32"),
    (1, "bfloat16"),
    (2, "float16"),
    (3, "int32"),
    (4, "int8"),
    (5, "uint8"),
    (6, "int64"),
]
_CODE_TO_NAME = {c: n for c, n in _DTYPES}
_NAME_TO_CODE = {n: c for c, n in _DTYPES}


def _np_dtype(name: str):
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def _dtype_code(arr: np.ndarray) -> int:
    name = arr.dtype.name if arr.dtype.name in _NAME_TO_CODE else str(arr.dtype)
    if name not in _NAME_TO_CODE:
        raise ValueError(f"unsupported wire dtype {arr.dtype}")
    return _NAME_TO_CODE[name]


def _contig(x) -> np.ndarray:
    arr = np.asarray(x)
    # (ascontiguousarray would promote 0-d to 1-d; only copy when needed)
    return arr if arr.flags["C_CONTIGUOUS"] else np.ascontiguousarray(arr)


def _buf(arr: np.ndarray):
    """Zero-copy byte memoryview over a C-contiguous array's storage (the
    uint8 reinterpret handles dtypes like bfloat16 whose buffer format
    memoryview.cast cannot)."""
    return arr.reshape(-1).view(np.uint8).data


def encode_tensor_parts(x) -> list:
    """numpy (or jax-convertible) array -> [header bytes, data buffer].

    The data part is a memoryview over the array's own storage when it is
    already contiguous — callers that can scatter-gather (wire.Connection
    hands a buffer sequence to ``sendmsg``) ship multi-MB activations with
    zero payload copies; ``encode_tensor`` joins the parts once for callers
    that need one bytes object."""
    arr = _contig(x)
    header = struct.pack("<BB", _dtype_code(arr), arr.ndim) + struct.pack(
        f"<{arr.ndim}I", *arr.shape
    )
    return [header, _buf(arr)]


def encode_tensor(x) -> bytes:
    """numpy (or jax-convertible) array -> wire bytes (one copy: the join;
    the reference's serializer copies per-field, message.rs:104-105)."""
    return b"".join(encode_tensor_parts(x))


def decode_tensor(buf: bytes) -> np.ndarray:
    code, ndim = struct.unpack_from("<BB", buf, 0)
    if code not in _CODE_TO_NAME:
        raise ValueError(f"unknown dtype code {code}")
    dims = struct.unpack_from(f"<{ndim}I", buf, 2)
    off = 2 + 4 * ndim
    dt = _np_dtype(_CODE_TO_NAME[code])
    expect = int(np.prod(dims)) * dt.itemsize if ndim else dt.itemsize
    data = buf[off:]
    if len(data) != expect:
        raise ValueError(
            f"tensor payload size {len(data)} != expected {expect} for "
            f"shape {dims} {dt}"
        )
    return np.frombuffer(data, dtype=dt).reshape(dims)


# -- activation wire codec ---------------------------------------------------
#
# Petals (Borzunov et al., 2022) showed activation compression is the
# enabling trick for pipeline inference over slow links; the reference ships
# raw full-precision tensors every token (llama.rs:100-119). Here the master
# negotiates a per-connection codec at handshake (WorkerInfo.codecs) and the
# worker mirrors whatever codec the request rode in. Encodings are
# self-describing: `none` is the plain tensor layout above (first byte is a
# dtype code < 0x80, so it stays wire-compatible with pre-codec peers);
# compressed layouts open with a marker byte >= 0x80.
#
#   bf16: 0x81 | u8 orig_dtype | tensor(bfloat16)          (~2x on f32)
#   int8: 0x82 | u8 orig_dtype | u8 ndim | u32 dims[ndim]
#         | f32 scales[rows] | i8 q[rows, last_dim]        (~4x on f32)
#
# int8 uses per-row symmetric absmax scales (a row = one token's hidden
# vector for [B, T, H] activations). Integer dtypes pass through as `none`
# under every codec (lossless; quantizing ids would corrupt them).

CODECS = ("none", "bf16", "int8")
_BF16_MARK, _INT8_MARK = 0x81, 0x82


def check_stream_width(config) -> None:
    """Refuse a model whose residual stream is several hidden vectors wide
    (``LlamaConfig.hc_mult`` > 1): the wire ships ``[B, T, hidden]`` between
    a topology's layer ranges (the int8 codec scales a row of ``hidden``
    values), and no range of such a model's layers has been compared with
    its reference behind it. The master and the worker ask before they
    plan a walk."""
    if config.hc_mult > 1:
        raise ValueError(
            f"hc_mult = {config.hc_mult}: a residual stream several hidden "
            "vectors wide is not wired across the wire (a topology that "
            "splits this model's layers ships [B, T, hidden] between its "
            "ranges); serve it on one host (--mode serve, no --topology)")


def check_codec(codec: str) -> str:
    """Validate a codec name (shared by the encoder, RemoteRunner, and
    Worker so the accepted set and the error live in one place)."""
    if codec not in CODECS:
        raise ValueError(f"unknown wire codec {codec!r} (know {CODECS})")
    return codec

# pre/post-compression payload bytes: the registry view of what the codec
# saves (flight records carry the per-call split via RemoteRunner.last_call)
_CODEC_RAW = _metrics.counter("wire.codec_bytes_raw")
_CODEC_ENC = _metrics.counter("wire.codec_bytes_encoded")


def encode_activation_parts(x, codec: str = "none") -> list:
    """Activation tensor -> buffer-sequence under ``codec`` (see module
    comment for layouts). Float inputs only compress; integer inputs ride
    the `none` layout regardless of codec."""
    check_codec(codec)
    arr = _contig(x)
    is_float = arr.dtype.kind == "f" or arr.dtype.name == "bfloat16"
    if codec == "none" or not is_float or (
        codec == "bf16" and arr.dtype.itemsize <= 2
    ):
        # 2-byte floats (bf16 itself, f16) gain nothing from the bf16
        # layout — same payload size, and an f16->bf16 cast would LOSE
        # mantissa bits; the none layout ships them verbatim
        parts = encode_tensor_parts(arr)
    elif codec == "bf16":
        import ml_dtypes

        orig = _dtype_code(arr)
        parts = [struct.pack("<BB", _BF16_MARK, orig)]
        parts += encode_tensor_parts(arr.astype(ml_dtypes.bfloat16))
    else:  # int8
        orig = _dtype_code(arr)
        f = np.asarray(arr, np.float32)
        rows = f.reshape(-1, f.shape[-1]) if f.ndim else f.reshape(1, 1)
        absmax = np.max(np.abs(rows), axis=1)
        scales = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
        q = np.clip(np.rint(rows / scales[:, None]), -127, 127).astype(
            np.int8
        )
        header = struct.pack("<BBB", _INT8_MARK, orig, arr.ndim)
        header += struct.pack(f"<{arr.ndim}I", *arr.shape)
        parts = [header, _buf(scales), _buf(q)]
    _CODEC_RAW.inc(arr.nbytes)
    _CODEC_ENC.inc(sum(len(p) for p in parts))
    return parts


def encode_activation(x, codec: str = "none") -> bytes:
    return b"".join(encode_activation_parts(x, codec))


def decode_activation(buf) -> tuple[np.ndarray, str]:
    """Self-describing inverse of :func:`encode_activation`. Returns the
    tensor (in its pre-compression dtype) and the codec it rode in, so a
    worker can mirror the master's choice in its reply."""
    buf = memoryview(buf)
    mark = buf[0]
    if mark < 0x80:
        return decode_tensor(buf), "none"
    if mark == _BF16_MARK:
        orig = _np_dtype(_CODE_TO_NAME[buf[1]])
        return decode_tensor(buf[2:]).astype(orig), "bf16"
    if mark == _INT8_MARK:
        orig_code, ndim = struct.unpack_from("<BB", buf, 1)
        dims = struct.unpack_from(f"<{ndim}I", buf, 3)
        off = 3 + 4 * ndim
        n_rows = int(np.prod(dims[:-1])) if ndim else 1
        last = dims[-1] if ndim else 1
        scales = np.frombuffer(buf, np.float32, count=n_rows, offset=off)
        q = np.frombuffer(buf, np.int8, offset=off + 4 * n_rows)
        if q.size != n_rows * last:
            raise ValueError(
                f"int8 activation payload {q.size} != expected "
                f"{n_rows * last} for shape {dims}"
            )
        x = (q.reshape(n_rows, last).astype(np.float32)
             * scales[:, None]).reshape(dims)
        return x.astype(_np_dtype(_CODE_TO_NAME[orig_code])), "int8"
    raise ValueError(f"unknown activation codec marker 0x{mark:02x}")


def _tensor_nbytes(buf) -> int:
    """Encoded length of the plain tensor layout at the head of ``buf``."""
    code, ndim = struct.unpack_from("<BB", buf, 0)
    if code not in _CODE_TO_NAME:
        raise ValueError(f"unknown dtype code {code}")
    dims = struct.unpack_from(f"<{ndim}I", buf, 2)
    n = int(np.prod(dims)) if ndim else 1
    return 2 + 4 * ndim + n * _np_dtype(_CODE_TO_NAME[code]).itemsize


def activation_nbytes(buf) -> int:
    """Byte length of the self-describing activation encoding at the head
    of ``buf`` — exactly what :func:`decode_activation` would consume. The
    seam that lets a frame carry an optional trailer AFTER the tensor
    (trace context on requests, span digests on replies) while the tensor
    layouts themselves stay byte-identical to pre-trailer peers."""
    buf = memoryview(buf)
    mark = buf[0]
    if mark < 0x80:
        return _tensor_nbytes(buf)
    if mark == _BF16_MARK:
        return 2 + _tensor_nbytes(buf[2:])
    if mark == _INT8_MARK:
        _, ndim = struct.unpack_from("<BB", buf, 1)
        dims = struct.unpack_from(f"<{ndim}I", buf, 3)
        n_rows = int(np.prod(dims[:-1])) if ndim else 1
        last = dims[-1] if ndim else 1
        return 3 + 4 * ndim + 4 * n_rows + n_rows * last
    raise ValueError(f"unknown activation codec marker 0x{mark:02x}")


def split_activation(buf) -> tuple[memoryview, dict | None]:
    """Split an activation payload into (tensor bytes, trailer dict). The
    trailer is whatever JSON follows the self-describing tensor encoding;
    a legacy frame has no leftover and yields ``None`` — the decode side
    needs no capability flag to stay compatible both directions."""
    buf = memoryview(buf)
    alen = activation_nbytes(buf)
    if len(buf) > alen:
        return buf[:alen], json.loads(bytes(buf[alen:]).decode())
    return buf, None


@dataclasses.dataclass
class WorkerInfo:
    """Capability/identity exchange (proto/message.rs:37-53): version, os,
    arch, device kind, latency (filled by the client from the handshake RTT,
    client.rs:41-47), dtype, plus the layers this worker serves."""

    name: str
    version: str = __version__
    os: str = dataclasses.field(default_factory=platform.system)
    arch: str = dataclasses.field(default_factory=platform.machine)
    device: str = ""
    # ordinal of the serving device within the worker process (the reference
    # carries the CUDA ordinal as `device_idx`, proto/message.rs:37-53)
    device_idx: int = 0
    dtype: str = ""
    latency_ms: float = 0.0
    layers: list[str] = dataclasses.field(default_factory=list)
    # KV capacity of this worker's caches; the master rejects a mismatch at
    # handshake (a silently smaller worker cache would clamp KV writes once
    # pos exceeds it and corrupt generation).
    max_seq: int = 0
    # Activation wire codecs this worker accepts (and will mirror in its
    # replies). Defaults to just "none" so a pre-codec peer — whose
    # handshake payload lacks the field — is never credited with
    # compression support it does not have.
    codecs: list[str] = dataclasses.field(default_factory=lambda: ["none"])
    # Wire-dialect extensions (CAP_*). Same old-peer rule as codecs: the
    # default is the empty set, so a peer is only ever sent PING/STATS or
    # trace trailers after it explicitly advertised them.
    caps: list[str] = dataclasses.field(default_factory=list)
    # Port of this worker's live status HTTP page (0 = none running). The
    # master's cluster scraper reaches it at the worker's connection host —
    # the fallback scrape path for a peer without CAP_STATS.
    status_port: int = 0

    def to_bytes(self) -> bytes:
        return json.dumps(dataclasses.asdict(self)).encode()

    @classmethod
    def from_bytes(cls, buf: bytes) -> "WorkerInfo":
        d = json.loads(buf.decode())
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    def __str__(self) -> str:
        return (
            f"{self.name}@{self.device or '?'}:{self.device_idx} "
            f"v{self.version} ({self.os}/{self.arch}, {self.dtype}, "
            f"latency {self.latency_ms:.1f}ms, {len(self.layers)} layers)"
        )


def encode_ops_parts(x, ops: list[tuple[str, int]], codec: str = "none",
                     trace_ctx: dict | None = None) -> list:
    """Batch payload as a buffer sequence: JSON op list (layer_name,
    index_pos) + codec-encoded activation tensor, plus an optional trace
    trailer.

    The reference `Batch` carries ``Vec<(layer_name, index_pos, block_idx)>``
    (message.rs:57-76); block_idx is recoverable from layer_name so the wire
    format carries just (name, pos).

    ``trace_ctx`` is the Dapper-style propagation record — ``{"tid":
    trace_id, "psid": parent_span_id, "seq": n, "pos": p}`` — appended as a
    JSON trailer after the self-describing tensor (CAP_TRACE peers only;
    with ``trace_ctx=None`` the frame is byte-identical to the legacy
    layout)."""
    meta = json.dumps(ops).encode()
    parts = [struct.pack("<I", len(meta)) + meta] + encode_activation_parts(
        x, codec
    )
    if trace_ctx is not None:
        parts.append(json.dumps({"tc": trace_ctx}).encode())
    return parts


def encode_ops(x: np.ndarray, ops: list[tuple[str, int]],
               codec: str = "none", trace_ctx: dict | None = None) -> bytes:
    return b"".join(encode_ops_parts(x, ops, codec, trace_ctx))


def decode_ops_traced(
    buf,
) -> tuple[np.ndarray, list[tuple[str, int]], str, dict | None]:
    """Inverse of :func:`encode_ops`, trailer included: returns
    ``(tensor, ops, codec, trailer)`` where the trailer is the parsed
    trace-context dict (``None`` on a legacy frame) and the codec name is
    what the request's tensor rode in (the worker mirrors it in the
    reply)."""
    buf = memoryview(buf)
    (mlen,) = struct.unpack_from("<I", buf, 0)
    ops = [tuple(o) for o in json.loads(bytes(buf[4 : 4 + mlen]).decode())]
    act, trailer = split_activation(buf[4 + mlen :])
    x, codec = decode_activation(act)
    return x, ops, codec, trailer


def decode_ops(buf) -> tuple[np.ndarray, list[tuple[str, int]], str]:
    """Trailer-blind :func:`decode_ops_traced` (the seed-era signature)."""
    x, ops, codec, _ = decode_ops_traced(buf)
    return x, ops, codec


class WorkerOpError(RuntimeError):
    """A worker-reported op failure (MsgType.ERROR reply). Deterministic
    model-side errors — distinct from transport failures (OSError /
    wire.WireError), which warrant reconnect+replay recovery; these do not
    (the same op would fail again after replay)."""


def encode_error(msg: str) -> bytes:
    return msg.encode()


def decode_error(buf: bytes) -> str:
    return buf.decode(errors="replace")
