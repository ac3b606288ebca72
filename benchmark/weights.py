"""Seeded random checkpoints, streamed to disk: what every architecture's
writer shares (``arch/<arch>.py`` names the tensors and their sizes).

Numpy and the standard library only: the parent of a chip run calls this
and may never import JAX. The files are plain safetensors (8-byte header
length, JSON header, raw little-endian bytes), written by hand so that
bf16 needs no extension type. Two layouts:

- ``q8``: every linear as ``<hf_name>.q8`` (int8, torch ``[out, in]``) +
  ``<hf_name>.scale`` (float32 ``[out]``); unquantized tensors (a
  router, the norms, the embedding) in float32.
- ``bf16``: every tensor in bfloat16, linears as ``[out, in]``.

Every value is a uniform int8 times a scale, so it costs one pass of a
counter-based generator and no quantization, and every unquantized
tensor (all of the bf16 layout) is exact in bfloat16: the server's cast
to its serving type loses nothing, and the float32 reference reads the
same numbers. A linear's scale differs per output channel, so a loader
that mixed up channels would show in the reference comparison.

A sparse model's routing is made robust to rounding (``routing_*``
below): with plain random weights the gap between the last expert chosen
and the first left out is often under the error of a bfloat16 matmul, the
server and the float32 reference then send some token to different
experts, and their log-probabilities part by half a nat for the rest of
the sequence (measured on the chip, PR 23), which says nothing about
either. So the first ``E`` channels of the residual stream belong to the
router: the embedding writes ``ROUTE_MARK`` into ``top_k`` of them, chosen
by the token's id, and zero into the others; no linear writes to them
(``linear(..., zero_rows=E)`` for whatever writes the residual stream);
the router's row ``e`` reads channel ``e`` alone. A token's experts are
then a function of its id, uniform over the pairs, with a margin no
rounding crosses; every other channel and all the arithmetic are as
random as before, and the server does the same work.

A writer draws tensor ``i`` of a file from ``SFC64([seed, file_index,
i])``: the bytes depend only on (sizes, layout, seed), not on the number
of threads.
"""

from __future__ import annotations

import json
import math
import struct
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

_ROWS = 2048  # output channels converted at a time (bf16 layout)
ROUTE_MARK = 4.0  # what the embedding writes into a token's routing channels


def hf_config(cfg: dict, keys) -> dict:
    """The config.json the server is given: the model's own keys of a
    configuration file, which its architecture lists (``HF_KEYS``)."""
    return {k: cfg[k] for k in keys if k in cfg and cfg[k] is not None}


def pow2_scale(std: float) -> float:
    """The power of two that brings a uniform int8 (std 73.3) nearest to
    ``std``."""
    return 2.0 ** round(math.log2(std / 73.3))


def int8(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` uniform int8 in [-127, 127] (-128 folded onto -127: the
    symmetric convention of the .q8 layout)."""
    words = rng.integers(0, 2**64, size=(n + 7) // 8, dtype=np.uint64)
    q = words.view(np.int8)[:n]
    np.maximum(q, -127, out=q)
    return q


def bf16_bits(x: np.ndarray) -> np.ndarray:
    """float32 values that are exact in bfloat16 -> their 16 bits."""
    return (np.ascontiguousarray(x, np.float32).view(np.uint32)
            >> 16).astype(np.uint16)


class File:
    """One safetensors file, written tensor by tensor."""

    def __init__(self, path: Path):
        self.path, self.header, self.parts, self.offset = path, {}, [], 0

    def add(self, name: str, dtype: str, shape, data: np.ndarray) -> None:
        n = data.nbytes
        self.header[name] = {"dtype": dtype, "shape": list(shape),
                             "data_offsets": [self.offset, self.offset + n]}
        self.parts.append(data)
        self.offset += n

    def write(self) -> tuple[str, list[str], int]:
        head = json.dumps(self.header).encode()
        head += b" " * (-len(head) % 8)
        with open(self.path, "wb") as f:
            f.write(struct.pack("<Q", len(head)))
            f.write(head)
            for p in self.parts:
                f.write(memoryview(np.ascontiguousarray(p)).cast("B"))
        names = list(self.header)
        self.parts = []
        return self.path.name, names, self.offset


def routing_channels(ids: np.ndarray, experts: int, top_k: int) -> np.ndarray:
    """[len(ids), top_k] distinct channels (= experts) for each token id."""
    base = ids % experts
    step = 1 + (ids // experts) % (experts - 1)
    ch = np.stack([(base + j * step) % experts for j in range(top_k)], -1)
    if any(len(set(row)) < top_k for row in ch[: experts * experts]):
        raise ValueError(f"no {top_k} distinct routing channels of {experts}")
    return ch


def routing_embed(embed: np.ndarray, experts: int, top_k: int) -> None:
    """Give the first ``experts`` channels of the embedding to the router."""
    ids = np.arange(embed.shape[0])
    embed[:, :experts] = 0.0
    for col in routing_channels(ids, experts, top_k).T:
        embed[ids, col] = ROUTE_MARK


def linear(out_file: File, rng, layout: str, name: str, fan_in: int,
            out: int, zero_rows: int = 0) -> None:
    """One linear ``[out, in]`` with std ~ 1/sqrt(fan_in); its first
    ``zero_rows`` output channels write nothing (the router's channels)."""
    q = int8(rng, out * fan_in).reshape(out, fan_in)
    q[:zero_rows] = 0
    # q8: a scale per output channel, 0.75 .. 1.25 of the base in
    # eighths. bf16: the power-of-two base alone, so that q * base is
    # exact in bfloat16
    base = pow2_scale(1.0 / math.sqrt(fan_in))
    if layout == "q8":
        steps = rng.integers(6, 11, size=out).astype(np.float32)
        out_file.add(f"{name}.q8", "I8", (out, fan_in), q)
        out_file.add(f"{name}.scale", "F32", (out,),
                     (steps * np.float32(base / 8.0)).astype(np.float32))
        return
    bits = np.empty((out, fan_in), np.uint16)
    for lo in range(0, out, _ROWS):
        w = q[lo:lo + _ROWS].astype(np.float32)
        w *= np.float32(base)
        bits[lo:lo + _ROWS] = bf16_bits(w)
    out_file.add(name, "BF16", (out, fan_in), bits)


def plain(out_file: File, layout: str, name: str, values: np.ndarray):
    """A tensor kept unquantized: float32 in the q8 layout, bfloat16 in
    the bf16 layout. ``values`` are exact in bfloat16."""
    if layout == "q8":
        out_file.add(name, "F32", values.shape,
                     np.ascontiguousarray(values, np.float32))
    else:
        out_file.add(name, "BF16", values.shape, bf16_bits(values))


def norm(rng, h: int) -> np.ndarray:
    # 0.875 .. 1.25 in eighths: exact in bfloat16, not all ones, so that
    # a norm weight applied twice or not at all shows
    return rng.integers(7, 11, size=h).astype(np.float32) / np.float32(8)


def small(rng, shape, std: float) -> np.ndarray:
    n = int(np.prod(shape))
    return (int8(rng, n).astype(np.float32)
            * np.float32(pow2_scale(std))).reshape(shape)


def rngs(seed: int, file_index: int):
    """The generators of one file's tensors, in the order they are drawn:
    tensor ``i`` of file ``file_index`` has ``SFC64([seed, file_index,
    i])``."""
    i = 0
    while True:
        yield np.random.Generator(np.random.SFC64([seed, file_index, i]))
        i += 1


def write_files(model_dir: Path, layout: str, jobs, config: dict,
                workers: int = 8) -> dict:
    """Run ``jobs`` (each builds one ``File`` and returns its ``write()``)
    on ``workers`` threads, then write the index over what they wrote and
    ``config`` as the server's ``config.json``; returns {"bytes", "files"}."""
    with ThreadPoolExecutor(max_workers=workers) as pool:
        done = [j.result() for j in [pool.submit(job) for job in jobs]]
    total = sum(n for _, _, n in done)
    meta = {"total_size": total}
    if layout == "q8":
        meta["cake_quant"] = "int8"
    (model_dir / "model.safetensors.index.json").write_text(json.dumps({
        "metadata": meta,
        "weight_map": {name: fname for fname, names, _ in done
                       for name in names}}))
    (model_dir / "config.json").write_text(json.dumps(config))
    return {"bytes": total, "files": len(done)}


class Checkpoint:
    """Read access to a checkpoint written so (or any safetensors
    directory with an index): memory-mapped, numpy only."""

    _DTYPES = {"I8": np.int8, "F32": np.float32, "BF16": np.uint16}

    def __init__(self, model_dir: Path):
        self.dir = Path(model_dir)
        index = json.loads(
            (self.dir / "model.safetensors.index.json").read_text())
        self.files = index["weight_map"]
        self._headers: dict[str, tuple[dict, int]] = {}

    def __contains__(self, name: str) -> bool:
        return name in self.files

    def raw(self, name: str) -> tuple[np.ndarray, str]:
        fname = self.files[name]
        if fname not in self._headers:
            with open(self.dir / fname, "rb") as f:
                (n,) = struct.unpack("<Q", f.read(8))
                self._headers[fname] = (json.loads(f.read(n)), 8 + n)
        header, base = self._headers[fname]
        e = header[name]
        lo, hi = e["data_offsets"]
        arr = np.memmap(self.dir / fname, dtype=self._DTYPES[e["dtype"]],
                        mode="r", offset=base + lo,
                        shape=tuple(e["shape"]))
        return arr, e["dtype"]

    def f32(self, name: str) -> np.ndarray:
        """The tensor as float32, dequantized as stored: ``name.q8`` times
        ``name.scale`` per output channel where the tensor is quantized."""
        if name not in self and f"{name}.q8" in self:
            q, _ = self.raw(f"{name}.q8")
            scale = np.asarray(self.raw(f"{name}.scale")[0])[:, None]
            out = np.empty(q.shape, np.float32)

            def rows(lo: int) -> None:  # float32(q) * scale, a slab at a time
                np.multiply(q[lo:lo + _ROWS], scale[lo:lo + _ROWS],
                            out=out[lo:lo + _ROWS])

            with ThreadPoolExecutor(8) as pool:
                list(pool.map(rows, range(0, q.shape[0], _ROWS)))
            return out
        arr, dtype = self.raw(name)
        if dtype == "BF16":
            return (arr.astype(np.uint32) << 16).view(np.float32)
        return np.asarray(arr, np.float32)
