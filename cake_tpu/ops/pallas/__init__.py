"""Pallas TPU kernels (SURVEY.md §7 step 4).

The reference delegates all device kernels to candle's CUDA/Metal backends
(`cake-core/Cargo.toml:28-48`); the TPU-native equivalent is hand-written
Pallas (Mosaic) kernels for the hot ops, with the pure-JAX reference-math
implementations in :mod:`cake_tpu.ops` retained as the fallback / parity
oracle.

Dispatch policy (``CAKE_PALLAS`` env): ``auto`` (default — kernels on TPU,
XLA elsewhere), ``1`` (force kernels; interpreted off-TPU, used by tests),
``0`` (force XLA fallback everywhere).
"""

from __future__ import annotations

import os

import jax


def _mode() -> str:
    return os.environ.get("CAKE_PALLAS", "auto").lower()


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernels_enabled() -> bool:
    """Should hot ops route to Pallas kernels?"""
    mode = _mode()
    if mode in ("1", "true", "force"):
        return True
    if mode in ("0", "false", "off"):
        return False
    return on_tpu()


def force_kernels() -> bool:
    """CAKE_PALLAS=1: kernels unconditionally, overriding the measured
    crossover dispatch (ops.attention, ops.quant) that would otherwise pick
    XLA at shapes where it wins."""
    return _mode() in ("1", "true", "force")


def interpret_default() -> bool:
    """Pallas kernels run interpreted off-TPU (CPU tests), compiled on TPU."""
    return not on_tpu()


from cake_tpu.ops.pallas.flash import (  # noqa: E402
    DECODE_BLOCK_K,
    NARROW_BLOCK_K,
    ONE_ROW_BLOCK_K,
    decode_block_k,
    decode_block_range,
    decode_blocks_read,
    flash_attention,
    flash_attention_q8,
    flash_decode,
    narrow_heads,
)
from cake_tpu.ops.pallas.dsa import (  # noqa: E402
    dsa_attend,
    dsa_attend_block,
    dsa_attend_gathered,
    dsa_index,
    dsa_prefill_attend,
    dsa_prefill_select,
    dsa_select,
)
from cake_tpu.ops.pallas.kda import kda_decode  # noqa: E402
from cake_tpu.ops.pallas.latent import latent_decode  # noqa: E402
from cake_tpu.ops.pallas.moe import (  # noqa: E402
    ROW_TILE as MOE_ROW_TILE,
    combine_rows,
    gather_rows,
    group_tiles,
    grouped_matmul,
    grouped_swiglu,
    rows_fetchable,
)
from cake_tpu.ops.pallas.quant import (  # noqa: E402
    quant4_matmul_pallas,
    quant_matmul_pallas,
)

__all__ = [
    "kernels_enabled",
    "interpret_default",
    "on_tpu",
    "DECODE_BLOCK_K",
    "NARROW_BLOCK_K",
    "ONE_ROW_BLOCK_K",
    "decode_block_k",
    "decode_block_range",
    "decode_blocks_read",
    "flash_attention",
    "flash_attention_q8",
    "flash_decode",
    "narrow_heads",
    "dsa_attend",
    "dsa_attend_block",
    "dsa_attend_gathered",
    "dsa_index",
    "dsa_prefill_attend",
    "dsa_prefill_select",
    "dsa_select",
    "kda_decode",
    "latent_decode",
    "MOE_ROW_TILE",
    "combine_rows",
    "gather_rows",
    "group_tiles",
    "grouped_matmul",
    "grouped_swiglu",
    "rows_fetchable",
    "quant_matmul_pallas",
    "quant4_matmul_pallas",
]
