"""Bytes a decode step has to move: what every architecture's count shares.

A decode step is bound by memory: every weight is read once for the few
rows of the batch. The least a step can read is what the algorithm needs:

- every linear of every layer as stored (int8: one byte a weight and a
  float32 scale per output channel; bf16: two bytes a weight), the norms,
  the output head, and one embedding row per live stream;
- of a sparse layer's experts, only those some token of the batch is
  routed to (in expectation over uniform routing), with the router;
- each live stream's cached state up to its position, in the cache's type.

An architecture (``arch/<arch>.py``) adds these up for its own tensors in
``weight_bytes`` and ``decode_step_bytes``; ``kernel.decode_hbm_share``
divides the latter by the chip's memory bandwidth (``peaks.json``) and by
the measured device time of a step. The counts are the yardstick's: a PR
to the program may not change them.
"""

from __future__ import annotations

WEIGHT_BYTES = {"q8": 1, "bf16": 2}
PLAIN_BYTES = {"bf16": 2, "f32": 4}


def linear_bytes(fan_in: int, out: int, layout: str) -> int:
    scales = 4 * out if layout == "q8" else 0
    return fan_in * out * WEIGHT_BYTES[layout] + scales


def expected_experts(experts: int, top_k: int, rows: float) -> float:
    """How many of ``experts`` some row is routed to, with ``rows`` rows
    each choosing ``top_k`` distinct experts uniformly."""
    return experts * (1.0 - (1.0 - top_k / experts) ** rows)
