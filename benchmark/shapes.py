"""Bytes a decode step has to move, from a configuration's sizes alone.

A decode step is bound by memory: every weight is read once for the few
rows of the batch. The least a step can read is what the algorithm needs:

- every linear of every layer as stored (int8: one byte a weight and a
  float32 scale per output channel; bf16: two bytes a weight), the norms,
  the output head, and one embedding row per live stream;
- of a sparse layer's experts, only those some token of the batch is
  routed to (in expectation over uniform routing), with the router;
- each live stream's keys and values up to its position, clipped to the
  sliding window, in the cache's type.

``kernel.decode_hbm_share`` divides this by the chip's memory bandwidth
(``peaks.json``) and by the measured device time of a step. The function
is the yardstick's: a PR to the program may not change it.
"""

from __future__ import annotations

from weights import layer_linears

_WEIGHT_BYTES = {"q8": 1, "bf16": 2}
_PLAIN_BYTES = {"bf16": 2, "f32": 4}


def linear_bytes(fan_in: int, out: int, layout: str) -> int:
    scales = 4 * out if layout == "q8" else 0
    return fan_in * out * _WEIGHT_BYTES[layout] + scales


def expected_experts(experts: int, top_k: int, rows: float) -> float:
    """How many of ``experts`` some row is routed to, with ``rows`` rows
    each choosing ``top_k`` distinct experts uniformly."""
    return experts * (1.0 - (1.0 - top_k / experts) ** rows)


def weight_bytes(cfg: dict, layout: str, serve_dtype: str = "bf16",
                 rows: float | None = None) -> float:
    """Bytes of weights one decode step reads (``rows`` live streams), or
    with ``rows=None`` all the weights the device holds for decoding,
    embedding included: the number a parameter count checks."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    plain = _PLAIN_BYTES[serve_dtype]
    experts = cfg.get("num_local_experts") or 0
    layer = 2 * h * plain  # the two norms
    for suffix, (fan_in, out) in layer_linears(cfg).items():
        b = linear_bytes(fan_in, out, layout)
        if experts and ".experts." in suffix and rows is not None:
            b *= expected_experts(experts, cfg["num_experts_per_tok"],
                                  rows) / experts
        layer += b
    if experts:
        layer += experts * h * plain  # router
    embed_rows = v if rows is None else rows
    return (cfg["num_hidden_layers"] * layer + embed_rows * h * plain
            + h * plain + linear_bytes(h, v, layout))


def kv_bytes(cfg: dict, context: float, rows: float,
             cache_dtype: str = "bf16") -> float:
    """Bytes of keys and values ``rows`` streams at a mean position of
    ``context`` read in one step."""
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    window = cfg.get("sliding_window")
    if window:
        context = min(context, window)
    return (rows * context * cfg["num_hidden_layers"] * 2
            * cfg["num_key_value_heads"] * d * _PLAIN_BYTES[cache_dtype])


def decode_step_bytes(cfg: dict, layout: str, rows: float, context: float,
                      serve_dtype: str = "bf16") -> float:
    return (weight_bytes(cfg, layout, serve_dtype, rows)
            + kv_bytes(cfg, context, rows, serve_dtype))
