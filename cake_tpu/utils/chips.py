"""Published per-chip peaks (one copy, so a correction can never leave one
caller's roofline denominator stale).

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
16 GB of HBM at 819 GB/s per chip. The same numbers are quoted in
``/opt/skills/guides/on-chip-measurement`` section 4. JAX reports that
chip as ``device_kind`` "TPU v5 lite".

These feed roofline DENOMINATORS (weights-bound ideal tok/s = HBM bytes/s
/ model bytes) — they are never presented as measurements. A device that
is not in the table is an error, not a default: a ratio against the wrong
chip's peak is worse than none. To run on another chip, add its row here
with the page it was read from.
"""

from __future__ import annotations

# device_kind substring (lower case) -> spec
HBM_GBPS = {"v5 lite": 819.0, "v5e": 819.0}      # HBM bandwidth, GB/s
HBM_GIB = {"v5 lite": 16.0, "v5e": 16.0}         # HBM capacity


def device_spec(device, table: dict) -> float:
    """Look up a spec by substring match on ``device.device_kind``;
    raises ``KeyError`` for a device this file has no published row for
    (a CPU included)."""
    kind = device.device_kind
    for k, v in table.items():
        if k in kind.lower():
            return v
    raise KeyError(
        f"no published peaks for device_kind {kind!r} in "
        "cake_tpu/utils/chips.py (known: "
        f"{sorted(table)}); add its row with a source, do not assume one")
