"""From the client's timeline of a run to the end-to-end metrics. Pure.

A request record (made by ``client.py``; times are seconds on the
parent's ``time.perf_counter``)::

    due     when the request was due: at its place in the schedule (open
            loop), or when its client's last request ended and it was
            sent (closed loop)
    sent    when the client sent it
    times   arrival time of every token event, in order
    asked   tokens asked for
    ok      it came back whole: no error, ``asked`` tokens, reason "length"

``window`` is (t0, t1): the measured window on the same clock.
"""

from __future__ import annotations

import math


def percentile(xs: list[float], q: float) -> float | None:
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest ranks (as ``numpy.percentile`` does by default); None for
    no samples, so that an empty metric is left out and never read as 0."""
    if not xs:
        return None
    s = sorted(xs)
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def tail_mean(xs: list[float], share: float) -> float | None:
    """Mean of the largest ``share`` of the samples (at least one). Where
    a distribution has steps -- gaps between tokens are either inside a
    block, a block, or a block and an admission -- a single percentile
    that falls on a step jumps from run to run; the mean beyond it moves
    with every sample of the tail."""
    if not xs:
        return None
    s = sorted(xs)
    k = max(1, round(len(s) * share))
    return sum(s[-k:]) / k


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the q-th percentile. A tail
    is worth reporting from ten (choosing-metrics section 1)."""
    return int(n * (100.0 - q) / 100.0)


def tokens_in_window(records: list[dict], window: tuple[float, float]) -> int:
    t0, t1 = window
    return sum(1 for r in records for t in r["times"] if t0 <= t < t1)


def tokens_per_s(records: list[dict], window: tuple[float, float]) -> float:
    """Output tokens delivered inside the window over its length: all
    the work, all the time."""
    return tokens_in_window(records, window) / (window[1] - window[0])


def tpot_ms(record: dict) -> float | None:
    """Time per output token of one request after its first."""
    t = record["times"]
    if len(t) < 2:
        return None
    return (t[-1] - t[0]) / (len(t) - 1) * 1e3


def tpot_p50_ms(records: list[dict]) -> float | None:
    return percentile([v for v in map(tpot_ms, records) if v is not None],
                      50)


def gaps_ms(records: list[dict], window: tuple[float, float]) -> list[float]:
    """Every gap between consecutive tokens of a stream whose later token
    arrived inside the window, pooled over the streams."""
    t0, t1 = window
    return [(b - a) * 1e3 for r in records
            for a, b in zip(r["times"], r["times"][1:]) if t0 <= b < t1]


def gap_tail1_ms(records, window) -> float | None:
    """Mean of the slowest 1% of the gaps: the stutter a reader sees
    when an admission or a stall lands between decode blocks. Which
    admissions share a pause depends on the order of the requests, so it
    moves by a tenth between seeds: a per-layer metric."""
    return tail_mean(gaps_ms(records, window), 0.01)


def ttft_ms(record: dict, origin: str = "due") -> float | None:
    """Time to the first token from when the request was due (what a
    user waits, queueing behind a stall included) or, with
    ``origin="sent"``, from when it was sent."""
    if not record["times"]:
        return None
    return (record["times"][0] - record[origin]) * 1e3


def ttft_percentile_ms(records, q: float, origin: str = "due"):
    return percentile([v for r in records
                       if (v := ttft_ms(r, origin)) is not None], q)


def ttft_mean_ms(records, origin: str = "due") -> float | None:
    """Mean time to first token over all the requests. Admissions cost
    by prompt bucket, so the distribution has a few modes and its median
    jumps between them with the order of the requests (7% between seeds
    on the chip, PR 23); the mean over one set of sizes does not."""
    waits = [v for r in records if (v := ttft_ms(r, origin)) is not None]
    return sum(waits) / len(waits) if waits else None


def ttft_tail10_ms(records, origin: str = "due") -> float | None:
    """Mean time to first token of the slowest tenth of the requests:
    those that met a queue or a long admission ahead of them."""
    return tail_mean([v for r in records
                      if (v := ttft_ms(r, origin)) is not None], 0.10)


def lateness_ms(records: list[dict]) -> dict:
    """How late the generator sent against when each request was due: a
    starved generator must not read as a fast server."""
    late = [(r["sent"] - r["due"]) * 1e3 for r in records]
    return {"median_ms": percentile(late, 50),
            "worst_ms": max(late, default=None)}


def end_to_end(records: list[dict], window) -> dict:
    """name -> value of every end-to-end metric the client side gives
    (``setup_s`` is the harness's); BENCHMARK.json says which of them a
    cell reports."""
    out = {"tokens_per_s": tokens_per_s(records, window),
           "tpot_p50_ms": tpot_p50_ms(records),
           "ttft_mean_ms": ttft_mean_ms(records)}
    return {k: v for k, v in out.items() if v is not None}
