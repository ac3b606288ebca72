"""One general traffic generator: a mix file's parameters + a seed -> the
requests of a run. Pure (standard library only); ``client.py`` sends what
this makes.

A mix file (``traffic/<name>.json``) holds::

    loop         "closed" (clients send their next request when the last
                 ended) or "open" (requests are due at fixed times)
    clients      closed loop: a number, or "slots" (the configuration's)
    rate_rps     open loop: requests per second offered
    arrival_cv   open loop: coefficient of variation of the gaps between
                 arrivals (1 = Poisson; above 1 = bursty, gamma gaps)
    prompt_len, output_len
                 a distribution: {"dist": "fixed", "value"} |
                 {"dist": "uniform" | "loguniform", "lo", "hi"} |
                 {"dist": "lognormal", "median", "sigma", "lo", "hi"} |
                 {"dist": "mixture", "of": [{"weight", ...a distribution}]}
    shared_prefix {"groups", "len", "share"}: ``share`` of the requests
                 open with one of ``groups`` prefixes of ``len`` tokens
    set_size     closed loop: how many (prompt, output) sizes the clients
                 cycle through
    set_seed     draws the set of sizes and the set of gaps, which every
                 run seed gets whole, in an order of its own
    drain_limit_s  how long a request due in the window is followed
                 after the window closes
    rehearsal    overrides of the keys above for the tiny CPU rehearsal

``--seed`` makes the traffic: it orders the sizes, orders the gaps (each
by a permutation of its own) and draws the token ids. Every seed gets the
same SET of sizes and the same SET of gaps, so the work of a window is
the same and only what depends on the order varies: which admissions
fall into the same pause between blocks, where a burst of arrivals meets
a run of long prompts. On the chip (PR 23) that moved the tail of the
token gaps by 10% between seeds; such a statistic is a per-layer metric,
never one with a bound (PERF.md section 2).
"""

from __future__ import annotations

import math
import random


def draw(spec: dict, rng: random.Random) -> int:
    """One integer from a distribution spec."""
    kind = spec["dist"]
    if kind == "fixed":
        return int(spec["value"])
    if kind == "mixture":
        parts = spec["of"]
        pick = rng.random() * sum(p["weight"] for p in parts)
        for p in parts:
            pick -= p["weight"]
            if pick <= 0:
                return draw(p, rng)
        return draw(parts[-1], rng)
    lo, hi = spec["lo"], spec["hi"]
    if kind == "uniform":
        x = rng.uniform(lo, hi)
    elif kind == "loguniform":
        x = math.exp(rng.uniform(math.log(lo), math.log(hi)))
    elif kind == "lognormal":
        x = spec["median"] * math.exp(rng.gauss(0.0, spec["sigma"]))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return int(min(hi, max(lo, round(x))))


def size_set(mix: dict, n: int) -> list[tuple[int, int, int]]:
    """The mix's set of ``n`` request sizes: (prompt_len, output_len,
    prefix_group or -1). Depends on the mix alone; the run seed orders
    it."""
    rng = random.Random(mix.get("set_seed", 0))
    shared = mix.get("shared_prefix") or {}
    groups, share = shared.get("groups", 0), shared.get("share", 0.0)
    out = []
    for i in range(n):
        group = i % groups if groups and i < share * n else -1
        out.append((draw(mix["prompt_len"], rng),
                    draw(mix["output_len"], rng), group))
    return out


def gap_set(mix: dict, n: int, seconds: float) -> list[float]:
    """The mix's set of gaps between ``n`` arrivals (``n - 1`` of them):
    gamma with the mix's coefficient of variation, scaled so that the
    first request is due when the window opens and the last one gap's
    mean before it closes. Depends on the mix alone; the run seed orders
    it."""
    rng = random.Random(mix.get("set_seed", 0) + 1)
    shape = 1.0 / mix.get("arrival_cv", 1.0) ** 2
    gaps = [rng.gammavariate(shape, 1.0 / shape) for _ in range(n - 1)]
    scale = seconds * (n - 1) / n / max(sum(gaps), 1e-9)
    return [g * scale for g in gaps]


def ordered(items: list, seed: int, what: str) -> list:
    """``items`` in the run seed's order (a permutation per ``what``)."""
    out = list(items)
    random.Random(f"{seed}/order/{what}").shuffle(out)
    return out


def tokens(rng: random.Random, n: int, vocab: int) -> list[int]:
    """``n`` random token ids clear of the special ones."""
    return [rng.randrange(3, vocab) for _ in range(n)]


class Schedule:
    """The requests of one run. ``request(k)`` is the k-th request: a
    dict with ``prompt_ids``, ``max_tokens`` and, in an open loop,
    ``due`` (seconds after the window opens). An open loop has ``count``
    requests; a closed loop goes on as long as it is asked."""

    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int,
                 slots: int):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        self.open = mix["loop"] == "open"
        if mix["loop"] not in ("open", "closed"):
            raise ValueError(f"loop must be open or closed: {mix['loop']!r}")
        shared = mix.get("shared_prefix") or {}
        self._prefix_len = shared.get("len", 0)
        if self.open:
            self.count = max(1, round(mix["rate_rps"] * seconds))
            self.clients = 0
            self._sizes = ordered(size_set(mix, self.count), seed, "sizes")
            gaps = ordered(gap_set(mix, self.count, seconds), seed, "gaps")
            self._due = [0.0]
            for g in gaps:
                self._due.append(self._due[-1] + g)
        else:
            self.count = None
            clients = mix.get("clients", "slots")
            self.clients = slots if clients == "slots" else int(clients)
            self._sizes = ordered(size_set(mix, mix.get("set_size", 64)),
                                  seed, "sizes")

    def request(self, k: int) -> dict:
        if self.open:
            if k >= self.count:
                raise IndexError(k)
            prompt_len, output_len, group = self._sizes[k]
        else:  # cycle through the set
            prompt_len, output_len, group = self._sizes[k % len(self._sizes)]
        ids = tokens(random.Random(f"{self.seed}/ids/{k}"), prompt_len,
                      self.vocab)
        if group >= 0 and self._prefix_len:
            prefix = tokens(random.Random(f"{self.seed}/prefix/{group}"),
                             self._prefix_len, self.vocab)
            ids = (prefix + ids)[:max(prompt_len, self._prefix_len + 1)]
        req = {"prompt_ids": ids, "max_tokens": output_len}
        if self.open:
            req["due"] = self._due[k]
        return req

    def admission_buckets(self, max_seq: int) -> list[int]:
        """One prompt length per admission bucket (a power of two from
        16, capped at the cache) that this mix's prompts can fall in:
        what warm-up has to send."""
        seen = {}
        for prompt_len, _, group in self._sizes:
            if group >= 0 and self._prefix_len:
                prompt_len = max(prompt_len, self._prefix_len + 1)
            b = 16
            while b < prompt_len:
                b *= 2
            b = min(b, max_seq)
            seen[b] = max(seen.get(b, 0), prompt_len)
        return [seen[b] for b in sorted(seen)]
