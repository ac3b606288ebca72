"""Deltas of the server's own counters over the measured window: what the
per-layer readers share. ``ctx["before"]`` and ``ctx["after"]`` hold the
serve status (``GET /``: its ``metrics`` block is the registry that
``/metrics`` renders) and ``GET /debug/prof`` on either side of it."""

from __future__ import annotations


def series_delta(ctx: dict, name: str, field: str = "value") -> float | None:
    """Growth of one field (``value`` of a counter; ``count`` or ``sum``
    of a histogram) of a ``/metrics`` series over the window."""
    before = ctx["before"]["status"]["metrics"].get(name, {})
    after = ctx["after"]["status"]["metrics"].get(name)
    if after is None or field not in after:
        return None
    return after[field] - before.get(field, 0)


def phase_delta(ctx: dict, phase: str, field: str) -> float:
    """Growth of ``count`` or ``sum`` (ms) of one engine phase's
    histogram (``/debug/prof`` -> ``phases``) over the window."""
    before = ctx["before"]["prof"]["phases"].get(phase, {})
    after = ctx["after"]["prof"]["phases"].get(phase, {})
    return after.get(field, 0) - before.get(field, 0)


def module_time(ctx: dict, which: str) -> tuple[float, int] | None:
    """(device seconds, dispatches) of the decode program
    (``which="decode"``) or of the admission programs (``"admit"``), on
    the first device that ran them; None without a device trace.

    The trace names a program ``jit_<function>(<fingerprint>)``, and the
    engine's decode and admission programs are all ``jit_step``: only the
    fingerprint tells them apart. So the decode program is the one module
    matching ``bench.programs.decode`` with the most device time (it runs
    every block); the admission programs are every other module that
    matches ``bench.programs.admit`` (one per prompt bucket, and the
    splice)."""
    import re

    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    programs = ctx["cfg"]["bench"]["programs"]
    for dev in trace["devices"][:ctx["chips"]]:
        mods = dev["modules"]
        decode = max((n for n in mods if re.search(programs["decode"], n)),
                     key=lambda n: mods[n]["seconds"], default=None)
        if decode is None:
            continue
        rows = ([mods[decode]] if which == "decode" else
                [m for n, m in mods.items()
                 if n != decode and re.search(programs["admit"], n)])
        if rows:
            return (sum(m["seconds"] for m in rows),
                    sum(m["count"] for m in rows))
    return None


def decode_step_ms(ctx: dict) -> float | None:
    """Device time of one decode step: the decode program's module events
    over the steps they ran (dispatches x the block's steps)."""
    got = module_time(ctx, "decode")
    if not got:
        return None
    seconds, dispatches = got
    return seconds * 1e3 / (dispatches * ctx["cfg"]["bench"]["decode_block"])


def prefill_ms_per_ktok(ctx: dict) -> float | None:
    """Device time of the admission programs per thousand prompt tokens
    admitted while the trace was open (prompts whose first token arrived
    inside the traced span)."""
    got = module_time(ctx, "admit")
    if not got or not ctx["trace_span"]:
        return None
    t0, t1 = (c["perf_s"] for c in ctx["trace_span"])
    tokens = sum(r["prompt_len"] for r in ctx["records"]
                 if r["times"] and t0 <= r["times"][0] < t1)
    if not tokens:
        return None
    return got[0] * 1e3 / (tokens / 1000.0)
