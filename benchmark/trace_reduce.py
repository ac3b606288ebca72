"""From a profiler trace (``.xplane.pb``) to device time by device,
program and operation, collective time, and the idle gaps.

Two steps, so that the arithmetic can be tested without a chip:

``dump(path)`` reads the trace with ``jax.profiler.ProfileData`` (nothing
but JAX; run it in a process of its own with ``JAX_PLATFORMS=cpu``, after
the server has exited) into plain lists: per device plane its ``XLA
Modules`` and ``XLA Ops`` lines as ``[name, start_ns, duration_ns]``, and
the host's runtime spans.

``reduce(dumped, t0_ns, t1_ns)`` does the arithmetic on those lists,
clipped to the window [t0, t1):

- an operation event that contains other events (a ``while``, a
  ``conditional``, a ``call``) is a container: its children carry the
  time, and it counts only for what no child covers (its self time);
- busy is the union of the operation intervals; idle gaps are what the
  union leaves of the window;
- a program's device time is the sum of its module events, by the
  module's full name (``jit_step(<fingerprint>)``: the program's jitted
  functions share names, so only the fingerprint tells them apart);
- collective time is the self time of collective operations (no metric
  reads it yet: for the reader of a cell on several chips);
- each of the longest gaps is named by the host runtime span that covers
  most of it.

    python benchmark/trace_reduce.py <trace dir or .xplane.pb> <out.json> \
        [t0_ns t1_ns [dump.json]]

(the last argument also keeps the plain dump: how ``testdata/`` got its
recorded trace)
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|ppermute|psum|\bsend\b|\brecv\b", re.I)
_HLO = re.compile(r"^%?([^ ]+) = \(?([a-z0-9]+\[[0-9,]*\])?")

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
KEEP_OPS, TOP_GAPS = 50, 10  # rows of the op table and gaps kept


def find_xplane(path: Path) -> Path:
    if path.is_file():
        return path
    found = sorted(path.rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def short(name: str) -> str:
    """An operation's name as the trace gives it, without the operands:
    ``%copy.88 = bf16[32,8]{..} copy(...)`` -> ``copy.88 bf16[32,8]``."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    return f"{m.group(1)} {m.group(2)}" if m.group(2) else m.group(1)


def dump(path: Path) -> dict:
    """The trace as plain data (see module docstring)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(find_xplane(path)))
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        [short(e.name), int(e.start_ns), int(e.duration_ns)]
                        for e in line.events]
            devices.append({"name": plane.name,
                            "ops": lines.get(OPS_LINE, []),
                            "modules": lines.get(MODULES_LINE, [])})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ev = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events if e.duration_ns > 20_000]
                if ev:
                    host.append({"thread": line.name, "events": ev})
    return {"devices": devices, "host": host}


def _clip(events, t0, t1):
    out = []
    for name, start, dur in events:
        lo, hi = max(start, t0), min(start + dur, t1)
        if hi > lo:
            out.append((name, lo, hi))
    return out


def self_times(events) -> list[tuple[str, int, int, int]]:
    """(name, start, end, self_ns) of each event: its duration less what
    its children cover. Events nest by containment on one line."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []  # stack of [name, start, end, covered]

    def close(upto):
        while stack and stack[-1][2] <= upto:
            name, lo, hi, covered = stack.pop()
            out.append((name, lo, hi, max(0, hi - lo - covered)))

    for name, lo, hi in events:
        close(lo)
        if stack:
            parent = stack[-1]
            hi = min(hi, parent[2])
            parent[3] += hi - lo
        stack.append([name, lo, hi, 0])
    close(float("inf"))
    return out


def union(intervals) -> list[tuple[int, int]]:
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def _total(intervals) -> int:
    return sum(hi - lo for lo, hi in intervals)


def _gaps(busy, t0, t1):
    gaps, at = [], t0
    for lo, hi in busy:
        if lo > at:
            gaps.append((at, lo))
        at = max(at, hi)
    if t1 > at:
        gaps.append((at, t1))
    return gaps


def _host_label(host, lo, hi) -> str:
    """The host runtime span that covers most of [lo, hi)."""
    best, best_ns = "no runtime call on the host", 0
    for thread in host:
        for name, start, dur in thread["events"]:
            cover = min(hi, start + dur) - max(lo, start)
            if cover > best_ns:
                best, best_ns = name, cover
    return best


def reduce_device(dev: dict, host: list, t0: int, t1: int) -> dict:
    ops = self_times(_clip(dev["ops"], t0, t1))
    busy = union([(lo, hi) for _, lo, hi, _ in ops])
    by_op: dict[str, list] = {}
    for name, lo, hi, self_ns in ops:
        row = by_op.setdefault(name, [0, 0])
        row[0] += self_ns
        row[1] += 1
    coll_ns = sum(s for name, _, _, s in ops if COLLECTIVE.search(name))
    by_module: dict[str, list] = {}
    for name, lo, hi in _clip(dev["modules"], t0, t1):
        row = by_module.setdefault(name, [0, 0])
        row[0] += hi - lo
        row[1] += 1
    gaps = sorted(_gaps(busy, t0, t1), key=lambda g: g[0] - g[1])[:TOP_GAPS]
    return {
        "name": dev["name"],
        "busy_s": _total(busy) / 1e9,
        "ops": sorted(([n, ns / 1e9, c] for n, (ns, c) in by_op.items()),
                      key=lambda r: -r[1])[:KEEP_OPS],
        "modules": {n: {"seconds": ns / 1e9, "count": c}
                    for n, (ns, c) in by_module.items()},
        "collective_s": coll_ns / 1e9,
        "gaps": [{"start_ns": lo, "seconds": (hi - lo) / 1e9,
                  "host": _host_label(host, lo, hi)} for lo, hi in gaps],
    }


def reduce(dumped: dict, t0: int | None = None, t1: int | None = None):
    """See module docstring. Without a window, the span of the device
    events is taken."""
    starts = [e[1] for d in dumped["devices"] for e in d["ops"] + d["modules"]]
    ends = [e[1] + e[2] for d in dumped["devices"]
            for e in d["ops"] + d["modules"]]
    if not starts:
        return {"window_s": 0.0, "devices": []}
    t0 = min(starts) if t0 is None else t0
    t1 = max(ends) if t1 is None else t1
    devices = [reduce_device(d, dumped["host"], t0, t1)
               for d in dumped["devices"] if d["ops"] or d["modules"]]
    return {"window_s": (t1 - t0) / 1e9, "t0_ns": t0, "t1_ns": t1,
            "devices": devices}


def main(argv: list[str]) -> int:
    src, out = Path(argv[0]), Path(argv[1])
    window = [int(v) for v in argv[2:4]] if len(argv) >= 4 else [None, None]
    dumped = dump(src)
    reduced = reduce(dumped, *window)
    if len(argv) >= 5:  # keep the plain dump too (for a recorded test trace)
        Path(argv[4]).write_text(json.dumps(dumped))
    out.write_text(json.dumps(reduced))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
