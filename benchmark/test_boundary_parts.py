"""The readers PR 53 added and their entries in ``BENCHMARK.json``: CPU
only, a second or two.

    python -m pytest benchmark/test_boundary_parts.py -q

A file of its own beside ``test_benchmark.py``, which stays as it is;
tier-1 (``pytest tests/``) does not collect it.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
PARTS = ("engine.boundary_emit_ms", "engine.boundary_pass_ms",
         "engine.boundary_enqueue_ms")
COUNTS = "engine.landing_counts_fetch_ms"


def _hist(count, total):
    return {"type": "histogram", "count": count, "sum": total}


def _ctx(before: dict, after: dict) -> dict:
    return {"before": {"status": {"metrics": before}},
            "after": {"status": {"metrics": after}}}


@pytest.mark.parametrize("name", PARTS + (COUNTS,))
def test_a_reader_gives_the_windows_mean(name):
    """Growth of the histogram's sum over growth of its count across the
    window, whatever stood in it before."""
    ctx = _ctx({name: _hist(40, 100.0)}, {name: _hist(440, 400.0)})
    assert run.load_reader(name)(ctx) == pytest.approx(0.75)


@pytest.mark.parametrize("name", PARTS + (COUNTS,))
def test_a_reader_gives_nothing_without_the_series_or_an_observation(name):
    """A program older than PR 53 has no such series, and a model whose
    decode programs count nothing observes none: the reader returns None
    (the line leaves the metric out) and does not raise."""
    other = {"engine.boundary_ms": _hist(500, 1000.0)}
    assert run.load_reader(name)(_ctx({}, other)) is None
    assert run.load_reader(name)(_ctx({}, {})) is None
    idle = _ctx({name: _hist(7, 3.5)}, {name: _hist(7, 3.5)})
    assert run.load_reader(name)(idle) is None


def test_the_four_are_appended_and_named_as_the_boundarys_metric_is():
    """Appended after everything PR 52 had, in this order; the three
    parts as ``engine.boundary_ms`` is declared (every cell lands
    blocks), the counts' fetch for the cells whose decode programs
    count."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"][-4:]] == [*PARTS, COUNTS]
    whole = by_name["engine.boundary_ms"]
    for name in PARTS:
        assert by_name[name] == dict(whole, name=name)
    counts = dict(by_name[COUNTS])
    assert counts.pop("workloads") == by_name[
        "moe.decode_experts_read_share"]["workloads"]
    assert counts == dict(whole, name=COUNTS)
