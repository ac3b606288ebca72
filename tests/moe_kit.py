"""What the files of MoE tests share (``tests/test_moe.py``,
``tests/test_moe_sorted.py``, ``tests/test_moe_sorted_engine.py``: PR 59
split the first along its section headings so that no one file sets
tier-1's wall clock). A plain module the parts import."""

import pytest

GREEDY = dict(temperature=0.0, repeat_penalty=1.1)


@pytest.fixture
def kernels(monkeypatch):
    """Off the chip the expert block stays dense unless kernels are
    forced (interpreted), as every Pallas path of the repo."""
    monkeypatch.setenv("CAKE_PALLAS", "1")
