"""The controls of ``evabyte-6p5b-cut``'s ``correct``
(``arch/eva_mha.py`` ``WRONG``, ``eva_controls.py``), at the rehearsal's
sizes on the CPU.

    python -m pytest benchmark/test_eva_controls.py -q

Under ``benchmark/`` for ``test_benchmark.py``'s reason; about half a
minute, nearly all of it the one rehearsal that starts three servers.
"""

from __future__ import annotations

import json
import random
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import eva_controls  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402

W, C = 32, 4


def _wants(wrong, n: int, m: int | None = None, ch: int | None = None):
    """Whether query ``n`` attends position ``m`` (or chunk ``ch``'s
    summary) under the control ``wrong``, written from the sentences of
    ``WRONG``'s comment (``None``: the equations as published)."""
    if m is not None:
        if wrong == "sliding":
            return n - W < m <= n
        return m // W == n // W and m <= n
    end = (ch + 1) * C  # the first position past the chunk
    if wrong == "window_only":
        return False
    if wrong == "early_chunk":
        return end <= n + 1
    if wrong == "sliding":
        return end <= n - W + 1
    return end <= (n // W) * W


@pytest.mark.parametrize("wrong", [None, "window_only", "sliding",
                                   "early_chunk"])
def test_what_a_query_sees_row_by_row(wrong):
    arch = run.load_arch("eva_mha")
    t = 3 * W + 15  # ends mid-window and mid-chunk
    for first in range(0, t, W):
        last = min(first + W, t)
        for lo in range(first, last, 8):
            rows = np.arange(lo, min(lo + 8, last))[:, None]
            (k_lo, k_hi), seen, local, remote = arch._sees(
                wrong, rows, first, last, W, C)
            for i, n in enumerate(rows[:, 0]):
                for m in range(t):
                    got = k_lo <= m < k_hi and bool(local[i, m - k_lo])
                    assert got == _wants(wrong, n, m=m), (n, m)
                for ch in range(t // C):
                    got = ch < seen and (remote is None
                                         or bool(remote[i, ch]))
                    assert got == _wants(wrong, n, ch=ch), (n, ch)


@pytest.fixture(scope="module")
def own(tmp_path_factory):
    """The rehearsal's configuration, a checkpoint, and the reference's
    own greedy answers to two prompts past the window."""
    cell = run.load_cell(eva_controls.CELL)
    cfg = run.overlay(cell["cfg"], cell["cfg"]["bench"]["rehearsal"])
    arch = run.load_arch(cfg["bench"]["arch"])
    tmp = tmp_path_factory.mktemp("eva")
    arch.write_checkpoint(cfg, "bf16", 3, tmp / "ckpt", workers=1)
    rng = random.Random(66)
    probes = []
    for n in (40, 72):
        prompt, ids = traffic.tokens(rng, n, cfg["vocab_size"]), []
        for _ in range(6):
            out, = arch.chosen_logprobs(cfg, tmp / "ckpt",
                                        [(prompt, ids + [0])])
            ids.append(out["best"][-1])
        probes.append({"prompt": prompt, "ids": ids})
    return cfg, arch, tmp, probes


def test_the_references_own_answers_are_correct(own):
    cfg, arch, tmp, probes = own
    ok, worst = run.check_reference(probes, cfg, arch, "t", tmp / "ckpt",
                                    tmp / "c")
    assert ok and worst == 0.0


@pytest.mark.parametrize("wrong", ["window_only", "sliding", "early_chunk",
                                   "uniform_v", "float8"])
def test_each_control_fails_the_harnesss_comparison(own, wrong):
    cfg, arch, tmp, probes = own
    assert wrong in arch.WRONG and len(arch.WRONG) == 5
    ok, worst = run.check_reference(
        probes, cfg, eva_controls.held_to(arch, wrong), "t", tmp / "ckpt",
        tmp / "c")
    assert not ok and worst > 10 * cfg["bench"]["margin_tol"]


def test_an_unknown_control_is_refused(own):
    cfg, arch, tmp, probes = own
    with pytest.raises(ValueError, match="unknown control 'no_rope'"):
        arch.chosen_logprobs(cfg, tmp / "ckpt", [(probes[0]["prompt"], [1])],
                             wrong="no_rope")


def test_float8_linears_are_written_and_nothing_else_moves(own):
    _, arch, tmp, _ = own
    import weights

    arch.write_rounded(tmp / "ckpt", tmp / "f8")
    a, b = weights.Checkpoint(tmp / "ckpt"), weights.Checkpoint(tmp / "f8")
    assert a.files == b.files
    for name in a.files:
        x, y = a.f32(name), b.f32(name)
        if name.endswith("_proj.weight") or name == "lm_head.weight":
            np.testing.assert_array_equal(y, arch.float8(x))
            assert (x != y).any()
        else:
            np.testing.assert_array_equal(x, y)


def test_rehearsal_serves_the_program_and_two_wrong_ones():
    out = subprocess.run(
        [sys.executable, str(HERE / "eva_controls.py"), "--rehearse"],
        capture_output=True, text=True, timeout=400, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(line) for line in out.stdout.splitlines()]
    assert rows[-1] == {"phase": "controls", "as_owed": True}
    got = {(r["side"], r["form"]): r["correct"]
           for r in rows if r["phase"] == "control"}
    assert got == {
        ("program", "as_published"): True,
        **{("reference", w): False for w in
           ("window_only", "sliding", "early_chunk", "uniform_v", "float8")},
        ("program", "float8"): False, ("program", "window_only"): False}
