"""Machinery proof for the 70B stage-slice pricing tool. The real
measurement needs one v5e chip (`python -m cake_tpu.tools.stage_slice`
through the chip tool); this pins the tool's arithmetic and output
contract at tiny dims on CPU, like tests/test_ici_probe.py does for the
ICI probe -- and pins that none of the measurement tools records a
``--json-out`` file off-chip (``stage_slice --mini``, which names its
tiny dims, is the one CPU run that may)."""

import json
import sys

import pytest

from cake_tpu.tools import (flash_sweep, int4_sweep, kda_sweep, kernel_check,
                            moe_sweep, stage_slice)


def test_stage_slice_mini_rows(capsys):
    rc = stage_slice.main(["--mini", "--steps", "2", "--layers", "3"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    rows = out["rows"]
    assert [r["quant"] for r in rows] == ["int8", "bf16"]
    for r in rows:
        assert r["layers_per_stage"] == 3
        assert r["stage_step_ms_measured"] > 0
        assert r["stage_prefill2048_ms_measured"] > 0
        assert r["single_stream_tok_s_projected"] > 0
        # the serialized projection is n_stages x slower than one stage
        t_tok = r["n_stages"] * (
            r["stage_step_ms_measured"] / 1e3 + r["hop_s_projected"])
        assert abs(r["single_stream_tok_s_projected"] - 1 / t_tok) < 0.5
        assert r["interleaved_aggregate_tok_s_upper"] > (
            r["single_stream_tok_s_projected"])
    assert "PROJECTIONS" in out["note"]


def test_slice_config_is_70b_geometry():
    cfg = stage_slice.slice_config(5, 8192, mini=False)
    assert (cfg.hidden_size, cfg.intermediate_size) == (8192, 28672)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads) == (64, 8)
    assert cfg.num_hidden_layers == 5 and cfg.vocab_size == 128256


@pytest.mark.parametrize("tool", [kernel_check, flash_sweep, int4_sweep,
                                  stage_slice, kda_sweep, moe_sweep])
def test_tools_refuse_offchip_json_out(tool, tmp_path, monkeypatch):
    """Off a TPU the kernels run interpreted and a stage step is a CPU
    step: a --json-out file would record those under device names.
    Every tool refuses before measuring."""
    out = tmp_path / "record.json"
    monkeypatch.setattr(sys, "argv", ["tool", "--json-out", str(out)])
    with pytest.raises(SystemExit) as e:
        tool.main()
    assert "--json-out records on-chip measurements" in str(e.value)
    assert not out.exists()


def test_stage_slice_mini_may_record(tmp_path, capsys):
    """--mini names the run a tiny-dims CPU proof: its file is allowed,
    and carries no roofline (there is no chip to divide by)."""
    out = tmp_path / "mini.json"
    assert stage_slice.main(["--mini", "--steps", "1", "--layers", "1",
                             "--json-out", str(out)]) == 0
    rows = json.loads(out.read_text())["rows"]
    assert [r["platform"] for r in rows] == ["cpu", "cpu"]
    assert all(r["stage_step_ms_roofline"] is None for r in rows)
