"""What the two files of CLI tests share (PR 59 split ``tests/test_cli.py``
in two, ``test_cli.py`` and ``test_cli_guards.py``, so that no one file
sets tier-1's wall clock): a tiny checkpoint on disk (a module-scoped
fixture, written once a part) and the subprocess runner. A plain module
the parts import, not a conftest plugin."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from cake_tpu.models import llama
from cake_tpu.models.config import tiny
from cake_tpu.utils.weights import save_llama_params

CFG = tiny()
REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("climodel")
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype="float32")
    save_llama_params(params, d)
    (d / "config.json").write_text(json.dumps(CFG.to_hf_dict()))
    return d


def _run_cli(argv, timeout=240, devices=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO)
    env["JAX_PLATFORMS"] = "cpu"
    if devices:
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={devices}"
        ).strip()
    return subprocess.run(
        [sys.executable, "-m", "cake_tpu.cli"] + argv,
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )
