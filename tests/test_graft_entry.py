"""Driver-contract tests for __graft_entry__.

The driver compile-checks ``entry()`` single-chip and runs
``dryrun_multichip(n)`` with n virtual CPU devices. These tests pin that
module import stays side-effect free (an importer must not initialize a
JAX backend, and so take a chip, by accident) and that the dryrun
completes in its CPU subprocess.
"""

import os
import subprocess
import sys


def test_import_does_not_touch_jax_backend():
    # Importing the module in a fresh interpreter must not even import
    # jax: 'jax' absent from sys.modules after import proves the module
    # is side-effect free.
    code = (
        "import sys; import __graft_entry__; "
        "assert 'jax' not in sys.modules, 'module import pulled in jax'; "
        "print('clean')"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", code],
        env={**os.environ, "PYTHONPATH": repo},
        cwd=repo,
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0, r.stderr
    assert "clean" in r.stdout


def test_dryrun_multichip_subprocess():
    import __graft_entry__ as g

    # Runs in its own CPU subprocess regardless of this process's JAX state.
    g.dryrun_multichip(8)
