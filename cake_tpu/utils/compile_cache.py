"""Where JAX's persistent compilation cache lives.

Every chip call starts from nothing unless compiled programs are kept on
disk, and a 32-layer model's programs are most of a cold start. The
cache's path is part of its key, so it must not move between runs:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this code sets
  no path (a machine that provides a directory it keeps between calls
  must be the only one naming it).
- unset: ``<checkout>/.jax_cache`` — a fixed path (never a temp name, a
  pid or the time), listed in ``.gitignore``.

Entry points call :func:`configure` first thing, before anything
compiles: ``cli.main``, ``chip_smoke.py``'s children and the
``tools/`` mains. Tests that AOT-compile for a described chip turn the
cache off around those compiles (tests/test_chip_compile.py): such an
entry is written but cannot be read back without a chip.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure() -> str:
    """Place the compile cache (see module docstring). Returns the
    directory in effect."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
