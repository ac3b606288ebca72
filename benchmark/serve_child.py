"""The process that holds the chip: ``cake_tpu.cli --mode serve``,
in-process, from this checkout.

    python benchmark/serve_child.py <cli arguments>

Nothing but ``cli.main`` runs here. A trace of this process is opened
and closed by the program itself, when the parent asks its capture
control (``POST /debug/trace`` on the serving port, ``cake_tpu/obs/prof``):
only the process that holds the chip can trace it, and the program now
has the hook.
"""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv: list[str]) -> int:
    sys.path.insert(0, str(ROOT))
    from cake_tpu import cli

    return cli.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
