"""``serve_child.py`` with ONE piece of the program's EVA attention changed
first: a control of ``evabyte-6p5b-cut``'s ``correct`` on the PROGRAM's
side (``benchmark/eva_controls.py`` starts it in ``serve_child.py``'s
place and holds what it serves to the true reference).

    python benchmark/eva_control_child.py <form> <cli arguments>

``window_only``: the summaries left out. A step attends its ring to the
frontier and NO summary row (``eva_attend``'s ``visible`` is 0: the kernel
fetches no block of the plane); an admission attends each window alone
(``eva_prefill`` a window at a time with no summary ahead). Everything
else, the summaries' making and writing included, runs as served.
"""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def window_only(eva) -> None:
    import jax.numpy as jnp

    attend, prefill = eva.eva_attend, eva.eva_prefill

    def eva_attend(q, ring_k, ring_v, sum_k, sum_v, at, visible, layer):
        return attend(q, ring_k, ring_v, sum_k, sum_v, at,
                      jnp.zeros_like(visible), layer)

    def eva_prefill(q, k, v, k_sum, v_sum, window, chunk):
        t = q.shape[2]
        span = min(t, window)
        return jnp.concatenate([
            prefill(q[:, :, lo:lo + span], k[:, :, lo:lo + span],
                    v[:, :, lo:lo + span], k_sum[:, :, :0], v_sum[:, :, :0],
                    window, chunk) for lo in range(0, t, span)], axis=2)

    eva.eva_attend, eva.eva_prefill = eva_attend, eva_prefill


FORMS = {"window_only": window_only}


def main(argv: list[str]) -> int:
    sys.path.insert(0, str(ROOT))
    from cake_tpu import cli
    from cake_tpu.ops import eva

    FORMS[argv[0]](eva)
    return cli.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
