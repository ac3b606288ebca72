"""cakelint: project-specific static analysis that gates CI.

``python -m cake_tpu.analysis`` runs every registered checker over the
package and the examples, and exits nonzero on any finding
not grandfathered by ``analysis-baseline.json``. See ``core.py`` for
the framework, the sibling modules for the checkers, and README
"Static analysis" for the workflow (baseline, suppressions, adding a
checker).
"""

from __future__ import annotations

from cake_tpu.analysis.core import (  # noqa: F401
    DEFAULT_ROOTS,
    REPO_ROOT,
    Checker,
    Finding,
    Module,
    run_checkers,
)
from cake_tpu.analysis.claims import ClaimChecker
from cake_tpu.analysis.engine_ownership import EngineOwnershipChecker
from cake_tpu.analysis.guarded_by import GuardedByChecker
from cake_tpu.analysis.metrics_catalog import MetricsCatalogChecker
from cake_tpu.analysis.thread_domains import ThreadDomainChecker
from cake_tpu.analysis.trace_purity import TracePurityChecker
from cake_tpu.analysis.wire_safety import WireSafetyChecker

ALL_CHECKERS = (
    MetricsCatalogChecker,
    EngineOwnershipChecker,
    GuardedByChecker,
    TracePurityChecker,
    WireSafetyChecker,
    ClaimChecker,
    ThreadDomainChecker,
)


def default_checkers() -> list[Checker]:
    return [cls() for cls in ALL_CHECKERS]


def run(roots=None, checkers=None, repo_root=None) -> list[Finding]:
    """Run (a subset of) the suite; returns raw findings (no baseline)."""
    return run_checkers(checkers or default_checkers(), roots=roots,
                        repo_root=repo_root)
