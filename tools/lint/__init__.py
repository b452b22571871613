"""tffm-lint: the repo's own static-analysis suite.

``python -m tools.lint`` runs every analyzer over the package and
exits nonzero on any NEW finding (one not grandfathered by
``tools/lint/baseline.txt``).  See LINTING.md for the rule catalog and
how to add a rule.

Programmatic use (tests)::

    from tools import lint
    result = lint.run(root=".")          # default rules + baseline
    result["new"]                        # findings that would fail CI
"""

from __future__ import annotations

import os

from tools.lint.core import (   # noqa: F401  (public API re-exports)
    Context, Finding, load_baseline, run_rules,
)
from tools.lint.donation import DonationRule
from tools.lint.knobs import KnobsRule
from tools.lint.legacy import ObsMetricsRule, Tier1Rule
from tools.lint.lifecycle import LifecycleRule
from tools.lint.locks import LocksRule
from tools.lint.records import RecordsRule

DEFAULT_BASELINE = "tools/lint/baseline.txt"

ALL_RULES = (
    LifecycleRule, DonationRule, LocksRule, KnobsRule, RecordsRule,
    Tier1Rule, ObsMetricsRule,
)


def default_rules():
    return [cls() for cls in ALL_RULES]


def run(root: str = ".", baseline_path: str = None, rules=None,
        ctx: Context = None) -> dict:
    """One lint pass; returns the run_rules() dict plus ``baseline``."""
    if ctx is None:
        ctx = Context(root)
    if baseline_path is None:
        baseline_path = os.path.join(ctx.root, DEFAULT_BASELINE)
    baseline = load_baseline(baseline_path)
    out = run_rules(rules if rules is not None else default_rules(),
                    ctx, baseline)
    out["baseline"] = baseline
    return out
