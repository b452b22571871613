"""T1001 / OB00x — the two ancestor lints folded in as rules.

tools/check_tier1.py (tier-1 marker audit) and tools/check_obs.py
(metric-name drift) predate the framework and stay importable on their
own (tests/test_tracing.py imports check_tier1 directly), but
``python -m tools.lint`` is now the one entry point: their findings
flow through the same baseline / exit-code machinery as every other
rule.

- T1001  one finding per check_tier1 problem (a test file with no
         tier-1 tests, an undeclared marker, a file defining no tests);
- OB001  an instrument registered in code but missing from
         OBSERVABILITY.md's Metric schema table;
- OB002  a documented metric no code registers.
"""

from __future__ import annotations

import os
import re

from tools.lint.core import Context, Finding


class Tier1Rule:
    name = "tier1"
    rule_ids = ("T1001",)

    def run(self, ctx: Context):
        from tools import check_tier1

        tests_dir = os.path.join(ctx.root, ctx.tests_dir)
        if not os.path.isdir(tests_dir):
            return []
        result = check_tier1.audit(tests_dir, ctx.root)
        findings = []
        for problem in result["problems"]:
            fname, _, detail = problem.partition(":")
            path = (
                f"{ctx.tests_dir}/{fname}" if fname.endswith(".py")
                else ctx.tests_dir
            )
            # Stable symbol: the file plus the problem's first clause
            # (line numbers never appear in check_tier1 output).
            sym = re.sub(r"\s+", "-", detail.strip())[:60] or fname
            findings.append(Finding(
                rule="T1001", path=path, line=1,
                message=problem,
                hint="see tools/check_tier1.py --list",
                symbol=f"{fname}:{sym.split('—')[0].strip('-')}",
            ))
        return findings


class ObsMetricsRule:
    name = "obs-metrics"
    rule_ids = ("OB001", "OB002")

    def run(self, ctx: Context):
        from tools import check_obs

        md = ctx.abspath(ctx.obs_md)
        pkg = os.path.join(ctx.root, ctx.pkg)
        if not (os.path.exists(md) and os.path.isdir(pkg)):
            return []
        result = check_obs.audit(pkg, md)
        findings = []
        for name in result["undocumented"]:
            site = result["registered"][name][0]
            path, _, line = site.partition(":")
            findings.append(Finding(
                rule="OB001", path=path, line=int(line or 1),
                message=f"instrument `{name}` is registered here but "
                        "missing from the Metric schema table",
                hint="add the row to OBSERVABILITY.md",
                symbol=name,
            ))
        for name in result["stale"]:
            findings.append(Finding(
                rule="OB002", path=ctx.obs_md, line=1,
                message=f"documented metric `{name}` is registered "
                        "nowhere in code",
                hint="remove the row or fix the name",
                symbol=name,
            ))
        if not result["documented"]:
            findings.append(Finding(
                rule="OB002", path=ctx.obs_md, line=1,
                message="no '## Metric schema' table found",
                hint="add the table",
                symbol="<missing-table>",
            ))
        return findings
