"""Static analysis of the lowered step-chain programs.

A rule-based lint engine over the StableHLO this repo actually
compiles (plus an AST pass for source-level host-sync hazards): the
hazard classes every past perf/correctness incident belonged to —
duplicated stencil gathers, closed-over constants, nondeterministic
GSPMD scatters, dropped donations, f64 leaks, stray host syncs —
checked statically on the CPU backend, in CI, before a TPU is ever
involved.

Entry points:

* ``tools/lint.py`` — the CLI (``--check`` gates CI,
  ``--update-baseline`` accepts current findings);
* :func:`ramses_tpu.analysis.engine.audit_sim` — the telemetry
  run-header hook (``analysis_findings`` next to
  ``hlo_gather_elems``);
* :mod:`ramses_tpu.analysis.programs` — the canonical program
  enumerator (one small lowered program per driver family).

See ``docs/static_analysis.md`` for the rule catalog and the
baseline workflow.
"""

from ramses_tpu.analysis.engine import (audit_program, audit_sim,
                                        report, run)
from ramses_tpu.analysis.rules import (Finding, Rule, Severity,
                                       all_rules, get_rule,
                                       load_baseline, save_baseline,
                                       severity_counts)

__all__ = [
    "Finding", "Rule", "Severity", "all_rules", "get_rule",
    "load_baseline", "save_baseline", "severity_counts",
    "audit_program", "audit_sim", "report", "run",
]
