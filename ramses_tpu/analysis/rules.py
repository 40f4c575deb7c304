"""Rule registry + finding model of the static-analysis engine.

Every major perf/correctness incident in this repo's history was
visible in the *lowered program* before any TPU ran it: the
6^d-duplicated stencil gather (PR 8), the ``ct_core`` closed-over
constant that caused involuntary full rematerialization (PR 10), the
GSPMD scatter reassociation that broke MHD determinism to ~1 ulp
(ROADMAP item 2), donation regressions, and stray host syncs.  This
package turns each of those incident classes into a :class:`Rule`
that runs over the lowered StableHLO of the canonical step-chain
programs (:mod:`ramses_tpu.analysis.programs`) — or, for the
source-level hazards, over the ``ramses_tpu`` AST — on the CPU test
backend, so the regression fails in CI instead of on a TPU.

Suppression model: every :class:`Finding` carries a *fingerprint*
that is stable across line moves and tree rebuilds (rule id +
program/module + a salient structural key, never raw byte offsets).
``analysis/baseline.json`` holds the fingerprints of accepted
findings; ``tools/lint.py --check`` fails only on findings outside
the baseline, and ``--update-baseline`` rewrites it.
"""

from __future__ import annotations

import enum
import hashlib
import json
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional


class Severity(enum.IntEnum):
    """Ordered so gates can threshold (``>= WARN`` fails --check)."""
    INFO = 0
    WARN = 1
    ERROR = 2

    def __str__(self) -> str:       # human sink prints "error", not "2"
        return self.name.lower()


@dataclass(frozen=True)
class Finding:
    """One rule violation in one program (or source module).

    ``key`` is the structural identity the fingerprint hashes —
    callers choose it so a finding survives unrelated churn (e.g.
    ``tensor<216x64xf32>`` for a constant, ``module:function:call``
    for a host sync) but changes when the hazard itself changes.
    """
    rule: str                       # rule id, e.g. "gather-blowup"
    severity: Severity
    program: str                    # program name or source module
    message: str                    # one-line human statement
    key: str                        # structural identity (fingerprinted)
    detail: Dict[str, Any] = field(default_factory=dict)

    @property
    def fingerprint(self) -> str:
        h = hashlib.sha256(
            f"{self.rule}|{self.program}|{self.key}".encode())
        return h.hexdigest()[:16]

    def to_json(self) -> Dict[str, Any]:
        return {
            "rule": self.rule,
            "severity": str(self.severity),
            "program": self.program,
            "message": self.message,
            "key": self.key,
            "fingerprint": self.fingerprint,
            "detail": self.detail,
        }


@dataclass(frozen=True)
class Rule:
    """One hazard class: a checker over a lowered program (kind
    ``"hlo"``) or over the package source tree (kind ``"source"``).

    HLO checkers are called once per program as ``check(program)``;
    source checkers once per run as ``check(root_dir)``.  Both return
    a list of :class:`Finding`.
    """
    id: str
    kind: str                       # "hlo" | "source"
    doc: str                        # incident the rule is grounded in
    check: Callable[..., List["Finding"]]


_REGISTRY: Dict[str, Rule] = {}


def register(rule: Rule) -> Rule:
    if rule.id in _REGISTRY:
        raise ValueError(f"duplicate rule id {rule.id!r}")
    _REGISTRY[rule.id] = rule
    return rule


def all_rules() -> List[Rule]:
    """Registered rules, importing the built-in rule modules on first
    use (registration is an import side effect there)."""
    from ramses_tpu.analysis import hlo_rules, source_rules  # noqa: F401
    return list(_REGISTRY.values())


def get_rule(rule_id: str) -> Rule:
    from ramses_tpu.analysis import hlo_rules, source_rules  # noqa: F401
    return _REGISTRY[rule_id]


# ---------------------------------------------------------------------
# baseline: fingerprinted accepted findings
# ---------------------------------------------------------------------
BASELINE_VERSION = 1
DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__),
                                "baseline.json")


def load_baseline(path: Optional[str] = None) -> Dict[str, Dict[str, Any]]:
    """``{fingerprint: entry}`` of accepted findings (empty when the
    file does not exist yet)."""
    path = path or DEFAULT_BASELINE
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        data = json.load(f)
    if data.get("version") != BASELINE_VERSION:
        raise ValueError(
            f"baseline {path} has version {data.get('version')!r}, "
            f"expected {BASELINE_VERSION}")
    return {e["fingerprint"]: e for e in data.get("findings", [])}


def save_baseline(findings: List[Finding],
                  path: Optional[str] = None) -> str:
    """Write the accepted-findings baseline for ``findings`` (sorted,
    deduplicated by fingerprint so reruns produce byte-identical
    files)."""
    path = path or DEFAULT_BASELINE
    seen: Dict[str, Dict[str, Any]] = {}
    for f in findings:
        seen.setdefault(f.fingerprint, {
            "fingerprint": f.fingerprint,
            "rule": f.rule,
            "program": f.program,
            "key": f.key,
            "message": f.message,
        })
    data = {
        "version": BASELINE_VERSION,
        "findings": [seen[k] for k in sorted(seen)],
    }
    with open(path, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def split_baselined(findings: List[Finding],
                    baseline: Dict[str, Dict[str, Any]]):
    """``(new, accepted)`` partition of ``findings`` against a loaded
    baseline."""
    new, accepted = [], []
    for f in findings:
        (accepted if f.fingerprint in baseline else new).append(f)
    return new, accepted


def severity_counts(findings: List[Finding]) -> Dict[str, int]:
    """``{"error": n, "warn": n, "info": n}`` — the telemetry
    run-header shape (``analysis_findings``)."""
    out = {"error": 0, "warn": 0, "info": 0}
    for f in findings:
        out[str(f.severity)] += 1
    return out
