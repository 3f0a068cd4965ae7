"""Finding / report containers shared by the jaxpr auditor and the lint
pass, plus the `analysis_report.json` writer CI uploads (DESIGN.md §11).

Severity contract: only unwaived ``error`` findings gate CI. ``warning``
is advisory (e.g. the peak-live-bytes estimate exceeding the planner's
budget — the linear-liveness estimate deliberately overcounts vs XLA's
scheduler, so the delta is data, not a hard failure).
"""
from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional

SEVERITIES = ("error", "warning", "info")


@dataclass
class Finding:
    """One rule violation. `code` is the stable machine id (JXAnnn for the
    jaxpr auditor, RLnnn for the repo lint); `where` is a human anchor —
    "path.py:line" for lint, "<step name>" for audits."""
    code: str
    message: str
    where: str
    severity: str = "error"
    waived: bool = False
    waiver_reason: str = ""
    data: Dict = field(default_factory=dict)

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)

    @property
    def gating(self) -> bool:
        """True when this finding should fail CI."""
        return self.severity == "error" and not self.waived


@dataclass
class StepAudit:
    """The auditor's account of one jitted step: findings plus the
    machine-readable sizing reconciled against the planner's pricing
    (peak_live_bytes is the jaxpr liveness estimate; plan_peak_bytes /
    budget_bytes come from the MemoryPlan that priced this step)."""
    name: str
    findings: List[Finding] = field(default_factory=list)
    n_eqns: int = 0
    in_bytes: int = 0
    out_bytes: int = 0
    donated_in: int = 0                      # donated inputs, per the jaxpr
    donated_aliased: int = 0                 # ...that alias-match an output
    peak_live_bytes: int = 0
    plan_peak_bytes: Optional[int] = None
    budget_bytes: Optional[int] = None
    fingerprint: str = ""                    # recompile-sentinel signature

    @property
    def plan_delta_bytes(self) -> Optional[int]:
        """estimate - plan price; positive means the jaxpr holds more live
        bytes than the planner charged for this step."""
        if self.plan_peak_bytes is None:
            return None
        return self.peak_live_bytes - self.plan_peak_bytes

    def to_dict(self) -> Dict:
        d = dataclasses.asdict(self)
        d["plan_delta_bytes"] = self.plan_delta_bytes
        return d


@dataclass
class AnalysisReport:
    steps: List[StepAudit] = field(default_factory=list)
    lint: List[Finding] = field(default_factory=list)
    meta: Dict = field(default_factory=dict)

    def all_findings(self) -> List[Finding]:
        out = list(self.lint)
        for s in self.steps:
            out.extend(s.findings)
        return out

    def gating_findings(self) -> List[Finding]:
        return [f for f in self.all_findings() if f.gating]

    @property
    def ok(self) -> bool:
        return not self.gating_findings()

    def to_dict(self) -> Dict:
        return {
            "ok": self.ok,
            "meta": self.meta,
            "steps": [s.to_dict() for s in self.steps],
            "lint": [f.to_dict() for f in self.lint],
            "n_findings": len(self.all_findings()),
            "n_gating": len(self.gating_findings()),
        }

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=1, sort_keys=True)

    def summary(self) -> str:
        lines = []
        for s in self.steps:
            delta = s.plan_delta_bytes
            delta_s = "n/a" if delta is None else f"{delta / 2**20:+.1f} MiB"
            lines.append(
                f"[audit] {s.name}: eqns={s.n_eqns} "
                f"donated {s.donated_aliased}/{s.donated_in} aliased, "
                f"peak~{s.peak_live_bytes / 2**20:.1f} MiB "
                f"(vs plan {delta_s}), findings={len(s.findings)}")
        for f in self.all_findings():
            tag = "waived" if f.waived else f.severity.upper()
            lines.append(f"[{tag}] {f.code} {f.where}: {f.message}")
        gating = self.gating_findings()
        lines.append(f"analysis: {len(gating)} gating finding(s), "
                     f"{len(self.all_findings()) - len(gating)} "
                     "waived/advisory")
        return "\n".join(lines)

