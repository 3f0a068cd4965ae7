"""Plan-conformance static analysis (DESIGN.md §11).

Two passes, both CI gates:

- `jaxpr_audit` abstractly traces jitted step functions (no backend, no
  compile) and checks the *compiled artifact's* contract against the
  memory plan: donation actually aliased, host-resident leaves never
  re-materialized whole on device, no transfers inside hot-path scans,
  no silent f32 upcasts of quantized leaves, and a peak-live-bytes
  estimate reconciled against the planner's priced budget.
- `lint` is an AST pass over the repo source encoding repo-specific
  hazard rules (monotonic clocks, Optional-truthiness, kv_dtype
  validation, tracer host pulls, benchmark sync, kernel index clamps)
  with per-rule codes and an inline waiver syntax.

`run` drives both over every step builder and writes
`analysis_report.json` as a CI artifact.
"""
from repro.analysis.report import AnalysisReport, Finding, StepAudit
from repro.analysis.jaxpr_audit import audit_step, aval_fingerprint

__all__ = ["AnalysisReport", "Finding", "StepAudit", "audit_step",
           "aval_fingerprint"]
