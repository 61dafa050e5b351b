"""Typed diagnostics for the compile-path static analyzer.

Mirrors the pipeline analyzer's contract (``repro.analysis.analyzer``):
frozen diagnostic records with a stable ``code`` vocabulary, a report
object with errors/warnings/ok/clean accessors, and dict round-trips for
the CLI/CI surfaces. The difference is the anchor: pipeline diagnostics
point at an operator index; compiled diagnostics point at a *site* — a
jit entry point, an HLO computation or a Pallas kernel — identified by a
free-form ``site`` string plus the model/kernel ``subject`` the audit was
running over.

Diagnostic codes (severity):

=======================  =========  ====================================
``recompile-risk``       warning    a serving jit site retraces across
                                    ticks (shape churn or an uncached
                                    jit closure)
``host-transfer``        error      host<->device copy (outfeed/infeed/
                                    custom-call transfer) on the hot path
``loop-transfer``        warning    a large copy executes inside a
                                    trip-weighted hot loop
``dtype-upcast``         warning    f32 dots carry a significant share
                                    of a bf16 model's matmul FLOPs
``non-donated-buffer``   error      an input buffer with a same-shaped
                                    output (KV cache / carried state) is
                                    not donated — peak HBM doubles
``pallas-block-shape``   error      kernel block shape does not divide
                                    the padded problem shape / violates
                                    TPU tiling alignment
``pallas-vmem``          error      per-step block working set exceeds
                                    the v5e VMEM budget
=======================  =========  ====================================
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

SEV_ERROR = "error"
SEV_WARNING = "warning"

RECOMPILE_RISK = "recompile-risk"
HOST_TRANSFER = "host-transfer"
LOOP_TRANSFER = "loop-transfer"
DTYPE_UPCAST = "dtype-upcast"
NON_DONATED_BUFFER = "non-donated-buffer"
PALLAS_BLOCK_SHAPE = "pallas-block-shape"
PALLAS_VMEM = "pallas-vmem"

ALL_CODES = (
    RECOMPILE_RISK, HOST_TRANSFER, LOOP_TRANSFER, DTYPE_UPCAST,
    NON_DONATED_BUFFER, PALLAS_BLOCK_SHAPE, PALLAS_VMEM,
)


class CompiledAnalysisError(RuntimeError):
    """Raised by ``CompiledReport.raise_for_errors`` under a strict gate."""


@dataclass(frozen=True)
class CompiledDiagnostic:
    """One compile-path finding, anchored to a jit/HLO/kernel site."""

    code: str
    severity: str
    subject: str        # model arch or kernel name the audit ran over
    site: str           # jit entry / HLO computation / kernel
    message: str
    data: Dict[str, Any] = field(default_factory=dict, hash=False)

    def format(self) -> str:
        return (f"[{self.severity}] {self.code} @ {self.subject}:{self.site}: "
                f"{self.message}")

    def to_dict(self) -> Dict[str, Any]:
        return {"code": self.code, "severity": self.severity,
                "subject": self.subject, "site": self.site,
                "message": self.message, "data": dict(self.data)}


@dataclass
class CompiledReport:
    """All diagnostics from one audit subject (a model or a kernel case)."""

    subject: str
    diagnostics: List[CompiledDiagnostic] = field(default_factory=list)
    analyze_s: float = 0.0

    def extend(self, diags: List[CompiledDiagnostic]) -> None:
        self.diagnostics.extend(diags)

    @property
    def errors(self) -> List[CompiledDiagnostic]:
        return [d for d in self.diagnostics if d.severity == SEV_ERROR]

    @property
    def warnings(self) -> List[CompiledDiagnostic]:
        return [d for d in self.diagnostics if d.severity == SEV_WARNING]

    @property
    def ok(self) -> bool:
        return not self.errors

    @property
    def clean(self) -> bool:
        return not self.diagnostics

    def codes(self) -> List[str]:
        return [d.code for d in self.diagnostics]

    def raise_for_errors(self, *, warnings_fatal: bool = False) -> None:
        bad = self.errors + (self.warnings if warnings_fatal else [])
        if bad:
            raise CompiledAnalysisError(
                f"{self.subject!r} failed compile-path static analysis: "
                + "; ".join(d.format() for d in bad))

    def format(self) -> str:
        if not self.diagnostics:
            return f"{self.subject}: clean"
        lines = [f"{self.subject}: {len(self.errors)} error(s), "
                 f"{len(self.warnings)} warning(s)"]
        lines += [f"  {d.format()}" for d in self.diagnostics]
        return "\n".join(lines)

    def to_dict(self) -> Dict[str, Any]:
        return {"subject": self.subject,
                "errors": len(self.errors),
                "warnings": len(self.warnings),
                "analyze_s": round(self.analyze_s, 4),
                "diagnostics": [d.to_dict() for d in self.diagnostics]}


def diag(code: str, severity: str, subject: str, site: str, message: str,
         **data: Any) -> CompiledDiagnostic:
    return CompiledDiagnostic(code=code, severity=severity, subject=subject,
                              site=site, message=message, data=data)


def merge_reports(subject: str,
                  reports: List[Optional[CompiledReport]]) -> CompiledReport:
    out = CompiledReport(subject)
    for r in reports:
        if r is not None:
            out.diagnostics.extend(r.diagnostics)
            out.analyze_s += r.analyze_s
    return out
