"""Static lint CLI (``python -m repro.launch.lint``).

Two modes share one CLI surface and exit-code contract:

Pipeline mode (default) runs the field-flow analyzer (``repro.analysis``)
over the six workload pipelines and — unless ``--no-rewrites`` — over
every rewrite any directive can produce from them (every directive x
target x params ``apply()`` output). Each pipeline is checked
closed-world: the source field universe is the union of the workload's
sample+test document keys, so every read is verified, not just the
provably-wrong ones.

Compile mode (``--compile``) runs the compile-path static analyzer
(``repro.analysis.compiled``) over the model zoo and the Pallas kernel
cases: dtype-upcast / recompile-risk lint from traced jaxprs, transfer +
donation lint from the compiled decode-step HLO, and block-shape + VMEM
lint against the v5e table in ``repro.hardware``.

Usage:
  python -m repro.launch.lint                      # human report
  python -m repro.launch.lint --json               # machine report
  python -m repro.launch.lint --strict             # warnings fail too
  python -m repro.launch.lint --workloads cuad,medec
  python -m repro.launch.lint --bench              # + BENCH_lint.json
  python -m repro.launch.lint --compile            # compile-path lint
  python -m repro.launch.lint --compile --archs llama3.2-1b
  python -m repro.launch.lint --compile --bench    # + BENCH_compile_lint.json

Exit codes: 0 = no error diagnostics (warnings allowed unless
``--strict``), 1 = errors (or warnings under ``--strict``), 2 = a
directive crashed / a model audit raised (sweep incomplete).

``--bench`` additionally measures (a) analyzer overhead per candidate
across the whole sweep (the gate must stay well under 1 ms to be free
relative to an LLM evaluation) and (b) a fault-injected search A/B on
blackvault: a ``MOARSearch`` subclass corrupts a deterministic fraction
of rewrites with an op that *runs fine* but reads a field no document
has — lint=False burns real evaluation budget on those candidates,
lint=True rejects them statically for zero cost.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

from repro.analysis import analyze
from repro.core.agent import AgentContext
from repro.core.directives import DIRECTIVES
from repro.core.search import MOARSearch
from repro.engine.backend import SimBackend
from repro.engine.workloads import WORKLOADS, Workload, load


def workload_source_fields(w: Workload) -> List[str]:
    """Closed-world field universe: every key any sample/test doc has."""
    fields: set = set()
    for d in w.sample + w.test:
        fields |= set(d.keys())
    return sorted(fields)


def iter_candidates(w: Workload, seed: int = 0
                    ) -> Iterator[Tuple[str, Dict[str, Any]]]:
    """Yield ``(label, pipeline)`` for the workload's own pipeline plus
    every directive x target x params rewrite of it."""
    yield "initial", w.initial_pipeline
    ctx = AgentContext(w.sample, w.tags, seed=seed)
    for d in DIRECTIVES:
        for ti, target in enumerate(d.targets(w.initial_pipeline)):
            param_sets = d.instantiate(ctx, w.initial_pipeline, target)
            for pi, params in enumerate(param_sets):
                yield (f"{d.name}[target={ti},params={pi}]",
                       d.apply(w.initial_pipeline, target, params))


def sweep(workload_names: List[str], *, rewrites: bool = True,
          seed: int = 0) -> Dict[str, Any]:
    """Analyze every candidate; returns the report plus timing samples."""
    records: List[Dict[str, Any]] = []
    crashes: List[Dict[str, str]] = []
    timings_us: List[float] = []
    for name in workload_names:
        w = load(name)
        src = workload_source_fields(w)
        gen = iter_candidates(w, seed=seed) if rewrites \
            else iter([("initial", w.initial_pipeline)])
        while True:
            try:
                label, pipeline = next(gen)
            except StopIteration:
                break
            except Exception as e:  # noqa: BLE001 — directive bug, not lint
                crashes.append({"workload": name, "error": repr(e)})
                break
            t0 = time.perf_counter()
            report = analyze(pipeline, source_fields=src)
            timings_us.append((time.perf_counter() - t0) * 1e6)
            if report.diagnostics:
                records.append({
                    "workload": name,
                    "candidate": label,
                    "errors": len(report.errors),
                    "warnings": len(report.warnings),
                    "diagnostics": [d.to_dict() for d in report.diagnostics],
                })
    n = len(timings_us)
    return {
        "workloads": workload_names,
        "candidates_analyzed": n,
        "flagged": records,
        "crashes": crashes,
        "errors": sum(r["errors"] for r in records),
        "warnings": sum(r["warnings"] for r in records),
        "analyze_mean_us": round(sum(timings_us) / n, 1) if n else 0.0,
        "analyze_max_us": round(max(timings_us), 1) if n else 0.0,
    }


# ---------------------------------------------------------------------------
# --compile: compile-path static analyzer sweep
# ---------------------------------------------------------------------------


def compile_sweep(archs: Optional[List[str]] = None,
                  kernels: Optional[List[str]] = None,
                  *, hlo: bool = True) -> Dict[str, Any]:
    """Run ``repro.analysis.compiled`` over the model zoo + kernel cases.

    ``archs``/``kernels`` subset the sweep (None = everything); ``hlo``
    False skips the lower+compile tier (jaxpr lint only — the fast path
    the backend gate uses). Returns a report shaped like ``sweep()``.
    """
    from repro.analysis.compiled import audit_model
    from repro.analysis.compiled.pallas_lint import default_kernel_cases
    from repro.analysis.compiled.audit import audit_kernels
    from repro.configs import list_archs

    names = archs if archs is not None else list_archs()
    cases = [(k, p) for k, p in default_kernel_cases()
             if kernels is None or k in kernels]

    records: List[Dict[str, Any]] = []
    crashes: List[Dict[str, str]] = []
    for arch in names:
        try:
            rep = audit_model(arch, compile=hlo)
        except Exception as e:  # noqa: BLE001 — audit bug, not a finding
            crashes.append({"subject": arch, "error": repr(e)})
            continue
        records.append(rep.to_dict())
    for rep in audit_kernels(cases):
        records.append(rep.to_dict())

    return {
        "mode": "compile",
        "archs": names,
        "kernel_cases": [k for k, _ in cases],
        "subjects_analyzed": len(records),
        "flagged": [r for r in records if r["diagnostics"]],
        "records": records,
        "crashes": crashes,
        "errors": sum(r["errors"] for r in records),
        "warnings": sum(r["warnings"] for r in records),
        "analyze_total_s": round(sum(r["analyze_s"] for r in records), 3),
    }


def format_compile_human(report: Dict[str, Any]) -> str:
    lines = [f"compile-lint: {report['subjects_analyzed']} subjects "
             f"({len(report['archs'])} models, "
             f"{len(report['kernel_cases'])} kernel cases) in "
             f"{report['analyze_total_s']:.1f}s"]
    for rec in report["flagged"]:
        lines.append(f"\n{rec['subject']}: {rec['errors']} error(s), "
                     f"{rec['warnings']} warning(s)")
        for d in rec["diagnostics"]:
            lines.append(f"  [{d['severity']}] {d['code']} @ "
                         f"{d['subject']}:{d['site']}: {d['message']}")
    for c in report["crashes"]:
        lines.append(f"\nCRASH auditing {c['subject']}: {c['error']}")
    if not report["flagged"] and not report["crashes"]:
        lines.append("all clean: no diagnostics")
    else:
        lines.append(f"\n{report['errors']} errors, "
                     f"{report['warnings']} warnings")
    return "\n".join(lines)


def run_compile_bench(report: Dict[str, Any], out_path: str
                      ) -> Dict[str, Any]:
    """Record per-subject diagnostics + analyze time for CI tracking."""
    bench = {
        "subjects": [
            {"subject": r["subject"], "errors": r["errors"],
             "warnings": r["warnings"], "analyze_s": r["analyze_s"],
             "codes": sorted({d["code"] for d in r["diagnostics"]})}
            for r in report["records"]],
        "analyze_total_s": report["analyze_total_s"],
        "errors": report["errors"],
        "warnings": report["warnings"],
        "crashes": len(report["crashes"]),
    }
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=1, sort_keys=True)
    return bench


# ---------------------------------------------------------------------------
# --bench: fault-injected search A/B
# ---------------------------------------------------------------------------

#: Appended by the fault injector: merge_lists tolerates the missing
#: field at runtime (``doc.get(f) or []``), so the corrupted pipeline
#: executes and scores normally — only closed-world lint can tell it
#: reads a field no document defines.
FAULT_OP: Dict[str, Any] = {
    "type": "code_map", "name": "lint_probe",
    "code": {"kind": "merge_lists", "fields": ["nonexistent_xyz"],
             "output_field": "lint_probe_merged"},
}


def is_faulted(pipeline: Dict[str, Any]) -> bool:
    return any(op.get("name") == "lint_probe"
               for op in pipeline.get("operators", ()))


class FaultInjectedSearch(MOARSearch):
    """MOARSearch whose agent emits a malformed rewrite on
    ``fault_num`` of every ``fault_den`` node expansions (deterministic
    in the attempt counter; defaults to 2 of 3)."""

    fault_num, fault_den = 2, 3

    def _transform_candidate(self, pipeline, directive, attempt):
        if attempt % self.fault_den < self.fault_num:
            faulty = dict(pipeline)
            faulty["operators"] = list(pipeline["operators"]) + [
                {**FAULT_OP, "code": dict(FAULT_OP["code"])}]
            return faulty
        return pipeline


def bench_search(workload: str = "blackvault", budget: int = 20,
                 seed: int = 0) -> Dict[str, Any]:
    runs = {}
    for lint in (True, False):
        w = load(workload)
        search = FaultInjectedSearch(
            w, SimBackend(seed=seed, domain=w.domain), budget=budget,
            seed=seed, lint=lint,
            lint_fields=workload_source_fields(w) if lint else None)
        res = search.run()
        runs[lint] = {
            "evaluated": len(res.evaluated),
            "budget_used": res.budget_used,
            "static_rejects": res.static_rejects,
            "static_rejects_by_directive": res.static_rejects_by_directive,
            "faulted_evaluated": sum(
                1 for node in res.evaluated if is_faulted(node.pipeline)),
        }
    return {
        "workload": workload, "budget": budget, "seed": seed,
        "fault_rate": "2/3 of expansions",
        "lint_on": runs[True], "lint_off": runs[False],
    }


def run_bench(report: Dict[str, Any], out_path: str) -> Dict[str, Any]:
    bench = {
        "analyze_overhead": {
            "candidates": report["candidates_analyzed"],
            "mean_us": report["analyze_mean_us"],
            "max_us": report["analyze_max_us"],
            "target": "mean < 1000 us per candidate",
        },
        "fault_injected_search": bench_search(),
    }
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=1, sort_keys=True)
    return bench


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def format_human(report: Dict[str, Any]) -> str:
    lines = [f"analyzed {report['candidates_analyzed']} candidate "
             f"pipelines across {len(report['workloads'])} workloads "
             f"({report['analyze_mean_us']:.0f} us mean per candidate)"]
    for rec in report["flagged"]:
        lines.append(f"\n{rec['workload']} :: {rec['candidate']}")
        for d in rec["diagnostics"]:
            fld = f" [{d['field']}]" if d.get("field") else ""
            lines.append(f"  {d['severity']}: {d['code']} at "
                         f"op {d['op_index']} ({d['op_name']}){fld}: "
                         f"{d['message']}")
    for c in report["crashes"]:
        lines.append(f"\nCRASH in {c['workload']} sweep: {c['error']}")
    if not report["flagged"] and not report["crashes"]:
        lines.append("all clean: no diagnostics")
    else:
        lines.append(f"\n{report['errors']} errors, "
                     f"{report['warnings']} warnings")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.lint",
        description="Field-flow lint over workload pipelines and their "
                    "directive rewrites.")
    ap.add_argument("--workloads", default=None,
                    help="comma-separated subset (default: all)")
    ap.add_argument("--no-rewrites", action="store_true",
                    help="lint only the six initial pipelines")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="print the machine-readable report")
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero on warnings too")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--bench", action="store_true",
                    help="also run the analyzer-overhead + fault-injected "
                         "search benchmark (pipeline mode) or write the "
                         "per-subject record (compile mode)")
    ap.add_argument("--bench-out", default=None,
                    help="bench output path (default BENCH_lint.json / "
                         "BENCH_compile_lint.json by mode)")
    ap.add_argument("--compile", action="store_true", dest="compile_mode",
                    help="run the compile-path analyzer (jaxpr/HLO/Pallas) "
                         "over the model zoo instead of pipeline lint")
    ap.add_argument("--archs", default=None,
                    help="[--compile] comma-separated model subset")
    ap.add_argument("--kernels", default=None,
                    help="[--compile] comma-separated kernel-name subset")
    ap.add_argument("--no-hlo", action="store_true",
                    help="[--compile] skip the lower+compile HLO tier "
                         "(jaxpr lint only)")
    args = ap.parse_args(argv)

    if args.compile_mode:
        return _main_compile(ap, args)

    names = (args.workloads.split(",") if args.workloads
             else list(WORKLOADS))
    unknown = [n for n in names if n not in WORKLOADS]
    if unknown:
        ap.error(f"unknown workloads {unknown} (known: {list(WORKLOADS)})")

    report = sweep(names, rewrites=not args.no_rewrites, seed=args.seed)
    if args.bench:
        report["bench"] = run_bench(report,
                                    args.bench_out or "BENCH_lint.json")

    if args.as_json:
        print(json.dumps(report, indent=1, sort_keys=True))
    else:
        print(format_human(report))
        if args.bench:
            b = report["bench"]["fault_injected_search"]
            print(f"\nbench -> {args.bench_out}: lint on evaluated "
                  f"{b['lint_on']['evaluated']} "
                  f"(rejected {b['lint_on']['static_rejects']} statically, "
                  f"{b['lint_on']['faulted_evaluated']} faulted evals), "
                  f"lint off evaluated {b['lint_off']['evaluated']} "
                  f"({b['lint_off']['faulted_evaluated']} faulted evals)")

    if report["crashes"]:
        return 2
    if report["errors"] or (args.strict and report["warnings"]):
        return 1
    return 0


def _main_compile(ap: argparse.ArgumentParser,
                  args: argparse.Namespace) -> int:
    from repro.configs import list_archs

    archs = args.archs.split(",") if args.archs else None
    if archs:
        known = list_archs()
        unknown = [a for a in archs if a not in known]
        if unknown:
            ap.error(f"unknown archs {unknown} (known: {known})")
    kernels = args.kernels.split(",") if args.kernels else None

    report = compile_sweep(archs, kernels, hlo=not args.no_hlo)
    bench_out = args.bench_out or "BENCH_compile_lint.json"
    if args.bench:
        report["bench"] = run_compile_bench(report, bench_out)

    if args.as_json:
        print(json.dumps(report, indent=1, sort_keys=True))
    else:
        print(format_compile_human(report))
        if args.bench:
            print(f"\nbench -> {bench_out}: "
                  f"{report['subjects_analyzed']} subjects, "
                  f"{report['analyze_total_s']:.1f}s total analyze time")

    if report["crashes"]:
        return 2
    if report["errors"] or (args.strict and report["warnings"]):
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
