"""Readings that set a cell's limits and rates, on the chip, in one
process (set-up is paid once; the compile cache serves the rest).

    python3 benchmarks/chip/calibrate.py limits --workload <cell> \\
        --seeds 1,2,3 --seconds 10
    python3 benchmarks/chip/calibrate.py sweep --workload <cell> \\
        --rates 2,4,8 --seconds 20

``limits``: for each seed, one run of the cell with the fp8 control
added to the check; prints the served program's widest logit gap (the
lower reading) and the control's (the upper reading) per seed, and the
verdict of each against the cell's limits (the control's has to be
false).

``sweep``: for an open-loop cell, one run per offered rate (the cell's
traffic file with ``rate_per_s`` replaced), without the check; prints
the rate completed, the latency median and 95th percentile, and how many
requests were still unresolved when the window closed. The sustained
rate is the highest whose completions keep up with the offer and whose
latency does not grow through the window.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import layout  # noqa: E402
from benchmarks.chip import run as R  # noqa: E402
from benchmarks.chip import traffic as T  # noqa: E402


def limits(cell: str, seeds, seconds: float) -> None:
    for seed in seeds:
        out = R.run_cell(cell, seed, seconds, False, control=True)
        r = out["readings"]
        control = out.get("control", {})
        print(json.dumps({
            "seed": seed, "correct": out["result"]["correct"],
            "control_correct": control.get("correct"),
            "attempted": out["result"]["attempted"],
            "checks": out["result"]["checks"],
            "control_checks": control.get("checks"),
            "served_max_logit_gap": r.get("max_logit_gap"),
            "control_max_logit_gap": r.get("control_max_logit_gap"),
            "served_per_request": r.get("per_request"),
            "control_per_request": r.get("control_per_request"),
            "reference_s": out["timings"]["reference_s"]}), flush=True)


def sweep(cell: str, rates, seconds: float, seed: int) -> None:
    full = layout.traffic
    for rate in rates:
        layout.traffic = lambda name, rate=rate: dict(full(name),
                                                      rate_per_s=rate)
        try:
            out = R.run_cell(cell, seed, seconds, False, check=False)
        finally:
            layout.traffic = full
        run = out["run"]
        reqs = run.in_window()
        w0, w1 = run.window
        lat = [r.done_at - r.due for r in reqs if r.done_at is not None]
        late = [r for r in reqs if r.done_at is None or r.done_at >= w1]
        half = [r.done_at - r.due for r in reqs
                if r.done_at is not None and r.due >= w0 + seconds / 2]
        print(json.dumps({
            "rate_offered": rate, "due": len(reqs),
            "completed_in_window": len(reqs) - len(late),
            "rate_completed": (len(reqs) - len(late)) / seconds,
            "p50_s": T.percentile(lat, 50) if lat else None,
            "p95_s": T.percentile(lat, 95) if lat else None,
            "p95_s_second_half": T.percentile(half, 95) if half else None,
            "unresolved_at_close": len(late),
            "lateness": out["lateness"]}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("limits", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.mode == "limits":
        limits(args.workload, seeds, args.seconds)
    else:
        sweep(args.workload, [float(r) for r in args.rates.split(",")],
              args.seconds, seeds[0])
    return 0


if __name__ == "__main__":
    sys.exit(main())
