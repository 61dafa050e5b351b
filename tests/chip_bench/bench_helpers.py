"""Shared by the chip benchmark's CPU tests: the benchmark's modules on
the path, and a cell run at the program's smoke size."""

from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import Any, Dict

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import layout  # noqa: E402

#: stand-in peaks for CPU runs (a CPU run reports no device metric)
CPU_PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

BENCH = layout.benchmark()

DATA = Path(__file__).resolve().parent / "data"


def smoke_limits(cell: str) -> Dict[str, Any]:
    """The limits that replace the cell's own at the program's smoke size,
    where the gaps are smaller: ``data/smoke_limits/<cell>.json`` where
    the cell has one, else ``<traffic>.json``, each set from CPU readings
    that the file gives (a smaller output wants a smaller limit). Every
    other limit is the cell's own."""
    here = DATA / "smoke_limits"
    path = here / f"{cell}.json"
    if not path.exists():
        path = here / f"{layout.cell(BENCH, cell)['traffic']}.json"
    with open(path) as f:
        return json.load(f)["limits"]


def config_file(cell: str) -> Dict[str, Any]:
    return layout.config(BENCH, layout.cell(BENCH, cell)["config"])


def reduced_sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration file with the sizes of the program's smoke
    config of the same model."""
    from repro.configs import get_config

    fam = layout.family(config["family"])
    red = get_config(config["name"], reduced=True)
    sizes = dict(config)
    for key, attr in fam.PROGRAM_KEYS.items():
        sizes[key] = getattr(red, attr)
    return sizes


def small_traffic(t: Dict[str, Any], arrival: str) -> Dict[str, Any]:
    """The cell's traffic with less of it: the same prompts, outputs and
    slots, 4 requests checked, and a backlog of 8 (``closed``) or 4
    arrivals a second (``poisson``, the generator's open loop)."""
    t = dict(t, arrival=arrival, warmup_requests=2,
             check=dict(t["check"], sample=4, block=4))
    if arrival == "closed":
        t["backlog"] = 8
    else:
        t["rate_per_s"] = 4
    return t


def run_reduced(cell: str, seed: int, seconds: float,
                arrival: str = "closed", **kw):
    """One run of ``cell`` on the CPU at the program's smoke size, with
    ``small_traffic`` and ``smoke_limits``."""
    from benchmarks.chip import run as R

    full, limits = layout.traffic, layout.limits
    smoke = smoke_limits(cell)
    layout.traffic = lambda name: small_traffic(full(name), arrival)
    layout.limits = lambda name: dict(limits(name), **smoke)
    try:
        return R.run_cell(cell, seed, seconds, False, require_tpu=False,
                          reduced=True,
                          sizes=reduced_sizes(config_file(cell)),
                          peaks=CPU_PEAKS, **kw)
    finally:
        layout.traffic, layout.limits = full, limits


def broken_step(fault: str):
    """``make_serve_step`` with a fault planted in the step it builds:
    ``"token"`` alters slot 0's token where the step produces it,
    ``"state"`` returns the cache it was given (only the length moves),
    ``"half"`` leaves half of the batch out (every odd slot keeps the
    token it was given)."""
    from repro.serving import decode

    make = decode.make_serve_step

    def make_broken(cfg, temperature=0.0):
        step = make(cfg, temperature)

        def serve_step(params, token, cache, key=None):
            tok, new_cache = step(params, token, cache, key)
            if fault == "token":
                tok = tok.at[0, 0].set((tok[0, 0] + 1) % cfg.vocab_size)
            if fault == "state":
                new_cache = dict(cache, len=new_cache["len"])
            if fault == "half":
                tok = tok.at[1::2].set(token[1::2])
            return tok, new_cache

        return serve_step

    return make_broken


def fault_cases():
    """(cell, fault) for every cell: the sound path and each fault it can
    have. Half of the batch is left out only where the batch is known to
    fill more than one slot: a closed backlog."""
    cases = []
    for w in BENCH["workloads"]:
        cases += [(w["name"], f) for f in (None, "token", "state")]
        if layout.traffic(w["traffic"])["arrival"] == "closed":
            cases.append((w["name"], "half"))
    return cases


def check_run(cell: str, fault, monkeypatch, arrival: str = "closed"
              ) -> None:
    """A run of ``cell`` is correct exactly when no fault is planted. On
    the sound path the fp8 control, judged in the program's place against
    the cell's own limits, comes out not correct, and reads at least 3x
    the served gap."""
    if fault is not None:
        from repro.serving import scheduler
        monkeypatch.setattr(scheduler, "make_serve_step",
                            broken_step(fault))
    out = run_reduced(cell, 2**31 + 101, 2.0, arrival=arrival,
                      control=fault is None)
    res = out["result"]
    assert res["attempted"] > 0
    assert res["correct"] is (fault is None), res["checks"]
    if fault is None:
        control = out["control"]
        assert control["correct"] is False, control["checks"]
        gap = control["checks"]["max_logit_gap"]
        assert gap["value"] > gap["limit"]
        r = out["readings"]
        assert r["control_max_logit_gap"] >= 3 * r["max_logit_gap"]
