"""The reduction from a profiler trace to the per-layer numbers: exact on
a hand-made trace, and sound on a small trace recorded on a TPU v5e."""

import json
from pathlib import Path

import pytest

import bench_helpers  # noqa: F401  (puts the benchmark on the path)
from benchmarks.chip import trace_reduce as TR

DATA = Path(__file__).resolve().parent / "data"


def test_hand_made_trace():
    # window 0..10 s on the trace's clock; one device
    ev = {
        "host": [[TR.WINDOW, 0.0, 10.0], [TR.BACKEND, 1.0, 4.0],
                 [TR.SUBMIT, 8.5, 0.5]],
        "devices": {"/device:TPU:0": {
            "modules": [["jit_prefill(7)", 1.0, 1.0],
                        [f"{TR.DECODE_MODULE}(3)", 3.0, 1.0],
                        [f"{TR.DECODE_MODULE}(3)", 4.5, 0.5],
                        ["jit_late(9)", 9.5, 1.0]],
            "ops": [["fusion.1", 1.0, 0.5], ["fusion.2", 1.25, 0.5],
                    ["dot.3", 3.0, 1.0], ["dot.3", 4.5, 0.5],
                    ["copy.4", 9.5, 1.0]]}},
    }
    out = TR.reduce(ev)
    assert out["window_s"] == 10.0
    # busy: [1, 1.75] + [3, 4] + [4.5, 5] + [9.5, 10] (clipped)
    assert out["busy_s"] == pytest.approx(0.75 + 1.0 + 0.5 + 0.5)
    assert out["module_busy_s"][TR.DECODE_MODULE] == pytest.approx(1.5)
    assert out["module_busy_s"]["jit_prefill"] == pytest.approx(0.75)
    assert out["decode_step_s"] == pytest.approx([1.0, 0.5])
    gaps = {name.split(":")[0]: s for name, s in
            out["breakdown"]["idle_gaps"]}
    # gaps: [0,1] neither; [1.75,3] and [4,4.5] in Backend.submit;
    # [5,9.5] neither (its midpoint 7.25 is outside both spans)
    assert gaps[f"host in {TR.BACKEND}"] == pytest.approx(1.25 + 0.5)
    assert gaps["host in neither benchmark span"] == pytest.approx(1.0 + 4.5)
    top = dict(out["breakdown"]["device_ops"])
    assert top[f"{TR.DECODE_MODULE}/dot.3"] == pytest.approx(1.5)


def test_mfu_counts_the_traced_decode_steps():
    from benchmarks.chip import layout
    from benchmarks.chip.families import ssm
    from benchmarks.chip.run import Run
    from test_bench_counts import TINY

    ev = {"host": [[TR.WINDOW, 0.0, 10.0]],
          "devices": {"/device:TPU:0": {
              "modules": [[f"{TR.DECODE_MODULE}(3)", 1.0, 1.0],
                          [f"{TR.DECODE_MODULE}(3)", 4.0, 0.5]],
              "ops": [["dot.3", 1.0, 1.0], ["dot.3", 4.0, 0.5]]}}}
    peak = 2.0e4
    run = Run(cell={}, traffic={"server": {"max_new_tokens": 5}},
              sizes=TINY, family=ssm, peaks={"bf16_flops_per_s": peak},
              seconds=10.0, setup_s=0.0, window=(0.0, 10.0), reqs=[],
              chunks=[(0.0, 1.0, 3), (2.0, 3.0, 3), (5.0, 6.0, 1)],
              trace=TR.reduce(ev))
    read = layout.metric_reader("mfu.batch")
    # two steps at 3 slots in use (the most common submit), 3*(432+80)
    # operations each, over 10 s at the peak
    assert read(run) == pytest.approx(100.0 * 2 * 3 * 512 / (10.0 * peak))
    run.trace = TR.reduce(dict(ev, devices={"/device:TPU:0": {
        "modules": [], "ops": [["dot.3", 1.0, 1.0]]}}))
    assert read(run) is None


@pytest.mark.parametrize("path", sorted(DATA.glob("trace_sample_*.json")),
                         ids=lambda p: p.stem)
def test_recorded_chip_trace(path):
    with open(path) as f:
        ev = json.load(f)
    out = TR.reduce(ev)
    assert out["devices"] == 1
    assert 0 < out["busy_s"] <= out["window_s"]
    assert sum(out["module_busy_s"].values()) == \
        pytest.approx(out["busy_s"], rel=1e-6)
    assert all(0 < s < out["window_s"] for s in out["decode_step_s"])
    share = TR.admit_share(out)
    assert 0 <= share <= 100
    if not out["decode_step_s"]:
        # a slice of admission alone: every busy second is outside decode
        assert share == pytest.approx(100.0)
    idle = sum(s for _, s in out["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)
    assert len(out["breakdown"]["device_ops"]) <= 10
    assert len(out["breakdown"]["idle_gaps"]) <= 10
