"""The program's own spans: what ``program_spans`` makes of them on a
hand-made trace, on a CPU profiler run of ``JaxBackend`` (so a rename on
either side fails here), and on a small trace recorded on a TPU v5e."""

import json
import tempfile
from pathlib import Path

import pytest

import bench_helpers  # noqa: F401  (puts the benchmark on the path)
from benchmarks.chip import program_spans as PS
from benchmarks.chip import trace_reduce as TR

DATA = Path(__file__).resolve().parent / "data"


def _span(name, start, end, **stats):
    return [name, start, end - start, stats]


#: window 0..10 s; the device is busy in [1, 2], [3, 3.5] and [6, 7], so
#: idle in [0, 1], [2, 3], [3.5, 6] and [7, 10]: 7.5 s
HAND_MADE = {
    "host": [[TR.WINDOW, 0.0, 10.0], [TR.BACKEND, 0.5, 7.5]],
    "devices": {"/device:TPU:0": {
        "modules": [[f"{TR.DECODE_MODULE}(3)", 3.0, 0.5],
                    [f"{TR.DECODE_MODULE}(3)", 6.0, 1.0]],
        "ops": [["fusion.1", 1.0, 1.0], ["dot.2", 3.0, 0.5],
                ["dot.2", 6.0, 1.0]]}},
    "program": [
        _span(PS.SUBMIT, 0.5, 8.0, requests=2),
        _span(PS.TICK, 0.5, 4.0, step_num=1),
        _span(PS.ADMIT, 0.5, 2.5, uid=1, prompt_len=5, bucket=32),
        _span(PS.PREFILL, 0.6, 1.0),
        _span(PS.SYNC, 1.0, 2.2),
        _span(PS.SPLICE, 2.2, 2.4),
        _span(PS.DECODE, 2.5, 4.0, active=1, slots=4),
        _span(PS.STEP, 2.5, 2.8),
        _span(PS.SYNC, 3.0, 3.6),
        _span(PS.TICK, 4.0, 7.5, step_num=2),
        _span(PS.DECODE, 4.2, 7.5, active=2, slots=4),
        _span(PS.STEP, 4.2, 4.5),
        _span(PS.SYNC, 5.0, 6.5),
        _span(PS.SYNC, 6.5, 7.2),
        # open past the window's end: its idle counts, the span does not
        _span(PS.SYNC, 9.5, 10.5),
    ],
}


def test_hand_made_program_spans():
    out = PS.reduce(HAND_MADE)
    self_idle = dict(out["idle_in_program_spans"])
    # each piece of idle time, named by the innermost span holding it
    assert self_idle == pytest.approx({
        PS.SYNC: 0.2 + 0.1 + 1.0 + 0.2 + 0.5,
        PS.DECODE: 0.2 + 0.4 + 0.5 + 0.3,
        PS.STEP: 0.3 + 0.3,
        PS.PREFILL: 0.4,
        PS.SPLICE: 0.2,
        PS.ADMIT: 0.1 + 0.1,
        PS.TICK: 0.2,
        PS.SUBMIT: 0.5,
        PS.OUTSIDE: 0.5 + 1.5,
    })
    assert sum(self_idle.values()) == pytest.approx(7.5)
    p = out["program"]
    assert {k: v["count"] for k, v in p["spans"].items()} == {
        PS.SUBMIT: 1, PS.TICK: 2, PS.ADMIT: 1, PS.PREFILL: 1, PS.SPLICE: 1,
        PS.DECODE: 2, PS.STEP: 2, PS.SYNC: 4}
    # idle inside a span counts its children's: [2.5, 3] + [3.5, 4] and
    # [4.2, 6] + [7, 7.5]; the admission's [0.5, 1] + [2, 2.5]
    assert p["spans"][PS.DECODE]["idle_s"] == pytest.approx(1.0 + 2.3)
    assert p["spans"][PS.ADMIT]["idle_s"] == pytest.approx(1.0)
    assert p["decode_syncs"] == 3
    assert p["decode_active"] == [1, 2] and p["decode_slots"] == [4, 4]
    assert p["admits"] == [[1, 5, 32]]
    assert PS.tick_idle_ms(p) == pytest.approx(1650.0)
    assert PS.admit_idle_ms(p) == pytest.approx(1000.0)
    assert PS.syncs_per_tick(p) == pytest.approx(1.5)
    assert PS.slot_occupancy(p) == pytest.approx(37.5)


def test_without_program_spans_the_reduction_is_as_before():
    bare = {k: v for k, v in HAND_MADE.items() if k != "program"}
    assert TR.reduce(HAND_MADE) == TR.reduce(bare)
    out = PS.reduce(dict(bare, program=[]))
    assert out["idle_in_program_spans"] == [[PS.OUTSIDE, 7.5]]
    p = out["program"]
    assert p["spans"] == {} and p["decode_syncs"] == 0
    for read in (PS.tick_idle_ms, PS.admit_idle_ms, PS.syncs_per_tick,
                 PS.slot_occupancy):
        assert read(p) is None


@pytest.fixture(scope="module")
def backend():
    from repro.engine.backend import JaxBackend

    return JaxBackend(seed=0, max_new_tokens=3, decode_slots=2)


@pytest.mark.parametrize("n_requests", [1, 5])
def test_profiler_records_the_program_spans(backend, n_requests):
    """A CPU profiler run at the host tracer level of the benchmark's
    traced runs: every span the reduction names is there, one admission
    per request with its stats, and one sync per active slot per tick."""
    import jax
    from repro.pipeline.protocols import OpRequest

    op = {"name": "m", "type": "map", "prompt": "Say", "model": "mamba2-370m"}
    requests = [OpRequest(kind="map", op=op,
                          doc={"id": f"d{i}", "text": "word " * (4 + 9 * i)})
                for i in range(n_requests)]
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(TR.WINDOW):
                out = backend.submit(requests)
        finally:
            jax.profiler.stop_trace()
        ev = PS.load(d)
    assert len(out) == n_requests and all(r.error is None for r in out)
    assert {e[0] for e in ev["program"]} == set(PS.PROGRAM_SPANS)
    (submit,) = [e for e in ev["program"] if e[0] == PS.SUBMIT]
    assert submit[3] == {"requests": n_requests}
    p = PS.reduce(ev)["program"]
    assert p["spans"][PS.ADMIT]["count"] == n_requests
    assert len({uid for uid, _, _ in p["admits"]}) == n_requests
    assert all(n <= b for _, n, b in p["admits"])
    assert p["spans"][PS.TICK]["count"] == p["spans"][PS.DECODE]["count"]
    assert p["spans"][PS.SYNC]["count"] == n_requests + p["decode_syncs"]
    # each decode span holds one sync per active slot
    syncs = [e[1] for e in ev["program"] if e[0] == PS.SYNC]
    for _, s, d, stats in (e for e in ev["program"] if e[0] == PS.DECODE):
        assert sum(s <= t < s + d for t in syncs) == stats["active"]
        assert 1 <= stats["active"] <= stats["slots"] == 2


@pytest.mark.parametrize("path",
                         sorted(DATA.glob("trace_sample_*_spans.json")),
                         ids=lambda p: p.stem)
def test_recorded_chip_trace_program_spans(path):
    with open(path) as f:
        ev = json.load(f)
    assert {e[0] for e in ev["program"]} <= set(PS.PROGRAM_SPANS)
    out = PS.reduce(ev)
    tr = TR.reduce(ev)
    idle = sum(s for _, s in out["idle_in_program_spans"])
    assert idle == pytest.approx(tr["window_s"] - tr["busy_s"], rel=1e-6)
    p = out["program"]
    assert {PS.ADMIT, PS.DECODE, PS.SYNC} <= set(p["spans"])
    assert p["decode_syncs"] == sum(p["decode_active"])
    assert PS.syncs_per_tick(p) == pytest.approx(
        sum(p["decode_active"]) / len(p["decode_active"]))
    assert 0 < PS.slot_occupancy(p) <= 100
    assert 0 <= PS.tick_idle_ms(p) <= 1e3 * tr["window_s"]
    assert all(n <= b for _, n, b in p["admits"])
    # one clock, to about a millisecond: each execution of the decode step
    # in the window starts at the batcher.step span that dispatched it
    # (the device plane reads 0.4-0.7 ms early in this recording)
    (w0, w1), = [(s, s + d) for name, s, d in ev["host"] if name == TR.WINDOW]
    dispatch = [(s, s + d) for name, s, d, _ in ev["program"]
                if name == PS.STEP]
    (dev,) = ev["devices"].values()
    steps = [s for name, s, _ in dev["modules"]
             if TR.module_name(name) == TR.DECODE_MODULE and w0 <= s < w1]
    assert steps and len(steps) == len(dispatch)
    for s, (a, b) in zip(steps, dispatch):
        assert a - 1e-3 < s < b
