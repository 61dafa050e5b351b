"""The chip benchmark finds every piece of every cell by name, and
``BENCHMARK.json`` holds together."""

import re

import pytest

import bench_helpers
from bench_helpers import BENCH, DATA, ROOT
from benchmarks.chip import layout

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _each(section):
    return [entry["name"] for entry in BENCH[section]]


def test_every_file_is_used():
    """Each configuration, traffic mix, limit file and metric reader
    belongs to an entry of ``BENCHMARK.json``."""
    here = layout.HERE
    named = {
        "configs": {c["file"] for c in BENCH["configs"]},
        "traffic": {w["traffic"] for w in BENCH["workloads"]},
        "limits": {w["name"] for w in BENCH["workloads"]},
        "metrics": {m["name"] for m in BENCH["end_to_end"]
                    + BENCH["per_layer"]},
    }
    assert {str(p.relative_to(ROOT)) for p in
            (here / "configs").glob("*.json")} == named["configs"]
    assert {p.stem for p in (here / "traffic").glob("*.json")} \
        == named["traffic"]
    assert {p.stem for p in (here / "limits").glob("*.json")} \
        == named["limits"]
    assert {p.stem for p in (here / "metrics").glob("*.py")} \
        == named["metrics"]
    # the CPU tests' data: smoke limits of a cell or a traffic mix, and
    # recorded reference logits of a configuration
    assert {p.stem for p in (DATA / "smoke_limits").glob("*.json")} \
        <= named["limits"] | named["traffic"]
    assert {p.stem for p in (DATA / "reference_logits").glob("*.json")} \
        <= {c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("cell", _each("workloads"))
def test_each_cell_finds_its_files(cell):
    bench = BENCH
    w = layout.cell(bench, cell)
    config = layout.config(bench, w["config"])
    fam = layout.family(config["family"])
    for fn in ("init_weights", "logits", "from_program", "decode_cost",
               "prefill_flops", "param_bytes"):
        assert callable(getattr(fam, fn))
    assert set(fam.PROGRAM_KEYS) <= set(config)
    t = layout.traffic(w["traffic"])
    assert t["arrival"] in ("closed", "poisson")
    limits = layout.limits(cell)
    assert set(limits) == {"max_logit_gap", "output_mismatches",
                           "prompt_mismatches", "failed_requests"}
    for kind in ("end_to_end", "per_layer"):
        for m in layout.cell_metrics(bench, cell, kind):
            assert callable(layout.metric_reader(m["name"]))


def test_smoke_limits_prefer_the_cells_file(tmp_path, monkeypatch):
    w = BENCH["workloads"][0]
    here = tmp_path / "smoke_limits"
    here.mkdir()
    (here / f"{w['traffic']}.json").write_text(
        '{"limits": {"max_logit_gap": 1}}')
    monkeypatch.setattr(bench_helpers, "DATA", tmp_path)
    assert bench_helpers.smoke_limits(w["name"]) == {"max_logit_gap": 1}
    (here / f"{w['name']}.json").write_text(
        '{"limits": {"max_logit_gap": 2}}')
    assert bench_helpers.smoke_limits(w["name"]) == {"max_logit_gap": 2}


@pytest.mark.parametrize("metric", _each("per_layer"))
def test_per_layer_metric_moves_what_its_cells_report(metric):
    bench = BENCH
    m = next(x for x in bench["per_layer"] if x["name"] == metric)
    assert m["workloads"]
    for cell in m["workloads"]:
        reported = {e["name"] for e in
                    layout.cell_metrics(bench, cell, "end_to_end")}
        assert m["moves"] in reported


def test_benchmark_file_names_and_keys():
    bench = BENCH
    names = [c["name"] for c in bench["configs"]] \
        + [w["name"] for w in bench["workloads"]] \
        + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert {"setup_s"} <= {m["name"] for m in bench["end_to_end"]}
    for path in bench["paths"]:
        assert (ROOT / path).is_dir()
    assert (ROOT / bench["command"][1]).is_file()
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        e2e = layout.cell_metrics(bench, w["name"], "end_to_end")
        assert len(e2e) >= 2
        assert layout.cell_metrics(bench, w["name"], "per_layer")


ENTRY_KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"},
                   {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"},
                  {"workloads"}),
}


def test_entries_hold_exactly_their_keys():
    bench = BENCH
    assert set(bench) == {"command", "paths", "run_seconds", *ENTRY_KEYS}
    for section, (required, optional) in ENTRY_KEYS.items():
        for entry in bench[section]:
            assert required <= set(entry) <= required | optional, entry
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_peaks_are_keyed_by_device_kind():
    v5e = layout.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        layout.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("traffic", sorted({w["traffic"]
                                            for w in BENCH["workloads"]}))
def test_every_seed_gets_the_same_work(traffic):
    """A seed reorders the document lengths and changes their words; the
    set of lengths, and the open loop's arrival schedule, are the same for
    every seed."""
    from benchmarks.chip import traffic as T

    t = dict(layout.traffic(traffic), rate_per_s=4)
    a, b = 2**31 + 7, 5
    assert sorted(T.word_counts(t, 64, a)) == sorted(T.word_counts(t, 64, b))
    assert T.word_counts(t, 64, a) != T.word_counts(t, 64, b)
    assert T.arrivals(t, 10.0, a) == T.arrivals(t, 10.0, b)
    assert T.document(a, 0, 900) != T.document(b, 0, 900)
