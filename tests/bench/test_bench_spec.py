"""``BENCHMARK.json`` keeps to the benchmark's contract, and every name in
it has the files the harness finds by that name."""

import json
import re

import bench_testroot
import pytest

SPEC = json.loads((bench_testroot.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = bench_testroot.REPO / "bench"


def _one_line(text, most=200):
    return 1 <= len(text) <= most and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][0] == "python3"
    assert all(_one_line(w) for w in SPEC["command"])
    assert (bench_testroot.REPO / SPEC["command"][1]).is_file()
    assert 1 <= SPEC["run_seconds"] <= 51
    # a full check of 24 cells fits its 43200 s
    cells = 24
    assert (2 + 14 * cells) * (SPEC["run_seconds"] + 60) \
        + cells * 2 * 90 + 1200 <= 43200


def test_names_units_and_whys():
    names = [c["name"] for c in SPEC["configs"]] + \
        [w["name"] for w in SPEC["workloads"]] + \
        [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads"):
        assert len({x["name"] for x in SPEC[group]}) == len(SPEC[group])
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
               for m in metrics)
    assert all(_one_line(x["why"]) for x in SPEC["workloads"])
    assert all(_one_line(m["layer"]) for m in SPEC["per_layer"])


def test_end_to_end_bounds():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in \
        e2e["setup_s"]
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_every_cell_has_its_files_and_metrics(cell):
    from bench import harness

    bench = harness.Bench(bench_testroot.REPO)
    cfg = bench.config(cell["config"])
    assert (BENCH / "drivers" / f"{cfg['driver']}.py").is_file()
    assert (BENCH / "check" / f"{cell['config']}.py").is_file()
    assert (BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
    assert cell["chips"] in (1, 4)
    e2e = bench.metrics(cell["name"], False)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    layer = bench.metrics(cell["name"], True)
    assert layer
    for m in e2e + layer:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for m in layer:      # a per-layer metric moves one this cell reports
        assert m["moves"] in {x["name"] for x in e2e}
