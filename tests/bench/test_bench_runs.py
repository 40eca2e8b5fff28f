"""Whole runs of tiny cells on the CPU, the chip gate stood in for: a sound
run is correct and prints the contract's line, and a new configuration,
traffic mix and metric need only new files and entries."""

import json

import bench_testroot
import pytest


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_testroot.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell,metrics", [
    ("tiny.chat", {"ttft_p85_ms", "itl_p95_ms"}),
    ("tiny.offline", {"output_tok_s"}), ("fdtiny.wave", {"step_ms"})])
def test_sound_run_is_correct(root, cell, metrics, capsys):
    out = bench_testroot.run_cell(root, cell)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] and line["correct"] is True
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "check"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == metrics | {"setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert all(c["ok"] for c in line["check"].values())


def test_new_config_mix_and_metric_are_files_and_entries(root):
    """A throwaway cell: a config, a mix and a per-layer metric added as
    files, and entries appended to BENCHMARK.json; nothing else edited."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/tiny.json").read_text())
    cfg["num_hidden_layers"] = 1
    (root / "bench/configs/tiny1.json").write_text(json.dumps(cfg))
    (root / "bench/check/tiny1.py").write_text(
        (root / "bench/check/tiny.py").read_text())
    mix = dict(bench_testroot.MIXES["tchat"], rate_per_s=4.0)
    (root / "bench/traffic/slowchat.json").write_text(json.dumps(mix))
    (root / "bench/metrics/requests_done.slowchat.py").write_text(
        "def read(rec):\n    return float(len(rec['meta']))\n")
    spec["configs"].append({"name": "tiny1", "source": "test",
                            "file": "bench/configs/tiny1.json",
                            "reduced": ["num_hidden_layers"], "why": "t"})
    spec["workloads"].append({"name": "tiny1.slowchat", "config": "tiny1",
                              "traffic": "slowchat", "chips": 1, "why": "t"})
    for m in spec["end_to_end"]:
        if m["name"] == "ttft_p85_ms":
            m["workloads"].append("tiny1.slowchat")
    spec["per_layer"].append({
        "name": "requests_done.slowchat", "unit": "requests",
        "better": "higher", "source": "program_counter", "layer": "engine",
        "moves": "ttft_p85_ms", "workloads": ["tiny1.slowchat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    from bench import harness
    bench = harness.Bench(root)
    assert [m["name"] for m in bench.metrics("tiny1.slowchat", True)] == \
        ["requests_done.slowchat"]
    out = bench_testroot.run_cell(root, "tiny1.slowchat", seconds=5.0)
    assert out["correct"]
    assert set(out["metrics"]) == {"ttft_p85_ms", "setup_s"}   # not itl
    reader = bench.reader("requests_done.slowchat")
    assert reader(out["rec"]) == 20.0         # 4 per second for 5 seconds
