"""The tensor-parallel serving cell (``internlm2-20b-tp4.chat``): a whole
run of a tiny twin through ``bench/drivers/serve_mesh.py`` on four
simulated host devices, and its three per-layer readers
(``collective_bytes.chat-tp4``, ``collective_ms.chat-tp4``,
``decode_step_ms.chat-tp4``) against numbers worked by hand."""

import json
import os
import subprocess
import sys

import bench_testroot
import pytest

from bench import harness

CELL = "internlm2-20b-tp4.chat"
METRICS = ("collective_bytes.chat-tp4", "collective_ms.chat-tp4",
           "decode_step_ms.chat-tp4")
# internlm2-20b's block at a tiny width: 24 heads over 4 kv heads (its
# 6:1), head dim 16; norm eps and rope theta as published
TINY20B = {"hidden_size": 384, "intermediate_size": 512,
           "num_attention_heads": 24, "num_key_value_heads": 4,
           "num_hidden_layers": 2, "vocab_size": 512,
           "serving": {"batch": 4, "max_len": 256, "mesh": [1, 4]}}


def reader(name):
    return harness.load_module(
        bench_testroot.REPO / "bench" / "metrics" / f"{name}.py").read


def test_the_cell_serves_the_published_model_on_four_chips():
    """Published widths and depth, nothing reduced, one replica over the
    four chips of a (1, 4) mesh, and chat's lengths."""
    bench = harness.Bench(bench_testroot.REPO)
    cell = bench.cell(CELL)
    cfg = bench.config(cell["config"])
    assert cell["chips"] == 4 and cfg["driver"] == "serve_mesh"
    assert (cfg["hidden_size"], cfg["num_hidden_layers"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["intermediate_size"], cfg["vocab_size"]) == \
        (6144, 48, 48, 8, 16384, 92544)
    assert cfg["reduced"] == [] and cfg["serving"]["mesh"] == [1, 4]
    mix, chat = bench.traffic(cell["traffic"]), bench.traffic("chat")
    assert mix["prompt"] == chat["prompt"] and \
        mix["output"] == chat["output"] and mix["loop"] == "open"
    assert [m["name"] for m in bench.metrics(CELL, True)] == list(METRICS)


_RUN = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
root, tests = sys.argv[1], sys.argv[2]
sys.path[:0] = [tests, root]
from pathlib import Path
import jax
import bench_testroot
from bench import harness, run

root = Path(root)
with bench_testroot.cpu_run(root) as stand_ins:
    out = run.main(["--workload", "tiny20b.chat", "--seed", str(2 ** 31 + 9),
                    "--seconds", "4", "--trace", "0"], root=root,
                   devices=jax.devices()[:4],
                   peaks_kind=stand_ins["peaks_kind"])
bench = harness.Bench(root)
from repro.runtime import spans
steps = sum(1 for r in spans.records() if r["name"] == "engine.decode")
print(json.dumps({"correct": out["correct"],
                  "bytes": bench.reader("collective_bytes.chat-tp4")(out["rec"]),
                  "counter": spans.counters().get("engine.collective_bytes"),
                  "steps": steps}))
"""


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """A tiny twin of the cell, added to a tiny root by files and entries
    only, run once through ``bench/run.py`` on four host devices."""
    root = bench_testroot.make_root(tmp_path_factory.mktemp("bench"))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/internlm2-20b.json").read_text())
    (root / "bench/configs/tiny20b.json").write_text(
        json.dumps(dict(cfg, **TINY20B)))
    (root / "bench/check/tiny20b.py").write_text(
        (root / "bench/check/internlm2-20b.py").read_text())
    (root / "bench/traffic/tchat4.json").write_text(
        json.dumps(bench_testroot.MIXES["tchat"]))
    spec["configs"].append({"name": "tiny20b", "source": "test",
                            "file": "bench/configs/tiny20b.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny20b.chat", "config": "tiny20b",
                              "traffic": "tchat4", "chips": 4, "why": "t"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("ttft_p85_ms", "itl_p95_ms") + METRICS:
            m["workloads"].append("tiny20b.chat")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    env = dict(os.environ, PYTHONPATH=str(bench_testroot.REPO / "src"))
    res = subprocess.run(
        [sys.executable, "-c", _RUN, str(root),
         str(bench_testroot.REPO / "tests" / "bench")],
        capture_output=True, text=True, timeout=420, env=env)
    assert res.returncode == 0, res.stderr[-3000:]
    lines = res.stdout.strip().splitlines()
    return json.loads(lines[-2]), json.loads(lines[-1])


def test_tiny_mesh_run_is_correct(tiny_run):
    """The result line of a sound run: correct, nothing failed, nothing
    compiled inside the window, and the chat cell's end-to-end metrics."""
    line, _ = tiny_run
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    assert set(line["metrics"]) == {"ttft_p85_ms", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["check"]["window_compiles"]["value"] == 0
    assert line["check"]["served_tokens"]["value"] >= 200


def test_collective_bytes_reads_the_counter_per_decode_step(tiny_run):
    _, got = tiny_run
    assert got["correct"] and got["steps"] > 0 and got["counter"] > 0
    assert got["bytes"] == got["counter"] / got["steps"]


def test_collective_bytes_reads_none_without_the_counter():
    from repro.runtime import spans

    spans.reset()
    assert reader("collective_bytes.chat-tp4")({"meta": {}}) is None


def _trace():
    # window 0..200 on chip 0: two decode steps (modules holding the
    # kernel) with an all-reduce each and an all-gather split into its
    # async halves; a prefill module with its own all-reduce; a collective
    # outside every module. Chip 1 holds a slower step that is not read.
    step = "%flash_decode_paged.1 = bf16[8] custom-call()"
    return {
        "window": [0, 200],
        "devices": [
            {"name": "/device:TPU:0",
             "ops": [[step, 12, 20, "flash_decode_paged"],
                     ["%all-reduce.3 = bf16[24,6144] all-reduce(%x)", 35, 4,
                      None],
                     ["%all-gather-start.2 = (bf16[6144,23168]) "
                      "all-gather-start(%h)", 40, 1, None],
                     ["%all-gather-done.2 = bf16[6144,92672] "
                      "all-gather-done(%s)", 41, 6, None],
                     [step, 62, 20, "flash_decode_paged"],
                     ["%all-reduce.3 = bf16[24,6144] all-reduce(%x)", 85, 2,
                      None],
                     ["%async-collective-start.4 = (bf16[6144,23168]) "
                      "async-start(%h), calls=%fusion.5", 87, 1, None],
                     ["%fusion.9 = bf16[8] fusion(%all-reduce.3)", 88, 3,
                      None],
                     ["%all-reduce.7 = bf16[1,6144] all-reduce(%y)", 130, 9,
                      None],
                     ["%collective-permute.1 = bf16[8] "
                      "collective-permute(%z)", 190, 5, None]],
             "modules": [["jit_engine_decode(1)", 10, 40],
                         ["jit_engine_decode(1)", 60, 30],
                         ["jit_engine_prefill(2)", 120, 30]]},
            {"name": "/device:TPU:1",
             "ops": [[step, 12, 90, "flash_decode_paged"]],
             "modules": [["jit_engine_decode(1)", 10, 100]]}],
        "host": [["bench.window", 0, 200]],
    }


def test_collective_ms_sums_the_decode_step_s_collectives():
    """(4 + 1 + 6) ns in the first step and (2 + 1) ns in the second, per
    step (the second's all-gather is the compiler's async fusion);
    the fusion that reads an all-reduce, the prefill's all-reduce and the
    permute outside every module do not count."""
    got = reader("collective_ms.chat-tp4")({"trace": _trace()})
    assert got == pytest.approx(1e-6 * (4 + 1 + 6 + 2 + 1) / 2)


def test_decode_step_ms_is_chip_0_s_step():
    got = reader("decode_step_ms.chat-tp4")({"trace": _trace()})
    assert got == pytest.approx(1e-6 * (40 + 30) / 2)


@pytest.mark.parametrize("name", METRICS[1:])
def test_no_decode_step_reads_none(name):
    rec = _trace()
    rec["devices"][0]["ops"] = [o for o in rec["devices"][0]["ops"]
                                if o[3] is None]
    assert reader(name)({"trace": rec}) is None
