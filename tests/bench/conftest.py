"""The tiny benchmark root of ``bench_testroot`` stands in for the cells it
has a tiny twin of (``internlm2-1.8b.chat``, ``.offline`` and
``fd2d-8192.wave``). A cell added to ``BENCHMARK.json`` since has none:
``make_root`` builds the root from a copy of the spec whose metrics list
only the cells it knows, so the twins' runs see the metrics they always
did, and the added cells are tested where their own tests build them."""

from __future__ import annotations

import json
import tempfile
from pathlib import Path

import bench_testroot

TWINS = ("internlm2-1.8b.chat", "internlm2-1.8b.offline", "fd2d-8192.wave")

_make_root = bench_testroot.make_root


def make_root(tmp: Path) -> Path:
    repo = bench_testroot.REPO
    spec = json.loads((repo / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w in TWINS]
    with tempfile.TemporaryDirectory() as view:
        view = Path(view)
        (view / "bench").symlink_to(repo / "bench", target_is_directory=True)
        (view / "BENCHMARK.json").write_text(json.dumps(spec))
        bench_testroot.REPO = view
        try:
            return _make_root(tmp)
        finally:
            bench_testroot.REPO = repo


bench_testroot.make_root = make_root
