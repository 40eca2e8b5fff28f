"""The benchmark's own machinery: finding a cell's files by name, the
platform gate, the peaks table, the metric readers and the result line.

Everything a cell needs is found by the names in ``BENCHMARK.json``:

- ``configs[].file``: the configuration's sizes (JSON); its ``driver`` key
  names ``bench/drivers/<driver>.py``, which runs the system under test;
- ``bench/traffic/<traffic>.json``: the traffic mix's parameters, read by
  the one generator in ``bench/traffic.py``;
- ``bench/check/<config>.py``: the comparison that decides ``correct``;
- ``bench/metrics/<metric>.py``: one reader per metric, end to end and per
  layer;
- ``bench/work/<kernel>.py``: the necessary operations and bytes of a kernel
  call, from its shapes;
- ``bench/peaks.json``: the chip's peaks, keyed by ``device_kind``.

So a later change adds a configuration, a traffic mix or a metric by adding
files and entries, without editing a file that is here.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import sys
from pathlib import Path

class NoChip(SystemExit):
    """The run found no accelerator it may measure on: exit non-zero with
    no result line."""

    def __init__(self, msg):
        super().__init__(f"bench: {msg}")


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a benchmark file by path (its name may hold '.' or '-')."""
    if not path.is_file():
        raise FileNotFoundError(f"bench: no file {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.spec = load_json(self.root / "BENCHMARK.json")
        self.dir = self.root / "bench"

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        """The configuration's file as it is run, with its catalog entry."""
        for c in self.spec["configs"]:
            if c["name"] == name:
                cfg = load_json(self.root / c["file"])
                return dict(cfg, name=name, source=c["source"])
        raise SystemExit(f"bench: no configuration {name!r}")

    def traffic(self, name: str) -> dict:
        return dict(load_json(self.dir / "traffic" / f"{name}.json"),
                    name=name)

    def driver(self, cfg: dict):
        return load_module(self.dir / "drivers" / f"{cfg['driver']}.py")

    def check(self, config_name: str):
        return load_module(self.dir / "check" / f"{config_name}.py")

    def peaks(self, kind: str) -> dict:
        table = load_json(self.dir / "peaks.json")["devices"]
        if kind not in table:
            raise NoChip(f"device kind {kind!r} is not in bench/peaks.json "
                         f"(known: {sorted(table)}); no peaks, no result")
        return table[kind]

    def metrics(self, cell: str, trace: bool) -> list[dict]:
        """The metrics this cell reports: its end-to-end metrics with
        ``--trace 0``, its per-layer metrics with ``--trace 1``."""
        if not trace:
            return [m for m in self.spec["end_to_end"]
                    if cell in m.get("workloads", [cell])]
        reported = {m["name"] for m in self.metrics(cell, False)}
        return [m for m in self.spec["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in reported)]

    def reader(self, metric: str):
        return load_module(self.dir / "metrics" / f"{metric}.py").read


def gate(chips: int, *, backend: str | None = None, devices=None) -> list:
    """The devices to measure on. A run that finds no TPU, or fewer chips
    than the cell asks for, stops here: it never falls back to the CPU."""
    import jax

    backend = backend or jax.default_backend()
    if backend != "tpu":
        raise NoChip(f"JAX runs on {backend!r}, not a TPU; nothing measured")
    devices = jax.devices() if devices is None else devices
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, {len(devices)} found")
    return list(devices[:chips])


def seed_key(seed: int):
    """A JAX key for any whole-number seed, however large."""
    import jax
    import numpy as np

    word = np.random.SeedSequence(seed).generate_state(1, np.uint32)[0]
    return jax.random.key(int(word))


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts the programs JAX traces while :meth:`counting` is open: every
    new shape or new function traces before it compiles, so a window whose
    count is 0 compiled nothing."""

    EVENT = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        import jax

        self.n = 0
        self._on = False
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, name, _secs, **_kw):
        if self._on and name == self.EVENT:
            self.n += 1

    @contextlib.contextmanager
    def counting(self):
        self._on = True
        try:
            yield
        finally:
            self._on = False

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._seen)


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between order
    statistics, as numpy's default."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: dict,
                breakdown: dict | None = None) -> str:
    """The run's last line of standard output. ``check`` comes last: each
    number compared, with its limit."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = checks
    return json.dumps(out)


def print_checks(checks: dict, file=None):
    """Each number compared beside its limit, as the last lines on
    standard error."""
    file = file or sys.stderr
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}, "
              f"{'ok' if c['ok'] else 'FAILED'})", file=file, flush=True)
