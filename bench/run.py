"""Run one benchmark cell once, as the check calls it:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It loads the cell's configuration and traffic by the names in
``BENCHMARK.json``, sets up (weights or fields from the seed, warm-up of
every shape the window uses, compiles from the persistent cache in
``<checkout>/.jax_cache``), measures for ``--seconds``, checks what the timed
path produced against the plain reference, and prints one JSON line last on
standard output. With ``--trace 0`` it reports the cell's end-to-end
metrics; with ``--trace 1`` it profiles a few seconds near the end of its
window and reports the per-layer metrics. The numbers compared, each with its limit,
are the last lines on standard error and the ``check`` key of the line;
a program compiled inside the window makes the run not correct.

A run on anything but a TPU with the chips the cell asks for exits non-zero
and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the seconds a traced run profiles, at the end of its window
TRACE_SECONDS = 5.0


def _paths(root: Path):
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare(workload: str, root: Path = ROOT, devices=None,
            peaks_kind=None) -> types.SimpleNamespace:
    """What a run and a calibration share: the cell's files found by name,
    the chip gate, the peaks and the persistent compile cache. ``devices``
    and ``peaks_kind`` stand in for the chip gate (tests drive the rest of
    a run on the CPU with them)."""
    _paths(root)
    from bench import harness

    bench = harness.Bench(root)
    cell = bench.cell(workload)
    if devices is None:
        devices = harness.gate(cell["chips"])
    peaks = bench.peaks(peaks_kind or devices[0].device_kind)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program, however quick to compile, comes from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cfg = bench.config(cell["config"])
    return types.SimpleNamespace(
        bench=bench, cell=cell, cfg=cfg, mix=bench.traffic(cell["traffic"]),
        devices=devices, peaks=peaks, check=bench.check(cell["config"]),
        driver=bench.driver(cfg))


def execute(cell, seed: int, seconds: float, trace_on: bool, t_start: float,
            control: bool = False) -> dict:
    """One run of a prepared cell: set-up, the window and the check. With
    ``control`` the driver also puts the control in the program's place
    and checks it (``rec["control"]``); the benchmark's runs never do."""
    from bench import harness, trace

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    ctx = types.SimpleNamespace(
        cfg=cell.cfg, mix=cell.mix, seed=seed, seconds=seconds,
        devices=cell.devices, peaks=cell.peaks, check=cell.check,
        control=control, t_start=t_start,
        tracer=trace.Tracer(trace_on, min(TRACE_SECONDS, seconds), seconds,
                            trace_dir),
        compiles=harness.CompileCounter())
    try:
        rec = cell.driver.run(ctx)
        rec.update(cfg=cell.cfg, mix=cell.mix, peaks=cell.peaks)
        rec["trace"] = (trace.load(trace_dir, len(cell.devices)) if trace_on
                        else None)
    finally:
        ctx.compiles.close()
        shutil.rmtree(trace_dir, ignore_errors=True)
    # a program compiled inside the window stalls it: the run measured
    # something else than the cell
    rec["checks"]["window_compiles"] = {"value": ctx.compiles.n, "limit": 0,
                                        "ok": ctx.compiles.n == 0}
    return rec


def main(argv=None, *, root: Path = ROOT, devices=None, peaks_kind=None,
         t_start: float | None = None) -> dict:
    """Run a cell and print its result line."""
    args = parse(argv)
    cell = prepare(args.workload, root, devices, peaks_kind)
    from bench import harness, trace

    rec = execute(cell, args.seed, args.seconds, bool(args.trace),
                  T_START if t_start is None else t_start)
    bench = cell.bench

    metrics = {}
    for m in bench.metrics(args.workload, bool(args.trace)):
        value = bench.reader(m["name"])(rec)
        if value is None:
            if not args.trace:
                raise RuntimeError(f"bench: end-to-end metric {m['name']} "
                                   "read nothing")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = rec["device"]
    breakdown = None
    if args.trace:
        device = dict(device, busy_s=trace.busy_s(rec["trace"]),
                      window_s=trace.window_s(rec["trace"]))
        breakdown = trace.breakdown(rec["trace"])
    checks = rec["checks"]
    correct = all(c["ok"] for c in checks.values()) and rec["failed"] == 0
    print(f"bench: {args.workload} seed {args.seed}: setup "
          f"{rec['setup_s']:.3f}s, attempted {rec['attempted']}, "
          f"failed {rec['failed']}",
          file=sys.stderr, flush=True)
    harness.print_checks(checks)
    line = harness.result_line(correct=correct, attempted=rec["attempted"],
                               failed=rec["failed"], metrics=metrics,
                               device=device, checks=checks,
                               breakdown=breakdown)
    print(line, flush=True)
    return {"line": line, "rec": rec, "correct": correct, "metrics": metrics}


if __name__ == "__main__":
    os.chdir(ROOT)
    main()
