"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. FD = paper Fig. 2; SEM = Figs. 3-4;
DG = Figs. 5-6; attention/ssm = LM kernel hot-spots; unified = matmul/rmsnorm
in the unified kernel language on all three backends; serve = continuous-vs-
static batching throughput; roofline rows summarize
the dry-run artifacts when present (full table via ``-m benchmarks.roofline``).
"""

from __future__ import annotations

from . import attention, dg, fd, sem, serve, unified
from .common import Row, check_manifest, emit, write_json


def _cost_rows(rows):
    """One static-cost-model row per registered op (default derived config):
    us column is 0 (nothing is timed), derived carries the footprint/traffic
    summary the CI smoke manifest pins."""
    import numpy as np

    import repro.kernels  # noqa: F401 — registers the op families
    from repro.core import registered_ops
    from repro.lint_kernels import cost_op

    for name, op in sorted(registered_ops().items()):
        c = cost_op(op, np.random.RandomState(0))
        k = c["kernels"][0]
        fl = "?" if k["flops"] is None else str(k["flops"])
        comm = (f"comm={k['comm_bytes']}B; " if k.get("comm_bytes") else "")
        rows.append(Row(
            f"cost/{name}", 0.0,
            f"vmem={k['vmem_bytes']}B ({k['vmem_frac']:.0%} budget); "
            f"hbm={k['hbm_bytes']}B; flops={fl}; {comm}"
            f"pruned={len(c['sweep_pruned'])}/"
            f"{len(c['sweep_pruned']) + c['sweep_kept']}"))
    return rows


def _roofline_rows(rows):
    from . import roofline
    recs = roofline.load("artifacts/dryrun")
    ok = [r for r in recs if not r.get("skipped") and "error" not in r]
    for r in ok:
        a = roofline.analyze(r)
        dom = a["dominant"]
        rows.append(Row(
            f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}",
            a["terms"][dom],
            f"dominant={dom}; frac={a['roofline_fraction']:.2f}; "
            f"6ND/HLO={a['useful_ratio']:.2f}"))
    return rows


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, one rep per row: a fast CI canary that "
                         "every benchmark path still builds and runs "
                         "(timings are not meaningful)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="also write the rows as JSON (the CI "
                         "bench_smoke.json artifact)")
    ap.add_argument("--check-manifest", default=None, metavar="PATH",
                    help="fail (exit 1) unless every row-name prefix listed "
                         "in PATH matched at least one emitted row — "
                         "benchmark drift breaks CI instead of rotting")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = []
    fd.run(rows, smoke=args.smoke)
    sem.run(rows, smoke=args.smoke)
    dg.run(rows, smoke=args.smoke)
    attention.run(rows, smoke=args.smoke)
    unified.run(rows, smoke=args.smoke)
    serve.run(rows, smoke=args.smoke)
    try:
        _cost_rows(rows)
    except Exception as e:
        rows.append(Row("cost/unavailable", 0.0, str(e)[:60]))
    try:
        _roofline_rows(rows)
    except Exception as e:  # artifacts may not exist yet
        rows.append(Row("roofline/unavailable", 0.0, str(e)[:60]))
    emit(rows)
    if args.out:
        write_json(rows, args.out)
    if args.check_manifest:
        import sys

        missing = check_manifest(rows, args.check_manifest)
        if missing:
            print("benchmarks.run: expected rows MISSING from this run "
                  f"(manifest {args.check_manifest}):", file=sys.stderr)
            for m in missing:
                print(f"  {m}", file=sys.stderr)
            sys.exit(1)
        print(f"benchmarks.run: manifest OK ({args.check_manifest})",
              file=sys.stderr)


if __name__ == "__main__":
    main()
