"""Registry-wide kernel lint — run the static analyzer over every op.

Sweeps every registered ``define_op`` across its example shapes and its
declared autotune sweep (the same candidate space ``op.tune`` explores), and
analyzes every buildable candidate spec: grid invariants, scratch liveness,
output coverage, dimension-semantics consistency (see
:mod:`repro.core.analyze`). Ops whose families build extra kernels outside
the registry (flash-attention's delta/bwd, the LM head's fused-CE backward)
have those builders linted too, against the same defines.

  PYTHONPATH=src python -m repro.lint_kernels            # verdict table
  PYTHONPATH=src python -m repro.lint_kernels --strict   # any finding fails
  PYTHONPATH=src python -m repro.lint_kernels --json artifacts/analyze.json
  PYTHONPATH=src python -m repro.lint_kernels --cost     # + static cost table

``--cost`` additionally runs the static cost model (VMEM footprint vs. the
``$REPRO_VMEM_BUDGET`` budget, HBM bytes moved, FLOPs, arithmetic intensity)
on every op's default config — its findings (``VMEM_OVERFLOW``,
``FOOTPRINT_NEAR_LIMIT``, ``REDUNDANT_FETCH``) join the lint verdict — and
previews which autotune sweep candidates the cost model would prune.
``--cost-json PATH`` writes the table machine-readably (the CI ``analyze``
stage's ``artifacts/cost.json``).

Exit status: 0 when clean; 1 on any error-severity finding (any finding at
all under ``--strict`` — what the CI ``analyze`` stage runs).
"""

from __future__ import annotations

import argparse
import itertools
import json
import os

import numpy as np

__all__ = ["cost_op", "lint_op", "main"]


def _aux_builders(op_name: str) -> list:
    """Kernel builders a family builds directly (no registry entry of their
    own) — linted with the op's defines, which are a superset of theirs."""
    if op_name == "flash_attention":
        from repro.kernels.flash_attention.kernel import (
            flash_bwd_dkdv_builder, flash_bwd_dq_builder, flash_delta_builder)
        return [("flash_attention/delta", flash_delta_builder),
                ("flash_attention/bwd_dkdv", flash_bwd_dkdv_builder),
                ("flash_attention/bwd_dq", flash_bwd_dq_builder)]
    if op_name == "lm_head_ce":
        from repro.kernels.lm_head.kernel import (lm_head_bwd_dw_builder,
                                                  lm_head_bwd_dx_builder)
        return [("lm_head_ce/bwd_dx", lm_head_bwd_dx_builder),
                ("lm_head_ce/bwd_dw", lm_head_bwd_dw_builder)]
    if op_name == "ring_flash":
        from repro.kernels.flash_attention.kernel import (
            flash_delta_builder, ring_flash_bwd_builder)
        return [("ring_flash/delta", flash_delta_builder),
                ("ring_flash/bwd", ring_flash_bwd_builder)]
    return []


def _candidates(op, defines: dict):
    """The derived defines first, then every autotune sweep combination over
    them — the exact candidate space a tuning run would build."""
    yield dict(defines)
    names = sorted(op.sweep)
    for combo in itertools.product(*(op.sweep[n] for n in names)):
        yield dict(defines, **dict(zip(names, combo)))


def lint_op(op, rng=None) -> dict:
    """Analyze one op across its example-shaped candidate sweep.

    Returns ``{"checked", "skipped", "findings"}`` where findings are unique
    dicts (code/spec/subject/message/severity). Invalid tilings (candidates
    ``op.tune`` would skip) count as skipped, not findings."""
    from repro.core import analyze_spec
    from repro.core.analyze import AnalysisError
    from repro.core.lang import defines_namespace

    rng = rng or np.random.RandomState(0)
    args, params = op.example(rng)
    _, _, params = op._resolve(params)
    run_args, defines, _ = op._prepare(tuple(args), params)

    builders = [(op.name, op.builder)] + _aux_builders(op.name)
    checked = skipped = 0
    findings: dict[tuple, dict] = {}

    def add(fs):
        for f in fs:
            key = (f.code, f.spec, f.subject, f.message)
            findings[key] = dict(code=f.code, spec=f.spec, subject=f.subject,
                                 severity=f.severity, message=f.message)

    for cand in _candidates(op, defines):
        D = defines_namespace(cand)
        for _label, builder in builders:
            try:
                spec = builder(D)
            except AnalysisError as e:
                add(e.findings)
                continue
            except (ValueError, AssertionError):
                skipped += 1  # invalid tiling for these shapes: tune skips it
                continue
            report = analyze_spec(spec, D)
            add(report.findings)
            checked += 1

    return {"checked": checked, "skipped": skipped,
            "findings": list(findings.values())}


def _cost_dict(rep) -> dict:
    return dict(
        spec=rep.spec, grid=list(rep.grid), cells=rep.cells,
        vmem_bytes=rep.vmem_bytes, vmem_budget=rep.vmem_budget,
        vmem_frac=round(rep.vmem_frac, 4), bytes_in=rep.bytes_in,
        bytes_out=rep.bytes_out, hbm_bytes=rep.hbm_bytes, flops=rep.flops,
        intensity=(None if rep.intensity is None
                   else round(rep.intensity, 4)),
        comm_bytes=rep.comm_bytes, comm_detail=dict(rep.comm_detail),
        findings=[dict(code=f.code, spec=f.spec, subject=f.subject,
                       severity=f.severity, message=f.message)
                  for f in rep.findings])


def cost_op(op, rng=None) -> dict:
    """Static cost model over one op's default (derived) config: a
    bytes/FLOPs/footprint report per kernel the family builds, plus a
    preview of which autotune sweep candidates the model would prune."""
    from repro.core import estimate_cost, prune_candidates
    from repro.core.lang import defines_namespace

    rng = rng or np.random.RandomState(0)
    args, params = op.example(rng)
    _, _, params = op._resolve(params)
    _, defines, _ = op._prepare(tuple(args), params)

    kernels = []
    for label, builder in [(op.name, op.builder)] + _aux_builders(op.name):
        try:
            spec = builder(defines_namespace(defines))
        except (ValueError, AssertionError):
            continue  # default config not buildable for an aux kernel
        kernels.append(dict(_cost_dict(
            estimate_cost(spec, defines_namespace(defines))), kernel=label))

    kept, pruned = prune_candidates(op.builder, defines, dict(op.sweep))
    return {"kernels": kernels, "sweep_kept": len(kept),
            "sweep_pruned": [
                {"overrides": {k: c[k] for k in sorted(op.sweep)},
                 "reason": r} for c, r in pruned]}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--op", default=None,
                    help="lint ONE op (default: the whole registry)")
    ap.add_argument("--strict", action="store_true",
                    help="fail on ANY finding, coverage warnings included")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write machine-readable findings to PATH")
    ap.add_argument("--cost", action="store_true",
                    help="also run the static cost model: per-op "
                         "bytes/FLOPs/footprint table + sweep prune preview; "
                         "its findings join the verdict")
    ap.add_argument("--cost-json", default=None, metavar="PATH",
                    help="write the cost table to PATH (implies --cost)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.cost_json:
        args.cost = True

    import repro.kernels  # noqa: F401 — registers the op families
    from repro.core import registered_ops

    ops = registered_ops()
    if args.op is not None:
        if args.op not in ops:
            ap.error(f"unknown op {args.op!r}; known: {sorted(ops)}")
        ops = {args.op: ops[args.op]}

    results = {}
    costs = {}
    for name in sorted(ops):
        results[name] = lint_op(ops[name], np.random.RandomState(args.seed))
        if args.cost:
            costs[name] = cost_op(ops[name], np.random.RandomState(args.seed))
            # cost findings on the DEFAULT config join the lint verdict
            seen = {(f["code"], f["spec"], f["subject"], f["message"])
                    for f in results[name]["findings"]}
            for k in costs[name]["kernels"]:
                for f in k["findings"]:
                    key = (f["code"], f["spec"], f["subject"], f["message"])
                    if key not in seen:
                        seen.add(key)
                        results[name]["findings"].append(f)

    n_err = sum(1 for r in results.values() for f in r["findings"]
                if f["severity"] == "error")
    n_all = sum(len(r["findings"]) for r in results.values())
    ok = (n_all == 0) if args.strict else (n_err == 0)

    w = max(len(n) for n in results) if results else 2
    print(f"{'op':<{w}}  {'checked':>7}  {'skipped':>7}  {'findings':>8}  verdict")
    for name, r in results.items():
        nf = len(r["findings"])
        bad = any(f["severity"] == "error" for f in r["findings"]) or \
            (args.strict and nf)
        verdict = "FAIL" if bad else ("WARN" if nf else "OK")
        print(f"{name:<{w}}  {r['checked']:>7}  {r['skipped']:>7}  "
              f"{nf:>8}  {verdict}")
    for name, r in results.items():
        for f in r["findings"]:
            print(f"  {name}: [{f['code']}] {f['message']}")

    if args.cost:
        print()
        kw = max((len(k["kernel"]) for c in costs.values()
                  for k in c["kernels"]), default=6)
        print(f"{'kernel':<{kw}}  {'vmem B':>10}  {'%bud':>5}  "
              f"{'hbm B':>12}  {'flops':>14}  {'flop/B':>7}  "
              f"{'comm B':>10}  pruned")
        for name, c in costs.items():
            for i, k in enumerate(c["kernels"]):
                fl = "?" if k["flops"] is None else f"{k['flops']:,}"
                ai = "?" if k["intensity"] is None else f"{k['intensity']:.2f}"
                cm = "-" if not k.get("comm_bytes") else f"{k['comm_bytes']:,}"
                npruned = (f"{len(c['sweep_pruned'])}/"
                           f"{len(c['sweep_pruned']) + c['sweep_kept']}"
                           if i == 0 else "")
                print(f"{k['kernel']:<{kw}}  {k['vmem_bytes']:>10,}  "
                      f"{k['vmem_frac']:>5.0%}  {k['hbm_bytes']:>12,}  "
                      f"{fl:>14}  {ai:>7}  {cm:>10}  {npruned}")
        for name, c in costs.items():
            for p in c["sweep_pruned"]:
                print(f"  {name}: {p['overrides']} -> {p['reason']}")

    if args.cost_json:
        payload = {"schema": 1, "ops": costs}
        d = os.path.dirname(args.cost_json)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.cost_json, "w") as fh:
            json.dump(payload, fh, indent=1, sort_keys=True)
        print(f"[lint] wrote {args.cost_json}")

    if args.json:
        payload = {"schema": 1, "strict": bool(args.strict), "ok": ok,
                   "ops": results}
        if args.cost:
            payload["cost"] = costs
        d = os.path.dirname(args.json)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=1, sort_keys=True)
        print(f"[lint] wrote {args.json}")

    print(f"[lint] {len(results)} ops, {n_all} findings "
          f"({n_err} errors){' — STRICT' if args.strict else ''}: "
          f"{'ok' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
