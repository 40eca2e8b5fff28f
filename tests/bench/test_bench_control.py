"""The controls, at a size a test run holds: the reference computed one
precision below the configuration's, in the program's place, goes through
the cell's own comparison and comes out not correct.

- served model (bf16): the int8 reference (weights per column, activations
  per row, keys and values rounded to int8) picks the token at each served
  position; its mean gap below the float32 reference's best fails the
  limit 4e-3 here (5.2e-3 to 6.5e-3) as on the chip at the cells' size
  (PERF.md), and reads at least three times the program's;
- solver (f32): the reference run and step computed in bfloat16 fail both
  numbers.
"""

import bench_testroot
import numpy as np
import pytest


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_testroot.make_root(tmp_path_factory.mktemp("bench"))


def test_served_int8_control_fails_the_limit(root):
    import jax

    from bench import harness, traffic
    from bench.drivers import serve
    from bench.trace import Tracer

    bench = harness.Bench(root)
    cfg = dict(bench.config("tiny"), hidden_size=512, num_hidden_layers=4,
               vocab_size=4096, intermediate_size=1024)
    mix = bench.traffic("toff")
    check = bench.check("tiny")
    cell = serve.ServeCell(cfg, mix, jax.devices()[:1])
    readings = []
    for seed in (1, 2, 3):
        cell.load(seed)
        eng = cell.engine()
        cell.warm(eng, 6.0)
        reqs = traffic.generate(mix, seed, 6.0, cell.dims["v"])
        res = cell.measure(eng, reqs, 6.0, Tracer(False, 0, 0, ""))
        eng.drain()        # every request served in full, however slow
        seqs = [(np.concatenate([m["req"].prompt,
                                 np.asarray(eng.result(rid), np.int32)]),
                 m["prompt"]) for rid, m in res["meta"].items()]
        p, c = (check.compare(cfg, cell.weights, seqs, control=ctl)
                for ctl in (False, True))
        assert all(x["ok"] for x in p.values()), p
        assert not all(x["ok"] for x in c.values()), c
        readings.append((p["served_gap_mean"]["value"],
                         c["served_gap_mean"]["value"]))
    for p, c in readings:
        assert c >= 3 * p, readings


def test_fd2d_bf16_control_fails_the_limit():
    from bench import harness
    from bench.drivers.fd2d import fields
    from bench.ref import fd2d

    bench = harness.Bench(bench_testroot.REPO)
    check = bench.check("fd2d-8192")
    cfg = dict(bench.config("fd2d-8192"), width=256, height=256)
    n = 200
    for seed in (1, 2, 3):
        start = fields(cfg, seed)
        u_n2 = fd2d.run(cfg, *start, n - 2)
        u_n1 = fd2d.step(cfg, u_n2, fd2d.run(cfg, *start, n - 3))
        state = (fd2d.step(cfg, u_n1, u_n2), u_n1, u_n2)   # sound, in f32
        assert all(c["ok"] for c in check.compare(cfg, state, start,
                                                  n).values())
        ctl = check.compare(cfg, state, start, n, control=True)
        assert not any(c["ok"] for c in ctl.values()), ctl


def test_calibration_puts_the_control_in_the_programs_place(root):
    """The run that ``bench/calibrate.py`` makes for each seed: the program
    passes, and the control, checked by the same comparison, fails."""
    import time

    from bench import run

    with bench_testroot.cpu_run(root) as stand_ins:
        cell = run.prepare("fdtiny.wave", **stand_ins)
        rec = run.execute(cell, 2 ** 33 + 5, 1.0, False, time.perf_counter(),
                          control=True)
    assert all(c["ok"] for c in rec["checks"].values()), rec["checks"]
    assert set(rec["control"]) == {"run_rel_err", "last_step_rel_err"}
    assert not any(c["ok"] for c in rec["control"].values()), rec["control"]
