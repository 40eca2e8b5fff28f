"""``correct`` for fd2d-8192, two numbers over the whole field:

- ``run_rel_err``: the solution after every step the solver made, from set-up
  to the window's end, against the plain reference (``bench/ref/fd2d.py``)
  run as many steps from the seed's own fields u(0) and u(-dt). A fault in
  any step, or in only some steps, leaves its mark here;
- ``last_step_rel_err``: the window's last step against the reference step
  from the two previous steps the program held, which rounding over the
  run does not blur.

Each is the largest error over the field relative to the reference's
largest value. Their limits sit between the program's readings over a dozen
seeds and the bfloat16 control's (PERF.md).
"""

import jax.numpy as jnp

from bench.ref import fd2d

REL_LIMIT = 1e-4        # readings: program <= 1.10e-7, bf16 control >= 8.7e-3
RUN_LIMIT = 1e-3        # readings: program <= 1.39e-5, bf16 control >= 0.336


def _rel(got, want) -> float:
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def compare(cfg, state, start, n_steps: int, control: bool = False):
    """``state`` is (u(n), u(n-1), u(n-2)) as the solver holds them after
    ``n_steps`` steps from ``start`` = (u(0), u(-dt)). With ``control``, the
    reference computed in bfloat16 takes the program's place."""
    u_n, u_n1, u_n2 = state
    if control:
        u_run = fd2d.run(cfg, *start, n_steps, jnp.bfloat16)
        u_last = fd2d.step(cfg, u_n1, u_n2, jnp.bfloat16)
    else:
        u_run = u_last = u_n
    out = {}
    for name, got, want, limit in (
            ("run_rel_err", u_run, fd2d.run(cfg, *start, n_steps),
             RUN_LIMIT),
            ("last_step_rel_err", u_last, fd2d.step(cfg, u_n1, u_n2),
             REL_LIMIT)):
        err = _rel(got, want)
        out[name] = {"value": err, "limit": limit, "ok": err <= limit}
    return out
