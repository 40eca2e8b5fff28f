"""Solver cells: the paper's FD wave solver (``FDWave``), its ``fd2d``
kernel stepped back to back through ``FDWave.timestep``.

Set-up builds the solver at the configuration's size, gives it fields drawn
from the seed on the device, and runs a few steps, which compile the kernel.
The window then calls ``timestep`` until ``--seconds`` have passed and ends
with ``block_until_ready``: a step counts once its result is on the device.
After the window the solver holds u(n), u(n-1) and u(n-2) in its three
buffers: the check compares u(n) with the reference run from the seed's
fields for the same n steps, and the last step with the reference step.
"""

from __future__ import annotations

import time

from bench import harness

WARM_STEPS = 3


def fields(cfg: dict, seed: int):
    """u(0) and u(-dt): seeded noise, made on the device in one call."""
    import jax
    import jax.numpy as jnp

    def make(key):
        k1, k2 = jax.random.split(key)
        shape = (cfg["height"], cfg["width"])
        u1 = jax.random.normal(k1, shape, jnp.float32)
        return u1, u1 + 0.1 * jax.random.normal(k2, shape, jnp.float32)

    return jax.jit(make)(harness.seed_key(seed))


class Fd2dCell:
    def __init__(self, cfg: dict, seed: int):
        from repro.apps.fd2d import FDWave

        self.cfg = cfg
        self.fd = FDWave(model="pallas", width=cfg["width"],
                         height=cfg["height"], radius=cfg["radius"],
                         cfl=cfg["cfl"], dtype=cfg["dtype"])
        u1, u2 = fields(cfg, seed)
        self.fd.o_u1.from_host(u1)
        self.fd.o_u2.from_host(u2)
        self.fd.run(WARM_STEPS)

    def measure(self, seconds: float, tracer) -> dict:
        fd = self.fd

        def sync():                       # the newest step's result
            fd.o_u1.data.block_until_ready()
        n = 0
        t0 = time.perf_counter()
        while True:
            with tracer.span("bench.timestep"):
                fd.timestep()
            n += 1
            t = time.perf_counter() - t0
            tracer.tick(t, sync=sync)
            if t >= seconds:
                break
        sync()
        window = time.perf_counter() - t0
        tracer.stop()
        return {"steps": n, "window_s": window}

    def state(self):
        """(u(n), u(n-1), u(n-2)): after ``timestep`` rotates the buffers,
        u1 holds the newest step, u2 the one before, u3 the one before
        that."""
        fd = self.fd
        return fd.o_u1.data, fd.o_u2.data, fd.o_u3.data


def run(ctx) -> dict:
    cell = Fd2dCell(ctx.cfg, ctx.seed)
    cell.fd.o_u1.data.block_until_ready()
    setup_s = time.perf_counter() - ctx.t_start
    with ctx.compiles.counting():
        res = cell.measure(ctx.seconds, ctx.tracer)
    device = harness.device_info(ctx.devices)
    args = (ctx.cfg, cell.state(), fields(ctx.cfg, ctx.seed),
            WARM_STEPS + res["steps"])
    out = dict(res, setup_s=setup_s, attempted=res["steps"], failed=0,
               device=device, checks=ctx.check.compare(*args))
    if ctx.control:
        out["control"] = ctx.check.compare(*args, control=True)
    return out
