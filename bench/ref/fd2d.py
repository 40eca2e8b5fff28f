"""The plain reference of the FD wave solver (the OCCA paper's section 4.1):
u_tt = u_xx + u_yy on the periodic square [-1, 1]^2, leapfrog in time,
with the order-2r central difference in space. Grid spacing ``dx = 2 / w``
and step ``dt = cfl * dx / sqrt(2)``. Written from that definition alone;
it imports nothing of the program.
"""

from __future__ import annotations

import functools
import math
from fractions import Fraction

import jax
import jax.numpy as jnp


def second_derivative_weights(r: int) -> list[float]:
    """Central weights w_k, k = -r..r, of u''(0) ~ sum_k w_k u(k) / dx^2,
    exact for polynomials of degree 2r: solved in exact arithmetic from
    sum_k w_k k^j = j! [j == 2]."""
    ks = list(range(-r, r + 1))
    n = len(ks)
    a = [[Fraction(k) ** j for k in ks] + [Fraction(2 if j == 2 else 0)]
         for j in range(n)]
    for c in range(n):                       # Gauss-Jordan
        p = next(i for i in range(c, n) if a[i][c] != 0)
        a[c], a[p] = a[p], a[c]
        a[c] = [x / a[c][c] for x in a[c]]
        for i in range(n):
            if i != c and a[i][c] != 0:
                a[i] = [x - a[i][c] * y for x, y in zip(a[i], a[c])]
    return [float(row[-1]) for row in a]


def steps(cfg: dict) -> tuple[float, float]:
    dx = 2.0 / cfg["width"]
    return dx, cfg["cfl"] * dx / math.sqrt(2.0)


def _step(cfg: dict, u1, u2, dtype):
    dx, dt = steps(cfg)
    r = cfg["radius"]
    w = second_derivative_weights(r)
    lap = jnp.zeros_like(u1)
    for k in range(-r, r + 1):
        lap = lap + jnp.asarray(w[k + r], dtype) * (
            jnp.roll(u1, -k, axis=0) + jnp.roll(u1, -k, axis=1))
    lap = lap * jnp.asarray(1.0 / (dx * dx), dtype)
    return 2 * u1 - u2 + jnp.asarray(dt * dt, dtype) * lap


def step(cfg: dict, u1, u2, dtype=jnp.float32):
    """u(t + dt) from u(t) = u1 and u(t - dt) = u2, computed in ``dtype``."""
    return _step(cfg, u1.astype(dtype), u2.astype(dtype), dtype).astype(
        jnp.float32)


@functools.lru_cache(maxsize=None)
def _run_fn(width: int, radius: int, cfl: float, dtype):
    cfg = {"width": width, "radius": radius, "cfl": cfl}

    def body(_, uu):
        return _step(cfg, uu[0], uu[1], dtype), uu[0]
    return jax.jit(lambda u1, u2, n: jax.lax.fori_loop(0, n, body, (u1, u2)))


def run(cfg: dict, u1, u2, n: int, dtype=jnp.float32):
    """u(t + n dt) from u(t) = u1 and u(t - dt) = u2: ``n`` steps, each
    computed and stored in ``dtype``; one program serves every ``n``."""
    fn = _run_fn(cfg["width"], cfg["radius"], cfg["cfl"], jnp.dtype(dtype))
    return fn(u1.astype(dtype), u2.astype(dtype), n)[0].astype(jnp.float32)
