"""Necessary work of one ``fd2d`` call: one leapfrog step of the order-2r
stencil on an ``h x w`` field. It reads u(t) and u(t - dt) once and writes
u(t + dt) once; the halo is re-read from on-chip memory, not from HBM.
Per node: 2(2r + 1) multiply-adds for the two second derivatives, then
2u - u_prev + dt^2 lap (3 operations).
"""

from __future__ import annotations


def work(h: int, w: int, r: int, itemsize: int = 4) -> tuple[float, float]:
    """(operations, bytes) of one step."""
    nodes = h * w
    return float(nodes * (4 * (2 * r + 1) + 3)), float(3 * nodes * itemsize)
