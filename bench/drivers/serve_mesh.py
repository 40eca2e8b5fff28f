"""Serving cells on a mesh: one tensor-parallel replica of the model over
the run's chips, served by ``Engine(mesh=...)``.

Everything but the placement is ``serve.py``'s: the same traffic, warm-up,
window, sampled sequences and check. Here the weights are made from the
seed directly in the program's parameter shardings (no chip ever holds
them whole), and the engine runs the sharded decode step of
``parallel/steps.py::build_paged_serve_step`` on a ``("data", "model")``
mesh of the shape the configuration's ``serving.mesh`` gives. The check
then runs the float32 reference over the sharded weights, once the
engine's pool is freed.
"""

from __future__ import annotations

from bench import harness
from bench.drivers import serve
from bench.ref import dense_gqa


class MeshServeCell(serve.ServeCell):
    def __init__(self, cfg: dict, mix: dict, devices):
        from repro.launch.mesh import make_mesh

        shape = tuple(cfg["serving"]["mesh"])
        if len(devices) != shape[0] * shape[1]:
            raise harness.NoChip(f"the mesh {shape} needs "
                                 f"{shape[0] * shape[1]} chips, "
                                 f"{len(devices)} given")
        # the parameter layout check of a one-chip cell; nothing is placed
        super().__init__(cfg, mix, devices[:1])
        self.devices = devices
        self.mesh = make_mesh(shape, ("data", "model"), devices=devices)

    def load(self, seed: int):
        import jax

        from repro.parallel.steps import make_shardings

        shardings = make_shardings(self.model, self.mesh)[0]
        self.weights = jax.jit(
            lambda k: dense_gqa.init_weights(self.cfg, k),
            out_shardings=shardings)(harness.seed_key(seed))
        jax.block_until_ready(self.weights)

    def engine(self):
        from repro.serving import Engine

        s = self.cfg["serving"]
        return Engine(self.model, self.weights, batch=s["batch"],
                      max_len=s["max_len"], mesh=self.mesh)


def run(ctx) -> dict:
    """``serve.run`` with the mesh cell in the one-chip cell's place."""
    one_chip = serve.ServeCell
    serve.ServeCell = MeshServeCell
    try:
        return serve.run(ctx)
    finally:
        serve.ServeCell = one_chip
