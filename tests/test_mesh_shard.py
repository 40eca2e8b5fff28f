"""Shard-aware kernel language: ShardAxis declarations, the analyzer's
cross-shard checks, the cost model's interconnect column, and ring flash
attention — local single-process form vs the real ``shard_map`` ring on 8
simulated host devices (subprocess, since XLA's device count is fixed
before jax imports; the ``mesh8`` fixture covers the in-process path when
the CI mesh leg forces 8 devices)."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AnalysisError, ShardAxis, Spec, Tile, estimate_cost
from repro.core.lang import defines_namespace
from repro.kernels.flash_attention import flash_attention, ring_flash, \
    ring_flash_attention
from repro.launch.mesh import make_local_mesh


def _ring_specs(which="fwd", **over):
    """The real ring spec(s) at smoke shapes, via the op's own derivation."""
    from repro.kernels.flash_attention.kernel import (ring_flash_bwd_builder,
                                                      ring_flash_fwd_builder)
    rng = np.random.RandomState(0)
    args, params = ring_flash.example(rng)
    _, _, params = ring_flash._resolve(dict(params, **over))
    _, defines, _ = ring_flash._prepare(tuple(args), params)
    D = defines_namespace(defines)
    builder = ring_flash_fwd_builder if which == "fwd" else ring_flash_bwd_builder
    return builder(D), D


# ---------------------------------------------------------------------------
# local (single-process) ring vs the unified flash kernel
# ---------------------------------------------------------------------------

def _qkv(rng, b=1, h=4, hk=2, s=128, d=32):
    return (rng.randn(b, h, s, d).astype("float32"),
            rng.randn(b, hk, s, d).astype("float32"),
            rng.randn(b, hk, s, d).astype("float32"))


def test_local_ring_matches_flash_gqa_fwd_and_grads():
    q, k, v = _qkv(np.random.RandomState(0))
    kw = dict(causal=True, block_q=32, block_kv=32, backend="jnp")
    ref = flash_attention(q, k, v, **kw)
    got = ring_flash_attention(q, k, v, ring_steps=4, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)

    def loss(fn):
        return lambda q_, k_, v_: (fn(q_, k_, v_) ** 2).sum()

    g_ref = jax.grad(loss(lambda *a: flash_attention(*a, **kw)),
                     argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(loss(lambda *a: ring_flash_attention(
        *a, ring_steps=4, **kw)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-4)


def test_local_ring_non_dividing_block_kv():
    # chunk length 32 with block_kv=40: fit_block degrades inside each step
    q, k, v = _qkv(np.random.RandomState(1), s=160)
    kw = dict(causal=True, block_q=64, block_kv=40, backend="jnp")
    ref = flash_attention(q, k, v, **kw)
    got = ring_flash_attention(q, k, v, ring_steps=5, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_local_ring_window_and_prefix():
    q, k, v = _qkv(np.random.RandomState(2))
    for extra in (dict(window=48), dict(prefix_len=24)):
        kw = dict(causal=True, block_q=32, block_kv=32, backend="jnp", **extra)
        ref = flash_attention(q, k, v, **kw)
        got = ring_flash_attention(q, k, v, ring_steps=4, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4, err_msg=str(extra))


def test_local_ring_rejects_non_dividing_steps():
    q, k, v = _qkv(np.random.RandomState(3))
    with pytest.raises(ValueError, match="does not divide"):
        ring_flash_attention(q, k, v, ring_steps=3)


# ---------------------------------------------------------------------------
# ShardAxis declaration: structural validation at Spec construction
# ---------------------------------------------------------------------------

def test_shard_axis_must_bind_a_reduce_axis():
    spec, _ = _ring_specs("fwd")
    with pytest.raises(ValueError, match="reduce"):
        dataclasses.replace(
            spec, shard=dataclasses.replace(spec.shard, axis=0))


def test_shard_axis_rotate_must_name_inputs():
    spec, _ = _ring_specs("fwd")
    with pytest.raises(ValueError, match="rotate"):
        dataclasses.replace(
            spec, shard=dataclasses.replace(spec.shard, rotate=("nope",)))


def test_shard_axis_rejects_unknown_collective():
    with pytest.raises(ValueError, match="collective"):
        ShardAxis(mesh_axis="model", axis=0, extent=2, collective="allgather")


# ---------------------------------------------------------------------------
# analyzer: cross-shard findings fire on seeded-defect bindings only
# ---------------------------------------------------------------------------

def test_ring_without_rotation_is_collective_undeclared():
    spec, _ = _ring_specs("fwd")
    with pytest.raises(AnalysisError) as ei:
        dataclasses.replace(
            spec, shard=dataclasses.replace(spec.shard, rotate=()))
    assert {f.code for f in ei.value.findings} == {"COLLECTIVE_UNDECLARED"}


def test_accumulating_output_without_collective_is_undeclared():
    spec, _ = _ring_specs("fwd")
    with pytest.raises(AnalysisError) as ei:
        dataclasses.replace(
            spec, shard=dataclasses.replace(spec.shard, collective=None,
                                            rotate=()))
    codes = {f.code for f in ei.value.findings}
    assert codes == {"COLLECTIVE_UNDECLARED"}
    # both accumulating outputs (o, lse) are flagged
    assert {f.subject for f in ei.value.findings} == {"o", "lse"}


def test_slot_output_not_declared_sharded_is_mesh_race():
    spec, _ = _ring_specs("bwd")
    with pytest.raises(AnalysisError) as ei:
        dataclasses.replace(
            spec, shard=dataclasses.replace(spec.shard, sharded_outputs=()))
    codes = {f.code for f in ei.value.findings}
    assert codes == {"RACE_MESH_WRITE"}
    assert {f.subject for f in ei.value.findings} == {"dk", "dv"}


def test_shipped_ring_specs_are_clean():
    for which in ("fwd", "bwd"):
        spec, _ = _ring_specs(which)   # construction runs the analyzer
        assert spec.shard is not None and spec.shard.extent == 4


# ---------------------------------------------------------------------------
# cost model: interconnect bytes per declared collective
# ---------------------------------------------------------------------------

def test_ring_comm_bytes_priced_per_shard():
    spec, D = _ring_specs("fwd")
    rep = estimate_cost(spec, D)
    n = spec.shard.extent
    kv_bytes = sum(int(np.prod(t.shape)) * 4
                   for t in spec.inputs if t.name in ("k", "v"))
    assert rep.comm_bytes == (n - 1) * kv_bytes
    assert set(rep.comm_detail) == {"k", "v"}
    assert "comm" in str(rep)


def test_unbound_spec_has_zero_comm():
    from repro.kernels.flash_attention.kernel import flash_fwd_builder
    rng = np.random.RandomState(0)
    args, params = flash_attention.example(rng)
    _, _, params = flash_attention._resolve(params)
    _, defines, _ = flash_attention._prepare(tuple(args), params)
    D = defines_namespace(defines)
    rep = estimate_cost(flash_fwd_builder(D), D)
    assert rep.comm_bytes == 0 and "comm" not in str(rep)


# ---------------------------------------------------------------------------
# the real shard_map ring: 8 simulated host devices (subprocess)
# ---------------------------------------------------------------------------

_RING_SUB = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels.flash_attention import flash_attention, ring_flash_attention
import repro.layers.attention as attn
from repro.launch.mesh import make_mesh
from repro.parallel.context import Rules, use_rules

mesh = make_mesh((8,), ("model",))
rng = np.random.RandomState(0)
q = rng.randn(1, 4, 256, 32).astype("float32")
k = rng.randn(1, 2, 256, 32).astype("float32")
v = rng.randn(1, 2, 256, 32).astype("float32")
sh = NamedSharding(mesh, P(None, None, "model", None))
qd, kd, vd = (jax.device_put(a, sh) for a in (q, k, v))
kw = dict(causal=True, block_q=32, block_kv=32, backend="jnp")

ref = flash_attention(q, k, v, **kw)
got = ring_flash_attention(qd, kd, vd, mesh=mesh, **kw)
fwd = float(jnp.abs(ref - np.asarray(got)).max())
sim = ring_flash_attention(q, k, v, ring_steps=8, **kw)
sim_vs_mesh = float(jnp.abs(np.asarray(sim) - np.asarray(got)).max())

g_ref = jax.grad(lambda *a: (flash_attention(*a, **kw) ** 2).sum(),
                 argnums=(0, 1, 2))(q, k, v)
g_got = jax.grad(lambda *a: (ring_flash_attention(
    *a, mesh=mesh, **kw) ** 2).sum(), argnums=(0, 1, 2))(qd, kd, vd)
grads = [float(jnp.abs(a - np.asarray(b)).max())
         for a, b in zip(g_ref, g_got)]

# layer routing: gqa_forward takes the declared ring under Rules(ring_axis=)
class Cfg:
    d_model = 64; n_heads = 4; n_kv_heads = 2; resolved_head_dim = 16
    pos_embed = "rope"; rope_theta = 1e4; window = None
params = attn.gqa_init(jax.random.PRNGKey(0), Cfg, jnp.float32)
x = jnp.asarray(rng.randn(2, 64, 64), jnp.float32)
y0 = attn.gqa_forward(params, x, Cfg)
with use_rules(Rules(batch_axes=(), mesh=mesh, ring_axis="model")):
    y1 = attn.gqa_forward(params, x, Cfg)
layer = float(jnp.abs(y0 - np.asarray(y1)).max())
print(json.dumps(dict(devices=jax.device_count(), fwd=fwd, grads=grads,
                      sim_vs_mesh=sim_vs_mesh, layer=layer)))
"""


def test_ring_shard_map_matches_single_device_subprocess():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    res = subprocess.run([sys.executable, "-c", _RING_SUB],
                         capture_output=True, text=True, timeout=420, env=env)
    assert res.returncode == 0, res.stderr[-3000:]
    rec = json.loads(res.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 8
    assert rec["fwd"] < 1e-5, rec
    assert rec["sim_vs_mesh"] < 1e-5, rec
    assert all(g < 1e-4 for g in rec["grads"]), rec
    assert rec["layer"] < 1e-4, rec


_ENGINE_SUB = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np, jax

from repro.configs import get_config, reduced
from repro.launch.mesh import make_mesh
from repro.layers.common import use_kernel_backend
from repro.models import LM
from repro.serving import Engine

cfg = reduced(get_config("internlm2_1_8b"))       # 4 heads, 2 kv heads
mesh = make_mesh((2, 2), ("data", "model"))
rng = np.random.RandomState(0)
prompts = [rng.randint(0, cfg.vocab_size, n).tolist() for n in (5, 9, 3, 7)]
out, logits, jaxprs = {}, {}, {}
with use_kernel_backend("pallas"):
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    for name, m in (("local", None), ("mesh", mesh)):
        eng = Engine(model, params, batch=4, max_len=32, page_size=8, mesh=m)
        rids = [eng.submit(p, 4) for p in prompts]
        toks = jax.ShapeDtypeStruct((4, 1), np.int32)
        jaxprs[name] = str(jax.make_jaxpr(eng.decode_step)(
            eng.params, eng.cache, toks))
        steps = []
        while not eng.idle:
            eng.step()
            if eng.last_decode is not None:
                steps.append(np.asarray(eng.last_decode[0], np.float32))
                eng.last_decode = None
        res = eng.drain()
        out[name] = [res[r] for r in rids]
        logits[name] = steps
err = max(float(np.abs(a - b).max())
          for a, b in zip(logits["local"], logits["mesh"]))
print(json.dumps(dict(devices=jax.device_count(), same=out["local"] == out["mesh"],
                      err=err, steps=len(logits["mesh"]),
                      local_map="shard_map" in jaxprs["local"],
                      mesh_map="shard_map" in jaxprs["mesh"])))
"""


def test_mesh_engine_runs_kernels_per_shard_subprocess():
    """The sharded Engine on the kernel path: every kernel call runs inside
    shard_map on a multi-device mesh (a compiled Mosaic kernel cannot be
    partitioned by GSPMD), and serving matches the unsharded Engine."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    res = subprocess.run([sys.executable, "-c", _ENGINE_SUB],
                         capture_output=True, text=True, timeout=420, env=env)
    assert res.returncode == 0, res.stderr[-3000:]
    rec = json.loads(res.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 4
    assert rec["mesh_map"] and not rec["local_map"], rec
    assert rec["same"], rec
    assert rec["steps"] > 0 and rec["err"] < 1e-4, rec


def test_ring_flash_mesh8_fwd_and_bwd(mesh8):
    """In-process shard_map parity when the CI mesh leg forces 8 devices."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    q, k, v = _qkv(np.random.RandomState(4), s=256)
    sh = NamedSharding(mesh8, P(None, None, "model", None))
    qd, kd, vd = (jax.device_put(a, sh) for a in (q, k, v))
    kw = dict(causal=True, block_q=32, block_kv=32, backend="jnp")
    ref = flash_attention(q, k, v, **kw)
    got = ring_flash_attention(qd, kd, vd, mesh=mesh8, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    g_ref = jax.grad(lambda *a: (flash_attention(*a, **kw) ** 2).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(lambda *a: (ring_flash_attention(
        *a, mesh=mesh8, **kw) ** 2).sum(), argnums=(0, 1, 2))(qd, kd, vd)
    for a, b in zip(g_ref, g_got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-4)


def test_ring_flash_attention_rejects_contradictory_steps(mesh8):
    q, k, v = _qkv(np.random.RandomState(5), s=256)
    with pytest.raises(ValueError, match="contradicts"):
        ring_flash_attention(q, k, v, mesh=mesh8, ring_steps=4)


# ---------------------------------------------------------------------------
# satellites: shardings dedupe + greedy serve step
# ---------------------------------------------------------------------------

def test_make_shardings_returns_params_shape():
    from repro.configs import get_config, reduced
    from repro.models import LM
    from repro.parallel.steps import make_shardings
    cfg = reduced(get_config("llama3_2_1b"))
    model = LM(cfg)
    mesh = make_local_mesh(data=1, model=1)
    _, _, rules, params_shape = make_shardings(model, mesh)
    want = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda a, b: (a.shape, a.dtype) == (b.shape, b.dtype),
                        params_shape, want)
    assert rules.ring_axis is None          # ring is opt-in
    _, _, ring_rules, _ = make_shardings(model, mesh, ring=True)
    assert ring_rules.ring_axis is None     # 1-way model axis: nothing to ring


def test_serve_step_greedy_routes_through_greedy_step():
    from repro.configs import get_config, reduced
    from repro.models import LM
    from repro.parallel.steps import build_serve_step
    cfg = reduced(get_config("llama3_2_1b"))
    model = LM(cfg)
    mesh = make_local_mesh(data=1, model=1)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.RandomState(6).randint(
        0, cfg.vocab_size, (2, 1)))

    step, sh = build_serve_step(model, mesh, batch=2, max_len=8,
                                greedy=False)
    assert sh["greedy"] is False
    cache = model.init_cache(2, 8)
    logits, _ = step(params, cache, tokens)

    # greedy is the DEFAULT now (flipped with the serving engine)
    gstep, gsh = build_serve_step(model, mesh, batch=2, max_len=8)
    assert gsh["greedy"] is True
    nxt, glogits, _ = gstep(params, model.init_cache(2, 8), tokens)
    np.testing.assert_allclose(np.asarray(glogits), np.asarray(logits),
                               rtol=1e-5, atol=1e-5)
    want = np.argmax(np.asarray(logits)[:, :cfg.vocab_size], axis=-1)
    np.testing.assert_array_equal(np.asarray(nxt), want)
