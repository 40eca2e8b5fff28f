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


# ---------------------------------------------------------------------------
# internlm2-20b's shape, tensor-parallel over 4 simulated host devices
# ---------------------------------------------------------------------------

_TP4_SUB = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np, jax

sys.path.insert(0, sys.argv[1])
from bench.drivers import serve
from bench.ref import dense_gqa
from repro.launch.mesh import make_mesh
from repro.models import LM
from repro.parallel.steps import build_paged_serve_step, make_shardings
from repro.runtime import spans
from repro.serving import Engine

# internlm2-20b's block at a tiny width: 24 heads over 4 kv heads (its
# 6:1), head dim 16, rms eps and rope theta as published
cfg = {"name": "tiny20b", "hidden_size": 384, "intermediate_size": 512,
       "num_attention_heads": 24, "num_key_value_heads": 4,
       "num_hidden_layers": 2, "vocab_size": 512, "rms_norm_eps": 1e-5,
       "rope_theta": 1e6, "torch_dtype": "bfloat16"}
model = LM(serve.arch_config(cfg))
mesh = make_mesh((1, 4), ("data", "model"))
weights = jax.jit(lambda k: dense_gqa.init_weights(cfg, k),
                  out_shardings=make_shardings(model, mesh)[0])(
    jax.random.key(3))

made = []                     # per cache built: were its leaves traced?
init_cache = model.init_paged_cache
def spy(*a, **k):
    out = init_cache(*a, **k)
    made.append(all(isinstance(x, jax.core.Tracer)
                    for x in jax.tree.leaves(out)))
    return out
model.init_paged_cache = spy

spans.reset()
eng = Engine(model, weights, batch=4, max_len=128, page_size=16, mesh=mesh)
want = build_paged_serve_step(model, mesh, batch=4)[1]["cache"]
pool = eng.cache["stacks"][0]["kp"]
rng = np.random.default_rng(0)
prompts = [rng.integers(0, 512, n).tolist() for n in (37, 16, 9, 50, 23)]
rids = [eng.submit(p, 8) for p in prompts]
rows = {r: [] for r in rids}
deltas = []
while not eng.idle:
    before = spans.counters().get("engine.collective_bytes", 0)
    eng.step()
    if eng.last_decode is not None:
        deltas.append(spans.counters()["engine.collective_bytes"] - before)
        logits, slots = eng.last_decode
        for r, s in slots.items():
            rows[r].append(np.asarray(logits[s], np.float32)[:512])
        eng.last_decode = None
# the same lengths again: every program was traced in the first round
traced = []
def on_trace(event, secs, **kw):
    if event == "/jax/core/compile/jaxpr_trace_duration":
        traced.append(kw.get("fun_name"))
jax.monitoring.register_event_duration_secs_listener(on_trace)
for p in prompts:
    eng.submit(rng.integers(0, 512, len(p)).tolist(), 8)
eng.drain()
jax.monitoring.unregister_event_duration_listener(on_trace)
rel, gaps, agree = [], [], []
for r, p in zip(rids, prompts):
    toks = np.asarray(p + eng.result(r), np.int32)
    ref = np.asarray(dense_gqa.logits(cfg, weights, toks))
    g, _ = dense_gqa.served_gaps(cfg, weights, toks)
    gaps.extend(np.asarray(g)[len(p) - 1:len(toks) - 1].tolist())
    for k, row in enumerate(rows[r]):       # decode k predicts token k + 1
        want_row = ref[len(p) + k]
        rel.append(float(np.linalg.norm(row - want_row)
                         / np.linalg.norm(want_row)))
        agree.append(int(row.argmax() == want_row.argmax()))
recs = spans.records()
shard = [x["attrs"] for x in recs if x["name"] == "engine.shard"]
print(json.dumps(dict(
    devices=jax.device_count(), made=made,
    sharded=all(x.sharding.is_equivalent_to(s, x.ndim) for x, s in
                zip(jax.tree.leaves(eng.cache), jax.tree.leaves(want))),
    pool_spec=[str(a) for a in pool.sharding.spec],
    pool_shard=list(pool.sharding.shard_shape(pool.shape)),
    params_kept=all(a is b for a, b in zip(jax.tree.leaves(eng.params),
                                           jax.tree.leaves(weights))),
    shard=shard, engine=eng.engine_id,
    bytes_chip0=sum(x.addressable_shards[0].data.nbytes
                    for x in jax.tree.leaves((eng.params, eng.cache))),
    decode_chips=sorted({x["attrs"]["chips"] for x in recs
                         if x["name"] == "engine.decode"}),
    deltas=deltas, compiles=spans.counters().get("compile.engine_decode"),
    traced=traced,
    rel=max(rel), steps=len(rel), agree=sum(agree),
    gap_mean=float(np.mean(gaps)), gap_max=float(np.max(gaps)))))
"""


@pytest.fixture(scope="module")
def tp4():
    """A tiny internlm2-20b-shaped model served by ``Engine(mesh=(1, 4))``
    on 4 simulated host devices, its weights made from a seed by the
    benchmark's weight maker directly in the decode step's shardings."""
    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    res = subprocess.run([sys.executable, "-c", _TP4_SUB, repo],
                         capture_output=True, text=True, timeout=420, env=env)
    assert res.returncode == 0, res.stderr[-3000:]
    rec = json.loads(res.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 4
    return rec


def test_tp4_engine_serves_the_reference_subprocess(tp4):
    """Every decode step's logits against the float32 reference
    (``bench/ref/dense_gqa.py``) over the prompt and the served tokens.
    The program runs in bf16: its rounding (8 bits of mantissa, over two
    layers and a sharded head) keeps the logits within a few percent of
    the reference, so rel-L2 below 5e-2; greedy serving takes the
    program's own best token, which lies at most a rounding below the
    reference's best, so the served tokens' gaps average below 4e-3 (the
    benchmark's limit for internlm2) and agree with the reference's
    argmax nearly everywhere."""
    assert tp4["steps"] == 5 * 7
    assert tp4["rel"] < 5e-2, tp4
    assert tp4["gap_mean"] < 4e-3, tp4
    assert tp4["agree"] >= tp4["steps"] - 2, tp4


def test_tp4_cache_is_made_in_its_shards_subprocess(tp4):
    """The cache is made inside the sharded program (its leaves were
    tracers, never arrays on one device), lies in the decode step's
    shardings, with the pool split over KV heads; weights already in the
    step's shardings were not placed again."""
    assert tp4["made"] == [True]
    assert tp4["sharded"]
    assert tp4["pool_spec"][2] == "model"
    assert tp4["pool_shard"][2] == 1                  # 4 kv heads / 4
    assert tp4["params_kept"]


def test_tp4_shard_span_and_decode_chips_subprocess(tp4):
    assert tp4["shard"] == [{"engine": tp4["engine"], "chips": 4,
                             "bytes_per_chip": tp4["bytes_chip0"]}]
    assert tp4["decode_chips"] == [4]


def test_tp4_engine_traces_no_program_again_subprocess(tp4):
    """Prompts of lengths already served trace no program: every program
    that returns cache state returns it in the cache's own shardings, so
    the next program sees the shardings it was traced with (an equivalent
    but unequal sharding would trace it again, inside a serving window)."""
    assert tp4["traced"] == []


def test_tp4_collective_bytes_per_decode_step_subprocess(tp4):
    """Every decode step adds the same positive bytes, read from the one
    compile of the step (reading them compiled nothing more)."""
    deltas = tp4["deltas"]
    assert len(deltas) >= 7 and deltas[0] > 0
    assert set(deltas) == {deltas[0]}
    assert tp4["compiles"] == 1


_HLO = """
%body (p: (s32[], bf16[8,64])) -> (s32[], bf16[8,64]) {
  %p = (s32[], bf16[8,64]) parameter(0)
  %ar = bf16[8,64]{1,0} all-reduce(%x), channel_id=1, replica_groups=[1,4]<=[4], to_apply=%add, metadata={op_name="jit(f)/while/body/dot_general"}
  %ag.1 = bf16[64,1024]{1,0} all-gather(%h), channel_id=2, replica_groups={{0,1,2,3}}, dimensions={1}, metadata={op_name="jit(f)/shard_map"}
  ROOT %t = (s32[], bf16[8,64]) tuple(%i, %ar)
}

%cond (p: (s32[], bf16[8,64])) -> pred[] {
  %c = s32[]{:T(128)} constant(3)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%piece (a: bf16[64,256]) -> bf16[64,1024] {
  %ag.2 = bf16[64,1024]{1,0} all-gather(%a), channel_id=2, replica_groups={{0,1,2,3}}, dimensions={1}, metadata={op_name="jit(f)/shard_map"}
}

ENTRY %main (x: bf16[8,64]) -> bf16[8,64] {
  %w = (s32[], bf16[8,64]) while(%init), condition=%cond, body=%body
  %f = bf16[64,1024] fusion(%a), kind=kLoop, calls=%piece
  %cp = (bf16[4,4]{1,0}, bf16[4,4]{1,0}, u32[]) collective-permute-start(%y), channel_id=3, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
  %cpd = bf16[4,4]{1,0} collective-permute-done(%cp)
  ROOT %r = bf16[8,64] copy(%x)
}
"""


def test_collective_bytes_counts_loops_and_split_collectives_once():
    """The loop's all-reduce runs 3 times (its condition's bound); the
    all-gather split over a loop piece and a fusion shares one channel and
    runs once; a permute counts its buffer, its done half nothing."""
    from repro.parallel.steps import collective_bytes

    ar = 2 * (8 * 64 * 2) * 3 / 4
    ag = (64 * 1024 * 2) * 3 / 4
    cp = 4 * 4 * 2
    assert collective_bytes(_HLO, 4) == int(3 * ar + ag + cp)
