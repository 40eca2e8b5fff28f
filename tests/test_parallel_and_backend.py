"""Parallel-substrate and kernel-backend tests.

- model forward parity: kernel_backend="pallas" (interpret) vs "jnp" oracle
  through REAL models (flash attention / rmsnorm / ssm_scan inside the LM);
- cache partition specs (head-dim fallback, seq sharding for batch=1);
- collective-bytes HLO parser;
- a miniature dry-run in a subprocess (8 fake devices, 2x2x2 mesh) proving
  the lower+compile path end-to-end without the 512-device sweep.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, reduced
from repro.layers.common import use_kernel_backend
from repro.models import LM


@pytest.mark.parametrize("arch", ["llama3_2_1b", "falcon_mamba_7b"])
def test_model_forward_pallas_kernels_match_jnp(arch):
    cfg = reduced(get_config(arch))
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.RandomState(1).randint(
        0, cfg.vocab_size, (2, 16)))
    with use_kernel_backend("jnp"):
        ref, _ = model.forward(params, tokens)
    with use_kernel_backend("pallas"):
        got, _ = model.forward(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_model_grads_pallas_kernels_match_jnp():
    cfg = reduced(get_config("llama3_2_1b"))
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(2))
    batch = {"tokens": jnp.asarray(np.random.RandomState(3).randint(
        0, cfg.vocab_size, (2, 16)))}

    def loss(params, backend):
        with use_kernel_backend(backend):
            return model.loss(params, batch)[0]

    g_ref = jax.grad(lambda p: loss(p, "jnp"))(params)
    g_pal = jax.grad(lambda p: loss(p, "pallas"))(params)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pal)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-3, atol=5e-3)


# ---------------------------------------------------------------------------
# cache partition specs
# ---------------------------------------------------------------------------

class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_cache_specs_seq_fallback_when_few_kv_heads():
    from repro.parallel.steps import cache_pspecs
    # granite: kv=8 < model 16 -> cache SEQ dim shards over model (§Perf it2:
    # head_dim sharding forced GSPMD to replicate the cache per decode step)
    cfg = get_config("granite_3_8b")
    model = LM(cfg)
    specs = cache_pspecs(model, FakeMesh({"data": 16, "model": 16}),
                         batch=128, max_len=32768)
    k_spec = specs["stacks"][0]["k"]
    assert k_spec == P(None, ("data",), None, "model", None), k_spec


def test_cache_specs_seq_sharding_for_batch1():
    from repro.parallel.steps import cache_pspecs
    cfg = get_config("mixtral_8x22b")   # window cache, batch 1
    model = LM(cfg)
    specs = cache_pspecs(model, FakeMesh({"data": 16, "model": 16}),
                         batch=1, max_len=524288)
    k_spec = specs["stacks"][0]["k"]
    # batch unshardable -> window seq shards over data AND model
    assert k_spec == P(None, None, None, ("data", "model"), None), k_spec


def test_cache_specs_mla_lora_sharding():
    from repro.parallel.steps import cache_pspecs
    cfg = get_config("deepseek_v2_lite")
    model = LM(cfg)
    specs = cache_pspecs(model, FakeMesh({"data": 16, "model": 16}),
                         batch=128, max_len=32768)
    assert specs["stacks"][-1]["ckv"] == P(None, ("data",), None, "model")


# ---------------------------------------------------------------------------
# HLO collective parser
# ---------------------------------------------------------------------------

def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = textwrap.dedent("""
      %p0 = f32[16,128]{1,0} parameter(0)
      %b0 = bf16[8,256]{1,0} convert(%p0)
      %ar = f32[16,128]{1,0} all-reduce(%p0), replica_groups={}
      %ag = (bf16[8,256]{1,0}, bf16[8,256]{1,0}) all-gather-start(%b0), dimensions={0}
      %agd = bf16[64,256]{1,0} all-gather-done(%ag)
      %cp = bf16[8,256]{1,0} collective-permute(%b0), source_target_pairs={{0,1}}
    """)
    out = collective_bytes(hlo)
    assert out["bytes"]["all-reduce"] == 16 * 128 * 4
    assert out["bytes"]["all-gather"] == 8 * 256 * 2      # start only
    assert out["bytes"]["collective-permute"] == 8 * 256 * 2
    assert out["counts"]["all-reduce"] == 1


# ---------------------------------------------------------------------------
# miniature dry-run (subprocess so XLA sees 8 devices)
# ---------------------------------------------------------------------------

_MINI = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.models import LM
from repro.optim import AdamW, WarmupCosine
from repro.launch.mesh import make_mesh
from repro.parallel.steps import build_serve_step, build_train_step

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = reduced(get_config("%ARCH%"))
model = LM(cfg, remat="full")
bs = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
if cfg.frontend:
    bs["prefix_embeddings"] = jax.ShapeDtypeStruct(
        (8, cfg.num_prefix_embeddings, cfg.d_model), jnp.dtype(cfg.dtype))
opt = AdamW(schedule=WarmupCosine())
step_fn, sh = build_train_step(model, opt, mesh, zero1=True, batch_shapes=bs)
p = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
o = jax.eval_shape(opt.init, p)
ctr = step_fn.lower(p, o, bs).compile()

serve_fn, ssh = build_serve_step(model, mesh, batch=8, max_len=64)
cache = jax.eval_shape(lambda: model.init_cache(8, 64))
csr = serve_fn.lower(p, cache, jax.ShapeDtypeStruct((8, 1), jnp.int32)).compile()
ca = ctr.cost_analysis()
if isinstance(ca, (list, tuple)): ca = ca[0]
print(json.dumps({"train_flops": float(ca.get("flops", 0)), "ok": True}))
"""


@pytest.mark.parametrize("arch", ["llama3_2_1b", "zamba2_7b",
                                  "deepseek_v2_lite"])
def test_mini_multipod_dryrun_subprocess(arch):
    code = _MINI.replace("%ARCH%", arch)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=420, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["train_flops"] > 0


# ---------------------------------------------------------------------------
# pipeline parallelism (GPipe over a "pipe" mesh axis; subprocess, 8 devices)
# ---------------------------------------------------------------------------

_PIPE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.parallel.pipeline import pipeline_apply, split_stages

mesh = make_mesh((4,), ("pipe",))
L, d, M, mb = 8, 16, 6, 4
rng = np.random.RandomState(0)
stacked = {"w": jnp.asarray(rng.randn(L, d, d) * 0.2, jnp.float32),
           "b": jnp.asarray(rng.randn(L, d) * 0.1, jnp.float32)}
x = jnp.asarray(rng.randn(M, mb, d), jnp.float32)

def layer(p, x):
    return x + jnp.tanh(x @ p["w"] + p["b"])

def stage_fn(params_i, x):   # params_i: (L/S, ...)
    def body(x, lp):
        return layer(lp, x), None
    x, _ = jax.lax.scan(body, x, params_i)
    return x

# sequential reference
def seq_apply(stacked, x):
    def body(x, lp):
        return layer(lp, x), None
    y, _ = jax.lax.scan(body, x, stacked)
    return y

stages = split_stages(stacked, 4)
stages = jax.device_put(stages, jax.tree.map(
    lambda _: NamedSharding(mesh, P("pipe")), stages))

y_pipe = pipeline_apply(stage_fn, stages, x, mesh=mesh)
y_seq = jax.vmap(lambda xb: seq_apply(stacked, xb))(x)
err_fwd = float(jnp.abs(y_pipe - y_seq).max())

# gradients through the pipeline must match the sequential model
def loss_pipe(stages):
    return (pipeline_apply(stage_fn, stages, x, mesh=mesh) ** 2).sum()

def loss_seq(stacked):
    return (jax.vmap(lambda xb: seq_apply(stacked, xb))(x) ** 2).sum()

g_pipe = jax.grad(loss_pipe)(stages)
g_seq = split_stages(jax.grad(loss_seq)(stacked), 4)
err_g = max(float(jnp.abs(a - jax.device_put(b, a.sharding)).max())
            for a, b in zip(jax.tree.leaves(g_pipe), jax.tree.leaves(g_seq)))
print(json.dumps({"ok": True, "err_fwd": err_fwd, "err_grad": err_g}))
"""


def test_pipeline_parallel_matches_sequential_subprocess():
    out = _run_pipe_sub(_PIPE)
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["ok"]
    assert rec["err_fwd"] < 1e-5, rec
    assert rec["err_grad"] < 1e-4, rec


def _run_pipe_sub(code, timeout=420):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


def test_mamba2_pallas_kernel_route_matches_ssd():
    """zamba2 backbone through the fused ssm kernel == the SSD jnp path."""
    cfg = reduced(get_config("zamba2_7b"))
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(4))
    tokens = jnp.asarray(np.random.RandomState(5).randint(
        0, cfg.vocab_size, (2, 16)))
    with use_kernel_backend("jnp"):
        ref, _ = model.forward(params, tokens)
    with use_kernel_backend("pallas"):
        got, _ = model.forward(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_shard_activation_raises_on_invalid_constraint_with_mesh():
    """With a mesh in the rules the constraint is applied, so an invalid
    one is an error instead of a silently dropped placement."""
    from repro.launch.mesh import make_local_mesh
    from repro.parallel.context import Rules, shard_activation, use_rules

    mesh = make_local_mesh(data=1, model=1)
    x = jnp.ones((4,))          # rank 1: the (batch, seq, d) spec cannot fit
    with use_rules(Rules(batch_axes=("data",), mesh=mesh)):
        with pytest.raises(ValueError, match="sharding"):
            shard_activation(x, "act_btd")
        y = shard_activation(jnp.ones((2, 4, 8)), "act_btd")
    assert y.shape == (2, 4, 8)


def test_kernel_backend_follows_platform_per_op(monkeypatch):
    """Kernels on a TPU, references elsewhere; an op the TPU compiler
    refuses (``TPU_REFUSED``) keeps its reference on a TPU too, and a
    pinned backend wins everywhere."""
    from repro.layers.common import TPU_REFUSED, kernel_backend

    assert "ssm_scan" in TPU_REFUSED
    assert kernel_backend() == kernel_backend("ssm_scan") == "jnp"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernel_backend() == kernel_backend("flash_attention") == "pallas"
    assert kernel_backend("ssm_scan") == "jnp"
    with use_kernel_backend("pallas"):
        assert kernel_backend("ssm_scan") == "pallas"
    with use_kernel_backend("jnp"):
        assert kernel_backend() == "jnp"
