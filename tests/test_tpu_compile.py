"""The main path's kernels compile for a TPU v5e, without a chip attached.

Each kernel test compiles one kernel at the serving/training shapes of
internlm2-1.8B (B=8, H=16, Hk=8, D=128, bf16) — or the paper's fd2d at
4096^2 — for a described ``v5e:2x2`` topology, and asserts the compiled
program contains the Mosaic kernel (``tpu_custom_call``). This is what
interpret mode cannot check: the TPU compiler's block-alignment and VMEM
rules. The model tests compile every configured architecture (published
widths, depth cut) with the layer backend a TPU picks, and the sharded
Engine and ring prefill on the 2x2 mesh. The topology is described inside
a fixture (never at import), so every test worker collects the same tests
and only the one that runs this file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.core.lang import kernel_names
from repro.kernels.apps import fd2d
from repro.kernels.flash_attention import flash_attention, \
    paged_decode_attention
from repro.kernels.lm_head import lm_head_ce, lm_head_logits
from repro.kernels.rmsnorm import rmsnorm

B, H, HK, D = 8, 16, 8, 128
VOCAB, VPAD, DMODEL = 92544, 92672, 2048
BF16 = jnp.bfloat16
COMPILED = dict(backend="pallas", interpret=False)


@pytest.fixture(scope="module")
def topology():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topology):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topology.devices[0])


@pytest.fixture
def chip_defaults(topology, monkeypatch):
    """What a TPU process sees: compiled kernels on the default device and
    the platform's per-op layer backend (``kernel_backend``)."""
    import repro.core.device as device

    monkeypatch.setitem(device._DEFAULT_DEVICES, ("pallas", None),
                        device.Device("pallas", interpret=False))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _abstract(tree, shardings):
    return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=s), tree, shardings)


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_decode_paged_compiles(one_chip):
    page, nsp = 128, 16
    npages = B * nsp + 1
    text = _compile_text(
        lambda q, k, v, t, n, p: paged_decode_attention(
            q, k, v, block_table=t, kv_len=n, pos_pages=p, **COMPILED),
        one_chip, ((B, H, 1, D), BF16), ((npages, HK, page, D), BF16),
        ((npages, HK, page, D), BF16), ((B, nsp), jnp.int32),
        ((B,), jnp.int32), ((npages, page), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention_compiles(one_chip, grad):
    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, **COMPILED)

    fn = fwd
    if grad:
        fn = jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                      argnums=(0, 1, 2))
    s = 2048
    text = _compile_text(fn, one_chip, ((4, H, s, D), BF16),
                         ((4, HK, s, D), BF16), ((4, HK, s, D), BF16))
    assert "tpu_custom_call" in text


def test_lm_head_logits_compiles(one_chip):
    text = _compile_text(
        lambda x, w: lm_head_logits.raw(x, w, vocab=VOCAB, **COMPILED),
        one_chip, ((B, DMODEL), BF16), ((DMODEL, VPAD), BF16))
    assert "tpu_custom_call" in text


def test_lm_head_ce_fwd_bwd_compiles(one_chip):
    rows = 4 * 2047
    fn = jax.grad(lambda x, w, lab: lm_head_ce(
        x, w, lab, vocab=VOCAB, **COMPILED).sum(), argnums=(0, 1))
    text = _compile_text(fn, one_chip, ((rows, DMODEL), BF16),
                         ((DMODEL, VPAD), BF16), ((rows, 1), jnp.int32))
    assert "tpu_custom_call" in text


def test_rmsnorm_compiles(one_chip):
    # a ragged row count (a prefill of 881 tokens): rows pad to the block
    text = _compile_text(lambda x, w: rmsnorm(x, w, **COMPILED), one_chip,
                         ((881, DMODEL), BF16), ((DMODEL,), jnp.float32))
    assert "tpu_custom_call" in text


def test_fd2d_compiles_at_4096(one_chip):
    n = 4096
    text = _compile_text(
        lambda a, b: fd2d(a, b, weights=(1.0, -2.0, 1.0), dx=2.0 / n,
                          dt=1e-4, **COMPILED),
        one_chip, ((n, n), jnp.float32), ((n, n), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch", ARCHS)
def test_model_programs_compile(one_chip, chip_defaults, arch):
    """Every architecture at its published widths (depth cut to one of each
    block kind) compiles its train gradient, a ragged prefill and a decode
    step on one v5e with the backend a TPU picks for each layer. Attention
    runs the flash kernels, norms and the head run theirs; ``ssm_scan`` —
    refused by the TPU compiler — stays on its XLA scan."""
    import dataclasses

    from repro.models import LM

    cfg = get_config(arch)
    cfg = dataclasses.replace(
        cfg, n_layers=cfg.shared_attn_every or min(cfg.n_layers, 2))
    model = LM(cfg)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    s = 300                                  # ragged: the flash op pads it

    def tokens(n):
        return jax.ShapeDtypeStruct((1, n), jnp.int32, sharding=one_chip)

    prefix = None
    if cfg.frontend:
        prefix = jax.ShapeDtypeStruct(
            (1, cfg.num_prefix_embeddings, cfg.d_model), jnp.dtype(cfg.dtype),
            sharding=one_chip)

    def grad(p, t, e):
        batch = {"tokens": t}
        if e is not None:
            batch["prefix_embeddings"] = e
        return jax.grad(lambda p: model.loss(p, batch)[0])(p)

    train = kernel_names(jax.jit(grad).lower(params, tokens(s), prefix)
                         .compile().as_text())
    prefill = kernel_names(jax.jit(
        lambda p, t, e: model.prefill(p, t, prefix_embeddings=e,
                                      max_len=1024))
        .lower(params, tokens(s), prefix).compile().as_text())
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: model.init_cache(1, 1024)))
    decode = kernel_names(jax.jit(model.decode_step)
                          .lower(params, tokens(1), cache).compile().as_text())

    assert {"rmsnorm", "lm_head_ce"} <= train
    assert {"rmsnorm", "lm_head_logits"} <= decode
    assert "rmsnorm" in prefill
    attn = {"flash_attention_fwd", "flash_attention_bwd_dkdv",
            "flash_attention_bwd_dq"}
    if cfg.has_attention:
        assert attn <= train
        assert "flash_attention_fwd" in prefill
    else:
        assert not attn & train
    assert "ssm_scan" not in train | prefill | decode


def test_mesh_engine_programs_compile(topology, chip_defaults):
    """The sharded Engine's decode step and prefill on a (1, 4) mesh of
    v5e chips, at internlm2-1.8B widths (one layer). GSPMD cannot partition
    a Mosaic kernel; each kernel call must run per shard (``shard_kernel``)
    or the compile fails."""
    import dataclasses

    from repro.launch.mesh import make_mesh
    from repro.models import LM
    from repro.parallel.context import use_rules
    from repro.parallel.steps import build_paged_serve_step

    mesh = make_mesh((1, 4), ("data", "model"), devices=topology.devices)
    cfg = dataclasses.replace(get_config("internlm2_1_8b"), n_layers=1)

    model = LM(cfg)
    step, specs = build_paged_serve_step(model, mesh, batch=B)
    params = _abstract(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                       specs["params"])
    cache = _abstract(jax.eval_shape(
        lambda: model.init_paged_cache(B, B * 4 + 1, 512, 4)),
        specs["cache"])
    toks = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=specs["tokens"])
    names = kernel_names(step.lower(params, cache, toks).compile().as_text())
    assert {"flash_decode_paged", "lm_head_logits", "rmsnorm"} <= names

    def prefill(p, t):
        with use_rules(specs["rules"]):
            return model.prefill(p, t)

    prompt = jax.ShapeDtypeStruct((1, 881), jnp.int32,
                                  sharding=NamedSharding(mesh, P()))
    names = kernel_names(jax.jit(prefill).lower(params, prompt)
                         .compile().as_text())
    assert {"flash_attention_fwd", "rmsnorm"} <= names


def test_ring_prefill_compiles(topology, chip_defaults):
    """Ring prefill (``build_prefill_step(ring=True)``): the sequence
    shards over the model axis and attention runs the ``ring_flash`` step
    kernel per shard, kv chunks rotating by ppermute."""
    import dataclasses

    from repro.launch.mesh import make_mesh
    from repro.models import LM
    from repro.parallel.steps import build_prefill_step

    mesh = make_mesh((1, 4), ("data", "model"), devices=topology.devices)
    cfg = dataclasses.replace(get_config("internlm2_1_8b"), n_layers=1)
    model = LM(cfg)
    s = 2048
    step, specs = build_prefill_step(
        model, mesh, batch=1, max_len=s, ring=True,
        batch_shapes={"tokens": jax.ShapeDtypeStruct((1, s), jnp.int32)})
    params = _abstract(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                       specs["params"])
    batch = {"tokens": jax.ShapeDtypeStruct((1, s), jnp.int32,
                                            sharding=specs["batch"]["tokens"])}
    text = step.lower(params, batch).compile().as_text()
    assert "ring_flash_fwd" in kernel_names(text)
    assert "collective-permute" in text
