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


@pytest.mark.parametrize("h,hk", [(16, 8), (12, 2)],
                         ids=["internlm2-1.8b", "internlm2-20b-tp4-per-chip"])
def test_flash_decode_paged_compiles(one_chip, h, hk):
    """The serving cells' paged decode at their shapes: 24 slots, pages of
    512, 4 pages a sequence, every KV head of a page in one grid cell."""
    b, page, nsp = 24, 512, 4
    npages = b * nsp + 1
    text = _compile_text(
        lambda q, k, v, t, n, p: paged_decode_attention(
            q, k, v, block_table=t, kv_len=n, pos_pages=p, **COMPILED),
        one_chip, ((b, h, 1, D), BF16), ((npages, hk, page, D), BF16),
        ((npages, hk, page, D), BF16), ((b, nsp), jnp.int32),
        ((b,), jnp.int32), ((npages, page), jnp.int32))
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "flash_decode_paged" in line]
    assert len(calls) == 1, calls
    assert kernel_names(text) == {"flash_decode_paged"}


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


def test_engine_decode_updates_the_pool_in_place(one_chip, chip_defaults):
    """The Engine's decode step carries the layer-stacked KV pools through
    the layer loop and updates them in place: its temporaries stay far
    below one layer's pool, and no copy, dynamic slice or dynamic update
    slice in the optimised program has the shape of a layer's pool or of
    the stack (the per-layer slice, relayouts and restack that cost a
    decode step several copies of the whole pool)."""
    import dataclasses
    import re

    from repro.models import LM
    from repro.serving import Engine

    n_layers, npages, page = 2, 33, 512
    cfg = dataclasses.replace(get_config("internlm2_1_8b"), n_layers=n_layers)
    model = LM(cfg)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    eng = Engine(model, params, batch=B, max_len=4 * page, page_size=page,
                 num_pages=npages)
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng.cache)
    toks = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    compiled = eng.decode_step.lower(params, cache, toks).compile()

    hk, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    layer_pool = (npages, hk, page, hd)
    layer_bytes = 2 * npages * hk * page * hd * 2      # K and V, bf16
    assert layer_bytes >= 64 * 2 ** 20
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_bytes // 4, (temp, layer_bytes)

    shapes = {",".join(map(str, layer_pool)),
              ",".join(map(str, (n_layers,) + layer_pool))}
    moved = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([a-z-]+)\(", line)
        if not m:
            continue
        name, shape, opcode = m.groups()
        moves = (opcode in ("copy", "copy-start", "dynamic-slice",
                            "dynamic-update-slice")
                 or re.search(r"copy|dynamic-slice|dynamic-update-slice",
                              name))
        # a leading unit dim (a one-layer slice of the stack) is the same
        # pool
        dims = {re.sub(r"^(1,)+", "", d)
                for d in re.findall(r"bf16\[([\d,]+)\]", shape)}
        if moves and shapes & dims:
            moved.append(line.strip()[:160])
    assert not moved, "\n".join(moved)
    assert "flash_decode_paged" in kernel_names(compiled.as_text())


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


def _pool_moves(text, shapes):
    """Lines of a compiled program that gather or copy an array of one of
    ``shapes`` (dims as "a,b,c", a leading unit dim stripped)."""
    import re

    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([a-z-]+)\(", line)
        if not m:
            continue
        name, shape, opcode = m.groups()
        moves = (opcode in ("all-gather", "all-gather-start", "copy",
                            "copy-start", "all-to-all", "collective-permute",
                            "collective-permute-start")
                 or re.search(r"all-gather|copy", name))
        dims = {re.sub(r"^(1,)+", "", d)
                for d in re.findall(r"bf16\[([\d,]+)\]", shape)}
        if moves and shapes & dims:
            out.append(line.strip()[:160])
    return out


def test_internlm2_20b_engine_fits_four_chips(topology, chip_defaults):
    """internlm2-20B at its published widths and depth, served by
    ``Engine(mesh=)`` on a (1, 4) mesh of v5e chips as the benchmark's
    ``internlm2-20b-tp4.chat`` cell runs it (24 slots, 97 pages of 512):
    the engine is built over abstract sharded weights, so its cache is
    abstract and sharded from the start. Per chip, the decode step's
    arguments and temporaries fit 15.75 GB, and so do the weights, the
    pool and the longest prefill's temporaries; the kernels run per
    shard; no program the engine runs gathers or copies the pool."""
    import math

    from repro.launch.mesh import make_mesh
    from repro.models import LM
    from repro.parallel.steps import make_shardings
    from repro.serving import Engine

    budget = 15.75e9
    cfg = get_config("internlm2_20b")
    mesh = make_mesh((1, 4), ("data", "model"), devices=topology.devices)
    model = LM(cfg)
    param_sh = make_shardings(model, mesh)[0]
    params = _abstract(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                       param_sh)
    batch, page, npages = 24, 512, 97
    eng = Engine(model, params, batch=batch, max_len=2048, page_size=page,
                 num_pages=npages, mesh=mesh)
    # weights already in the step's shardings are not placed again
    assert all(a is b for a, b in zip(jax.tree.leaves(eng.params),
                                      jax.tree.leaves(params)))
    pool = eng.cache["stacks"][0]["kp"]
    assert pool.shape == (48, npages, 8, page, 128)
    assert pool.sharding.shard_shape(pool.shape) == (48, npages, 2, page, 128)
    whole = ",".join(map(str, pool.shape[1:]))
    shard = ",".join(map(str, (npages, 2, page, 128)))
    shapes = {whole, shard, "48," + whole, "48," + shard}

    rep = NamedSharding(mesh, P())
    toks = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=rep)
    decode = eng.decode_step.lower(eng.params, eng.cache, toks).compile()
    mem = decode.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes <= budget, mem
    text = decode.as_text()
    assert {"flash_decode_paged", "lm_head_logits", "rmsnorm"} <= \
        kernel_names(text)
    assert not _pool_moves(text, shapes)
    assert "shard_map" in text                 # kernels run per shard

    def per_chip(a):
        return math.prod(a.sharding.shard_shape(a.shape)) * a.dtype.itemsize

    weights = sum(map(per_chip, jax.tree.leaves(params)))
    pools = 2 * per_chip(pool)

    n = 1536                                   # the chat mix's longest prompt
    prompt = jax.ShapeDtypeStruct((1, n), jnp.int32, sharding=rep)
    prefill = eng._prefill_fn.lower(eng.params, prompt).compile()
    ptext = prefill.as_text()
    assert {"flash_attention_fwd", "rmsnorm", "lm_head_logits"} <= \
        kernel_names(ptext)
    assert weights + pools + prefill.memory_analysis().temp_size_in_bytes \
        <= budget
    _, pcache = jax.eval_shape(eng._prefill_fn, eng.params, prompt)
    pcache = _abstract(pcache, prefill.output_shardings[1])

    c = eng.cache
    nsp = eng.sched.nseq_pages
    ints = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=rep)
            for s in ((nsp,), ())]
    scatter = eng._scatter_fn.lower(c["stacks"], c["pos_pages"], c["table"],
                                    c["len"], pcache["stacks"], *ints)
    grow = eng._grow_fn.lower(
        c["pos_pages"], c["table"], ints[0],
        jax.ShapeDtypeStruct((nsp,), jnp.bool_, sharding=rep), ints[1])
    clear = eng._clear_fn.lower(c["table"], c["len"], ints[1])
    logits = jax.ShapeDtypeStruct((model.vpad,), jnp.float32, sharding=rep)
    first = eng._greedy_fn.lower(logits)
    for lowered in (scatter, grow, clear, first):
        assert not _pool_moves(lowered.compile().as_text(), shapes)
    out = scatter.compile().output_shardings[0]
    assert all(s.is_equivalent_to(p.sharding, 5) for s, p in
               zip(jax.tree.leaves(out), jax.tree.leaves(c["stacks"])))
