"""Substrate tests: device memory, optimizer, data determinism,
checkpointing, recovery, watchdog, sharding rules, elastic mesh choice."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.checkpoint import CheckpointManager, restore_tree, save_tree
from repro.configs import get_config, reduced
from repro.data import Prefetcher, SyntheticLMData, TextLMData, make_corpus
from repro.models import LM
from repro.optim import AdamW, WarmupCosine, global_norm
from repro.parallel import rules as R
from repro.runtime import ChaosError, FailureInjector, StepWatchdog, \
    choose_mesh_shape
from repro.launch.mesh import make_local_mesh
from repro.launch.train import TrainLoop


# ---------------------------------------------------------------------------
# device memory
# ---------------------------------------------------------------------------

def test_malloc_casts_array_to_requested_dtype():
    from repro.core import Device

    dev = Device("jnp")
    m = dev.malloc(np.arange(4, dtype=np.int32), jnp.float32)
    assert m.dtype == jnp.float32          # dtype was silently dropped before
    np.testing.assert_allclose(m.to_host(), [0.0, 1.0, 2.0, 3.0])
    # no dtype -> keep the array's own
    assert dev.malloc(np.arange(4, dtype=np.int32)).dtype == jnp.int32
    # shape forms unchanged
    assert dev.malloc((2, 3)).shape == (2, 3)
    assert dev.malloc(5, jnp.int32).dtype == jnp.int32


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_matches_reference_formula():
    opt = AdamW(schedule=WarmupCosine(peak_lr=1e-2, warmup_steps=0,
                                      total_steps=10, final_frac=1.0),
                b1=0.9, b2=0.99, eps=1e-8, weight_decay=0.0, clip_norm=1e9)
    p = {"w": jnp.asarray([1.0, -2.0])}
    g = {"w": jnp.asarray([0.5, 0.25])}
    state = opt.init(p)
    newp, state, _ = opt.update(g, state, p)
    # reference: m=0.1g/0.1 -> g ; v=0.01g^2/0.01 -> g^2; delta = g/|g| = sign
    want = np.asarray([1.0, -2.0]) - 1e-2 * np.asarray(
        [0.5 / (0.5 + 1e-8), 0.25 / (0.25 + 1e-8)])
    np.testing.assert_allclose(np.asarray(newp["w"]), want, rtol=1e-5)


def test_adamw_clipping_and_wd():
    opt = AdamW(schedule=WarmupCosine(peak_lr=1e-3, warmup_steps=0,
                                      total_steps=10), clip_norm=0.1,
                weight_decay=0.5)
    p = {"w": jnp.ones(4)}
    g = {"w": jnp.full(4, 100.0)}
    st_ = opt.init(p)
    newp, _, m = opt.update(g, st_, p)
    assert float(m["grad_norm"]) == pytest.approx(200.0)
    assert np.all(np.isfinite(np.asarray(newp["w"])))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_global_norm_property(seed):
    rng = np.random.RandomState(seed)
    tree = {"a": jnp.asarray(rng.randn(7)), "b": [jnp.asarray(rng.randn(3, 2))]}
    flat = np.concatenate([np.asarray(x).ravel() for x in jax.tree.leaves(tree)])
    np.testing.assert_allclose(float(global_norm(tree)),
                               np.linalg.norm(flat), rtol=1e-5)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_and_host_sharded():
    mk = lambda h: SyntheticLMData(vocab_size=97, seq_len=17, global_batch=8,
                                   seed=3, num_hosts=2, host_id=h)
    a, a2, b = mk(0), mk(0), mk(1)
    assert (a.batch(5) == a2.batch(5)).all()          # deterministic
    assert not (a.batch(5) == b.batch(5)).all()       # hosts disjoint
    assert not (a.batch(5) == a.batch(6)).all()       # steps differ
    assert a.batch(5).shape == (4, 17)
    assert a.batch(0).min() >= 0 and a.batch(0).max() < 97


def test_data_has_learnable_structure():
    d = SyntheticLMData(vocab_size=64, seq_len=256, global_batch=4, seed=0,
                        order_strength=0.95)
    b = d.batch(0)
    # successor distribution must be concentrated (markov structure)
    follows = {}
    for row in b:
        for t in range(len(row) - 1):
            follows.setdefault(row[t], []).append(row[t + 1])
    concentrations = [len(set(v)) / len(v) for v in follows.values()
                      if len(v) >= 8]
    assert np.mean(concentrations) < 0.8


def test_prefetcher_propagates_errors():
    class Bad:
        def batch(self, step):
            raise RuntimeError("boom")

    p = Prefetcher(Bad())
    with pytest.raises(RuntimeError, match="boom"):
        p.next()
    p.close()


def test_text_pipeline():
    t = TextLMData(make_corpus(5000, seed=1), seq_len=32, global_batch=4)
    b = t.batch(0)
    assert b.shape == (4, 32) and b.max() < 256
    assert (t.batch(3) == t.batch(3)).all()


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.asarray(7, jnp.int32)},
            "d": [jnp.ones(2), jnp.zeros(3)]}
    save_tree(tree, str(tmp_path / "ck"))
    template = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    got = restore_tree(template, str(tmp_path / "ck"))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_manager_keep_k_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (10, 20, 30):
        mgr.save(s, {"x": jnp.full(3, float(s))}, async_=False)
    assert mgr.latest_step() == 30
    assert mgr.all_steps() == [20, 30]          # step 10 GC'd
    _, tree, meta = mgr.restore({"x": jax.ShapeDtypeStruct((3,), jnp.float32)})
    assert float(np.asarray(tree["x"])[0]) == 30.0
    assert meta["step"] == 30


def test_checkpoint_async_save_waits(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"x": jnp.ones(4)}, async_=True)
    mgr.wait()
    assert mgr.latest_step() == 1


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    save_tree({"x": jnp.ones(4)}, str(tmp_path / "ck"))
    with pytest.raises(ValueError, match="shape mismatch"):
        restore_tree({"x": jax.ShapeDtypeStruct((5,), jnp.float32)},
                     str(tmp_path / "ck"))


# ---------------------------------------------------------------------------
# training loop: loss decreases, resume, recovery
# ---------------------------------------------------------------------------

def _loop(tmp_path, steps, **kw):
    cfg = reduced(get_config("llama3_2_1b"))
    model = LM(cfg)
    return TrainLoop(model=model, mesh=make_local_mesh(model=1),
                     global_batch=8, seq_len=32, steps=steps,
                     ckpt_dir=str(tmp_path / "ck"), ckpt_every=10,
                     verbose=False, **kw)


def test_training_loss_decreases(tmp_path):
    out = _loop(tmp_path, 40).run()
    h = out["history"]
    assert np.mean(h[-5:]) < np.mean(h[:5]) - 0.3, (h[:5], h[-5:])


def test_training_resume_continues(tmp_path):
    loop = _loop(tmp_path, 20)
    loop.run()
    out = _loop(tmp_path, 30).run()   # resumes from step 20
    assert out["final_step"] == 30
    assert len(out["history"]) == 10  # only 10 new steps


def test_training_recovers_from_injected_failure(tmp_path):
    loop = _loop(tmp_path, 25, injector=FailureInjector([15]))
    out = loop.run()
    assert out["final_step"] == 25
    assert len(out["history"]) > 25 - 10  # re-ran some steps after restore


def test_training_gives_up_after_max_retries(tmp_path):
    inj = FailureInjector([5], fail_once=False)
    inj.fail_at = {5}
    loop = _loop(tmp_path, 10, injector=inj, max_retries=2)
    loop.injector.fail_once = False
    with pytest.raises(ChaosError):
        loop.run()


# ---------------------------------------------------------------------------
# watchdog + elastic
# ---------------------------------------------------------------------------

def test_watchdog_flags_stragglers():
    wd = StepWatchdog(window=20, sigma=3.0, min_samples=5)
    for i in range(10):
        assert not wd.observe(i, 1.0 + 0.01 * (i % 3))
    assert wd.observe(10, 5.0)
    assert wd.flagged and wd.flagged[0][0] == 10


def test_watchdog_absolute_deadline():
    wd = StepWatchdog(absolute_deadline_s=2.0, min_samples=100)
    assert wd.observe(0, 3.0)


def test_make_local_mesh_axes_are_auto():
    # sharding-in-types (Explicit axes, jax.make_mesh's default) rejects the
    # layers' GSPMD-style constraints; every repo mesh is Auto
    from jax.sharding import AxisType

    from repro.launch.mesh import make_mesh

    mesh = make_local_mesh(model=1)
    assert mesh.axis_names == ("data", "model")
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    assert make_mesh((1,), ("pipe",)).axis_types == (AxisType.Auto,)


def test_choose_mesh_shape_elastic():
    assert choose_mesh_shape(512, model=16, pod=2) == (2, 16, 16)
    assert choose_mesh_shape(256, model=16) == (16, 16)
    assert choose_mesh_shape(240, model=16) == (15, 16)  # lost a host
    assert choose_mesh_shape(17, model=16) == (1, 16)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_param_specs_divisibility_and_layout():
    cfg = get_config("llama3_2_1b")
    model = LM(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    mesh = make_local_mesh(data=1, model=1)

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")
    specs = R.param_specs(params, cfg, FakeMesh())
    # embedding shards vocab; stacked attn weights shard their output dim
    assert specs["embed"] == P("model", None)
    stack = specs["stacks"][0]
    assert stack["attn"]["wq"] == P(None, None, "model")
    assert stack["attn"]["wo"] == P(None, "model", None)
    assert stack["norm1"] == P(None, None)
    # kv proj for llama: Hk*hd = 512, divisible by 16 -> sharded
    assert stack["attn"]["wk"] == P(None, None, "model")


def test_param_specs_moe_ep_vs_tp():
    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    for arch, expect_ep in (("deepseek_v2_lite", True), ("mixtral_8x22b", False)):
        cfg = get_config(arch)
        model = LM(cfg)
        params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        specs = R.param_specs(params, cfg, FakeMesh())
        moe_stack = specs["stacks"][-1]["moe"]
        wg = moe_stack["w_gate"]
        if expect_ep:    # 64 experts % 16 == 0 -> expert-parallel
            assert wg == P(None, "model", None, None), (arch, wg)
        else:            # 8 experts -> TP over ffn dim
            assert wg == P(None, None, None, "model"), (arch, wg)


def test_zero1_shards_largest_dim():
    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    cfg = get_config("llama3_2_1b")
    model = LM(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    specs = R.param_specs(params, cfg, FakeMesh())
    z = R.zero1_specs(specs, params, FakeMesh())
    # embed: (V, d) was ("model", None) -> d=2048 now data-sharded
    assert z["embed"] == P("model", "data")


def test_spec_bytes_per_device():
    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    shapes = {"w": jax.ShapeDtypeStruct((1600, 320), jnp.float32)}
    specs = {"w": P("model", None)}
    b = R.spec_bytes_per_device(shapes, specs, FakeMesh())
    assert b == 100 * 320 * 4
