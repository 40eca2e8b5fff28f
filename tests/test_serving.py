"""Continuous-batching serving: paged decode parity, allocator/scheduler
invariants, and the engine vs the static-batch oracle.

The load-bearing claim: ``flash_decode_paged`` reading KV through a block
table is BIT-IDENTICAL to contiguous ``flash_decode`` when the page size
equals its kv block size — paged pages stream through the same online-
softmax accumulation in the same logical order, and fully-masked blocks
are exact no-ops. Everything above it (layer, model, Engine) inherits that
parity, so a mixed-length engine run with mid-flight slot refill and
preemption must reproduce the per-sequence static-batch tokens exactly.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import BACKENDS
from repro.kernels.flash_attention import (decode_attention,
                                           paged_decode_attention,
                                           paged_decode_ref)
from repro.models import LM
from repro.serving import Engine, PageAllocator, Scheduler

from tests._hypothesis_compat import given, settings, strategies as st

import repro.kernels  # noqa: F401 — registers the op families


def _paged_from_contiguous(rng, kc, vc, page):
    """Scatter (b, hk, cap, d) contiguous caches into a SHUFFLED page pool.
    Returns (k_pages, v_pages, block_table); page 0 stays the null page."""
    b, hk, cap, d = kc.shape
    nsp = cap // page
    npages = b * nsp + 1
    perm = rng.permutation(np.arange(1, npages))[:b * nsp].reshape(b, nsp)
    kp = np.zeros((npages, hk, page, d), kc.dtype)
    vp = np.zeros((npages, hk, page, vc.shape[-1]), vc.dtype)
    for bi in range(b):
        for j in range(nsp):
            kp[perm[bi, j]] = kc[bi, :, j * page:(j + 1) * page]
            vp[perm[bi, j]] = vc[bi, :, j * page:(j + 1) * page]
    return kp, vp, perm.astype(np.int32)


# ---------------------------------------------------------------------------
# kernel-level bit-parity: paged vs contiguous, all three expansions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@settings(max_examples=5, deadline=None)
@given(page=st.sampled_from([4, 8, 16]),
       extra=st.integers(min_value=0, max_value=13),
       g=st.sampled_from([1, 2, 4]),
       window=st.sampled_from([None, 48]))
def test_paged_decode_bitwise_matches_contiguous(backend, page, extra, g,
                                                 window):
    """Every sequence's paged output must equal the contiguous kernel run
    at block_kv == page — bitwise, including non-dividing kv lengths."""
    b, hk, d = 2, 2, 32
    h = hk * g
    rng = np.random.default_rng(page * 100 + extra * 7 + g)
    cap = 4 * page                        # pool capacity per sequence
    kv_len = np.minimum(
        np.array([cap - extra, 2 * page + 1], np.int32), cap)
    kv_len = np.maximum(kv_len, 1)
    q = rng.standard_normal((b, h, 1, d), np.float32)
    kc = rng.standard_normal((b, hk, cap, d), np.float32)
    vc = rng.standard_normal((b, hk, cap, d), np.float32)
    kp, vp, table = _paged_from_contiguous(rng, kc, vc, page)

    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        block_table=table, kv_len=kv_len, window=window, backend=backend))
    for bi in range(b):
        exp = np.asarray(decode_attention(
            jnp.asarray(q[bi:bi + 1]), jnp.asarray(kc[bi:bi + 1]),
            jnp.asarray(vc[bi:bi + 1]), kv_len=int(kv_len[bi]),
            window=window, block_kv=page, backend=backend))
        if backend == "jnp":
            # the fully-jitted jnp expansion lets XLA fuse the gather into
            # the surrounding graph, which can reassociate a rounding step;
            # loops/pallas execute block-by-block and stay bit-exact
            np.testing.assert_allclose(got[bi:bi + 1], exp,
                                       rtol=1e-5, atol=1e-6)
        else:
            assert (got[bi:bi + 1] == exp).all(), (
                f"paged != contiguous bitwise at row {bi} "
                f"(page={page}, kv_len={int(kv_len[bi])}, g={g}, "
                f"window={window}, backend={backend})")


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("layer", [0, 2], ids=["first_layer", "last_layer"])
@settings(max_examples=5, deadline=None)
@given(page=st.sampled_from([4, 8, 16]),
       extra=st.integers(min_value=0, max_value=13),
       g=st.sampled_from([1, 2, 4]),
       window=st.sampled_from([None, 48]))
def test_paged_decode_stacked_pool_matches_sliced_layer(backend, layer, page,
                                                        extra, g, window):
    """A layer-stacked pool (L, P, ...) flattened to (L*P, ...) and read
    through ``kv_table = block_table + layer*P`` gives bitwise the output
    of the same call on the sliced layer (P, ...): the model's decode step
    reads each layer's pages where they lie instead of slicing the stack.
    The XLA reference takes the same addressing."""
    n_layers, b, hk, d = 3, 2, 2, 32
    h = hk * g
    rng = np.random.default_rng(page * 100 + extra * 7 + g + layer)
    cap = 4 * page
    kv_len = np.maximum(np.minimum(
        np.array([cap - extra, 2 * page + 1], np.int32), cap), 1)
    q = jnp.asarray(rng.standard_normal((b, h, 1, d), np.float32))
    pools = []
    for _ in range(n_layers):
        kc = rng.standard_normal((b, hk, cap, d), np.float32)
        vc = rng.standard_normal((b, hk, cap, d), np.float32)
        kp, vp, table = _paged_from_contiguous(np.random.default_rng(0),
                                               kc, vc, page)
        pools.append((kp, vp))          # same table (same rng) every layer
    kst = np.stack([p[0] for p in pools])
    vst = np.stack([p[1] for p in pools])
    npages = kst.shape[1]
    pos = np.full((npages, page), -1, np.int32)
    for bi in range(b):
        for j, pg in enumerate(table[bi]):
            pos[pg] = np.arange(j * page, (j + 1) * page)
    kw = dict(block_table=table, kv_len=kv_len, pos_pages=pos, window=window)
    flat = (n_layers * npages, hk, page, d)

    got = np.asarray(paged_decode_attention(
        q, jnp.asarray(kst.reshape(flat)), jnp.asarray(vst.reshape(flat)),
        kv_table=table + layer * npages, backend=backend, **kw))
    exp = np.asarray(paged_decode_attention(
        q, jnp.asarray(kst[layer]), jnp.asarray(vst[layer]),
        backend=backend, **kw))
    assert (got == exp).all(), (
        f"stacked pool != sliced layer bitwise (layer={layer}, page={page}, "
        f"g={g}, window={window}, backend={backend})")
    got_ref = np.asarray(paged_decode_ref(
        q, jnp.asarray(kst.reshape(flat)), jnp.asarray(vst.reshape(flat)),
        kv_table=table + layer * npages, **kw))
    exp_ref = np.asarray(paged_decode_ref(
        q, jnp.asarray(kst[layer]), jnp.asarray(vst[layer]), **kw))
    assert (got_ref == exp_ref).all()


@pytest.mark.parametrize("backend", BACKENDS)
def test_paged_decode_matches_ref_with_pos_pages(backend):
    """Rotated layouts: explicit pos_pages (with -1 holes) drive the mask
    identically in the op and the oracle."""
    b, h, hk, d, page = 1, 4, 2, 32, 8
    rng = np.random.default_rng(3)
    nsp, npages = 3, 5
    q = rng.standard_normal((b, h, 1, d), np.float32)
    kp = rng.standard_normal((npages, hk, page, d), np.float32)
    vp = rng.standard_normal((npages, hk, page, d), np.float32)
    table = np.array([[2, 4, 1]], np.int32)
    pos = np.full((npages, page), -1, np.int32)
    # pages hold positions out of slot order, with holes. The kernel's
    # block-skip shortcut assumes logical order only while q_pos < capacity
    # (the rolling-cache contract flash_decode shares), so a rotated layout
    # is exercised with kv_len > capacity — every block runs, the mask does
    # the work.
    pos[2, :5] = np.arange(5)
    pos[4, :8] = np.arange(5, 13)
    pos[1, :3] = np.arange(13, 16)
    kv_len = np.array([3 * page + 1], np.int32)
    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), block_table=table,
        kv_len=kv_len, pos_pages=pos, backend=backend))
    exp = np.asarray(paged_decode_ref(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), block_table=table,
        kv_len=kv_len, pos_pages=pos))
    np.testing.assert_allclose(got, exp, rtol=3e-4, atol=3e-4)


def _engine_batch(case, page, nsp, npages, rng):
    """Block table, kv_len and pos_pages as the engine passes them: page 0
    is the null page (positions -1) that idle slots (``kv_len`` 1) and
    pages past a sequence's end point at. ``idle`` puts idle slots beside
    full and partial sequences; ``window`` masks below a sliding window;
    ``wrapped`` is a rolling cache past its capacity, slot ``s`` holding
    the newest position ``t`` with ``t % cap == s``."""
    cap = nsp * page
    kv_len = {"idle": [cap, 1, 2 * page + 3, 1],
              "window": [cap, 3 * page - 2, page + 1, 2],
              "wrapped": [cap + 5, 3 * cap - 1, 2 * cap, cap + 1]}[case]
    kv_len = np.array(kv_len, np.int32)
    table = np.zeros((len(kv_len), nsp), np.int32)
    pos = np.full((npages, page), -1, np.int32)
    free = list(rng.permutation(np.arange(1, npages)))
    for bi, n in enumerate(kv_len):
        if n == 1:
            continue                               # idle slot: null page
        for j in range(min(nsp, -(-int(n) // page))):
            pg = table[bi, j] = free.pop()
            slots = np.arange(j * page, (j + 1) * page)
            if n > cap:                            # rotated: newest per slot
                pos[pg] = slots + cap * ((n - 1 - slots) // cap)
            else:
                pos[pg] = np.where(slots < n, slots, -1)
    window = {"idle": None, "window": page + 3, "wrapped": cap}[case]
    return table, kv_len, pos, window


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", ["idle", "window", "wrapped"])
@pytest.mark.parametrize("h,hk", [(16, 8), (12, 2), (8, 8), (8, 1)],
                         ids=["gqa-g2", "gqa-g6", "mha", "mqa"])
def test_paged_decode_head_groups_match_ref(backend, case, h, hk):
    """One grid cell serves every query head of its KV heads: GQA, MHA
    (g = 1) and MQA (hk = 1) against the oracle, over the engine's batches
    (idle slots, a sliding window, a wrapped rolling cache)."""
    b, d, page, nsp = 4, 32, 8, 4
    npages = b * nsp + 1
    rng = np.random.default_rng(h * 10 + hk)
    table, kv_len, pos, window = _engine_batch(case, page, nsp, npages, rng)
    q = jnp.asarray(rng.standard_normal((b, h, 1, d), np.float32))
    kp = jnp.asarray(rng.standard_normal((npages, hk, page, d), np.float32))
    vp = jnp.asarray(rng.standard_normal((npages, hk, page, d), np.float32))
    kw = dict(block_table=table, kv_len=kv_len, pos_pages=pos, window=window)
    got = np.asarray(paged_decode_attention(q, kp, vp, backend=backend, **kw))
    exp = np.asarray(paged_decode_ref(q, kp, vp, **kw))
    assert got.shape == (b, h, 1, d)
    np.testing.assert_allclose(got, exp, rtol=3e-4, atol=3e-4)
    idle = kv_len == 1
    assert (got[idle] == 0).all()          # the null page attends to nothing


def _paged_spec(b, h, hk, page, nsp, d, dtype):
    from repro.core.lang import defines_namespace
    from repro.kernels.flash_attention import flash_decode_paged

    npages = b * nsp + 1
    args = (jax.ShapeDtypeStruct((b, h, 1, d), dtype),
            jax.ShapeDtypeStruct((npages, hk, page, d), dtype),
            jax.ShapeDtypeStruct((npages, hk, page, d), dtype))
    table = np.arange(b * nsp, dtype=np.int32).reshape(b, nsp) + 1
    _, _, params = flash_decode_paged._resolve(
        dict(block_table=table, kv_len=np.full((b,), nsp * page, np.int32)))
    _, defines, _ = flash_decode_paged._prepare(args, params)
    return flash_decode_paged.builder(defines_namespace(defines))


def test_paged_decode_grid_is_one_cell_per_sequence_page(monkeypatch):
    """At internlm2-1.8B's serving shapes the grid is (b, nsp) and a K/V
    block spans all 8 KV heads (K and V, 1 MiB each, double-buffered:
    4 MiB). A VMEM budget too small for that splits the KV heads into
    chunks of the largest divisor that fits, as a grid axis; the result is
    unchanged."""
    b, h, hk, page, nsp, d = 24, 16, 8, 512, 4, 128
    spec = _paged_spec(b, h, hk, page, nsp, d, jnp.bfloat16)
    tiles = {t.name: t for t in spec.inputs}
    assert spec.grid == (b, nsp)
    assert spec.reduce_axes == (1,)
    assert tiles["k"].block == (1, hk, page, d)
    assert tiles["v"].block == (1, hk, page, d)
    assert tiles["q"].block == (1, hk, h // hk, d)

    # a head's K+V blocks, double-buffered: 512 KiB; 3/4 of 2 MiB holds 3
    monkeypatch.setenv("REPRO_VMEM_BUDGET", str(2 * 2**20))
    spec = _paged_spec(b, h, hk, page, nsp, d, jnp.bfloat16)
    tiles = {t.name: t for t in spec.inputs}
    assert spec.grid == (b, 4, nsp)        # hkc = 2, the largest divisor
    assert tiles["k"].block == (1, 2, page, d)

    # a chunked grid computes what the whole-head cell does
    b, page, d = 4, 16, 128
    nsp, npages = 4, 4 * 4 + 1
    rng = np.random.default_rng(0)
    table, kv_len, pos, window = _engine_batch("idle", page, nsp, npages, rng)
    q = jnp.asarray(rng.standard_normal((b, h, 1, d), np.float32))
    kp = jnp.asarray(rng.standard_normal((npages, hk, page, d), np.float32))
    vp = jnp.asarray(rng.standard_normal((npages, hk, page, d), np.float32))
    kw = dict(block_table=table, kv_len=kv_len, pos_pages=pos, window=window)
    # 32 KiB a head here: 3/4 of 128 KiB holds 3, so again hkc = 2
    monkeypatch.setenv("REPRO_VMEM_BUDGET", str(128 * 2**10))
    assert _paged_spec(b, h, hk, page, nsp, d, jnp.float32).grid == \
        (b, 4, nsp)
    exp = np.asarray(paged_decode_ref(q, kp, vp, **kw))
    for backend in BACKENDS:
        got = np.asarray(paged_decode_attention(q, kp, vp, backend=backend,
                                                **kw))
        np.testing.assert_allclose(got, exp, rtol=3e-4, atol=3e-4,
                                   err_msg=backend)


# ---------------------------------------------------------------------------
# allocator / scheduler: no page leaked, none double-owned
# ---------------------------------------------------------------------------

def test_allocator_all_or_nothing_and_release():
    pa = PageAllocator(num_pages=6, page_size=4)
    assert pa.free_pages == 5
    a = pa.alloc("a", 3)
    assert a is not None and len(a) == 3 and 0 not in a
    assert pa.alloc("b", 3) is None          # shortfall: NO state change
    assert pa.free_pages == 2
    b = pa.alloc("b", 2)
    assert b is not None and not (set(a) & set(b))
    pa.check_invariants()
    freed = pa.release("a")
    assert sorted(freed) == sorted(a) and pa.free_pages == 3
    pa.check_invariants()
    pa.release("b")
    assert pa.free_pages == 5
    pa.check_invariants()


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_scheduler_random_walk_never_leaks_pages(seed):
    rng = np.random.default_rng(seed)
    sched = Scheduler(batch=3, page_size=4, num_pages=10, max_len=24)
    for _ in range(400):
        op = int(rng.integers(0, 5))
        if op == 0 and len(sched.queue) < 6:
            plen = int(rng.integers(1, 12))
            sched.submit([1] * plen, int(rng.integers(1, 8)))
        elif op == 1:
            sched.admit()
        elif op == 2 and sched.running:
            # simulate one emitted token, then grow (preempting on famine)
            slot = int(rng.choice(sched.running))
            req = sched.slots[slot]
            req.tokens.append(3)
            if len(req.tokens) >= req.max_new:
                sched.retire(slot)
            else:
                while not sched.grow(slot):
                    if sched.preempt_youngest(exclude=slot) is None:
                        raise AssertionError("pool lost a whole sequence")
        elif op == 3 and sched.running:
            sched.preempt_youngest()
        elif op == 4 and sched.running:
            sched.retire(int(rng.choice(sched.running)))
        sched.pages.check_invariants()
    for slot in list(sched.running):
        sched.retire(slot)
    sched.pages.check_invariants()
    assert sched.pages.free_pages == 9       # everything returned


def test_admission_is_fifo_no_queue_jumping():
    sched = Scheduler(batch=2, page_size=4, num_pages=4, max_len=16)
    big = sched.submit([1] * 12, 4)          # needs 4 pages, only 3 free
    small = sched.submit([1], 1)
    placed = sched.admit()
    # the big front request can't fit -> NOTHING admits (small must wait)
    assert placed == [] and sched.queue[0].rid == big
    assert sched.pages.free_pages == 3
    del small


# ---------------------------------------------------------------------------
# engine vs per-sequence static oracle (mixed lengths, refill, preemption)
# ---------------------------------------------------------------------------

def _tiny_model():
    cfg = reduced(get_config("llama3_2_1b"))
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _oracle(model, params, prompt, m, max_len):
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    logits, cache = model.prefill(params, toks, max_len=max_len)
    tok = int(model.greedy_token(logits[0]))
    outs = [tok]
    for _ in range(m - 1):
        nxt, _, cache = model.greedy_step(params,
                                          jnp.asarray([[tok]], jnp.int32),
                                          cache)
        tok = int(nxt[0])
        outs.append(tok)
    return outs


def test_engine_mixed_lengths_matches_static_oracle():
    cfg, model, params = _tiny_model()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (5, 9, 3, 7)]
    max_new = [6, 4, 8, 5]
    eng = Engine(model, params, batch=2, max_len=32, page_size=4,
                 greedy=True)
    rids = [eng.submit(p, m) for p, m in zip(prompts, max_new)]
    out = eng.drain(max_steps=300)
    # 4 requests through 2 slots: refill happened mid-flight
    for rid, p, m in zip(rids, prompts, max_new):
        assert out[rid] == _oracle(model, params, p, m, 32), rid
    eng.sched.pages.check_invariants()
    assert eng.sched.pages.free_pages == eng.sched.pages.num_pages - 1


def test_engine_preemption_still_bit_exact():
    cfg, model, params = _tiny_model()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (6, 10, 4)]
    max_new = [8, 6, 9]
    # pool too small for 3 full sequences: preemption-by-eviction must fire
    eng = Engine(model, params, batch=3, max_len=24, page_size=4,
                 num_pages=9, greedy=True)
    rids = [eng.submit(p, m) for p, m in zip(prompts, max_new)]
    out = eng.drain(max_steps=500)
    assert sum(r.preempted for r in eng._requests.values()) > 0
    for rid, p, m in zip(rids, prompts, max_new):
        assert out[rid] == _oracle(model, params, p, m, 24), rid
    eng.sched.pages.check_invariants()


def test_engine_eos_retires_and_refills():
    cfg, model, params = _tiny_model()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (5, 6, 4)]
    # pick an EOS that row 0 actually emits, from an eos-free dry run
    free = _oracle(model, params, prompts[0], 6, 32)
    eos = free[2]
    eng = Engine(model, params, batch=2, max_len=32, page_size=8,
                 eos_id=eos, greedy=True)
    rids = [eng.submit(p, 8) for p in prompts]
    out = eng.drain(max_steps=300)
    for rid, p in zip(rids, prompts):
        exp = _oracle(model, params, p, 8, 32)
        if eos in exp:
            exp = exp[:exp.index(eos) + 1]   # EOS itself is emitted
        assert out[rid] == exp, rid
    assert out[rids[0]][-1] == eos and len(out[rids[0]]) == 3


# ---------------------------------------------------------------------------
# launch.serve.generate: engine wrapper vs static path, pad/temperature fixes
# ---------------------------------------------------------------------------

def test_generate_engine_matches_static():
    from repro.launch.serve import _generate_static, generate
    cfg, model, params = _tiny_model()
    prompts = np.random.RandomState(3).randint(
        0, cfg.vocab_size, (3, 6)).astype(np.int32)
    out_e, st_e = generate(model, params, prompts, gen_tokens=5,
                           engine="paged", page_size=4)
    out_s, st_s = _generate_static(model, params, prompts, gen_tokens=5)
    assert st_e["engine"] and not st_s["engine"]
    np.testing.assert_array_equal(out_e, out_s)


def test_generate_routes_static_for_unpageable():
    from repro.launch.serve import generate
    cfg = dataclasses.replace(reduced(get_config("llama3_2_1b")), window=8)
    model = LM(cfg)
    assert not model.pageable
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, 6)).astype(np.int32)
    out, stats = generate(model, params, prompts, gen_tokens=3)
    assert stats["engine"] is False and out.shape == (2, 3)


@pytest.mark.parametrize("engine", ["paged", "static"])
def test_generate_pad_token_is_explicit(engine):
    from repro.launch.serve import generate
    cfg, model, params = _tiny_model()
    prompts = np.random.RandomState(4).randint(
        0, cfg.vocab_size, (2, 5)).astype(np.int32)
    base, _ = generate(model, params, prompts, gen_tokens=6, engine=engine)
    eos = int(base[0, 2])                    # row 0 finishes at column 2
    out, _ = generate(model, params, prompts, gen_tokens=6, engine=engine,
                      eos_id=eos, pad_id=0)
    row = out[0]
    stop = int(np.argmax(row == eos))
    assert row[stop] == eos
    assert (row[stop + 1:] == 0).all()
    # the old behavior (pad with eos) is still the DEFAULT when pad_id unset
    out2, _ = generate(model, params, prompts, gen_tokens=6, engine=engine,
                       eos_id=eos)
    row2 = out2[0]
    assert (row2[int(np.argmax(row2 == eos)):] == eos).all()


def test_generate_temperature_threads_into_sampling():
    from repro.launch.serve import _generate_static
    cfg, model, params = _tiny_model()
    prompts = np.random.RandomState(5).randint(
        0, cfg.vocab_size, (2, 5)).astype(np.int32)
    greedy_out, _ = _generate_static(model, params, prompts, gen_tokens=4)
    # temperature -> 0 sharpens categorical into argmax: the fix is visible
    # (pre-fix, temperature was silently ignored)
    cold, _ = _generate_static(model, params, prompts, gen_tokens=4,
                               greedy=False, rng=jax.random.PRNGKey(0),
                               temperature=1e-4)
    np.testing.assert_array_equal(cold, greedy_out)
    with pytest.raises(ValueError, match="temperature"):
        _generate_static(model, params, prompts, gen_tokens=2, greedy=False,
                         temperature=0.0)


# ---------------------------------------------------------------------------
# model-level gates
# ---------------------------------------------------------------------------

def test_unpageable_models_raise_on_paged_cache():
    cfg = dataclasses.replace(reduced(get_config("llama3_2_1b")), window=8)
    model = LM(cfg)
    with pytest.raises(ValueError, match="paged decode"):
        model.init_paged_cache(2, 8, 4, 4)
    with pytest.raises(ValueError, match="pageable"):
        Engine(model, {}, batch=2, max_len=16, page_size=4)


def test_engine_surfaces_corrupt_tune_cache(tmp_path, monkeypatch):
    """A persisted flash_decode winner that cannot build at the engine's
    shapes is an error at Engine construction — never a silent fallback to
    a default page size."""
    import json

    from repro.core.device import default_device
    from repro.core.tune import SCHEMA_VERSION, tune_cache_key
    from repro.kernels.flash_attention import flash_decode
    from repro.launch.tuning import serving_probes

    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    cfg = reduced(get_config("llama3_2_1b"))
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch, max_len = 2, 32
    args, probe = serving_probes(cfg, batch, max_len, max_len)["flash_decode"]
    backend, interpret, rp = flash_decode._resolve(probe)
    _, defines, _ = flash_decode._prepare(args, rp)
    dev = default_device(backend, interpret)
    digest, payload = tune_cache_key("flash_decode", defines,
                                     flash_decode.sweep, dev.backend,
                                     dev.interpret)
    path = tmp_path / "autotune" / f"{digest}.json"
    path.parent.mkdir(parents=True)
    path.write_text(json.dumps(dict(payload, schema=SCHEMA_VERSION,
                                    winner={"block_kv": 0},
                                    best_seconds=1.0)))
    before = dict(flash_decode.defaults)
    try:
        with pytest.raises(ValueError, match="block must be positive"):
            Engine(model, params, batch=batch, max_len=max_len)
        assert flash_decode.defaults == before   # nothing was adopted
    finally:
        flash_decode.defaults.clear()
        flash_decode.defaults.update(before)
