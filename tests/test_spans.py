"""The program's span recorder (``repro.runtime.spans``) and the spans and
counters the serving engine records with it: nesting and parents, the
queue span of each request, the decode span's attributes against the
scheduler, the counters against the requests drained, preemption, a
recorder turned off (as for measuring its cost), the spans on the
profiler's host plane,
and the stable names of the jitted programs."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import Device, Spec, Tile
from repro.models import LM
from repro.runtime import spans
from repro.serving import Engine

STEP_CHILDREN = {"engine.admit", "engine.grow", "engine.decode",
                 "engine.fetch", "engine.emit"}
ADMIT_CHILDREN = {"engine.prefill", "engine.scatter", "engine.first_token"}


@pytest.fixture(autouse=True)
def fresh():
    spans.reset()
    yield
    spans.reset()


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced(get_config("llama3_2_1b"))
    model = LM(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=n).tolist() for n in lengths]


def _spy_decode(eng):
    """The scheduler's state at each decode dispatch."""
    seen, fn = [], eng._step_fn
    sched = eng.sched

    def spy(*args):
        seen.append((len(sched.running),
                     sum(len(sched.pages.owned(sched.slots[s].rid))
                         for s in sched.running)))
        return fn(*args)

    eng._step_fn = spy
    return seen


@pytest.fixture(scope="module")
def drained(tiny):
    """Four requests through two slots, recorded once for the module."""
    cfg, model, params = tiny
    spans.reset()
    eng = Engine(model, params, batch=2, max_len=32, page_size=4)
    seen = _spy_decode(eng)
    max_new = [6, 4, 8, 5]
    rids = [eng.submit(p, m) for p, m in
            zip(_prompts(cfg, (5, 9, 3, 7)), max_new)]
    eng.drain(max_steps=300)
    return {"eng": eng, "rids": rids, "seen": seen, "tokens": sum(max_new),
            "records": spans.records(), "counters": spans.counters()}


def test_child_spans_nest_inside_the_step_and_carry_their_parent(drained):
    recs = drained["records"]
    by_id = {r["id"]: r for r in recs}
    eid = drained["eng"].engine_id
    assert all(r["attrs"]["engine"] == eid for r in recs)
    steps = [r for r in recs if r["name"] == "engine.step"]
    assert steps and all(r["parent"] is None for r in steps)
    for r in recs:
        if r["name"] in STEP_CHILDREN:
            want = "engine.step"
        elif r["name"] in ADMIT_CHILDREN:
            want = "engine.admit"
        else:
            continue
        p = by_id[r["parent"]]
        assert p["name"] == want, r
        assert p["start_ns"] <= r["start_ns"] <= r["end_ns"] <= p["end_ns"]
    names = {r["name"] for r in recs}
    assert STEP_CHILDREN | ADMIT_CHILDREN | {"engine.queue"} <= names
    # every step that decoded fetched and emitted once
    decodes = [r for r in recs if r["name"] == "engine.decode"]
    for name in ("engine.fetch", "engine.emit", "engine.grow"):
        assert len([r for r in recs if r["name"] == name]) == len(decodes)


def test_each_request_waits_in_the_queue_once_before_its_admission(drained):
    recs = drained["records"]
    for rid in drained["rids"]:
        queue = [r for r in recs if r["name"] == "engine.queue"
                 and r["attrs"]["rid"] == rid]
        admit = [r for r in recs if r["name"] == "engine.admit"
                 and r["attrs"]["rid"] == rid]
        assert len(queue) == 1 and len(admit) == 1, rid
        assert queue[0]["start_ns"] <= queue[0]["end_ns"] \
            <= admit[0]["start_ns"]
    # two slots: the last two requests waited for the first to finish
    waits = {r["attrs"]["rid"]: r["end_ns"] - r["start_ns"] for r in recs
             if r["name"] == "engine.queue"}
    steps = sorted(r["end_ns"] - r["start_ns"] for r in recs
                   if r["name"] == "engine.step")
    assert waits[drained["rids"][3]] > steps[0]


def test_decode_span_attributes_are_the_schedulers_state(drained):
    decodes = [r["attrs"] for r in drained["records"]
               if r["name"] == "engine.decode"]
    assert [(a["running"], a["pages_used"]) for a in decodes] == \
        drained["seen"]
    pool = drained["eng"].sched.pages
    assert {a["pages_total"] for a in decodes} == {pool.num_pages - 1}
    assert max(a["running"] for a in decodes) == 2


def test_admitted_and_retired_count_the_requests_drained(drained):
    c = drained["counters"]
    n = len(drained["rids"])
    assert c["engine.admitted"] == n and c["engine.retired"] == n
    assert c["engine.tokens"] == drained["tokens"]
    assert c.get("engine.preempted", 0) == 0


def test_preempted_counts_evictions_on_a_shrunk_pool(tiny):
    cfg, model, params = tiny
    eng = Engine(model, params, batch=3, max_len=24, page_size=4,
                 num_pages=9)
    rids = [eng.submit(p, m) for p, m in
            zip(_prompts(cfg, (6, 10, 4), seed=1), (8, 6, 9))]
    eng.drain(max_steps=500)
    evicted = sum(r.preempted for r in eng._requests.values())
    c = spans.counters()
    assert evicted > 0 and c["engine.preempted"] == evicted
    assert c["engine.admitted"] == len(rids) + evicted
    assert c["engine.retired"] == len(rids)
    recs = spans.records()
    for rid in rids:
        queue = sorted((r for r in recs if r["name"] == "engine.queue"
                        and r["attrs"]["rid"] == rid),
                       key=lambda r: r["start_ns"])
        admit = sorted((r for r in recs if r["name"] == "engine.admit"
                        and r["attrs"]["rid"] == rid),
                       key=lambda r: r["start_ns"])
        # a preempted request opens a new wait, closed by its re-admission
        assert len(queue) == len(admit) == 1 + eng._requests[rid].preempted
        for q, a in zip(queue, admit):
            assert q["end_ns"] <= a["start_ns"]


def test_nothing_is_recorded_with_recording_off(tiny, monkeypatch):
    cfg, model, params = tiny
    monkeypatch.setattr(spans, "_on", False)
    eng = Engine(model, params, batch=2, max_len=32, page_size=4)
    eng.submit(_prompts(cfg, (5,))[0], 3)
    out = eng.drain(max_steps=50)
    assert len(out[0]) == 3
    assert spans.records() == [] and spans.counters() == {}
    with spans.span("engine.step", engine=0):
        spans.end(spans.begin("engine.queue", rid=0))
        spans.count("engine.tokens")
    assert spans.records() == [] and spans.counters() == {}


def test_ring_keeps_the_newest_records():
    for i in range(spans.RING + 5):
        with spans.span("x", i=i):
            pass
    recs = spans.records()
    assert len(recs) == spans.RING
    assert recs[0]["attrs"]["i"] == 5 and recs[-1]["attrs"]["i"] == \
        spans.RING + 4


def test_async_span_takes_the_enclosing_span_as_parent():
    with spans.span("outer") as _:
        tok = spans.begin("wait", rid=3)
    with spans.span("later"):
        spans.end(tok)
    recs = {r["name"]: r for r in spans.records()}
    assert recs["wait"]["parent"] == recs["outer"]["id"]
    assert recs["wait"]["end_ns"] >= recs["later"]["start_ns"]
    assert recs["later"]["parent"] is None


def test_spans_lie_on_the_profilers_host_plane(tiny, tmp_path):
    from jax.profiler import ProfileData

    cfg, model, params = tiny
    eng = Engine(model, params, batch=2, max_len=32, page_size=4)
    eng.submit(_prompts(cfg, (5,))[0], 3)
    eng.step()                                    # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    seen = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            seen |= {e.name for line in plane.lines for e in line.events}
    assert {"engine.step", "engine.grow", "engine.decode", "engine.fetch",
            "engine.emit"} <= seen


def test_a_new_prompt_length_compiles_only_its_admission(tiny):
    cfg, model, params = tiny
    eng = Engine(model, params, batch=2, max_len=32, page_size=4)
    for p in _prompts(cfg, (5, 9)):
        eng.submit(p, 6)
    eng.drain(max_steps=100)
    assert {"compile.engine_decode", "compile.engine_prefill",
            "compile.engine_scatter", "compile.engine_first_token",
            "compile.engine_grow", "compile.engine_clear"} <= \
        set(spans.counters())
    before = spans.counters()
    eng.submit(_prompts(cfg, (7,), seed=3)[0], 3)
    eng.submit(_prompts(cfg, (9,), seed=4)[0], 3)        # a warmed length
    eng.drain(max_steps=100)
    after = spans.counters()
    new = {k: after[k] - before.get(k, 0) for k in after
           if k.startswith("compile.") and after[k] != before.get(k, 0)}
    # prefill and its page scatter are keyed on the prompt's length
    assert new == {"compile.engine_prefill": 1, "compile.engine_scatter": 1}


def test_kernel_jit_is_named_after_its_spec():
    def builder(D):
        def body(ctx, x, out):
            out[...] = 2.0 * x[...]

        return Spec("double_it", grid=(2,),
                    inputs=[Tile("x", (16,), jnp.float32, block=(8,))],
                    outputs=[Tile("out", (16,), jnp.float32, block=(8,))],
                    body=body)

    k = Device("jnp").build_kernel(builder, {})
    out, = k.run(jnp.arange(16, dtype=jnp.float32))
    np.testing.assert_array_equal(np.asarray(out), 2.0 * np.arange(16))
    assert spans.counters().get("compile.double_it") == 1
    assert "jit_double_it" in k.lowered_text(jnp.zeros(16, jnp.float32))


def test_serve_summary_reads_the_counters(capsys, monkeypatch):
    """``launch/serve.py``'s summary line: the engine's admissions,
    retirements, preemptions and tokens over the call, and the programs it
    compiled, from the recorder's counters."""
    from repro.launch import serve

    # keep this process's compiles out of the checkout's persistent cache
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    out = serve.main(["--reduced", "--batch", "2", "--prompt-len", "5",
                "--gen", "4"])
    lines = capsys.readouterr().out.splitlines()
    assert out.shape == (2, 4)
    assert "[serve] engine: 2 admissions, 2 retirements, 0 preemptions, " \
        "8 tokens" in lines
    compiled, = [ln for ln in lines if ln.startswith("[serve] compiled: ")]
    assert {"engine_decode", "engine_prefill"} <= \
        set(compiled[len("[serve] compiled: "):].split(", "))


def test_one_chip_engine_moves_no_collective_bytes(drained):
    """On one device the decode span says one chip, no placement span is
    recorded and the collective counter stays at 0 (the mesh's side:
    ``tests/test_mesh_shard.py``)."""
    recs = drained["records"]
    decodes = [r["attrs"] for r in recs if r["name"] == "engine.decode"]
    assert decodes and {a["chips"] for a in decodes} == {1}
    assert not [r for r in recs if r["name"] == "engine.shard"]
    assert drained["counters"]["engine.collective_bytes"] == 0
