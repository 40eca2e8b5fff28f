"""The readers of the engine's host-loop metrics (``queue_wait_p85_ms.chat``,
``queue_wait_mean_ms.chat``, ``engine_host_ms.offline``,
``kv_pages_used.offline``) on recorded engine
runs: they read the newest engine's spans from the window's first request
on, agree with the same numbers worked out from the records by hand, and
read ``None`` where the program recorded no spans."""

import bench_testroot
import numpy as np
import pytest

from bench import harness

METRICS = ("queue_wait_p85_ms.chat", "queue_wait_mean_ms.chat",
           "engine_host_ms.offline", "kv_pages_used.offline")


def reader(name):
    return harness.load_module(
        bench_testroot.REPO / "bench" / "metrics" / f"{name}.py").read


@pytest.fixture(scope="module")
def tiny():
    import jax

    from repro.configs import get_config, reduced
    from repro.models import LM

    cfg = reduced(get_config("llama3_2_1b"))
    model = LM(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _serve(tiny, lengths, max_new, eng=None):
    from repro.serving import Engine

    cfg, model, params = tiny
    if eng is None:
        eng = Engine(model, params, batch=2, max_len=64, page_size=4)
    rng = np.random.default_rng(len(lengths))
    rids = [eng.submit(rng.integers(0, cfg.vocab_size, n).tolist(), m)
            for n, m in zip(lengths, max_new)]
    eng.drain(max_steps=500)
    return eng, rids


@pytest.fixture(scope="module")
def recorded(tiny):
    """A warm-up of six requests through two slots (long queue waits), then
    a window of five; the window's ``meta`` holds only its own rids."""
    from repro.runtime import spans

    spans.reset()
    eng, warm = _serve(tiny, (9, 5, 12, 7, 3, 10), (12, 12, 12, 12, 12, 12))
    eng, window = _serve(tiny, (6, 11, 4, 8, 5), (5, 9, 3, 7, 4), eng)
    recs = spans.records()
    yield {"eng": eng, "warm": warm, "window": window, "records": recs,
           "rec": {"meta": {rid: {} for rid in window}}}
    spans.reset()


def _by_hand(recs, window):
    """The four numbers from the window's records, worked out here."""
    t0 = min(r["start_ns"] for r in recs if r["name"] == "engine.queue"
             and r["attrs"]["rid"] == min(window))
    win = [r for r in recs if r["start_ns"] >= t0]
    waits = [(r["end_ns"] - r["start_ns"]) / 1e6 for r in win
             if r["name"] == "engine.queue" and r["attrs"]["rid"] in window]
    steps = [r for r in win if r["name"] == "engine.step"]
    host = []
    for s in steps:
        admits = {r["id"] for r in win if r["parent"] == s["id"]
                  and r["name"] == "engine.admit"}
        waited = sum(r["end_ns"] - r["start_ns"] for r in win
                     if (r["name"] == "engine.fetch"
                         and r["parent"] == s["id"])
                     or (r["name"] == "engine.first_token"
                         and r["parent"] in admits))
        host.append((s["end_ns"] - s["start_ns"] - waited) / 1e6)
    used = [100 * r["attrs"]["pages_used"] / r["attrs"]["pages_total"]
            for r in win if r["name"] == "engine.decode"]
    return {"queue_wait_p85_ms.chat": float(np.percentile(waits, 85)),
            "queue_wait_mean_ms.chat": float(np.mean(waits)),
            "engine_host_ms.offline": float(np.mean(host)),
            "kv_pages_used.offline": float(np.mean(used))}, len(waits)


def test_readers_agree_with_the_records_worked_by_hand(recorded):
    want, n = _by_hand(recorded["records"], recorded["window"])
    assert n == len(recorded["window"])     # one first wait per request
    for name in METRICS:
        got = reader(name)(recorded["rec"])
        assert got == pytest.approx(want[name], rel=1e-9), name
    assert 0 < want["kv_pages_used.offline"] <= 100
    assert want["engine_host_ms.offline"] > 0


def test_the_window_starts_at_its_first_request(recorded):
    """The same records read from the warm-up's first request on hold the
    warm-up's steps and waits too (six requests for two slots), and read
    other numbers: the window is chosen by the rids in ``meta``."""
    recs, warm = recorded["records"], recorded["warm"]
    assert min(recorded["window"]) > max(warm)
    early = {"meta": {rid: {} for rid in warm}}
    want, n = _by_hand(recs, warm)
    assert n == len(warm)
    window, _ = _by_hand(recs, recorded["window"])
    for name in METRICS:
        got = reader(name)(early)
        assert got == pytest.approx(want[name], rel=1e-9), name
        assert got != pytest.approx(window[name], rel=1e-6), name


def test_the_newest_engine_is_read(recorded, tiny):
    """A second engine restarts its rids at 0: only its spans are read."""
    from repro.runtime import spans

    eng2, rids = _serve(tiny, (5, 7), (3, 4))
    assert rids == [0, 1] and eng2.engine_id > recorded["eng"].engine_id
    rec = {"meta": {rid: {} for rid in rids}}
    mine = [r for r in spans.records()
            if r["attrs"].get("engine") == eng2.engine_id]
    want, n = _by_hand(mine, rids)
    assert n == 2
    for name in METRICS:
        assert reader(name)(rec) == pytest.approx(want[name], rel=1e-9)


@pytest.mark.parametrize("name", METRICS)
def test_no_spans_read_none(name):
    from repro.runtime import spans

    spans.reset()
    assert reader(name)({"meta": {0: {}, 1: {}}}) is None
    assert reader(name)({"meta": {}}) is None


def test_readers_on_a_tiny_chat_run(tmp_path):
    """Through ``bench/run.py`` on the CPU: the readers find the window in
    the run's own ``rec``, and no request waited in the queue longer than
    it waited for its first token."""
    root = bench_testroot.make_root(tmp_path)
    out = bench_testroot.run_cell(root, "tiny.chat", seconds=3.0)
    assert out["correct"]
    rec = out["rec"]
    got = {name: reader(name)(rec) for name in METRICS}
    assert all(v is not None for v in got.values()), got
    assert 0 <= got["queue_wait_p85_ms.chat"] <= \
        out["metrics"]["ttft_p85_ms"]["value"]
    assert got["queue_wait_mean_ms.chat"] >= 0
    assert got["engine_host_ms.offline"] > 0
    assert 0 < got["kv_pages_used.offline"] <= 100
