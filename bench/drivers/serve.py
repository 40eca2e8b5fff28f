"""Serving cells: the program's continuous-batching ``Engine`` over paged KV
caches, driven from the benchmark's traffic.

Set-up makes the weights on the device from the seed, builds the engine and
warms every shape the window will use: one admission per prompt length of
the mix (prefill and its page scatter), the decode step, slot retirement and
page growth. The window then drives ``Engine.submit`` / ``Engine.step``:

- open loop: requests are submitted at their scheduled arrival times; time
  to first token counts from the scheduled arrival, so a stall delays every
  later request. After the window the engine steps on until every request
  that arrived in it has its first token, so the tails are over all of
  them; the gaps between tokens are those of every request up to then;
- closed loop: ``outstanding`` requests are kept submitted; the rate is the
  tokens emitted by the steps of the window over the window.

A token is stamped when ``Engine.step`` returns it: the step has copied the
next tokens to the host, so the device has produced them.
"""

from __future__ import annotations

import time

import numpy as np

from bench import harness, traffic
from bench.ref import dense_gqa


def arch_config(cfg: dict):
    """The program's configuration object from the file's keys."""
    from repro.configs import ArchConfig

    n = dense_gqa.dims(cfg)
    return ArchConfig(
        name=cfg["name"], family="dense", n_layers=n["L"], d_model=n["d"],
        n_heads=n["h"], n_kv_heads=n["hk"], d_ff=n["f"], vocab_size=n["v"],
        head_dim=n["hd"], rope_theta=n["theta"], norm_eps=n["eps"],
        tie_embeddings=False, dtype=cfg["torch_dtype"])


class ServeCell:
    def __init__(self, cfg: dict, mix: dict, devices):
        import jax

        from repro.models import LM

        self.cfg, self.mix, self.devices = cfg, mix, devices
        self.dims = dense_gqa.dims(cfg)
        self.model = LM(arch_config(cfg))
        if len(devices) != 1:
            raise NotImplementedError("bench: serving cells run on one chip")
        want = jax.tree.map(lambda a: (a.shape, a.dtype), jax.eval_shape(
            lambda: self.model.init(jax.random.PRNGKey(0))))
        have = jax.tree.map(lambda a: (a.shape, a.dtype),
                            dense_gqa.weight_shapes(cfg))
        if want != have:
            raise RuntimeError("bench: the program's parameter layout is not "
                               "the one the benchmark makes weights in")

    def load(self, seed: int):
        import jax

        self.weights = dense_gqa.init_weights(self.cfg, harness.seed_key(seed))
        jax.block_until_ready(self.weights)

    def engine(self):
        from repro.serving import Engine

        s = self.cfg["serving"]
        return Engine(self.model, self.weights, batch=s["batch"],
                      max_len=s["max_len"])

    def warm(self, eng, seconds: float):
        """Compile every program the window runs: one admission per prompt
        length of the mix, the decode step, retirement, and a page grown."""
        lengths = traffic.prompt_lengths(self.mix, seconds)
        rng = np.random.default_rng(0)
        v = self.dims["v"]
        for n in lengths:
            eng.submit(rng.integers(0, v, n).tolist(), 2)
        eng.drain()
        pg, max_len = eng.page_size, eng.max_len
        # a request of a warmed length whose slot grows a page: its next
        # write reaches the first position past its pages while it still
        # has a token to make
        grow = [(n, (-(-(n + 1) // pg) * pg) - n + 2) for n in lengths]
        grow = [(n, k) for n, k in grow if n + k <= max_len]
        if grow:
            n, k = min(grow, key=lambda g: g[1])
            eng.submit(rng.integers(0, v, n).tolist(), k)
            eng.drain()

    def measure(self, eng, reqs, seconds: float, tracer) -> dict:
        """Run the window. Returns per-request timings (seconds from the
        window's start) and the work of the traced steps."""
        meta = {}                         # rid -> record
        steps = []                        # the traced steps' work
        closed = self.mix["loop"] == "closed"
        win_tokens, last_end, nxt = 0, 0.0, 0

        def submit(r, at):
            rid = eng.submit(r.prompt.tolist(), r.max_new)
            meta[rid] = {"arrival": at, "prompt": len(r.prompt),
                         "max_new": r.max_new, "times": [], "req": r}

        t0 = time.perf_counter()
        if closed:
            for r in reqs[:self.mix["outstanding"]]:
                submit(r, 0.0)
            nxt = self.mix["outstanding"]
        while True:
            now = time.perf_counter() - t0
            tracer.tick(now)
            if closed:
                if now >= seconds:
                    break
            else:
                while nxt < len(reqs) and reqs[nxt].arrival_s <= now:
                    submit(reqs[nxt], reqs[nxt].arrival_s)
                    nxt += 1
                if nxt >= len(reqs) and all(m["times"]
                                            for m in meta.values()):
                    break                 # every request has begun
                if eng.idle:
                    with tracer.span("bench.wait_arrival"):
                        time.sleep(max(0.0, reqs[nxt].arrival_s - now))
                    continue
            before = {r.rid for r in eng.sched.slots if r is not None}
            traced = tracer.active
            with tracer.span("bench.engine_step"):
                out = eng.step()
            t = time.perf_counter() - t0
            work = {"admit": [], "decode": []}
            done = 0
            for rid, toks in out.items():
                m = meta[rid]
                m["times"].extend([t] * len(toks))
                admitted = rid not in before
                if admitted:
                    work["admit"].append(m["prompt"])
                if len(toks) > admitted:          # it decoded this step
                    work["decode"].append(m["prompt"] + len(m["times"]) - 1)
                done += len(m["times"]) >= m["max_new"]
            if t <= seconds:
                win_tokens += sum(len(x) for x in out.values())
                last_end = t
            if traced:
                steps.append(work)
            if closed:
                for _ in range(done):
                    if nxt < len(reqs):
                        submit(reqs[nxt], t)
                        nxt += 1
        tracer.stop()
        return {"meta": meta, "steps": steps, "eng": eng,
                "window_s": last_end if closed else seconds,
                "tokens_in_window": win_tokens}


def _sample(meta: dict, seed: int, k: int) -> list[int]:
    """The finished requests the check compares: the longest, and others
    drawn from the seed."""
    done = [rid for rid, m in meta.items() if len(m["times"]) == m["max_new"]]
    if not done:
        return []
    longest = max(done, key=lambda r: meta[r]["prompt"] + meta[r]["max_new"])
    rest = [r for r in done if r != longest]
    rng = np.random.default_rng(seed + 1)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def served_sequences(res: dict, seed: int, k: int) -> list[tuple[np.ndarray, int]]:
    """(prompt + served tokens, prompt length) of the sampled requests."""
    eng, meta = res["eng"], res["meta"]
    out = []
    for rid in _sample(meta, seed, k):
        toks = eng.result(rid)
        out.append((np.concatenate([meta[rid]["req"].prompt,
                                    np.asarray(toks, np.int32)]),
                    meta[rid]["prompt"]))
    return out


def run(ctx) -> dict:
    """One run of a serving cell: set-up, the window, then the check."""
    import jax

    cell = ServeCell(ctx.cfg, ctx.mix, ctx.devices)
    cell.load(ctx.seed)
    eng = cell.engine()
    cell.warm(eng, ctx.seconds)
    reqs = traffic.generate(ctx.mix, ctx.seed, ctx.seconds, cell.dims["v"])
    jax.block_until_ready(eng.cache)
    setup_s = time.perf_counter() - ctx.t_start
    with ctx.compiles.counting():
        res = cell.measure(eng, reqs, ctx.seconds, ctx.tracer)
    device = harness.device_info(ctx.devices)
    seqs = served_sequences(res, ctx.seed, ctx.check.SAMPLE)
    meta = res.pop("meta")
    # an open-loop request that never began failed; a closed loop's last
    # requests are still queued when the window closes
    failed = sum(1 for m in meta.values() if ctx.mix["loop"] == "open"
                 and not m["times"])
    del eng, res["eng"]                   # free the KV pool first
    checks = ctx.check.compare(ctx.cfg, cell.weights, seqs)
    out = dict(res, setup_s=setup_s, meta=meta, attempted=len(meta),
               failed=failed, device=device, checks=checks, dims=cell.dims)
    if ctx.control:
        out["control"] = ctx.check.compare(ctx.cfg, cell.weights, seqs,
                                           control=True)
    return out
