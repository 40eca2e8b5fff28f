"""Batched serving: continuous batching over paged KV caches.

``generate`` is a thin wrapper over :class:`repro.serving.Engine` — one
jitted one-token decode step runs over ``batch`` slots with per-slot
sequence positions, EOS retirement + mid-flight slot refill, and
preemption-by-eviction when the page pool runs dry. Models the paged path
cannot serve (MLA, rolling windows, SSM hybrids) fall back to
``_generate_static``, the classic static-batch loop — which doubles as the
per-sequence oracle the engine's bit-parity tests compare against.

Warmup consults the persistent autotune cache (``$REPRO_CACHE_DIR``)
through :func:`repro.launch.tuning.adopt`: any op with a persisted
``op.tune`` winner for the serving shapes gets its defaults updated, so
the prefill/decode paths pick the TUNED block sizes — and the engine
adopts ``flash_decode``'s tuned block as its page size.

On the engine path ``prefill_s`` is the admissions' share of the drain,
read from the engine's own ``engine.admit`` spans (``repro.runtime.spans``).
The summary line reads the recorder's counters over the call: the engine's
admissions, retirements, preemptions and tokens, and the programs compiled.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_1b --reduced \
      --batch 4 --prompt-len 16 --gen 32
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced as reduce_cfg
from repro.models import LM
from repro.parallel.steps import build_prefill_step, build_serve_step
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.runtime import spans

__all__ = ["apply_tuned_winners", "generate", "main"]


def apply_tuned_winners(cfg, batch: int, prompt_len: int, max_len: int):
    """DEPRECATED shim: use ``repro.launch.tuning.adopt(cfg, shapes,
    kind="serve")`` — one adoption surface now covers the serve/train/mesh
    probe families. Kept for callers of the old per-launcher name."""
    from repro.launch.tuning import adopt

    return adopt(cfg, dict(batch=batch, prompt_len=prompt_len,
                           max_len=max_len), kind="serve")


def _pad_token(eos_id, pad_id):
    """The token written after a sequence finishes. Explicit ``pad_id``
    wins; otherwise the EOS token when one is configured, else 0 (the old
    implicit behavior, now a documented contract)."""
    if pad_id is not None:
        return pad_id
    return eos_id if eos_id is not None else 0


def generate(model: LM, params, prompts: np.ndarray, *, gen_tokens: int,
             mesh=None, eos_id: int | None = None, greedy: bool = True,
             rng=None, max_len: int | None = None, temperature: float = 1.0,
             pad_id: int | None = None, engine: str = "auto",
             page_size: int | None = None, num_pages: int | None = None):
    """prompts: (B, P) int32 -> (B, <=gen_tokens) int32 + stats.

    Rows that finish early are padded with ``pad_id`` (default: ``eos_id``
    when set, else 0). Non-greedy sampling draws from
    ``softmax(logits / temperature)``.

    ``engine="auto"`` serves through the continuous-batching
    :class:`repro.serving.Engine` whenever the model is pageable;
    ``"static"`` forces the static-batch loop (``"paged"`` forces the
    engine and raises if the model can't page). ``page_size`` /
    ``num_pages`` pass through to the engine; ``max_len`` sizes the caches
    on both paths (default: exactly prompt + generation)."""
    b, plen = prompts.shape
    max_len = max_len or (plen + gen_tokens)
    if engine not in ("auto", "paged", "static"):
        raise ValueError(f"engine must be auto|paged|static, got {engine!r}")
    use_engine = (model.pageable if engine == "auto" else engine == "paged")
    if not use_engine:
        return _generate_static(model, params, prompts,
                                gen_tokens=gen_tokens, mesh=mesh,
                                eos_id=eos_id, greedy=greedy, rng=rng,
                                max_len=max_len, temperature=temperature,
                                pad_id=pad_id)
    from repro.serving import Engine

    eng = Engine(model, params, batch=b, max_len=max_len,
                 page_size=page_size, num_pages=num_pages, eos_id=eos_id,
                 greedy=greedy, temperature=temperature, rng=rng, mesh=mesh)
    t0 = time.perf_counter()
    rids = [eng.submit(prompts[i].tolist(), gen_tokens) for i in range(b)]
    results = eng.drain(max_steps=8 * (b * gen_tokens + b))
    drain_s = time.perf_counter() - t0
    admit_s = _admit_seconds(eng.engine_id)
    pad = _pad_token(eos_id, pad_id)
    rows = [results[r] for r in rids]
    width = (max(len(r) for r in rows)
             if all(eos_id is not None and r and r[-1] == eos_id
                    for r in rows) else gen_tokens)
    out = np.full((b, width), pad, np.int32)
    n_gen = 0
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
        n_gen += len(r)
    preempted = sum(req.preempted for req in eng._requests.values())
    return out, {"prefill_s": admit_s, "decode_s": drain_s - admit_s,
                 "tokens_per_s": n_gen / max(drain_s, 1e-9),
                 "tuned": {}, "engine": True, "preempted": preempted,
                 "page_size": eng.page_size}


def _admit_seconds(engine_id: int) -> float:
    """Seconds one engine spent admitting (prefill, KV scatter, first
    token), from its ``engine.admit`` spans still in the recorder's ring
    (the newest ``spans.RING`` records)."""
    return sum(r["end_ns"] - r["start_ns"] for r in spans.records()
               if r["name"] == "engine.admit"
               and r["attrs"].get("engine") == engine_id) / 1e9


def _generate_static(model: LM, params, prompts: np.ndarray, *,
                     gen_tokens: int, mesh=None, eos_id: int | None = None,
                     greedy: bool = True, rng=None,
                     max_len: int | None = None, temperature: float = 1.0,
                     pad_id: int | None = None):
    """Static batching: one prefill + a jitted decode step over a contiguous
    cache, every slot in lockstep. The engine's bit-parity oracle, and the
    serving path for non-pageable models."""
    cfg = model.cfg
    b, plen = prompts.shape
    max_len = max_len or (plen + gen_tokens)
    if model.has_positional_cache and plen + gen_tokens > max_len:
        raise ValueError(
            f"kv cache overflow: prompt_len {plen} + gen_tokens {gen_tokens} "
            f"= {plen + gen_tokens} tokens but max_len={max_len}; raise "
            "max_len (rolling-window archs are exempt — their caches rotate)")
    if not greedy and rng is None:
        rng = jax.random.PRNGKey(0)
    if temperature <= 0:
        raise ValueError(f"temperature must be > 0, got {temperature}")
    mesh = mesh or make_local_mesh(model=1)
    pad = _pad_token(eos_id, pad_id)

    # adopt persisted autotune winners BEFORE the steps trace: the traced
    # kernels bake in whatever block sizes the ops resolve to
    tuned = apply_tuned_winners(cfg, b, plen, max_len)

    prefill_fn, _ = build_prefill_step(model, mesh, batch=b, max_len=max_len)
    serve_fn, sh = build_serve_step(model, mesh, batch=b, max_len=max_len,
                                    greedy=greedy)

    t0 = time.perf_counter()
    logits, cache = prefill_fn(params, {"tokens": jnp.asarray(prompts)})
    cache = jax.block_until_ready(jax.device_put(cache, sh["cache"]))
    prefill_s = time.perf_counter() - t0

    out = np.zeros((b, gen_tokens), np.int32)
    done = np.zeros((b,), bool)
    tok = np.asarray(model.greedy_token(logits))
    t0 = time.perf_counter()
    for t in range(gen_tokens):
        out[:, t] = np.where(done, pad, tok)
        if eos_id is not None:
            done |= tok == eos_id
            if done.all():
                out = out[:, :t + 1]
                break
        if greedy:
            nxt, logits, cache = serve_fn(params, cache,
                                          jnp.asarray(tok[:, None]))
            tok = np.asarray(nxt)
        else:
            logits, cache = serve_fn(params, cache, jnp.asarray(tok[:, None]))
            rng, sub = jax.random.split(rng)
            tok = np.asarray(jax.random.categorical(
                sub, logits[..., :cfg.vocab_size] / temperature))
    decode_s = time.perf_counter() - t0
    n_gen = out.shape[1] * b
    return out, {"prefill_s": prefill_s, "decode_s": decode_s,
                 "tokens_per_s": n_gen / max(decode_s, 1e-9),
                 "tuned": tuned, "engine": False}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_2_1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", default="auto",
                    choices=("auto", "paged", "static"))
    from repro.core import ANALYZE_MODES, set_analysis_mode
    ap.add_argument("--analyze", default=None, choices=ANALYZE_MODES,
                    help="kernel static-analyzer strictness for every build "
                         "this run performs (default: $REPRO_ANALYZE or error)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    if args.analyze is not None:
        set_analysis_mode(args.analyze)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    prompts = np.random.RandomState(args.seed).randint(
        0, cfg.vocab_size, (args.batch, args.prompt_len)).astype(np.int32)
    before = spans.counters()
    out, stats = generate(model, params, prompts, gen_tokens=args.gen,
                          engine=args.engine)
    ran = {k: v - before.get(k, 0) for k, v in spans.counters().items()}
    if stats.get("tuned"):
        print(f"[serve] adopted persisted tune winners: {stats['tuned']}")
    path = "paged-engine" if stats["engine"] else "static"
    print(f"[serve] {path} batch={args.batch} prompt={args.prompt_len} "
          f"gen={out.shape[1]}: prefill {stats['prefill_s']:.2f}s, "
          f"{stats['tokens_per_s']:.1f} tok/s decode")
    if stats["engine"]:
        print(f"[serve] engine: {ran.get('engine.admitted', 0)} admissions, "
              f"{ran.get('engine.retired', 0)} retirements, "
              f"{ran.get('engine.preempted', 0)} preemptions, "
              f"{ran.get('engine.tokens', 0)} tokens")
    compiled = sorted(k[len("compile."):] for k, n in ran.items()
                      if k.startswith("compile.") and n)
    print(f"[serve] compiled: {', '.join(compiled) or 'nothing'}")
    print("[serve] first row:", out[0, :16].tolist())
    return out


if __name__ == "__main__":
    main()
