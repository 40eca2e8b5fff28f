"""Fault-tolerant training launcher.

Integrates: sharded train step, deterministic host-sharded data with
prefetch, async atomic checkpointing + resume, straggler watchdog, failure
injection with automatic restore-retry, and elastic restart hooks.

CLI (CPU-sized by default):
  PYTHONPATH=src python -m repro.launch.train --arch llama3_2_1b --reduced \
      --steps 50 --global-batch 8 --seq-len 64 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, reduced as reduce_cfg
from repro.data import Prefetcher, SyntheticLMData
from repro.models import LM
from repro.optim import AdamW, WarmupCosine
from repro.parallel.steps import build_train_step
from repro.runtime import ChaosError, FailureInjector, StepWatchdog
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh

__all__ = ["TrainLoop", "apply_tuned_winners", "main", "validate_host_batch"]


def validate_host_batch(tokens, vocab_size: int):
    """Reject out-of-range token ids while the batch is still HOST data.

    The jitted train step sees tracers, so ``LM.loss``'s label guard cannot
    fire there — this is the host-side complement: a label >= vocab_size (or
    negative) would otherwise silently train against padded-vocab logits."""
    t = np.asarray(tokens)
    if t.size == 0:
        return
    lo, hi = int(t.min()), int(t.max())
    if lo < 0 or hi >= vocab_size:
        raise ValueError(
            f"batch tokens out of range [{lo}, {hi}] for vocab_size="
            f"{vocab_size}: the jitted CE would silently train on padded-"
            "vocab logits; fix the data pipeline")


def apply_tuned_winners(cfg, global_batch: int, seq_len: int):
    """DEPRECATED shim: use ``repro.launch.tuning.adopt(cfg, shapes,
    kind="train")`` — one adoption surface now covers the serve/train/mesh
    probe families. Kept for callers of the old per-launcher name."""
    from repro.launch.tuning import adopt

    return adopt(cfg, dict(global_batch=global_batch, seq_len=seq_len),
                 kind="train")


@dataclasses.dataclass
class TrainLoop:
    """Restartable training loop with recovery; returns loss history."""

    model: LM
    mesh: object
    global_batch: int
    seq_len: int
    steps: int
    ckpt_dir: str | None = None
    ckpt_every: int = 50
    peak_lr: float = 3e-3
    seed: int = 0
    injector: FailureInjector | None = None
    max_retries: int = 3
    log_every: int = 10
    verbose: bool = True

    def run(self):
        model, cfg = self.model, self.model.cfg
        optimizer = AdamW(schedule=WarmupCosine(
            peak_lr=self.peak_lr, warmup_steps=max(self.steps // 20, 5),
            total_steps=self.steps))
        batch_shapes = {"tokens": jax.ShapeDtypeStruct(
            (self.global_batch, self.seq_len), jnp.int32)}
        if cfg.frontend:
            batch_shapes["prefix_embeddings"] = jax.ShapeDtypeStruct(
                (self.global_batch, cfg.num_prefix_embeddings, cfg.d_model),
                jnp.dtype(cfg.dtype))

        # adopt persisted autotune winners BEFORE the step traces
        tuned = apply_tuned_winners(cfg, self.global_batch, self.seq_len)
        if tuned and self.verbose:
            print(f"[train] adopted persisted tune winners: {tuned}")

        step_fn, shardings = build_train_step(model, optimizer, self.mesh,
                                              batch_shapes=batch_shapes)
        data = SyntheticLMData(vocab_size=cfg.vocab_size, seq_len=self.seq_len,
                               global_batch=self.global_batch, seed=self.seed)
        mgr = CheckpointManager(self.ckpt_dir) if self.ckpt_dir else None
        watchdog = StepWatchdog(absolute_deadline_s=None)

        def fresh_state():
            params = model.init(jax.random.PRNGKey(self.seed))
            params = jax.device_put(params, shardings["params"])
            opt = optimizer.init(params)
            opt = jax.device_put(opt, shardings["opt"])
            return params, opt, 0

        def restore_state():
            template = jax.eval_shape(
                lambda: (model.init(jax.random.PRNGKey(self.seed)),))[0]
            opt_t = jax.eval_shape(lambda: optimizer.init(template))
            step, (params, opt), _ = mgr.restore(
                (template, opt_t),
                shardings=(shardings["params"], shardings["opt"]))
            return params, opt, step

        if mgr and mgr.latest_step() is not None:
            params, opt_state, start = restore_state()
            if self.verbose:
                print(f"[train] resumed from step {start}")
        else:
            params, opt_state, start = fresh_state()

        history = []
        step = start
        retries = 0
        prefetch = Prefetcher(data, start_step=step)
        try:
            while step < self.steps:
                try:
                    if self.injector:
                        self.injector.maybe_fail(step)
                    dstep, host_batch = prefetch.next()
                    validate_host_batch(host_batch, cfg.vocab_size)
                    batch = {"tokens": jnp.asarray(host_batch)}
                    if cfg.frontend:
                        rs = np.random.Generator(np.random.Philox(
                            key=[self.seed * 2654435761 + 7, dstep]))
                        batch["prefix_embeddings"] = jnp.asarray(
                            rs.standard_normal((self.global_batch,
                                                cfg.num_prefix_embeddings,
                                                cfg.d_model), np.float32),
                            jnp.dtype(cfg.dtype))
                    batch = jax.device_put(batch, shardings["batch"])
                    watchdog.start()
                    params, opt_state, loss, metrics = step_fn(
                        params, opt_state, batch)
                    loss = float(loss)
                    watchdog.stop()
                    history.append(loss)
                    if self.verbose and step % self.log_every == 0:
                        print(f"[train] step {step:5d} loss {loss:8.4f} "
                              f"lr {float(metrics['lr']):.2e} "
                              f"gnorm {float(metrics['grad_norm']):.2f}")
                    step += 1
                    if mgr and step % self.ckpt_every == 0:
                        mgr.save(step, (params, opt_state),
                                 meta={"loss": loss})
                except ChaosError as e:
                    retries += 1
                    if self.verbose:
                        print(f"[train] {e} -> recovering "
                              f"(retry {retries}/{self.max_retries})")
                    if retries > self.max_retries:
                        raise
                    prefetch.close()
                    if mgr and mgr.latest_step() is not None:
                        params, opt_state, step = restore_state()
                    else:
                        params, opt_state, step = fresh_state()
                    prefetch = Prefetcher(data, start_step=step)
            if mgr:
                mgr.save(self.steps, (params, opt_state), async_=False,
                         meta={"loss": history[-1] if history else None})
                mgr.wait()
        finally:
            prefetch.close()
        return {"history": history, "params": params, "opt": opt_state,
                "straggler_flags": watchdog.flagged, "final_step": step,
                "tuned": tuned}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_2_1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--peak-lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--remat", default="none", choices=["none", "full", "dots"])
    ap.add_argument("--fail-at", type=int, nargs="*", default=[])
    ap.add_argument("--data-axis", type=int, default=None)
    ap.add_argument("--model-axis", type=int, default=1)
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
    model = LM(cfg, remat=args.remat)
    mesh = make_local_mesh(data=args.data_axis, model=args.model_axis)
    injector = FailureInjector(args.fail_at) if args.fail_at else None
    loop = TrainLoop(model=model, mesh=mesh, global_batch=args.global_batch,
                     seq_len=args.seq_len, steps=args.steps,
                     ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                     peak_lr=args.peak_lr, injector=injector)
    t0 = time.time()
    out = loop.run()
    h = out["history"]
    print(f"[train] done: {len(h)} steps in {time.time() - t0:.1f}s; "
          f"loss {h[0]:.3f} -> {h[-1]:.3f}")
    return out


if __name__ == "__main__":
    main()
