"""Bring-up check on one TPU chip: serve, train and solve through the normal
entry points, with the Pallas kernels compiled and checked.

    python chip_smoke.py              # one chip (the phases below)
    python chip_smoke.py --chips 4    # four chips: sharded serving only

Phases (one process; any failure raises and the exit code is non-zero):

1. Platform gate: JAX must run on a TPU, or the script exits before any
   result is printed.
2. Serve: internlm2-1.8B at its published widths and depth (bf16, random
   weights from ``--seed``) through ``repro.serving.Engine`` — 8 prompts of
   seeded mixed lengths, 32 new tokens each, drained through
   ``submit``/``drain``. The compiled decode step must contain the
   ``flash_decode_paged``, ``lm_head_logits`` and ``rmsnorm`` kernels, and
   the decode logits of two requests must match a float32 full-sequence
   reference forward (XLA reference kernels, highest matmul precision).
3. Train: three ``TrainLoop`` steps at internlm2-1.8B widths, depth cut to
   2 layers, global batch 4 x 2048 tokens: the loss must be finite, and one
   batch's gradients must match the float32 reference path.
4. Solve: the paper's FD wave solver (``FDWave``) through the ``fd2d``
   kernel at 4096^2 float32 for 10 steps, against ``reference_step``.

With ``--chips 4``: internlm2-20B at full width and depth, its parameters
initialised sharded over a (data=1, model=4) mesh, serves 4 requests
through ``Engine(mesh=...)``, whose decode step must contain the kernels.
Every decode step's logits are compared with a full-sequence forward of the
same sharded parameters on the XLA reference path over the same tokens; a
control whose first page differs must fall outside the limit.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

# bf16 weights, activations and KV cache against a float32 reference: per
# decode step the logits agree to about 1e-2 in relative L2 norm. A decode
# that reads a wrong page or applies a wrong mask attends different context;
# the run measures how far that moves the logits (a reference over a prompt
# whose first page was replaced) and requires it to exceed this bound.
LOGITS_RTOL = 5e-2
# gradients of a 2-layer model, bf16 kernel path vs float32 reference: about
# 1e-2 relative L2 per parameter; a stale accumulation block is O(1)
GRAD_RTOL = 1e-1
# fd2d: the same float32 stencil summed in another order, over 10 steps
FD_ATOL = 1e-5


def log(msg):
    print(msg, flush=True)


def platform_gate(chips: int):
    import jax

    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: JAX runs on {jax.default_backend()!r}, not a "
                 "TPU; nothing was checked")
    devs = jax.devices()
    if len(devs) < chips:
        sys.exit(f"chip_smoke: {chips} chips asked for, {len(devs)} found")
    dev = devs[0]
    log(f"platform {dev.platform} kind {dev.device_kind} count {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def rel_l2(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def reference_logits(cfg, params, seqs):
    """float32 full-sequence logits (len(seqs), T, vocab) of equal-length
    token sequences through the XLA references."""
    import jax
    import jax.numpy as jnp

    from repro.layers.common import use_kernel_backend
    from repro.models import LM

    ref = LM(dataclasses.replace(cfg, dtype="float32"), fused_head=False)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with use_kernel_backend("jnp"), jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(ref.forward)(p32, jnp.asarray(seqs, jnp.int32))
    out = np.asarray(logits[:, :, :cfg.vocab_size])
    del p32, logits
    return out


def serve_phase(seed: int):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.lang import kernel_names
    from repro.models import LM
    from repro.serving import Engine

    cfg = get_config("internlm2_1_8b")
    model = LM(cfg)
    t0 = time.time()
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    log(f"serve: internlm2_1_8b {model.param_count(params) / 1e9:.3f}B "
        f"params initialised in {time.time() - t0:.1f}s")

    rng = np.random.default_rng(seed)
    lens = rng.integers(64, 1025, size=8)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n)).tolist()
               for n in lens]
    engine = Engine(model, params, batch=8, max_len=2048)
    rids = [engine.submit(p, 32) for p in prompts]
    checked = [rids[int(np.argmax(lens))], rids[int(np.argmin(lens))]]
    log(f"serve: prompt lengths {lens.tolist()}, page size "
        f"{engine.page_size}")

    # compiled decode step: the kernels must be in it
    toks = jax.ShapeDtypeStruct((8, 1), jnp.int32)
    t0 = time.time()
    text = engine.decode_step.lower(engine.params, engine.cache,
                                    toks).compile().as_text()
    compile_s = time.time() - t0
    names = kernel_names(text)
    missing = {"flash_decode_paged", "lm_head_logits", "rmsnorm"} - names
    if missing:
        raise AssertionError(f"decode step lacks kernels {sorted(missing)}; "
                             f"found {sorted(names)}")
    log(f"serve: decode step compiled in {compile_s:.1f}s with kernels "
        f"{sorted(names)}")

    # drive the engine step by step (what drain does) to keep the decode
    # logits of the checked requests
    steps = {r: [] for r in checked}
    t0 = time.time()
    while not engine.idle:
        engine.step()
        if engine.last_decode is None:
            continue
        logits, slot_of = engine.last_decode
        for r in checked:
            if r in slot_of:
                steps[r].append(np.asarray(
                    logits[slot_of[r], :cfg.vocab_size]))
        engine.last_decode = None
    results = engine.drain()
    wall_s = time.time() - t0
    n_tok = sum(len(results[r]) for r in rids)
    if any(len(results[r]) != 32 for r in rids):
        raise AssertionError("a request did not get its 32 tokens")
    log(f"serve: {n_tok} tokens generated for {len(rids)} requests in "
        f"{wall_s:.1f}s wall (first-use compiles included)")
    page = engine.page_size
    del engine

    for r in checked:
        prompt, gen = prompts[rids.index(r)], results[r]
        got = np.stack(steps[r])                  # decode steps: 31
        if got.shape[0] != len(gen) - 1:
            raise AssertionError(f"request {r}: {got.shape[0]} decode steps "
                                 f"for {len(gen)} tokens")
        seq = prompt + gen[:-1]
        ctrl_seq = list(seq)
        n = min(page, len(prompt) - 1)
        ctrl_seq[:n] = rng.integers(0, cfg.vocab_size, size=n).tolist()
        ref, ctrl = reference_logits(cfg, params,
                                     [seq, ctrl_seq])[:, len(prompt):]
        err = max(rel_l2(g, w) for g, w in zip(got, ref))
        ctrl_err = min(rel_l2(g, w) for g, w in zip(got, ctrl))
        log(f"serve: request {r} (prompt {len(prompt)}): decode logits vs "
            f"f32 reference, worst step rel-L2 {err:.3e} (limit "
            f"{LOGITS_RTOL}); with the first {n} prompt tokens replaced, "
            f"best step {ctrl_err:.3e}")
        if not err <= LOGITS_RTOL:
            raise AssertionError(f"request {r}: decode logits off the "
                                 f"reference by {err:.3e}")
        if not ctrl_err > LOGITS_RTOL:
            raise AssertionError(
                f"request {r}: replacing the first page moved the logits "
                f"only {ctrl_err:.3e}: the check cannot see a wrong page")
    del params


def train_phase(seed: int):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.lang import kernel_names
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import TrainLoop
    from repro.layers.common import use_kernel_backend
    from repro.models import LM

    cfg = dataclasses.replace(get_config("internlm2_1_8b"), n_layers=2)
    model = LM(cfg)
    t0 = time.time()
    out = TrainLoop(model=model, mesh=make_local_mesh(), global_batch=4,
                    seq_len=2048, steps=3, seed=seed, verbose=False).run()
    hist = out["history"]
    log(f"train: 3 steps, losses {[round(x, 4) for x in hist]}, "
        f"{time.time() - t0:.1f}s wall (compile included)")
    if len(hist) != 3 or not np.all(np.isfinite(hist)):
        raise AssertionError(f"train loss not finite: {hist}")
    del out

    # one batch's gradients: kernels (bf16) vs the float32 reference path
    params = jax.jit(model.init)(jax.random.PRNGKey(seed + 1))
    toks = jax.random.randint(jax.random.PRNGKey(seed + 2), (2, 1024), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks}
    kgrad = jax.jit(jax.grad(lambda p: model.loss(p, batch)[0]))
    compiled = kgrad.lower(params).compile()
    names = kernel_names(compiled.as_text())
    want = {"flash_attention_fwd", "flash_attention_bwd_dkdv",
            "flash_attention_bwd_dq", "lm_head_ce", "lm_head_ce_bwd_dx",
            "lm_head_ce_bwd_dw"}
    if want - names:
        raise AssertionError(f"train grad lacks kernels {sorted(want - names)}")
    g = compiled(params)
    ref = LM(dataclasses.replace(cfg, dtype="float32"), fused_head=False)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with use_kernel_backend("jnp"), jax.default_matmul_precision("highest"):
        gr = jax.jit(jax.grad(lambda p: ref.loss(p, batch)[0]))(p32)
    leaves = jax.tree_util.tree_leaves_with_path(g)
    errs = {jax.tree_util.keystr(k): rel_l2(a.astype(jnp.float32), b)
            for (k, a), b in zip(leaves, jax.tree.leaves(gr))}
    worst = max(errs, key=errs.get)
    log(f"train: grads vs f32 reference, worst rel-L2 {errs[worst]:.3e} at "
        f"{worst} (limit {GRAD_RTOL}); kernels {sorted(names & want)}")
    if not errs[worst] <= GRAD_RTOL:
        raise AssertionError(f"gradient {worst} off by {errs[worst]:.3e}")


def solve_phase():
    import jax

    from repro.apps.fd2d import FDWave, reference_step

    t0 = time.time()
    fd = FDWave(model="pallas", width=4096, height=4096, radius=1)
    if fd.device.interpret:
        raise AssertionError("fd2d would run interpreted")
    u1, u2 = fd.o_u1.data, fd.o_u2.data
    fd.run(10)
    got = fd.solution
    wall_s = time.time() - t0
    step = jax.jit(lambda a, b: (reference_step(a, b, fd.weights, fd.dx,
                                                fd.dt), a))
    for _ in range(10):
        u1, u2 = step(u1, u2)
    err = float(np.abs(got - np.asarray(u1)).max())
    log(f"solve: fd2d 4096^2 f32, 10 steps in {wall_s:.1f}s wall (compile "
        f"included); max |kernel - reference| {err:.3e} (limit {FD_ATOL})")
    if not err <= FD_ATOL:
        raise AssertionError(f"fd2d off the reference by {err:.3e}")


def mesh_phase(seed: int):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.lang import kernel_names
    from repro.launch.mesh import make_local_mesh
    from repro.layers.common import use_kernel_backend
    from repro.models import LM
    from repro.parallel.context import use_rules
    from repro.parallel.steps import make_shardings
    from repro.serving import Engine

    cfg = get_config("internlm2_20b")
    mesh = make_local_mesh(data=1, model=4)
    rng = np.random.default_rng(seed)
    lens = rng.integers(64, 1025, size=4)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n)).tolist()
               for n in lens]
    new = 16

    # kernel path: the sharded Engine
    with use_kernel_backend("pallas"):
        model = LM(cfg)
        param_sh, _, rules, _ = make_shardings(model, mesh)
        t0 = time.time()
        params = jax.jit(model.init, out_shardings=param_sh)(
            jax.random.PRNGKey(seed))
        jax.block_until_ready(params)
        log(f"mesh: internlm2_20b {model.param_count(params) / 1e9:.3f}B "
            f"params initialised sharded over {dict(mesh.shape)} in "
            f"{time.time() - t0:.1f}s")
        engine = Engine(model, params, batch=4, max_len=2048, mesh=mesh)
        toks = jax.ShapeDtypeStruct((4, 1), jnp.int32)
        names = kernel_names(engine.decode_step.lower(
            engine.params, engine.cache, toks).compile().as_text())
        missing = {"flash_decode_paged", "lm_head_logits", "rmsnorm"} - names
        if missing:
            raise AssertionError(f"sharded decode step lacks kernels "
                                 f"{sorted(missing)}; found {sorted(names)}")
        rids = [engine.submit(p, new) for p in prompts]
        steps = {r: [] for r in rids}
        t0 = time.time()
        while not engine.idle:
            engine.step()
            if engine.last_decode is None:
                continue
            lg, slot_of = engine.last_decode
            for r, s in slot_of.items():
                steps[r].append(np.asarray(lg[s, :cfg.vocab_size],
                                           np.float32))
            engine.last_decode = None
        out = engine.drain()
        page = engine.page_size
        del engine
    log(f"mesh: {sum(len(v) for v in out.values())} tokens through "
        f"Engine(mesh=...) in {time.time() - t0:.1f}s wall (compile "
        f"included); decode step kernels {sorted(names)}")

    # reference: the same sharded params on the XLA path, one full-sequence
    # forward over each request's prompt + the tokens the engine chose (the
    # same history at every step), plus a control whose first page differs
    seqs, ctrls, at = [], [], []
    for r, prompt in zip(rids, prompts):
        gen = out[r]
        if len(gen) != new or len(steps[r]) != new - 1:
            raise AssertionError(f"request {r}: {len(gen)} tokens, "
                                 f"{len(steps[r])} decode steps")
        seq = prompt + gen[:-1]
        ctrl = list(seq)
        n = min(page, len(prompt) - 1)
        ctrl[:n] = rng.integers(0, cfg.vocab_size, size=n).tolist()
        seqs.append(seq)
        ctrls.append(ctrl)
        at.append(np.arange(len(prompt), len(seq)))
    width = -(-max(map(len, seqs)) // 128) * 128
    batch = np.zeros((2 * len(seqs), width), np.int32)   # right-padded:
    for i, s in enumerate(seqs + ctrls):                 # causal, harmless
        batch[i, :len(s)] = s
    at = np.asarray(at + at, np.int32)
    ref = LM(cfg, fused_head=False)

    def forward(p, t, at):
        with use_rules(rules):
            logits, _ = ref.forward(p, t)
        return jnp.take_along_axis(logits, at[:, :, None], axis=1)

    t0 = time.time()
    with use_kernel_backend("jnp"):
        want = np.asarray(jax.jit(forward)(params, jnp.asarray(batch),
                                           jnp.asarray(at))
                          [:, :, :cfg.vocab_size], np.float32)
    log(f"mesh: XLA reference forward over {batch.shape} tokens in "
        f"{time.time() - t0:.1f}s (compile included)")
    worst, ctrl_best = 0.0, np.inf
    for i, r in enumerate(rids):
        got = np.stack(steps[r])
        err = max(rel_l2(g, w) for g, w in zip(got, want[i]))
        ctrl_err = min(rel_l2(g, w) for g, w in
                       zip(got, want[len(rids) + i]))
        log(f"mesh: request {r} (prompt {len(prompts[i])}): {len(got)} "
            f"decode steps vs the reference, worst rel-L2 {err:.3e}; "
            f"first page replaced, best {ctrl_err:.3e}")
        worst, ctrl_best = max(worst, err), min(ctrl_best, ctrl_err)
    log(f"mesh: decode logits, kernel path vs XLA reference path, worst "
        f"step rel-L2 {worst:.3e} (limit {LOGITS_RTOL}); control best "
        f"{ctrl_best:.3e}")
    if not worst <= LOGITS_RTOL:
        raise AssertionError(f"sharded kernel path off by {worst:.3e}")
    if not ctrl_best > LOGITS_RTOL:
        raise AssertionError(
            f"replacing the first page moved the logits only "
            f"{ctrl_best:.3e}: the check cannot see a wrong page")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded serving path on a (1, 4) mesh only")
    args = ap.parse_args(argv)

    device = platform_gate(args.chips)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        mesh_phase(args.seed)
    else:
        serve_phase(args.seed)
        train_phase(args.seed)
        solve_phase()
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
