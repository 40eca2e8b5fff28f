"""Distributed step builders: sharded train / prefill / serve steps.

Builds the pjit-able step functions plus the NamedShardings for params,
optimizer state, batches and KV/SSM caches, wiring in the ambient
activation-sharding rules. Used by the launcher and by the multi-pod
dry-run (which lowers exactly these functions).
"""

from __future__ import annotations

import math
import re
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.parallel import rules as R
from repro.parallel.context import Rules, use_rules

__all__ = [
    "axis_names", "make_shardings", "cache_pspecs", "paged_cache_pspecs",
    "build_train_step", "build_prefill_step", "build_serve_step",
    "build_paged_serve_step", "collective_bytes",
]


def axis_names(mesh: Mesh):
    names = mesh.axis_names
    batch_axes = tuple(n for n in names if n in ("pod", "data"))
    return batch_axes, "model"


def _named(mesh, pspec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _join(a, b):
    """Combine two axis selections for one dim into a tuple spec entry."""
    if a is None:
        return b
    if b is None:
        return a
    at = a if isinstance(a, tuple) else (a,)
    bt = b if isinstance(b, tuple) else (b,)
    return at + bt


def make_shardings(model, mesh: Mesh, *, fsdp: bool = False,
                   ring: bool = False):
    """Returns (param_shardings, pspecs, rules, params_shape) for a model on
    a mesh. ``params_shape`` is the abstract init tree — step builders reuse
    it rather than re-tracing ``model.init`` a second time.

    ``fsdp=True`` additionally shards each param's largest replicated dim over
    the data axis (ZeRO-3 via GSPMD: XLA all-gathers weights per layer).
    ``ring=True`` declares sequence-parallel ring attention over the model
    axis: activations shard their sequence dim and attention runs the
    declared ``shard_map`` ring schedule when the length divides the axis."""
    batch_axes, model_axis = axis_names(mesh)
    params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pspecs = R.param_specs(params_shape, model.cfg, mesh, model_axis=model_axis)
    if fsdp and "data" in mesh.axis_names:
        pspecs = R.zero1_specs(pspecs, params_shape, mesh, data_axis="data")
    ring_axis = model_axis if ring and mesh.shape[model_axis] > 1 else None
    rules = Rules(batch_axes=batch_axes, model_axis=model_axis, mesh=mesh,
                  ring_axis=ring_axis)
    return _named(mesh, pspecs), pspecs, rules, params_shape


# ---------------------------------------------------------------------------
# cache partition specs (per stack kind; base ranks are kind-specific)
# ---------------------------------------------------------------------------

def cache_pspecs(model, mesh: Mesh, batch: int, max_len: int,
                 kind: str = "decode"):
    """kind="decode": layouts optimized for per-token reads (seq-sharded
    fallback for kv_heads < model axis — §Perf it2). kind="prefill": the
    natural layout of the freshly computed k/v (head/head-dim sharded) —
    bulk-writing a 32k cache into the seq-sharded layout costs a full
    reshard per layer; the one-time handoff reshard at prefill->decode is
    the cheaper place to pay it (measured: v2 prefill regression)."""
    cfg = model.cfg
    batch_axes, m = axis_names(mesh)
    bsize = math.prod(mesh.shape[a] for a in batch_axes)
    b_ax = batch_axes if batch % bsize == 0 else None
    # if batch can't shard (long_500k B=1), shard the sequence dim instead
    seq_ax = None if b_ax is not None else batch_axes

    def div(dim, axis):
        if axis is None:
            return None
        axes = axis if isinstance(axis, tuple) else (axis,)
        size = math.prod(mesh.shape[a] for a in axes)
        return axis if dim % size == 0 else None

    hk, hd = max(cfg.n_kv_heads, 1), cfg.resolved_head_dim
    win = min(max_len, cfg.window) if cfg.window else max_len
    msize = mesh.shape[m]

    def attn_spec():
        if cfg.attn_type == "mla":
            lora = cfg.kv_lora_rank
            return {
                "ckv": P(b_ax, div(max_len, seq_ax), div(lora, m)),
                "krope": P(b_ax, div(max_len, seq_ax), None),
                "pos": P(),
            }
        # kv heads < model axis (GQA/MQA/MHA with few kv heads): for DECODE,
        # shard the cache SEQUENCE dim over the model axis — replicated 32k
        # caches would blow HBM, and head_dim sharding forces GSPMD to
        # replicate the cache around the decode einsums (involuntary full
        # rematerialization; measured in §Perf it2). Softmax over the
        # seq-sharded scores uses cheap partial-max/sum reductions. For
        # PREFILL, keep the computed k/v's natural layout (head-dim sharded).
        hd_ax = None
        if hk % msize == 0:
            head_ax, kseq_ax = m, div(win, seq_ax)
        elif kind == "decode":
            head_ax = None
            kseq_ax = _join(div(win, seq_ax), m if win % msize == 0 else None)
        else:  # prefill
            head_ax = None
            kseq_ax = div(win, seq_ax)
            hd_ax = m if hd % msize == 0 else None
        d = {
            "k": P(b_ax, head_ax, kseq_ax, hd_ax),
            "v": P(b_ax, head_ax, kseq_ax, hd_ax),
            "pos": P(),
        }
        if cfg.window:
            d["slot_pos"] = P(kseq_ax)
        return d

    def mamba_spec():
        if cfg.ssm_type == "mamba1":
            di = cfg.resolved_d_inner
            return {
                "conv": P(b_ax, None, div(di, m)),
                "h": P(b_ax, div(di, m), None),
            }
        di, n, p = cfg.resolved_d_inner, cfg.ssm_state, cfg.ssm_head_dim
        h = di // p
        return {
            "conv": P(b_ax, None, div(di + 2 * n, m)),
            "h": P(b_ax, div(h, m), None, None),
        }

    def prefixed(tree, n_extra):
        return jax.tree.map(lambda s: P(*([None] * n_extra + list(s))), tree,
                            is_leaf=lambda x: isinstance(x, P))

    stacks = []
    for spec in model.program:
        if spec.kind == "zamba_group":
            stacks.append({
                "mamba": prefixed(mamba_spec(), 2),
                "attn": prefixed(attn_spec(), 1),
            })
        elif spec.kind in ("mamba1", "mamba2"):
            stacks.append(prefixed(mamba_spec(), 1))
        else:
            stacks.append(prefixed(attn_spec(), 1))
    return {"pos": P(), "stacks": stacks}


def batch_pspecs(batch_shapes, mesh):
    batch_axes, _ = axis_names(mesh)
    return jax.tree.map(lambda s: P(*([batch_axes] + [None] * (len(s.shape) - 1))),
                        batch_shapes)


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------

def build_train_step(model, optimizer, mesh: Mesh, *, zero1: bool = False,
                     fsdp: bool = False, accum_steps: int = 1,
                     batch_shapes=None):
    """Returns (jitted step, shardings dict). step(params, opt, batch) ->
    (params, opt, loss, metrics)."""
    param_sh, pspecs, act_rules, params_shape = make_shardings(
        model, mesh, fsdp=fsdp)
    if (zero1 or fsdp) and "data" in mesh.axis_names:
        moment_pspecs = R.zero1_specs(pspecs, params_shape, mesh,
                                      data_axis="data")
    else:
        moment_pspecs = pspecs
    opt_sh = {
        "m": _named(mesh, moment_pspecs),
        "v": _named(mesh, moment_pspecs),
        "step": NamedSharding(mesh, P()),
    }

    def loss_fn(params, batch):
        with use_rules(act_rules):
            return model.loss(params, batch)

    def step(params, opt_state, batch):
        if accum_steps == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            def micro(carry, mb):
                gacc, lacc = carry
                (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                return (jax.tree.map(jnp.add, gacc, g), lacc + l), None

            mbs = jax.tree.map(
                lambda x: x.reshape(accum_steps, x.shape[0] // accum_steps,
                                    *x.shape[1:]), batch)
            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(micro, (zero, 0.0), mbs)
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            loss = loss / accum_steps
            metrics = {"ce": loss, "moe_lb": 0.0, "moe_z": 0.0}
        params, opt_state, opt_metrics = optimizer.update(grads, opt_state, params)
        metrics = dict(metrics, **opt_metrics)
        return params, opt_state, loss, metrics

    if batch_shapes is None:
        batch_sh = None
        jit_step = jax.jit(step, donate_argnums=(0, 1))
    else:
        batch_sh = _named(mesh, batch_pspecs(batch_shapes, mesh))
        jit_step = jax.jit(
            step,
            in_shardings=(param_sh, opt_sh, batch_sh),
            out_shardings=(param_sh, opt_sh, NamedSharding(mesh, P()), None),
            donate_argnums=(0, 1),
        )
    return jit_step, {"params": param_sh, "opt": opt_sh, "batch": batch_sh,
                      "pspecs": pspecs, "rules": act_rules}


def build_prefill_step(model, mesh: Mesh, *, batch: int, max_len: int,
                       batch_shapes=None, fsdp: bool = False,
                       ring: bool = False):
    """``ring=True`` opts prefill attention into the declared sequence-
    parallel ring schedule (see ``make_shardings``)."""
    param_sh, pspecs, act_rules, _ = make_shardings(model, mesh, fsdp=fsdp,
                                                    ring=ring)
    c_pspecs = cache_pspecs(model, mesh, batch, max_len, kind="prefill")
    cache_sh = _named(mesh, c_pspecs)

    def prefill(params, batch_):
        with use_rules(act_rules):
            return model.prefill(params, batch_["tokens"],
                                 prefix_embeddings=batch_.get("prefix_embeddings"),
                                 max_len=max_len)

    if batch_shapes is None:
        jit_fn = jax.jit(prefill)
        batch_sh = None
    else:
        batch_sh = _named(mesh, batch_pspecs(batch_shapes, mesh))
        jit_fn = jax.jit(prefill, in_shardings=(param_sh, batch_sh),
                         out_shardings=(None, cache_sh))
    return jit_fn, {"params": param_sh, "batch": batch_sh, "cache": cache_sh,
                    "pspecs": pspecs, "rules": act_rules}


def build_serve_step(model, mesh: Mesh, *, batch: int, max_len: int,
                     greedy: bool = True):
    """One-token decode step over a sharded cache.

    ``greedy=True`` (the default — DEPRECATION: flipped from False, the
    served configuration is greedy + fused head; pass greedy=False
    explicitly for host-side sampling) routes through ``model.greedy_step``
    -> (next_token, logits, cache): with a fused LM head the argmax comes
    out of the logits kernel itself, so the host loop feeds tokens straight
    back without a device round-trip. ``greedy=False`` steps via
    ``model.decode_step`` -> (logits, cache), leaving sampling to the
    host."""
    param_sh, pspecs, act_rules, _ = make_shardings(model, mesh)
    c_pspecs = cache_pspecs(model, mesh, batch, max_len)
    cache_sh = _named(mesh, c_pspecs)
    batch_axes, _ = axis_names(mesh)
    bsize = math.prod(mesh.shape[a] for a in batch_axes)
    tok_sh = NamedSharding(mesh, P(batch_axes if batch % bsize == 0 else None,
                                   None))

    if greedy:
        def serve(params, cache, tokens):
            with use_rules(act_rules):
                nxt, logits, new_cache = model.greedy_step(
                    params, tokens, cache)
                return nxt, logits, new_cache
        out_sh = (None, None, cache_sh)
    else:
        def serve(params, cache, tokens):
            with use_rules(act_rules):
                return model.decode_step(params, tokens, cache)
        out_sh = (None, cache_sh)

    jit_fn = jax.jit(serve, in_shardings=(param_sh, cache_sh, tok_sh),
                     out_shardings=out_sh, donate_argnums=(1,))
    return jit_fn, {"params": param_sh, "cache": cache_sh, "tokens": tok_sh,
                    "cache_pspecs": c_pspecs, "pspecs": pspecs,
                    "rules": act_rules, "greedy": greedy}


def paged_cache_pspecs(model, mesh: Mesh, batch: int):
    """Partition specs for a paged decode cache. The page axis of the pools
    is a POOL dimension (any sequence's page can live anywhere), so it never
    shards over batch axes; kv heads shard over the model axis when they
    divide it, else the pool replicates (paged decode targets serving
    batches, where the pool is small next to the params). Tables, lengths
    and the position map are host-managed control state: replicated."""
    cfg = model.cfg
    _, m = axis_names(mesh)
    msize = mesh.shape[m]
    hk = max(cfg.n_kv_heads, 1)
    head_ax = m if hk % msize == 0 else None
    pool = {"kp": P(None, None, head_ax, None, None),
            "vp": P(None, None, head_ax, None, None)}
    return {"table": P(), "len": P(), "pos_pages": P(),
            "stacks": [dict(pool) for _ in model.program]}


def build_paged_serve_step(model, mesh: Mesh, *, batch: int,
                           greedy: bool = True):
    """One-token decode step over PAGED KV pools (the continuous-batching
    engine's inner loop). The cache (pools + block tables + lengths +
    pos_pages) is a single donated pytree; the host mutates only the control
    state (tables/lengths) between steps via the serving scheduler."""
    if not model.pageable:
        raise ValueError("build_paged_serve_step: model is not pageable "
                         "(see LM.pageable)")
    param_sh, pspecs, act_rules, _ = make_shardings(model, mesh)
    c_pspecs = paged_cache_pspecs(model, mesh, batch)
    cache_sh = _named(mesh, c_pspecs)
    tok_sh = NamedSharding(mesh, P(None, None))

    if greedy:
        def serve(params, cache, tokens):
            with use_rules(act_rules):
                return model.paged_greedy_step(params, tokens, cache)
        out_sh = (None, None, cache_sh)
    else:
        def serve(params, cache, tokens):
            with use_rules(act_rules):
                return model.paged_decode_step(params, tokens, cache)
        out_sh = (None, cache_sh)

    jit_fn = jax.jit(serve, in_shardings=(param_sh, cache_sh, tok_sh),
                     out_shardings=out_sh, donate_argnums=(1,))
    return jit_fn, {"params": param_sh, "cache": cache_sh, "tokens": tok_sh,
                    "cache_pspecs": c_pspecs, "pspecs": pspecs,
                    "rules": act_rules, "greedy": greedy}


# ---------------------------------------------------------------------------
# what a compiled program's collectives move
# ---------------------------------------------------------------------------

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_HEADER = re.compile(r"^(ENTRY )?%(\S+) .*\{$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%\S+ = (.*?) ([a-z][a-z0-9-]*)\(")
_ARRAY = re.compile(r"\b(pred|[a-z]+\d+[a-z0-9]*)\[([\d,]*)\]")
_CALLED = re.compile(
    r"\b(body|condition|calls|to_apply|called_computations|"
    r"branch_computations)=(\{[^}]*\}|%[^\s,)]+)")


def _arrays(shape_text: str) -> list[int]:
    """Bytes of each array in an HLO result type (one, or a tuple's)."""
    out = []
    for dtype, dims in _ARRAY.findall(shape_text):
        bits = 8 if dtype == "pred" else int(re.search(r"\d+", dtype).group())
        out.append(math.prod(int(d) for d in dims.split(",") if d)
                   * max(bits // 8, 1))
    return out


def _group_size(line: str, n_devices: int) -> int:
    m = re.search(r"replica_groups=\[\d+,(\d+)\]<=", line)
    if m:
        return int(m.group(1))
    m = re.search(r"replica_groups=\{\{([\d,]*)\}", line)
    if m:
        return len(m.group(1).split(","))
    return n_devices


def collective_bytes(compiled_text: str, n_devices: int) -> int:
    """Bytes that one execution of a compiled SPMD program's collectives
    bring into one chip from the others, from its optimised HLO text
    (``Compiled.as_text()``), for groups of ``n`` chips: an all-gather
    ``(n - 1) / n`` of its output, an all-reduce twice that of its operand
    (reduce-scatter, then all-gather), a reduce-scatter ``n - 1`` of its
    output pieces, an all-to-all ``(n - 1) / n`` of its operand and a
    collective permute its whole buffer. ``-start`` forms count, ``-done``
    forms do not.

    A collective runs once per execution of the computation that holds it:
    a loop body's collectives count the loop's trip count (read from the
    bound its condition compares with). The compiler may split one
    collective over pieces placed in a loop to overlap it with the loop's
    work; the pieces share a channel and count once, and only a collective
    that the program itself issued inside a loop (its ``op_name`` names a
    ``while/body``) counts the trip count."""
    comps, order, current = {}, [], None
    for line in compiled_text.splitlines():
        m = _HEADER.match(line)
        if m:
            current = m.group(2)
            comps[current] = []
            order.append((current, bool(m.group(1))))
        elif current is not None and line.startswith(" "):
            comps[current].append(line)

    def trips(cond: str) -> int:
        bounds = [int(c) for line in comps.get(cond, ())
                  for c in re.findall(r"s32\[\](?:\{[^}]*\})? constant\((\d+)\)",
                                    line)]
        return max(bounds, default=1)

    # executions of each computation per execution of the program; callers
    # are printed after the computations they call, the entry last
    runs = {name: 0 for name in comps}
    for name, entry in reversed(order):
        if entry:
            runs[name] = 1
        for line in comps[name]:
            loop = re.search(r"condition=%([^\s,)]+)", line)
            for kind, called in _CALLED.findall(line):
                if kind == "condition":
                    continue
                k = trips(loop.group(1)) if kind == "body" and loop else 1
                for callee in re.findall(r"%([^\s,}]+)", called):
                    if callee in runs:
                        runs[callee] += runs[name] * k

    seen, total = set(), 0.0
    for name, _ in order:
        for line in comps[name]:
            m = _INSTR.match(line)
            if not m or runs[name] == 0:
                continue
            shape, op = m.groups()
            kind = op[:-len("-start")] if op.endswith("-start") else op
            if kind not in _COLLECTIVES:
                continue
            chan = re.search(r"channel_id=(\d+)", line)
            key = chan.group(1) if chan else (name, line)
            if key in seen:
                continue
            seen.add(key)
            arrays = _arrays(shape)
            if op.endswith("-start") and len(arrays) > 1:
                arrays = ([arrays[-1]] if kind == "all-gather"
                          else [arrays[1]] if kind == "collective-permute"
                          else arrays[:len(arrays) // 2])
            n = _group_size(line, n_devices)
            size = sum(arrays)
            per = {"all-gather": size * (n - 1) / n,
                   "all-reduce": 2 * size * (n - 1) / n,
                   "reduce-scatter": size * (n - 1),
                   "all-to-all": size * (n - 1) / n,
                   "collective-permute": size}[kind]
            in_loop = "while/body" in line
            total += per * (runs[name] if in_loop else 1)
    return int(total)
