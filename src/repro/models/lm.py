"""Unified decoder LM covering all assigned architecture families.

A model is a *program* of homogeneous layer stacks (dense / moe / mamba1 /
mamba2 / zamba groups), each scanned with ``lax.scan`` over stacked layer
params so compile time and HLO size are ~O(1) in depth. Modality frontends
are stubs per the assignment: precomputed prefix embeddings are prepended to
the token embeddings (vision patches / audio conditioning).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ArchConfig
from repro.kernels.lm_head import lm_head_ce, lm_head_logits
from repro.layers import blocks
from repro.layers.common import dense_init, rmsnorm
from repro.layers.rope import sinusoidal_embedding
from repro.parallel.context import shard_activation, shard_kernel

# the fused head's kernel calls on a mesh: rows over the batch axes, the
# head matrix whole on every shard (see ``shard_kernel``)
_ROWS = ("batch", None)
_WHOLE = (None, None)

__all__ = ["LM", "StackSpec", "build_program", "pad_vocab"]


def pad_vocab(v: int, multiple: int = 256) -> int:
    """Megatron-style vocab padding so embeddings always shard."""
    return -(-v // multiple) * multiple


@dataclasses.dataclass(frozen=True)
class StackSpec:
    kind: str           # dense | moe | mamba1 | mamba2 | zamba_group
    n: int
    group: int = 0      # zamba_group: mamba layers per shared-attn application


def build_program(cfg: ArchConfig) -> list[StackSpec]:
    if cfg.shared_attn_every:                       # zamba2 hybrid
        g = cfg.shared_attn_every
        ngroups = cfg.n_layers // g
        tail = cfg.n_layers - ngroups * g
        prog = [StackSpec("zamba_group", ngroups, group=g)]
        if tail:
            prog.append(StackSpec("mamba2", tail))
        return prog
    if cfg.ssm_type == "mamba1":
        return [StackSpec("mamba1", cfg.n_layers)]
    if cfg.ssm_type == "mamba2":
        return [StackSpec("mamba2", cfg.n_layers)]
    if cfg.n_experts:
        prog = []
        if cfg.first_dense_layers:
            prog.append(StackSpec("dense", cfg.first_dense_layers))
        prog.append(StackSpec("moe", cfg.n_layers - cfg.first_dense_layers))
        return prog
    return [StackSpec("dense", cfg.n_layers)]


class LM:
    def __init__(self, cfg: ArchConfig, *, remat: str = "none",
                 moe_dispatch: str = "einsum", scan_layers: bool = True,
                 ce_chunks: int = 1, fused_head: bool = True,
                 head_backend: str = "auto"):
        assert remat in ("none", "full", "dots")
        self.cfg = cfg
        self.dtype = jnp.dtype(cfg.dtype)
        self.program = build_program(cfg)
        self.vpad = pad_vocab(cfg.vocab_size)
        self.remat = remat
        self.moe_dispatch = moe_dispatch
        # ce_chunks > 1: compute CE in sequence chunks with rematerialized
        # per-chunk logits — peak logits memory drops by the chunk count
        self.ce_chunks = ce_chunks
        # fused_head: route the LM head through the fused unified-language
        # kernels — loss uses lm_head_ce (one matmul + online-softmax pass;
        # nothing (B, S, Vpad)-shaped materializes, so ce_chunks is moot),
        # _logits/decode use lm_head_logits (logits + row max + greedy argmax
        # from the same pass). head_backend picks the kernel expansion
        # ("auto" = pallas, or $REPRO_BACKEND).
        # DEPRECATION: the default flipped False -> True — the fused head is
        # the served configuration. Pass fused_head=False explicitly to keep
        # the einsum + pad-mask reference head (tests do, as the baseline).
        self.fused_head = fused_head
        self.head_backend = head_backend
        # scan_layers=False unrolls the layer loops (python for). Used by the
        # dry-run cost extrapolation: HLO cost analysis counts a while-loop
        # body ONCE regardless of trip count, so per-layer costs are measured
        # on small unrolled variants and extrapolated linearly.
        self.scan_layers = scan_layers

    def _scan_or_loop(self, body, x, xs, n):
        if self.scan_layers:
            return jax.lax.scan(body, x, xs)
        ys = []
        for i in range(n):
            x, y = body(x, jax.tree.map(lambda a: a[i], xs))
            ys.append(y)
        ystack = jax.tree.map(lambda *v: jnp.stack(v), *ys)
        return x, ystack

    # ------------------------------------------------------------------ init
    def _layer_init(self, rng, kind):
        cfg, dtype = self.cfg, self.dtype
        if kind == "dense":
            return blocks.tblock_init(rng, cfg, dtype, moe=False)
        if kind == "moe":
            return blocks.tblock_init(rng, cfg, dtype, moe=True)
        if kind in ("mamba1", "mamba2"):
            return blocks.mamba_block_init(rng, cfg, dtype)
        raise ValueError(kind)

    def _stack_init(self, rng, spec: StackSpec):
        if spec.kind == "zamba_group":
            keys = jax.random.split(rng, spec.n * spec.group)
            keys = keys.reshape(spec.n, spec.group, *keys.shape[1:])
            inner = jax.vmap(lambda k: self._layer_init(k, "mamba2"))
            return jax.vmap(inner)(keys)
        keys = jax.random.split(rng, spec.n)
        return jax.vmap(lambda k: self._layer_init(k, spec.kind))(keys)

    def init(self, rng):
        cfg, dtype = self.cfg, self.dtype
        keys = jax.random.split(rng, len(self.program) + 3)
        params = {
            "embed": dense_init(keys[0], (self.vpad, cfg.d_model), dtype,
                                scale=cfg.d_model ** -0.5),
            "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        }
        if not cfg.tie_embeddings:
            params["head"] = dense_init(keys[1], (cfg.d_model, self.vpad), dtype)
        if cfg.shared_attn_every:
            params["shared_attn"] = blocks.tblock_init(keys[2], cfg, dtype, moe=False)
        params["stacks"] = [self._stack_init(k, spec)
                            for k, spec in zip(keys[3:], self.program)]
        return params

    def param_count(self, params) -> int:
        return sum(x.size for x in jax.tree.leaves(params))

    def active_param_count(self, params) -> int:
        """Parameters touched per token (MoE: only top-k experts count)."""
        cfg = self.cfg
        total = self.param_count(params)
        if not cfg.n_experts:
            return total
        # subtract inactive expert fraction
        stack = params["stacks"][-1]
        expert_leaves = [stack["moe"][k] for k in ("w_gate", "w_up", "w_down")]
        expert_params = sum(x.size for x in expert_leaves)
        inactive = expert_params * (1 - cfg.n_experts_per_tok / cfg.n_experts)
        return int(total - inactive)

    # --------------------------------------------------------------- embed
    def _embed(self, params, tokens, prefix_embeddings=None, pos0=0):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.embed_scale:
            x = x * math.sqrt(cfg.d_model)
        if prefix_embeddings is not None:
            x = jnp.concatenate([prefix_embeddings.astype(x.dtype), x], axis=1)
        if cfg.pos_embed == "sinusoidal":
            pos = sinusoidal_embedding(pos0 + jnp.arange(x.shape[1]), cfg.d_model)
            x = x + pos[None].astype(x.dtype)
        return shard_activation(x, "act_btd")

    def _head(self, params):
        """The (d_model, Vpad) head matrix (tied embeddings transposed)."""
        cfg = self.cfg
        head = params["embed"].T if cfg.tie_embeddings else params["head"]
        return head

    def _logits(self, params, x):
        cfg = self.cfg
        head = self._head(params)
        if self.fused_head:
            b, s, d = x.shape
            logits = shard_kernel(
                lambda x, w: lm_head_logits(x, w, vocab=cfg.vocab_size,
                                            backend=self.head_backend),
                (x.reshape(b * s, d), head.astype(x.dtype)),
                (_ROWS, _WHOLE), _ROWS)
            return shard_activation(logits.reshape(b, s, self.vpad),
                                    "act_btv")
        logits = jnp.einsum("...d,dv->...v", x, head,
                            preferred_element_type=jnp.float32)
        # mask padded vocab entries
        pad_mask = jnp.where(jnp.arange(self.vpad) < cfg.vocab_size, 0.0, -1e30)
        logits = logits + pad_mask
        return shard_activation(logits, "act_btv")

    # -------------------------------------------------------------- forward
    def _wrap_remat(self, body):
        if self.remat == "none":
            return body
        policy = None
        if self.remat == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(body, policy=policy)

    def _stack_forward(self, params, stack_params, x, spec, prefix_len):
        cfg = self.cfg

        if spec.kind == "zamba_group":
            shared = params["shared_attn"]

            def body(x, gp):
                def inner(x, lp):
                    return blocks.mamba_block_forward(lp, x, cfg)
                x, auxs = self._scan_or_loop(inner, x, gp, spec.group)
                x, aux2 = blocks.tblock_forward(shared, x, cfg, moe=False)
                return x, auxs.sum(0) + aux2
        else:
            moe = spec.kind == "moe"

            def body(x, lp):
                if spec.kind in ("mamba1", "mamba2"):
                    return blocks.mamba_block_forward(lp, x, cfg)
                return blocks.tblock_forward(lp, x, cfg, moe=moe,
                                             prefix_len=prefix_len,
                                             dispatch=self.moe_dispatch)

        x, auxs = self._scan_or_loop(self._wrap_remat(body), x, stack_params,
                                     spec.n)
        return x, auxs.sum(0)

    def _hidden_states(self, params, tokens, prefix_embeddings=None):
        """Embed -> layer stacks -> final norm: the shared forward trunk.
        Returns (hidden (B, S*, d), aux[2])."""
        cfg = self.cfg
        x = self._embed(params, tokens, prefix_embeddings)
        prefix_len = (prefix_embeddings.shape[1]
                      if (prefix_embeddings is not None and cfg.prefix_lm) else 0)
        aux = blocks.ZERO_AUX
        for spec, sp in zip(self.program, params["stacks"]):
            x, a = self._stack_forward(params, sp, x, spec, prefix_len)
            aux = aux + a
        return rmsnorm(x, params["final_norm"], eps=cfg.norm_eps), aux

    def forward(self, params, tokens, prefix_embeddings=None):
        """Full-sequence forward. Returns (logits (B,S*,Vpad) f32, aux[2])."""
        x, aux = self._hidden_states(params, tokens, prefix_embeddings)
        return self._logits(params, x), aux

    def _ce_from_hidden(self, params, x, labels):
        """CE over sequence chunks with rematerialized logits (peak-memory
        lever: nothing (B, S, Vpad)-f32-shaped is live across the step)."""
        b, s, d = x.shape
        k = self.ce_chunks
        while s % k:
            k -= 1

        def chunk_ce(args):
            xc, lc = args
            logits = self._logits(params, xc)
            logz = jax.nn.logsumexp(logits, axis=-1)
            onehot = jax.nn.one_hot(lc, self.vpad, dtype=logits.dtype)
            gold = jnp.sum(logits * onehot, axis=-1)
            return jnp.sum(logz - gold)

        body = jax.checkpoint(chunk_ce)
        xs = x.reshape(b, k, s // k, d).swapaxes(0, 1)
        ls = labels.reshape(b, k, s // k).swapaxes(0, 1)
        total, _ = jax.lax.scan(lambda acc, a: (acc + body(a), None), 0.0,
                                (xs, ls))
        return total / (b * s)

    def _fused_ce(self, params, x, labels):
        """Fused chunked CE through ``lm_head_ce``: one matmul + online-
        softmax pass streams logsumexp and the gold logit out of the kernel
        block by block — nothing (B, S, Vpad)-shaped is ever live, forward
        OR backward (the custom VJP recomputes softmax - onehot blockwise
        from the saved row stats)."""
        b, s, d = x.shape
        head = self._head(params).astype(x.dtype)
        nll = shard_kernel(
            lambda x, w, lab: lm_head_ce(x, w, lab, vocab=self.cfg.vocab_size,
                                         backend=self.head_backend),
            (x.reshape(b * s, d), head,
             labels.reshape(b * s, 1).astype(jnp.int32)),
            (_ROWS, _WHOLE, _ROWS), ("batch",))
        return nll.mean()

    def _check_labels(self, labels):
        """Labels >= vocab_size index PADDED-vocab columns: ``one_hot`` over
        vpad plus the -1e30 pad mask keeps the loss finite, so training
        would silently optimize against pad logits. Raise host-side whenever
        the values are concrete (eager loss calls; jitted steps see tracers
        and rely on the data pipeline / eager first step)."""
        if isinstance(labels, jax.core.Tracer) or labels.size == 0:
            return
        host = np.asarray(labels)            # one device pull, checked on host
        lo, hi = int(host.min()), int(host.max())
        if lo < 0 or hi >= self.cfg.vocab_size:
            raise ValueError(
                f"labels out of range [{lo}, {hi}] for vocab_size="
                f"{self.cfg.vocab_size} (vpad={self.vpad}): CE would "
                "silently train on padded-vocab logits; clean the batch")

    # ----------------------------------------------------------------- loss
    def loss(self, params, batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        prefix = batch.get("prefix_embeddings")
        p = prefix.shape[1] if prefix is not None else 0
        labels = tokens[:, 1:]
        self._check_labels(labels)
        if self.fused_head or self.ce_chunks > 1:
            # forward to the final hidden states; CE never sees full logits
            x, aux = self._hidden_states(params, tokens, prefix)
            pred_x = x[:, p:-1] if x.shape[1] > p + 1 else x[:, p:]
            if self.fused_head:
                ce = self._fused_ce(params, pred_x, labels)
            else:
                ce = self._ce_from_hidden(params, pred_x, labels)
        else:
            logits, aux = self.forward(params, tokens, prefix_embeddings=prefix)
            pred = logits[:, p:-1] if logits.shape[1] > p + 1 else logits[:, p:]
            logz = jax.nn.logsumexp(pred, axis=-1)
            onehot = jax.nn.one_hot(labels, self.vpad, dtype=pred.dtype)
            gold = jnp.sum(pred * onehot, axis=-1)
            ce = jnp.mean(logz - gold)
        lb, z = aux[0], aux[1]
        nl = max(sum(s.n * max(s.group, 1) for s in self.program), 1)
        total = ce + (0.02 * lb + 1e-3 * z) / nl
        metrics = {"ce": ce, "moe_lb": lb, "moe_z": z}
        return total, metrics

    # ---------------------------------------------------------------- cache
    def _stack_cache_init(self, spec, batch, max_len, dtype):
        cfg = self.cfg

        def stacked(n, single):
            return jax.tree.map(lambda a: jnp.zeros((n,) + a.shape, a.dtype), single)

        if spec.kind == "zamba_group":
            mamba_single = blocks.mamba_block_cache_init(cfg, batch, dtype)
            attn_single = blocks.tblock_cache_init(cfg, batch, max_len, dtype)
            return {
                "mamba": stacked(spec.n, stacked(spec.group, mamba_single)),
                "attn": stacked(spec.n, attn_single),
            }
        if spec.kind in ("mamba1", "mamba2"):
            return stacked(spec.n, blocks.mamba_block_cache_init(cfg, batch, dtype))
        return stacked(spec.n, blocks.tblock_cache_init(cfg, batch, max_len, dtype))

    def init_cache(self, batch, max_len, dtype=None):
        dtype = dtype or self.dtype
        return {"pos": jnp.zeros((), jnp.int32),
                "stacks": [self._stack_cache_init(s, batch, max_len, dtype)
                           for s in self.program]}

    @property
    def has_positional_cache(self) -> bool:
        """True when decode positions are bounded by the cache's max_len:
        attention stacks WITHOUT a rolling window (rolling caches rotate and
        never overflow; SSM stacks carry O(1) state)."""
        return (not self.cfg.window and
                any(s.kind in ("dense", "moe", "zamba_group")
                    for s in self.program))

    def cache_capacity(self, cache) -> int | None:
        """Token positions the attention caches can hold, or None when
        unbounded (rolling-window or attention-free programs)."""
        if not self.has_positional_cache:
            return None
        caps = []
        for spec, sc in zip(self.program, cache["stacks"]):
            if spec.kind in ("dense", "moe"):
                c = sc
            elif spec.kind == "zamba_group":
                c = sc["attn"]
            else:
                continue
            # stacked leaves: ckv (n, B, max_len, lora) / k (n, B, Hk, m, hd)
            caps.append(c["ckv"].shape[2] if "ckv" in c else c["k"].shape[3])
        return min(caps) if caps else None

    # -------------------------------------------------------------- prefill
    def prefill(self, params, tokens, prefix_embeddings=None, max_len=None):
        """Returns (last-token logits (B, Vpad), cache)."""
        cfg = self.cfg
        x = self._embed(params, tokens, prefix_embeddings)
        max_len = max_len or x.shape[1]
        if self.has_positional_cache and x.shape[1] > max_len:
            raise ValueError(
                f"kv cache overflow: prefilling {x.shape[1]} tokens into a "
                f"cache of max_len={max_len}; decode would silently attend "
                "truncated history — raise max_len")
        prefix_len = (prefix_embeddings.shape[1]
                      if (prefix_embeddings is not None and cfg.prefix_lm) else 0)
        caches = []
        for spec, sp in zip(self.program, params["stacks"]):
            if spec.kind == "zamba_group":
                shared = params["shared_attn"]

                def body(x, gp):
                    def inner(x, lp):
                        y, aux, c = blocks.mamba_block_prefill(lp, x, cfg,
                                                               cache_dtype=self.dtype)
                        return y, c
                    x, cm = self._scan_or_loop(inner, x, gp, spec.group)
                    x, _, ca = blocks.tblock_prefill(shared, x, cfg, moe=False,
                                                     max_len=max_len,
                                                     cache_dtype=self.dtype)
                    return x, {"mamba": cm, "attn": ca}
            elif spec.kind in ("mamba1", "mamba2"):
                def body(x, lp):
                    y, _, c = blocks.mamba_block_prefill(lp, x, cfg,
                                                         cache_dtype=self.dtype)
                    return y, c
            else:
                moe = spec.kind == "moe"

                def body(x, lp, moe=moe):
                    y, _, c = blocks.tblock_prefill(lp, x, cfg, moe=moe,
                                                    max_len=max_len,
                                                    prefix_len=prefix_len,
                                                    dispatch=self.moe_dispatch,
                                                    cache_dtype=self.dtype)
                    return y, c

            x, cache = self._scan_or_loop(body, x, sp, spec.n)
            caches.append(cache)
        x = rmsnorm(x, params["final_norm"], eps=cfg.norm_eps)
        logits = self._logits(params, x[:, -1:])[:, 0]
        return logits, {"pos": jnp.asarray(x.shape[1], jnp.int32),
                        "stacks": caches}

    # ------------------------------------------------------------- decoding
    def _decode_hidden(self, params, tokens, cache):
        """One decode step up to the final norm: tokens (B, 1) -> (hidden
        (B, 1, d), new_cache). The head (logits / fused greedy) goes on top."""
        cfg = self.cfg
        pos = cache.get("pos", 0)
        # cache overflow is an ERROR, not a silent clobber of the last slot:
        # checked here when pos is concrete (eager decode loops); jitted
        # loops are guarded host-side by launch.serve.generate
        cap = self.cache_capacity(cache) if "stacks" in cache else None
        if (cap is not None and not isinstance(pos, jax.core.Tracer)
                and int(pos) >= cap):
            raise ValueError(
                f"kv cache overflow: decode at position {int(pos)} but the "
                f"cache holds {cap} tokens; grow max_len at prefill/"
                "init_cache (the layer-level write would silently overwrite "
                "the last slot and attend corrupted history)")
        x = self._embed(params, tokens, pos0=pos)
        new_caches = []
        for spec, sp, sc in zip(self.program, params["stacks"], cache["stacks"]):
            if spec.kind == "zamba_group":
                shared = params["shared_attn"]

                def body(x, args):
                    gp, gc = args

                    def inner(x, a):
                        lp, lc = a
                        y, nc = blocks.mamba_block_decode(lp, x, lc, cfg)
                        return y, nc
                    x, ncm = self._scan_or_loop(inner, x, (gp, gc["mamba"]),
                                                spec.group)
                    x, nca = blocks.tblock_decode(shared, x, gc["attn"], cfg)
                    return x, {"mamba": ncm, "attn": nca}
            elif spec.kind in ("mamba1", "mamba2"):
                def body(x, args):
                    lp, lc = args
                    y, nc = blocks.mamba_block_decode(lp, x, lc, cfg)
                    return y, nc
            else:
                moe = spec.kind == "moe"

                def body(x, args, moe=moe):
                    lp, lc = args
                    y, nc = blocks.tblock_decode(lp, x, lc, cfg, moe=moe,
                                                 dispatch=self.moe_dispatch)
                    return y, nc

            x, nc = self._scan_or_loop(body, x, (sp, sc), spec.n)
            new_caches.append(nc)
        x = rmsnorm(x, params["final_norm"], eps=cfg.norm_eps)
        return x, {"pos": pos + 1, "stacks": new_caches}

    def decode_step(self, params, tokens, cache):
        """One token for every sequence. tokens: (B, 1). Returns
        (logits (B, Vpad), new_cache)."""
        x, new_cache = self._decode_hidden(params, tokens, cache)
        logits = self._logits(params, x)[:, 0]
        return logits, new_cache

    def greedy_step(self, params, tokens, cache):
        """One greedy decode step: tokens (B, 1) -> (next token (B,),
        logits (B, Vpad), new_cache). With ``fused_head`` the argmax comes
        straight out of the fused LM-head kernel (its row-max/argmax outputs
        share the logits pass) instead of a second scan over the vocab;
        otherwise it falls back to ``greedy_token`` on the logits."""
        x, new_cache = self._decode_hidden(params, tokens, cache)
        if not self.fused_head:
            logits = self._logits(params, x)[:, 0]
            return self.greedy_token(logits), logits, new_cache
        logits, arg = self._greedy_head(params, x)
        return arg, logits, new_cache

    def _greedy_head(self, params, x):
        """(logits (B, Vpad), argmax (B,)) of decode hidden states
        x (B, 1, d) from one fused LM-head kernel pass."""
        b, s, d = x.shape                    # s == 1

        def head(x, w):
            # .raw returns the kernel outputs unsliced: drop the pre-hook's
            # row padding per shard, before the shards concatenate
            logits, _m, arg = lm_head_logits.raw(
                x, w, vocab=self.cfg.vocab_size, backend=self.head_backend)
            return logits[:x.shape[0]], arg[:x.shape[0]]

        logits, arg = shard_kernel(
            head, (x.reshape(b, d), self._head(params).astype(x.dtype)),
            (_ROWS, _WHOLE), (_ROWS, _ROWS))
        logits = shard_activation(logits.reshape(b, 1, self.vpad),
                                  "act_btv")[:, 0]
        return logits, arg[:, 0]

    def greedy_token(self, logits):
        return jnp.argmax(logits[..., :self.cfg.vocab_size], axis=-1)

    # -------------------------------------------------------- paged decoding
    @property
    def pageable(self) -> bool:
        """True when the program can decode against a paged KV pool: pure
        attention stacks (dense/moe) with GQA, rope positions and no rolling
        window. SSM state is O(1) (nothing to page), MLA's latent cache and
        rotated windowed caches use different layouts."""
        cfg = self.cfg
        return (all(s.kind in ("dense", "moe") for s in self.program)
                and cfg.attn_type != "mla" and not cfg.window
                and cfg.pos_embed == "rope")

    def init_paged_cache(self, batch, num_pages, page_size, nseq_pages,
                         dtype=None):
        """A paged decode cache: per-layer KV pools of ``num_pages`` fixed
        ``page_size``-token pages shared by all ``batch`` slots, plus the
        per-slot block tables (``nseq_pages`` logical pages each), lengths,
        and the pool-wide slot -> absolute-position map. Page 0 is reserved
        as the NULL page (idle slots point at it; its positions stay -1)."""
        if not self.pageable:
            raise ValueError(
                "paged decode needs an attention-only GQA program with rope "
                "positions and no rolling window "
                f"(program={[s.kind for s in self.program]}, "
                f"attn_type={self.cfg.attn_type}, window={self.cfg.window}, "
                f"pos_embed={self.cfg.pos_embed})")
        dtype = dtype or self.dtype

        def stacked(n, single):
            return jax.tree.map(lambda a: jnp.zeros((n,) + a.shape, a.dtype),
                                single)

        stacks = [stacked(s.n, blocks.tblock_paged_cache_init(
                      self.cfg, num_pages, page_size, dtype))
                  for s in self.program]
        return {"table": jnp.zeros((batch, nseq_pages), jnp.int32),
                "len": jnp.zeros((batch,), jnp.int32),
                "pos_pages": jnp.full((num_pages, page_size), -1, jnp.int32),
                "stacks": stacks}

    def _paged_decode_hidden(self, params, tokens, cache):
        """One paged decode step up to the final norm. Every slot decodes
        every step — idle slots carry len 0 and a zero block table, writing
        into and reading from the null page (their output is ignored)."""
        cfg = self.cfg
        table, lens = cache["table"], cache["len"]
        pos_pages = cache["pos_pages"]
        b, nsp = table.shape
        pg = pos_pages.shape[1]
        # pool coordinates of this step's KV write, shared by every layer
        page_ids = table[jnp.arange(b), jnp.clip(lens // pg, 0, nsp - 1)]
        offs = lens % pg
        # stamp the new positions; the null page is pinned to -1 so idle
        # slots' writes never masquerade as valid history for live tables
        pos_pages = pos_pages.at[page_ids, offs].set(lens).at[0].set(-1)
        x = self._embed(params, tokens)
        new_stacks = []
        for spec, sp, sc in zip(self.program, params["stacks"],
                                cache["stacks"]):
            moe = spec.kind == "moe"

            def body(x, args, moe=moe):
                lp, lc = args
                y, nc = blocks.tblock_paged_decode(
                    lp, x, lc, cfg, moe=moe, dispatch=self.moe_dispatch,
                    table=table, lens=lens, pos_pages=pos_pages,
                    page_ids=page_ids, offs=offs)
                return y, nc

            x, nc = self._scan_or_loop(body, x, (sp, sc), spec.n)
            new_stacks.append(nc)
        x = rmsnorm(x, params["final_norm"], eps=cfg.norm_eps)
        return x, dict(cache, len=lens + 1, pos_pages=pos_pages,
                       stacks=new_stacks)

    def paged_decode_step(self, params, tokens, cache):
        """One paged decode token for every slot. tokens: (B, 1). Returns
        (logits (B, Vpad), new_cache)."""
        x, new_cache = self._paged_decode_hidden(params, tokens, cache)
        logits = self._logits(params, x)[:, 0]
        return logits, new_cache

    def paged_greedy_step(self, params, tokens, cache):
        """Paged twin of ``greedy_step``: (next (B,), logits, new_cache)."""
        x, new_cache = self._paged_decode_hidden(params, tokens, cache)
        if not self.fused_head:
            logits = self._logits(params, x)[:, 0]
            return self.greedy_token(logits), logits, new_cache
        logits, arg = self._greedy_head(params, x)
        return arg, logits, new_cache
