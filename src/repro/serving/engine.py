"""Continuous-batching decode engine over paged KV caches.

One jitted one-token decode step (``LM.paged_greedy_step`` /
``paged_decode_step``) runs over ``batch`` SLOTS every step, whatever mix of
sequences currently occupies them; the :class:`~repro.serving.scheduler.
Scheduler` retires finished sequences, refills slots from the FIFO queue
mid-flight, and preempts-by-eviction when the page pool runs dry. Admission
prefills the new sequence per-slot (B=1 ``LM.prefill``) and scatters its
contiguous KV into the sequence's pages host-side, so the hot loop is
always the SAME compiled step — no recompilation across traffic mixes.

Token semantics match ``launch.serve.generate`` exactly: the first emitted
token comes from the prefill logits, every decode step emits the next, the
EOS token itself is emitted before the sequence retires, and a sequence
emits at most ``max_new`` tokens. Attention reads KV exclusively through
the block-table tile (``flash_decode_paged``), which is bit-identical to
contiguous ``flash_decode`` when the page size equals its block size — so
a greedy Engine run reproduces the static per-sequence baseline token for
token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.device import fit_block
from repro.parallel.context import use_rules

from .scheduler import Scheduler

__all__ = ["Engine"]


class Engine:
    def __init__(self, model, params, *, batch: int, max_len: int,
                 num_pages: int | None = None, page_size: int | None = None,
                 eos_id: int | None = None, greedy: bool = True,
                 temperature: float = 1.0, rng=None, mesh=None,
                 cache_dtype=None):
        if not model.pageable:
            raise ValueError("Engine needs a pageable model (see LM.pageable)")
        if temperature <= 0:
            raise ValueError(f"temperature must be > 0, got {temperature}")
        self.model = model
        self.batch = batch
        self.max_len = max_len
        self.eos_id = eos_id
        self.greedy = greedy
        self.temperature = float(temperature)
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        if page_size is None:
            # the page size IS flash_decode's tuned block size: paged blocks
            # then stream identically to contiguous ones (and bit-identically
            # -- the parity the tests pin). Adopt persisted winners first so
            # a pre-tuned fleet serves at its tuned block.
            from repro.kernels.flash_attention import flash_decode
            from repro.launch import tuning
            tuning.adopt(model.cfg, dict(batch=batch, prompt_len=max_len,
                                         max_len=max_len), kind="serve")
            page_size = fit_block(int(flash_decode.defaults["block_kv"]),
                                  max_len)
        self.page_size = int(page_size)
        nsp = -(-max_len // self.page_size)
        if num_pages is None:
            # default pool: every slot can grow to max_len, so preemption
            # never fires unless the caller shrinks the pool deliberately
            num_pages = batch * nsp + 1
        if num_pages - 1 < nsp:
            raise ValueError(
                f"num_pages={num_pages} cannot hold one max_len={max_len} "
                f"sequence ({nsp} pages of {self.page_size})")
        self.sched = Scheduler(batch=batch, page_size=self.page_size,
                               num_pages=num_pages, max_len=max_len)
        self.cache = model.init_paged_cache(batch, num_pages, self.page_size,
                                            nsp, dtype=cache_dtype)
        self._requests = {}
        self.last_decode = None
        self._pending = np.zeros((batch,), np.int32)
        self._slot_pages = [[] for _ in range(batch)]
        rules = None
        if mesh is not None:
            from repro.parallel.steps import build_paged_serve_step
            self._step_fn, specs = build_paged_serve_step(
                model, mesh, batch=batch, greedy=greedy)
            self.params = jax.device_put(params, specs["params"])
            self.cache = jax.device_put(self.cache, specs["cache"])
            rules = specs["rules"]
        else:
            self.params = params
            fn = model.paged_greedy_step if greedy else model.paged_decode_step
            self._step_fn = jax.jit(lambda p, c, t: fn(p, t, c),
                                    donate_argnums=(1,))

        def _prefill(p, t):
            # on a mesh, under the step's rules: the kernels then run per
            # shard (``shard_kernel``) like the decode step's
            with use_rules(rules):
                return model.prefill(p, t)

        self._prefill_fn = jax.jit(_prefill)

        # admission scatter, fused: ALL stacks' pages + pos rows land in one
        # jitted call (the eager .at[].set chain was ~10 dispatches per
        # admission and dominated engine wall time on small models). Keyed
        # on the prefill length, like the prefill itself. ``pages`` is the
        # slot's table row, padded with the null page 0 — padded entries
        # write zero KV and all-(-1) pos rows to page 0, which the decode
        # step re-pins to -1 anyway.
        pg = self.page_size

        def _scatter_impl(stacks, pos_pages, table, lens, pstacks, pages,
                          slot):
            nsp_ = pages.shape[0]
            out = []
            for sc, pc in zip(stacks, pstacks):
                kc, vc = pc["k"], pc["v"]          # (n, 1, hk, plen, hd)
                n, _, hk, plen, hd = kc.shape
                L = nsp_ * pg

                def paged(c, pool):                # -> (n, nsp, hk, pg, hd)
                    full = jnp.zeros((n, hk, L, hd), pool.dtype)
                    full = full.at[:, :, :plen].set(c[:, 0].astype(pool.dtype))
                    return full.reshape(n, hk, nsp_, pg, hd).transpose(
                        0, 2, 1, 3, 4)

                out.append({"kp": sc["kp"].at[:, pages].set(
                                paged(kc, sc["kp"])),
                            "vp": sc["vp"].at[:, pages].set(
                                paged(vc, sc["vp"]))})
            ar = jnp.arange(pg, dtype=jnp.int32)
            pos = jnp.arange(nsp_, dtype=jnp.int32)[:, None] * pg + ar[None]
            rows = jnp.where(pos < plen, pos, -1)
            return (out, pos_pages.at[pages].set(rows),
                    table.at[slot].set(pages), lens.at[slot].set(plen))

        self._scatter_fn = jax.jit(_scatter_impl,
                                   donate_argnums=(0, 1, 2, 3))

        # retirement + growth are tiny table/pos edits — still worth one
        # jitted call each instead of an eager dispatch chain
        nsp_t = self.sched.nseq_pages

        def _clear_impl(table, lens, slot):
            return (table.at[slot].set(jnp.zeros((nsp_t,), jnp.int32)),
                    lens.at[slot].set(0))

        self._clear_fn = jax.jit(_clear_impl, donate_argnums=(0, 1))

        def _grow_impl(pos_pages, table, pages, new, slot):
            cur = pos_pages[pages]                 # (nsp, pg); dup page-0
            rows = jnp.where(new[:, None], -1, cur)  # reads write back as-is
            return pos_pages.at[pages].set(rows), table.at[slot].set(pages)

        self._grow_fn = jax.jit(_grow_impl, donate_argnums=(0, 1))
        self._greedy_fn = jax.jit(model.greedy_token)

    # -------------------------------------------------------------- requests
    def submit(self, prompt, max_new: int) -> int:
        """Queue a prompt for generation. Returns the request id."""
        rid = self.sched.submit(prompt, max_new)
        self._requests[rid] = self.sched.queue[-1]
        return rid

    def result(self, rid: int) -> list[int]:
        return list(self._requests[rid].tokens)

    @property
    def idle(self) -> bool:
        return self.sched.idle

    @property
    def decode_step(self):
        """The jitted one-token decode step every engine step runs:
        ``(params, cache, tokens (batch, 1)) -> (next, logits, cache)``
        (greedy) or ``(logits, cache)``."""
        return self._step_fn

    # ------------------------------------------------------- device mirrors
    def _table_row(self, pages: list[int]) -> np.ndarray:
        row = np.zeros((self.sched.nseq_pages,), np.int32)
        row[:len(pages)] = pages               # padded entries hit null page 0
        return row

    def _clear_slot(self, slot: int):
        self.cache["table"], self.cache["len"] = self._clear_fn(
            self.cache["table"], self.cache["len"], slot)
        self._slot_pages[slot] = []
        self._pending[slot] = 0

    def _scatter_prefill(self, pcache, pages: list[int], slot: int):
        """Copy a B=1 contiguous prefill cache into the sequence's pages
        (logical page j -> pool page pages[j]), stamp their pos rows and the
        slot's table/len — one jitted call (see ``_scatter_impl``)."""
        c = self.cache
        (c["stacks"], c["pos_pages"], c["table"], c["len"]) = \
            self._scatter_fn(c["stacks"], c["pos_pages"], c["table"],
                             c["len"], pcache["stacks"],
                             jnp.asarray(self._table_row(pages)), slot)
        self._slot_pages[slot] = list(pages)

    def _sync_grown(self, slot: int):
        """Push newly granted pages into the device table; their pos rows
        reset to -1 (the decode step stamps positions as it writes)."""
        req = self.sched.slots[slot]
        pages = self.sched.pages.owned(req.rid)
        if pages == self._slot_pages[slot]:
            return
        known = set(self._slot_pages[slot])
        row = self._table_row(pages)
        new = np.array([p not in known and p != 0 for p in row], bool)
        self.cache["pos_pages"], self.cache["table"] = self._grow_fn(
            self.cache["pos_pages"], self.cache["table"],
            jnp.asarray(row), jnp.asarray(new), slot)
        self._slot_pages[slot] = list(pages)

    # ----------------------------------------------------------------- step
    def _sample(self, logits):
        self._rng, sub = jax.random.split(self._rng)
        scaled = (logits[..., :self.model.cfg.vocab_size]
                  / self.temperature)
        return np.asarray(jax.random.categorical(sub, scaled))

    def _emit(self, slot: int, tok: int, emitted: dict):
        req = self.sched.slots[slot]
        req.tokens.append(tok)
        emitted.setdefault(req.rid, []).append(tok)
        if ((self.eos_id is not None and tok == self.eos_id)
                or len(req.tokens) >= req.max_new):
            self.sched.retire(slot)
            self._clear_slot(slot)

    def _admit(self, slot: int, req, emitted: dict):
        resume = req.resume_prompt             # prompt + generated-so-far
        toks = jnp.asarray(np.asarray(resume, np.int32)[None])
        logits, pcache = self._prefill_fn(self.params, toks)
        pages = self.sched.pages.owned(req.rid)
        self._scatter_prefill(pcache, pages, slot)
        if self.greedy:
            tok = int(np.asarray(self._greedy_fn(logits[0])))
        else:
            tok = int(self._sample(np.asarray(logits))[0])
        self._pending[slot] = tok
        self._emit(slot, tok, emitted)

    def step(self) -> dict:
        """One engine step: retirement happened at the previous emission;
        admit queued requests into free slots, grow (preempting on famine),
        run ONE batched decode step, emit. Returns ``{rid: [tokens]}``
        emitted this step (admissions emit their prefill token here too)."""
        emitted: dict = {}
        for slot, req in self.sched.admit():
            self._admit(slot, req, emitted)
        for slot in list(self.sched.running):
            if self.sched.slots[slot] is None:
                continue                        # evicted by a younger grow
            while not self.sched.grow(slot):
                freed = self.sched.preempt_youngest(exclude=slot)
                if freed is None:
                    raise RuntimeError(
                        "page pool cannot hold a single sequence")
                self._clear_slot(freed)
            self._sync_grown(slot)
        running = self.sched.running
        if not running:
            if self.sched.queue:
                raise RuntimeError(
                    "no slot admitted but requests remain queued — page "
                    "pool too small for the front request")
            return emitted
        toks = jnp.asarray(self._pending.reshape(-1, 1))
        if self.greedy:
            nxt, logits, self.cache = self._step_fn(self.params, self.cache,
                                                    toks)
            nxt = np.asarray(nxt)
        else:
            logits, self.cache = self._step_fn(self.params, self.cache, toks)
            nxt = self._sample(np.asarray(logits))
        # this step's (batch, Vpad) logits, on device, and which request
        # each running slot held — for callers that inspect the decode
        self.last_decode = (
            logits, {self.sched.slots[s].rid: s for s in running})
        for slot in running:
            tok = int(nxt[slot])
            self._pending[slot] = tok
            self._emit(slot, tok, emitted)
        return emitted

    def drain(self, max_steps: int | None = None) -> dict:
        """Step until every submitted request completed. Returns
        ``{rid: generated tokens}`` for all requests ever submitted."""
        steps = 0
        while not self.sched.idle:
            self.step()
            steps += 1
            if max_steps is not None and steps > max_steps:
                raise RuntimeError(f"drain: exceeded {max_steps} steps")
        return {rid: list(r.tokens) for rid, r in self._requests.items()}
