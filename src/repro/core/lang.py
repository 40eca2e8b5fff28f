"""The unified kernel language (paper §3, adapted for TPU).

One kernel source — a ``body(ctx, *tiles)`` function over VMEM-sized tiles
plus a :class:`Spec` describing its grid/block structure — expands to three
backends, mirroring the paper's macro expansion to OpenMP/OpenCL/CUDA:

  ``loops``   serial ``lax.fori_loop`` over the grid   (the OpenMP expansion)
  ``jnp``     whole-grid vectorized expansion          (portable reference / oracle)
  ``pallas``  ``pl.pallas_call`` + BlockSpec           (the TPU/"CUDA" expansion)

Keyword mapping (paper appendix tables → this module):

  occaOuterFor / occaOuterId   grid / ``ctx.outer_id(d)``
  occaInnerFor / occaInnerId   vector lanes of the tile / ``ctx.lane_ids(n)``
  occaShared (+ manual cache)  ``ctx.cache(ref)`` — tile load into VMEM
  occaShared (accumulators)    ``ctx.scratch`` — VMEM scratch declared via
                               ``Spec(scratch=[Scratch(shape, dtype)])``; the
                               refs persist across sequential reduce steps
  sequential inner loop        reduce axes — ``Spec(reduce_axes=...)`` marks
                               trailing grid axes as *sequential*: blocks
                               mapped to the same output index along those
                               axes are visited in order (occa's "outer loop
                               over work-groups, inner loop carrying state")
  occaBarrier(...)             ``ctx.barrier()`` — a no-op: a TPU block executes
                               as ONE sequenced program, which is exactly the
                               paper's OpenMP "inner loops run serially" model
  guarded occaOuterFor body    ``ctx.cell_when(pred)`` — masked/predicated grid
  (if(...) around the block)   cells: skip a whole block's work when ``pred``
                               (a function of grid ids + defines) is false.
                               Expands to ``pl.when`` on pallas and to
                               ``lax.cond`` over the tracked refs on jnp/loops
  loop scheduling pragmas      dimension_semantics — the pallas expansion marks
  (omp parallel for / CUDA     outer grid axes ``"parallel"`` and reduce axes
  blockIdx independence)       ``"arbitrary"`` so real-TPU grids pipeline
  streamed outputs             ``Tile(..., stream=True)`` — an *output* whose
  (writes inside the           index map may depend on reduce ids: every grid
  sequential inner loop)       cell writes its own block exactly once (e.g. the
                               per-chunk ``y`` of a chunked scan)
  per-output reduce            ``Tile(..., reduce=(axes,))`` — an *output* that
  granularity (outputs         accumulates over a SUBSET of the kernel's reduce
  accumulated at different     axes; its index map may depend on the remaining
  levels of the sequential     reduce axes (e.g. flash-bwd's fused pass: ``dq``
  loop nest)                   accumulates over k-blocks while ``dk``/``dv``
                               accumulate over q-blocks in ONE grid).
                               ``stream=True`` is sugar for ``reduce=()``;
                               the default (``reduce=None``) accumulates over
                               every reduce axis. Blocks keep their contents
                               across their accumulated visits — initialize
                               under ``ctx.reduce_first(d)`` and read-modify-
                               write (first-visit contents are undefined on a
                               real TPU, zero-filled on jnp/loops/interpret).
                               Real-TPU rule: when an ACCUMULATED axis is
                               outer to a slot axis, the block's revisits
                               are non-consecutive. jnp/loops/interpret
                               keep the contents; a compiled TPU pipeline
                               writes the block back when its index changes
                               and never re-reads it, so the compiled
                               pallas expansion REFUSES such outputs (the
                               accumulated axis innermost, as in dq or
                               matmul, is the portable pattern)
  dynamic input tiles          run-time data read by bodies and predicates
  (run-time kernel args /      without recompiling: a WHOLE-ARRAY input tile
  indirection arrays — the     (``block=None``) is visible to every grid cell
  unstructured-mesh pattern)   (flash-decode's ``(1, 1)`` ``kv_len`` scalar,
                               read by a ``cell_when`` block skip), while a
                               BLOCKED input tile streams per-cell data-
                               dependent state (flash-decode's ``(1, skv)``
                               ``slot_pos`` map — a rotated cache's
                               slot->position indirection, blocked along the
                               kv axis exactly like k/v). Input index maps
                               are bounds-checked over the whole grid at
                               build time; the jnp expansion hoists inputs
                               whose index map ignores the reduce ids out of
                               the sequential reduce loop (one slice per
                               outer cell instead of one per reduce step)
  tile-indexed index maps      ``Tile(index_tile=("table", axis))`` — the
  (indirection DRIVING the     block index along ``axis`` is READ AT RUN TIME
  fetch itself: vLLM's         from another i32 input tile (the "table") for
  PagedAttention block         the current grid cell, instead of computed by
  table)                       the static index map (whose value at ``axis``
                               is an ignored placeholder; return 0 there).
                               The table must be an integer input tile with
                               an all-ones block — its block index IS the
                               element it contributes — and the looked-up
                               value is clamped to the valid block range.
                               jnp/loops read the table element and
                               dynamic-slice; pallas lowers the table to a
                               scalar-prefetch operand
                               (``pltpu.PrefetchScalarGridSpec``) whose ref
                               the wrapped index maps read. The analyzer
                               bounds-checks the declaration (BOUNDS_TABLE)
                               and the cost model prices the gather as one
                               fetch per visiting cell (no consecutive-reuse
                               credit: the indices are dynamic)
  occaPrivate(Array)           ``ctx.private(x)`` — per-tile values (registers)
  occaCPU/occaGPU/occaOpenMP…  ``ctx.backend`` / ``ctx.is_pallas`` etc.
  occaKernelInfoArg            the ``ctx`` argument itself
  addDefine / buildKernel      ``Device.build_kernel(builder, defines=...)``

Reduction protocol (mirrors ``kernels/flash_attention``'s hand-rolled m/l/acc
pattern): reduce axes must be the *trailing* grid axes (innermost = sequential
on TPU). Scratch contents are undefined before the first reduce step — bodies
initialize under ``ctx.when(ctx.is_first)``, accumulate every step, and flush
outputs under ``ctx.when(ctx.is_last)`` (unconditional output writes are also
fine: the last visit wins on every backend). Output refs keep their contents
across the reduce visits of a block, so scratch-free accumulation directly
into an output block works too — but like scratch, an output block's
first-visit contents are undefined on a real TPU (zero-filled only on the
jnp/loops/interpret expansions), so read-modify-write bodies must initialize
the block under ``ctx.when(ctx.is_first)`` as well.

Restrictions (asserted): block shapes must divide the full array shape; an
output's index map must not depend on the reduce axes it ACCUMULATES over
(all of them by default; the declared subset with ``Tile(reduce=...)``; none
with ``stream=True``) — it may depend on the rest; and distinct
(outer x non-accumulated-reduce) cells must write distinct blocks, covering
every block exactly once (exactly once overall when the kernel has no reduce
axes).
"""

from __future__ import annotations

import dataclasses
import math
import re
from types import SimpleNamespace
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "Tile",
    "Scratch",
    "ShardAxis",
    "Spec",
    "Ctx",
    "TileRef",
    "cdiv",
    "defines_namespace",
    "expand",
    "kernel_names",
    "BACKENDS",
]

BACKENDS = ("jnp", "loops", "pallas")


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def defines_namespace(defines: dict | None) -> SimpleNamespace:
    return SimpleNamespace(**(defines or {}))


@dataclasses.dataclass(frozen=True)
class Tile:
    """One kernel argument: full array shape + its per-grid-cell block.

    ``block=None`` means the whole array is visible to every grid cell (the
    "global memory" view). ``index`` maps grid ids to *block* indices (Pallas
    convention); ``None`` selects the canonical identity map (requires
    ``len(grid) == ndim``) or the constant-zero map for whole-array tiles.

    ``halo=(r0, r1, ...)`` (INPUT tiles only, requires ``block=``) fetches
    each block with a per-axis halo: the body sees a
    ``(b0 + 2*r0, b1 + 2*r1, ...)`` window centered on the block, with the
    out-of-block fringe taken periodically (``wrap=True``, the default) or
    edge-clamped (``wrap=False``) — the stencil pattern, without caching the
    whole field per grid cell. The index map is unchanged (it still returns
    un-haloed block indices); interior element ``(i, j)`` of the block lives
    at ``window[r0 + i, r1 + j]``.
    """

    name: str
    shape: tuple[int, ...]
    dtype: object
    block: tuple[int, ...] | None = None
    index: Callable[..., tuple] | None = None
    # Output tiles only: a *streamed* output's index map may depend on reduce
    # ids — each grid cell (outer x reduce) writes a distinct block exactly
    # once, instead of accumulating into one block across the reduce space.
    stream: bool = False
    # Output tiles only: the subset of the Spec's reduce axes (grid-axis
    # numbers) this output ACCUMULATES over. None (default) = all reduce
    # axes; () = none (same as stream=True). The index map may depend on the
    # reduce axes NOT in this set — per-output reduce granularity.
    reduce: tuple[int, ...] | None = None
    # Input tiles only: per-axis halo radii; the fetched window is the block
    # plus r elements on each side along every axis (see class docstring).
    halo: tuple[int, ...] | None = None
    # Halo boundary rule: periodic wrap (True) or edge clamp (False).
    wrap: bool = True
    # Input tiles only: ("table", axis) — the block index along ``axis`` is
    # read at run time from the named i32 input tile's element for the
    # current grid cell (the PagedAttention block-table idiom). The static
    # index map's value at ``axis`` is an ignored placeholder; the table
    # tile must have an all-ones block. The looked-up index is clamped to
    # the block grid. Validated by the analyzer (BOUNDS_TABLE).
    index_tile: tuple[str, int] | None = None

    def resolved_block(self) -> tuple[int, ...]:
        blk = tuple(self.shape) if self.block is None else tuple(self.block)
        if len(blk) != len(self.shape):
            raise ValueError(
                f"tile {self.name!r}: block rank {len(blk)} != array rank {len(self.shape)}")
        for s, b in zip(self.shape, blk):
            if s % b != 0:
                raise ValueError(
                    f"tile {self.name!r}: block {blk} does not divide shape {self.shape}")
        return blk

    def resolved_halo(self) -> tuple[int, ...]:
        """Validated per-axis halo radii ((0,)*ndim when no halo)."""
        if self.halo is None:
            return (0,) * len(self.shape)
        halo = tuple(int(r) for r in self.halo)
        if len(halo) != len(self.shape):
            raise ValueError(
                f"tile {self.name!r}: halo rank {len(halo)} != array rank "
                f"{len(self.shape)}")
        if any(r < 0 for r in halo):
            raise ValueError(f"tile {self.name!r}: negative halo radius {halo}")
        if self.block is None and any(halo):
            raise ValueError(
                f"tile {self.name!r}: halo= requires a blocked tile (block=); "
                "a whole-array tile already sees every element")
        return halo

    def body_block(self) -> tuple[int, ...]:
        """The block shape the BODY sees: the resolved block grown by the
        halo fringe (identical to ``resolved_block()`` for halo-free tiles).
        This is also the per-cell VMEM-resident shape the cost model prices."""
        return tuple(b + 2 * r
                     for b, r in zip(self.resolved_block(),
                                     self.resolved_halo()))

    def resolved_index(self, grid: tuple[int, ...]) -> Callable[..., tuple]:
        if self.index is not None:
            return self.index
        blk = self.resolved_block()
        if blk == tuple(self.shape):  # whole-array tile
            ndim = len(self.shape)
            return lambda *gids: (0,) * ndim
        if len(grid) != len(self.shape):
            raise ValueError(
                f"tile {self.name!r}: no index map and grid rank {len(grid)} != "
                f"array rank {len(self.shape)}; pass index= explicitly")
        return lambda *gids: gids


@dataclasses.dataclass(frozen=True)
class Scratch:
    """A VMEM scratch buffer (occaShared accumulator analogue).

    Scratch refs are handed to the body via ``ctx.scratch`` and persist across
    the sequential visits of a reduce iteration-space (Pallas: real
    ``pltpu.VMEM`` scratch; jnp/loops: carried accumulators)."""

    shape: tuple[int, ...]
    dtype: object = jnp.float32

    def __post_init__(self):
        object.__setattr__(self, "shape", tuple(int(s) for s in self.shape))


SHARD_COLLECTIVES = (None, "ppermute", "psum", "psum_scatter")


@dataclasses.dataclass(frozen=True)
class ShardAxis:
    """A grid reduce axis that lives ACROSS devices on a named mesh axis.

    The spec's grid stays the per-shard (local) grid; ``extent`` says how many
    shards the bound reduce axis spans, and ``collective`` declares how the
    per-shard partials meet:

      ``"ppermute"``      ring schedule — the ``rotate`` input tiles hop to the
                          next shard after each ring step (ring attention's
                          k/v), so every shard eventually reduces over the full
                          axis. Outputs that do NOT accumulate over the bound
                          axis (it is one of their slot axes) write per-chunk
                          blocks owned by a *different* shard each step and
                          must be declared in ``sharded_outputs`` (their
                          cotangents/partials ride the ring home).
      ``"psum"``          every shard reduces its local slice, partials meet in
                          an all-reduce (the sharded-matmul pattern).
      ``"psum_scatter"``  as psum, but each shard keeps only its slice of the
                          result.
      ``None``            declared distribution with no collective — only legal
                          when nothing crosses shards (the analyzer rejects
                          accumulating outputs with COLLECTIVE_UNDECLARED).

    Structural validation happens in ``Spec.__post_init__``; the semantic
    cross-shard checks (write races over the mesh-extended grid, undeclared
    collectives) live in ``core.analyze.check_shard_binding`` and fail the
    build with stable finding codes (RACE_MESH_WRITE, COLLECTIVE_UNDECLARED).
    """

    mesh_axis: str
    axis: int
    extent: int = 1
    collective: str | None = "ppermute"
    rotate: tuple[str, ...] = ()
    sharded_outputs: tuple[str, ...] = ()

    def __post_init__(self):
        object.__setattr__(self, "axis", int(self.axis))
        object.__setattr__(self, "extent", int(self.extent))
        object.__setattr__(self, "rotate", tuple(self.rotate))
        object.__setattr__(self, "sharded_outputs",
                           tuple(self.sharded_outputs))
        if not self.mesh_axis or not isinstance(self.mesh_axis, str):
            raise ValueError("ShardAxis.mesh_axis must be a mesh axis name")
        if self.extent < 1:
            raise ValueError(f"ShardAxis.extent must be >= 1, got {self.extent}")
        if self.collective not in SHARD_COLLECTIVES:
            raise ValueError(
                f"ShardAxis.collective {self.collective!r} unknown "
                f"(one of {SHARD_COLLECTIVES})")


@dataclasses.dataclass
class Spec:
    """A built kernel: grid + tiles + body. Produced by a builder(D) call.

    ``reduce_axes`` marks trailing grid axes as sequential reduction axes;
    ``scratch`` declares VMEM accumulators that persist across the reduce
    steps (see module docstring for the protocol)."""

    name: str
    grid: tuple[int, ...]
    inputs: list[Tile]
    outputs: list[Tile]
    body: Callable
    reduce_axes: tuple[int, ...] = ()
    scratch: list[Scratch] = dataclasses.field(default_factory=list)
    # Per-axis pallas pipelining override ("parallel" | "arbitrary" per grid
    # axis). None derives the safe default: outer axes parallel, reduce axes
    # arbitrary. The analyzer rejects a "parallel" reduce axis that carries
    # scratch or an output accumulation (SEMANTICS_PARALLEL_CARRIED).
    dimension_semantics: tuple[str, ...] | None = None
    # Declared mesh binding: one reduce axis distributed across devices with
    # a named collective (see ShardAxis). The grid stays per-shard; the
    # analyzer extends its race/coverage/cost reasoning over
    # extent-many shards when the binding is active (extent > 1).
    shard: ShardAxis | None = None

    def __post_init__(self):
        self.grid = tuple(int(g) for g in self.grid)
        if not self.grid:
            raise ValueError("grid must be non-empty")
        names = [t.name for t in self.inputs + self.outputs]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate tile names in kernel {self.name!r}")

        self.reduce_axes = tuple(sorted(int(a) for a in self.reduce_axes))
        if len(set(self.reduce_axes)) != len(self.reduce_axes):
            raise ValueError(f"duplicate reduce axes {self.reduce_axes}")
        k = len(self.grid) - len(self.reduce_axes)
        if self.reduce_axes and self.reduce_axes != tuple(range(k, len(self.grid))):
            raise ValueError(
                f"reduce_axes {self.reduce_axes} must be the trailing grid axes "
                f"(grid rank {len(self.grid)}): sequential axes are innermost on TPU")
        self.scratch = list(self.scratch)
        for s in self.scratch:
            if not isinstance(s, Scratch):
                raise TypeError(f"scratch entries must be lang.Scratch, got {type(s)}")

        if self.dimension_semantics is not None:
            sem = tuple(self.dimension_semantics)
            if len(sem) != len(self.grid):
                raise ValueError(
                    f"dimension_semantics has {len(sem)} entries for a rank-"
                    f"{len(self.grid)} grid")
            bad = [s for s in sem if s not in ("parallel", "arbitrary")]
            if bad:
                raise ValueError(
                    f"dimension_semantics entries must be 'parallel' or "
                    f"'arbitrary', got {bad}")
            self.dimension_semantics = sem

        for t in self.inputs:
            # stream=/reduce= are OUTPUT declarations (accumulation contracts);
            # on an input they would be silently ignored — reject at build
            # time so a mis-declared kernel fails loudly (surfaced by the
            # first op whose outputs span several reduce granularities)
            if t.stream or t.reduce is not None:
                raise ValueError(
                    f"input tile {t.name!r}: stream=/reduce= are output-only "
                    "declarations (inputs are read at every visit)")
            t.resolved_halo()  # structural halo validation (rank/sign/block)

        for t in self.outputs:
            # a halo is a FETCH pattern; overlapping output windows would race
            if t.halo is not None and any(int(r) for r in t.halo):
                raise ValueError(
                    f"output tile {t.name!r}: halo= is input-only "
                    "(overlapping output windows would write racily)")

        if self.shard is not None:
            # Structural shard-binding checks; the semantic cross-shard pass
            # (races / undeclared collectives over the mesh-extended grid)
            # runs in check_grid_invariants below.
            sh = self.shard
            if not isinstance(sh, ShardAxis):
                raise TypeError(
                    f"Spec.shard must be a lang.ShardAxis, got {type(sh)}")
            if sh.axis not in self.reduce_axes:
                raise ValueError(
                    f"kernel {self.name!r}: shard axis {sh.axis} is not a "
                    f"reduce axis {self.reduce_axes} — only sequential "
                    "(reduce) grid axes can be distributed across the mesh")
            in_names = {t.name for t in self.inputs}
            out_names = {t.name for t in self.outputs}
            unknown = set(sh.rotate) - in_names
            if unknown:
                raise ValueError(
                    f"kernel {self.name!r}: ShardAxis.rotate names unknown "
                    f"input tiles {sorted(unknown)}")
            unknown = set(sh.sharded_outputs) - out_names
            if unknown:
                raise ValueError(
                    f"kernel {self.name!r}: ShardAxis.sharded_outputs names "
                    f"unknown output tiles {sorted(unknown)}")

        # Concrete-grid invariants — non-dividing blocks, out-of-range index
        # maps (inputs AND outputs), parallel-cell write races, accumulated-
        # axis index dependence, unwritten blocks — are enforced at build
        # time: autotune relies on invalid candidates failing inside
        # build_kernel, not at the first (jitted) run. The enumeration lives
        # in core.analyze (the static analyzer's grid pass); it also computes
        # which inputs' block index ignores the reduce ids, so the jnp
        # expansion can hoist those slices out of the sequential reduce loop
        # (e.g. flash-decode's q tile is sliced once per (b, h) cell, not
        # once per kv block).
        from .analyze import AnalysisError, check_grid_invariants

        findings, self._input_reduce_invariant = check_grid_invariants(self)
        if findings:
            raise AnalysisError(findings)

    # -- grid split helpers --------------------------------------------------
    @property
    def outer_grid(self) -> tuple[int, ...]:
        return self.grid[: len(self.grid) - len(self.reduce_axes)]

    @property
    def reduce_grid(self) -> tuple[int, ...]:
        return tuple(self.grid[a] for a in self.reduce_axes)

    def resolved_semantics(self) -> tuple[str, ...]:
        """Per-axis ``dimension_semantics``: the declared tuple, else the
        default — outer axes are embarrassingly parallel (each output block
        is written from exactly one outer cell), reduce axes carry scratch
        state and must stay sequential ("arbitrary")."""
        if self.dimension_semantics is not None:
            return tuple(self.dimension_semantics)
        n_par = len(self.grid) - len(self.reduce_axes)
        return ("parallel",) * n_par + ("arbitrary",) * len(self.reduce_axes)

    def output_reduce_axes(self, t: Tile) -> tuple[int, ...]:
        """The reduce axes this output ACCUMULATES over (sorted grid axes)."""
        if t.reduce is not None:
            r = tuple(sorted(int(a) for a in t.reduce))
            if len(set(r)) != len(r):
                raise ValueError(
                    f"output tile {t.name!r}: duplicate axes in reduce={r}")
            if t.stream and r:
                raise ValueError(
                    f"output tile {t.name!r}: stream=True means reduce=(), "
                    f"got reduce={r}")
            if not set(r) <= set(self.reduce_axes):
                raise ValueError(
                    f"output tile {t.name!r}: reduce={r} is not a subset of "
                    f"the kernel's reduce axes {self.reduce_axes}")
            return r
        return () if t.stream else self.reduce_axes

    def output_slot_axes(self, t: Tile) -> tuple[int, ...]:
        """Reduce axes the output's index map may depend on — they select
        which of the output's blocks ("slot") a reduce step writes."""
        acc = set(self.output_reduce_axes(t))
        return tuple(a for a in self.reduce_axes if a not in acc)

    def slot_index(self, t: Tile) -> Callable[..., tuple]:
        """Output index map over (outer + slot-axis) cells — the accumulated
        reduce ids are pinned to 0 (the map does not depend on them)."""
        full = t.resolved_index(self.grid)
        acc = set(self.output_reduce_axes(t))
        k = len(self.outer_grid)

        def f(*cells):
            og, sg = cells[:k], iter(cells[k:])
            rids = tuple(0 if a in acc else next(sg) for a in self.reduce_axes)
            return full(*og, *rids)

        return f


class TileRef:
    """Functional ref shim exposing the same read/write surface as a Pallas Ref."""

    __slots__ = ("_value",)

    def __init__(self, value):
        self._value = value

    def __getitem__(self, idx):
        return self._value[idx]

    def __setitem__(self, idx, val):
        if idx is Ellipsis or idx == slice(None):
            self._value = jnp.broadcast_to(val, self._value.shape).astype(self._value.dtype)
        else:
            self._value = self._value.at[idx].set(val)

    @property
    def value(self):
        return self._value

    @property
    def shape(self):
        return self._value.shape

    @property
    def dtype(self):
        return self._value.dtype


class Ctx:
    """occaKernelInfoArg analogue: grid ids/dims, defines, backend flags,
    reduce position and scratch refs."""

    def __init__(self, backend: str, defines: SimpleNamespace,
                 gids: Sequence, grid: tuple[int, ...], *,
                 reduce_axes: tuple[int, ...] = (), scratch: Sequence = (),
                 refs: Sequence = ()):
        self.backend = backend
        self.D = defines
        self._gids = tuple(gids)
        self.grid = grid
        self._reduce_axes = tuple(reduce_axes)
        self.scratch = tuple(scratch)
        self._refs = tuple(refs)

    # --- occaOuterId / occaOuterDim ---------------------------------------
    def outer_id(self, d: int):
        return self._gids[d]

    def outer_dim(self, d: int) -> int:
        return self.grid[d]

    # --- reduce (sequential) axes -----------------------------------------
    def reduce_id(self, d: int = 0):
        """Position along the d-th reduce axis (0 .. reduce_dim(d) - 1)."""
        return self._gids[self._reduce_axes[d]]

    def reduce_dim(self, d: int = 0) -> int:
        return self.grid[self._reduce_axes[d]]

    def reduce_first(self, d: int = 0):
        """True on the first step along the d-th reduce axis — the init point
        for state accumulated over THAT axis only (e.g. a ``Tile(reduce=...)``
        output or a scratch reset per outer sweep of a 2-deep reduce nest)."""
        return self._gids[self._reduce_axes[d]] == 0

    def reduce_last(self, d: int = 0):
        """True on the last step along the d-th reduce axis (flush point)."""
        a = self._reduce_axes[d]
        return self._gids[a] == self.grid[a] - 1

    @property
    def is_first(self):
        """True on the first visit of the reduce iteration-space (init point).

        A plain ``True`` for kernels without reduce axes; a traced scalar
        bool otherwise."""
        ids = [self._gids[a] for a in self._reduce_axes]
        if not ids:
            return True
        pred = ids[0] == 0
        for i in ids[1:]:
            pred = pred & (i == 0)
        return pred

    @property
    def is_last(self):
        """True on the last visit of the reduce iteration-space (flush point)."""
        if not self._reduce_axes:
            return True
        pred = None
        for a in self._reduce_axes:
            p = self._gids[a] == self.grid[a] - 1
            pred = p if pred is None else pred & p
        return pred

    def when(self, pred):
        """Run the decorated thunk only when ``pred`` holds (pl.when analogue).

        Under pallas this is ``pl.when``; under jnp/loops the thunk runs
        unconditionally and every tracked ref write is select-masked on
        ``pred`` — semantically identical, fully functional."""
        def deco(fn):
            if isinstance(pred, (bool, np.bool_)):
                if pred:
                    fn()
                return fn
            if self.backend == "pallas":
                pl.when(pred)(fn)
                return fn
            before = [r._value for r in self._refs]
            fn()
            for r, old in zip(self._refs, before):
                r._value = jnp.where(pred, r._value, old)
            return fn
        return deco

    def cell_when(self, pred):
        """Masked grid cell: run the thunk only when ``pred`` holds, skipping
        the WHOLE block's work otherwise (flash-attention's causal block skip).

        ``pred`` must be a scalar bool of grid ids, defines and values already
        loaded from input tiles (e.g. flash-decode's dynamic kv length) —
        never of output/scratch contents. Under pallas
        this is ``pl.when`` (no MXU work issued for skipped cells); under
        jnp/loops the thunk becomes one branch of a ``lax.cond`` over the
        tracked refs (a real skip on the loops expansion; a select under the
        jnp vmap, which is semantically identical)."""
        def deco(fn):
            if isinstance(pred, (bool, np.bool_)):
                if pred:
                    fn()
                return fn
            if self.backend == "pallas":
                pl.when(pred)(fn)
                return fn
            before = tuple(r._value for r in self._refs)

            def _taken(vals):
                for r, v in zip(self._refs, vals):
                    r._value = v
                fn()
                return tuple(r._value for r in self._refs)

            after = lax.cond(pred, _taken, lambda vals: vals, before)
            for r, v in zip(self._refs, after):
                r._value = v
            return fn
        return deco

    # --- occaInnerId: lanes of the vectorized tile ------------------------
    def lane_ids(self, n: int):
        return jnp.arange(n)

    # --- occaBarrier: no-op (sequential block execution; see module doc) --
    def barrier(self, *_fence):
        return None

    # --- occaShared manual caching: load a tile into VMEM ------------------
    def cache(self, ref):
        return ref[...]

    # --- occaPrivate ------------------------------------------------------
    def private(self, value):
        return value

    # --- occaCPU / occaGPU / occaOpenMP / occaOpenCL / occaCUDA ------------
    @property
    def is_pallas(self) -> bool:
        return self.backend == "pallas"

    @property
    def is_jnp(self) -> bool:
        return self.backend == "jnp"

    @property
    def is_loops(self) -> bool:
        return self.backend == "loops"


# ---------------------------------------------------------------------------
# Halo lowering
# ---------------------------------------------------------------------------
#
# A halo tile is lowered to a REGULAR blocked tile over a windowed layout
# before any backend sees it: per block index ``i`` along a haloed axis, the
# window ``[i*b - r, (i+1)*b + r)`` (periodic or edge-clamped) is materialized
# contiguously, so block ``i`` of the lowered array IS the haloed window and
# every backend — including Pallas, whose BlockSpec cannot express
# overlapping fetches — runs the exact same non-overlapping blocked machinery.
# The gather is one static-index ``jnp.take`` per haloed axis on the host
# side of the call; its cost is the halo amplification ``(b + 2r) / b`` the
# static cost model charges for the tile.
#
# On the compiled TPU expansion a block's last two dims must be multiples of
# (sublane, 128) or span the whole array, which a ``b + 2r`` window never
# does. There (``align=True``) the windows along the trailing two haloed axes
# are widened to the next legal size; the extra elements continue the
# window's wrap/clamp pattern and the body still sees exactly its
# ``b + 2r`` window (a view of the fetched block).

_LANES = 128


def _sublanes(dtype) -> int:
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _halo_axis_index(nblocks: int, b: int, r: int, s: int, wrap: bool,
                     width: int):
    """Static source indices for one haloed axis's windowed layout."""
    offs = np.arange(-r, width - r)
    idx = (np.arange(nblocks)[:, None] * b + offs[None, :]).reshape(-1)
    return idx % s if wrap else np.clip(idx, 0, s - 1)


def _lower_halo_tile(tile: Tile, align: bool = False) -> tuple[Tile, Callable]:
    blk = tile.resolved_block()
    halo = tile.resolved_halo()
    nb = tuple(s // b for s, b in zip(tile.shape, blk))
    wblk = list(tile.body_block())
    if align:
        nd = len(wblk)
        for d, unit in ((nd - 2, _sublanes(tile.dtype)), (nd - 1, _LANES)):
            if d >= 0 and halo[d]:
                wblk[d] = cdiv(wblk[d], unit) * unit
    wblk = tuple(wblk)
    wshape = tuple(n * w for n, w in zip(nb, wblk))
    takes = [(d, jnp.asarray(_halo_axis_index(n, b, r, s, tile.wrap, w),
                             dtype=jnp.int32))
             for d, (n, b, r, s, w) in enumerate(
                 zip(nb, blk, halo, tile.shape, wblk))
             if r]

    def windowize(arr):
        for d, idx in takes:
            arr = jnp.take(arr, idx, axis=d)
        return arr

    lowered = dataclasses.replace(
        tile, shape=wshape, block=wblk, halo=None)
    return lowered, windowize


def _lower_halos(spec: Spec, align: bool = False
                 ) -> tuple[Spec, list | None, list | None]:
    """(lowered spec, per-input window fns, per-input body windows) —
    (spec, None, None) when halo-free. A body window is the ``b + 2r`` shape
    the body sees where the fetched block is wider (``align``), else None."""
    if not any(t.halo is not None and any(t.resolved_halo())
               for t in spec.inputs):
        return spec, None, None
    preps, inputs, views = [], [], []
    for t in spec.inputs:
        view = None
        if t.halo is not None and any(t.resolved_halo()):
            lowered, prep = _lower_halo_tile(t, align)
            if lowered.block != t.body_block():
                view = t.body_block()
        else:
            lowered, prep = t, None
        inputs.append(lowered)
        preps.append(prep)
        views.append(view)
    lowered = dataclasses.replace(spec, inputs=inputs)
    return lowered, preps, views


# ---------------------------------------------------------------------------
# Backend expansions
# ---------------------------------------------------------------------------

def _slice_tile(tile: Tile, arr, gids, grid, tables=None):
    blk = tile.resolved_block()
    if tile.index_tile is None and blk == tuple(tile.shape):
        return TileRef(arr)  # whole-array view: no copy, no vmap blow-up
    bidx = list(tile.resolved_index(grid)(*gids))
    if tile.index_tile is not None:
        # the block index along the gathered axis comes from the table
        # tile's element for this cell (the static map's value there is a
        # placeholder); clamped so a corrupt table cannot read out of bounds
        tname, axis = tile.index_tile
        ttile, tarr = tables[tname]
        val = _slice_tile(ttile, tarr, gids, grid).value.reshape(-1)[0]
        nb = tile.shape[axis] // blk[axis]
        bidx[axis] = jnp.clip(val.astype(jnp.int32), 0, nb - 1)
    starts = [i * b for i, b in zip(bidx, blk)]
    return TileRef(lax.dynamic_slice(arr, starts, blk))


def _static_starts(tile: Tile, grid, index_fn) -> np.ndarray:
    """Evaluate an index map for every cell of ``grid`` at trace time."""
    blk = tile.resolved_block()
    starts = [
        [int(i) * b for i, b in zip(index_fn(*cell), blk)]
        for cell in np.ndindex(*grid)
    ]
    return np.asarray(starts, dtype=np.int32)


def _is_canonical(tile: Tile, grid, index_fn) -> bool:
    """True if ``index_fn`` is the identity over ``grid`` (fast reshape path)."""
    blk = tile.resolved_block()
    if len(grid) != len(tile.shape):
        return False
    if any(g * b != s for g, b, s in zip(grid, blk, tile.shape)):
        return False
    for cell in np.ndindex(*grid):
        if tuple(int(i) for i in index_fn(*cell)) != cell:
            return False
    return True


def _run_body(spec: Spec, backend: str, defines, gids, ins, out_vals, scr_vals):
    """One grid-cell body invocation on the functional (jnp/loops) backends.

    Returns the updated (output block values, scratch values)."""
    outs = [TileRef(v) for v in out_vals]
    scr = [TileRef(v) for v in scr_vals]
    ctx = Ctx(backend, defines, gids, spec.grid,
              reduce_axes=spec.reduce_axes, scratch=scr, refs=tuple(outs) + tuple(scr))
    spec.body(ctx, *ins, *outs)
    return tuple(o.value for o in outs), tuple(s.value for s in scr)


def _assemble_blocks(t: Tile, stack, grid_used, index_fn):
    """Scatter a (prod(grid_used), *blk) stack of blocks into the full array."""
    blk = t.resolved_block()
    ngrid = math.prod(grid_used) if grid_used else 1
    if _is_canonical(t, grid_used, index_fn):
        # (g0..gk, b0..bk) -> interleave -> full shape
        x = stack.reshape(tuple(grid_used) + blk)
        perm = []
        for d in range(len(grid_used)):
            perm += [d, len(grid_used) + d]
        x = x.transpose(perm)
        return x.reshape(t.shape)
    starts = jnp.asarray(_static_starts(t, grid_used, index_fn))
    out0 = jnp.zeros(t.shape, t.dtype)

    def write(j, acc):
        st = [starts[j, k] for k in range(starts.shape[1])]
        return lax.dynamic_update_slice(acc, stack[j], st)

    return lax.fori_loop(0, ngrid, write, out0)


def _expand_jnp(spec: Spec, defines: SimpleNamespace):
    grid = spec.grid
    outer_grid = spec.outer_grid
    red_grid = spec.reduce_grid
    nouter = math.prod(outer_grid) if outer_grid else 1
    nred = math.prod(red_grid) if red_grid else 1
    # Per-output slot structure: within one outer cell, an output owns one
    # block per combination of its slot axes (the reduce axes it does NOT
    # accumulate over). Full-accumulate outputs have 1 slot; streamed outputs
    # have nred. Blocks are carried as a (nslots, *blk) stack across the
    # sequential reduce loop — a visited slot keeps its contents, so partial-
    # reduce outputs read-modify-write their block exactly like the resident
    # Pallas block.
    slot_pos = []   # positions (within reduce_axes) of each output's slot axes
    slot_dims = []  # the grid extents of those axes
    for t in spec.outputs:
        axes = spec.output_slot_axes(t)
        slot_pos.append(tuple(spec.reduce_axes.index(a) for a in axes))
        slot_dims.append(tuple(spec.grid[a] for a in axes))

    # inputs whose block index ignores the reduce ids (statically probed at
    # Spec build): slice ONCE per outer cell, not once per reduce step
    hoistable = spec._input_reduce_invariant if red_grid else \
        [False] * len(spec.inputs)
    zero_r = (0,) * len(spec.reduce_axes)

    def fn(*in_arrays):
        tables = {t.name: (t, a) for t, a in zip(spec.inputs, in_arrays)}

        def cell(flat_idx):
            ogids = jnp.unravel_index(flat_idx, outer_grid) if outer_grid else ()
            pinned = [
                _slice_tile(t, a, tuple(ogids) + zero_r, grid, tables).value
                if h else None
                for t, a, h in zip(spec.inputs, in_arrays, hoistable)]
            stk0 = tuple(
                jnp.zeros((math.prod(sd) if sd else 1,) + t.resolved_block(),
                          t.dtype)
                for t, sd in zip(spec.outputs, slot_dims))
            scr0 = tuple(jnp.zeros(s.shape, s.dtype) for s in spec.scratch)

            def step(r, carry):
                stacks, scr_vals = carry
                rgids = jnp.unravel_index(r, red_grid) if red_grid else ()
                gids = tuple(ogids) + tuple(rgids)
                # hoisted inputs get a FRESH TileRef per step: input refs are
                # read-only by contract, but a stray in-body write must not
                # leak across reduce steps
                ins = [TileRef(p) if h else _slice_tile(t, a, gids, grid,
                                                        tables)
                       for t, a, h, p in zip(spec.inputs, in_arrays,
                                             hoistable, pinned)]
                slots, cur = [], []
                for t, stack, pos, sd in zip(spec.outputs, stacks, slot_pos,
                                             slot_dims):
                    s = 0
                    for p, dim in zip(pos, sd):
                        s = s * dim + rgids[p]
                    slots.append(s)
                    blk = t.resolved_block()
                    cur.append(lax.dynamic_slice(
                        stack, (s,) + (0,) * len(blk), (1,) + blk)[0])
                new_out, new_scr = _run_body(spec, "jnp", defines, gids, ins,
                                             tuple(cur), scr_vals)
                new_stacks = tuple(
                    lax.dynamic_update_slice(
                        stack, v[None], (s,) + (0,) * (stack.ndim - 1))
                    for stack, v, s in zip(stacks, new_out, slots))
                return new_stacks, new_scr

            if red_grid:
                stacks, _ = lax.fori_loop(0, nred, step, (stk0, scr0))
            else:
                stacks, _ = step(0, (stk0, scr0))
            return stacks

        blocks = jax.vmap(cell)(jnp.arange(nouter))  # tuple of (nouter, nslots, ...)
        results = []
        for t, stack, sd in zip(spec.outputs, blocks, slot_dims):
            blk = t.resolved_block()
            ns = math.prod(sd) if sd else 1
            # (nouter, nslots, *blk) -> flat C order over (outer + slot axes),
            # the same visit order as np.ndindex over that combined grid
            results.append(_assemble_blocks(
                t, stack.reshape((nouter * ns,) + blk),
                tuple(outer_grid) + sd, spec.slot_index(t)))
        return tuple(results)

    return fn


def _expand_single_cell(spec: Spec, defines: SimpleNamespace, backend: str):
    """Degenerate grid (one cell): run the body once, directly on the full
    arrays — no vmap, no fori_loop, no dynamic slicing. The jnp and loops
    expansions collapse to the same program here, and the removed machinery
    is pure overhead at exactly the shapes where it matters most (a block
    sized to the whole problem, the autotuner's frequent small-shape winner)."""
    grid = spec.grid
    gids = (0,) * len(grid)

    def fn(*in_arrays):
        tables = {t.name: (t, a) for t, a in zip(spec.inputs, in_arrays)}
        ins = [_slice_tile(t, a, gids, grid, tables)
               for t, a in zip(spec.inputs, in_arrays)]
        out0 = tuple(jnp.zeros(t.resolved_block(), t.dtype)
                     for t in spec.outputs)
        scr0 = tuple(jnp.zeros(s.shape, s.dtype) for s in spec.scratch)
        out_vals, _ = _run_body(spec, backend, defines, gids, ins, out0, scr0)
        results = []
        for t, v in zip(spec.outputs, out_vals):
            blk = t.resolved_block()
            if blk == tuple(t.shape):
                results.append(v)
            else:
                bidx = t.resolved_index(grid)(*gids)
                starts = [int(i) * b for i, b in zip(bidx, blk)]
                results.append(lax.dynamic_update_slice(
                    jnp.zeros(t.shape, t.dtype), v, starts))
        return tuple(results)

    return fn


def _expand_loops(spec: Spec, defines: SimpleNamespace):
    grid = spec.grid
    ncells = math.prod(grid)

    def fn(*in_arrays):
        tables = {t.name: (t, a) for t, a in zip(spec.inputs, in_arrays)}
        outs0 = tuple(jnp.zeros(t.shape, t.dtype) for t in spec.outputs)
        scr0 = tuple(jnp.zeros(s.shape, s.dtype) for s in spec.scratch)

        def step(flat_idx, carry):
            accs, scr_vals = carry
            # C-order unravel: trailing (reduce) axes iterate innermost, so
            # scratch carried across steps sees the reduce space sequentially
            # — the same visit order as the Pallas grid.
            gids = jnp.unravel_index(flat_idx, grid)
            ins = [_slice_tile(t, a, gids, grid, tables)
                   for t, a in zip(spec.inputs, in_arrays)]
            # With reduce axes, output refs see the block's CURRENT contents
            # (zeros on first visit): bodies that accumulate directly into an
            # output behave like the jnp carry / resident Pallas block.
            # Without them every block is visited once and the slice would
            # always read zeros — skip it.
            out_blk0, out_starts = [], []
            for t, acc in zip(spec.outputs, accs):
                blk = t.resolved_block()
                bidx = t.resolved_index(grid)(*gids)
                starts = [i * b for i, b in zip(bidx, blk)]
                out_starts.append(starts)
                if spec.reduce_axes:
                    out_blk0.append(lax.dynamic_slice(acc, starts, blk))
                else:
                    out_blk0.append(jnp.zeros(blk, t.dtype))
            out_vals, scr_vals = _run_body(spec, "loops", defines, gids, ins,
                                           tuple(out_blk0), scr_vals)
            new = [lax.dynamic_update_slice(acc, val, starts)
                   for val, acc, starts in zip(out_vals, accs, out_starts)]
            return tuple(new), scr_vals

        outs, _ = lax.fori_loop(0, ncells, step, (outs0, scr0))
        return outs

    return fn


def _is_scalar_tile(t: Tile) -> bool:
    """An integer input whose block is all ones: every grid cell reads ONE
    element (a length, an offset, a block-table entry)."""
    return (jnp.issubdtype(jnp.dtype(t.dtype), jnp.integer)
            and all(b == 1 for b in t.resolved_block()))


class _ScalarView:
    """The body's view of a scalar tile on the pallas expansion: the whole
    array lives in SMEM (scalar prefetch), and the body's read of its
    all-ones block becomes the element the cell's index map names — a
    scalar for ``ref[0, ..., 0]``, the (1, ..., 1) block broadcast from it
    for any other index."""

    __slots__ = ("_ref", "_index", "_gids", "_ndim")

    def __init__(self, ref, index, gids, ndim):
        self._ref, self._index, self._gids = ref, index, gids
        self._ndim = ndim

    def __getitem__(self, idx):
        idx = idx if isinstance(idx, tuple) else (idx,)
        val = self._ref[tuple(self._index(*self._gids))]
        if len(idx) == self._ndim and all(isinstance(i, int) for i in idx):
            return val
        return jnp.full((1,) * self._ndim, val)[idx]


def _noncontiguous_revisits(spec: Spec, t: Tile) -> bool:
    """True when an output accumulates over a reduce axis OUTER to one of
    its slot axes: its block is then revisited on non-consecutive grid
    steps."""
    acc = spec.output_reduce_axes(t)
    return any(a < s_ for a in acc for s_ in spec.output_slot_axes(t))


def _expand_pallas(spec: Spec, defines: SimpleNamespace, interpret: bool,
                   views=None):
    grid = spec.grid
    ng = len(grid)
    n_out = len(spec.outputs)
    if not interpret:
        # a compiled TPU pipeline writes an output block back when its index
        # changes and never reads it again: accumulating across
        # non-consecutive visits would silently read a stale buffer
        bad = [t.name for t in spec.outputs
               if _noncontiguous_revisits(spec, t)]
        if bad:
            raise ValueError(
                f"kernel {spec.name!r}: outputs {bad} accumulate over a "
                "reduce axis outer to their slot axes — their blocks are "
                "revisited non-consecutively, which the compiled Pallas "
                "pipeline does not support (split the kernel so each output "
                "accumulates over its innermost axes)")
    tiles = {t.name: t for t in spec.inputs}
    # Scalar tiles (integer, all-ones block: lengths, offsets, block tables)
    # travel as scalar-prefetch operands in SMEM — a (1, 1) VMEM block would
    # break the TPU's (8, 128) block rule — and index_tile tables are always
    # scalar tiles, so the wrapped index maps read them from the same refs.
    scalars = [t.name for t in spec.inputs if _is_scalar_tile(t)]
    for t in spec.inputs:
        if t.index_tile is not None and t.index_tile[0] not in scalars:
            raise ValueError(
                f"kernel {spec.name!r}: index_tile table "
                f"{t.index_tile[0]!r} must be an integer tile with an "
                "all-ones block")
    n_sc = len(scalars)
    blocked = [t for t in spec.inputs if t.name not in scalars]
    views = list(views) if views is not None else [None] * len(spec.inputs)

    def body_adapter(*refs):
        srefs, refs = refs[:n_sc], refs[n_sc:]
        gids = tuple(pl.program_id(d) for d in range(ng))
        vmem_in = iter(refs[:len(blocked)])
        ins = []
        for t, view in zip(spec.inputs, views):
            if t.name in scalars:
                ins.append(_ScalarView(srefs[scalars.index(t.name)],
                                       t.resolved_index(grid), gids,
                                       len(t.shape)))
                continue
            ref = next(vmem_in)
            if view is not None:   # aligned halo block: the body's window
                ref = ref.at[tuple(pl.ds(0, w) for w in view)]
            ins.append(ref)
        outs = refs[len(blocked): len(blocked) + n_out]
        scr = refs[len(blocked) + n_out:]
        ctx = Ctx("pallas", defines, gids, grid,
                  reduce_axes=spec.reduce_axes, scratch=scr)
        spec.body(ctx, *ins, *outs)

    def mk_index(t: Tile):
        base = t.resolved_index(grid)
        if t.index_tile is None:
            if not n_sc:
                return base
            return lambda *a: base(*a[:ng])
        tname, axis = t.index_tile
        tindex = tiles[tname].resolved_index(grid)
        ti = scalars.index(tname)
        nb = t.shape[axis] // t.resolved_block()[axis]

        def gather(*a):
            ids, srefs = a[:ng], a[ng:]
            # all-ones table block: its block index IS the element index
            val = srefs[ti][tuple(tindex(*ids))]
            out = list(base(*ids))
            out[axis] = jnp.clip(val, 0, nb - 1)
            return tuple(out)

        return gather

    def mk_block(t: Tile):
        return pl.BlockSpec(t.resolved_block(), mk_index(t))

    # Real-TPU pipelining: outer axes are embarrassingly parallel (validated:
    # each output block is written from exactly one outer cell), reduce axes
    # carry scratch state and must stay sequential ("arbitrary"). The
    # interpreter ignores compiler params, so only pass them when compiling.
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=spec.resolved_semantics())

    in_specs = [mk_block(t) for t in blocked]
    out_specs = [mk_block(t) for t in spec.outputs]
    out_shape = [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in spec.outputs]
    scratch_shapes = [pltpu.VMEM(s.shape, s.dtype) for s in spec.scratch]

    call = pl.pallas_call(
        body_adapter,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_sc, grid=grid,
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        interpret=interpret,
        name=spec.name,            # the kernel's name in HLO and traces
        **kwargs,
    )
    pos = {t.name: i for i, t in enumerate(spec.inputs)}
    scalar_pos = [pos[n] for n in scalars]
    blocked_pos = [pos[t.name] for t in blocked]

    def fn(*in_arrays):
        return tuple(call(*(in_arrays[i] for i in scalar_pos),
                          *(in_arrays[i] for i in blocked_pos)))

    return fn


def kernel_names(compiled_text: str) -> set:
    """Names of the compiled Pallas kernels (Mosaic ``tpu_custom_call``s)
    in a compiled program's HLO text: each is its spec's ``name``."""
    names = set()
    for line in compiled_text.splitlines():
        if "tpu_custom_call" in line:
            m = re.search(r'op_name="[^"]*?/([A-Za-z0-9_]+)/pallas_call"',
                          line)
            if m:
                names.add(m.group(1))
    return names


def expand(spec: Spec, defines: SimpleNamespace, backend: str, *, interpret: bool = True):
    """Expand one kernel Spec for a backend (the run-time 'macro expansion')."""
    spec, preps, views = _lower_halos(spec, align=backend == "pallas")
    if backend in ("jnp", "loops") and math.prod(spec.grid) == 1:
        inner = _expand_single_cell(spec, defines, backend)
    elif backend == "jnp":
        inner = _expand_jnp(spec, defines)
    elif backend == "loops":
        inner = _expand_loops(spec, defines)
    elif backend == "pallas":
        inner = _expand_pallas(spec, defines, interpret, views)
    else:
        raise ValueError(
            f"unknown backend {backend!r}; expected one of {BACKENDS}")
    if preps is None:
        return inner

    def fn(*in_arrays):
        return inner(*(a if p is None else p(a)
                       for p, a in zip(preps, in_arrays)))

    return fn
