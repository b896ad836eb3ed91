"""Thread-coarsening for Pallas TPU kernels — the paper's core technique.

The paper ("Exploring Thread Coarsening on FPGA", Eghbali Zarch et al. 2022)
consolidates the work of C OpenCL work-items into one work-item.  On TPU the
work-item analog is one Pallas *grid program*; coarsening therefore shrinks the
grid by C and grows the per-program work:

* ``consecutive``  — the C fused blocks are *contiguous*.  Expressed by viewing
  the streamed axis as ``(G, C, B)`` and fetching block ``(1, C, B)``: one wide
  HBM->VMEM DMA per operand per grid step.  This is the analog of the single
  wide burst-coalesced LSU the Intel offline compiler emits (paper Fig. 4,
  top-right).

* ``gapped``       — the C fused blocks are strided by ``G``.  Expressed by
  viewing the axis as ``(C, G, B)`` and fetching block ``(C, 1, B)``: the DMA
  engine must issue C strided row transfers per operand per grid step — the
  analog of the C narrow cached LSUs (paper Fig. 4, bottom).

Both views hand the kernel body an identical ``(C, B)`` tile, so a single body
serves every coarsening variant; only the *distribution* of work differs,
exactly as in the paper's Fig. 2.

The two competing mechanisms studied by the paper are also first-class:

* ``replication``  — pipeline replication (``num_compute_units``): the grid is
  split across R independent execution resources.  Within a chip this maps to
  parallel grid dimensions over TensorCores; across chips to `shard_map`.  The
  cost model charges replicas the *shared* HBM bandwidth, reproducing the
  paper's observation that replication only scales for compute-bound kernels.

* ``vector_width`` — SIMD vectorization (``num_simd_work_items``): the minor
  (lane) block dimension is widened V×.  Like the OpenCL compiler, we refuse to
  vectorize kernels with data-dependent control flow (`simd_ok=False`).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

KIND_NONE = "none"
KIND_CONSECUTIVE = "consecutive"
KIND_GAPPED = "gapped"
KINDS = (KIND_NONE, KIND_CONSECUTIVE, KIND_GAPPED)

# Default 1-D streaming block: 8 sublanes x 128 lanes of f32.
DEFAULT_BLOCK = 1024


@dataclasses.dataclass(frozen=True)
class CoarseningConfig:
    """The paper's (type, degree) pair plus the two competing mechanisms.

    kind:         none | consecutive | gapped       (paper §III.A)
    degree:       work-items fused per program      (paper degrees 2/4/8)
    replication:  pipeline-replication analog       (paper `num_compute_units`)
    vector_width: SIMD-vectorization analog         (paper `num_simd_work_items`)
    """

    kind: str = KIND_NONE
    degree: int = 1
    replication: int = 1
    vector_width: int = 1

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"kind must be one of {KINDS}, got {self.kind!r}")
        if self.kind == KIND_NONE and self.degree != 1:
            object.__setattr__(self, "degree", 1)
        if self.degree < 1 or self.replication < 1 or self.vector_width < 1:
            raise ValueError("degree/replication/vector_width must be >= 1")
        if self.kind != KIND_NONE and self.degree == 1:
            object.__setattr__(self, "kind", KIND_NONE)

    @property
    def label(self) -> str:
        bits = []
        if self.kind != KIND_NONE:
            bits.append(f"{'con' if self.kind == KIND_CONSECUTIVE else 'gap'}{self.degree}")
        if self.replication > 1:
            bits.append(f"pipe{self.replication}")
        if self.vector_width > 1:
            bits.append(f"simd{self.vector_width}")
        return "+".join(bits) if bits else "base"

    @staticmethod
    def parse(spec: str) -> "CoarseningConfig":
        """Parse e.g. 'consecutive:4', 'gapped:8', 'none', 'con4+pipe2'."""
        kind, degree, repl, vw = KIND_NONE, 1, 1, 1
        for part in spec.replace(",", "+").split("+"):
            part = part.strip().lower()
            if not part or part in ("none", "base"):
                continue
            if ":" in part:
                k, d = part.split(":")
                kind = {"con": KIND_CONSECUTIVE, "consecutive": KIND_CONSECUTIVE,
                        "gap": KIND_GAPPED, "gapped": KIND_GAPPED}[k]
                degree = int(d)
            elif part.startswith("con"):
                kind, degree = KIND_CONSECUTIVE, int(part[3:])
            elif part.startswith("gap"):
                kind, degree = KIND_GAPPED, int(part[3:])
            elif part.startswith("pipe"):
                repl = int(part[4:])
            elif part.startswith("simd"):
                vw = int(part[4:])
            else:
                raise ValueError(f"bad coarsening spec part: {part!r}")
        return CoarseningConfig(kind, degree, repl, vw)


@dataclasses.dataclass(frozen=True)
class StreamPlan:
    """Grid/Block plan for a coarsened 1-D stream of N elements.

    The stream is reshaped to a 3-D view whose middle/leading axes encode the
    coarsening distribution; the kernel body always sees a (degree, block)
    tile.
    """

    n: int                      # total elements
    block: int                  # base block (pre-coarsening work-item size)
    cfg: CoarseningConfig
    grid: int                   # programs launched
    view_shape: tuple           # reshaped array view
    block_shape: tuple          # BlockSpec block shape on the view
    index_map: Callable[..., tuple]
    # --- analysis metadata (the paper's LSU table analog) ---
    dmas_per_operand: int       # LSU count analog
    dma_elems: int              # elements per DMA transfer (LSU width analog)
    contiguous: bool

    @property
    def tile_shape(self) -> tuple:
        return (self.cfg.degree, self.block * self.cfg.vector_width)


def plan_stream(n: int, cfg: CoarseningConfig, block: int = DEFAULT_BLOCK) -> StreamPlan:
    """Build the grid/BlockSpec plan for a coarsened 1-D stream kernel."""
    block = block * cfg.vector_width              # SIMD analog: widen lanes
    c = cfg.degree
    if n % (block * c) != 0:
        raise ValueError(f"N={n} not divisible by degree*block={c * block}")
    grid = n // (block * c)
    if cfg.kind in (KIND_NONE, KIND_CONSECUTIVE):
        # view (G, C, B); program i fetches rows [i, :, :]  -> 1 contiguous DMA
        return StreamPlan(
            n=n, block=block, cfg=cfg, grid=grid,
            view_shape=(grid, c, block),
            block_shape=(1, c, block),
            index_map=lambda i: (i, 0, 0),
            dmas_per_operand=1, dma_elems=c * block, contiguous=True,
        )
    else:
        # view (C, G, B); program i fetches rows [:, i, :]  -> C strided DMAs
        return StreamPlan(
            n=n, block=block, cfg=cfg, grid=grid,
            view_shape=(c, grid, block),
            block_shape=(c, 1, block),
            index_map=lambda i: (0, i, 0),
            dmas_per_operand=c, dma_elems=block, contiguous=False,
        )


def stream_view(x: jax.Array, plan: StreamPlan) -> jax.Array:
    """Reshape a flat stream into the coarsening view (free: no data movement
    for the consecutive view; the gapped view is a (C, G*B) transpose of the
    logical order, realised lazily by XLA as a strided DMA pattern)."""
    c, g, b = plan.cfg.degree, plan.grid, plan.block
    if plan.contiguous:
        return x.reshape(plan.view_shape)
    # gapped: element (k, i, j) of the view is x[k*g*b + i*b + j] — i.e. the
    # stream is split into C equal segments and segment k contributes the k-th
    # row of every tile.  A pure reshape, no transpose: matches paper Fig. 2
    # ("divide work-items into C evenly distributed groups").
    return x.reshape(plan.view_shape)


def unstream_view(y: jax.Array, plan: StreamPlan) -> jax.Array:
    return y.reshape(plan.n)


def stream_specs(plan: StreamPlan, n_operands: int):
    """BlockSpecs for n_operands inputs + 1 output, all following the plan."""
    spec = pl.BlockSpec(plan.block_shape, plan.index_map)
    return [spec] * n_operands, spec


def pallas_stream_call(body: Callable, plan: StreamPlan, n_in: int,
                       out_dtype=jnp.float32, interpret: bool = True,
                       cost_estimate: pl.CostEstimate | None = None):
    """Build a pallas_call for a coarsened streaming kernel.

    ``body(*in_refs, out_ref)`` sees (1,C,B) [consecutive] or (C,1,B) [gapped]
    tiles; use ``tile(ref)`` to obtain the canonical (C,B) array.

    Pipeline replication (cfg.replication = R > 1) splits the grid into an
    outer R-way *parallel* dimension — the `num_compute_units` analog: on TPU
    the parallel dimension is distributed across TensorCores (declared via
    dimension_semantics; a no-op under interpret mode but preserved for the
    Mosaic lowering).
    """
    in_specs, out_spec = stream_specs(plan, n_in)
    kwargs: dict[str, Any] = {}
    if cost_estimate is not None:
        kwargs["cost_estimate"] = cost_estimate

    r = plan.cfg.replication
    if r > 1 and plan.grid % r == 0:
        inner = plan.grid // r
        grid = (r, inner)
        base_map = plan.index_map

        def remap(spec):
            return pl.BlockSpec(spec.block_shape,
                                lambda p, i: base_map(p * inner + i))

        in_specs = [remap(s) for s in in_specs]
        out_spec = remap(out_spec)
        from jax.experimental.pallas import tpu as pltpu
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
    else:
        grid = (plan.grid,)

    call = pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(plan.view_shape, out_dtype),
        interpret=interpret,
        **kwargs,
    )

    def run(*flat_inputs):
        views = [stream_view(x, plan) for x in flat_inputs]
        return unstream_view(call(*views), plan)

    return run


def flat_pid(plan: StreamPlan):
    """Flat grid position, replication-aware (kernel-body helper)."""
    r = plan.cfg.replication
    if r > 1 and plan.grid % r == 0:
        inner = plan.grid // r
        return pl.program_id(0) * inner + pl.program_id(1)
    return pl.program_id(0)


def tile(ref) -> jax.Array:
    """Canonical (C, B) tile from either coarsening view block."""
    x = ref[...]
    return x.reshape(x.shape[0] * x.shape[1], x.shape[2])


def untile(val: jax.Array, ref) -> None:
    ref[...] = val.reshape(ref.shape)


# ---------------------------------------------------------------------------
# 2-D (row-block) coarsening plans — used by matmul / attention / stencil,
# where coarsening fuses C row-blocks of the leading dimension.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RowPlan:
    rows: int
    block_rows: int
    cfg: CoarseningConfig
    grid: int
    fused_rows: int             # rows handled per program
    stride_blocks: int          # distance (in blocks) between fused blocks
    dmas_per_operand: int
    contiguous: bool


def plan_rows(rows: int, cfg: CoarseningConfig, block_rows: int) -> RowPlan:
    c = cfg.degree
    if rows % (block_rows * c) != 0:
        raise ValueError(f"rows={rows} not divisible by degree*block={c * block_rows}")
    grid = rows // (block_rows * c)
    if cfg.kind in (KIND_NONE, KIND_CONSECUTIVE):
        return RowPlan(rows, block_rows, cfg, grid, fused_rows=c * block_rows,
                       stride_blocks=1, dmas_per_operand=1, contiguous=True)
    return RowPlan(rows, block_rows, cfg, grid, fused_rows=c * block_rows,
                   stride_blocks=grid, dmas_per_operand=c, contiguous=False)


def row_starts(plan: RowPlan, i) -> list:
    """Starting row (in units of block_rows) of each fused block for program i."""
    c = plan.cfg.degree
    if plan.contiguous:
        return [i * c + k for k in range(c)]
    return [i + k * plan.grid for k in range(c)]
