"""Grouped matmul over stacked kernels, pallas-on-TPU: `x[rows of g] @ w[g]`
for every group g, the routed experts' three projections
(models/mla_moe.py through `quant.ragged_mm`).

Why not `jax.lax.ragged_dot` on the chip: the TPU compiler tiles it 512 rows
x 512 x 128, and an ingest dispatch brings ~135 rows an expert, so every row
tile is a quarter full and the expert's kernel is streamed again for each of
its column tiles (2.3 GB of DMA against 0.37 GB of weights, PERF.md section
5). Here the row tile fits the groups and the weight block stays put while
one expert's rows pass.

Design (after jax.experimental.pallas.ops.tpu.megablox.gmm, cut to what
this caller needs):
- rows arrive sorted by group; `group_sizes` [G] need not sum to m: rows
  past the last group belong to no group, no tile of theirs is visited and
  their output is whatever the buffer held (NaN included) — the caller
  discards them, as with `ragged_dot`.
- a VISIT is a (group, row tile) pair that holds at least one row. The
  visits are computed on the device from `group_sizes` (scalar prefetch) and
  the grid runs over them: at most m/128 + G - 1, as many as there really
  are. A tile two groups share is visited once by each; a visit stores only
  its own group's rows (row mask), the rest of the tile is kept.
- the whole [in, out] kernel of a group is one block in VMEM, in one of two
  buffers: the first visit of a group waits for its kernel and starts the
  copy of the NEXT group that has rows into the other buffer, so that copy
  runs under all of this group's row tiles (the pipeline's own fetch would
  start it under the last tile alone: 0.78 ms against 0.65 at the ingest
  shapes on the v5e, PERF.md section 3). An expert's kernel crosses HBM ->
  VMEM once per call; row and output tiles ride the ordinary pipeline.
- numerics: operands in their own dtype on the MXU, float32 accumulation,
  output in x.dtype — what `ragged_dot` gives the caller.
- `grouped_matmul` picks by what it can see: the compiled kernel on a `tpu`
  backend when the shapes tile (in and out multiples of 128, m a multiple of
  the row tile, both kernel buffers inside the VMEM budget), else
  `jax.lax.ragged_dot`. Either way the choice is counted,
  `moe.grouped_mm{path}`, once per traced call (flash_attention's
  `_announce` idiom), and the fallback says so in the log.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from symbiont_tpu.ops.flash_attention import _dot_prec
from symbiont_tpu.utils.telemetry import metrics

log = logging.getLogger(__name__)

ROW_TILE = 128
LANE = 128
# the v5e has 128 MiB of VMEM and the compiler's default scope is 16: the
# two kernel buffers (2 x 5.8 MB at the published widths in bfloat16) and
# the double-buffered row and output tiles must fit the budget, under the
# limit the call asks for
_VMEM_BUDGET_BYTES = 48 << 20
_VMEM_LIMIT_BYTES = 64 << 20


@functools.partial(jax.jit, static_argnums=1)
def visits(group_sizes: jax.Array, m: int):
    """The (group, row tile) pairs that hold rows, in row order, and what a
    visit needs to know of its group.

    -> (offsets [G+1] row at which each group starts, group_ids [V],
    tile_ids [V], buffer [G] which of the two kernel buffers a group with
    rows uses, after [G] the next group with rows or -1, count) with
    V = m/128 + G - 1 slots of which the first `count` are real; the rest
    are never run.

    Jitted, so the three projections of a layer trace and lower it once.
    Everything is a masked [., G] reduction: prefix sums over G an op each
    where a scan is a dozen, and what a visit takes from its group summed
    over a one-hot and not gathered (past 256 visits the TPU compiler
    unrolls a gather from a 64-entry table into a reduction per entry:
    1,500 more instructions a layer, seconds more to load the program).
    15 warmed programs trace, lower and load this at every boot, inside
    `setup_s`."""
    G = group_sizes.shape[0]
    tm = ROW_TILE
    g = jnp.arange(G, dtype=jnp.int32)
    upto = g[:, None] >= g[None, :]

    def prefix(a):
        return jnp.where(upto, a[None, :], 0).sum(1, dtype=jnp.int32)

    ends = prefix(group_sizes)
    first = (ends - group_sizes) // tm
    has_rows = group_sizes > 0
    tiles = jnp.where(has_rows, (ends + tm - 1) // tm - first, 0)
    tiles_upto = prefix(tiles)
    before = tiles_upto - tiles
    v = jnp.arange(m // tm + G - 1, dtype=jnp.int32)[:, None]
    mine = jnp.logical_and(v >= before[None, :], v < tiles_upto[None, :])

    def of_group(a):
        return jnp.where(mine, a[None, :], 0).sum(1, dtype=jnp.int32)

    tile_ids = v[:, 0] + of_group(first - before)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    buffer = (prefix(has_rows.astype(jnp.int32)) - 1) % 2
    after = jnp.where(jnp.logical_and(g[None, :] > g[:, None],
                                      has_rows[None, :]), g[None, :], G).min(1)
    return (offsets, of_group(g), jnp.clip(tile_ids, 0, m // tm - 1), buffer,
            jnp.where(after < G, after, -1), tiles_upto[-1])


def _kernel(offsets_ref, group_ids_ref, tile_ids_ref, buffer_ref, after_ref,
            x_ref, w_hbm, o_ref, w_buf, sem):
    v = pl.program_id(0)
    g = group_ids_ref[v]
    slot = buffer_ref[g]

    def fetch(group, into):
        return pltpu.make_async_copy(w_hbm.at[group], w_buf.at[into],
                                     sem.at[into])

    @pl.when(v == 0)
    def _():
        fetch(g, slot).start()

    @pl.when(jnp.logical_or(v == 0,
                            group_ids_ref[jnp.maximum(v - 1, 0)] != g))
    def _():
        # the group's first visit: its kernel was asked for one group ago.
        # The other buffer's group has passed: the next one's goes there
        fetch(g, slot).wait()
        nxt = after_ref[g]

        @pl.when(nxt >= 0)
        def _():
            fetch(nxt, 1 - slot).start()

    start, end = offsets_ref[g], offsets_ref[g + 1]
    row0 = tile_ids_ref[v] * ROW_TILE
    x, w = x_ref[...], w_buf[slot]
    y = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=_dot_prec(x, w)).astype(o_ref.dtype)
    whole = jnp.logical_and(row0 >= start, row0 + ROW_TILE <= end)

    @pl.when(whole)
    def _():
        o_ref[...] = y

    @pl.when(jnp.logical_not(whole))
    def _():
        # a tile shared with a neighbour group (or with rows of no group):
        # this visit's rows only, the others as the last visit left them
        row = row0 + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        o_ref[...] = jnp.where(jnp.logical_and(row >= start, row < end), y,
                               o_ref[...])


@jax.jit
def grouped_matmul_pallas(x: jax.Array, w: jax.Array, group_sizes: jax.Array
                          ) -> jax.Array:
    """The kernel itself. x [m, k] sorted by group, w [G, k, n],
    group_sizes [G] int32 -> [m, n] in x.dtype. Shapes must tile
    (`_tiles`)."""
    m, k = x.shape
    n = w.shape[2]
    *meta, count = visits(group_sizes.astype(jnp.int32), m)

    def at_tile(v, offsets, group_ids, tile_ids, buffer, after):
        return tile_ids[v], 0

    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(meta),
            grid=(count,),
            in_specs=[pl.BlockSpec((ROW_TILE, k), at_tile),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((ROW_TILE, n), at_tile),
            scratch_shapes=[pltpu.VMEM((2, k, n), w.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(x.size * x.dtype.itemsize
                            + w.size * w.dtype.itemsize
                            + m * n * x.dtype.itemsize)),
        name="grouped_matmul",
    )(*meta, x, w)


def _tiles(x, w) -> bool:
    m, k = x.shape
    n = w.shape[2]
    size = x.dtype.itemsize
    vmem = (2 * k * n + 2 * ROW_TILE * (k + n)) * size + ROW_TILE * n * 4
    return (m % ROW_TILE == 0 and k % LANE == 0 and n % LANE == 0
            and x.dtype == w.dtype and vmem <= _VMEM_BUDGET_BYTES)


def grouped_matmul(x: jax.Array, w: jax.Array, group_sizes: jax.Array
                   ) -> jax.Array:
    """x [m, k] (rows sorted by group) times the group's own kernel of
    w [G, k, n] -> [m, n] in x.dtype; rows past sum(group_sizes) are
    unspecified. The Pallas kernel on the chip where the shapes tile,
    `jax.lax.ragged_dot` everywhere else; `moe.grouped_mm{path}` says
    which, once per traced call."""
    path = ("pallas" if jax.default_backend() == "tpu" and _tiles(x, w)
            else "ragged_dot")
    metrics.inc("moe.grouped_mm", labels={"path": path})
    if path == "ragged_dot":
        log.info("grouped_matmul: ragged_dot for x%s w%s %s on %s",
                 tuple(x.shape), tuple(w.shape), x.dtype,
                 jax.default_backend())
        return jax.lax.ragged_dot(x, w, group_sizes)
    return grouped_matmul_pallas(x, w, group_sizes)
