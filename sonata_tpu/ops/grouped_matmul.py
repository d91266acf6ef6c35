"""A grouped matmul tiled for few rows a group: the expert products.

``grouped_matmul(x, w, sizes)`` means what ``jax.lax.ragged_dot`` means:
the rows of ``x`` ``[M, K]`` lie sorted by group, ``sizes`` ``[G]`` says how
many each group has, and row ``i`` of group ``g`` comes back as
``x[i] @ w[g]`` (``w`` ``[G, K, N]``), accumulated in float32.  Rows after
the last group come back as anything, finite or not: the caller masks them.

A unit voice's expert layer hands it 4 to 17 rows a group where a group's
matrix is 3 to 13 MB, so the product is a stream of weights and what has
to be right is how they leave HBM: once per touched group, in blocks of
megabytes.  The kernel walks a list of *visits*, one per (group, row tile
the group touches), built on the device from ``sizes`` and prefetched as
scalars; the weight block's index map reads the visit's group, so a group
no row chose is never read and one that spans two row tiles is read once
(the block index does not change between its visits).  A visit multiplies
the whole row tile and keeps the rows that are the group's.

Which tiles, and whether the kernel runs at all, is ``tile_rule``: a pure
function of the shape.  Off a TPU the function *is* ``lax.ragged_dot``.

**Widths the lanes do not divide** (an expert width of 1856 = 14.5 x 128).
A block that spans a whole dimension needs no multiple of 128, and the
kernel compiles for a v5e at ``[2688, 1856]``; but XLA keeps an array
``[G, 2688, 1856]`` with the 2688 minor-most, so as not to pad the lanes, and
a kernel that wants it row-major is handed a copy of **all** ``G`` matrices
every call (660 MB of temporaries at 64 groups, compiled for a v5e: PERF.md
§5), which is the stream this kernel exists to avoid.  So the rule stays:
such a width gets no tiles, and whoever lays the weights out pads the width
to whole lanes once (:func:`lanes`; zero columns of an up matrix and zero
rows of a down matrix add nothing where the form between them maps 0 to 0).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: one v5e chip, as ``perfbench/harness/peaks.json`` has it
HBM_BYTES_PER_S = 819e9
MXU_FLOPS_PER_S = 197e12
#: what the kernel may hold in VMEM: two weight blocks, two row tiles, two
#: output tiles (the pipeline double-buffers each); a v5e has 128 MiB
VMEM_BUDGET = 40 * 2 ** 20
#: the tallest row tile: the MXU's own 128 rows.  A taller one multiplies
#: more than it streams; a shorter one makes more visits and won nothing
#: at 4 or at 17 rows a group (PERF.md §5)
ROW_TILE = 128


def lanes(n: int) -> int:
    """``n`` up to a whole number of the 128 lanes."""
    return -(-n // 128) * 128


class Tiles(NamedTuple):
    tm: int     #: rows of a row tile
    tn: int     #: columns of a weight block


def max_visits(rows: int, groups: int, tm: int) -> int:
    """The most (group, row tile) pairs ``groups`` groups over ``rows`` rows
    can touch: every tile once, and once more for each group that starts
    inside one."""
    return -(-rows // tm) + groups - 1


def vmem_bytes(tiles: Tiles, k: int, x_bytes: int, w_bytes: int) -> int:
    tm, tn = tiles
    return 2 * (k * tn * w_bytes + tm * k * x_bytes + tm * tn * 4)


def mxu_seconds(rows: int, groups: int, k: int, n: int, tm: int) -> float:
    """The masked multiply at its worst: every visit a whole row tile."""
    return max_visits(rows, groups, tm) * 2.0 * tm * k * n / MXU_FLOPS_PER_S


def stream_seconds(rows: int, groups: int, k: int, n: int,
                   w_bytes: int) -> float:
    """The weights of the groups ``rows`` rows can touch, at the memory's
    pace."""
    return min(rows, groups) * k * n * w_bytes / HBM_BYTES_PER_S


@functools.lru_cache(maxsize=None)
def tile_rule(rows: int, groups: int, k: int, n: int,
              dtype) -> Optional[Tiles]:
    """The kernel's tiles for a product of ``rows`` rows on ``groups``
    groups of ``[k, n]``, or None where ``lax.ragged_dot`` stays: a pure
    function of the shape (PERF.md §5 has the table it was read from).

    The row tile is ``ROW_TILE`` (fewer rows: all of them, in sublanes of
    16); ``tn`` is the largest divisor of ``n`` in lanes of 128 whose blocks
    fit ``VMEM_BUDGET``: the whole ``n`` at a unit voice's widths, so that
    a weight block is a group's whole matrix, 3 to 13 MB in one piece.  The
    kernel is for products that are weight streams: where the masked
    multiply at its worst would outlast the weights' streaming (about 100
    rows a group and up), and at widths the lanes do not divide, XLA's own
    product stays."""
    x_bytes = w_bytes = jnp.dtype(dtype).itemsize
    if n % 128 or k % 128 or rows < 1 or groups < 1:
        return None
    tm = min(ROW_TILE, -(-rows // 16) * 16)
    if mxu_seconds(rows, groups, k, n, tm) > stream_seconds(
            rows, groups, k, n, w_bytes):
        return None
    for tn in range(n, 0, -128):
        tiles = Tiles(tm, tn)
        if n % tn == 0 and vmem_bytes(tiles, k, x_bytes,
                                      w_bytes) <= VMEM_BUDGET:
            return tiles
    return None


def visit_list(sizes, rows: int, tm: int):
    """From ``sizes`` ``[G]``: the groups' row offsets ``[G + 1]``, each
    visit's group and row tile ``[V]`` (V = ``max_visits``; the visits past
    the last one repeat it, so they fetch nothing), and the count of
    visits ``[1]``."""
    groups = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    count = visit_ends[-1]
    v = jnp.minimum(jnp.arange(max_visits(rows, groups, tm)),
                    jnp.maximum(count - 1, 0))
    group = jnp.minimum(jnp.searchsorted(visit_ends, v, side="right"),
                        groups - 1)
    tile = first[group] + v - (visit_ends[group] - tiles[group])
    # no visit at all: tile 0 of group 0, whose body is skipped
    tile = jnp.where(count > 0, tile, 0)
    offsets = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends])
    return (offsets.astype(jnp.int32), group.astype(jnp.int32),
            tile.astype(jnp.int32), count.reshape(1).astype(jnp.int32))


def _kernel(offsets, group, tile, count, x_ref, w_ref, o_ref):
    v = pl.program_id(1)
    tm = o_ref.shape[0]

    @pl.when(v < count[0])
    def _visit():
        g, t = group[v], tile[v]
        row = t * tm + lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        acc = jnp.dot(x_ref[...], w_ref[...],
                      preferred_element_type=jnp.float32)
        # a tile's visits follow each other: the first one starts it
        first = (v == 0) | (tile[jnp.maximum(v - 1, 0)] != t)

        @pl.when(first)
        def _start():
            o_ref[...] = jnp.where(mine, acc, 0.0).astype(o_ref.dtype)

        @pl.when(jnp.logical_not(first))
        def _merge():
            o_ref[...] = jnp.where(mine, acc, o_ref[...]).astype(o_ref.dtype)


def grouped_matmul_kernel(x, w, sizes, tiles: Tiles, *,
                          preferred_element_type=jnp.float32,
                          interpret: bool = False):
    """The kernel itself, whatever the backend (``interpret`` for the
    CPU): ``x`` ``[M, K]``, ``w`` ``[G, K, N]``, ``sizes`` ``[G]``."""
    m, k = x.shape
    n = w.shape[2]
    tm, tn = tiles
    if n % tn:
        raise ValueError(f"tn = {tn} does not divide n = {n}")
    pad = -m % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    rows = m + pad
    meta = visit_list(sizes.astype(jnp.int32), rows, tm)
    w_bytes = jnp.dtype(w.dtype).itemsize
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((rows, n), preferred_element_type),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, meta[1].shape[0]),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, o, g, t, c: (t[v], 0)),
                pl.BlockSpec((None, k, tn),
                             lambda j, v, o, g, t, c: (g[v], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, o, g, t, c: (t[v], j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BUDGET + 8 * 2 ** 20),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=(min(rows, w.shape[0]) * k * n * w_bytes
                            + rows * k * x.dtype.itemsize + rows * n * 4)),
        name="grouped_matmul",
        interpret=interpret,
    )(*meta, x, w)
    return out[:m] if pad else out


def _tiles_here(rows: int, groups: int, k: int, n: int,
                dtype) -> Optional[Tiles]:
    """``tile_rule``'s tiles on a TPU, None on every other backend."""
    if jax.default_backend() != "tpu":
        return None
    return tile_rule(rows, groups, k, n, jnp.dtype(dtype))


def implementation(rows: int, groups: int, k: int, n: int, dtype) -> str:
    """``"grouped"`` where ``grouped_matmul`` runs the kernel at this shape
    on this backend, else ``"ragged_dot"``: what the spans report."""
    return ("ragged_dot" if _tiles_here(rows, groups, k, n, dtype) is None
            else "grouped")


def grouped_matmul(x, w, sizes, *, preferred_element_type=jnp.float32):
    """``lax.ragged_dot(x, w, sizes)``: by this module's kernel where the
    backend is a TPU and ``tile_rule`` has tiles for the shape."""
    tiles = _tiles_here(x.shape[0], w.shape[0], x.shape[1], w.shape[2],
                        x.dtype)
    if tiles is None:
        return lax.ragged_dot(x, w, sizes,
                              preferred_element_type=preferred_element_type)
    return grouped_matmul_kernel(
        x, w, sizes, tiles, preferred_element_type=preferred_element_type)
