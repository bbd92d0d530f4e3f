"""The routed experts' grouped product as one Pallas TPU kernel.

``models/experts.py`` sorts a layer's token-expert pairs by expert into a
buffer of rows ``x`` [M, D] and multiplies each group of rows by its
expert's weights ``w[g]`` [D, N], the rows behind the last group (idle
slots, padding, pairs of experts held elsewhere) by nothing. XLA's
``lax.ragged_dot`` costs about the same for each group at the expert
layers' widths whatever its rows, some 2.8 times the time its weights take
to stream at a v5e's bandwidth (PERF.md section 5). A decode step
gives an expert some 16 rows, so the product is a stream of weights: this
kernel reads each hit expert's weights once, in large tiles whose copies
overlap the products before them.

Design:
- The walk is a flat list of visits, (group, tile of ``tm`` rows), over the
  groups that hold rows only, reckoned from the group sizes once a call
  (``plan``): a group whose rows straddle two row tiles visits both, and a
  row tile that holds several groups is visited once for each, in
  consecutive steps. Rows behind the last group lie in no visit and are not
  computed: what they hold is unspecified, NaN included.
- The grid is (column tile, visit), the visits innermost, so that for one
  column tile each group's weight tile ``[D, tn]`` is copied once and kept
  while the group's visits last, and a row tile ``[tm, D]`` is copied once
  while the visits to it last. Each hit expert's weights are read once
  over the whole grid; the rows once a column tile. The pipeline's double
  buffering copies the next visit's tiles while this one is computed. The
  number of visits is the grid's second extent, a traced scalar: no dead
  step, and the kernel's code is the same whatever the groups.
- The contraction is whole (``D``, one product a visit): float32
  accumulation over all of it, as ``ragged_dot``, and the result written in
  the input's dtype (no float32 copy leaves the kernel). A visit writes the
  rows of its group in the output tile and leaves the tile's other rows as
  they are: those of the groups before it were written by the visit before.
- The stack of weights is handed WHOLE, ``[groups in the stack, D, N]``,
  and ``group_offset`` names where this call's groups stand in it (a layer
  of a scan over several layers' experts, ``models/experts.py``): it rides
  in with the plan as a prefetched scalar, and no slice of the stack is
  made (a slice there is a copy of every weight: PERF.md section 6).
- Tiles (``tiling``): ``tm`` 128 rows, ``tn`` the widest whole tile of
  columns whose weight tile is at most 4 MiB (``_TILE_BYTES``), measured at
  the LFM2 cell's two products (PERF.md section 6).
- ``interpret`` as in ``ops/paged_attention.py``: None follows the backend
  (compiled on a TPU, interpreted elsewhere).
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
from jax import lax

# As ``ops/paged_attention.py``: the Mosaic GPU interpreter, which no TPU
# and no TPU interpret mode runs, is not imported on the way to the window.
sys.modules.setdefault("jax._src.pallas.mosaic_gpu.interpret", None)
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

_LANES = 128
_ROWS = 128                     # ``tm``: rows a visit
_SUBLANES = 16                  # a bf16 tile's rows; a short buffer's multiple
_TILE_BYTES = 4 * 1024 * 1024   # the most a weight tile [D, tn] may hold


def supported(d: int, n: int, dtype) -> bool:
    """Whether the compiled kernel takes a product of rows ``[M, d]`` by
    weights ``[groups, d, n]`` in ``dtype``: bf16, and ``d`` and ``n``
    whole multiples of 128 lanes."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and d % _LANES == 0
            and n % _LANES == 0)


def tiling(rows: int, d: int, n: int, dtype) -> tuple:
    """``(tm, tn)`` for ``rows`` rows by ``[d, n]`` weights: 128 rows (fewer,
    in whole tiles of 16, for a shorter buffer), and the widest multiple of
    128 that divides ``n`` and keeps a weight tile within 4 MiB; ``n`` whole
    where it is no multiple of 128 (interpret mode at small widths)."""
    tm = min(_ROWS, -(-rows // _SUBLANES) * _SUBLANES)
    if n % _LANES:
        return tm, n
    cap = max(_LANES, _TILE_BYTES // (d * jnp.dtype(dtype).itemsize))
    tn = max(t for t in range(_LANES, n + 1, _LANES)
             if n % t == 0 and (t <= cap or t == _LANES))
    return tm, tn


def plan(sizes: jnp.ndarray, rows: int, tm: int) -> tuple:
    """The visits of a call over ``rows`` rows in tiles of ``tm`` whose
    groups hold ``sizes`` [G] rows each, in order from row 0: (group of each
    visit, row tile of each visit, both [rows / tm + G - 1], the number of
    visits, at least 1 [1], the groups' row bounds [G + 1]). Entries past
    the number of visits are never read. Where no group holds a row the one
    visit is to group G - 1 with no row of its own, so it writes nothing."""
    g = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    count = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    done = jnp.cumsum(count)
    v = jnp.arange(rows // tm + g - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(done, v, side="right"),
                        g - 1).astype(jnp.int32)
    tile = jnp.minimum(first[group] + v - (done - count)[group],
                       rows // tm - 1).astype(jnp.int32)
    bounds = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return group, tile, jnp.maximum(done[-1:], 1), bounds


def _kernel(group_ref, tile_ref, bounds_ref, offset_ref, x_ref, w_ref,
            o_ref, *, tm: int):
    del offset_ref
    v = pl.program_id(1)
    g = group_ref[v]
    row = tile_ref[v] * tm + lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
    mine = (row >= bounds_ref[g]) & (row < bounds_ref[g + 1])
    y = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    o_ref[...] = jnp.where(mine, y, o_ref[...].astype(jnp.float32)
                           ).astype(o_ref.dtype)


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray, sizes: jnp.ndarray,
                   group_offset=None, *, tiles: tuple | None = None,
                   interpret: bool | None = None) -> jnp.ndarray:
    """Rows ``x`` [M, D] by groups: rows ``[sum(sizes[:g]), sum(sizes[:g+1]))``
    times ``w[group_offset + g]`` [D, N] for each of the ``G`` groups of
    ``sizes`` [G] int32. ``w`` is the whole stack [G' >= group_offset + G,
    D, N]; ``group_offset`` a scalar (None: 0). Returns [M, N] in x's dtype,
    accumulated in float32; rows behind the last group hold anything.
    ``tiles``: ``(tm, tn)``, else ``tiling``'s."""
    m, d = x.shape
    n = w.shape[-1]
    tm, tn = tiles or tiling(m, d, n, x.dtype)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows = -(-m // tm) * tm
    if rows != m:
        x = jnp.pad(x, ((0, rows - m), (0, 0)))
    group, tile, visits, bounds = plan(sizes, rows, tm)
    offset = jnp.reshape(0 if group_offset is None else group_offset,
                         (1,)).astype(jnp.int32)
    item = x.dtype.itemsize
    # two buffers of each tile, and the product in float32
    vmem = 2 * (tm * d + d * tn + tm * tn) * item + tm * tn * 4
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, visits[0]),
            in_specs=[
                pl.BlockSpec((tm, d), lambda j, v, grp, t, *_: (t[v], 0)),
                pl.BlockSpec((None, d, tn),
                             lambda j, v, grp, t, b, off: (off[0] + grp[v],
                                                           0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, grp, t, *_: (t[v], j))),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 * 1024 * 1024, 2 * vmem)),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * d * n, transcendentals=0,
            bytes_accessed=(rows * d + sizes.shape[0] * d * n + rows * n)
            * item),
        interpret=interpret,
        name="grouped_matmul",
    )(group, tile, bounds, offset, x, w)
    return out if rows == m else out[:m]
