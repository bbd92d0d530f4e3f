"""One step of the state-space recurrence over the whole state store as one
Pallas TPU kernel.

The decode program of a model with a state-space mixer
(``models/state_space.py``) advances every decoding slot's state by one
token: per head ``j`` of group ``g = j // (heads / groups)``

    S' = exp(dt a) S + (dt x) (outer) B_g        S: [head_dim, state]
    y  = S' C_g

over a store ``[L, slots, heads, head_dim, state]`` in float32 that the
layer scan carries (``kvcache.init_state``). XLA makes two passes over a
layer's state there, one that reads it for ``y`` and one that reads it again
and writes ``S'`` into the store (PERF.md section 5). This kernel reads each
block of the state once into VMEM, forms ``S'`` and ``y`` from it, and writes
``S'`` back to the place it came from.

Design:
- The store is handed WHOLE and aliased from input to output
  (``input_output_aliases``): the layer number rides in as a prefetched
  scalar, and no slice of the store is made (a slice of one layer is a copy
  of it and a write back: PERF.md section 6).
- The walk is a flat list of visits, (decoding slot, block of heads), over
  the decoding slots only (``plan``: their numbers first, then the rest;
  the number of visits, a traced scalar, is the grid's extent). A slot that
  is not decoding is not visited: its state is neither read nor written and
  keeps its bits. Where no slot decodes the one visit copies a block onto
  itself.
- A block is ``heads_per_block`` heads of one slot, ``[hb, P, N]``
  (``tiling``: 2 MiB, 16 heads at Falcon-H1's widths), within one group of B
  and C or over whole groups, so that one copy of the group's rows serves
  it. The pipeline's double buffering copies the next block while this one
  is computed. On a v5e the kernel takes what a plain copy of the same
  blocks takes, whatever the heads a block from 4 to 32 (PERF.md
  section 6).
- The arithmetic is ``scan_step``'s, elementwise in float32: the decay
  ``exp(dt a)`` and ``dt x`` are formed outside (as ``scan_step`` forms
  them), the sum over the state for ``y`` is a lane reduction on the vector
  unit. The ``D x`` skip, the gate, the norm and ``W_out`` stay with the
  caller.
- ``interpret`` as in ``ops/paged_attention.py``: None follows the backend
  (compiled on a TPU, interpreted elsewhere).
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

# As ``ops/paged_attention.py``: the Mosaic GPU interpreter, which no TPU
# and no TPU interpret mode runs, is not imported on the way to the window.
sys.modules.setdefault("jax._src.pallas.mosaic_gpu.interpret", None)
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

_LANES = 128
_BLOCK_BYTES = 2 * 1024 * 1024    # the most a block of the state may hold


def supported(p: int, n: int, dtype) -> bool:
    """Whether the compiled kernel takes a state of ``[p, n]`` a head in
    ``dtype``: float32, both widths whole multiples of 128 lanes."""
    return (jnp.dtype(dtype) == jnp.float32 and p % _LANES == 0
            and n % _LANES == 0)


def tiling(heads: int, groups: int, p: int, n: int) -> int:
    """Heads a block: the most whose block ``[hb, p, n]`` float32 holds at
    most 2 MiB, among the divisors of a group's heads and the multiples of
    them that divide ``heads`` (a block lies in one group or over whole
    ones); one head where even that is larger."""
    per_group = heads // groups
    fits = [hb for hb in range(1, heads + 1)
            if heads % hb == 0 and (per_group % hb == 0 or hb % per_group == 0)
            and hb * p * n * 4 <= _BLOCK_BYTES]
    return max(fits, default=1)


def plan(decoding: jnp.ndarray) -> tuple:
    """The slots a step visits, from ``decoding`` [S] bool: (the slot
    numbers, the decoding ones first in order, [S] int32; how many decode,
    [1] int32; ``decoding``). Entries past the count are never visited."""
    order = jnp.argsort(~decoding, stable=True).astype(jnp.int32)
    return order, jnp.sum(decoding, dtype=jnp.int32).reshape(1), decoding


def _kernel(layer_ref, order_ref, count_ref, decay_ref, dtx_ref, b_ref, c_ref,
            s_ref, so_ref, y_ref):
    del layer_ref, order_ref
    s = s_ref[...]                                          # [hb, P, N]

    @pl.when(count_ref[0] > 0)
    def _step():
        gb = b_ref.shape[0]                                 # groups a block
        sg = s.reshape((gb, -1) + s.shape[1:])
        decay = decay_ref[...].reshape(sg.shape[:2] + (1, 1))
        dtx = dtx_ref[...].reshape(sg.shape[:3] + (1,))
        s1 = sg * decay + dtx * b_ref[...][:, None]
        so_ref[...] = s1.reshape(s.shape)
        y_ref[...] = jnp.sum(s1 * c_ref[...][:, None], axis=-1
                             ).reshape(y_ref.shape)

    @pl.when(count_ref[0] == 0)
    def _keep():
        so_ref[...] = s


def state_step(store: jnp.ndarray, layer, x: jnp.ndarray, dt: jnp.ndarray,
               a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray, walk: tuple,
               *, heads_per_block: int | None = None,
               interpret: bool | None = None):
    """One step of the recurrence for the decoding slots of ``walk``
    (``plan``), in layer ``layer`` (a scalar) of ``store`` [L, S, H, P, N]:
    x [S, H, P], dt [S, H], a [H], b, c [S, G, N], all in the store's type
    -> (y [S, H, P], 0 for a slot that is not decoding; the store with that
    layer's decoding slots advanced, every other row as it was).
    ``heads_per_block``: else ``tiling``'s."""
    _, s, h, p, n = store.shape
    g = b.shape[1]
    hb = heads_per_block or tiling(h, g, p, n)
    blocks = h // hb
    gb = max(1, hb // (h // g))                 # groups a block spans
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    order, count, decoding = walk
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)
    # per block of heads, so that a block's rows are whole dimensions
    decay = jnp.exp(dt * a).reshape(s, blocks, hb, 1)
    dtx = (dt[..., None] * x).reshape(s, blocks, hb, p)

    def heads(v, layer, order, count):
        return (order[v // blocks], v % blocks, 0, 0)

    def state(v, layer, order, count):
        return (layer[0], order[v // blocks], v % blocks, 0, 0)

    def group(v, layer, order, count):
        return (order[v // blocks], (v % blocks) * hb // (h // g) // gb, 0, 0)

    block = hb * p * n * store.dtype.itemsize
    new, y = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(jnp.maximum(count[0], 1) * blocks,),
            in_specs=[
                pl.BlockSpec((None, None, hb, 1), heads),
                pl.BlockSpec((None, None, hb, p), heads),
                pl.BlockSpec((None, gb, 1, n), group),
                pl.BlockSpec((None, gb, 1, n), group),
                pl.BlockSpec((None, None, hb, p, n), state),
            ],
            out_specs=[pl.BlockSpec((None, None, hb, p, n), state),
                       pl.BlockSpec((None, None, hb, p), heads)]),
        out_shape=[jax.ShapeDtypeStruct(store.shape, store.dtype),
                   jax.ShapeDtypeStruct((s, blocks, hb, p), store.dtype)],
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # two buffers of the block in and out, and the step's values
            vmem_limit_bytes=max(32 * 1024 * 1024, 8 * block)),
        cost_estimate=pl.CostEstimate(
            flops=5 * s * h * p * n, transcendentals=0,
            bytes_accessed=2 * s * h * p * n * store.dtype.itemsize),
        interpret=interpret,
        name="state_step",
    )(layer, order, count, decay, dtx, b[:, :, None], c[:, :, None], store)
    # a slot that is not decoding was not visited: its rows of y hold
    # nothing that was computed
    return jnp.where(decoding[:, None, None], y.reshape(s, h, p), 0), new
