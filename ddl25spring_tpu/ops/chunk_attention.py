"""A prefill chunk's latent attention over per-head K and V as one forward
Pallas TPU kernel.

``models/latent.py::attend_expanded`` expands the gathered latent rows to
per-head keys and values (an XLA product, ``rows @ w_kvb``) and then, a
block of queries at a time, writes the float32 scores of all heads over the
table's whole padded width to HBM and reads them back three times (mask and
maximum, exponent and sum, ``PV``). This kernel is those three operations
and the loop over query blocks, nothing before them: it is handed the
expansion's result as it lies and the rows' rotated part, keeps the scores
in VMEM, and stops at the chunk's live keys.

Design (see /opt/skills/guides/pallas_guide.md), the pattern of
``ops/flash_attention.py::_fwd_kernel`` and ``_causal_kv_index``:
- grid = (slot, head, block of queries, block of keys); the last axis runs
  sequentially, so a query block's running maximum, sum and weighted sum
  live in VMEM scratch across its key blocks (an online softmax). With all
  of a chunk's queries in one block (``blocks``), a head's K and V are read
  once a chunk and not once a query block.
- No operand is laid out anew for the kernel but the queries (small). The
  expansion's result ``kv`` is taken as the product leaves it, [S, K, H
  (nope + v)] (on the chip its reshape to [S, K, H, nope + v] and back is
  a copy of the whole): a head's un-rotated keys and its values are the
  lanes ``[h (nope + v), (h + 1) (nope + v))`` of a position's row, one
  block of whole lane tiles, split at ``nope`` in VMEM. The rotated key
  part ``k_rope`` [S, K, rope], which all heads share, is an operand of its
  own; its scores are added to the un-rotated part's in float32. No ``[K,
  H, nope + rope]`` concatenation and no slice of the values exist. The
  output is written as [S, T, H v], the layout ``w_o`` wants.
- Live keys: ``bounds`` says, a slot and a query block, the last key block
  any of its queries may see (the chunk's live length and the block's
  largest position, whichever is less); it rides in as a prefetched scalar.
  A grid step past it is predicated out (``pl.when``: no arithmetic) and
  its index map names the block the step before it fetched, so the pipeline
  elides the copy. One compiled program serves every offset.
- Numerics: operands of both products as given (bf16 in the serving cell),
  scores accumulated and scaled in float32, mask by absolute position
  (``k <= q``), running maximum, exponent and running sum in float32; the
  exponent is cast to the values' dtype only as the operand of ``PV``,
  whose accumulator is float32; one division by the sum in float32 at the
  end, one cast of the output. What is reordered against
  ``latent._masked_softmax`` is the running maximum, and the exponent is
  rounded before the division where the XLA form rounds the quotient: the
  same 8 bits (PERF.md, PR 33, has both against float32). Every query row
  sees key 0, so no row's sum is 0: a padding row of a final chunk gives
  finite numbers.
- ``interpret`` as in ``ops/paged_attention.py``: None follows the backend
  (compiled on a TPU, interpreted elsewhere).

``ops/flash_attention.py`` holds a second forward kernel of this family:
square, with a backward, the training cell's. The two are kept apart until
ROADMAP S5 has settled that kernel's blocks (ROADMAP D15).
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
from jax import lax

# As in ``ops/paged_attention.py``: keep the Mosaic GPU interpreter, which
# nothing here runs, out of the serving process's imports (``setup_s``).
sys.modules.setdefault("jax._src.pallas.mosaic_gpu.interpret", None)
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

_LANES = 128
_NEG_INF = -1e30
# Swept on a v5e at A.X-K1's shape (64 heads, 192 | 128, 512 queries at an
# offset, 1024 to 8192 live keys; PERF.md, PR 33).
_BLOCK_Q = 512
_BLOCK_K = 1024


def blocks(t: int, k_len: int) -> tuple:
    """(block of queries, block of keys) for ``t`` query rows over ``k_len``
    key positions: the largest that divide them, up to the swept sizes."""
    return (_BLOCK_Q if t % _BLOCK_Q == 0 else t,
            _BLOCK_K if k_len % _BLOCK_K == 0 else k_len)


def supported(t: int, k_len: int, nope_dim: int, rope_dim: int, v_dim: int,
              dtype) -> bool:
    """Whether the compiled kernel takes this shape: a head's keys and
    values are whole lane tiles of ``kv``'s row, and the blocks whole tiles
    (lanes of 128, sublanes of 8 x 4 / itemsize) no larger than the swept
    sizes, which is what VMEM was seen to hold."""
    sublanes = 8 * 4 // jnp.dtype(dtype).itemsize
    block_q, block_k = blocks(t, k_len)
    return (nope_dim % _LANES == 0 and v_dim % _LANES == 0
            and rope_dim % sublanes == 0
            and block_q % sublanes == 0 and block_q <= _BLOCK_Q
            and block_k % _LANES == 0 and block_k <= _BLOCK_K)


def bounds(q_positions: jnp.ndarray, live: jnp.ndarray, block_q: int,
           block_k: int) -> jnp.ndarray:
    """The last key block each block of queries visits, [S, T / block_q]:
    that of the last key any of its queries may see, which is the slot's
    last live key (``live`` [S] keys are live) or the block's largest
    position, whichever comes first."""
    s, t = q_positions.shape
    top = jnp.max(q_positions.reshape(s, t // block_q, block_q), axis=-1)
    last_key = jnp.minimum(top, live[:, None] - 1)
    return (jnp.maximum(last_key, 0) // block_k).astype(jnp.int32)


def _kernel(last_ref, qn_ref, qr_ref, pos_ref, kv_ref, kr_ref, o_ref,
            m_ref, l_ref, acc_ref, *, block_k: int, nope_dim: int,
            scale: float):
    s, iq, ik = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ik <= last_ref[s, iq])
    def _live_block():
        kv = kv_ref[...]                                  # [bk, nope + v]
        v = kv[:, nope_dim:]
        nt = (((1,), (1,)), ((), ()))
        sc = (lax.dot_general(qn_ref[...], kv[:, :nope_dim], nt,
                              preferred_element_type=jnp.float32)
              + lax.dot_general(qr_ref[...], kr_ref[...], nt,
                                preferred_element_type=jnp.float32)) * scale
        kpos = ik * block_k + lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where(pos_ref[...] >= kpos, sc, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        acc_ref[...] = alpha * acc_ref[...] + lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(ik == pl.num_programs(3) - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def chunk_attention(q: jnp.ndarray, kv: jnp.ndarray, k_rope: jnp.ndarray,
                    q_positions: jnp.ndarray, live: jnp.ndarray, *,
                    nope_dim: int, scale: float, block_q: int | None = None,
                    block_k: int | None = None,
                    interpret: bool | None = None) -> jnp.ndarray:
    """q [S, T, H, nope + rope] (rotated) at absolute ``q_positions`` [S, T]
    over the first ``live`` [S] key positions of ``kv`` [S, K, H (nope + v)]
    (a head's un-rotated keys beside its values, as the expansion's
    product leaves them) and ``k_rope`` [S, K, rope] (the rotated key part
    all heads share): softmax over the keys ``k <= q_position`` of
    ``(q_nope k_nope + q_rope k_rope) scale``, times the values. Returns
    [S, T, H, v] in q's dtype. Key blocks past the live length are not
    read; within the last live block the mask is the position's alone, as
    in ``latent._masked_softmax``."""
    s, t, h, _ = q.shape
    k_len, kv_dim = kv.shape[1], kv.shape[2] // h
    v_dim = kv_dim - nope_dim
    if block_q is None or block_k is None:
        block_q, block_k = blocks(t, k_len)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    last = bounds(q_positions, live.astype(jnp.int32), block_q, block_k)

    def q_spec(d):
        return pl.BlockSpec(
            (None, None, block_q, d),
            lambda s_, h_, iq, ik, last_ref: (s_, h_, iq, 0))

    def k_spec(d, lane_block):
        # a step past the last live block names that block again: no copy
        return pl.BlockSpec(
            (None, block_k, d), lambda s_, h_, iq, ik, last_ref:
            (s_, jnp.minimum(ik, last_ref[s_, iq]), lane_block(h_)))

    qh = q.transpose(0, 2, 1, 3)                          # [S, H, T, qk]
    out = pl.pallas_call(
        functools.partial(_kernel, block_k=block_k, nope_dim=nope_dim,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, h, t // block_q, k_len // block_k),
            in_specs=[
                q_spec(nope_dim), q_spec(q.shape[3] - nope_dim),
                pl.BlockSpec(
                    (None, block_q, 1),
                    lambda s_, h_, iq, ik, last_ref: (s_, iq, 0)),
                k_spec(kv_dim, lambda h_: h_),
                k_spec(k_rope.shape[2], lambda h_: 0),
            ],
            out_specs=pl.BlockSpec(
                (None, block_q, v_dim),
                lambda s_, h_, iq, ik, last_ref: (s_, iq, h_)),
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),     # m
                pltpu.VMEM((block_q, _LANES), jnp.float32),     # l
                pltpu.VMEM((block_q, v_dim), jnp.float32),      # acc
            ]),
        out_shape=jax.ShapeDtypeStruct((s, t, h * v_dim), q.dtype),
        interpret=interpret,
        name="chunk_attention",
    )(last, qh[..., :nope_dim], qh[..., nope_dim:],
      q_positions.astype(jnp.int32)[..., None],
      kv, k_rope)
    return out.reshape(s, t, h, v_dim)
