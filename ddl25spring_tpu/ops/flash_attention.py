"""Fused causal attention (FlashAttention) as Pallas TPU kernels, fwd + bwd.

Capability/perf target: the reference computes attention inside simplellm's
torch modules (materializing the full [T, T] score matrix per head). On TPU
the memory-bound step is HBM traffic for those scores; these kernels stream
K/V blocks through VMEM with the online-softmax recurrence so scores never
leave the chip, and the matmuls hit the MXU.

The op is differentiable via ``jax.custom_vjp``: the forward kernel saves the
per-row logsumexp (LSE) alongside the output, and the backward pass recomputes
attention probabilities block-wise from (q, k, lse) — the standard
FlashAttention backward — in two kernels:

- dQ kernel: for each query block, sweep key blocks (sequential last grid
  axis), accumulating ``dq += ds @ k`` in VMEM scratch;
- dK/dV kernel: for each key block, sweep query blocks, accumulating
  ``dk += ds^T @ q`` and ``dv += p^T @ do``.

Design notes (see /opt/skills/guides/pallas_guide.md):
- grid = (batch*heads, outer_blocks, inner_blocks); the LAST grid axis runs
  sequentially on TPU, so running statistics / accumulators live in VMEM
  scratch that persists across the inner sweep.
- m/l/lse/delta are kept lane-replicated at (block, 128) to respect the fp32
  (8, 128) min tile; column values are identical across lanes.
- Causal blocks strictly above the diagonal are skipped via `pl.when`
  (predicated out — no FLOPs), and their block index maps are clamped so the
  pipeline elides the HBM fetch entirely.
- ``interpret`` is the caller's to name. Left at None it follows the
  backend (``default_interpret``): compiled on a TPU, interpreted elsewhere,
  which keeps the CPU tests runnable. Whatever must prove the Mosaic
  lowering (``chip_smoke.py``, ``tests/test_tpu_compile.py``) passes
  ``interpret=False`` itself, and the trainers record the mode their step
  was built with (``models/llama.attention_path`` -> run manifest).
  Production CPU paths should use the XLA einsum attention
  (models/llama._xla_attention) instead.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_NEG_INF = -1e30


def default_interpret() -> bool:
    """Pallas interpret mode for a caller that names none: False (the
    compiled kernel) on a TPU backend, True elsewhere."""
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------------ forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                block_q: int, block_k: int, n_k_blocks: int, scale: float,
                causal: bool, seq_len: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Causal: block contributes iff its first key position can be visible to
    # the last query position of this q block.
    run = (ik * block_k <= iq * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)                     # [bq, dh]
        k = k_ref[0].astype(jnp.float32)                     # [bk, dh]
        v = v_ref[0].astype(jnp.float32)                     # [bk, dh]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) \
            + ik * block_k
        if causal:
            qpos = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) \
                + iq * block_q
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        if not causal:
            # Zero-padded tail keys must not receive softmax mass. (With
            # causal=True the causal mask already hides them from every real
            # query, and padded query rows are trimmed by the wrapper.)
            s = jnp.where(kpos < seq_len, s, _NEG_INF)

        m_prev = m_ref[:, :1]                                # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                               # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)                      # [bq, 1]
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == n_k_blocks - 1)
    def _finalize():
        # Fully-masked rows (can't happen for causal q>=0) would have l=0;
        # guard anyway so padding rows emit zeros, not NaNs.
        l = l_ref[:, :1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / safe).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(jnp.broadcast_to(safe, lse_ref.shape[1:]))


def _causal_kv_index(block_q: int, block_k: int):
    # Above-diagonal grid steps are predicated out in the kernel; clamp
    # their K/V block index to the diagonal so consecutive steps reference
    # the same block and the pipeline elides the HBM fetch entirely.
    def kv_index(bh, iq, ik):
        return (bh, jnp.minimum(ik, (iq * block_q + block_q - 1) // block_k), 0)
    return kv_index


def _fwd(qb, kb, vb, causal: bool, block_q: int, block_k: int,
         interpret: bool, seq_len: int, out_dtype):
    """Runs the forward kernel on [BH, T_pad, Dh] inputs.

    Returns (out [BH, T_pad, Dh], lse [BH, T_pad, LANES] lane-replicated).
    """
    bh, t_pad, dh = qb.shape
    n_q = t_pad // block_q
    n_k = t_pad // block_k
    scale = 1.0 / math.sqrt(dh)

    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, n_k_blocks=n_k,
        scale=scale, causal=causal, seq_len=seq_len)
    kv_index = (_causal_kv_index(block_q, block_k) if causal
                else (lambda bh_, iq, ik: (bh_, ik, 0)))

    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh_, iq, ik: (bh_, iq, 0)),
            pl.BlockSpec((1, block_k, dh), kv_index),
            pl.BlockSpec((1, block_k, dh), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh_, iq, ik: (bh_, iq, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda bh_, iq, ik: (bh_, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_pad, dh), out_dtype),
            jax.ShapeDtypeStruct((bh, t_pad, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),       # m
            pltpu.VMEM((block_q, _LANES), jnp.float32),       # l
            pltpu.VMEM((block_q, dh), jnp.float32),           # acc
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(qb, kb, vb)


# ----------------------------------------------------------------- backward

def _bwd_mask(iq, ik, block_q: int, block_k: int, causal: bool, seq_len: int):
    """[bq, bk] validity mask. Unlike the forward (where padded query rows
    are merely trimmed), the backward MUST zero padded query rows: their
    lse is -inf, so exp(s - lse) would overflow and 0*inf-poison dK/dV."""
    qpos = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) \
        + iq * block_q
    kpos = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) \
        + ik * block_k
    mask = qpos < seq_len
    if causal:
        mask &= qpos >= kpos
    else:
        mask &= kpos < seq_len
    return mask


def _bwd_p_ds(q, k, v, do, lse, delta, iq, ik, *, block_q, block_k, scale,
              causal, seq_len):
    """Shared recompute: attention probs p and score-gradient ds for a block.

    p  = exp(q k^T scale - lse)         (exact softmax probabilities)
    ds = p * (do v^T - delta) * scale   (delta = rowsum(do * o))
    """
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = _bwd_mask(iq, ik, block_q, block_k, causal, seq_len)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)               # [bq, bk]
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale                            # [bq, bk]
    return p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, block_q: int, block_k: int, n_k_blocks: int,
               scale: float, causal: bool, seq_len: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = (ik * block_k <= iq * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        _, ds = _bwd_p_ds(q, k, v, do, lse_ref[0][:, :1], delta_ref[0][:, :1],
                          iq, ik, block_q=block_q, block_k=block_k,
                          scale=scale, causal=causal, seq_len=seq_len)
        dq_acc[:] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(ik == n_k_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, block_q: int, block_k: int,
                n_q_blocks: int, scale: float, causal: bool, seq_len: int):
    # Grid is (bh, ik, iq): the sequential inner sweep is over QUERY blocks.
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = (iq * block_q + block_q - 1 >= ik * block_k) if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        p, ds = _bwd_p_ds(q, k, v, do, lse_ref[0][:, :1], delta_ref[0][:, :1],
                          iq, ik, block_q=block_q, block_k=block_k,
                          scale=scale, causal=causal, seq_len=seq_len)
        dv_acc[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(iq == n_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_kernels(qb, kb, vb, dob, lse, delta, causal: bool, block_q: int,
                 block_k: int, interpret: bool, seq_len: int):
    """Runs dQ and dK/dV kernels on [BH, T_pad, Dh] inputs."""
    bh, t_pad, dh = qb.shape
    n_q = t_pad // block_q
    n_k = t_pad // block_k
    scale = 1.0 / math.sqrt(dh)
    common = dict(block_q=block_q, block_k=block_k, scale=scale,
                  causal=causal, seq_len=seq_len)

    q_spec = pl.BlockSpec((1, block_q, dh), lambda bh_, iq, ik: (bh_, iq, 0))
    row_spec = pl.BlockSpec((1, block_q, _LANES),
                            lambda bh_, iq, ik: (bh_, iq, 0))
    kv_index = (_causal_kv_index(block_q, block_k) if causal
                else (lambda bh_, iq, ik: (bh_, ik, 0)))
    kv_spec = pl.BlockSpec((1, block_k, dh), kv_index)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, n_k_blocks=n_k, **common),
        grid=(bh, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, t_pad, dh), qb.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dq",
    )(qb, kb, vb, dob, lse, delta)

    # Grid reordered to (bh, ik, iq). Below-diagonal skipped steps clamp the
    # q-side index to the first contributing q block of this key block.
    if causal:
        def q_index(bh_, ik, iq):
            return (bh_, jnp.maximum(iq, (ik * block_k) // block_q), 0)
    else:
        def q_index(bh_, ik, iq):
            return (bh_, iq, 0)
    q_spec_t = pl.BlockSpec((1, block_q, dh), q_index)
    row_spec_t = pl.BlockSpec((1, block_q, _LANES), q_index)
    kv_spec_t = pl.BlockSpec((1, block_k, dh),
                             lambda bh_, ik, iq: (bh_, ik, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n_q_blocks=n_q, **common),
        grid=(bh, n_k, n_q),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t,
                  row_spec_t],
        out_specs=[kv_spec_t, kv_spec_t],
        out_shape=[jax.ShapeDtypeStruct((bh, t_pad, dh), kb.dtype),
                   jax.ShapeDtypeStruct((bh, t_pad, dh), vb.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, dh), jnp.float32),
                        pltpu.VMEM((block_k, dh), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dkv",
    )(qb, kb, vb, dob, lse, delta)
    return dq, dk, dv


# ------------------------------------------------ dh-major ("packed") layout
#
# The kernels above stream [BH, T, Dh] blocks. At this model's Dh=48 the
# minor dim is lane-padded to 128 in the TPU tiled layout, so every q/k/v/o
# (and backward dq/dk/dv) HBM transfer moves 128/48 ≈ 2.67x the useful
# bytes. Transposing the operands to [BH, Dh, T] makes them exactly dense —
# Dh=48 is a whole number of f32/bf16 sublane tiles and T a lane multiple —
# which converts the streamed traffic to 100% useful bytes. The MXU dots
# keep the same shapes (K=Dh for QK is intrinsic to attention; no dense
# packing can beat XLA's K-padding — a block-diagonal 2-head pack spends
# exactly its saved padding on zero blocks), so this is a pure
# memory-bandwidth play; scores are computed key-major ([bk, bq]) so the
# softmax statistics live along lanes and never need a relayout.

def _fwd_kernel_t(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                  *, block_q: int, block_k: int, n_k_blocks: int, scale: float,
                  causal: bool, seq_len: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = (ik * block_k <= iq * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _body():
        qt = q_ref[0].astype(jnp.float32)                    # [dh, bq]
        kt = k_ref[0].astype(jnp.float32)                    # [dh, bk]
        vt = v_ref[0].astype(jnp.float32)                    # [dh, bk]
        # Key-major scores: keys on sublanes, queries on lanes.
        s = jax.lax.dot_general(kt, qt, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0) \
            + ik * block_k
        if causal:
            qpos = jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1) \
                + iq * block_q
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        else:
            s = jnp.where(kpos < seq_len, s, _NEG_INF)

        m_prev = m_ref[:1, :]                                # [1, bq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)                               # [bk, bq]
        alpha = jnp.exp(m_prev - m_new)                      # [1, bq]
        l_new = alpha * l_ref[:1, :] + jnp.sum(p, axis=0, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot(
            vt, p, preferred_element_type=jnp.float32)       # [dh, bq]
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == n_k_blocks - 1)
    def _finalize():
        l = l_ref[:1, :]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / safe).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(
            jnp.broadcast_to(safe, lse_ref.shape[1:]))


_SUBLANES = 8


def _fwd_t(qb, kb, vb, causal: bool, block_q: int, block_k: int,
           interpret: bool, seq_len: int, out_dtype):
    """Forward on dh-major [BH, Dh, T_pad] inputs.

    Returns (out [BH, Dh, T_pad], lse [BH, SUBLANES, T_pad] row-replicated).
    """
    bh, dh, t_pad = qb.shape
    n_q = t_pad // block_q
    n_k = t_pad // block_k
    scale = 1.0 / math.sqrt(dh)

    kernel = functools.partial(
        _fwd_kernel_t, block_q=block_q, block_k=block_k, n_k_blocks=n_k,
        scale=scale, causal=causal, seq_len=seq_len)
    if causal:
        def kv_index(bh_, iq, ik):
            return (bh_, 0,
                    jnp.minimum(ik, (iq * block_q + block_q - 1) // block_k))
    else:
        def kv_index(bh_, iq, ik):
            return (bh_, 0, ik)

    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, dh, block_q), lambda bh_, iq, ik: (bh_, 0, iq)),
            pl.BlockSpec((1, dh, block_k), kv_index),
            pl.BlockSpec((1, dh, block_k), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, dh, block_q), lambda bh_, iq, ik: (bh_, 0, iq)),
            pl.BlockSpec((1, _SUBLANES, block_q),
                         lambda bh_, iq, ik: (bh_, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, dh, t_pad), out_dtype),
            jax.ShapeDtypeStruct((bh, _SUBLANES, t_pad), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((_SUBLANES, block_q), jnp.float32),    # m
            pltpu.VMEM((_SUBLANES, block_q), jnp.float32),    # l
            pltpu.VMEM((dh, block_q), jnp.float32),           # acc
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(qb, kb, vb)


def _bwd_mask_t(iq, ik, block_q: int, block_k: int, causal: bool,
                seq_len: int):
    """[bk, bq] validity mask (key-major twin of _bwd_mask)."""
    kpos = jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0) \
        + ik * block_k
    qpos = jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1) \
        + iq * block_q
    mask = qpos < seq_len
    if causal:
        mask &= qpos >= kpos
    else:
        mask &= kpos < seq_len
    return mask


def _bwd_p_ds_t(qt, kt, vt, dot_, lse_row, delta_row, iq, ik, *, block_q,
                block_k, scale, causal, seq_len):
    """Key-major recompute: pT [bk, bq] and dsT [bk, bq]."""
    s = jax.lax.dot_general(kt, qt, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = _bwd_mask_t(iq, ik, block_q, block_k, causal, seq_len)
    p = jnp.where(mask, jnp.exp(s - lse_row), 0.0)           # [bk, bq]
    dp = jax.lax.dot_general(vt, dot_, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_row) * scale                        # [bk, bq]
    return p, ds


def _dq_kernel_t(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                 dq_acc, *, block_q: int, block_k: int, n_k_blocks: int,
                 scale: float, causal: bool, seq_len: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = (ik * block_k <= iq * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _body():
        qt = q_ref[0].astype(jnp.float32)
        kt = k_ref[0].astype(jnp.float32)
        vt = v_ref[0].astype(jnp.float32)
        dot_ = do_ref[0].astype(jnp.float32)
        _, ds = _bwd_p_ds_t(qt, kt, vt, dot_, lse_ref[0][:1, :],
                            delta_ref[0][:1, :], iq, ik, block_q=block_q,
                            block_k=block_k, scale=scale, causal=causal,
                            seq_len=seq_len)
        dq_acc[:] += jax.lax.dot(kt, ds,
                                 preferred_element_type=jnp.float32)

    @pl.when(ik == n_k_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel_t(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                  dv_ref, dk_acc, dv_acc, *, block_q: int, block_k: int,
                  n_q_blocks: int, scale: float, causal: bool, seq_len: int):
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = (iq * block_q + block_q - 1 >= ik * block_k) if causal else True

    @pl.when(run)
    def _body():
        qt = q_ref[0].astype(jnp.float32)
        kt = k_ref[0].astype(jnp.float32)
        vt = v_ref[0].astype(jnp.float32)
        dot_ = do_ref[0].astype(jnp.float32)
        p, ds = _bwd_p_ds_t(qt, kt, vt, dot_, lse_ref[0][:1, :],
                            delta_ref[0][:1, :], iq, ik, block_q=block_q,
                            block_k=block_k, scale=scale, causal=causal,
                            seq_len=seq_len)
        dv_acc[:] += jax.lax.dot_general(
            dot_, p, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [dh, bk]
        dk_acc[:] += jax.lax.dot_general(
            qt, ds, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [dh, bk]

    @pl.when(iq == n_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_kernels_t(qb, kb, vb, dob, lse, delta, causal: bool, block_q: int,
                   block_k: int, interpret: bool, seq_len: int):
    """dQ and dK/dV kernels on dh-major [BH, Dh, T_pad] inputs."""
    bh, dh, t_pad = qb.shape
    n_q = t_pad // block_q
    n_k = t_pad // block_k
    scale = 1.0 / math.sqrt(dh)
    common = dict(block_q=block_q, block_k=block_k, scale=scale,
                  causal=causal, seq_len=seq_len)

    q_spec = pl.BlockSpec((1, dh, block_q), lambda bh_, iq, ik: (bh_, 0, iq))
    row_spec = pl.BlockSpec((1, _SUBLANES, block_q),
                            lambda bh_, iq, ik: (bh_, 0, iq))
    if causal:
        def kv_index(bh_, iq, ik):
            return (bh_, 0,
                    jnp.minimum(ik, (iq * block_q + block_q - 1) // block_k))
    else:
        def kv_index(bh_, iq, ik):
            return (bh_, 0, ik)
    kv_spec = pl.BlockSpec((1, dh, block_k), kv_index)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel_t, n_k_blocks=n_k, **common),
        grid=(bh, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, dh, t_pad), qb.dtype),
        scratch_shapes=[pltpu.VMEM((dh, block_q), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dq",
    )(qb, kb, vb, dob, lse, delta)

    if causal:
        def q_index(bh_, ik, iq):
            return (bh_, 0, jnp.maximum(iq, (ik * block_k) // block_q))
    else:
        def q_index(bh_, ik, iq):
            return (bh_, 0, iq)
    q_spec_t = pl.BlockSpec((1, dh, block_q), q_index)
    row_spec_t = pl.BlockSpec((1, _SUBLANES, block_q), q_index)
    kv_spec_t = pl.BlockSpec((1, dh, block_k),
                             lambda bh_, ik, iq: (bh_, 0, ik))

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel_t, n_q_blocks=n_q, **common),
        grid=(bh, n_k, n_q),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t,
                  row_spec_t],
        out_specs=[kv_spec_t, kv_spec_t],
        out_shape=[jax.ShapeDtypeStruct((bh, dh, t_pad), kb.dtype),
                   jax.ShapeDtypeStruct((bh, dh, t_pad), vb.dtype)],
        scratch_shapes=[pltpu.VMEM((dh, block_k), jnp.float32),
                        pltpu.VMEM((dh, block_k), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dkv",
    )(qb, kb, vb, dob, lse, delta)
    return dq, dk, dv


def _layout_t(x, t_pad: int):
    """[B, T, H, Dh] -> [B*H, Dh, T_pad] (dense dh-major kernel layout)."""
    b, t, h, dh = x.shape
    x = jnp.transpose(x, (0, 2, 3, 1)).reshape(b * h, dh, t)
    if t_pad != t:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, t_pad - t)))
    return x


def _unlayout_t(x, b: int, t: int):
    """[B*H, Dh, T_pad] -> [B, T, H, Dh]."""
    bh, dh, _ = x.shape
    return jnp.transpose(x[:, :, :t].reshape(b, bh // b, dh, t), (0, 3, 1, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_t(q, k, v, causal: bool, block_q: int, block_k: int,
             interpret: bool):
    out, _ = _flash_t_fwd(q, k, v, causal, block_q, block_k, interpret)
    return out


def _flash_t_fwd(q, k, v, causal, block_q, block_k, interpret):
    b, t, h, dh = q.shape
    t_pad = _pad_len(t, block_q, block_k)
    out, lse = _fwd_t(_layout_t(q, t_pad), _layout_t(k, t_pad),
                      _layout_t(v, t_pad), causal, block_q, block_k,
                      interpret, t, q.dtype)
    return _unlayout_t(out, b, t), (q, k, v, _unlayout_t(out, b, t),
                                    lse[:, :1, :])


def _flash_t_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    b, t, h, dh = q.shape
    t_pad = _pad_len(t, block_q, block_k)
    lse = jnp.broadcast_to(lse, (b * h, _SUBLANES, t_pad))
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.moveaxis(delta, 2, 1).reshape(b * h, t)       # [BH, T]
    delta = jnp.pad(delta, ((0, 0), (0, t_pad - t)))
    delta = jnp.broadcast_to(delta[:, None, :], (b * h, _SUBLANES, t_pad))
    dq, dk, dv = _bwd_kernels_t(
        _layout_t(q, t_pad), _layout_t(k, t_pad), _layout_t(v, t_pad),
        _layout_t(g, t_pad), lse, delta, causal, block_q, block_k, interpret,
        t)
    return (_unlayout_t(dq, b, t), _unlayout_t(dk, b, t),
            _unlayout_t(dv, b, t))


_flash_t.defvjp(_flash_t_fwd, _flash_t_bwd)


# --------------------------------------------------- custom_vjp + public API

def _layout(x, t_pad: int):
    """[B, T, H, Dh] -> [B*H, T_pad, Dh] (the kernels' layout)."""
    b, t, h, dh = x.shape
    x = jnp.moveaxis(x, 2, 1).reshape(b * h, t, dh)
    if t_pad != t:
        x = jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0)))
    return x


def _unlayout(x, b: int, t: int):
    """[B*H, T_pad, Dh] -> [B, T, H, Dh]."""
    bh, _, dh = x.shape
    return jnp.moveaxis(x[:, :t].reshape(b, bh // b, t, dh), 1, 2)


def _pad_len(t: int, block_q: int, block_k: int) -> int:
    # Common multiple of both block sizes so the q and k grids each tile
    # t_pad exactly.
    lcm = math.lcm(block_q, block_k)
    return math.ceil(t / lcm) * lcm


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal: bool, block_q: int, block_k: int,
           interpret: bool):
    out, _ = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    b, t, h, dh = q.shape
    t_pad = _pad_len(t, block_q, block_k)
    out, lse = _fwd(_layout(q, t_pad), _layout(k, t_pad), _layout(v, t_pad),
                    causal, block_q, block_k, interpret, t, q.dtype)
    out = _unlayout(out, b, t)
    # The kernel emits lse lane-replicated ([BH, T_pad, 128]); keep only one
    # lane as the residual (128x less memory held until the backward).
    return out, (q, k, v, out, lse[:, :, :1])


def _flash_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    b, t, h, dh = q.shape
    t_pad = _pad_len(t, block_q, block_k)
    lse = jnp.broadcast_to(lse, (b * h, t_pad, _LANES))
    # delta = rowsum(dO * O), the softmax-Jacobian correction term. An XLA
    # elementwise reduce — not worth a kernel. Lane-replicated like lse.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.moveaxis(delta, 2, 1).reshape(b * h, t)       # [BH, T]
    delta = jnp.pad(delta, ((0, 0), (0, t_pad - t)))
    delta = jnp.broadcast_to(delta[:, :, None], (b * h, t_pad, _LANES))
    dq, dk, dv = _bwd_kernels(
        _layout(q, t_pad), _layout(k, t_pad), _layout(v, t_pad),
        _layout(g, t_pad), lse, delta, causal, block_q, block_k, interpret, t)
    return (_unlayout(dq, b, t), _unlayout(dk, b, t), _unlayout(dv, b, t))


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret", "dh_major"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None,
                    dh_major: bool = False) -> jnp.ndarray:
    """Fused attention, differentiable. q, k, v: [B, T, H, Dh] (same layout
    as the XLA path in models/llama.attention). Returns [B, T, H, Dh].

    Sequence length is padded up to a block multiple internally; padded keys
    get zero softmax mass and padded query rows are trimmed on return (and
    zeroed in the backward).

    ``dh_major=True`` streams operands in the [BH, Dh, T] layout, which is
    exactly dense on TPU at any head size (a [_, T, 48] operand is
    lane-padded to 128, 2.67x the HBM bytes on every q/k/v/o and gradient
    transfer; at the cells' head size of 128 row-major pads nothing). Same
    math, same MXU shapes; the two layouts are not compared at published
    widths (ROADMAP S5).
    """
    if interpret is None:
        interpret = default_interpret()
    if dh_major:
        return _flash_t(q, k, v, causal, block_q, block_k, interpret)
    return _flash(q, k, v, causal, block_q, block_k, interpret)
