"""tiny-Llama: a functional causal transformer, designed for TPU parallelism.

Capability target (NOT a port): the ``simplellm`` Llama family the reference
trains everywhere — full model `LLama(...)`, plus the pipeline-stage variants
`LLamaFirstStage` (with a separate ``.embed``), `LLamaStage` (hidden→hidden),
and `LLamaLastStage` (hidden→logits); canonical config dmodel=288, 6 heads,
6 layers, ctx 256 (reference: lab/tutorial_1b/primer/intro.py:7-18,
lab/tutorial_1b/PP/1F1B/intro_PP_1F1B.py:29-39).

TPU-first design decisions:
- Transformer blocks are *stacked*: every block parameter has a leading
  ``[n_layers, ...]`` axis and the forward pass is a single ``lax.scan`` —
  one compiled block body regardless of depth, which keeps compile time flat
  and makes pipeline-stage splitting a pure array slice on the leading axis
  (`split_stages` / `stage_apply`).
- Pre-norm RMSNorm + RoPE + SwiGLU MLP (Llama conventions).
- dtype-parameterized: params in fp32, activations typically bf16 so matmuls
  land on the MXU at full rate.
- No data-dependent Python control flow: jit/scan end-to-end.
- Attention is pluggable: "xla" einsum-softmax (XLA fuses it well) or the
  Pallas flash kernel (ops.flash_attention) once seq lengths warrant it.
"""

from __future__ import annotations

import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..config import LlamaConfig
from .. import nn


# ------------------------------------------------------------------ init

def _normal(key, shape, std, dtype):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)


def init_block(key, cfg: LlamaConfig) -> dict:
    """One transformer block's parameters (un-stacked)."""
    dt = jnp.dtype(cfg.param_dtype)
    d, f = cfg.dmodel, cfg.ffn_dim
    ks = jax.random.split(key, 7)
    std = 0.02
    # Residual-out projections scaled down by sqrt(2·L) (GPT-2/Llama init).
    out_std = 0.02 / math.sqrt(2 * cfg.n_layers)
    return {
        "attn_norm": nn.rmsnorm_init(d, dt),
        "wq": _normal(ks[0], (d, d), std, dt),
        "wk": _normal(ks[1], (d, d), std, dt),
        "wv": _normal(ks[2], (d, d), std, dt),
        "wo": _normal(ks[3], (d, d), out_std, dt),
        "mlp_norm": nn.rmsnorm_init(d, dt),
        "w_gate": _normal(ks[4], (d, f), std, dt),
        "w_up": _normal(ks[5], (d, f), std, dt),
        "w_down": _normal(ks[6], (f, d), out_std, dt),
    }


def init_llama(key, cfg: LlamaConfig) -> dict:
    """Full model parameters.

    Structure: {"embed": [V, D], "blocks": pytree with leading [L] axis,
    "final_norm": ..., "lm_head": [D, V]} — the leading block axis is what
    `split_stages` slices for pipeline parallelism.
    """
    dt = jnp.dtype(cfg.param_dtype)
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    block_keys = jax.random.split(k_blocks, cfg.n_layers)
    blocks = jax.vmap(lambda k: init_block(k, cfg))(block_keys)
    embed = _normal(k_embed, (cfg.vocab_size, cfg.dmodel), 0.02, dt)
    if cfg.padding_idx is not None:
        embed = embed.at[cfg.padding_idx].set(0.0)
    return {
        "embed": embed,
        "blocks": blocks,
        "final_norm": nn.rmsnorm_init(cfg.dmodel, dt),
        "lm_head": _normal(k_head, (cfg.dmodel, cfg.vocab_size), 0.02, dt),
    }


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


# ------------------------------------------------------------------ RoPE

def rope_angles(positions: jnp.ndarray, head_dim: int, theta: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for rotary embeddings. positions: [T] (absolute), so
    sequence-parallel shards pass their global offsets and stay correct."""
    half = head_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]   # [T, half]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: [B, T, H, Dh]; rotate pairs (x1, x2) = (x[..., :half], x[..., half:])."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[None, :, None, :].astype(x.dtype)
    s = sin[None, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


# ------------------------------------------------------------------ attention
#
# ``attention``, ``mlp``, ``embed``, ``head`` and ``head_loss`` run under a
# ``jax.named_scope`` of that name (``attn`` for attention): it costs nothing
# at run time, changes no number, and is how a device trace tells the step's
# parts apart (docs/COMPONENTS.md lists the names).

def qkv_proj(block: dict, x: jnp.ndarray, head_dim: int
             ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused QKV projection: x [B, T, D] → q, k, v [B, T, H_local, Dh].

    One [D, 3·D_local] matmul instead of three: at dmodel 288 each separate
    projection's 288-wide output pads to a 384-wide MXU tile (25% waste);
    fused, 3·288=864 pads to 896 (~4%). The concat copies ~1 MB of weights
    per step — noise next to the matmul. Param tree unchanged, so TP sharding
    (column-sharded wq/wk/wv concat along the sharded axis), checkpoints and
    stage splitting are unaffected. The decode path (models.generate)
    performs the same split on weights pre-fused once per generate() call —
    its per-position agreement with this path is asserted in
    tests/test_generate.py.
    """
    b, t, _ = x.shape
    dl = block["wq"].shape[1]                        # = dmodel / tp_size
    h_local = dl // head_dim                         # = num_heads / tp_size
    w_qkv = jnp.concatenate(
        [block["wq"], block["wk"], block["wv"]], axis=1).astype(x.dtype)
    qkv = x @ w_qkv
    q = qkv[..., :dl].reshape(b, t, h_local, head_dim)
    k = qkv[..., dl:2 * dl].reshape(b, t, h_local, head_dim)
    v = qkv[..., 2 * dl:].reshape(b, t, h_local, head_dim)
    return q, k, v


def _xla_attention(q, k, v, *, causal: bool, q_offset: int = 0,
                   softmax_dtype: str = "float32") -> jnp.ndarray:
    """[B, T, H, Dh] attention. q_offset shifts the causal mask for
    sequence-parallel query shards.

    Heads are folded into the batch dimension and the two O(T²) contractions
    are explicit batched dot_generals in [B·H, T, Dh] layout — identical math
    to the einsum formulation, whose backward introduces extra layout
    transposes. The training cell runs the flash kernel, not this path:
    it is not measured at published widths (ROADMAP S5).

    ``softmax_dtype="bfloat16"`` (opt-in via LlamaConfig) materializes the
    [B·H, T, T] score tensor in bf16 — halving the largest tensor of the
    attention leg (what that buys is not measured at published widths)
    — while the softmax max/sum still accumulate in fp32.
    Off by default: the ~1e-2 drift is outside the PP/SP equivalence-test
    tolerances.
    """
    b, tq, h, dh = q.shape
    tk = k.shape[1]
    scale = 1.0 / math.sqrt(dh)
    st = jnp.dtype(softmax_dtype)
    qm = q.transpose(0, 2, 1, 3).reshape(b * h, tq, dh)
    km = k.transpose(0, 2, 1, 3).reshape(b * h, tk, dh)
    vm = v.transpose(0, 2, 1, 3).reshape(b * h, tk, dh)
    scores = lax.dot_general(qm, km, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=st) * jnp.asarray(scale, st)
    if causal:
        qpos = jnp.arange(tq)[:, None] + q_offset
        kpos = jnp.arange(tk)[None, :]
        scores = jnp.where(qpos >= kpos, scores, -jnp.inf)
    if st == jnp.float32:
        probs = jax.nn.softmax(scores, axis=-1)
    else:
        # bf16 scores; subtract the fp32 row max, then divide in fp32 (the
        # upcast/divide/downcast fuses into one elementwise kernel, so no
        # fp32 [T, T] tensor ever hits HBM) — only the stored [T, T]-sized
        # tensors stay bf16.
        m = jnp.max(scores.astype(jnp.float32), axis=-1, keepdims=True)
        e = jnp.exp(scores - m.astype(st)).astype(jnp.float32)
        probs = e / jnp.sum(e, axis=-1, keepdims=True)
    probs = probs.astype(q.dtype)
    out = lax.dot_general(probs, vm, (((2,), (1,)), ((0,), (0,))))
    return out.reshape(b, h, tq, dh).transpose(0, 2, 1, 3)


def attention_path(cfg: LlamaConfig, t: int) -> dict:
    """The attention inner a length-``t`` call traces to on this backend:
    ``{"impl": "pallas" | "xla", "interpret": bool | None}`` (``interpret``
    is the Pallas mode, None on the XLA path). ``attention`` dispatches on
    it and the trainers write it into the run manifest, so a run records
    whether its step was built with the compiled flash kernel."""
    use_pallas = cfg.attention_impl == "pallas" or (
        cfg.attention_impl == "auto"
        and t >= cfg.flash_min_seq
        and jax.default_backend() == "tpu")
    if not use_pallas:
        return {"impl": "xla", "interpret": None}
    from ..ops.flash_attention import default_interpret
    return {"impl": "pallas", "interpret": default_interpret()}


@jax.named_scope("attn")
def attention(block: dict, x: jnp.ndarray, cfg: LlamaConfig,
              cos: jnp.ndarray, sin: jnp.ndarray,
              attn_fn: Optional[Callable] = None,
              tp_axis: Optional[str] = None) -> jnp.ndarray:
    """``attn_fn(q, k, v) -> out`` (all [B, T, H, Dh]) overrides the attention
    inner — the hook sequence parallelism uses to swap in ring attention.

    ``tp_axis`` enables Megatron-style tensor parallelism under shard_map:
    wq/wk/wv are column-sharded (local heads), wo row-sharded, and the output
    projection's partial sum is psum-ed over the axis. Head count is inferred
    from the local weight shapes, so the same code runs sharded or full.
    """
    b, t, d = x.shape
    dh = cfg.head_dim
    q, k, v = qkv_proj(block, x, dh)
    h_local = q.shape[2]                             # = num_heads / tp_size
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    path = attention_path(cfg, t)
    if attn_fn is not None:
        out = attn_fn(q, k, v)
    elif path["impl"] == "pallas":
        from ..ops.flash_attention import flash_attention
        blk = min(t, cfg.flash_block)
        out = flash_attention(q, k, v, causal=True,
                              dh_major=cfg.flash_dh_major,
                              block_q=blk, block_k=blk,
                              interpret=path["interpret"])
    else:
        out = _xla_attention(q, k, v, causal=True,
                             softmax_dtype=cfg.softmax_dtype)
    y = out.reshape(b, t, h_local * dh) @ block["wo"].astype(x.dtype)
    if tp_axis is not None:
        y = lax.psum(y, tp_axis)                     # combine head groups
    return y


@jax.named_scope("mlp")
def mlp(block: dict, x: jnp.ndarray,
        tp_axis: Optional[str] = None) -> jnp.ndarray:
    """SwiGLU MLP. With ``tp_axis``: w_gate/w_up column-sharded (local ffn
    slice), w_down row-sharded, partial output psum-ed over the axis."""
    f = block["w_gate"].shape[1]                     # = ffn_dim / tp_size
    w_gu = jnp.concatenate(
        [block["w_gate"], block["w_up"]], axis=1).astype(x.dtype)
    gu = x @ w_gu                                    # fused gate+up matmul
    y = (jax.nn.silu(gu[..., :f]) * gu[..., f:]) @ block["w_down"].astype(x.dtype)
    if tp_axis is not None:
        y = lax.psum(y, tp_axis)
    return y


def block_apply(block: dict, x: jnp.ndarray, cfg: LlamaConfig,
                cos: jnp.ndarray, sin: jnp.ndarray,
                attn_fn: Optional[Callable] = None,
                tp_axis: Optional[str] = None) -> jnp.ndarray:
    x = x + attention(block, nn.rmsnorm(block["attn_norm"], x, eps=cfg.norm_eps),
                      cfg, cos, sin, attn_fn, tp_axis)
    x = x + mlp(block, nn.rmsnorm(block["mlp_norm"], x, eps=cfg.norm_eps), tp_axis)
    return x


# ------------------------------------------------------------------ stages
# These four functions are the framework's equivalent of simplellm's
# LLamaFirstStage.embed / LLamaStage / LLamaLastStage surface
# (reference: intro_PP_1F1B.py:29-39,53).

@jax.named_scope("embed")
def embed(params: dict, tokens: jnp.ndarray, cfg: LlamaConfig) -> jnp.ndarray:
    """tokens [B, T] -> activations [B, T, D] in the compute dtype.

    With ``padding_idx`` set, pad positions produce zero vectors AND the pad
    row receives no gradient (torch Embedding(padding_idx) semantics — the
    masked output cuts the backward path to that row).
    """
    h = params["embed"][tokens]
    if cfg.padding_idx is not None:
        h = jnp.where((tokens == cfg.padding_idx)[..., None], 0.0, h)
    return h.astype(jnp.dtype(cfg.dtype))


def blocks_apply(blocks: dict, h: jnp.ndarray, cfg: LlamaConfig,
                 positions: Optional[jnp.ndarray] = None,
                 attn_fn: Optional[Callable] = None,
                 tp_axis: Optional[str] = None) -> jnp.ndarray:
    """Apply a stack of blocks (leading [L] axis) via one lax.scan."""
    t = h.shape[1]
    if positions is None:
        positions = jnp.arange(t)
    cos, sin = rope_angles(positions, cfg.head_dim, cfg.rope_theta)

    def apply_one(block, carry, cos, sin):
        # cfg/attn_fn captured by closure: cfg is static config, attn_fn may
        # close over collective primitives that must trace fresh per call.
        return block_apply(block, carry, cfg, cos, sin, attn_fn, tp_axis)

    fn = jax.checkpoint(apply_one) if cfg.remat else apply_one

    def body(carry, block):
        return fn(block, carry, cos, sin), None

    out, _ = lax.scan(body, h, blocks)
    return out


@jax.named_scope("head")
def head(params: dict, h: jnp.ndarray, cfg: LlamaConfig) -> jnp.ndarray:
    """activations [B, T, D] -> logits [B, T, V] (fp32 for a stable loss)."""
    h = nn.rmsnorm(params["final_norm"], h, eps=cfg.norm_eps)
    return (h @ params["lm_head"].astype(h.dtype)).astype(jnp.float32)


def forward(params: dict, tokens: jnp.ndarray, cfg: LlamaConfig,
            positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Full causal LM: tokens [B, T] -> logits [B, T, V]."""
    h = embed(params, tokens, cfg)
    h = blocks_apply(params["blocks"], h, cfg, positions)
    return head(params, h, cfg)


@jax.named_scope("head_loss")
def head_loss(params: dict, h: jnp.ndarray, tokens: jnp.ndarray,
              cfg: LlamaConfig, chunk_size: int = 512) -> jnp.ndarray:
    """Fused final-norm + lm_head + next-token cross-entropy.

    Mathematically ``causal_lm_loss(head(params, h, cfg), tokens)`` but the
    [B, T, V] logits are never materialized in HBM — see
    ops.losses.fused_linear_cross_entropy. At the canonical config the
    unfused fp32 logits are the single largest HBM tensor of the train step.
    """
    from ..ops.losses import fused_linear_cross_entropy
    h = nn.rmsnorm(params["final_norm"], h, eps=cfg.norm_eps)
    shift_h = h[:, :-1, :].reshape(-1, h.shape[-1])
    labels = tokens[:, 1:].reshape(-1)
    return fused_linear_cross_entropy(shift_h, params["lm_head"], labels,
                                      chunk_size=chunk_size)


def forward_loss(params: dict, tokens: jnp.ndarray, cfg: LlamaConfig,
                 positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Full causal-LM training loss with the fused head (no [B,T,V] logits)."""
    h = embed(params, tokens, cfg)
    h = blocks_apply(params["blocks"], h, cfg, positions)
    return head_loss(params, h, tokens, cfg)


# ------------------------------------------------------------------ pipeline splitting

def split_stages(params: dict, n_stages: int) -> list:
    """Slice the stacked block axis into ``n_stages`` contiguous stage params.

    Stage 0 carries the embedding, the last stage carries final_norm+lm_head —
    mirroring the First/Stage/Last decomposition of the reference's pipeline
    (intro_PP_1F1B.py:29-39) as pure array slicing.
    """
    n_layers = jax.tree.leaves(params["blocks"])[0].shape[0]
    assert n_layers % n_stages == 0, (n_layers, n_stages)
    per = n_layers // n_stages
    stages = []
    for s in range(n_stages):
        stage = {"blocks": jax.tree.map(lambda x: x[s * per:(s + 1) * per], params["blocks"])}
        if s == 0:
            stage["embed"] = params["embed"]
        if s == n_stages - 1:
            stage["final_norm"] = params["final_norm"]
            stage["lm_head"] = params["lm_head"]
        stages.append(stage)
    return stages


def merge_stages(stages: list) -> dict:
    """Inverse of split_stages."""
    blocks = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                          *[s["blocks"] for s in stages])
    return {
        "embed": stages[0]["embed"],
        "blocks": blocks,
        "final_norm": stages[-1]["final_norm"],
        "lm_head": stages[-1]["lm_head"],
    }


def stage_apply(stage: dict, x: jnp.ndarray, cfg: LlamaConfig, *,
                is_first: bool, is_last: bool,
                positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Run one pipeline stage: embeds if first, heads if last.

    x is tokens [B, T] for the first stage, activations [B, T, D] otherwise.
    """
    h = embed(stage, x, cfg) if is_first else x
    h = blocks_apply(stage["blocks"], h, cfg, positions)
    return head(stage, h, cfg) if is_last else h
