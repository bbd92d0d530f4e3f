"""Sequence/context parallelism: ring attention over a ``seq`` mesh axis.

The reference has NO long-context support — sequence length is fixed at 256
on a single device (reference: lab/tutorial_1b/primer/intro.py:10; SURVEY.md
§5.7). This module is the capability the TPU build adds as first-class: the
sequence axis becomes a mesh axis, each device holds a contiguous window of
the sequence, and attention runs as a **ring**: K/V shards rotate around the
ICI ring via ``lax.ppermute`` while each device's queries accumulate the
online-softmax statistics (the blockwise-parallel/RingAttention recurrence).
Peak activation memory per device drops from O(T) to O(T / n_seq), so context
scales linearly with the ring size.

Design notes:
- The rotation direction is the ICI ring: device s sends its current K/V
  chunk to s+1, so after t hops device s holds the chunk owned by s−t.
- Causality is positional: the owner of the incoming chunk determines its
  global key offsets; masked entries get zero softmax mass exactly (the
  `p = where(visible, ...)` guard, not just a −inf logit, so fully-masked
  future chunks contribute nothing to the running sums).
- The backward pass is jax.grad through the scanned ppermute — the cotangent
  rotates the opposite way around the ring automatically; no hand-written
  reverse schedule.
- RoPE stays correct because models/llama.rope_angles takes *absolute*
  positions; each shard passes its global window offsets.
- Composes with data parallelism on a ``(data, seq)`` mesh: batch sharded
  over ``data``, sequence over ``seq``, grads psum over both.
- The per-hop inner attention is the XLA einsum + online-softmax, not the
  Pallas flash kernel: each hop sees a [T/n_seq, T/n_seq] block and owns
  its own fp32 online-softmax carry across hops, which the kernel's
  interface does not take. Whether a flash inner would pay at a hop's
  length is not measured at published widths (ROADMAP S5, S8).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..telemetry import comm

from ..config import LlamaConfig
from ..models import llama
from .dp import TrainState, sharded_opt_init

_NEG_INF = -1e30


# --------------------------------------------------------------- the kernel

def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str, *, causal: bool = True,
                   comm_scale: int = 1) -> jnp.ndarray:
    """Ring attention over sequence shards. Must run inside shard_map.

    q, k, v: local shards [B, T_local, H, Dh] whose global positions are
    ``axis_index * T_local + arange(T_local)``. Returns [B, T_local, H, Dh] —
    each query attends over the FULL global sequence (causally masked).

    ``comm_scale``: executions of this call per step beyond what tracing
    sees — callers inside a scanned layer stack pass their layer count so
    telemetry.comm's per-step byte accounting stays truthful (the K/V hop
    ppermutes below already self-scale by the ring length; the backward
    ring autodiff synthesizes is the documented under-count).
    """
    n = lax.axis_size(axis_name)
    s = lax.axis_index(axis_name)
    b, tl, h, dh = q.shape
    scale = 1.0 / (dh ** 0.5)
    perm = [(i, (i + 1) % n) for i in range(n)]
    qpos = jnp.arange(tl)[:, None] + s * tl                     # [tl, 1]

    def step(carry, t):
        k_c, v_c, m, l, acc = carry
        owner = (s - t) % n                                     # chunk origin
        scores = (jnp.einsum("bthd,bshd->bhts", q, k_c)
                  .astype(jnp.float32) * scale)                 # [b,h,tl,tl]
        kpos = jnp.arange(tl)[None, :] + owner * tl
        visible = (qpos >= kpos) if causal else jnp.ones_like(qpos >= kpos)
        scores = jnp.where(visible[None, None], scores, _NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        # Explicit zeroing (not just −inf logits): a fully-masked chunk has
        # m_new == m == _NEG_INF, where exp(scores − m_new) would be exp(0)=1.
        p = jnp.where(visible[None, None], jnp.exp(scores - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhts,bshd->bhtd", p.astype(v_c.dtype), v_c).astype(jnp.float32)
        # scale = n·comm_scale: the scan body traces ONCE but hops n times
        # per attention call, comm_scale attention calls per step.
        k_n = comm.ppermute(k_c, axis_name, perm, label="ring_kv_hop",
                            scale=n * comm_scale)
        v_n = comm.ppermute(v_c, axis_name, perm, label="ring_kv_hop",
                            scale=n * comm_scale)
        return (k_n, v_n, m_new, l, acc), None

    init = (k, v,
            jnp.full((b, h, tl, 1), _NEG_INF, jnp.float32),
            jnp.zeros((b, h, tl, 1), jnp.float32),
            jnp.zeros((b, h, tl, dh), jnp.float32))
    (_, _, _, l, acc), _ = lax.scan(step, init, jnp.arange(n))
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)              # [b,tl,h,dh]


# ------------------------------------------------------- sequence-parallel LM

def _local_window(tokens: jnp.ndarray, s, tl: int) -> jnp.ndarray:
    """Slice shard s's [B, tl] window out of the replicated [B, T] batch."""
    return lax.dynamic_slice_in_dim(tokens, s * tl, tl, axis=1)


def _sp_logits(params: dict, tokens: jnp.ndarray, cfg: LlamaConfig,
               n_seq: int) -> jnp.ndarray:
    """Per-shard body: local logits [B, T/n_seq, V] for this shard's window."""
    s = lax.axis_index("seq")
    t = tokens.shape[1]
    assert t % n_seq == 0, (t, n_seq)
    tl = t // n_seq
    local_tok = _local_window(tokens, s, tl)
    positions = jnp.arange(tl) + s * tl                         # global RoPE
    h = llama.embed(params, local_tok, cfg)
    # comm_scale=n_layers: blocks_apply scans the layer stack, so the ring
    # traces once for L executions per step.
    attn = functools.partial(ring_attention, axis_name="seq", causal=True,
                             comm_scale=cfg.n_layers)
    h = llama.blocks_apply(params["blocks"], h, cfg, positions, attn_fn=attn)
    return llama.head(params, h, cfg)


def _sp_loss(params: dict, tokens: jnp.ndarray, cfg: LlamaConfig,
             n_seq: int) -> jnp.ndarray:
    """LOCAL share of the causal LM loss under sequence sharding; psum over
    ``seq`` of this equals single-device ops.causal_lm_loss (mean NLL over
    the B·(T−1) next-token positions).

    The shift crosses shard boundaries: shard s's last position is predicted
    against shard s+1's first token, so targets come from the *replicated*
    token batch rolled left by one; the global final position is masked.

    Deliberately NO psum inside: this function sits under value_and_grad, and
    psum's transpose is psum — reducing the loss before differentiation would
    seed every replica and scale gradients by n_seq (same pitfall documented
    in parallel.pp._pipeline_loss_and_grad). Callers psum loss and grads
    AFTER the grad computation.
    """
    s = lax.axis_index("seq")
    b, t = tokens.shape
    tl = t // n_seq
    logits = _sp_logits(params, tokens, cfg, n_seq)
    rolled = jnp.roll(tokens, -1, axis=1)
    targets = _local_window(rolled, s, tl)
    gpos = jnp.arange(tl) + s * tl
    valid = (gpos < t - 1)[None, :]                             # [1, tl]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return (nll * valid).sum() / (b * (t - 1))


@functools.cache
def _sp_forward_fn(cfg: LlamaConfig, mesh: Mesh, n_seq: int) -> Callable:
    fn = shard_map(
        lambda p, tok: _sp_logits(p, tok, cfg, n_seq),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(None, "seq"),
        check_vma=False,
    )
    return jax.jit(fn)


def sp_forward(params: dict, tokens: jnp.ndarray, cfg: LlamaConfig,
               mesh: Mesh) -> jnp.ndarray:
    """Full logits [B, T, V] computed sequence-parallel (for tests/eval).
    The jitted program is cached on (cfg, mesh) so eval loops don't retrace."""
    return _sp_forward_fn(cfg, mesh, mesh.shape["seq"])(params, tokens)


def init_state(mesh: Mesh, params: dict,
               optimizer: optax.GradientTransformation) -> TrainState:
    """Params replicated (sequence parallelism shards activations, not
    weights); see parallel.tp for weight sharding."""
    params = jax.device_put(params, NamedSharding(mesh, P()))
    opt_state = sharded_opt_init(mesh, params, optimizer,
                                 jax.tree.map(lambda _: P(), params))
    step = jax.device_put(jnp.zeros((), jnp.int32), NamedSharding(mesh, P()))
    return TrainState(params, opt_state, step)


def make_sp_train_step(cfg: LlamaConfig, optimizer: optax.GradientTransformation,
                       mesh: Mesh) -> Callable:
    """jit-compiled train step on a ``(data?, seq)`` mesh.

    ``step(state, tokens)`` with tokens [B_global, T]: batch axis sharded over
    ``data`` (if present), tokens replicated over ``seq`` (each shard slices
    its own window — int tokens are tiny; activations are what SP shards).
    """
    n_seq = mesh.shape["seq"]
    has_data = mesh.shape.get("data", 1) > 1

    def local_step(state: TrainState, tokens):
        loss, grads = jax.value_and_grad(_sp_loss)(
            state.params, tokens, cfg, n_seq)
        # Each shard computed grads from its local loss slice; the total
        # gradient is the sum over shards (loss was already globally scaled).
        grads = comm.psum(grads, "seq", label="sp_grad_allreduce")
        loss = comm.psum(loss, "seq", label="sp_loss_allreduce")
        if has_data:
            grads = comm.pmean(grads, "data", label="grad_allreduce")
            loss = comm.pmean(loss, "data", label="loss_allreduce")
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss

    sharded = shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P("data") if has_data else P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0,))


from .mesh import shard_batch  # noqa: E402,F401  (shared batch placement)
