"""Pipeline parallelism: GPipe and 1F1B microbatch schedules as one SPMD program.

Capability target (NOT a port): the reference's three pipeline variants —
- naive 3-stage PP: one batch flows stage0→1→2 forward then back with
  blocking send/recv (reference: lab/tutorial_1b/PP/1F1B/intro_PP_1F1B.py:27-99
  — the file is *named* 1F1B but implements a naive schedule; here 1F1B is
  actually implemented, see `_pipeline_1f1b_loss_and_grad`);
- microbatched GPipe: batch split into microbatches streamed with
  isend/irecv(tag=itr), grads accumulated across microbatches, one step per
  iteration (lab/tutorial_1a/homework_1_b1.py:50-144);
- joint DP×PP: two 3-stage pipelines + a cross-pipeline gradient allreduce
  (lab/hw01/homework 1 b/homework_1_b2.py:28-32,141-150).

TPU-native shape: ranks, tags, and point-to-point sockets disappear. Stages
are a named mesh axis; the per-iteration schedule is a ``lax.scan`` over
``n_microbatches + n_stages - 1`` ticks; the stage→stage activation hop is a
single ``lax.ppermute`` over the ICI ring. Crucially the *backward* pipeline
is not hand-written: ``jax.grad`` of the scanned forward transposes every
ppermute (hop direction reverses) and replays ticks in reverse — the reverse
schedule the reference codes by hand (homework_1_b1.py:111-139) falls out of
autodiff. Microbatch gradient semantics match the reference's accumulate-
then-step (one optimizer step per iteration, loss averaged over microbatches).

Two recorded reference quirks are deliberately NOT reproduced (documented
deviations, SURVEY.md §2.10/§3.3):
- homework_1_b1 retains only the *last* microbatch's activations, so stages
  0/1 only receive the last microbatch's backward. Here every microbatch
  back-propagates through every stage (faithful GPipe).
- homework_1_b2 allreduces gradients only in the first-stage DP group [0,3];
  replicas of other stages silently diverge. Here ALL stages pmean over the
  ``data`` axis.

DP×PP composes by construction: build the mesh with ``{"data": d, "stage": s}``
and the same step function pmean-s grads over ``data`` — the 2-pipeline ×
3-stage homework topology is ``make_mesh({"data": 2, "stage": 3})``.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..telemetry import comm

from ..config import LlamaConfig
from ..models import llama
from .dp import TrainState, apply_optimizer, sharded_opt_init


# ------------------------------------------------------------- param layout

from .tp import _COL as _TP_COL, _ROW as _TP_ROW  # one source of truth for
# which block leaves are column- vs row-sharded under tensor parallelism.


def param_specs(params: dict, tp: bool = False) -> dict:
    """PartitionSpecs for a stacked-block Llama param tree on a pipeline mesh.

    ``blocks`` (leading [n_layers] axis) shards over ``stage`` — each stage
    holds its contiguous slice of layers, the SPMD analog of simplellm's
    First/Stage/Last per-rank modules. With ``tp`` the block weight matrices
    additionally shard over ``model`` in the Megatron layout (parallel.tp).
    Embedding/head/final-norm stay replicated: only the first/last stage
    *reads* them, and their gradients are psum-ed back to all stages so the
    replicated update is identical.
    """
    def block_leaf_spec(name):
        if tp and name in _TP_COL:
            return P("stage", None, "model")
        if tp and name in _TP_ROW:
            return P("stage", "model", None)
        return P("stage")

    specs = {}
    for k, v in params.items():
        if k == "blocks":
            specs[k] = {name: jax.tree.map(lambda _, s=block_leaf_spec(name): s,
                                           leaf)
                        for name, leaf in v.items()}
        else:
            specs[k] = jax.tree.map(lambda _: P(), v)
    return specs


def shard_params(mesh: Mesh, params: dict) -> dict:
    specs = param_specs(params, tp=mesh.shape.get("model", 1) > 1)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs)


def init_state(mesh: Mesh, params: dict, optimizer: optax.GradientTransformation) -> TrainState:
    """Shard params over the pipeline mesh; optimizer moments are explicitly
    placed with the param specs via dp.sharded_opt_init (a plain jitted
    optimizer.init would commit the whole opt state to one device)."""
    params = shard_params(mesh, params)
    opt_state = sharded_opt_init(mesh, params, optimizer,
                                 param_specs(params, tp=mesh.shape.get("model", 1) > 1))
    step = jax.device_put(jnp.zeros((), jnp.int32), NamedSharding(mesh, P()))
    return TrainState(params, opt_state, step)


# ------------------------------------------------------------- the schedule

def _pipeline_loss_and_grad(params: dict, tokens: jnp.ndarray, cfg: LlamaConfig,
                            n_stages: int, n_microbatches: int,
                            has_data_axis: bool,
                            tp: int = 1,
                            comm_scale: int = 1) -> Tuple[jnp.ndarray, dict]:
    """Per-device body (runs under shard_map): GPipe forward over ticks,
    grads via autodiff, cross-stage/data reductions.

    ``params["blocks"]`` is the LOCAL stage slice [n_layers/n_stages, ...];
    ``tokens`` is the local data shard [B_local, T] with
    B_local = n_microbatches · microbatch_size. With ``tp > 1`` the block
    weights are additionally model-sharded (Megatron; see parallel.tp) and
    the loss is scaled by 1/tp under differentiation — every model shard
    seeds an identical loss replica, and the in-forward psums (transpose:
    psum) would otherwise count each weight path tp times.

    ``comm_scale`` is the telemetry execution multiplier for the fused
    K-step scan driver (``make_pipeline_multi_step``): the body traces
    once per compilation but runs K times per dispatch, and the comm
    wrappers record that trip count so the static wire profile stays
    exact (the ``_make_local_grad_step`` convention, parallel/dp.py).
    """
    stage = lax.axis_index("stage")
    is_first = stage == 0
    is_last = stage == n_stages - 1
    tp_axis = "model" if tp > 1 else None
    b, t = tokens.shape
    assert b % n_microbatches == 0, (b, n_microbatches)
    mb = b // n_microbatches
    tokens_mb = tokens.reshape(n_microbatches, mb, t)
    n_ticks = n_microbatches + n_stages - 1
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def loss_fn(p: dict) -> jnp.ndarray:
        def tick(carry, i):
            x_prev, loss_sum = carry
            # Stage 0 injects microbatch i (clipped: bubble ticks re-embed the
            # last microbatch and the result is masked out by the schedule).
            tok_in = tokens_mb[jnp.clip(i, 0, n_microbatches - 1)]
            x_in = jnp.where(is_first[..., None, None, None],
                             llama.embed(p, tok_in, cfg), x_prev)
            h = llama.blocks_apply(p["blocks"], x_in, cfg, tp_axis=tp_axis)
            # Last stage: microbatch (i - (n_stages-1)) exits the pipe here.
            out_i = i - (n_stages - 1)
            tok_out = tokens_mb[jnp.clip(out_i, 0, n_microbatches - 1)]
            valid = is_last & (out_i >= 0)
            mb_loss = lax.cond(
                valid,
                lambda: llama.head_loss(p, h, tok_out, cfg),
                lambda: jnp.zeros((), jnp.float32))
            # The hop: activations ride the ICI ring to the next stage. The
            # last→first edge carries bubble garbage that stage 0 discards.
            # (scale=n_ticks: the scan body traces once, hops n_ticks times;
            # the backward hops autodiff adds are telemetry/comm.py's
            # documented under-count.)
            x_next = comm.ppermute(h, "stage", fwd, label="pp_activation_hop",
                                   scale=n_ticks * comm_scale)
            return (x_next, loss_sum + mb_loss), None

        x0 = jnp.zeros((mb, t, cfg.dmodel), jnp.dtype(cfg.dtype))
        (_, loss_sum), _ = lax.scan(
            tick, (x0, jnp.zeros((), jnp.float32)), jnp.arange(n_ticks))
        # LOCAL loss: nonzero only on the last stage. Do NOT psum over
        # ``stage`` here — the backward program is itself SPMD (ppermute
        # transposes hop the cotangent back up the ring), so every stage's
        # grads are reached from the last stage's seed alone; psum-ing the
        # loss first would seed all n_stages replicas and count each path
        # n_stages times. The 1/tp scaling is the model-axis counterpart.
        return loss_sum / n_microbatches / tp

    loss, grads = jax.value_and_grad(loss_fn)(params)
    return _reduce_loss_and_grads(loss, grads, tp_axis, has_data_axis, tp,
                                  comm_scale)


def _reduce_loss_and_grads(loss, grads, tp_axis, has_data_axis, tp,
                           comm_scale: int = 1):
    """Cross-stage/model/data reductions shared by all three schedules.

    ``has_data_axis=False`` with a real ``data`` axis present is the
    composed DP×PP path (``make_pipeline_overlap_*``): the cross-STAGE
    reductions still run, but the data-axis sync is left to the caller's
    ring driver — the seam where zero1/wire-compression attach."""
    loss = comm.psum(loss, "stage",  # broadcast + undo 1/tp for reporting
                     label="pp_loss_allreduce", scale=comm_scale) * tp

    def reduce_grad(name, g):
        # Block weight matrices under TP are sharded over ``model`` — their
        # local grads are complete. Everything else replicated over ``model``
        # gets partial grads from each shard: psum. Leaves outside ``blocks``
        # (embed/head/final_norm) are also replicated over ``stage`` and got
        # grads only on the stage that read them: psum over ``stage`` too.
        if tp_axis is not None and name not in _TP_COL | _TP_ROW:
            g = jax.tree.map(
                lambda x: comm.psum(x, tp_axis,
                                    label="tp_replicated_grads",
                                    scale=comm_scale), g)
        return g

    grads = {
        k: ({name: reduce_grad(name, g) for name, g in v.items()}
            if k == "blocks"
            else jax.tree.map(
                lambda g: comm.psum(g, "stage",
                                    label="pp_replicated_grads",
                                    scale=comm_scale),
                reduce_grad(k, v)))
        for k, v in grads.items()
    }
    if has_data_axis:
        # The DP×PP cross-pipeline sync — for ALL stages, not just stage 0
        # (the reference's [0,3]-only allreduce is a recorded bug).
        grads = comm.pmean(grads, "data", label="grad_allreduce",
                           scale=comm_scale)
        loss = comm.pmean(loss, "data", label="loss_allreduce",
                          scale=comm_scale)
    return loss, grads


# ------------------------------------------------------- interleaved layout

def interleave_blocks(blocks, n_stages: int, n_chunks: int):
    """Permute the stacked [L] block axis into the interleaved-schedule layout.

    The interleaved schedule assigns stage ``s`` the *non-contiguous* virtual
    stages ``c·S + s`` (chunk c ∈ [0, v)); mesh sharding over ``stage`` always
    hands each device a *contiguous* slice of the leading axis. Rather than
    reshard every step, permute once so that the contiguous local slice
    [s·L/S, (s+1)·L/S) holds exactly stage s's chunks, ordered by c:
    position ``s·(L/S) + c·per + l`` ← layer ``(c·S + s)·per + l`` with
    ``per = L/(S·v)``. `deinterleave_blocks` inverts (e.g. before comparing
    with a GPipe run or exporting a checkpoint in natural layer order).
    """
    return jax.tree.map(
        lambda x: x[_interleave_order(x.shape[0], n_stages, n_chunks)], blocks)


def deinterleave_blocks(blocks, n_stages: int, n_chunks: int):
    """Inverse of `interleave_blocks`."""
    def inv(x):
        order = _interleave_order(x.shape[0], n_stages, n_chunks)
        inverse = jnp.zeros_like(order).at[order].set(jnp.arange(order.size))
        return x[inverse]
    return jax.tree.map(inv, blocks)


# The interleaved layout is shape-identical to the natural one, so a layout
# mistake cannot be caught from the arrays. interleave_params tags the tree
# with a scalar sentinel (value encodes S and v) that make_pipeline_step
# verifies on the first call — natural-layout params under
# schedule="interleaved" (or vice versa) fail loudly instead of silently
# running layers in the wrong order. The sentinel is a float32 leaf; its
# grad is identically zero so plain Adam/SGD leave it alone, and
# make_pipeline_step additionally re-pins it after every optimizer update so
# params-coupled transforms (adamw weight decay, EMA) cannot drift it.
_LAYOUT_KEY = "blocks_layout"


def _layout_tag(n_stages: int, n_chunks: int) -> float:
    return float(n_stages * 1000 + n_chunks)


def interleave_params(params: dict, n_stages: int, n_chunks: int) -> dict:
    """`interleave_blocks` over the full param tree, plus the layout tag.

    Use this (not a bare ``dict(params, blocks=interleave_blocks(...))``)
    before ``init_state`` when training with ``schedule="interleaved"``.
    """
    out = dict(params, blocks=interleave_blocks(params["blocks"],
                                                n_stages, n_chunks))
    out[_LAYOUT_KEY] = jnp.float32(_layout_tag(n_stages, n_chunks))
    return out


def deinterleave_params(params: dict, n_stages: int, n_chunks: int) -> dict:
    """Inverse of `interleave_params` (natural layer order, tag stripped)."""
    out = dict(params, blocks=deinterleave_blocks(params["blocks"],
                                                  n_stages, n_chunks))
    out.pop(_LAYOUT_KEY, None)
    return out


def _interleave_order(n_layers: int, n_stages: int, n_chunks: int) -> jnp.ndarray:
    assert n_layers % (n_stages * n_chunks) == 0, (n_layers, n_stages, n_chunks)
    per = n_layers // (n_stages * n_chunks)
    return jnp.asarray([(c * n_stages + s) * per + l
                        for s in range(n_stages)
                        for c in range(n_chunks)
                        for l in range(per)])


def _pipeline_interleaved_loss_and_grad(params: dict, tokens: jnp.ndarray,
                                        cfg: LlamaConfig, n_stages: int,
                                        n_microbatches: int, has_data_axis: bool,
                                        tp: int = 1, comm_scale: int = 1,
                                        n_chunks: int = 2
                                        ) -> Tuple[jnp.ndarray, dict]:
    """Interleaved virtual-stage schedule (Megatron-LM's "virtual pipeline"):
    each stage holds ``v = n_chunks`` non-contiguous layer chunks and every
    microbatch rides the ICI ring v times, visiting virtual stage c·S+s on
    its c-th lap. A stage is busy v·M of the v·M + S − 1 ticks, so the
    bubble fraction drops from GPipe's (S−1)/(M+S−1) to (S−1)/(v·M+S−1) —
    the fill/drain cost is amortized over v× more (smaller) stage visits.

    Injection is grouped: microbatches enter in waves of S (ticks where
    (j − s) mod v·S < S present stage 0 with a fresh microbatch; on all other
    ticks its input is the wrap-around of an in-flight lap), so M must be a
    multiple of S. At tick j, stage s works on relative tick r = j − s:
    group g = r // (v·S), chunk c = (r mod v·S) // S, microbatch
    g·S + (r mod S); valid iff 0 ≤ r < v·M. The loss exits at stage S−1 on
    chunk v−1. Backward is the autodiff transpose of the whole scan (GPipe
    semantics): simple and exact, at the cost of stashing O(v·M) microbatch
    activations — combine with ``cfg.remat`` when memory matters; the 1F1B
    O(S) stash bound does not apply to this schedule.

    ``params["blocks"]`` must be in `interleave_blocks` layout (the local
    [L/S] slice is [v, per] chunk-major): permute with
    ``interleave_params(params, S, v)`` BEFORE ``init_state`` places the
    tree on the mesh (a later permute across the sharded stage axis would
    be an all-to-all). The layout is shape-identical to the natural one so
    it cannot be asserted from the arrays; `make_pipeline_step` checks the
    `interleave_params` layout tag on the first call instead.
    """
    stage = lax.axis_index("stage")
    is_first = stage == 0
    is_last = stage == n_stages - 1
    tp_axis = "model" if tp > 1 else None
    v = n_chunks
    b, t = tokens.shape
    assert b % n_microbatches == 0, (b, n_microbatches)
    assert n_microbatches % n_stages == 0, (n_microbatches, n_stages)
    mb = b // n_microbatches
    tokens_mb = tokens.reshape(n_microbatches, mb, t)
    n_ticks = v * n_microbatches + n_stages - 1
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def loss_fn(p: dict) -> jnp.ndarray:
        # Local blocks [L/S, ...] → [v, per, ...], chunk-major by layout.
        n_local = jax.tree.leaves(p["blocks"])[0].shape[0]
        per = n_local // v
        chunks = jax.tree.map(
            lambda x: x.reshape((v, per) + x.shape[1:]), p["blocks"])

        def tick(carry, j):
            x_prev, loss_sum = carry
            r = j - stage
            valid = (r >= 0) & (r < v * n_microbatches)
            cyc = jnp.mod(r, v * n_stages)
            c = jnp.clip(cyc // n_stages, 0, v - 1)
            mb_idx = jnp.clip(r // (v * n_stages) * n_stages
                              + jnp.mod(cyc, n_stages),
                              0, n_microbatches - 1)
            tok = tokens_mb[mb_idx]
            inject = is_first & (cyc < n_stages)
            x_in = jnp.where(inject[..., None, None, None],
                             llama.embed(p, tok, cfg), x_prev)
            chunk_c = jax.tree.map(
                lambda x: lax.dynamic_index_in_dim(x, c, keepdims=False),
                chunks)
            h = llama.blocks_apply(chunk_c, x_in, cfg, tp_axis=tp_axis)
            exit_here = is_last & (c == v - 1) & valid
            mb_loss = lax.cond(
                exit_here,
                lambda: llama.head_loss(p, h, tok, cfg),
                lambda: jnp.zeros((), jnp.float32))
            x_next = comm.ppermute(h, "stage", fwd, label="pp_activation_hop",
                                   scale=n_ticks * comm_scale)
            return (x_next, loss_sum + mb_loss), None

        x0 = jnp.zeros((mb, t, cfg.dmodel), jnp.dtype(cfg.dtype))
        (_, loss_sum), _ = lax.scan(
            tick, (x0, jnp.zeros((), jnp.float32)), jnp.arange(n_ticks))
        return loss_sum / n_microbatches / tp   # same seeding rule as GPipe

    loss, grads = jax.value_and_grad(loss_fn)(params)
    return _reduce_loss_and_grads(loss, grads, tp_axis, has_data_axis, tp,
                                  comm_scale)


def _pipeline_1f1b_loss_and_grad(params: dict, tokens: jnp.ndarray, cfg: LlamaConfig,
                                 n_stages: int, n_microbatches: int,
                                 has_data_axis: bool,
                                 tp: int = 1,
                                 comm_scale: int = 1) -> Tuple[jnp.ndarray, dict]:
    """1F1B (one-forward-one-backward) schedule, hand-written backward.

    GPipe (above) lets autodiff transpose the whole forward scan, which means
    every tick's stage input — n_microbatches + n_stages − 1 activations —
    is saved for the backward replay: activation memory grows linearly with
    the microbatch count. 1F1B interleaves each microbatch's backward as soon
    as its forward clears the last stage, so at most ``2·n_stages − 1``
    microbatch inputs are ever in flight per stage (Megatron-LM's memory
    argument; the bubble fraction itself matches GPipe). Because a ``vjp``
    closure cannot ride a ``lax.scan`` carry, the backward recomputes the
    stage forward from the stashed *input* — the standard full-recompute
    (remat) variant, so the fair time comparison is GPipe with
    ``cfg.remat=True`` (see experiments/pp_schedules.py for measurements).

    Schedule (SPMD lockstep; iteration j does one F then one B sub-tick):
    - F: stage s runs microbatch ``i_f = j − s``            (valid if 0≤i_f<M)
    - B: stage s runs microbatch ``i_b = j − 2(S−1) + s``   (valid if 0≤i_b<M)
    so the last stage backs up microbatch i immediately after forwarding it
    (same j), and the cotangent hops one stage down the ring per iteration.
    Gradient semantics are identical to GPipe: mean loss over microbatches,
    grads accumulated across B sub-ticks, one optimizer step per call.
    """
    stage = lax.axis_index("stage")
    is_first = stage == 0
    is_last = stage == n_stages - 1
    tp_axis = "model" if tp > 1 else None
    b, t = tokens.shape
    assert b % n_microbatches == 0, (b, n_microbatches)
    mb = b // n_microbatches
    tokens_mb = tokens.reshape(n_microbatches, mb, t)
    n_iters = n_microbatches + 2 * (n_stages - 1)
    n_slots = min(2 * n_stages - 1, n_microbatches)
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    bwd_perm = [(i, (i - 1) % n_stages) for i in range(n_stages)]
    dt = jnp.dtype(cfg.dtype)

    def stage_fn(p: dict, act_in: jnp.ndarray, i: jnp.ndarray):
        """One stage application for microbatch index i (clipped): embeds on
        the first stage, computes the (masked) loss on the last."""
        tok = tokens_mb[jnp.clip(i, 0, n_microbatches - 1)]
        x_in = jnp.where(is_first[..., None, None, None],
                         llama.embed(p, tok, cfg), act_in)
        h = llama.blocks_apply(p["blocks"], x_in, cfg, tp_axis=tp_axis)
        mb_loss = lax.cond(
            is_last,
            lambda: llama.head_loss(p, h, tok, cfg),
            lambda: jnp.zeros((), jnp.float32))
        return h, mb_loss

    def iteration(carry, j):
        stash, grads, loss_sum, x_fwd, g_bwd = carry

        # --- F sub-tick: forward microbatch i_f, stash its input ----------
        i_f = j - stage
        valid_f = (i_f >= 0) & (i_f < n_microbatches)
        act_in = x_fwd
        h, _ = stage_fn(params, act_in, i_f)
        slot_f = jnp.clip(i_f, 0, n_microbatches - 1) % n_slots
        old = lax.dynamic_index_in_dim(stash, slot_f, keepdims=False)
        stash = lax.dynamic_update_index_in_dim(
            stash, jnp.where(valid_f, act_in, old), slot_f, axis=0)
        x_fwd = comm.ppermute(h, "stage", fwd_perm,
                              label="pp_activation_hop",
                              scale=n_iters * comm_scale)

        # --- B sub-tick: vjp-recompute microbatch i_b from its stash ------
        i_b = j - 2 * (n_stages - 1) + stage
        valid_b = (i_b >= 0) & (i_b < n_microbatches)
        slot_b = jnp.clip(i_b, 0, n_microbatches - 1) % n_slots
        act_b = lax.dynamic_index_in_dim(stash, slot_b, keepdims=False)
        (_, mb_loss), pull = jax.vjp(
            lambda p, a: stage_fn(p, a, i_b), params, act_b)
        # Cotangent seeds: the last stage seeds from its own loss (scaled for
        # the microbatch mean and the TP loss-replica double count, as in
        # GPipe's loss_fn); every other stage seeds from the cotangent that
        # arrived down the ring. Invalid sub-ticks seed zero, which makes
        # their (finite) recomputed grads exactly zero — no masking needed.
        g_h = jnp.where((is_last | ~valid_b)[..., None, None, None],
                        jnp.zeros_like(g_bwd), g_bwd)
        g_loss = jnp.where(is_last & valid_b, 1.0 / (n_microbatches * tp), 0.0)
        dp, da = pull((g_h, g_loss.astype(jnp.float32)))
        grads = jax.tree.map(jnp.add, grads, dp)
        loss_sum = loss_sum + jnp.where(is_last & valid_b, mb_loss, 0.0)
        g_bwd = comm.ppermute(da.astype(dt), "stage", bwd_perm,
                              label="pp_cotangent_hop",
                              scale=n_iters * comm_scale)

        return (stash, grads, loss_sum, x_fwd, g_bwd), None

    stash0 = jnp.zeros((n_slots, mb, t, cfg.dmodel), dt)
    grads0 = jax.tree.map(jnp.zeros_like, params)
    act0 = jnp.zeros((mb, t, cfg.dmodel), dt)
    (_, grads, loss_sum, _, _), _ = lax.scan(
        iteration,
        (stash0, grads0, jnp.zeros((), jnp.float32), act0, act0),
        jnp.arange(n_iters))
    return _reduce_loss_and_grads(loss_sum / n_microbatches / tp, grads,
                                  tp_axis, has_data_axis, tp, comm_scale)


def _schedule_body(schedule: str, n_chunks: int) -> Callable:
    """The per-shard loss+grad body for a schedule name — the ONE lookup
    every pipeline step factory routes through, so a new factory cannot
    support a different schedule set by accident."""
    if schedule == "interleaved":
        return functools.partial(_pipeline_interleaved_loss_and_grad,
                                 n_chunks=n_chunks)
    try:
        return {"gpipe": _pipeline_loss_and_grad,
                "1f1b": _pipeline_1f1b_loss_and_grad}[schedule]
    except KeyError:
        raise ValueError(f"unknown schedule {schedule!r}: expected 'gpipe', "
                         "'1f1b' or 'interleaved'") from None


def _opt_specs(opt_state, params, specs):
    """PartitionSpecs for a pipeline optimizer state: moment subtrees
    (anything tree-isomorphic to params — adam's mu/nu) inherit the param
    specs, scalars (count) replicate — ``sharded_opt_init``'s placement
    rule as SPECS, computable from a traced state inside a jitted step
    (only tree structure is read, never values)."""
    pstruct = jax.tree.structure(params)

    def is_params_like(node):
        try:
            return jax.tree.structure(node) == pstruct
        except Exception:
            return False

    return jax.tree.map(
        lambda node: specs if is_params_like(node)
        else jax.tree.map(lambda _: P(), node),
        opt_state, is_leaf=is_params_like)


def _check_layout(params_tag, schedule: str, n_stages: int,
                  n_chunks: int) -> None:
    """The interleaved-layout sanity check shared by every factory:
    schedule="interleaved" demands the interleave_params tag for exactly
    this (S, v); any other schedule demands its absence."""
    if schedule == "interleaved":
        want = _layout_tag(n_stages, n_chunks)
        if params_tag is None:
            raise ValueError(
                "schedule='interleaved' requires params permuted with "
                "interleave_params(params, n_stages, n_chunks) before "
                "init_state — natural-layout blocks would run layers "
                "in the wrong order")
        if float(params_tag) != want:
            raise ValueError(
                f"params were interleaved for a different topology "
                f"(tag {float(params_tag):.0f}, expected {want:.0f} = "
                f"stages*1000+chunks)")
    elif params_tag is not None:
        raise ValueError(
            f"params carry the interleaved layout tag but "
            f"schedule={schedule!r} expects natural layer order — "
            f"undo with deinterleave_params first")


def _layout_guarded(jitted: Callable, schedule: str, n_stages: int,
                    n_chunks: int) -> Callable:
    """First-call layout guard around a jitted pipeline step (params are
    concrete at the Python call boundary, and reading the scalar here
    avoids a per-step host sync)."""
    checked = []

    def guarded(state: TrainState, tokens):
        if not checked:
            _check_layout(state.params.get(_LAYOUT_KEY), schedule,
                          n_stages, n_chunks)
            checked.append(True)
        return jitted(state, tokens)

    guarded.lower = jitted.lower   # AOT inspection (experiments/pp_schedules)
    if hasattr(jitted, "_cache_size"):
        # CompileWatch's compile/retrace detection reads the jit cache
        # size through whatever it wraps (introspect.CompileWatch._size);
        # without this passthrough the guard wrapper silently disables
        # compile accounting for every pipeline step factory (pinned by
        # experiments/pp_fusion_smoke.py's retrace + compile-meta gates).
        guarded._cache_size = jitted._cache_size
    return guarded


def _make_pp_local_step(cfg: LlamaConfig, optimizer, body: Callable, *,
                        n_stages: int, n_microbatches: int, has_data: bool,
                        tp: int, comm_scale: int = 1,
                        numerics=None) -> Callable:
    """The per-shard pipeline train-step body shared by the per-step
    factory (``make_pipeline_step``) and the K-step scan driver
    (``make_pipeline_multi_step``) — the ``_make_local_grad_step`` pattern
    (parallel/dp.py): one implementation, so per-step and fused dispatch
    cannot drift, and their bitwise equality at any K is a structural
    property, not a numerical accident (pinned in tests/test_pp.py for
    all three schedules).

    Runs under shard_map over (data, stage[, model]). The optimizer is
    applied to each shard's LOCAL param slice — valid for elementwise
    optimizers (sgd/adam/adamw/..., the same slice-commuting argument as
    ZeRO-1, ops/adam.py), which is every optimizer this repo ships. The
    interleaved layout tag is re-pinned exactly after the update.

    ``numerics`` (a ``make_pp_numerics`` handle): the second output
    becomes ``(loss, NumericsSummary)`` with stage-stacked group stats —
    extra OUTPUTS only, so losses/params are bitwise identical on vs off.
    """

    def local_step(state: TrainState, tokens):
        loss, grads = body(state.params, tokens, cfg, n_stages,
                           n_microbatches, has_data, tp,
                           comm_scale=comm_scale)
        params, opt_state = apply_optimizer(optimizer, grads,
                                            state.opt_state, state.params)
        if _LAYOUT_KEY in params:
            # Keep the layout tag exactly invariant under ANY optimizer —
            # zero grad does not protect it from params-coupled transforms
            # like decoupled weight decay.
            params = dict(params, **{_LAYOUT_KEY: state.params[_LAYOUT_KEY]})
        new_state = TrainState(params, opt_state, state.step + 1)
        if numerics is not None:
            summary = numerics.summarize(state.params, grads, params)
            return new_state, (loss, summary)
        return new_state, loss

    return local_step


def make_pipeline_step(cfg: LlamaConfig, optimizer: optax.GradientTransformation,
                       mesh: Mesh, n_microbatches: int = 1,
                       schedule: str = "gpipe", n_chunks: int = 2,
                       numerics=None) -> Callable:
    """jit-compiled pipeline train step over mesh axes (data, stage).

    ``n_microbatches=1`` degenerates to the reference's naive staged pipeline
    (intro_PP_1F1B.py); ``>1`` is the homework_1_b1 GPipe schedule; a mesh
    with ``data > 1`` is the homework_1_b2 DP×PP topology; adding a
    ``model`` axis gives the full 3-D DP×PP×TP layout.

    ``schedule`` selects "gpipe" (autodiff-transposed forward scan, O(M)
    activation memory), "1f1b" (interleaved hand-written backward, O(S)
    activation memory), or "interleaved" (virtual-stage schedule with
    ``n_chunks`` chunks per stage — smallest bubble, O(v·M) memory;
    requires params permuted via `interleave_params` — checked loudly on
    the first step — and n_microbatches divisible by n_stages) — all
    compute the identical gradient.

    ``numerics`` (``make_pp_numerics``) arms the in-jit run-health summary;
    the step then returns ``(state, (loss, NumericsSummary))``.

    ``optimizer`` must be ELEMENTWISE (sgd / adam / adamw / the ops/
    fused variants — everything this repo ships): the update runs inside
    shard_map on each shard's local stage slice (so the per-step and
    fused K-step drivers share one body bitwise), which is only
    equivalent to a full-tree update for transforms that commute with
    slicing. A globally-coupled transform (e.g.
    ``optax.clip_by_global_norm``) would clip per stage slice — wrong
    silently; keep such chains on the DP trainer.

    Returns ``step(state, tokens) -> (state, loss)`` where tokens is the
    global [B, T] batch, B divisible by data_size · n_microbatches.
    """
    n_stages = mesh.shape["stage"]
    has_data = mesh.shape.get("data", 1) > 1
    tp = mesh.shape.get("model", 1)
    body = _schedule_body(schedule, n_chunks)
    local_step = _make_pp_local_step(cfg, optimizer, body, n_stages=n_stages,
                                     n_microbatches=n_microbatches,
                                     has_data=has_data, tp=tp,
                                     numerics=numerics)

    def step(state: TrainState, tokens):
        specs = param_specs(state.params, tp=tp > 1)
        state_specs = TrainState(specs,
                                 _opt_specs(state.opt_state, state.params,
                                            specs), P())
        out_specs = (state_specs,
                     ((P(), numerics.summary_specs()) if numerics is not None
                      else P()))
        return shard_map(
            local_step, mesh=mesh,
            in_specs=(state_specs, P("data") if has_data else P()),
            out_specs=out_specs,
            check_vma=False,
        )(state, tokens)

    jitted = jax.jit(step, donate_argnums=(0,))
    return _layout_guarded(jitted, schedule, n_stages, n_chunks)


def make_pipeline_multi_step(cfg: LlamaConfig,
                             optimizer: optax.GradientTransformation,
                             mesh: Mesh, n_microbatches: int = 1,
                             schedule: str = "gpipe", n_chunks: int = 2,
                             numerics=None) -> Callable:
    """Fused K-step pipeline driver: ``step(state, window) -> (state,
    losses)`` where ``window`` is a device-resident ``[K, B, T]`` token
    window (leading axis = K consecutive training steps, second axis
    sharded over ``data`` on a DP×PP mesh — ``shard_batch_window``) and
    ``losses`` is the ``[K]`` per-step loss sequence from the scan's
    stacked outputs.

    One compiled, donated dispatch runs all K steps of ANY schedule
    (gpipe / 1f1b / interleaved): the per-step Python dispatch, donation
    bookkeeping and host round trip — the ~1.6× per-step tax on
    dispatch-bound hosts (PR 4 bench) that the PP schedules kept paying
    after DP stopped — are paid once per window. The scanned body IS
    ``make_pipeline_step``'s body (shared ``_make_pp_local_step``), so the
    loss sequence and final params are BITWISE identical to K per-step
    calls at K∈{1,4} for every schedule (tests/test_pp.py), and per-step
    wire bytes are unchanged — the comm profile records the same
    collectives at ``scale=K`` per dispatch
    (``CommProfile.as_dict(steps_per_dispatch=K)`` normalizes).

    K is read from the window's static leading dim at trace time, so ONE
    returned callable serves every chunk size (a tail chunk of k < K
    steps just triggers one more compile for that shape — the trainer's
    CompileWatch stamps each compile event with its actual window size).

    ``optimizer`` must be elementwise — same rule and reason as
    ``make_pipeline_step`` (the shared per-shard body applies it to the
    local stage slice).
    """
    n_stages = mesh.shape["stage"]
    has_data = mesh.shape.get("data", 1) > 1
    tp = mesh.shape.get("model", 1)
    body = _schedule_body(schedule, n_chunks)

    def step(state: TrainState, window):
        specs = param_specs(state.params, tp=tp > 1)
        state_specs = TrainState(specs,
                                 _opt_specs(state.opt_state, state.params,
                                            specs), P())

        def multi(st, win):
            local_step = _make_pp_local_step(
                cfg, optimizer, body, n_stages=n_stages,
                n_microbatches=n_microbatches, has_data=has_data, tp=tp,
                comm_scale=win.shape[0], numerics=numerics)
            return lax.scan(local_step, st, win)

        out_specs = (state_specs,
                     ((P(), numerics.summary_specs(stacked=True))
                      if numerics is not None else P()))
        return shard_map(
            multi, mesh=mesh,
            in_specs=(state_specs, P(None, "data") if has_data else P()),
            out_specs=out_specs,
            check_vma=False,
        )(state, window)

    jitted = jax.jit(step, donate_argnums=(0,))
    return _layout_guarded(jitted, schedule, n_stages, n_chunks)


# ------------------------------------------- DP×PP data-axis ring drivers
#
# The fused hot path built for DP (PRs 3/10/12) stops at the data mesh:
# ZeRO-1 sliced updates, wire-compressed ring reduce-scatter and ACCO-style
# microbatch overlap all assume the step sees the FULL params tree. Under
# DP×PP each (data, stage) shard holds one stage's slice, but the data-axis
# sync of the CROSS-STAGE-REDUCED gradient has exactly the same shape as
# flat DP's: flatten the LOCAL stage tree, ring it over ``data``, update
# the owned 1/n slice, gather the fresh slices back. The drivers below
# compose the existing machinery (compress.ring_reduce_scatter, the int8
# encode + EF-residual discipline, dp.slice_index's data-rank ownership)
# with the pipeline schedule bodies — the one new piece is the residual /
# moment layout, which gains a ``stage`` axis ([n_data, n_stages, ...],
# sharded P("data", "stage")) because each stage's shard group compensates
# its own stage's quantization error. With a real ``model`` axis in the
# mesh (DP×PP×TP) the layout gains one more trailing shard axis and the
# schedule bodies run their Megatron-TP partial forms — the composition
# rule that replaced the old model=1 hard error (see parallel/tp.py's
# DP×TP section for the TP-mesh counterpart and the int8 cross-model
# scale caveat, which applies to the stage/model-replicated leaves here
# identically).


def _pp_flat_geometry(mesh: Mesh, params):
    """Padded flat-vector geometry of the LOCAL per-(stage[, model])-shard
    param tree — the unit the DP×PP data-axis zero1/ring sync operates on.
    Every stage's local tree has the same flat length (equal [L/S] block
    slices + the stage-replicated embed/head/final_norm), and on a
    DP×PP×TP mesh the column/row-sharded block leaves additionally
    contribute 1/tp of their elements, identically on every model shard —
    so the geometry is SPMD-consistent across both non-data axes. Returns
    ``(n, pad, local, total)`` with n = the ``data`` axis size and total =
    the per-shard param count."""
    n = mesh.shape.get("data", 1)
    n_stages = mesh.shape["stage"]
    tp = mesh.shape.get("model", 1)
    total = 0
    for k, v in params.items():
        if k == "blocks":
            for name, leaf in v.items():
                size = sum(int(x.size) for x in jax.tree.leaves(leaf))
                size //= n_stages
                if name in _TP_COL or name in _TP_ROW:
                    size //= tp
                total += size
        else:
            total += sum(int(x.size) for x in jax.tree.leaves(v))
    pad = (-total) % n
    local = (total + pad) // n
    return n, pad, local, total


def _pp_bucket_map(mesh: Mesh, params, comm_buckets: int):
    """The DP×PP ``BucketMap``: ``compress.make_bucket_map`` over the
    PER-CELL leaf geometry — each (stage[, model]) cell's local tree
    (stage block slices of [L/S] layers, col/row leaves at 1/tp, the
    stage-replicated embed/head/final-norm in full), which is the tree
    the shard_map body actually flattens. Returns None at
    ``comm_buckets == 1`` (the legacy single-vector path)."""
    from .compress import make_bucket_map

    if int(comm_buckets) < 1:
        raise ValueError(
            f"comm_buckets must be >= 1 (got {comm_buckets})")
    if int(comm_buckets) == 1:
        return None
    n = mesh.shape.get("data", 1)
    n_stages = mesh.shape["stage"]
    tp = mesh.shape.get("model", 1)

    def leaf_local(path, leaf):
        key = getattr(path[0], "key", None) if path else None
        if key == "blocks":
            name = getattr(path[1], "key", None) if len(path) > 1 else None
            size = int(leaf.size) // n_stages
            if name in _TP_COL or name in _TP_ROW:
                size //= tp
            return size, int(leaf.shape[0]) // n_stages
        return int(leaf.size), None

    return make_bucket_map(params, n, comm_buckets, leaf_local=leaf_local)


def _pp_overlap_setup(optimizer, mesh: Mesh, params, wire: str,
                      aggregation: str, schedule: str, n_chunks: int,
                      comm_buckets: int = 1):
    """State + shard specs + flat geometry for the DP×PP overlap drivers.

    ZeRO-1 moments live as ``[n_data, n_stages, local]`` global arrays
    sharded ``P("data", "stage")`` — each (d, s) shard owns the moments of
    stage s's d-th flat slice (the ``dp.slice_index`` data-rank ownership
    map applied per stage group); int8 EF residuals get the same layout
    (ring: ``[n, S, n·local]``; gather: ``[n, S, local]``), because each
    (data, stage) shard compensates its OWN quantization error.

    On a DP×PP×TP mesh (``model > 1`` — the composition rule the TP PSA
    work lifted the old model=1 hard error into, see parallel/tp.py's
    DP×TP section) every per-shard layout gains a trailing ``model``
    axis: moments ``[n, S, tp, local]``, residuals
    ``[n, S, tp, n·local | local]``, sharded ``P("data", "stage",
    "model")`` — each (d, s, m) shard rings its OWN per-model-shard flat
    slice over ``data``, so the rings on different model coordinates are
    independent. The tp == 1 layouts stay byte-identical to the classic
    DP×PP ones (checkpoint compatibility).

    ``comm_buckets > 1`` (the bucketed backward, ``compress.BucketMap``
    over the PER-CELL geometry — ``_pp_bucket_map``) turns the ZeRO-1
    moments and both EF residuals into per-bucket tuples, mirroring the
    DP driver's layout rule with the (stage[, model]) shard axes kept."""
    if aggregation not in ("gradient", "zero1"):
        raise ValueError("the DP×PP overlap driver supports gradient/zero1 "
                         f"aggregation only (got {aggregation!r})")
    if wire not in ("fp32", "bf16", "int8_ef"):
        raise ValueError(f"unknown wire format {wire!r}")
    if "data" not in mesh.axis_names:
        raise ValueError("the DP×PP overlap driver needs a mesh with a "
                         "'data' axis (size 1 is fine) — build it with "
                         'make_mesh({"data": d, "stage": s})')
    if mesh.shape.get("dcn", 1) > 1:
        raise ValueError("the DP×PP overlap driver runs the flat data ring "
                         "only; the hierarchical (dcn x data) tier is the "
                         "DP trainer's (parallel/compress.py)")
    tp = mesh.shape.get("model", 1)
    n_stages = mesh.shape["stage"]
    # Leading shard axes the per-shard [local] views are wrapped in:
    # (data, stage) on the classic DP×PP mesh, (data, stage, model) once
    # a real model axis joins. tp == 1 keeps the old 2-axis layout so
    # existing checkpoints round-trip byte-identically.
    lead = 3 if tp > 1 else 2
    dshard = (P("data", "stage", "model") if tp > 1
              else P("data", "stage"))
    _check_layout(params.get(_LAYOUT_KEY), schedule, n_stages, n_chunks)
    n, pad, local, total = _pp_flat_geometry(mesh, params)
    bm = _pp_bucket_map(mesh, params, comm_buckets)
    specs = param_specs(params, tp=tp > 1)
    sharded = shard_params(mesh, params)
    step0 = jax.device_put(jnp.zeros((), jnp.int32),
                           NamedSharding(mesh, P()))
    if aggregation == "zero1":
        sizes = bm.sizes if bm is not None else (local,)

        def _specs_for(sz):
            abstract = jax.eval_shape(
                optimizer.init, jax.ShapeDtypeStruct((sz,), jnp.float32))
            return jax.tree.map(
                lambda x: dshard if getattr(x, "ndim", 0) >= 1 else P(),
                abstract)

        opt_specs = (_specs_for(local) if bm is None
                     else tuple(_specs_for(sz) for sz in sizes))

        def local_init(p):
            from ..utils import pytree as pt
            from .compress import _bucket_vectors
            shard = lax.axis_index("data")
            if bm is None:
                flat = jnp.pad(pt.flatten(p)[0].astype(jnp.float32),
                               (0, pad))
                mine = [lax.dynamic_slice_in_dim(flat, shard * local,
                                                 local)]
            else:
                vecs = _bucket_vectors(bm, p)
                mine = [lax.dynamic_slice_in_dim(
                    vecs[b], shard * bm.sizes[b], bm.sizes[b])
                    for b in range(bm.nbuckets)]
            # Vector leaves gain the (data, stage[, model]) shard axes;
            # scalars (count) replicate — every shard steps them
            # identically.
            opts = [jax.tree.map(
                lambda x: (x[(None,) * lead]
                           if getattr(x, "ndim", 0) >= 1 else x),
                optimizer.init(m)) for m in mine]
            return opts[0] if bm is None else tuple(opts)

        opt_state = jax.jit(shard_map(
            local_init, mesh=mesh, in_specs=(specs,),
            out_specs=opt_specs, check_vma=False))(sharded)
        state = TrainState(sharded, opt_state, step0)
    else:
        opt_state = sharded_opt_init(mesh, sharded, optimizer, specs)
        opt_specs = _opt_specs(opt_state, sharded, specs)
        state = TrainState(sharded, opt_state, step0)
    if wire == "int8_ef":
        from .compress import OverlapEFState
        mid = (n_stages, tp) if tp > 1 else (n_stages,)

        def _zeros(shape):
            return jax.device_put(jnp.zeros(shape, jnp.float32),
                                  NamedSharding(mesh, dshard))

        if bm is None:
            ring_res = _zeros((n,) + mid + (n * local,))
            gather_res = _zeros((n,) + mid + (local,))
            ring_specs = gather_specs = dshard
        else:
            ring_res = tuple(_zeros((n,) + mid + (n * sz,))
                             for sz in bm.sizes)
            gather_res = tuple(_zeros((n,) + mid + (sz,))
                               for sz in bm.sizes)
            ring_specs = gather_specs = (dshard,) * bm.nbuckets
        state = OverlapEFState(state.params, state.opt_state, state.step,
                               ring_res, gather_res)
        state_specs = OverlapEFState(specs, opt_specs, P(), ring_specs,
                                     gather_specs)
    else:
        state_specs = TrainState(specs, opt_specs, P())
    return state, state_specs, n, pad, local, total, bm


def _stage_coord_ids(params, n: int, n_stages: int, comm_buckets: int):
    """Global-coordinate id layout of the DP×PP flat state space: for each
    stage ``s`` and ring bucket ``b``, the int64 array mapping every slot of
    the ``[n·sizes[b]]`` bucket vector (data-row-major: row ``r`` owns slots
    ``[r·sizes[b], (r+1)·sizes[b])``) to a unique id over the GLOBAL param
    coordinates, with ``-1`` marking pad slots. Ids are assigned in tree
    order over the global leaves; a stage's block slice maps to the
    contiguous ``[s·gsz/S, (s+1)·gsz/S)`` range of its leaf's ravel (the
    blocked layer layout), and stage-replicated leaves (embed/head/
    final-norm) share one id range across stages.

    This is the coordinate system ``repartition_stage_state`` reshards
    through: a value's id is topology-invariant, so gathering an old
    ``(n, S)`` stack by id and re-reading it at ``(n', S')`` is a bitwise
    per-coordinate copy whatever moved — the data world, the stage count,
    or both. Returns ``(ids, sizes, total_coords)`` with ``ids[s][b]`` the
    per-(stage, bucket) map and ``sizes`` the per-shard bucket sizes."""
    from .compress import make_bucket_map

    entries = jax.tree_util.tree_flatten_with_path(params)[0]
    bases, metas = [], []
    off = 0
    for path, leaf in entries:
        key = getattr(path[0], "key", None) if path else None
        gsz = int(np.prod(np.shape(leaf), dtype=int))
        is_block = key == "blocks"
        if is_block and gsz % n_stages:
            raise ValueError(f"blocks leaf of {gsz} elements does not "
                             f"split over {n_stages} stages")
        bases.append(off)
        metas.append((is_block, gsz, gsz // n_stages if is_block else gsz))
        off += gsz
    total_coords = off

    def local_ids(s):
        out = []
        for base, (is_block, gsz, lsz) in zip(bases, metas):
            start = base + s * lsz if is_block else base
            out.append(np.arange(start, start + lsz, dtype=np.int64))
        return out

    B = int(comm_buckets)
    if B == 1:
        total = sum(lsz for _, _, lsz in metas)
        pad = (-total) % n
        sizes = ((total + pad) // n,)
        ids = [[np.concatenate(local_ids(s)
                               + [np.full((pad,), -1, np.int64)])]
               for s in range(n_stages)]
        return ids, sizes, total_coords

    def leaf_local(path, leaf):
        key = getattr(path[0], "key", None) if path else None
        if key == "blocks":
            return (int(np.prod(np.shape(leaf), dtype=int)) // n_stages,
                    int(np.shape(leaf)[0]) // n_stages)
        return int(np.prod(np.shape(leaf), dtype=int)), None

    bm = make_bucket_map(params, n, B, leaf_local=leaf_local)
    ids = []
    for s in range(n_stages):
        lids = local_ids(s)
        per_bucket = []
        for b, pieces in enumerate(bm.pieces):
            parts = [lids[li][st:st + sz] for li, st, sz in pieces]
            if b == bm.nbuckets - 1 and bm.pad:
                parts.append(np.full((bm.pad,), -1, np.int64))
            per_bucket.append(np.concatenate(parts))
        ids.append(per_bucket)
    return ids, bm.sizes, total_coords


def repartition_stage_state(host_state, template_state):
    """Stage re-partition / data reshard of a DP×PP overlap-state host
    snapshot: rewrite the ``(data, stage)``-stacked ZeRO-1 moments
    (``[n, S, local]``, per-bucket tuples under ``comm_buckets > 1``), ring
    EF residuals (``[n, S, n·local]``) and gather residuals
    (``[n, S, local]``) from the snapshot's ``(n, S)`` topology to the
    template's ``(n', S')`` — S may change (layer re-partition after a
    stage loss), n may change (data-axis shrink/grow on the DP×PP mesh),
    or both. Equal-shape leaves — global params (``blocks`` keeps its
    ``[n_layers, ...]`` shape at ANY stage count), per-leaf moments of the
    gradient-aggregation path, scalars — pass through untouched for
    ``reshard_state``'s placement rule.

    Mechanism: every state coordinate gets a topology-invariant global id
    (``_stage_coord_ids``); the old stacks scatter by id into one global
    vector per (row, bucket) and the new stacks gather back — a bitwise
    per-coordinate copy, the stage-axis generalization of
    ``dp._resize_ring_residual``'s pad swap. Conventions carried over from
    the data-only path: values in pad slots must be exactly zero (hard
    error, never silent truncation); ring rows beyond the new data world
    are dropped with their shards, new rows start at zero error, and each
    surviving row's own-chunk slot re-zeros in the new geometry.
    Stage-replicated leaves (embed/head/final-norm) carry identical
    moments on every stage (their gradients are stage-psum'd), so the
    by-id overwrite is value-stable; ring residuals there keep the
    highest surviving stage's pending error (deterministic — both
    recovery paths and the fresh-run comparison all route through here).

    Named errors: bucket-count mismatches (rebucketing a live EF state is
    undefined), an interleaved layout across a stage-count change (the
    chunked layer order breaks the blocked-slice id map), a model axis in
    the template mesh (DP×PP×TP elastic is out of scope), and an ``S'``
    that does not divide ``n_layers``."""
    t_arrays = [x for x in jax.tree.leaves(template_state)
                if isinstance(x, jax.Array)]
    if not t_arrays:
        return host_state
    mesh = t_arrays[0].sharding.mesh
    if mesh.shape.get("model", 1) > 1:
        raise ValueError(
            "elastic re-mesh of the DP×PP×TP overlap state is unsupported "
            "— the (data, stage, model) stacks have no reshard rule; run "
            "elastic DP×PP at model=1")
    n_new = int(mesh.shape.get("data", 1))
    s_new = int(mesh.shape["stage"])

    def _stacks(state):
        """(opt vector stacks, ring tuple, gather tuple) — tuples
        normalized to per-bucket lists; None where the field is absent."""
        ring = getattr(state, "ring_residual", None)
        gather = getattr(state, "gather_residual", None)
        as_list = (lambda x: None if x is None
                   else (list(x) if isinstance(x, tuple) else [x]))
        return as_list(ring), as_list(gather)

    h_ring, h_gather = _stacks(host_state)
    t_ring, t_gather = _stacks(template_state)
    if (h_ring is None) != (t_ring is None) or (
            h_ring is not None and len(h_ring) != len(t_ring)):
        raise ValueError(
            f"comm_buckets mismatch: the snapshot carries "
            f"{len(h_ring) if h_ring else 0} EF residual bucket(s), the "
            f"template {len(t_ring) if t_ring else 0} — rebucketing a "
            "live EF state is not defined; rebuild the trainer with the "
            "snapshot's comm_buckets")

    # The snapshot's (n, S) topology, read off the stacked leaves whose
    # shapes DIFFER from the template's. Shape-equal 3-D leaves must pass
    # through untouched — the gradient-aggregation path's param-shaped
    # moments (blocks [L, d, d]) are global arrays, not stacks — so only
    # mismatched pairs identify the (data, stage) stacks to rewrite.
    pairs = set()

    def _note(h, t):
        hs, ts = tuple(np.shape(h)), tuple(np.shape(t))
        if len(hs) == 3 and len(ts) == 3 and hs != ts:
            pairs.add((hs[:2], ts[:2]))

    jax.tree.map(_note, host_state.opt_state, template_state.opt_state)
    for h, t in zip(h_ring or [], t_ring or []):
        _note(h, t)
    for h, t in zip(h_gather or [], t_gather or []):
        _note(h, t)
    if not pairs:
        return host_state       # same topology: placement-only reshard
    olds = {o for o, _ in pairs}
    news = {t for _, t in pairs}
    if len(olds) != 1 or len(news) != 1:
        raise ValueError(
            f"inconsistent (data, stage) stack topologies across the "
            f"snapshot/template state: {sorted(olds)} -> {sorted(news)} — "
            "the stacks of one overlap state must share one layout")
    (n_old, s_old), = olds
    n_old, s_old = int(n_old), int(s_old)
    if next(iter(news)) != (n_new, s_new):
        raise ValueError(
            f"template stacks are laid out {next(iter(news))} but its "
            f"mesh is (data={n_new}, stage={s_new}) — not a DP×PP "
            "overlap template")

    params = host_state.params
    blocks = params.get("blocks", {})
    n_layers = int(np.shape(jax.tree.leaves(blocks)[0])[0]) if blocks else 0
    for s, tag in ((s_old, "snapshot"), (s_new, "template")):
        if n_layers and n_layers % s:
            raise ValueError(
                f"stage re-partition: the {tag}'s stage count {s} does "
                f"not divide n_layers={n_layers} — layers shard as equal "
                "[n_layers/S] blocks, so S' must divide n_layers")
    if s_old != s_new and _LAYOUT_KEY in params:
        raise ValueError(
            "stage re-partition of an interleaved layout is unsupported: "
            "the chunk-major layer order breaks the blocked [L/S] stage "
            "slices the re-partition re-slices — run elastic PP with "
            "schedule='gpipe' or '1f1b'")

    # Bucket structure. The ring-bucket count splits every per-shard flat
    # slice into per-bucket stacks, and the ZeRO-1 opt tree mirrors it as
    # a TOP-LEVEL tuple of per-bucket optax states. With EF residuals the
    # count is the residual tuple's; without them a bucketed opt tuple
    # must be told apart from a single optax state (which is itself a
    # tuple) — done by checking which bucket geometry actually explains
    # the mismatched stack sizes.
    def _mismatch_dims(opt, t_opt):
        dims = []

        def leaf(x, t):
            hs, ts = tuple(np.shape(x)), tuple(np.shape(t))
            if len(hs) == 3 and hs != ts:
                dims.append(int(hs[2]))

        jax.tree.map(leaf, opt, t_opt)
        return dims

    def _explains(nb_try):
        try:
            sizes = _stage_coord_ids(params, n_old, s_old, nb_try)[1]
        except ValueError:
            return False
        if nb_try == 1:
            return all(d == sizes[0]
                       for d in _mismatch_dims(host_state.opt_state,
                                               template_state.opt_state))
        if not (isinstance(host_state.opt_state, tuple)
                and len(host_state.opt_state) == nb_try):
            # nb buckets but no per-bucket opt tuple: legal only when the
            # opt tree has no stacks at all (gradient aggregation keeps
            # param-shaped global moments).
            return not _mismatch_dims(host_state.opt_state,
                                      template_state.opt_state)
        return all(d == sizes[b]
                   for b in range(nb_try)
                   for d in _mismatch_dims(host_state.opt_state[b],
                                           template_state.opt_state[b]))

    if h_ring is not None:
        nb = len(h_ring)
        if not _explains(nb):
            raise ValueError(
                f"DP×PP overlap snapshot does not match its own bucket "
                f"geometry ({nb} bucket(s) at data={n_old}, "
                f"stage={s_old}) — refusing to re-partition")
    else:
        cands = [1] + ([len(host_state.opt_state)]
                       if isinstance(host_state.opt_state, tuple) else [])
        nb = next((c for c in cands if _explains(c)), None)
        if nb is None:
            raise ValueError(
                "cannot infer the bucket structure of the DP×PP ZeRO-1 "
                "stacks — the mismatched stack sizes fit neither a "
                "single-bucket nor a per-bucket tuple layout")
    opt_bucketed = (nb > 1 and isinstance(host_state.opt_state, tuple)
                    and len(host_state.opt_state) == nb
                    and bool(_mismatch_dims(host_state.opt_state,
                                            template_state.opt_state)))
    ids_old, sizes_old, total_coords = _stage_coord_ids(
        params, n_old, s_old, nb)
    ids_new, sizes_new, _ = _stage_coord_ids(params, n_new, s_new, nb)

    def _scatter(g, vals, ids, what):
        pad = ids < 0
        if np.any(vals[pad] != 0):
            raise ValueError(
                f"nonzero {what} values in the flat pad tail — the "
                "snapshot does not look like a zero-padded DP×PP stack")
        g[ids[~pad]] = vals[~pad]

    # A coordinate's bucket changes with the topology (bucket boundaries
    # are carved out of the per-stage LOCAL geometry), so a field's
    # buckets pool into ONE global id-indexed vector before the new
    # layout gathers back — a per-bucket-independent remap would drop
    # every coordinate that migrated buckets.
    def _stacks_to_global(stacks, what):
        g = None
        for b, h in enumerate(stacks):
            h = np.asarray(h)
            if h.shape != (n_old, s_old, sizes_old[b]):
                raise ValueError(
                    f"{what} stack has shape {h.shape}, expected "
                    f"{(n_old, s_old, sizes_old[b])}")
            if g is None:
                g = np.zeros((total_coords,), h.dtype)
            for s in range(s_old):
                _scatter(g, np.ascontiguousarray(h[:, s]).reshape(-1),
                         ids_old[s][b], what)
        return g

    def _global_to_stacks(g, dtype):
        out = []
        for b in range(nb):
            ob = np.zeros((n_new, s_new, sizes_new[b]), dtype)
            for s2 in range(s_new):
                ids = ids_new[s2][b]
                vals = np.where(ids >= 0, g[np.clip(ids, 0, None)], 0)
                ob[:, s2] = vals.reshape(n_new, sizes_new[b]).astype(dtype)
            out.append(ob)
        return out

    def _remap_field(stacks, what):
        g = _stacks_to_global(stacks, what)
        return _global_to_stacks(g, np.asarray(stacks[0]).dtype)

    def _remap_ring_field(rings):
        dtype = np.asarray(rings[0]).dtype
        outs = [np.zeros((n_new, s_new, n_new * sizes_new[b]), dtype)
                for b in range(nb)]
        for r in range(min(n_old, n_new)):
            g = np.zeros((total_coords,), dtype)
            for b, h in enumerate(rings):
                h = np.asarray(h)
                if h.shape != (n_old, s_old, n_old * sizes_old[b]):
                    raise ValueError(
                        f"ring_residual stack has shape {h.shape}, "
                        f"expected "
                        f"{(n_old, s_old, n_old * sizes_old[b])}")
                for s in range(s_old):
                    _scatter(g, h[r, s], ids_old[s][b], "ring_residual")
            for b in range(nb):
                for s2 in range(s_new):
                    ids = ids_new[s2][b]
                    outs[b][r, s2] = np.where(ids >= 0,
                                              g[np.clip(ids, 0, None)], 0)
                # The owner never quantizes its own chunk — structurally
                # zero, but the chunk boundaries moved with (n', S').
                outs[b][r, :,
                        r * sizes_new[b]:(r + 1) * sizes_new[b]] = 0.0
        return outs

    def _remap_opt_tree(opts, t_opts):
        """Remap the stacked leaves of per-bucket same-treedef opt states
        jointly (leaf j of bucket b is one field's bucket-b stack)."""
        flat = [jax.tree_util.tree_flatten(o) for o in opts]
        t_flat = [jax.tree_util.tree_flatten(o)[0] for o in t_opts]
        leaves = [list(f[0]) for f in flat]
        for j in range(len(leaves[0])):
            hs = tuple(np.shape(leaves[0][j]))
            ts = tuple(np.shape(t_flat[0][j]))
            if len(hs) == 3 and hs != ts:
                outs = _remap_field([leaves[b][j] for b in range(nb)],
                                    "opt_state")
                for b in range(nb):
                    leaves[b][j] = outs[b]
        return [jax.tree_util.tree_unflatten(flat[b][1], leaves[b])
                for b in range(nb)]

    if opt_bucketed:
        new_opt = tuple(_remap_opt_tree(list(host_state.opt_state),
                                        list(template_state.opt_state)))
    else:
        new_opt = _remap_opt_tree([host_state.opt_state],
                                  [template_state.opt_state])[0]
    host_state = host_state._replace(opt_state=new_opt)
    if h_ring is not None:
        ring = _remap_ring_field(h_ring)
        gather = _remap_field(h_gather, "gather_residual")
        host_state = host_state._replace(
            ring_residual=tuple(ring) if len(ring) > 1 else ring[0],
            gather_residual=tuple(gather) if len(gather) > 1 else gather[0])
    return host_state


def _make_pp_overlap_local_step(cfg: LlamaConfig, optimizer, body: Callable,
                                *, n_stages: int, n_microbatches: int,
                                tp: int, n: int, pad: int, local: int,
                                total: int, microbatches: int, wire: str,
                                aggregation: str, comm_scale: int = 1,
                                bucket_map=None,
                                numerics=None) -> Callable:
    """The per-shard DP×PP overlapped step body shared by
    ``make_pipeline_overlap_step`` and ``make_pipeline_overlap_multi_step``.

    Structure per step (under shard_map over (data, stage)): the local
    batch splits into M sync-microbatches; each runs the FULL pipeline
    schedule (with its own n_microbatches pipeline microbatches) via the
    shared schedule body called with ``has_data_axis=False`` — the
    cross-STAGE reductions still run, but the data-axis pmean is replaced
    by the ring: microbatch m−1's flat cross-stage-reduced gradient rides
    the ppermute ring (``compress.ring_reduce_scatter`` over ``data``, in
    the ``wire`` format with per-(shard, chunk) error feedback) in the same
    trace positions as microbatch m's schedule — the ACCO overlap, now
    under the pipeline. Reduced chunks accumulate in fp32 on the owner;
    zero1 updates the owned slice and gathers fresh params (int8 delta
    gather under ``wire="int8_ef"`` — everyone applies the same quantized
    deltas, so replicas stay bitwise in sync), gradient aggregation
    gathers the reduced gradient (in the wire format) and applies the
    replicated update.

    Numerics contract mirrors the flat driver's
    (``compress._make_overlap_local_step``): M>1 re-associates (reduce-
    then-accumulate vs the pmean path's accumulate-then-reduce), so
    equivalence vs ``make_pipeline_step`` is fp32-tolerance; M=1 fp32
    differs only by ring-vs-XLA reduction order. The interleaved layout
    tag re-pins exactly after the flat update round-trip.

    ``bucket_map`` (``compress.BucketMap`` over the per-cell geometry,
    None for the legacy single-vector path) selects the bucketed
    backward: per-bucket rings in VJP emission order under
    ``pp_ring_grad_b{b}`` labels, single-collective gather legs, and
    per-bucket moment/residual tuples — the DP driver's rules
    (``compress._make_overlap_local_step``) under the pipeline."""
    from ..utils import pytree as pt
    from .compress import (_bucket_slices, _bucket_vectors, _int8_encode,
                           _scatter_buckets, ring_reduce_scatter)

    M = microbatches
    bm = bucket_map
    B = bm.nbuckets if bm is not None else 1
    ef = wire == "int8_ef"
    # Leading shard axes wrapping the per-shard [local] state views:
    # (data, stage) classically, (data, stage, model) on a DP×PP×TP mesh
    # (layout rule in _pp_overlap_setup).
    lead = 3 if tp > 1 else 2
    # Cell-agreed int8 scales (compress._int8_encode docstring): each
    # (stage[, model]) cell's flat vector mixes cell-SPECIFIC leaves (the
    # stage's block slice, its col/row shards) with leaves REPLICATED
    # across those axes (embed/head/final-norm over stage, norm scales
    # over model), so per-cell scales would decode the replicated entries
    # differently per cell and silently drift the replicas apart — the
    # stage axis always needs the agreement, the model axis joins on the
    # composed DP×PP×TP mesh. Pinned by the replica-sync tests in
    # tests/test_pp.py.
    ssync = ("stage", "model") if tp > 1 else ("stage",)

    def _ring_all(pending, ring_res):
        # pending: the flat vector (bm None) or the per-bucket vector
        # list; ring_res mirrors it. Returns the owned [local] slice
        # (concat of per-bucket chunks when bucketed).
        if bm is None:
            return ring_reduce_scatter(
                pending, "data", wire=wire, residual=ring_res,
                label="pp_ring_grad", comm_scale=comm_scale,
                scale_sync_axis=ssync)
        reds, news = [], []
        for b in range(B):
            red_b, r_b = ring_reduce_scatter(
                pending[b], "data", wire=wire,
                residual=ring_res[b] if ef else None,
                label=f"pp_ring_grad_b{b}", comm_scale=comm_scale,
                scale_sync_axis=ssync)
            reds.append(red_b)
            news.append(r_b)
        return jnp.concatenate(reds), news

    def local_step(state, tokens):
        params = state.params
        if tokens.shape[0] % M:
            raise ValueError(f"local batch {tokens.shape[0]} not divisible "
                             f"by overlap_microbatches={M}")
        micro = tokens.reshape((M, -1) + tokens.shape[1:])
        if not ef:
            ring_res = None
        elif bm is None:
            ring_res = state.ring_residual[(0,) * lead]
        else:
            ring_res = [r[(0,) * lead] for r in state.ring_residual]
        acc = jnp.zeros((local,), jnp.float32)
        loss_sum = jnp.zeros((), jnp.float32)
        gacc = None
        pending = None
        for m in range(M):
            l, g = body(params, micro[m], cfg, n_stages, n_microbatches,
                        False, tp, comm_scale=comm_scale)
            loss_sum = loss_sum + l.astype(jnp.float32)
            if numerics is not None:
                # Extra OUTPUT only: the fp32 grad accumulator feeds the
                # summary, never the ring — losses/params bitwise on/off.
                gacc = (jax.tree.map(lambda x: x.astype(jnp.float32), g)
                        if gacc is None else
                        jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                     gacc, g))
            if pending is not None:
                # Microbatch m−1's ring rides alongside microbatch m's
                # schedule (the body call above): independent dataflow.
                red, ring_res = _ring_all(pending, ring_res)
                acc = acc + red
            pending = (_bucket_vectors(bm, g) if bm is not None else
                       jnp.pad(pt.flatten(g)[0].astype(jnp.float32),
                               (0, pad)))
        red, ring_res = _ring_all(pending, ring_res)
        acc = acc + red
        g_mine = acc / (n * M)      # mean over data shards and microbatches
        loss = comm.pmean(loss_sum / M, "data", label="loss_allreduce",
                          scale=comm_scale)

        raw_flat, unravel = pt.flatten(params)
        if bm is None:
            flat_p = jnp.pad(raw_flat.astype(jnp.float32), (0, pad))
            pvecs = None
        else:
            flat_p = None
            pvecs = _bucket_vectors(bm, params)
        gather_res = None
        shard = lax.axis_index("data")
        if aggregation == "zero1":
            if bm is None:
                p_mine = lax.dynamic_slice_in_dim(flat_p, shard * local,
                                                  local)
                # Local moment view: (data, stage[, model])-sharded vector
                # leaves squeeze to the flat slice; scalars pass.
                opt_local = jax.tree.map(
                    lambda x: (x[(0,) * lead]
                               if getattr(x, "ndim", 0) >= lead + 1 else x),
                    state.opt_state)
                new_p_mine, opt_local = apply_optimizer(optimizer, g_mine,
                                                        opt_local, p_mine)
                opt_state = jax.tree.map(
                    lambda x: (x[(None,) * lead]
                               if getattr(x, "ndim", 0) >= 1 else x),
                    opt_local)
            else:
                # One optimizer apply per bucket against the per-bucket
                # moment tuple (layout rule in _pp_overlap_setup).
                p_chunks = [lax.dynamic_slice_in_dim(
                    pvecs[b], shard * bm.sizes[b], bm.sizes[b])
                    for b in range(B)]
                new_chunks, opts = [], []
                for b in range(B):
                    opt_local = jax.tree.map(
                        lambda x: (x[(0,) * lead]
                                   if getattr(x, "ndim", 0) >= lead + 1
                                   else x),
                        state.opt_state[b])
                    np_b, opt_local = apply_optimizer(
                        optimizer,
                        g_mine[bm.offsets[b]:bm.offsets[b] + bm.sizes[b]],
                        opt_local, p_chunks[b])
                    new_chunks.append(np_b)
                    opts.append(jax.tree.map(
                        lambda x: (x[(None,) * lead]
                                   if getattr(x, "ndim", 0) >= 1 else x),
                        opt_local))
                p_mine = jnp.concatenate(p_chunks)
                new_p_mine = jnp.concatenate(new_chunks)
                opt_state = tuple(opts)
            vec_new = None
            if wire == "int8_ef":
                # Compressed second leg: broadcast the param DELTA int8
                # with its own EF residual (the compress.py zero1 rule —
                # fp32 moments stay exact, replicas stay bitwise in sync).
                gres = (jnp.concatenate(
                    [r[(0,) * lead] for r in state.gather_residual])
                    if bm is not None
                    else state.gather_residual[(0,) * lead])
                q, s, gather_res = _int8_encode(
                    (new_p_mine - p_mine) + gres,
                    scale_sync_axis=ssync)
                q_all = comm.all_gather(q, "data", tiled=True,
                                        label="pp_delta_gather_int8",
                                        scale=comm_scale)
                s_all = comm.all_gather(s[None], "data", tiled=True,
                                        label="pp_delta_scale_gather",
                                        scale=comm_scale)
                if bm is None:
                    flat_new = flat_p + (jnp.repeat(s_all, local)
                                         * q_all.astype(jnp.float32))
                else:
                    q_slc = _bucket_slices(bm, q_all.astype(jnp.float32))
                    vec_new = [pvecs[b]
                               + jnp.repeat(s_all, bm.sizes[b]) * q_slc[b]
                               for b in range(B)]
            else:
                # bf16 wire compresses the RING leg only — the param
                # gather stays fp32 (params stay exact, compress.py rule).
                flat_new = comm.all_gather(new_p_mine, "data", tiled=True,
                                           label="pp_param_gather",
                                           scale=comm_scale)
                if bm is not None:
                    vec_new = _bucket_slices(bm, flat_new)
            if bm is None:
                new_params = unravel(
                    flat_new[:total].astype(raw_flat.dtype))
            else:
                new_params = _scatter_buckets(bm, vec_new, params)
        else:                       # replicated gradient update
            if wire == "int8_ef":
                gres = (jnp.concatenate(
                    [r[(0,) * lead] for r in state.gather_residual])
                    if bm is not None
                    else state.gather_residual[(0,) * lead])
                q, s, gather_res = _int8_encode(
                    g_mine + gres, scale_sync_axis=ssync)
                q_all = comm.all_gather(q, "data", tiled=True,
                                        label="pp_grad_gather_int8",
                                        scale=comm_scale)
                s_all = comm.all_gather(s[None], "data", tiled=True,
                                        label="pp_grad_scale_gather",
                                        scale=comm_scale)
                flat_g = (jnp.repeat(s_all, local)
                          * q_all.astype(jnp.float32))
            elif wire == "bf16":
                flat_g = comm.all_gather(
                    g_mine.astype(jnp.bfloat16), "data", tiled=True,
                    label="pp_grad_gather_bf16",
                    scale=comm_scale).astype(jnp.float32)
            else:
                flat_g = comm.all_gather(g_mine, "data", tiled=True,
                                         label="pp_grad_gather",
                                         scale=comm_scale)
            if bm is None:
                grads = unravel(flat_g[:total].astype(raw_flat.dtype))
            else:
                grads = _scatter_buckets(bm, _bucket_slices(bm, flat_g),
                                         params)
            new_params, opt_state = apply_optimizer(optimizer, grads,
                                                    state.opt_state, params)
        if _LAYOUT_KEY in new_params:
            new_params = dict(new_params,
                              **{_LAYOUT_KEY: params[_LAYOUT_KEY]})
        step = state.step + 1
        if ef:
            from .compress import OverlapEFState
            if bm is not None:
                ring_out = tuple(r[(None,) * lead] for r in ring_res)
                gather_out = tuple(
                    gather_res[bm.offsets[b]:bm.offsets[b] + bm.sizes[b]]
                    [(None,) * lead]
                    for b in range(B))
            else:
                ring_out = ring_res[(None,) * lead]
                gather_out = gather_res[(None,) * lead]
            new_state = OverlapEFState(new_params, opt_state, step,
                                       ring_out, gather_out)
        else:
            new_state = TrainState(new_params, opt_state, step)
        if numerics is not None:
            summary = numerics.summarize(
                params, jax.tree.map(lambda x: x / M, gacc), new_params)
            return new_state, (loss, summary)
        return new_state, loss

    return local_step


def make_pipeline_overlap_step(cfg: LlamaConfig,
                               optimizer: optax.GradientTransformation,
                               mesh: Mesh, params, *,
                               n_microbatches: int = 1,
                               schedule: str = "gpipe", n_chunks: int = 2,
                               aggregation: str = "zero1",
                               wire: str = "fp32",
                               overlap_microbatches: int = 1,
                               comm_buckets: int = 1,
                               numerics=None):
    """Per-step DP×PP composition driver: ``step(state, tokens) -> (state,
    loss)`` over a ``[n_data·B, T]`` batch sharded over ``data``, with the
    data-axis gradient sync routed through the compressed/overlapped ring
    (semantics in ``_make_pp_overlap_local_step``; ``comm_buckets > 1``
    selects the bucketed backward). Returns ``(state,
    step_fn)`` — an ``OverlapEFState`` under ``wire="int8_ef"`` (EF
    residuals in the checkpointed tree, per (data, stage) shard), a plain
    TrainState otherwise, with ZeRO-1 moments sharded over
    ``(data, stage)`` when ``aggregation="zero1"``."""
    n_stages = mesh.shape["stage"]
    body = _schedule_body(schedule, n_chunks)
    state, state_specs, n, pad, local, total, bm = _pp_overlap_setup(
        optimizer, mesh, params, wire, aggregation, schedule, n_chunks,
        comm_buckets)
    has_data = mesh.shape.get("data", 1) > 1
    local_step = _make_pp_overlap_local_step(
        cfg, optimizer, body, n_stages=n_stages,
        n_microbatches=n_microbatches, tp=mesh.shape.get("model", 1), n=n,
        pad=pad, local=local, total=total,
        microbatches=overlap_microbatches, wire=wire,
        aggregation=aggregation, bucket_map=bm, numerics=numerics)
    out_specs = (state_specs,
                 ((P(), numerics.summary_specs()) if numerics is not None
                  else P()))
    sharded = shard_map(
        local_step, mesh=mesh,
        in_specs=(state_specs, P("data") if has_data else P()),
        out_specs=out_specs, check_vma=False)
    return state, jax.jit(sharded, donate_argnums=(0,))


def make_pipeline_overlap_multi_step(cfg: LlamaConfig,
                                     optimizer: optax.GradientTransformation,
                                     mesh: Mesh, params, *,
                                     n_microbatches: int = 1,
                                     schedule: str = "gpipe",
                                     n_chunks: int = 2,
                                     aggregation: str = "zero1",
                                     wire: str = "fp32",
                                     overlap_microbatches: int = 1,
                                     comm_buckets: int = 1,
                                     numerics=None):
    """The DP×PP composition driver inside the K-step scan: ``step(state,
    window) -> (state, losses)`` with ``window`` a ``[K, n_data·B, T]``
    batch window (``shard_batch_window``) run in ONE compiled, donated
    dispatch — ZeRO-1 moments AND int8 EF residuals ride the scan carry,
    so error feedback is exact across fused steps, chunk-edge checkpoints
    and a preempt/resume cycle (pinned in tests/test_pp.py). The scanned
    body IS ``make_pipeline_overlap_step``'s, so the loss sequence and
    final state are bitwise-identical to K per-step calls at any K."""
    n_stages = mesh.shape["stage"]
    body = _schedule_body(schedule, n_chunks)
    state, state_specs, n, pad, local, total, bm = _pp_overlap_setup(
        optimizer, mesh, params, wire, aggregation, schedule, n_chunks,
        comm_buckets)
    has_data = mesh.shape.get("data", 1) > 1

    def multi(st, window):
        local_step = _make_pp_overlap_local_step(
            cfg, optimizer, body, n_stages=n_stages,
            n_microbatches=n_microbatches, tp=mesh.shape.get("model", 1),
            n=n, pad=pad, local=local, total=total,
            microbatches=overlap_microbatches, wire=wire,
            aggregation=aggregation, comm_scale=window.shape[0],
            bucket_map=bm, numerics=numerics)
        return lax.scan(local_step, st, window)

    out_specs = (state_specs,
                 ((P(), numerics.summary_specs(stacked=True))
                  if numerics is not None else P()))
    sharded = shard_map(
        multi, mesh=mesh,
        in_specs=(state_specs, P(None, "data") if has_data else P()),
        out_specs=out_specs, check_vma=False)
    return state, jax.jit(sharded, donate_argnums=(0,))


# --------------------------------------------------- stage-stacked numerics

def make_pp_numerics(params, mesh: Mesh, *, psum_data: bool = False):
    """In-jit numerics for the pipeline step bodies (the
    ``TrainConfig.numerics_every`` lever, telemetry/introspect.py).

    The DP summarizer assumes the step sees the FULL params tree; under PP
    each shard holds only its stage's block slice, so the per-layer-group
    geometry is built on the LOCAL stage template and the per-stage group
    stats come back STACKED over the ``stage`` axis (shard_map out-spec
    ``P("stage")``). Host-side, block groups are stage-qualified
    ("stage1/blocks/0" = the second stage's first LOCAL layer; under the
    interleaved layout, local indices follow ``interleave_params``'s
    chunk-major order) and the stage-replicated groups (embed / head /
    final norm — their grads are psum'd across stages by
    ``_reduce_loss_and_grads``) are kept once, from stage 0's copy.

    ``psum_data=True`` additionally psum-agrees grad stats and the finite
    mask over ``data`` (the overlap/ring path, where local gradients
    differ per data shard — compress.py's rule); the plain gradient path's
    grads are already data-pmean'd, so it passes False and pays nothing.
    Same bitwise contract as DP's: extra OUTPUTS only — losses/params are
    identical with the summary on or off (pinned in tests/test_pp.py)."""
    import numpy as np

    from ..telemetry import introspect

    if mesh.shape.get("model", 1) > 1:
        raise ValueError(
            "make_pp_numerics supports model=1 meshes: its per-group "
            "summaries are not model-axis psum-agreed, so stats would "
            "differ per TP shard. The overlap/ring drivers themselves DO "
            "compose with model>1 now (DP×PP×TP, see _pp_overlap_setup); "
            "for model-axis-agreed numerics use a TP mesh with "
            "tp.make_tp_numerics.")
    n_stages = mesh.shape["stage"]
    local_template = {
        k: (jax.tree.map(lambda x: x[: x.shape[0] // n_stages], v)
            if k == "blocks" else v)
        for k, v in params.items()}
    base = introspect.make_summarizer(
        local_template, psum_axis="data" if psum_data else None)

    def stage_expand(names, block_flags):
        rows, cols, out = [], [], []
        for s in range(n_stages):
            for i, name in enumerate(names):
                if block_flags[i]:
                    rows.append(s)
                    cols.append(i)
                    out.append(f"stage{s}/{name}")
        for i, name in enumerate(names):
            if not block_flags[i]:
                rows.append(0)
                cols.append(i)
                out.append(name)
        return (np.asarray(rows), np.asarray(cols)), out

    g_idx, groups = stage_expand(
        base.groups, [g.startswith("blocks/") for g in base.groups])
    l_idx, paths = stage_expand(
        base.paths, [p.startswith("blocks/") for p in base.paths])

    def summarize(params_local, grads_local, new_params_local):
        s = base.summarize(params_local, grads_local, new_params_local)
        # [1, G]/[1, L]: the leading axis becomes ``stage`` through the
        # shard_map out-spec.
        return introspect.NumericsSummary(*(x[None] for x in s))

    class _PPHandle(introspect.NumericsHandle):
        def summary_specs(self, stacked: bool = False):
            """shard_map out-specs for the stage-stacked summary leaves:
            ``[S, ·]`` per-step, ``[K, S, ·]`` under the K-step scan."""
            spec = P(None, "stage") if stacked else P("stage")
            return introspect.NumericsSummary(spec, spec, spec, spec)

        def event_fields(self, summary, *, index=None, top=4):
            def host(x):
                a = np.asarray(x)
                return a[index] if index is not None else a

            flat = introspect.NumericsSummary(
                grad_sq=host(summary.grad_sq)[g_idx],
                param_sq=host(summary.param_sq)[g_idx],
                update_sq=host(summary.update_sq)[g_idx],
                grad_finite=host(summary.grad_finite)[l_idx])
            return introspect.NumericsHandle.event_fields(
                self, flat, index=None, top=top)

    return _PPHandle(groups, paths, summarize)


def shard_batch_window(mesh: Mesh, window) -> jax.Array:
    """Device-put a [K, B, T] host batch window for the fused pipeline
    drivers: leading axis = K consecutive steps (replicated — every shard
    scans the same step sequence), second axis sharded over ``data`` when
    the mesh carries a real data axis (a size-1 axis normalizes to the
    replicated spec — the dp.data_partition jit-cache-stability rule)."""
    spec = P(None, "data") if mesh.shape.get("data", 1) > 1 else P()
    return jax.device_put(window, NamedSharding(mesh, spec))


from .mesh import shard_batch  # noqa: E402,F401  (shared batch placement)
