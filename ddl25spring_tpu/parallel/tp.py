"""Tensor (model) parallelism: Megatron-style sharded transformer blocks.

The reference has NO tensor parallelism — its closest analog is the VFL
bottom/top model split (SURVEY.md §2.10 marks TP "Absent", optional
parity-plus). This module adds it TPU-first: attention heads and the SwiGLU
hidden dimension are sharded over a ``model`` mesh axis, the two row-sharded
projections (wo, w_down) produce partial sums, and one ``lax.psum`` per
sub-layer combines them over ICI — the classic Megatron f/g collective
pattern, expressed through shard_map.

Sharding layout (per block; leading [n_layers] axis never sharded here):
- wq, wk, wv:      [L, D, D]  column-sharded  P(None, None, "model")
  → each device computes num_heads / tp local heads end-to-end.
- wo:              [L, D, D]  row-sharded     P(None, "model", None)
  → partial [B,T,D] outputs, psum over "model" (inside llama.attention).
- w_gate, w_up:    [L, D, F]  column-sharded; w_down [L, F, D] row-sharded,
  psum inside llama.mlp.
- norms, embedding, lm_head: replicated (their grads are psum-ed instead).

Gradient accounting: the per-shard loss is scaled by 1/tp before
differentiation. Every shard's loss copy depends on every shard's weight
slice (through the psums), so differentiating the unscaled replicated loss
would count each path tp times; with the 1/tp scaling, sharded-leaf grads
come out exact locally and replicated-leaf grads become exact after a psum
over ``model``. Composes with data parallelism on a ``(data, model)`` mesh.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import nn
from ..telemetry import comm

from ..config import LlamaConfig
from ..models import llama
from ..ops import causal_lm_loss
from .dp import TrainState, apply_optimizer, sharded_opt_init

_COL = {"wq", "wk", "wv", "w_gate", "w_up"}   # shard last dim (output cols)
_ROW = {"wo", "w_down"}                        # shard middle dim (input rows)


def param_specs(params: dict) -> dict:
    """Megatron PartitionSpecs for the stacked-block Llama tree."""
    def block_spec(name):
        def spec(_):
            if name in _COL:
                return P(None, None, "model")
            if name in _ROW:
                # No trailing None: XLA normalizes output shardings to the
                # trailing-None-free form, and a device_put'd input with
                # the unnormalized spec would be a DIFFERENT jit cache
                # signature — one spurious re-lowering on the second
                # donated dispatch (the zero-retrace gate in
                # experiments/tp_fusion_smoke.py pins this).
                return P(None, "model")
            return P()
        return spec

    specs = {}
    for k, v in params.items():
        if k == "blocks":
            specs[k] = {name: jax.tree.map(block_spec(name), leaf)
                        for name, leaf in v.items()}
        else:
            specs[k] = jax.tree.map(lambda _: P(), v)
    return specs


def shard_params(mesh: Mesh, params: dict) -> dict:
    specs = param_specs(params)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs)


def init_state(mesh: Mesh, params: dict,
               optimizer: optax.GradientTransformation) -> TrainState:
    params = shard_params(mesh, params)
    opt_state = sharded_opt_init(mesh, params, optimizer, param_specs(params))
    step = jax.device_put(jnp.zeros((), jnp.int32), NamedSharding(mesh, P()))
    return TrainState(params, opt_state, step)


def _tp_loss(params: dict, tokens: jnp.ndarray, cfg: LlamaConfig,
             tp: int) -> jnp.ndarray:
    """Per-shard body: full loss / tp (see module docstring on why /tp)."""
    h = llama.embed(params, tokens, cfg)
    h = llama.blocks_apply(params["blocks"], h, cfg, tp_axis="model")
    logits = llama.head(params, h, cfg)
    return causal_lm_loss(logits, tokens) / tp


def _sharded_mask(grads: dict) -> dict:
    """Bool pytree marking leaves that are model-sharded (complete locally)
    vs replicated (partial grads needing a psum over ``model``)."""
    return {
        outer: ({name: jax.tree.map(lambda _: name in _COL or name in _ROW, leaf)
                 for name, leaf in v.items()} if outer == "blocks"
                else jax.tree.map(lambda _: False, v))
        for outer, v in grads.items()
    }


def make_tp_train_step(cfg: LlamaConfig, optimizer: optax.GradientTransformation,
                       mesh: Mesh) -> Callable:
    """jit-compiled train step on a ``(data?, model)`` mesh.

    ``step(state, tokens)``: tokens [B, T] sharded over ``data`` if present,
    replicated over ``model`` (every TP shard sees the full local batch).
    The grad computation runs under shard_map (explicit psums); the optimizer
    update runs at jit level where GSPMD keeps opt-state shardings aligned
    with the param shardings (same split as parallel.pp.make_pipeline_step).
    """
    tp = mesh.shape["model"]
    has_data = mesh.shape.get("data", 1) > 1

    def sharded_grads(params: dict, tokens):
        loss, grads = jax.value_and_grad(_tp_loss)(params, tokens, cfg, tp)
        mask = _sharded_mask(grads)
        # Telemetry note: the in-forward f/g psums inside llama.attention/
        # mlp run under value_and_grad — autodiff synthesizes their
        # transposes, which trace-time accounting cannot see (documented in
        # telemetry/comm.py). The post-AD reductions below are exact.
        grads = jax.tree.map(
            lambda g, s: g if s else comm.psum(g, "model",
                                               label="tp_replicated_grads"),
            grads, mask)
        loss = loss * tp                          # undo the 1/tp scaling
        if has_data:
            grads = comm.pmean(grads, "data", label="grad_allreduce")
            loss = comm.pmean(loss, "data", label="loss_allreduce")
        return loss, grads

    def step(state: TrainState, tokens):
        pspecs = param_specs(state.params)
        loss, grads = shard_map(
            sharded_grads, mesh=mesh,
            in_specs=(pspecs, P("data") if has_data else P()),
            out_specs=(P(), pspecs),
            check_vma=False,
        )(state.params, tokens)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss

    return jax.jit(step, donate_argnums=(0,))


def tp_forward(params: dict, tokens: jnp.ndarray, cfg: LlamaConfig,
               mesh: Mesh) -> jnp.ndarray:
    """Full logits via tensor-parallel forward (tests/eval); cached on
    (cfg, mesh)."""
    return _tp_forward_fn(cfg, mesh)(params, tokens)


@functools.cache
def _tp_forward_fn(cfg: LlamaConfig, mesh: Mesh) -> Callable:
    def body(params, tokens):
        h = llama.embed(params, tokens, cfg)
        h = llama.blocks_apply(params["blocks"], h, cfg, tp_axis="model")
        return llama.head(params, h, cfg)

    def fn(params, tokens):
        return shard_map(
            body, mesh=mesh,
            in_specs=(param_specs(params), P()),
            out_specs=P(),
            check_vma=False,
        )(params, tokens)

    return jax.jit(fn)


# ------------------------------------- partially-synchronized activations
#
# "Tensor-Parallelism with Partially Synchronized Activations" (PAPERS.md,
# arXiv 2506.19645): the two per-layer activation all-reduces of the
# Megatron forward sit on the critical path of every TP step, and they can
# be relaxed — deferred across layers, or compressed with error feedback —
# at a bounded quality cost. The modes below keep the relaxation additive:
# ``psa=""`` routes through ``llama.blocks_apply(tp_axis="model")``
# unchanged (the bitwise reference), and every relaxed mode reuses
# ``llama.attention``/``llama.mlp`` with ``tp_axis=None`` — the partial
# (un-psummed) per-shard outputs — applying its own sync externally, so
# the model code carries no PSA logic. Analytic model-axis wire budgets
# are in ``psa_sync_wire_bytes`` and gated by experiments/tp_fusion_smoke.


def _parse_psa(psa: str, n_layers: int) -> Tuple[str, int]:
    """Validate a ``TrainConfig.psa`` string → ``(mode, defer_period)``
    with mode ∈ {"", "full", "defer", "int8_ef"}."""
    if psa in ("", "full", "int8_ef"):
        return psa, 0
    if psa.startswith("defer:"):
        try:
            period = int(psa.split(":", 1)[1])
        except ValueError:
            period = 0
        if period < 1:
            raise ValueError(f"bad PSA defer period in {psa!r}: want "
                             "'defer:L' with integer L >= 1")
        if n_layers % period:
            raise ValueError(
                f"psa='defer:{period}' needs n_layers divisible by the "
                f"defer period (got n_layers={n_layers}) — the last layer "
                "group must end on a sync boundary or shards never agree")
        return "defer", period
    raise ValueError(f"unknown psa mode {psa!r}: expected '', 'full', "
                     "'defer:L' or 'int8_ef'")


def psa_sync_wire_bytes(cfg: LlamaConfig, psa: str, tp: int,
                        batch: int, seq: int) -> int:
    """Analytic per-device per-step MODEL-axis activation-sync wire bytes
    for one forward pass, exactly as telemetry/comm.py accounts the
    forward sync collectives (backward-sync bytes are AD-synthesized
    transposes on every mode — the documented under-count; the ratio
    between modes is therefore measured on a consistent basis):

    - ""/"full":  2L psums of the [B, T, D] activation → 2L · 2(tp−1)/tp
      · B·T·D·itemsize.
    - "defer:P":  one boundary psum per P layers → (L/P) · 2(tp−1)/tp
      · B·T·D·itemsize — a 1/(2P) reduction.
    - "int8_ef":  2L int8 all-gathers (+ a 4-byte scale gather each) →
      2L · (tp−1) · (B·T·D + 4) — ~tp/8 of full sync.

    ``psa=""`` shares the full-sync formula: the wire is identical, it is
    just invisible to telemetry (raw in-model psum)."""
    mode, period = _parse_psa(psa, cfg.n_layers)
    act = batch * seq * cfg.dmodel
    item = jnp.dtype(cfg.dtype).itemsize
    if mode == "int8_ef":
        return 2 * cfg.n_layers * (tp - 1) * (act + 4)
    syncs = (cfg.n_layers // period) if mode == "defer" else 2 * cfg.n_layers
    return int(syncs * (2 * (tp - 1) / tp) * act * item)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _psum_ste(y, summed, axis_name):
    """Swap a shard's partial sub-layer output ``y`` for the externally
    combined ``summed`` on the forward pass, while the backward pass keeps
    the EXACT psum's transpose (itself a psum under shard_map semantics).
    The 1/tp Megatron gradient accounting of the module docstring then
    carries over to the compressed sync unchanged: gradients are computed
    as if the sync were a true ``lax.psum`` of the partials."""
    return summed


def _psum_ste_fwd(y, summed, axis_name):
    return summed, None


def _psum_ste_bwd(axis_name, _, ct):
    # Raw lax.psum on purpose: telemetry counts FORWARD sync wire only, so
    # the backward-sync bytes stay the same documented under-count as the
    # full-sync path's autodiff-synthesized transposes (telemetry/comm.py)
    # — recording them here would inflate the compressed mode's measured
    # bytes against a baseline that cannot see its own.
    return lax.psum(ct, axis_name), jnp.zeros_like(ct)


_psum_ste.defvjp(_psum_ste_fwd, _psum_ste_bwd)


def _psa_int8_sync(y, res, comm_scale: int):
    """One compressed activation sync over ``model``: each shard quantizes
    its EF-compensated partial ``y + res`` to int8 (compress.py's
    symmetric per-tensor rule), all-gathers (q, s) from every shard and
    sums the dequantized partials locally — the cross-shard combine at
    ~tp/8 of the psum's wire. Returns ``(combined, residual')`` with the
    new per-shard quantization error feeding the next step's sync."""
    from .compress import _int8_encode
    c = lax.stop_gradient(y.astype(jnp.float32) + res)
    q, s, new_res = _int8_encode(c)
    q_all = comm.all_gather(q, "model", label="psa_act_int8",
                            scale=comm_scale)
    s_all = comm.all_gather(s[None], "model", tiled=True,
                            label="psa_act_scale", scale=comm_scale)
    summed = jnp.einsum("i,i...->...", s_all, q_all.astype(jnp.float32))
    return _psum_ste(y, summed.astype(y.dtype), "model"), new_res


def _psa_blocks_apply(blocks, h, cfg: LlamaConfig, tp: int, mode: str,
                      period: int, act_res, comm_scale: int = 1):
    """The PSA transformer stack: ``llama.blocks_apply`` with the per-sub-
    layer model-axis sync performed per ``mode``. Returns ``(h, act_res')``
    — the residual tree is None except under ``mode="int8_ef"``.

    - ``""``:     the in-model raw-psum path, bitwise the legacy forward.
    - ``"full"``: the SAME sync positions through ``comm.psum`` — value-
      identical (one lax.psum per sub-layer either way), but the model-axis
      activation wire becomes visible to trace-time accounting. This is the
      smoke's same-run full-sync baseline.
    - ``"defer"``: no sync inside a group of ``period`` layers — each shard
      evolves its hidden state from its OWN partial sub-layer outputs —
      then one boundary correction ``psum(h) − (tp−1)·h0``: every shard
      carried ``h0`` plus its local contributions, so the correction is
      exactly ``h0 + Σ_shards(local contributions)`` — each sub-layer
      contribution (computed from per-shard partial inputs, the PSA
      relaxation) counted once, and all shards agree at every boundary.
    - ``"int8_ef"``: per-sub-layer compressed sync (``_psa_int8_sync``)
      with the [L, 2, B, T, D] error-feedback residual tree threaded as
      scan xs and returned updated.
    """
    if mode == "":
        return llama.blocks_apply(blocks, h, cfg, tp_axis="model"), act_res
    t = h.shape[1]
    cos, sin = llama.rope_angles(jnp.arange(t), cfg.head_dim, cfg.rope_theta)
    n_layers = jax.tree.leaves(blocks)[0].shape[0]

    if mode == "full":
        def layer(block, c, cos, sin):
            a = llama.attention(
                block, nn.rmsnorm(block["attn_norm"], c, eps=cfg.norm_eps),
                cfg, cos, sin)
            c = c + comm.psum(a, "model", label="psa_full_sync",
                              scale=n_layers * comm_scale)
            m = llama.mlp(
                block, nn.rmsnorm(block["mlp_norm"], c, eps=cfg.norm_eps))
            return c + comm.psum(m, "model", label="psa_full_sync",
                                 scale=n_layers * comm_scale)

        fn = jax.checkpoint(layer) if cfg.remat else layer

        def body(carry, block):
            return fn(block, carry, cos, sin), None

        out, _ = lax.scan(body, h, blocks)
        return out, act_res

    if mode == "defer":
        n_groups = n_layers // period
        grouped = jax.tree.map(
            lambda x: x.reshape((n_groups, period) + x.shape[1:]), blocks)

        def layer(block, c, cos, sin):
            return llama.block_apply(block, c, cfg, cos, sin)  # partials

        fn = jax.checkpoint(layer) if cfg.remat else layer

        def group(carry, gblocks):
            h0 = carry

            def inner(c, block):
                return fn(block, c, cos, sin), None

            hp, _ = lax.scan(inner, h0, gblocks)
            hp = comm.psum(hp, "model", label="psa_defer_sync",
                           scale=n_groups * comm_scale)
            return hp - (tp - 1) * h0, None

        out, _ = lax.scan(group, h, grouped)
        return out, act_res

    # mode == "int8_ef"
    def layer(block, res_pair, c, cos, sin):
        a = llama.attention(
            block, nn.rmsnorm(block["attn_norm"], c, eps=cfg.norm_eps),
            cfg, cos, sin)
        a, r0 = _psa_int8_sync(a, res_pair[0], n_layers * comm_scale)
        c = c + a
        m = llama.mlp(
            block, nn.rmsnorm(block["mlp_norm"], c, eps=cfg.norm_eps))
        m, r1 = _psa_int8_sync(m, res_pair[1], n_layers * comm_scale)
        return c + m, jnp.stack([r0, r1])

    fn = jax.checkpoint(layer) if cfg.remat else layer

    def body(carry, xs):
        block, res_pair = xs
        return fn(block, res_pair, carry, cos, sin)

    out, new_res = lax.scan(body, h, (blocks, act_res))
    return out, new_res


def _tp_psa_loss(params: dict, tokens, cfg: LlamaConfig, tp: int,
                 mode: str, period: int, act_res, comm_scale: int = 1):
    """``_tp_loss`` with the activation sync per PSA mode; returns
    ``(loss/tp, act_res')`` (aux threads the EF residuals out of
    value_and_grad — they are stop-gradiented at the sync)."""
    h = llama.embed(params, tokens, cfg)
    h, new_res = _psa_blocks_apply(params["blocks"], h, cfg, tp, mode,
                                   period, act_res, comm_scale)
    logits = llama.head(params, h, cfg)
    return causal_lm_loss(logits, tokens) / tp, new_res


class TPActState(NamedTuple):
    """TrainState + the PSA activation error-feedback residual tree of
    ``psa="int8_ef"``: ``[n_data, tp, L, 2, B_local, T, D]`` fp32 sharded
    ``P(data?, "model")`` — each (data, model) shard compensates the
    quantization error of its OWN partial activations (slot [l, 0] = layer
    l's attention output, [l, 1] = its MLP output). Rides the K-step scan
    carry and the checkpointed state tree, so the accumulated error
    survives fused dispatch, chunk-edge checkpoints and a preempt/resume
    cycle exactly (pinned in tests/test_tp.py)."""
    params: Any
    opt_state: Any
    step: jnp.ndarray
    act_residual: Any


def _act_residual_setup(mesh: Mesh, cfg: LlamaConfig,
                        batch_shape: Optional[Tuple[int, int]]):
    """Zero activation-EF residual + its PartitionSpec. The residual is
    sized by the LOCAL (per data shard) batch, which the factory cannot
    infer — callers pass ``batch_shape=(per_shard_batch, seq_len)``."""
    if batch_shape is None:
        raise ValueError(
            "psa='int8_ef' carries a per-(model shard, sub-layer) "
            "activation EF residual sized by the local batch — pass "
            "batch_shape=(per_data_shard_batch, seq_len) to the factory")
    b, t = batch_shape
    has_data = mesh.shape.get("data", 1) > 1
    n_data = mesh.shape.get("data", 1)
    tp = mesh.shape["model"]
    spec = P("data", "model") if has_data else P(None, "model")
    res = jax.device_put(
        jnp.zeros((n_data, tp, cfg.n_layers, 2, b, t, cfg.dmodel),
                  jnp.float32),
        NamedSharding(mesh, spec))
    return res, spec


# ------------------------------------------- shared-body step factories
#
# ``make_tp_train_step`` above is kept byte-for-byte as the reference
# path (optimizer at jit level). The factories below share ONE per-shard
# body between the per-step and the K-step scan driver — the
# dp._make_local_grad_step / pp._make_pp_local_step convention — so
# per-step and fused dispatch cannot drift and their bitwise equality at
# any K is structural (pinned at K∈{1,4} in tests/test_tp.py).


def _make_tp_local_step(cfg: LlamaConfig, optimizer, *, tp: int,
                        has_data: bool, mode: str, period: int,
                        comm_scale: int = 1, numerics=None) -> Callable:
    """The per-shard TP train-step body shared by ``make_tp_step`` and
    ``make_tp_multi_step``. Runs under shard_map over (data?, model); the
    optimizer applies to each shard's LOCAL param slice — valid for
    elementwise optimizers (the ZeRO-1 slice-commuting argument,
    ops/adam.py), which is every optimizer this repo ships. With
    ``psa=""`` the gradient computation is bitwise ``make_tp_train_step``'s
    and the elementwise update matches the jit-level one coordinate for
    coordinate (pinned in tests/test_tp.py)."""
    ef = mode == "int8_ef"

    def local_step(state, tokens):
        act_res = state.act_residual[0, 0] if ef else None
        (loss, new_res), grads = jax.value_and_grad(
            _tp_psa_loss, has_aux=True)(state.params, tokens, cfg, tp,
                                        mode, period, act_res, comm_scale)
        mask = _sharded_mask(grads)
        grads = jax.tree.map(
            lambda g, s: g if s else comm.psum(g, "model",
                                               label="tp_replicated_grads",
                                               scale=comm_scale),
            grads, mask)
        loss = loss * tp                          # undo the 1/tp scaling
        if has_data:
            grads = comm.pmean(grads, "data", label="grad_allreduce",
                               scale=comm_scale)
            loss = comm.pmean(loss, "data", label="loss_allreduce",
                              scale=comm_scale)
        params, opt_state = apply_optimizer(optimizer, grads,
                                            state.opt_state, state.params)
        step = state.step + 1
        if ef:
            new_state = TPActState(params, opt_state, step,
                                   new_res[None, None])
        else:
            new_state = TrainState(params, opt_state, step)
        if numerics is not None:
            summary = numerics.summarize(state.params, grads, params)
            return new_state, (loss, summary)
        return new_state, loss

    return local_step


def _tp_state_specs(state, mode: str, res_spec):
    """shard_map PartitionSpecs for a (TrainState | TPActState) under the
    Megatron layout, computed from the traced state's tree structure only
    (the pp._opt_specs rule)."""
    from .pp import _opt_specs
    pspecs = param_specs(state.params)
    ospecs = _opt_specs(state.opt_state, state.params, pspecs)
    if mode == "int8_ef":
        return TPActState(pspecs, ospecs, P(), res_spec)
    return TrainState(pspecs, ospecs, P())


def make_tp_step(cfg: LlamaConfig, optimizer: optax.GradientTransformation,
                 mesh: Mesh, params, *, psa: str = "",
                 batch_shape: Optional[Tuple[int, int]] = None,
                 numerics=None):
    """Per-step shared-body TP driver on a ``(data?, model)`` mesh:
    returns ``(state, step)`` with ``step(state, tokens) -> (state, loss)``
    — a ``TPActState`` under ``psa="int8_ef"`` (activation EF residuals in
    the checkpointed tree), a plain TrainState otherwise.

    ``psa`` selects the activation sync mode (``TrainConfig.psa``;
    semantics in ``_psa_blocks_apply``): ``""`` and ``"full"`` are bitwise
    the legacy ``make_tp_train_step`` path, ``"defer:L"``/``"int8_ef"``
    hold the pinned convergence bars of tests/test_tp.py.

    ``numerics`` (a ``make_tp_numerics`` handle) arms the in-jit summary:
    the step then returns ``(state, (loss, NumericsSummary))`` — extra
    OUTPUTS only, losses/params bitwise on vs off."""
    tp = mesh.shape["model"]
    has_data = mesh.shape.get("data", 1) > 1
    mode, period = _parse_psa(psa, cfg.n_layers)
    state = init_state(mesh, params, optimizer)
    res_spec = None
    if mode == "int8_ef":
        res, res_spec = _act_residual_setup(mesh, cfg, batch_shape)
        state = TPActState(state.params, state.opt_state, state.step, res)
    local_step = _make_tp_local_step(cfg, optimizer, tp=tp,
                                     has_data=has_data, mode=mode,
                                     period=period, numerics=numerics)

    def step(state, tokens):
        state_specs = _tp_state_specs(state, mode, res_spec)
        out_specs = (state_specs,
                     ((P(), numerics.summary_specs()) if numerics is not None
                      else P()))
        return shard_map(
            local_step, mesh=mesh,
            in_specs=(state_specs, P("data") if has_data else P()),
            out_specs=out_specs,
            check_vma=False,
        )(state, tokens)

    return state, jax.jit(step, donate_argnums=(0,))


def make_tp_multi_step(cfg: LlamaConfig,
                       optimizer: optax.GradientTransformation,
                       mesh: Mesh, params, *, psa: str = "",
                       batch_shape: Optional[Tuple[int, int]] = None,
                       numerics=None):
    """Fused K-step TP driver: ``step(state, window) -> (state, losses)``
    with ``window`` a device-resident ``[K, B, T]`` token window
    (``shard_batch_window``) run in ONE compiled, donated dispatch — the
    dp.make_multi_step / pp.make_pipeline_multi_step shape carried to the
    model axis. The scanned body IS ``make_tp_step``'s
    (``_make_tp_local_step``), so the loss sequence and final params are
    BITWISE identical to K per-step calls at any K (pinned at K∈{1,4});
    per-train-step wire is unchanged — collectives record at ``scale=K``
    per dispatch and ``CommProfile.as_dict(steps_per_dispatch=K)``
    normalizes. Under ``psa="int8_ef"`` the activation EF residuals ride
    the scan carry, so error feedback is exact across fused steps.

    K is read from the window's static leading dim at trace time — one
    returned callable serves every chunk size (a tail chunk of k < K
    steps is one more legitimate compile, stamped by the trainer's
    CompileWatch)."""
    tp = mesh.shape["model"]
    has_data = mesh.shape.get("data", 1) > 1
    mode, period = _parse_psa(psa, cfg.n_layers)
    state = init_state(mesh, params, optimizer)
    res_spec = None
    if mode == "int8_ef":
        res, res_spec = _act_residual_setup(mesh, cfg, batch_shape)
        state = TPActState(state.params, state.opt_state, state.step, res)

    def step(state, window):
        state_specs = _tp_state_specs(state, mode, res_spec)

        def multi(st, win):
            local_step = _make_tp_local_step(
                cfg, optimizer, tp=tp, has_data=has_data, mode=mode,
                period=period, comm_scale=win.shape[0], numerics=numerics)
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

    return state, jax.jit(step, donate_argnums=(0,))


# --------------------------------------------- model-axis agreed numerics

def make_tp_numerics(params, mesh: Mesh, *, psum_data: bool = False):
    """In-jit numerics for the TP step bodies (``TrainConfig.
    numerics_every``, telemetry/introspect.py).

    Under TP each shard holds a SLICE of every column/row-sharded block
    leaf and a full copy of the rest, so per-group sums of squares need a
    psum over ``model`` to be global — and the replicated leaves would
    then count tp times. Fix: replicated leaves are pre-scaled by
    tp^(−1/2) before squaring (their psum then telescopes back to the
    exact single-copy value), sharded leaves pass through (their local
    squares SUM to the global), and the whole summary psums over
    ``model`` — every shard agrees on exact global stats, so the summary
    out-spec is plainly replicated.

    ``psum_data=True`` additionally agrees grad stats and the finite mask
    over ``data`` (the overlap/ring path, where local gradients differ
    per data shard — same RMS-style Σ-over-shards semantics as the DP
    drivers'); param/update stats are data-replicated either way and psum
    over ``model`` only. Extra OUTPUTS only — losses/params bitwise on vs
    off (pinned in tests/test_tp.py)."""
    from ..telemetry import introspect

    tp = mesh.shape["model"]
    base = introspect.make_summarizer(params)
    scale = tp ** -0.5
    mask = _sharded_mask(params)
    grad_axes = ("data", "model") if psum_data else ("model",)

    def _prescale(tree):
        return jax.tree.map(lambda x, s: x if s else x * scale, tree, mask)

    def summarize(params_, grads, new_params):
        s = base.summarize(_prescale(params_), _prescale(grads),
                           _prescale(new_params))
        # Raw lax collectives on purpose — observability tax, not payload
        # (the introspect.make_summarizer accounting rule).
        return introspect.NumericsSummary(
            grad_sq=lax.psum(s.grad_sq, grad_axes),
            param_sq=lax.psum(s.param_sq, ("model",)),
            update_sq=lax.psum(s.update_sq, ("model",)),
            grad_finite=lax.psum(jnp.logical_not(s.grad_finite)
                                 .astype(jnp.int32), grad_axes) == 0)

    class _TPHandle(introspect.NumericsHandle):
        def summary_specs(self, stacked: bool = False):
            """Replicated on every shard — the model-axis psums above agree
            the stats, so per-step [G] and K-scanned [K, G] leaves both
            carry the plain spec."""
            return introspect.NumericsSummary(P(), P(), P(), P())

    return _TPHandle(base.groups, base.paths, summarize)


# --------------------------------------------- DP×TP data-axis ring drivers
#
# The same composition step PP took in pp.py's overlap drivers, now on a
# (data, model) mesh: each (d, m) shard flattens its LOCAL param tree —
# the model-sharded block slices plus the model-replicated embed/head/
# norms, the same flat length on every model shard — rings the data axis
# with the compressed/overlapped machinery (compress.ring_reduce_scatter,
# int8 + EF residuals, ZeRO-1 sliced updates), and gathers fresh slices
# back. Under shard_map a collective over ``data`` runs independently per
# model coordinate, so the ring needs no model-axis awareness; the one
# cross-axis step is that model-REPLICATED leaf grads psum over ``model``
# BEFORE flattening (each model shard contributes its partial), exactly as
# the plain TP step does. Moments and EF residuals gain a model axis
# ([n_data, tp, ...], sharded P("data", "model")) because each (data,
# model) shard compensates its OWN slice's quantization error.
#
# Cross-model caveat (shared with pp.py's stage-replicated leaves under
# int8): the int8 scale is per flat chunk, and chunks mix model-sharded
# and model-replicated coordinates, so replicated coordinates can apply
# per-model-shard deltas differing by up to one int8 step — bounded by
# the per-(data, model) EF residuals, and zero under fp32/bf16 wire or
# zero1's fp32 param gather. DATA replicas stay bitwise in sync in every
# mode (everyone applies the same gathered deltas; pinned in
# tests/test_tp.py).


def _tp_flat_geometry(mesh: Mesh, params):
    """Padded flat-vector geometry of the LOCAL per-model-shard param tree
    — the unit the DP×TP data-axis zero1/ring sync operates on. Column/
    row-sharded block leaves contribute 1/tp of their elements, everything
    else its full size; every model shard's local tree has the same flat
    length, so the geometry is SPMD-consistent across the model axis.
    Returns ``(n, pad, local, total)`` with n = the ``data`` axis size and
    total = the per-model-shard param count."""
    n = mesh.shape.get("data", 1)
    tp = mesh.shape["model"]
    total = 0
    for k, v in params.items():
        if k == "blocks":
            for name, leaf in v.items():
                size = sum(int(x.size) for x in jax.tree.leaves(leaf))
                total += size // tp if (name in _COL or name in _ROW) else size
        else:
            total += sum(int(x.size) for x in jax.tree.leaves(v))
    pad = (-total) % n
    local = (total + pad) // n
    return n, pad, local, total


def _tp_bucket_map(mesh: Mesh, params, comm_buckets: int):
    """The DP×TP ``BucketMap``: ``compress.make_bucket_map`` over the
    PER-MODEL-SHARD leaf geometry (col/row block leaves at 1/tp, full
    stacked [L] layer depth) — the tree the shard_map body flattens.
    Returns None at ``comm_buckets == 1`` (the legacy path)."""
    from .compress import make_bucket_map

    if int(comm_buckets) < 1:
        raise ValueError(
            f"comm_buckets must be >= 1 (got {comm_buckets})")
    if int(comm_buckets) == 1:
        return None
    n = mesh.shape.get("data", 1)
    tp = mesh.shape["model"]

    def leaf_local(path, leaf):
        key = getattr(path[0], "key", None) if path else None
        if key == "blocks":
            name = getattr(path[1], "key", None) if len(path) > 1 else None
            size = int(leaf.size)
            if name in _COL or name in _ROW:
                size //= tp
            return size, int(leaf.shape[0])
        return int(leaf.size), None

    return make_bucket_map(params, n, comm_buckets, leaf_local=leaf_local)


def _tp_overlap_setup(optimizer, mesh: Mesh, params, wire: str,
                      aggregation: str, psa: str, n_layers: int,
                      comm_buckets: int = 1):
    """State + shard specs + flat geometry for the DP×TP overlap drivers.

    ZeRO-1 moments live as ``[n_data, tp, local]`` global arrays sharded
    ``P("data", "model")`` — each (d, m) shard owns the moments of model
    shard m's d-th flat slice; int8 EF residuals get the same layout
    (ring: ``[n, tp, n·local]``; gather: ``[n, tp, local]``).
    ``comm_buckets > 1`` (the bucketed backward, ``_tp_bucket_map``)
    turns moments and residuals into per-bucket tuples, mirroring the DP
    driver's layout rule with the (data, model) shard axes kept."""
    mode, period = _parse_psa(psa, n_layers)
    if aggregation not in ("gradient", "zero1"):
        raise ValueError("the DP×TP overlap driver supports gradient/zero1 "
                         f"aggregation only (got {aggregation!r})")
    if wire not in ("fp32", "bf16", "int8_ef"):
        raise ValueError(f"unknown wire format {wire!r}")
    if "data" not in mesh.axis_names:
        raise ValueError("the DP×TP overlap driver needs a mesh with a "
                         "'data' axis (size 1 is fine) — build it with "
                         'make_mesh({"data": d, "model": t})')
    if mesh.shape.get("dcn", 1) > 1:
        raise ValueError("the DP×TP overlap driver runs the flat data ring "
                         "only; the hierarchical (dcn x data) tier is the "
                         "DP trainer's (parallel/compress.py)")
    if mesh.shape.get("model", 1) < 2:
        raise ValueError("the DP×TP overlap driver needs model >= 2 — on a "
                         "model=1 mesh the flat DP ring driver "
                         "(parallel/compress.py) is the same machinery "
                         "without the model axis")
    if mode == "int8_ef":
        raise ValueError(
            "psa='int8_ef' × the overlap ring driver is deferred: the "
            "activation EF residual tree does not yet thread the "
            "OverlapEFState scan carry — use psa in {'', 'full', "
            "'defer:L'} with the ring, or psa='int8_ef' on the non-overlap "
            "TP factories (make_tp_step / make_tp_multi_step)")
    n, pad, local, total = _tp_flat_geometry(mesh, params)
    bm = _tp_bucket_map(mesh, params, comm_buckets)
    specs = param_specs(params)
    sharded = shard_params(mesh, params)
    step0 = jax.device_put(jnp.zeros((), jnp.int32),
                           NamedSharding(mesh, P()))
    tp = mesh.shape["model"]
    dshard = P("data", "model")
    if aggregation == "zero1":
        def _specs_for(sz):
            abstract = jax.eval_shape(
                optimizer.init, jax.ShapeDtypeStruct((sz,), jnp.float32))
            return jax.tree.map(
                lambda x: dshard if getattr(x, "ndim", 0) >= 1 else P(),
                abstract)

        opt_specs = (_specs_for(local) if bm is None else
                     tuple(_specs_for(sz) for sz in bm.sizes))

        def local_init(p):
            from ..utils import pytree as pt
            from .compress import _bucket_vectors
            if bm is None:
                flat = jnp.pad(pt.flatten(p)[0].astype(jnp.float32),
                               (0, pad))
                mine = [lax.dynamic_slice_in_dim(
                    flat, lax.axis_index("data") * local, local)]
            else:
                vecs = _bucket_vectors(bm, p)
                mine = [lax.dynamic_slice_in_dim(
                            vecs[b], lax.axis_index("data") * bm.sizes[b],
                            bm.sizes[b])
                        for b in range(bm.nbuckets)]
            # Vector leaves gain the (data, model) shard axes; scalars
            # (count) replicate — every shard steps them identically.
            opts = [jax.tree.map(
                        lambda x: (x[None, None]
                                   if getattr(x, "ndim", 0) >= 1 else x),
                        optimizer.init(m)) for m in mine]
            return opts[0] if bm is None else tuple(opts)

        opt_state = jax.jit(shard_map(
            local_init, mesh=mesh, in_specs=(specs,),
            out_specs=opt_specs, check_vma=False))(sharded)
        state = TrainState(sharded, opt_state, step0)
    else:
        from .pp import _opt_specs
        opt_state = sharded_opt_init(mesh, sharded, optimizer, specs)
        opt_specs = _opt_specs(opt_state, sharded, specs)
        state = TrainState(sharded, opt_state, step0)
    if wire == "int8_ef":
        from .compress import OverlapEFState

        def _zeros(shape):
            return jax.device_put(jnp.zeros(shape, jnp.float32),
                                  NamedSharding(mesh, dshard))

        if bm is None:
            ring_res = _zeros((n, tp, n * local))
            gather_res = _zeros((n, tp, local))
            ring_specs = gather_specs = dshard
        else:
            ring_res = tuple(_zeros((n, tp, n * sz)) for sz in bm.sizes)
            gather_res = tuple(_zeros((n, tp, sz)) for sz in bm.sizes)
            ring_specs = gather_specs = (dshard,) * bm.nbuckets
        state = OverlapEFState(state.params, state.opt_state, state.step,
                               ring_res, gather_res)
        state_specs = OverlapEFState(specs, opt_specs, P(),
                                     ring_specs, gather_specs)
    else:
        state_specs = TrainState(specs, opt_specs, P())
    return state, state_specs, n, pad, local, total, mode, period, bm


def _make_tp_overlap_local_step(cfg: LlamaConfig, optimizer, *, tp: int,
                                mode: str, period: int, n: int, pad: int,
                                local: int, total: int, microbatches: int,
                                wire: str, aggregation: str,
                                comm_scale: int = 1,
                                bucket_map=None,
                                numerics=None) -> Callable:
    """The per-shard DP×TP overlapped step body shared by
    ``make_tp_overlap_step`` and ``make_tp_overlap_multi_step`` — the
    ``_make_pp_overlap_local_step`` structure with the TP loss: the local
    batch splits into M sync-microbatches; each runs the PSA forward and
    psums its model-REPLICATED leaf grads over ``model``; microbatch m−1's
    flat gradient rides the ppermute ring over ``data`` (wire-formatted,
    per-(shard, chunk) error feedback) in the same trace positions as
    microbatch m's compute — the ACCO overlap, now under TP. Reduced
    chunks accumulate in fp32 on the owner; zero1 updates the owned slice
    and gathers fresh params (int8 delta gather under ``wire="int8_ef"``),
    gradient aggregation gathers the reduced gradient and applies the
    replicated update.

    Numerics contract mirrors the flat driver's: M>1 re-associates, so
    equivalence vs ``make_tp_step`` is fp32-tolerance; M=1 fp32 differs
    only by ring-vs-XLA reduction order.

    ``bucket_map`` (``_tp_bucket_map``, None for the legacy path) selects
    the bucketed backward: per-bucket ring vectors under labels
    ``tp_ring_grad_b{b}``, per-(data, model)-shard per-bucket EF/moment
    tuples, gather legs kept as ONE collective — the compress.py bucketing
    contract verbatim, with the model-agreed scale rule intact per
    bucket."""
    from ..utils import pytree as pt
    from .compress import (_bucket_slices, _bucket_vectors, _int8_encode,
                           _scatter_buckets, ring_reduce_scatter)

    M = microbatches
    bm = bucket_map
    B = bm.nbuckets if bm is not None else 1
    ef = wire == "int8_ef"
    # Model-agreed int8 scales (compress._int8_encode docstring): the flat
    # vector mixes model-cell-specific col/row shards with model-REPLICATED
    # leaves, so per-cell scales would decode the replicated entries
    # differently per cell and drift the model replicas apart — pinned by
    # tests/test_tp.py's replica-sync and preempt/resume tests.
    ssync = "model" if tp > 1 else None

    def _ring(pending, ring_res, bucket=None):
        label = ("tp_ring_grad" if bucket is None
                 else f"tp_ring_grad_b{bucket}")
        return ring_reduce_scatter(pending, "data", wire=wire,
                                   residual=ring_res, label=label,
                                   comm_scale=comm_scale,
                                   scale_sync_axis=ssync)

    def _ring_all(pending, ring_res):
        if bm is None:
            return _ring(pending, ring_res)
        reds, new_res = [], []
        for b in range(B):
            red_b, r_b = _ring(pending[b],
                               ring_res[b] if ef else None, b)
            reds.append(red_b)
            new_res.append(r_b)
        return jnp.concatenate(reds), new_res

    def local_step(state, tokens):
        params = state.params
        if tokens.shape[0] % M:
            raise ValueError(f"local batch {tokens.shape[0]} not divisible "
                             f"by overlap_microbatches={M}")
        micro = tokens.reshape((M, -1) + tokens.shape[1:])
        if not ef:
            ring_res = None
        elif bm is None:
            ring_res = state.ring_residual[0, 0]
        else:
            ring_res = [r[0, 0] for r in state.ring_residual]
        acc = jnp.zeros((local,), jnp.float32)
        loss_sum = jnp.zeros((), jnp.float32)
        gacc = None
        pending = None
        for m in range(M):
            (l, _), g = jax.value_and_grad(_tp_psa_loss, has_aux=True)(
                params, micro[m], cfg, tp, mode, period, None, comm_scale)
            g = jax.tree.map(
                lambda gr, s: gr if s else comm.psum(
                    gr, "model", label="tp_replicated_grads",
                    scale=comm_scale),
                g, _sharded_mask(g))
            loss_sum = loss_sum + (l * tp).astype(jnp.float32)
            if numerics is not None:
                # Extra OUTPUT only: the fp32 grad accumulator feeds the
                # summary, never the ring — losses/params bitwise on/off.
                gacc = (jax.tree.map(lambda x: x.astype(jnp.float32), g)
                        if gacc is None else
                        jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                     gacc, g))
            if pending is not None:
                # Microbatch m−1's ring rides alongside microbatch m's
                # forward/backward (the call above): independent dataflow.
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
            # Bucketed: per-bucket param-side flat views — the owned slice
            # is the concat of per-bucket chunks, in ring coordinate order.
            flat_p = None
            pvecs = _bucket_vectors(bm, params)
        gather_res = None
        gres = None
        if ef:
            gres = (jnp.concatenate([r[0, 0]
                                     for r in state.gather_residual])
                    if bm is not None else state.gather_residual[0, 0])
        shard = lax.axis_index("data")
        if aggregation == "zero1":
            if bm is None:
                p_mine = lax.dynamic_slice_in_dim(flat_p, shard * local,
                                                  local)
                # Local moment view: [1, 1, local] (data, model)-sharded
                # vector leaves squeeze to the flat slice; scalars pass.
                opt_local = jax.tree.map(
                    lambda x: x[0, 0] if getattr(x, "ndim", 0) >= 3 else x,
                    state.opt_state)
                new_p_mine, opt_local = apply_optimizer(optimizer, g_mine,
                                                        opt_local, p_mine)
                opt_state = jax.tree.map(
                    lambda x: (x[None, None] if getattr(x, "ndim", 0) >= 1
                               else x), opt_local)
            else:
                # One optimizer apply per bucket against the per-bucket
                # moments; elementwise updates make the concat
                # value-identical to the single-slice apply.
                p_chunks = [lax.dynamic_slice_in_dim(
                    pvecs[b], shard * bm.sizes[b], bm.sizes[b])
                    for b in range(B)]
                new_chunks, opts = [], []
                for b in range(B):
                    opt_b = jax.tree.map(
                        lambda x: (x[0, 0] if getattr(x, "ndim", 0) >= 3
                                   else x), state.opt_state[b])
                    np_b, opt_b = apply_optimizer(
                        optimizer,
                        g_mine[bm.offsets[b]:bm.offsets[b] + bm.sizes[b]],
                        opt_b, p_chunks[b])
                    new_chunks.append(np_b)
                    opts.append(jax.tree.map(
                        lambda x: (x[None, None]
                                   if getattr(x, "ndim", 0) >= 1 else x),
                        opt_b))
                p_mine = jnp.concatenate(p_chunks)
                new_p_mine = jnp.concatenate(new_chunks)
                opt_state = tuple(opts)
            vec_new = None
            if wire == "int8_ef":
                # Compressed second leg: broadcast the param DELTA int8
                # with its own EF residual (the compress.py zero1 rule —
                # fp32 moments stay exact, data replicas stay bitwise in
                # sync).
                q, s, gather_res = _int8_encode(
                    (new_p_mine - p_mine) + gres,
                    scale_sync_axis=ssync)
                q_all = comm.all_gather(q, "data", tiled=True,
                                        label="tp_delta_gather_int8",
                                        scale=comm_scale)
                s_all = comm.all_gather(s[None], "data", tiled=True,
                                        label="tp_delta_scale_gather",
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
                                           label="tp_param_gather",
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
                q, s, gather_res = _int8_encode(
                    g_mine + gres, scale_sync_axis=ssync)
                q_all = comm.all_gather(q, "data", tiled=True,
                                        label="tp_grad_gather_int8",
                                        scale=comm_scale)
                s_all = comm.all_gather(s[None], "data", tiled=True,
                                        label="tp_grad_scale_gather",
                                        scale=comm_scale)
                flat_g = (jnp.repeat(s_all, local)
                          * q_all.astype(jnp.float32))
            elif wire == "bf16":
                flat_g = comm.all_gather(
                    g_mine.astype(jnp.bfloat16), "data", tiled=True,
                    label="tp_grad_gather_bf16",
                    scale=comm_scale).astype(jnp.float32)
            else:
                flat_g = comm.all_gather(g_mine, "data", tiled=True,
                                         label="tp_grad_gather",
                                         scale=comm_scale)
            if bm is None:
                grads = unravel(flat_g[:total].astype(raw_flat.dtype))
            else:
                grads = _scatter_buckets(bm, _bucket_slices(bm, flat_g),
                                         params)
            new_params, opt_state = apply_optimizer(optimizer, grads,
                                                    state.opt_state, params)
        step = state.step + 1
        if ef:
            from .compress import OverlapEFState
            if bm is not None:
                # Per-bucket storage: each bucket's stack is a contiguous
                # ordered-coordinate range (the reshard_state contract).
                ring_out = tuple(r[None, None] for r in ring_res)
                gather_out = tuple(
                    gather_res[bm.offsets[b]:bm.offsets[b] + bm.sizes[b]]
                    [None, None] for b in range(B))
            else:
                ring_out = ring_res[None, None]
                gather_out = gather_res[None, None]
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


def make_tp_overlap_step(cfg: LlamaConfig,
                         optimizer: optax.GradientTransformation,
                         mesh: Mesh, params, *,
                         aggregation: str = "zero1",
                         wire: str = "fp32",
                         overlap_microbatches: int = 1,
                         psa: str = "",
                         comm_buckets: int = 1,
                         numerics=None):
    """Per-step DP×TP composition driver: ``step(state, tokens) -> (state,
    loss)`` over a ``[n_data·B, T]`` batch sharded over ``data``, with the
    data-axis gradient sync routed through the compressed/overlapped ring
    (semantics in ``_make_tp_overlap_local_step``). Returns ``(state,
    step_fn)`` — an ``OverlapEFState`` under ``wire="int8_ef"`` (EF
    residuals in the checkpointed tree, per (data, model) shard), a plain
    TrainState otherwise, with ZeRO-1 moments sharded over
    ``(data, model)`` when ``aggregation="zero1"``. ``comm_buckets > 1``
    selects the bucketed backward (per-bucket rings inside each
    microbatch's VJP window; compress.py contract)."""
    (state, state_specs, n, pad, local, total, mode, period,
     bm) = _tp_overlap_setup(optimizer, mesh, params, wire,
                             aggregation, psa, cfg.n_layers,
                             comm_buckets=comm_buckets)
    tp = mesh.shape["model"]
    has_data = mesh.shape.get("data", 1) > 1
    local_step = _make_tp_overlap_local_step(
        cfg, optimizer, tp=tp, mode=mode, period=period, n=n, pad=pad,
        local=local, total=total, microbatches=overlap_microbatches,
        wire=wire, aggregation=aggregation, bucket_map=bm,
        numerics=numerics)
    out_specs = (state_specs,
                 ((P(), numerics.summary_specs()) if numerics is not None
                  else P()))
    sharded = shard_map(
        local_step, mesh=mesh,
        in_specs=(state_specs, P("data") if has_data else P()),
        out_specs=out_specs, check_vma=False)
    return state, jax.jit(sharded, donate_argnums=(0,))


def make_tp_overlap_multi_step(cfg: LlamaConfig,
                               optimizer: optax.GradientTransformation,
                               mesh: Mesh, params, *,
                               aggregation: str = "zero1",
                               wire: str = "fp32",
                               overlap_microbatches: int = 1,
                               psa: str = "",
                               comm_buckets: int = 1,
                               numerics=None):
    """The DP×TP composition driver inside the K-step scan: ``step(state,
    window) -> (state, losses)`` with ``window`` a ``[K, n_data·B, T]``
    batch window (``shard_batch_window``) run in ONE compiled, donated
    dispatch — ZeRO-1 moments AND int8 EF residuals ride the scan carry,
    so error feedback is exact across fused steps, chunk-edge checkpoints
    and a preempt/resume cycle (pinned in tests/test_tp.py). The scanned
    body IS ``make_tp_overlap_step``'s, so the loss sequence and final
    state are bitwise-identical to K per-step calls at any K."""
    (state, state_specs, n, pad, local, total, mode, period,
     bm) = _tp_overlap_setup(optimizer, mesh, params, wire,
                             aggregation, psa, cfg.n_layers,
                             comm_buckets=comm_buckets)
    tp = mesh.shape["model"]
    has_data = mesh.shape.get("data", 1) > 1

    def multi(st, window):
        local_step = _make_tp_overlap_local_step(
            cfg, optimizer, tp=tp, mode=mode, period=period, n=n, pad=pad,
            local=local, total=total, microbatches=overlap_microbatches,
            wire=wire, aggregation=aggregation, bucket_map=bm,
            comm_scale=window.shape[0], numerics=numerics)
        return lax.scan(local_step, st, window)

    out_specs = (state_specs,
                 ((P(), numerics.summary_specs(stacked=True))
                  if numerics is not None else P()))
    sharded = shard_map(
        multi, mesh=mesh,
        in_specs=(state_specs, P(None, "data") if has_data else P()),
        out_specs=out_specs, check_vma=False)
    return state, jax.jit(sharded, donate_argnums=(0,))


def shard_batch_window(mesh: Mesh, window) -> jax.Array:
    """Device-put a [K, B, T] host batch window for the fused TP drivers:
    leading axis = K consecutive steps (replicated — every shard scans the
    same step sequence), second axis sharded over ``data`` when the mesh
    carries a real data axis (a size-1 axis normalizes to the replicated
    spec — the dp.data_partition jit-cache-stability rule); the ``model``
    axis never shards the batch (every TP shard sees the full local
    batch)."""
    spec = P(None, "data") if mesh.shape.get("data", 1) > 1 else P()
    return jax.device_put(window, NamedSharding(mesh, spec))


from .mesh import shard_batch  # noqa: E402,F401  (shared batch placement)
