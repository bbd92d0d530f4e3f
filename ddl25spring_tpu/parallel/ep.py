"""Expert parallelism: the MoE expert bank sharded over an ``expert`` axis.

Parity-plus (SURVEY.md §2.10: EP "Absent" in the reference). Each device
holds ``n_experts / ep`` experts' weights and runs ONLY its local experts'
matmuls; the tiny router runs replicated on every shard (its [D, E] matrix
is negligible next to the expert FFNs) and the combine is one psum over the
``expert`` axis — dispatch stays dense/static-shaped, so the per-expert
matmuls land on the MXU and the collective rides ICI.

Gradient accounting mirrors parallel.tp: per-shard loss is scaled by 1/ep
before differentiation (each shard's replicated loss copy sees every shard's
expert weights through the psum), making sharded-leaf grads exact locally
and replicated-leaf grads exact after a psum over ``expert``. Composes with
data parallelism on a ``(data, expert)`` mesh.
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

from ..config import MoEConfig
from ..models import moe
from ..ops import causal_lm_loss
from .dp import TrainState, apply_optimizer, sharded_opt_init

_EXPERT_LEAVES = {"w_gate", "w_up", "w_down"}   # leading [L, E, ...] axis


def param_specs(params: dict) -> dict:
    """PartitionSpecs: expert banks sharded on their [E] axis (dim 1 after
    the stacked-layer dim), everything else replicated."""
    def block_spec(name, leaf):
        if name in _EXPERT_LEAVES:
            return jax.tree.map(lambda _: P(None, "expert", None, None), leaf)
        return jax.tree.map(lambda _: P(), leaf)

    return {
        k: ({name: block_spec(name, leaf) for name, leaf in v.items()}
            if k == "blocks" else jax.tree.map(lambda _: P(), v))
        for k, v in params.items()
    }


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


def _ep_loss(params: dict, tokens: jnp.ndarray, cfg: MoEConfig,
             ep: int) -> jnp.ndarray:
    logits, aux = moe.forward(params, tokens, cfg, expert_axis="expert")
    loss = causal_lm_loss(logits, tokens) + cfg.aux_loss_coef * aux
    return loss / ep


def make_ep_train_step(cfg: MoEConfig, optimizer: optax.GradientTransformation,
                       mesh: Mesh) -> Callable:
    """jit-compiled MoE train step on a ``(data?, expert)`` mesh."""
    ep = mesh.shape["expert"]
    has_data = mesh.shape.get("data", 1) > 1

    def sharded_grads(params: dict, tokens):
        loss, grads = jax.value_and_grad(_ep_loss)(params, tokens, cfg, ep)
        def _replicated_psum(x):
            return comm.psum(x, "expert", label="ep_replicated_grads")

        grads = {
            k: ({name: (g if name in _EXPERT_LEAVES else
                        jax.tree.map(_replicated_psum, g))
                 for name, g in v.items()} if k == "blocks"
                else jax.tree.map(_replicated_psum, v))
            for k, v in grads.items()
        }
        loss = loss * ep
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
        params, opt_state = apply_optimizer(optimizer, grads,
                                            state.opt_state, state.params)
        return TrainState(params, opt_state, state.step + 1), loss

    return jax.jit(step, donate_argnums=(0,))


@functools.cache
def _ep_forward_fn(cfg: MoEConfig, mesh: Mesh) -> Callable:
    def body(params, tokens):
        logits, aux = moe.forward(params, tokens, cfg, expert_axis="expert")
        return logits, aux

    def fn(params, tokens):
        return shard_map(
            body, mesh=mesh,
            in_specs=(param_specs(params), P()),
            out_specs=(P(), P()),
            check_vma=False,
        )(params, tokens)

    return jax.jit(fn)


def ep_forward(params: dict, tokens: jnp.ndarray, cfg: MoEConfig,
               mesh: Mesh):
    """(logits, aux) via expert-parallel forward; cached on (cfg, mesh)."""
    return _ep_forward_fn(cfg, mesh)(params, tokens)


from .mesh import shard_batch  # noqa: E402,F401  (shared batch placement)
