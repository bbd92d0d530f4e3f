"""Data parallelism: one SPMD train step over a ``data`` mesh axis.

Capability target: the reference's two DP variants —
- gradient aggregation: per-iter allreduce of flattened grads then avg+step
  (reference: lab/tutorial_1b/DP/gradient_aggr/intro_DP_GA.py:41-68);
- weight aggregation: step first, then allreduce and average the *weights*
  (intro_DP_WA.py:41-67; the reference script never writes the averaged
  weights back — a recorded bug. We implement the intended semantics.)

TPU-native shape: the barrier/flatten/all_reduce/unflatten/scale dance
(intro_DP_GA.py:53-66) collapses to ``lax.pmean(grads, "data")`` inside a
``shard_map`` — the collective lowers to one XLA all-reduce over ICI, fused
with the step. No CPU staging, no sockets, no tags.

Hot-path fusion (the headline-bench lever): ``make_multi_step`` /
``make_zero1_multi_step`` scan K steps over a device-resident
``[K, B, T]`` batch window inside ONE compiled, donated dispatch — the
per-step Python dispatch/donation overhead (dominant on the oversubscribed
CPU fallback, measurable on accelerators) is paid once per K steps, and the
per-step loss history comes back as the scan's stacked output instead of K
host round trips. Semantics are bit-identical to K calls of the per-step
factory (asserted in tests/test_dp.py). Pattern references: weight-update
sharding (Xu et al., arxiv 2004.13336) and accumulate-while-you-communicate
overlap (ACCO, arxiv 2406.02613) — PAPERS.md.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.adam import apply_optimizer  # noqa: F401  (canonical home moved;
#                                         re-exported for existing callers)
from ..telemetry import comm


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jnp.ndarray


def init_state(params, optimizer: optax.GradientTransformation) -> TrainState:
    return TrainState(params, optimizer.init(params), jnp.zeros((), jnp.int32))


def sharded_opt_init(mesh: Mesh, params, optimizer: optax.GradientTransformation,
                     param_specs):
    """``optimizer.init`` with the optimizer state placed CORRECTLY on the
    mesh: moment subtrees (anything tree-isomorphic to params, e.g. adam's
    mu/nu) inherit the param PartitionSpecs; scalars (count) replicate.

    Plain ``jax.jit(optimizer.init)(params)`` does NOT do this — absent
    out_shardings it commits every output to one device, silently wasting
    HBM on what should be sharded moments.
    """
    pstruct = jax.tree.structure(params)

    def is_params_like(node):
        try:
            return jax.tree.structure(node) == pstruct
        except Exception:
            return False

    def shard_of(node):
        if is_params_like(node):
            return jax.tree.map(lambda s: NamedSharding(mesh, s), param_specs)
        return jax.tree.map(lambda _: NamedSharding(mesh, P()), node)

    abstract = jax.eval_shape(optimizer.init, params)
    out_shardings = jax.tree.map(shard_of, abstract, is_leaf=is_params_like)
    return jax.jit(optimizer.init, out_shardings=out_shardings)(params)


def _require_flat_data_mesh(mesh: Mesh, what: str) -> None:
    """The per-step dp factories reduce over the ``data`` axis only: on a
    hierarchical (dcn × data) mesh their pmean/scatter would aggregate
    within islands and silently never cross DCN. Hard error with the
    pointer to the composing path (compress.make_overlap_* with a per-axis
    wire dict) — the hierarchical collective layer is the one that knows
    the two-tier topology."""
    if mesh.shape.get("dcn", 1) > 1:
        raise ValueError(
            f"{what} reduces over the 'data' axis only and would silently "
            "aggregate per-island on a hierarchical (dcn x data) mesh; "
            "use the two-level ring driver (parallel/compress.py "
            "make_overlap_step / make_overlap_multi_step with "
            'wire={"ici": ..., "dcn": ...})')


def _make_local_grad_step(loss_fn: Callable, optimizer, accum_steps: int,
                          guard_nonfinite: bool, comm_scale: int = 1,
                          numerics=None) -> Callable:
    """The per-shard gradient-aggregation step body shared by the per-step
    factory (``make_grad_aggregation_step``) and the K-step scan driver
    (``make_multi_step``) — one implementation, so the two cannot drift.

    ``comm_scale`` is the telemetry execution multiplier: inside a
    ``lax.scan`` body the collectives trace once but run ``K`` times per
    dispatch, and the comm wrappers record that trip count so the static
    wire-byte profile stays exact (telemetry/comm.py ``scale``).

    ``numerics`` (telemetry.introspect.NumericsHandle) turns on the
    in-jit run-health summary: the step's second output becomes
    ``(loss, NumericsSummary)`` — per-layer-group grad/param/update norms
    plus the per-leaf gradient finite mask, computed from values the step
    already holds. Extra OUTPUTS never perturb the existing computation,
    so losses/params are bitwise identical with the summary on or off
    (pinned in tests/test_introspect.py). On THIS (replicated-gradient)
    path the summary reflects the ATTEMPTED update — under
    ``guard_nonfinite`` a skipped step still reports the norms/finite-mask
    of the update it refused (the zero1 body differs; see
    ``_make_zero1_local_step``)."""

    def local_step(state: TrainState, batch) -> Tuple[TrainState, jnp.ndarray]:
        if accum_steps == 1:
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        else:
            micro = batch.reshape((accum_steps, -1) + batch.shape[1:])

            def body(carry, mb):
                loss_sum, gsum = carry
                l, g = jax.value_and_grad(loss_fn)(state.params, mb)
                # Accumulate in fp32 regardless of param/grad dtype: a bf16
                # running sum would round away small microbatch
                # contributions (the vanishing-accumulation failure mode
                # ops/mixed_precision.py exists to fix).
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g)
                return (loss_sum + l.astype(jnp.float32), gsum), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (loss, gsum), _ = lax.scan(body, (jnp.zeros(()), zeros), micro)
            loss = loss / accum_steps
            grads = jax.tree.map(
                lambda g, p: (g / accum_steps).astype(p.dtype),
                gsum, state.params)
        # The one payload collective per iter (telemetry.comm wrappers are
        # lax pass-throughs that record bytes at trace time — see
        # telemetry/comm.py; compiled HLO is unchanged).
        # Named scopes (``grad_sync``, ``optimizer``, ``guard``) cost
        # nothing at run time: they are how a device trace tells the
        # step's parts apart (docs/COMPONENTS.md).
        with jax.named_scope("grad_sync"):
            grads = comm.pmean(grads, "data", label="grad_allreduce",
                               scale=comm_scale)
            loss = comm.pmean(loss, "data", label="loss_allreduce",
                              scale=comm_scale)
        with jax.named_scope("optimizer"):
            params, opt_state = apply_optimizer(optimizer, grads,
                                                state.opt_state, state.params)
        summary = (numerics.summarize(state.params, grads, params)
                   if numerics is not None else None)
        if guard_nonfinite:
            with jax.named_scope("guard"):
                ok = jnp.isfinite(loss)
                for g in jax.tree.leaves(grads):
                    ok &= jnp.all(jnp.isfinite(g))
                # Select-back, not zeroed grads: a zero-grad optimizer
                # update still decays Adam moments and bumps count — only
                # keeping the incoming state makes the skip a true no-op.
                params = jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                                      params, state.params)
                opt_state = jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                                         opt_state, state.opt_state)
            new_state = TrainState(params, opt_state,
                                   state.step + ok.astype(state.step.dtype))
        else:
            new_state = TrainState(params, opt_state, state.step + 1)
        return new_state, ((loss, summary) if summary is not None else loss)

    return local_step


def make_grad_aggregation_step(loss_fn: Callable, optimizer: optax.GradientTransformation,
                               mesh: Mesh, accum_steps: int = 1,
                               guard_nonfinite: bool = False,
                               numerics=None) -> Callable:
    """jit-compiled SPMD step: local grads -> pmean over ``data`` -> update.

    ``loss_fn(params, batch) -> scalar``. The batch's leading axis is sharded
    over ``data``; params/opt state are replicated and stay bitwise-identical
    across shards because every shard applies the same averaged gradient.

    ``accum_steps > 1`` enables gradient accumulation: each shard's local
    batch is split into ``accum_steps`` microbatches scanned sequentially,
    their gradients averaged before the ONE pmean + update — an
    ``accum_steps``-times larger effective batch at one microbatch's
    activation memory, with unchanged collective traffic. The local batch's
    leading dim must divide evenly. Equivalent to the full-batch step up to
    float re-association (asserted in tests/test_dp.py).

    ``guard_nonfinite=True`` fuses a post-allreduce finiteness guard into
    the step (resilience layer): if the *averaged* gradient or loss carries
    a NaN/Inf — one poisoned shard poisons the pmean for everyone, which is
    exactly why the check sits after the collective — the update is a
    select-back to the incoming params/opt state and ``step`` does not
    advance. Zero host syncs and donation-safe (the select happens inside
    the jitted program), so it composes with compressed-wire and accum
    variants of the surrounding loop; the skipped step is visible to the
    host as the returned non-finite loss and the non-advancing ``step``.
    The host-side StepGuard (resilience/guard.py) layers EMA anomaly
    detection and checkpoint rollback on top when those are wanted.

    ``numerics`` (see ``_make_local_grad_step``) changes the second
    output to ``(loss, NumericsSummary)`` — replicated, computed from the
    post-pmean gradient, bitwise-free for losses/params.
    """
    _require_flat_data_mesh(mesh, "make_grad_aggregation_step")
    local_step = _make_local_grad_step(loss_fn, optimizer, accum_steps,
                                       guard_nonfinite, numerics=numerics)
    sharded = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(), P("data")),
        out_specs=(P(), P()),
        check_vma=False,  # optax state carries non-vma-tracked leaves
    )
    return jax.jit(sharded, donate_argnums=(0,))


def make_multi_step(loss_fn: Callable, optimizer: optax.GradientTransformation,
                    mesh: Mesh, accum_steps: int = 1,
                    guard_nonfinite: bool = False, numerics=None) -> Callable:
    """Fused K-step driver: ``step(state, window) -> (state, losses)`` where
    ``window`` is a device-resident ``[K, n_shards·B, T]`` batch window
    (leading axis = consecutive training steps, second axis sharded over
    ``data`` — ``shard_batch_window``) and ``losses`` is the ``[K]``
    per-step loss sequence from the scan's stacked outputs.

    One compiled, donated dispatch runs all K steps: Python dispatch,
    donation bookkeeping and the host round trip are paid once per window
    instead of once per step. The scanned body IS
    ``make_grad_aggregation_step``'s body (shared ``_make_local_grad_step``),
    so the loss sequence and final state are bit-identical to K per-step
    calls (asserted in tests/test_dp.py at K∈{1,4}), and per-step wire
    bytes are unchanged — the comm profile records the same collectives at
    ``scale=K`` per dispatch.

    K is read from the window's static leading dim at trace time, so ONE
    returned callable serves every chunk size (a tail chunk of k < K steps
    just triggers one more compile for that shape).
    """

    _require_flat_data_mesh(mesh, "make_multi_step")

    def multi(state: TrainState, window):
        local_step = _make_local_grad_step(loss_fn, optimizer, accum_steps,
                                           guard_nonfinite,
                                           comm_scale=window.shape[0],
                                           numerics=numerics)
        return lax.scan(local_step, state, window)

    sharded = shard_map(
        multi,
        mesh=mesh,
        in_specs=(P(), P(None, "data")),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0,))


def make_weight_aggregation_step(loss_fn: Callable, optimizer: optax.GradientTransformation,
                                 mesh: Mesh) -> Callable:
    """Step locally on the local shard's gradient, then average the *weights*
    across shards — the reference's intro_DP_WA semantics, implemented as the
    intended average-in-place (not its no-op bug)."""
    _require_flat_data_mesh(mesh, "make_weight_aggregation_step")

    def local_step(state: TrainState, batch) -> Tuple[TrainState, jnp.ndarray]:
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        params = comm.pmean(params, "data", label="weight_allreduce")
        # Average the optimizer moments too: the reference keeps per-process
        # Adam state, but an SPMD TrainState declared replicated must BE
        # replicated — divergent per-shard moments would silently collapse to
        # shard 0's on any reshard/checkpoint. Documented deviation.
        opt_state = comm.pmean(opt_state, "data", label="optstate_allreduce")
        loss = comm.pmean(loss, "data", label="loss_allreduce")
        return TrainState(params, opt_state, state.step + 1), loss

    sharded = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(), P("data")),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0,))


def data_axes(mesh: Mesh):
    """The mesh axes that together form the data-parallel world, outermost
    first: ``("dcn", "data")`` on a hierarchical mesh
    (parallel/distributed.py:hier_data_mesh — ICI islands bridged by DCN),
    ``("data",)`` otherwise. Every batch-sharding helper and the
    hierarchical collective layer (parallel/compress.py) read the topology
    through this one function, so flat and two-tier meshes cannot drift."""
    if mesh.shape.get("dcn", 1) > 1:
        return ("dcn", "data")
    return ("data",)


def data_partition(mesh: Mesh):
    """The PartitionSpec ENTRY for a dim sharded over the data world,
    normalized for jit-cache stability: a bare axis name when one axis
    carries the sharding, a tuple only when both hierarchical axes are
    real (size > 1). Sharding over a size-1 axis is a placement no-op,
    but the un-normalized spec survives into the state's sharding and
    differs from what shard_map's outputs report — the donated state
    would then miss the jit cache on its SECOND dispatch (one silent
    retrace per driver, caught by the comm_wire_smoke retrace gate)."""
    axes = data_axes(mesh)
    if len(axes) == 1:
        return axes[0]
    axes = tuple(a for a in axes if mesh.shape[a] > 1)
    return axes if len(axes) > 1 else axes[0]


def _flat_geometry(mesh: Mesh, params):
    """Padded flat-vector geometry shared by ZeRO-1 and the overlapped ring
    driver (parallel/compress.py): ``(n, pad, local, total)`` — n = the
    data-parallel world size (the ``data`` axis, × the ``dcn`` axis on a
    hierarchical mesh), total = the param count, pad brings it to a
    multiple of n, local = (total + pad) // n = one shard's slice (and one
    ring chunk). One implementation so the slice a ring chunk lands on is
    always the slice the ZeRO-1 update owns."""
    from ..utils import pytree as pt

    n = 1
    for a in data_axes(mesh):
        n *= mesh.shape[a]
    total = pt.param_count(params)
    pad = (-total) % n
    local = (total + pad) // n
    return n, pad, local, total


def hier_slice_index(n_dcn: int):
    """The hierarchical slice-ownership map, trace-time inside
    ``shard_map``: shard (d, s) owns flat slice ``s·D + d`` — the slice
    the two-level reduce-scatter's chunk lands on (phase 1 over the ICI
    ``data`` axis scatters superchunk s, phase 2 over ``dcn`` scatters
    chunk d within it; see compress.hier_reduce_scatter). THE one rule —
    the ZeRO-1 setup and the ring drivers both call it, so the reduced
    chunk always lands on the shard whose update owns it."""
    return lax.axis_index("data") * n_dcn + lax.axis_index("dcn")


def slice_index(mesh: Mesh):
    """This shard's slice of the padded flat param vector (trace-time,
    must run inside ``shard_map``): the ``data`` rank on a flat mesh,
    ``hier_slice_index`` on a hierarchical one. On a mesh that also
    carries a ``stage`` axis the same data-rank ownership map applies
    PER STAGE GROUP — the DP×PP drivers (parallel/pp.py
    ``_pp_overlap_setup``) read ``lax.axis_index("data")`` directly and
    shard their moments/residuals ``(data, stage)``, each stage's shard
    group owning its own stage slice's 1/n."""
    axes = data_axes(mesh)
    if len(axes) == 1:
        return lax.axis_index(axes[0])
    return hier_slice_index(mesh.shape["dcn"])


def _zero1_setup(optimizer, mesh: Mesh, params):
    """Shared ZeRO-1 initialization: the padded flat-vector geometry, the
    local-slice optimizer PartitionSpecs, and the initial TrainState with
    moments sharded over the data-parallel world (each shard owns the
    moments of its 1/n slice — the ``sharded_opt_init`` placement idea
    taken one step further, from "moments on the right devices" to "each
    device holds only its slice"; on a hierarchical mesh the slice is the
    one ``slice_index`` assigns). Returns ``(state, opt_specs, n, pad,
    local, total)``. The DP×PP generalization — the same geometry per
    STAGE slice, moments ``[n, S, local]`` sharded ``(data, stage)`` —
    lives in parallel/pp.py ``_pp_overlap_setup``."""
    from ..utils import pytree as pt

    dpart = data_partition(mesh)
    n, pad, local, total = _flat_geometry(mesh, params)

    # PartitionSpecs for the local-slice optimizer state: vector leaves
    # (mu/nu, [local]) shard over the data world; scalars (count)
    # replicate — every shard steps them identically.
    abstract_opt = jax.eval_shape(
        optimizer.init, jax.ShapeDtypeStruct((local,), jnp.float32))
    opt_specs = jax.tree.map(
        lambda x: P(dpart) if getattr(x, "ndim", 0) >= 1 else P(),
        abstract_opt)

    def local_init(params):
        # Each shard owns moments for its slice of the padded flat vector.
        shard = slice_index(mesh)
        flat = jnp.pad(pt.flatten(params)[0].astype(jnp.float32), (0, pad))
        mine = lax.dynamic_slice_in_dim(flat, shard * local, local)
        return optimizer.init(mine)

    opt_state = jax.jit(shard_map(
        local_init, mesh=mesh, in_specs=P(),
        out_specs=opt_specs, check_vma=False))(params)
    state = TrainState(replicate(mesh, params), opt_state,
                       jax.device_put(jnp.zeros((), jnp.int32),
                                      NamedSharding(mesh, P())))
    return state, opt_specs, n, pad, local, total


def _make_zero1_local_step(loss_fn: Callable, optimizer, n: int, pad: int,
                           local: int, total: int, *,
                           guard_nonfinite: bool = False,
                           comm_scale: int = 1, numerics=None) -> Callable:
    """The per-shard ZeRO-1 step body shared by ``make_zero1_step`` and
    ``make_zero1_multi_step``: local grads → reduce-scatter (each shard
    receives the averaged 1/n-th of the flat gradient) → optimizer update on
    the LOCAL slice only → all-gather of the fresh parameter slices.

    ``guard_nonfinite`` needs one extra (4-byte) collective here, unlike the
    replicated path: a NaN in shard j's gradient contribution lands only in
    the slice coordinates whose owner summed it, so the finiteness verdict
    is per-shard and must be psum-agreed before anyone applies an update —
    otherwise the replicas' "replicated" params would silently diverge."""

    def local_step(state: TrainState, batch):
        from ..utils import pytree as pt

        params = state.params
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        flat_g = jnp.pad(pt.flatten(grads)[0].astype(jnp.float32), (0, pad))
        # Averaged 1/n-th of the gradient lands on its owner shard.
        with jax.named_scope("grad_sync"):
            g_mine = comm.psum_scatter(flat_g, "data", scatter_dimension=0,
                                       tiled=True, label="zero1_grad_scatter",
                                       scale=comm_scale) / n
        raw_flat, unravel = pt.flatten(params)
        flat_p = jnp.pad(raw_flat.astype(jnp.float32), (0, pad))
        shard = lax.axis_index("data")
        p_mine = lax.dynamic_slice_in_dim(flat_p, shard * local, local)
        with jax.named_scope("optimizer"):
            new_p_mine, opt_state = apply_optimizer(optimizer, g_mine,
                                                    state.opt_state, p_mine)
        with jax.named_scope("grad_sync"):
            loss = comm.pmean(loss, "data", label="loss_allreduce",
                              scale=comm_scale)
        if guard_nonfinite:
            with jax.named_scope("guard"):
                ok = jnp.all(jnp.isfinite(g_mine)) & jnp.isfinite(loss)
                ok = comm.psum(ok.astype(jnp.int32), "data",
                               label="zero1_guard_verdict",
                               scale=comm_scale) == n
                new_p_mine = jnp.where(ok, new_p_mine, p_mine)
                opt_state = jax.tree.map(lambda nw, o: jnp.where(ok, nw, o),
                                         opt_state, state.opt_state)
            step = state.step + ok.astype(state.step.dtype)
        else:
            step = state.step + 1
        with jax.named_scope("grad_sync"):
            flat_new = comm.all_gather(new_p_mine, "data", tiled=True,
                                       label="zero1_param_gather",
                                       scale=comm_scale)[:total]
        # Cast back before unravel: for single-dtype trees ravel_pytree's
        # unravel is dtype-polymorphic and would silently rebuild non-fp32
        # params (e.g. param_dtype="bfloat16") as fp32.
        new_params = unravel(flat_new.astype(raw_flat.dtype))
        if numerics is not None:
            # Built with psum_axis="data": the LOCAL grads differ per
            # shard, so the summarizer psum-agrees the grad stats + finite
            # mask inside this same dispatch (introspect.make_summarizer).
            # Under ``guard_nonfinite`` the summary here describes the
            # POST-guard state (a skipped step reports update ≈ 0) — the
            # attempted update's magnitude would cost a second all-gather
            # of the unselected slices; the grad norms and finite mask
            # still describe the FAULTED gradient, which is the
            # attribution a postmortem needs. The replicated-gradient
            # path reports the attempted update (no extra wire there).
            summary = numerics.summarize(params, grads, new_params)
            return TrainState(new_params, opt_state, step), (loss, summary)
        return TrainState(new_params, opt_state, step), loss

    return local_step


def make_zero1_step(loss_fn: Callable, optimizer: optax.GradientTransformation,
                    mesh: Mesh, params, *,
                    guard_nonfinite: bool = False,
                    numerics=None) -> Tuple[TrainState, Callable]:
    """ZeRO-1 data parallelism: optimizer state sharded across the ``data``
    axis (parity-plus — SURVEY.md §2.10 marks ZeRO/FSDP absent in the
    reference; pattern reference: "Automatic Cross-Replica Sharding of
    Weight Update in Data-Parallel Training", arxiv 2004.13336, PAPERS.md).

    Per step, on each shard: local grads → ``lax.psum_scatter`` (averaged
    1/n-th of the flattened gradient, half the allreduce's wire volume for
    this leg) → optimizer update on the LOCAL moment slice only →
    ``lax.all_gather`` of the updated parameter slice. Params stay
    replicated; Adam's mu/nu shrink to 1/n per device — the memory that
    caps model size under plain DP — and the update FLOPs drop n× with
    them. Ring wire bytes stay at allreduce parity: scatter ``(n−1)/n`` +
    gather ``(n−1)``·(1/n local shard) ≈ allreduce's ``2(n−1)/n`` —
    verified against the telemetry comm profile in tests/test_dp.py.

    Exact-equivalence caveat: valid for elementwise optimizers (sgd, adam,
    adamw, ...) whose update at coordinate i depends only on history at i —
    slicing commutes with the update rule (ops/adam.py), so the result is
    bit-comparable to ``make_grad_aggregation_step`` (asserted in
    tests/test_dp.py). The update goes through ``apply_optimizer``, so the
    fused-apply fast path (ops/pallas_adam.py) works on the slice too.

    ``guard_nonfinite`` fuses the in-jit skip guard, at the cost of one
    4-byte psum per step (see ``_make_zero1_local_step``).

    Returns ``(state, step_fn)`` — the initial TrainState with sharded
    moments, and ``step_fn(state, batch) -> (state, loss)``.

    Transient-memory note: each step ravels the replicated params/grads into
    one padded fp32 vector before the scatter — a ~2·|params| fp32 transient
    per device. The *persistent* saving (moments at 1/n, the 2/3 of Adam
    state that caps model size) is what ZeRO-1 is for; a fully flat-resident
    params layout would trade API simplicity for removing the transient.
    """
    _require_flat_data_mesh(mesh, "make_zero1_step")
    state, opt_specs, n, pad, local, total = _zero1_setup(optimizer, mesh,
                                                          params)
    local_step = _make_zero1_local_step(loss_fn, optimizer, n, pad, local,
                                        total,
                                        guard_nonfinite=guard_nonfinite,
                                        numerics=numerics)
    step = shard_map(
        local_step, mesh=mesh,
        in_specs=(TrainState(P(), opt_specs, P()), P("data")),
        out_specs=(TrainState(P(), opt_specs, P()), P()),
        check_vma=False)
    return state, jax.jit(step, donate_argnums=(0,))


def make_zero1_multi_step(loss_fn: Callable,
                          optimizer: optax.GradientTransformation,
                          mesh: Mesh, params, *,
                          guard_nonfinite: bool = False, numerics=None
                          ) -> Tuple[TrainState, Callable]:
    """The two hot-path levers composed: the ZeRO-1 sharded weight update
    *inside* the K-step scan driver. ``step(state, window) -> (state,
    losses)`` with ``window`` a ``[K, n_shards·B, T]`` batch window
    (``shard_batch_window``) — one donated dispatch runs K full
    reduce-scatter → sliced-update → all-gather steps, moments staying
    sharded in the scan carry throughout. Same equivalence contract as
    ``make_zero1_step`` (fp32-tolerance vs the replicated update), same
    per-step wire bytes (comm profile records ``scale=K``)."""
    _require_flat_data_mesh(mesh, "make_zero1_multi_step")
    state, opt_specs, n, pad, local, total = _zero1_setup(optimizer, mesh,
                                                          params)

    def multi(state: TrainState, window):
        local_step = _make_zero1_local_step(
            loss_fn, optimizer, n, pad, local, total,
            guard_nonfinite=guard_nonfinite, comm_scale=window.shape[0],
            numerics=numerics)
        return lax.scan(local_step, state, window)

    step = shard_map(
        multi, mesh=mesh,
        in_specs=(TrainState(P(), opt_specs, P()), P(None, "data")),
        out_specs=(TrainState(P(), opt_specs, P()), P()),
        check_vma=False)
    return state, jax.jit(step, donate_argnums=(0,))


def reshard_state(host_state, template_state):
    """Cross-topology state resharding: place a host-RAM TrainState snapshot
    (numpy leaves — e.g. an elastic controller's last-good mirror, or a
    checkpoint restored at its saved shapes) into ``template_state``'s
    layout, which may live on a DIFFERENT-SIZE mesh than the snapshot was
    taken on.

    Leaf rule: equal shapes re-place as-is into the template's sharding
    (replicated params land on every survivor; scalars replicate); a flat
    vector whose length differs is an N-way ZeRO-1 padded slice stack
    (params/mu/nu over the old ``data`` axis) and is resized to the M-way
    padded length via ``ops.adam.resize_zero_padded`` — the
    all-gather-then-rescatter: the host copy IS the gather, the resize
    swaps the pad, and the ``device_put`` against the template's
    ``P("data")`` sharding is the rescatter. Zero-pad-tail violations are
    a hard error there, not silent truncation.

    ``OverlapEFState`` snapshots (the int8-ring drivers) reshard too: the
    1-D ``gather_residual`` [Ppad] is pad-swapped by the flat-vector rule
    above (pad coordinates carry zero error — quantizing an exactly-zero
    pad is exact — so the zero-tail check holds), and the 2-D
    ``ring_residual`` [n, ring_len] goes through ``_resize_ring_residual``
    row-wise before the leaf pass. That is what lets elastic mode compose
    with compressed wire (ROADMAP 7c).

    Bucketed snapshots (``comm_buckets > 1``: both EF residual fields are
    per-bucket TUPLES) reshard bucket-by-bucket. Bucket counts must match
    between snapshot and template (rebucketing a live EF state is
    undefined — the residuals are per-coordinate pending corrections in
    bucket coordinate order). Every bucket except the last covers a FIXED
    span of flat coordinates (the global pad rides the last bucket), so a
    world resize is representable only when the new ``(world, buckets)``
    pair reproduces the interior bucket spans; otherwise the named
    "indivisible bucket×shard factorization" error fires — resize through
    ``comm_buckets=1``, or pick a divisible pair. Interior-span-preserving
    resizes run ``_resize_ring_residual`` per bucket (rows re-chunk, last
    bucket pad-swaps) and the per-bucket 1-D gather residuals fall through
    to the flat-vector leaf rule.

    Multi-axis templates route through dedicated pre-passes before the
    leaf rule:

    - a template living on a mesh WITH a ``stage`` axis is a DP×PP
      overlap state — ``pp.repartition_stage_state`` rewrites the
      ``(data, stage)`` stacks (ZeRO-1 moments, ring/gather EF residuals)
      through topology-invariant global coordinate ids, handling stage
      re-partition S→S′, data resize, or both at once. That pre-pass
      REPLACES the flat-ring pre-pass below (the PP residuals are 3-D
      ``[n, S, ·]`` stacks, not flat rings) and leaves every stack at the
      template's exact shape, so the leaf rule is placement-only.
    - a ``TPActState`` snapshot (the PSA activation-EF trainer) resizes
      its ``act_residual`` ``[n_data, tp, L, 2, B, T, D]`` across a
      data-axis resize by the row rule of ``_resize_ring_residual``:
      per-shard batch is fixed, so surviving data rows copy bitwise,
      new rows start at zero pending error, dropped rows die with their
      shards. Any non-``data`` dimension changing is a named error.

    Value-exact by construction: every surviving coordinate is a bitwise
    copy, so a trajectory continued from the resharded state is the
    trajectory of a fresh M-way run initialized from the same snapshot
    (asserted in tests/test_elastic.py)."""
    from ..ops.adam import resize_zero_padded

    t_arrays = [x for x in jax.tree.leaves(template_state)
                if isinstance(x, jax.Array)]
    t_mesh = t_arrays[0].sharding.mesh if t_arrays else None
    on_stage_mesh = (t_mesh is not None
                     and "stage" in getattr(t_mesh, "axis_names", ()))
    if on_stage_mesh:
        from . import pp as _pp
        host_state = _pp.repartition_stage_state(host_state, template_state)

    if hasattr(host_state, "act_residual") and hasattr(
            template_state, "act_residual"):
        host_state = host_state._replace(
            act_residual=_resize_act_residual(
                np.asarray(host_state.act_residual),
                tuple(template_state.act_residual.shape)))

    if (not on_stage_mesh
            and hasattr(host_state, "ring_residual")
            and hasattr(template_state, "ring_residual")):
        h_rr = host_state.ring_residual
        t_rr = template_state.ring_residual
        h_tup, t_tup = isinstance(h_rr, tuple), isinstance(t_rr, tuple)
        if h_tup != t_tup or (h_tup and len(h_rr) != len(t_rr)):
            raise ValueError(
                f"comm_buckets mismatch: the snapshot carries "
                f"{len(h_rr) if h_tup else 1} EF residual bucket(s), the "
                f"template {len(t_rr) if t_tup else 1} — rebucketing a "
                f"live EF state is not defined; rebuild the trainer with "
                f"the snapshot's comm_buckets")
        if h_tup:
            for b, (h, t) in enumerate(zip(h_rr[:-1], t_rr[:-1])):
                if int(np.asarray(h).shape[-1]) != int(t.shape[-1]):
                    raise ValueError(
                        f"indivisible bucket×shard factorization: "
                        f"interior bucket {b} covers "
                        f"{int(np.asarray(h).shape[-1])} coordinates in "
                        f"the snapshot but {int(t.shape[-1])} in the "
                        f"template — bucket boundaries move with the data "
                        f"world unless the per-shard slice divides "
                        f"evenly; resize via comm_buckets=1 or choose a "
                        f"(world, comm_buckets) pair that preserves the "
                        f"interior bucket spans")
            host_state = host_state._replace(ring_residual=tuple(
                _resize_ring_residual(np.asarray(h), tuple(t.shape))
                for h, t in zip(h_rr, t_rr)))
        else:
            host_state = host_state._replace(
                ring_residual=_resize_ring_residual(
                    np.asarray(h_rr), tuple(t_rr.shape)))

    def leaf(h, t):
        if not isinstance(t, jax.Array):
            return h
        h = np.asarray(h)
        if h.shape != t.shape:
            h = resize_zero_padded(h, t.shape[0] if t.ndim == 1 else -1)
        return jax.device_put(h, t.sharding)

    return jax.tree.map(leaf, host_state, template_state)


def _resize_ring_residual(h: np.ndarray, new_shape) -> np.ndarray:
    """Resize an int8-ring EF ``ring_residual`` [n_old, ring_len_old] to a
    new data-parallel world's [n_new, ring_len_new] — the per-(shard,chunk)
    generalization of ``resize_zero_padded``'s pad swap.

    Row r is shard r's per-coordinate pending quantization error over the
    flat padded vector, so each surviving row pad-swaps exactly like a
    ZeRO-1 moment slice stack (tail coordinates sit in the zero pad, where
    quantization error is exactly zero — nonzero tails hard-error, same
    contract). New rows (grow) start at zero error like a fresh shard's.
    Each row's OWN-chunk slice is re-zeroed in the NEW geometry: the owner
    never quantizes its own chunk (its contribution is added in fp32), so
    the slot is structurally zero — but the chunk boundaries moved with
    ``n``, and coordinates that used to belong to another shard's chunk may
    land in the own-chunk slot carrying old error the ring would never
    read or clear.

    Dropped rows (shrink) carry the dead shards' pending corrections —
    bounded by one int8 quantization step per coordinate — and are lost
    with the topology, exactly as the dead shards' unsent partials are.
    Both recovery paths (mirror and checkpoint) route through here, so the
    post-remesh trajectory still bitwise-matches a fresh run restored from
    the same snapshot."""
    from ..ops.adam import resize_zero_padded

    n_new, len_new = int(new_shape[0]), int(new_shape[1])
    n_old, _ = h.shape
    if len_new % n_new:
        raise ValueError(f"ring_len {len_new} is not a multiple of the "
                         f"data world {n_new} — not a flat-ring residual")
    local_new = len_new // n_new
    out = np.zeros((n_new, len_new), h.dtype)
    for r in range(min(n_old, n_new)):
        out[r] = resize_zero_padded(np.asarray(h[r]), len_new)
        out[r, r * local_new:(r + 1) * local_new] = 0.0
    return out


def _resize_act_residual(h: np.ndarray, new_shape) -> np.ndarray:
    """Resize a PSA ``act_residual`` [n_data, tp, L, 2, B, T, D] across a
    data-axis resize. Row r is data-shard r's per-sub-layer pending
    activation quantization error over its OWN fixed-size microbatch
    (per-shard batch is constant across worlds — the global batch scales
    with n), so the data dimension follows ``_resize_ring_residual``'s row
    rule: surviving rows copy bitwise, new rows (grow) start at zero
    pending error like a fresh shard's, dropped rows (shrink) die with
    their shards' in-flight data. Every non-``data`` dimension is
    topology-independent (tp layout, layer count, sub-layer pair, batch
    geometry) — a mismatch there is a reconfiguration, not a resize, and
    hard-errors by name."""
    if h.shape[1:] != tuple(new_shape[1:]):
        raise ValueError(
            f"act_residual resize only moves the data axis: snapshot "
            f"{h.shape} vs template {tuple(new_shape)} differ beyond "
            f"dimension 0 — changing tp/layers/batch geometry across a "
            f"re-mesh is not a resize")
    n_new = int(new_shape[0])
    out = np.zeros(tuple(new_shape), h.dtype)
    n_keep = min(h.shape[0], n_new)
    out[:n_keep] = h[:n_keep]
    return out


def host_snapshot(state):
    """Full host-RAM copy of a (possibly sharded) TrainState — the gather
    half of elastic recovery's fast path. ``np.asarray`` on a sharded
    global array materializes the whole array on host (single-process),
    so ZeRO-1 moment slices from EVERY replica land in the mirror — which
    is what makes recovery onto fewer replicas possible after some of
    those slices' owners die."""
    return jax.tree.map(
        lambda x: np.asarray(x) if isinstance(x, jax.Array) else x, state)


def shard_batch(mesh: Mesh, batch) -> jax.Array:
    """Device-put a [n_shards·B, ...] host batch with leading axis sharded
    over the data-parallel world — ``data``, or ``("dcn", "data")``
    island-major on a hierarchical mesh (shard (d, s) reads batch rows
    [(d·S+s)·B, (d·S+s+1)·B), matching the device order)."""
    return jax.device_put(batch,
                          NamedSharding(mesh, P(data_partition(mesh))))


def shard_batch_window(mesh: Mesh, window) -> jax.Array:
    """Device-put a [K, n_shards·B, T] host batch window for the multi-step
    drivers: leading axis = K consecutive steps (replicated — every shard
    scans the same step sequence), second axis sharded over the
    data-parallel world (``data``, or ``("dcn", "data")`` hierarchically —
    same rule as ``shard_batch``)."""
    return jax.device_put(
        window, NamedSharding(mesh, P(None, data_partition(mesh))))


def replicate(mesh: Mesh, tree):
    """Replicate a host/device tree onto the mesh — via an explicit copy.

    A plain device_put keeps the caller's own buffer as one replica shard,
    and every step factory here donates its state: donating that aliased
    buffer silently deletes the caller's original ('Array has been deleted'
    when two states are built from one params tree — and ``may_alias=False``
    does NOT prevent the alias on this backend, verified empirically). The
    copy is init-time-only and insulates the caller's tree."""
    fresh = jax.tree.map(
        lambda x: jnp.array(x, copy=True) if isinstance(x, jax.Array) else x,
        tree)
    return jax.device_put(fresh, NamedSharding(mesh, P()))
