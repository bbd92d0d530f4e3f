"""Compressed gradient all-reduce for data parallelism.

Capability/pattern target: the reference's DP loop all-reduces full-precision
fp32 gradients every iteration (lab/tutorial_1b/DP/gradient_aggr/
intro_DP_GA.py:53-66 — flatten, allreduce, scale); at multi-host scale the
wire bytes of that allreduce are the step's bandwidth bill. Public pattern
references for shrinking it inside an XLA program: EQuARX (quantized
all-reduce in XLA, arxiv 2506.17615) and DynamiQ (compressed all-reduce,
arxiv 2602.08923) — see PAPERS.md. This module implements the two standard
operating points, TPU-first (the compression is elementwise work XLA fuses
around one collective; no custom comm code):

- **bf16 wire format** (``make_bf16_grad_step``): cast grads to bf16, pmean,
  upcast. Halves the wire bytes; stateless; the mantissa loss per step is
  ~1e-3 relative and unbiased enough in practice that it is the default
  "free" lever on DCN-bound topologies.

- **int8 + error feedback** (``make_int8_ef_grad_step``): per-leaf symmetric
  quantization to int8 around the shard-group max (one pmax of the stacked
  per-leaf maxima keeps every shard on the same fixed-point grid), then ONE
  **int8 all-gather of the whole concatenated gradient** — a single
  collective launch whose wire operand is the 1-byte payload — followed by
  an exact local int32 sum and per-leaf dequantization. (A psum of the
  quantized values would be mathematically identical but moves int32 on the
  wire — zero savings; gathering the int8 payload keeps the wire at
  1 byte/element, ~8× fewer bytes than the fp32 allreduce's ≈2×4
  bytes/element, at the cost of an n_shards× int8 transient.) The local
  quantization residual is fed back into the next step's gradient (error
  feedback — the standard fix that restores convergence for biased
  compressors).

Both factories return ``(state, step_fn)`` with the same TrainState the
plain step uses; the int8 variant carries its residual tree inside an
extended state tuple. Equivalence/convergence pinned in
tests/test_compress.py.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..telemetry import comm

from .dp import TrainState, apply_optimizer, init_state, replicate


def _pmean_bf16(grads, axis: str):
    """pmean with a bf16 wire format: the collective moves half the bytes;
    accumulation happens in the reduction's native precision."""
    down = jax.tree.map(lambda g: g.astype(jnp.bfloat16), grads)
    # Recorded on the bf16 operand: telemetry.comm credits this collective
    # with HALF the fp32 allreduce's payload — the whole point of the wire
    # format, now visible in the comm profile.
    summed = comm.pmean(down, axis, label="grad_allreduce_bf16")
    return jax.tree.map(lambda g, ref: g.astype(ref.dtype), summed, grads)


def make_bf16_grad_step(loss_fn: Callable,
                        optimizer: optax.GradientTransformation,
                        mesh: Mesh) -> Callable:
    """The plain DP gradient-aggregation step with a bf16 collective.

    Drop-in for ``dp.make_grad_aggregation_step`` — same TrainState, same
    loss semantics; only the gradient allreduce's wire format changes."""

    def local_step(state: TrainState, batch) -> Tuple[TrainState, jnp.ndarray]:
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        grads = _pmean_bf16(grads, "data")
        loss = comm.pmean(loss, "data", label="loss_allreduce")
        params, opt_state = apply_optimizer(optimizer, grads,
                                            state.opt_state, state.params)
        return TrainState(params, opt_state, state.step + 1), loss

    sharded = shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P("data")), out_specs=(P(), P()),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0,))


class EFTrainState(NamedTuple):
    """TrainState + the per-shard error-feedback residual tree."""
    params: Any
    opt_state: Any
    step: jnp.ndarray
    residual: Any


def init_ef_state(mesh: Mesh, params,
                  optimizer: optax.GradientTransformation) -> EFTrainState:
    """The residual is PER-SHARD state (each shard compensates its own
    quantization error): materialized as a ``[n_data, ...]``-stacked tree
    sharded over ``data``, so each shard owns one zero-initialized slice."""
    base = replicate(mesh, init_state(params, optimizer))
    n = mesh.shape["data"]
    stacked = jax.tree.map(
        lambda p: jnp.zeros((n,) + p.shape, p.dtype), params)
    stacked = jax.device_put(stacked, NamedSharding(mesh, P("data")))
    return EFTrainState(base.params, base.opt_state, base.step, stacked)


def make_int8_ef_grad_step(loss_fn: Callable,
                           optimizer: optax.GradientTransformation,
                           mesh: Mesh) -> Callable:
    """DP step with int8-quantized gradient allreduce + error feedback.

    Per step, on each shard: ``c = g_local + residual`` per leaf → ONE pmax
    of the stacked per-leaf maxima (shared fixed-point grids, [n_leaves]
    scalars on the wire) → per-leaf ``q = round(c/s)`` (int8 range) → ONE
    **int8 all-gather of the concatenated payload** (the wire leg: 1
    byte/element, and one collective launch regardless of tree size — the
    per-leaf formulation would pay ~2·n_leaves collective latencies, which
    is what per-collective-latency-bound DCN topologies cannot afford) →
    exact local int32 sum → ``g_avg = s·Σq/n`` per leaf → new residual
    ``c − s·q``. The optimizer consumes ``g_avg``; the un-transmitted
    remainder re-enters next step, so the compressor's bias does not
    accumulate.
    """
    n = mesh.shape["data"]

    def local_step(state: EFTrainState, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        loss = comm.pmean(loss, "data", label="loss_allreduce")

        flat_g, treedef = jax.tree.flatten(grads)
        res = jax.tree.leaves(state.residual)
        c_leaves = [g + r[0] for g, r in zip(flat_g, res)]

        # One collective for all scales: pmax of the [n_leaves] maxima.
        local_max = jnp.stack(
            [jnp.max(jnp.abs(c)).astype(jnp.float32) for c in c_leaves])
        scales = jnp.maximum(
            comm.pmax(local_max, "data", label="int8_scale_pmax") / 127.0,
            jnp.finfo(jnp.float32).tiny)

        q_leaves = [
            jnp.clip(jnp.round(c / scales[i].astype(c.dtype)),
                     -127, 127).astype(jnp.int8)
            for i, c in enumerate(c_leaves)]
        # One collective for all payload bytes: gather the concatenated
        # int8 vector (1 byte/element on the wire; a psum of quantized
        # values would up-cast the operand to int32 and save nothing).
        payload = jnp.concatenate([q.reshape(-1) for q in q_leaves])
        gathered = comm.all_gather(payload, "data",
                                   label="int8_grad_gather")  # [n, N] int8
        totals = jnp.sum(gathered.astype(jnp.int32), axis=0)

        g_avg_leaves, res_leaves = [], []
        off = 0
        for i, (g, c, q) in enumerate(zip(flat_g, c_leaves, q_leaves)):
            s = scales[i].astype(c.dtype)
            tot = totals[off:off + g.size].reshape(g.shape)
            off += g.size
            g_avg_leaves.append((s * tot.astype(c.dtype) / n).astype(g.dtype))
            res_leaves.append((c - s * q.astype(c.dtype))[None])
        g_avg = jax.tree.unflatten(treedef, g_avg_leaves)
        residual = jax.tree.unflatten(treedef, res_leaves)
        params, opt_state = apply_optimizer(optimizer, g_avg,
                                            state.opt_state, state.params)
        return EFTrainState(params, opt_state, state.step + 1, residual), loss

    state_specs = EFTrainState(P(), P(), P(), P("data"))
    sharded = shard_map(
        local_step, mesh=mesh,
        in_specs=(state_specs, P("data")),
        out_specs=(state_specs, P()),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0,))


# --------------------------------------------------------------------------
# Overlapped, compressed gradient sync (the ACCO-style microbatch ring).
#
# The factories above compose with neither ``make_multi_step`` nor ZeRO-1 —
# the fastest correctness path and the cheapest wire path were mutually
# exclusive. The machinery below closes that: a ppermute-pipelined ring
# reduce-scatter whose in-flight chunks can ride the wire in fp32, bf16 or
# int8+error-feedback, driven by a microbatch software pipeline in which
# microbatch k+1's gradient compute is dataflow-independent of microbatch
# k's ring hops — the compute/comm overlap is explicit in the HLO, not
# hoped-for from the XLA scheduler. Pattern references (PAPERS.md):
# accumulate-while-you-communicate (ACCO, arxiv 2406.02613) and quantized
# in-flight collectives (EQuARX, arxiv 2506.17615; DynamiQ, 2602.08923).
#
# On a HIERARCHICAL mesh (parallel/distributed.py:hier_data_mesh — fast
# ICI islands bridged by slow DCN) the same drivers take a PER-AXIS wire
# format (wire={"ici": ..., "dcn": ...}) and run the TWO-LEVEL reduction
# (``hier_reduce_scatter``): full-precision ring within each island, the
# compressed ring across the DCN axis only, compressed DCN broadcast +
# intra-island gather on the way back — wire compression spent exactly
# where bandwidth is scarce (the EQuARX/DynamiQ topology-aware shape),
# with every hop's bytes attributed to its mesh axis in the telemetry
# comm profile (CommProfile.by_axis — the CI-gated DCN budget).


def _int8_encode(c, scale_sync_axis=None):
    """Symmetric per-vector int8 quantization around max|c|: returns
    ``(q, s, residual)`` with ``c ≈ s·q`` and ``residual = c − s·q`` (the
    error-feedback remainder, |residual| ≤ s/2 elementwise).

    ``scale_sync_axis``: mesh axis (or tuple of axes) to ``pmax`` the
    scale over before quantizing (must run inside ``shard_map`` over
    those axes). The composed drivers set this to every axis their flat
    vector is PARTIALLY replicated over — ``"model"`` for DP×TP,
    ``("stage"[, "model"])`` for DP×PP[×TP]: each cell's flat vector
    mixes cell-SPECIFIC leaves (col/row shards, the stage's block slice)
    with cell-REPLICATED leaves (norm scales, embed/head), and a per-cell
    scale would decode the replicated entries differently per cell —
    replicas drift apart and ``device_get``-based checkpoints silently
    lose the divergence. A cell-agreed scale keeps every replicated
    entry's quantize/decode (and its EF residual) bitwise identical
    across cells; cell-specific entries just see the more conservative
    max. Scale agreement costs one scalar pmax (raw ``lax.pmax`` — not a
    wire-accounted collective; the scale that rides the wire is unchanged
    in size)."""
    m = jnp.max(jnp.abs(c))
    if scale_sync_axis is not None:
        m = lax.pmax(m, scale_sync_axis)
    s = jnp.maximum(m / 127.0, jnp.finfo(jnp.float32).tiny)
    q = jnp.clip(jnp.round(c / s), -127, 127).astype(jnp.int8)
    return q, s, c - s * q.astype(jnp.float32)


def ring_reduce_scatter(x, axis_name: str, *, wire: str = "fp32",
                        residual=None, label: str = "ring_grad",
                        comm_scale: int = 1, scale_sync_axis=None):
    """Pipelined ring reduce-scatter of a padded flat vector over
    ``lax.ppermute`` hops, with a selectable wire format for the in-flight
    chunk partials. Must run inside ``shard_map``.

    ``x``: ``[n·chunk]`` fp32 local contribution (n = the axis size).
    Returns ``(owned, residual')`` where ``owned`` is this shard's chunk of
    the cross-shard SUM — chunk r lands on shard r, the ``lax.psum_scatter``
    ownership convention — and ``residual'`` threads the int8
    error-feedback state (flat ``[n·chunk]``, slot c = this shard's error
    for chunk c's partial; pass ``None`` for fp32/bf16, where it is
    returned unchanged).

    Summation order (the documented ring spec, pinned bitwise against a
    host-side reference in tests/test_compress.py): the partial for chunk c
    starts at rank (c+1) % n and travels c+1 → c+2 → ... → c, each rank
    adding its own contribution on receipt, so chunk c associates as
    (((g_{c+1} + g_{c+2}) + ...) + g_c) with the OWNER's contribution added
    last — in fp32, never quantized. XLA CPU's ``psum_scatter`` associates
    rank-linearly (((g_0 + g_1) + g_2) + ...) instead, so the two are
    bitwise-equal exactly when the addition is exact (pinned on
    integer-valued gradients) and re-association-close otherwise; a ring
    cannot reproduce the rank-linear order for every chunk without
    serializing all partials through rank 0, which would forfeit the
    balanced (n−1)·chunk_bytes wire profile this exists for.

    Wire formats, applied to each hop's in-flight partial:
    - ``"fp32"``: sent as-is — exact math at allreduce-parity wire.
    - ``"bf16"``: cast to bf16 on the wire (half the bytes), upcast and
      accumulated in fp32 on receipt; stateless — each hop's rounding is
      dropped, like the bf16 pmean path above.
    - ``"int8_ef"``: quantized to int8 around a per-hop scale that rides
      alongside as one fp32 scalar per chunk per hop; the SENDER's
      quantization error is fed back into its next send of the same chunk
      slot (the residual — per (shard, chunk), so the static ring schedule
      makes the feedback loop consistent across calls), restoring
      convergence for the biased compressor exactly as error feedback does
      for the all-gather path above.

    Telemetry: every hop is a ``comm.ppermute`` record — (n−1) trips of
    chunk-payload bytes per call (plus (n−1) 4-byte scale trips for int8),
    so the comm profile's ring accounting reproduces the analytic
    (n−1)·chunk_bytes wire formula exactly (pinned in
    tests/test_telemetry.py).

    ``scale_sync_axis`` threads through to ``_int8_encode`` (see its
    docstring): the composed DP×TP / DP×PP×TP drivers sync each hop's
    int8 scale over the ORTHOGONAL ``model`` axis so model-replicated
    entries of the flat vector decode identically in every model cell.
    No effect on fp32/bf16 wire, and no change to the ring's wire bytes.
    """
    if residual is not None and wire != "int8_ef":
        # Fail loudly: the fp32/bf16 hops never touch the residual, and
        # threading one through them would silently return garbage in
        # place of accumulated EF state (the write-back below only covers
        # the int8 schedule).
        raise ValueError(f"residual is int8_ef-only (got wire={wire!r})")
    n = lax.axis_size(axis_name)
    if n == 1:
        return x, residual
    chunk = x.shape[0] // n
    chunks = x.reshape(n, chunk)
    r = lax.axis_index(axis_name)
    # Rank-relative schedule: rolled[t] = chunks[(r − 1 − t) % n] is the
    # chunk this rank initiates/forwards at hop t, rolled[n−1] its own
    # (received-last) chunk. The index map is an involution, so the same
    # gather restores the residual's chunk-indexed layout on write-back.
    idx = (r - 1 - jnp.arange(n)) % n
    rolled = chunks[idx]
    res_rolled = (residual.reshape(n, chunk)[idx]
                  if residual is not None else None)
    perm = [(i, (i + 1) % n) for i in range(n)]
    new_res = []
    partial = rolled[0]
    for t in range(n - 1):
        if wire == "int8_ef":
            c = partial + res_rolled[t]
            q, s, err = _int8_encode(c, scale_sync_axis=scale_sync_axis)
            new_res.append(err)
            q = comm.ppermute(q, axis_name, perm, label=f"{label}_int8",
                              scale=comm_scale)
            s = comm.ppermute(s, axis_name, perm, label=f"{label}_scale",
                              scale=comm_scale)
            got = s * q.astype(jnp.float32)
        elif wire == "bf16":
            got = comm.ppermute(partial.astype(jnp.bfloat16), axis_name,
                                perm, label=f"{label}_bf16",
                                scale=comm_scale).astype(jnp.float32)
        elif wire == "fp32":
            got = comm.ppermute(partial, axis_name, perm,
                                label=f"{label}_f32", scale=comm_scale)
        else:
            raise ValueError(f"unknown ring wire format {wire!r}")
        partial = got + rolled[t + 1]
    if residual is not None:
        # Own-chunk slot (never quantized by this rank) passes through.
        new_res.append(res_rolled[n - 1])
        # Involution: the same gather restores chunk-indexed flat layout.
        residual = jnp.stack(new_res)[idx].reshape(-1)
    return partial, residual


def hier_reduce_scatter(x, *, wire_ici: str = "fp32",
                        wire_dcn: str = "int8_ef", residual=None,
                        ici_axis: str = "data", dcn_axis: str = "dcn",
                        label: str = "ring_grad", comm_scale: int = 1):
    """Two-level reduce-scatter on the hierarchical (dcn × data) mesh
    (parallel/distributed.py:hier_data_mesh): a full-precision ring
    reduce-scatter WITHIN each ICI island (the fast tier — ``wire_ici`` ∈
    {fp32, bf16}), then a second ring across the ``dcn`` axis only (the
    scarce tier — ``wire_dcn`` ∈ {fp32, bf16, int8_ef}), so compressed
    wire formats are spent exactly on the hops where bandwidth is scarce
    (EQuARX / DynamiQ, PAPERS.md). Must run inside ``shard_map`` over both
    axes.

    ``x``: ``[n·chunk]`` fp32 local contribution with n = D·S (D =
    islands, S = island size). Phase 1 scatters S superchunks of D·chunk
    over the island (each a contiguous ``(S−1)``-hop ICI ring of
    ``ring_reduce_scatter``'s documented order); phase 2 scatters each
    superchunk's D chunks across islands ((D−1) DCN hops of chunk bytes —
    1/S of the vector ever crosses DCN, and S parallel DCN rings carry
    it). Shard (d, s) ends up owning chunk ``s·D + d`` of the cross-shard
    SUM — the ``dp.slice_index`` ownership map, shared with the ZeRO-1
    update so the reduced chunk lands on the shard that owns its slice.

    ``residual`` threads the DCN ring's int8 error-feedback state (flat
    ``[D·chunk]``, per (shard, dcn-chunk) — the ICI tier is full
    precision and carries none); pass None for fp32/bf16 DCN wire.

    Summation-order spec (pinned in tests/test_hier_collectives.py):
    chunk ``s·D + d`` associates as the DCN-ring-order chain over island
    partials, each island partial itself the ICI-ring-order chain of its
    members — a chain of chains. At D = 1 or S = 1 this IS the flat
    ring's single chain (bitwise — one of the two rings degenerates to
    the identity); at other factorizations it re-associates the same sum,
    so flat-vs-two-level equality is bitwise exactly where the addition
    is exact (integer-valued gradients — the ``ring_reduce_scatter`` vs
    ``psum_scatter`` contract) and re-association-close on general
    floats.

    Telemetry: every hop records through ``comm.ppermute`` with its OWN
    axis name, so the comm profile attributes ICI and DCN bytes
    separately (``CommProfile.by_axis``) — per device: (S−1)·(D·chunk)
    bytes on the ICI axis, (D−1)·chunk bytes (in the DCN wire format) on
    the DCN axis, per call.
    """
    if wire_ici not in ("fp32", "bf16"):
        raise ValueError(
            "the ICI tier is the full-precision tier: wire_ici must be "
            f"'fp32' or 'bf16' (got {wire_ici!r}) — int8+EF belongs on "
            "the scarce DCN axis")
    superchunk, _ = ring_reduce_scatter(
        x, ici_axis, wire=wire_ici, residual=None,
        label=f"{label}_ici", comm_scale=comm_scale)
    return ring_reduce_scatter(
        superchunk, dcn_axis, wire=wire_dcn, residual=residual,
        label=f"{label}_dcn", comm_scale=comm_scale)


# ------------------------------------------------- bucketed backward sync
#
# Everything below `_make_overlap_local_step` historically flattened the
# WHOLE microbatch gradient (pt.flatten, tree order) before the first ring
# hop — the overlap was across microbatches only, and the first hop always
# waited on the last layer's VJP. The bucket map splits the flat geometry
# into an ORDERED list of buckets matching reverse-mode emission order
# (lm_head first, final_norm, the stacked `blocks` layer groups top-down,
# the embedding last), so bucket b's ring vector is built from ONLY the
# leaf slices it covers: its quantize/EF/ring is dataflow-independent of
# every later bucket's grad compute, and the overlap is visible in the
# jaxpr (``ring_overlap_evidence`` — the PR 10 evidence standard, asserted
# in experiments/comm_wire_smoke.py). The ACCO shape ROADMAP 7b names,
# composed with DynamiQ-style chunking (PAPERS.md).


class BucketMap(NamedTuple):
    """Ordered bucket decomposition of the padded flat gradient space —
    ``dp._flat_geometry`` split into ``comm_buckets`` contiguous ranges of
    a VJP-emission-ordered coordinate space (NOT tree order: top-of-network
    leaves first, embedding last, see ``_ordered_pieces``).

    Geometry: ``local`` (one shard's slice of the padded flat vector)
    splits into per-bucket chunk ``sizes`` (``local//B`` each, the
    remainder spread over the leading buckets, so ``sum(sizes) == local``
    EXACTLY — no per-bucket padding, which is what keeps total ring wire
    bytes invariant in the bucket count). Bucket b covers the ordered
    coordinates ``[n·offsets[b], n·offsets[b] + n·sizes[b])``; the global
    ``pad`` rides the tail of the LAST bucket (``pad < n ≤ n·sizes[-1]``
    always fits). ``pieces[b]`` lists the ``(leaf_idx, start, size)``
    slices of the tree-order leaf ravels that bucket b concatenates —
    the static map both ``_bucket_vectors`` (grads → ring vectors) and
    ``_scatter_buckets`` (gathered vectors → param tree) drive.

    Ring ownership at B > 1 is bucket-major: shard r owns chunk r of
    EVERY bucket, and its ZeRO-1 slice is the concat of those per-bucket
    chunks — which is why the per-bucket EF residuals, gather residuals
    and ZeRO-1 moments are all stored per bucket (each bucket's stack is
    a contiguous ordered-coordinate range, the property ``reshard_state``
    needs to pad-swap them across elastic world changes)."""
    n: int
    pad: int
    local: int
    total: int
    sizes: Tuple[int, ...]
    offsets: Tuple[int, ...]
    pieces: Tuple[Tuple[Tuple[int, int, int], ...], ...]

    @property
    def nbuckets(self) -> int:
        return len(self.sizes)


def _ordered_pieces(params, leaf_local=None):
    """VJP-emission-ordered coverage of the local flat param space: a list
    of ``(leaf_idx, start, size)`` pieces over the tree-order leaf ravels,
    ordered ``lm_head`` → ``final_norm`` → the stacked ``blocks`` layer
    groups from the TOP layer down (each layer group = that layer's slice
    of every stacked block leaf, contiguous in the leaf's own ravel) → any
    remaining leaves (tree order) → ``embed`` last. That is the order
    reverse-mode autodiff produces gradients in, so cutting buckets along
    it puts the gradients that materialize FIRST into the buckets that
    ring FIRST. Trees without the llama top-level keys (the quadratic test
    trees, generic models) degrade to plain tree order — the bucketing
    still reshapes the ring, it just stops tracking emission order.

    ``leaf_local``: optional ``(path, leaf) -> (local_size, local_layers)``
    override for the composed drivers whose per-cell leaf sizes differ
    from the global shapes (DP×PP stage slices, DP×TP col/row shards);
    ``local_layers`` is the stacked leading dim of a per-cell blocks leaf
    (None for unstacked leaves). Defaults to the DP identity."""
    entries = jax.tree_util.tree_flatten_with_path(params)[0]
    head, norm, embed, other = [], [], [], []
    blocks = []                          # (leaf_idx, per_layer_size, layers)
    for li, (path, leaf) in enumerate(entries):
        key = getattr(path[0], "key", None) if path else None
        if leaf_local is not None:
            size, layers = leaf_local(path, leaf)
        else:
            size = int(leaf.size)
            layers = (int(leaf.shape[0])
                      if key == "blocks" and getattr(leaf, "ndim", 0) >= 1
                      else None)
        if size == 0:
            continue
        whole = (li, 0, size)
        if key == "lm_head":
            head.append(whole)
        elif key == "final_norm":
            norm.append(whole)
        elif key == "embed":
            embed.append(whole)
        elif key == "blocks" and layers and size % layers == 0:
            blocks.append((li, size // layers, layers))
        else:
            other.append(whole)
    pieces = head + norm
    if blocks:
        n_layers = max(layers for _, _, layers in blocks)
        for layer in range(n_layers - 1, -1, -1):
            for li, per_layer, layers in blocks:
                if layer < layers:
                    pieces.append((li, layer * per_layer, per_layer))
    return pieces + other + embed


def make_bucket_map(params, n: int, comm_buckets: int,
                    *, leaf_local=None) -> BucketMap:
    """Build the ``BucketMap`` for ``params`` over an ``n``-shard data
    world: ``_ordered_pieces``'s emission-ordered coverage, cut at the
    ``n·sizes[b]`` bucket boundaries (a piece straddling a boundary splits
    — buckets are exact coordinate ranges, never rounded to leaf edges).
    Raises for non-positive or oversubscribed bucket counts (every bucket
    needs ≥ 1 coordinate per shard)."""
    B = int(comm_buckets)
    if B < 1:
        raise ValueError(f"comm_buckets must be >= 1 (got {comm_buckets})")
    pieces = _ordered_pieces(params, leaf_local)
    total = sum(sz for _, _, sz in pieces)
    pad = (-total) % n
    local = (total + pad) // n
    if B > local:
        raise ValueError(
            f"comm_buckets={B} exceeds the per-shard slice ({local} "
            f"coordinates at data world {n}) — every bucket needs at "
            "least one coordinate per shard")
    base, rem = divmod(local, B)
    sizes = tuple(base + (1 if b < rem else 0) for b in range(B))
    offsets = tuple(sum(sizes[:b]) for b in range(B))
    buckets, cur = [], []
    need = n * sizes[0]
    for li, st, sz in pieces:
        while sz:
            if need == 0:
                buckets.append(tuple(cur))
                cur = []
                need = n * sizes[len(buckets)]
            take = min(sz, need)
            cur.append((li, st, take))
            st += take
            sz -= take
            need -= take
    buckets.append(tuple(cur))           # last bucket; the pad fills `need`
    return BucketMap(n, pad, local, total, sizes, offsets, tuple(buckets))


def _bucket_vectors(bm: BucketMap, tree):
    """Per-bucket fp32 ring vectors ``[n·sizes[b]]`` from a tree's leaves.
    Each bucket's vector concatenates ONLY the leaf slices its pieces
    cover, so bucket b's vector — and everything downstream of it
    (quantize, EF, ring hops) — carries no data dependence on any leaf
    outside bucket b: the jaxpr-visible overlap. The global pad is
    appended to the last bucket's tail (its coordinates are the tail of
    the ordered space)."""
    leaves = jax.tree.leaves(tree)
    vecs = []
    for b, pieces in enumerate(bm.pieces):
        parts = [leaves[li].reshape(-1)[st:st + sz].astype(jnp.float32)
                 for li, st, sz in pieces]
        if b == bm.nbuckets - 1 and bm.pad:
            parts.append(jnp.zeros((bm.pad,), jnp.float32))
        vecs.append(parts[0] if len(parts) == 1
                    else jnp.concatenate(parts))
    return vecs


def _scatter_buckets(bm: BucketMap, vecs, ref_tree):
    """Inverse of ``_bucket_vectors``: reassemble a tree from per-bucket
    FULL vectors ``[n·sizes[b]]`` (every shard's chunk present — the
    post-all-gather layout), casting each leaf back to its reference
    dtype. The last bucket's pad tail is simply never referenced."""
    ref_leaves, treedef = jax.tree.flatten(ref_tree)
    per_leaf = {}
    for b, pieces in enumerate(bm.pieces):
        pos = 0
        for li, st, sz in pieces:
            per_leaf.setdefault(li, []).append((st, b, pos, sz))
            pos += sz
    out = []
    for li, ref in enumerate(ref_leaves):
        segs = sorted(per_leaf[li])
        parts = [vecs[b][pos:pos + sz] for _, b, pos, sz in segs]
        flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        out.append(flat.reshape(ref.shape).astype(ref.dtype))
    return jax.tree.unflatten(treedef, out)


def _bucket_slices(bm: BucketMap, gathered, lead: int = 1):
    """Split a rank-major gathered stack back into per-bucket full
    vectors: ``gathered`` is ``[ranks·local]`` (each rank's slot its
    owned concat-of-bucket-chunks slice — the flat all-gather, the DCN
    q_all, or the two-leg hierarchical gather, whose S·D rows compose in
    exactly the s·D + d ownership order), so bucket b's full vector is
    the ``[:, offsets[b]:offsets[b]+sizes[b]]`` stripe re-flattened.
    ``lead = D`` handles the one layout where each rank's slot is itself
    a concat of ``[D·sizes[b]]`` superchunk blocks (the ICI gather of
    per-bucket DCN-decoded superchunks in the hierarchical int8 path)."""
    g = gathered.reshape(-1, lead * bm.local)
    return [g[:, lead * bm.offsets[b]:
              lead * (bm.offsets[b] + bm.sizes[b])].reshape(-1)
            for b in range(bm.nbuckets)]


def _find_ppermute_jaxpr(jaxpr):
    """Depth-first search for the (sub)jaxpr whose equation list directly
    contains ``ppermute`` equations — the shard_map body the ring hops
    live in. Returns None when the program has no ring."""
    if any(e.primitive.name == "ppermute" for e in jaxpr.eqns):
        return jaxpr
    for eqn in jaxpr.eqns:
        subs = []
        for v in eqn.params.values():
            cand = v if isinstance(v, (tuple, list)) else (v,)
            for c in cand:
                inner = getattr(c, "jaxpr", c)
                if hasattr(inner, "eqns"):
                    subs.append(inner)
        for sub in subs:
            found = _find_ppermute_jaxpr(sub)
            if found is not None:
                return found
    return None


def ring_overlap_evidence(fn, *args):
    """Structural (jaxpr-level) proof of the bucketed-backward overlap —
    the PR 10 evidence standard applied to ISSUE 19's sub-gradient
    chunking. Traces ``fn(*args)`` (no execution), finds the shard_map
    body carrying the ring's ``ppermute`` hops, and classifies each hop
    against the TEXTUALLY LAST ``scan`` equation — the final microbatch's
    backward scan, i.e. the point where the full gradient has
    materialized. Returns::

        {"n_ring_hops":        total ppermute equations,
         "waited_hops":        hops data-dependent on the last scan,
         "independent_hops":   hops with NO such dependence,
         "overlap_fraction":   independent / total,
         "first_hop_independent": bucket 0's first hop carries no data
                                  dependence on the last backward scan}

    Unbucketed (B = 1, M = 1) the single ring vector includes the
    embedding gradient, every hop descends from the backward scan, and
    ``overlap_fraction`` is 0.0 — the sanity negative. Bucketed, the
    top-of-network buckets' hops (and at M > 1 every non-final
    microbatch's hops) are independent: ``first_hop_independent`` is the
    acceptance predicate comm_wire_smoke asserts, and
    ``overlap_fraction`` is the higher-is-better row it emits for
    bench_compare."""
    closed = jax.make_jaxpr(fn)(*args)
    inner = _find_ppermute_jaxpr(closed.jaxpr)
    if inner is None:
        return {"n_ring_hops": 0, "waited_hops": 0, "independent_hops": 0,
                "overlap_fraction": 0.0, "first_hop_independent": False}
    eqns = list(inner.eqns)
    hops = [e for e in eqns if e.primitive.name == "ppermute"]
    scans = [e for e in eqns if e.primitive.name == "scan"]
    if not scans:
        # No scanned layer stack in the loss — every hop trivially
        # "independent"; report zero evidence rather than free credit.
        return {"n_ring_hops": len(hops), "waited_hops": 0,
                "independent_hops": 0, "overlap_fraction": 0.0,
                "first_hop_independent": False}
    anchor = scans[-1]
    consumers = {}
    for e in eqns:
        for v in e.invars:
            if v.__class__.__name__ == "Literal":
                continue
            consumers.setdefault(v, []).append(e)
    # Transitive descendants of the anchor scan, equations treated
    # atomically (any invar produced downstream taints the whole eqn).
    desc, stack = set(), [anchor]
    while stack:
        e = stack.pop()
        if id(e) in desc:
            continue
        desc.add(id(e))
        for v in e.outvars:
            stack.extend(consumers.get(v, ()))
    waited = sum(1 for h in hops if id(h) in desc)
    independent = len(hops) - waited
    return {"n_ring_hops": len(hops), "waited_hops": waited,
            "independent_hops": independent,
            "overlap_fraction": (independent / len(hops)) if hops else 0.0,
            "first_hop_independent": bool(hops)
            and id(hops[0]) not in desc}


class OverlapEFState(NamedTuple):
    """TrainState + the two error-feedback residual trees of the int8 ring
    driver, both sharded over the data-parallel world and zero at init:

    - ``ring_residual`` [n, ring_len] (per-shard slice [1, ring_len]):
      chunk-indexed per-hop quantization error of the int8 gradient ring —
      shard r's slot c is the error of the partial r last sent for chunk c
      (r's own chunk slot stays 0: the owner's contribution is added in
      fp32). Flat driver: ring_len = Ppad (the n-chunk data ring).
      Hierarchical driver: ring_len = D·local (only the DCN ring carries
      EF state — the ICI tier is full precision).
    - ``gather_residual`` [Ppad] (per-shard slice [local]): error of the
      second-leg quantization — the param-delta broadcast (zero1) or the
      reduced-grad-slice broadcast (gradient aggregation); hierarchically,
      the broadcast's DCN leg.

    Both ride the scan carry of the K-step driver and the checkpointed
    state tree, so the accumulated quantization error survives
    ``make_overlap_multi_step`` composition, chunk-edge checkpoints and a
    preempt/resume cycle exactly (pinned in tests/test_compress.py and
    tests/test_hier_collectives.py).

    The DP×PP drivers (parallel/pp.py ``_pp_overlap_setup``) reuse this
    tuple with a ``stage`` axis spliced in — ring ``[n, S, n·local]``,
    gather ``[n, S, local]``, sharded ``P("data", "stage")`` — because
    each (data, stage) shard compensates its OWN stage slice's
    quantization error (same bars, pinned in tests/test_pp.py).

    At ``comm_buckets > 1`` both fields are TUPLES of per-bucket arrays
    (ring ``[n, ring_n·sizes[b]]``, gather ``[n·sizes[b]]``) — same
    semantics per bucket, stored per bucket so each stack is a contiguous
    ordered-coordinate range ``dp.reshard_state`` can pad-swap across
    elastic world changes (see ``BucketMap``)."""
    params: Any
    opt_state: Any
    step: jnp.ndarray
    ring_residual: Any
    gather_residual: Any


def _zero1_bucket_setup(optimizer, mesh: Mesh, params, bm: BucketMap,
                        dpart):
    """ZeRO-1 initialization at ``comm_buckets > 1``: one optimizer state
    PER BUCKET, each over that bucket's per-shard chunk (``[sizes[b]]``
    locally, ``[n·sizes[b]]`` globally). Elementwise optimizers make the
    split value-identical to ``dp._zero1_setup``'s single ``[local]``
    slice — the tuple exists for the STORAGE layout: each bucket's moment
    stack is a contiguous ordered-coordinate range, which is what lets
    ``reshard_state`` pad-swap it across elastic world changes (a single
    ``[n·local]`` stack at B > 1 would interleave buckets rank-major and
    scramble under a world resize)."""
    from .dp import slice_index

    specs = []
    for sz in bm.sizes:
        abstract = jax.eval_shape(
            optimizer.init, jax.ShapeDtypeStruct((sz,), jnp.float32))
        specs.append(jax.tree.map(
            lambda x: P(dpart) if getattr(x, "ndim", 0) >= 1 else P(),
            abstract))
    opt_specs = tuple(specs)

    def local_init(params):
        shard = slice_index(mesh)
        vecs = _bucket_vectors(bm, params)
        return tuple(
            optimizer.init(lax.dynamic_slice_in_dim(
                vecs[b], shard * bm.sizes[b], bm.sizes[b]))
            for b in range(bm.nbuckets))

    opt_state = jax.jit(shard_map(
        local_init, mesh=mesh, in_specs=P(),
        out_specs=opt_specs, check_vma=False))(params)
    state = TrainState(replicate(mesh, params), opt_state,
                       jax.device_put(jnp.zeros((), jnp.int32),
                                      NamedSharding(mesh, P())))
    return state, opt_specs


def _overlap_setup(mesh: Mesh, params, optimizer, wire, aggregation: str,
                   comm_buckets: int = 1):
    """State + shard specs + flat geometry for the overlap driver. The
    zero1 variant reuses ``dp._zero1_setup`` wholesale, so the slice the
    ring chunk lands on IS the slice the sharded update owns (including
    the hierarchical ``dp.slice_index`` map).

    ``wire``: a format string for the flat data ring, or the per-axis dict
    ``{"ici": ..., "dcn": ...}`` selecting the two-level path on a
    hierarchical mesh. ``comm_buckets > 1`` selects the bucketed backward
    (``BucketMap``): the EF residuals, gather residuals and ZeRO-1 moments
    all become per-bucket tuples, and the returned ``bm`` drives the
    bucketed local step. Returns ``(state, specs, dpart, n, pad, local,
    total, hier_shape, bm)`` — ``dpart`` the normalized data PartitionSpec
    entry (dp.data_partition), ``hier_shape`` = ``(D, S)`` for the
    two-level path, None for the flat ring, ``bm`` None at
    ``comm_buckets == 1`` (the exact legacy path)."""
    from .dp import _flat_geometry, _zero1_setup, data_partition

    if aggregation not in ("gradient", "zero1"):
        raise ValueError("overlap driver supports gradient/zero1 "
                         f"aggregation only (got {aggregation!r})")
    if isinstance(wire, dict):
        if set(wire) != {"ici", "dcn"}:
            raise ValueError("per-axis wire must be "
                             '{"ici": fmt, "dcn": fmt} '
                             f"(got keys {sorted(wire)})")
        if "dcn" not in mesh.shape:
            raise ValueError(
                "per-axis wire formats need a hierarchical mesh with a "
                "'dcn' axis (parallel/distributed.py:hier_data_mesh)")
        if wire["ici"] not in ("fp32", "bf16"):
            raise ValueError(
                "the ICI tier is the full-precision tier: wire['ici'] "
                f"must be 'fp32' or 'bf16' (got {wire['ici']!r}) — "
                "int8+EF belongs on the scarce DCN axis")
        if wire["dcn"] not in ("fp32", "bf16", "int8_ef"):
            raise ValueError(f"unknown DCN wire format {wire['dcn']!r}")
        hier_shape = (mesh.shape["dcn"], mesh.shape["data"])
        ef = wire["dcn"] == "int8_ef"
    else:
        if wire not in ("fp32", "bf16", "int8_ef"):
            raise ValueError(f"unknown wire format {wire!r}")
        if mesh.shape.get("dcn", 1) > 1:
            raise ValueError(
                "a hierarchical (dcn x data) mesh needs the per-axis wire "
                'dict ({"ici": ..., "dcn": ...}) — a flat wire string '
                "would run the ring over the 'data' axis only and never "
                "cross DCN")
        hier_shape = None
        ef = wire == "int8_ef"
    dpart = data_partition(mesh)
    n, pad, local, total = _flat_geometry(mesh, params)
    if int(comm_buckets) < 1:
        raise ValueError(
            f"comm_buckets must be >= 1 (got {comm_buckets})")
    bm = (make_bucket_map(params, n, comm_buckets)
          if int(comm_buckets) > 1 else None)
    if aggregation == "zero1":
        if bm is not None:
            base, opt_specs = _zero1_bucket_setup(
                optimizer, mesh, params, bm, dpart)
        else:
            base, opt_specs, *_ = _zero1_setup(optimizer, mesh, params)
    else:
        base = replicate(mesh, init_state(params, optimizer))
        opt_specs = P()
    if ef:
        ring_n = hier_shape[0] if hier_shape is not None else n
        dshard = P(dpart)
        if bm is not None:
            ring_res = tuple(
                jax.device_put(jnp.zeros((n, ring_n * sz), jnp.float32),
                               NamedSharding(mesh, dshard))
                for sz in bm.sizes)
            gather_res = tuple(
                jax.device_put(jnp.zeros((n * sz,), jnp.float32),
                               NamedSharding(mesh, dshard))
                for sz in bm.sizes)
            specs = OverlapEFState(P(), opt_specs, P(),
                                   (dshard,) * bm.nbuckets,
                                   (dshard,) * bm.nbuckets)
        else:
            ring_res = jax.device_put(
                jnp.zeros((n, ring_n * local), jnp.float32),
                NamedSharding(mesh, dshard))
            gather_res = jax.device_put(
                jnp.zeros((n * local,), jnp.float32),
                NamedSharding(mesh, dshard))
            specs = OverlapEFState(P(), opt_specs, P(), dshard, dshard)
        state = OverlapEFState(base.params, base.opt_state, base.step,
                               ring_res, gather_res)
    else:
        state = base
        specs = TrainState(P(), opt_specs, P())
    return state, specs, dpart, n, pad, local, total, hier_shape, bm


def _make_overlap_local_step(loss_fn: Callable, optimizer, n: int, pad: int,
                             local: int, total: int, *, microbatches: int,
                             wire, aggregation: str,
                             comm_scale: int = 1, hier_shape=None,
                             bucket_map=None,
                             guard_nonfinite: bool = False,
                             numerics=None) -> Callable:
    """The per-shard overlapped step body shared by ``make_overlap_step``
    and ``make_overlap_multi_step`` — one implementation, so per-step and
    K-scanned dispatch cannot drift (their bitwise equality at any K is the
    same contract ``make_multi_step`` pins).

    Structure per step: the local batch splits into M microbatches; the
    ring reduce-scatter of microbatch m−1's flat gradient is issued in the
    same trace position as microbatch m's forward+backward, with no data
    dependence between them — the explicit overlap. Reduced chunks
    accumulate in fp32 on the owner; the result is averaged over n·M and
    fed to the ZeRO-1 sliced update + (compressed) param gather, or
    all-gathered (in the wire format) for the replicated update.

    ``hier_shape`` = (D, S) selects the two-level topology: the reduce is
    ``hier_reduce_scatter`` (full-precision ICI ring within each island,
    ``wire["dcn"]`` ring across islands), slice ownership is
    ``dp.slice_index``'s s·D + d map, and the broadcast leg runs its DCN
    hop first (compressed when ``wire["dcn"] = "int8_ef"``: the quantized
    delta/grad payload crosses DCN once at one byte/element) and the
    intra-island gather second — only 1/S of the vector ever crosses the
    DCN axis, the telemetry-visible budget the smoke gates. bf16 on the
    ICI tier compresses the ring's in-flight partials (and the replicated
    path's grad gather); the zero1 param gather stays fp32 on both legs
    except the int8 DCN delta, mirroring the flat driver's
    params-stay-exact rule.

    ``guard_nonfinite`` fuses the in-jit skip: the finiteness verdict on
    (loss, owned gradient slice) is psum-agreed across every data axis —
    per-shard slices can disagree, and replicas applying different
    verdicts would silently diverge — and a bad step select-backs the
    WHOLE incoming state (params, moments, both EF residual trees) without
    leaving jit; ``step`` does not advance, which is how the host counts
    skips into ResilienceStats (train/llm.py). The returned loss stays the
    non-finite one, so host-side guards/telemetry still see the fault.

    ``numerics`` (telemetry.introspect.NumericsHandle, built with
    ``psum_axis`` = the data axes): the step's second output becomes
    ``(loss, NumericsSummary)`` — grad stats over the local microbatch-mean
    gradient (psum-agreed by the summarizer), update stats over the
    ATTEMPTED update — computed from values the step already holds, so
    losses/params are bitwise identical on vs off (pinned).

    Numerics contract: microbatch gradients are REDUCED per microbatch and
    summed on the owner (reduce-then-accumulate), whereas ``accum_steps``
    accumulates locally then reduces once — same math, different float
    association, so M>1 matches the monolithic paths to fp32 tolerance,
    not bitwise (M=1 differs from them only by the ring-vs-linear
    reduction order; see ``ring_reduce_scatter``). The compressed gather
    legs broadcast one payload that every shard applies identically, so
    replicas stay bitwise in sync in every mode and topology.

    ``bucket_map`` (a ``BucketMap``, None for the legacy single-vector
    path) selects the bucketed backward: each microbatch gradient is
    produced as per-bucket ring vectors (``_bucket_vectors`` — bucket b
    built from ONLY the leaf slices it covers, in VJP emission order), and
    each bucket rings independently under its own label
    (``ring_grad_b{b}``), so bucket b's quantize/EF/ring carries no data
    dependence on bucket b+1..'s grad compute — the within-backward
    overlap (``ring_overlap_evidence``), on top of the across-microbatch
    overlap above. A shard's owned slice becomes the concat of its
    per-bucket chunks; the gather legs stay ONE collective of ``local``
    elements in every mode (buckets are extracted from the gathered stack
    with static slices), so gather-leg bytes and collective counts — and,
    in fp32/bf16, total wire bytes — are exactly invariant in the bucket
    count (the int8 ring adds one 4-byte scale sideband per extra bucket
    per hop, pinned analytically in the smoke). EF residuals, gather
    residuals and ZeRO-1 moments are per-bucket tuples (see
    ``_zero1_bucket_setup`` for why)."""
    M = microbatches
    bm = bucket_map
    B = bm.nbuckets if bm is not None else 1
    hier = hier_shape is not None
    if hier:
        D, S = hier_shape
        wire_ici, wire_dcn = wire["ici"], wire["dcn"]
        ef = wire_dcn == "int8_ef"
    else:
        ef = wire == "int8_ef"

    def _reduce(pending, ring_res, bucket=None):
        label = "ring_grad" if bucket is None else f"ring_grad_b{bucket}"
        if hier:
            return hier_reduce_scatter(
                pending, wire_ici=wire_ici, wire_dcn=wire_dcn,
                residual=ring_res, comm_scale=comm_scale, label=label)
        return ring_reduce_scatter(pending, "data", wire=wire,
                                   residual=ring_res,
                                   comm_scale=comm_scale, label=label)

    def _reduce_all(pending, ring_res):
        # pending: the flat vector (bm None) or the per-bucket vector
        # list; ring_res mirrors it. Returns this shard's owned [local]
        # slice (concat of per-bucket chunks when bucketed).
        if bm is None:
            return _reduce(pending, ring_res)
        reds, new_res = [], []
        for b in range(B):
            red_b, r_b = _reduce(pending[b],
                                 ring_res[b] if ef else None, b)
            reds.append(red_b)
            new_res.append(r_b)
        return jnp.concatenate(reds), new_res

    def local_step(state, batch):
        from ..utils import pytree as pt

        if batch.shape[0] % M:
            raise ValueError(f"local batch {batch.shape[0]} not divisible "
                             f"by overlap_microbatches={M}")
        params = state.params
        if not ef:
            ring_res = None
        elif bm is None:
            ring_res = state.ring_residual[0]
        else:
            ring_res = [r[0] for r in state.ring_residual]
        micro = batch.reshape((M, -1) + batch.shape[1:])
        acc = jnp.zeros((local,), jnp.float32)
        loss_sum = jnp.zeros((), jnp.float32)
        gacc = None
        pending = None
        for m in range(M):
            l, g = jax.value_and_grad(loss_fn)(params, micro[m])
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
                # grad compute (the lines above): independent dataflow.
                red, ring_res = _reduce_all(pending, ring_res)
                acc = acc + red
            pending = (_bucket_vectors(bm, g) if bm is not None else
                       jnp.pad(pt.flatten(g)[0].astype(jnp.float32),
                               (0, pad)))
        red, ring_res = _reduce_all(pending, ring_res)
        acc = acc + red
        g_mine = acc / (n * M)      # mean over shards and microbatches
        loss = comm.pmean(loss_sum / M, "data", label="loss_allreduce",
                          scale=comm_scale)
        if hier:
            # Mean of equal-size island means == the global mean; the DCN
            # leg of the loss reduction is 4 bytes, attributed to its axis.
            loss = comm.pmean(loss, "dcn", label="loss_allreduce_dcn",
                              scale=comm_scale)

        raw_flat, unravel = pt.flatten(params)
        if bm is None:
            flat_p = jnp.pad(raw_flat.astype(jnp.float32), (0, pad))
            pvecs = None
        else:
            # Bucketed: the param-side flat views are per-bucket too, so
            # the owned slice is the concat of per-bucket chunks — the
            # same coordinate order the per-bucket rings reduce into.
            flat_p = None
            pvecs = _bucket_vectors(bm, params)
        gather_res = None
        if aggregation == "zero1":
            if hier:
                from .dp import hier_slice_index
                shard = hier_slice_index(D)
            else:
                shard = lax.axis_index("data")
            if bm is None:
                p_mine = lax.dynamic_slice_in_dim(flat_p, shard * local,
                                                  local)
                new_p_mine, opt_state = apply_optimizer(
                    optimizer, g_mine, state.opt_state, p_mine)
            else:
                # One optimizer apply per bucket against the per-bucket
                # moment state; elementwise updates make the concat
                # value-identical to the single-slice apply.
                p_chunks = [lax.dynamic_slice_in_dim(
                    pvecs[b], shard * bm.sizes[b], bm.sizes[b])
                    for b in range(B)]
                new_chunks, opts = [], []
                for b in range(B):
                    np_b, opt_b = apply_optimizer(
                        optimizer,
                        g_mine[bm.offsets[b]:bm.offsets[b] + bm.sizes[b]],
                        state.opt_state[b], p_chunks[b])
                    new_chunks.append(np_b)
                    opts.append(opt_b)
                p_mine = jnp.concatenate(p_chunks)
                new_p_mine = jnp.concatenate(new_chunks)
                opt_state = tuple(opts)
            vec_new = None
            if hier:
                # Two-level broadcast, DCN leg first: islands exchange
                # their superchunk's D slices (compressed when the DCN
                # wire says so), then the island gathers S superchunks
                # over ICI in fp32 — params stay exact on the fast tier.
                if wire_dcn == "int8_ef":
                    gres = (jnp.concatenate(state.gather_residual)
                            if bm is not None else state.gather_residual)
                    q, s, gather_res = _int8_encode(
                        (new_p_mine - p_mine) + gres)
                    q_all = comm.all_gather(
                        q, "dcn", tiled=True,
                        label="overlap_delta_gather_int8",
                        scale=comm_scale)
                    s_all = comm.all_gather(
                        s[None], "dcn", tiled=True,
                        label="overlap_delta_scale_gather",
                        scale=comm_scale)
                    if bm is None:
                        p_super = lax.dynamic_slice_in_dim(
                            flat_p, lax.axis_index("data") * (D * local),
                            D * local)
                        super_new = p_super + (jnp.repeat(s_all, local)
                                               * q_all.astype(jnp.float32))
                    else:
                        q_slc = _bucket_slices(bm,
                                               q_all.astype(jnp.float32))
                        super_new = jnp.concatenate([
                            lax.dynamic_slice_in_dim(
                                pvecs[b],
                                lax.axis_index("data") * (D * bm.sizes[b]),
                                D * bm.sizes[b])
                            + jnp.repeat(s_all, bm.sizes[b]) * q_slc[b]
                            for b in range(B)])
                else:
                    super_new = comm.all_gather(
                        new_p_mine, "dcn", tiled=True,
                        label="overlap_param_gather_dcn",
                        scale=comm_scale)
                flat_new = comm.all_gather(
                    super_new, "data", tiled=True,
                    label="overlap_param_gather_ici", scale=comm_scale)
                if bm is not None:
                    # int8 DCN builds per-rank superchunk CONCATS (lead=D
                    # blocks); the fp32/bf16 two-leg gather stacks plain
                    # [local] slots in s·D + d order (lead=1).
                    vec_new = _bucket_slices(
                        bm, flat_new,
                        lead=(D if wire_dcn == "int8_ef" else 1))
            elif wire == "int8_ef":
                # Compressed second leg: broadcast the param DELTA int8
                # (one byte/element + one scale/shard) with its own EF
                # residual at the owner. Every shard — the owner included —
                # applies the same dequantized deltas, so replicas stay
                # bitwise identical; the fp32 moments stay exact; the
                # quantization drift is compensated next step.
                gres = (jnp.concatenate(state.gather_residual)
                        if bm is not None else state.gather_residual)
                q, s, gather_res = _int8_encode(
                    (new_p_mine - p_mine) + gres)
                q_all = comm.all_gather(q, "data", tiled=True,
                                        label="overlap_delta_gather_int8",
                                        scale=comm_scale)
                s_all = comm.all_gather(s[None], "data", tiled=True,
                                        label="overlap_delta_scale_gather",
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
                flat_new = comm.all_gather(new_p_mine, "data", tiled=True,
                                           label="overlap_param_gather",
                                           scale=comm_scale)
                if bm is not None:
                    vec_new = _bucket_slices(bm, flat_new)
            if bm is None:
                new_params = unravel(
                    flat_new[:total].astype(raw_flat.dtype))
            else:
                new_params = _scatter_buckets(bm, vec_new, params)
        else:                       # replicated update
            gres = (jnp.concatenate(state.gather_residual)
                    if ef and bm is not None else state.gather_residual
                    if ef else None)
            if hier:
                if wire_dcn == "int8_ef":
                    q, s, gather_res = _int8_encode(g_mine + gres)
                    q_all = comm.all_gather(
                        q, "dcn", tiled=True,
                        label="overlap_grad_gather_int8",
                        scale=comm_scale)
                    s_all = comm.all_gather(
                        s[None], "dcn", tiled=True,
                        label="overlap_grad_scale_gather",
                        scale=comm_scale)
                    super_g = (jnp.repeat(s_all, local)
                               * q_all.astype(jnp.float32))
                elif wire_dcn == "bf16":
                    super_g = comm.all_gather(
                        g_mine.astype(jnp.bfloat16), "dcn", tiled=True,
                        label="overlap_grad_gather_dcn_bf16",
                        scale=comm_scale).astype(jnp.float32)
                else:
                    super_g = comm.all_gather(
                        g_mine, "dcn", tiled=True,
                        label="overlap_grad_gather_dcn",
                        scale=comm_scale)
                if wire_ici == "bf16":
                    flat_g = comm.all_gather(
                        super_g.astype(jnp.bfloat16), "data", tiled=True,
                        label="overlap_grad_gather_ici_bf16",
                        scale=comm_scale).astype(jnp.float32)
                else:
                    flat_g = comm.all_gather(
                        super_g, "data", tiled=True,
                        label="overlap_grad_gather_ici",
                        scale=comm_scale)
            elif wire == "int8_ef":
                q, s, gather_res = _int8_encode(g_mine + gres)
                q_all = comm.all_gather(q, "data", tiled=True,
                                        label="overlap_grad_gather_int8",
                                        scale=comm_scale)
                s_all = comm.all_gather(s[None], "data", tiled=True,
                                        label="overlap_grad_scale_gather",
                                        scale=comm_scale)
                flat_g = (jnp.repeat(s_all, local)
                          * q_all.astype(jnp.float32))
            elif wire == "bf16":
                flat_g = comm.all_gather(
                    g_mine.astype(jnp.bfloat16), "data", tiled=True,
                    label="overlap_grad_gather_bf16",
                    scale=comm_scale).astype(jnp.float32)
            else:
                flat_g = comm.all_gather(g_mine, "data", tiled=True,
                                         label="overlap_grad_gather",
                                         scale=comm_scale)
            if bm is None:
                grads = unravel(flat_g[:total].astype(raw_flat.dtype))
            else:
                # Every gathered stack in this branch is rank-major
                # [ranks, local] in ownership order — lead=1 extraction.
                grads = _scatter_buckets(bm, _bucket_slices(bm, flat_g),
                                         params)
            new_params, opt_state = apply_optimizer(
                optimizer, grads, state.opt_state, params)
        summary = None
        if numerics is not None:
            # Grad stats: local microbatch-mean gradient (the summarizer
            # psum-agrees them over the data axes); update stats: the
            # ATTEMPTED update — under guard_nonfinite a skipped step
            # still reports the norms of the update it refused, the
            # attribution a postmortem needs.
            summary = numerics.summarize(
                params, jax.tree.map(lambda x: x / M, gacc), new_params)
        step = state.step + 1
        if ef:
            if bm is not None:
                # Per-bucket storage: each bucket's stack is a contiguous
                # ordered-coordinate range (the reshard_state contract).
                ring_res = tuple(r[None] for r in ring_res)
                gather_res = tuple(
                    gather_res[bm.offsets[b]:bm.offsets[b] + bm.sizes[b]]
                    for b in range(B))
            else:
                ring_res = ring_res[None]
            new_state = OverlapEFState(new_params, opt_state, step,
                                       ring_res, gather_res)
        else:
            new_state = TrainState(new_params, opt_state, step)
        if guard_nonfinite:
            # Per-shard verdicts CAN disagree (each shard owns a different
            # slice of the reduced gradient), so the skip must be
            # psum-agreed before anyone applies state — the zero1 guard's
            # rule, extended over both axes of the hierarchical mesh.
            ok = jnp.isfinite(loss) & jnp.all(jnp.isfinite(g_mine))
            oki = comm.psum(ok.astype(jnp.int32), "data",
                            label="overlap_guard_verdict",
                            scale=comm_scale)
            if hier:
                oki = comm.psum(oki, "dcn",
                                label="overlap_guard_verdict_dcn",
                                scale=comm_scale)
            ok = oki == n
            # Select-back the WHOLE state (EF residuals included): a
            # skipped step is a true no-op, and the residuals must not
            # absorb a rejected step's quantization error.
            new_state = jax.tree.map(lambda a, b: jnp.where(ok, a, b),
                                     new_state, state)
            new_state = new_state._replace(
                step=state.step + ok.astype(state.step.dtype))
        return new_state, ((loss, summary) if summary is not None
                           else loss)

    return local_step


def make_overlap_step(loss_fn: Callable,
                      optimizer: optax.GradientTransformation,
                      mesh: Mesh, params, *, microbatches: int = 1,
                      wire="fp32", aggregation: str = "gradient",
                      comm_buckets: int = 1,
                      guard_nonfinite: bool = False, numerics=None):
    """Per-step overlapped+compressed gradient-sync driver: ``step(state,
    batch) -> (state, loss)`` over a ``[B, T]`` batch sharded over the
    data-parallel world. Returns ``(state, step_fn)``; the state is an
    ``OverlapEFState`` when any tier runs ``int8_ef`` (EF residuals in the
    tree), a plain TrainState otherwise — with ZeRO-1-sharded moments when
    ``aggregation="zero1"``.

    ``wire``: a format string runs the flat data-axis ring (PR 10); the
    per-axis dict ``{"ici": "fp32"|"bf16", "dcn":
    "fp32"|"bf16"|"int8_ef"}`` runs the TWO-LEVEL reduction on a
    hierarchical mesh (``hier_data_mesh``): full-precision reduce-scatter
    within each ICI island, the compressed exchange across the DCN axis
    only, then the intra-island gather. ``comm_buckets > 1`` turns on the
    bucketed backward — per-bucket ring dispatch in VJP emission order,
    so the first hop starts before the full gradient materializes (the
    semantics and invariants in ``_make_overlap_local_step``; structural
    proof via ``ring_overlap_evidence``). ``guard_nonfinite`` fuses the
    psum-agreed in-jit skip; ``numerics`` turns on the in-jit run-health
    summary."""
    (state, specs, dpart, n, pad, local, total, hier_shape,
     bm) = _overlap_setup(mesh, params, optimizer, wire, aggregation,
                          comm_buckets)
    local_step = _make_overlap_local_step(
        loss_fn, optimizer, n, pad, local, total, microbatches=microbatches,
        wire=wire, aggregation=aggregation, hier_shape=hier_shape,
        bucket_map=bm, guard_nonfinite=guard_nonfinite, numerics=numerics)
    sharded = shard_map(
        local_step, mesh=mesh,
        in_specs=(specs, P(dpart)), out_specs=(specs, P()),
        check_vma=False)
    return state, jax.jit(sharded, donate_argnums=(0,))


def make_overlap_multi_step(loss_fn: Callable,
                            optimizer: optax.GradientTransformation,
                            mesh: Mesh, params, *, microbatches: int = 1,
                            wire="fp32", aggregation: str = "gradient",
                            comm_buckets: int = 1,
                            guard_nonfinite: bool = False, numerics=None):
    """The overlapped+compressed driver inside the K-step scan:
    ``step(state, window) -> (state, losses)`` with ``window`` a
    ``[K, n_shards·B, T]`` batch window (``dp.shard_batch_window``) run in
    ONE compiled, donated dispatch. The scanned body IS
    ``make_overlap_step``'s body, so the loss sequence and final state are
    bitwise-identical to K per-step calls at any K and M (pinned in
    tests/test_compress.py) — and the int8 EF residuals ride the scan
    carry, so error feedback is exact across fused steps and chunk-edge
    checkpoints. ``wire`` accepts the same per-axis dict as
    ``make_overlap_step`` for the two-level hierarchical path, and
    ``guard_nonfinite``/``numerics`` ride the scanned body unchanged (the
    numerics summary comes back stacked [K], exactly like
    ``dp.make_multi_step``'s). ``comm_buckets`` composes: the per-bucket
    EF residual tuples ride the scan carry like the legacy arrays, so
    K-scanned bucketed dispatch stays bitwise-equal to K per-step calls
    at any K, M and bucket count."""
    (state, specs, dpart, n, pad, local, total, hier_shape,
     bm) = _overlap_setup(mesh, params, optimizer, wire, aggregation,
                          comm_buckets)

    def multi(state, window):
        local_step = _make_overlap_local_step(
            loss_fn, optimizer, n, pad, local, total,
            microbatches=microbatches, wire=wire, aggregation=aggregation,
            comm_scale=window.shape[0], hier_shape=hier_shape,
            bucket_map=bm, guard_nonfinite=guard_nonfinite,
            numerics=numerics)
        return lax.scan(local_step, state, window)

    sharded = shard_map(
        multi, mesh=mesh,
        in_specs=(specs, P(None, dpart)), out_specs=(specs, P()),
        check_vma=False)
    return state, jax.jit(sharded, donate_argnums=(0,))
